"""The latent chunk attention kernel (`mla_prefill`, ops/pallas/mla_prefill.py),
interpreted, against the XLA path (`attend_rows(absorbed=False)`, the CPU path
and the oracle) on the same pool: every way a chunk's query blocks, head
blocks and page trips can fall across unequal rows, a padded tail and a layer
stack, at small widths and at the two serve cells' tiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.ops.latent_attention import paged_latent_attention
from llm_training_tpu.ops.pallas import mla_prefill as kernel
from tests.test_paged_prefill import _avals

# rows of unequal length: one that starts at 0, two that start inside a page
_CASE = dict(
    lengths=(0, 21, 37), seq=20, heads=4, latent=64, nope=32, rope=16, v=32, width=128,
    page=8, pages=12, pad=0, layers=None, layer=None,
    trip_tokens=16, block_queries=512, head_bytes=None, together=2, dtype="float32",
)
# the serve cells' tile: pages of 16 rows of 640, latents of 512, heads of 128 + 64 / 128
_CELL = dict(latent=512, nope=128, rope=64, v=128, width=640, page=16, dtype="bfloat16")


@pytest.mark.parametrize("case", [
    pytest.param({}, id="chunk-starts-at-0-and-mid-page"),
    pytest.param(dict(lengths=(85, 3), pages=16), id="several-trips-deep"),
    pytest.param(dict(lengths=(0, 64), seq=16, trip_tokens=16), id="chunk-is-its-own-trip"),
    pytest.param(dict(pad=7), id="padded-tail"),
    pytest.param(dict(pad=7, lengths=(85, 3), pages=16), id="padded-tail-several-trips"),
    pytest.param(dict(layers=3, layer=1), id="layer-stacked-pool"),
    pytest.param(dict(layers=3, layer="traced", lengths=(29, 44, 85), pages=16),
                 id="layer-stacked-traced-index"),
    pytest.param(dict(lengths=(5,), seq=40, trip_tokens=8), id="one-page-a-trip"),
    pytest.param(dict(lengths=(0, 40), seq=24, trip_tokens=96), id="one-trip"),
    pytest.param(dict(lengths=(5, 30), seq=40, block_queries=16, pages=16), id="three-query-blocks"),
    pytest.param(dict(heads=8, head_bytes=4, seq=24), id="two-head-blocks"),
    pytest.param(dict(heads=3, together=2), id="heads-no-pair-divides"),
    pytest.param(dict(heads=4, together=1), id="a-head-at-a-time"),
    pytest.param(dict(lengths=(90,), seq=20, pages=12), id="chunk-runs-past-the-table"),
    pytest.param(dict(_CELL, heads=64, lengths=(0, 50), seq=32, pages=8, trip_tokens=64),
                 id="bf16-longcat-tile-64-heads"),
    pytest.param(dict(_CELL, heads=128, lengths=(50,), seq=32, pages=8, trip_tokens=64),
                 id="bf16-pangu-tile-128-heads"),
])
def test_mla_prefill_matches_the_xla_path(case, monkeypatch):
    case = {**_CASE, **case}
    monkeypatch.setattr(kernel, "_TRIP_TOKENS", case["trip_tokens"])
    monkeypatch.setattr(kernel, "_BLOCK_QUERIES", case["block_queries"])
    monkeypatch.setattr(kernel, "_HEAD_UNROLL", case["together"])
    heads, latent, nope, rope, v = (case[k] for k in ("heads", "latent", "nope", "rope", "v"))
    width, page, pages, seq = case["width"], case["page"], case["pages"], case["seq"]
    dtype = jnp.dtype(case["dtype"])
    if case["head_bytes"] is not None:
        # room for `head_bytes` heads of a grid step, as `head_block` counts a head
        block_q = kernel.query_block(seq)
        a_head = 2 * dtype.itemsize * (latent * (nope + v) + block_q * (nope + width - latent + v)) \
            + 4 * block_q * (v + 2 * 128)
        monkeypatch.setattr(kernel, "_HEAD_BLOCK_BYTES", case["head_bytes"] * a_head)
        assert kernel.head_block(heads, block_q, latent, nope, width - latent, v, dtype.itemsize) \
            == case["head_bytes"] < heads
    lengths, batch = np.asarray(case["lengths"]), len(case["lengths"])
    rng = np.random.default_rng(0)
    per_layer = 1 + batch * pages
    tables = rng.permutation(np.arange(1, per_layer)).reshape(batch, pages)
    stack = () if case["layers"] is None else (case["layers"],)
    pool = rng.normal(size=(*stack, per_layer, 1, page, width))
    pool[..., latent + rope:] = 0  # a stored row is `[c_kv | k_r | zeros]`
    pool = jnp.asarray(pool, dtype)
    q_nope = jnp.asarray(rng.normal(size=(batch, seq, heads, nope)), dtype)
    q_rope = jnp.asarray(rng.normal(size=(batch, seq, heads, rope)), dtype)
    row = rng.normal(size=(batch, seq, width))
    row[..., latent + rope:] = 0
    row = jnp.asarray(row, dtype)
    w_kvb = jnp.asarray(rng.normal(size=(latent, heads, nope + v)) * latent ** -0.5, dtype)
    segment_ids = np.ones((batch, seq), np.int32)
    segment_ids[:, seq - case["pad"]:] = 0

    def attend(impl, layer, q_nope, q_rope, row, w_kvb, pool):
        if case["layer"] == "traced":
            layer = jnp.asarray(layer)
        return paged_latent_attention(
            q_nope, q_rope, row, w_kvb, pool, jnp.asarray(lengths, jnp.int32),
            jnp.asarray(tables, jnp.int32), layer=layer, segment_ids=jnp.asarray(segment_ids),
            scale=(nope + rope) ** -0.5, impl=impl,
        )

    layer = None if case["layers"] is None else 1
    got, got_pool = jax.jit(attend, static_argnums=0)("pallas", layer, q_nope, q_rope, row, w_kvb, pool)
    want, want_pool = jax.jit(attend, static_argnums=0)("xla", layer, q_nope, q_rope, row, w_kvb, pool)
    assert got.dtype == dtype and got.shape == (batch, seq, heads, v)
    # both round the expanded keys and values and the probabilities to the
    # queries' dtype; what differs is the order a row's trips are summed in
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_array_equal(np.asarray(got_pool, np.float32), np.asarray(want_pool, np.float32))
    if case["pad"]:
        assert not np.asarray(got, np.float32)[:, seq - case["pad"]:].any()  # exactly 0
        assert np.asarray(got, np.float32)[:, : seq - case["pad"]].any()


@pytest.mark.parametrize("heads,block_q,expect", [
    (128, 512, 16),   # openPangu's cell: 16 grid steps a row
    (64, 512, 16),    # LongCat's: 8
    (32, 512, 16),    # a tensor-parallel half of LongCat's heads
    (16, 512, 16),    # all of them: the blocks are the arrays
    (128, 128, 32),   # a short chunk leaves room for more heads
    (128, 1024, 8),   # a block of 1,024 queries for fewer
    (24, 512, 12),    # the most that divide
    (7, 512, 7),      # a prime count under the room: all
])
def test_head_block_follows_the_shapes(heads, block_q, expect):
    n = kernel.head_block(heads, block_q, 512, 128, 128, 128, 2)
    assert n == expect and heads % n == 0
    a_head = 2 * 2 * (512 * 256 + block_q * 384) + 4 * block_q * 384
    assert n * a_head <= kernel._HEAD_BLOCK_BYTES


@pytest.mark.parametrize("heads,nope,tail,v,itemsize,expect", [
    (8, 32, 64, 32, 4, 8),    # widths under a lane tile: 4 heads abreast are whole tiles, all 8 fit
    (6, 32, 64, 32, 4, 6),    # no divisor makes whole tiles: all heads, whose blocks are the arrays
    (128, 192, 128, 128, 2, 8),   # a wider key leaves room for fewer heads
    (128, 128, 128, 128, 4, 8),   # float32 queries and weights: half the heads
])
def test_head_block_keeps_whole_lane_tiles(heads, nope, tail, v, itemsize, expect):
    n = kernel.head_block(heads, 512, 512, nope, tail, v, itemsize)
    assert n == expect
    assert n == heads or not any(n * width % 128 for width in (nope, tail, v))


@pytest.mark.parametrize("page,table,expect", [
    (16, 544, 32),   # openPangu's cell: 512 tokens a trip
    (16, 352, 32),   # LongCat's
    (16, 12, 8),     # a table narrower than a trip: whole runs of 128 tokens
    (16, 5, 5),      # narrower than one run: the whole table
    (128, 64, 4),    # pages of 128 tokens
    (8, 100, 64),
    (1024, 9, 1),    # a page wider than a trip: one
])
def test_chunk_latent_pages_per_trip_follows_shapes(page, table, expect):
    n = kernel.chunk_latent_pages_per_trip(page, table)
    assert n == expect and 1 <= n <= table


@pytest.mark.parametrize("seq,expect", [(512, 512), (128, 128), (5, 16), (1024, 512), (1100, 368), (40, 48)])
def test_query_block_is_the_chunk_whole_up_to_512(seq, expect):
    block = kernel.query_block(seq)
    assert block == expect and block % 16 == 0
    assert -(-seq // block) * block < seq + block


@pytest.mark.parametrize("heads", [64, 128])
@pytest.mark.parametrize("impl,wide", [("pallas", False), ("xla", True)])
def test_a_chunk_in_the_kernel_holds_no_scores_of_all_heads(impl, wide, heads):
    """`paged_latent_attention(seq=512)` at an 8,704-token table: the XLA
    path's program holds a trip's float32 scores `[1, heads, 512, 512]` and
    the expanded keys and values of all heads; the kernel's holds no float32
    value that large, in the kernel or around it, and nothing with an axis of
    more cached tokens than a trip."""
    latent, nope, rope, v, page, pages, seq = 512, 128, 64, 128, 16, 544, 512
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q_nope, q_rope, row, w_kvb, pool, lens, tables, seg: paged_latent_attention(
            q_nope, q_rope, row, w_kvb, pool, lens, tables, segment_ids=seg,
            scale=192 ** -0.5, impl=impl,
        )
    )(
        shape(1, seq, heads, nope), shape(1, seq, heads, rope), shape(1, seq, 640),
        shape(latent, heads, nope + v), shape(pages + 1, 1, page, 640),
        jax.ShapeDtypeStruct((1,), jnp.int32), jax.ShapeDtypeStruct((1, pages), jnp.int32),
        jax.ShapeDtypeStruct((1, seq), jnp.int32),
    )
    avals = [aval for aval in _avals(jaxpr.jaxpr) if hasattr(aval, "shape")]
    scores = [
        aval for aval in avals
        if aval.dtype == jnp.float32 and int(np.prod(aval.shape)) >= heads * seq * 512
    ]
    assert bool(scores) == wide, scores
    if wide:
        assert (1, heads, seq, 512) in {aval.shape for aval in scores}
    else:
        # the largest float32 values are the accumulator of a block of heads
        # and one head's `[512, 512]` tile
        assert max(
            int(np.prod(aval.shape)) for aval in avals if aval.dtype == jnp.float32
        ) <= 16 * seq * 128
        assert not [aval for aval in avals if any(d > 512 and d % page == 0 and d >= 1024 for d in aval.shape[-2:-1])]
    # the expanded keys and values of every head of a trip, `[1, 512, heads, 256]`
    expanded = [aval for aval in avals if aval.shape[-2:] == (heads, nope + v) and aval.shape != (latent, heads, nope + v)]
    assert bool(expanded) == wide, expanded


def test_the_kernel_refuses_untileable_shapes_when_compiled():
    """A width Mosaic cannot tile raises on the chip's path, and is not
    routed to the XLA path."""
    q_nope, q_rope = jnp.zeros((1, 8, 2, 32)), jnp.zeros((1, 8, 2, 16))
    pool = jnp.zeros((3, 1, 8, 128))
    with pytest.raises(ValueError, match="mla_prefill kernel .* latent 64"):
        kernel.mla_prefill_attention(
            q_nope, q_rope, jnp.zeros((64, 2, 64)), pool, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), scale=1.0, interpret=False,
        )
    with pytest.raises(ValueError, match="do not match"):
        kernel.mla_prefill_attention(
            q_nope, q_rope, jnp.zeros((64, 4, 64)), pool, jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), scale=1.0,
        )
