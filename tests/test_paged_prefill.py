"""The chunk attention kernel (`paged_prefill`, ops/pallas/paged_attention.py),
interpreted, against the gather path (`_gather_attention`, the CPU path and
the oracle) on the same pools: every way a chunk's query blocks and page
trips can fall across a row, a window, a ring table and a padded tail."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.ops.pallas import paged_attention as kernel
from llm_training_tpu.ops.paged_attention import paged_cached_attention
from llm_training_tpu.serve.paged_cache import window_page_budget

# rows of unequal length: one that starts at 0, two that start inside a page
_CASE = dict(
    lengths=(0, 21, 37), seq=20, kv_heads=2, group=4, head_dim=16, page=8, pages=12,
    window=None, cap=None, ring=False, pad=0, layers=None, layer=None,
    tile_rows=32, trip_tokens=16, dtype="float32",
)


def _ring_tables(blocks, lengths, seq, page, window, budget):
    """A window group's tables: logical page `p` in slot `p % budget`, for
    the pages from the window of the chunk's first query to the chunk's last
    token; every other slot names the trash block."""
    ring = np.zeros((len(lengths), budget), np.int32)
    for row, length in enumerate(lengths):
        first = max(0, length - window + 1) // page
        pages = np.arange(first, (length + seq - 1) // page + 1)
        ring[row, pages % budget] = blocks[row, pages]
    return ring


@pytest.mark.parametrize("case", [
    pytest.param({}, id="plain-table-unequal-rows"),
    pytest.param(dict(window=10), id="window-binds"),
    pytest.param(dict(window=200), id="window-does-not-bind"),
    pytest.param(dict(window=16, ring=True, seq=8, lengths=(0, 3, 21)), id="ring-before-its-wrap"),
    pytest.param(dict(window=16, ring=True, seq=8, lengths=(29, 44, 85)), id="ring-past-its-first-wrap"),
    pytest.param(dict(window=16, ring=True, lengths=(0, 21, 70), tile_rows=16),
                 id="ring-chunk-longer-than-the-window"),
    pytest.param(dict(pad=7), id="padded-tail"),
    pytest.param(dict(pad=7, window=10), id="padded-tail-window"),
    pytest.param(dict(group=1, kv_heads=3), id="group1"),
    pytest.param(dict(group=8, kv_heads=1, tile_rows=64), id="group8"),
    pytest.param(dict(cap=5.0), id="soft-cap"),
    pytest.param(dict(layers=3, layer=1), id="layer-stacked-pool"),
    pytest.param(dict(layers=3, layer="traced", window=16, ring=True, seq=8, lengths=(29, 44, 85)),
                 id="layer-stacked-ring-traced-index"),
    pytest.param(dict(lengths=(5,), seq=40, trip_tokens=8, tile_rows=64), id="one-page-a-trip"),
    pytest.param(dict(lengths=(0, 40), seq=24, trip_tokens=96, tile_rows=1024),
                 id="one-block-one-trip"),
    # the cells' tile (page 16, head_dim 128, bf16 pool and queries) at
    # Trinity's heads, against the gather path computed in float32
    pytest.param(dict(lengths=(0, 50, 95), seq=48, kv_heads=4, group=8, page=16, head_dim=128,
                      pages=10, dtype="bfloat16", tile_rows=128, trip_tokens=32, window=64),
                 id="bf16-page16-dim128-group8"),
])
def test_paged_prefill_matches_the_gather_path(case, monkeypatch):
    case = {**_CASE, **case}
    monkeypatch.setattr(kernel, "_PREFILL_TILE_ROWS", case["tile_rows"])
    monkeypatch.setattr(kernel, "_PREFILL_TRIP_TOKENS", case["trip_tokens"])
    kv_heads, group, dim, page = case["kv_heads"], case["group"], case["head_dim"], case["page"]
    seq, pages, window, dtype = case["seq"], case["pages"], case["window"], jnp.dtype(case["dtype"])
    lengths, batch = np.asarray(case["lengths"]), len(case["lengths"])
    rng = np.random.default_rng(0)
    per_layer = 1 + batch * pages
    blocks = rng.permutation(np.arange(1, per_layer)).reshape(batch, pages)
    tables = blocks
    if case["ring"]:
        budget = window_page_budget(window, seq, page, pages)
        assert budget < pages
        tables = _ring_tables(blocks, lengths, seq, page, window, budget)
    stack = () if case["layers"] is None else (case["layers"],)
    pool = jnp.asarray(rng.normal(size=(2, *stack, per_layer, kv_heads, page, dim)), dtype)
    q = jnp.asarray(rng.normal(size=(batch, seq, kv_heads * group, dim)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(batch, seq, kv_heads, dim)), dtype) for _ in range(2))
    segment_ids = np.ones((batch, seq), np.int32)
    segment_ids[:, seq - case["pad"]:] = 0

    def attend(impl, layer, q, k, v, pool):
        if case["layer"] == "traced":
            layer = jnp.asarray(layer)
        return paged_cached_attention(
            q, k, v, (pool[0], pool[1]), jnp.asarray(lengths, jnp.int32),
            jnp.asarray(tables, jnp.int32), layer=layer, segment_ids=jnp.asarray(segment_ids),
            sliding_window=window, logits_soft_cap=case["cap"], impl=impl, ring=case["ring"],
        )

    layer = None if case["layers"] is None else 1
    got, got_pool = jax.jit(attend, static_argnums=0)("pallas", layer, q, k, v, pool)
    want, want_pool = jax.jit(attend, static_argnums=0)(
        "xla", layer, *(x.astype(jnp.float32) for x in (q, k, v, pool))
    )
    assert got.dtype == dtype and got.shape == q.shape
    tol = 2e-5 if dtype == jnp.float32 else 1e-2  # the bf16 output's own rounding
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=tol, atol=tol)
    for mine, theirs in zip(got_pool, want_pool):
        np.testing.assert_array_equal(np.asarray(mine, np.float32), np.asarray(theirs))
    if case["pad"]:
        assert not np.asarray(got, np.float32)[:, seq - case["pad"]:].any()  # exactly 0
        assert np.asarray(got, np.float32)[:, : seq - case["pad"]].any()


@pytest.mark.parametrize("seq,group,expect", [
    (512, 8, 128),   # Trinity, Solar: 1,024 rows a kv head a block
    (512, 4, 256),   # Phi-3
    (512, 1, 512),   # OLMoE: the whole chunk
    (512, 3, 256),   # a group that does not divide the rows: equal blocks
    (5, 2, 16),      # a short chunk is one block of whole tiles
    (40, 32, 32),
])
def test_query_block_follows_the_group(seq, group, expect):
    block = kernel.query_block(seq, group)
    assert block == expect and block % 16 == 0
    assert -(-seq // block) * block < seq + block


@pytest.mark.parametrize("kv_heads,page,head_dim,itemsize,table,expect", [
    (4, 16, 128, 2, 800, 64),    # Trinity's global group: 1,024 tokens a trip
    (4, 16, 128, 2, 161, 64),    # its window group's ring
    (10, 16, 128, 2, 96, 64),    # Phi-3
    (16, 16, 128, 2, 96, 64),    # OLMoE: the scratch's 16 MiB, exactly
    (8, 16, 128, 2, 192, 64),    # Solar
    (8, 16, 128, 2, 12, 8),      # a table narrower than a trip: whole runs of 128 tokens
    (8, 16, 128, 2, 5, 5),       # narrower than one run: the whole table
    (8, 128, 128, 2, 64, 8),     # pages of 128 tokens
    (24, 16, 256, 4, 800, 8),    # the scratch's bytes bind before the tokens do: 10 pages, one run
    (64, 16, 256, 4, 800, 4),    # less than a run
])
def test_chunk_pages_per_trip_follows_shapes(kv_heads, page, head_dim, itemsize, table, expect):
    n = kernel.chunk_pages_per_trip(kv_heads, page, head_dim, itemsize, table)
    assert n == expect and 1 <= n <= table
    assert 4 * n * kv_heads * page * head_dim * itemsize <= kernel._PREFILL_KV_SCRATCH_BYTES


def _avals(jaxpr):
    """Every value of a jaxpr, those of the jaxprs inside it too (a kernel's
    body, a loop's, a shard_map's)."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for param in eqn.params.values():
            for inner in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _avals(inner)


@pytest.mark.parametrize("impl,wide", [("pallas", False), ("xla", True)])
def test_a_chunk_in_the_kernel_makes_nothing_as_wide_as_the_table(impl, wide):
    """`paged_cached_attention(seq=512)` at a 12,800-token table: the gather
    path's scores are float32 `[.., 512, 12800]`; the kernel's program holds
    no float32 value with the table's width in tokens for an axis, in the
    kernel or around it."""
    heads, kv_heads, dim, page, pages, seq = 8, 2, 128, 16, 800, 512
    pool = jax.ShapeDtypeStruct((pages + 1, kv_heads, page, dim), jnp.bfloat16)
    chunk = lambda h: jax.ShapeDtypeStruct((1, seq, h, dim), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, pk, pv, lens, tables, seg: paged_cached_attention(
            q, k, v, (pk, pv), lens, tables, segment_ids=seg, impl=impl
        )
    )(
        chunk(heads), chunk(kv_heads), chunk(kv_heads), pool, pool,
        jax.ShapeDtypeStruct((1,), jnp.int32), jax.ShapeDtypeStruct((1, pages), jnp.int32),
        jax.ShapeDtypeStruct((1, seq), jnp.int32),
    )
    table_wide = [
        aval for aval in _avals(jaxpr.jaxpr)
        if getattr(aval, "dtype", None) == jnp.float32 and pages * page in aval.shape
    ]
    assert bool(table_wide) == wide, table_wide
    # nor a gathered copy of the row's pages, in any dtype
    gathered = [
        aval for aval in _avals(jaxpr.jaxpr)
        if pages * page in getattr(aval, "shape", ()) or getattr(aval, "shape", ())[:2] == (1, pages)
        and len(aval.shape) > 2
    ]
    assert bool(gathered) == wide, gathered
