"""Pallas flash attention kernel vs the XLA reference path.

The kernel runs in interpreter mode on CPU (the wrapper auto-selects), so
these tests exercise the real kernel logic — tiling, online softmax, block
skipping, GQA grid folding, the custom VJP — without TPU hardware. The
reference validated its attention only implicitly through flash-attn's own
tests (SURVEY.md §4); here packed/causal/windowed parity is asserted
directly against the einsum path.
"""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.ops.attention import dot_product_attention
from llm_training_tpu.ops.pallas.flash_attention import flash_attention


def _rand(rng, shape):
    return rng.standard_normal(shape).astype(np.float32)


def _make_qkv(rng, batch, sq, skv, hq, hkv, d):
    return (
        jnp.asarray(_rand(rng, (batch, sq, hq, d))),
        jnp.asarray(_rand(rng, (batch, skv, hkv, d))),
        jnp.asarray(_rand(rng, (batch, skv, hkv, d))),
    )


def _packed_segments(rng, batch, seq, max_docs=4):
    """Random packed segment ids: 1..N runs then 0-padding."""
    rows = []
    for _ in range(batch):
        cuts = np.sort(rng.choice(np.arange(1, seq), size=max_docs - 1, replace=False))
        row, seg = [], 1
        prev = 0
        for c in list(cuts) + [seq - 2]:
            if c <= prev:
                continue
            row += [seg] * (c - prev)
            seg += 1
            prev = c
        row += [0] * (seq - len(row))
        rows.append(row)
    return jnp.asarray(rows, jnp.int32)


CASES = [
    # (name, hq, hkv, sliding_window, soft_cap, packed)
    ("causal", 4, 4, None, None, False),
    ("gqa", 4, 2, None, None, False),
    ("packed_gqa", 4, 2, None, None, True),
    ("window", 2, 2, 37, None, False),
    ("softcap", 2, 2, None, 20.0, False),
    ("everything", 4, 2, 50, 30.0, True),
]


@pytest.mark.parametrize("name,hq,hkv,window,cap,packed", CASES, ids=[c[0] for c in CASES])
def test_forward_matches_xla(name, hq, hkv, window, cap, packed):
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    batch, seq, d = 2, 256, 32
    q, k, v = _make_qkv(rng, batch, seq, seq, hq, hkv, d)
    seg = _packed_segments(rng, batch, seq) if packed else None

    kwargs = dict(segment_ids=seg, causal=True, sliding_window=window, logits_soft_cap=cap)
    expected = dot_product_attention(q, k, v, impl="xla", **kwargs)
    got = flash_attention(q, k, v, block_q=128, block_k=128, **kwargs)
    np.testing.assert_allclose(got, expected, rtol=2e-3, atol=2e-3)


def test_packed_block_aligned_docs():
    """Document boundaries aligned to kv blocks: the DMA-elision index maps
    redirect segment-skipped tiles onto already-resident kv blocks, so the
    kernel's skip decision must come from the grid index, not the streamed
    segment ids. Two 256-token docs at block 128 put kv blocks wholly inside
    an earlier document — the exact layout the random cuts in
    `_packed_segments` never produce (r4 advisor repro: max abs error 2.5)."""
    rng = np.random.default_rng(7)
    batch, seq, h, d = 2, 512, 2, 32
    q, k, v = _make_qkv(rng, batch, seq, seq, h, h, d)
    seg = jnp.asarray(np.tile(np.repeat([1, 2], 256)[None], (batch, 1)), jnp.int32)
    expected = dot_product_attention(q, k, v, segment_ids=seg, causal=True, impl="xla")
    got = flash_attention(q, k, v, segment_ids=seg, causal=True, block_q=128, block_k=128)
    np.testing.assert_allclose(got, expected, rtol=2e-3, atol=2e-3)


def test_gradients_smoke_packed_aligned():
    """Fast (non-slow) gradient check so the default suite always traces the
    backward kernels — the r4 regression shipped because every gradient test
    was slow-marked. Block-aligned packing exercises the dq/dkv segment-skip
    gates too."""
    rng = np.random.default_rng(8)
    batch, seq, h, d = 1, 256, 2, 32
    q, k, v = _make_qkv(rng, batch, seq, seq, h, h, d)
    seg = jnp.asarray(np.repeat([1, 2], 128)[None], jnp.int32)
    cot = jnp.asarray(_rand(rng, (batch, seq, h, d)))

    gx = jax.jit(jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v, segment_ids=seg, impl="xla") * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gp = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, segment_ids=seg, block_q=128, block_k=128) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for a, b, name in zip(gx, gp, "qkv"):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


@pytest.mark.slow
def test_gradients_match_xla():
    rng = np.random.default_rng(0)
    batch, seq, hq, hkv, d = 1, 256, 4, 2, 32
    q, k, v = _make_qkv(rng, batch, seq, seq, hq, hkv, d)
    seg = _packed_segments(rng, batch, seq)
    cot = jnp.asarray(_rand(rng, (batch, seq, hq, d)))

    def loss(fn, q, k, v):
        return (fn(q, k, v) * cot).sum()

    def xla(q, k, v):
        return dot_product_attention(q, k, v, segment_ids=seg, impl="xla")

    def pallas(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg, block_q=128, block_k=128)

    gx = jax.jit(jax.grad(lambda *a: loss(xla, *a), argnums=(0, 1, 2)))(q, k, v)
    gp = jax.jit(jax.grad(lambda *a: loss(pallas, *a), argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gx, gp, "qkv"):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


@pytest.mark.slow
def test_gradients_match_xla_softcap_window():
    rng = np.random.default_rng(1)
    batch, seq, h, d = 1, 128, 2, 32
    q, k, v = _make_qkv(rng, batch, seq, seq, h, h, d)
    cot = jnp.asarray(_rand(rng, (batch, seq, h, d)))
    kw = dict(sliding_window=33, logits_soft_cap=25.0)

    gx = jax.jit(jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v, impl="xla", **kw) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gp = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, block_q=128, block_k=128, **kw) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for a, b, name in zip(gx, gp, "qkv"):
        np.testing.assert_allclose(b, a, rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


def test_unaligned_shapes_are_padded():
    """seq/head_dim not multiples of the lane width go through the padding
    path; result must still match the XLA path on the unpadded region."""
    rng = np.random.default_rng(2)
    batch, seq, h, d = 2, 200, 2, 24
    q, k, v = _make_qkv(rng, batch, seq, seq, h, h, d)
    expected = dot_product_attention(q, k, v, impl="xla")
    got = flash_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(got, expected, rtol=2e-3, atol=2e-3)


def test_cross_length_chunk_matches_slice():
    """Ring-attention chunk shape: q shorter than kv with q_offset."""
    rng = np.random.default_rng(3)
    seq, d = 256, 32
    q, k, v = _make_qkv(rng, 1, seq, seq, 2, 2, d)
    seg = _packed_segments(rng, 1, seq)

    full = flash_attention(q, k, v, segment_ids=seg, block_q=128, block_k=128)
    chunk = slice(128, 256)
    part = flash_attention(
        q[:, chunk], k, v,
        segment_ids=seg, q_segment_ids=seg[:, chunk], q_offset=128,
        block_q=128, block_k=128,
    )
    np.testing.assert_allclose(part, full[:, chunk], rtol=2e-3, atol=2e-3)


def test_fully_masked_rows_emit_zero():
    """Padding rows (segment 0) must produce exactly 0 output, not NaN —
    the invariant ring attention's combiner relies on."""
    rng = np.random.default_rng(4)
    q, k, v = _make_qkv(rng, 1, 128, 128, 2, 2, 32)
    seg = jnp.asarray([[1] * 64 + [0] * 64], jnp.int32)
    out = flash_attention(q, k, v, segment_ids=seg, block_q=128, block_k=128)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_array_equal(np.asarray(out[:, 64:]), 0.0)


def test_dispatch_uses_pallas_off_tpu():
    """`impl='pallas'` now runs the kernel (interpreted off-TPU) instead of
    raising, and agrees with the XLA path through the dispatcher."""
    rng = np.random.default_rng(5)
    q, k, v = _make_qkv(rng, 1, 128, 128, 2, 2, 32)
    got = dot_product_attention(q, k, v, impl="pallas")
    expected = dot_product_attention(q, k, v, impl="xla")
    np.testing.assert_allclose(got, expected, rtol=2e-3, atol=2e-3)


def test_bf16_inputs():
    rng = np.random.default_rng(6)
    q, k, v = _make_qkv(rng, 1, 128, 128, 2, 2, 32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = flash_attention(q, k, v, block_q=128, block_k=128)
    assert got.dtype == jnp.bfloat16
    expected = dot_product_attention(q, k, v, impl="xla")
    np.testing.assert_allclose(
        got.astype(np.float32), expected.astype(np.float32), rtol=5e-2, atol=5e-2
    )


@pytest.mark.parametrize("sliding_window", [None, 8])
def test_sinks_match_xla(sliding_window):
    """gpt-oss sink softmax in the kernel (denominator seeded with the sink
    mass) must match the einsum reference on outputs AND on every gradient
    including d_sinks."""
    from llm_training_tpu.ops.attention import dot_product_attention

    rng = np.random.default_rng(60)
    b, s, hq, hkv, d = 2, 32, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    sinks = jnp.asarray(rng.standard_normal(hq), jnp.float32)
    seg = jnp.asarray(
        np.concatenate([np.ones((b, s - 6)), np.full((b, 4), 2), np.zeros((b, 2))], 1),
        jnp.int32,
    )

    def loss(fn_impl):
        def f(q, k, v, sinks):
            out = dot_product_attention(
                q, k, v, segment_ids=seg, causal=True,
                sliding_window=sliding_window, sinks=sinks, impl=fn_impl,
            )
            return (out * jnp.arange(d)).sum(), out

        return jax.jit(jax.value_and_grad(lambda *a: f(*a)[0], argnums=(0, 1, 2, 3))), f

    (gx, fx), (gp, fp) = loss("xla"), loss("pallas")
    out_x, out_p = fx(q, k, v, sinks)[1], fp(q, k, v, sinks)[1]
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x), rtol=2e-5, atol=2e-5)

    (_, grads_x), (_, grads_p) = gx(q, k, v, sinks), gp(q, k, v, sinks)
    for name, a, b_ in zip(("dq", "dk", "dv", "d_sinks"), grads_x, grads_p):
        # d_sinks sums hundreds-magnitude row contributions that can cancel
        # to near zero — tolerate the accumulation-order noise
        np.testing.assert_allclose(
            np.asarray(b_), np.asarray(a), rtol=1e-4, atol=1e-3, err_msg=name
        )


@pytest.mark.parametrize("name,hq,hkv,window,cap,packed", CASES, ids=[c[0] for c in CASES])
def test_backward_matches_xla(name, hq, hkv, window, cap, packed):
    """Fast-tier grad parity vs the einsum path across the full config
    grid (causal / GQA / packed / sliding-window / softcap / everything) —
    the BENCH_r04 crash class (`_dq_kernel` arity at trace time) can never
    again reach hardware untraced, and dq/dk/dv stay numerically pinned.
    GQA cases (group 2) drive the dkv kernel's 4-D (bh_kv, nk, group, nq)
    grid."""
    rng = np.random.default_rng(zlib.crc32(("bwd" + name).encode()))
    batch, seq, d = 2, 256, 32
    q, k, v = _make_qkv(rng, batch, seq, seq, hq, hkv, d)
    seg = _packed_segments(rng, batch, seq) if packed else None
    cot = jnp.asarray(_rand(rng, (batch, seq, hq, d)))
    kwargs = dict(segment_ids=seg, causal=True, sliding_window=window, logits_soft_cap=cap)

    gx = jax.jit(jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v, impl="xla", **kwargs) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gp = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, block_q=128, block_k=128, **kwargs) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for a, b, grad_name in zip(gx, gp, "qkv"):
        np.testing.assert_allclose(b, a, rtol=3e-3, atol=3e-3, err_msg=f"d{grad_name}")


def test_backward_traces_with_resolved_blocks():
    """The exact r04 call path: NO explicit blocks, so the backward traces
    with tuning-layer-resolved tiles (table/default). A fwd/bwd kernel-arity
    or resolution regression fails here before any hardware round."""
    rng = np.random.default_rng(41)
    q, k, v = _make_qkv(rng, 1, 256, 256, 4, 2, 32)
    cot = jnp.asarray(_rand(rng, (1, 256, 4, 32)))

    gx = jax.jit(jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v, impl="xla", causal=True) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gp = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal=True) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for a, b, grad_name in zip(gx, gp, "qkv"):
        np.testing.assert_allclose(b, a, rtol=3e-3, atol=3e-3, err_msg=f"d{grad_name}")


def test_backward_independent_fwd_bwd_blocks():
    """fwd and bwd tiles are independent knobs; mixing them must be
    numerically invisible (same grads as uniform tiles)."""
    rng = np.random.default_rng(42)
    q, k, v = _make_qkv(rng, 1, 512, 512, 4, 2, 32)
    seg = jnp.asarray(np.repeat([1, 2], 256)[None], jnp.int32)
    cot = jnp.asarray(_rand(rng, (1, 512, 4, 32)))

    def grads(**blocks):
        return jax.jit(jax.grad(
            lambda q, k, v: (flash_attention(
                q, k, v, segment_ids=seg, causal=True, sliding_window=100, **blocks
            ) * cot).sum(),
            argnums=(0, 1, 2),
        ))(q, k, v)

    base = grads(block_q=128, block_k=128, bwd_block_q=128, bwd_block_k=128)
    mixed = grads(block_q=256, block_k=128, bwd_block_q=128, bwd_block_k=256)
    for a, b, grad_name in zip(base, mixed, "qkv"):
        np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5, err_msg=f"d{grad_name}")


def test_flash_bwd_flat_kernel_arity():
    """Direct flat-kernel call (the layer ring attention uses): the dq
    pallas_call hands the kernel 2 scalar-prefetch + 8 input refs + 1
    output + 1 scratch, the dkv call 2+8+2+2 on its 4-D grid — a parameter
    drift in either kernel body TypeErrors at trace time right here."""
    from llm_training_tpu.ops.pallas.flash_attention import (
        flash_bwd_flat, flash_fwd_flat,
    )

    rng = np.random.default_rng(43)
    batch, seq, hq, hkv, d = 2, 256, 4, 2, 64
    q = jnp.asarray(_rand(rng, (batch * hq, seq, d)))
    k = jnp.asarray(_rand(rng, (batch * hkv, seq, d)))
    v = jnp.asarray(_rand(rng, (batch * hkv, seq, d)))
    seg = jnp.asarray(np.tile(np.repeat([1, 2], seq // 2)[None], (batch, 1)), jnp.int32)
    kw = dict(num_q_heads=hq, num_kv_heads=hkv, scale=d**-0.5, causal=True,
              block_q=128, block_k=128, interpret=True)

    o, lse = flash_fwd_flat(q, k, v, seg, seg, **kw)
    do = jnp.asarray(_rand(rng, (batch * hq, seq, d)))
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)
    dq, dk, dv = flash_bwd_flat(q, k, v, seg, seg, do, lse, delta, **kw)
    assert dq.shape == q.shape and dk.shape == k.shape and dv.shape == v.shape
    for name, g in (("dq", dq), ("dk", dk), ("dv", dv)):
        assert np.isfinite(np.asarray(g)).all(), f"{name} has non-finite entries"


def test_backward_gqa_group4_dkv_grid():
    """Group-4 GQA: the dkv kernel's group axis is length 4, so its
    (g == ng-1) flush gate and q-head indexing get a non-trivial workout."""
    rng = np.random.default_rng(44)
    q, k, v = _make_qkv(rng, 1, 256, 256, 8, 2, 32)
    cot = jnp.asarray(_rand(rng, (1, 256, 8, 32)))
    kwargs = dict(causal=True, sliding_window=70)

    gx = jax.jit(jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v, impl="xla", **kwargs) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    gp = jax.jit(jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, block_q=128, block_k=128, **kwargs) * cot).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    for a, b, grad_name in zip(gx, gp, "qkv"):
        np.testing.assert_allclose(b, a, rtol=3e-3, atol=3e-3, err_msg=f"d{grad_name}")


@pytest.mark.parametrize("case", [
    # (seq, docs spec, window, gqa, block)  — layouts chosen to stress the
    # DMA-elision index maps: block-aligned boundaries, a doc spanning
    # blocks, windows cutting through doc boundaries, uneven GQA
    dict(seq=384, docs=[128, 128, 128], window=None, hq=4, hkv=1, blk=128),
    dict(seq=384, docs=[256, 128], window=64, hq=4, hkv=2, blk=128),
    dict(seq=512, docs=[128, 256, 128], window=96, hq=8, hkv=2, blk=128),
    dict(seq=512, docs=[384, 128], window=None, hq=2, hkv=2, blk=256),
    dict(seq=512, docs=[64, 192, 256], window=160, hq=4, hkv=4, blk=128),
])
def test_packed_layout_fuzz_fwd_and_grad(case):
    """Structured fuzz over packed layouts x windows x GQA x blocks for
    BOTH passes — the r4 regression (segment-skip on redirected tiles)
    shipped because only random unaligned cuts were tested."""
    rng = np.random.default_rng(zlib.crc32(str(sorted(case.items())).encode()))
    seq, hq, hkv, blk = case["seq"], case["hq"], case["hkv"], case["blk"]
    q, k, v = _make_qkv(rng, 1, seq, seq, hq, hkv, 32)
    seg_row = np.concatenate([
        np.full(n, i + 1) for i, n in enumerate(case["docs"])
    ])
    seg = jnp.asarray(seg_row[None], jnp.int32)
    cot = jnp.asarray(_rand(rng, (1, seq, hq, 32)))
    kw = dict(segment_ids=seg, causal=True, sliding_window=case["window"])

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                             * cot.astype(jnp.float32)).sum(),
            argnums=(0, 1, 2),
        ))

    vx, gx = loss(lambda q, k, v: dot_product_attention(q, k, v, impl="xla", **kw))(q, k, v)
    vp, gp = loss(lambda q, k, v: flash_attention(q, k, v, block_q=blk, block_k=blk, **kw))(q, k, v)
    np.testing.assert_allclose(float(vp), float(vx), rtol=2e-3, atol=1e-2)
    for a, b, name in zip(gx, gp, "qkv"):
        np.testing.assert_allclose(b, a, rtol=3e-3, atol=3e-3, err_msg=f"d{name}")


def test_flash_under_a_sharded_mesh_matches_xla(devices):
    """On a multi-device mesh `dot_product_attention` runs the kernel in a
    shard_map (batch over data/fsdp, heads over tensor) — GSPMD cannot
    partition a Mosaic kernel, which the TPU compiler refuses outright
    (tests/test_chip_compile.py holds that compile). Forward and gradients
    must equal the einsum path, packed segment ids included."""
    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(fsdp_size=4, tensor_parallel_size=2), devices)
    rng = np.random.default_rng(7)
    q, k, v = _make_qkv(rng, 4, 256, 256, 4, 2, 16)
    seg = _packed_segments(rng, 4, 256)

    def loss(impl):
        def fn(q, k, v):
            out = dot_product_attention(q, k, v, segment_ids=seg, impl=impl)
            return jnp.sum(out * jnp.cos(out)), out

        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True))

    with mesh:
        (_, out), grads = loss("pallas")(q, k, v)
    (_, ref_out), ref_grads = loss("xla")(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), rtol=2e-5, atol=2e-5)
    for got, ref in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-4)
