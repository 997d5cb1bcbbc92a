"""Pallas TPU flash attention with segment-id packing.

TPU-native replacement for the reference's flash-attn CUDA dispatch
(`ops/attention_op.py:538-654`): causal, GQA, sliding window, soft-cap, and
packed varlen via segment ids instead of unpad/cu_seqlens. The reference's
block-diagonal packed mask (`attention_op.py:305-314`) becomes a block-level
segment-id comparison inside the kernel; its `_upad_input`/`pad_input`
round-trip (`attention_op.py:415-485`) has no analogue — packed rows stay
dense and static-shaped, which is what XLA wants anyway.

Design (standard flash attention 2 tiling, TPU-shaped):
  forward: grid (batch*q_heads, q_blocks, kv_blocks), kv innermost
    ("arbitrary"), online-softmax state (m, l, acc) carried in VMEM scratch
    across kv iterations; returns O and the row logsumexp for the backward.
  backward dQ: same grid; recomputes P from (Q, K, LSE), accumulates
    dQ = sum_j dS_ij K_j in scratch.
  backward dK/dV: grid (batch*kv_heads, kv_blocks, gqa_group, q_blocks) —
    the GQA group axis is folded into the kernel grid so dK/dV accumulate
    over the query heads sharing a kv head without an XLA-level reduction.

Causal/sliding-window block skipping: fully-masked (q_block, kv_block) tiles
are skipped with `pl.when`, so causal attention does ~half the FLOPs and a
sliding-window run is linear in window size — the reason flash-attn varlen
wins in the reference, reproduced at the tile level.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_training_tpu.ops.pallas import resolve_interpret
from llm_training_tpu.ops.pallas.tuning import (
    SOURCE_ORDER,
    BlockChoice,
    bwd_env_override,
    fit_block,
    record_block_choice,
    resolve_block_sizes,
)

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128

# block sizes are resolved at CALL time by ops/pallas/tuning.py (explicit
# arg > FLASH_BLOCK_* env > config/tuning table > 1024 default) — never at
# import, so tests and the offline sweep can override without re-importing.
# The old import-time constants lived here; see tuning.DEFAULT_BLOCK for
# the v5e rationale behind the 1024x1024 fallback.


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _kv_bh_map(num_q_heads: int, num_kv_heads: int):
    """Flat q batch-head index -> flat kv batch-head index (GQA)."""
    group = num_q_heads // num_kv_heads

    def kv_bh(bh_idx):
        return (bh_idx // num_q_heads) * num_kv_heads + (bh_idx % num_q_heads) // group

    return kv_bh


def _q_bh_map(num_q_heads: int, num_kv_heads: int):
    """Flat kv batch-head index + group member -> flat q batch-head index."""
    group = num_q_heads // num_kv_heads

    def q_bh(bhk, g):
        return (bhk // num_kv_heads) * num_q_heads + (bhk % num_kv_heads) * group + g

    return q_bh


def _kv_clamp(
    block_q: int,
    block_k: int,
    q_offset: int,
    causal: bool,
    sliding_window: int | None,
    num_kv_blocks: int,
):
    """j -> clamped kv-block index for q-block i: position-skipped tiles map
    to the nearest VISITED kv block, so their BlockSpec index repeats and
    Pallas elides the k/v DMA entirely (the tile still dispatches, but
    `pl.when` skips its compute). At long causal sequences ~half the grid is
    skipped tiles; without the clamp each still streamed a k/v block."""
    if not causal and sliding_window is None:
        return lambda i, j: j

    def clamp(i, j):
        lo, hi = 0, num_kv_blocks - 1  # unset bounds stay array-wide
        if causal:
            # visit needs k_lo <= q_hi: j <= (q_hi) // block_k
            hi = (i * block_q + q_offset + block_q - 1) // block_k
        if sliding_window is not None:
            # visit needs q_lo - k_hi < w: j*bk + bk - 1 > q_lo - w
            lo = (
                i * block_q + q_offset - sliding_window - block_k + 1
            ) // block_k + 1
        # rows with an empty visited range (or a range outside the array)
        # may point anywhere in bounds — their compute is skipped regardless
        return jnp.clip(jnp.clip(j, lo, hi), 0, num_kv_blocks - 1)

    return clamp


def _q_clamp(
    block_q: int,
    block_k: int,
    q_offset: int,
    causal: bool,
    sliding_window: int | None,
    num_q_blocks: int,
):
    """i -> clamped q-block index for kv-block j (the dkv kernel's mirror of
    `_kv_clamp`)."""
    if not causal and sliding_window is None:
        return lambda j, i: i

    def clamp(j, i):
        lo, hi = 0, num_q_blocks - 1  # unset bounds stay array-wide
        if causal:
            # visit needs q_hi >= k_lo: i >= ceil((j*bk - off - bq + 1)/bq)
            lo = -((q_offset + block_q - 1 - j * block_k) // block_q)
        if sliding_window is not None:
            # visit needs q_lo - k_hi < w: i <= (k_hi + w - 1 - off) // bq
            hi = (
                j * block_k + block_k - 2 + sliding_window - q_offset
            ) // block_q
        return jnp.clip(jnp.clip(i, lo, hi), 0, num_q_blocks - 1)

    return clamp


def _segment_block_bounds(
    seg_q: jnp.ndarray, seg_kv: jnp.ndarray, block_q: int, block_k: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """[B, nq] int32 (lo, hi): the kv-block index range whose segment ids can
    intersect each q block. Feeds the kernels as SCALAR-PREFETCH operands so
    the BlockSpec index maps can clamp segment-skipped tiles onto an
    already-resident kv block — extending the DMA elision from
    position-skipped tiles to runtime packing. [min, max] of the
    intersecting set is a superset for ANY id pattern (conservative: a
    wrongly-included tile only streams, never mis-computes; the in-kernel
    masks stay authoritative). Blocks that are all padding (id 0) are
    treated as intersecting nothing."""
    batch = seg_q.shape[0]
    big = jnp.int32(2**30)
    qb = seg_q.reshape(batch, -1, block_q)
    kb = seg_kv.reshape(batch, -1, block_k)
    qmin = jnp.where(qb == 0, big, qb).min(-1)
    qmax = qb.max(-1)
    kmin = jnp.where(kb == 0, big, kb).min(-1)
    kmax = jnp.where(kb.max(-1) == 0, -1, kb.max(-1))
    nk = kb.shape[1]
    inter = (
        (qmin[..., None] <= kmax[:, None, :])
        & (kmin[:, None, :] <= qmax[..., None])
        & (qmax[..., None] > 0)
    )  # [B, nq, nk]
    any_j = inter.any(-1)
    lo = jnp.where(any_j, jnp.argmax(inter, axis=-1), 0)
    hi = jnp.where(any_j, nk - 1 - jnp.argmax(inter[..., ::-1], axis=-1), 0)
    return lo.astype(jnp.int32), hi.astype(jnp.int32)


def _bounded_idx(pos_clamp, heads_divisor: int):
    """Shared BlockSpec index clamp: static position clamp (`pos_clamp`),
    then the runtime segment clamp from the prefetched [B, n] bounds — tiles
    the kernel will visit are inside both ranges, so their index stays the
    identity; skipped tiles repeat an already-resident block and Pallas
    elides the DMA. `max(hi, lo)` guards the empty-range rows (their compute
    is skipped regardless). Args at call time: (b, a, x, lo, hi) where `a`
    indexes the bounds row and `x` is the streamed-axis grid index."""

    def idx(b, a, x, lo, hi):
        xx = pos_clamp(a, x)
        batch_i = b // heads_divisor
        return jnp.clip(xx, lo[batch_i, a], jnp.maximum(hi[batch_i, a], lo[batch_i, a]))

    return idx


def _resolve_flat_blocks(
    kind: str,
    sq: int,
    skv: int,
    head_dim: int,
    dtype,
    causal: bool,
    sliding_window: int | None,
    block_q: int | None,
    block_k: int | None,
) -> tuple[int, int]:
    """Fill unset block knobs for a flat-kernel call via the tuning layer,
    then fit the RESOLVED (non-explicit) knobs to the actual sequence
    lengths — a table/default block that doesn't divide the input degrades
    to the nearest dividing tile; an explicit block that doesn't divide
    still raises through `_check_block_divisibility` (caller bug)."""
    explicit_q, explicit_k = block_q is not None, block_k is not None
    if explicit_q and explicit_k:
        return block_q, block_k
    choice = resolve_block_sizes(
        kind, seq_len=max(sq, skv), head_dim=head_dim, dtype=dtype,
        causal=causal, sliding_window=sliding_window,
        block_q=block_q, block_k=block_k,
    )
    bq, bk = choice.block_q, choice.block_k
    if not explicit_q and sq % _LANES == 0:
        bq = fit_block(bq, sq)
    if not explicit_k and skv % _LANES == 0:
        bk = fit_block(bk, skv)
    # record the post-fit tiles (what actually compiles), not the raw pick
    record_block_choice(kind, BlockChoice(bq, bk, choice.source))
    return bq, bk


def _check_block_divisibility(sq: int, skv: int, block_q: int, block_k: int) -> None:
    # the kernels floor the grid; a non-dividing block would silently drop
    # trailing rows/columns (callers pad — the public wrapper and ring both do)
    if sq % block_q or skv % block_k:
        raise ValueError(
            f"sequence lengths ({sq}, {skv}) must be multiples of the blocks "
            f"({block_q}, {block_k}); pad inputs or pick dividing blocks"
        )


def _seg_mask(seg_q, seg_kv):
    """(block_q, block_k) segment mask (True = attend): same packed document,
    and the q row is not padding (seg 0)."""
    return (seg_q[:, None] == seg_kv[None, :]) & (seg_q[:, None] > 0)


def _seg_overlap(seg_q, seg_kv):
    """Scalar predicate: the q tile's segment-id range intersects the kv
    tile's, and the q tile is not all padding. Packed documents occupy
    consecutive rows, so disjoint ranges ⇒ fully-masked tile ⇒ skip it —
    this makes packed attention cost the sum of per-document squares instead
    of the full quadratic (the varlen win of the reference's flash-attn
    dispatch, `attention_op.py:538-654`, at tile granularity). Range
    intersection is conservative (interleaved ids only cost a visit, never a
    wrong skip), and padding zeros only widen the ranges."""
    q_max = jnp.max(seg_q)
    return (
        (jnp.min(seg_q) <= jnp.max(seg_kv))
        & (jnp.min(seg_kv) <= q_max)
        & (q_max > 0)
    )


def _seg_uniform(seg_q, seg_kv):
    """Scalar predicate: both blocks hold one identical non-padding segment,
    so the segment mask is all-True and can be skipped. Four cheap vector
    reduces per tile buy skipping the (block_q, block_k) broadcast compare +
    select on the common case (unpacked data, or packed tiles away from
    document boundaries)."""
    q_min = jnp.min(seg_q)
    return (
        (q_min == jnp.max(seg_q))
        & (q_min == jnp.min(seg_kv))
        & (q_min == jnp.max(seg_kv))
        & (q_min > 0)
    )


def _masked_dispatch(visit, interior, uniform, body):
    """Run `body(with_pos, with_seg)` under the cheapest applicable mask
    variant. All four specializations are compiled; exactly one executes per
    tile (scalar-predicated branches, not lane masking)."""
    pl.when(visit & interior & uniform)(lambda: body(False, False))
    pl.when(visit & interior & ~uniform)(lambda: body(False, True))
    pl.when(visit & ~interior & uniform)(lambda: body(True, False))
    pl.when(visit & ~interior & ~uniform)(lambda: body(True, True))


def _pos_mask(
    i,
    j,
    block_q: int,
    block_k: int,
    q_offset: int,
    causal: bool,
    sliding_window: int | None,
):
    """(block_q, block_k) position mask for tile (i, j) — built only on
    boundary tiles (see `_pos_interior`); interior tiles skip the iota and
    compare VPU work entirely, which is most of a flash tile's non-MXU cost."""
    q_pos = (
        i * block_q
        + q_offset
        + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    )
    k_pos = j * block_k + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if sliding_window is not None:
        mask &= q_pos - k_pos < sliding_window
    return mask


def _pos_interior(
    i,
    j,
    block_q: int,
    block_k: int,
    q_offset: int,
    causal: bool,
    sliding_window: int | None,
):
    """Scalar predicate: every (q, k) position pair in tile (i, j) satisfies
    the causal/window constraints, so only the segment mask applies."""
    interior = jnp.bool_(True)
    q_lo = i * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = j * block_k
    k_hi = k_lo + block_k - 1
    if causal:
        interior &= k_hi <= q_lo
    if sliding_window is not None:
        interior &= q_hi - k_lo < sliding_window
    return interior


def _should_visit(
    i,
    j,
    block_q: int,
    block_k: int,
    q_offset: int,
    causal: bool,
    sliding_window: int | None,
):
    """Tile-level skip predicate: False when tile (i, j) is fully masked by
    position alone (segments can only mask further)."""
    visit = jnp.bool_(True)
    q_lo = i * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = j * block_k
    k_hi = k_lo + block_k - 1
    if causal:
        visit &= k_lo <= q_hi
    if sliding_window is not None:
        visit &= q_lo - k_hi < sliding_window
    return visit


def _scores(q, k, scale: float, logits_soft_cap: float | None):
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if scale != 1.0:  # callers fold scale into q; this is the generic path
        s = s * scale
    if logits_soft_cap is not None:
        s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
    return s


def _fwd_kernel(
    seg_lo_ref,  # scalar-prefetch [B, nq]: kv-block bounds per q block
    seg_hi_ref,
    q_seg_ref,
    kv_seg_ref,
    q_ref,
    k_ref,
    v_ref,
    *rest,
    scale: float,
    causal: bool,
    sliding_window: int | None,
    logits_soft_cap: float | None,
    q_offset: int,
    block_q: int,
    block_k: int,
    num_q_heads: int,
    has_sinks: bool = False,
):
    if has_sinks:
        sinks_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        sinks_ref = None
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    @pl.when(j == 0)
    def _init():
        if sinks_ref is None:
            m_scr[:] = jnp.full_like(m_scr, _MASK_VALUE)
            l_scr[:] = jnp.zeros_like(l_scr)
        else:
            # gpt-oss attention sink: the softmax denominator starts life
            # holding exp(sink - sink) == 1 at running max == sink; the
            # standard online-softmax rescaling keeps it exact from there.
            # The sink contributes no value, so acc stays zero-initialized.
            # (This program's head is selected by the sink BlockSpec index
            # map — a dynamic lane index would not lower on Mosaic.)
            sink = sinks_ref[0, 0, 0]
            m_scr[:] = jnp.full_like(m_scr, sink)
            l_scr[:] = jnp.ones_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _visit(with_pos_mask: bool, with_seg_mask: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]

        s = _scores(q, k, scale, logits_soft_cap)
        mask = None
        if with_seg_mask:
            mask = _seg_mask(q_seg_ref[0, 0], kv_seg_ref[0, 0])
        if with_pos_mask:
            pos = _pos_mask(i, j, block_q, block_k, q_offset, causal, sliding_window)
            mask = pos if mask is None else mask & pos

        # masked entries must be numerically inert BEFORE the running max: a
        # masked outlier logit ~88 above the row's true max would otherwise
        # lock m_new and underflow every valid probability (0/0 at flush).
        # The uniform branch has no masked entries, so its raw max is exact
        # and it skips both selects.
        if mask is not None:
            s = jnp.where(mask, s, _MASK_VALUE)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if mask is not None:
            # explicit zeroing keeps fully-masked rows exactly at l == 0 so
            # padding rows emit O = 0, LSE = -inf (exp(MASK - MASK) == 1)
            p = jnp.where(mask, p, 0.0)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)

        m_scr[:, :1] = m_new
        l_scr[:, :1] = l_new
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )

    # the kv BlockSpec index map redirects segment-skipped tiles onto an
    # already-resident kv block (DMA elision), so the STREAMED seg block may
    # not be block j's. The skip decision must therefore come from the
    # ORIGINAL grid index: j inside the prefetched bounds ⇔ no redirection
    # happened ⇔ the streamed data is block j's and _seg_overlap/_seg_uniform
    # are evaluated on the right ids.
    batch_i = pl.program_id(0) // num_q_heads
    in_bounds = (j >= seg_lo_ref[batch_i, i]) & (j <= seg_hi_ref[batch_i, i])
    visit = (
        _should_visit(i, j, block_q, block_k, q_offset, causal, sliding_window)
        & in_bounds
        & _seg_overlap(q_seg_ref[0, 0], kv_seg_ref[0, 0])
    )
    interior = _pos_interior(i, j, block_q, block_k, q_offset, causal, sliding_window)
    uniform = _seg_uniform(q_seg_ref[0, 0], kv_seg_ref[0, 0])
    _masked_dispatch(visit, interior, uniform, _visit)

    @pl.when(j == nk - 1)
    def _flush():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        l_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l > 0, m + jnp.log(l_safe), -jnp.inf)
        lse_ref[0, 0] = lse[:, 0]


def _dq_kernel(
    seg_lo_ref,  # scalar-prefetch [B, nq]: kv-block bounds per q block
    seg_hi_ref,
    q_seg_ref,
    kv_seg_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dq_ref,
    dq_scr,
    *,
    scale: float,
    causal: bool,
    sliding_window: int | None,
    logits_soft_cap: float | None,
    q_offset: int,
    block_q: int,
    block_k: int,
    num_q_heads: int,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _visit(with_pos_mask: bool, with_seg_mask: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]

        s = _scores(q, k, scale, logits_soft_cap)
        mask = None
        if with_seg_mask:
            mask = _seg_mask(q_seg_ref[0, 0], kv_seg_ref[0, 0])
        if with_pos_mask:
            pos = _pos_mask(i, j, block_q, block_k, q_offset, causal, sliding_window)
            mask = pos if mask is None else mask & pos
        # lse == -inf on fully-padded rows would give exp(inf); the uniform
        # (maskless) branch only runs when every q row is non-padding, so
        # those rows always carry a finite lse there
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        if logits_soft_cap is not None:
            ds = ds * (1.0 - (s / logits_soft_cap) ** 2)
        if scale != 1.0:
            ds = ds * scale
        dq_scr[:] += jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32
        )

    # see _fwd_kernel: skip decisions must come from the ORIGINAL grid index,
    # not from the streamed (possibly redirected) seg block
    batch_i = pl.program_id(0) // num_q_heads
    in_bounds = (j >= seg_lo_ref[batch_i, i]) & (j <= seg_hi_ref[batch_i, i])
    visit = (
        _should_visit(i, j, block_q, block_k, q_offset, causal, sliding_window)
        & in_bounds
        & _seg_overlap(q_seg_ref[0, 0], kv_seg_ref[0, 0])
    )
    interior = _pos_interior(i, j, block_q, block_k, q_offset, causal, sliding_window)
    uniform = _seg_uniform(q_seg_ref[0, 0], kv_seg_ref[0, 0])
    _masked_dispatch(visit, interior, uniform, _visit)

    @pl.when(j == nk - 1)
    def _flush():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    seg_lo_ref,  # scalar-prefetch [B, nk] (q-block bounds per KV block)
    seg_hi_ref,
    q_seg_ref,
    kv_seg_ref,
    q_ref,
    k_ref,
    v_ref,
    do_ref,
    lse_ref,
    delta_ref,
    dk_ref,
    dv_ref,
    dk_scr,
    dv_scr,
    *,
    scale: float,
    causal: bool,
    sliding_window: int | None,
    logits_soft_cap: float | None,
    q_offset: int,
    block_q: int,
    block_k: int,
    num_kv_heads: int,
):
    j = pl.program_id(1)
    g = pl.program_id(2)
    i = pl.program_id(3)
    ng = pl.num_programs(2)
    nq = pl.num_programs(3)

    @pl.when((g == 0) & (i == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _visit(with_pos_mask: bool, with_seg_mask: bool):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]

        s = _scores(q, k, scale, logits_soft_cap)
        mask = None
        if with_seg_mask:
            mask = _seg_mask(q_seg_ref[0, 0], kv_seg_ref[0, 0])
        if with_pos_mask:
            pos = _pos_mask(i, j, block_q, block_k, q_offset, causal, sliding_window)
            mask = pos if mask is None else mask & pos
        p = jnp.exp(s - lse)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        # dV_j += P^T dO ; contraction over the q rows (dim 0 of both)
        dv_scr[:] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        if logits_soft_cap is not None:
            ds = ds * (1.0 - (s / logits_soft_cap) ** 2)
        if scale != 1.0:
            ds = ds * scale
        dk_scr[:] += lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # see _fwd_kernel: skip decisions must come from the ORIGINAL grid index,
    # not from the streamed (possibly redirected) seg block. Here the bounds
    # are q-block ranges per kv block, so the gate runs on i.
    batch_i = pl.program_id(0) // num_kv_heads
    in_bounds = (i >= seg_lo_ref[batch_i, j]) & (i <= seg_hi_ref[batch_i, j])
    visit = (
        _should_visit(i, j, block_q, block_k, q_offset, causal, sliding_window)
        & in_bounds
        & _seg_overlap(q_seg_ref[0, 0], kv_seg_ref[0, 0])
    )
    interior = _pos_interior(i, j, block_q, block_k, q_offset, causal, sliding_window)
    uniform = _seg_uniform(q_seg_ref[0, 0], kv_seg_ref[0, 0])
    _masked_dispatch(visit, interior, uniform, _visit)

    @pl.when((g == ng - 1) & (i == nq - 1))
    def _flush():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def flash_fwd_flat(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seg_q: jnp.ndarray,
    seg_kv: jnp.ndarray,
    *,
    num_q_heads: int,
    num_kv_heads: int,
    scale: float,
    causal: bool,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    q_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    sinks: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Forward kernel over flat padded inputs: q [B*Hq, Sq, D], k/v
    [B*Hkv, Skv, D], seg_q [B, Sq], seg_kv [B, Skv]. Returns
    (o [B*Hq, Sq, D], lse [B*Hq, Sq] fp32). `sinks` [num_q_heads] fp32
    seeds each row's softmax denominator (gpt-oss; lse then includes the
    sink mass). Building block for both the public wrapper and ring
    attention (which re-runs the backward with the globally-combined
    lse)."""
    interpret = resolve_interpret(interpret)
    bh, sq, d = q.shape
    skv = k.shape[1]
    block_q, block_k = _resolve_flat_blocks(
        "fwd", sq, skv, d, q.dtype, causal, sliding_window, block_q, block_k
    )
    _check_block_divisibility(sq, skv, block_q, block_k)
    nq, nk = sq // block_q, skv // block_k
    hyper = dict(
        scale=scale, causal=causal, sliding_window=sliding_window,
        logits_soft_cap=logits_soft_cap, q_offset=q_offset,
        block_q=block_q, block_k=block_k, num_q_heads=num_q_heads,
        has_sinks=sinks is not None,
    )
    kv_bh = _kv_bh_map(num_q_heads, num_kv_heads)
    kv_c = _kv_clamp(block_q, block_k, q_offset, causal, sliding_window, nk)
    seg_lo, seg_hi = _segment_block_bounds(seg_q, seg_kv, block_q, block_k)

    kv_idx = _bounded_idx(kv_c, num_q_heads)

    in_specs = [
        pl.BlockSpec((1, 1, block_q), lambda b, i, j, lo, hi: (b // num_q_heads, 0, i)),
        pl.BlockSpec(
            (1, 1, block_k),
            lambda b, i, j, lo, hi: (b // num_q_heads, 0, kv_idx(b, i, j, lo, hi)),
        ),
        pl.BlockSpec((1, block_q, d), lambda b, i, j, lo, hi: (b, i, 0)),
        pl.BlockSpec(
            (1, block_k, d),
            lambda b, i, j, lo, hi: (kv_bh(b), kv_idx(b, i, j, lo, hi), 0),
        ),
        pl.BlockSpec(
            (1, block_k, d),
            lambda b, i, j, lo, hi: (kv_bh(b), kv_idx(b, i, j, lo, hi), 0),
        ),
    ]
    inputs = [seg_q[:, None], seg_kv[:, None], q, k, v]
    if sinks is not None:
        # one lane-width row per head; the index map picks this program's
        # head so the kernel reads a STATIC [0, 0, 0] scalar
        in_specs.append(
            pl.BlockSpec((1, 1, _LANES), lambda b, i, j, lo, hi: (b % num_q_heads, 0, 0))
        )
        inputs.append(jnp.broadcast_to(
            sinks.astype(jnp.float32)[:, None, None], (num_q_heads, 1, _LANES)
        ))

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **hyper),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, lo, hi: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j, lo, hi: (b, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(seg_lo, seg_hi, *inputs)
    # remat tags: under `recompute_granularity='selective'` the model policy
    # saves exactly these two (save_only_these_names), so the backward pass
    # reads O/LSE instead of re-running this kernel — attention is the one
    # block whose recompute costs as much as its forward
    o = checkpoint_name(o, "flash_out")
    lse = checkpoint_name(lse[:, 0], "flash_lse")
    return o, lse


def flash_bwd_flat(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seg_q: jnp.ndarray,
    seg_kv: jnp.ndarray,
    do: jnp.ndarray,
    lse: jnp.ndarray,
    delta: jnp.ndarray,
    *,
    num_q_heads: int,
    num_kv_heads: int,
    scale: float,
    causal: bool,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    q_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Backward kernels over flat padded inputs. `lse`/`delta` are [B*Hq, Sq]
    fp32 — for ring attention they are the globally-combined values, which is
    exactly what makes per-chunk dQ/dK/dV contributions sum to the full-
    sequence gradient.

    `block_q`/`block_k` are the BACKWARD tiles (tuning kind "bwd") — the
    dq/dkv kernels carry different scratch footprints than the forward, so
    their optimal blocks are tuned independently."""
    interpret = resolve_interpret(interpret)
    bh, sq, d = q.shape
    skv = k.shape[1]
    block_q, block_k = _resolve_flat_blocks(
        "bwd", sq, skv, d, q.dtype, causal, sliding_window, block_q, block_k
    )
    _check_block_divisibility(sq, skv, block_q, block_k)
    nq, nk = sq // block_q, skv // block_k
    bh_kv = k.shape[0]
    group = num_q_heads // num_kv_heads
    hyper = dict(
        scale=scale, causal=causal, sliding_window=sliding_window,
        logits_soft_cap=logits_soft_cap, q_offset=q_offset,
        block_q=block_q, block_k=block_k,
    )
    kv_bh = _kv_bh_map(num_q_heads, num_kv_heads)
    q_bh = _q_bh_map(num_q_heads, num_kv_heads)
    kv_c = _kv_clamp(block_q, block_k, q_offset, causal, sliding_window, nk)
    q_c = _q_clamp(block_q, block_k, q_offset, causal, sliding_window, nq)
    # kv-block bounds per q block (dq) and q-block bounds per kv block (dkv):
    # the same runtime DMA elision the forward does, mirrored for the dkv
    # kernel's transposed grid
    seg_lo, seg_hi = _segment_block_bounds(seg_q, seg_kv, block_q, block_k)
    qblk_lo, qblk_hi = _segment_block_bounds(seg_kv, seg_q, block_k, block_q)

    kv_idx = _bounded_idx(kv_c, num_q_heads)
    q_idx = _bounded_idx(q_c, num_kv_heads)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, num_q_heads=num_q_heads, **hyper),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q), lambda b, i, j, lo, hi: (b // num_q_heads, 0, i)),
                pl.BlockSpec(
                    (1, 1, block_k),
                    lambda b, i, j, lo, hi: (b // num_q_heads, 0, kv_idx(b, i, j, lo, hi)),
                ),
                pl.BlockSpec((1, block_q, d), lambda b, i, j, lo, hi: (b, i, 0)),
                pl.BlockSpec(
                    (1, block_k, d),
                    lambda b, i, j, lo, hi: (kv_bh(b), kv_idx(b, i, j, lo, hi), 0),
                ),
                pl.BlockSpec(
                    (1, block_k, d),
                    lambda b, i, j, lo, hi: (kv_bh(b), kv_idx(b, i, j, lo, hi), 0),
                ),
                pl.BlockSpec((1, block_q, d), lambda b, i, j, lo, hi: (b, i, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j, lo, hi: (b, 0, i)),
                pl.BlockSpec((1, 1, block_q), lambda b, i, j, lo, hi: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j, lo, hi: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(seg_lo, seg_hi, seg_q[:, None], seg_kv[:, None], q, k, v, do, lse[:, None], delta[:, None])

    # q-side refs are indexed by (kv batch-head, group member): the GQA
    # reduction over the q heads sharing one kv head happens in scratch
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, num_kv_heads=num_kv_heads, **hyper),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh_kv, nk, group, nq),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, block_q),
                    lambda b, j, g, i, lo, hi: (b // num_kv_heads, 0, q_idx(b, j, i, lo, hi)),
                ),
                pl.BlockSpec(
                    (1, 1, block_k), lambda b, j, g, i, lo, hi: (b // num_kv_heads, 0, j)
                ),
                pl.BlockSpec(
                    (1, block_q, d),
                    lambda b, j, g, i, lo, hi: (q_bh(b, g), q_idx(b, j, i, lo, hi), 0),
                ),
                pl.BlockSpec((1, block_k, d), lambda b, j, g, i, lo, hi: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, g, i, lo, hi: (b, j, 0)),
                pl.BlockSpec(
                    (1, block_q, d),
                    lambda b, j, g, i, lo, hi: (q_bh(b, g), q_idx(b, j, i, lo, hi), 0),
                ),
                pl.BlockSpec(
                    (1, 1, block_q),
                    lambda b, j, g, i, lo, hi: (q_bh(b, g), 0, q_idx(b, j, i, lo, hi)),
                ),
                pl.BlockSpec(
                    (1, 1, block_q),
                    lambda b, j, g, i, lo, hi: (q_bh(b, g), 0, q_idx(b, j, i, lo, hi)),
                ),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j, g, i, lo, hi: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, g, i, lo, hi: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qblk_lo, qblk_hi, seg_q[:, None], seg_kv[:, None], q, k, v, do, lse[:, None], delta[:, None])
    return dq, dk, dv


def _make_attention(
    *,
    num_q_heads: int,
    num_kv_heads: int,
    scale: float,
    causal: bool,
    sliding_window: int | None,
    logits_soft_cap: float | None,
    q_offset: int,
    block_q: int,
    block_k: int,
    bwd_block_q: int,
    bwd_block_k: int,
    interpret: bool,
    bwd_source: str = "call",
):
    """Build the custom-VJP flash attention over padded flat inputs.

    `block_q/block_k` tile the forward kernel; `bwd_block_q/bwd_block_k`
    tile the dq/dkv kernels (independent knobs — the backward's scratch
    footprints want different VMEM trade-offs). `bwd_source` is only
    telemetry provenance for the bwd-tile gauges."""
    hyper = dict(
        num_q_heads=num_q_heads,
        num_kv_heads=num_kv_heads,
        scale=scale,
        causal=causal,
        sliding_window=sliding_window,
        logits_soft_cap=logits_soft_cap,
        q_offset=q_offset,
        interpret=interpret,
    )
    fwd_blocks = dict(block_q=block_q, block_k=block_k)
    bwd_blocks = dict(block_q=bwd_block_q, block_k=bwd_block_k)

    @jax.custom_vjp
    def attention(q, k, v, seg_q, seg_kv, sinks):
        o, _ = flash_fwd_flat(q, k, v, seg_q, seg_kv, sinks=sinks, **hyper, **fwd_blocks)
        return o

    def attention_fwd(q, k, v, seg_q, seg_kv, sinks):
        o, lse = flash_fwd_flat(q, k, v, seg_q, seg_kv, sinks=sinks, **hyper, **fwd_blocks)
        return o, (q, k, v, seg_q, seg_kv, sinks, o, lse)

    def attention_bwd(res, do):
        q, k, v, seg_q, seg_kv, sinks, o, lse = res
        # record the bwd tiles HERE, not in the wrapper: this rule only
        # traces when a backward exists in the program, so forward-only
        # traces (eval/validation) never report bwd gauges for kernels
        # they never compile
        record_block_choice(
            "bwd", BlockChoice(bwd_block_q, bwd_block_k, bwd_source)
        )
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
        # the dQ/dK/dV kernels are sink-agnostic: with the sink mass folded
        # into lse, p = exp(s - lse) already sums to < 1 per row and
        # delta == sum_k p_k dP_k still holds (the sink's value is zero)
        dq, dk, dv = flash_bwd_flat(
            q, k, v, seg_q, seg_kv, do, lse, delta, **hyper, **bwd_blocks
        )
        if sinks is None:
            d_sinks = None
        else:
            # d/ds of the sink-softmax: -p_sink * delta per row, summed per
            # head; p_sink = exp(sink - lse)
            bh = lse.shape[0]
            num_q_heads = hyper["num_q_heads"]
            sinks_bh = jnp.tile(sinks.astype(jnp.float32), bh // num_q_heads)
            ds_rows = -jnp.exp(sinks_bh[:, None] - lse) * delta  # [B*H, S]
            d_sinks = (
                ds_rows.reshape(-1, num_q_heads, lse.shape[-1])
                .sum(axis=(0, 2))
                .astype(sinks.dtype)
            )
        return dq, dk, dv, None, None, d_sinks

    attention.defvjp(attention_fwd, attention_bwd)
    return attention


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    q_segment_ids: jnp.ndarray | None = None,
    causal: bool = True,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    q_offset: int = 0,
    block_q: int | None = None,
    block_k: int | None = None,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
    interpret: bool | None = None,
    sinks: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Flash attention over packed sequences.

    q: [batch, q_len, num_q_heads, head_dim]; k/v: [batch, kv_len,
    num_kv_heads, head_dim]; segment ids as in
    `llm_training_tpu.ops.attention.dot_product_attention` (0 = padding).
    Runs compiled on TPU, interpreted elsewhere (tests); `interpret=True`
    on a TPU raises (`resolve_interpret`).

    Block sizes left as None resolve at call time through
    `ops/pallas/tuning.py` (env > tuning table > default), independently
    for the forward (`block_q/block_k`) and backward
    (`bwd_block_q/bwd_block_k`) kernels.
    """
    batch, q_len, num_q_heads, head_dim = q.shape
    kv_len, num_kv_heads = k.shape[1], k.shape[2]
    if num_q_heads % num_kv_heads != 0:
        raise ValueError(
            f"num_q_heads ({num_q_heads}) not divisible by num_kv_heads ({num_kv_heads})"
        )
    if scale is None:
        scale = head_dim**-0.5
    interpret = resolve_interpret(interpret)
    orig_dtype = q.dtype
    # fold the softmax scale into q: one multiply per q element replaces one
    # per SCORE element in every kernel (fwd + both bwd recomputes) — the
    # kernels are VPU-bound, so per-score passes are the scarce resource.
    # Gradients stay exact: autodiff chains dq through this multiply, and
    # dk = ds_unscaled · (q·scale) == (ds_unscaled·scale) · q inside the
    # kernel. The tiny bf16 rounding shift is the standard pre-scaled-q
    # formulation (flash-attn does the same).
    if scale != 1.0:
        q = q * jnp.asarray(scale, q.dtype)
        scale = 1.0

    if q_segment_ids is None:
        if segment_ids is not None and q_len != kv_len:
            raise ValueError(
                "q_segment_ids is required when segment_ids is given and "
                f"q_len ({q_len}) != kv_len ({kv_len})"
            )
        q_segment_ids = (
            segment_ids
            if segment_ids is not None
            else jnp.ones((batch, q_len), jnp.int32)
        )
    if segment_ids is None:
        segment_ids = jnp.ones((batch, kv_len), jnp.int32)
    q_segment_ids = q_segment_ids.astype(jnp.int32)
    segment_ids = segment_ids.astype(jnp.int32)

    # resolve fwd/bwd tile sizes at call time (explicit arg > FLASH_BLOCK_*
    # env > tuning table > default). Backward knobs resolve PER KNOB:
    # explicit bwd_block_* arg > bwd-specific FLASH_BLOCK_{Q,K}_BWD env >
    # the same-knob explicit fwd tile (the pre-tuning-layer contract every
    # sweep/microbench call site relies on — a tile you pin tiles BOTH
    # passes, and a stale table entry can never retile a pinned knob) >
    # the shared env/table/default chain for knobs the caller never pinned.
    explicit_bwd_q, explicit_bwd_k = bwd_block_q is not None, bwd_block_k is not None
    fwd_choice = resolve_block_sizes(
        "fwd", seq_len=max(q_len, kv_len), head_dim=head_dim, dtype=q.dtype,
        causal=causal, sliding_window=sliding_window,
        block_q=block_q, block_k=block_k,
    )
    spec = []
    for name, bwd_arg, fwd_arg, fwd_val in (
        ("block_q", bwd_block_q, block_q, fwd_choice.block_q),
        ("block_k", bwd_block_k, block_k, fwd_choice.block_k),
    ):
        if bwd_arg is not None:
            value, src = int(bwd_arg), "call"
        else:
            env_value = bwd_env_override(name)
            if env_value is not None:
                value, src = env_value, "env"
            elif fwd_arg is not None:
                value, src = fwd_val, "call"  # inherited pinned fwd tile
            else:
                value, src = None, None  # shared chain below
        if value is not None and (value < _LANES or value % _LANES):
            raise ValueError(
                f"bwd {name} must be a positive multiple of {_LANES}, got {value}"
            )
        spec.append((value, src))
    if any(value is None for value, _ in spec):
        shared = resolve_block_sizes(
            "bwd", seq_len=max(q_len, kv_len), head_dim=head_dim, dtype=q.dtype,
            causal=causal, sliding_window=sliding_window,
        )
        chain = ((shared.block_q, shared.source_q), (shared.block_k, shared.source_k))
        spec = [pinned if pinned[0] is not None else fallthrough
                for pinned, fallthrough in zip(spec, chain)]
    (bq, src_q), (bk, src_k) = spec
    bwd_choice = BlockChoice(bq, bk, min((src_q, src_k), key=SOURCE_ORDER.index))

    # pad sequence dims to block multiples and head_dim to the lane width;
    # padded tokens get segment id 0, so they are masked not attended.
    # head_dim needs NO padding when the blocks cover it exactly and it is
    # sublane-aligned (64 = Llama-style head dim): Mosaic accepts full-array
    # blocks, and skipping the pad saves ~25% attention time vs 64->128
    # zero-padding (measured on v5e)
    block_q = min(fwd_choice.block_q, _round_up(q_len, _LANES))
    block_k = min(fwd_choice.block_k, _round_up(kv_len, _LANES))
    sq_pad = _round_up(q_len, block_q) - q_len
    skv_pad = _round_up(kv_len, block_k) - kv_len
    # the padded lengths are multiples of the FWD blocks; non-explicit bwd
    # tiles (env/table-resolved, or inherited from the fwd pair) degrade to
    # the nearest dividing block, while explicitly-passed bwd_block_* stay
    # strict (flash_bwd_flat raises on non-divisibility — caller bug)
    if not explicit_bwd_q:
        bwd_block_q = fit_block(bwd_choice.block_q, q_len + sq_pad)
    if not explicit_bwd_k:
        bwd_block_k = fit_block(bwd_choice.block_k, kv_len + skv_pad)
    # record the tiles the kernels will actually compile with (post
    # clamp/fit), not the raw resolution; the bwd gauges are recorded
    # inside the VJP's bwd rule so forward-only traces don't report them
    record_block_choice("fwd", BlockChoice(block_q, block_k, fwd_choice.source))
    d_pad = (
        0
        if head_dim == 64 or head_dim % _LANES == 0
        else _round_up(head_dim, _LANES) - head_dim
    )
    if sq_pad or d_pad:
        q = jnp.pad(q, ((0, 0), (0, sq_pad), (0, 0), (0, d_pad)))
        q_segment_ids = jnp.pad(q_segment_ids, ((0, 0), (0, sq_pad)))
    if skv_pad or d_pad:
        k = jnp.pad(k, ((0, 0), (0, skv_pad), (0, 0), (0, d_pad)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad), (0, 0), (0, d_pad)))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, skv_pad)))

    # [B, S, H, D] -> flat [B*H, S, D]
    qf = q.transpose(0, 2, 1, 3).reshape(batch * num_q_heads, q_len + sq_pad, -1)
    kf = k.transpose(0, 2, 1, 3).reshape(batch * num_kv_heads, kv_len + skv_pad, -1)
    vf = v.transpose(0, 2, 1, 3).reshape(batch * num_kv_heads, kv_len + skv_pad, -1)

    attention = _make_attention(
        num_q_heads=num_q_heads,
        num_kv_heads=num_kv_heads,
        scale=scale,
        causal=causal,
        sliding_window=sliding_window,
        logits_soft_cap=logits_soft_cap,
        q_offset=q_offset,
        block_q=block_q,
        block_k=block_k,
        bwd_block_q=bwd_block_q,
        bwd_block_k=bwd_block_k,
        interpret=interpret,
        bwd_source=bwd_choice.source,
    )
    of = attention(qf, kf, vf, q_segment_ids, segment_ids, sinks)

    o = of.reshape(batch, num_q_heads, q_len + sq_pad, -1).transpose(0, 2, 1, 3)
    return o[:, :q_len, :, :head_dim].astype(orig_dtype)
