"""Ring attention: context parallelism over the `sequence` mesh axis.

The reference has NO context parallelism (SURVEY.md §2.8: "CP / ring
attention / Ulysses — absent"); it reaches 131k tokens by composing TP+SP
with activation checkpointing (SURVEY.md §5.7). Here long context is a
first-class axis: activations are sequence-sharded across devices and
attention runs as a ring — each device keeps its q chunk and circulates
k/v chunks with `ppermute` over ICI, overlapping the transfer with the
block-attention compute.

Causality at chunk granularity makes the rotating offset static:
  kv chunk from an EARLIER position  -> full (unmasked) attention
  kv chunk from the SAME position    -> ordinary causal attention
  kv chunk from a LATER position     -> skipped entirely
so no traced q_offset ever reaches a kernel, and the causal ring does
~half the chunk-pair work, like the tile-level skipping inside the kernel.

Partial results combine with the running-logsumexp rule (the same online
softmax the flash kernel uses across kv blocks, lifted to chunks). The
backward is a custom VJP that re-runs the ring with the globally-combined
lse and delta: with those fixed, per-chunk-pair dQ/dK/dV contributions sum
exactly to the full-sequence gradient; dK/dV accumulators ride the ring
with their chunk and arrive home after a full rotation.

Packing composes for free: segment ids are global document ids, so the
chunk-pair mask `seg_q == seg_kv` is correct across chunk boundaries.

Sliding windows compose (r4): the window mask applies inside partially-
covered chunk pairs (static q_offset = step·chunk), and the ring stops
rotating once every remaining pair is outside the window — compute AND
communication are O(window). Attention sinks (gpt-oss) compose by seeding
each owner chunk's running logsumexp with the sink logit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from llm_training_tpu.parallel.mesh import SEQUENCE_AXIS


def batch_head_axes(mesh, q, k):
    """(batch mesh axes, head mesh axis) an attention shard_map splits
    q/k/v `[B, S, H, D]` over, degrading to replication on axes the shapes
    can't fill — the init trace runs with batch 1, and tiny-head configs may
    not divide the tensor axis. The expert axis joins the batch factors (the
    batch sharding rule treats EP groups as extra data parallelism), else
    EP runs would all-gather and redundantly recompute attention across EP
    ranks."""
    from llm_training_tpu.parallel.mesh import (
        DATA_AXIS, EXPERT_AXIS, FSDP_AXIS, TENSOR_AXIS,
    )

    dp_ways = (
        mesh.shape[DATA_AXIS]
        * mesh.shape[FSDP_AXIS]
        * mesh.shape.get(EXPERT_AXIS, 1)
    )
    if q.shape[0] % dp_ways == 0:
        batch_axes = (DATA_AXIS, FSDP_AXIS, EXPERT_AXIS)
    elif q.shape[0] % (mesh.shape[DATA_AXIS] * mesh.shape[FSDP_AXIS]) == 0:
        # degrade only the expert factor, keeping data/fsdp sharding
        batch_axes = (DATA_AXIS, FSDP_AXIS)
    else:
        batch_axes = None
    tp = mesh.shape[TENSOR_AXIS]
    head_axis = (
        TENSOR_AXIS if q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None
    )
    return batch_axes, head_axis


def dispatch_ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray | None,
    *,
    sliding_window: int | None = None,
    sinks: jnp.ndarray | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    impl: str = "auto",
):
    """shard_map `ring_attention` over the active mesh's sequence axis, or
    return None when no sequence-sharded mesh is active (callers fall back
    to the single-device flash/XLA path; GSPMD handles any other sharding by
    inserting collectives itself).

    Shared dispatch for every family with a `ring_attention` config flag
    (llama/OLMo sliding windows, gemma-2/3 windows + softcap, gpt-oss
    windows + sinks)."""
    from jax.sharding import PartitionSpec as P

    from llm_training_tpu.parallel.mesh import active_mesh

    mesh = active_mesh()
    if mesh is None or mesh.shape.get(SEQUENCE_AXIS, 1) <= 1:
        return None
    if segment_ids is None:
        segment_ids = jnp.ones(q.shape[:2], jnp.int32)
    batch_axes, head_axis = batch_head_axes(mesh, q, k)
    spec_qkv = P(batch_axes, SEQUENCE_AXIS, head_axis, None)
    spec_seg = P(batch_axes, SEQUENCE_AXIS)
    in_specs = [spec_qkv, spec_qkv, spec_qkv, spec_seg]
    args = [q, k, v, segment_ids]
    if sinks is not None:
        in_specs.append(P(head_axis))
        args.append(sinks)

    def run(q, k, v, seg, *maybe_sinks):
        return ring_attention(
            q, k, v, seg,
            axis_name=SEQUENCE_AXIS,
            causal=True,
            logits_soft_cap=logits_soft_cap,
            scale=scale,
            impl=impl,
            sliding_window=sliding_window,
            sinks=maybe_sinks[0] if maybe_sinks else None,
        )

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=spec_qkv,
        check_vma=False,
    )(*args)


def _safe_weight(lse: jnp.ndarray, lse_total: jnp.ndarray) -> jnp.ndarray:
    """exp(lse - lse_total) with fully-masked rows (-inf) mapping to weight 0
    without producing NaN in either branch (NaN in an untaken `where` branch
    still poisons gradients)."""
    finite_total = jnp.where(jnp.isneginf(lse_total), 0.0, lse_total)
    return jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(lse - finite_total))


def _pos_mask(c_q, c_kv, q_offset, causal, sliding_window):
    """[C_q, C_kv] bool position mask for a chunk pair whose q chunk starts
    `q_offset` positions after the kv chunk (static int)."""
    q_pos = q_offset + jnp.arange(c_q)[:, None]
    k_pos = jnp.arange(c_kv)[None, :]
    mask = jnp.ones((c_q, c_kv), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if sliding_window is not None:
        mask &= q_pos - k_pos < sliding_window
    return mask


def _chunk_fwd_xla(
    q, k, v, seg_q, seg_kv, causal, scale, logits_soft_cap, sliding_window, q_offset
):
    """(o, lse) for one chunk pair. q [B,C,Hq,D]; k/v [B,C,Hkv,D];
    lse [B,Hq,C] fp32; o is fp32 (combined then cast by the caller)."""
    batch, c_q, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(batch, c_q, hkv, group, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s = s * scale
    if logits_soft_cap is not None:
        s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)

    mask = (seg_q[:, None, None, :, None] == seg_kv[:, None, None, None, :]) & (
        seg_q[:, None, None, :, None] > 0
    )
    if causal or sliding_window is not None:
        mask = mask & _pos_mask(
            c_q, k.shape[1], q_offset, causal, sliding_window
        )[None, None, None]

    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    lse = jnp.where(l[..., 0] > 0, m[..., 0] + jnp.log(jnp.where(l[..., 0] > 0, l[..., 0], 1.0)), -jnp.inf)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p / jnp.where(l > 0, l, 1.0), v.astype(jnp.float32))
    # lse [b,hkv,g,q] -> [b,hq,q]
    return o.reshape(batch, c_q, hq, d), lse.reshape(batch, hq, c_q)


def _chunk_bwd_xla(
    q, k, v, seg_q, seg_kv, do, lse, delta, causal, scale, logits_soft_cap,
    sliding_window, q_offset,
):
    """Chunk-pair gradients given the GLOBAL lse/delta ([B,Hq,C] fp32)."""
    batch, c_q, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    qg = q.reshape(batch, c_q, hkv, group, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32)
    s_raw = s * scale
    s = s_raw
    if logits_soft_cap is not None:
        s = logits_soft_cap * jnp.tanh(s_raw / logits_soft_cap)

    mask = (seg_q[:, None, None, :, None] == seg_kv[:, None, None, None, :]) & (
        seg_q[:, None, None, :, None] > 0
    )
    if causal or sliding_window is not None:
        mask = mask & _pos_mask(
            c_q, k.shape[1], q_offset, causal, sliding_window
        )[None, None, None]

    lse_g = lse.reshape(batch, hkv, group, c_q)[..., None]  # [b,hkv,g,q,1]
    lse_safe = jnp.where(jnp.isneginf(lse_g), 0.0, lse_g)
    p = jnp.where(mask, jnp.exp(s - lse_safe), 0.0)

    dog = do.astype(jnp.float32).reshape(batch, c_q, hkv, group, d)
    dv = jnp.einsum("bhgqk,bqhgd->bkhd", p, dog)
    dp = jnp.einsum("bqhgd,bkhd->bhgqk", dog, v.astype(jnp.float32))
    delta_g = delta.reshape(batch, hkv, group, c_q)[..., None]
    ds = p * (dp - delta_g)
    if logits_soft_cap is not None:
        ds = ds * (1.0 - (s / logits_soft_cap) ** 2)
    ds = ds * scale
    dq = jnp.einsum("bhgqk,bkhd->bqhgd", ds, k.astype(jnp.float32)).reshape(
        batch, c_q, hq, d
    )
    dk = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qg.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _to_flat(x):
    """[B, C, H, D] -> [B*H, C, D]."""
    b, c, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, c, d)


def _from_flat(x, batch):
    bh, c, d = x.shape
    return x.reshape(batch, bh // batch, c, d).transpose(0, 2, 1, 3)


def _ring_block(c: int) -> int:
    """Largest lane-aligned block <= 512 that divides the chunk length (the
    flat kernels require exact divisibility — they do not pad)."""
    for b in (512, 384, 256, 128):
        if c % b == 0:
            return b
    raise ValueError(f"chunk length {c} is not a multiple of 128")


def _ring_blocks(
    kind: str, q, k, causal: bool, sliding_window: int | None
) -> tuple[int, int]:
    """Chunk-kernel tiles via the tuning layer (keyed at the CHUNK length
    and the chunk pair's actual causality — the ring runs causal diagonal
    pairs AND non-causal off-diagonal pairs, tuned separately). Env/table
    choices win, fitted to divide the chunk; an untuned resolution keeps
    the conservative <=512 heuristic the ring was measured with rather
    than inheriting the full-sequence 1024 default."""
    from llm_training_tpu.ops.pallas import tuning

    choice = tuning.resolve_block_sizes(
        kind, seq_len=max(q.shape[1], k.shape[1]), head_dim=q.shape[-1],
        dtype=q.dtype, causal=causal, sliding_window=sliding_window,
    )
    if choice.source == "default":
        block_q, block_k = _ring_block(q.shape[1]), _ring_block(k.shape[1])
    else:
        block_q = tuning.fit_block(choice.block_q, q.shape[1])
        block_k = tuning.fit_block(choice.block_k, k.shape[1])
    # record what actually compiles (post-fit), not the raw pick
    tuning.record_block_choice(
        kind, tuning.BlockChoice(block_q, block_k, choice.source)
    )
    return block_q, block_k


def _pallas_ok(q, k) -> bool:
    return (
        q.shape[1] % 128 == 0
        and k.shape[1] % 128 == 0
        and q.shape[-1] % 128 == 0
        and jax.default_backend() == "tpu"
    )


def _chunk_fwd(
    q, k, v, seg_q, seg_kv, causal, scale, logits_soft_cap, impl,
    sliding_window=None, q_offset=0,
):
    if impl == "pallas" or (impl == "auto" and _pallas_ok(q, k)):
        from llm_training_tpu.ops.pallas.flash_attention import flash_fwd_flat

        batch, _, hq, _ = q.shape
        hkv = k.shape[2]
        block_q, block_k = _ring_blocks("fwd", q, k, causal, sliding_window)
        o, lse = flash_fwd_flat(
            _to_flat(q), _to_flat(k), _to_flat(v), seg_q, seg_kv,
            num_q_heads=hq, num_kv_heads=hkv, scale=scale, causal=causal,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
        )
        return _from_flat(o, batch).astype(jnp.float32), lse.reshape(batch, hq, -1)
    return _chunk_fwd_xla(
        q, k, v, seg_q, seg_kv, causal, scale, logits_soft_cap,
        sliding_window, q_offset,
    )


def _chunk_bwd(
    q, k, v, seg_q, seg_kv, do, lse, delta, causal, scale, logits_soft_cap, impl,
    sliding_window=None, q_offset=0,
):
    if impl == "pallas" or (impl == "auto" and _pallas_ok(q, k)):
        from llm_training_tpu.ops.pallas.flash_attention import flash_bwd_flat

        batch, _, hq, _ = q.shape
        hkv = k.shape[2]
        flat = lambda x: x.reshape(batch * hq, -1)
        block_q, block_k = _ring_blocks("bwd", q, k, causal, sliding_window)
        dq, dk, dv = flash_bwd_flat(
            _to_flat(q), _to_flat(k), _to_flat(v), seg_q, seg_kv,
            _to_flat(do), flat(lse), flat(delta),
            num_q_heads=hq, num_kv_heads=hkv, scale=scale, causal=causal,
            logits_soft_cap=logits_soft_cap,
            sliding_window=sliding_window, q_offset=q_offset,
            block_q=block_q, block_k=block_k,
        )
        return _from_flat(dq, batch), _from_flat(dk, batch), _from_flat(dv, batch)
    return _chunk_bwd_xla(
        q, k, v, seg_q, seg_kv, do, lse, delta, causal, scale, logits_soft_cap,
        sliding_window, q_offset,
    )


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_ids: jnp.ndarray | None = None,
    axis_name: str = SEQUENCE_AXIS,
    causal: bool = True,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    impl: str = "auto",
    sliding_window: int | None = None,
    sinks: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Causal ring attention over sequence-sharded chunks.

    Must be called inside `shard_map` (or any context where `axis_name` is a
    bound SPMD axis). Arguments are the per-device chunks:
    q/k/v [B, C, H, D], segment_ids [B, C] with GLOBAL document ids.

    `sliding_window` composes with the ring: rotated chunks wholly outside
    the window are never computed, and — since a window of w needs only the
    last ceil-ish w positions — the ring stops rotating after
    (w + c - 2)//c + 1 steps, so both compute AND communication are
    O(window), not O(sequence).

    `sinks` ([H_local] fp32, gpt-oss) seed each owner chunk's running
    logsumexp, so the sink mass joins every softmax denominator exactly once
    and the combine stays exact.
    """
    if not causal:
        raise NotImplementedError("ring attention currently requires causal=True")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # fold scale into q (see ops/pallas/flash_attention.py: the kernels are
    # VPU-bound, and the chunk kernels run once per ring step — folding pays
    # once per q chunk instead of once per score per step). Autodiff chains
    # dq through this multiply; dk inside uses q·scale which cancels against
    # the kernels' unscaled ds.
    if scale != 1.0:
        q = q * jnp.asarray(scale, q.dtype)
        scale = 1.0
    if segment_ids is None:
        segment_ids = jnp.ones(q.shape[:2], jnp.int32)
    segment_ids = segment_ids.astype(jnp.int32)

    ring = _make_ring(
        axis_name=axis_name,
        scale=scale,
        logits_soft_cap=logits_soft_cap,
        impl=impl,
        sliding_window=sliding_window,
        has_sinks=sinks is not None,
    )
    # sinks=None flows through the custom_vjp as an empty pytree leaf
    return ring(q, k, v, segment_ids, sinks)


@functools.cache
def _make_ring(
    *,
    axis_name: str,
    scale: float,
    logits_soft_cap: float | None,
    impl: str,
    sliding_window: int | None,
    has_sinks: bool,
):
    chunk_fwd = functools.partial(
        _chunk_fwd, scale=scale, logits_soft_cap=logits_soft_cap, impl=impl
    )
    chunk_bwd = functools.partial(
        _chunk_bwd, scale=scale, logits_soft_cap=logits_soft_cap, impl=impl
    )

    def _rotate(tree):
        n = lax.axis_size(axis_name)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return jax.tree.map(lambda x: lax.ppermute(x, axis_name, perm), tree)

    def _num_steps(n: int, c: int) -> int:
        """Ring steps with any in-window pair: step s pairs q positions with
        kv positions s·c older at chunk granularity; beyond the window the
        mask is all-False, so the ring stops early (static bound)."""
        if sliding_window is None:
            return n
        return min(n, (sliding_window + c - 2) // c + 1)

    def _fwd(q, k, v, seg_q, sinks):
        n = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        batch, c, hq, d = q.shape

        o_acc = jnp.zeros((batch, c, hq, d), jnp.float32)
        if has_sinks:
            # seed the combine at the owner chunk: the running softmax
            # denominator starts holding the sink mass (zero value), and
            # every later combine rescales it exactly
            lse_acc = jnp.broadcast_to(
                sinks.astype(jnp.float32)[None, :, None], (batch, hq, c)
            )
        else:
            lse_acc = jnp.full((batch, hq, c), -jnp.inf, jnp.float32)
        k_cur, v_cur, seg_cur = k, v, seg_q
        steps = _num_steps(n, c)
        for s in range(steps):
            if s == 0:
                o_s, lse_s = chunk_fwd(
                    q, k_cur, v_cur, seg_q, seg_cur, causal=True,
                    sliding_window=sliding_window, q_offset=0,
                )
            else:
                # non-wrapped sources sit exactly s chunks earlier (static
                # offset s·c); wrapped sources are in the future -> skip
                o_s, lse_s = lax.cond(
                    idx >= s,
                    lambda args: chunk_fwd(
                        *args, causal=False,
                        sliding_window=sliding_window, q_offset=s * c,
                    ),
                    lambda args: (
                        jnp.zeros((batch, c, hq, d), jnp.float32),
                        jnp.full((batch, hq, c), -jnp.inf, jnp.float32),
                    ),
                    (q, k_cur, v_cur, seg_q, seg_cur),
                )
            lse_new = jnp.logaddexp(lse_acc, lse_s)
            w_acc = _safe_weight(lse_acc, lse_new)[..., None].swapaxes(1, 2)
            w_s = _safe_weight(lse_s, lse_new)[..., None].swapaxes(1, 2)
            o_acc = o_acc * w_acc + o_s * w_s
            lse_acc = lse_new
            if s < steps - 1:
                k_cur, v_cur, seg_cur = _rotate((k_cur, v_cur, seg_cur))
        return o_acc.astype(q.dtype), lse_acc

    def _bwd_ring(q, k, v, seg_q, o, lse, do):
        n = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        batch, c, hq, d = q.shape

        delta = jnp.sum(
            do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
        ).transpose(0, 2, 1)  # [B, Hq, C]

        dq_acc = jnp.zeros_like(q, jnp.float32)
        k_cur, v_cur, seg_cur = k, v, seg_q
        dk_cur = jnp.zeros_like(k, jnp.float32)
        dv_cur = jnp.zeros_like(v, jnp.float32)
        zeros = lambda: (
            jnp.zeros_like(q), jnp.zeros_like(k), jnp.zeros_like(v)
        )
        steps = _num_steps(n, c)
        for s in range(steps):
            if s == 0:
                dq_s, dk_s, dv_s = chunk_bwd(
                    q, k_cur, v_cur, seg_q, seg_cur, do, lse, delta,
                    causal=True, sliding_window=sliding_window, q_offset=0,
                )
            else:
                dq_s, dk_s, dv_s = lax.cond(
                    idx >= s,
                    lambda args: chunk_bwd(
                        *args, causal=False,
                        sliding_window=sliding_window, q_offset=s * c,
                    ),
                    lambda args: zeros(),
                    (q, k_cur, v_cur, seg_q, seg_cur, do, lse, delta),
                )
            dq_acc = dq_acc + dq_s.astype(jnp.float32)
            dk_cur = dk_cur + dk_s.astype(jnp.float32)
            dv_cur = dv_cur + dv_s.astype(jnp.float32)
            # rotate the kv chunk together with its gradient accumulators
            k_cur, v_cur, seg_cur, dk_cur, dv_cur = _rotate(
                (k_cur, v_cur, seg_cur, dk_cur, dv_cur)
            )
        if steps < n:
            # the window cut the ring short: jump each dk/dv accumulator the
            # remaining n - steps hops straight home in ONE ppermute
            perm = [(i, (i + (n - steps)) % n) for i in range(n)]
            dk_cur, dv_cur = (
                lax.ppermute(dk_cur, axis_name, perm),
                lax.ppermute(dv_cur, axis_name, perm),
            )
        return dq_acc.astype(q.dtype), dk_cur.astype(k.dtype), dv_cur.astype(v.dtype)

    @jax.custom_vjp
    def ring(q, k, v, seg_q, sinks):
        o, _ = _fwd(q, k, v, seg_q, sinks)
        return o

    def ring_fwd(q, k, v, seg_q, sinks):
        o, lse = _fwd(q, k, v, seg_q, sinks)
        return o, (q, k, v, seg_q, sinks, o, lse)

    def ring_bwd(res, do):
        q, k, v, seg_q, sinks, o, lse = res
        dq, dk, dv = _bwd_ring(q, k, v, seg_q, o, lse, do)
        if has_sinks:
            # d/dsink of the sink-seeded softmax: -p_sink · delta per row,
            # summed over this device's (batch, chunk); replicated-axis
            # cotangent summation (sequence/batch) is the enclosing
            # shard_map transpose's job
            delta = jnp.sum(
                do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
            ).transpose(0, 2, 1)  # [B, Hq, C]
            p_sink = jnp.exp(sinks.astype(jnp.float32)[None, :, None] - lse)
            d_sinks = -(p_sink * delta).sum(axis=(0, 2)).astype(sinks.dtype)
        else:
            d_sinks = None
        return dq, dk, dv, None, d_sinks

    ring.defvjp(ring_fwd, ring_bwd)
    return ring
