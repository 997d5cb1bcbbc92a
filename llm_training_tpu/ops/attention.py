"""Attention with segment-id packing (no-cross-contamination), GQA,
sliding window, and soft-capping.

Capability parity: reference `src/llm_training/ops/attention_op.py` — the
entire 4-D mask-building + varlen unpad/repad machinery
(`attention_op.py:286-535`) collapses on TPU into *segment ids*: the
reference's per-document attention-mask ids (1..N, 0 = padding) are used
directly as segment ids, and the mask `seg_q == seg_kv & causal & window`
reproduces its block-diagonal packed mask (`attention_op.py:305-314`) with no
unpadding (static shapes are required by XLA anyway; packed batches waste no
FLOPs on padding because packing fills rows to max_length).

`flash_attention_forward`'s dispatch surface (`attention_op.py:538-654`:
causal, sliding window, softcap, varlen-vs-dense) maps onto the `impl=`
argument: 'xla' is the einsum/softmax reference path (fp32 accumulation),
'pallas' is the flash kernel in `ops/pallas/flash_attention.py`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def segment_ids_from_attention_mask(attention_mask: jnp.ndarray) -> jnp.ndarray:
    """The reference's document-id attention mask *is* a segment-id tensor:
    values 1..N identify packed documents, 0 marks padding
    (`attention_op.py:286-302`)."""
    return attention_mask.astype(jnp.int32)


def make_attention_mask(
    segment_ids_q: jnp.ndarray | None,
    segment_ids_kv: jnp.ndarray | None,
    q_len: int,
    kv_len: int,
    causal: bool = True,
    sliding_window: int | None = None,
    q_offset: int | jnp.ndarray = 0,
) -> jnp.ndarray:
    """Boolean mask [batch, 1, q_len, kv_len] (True = attend).

    `q_offset` is the absolute position of query row 0 in the kv sequence:
    a static int for ring attention (q is a rotating kv chunk's neighbour),
    or a TRACED scalar for KV-cache decoding (`infer/`), where kv is the
    whole static-shape cache and the offset is the dynamic append index —
    row `q_offset + i` of this mask must equal row `q_offset + i` of the
    full dense q_len==kv_len mask (the invariant the decode path relies
    on; tests/test_ops.py::test_make_attention_mask_q_offset_decode_rows).
    Positions the cache has not reached yet fall away via the causal term
    (kv_pos > q_pos) and the `seg_kv > 0` term (unwritten slots carry
    segment id 0)."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    mask = jnp.ones((q_len, kv_len), dtype=bool)
    if causal:
        mask &= kv_pos <= q_pos
    if sliding_window is not None:
        mask &= q_pos - kv_pos < sliding_window
    mask = mask[None, None]  # [1, 1, q, kv]
    if segment_ids_q is not None:
        seg_q = segment_ids_q[:, None, :, None]
        seg_kv = segment_ids_kv[:, None, None, :]
        mask = mask & (seg_q == seg_kv) & (seg_q > 0) & (seg_kv > 0)
    return mask


def _xla_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray | None,
    scale: float,
    logits_soft_cap: float | None,
    sinks: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Reference einsum attention, fp32 softmax, GQA without repeating kv.

    q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D]; Hq % Hkv == 0.
    """
    batch, q_len, num_q_heads, head_dim = q.shape
    num_kv_heads = k.shape[2]
    if num_q_heads % num_kv_heads != 0:
        raise ValueError(
            f"num_q_heads ({num_q_heads}) must be divisible by num_kv_heads ({num_kv_heads})"
        )
    group = num_q_heads // num_kv_heads

    qg = q.reshape(batch, q_len, num_kv_heads, group, head_dim)
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    ) * scale
    if logits_soft_cap is not None:
        scores = logits_soft_cap * jnp.tanh(scores / logits_soft_cap)
    if mask is not None:
        scores = jnp.where(mask[:, :, None], scores, _MASK_VALUE)
    if sinks is not None:
        # gpt-oss attention sinks: one learned logit per query head joins
        # each row's softmax denominator (with zero value), damping rows
        # whose real scores are all weak
        sink = sinks.reshape(num_kv_heads, group)[None, :, :, None]
        m = jnp.maximum(scores.max(axis=-1), sink)
        p = jnp.exp(scores - m[..., None])
        if mask is not None:
            p = jnp.where(mask[:, :, None], p, 0.0)
        denom = p.sum(axis=-1) + jnp.exp(sink - m)
        probs = p / denom[..., None]
    else:
        probs = jax.nn.softmax(scores, axis=-1)
    if mask is not None:
        # fully-masked rows (padding / empty ring chunks) emit exactly 0, not
        # the mean of v that a softmax over all-masked scores would give —
        # the invariant the flash kernel and ring combiner provide
        probs = jnp.where(mask[:, :, None].any(-1, keepdims=True), probs, 0.0)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
    out = out.reshape(batch, q_len, num_q_heads, head_dim)
    # same remat tag the flash kernel carries, so
    # recompute_granularity='selective' saves the attention output on this
    # path too (its backward still rebuilds softmax internals from q/k —
    # autodiff residuals, unlike the flash kernel's O/LSE-only backward)
    return checkpoint_name(out, "flash_out")


def _flash_under_mesh(q, k, v, segment_ids, q_segment_ids, sinks, **kwargs):
    """The flash kernel on whatever mesh is active. GSPMD cannot partition a
    Mosaic kernel ("Mosaic kernels cannot be automatically partitioned" —
    the TPU compiler's refusal of any sharded step that holds one), so on a
    multi-device mesh the kernel runs inside a shard_map: batch over the
    data/fsdp/expert axes, heads over tensor. Attention needs no collective
    across either, so the shards match what the surrounding layers already
    hold. A sequence-sharded input is gathered here — ring attention
    (`parallel/ring_attention.py`) is the path that keeps it sharded."""
    from llm_training_tpu.ops.pallas.flash_attention import flash_attention
    from llm_training_tpu.parallel.mesh import active_mesh

    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(
            q, k, v, segment_ids=segment_ids, q_segment_ids=q_segment_ids,
            sinks=sinks, **kwargs,
        )
    from jax.sharding import PartitionSpec as P

    from llm_training_tpu.parallel.ring_attention import batch_head_axes

    batch_axes, head_axis = batch_head_axes(mesh, q, k)
    spec_qkv = P(batch_axes, None, head_axis, None)
    # optional operands ride as a dict so absent ones need no placeholder
    optional = {"segment_ids": segment_ids, "q_segment_ids": q_segment_ids, "sinks": sinks}
    optional = {name: x for name, x in optional.items() if x is not None}
    optional_specs = {
        name: P(head_axis) if name == "sinks" else P(batch_axes, None)
        for name in optional
    }

    def run(q, k, v, optional):
        return flash_attention(
            q, k, v,
            segment_ids=optional.get("segment_ids"),
            q_segment_ids=optional.get("q_segment_ids"),
            sinks=optional.get("sinks"),
            **kwargs,
        )

    return jax.shard_map(
        run,
        mesh=mesh,
        in_specs=(spec_qkv, spec_qkv, spec_qkv, optional_specs),
        out_specs=spec_qkv,
        check_vma=False,
    )(q, k, v, optional)


def dot_product_attention(
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
    impl: str = "auto",
    sinks: jnp.ndarray | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
) -> jnp.ndarray:
    """Multi-head attention over packed sequences.

    q: [batch, q_len, num_q_heads, head_dim]
    k, v: [batch, kv_len, num_kv_heads, head_dim]
    segment_ids: [batch, kv_len] int (0 = padding, 1..N = packed documents)
    q_segment_ids: [batch, q_len]; defaults to `segment_ids` when q and kv
        are the same sequence (q_len == kv_len). Required when packing is
        used with q_len != kv_len (e.g. ring-attention chunks).
    q_offset: absolute position of query row 0 within the kv sequence, for
        causal masking of cross-length chunks.
    impl: 'auto' (pallas flash kernel on TPU, einsum path elsewhere) |
        'xla' | 'pallas' (forced; interpreted off-TPU).
    sinks: [num_q_heads] learned per-head sink logits (gpt-oss); joins each
        softmax denominator with zero value (both impls — the flash kernel
        seeds its online-softmax denominator with the sink mass).
    block_q/block_k/bwd_block_q/bwd_block_k: flash-kernel tile overrides
        (fwd and bwd independently); None resolves at call time through
        `ops/pallas/tuning.py` (env > tuning table > default). Ignored on
        the XLA path.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if q_segment_ids is None and segment_ids is not None:
        if q.shape[1] != k.shape[1]:
            raise ValueError(
                "q_segment_ids is required when segment_ids is given and "
                f"q_len ({q.shape[1]}) != kv_len ({k.shape[1]})"
            )
        q_segment_ids = segment_ids

    use_pallas = impl == "pallas" or (impl == "auto" and jax.default_backend() == "tpu")
    if use_pallas:
        return _flash_under_mesh(
            q, k, v, segment_ids, q_segment_ids, sinks,
            causal=causal,
            sliding_window=sliding_window,
            logits_soft_cap=logits_soft_cap,
            scale=scale,
            q_offset=q_offset,
            block_q=block_q,
            block_k=block_k,
            bwd_block_q=bwd_block_q,
            bwd_block_k=bwd_block_k,
        )

    mask = None
    if segment_ids is not None or causal or sliding_window is not None:
        mask = make_attention_mask(
            q_segment_ids, segment_ids, q.shape[1], k.shape[1],
            causal=causal, sliding_window=sliding_window, q_offset=q_offset,
        )
    return _xla_attention(q, k, v, mask, scale, logits_soft_cap, sinks=sinks)
