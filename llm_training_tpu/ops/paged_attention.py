"""Paged KV-cache attention: scatter-append + ragged attention dispatch.

The serving counterpart of the dense cached-attention path in the shared
decoder stacks (docs/serving.md). The cache is a POOL of fixed-size blocks
(`[num_blocks, kv_heads, block_size, head_dim]` per layer) owned by
`serve/paged_cache.py`; each row addresses it through a block table and
its own length — so this module does per-row scatter writes and per-row
ragged reads where the dense path does one `dynamic_update_slice` at a
shared index.

Two attention paths behind one call:

- single-token decode on TPU (or `impl='pallas'`): the Pallas ragged
  paged-decode kernel (`ops/pallas/paged_attention.py`) — per-row lengths,
  block-table gathers in the DMA engine. On a TPU the kernel always
  compiles; a head_dim/page Mosaic cannot tile raises there;
- everything else (chunked prefill q_len > 1, CPU tier-1): an XLA gather
  path — block-table gather to a dense `[B, P*page, H, D]` view plus a
  per-row position mask into the reference einsum attention. Same math,
  shape-static, differentiable-free (decode only), and the oracle the
  kernel is tested against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_training_tpu.ops.attention import _xla_attention


def paged_append(
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    segment_ids: jnp.ndarray | None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter this chunk's k/v `[B, S, H, D]` into the pool at each row's
    next positions (`lengths[b] + i`). Padded chunk positions (segment id
    0) and any out-of-table position are redirected to the reserved trash
    block 0 — garbage can land there but never in a live block."""
    batch, seq = k.shape[:2]
    page_size = pool_k.shape[2]
    num_pages = block_tables.shape[1]
    pos = lengths[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]  # [B, S]
    valid = (
        jnp.ones((batch, seq), bool) if segment_ids is None else segment_ids > 0
    )
    valid &= pos < num_pages * page_size
    page = jnp.take_along_axis(
        block_tables, jnp.minimum(pos // page_size, num_pages - 1), axis=1
    )
    page = jnp.where(valid, page, 0)
    offset = jnp.where(valid, pos % page_size, 0)
    # advanced indices split by the head slice: the [B, S] index dims lead
    # the update window, which is k/v's own [B, S, H, D]
    return (
        pool_k.at[page, :, offset].set(k.astype(pool_k.dtype)),
        pool_v.at[page, :, offset].set(v.astype(pool_v.dtype)),
    )


def _gather_attention(
    q, pool_k, pool_v, lengths, block_tables, segment_ids,
    sliding_window, logits_soft_cap, scale,
):
    """XLA fallback: dense gather of each row's pages + per-row causal
    mask. `lengths` here is the PRE-append count, so q position i of row b
    sits at absolute slot lengths[b] + i."""
    batch, seq = q.shape[:2]
    _, kv_heads, page_size, head_dim = pool_k.shape
    num_pages = block_tables.shape[1]

    def gather(pool):
        # [B, P, H, page, D] -> [B, P*page, H, D]: row b's cache in slot order
        return pool[block_tables].swapaxes(2, 3).reshape(
            batch, num_pages * page_size, kv_heads, head_dim
        )

    gk, gv = gather(pool_k), gather(pool_v)
    q_pos = lengths[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]
    kv_pos = jnp.arange(num_pages * page_size, dtype=jnp.int32)
    # [B, 1, S, KV] — True = attend; the causal term alone hides unwritten
    # slots (their position is ahead of every query) and other requests'
    # blocks never appear in this row's table
    mask = kv_pos[None, None, None, :] <= q_pos[:, None, :, None]
    if sliding_window is not None:
        mask &= q_pos[:, None, :, None] - kv_pos[None, None, None, :] < sliding_window
    if segment_ids is not None:
        mask &= (segment_ids > 0)[:, None, :, None]
    return _xla_attention(
        q, gk.astype(q.dtype), gv.astype(q.dtype), mask, scale, logits_soft_cap
    )


def _kernel_under_mesh(q, pool_k, pool_v, block_tables, lengths, **kwargs):
    """The paged-decode kernel on whatever mesh is active. Like the flash
    kernel (`ops/attention.py:_flash_under_mesh`), a Mosaic kernel cannot be
    partitioned by GSPMD, so on a multi-device mesh it runs in a shard_map:
    heads over `tensor` — how the pool's kv heads and q's heads are already
    sharded — and every other axis replicated, as the pool's block axis is
    (each data-parallel rank owns its whole pool)."""
    from llm_training_tpu.ops.pallas.paged_attention import paged_decode_attention
    from llm_training_tpu.parallel.mesh import TENSOR_AXIS, active_mesh

    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return paged_decode_attention(
            q, pool_k, pool_v, block_tables, lengths, **kwargs
        )
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[TENSOR_AXIS]
    head_axis = (
        TENSOR_AXIS if q.shape[1] % tp == 0 and pool_k.shape[1] % tp == 0 else None
    )
    spec_q = P(None, head_axis, None)
    spec_pool = P(None, head_axis, None, None)
    return jax.shard_map(
        lambda q, pk, pv, tables, lens: paged_decode_attention(
            q, pk, pv, tables, lens, **kwargs
        ),
        mesh=mesh,
        in_specs=(spec_q, spec_pool, spec_pool, P(), P()),
        out_specs=spec_q,
        check_vma=False,
    )(q, pool_k, pool_v, block_tables, lengths)


def paged_cached_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    layer_kv: tuple[jnp.ndarray, jnp.ndarray],
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    *,
    segment_ids: jnp.ndarray | None = None,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    impl: str = "auto",
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Append this chunk's k/v through the block table, then attend each
    row against its own cache. q/k/v `[B, S, H*, D]` (S == 1 on the decode
    hot path, S == chunk width during chunked prefill); `layer_kv` is this
    layer's pool pair; `lengths [B]` counts tokens already in each row's
    cache BEFORE this chunk. Returns `(out [B, S, Hq, D], new pool pair)`.

    impl: 'auto' (Pallas kernel for single-token decode on TPU, XLA gather
    otherwise) | 'pallas' (kernel forced — interpreted off-TPU) | 'xla'.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pool_k, pool_v = layer_kv
    lengths = lengths.astype(jnp.int32)
    ck, cv = paged_append(pool_k, pool_v, k, v, lengths, block_tables, segment_ids)

    seq = q.shape[1]
    use_kernel = seq == 1 and (
        impl == "pallas" or (impl == "auto" and jax.default_backend() == "tpu")
    )
    if use_kernel:
        out = _kernel_under_mesh(
            q[:, 0], ck, cv, block_tables, lengths + 1,
            scale=scale, sliding_window=sliding_window,
            logits_soft_cap=logits_soft_cap,
        )[:, None]
    else:
        out = _gather_attention(
            q, ck, cv, lengths, block_tables, segment_ids,
            sliding_window, logits_soft_cap, scale,
        )
    return out, (ck, cv)
