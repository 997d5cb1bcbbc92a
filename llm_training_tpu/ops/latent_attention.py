"""Cached latent (MLA) attention: a chunk's queries against rows of
`[c_kv (latent_dim) | k_r (rope_dim) | zeros]`, ONE row a cached token.

Two forms of the same numbers (docs/inference.md, "How a layer meets its
cache"), with `W_kvb [latent, H, nope + v]` split a head into `W_uk` and
`W_uv`:

- expanded: the rows' latents go through `W_kvb` to every head's keys and
  values and the chunk attends at the heads' own widths. The projection is
  paid once a cached row, whatever the number of queries: the prefill route,
  whose kernel is `ops/pallas/mla_prefill.py`.
- absorbed: `W_uk` is folded into the query (`q~_h = W_uk_h q_nope_h`), the
  score is one dot product over the row, the output the weighted sum of the
  rows' latents taken through `W_uv` afterwards. Nothing of the heads' width
  is made of a cached row: the decode route, whose kernel is
  `ops/pallas/mla_decode.py`.

`paged_latent_attention` is the paged entry: in-place page append
(`ops/paged_attention.py:latent_append`), then, on a TPU, `mla_decode` for
one token a row and `mla_prefill` for a chunk: the pool stays in HBM, a
row's pages come by double-buffered trips, and the softmax state (and, for a
chunk, the expanded keys and values of a trip) never leaves VMEM.

`attend_rows` is the same mathematics in XLA: the CPU path, the dense-buffer
path (`LayerCache.attend_latent` without pages: one trip) and the oracle both
kernels are tested against. It runs either form over rows brought a TRIP at a
time with an online softmax, so a paged chunk reads the pages its rows have
and not the table's width (the trip count is traced); every trip's float32
scores `[B, H, S, T]` and statistics are arrays of their own, which is what
the chunk kernel keeps out of HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_training_tpu.ops.paged_attention import (
    _count_chunk_kernel_layers,
    _on_kernels,
    _over_heads,
    latent_append,
)

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# cached tokens a trip of the paged XLA path brings: a chunk of 512 queries
# and 64 heads holds [64, 512, 512] float32 scores a trip (off the chip only:
# on a TPU a chunk attends in `mla_prefill`)
_TRIP_TOKENS = 512


def absorb_queries(q_nope, q_rope, w_uk, width: int):
    """`[q~ | q_rope | zeros]`, `[B, S, H, width]`: `q~_h = W_uk_h q_nope_h`."""
    with jax.named_scope("mla_absorb"):
        folded = jnp.einsum(
            "bshd,lhd->bshl", q_nope, w_uk, preferred_element_type=jnp.float32
        ).astype(q_nope.dtype)
        q = jnp.concatenate([folded, q_rope], axis=-1)
        return jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))


def attend_rows(q_nope, q_rope, w_kvb, fetch, trips, q_pos, q_live, *, scale, absorbed):
    """q_nope `[B, S, H, nope]`, q_rope `[B, S, H, rope]` at positions `q_pos
    [B, S]` (`q_live` False: a padded query, whose output is 0) against the
    rows `fetch(i) -> (rows [B, T, W], kv_pos [., T], kv_live [., T])` gives
    for trip i of `trips` (a Python int, or traced). A query sees the live
    rows at or before its own position. Returns `[B, S, H, v]`."""
    latent, heads, _ = w_kvb.shape
    nope, rope = q_nope.shape[-1], q_rope.shape[-1]
    w_uk, w_uv = w_kvb[..., :nope], w_kvb[..., nope:]
    batch, seq = q_pos.shape
    f32 = jnp.float32
    if absorbed:
        q_abs = absorb_queries(q_nope, q_rope, w_uk, latent + rope)

    def one_trip(i, carry):
        m_prev, l_prev, acc = carry
        rows, kv_pos, kv_live = fetch(i)
        rows = rows.astype(q_nope.dtype)
        c, k_r = rows[..., :latent], rows[..., latent:latent + rope]
        if absorbed:
            with jax.named_scope("mla_attend"):
                s = jnp.einsum(
                    "bshw,btw->bhst", q_abs, rows[..., :latent + rope], preferred_element_type=f32
                )
            values, product = c, "bhst,btl->bhsl"
        else:
            with jax.named_scope("mla_expand"):
                kv = jnp.einsum("btl,lhe->bthe", c, w_kvb, preferred_element_type=f32)
                kv = kv.astype(c.dtype)
            with jax.named_scope("mla_attend"):
                s = jnp.einsum(
                    "bshd,bthd->bhst", q_nope, kv[..., :nope], preferred_element_type=f32
                ) + jnp.einsum("bshr,btr->bhst", q_rope, k_r, preferred_element_type=f32)
            values, product = kv[..., nope:], "bhst,bthv->bhsv"
        with jax.named_scope("mla_attend"):
            mask = (kv_pos[:, None, :] <= q_pos[:, :, None]) & kv_live[:, None, :]
            mask = (mask & q_live[:, :, None])[:, None]  # [B, 1, S, T]
            s = jnp.where(mask, s * scale, _MASK_VALUE)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            acc = acc * alpha + jnp.einsum(
                product, p.astype(values.dtype), values, preferred_element_type=f32
            )
            return m_new, l_prev * alpha + p.sum(axis=-1, keepdims=True), acc

    out_dim = latent if absorbed else w_uv.shape[-1]
    carry = (
        jnp.full((batch, heads, seq, 1), -jnp.inf, f32),
        jnp.zeros((batch, heads, seq, 1), f32),
        jnp.zeros((batch, heads, seq, out_dim), f32),
    )
    if isinstance(trips, int):
        for i in range(trips):
            carry = one_trip(i, carry)
    else:
        carry = jax.lax.fori_loop(0, trips, one_trip, carry)
    _, l, acc = carry
    with jax.named_scope("mla_attend"):
        # a fully masked query (padding) emits exactly 0
        out = (acc / jnp.where(l == 0.0, 1.0, l)).astype(q_nope.dtype)
    if absorbed:
        return project_values(out.swapaxes(1, 2), w_uv)
    return out.swapaxes(1, 2)


def project_values(weighted_latents, w_uv):
    """The absorbed form's last step: `[B, S, H, latent]` through `W_uv`."""
    with jax.named_scope("mla_absorb"):
        return jnp.einsum(
            "bshl,lhv->bshv", weighted_latents, w_uv, preferred_element_type=jnp.float32
        ).astype(weighted_latents.dtype)


def paged_latent_attention(
    q_nope, q_rope, row, w_kvb, pool, lengths, block_tables, *,
    layer=None, segment_ids=None, scale: float, absorbed: bool | None = None,
    impl: str = "auto",
):
    """Append this chunk's latent rows `row [B, S, W]` through the block
    table, then attend each row of the batch against its own pages. `pool` is
    one block's latent pool `[N, 1, page, W]` or, with `layer`, every MLA
    block's `[L, N, 1, page, W]` as the layer loop carries it, addressed as
    one pool of `L * N` blocks through a shifted table (`ops/
    paged_attention.py:paged_cached_attention`). `lengths [B]` counts the
    tokens each row holds BEFORE this chunk. `absorbed`: None takes the
    absorbed form for one token a row and the expanded one for a chunk.
    impl: 'auto' (the Pallas kernels on a TPU: the page writer, `mla_decode`
    for one token a row, `mla_prefill` for a chunk; XLA elsewhere) | 'pallas'
    (kernels forced, interpreted off-TPU) | 'xla'. Returns `(out [B, S, H,
    v], the pool)`."""
    latent, _, _ = w_kvb.shape
    nope = q_nope.shape[-1]
    batch, seq = q_nope.shape[:2]
    stack_shape, trash = pool.shape, 0
    if layer is not None:
        trash = jnp.asarray(layer * stack_shape[1], block_tables.dtype)
        block_tables = block_tables + trash
        pool = pool.reshape(-1, *stack_shape[2:])
    _, _, page_size, width = pool.shape
    lengths = lengths.astype(jnp.int32)
    pool = latent_append(
        pool, row[:, :, None, :], lengths, block_tables, segment_ids, impl, trash
    )
    if absorbed is None:
        absorbed = seq == 1
    if seq == 1 and absorbed and _on_kernels(impl):
        from llm_training_tpu.ops.pallas.mla_decode import mla_decode_attention

        q = absorb_queries(q_nope, q_rope, w_kvb[..., :nope], width)
        with jax.named_scope("mla_attend"):
            weighted = _over_heads(
                lambda q, pool, tables, lens: mla_decode_attention(
                    q, pool, tables, lens, latent_dim=latent, scale=scale
                ),
                (q[:, 0], pool, block_tables, lengths + 1), (1, None, None, None), 0,
            )[:, None]
        return project_values(weighted, w_kvb[..., nope:]), pool.reshape(stack_shape)
    if seq > 1 and not absorbed and _on_kernels(impl):
        from llm_training_tpu.ops.pallas.mla_prefill import mla_prefill_attention

        with jax.named_scope("mla_attend"):
            out = _over_heads(
                lambda q_nope, q_rope, w_kvb, pool, tables, lens: mla_prefill_attention(
                    q_nope, q_rope, w_kvb, pool, tables, lens, scale=scale
                ),
                (q_nope, q_rope, w_kvb, pool, block_tables, lengths),
                (2, 2, 1, None, None, None), 0,
            )
            if segment_ids is not None:
                # a padded query emits exactly 0, as on the XLA path
                out = jnp.where((segment_ids > 0)[:, :, None, None], out, 0)
        _count_chunk_kernel_layers(1 if layer is None else stack_shape[0], "latent")
        return out, pool.reshape(stack_shape)

    num_pages = block_tables.shape[1]
    trip_pages = min(num_pages, max(1, _TRIP_TOKENS // page_size))
    within = jnp.arange(trip_pages * page_size, dtype=jnp.int32)

    def fetch(i):
        # trip i of each row's pages, in slot order; a page past the table is dead
        pages = i * trip_pages + jnp.arange(trip_pages, dtype=jnp.int32)
        blocks = jnp.take(block_tables, jnp.minimum(pages, num_pages - 1), axis=1)
        rows = pool[blocks][:, :, 0].reshape(batch, trip_pages * page_size, width)
        kv_live = jnp.repeat(pages < num_pages, page_size)
        return rows, (i * trip_pages * page_size + within)[None], kv_live[None]

    q_pos = lengths[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]
    q_live = jnp.ones((batch, seq), bool) if segment_ids is None else segment_ids > 0
    # the pages that hold a token of some row after this chunk
    trips = -(-jnp.max(lengths + seq) // (trip_pages * page_size))
    out = attend_rows(
        q_nope, q_rope, w_kvb, fetch, trips, q_pos, q_live, scale=scale, absorbed=absorbed
    )
    return out, pool.reshape(stack_shape)
