"""Pallas TPU ragged decode over a paged LATENT (MLA) pool, and its page writer.

The latent counterpart of `paged_attention.py`. A latent-attention block
caches ONE row a token, `[c_kv (latent_dim) | k_r (rope_dim) | zeros]`,
shared by all its heads, and decodes in the absorbed form: the caller folds
`W_uk` into the query, so head h's query is `[q~_h | q_rope_h | zeros]` of the
row's width, its score against a cached row is one dot product over the
row, and its output is the probability-weighted sum of the rows' first
`latent_dim` values (`W_uv` is applied by the caller, afterwards). The
pool is therefore read ONCE, as keys and as values, and nothing of 64 heads'
width is cached or fetched.

`mla_decode` (one grid step a row, hand-made page fetches, online softmax):
the block table and lengths ride as scalar prefetch, the pool `[blocks, 1,
page, width]` stays in HBM. A row walks its live pages, `n` a trip, into one
slot of a double-buffered `[2, n*page, width]` VMEM scratch; the next trip's
copies are started before this trip's slot is reduced; pages past the row's
last are never fetched and their rows in the slot are zeroed. Scores are
`[heads, n*page]` in float32; the probabilities meet the rows again in the
pool's dtype (bfloat16 on the chip) with float32 accumulation.

`latent_page_write`: the append's writer, `pool[blocks[i]] = pages[i]`, whole
pages HBM to HBM into the pool aliased to the output (`paged_attention.py:
write_pages`, for one pool).

Off-TPU both run interpreted; on a TPU they always compile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_training_tpu.ops.pallas import resolve_interpret

_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
_SUBLANES = 8
# VMEM the double-buffered page scratch may take (2 slots of rows)
_SCRATCH_BYTES = 2 * 1024 * 1024


def latent_pages_per_trip(page_size: int, width: int, itemsize: int, num_pages: int) -> int:
    """Consecutive logical pages of a row one trip fetches: as many as keep
    `[2 slots, n*page, width]` inside `_SCRATCH_BYTES`, whole multiples of 8
    pages where there are that many (a trip's tokens then fill whole lanes of
    the score tile), at most the table's width."""
    n = max(1, min(_SCRATCH_BYTES // (2 * page_size * width * itemsize), num_pages))
    return n - n % 8 if n >= 8 else n


def _mla_decode_kernel(
    tables,    # scalar prefetch: [B, P] pool block of (row, logical page)
    lens,      # scalar prefetch: [B] tokens written, this step's included
    q_ref,     # [1, H, W] this row's absorbed queries
    pool_hbm,  # [N, 1, page, W] the whole latent pool, left in place
    o_ref,     # [1, H, latent_dim]
    buf,       # VMEM [2, n*page, W] double-buffered trip of pages
    sems,      # DMA semaphores [2 slots]
    *,
    page_size: int,
    trip_pages: int,
    latent_dim: int,
    scale: float,
):
    b = pl.program_id(0)
    heads = q_ref.shape[1]
    trip_tokens = trip_pages * page_size
    q_pos = lens[b] - 1  # the decoded token's cache slot: rows 0..q_pos are its keys
    live_pages = pl.cdiv(lens[b], page_size)
    trips = pl.cdiv(live_pages, trip_pages)

    def page_rows(i):
        return pl.ds(pl.multiple_of(i * page_size, page_size), page_size)

    def for_live_pages(trip, slot, act):
        start = trip * trip_pages
        live = jnp.minimum(trip_pages, live_pages - start)

        def one(i, _):
            act(pltpu.make_async_copy(
                pool_hbm.at[tables[b, start + i], 0], buf.at[slot, page_rows(i), :],
                sems.at[slot],
            ))

        lax.fori_loop(0, live, one, None)
        return start, live

    for_live_pages(0, 0, lambda copy: copy.start())
    # bf16 queries against a bf16 pool feed the matrix unit as they are
    mm_dtype = q_ref.dtype if q_ref.dtype == buf.dtype else jnp.float32
    q = q_ref[0].astype(mm_dtype)  # [H, W]

    def trip_body(trip, carry):
        m_prev, l_prev, acc = carry
        slot = trip % 2

        @pl.when(trip + 1 < trips)
        def _next_fetch():
            for_live_pages(trip + 1, 1 - slot, lambda copy: copy.start())

        start, live = for_live_pages(trip, slot, lambda copy: copy.wait())

        # rows of the slot past the row's last page hold an earlier trip's
        # (or nothing yet): masked out of the scores, and zeroed so that
        # 0 * whatever-was-there stays 0 in the value product
        def zero_page(i, _):
            buf[slot, page_rows(i), :] = jnp.zeros((page_size, buf.shape[-1]), buf.dtype)

        lax.fori_loop(live, trip_pages, zero_page, None)

        rows = buf[slot].astype(mm_dtype)  # [T, W]
        s = lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [H, T]
        kv_pos = start * page_size + lax.broadcasted_iota(jnp.int32, (1, trip_tokens), 1)
        mask = kv_pos <= q_pos
        s = jnp.where(mask, s, _MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        acc = acc * alpha + jnp.dot(
            p.astype(mm_dtype), rows[:, :latent_dim], preferred_element_type=jnp.float32
        )
        return m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True), acc

    _, l, acc = lax.fori_loop(
        0, trips, trip_body,
        (
            jnp.full((heads, 1), -jnp.inf, jnp.float32),
            jnp.zeros((heads, 1), jnp.float32),
            jnp.zeros((heads, latent_dim), jnp.float32),
        ),
    )
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def mla_decode_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    latent_dim: int,
    scale: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One ragged decode step in the absorbed form: q `[B, H, W]` (one token
    a row; `[q~ | q_rope | zeros]` a head) against each row's pages of the
    latent pool `[N, 1, page, W]`. `lengths [B]` counts tokens written
    INCLUDING this step's (the caller appends first); an idle row carries
    length 1 and a trash-block table. Returns `[B, H, latent_dim]`: the
    probability-weighted sum of the rows' first `latent_dim` values, which
    the caller takes through `W_uv`."""
    batch, heads, width = q.shape
    _, one, page_size, pool_width = pool.shape
    if one != 1 or pool_width != width:
        raise ValueError(f"latent pool {pool.shape} does not match queries {q.shape}")
    interpret = resolve_interpret(interpret)
    if not interpret and page_size % _SUBLANES:
        raise ValueError(
            f"the compiled mla_decode kernel wants whole sublane tiles a page: got page {page_size}"
        )
    num_pages = block_tables.shape[1]
    trip_pages = latent_pages_per_trip(page_size, width, pool.dtype.itemsize, num_pages)
    row = lambda last: pl.BlockSpec((1, heads, last), lambda b, tables, lens: (b, 0, 0))
    return pl.pallas_call(
        functools.partial(
            _mla_decode_kernel, page_size=page_size, trip_pages=trip_pages,
            latent_dim=latent_dim, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch,),
            in_specs=[row(width), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row(latent_dim),
            scratch_shapes=[
                pltpu.VMEM((2, trip_pages * page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, heads, latent_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name="mla_decode",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)


def _write_kernel(blocks, live, pages, pool_in, pool, sem):
    del pool_in  # aliased to `pool`: the same memory

    def for_live_pages(act):
        def one(i, _):
            @pl.when(live[i] != 0)
            def _():
                act(pltpu.make_async_copy(pages.at[i], pool.at[blocks[i]], sem.at[0]))

        lax.fori_loop(0, blocks.shape[0], one, None)

    # all in flight, then a wait a copy: they are of one size
    for_live_pages(lambda copy: copy.start())
    for_live_pages(lambda copy: copy.wait())


def write_latent_pages(
    pool: jnp.ndarray,
    pages: jnp.ndarray,
    blocks: jnp.ndarray,
    live: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """`pool[blocks[i]] = pages[i]` wherever `live[i]`, for the latent pool
    `[N, 1, page, W]` and pages `[M, 1, page, W]` of its dtype: one
    HBM-to-HBM copy a page into the pool aliased to the output, so nothing of
    the pool's shape is produced (`paged_attention.py:write_pages`)."""
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[any_space] * 2,
            out_specs=any_space,
            scratch_shapes=[pltpu.SemaphoreType.DMA((1,))],
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},  # operands count the two scalar-prefetch arrays
        interpret=resolve_interpret(interpret),
        name="latent_page_write",
    )(blocks.astype(jnp.int32), live.astype(jnp.int32), pages, pool)
