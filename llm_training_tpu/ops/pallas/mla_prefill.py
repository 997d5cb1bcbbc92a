"""Pallas TPU chunk attention over a paged LATENT (MLA) pool: `mla_prefill`.

The chunk's counterpart of `mla_decode.py`, in the EXPANDED form (`ops/
latent_attention.py`): a cached row `[c_kv (latent) | k_r (rope) | zeros]`
goes through `W_kvb` to each head's `[k_nope | v]`, a head's score against
it is `q_nope . k_nope + q_rope . k_r`, its output the probability-weighted
sum of the head's values. At a chunk's width the expansion is paid once a
cached row and the absorbed form would cost 3.4 times the operations.

One grid step is (row, block of heads, block of queries). The pool `[blocks,
1, page, width]` stays in HBM, the block table and the lengths BEFORE the
chunk ride as scalar prefetch, and the row's pages come `n` a trip into one
slot of a double-buffered `[2, n*page, width]` VMEM scratch, the next trip's
copies started before this trip's slot is reduced (`mla_decode`'s walk). A
grid step holds its heads' slice of `W_kvb` and their queries; a trip's
latents are expanded to ONE head's keys and values at a time, in VMEM,
rounded to the queries' dtype as the XLA path rounds them, and meet that
head's queries there: scores `[queries, n*page]` in float32, the running
maximum, sum and float32 accumulator of every head of the block in VMEM
scratch across the trips. Nothing with a key axis leaves the kernel. A block
of queries walks from page 0 to the page of its last query's own key; a trip
every key of which every query of the block sees skips the mask. The maximum
starts at `_M_FLOOR`, far above the mask's value, so a masked score weighs
exactly 0 and a query no key is visible to emits exactly 0.

The heads a grid step takes and the pages a trip brings follow the shapes
the call sees (`head_block`, `chunk_latent_pages_per_trip`): 64 heads and
128 want the same algorithm with different tile counts.

Off-TPU the kernel runs interpreted; on a TPU it always compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_training_tpu.ops.pallas import resolve_interpret
from llm_training_tpu.ops.pallas.paged_attention import (
    _LANES,
    _M_FLOOR,
    _MASK_VALUE,
    _Q_ALIGN,
    _SUBLANES,
)

# the queries one grid step takes: a serving chunk whole, so that a trip's
# latents are expanded once; a longer chunk goes in equal blocks
_BLOCK_QUERIES = 512
# cached tokens a trip brings: the width of a head's score tile
_TRIP_TOKENS = 512
# VMEM the kernel may take (of the v5e's 128 MiB), and what of it the blocks
# that grow with the heads of a grid step may: the double-buffered `W_kvb`
# slice, queries and outputs, the accumulator and the softmax state; the rest
# is the page scratch and one head's temporaries
_VMEM_BYTES = 96 * 1024 * 1024
_HEAD_BLOCK_BYTES = 32 * 1024 * 1024
# heads of a grid step whose tiles the compiler may schedule together: one
# head's expansion and value products beside another's exponentials
_HEAD_UNROLL = 2


def chunk_latent_pages_per_trip(page_size: int, num_pages: int) -> int:
    """Consecutive logical pages of a row one trip fetches: `_TRIP_TOKENS`
    tokens, in whole runs of 128 (the score tile's lanes) where a trip holds
    one, at most the table's width."""
    pages = max(1, min(_TRIP_TOKENS // page_size, num_pages))
    run = max(1, _LANES // page_size)
    return pages - pages % run if pages >= run else pages


def query_block(seq: int) -> int:
    """How many of a chunk's queries one grid step takes: the chunk whole up
    to `_BLOCK_QUERIES`, else equal blocks, in whole (16, 128) tiles."""
    blocks = -(-seq // _BLOCK_QUERIES)
    return -(-seq // (blocks * _Q_ALIGN)) * _Q_ALIGN


def head_block(
    heads: int, block_q: int, latent: int, nope: int, tail: int, v_dim: int, itemsize: int
) -> int:
    """How many heads one grid step takes: the most that divide `heads`, keep
    what grows with them inside `_HEAD_BLOCK_BYTES` (a head's `W_kvb` slice,
    its queries, `tail` wide on the rotary side, and outputs, each double
    buffered; its float32 accumulator and the two softmax statistics, a lane
    tile wide) and make every block's last axis whole lane tiles (or take all
    heads, whose blocks are the arrays)."""
    a_head = (
        2 * itemsize * (latent * (nope + v_dim) + block_q * (nope + tail + v_dim))
        + 4 * block_q * (v_dim + 2 * _LANES)
    )
    fits = [
        n for n in range(1, heads + 1)
        if heads % n == 0 and n * a_head <= _HEAD_BLOCK_BYTES and (
            n == heads or not any(n * width % _LANES for width in (nope, tail, v_dim))
        )
    ]
    return max(fits, default=heads)


def _mla_prefill_kernel(
    tables,    # scalar prefetch: [B, P] pool block of (row, logical page)
    lens,      # scalar prefetch: [B] tokens the row held BEFORE this chunk
    qn_ref,    # [1, bq, Hb * nope] a block of queries, a block of heads abreast
    qr_ref,    # [1, bq, Hb * tail] their rotary parts, zeros up to the row's tail
    w_ref,     # [latent, Hb * (nope + v)] those heads' slice of W_kvb
    pool_hbm,  # [N, 1, page, W] the whole latent pool, left in place
    o_ref,     # [1, bq, Hb * v]
    buf,       # VMEM [2, n*page, W] double-buffered trip of pages
    sems,      # DMA semaphores [2 slots]
    m_scr,     # VMEM [Hb, bq, 128] float32: the running maximum, in lane 0
    l_scr,     # VMEM [Hb, bq, 128] float32: the running sum, in lane 0
    acc_scr,   # VMEM [Hb, bq, v] float32
    *,
    page_size: int,
    trip_pages: int,
    latent: int,
    nope: int,
    scale: float,
):
    b, qb = pl.program_id(0), pl.program_id(2)
    heads, block_q, v_dim = acc_scr.shape
    tail = buf.shape[-1] - latent
    trip_tokens = trip_pages * page_size
    # query i of the chunk sits at cache slot lens[b] + i; the caller appended
    # the chunk BEFORE attention, so its own rows are in the pool
    q_lo = lens[b] + qb * block_q
    # the block's keys end at its last query's own
    end_page = jnp.minimum((q_lo + block_q - 1) // page_size + 1, tables.shape[1])
    trips = pl.cdiv(end_page, trip_pages)

    def page_rows(i):
        return pl.ds(pl.multiple_of(i * page_size, page_size), page_size)

    def for_live_pages(trip, slot, act):
        start = trip * trip_pages
        live = jnp.minimum(trip_pages, end_page - start)

        def one(i, _):
            act(pltpu.make_async_copy(
                pool_hbm.at[tables[b, start + i], 0], buf.at[slot, page_rows(i), :],
                sems.at[slot],
            ))

        lax.fori_loop(0, live, one, None)
        return start, live

    for_live_pages(0, 0, lambda copy: copy.start())

    m_scr[...] = jnp.full(m_scr.shape, _M_FLOOR, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    dtype = qn_ref.dtype
    row_pos = q_lo + lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    transposed = (((1,), (1,)), ((), ()))

    def of_head(h, width):
        """Head h's columns of a block whose heads lie abreast."""
        return pl.ds(pl.multiple_of(h * width, width), width)

    def trip_body(trip, _):
        slot = trip % 2

        @pl.when(trip + 1 < trips)
        def _next_fetch():
            for_live_pages(trip + 1, 1 - slot, lambda copy: copy.start())

        start, live = for_live_pages(trip, slot, lambda copy: copy.wait())

        # rows of the slot past the trip's last live page hold an earlier
        # trip's (or nothing yet): masked out of the scores by position, and
        # zeroed so that their expanded values are 0 and 0 * 0 stays 0
        def zero_page(i, _):
            buf[slot, page_rows(i), :] = jnp.zeros((page_size, buf.shape[-1]), buf.dtype)

        lax.fori_loop(live, trip_pages, zero_page, None)
        k_lo = start * page_size

        def tile(masked: bool):
            """Each head's `[bq, n*page]` tile of this trip into its running
            softmax, the trip's latents expanded to that head's keys and
            values on the way. Unmasked where every key of the trip is
            visible to every query of the block."""
            c = buf[slot, :, :latent].astype(dtype)      # [T, latent]
            # [k_r | zeros]: the rotary queries carry zeros against the row's tail
            k_r = buf[slot, :, latent:].astype(dtype)    # [T, tail]
            if masked:
                kv_pos = k_lo + lax.broadcasted_iota(jnp.int32, (1, trip_tokens), 1)
                mask = kv_pos <= row_pos                  # [bq, T]

            def head(h, _):
                # rounded to the queries' dtype before the products, as the XLA path's
                kv = jnp.dot(
                    c, w_ref[:, of_head(h, nope + v_dim)], preferred_element_type=jnp.float32
                ).astype(dtype)                           # [T, nope + v]
                s = lax.dot_general(
                    qn_ref[0, :, of_head(h, nope)], kv[:, :nope], transposed,
                    preferred_element_type=jnp.float32,
                ) + lax.dot_general(
                    qr_ref[0, :, of_head(h, tail)], k_r, transposed,
                    preferred_element_type=jnp.float32,
                )
                s = s * scale                             # [bq, T]
                if masked:
                    s = jnp.where(mask, s, _MASK_VALUE)
                m_prev, l_prev = m_scr[h, :, :1], l_scr[h, :, :1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                m_scr[h, :, :1] = m_new
                l_scr[h, :, :1] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
                acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                    p.astype(dtype), kv[:, nope:], preferred_element_type=jnp.float32
                )

            # the loop lowers whole or not at all: a step takes `together` heads
            together = _HEAD_UNROLL if heads % _HEAD_UNROLL == 0 else 1

            def some_heads(i, _):
                for j in range(together):
                    head(i * together + j, None)

            lax.fori_loop(0, heads // together, some_heads, None)

        interior = (live == trip_pages) & (k_lo + trip_tokens - 1 <= q_lo)
        pl.when(interior)(lambda: tile(False))
        pl.when(jnp.logical_not(interior))(lambda: tile(True))

    lax.fori_loop(0, trips, trip_body, None)

    def flush(h, _):
        l = l_scr[h, :, :1]
        # a query no key is visible to emits exactly 0
        o_ref[0, :, of_head(h, v_dim)] = (
            acc_scr[h] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)

    lax.fori_loop(0, heads, flush, None)


def mla_prefill_attention(
    q_nope: jnp.ndarray,
    q_rope: jnp.ndarray,
    w_kvb: jnp.ndarray,
    pool: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """A chunk of queries against each row's pages of the latent pool, in the
    expanded form: q_nope `[B, S, H, nope]`, q_rope `[B, S, H, rope]`, query
    i of row b at cache slot `lengths[b] + i` (`lengths [B]` counts the
    tokens the row held BEFORE the chunk; the caller appended the chunk's rows
    first, so the pool `[N, 1, page, W]` holds them: `[c_kv | k_r | zeros]`),
    `w_kvb [latent, H, nope + v]` the block's up-projection. Returns `[B, S,
    H, v]`; a caller with padded queries zeroes them (`ops/
    latent_attention.py`)."""
    batch, seq, heads, nope = q_nope.shape
    rope = q_rope.shape[-1]
    latent, _, expanded = w_kvb.shape
    v_dim = expanded - nope
    _, one, page_size, width = pool.shape
    tail = width - latent
    if one != 1 or tail < rope or w_kvb.shape[1] != heads:
        raise ValueError(
            f"latent pool {pool.shape} and W_kvb {w_kvb.shape} do not match "
            f"queries {q_nope.shape} + {q_rope.shape}"
        )
    interpret = resolve_interpret(interpret)
    if not interpret and (page_size % _SUBLANES or any(
        d % _LANES for d in (latent, tail, nope, v_dim)
    )):
        raise ValueError(
            "the compiled mla_prefill kernel wants whole sublane tiles a page and whole lane "
            f"tiles of latents, tail and head widths: got page {page_size}, latent {latent}, "
            f"tail {tail}, nope {nope}, v {v_dim}"
        )
    num_pages = block_tables.shape[1]
    trip_pages = chunk_latent_pages_per_trip(page_size, num_pages)
    block_q = query_block(seq)
    blocks = -(-seq // block_q)
    block_h = head_block(heads, block_q, latent, nope, tail, v_dim, q_nope.dtype.itemsize)

    def abreast(q, last):
        # [B, S, H, D] -> [B, blocks * bq, H * last]: a block's heads side by side
        q = jnp.pad(q, ((0, 0), (0, blocks * block_q - seq), (0, 0), (0, last - q.shape[-1])))
        return q.reshape(batch, blocks * block_q, heads * last)

    per_head = lambda last: pl.BlockSpec(
        (1, block_q, block_h * last), lambda b, h, i, tables, lens: (b, i, h)
    )
    stat = pltpu.VMEM((block_h, block_q, _LANES), jnp.float32)
    out = pl.pallas_call(
        functools.partial(
            _mla_prefill_kernel, page_size=page_size, trip_pages=trip_pages,
            latent=latent, nope=nope, scale=scale,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # the queries' blocks innermost: a block of heads keeps its slice
            # of W_kvb across them
            grid=(batch, heads // block_h, blocks),
            in_specs=[
                per_head(nope), per_head(tail),
                pl.BlockSpec((latent, block_h * expanded), lambda b, h, i, tables, lens: (0, h)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=per_head(v_dim),
            scratch_shapes=[
                pltpu.VMEM((2, trip_pages * page_size, width), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                stat, stat,
                pltpu.VMEM((block_h, block_q, v_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, blocks * block_q, heads * v_dim), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_VMEM_BYTES,
        ),
        interpret=interpret,
        name="mla_prefill",
    )(
        block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
        abreast(q_nope, nope), abreast(q_rope, tail),
        w_kvb.reshape(latent, heads * expanded), pool,
    )
    return out[:, :seq].reshape(batch, seq, heads, v_dim)
