"""Pallas TPU ragged paged attention: a decoded token a row (`paged_decode`)
and a chunk of queries a row (`paged_prefill`).

The serving-tier kernel (docs/serving.md, "Ragged Paged Attention" in
PAPERS.md): each decode row attends over ITS OWN cache length, gathering
K/V pages through its block table — no shared append index, no left
padding, no FLOPs on another row's history. This is the designated
successor to the dense `DecodeState` decode path's XLA einsum attention
(`models/cache.py:LayerCache.attend`), whose whole-cache attention
bills every row for the longest row's capacity.

Design (one grid step per row, hand-made page fetches, flash-style online
softmax):

  grid (batch,); the block table and per-row lengths ride as SCALAR
  PREFETCH operands and the K/V pools stay where they are
  (`memory_space=pl.ANY`). The pool is `[blocks, kv_heads, page,
  head_dim]`, so one physical block `pool[tables[b, p]]` is a single
  contiguous `[kv_heads, page, head_dim]` region: one async copy brings a
  page for EVERY kv head the call holds. A row walks its live pages only,
  `n` consecutive logical pages a trip, in a loop whose trip count comes
  from `lens[b]`; a trip's copies land in one slot of a double-buffered
  `[2, kv_heads, n*page, head_dim]` VMEM scratch and are started while the
  previous trip's slot is being reduced. Every copy that is started is
  waited for; pages past the row's last live one are never fetched (their
  V rows in the slot are zeroed, their scores masked), and pages wholly in
  front of a sliding window are skipped. The online softmax runs in
  float32 over a `[kv_heads, group, n*page]` score tile, a product
  batched over kv heads. A row at length L costs ceil(L/page) page copies
  and ceil(L/(n*page)) trips regardless of the pool size, the table's
  width or its neighbours' lengths.

`n` follows the shapes the call sees (`pages_per_trip`): as many pages as
keep the double-buffered K and V scratch inside `_KV_SCRATCH_BYTES`, at
most the table's width. The page size stays the pool's unit (kind="paged"
in `ops/pallas/tuning.py`), not this kernel's tile. On a TPU a page must
be whole (8, 128) tiles — page in sublanes, head_dim in lanes — so that a
copy lands tile-aligned in the slot.
Off-TPU the kernel runs interpreted (tier-1 tests), following the
`flash_attention.py` pattern; on a TPU it always compiles, and a shape
Mosaic cannot tile raises here instead of being routed elsewhere. The XLA
gather fallback lives in `ops/paged_attention.py`.

A chunk of queries (`paged_prefill_attention`, kernel name `paged_prefill`)
walks its row the same way, by the same code (`_RowPages`), with the grid
over (row, block of queries): the block's rows are its queries times the
GQA group, so a kv head's keys are multiplied once for the whole group; the
running maximum, sum and float32 accumulator of every kv head stay in VMEM
across the trips; a block starts at the page of the first key its first
query may see (its window's, else 0) and ends at the page of its last
query's own key; and a trip that every query of the block sees whole skips
the mask. Nothing as wide as the block table is produced.

Beside them lives the append's writer (`write_pages`, kernel name
`kv_page_write`): whole pages copied HBM to HBM into a pool that is aliased
to the call's output, so the pool is written where it lies and in the
layout the decode kernel reads.
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
# below every score a query and a key can have, and so far above _MASK_VALUE
# that exp(_MASK_VALUE - _M_FLOOR) is exactly 0
_M_FLOOR = -1e30
_LANES = 128
_SUBLANES = 8
# VMEM the double-buffered K and V page scratch may take (2 slots x K and V);
# pages a trip follow from it and from the shapes of the call
_KV_SCRATCH_BYTES = 2 * 1024 * 1024
# the prefill kernel's score tile a kv head: its rows (a block of queries
# times the GQA group, in query blocks of whole bf16 (16, 128) tiles) and its
# width, the tokens of a trip (a tile pays for its rows' softmax state once a
# trip whatever its width: on the v5e 1,024 beat 512 and 2,048 at every
# serve cell's shapes, PERF.md section 6); and what that kernel's page
# scratch may take
_PREFILL_TILE_ROWS = 1024
_PREFILL_TRIP_TOKENS = 1024
_PREFILL_KV_SCRATCH_BYTES = 16 * 1024 * 1024
_Q_ALIGN = 16
# VMEM the prefill kernel may take: the double-buffered q and output blocks,
# the float32 accumulator and softmax state of every head of the block, the
# page scratch and a tile's temporaries (of the v5e's 128 MiB)
_PREFILL_VMEM_BYTES = 96 * 1024 * 1024


def pages_per_trip(
    num_kv_heads: int, page_size: int, head_dim: int, itemsize: int,
    num_pages: int, scratch_bytes: int | None = None,
) -> int:
    """How many consecutive logical pages of a row one trip fetches: as many
    as keep `[2 slots] x [K, V] x [kv_heads, n*page, head_dim]` inside
    `scratch_bytes` (the decode kernel's `_KV_SCRATCH_BYTES` where not
    given), at least 1, at most the table's width."""
    if scratch_bytes is None:
        scratch_bytes = _KV_SCRATCH_BYTES
    page_bytes = num_kv_heads * page_size * head_dim * itemsize
    return max(1, min(scratch_bytes // (4 * page_bytes), num_pages))


def chunk_pages_per_trip(
    num_kv_heads: int, page_size: int, head_dim: int, itemsize: int,
    num_pages: int,
) -> int:
    """`pages_per_trip` for the prefill kernel, whose trip is the width of a
    `[rows, n*page]` score tile a kv head: `_PREFILL_TRIP_TOKENS` tokens (a
    tile pays its softmax state's read and write once a trip, so a chunk's
    trips are longer than a decoded token's), fewer where the scratch would
    pass `_PREFILL_KV_SCRATCH_BYTES`, and whole runs of 128 tokens, the
    tile's lanes, where a trip holds one."""
    pages = min(
        max(1, _PREFILL_TRIP_TOKENS // page_size),
        pages_per_trip(
            num_kv_heads, page_size, head_dim, itemsize, num_pages,
            scratch_bytes=_PREFILL_KV_SCRATCH_BYTES,
        ),
    )
    run = max(1, _LANES // page_size)
    return pages - pages % run if pages >= run else pages


def _gqa_group(num_q_heads: int, num_kv_heads: int) -> int:
    if num_q_heads % num_kv_heads:
        raise ValueError(
            f"num_q_heads ({num_q_heads}) not divisible by num_kv_heads "
            f"({num_kv_heads})"
        )
    return num_q_heads // num_kv_heads


def _check_tiling(interpret: bool, kernel: str, page_size: int, head_dim: int) -> None:
    """On a TPU a page must be whole (8, 128) tiles, page in sublanes and
    head_dim in lanes: a shape Mosaic cannot tile raises here, and is not
    routed elsewhere."""
    if not interpret and (head_dim % _LANES or page_size % _SUBLANES):
        raise ValueError(
            f"the compiled {kernel} kernel tiles a (page, head_dim) block "
            f"as ({_SUBLANES}, {_LANES}): got page {page_size}, head_dim "
            f"{head_dim}. Serve this model with attention impl 'xla', or "
            "off-TPU where the kernel is interpreted"
        )


class _RowPages:
    """One row's walk over its pages, for the kernels that read a row: logical
    pages `first_page` up to (not including) `end_page`, `trip_pages`
    consecutive ones a trip, each live page one async copy for K and one for
    V from the pools in HBM into slot `slot` of the double-buffered scratch
    `[2, Hkv, trip_pages * page, D]`. Logical page `p` of row `b` is block
    `tables[b, p]`, or `tables[b, p % width]` where the table is a ring."""

    def __init__(self, tables, b, pools, bufs, sems, *, first_page, end_page,
                 page_size: int, trip_pages: int, ring: bool):
        self.tables, self.b, self.pools, self.bufs, self.sems = tables, b, pools, bufs, sems
        self.first_page, self.end_page = first_page, end_page
        self.page_size, self.trip_pages, self.ring = page_size, trip_pages, ring
        self.trips = pl.cdiv(jnp.maximum(end_page - first_page, 0), trip_pages)

    def span(self, trip):
        """(first logical page, live pages) of a trip."""
        start = self.first_page + trip * self.trip_pages
        return start, jnp.minimum(self.trip_pages, self.end_page - start)

    def rows(self, i):
        """Page i of a trip, as rows of a slot."""
        return pl.ds(pl.multiple_of(i * self.page_size, self.page_size), self.page_size)

    def for_live_pages(self, trip, slot, act):
        """`act` on the K and the V copy of each live page of a trip."""
        start, live = self.span(trip)
        tables, b = self.tables, self.b

        def one(i, _):
            page = start + i
            # a window group's table is a ring as wide as its page budget
            block = tables[b, lax.rem(page, tables.shape[1]) if self.ring else page]
            for which, (hbm, buf) in enumerate(zip(self.pools, self.bufs)):
                act(pltpu.make_async_copy(
                    hbm.at[block], buf.at[slot, :, self.rows(i), :],
                    self.sems.at[which, slot],
                ))

        lax.fori_loop(0, live, one, None)

    def start(self, trip, slot):
        self.for_live_pages(trip, slot, lambda copy: copy.start())

    def wait(self, trip, slot):
        self.for_live_pages(trip, slot, lambda copy: copy.wait())

    def zero_dead_values(self, slot, live):
        """The slot's pages past a trip's `live` ones hold an earlier trip's
        rows (or nothing yet): their scores are masked by position, and their
        V rows zeroed here so that 0 * whatever-was-there stays 0."""
        v_buf = self.bufs[1]

        def zero_page(i, _):
            v_buf[slot, :, self.rows(i), :] = jnp.zeros(
                (v_buf.shape[1], self.page_size, v_buf.shape[3]), v_buf.dtype
            )

        lax.fori_loop(live, self.trip_pages, zero_page, None)


def _decode_kernel(
    tables,  # scalar prefetch: [B, P] physical block per (row, logical page)
    lens,    # scalar prefetch: [B] tokens already written (incl. this one)
    q_ref,   # [1, Hkv, G, D] this row's q, kv-head major
    k_hbm,   # [N, Hkv, page, D] the whole K pool, left in place
    v_hbm,   # [N, Hkv, page, D]
    o_ref,   # [1, Hkv, G, D]
    k_buf,   # VMEM [2, Hkv, n*page, D] double-buffered trip of K pages
    v_buf,   # VMEM [2, Hkv, n*page, D]
    sems,    # DMA semaphores [2 (K, V), 2 slots]
    *,
    page_size: int,
    trip_pages: int,
    scale: float,
    sliding_window: int | None,
    logits_soft_cap: float | None,
    ring: bool,
):
    b = pl.program_id(0)
    num_kv_heads, group, head_dim = q_ref.shape[1:]
    trip_tokens = trip_pages * page_size
    # q position of the decoded token == its (0-based) cache slot; the
    # caller appends k/v BEFORE attention, so valid kv slots are 0..q_pos
    q_pos = lens[b] - 1
    live_pages = pl.cdiv(lens[b], page_size)
    # pages wholly in front of the window hold nothing this row can see
    first_page = (
        0 if sliding_window is None
        else jnp.maximum(q_pos - sliding_window + 1, 0) // page_size
    )
    walk = _RowPages(
        tables, b, (k_hbm, v_hbm), (k_buf, v_buf), sems,
        first_page=first_page, end_page=live_pages,
        page_size=page_size, trip_pages=trip_pages, ring=ring,
    )
    walk.start(0, 0)

    q = q_ref[0]                                   # [Hkv, G, D]
    # bf16 q against a bf16 pool feeds the matrix unit as is: products of
    # bf16 values are exact in its float32 accumulator
    qk_dtype = q.dtype if q.dtype == k_buf.dtype else jnp.float32
    q = q.astype(qk_dtype)

    def trip_body(trip, carry):
        m_prev, l_prev, acc = carry
        slot = trip % 2

        @pl.when(trip + 1 < walk.trips)
        def _next_fetch():
            walk.start(trip + 1, 1 - slot)

        walk.wait(trip, slot)
        start, live = walk.span(trip)
        walk.zero_dead_values(slot, live)

        k = k_buf[slot].astype(qk_dtype)           # [Hkv, T, D]
        s = jnp.einsum(
            "hgd,htd->hgt", q, k, preferred_element_type=jnp.float32
        ) * scale                                  # [Hkv, G, T]
        if logits_soft_cap is not None:
            s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
        kv_pos = start * page_size + lax.broadcasted_iota(
            jnp.int32, (1, 1, trip_tokens), 2
        )
        mask = kv_pos <= q_pos
        if sliding_window is not None:
            mask &= (q_pos - kv_pos) < sliding_window
        s = jnp.where(mask, s, _MASK_VALUE)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [Hkv, G, T]
        v = v_buf[slot].astype(jnp.float32)           # [Hkv, T, D]
        acc = acc * alpha + jnp.einsum(
            "hgt,htd->hgd", p, v, preferred_element_type=jnp.float32
        )
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        return m_new, l_new, acc

    _, l, acc = lax.fori_loop(
        0, walk.trips, trip_body,
        (
            jnp.full((num_kv_heads, group, 1), -jnp.inf, jnp.float32),
            jnp.zeros((num_kv_heads, group, 1), jnp.float32),
            jnp.zeros((num_kv_heads, group, head_dim), jnp.float32),
        ),
    )
    # a fully-masked row (a sliding window that excludes everything)
    # emits exactly 0 — the _xla_attention invariant
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def paged_decode_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    ring: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One ragged decode step: q `[B, Hq, D]` (one token per row) against
    each row's paged cache. `k_pages`/`v_pages` `[N, Hkv, page, D]` are the
    pool, `block_tables [B, P]` maps logical page -> pool block, and
    `lengths [B]` counts tokens written INCLUDING this step's (the caller
    appends before attending). Rows a scheduler left idle should carry
    length 1 and a trash-block table — they compute one garbage token the
    caller ignores. Returns `[B, Hq, D]`. With `ring` (a sliding window's
    own short table) logical page `p` of a row is at `block_tables[b, p % P]`:
    the row walks the pages its window reaches, whatever its length.

    `interpret=None` interprets off-TPU and compiles on a TPU; asking for
    the interpreter on a TPU raises (`resolve_interpret`)."""
    batch, num_q_heads, head_dim = q.shape
    _, num_kv_heads, page_size, _ = k_pages.shape
    num_pages = block_tables.shape[1]
    group = _gqa_group(num_q_heads, num_kv_heads)
    if scale is None:
        scale = head_dim**-0.5
    interpret = resolve_interpret(interpret)
    _check_tiling(interpret, "paged-decode", page_size, head_dim)
    trip_pages = pages_per_trip(
        num_kv_heads, page_size, head_dim, k_pages.dtype.itemsize, num_pages
    )

    # q heads are kv-major (head h*G+g serves kv head h) — the same layout
    # _xla_attention's GQA reshape uses
    qg = q.reshape(batch, num_kv_heads, group, head_dim)
    tables = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    row_block = pl.BlockSpec(
        (1, num_kv_heads, group, head_dim),
        lambda b, tables, lens: (b, 0, 0, 0),
    )
    kv_slots = pltpu.VMEM(
        (2, num_kv_heads, trip_pages * page_size, head_dim), k_pages.dtype
    )

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            page_size=page_size,
            trip_pages=trip_pages,
            scale=scale,
            sliding_window=sliding_window,
            logits_soft_cap=logits_soft_cap,
            ring=ring,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch,),
            in_specs=[
                row_block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=row_block,
            scratch_shapes=[
                kv_slots, kv_slots, pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (batch, num_kv_heads, group, head_dim), q.dtype
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="paged_decode",
    )(tables, lens, qg, k_pages, v_pages)
    return out.reshape(batch, num_q_heads, head_dim)


def _prefill_kernel(
    tables,   # scalar prefetch: [B, P] physical block per (row, logical page)
    lens,     # scalar prefetch: [B] tokens the row held BEFORE this chunk
    q_ref,    # [1, Hkv, 1, G*bq, D] one block of bq queries, the group folded into its rows
    k_hbm,    # [N, Hkv, page, D] the whole K pool, left in place
    v_hbm,
    o_ref,    # [1, Hkv, 1, G*bq, D]
    k_buf,    # VMEM [2, Hkv, n*page, D] double-buffered trip of K pages
    v_buf,
    sems,     # DMA semaphores [2 (K, V), 2 slots]
    m_scr,    # VMEM [Hkv, G*bq, 128] float32: the running maximum, in lane 0
    l_scr,    # VMEM [Hkv, G*bq, 128] float32: the running sum, in lane 0
    acc_scr,  # VMEM [Hkv, G*bq, D] float32
    *,
    page_size: int,
    trip_pages: int,
    block_q: int,
    seq: int,
    scale: float,
    sliding_window: int | None,
    logits_soft_cap: float | None,
    ring: bool,
):
    b, qb = pl.program_id(0), pl.program_id(1)
    num_kv_heads, rows, _ = acc_scr.shape
    trip_tokens = trip_pages * page_size
    # query i of the chunk sits at cache slot lens[b] + i; the caller appended
    # the chunk BEFORE attention, so its own keys are in the pool
    q_lo = lens[b] + qb * block_q
    q_hi = q_lo + block_q - 1
    # the block's keys end at its last query's own; pages wholly in front of
    # its first query's window hold nothing any of its queries can see
    end_page = q_hi // page_size + 1
    first_page = (
        0 if sliding_window is None
        else jnp.maximum(q_lo - sliding_window + 1, 0) // page_size
    )
    if ring:
        # a slot stands for the newest page congruent to it that the chunk's
        # append reached: an older page is no longer there to be read
        newest = (lens[b] + seq - 1) // page_size
        first_page = jnp.maximum(first_page, newest - tables.shape[1] + 1)
    else:
        end_page = jnp.minimum(end_page, tables.shape[1])
    walk = _RowPages(
        tables, b, (k_hbm, v_hbm), (k_buf, v_buf), sems,
        first_page=first_page, end_page=end_page,
        page_size=page_size, trip_pages=trip_pages, ring=ring,
    )
    walk.start(0, 0)

    # the running maximum starts far above the mask's value, not at -inf: a
    # masked score then weighs exp(_MASK_VALUE - m) == 0 whatever the row has
    # seen, and a query no key is visible to keeps l == 0
    m_scr[...] = jnp.full(m_scr.shape, _M_FLOOR, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
    qk_dtype = q_ref.dtype if q_ref.dtype == k_buf.dtype else jnp.float32
    # row r of the tile is query r % block_q of the block, for one q head of the group
    row_pos = q_lo + lax.rem(
        lax.broadcasted_iota(jnp.int32, (rows, 1), 0), block_q
    )

    def trip_body(trip, _):
        slot = trip % 2

        @pl.when(trip + 1 < walk.trips)
        def _next_fetch():
            walk.start(trip + 1, 1 - slot)

        walk.wait(trip, slot)
        start, live = walk.span(trip)
        walk.zero_dead_values(slot, live)
        k_lo = start * page_size

        def tile(masked: bool):
            """Every kv head's `[G*bq, n*page]` tile of this trip into the
            running softmax: the head's keys are multiplied once for its
            whole group. Unmasked where every key of the trip is visible to
            every query of the block."""
            if masked:
                kv_pos = k_lo + lax.broadcasted_iota(jnp.int32, (1, trip_tokens), 1)
                mask = kv_pos <= row_pos                      # [G*bq, T]
                if sliding_window is not None:
                    mask &= (row_pos - kv_pos) < sliding_window

            def head(h, _):
                q = q_ref[0, h, 0].astype(qk_dtype)           # [G*bq, D]
                k = k_buf[slot, h].astype(qk_dtype)           # [T, D]
                s = lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale                                     # [G*bq, T]
                if logits_soft_cap is not None:
                    s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
                if masked:
                    s = jnp.where(mask, s, _MASK_VALUE)
                m_prev, l_prev = m_scr[h, :, :1], l_scr[h, :, :1]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_new)
                p = jnp.exp(s - m_new)
                m_scr[h, :, :1] = m_new
                l_scr[h, :, :1] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
                v = v_buf[slot, h].astype(qk_dtype)           # [T, D]
                acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                    p.astype(qk_dtype), v, preferred_element_type=jnp.float32
                )

            lax.fori_loop(0, num_kv_heads, head, None)

        interior = (live == trip_pages) & (k_lo + trip_tokens - 1 <= q_lo)
        if sliding_window is not None:
            interior &= q_hi - k_lo < sliding_window
        pl.when(interior)(lambda: tile(False))
        pl.when(jnp.logical_not(interior))(lambda: tile(True))

    lax.fori_loop(0, walk.trips, trip_body, None)

    def flush(h, _):
        l = l_scr[h, :, :1]
        # a query no key is visible to emits exactly 0 — the _xla_attention invariant
        o_ref[0, h, 0] = (acc_scr[h] * (1.0 / jnp.where(l == 0.0, 1.0, l))).astype(o_ref.dtype)

    lax.fori_loop(0, num_kv_heads, flush, None)


def query_block(seq: int, group: int) -> int:
    """How many of a chunk's queries one grid step of the prefill kernel
    takes: with the GQA group folded in, about `_PREFILL_TILE_ROWS` rows a kv
    head, in equal blocks of whole (16, 128) tiles."""
    blocks = -(-seq * group // _PREFILL_TILE_ROWS)
    return -(-seq // (blocks * _Q_ALIGN)) * _Q_ALIGN


def paged_prefill_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    block_tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    scale: float | None = None,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    ring: bool = False,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """A chunk of queries against each row's paged cache: q `[B, S, Hq, D]`,
    query i of row b at cache slot `lengths[b] + i` (`lengths [B]` counts the
    tokens the row held BEFORE the chunk; the caller appended the chunk's keys
    and values first, so the pools hold them). What `paged_decode_attention`
    is for one query: the pools stay in HBM, the row's pages come by the same
    double-buffered trips (`_RowPages`, `chunk_pages_per_trip` pages each),
    and the softmax state lives in VMEM across them, so nothing as
    wide as the table is produced. The grid is (row, block of `block_q`
    queries): a block walks from the page of the first key its first query
    may see (its window's, else 0) to the page of its last query, and a trip
    every key of which every query sees skips the mask. A kv head's keys
    meet the whole GQA group at once: the block's q rows are `group *
    block_q`, group-major. Returns `[B, S, Hq, D]`; a caller with padded
    queries zeroes them (`ops/paged_attention.py`)."""
    batch, seq, num_q_heads, head_dim = q.shape
    _, num_kv_heads, page_size, _ = k_pages.shape
    num_pages = block_tables.shape[1]
    group = _gqa_group(num_q_heads, num_kv_heads)
    if scale is None:
        scale = head_dim**-0.5
    interpret = resolve_interpret(interpret)
    _check_tiling(interpret, "paged-prefill", page_size, head_dim)
    block_q = query_block(seq, group)
    blocks = -(-seq // block_q)
    rows = group * block_q
    trip_pages = chunk_pages_per_trip(
        num_kv_heads, page_size, head_dim, k_pages.dtype.itemsize, num_pages
    )

    # [B, S, Hq, D] -> [B, Hkv, blocks, G * bq, D]: q heads are kv-major (head
    # h*G+g serves kv head h), a block's rows group-major
    qg = jnp.pad(q, ((0, 0), (0, blocks * block_q - seq), (0, 0), (0, 0)))
    qg = qg.reshape(batch, blocks, block_q, num_kv_heads, group, head_dim)
    qg = qg.transpose(0, 3, 1, 4, 2, 5).reshape(batch, num_kv_heads, blocks, rows, head_dim)
    block = pl.BlockSpec(
        (1, num_kv_heads, 1, rows, head_dim),
        lambda b, i, tables, lens: (b, 0, i, 0, 0),
    )
    kv_slots = pltpu.VMEM(
        (2, num_kv_heads, trip_pages * page_size, head_dim), k_pages.dtype
    )
    stat = pltpu.VMEM((num_kv_heads, rows, _LANES), jnp.float32)

    out = pl.pallas_call(
        functools.partial(
            _prefill_kernel,
            page_size=page_size,
            trip_pages=trip_pages,
            block_q=block_q,
            seq=seq,
            scale=scale,
            sliding_window=sliding_window,
            logits_soft_cap=logits_soft_cap,
            ring=ring,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, blocks),
            in_specs=[
                block,
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=block,
            scratch_shapes=[
                kv_slots, kv_slots, pltpu.SemaphoreType.DMA((2, 2)),
                stat, stat,
                pltpu.VMEM((num_kv_heads, rows, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_PREFILL_VMEM_BYTES,
        ),
        interpret=interpret,
        name="paged_prefill",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), qg, k_pages, v_pages)
    out = out.reshape(batch, num_kv_heads, blocks, group, block_q, head_dim)
    out = out.transpose(0, 2, 4, 1, 3, 5).reshape(batch, blocks * block_q, num_q_heads, head_dim)
    return out[:, :seq]


def _write_pages_kernel(
    blocks,      # scalar prefetch: [M] pool block of each page
    live,        # scalar prefetch: [M] nonzero where the page is to be written
    k_pages,     # [M, Hkv, page, D] in HBM
    v_pages,
    k_in, v_in,  # the pools, aliased to the outputs below: the same memory
    k_pool,      # [N, Hkv, page, D], left in place
    v_pool,
    sems,        # DMA semaphores [2 (K, V)]
):
    del k_in, v_in

    def for_live_pages(act):
        def one(i, _):
            @pl.when(live[i] != 0)
            def _():
                for which, (pages, pool) in enumerate(((k_pages, k_pool), (v_pages, v_pool))):
                    act(pltpu.make_async_copy(
                        pages.at[i], pool.at[blocks[i]], sems.at[which]
                    ))

        lax.fori_loop(0, blocks.shape[0], one, None)

    # every copy is in flight before the first is waited for: they are of one
    # size, so a wait a copy drains each semaphore whatever order they end in
    for_live_pages(lambda copy: copy.start())
    for_live_pages(lambda copy: copy.wait())


def write_pages(
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    blocks: jnp.ndarray,
    live: jnp.ndarray,
    *,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The append's write: `pool[blocks[i]] = pages[i]` wherever `live[i]`,
    for the K and the V pool `[N, Hkv, page, D]` and pages `[M, Hkv, page,
    D]` of the pool's dtype. The pools are aliased to the outputs and stay
    in HBM (`memory_space=pl.ANY`): a page is one contiguous block of the
    pool in the layout `paged_decode_attention` reads, so a write is one
    HBM-to-HBM copy, the pool's other blocks are not touched, and nothing of
    the pool's shape is produced. Live pages go to blocks of their own (a
    block belongs to one row); which copy wins a block named twice is not
    defined."""
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    new_k, new_v = pl.pallas_call(
        _write_pages_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[any_space] * 4,
            out_specs=[any_space] * 2,
            scratch_shapes=[pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # operands count the two scalar-prefetch arrays
        input_output_aliases={4: 0, 5: 1},
        interpret=resolve_interpret(interpret),
        name="kv_page_write",
    )(
        blocks.astype(jnp.int32), live.astype(jnp.int32),
        k_pages, v_pages, k_pool, v_pool,
    )
    return new_k, new_v
