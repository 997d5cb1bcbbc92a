"""Pallas TPU ragged paged-decode attention.

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

Beside it lives the append's writer (`write_pages`, kernel name
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
_LANES = 128
_SUBLANES = 8
# VMEM the double-buffered K and V page scratch may take (2 slots x K and V);
# pages a trip follow from it and from the shapes of the call
_KV_SCRATCH_BYTES = 2 * 1024 * 1024


def pages_per_trip(
    num_kv_heads: int, page_size: int, head_dim: int, itemsize: int,
    num_pages: int,
) -> int:
    """How many consecutive logical pages of a row one trip fetches: as many
    as keep `[2 slots] x [K, V] x [kv_heads, n*page, head_dim]` inside
    `_KV_SCRATCH_BYTES`, at least 1, at most the table's width."""
    page_bytes = num_kv_heads * page_size * head_dim * itemsize
    return max(1, min(_KV_SCRATCH_BYTES // (4 * page_bytes), num_pages))


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
    trips = pl.cdiv(jnp.maximum(live_pages - first_page, 0), trip_pages)

    def trip_span(trip):
        """(first logical page, live pages) of a trip."""
        start = first_page + trip * trip_pages
        return start, jnp.minimum(trip_pages, live_pages - start)

    def page_rows(i):
        """Page i of a trip, as rows of a slot."""
        return pl.ds(pl.multiple_of(i * page_size, page_size), page_size)

    def for_live_pages(trip, slot, act):
        """`act` on the K and the V copy of each live page of a trip."""
        start, live = trip_span(trip)

        def one(i, _):
            page = start + i
            # a window group's table is a ring as wide as its page budget
            block = tables[b, lax.rem(page, tables.shape[1]) if ring else page]
            for which, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                act(pltpu.make_async_copy(
                    hbm.at[block], buf.at[slot, :, page_rows(i), :],
                    sems.at[which, slot],
                ))

        lax.fori_loop(0, live, one, None)

    for_live_pages(0, 0, lambda copy: copy.start())

    q = q_ref[0]                                   # [Hkv, G, D]
    # bf16 q against a bf16 pool feeds the matrix unit as is: products of
    # bf16 values are exact in its float32 accumulator
    qk_dtype = q.dtype if q.dtype == k_buf.dtype else jnp.float32
    q = q.astype(qk_dtype)

    def trip_body(trip, carry):
        m_prev, l_prev, acc = carry
        slot = trip % 2

        @pl.when(trip + 1 < trips)
        def _next_fetch():
            for_live_pages(trip + 1, 1 - slot, lambda copy: copy.start())

        for_live_pages(trip, slot, lambda copy: copy.wait())
        start, live = trip_span(trip)

        # the slot's pages past the row's last hold an earlier trip's rows
        # (or nothing yet): masked out of the scores below, zeroed in V so
        # that 0 * whatever-was-there stays 0
        def zero_page(i, _):
            v_buf[slot, :, page_rows(i), :] = jnp.zeros(
                (num_kv_heads, page_size, head_dim), v_buf.dtype
            )

        lax.fori_loop(live, trip_pages, zero_page, None)

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
        0, trips, trip_body,
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
    if num_q_heads % num_kv_heads:
        raise ValueError(
            f"num_q_heads ({num_q_heads}) not divisible by num_kv_heads "
            f"({num_kv_heads})"
        )
    group = num_q_heads // num_kv_heads
    if scale is None:
        scale = head_dim**-0.5
    interpret = resolve_interpret(interpret)
    if not interpret and (head_dim % _LANES or page_size % _SUBLANES):
        raise ValueError(
            "the compiled paged-decode kernel tiles a (page, head_dim) block "
            f"as ({_SUBLANES}, {_LANES}): got page {page_size}, head_dim "
            f"{head_dim}. Serve this model with attention impl 'xla', or "
            "off-TPU where the kernel is interpreted"
        )
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
