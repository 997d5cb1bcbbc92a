"""Pallas TPU ragged paged-decode attention.

The serving-tier kernel (docs/serving.md, "Ragged Paged Attention" in
PAPERS.md): each decode row attends over ITS OWN cache length, gathering
K/V pages through its block table — no shared append index, no left
padding, no FLOPs on another row's history. This is the designated
successor to the dense `DecodeState` decode path's XLA einsum attention
(`models/llama/model.py:_cached_attention`), whose whole-cache attention
bills every row for the longest row's capacity.

Design (one page per kv grid step, flash-style online softmax):

  grid (batch, kv_heads, max_pages_per_request), pages innermost
  ("arbitrary"); the block table and per-row lengths ride as SCALAR
  PREFETCH operands, so each page's BlockSpec index map resolves the
  PHYSICAL pool block to stream — the gather happens in the DMA engine,
  not in compute. Pages past a row's length clamp onto the last valid
  page (the already-resident block), so Pallas elides their DMA and
  `pl.when` skips their compute: a row at length L costs ceil(L/page)
  page visits regardless of the pool size or its neighbours' lengths.

The page size IS this kernel's kv tile (the [group, page_size] score tile
per q-head group), registered with `ops/pallas/tuning.py` under
kind="paged". The pool is `[blocks, kv_heads, page, head_dim]`: one kv
head's page is the (page, head_dim) trailing tile Mosaic requires of a
block — page in sublanes (8-aligned), head_dim in lanes (128-aligned) —
so a kv head is selected by the BlockSpec, never by a slice in the kernel.
Off-TPU the kernel runs interpreted (tier-1 tests), following the
`flash_attention.py` pattern; on a TPU it always compiles, and a shape
Mosaic cannot tile raises here instead of being routed elsewhere. The XLA
gather fallback lives in `ops/paged_attention.py`.
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


def _decode_kernel(
    tables,  # scalar prefetch: [B, P] physical block per (row, logical page)
    lens,    # scalar prefetch: [B] tokens already written (incl. this one)
    q_ref,   # [1, 1, G, D] this row's q for one kv head's group
    k_ref,   # [1, 1, page, D] one pool page for this kv head
    v_ref,   # [1, 1, page, D]
    o_ref,   # [1, 1, G, D]
    m_ref,   # VMEM [G, lanes] running row max
    l_ref,   # VMEM [G, lanes] running denominator
    acc_ref,  # VMEM [G, D] running numerator
    *,
    page_size: int,
    scale: float,
    sliding_window: int | None,
    logits_soft_cap: float | None,
    num_pages: int,
):
    b, j = pl.program_id(0), pl.program_id(2)
    # q position of the decoded token == its (0-based) cache slot; the
    # caller appends k/v BEFORE attention, so valid kv slots are 0..q_pos
    q_pos = lens[b] - 1

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages whose first slot is past q_pos hold nothing this row can see
    @pl.when(j * page_size <= q_pos)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)   # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)   # [page, D]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [G, page]
        if logits_soft_cap is not None:
            s = logits_soft_cap * jnp.tanh(s / logits_soft_cap)
        kv_pos = j * page_size + lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        mask = kv_pos <= q_pos
        if sliding_window is not None:
            mask &= (q_pos - kv_pos) < sliding_window
        s = jnp.where(mask, s, _MASK_VALUE)
        m_prev = m_ref[:, :1]                       # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # [G, page]
        v = v_ref[0, 0].astype(jnp.float32)           # [page, D]
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        l_new = l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_pages - 1)
    def _finish():
        l = l_ref[:, :1]
        # a fully-masked row (a sliding window that excludes everything)
        # emits exactly 0 — the _xla_attention invariant
        o_ref[0, 0] = (
            acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)


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
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One ragged decode step: q `[B, Hq, D]` (one token per row) against
    each row's paged cache. `k_pages`/`v_pages` `[N, Hkv, page, D]` are the
    pool, `block_tables [B, P]` maps logical page -> pool block, and
    `lengths [B]` counts tokens written INCLUDING this step's (the caller
    appends before attending). Rows a scheduler left idle should carry
    length 1 and a trash-block table — they compute one garbage token the
    caller ignores. Returns `[B, Hq, D]`.

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

    # q heads are kv-major (head h*G+g serves kv head h) — the same layout
    # _xla_attention's GQA reshape uses
    qg = q.reshape(batch, num_kv_heads, group, head_dim)
    tables = block_tables.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def page_idx(b, h, j, tables, lens):
        # pages past the row's last valid page repeat the last valid one:
        # their DMA is elided and their compute is pl.when-skipped
        jc = jnp.minimum(j, jnp.maximum(lens[b] - 1, 0) // page_size)
        return (tables[b, jc], h, 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            page_size=page_size,
            scale=scale,
            sliding_window=sliding_window,
            logits_soft_cap=logits_soft_cap,
            num_pages=num_pages,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, num_kv_heads, num_pages),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, group, head_dim),
                    lambda b, h, j, tables, lens: (b, h, 0, 0),
                ),
                pl.BlockSpec((1, 1, page_size, head_dim), page_idx),
                pl.BlockSpec((1, 1, page_size, head_dim), page_idx),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, group, head_dim),
                lambda b, h, j, tables, lens: (b, h, 0, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((group, _LANES), jnp.float32),
                pltpu.VMEM((group, _LANES), jnp.float32),
                pltpu.VMEM((group, head_dim), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (batch, num_kv_heads, group, head_dim), q.dtype
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="paged_decode",
    )(tables, lens, qg, k_pages, v_pages)
    return out.reshape(batch, num_q_heads, head_dim)
