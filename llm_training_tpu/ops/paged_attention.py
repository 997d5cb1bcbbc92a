"""Paged KV-cache attention: in-place page append + ragged attention dispatch.

The serving counterpart of the dense cached-attention path in the shared
decoder stacks (docs/serving.md). The cache is a POOL of fixed-size blocks
(`[num_blocks, kv_heads, block_size, head_dim]` per layer) owned by
`serve/paged_cache.py`; each row addresses it through a block table and
its own length — so this module does per-row page writes and per-row
ragged reads where the dense path does one `dynamic_update_slice` at a
shared index.

The append (`paged_append`) never rewrites the pool: it gathers the few
pages a chunk's positions lie in, lays the new rows over them and writes
each page back whole to its own block — on a TPU with the Pallas page
writer (`ops/pallas/paged_attention.py:write_pages`, pools aliased and left
in HBM, one copy a page, the layout the decode kernel reads), elsewhere
with one XLA scatter over whole blocks. A decoder stack carries the pools
of ALL its layers through its layer loop and passes `layer`: the stack is
then addressed as one pool of `layers * blocks` blocks through a shifted
block table (docs/serving.md, "How the cache is carried and appended"), so
a step produces nothing of a pool's shape.

ONE rule says where a row's logical page `p` is in its table: slot `p %
width`. A table as wide as the row can grow (`max_model_len` in pages) never
wraps, so for it the rule is `p` itself and the remainder is left out; the
table of a group of layers that keep a WINDOW of the past is as wide as that
group's page budget (`serve/paged_cache.py:window_page_budget`) and does
wrap: `ring=True`. The append, the gather path and the decode kernel all
follow it, and none reads or writes past the table's width, whatever
`max_model_len` is. What a slot held before it was given to a newer page, and
what a slot whose page went back to the pool points at (the trash block), is
masked by POSITION: the slot stands for the newest page congruent to it, and
the window's own mask hides every position that page no longer holds.

Two attention paths behind one call:

- on a TPU (or `impl='pallas'`): the Pallas kernels of
  `ops/pallas/paged_attention.py`, which leave the pools in HBM and fetch a
  row's pages through its block table in the DMA engine, several a trip,
  double buffered. Single-token decode runs `paged_decode` (one grid step a
  row); a chunk (q_len > 1, chunked prefill) runs `paged_prefill` (one grid
  step a block of queries, the GQA group folded into the tile's rows, the
  softmax state in VMEM across the trips): a chunk walks only the pages its
  row holds, from its window's first to its last query's, and produces
  nothing as wide as the table. On a TPU the kernels always compile; a
  head_dim/page Mosaic cannot tile raises there;
- everything else (the CPU, `impl='xla'`): an XLA gather path — block-table
  gather to a dense `[B, P*page, H, D]` view plus a per-row position mask
  into the reference einsum attention, float32 scores `[B, heads, S,
  P*page]` whatever the row's length. Same math, shape-static,
  differentiable-free (decode only), and the oracle both kernels are tested
  against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_training_tpu.ops.attention import _xla_attention
from llm_training_tpu.telemetry.registry import get_registry


# how many layers' chunk attention the serving programs traced last run in a
# kernel (`paged_prefill`; `mla_prefill` for latent layers); `serve/engine.py`
# zeroes it before it builds its programs
CHUNK_KERNEL_GAUGE = "decode/chunk_attention_kernel_layers"
_chunk_kernel_layers: dict[bool | str, int] = {}


def reset_chunk_kernel_layers() -> None:
    _chunk_kernel_layers.clear()
    get_registry().gauge(CHUNK_KERNEL_GAUGE).set(0)


def _count_chunk_kernel_layers(layers: int, group: bool | str) -> None:
    """Said when a chunk's attention is traced into its kernel: the call
    stands for every layer of the stack it addresses; each of two page groups
    (`group`: is it behind a ring table) says its own, a latent stack "latent"."""
    _chunk_kernel_layers[group] = layers
    get_registry().gauge(CHUNK_KERNEL_GAUGE).set(sum(_chunk_kernel_layers.values()))


def _on_kernels(impl: str) -> bool:
    return impl == "pallas" or (impl == "auto" and jax.default_backend() == "tpu")


def _chunk_pages(lengths, block_tables, segment_ids, batch, seq, page_size, ring=False):
    """Where a chunk of `seq` tokens a row lands, page by page. A chunk that
    starts anywhere touches at most `T = ceil((seq - 1) / page) + 1` pages of
    its row (one for a single token). Returns, for row b's t-th touched page:
    `blocks [B, T]` its pool block, `token [B, T * page]` which chunk token
    each slot takes (clipped into the chunk) and `valid [B, T, page]` whether
    it takes one; and `stray [B, seq]`, the chunk positions that have no slot:
    padded ones (segment id 0) and those past the table (a ring has no end)."""
    num_pages = block_tables.shape[1]
    touched = (seq - 1 + page_size - 1) // page_size + 1
    logical = (lengths // page_size)[:, None] + jnp.arange(touched, dtype=jnp.int32)
    pos = logical[:, :, None] * page_size + jnp.arange(page_size, dtype=jnp.int32)
    token = (pos - lengths[:, None, None]).reshape(batch, touched * page_size)
    in_chunk = (token >= 0) & (token < seq)
    token = jnp.clip(token, 0, seq - 1)
    real = jnp.ones((batch, seq), bool) if segment_ids is None else segment_ids > 0
    if not ring:
        chunk_pos = lengths[:, None] + jnp.arange(seq, dtype=jnp.int32)
        real &= chunk_pos < num_pages * page_size
    valid = (in_chunk & jnp.take_along_axis(real, token, axis=1)).reshape(
        batch, touched, page_size
    )
    blocks = jnp.take_along_axis(
        block_tables,
        logical % num_pages if ring else jnp.minimum(logical, num_pages - 1),
        axis=1,
    )
    return blocks, token, valid, ~real


def paged_append(
    pool_k: jnp.ndarray,
    pool_v: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    segment_ids: jnp.ndarray | None,
    impl: str = "auto",
    trash: jnp.ndarray | int = 0,
    ring: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`_append` for a key pool and a value pool."""
    return _append(
        (pool_k, pool_v), (k, v), lengths, block_tables, segment_ids, impl, trash, ring
    )


def latent_append(pool, rows, lengths, block_tables, segment_ids, impl="auto", trash=0):
    """`_append` for the one pool of a latent cache: `rows [B, S, 1, W]` into
    `pool [N, 1, page, W]`."""
    return _append((pool,), (rows,), lengths, block_tables, segment_ids, impl, trash)[0]


def _append(pools, xs, lengths, block_tables, segment_ids, impl, trash, ring=False):
    """Write this chunk's k/v `[B, S, H, D]` into the pool at each row's next
    positions (`lengths[b] + i`), touching only the pages those positions lie
    in: each such page is read, the chunk's rows are laid over it, and the
    page is written back whole to its own block, in the pool's own layout
    (`_write_pages`: one copy a page on a TPU, in place on a donated or
    carried pool). Nothing of the pool's shape is produced on the way, and
    the chunk may start anywhere in a page. Padded chunk positions (segment
    id 0) and any out-of-table position are redirected to slot 0 of the
    reserved trash block `trash` — garbage can land there but never in a
    live block."""
    batch, seq = xs[0].shape[:2]
    _, kv_heads, page_size, head_dim = pools[0].shape
    blocks, token, valid, stray = _chunk_pages(
        lengths, block_tables, segment_ids, batch, seq, page_size, ring
    )
    # one more page for the strays: the trash block with one of them on slot 0
    blocks = jnp.append(blocks.reshape(-1), jnp.asarray(trash, blocks.dtype))
    live = jnp.append(valid.any(axis=-1).reshape(-1), stray.any())
    a_stray = jnp.argmax(stray.reshape(-1))
    first_slot = jnp.arange(page_size) == 0

    def pages(pool, x):
        # the chunk's rows in page shape [B * T, H, page, D], over what the
        # touched pages hold
        rows = (
            jnp.broadcast_to(x, (batch, token.shape[1], kv_heads, head_dim))
            if seq == 1 else jnp.take_along_axis(x, token[:, :, None, None], axis=1)
        )
        rows = rows.reshape(-1, page_size, kv_heads, head_dim).swapaxes(1, 2)
        rows = jnp.append(
            rows, jnp.broadcast_to(
                x.reshape(-1, kv_heads, 1, head_dim)[a_stray], (1, *rows.shape[1:])
            ), axis=0,
        )
        take = jnp.append(valid.reshape(-1, page_size), first_slot[None], axis=0)
        return jnp.where(take[:, None, :, None], rows.astype(pool.dtype), pool[blocks])

    return _write_pages(
        pools, tuple(pages(pool, x) for pool, x in zip(pools, xs)), blocks, live, impl
    )


def _write_pages(pools, pages, blocks, live, impl):
    """`pool[blocks[i]] = pages[i]` for every live i, for each pool (K and V,
    or the one latent pool). On a TPU (or `impl='pallas'`) the Pallas page
    writers, whose pools are aliased to their outputs and never leave HBM;
    elsewhere one XLA scatter over whole blocks, rows that are not live
    dropped."""
    if _on_kernels(impl):
        if len(pools) == 1:
            from llm_training_tpu.ops.pallas.mla_decode import write_latent_pages

            # one row a token, shared by the heads: nothing to split over them
            return (_over_heads(
                write_latent_pages, (*pools, *pages, blocks, live), (None,) * 4, 0
            ),)
        from llm_training_tpu.ops.pallas.paged_attention import write_pages

        return _over_heads(
            write_pages, (*pools, *pages, blocks, live),
            (1, 1, 1, 1, None, None), (0, 1),
        )
    at = jnp.where(live, blocks, pools[0].shape[0])  # out of range: dropped
    return tuple(
        pool.at[at].set(new, mode="drop") for pool, new in zip(pools, pages)
    )


def _gather_attention(
    q, pool_k, pool_v, lengths, block_tables, segment_ids,
    sliding_window, logits_soft_cap, scale, ring=False,
):
    """The XLA path, and the kernels' oracle: dense gather of each row's
    pages + per-row causal mask. `lengths` here is the PRE-append count, so
    q position i of row b sits at absolute slot lengths[b] + i. The gather is
    as wide as the table: a ring's scores are `[B, heads, S, budget * page]`,
    whatever the row's length."""
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
    keys = lambda: kv_pos[None, None, None, :]
    if ring:
        # slot s stands for the newest page congruent to it that the chunk's
        # last position has reached; one that never held a page is before 0
        newest = (lengths + seq - 1) // page_size
        page_of = newest[:, None] - (
            newest[:, None] - jnp.arange(num_pages, dtype=jnp.int32)
        ) % num_pages
        kv_pos = jnp.repeat(page_of, page_size, axis=1) * page_size + kv_pos % page_size
        keys = lambda: kv_pos[:, None, None, :]
    # [B, 1, S, KV] — True = attend; the causal term alone hides unwritten
    # slots (their position is ahead of every query) and other requests'
    # blocks never appear in this row's table
    mask = keys() <= q_pos[:, None, :, None]
    if ring:
        mask &= keys() >= 0
    if sliding_window is not None:
        mask &= q_pos[:, None, :, None] - keys() < sliding_window
    if segment_ids is not None:
        mask &= (segment_ids > 0)[:, None, :, None]
    return _xla_attention(
        q, gk.astype(q.dtype), gv.astype(q.dtype), mask, scale, logits_soft_cap
    )


def _over_heads(fn, args, head_axes, like):
    """A Mosaic call on whatever mesh is active. Like the flash kernel
    (`ops/attention.py:_flash_under_mesh`), a Mosaic kernel cannot be
    partitioned by GSPMD, so on a multi-device mesh it runs in a shard_map:
    heads over `tensor` — how the pool's kv heads and q's heads are already
    sharded, so neither kernel gathers them — and every other axis
    replicated, as the pool's block axis is (each data-parallel rank owns
    its whole pool). `head_axes[i]` is the axis of `args[i]` that counts
    heads (None: no such axis); the outputs are sharded as the args that
    `like` indexes are."""
    from llm_training_tpu.parallel.mesh import TENSOR_AXIS, active_mesh

    mesh = active_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[TENSOR_AXIS]
    split = all(
        arg.shape[axis] % tp == 0
        for arg, axis in zip(args, head_axes) if axis is not None
    )
    specs = tuple(
        P(*(TENSOR_AXIS if split and i == axis else None for i in range(arg.ndim)))
        for arg, axis in zip(args, head_axes)
    )
    out_specs = (
        tuple(specs[i] for i in like) if isinstance(like, tuple) else specs[like]
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=specs, out_specs=out_specs, check_vma=False,
    )(*args)


def paged_cached_attention(
    q: jnp.ndarray,
    k: jnp.ndarray | None,
    v: jnp.ndarray | None,
    layer_kv: tuple[jnp.ndarray, jnp.ndarray],
    lengths: jnp.ndarray,
    block_tables: jnp.ndarray,
    *,
    layer: jnp.ndarray | int | None = None,
    segment_ids: jnp.ndarray | None = None,
    sliding_window: int | None = None,
    logits_soft_cap: float | None = None,
    scale: float | None = None,
    impl: str = "auto",
    ring: bool = False,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Append this chunk's k/v through the block table, then attend each
    row against its own cache. q/k/v `[B, S, H*, D]` (S == 1 on the decode
    hot path, S == chunk width during chunked prefill); `lengths [B]` counts
    tokens already in each row's cache BEFORE this chunk. Returns `(out [B,
    S, Hq, D], new pool pair)`. With `k` and `v` None nothing is appended:
    the chunk's keys and values are in the pages already (another layer of
    the same call put them there) and q attends against them as they are.

    `layer_kv` is one layer's pool pair `[N, Hkv, page, D]`, or, with
    `layer`, the pools of the whole stack `[L, N, Hkv, page, D]` as the
    layer loop carries them: the stack is viewed as one pool of `L * N`
    blocks and `layer * N` is added to the table, so this layer appends to
    and reads ITS blocks of the carried buffer (its trash block is `layer *
    N`) and the layer's pool is never cut out of the stack.

    `ring`: the table is a window group's, as wide as its page budget, and
    logical page `p` is in slot `p % width` (the module docstring).

    impl: 'auto' (Pallas kernels on TPU — the page writer, the decode kernel
    for single-token decode, the prefill kernel for a chunk — XLA elsewhere)
    | 'pallas' (kernels forced, interpreted off-TPU) | 'xla'.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    pool_k, pool_v = layer_kv
    stack_shape, trash = pool_k.shape, 0
    if layer is not None:
        # this layer's first block in the stack seen as one pool: its trash block
        trash = jnp.asarray(layer * stack_shape[1], block_tables.dtype)
        block_tables = block_tables + trash
        pool_k, pool_v = (pool.reshape(-1, *stack_shape[2:]) for pool in layer_kv)
    lengths = lengths.astype(jnp.int32)
    ck, cv = (pool_k, pool_v) if k is None else paged_append(
        pool_k, pool_v, k, v, lengths, block_tables, segment_ids, impl, trash, ring
    )

    seq = q.shape[1]
    if not _on_kernels(impl):
        out = _gather_attention(
            q, ck, cv, lengths, block_tables, segment_ids,
            sliding_window, logits_soft_cap, scale, ring,
        )
    elif seq == 1:
        from llm_training_tpu.ops.pallas.paged_attention import paged_decode_attention

        out = _over_heads(
            lambda q, pk, pv, tables, lens: paged_decode_attention(
                q, pk, pv, tables, lens, scale=scale,
                sliding_window=sliding_window, logits_soft_cap=logits_soft_cap,
                ring=ring,
            ),
            (q[:, 0], ck, cv, block_tables, lengths + 1), (1, 1, 1, None, None), 0,
        )[:, None]
    else:
        from llm_training_tpu.ops.pallas.paged_attention import paged_prefill_attention

        out = _over_heads(
            lambda q, pk, pv, tables, lens: paged_prefill_attention(
                q, pk, pv, tables, lens, scale=scale,
                sliding_window=sliding_window, logits_soft_cap=logits_soft_cap,
                ring=ring,
            ),
            (q, ck, cv, block_tables, lengths), (2, 1, 1, None, None), 0,
        )
        if segment_ids is not None:
            # a padded query emits exactly 0, as on the gather path
            out = jnp.where((segment_ids > 0)[:, :, None, None], out, 0)
        _count_chunk_kernel_layers(1 if layer is None else stack_shape[0], ring)
    return out, (ck.reshape(stack_shape), cv.reshape(stack_shape))
