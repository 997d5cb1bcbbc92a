"""How a decoder stack meets its cache: the one module of `models/` that
knows what a cache kind is (docs/inference.md, "How a layer meets its cache").

A family's `__call__` opens its `decode_state` once (`open_cache`), hands the
`LayerCache` to its layer loop, and closes it (`close_cache`). A layer asks
the cache to append its chunk's keys and values and attend against its own
part (`attend`), to append its chunk's latent rows and attend against them
(`attend_latent`), or for its recurrent rows and to take them back
(`recurrent_rows`, `put_recurrent_rows`); it never sees whether the cache is
dense or paged. That is decided here, once, from the state's type:

- dense (`DecodeState`, `infer/`): buffers `[L, B, max_length, kv_heads,
  head_dim]`, one append position shared by the batch, and the filled-slot
  ids every layer masks against;
- paged (`PagedDecodeState`, `serve/`): pools `[L, blocks, kv_heads, page,
  head_dim]` addressed through per-row lengths and block tables
  (`ops/paged_attention.py`).

What a token leaves in those buffers is the stack's declaration
(`BaseModelConfig.cache_specs`), of three kinds:

- keys and values a head (`KVCacheSpec`): `k` and `v`, as above;
- one latent row shared by the heads (`LatentCacheSpec`, MLA): `k` alone,
  `[mla_blocks, ..., 1, width]` in either layout, `v` None; the row is read
  as keys and, its first `latent_dim` values, as values
  (`ops/latent_attention.py`);
- beside either, where the stack has linear-attention layers, their slab
  (`RecurrentCacheSpec`): `state`, `conv` `[layers, slots, ...]`;
- beside keys and values, where some of the stack's layers keep a window of
  the past and the others all of it, the window layers' own buffers
  (`KVCacheSpec.window`, the second GROUP: `window_k`, `window_v`). Paged,
  they are a pool of their own behind a table as short as the window's page
  budget (`window_tables`, a ring); dense, buffers at full length, masked. A
  layer says which group it is of by the `window` it attends with.

The buffers hold EVERY layer of their kind, leading axis over layers, and
ride the layer loop as its carry (`scan_layers`; a Python variable on a
looped path): a layer writes its new rows into them in place and reads its
own part, and its slice is never cut out of the stack and put back. What is
the same for every layer is closed over by the loop, not carried: the rest
of the cache, and the stacked weights a layer must not cut out either
(`scan_layers`, `whole=`).
"""

from __future__ import annotations

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp
from flax.traverse_util import flatten_dict, unflatten_dict

from llm_training_tpu.models.base import PagedDecodeState
from llm_training_tpu.ops import dot_product_attention
from llm_training_tpu.ops.delta_rule import SlabRows, slab_rows

_BUFFERS = ("k", "v", "state", "conv", "window_k", "window_v")


def _slot_rows(slab, slots, fresh):
    """A slab layer's rows for this batch: `slots` picks them (None: row i is
    slot i), and a row whose request starts here reads zeros."""
    rows = slab if slots is None else slab[slots]
    if fresh is not None:
        rows = jnp.where(fresh.reshape((-1,) + (1,) * (rows.ndim - 1)), 0, rows)
    return rows


def _layer_rows(slab, layer, slots, fresh, read, in_place=False):
    """`read` (`_slot_rows`) of layer `layer` of the whole slab `[layers,
    slots, ...]`. Picked slots are gathered out of the slab seen as one run
    of `layers * slots` rows, so the layer's part is not cut out first.
    `in_place`: every slot's rows are read through that same view, the one
    `_put_rows` writes through, so that an elementwise update of them can be
    written where they lie (the compiler copies the WHOLE slab first when the
    rows it reads and the rows it writes reach it as two views)."""
    if slots is None and in_place:
        per_layer = slab.shape[1]
        flat = slab.reshape(-1, *slab.shape[2:])
        mine = jax.lax.dynamic_slice_in_dim(flat, layer * per_layer, per_layer, axis=0)
        return read(mine, None, fresh)
    if slots is None:
        mine = jax.lax.dynamic_index_in_dim(slab, layer, keepdims=False)
        return read(mine, None, fresh)
    flat = slab.reshape(-1, *slab.shape[2:])
    return read(flat, layer * slab.shape[1] + slots, fresh)


def _put_rows(slab, layer, slots, rows):
    """The carried slab `[layers, slots, ...]` with layer `layer`'s rows for
    this batch replaced, in place: the slab is addressed as one run of
    `layers * slots` rows, not cut up and restacked."""
    per_layer = slab.shape[1]
    flat = slab.reshape(-1, *slab.shape[2:])
    if slots is None:
        flat = jax.lax.dynamic_update_slice_in_dim(flat, rows, layer * per_layer, axis=0)
    else:
        flat = flat.at[layer * per_layer + slots].set(rows)
    return flat.reshape(slab.shape)


@flax.struct.dataclass
class LayerCache:
    """An opened `DecodeState` / `PagedDecodeState` as the layers see it.
    `k`, `v`, `state`, `conv` are the carried buffers (`buffers`); the other
    fields are the same for every layer. Dense: `index` is the shared append
    position and `kv_segment_ids` the cache's filled-slot ids with the
    incoming chunk already merged in, so every layer masks against the same
    view. Paged: `lengths [B]` counts each row's tokens before this chunk,
    `block_tables` maps its pages; `slots`, `fresh` address the slab
    (`PagedDecodeState`); `window_tables` maps the window group's pages."""

    k: jnp.ndarray | None
    v: jnp.ndarray | None  # None beside a latent buffer in `k`
    state: jnp.ndarray | None = None
    conv: jnp.ndarray | None = None
    # the buffers of the layers that keep a window, where the stack has such
    # a group beside the layers that keep everything
    window_k: jnp.ndarray | None = None
    window_v: jnp.ndarray | None = None
    window_tables: jnp.ndarray | None = None
    fresh: jnp.ndarray | None = None
    index: jnp.ndarray | None = None
    kv_segment_ids: jnp.ndarray | None = None
    lengths: jnp.ndarray | None = None
    block_tables: jnp.ndarray | None = None
    slots: jnp.ndarray | None = None
    paged: bool = flax.struct.field(pytree_node=False, default=False)

    @property
    def buffers(self) -> tuple:
        return tuple(getattr(self, name) for name in _BUFFERS)

    def holding(self, buffers: tuple) -> "LayerCache":
        return self.replace(**dict(zip(_BUFFERS, buffers)))

    def attend(self, layer, q, k, v, segment_ids, *, window=None, scale=None,
               logits_soft_cap=None):
        """Append this chunk's post-RoPE k/v `[B, S, H*, D]` to layer
        `layer`'s part of the cache and attend q against that part; returns
        `(out, the cache holding the new buffers)`. `layer` is the loop's
        index among the stack's softmax-attention layers: traced under a
        scan, a Python int in a Python loop. In a stack with two groups
        (`window_k` is there) a layer that attends with a `window` is of the
        window group and `layer` counts that group's layers; any other layer
        is of the group that keeps everything. With `k` and `v` None the layer
        appends NOTHING and reads part `layer` as it stands: the keys and
        values another layer of the same call appended there (a stack whose
        later layers attend over one earlier layer's cache; the declaration
        counts them, `KVCacheSpec.readers`).

        Dense: the chunk goes in at the shared `index`. The causal term of
        the mask (q_offset = index) hides slots written after this chunk and
        `kv_segment_ids` (0 on unwritten/pad slots) hides garbage, so ONE
        program serves both prefill (chunk at index 0) and single-token
        decode steps. Always the XLA einsum path: the flash kernel's block
        tiling assumes q_len >= a block and a static q_offset.

        Paged: page writer and the ragged Pallas kernels on a TPU (one for
        a decoded token, one for a chunk), XLA elsewhere; padded chunk
        positions (segment id 0) go to the trash block. The window group's table is a ring as wide as its page
        budget, so neither the append nor the read reaches past it."""
        windowed = window is not None and self.window_k is not None
        names = ("window_k", "window_v") if windowed else ("k", "v")
        mine_k, mine_v = (getattr(self, name) for name in names)
        held = lambda ck, cv: self.replace(**dict(zip(names, (ck, cv))))
        if self.paged:
            from llm_training_tpu.ops.paged_attention import paged_cached_attention

            out, (ck, cv) = paged_cached_attention(
                q, k, v, (mine_k, mine_v), self.lengths,
                self.window_tables if windowed else self.block_tables,
                layer=layer,
                segment_ids=segment_ids,
                sliding_window=window,
                logits_soft_cap=logits_soft_cap,
                scale=scale,
                ring=windowed,
            )
            return out, held(ck, cv)
        ck, cv = (mine_k, mine_v) if k is None else (
            jax.lax.dynamic_update_slice(
                cache, new[None].astype(cache.dtype), (layer, 0, self.index, 0, 0)
            )
            for cache, new in zip((mine_k, mine_v), (k, v))
        )
        mine = lambda cache: jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)
        out = dot_product_attention(
            q, mine(ck).astype((q if k is None else k).dtype),
            mine(cv).astype((q if v is None else v).dtype),
            segment_ids=self.kv_segment_ids,
            q_segment_ids=segment_ids,
            causal=True,
            sliding_window=window,
            logits_soft_cap=logits_soft_cap,
            scale=scale,
            q_offset=self.index,
            impl="xla",
        )
        return out, held(ck, cv)

    def attend_latent(self, layer, q_nope, q_rope, row, w_kvb, segment_ids, *, scale):
        """A latent-attention (MLA) block's turn: append this chunk's rows
        `row [B, S, latent + rope]` (`[c_kv | rotated k_r]`, ONE a token) to
        part `layer` of the latent buffer, zeros up to the stored width, and
        attend `q_nope [B, S, H, nope]`, `q_rope [B, S, H, rope]` against that
        part; `w_kvb [latent, H, nope + v]` is the block's up-projection.
        Returns `(out [B, S, H, v], the cache holding the new buffer)`. One
        token a row attends in the absorbed form (paged: `mla_decode`), a
        chunk in the expanded one (paged: `mla_prefill`; both kernels on a
        TPU, `ops/latent_attention.py`). `layer` counts MLA blocks."""
        from llm_training_tpu.ops import latent_attention

        row = jnp.pad(row, ((0, 0), (0, 0), (0, self.k.shape[-1] - row.shape[-1])))
        if self.paged:
            out, pool = latent_attention.paged_latent_attention(
                q_nope, q_rope, row, w_kvb, self.k, self.lengths, self.block_tables,
                layer=layer, segment_ids=segment_ids, scale=scale,
            )
            return out, self.replace(k=pool)
        buffer = jax.lax.dynamic_update_slice(
            self.k, row[None, :, :, None, :].astype(self.k.dtype), (layer, 0, self.index, 0, 0)
        )
        mine = jax.lax.dynamic_index_in_dim(buffer, layer, keepdims=False)[:, :, 0]
        seq = row.shape[1]
        out = latent_attention.attend_rows(
            q_nope, q_rope, w_kvb,
            # one trip: the whole buffer; unwritten and padded slots have id 0
            lambda _: (mine, jnp.arange(mine.shape[1])[None], self.kv_segment_ids > 0),
            1, jnp.broadcast_to(self.index + jnp.arange(seq), segment_ids.shape),
            segment_ids > 0,
            scale=scale, absorbed=seq == 1,
        )
        return out, self.replace(k=buffer)

    def recurrent_rows(self, layer, read=_slot_rows, in_place=False, *, delta_step=False):
        """`(state [B, ...] float32, conv tail [B, ...])` of recurrent layer
        `layer` for this batch's rows. (`read`: the benchmark's planted fault,
        `benchmarks/tests/test_solar_open2.py`, swaps the slot read by its
        name in the family's module.) `in_place`: the caller's new state is
        an elementwise update of the state it is handed here, and goes back
        through `put_recurrent_rows(..., in_place=True)`. `delta_step`: the
        caller advances the state by ONE token of a delta rule through
        `ops/delta_rule.py:one_token_step`; where row i is slot i and the
        kernel takes the slab, the state handed out is the slab itself and
        the layer's place in it (`SlabRows`: nothing is read out), and what
        `one_token_step` gives back goes to `put_recurrent_rows` as ever."""
        if delta_step:
            as_they_lie = self.slots is None and self.fresh is None and read is _slot_rows
            where = slab_rows(self.state, layer, as_they_lie)
            if where is not None:
                return where, _layer_rows(self.conv, layer, self.slots, self.fresh, read)
        return (
            _layer_rows(self.state, layer, self.slots, self.fresh, read, in_place),
            _layer_rows(self.conv, layer, self.slots, self.fresh, read),
        )

    def put_recurrent_rows(self, layer, rows, in_place=False) -> "LayerCache":
        """`in_place` (a decode step, where row i is slot i): the new state is
        NOT made whole first; its update fuses with the write into the
        carried slab, which reads and writes the slab through one view, so
        the state is written where it lay and no array of a layer's states
        is made (`tests/test_chip_compile.py` holds that for the v5e)."""
        if isinstance(rows[0], SlabRows):
            # the kernel wrote the states where they lie: the slab it gave
            # back is the carried one; the tail is whole before it goes in
            tail = jax.lax.optimization_barrier(rows[1])
            return self.replace(
                state=rows[0].slab, conv=_put_rows(self.conv, layer, self.slots, tail)
            )
        # the new rows are whole before they go in: fused into the update,
        # their computation reads the slab it writes, and the compiler then
        # copies the whole slab first, once a layer
        if in_place and self.slots is None:
            rows = (rows[0], jax.lax.optimization_barrier(rows[1]))
        else:
            rows = jax.lax.optimization_barrier(rows)
        state, conv = (
            _put_rows(slab, layer, self.slots, new)
            for slab, new in zip((self.state, self.conv), rows)
        )
        return self.replace(state=state, conv=conv)


def open_cache(decode_state, segment_ids, batch: int, seq: int):
    """-> `(LayerCache or None, segment_ids)`, before the layer loop. With a
    state the chunk's q-side segment ids (pads 0, real tokens 1; all ones
    when not given) are part of the cache's bookkeeping: dense, they double
    as the ids of the slots the chunk writes and are merged into the
    filled-slot map here, BEFORE the layers; paged, they mark the padded
    chunk positions the append sends to the trash block."""
    if decode_state is None:
        return None, segment_ids
    if segment_ids is None:
        segment_ids = jnp.ones((batch, seq), jnp.int32)
    held = {name: getattr(decode_state, name) for name in _BUFFERS}
    if isinstance(decode_state, PagedDecodeState):
        return LayerCache(
            **held, paged=True, lengths=decode_state.lengths,
            block_tables=decode_state.block_tables,
            window_tables=decode_state.window_tables,
            slots=decode_state.slots, fresh=decode_state.fresh,
        ), segment_ids
    return LayerCache(
        **held, index=decode_state.index,
        kv_segment_ids=jax.lax.dynamic_update_slice(
            decode_state.segment_ids, segment_ids.astype(jnp.int32),
            (0, decode_state.index),
        ),
    ), segment_ids


def close_cache(cache: LayerCache | None, decode_state, segment_ids):
    """The state after the call (None on the training path): buffers
    replaced; paged rows advance by the chunk's REAL token count (padded
    tail positions of a final prefill chunk occupy no slot), the dense index
    by the chunk's width."""
    if cache is None:
        return None
    held = dict(zip(_BUFFERS, cache.buffers))
    if cache.paged:
        return decode_state.replace(
            **held,
            lengths=decode_state.lengths
            + jnp.sum(segment_ids > 0, axis=1).astype(jnp.int32),
        )
    return decode_state.replace(
        **held, index=decode_state.index + segment_ids.shape[1],
        segment_ids=cache.kv_segment_ids,
    )


def _whole_leaves(params, names):
    """The part of a scanned body's stacked `params` that holds the leaves
    called one of `names`, each `[L, ...]`, under their modules' names; None
    where there is none (so also while the parameters are being made)."""
    found = {
        path: nn.meta.unbox(leaf)
        for path, leaf in flatten_dict(params).items() if path[-1] in names
    }
    return unflatten_dict(found) or None


def scan_layers(body, args: tuple, length: int, hidden, inputs: tuple,
                cache: LayerCache | None = None, whole: tuple[str, ...] = (),
                name: str = "layers"):
    """`body(*args, name=name)` run `length` times by `nn.scan`, params
    stacked on axis 0: `-> (hidden, stacked ys, cache)`. Training: the body
    is called `(hidden, *inputs) -> (hidden, ys)`. Decoding: the buffers are
    CARRIED beside `hidden` and the step's index scanned over (as a scanned
    input and output each step would cut its slice out of the stack and
    write a whole slice into a new one), so the body is called `((hidden,
    buffers), *inputs, cache=<the rest of the cache>, layer=<step>)` and
    puts them together with `cache.holding(buffers)`. Same param scope
    either way: only one of the two traces per call.

    `whole` names parameter leaves that a decoding layer must not have cut
    out of the stack for it (the scan's slice of a leaf is a copy wherever
    its reader wants a buffer of its own: `models/moe.py:grouped_matmul`).
    A body that names some is called with one more argument, `stack=`:
    those leaves whole, `[L, ...]`, nested under the body's module names as
    its params are and closed over like the rest of the cache; None where
    the stack has no such leaf. The body still owns its slices of them; a
    layer that reads the stack leaves them unread, and the compiler drops
    the cut.

    Which reads of a stacked leaf are in place (docs/inference.md, "How a
    layer meets a stacked weight"): a plain product on the slice is; a reader
    that wants its own buffer is not, hence `whole=`; a product whose result
    is reshaped to heads is only behind `models/llama/model.py:_plain_rows`
    (left free, the compiler wants that weight transposed and copies the
    slice to get it)."""
    decoding = cache is not None
    extra = ((nn.broadcast, 0) + (nn.broadcast,) * bool(whole)) if decoding else ()
    scanned = nn.scan(
        body,
        variable_axes={"params": 0},
        split_rngs={"params": True},
        in_axes=(nn.broadcast,) * len(inputs) + extra,
        length=length,
        metadata_params={nn.PARTITION_NAME: "layers"},
    )(*args, name=name)
    if not decoding:
        hidden, ys = scanned(hidden, *inputs)
        return hidden, ys, None
    stack = ()
    if whole:
        stack = (_whole_leaves(scanned.variables.get("params", {}), whole),)
    (hidden, buffers), ys = scanned(
        (hidden, cache.buffers), *inputs, cache.holding((None,) * len(_BUFFERS)),
        jnp.arange(length, dtype=jnp.int32), *stack,
    )
    return hidden, ys, cache.holding(buffers)
