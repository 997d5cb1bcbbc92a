"""Solar-Open2 decoder (`model_type: solar_open2`), TPU-native.

Every layer is `x += mixer(RMSNorm(x)); x += moe(RMSNorm(x))`. The mixer is

- on the layers in `gqa_layers` (every 4th, from 0): softmax attention over
  grouped key/value heads with NO positional term at all (`use_rope` false)
  and a sigmoid gate an output channel: `o_proj(attn * sigmoid(g_proj x))`;
- on the others: Kimi Delta Attention (`kda.py`): q, k, v projections through
  one causal depthwise conv (width 4) and SiLU, q and k L2-normalised a
  head, a decay per key channel from a low-rank projection, a write strength
  `2 * sigmoid` a head, the delta-rule recurrence in float32, then
  `o_proj(RMSNorm_head(o) * sigmoid(low-rank gate))`.

The MoE is `deepseek.DeepseekMoE` (sigmoid scores, correction bias, top-k
normalised, shared experts always on), which a config with `experts_held`
turns into an expert-parallel share.

Decoding (docs/inference.md, docs/serving.md): the two kinds of layer keep
two kinds of cache, declared once by `SolarOpen2Config.cache_specs()`. A GQA
layer appends its keys and values to the paged pool (or the dense buffer)
exactly as `LlamaAttention` does. A KDA layer reads and writes its decode
slot's slab: the [heads, 128, 128] float32 state and the conv's last three
inputs. One token is the recurrence itself (`kda_step`); a chunk is the
chunked rule from the slot's state to the slot's state. Positions with
segment id 0 (padding, idle decode slots) change nothing.

The stack scans over periods of the layer pattern ([GQA, KDA, KDA, KDA] as
published). When decoding, the pool and the slab ride that loop as its carry
beside `hidden`, whole: a layer writes its new rows into them in place
(`llama.model.cached_attention`, `_put_rows`) and reads its own part.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import (
    CausalLMOutput,
    DecodeState,
    PagedDecodeState,
    RouterStats,
)
from llm_training_tpu.models.deepseek.model import DeepseekMoE
from llm_training_tpu.models.llama.model import RMSNorm, _dense, cached_attention
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.models.solar_open2.config import SolarOpen2Config
from llm_training_tpu.models.solar_open2.kda import kda_chunked, kda_step
from llm_training_tpu.ops import dot_product_attention


def _l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class KimiDeltaAttention(nn.Module):
    """`cache` is this layer's `(state [B, H, dk, dv] float32, tail
    [B, taps, 3 * H * dk])` for the batch's rows, or None (training: zero
    state, zero tail). With a cache the call returns `(out, new_cache)`."""

    config: SolarOpen2Config

    @nn.compact
    def __call__(self, hidden, segment_ids=None, cache=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, dim = cfg.linear_num_heads, cfg.linear_head_dim
        width, rank, taps = heads * dim, cfg.kda_rank, cfg.linear_conv_kernel_dim - 1
        valid = (
            jnp.ones((batch, seq), bool) if segment_ids is None else segment_ids > 0
        )
        # a packed document starts from a zero state and its own conv window
        cut = segment_ids is not None

        with jax.named_scope("kda_conv"):
            mixed = jnp.concatenate(
                [_dense(cfg, width, ("embed", "heads"), name, False)(hidden)
                 for name in ("q_proj", "k_proj", "v_proj")],
                axis=-1,
            )
            conv_w = self.param(
                "conv_kernel",
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.initializer_range), (None, "heads")
                ),
                (taps + 1, 3 * width),
                cfg.param_jnp_dtype,
            ).astype(jnp.float32)
            # a padded position feeds nothing: not the conv, not the tail
            mixed = jnp.where(valid[..., None], mixed, 0)
            tail = (
                jnp.zeros((batch, taps, 3 * width), mixed.dtype) if cache is None
                else cache[1].astype(mixed.dtype)
            )
            padded = jnp.concatenate([tail, mixed], axis=1)
            if cut:
                # a tap never crosses a document boundary; the tail is the
                # first position's own document (a request's earlier chunk)
                seg_p = jnp.concatenate(
                    [jnp.broadcast_to(segment_ids[:, :1], (batch, taps)), segment_ids], axis=1
                )
            conv = 0.0
            for i in range(taps + 1):
                tap = padded[:, i:i + seq].astype(jnp.float32) * conv_w[i]
                if cut:
                    tap = jnp.where((seg_p[:, i:i + seq] == segment_ids)[..., None], tap, 0.0)
                conv = conv + tap
            q, k, v = jnp.split(jax.nn.silu(conv), 3, axis=-1)
            # the last `taps` inputs up to the last real position
            end = jnp.max(jnp.where(valid, jnp.arange(1, seq + 1), 0), axis=1)
            new_tail = jax.vmap(
                lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, taps, axis=0)
            )(padded, end)

        by_head = lambda x: x.reshape(batch, seq, heads, dim)
        q = _l2norm(by_head(q)) * dim ** -0.5
        k = _l2norm(by_head(k))
        v = by_head(v)

        with jax.named_scope("kda_gates"):
            low = lambda name: _dense(cfg, rank, ("embed", None), name, False)
            up = lambda name: _dense(cfg, width, (None, "heads"), name, False)
            a_log = self.param(
                "A_log", nn.with_logical_partitioning(nn.initializers.zeros_init(), ("heads",)),
                (heads,), jnp.float32,
            )
            dt_bias = self.param(
                "dt_bias", nn.with_logical_partitioning(nn.initializers.zeros_init(), ("heads",)),
                (width,), jnp.float32,
            )
            decay_in = up("f_b_proj")(low("f_a_proj")(hidden)).astype(jnp.float32)
            log_alpha = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                by_head(decay_in + dt_bias)
            )
            strength = 2.0 if cfg.kda_allow_neg_eigval else 1.0
            beta = strength * jax.nn.sigmoid(
                _dense(cfg, heads, ("embed", "heads"), "b_proj", False)(hidden).astype(jnp.float32)
            )
            gate = jax.nn.sigmoid(up("g_b_proj")(low("g_a_proj")(hidden)).astype(jnp.float32))
            log_alpha = jnp.where(valid[..., None, None], log_alpha, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)

        state = (
            jnp.zeros((batch, heads, dim, dim), jnp.float32) if cache is None else cache[0]
        )
        if cache is not None and seq == 1:
            with jax.named_scope("kda_recurrence"):
                state, out = kda_step(
                    state, q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0]
                )
                out = out[:, None]
        else:
            starts = None
            if cut:
                before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
                starts = valid & (segment_ids != before)
            with jax.named_scope("kda_chunk"):
                out, state = kda_chunked(q, k, v, log_alpha, beta, state, starts)
        out = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="o_norm")(out)
        out = (out.reshape(batch, seq, width) * gate).astype(hidden.dtype)
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", False)(out)
        if cache is None:
            return out
        return out, (state, new_tail.astype(cache[1].dtype))


class GatedAttention(nn.Module):
    """Softmax attention with no positional term, gated an output channel.
    `cache`, `kv_index`, `kv_segment_ids`, `layer`: as `LlamaAttention`'s
    `layer_kv` plumbing (`llama.model.cached_attention`), dense or paged:
    the cache of every GQA layer, and this layer's index in it."""

    config: SolarOpen2Config

    @nn.compact
    def __call__(self, hidden, segment_ids=None, cache=None, kv_index=None,
                 kv_segment_ids=None, layer=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, kv_heads, dim = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(cfg, heads * dim, ("embed", "heads"), "q_proj", False)(hidden)
        k = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "k_proj", False)(hidden)
        v = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "v_proj", False)(hidden)
        q = q.reshape(batch, seq, heads, dim)
        k = k.reshape(batch, seq, kv_heads, dim)
        v = v.reshape(batch, seq, kv_heads, dim)
        new_cache = None
        if cache is not None:
            out, new_cache = cached_attention(
                q, k, v, segment_ids, cache, kv_index, kv_segment_ids, layer
            )
        else:
            out = dot_product_attention(
                q, k, v, segment_ids=segment_ids, causal=True, impl=cfg.attention_impl
            )
        out = out.astype(hidden.dtype).reshape(batch, seq, heads * dim)
        if cfg.use_gqa_gate:
            gate = _dense(cfg, heads * dim, ("embed", "heads"), "g_proj", False)(hidden)
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", False)(out)
        if cache is None:
            return out
        return out, new_cache


class SolarOpen2DecoderLayer(nn.Module):
    """Returns (hidden, router stats, new cache or None). `cache` is, for a
    GQA layer, the pool (or dense buffers) of every GQA layer with `layer`
    this one's index in it; for a KDA layer, its rows of the slab."""

    config: SolarOpen2Config
    is_gqa: bool

    @nn.compact
    def __call__(self, hidden, segment_ids=None, cache=None, kv_index=None,
                 kv_segment_ids=None, layer=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)
        normed = norm("input_layernorm")(hidden)
        if self.is_gqa:
            mixed = GatedAttention(cfg, name="self_attn")(
                normed, segment_ids, cache, kv_index, kv_segment_ids, layer
            )
        else:
            mixed = KimiDeltaAttention(cfg, name="linear_attn")(normed, segment_ids, cache)
        new_cache = None
        if cache is not None:
            mixed, new_cache = mixed
        hidden = hidden + mixed
        pad_mask = None if segment_ids is None else segment_ids > 0
        mlp_out, stats = DeepseekMoE(cfg, name="mlp")(
            norm("post_attention_layernorm")(hidden), pad_mask
        )
        return hidden + mlp_out, stats, new_cache


def _slot_rows(slab, slots, fresh):
    """A slab layer's rows for this batch: `slots` picks them (None: row i is
    slot i), and a row whose request starts here reads zeros."""
    rows = slab if slots is None else slab[slots]
    if fresh is not None:
        rows = jnp.where(fresh.reshape((-1,) + (1,) * (rows.ndim - 1)), 0, rows)
    return rows


def _layer_rows(slab, layer, slots, fresh):
    """`_slot_rows` of layer `layer` of the whole slab `[layers, slots, ...]`.
    Picked slots are gathered out of the slab seen as one run of `layers *
    slots` rows, so the layer's part is not cut out first."""
    if slots is None:
        mine = jax.lax.dynamic_index_in_dim(slab, layer, keepdims=False)
        return _slot_rows(mine, None, fresh)
    flat = slab.reshape(-1, *slab.shape[2:])
    return _slot_rows(flat, layer * slab.shape[1] + slots, fresh)


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


class _PeriodBody(nn.Module):
    """Scan body: the layers `first .. first + len(kinds)` of the pattern
    (one period). The carry is `hidden` or, when decoding, `(hidden, pool,
    slab)`: pool `(k, v)` with a leading axis over ALL the stack's GQA
    layers, slab `(state, tail)` over all its KDA layers, and `cycle` says
    which period of them this is. `ctx` holds `kv_index`, `kv_segment_ids`
    and, for a paged slab, `slots` and `fresh`."""

    config: SolarOpen2Config
    kinds: tuple[bool, ...]

    @nn.compact
    def __call__(self, carry, segment_ids, ctx=None, cycle=None):
        cfg = self.config
        ctx = ctx or {}
        slots, fresh = ctx.get("slots"), ctx.get("fresh")
        hidden, pool, slab = (carry, None, None) if cycle is None else carry
        stats = []
        for j, is_gqa in enumerate(self.kinds):
            layer = SolarOpen2DecoderLayer(cfg, is_gqa, name=f"slot{j}")
            if cycle is None:
                hidden, layer_stats, _ = layer(hidden, segment_ids)
                stats.append(layer_stats)
                continue
            # this layer's index among the stack's layers of its kind
            index = cycle * self.kinds.count(is_gqa) + self.kinds[:j].count(is_gqa)
            if is_gqa:
                hidden, layer_stats, pool = layer(
                    hidden, segment_ids, pool, ctx["kv_index"], ctx["kv_segment_ids"], index
                )
            else:
                rows = jax.tree.map(lambda a: _layer_rows(a, index, slots, fresh), slab)
                hidden, layer_stats, new = layer(hidden, segment_ids, rows)
                # the new rows are whole before they go in: fused into the
                # update, their computation reads the slab it writes, and the
                # compiler then copies the whole slab first, once a layer
                new = jax.lax.optimization_barrier(new)
                slab = jax.tree.map(lambda a, n: _put_rows(a, index, slots, n), slab, new)
            stats.append(layer_stats)
        stats = jax.tree.map(lambda *leaves: jnp.stack(leaves), *stats)
        return (hidden if cycle is None else (hidden, pool, slab)), stats


class SolarOpen2(nn.Module):
    """Solar-Open2 causal LM with the `CausalLMProto` surface, decoding
    through `decode_state` (dense or paged) like the Llama stack."""

    config: SolarOpen2Config

    def _layers(self, hidden, segment_ids, ctx, caches):
        """-> (hidden, pooled router stats [L, ...], new (pool, slab) or None)."""
        cfg = self.config
        kinds = cfg.layer_kinds
        period = cfg.scan_period or cfg.num_hidden_layers
        cycles = cfg.num_hidden_layers // period
        body = _PeriodBody
        policy = _remat_policy(cfg)
        if policy is not None:
            body = nn.remat(_PeriodBody, policy=policy, prevent_cse=False)
        carry = hidden if caches is None else (hidden, *caches)
        if not cfg.scan_period:
            # the loop: the whole stack is one body, under the scan's names
            carry, stats = body(cfg, tuple(kinds), name="layers")(
                carry, segment_ids, ctx, None if caches is None else 0
            )
        else:
            # the caches are CARRIED, whole, and the period's index scanned over
            scanned = nn.scan(
                body, variable_axes={"params": 0}, split_rngs={"params": True},
                in_axes=(nn.broadcast,) if caches is None else (nn.broadcast, nn.broadcast, 0),
                length=cycles, metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, tuple(kinds[:period]), name="layers")
            if caches is None:
                carry, stats = scanned(carry, segment_ids)
            else:
                carry, stats = scanned(
                    carry, segment_ids, ctx, jnp.arange(cycles, dtype=jnp.int32)
                )
            stats = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), stats)
        if caches is None:
            return carry, stats, None
        return carry[0], stats, carry[1:]

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,  # no layer reads a position
        inputs_embeds: jnp.ndarray | None = None,
        compute_logits: bool = True,
        return_last_hidden_states: bool = False,
        decode_state: DecodeState | PagedDecodeState | None = None,
    ) -> CausalLMOutput:
        cfg = self.config
        embed_tokens = nn.Embed(
            num_embeddings=cfg.vocab_size,
            features=cfg.hidden_size,
            dtype=cfg.compute_jnp_dtype,
            param_dtype=cfg.param_jnp_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), ("vocab", "embed")
            ),
            name="embed_tokens",
        )
        if inputs_embeds is None:
            if input_ids is None:
                raise ValueError("one of input_ids / inputs_embeds is required")
            inputs_embeds = embed_tokens(input_ids)
        hidden = inputs_embeds
        seq = hidden.shape[1]

        ctx = caches = None
        paged = isinstance(decode_state, PagedDecodeState)
        if decode_state is not None:
            if segment_ids is None:
                segment_ids = jnp.ones((hidden.shape[0], seq), jnp.int32)
            caches = ((decode_state.k, decode_state.v), (decode_state.state, decode_state.conv))
            if paged:
                # the paged plumbing of the Llama stack: per-row lengths for
                # the append position, the block table where the dense cache
                # has its filled-slot map
                ctx = {"kv_index": decode_state.lengths,
                       "kv_segment_ids": decode_state.block_tables}
                if decode_state.slots is not None:
                    ctx["slots"] = decode_state.slots
                if decode_state.fresh is not None:
                    ctx["fresh"] = decode_state.fresh
            else:
                ctx = {
                    "kv_index": decode_state.index,
                    "kv_segment_ids": jax.lax.dynamic_update_slice(
                        decode_state.segment_ids, segment_ids.astype(jnp.int32),
                        (0, decode_state.index),
                    ),
                }

        hidden, (sel_frac, mean_prob, dropped), new_caches = self._layers(
            hidden, segment_ids, ctx, caches
        )

        new_decode_state = None
        if decode_state is not None:
            (new_k, new_v), (new_state, new_conv) = new_caches
            new_decode_state = decode_state.replace(
                k=new_k, v=new_v, state=new_state, conv=new_conv
            )
            if paged:
                new_decode_state = new_decode_state.replace(
                    lengths=decode_state.lengths
                    + jnp.sum(segment_ids > 0, axis=1).astype(jnp.int32),
                )
            else:
                new_decode_state = new_decode_state.replace(
                    index=decode_state.index + seq, segment_ids=ctx["kv_segment_ids"],
                )

        hidden = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        logits = None
        if compute_logits:
            if cfg.tie_word_embeddings:
                logits = embed_tokens.attend(hidden)
            else:
                logits = _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head", False)(hidden)
            logits = nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))

        ep_dropped = dropped.sum()
        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            # the correction bias balances the experts: no auxiliary loss
            aux_loss=None,
            ep_dropped_rows=ep_dropped,
            router_stats=RouterStats(
                sel_frac=sel_frac, mean_prob=mean_prob, dropped=ep_dropped,
                layer_ids=tuple(range(cfg.num_hidden_layers)),
            ),
            decode_state=new_decode_state,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        if self.config.tie_word_embeddings:
            return "embed_tokens/embedding"
        return "lm_head/kernel"
