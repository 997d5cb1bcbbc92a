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
beside `hidden`, whole: a layer writes its new rows into them in place and
reads its own part (`models/cache.py`).
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
from llm_training_tpu.models.cache import _slot_rows, close_cache, open_cache, scan_layers
from llm_training_tpu.models.deepseek.model import DeepseekMoE
from llm_training_tpu.models.llama.model import RMSNorm, _dense, _plain_rows
from llm_training_tpu.models.moe import EXPERT_LEAVES, decoding_experts
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.models.solar_open2.config import SolarOpen2Config
from llm_training_tpu.models.solar_open2.kda import kda_chunked, kda_step
from llm_training_tpu.ops import dot_product_attention
from llm_training_tpu.ops.delta_rule import l2norm as _l2norm, one_token_step, short_conv


class KimiDeltaAttention(nn.Module):
    """`rows` is this layer's `(state [B, H, dk, dv] float32, tail
    [B, taps, 3 * H * dk])` for the batch's rows, or None (training: zero
    state, zero tail). Returns `(out, new rows)`, the rows None without any."""

    config: SolarOpen2Config

    @nn.compact
    def __call__(self, hidden, segment_ids=None, rows=None):
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
            (q, k, v), new_tail = short_conv(
                mixed, conv_w, None if rows is None else rows[1], segment_ids, valid, 3
            )

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
            jnp.zeros((batch, heads, dim, dim), jnp.float32) if rows is None else rows[0]
        )
        if rows is not None and seq == 1:
            with jax.named_scope("kda_recurrence"):
                state, out = one_token_step(
                    state, kda_step, q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0]
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
        return out, None if rows is None else (state, new_tail.astype(rows[1].dtype))


class GatedAttention(nn.Module):
    """Softmax attention with no positional term, gated an output channel.
    Returns `(out, cache)`: with a `cache` (`models/cache.py`) k/v are
    appended to part `layer` of it (this layer's index among the stack's GQA
    layers) and attention runs against that part."""

    config: SolarOpen2Config

    @nn.compact
    def __call__(self, hidden, segment_ids=None, cache=None, layer=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, kv_heads, dim = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(cfg, heads * dim, ("embed", "heads"), "q_proj", False)(hidden)
        k = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "k_proj", False)(hidden)
        v = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "v_proj", False)(hidden)
        q, k, v = _plain_rows(cache, (q, k, v))
        q = q.reshape(batch, seq, heads, dim)
        k = k.reshape(batch, seq, kv_heads, dim)
        v = v.reshape(batch, seq, kv_heads, dim)
        if cache is not None:
            out, cache = cache.attend(layer, q, k, v, segment_ids)
        else:
            out = dot_product_attention(
                q, k, v, segment_ids=segment_ids, causal=True, impl=cfg.attention_impl
            )
        out = out.astype(hidden.dtype).reshape(batch, seq, heads * dim)
        if cfg.use_gqa_gate:
            gate = _dense(cfg, heads * dim, ("embed", "heads"), "g_proj", False)(hidden)
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", False)(out), cache


class SolarOpen2DecoderLayer(nn.Module):
    """Returns (hidden, router stats, cache). `layer` is this layer's index
    among the stack's layers of its kind: a GQA layer's part of the cache's
    pool (or dense buffers), a KDA layer's rows of its slab. `stack =
    (leaves, index)`: what a decoding layer's experts are read from
    (`models/moe.py:decoding_experts`)."""

    config: SolarOpen2Config
    is_gqa: bool

    @nn.compact
    def __call__(self, hidden, segment_ids=None, cache=None, layer=None, stack=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)
        rows = None
        if self.is_gqa:
            mixed, cache = GatedAttention(cfg, name="self_attn")(
                norm("input_layernorm")(hidden), segment_ids, cache, layer
            )
        else:
            if cache is not None:
                # `_slot_rows` by this module's name: where the benchmark's
                # tests plant their fault (`LayerCache.recurrent_rows`)
                # one token a slot: where the kernel takes the slab, the state
                # is advanced where it lies (`ops/delta_rule.py:one_token_step`)
                rows = cache.recurrent_rows(layer, _slot_rows, delta_step=hidden.shape[1] == 1)
            mixed, rows = KimiDeltaAttention(cfg, name="linear_attn")(
                norm("input_layernorm")(hidden), segment_ids, rows
            )
        hidden = hidden + mixed
        pad_mask = None if segment_ids is None else segment_ids > 0
        mlp_out, stats = DeepseekMoE(cfg, name="mlp")(
            norm("post_attention_layernorm")(hidden), pad_mask, stack
        )
        hidden = hidden + mlp_out
        if rows is not None:
            cache = cache.put_recurrent_rows(layer, rows)
        return hidden, stats, cache


class _PeriodBody(nn.Module):
    """Scan body: the layers `first .. first + len(kinds)` of the pattern
    (one period). The carry is `hidden` or, when decoding, `(hidden, the
    cache's buffers)`: the pool with a leading axis over ALL the stack's GQA
    layers, the slab over all its KDA layers, and `cycle` says which period
    of them this is (`models/cache.py:scan_layers`); `stack` holds the
    periods' expert leaves whole (None under the loop)."""

    config: SolarOpen2Config
    kinds: tuple[bool, ...]

    @nn.compact
    def __call__(self, carry, segment_ids, cache=None, cycle=None, stack=None):
        cfg = self.config
        hidden = carry
        if cache is not None:
            hidden, buffers = carry
            cache = cache.holding(buffers)
        stats = []
        for j, is_gqa in enumerate(self.kinds):
            # this layer's index among the stack's layers of its kind
            index = None if cache is None else (
                cycle * self.kinds.count(is_gqa) + self.kinds[:j].count(is_gqa)
            )
            hidden, layer_stats, cache = SolarOpen2DecoderLayer(cfg, is_gqa, name=f"slot{j}")(
                hidden, segment_ids, cache, index,
                decoding_experts(cache, stack, cycle, f"slot{j}", "mlp"),
            )
            stats.append(layer_stats)
        stats = jax.tree.map(lambda *leaves: jnp.stack(leaves), *stats)
        return (hidden if cache is None else (hidden, cache.buffers)), stats


class SolarOpen2(nn.Module):
    """Solar-Open2 causal LM with the `CausalLMProto` surface, decoding
    through `decode_state` (dense or paged) like the Llama stack."""

    config: SolarOpen2Config

    def _layers(self, hidden, segment_ids, cache):
        """-> (hidden, pooled router stats [L, ...], cache or None)."""
        cfg = self.config
        kinds = cfg.layer_kinds
        period = cfg.scan_period or cfg.num_hidden_layers
        body = _PeriodBody
        policy = _remat_policy(cfg)
        if policy is not None:
            body = nn.remat(_PeriodBody, policy=policy, prevent_cse=False)
        if cfg.scan_period:
            hidden, stats, cache = scan_layers(
                body, (cfg, tuple(kinds[:period])), cfg.num_hidden_layers // period,
                hidden, (segment_ids,), cache, whole=EXPERT_LEAVES,
            )
            return hidden, jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), stats), cache
        # the loop: the whole stack is one body, under the scan's names
        if cache is None:
            hidden, stats = body(cfg, tuple(kinds), name="layers")(hidden, segment_ids)
            return hidden, stats, None
        (hidden, buffers), stats = body(cfg, tuple(kinds), name="layers")(
            (hidden, cache.buffers), segment_ids, cache, 0
        )
        return hidden, stats, cache.holding(buffers)

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

        cache, segment_ids = open_cache(decode_state, segment_ids, hidden.shape[0], seq)
        hidden, (sel_frac, mean_prob, dropped), cache = self._layers(hidden, segment_ids, cache)
        new_decode_state = close_cache(cache, decode_state, segment_ids)

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
