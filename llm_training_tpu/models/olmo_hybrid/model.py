"""Olmo-Hybrid decoder (`model_type: olmo_hybrid`), TPU-native.

Every layer is `h = x + N(mixer(x)); y = h + N(mlp(h))`: the RMSNorm sits on
a sub-block's OUTPUT (Olmo 2/3's order), the MLP is a dense SwiGLU, then
`logits = Head(N_f y)`, the head untied. The mixer is

- on a `linear_attention` layer (three of four, first): a gated delta rule
  (`ops/delta_rule.py`). q, k (heads x 96) and v (heads x 192) each through
  a causal depthwise convolution of 4 taps and SiLU; q and k L2-normalised a
  head, q scaled by 96^-1/2; a write strength `beta = 2 sigmoid(W_b x)` and
  ONE decay `g = -exp(A_log) softplus(W_a x + dt_bias)` a head; the
  recurrence on a float32 [96, 192] state a head; then
  `o_proj(RMSNorm_192(o) * silu(g_proj x))`;
- on a `full_attention` layer: multi-head softmax attention, an RMSNorm over
  the whole q and the whole k projection, NO positional term at all
  (`rope_theta: null`).

Decoding (docs/inference.md, docs/serving.md): the two kinds of layer keep
two kinds of cache, declared once by `OlmoHybridConfig.cache_specs()`. A
full layer appends its keys and values to the paged pool (or the dense
buffer) exactly as `LlamaAttention` does. A linear layer reads and writes
its decode slot's slab: the state as it is STORED (`RecurrentCacheSpec.
stored`: two heads side by side, `[15, 96, 384]`, whole tiles of the chip)
and the convolutions' last three inputs. One token is the recurrence itself
on the stored state (`gated_delta_step`); a chunk is the chunked rule from
the slot's state to the slot's state. Positions with segment id 0 (padding,
idle decode slots, slots still prefilling) change nothing: beta = 0, g = 0.

The stack scans over periods of the layer pattern ([linear x 3, full] as
published). When decoding, the pool and the slab ride that loop as its carry
beside `hidden`, whole (`models/cache.py`). Scopes inside `/linear_attn/`:
`gdn_conv`, `gdn_gates`, `gdn_recurrence` (one token) or `gdn_chunk`,
`gdn_out` (docs/observability.md).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import CausalLMOutput, DecodeState, PagedDecodeState
from llm_training_tpu.models.cache import _slot_rows, close_cache, open_cache, scan_layers
from llm_training_tpu.models.llama.model import LlamaMLP, RMSNorm, _dense, _plain_rows
from llm_training_tpu.models.olmo_hybrid.config import OlmoHybridConfig
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.ops import dot_product_attention
from llm_training_tpu.ops.delta_rule import (
    gated_delta_chunked,
    gated_delta_step,
    l2norm,
    one_token_step,
    pack_heads,
    short_conv,
    unpack_heads,
)


def _a_log_init(key, shape, dtype=jnp.float32):
    """Gated DeltaNet's: A uniform in (0, 16]."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-3, 16.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Gated DeltaNet's: dt log-uniform in [1e-3, 1e-1], through softplus' inverse."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


class GatedDeltaNet(nn.Module):
    """The gated-delta-rule mixer of every family that decodes with one
    (this one, `gigachat35`). `rows` is this layer's `(state [B,
    *RecurrentCacheSpec.stored] float32, tail [B, taps, channels])` for the
    batch's rows, or None (training: zero state, zero tail). Returns `(out,
    new rows)`, the rows None without any.

    `config` gives the sizes (`linear_num_key_heads`, `linear_num_value_heads`,
    `linear_key_head_dim`, `linear_value_head_dim`, `linear_conv_kernel_dim`,
    `delta_chunk_size`); what differs between the families is the caller's:
    `joint`: q, k and v are ONE projection (`qkv_proj`) under ONE convolution
    (`conv_kernel`), not three of each; `beta_max`: the write strength is
    `beta_max * sigmoid(.)` (above 1: negative eigenvalues); `out_norm`
    (`name -> a norm module` over a value head; None: RMSNorm at
    `rms_norm_eps`) and `out_gate` (what the gate projection goes through):
    `o_proj(out_norm(o) * out_gate(g_proj x))`. Fewer key heads than value
    heads: each key head serves `value heads / key heads` neighbouring value
    heads; q and k are normalised a key head and only the token's vectors are
    repeated, never a state."""

    config: Any
    joint: bool = False
    beta_max: float = 2.0
    out_norm: Any = None
    out_gate: Any = jax.nn.silu

    @nn.compact
    def __call__(self, hidden, segment_ids=None, rows=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, key_heads = cfg.linear_num_value_heads, cfg.linear_num_key_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        widths = (key_heads * dk, key_heads * dk, heads * dv)
        taps = cfg.linear_conv_kernel_dim - 1
        valid = (
            jnp.ones((batch, seq), bool) if segment_ids is None else segment_ids > 0
        )
        conv_param = lambda name, width: self.param(
            name,
            nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), (None, "heads")
            ),
            (taps + 1, width),
            cfg.param_jnp_dtype,
        )
        if self.joint:
            mixed = _dense(cfg, sum(widths), ("embed", "heads"), "qkv_proj", False)(hidden)
        else:
            mixed = jnp.concatenate(
                [_dense(cfg, width, ("embed", "heads"), name, False)(hidden)
                 for name, width in zip(("q_proj", "k_proj", "v_proj"), widths)],
                axis=-1,
            )
        with jax.named_scope("gdn_conv"):
            if self.joint:
                conv_w = conv_param("conv_kernel", sum(widths)).astype(jnp.float32)
            else:
                # three convolutions, one a projection; depthwise, so they run as one
                conv_w = jnp.concatenate([
                    conv_param(f"{name}_conv_kernel", width) for name, width in zip("qkv", widths)
                ], axis=-1).astype(jnp.float32)
            (q, k, v), new_tail = short_conv(
                mixed, conv_w, None if rows is None else rows[1], segment_ids, valid,
                (widths[0], 2 * widths[0]),
            )

        q = l2norm(q.reshape(batch, seq, key_heads, dk)) * dk ** -0.5
        k = l2norm(k.reshape(batch, seq, key_heads, dk))
        if key_heads != heads:
            # a key head's two vectors a token, once a value head it serves
            q, k = (jnp.repeat(x, heads // key_heads, axis=2) for x in (q, k))
        v = v.reshape(batch, seq, heads, dv)

        with jax.named_scope("gdn_gates"):
            a_log = self.param(
                "A_log", nn.with_logical_partitioning(_a_log_init, ("heads",)),
                (heads,), jnp.float32,
            )
            dt_bias = self.param(
                "dt_bias", nn.with_logical_partitioning(_dt_bias_init, ("heads",)),
                (heads,), jnp.float32,
            )
            small = lambda name: _dense(cfg, heads, ("embed", "heads"), name, False)(
                hidden
            ).astype(jnp.float32)
            g = -jnp.exp(a_log) * jax.nn.softplus(small("a_proj") + dt_bias)
            beta = self.beta_max * jax.nn.sigmoid(small("b_proj"))
            g = jnp.where(valid[..., None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)

        if rows is not None and seq == 1:
            with jax.named_scope("gdn_recurrence"):
                # on the state as it is stored: nothing of its size is reshaped
                state, out = one_token_step(
                    rows[0], gated_delta_step, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
                )
                out = out[:, None]
        else:
            starts = None
            if segment_ids is not None:  # a packed document starts from a zero state
                before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
                starts = valid & (segment_ids != before)
            with jax.named_scope("gdn_chunk"):
                abreast = 1 if rows is None else heads // rows[0].shape[1]
                state = (
                    jnp.zeros((batch, heads, dk, dv), jnp.float32) if rows is None
                    else unpack_heads(rows[0], abreast)
                )
                out, state = gated_delta_chunked(
                    q, k, v, g, beta, state, starts, cfg.delta_chunk_size
                )
                state = pack_heads(state, abreast)
        with jax.named_scope("gdn_out"):
            out_norm = self.out_norm or (
                lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)
            )
            out = out_norm("o_norm")(out)
            gate = _dense(cfg, heads * dv, ("embed", "heads"), "g_proj", False)(hidden)
            out = out.reshape(batch, seq, heads * dv) * self.out_gate(gate.astype(jnp.float32))
            out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", False)(
                out.astype(hidden.dtype)
            )
        return out, None if rows is None else (state, new_tail.astype(rows[1].dtype))


class FullAttention(nn.Module):
    """Multi-head softmax attention with no positional term and an RMSNorm
    over the whole q and k projections. Returns `(out, cache)`: with a
    `cache` (`models/cache.py`) k/v are appended to part `layer` of it (this
    layer's index among the stack's full layers) and attention runs against
    that part."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, hidden, segment_ids=None, cache=None, layer=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, kv_heads, dim = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.resolved_head_dim
        q = _dense(cfg, heads * dim, ("embed", "heads"), "q_proj", False)(hidden)
        k = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "k_proj", False)(hidden)
        v = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "v_proj", False)(hidden)
        q, k, v = _plain_rows(cache, (q, k, v))
        q = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="q_norm")(q)
        k = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="k_norm")(k)
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
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", False)(out), cache


class OlmoHybridDecoderLayer(nn.Module):
    """Returns (hidden, cache). `layer` is this layer's index among the
    stack's layers of its kind: a full layer's part of the cache's pool (or
    dense buffers), a linear layer's rows of its slab."""

    config: OlmoHybridConfig
    is_full: bool

    @nn.compact
    def __call__(self, hidden, segment_ids=None, cache=None, layer=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)
        rows = None
        if self.is_full:
            mixed, cache = FullAttention(cfg, name="self_attn")(hidden, segment_ids, cache, layer)
        else:
            if cache is not None:
                # `_slot_rows` by this module's name: where the benchmark's
                # tests plant their fault (`LayerCache.recurrent_rows`)
                # one token a slot: the state is updated where it lies
                one_token = hidden.shape[1] == 1
                rows = cache.recurrent_rows(
                    layer, _slot_rows, in_place=one_token, delta_step=one_token
                )
            mixed, rows = GatedDeltaNet(
                cfg, beta_max=2.0 if cfg.linear_allow_neg_eigval else 1.0, name="linear_attn"
            )(hidden, segment_ids, rows)
            if rows is not None:
                # the write belongs to the recurrence's scope: in a decode step
                # the state's update fuses INTO it, and a fusion lands in a
                # trace where its root does (`gdn_decode_roofline_pct` would
                # otherwise time two of the state's three passes and read 157%)
                with jax.named_scope("linear_attn/" + ("gdn_recurrence" if one_token else "gdn_chunk")):
                    cache = cache.put_recurrent_rows(layer, rows, in_place=one_token)
        hidden = hidden + norm("post_attention_layernorm")(mixed)
        mlp_out = LlamaMLP(cfg, name="mlp")(hidden)
        return hidden + norm("post_feedforward_layernorm")(mlp_out), cache


class _PeriodBody(nn.Module):
    """Scan body: the layers `first .. first + len(kinds)` of the pattern
    (one period). The carry is `hidden` or, when decoding, `(hidden, the
    cache's buffers)`: the pool with a leading axis over ALL the stack's full
    layers, the slab over all its linear layers, and `cycle` says which
    period of them this is (`models/cache.py:scan_layers`)."""

    config: OlmoHybridConfig
    kinds: tuple[bool, ...]

    @nn.compact
    def __call__(self, carry, segment_ids, cache=None, cycle=None):
        cfg = self.config
        hidden = carry
        if cache is not None:
            hidden, buffers = carry
            cache = cache.holding(buffers)
        for j, is_full in enumerate(self.kinds):
            # this layer's index among the stack's layers of its kind
            index = None if cache is None else (
                cycle * self.kinds.count(is_full) + self.kinds[:j].count(is_full)
            )
            hidden, cache = OlmoHybridDecoderLayer(cfg, is_full, name=f"slot{j}")(
                hidden, segment_ids, cache, index
            )
        return (hidden if cache is None else (hidden, cache.buffers)), None


class OlmoHybrid(nn.Module):
    """Olmo-Hybrid causal LM with the `CausalLMProto` surface, decoding
    through `decode_state` (dense or paged) like the Llama stack."""

    config: OlmoHybridConfig

    def _layers(self, hidden, segment_ids, cache):
        """-> (hidden, cache or None)."""
        cfg = self.config
        kinds = cfg.layer_kinds
        period = cfg.scan_period or cfg.num_hidden_layers
        body = _PeriodBody
        policy = _remat_policy(cfg)
        if policy is not None:
            body = nn.remat(_PeriodBody, policy=policy, prevent_cse=False)
        if cfg.scan_period:
            hidden, _, cache = scan_layers(
                body, (cfg, tuple(kinds[:period])), cfg.num_hidden_layers // period,
                hidden, (segment_ids,), cache,
            )
            return hidden, cache
        # the loop: the whole stack is one body, under the scan's names
        if cache is None:
            hidden, _ = body(cfg, tuple(kinds), name="layers")(hidden, segment_ids)
            return hidden, None
        (hidden, buffers), _ = body(cfg, tuple(kinds), name="layers")(
            (hidden, cache.buffers), segment_ids, cache, 0
        )
        return hidden, cache.holding(buffers)

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

        cache, segment_ids = open_cache(decode_state, segment_ids, *hidden.shape[:2])
        hidden, cache = self._layers(hidden, segment_ids, cache)
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

        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            decode_state=new_decode_state,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        if self.config.tie_word_embeddings:
            return "embed_tokens/embedding"
        return "lm_head/kernel"
