"""Gemma 1/2/3 decoder, TPU-native.

Graph differences vs Llama (all verified against HF
`modeling_gemma.py`/`modeling_gemma2.py`):
- RMSNorm multiplies by (1 + weight) with zero-initialized weight, and the
  product happens in fp32 BEFORE the downcast ((x̂ * w).to(dtype), not
  x̂.to(dtype) * w)
- embeddings are scaled by sqrt(hidden_size) (cast to the compute dtype
  first — the cast is numerics-visible in bf16 and HF does it this way)
- MLP is GeGLU: down(gelu_tanh(gate) * up)
- always-tied lm_head
Gemma-2 (version=2) additionally:
- sandwich norms: residual + post_norm(block(pre_norm(x))) for both attn
  and mlp
- attention soft-capping (the flash kernel's logits_soft_cap) and final
  logit soft-capping (applied in compute_logits AND by the fused CE)
- attention scale from query_pre_attn_scalar, not head_dim
- sliding window on even layer indices; under scan_layers the scanned body
  is a (sliding, full) layer PAIR so the alternation stays static
Gemma-3 text (version=3, verified against HF `modeling_gemma3.py`)
additionally:
- per-head zero-centered qk-norm (Gemma3RMSNorm over head_dim) before RoPE
- explicit layer_types sliding/full pattern (5:1), looped not scanned
- DUAL rotary tables: sliding layers rotate with rope_local_base_freq
  (unscaled), full layers with rope_theta + optional rope_scaling
- no soft-capping (the fields stay None)
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import CausalLMOutput, DecodeState
from llm_training_tpu.models.cache import close_cache, open_cache, scan_layers
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.models.gemma.config import GemmaConfig
from llm_training_tpu.ops import apply_rope, dot_product_attention
from llm_training_tpu.ops.rope_utils import compute_rope_cos_sin, compute_rope_frequencies


class GemmaRMSNorm(nn.Module):
    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        weight = self.param(
            "weight",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (normed * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def _dense(config: GemmaConfig, features: int, logical_axes: tuple[str, str], name: str) -> nn.Dense:
    return nn.Dense(
        features=features,
        use_bias=config.attention_bias,
        dtype=config.compute_jnp_dtype,
        param_dtype=config.param_jnp_dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(config.initializer_range), logical_axes
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (logical_axes[-1],)
        ),
        name=name,
    )


class GemmaAttention(nn.Module):
    """Returns `(out, cache)`: with a `cache` (`models/cache.py`) the k/v are
    appended to layer `layer`'s part of it and attention runs against that
    part; `cache` is None on the training path."""

    config: GemmaConfig
    sliding_window: int | None

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin, cache=None, layer=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        q = _dense(cfg, cfg.num_attention_heads * cfg.head_dim, ("embed", "heads"), "q_proj")(hidden)
        k = _dense(cfg, cfg.num_key_value_heads * cfg.head_dim, ("embed", "kv_heads"), "k_proj")(hidden)
        v = _dense(cfg, cfg.num_key_value_heads * cfg.head_dim, ("embed", "kv_heads"), "v_proj")(hidden)
        q = q.reshape(batch, seq, cfg.num_attention_heads, cfg.head_dim)
        k = k.reshape(batch, seq, cfg.num_key_value_heads, cfg.head_dim)
        v = v.reshape(batch, seq, cfg.num_key_value_heads, cfg.head_dim)
        if getattr(cfg, "use_qk_norm", False):
            # Gemma3: per-head zero-centered RMSNorm over head_dim, pre-RoPE
            q = GemmaRMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="q_norm")(q)
            k = GemmaRMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="k_norm")(k)
        q, k = apply_rope(q, k, cos, sin)
        out = None
        if cache is not None:
            out, cache = cache.attend(
                layer, q, k, v, segment_ids,
                window=self.sliding_window,
                scale=cfg.attention_scale,
                logits_soft_cap=cfg.attn_logit_softcapping,
            )
        elif getattr(cfg, "ring_attention", False):
            from llm_training_tpu.parallel.ring_attention import (
                dispatch_ring_attention,
            )

            out = dispatch_ring_attention(
                q, k, v, segment_ids,
                sliding_window=self.sliding_window,
                logits_soft_cap=cfg.attn_logit_softcapping,
                scale=cfg.attention_scale,
                impl=cfg.attention_impl,
            )
        if out is None:
            out = dot_product_attention(
                q, k, v,
                segment_ids=segment_ids,
                causal=True,
                sliding_window=self.sliding_window,
                logits_soft_cap=cfg.attn_logit_softcapping,
                scale=cfg.attention_scale,
                impl=cfg.attention_impl,
            )
        out = out.astype(hidden.dtype).reshape(batch, seq, cfg.num_attention_heads * cfg.head_dim)
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj")(out), cache


class GemmaMLP(nn.Module):
    config: GemmaConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = _dense(cfg, cfg.intermediate_size, ("embed", "mlp"), "gate_proj")(hidden)
        up = _dense(cfg, cfg.intermediate_size, ("embed", "mlp"), "up_proj")(hidden)
        return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj")(
            nn.gelu(gate, approximate=True) * up
        )


class GemmaDecoderLayer(nn.Module):
    """Returns `(hidden, cache)` (`GemmaAttention`)."""

    config: GemmaConfig
    sliding_window: int | None

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin, cache=None, layer=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: GemmaRMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)

        attn_in = norm("input_layernorm")(hidden)
        attn_out, cache = GemmaAttention(cfg, self.sliding_window, name="self_attn")(
            attn_in, segment_ids, cos, sin, cache, layer
        )
        if cfg.version in (2, 3):
            attn_out = norm("post_attention_layernorm")(attn_out)
            hidden = hidden + attn_out
            mlp_in = norm("pre_feedforward_layernorm")(hidden)
            mlp_out = norm("post_feedforward_layernorm")(GemmaMLP(cfg, name="mlp")(mlp_in))
            hidden = hidden + mlp_out
        else:
            hidden = hidden + attn_out
            mlp_in = norm("post_attention_layernorm")(hidden)
            hidden = hidden + GemmaMLP(cfg, name="mlp")(mlp_in)
        return hidden, cache


class _ScannedBody(nn.Module):
    """Scan body: one layer (gemma 1 / windowless gemma 2) or a
    (sliding, full) pair (gemma 2 with sliding_window). The carry is
    `hidden` or, when decoding, `(hidden, the cache's buffers)` with the
    layer's index as the scanned input (`models/cache.py:scan_layers`)."""

    config: GemmaConfig

    @nn.compact
    def __call__(self, carry, segment_ids, cos, sin, cache=None, layer=None):
        cfg = self.config
        if cfg.version == 2 and cfg.sliding_window:
            hidden, _ = GemmaDecoderLayer(cfg, cfg.sliding_window, name="sliding")(
                carry, segment_ids, cos, sin
            )
            hidden, _ = GemmaDecoderLayer(cfg, None, name="full")(
                hidden, segment_ids, cos, sin
            )
            return hidden, None
        block = GemmaDecoderLayer(cfg, None, name="layer")
        if cache is None:
            return block(carry, segment_ids, cos, sin)
        hidden, buffers = carry
        hidden, cache = block(hidden, segment_ids, cos, sin, cache.holding(buffers), layer)
        return (hidden, cache.buffers), None


class Gemma(nn.Module):
    """Gemma causal LM with the `CausalLMProto` surface."""

    config: GemmaConfig

    def _layers(self, hidden, segment_ids, cos, sin, cos_local, sin_local, cache=None):
        """-> (hidden, the cache as the layers left it, None when training)."""
        cfg = self.config
        policy = _remat_policy(cfg)
        paired = cfg.version == 2 and cfg.sliding_window
        if cfg.scan_layers:
            if cache is not None and paired:
                raise NotImplementedError(
                    "KV-cache decoding of gemma-2's paired (sliding, full) "
                    "scan body is not supported; its cache layer axis would "
                    "have to fold into [L/2, 2] pairs"
                )
            body = _ScannedBody
            if policy is not None:
                body = nn.remat(_ScannedBody, policy=policy, prevent_cse=False)
            length = cfg.num_hidden_layers // 2 if paired else cfg.num_hidden_layers
            hidden, _, cache = scan_layers(
                body, (cfg,), length, hidden, (segment_ids, cos, sin), cache
            )
            return hidden, cache
        for i in range(cfg.num_hidden_layers):
            layer_cls = GemmaDecoderLayer
            if policy is not None:
                layer_cls = nn.remat(GemmaDecoderLayer, policy=policy, static_argnums=())
            window = cfg.layer_sliding_window(i)
            # Gemma3 sliding layers rotate with the LOCAL tables
            lcos, lsin = (
                (cos_local, sin_local) if cfg.version == 3 and window else (cos, sin)
            )
            hidden, cache = layer_cls(cfg, window, name=f"layers_{i}")(
                hidden, segment_ids, lcos, lsin, cache, i
            )
        return hidden, cache

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,
        inputs_embeds: jnp.ndarray | None = None,
        compute_logits: bool = True,
        return_last_hidden_states: bool = False,
        decode_state: DecodeState | None = None,
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
        # sqrt(hidden) normalizer, cast before multiplying (HF numerics)
        normalizer = jnp.asarray(cfg.hidden_size**0.5, dtype=inputs_embeds.dtype)
        hidden = inputs_embeds * normalizer
        seq = hidden.shape[1]

        cache, segment_ids = open_cache(decode_state, segment_ids, hidden.shape[0], seq)

        if position_ids is None:
            position_ids = jnp.arange(seq)[None, :]
        rope_len = seq if decode_state is None else decode_state.table_length
        inv_freq, attention_scaling = compute_rope_frequencies(
            cfg.rope_config, seq_len=rope_len
        )
        cos, sin = compute_rope_cos_sin(inv_freq, position_ids, attention_scaling)
        cos_local = sin_local = None
        if cfg.version == 3:
            inv_freq_l, scaling_l = compute_rope_frequencies(
                cfg.local_rope_config, seq_len=rope_len
            )
            cos_local, sin_local = compute_rope_cos_sin(
                inv_freq_l, position_ids, scaling_l
            )

        hidden, cache = self._layers(
            hidden, segment_ids, cos, sin, cos_local, sin_local, cache
        )
        new_decode_state = close_cache(cache, decode_state, segment_ids)
        hidden = GemmaRMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        logits = None
        if compute_logits:
            logits = embed_tokens.attend(hidden)
            if cfg.final_logit_softcapping:
                cap = cfg.final_logit_softcapping
                logits = cap * jnp.tanh(logits / cap)
            logits = nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))

        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            decode_state=new_decode_state,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        return "embed_tokens/embedding"  # always tied
