"""Llama 2/3/3.x decoder, TPU-native.

Capability parity: reference `models/llama/llama_model.py` — GQA attention
(`:430-663`), RMSNorm blocks (`:271-286`), rotary embedding with all scaling
variants (`:289-412`), SwiGLU MLP (`:415-427`), tied embeddings (`:57-58`),
full/selective activation checkpointing (`:98-121,506-534`), and the TP/FSDP
sharding plans (`:197-268`) — re-designed as a single flax.linen module tree:

- the three attention impls (eager/SDPA/FA2) collapse into
  `ops.dot_product_attention` (XLA reference path or Pallas flash kernel);
  packed-document masks are segment ids, so no unpad/repad exists
- the DTensor TP plan + FSDP2 plan become logical-axis names on each kernel
  (`nn.with_logical_partitioning`), resolved by the rule table in
  `parallel/sharding.py`
- `recompute_granularity`: 'full' == remat everything per layer;
  'selective' == save matmul outputs, recompute the rest (the analogue of
  checkpointing only core attention)
- `scan_layers` compiles ONE decoder layer and `nn.scan`s it over depth —
  constant compile time in num_hidden_layers (no torch analogue)
"""

from __future__ import annotations

from functools import partial as _partial

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from llm_training_tpu.models.base import CausalLMOutput, DecodeState, RouterStats
from llm_training_tpu.models.cache import LayerCache, close_cache, open_cache, scan_layers
from llm_training_tpu.models.moe import EXPERT_LEAVES
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.models.llama.config import LlamaConfig
from llm_training_tpu.ops import apply_rope, dot_product_attention, rms_norm
from llm_training_tpu.ops.rope_utils import compute_rope_cos_sin, compute_rope_frequencies
from llm_training_tpu.ops.swiglu import silu_mul


class RMSNorm(nn.Module):
    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        weight = self.param(
            "weight",
            nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        return rms_norm(x, weight.astype(x.dtype), self.eps)


class LayerNorm(nn.Module):
    """Mean-centered LayerNorm with fp32 stats over the LAST dim.

    use_bias=True is the Starcoder2 block norm (HF param names weight/bias);
    use_bias=False is Cohere's weight-only CohereLayerNorm, whose weight may
    be multi-dim ([heads, head_dim] for the per-head qk-norm) spanning the
    trailing dims of x; zero_centered=True is Nemotron's LayerNorm1P
    (weight stored zero-centered, applied as 1 + w)."""

    eps: float
    param_dtype: jnp.dtype
    use_bias: bool = True
    zero_centered: bool = False
    # OLMo-1: F.layer_norm with NO weight and NO bias at all
    parametric: bool = True
    weight_shape: tuple[int, ...] | None = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x32 = x.astype(jnp.float32)
        mean = x32.mean(axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        normed = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        if not self.parametric:
            return normed.astype(x.dtype)
        shape = self.weight_shape or (x.shape[-1],)
        axes = (None,) * (len(shape) - 1) + ("norm",)
        weight = self.param(
            "weight",
            nn.with_logical_partitioning(
                nn.initializers.zeros_init() if self.zero_centered
                else nn.initializers.ones,
                axes,
            ),
            shape,
            self.param_dtype,
        )
        if self.zero_centered:
            weight = weight + jnp.ones_like(weight)
        out = normed * weight.astype(jnp.float32)
        if self.use_bias:
            bias = self.param(
                "bias",
                nn.with_logical_partitioning(nn.initializers.zeros_init(), axes),
                shape,
                self.param_dtype,
            )
            out = out + bias.astype(jnp.float32)
        return out.astype(x.dtype)


_NORM_CLASSES = {
    "rmsnorm": RMSNorm,
    "layernorm": LayerNorm,
    "layernorm_nobias": _partial(LayerNorm, use_bias=False),
    "layernorm1p": _partial(LayerNorm, zero_centered=True),
    # OLMo-1: fully non-parametric LayerNorm (no keys in the checkpoint)
    "layernorm_nonparam": _partial(LayerNorm, use_bias=False, parametric=False),
}


def _norm_cls(config):
    return _NORM_CLASSES[getattr(config, "norm_type", "rmsnorm")]


def _dense(config: LlamaConfig, features: int, logical_axes: tuple[str, str], name: str,
           use_bias: bool) -> nn.Dense:
    return nn.Dense(
        features=features,
        use_bias=use_bias,
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


def _plain_rows(cache: LayerCache | None, projected):
    """Projections `[..., rows, out]` on their way to a reshape by heads, as
    a layer that runs with a `cache` must hand them on: an array (or a tuple
    of them) the compiler takes as it is. Left free, it folds the reshape and
    the layout attention likes into the product, which then wants its weight
    as `[heads, head_dim, in]`; a scan's slice of the stacked leaf cannot give
    that, so every layer of every step cut the slice out and transposed it
    (a looped layer: transposed its parameter) before the product read it:
    two passes over the weight that compute nothing (docs/inference.md, "How
    a layer meets a stacked weight"). Behind the barrier the product is
    `[rows, out]` and reads its `[in, out]` slice where it lies, as `o_proj`
    and the MLP's do. Training (no cache) lowers to what it did."""
    return projected if cache is None else jax.lax.optimization_barrier(projected)


class LlamaAttention(nn.Module):
    """GQA attention (reference `llama_model.py:434-663`).

    `sliding_window_override` carries the per-layer window for layer_types
    models (set by the looped `_layers`; the scanned path never uses it —
    "unset" means fall back to config.sliding_window).

    q/k/v projections are colwise-parallel ('heads'/'kv_heads' → tensor axis),
    o_proj rowwise ('embed' output) — the reference TP plan
    (`llama_model.py:197-244`) via logical axes.

    Also serves Phi-3 (reference `phi3_model.py:436-480`): the config may
    carry `sliding_window` and `attention_compute_dtype` (Phi-3's SDPA
    upcast workaround, `phi3_model.py:172-187`).

    KV-cache decoding (docs/inference.md): with a `cache` (`models/cache.py`)
    the post-RoPE k/v are appended to layer `layer`'s part of it and
    attention runs against that part. Returns `(out, cache)`, the cache as
    this layer left it (None when none came in)."""

    config: LlamaConfig
    sliding_window_override: int | None | str = "unset"

    @nn.compact
    def __call__(
        self,
        hidden: jnp.ndarray,
        segment_ids: jnp.ndarray | None,
        cos: jnp.ndarray,
        sin: jnp.ndarray,
        cache: LayerCache | None = None,
        layer: jnp.ndarray | int | None = None,
    ) -> tuple[jnp.ndarray, LayerCache | None]:
        cfg = self.config
        head_dim = cfg.resolved_head_dim
        batch, seq, _ = hidden.shape

        q = _dense(cfg, cfg.num_attention_heads * head_dim, ("embed", "heads"),
                   "q_proj", cfg.attention_bias)(hidden)
        k = _dense(cfg, cfg.num_key_value_heads * head_dim, ("embed", "kv_heads"),
                   "k_proj", cfg.attention_bias)(hidden)
        v = _dense(cfg, cfg.num_key_value_heads * head_dim, ("embed", "kv_heads"),
                   "v_proj", cfg.attention_bias)(hidden)
        q, k, v = _plain_rows(cache, (q, k, v))

        if cfg.qk_norm and cfg.qk_norm_scope == "full":
            # OLMo-2/OLMoE: one RMSNorm over the whole projected width, before
            # the head reshape — different statistics than the per-head variant
            q = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="k_norm")(k)

        clip = getattr(cfg, "clip_qkv", None)
        if clip is not None:  # OLMo/OLMoE activation clamp, after qk-norm
            q = jnp.clip(q, -clip, clip)
            k = jnp.clip(k, -clip, clip)
            v = jnp.clip(v, -clip, clip)

        q = q.reshape(batch, seq, cfg.num_attention_heads, head_dim)
        k = k.reshape(batch, seq, cfg.num_key_value_heads, head_dim)
        v = v.reshape(batch, seq, cfg.num_key_value_heads, head_dim)

        def _head_qk_norm(q, k):
            if getattr(cfg, "norm_type", "rmsnorm") == "layernorm_nobias":
                # Cohere: per-HEAD weights [heads, head_dim], mean-centered
                q = LayerNorm(
                    cfg.rms_norm_eps, cfg.param_jnp_dtype, use_bias=False,
                    weight_shape=(cfg.num_attention_heads, head_dim), name="q_norm",
                )(q)
                k = LayerNorm(
                    cfg.rms_norm_eps, cfg.param_jnp_dtype, use_bias=False,
                    weight_shape=(cfg.num_key_value_heads, head_dim), name="k_norm",
                )(k)
            else:
                # Qwen3/HunYuan: per-head RMSNorm over head_dim, shared weight
                # (HF applies the q/k norms on the reshaped heads)
                q = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="q_norm")(q)
                k = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="k_norm")(k)
            return q, k

        head_norm = cfg.qk_norm and cfg.qk_norm_scope == "head"
        if head_norm and getattr(cfg, "qk_norm_position", "pre_rope") == "pre_rope":
            q, k = _head_qk_norm(q, k)

        rotary = getattr(cfg, "partial_rotary_factor", 1.0)
        if getattr(cfg, "position_embedding_type", "rope") == "learned":
            pass  # GPT-2: positions entered via wpe, no rotation
        elif rotary != 1.0:
            # Phi: rotate only the first int(factor * head_dim) dims of each
            # head; the remainder passes through unrotated
            rot = int(head_dim * rotary)
            q_rot, k_rot = apply_rope(
                q[..., :rot], k[..., :rot], cos, sin,
                interleaved=getattr(cfg, "rope_interleaved", False),
            )
            q = jnp.concatenate([q_rot, q[..., rot:]], axis=-1)
            k = jnp.concatenate([k_rot, k[..., rot:]], axis=-1)
        else:
            q, k = apply_rope(
                q, k, cos, sin, interleaved=getattr(cfg, "rope_interleaved", False)
            )

        if head_norm and getattr(cfg, "qk_norm_position", "pre_rope") == "post_rope":
            q, k = _head_qk_norm(q, k)  # HunYuan: norms AFTER rotary

        attention_dtype = getattr(cfg, "attention_compute_dtype", None)
        if attention_dtype is not None:
            from llm_training_tpu.models.base import resolve_dtype

            dtype = resolve_dtype(attention_dtype)
            q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)

        window = (
            getattr(cfg, "sliding_window", None)
            if self.sliding_window_override == "unset"
            else self.sliding_window_override
        )
        # Granite replaces 1/sqrt(head_dim) with a config scalar
        scale = getattr(cfg, "attention_multiplier", None)
        if cache is not None:
            out, cache = cache.attend(
                layer, q, k, v, segment_ids, window=window, scale=scale
            )
        else:
            out = self._attention(q, k, v, segment_ids, window, scale)
        out = out.astype(hidden.dtype)
        out = out.reshape(batch, seq, cfg.num_attention_heads * head_dim)
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", cfg.attention_out_bias)(out)
        return out, cache

    def _attention(self, q, k, v, segment_ids, window, scale):
        """Dispatch: ring attention over a sequence-sharded mesh when enabled,
        otherwise the single-device flash/XLA path (GSPMD handles any other
        sharding by inserting collectives itself)."""
        cfg = self.config
        if getattr(cfg, "ring_attention", False):
            from llm_training_tpu.parallel.ring_attention import (
                dispatch_ring_attention,
            )

            out = dispatch_ring_attention(
                q, k, v, segment_ids,
                sliding_window=window,
                scale=scale,
                impl=cfg.attention_impl,
            )
            if out is not None:
                return out
        return dot_product_attention(
            q, k, v,
            segment_ids=segment_ids,
            causal=True,
            sliding_window=window,
            scale=scale,
            impl=cfg.attention_impl,
        )


class LlamaMLP(nn.Module):
    """SwiGLU MLP (reference `llama_model.py:415-427`): gate/up colwise
    ('mlp' → tensor), down rowwise. mlp_type='gelu' is the Starcoder2
    non-gated variant (c_fc → gelu_tanh → c_proj, HF param names)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, hidden: jnp.ndarray) -> jnp.ndarray:
        cfg = self.config
        if getattr(cfg, "mlp_type", "swiglu") == "gelu":
            up = _dense(cfg, cfg.intermediate_size, ("embed", "mlp"), "c_fc", cfg.mlp_bias)(hidden)
            return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "c_proj", cfg.mlp_bias)(
                nn.gelu(up, approximate=getattr(cfg, "gelu_approximate", True))
            )
        if getattr(cfg, "mlp_type", "swiglu") == "relu2":
            up = _dense(cfg, cfg.intermediate_size, ("embed", "mlp"), "up_proj", cfg.mlp_bias)(hidden)
            return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj", cfg.mlp_bias)(
                jnp.square(nn.relu(up))
            )
        if getattr(cfg, "mlp_type", "swiglu") == "xielu":
            # Apertus xIELU (arXiv 2411.13010): a non-gated MLP whose
            # activation carries two LEARNABLE scalars. Parameters store the
            # softplus PRE-images (HF inits log(expm1(0.8)) and
            # log(expm1(0.8 - beta))); beta/eps are the HF constants.
            up = _dense(cfg, cfg.intermediate_size, ("embed", "mlp"), "up_proj", cfg.mlp_bias)(hidden)
            beta, eps = 0.5, -1e-6
            init_p = float(np.log(np.expm1(0.8)))
            init_n = float(np.log(np.expm1(0.8 - beta)))
            alpha_p = self.param(
                "xielu_alpha_p",
                nn.with_logical_partitioning(
                    nn.initializers.constant(init_p), (None,)
                ),
                (1,), cfg.param_jnp_dtype,
            )
            alpha_n = self.param(
                "xielu_alpha_n",
                nn.with_logical_partitioning(
                    nn.initializers.constant(init_n), (None,)
                ),
                (1,), cfg.param_jnp_dtype,
            )
            x = up.astype(jnp.float32)
            a_p = jax.nn.softplus(alpha_p.astype(jnp.float32))
            a_n = beta + jax.nn.softplus(alpha_n.astype(jnp.float32))
            act = jnp.where(
                x > 0,
                a_p * x * x + beta * x,
                (jnp.expm1(jnp.minimum(x, eps)) - x) * a_n + beta * x,
            ).astype(up.dtype)
            return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj", cfg.mlp_bias)(act)
        gate = _dense(cfg, cfg.intermediate_size, ("embed", "mlp"), "gate_proj", cfg.mlp_bias)(hidden)
        up = _dense(cfg, cfg.intermediate_size, ("embed", "mlp"), "up_proj", cfg.mlp_bias)(hidden)
        return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj", cfg.mlp_bias)(silu_mul(gate, up))


class LlamaDecoderLayer(nn.Module):
    """Pre-norm block (reference `llama_model.py:747-789`). Returns `(hidden,
    aux, cache)`: `cache` (`LlamaAttention`) is None without one, and the
    traced graph is then identical to before the cache existed. `stack`: the
    layer loop's stacked leaves that this layer must not have cut out for it
    (`models/cache.py:scan_layers`), under this block's module names."""

    config: LlamaConfig
    sliding_window_override: int | None | str = "unset"

    @nn.compact
    def __call__(
        self,
        hidden: jnp.ndarray,
        segment_ids: jnp.ndarray | None,
        cos: jnp.ndarray,
        sin: jnp.ndarray,
        cache: LayerCache | None = None,
        layer: jnp.ndarray | int | None = None,
        stack=None,
    ) -> tuple[jnp.ndarray, jnp.ndarray, LayerCache | None]:
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: _norm_cls(cfg)(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)

        def attention(x):
            return LlamaAttention(cfg, self.sliding_window_override, name="self_attn")(
                x, segment_ids, cos, sin, cache, layer
            )

        def mlp(x):
            """(out, aux): MoE block returns per-layer router stats
            (sel_frac [E], mean_prob [E], dropped scalar); dense SwiGLU a
            zero scalar (the ys type is uniform across layers within one
            model — a config is either all-MoE or all-dense)."""
            if cfg.num_experts:
                from llm_training_tpu.models.moe import MoEMLP, decoding_experts

                pad_mask = None if segment_ids is None else segment_ids > 0
                return MoEMLP(cfg, name="mlp")(
                    x, pad_mask, decoding_experts(cache, stack, layer, "mlp")
                )
            return LlamaMLP(cfg, name="mlp")(x), jnp.float32(0.0)

        # Granite scales every block output before the residual add;
        # rm == 1.0 (the default) folds away at trace time
        rm = getattr(cfg, "residual_multiplier", 1.0)
        join = (lambda x: x) if rm == 1.0 else (lambda x: x * jnp.asarray(rm, x.dtype))

        if cfg.norm_scheme == "parallel":
            # Cohere: ONE input norm feeds attention and mlp; both outputs
            # join the residual in a single add
            normed = norm("input_layernorm")(hidden)
            attn, cache = attention(normed)
            mlp_out, aux = mlp(normed)
            return hidden + join(attn) + join(mlp_out), aux, cache
        if cfg.norm_scheme == "parallel2":
            # GPT-NeoX: TWO norms over the SAME block input feed attention
            # and mlp in parallel; one residual join
            attn, cache = attention(norm("input_layernorm")(hidden))
            mlp_out, aux = mlp(norm("post_attention_layernorm")(hidden))
            return hidden + join(attn) + join(mlp_out), aux, cache
        if cfg.norm_scheme == "sandwich":
            # GLM-4: pre-norm AND output-norm around both blocks
            attn, cache = attention(norm("input_layernorm")(hidden))
            hidden = hidden + join(norm("post_self_attn_layernorm")(attn))
            normed = norm("post_attention_layernorm")(hidden)
            mlp_out, aux = mlp(normed)
            return hidden + join(norm("post_mlp_layernorm")(mlp_out)), aux, cache
        if cfg.norm_scheme == "post":
            # OLMo-2 reordering: no input norms; normalize each block's
            # OUTPUT before it joins the residual stream
            attn, cache = attention(hidden)
            hidden = hidden + join(norm("post_attention_layernorm")(attn))
            mlp_out, aux = mlp(hidden)
            return hidden + join(norm("post_feedforward_layernorm")(mlp_out)), aux, cache
        attn, cache = attention(norm("input_layernorm")(hidden))
        hidden = hidden + join(attn)
        normed = norm("post_attention_layernorm")(hidden)
        mlp_out, aux = mlp(normed)
        return hidden + join(mlp_out), aux, cache


class _ScannedLayer(nn.Module):
    """Adapter giving LlamaDecoderLayer the (carry, xs) -> (carry, ys)
    signature nn.scan expects; ys carries the per-layer MoE aux loss. The
    carry is `hidden` or, when decoding, `(hidden, the cache's buffers)`
    with the layer's index `layer` as the scanned input and, for a sparse
    MLP, the experts' stacked leaves `stack` closed over
    (`models/cache.py:scan_layers`)."""

    config: LlamaConfig
    layer_cls: type

    @nn.compact
    def __call__(self, carry, segment_ids, cos, sin, cache=None, layer=None, stack=None):
        block = self.layer_cls(self.config, name="layer")
        if cache is None:
            hidden, aux, _ = block(carry, segment_ids, cos, sin)
            return hidden, aux
        hidden, buffers = carry
        hidden, aux, cache = block(
            hidden, segment_ids, cos, sin, cache.holding(buffers), layer,
            None if stack is None else stack["layer"],
        )
        return (hidden, cache.buffers), aux


class Llama(nn.Module):
    """Llama causal LM.

    __call__(input_ids, segment_ids, position_ids, inputs_embeds,
             compute_logits, return_last_hidden_states) -> CausalLMOutput
    mirrors the reference's `CausalLMProto` surface (`lms/protos/clm_proto.py`).
    """

    config: LlamaConfig

    def _layers(self, hidden, segment_ids, cos, sin, local_cos=None, local_sin=None,
                cache=None):
        """Returns (hidden, aux_loss, ep_dropped_rows, layer_stats, cache).
        For MoE configs the per-layer router stats (sel_frac, mean_prob,
        dropped) are pooled across depth BEFORE the E * sum(f * P) product —
        matching HF `load_balancing_loss_func`, which concatenates all
        layers' gate logits first, so the loss stays ~top_k when balanced
        regardless of num_hidden_layers. `layer_stats` is the PRE-pooled
        (sel_frac [L, E], mean_prob [L, E]) pair for the health layer
        (None for dense configs).

        `cache` (`models/cache.py`) rides the layer loop: its buffers as the
        carry beside `hidden` under scan_layers, a Python variable on the
        looped path. It comes back as the layers left it (None on the
        training path)."""
        cfg = self.config
        policy = _remat_policy(cfg)
        if getattr(cfg, "pipeline_stages", 1) > 1:
            from llm_training_tpu.models.pipeline import PipelinedLayers

            if cache is not None:
                raise NotImplementedError(
                    "KV-cache decoding does not compose with "
                    "pipeline_stages > 1; restore the checkpoint with "
                    "pipeline_stages=1 for inference"
                )
            layer_cls = _ScannedLayer
            if policy is not None:
                layer_cls = nn.remat(
                    _ScannedLayer, policy=policy, prevent_cse=False,
                )
            # aux comes back pre-pooled to the scan layout ([L, ...], real
            # microbatches only) so the MoE tail below applies unchanged
            hidden, aux = PipelinedLayers(
                cfg, layer_cls, LlamaDecoderLayer, name="pipeline"
            )(hidden, segment_ids, cos, sin)
        elif cfg.scan_layers:
            layer_cls = _ScannedLayer
            if policy is not None:
                layer_cls = nn.remat(
                    _ScannedLayer, policy=policy, prevent_cse=False,
                )
            hidden, aux, cache = scan_layers(
                layer_cls, (cfg, LlamaDecoderLayer), cfg.num_hidden_layers,
                hidden, (segment_ids, cos, sin), cache,
                whole=EXPERT_LEAVES if cfg.num_experts else (),
            )
        else:
            no_rope = getattr(cfg, "no_rope_layers", None)
            if no_rope is not None and cos is not None:
                # NoPE layers rotate with identity tables — zero layer-body
                # variation, so conversion/remat stay uniform
                id_cos = jnp.ones_like(cos)
                id_sin = jnp.zeros_like(sin)
            layer_types = getattr(cfg, "layer_types", None)
            stats = []
            for i in range(cfg.num_hidden_layers):
                layer_cls = LlamaDecoderLayer
                if policy is not None:
                    layer_cls = nn.remat(LlamaDecoderLayer, policy=policy)
                use_rope = no_rope is None or bool(no_rope[i])
                window = (
                    cfg.layer_sliding_window(i) if layer_types is not None
                    else "unset"
                )
                lcos, lsin = cos, sin
                if not use_rope:
                    lcos, lsin = id_cos, id_sin
                elif layer_types is not None and window and local_cos is not None:
                    # OLMo-3: sliding layers rotate with the UNSCALED tables
                    lcos, lsin = local_cos, local_sin
                hidden, layer_ys, cache = layer_cls(cfg, window, name=f"layers_{i}")(
                    hidden, segment_ids, lcos, lsin, cache, i
                )
                stats.append(layer_ys)
            aux = jax.tree.map(lambda *xs: jnp.stack(xs), *stats)
        if not cfg.num_experts:
            return hidden, jnp.float32(0.0), jnp.float32(0.0), None, cache
        sel_frac, mean_prob, dropped = aux  # [L, E], [L, E], [L]
        aux_loss = cfg.num_experts * jnp.sum(
            sel_frac.mean(axis=0) * mean_prob.mean(axis=0)
        )
        return hidden, aux_loss, dropped.sum(), (sel_frac, mean_prob), cache

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
        hidden = inputs_embeds
        em = getattr(cfg, "embedding_multiplier", 1.0)
        if em != 1.0:  # Granite scales the embeddings into the residual stream
            hidden = hidden * jnp.asarray(em, hidden.dtype)
        seq = hidden.shape[1]

        cache, segment_ids = open_cache(decode_state, segment_ids, hidden.shape[0], seq)

        if position_ids is None:
            position_ids = jnp.arange(seq)[None, :]
        learned = getattr(cfg, "position_embedding_type", "rope") == "learned"
        if learned:
            if seq > cfg.max_position_embeddings:
                raise ValueError(
                    f"sequence length {seq} exceeds the learned position "
                    f"table ({cfg.max_position_embeddings}); jnp.take would "
                    "silently clamp out-of-range positions"
                )
            # GPT-2: learned absolute positions into the residual stream
            wpe = nn.Embed(
                num_embeddings=cfg.max_position_embeddings,
                features=cfg.hidden_size,
                dtype=cfg.compute_jnp_dtype,
                param_dtype=cfg.param_jnp_dtype,
                embedding_init=nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.initializer_range), (None, "embed")
                ),
                name="wpe",
            )
            hidden = hidden + wpe(position_ids)
        # host-side rotary tables (static config -> numpy); seq is static at
        # trace time, so seq-dependent variants (dynamic NTK, longrope
        # short/long factor selection — HF Phi3RotaryEmbedding semantics)
        # resolve per compiled shape. Learned-position models carry no
        # rotation at all. Under a KV cache the chunk is 1 token wide but
        # positions span the generation, so the table-selection length is
        # the cache's (static) planned length, not the chunk width.
        rope_len = seq if decode_state is None else decode_state.table_length
        if learned:
            cos = sin = None
        else:
            inv_freq, attention_scaling = compute_rope_frequencies(
                cfg.rope_config, seq_len=rope_len
            )
            cos, sin = compute_rope_cos_sin(inv_freq, position_ids, attention_scaling)
        if cos is not None and getattr(cfg, "rope_interleaved", False):
            # repeat_interleave(freqs, 2) layout instead of concat halves
            half = cos.shape[-1] // 2
            cos = jnp.repeat(cos[..., :half], 2, axis=-1)
            sin = jnp.repeat(sin[..., :half], 2, axis=-1)

        local_cos = local_sin = None
        if (
            getattr(cfg, "layer_types", None) is not None
            and cfg.rope_scaling
            and getattr(cfg, "dual_local_rope", False)
        ):
            # sliding layers use the UNSCALED default tables (OLMo-3;
            # Ministral's layer_types pattern keeps ONE table everywhere)
            inv_freq_l, scaling_l = compute_rope_frequencies(
                cfg.local_rope_config, seq_len=rope_len
            )
            local_cos, local_sin = compute_rope_cos_sin(
                inv_freq_l, position_ids, scaling_l
            )
            if getattr(cfg, "rope_interleaved", False):
                half = local_cos.shape[-1] // 2
                local_cos = jnp.repeat(local_cos[..., :half], 2, axis=-1)
                local_sin = jnp.repeat(local_sin[..., :half], 2, axis=-1)
        hidden, aux_loss, ep_dropped, layer_stats, cache = self._layers(
            hidden, segment_ids, cos, sin, local_cos, local_sin, cache
        )
        new_decode_state = close_cache(cache, decode_state, segment_ids)
        hidden = _norm_cls(cfg)(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")(hidden)
        mult = getattr(cfg, "logit_scale", None)
        if mult is not None:
            # Cohere multiplies the logits by logit_scale; folded into the
            # hidden states for the same fused-CE reason as logits_scaling
            hidden = hidden * jnp.asarray(mult, hidden.dtype)
        ls = getattr(cfg, "logits_scaling", 1.0)
        if ls != 1.0:
            # Granite divides the logits by logits_scaling; folding the
            # division into the final hidden states makes the fused-CE path
            # (which consumes last_hidden_states + the head weights, see
            # lms/clm.py) see exactly logits/ls too
            hidden = hidden / jnp.asarray(ls, hidden.dtype)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        logits = None
        if compute_logits:
            if cfg.tie_word_embeddings:
                logits = embed_tokens.attend(hidden)
            else:
                logits = _dense(
                    cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head",
                    getattr(cfg, "lm_head_bias", False),
                )(hidden)
            logits = nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))

        router_stats = None
        if cfg.num_experts and layer_stats is not None:
            router_stats = RouterStats(
                sel_frac=layer_stats[0],
                mean_prob=layer_stats[1],
                dropped=ep_dropped,
                layer_ids=tuple(range(cfg.num_hidden_layers)),
            )
        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            # unscaled load-balancing loss; the objective applies
            # router_aux_loss_coef (None for dense models)
            aux_loss=aux_loss if cfg.num_experts else None,
            ep_dropped_rows=ep_dropped if cfg.num_experts else None,
            router_stats=router_stats,
            decode_state=new_decode_state,
        )

    def get_input_embeddings_path(self) -> str:
        """Param-tree path of the embedding table (NEFTune hook point,
        reference `clm.py:45-82`)."""
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str | None:
        if self.config.tie_word_embeddings:
            return "embed_tokens/embedding"
        return "lm_head/kernel"
