"""DeepSeek V2/V3 decoder, TPU-native; openPangu-Ultra-MoE (`model_type:
pangu_ultra_moe`) is the V3 graph without expert groups plus `sandwich_norm`
and a multi-token-prediction module.

Graph verified against HF `modeling_deepseek_v2.py` / `modeling_deepseek_v3.py`:

- MLA (multi-head latent attention, `MLAttention`, which `LongcatFlash`
  shares): q via optional LoRA factorization (q_a_proj -> RMSNorm ->
  q_b_proj), kv via a shared compressed latent (kv_a_proj_with_mqa -> split
  latent + rope part -> RMSNorm -> kv_b_proj). Per head, q/k are [nope |
  rope] concatenations; the rope part of k is MQA-style (one head,
  broadcast). Rotation uses the interleaved (complex-pair) layout the HF
  checkpoints store (`rope_interleave`).
- attention scale 1/sqrt(qk_head_dim) with DeepSeek-yarn's squared-mscale
  correction (config.attention_scale).
- MoE: fp32 router (sigmoid + e_score_correction_bias + top-2-sum group
  selection for v3; softmax + greedy / group-limited max for v2), dropless
  `lax.ragged_dot` grouped matmuls over ONE stacked parameter per
  projection, always-on shared experts, routed_scaling_factor. No aux loss:
  v3 balances via the noaux bias; the HF v2 port computes none either. With
  `experts_held` the block is an expert-parallel SHARE (`DeepseekMoE`).
- dense prefix: layers [0, first_k_dense_replace) use the full-width MLP and
  are looped; the uniform MoE suffix scans (`nn.scan`) so compile time stays
  ~flat in depth.
- `sandwich_norm` (pangu_ultra_moe): `h = x + N(MLA(N x))`, `y = h + N(MLP(N
  h))`, four norms a layer.
- `num_nextn_predict_layers` (`MTPModule`): `h'_i = W_eh [N(Emb(t_{i+1})) ;
  N(h_i)]`, one more decoder layer, the stack's own final norm and head: a
  second training loss (`lms/clm.py`), run only by a call that asks for it.

Decoding (docs/inference.md, docs/serving.md): a token leaves ONE row in the
cache for each layer's MLA block, `[c_kv | rotated k_r]`, declared by
`DeepseekConfig.cache_specs()`; the block appends it and attends through
`LayerCache.attend_latent`: absorbed against the paged latent pool for one
token a row, expanded through `W_kvb` for a chunk. The looped prefix threads
the cache as a Python variable; the scanned suffix carries the latent buffer
of ALL the stack's blocks as its carry and reads the held experts' stacked
weights where they lie (`models/cache.py:scan_layers`, `whole=`).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import (
    CausalLMOutput,
    DecodeState,
    PagedDecodeState,
    RouterStats,
)
from llm_training_tpu.models.cache import close_cache, open_cache, scan_layers
from llm_training_tpu.models.deepseek.config import DeepseekConfig
from llm_training_tpu.models.llama.model import RMSNorm, _dense, _plain_rows
from llm_training_tpu.models.moe import (
    EXPERT_LEAVES,
    assignment_counts,
    decoding_experts,
    dropless_moe_apply,
    experts_in_place,
    grouped_matmul,
    router_block_stats,
)
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.ops import apply_rope, dot_product_attention
from llm_training_tpu.ops.rope_utils import compute_rope_cos_sin, compute_rope_frequencies
from llm_training_tpu.ops.swiglu import silu_mul


class _UpProjection(nn.Module):
    """`kv_b_proj` as the HuggingFace checkpoints keep it, a `kernel` of
    `[latent, heads * (nope + v)]`, handed out a head: `[latent, heads, nope +
    v]` in the compute dtype. The module multiplies nothing: the expanded
    route and the absorbed one (`ops/latent_attention.py`) each take the
    parts of it they need."""

    config: Any
    heads: int
    width: int

    @nn.compact
    def __call__(self):
        cfg = self.config
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), (None, "heads")
            ),
            (cfg.kv_lora_rank, self.heads * self.width),
            cfg.param_jnp_dtype,
        )
        return kernel.astype(cfg.compute_jnp_dtype).reshape(
            cfg.kv_lora_rank, self.heads, self.width
        )


def _output_gate(projected):
    """A gated MLA block's gate from its projection. (By this name: where the
    benchmark's tests plant their fault, a gate forced to 1.)"""
    return jax.nn.sigmoid(projected.astype(jnp.float32))


class MLAttention(nn.Module):
    """Multi-head latent attention, for every family that has it (`Deepseek`
    V2 / V3 / pangu_ultra_moe, `LongcatFlash`). Returns `(out, cache)`: with
    a `cache` (`models/cache.py`) the token's latent row `[c_kv | rotated
    k_r]` is appended to part `block` of it (this block's index among the
    stack's MLA blocks) and attention runs against that part, absorbed for
    one token a row and expanded for a chunk; without one, the chunk's own
    latents are expanded and attended with the training kernels.

    `config` gives the widths (`num_attention_heads`, `q_lora_rank` (None: a
    full-rank `q_proj`), `kv_lora_rank`, `qk_nope_head_dim`,
    `qk_rope_head_dim`, `v_head_dim`, `attention_bias`). `q_scale`, `kv_scale`:
    LongCat's two low-rank scale factors, on the up-projected query and on
    the normalised latent. `scale`: the softmax scale (None: `1 /
    sqrt(qk_head_dim)`). `interleaved`: rotary pairs (2i, 2i+1), as the
    checkpoints store them. `kv_b_stacked`: `kv_b_proj` is one parameter
    `[latent, heads, nope + v]` (LongCat's tree) and not a `kernel` in the
    HuggingFace layout. `gated`: an output gate a head's value channel,
    `o_proj(attn * sigmoid(gate_proj x))`, from the block's input (scope
    `attn_gate`)."""

    config: Any
    q_scale: float = 1.0
    kv_scale: float = 1.0
    scale: float | None = None
    interleaved: bool = True
    kv_b_stacked: bool = False
    gated: bool = False

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin, cache=None, block=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        latent, v_dim = cfg.kv_lora_rank, cfg.v_head_dim
        bias = cfg.attention_bias
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)

        with jax.named_scope("mla_q"):
            if cfg.q_lora_rank is None:
                q = _dense(cfg, heads * (nope + rope), ("embed", "heads"), "q_proj", False)(hidden)
            else:
                c_q = norm("q_a_layernorm")(
                    _dense(cfg, cfg.q_lora_rank, ("embed", None), "q_a_proj", bias)(hidden)
                )
                q = _dense(cfg, heads * (nope + rope), (None, "heads"), "q_b_proj", False)(c_q)
            q = _plain_rows(cache, q)
            if self.q_scale != 1.0:
                q = q * jnp.asarray(self.q_scale, q.dtype)
            q = q.reshape(batch, seq, heads, nope + rope)
            q_nope, q_rope = q[..., :nope], q[..., nope:]
        with jax.named_scope("mla_kv"):
            compressed = _dense(
                cfg, latent + rope, ("embed", None), "kv_a_proj_with_mqa", bias
            )(hidden)
            c_kv = norm("kv_a_layernorm")(compressed[..., :latent])
            if self.kv_scale != 1.0:
                c_kv = c_kv * jnp.asarray(self.kv_scale, c_kv.dtype)
            # one rotated key a token, shared by the heads; it is not scaled
            q_rope, k_rope = apply_rope(
                q_rope, compressed[..., None, latent:], cos, sin, interleaved=self.interleaved
            )
            if self.kv_b_stacked:
                w_kvb = self.param(
                    "kv_b_proj",
                    nn.with_logical_partitioning(
                        nn.initializers.normal(cfg.initializer_range), (None, "heads", None)
                    ),
                    (latent, heads, nope + v_dim),
                    cfg.param_jnp_dtype,
                ).astype(cfg.compute_jnp_dtype)
            else:
                w_kvb = _UpProjection(cfg, heads, nope + v_dim, name="kv_b_proj")()

        scale = (nope + rope) ** -0.5 if self.scale is None else self.scale
        if cache is not None:
            row = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
            out, cache = cache.attend_latent(
                block, q_nope, q_rope, row, w_kvb, segment_ids, scale=scale
            )
        else:
            with jax.named_scope("mla_expand"):
                kv = jnp.einsum(
                    "bsl,lhe->bshe", c_kv, w_kvb, preferred_element_type=jnp.float32
                ).astype(c_kv.dtype)
            with jax.named_scope("mla_attend"):
                k = jnp.concatenate(
                    [kv[..., :nope], jnp.broadcast_to(k_rope, (batch, seq, heads, rope))], axis=-1
                )
                # the kernels want one head size: v zero-padded to the keys'
                # (the padded columns get no weight and are sliced off after)
                v = jnp.pad(kv[..., nope:], ((0, 0),) * 3 + ((0, nope + rope - v_dim),))
                out = dot_product_attention(
                    jnp.concatenate([q_nope, q_rope], axis=-1), k, v,
                    segment_ids=segment_ids, causal=True, scale=scale, impl=cfg.attention_impl,
                )[..., :v_dim]
        with jax.named_scope("mla_out"):
            out = out.astype(hidden.dtype).reshape(batch, seq, heads * v_dim)
        if self.gated:
            with jax.named_scope("attn_gate"):
                gate = _dense(cfg, heads * v_dim, ("embed", "heads"), "gate_proj", False)(hidden)
                out = out * _output_gate(gate).astype(out.dtype)
        with jax.named_scope("mla_out"):
            return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", bias)(out), cache


class DeepseekMLP(nn.Module):
    """SwiGLU MLP (HF DeepseekV2/V3MLP) with a configurable width; clamped
    where the config has a `swiglu_limit` (`ops/swiglu.py`)."""

    config: DeepseekConfig
    intermediate_size: int

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = _dense(cfg, self.intermediate_size, ("embed", "mlp"), "gate_proj", False)(hidden)
        up = _dense(cfg, self.intermediate_size, ("embed", "mlp"), "up_proj", False)(hidden)
        return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj", False)(
            silu_mul(gate, up, getattr(cfg, "swiglu_limit", None))
        )


class DeepseekMoE(nn.Module):
    """Router + dropless grouped experts + always-on shared experts.

    Returns (out, (sel_frac [E], mean_prob [E], dropped scalar)) — the
    router health triple (`models.moe.router_block_stats` semantics;
    `pad_mask` excludes padding tokens like MoEMLP).

    A config with `experts_held` (and `experts_first`) makes this an
    expert-parallel SHARE: the router keeps its `n_routed_experts` outputs
    and its top-k, the stacked expert parameters hold `experts_held` experts,
    and the output is their part of the routed sum plus the shared experts
    (`models.moe.dropless_moe_apply(held=...)`); no code stands in for the
    chips that hold the others. `stack = (leaves, layer)` from a decoding
    layer (`models/moe.py:decoding_experts`): the experts' `EXPERT_LEAVES`
    whole, `[L, E, ...]`, read in place, or a looped layer's own as a stack
    of one (`experts_in_place`); None, and the grouped products are
    `jax.lax.ragged_dot` on this layer's own matrices."""

    config: DeepseekConfig
    # a third result, `counts [3]` int32: this call's assignments to experts
    # held here, to zero-compute experts (this router has none) and to
    # experts held elsewhere, padding left out (`CausalLMOutput.
    # moe_assignments`)
    count_assignments: bool = False

    @nn.compact
    def __call__(self, hidden, pad_mask=None, stack=None):
        cfg = self.config
        num_experts = cfg.n_routed_experts
        held = getattr(cfg, "experts_held", None)
        num_held = num_experts if held is None else held
        top_k = cfg.num_experts_per_tok
        inter = cfg.moe_intermediate_size
        compute_dtype = cfg.compute_jnp_dtype
        param_dtype = cfg.param_jnp_dtype
        batch, seq, embed = hidden.shape
        x = hidden.reshape(-1, embed)
        n_tokens = x.shape[0]

        # ---- router (fp32; HF computes scores in float32)
        gate_kernel = self.param(
            "gate_kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), ("embed", "expert")
            ),
            (embed, num_experts),
            param_dtype,
        )
        with jax.named_scope("moe_route"):
            logits = x.astype(jnp.float32) @ gate_kernel.astype(jnp.float32)
            if cfg.version == 3:
                scores = jax.nn.sigmoid(logits)
                bias = self.param(
                    "e_score_correction_bias",
                    nn.with_logical_partitioning(nn.initializers.zeros_init(), ("expert",)),
                    (num_experts,),
                    jnp.float32,
                )
                # selection sees scores+bias; combine weights use raw scores (the
                # noaux balancing trick) — no gradient reaches the bias (top_k
                # indices are non-differentiable), matching its HF buffer role
                choice = scores + jax.lax.stop_gradient(bias)
            else:
                scores = jax.nn.softmax(logits, axis=-1)
                choice = scores

            group_limited = cfg.n_group and (
                cfg.version == 3 or cfg.topk_method == "group_limited_greedy"
            )
            if group_limited:
                groups = cfg.n_group
                per_group = choice.reshape(n_tokens, groups, num_experts // groups)
                if cfg.version == 3:
                    # group score = sum of its top-2 member scores
                    group_scores = jax.lax.top_k(per_group, 2)[0].sum(axis=-1)
                else:
                    group_scores = per_group.max(axis=-1)
                _, group_idx = jax.lax.top_k(group_scores, cfg.topk_group)
                group_mask = jax.nn.one_hot(group_idx, groups, dtype=jnp.float32).sum(axis=1)
                mask = jnp.repeat(group_mask, num_experts // groups, axis=-1)
                choice = jnp.where(mask > 0, choice, 0.0)

            _, topk_idx = jax.lax.top_k(choice, top_k)  # [T, K]
            topk_weights = jnp.take_along_axis(scores, topk_idx, axis=1)
            if cfg.version == 3 and cfg.norm_topk_prob:
                topk_weights = topk_weights / (
                    topk_weights.sum(axis=-1, keepdims=True) + 1e-20
                )
            topk_weights = (topk_weights * cfg.routed_scaling_factor).astype(compute_dtype)

        # ---- stacked expert weights
        def expert_param(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.initializer_range), axes
                ),
                shape,
                param_dtype,
            ).astype(compute_dtype)

        w_gate = expert_param(
            "experts_gate_proj", (num_held, embed, inter), ("expert", "embed", "mlp")
        )
        w_up = expert_param(
            "experts_up_proj", (num_held, embed, inter), ("expert", "embed", "mlp")
        )
        w_down = expert_param(
            "experts_down_proj", (num_held, inter, embed), ("expert", "mlp", "embed")
        )

        limit = getattr(cfg, "swiglu_limit", None)

        def dense_fn(xc):
            gate = jnp.einsum("th,ehi->tei", xc, w_gate)
            up = jnp.einsum("th,ehi->tei", xc, w_up)
            return jnp.einsum("tei,eih->teh", silu_mul(gate, up, limit), w_down)

        weights, layer = experts_in_place(
            stack, (w_gate, w_up, w_down), cfg.moe_impl, compute_dtype, self.path
        )

        def ragged_fn(xs, group_sizes, expert_order, w):
            wg, wu, wd = w
            gate = grouped_matmul(xs, wg, group_sizes, layer)
            up = grouped_matmul(xs, wu, group_sizes, layer)
            return grouped_matmul(silu_mul(gate, up, limit), wd, group_sizes, layer)

        out, dropped = dropless_moe_apply(
            x.astype(compute_dtype), topk_idx, topk_weights, num_experts,
            cfg.moe_impl, dense_fn, ragged_fn,
            weights=weights,
            ep_capacity_factor=getattr(cfg, "ep_capacity_factor", 2.0),
            held=None if held is None else (cfg.experts_first, held),
        )
        out = out.reshape(batch, seq, embed).astype(hidden.dtype)
        with jax.named_scope("moe_shared"):
            shared = DeepseekMLP(
                cfg, cfg.moe_intermediate_size * cfg.n_shared_experts,
                name="shared_experts",
            )(hidden)
        # router health stats (telemetry/health.py) — sigmoid scores (v3)
        # normalize per token first so the entropy stays a distribution
        # statistic. DCE'd when unused.
        if cfg.version == 3:
            norm_scores = scores / jnp.maximum(
                scores.sum(axis=-1, keepdims=True), 1e-9
            )
        else:
            norm_scores = scores
        sel_frac, mean_prob = router_block_stats(
            topk_idx, norm_scores, num_experts, pad_mask
        )
        stats = (sel_frac, mean_prob, dropped)
        if not self.count_assignments:
            return out + shared, stats
        counts = assignment_counts(topk_idx, getattr(cfg, "experts_first", 0), num_held, pad_mask)
        return out + shared, stats, counts


class DeepseekDecoderLayer(nn.Module):
    """One block (HF DeepseekV2/V3DecoderLayer; with `sandwich_norm`,
    pangu_ultra_moe's): pre-norm, `h = x + MLA(N x)`, `y = h + MLP(N h)`, or
    sandwiched, `h = x + N(MLA(N x))`, `y = h + N(MLP(N h))`. Returns
    `(hidden, ys, cache)`. DeepSeek computes no aux loss (the noaux bias
    balances instead), so `ys` carries, on a MoE layer, `(the router health
    triple (sel_frac [E], mean_prob [E], dropped scalar), the assignment
    counts [3] of a share or None)`, and None on a dense layer (`is_moe` is
    static, so the structures are trace-time constants). `layer` is this
    layer's index in the stack, its MLA block's part of the cache; `stack =
    (leaves, index)` the scanned suffix's expert leaves whole, for a
    decoding layer of it (a looped one hands its block its own)."""

    config: DeepseekConfig
    is_moe: bool

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin, cache=None, layer=None, stack=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)

        attn, cache = MLAttention(
            cfg, scale=cfg.attention_scale, interleaved=cfg.rope_interleave, name="self_attn"
        )(norm("input_layernorm")(hidden), segment_ids, cos, sin, cache, layer)
        if cfg.sandwich_norm:
            hidden = hidden + norm("post_attention_layernorm")(attn)
            normed = norm("pre_mlp_layernorm")(hidden)
        else:
            hidden = hidden + attn
            normed = norm("post_attention_layernorm")(hidden)
        ys = None
        if self.is_moe:
            pad_mask = None if segment_ids is None else segment_ids > 0
            counts = None
            # a looped decoding layer: its own experts, a stack of one
            experts = decoding_experts(cache, None, 0) if stack is None else stack
            if cfg.counts_expert_assignments:
                mlp_out, stats, counts = DeepseekMoE(cfg, True, name="mlp")(normed, pad_mask, experts)
            else:
                mlp_out, stats = DeepseekMoE(cfg, name="mlp")(normed, pad_mask, experts)
            ys = (stats, counts)
        else:
            mlp_out = DeepseekMLP(cfg, cfg.intermediate_size, name="mlp")(normed)
        if cfg.sandwich_norm:
            mlp_out = norm("post_mlp_layernorm")(mlp_out)
        return hidden + mlp_out, ys, cache


class _MoEScanBody(nn.Module):
    """Scan body: one MoE layer. The dense prefix is non-uniform with the
    suffix, so it is looped; everything from `first_k_dense_replace` on is
    the SAME graph and scans — compile time stays ~flat in depth (DeepSeek-V3
    is 61 layers; a looped stack would compile 58 copies of this body).
    `(carry, xs) -> (carry, ys)` for `nn.scan`; the carry is `hidden` or,
    when decoding, `(hidden, the cache's buffers)`, and `layer` then counts
    the suffix's layers (`models/cache.py:scan_layers`)."""

    config: DeepseekConfig

    @nn.compact
    def __call__(self, carry, segment_ids, cos, sin, cache=None, layer=None, stack=None):
        cfg = self.config
        block = DeepseekDecoderLayer(cfg, True, name="layer")
        if cache is None:
            hidden, ys, _ = block(carry, segment_ids, cos, sin)
            return hidden, ys
        hidden, buffers = carry
        hidden, ys, cache = block(
            hidden, segment_ids, cos, sin, cache.holding(buffers),
            cfg.first_k_dense_replace + layer,
            decoding_experts(cache, stack, layer, "layer", "mlp"),
        )
        return (hidden, cache.buffers), ys


class MTPModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3's, which
    pangu_ultra_moe publishes one of): `h'_i = W_eh [N_e(Emb(t_{i+1})) ;
    N_h(h_i)]`, then one decoder layer of the stack's last kind. `h_i` is the
    stack's output at position i BEFORE the final norm, `next_embeds` the
    shared embedding of the token after it. The caller applies the model's
    own final norm and head to what comes back: its logits at i are for
    `t_{i+2}`. Modules chain: the next one reads this one's `hidden` and the
    embedding of the token one further on. Returns `(hidden, dropped)`.

    A family whose layers are not `DeepseekDecoderLayer`s gives its own:
    `norm` (`name -> a norm module`) and `block` (`name -> a decoder layer`
    called `(hidden, segment_ids, cos, sin) -> (hidden, ys, cache)`, `ys` as
    `DeepseekDecoderLayer`'s)."""

    config: DeepseekConfig
    norm: Any = None
    block: Any = None

    @nn.compact
    def __call__(self, hidden, next_embeds, segment_ids, cos, sin):
        cfg = self.config
        norm = self.norm or (
            lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)
        )
        joined = jnp.concatenate([norm("enorm")(next_embeds), norm("hnorm")(hidden)], axis=-1)
        merged = _dense(cfg, cfg.hidden_size, (None, "embed"), "eh_proj", False)(joined)
        if self.block is not None:
            layer = self.block("layer")
        else:
            layer_cls = DeepseekDecoderLayer
            policy = _remat_policy(cfg)
            if policy is not None:
                layer_cls = nn.remat(DeepseekDecoderLayer, policy=policy)
            layer = layer_cls(cfg, cfg.layer_is_moe(cfg.num_hidden_layers), name="layer")
        out, ys, _ = layer(merged, segment_ids, cos, sin)
        return out, (jnp.float32(0.0) if ys is None else ys[0][2])


class Deepseek(nn.Module):
    """DeepSeek V2/V3 causal LM with the `CausalLMProto` surface, decoding
    through `decode_state` (dense or paged) like the Llama stack."""

    config: DeepseekConfig

    def _layers(self, hidden, segment_ids, cos, sin, cache):
        """-> (hidden, [(layer index, router stats, counts)] of the looped MoE
        layers, the scanned suffix's stacked (stats, counts) or None, cache)."""
        cfg = self.config
        policy = _remat_policy(cfg)
        n_scanned = cfg.num_scanned_layers
        looped = []
        for i in range(cfg.num_hidden_layers - n_scanned):
            layer_cls = DeepseekDecoderLayer
            if policy is not None:
                layer_cls = nn.remat(DeepseekDecoderLayer, policy=policy)
            hidden, ys, cache = layer_cls(cfg, cfg.layer_is_moe(i), name=f"layers_{i}")(
                hidden, segment_ids, cos, sin, cache, i
            )
            if ys is not None:
                looped.append((i, *ys))
        scanned = None
        if n_scanned:
            body = _MoEScanBody
            if policy is not None:
                body = nn.remat(_MoEScanBody, policy=policy, prevent_cse=False)
            hidden, scanned, cache = scan_layers(
                body, (cfg,), n_scanned, hidden, (segment_ids, cos, sin), cache,
                whole=EXPERT_LEAVES, name="moe_layers",
            )
        return hidden, looped, scanned, cache

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,
        inputs_embeds: jnp.ndarray | None = None,
        compute_logits: bool = True,
        return_last_hidden_states: bool = False,
        decode_state: DecodeState | PagedDecodeState | None = None,
        return_mtp: bool = False,
    ) -> CausalLMOutput:
        """`return_mtp` (a config with `num_nextn_predict_layers`; needs
        `input_ids`, also beside `inputs_embeds`): the multi-token-prediction
        module runs too and its final-normed hidden states come back as
        `mtp_hidden_states`, with `mtp_logits` beside them under
        `compute_logits`. No other call runs the module."""
        cfg = self.config
        if return_mtp and not cfg.num_nextn_predict_layers:
            raise ValueError("return_mtp needs a config with num_nextn_predict_layers")
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
        batch, seq = hidden.shape[:2]

        if position_ids is None:
            if decode_state is not None:
                raise ValueError("decoding needs position_ids: a chunk's place in its row")
            position_ids = jnp.arange(seq)[None, :]
        # a cache sets the length the rotary tables are chosen for (yarn's
        # long/short factors), not the chunk in hand
        table_length = seq if decode_state is None else decode_state.table_length
        inv_freq, attention_scaling = compute_rope_frequencies(
            cfg.rope_config, seq_len=table_length
        )
        cos, sin = compute_rope_cos_sin(inv_freq, position_ids, attention_scaling)
        if cfg.rope_interleave:
            half = cos.shape[-1] // 2
            cos = jnp.repeat(cos[..., :half], 2, axis=-1)
            sin = jnp.repeat(sin[..., :half], 2, axis=-1)

        cache, segment_ids = open_cache(decode_state, segment_ids, batch, seq)
        hidden, looped, scanned, cache = self._layers(hidden, segment_ids, cos, sin, cache)
        new_decode_state = close_cache(cache, decode_state, segment_ids)
        ep_dropped = sum((stats[2] for _, stats, _ in looped), jnp.float32(0.0))
        if scanned is not None:
            ep_dropped = ep_dropped + scanned[0][2].sum()

        final_norm = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")
        mtp_hidden = None
        # `init` makes the module's parameters whatever it was asked to return
        if return_mtp or (self.is_initializing() and cfg.num_nextn_predict_layers):
            if input_ids is None or decode_state is not None:
                raise ValueError("the MTP module reads input_ids, and is no part of decoding")
            with jax.named_scope("mtp"):
                # position i is given the token after it; the row's last
                # position has none, and predicts nothing (`lms/clm.py`)
                next_embeds = embed_tokens(jnp.roll(input_ids, -1, axis=1))
                mtp_hidden, mtp_dropped = MTPModule(cfg, name="mtp_0")(
                    hidden, next_embeds, segment_ids, cos, sin
                )
                mtp_hidden = final_norm(mtp_hidden)
            ep_dropped = ep_dropped + mtp_dropped
        hidden = final_norm(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        # per-MoE-layer router stats in layer order (dense prefix layers
        # carry none); DeepSeek optimizes no aux loss, but the health layer
        # still wants the balance signal per layer
        stats = [jax.tree.map(lambda *leaves: jnp.stack(leaves), *(s for _, s, _ in looped))] if looped else []
        counts = [jnp.stack([c for _, _, c in looped])] if looped and looped[0][2] is not None else []
        moe_ids = [i for i, _, _ in looped]
        if scanned is not None:
            stats.append(scanned[0])
            if scanned[1] is not None:
                counts.append(scanned[1])
            moe_ids.extend(
                range(cfg.num_hidden_layers - cfg.num_scanned_layers, cfg.num_hidden_layers)
            )
        router_stats = None
        if stats:
            sel_frac, mean_prob, _ = jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *stats)
            router_stats = RouterStats(
                sel_frac=sel_frac, mean_prob=mean_prob, dropped=ep_dropped,
                layer_ids=tuple(moe_ids),
            )

        logits = mtp_logits = None
        if compute_logits:
            if cfg.tie_word_embeddings:
                head = embed_tokens.attend
            else:
                head = _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head", False)
            logits = nn.with_logical_constraint(head(hidden), ("batch", "act_seq", "act_vocab"))
            if mtp_hidden is not None:
                mtp_logits = head(mtp_hidden)

        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            ep_dropped_rows=ep_dropped,
            router_stats=router_stats,
            decode_state=new_decode_state,
            # only a share of the experts has assignments held elsewhere to count
            moe_assignments=jnp.concatenate(counts).sum(axis=0) if counts else None,
            mtp_hidden_states=mtp_hidden,
            mtp_logits=mtp_logits,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        if self.config.tie_word_embeddings:
            return "embed_tokens/embedding"
        return "lm_head/kernel"
