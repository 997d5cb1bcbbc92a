"""DeepSeek V2/V3 decoder, TPU-native.

Graph verified against HF `modeling_deepseek_v2.py` / `modeling_deepseek_v3.py`:

- MLA (multi-head latent attention): q via optional LoRA factorization
  (q_a_proj -> RMSNorm -> q_b_proj), kv via a shared compressed latent
  (kv_a_proj_with_mqa -> split latent + rope part -> RMSNorm -> kv_b_proj).
  Per head, q/k are [nope | rope] concatenations; the rope part of k is
  MQA-style (one head, broadcast). Rotation uses the interleaved
  (complex-pair) layout the HF checkpoints store (`rope_interleave`).
  v (v_head_dim) is zero-padded to qk_head_dim for the attention kernel and
  sliced back — padding columns receive zero weight, exactly HF's FA2 trick.
- attention scale 1/sqrt(qk_head_dim) with DeepSeek-yarn's squared-mscale
  correction (config.attention_scale).
- MoE: fp32 router (sigmoid + e_score_correction_bias + top-2-sum group
  selection for v3; softmax + greedy / group-limited max for v2), dropless
  `lax.ragged_dot` grouped matmuls over ONE stacked parameter per
  projection, always-on shared experts, routed_scaling_factor. No aux loss:
  v3 balances via the noaux bias; the HF v2 port computes none either.
- dense prefix: layers [0, first_k_dense_replace) use the full-width MLP and
  are looped; the uniform MoE suffix scans (`nn.scan`) so compile time stays
  ~flat in depth.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import CausalLMOutput, RouterStats
from llm_training_tpu.models.deepseek.config import DeepseekConfig
from llm_training_tpu.models.llama.model import RMSNorm, _dense
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.ops import apply_rope, dot_product_attention
from llm_training_tpu.ops.rope_utils import compute_rope_cos_sin, compute_rope_frequencies
from llm_training_tpu.ops.swiglu import silu_mul


class MLAttention(nn.Module):
    config: DeepseekConfig

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads = cfg.num_attention_heads
        qk_dim, rope_dim, nope_dim = (
            cfg.qk_head_dim, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim
        )

        if cfg.q_lora_rank is None:
            q = _dense(cfg, heads * qk_dim, ("embed", "heads"), "q_proj", False)(hidden)
        else:
            q = _dense(cfg, cfg.q_lora_rank, ("embed", None), "q_a_proj",
                       cfg.attention_bias)(hidden)
            q = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="q_a_layernorm")(q)
            q = _dense(cfg, heads * qk_dim, (None, "heads"), "q_b_proj", False)(q)
        q = q.reshape(batch, seq, heads, qk_dim)
        q_nope, q_rot = q[..., :nope_dim], q[..., nope_dim:]

        compressed = _dense(
            cfg, cfg.kv_lora_rank + rope_dim, ("embed", None),
            "kv_a_proj_with_mqa", cfg.attention_bias,
        )(hidden)
        kv_latent, k_rot = compressed[..., : cfg.kv_lora_rank], compressed[..., cfg.kv_lora_rank:]
        kv_latent = RMSNorm(
            cfg.rms_norm_eps, cfg.param_jnp_dtype, name="kv_a_layernorm"
        )(kv_latent)
        kv = _dense(
            cfg, heads * (nope_dim + cfg.v_head_dim), (None, "heads"), "kv_b_proj", False
        )(kv_latent).reshape(batch, seq, heads, nope_dim + cfg.v_head_dim)
        k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]

        # MQA rope head: one k head, rotated, broadcast across query heads
        k_rot = k_rot[:, :, None, :]
        q_rot, k_rot = apply_rope(
            q_rot, k_rot, cos, sin, interleaved=cfg.rope_interleave
        )
        k_rot = jnp.broadcast_to(k_rot, (batch, seq, heads, rope_dim))

        q = jnp.concatenate([q_nope, q_rot], axis=-1)
        k = jnp.concatenate([k_nope, k_rot], axis=-1)
        # pad v to the qk head dim for the kernel; the padded columns get
        # zero attention weight mass and are sliced off after
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_dim - cfg.v_head_dim)))

        out = dot_product_attention(
            q, k, v_pad,
            segment_ids=segment_ids,
            causal=True,
            scale=cfg.attention_scale,
            impl=cfg.attention_impl,
        )[..., : cfg.v_head_dim]
        out = out.astype(hidden.dtype).reshape(batch, seq, heads * cfg.v_head_dim)
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj",
                      cfg.attention_bias)(out)


class DeepseekMLP(nn.Module):
    """SwiGLU MLP (HF DeepseekV2/V3MLP) with a configurable width."""

    config: DeepseekConfig
    intermediate_size: int

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = _dense(cfg, self.intermediate_size, ("embed", "mlp"), "gate_proj", False)(hidden)
        up = _dense(cfg, self.intermediate_size, ("embed", "mlp"), "up_proj", False)(hidden)
        return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj", False)(
            silu_mul(gate, up)
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
    layer scan: the experts' `EXPERT_LEAVES` whole, `[L, E, ...]`, read in
    place (`models/moe.py:experts_in_place`); None, and the grouped products
    are `jax.lax.ragged_dot` on this layer's own matrices."""

    config: DeepseekConfig

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

        def dense_fn(xc):
            gate = jnp.einsum("th,ehi->tei", xc, w_gate)
            up = jnp.einsum("th,ehi->tei", xc, w_up)
            return jnp.einsum("tei,eih->teh", nn.silu(gate) * up, w_down)

        from llm_training_tpu.models.moe import (
            dropless_moe_apply,
            experts_in_place,
            grouped_matmul,
        )

        weights, layer = experts_in_place(
            stack, (w_gate, w_up, w_down), cfg.moe_impl, compute_dtype
        )

        def ragged_fn(xs, group_sizes, expert_order, w):
            wg, wu, wd = w
            gate = grouped_matmul(xs, wg, group_sizes, layer)
            up = grouped_matmul(xs, wu, group_sizes, layer)
            return grouped_matmul(nn.silu(gate) * up, wd, group_sizes, layer)

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
        from llm_training_tpu.models.moe import router_block_stats

        sel_frac, mean_prob = router_block_stats(
            topk_idx, norm_scores, num_experts, pad_mask
        )
        return out + shared, (sel_frac, mean_prob, dropped)


class DeepseekDecoderLayer(nn.Module):
    """Pre-norm block (HF DeepseekV2/V3DecoderLayer). Returns
    (hidden, stats) — DeepSeek computes no aux loss (the noaux bias
    balances instead), so the layer ys channel carries the router health
    triple (sel_frac [E], mean_prob [E], dropped scalar) on MoE layers and
    None on dense layers (`is_moe` is static, so the structures are
    trace-time constants)."""

    config: DeepseekConfig
    is_moe: bool

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)

        normed = norm("input_layernorm")(hidden)
        hidden = hidden + MLAttention(cfg, name="self_attn")(normed, segment_ids, cos, sin)
        normed = norm("post_attention_layernorm")(hidden)
        if self.is_moe:
            pad_mask = None if segment_ids is None else segment_ids > 0
            mlp_out, stats = DeepseekMoE(cfg, name="mlp")(normed, pad_mask)
        else:
            mlp_out = DeepseekMLP(cfg, cfg.intermediate_size, name="mlp")(normed)
            stats = None
        return hidden + mlp_out, stats


class _MoEScanBody(nn.Module):
    """Scan body: one MoE layer. The dense prefix is non-uniform with the
    suffix, so it is looped; everything from `first_k_dense_replace` on is
    the SAME graph and scans — compile time stays ~flat in depth (DeepSeek-V3
    is 61 layers; a looped stack would compile 58 copies of this body)."""

    config: DeepseekConfig

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin):
        hidden, stats = DeepseekDecoderLayer(self.config, True, name="layer")(
            hidden, segment_ids, cos, sin
        )
        return hidden, stats


class Deepseek(nn.Module):
    """DeepSeek V2/V3 causal LM with the `CausalLMProto` surface."""

    config: DeepseekConfig

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,
        inputs_embeds: jnp.ndarray | None = None,
        compute_logits: bool = True,
        return_last_hidden_states: bool = False,
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

        if position_ids is None:
            position_ids = jnp.arange(seq)[None, :]
        inv_freq, attention_scaling = compute_rope_frequencies(
            cfg.rope_config, seq_len=seq
        )
        cos, sin = compute_rope_cos_sin(inv_freq, position_ids, attention_scaling)
        if cfg.rope_interleave:
            half = cos.shape[-1] // 2
            cos = jnp.repeat(cos[..., :half], 2, axis=-1)
            sin = jnp.repeat(sin[..., :half], 2, axis=-1)

        policy = _remat_policy(cfg)
        n_scanned = cfg.num_scanned_layers
        ep_dropped = jnp.float32(0.0)
        moe_sel, moe_prob, moe_ids = [], [], []
        for i in range(cfg.num_hidden_layers - n_scanned):
            layer_cls = DeepseekDecoderLayer
            if policy is not None:
                layer_cls = nn.remat(DeepseekDecoderLayer, policy=policy)
            hidden, stats = layer_cls(cfg, cfg.layer_is_moe(i), name=f"layers_{i}")(
                hidden, segment_ids, cos, sin
            )
            if stats is not None:
                moe_sel.append(stats[0])
                moe_prob.append(stats[1])
                moe_ids.append(i)
                ep_dropped = ep_dropped + stats[2]
        if n_scanned:
            body = _MoEScanBody
            if policy is not None:
                body = nn.remat(_MoEScanBody, policy=policy, prevent_cse=False)
            scanned = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
                length=n_scanned,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="moe_layers")
            hidden, (sel, prob, dropped) = scanned(hidden, segment_ids, cos, sin)
            ep_dropped = ep_dropped + dropped.sum()

        hidden = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        # assemble per-MoE-layer router stats in layer order (dense prefix
        # layers carry none); DeepSeek optimizes no aux loss, but the health
        # layer still wants the balance signal per layer
        sel_parts = [jnp.stack(moe_sel)] if moe_sel else []
        prob_parts = [jnp.stack(moe_prob)] if moe_prob else []
        if n_scanned:
            sel_parts.append(sel)
            prob_parts.append(prob)
            moe_ids.extend(
                range(cfg.num_hidden_layers - n_scanned, cfg.num_hidden_layers)
            )
        router_stats = None
        if sel_parts:
            router_stats = RouterStats(
                sel_frac=jnp.concatenate(sel_parts),
                mean_prob=jnp.concatenate(prob_parts),
                dropped=ep_dropped,
                layer_ids=tuple(moe_ids),
            )

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
            ep_dropped_rows=ep_dropped,
            router_stats=router_stats,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        if self.config.tie_word_embeddings:
            return "embed_tokens/embedding"
        return "lm_head/kernel"
