"""LongCat-Flash decoder (`model_type: longcat_flash`), TPU-native: the
language model of LongCat-Flash-Omni (560B-A27B), text ids in, logits out.

One DOUBLE layer (`num_layers` of them; four RMSNorms with their own weights):

    h = x + MLA_0(N1(x))
    u = N2(h)
    m = MoE(u)                    # the shortcut: computed here, added at the end
    h = h + FFN_0(u)
    h = h + MLA_1(N3(h))
    y = h + FFN_1(N4(h)) + m

- MLA block: `c_q = RMSNorm(W_qa z)`, `q = s_q W_qb c_q` a head `[q_nope 128 |
  q_rope 64]`; `[c | k_r] = W_kva z`, `c_kv = s_kv RMSNorm(c)`, `[k_nope | v] =
  W_kvb c_kv` a head; `q_rope` and the ONE `k_r` a token take interleaved
  rotary positions; scores `(q_nope . k_nope + q_rope . k_r) / sqrt(192)`.
  `s_q = sqrt(hidden / q_lora_rank)`, `s_kv = sqrt(hidden / kv_lora_rank)`.
- MoE: float32 softmax over `n_routed_experts + zero_expert_num` outputs, the
  `moe_topk` largest of `p + bias` chosen, weight `routed_scaling_factor *
  p_i`, NOT renormalised; a chosen real expert adds `w_i E_i(u)`, a chosen
  zero-compute expert adds `w_i u`. With `experts_held` the block is an
  expert-parallel SHARE: real experts held elsewhere add nothing here
  (`models/moe.py:dropless_moe_apply(held=)`), the zero-compute ones are this
  chip's own rows' and are computed in full, never entering the dispatch.

Decoding (docs/inference.md, docs/serving.md): a token leaves ONE row in the
cache for each MLA block, `[c_kv | rotated k_r]` (576 values), declared by
`LongcatFlashConfig.cache_specs()`; the block appends it and attends through
`LayerCache.attend_latent`: absorbed against the paged latent pool for one
token a row, expanded through `W_kvb` for a chunk. The stack scans over
double layers; the latent buffer of all `2 * num_layers` blocks rides that
loop as its carry, and the held experts' stacked weights are read where they
lie (`models/cache.py:scan_layers`, `whole=`).
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
from llm_training_tpu.models.cache import close_cache, open_cache, scan_layers
from llm_training_tpu.models.deepseek.model import MLAttention
from llm_training_tpu.models.llama.model import RMSNorm, _dense
from llm_training_tpu.models.longcat_flash.config import LongcatFlashConfig
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
from llm_training_tpu.ops.swiglu import silu_mul


class LongcatMLP(nn.Module):
    """The dense SwiGLU FFN of a sub-block, width `ffn_hidden_size`."""

    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, hidden):
        cfg = self.config
        gate = _dense(cfg, cfg.ffn_hidden_size, ("embed", "mlp"), "gate_proj", False)(hidden)
        up = _dense(cfg, cfg.ffn_hidden_size, ("embed", "mlp"), "up_proj", False)(hidden)
        return _dense(cfg, cfg.hidden_size, ("mlp", "embed"), "down_proj", False)(
            silu_mul(gate, up)
        )


class _Router(nn.Module):
    """Float32 softmax scores over the real and the zero-compute experts, and
    what the choice sees: the scores plus the correction bias (a deployment's
    load controller tunes it; no gradient reaches it). The weights of the
    chosen are the raw scores."""

    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        kernel = self.param(
            "kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), ("embed", "expert")
            ),
            (x.shape[-1], cfg.router_width), cfg.param_jnp_dtype,
        )
        bias = self.param(
            "bias", nn.with_logical_partitioning(nn.initializers.zeros_init(), ("expert",)),
            (cfg.router_width,), jnp.float32,
        )
        scores = jax.nn.softmax(x.astype(jnp.float32) @ kernel.astype(jnp.float32), axis=-1)
        return scores, scores + jax.lax.stop_gradient(bias)


class LongcatMoE(nn.Module):
    """Router over real and zero-compute experts, the held real experts, the
    identity term. Returns `(out, (sel_frac, mean_prob, dropped), counts)`:
    `counts [3]` int32, this call's assignments to experts held here, to
    zero-compute experts, and to real experts held elsewhere, padding left
    out. `stack = (leaves, layer)` from a decoding layer scan: the held
    experts' `EXPERT_LEAVES` whole, `[L, E, ...]`, read in place
    (`models/moe.py:MoEMLP`)."""

    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, hidden, pad_mask=None, stack=None):
        cfg = self.config
        real, width, top_k = cfg.n_routed_experts, cfg.router_width, cfg.moe_topk
        first, num_held = cfg.experts_first, cfg.num_experts_held
        inter = cfg.expert_ffn_hidden_size
        compute_dtype, param_dtype = cfg.compute_jnp_dtype, cfg.param_jnp_dtype
        batch, seq, embed = hidden.shape
        x = hidden.reshape(-1, embed)

        with jax.named_scope("moe_route"):
            scores, choice = _Router(cfg, name="router")(x)
            _, topk_idx = jax.lax.top_k(choice, top_k)
            topk_weights = jnp.take_along_axis(scores, topk_idx, axis=1)
            topk_weights = (topk_weights * cfg.routed_scaling_factor).astype(compute_dtype)
            is_zero = topk_idx >= real

        def expert_param(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(nn.initializers.normal(cfg.initializer_range), axes),
                shape, param_dtype,
            ).astype(compute_dtype)

        w_gate = expert_param("experts_gate_proj", (num_held, embed, inter), ("expert", "embed", "mlp"))
        w_up = expert_param("experts_up_proj", (num_held, embed, inter), ("expert", "embed", "mlp"))
        w_down = expert_param("experts_down_proj", (num_held, inter, embed), ("expert", "mlp", "embed"))

        def dense_fn(xc):
            gate = jnp.einsum("th,ehi->tei", xc, w_gate)
            up = jnp.einsum("th,ehi->tei", xc, w_up)
            return jnp.einsum("tei,eih->teh", nn.silu(gate) * up, w_down)

        weights, layer = experts_in_place(
            stack, (w_gate, w_up, w_down), cfg.moe_impl, compute_dtype, self.path
        )

        def ragged_fn(xs, group_sizes, expert_order, w):
            # with `layer`, the grouped product that visits the non-empty
            # groups' row tiles only: of a share's rows, 47 of 48 are of no
            # held group, and `ragged_dot` on the chip multiplies them all
            wg, wu, wd = w
            gate = grouped_matmul(xs, wg, group_sizes, layer)
            up = grouped_matmul(xs, wu, group_sizes, layer)
            return grouped_matmul(nn.silu(gate) * up, wd, group_sizes, layer)

        xc = x.astype(compute_dtype)
        out, dropped = dropless_moe_apply(
            xc, topk_idx, topk_weights, real, cfg.moe_impl, dense_fn, ragged_fn,
            weights=weights,
            # a zero-compute choice is of no held group, like one held elsewhere
            held=(first, num_held),
        )
        with jax.named_scope("moe_zero"):
            # the zero-compute experts are the identity: their weights, summed
            # a token, times the token. This chip's own rows', in full
            zero_weight = jnp.sum(
                jnp.where(is_zero, topk_weights.astype(jnp.float32), 0.0), axis=-1, keepdims=True
            )
            out = out + (zero_weight * xc.astype(jnp.float32)).astype(out.dtype)

        sel_frac, mean_prob = router_block_stats(topk_idx, scores, width, pad_mask)
        counts = assignment_counts(topk_idx, first, num_held, pad_mask, is_zero)
        return (
            out.reshape(batch, seq, embed).astype(hidden.dtype),
            (sel_frac, mean_prob, dropped), counts,
        )


class _SubBlock(nn.Module):
    """Half a double layer up to its FFN: `h = x + MLA(N_a(x))`, `u = N_b(h)`;
    returns `(h, u, FFN(u), cache)`. The MoE of the first half reads `u`."""

    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x, segment_ids, cos, sin, cache=None, block=None):
        cfg = self.config
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)
        attn, cache = MLAttention(
            cfg, q_scale=cfg.q_scale, kv_scale=cfg.kv_scale, kv_b_stacked=True, name="self_attn"
        )(
            norm("input_layernorm")(x), segment_ids, cos, sin, cache, block
        )
        h = x + attn
        u = norm("post_attention_layernorm")(h)
        return h, u, LongcatMLP(cfg, name="mlp")(u), cache


class LongcatDoubleLayer(nn.Module):
    """Returns `(hidden, (router stats, assignment counts), cache)`. `layer`
    is this double layer's index: its MLA blocks are `2 * layer` and `2 *
    layer + 1` of the cache, its experts layer `layer` of `stack`."""

    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, x, segment_ids, cos, sin, cache=None, layer=None, stack=None):
        cfg = self.config
        x = nn.with_logical_constraint(x, ("batch", "act_seq", "act_embed"))
        block = lambda j: None if cache is None else 2 * layer + j
        h, u, ffn, cache = _SubBlock(cfg, name="sub_0")(x, segment_ids, cos, sin, cache, block(0))
        with jax.named_scope("scmoe"):
            pad_mask = None if segment_ids is None else segment_ids > 0
            m, stats, counts = LongcatMoE(cfg, name="mlp")(
                u, pad_mask, decoding_experts(cache, stack, layer, "mlp")
            )
        h = h + ffn
        h, _, ffn, cache = _SubBlock(cfg, name="sub_1")(h, segment_ids, cos, sin, cache, block(1))
        return h + ffn + m, (stats, counts), cache


class _ScannedLayer(nn.Module):
    """`(carry, xs) -> (carry, ys)` for `nn.scan`; the carry is `hidden` or,
    when decoding, `(hidden, the cache's buffers)` (`models/cache.py`)."""

    config: LongcatFlashConfig

    @nn.compact
    def __call__(self, carry, segment_ids, cos, sin, cache=None, layer=None, stack=None):
        block = LongcatDoubleLayer(self.config, name="layer")
        if cache is None:
            hidden, ys, _ = block(carry, segment_ids, cos, sin)
            return hidden, ys
        hidden, buffers = carry
        hidden, ys, cache = block(
            hidden, segment_ids, cos, sin, cache.holding(buffers), layer,
            None if stack is None else stack["layer"],
        )
        return (hidden, cache.buffers), ys


class LongcatFlash(nn.Module):
    """LongCat-Flash causal LM with the `CausalLMProto` surface, decoding
    through `decode_state` (dense or paged) like the Llama stack."""

    config: LongcatFlashConfig

    def _layers(self, hidden, segment_ids, cos, sin, cache):
        """-> (hidden, (router stats [L, ...], counts [L, 3]), cache or None)."""
        cfg = self.config
        policy = _remat_policy(cfg)
        if cfg.scan_layers:
            body = _ScannedLayer
            if policy is not None:
                body = nn.remat(_ScannedLayer, policy=policy, prevent_cse=False)
            return scan_layers(
                body, (cfg,), cfg.num_layers, hidden, (segment_ids, cos, sin), cache,
                whole=EXPERT_LEAVES,
            )
        ys = []
        for i in range(cfg.num_layers):
            layer_cls = LongcatDoubleLayer
            if policy is not None:
                layer_cls = nn.remat(LongcatDoubleLayer, policy=policy)
            hidden, layer_ys, cache = layer_cls(cfg, name=f"layers_{i}")(
                hidden, segment_ids, cos, sin, cache, i
            )
            ys.append(layer_ys)
        return hidden, jax.tree.map(lambda *leaves: jnp.stack(leaves), *ys), cache

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
        batch, seq = hidden.shape[:2]

        if position_ids is None:
            if decode_state is not None:
                raise ValueError("decoding needs position_ids: a chunk's place in its row")
            position_ids = jnp.arange(seq)[None, :]
        # plain rotary, no scaling; tables in the interleaved (even, odd) pairing
        rope = cfg.qk_rope_head_dim
        inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope))
        angles = position_ids.astype(jnp.float32)[..., None] * inv_freq
        angles = jnp.repeat(angles, 2, axis=-1)
        cos, sin = jnp.cos(angles), jnp.sin(angles)

        cache, segment_ids = open_cache(decode_state, segment_ids, batch, seq)
        hidden, ((sel_frac, mean_prob, dropped), counts), cache = self._layers(
            hidden, segment_ids, cos, sin, cache
        )
        new_decode_state = close_cache(cache, decode_state, segment_ids)

        hidden = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        logits = None
        if compute_logits:
            logits = _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head", False)(hidden)
            logits = nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))

        ep_dropped = dropped.sum()
        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            aux_loss=None,  # the correction bias balances the experts
            ep_dropped_rows=ep_dropped,
            router_stats=RouterStats(
                sel_frac=sel_frac, mean_prob=mean_prob, dropped=ep_dropped,
                layer_ids=tuple(range(cfg.num_layers)),
            ),
            decode_state=new_decode_state,
            # only a share of the experts has assignments held elsewhere to count
            moe_assignments=counts.sum(axis=0) if cfg.counts_expert_assignments else None,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        return "lm_head/kernel"
