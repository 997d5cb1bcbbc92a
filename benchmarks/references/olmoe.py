"""Plain reference for OLMoE (allenai/OLMoE-1B-7B-0125-Instruct): pre-norm
blocks of multi-head attention with one RMSNorm over the whole projected
query and key widths before the heads split, rotary positions, and a sparse
MLP of 64 SwiGLU experts of which the router's softmax picks 8 per token,
their probabilities used as they are (`norm_topk_prob` false). Every expert
is evaluated on every token and weighted by the routing matrix: slow, and
plainly the same sum."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references import _common as c


def sparse_mlp(x, w, cfg, quant=c.identity):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    probs = jax.nn.softmax(c.mm(x, w["gate"]["kernel"], quant), axis=-1)
    top, index = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob"):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    routing = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], index].set(top)

    def one_expert(total, expert):
        gate, up, down, weight = expert
        out = c.mm(jax.nn.silu(c.mm(x, gate, quant)) * c.mm(x, up, quant), down, quant)
        return total + out * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"], routing.T),
    )
    return total.reshape(shape)


def layer(x, w, cfg, segment_ids, cos, sin, quant=c.identity):
    eps = cfg["rms_norm_eps"]
    h = c.rms_norm(x, w["input_layernorm"]["weight"], eps)
    x = x + c.gqa_block(h, w["self_attn"], cfg, segment_ids, cos, sin, quant, qk_norm=True)
    h = c.rms_norm(x, w["post_attention_layernorm"]["weight"], eps)
    return x + sparse_mlp(h, w["mlp"], cfg, quant)


def logits(params, cfg, input_ids, segment_ids, position_ids, quant=c.identity):
    return c.decoder_logits(params, cfg, layer, input_ids, segment_ids, position_ids, quant)
