"""Plain reference for LongCat-Flash: the layer equations of `model.py`'s
docstring in float32 `jax.numpy`. No kernel, no cache, no chunking, no flax:
latent attention in the NON-absorbed form (every token's latent goes through
`W_kvb` to 64 heads' keys and values, full [S, S] softmax a head), every held
expert evaluated on every token and weighted by the routing matrix, the
zero-compute experts' weights summed a token and multiplied with the token.
Callers trace it under `jax.default_matmul_precision("highest")` (`logits`
does so itself).

`params` is the tree under 'params' of `LongcatFlash.init` with
`scan_layers=True` (`layers/layer/{sub_0, sub_1, mlp}`, a leading axis over
double layers); `cfg` is a mapping with the source's keys
(`benchmarks/configs/longcat-flash-omni-ep32.json` is one). The router's
width is its kernel's; the real experts are its first `width -
zero_expert_num` outputs, of which the stacked expert weights hold those from
`experts_first` on (all of them, or a chip's share; what is held elsewhere
adds nothing).

`benchmarks/references/longcat_flash.py` is the benchmark's copy (it may
import nothing from the program, and steps through a layer in smaller pieces
to fit beside the weights); `tests/test_longcat_flash.py` holds the two equal.

Departures from the source, none: what it does not give is listed as
`assumed` in the configuration file and in docs/models.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def swiglu(x, w):
    gate, up = x @ w["gate_proj"]["kernel"], x @ w["up_proj"]["kernel"]
    return (jax.nn.silu(gate) * up) @ w["down_proj"]["kernel"]


def rotate_pairs(x, positions, theta):
    """Rotary positions on the last axis of x `[B, S, ..., D]`, pairs (2i, 2i+1)."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    angles = positions.astype(F32)[..., None] * inv_freq  # [B, S, D/2]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + angles.shape[-1:])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def mla_block(z, w, cfg, segment_ids, position_ids):
    batch, seq, hidden = z.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    s_q = (hidden / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0
    s_kv = (hidden / latent) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0

    c_q = rms_norm(z @ w["q_a_proj"]["kernel"], w["q_a_layernorm"]["weight"], eps)
    q = s_q * (c_q @ w["q_b_proj"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    compressed = z @ w["kv_a_proj_with_mqa"]["kernel"]
    c_kv = s_kv * rms_norm(compressed[..., :latent], w["kv_a_layernorm"]["weight"], eps)
    kv = jnp.einsum("bsl,lhe->bshe", c_kv, w["kv_b_proj"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rotate_pairs(q[..., nope:], position_ids, cfg["rope_theta"])
    k_rope = rotate_pairs(compressed[..., latent:], position_ids, cfg["rope_theta"])

    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], k_nope)
        + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope)
    ) * (nope + rope) ** -0.5
    idx = jnp.arange(seq)
    mask = (idx[:, None] >= idx[None, :])[None] & (
        (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    )
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhv->bqhv", probs, v).reshape(batch, seq, -1)
    return out @ w["o_proj"]["kernel"]


def moe_block(u, w, cfg):
    shape = u.shape
    x = u.reshape(-1, shape[-1])
    scores = jax.nn.softmax(x @ w["router"]["kernel"], axis=-1)
    real = scores.shape[-1] - cfg["zero_expert_num"]
    _, chosen = jax.lax.top_k(scores + w["router"]["bias"], cfg["moe_topk"])
    weights = cfg["routed_scaling_factor"] * jnp.take_along_axis(scores, chosen, axis=1)
    routing = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    first, held = cfg.get("experts_first", 0), w["experts_gate_proj"].shape[0]
    experts = jax.vmap(
        lambda gate, up, down: (jax.nn.silu(x @ gate) * (x @ up)) @ down
    )(w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"])  # [E, T, H]
    routed = jnp.einsum("eth,te->th", experts, routing[:, first:first + held])
    zero = jnp.sum(routing[:, real:], axis=-1, keepdims=True) * x
    return (routed + zero).reshape(shape)


def double_layer(x, w, cfg, segment_ids, position_ids):
    eps = cfg["rms_norm_eps"]
    norm = lambda sub, name, h: rms_norm(h, w[sub][name]["weight"], eps)
    h = x + mla_block(norm("sub_0", "input_layernorm", x), w["sub_0"]["self_attn"], cfg,
                      segment_ids, position_ids)
    u = norm("sub_0", "post_attention_layernorm", h)
    m = moe_block(u, w["mlp"], cfg)
    h = h + swiglu(u, w["sub_0"]["mlp"])
    h = h + mla_block(norm("sub_1", "input_layernorm", h), w["sub_1"]["self_attn"], cfg,
                      segment_ids, position_ids)
    return h + swiglu(norm("sub_1", "post_attention_layernorm", h), w["sub_1"]["mlp"]) + m


def logits(params, cfg, input_ids, segment_ids, position_ids=None):
    """Full-sequence logits [B, S, V], one jitted double layer at a time."""
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    one_layer = jax.jit(lambda x, w, seg, pos: double_layer(x, w, cfg, seg, pos))
    stack = params["layers"]["layer"]
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(F32)[input_ids]
        for index in range(cfg["num_layers"]):
            w = jax.tree.map(lambda a: a[index].astype(F32), stack)
            x = one_layer(x, w, segment_ids, position_ids)
        x = rms_norm(x, params["norm"]["weight"].astype(F32), cfg["rms_norm_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)
