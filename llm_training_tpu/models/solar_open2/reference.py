"""Plain reference for Solar-Open2: the layer equations of
`model.py`'s docstring in float32 `jax.numpy`, straight from the published
description. No kernel, no cache, no chunking, no flax: the KDA state is
advanced ONE TOKEN AT A TIME with `lax.scan`, the softmax attention builds
its full [S, S] scores, and every held expert is evaluated on every token
and weighted by the routing matrix. Callers trace it under
`jax.default_matmul_precision("highest")` (`logits` does so itself).

`params` is the tree under 'params' of `SolarOpen2.init` with
`scan_layers=True` (`layers/slot{j}/...`, a leading axis over periods);
`cfg` is a mapping with the published keys (`benchmarks/configs/
solar-open2-250b-ep8.json` is one): `n_routed_experts` counts the experts
HELD, `reduced_from.n_routed_experts` the router's outputs when they
differ, `experts_first` the first one held.

`benchmarks/references/solar_open2.py` is the benchmark's copy of this file
(it may import nothing from the program); `tests/test_solar_open2.py` holds
the two equal.

Departures from the published description, none: sizes it does not give are
listed as `assumed` in the configuration file and in docs/models.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def kda_block(x, w, cfg, segment_ids):
    """Kimi Delta Attention on x [B, S, hidden], from a zero state."""
    batch, seq, _ = x.shape
    linear = cfg["linear_attn_config"]
    heads, dim, width_k = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
    valid = segment_ids > 0
    before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
    starts = valid & (segment_ids != before)  # a packed document begins: zero state, cut conv

    mixed = jnp.concatenate([x @ w[n]["kernel"] for n in ("q_proj", "k_proj", "v_proj")], axis=-1)
    mixed = jnp.where(valid[..., None], mixed, 0.0)
    padded = jnp.pad(mixed, ((0, 0), (width_k - 1, 0), (0, 0)))
    seg_p = jnp.concatenate(
        [jnp.broadcast_to(segment_ids[:, :1], (batch, width_k - 1)), segment_ids], axis=1
    )
    conv = sum(
        jnp.where((seg_p[:, i:i + seq] == segment_ids)[..., None], padded[:, i:i + seq], 0.0)
        * w["conv_kernel"][i]
        for i in range(width_k)
    )
    q, k, v = (
        part.reshape(batch, seq, heads, dim) for part in jnp.split(jax.nn.silu(conv), 3, axis=-1)
    )
    q, k = l2_norm(q) * dim ** -0.5, l2_norm(k)

    decay_in = (x @ w["f_a_proj"]["kernel"]) @ w["f_b_proj"]["kernel"] + w["dt_bias"]
    log_alpha = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        decay_in.reshape(batch, seq, heads, dim)
    )
    strength = 2.0 if cfg["kda_allow_neg_eigval"] else 1.0
    beta = strength * jax.nn.sigmoid(x @ w["b_proj"]["kernel"])  # [B, S, H]
    alpha = jnp.where(valid[..., None, None], jnp.exp(log_alpha), 1.0)
    beta = jnp.where(valid[..., None], beta, 0.0)

    def one_token(state, token):
        q_t, k_t, v_t, alpha_t, beta_t, start_t = token
        state = jnp.where(start_t[:, None, None, None], 0.0, state)
        # S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T
        state = alpha_t[..., None] * state
        state = state - beta_t[..., None, None] * k_t[..., None] * jnp.einsum(
            "bhk,bhkv->bhv", k_t, state)[..., None, :]
        state = state + beta_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)  # o_t = S_t^T q_t

    by_time = lambda a: jnp.moveaxis(a, 1, 0)
    _, out = jax.lax.scan(
        one_token, jnp.zeros((batch, heads, dim, dim), F32),
        tuple(by_time(a) for a in (q, k, v, alpha, beta, starts)),
    )
    out = rms_norm(by_time(out), w["o_norm"]["weight"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid((x @ w["g_a_proj"]["kernel"]) @ w["g_b_proj"]["kernel"])
    return (out.reshape(batch, seq, heads * dim) * gate) @ w["o_proj"]["kernel"]


def gqa_block(x, w, cfg, segment_ids):
    """Causal softmax attention, no positional term, gated an output channel."""
    batch, seq, _ = x.shape
    heads, kv_heads, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ w["q_proj"]["kernel"]).reshape(batch, seq, kv_heads, heads // kv_heads, dim)
    k = (x @ w["k_proj"]["kernel"]).reshape(batch, seq, kv_heads, dim)
    v = (x @ w["v_proj"]["kernel"]).reshape(batch, seq, kv_heads, dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * dim ** -0.5
    index = jnp.arange(seq)
    same = (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    mask = (index[:, None] >= index[None, :])[None] & same
    probs = jax.nn.softmax(jnp.where(mask[:, None, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(batch, seq, heads * dim)
    if cfg["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(x @ w["g_proj"]["kernel"])
    return out @ w["o_proj"]["kernel"]


def moe_block(x, w, cfg):
    """sigmoid scores over ALL the router's experts, the top k of score +
    bias, weights normalised over the chosen k; the experts held here (the
    stacked weights' leading axis) each evaluated on every token; what is
    held elsewhere adds nothing; the shared expert adds to every token."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(x @ w["gate_kernel"])
    _, chosen = jax.lax.top_k(scores + w["e_score_correction_bias"], cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg["routed_scaling_factor"]
    routing = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    held = w["experts_gate_proj"].shape[0]
    first = cfg.get("experts_first", 0)
    routing = routing[:, first:first + held]

    def one_expert(total, expert):
        gate, up, down, weight = expert
        return total + swiglu(x, gate, up, down) * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"], routing.T),
    )
    shared = w["shared_experts"]
    total = total + swiglu(
        x, shared["gate_proj"]["kernel"], shared["up_proj"]["kernel"], shared["down_proj"]["kernel"]
    )
    return total.reshape(shape)


def layer(x, w, cfg, segment_ids, is_gqa: bool):
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, w["input_layernorm"]["weight"], eps)
    if is_gqa:
        x = x + gqa_block(h, w["self_attn"], cfg, segment_ids)
    else:
        x = x + kda_block(h, w["linear_attn"], cfg, segment_ids)
    return x + moe_block(rms_norm(x, w["post_attention_layernorm"]["weight"], eps), w["mlp"], cfg)


def layer_is_gqa(cfg, index: int) -> bool:
    if cfg.get("gqa_layers") is not None:
        return index in cfg["gqa_layers"]
    return index % (cfg["gqa_interval"] + 1) == 0


def logits(params, cfg, input_ids, segment_ids):
    """Full-sequence logits [B, S, V], one jitted layer at a time."""
    stack = params["layers"]
    period = len(stack)  # slot0 .. slot{period-1}, each stacked over the periods
    one_layer = jax.jit(lambda x, w, seg, is_gqa: layer(x, w, cfg, seg, is_gqa), static_argnums=3)
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(F32)[input_ids]
        for index in range(cfg["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[index // period].astype(F32), stack[f"slot{index % period}"])
            x = one_layer(x, w, segment_ids, layer_is_gqa(cfg, index))
        x = rms_norm(x, params["norm"]["weight"].astype(F32), cfg["rms_norm_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)

