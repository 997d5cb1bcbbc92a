"""Plain reference for Solar-Open2 (upstage/Solar-Open2-250B, `model_type:
solar_open2`): the benchmark's copy of `llm_training_tpu/models/solar_open2/
reference.py` (one tier-1 test holds the two equal), importing nothing from
the program.

Every layer is `x += mixer(RMSNorm(x)); x += moe(RMSNorm(x))`. The mixer of
a layer in `gqa_layers` is causal softmax attention over grouped key/value
heads with no positional term at all, times a sigmoid gate an output
channel; of the others Kimi Delta Attention, its [128, 128] state a head
advanced ONE TOKEN AT A TIME straight from

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T,  o_t = S_t^T q_t.

The MoE scores all the router's experts with a sigmoid, takes the top k of
score + bias, normalises the chosen weights, and evaluates every expert HELD
here (the stacked weights' leading axis: the chip's share, experts
`experts_first` onwards) on every token, weighted by the routing matrix;
what is held elsewhere adds nothing, the shared expert adds to every token.
Positions of segment 0 change nothing; `position_ids` is read by nothing."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.references import _common as c


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def kda_block(x, w, cfg, segment_ids, quant=c.identity):
    batch, seq, _ = x.shape
    linear = cfg["linear_attn_config"]
    heads, dim, width_k = linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"]
    valid = segment_ids > 0
    before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
    starts = valid & (segment_ids != before)  # a packed document begins: zero state, cut conv

    mixed = jnp.concatenate(
        [c.mm(x, w[n]["kernel"], quant) for n in ("q_proj", "k_proj", "v_proj")], axis=-1
    )
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

    low_rank = lambda a, b: c.mm(c.mm(x, w[a]["kernel"], quant), w[b]["kernel"], quant)
    log_alpha = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (low_rank("f_a_proj", "f_b_proj") + w["dt_bias"]).reshape(batch, seq, heads, dim)
    )
    strength = 2.0 if cfg["kda_allow_neg_eigval"] else 1.0
    beta = strength * jax.nn.sigmoid(c.mm(x, w["b_proj"]["kernel"], quant))  # [B, S, H]
    alpha = jnp.where(valid[..., None, None], jnp.exp(log_alpha), 1.0)
    beta = jnp.where(valid[..., None], beta, 0.0)

    def one_token(state, token):
        q_t, k_t, v_t, alpha_t, beta_t, start_t = token
        state = jnp.where(start_t[:, None, None, None], 0.0, state)
        state = alpha_t[..., None] * state
        state = state - beta_t[..., None, None] * k_t[..., None] * jnp.einsum(
            "bhk,bhkv->bhv", k_t, state)[..., None, :]
        state = state + beta_t[..., None, None] * k_t[..., None] * v_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    by_time = lambda a: jnp.moveaxis(a, 1, 0)
    _, out = jax.lax.scan(
        one_token, jnp.zeros((batch, heads, dim, dim), c.F32),
        tuple(by_time(a) for a in (q, k, v, alpha, beta, starts)),
    )
    out = c.rms_norm(by_time(out), w["o_norm"]["weight"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(low_rank("g_a_proj", "g_b_proj"))
    return c.mm(out.reshape(batch, seq, heads * dim) * gate, w["o_proj"]["kernel"], quant)


def gqa_block(x, w, cfg, segment_ids, quant=c.identity):
    batch, seq, _ = x.shape
    heads, kv_heads, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = c.mm(x, w["q_proj"]["kernel"], quant).reshape(batch, seq, heads, dim)
    k = c.mm(x, w["k_proj"]["kernel"], quant).reshape(batch, seq, kv_heads, dim)
    v = c.mm(x, w["v_proj"]["kernel"], quant).reshape(batch, seq, kv_heads, dim)
    out = c.attention(q, k, v, segment_ids, None, quant)  # full [S, S] scores, a kv group at a time
    if cfg["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(c.mm(x, w["g_proj"]["kernel"], quant))
    return c.mm(out, w["o_proj"]["kernel"], quant)


def moe_block(x, w, cfg, quant=c.identity):
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(c.mm(x, w["gate_kernel"], quant))
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
        out = c.mm(jax.nn.silu(c.mm(x, gate, quant)) * c.mm(x, up, quant), down, quant)
        return total + out * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"], routing.T),
    )
    return (total + c.swiglu(x, w["shared_experts"], quant)).reshape(shape)


def layer(x, w, cfg, segment_ids, is_gqa, quant=c.identity):
    eps = cfg["rms_norm_eps"]
    h = c.rms_norm(x, w["input_layernorm"]["weight"], eps)
    if is_gqa:
        x = x + gqa_block(h, w["self_attn"], cfg, segment_ids, quant)
    else:
        x = x + kda_block(h, w["linear_attn"], cfg, segment_ids, quant)
    h = c.rms_norm(x, w["post_attention_layernorm"]["weight"], eps)
    return x + moe_block(h, w["mlp"], cfg, quant)


def layer_is_gqa(cfg, index: int) -> bool:
    if cfg.get("gqa_layers") is not None:
        return index in cfg["gqa_layers"]
    return index % (cfg["gqa_interval"] + 1) == 0


@functools.cache
def _programs(cfg_text: str, quant):
    """The jitted pieces, once a configuration and precision: a check calls
    `logits` once for every four requests, and a new closure would be traced
    and compiled each time."""
    cfg = json.loads(cfg_text)

    @jax.jit
    def embed(table, ids):
        return table.astype(c.F32)[ids]

    def one_layer(is_gqa):
        return jax.jit(lambda x, w, seg: layer(
            x, jax.tree.map(lambda a: a.astype(c.F32), w), cfg, seg, is_gqa, quant))

    @jax.jit
    def head(x, norm_w, head_w):
        x = c.rms_norm(x, norm_w.astype(c.F32), cfg["rms_norm_eps"])
        return c.mm(x, head_w.astype(c.F32), quant)

    return embed, {True: one_layer(True), False: one_layer(False)}, head


def logits(params, cfg, input_ids, segment_ids, position_ids=None, quant=c.identity):
    """Full-sequence logits [B, S, V], one jitted layer at a time so that only
    one layer's float32 weights exist at once. `params` is the tree under
    'params' of what the benchmark's initialiser made: `layers/slot{j}`, each
    stacked over the periods of the layer pattern."""
    stack = params["layers"]
    period = len(stack)
    embed, kinds, head = _programs(json.dumps(cfg, sort_keys=True), quant)
    with c.exact():
        x = embed(params["embed_tokens"]["embedding"], input_ids)
        for index in range(cfg["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[index // period], stack[f"slot{index % period}"])
            x = kinds[layer_is_gqa(cfg, index)](x, w, segment_ids)
        return head(x, params["norm"]["weight"], params["lm_head"]["kernel"])
