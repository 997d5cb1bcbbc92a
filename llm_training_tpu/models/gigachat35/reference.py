"""Plain reference for GigaChat 3.5 (`model_type: gigachat3_5`,
ai-sage/GigaChat3.5-432B-A28B): the equations below in float32 `jax.numpy`.
No kernel, no cache, no chunks, no flax: latent attention in the NON-absorbed
form (full [S, S] softmax a head), the delta rule ONE TOKEN AT A TIME straight
from its recurrence, every held expert evaluated on every token and weighted
by the routing matrix. `logits`, `mtp_logits` and `loss` trace under
`jax.default_matmul_precision("highest")` themselves.

    N(x) = x / rms(x) * g sigmoid(w)            (g = layernorm_gating_weight)
    layer:  h = x + N2(mixer(N1 x));  y = h + N4(ffn(N3 h))
    MLA (a layer of full_attention_layers), DeepSeek-V3's:
          c_q = R(x W_qa); q = c_q W_qb, a head [q_nope | q_rope]
          [c | k_r] = x W_kva; c_kv = R(c); [k_nope | v] a head = c_kv W_kvb
          (R: a plain RMSNorm); yarn rotary, pairs (2i, 2i+1), on q_rope and
          the ONE k_r a token; scores (q_nope . k_nope + q_rope . k_r) *
          scale, causal inside a segment, softmax in float32;
          out = (concat_h(p v) * sigmoid(x W_gate)) W_o
    delta rule (every other layer), Qwen3-Next's Gated DeltaNet:
          [q | k | v] = silu(conv4(x W_qkv)), ONE depthwise causal
          convolution; q, k L2-normalised a KEY head, q / sqrt(dk), key head
          j serving value heads j r .. j r + r - 1 (r = value / key heads);
          beta = sigmoid(x W_b), alpha = exp(-exp(A_log) softplus(x W_a +
          dt_bias)) a value head;
          S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
          o_t = S_t^T q_t
          out = (N_o(o) a head * s sigmoid(x W_g)) W_o   (s = linear_sigmoid_gate_scale)
    ffn:  W_d(silu(min(x W_g, L)) * clip(x W_u, -L, L)), L = swiglu_limit, of
          `intermediate_size` on layers < first_k_dense_replace; on the
          others s = sigmoid(x W_r), the num_experts_per_tok largest of s +
          bias chosen, weights s_i / (sum of the chosen + 1e-20) *
          routed_scaling_factor, y = sum_i w_i E_i(x) + E_shared(x), every
          expert the same clamped SwiGLU
    MTP:  module k: h^k_i = W_eh [N_e(Emb(t_{i+k+1})) ; N_h(h^{k-1}_i)], h^{-1}
          the stack's output before the final norm; one MLA layer with a
          dense ffn; the model's final norm and head: logits at i for t_{i+k+2}
    loss: CE(logits_i, t_{i+1}) + lambda * mean_k CE(mtp^k_i, t_{i+k+2}), each
          a mean over the positions whose target lies in their own segment

`params` is the tree under 'params' of `GigaChat35.init`: `layers_{i}` for the
looped layers, `periods/slot{j}` with a leading axis over the scanned periods,
`mtp_{k}` for the modules. `cfg` is a mapping with the source's keys
(`benchmarks/configs/gigachat3.5-432b-a28b-ep16.json` is one) and may give
`experts_first`. The stacked expert weights hold the experts from
`experts_first` on: all of them, or a chip's share; what is held elsewhere
adds nothing, AFTER the weights were normalised over all the chosen.

Departures from the source, none that is known: what its keys do not settle
is listed as `assumed` in the configuration file and in docs/models.md.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def gated_norm(x, weight, eps, gating_weight):
    normed = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return normed * (gating_weight * jax.nn.sigmoid(weight))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def swiglu(x, w, limit):
    gate, up = x @ w["gate_proj"]["kernel"], x @ w["up_proj"]["kernel"]
    if limit is not None:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return (jax.nn.silu(gate) * up) @ w["down_proj"]["kernel"]


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None):
    """The rotary frequencies `[dim / 2]` (DeepSeek's yarn: interpolated by
    `factor` below the correction range, as they are above it, a ramp
    between) and the factor on cos and sin (mscale / mscale_all_dim: 1 as
    published)."""
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    if not scaling:
        return plain, 1.0
    factor, original = scaling["factor"], scaling["original_max_position_embeddings"]
    at = lambda turns: dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(at(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(at(scaling.get("beta_slow", 1))), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=F32) - low) / max(high - low, 0.001), 0, 1)
    mscale = lambda m: 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0
    on_tables = mscale(scaling.get("mscale", 1)) / mscale(scaling.get("mscale_all_dim", 1))
    return plain / factor * ramp + plain * (1 - ramp), on_tables


def attention_scale(cfg) -> float:
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    scaling = cfg.get("rope_scaling")
    if cfg.get("use_mla_scaling_factor", True) and scaling and scaling.get("mscale_all_dim"):
        scale *= (0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0) ** 2
    return scale


def rotate(x, positions, cfg):
    """Rotary positions on the last axis of x `[B, S, ..., D]`, pairs (2i, 2i+1)."""
    dim = x.shape[-1]
    inv_freq, on_tables = yarn_inv_freq(dim, cfg["rope_theta"], cfg.get("rope_scaling"))
    angles = positions.astype(F32)[..., None] * inv_freq  # [B, S, D/2]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + angles.shape[-1:])
    cos, sin = jnp.cos(angles) * on_tables, jnp.sin(angles) * on_tables
    first, second = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    ).reshape(x.shape)


def mla_block(z, w, cfg, segment_ids, position_ids):
    batch, seq, _ = z.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]

    c_q = rms_norm(z @ w["q_a_proj"]["kernel"], w["q_a_layernorm"]["weight"], eps)
    q = (c_q @ w["q_b_proj"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    compressed = z @ w["kv_a_proj_with_mqa"]["kernel"]
    c_kv = rms_norm(compressed[..., :latent], w["kv_a_layernorm"]["weight"], eps)
    kv = (c_kv @ w["kv_b_proj"]["kernel"]).reshape(batch, seq, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rotate(q[..., nope:], position_ids, cfg)
    k_rope = rotate(compressed[..., latent:], position_ids, cfg)

    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], k_nope)
        + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope)
    ) * attention_scale(cfg)
    idx = jnp.arange(seq)
    mask = (idx[:, None] >= idx[None, :])[None] & (
        (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    )
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhv->bqhv", probs, v).reshape(batch, seq, -1)
    if cfg.get("gated_attention", True):
        out = out * jax.nn.sigmoid(z @ w["gate_proj"]["kernel"])
    return out @ w["o_proj"]["kernel"]


def delta_rule(q, k, v, alpha, beta, starts):
    """The recurrence, a token at a time. q, k [B, S, H, dk]; v [B, S, H, dv];
    alpha, beta [B, S, H]; starts [B, S] bool (a packed document begins: zero
    state) -> out [B, S, H, dv]."""
    batch, _, heads, dk = q.shape

    def one_token(state, token):
        q_t, k_t, v_t, alpha_t, beta_t, start_t = token
        state = jnp.where(start_t[:, None, None, None], 0.0, state)
        state = alpha_t[..., None, None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + beta_t[..., None, None] * k_t[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    by_time = lambda a: jnp.moveaxis(a, 1, 0)
    _, out = jax.lax.scan(
        one_token, jnp.zeros((batch, heads, dk, v.shape[-1]), F32),
        tuple(by_time(a) for a in (q, k, v, alpha, beta, starts)),
    )
    return by_time(out)


def linear_block(x, w, cfg, segment_ids):
    batch, seq, _ = x.shape
    heads, key_heads = cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    valid = segment_ids > 0
    before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
    starts = valid & (segment_ids != before)
    seg_p = jnp.concatenate(
        [jnp.broadcast_to(segment_ids[:, :1], (batch, taps - 1)), segment_ids], axis=1
    )
    mixed = jnp.where(valid[..., None], x @ w["qkv_proj"]["kernel"], 0.0)
    padded = jnp.pad(mixed, ((0, 0), (taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(
        jnp.where((seg_p[:, i:i + seq] == segment_ids)[..., None], padded[:, i:i + seq], 0.0)
        * w["conv_kernel"][i]
        for i in range(taps)
    ))
    q, k, v = jnp.split(mixed, (key_heads * dk, 2 * key_heads * dk), axis=-1)
    q = l2_norm(q.reshape(batch, seq, key_heads, dk)) * dk ** -0.5
    k = l2_norm(k.reshape(batch, seq, key_heads, dk))
    # key head j serves value heads j r .. j r + r - 1
    q, k = (jnp.repeat(a, heads // key_heads, axis=2) for a in (q, k))
    v = v.reshape(batch, seq, heads, dv)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["a_proj"]["kernel"] + w["dt_bias"])
    beta = jax.nn.sigmoid(x @ w["b_proj"]["kernel"])
    alpha = jnp.where(valid[..., None], jnp.exp(g), 1.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    out = gated_norm(
        delta_rule(q, k, v, alpha, beta, starts), w["o_norm"]["weight"],
        cfg["linear_attn_o_norm_eps"], cfg["layernorm_gating_weight"],
    )
    gate = cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(x @ w["g_proj"]["kernel"])
    return (out.reshape(batch, seq, heads * dv) * gate) @ w["o_proj"]["kernel"]


def routing_matrix(x, w, cfg):
    """`[T, n_routed_experts]`: a token's weight for each expert it chose, 0 elsewhere."""
    scores = jax.nn.sigmoid(x @ w["gate_kernel"])
    _, chosen = jax.lax.top_k(scores + w["e_score_correction_bias"], cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)


def moe_block(u, w, cfg):
    shape = u.shape
    x = u.reshape(-1, shape[-1])
    limit = cfg.get("swiglu_limit")
    routing = routing_matrix(x, w, cfg)
    first, held = cfg.get("experts_first", 0), w["experts_gate_proj"].shape[0]
    experts = jax.vmap(
        lambda gate, up, down: swiglu(
            x, {"gate_proj": {"kernel": gate}, "up_proj": {"kernel": up}, "down_proj": {"kernel": down}},
            limit,
        )
    )(w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"])  # [E, T, H]
    routed = jnp.einsum("eth,te->th", experts, routing[:, first:first + held])
    return (routed + swiglu(x, w["shared_experts"], limit)).reshape(shape)


def layer(x, w, cfg, segment_ids, position_ids):
    """One layer; its kinds are read off its weights."""
    norm = lambda name, h: gated_norm(
        h, w[name]["weight"], cfg["rms_norm_eps"], cfg["layernorm_gating_weight"]
    )
    z = norm("input_layernorm", x)
    if "self_attn" in w:
        mixed = mla_block(z, w["self_attn"], cfg, segment_ids, position_ids)
    else:
        mixed = linear_block(z, w["linear_attn"], cfg, segment_ids)
    h = x + norm("post_attention_layernorm", mixed)
    u = norm("pre_mlp_layernorm", h)
    if "gate_kernel" in w["mlp"]:
        out = moe_block(u, w["mlp"], cfg)
    else:
        out = swiglu(u, w["mlp"], cfg.get("swiglu_limit"))
    return h + norm("post_mlp_layernorm", out)


def layer_weights(params, index: int):
    """Layer `index`'s float32 weights out of the program's tree: a looped
    layer, or its slice of the scanned periods."""
    if f"layers_{index}" in params:
        w = params[f"layers_{index}"]
    else:
        before = sum(1 for i in range(index) if f"layers_{i}" in params)
        period = len(params["periods"])
        at = index - before
        w = jax.tree.map(lambda a: a[at // period], params["periods"][f"slot{at % period}"])
    return jax.tree.map(lambda a: a.astype(F32), w)


def _default_positions(input_ids, position_ids):
    if position_ids is None:
        return jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    return position_ids


def stack_output(params, cfg, input_ids, segment_ids, position_ids):
    """The last layer's output `[B, S, hidden]`, before the final norm."""
    one_layer = jax.jit(lambda x, w, seg, pos: layer(x, w, cfg, seg, pos))
    x = params["embed_tokens"]["embedding"].astype(F32)[input_ids]
    for index in range(cfg["num_hidden_layers"]):
        x = one_layer(x, layer_weights(params, index), segment_ids, position_ids)
    return x


def head(params, cfg, x):
    x = gated_norm(
        x, params["norm"]["weight"].astype(F32), cfg["rms_norm_eps"], cfg["layernorm_gating_weight"]
    )
    return x @ params["lm_head"]["kernel"].astype(F32)


def mtp_outputs(params, cfg, x, input_ids, segment_ids, position_ids):
    """The modules' outputs before the final norm, chained from the stack's `x`."""
    table = params["embed_tokens"]["embedding"].astype(F32)
    eps, g = cfg["rms_norm_eps"], cfg["layernorm_gating_weight"]
    outs = []
    for k in range(cfg["num_nextn_predict_layers"]):
        w = jax.tree.map(lambda a: a.astype(F32), params[f"mtp_{k}"])
        following = table[jnp.roll(input_ids, -(k + 1), axis=1)]
        joined = jnp.concatenate([
            gated_norm(following, w["enorm"]["weight"], eps, g),
            gated_norm(x, w["hnorm"]["weight"], eps, g),
        ], axis=-1)
        x = layer(joined @ w["eh_proj"]["kernel"], w["layer"], cfg, segment_ids, position_ids)
        outs.append(x)
    return outs


def logits(params, cfg, input_ids, segment_ids, position_ids=None):
    """Full-sequence logits [B, S, V], one jitted layer at a time."""
    position_ids = _default_positions(input_ids, position_ids)
    with jax.default_matmul_precision("highest"):
        return head(params, cfg, stack_output(params, cfg, input_ids, segment_ids, position_ids))


def mtp_logits(params, cfg, input_ids, segment_ids, position_ids=None):
    """`(logits, [module k's logits])`, each [B, S, V]: module k's row i is
    for the token at i + k + 2 (a row's and a segment's last k + 1 rows read
    tokens that are not theirs: nothing may use them)."""
    position_ids = _default_positions(input_ids, position_ids)
    with jax.default_matmul_precision("highest"):
        x = stack_output(params, cfg, input_ids, segment_ids, position_ids)
        ahead = mtp_outputs(params, cfg, x, input_ids, segment_ids, position_ids)
        return head(params, cfg, x), [head(params, cfg, a) for a in ahead]


def targets(input_ids, segment_ids, ahead: int):
    """Position i predicts token i + `ahead` when both lie in one segment."""
    shift = lambda a: jnp.concatenate([a[:, ahead:], jnp.zeros_like(a[:, :ahead])], axis=1)
    return shift(input_ids), (segment_ids > 0) & (segment_ids == shift(segment_ids))


def cross_entropy(logits, labels, valid):
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(jnp.sum(valid), 1)


def loss(params, cfg, input_ids, segment_ids, position_ids=None, mtp_weight=0.3):
    """`(CE + mtp_weight * mean_k CE_k, (CE, [CE_k]))`; differentiable in `params`."""
    main, ahead = mtp_logits(params, cfg, input_ids, segment_ids, position_ids)
    with jax.default_matmul_precision("highest"):
        ce = cross_entropy(main, *targets(input_ids, segment_ids, 1))
        ce_mtp = [
            cross_entropy(a, *targets(input_ids, segment_ids, k + 2)) for k, a in enumerate(ahead)
        ]
    return ce + mtp_weight * sum(ce_mtp) / max(len(ce_mtp), 1), (ce, ce_mtp)
