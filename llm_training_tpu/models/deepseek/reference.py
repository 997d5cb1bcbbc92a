"""Plain reference for the `Deepseek` stack as openPangu-Ultra-MoE runs it
(`model_type: pangu_ultra_moe`; DeepSeek-V3's graph without expert groups,
with or without `sandwich_norm`): the equations below in float32
`jax.numpy`. No kernel, no cache, no chunking, no flax: latent attention in
the NON-absorbed form (every token's latent goes through `W_kvb` to the
heads' keys and values, full [S, S] softmax a head), every held expert
evaluated on every token and weighted by the routing matrix. Callers trace it
under `jax.default_matmul_precision("highest")` (`logits`, `mtp_logits` and
`loss` do so themselves).

    layer, sandwiched:  a = N_pa(MLA(N_in x));  h = x + a
                        m = N_pm(MLP(N_pre h)); y = h + m
    layer, pre-norm:    h = x + MLA(N_in x);    y = h + MLP(N_pa h)
    MLA:  c_q = N(x W_qa); q = c_q W_qb, a head [q_nope | q_rope]
          [c | k_r] = x W_kva; c_kv = N(c); [k_nope | v] a head = c_kv W_kvb
          rotary on q_rope and the ONE k_r a token
          scores (q_nope . k_nope + q_rope . k_r) * scale, causal inside a
          segment, softmax in float32; out = concat_h(p v) W_o
    MLP:  SwiGLU of `intermediate_size` on layers < first_k_dense_replace;
          on the others s = sigmoid(x W_g), the num_experts_per_tok largest
          of s + bias chosen, weights s_i / (sum of the chosen + 1e-20) *
          routed_scaling_factor, y = sum_i w_i E_i(x) + E_shared(x)
    MTP:  h'_i = W_eh [N_e(Emb(t_{i+1})) ; N_h(h_i)], h_i the stack's output
          before the final norm; one more layer (of the stack's last kind);
          the model's final norm and head: logits at i for t_{i+2}
    loss: CE(logits_i, t_{i+1}) + lambda * CE(mtp_logits_i, t_{i+2}), each a
          mean over the positions whose target lies in their own segment

`params` is the tree under 'params' of `Deepseek.init`: `layers_{i}` for the
looped layers, `moe_layers/layer` with a leading axis for the scanned suffix,
`mtp_0` for the module. `cfg` is a mapping with the source's keys
(`benchmarks/configs/openpangu-ultra-moe-718b-ep32.json` is one), and may
give `rope_interleave` (default true: pairs (2i, 2i+1)), `experts_first` and
`attention_scale` (default `1 / sqrt(nope + rope)`). The stacked expert
weights hold the experts from `experts_first` on: all of them, or a chip's
share; what is held elsewhere adds nothing, AFTER the weights were
normalised over all the chosen.

What this file does not cover: expert groups (`n_group`), version 2's
softmax router, yarn. `tests/test_deepseek.py` holds those to the
HuggingFace modules.

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


def rotate(x, positions, theta, interleaved=True):
    """Rotary positions on the last axis of x `[B, S, ..., D]`: pairs (2i,
    2i+1), or (i, i + D/2) with `interleaved` false."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    angles = positions.astype(F32)[..., None] * inv_freq  # [B, S, D/2]
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + angles.shape[-1:])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if interleaved:
        first, second = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [first * cos - second * sin, second * cos + first * sin], axis=-1
        ).reshape(x.shape)
    first, second = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def mla_block(z, w, cfg, segment_ids, position_ids):
    batch, seq, _ = z.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    theta, pairs = cfg["rope_theta"], cfg.get("rope_interleave", True)

    c_q = rms_norm(z @ w["q_a_proj"]["kernel"], w["q_a_layernorm"]["weight"], eps)
    q = (c_q @ w["q_b_proj"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    compressed = z @ w["kv_a_proj_with_mqa"]["kernel"]
    c_kv = rms_norm(compressed[..., :latent], w["kv_a_layernorm"]["weight"], eps)
    kv = (c_kv @ w["kv_b_proj"]["kernel"]).reshape(batch, seq, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rotate(q[..., nope:], position_ids, theta, pairs)
    k_rope = rotate(compressed[..., latent:], position_ids, theta, pairs)

    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], k_nope)
        + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope)
    ) * cfg.get("attention_scale", (nope + rope) ** -0.5)
    idx = jnp.arange(seq)
    mask = (idx[:, None] >= idx[None, :])[None] & (
        (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    )
    probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bhqk,bkhv->bqhv", probs, v).reshape(batch, seq, -1)
    return out @ w["o_proj"]["kernel"]


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
    routing = routing_matrix(x, w, cfg)
    first, held = cfg.get("experts_first", 0), w["experts_gate_proj"].shape[0]
    experts = jax.vmap(
        lambda gate, up, down: (jax.nn.silu(x @ gate) * (x @ up)) @ down
    )(w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"])  # [E, T, H]
    routed = jnp.einsum("eth,te->th", experts, routing[:, first:first + held])
    return (routed + swiglu(x, w["shared_experts"])).reshape(shape)


def layer(x, w, cfg, segment_ids, position_ids):
    eps = cfg["rms_norm_eps"]
    norm = lambda name, h: rms_norm(h, w[name]["weight"], eps)
    mlp = moe_block if "gate_kernel" in w["mlp"] else lambda u, w_mlp, _: swiglu(u, w_mlp)
    attn = mla_block(norm("input_layernorm", x), w["self_attn"], cfg, segment_ids, position_ids)
    if cfg.get("sandwich_norm", False):
        h = x + norm("post_attention_layernorm", attn)
        return h + norm("post_mlp_layernorm", mlp(norm("pre_mlp_layernorm", h), w["mlp"], cfg))
    h = x + attn
    return h + mlp(norm("post_attention_layernorm", h), w["mlp"], cfg)


def layer_weights(params, index: int):
    """Layer `index`'s float32 weights out of the program's tree: a looped
    layer, or its slice of the scanned suffix."""
    if f"layers_{index}" in params:
        w = params[f"layers_{index}"]
    else:
        looped = sum(1 for name in params if name.startswith("layers_"))
        w = jax.tree.map(lambda a: a[index - looped], params["moe_layers"]["layer"])
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
    x = rms_norm(x, params["norm"]["weight"].astype(F32), cfg["rms_norm_eps"])
    if "lm_head" in params:
        return x @ params["lm_head"]["kernel"].astype(F32)
    return x @ params["embed_tokens"]["embedding"].astype(F32).T


def mtp_output(params, cfg, x, input_ids, segment_ids, position_ids):
    """The module's output before the final norm, from the stack's `x`."""
    w = jax.tree.map(lambda a: a.astype(F32), params["mtp_0"])
    eps = cfg["rms_norm_eps"]
    following = params["embed_tokens"]["embedding"].astype(F32)[jnp.roll(input_ids, -1, axis=1)]
    joined = jnp.concatenate([
        rms_norm(following, w["enorm"]["weight"], eps), rms_norm(x, w["hnorm"]["weight"], eps),
    ], axis=-1)
    return layer(joined @ w["eh_proj"]["kernel"], w["layer"], cfg, segment_ids, position_ids)


def logits(params, cfg, input_ids, segment_ids, position_ids=None):
    """Full-sequence logits [B, S, V], one jitted layer at a time."""
    position_ids = _default_positions(input_ids, position_ids)
    with jax.default_matmul_precision("highest"):
        return head(params, cfg, stack_output(params, cfg, input_ids, segment_ids, position_ids))


def mtp_logits(params, cfg, input_ids, segment_ids, position_ids=None):
    """`(logits, the module's logits)`, both [B, S, V]: the second's row i is
    for the token at i + 2 (its last row, and a segment's last, read a token
    that is not theirs: nothing may use them)."""
    position_ids = _default_positions(input_ids, position_ids)
    with jax.default_matmul_precision("highest"):
        x = stack_output(params, cfg, input_ids, segment_ids, position_ids)
        ahead = mtp_output(params, cfg, x, input_ids, segment_ids, position_ids)
        return head(params, cfg, x), head(params, cfg, ahead)


def targets(input_ids, segment_ids, ahead: int):
    """Position i predicts token i + `ahead` when both lie in one segment."""
    shift = lambda a: jnp.concatenate([a[:, ahead:], jnp.zeros_like(a[:, :ahead])], axis=1)
    return shift(input_ids), (segment_ids > 0) & (segment_ids == shift(segment_ids))


def cross_entropy(logits, labels, valid):
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(jnp.sum(valid), 1)


def loss(params, cfg, input_ids, segment_ids, position_ids=None, mtp_weight=0.3):
    """`(CE + mtp_weight * CE_mtp, (CE, CE_mtp))`; differentiable in `params`."""
    main, ahead = mtp_logits(params, cfg, input_ids, segment_ids, position_ids)
    with jax.default_matmul_precision("highest"):
        ce = cross_entropy(main, *targets(input_ids, segment_ids, 1))
        ce_mtp = cross_entropy(ahead, *targets(input_ids, segment_ids, 2))
    return ce + mtp_weight * ce_mtp, (ce, ce_mtp)
