"""Plain reference for AFMoE (Trinity-Mini): the layer equations of
`model.py`'s docstring in float32 `jax.numpy`, straight from the published
description. No kernel, no cache, no chunking, no flax: every attention layer
builds its full [S, S] scores (a sliding layer masks them to its window), and
every held expert is evaluated on every token and weighted by the routing
matrix. `logits` traces under `jax.default_matmul_precision("highest")`.

`params` is the tree under 'params' of `Afmoe.init`: `front/slot{j}/...` the
looped layers in front, `layers/slot{j}/...` the scanned periods with a
leading axis over them (absent where the whole stack is looped). `cfg` is a
mapping with the published keys (`benchmarks/configs/trinity-mini-ep8.json`
is one): the experts HELD are the stacked weights' leading axis, the router's
outputs its kernel's, `experts_first` the first one held.

`benchmarks/references/afmoe.py` is the benchmark's copy of these equations,
computed in blocks so that 12,800 tokens fit (it may import nothing from the
program); `tests/test_afmoe.py` holds the two equal.

Departures from the published description, none: what its keys do not give is
listed as `assumed` in the configuration file and in docs/models.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rotate(x, positions, theta):
    """Rotary positions on x [B, S, heads, D]; pairs (i, i + D/2) rotate together."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=F32) / dim))
    angles = positions.astype(F32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)[:, :, None, :]
    turned = jnp.concatenate([-x[..., dim // 2:], x[..., : dim // 2]], axis=-1)
    return x * jnp.cos(angles) + turned * jnp.sin(angles)


def swiglu(x, w):
    gate, up, down = (w[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def attention_block(x, w, cfg, segment_ids, position_ids, is_window: bool):
    """Gated causal softmax attention, an RMSNorm a q and k head; a sliding
    layer rotates q and k and sees `sliding_window` positions, itself
    included; a full layer has no positional term and sees everything."""
    batch, seq, _ = x.shape
    heads, kv_heads, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm((x @ w["q_proj"]["kernel"]).reshape(batch, seq, heads, dim), w["q_norm"]["weight"], eps)
    k = rms_norm((x @ w["k_proj"]["kernel"]).reshape(batch, seq, kv_heads, dim), w["k_norm"]["weight"], eps)
    v = (x @ w["v_proj"]["kernel"]).reshape(batch, seq, kv_heads, dim)
    if is_window:
        q = rotate(q, position_ids, cfg["rope_theta"])
        k = rotate(k, position_ids, cfg["rope_theta"])
    q = q.reshape(batch, seq, kv_heads, heads // kv_heads, dim)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k) * dim ** -0.5
    index = jnp.arange(seq)
    back = index[:, None] - index[None, :]
    seen = back >= 0
    if is_window:
        seen &= back < cfg["sliding_window"]
    same = (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    probs = jax.nn.softmax(jnp.where((seen[None] & same)[:, None, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(batch, seq, heads * dim)
    return (out * jax.nn.sigmoid(x @ w["gate_proj"]["kernel"])) @ w["o_proj"]["kernel"]


def moe_block(x, w, cfg):
    """sigmoid scores over ALL the router's experts, the top k of score +
    bias, weights normalised over the chosen k and scaled; the experts held
    here (the stacked weights' leading axis) each evaluated on every token;
    what is held elsewhere adds nothing; the shared expert adds to every
    token."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    scores = jax.nn.sigmoid(x @ w["gate_kernel"])
    _, chosen = jax.lax.top_k(scores + w["e_score_correction_bias"], cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if cfg["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg["route_scale"]
    routing = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    held = w["experts_gate_proj"].shape[0]
    first = cfg.get("experts_first", 0)
    routing = routing[:, first:first + held]

    def one_expert(total, expert):
        gate, up, down, weight = expert
        return total + ((jax.nn.silu(x @ gate) * (x @ up)) @ down) * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"], routing.T),
    )
    return (total + swiglu(x, w["shared_experts"])).reshape(shape)


def layer(x, w, cfg, segment_ids, position_ids, is_window: bool, is_moe: bool):
    norm = lambda name, y: rms_norm(y, w[name]["weight"], cfg["rms_norm_eps"])
    attn = attention_block(
        norm("input_layernorm", x), w["self_attn"], cfg, segment_ids, position_ids, is_window
    )
    x = x + norm("post_attention_layernorm", attn)
    h = norm("pre_mlp_layernorm", x)
    mlp = moe_block(h, w["mlp"], cfg) if is_moe else swiglu(h, w["mlp"])
    return x + norm("post_mlp_layernorm", mlp)


def layer_weights(params, index: int):
    """Layer `index`'s weights out of the program's tree: a looped layer in
    front, or its slot of the scanned periods at its period's index."""
    front = len(params["front"])
    if index < front:
        return params["front"][f"slot{index}"]
    period = len(params["layers"])
    at = (index - front) // period
    return jax.tree.map(lambda a: a[at], params["layers"][f"slot{(index - front) % period}"])


def layer_is_window(cfg, index: int) -> bool:
    if cfg.get("layer_types") is not None:
        return cfg["layer_types"][index] == "sliding_attention"
    return (index + 1) % cfg["global_attn_every_n_layers"] != 0


def logits(params, cfg, input_ids, segment_ids, position_ids=None):
    """Full-sequence logits [B, S, V], one jitted layer at a time."""
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    one_layer = jax.jit(
        lambda x, w, seg, pos, is_window, is_moe: layer(x, w, cfg, seg, pos, is_window, is_moe),
        static_argnums=(4, 5),
    )
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"]["embedding"].astype(F32)[input_ids]
        if cfg["mup_enabled"]:
            x = x * cfg["hidden_size"] ** 0.5
        for index in range(cfg["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a.astype(F32), layer_weights(params, index))
            x = one_layer(
                x, w, segment_ids, position_ids,
                layer_is_window(cfg, index), index >= cfg["num_dense_layers"],
            )
        x = rms_norm(x, params["norm"]["weight"].astype(F32), cfg["rms_norm_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)
