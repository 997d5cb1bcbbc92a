"""Plain reference for Olmo-Hybrid: the layer equations of `model.py`'s
docstring in float32 `jax.numpy`. No kernel, no cache, no chunking, no flax:
the delta rule's state is advanced ONE TOKEN AT A TIME with `lax.scan`, the
convolutions are left-padded, the softmax attention builds its full [S, S]
scores. `logits` traces under `jax.default_matmul_precision("highest")`.

`params` is the tree under 'params' of `OlmoHybrid.init` with
`scan_layers=True` (`layers/slot{j}/...`, a leading axis over periods);
`cfg` is a mapping with the published keys (`benchmarks/configs/
olmo-hybrid-7b.json` is one).

`benchmarks/references/olmo_hybrid.py` is the benchmark's copy of this file
(it may import nothing from the program); `tests/test_olmo_hybrid.py` holds
the two equal.

Departures from the published description, none known: what the source's
keys do not settle is listed as `assumed` in the configuration file and in
docs/models.md.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def delta_rule(q, k, v, alpha, beta, starts, state=None):
    """S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T,
    o_t = S_t^T q_t, a token at a time. q, k [B, S, H, dk]; v [B, S, H, dv];
    alpha, beta [B, S, H]; starts [B, S] bool (a packed document begins: zero
    state) -> (out [B, S, H, dv], the last state [B, H, dk, dv])."""
    batch, _, heads, dk = q.shape
    if state is None:
        state = jnp.zeros((batch, heads, dk, v.shape[-1]), F32)

    def one_token(state, token):
        q_t, k_t, v_t, alpha_t, beta_t, start_t = token
        state = jnp.where(start_t[:, None, None, None], 0.0, state)
        state = alpha_t[..., None, None] * state
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + beta_t[..., None, None] * k_t[..., None] * (v_t - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    by_time = lambda a: jnp.moveaxis(a, 1, 0)
    state, out = jax.lax.scan(
        one_token, state, tuple(by_time(a) for a in (q, k, v, alpha, beta, starts))
    )
    return by_time(out), state


def linear_block(x, w, cfg, segment_ids):
    batch, seq, _ = x.shape
    heads, dk, dv = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width_k = cfg["linear_conv_kernel_dim"]
    valid = segment_ids > 0
    before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
    starts = valid & (segment_ids != before)
    seg_p = jnp.concatenate(
        [jnp.broadcast_to(segment_ids[:, :1], (batch, width_k - 1)), segment_ids], axis=1
    )

    def conv_silu(name):
        mixed = jnp.where(valid[..., None], x @ w[f"{name}_proj"]["kernel"], 0.0)
        padded = jnp.pad(mixed, ((0, 0), (width_k - 1, 0), (0, 0)))
        return jax.nn.silu(sum(
            jnp.where((seg_p[:, i:i + seq] == segment_ids)[..., None], padded[:, i:i + seq], 0.0)
            * w[f"{name}_conv_kernel"][i]
            for i in range(width_k)
        ))

    q = l2_norm(conv_silu("q").reshape(batch, seq, heads, dk)) * dk ** -0.5
    k = l2_norm(conv_silu("k").reshape(batch, seq, heads, dk))
    v = conv_silu("v").reshape(batch, seq, heads, dv)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(x @ w["a_proj"]["kernel"] + w["dt_bias"])
    strength = 2.0 if cfg["linear_allow_neg_eigval"] else 1.0
    beta = strength * jax.nn.sigmoid(x @ w["b_proj"]["kernel"])
    alpha = jnp.where(valid[..., None], jnp.exp(g), 1.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    out, _ = delta_rule(q, k, v, alpha, beta, starts)
    out = rms_norm(out, w["o_norm"]["weight"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(x @ w["g_proj"]["kernel"])
    return (out.reshape(batch, seq, heads * dv) * gate) @ w["o_proj"]["kernel"]


def full_block(x, w, cfg, segment_ids):
    batch, seq, _ = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    eps = cfg["rms_norm_eps"]
    q = rms_norm(x @ w["q_proj"]["kernel"], w["q_norm"]["weight"], eps)
    k = rms_norm(x @ w["k_proj"]["kernel"], w["k_norm"]["weight"], eps)
    v = x @ w["v_proj"]["kernel"]
    q = q.reshape(batch, seq, kv_heads, heads // kv_heads, dim)
    k, v = (a.reshape(batch, seq, kv_heads, dim) for a in (k, v))
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / jnp.sqrt(F32(dim))
    idx = jnp.arange(seq)
    same = (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    mask = (idx[:, None] >= idx[None, :])[None] & same  # no positional term: only the causal order
    probs = jax.nn.softmax(jnp.where(mask[:, None, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(batch, seq, heads * dim)
    return out @ w["o_proj"]["kernel"]


def swiglu(x, w):
    gate, up, down = (w[n]["kernel"] for n in ("gate_proj", "up_proj", "down_proj"))
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def layer(x, w, cfg, segment_ids, is_full: bool):
    eps = cfg["rms_norm_eps"]
    if is_full:
        mixed = full_block(x, w["self_attn"], cfg, segment_ids)
    else:
        mixed = linear_block(x, w["linear_attn"], cfg, segment_ids)
    x = x + rms_norm(mixed, w["post_attention_layernorm"]["weight"], eps)
    return x + rms_norm(swiglu(x, w["mlp"]), w["post_feedforward_layernorm"]["weight"], eps)


def layer_is_full(cfg, index: int) -> bool:
    if cfg.get("layer_types") is not None:
        return cfg["layer_types"][index] == "full_attention"
    return index % 4 == 3


def logits(params, cfg, input_ids, segment_ids):
    """Full-sequence logits [B, S, V] in float32."""
    stack = params["layers"]
    period = len(stack)
    f32 = lambda tree: jax.tree.map(lambda a: jnp.asarray(a, F32), tree)

    @jax.jit
    def run(params, input_ids, segment_ids):
        x = f32(params["embed_tokens"]["embedding"])[input_ids]
        for index in range(cfg["num_hidden_layers"]):
            w = f32(jax.tree.map(lambda a: a[index // period], stack[f"slot{index % period}"]))
            x = layer(x, w, cfg, segment_ids, layer_is_full(cfg, index))
        x = rms_norm(x, f32(params["norm"]["weight"]), cfg["rms_norm_eps"])
        return x @ f32(params["lm_head"]["kernel"])

    with jax.default_matmul_precision("highest"):
        return run(params, input_ids, segment_ids)
