"""Plain reference for Olmo-Hybrid (allenai/Olmo-Hybrid-7B, `model_type:
olmo_hybrid`): the benchmark's copy of `llm_training_tpu/models/olmo_hybrid/
reference.py` (one tier-1 test holds the two equal), importing nothing from
the program.

Every layer is `h = x + RMSNorm(mixer(x)); y = h + RMSNorm(swiglu(h))`: the
norm sits on a sub-block's OUTPUT. The mixer of a `full_attention` layer is
causal multi-head softmax attention with an RMSNorm over the whole q and the
whole k projection and no positional term at all; of a `linear_attention`
layer a gated delta rule, its [96, 192] state a head advanced ONE TOKEN AT A
TIME straight from

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T,  o_t = S_t^T q_t

(no chunks, no cache, no conv tail: a left-padded convolution). Positions of
segment 0 change nothing; `position_ids` is read by nothing."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.references import _common as c


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


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
        one_token, jnp.zeros((batch, heads, dk, v.shape[-1]), c.F32),
        tuple(by_time(a) for a in (q, k, v, alpha, beta, starts)),
    )
    return by_time(out)


def linear_block(x, w, cfg, segment_ids, quant=c.identity):
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
        mixed = jnp.where(valid[..., None], c.mm(x, w[f"{name}_proj"]["kernel"], quant), 0.0)
        padded = jnp.pad(mixed, ((0, 0), (width_k - 1, 0), (0, 0)))
        return jax.nn.silu(sum(
            jnp.where((seg_p[:, i:i + seq] == segment_ids)[..., None], padded[:, i:i + seq], 0.0)
            * w[f"{name}_conv_kernel"][i]
            for i in range(width_k)
        ))

    q = l2_norm(conv_silu("q").reshape(batch, seq, heads, dk)) * dk ** -0.5
    k = l2_norm(conv_silu("k").reshape(batch, seq, heads, dk))
    v = conv_silu("v").reshape(batch, seq, heads, dv)
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(c.mm(x, w["a_proj"]["kernel"], quant) + w["dt_bias"])
    strength = 2.0 if cfg["linear_allow_neg_eigval"] else 1.0
    beta = strength * jax.nn.sigmoid(c.mm(x, w["b_proj"]["kernel"], quant))
    alpha = jnp.where(valid[..., None], jnp.exp(g), 1.0)
    beta = jnp.where(valid[..., None], beta, 0.0)
    out = c.rms_norm(delta_rule(q, k, v, alpha, beta, starts), w["o_norm"]["weight"], cfg["rms_norm_eps"])
    gate = jax.nn.silu(c.mm(x, w["g_proj"]["kernel"], quant))
    return c.mm(out.reshape(batch, seq, heads * dv) * gate, w["o_proj"]["kernel"], quant)


def full_block(x, w, cfg, segment_ids, quant=c.identity):
    batch, seq, _ = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    eps = cfg["rms_norm_eps"]
    q = c.rms_norm(c.mm(x, w["q_proj"]["kernel"], quant), w["q_norm"]["weight"], eps)
    k = c.rms_norm(c.mm(x, w["k_proj"]["kernel"], quant), w["k_norm"]["weight"], eps)
    v = c.mm(x, w["v_proj"]["kernel"], quant)
    out = c.attention(  # [S, S] scores of one key/value head at a time
        q.reshape(batch, seq, heads, dim), k.reshape(batch, seq, kv_heads, dim),
        v.reshape(batch, seq, kv_heads, dim), segment_ids, None, quant,
    )
    return c.mm(out, w["o_proj"]["kernel"], quant)


def layer(x, w, cfg, segment_ids, is_full, quant=c.identity):
    eps = cfg["rms_norm_eps"]
    if is_full:
        mixed = full_block(x, w["self_attn"], cfg, segment_ids, quant)
    else:
        mixed = linear_block(x, w["linear_attn"], cfg, segment_ids, quant)
    x = x + c.rms_norm(mixed, w["post_attention_layernorm"]["weight"], eps)
    return x + c.rms_norm(c.swiglu(x, w["mlp"], quant), w["post_feedforward_layernorm"]["weight"], eps)


def layer_is_full(cfg, index: int) -> bool:
    if cfg.get("layer_types") is not None:
        return cfg["layer_types"][index] == "full_attention"
    return index % 4 == 3


@functools.cache
def _programs(cfg_text: str, quant):
    """The jitted pieces, once a configuration and precision: a check calls
    `logits` once for every four requests, and a new closure would be traced
    and compiled each time."""
    cfg = json.loads(cfg_text)

    @jax.jit
    def embed(table, ids):
        return table.astype(c.F32)[ids]

    def one_layer(is_full):
        return jax.jit(lambda x, w, seg: layer(
            x, jax.tree.map(lambda a: a.astype(c.F32), w), cfg, seg, is_full, quant))

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
            x = kinds[layer_is_full(cfg, index)](x, w, segment_ids)
        return head(x, params["norm"]["weight"], params["lm_head"]["kernel"])
