"""Plain reference for Phi-4-mini-flash-reasoning (`model_type: phi4flash`,
arXiv:2507.06607): the layer equations in float32 `jax.numpy`. No kernel, no
cache, no chunking, no flax, nothing imported from the program or the
benchmark: Mamba's state is advanced ONE TOKEN AT A TIME with `lax.scan`, its
convolution is left-padded, and differential attention is two dense masked
softmaxes over `[S, S]` scores, subtracted. Everything traces under
`jax.default_matmul_precision("highest")`.

`benchmarks/references/phi4flash.py` is this file, byte for byte (the
benchmark may import nothing from the program; `benchmarks/tests/
test_phi4flash.py` holds the two equal).

With `L` layers, `i` from 0, every layer is `h = x + mixer_i(LN(x)); y = h +
MLP(LN(h))` (LayerNorm with weight and bias, `MLP(x) = W_d (silu(W_g x) *
W_u x)`), then `logits = Emb^T LN_f y`; no layer has a positional term, so
`position_ids` is read by nothing. The mixer:

- `i` even, `i <= L/2` (Mamba-1): `[x, z] = W_in u`; `x = silu(conv4(x) +
  b_c)`; `[dt, B, C] = W_x x`; `delta = softplus(W_dt dt + b_dt)`; `A =
  -exp(A_log)`; `s_t = exp(delta_t A) * s_{t-1} + (delta_t x_t) B_t^T`;
  `y_t = s_t C_t + D x_t`; out `W_out (y * silu(z))`. Layer `L/2`'s `y` is
  the memory `m`;
- `i` odd, `i < L/2`: differential attention over a window (a query sees
  itself and the `sliding_window - 1` positions before it): `[q, k, v] = W u
  + b`; query heads `(2j, 2j + 1)` are `q1_j, q2_j`, key heads `k1, k2`,
  value heads `v1, v2`; `a_r = softmax(q_r k_r^T / sqrt(d)) [v1 ; v2]`; `o_j
  = (1 - l_i) RMSNorm(a_1 - lambda a_2)`, `lambda = exp(lq1 . lk1) - exp(lq2
  . lk2) + l_i`, `l_i = 0.8 - 0.6 exp(-0.3 i)`; out `W_o o + b`;
- `i = L/2 + 1`: the same with no window;
- `i` even, `i >= L/2 + 2`: `W_out (silu(W_in u) * m)`;
- `i` odd, `i >= L/2 + 3`: the same attention with `q = W_q u + b` only,
  over layer `L/2 + 1`'s keys and values, no window.

Positions of segment 0 change nothing; a packed document starts from a zero
state, its own convolution window and its own attention.

Departures from the publisher's modelling code, as far as this repo knows it
(written from memory, no network here): the fused `Wqkv` and `fc1`
projections are separate matrices (`q_proj`, `k_proj`, `v_proj`; `gate_proj`,
`up_proj`), the same products; dropout (`embd_pdrop`, `resid_pdrop`: 0) is
left out. What the source's keys do not settle is listed as `assumed` in the
benchmark's configuration file and in docs/models.md.

`params` is the tree under 'params' of `Phi4Flash.init` (`self_decoder/
slot{0,1}` and `cross_decoder/slot{0,1}` with a leading axis over periods,
`between/slot{0,1}` without); `cfg` is a mapping with the published keys
(`benchmarks/configs/phi4-mini-flash-reasoning.json` is one). `quant` is a
control's hook: the identity here, a round trip through a lower precision on
both operands of every matrix product in a control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def identity(x):
    return x


def mm(x, w, quant=identity):
    return jnp.matmul(quant(x), quant(w))


def layer_norm(x, w, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w["weight"] + w["bias"]


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def swiglu(x, w, quant=identity):
    gate, up = mm(x, w["gate_proj"]["kernel"], quant), mm(x, w["up_proj"]["kernel"], quant)
    return mm(jax.nn.silu(gate) * up, w["down_proj"]["kernel"], quant)


def layer_kinds(cfg) -> list[str]:
    layers, half = cfg["num_hidden_layers"], cfg["num_hidden_layers"] // 2
    first = ["mamba" if i % cfg["mb_per_layer"] == 0 else "window" for i in range(half)]
    second = ["gmu" if i % 2 == 0 else "cross" for i in range(half + 2, layers)]
    return first + ["memory", "full"] + second


def mamba(x, w, cfg, segment_ids, quant=identity):
    """-> (the mixer's output, y: the scan's output with the D term, before
    the gate)."""
    batch, seq, hidden = x.shape
    n, taps = cfg.get("mamba_d_state", 16), cfg.get("mamba_d_conv", 4)
    rank = cfg.get("mamba_dt_rank") or math.ceil(hidden / 16)
    valid = segment_ids > 0
    before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
    starts = valid & (segment_ids != before)
    xs, z = jnp.split(mm(x, w["in_proj"]["kernel"], quant), 2, axis=-1)
    xs = jnp.where(valid[..., None], xs, 0.0)
    padded = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
    seg_p = jnp.concatenate(
        [jnp.broadcast_to(segment_ids[:, :1], (batch, taps - 1)), segment_ids], axis=1
    )
    xs = jax.nn.silu(w["conv_bias"] + sum(
        jnp.where((seg_p[:, i:i + seq] == segment_ids)[..., None], padded[:, i:i + seq], 0.0)
        * w["conv_kernel"][i]
        for i in range(taps)
    ))
    dt, b, c = jnp.split(mm(xs, w["x_proj"]["kernel"], quant), (rank, rank + n), axis=-1)
    delta = jax.nn.softplus(mm(dt, w["dt_proj"]["kernel"], quant) + w["dt_bias"])
    delta = jnp.where(valid[..., None], delta, 0.0)
    a = -jnp.exp(w["A_log"])  # [inner, n]

    def one_token(state, token):
        x_t, delta_t, b_t, c_t, start_t = token
        state = jnp.where(start_t[:, None, None], 0.0, state)
        state = jnp.exp(delta_t[..., None] * a) * state + (delta_t * x_t)[..., None] * b_t[:, None, :]
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    by_time = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(
        one_token, jnp.zeros((batch, xs.shape[-1], n), F32),
        tuple(by_time(t) for t in (xs, delta, b, c, starts)),
    )
    y = by_time(y) + w["D"] * xs
    return mm(y * jax.nn.silu(z), w["out_proj"]["kernel"], quant), y


def diff_attention(x, w, cfg, segment_ids, depth, window, kv=None, quant=identity):
    """-> (the mixer's output, this layer's (k, v)). `kv`: another layer's
    keys and values (a cross layer has none of its own). `depth` is the
    layer's index `i`, a traced number."""
    batch, seq, _ = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    project = lambda name: mm(x, w[name]["kernel"], quant) + w[name]["bias"]
    q = project("q_proj").reshape(batch, seq, heads // 2, 2, dim)
    if kv is None:
        kv = (
            project("k_proj").reshape(batch, seq, kv_heads // 2, 2, dim),
            project("v_proj").reshape(batch, seq, kv_heads // 2, 2 * dim),  # [v1 ; v2]
        )
    k, v = kv
    idx = jnp.arange(seq)
    back = idx[:, None] - idx[None, :]
    mask = back >= 0
    if window is not None:
        mask &= back < window
    same = (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    mask = mask[None, None] & same[:, None]

    @jax.checkpoint
    def one_pair(group):
        """A key/value pair's query pairs: [B, S, G, 2, d], [B, S, 2, d], [B, S, 2d]."""
        qg, kg, vg = group
        scores = jnp.einsum("bqgrd,bkrd->brgqk", quant(qg), quant(kg)) / jnp.sqrt(F32(dim))
        probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
        return jnp.einsum("brgqk,bkd->bqgrd", quant(probs), quant(vg))  # a_r a query pair

    group = heads // kv_heads
    grouped = q.reshape(batch, seq, kv_heads // 2, group, 2, dim)
    a = jax.lax.map(
        one_pair, (jnp.moveaxis(grouped, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0))
    )  # [KV / 2, B, S, G, 2, 2d]
    a = jnp.moveaxis(a, 0, 2).reshape(batch, seq, heads // 2, 2, 2 * dim)
    start = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = (
        jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + start
    )
    o = rms_norm(a[:, :, :, 0] - lam * a[:, :, :, 1], w["subln"]["weight"], cfg["layer_norm_eps"])
    o = ((1.0 - start) * o).reshape(batch, seq, heads * dim)
    return mm(o, w["o_proj"]["kernel"], quant) + w["o_proj"]["bias"], kv


def layer(x, w, cfg, segment_ids, kind, depth, shared, quant=identity):
    """One layer of kind `kind` -> (x, what it made for later layers: the
    memory, or the full layer's keys and values). `shared = (memory, kv)`."""
    eps = cfg["layer_norm_eps"]
    normed = layer_norm(x, w["input_layernorm"], eps)
    made = None
    if kind in ("mamba", "memory"):
        mixed, made = mamba(normed, w["mamba"], cfg, segment_ids, quant)
    elif kind == "gmu":
        gate = jax.nn.silu(mm(normed, w["gmu"]["in_proj"]["kernel"], quant))
        mixed = mm(gate * shared[0], w["gmu"]["out_proj"]["kernel"], quant)
    else:
        mixed, made = diff_attention(
            normed, w["self_attn"], cfg, segment_ids, depth,
            cfg["sliding_window"] if kind == "window" else None,
            shared[1] if kind == "cross" else None, quant,
        )
    x = x + mixed
    return x + swiglu(layer_norm(x, w["post_attention_layernorm"], eps), w["mlp"], quant), made


@functools.cache
def _jitted(kind, quant, cfg_items):
    cfg = dict(cfg_items)
    f32 = lambda tree: jax.tree.map(lambda t: jnp.asarray(t, F32), tree)
    return jax.jit(lambda x, w, seg, depth, shared: layer(x, f32(w), cfg, seg, kind, depth, shared, quant))


def _static(cfg) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str, bool, type(None)))))


def hidden_states(params, cfg, input_ids, segment_ids, quant=identity):
    """The final LayerNorm's output `[B, S, hidden]` in float32: one jitted
    layer at a time, so that only one layer's float32 weights exist at once."""
    kinds = layer_kinds(cfg)
    half, key = cfg["num_hidden_layers"] // 2, _static(cfg)
    with jax.default_matmul_precision("highest"):
        x = jax.jit(lambda table, ids: jnp.asarray(table, F32)[ids])(
            params["embed_tokens"]["embedding"], input_ids
        )
        memory = kv = None
        for index, kind in enumerate(kinds):
            if index < half:
                stack, period = params["self_decoder"], index // 2
            elif index < half + 2:
                stack, period = params["between"], None
            else:
                stack, period = params["cross_decoder"], (index - half - 2) // 2
            w = stack[f"slot{index % 2}"]
            if period is not None:
                w = jax.tree.map(lambda t: t[period], w)
            x, made = _jitted(kind, quant, key)(x, w, segment_ids, F32(index), (memory, kv))
            if kind == "memory":
                memory = made
            elif kind == "full":
                kv = made
        return jax.jit(lambda x, w: layer_norm(x, jax.tree.map(lambda t: jnp.asarray(t, F32), w), cfg["layer_norm_eps"]))(
            x, params["norm"]
        )


@functools.cache
def _head(quant):
    return jax.jit(lambda hidden, table: jnp.einsum("...h,vh->...v", quant(hidden), quant(jnp.asarray(table, F32))))


def head(params, hidden, quant=identity):
    """Logits over the whole (tied) vocabulary for `hidden [..., hidden]`:
    the caller chooses how many positions at once."""
    with jax.default_matmul_precision("highest"):
        return _head(quant)(hidden, params["embed_tokens"]["embedding"])


def logits(params, cfg, input_ids, segment_ids, position_ids=None, quant=identity):
    """Full-sequence logits `[B, S, V]` in float32."""
    return head(params, hidden_states(params, cfg, input_ids, segment_ids, quant), quant)
