"""Plain reference for GigaChat 3.5 (`model_type: gigachat3_5`,
ai-sage/GigaChat3.5-432B-A28B): the benchmark's copy of the equations of
`llm_training_tpu/models/gigachat35/reference.py` (one tier-1 test holds the
two equal), importing nothing from the program, COMPUTED IN BLOCKS so that
rows of 10,240 tokens fit beside 9.5 GB of weights.

    N(x) = x / rms(x) * g sigmoid(w)            (g = layernorm_gating_weight)
    layer:  h = x + N2(mixer(N1 x));  y = h + N4(ffn(N3 h))
    logits = Head(N_f y)

The mixer of a layer in `full_attention_layers` is DeepSeek-V3's latent
attention in the NON-absorbed form (`c_q = R(x W_qa)`, `q = c_q W_qb` a head
`[nope | rope]`; `[c | k_r] = x W_kva`, `c_kv = R(c)`, `[k_nope | v] = c_kv
W_kvb` a head; `R` a plain RMSNorm; yarn rotary, pairs interleaved, on
`q_rope` and the one `k_r` a token; causal softmax a head, in float32, of
`(q_nope . k_nope + q_rope . k_r) * scale`) with an output gate, `(attn *
sigmoid(x W_gate)) W_o`. Of every other layer Qwen3-Next's gated delta rule:
`[q | k | v] = silu(conv4(x W_qkv))`, ONE left-padded causal convolution (no
conv tail), q and k L2-normalised a KEY head, key head j serving value heads
j r .. j r + r - 1; `beta = sigmoid(x W_b)`, `alpha = exp(-exp(A_log)
softplus(x W_a + dt_bias))` a value head; the [128, 128] state a value head
advanced ONE TOKEN AT A TIME straight from

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T,  o_t = S_t^T q_t

(no chunks, no cache); `(N_o(o) * s sigmoid(x W_g)) W_o`. The feed-forward is
`W_d(silu(min(x W_g, L)) * clip(x W_u, -L, L))`, `L = swiglu_limit`, of
`intermediate_size` on the first `first_k_dense_replace` layers; on the others
sigmoid scores over ALL the router's outputs, the `num_experts_per_tok`
largest of score + bias, weights normalised over the chosen and scaled by
`routed_scaling_factor`, every expert HELD here (the stacked weights' expert
axis: the chip's share, experts `experts_first` onwards) evaluated on every
token and weighted by the routing matrix; what is held elsewhere adds
nothing, the shared expert adds to every token. Positions of segment 0 change
nothing.

What the source's keys do not settle (the norm's formula, the attention
gate's input, the delta-rule block's output gate, `beta`'s range, the clamp's
place) is `assumed` in the configuration file; the lines below that rest on
one say so.

The blocks (the mathematics is the plain file's): rows one at a time, each at
the whole `SPAN`s up to its last token (what lies past it stays zero: nothing
reads it), a sub-block a jitted call; attention `HEAD_GROUP` heads at a time
and, inside a group, `QUERY_BLOCK` queries at a time against the keys at or
before the block's end; the dense SwiGLU's width in `MLP_SLABS` slabs; the
experts one at a time, each cut out of the periods' stack and taken to
float32 on its own. The multi-token-prediction modules are not here: no cell
loads them."""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import _common as c

SPAN = 2048  # a row is computed at the next multiple of this past its last token
HEAD_GROUP = 8
QUERY_BLOCK = 1024
MLP_SLABS = 4
EXPERT_NAMES = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
NORMS = ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm", "post_mlp_layernorm")


def gated_norm(x, weight, eps, gating_weight):
    # assumed: ZeroCenteredGatedNorm read as x / rms(x) * g sigmoid(w)
    normed = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return normed * (gating_weight * jax.nn.sigmoid(weight.astype(c.F32)))


def l2_norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def clamped_swiglu(x, gate, up, down, limit, quant=c.identity):
    # assumed: swiglu_limit clamps the gate from above and the linear branch
    # on both sides, before the product
    g, u = c.mm(x, gate, quant), c.mm(x, up, quant)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return c.mm(jax.nn.silu(g) * u, down, quant)


def yarn_inv_freq(dim: int, theta: float, scaling: dict | None):
    """-> (frequencies `[dim / 2]`, the factor on cos and sin): DeepSeek's
    yarn, interpolated by `factor` below the correction range."""
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=c.F32) / dim))
    if not scaling:
        return plain, 1.0
    factor, original = scaling["factor"], scaling["original_max_position_embeddings"]
    at = lambda turns: dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(at(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(at(scaling.get("beta_slow", 1))), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=c.F32) - low) / max(high - low, 0.001), 0, 1)
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
    """Rotary positions on x `[..., S, D]` at `positions [S]`, pairs (2i, 2i+1)."""
    inv_freq, on_tables = yarn_inv_freq(x.shape[-1], cfg["rope_theta"], cfg.get("rope_scaling"))
    angles = positions.astype(c.F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles) * on_tables, jnp.sin(angles) * on_tables
    first, second = x[..., 0::2], x[..., 1::2]
    return jnp.stack(
        [first * cos - second * sin, second * cos + first * sin], axis=-1
    ).reshape(x.shape)


def mla_row(z, w, cfg, seg, pos, quant=c.identity):
    """One MLA block on ONE row: z `[S, hidden]`, seg and pos `[S]`; `w` as
    the program keeps it (any float type)."""
    seq = z.shape[0]
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    scale = attention_scale(cfg)
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads
    f32 = lambda name: w[name]["kernel"].astype(c.F32)

    c_q = c.rms_norm(c.mm(z, f32("q_a_proj"), quant), w["q_a_layernorm"]["weight"].astype(c.F32), eps)
    compressed = c.mm(z, f32("kv_a_proj_with_mqa"), quant)
    c_kv = c.rms_norm(compressed[:, :latent], w["kv_a_layernorm"]["weight"].astype(c.F32), eps)
    k_rope = rotate(compressed[:, latent:], pos, cfg)
    idx = jnp.arange(seq)

    def some_heads(mine):
        w_q, w_kv = (a.astype(c.F32) for a in mine)  # [G, q_lora, nope + rope], [G, latent, nope + v]
        q = jnp.einsum("sr,gre->gse", quant(c_q), quant(w_q))
        kv = jnp.einsum("sl,gle->gse", quant(c_kv), quant(w_kv))
        q_rope = rotate(q[..., nope:], pos, cfg)
        out = []
        for lo in range(0, seq, QUERY_BLOCK):
            hi = min(lo + QUERY_BLOCK, seq)  # a query sees no key past its block's end
            scores = (
                jnp.einsum("gqd,gkd->gqk", quant(q[:, lo:hi, :nope]), quant(kv[:, :hi, :nope]))
                + jnp.einsum("gqr,kr->gqk", quant(q_rope[:, lo:hi]), quant(k_rope[:hi]))
            ) * scale
            seen = (
                (idx[lo:hi, None] >= idx[None, :hi]) & (seg[lo:hi, None] == seg[None, :hi])
                & (seg[lo:hi, None] > 0)
            )
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
            out.append(jnp.einsum("gqk,gkv->gqv", quant(probs), quant(kv[:, :hi, nope:])))
        return jnp.concatenate(out, axis=1)  # [G, S, v]

    by_head = lambda kernel: jnp.moveaxis(
        kernel.reshape(kernel.shape[0], heads // group, group, -1), 0, 2
    )  # [in, H * e] -> [H / G, G, in, e]
    out = jax.lax.map(
        some_heads, (by_head(w["q_b_proj"]["kernel"]), by_head(w["kv_b_proj"]["kernel"]))
    )  # [H / G, G, S, v]
    out = jnp.moveaxis(out.reshape(heads, seq, -1), 0, 1).reshape(seq, -1)
    if cfg.get("gated_attention", True):
        # assumed: the gate reads the block's (normed) input, a value channel a head
        out = out * jax.nn.sigmoid(c.mm(z, f32("gate_proj"), quant))
    return c.mm(out, f32("o_proj"), quant)


def delta_rule(q, k, v, alpha, beta):
    """The recurrence on ONE row, a token at a time. q, k `[S, H, dk]`; v `[S,
    H, dv]`; alpha, beta `[S, H]` -> out `[S, H, dv]`."""

    def one_token(state, token):
        q_t, k_t, v_t, alpha_t, beta_t = token
        state = alpha_t[:, None, None] * state
        seen = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + beta_t[:, None, None] * k_t[..., None] * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, out = jax.lax.scan(
        one_token, jnp.zeros((q.shape[1], q.shape[2], v.shape[-1]), c.F32), (q, k, v, alpha, beta)
    )
    return out


def linear_row(z, w, cfg, seg, quant=c.identity):
    """One delta-rule block on ONE row (one document: a serving request)."""
    seq = z.shape[0]
    heads, key_heads = cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    f32 = lambda name: w[name]["kernel"].astype(c.F32)
    valid = seg > 0
    mixed = jnp.where(valid[:, None], c.mm(z, f32("qkv_proj"), quant), 0.0)
    padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    taps_w = w["conv_kernel"].astype(c.F32)
    mixed = jax.nn.silu(sum(padded[i:i + seq] * taps_w[i] for i in range(taps)))
    q, k, v = jnp.split(mixed, (key_heads * dk, 2 * key_heads * dk), axis=-1)
    q = l2_norm(q.reshape(seq, key_heads, dk)) * dk ** -0.5
    k = l2_norm(k.reshape(seq, key_heads, dk))
    # key head j serves value heads j r .. j r + r - 1
    q, k = (jnp.repeat(a, heads // key_heads, axis=1) for a in (q, k))
    v = v.reshape(seq, heads, dv)
    g = -jnp.exp(w["A_log"].astype(c.F32)) * jax.nn.softplus(
        c.mm(z, f32("a_proj"), quant) + w["dt_bias"].astype(c.F32)
    )
    # assumed: beta in (0, 1) (the source has no allow_neg_eigval key)
    beta = jnp.where(valid[:, None], jax.nn.sigmoid(c.mm(z, f32("b_proj"), quant)), 0.0)
    alpha = jnp.where(valid[:, None], jnp.exp(g), 1.0)
    # assumed: gated_rmsnorm_sigmoid_zero_centered read as the model's own norm
    # a head, times linear_sigmoid_gate_scale * sigmoid of the gate projection
    out = gated_norm(
        delta_rule(q, k, v, alpha, beta), w["o_norm"]["weight"],
        cfg["linear_attn_o_norm_eps"], cfg["layernorm_gating_weight"],
    )
    gate = cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(c.mm(z, f32("g_proj"), quant))
    return c.mm(out.reshape(seq, heads * dv) * gate, f32("o_proj"), quant)


def dense_mlp(x, w, limit, quant=c.identity):
    """The clamped SwiGLU on x `[S, hidden]`, the width a slab at a time: a
    cast of all three matrices at once is 1.6 GB at the published 18,432."""
    inter = w["gate_proj"]["kernel"].shape[-1]
    slabs = MLP_SLABS if inter % MLP_SLABS == 0 else 1
    columns = lambda name: jnp.moveaxis(
        w[name]["kernel"].reshape(x.shape[-1], slabs, inter // slabs), 1, 0
    )
    rows = w["down_proj"]["kernel"].reshape(slabs, inter // slabs, -1)

    def one_slab(total, slab):
        gate, up, down = (a.astype(c.F32) for a in slab)
        return total + clamped_swiglu(x, gate, up, down, limit, quant), None

    total, _ = jax.lax.scan(
        one_slab, jnp.zeros_like(x), (columns("gate_proj"), columns("up_proj"), rows)
    )
    return total


def routing_matrix(x, w, cfg, quant=c.identity):
    """`[T, router outputs]`: a token's weight for each expert it chose, 0 elsewhere."""
    scores = jax.nn.sigmoid(c.mm(x, w["gate_kernel"].astype(c.F32), quant))
    _, chosen = jax.lax.top_k(
        scores + w["e_score_correction_bias"].astype(c.F32), cfg["num_experts_per_tok"]
    )
    weights = jnp.take_along_axis(scores, chosen, axis=1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * cfg["routed_scaling_factor"]
    return jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)


def moe_block(x, w, cfg, quant=c.identity, at=None):
    """x `[T, hidden]`. With `at`, the router, the bias and the experts are
    every scanned period's, `[periods, ...]`, and period `at`'s are cut out
    here, the experts one at a time."""
    mine = (lambda a: a) if at is None else (lambda a: a[at])
    limit = cfg.get("swiglu_limit")
    routing = routing_matrix(
        x, {k: mine(w[k]) for k in ("gate_kernel", "e_score_correction_bias")}, cfg, quant
    )
    first, held = cfg.get("experts_first", 0), mine(w[EXPERT_NAMES[0]]).shape[0]

    def one_expert(total, expert):
        index, weight = expert
        gate, up, down = (mine(w[n])[index].astype(c.F32) for n in EXPERT_NAMES)
        return total + clamped_swiglu(x, gate, up, down, limit, quant) * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (jnp.arange(held), routing[:, first:first + held].T)
    )
    shared = jax.tree.map(mine, w["shared_experts"])
    # use_shared_expert_sigmoid false: the shared expert is added ungated
    return total + clamped_swiglu(
        x, *(shared[n]["kernel"].astype(c.F32) for n in ("gate_proj", "up_proj", "down_proj")),
        limit, quant,
    )


@functools.cache
def _programs(cfg_text: str, quant):
    """The jitted sub-blocks, once a configuration and precision (a check
    calls `hidden_states` once for every four requests); each is traced again
    for each length of row it meets."""
    cfg = json.loads(cfg_text)
    norm = lambda h, weight: gated_norm(h, weight, cfg["rms_norm_eps"], cfg["layernorm_gating_weight"])

    @jax.jit
    def embed(table, ids):
        return table.astype(c.F32)[ids]

    def mix(block):
        @jax.jit
        def run(x, norms, w, seg, pos):
            """-> (h, the feed-forward's input)."""
            mixed = block(norm(x, norms["input_layernorm"]["weight"]), w, seg, pos)
            h = x + norm(mixed, norms["post_attention_layernorm"]["weight"])
            return h, norm(h, norms["pre_mlp_layernorm"]["weight"])
        return run

    attend = mix(lambda z, w, seg, pos: mla_row(z, w, cfg, seg, pos, quant))
    recur = mix(lambda z, w, seg, pos: linear_row(z, w, cfg, seg, quant))
    close = lambda h, out, norms: h + norm(out, norms["post_mlp_layernorm"]["weight"])

    @jax.jit
    def dense(h, u, norms, w):
        return close(h, dense_mlp(u, w, cfg.get("swiglu_limit"), quant), norms)

    @jax.jit
    def moe(h, u, norms, w, at):
        # `w`: every scanned period's MoE weights of this slot, as they lie
        return close(h, moe_block(u, w, cfg, quant, at), norms)

    @jax.jit
    def moe_alone(h, u, norms, w):
        return close(h, moe_block(u, w, cfg, quant), norms)

    @functools.partial(jax.jit, donate_argnums=0)
    def final(out, row, x, norm_w):
        return jax.lax.dynamic_update_slice(out, norm(x, norm_w)[None], (row, 0, 0))

    return embed, attend, recur, dense, moe, moe_alone, final


def _one_layer(programs, w, at, x, seg, pos):
    """A layer on one row: `w` its own weights (a looped layer, `at` None) or
    its slot's of the scanned periods, `[periods, ...]`, of which it is
    period `at`."""
    _, attend, recur, dense, moe, moe_alone, _ = programs
    cut = (lambda tree: tree) if at is None else (lambda tree: jax.tree.map(lambda a: a[at], tree))
    norms = cut({k: w[k] for k in NORMS})
    if "self_attn" in w:
        h, u = attend(x, norms, cut(w["self_attn"]), seg, pos)
    else:
        h, u = recur(x, norms, cut(w["linear_attn"]), seg, pos)
    if "gate_kernel" not in w["mlp"]:
        return dense(h, u, norms, cut(w["mlp"]))
    if at is None:
        return moe_alone(h, u, norms, w["mlp"])
    return moe(h, u, norms, w["mlp"], at)


def layer_weights(params, index: int):
    """-> (layer `index`'s weights, its period among the scanned or None)."""
    if f"layers_{index}" in params:
        return params[f"layers_{index}"], None
    before = sum(1 for i in range(index) if f"layers_{i}" in params)
    period = len(params["periods"])
    return params["periods"][f"slot{(index - before) % period}"], (index - before) // period


def hidden_states(params, cfg, input_ids, segment_ids, quant=c.identity, position_ids=None):
    """The final norm's output `[B, S, hidden]` in float32. `params` is the
    tree under 'params' of what the benchmark's initialiser made: `layers_{i}`
    for the looped layers, `periods/slot{j}` stacked over the scanned periods.
    Rows are filled into ONE preallocated result."""
    batch, seq = input_ids.shape
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(seq), input_ids.shape)
    programs = _programs(json.dumps(cfg, sort_keys=True), quant)
    embed, *_, final = programs
    table = params["embed_tokens"]["embedding"]
    seg_host = np.asarray(segment_ids)
    with c.exact():
        out = jnp.zeros((batch, seq, table.shape[-1]), c.F32)
        for row in range(batch):
            live = np.flatnonzero(seg_host[row] > 0)
            last = int(live[-1]) + 1 if live.size else 1
            span = min(seq, -(-last // SPAN) * SPAN)
            ids, seg, pos = (a[row, :span] for a in (input_ids, segment_ids, position_ids))
            x = embed(table, ids)
            for index in range(cfg["num_hidden_layers"]):
                w, at = layer_weights(params, index)
                x = _one_layer(programs, w, at, x, seg, pos)
            out = final(out, row, x, params["norm"]["weight"])
        return out


@functools.cache
def _head(quant):
    return jax.jit(lambda hidden, kernel: c.mm(hidden, kernel.astype(c.F32), quant))


def head(params, hidden, quant=c.identity):
    """Logits over the (sliced) vocabulary for `hidden [..., hidden]`, the
    final norm's output: the caller chooses how many positions at once."""
    with c.exact():
        return _head(quant)(hidden, params["lm_head"]["kernel"])


def logits(params, cfg, input_ids, segment_ids, position_ids=None, quant=c.identity):
    """Full-sequence logits `[B, S, V]` in float32."""
    return head(params, hidden_states(params, cfg, input_ids, segment_ids, quant, position_ids), quant)
