"""Plain reference for openPangu-Ultra-MoE (`model_type: pangu_ultra_moe`,
FreedomIntelligence/openPangu-Ultra-MoE-718B): the benchmark's copy of the
equations of `llm_training_tpu/models/deepseek/reference.py` (one tier-1 test
holds the two equal), importing nothing from the program, COMPUTED IN BLOCKS
so that rows of 8,704 tokens at 128 heads fit beside 6.8 GB of weights.

    layer (sandwich_norm):  a = N_pa(MLA(N_in x));  h = x + a
                            m = N_pm(MLP(N_pre h)); y = h + m
    (without it, DeepSeek's: h = x + MLA(N_in x); y = h + MLP(N_pa h))
    logits = Head(N_f y)

MLA in the NON-absorbed form: `c_q = N(x W_qa)`, `q = c_q W_qb` a head `[nope
| rope]`; `[c | k_r] = x W_kva`, `c_kv = N(c)`, `[k_nope | v] = c_kv W_kvb` a
head; `q_rope` and the one `k_r` a token rotated (pairs interleaved unless
`rope_interleave` is false); causal softmax a head, in float32, of `(q_nope .
k_nope + q_rope . k_r) / sqrt(nope + rope)`. The MLP is a SwiGLU of
`intermediate_size` on the first `first_k_dense_replace` layers; on the
others sigmoid scores over ALL the router's outputs, the `num_experts_per_tok`
largest of score + bias, weights normalised over the chosen and scaled by
`routed_scaling_factor`, every expert HELD here (the stacked weights' expert
axis: the chip's share, experts `experts_first` onwards) evaluated on every
token and weighted by the routing matrix; what is held elsewhere adds
nothing, the shared expert adds to every token.

The multi-token-prediction module (`mtp_logits`; no cell serves it):
`h'_i = W_eh [N_e(Emb(t_{i+1})) ; N_h(y_i)]`, one more layer, `Head(N_f .)`.

The blocks (the mathematics is the plain file's): rows one at a time, each
at the whole `SPAN`s up to its last token (what lies past it stays zero:
nothing reads it), a sub-block a jitted call; attention `HEAD_GROUP` heads at a
time and, inside a group, `QUERY_BLOCK` queries at a time against the keys at
or before the block's end; the dense MLP's width in `MLP_SLABS` slabs; the
experts one at a time, each cut out of the layers' stack and taken to float32
on its own."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.references import _common as c

SPAN = 1024  # a row is computed at the next multiple of this past its last token
HEAD_GROUP = 8
QUERY_BLOCK = 1024
MLP_SLABS = 4
EXPERT_NAMES = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")


def rotate(x, positions, theta, interleaved):
    """Rotary positions on x `[..., S, D]` at `positions [S]`: pairs (2i,
    2i+1) turn together, or (i, i + D/2) with `interleaved` false."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=c.F32) / dim))
    angles = positions.astype(c.F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if interleaved:
        first, second = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [first * cos - second * sin, second * cos + first * sin], axis=-1
        ).reshape(x.shape)
    first, second = x[..., : dim // 2], x[..., dim // 2:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(c.F32), tree)


def mla_row(z, w, cfg, seg, pos, quant=c.identity):
    """One MLA block on ONE row: z `[S, hidden]`, seg and pos `[S]`; `w` as
    the program keeps it (any float type)."""
    seq = z.shape[0]
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    theta, pairs = cfg["rope_theta"], cfg.get("rope_interleave", True)
    scale = cfg.get("attention_scale", (nope + rope) ** -0.5)
    group = HEAD_GROUP if heads % HEAD_GROUP == 0 else heads

    c_q = c.rms_norm(
        c.mm(z, w["q_a_proj"]["kernel"].astype(c.F32), quant),
        w["q_a_layernorm"]["weight"].astype(c.F32), eps,
    )
    compressed = c.mm(z, w["kv_a_proj_with_mqa"]["kernel"].astype(c.F32), quant)
    c_kv = c.rms_norm(compressed[:, :latent], w["kv_a_layernorm"]["weight"].astype(c.F32), eps)
    k_rope = rotate(compressed[:, latent:], pos, theta, pairs)
    idx = jnp.arange(seq)

    def some_heads(mine):
        w_q, w_kv = (a.astype(c.F32) for a in mine)  # [G, q_lora, nope + rope], [G, latent, nope + v]
        q = jnp.einsum("sr,gre->gse", quant(c_q), quant(w_q))
        kv = jnp.einsum("sl,gle->gse", quant(c_kv), quant(w_kv))
        q_rope = rotate(q[..., nope:], pos, theta, pairs)
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
    return c.mm(out, w["o_proj"]["kernel"].astype(c.F32), quant)


def dense_mlp(x, w, quant=c.identity):
    """SwiGLU on x `[S, hidden]`, the width a slab at a time: a cast of all
    three matrices at once is 1.7 GB at the published 18,432."""
    inter = w["gate_proj"]["kernel"].shape[-1]
    slabs = MLP_SLABS if inter % MLP_SLABS == 0 else 1
    columns = lambda name: jnp.moveaxis(
        w[name]["kernel"].reshape(x.shape[-1], slabs, inter // slabs), 1, 0
    )
    rows = w["down_proj"]["kernel"].reshape(slabs, inter // slabs, -1)

    def one_slab(total, slab):
        gate, up, down = (a.astype(c.F32) for a in slab)
        return total + c.mm(jax.nn.silu(c.mm(x, gate, quant)) * c.mm(x, up, quant), down, quant), None

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


def moe_block(x, w, cfg, quant=c.identity, layer=None):
    """x `[T, hidden]`. With `layer`, the router, the bias and the experts
    are every scanned layer's, `[L, ...]`, and this layer's are cut out here,
    the experts one at a time."""
    mine = (lambda a: a) if layer is None else (lambda a: a[layer])
    routing = routing_matrix(
        x, {k: mine(w[k]) for k in ("gate_kernel", "e_score_correction_bias")}, cfg, quant
    )
    first, held = cfg.get("experts_first", 0), mine(w[EXPERT_NAMES[0]]).shape[0]

    def one_expert(total, expert):
        index, weight = expert
        gate, up, down = (mine(w[n])[index].astype(c.F32) for n in EXPERT_NAMES)
        out = c.mm(jax.nn.silu(c.mm(x, gate, quant)) * c.mm(x, up, quant), down, quant)
        return total + out * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (jnp.arange(held), routing[:, first:first + held].T)
    )
    return total + c.swiglu(x, _f32(jax.tree.map(mine, w["shared_experts"])), quant)


@functools.cache
def _programs(cfg_text: str, quant):
    """The jitted sub-blocks, once a configuration and precision (a check
    calls `logits` once for every four requests); each is traced again for
    each length of row it meets."""
    cfg = json.loads(cfg_text)
    eps, sandwich = cfg["rms_norm_eps"], cfg.get("sandwich_norm", False)
    norm = lambda h, weight: c.rms_norm(h, weight.astype(c.F32), eps)

    @jax.jit
    def embed(table, ids):
        return table.astype(c.F32)[ids]

    @jax.jit
    def attend(x, norms, w, seg, pos):
        """-> (h, the MLP's input)."""
        attn = mla_row(norm(x, norms["input_layernorm"]["weight"]), w, cfg, seg, pos, quant)
        if sandwich:
            h = x + norm(attn, norms["post_attention_layernorm"]["weight"])
            return h, norm(h, norms["pre_mlp_layernorm"]["weight"])
        h = x + attn
        return h, norm(h, norms["post_attention_layernorm"]["weight"])

    def close(h, mlp, norms):
        return h + (norm(mlp, norms["post_mlp_layernorm"]["weight"]) if sandwich else mlp)

    @jax.jit
    def dense(h, u, norms, w):
        return close(h, dense_mlp(u, w, quant), norms)

    @jax.jit
    def moe(h, u, norms, w, layer):
        # `w`: every scanned layer's MoE weights, as they lie
        return close(h, moe_block(u, w, cfg, quant, layer), norms)

    @jax.jit
    def moe_alone(h, u, norms, w):
        return close(h, moe_block(u, w, cfg, quant), norms)

    @jax.jit
    def merge(table, following, x, w):
        joined = jnp.concatenate([
            norm(table.astype(c.F32)[following], w["enorm"]["weight"]),
            norm(x, w["hnorm"]["weight"]),
        ], axis=-1)
        return c.mm(joined, w["eh_proj"]["kernel"].astype(c.F32), quant)

    @functools.partial(jax.jit, donate_argnums=0)
    def head(out, row, x, norm_w, head_w):
        rows = c.mm(norm(x, norm_w), head_w.astype(c.F32), quant)
        return jax.lax.dynamic_update_slice(out, rows[None], (row, 0, 0))

    return embed, attend, dense, moe, moe_alone, merge, head


NORMS = ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm", "post_mlp_layernorm")


def _one_layer(programs, w, stack, at, x, seg, pos):
    """A layer on one row: `w` its own weights, or None for layer `at` of the
    scanned `stack`."""
    _, attend, dense, moe, moe_alone, _, _ = programs
    if w is None:
        w = jax.tree.map(lambda a: a[at], {k: v for k, v in stack.items() if k != "mlp"})
    norms = {k: w[k] for k in NORMS if k in w}
    h, u = attend(x, norms, w["self_attn"], seg, pos)
    if "mlp" not in w:
        return moe(h, u, norms, stack["mlp"], at)
    if "gate_kernel" in w["mlp"]:
        return moe_alone(h, u, norms, w["mlp"])
    return dense(h, u, norms, w["mlp"])


def _rows(params, cfg, input_ids, segment_ids, position_ids, quant, with_mtp):
    batch, seq = input_ids.shape
    programs = _programs(json.dumps(cfg, sort_keys=True), quant)
    embed, *_, merge, head = programs
    looped = sum(1 for name in params if name.startswith("layers_"))
    stack = params["moe_layers"]["layer"] if "moe_layers" in params else None
    table, head_w = params["embed_tokens"]["embedding"], params["lm_head"]["kernel"]
    seg_host = np.asarray(segment_ids)
    with c.exact():
        out = jnp.zeros((batch, seq, head_w.shape[-1]), c.F32)
        ahead = jnp.zeros_like(out) if with_mtp else None
        for row in range(batch):
            live = np.flatnonzero(seg_host[row] > 0)
            last = int(live[-1]) + 1 if live.size else 1
            span = min(seq, -(-last // SPAN) * SPAN)
            ids, seg, pos = (a[row, :span] for a in (input_ids, segment_ids, position_ids))
            x = embed(table, ids)
            for index in range(cfg["num_hidden_layers"]):
                own = params.get(f"layers_{index}")
                x = _one_layer(programs, own, stack, index - looped, x, seg, pos)
            out = head(out, row, x, params["norm"]["weight"], head_w)
            if with_mtp:
                w = params["mtp_0"]
                merged = merge(table, jnp.roll(ids, -1), x, {k: w[k] for k in ("enorm", "hnorm", "eh_proj")})
                y = _one_layer(programs, w["layer"], None, 0, merged, seg, pos)
                ahead = head(ahead, row, y, params["norm"]["weight"], head_w)
        return out, ahead


def logits(params, cfg, input_ids, segment_ids, position_ids=None, quant=c.identity):
    """Full-sequence logits [B, S, V] float32. `params` is the tree under
    'params' of what the benchmark's initialiser made: `layers_{i}` for the
    looped dense prefix, `moe_layers/layer` stacked over the scanned suffix.
    Rows are filled into ONE preallocated result."""
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    return _rows(params, cfg, input_ids, segment_ids, position_ids, quant, False)[0]


def mtp_logits(params, cfg, input_ids, segment_ids, position_ids=None, quant=c.identity):
    """`(logits, the multi-token-prediction module's logits)`, both [B, S, V]:
    the second's row i is for the token at i + 2 (`params["mtp_0"]`)."""
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    return _rows(params, cfg, input_ids, segment_ids, position_ids, quant, True)
