"""Plain reference for LongCat-Flash (the language model of
meituan-longcat/LongCat-Flash-Omni): the benchmark's copy of
`llm_training_tpu/models/longcat_flash/reference.py` (one tier-1 test holds
the two equal), importing nothing from the program.

One double layer (four RMSNorms with their own weights):

    h = x + MLA_0(N1(x));  u = N2(h);  m = MoE(u);  h = h + FFN_0(u)
    h = h + MLA_1(N3(h));  y = h + FFN_1(N4(h)) + m

MLA in the NON-absorbed form: `c_q = RMSNorm(W_qa z)`, `q = s_q W_qb c_q` a
head `[nope | rope]`; `[c | k_r] = W_kva z`, `c_kv = s_kv RMSNorm(c)`, `[k_nope
| v] = W_kvb c_kv` a head; `q_rope` and the one `k_r` a token rotated (pairs
interleaved); full [S, S] causal softmax a head of `(q_nope . k_nope + q_rope
. k_r) / sqrt(192)`. The MoE takes a float32 softmax over the router's width,
the `moe_topk` largest of score + bias, weight `routed_scaling_factor *
score`, not renormalised; every expert HELD here (the stacked weights'
leading axis, real experts `experts_first` onwards) is evaluated on every
token and weighted by the routing matrix, real experts held elsewhere add
nothing, and the weights of the chosen zero-compute experts (the router's
last `zero_expert_num` outputs), summed a token, multiply the token.

It runs beside 10 GB of bfloat16 weights on rows of 5,632 tokens, so it steps
through a layer one sub-block a jitted call, one row of the batch at a time,
one head at a time in attention and one expert at a time in the MoE: at the
cell's widths a call's float32 weights and intermediates stay under 1.5 GB."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.references import _common as c


def rotate_pairs(x, positions, theta):
    """Rotary positions on x `[S, D]`, pairs (2i, 2i+1) turning together."""
    dim = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=c.F32) / dim))
    angles = positions.astype(c.F32)[:, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[:, 0::2], x[:, 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def mla_row(z, w, cfg, seg, pos, quant=c.identity):
    """One MLA block on ONE row: z `[S, hidden]`, seg and pos `[S]`."""
    seq, hidden = z.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, latent = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["kv_lora_rank"]
    s_q = (hidden / cfg["q_lora_rank"]) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0
    s_kv = (hidden / latent) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0

    c_q = c.rms_norm(c.mm(z, w["q_a_proj"]["kernel"], quant), w["q_a_layernorm"]["weight"], eps)
    compressed = c.mm(z, w["kv_a_proj_with_mqa"]["kernel"], quant)
    c_kv = s_kv * c.rms_norm(compressed[:, :latent], w["kv_a_layernorm"]["weight"], eps)
    k_rope = rotate_pairs(compressed[:, latent:], pos, cfg["rope_theta"])
    idx = jnp.arange(seq)
    mask = (idx[:, None] >= idx[None, :]) & (seg[:, None] == seg[None, :]) & (seg[:, None] > 0)

    def one_head(head):
        w_q, w_kv = head  # [q_lora, nope + rope], [latent, nope + v]
        q = s_q * c.mm(c_q, w_q, quant)
        kv = c.mm(c_kv, w_kv, quant)
        q_rope = rotate_pairs(q[:, nope:], pos, cfg["rope_theta"])
        scores = (
            c.mm(q[:, :nope], kv[:, :nope].T, quant) + c.mm(q_rope, k_rope.T, quant)
        ) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return c.mm(probs, kv[:, nope:], quant)

    by_head = w["q_b_proj"]["kernel"].reshape(-1, heads, nope + rope)
    out = jax.lax.map(
        one_head, (jnp.moveaxis(by_head, 1, 0), jnp.moveaxis(w["kv_b_proj"], 1, 0))
    )  # [H, S, v]
    return c.mm(jnp.moveaxis(out, 0, 1).reshape(seq, -1), w["o_proj"]["kernel"], quant)


def moe_block(u, w, cfg, quant=c.identity, layer=None):
    """u `[..., hidden]`. The stacked experts may come in any float type, and
    with `layer` as every layer's `[L, E, ...]`: each expert's matrices are
    cut out and taken to float32 on their own."""
    shape = u.shape
    x = u.reshape(-1, shape[-1])
    scores = jax.nn.softmax(c.mm(x, w["router"]["kernel"].astype(c.F32), quant), axis=-1)
    real = scores.shape[-1] - cfg["zero_expert_num"]
    _, chosen = jax.lax.top_k(scores + w["router"]["bias"], cfg["moe_topk"])
    weights = cfg["routed_scaling_factor"] * jnp.take_along_axis(scores, chosen, axis=1)
    routing = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(weights)
    names = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
    first, held = cfg.get("experts_first", 0), w[names[0]].shape[0 if layer is None else 1]

    def one_expert(total, expert):
        index, weight = expert
        gate, up, down = (
            (w[n][index] if layer is None else w[n][layer, index]).astype(c.F32) for n in names
        )
        out = c.mm(jax.nn.silu(c.mm(x, gate, quant)) * c.mm(x, up, quant), down, quant)
        return total + out * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (jnp.arange(held), routing[:, first:first + held].T)
    )
    zero = jnp.sum(routing[:, real:], axis=-1, keepdims=True) * x
    return (total + zero).reshape(shape)


@functools.cache
def _programs(cfg_text: str, quant):
    """The jitted sub-blocks, once a configuration and precision (a check
    calls `logits` once for every four requests). Each takes the rows one at
    a time (`lax.map` over the batch)."""
    cfg = json.loads(cfg_text)
    eps = cfg["rms_norm_eps"]
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(c.F32), tree)

    @jax.jit
    def embed(table, ids):
        return table.astype(c.F32)[ids]

    @jax.jit
    def attend(x, norm_w, w, seg, pos):
        w, norm_w = f32(w), norm_w.astype(c.F32)
        return x + jax.lax.map(
            lambda row: mla_row(c.rms_norm(row[0], norm_w, eps), w, cfg, row[1], row[2], quant),
            (x, seg, pos),
        )

    @jax.jit
    def normed(h, norm_w):
        return c.rms_norm(h, norm_w.astype(c.F32), eps)

    @jax.jit
    def moe(u, w, layer):
        # `w`: every layer's MoE weights, as they lie; this layer's router and,
        # one at a time, its experts are cut out inside
        router = jax.tree.map(lambda a: a[layer].astype(c.F32), w["router"])
        return moe_block(u, {**w, "router": router}, cfg, quant, layer)

    @jax.jit
    def ffn(h, u, w):
        w = f32(w)
        return h + jax.lax.map(lambda row: c.swiglu(row, w, quant), u)

    @jax.jit
    def head(x, norm_w, head_w):
        return c.mm(c.rms_norm(x, norm_w.astype(c.F32), eps), head_w.astype(c.F32), quant)

    return embed, attend, normed, moe, ffn, head


def logits(params, cfg, input_ids, segment_ids, position_ids=None, quant=c.identity):
    """Full-sequence logits [B, S, V]. `params` is the tree under 'params' of
    what the benchmark's initialiser made: `layers/layer/{sub_0, sub_1, mlp}`,
    each leaf stacked over the double layers."""
    if position_ids is None:
        position_ids = jnp.broadcast_to(jnp.arange(input_ids.shape[1]), input_ids.shape)
    embed, attend, normed, moe, ffn, head = _programs(json.dumps(cfg, sort_keys=True), quant)
    stack = params["layers"]["layer"]
    with c.exact():
        x = embed(params["embed_tokens"]["embedding"], input_ids)
        for index in range(cfg["num_layers"]):
            # one sub-block's weights cut out of the stack at a time
            mine = lambda tree: jax.tree.map(lambda a: a[index], tree)
            first, second = stack["sub_0"], stack["sub_1"]
            h = attend(x, mine(first["input_layernorm"]["weight"]), mine(first["self_attn"]),
                       segment_ids, position_ids)
            u = normed(h, mine(first["post_attention_layernorm"]["weight"]))
            m = moe(u, stack["mlp"], index)
            h = ffn(h, u, mine(first["mlp"]))
            h = attend(h, mine(second["input_layernorm"]["weight"]), mine(second["self_attn"]),
                       segment_ids, position_ids)
            u = normed(h, mine(second["post_attention_layernorm"]["weight"]))
            x = ffn(h, u, mine(second["mlp"])) + m
        return head(x, params["norm"]["weight"], params["lm_head"]["kernel"])
