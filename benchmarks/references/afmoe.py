"""Plain reference for AFMoE (arcee-ai/Trinity-Mini, `model_type: afmoe`): the
benchmark's copy of the equations of `llm_training_tpu/models/afmoe/
reference.py` (one tier-1 test holds the two equal), importing nothing from
the program, COMPUTED IN BLOCKS so that 12,800 tokens fit beside the weights.

    h = E[ids] * sqrt(hidden_size)                       (mup_enabled)
    a = Attn(N1 h);  h = h + N2 a;  m = MLP(N3 h);  h = h + N4 m
    logits = Head(N_f h)

`Attn`: q, k, v and a gate from four bias-free projections, an RMSNorm over
each q and k head; a `sliding_attention` layer rotates q and k (theta 10,000,
pairs (i, i + 64)) and a query at `p` sees the keys at `p - 2047 ... p`; a
`full_attention` layer has NO positional term and sees every key at or before
it; softmax in float32 at scale `128 ** -0.5`; `o_proj(attn * sigmoid(gate))`.
`MLP`: SwiGLU of 6144 on the first `num_dense_layers` layers; on the others
sigmoid scores over ALL the router's experts, the top k of score + bias,
weights normalised over the chosen k and scaled by `route_scale`, every expert
HELD here (the stacked weights' leading axis: the chip's share, experts
`experts_first` onwards) evaluated on every token and weighted by the routing
matrix; what is held elsewhere adds nothing, the shared expert adds to every
token. Positions of segment 0 change nothing.

The blocks (the mathematics is the plain file's): rows one at a time; a
row's keys and values for a whole layer first, then its queries `block` at a
time, each against all the row's keys (a full layer: scores `[4, 8, block,
S]`) or against its band only (a sliding layer: the `block` keys of the
queries and the whole blocks that hold the 2,047 before them). Blocks past a
row's last token are not computed at all (their logits stay zero: nothing
reads them), so a short request costs what it is long."""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.references import _common as c

BLOCK = 256  # queries at a time, where it divides the row


def rotate(x, positions, theta):
    """x [T, heads, D] at `positions [T]`; pairs (i, i + D/2) rotate together."""
    cos, sin = c.rope_tables(positions, x.shape[-1], theta)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def moe_block(x, w, cfg, quant):
    """x [T, hidden] -> the held experts' part of the routed sum + the shared expert."""
    scores = jax.nn.sigmoid(c.mm(x, w["gate_kernel"], quant))
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
        f32 = lambda a: a.astype(c.F32)
        out = c.mm(jax.nn.silu(c.mm(x, f32(gate), quant)) * c.mm(x, f32(up), quant), f32(down), quant)
        return total + out * weight[:, None], None

    total, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (w["experts_gate_proj"], w["experts_up_proj"], w["experts_down_proj"], routing.T),
    )
    return total + c.swiglu(x, w["shared_experts"], quant)


def layer(x, w, cfg, seg, pos, live_blocks, block, is_window: bool, is_moe: bool, quant):
    """One layer on one row: x [S, hidden], seg / pos [S]; the first
    `live_blocks` blocks of `block` positions are computed."""
    seq = x.shape[0]
    heads, kv_heads, dim = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    attn_w = w["self_attn"]
    # the expert stacks stay as they came (a cast of them all is 0.4 GB); a cast an expert inside
    small = lambda tree: jax.tree.map(lambda a: a.astype(c.F32), tree)
    mlp_w = {k: (v if k.startswith("experts_") else small(v)) for k, v in w["mlp"].items()}
    attn_w, norms = small(attn_w), small({k: v for k, v in w.items() if k.endswith("layernorm")})
    norm = lambda name, y: c.rms_norm(y, norms[name]["weight"], eps)
    # whole blocks in front of the row, so that a band never starts before it
    back = -(-(cfg["sliding_window"] - 1) // block) * block if is_window else 0
    cut = lambda a, at, n=block: jax.lax.dynamic_slice_in_dim(a, at, n, axis=0)

    def keys_values(i, kv):
        at = i * block
        h = norm("input_layernorm", cut(x, at))
        k = c.mm(h, attn_w["k_proj"]["kernel"], quant).reshape(block, kv_heads, dim)
        k = c.rms_norm(k, attn_w["k_norm"]["weight"], eps)
        if is_window:
            k = rotate(k, cut(pos, at), theta)
        v = c.mm(h, attn_w["v_proj"]["kernel"], quant).reshape(block, kv_heads, dim)
        put = lambda buffer, new: jax.lax.dynamic_update_slice_in_dim(buffer, new, back + at, axis=0)
        return put(kv[0], k), put(kv[1], v)

    empty = jnp.zeros((back + seq, kv_heads, dim), c.F32)
    keys, values = jax.lax.fori_loop(0, live_blocks, keys_values, (empty, empty))
    key_seg = jnp.concatenate([jnp.zeros((back,), seg.dtype), seg])
    key_at = jnp.arange(-back, seq)

    def queries(i, out):
        at = i * block
        xb, q_seg, q_at = cut(x, at), cut(seg, at), at + jnp.arange(block)
        h = norm("input_layernorm", xb)
        q = c.mm(h, attn_w["q_proj"]["kernel"], quant).reshape(block, heads, dim)
        q = c.rms_norm(q, attn_w["q_norm"]["weight"], eps)
        if is_window:
            q = rotate(q, cut(pos, at), theta)
        # a sliding layer: its band, from `back` before the block to its end
        span = (back + block) if is_window else seq
        first = at if is_window else 0  # in the buffers' own coordinates
        k, v = cut(keys, first, span), cut(values, first, span)
        k_seg, k_at = cut(key_seg, first, span), cut(key_at, first, span)
        behind = q_at[:, None] - k_at[None, :]
        seen = (behind >= 0) & (k_seg[None, :] == q_seg[:, None]) & (q_seg[:, None] > 0)
        if is_window:
            seen &= behind < cfg["sliding_window"]
        scores = jnp.einsum(
            "qkgd,skd->kgqs", quant(q.reshape(block, kv_heads, heads // kv_heads, dim)), quant(k)
        ) * dim ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30), axis=-1)
        attn = jnp.einsum("kgqs,skd->qkgd", quant(probs), quant(v)).reshape(block, heads * dim)
        attn = attn * jax.nn.sigmoid(c.mm(h, attn_w["gate_proj"]["kernel"], quant))
        xb = xb + norm("post_attention_layernorm", c.mm(attn, attn_w["o_proj"]["kernel"], quant))
        h = norm("pre_mlp_layernorm", xb)
        mlp = moe_block(h, mlp_w, cfg, quant) if is_moe else c.swiglu(h, mlp_w, quant)
        xb = xb + norm("post_mlp_layernorm", mlp)
        return jax.lax.dynamic_update_slice_in_dim(out, xb, at, axis=0)

    return jax.lax.fori_loop(0, live_blocks, queries, jnp.zeros_like(x))


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


@functools.cache
def _programs(cfg_text: str, quant, block: int):
    """The row's jitted pieces, built once a process for a configuration, a
    precision and a block: every call of `logits` uses the same ones."""
    cfg = json.loads(cfg_text)

    @jax.jit
    def embed(table, ids):
        x = table.astype(c.F32)[ids]
        return x * cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"] else x

    @functools.partial(jax.jit, static_argnums=(5, 6))
    def one_layer(x, w, seg, pos, live_blocks, is_window, is_moe):
        return layer(x, w, cfg, seg, pos, live_blocks, block, is_window, is_moe, quant)

    @functools.partial(jax.jit, donate_argnums=0)
    def head(out, row, x, norm_w, head_w):
        x = c.rms_norm(x, norm_w.astype(c.F32), cfg["rms_norm_eps"])
        return out.at[row].set(c.mm(x, head_w.astype(c.F32), quant))

    return embed, one_layer, head


def logits(params, cfg, input_ids, segment_ids, position_ids, quant=c.identity, block=None):
    """Full-sequence logits [B, S, V] float32, a row and a layer at a time.
    Rows are filled into ONE preallocated result (a stack of them would hold
    the result twice)."""
    batch, seq = input_ids.shape
    if block is None:
        block = BLOCK if seq % BLOCK == 0 else seq
    if seq % block:
        raise ValueError(f"a row of {seq} is not whole blocks of {block}")
    embed, one_layer, head = _programs(json.dumps(cfg, sort_keys=True), quant, block)
    with c.exact():
        out = jnp.zeros((batch, seq, params["lm_head"]["kernel"].shape[-1]), c.F32)
        for row in range(batch):
            seg, pos = segment_ids[row], position_ids[row]
            # the blocks up to the row's last token
            last = jnp.max(jnp.where(seg > 0, jnp.arange(seq) + 1, 0))
            live_blocks = (last + block - 1) // block
            x = embed(params["embed_tokens"]["embedding"], input_ids[row])
            for index in range(cfg["num_hidden_layers"]):
                x = one_layer(
                    x, layer_weights(params, index), seg, pos, live_blocks,
                    layer_is_window(cfg, index), index >= cfg["num_dense_layers"],
                )
            out = head(out, row, x, params["norm"]["weight"], params["lm_head"]["kernel"])
        return out
