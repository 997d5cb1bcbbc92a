"""Plain float32 `jax.numpy` pieces the references share: no kernels, no
cache, no batching tricks, nothing imported from the program. Every caller
traces under `jax.default_matmul_precision("highest")` (`exact()` below),
because a TPU otherwise multiplies float32 in bfloat16 passes.

`quant` is the control's hook: the identity in the reference, a round trip
through a lower precision in the control (tests and PERF.md), applied to both
operands of every matrix multiplication."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def exact():
    return jax.default_matmul_precision("highest")


def identity(x):
    return x


def fp8_round_trip(x):
    """Straight-through float8_e4m3 rounding, scaled per tensor to the
    format's range: the control for a bfloat16 configuration."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


QUANTS = {"none": identity, "fp8": fp8_round_trip}


def mm(x, w, quant=identity):
    return jnp.matmul(quant(x), quant(w))


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rope_tables(positions, head_dim: int, theta: float):
    """cos/sin [..., head_dim], the frequency vector repeated in both halves."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))
    angles = positions.astype(F32)[..., None] * inv_freq
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """x [B, S, heads, D]; pairs (i, i + D/2) rotate together."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, :, None, :] + turned * sin[:, :, None, :]


def attention(q, k, v, segment_ids, window, quant=identity):
    """q [B, S, H, D], k/v [B, S, KV, D]. A query sees a key of the same
    segment (segment 0 is padding) at or before it and, with `window`, fewer
    than `window` positions back (the published sliding window). One group of
    query heads per key/value head at a time, each recomputed in a backward
    pass, so that only one group's [S, S] scores exist at once."""
    batch, seq, heads, dim = q.shape
    kv_heads = k.shape[2]
    idx = jnp.arange(seq)
    delta = idx[:, None] - idx[None, :]
    mask = delta >= 0
    if window is not None:
        mask &= delta < window
    same = (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] > 0)
    mask = mask[None, None] & same[:, None]

    @jax.checkpoint
    def one_group(group):
        qg, kg, vg = group  # [B, S, G, D], [B, S, D], [B, S, D]
        scores = jnp.einsum("bqgd,bkd->bgqk", quant(qg), quant(kg)) / jnp.sqrt(F32(dim))
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", quant(probs), quant(vg))

    grouped = q.reshape(batch, seq, kv_heads, heads // kv_heads, dim)
    out = jax.lax.map(
        one_group,
        (jnp.moveaxis(grouped, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)),
    )  # [KV, B, S, G, D]
    return jnp.moveaxis(out, 0, 2).reshape(batch, seq, heads * dim)


def gqa_block(x, w, cfg, segment_ids, cos, sin, quant=identity, qk_norm=False):
    """Self attention of one pre-norm decoder layer; `w` holds this layer's
    float32 weights under the names of the program's tree."""
    batch, seq, _ = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    q = mm(x, w["q_proj"]["kernel"], quant)
    k = mm(x, w["k_proj"]["kernel"], quant)
    v = mm(x, w["v_proj"]["kernel"], quant)
    if qk_norm:  # one RMSNorm over the whole projected width, before the heads split
        q = rms_norm(q, w["q_norm"]["weight"], cfg["rms_norm_eps"])
        k = rms_norm(k, w["k_norm"]["weight"], cfg["rms_norm_eps"])
    q = rotate(q.reshape(batch, seq, heads, dim), cos, sin)
    k = rotate(k.reshape(batch, seq, kv_heads, dim), cos, sin)
    v = v.reshape(batch, seq, kv_heads, dim)
    out = attention(q, k, v, segment_ids, cfg.get("sliding_window"), quant)
    return mm(out, w["o_proj"]["kernel"], quant)


def swiglu(x, w, quant=identity):
    gate = mm(x, w["gate_proj"]["kernel"], quant)
    up = mm(x, w["up_proj"]["kernel"], quant)
    return mm(jax.nn.silu(gate) * up, w["down_proj"]["kernel"], quant)


def decoder_logits(params, cfg, layer_fn, input_ids, segment_ids, position_ids, quant=identity):
    """Full-sequence logits [B, S, V], one jitted layer at a time so that only
    one layer's float32 weights exist at once. `params` is the tree under
    'params' of what the benchmark's initialiser made."""
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]

    @jax.jit
    def embed(table, ids, pos):
        cos, sin = rope_tables(pos, head_dim, cfg["rope_theta"])
        return table.astype(F32)[ids], cos, sin

    @jax.jit
    def one_layer(x, w, seg, cos, sin):
        return layer_fn(x, jax.tree.map(lambda a: a.astype(F32), w), cfg, seg, cos, sin, quant)

    @jax.jit
    def head(x, norm_w, head_w):
        x = rms_norm(x, norm_w.astype(F32), cfg["rms_norm_eps"])
        return mm(x, head_w.astype(F32), quant)

    with exact():
        x, cos, sin = embed(params["embed_tokens"]["embedding"], input_ids, position_ids)
        stack = params["layers"]["layer"]
        for index in range(cfg["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[index], stack)
            x = one_layer(x, w, segment_ids, cos, sin)
        return head(x, params["norm"]["weight"], params["lm_head"]["kernel"])


def token_cross_entropy(logits, labels, valid):
    """(sum of -log p[label] over valid positions, their count)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)), jnp.sum(valid)


def shifted_targets(input_ids, segment_ids):
    """Position i predicts token i+1 when both lie in one document."""
    labels = jnp.concatenate([input_ids[:, 1:], jnp.zeros_like(input_ids[:, :1])], axis=1)
    next_seg = jnp.concatenate([segment_ids[:, 1:], jnp.zeros_like(segment_ids[:, :1])], axis=1)
    return labels, (segment_ids > 0) & (segment_ids == next_seg)
