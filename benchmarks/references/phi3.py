"""Plain reference for the Phi-3 decoder (microsoft/Phi-3-medium-4k-instruct):
pre-norm blocks of grouped-query attention with rotary positions and a
sliding window, and a SwiGLU MLP; untied head. The published fused qkv and
gate_up matrices are held split, as the program holds them (same product).

For training it also follows the published recipe of the program's objective
and optimizer in float32: mean next-token cross entropy inside documents,
gradient clipped by its global norm, AdamW."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.references import _common as c


def layer(x, w, cfg, segment_ids, cos, sin, quant=c.identity):
    eps = cfg["rms_norm_eps"]
    h = c.rms_norm(x, w["input_layernorm"]["weight"], eps)
    x = x + c.gqa_block(h, w["self_attn"], cfg, segment_ids, cos, sin, quant)
    h = c.rms_norm(x, w["post_attention_layernorm"]["weight"], eps)
    return x + c.swiglu(h, w["mlp"], quant)


def logits(params, cfg, input_ids, segment_ids, position_ids, quant=c.identity):
    return c.decoder_logits(params, cfg, layer, input_ids, segment_ids, position_ids, quant)


# ------------------------------------------------------------------ training


def _loss_sum(params, cfg, batch, quant):
    """Sum of token losses and their count over a block of rows; the whole
    stack in one program with each layer recomputed in the backward pass."""
    ids, seg, pos = batch["input_ids"], batch["segment_ids"], batch["position_ids"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    cos, sin = c.rope_tables(pos, head_dim, cfg["rope_theta"])
    x = params["embed_tokens"]["embedding"][ids]

    @jax.checkpoint
    def body(x, w):
        return layer(x, w, cfg, seg, cos, sin, quant), None

    x, _ = jax.lax.scan(body, x, params["layers"]["layer"])
    x = c.rms_norm(x, params["norm"]["weight"], cfg["rms_norm_eps"])
    out = c.mm(x, params["lm_head"]["kernel"], quant)
    labels, valid = c.shifted_targets(ids, seg)
    return c.token_cross_entropy(out, labels, valid)


def make_step(cfg, optim, quant=c.identity, shardings=None):
    """One jitted AdamW step: (params, m, v, blocks, t) -> (params, m, v, loss,
    clipped gradient's norm per leaf); `blocks` holds [blocks, rows, seq] arrays."""
    lr, b1, b2 = optim["learning_rate"], optim["b1"], optim["b2"]
    eps, decay, clip = optim["eps"], optim["weight_decay"], optim["grad_clip_norm"]

    def mean_loss(p, blocks):
        def one_block(carry, block):
            total, count = jax.checkpoint(lambda q, b: _loss_sum(q, cfg, b, quant))(p, block)
            return (carry[0] + total, carry[1] + count), None

        (total, count), _ = jax.lax.scan(one_block, (jnp.float32(0.0), jnp.int32(0)), blocks)
        return total / count.astype(c.F32)

    def step(p, m, v, blocks, t):
        loss, grads = jax.value_and_grad(mean_loss)(p, blocks)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        factor = jnp.minimum(1.0, clip / (norm + 1e-30)) if clip else 1.0
        grads = jax.tree.map(lambda g: g * factor, grads)
        leaf_norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), v, grads)

        def update(w, a, s):
            direction = (a / (1 - b1**t)) / (jnp.sqrt(s / (1 - b2**t)) + eps) + decay * w
            return w - lr * direction

        return jax.tree.map(update, p, m, v), m, v, loss, leaf_norms

    return jax.jit(
        step, static_argnums=4, donate_argnums=(0, 1, 2),
        **({} if shardings is None else
           {"out_shardings": (shardings[0], shardings[0], shardings[0], None, None)}),
    )


def train_steps(fresh_params, cfg, optim, batches, rows_per_block, quant=c.identity, shardings=None):
    """Follow `len(batches)` optimizer steps from `fresh_params()`, the float32
    starting parameters (called again at the end for the change: holding a
    second copy through the steps would not fit beside the moments).

    Returns per step the loss, and per leaf the norm of the first clipped
    gradient (what AdamW is handed) and of the parameters' change after the
    last step. `batches` are host arrays; a step walks its rows in blocks of
    `rows_per_block`, each block recomputed in the backward pass.
    `shardings` = (params sharding tree, sharding of [blocks, rows, seq])
    when the state lives across chips; the arithmetic is the same."""
    step = make_step(cfg, optim, quant, shardings)
    out = {} if shardings is None else {"out_shardings": shardings[0]}
    delta = jax.jit(lambda a, b: jax.tree.map(lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t), **out)
    put = jnp.asarray if shardings is None else (lambda a: jax.device_put(a, shardings[1]))

    with c.exact():
        params = fresh_params()
        m, v = zeros(params), zeros(params)
        losses, first_grad_norms = [], None
        for t, batch in enumerate(batches, start=1):
            blocks = {
                k: put(a.reshape(-1, rows_per_block, a.shape[-1])) for k, a in batch.items()
            }
            params, m, v, loss, leaf_norms = step(params, m, v, blocks, t)
            losses.append(float(loss))
            if first_grad_norms is None:
                first_grad_norms = jax.device_get(leaf_norms)
        del m, v
        change_norms = jax.device_get(delta(params, fresh_params()))
    return {"losses": losses, "grad_norms": first_grad_norms, "change_norms": change_norms}
