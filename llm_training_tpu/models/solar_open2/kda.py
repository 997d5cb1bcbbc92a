"""Kimi Delta Attention's recurrence: a delta rule whose decay is a vector a
head, one factor per KEY CHANNEL (qwen3_next's rule has one scalar a head).

A head's state S is [key_dim, value_dim], float32:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with alpha_t in (0, 1)^key_dim and beta_t in [0, 2] (above 1 the transition
has a negative eigenvalue). `kda_step` is that equation for one token: the
decode path. `kda_chunked` advances a whole sequence from a state to a state
in chunks, the prefill and training path.

The chunked form. Let G_i be the running sum of log alpha inside a chunk
(G_i <= 0) and w_i = v_i - S_{i-1}^T (alpha_i * k_i), so that
S_i = Diag(alpha_i) S_{i-1} + beta_i k_i w_i^T. Unrolling from the chunk's
incoming state S_0:

    (I + A Diag(beta)) W = V - (K * e^G) S_0,  A_ij = sum_c k_ic k_jc e^(G_ic - G_jc), j < i
    O = (Q * e^G) S_0 + (B Diag(beta)) W,      B_ij = sum_c q_ic k_jc e^(G_ic - G_jc), j <= i
    S_C = Diag(e^(G_C)) S_0 + (K * e^(G_C - G) * beta)^T W

Every exponent above is a DIFFERENCE of running sums with the later position
first, so it is <= 0 and nothing overflows. Factoring A_ij as
(k_i e^(G_i)) . (k_j e^(-G_j)) would: at log alpha near -5 a step, e^(-G_j)
passes float32's range within 18 tokens. That is why the pairwise decays are
computed from differences, over the chunk's [C, C, key_dim] cube, and why
the chunk is short (16): the cube is what a chunk costs.

Positions that must change nothing (padding, idle decode slots) carry
beta = 0 and log alpha = 0: then S_t = S_{t-1} exactly. `starts` marks the
first token of a packed document: the state is zero before it, which the
chunked form gets by dropping every term that crosses a start.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# the recurrence is float32 end to end: on a TPU a float32 product otherwise
# runs in bfloat16 passes, and the state is summed into over the whole sequence
_EXACT = jax.lax.Precision.HIGHEST


def kda_step(state, q, k, v, log_alpha, beta):
    """One token. state [B, H, dk, dv]; q, k, log_alpha [B, H, dk]; v
    [B, H, dv]; beta [B, H]; all float32 -> (state, out [B, H, dv])."""
    state = state * jnp.exp(log_alpha)[..., None]
    seen = jnp.einsum("bhkv,bhk->bhv", state, k, precision=_EXACT)
    write = (v - seen) * beta[..., None]
    state = state + k[..., None] * write[..., None, :]
    return state, jnp.einsum("bhkv,bhk->bhv", state, q, precision=_EXACT)


def kda_chunked(q, k, v, log_alpha, beta, state, starts=None, chunk_size: int = 16):
    """A sequence, from `state` to the state after it. q, k, log_alpha
    [B, S, H, dk]; v [B, S, H, dv]; beta [B, S, H]; state [B, H, dk, dv];
    starts [B, S] bool or None; all float32 -> (out [B, S, H, dv], state)."""
    batch, seq, heads, _ = q.shape
    c = chunk_size
    pad = (-seq) % c
    if pad:  # zeros: beta 0 and log alpha 0 change nothing
        widen = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, log_alpha, beta = map(widen, (q, k, v, log_alpha, beta))
        starts = None if starts is None else widen(starts)
    chunks = (seq + pad) // c

    def lead(x):  # [B, S, H, ...] -> [chunks, B, H, C, ...]
        x = x.reshape(batch, chunks, c, heads, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, log_alpha, beta = map(lead, (q, k, v, log_alpha, beta))
    g = jnp.cumsum(log_alpha, axis=3)  # [chunks, B, H, C, dk]
    lower = jnp.tril(jnp.ones((c, c), bool))
    # e^(G_i - G_j) for j <= i: the later position first, so never above 1
    decay = jnp.exp(jnp.minimum(g[..., :, None, :] - g[..., None, :, :], 0.0))
    pair = lower
    from_state = to_state = keep = None
    if starts is not None:
        # documents begun inside the chunk up to each position
        begun = jnp.cumsum(
            starts.reshape(batch, chunks, c).transpose(1, 0, 2).astype(jnp.int32), axis=-1
        )[:, :, None]  # [chunks, B, 1, C]
        pair = lower & (begun[..., :, None] == begun[..., None, :])
        from_state = (begun == 0)[..., None]  # still the incoming document
        to_state = (begun == begun[..., -1:])[..., None]  # the outgoing one
        keep = (begun[..., -1] == 0)[..., None]  # [chunks, B, 1, 1]
    decay = jnp.where(pair[..., None], decay, 0.0)
    kk = jnp.sum(k[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    qk = jnp.sum(q[..., :, None, :] * k[..., None, :, :] * decay, axis=-1)
    by_beta = beta[..., None, :]  # column j carries beta_j
    eye = jnp.eye(c, dtype=jnp.float32)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    system = eye + jnp.where(strict, kk * by_beta, 0.0)
    solve = jax.scipy.linalg.solve_triangular(
        system, jnp.broadcast_to(eye, system.shape), lower=True, unit_diagonal=True
    )
    from_g = jnp.exp(g)
    k_in, q_in = k * from_g, q * from_g
    if from_state is not None:
        k_in, q_in = jnp.where(from_state, k_in, 0.0), jnp.where(from_state, q_in, 0.0)
    v_solved = jnp.einsum("nbhij,nbhjv->nbhiv", solve, v, precision=_EXACT)
    k_solved = jnp.einsum("nbhij,nbhjk->nbhik", solve, k_in, precision=_EXACT)
    within = qk * by_beta
    k_out = k * jnp.exp(g[..., -1:, :] - g) * beta[..., None]
    s_keep = jnp.exp(g[..., -1, :])  # [chunks, B, H, dk]
    if to_state is not None:
        k_out = jnp.where(to_state, k_out, 0.0)
        s_keep = jnp.where(keep, s_keep, 0.0)

    def one_chunk(s, xs):
        v_i, k_i, q_i, within_i, k_out_i, keep_i = xs
        w = v_i - jnp.einsum("bhik,bhkv->bhiv", k_i, s, precision=_EXACT)
        out = jnp.einsum("bhik,bhkv->bhiv", q_i, s, precision=_EXACT) + jnp.einsum(
            "bhij,bhjv->bhiv", within_i, w, precision=_EXACT
        )
        s = keep_i[..., None] * s + jnp.einsum("bhik,bhiv->bhkv", k_out_i, w, precision=_EXACT)
        return s, out

    state, out = jax.lax.scan(one_chunk, state, (v_solved, k_solved, q_in, within, k_out, s_keep))
    # [chunks, B, H, C, dv] -> [B, S, H, dv]
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3).reshape(batch, chunks * c, heads, -1)
    return out[:, :seq], state
