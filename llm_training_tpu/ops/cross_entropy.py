"""Cross-entropy losses, including chunked fused-linear-CE.

Capability parity: reference `src/llm_training/ops/cross_entropy_op.py:4-8`
(`shift_labels`) and the liger Triton kernels
`ops/liger_kernel/cross_entropy_op.py:10-54` (`cross_entropy`,
`fused_linear_cross_entropy`).

The fused-linear variant is the TPU-idiomatic equivalent of liger's kernel:
instead of a hand-written Triton kernel that never materializes the full
`[tokens, vocab]` logit tensor, we chunk the token axis with `lax.scan` and
wrap the chunk body in `jax.checkpoint`, so both forward and backward peak at
`O(chunk_size * vocab)` logits. XLA fuses the matmul + logsumexp + gather per
chunk onto the MXU/VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shift_labels(labels: jnp.ndarray, ignore_index: int = -100) -> jnp.ndarray:
    """Next-token shift: labels[i] = input[i+1]; final position is ignored."""
    shifted = jnp.roll(labels, -1, axis=-1)
    return shifted.at[..., -1].set(ignore_index)


def _token_nll(logits32: jnp.ndarray, labels: jnp.ndarray, ignore_index: int):
    """Per-token negative log-likelihood (fp32) and validity mask."""
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    label_logits = jnp.take_along_axis(logits32, safe_labels[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, lse - label_logits, 0.0)
    return nll, valid


def cross_entropy(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    ignore_index: int = -100,
    reduction: str = "mean",
) -> jnp.ndarray:
    """Cross-entropy over the last dim of `logits`, fp32 accumulation.

    reduction: 'mean' (over non-ignored tokens), 'sum', or 'none'.
    """
    nll, valid = _token_nll(logits.astype(jnp.float32), labels, ignore_index)
    if reduction == "none":
        return nll
    if reduction == "sum":
        return nll.sum()
    if reduction == "mean":
        return nll.sum() / jnp.maximum(valid.sum(), 1).astype(jnp.float32)
    raise ValueError(f"unknown reduction {reduction!r}")


@jax.named_scope("loss_ce")  # a device profile reads the head + loss by this name
def fused_linear_cross_entropy(
    hidden: jnp.ndarray,
    weight: jnp.ndarray,
    labels: jnp.ndarray,
    ignore_index: int = -100,
    chunk_size: int = 1024,
    logits_soft_cap: float | None = None,
    bias: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """CE of `hidden @ weight (+ bias)` against `labels` without full logits.

    hidden: [tokens, embed] (any leading shape is flattened)
    weight: [embed, vocab] — the lm_head matrix
    bias: [vocab] — the lm_head bias (Phi-style heads), added per chunk
    Returns (sum_nll fp32 scalar, num_valid_tokens int32 scalar); callers
    divide to get the mean so distributed reductions stay exact.
    """
    embed = hidden.shape[-1]
    hidden = hidden.reshape(-1, embed)
    labels = labels.reshape(-1)
    n_tokens = hidden.shape[0]

    chunk_size = min(chunk_size, n_tokens)
    num_chunks = -(-n_tokens // chunk_size)
    pad = num_chunks * chunk_size - n_tokens
    if pad:
        hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=ignore_index)

    hidden_chunks = hidden.reshape(num_chunks, chunk_size, embed)
    label_chunks = labels.reshape(num_chunks, chunk_size)

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_loss(h: jnp.ndarray, l: jnp.ndarray):
        logits = jnp.dot(h, weight, preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        if logits_soft_cap is not None:
            logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
        nll, valid = _token_nll(logits, l, ignore_index)
        return nll.sum(), valid.sum()

    def body(carry, xs):
        total, count = carry
        s, c = chunk_loss(*xs)
        return (total + s, count + c), None

    (total, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.int32(0)), (hidden_chunks, label_chunks)
    )
    return total, count


def fused_linear_log_probs(
    hidden: jnp.ndarray,
    weight: jnp.ndarray,
    labels: jnp.ndarray,
    ignore_index: int = -100,
    chunk_size: int = 1024,
    logits_soft_cap: float | None = None,
    bias: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-sequence label log-probs of `hidden @ weight` without full logits.

    hidden: [batch, seq, embed]; labels: [batch, seq].
    Returns (sum log p per row [batch] fp32, valid-token counts [batch]).
    The DPO/ORPO building block (reference `dpo.py:89-108`,
    `orpo.py:60-93`): chunked over the sequence axis with rematerialized
    chunks, so peak memory is O(batch * chunk * vocab) — the same trick as
    `fused_linear_cross_entropy` but with per-row reductions.
    """
    batch, seq, embed = hidden.shape
    chunk_size = min(chunk_size, seq)
    num_chunks = -(-seq // chunk_size)
    pad = num_chunks * chunk_size - seq
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=ignore_index)

    # [num_chunks, batch, chunk, ...] for scan
    hidden_chunks = jnp.moveaxis(
        hidden.reshape(batch, num_chunks, chunk_size, embed), 1, 0
    )
    label_chunks = jnp.moveaxis(
        labels.reshape(batch, num_chunks, chunk_size), 1, 0
    )

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_logps(h: jnp.ndarray, l: jnp.ndarray):
        logits = jnp.dot(h, weight, preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        if logits_soft_cap is not None:
            logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
        nll, valid = _token_nll(logits, l, ignore_index)
        return -nll.sum(axis=-1), valid.sum(axis=-1)

    def body(carry, xs):
        total, count = carry
        s, c = chunk_logps(*xs)
        return (total + s, count + c), None

    (logps, counts), _ = jax.lax.scan(
        body,
        (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32)),
        (hidden_chunks, label_chunks),
    )
    return logps, counts


def fused_linear_token_log_probs(
    hidden: jnp.ndarray,
    weight: jnp.ndarray,
    labels: jnp.ndarray,
    ignore_index: int = -100,
    chunk_size: int = 1024,
    logits_soft_cap: float | None = None,
    bias: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-TOKEN label log-probs of `hidden @ weight` without full logits.

    hidden: [batch, seq, embed]; labels: [batch, seq].
    Returns (log p per token [batch, seq] fp32 — 0.0 at ignore_index
    positions — and the validity mask [batch, seq] bool). The GRPO
    building block (lms/grpo.py): a token-level policy gradient needs
    each completion token's logp under policy and reference, not a
    per-sequence sum, but must still never materialize [batch, seq,
    vocab] logits — same chunked-remat scan as `fused_linear_log_probs`,
    stacking per-chunk results instead of reducing them.
    """
    batch, seq, embed = hidden.shape
    chunk_size = min(chunk_size, seq)
    num_chunks = -(-seq // chunk_size)
    pad = num_chunks * chunk_size - seq
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=ignore_index)

    hidden_chunks = jnp.moveaxis(
        hidden.reshape(batch, num_chunks, chunk_size, embed), 1, 0
    )
    label_chunks = jnp.moveaxis(
        labels.reshape(batch, num_chunks, chunk_size), 1, 0
    )

    @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_logps(h: jnp.ndarray, l: jnp.ndarray):
        logits = jnp.dot(h, weight, preferred_element_type=jnp.float32)
        if bias is not None:
            logits = logits + bias.astype(jnp.float32)
        if logits_soft_cap is not None:
            logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
        nll, valid = _token_nll(logits, l, ignore_index)
        return -nll, valid

    def body(carry, xs):
        return carry, chunk_logps(*xs)

    _, (logps, valids) = jax.lax.scan(body, None, (hidden_chunks, label_chunks))
    # [num_chunks, batch, chunk] -> [batch, seq(+pad)] -> strip the pad
    logps = jnp.moveaxis(logps, 0, 1).reshape(batch, -1)[:, :seq]
    valids = jnp.moveaxis(valids, 0, 1).reshape(batch, -1)[:, :seq]
    return logps, valids
