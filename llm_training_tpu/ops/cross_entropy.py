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
import math

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


def _local_token_axes(mesh, lead_shape: tuple[int, ...]):
    """Per leading (token) dim of `hidden`, the mesh axes it is split over
    the way the models leave activations (`parallel/sharding.py`: batch over
    data/fsdp/expert, `act_seq` over sequence), None on a dim the axes do
    not divide — the init trace runs with batch 1."""
    from llm_training_tpu.parallel.mesh import (
        DATA_AXIS, EXPERT_AXIS, FSDP_AXIS, SEQUENCE_AXIS,
    )

    groups = ((DATA_AXIS, FSDP_AXIS, EXPERT_AXIS), (SEQUENCE_AXIS,))
    axes = []
    for dim, group in zip(lead_shape, groups):
        group = tuple(a for a in group if mesh.shape.get(a, 1) > 1)
        ways = math.prod(mesh.shape[a] for a in group)
        axes.append(group if group and dim % ways == 0 else None)
    return tuple(axes)


def _on_own_tokens(run, hidden, weight, labels, bias, kept_dims: tuple[int, ...]):
    """`run(hidden, weight, labels, bias)`, each device on its OWN tokens.

    Under an active mesh that splits the token dims (`_local_token_axes`)
    and nothing else, `run` executes inside a shard_map that is manual over
    those axes: hidden and labels arrive split as the model left them, the
    head and bias whole. So the head is gathered over the batch axes once,
    before `run`'s scan, its gradient is reduced once, after the backward
    scan, and inside the scan nothing crosses chips. Left to GSPMD, a scan
    whose chunks span the devices all-reduces every chunk's partial logits
    over the head's fsdp-split `embed`, forward and recomputed backward.

    `kept_dims`: per output of `run`, how many leading token dims it keeps
    (0: a sum over all tokens, 1: `[batch]`, 2: `[batch, seq]`); an output
    is summed over the mesh axes of the token dims it reduced away.

    Falls through to `run` as it stands — one program, partitioned by GSPMD —
    with no mesh, on one device, where the axes do not divide the token
    dims, and on a mesh that splits anything else too (tensor: the
    vocabulary; pipe): that axis would have to stay automatic inside the
    shard_map, and XLA:CPU (jax 0.9.0) aborts on bf16 crossing such a
    partial-auto boundary ("invalid binary instruction opcode copy",
    `models/moe.py`). GSPMD keeps the logits vocab-sharded there
    (`tests/test_ce_sharding.py`).
    """
    from llm_training_tpu.parallel.mesh import active_mesh

    mesh = active_mesh()
    token_axes = _local_token_axes(mesh, hidden.shape[:-1]) if mesh is not None else ()
    flat = lambda groups: tuple(a for group in groups if group for a in group)
    split = flat(token_axes)
    if not split or any(mesh.shape[a] > 1 and a not in split for a in mesh.axis_names):
        return run(hidden, weight, labels, bias)

    from jax.sharding import PartitionSpec as P

    def run_local(*operands):
        outs = []
        for out, kept in zip(run(*operands), kept_dims):
            reduced = flat(token_axes[kept:])
            outs.append(jax.lax.psum(out, reduced) if reduced else out)
        return tuple(outs)

    return jax.shard_map(
        run_local,
        mesh=mesh,
        in_specs=(P(*token_axes, None), P(), P(*token_axes), P()),
        out_specs=tuple(P(*token_axes[:kept]) for kept in kept_dims),
        check_vma=False,
    )(hidden, weight, labels, bias)


def _chunk_nll(h, weight, l, bias, ignore_index, logits_soft_cap):
    """fp32 logits of one chunk -> (per-token NLL, validity mask)."""
    logits = jnp.dot(h, weight, preferred_element_type=jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    return _token_nll(logits, l, ignore_index)


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

    hidden: [tokens, embed] or [batch, seq, embed]
    weight: [embed, vocab] — the lm_head matrix
    bias: [vocab] — the lm_head bias (Phi-style heads), added per chunk
    Returns (sum_nll fp32 scalar, num_valid_tokens int32 scalar); callers
    divide to get the mean so distributed reductions stay exact.

    `chunk_size` bounds ONE DEVICE's logits: `f32[chunk_size, vocab]` (its
    `vocab / tensor` columns under tensor parallelism). On a mesh that
    splits the tokens, a device scans its own tokens in chunks of
    `chunk_size` (`_on_own_tokens`), and only the two scalars and the
    head's gradient cross chips, once a step each.
    """
    embed = hidden.shape[-1]

    def run(hidden, weight, labels, bias):
        hidden = hidden.reshape(-1, embed)
        labels = labels.reshape(-1)
        n_tokens = hidden.shape[0]

        chunk = min(chunk_size, n_tokens)
        num_chunks = -(-n_tokens // chunk)
        pad = num_chunks * chunk - n_tokens
        if pad:
            hidden = jnp.pad(hidden, ((0, pad), (0, 0)))
            labels = jnp.pad(labels, (0, pad), constant_values=ignore_index)

        hidden_chunks = hidden.reshape(num_chunks, chunk, embed)
        label_chunks = labels.reshape(num_chunks, chunk)

        @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
        def chunk_loss(h: jnp.ndarray, l: jnp.ndarray):
            nll, valid = _chunk_nll(h, weight, l, bias, ignore_index, logits_soft_cap)
            return nll.sum(), valid.sum()

        def body(carry, xs):
            total, count = carry
            s, c = chunk_loss(*xs)
            return (total + s, count + c), None

        (total, count), _ = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.int32(0)), (hidden_chunks, label_chunks)
        )
        return total, count

    return _on_own_tokens(run, hidden, weight, labels, bias, kept_dims=(0, 0))


def _sequence_chunks(hidden, labels, chunk_size: int, ignore_index: int):
    """`[batch, seq, ...]` -> `[num_chunks, batch, chunk, ...]` for a scan
    over the sequence with the batch kept, the tail padded with ignored
    labels."""
    batch, seq, embed = hidden.shape
    chunk = min(chunk_size, seq)
    num_chunks = -(-seq // chunk)
    pad = num_chunks * chunk - seq
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=ignore_index)
    hidden_chunks = jnp.moveaxis(hidden.reshape(batch, num_chunks, chunk, embed), 1, 0)
    label_chunks = jnp.moveaxis(labels.reshape(batch, num_chunks, chunk), 1, 0)
    return hidden_chunks, label_chunks


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
    `fused_linear_cross_entropy` but with per-row reductions, and like it
    each device on its own rows (`_on_own_tokens`).
    """

    def run(hidden, weight, labels, bias):
        batch = hidden.shape[0]

        @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
        def chunk_logps(h: jnp.ndarray, l: jnp.ndarray):
            nll, valid = _chunk_nll(h, weight, l, bias, ignore_index, logits_soft_cap)
            return -nll.sum(axis=-1), valid.sum(axis=-1)

        def body(carry, xs):
            total, count = carry
            s, c = chunk_logps(*xs)
            return (total + s, count + c), None

        (logps, counts), _ = jax.lax.scan(
            body,
            (jnp.zeros((batch,), jnp.float32), jnp.zeros((batch,), jnp.int32)),
            _sequence_chunks(hidden, labels, chunk_size, ignore_index),
        )
        return logps, counts

    return _on_own_tokens(run, hidden, weight, labels, bias, kept_dims=(1, 1))


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

    def run(hidden, weight, labels, bias):
        batch, seq, _ = hidden.shape

        @functools.partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
        def chunk_logps(h: jnp.ndarray, l: jnp.ndarray):
            nll, valid = _chunk_nll(h, weight, l, bias, ignore_index, logits_soft_cap)
            return -nll, valid

        def body(carry, xs):
            return carry, chunk_logps(*xs)

        _, (logps, valids) = jax.lax.scan(
            body, None, _sequence_chunks(hidden, labels, chunk_size, ignore_index)
        )
        # [num_chunks, batch, chunk] -> [batch, seq(+pad)] -> strip the pad
        logps = jnp.moveaxis(logps, 0, 1).reshape(batch, -1)[:, :seq]
        valids = jnp.moveaxis(valids, 0, 1).reshape(batch, -1)[:, :seq]
        return logps, valids

    return _on_own_tokens(run, hidden, weight, labels, bias, kept_dims=(2, 2))
