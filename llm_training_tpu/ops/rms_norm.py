"""RMS normalization.

Capability parity: reference `src/llm_training/ops/rms_norm_op.py:4-14` (fp32
upcast, variance over last dim) and the Triton-fused
`ops/liger_kernel/rms_norm_op.py`. On TPU this function is left to XLA: the
scale and the multiplies fuse into the neighbouring matmuls, the statistic
(a reduction over the hidden width) cannot, and stays a device op of its own
or rides out of a neighbour's fusion as a second output. That costs little
but not nothing, and `rms_norm` runs under a named scope of that name so a
device profile says how much: 0.96% of the benchmark's train step (most of it
the backward's two passes over the activation), 0.008 to 0.07 ms of a decode
step and 0.03 to 0.3 ms of a 512-token chunk (chip runs, PR 37: PERF.md
section 5; `train_norm_device_pct`, `decode_norm_device_ms`,
`prefill_norm_device_ms`; docs/observability.md lists the readers).
"""

import jax
import jax.numpy as jnp


@jax.named_scope("rms_norm")  # a device profile reads the norms by this name
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """y = weight * (x / rms(x)).

    The normalization runs in fp32; the normalized value is rounded back to
    x.dtype *before* the weight multiply, matching the reference's order of
    operations (`rms_norm_op.py:4-14`: `weight * x_normed.to(input_dtype)`) so
    bf16 activations produce bit-identical results.
    """
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    variance = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    x_normed = (x32 * jax.lax.rsqrt(variance + eps)).astype(dtype)
    return weight * x_normed


def gated_rms_norm(
    x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6, gating_weight: float = 2.0
) -> jnp.ndarray:
    """y = gating_weight * sigmoid(weight) * (x / rms(x)): a zero-centred
    gated norm. `weight` is learned from 0, where the scale is `gating_weight
    / 2` (1 at the published 2), and the scale stays inside (0,
    `gating_weight`). The statistic is `rms_norm`'s, under its scope."""
    scale = gating_weight * jax.nn.sigmoid(weight.astype(jnp.float32))
    return rms_norm(x, scale.astype(x.dtype), eps)
