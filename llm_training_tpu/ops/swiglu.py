"""SwiGLU activation.

Capability parity: reference `src/llm_training/ops/swiglu_op.py:5-29`
(separate and fused-weight variants) and the Triton `silu_mul` of
`ops/liger_kernel/swiglu_op.py`. On TPU, `silu(gate) * up` fuses into the
adjacent projections under XLA, so the "fused kernel" is the default path.
"""

import jax
import jax.numpy as jnp


def silu_mul(gate: jnp.ndarray, up: jnp.ndarray, limit: float | None = None) -> jnp.ndarray:
    """silu(gate) * up — the SwiGLU elementwise core. With a `limit` (a
    clamped SwiGLU, `swiglu_limit`): the gate is held under it and the linear
    branch inside +-limit, both before the product."""
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu(x: jnp.ndarray, w_gate_up: jnp.ndarray) -> jnp.ndarray:
    """Fused-weight SwiGLU: x @ [w_gate | w_up] then silu(gate) * up.

    `w_gate_up` is `[embed, 2 * intermediate]` with gate in the first half,
    matching the Phi-3 fused `gate_up_proj` layout
    (reference `models/phi3/phi3_model.py:421`).
    """
    gate_up = x @ w_gate_up
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return silu_mul(gate, up)
