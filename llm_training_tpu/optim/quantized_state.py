"""Block-quantized storage codec for offloaded optimizer state.

The host-offloaded optimizer round trip is transfer-bound: fp32 mu/nu of a
916M-param model are ~14.7 GB a step, there and back, over the host link,
and a builder's r5 chip run (never re-taken by the driver: PERF.md section
7) found the per-leaf "overlapped" chains hide none of it, because the update
compute they overlap with is negligible next to the transfers. The lever
that works is shrinking the bytes: store mu as block-wise int8 and nu as
block-wise uint8 of sqrt(nu) (8-bit-Adam-style state compression — the
capability analogue of DeepSpeed's quantized ZeRO-offload knobs,
`/root/reference/src/llm_training/lightning/strategy/deepspeed/deepspeed_strategy.py:70-102`),
cutting the round trip 4x while mu/nu still never reside in HBM between
steps.

Codec design:
- symmetric int8 ("sym", for mu and any signed state): per-block scale =
  max|x|/127 over BLOCK consecutive elements of the last axis;
  dequant = q * scale. Round-to-nearest; the quantization error decays
  geometrically under the EMA (mu' = b1*dq(q(mu)) + (1-b1)g).
- sqrt-uint8 ("sqrt", for nu / adafactor v*): quantize r = sqrt(nu) —
  halves the dynamic range the linear scale must span — with CEIL
  rounding, so the dequantized nu is an upper bound of the true value
  wherever it underestimates the scale grid. Adam divides by
  sqrt(nu_hat)+eps: over-estimating nu only shrinks a coordinate's step
  (safe); under-estimating it (in particular quantizing a tiny nu to 0)
  would multiply the step by up to sqrt(nu_true)/eps — catastrophic. Ceil
  bounds every per-coordinate step from above by its true-Adam value.

Arrays whose last axis is not a multiple of the block (tiny gates/scalars)
stay fp32 — their transfer cost is noise. Scales are fp32 at 1/BLOCK the
element count (1.6% overhead at 256).
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

DEFAULT_BLOCK = 256

# Quantization is a WHITELIST of known optimizer state fields — anything
# unrecognized stays fp32. This is what makes the codec safe under wrapper
# transforms: e.g. optax.MultiSteps' acc_grads accumulator repeatedly adds
# small per-micro-batch gradients, which block quantization would zero out;
# it is not listed, so it passes through exact.
#   sym codec: first-moment / momentum EMAs (quantization error decays
#   geometrically under the EMA update). 'ema' is adafactor's momentum
#   (optax appends optax.transform.ema when momentum is set)
_SYM_FIELDS = {"mu", "trace", "ema"}
#   sqrt codec: non-negative second-moment accumulators (adam/adamw nu,
#   adafactor v/v_row/v_col)
_NONNEG_FIELDS = {"nu", "v", "v_row", "v_col"}


@flax.struct.dataclass
class QuantArray:
    """Block-quantized stand-in for one fp32 optimizer-state array.

    q keeps the original array shape (int8 for "sym", uint8 for "sqrt") so
    it inherits the parent array's sharding spec unchanged; scale has the
    last axis divided by `block`. `kind`/`block` are treedef constants —
    checkpoints restore them from the abstract target, not from disk.
    """

    q: Any
    scale: Any
    kind: str = flax.struct.field(pytree_node=False)
    block: int = flax.struct.field(pytree_node=False)

    @property
    def shape(self):
        return self.q.shape

    @property
    def dtype(self):  # the logical dtype (what dequantize returns)
        return jnp.float32


def _blocked(x: jnp.ndarray, block: int) -> jnp.ndarray:
    return x.reshape(*x.shape[:-1], x.shape[-1] // block, block)


def quantize_array(x: jnp.ndarray, kind: str, block: int) -> QuantArray:
    xb = _blocked(x.astype(jnp.float32), block)
    if kind == "sym":
        scale = jnp.max(jnp.abs(xb), axis=-1) / 127.0
        q = jnp.round(xb / jnp.maximum(scale, 1e-30)[..., None])
        q = jnp.clip(q, -127, 127).astype(jnp.int8)
    elif kind == "sqrt":
        r = jnp.sqrt(xb)
        scale = jnp.max(r, axis=-1) / 254.0
        # ceil with a slack of 5e-4 grid steps: large enough to absorb the
        # fp32 rounding of a dequantize->requantize cycle (~6e-5 steps at
        # code 254), so the codec is GRID-IDEMPOTENT — re-encoding an
        # unchanged state reproduces q and scale exactly instead of
        # ratcheting codes upward (the serialized offload path re-encodes
        # every accumulation micro-step). Weakens the never-underestimate
        # guarantee by at most 5e-4 steps — noise against the sqrt(nu)/eps
        # blowup the ceil protects from
        q = jnp.ceil(r / jnp.maximum(scale, 1e-30)[..., None] - 5e-4)
        # the slack must never let a NONZERO nu encode to 0 — dequantized
        # nu = 0 is the sqrt(nu)/eps catastrophe this codec exists to
        # prevent. Floor positive inputs at code 1 (idempotent: code 1
        # dequantizes to exactly one step, which re-encodes to 1)
        q = jnp.maximum(q, (xb > 0).astype(q.dtype))
        q = jnp.clip(q, 0, 255).astype(jnp.uint8)
    else:
        raise ValueError(f"unknown quantization kind {kind!r}")
    return QuantArray(
        q=q.reshape(x.shape), scale=scale.astype(jnp.float32), kind=kind, block=block
    )


def dequantize_array(qa: QuantArray) -> jnp.ndarray:
    xb = _blocked(qa.q.astype(jnp.float32), qa.block) * qa.scale[..., None]
    if qa.kind == "sqrt":
        xb = xb * xb
    return xb.reshape(qa.q.shape)


def _codec_kind(path) -> str | None:
    """Which codec this leaf's optax state field gets (None = keep fp32).

    State trees nest as (chain idx, state-namedtuple field, *param-tree
    path): namedtuple fields flatten to GetAttrKey (which has .name), while
    param-tree keys are DictKey (.key) — so checking only .name entries
    against the field sets cannot be fooled by a model param literally
    named 'v', and survives wrapper states (MaskedState, MultiStepsState)
    that add their own GetAttrKeys around the field. A non-negative match
    wins over a sym match (no current optax state nests one inside the
    other, but under-stepping is the safe direction)."""
    names = {getattr(entry, "name", None) for entry in path}
    if names & _NONNEG_FIELDS:
        return "sqrt"
    if names & _SYM_FIELDS:
        return "sym"
    return None


def _boxed(ref, value):
    """Re-wrap value in ref's Partitioned box (sharding metadata), if any."""
    if isinstance(ref, nn.Partitioned):
        return ref.replace_boxed(value)
    return value


def _unboxed(leaf):
    return leaf.value if isinstance(leaf, nn.Partitioned) else leaf


def encode_state(state: Any, block: int = DEFAULT_BLOCK) -> Any:
    """Quantize every eligible fp32 array in an optax state tree.

    Eligible: floating arrays with ndim >= 1 whose last axis is a multiple
    of `block`, under a WHITELISTED optimizer field (mu/trace -> "sym",
    nu/v* -> "sqrt"; anything else — counts, MultiSteps grad accumulators,
    unknown fields — stays exact). Partitioned boxes are preserved AROUND
    q and scale so the abstract tree still carries per-array sharding
    metadata.
    """

    def enc(path, leaf):
        value = _unboxed(leaf)
        kind = _codec_kind(path)
        if (
            kind is None
            or not hasattr(value, "ndim")
            or value.ndim < 1
            or not jnp.issubdtype(value.dtype, jnp.floating)
            or value.shape[-1] % block != 0
        ):
            return leaf
        qa = quantize_array(value, kind, block)
        return QuantArray(
            q=_boxed(leaf, qa.q), scale=_boxed(leaf, qa.scale),
            kind=kind, block=block,
        )

    return jax.tree_util.tree_map_with_path(
        enc, state, is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )


def decode_state(state: Any) -> Any:
    """Inverse of encode_state: QuantArray leaves back to fp32 arrays."""

    def dec(leaf):
        if isinstance(leaf, QuantArray):
            qa = QuantArray(
                q=_unboxed(leaf.q), scale=_unboxed(leaf.scale),
                kind=leaf.kind, block=leaf.block,
            )
            return _boxed(leaf.q, dequantize_array(qa))
        return leaf

    return jax.tree.map(dec, state, is_leaf=lambda x: isinstance(x, QuantArray))


def cast_state(state: Any, dtype) -> Any:
    """Elementwise storage cast (the "bfloat16" offload dtype): floating
    arrays under whitelisted fields are stored as `dtype`; ints/scalars and
    unlisted fields (e.g. MultiSteps grad accumulators, whose repeated
    small adds need fp32) stay."""

    def cast(path, leaf):
        value = _unboxed(leaf)
        if (
            _codec_kind(path) is not None
            and hasattr(value, "ndim")
            and value.ndim >= 1
            and jnp.issubdtype(value.dtype, jnp.floating)
        ):
            return _boxed(leaf, value.astype(dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(
        cast, state, is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )


def uncast_state(state: Any, dtype=jnp.float32) -> Any:
    return cast_state(state, dtype)
