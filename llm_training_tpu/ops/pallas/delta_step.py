"""Pallas TPU kernel for ONE token of a delta rule on the states of a decode
slab, written where they lie.

KDA's step (`models/solar_open2/kda.py:kda_step`) and the gated delta rule's
(`ops/delta_rule.py:gated_delta_step`) are one recurrence on a head's float32
state S `[dk, dv]`; they differ in what the decay multiplies and in which
state `q` reads, and the kernel takes that from the decay it is handed:

- a decay a KEY CHANNEL, `decay [B, H, dk]` (KDA), `kda_step`'s order:

      D = Diag(a) S;  u = beta (v - D^T k);  S' = D + k u^T;  o = S'^T q

- a decay a HEAD, `decay [B, H]` (the gated delta rule), `gated_delta_step`'s
  form, both reductions over the OLD state so that they share its one pass:

      u = beta (v - a S^T k);  o = a S^T q + (k . q) u;  S' = a S + k u^T

The states are worked on as they are STORED (`ops/delta_rule.py`, "The STORED
state"): `[P, dk, n * dv]`, `n = H / P` heads side by side on the lanes. A
head's `k`, `q` (and KDA's `a`) meet their lanes of a stored head's tile
through a select over a lane iota, `n` static; `v`, `u`, `o`, `beta` (and the
gated rule's `a` and `k . q`) lie on the lanes as the state does.

`delta_step`: grid (row, block of stored heads). The slab `[layers, slots, P,
dk, n * dv]` is aliased to the output: the index map picks layer `layer`'s
row `b` (`layer` rides as scalar prefetch), a block comes into VMEM once and
goes back to the rows it came from, and every other row of the slab is left
as it lies. Nothing of a layer's states' shape is produced. Everything is
float32 on the vector unit: no product goes through the matrix unit's
bfloat16 passes. An idle row (beta 0, decay 1) gets its state back bit for
bit.

The token's key-axis vectors come in lane-major, `[.., kinds * n * hb, dk]` a
grid step (k, q and a of the block's heads), and are turned inside the kernel,
one transpose a step: as `[.., dk, 1]` arrays the chip would pad each to 128
lanes in HBM, as many bytes as the state. The block's stored heads are a
`fori_loop` over trips of `_HELD` heads, not a Python loop over the block:
the kernel's module holds a trip's bodies whatever the block (a block of 16
unrolled, traced and lowered once a call site, is what a process start paid
for: PERF.md section 6, PR 46 and 47). One rotation of the turned vectors a
trip brings its heads to lanes that are static.

The call goes through ONE jitted function (`delta_step`): the layers of a
looped or scanned body share its trace and its lowered module, as
`moe_experts/jit(gmm)` does.

Off-TPU the kernel runs interpreted; on a TPU it always compiles.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# stored heads a trip of the block's loop takes (those of them that divide the
# block): ONE rotation of the turned vectors, 16 vregs through the XLU, serves
# them all, at that many copies of a head's body in the module. Tried on a v5e
# (PR 47, `scripts/delta_step_sweep.py`, KDA `[64, 128, 128]` at 16 heads a
# step, ms a layer): 1 head a trip 0.604 (a rotation a head is a third of the
# step), 2 0.479, 4 0.452, 8 0.450, all 16 0.449; the gated rule `[15, 96,
# 384]` at 5 a step: 1 a trip 0.244, all 5 0.240
_HELD = 4


def _delta_step_kernel(
    layer,      # scalar prefetch: [1] the layer of the slab (the index maps read it)
    keys_ref,   # [kinds * n * hb, dk]: k, q (and a) of the block's heads, lane-major
    lanes_ref,  # [(2 or 4) * hb, n * dv]: v, beta (and a, k . q) of the block's stored heads
    slab_in,    # [hb, dk, n * dv]: the block's states
    out_ref,    # [hb, n * dv]
    slab_out,   # the same rows of the slab: aliased
    *,
    abreast: int,
    value_dim: int,
    decay_a_key: bool,
):
    del layer
    block, key_dim, lanes = slab_in.shape
    heads = abreast * block
    keys = keys_ref[...]
    # the turn, once a step, on whole tiles: [128, dk] -> [dk, 128], vector
    # `kind` of the block's head `i` on lane `kind * heads + i`
    keys = jnp.concatenate(
        [keys, jnp.zeros((_LANES - keys.shape[0], key_dim), jnp.float32)], axis=0
    )
    columns = keys.T
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

    def one_stored_head(p, mine, first):
        """Stored head `p`, its heads' vectors on lanes `kind * heads + first
        + j` of `mine`, j < n."""

        def column(kind):
            """`[dk, 1]` a head, each on its lanes of the tile."""
            at = kind * heads + first
            wide = mine[:, at:at + 1]
            for j in range(1, abreast):
                wide = jnp.where(lane >= j * value_dim, mine[:, at + j:at + j + 1], wide)
            return wide

        row = lambda i: lanes_ref[pl.ds(i * block + p, 1), :]  # [1, n dv]
        k, q, v, beta = column(0), column(1), row(0), row(1)
        state = slab_in[p]
        if decay_a_key:
            state = state * column(2)
            write = beta * (v - jnp.sum(state * k, axis=0, keepdims=True))
            state = state + k * write
            out = jnp.sum(state * q, axis=0, keepdims=True)
        else:
            decay, k_dot_q = row(2), row(3)
            seen = jnp.sum(state * k, axis=0, keepdims=True)
            read = jnp.sum(state * q, axis=0, keepdims=True)
            write = beta * (v - decay * seen)
            out = decay * read + k_dot_q * write
            state = decay * state + k * write
        out_ref[pl.ds(p, 1), :] = out
        slab_out[p] = state

    held = math.gcd(_HELD, block)

    def some_stored_heads(group, _):
        # ONE rotation brings the group's heads to lanes that are static
        first = group * held
        mine = pltpu.roll(columns, (_LANES - abreast * first) % _LANES, 1)
        for i in range(held):
            one_stored_head(first + i, mine, abreast * i)

    jax.lax.fori_loop(0, block // held, some_stored_heads, None)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def delta_step(
    slab: jnp.ndarray,
    layer: jnp.ndarray,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    decay: jnp.ndarray,
    beta: jnp.ndarray,
    *,
    block: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row on layer `layer` (an int32 scalar) of the slab
    `[layers, B, P, dk, n * dv]` float32, row i the state of batch row i. q, k
    `[B, H, dk]`; v `[B, H, dv]`; beta `[B, H]`; `decay` (in (0, 1]) `[B, H,
    dk]`, a key channel, or `[B, H]`, a head: which rule this is (the
    module's text); all float32. `block` stored heads a grid step
    (`ops/pallas/tuning.py:delta_step_heads`). -> (out `[B, H, dv]`, the slab
    with that layer's rows advanced in place)."""
    _, batch, stored, key_dim, lanes = slab.shape
    heads, value_dim = v.shape[1:]
    abreast = heads // stored
    if slab.dtype != jnp.float32 or abreast * stored != heads or abreast * value_dim != lanes:
        raise ValueError(f"slab {slab.shape} {slab.dtype} does not hold {heads} heads of {v.shape}")
    if q.shape != (batch, heads, key_dim):
        raise ValueError(f"row i is slot i: got q {q.shape} for a slab {slab.shape}")
    decay_a_key = decay.ndim == 3
    steps = stored // block
    by_step = lambda x: x.reshape(batch, steps, -1, x.shape[-1])
    on_lanes = lambda x: by_step(x.reshape(batch, stored, lanes))
    per_lane = lambda x: on_lanes(jnp.broadcast_to(x[..., None], v.shape))
    if decay_a_key:
        key_vectors, lane_vectors = (k, q, decay), (on_lanes(v), per_lane(beta))
    else:
        key_vectors = (k, q)
        lane_vectors = (
            on_lanes(v), per_lane(beta), per_lane(decay), per_lane(jnp.sum(k * q, axis=-1))
        )
    keys = jnp.concatenate([by_step(x) for x in key_vectors], axis=2)
    if keys.shape[2] > _LANES:
        raise ValueError(f"a block of {block} stored heads turns {keys.shape[2]} vectors a step: over {_LANES}")
    lane_rows = jnp.concatenate(lane_vectors, axis=2)
    vectors = lambda x: pl.BlockSpec((None, None, *x.shape[2:]), lambda b, g, layer: (b, g, 0, 0))
    states = pl.BlockSpec(
        (None, None, block, key_dim, lanes), lambda b, g, layer: (layer[0], b, g, 0, 0)
    )
    out_rows = jax.ShapeDtypeStruct((batch, steps, block, lanes), jnp.float32)
    out, slab = pl.pallas_call(
        functools.partial(
            _delta_step_kernel, abreast=abreast, value_dim=value_dim, decay_a_key=decay_a_key
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, steps),
            in_specs=[vectors(keys), vectors(lane_rows), states],
            out_specs=[vectors(out_rows), states],
        ),
        out_shape=[out_rows, jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
        # operands count the scalar-prefetch array
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="delta_step",
    )(layer.astype(jnp.int32).reshape(1), keys, lane_rows, slab)
    return out.reshape(batch, heads, value_dim), slab
