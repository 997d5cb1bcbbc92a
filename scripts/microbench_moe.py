"""Microbenchmark the MoE grouped-matmul primitive on the chip.

In rounds 4-5 an 8-expert MoE proxy trained well under its dense twin's
utilisation on builders' chip runs (never re-taken by the driver: PERF.md
section 7), and much of the gap was put down to the `jax.lax.ragged_dot`
lowering at E=8/width-704. This measures the three-projection expert MLP
(gate/up -> silu*mul -> down) as a unit — fwd and fwd+bwd — for:

- `ragged`: jax.lax.ragged_dot (the XLA lowering training uses)
- `gmm`: the Pallas megablox grouped-matmul kernel bundled with jax
  (jax.experimental.pallas.ops.tpu.megablox.ops.gmm, custom VJP included)

across expert counts E=8 (that proxy) and E=64/E=256-class widths
(DeepSeek-style fine-grained experts), with balanced groups (a trained
router's are near-balanced). MXU eff credits 3 * 2*rows*h*w FLOPs (fwd;
x3 for fwd+bwd) against the nominal v5e peak.

Timing per scripts/microbench_ops.py: chained iterations in one jit,
per-rep salt, completion proven by fetching bytes.

Usage:
  python scripts/microbench_moe.py
  CASES=8x704,64x176 IMPLS=ragged,gmm PASSES=fwd,bwd python scripts/microbench_moe.py
"""

from __future__ import annotations

import functools
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

ITERS = 8
_PEAK = 197e12  # v5e nominal bf16
_RNG = np.random.default_rng(0)

HIDDEN = int(os.environ.get("MOE_HIDDEN", 2048))
# the proxy's rows: 2048 seq * 16 batch * top-2. ROWS is overridable so new
# graph shapes (e.g. the bucketed gather/scatter probe) can be validated
# small first, before a chip call is spent on the full size.
ROWS = int(os.environ.get("MOE_ROWS", 65536))


def _fetch(out) -> None:
    jax.device_get(jax.tree.leaves(out)[0].ravel()[:8])


def _timed(fn, *args) -> float:
    _fetch(fn(jnp.bfloat16(0.0), *args))  # compile
    times = []
    for rep in range(1, 4):
        t0 = time.perf_counter()
        _fetch(fn(jnp.bfloat16(rep * 1e-3), *args))
        times.append((time.perf_counter() - t0) / ITERS)
    return float(np.median(times))


def _expert_mlp(impl: str):
    if impl == "gmm":
        from jax.experimental.pallas.ops.tpu.megablox.ops import gmm

        dot = functools.partial(gmm, preferred_element_type=jnp.bfloat16)
    else:
        dot = jax.lax.ragged_dot

    def mlp(x, wg, wu, wd, gs):
        gate = dot(x, wg, gs)
        up = dot(x, wu, gs)
        return dot(jax.nn.silu(gate) * up, wd, gs)

    return mlp


def bench_one(n_experts: int, width: int, impl: str, bwd: bool, mlp=None):
    x = jnp.asarray(_RNG.standard_normal((ROWS, HIDDEN)) * 0.1, jnp.bfloat16)
    wg = jnp.asarray(_RNG.standard_normal((n_experts, HIDDEN, width)) * 0.02, jnp.bfloat16)
    wu = jnp.asarray(_RNG.standard_normal((n_experts, HIDDEN, width)) * 0.02, jnp.bfloat16)
    wd = jnp.asarray(_RNG.standard_normal((n_experts, width, HIDDEN)) * 0.02, jnp.bfloat16)
    gs = jnp.full((n_experts,), ROWS // n_experts, jnp.int32)  # balanced
    if mlp is None:
        mlp = _expert_mlp(impl)

    if not bwd:
        @jax.jit
        def run(salt, x, wg, wu, wd, gs):
            def body(carry, _):
                y = mlp(x + carry, wg, wu, wd, gs)
                return y[0, 0].astype(jnp.bfloat16), None

            y, _ = jax.lax.scan(body, salt, None, length=ITERS)
            return y
    else:
        def loss(x, wg, wu, wd, gs):
            return jnp.sum(mlp(x, wg, wu, wd, gs).astype(jnp.float32) ** 2)

        grad = jax.grad(loss, argnums=(0, 1, 2, 3))

        @jax.jit
        def run(salt, x, wg, wu, wd, gs):
            def body(carry, _):
                # every gradient output must feed the carry, or jax's DCE
                # removes the dw matmuls from the timed graph entirely
                gx, gg, gu, gd = grad(x + carry, wg, wu, wd, gs)
                live = gx[0, 0] + gg[0, 0, 0] + gu[0, 0, 0] + gd[0, 0, 0]
                return live.astype(jnp.bfloat16), None

            y, _ = jax.lax.scan(body, salt, None, length=ITERS)
            return y

    t = _timed(run, x, wg, wu, wd, gs)
    n_mm = 3 if not bwd else 9  # bwd: dx + dw per projection (2x) + fwd recompute
    flops = n_mm * 2 * ROWS * HIDDEN * width
    return t, flops / t / _PEAK


def main():
    # (E, width): 8x704 = the proxy (total expert params == 697M dense
    # MLP); E-sweeps hold TOTAL params constant so MFU is comparable;
    # 64x2048-class = DeepSeek-V3-like wide-E fine-grained shape at h2048
    cases = [
        tuple(int(v) for v in c.split("x"))
        for c in os.environ.get(
            "CASES", "8x704,16x352,64x88,8x2048,64x256,256x64"
        ).split(",")
    ]
    impls = os.environ.get("IMPLS", "ragged,gmm").split(",")
    passes = os.environ.get("PASSES", "fwd,bwd").split(",")
    print(f"| E | width | impl | pass | ms/iter | MXU eff | rows {ROWS} h {HIDDEN} |")
    print("|---|---|---|---|---|---|---|")
    for n_experts, width in cases:
        for impl in impls:
            for p in passes:
                try:
                    t, eff = bench_one(n_experts, width, impl, p == "bwd")
                    print(
                        f"| {n_experts} | {width} | {impl} | {p} "
                        f"| {t*1e3:.2f} | {eff:.3f} |",
                        flush=True,
                    )
                except Exception as e:  # shape/lowering limits: record, move on
                    print(
                        f"| {n_experts} | {width} | {impl} | {p} | FAIL "
                        f"| {type(e).__name__}: {str(e)[:60]} |",
                        flush=True,
                    )


if __name__ == "__main__":
    main()
