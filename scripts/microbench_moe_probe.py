"""Follow-up probes for the MoE grouped-matmul gap (r5).

Reuses the harness from `scripts/microbench_moe.py` (timing discipline,
input builder, ragged/gmm MLPs). Adds, at the 8-expert proxy's shape
(rows 65536, h 2048):
  1. ragged_dot at MXU-aligned width 768 vs the proxy's 704 — how much of
     the gap is lane misalignment? (a builder's r5 run: minor)
  2. gmm tiling sweep — rejected: non-128-multiple expert widths violate
     the megablox kernel's lowering constraints.
  3. a BUCKETED formulation: balanced groups -> fixed per-expert capacity
     buckets -> ONE dense batched matmul [E, C, h] @ [E, h, w] with
     gather/scatter at the edges. Semantics = capacity-factor MoE (drops
     on overflow — surfaced by the ep_dropped_rows metric); the matmul is
     fully dense on the MXU.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp

from scripts.microbench_moe import HIDDEN, ROWS, bench_one


def bucketed_mlp(x, wg, wu, wd, gs):
    """Fixed-capacity buckets + dense bmm. Rows are already expert-sorted
    (as in dropless_moe_apply); bucket e takes rows [start_e, start_e + C)
    of the sorted layout via one gather."""
    E = wg.shape[0]
    cap = ROWS // E  # balanced probe: capacity factor 1.0
    start = jnp.cumsum(gs) - gs
    offs = jnp.arange(cap)
    src = (start[:, None] + offs[None, :]).reshape(-1)  # [E*cap]
    valid = (offs[None, :] < gs[:, None]).reshape(-1)
    xb = (x[jnp.clip(src, 0, ROWS - 1)] * valid[:, None].astype(x.dtype))
    xb = xb.reshape(E, cap, HIDDEN)
    gate = jnp.einsum("ech,ehw->ecw", xb, wg, preferred_element_type=jnp.bfloat16)
    up = jnp.einsum("ech,ehw->ecw", xb, wu, preferred_element_type=jnp.bfloat16)
    yb = jnp.einsum("ecw,ewh->ech", jax.nn.silu(gate) * up, wd,
                    preferred_element_type=jnp.bfloat16)
    y = jnp.zeros((ROWS, HIDDEN), yb.dtype)
    return y.at[jnp.clip(src, 0, ROWS - 1)].add(
        yb.reshape(-1, HIDDEN) * valid[:, None].astype(yb.dtype)
    )


def main():
    print("| case | impl | pass | ms/iter | MXU eff |")
    print("|---|---|---|---|---|")
    for E, W in ((8, 704), (8, 768), (64, 256)):
        for p in ("fwd", "bwd"):
            t, eff = bench_one(E, W, "ragged", p == "bwd")
            print(f"| {E}x{W} | ragged | {p} | {t*1e3:.2f} | {eff:.3f} |", flush=True)
    for E, W in ((8, 704), (64, 256)):
        for p in ("fwd", "bwd"):
            try:
                t, eff = bench_one(E, W, "bucketed", p == "bwd", mlp=bucketed_mlp)
                print(f"| {E}x{W} | bucketed | {p} | {t*1e3:.2f} | {eff:.3f} |", flush=True)
            except Exception as e:
                print(f"| {E}x{W} | bucketed | {p} | FAIL | {str(e)[:60]} |", flush=True)


if __name__ == "__main__":
    main()
