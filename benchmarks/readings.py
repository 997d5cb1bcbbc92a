"""Readings that set the limits of `correct`, never part of a benchmark run:
several seeds of one cell in one process (one set-up's compiles shared), each
printing the numbers the check compares, and on the first `--control-seeds`
seeds the same numbers for the control, the reference computed in the
nearest precision below the configuration's.

    python benchmarks/readings.py --workload <cell> --seeds 11,12,13 --control-seeds 3 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import common  # noqa: E402
from benchmarks.references import _common as ref_common  # noqa: E402


def int8_round_trip(x):
    """Straight-through symmetric int8 rounding with one scale per row of the
    last axis' complement (per output channel for a weight). Not a
    configuration's control: read beside it with `--control fp8,int8`, to see
    what the limit lets through."""
    import jax
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=-2 if x.ndim > 1 else 0, keepdims=True) / 127.0 + 1e-30
    q = jnp.round(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)


ref_common.QUANTS["int8"] = int8_round_trip


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--control", default=None, help="comma list; default: the configuration's")
    parser.add_argument("--seconds", type=float, default=30.0)
    args = parser.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    for index, seed in enumerate(seeds):
        cell = common.Cell(ROOT, args.workload)
        runner = cell.module("runners", cell.traffic["kind"])
        names = (args.control or cell.config["control_precision"]).split(",")
        outcome = runner.run(cell, seed, args.seconds, False)
        common.restore_host()
        readings = dict(outcome["readings"])
        if index < args.control_seeds:
            readings.update(outcome["control"](*names))
        common.log("READING " + json.dumps({
            "workload": args.workload, "seed": seed, "correct": outcome["correct"],
            "failed": outcome["failed"], "readings": readings,
            "measured": outcome["measured"], "device": outcome["device"],
        }))
        del outcome
    return 0


if __name__ == "__main__":
    sys.exit(main())
