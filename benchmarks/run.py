"""The benchmark's one command:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It reads the cell from BENCHMARK.json and finds everything else by name:
configs/<config>.json, traffic/<traffic>.json (whose `kind` names the runner
runners/<kind>.py), references/<name>.py, layer_metrics/<metric>.py,
costs/<kernel>.py. The last line of standard output is the result."""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import common  # noqa: E402


def run_cell(root: Path, workload: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result object (the last line, parsed).
    `require_tpu=False` is the CPU rehearsal: counts and the check's
    readings, no device metric."""
    cell = common.Cell(root, workload)
    runner = cell.module("runners", cell.traffic["kind"])
    outcome = runner.run(cell, seed, seconds, trace, require_tpu=require_tpu)
    device = outcome["device"]
    cell.device = device
    result = {
        "correct": outcome["correct"], "attempted": outcome["attempted"],
        "failed": outcome["failed"], "metrics": {}, "device": device,
    }
    if not require_tpu:
        result["counters"] = outcome["counters"]
        result["readings"] = outcome["readings"]
        return result
    if not trace:
        for metric in cell.metrics("end_to_end"):
            result["metrics"][metric["name"]] = {
                "value": outcome["measured"][metric["name"]], "unit": metric["unit"],
            }
        return result
    from benchmarks import trace_reduce

    reduced = trace_reduce.load(common.newest_xplane(outcome["trace_dir"]))
    lo, hi = trace_reduce.window_ns(reduced)
    busy = trace_reduce.busy_s(reduced)
    device["busy_s"] = sum(busy.values()) / len(busy)
    device["window_s"] = (hi - lo) * trace_reduce.NS
    for metric in cell.metrics("per_layer"):
        reader = cell.module("layer_metrics", metric["name"])
        value = reader.read(reduced, outcome["counters"], cell)
        if value is None:
            # BENCHMARK.json says this cell has something for this reader: a
            # program, kernel or counter has been renamed or is no longer run
            raise SystemExit(
                f"per-layer metric {metric['name']} found nothing to read in {cell.name}"
            )
        result["metrics"][metric["name"]] = {"value": value, "unit": metric["unit"]}
    result["breakdown"] = trace_reduce.breakdown(reduced)
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    print(common.result_line(
        result["correct"], result["attempted"], result["failed"], result["metrics"],
        result["device"], result.get("breakdown"),
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
