"""Where a serve cell's `setup_s` goes, before the benchmark has a reader for
it (ROADMAP S9): one run of `benchmarks/run.py`'s own path for a closed-loop
serve cell, with wall-clock marks on its phases and jax's own compile events
summed beside them. Nothing of `benchmarks/` is changed: the runner's
functions are wrapped from here, in this process.

Run it from the ROOT OF THE TREE it is to measure (a `git archive` of the
parent, say): that tree's `benchmarks/` and `llm_training_tpu/` are the ones
imported, whichever tree holds this file.

    cd <tree> && python <repo>/scripts/setup_split.py --workload solar2-serve-longdoc \\
        --seed 2147000001 --seconds 5 --no-check --out chiprun_out/pr47/split.jsonl --label parent

Marks, seconds since the process's first line (as `setup_s` counts them,
from `benchmarks/common.py:T_PROCESS_START`, which is the first import):
`imported` (jax, flax, the package, the benchmark), `weights` (the model's
shapes and its seeded leaves on the device), `engine` (`ServingEngine(...)`:
pools, slab, the jitted programs' wrappers), `first_prefill` and
`first_decode` (the first call of each program returned: its trace, its
lowering, its compile or cache read; the device's work is not waited for),
`ramp` (every client decoding, four steps more, the pool ready: `setup_s`).
Events (`jax.monitoring`, summed): `trace` and `lower` of each serving program
by its name (`/jax/core/compile/jaxpr_trace_duration`,
`jaxpr_to_mlir_module_duration`: what every process pays before a cache key
exists, and what `compile_s` does not hear), `backend_compile` (what
`compile_s` hears: the backend's compile or the cache's read), and the cache's
own `cache_retrieval_time_sec`. `--no-check` leaves the float32 reference out
(a minute or two a run that tells `setup_s` nothing): `correct` is then not
a reading.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path.cwd()
sys.path.insert(0, str(ROOT))

PROGRAMS = ("prefill_chunk", "decode_step")
EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--no-check", action="store_true")
    parser.add_argument("--rehearse", action="store_true", help="off the chip: marks and events, no metric")
    parser.add_argument("--label", default="")
    parser.add_argument("--out", default="")
    args = parser.parse_args()

    marks: dict[str, float] = {}
    events: dict[str, float] = {}
    counts: dict[str, int] = {}

    def mark(name):
        marks.setdefault(name, time.perf_counter() - T0)

    import jax.monitoring

    from benchmarks import common, run as bench
    from llm_training_tpu.serve import engine as serve_engine

    def on_duration(event, duration, **kwargs):
        kind = EVENTS.get(event)
        if kind is None:
            return
        name = str(kwargs.get("fun_name", ""))
        name = name.removeprefix("jit(").removesuffix(")")
        if kind in ("trace", "lower"):
            # an inner function's trace lies inside its program's: only the
            # programs' own are disjoint
            key = f"{kind}/{name}" if name in PROGRAMS else f"{kind}/inner_and_other"
        else:
            key = kind
        events[key] = events.get(key, 0.0) + duration
        counts[key] = counts.get(key, 0) + 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    mark("imported")

    cell = common.Cell(ROOT, args.workload)
    # the check a cell's runner makes: `serve_closed`'s, or the runner's own
    runners = [cell.module("runners", kind) for kind in {"serve_closed", cell.traffic["kind"]}]
    init = serve_engine.ServingEngine.__init__

    def timed_init(self, *a, **kw):
        mark("weights")
        init(self, *a, **kw)
        mark("engine")
        for program, jitted in (("first_prefill", "_prefill_jit"), ("first_decode", "_decode_jit")):
            call = getattr(self, jitted)

            def first(*args, _call=call, _program=program, **kwargs):
                out = _call(*args, **kwargs)
                mark(_program)
                return out

            setattr(self, jitted, first)

    serve_engine.ServingEngine.__init__ = timed_init
    quiet = common.quiet_host

    def ramp_ends():
        quiet()
        mark("ramp")

    common.quiet_host = ramp_ends
    if args.no_check:
        for runner in runners:
            if hasattr(runner, "served_gaps"):
                runner.served_gaps = lambda cell, variables, finished, control=(): {
                    "served_logit_gap": 0.0, "tokens_compared": 0, "requests": len(finished)
                }
    # what the runner counted of the compiler (`compile_s`, `compiles`, `cache_hits`)
    counted: dict = {}
    log = common.log

    def heard(*parts):
        if len(parts) == 2 and parts[0] == "counters":
            counted.update({k: parts[1][k] for k in ("compile_s", "compiles", "cache_hits")})
        log(*parts)

    common.log = heard
    # the benchmark counts from its own first import: so do the marks
    offset = T0 - common.T_PROCESS_START

    result = bench.run_cell(
        ROOT, args.workload, args.seed, args.seconds, False, require_tpu=not args.rehearse
    )
    record = {
        "label": args.label, "root": str(ROOT), "workload": args.workload, "seed": args.seed,
        "checked": not args.no_check, "correct": result["correct"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "compiler": counted,
        "marks_s": {k: round(v + offset, 3) for k, v in marks.items()},
        "events_s": {k: round(v, 3) for k, v in sorted(events.items())},
        "event_counts": dict(sorted(counts.items())),
        "device": result["device"].get("kind"),
    }
    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        with out.open("a") as handle:
            handle.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
