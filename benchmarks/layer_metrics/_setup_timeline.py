"""The program's own start-up timeline, as five readers share it: the process
tracer's pinned events (`llm_training_tpu/telemetry/trace.py`: `setup/*` spans
from the loops, `compile/*` spans from jax's compile events) up to the FIRST
pinned instant `setup/ready`, on the clock `setup_s` is taken on
(`time.perf_counter()`, counted from `common.T_PROCESS_START`). What the
process compiles after that (the reference, after the window) is never read.

`load()` gives that timeline; or `span_reduce.NOT_A_READING`, logged, for a
program older than the pinned store (the driver traces a PR's parent with the
PR's benchmark laid over it); or None, which ends a traced run, where the
store is there and no `setup/ready` is in it."""

from __future__ import annotations

from benchmarks import common, span_reduce

# the spans that open a loop's own part of start-up
LOOP_OPENERS = ("setup/engine_init", "setup/fit_prepare")
LOOP_SPANS = LOOP_OPENERS + ("setup/first_call", "train/compile", "setup/first_step")


def reading(value):
    """`value(timeline)`, or what `load()` gave in a timeline's place."""
    startup = load()
    return value(startup) if isinstance(startup, dict) else startup


def load():
    from llm_training_tpu.telemetry.trace import get_tracer

    tracer = get_tracer()
    if not hasattr(tracer, "pinned"):
        common.log(
            "the process tracer has no pinned store: a program older than the start-up "
            f"timeline, {span_reduce.NOT_A_READING} is not a reading"
        )
        return span_reduce.NOT_A_READING
    return timeline(tracer.pinned(), common.T_PROCESS_START)


def timeline(events: list[dict], t0: float) -> dict | None:
    """`{"t0", "ready": the first setup/ready, "events": what ended by then}`."""
    ready = min(
        (e for e in events if name(e) == "setup/ready"), key=lambda e: e["ts"], default=None
    )
    if ready is None:
        return None
    before = [
        e for e in events
        if e is not ready and e["ts"] + e.get("dur", 0.0) <= ready["ts"]
    ]
    return {"t0": t0, "ready": ready, "events": sorted(before, key=lambda e: e["ts"])}


def name(event: dict) -> str:
    return f"{event['cat']}/{event['name']}"


def named(startup: dict, *names: str) -> list[dict]:
    return [e for e in startup["events"] if name(e) in names]


def union_s(spans: list[dict]) -> float:
    """Seconds that some span of `spans` covers: a layer's self time by the
    guide's rule (an inner function's long trace lies inside its program's
    and counts once)."""
    total, end = 0.0, float("-inf")
    for start, stop in sorted((e["ts"], e["ts"] + e["dur"]) for e in spans):
        total += max(0.0, stop - max(start, end))
        end = max(end, stop)
    return total


def by_fun(spans: list[dict], seconds=lambda e: e["dur"]) -> str:
    """`fun seconds (count)` of each program or function, longest first."""
    table: dict[str, list] = {}
    for e in spans:
        row = table.setdefault((e.get("args") or {}).get("fun", "?"), [0.0, 0])
        row[0] += seconds(e)
        row[1] += 1
    return ", ".join(
        f"{fun} {total:.3f} ({count})"
        for fun, (total, count) in sorted(table.items(), key=lambda kv: -kv[1][0])
    )


def short(startup: dict, kind: str) -> tuple[float, int]:
    """What the events of `kind` under the listener's 0.1 s summed to by
    `setup/ready`, and how many they were (the instant's args)."""
    args = startup["ready"].get("args") or {}
    return float(args.get(f"{kind}_short_s", 0.0)), int(args.get(f"{kind}_short_n", 0))


def compile_union_s(startup: dict, kind: str, metric: str) -> float:
    """The seconds the pinned `compile/<kind>` spans cover up to `setup/ready`,
    logged by `fun` with the short events' sum beside them (which lies inside
    the union more often than not: logged, not added)."""
    spans = named(startup, f"compile/{kind}")
    value = union_s(spans)
    short_s, short_n = short(startup, kind)
    common.log(
        f"{metric} {value:.3f} = union of {len(spans)} pinned compile/{kind} spans by fun: "
        f"{by_fun(spans) or 'none'}; beside them {short_n} events under 0.1 s, {short_s:.3f} s"
    )
    return value
