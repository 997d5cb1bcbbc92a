"""The backend's seconds up to `setup/ready` (jax's `backend_compile_duration`:
the compiler, or the persistent cache's read in its place): the pinned
`compile/backend` spans plus the short ones' sum, which do not nest. `compile_s`
from inside the program; the log says WHICH program, and what the cache read."""
from pathlib import Path

from benchmarks import common

LAYER, UNIT, MOVES = "entry (cli, process start)", "s", "setup_s"
timeline = common.load_module(Path(__file__).with_name("_setup_timeline.py"))


def read(trace, counters, cell):
    return timeline.reading(value)


def value(startup):
    spans = timeline.named(startup, "compile/backend")
    cached = [e for e in spans if "cache_read_s" in (e.get("args") or {})]
    pinned = sum(e["dur"] for e in spans)
    short_s, short_n = timeline.short(startup, "backend")
    read_s, read_n = timeline.short(startup, "cache_read")
    common.log(
        f"setup_backend_compile_s {pinned + short_s:.3f} = {len(spans)} pinned compile/backend "
        f"spans {pinned:.3f} by fun: {timeline.by_fun(spans) or 'none'}; plus {short_n} under "
        f"0.1 s, {short_s:.3f} s; of the pinned, cache reads by fun: "
        f"{timeline.by_fun(cached, lambda e: e['args']['cache_read_s']) or 'none'}"
        f" ({read_n} reads under 0.1 s, {read_s:.3f} s)"
    )
    return pinned + short_s
