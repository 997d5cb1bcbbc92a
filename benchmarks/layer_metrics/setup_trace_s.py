"""Python tracing up to `setup/ready`: the union of the pinned `compile/trace`
spans (jax's `jaxpr_trace_duration`, by program). What no compile cache skips."""
from pathlib import Path

from benchmarks import common

LAYER, UNIT, MOVES = "entry (cli, process start)", "s", "setup_s"
timeline = common.load_module(Path(__file__).with_name("_setup_timeline.py"))


def read(trace, counters, cell):
    return timeline.reading(value)


def value(startup):
    return timeline.compile_union_s(startup, "trace", "setup_trace_s")
