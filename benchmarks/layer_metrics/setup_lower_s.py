"""Lowering to MLIR up to `setup/ready`: the union of the pinned `compile/lower`
spans (jax's `jaxpr_to_mlir_module_duration`, by program)."""
from pathlib import Path

from benchmarks import common

LAYER, UNIT, MOVES = "entry (cli, process start)", "s", "setup_s"
timeline = common.load_module(Path(__file__).with_name("_setup_timeline.py"))


def read(trace, counters, cell):
    return timeline.reading(value)


def value(startup):
    return timeline.compile_union_s(startup, "lower", "setup_lower_s")
