"""Process start to the start of the loop's first own span (`setup/engine_init`,
`setup/fit_prepare`): imports, the chip's bring-up, the weights. What no change
to a traced program moves."""
from pathlib import Path

from benchmarks import common

LAYER, UNIT, MOVES = "entry (cli, process start)", "s", "setup_s"
timeline = common.load_module(Path(__file__).with_name("_setup_timeline.py"))


def read(trace, counters, cell):
    return timeline.reading(value)


def value(startup):
    opened = timeline.named(startup, *timeline.LOOP_OPENERS)
    if not opened:
        return None
    return opened[0]["ts"] - startup["t0"]
