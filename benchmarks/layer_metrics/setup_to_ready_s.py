"""Process start to the program's first useful step (`setup/ready`: both
serving programs have run once; a fit's first step has been fetched).
`setup_s` less this is the ramp and the harness. Logs the loop's own spans."""
from pathlib import Path

from benchmarks import common

LAYER, UNIT, MOVES = "entry (cli, process start)", "s", "setup_s"
timeline = common.load_module(Path(__file__).with_name("_setup_timeline.py"))


def read(trace, counters, cell):
    return timeline.reading(value)


def value(startup):
    ready_s = startup["ready"]["ts"] - startup["t0"]
    spans = timeline.named(startup, *timeline.LOOP_SPANS)
    covered = timeline.union_s(spans)
    common.log(
        f"setup_to_ready_s {ready_s:.3f}: "
        + ", ".join(
            f"{timeline.name(e)}"
            f"{'[' + e['args']['program'] + ']' if 'program' in (e.get('args') or {}) else ''}"
            f" {e['ts'] - startup['t0']:.3f}+{e['dur']:.3f}"
            for e in spans
        )
        + f"; the loop's spans cover {covered:.3f}"
    )
    return ready_s
