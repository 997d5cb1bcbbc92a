"""The engine's host time a step outside its waits for the device: mean over
the traced `serve/engine_step` spans of the span less its `prefill_fetch` and
`decode_fetch` children. How much of it the device idles through is the idle
table's to say (`serve_idle_outside_spans_pct` logs it)."""
from benchmarks import common, span_reduce
from benchmarks.trace_reduce import NS

LAYER, UNIT, MOVES = "serving (serve/engine.py, serve/scheduler.py)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    if (older := span_reduce.older_program(spans)) is not None:
        return older
    steps = span_reduce.spans_named(spans, "serve/engine_step")
    if not steps:
        return None
    for step in span_reduce.longest_steps(spans):
        common.log(f"longest engine_step {step}")
    host_ns = [span_reduce.less_ns(spans, s, span_reduce.FETCHES) for s in steps]
    return 1e3 * NS * sum(host_ns) / len(host_ns)
