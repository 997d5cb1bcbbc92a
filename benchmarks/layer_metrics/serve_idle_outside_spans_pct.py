"""Device idle seconds whose gap lies under no `llmt/serve/` span, over all
idle seconds of the traced window: what the harness and untraced code cost
the device. Logs the whole table: idle seconds by the innermost span."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "device", "%", "serve_tok_s"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    if (older := span_reduce.older_program(spans)) is not None:
        return older
    if not span_reduce.spans_named(spans, "serve/engine_step") or not spans["devices"].get("0", {}).get("ops"):
        return None
    table = span_reduce.idle_by_span(spans, prefix="serve/")
    idle = sum(table.values())
    if not idle:
        return None
    common.log(f"idle seconds by span (sum {idle:.6f}): " + ", ".join(f"{k} {v:.6f}" for k, v in table.items()))
    return 100.0 * table.get("outside", 0.0) / idle
