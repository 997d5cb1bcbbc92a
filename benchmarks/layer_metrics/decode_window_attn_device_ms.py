"""Device time a `decode_step` execution spends under `attn_window`: the append and the paged attention of the layers that
keep a sliding window, against their own page group. Logs `attn_global` (the layers that keep everything) beside it, and
the two as shares of what lies under `/self_attn/` (`decode_attn_device_ms`)."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"decode_step")
    if not calls:
        return None
    ms = lambda *needles: 1e3 * span_reduce.seconds_under(ops, *needles) / calls
    window, globe, attn = ms("attn_window"), ms("attn_global"), ms(span_reduce.ATTN)
    if not window or not attn:
        return None  # no time under it: the scope is gone
    common.log(
        f"decode_step device ms: attn_window {window:.4f} ({100 * window / attn:.1f}% of /self_attn/ {attn:.4f}), "
        f"attn_global {globe:.4f} ({100 * globe / attn:.1f}%), attn_gate {ms('attn_gate'):.4f}"
    )
    return window
