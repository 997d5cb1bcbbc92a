"""Least time the chip could take for the `paged_decode` calls of the traced window over the time they took, in a stack
whose layers are in two page groups: a global layer's call reads its rows' live tokens, a window layer's call of each row
its window at most (`window_live_tokens`, a closing arg of the engine's `serve/engine_step` spans; `costs/
paged_decode_groups.py`). Logs both groups' calls (those under `attn_global` and under `attn_window`) and the MB a call."""
from benchmarks import common, span_reduce, trace_reduce
from benchmarks.costs import paged_decode_groups

LAYER, UNIT, MOVES = "kernels (ops/pallas/paged_attention.py)", "%", "serve_tok_s"
KERNEL = r"paged_decode"
SCOPES = {"global": "attn_global", "window": "attn_window"}


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    steps = [s["args"] for s in span_reduce.spans_named(spans, "serve/engine_step") if s["args"].get("decode_rows")]
    if not steps or any("window_live_tokens" not in a for a in steps):
        return None  # a program whose engine does not count a window group's reads
    ops, _ = span_reduce.scoped_ops(spans, program=r"decode_step")
    # (seconds, calls) of the kernel under each group's scope
    took = {g: trace_reduce.time_by_name([e for e in ops if scope in e[3]], KERNEL) for g, scope in SCOPES.items()}
    if not all(calls for _, calls in took.values()):
        return None  # the kernel or a group's scope is gone
    cfg, peaks, n = cell.config, cell.peaks(cell.device["kind"]), len(steps)
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    heads = cfg["num_attention_heads"]
    one = paged_decode_groups.cost(
        sum(int(a["live_tokens"]) for a in steps) / n, sum(int(a["window_live_tokens"]) for a in steps) / n,
        sum(int(a["decode_rows"]) for a in steps) / n,
        kinds.count("full_attention"), kinds.count("sliding_attention"),
        heads, cfg["num_key_value_heads"], cfg["head_dim"], 2,
    )
    least = lambda part: max(part["flops"] / peaks["bf16_flops_per_s"], part["bytes"] / peaks["hbm_bytes_per_s"])
    for g, (seconds, calls) in took.items():
        common.log(
            f"paged_decode under {SCOPES[g]}: {calls} calls, {1e3 * seconds / calls:.4f} ms a call, "
            f"{one[g]['bytes'] / 1e6:.1f} MB a call, {100.0 * least(one[g]) * calls / seconds:.2f}% of its roofline"
        )
    # one call a layer a step: the step's least time, over the steps whose calls the trace holds
    traced_steps = took["global"][1] / kinds.count("full_attention")
    return 100.0 * least(one) * traced_steps / sum(seconds for seconds, _ in took.values())
