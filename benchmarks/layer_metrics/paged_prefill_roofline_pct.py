"""Least time the chip could take for the `paged_prefill` calls of the traced window over the time they took. A call is
one layer's attention for one chunk; its least time counts EXACTLY the (query, key) pairs the chunk may see and the pages
that hold a visible key, once (`costs/paged_prefill.py`), from `prefill_start` and `prefill_tokens`, closing args of the
engine's `serve/engine_step` spans: a lower bound on the kernel's work, so the share stays under 100. In a stack with two
page groups a global layer's call sees every key up to the query's, a window layer's its window at most; the groups' calls
are summed by their layer counts. Logs each group's calls (under `attn_global` and `attn_window`, where the stack names
them). A program older than the kernel says no `prefill_start`: that is `span_reduce.NOT_A_READING`, logged, not a reading."""
from benchmarks import common, span_reduce, trace_reduce
from benchmarks.costs import paged_prefill

LAYER, UNIT, MOVES = "kernels (ops/pallas/paged_attention.py)", "%", "itl_p95_ms"
KERNEL = r"paged_prefill"
SCOPES = {"global": "attn_global", "window": "attn_window"}


def groups(cfg: dict) -> dict:
    """{group: (layers, window)} of the stack's key/value layers."""
    window = cfg.get("sliding_window")
    if "layer_types" not in cfg:
        return {"global": (cfg["num_hidden_layers"], window)}
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return {"global": (kinds.count("full_attention"), None), "window": (kinds.count("sliding_attention"), window)}


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    older = span_reduce.older_program(spans)
    if older is not None:
        return older
    steps = [s["args"] for s in span_reduce.spans_named(spans, "serve/engine_step") if s["args"].get("prefill_chunks")]
    if not steps:
        return None
    if any("prefill_start" not in a for a in steps):
        common.log(
            "no prefill_start on serve/engine_step: a program older than the paged_prefill kernel, "
            f"{span_reduce.NOT_A_READING} is not a reading"
        )
        return span_reduce.NOT_A_READING
    ops, _ = span_reduce.scoped_ops(spans, program=r"prefill_chunk")
    seconds, calls = trace_reduce.time_by_name(ops, KERNEL)
    if not calls:
        return None  # the kernel is gone from a program that says where its chunks start
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    heads = cfg["num_attention_heads"]
    shape = (
        cell.traffic["engine"]["block_size"], heads, cfg["num_key_value_heads"],
        cfg.get("head_dim") or cfg["hidden_size"] // heads, 2,
    )
    least = lambda part: max(part["flops"] / peaks["bf16_flops_per_s"], part["bytes"] / peaks["hbm_bytes_per_s"])
    mine = groups(cfg)
    # a group's mean least time a call, over the chunks the spans hold
    a_call = {
        g: sum(
            least(paged_prefill.cost(int(a["prefill_start"]), int(a["prefill_tokens"]), window, *shape)) for a in steps
        ) / len(steps)
        for g, (_, window) in mine.items()
    }
    for g, (layers, _) in mine.items():
        took, n = trace_reduce.time_by_name([e for e in ops if SCOPES[g] in e[3]], KERNEL)
        if n:
            common.log(
                f"paged_prefill under {SCOPES[g]}: {n} calls, {1e3 * took / n:.4f} ms a call, "
                f"{100.0 * a_call[g] * n / took:.2f}% of its roofline ({layers} layers)"
            )
    layers = sum(n for n, _ in mine.values())
    common.log(
        f"paged_prefill: {calls} calls in {len(steps)} chunks' spans, {1e3 * seconds / calls:.4f} ms a call, "
        f"a chunk starts at {sum(int(a['prefill_start']) for a in steps) / len(steps):.0f} tokens on average"
    )
    # one call a layer a chunk: a chunk's least time, over the chunks whose calls the trace holds
    return 100.0 * sum(n * a_call[g] for g, (n, _) in mine.items()) * (calls / layers) / seconds
