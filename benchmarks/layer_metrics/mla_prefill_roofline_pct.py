"""Least time the chip could take for the `mla_prefill` calls of the traced window (one a latent-attention block a
chunk) over the time they took. A call's least time counts EXACTLY the (query, key) pairs the chunk may see at the heads'
own widths, the visible latent rows once, `W_kvb` once, the queries and the outputs (`costs/mla_prefill.py`; the rows'
expansion through `W_kvb`, which the kernel does inside, is NOT counted), from `prefill_start` and `prefill_tokens`,
closing args of the engine's `serve/engine_step` spans: a lower bound on the kernel's work, so the share stays under 100.
A program whose chunks attend under `mla_attend` WITHOUT the kernel (this PR's parent: XLA's online softmax over trips)
reads `span_reduce.NOT_A_READING`, logged, not a reading; nothing only where the chunks or the scope are gone."""
from benchmarks import common, span_reduce, trace_reduce
from benchmarks.costs import mla_prefill

LAYER, UNIT, MOVES = "kernels (ops/pallas/mla_prefill.py)", "%", "serve_tok_s"
KERNEL, SCOPE = r"mla_prefill", "mla_attend"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    older = span_reduce.older_program(spans)
    if older is not None:
        return older
    steps = [s["args"] for s in span_reduce.spans_named(spans, "serve/engine_step") if s["args"].get("prefill_chunks")]
    if not steps or any("prefill_start" not in a for a in steps):
        return None
    ops, _ = span_reduce.scoped_ops(spans, program=r"prefill_chunk")
    seconds, calls = trace_reduce.time_by_name(ops, KERNEL)
    if not calls:
        if not any(SCOPE in e[3] for e in ops):
            return None  # neither the kernel nor the scope its call sits under: renamed, or no longer run
        common.log(
            f"no mla_prefill call under {SCOPE} in prefill_chunk: a program older than the kernel "
            f"(its chunks attend in XLA), {span_reduce.NOT_A_READING} is not a reading"
        )
        return span_reduce.NOT_A_READING
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    shape = (
        cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
        cfg["v_head_dim"], 2,
    )
    costs = [mla_prefill.cost(int(a["prefill_start"]), int(a["prefill_tokens"]), *shape) for a in steps]
    mean = lambda values: sum(values) / len(steps)
    by_flops = [c["flops"] / peaks["bf16_flops_per_s"] for c in costs]
    by_bytes = [c["bytes"] / peaks["hbm_bytes_per_s"] for c in costs]
    common.log(
        f"mla_prefill: {calls} calls in {len(steps)} chunks' spans ({calls / len(steps):.2f} a chunk), "
        f"{1e3 * seconds / calls:.4f} ms a call, {mean([c['flops'] for c in costs]) / 1e9:.2f} GFLOP and "
        f"{mean([c['bytes'] for c in costs]) / 1e6:.1f} MB a call, "
        f"bound by {'bytes' if mean(by_bytes) >= mean(by_flops) else 'operations'}, "
        f"a chunk starts at {mean([int(a['prefill_start']) for a in steps]):.0f} tokens on average"
    )
    # a call's mean least time, the larger bound of each chunk, over the chunks the spans hold
    return 100.0 * mean([max(pair) for pair in zip(by_flops, by_bytes)]) * calls / seconds
