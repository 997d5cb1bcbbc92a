"""Device time a `decode_step` execution spends under `attn_global`: the ONE full key/value layer's append and paged
attention and, under `attn_cross` inside it, the paged attention of the layers that read that layer's pages and append
nothing (`llm_training_tpu/models/phi4flash/model.py`). Logs the two apart, and from the engine's `shared_kv_reads` (a closing
arg of `serve/engine_step`: layer-reads of pages by layers that wrote none of them) the pages and MB a step the readers
fetch and the rate that is. An engine that does not count such reads has no such layers: None."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "serve_tok_s"


def read(trace, counters, cell):
    spans = span_reduce.for_cell(cell)
    steps = [s["args"] for s in span_reduce.spans_named(spans, "serve/engine_step") if s["args"].get("decode_rows")]
    ops, calls = span_reduce.scoped_ops(spans, program=r"decode_step")
    if not calls or not steps or any("shared_kv_reads" not in a for a in steps):
        return None
    ms = lambda *needles: 1e3 * span_reduce.seconds_under(ops, *needles) / calls
    shared, cross = ms("attn_global"), ms("attn_cross")
    if not shared or not cross:
        return None  # no time under it: the scope is gone
    cfg, engine = cell.config, cell.traffic["engine"]
    heads = cfg["num_attention_heads"]
    page_bytes = engine["block_size"] * cfg["num_key_value_heads"] * (cfg.get("head_dim") or cfg["hidden_size"] // heads) * 2 * 2
    pages = sum(int(a["shared_kv_reads"]) for a in steps) / len(steps)
    common.log(
        f"decode_step device ms: attn_global {shared:.4f}, of it attn_cross {cross:.4f} (the readers) and "
        f"{shared - cross:.4f} the writing layer; shared_kv_reads {pages:.0f} pages a step, "
        f"{pages * page_bytes / 1e6:.1f} MB, {pages * page_bytes / (cross * 1e6):.1f} GB/s under attn_cross"
    )
    return shared
