"""Least time the chip could take for the `mla_decode` calls of the traced window (one a latent-attention
block a decode step) over the time they took. Bytes are those of the live tokens' latent rows, 1,152 each, read once
(`costs/mla_decode.py`); the pool stores 1,280 a row, so the padding shows here as roofline lost."""
from benchmarks import common, trace_reduce
from benchmarks.costs import mla_decode

LAYER, UNIT, MOVES = "kernels (ops/pallas/mla_decode.py)", "%", "serve_tok_s"


def read(trace, counters, cell):
    traced = counters.get("traced") or {}
    seconds, calls = trace_reduce.time_by_name(trace["devices"]["0"]["ops"], r"mla_decode")
    if not calls or not seconds or not traced.get("decode_steps"):
        return None
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    one = mla_decode.cost(
        traced["live_tokens"] / traced["decode_steps"], traced["decode_rows"] / traced["decode_steps"],
        cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"], 2,
    )
    by_flops = one["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = one["bytes"] / peaks["hbm_bytes_per_s"]
    common.log(
        f"mla_decode: {calls} calls, {1e3 * seconds / calls:.4f} ms a call, "
        f"{one['bytes'] / 1e6:.1f} MB and {one['flops'] / 1e9:.2f} GFLOP a call, "
        f"bound by {'bytes' if by_bytes >= by_flops else 'operations'}"
    )
    return 100.0 * max(by_flops, by_bytes) * calls / seconds
