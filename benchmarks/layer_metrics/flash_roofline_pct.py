"""Least time for the flash_fwd, flash_bwd_dq and flash_bwd_dkv calls of the
traced window together over the time they took; operations causal and per
document, each call on one chip's rows."""
from benchmarks import common, trace_reduce
from benchmarks.costs import flash

LAYER, UNIT, MOVES = "kernels (ops/pallas/flash_attention.py)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    if "rows_per_chip" not in counters:
        return None
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    heads = cfg["num_attention_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    least = taken = 0.0
    for kernel in flash.PRODUCTS:
        seconds, calls = trace_reduce.time_by_name(trace["devices"]["0"]["ops"], kernel)
        if not calls:
            return None
        one = flash.cost(
            kernel, counters["rows_per_chip"], cell.traffic["documents"], heads,
            cfg["num_key_value_heads"], dim, 2, cfg.get("sliding_window"),
        )
        by_flops = one["flops"] / peaks["bf16_flops_per_s"]
        by_bytes = one["bytes"] / peaks["hbm_bytes_per_s"]
        common.log(
            f"{kernel}: {calls} calls, {1e3 * seconds / calls:.4f} ms a call, least "
            f"{1e3 * max(by_flops, by_bytes):.4f} ms, bound by "
            f"{'bytes' if by_bytes >= by_flops else 'operations'}"
        )
        least += max(by_flops, by_bytes) * calls
        taken += seconds
    return 100.0 * least / taken
