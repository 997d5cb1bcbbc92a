"""Least time the chip could take for the MLP blocks' REQUIRED operations of the traced steps (`costs/mlp_matmul.py`: 18 x
hidden x intermediate a token a dense layer, x one chip's tokens a step, x the executions of the step on device 0's `XLA
Modules` line) over the device time of the `/mlp/` bucket, which holds the second pass and the elementwise ops: it
cannot pass 100. Not a kernel's share: the block's. Needs no new scope, so parent and change read the same."""
from benchmarks import common, step_reduce
from benchmarks.costs import mlp_matmul

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"


def read(trace, counters, cell):
    if "rows_per_chip" not in counters:
        return None
    found = step_reduce.train_table(cell)
    took = step_reduce.seconds_of(found, "mlp") if found else 0.0
    if not took:
        return None
    cfg, peaks = cell.config, cell.peaks(cell.device["kind"])
    one = mlp_matmul.cost(
        counters["rows_per_chip"] * cell.traffic["seq_len"], cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg["intermediate_size"], 2, cfg.get("num_experts", 0), cfg.get("num_experts_per_tok", 1),
    )
    by_flops, by_bytes = one["flops"] / peaks["bf16_flops_per_s"], one["bytes"] / peaks["hbm_bytes_per_s"]
    steps = found["steps"]
    common.log(
        f"/mlp/: {took / steps:.4f} s a step for {one['flops']:.4e} required operations (the traced ops ran "
        f"{step_reduce.flops_of(found, 'mlp') / steps:.4e}), least {max(by_flops, by_bytes):.4f} s, bound by "
        f"{'bytes' if by_bytes >= by_flops else 'operations'}"
    )
    return 100.0 * max(by_flops, by_bytes) * steps / took
