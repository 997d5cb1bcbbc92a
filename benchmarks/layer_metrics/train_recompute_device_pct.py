"""Share of the train step's device time on device 0 spent in ops whose name stack holds `rematted_computation`: the
forward run a second time inside the backward under `jax.checkpoint` (models/remat.py), every bucket together; the table
logs it by bucket. The marker is jax's own, so a program older than this reader reads the same. Logs the operations the
compiler counts for the ops that ran over `required_ops`' for the step: the device-side twin of the registry's
`xla/flops_per_step` over the same, which is logged where the process's registry holds it."""
from benchmarks import common, required_ops, step_reduce

LAYER, UNIT, MOVES = "model (models/phi3, train step)", "%", "train_tok_s_chip"
GAUGE = "xla/flops_per_step"


def read(trace, counters, cell):
    found = step_reduce.train_table(cell)
    if found is None:
        return None
    if "rows_per_chip" in counters:
        tokens = counters["rows_per_chip"] * cell.traffic["seq_len"]
        required = tokens * required_ops.train_flops_per_token(cell.config, cell.traffic["documents"])
        ran = step_reduce.flops_of(found) / found["steps"]
        common.log(
            f"operations a chip a step: required {required:.4e}, run by the traced ops {ran:.4e} "
            f"(x {ran / required:.3f}; of them recomputed {step_reduce.flops_of(found, None, 'recompute') / found['steps']:.4e}; "
            "a Pallas kernel counts none)"
        )
        from llm_training_tpu.telemetry.registry import get_registry

        compiled = get_registry().snapshot().get(GAUGE)
        if compiled:
            common.log(f"{GAUGE} {compiled:.4e}: x {compiled / required:.3f} of the required operations")
    return step_reduce.share_pct(found, None, "recompute")
