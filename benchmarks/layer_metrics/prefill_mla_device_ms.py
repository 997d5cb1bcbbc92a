"""Device time a `prefill_chunk` execution spends under the latent-attention (MLA) blocks' scope (`/self_attn/`):
projections and norms (`mla_q`, `mla_kv`), the cached latents' expansion through `kv_b` (`mla_expand`) or the absorbed
products (`mla_absorb`), the scores and values over the row's pages (`mla_attend`), the output projection (`mla_out`)."""
from benchmarks import common, span_reduce

LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "itl_p95_ms"
SCOPE = "/self_attn/"
PARTS = ("mla_q", "mla_kv", "mla_expand", "mla_absorb", "mla_attend", "mla_out")


def read(trace, counters, cell):
    ops, calls = span_reduce.scoped_ops(span_reduce.for_cell(cell), program=r"prefill_chunk")
    if not calls:
        return None
    mine = [e for e in ops if SCOPE in e[3]]
    parts = {p: 1e3 * span_reduce.seconds_under(mine, p) / calls for p in PARTS}
    common.log("mla device ms a prefill_chunk: " + ", ".join(f"{k} {v:.4f}" for k, v in parts.items()))
    return 1e3 * span_reduce.seconds_under(mine, SCOPE) / calls or None  # no time under it: the scope is gone
