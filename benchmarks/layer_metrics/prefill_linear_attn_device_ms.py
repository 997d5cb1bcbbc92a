"""Device time a `prefill_chunk` execution spends under the linear-attention (KDA) blocks' scope
(`/linear_attn/`): projections, conv, gates, the chunked rule from the slot's state to the slot's state."""
LAYER, UNIT, MOVES = "model step (models/* decode program)", "ms", "itl_p95_ms"


def read(trace, counters, cell):
    return cell.module("layer_metrics", "decode_linear_attn_device_ms").under_ms(cell, r"prefill_chunk")
