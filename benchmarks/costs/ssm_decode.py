"""Operations and bytes one Mamba-1 layer's decode recurrence needs for one
token a row (`llm_training_tpu/ops/selective_scan.py:selective_step`, the ops
under the `ssm_step` scope): each decoding row's LOGICAL float32 state
[channels, d_state] read once and written once, the token's vectors (x and
delta in and y out a channel, B and C a state value) and `A` once a call,
whatever implements the step. What a stored layout pads, and every further
pass over the state, shows as roofline lost. Rows that do not decode (idle
slots) need nothing."""


def cost(rows: float, channels: int, d_state: int) -> dict:
    """`rows`: the rows that decode in the call."""
    state_bytes = rows * channels * d_state * 4
    vector_bytes = rows * (3 * channels + 2 * d_state) * 4
    a_bytes = channels * d_state * 4
    # a state element: delta A 1, exp 1, the decay 1, the write 2, the readout 2
    flops = rows * channels * d_state * 7
    return {"flops": flops, "bytes": 2 * state_bytes + vector_bytes + a_bytes}
