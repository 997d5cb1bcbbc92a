"""Operations and bytes one gated-delta-rule layer's decode recurrence needs
for one token a row (`llm_training_tpu/ops/delta_rule.py:gated_delta_step`,
the ops under the `gdn_recurrence` scope): each decoding row's LOGICAL float32
state [heads, key_dim, value_dim] read once and written once, and the token's
vectors. What the stored layout pads, and every further pass over the state,
shows as roofline lost. Rows that do not decode (idle slots) need nothing."""


def cost(rows: float, heads: int, key_dim: int, value_dim: int) -> dict:
    """`rows`: the rows that decode in the call."""
    state_bytes = rows * heads * key_dim * value_dim * 4
    # q and k a key channel; v and the output a value channel; beta and g a head
    vector_bytes = rows * heads * (2 * key_dim + 2 * value_dim + 2) * 4
    # a state element: the decay 1, k.S 2, the rank-one write 2, the readout 2
    flops = rows * heads * key_dim * value_dim * 7
    return {"flops": flops, "bytes": 2 * state_bytes + vector_bytes}
