"""Operations and bytes one KDA layer's decode recurrence needs for one
token a row (`llm_training_tpu/models/solar_open2/kda.py:kda_step`, the ops
under the `kda_recurrence` scope): each decoding row's float32 state
[heads, key_dim, value_dim] read once and written once, and the token's
vectors. Rows that do not decode (idle slots) need nothing."""


def cost(rows: float, heads: int, key_dim: int, value_dim: int) -> dict:
    """`rows`: the rows that decode in the call."""
    state_bytes = rows * heads * key_dim * value_dim * 4
    # q, k and the log decay a key channel; v and the output a value channel; beta
    vector_bytes = rows * heads * (3 * key_dim + 2 * value_dim + 1) * 4
    # a state element: the decay 1, k.S 2, the rank-one write 2, the readout 2
    flops = rows * heads * key_dim * value_dim * 7
    return {"flops": flops, "bytes": 2 * state_bytes + vector_bytes}
