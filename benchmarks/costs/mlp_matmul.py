"""Operations and bytes the MLP blocks of one chip's train step REQUIRE: three
matrix products a layer (gate, up, down; for a sparse MLP those of the
experts a token is routed to, and the router), each run forward once and
backward twice (for its input and for its weight), 2 operations a
multiply-add: 18 x hidden x intermediate a token a dense layer. Operations
run a second time under `jax.checkpoint` do not count, as in
`required_ops.py`. Bytes are the least a step can move: each weight read in
the compute dtype for the forward and for the input's gradient, its gradient
written once, and a token's hidden vector in and out of the block in both
directions; a lower bound, the intermediates kept on the chip."""


def params_per_token(hidden: int, intermediate: int, experts: int = 0, experts_per_token: int = 1) -> int:
    """Matmul parameters of one layer's MLP that multiply one token."""
    if experts:
        return experts_per_token * 3 * hidden * intermediate + hidden * experts
    return 3 * hidden * intermediate


def cost(tokens: int, layers: int, hidden: int, intermediate: int, itemsize: int,
         experts: int = 0, experts_per_token: int = 1) -> dict:
    flops = 6 * params_per_token(hidden, intermediate, experts, experts_per_token) * tokens * layers
    weights = 3 * hidden * intermediate * max(experts, 1) + hidden * experts
    moved = layers * (3 * weights + 5 * tokens * hidden) * itemsize  # x, y; dy, x again, dx
    return {"flops": flops, "bytes": moved}
