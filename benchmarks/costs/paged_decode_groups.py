"""Operations and bytes the `paged_decode` calls of ONE decode step need in a
stack whose key/value layers are in two groups: a layer that keeps every token
reads each row's LIVE keys and values, a layer that keeps a window reads of
each row its window at most (`window_live_tokens`: the sum over the rows of
`min(length, window)`). One call a layer; `costs/paged_decode.py` counts a
call."""
from benchmarks.costs import paged_decode


def cost(live_tokens: float, window_live_tokens: float, rows: float, global_layers: int,
         window_layers: int, q_heads: int, kv_heads: int, head_dim: int, itemsize: int) -> dict:
    """-> {"global": one global layer's call, "window": one window layer's
    call, "flops", "bytes": the step's, over all the layers of both groups}."""
    one = lambda tokens: paged_decode.cost(tokens, rows, q_heads, kv_heads, head_dim, itemsize)
    parts = {"global": one(live_tokens), "window": one(window_live_tokens)}
    layers = {"global": global_layers, "window": window_layers}
    return {
        **parts,
        "flops": sum(layers[g] * parts[g]["flops"] for g in parts),
        "bytes": sum(layers[g] * parts[g]["bytes"] for g in parts),
    }
