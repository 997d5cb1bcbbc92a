"""Operations and bytes one call of the `paged_prefill` kernel needs: a chunk
of `tokens` queries of ONE row that held `start` tokens before the chunk,
against the keys each query may see and no other. Query i sits at position
`start + i` and sees the keys up to its own, `start + i + 1` of them, or the
last `window` of those where the layer keeps a window. A lower bound on the
work: the kernel multiplies whole tiles and fetches whole trips."""


def visible_pairs(start: int, tokens: int, window: int | None = None) -> int:
    """(query, key) pairs of the chunk under the causal term and the window."""
    if window is None:
        return tokens * start + tokens * (tokens + 1) // 2
    return sum(min(start + i + 1, window) for i in range(tokens))


def visible_pages(start: int, tokens: int, window: int | None, page: int) -> int:
    """Pages that hold a key some query of the chunk sees: from the page of
    the first query's oldest visible key to the page of the last query."""
    if tokens <= 0:
        return 0
    oldest = 0 if window is None else max(start - window + 1, 0)
    return (start + tokens - 1) // page - oldest // page + 1


def cost(start: int, tokens: int, window: int | None, page: int, q_heads: int,
         kv_heads: int, head_dim: int, itemsize: int) -> dict:
    pairs = visible_pairs(start, tokens, window)
    kv_bytes = visible_pages(start, tokens, window, page) * page * kv_heads * head_dim * 2 * itemsize  # K and V
    q_out_bytes = 2 * tokens * q_heads * head_dim * itemsize
    flops = 2 * 2 * pairs * q_heads * head_dim  # q.K^T and p.V, 2 per multiply-add
    return {"pairs": pairs, "flops": flops, "bytes": kv_bytes + q_out_bytes}
