"""Operations and bytes one call of the `paged_decode` kernel needs: one new
query per row against that row's LIVE keys and values only (not the pool,
not the page table's capacity)."""


def cost(live_tokens: float, rows: int, q_heads: int, kv_heads: int, head_dim: int,
         itemsize: int) -> dict:
    """`live_tokens`: sum over the call's rows of the row's length."""
    kv_bytes = live_tokens * kv_heads * head_dim * 2 * itemsize  # K and V
    q_out_bytes = 2 * rows * q_heads * head_dim * itemsize
    flops = 2 * 2 * live_tokens * q_heads * head_dim  # q.K^T and p.V, 2 per multiply-add
    return {"flops": flops, "bytes": kv_bytes + q_out_bytes}
