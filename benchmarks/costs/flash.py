"""Operations and bytes of the three flash-attention kernels for one call on
packed rows: causal, per document, with the sliding window where it binds.
A (query, key) pair is counted once if the query may see the key."""


def visible_pairs(document: int, window: int | None) -> int:
    if window is None or document <= window:
        return document * (document + 1) // 2
    return document * window - window * (window - 1) // 2


# matrix products over the visible pairs, each 2 operations per multiply-add:
#   fwd:     S = QK^T, O = PV
#   bwd_dq:  S again, dP = dO V^T, dQ = dS K
#   bwd_dkv: S again, dP again, dV = P^T dO, dK = dS^T Q
PRODUCTS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 4}


def cost(kernel: str, rows: int, documents: list[int], q_heads: int, kv_heads: int,
         head_dim: int, itemsize: int, window: int | None = None) -> dict:
    seq = sum(documents)
    pairs = rows * sum(visible_pairs(d, window) for d in documents)
    flops = 2 * PRODUCTS[kernel] * pairs * q_heads * head_dim
    q_like = rows * seq * q_heads * head_dim * itemsize  # q, o, do, dq
    kv_like = rows * seq * kv_heads * head_dim * itemsize  # k, v, dk, dv
    stats = rows * seq * q_heads * 4  # lse / delta, float32
    moved = {
        "flash_fwd": 2 * q_like + 2 * kv_like + stats,  # q k v -> o lse
        "flash_bwd_dq": 3 * q_like + 2 * kv_like + 2 * stats,  # q k v do lse delta -> dq
        "flash_bwd_dkv": 2 * q_like + 4 * kv_like + 2 * stats,  # q k v do lse delta -> dk dv
    }[kernel]
    return {"flops": flops, "bytes": moved}
