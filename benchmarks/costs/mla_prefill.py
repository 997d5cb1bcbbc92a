"""Operations and bytes one call of the `mla_prefill` kernel needs (one MLA
block, one chunk): a chunk of `tokens` queries of ONE row that held `start`
tokens before the chunk, against the cached rows each query may see (query i
at position `start + i` sees `start + i + 1` of them) and no other, in the
expanded form at the heads' own widths: a (query, key) pair costs a head
`nope + rope` multiply-adds for its score and `v` for its value. Bytes: the
visible rows' `latent + rope` values read ONCE (a row serves every head), the
block's `W_kvb` once, the queries in, the outputs out.

The EXPANSION of the rows through `W_kvb` (`2 * rows * latent * heads * (nope
+ v)` operations a call) is NOT counted: it is the same work on either side of
the kernel's edge, and leaving it out makes the share a lower bound wherever
the expansion sits, inside the kernel (as shipped) or in XLA before it. Nor
are the whole tiles the kernel multiplies past the causal edge, nor the zeros a
stored row carries up to whole lanes. So the share stays under 100."""
from benchmarks.costs.paged_prefill import visible_pairs


def cost(start: int, tokens: int, heads: int, latent: int, nope: int, rope: int, v: int,
         itemsize: int) -> dict:
    if tokens <= 0:
        return {"pairs": 0, "flops": 0, "bytes": 0}
    pairs = visible_pairs(start, tokens)
    moved = (
        (start + tokens) * (latent + rope)  # the visible latent rows, once
        + latent * heads * (nope + v)       # W_kvb, once
        + tokens * heads * (nope + rope)    # the queries in
        + tokens * heads * v                # the outputs out
    ) * itemsize
    flops = 2 * pairs * heads * (nope + rope + v)  # q.k and p.v, 2 per multiply-add
    return {"pairs": pairs, "flops": flops, "bytes": moved}
