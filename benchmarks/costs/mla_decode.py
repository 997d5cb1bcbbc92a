"""Operations and bytes one call of the `mla_decode` kernel needs (one MLA
block, one decode step): one absorbed query a head a row against that row's
LIVE latent rows only, each row of `latent_dim + rope_dim` values read ONCE
(it serves as key and, its first `latent_dim` values, as value). What the
mathematics needs: a row stored wider (zeros up to whole lanes) costs bytes
that are not counted here, so padding shows as roofline lost."""


def cost(live_tokens: float, rows: float, heads: int, latent_dim: int, rope_dim: int,
         itemsize: int) -> dict:
    """`live_tokens`: sum over the call's rows of the row's length."""
    row = latent_dim + rope_dim
    cache_bytes = live_tokens * row * itemsize
    q_out_bytes = rows * heads * (row + latent_dim) * itemsize  # the queries in, the weighted latents out
    flops = live_tokens * heads * 2 * (row + latent_dim)  # q.row and p.latent, 2 per multiply-add
    return {"flops": flops, "bytes": cache_bytes + q_out_bytes}
