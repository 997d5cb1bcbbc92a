"""Microbenchmark the XLA-fusion stand-in ops (SURVEY §2.9).

The reference ships Triton kernels for rms_norm / rope / swiglu / fused CE
(`ops/liger_kernel/*.py`); this repo leaves the first three to XLA fusion and
hand-chunks the CE. This script measures whether that bet holds on the real
chip: each op runs CHAINED inside one jit (output feeds the next iteration,
so neither XLA nor the async dispatch queue can elide or overlap iterations)
and is reported as ns/token and achieved HBM GB/s against the chip's ~819
GB/s peak (all four ops are bandwidth-bound — roofline says a fused
implementation can only win by moving fewer bytes).

One device, no mesh: the fused CE here is the one scan over all the tokens.
What it does on a mesh that splits the tokens (each device scanning its own,
`ops/cross_entropy.py:_on_own_tokens`) is not exercised by this script: the
benchmark's `phi3m-train-4k-fsdp4` cell and `tests/test_ce_sharding.py` are.

Usage: python scripts/microbench_ops.py  (prints a markdown table)
"""

from __future__ import annotations

import functools
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from llm_training_tpu.ops import apply_rope, rms_norm
from llm_training_tpu.ops.cross_entropy import fused_linear_cross_entropy
from llm_training_tpu.ops.swiglu import silu_mul

ITERS = 200
# The working set must exceed the chip's ~128M VMEM or the chained scan keeps
# the carry resident in VMEM and reports impossible bandwidth (26 TB/s at
# 16384 tokens, measured r3) — 131072 tokens x hidden 1024 is 268M bf16, so
# every iteration genuinely streams HBM like a model layer does.
TOKENS = 131072  # 64 x 2048
HIDDEN = 1024
INTER = 4096
VOCAB = 32000
HEADS, HEAD_DIM = 8, 128
_RNG = np.random.default_rng(0)


def _fetch(out) -> None:
    """Force completion by pulling a few result elements to the host.

    Dispatch is asynchronous: a timing that does not wait for the result
    measures the enqueue. Round-tripping a few real bytes proves completion
    on any backend; the one host round trip it costs is amortized over
    ITERS chained iterations.
    """
    jax.device_get(jax.tree.leaves(out)[0].ravel()[:8])


def _timed(fn, *args) -> float:
    """Median seconds per chained iteration.

    Every rep passes a distinct salt that perturbs the carry before the
    chain, so no rep can be served from any repeat-execution fast path.
    """
    _fetch(fn(jnp.float32(0.0), *args))
    times = []
    for rep in range(1, 4):
        t0 = time.perf_counter()
        _fetch(fn(jnp.float32(rep), *args))
        times.append((time.perf_counter() - t0) / ITERS)
    return float(np.median(times))


def _chain(op):
    """iterate x -> op(x) ITERS times inside one jit via lax.scan."""

    @jax.jit
    def run(salt, x, *rest):
        x = jax.tree.map(
            lambda a: a + jnp.asarray(salt, a.dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a,
            x,
        )

        def body(carry, _):
            return op(carry, *rest), None

        y, _ = jax.lax.scan(body, x, None, length=ITERS)
        return y

    return run


def bench_rms_norm():
    x = jnp.asarray(_RNG.standard_normal((TOKENS, HIDDEN)), jnp.bfloat16)
    w = jnp.ones((HIDDEN,), jnp.bfloat16)
    t = _timed(_chain(lambda x, w: rms_norm(x, w, 1e-5)), x, w)
    moved = TOKENS * HIDDEN * 2 * 2  # read + write bf16
    return "rms_norm", t, moved


def bench_rope():
    q = jnp.asarray(_RNG.standard_normal((1, TOKENS, HEADS, HEAD_DIM)), jnp.bfloat16)
    k = jnp.asarray(_RNG.standard_normal((1, TOKENS, HEADS // 2, HEAD_DIM)), jnp.bfloat16)
    inv = 1.0 / (10000.0 ** (np.arange(0, HEAD_DIM, 2) / HEAD_DIM))
    freqs = np.outer(np.arange(TOKENS), inv)
    # rotate_half layout: full-width [seq, head_dim] tables, halves duplicated
    cos = jnp.asarray(np.cos(np.concatenate([freqs, freqs], -1)), jnp.float32)
    sin = jnp.asarray(np.sin(np.concatenate([freqs, freqs], -1)), jnp.float32)

    def op(qk, cos, sin):
        q, k = qk
        q2, k2 = apply_rope(q, k, cos, sin)
        return (q2, k2)

    t = _timed(_chain(op), (q, k), cos, sin)
    moved = (q.size + k.size) * 2 * 2 + (cos.size + sin.size) * 4
    return "rope", t, moved


def bench_swiglu():
    gate = jnp.asarray(_RNG.standard_normal((TOKENS, INTER)), jnp.bfloat16)
    up = jnp.asarray(_RNG.standard_normal((TOKENS, INTER)), jnp.bfloat16)

    def op(gate, up):
        out = silu_mul(gate, up)
        # chain through gate so the scan carries a same-shaped tensor
        return out

    t = _timed(_chain(op), gate, up)
    moved = TOKENS * INTER * 2 * 3  # 2 reads + 1 write
    return "silu_mul", t, moved


def bench_fused_ce():
    hidden = jnp.asarray(_RNG.standard_normal((TOKENS, HIDDEN)) * 0.01, jnp.bfloat16)
    w = jnp.asarray(_RNG.standard_normal((HIDDEN, VOCAB)) * 0.01, jnp.bfloat16)
    labels = jnp.asarray(_RNG.integers(0, VOCAB, TOKENS), jnp.int32)

    def op(hidden, w, labels):
        loss, _ = fused_linear_cross_entropy(
            hidden, w, labels, chunk_size=2048
        )
        # chain: fold the scalar back in so iterations serialize
        return hidden + loss.astype(hidden.dtype) * 0

    t = _timed(_chain(op), hidden, w, labels)
    # dominated by the lm_head matmul: report FLOP efficiency instead
    flops = 2 * TOKENS * HIDDEN * VOCAB
    return "fused_linear_ce(fwd)", t, None, flops


def main():
    peak_bw = 819e9  # v5e HBM
    peak_flops = 197e12
    print(f"| op | time/iter | ns/token | GB/s (of ~819) | MXU eff |")
    print(f"|---|---|---|---|---|")
    for fn in (bench_rms_norm, bench_rope, bench_swiglu, bench_fused_ce):
        res = fn()
        name, t, moved = res[0], res[1], res[2]
        flops = res[3] if len(res) > 3 else None
        ns_tok = t / TOKENS * 1e9
        bw = f"{moved / t / 1e9:.0f}" if moved else "-"
        eff = f"{flops / t / peak_flops:.2f}" if flops else "-"
        print(f"| {name} | {t*1e6:.1f} us | {ns_tok:.2f} | {bw} | {eff} |")


if __name__ == "__main__":
    main()
