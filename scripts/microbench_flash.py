"""Microbenchmark the Pallas flash-attention kernel at long sequence lengths.

The kernel's default 1024x1024 tiles were tuned at seq 2048;
this measures fwd and fwd+bwd at the Llama-3-8B attention shape (32 q heads,
8 kv heads, head_dim 128) for seq 8k/32k/64k, causal and packed-causal, and
reports effective MXU utilization against the credited matmul FLOPs
(causal = half the full quadratic; packed = sum of per-document halves).

Timing follows scripts/microbench_ops.py: chained iterations inside one
jit, per-rep salt, completion proven by fetching bytes.

Usage:
  python scripts/microbench_flash.py             # full sweep
  SEQS=32768 BLOCKS=1024x1024,2048x1024 python scripts/microbench_flash.py
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from llm_training_tpu.ops.pallas.flash_attention import flash_attention

HEADS_Q, HEADS_KV, HEAD_DIM = 32, 8, 128
ITERS = 8
_RNG = np.random.default_rng(0)
_PEAK = 197e12  # v5e bf16


def _fetch(out) -> None:
    jax.device_get(jax.tree.leaves(out)[0].ravel()[:8])


def _timed(fn, *args) -> float:
    _fetch(fn(jnp.bfloat16(0.0), *args))  # compile
    times = []
    for rep in range(1, 4):
        t0 = time.perf_counter()
        _fetch(fn(jnp.bfloat16(rep * 1e-3), *args))
        times.append((time.perf_counter() - t0) / ITERS)
    return float(np.median(times))


def _make_inputs(seq: int, n_docs: int):
    q = jnp.asarray(
        _RNG.standard_normal((1, seq, HEADS_Q, HEAD_DIM)) * 0.1, jnp.bfloat16
    )
    k = jnp.asarray(
        _RNG.standard_normal((1, seq, HEADS_KV, HEAD_DIM)) * 0.1, jnp.bfloat16
    )
    v = jnp.asarray(
        _RNG.standard_normal((1, seq, HEADS_KV, HEAD_DIM)) * 0.1, jnp.bfloat16
    )
    if n_docs == 1:
        seg = None
    else:
        seg = jnp.asarray(
            np.repeat(np.arange(1, n_docs + 1), seq // n_docs)[None, :], jnp.int32
        )
    return q, k, v, seg


def _credited_flops(seq: int, n_docs: int, n_matmuls: int) -> float:
    """Matmul FLOPs the kernel must do: n_matmuls x (2*Hq*D) per attended
    (q, k) pair; causal packing attends ~half of each document's square."""
    doc = seq // n_docs
    pairs = n_docs * doc * (doc + 1) / 2
    return n_matmuls * 2 * HEADS_Q * HEAD_DIM * pairs


def bench_one(seq: int, n_docs: int, block_q: int, block_k: int, bwd: bool):
    q, k, v, seg = _make_inputs(seq, n_docs)

    if not bwd:
        @jax.jit
        def run(salt, q, k, v, seg):
            def body(carry, _):
                o = flash_attention(
                    q + carry[None, None, None], k, v, segment_ids=seg,
                    causal=True, block_q=block_q, block_k=block_k,
                )
                return o[0, 0, 0, 0].astype(jnp.bfloat16), None

            y, _ = jax.lax.scan(body, salt, None, length=ITERS)
            return y
    else:
        def loss_fn(q, k, v, seg):
            o = flash_attention(
                q, k, v, segment_ids=seg, causal=True,
                block_q=block_q, block_k=block_k,
            )
            return jnp.sum(o.astype(jnp.float32) ** 2)

        grad_fn = jax.grad(loss_fn, argnums=(0, 1, 2))

        @jax.jit
        def run(salt, q, k, v, seg):
            def body(carry, _):
                # all three gradients must feed the carry, or DCE removes
                # the dkv pallas_call from the timed graph
                dq, dk, dv = grad_fn(q + carry[None, None, None], k, v, seg)
                live = dq[0, 0, 0, 0] + dk[0, 0, 0, 0] + dv[0, 0, 0, 0]
                return live.astype(jnp.bfloat16), None

            y, _ = jax.lax.scan(body, salt, None, length=ITERS)
            return y

    t = _timed(run, q, k, v, seg)
    # fwd: QK^T + PV = 2 matmuls; bwd adds dq kernel (s, dp, dq = 3) and
    # dkv kernel (s, dv, dp, dk = 4); fwd+bwd jit re-runs fwd = 2+3+4+2? no:
    # grad of the custom VJP runs fwd once (residuals) + bwd kernels = 2+7
    n_matmuls = 2 if not bwd else 9
    flops = _credited_flops(seq, n_docs, n_matmuls)
    eff = flops / t / _PEAK
    return t, eff


def main():
    seqs = [int(s) for s in os.environ.get("SEQS", "8192,32768,65536").split(",")]
    blocks = [
        tuple(int(x) for x in b.split("x"))
        for b in os.environ.get("BLOCKS", "1024x1024").split(",")
    ]
    passes = os.environ.get("PASSES", "fwd,bwd").split(",")
    print("| seq | docs | block | pass | ms/iter | MXU eff (credited) |")
    print("|---|---|---|---|---|---|")
    for seq in seqs:
        for n_docs in (1, 4):
            if n_docs > 1 and seq // n_docs % 128:
                continue
            for bq, bk in blocks:
                for p in passes:
                    t, eff = bench_one(seq, n_docs, bq, bk, p == "bwd")
                    label = "packed" if n_docs > 1 else "causal"
                    print(
                        f"| {seq} | {label}x{n_docs} | {bq}x{bk} | {p} "
                        f"| {t*1e3:.2f} | {eff:.3f} |",
                        flush=True,
                    )


if __name__ == "__main__":
    main()
