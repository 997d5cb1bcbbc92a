"""Offline block-size sweep for the Pallas flash-attention kernels.

Times candidate `(block_q, block_k)` tiles per
`(seq_len, head_dim, dtype, causal, sliding_window)` key — FORWARD and
BACKWARD independently (the bwd kernels carry different scratch footprints
and a 4-D dkv grid, so their best tiles are generally not the forward's) —
and persists the winners into the tuning table that
`llm_training_tpu/ops/pallas/tuning.py` consults at trace time.

Sweep order per key: the forward candidates first; then the backward
candidates with the forward pinned to its winner, so the fwd+bwd timing
delta isolates the backward tiles.

Deterministic by construction: fixed input seed, sorted candidate
enumeration, sorted JSON output, no timestamps — re-running on identical
hardware produces an identical table modulo the measured times. On CPU the
kernels run in interpreter mode; entries are tagged `cpu-interpret` and are
plumbing placeholders (real block choice only matters compiled on TPU) —
re-run on the chip to fill in measured entries.

Usage:
  python scripts/tune_flash_blocks.py                    # backend-sized sweep
  python scripts/tune_flash_blocks.py --seqs 8192,32768 --blocks 1024x1024,2048x1024
  python scripts/tune_flash_blocks.py --seed-defaults    # also write the
      1024x1024 @ seq-2048/8192 entries builders chose on a v5e in rounds 3-4

Timing follows scripts/microbench_flash.py: chained
iterations inside one jit, per-rep salt, completion proven by fetching
bytes.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from llm_training_tpu.ops.pallas.flash_attention import flash_attention
from llm_training_tpu.ops.pallas import tuning

_RNG = np.random.default_rng(0)


def _fetch(out) -> None:
    jax.device_get(jax.tree.leaves(out)[0].ravel()[:8])


def _timed(fn, *args, iters: int, reps: int) -> float:
    """Median per-iteration seconds; first call absorbs the compile."""
    _fetch(fn(jnp.zeros((), jnp.float32), *args))
    times = []
    for rep in range(1, reps + 1):
        t0 = time.perf_counter()
        _fetch(fn(jnp.float32(rep * 1e-3), *args))
        times.append((time.perf_counter() - t0) / iters)
    return float(np.median(times))


def _make_inputs(seq: int, heads_q: int, heads_kv: int, head_dim: int, dtype):
    q = jnp.asarray(_RNG.standard_normal((1, seq, heads_q, head_dim)) * 0.1, dtype)
    k = jnp.asarray(_RNG.standard_normal((1, seq, heads_kv, head_dim)) * 0.1, dtype)
    v = jnp.asarray(_RNG.standard_normal((1, seq, heads_kv, head_dim)) * 0.1, dtype)
    return q, k, v


def _run_case(
    q, k, v, *, causal, sliding_window, fwd_blocks, bwd_blocks, bwd, iters, interpret
):
    """Build the timed jit: `iters` chained fwd (or fwd+grad) invocations."""
    kwargs = dict(
        causal=causal, sliding_window=sliding_window, interpret=interpret,
        block_q=fwd_blocks[0], block_k=fwd_blocks[1],
    )
    if bwd_blocks is not None:
        kwargs.update(bwd_block_q=bwd_blocks[0], bwd_block_k=bwd_blocks[1])

    if not bwd:
        @jax.jit
        def run(salt, q, k, v):
            def body(carry, _):
                o = flash_attention(q + carry.astype(q.dtype), k, v, **kwargs)
                return o[0, 0, 0, 0].astype(jnp.float32), None

            y, _ = jax.lax.scan(body, salt, None, length=iters)
            return y
    else:
        def loss_fn(q, k, v):
            o = flash_attention(q, k, v, **kwargs)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        grad_fn = jax.grad(loss_fn, argnums=(0, 1, 2))

        @jax.jit
        def run(salt, q, k, v):
            def body(carry, _):
                # all three grads feed the carry or DCE drops the dkv call
                dq, dk, dv = grad_fn(q + carry.astype(q.dtype), k, v)
                live = dq[0, 0, 0, 0] + dk[0, 0, 0, 0] + dv[0, 0, 0, 0]
                return live.astype(jnp.float32), None

            y, _ = jax.lax.scan(body, salt, None, length=iters)
            return y

    return run


def _candidates(blocks: list[tuple[int, int]], seq: int) -> list[tuple[int, int]]:
    """Sorted candidates whose tiles divide the (block-padded) sequence —
    the wrapper pads seq up to a block multiple, so any tile <= padded seq
    works; skip tiles larger than the sequence (they'd all collapse to the
    same clamped shape and re-measure it)."""
    out = sorted(
        {(bq, bk) for bq, bk in blocks if bq <= max(seq, 128) and bk <= max(seq, 128)}
    )
    return out or [(min(seq, 128), min(seq, 128))]


def sweep(args) -> dict:
    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    backend = jax.default_backend() + ("-interpret" if interpret else "")
    iters = args.iters or (8 if on_tpu else 2)
    reps = 3 if on_tpu else 2

    entries: dict[str, dict] = {}
    for seq in args.seqs:
        for head_dim in args.head_dims:
            heads_q, heads_kv = args.heads
            for dtype_name in args.dtypes:
                dtype = jnp.dtype(dtype_name)
                for causal, window in args.configs:
                    q, k, v = _make_inputs(seq, heads_q, heads_kv, head_dim, dtype)
                    cands = _candidates(args.blocks, seq)

                    def time_blocks(fwd_blocks, bwd_blocks, bwd):
                        run = _run_case(
                            q, k, v, causal=causal, sliding_window=window,
                            fwd_blocks=fwd_blocks, bwd_blocks=bwd_blocks,
                            bwd=bwd, iters=iters, interpret=interpret,
                        )
                        return _timed(run, q, k, v, iters=iters, reps=reps)

                    # ---- forward sweep
                    fwd_times = {c: time_blocks(c, None, bwd=False) for c in cands}
                    best_fwd = min(sorted(fwd_times), key=fwd_times.get)
                    key = tuning.table_key("fwd", seq, head_dim, dtype, causal, window)
                    entries[key] = {
                        "block_q": best_fwd[0], "block_k": best_fwd[1],
                        "time_us": round(fwd_times[best_fwd] * 1e6, 2),
                        "backend": backend,
                    }
                    print(f"{key}: {best_fwd} "
                          f"({entries[key]['time_us']}us/iter)", flush=True)

                    # ---- backward sweep, forward pinned to its winner
                    bwd_times = {c: time_blocks(best_fwd, c, bwd=True) for c in cands}
                    best_bwd = min(sorted(bwd_times), key=bwd_times.get)
                    key = tuning.table_key("bwd", seq, head_dim, dtype, causal, window)
                    entries[key] = {
                        "block_q": best_bwd[0], "block_k": best_bwd[1],
                        "time_us": round(bwd_times[best_bwd] * 1e6, 2),
                        "backend": backend,
                    }
                    print(f"{key}: {best_bwd} "
                          f"({entries[key]['time_us']}us/iter)", flush=True)
    return entries


# the tile builders chose on a v5e in rounds 3-4 (their sweep is not in the
# repo and the driver never re-took it: ROADMAP S6): 1024x1024 at seq 2048
# and 8192. Written only with --seed-defaults so a CPU placeholder run
# cannot masquerade as chip data.
_V5E_SEEDS = {
    tuning.table_key(kind, seq, 128, jnp.bfloat16, True, None): {
        "block_q": 1024, "block_k": 1024, "time_us": None, "backend": "v5e",
    }
    for kind in ("fwd", "bwd")
    for seq in (2048, 8192)
}


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    on_tpu = jax.default_backend() == "tpu"
    parser.add_argument("--out", default=str(tuning.DEFAULT_TABLE_PATH))
    parser.add_argument("--seqs", default=None,
                        help="comma ints (default: 2048,8192 on TPU; 256,512 on CPU)")
    parser.add_argument("--head-dims", default=None, help="comma ints")
    parser.add_argument("--heads", default=None, help="HQxHKV (default 32x8 TPU, 4x2 CPU)")
    parser.add_argument("--dtypes", default=None, help="comma dtype names")
    parser.add_argument("--blocks", default=None,
                        help="comma QxK candidates, e.g. 512x512,1024x1024")
    parser.add_argument("--windows", default="",
                        help="comma sliding windows to sweep in addition to "
                             "plain causal (each adds a causal+window config)")
    parser.add_argument("--causal-only", action="store_true",
                        help="skip the non-causal config (swept by default: "
                             "ring attention's off-diagonal chunk pairs — the "
                             "bulk of ring compute at high ring degree — look "
                             "up causal0 entries at the chunk length)")
    parser.add_argument("--iters", type=int, default=None)
    parser.add_argument("--no-merge", action="store_true",
                        help="replace the table instead of merging entries in")
    parser.add_argument("--seed-defaults", action="store_true",
                        help="also write the recorded v5e 1024x1024 entries")
    args = parser.parse_args()

    args.seqs = [int(s) for s in (
        args.seqs or ("2048,8192" if on_tpu else "256,512")).split(",")]
    args.head_dims = [int(s) for s in (args.head_dims or ("128" if on_tpu else "64")).split(",")]
    hq, hkv = (args.heads or ("32x8" if on_tpu else "4x2")).split("x")
    args.heads = (int(hq), int(hkv))
    args.dtypes = (args.dtypes or ("bfloat16" if on_tpu else "float32")).split(",")
    default_blocks = "512x512,1024x1024,1024x2048,2048x1024" if on_tpu else "128x128,256x256,128x256"
    args.blocks = [
        tuple(int(x) for x in pair.split("x"))
        for pair in (args.blocks or default_blocks).split(",")
    ]
    args.configs = [(True, None)]
    if not args.causal_only:
        args.configs.append((False, None))
    args.configs += [(True, int(w)) for w in args.windows.split(",") if w]

    entries = sweep(args)
    if args.seed_defaults:
        for key, value in _V5E_SEEDS.items():
            entries.setdefault(key, value)

    out = Path(args.out)
    table = {"version": 1, "generated_by": "scripts/tune_flash_blocks.py", "entries": {}}
    if out.exists() and not args.no_merge:
        try:
            prior = json.loads(out.read_text())
            table["entries"].update(prior.get("entries", {}))
        except (OSError, json.JSONDecodeError):
            print(f"warning: could not merge unreadable table at {out}", file=sys.stderr)
    table["entries"].update(entries)
    table["entries"] = dict(sorted(table["entries"].items()))
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(table, indent=2) + "\n")
    print(f"wrote {len(entries)} swept entries ({len(table['entries'])} total) -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
