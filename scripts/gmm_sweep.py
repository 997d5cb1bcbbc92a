"""Time the experts' grouped products on the chip at a held share's shapes.

One product a call, `xs [rows, K]` sorted by expert times `w [1, E, K, N]`
(a stack of one layer, as a looped or one-period decoding stack hands it):
`models/moe.py:grouped_matmul` with `layer=0` (megablox `gmm`) at the shipped
tiling (`_gmm_tiling`) and at each tiling of the sweep, beside
`jax.lax.ragged_dot` on the same inputs, with the largest difference between
the two over the rows that have a group (what `ragged_dot` leaves in the rows
past the last group is not defined on the chip; the caller selects them
away). The group sizes are a held share's: `rows` assignments drawn
uniformly over `of` experts of which the first E are held here, so most rows
belong to no group and some groups are empty; `--full` draws them over the E
held ones alone (every row in a group). Needs a TPU; prints a JSON line a
reading and writes them to `--out`.

    python scripts/gmm_sweep.py --out chiprun_out/pr43/gmm_sweep.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# (name, experts held, of, hidden, expert width, (decode rows, chunk rows))
SHAPES = {
    "solar": (40, 320, 4096, 1280, (256, 4096)),
    "trinity": (16, 128, 2048, 1024, (128, 4096)),
    "pangu": (8, 256, 7680, 2048, (256, 4096)),
}
# (tm, tk, tn) tried beside the shipped one, by (K, N)
SWEEP = {
    (4096, 1280): [
        (128, 2048, 640), (128, 4096, 256), (128, 4096, 128), (128, 2048, 1280), (128, 1024, 1280),
        (64, 2048, 640), (32, 2048, 640), (256, 2048, 640), (64, 4096, 256), (32, 4096, 256),
    ],
    (1280, 4096): [
        (128, 1280, 2048), (128, 1280, 512), (128, 640, 4096), (128, 1280, 256),
        (64, 1280, 1024), (32, 1280, 1024), (256, 1280, 1024),
    ],
    (2048, 7680): [(128, 1024, 1920), (128, 2048, 768), (128, 2048, 512), (128, 2048, 640), (128, 1024, 3840)],
}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="chiprun_out/gmm_sweep.jsonl")
    parser.add_argument("--calls", type=int, default=50)
    parser.add_argument("--shapes", default="solar,trinity")
    parser.add_argument("--quick", action="store_true", help="the shipped tiling only")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_training_tpu.models import moe

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs a TPU, found {device.platform}", file=sys.stderr)
        return 1
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    lines = []

    def say(**fields):
        fields["device"] = device.device_kind
        lines.append(fields)
        print(json.dumps(fields), flush=True)
        out.write_text("".join(json.dumps(line) + "\n" for line in lines))

    def timed(fn, *operands):
        result = fn(*operands).block_until_ready()
        began = time.perf_counter()
        for _ in range(args.calls):
            result = fn(*operands)
        result.block_until_ready()
        return result, (time.perf_counter() - began) / args.calls * 1e3

    shipped = moe._gmm_tiling
    ragged = jax.jit(lambda xs, w, sizes: moe.grouped_matmul(xs, w[0], sizes))
    for name in args.shapes.split(","):
        held, of, hidden, width, row_counts = SHAPES[name]
        for k, n in ((hidden, width), (width, hidden)):
            keys = jax.random.split(jax.random.key(k), 2)
            w = (jax.random.normal(keys[0], (1, held, k, n)) * k**-0.5).astype(jnp.bfloat16)
            for rows in row_counts:
                xs = jax.random.normal(keys[1], (rows, k), jnp.bfloat16)
                for full in (False, True):
                    chosen = np.random.default_rng(rows).integers(0, held if full else of, rows)
                    sizes = jnp.asarray(np.bincount(chosen, minlength=of)[:held], jnp.int32)
                    in_groups, non_empty = int(sizes.sum()), int((sizes > 0).sum())
                    case = dict(
                        shape=name, k=k, n=n, rows=rows, full=full, rows_in_groups=in_groups,
                        non_empty_groups=non_empty, groups=held,
                    )
                    # what the product has to read: the non-empty experts' matrices
                    read = non_empty * k * n * 2
                    want, ms = timed(ragged, xs, w, sizes)
                    say(**case, path="ragged_dot", ms_a_call=ms, all_experts_gb_s=held * k * n * 2 / ms / 1e6)
                    tilings = [shipped(rows, k, n, 2)]
                    if not args.quick and not full:
                        tilings += [
                            t for t in SWEEP.get((k, n), []) if t[0] <= max(rows, 128) and t != tilings[0]
                        ]
                    for tiling in tilings:
                        moe._gmm_tiling = lambda *_, tiling=tiling: tiling
                        in_place = jax.jit(lambda xs, w, sizes: moe.grouped_matmul(xs, w, sizes, 0))
                        try:
                            got, ms = timed(in_place, xs, w, sizes)
                        except Exception as e:  # noqa: BLE001 — a tile the compiler refuses is a reading
                            say(**case, path="gmm", tiling=tiling, refused=f"{type(e).__name__}: {str(e)[:300]}")
                            continue
                        finally:
                            moe._gmm_tiling = shipped
                        got, held_rows = got[:in_groups], want[:in_groups]
                        gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - held_rows.astype(jnp.float32))))
                        say(**case, path="gmm", tiling=tiling, shipped=tiling == tilings[0], ms_a_call=ms,
                            non_empty_gb_s=read / ms / 1e6, max_abs_gap=gap,
                            bitwise_equal=bool(jnp.array_equal(got, held_rows)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
