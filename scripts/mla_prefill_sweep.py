"""Time `mla_prefill` on the chip at the two latent serve cells' shapes.

One chunk of 512 queries a call against one row of the paged latent pool,
through `ops/latent_attention.py:paged_latent_attention` (append included on
both sides): the kernel at each (heads a grid step, tokens a trip, heads
scheduled together) of the sweep, and the XLA path (`attend_rows`) beside it
with the largest difference between the two outputs. Needs a TPU; prints a
JSON line a reading and writes them to `--out`.

    python scripts/mla_prefill_sweep.py --out chiprun_out/pr42/sweep.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

PAGE, WIDTH, LATENT, NOPE, ROPE, V = 16, 640, 512, 128, 64, 128
SEQ = 512
# (heads, pages a row, chunk starts): openPangu's cell and LongCat's
SHAPES = ((128, 544, (3072, 0, 7680)), (64, 352, (3072, 0, 4608)))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="chiprun_out/mla_prefill_sweep.jsonl")
    parser.add_argument("--calls", type=int, default=20)
    parser.add_argument("--quick", action="store_true", help="the shipped tiles only")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from llm_training_tpu.ops import latent_attention
    from llm_training_tpu.ops.pallas import mla_prefill

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

    shipped = (mla_prefill._HEAD_BLOCK_BYTES, mla_prefill._TRIP_TOKENS, mla_prefill._HEAD_UNROLL)
    a_head = 2 * 1024 * 1024  # what `head_block` counts a head at these widths, rounded up
    sweep = [shipped] if args.quick else [shipped] + [
        (h * a_head, trip, together)
        for h, trip, together in (
            (4, 512, 2), (8, 512, 2), (16, 512, 1), (16, 512, 4), (16, 256, 2), (16, 1024, 2),
            (8, 1024, 2), (32, 512, 2),
        )
    ]
    for heads, pages, starts in SHAPES:
        keys = jax.random.split(jax.random.key(heads), 5)
        blocks = pages + 1
        q_nope = jax.random.normal(keys[0], (1, SEQ, heads, NOPE), jnp.bfloat16)
        q_rope = jax.random.normal(keys[1], (1, SEQ, heads, ROPE), jnp.bfloat16)
        w_kvb = (jax.random.normal(keys[2], (LATENT, heads, NOPE + V)) * LATENT**-0.5).astype(jnp.bfloat16)
        pool = jax.random.normal(keys[3], (blocks, 1, PAGE, WIDTH), jnp.bfloat16)
        pool = pool.at[..., LATENT + ROPE:].set(0)
        row = jax.random.normal(keys[4], (1, SEQ, WIDTH), jnp.bfloat16).at[..., LATENT + ROPE:].set(0)
        tables = (1 + jnp.arange(pages, dtype=jnp.int32))[None]
        scale = (NOPE + ROPE) ** -0.5

        def program(impl):
            return jax.jit(
                lambda pool, lens: latent_attention.paged_latent_attention(
                    q_nope, q_rope, row, w_kvb, pool, lens, tables, scale=scale, impl=impl
                )[0]
            )

        def timed(fn, start):
            lens = jnp.asarray([start], jnp.int32)
            result = fn(pool, lens).block_until_ready()
            began = time.perf_counter()
            for _ in range(args.calls):
                result = fn(pool, lens)
            result.block_until_ready()
            return result, (time.perf_counter() - began) / args.calls * 1e3

        xla = program("xla")
        reference = {}
        for start in starts:
            reference[start], ms = timed(xla, start)
            say(heads=heads, start=start, path="xla", ms_a_call=ms)
        for block_bytes, trip, together in sweep:
            mla_prefill._HEAD_BLOCK_BYTES = block_bytes
            mla_prefill._TRIP_TOKENS = trip
            mla_prefill._HEAD_UNROLL = together
            block_h = mla_prefill.head_block(heads, SEQ, LATENT, NOPE, WIDTH - LATENT, V, 2)
            kernel = program("pallas")
            for start in starts if (block_bytes, trip, together) == shipped else starts[:1]:
                try:
                    got, ms = timed(kernel, start)
                except Exception as e:  # noqa: BLE001 — a tile the compiler refuses is a reading
                    say(heads=heads, start=start, path="mla_prefill", block_h=block_h,
                        trip_tokens=trip, together=together, refused=f"{type(e).__name__}: {str(e)[:300]}")
                    break
                gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - reference[start].astype(jnp.float32))))
                size = float(jnp.max(jnp.abs(reference[start].astype(jnp.float32))))
                # what the mathematics needs of the visible pairs, as benchmarks/costs/mla_prefill.py
                pairs = SEQ * start + SEQ * (SEQ + 1) // 2
                flops = 2 * pairs * heads * (NOPE + ROPE + V)
                say(heads=heads, start=start, path="mla_prefill", block_h=block_h, trip_tokens=trip,
                    together=together, ms_a_call=ms, max_abs_gap=gap, max_abs_reference=size,
                    attend_tflops=flops / ms / 1e9)
        mla_prefill._HEAD_BLOCK_BYTES, mla_prefill._TRIP_TOKENS, mla_prefill._HEAD_UNROLL = shipped
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
