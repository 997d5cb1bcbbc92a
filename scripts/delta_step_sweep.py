"""Time the one-token delta-rule kernel on the chip at the two cells' states.

One decode step's worth of calls: every layer of a slab `[layers, 32, P, dk,
n * dv]` advanced one token, in a loop inside one program as a layer scan
does, through `ops/pallas/delta_step.py:delta_step` at each (stored heads a
grid step) of the sweep, and beside it the XLA step it replaces (`kda_step` /
`gated_delta_step` on the layer's rows, written back with
`models/cache.py:_put_rows`), with the largest difference between the two.
GB/s counts the state once in and once out. Needs a TPU; prints a JSON line a
reading and writes them to `--out`. What it read is in
`ops/pallas/tuning.py:DELTA_STEP_HEADS` and PERF.md section 6 (PR 46, 47).

    python scripts/delta_step_sweep.py --out chiprun_out/pr47/sweep.jsonl
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

ROWS = 32
# (name, layers, heads, key_dim, value_dim, heads abreast, decay a key channel, blocks swept)
SHAPES = (
    ("kda", 3, 64, 128, 128, 1, True, (4, 8, 16, 32)),
    ("gdn", 6, 30, 96, 192, 2, False, (1, 3, 5, 15)),
)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", default="chiprun_out/delta_step_sweep.jsonl")
    parser.add_argument("--steps", type=int, default=20, help="decode steps a timing")
    parser.add_argument("--quick", action="store_true", help="the table's block only")
    parser.add_argument("--held", type=int, nargs="*", default=[],
                        help="stored heads a trip of the kernel's loop takes, tried at the table's block")
    parser.add_argument("--rehearse", action="store_true",
                        help="tiny shapes on any device (the interpreter off the chip): no reading")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from llm_training_tpu.models.cache import _layer_rows, _put_rows, _slot_rows
    from llm_training_tpu.models.solar_open2.kda import kda_step
    from llm_training_tpu.ops import delta_rule
    from llm_training_tpu.ops.pallas import delta_step as kernel
    from llm_training_tpu.ops.pallas.delta_step import delta_step
    from llm_training_tpu.ops.pallas.tuning import delta_step_heads

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
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

    shapes, rows = SHAPES, ROWS
    if args.rehearse:
        shapes, rows = (("kda", 2, 4, 16, 128, 1, True, (1, 4)), ("gdn", 2, 4, 8, 64, 2, False, (1, 2))), 2
    for name, layers, heads, dk, dv, abreast, per_channel, blocks in shapes:
        stored, lanes = heads // abreast, abreast * dv
        keys = jax.random.split(jax.random.key(heads), 6)
        slab = jax.random.normal(keys[0], (layers, rows, stored, dk, lanes), jnp.float32)
        q = delta_rule.l2norm(jax.random.normal(keys[1], (rows, heads, dk))) * dk**-0.5
        k = delta_rule.l2norm(jax.random.normal(keys[2], (rows, heads, dk)))
        v = jax.random.normal(keys[3], (rows, heads, dv))
        beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[4], (rows, heads)))
        log_decay = -jax.nn.softplus(
            jax.random.normal(keys[5], (rows, heads, dk) if per_channel else (rows, heads))
        )
        state_bytes = layers * rows * heads * dk * dv * 4

        def xla_layer(slab, layer):
            state = _layer_rows(slab, layer, None, None, _slot_rows, in_place=True)
            step = kda_step if per_channel else delta_rule.gated_delta_step
            state, result = step(state, q, k, v, log_decay, beta)
            return _put_rows(slab, layer, None, state), result

        def kernel_layer(block):
            def one_layer(slab, layer):
                result, slab = delta_step(
                    slab, layer, q, k, v, jnp.exp(log_decay), beta,
                    block=block, interpret=device.platform != "tpu",
                )
                return slab, result
            return one_layer

        def program(one_layer):
            def decode_step(slab):
                def body(layer, carry):
                    slab, first = carry
                    slab, result = one_layer(slab, layer)
                    return slab, jnp.where(layer == 0, result, first)  # layer 0's output
                return jax.lax.fori_loop(0, layers, body, (slab, jnp.zeros((rows, heads, dv))))
            return jax.jit(decode_step, donate_argnums=0)

        def timed(fn):
            held, result = fn(slab + 0.0)  # the program owns its copy: it is donated on
            result.block_until_ready()
            first = (held + 0.0, result)  # `held` is donated on below
            began = time.perf_counter()
            for _ in range(args.steps):
                held, result = fn(held)
            result.block_until_ready()
            return first, (time.perf_counter() - began) / args.steps * 1e3

        (ref_slab, ref_out), ms = timed(program(xla_layer))
        say(rule=name, path="xla", ms_a_step=ms, ms_a_layer=ms / layers,
            state_gb_s=2 * state_bytes / ms / 1e6)
        listed = delta_step_heads(stored, dk, lanes, (3 if per_channel else 2) * abreast)
        shipped = kernel._HELD
        # every block at the shipped trip, then the listed block at the others
        swept = [(block, shipped) for block in ((listed,) if args.quick else sorted({listed, *blocks}))]
        swept += [(listed, held) for held in args.held if held != shipped and listed % held == 0]
        for block, held in swept:
            kernel._HELD = held
            jax.clear_caches()  # the jitted call's key does not hold the constant
            try:
                (got_slab, got_out), ms = timed(program(kernel_layer(block)))
            except Exception as e:  # noqa: BLE001 — a block the compiler refuses is a reading
                say(rule=name, path="delta_step", block=block, held=held,
                    refused=f"{type(e).__name__}: {str(e)[:300]}")
                continue
            finally:
                kernel._HELD = shipped
            say(rule=name, path="delta_step", block=block, held=held,
                listed=block == listed and held == shipped,
                block_bytes=block * dk * lanes * 4, ms_a_step=ms, ms_a_layer=ms / layers,
                state_gb_s=2 * state_bytes / ms / 1e6,
                max_abs_gap_out=float(jnp.max(jnp.abs(got_out - ref_out))),
                max_abs_gap_state=float(jnp.max(jnp.abs(got_slab - ref_slab))),
                same_bits_state=float(jnp.mean(got_slab == ref_slab)),
                max_abs_out=float(jnp.max(jnp.abs(ref_out))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
