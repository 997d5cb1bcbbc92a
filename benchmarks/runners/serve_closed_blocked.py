"""`serve_closed`'s closed loop, window and counts, with the check taken in
BLOCKS: for a vocabulary too wide for `serve_closed.served_gaps`, which holds
two `[4, max_model_len, vocab]` float32 arrays (at 200,064 rows 19.7 GB: more
than the chip).

What is compared is what that runner compares: for every request the window
finished, the float32 reference logits over the WHOLE vocabulary at every
served position, and of them the widest gap by which a served token's logit
lies below the reference's best, against `check.served_logit_gap`; and, as
`serve_closed_counted` holds it, a SECOND number: the share of the served
tokens that lie more than `check.far_level` below the best, against
`check.far_token_share`. A maximum over 78,000 tokens is one token's
accident: a fault that moves every token a little (a zeroed memory: 1.75
beside a sound 1.15, PERF.md section 2) shows in how MANY lie far off. How: the
reference's hidden states four requests at a time (`hidden_states`, each
request padded to `max_model_len`, so that every run uses one set of shapes),
then its head and the gap a block of `BLOCK_POSITIONS` positions (`head`):
nothing of the logits outlives its block, about 0.8 GB.

Nothing of `serve_closed` is edited or patched. Its `Loop`, `make_request`
(through `Loop`), `step_counts`, `host_stalls` and `TRACED_SECONDS` are used
as they are, by import, and its `build_engine` by a call. Its `run` ends in
`served_gaps` and cannot be called, so `run` below states the same set-up,
window and counts again, statement for statement, up to the check
(`benchmarks/tests/test_phi4flash.py` holds the two texts equal up to
there).

The weights are `serve_closed.build_engine`'s, but for the leaves that the
configuration's `initializer_scales` names (`scaled_leaves`): drawn N(0,
0.02) like every matrix, a VECTOR that multiplies an activation (a
convolution's taps, Mamba's `D`) makes its layer's output a few 1e-4 beside
a residual of 1, which bfloat16 rounds away, and the check then sees nothing
of that layer, sound or faulty."""

from __future__ import annotations

import resource
import time
from pathlib import Path

import numpy as np

from benchmarks import common

_serve_closed = common.load_module(Path(__file__).with_name("serve_closed.py"))
Loop = _serve_closed.Loop
step_counts, host_stalls = _serve_closed.step_counts, _serve_closed.host_stalls
TRACED_SECONDS = _serve_closed.TRACED_SECONDS

CHECK_ROWS = 4  # requests the reference takes at once, each padded to max_model_len (`serve_closed`'s)
BLOCK_POSITIONS = 256  # positions of those rows whose logits over the whole vocabulary exist at once
FAR_LADDER = (0.1, 0.2, 0.3, 0.5, 1.0)  # levels whose shares the readings list beside `check.far_level`'s


def scaled_leaves(variables, scales: dict, seed: int):
    """`variables` with every leaf whose own name is a key of `scales` made
    anew: `{"std": s}` is the benchmark's seeded draw at that width,
    `{"value": v}` a constant. The other leaves are the same arrays."""
    import jax
    import jax.numpy as jnp

    key = common.base_key(seed)

    def leaf(path, old):
        name = common.path_str(path)
        rule = scales.get(name.rsplit("/", 1)[-1])
        if rule is None:
            return old
        if "value" in rule:
            return jnp.full(old.shape, rule["value"], old.dtype)
        return common.seeded_leaf(key, name, old.shape, old.dtype, rule["std"])

    return jax.tree_util.tree_map_with_path(leaf, variables)


def build_engine(cell, seed: int):
    """`serve_closed.build_engine`'s weights and engine, then the leaves of
    `initializer_scales` swapped in before any request exists (`reload_weights`
    binds the new tree; both programs take the weights as an argument)."""
    variables, engine = _serve_closed.build_engine(cell, seed)
    scales = cell.config.get("initializer_scales")
    if scales:
        variables = scaled_leaves(variables, scales, seed)
        engine.reload_weights(variables)
    return variables, engine


def served_gaps(cell, variables, finished: list[dict], control: tuple = ()) -> dict:
    """`serve_closed.served_gaps`'s numbers, `CHECK_ROWS` requests and a block
    of positions at a time, and the shares of the served tokens that lie
    farther below the reference's best than each level of `FAR_LADDER` and
    `check.far_level` (`far_token_share`: the last). For each precision in
    `control` (readings and tests, never a benchmark run) the same numbers
    for the token that the reference computed in that precision puts first
    at each of the same positions."""
    import jax
    import jax.numpy as jnp

    from benchmarks.references import _common as ref_common

    reference = cell.module("references", cell.config["reference"])
    width = cell.traffic["engine"]["max_model_len"]
    params = variables["params"]

    far_level = cell.config["check"]["far_level"]
    levels = sorted({far_level, *FAR_LADDER})

    @jax.jit
    def widest(logits, picked, mask):
        """-> (the widest gap, the tokens farther off than each level)."""
        below = logits.max(axis=-1) - jnp.take_along_axis(logits, picked[..., None], axis=-1)[..., 0]
        below = jnp.where(mask, below, 0.0)
        return jnp.max(below), jnp.sum(below[..., None] > jnp.asarray(levels), axis=(0, 1))

    out = {"served_logit_gap": 0.0, "tokens_compared": 0, "requests": len(finished)}
    out.update({f"control_{name}": 0.0 for name in control})
    far = {name: np.zeros(len(levels), np.int64) for name in ("served", *control)}

    def take(gap_name, far_name, reading):
        out[gap_name] = max(out[gap_name], float(reading[0]))
        far[far_name] += np.asarray(reading[1])

    for at in range(0, len(finished), CHECK_ROWS):
        ids = np.zeros((CHECK_ROWS, width), np.int32)
        seg = np.zeros((CHECK_ROWS, width), np.int32)
        served = np.zeros((CHECK_ROWS, width), np.int32)
        mask = np.zeros((CHECK_ROWS, width), bool)
        for row, r in enumerate(finished[at : at + CHECK_ROWS]):
            tokens = r["prompt"] + list(r["done"]["tokens"])
            ids[row, : len(tokens)] = tokens
            seg[row, : len(tokens)] = 1
            # the logits at position p choose the token at p + 1
            first, n = len(r["prompt"]) - 1, len(r["done"]["tokens"])
            served[row, first : first + n] = r["done"]["tokens"]
            mask[row, first : first + n] = True
            out["tokens_compared"] += n
        args = (params, cell.config, jnp.asarray(ids), jnp.asarray(seg))
        hidden = reference.hidden_states(*args, ref_common.QUANTS["none"])
        lower = {name: reference.hidden_states(*args, ref_common.QUANTS[name]) for name in control}
        for start in range(0, width, BLOCK_POSITIONS):
            block = slice(start, start + BLOCK_POSITIONS)
            if not mask[:, block].any():
                continue
            logits = reference.head(params, hidden[:, block], ref_common.QUANTS["none"])
            here = jnp.asarray(mask[:, block])
            take("served_logit_gap", "served", widest(logits, jnp.asarray(served[:, block]), here))
            for name in control:
                first_there = reference.head(
                    params, lower[name][:, block], ref_common.QUANTS[name]
                ).argmax(axis=-1)
                take(f"control_{name}", name, widest(logits, first_there, here))
    for name, counts in far.items():
        shares = {str(level): int(n) / max(out["tokens_compared"], 1) for level, n in zip(levels, counts)}
        prefix = "" if name == "served" else f"control_{name}_"
        out[prefix + "far_token_share"] = shares[str(far_level)]
        out[prefix + "far_shares"] = shares
    return out


def run(cell, seed: int, seconds: float, trace: bool, require_tpu: bool = True) -> dict:
    import jax

    device = common.device_record(cell.chips, require_tpu)
    common.configure_cache()
    compiles = common.CompileCounter()
    variables, engine = build_engine(cell, seed)
    loop = Loop(cell, engine, seed)
    for _ in range(cell.traffic["clients"]):
        loop.submit_next()
    while not loop.all_clients_decoding():
        loop.step()
    # one more prefill and decode after the ramp so both programs have run
    # with a full batch before the window
    for _ in range(4):
        loop.step()
    jax.block_until_ready(engine._pool_k)
    warm = len(loop.steps)
    setup_compiles = compiles.mark()
    common.quiet_host()
    setup_s = time.perf_counter() - common.T_PROCESS_START

    trace_dir = cell.root / ".bench_trace" / cell.name if trace else None
    usage_open = resource.getrusage(resource.RUSAGE_SELF)
    t_open = time.perf_counter()
    with common.profiled(trace_dir):
        while time.perf_counter() - t_open < (min(seconds, TRACED_SECONDS) if trace else seconds):
            loop.step()
        jax.block_until_ready(engine._pool_k)
    t_traced = time.perf_counter()
    while time.perf_counter() - t_open < seconds:
        loop.step()
    t_close = time.perf_counter()
    usage_close = resource.getrusage(resource.RUSAGE_SELF)
    window_compiles = compiles.compiles - setup_compiles[0]
    if window_compiles:
        raise SystemExit(f"{window_compiles} program(s) compiled inside the window")
    memory_peak = common.memory_peak_bytes(cell.chips)

    steps = loop.steps[warm:]
    counters = step_counts(steps, cell.traffic["engine"]["max_batch"])
    tokens, span = counters["tokens"], counters["span_s"]
    # a token comes from a decoding row or from a prompt's last chunk: if the
    # engine steps past the calls the loop counts, nothing below can be read
    if not 0 <= tokens - counters["decode_rows"] <= counters["prefill_steps"]:
        raise SystemExit(
            f"{tokens} tokens from {counters['decode_rows']} decoding rows and "
            f"{counters['prefill_steps']} prefill chunks: the engine's calls are not the ones counted"
        )
    gaps_ms = []
    for record in loop.requests.values():
        times = record["times"]
        gaps_ms += [
            1e3 * (b - a) for a, b in zip(times, times[1:]) if t_open <= b <= t_close
        ]
    finished = [
        r for r in loop.requests.values()
        if r["done"] is not None and t_open <= r.get("done_at", 0.0) <= t_close
    ]
    failed = sum(r["done"]["stop_reason"] != "max_tokens" for r in finished)
    counters.update(
        compile_s=setup_compiles[1], compiles=setup_compiles[0], cache_hits=setup_compiles[2],
        itl_samples=len(gaps_ms), finished=len(finished),
        # the steps the profiler saw, for what is read from the trace per call
        traced=step_counts([s for s in steps if s[1] <= t_traced], counters["max_batch"]),
        host=host_stalls(steps, usage_open, usage_close),
    )
    common.log("counters", counters)

    # the engine's state goes before the reference comes: only the weights
    # the benchmark made stay (the slab and the window group's pool with the
    # pool: `close` gives all three back)
    engine.close()
    del engine, loop
    check = cell.config["check"]
    limit, far_limit = check["served_logit_gap"], check["far_token_share"]
    t_check = time.perf_counter()
    readings = served_gaps(cell, variables, finished)
    gap = readings["served_logit_gap"] if finished else float("inf")
    far_share = readings["far_token_share"]
    common.log(
        f"check served_logit_gap={gap:.6g} limit={limit} "
        f"far_token_share={far_share:.6g} (over {check['far_level']}) limit={far_limit} "
        f"tokens_compared={readings['tokens_compared']} requests={len(finished)} "
        f"reference_s={time.perf_counter() - t_check:.1f}"
    )
    correct = bool(finished) and gap <= limit and far_share <= far_limit and failed == 0

    device["memory_peak_bytes"] = memory_peak
    measured = {
        "serve_tok_s": tokens / span,
        "itl_p95_ms": common.percentile(gaps_ms, 95.0),
        "setup_s": setup_s,
    }
    return {
        "correct": correct, "attempted": len(finished), "failed": failed,
        "measured": measured, "counters": counters, "device": device,
        "trace_dir": trace_dir, "readings": readings,
        # readings.py and the tests: the same comparison with the control beside it
        "control": lambda *names: served_gaps(cell, variables, finished, names),
    }
