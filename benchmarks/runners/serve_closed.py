"""Closed-loop serving: `clients` callers against `ServingEngine.submit()` /
`step()` in this process (the JSONL protocol loop of `cli serve` is bypassed:
one run is one process that holds the chip).

Request j has prompt length `prompt_lengths[j % len]` and output length
`output_lengths[j % len]`: the seed draws token ids and weights, never how
much work a run does. The first request of client i is cut to (i+1)/clients
of its output so completions are staggered from the start; the window opens
at a step index fixed by the schedule, once every client has prefilled."""

from __future__ import annotations

import math
import resource
import time

import numpy as np

from benchmarks import common

TRACED_SECONDS = 8.0  # of the window, what the profiler sees in a --trace 1 run
CHECK_ROWS = 4  # requests the reference takes at once, each padded to max_model_len
# what the loop reaches into, for want of hooks (PERF.md, section 7): a rename
# ends the run here, not with counts that are quietly zero
ENGINE_INTERNALS = ("_run_prefill", "_run_decode", "_pool_k", "_pool_v")


def make_request(traffic: dict, vocab: int, seed: int, index: int) -> dict:
    plens, olens = traffic["prompt_lengths"], traffic["output_lengths"]
    rng = np.random.default_rng((seed, index))
    out = olens[index % len(olens)]
    clients = traffic["clients"]
    if traffic.get("stagger_first_output") and index < clients:
        out = max(2, math.ceil(out * (index + 1) / clients))
    return {
        "id": f"r{index}",
        "prompt": rng.integers(0, vocab, size=plens[index % len(plens)]).tolist(),
        "max_new_tokens": out,
    }


def build_engine(cell, seed: int):
    """Seeded bf16 weights on the device in one jitted call, then the engine."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from llm_training_tpu.serve.engine import ServeConfig, ServingEngine

    model = common.build_model(cell.config)
    abstract = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    std = cell.config["initializer_range"]
    variables = jax.jit(lambda key: nn.meta.unbox(common.seeded_tree(key, abstract, std)))(
        common.base_key(seed)
    )
    engine_cfg = cell.traffic["engine"]
    engine = ServingEngine(
        model, variables,
        ServeConfig(
            max_batch=engine_cfg["max_batch"], max_model_len=engine_cfg["max_model_len"],
            block_size=engine_cfg.get("block_size"), prefill_chunk=engine_cfg["prefill_chunk"],
            seed=seed % (2**31 - 1), eos_token_id=cell.traffic.get("eos"),
        ),
    )
    return variables, engine


class Loop:
    """The closed loop and its books. One `step()` is one engine step with
    the clients' resubmissions; every count the metrics need is kept here."""

    def __init__(self, cell, engine, seed: int):
        self.traffic = cell.traffic
        self.vocab = cell.config["vocab_size"]
        self.engine, self.seed = engine, seed
        self.next_index = 0
        self.requests: dict[str, dict] = {}  # id -> request + token times + done event
        # (t_start, t_end, tokens, prefill_chunks, decode_rows, live_tokens,
        #  seconds inside the prefill call, seconds inside the decode call)
        self.steps: list[tuple] = []
        self._prefills = self._rows = self._live = 0
        self._prefill_s = self._decode_s = 0.0
        missing = [name for name in ENGINE_INTERNALS if not hasattr(engine, name)]
        if missing:
            raise SystemExit(f"the engine no longer has {missing}: the loop cannot count its steps")
        run_prefill, run_decode = engine._run_prefill, engine._run_decode

        def counted_prefill(*args):
            self._prefills += 1
            t = time.perf_counter()
            out = run_prefill(*args)
            self._prefill_s += time.perf_counter() - t
            return out

        def counted_decode(rows):
            self._rows += len(rows)
            # what the paged kernel reads this call: each row's cache and its new token
            self._live += sum(r.cache_len + 1 for r in rows)
            t = time.perf_counter()
            out = run_decode(rows)
            self._decode_s += time.perf_counter() - t
            return out

        engine._run_prefill, engine._run_decode = counted_prefill, counted_decode

    def submit_next(self) -> None:
        request = make_request(self.traffic, self.vocab, self.seed, self.next_index)
        self.next_index += 1
        self.requests[request["id"]] = {**request, "times": [], "done": None}
        for event in self.engine.submit(**request):
            self._on_event(event, time.perf_counter())

    def _on_event(self, event: dict, now: float) -> None:
        record = self.requests[event["id"]]
        if event["type"] == "token":
            record["times"].append(now)
        elif event["type"] == "done":
            record["done"] = event
            record["done_at"] = now
            self.submit_next()

    def step(self) -> None:
        self._prefills = self._rows = self._live = 0
        self._prefill_s = self._decode_s = 0.0
        t_start = time.perf_counter()
        events = self.engine.step()
        t_end = time.perf_counter()
        tokens = 0
        for event in events:
            tokens += event["type"] == "token"
            self._on_event(event, t_end)
        self.steps.append((t_start, t_end, tokens, self._prefills, self._rows, self._live,
                           self._prefill_s, self._decode_s))

    def all_clients_decoding(self) -> bool:
        first = [self.requests[f"r{i}"] for i in range(self.traffic["clients"])]
        return all(r["times"] for r in first)


def served_gaps(cell, variables, finished: list[dict], control: tuple = ()) -> dict:
    """The plain reference, once, over every finished request's prompt with
    its served tokens: the widest gap by which a served token's reference
    logit lies below the reference's best. For each precision in `control`
    (readings and tests, never a benchmark run) the same gap for the token
    that the reference computed in that precision puts first at each of the
    same positions. `CHECK_ROWS` requests at a time, each padded to
    `max_model_len`, so that every run uses one set of shapes."""
    import jax
    import jax.numpy as jnp

    from benchmarks.references import _common as ref_common

    reference = cell.module("references", cell.config["reference"])
    width = cell.traffic["engine"]["max_model_len"]

    @jax.jit
    def widest(logits, picked, mask):
        below = logits.max(axis=-1) - jnp.take_along_axis(logits, picked[..., None], axis=-1)[..., 0]
        return jnp.max(jnp.where(mask, below, 0.0))

    out = {"served_logit_gap": 0.0, "tokens_compared": 0, "requests": len(finished)}
    out.update({f"control_{name}": 0.0 for name in control})
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
        pos = np.broadcast_to(np.arange(width, dtype=np.int32), ids.shape)
        args = (variables["params"], cell.config, jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos))
        logits = reference.logits(*args, ref_common.QUANTS["none"])
        out["served_logit_gap"] = max(
            out["served_logit_gap"], float(widest(logits, jnp.asarray(served), mask))
        )
        for name in control:
            first_there = reference.logits(*args, ref_common.QUANTS[name]).argmax(axis=-1)
            out[f"control_{name}"] = max(
                out[f"control_{name}"], float(widest(logits, first_there, mask))
            )
    return out


def step_counts(steps: list[tuple], max_batch: int) -> dict:
    decode = [s for s in steps if s[4]]
    return {
        "steps": len(steps),
        "prefill_steps": sum(1 for s in steps if s[3]),
        "decode_steps": len(decode),
        "decode_rows": sum(s[4] for s in decode),
        "live_tokens": sum(s[5] for s in decode),
        "max_batch": max_batch,
        "tokens": sum(s[2] for s in steps),
        "span_s": steps[-1][1] - steps[0][0] if steps else 0.0,
    }


def host_stalls(steps: list[tuple], usage_open, usage_close) -> dict:
    """Where a run that reads far off lost its time (the rate still counts
    it): the three longest steps as [index, seconds, of them inside the
    prefill call, inside the decode call], beside the median step and what
    the process's own accounting saw over the window."""
    longest = sorted(range(len(steps)), key=lambda i: steps[i][0] - steps[i][1])[:3]
    return {
        "median_step_s": common.percentile([s[1] - s[0] for s in steps], 50.0),
        "longest_steps": [
            [i, steps[i][1] - steps[i][0], steps[i][6], steps[i][7]] for i in longest
        ],
        "between_steps_s": sum(b[0] - a[1] for a, b in zip(steps, steps[1:])),
        "involuntary_switches": usage_close.ru_nivcsw - usage_open.ru_nivcsw,
        "major_faults": usage_close.ru_majflt - usage_open.ru_majflt,
        "cpu_s": (usage_close.ru_utime + usage_close.ru_stime)
        - (usage_open.ru_utime + usage_open.ru_stime),
    }


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
    # the benchmark made stay
    engine._pool_k = engine._pool_v = None
    del engine, loop
    limit = cell.config["check"]["served_logit_gap"]
    t_check = time.perf_counter()
    readings = served_gaps(cell, variables, finished)
    gap = readings["served_logit_gap"] if finished else float("inf")
    common.log(
        f"check served_logit_gap={gap:.6g} limit={limit} "
        f"tokens_compared={readings['tokens_compared']} requests={len(finished)} "
        f"reference_s={time.perf_counter() - t_check:.1f}"
    )
    correct = bool(finished) and gap <= limit and failed == 0

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
