"""The serving engine keeps a step's inputs and edits them where they change
(docs/serving.md, "What a step sends"): block tables a decode slot, written
where a page is taken or given back, and one packed int32 array a call.

The invariant: what a program is handed equals, entry for entry, what a plain
rebuild from the requests (`ServingEngine._table_row`, zeros for every slot
that does not decode this step) would hand it. Checked at every call of both
programs over a seeded schedule that holds every event that moves a row, on a
stack with one page group and on one with a window group's ring.

The engine runs a step ahead of its fetches (docs/serving.md, "A step ahead of
its fetches"), so at a call the tokens of the call before are still in flight:
a row's LENGTH is checked against `cache_len` as `_run_decode` found it (what
the benchmark's harness counts there), and the token a row feeds is checked
where it can be, against the device's carry once every call has been read."""

import functools
import random
import time

import numpy as np
import pytest
from test_serve_spans import SERVE as SERVE_SPANS, TINY_OLMOH, TINY_SOLAR, _afmoe_engine, _engine

from llm_training_tpu.serve.engine import _split
from llm_training_tpu.telemetry.registry import TelemetryRegistry, get_registry, set_registry

SERVE = dict(max_batch=3, max_model_len=48, block_size=8, prefill_chunk=4, num_blocks=9)
BUILD = {"llama": _engine, "afmoe": _afmoe_engine}
EVENTS = (
    "chunk_beside_decode", "finish_by_length", "finish_by_eos", "eviction_in_decode_blocks",
    "deadline_expiry", "submit_resumed", "second_tenant", "ring_pages_given_back",
)
EOS_PROMPT = [3, 17, 42, 7, 9]
STEPS = 150


class Watch:
    """Both programs of an engine, each call's packed inputs compared with the
    rebuild AT the call: a step later the rows have moved on."""

    def __init__(self, engine):
        self.engine, self.violations, self.plan = engine, [], None
        self.calls = {"prefill_chunk": 0, "decode_step": 0}
        self.aliased = dict(self.calls)  # calls handed the staging buffer itself
        self.tenants = [set() for _ in range(engine.config.max_batch)]
        run_prefill, decode_jit, prefill_jit = engine._run_prefill, engine._decode_jit, engine._prefill_jit
        run_decode = engine._run_decode
        self.lengths_at_run_decode = {}  # slot -> cache_len, as the harness's wrapper reads it
        self.decode_calls_behind = 0  # decode calls enqueued with an earlier step's outputs unread

        def watched_prefill(request, chunk, start):
            self.plan = (request, chunk, start)
            return run_prefill(request, chunk, start)

        def watched_decode(rows):
            self.lengths_at_run_decode = {r.slot: r.cache_len for r in rows}
            return run_decode(rows)

        def watched_decode_call(variables, packed, *args, **caches):
            self.calls["decode_step"] += 1
            self.decode_calls_behind += bool(engine._in_flight) and (
                engine._in_flight[0].step < engine._step_index
            )
            self.aliased["decode_step"] += np.shares_memory(packed, engine._decode_packed)
            self.check_decode(np.array(packed))
            return decode_jit(variables, packed, *args, **caches)

        def watched_prefill_call(variables, packed, *args, **caches):
            self.calls["prefill_chunk"] += 1
            self.aliased["prefill_chunk"] += np.shares_memory(packed, engine._prefill_packed)
            self.check_prefill(np.array(packed))
            return prefill_jit(variables, packed, *args, **caches)

        engine._run_prefill, engine._run_decode = watched_prefill, watched_decode
        engine._decode_jit, engine._prefill_jit = watched_decode_call, watched_prefill_call

    def expect(self, what, got, want):
        if not np.array_equal(got, want):
            self.violations.append(
                f"step {self.engine._step_index} {what}: handed {np.asarray(got).tolist()}, "
                f"the rebuild gives {np.asarray(want).tolist()}"
            )

    def check_decode(self, packed):
        engine = self.engine
        sent = _split(packed, engine._decode_fields)
        for slot in range(engine.config.max_batch):
            request = engine.scheduler.running.get(slot)
            if request is not None and request.decoding:
                self.tenants[slot].add(request.id)
                want = (request.cache_len, engine._table_row(request))
                # the books moved when the call before was enqueued, so the
                # length `_run_decode` was called with IS the one handed
                self.expect(
                    f"decode slot {slot} length at _run_decode", self.lengths_at_run_decode.get(slot), want[0]
                )
            else:  # idle, its prompt still prefilling, or evicted a moment ago
                want = (0, np.zeros_like(sent["tables"][slot]))
            self.expect(f"decode slot {slot} length", sent["lengths"][slot], want[0])
            self.expect(f"decode slot {slot} table", sent["tables"][slot], want[1])
            if "window_tables" in sent:
                row = (
                    engine._table_row(request, window=True) if want[0]
                    else np.zeros_like(sent["window_tables"][slot])
                )
                self.expect(f"decode slot {slot} window table", sent["window_tables"][slot], row)
        self.expect("decode call index", sent["call"], engine._call)

    def check_prefill(self, packed):
        engine = self.engine
        request, chunk, start = self.plan
        sent = _split(packed, engine._prefill_fields)
        ids = np.zeros_like(sent["ids"])
        ids[0, : len(chunk)] = chunk
        self.expect("chunk ids", sent["ids"], ids)
        self.expect(
            "chunk tokens, start, slot, fresh, call",
            [sent[k] for k in ("tokens", "start", "slot", "fresh", "call")],
            [len(chunk), start, request.slot, start == 0, engine._call],
        )
        self.expect("chunk table", sent["tables"][0], engine._table_row(request))
        if "window_tables" in sent:
            self.expect("chunk window table", sent["window_tables"][0], engine._table_row(request, window=True))


def _first_tokens(engine, n):
    """What `EOS_PROMPT` decodes to, alone: its second token then stops it."""
    events = engine.run([{"id": "probe", "prompt": EOS_PROMPT, "max_new_tokens": n}])
    return [e["token"] for e in events if e["type"] == "token"]


def _schedule(engine, steps=STEPS, seed=0):
    """Seeded arrivals over `steps` engine steps, with the events no arrival
    brings planted at fixed steps. -> (events, what happened)."""
    rng = random.Random(seed)
    happened = dict.fromkeys(EVENTS, 0)
    scheduler = engine.scheduler
    ensure = scheduler.ensure_decode_blocks

    def counted_ensure(request):
        before = scheduler.evictions
        kept = ensure(request)
        happened["eviction_in_decode_blocks"] += scheduler.evictions - before
        return kept

    scheduler.ensure_decode_blocks = counted_ensure
    events, submitted = [], 0
    for step in range(steps):
        if step == 5:  # stops at its second token
            events += engine.submit("eos", EOS_PROMPT, max_new_tokens=12)
        elif step == 40:  # a journal's entry after a relaunch: prompt and progress folded in
            events += engine.submit_resumed({
                "id": "resumed", "prompt": [5, 9, 11, 2], "generated": [7, 7, 1],
                "logprobs": [-1.0, -1.5, -2.0], "emitted": 3, "max_new_tokens": 10,
            })
        elif step == 60:
            events += engine.submit("late", [8, 1, 13, 21, 34, 55], max_new_tokens=30, deadline_ms=3.6e6)
        elif len(scheduler.waiting) < 2 and rng.random() < 0.45:
            prompt = [rng.randrange(1, 64) for _ in range(rng.choice((3, 6, 10, 15)))]
            events += engine.submit(f"r{submitted}", prompt, max_new_tokens=rng.choice((5, 12, 24)))
            submitted += 1
        for request in scheduler.running.values():
            if request.id == "late" and len(request.generated) >= 3:
                request.deadline_s = time.perf_counter() - 1.0  # expires mid-decode, at this step's top
        events += engine.step()
        counts = engine._step_counts
        happened["chunk_beside_decode"] += bool(counts["prefill_chunks"] and counts["decode_rows"])
        happened["ring_pages_given_back"] += counts.get("window_pages_released", 0)
    for event in events:
        if event["type"] == "done":
            reason = event["stop_reason"]
            happened["finish_by_length"] += reason == "max_tokens"
            happened["finish_by_eos"] += reason == "eos"
            happened["deadline_expiry"] += reason == "deadline" and event["n_tokens"] >= 3
            happened["submit_resumed"] += event["id"] == "resumed" and reason in ("max_tokens", "eos")
    return events, happened


@pytest.fixture(scope="module", params=sorted(BUILD))
def watched(request):
    previous = set_registry(TelemetryRegistry())  # this run's counters alone
    try:
        engine = BUILD[request.param](**SERVE)
        engine.config.eos_token_id = _first_tokens(engine, 3)[1]
        watch = Watch(engine)
        events, happened = _schedule(engine)
        happened["second_tenant"] = sum(len(ids) > 1 for ids in watch.tenants)
        yield request.param, engine, watch, happened, engine.stats()
    finally:
        set_registry(previous)


@pytest.mark.parametrize("event", EVENTS)
def test_handed_tables_equal_the_rebuild_through(watched, event):
    """Every call of both programs was handed the rebuild's inputs, in a run
    where `event` happened: zeros for every slot that did not decode."""
    config, engine, watch, happened, _ = watched
    if event == "ring_pages_given_back" and engine.window_allocator is None:
        pytest.skip("one page group: no ring")
    assert happened[event] > 0, happened
    assert not watch.violations, watch.violations[:5]


def test_cache_len_at_run_decode_is_the_length_the_call_is_handed(watched):
    """What the benchmark's harness counts (`sum(cache_len + 1)` at
    `_run_decode`'s call, the bytes `paged_decode_roofline_pct` divides by):
    the books moved when the call before was enqueued, so at every call each
    decoding row's `cache_len` there was the length the program was handed
    (the watcher records a violation otherwise), a step ahead of the fetches:
    nearly every decode call was enqueued with the step before's unread."""
    _, engine, watch, happened, stats = watched
    assert not [v for v in watch.violations if "length" in v]
    assert watch.decode_calls_behind >= watch.calls["decode_step"] - 8  # flushes: evictions, the deadline
    assert stats["serve/steps_ahead"] >= watch.decode_calls_behind
    # eos set: a finish by eos cost a row-step (two where the eos was a residency's
    # first token: the decode step beside its last chunk AND the next were enqueued
    # before it was read; none where the length ran out with it), and nothing else did
    assert 0 < stats["serve/discarded_row_steps"] <= 2 * happened["finish_by_eos"]
    assert stats["serve/pipeline_flushes"] >= happened["eviction_in_decode_blocks"] + 1


def test_rows_are_edited_not_rebuilt(watched):
    """The counter says so: far fewer entries written than rows decoded x the
    table's width, which is what a rebuild a step writes."""
    _, engine, _, _, stats = watched
    width = engine.pages_per_request + (engine.window_pages or 0)
    assert 0 < stats["serve/table_writes"] < stats["serve/decode_rows"] * width / 4


@pytest.mark.parametrize("program", ["prefill_chunk", "decode_step"])
def test_a_call_is_handed_a_copy_of_the_staging_buffer(watched, program):
    """The runtime may read a numpy argument after the call returns (the CPU
    backend aliases a 64-byte-aligned one outright), and a chunk that is not a
    prompt's last is not fetched before the next one fills the buffer: handed
    the buffer itself, one engine in four served garbage under load."""
    _, _, watch, _, _ = watched
    assert watch.calls[program] > 20 and watch.aliased[program] == 0


def _tokens(events):
    return [(e["id"], e["token"]) for e in events if e["type"] == "token"]


@pytest.mark.parametrize("config", sorted(BUILD))
def test_a_prefilling_slots_pages_shown_to_decode_change_served_tokens(config):
    """The planted fault: the kept table, unmasked. A slot whose prompt is
    still prefilling goes into `decode_step` with length 0, so its append
    overwrites position 0 of the request's first page: wrong tokens, no crash."""
    def serve(fault):
        engine = BUILD[config](**{**SERVE, "num_blocks": None})
        decode_jit = engine._decode_jit

        def unmasked(variables, packed, *args, **caches):
            sent = _split(packed, engine._decode_fields)
            for slot, request in engine.scheduler.running.items():
                if not request.decoding:
                    engine._sync_row(request)
                    sent["tables"][slot] = engine._tables[slot]
                    if "window_tables" in sent:
                        sent["window_tables"][slot] = engine._window_tables[slot]
            return decode_jit(variables, packed, *args, **caches)

        if fault:
            engine._decode_jit = unmasked
        events = engine.submit("first", [3, 17, 42], max_new_tokens=24)
        for step in range(40):
            if step == 2:  # five chunks beside the first request's decode steps
                events += engine.submit("long", list(range(11, 29)), max_new_tokens=12)
            events += engine.step()
        return _tokens(events)

    sound, faulty = serve(False), serve(True)
    assert len(sound) == len(faulty) == 36
    assert [t for t in sound if t[0] == "first"] == [t for t in faulty if t[0] == "first"]
    assert [t for t in sound if t[0] == "long"] != [t for t in faulty if t[0] == "long"]


# ------------------------------- what a process start traces: the delta rules
#
# A delta-rule stack's decode step takes the `delta_step` kernel on a TPU
# (`ops/delta_rule.py:slab_rows`) and the XLA step elsewhere. Either way each
# serving program is traced ONCE over an engine's construction and first
# steps: the path's counter is a side effect of that trace, and nothing beside
# it lowers or shape-evaluates a step (PR 46 lost `setup_s` to host work the
# compile cache cannot skip: PERF.md section 6).

PROGRAMS = ("prefill_chunk", "decode_step")
# state heads that are whole 8 x 128 tiles, as the kernel wants them
DELTA_STACKS = {
    "solar_open2": (
        "SolarOpen2",
        dict(TINY_SOLAR, linear_head_dim=128, moe_impl="dense"),
    ),
    "olmo_hybrid": ("OlmoHybrid", dict(TINY_OLMOH, linear_key_head_dim=8)),
}


@functools.cache
def _delta_stack_served(family, path):
    """A tiny engine of `family` whose one-token steps take `path`, through
    two requests' prefill and decode: (trace events a program, its stats,
    tokens and logprobs a request)."""
    import jax
    from jax._src import monitoring

    from llm_training_tpu import models
    from llm_training_tpu.ops import delta_rule
    from llm_training_tpu.serve.engine import ServeConfig, ServingEngine

    name, config = DELTA_STACKS[family]
    model = getattr(models, name)(getattr(models, f"{name}Config")(**config))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    traced = dict.fromkeys(PROGRAMS, 0)

    def listen(event, duration, **kwargs):
        if event == "/jax/core/compile/jaxpr_trace_duration" and kwargs.get("fun_name") in traced:
            traced[kwargs["fun_name"]] += 1

    previous = set_registry(TelemetryRegistry())
    on_kernels, delta_rule._on_kernels = delta_rule._on_kernels, lambda impl: path == "kernel"
    monitoring.register_event_duration_secs_listener(listen)
    try:
        engine = ServingEngine(model, variables, ServeConfig(**SERVE_SPANS))
        events = engine.submit("a", [3, 17, 42, 7, 9, 11], max_new_tokens=6)
        events += engine.submit("b", [5, 9, 11], max_new_tokens=6)
        steps = 0
        decode_steps = get_registry().counter("serve/decode_steps")
        while decode_steps.value < 2:  # first prefill, first two decode steps
            events += engine.step()
            steps += 1
            assert steps < 20
        early = dict(traced)
        while not engine.idle:
            events += engine.step()
        stats = engine.stats()
        engine.close()
    finally:
        monitoring.unregister_event_duration_listener(listen)
        delta_rule._on_kernels = on_kernels
        set_registry(previous)
    done = {e["id"]: (e["tokens"], e["logprobs"]) for e in events if e["type"] == "done"}
    return early, traced, stats, done


@pytest.mark.parametrize("path", ["kernel", "xla"])
@pytest.mark.parametrize("family", sorted(DELTA_STACKS))
def test_a_delta_rule_engine_traces_each_serving_program_once(family, path):
    early, traced, stats, done = _delta_stack_served(family, path)
    assert early == traced == dict.fromkeys(PROGRAMS, 1)
    # three delta-rule layers of four, all on the one path, and `stats()` says which
    other = "xla" if path == "kernel" else "kernel"
    assert stats[f"decode/delta_step_calls/{path}"] == 3 and stats[f"decode/delta_step_calls/{other}"] == 0
    assert len(done) == 2 and all(len(tokens) == 6 for tokens, _ in done.values())


@pytest.mark.parametrize("family", sorted(DELTA_STACKS))
def test_the_kernel_serves_what_the_xla_step_serves(family):
    from llm_training_tpu.telemetry.report import _serving_section

    (_, _, stats, kernel), (_, _, _, xla) = (_delta_stack_served(family, path) for path in ("kernel", "xla"))
    for request in xla:
        assert kernel[request][0] == xla[request][0]
        assert np.allclose(kernel[request][1], xla[request][1], atol=1e-5)
    line = next(line for line in _serving_section(stats) if line.startswith("one-token delta rule:"))
    assert line == "one-token delta rule: 3 layers in the delta_step kernel"


def test_an_engine_zeroes_the_paths_count_and_a_stack_without_a_delta_rule_leaves_it():
    from llm_training_tpu.ops.delta_rule import DELTA_STEP_GAUGES
    from llm_training_tpu.telemetry.report import _serving_section

    previous = set_registry(TelemetryRegistry())
    try:
        for gauge in DELTA_STEP_GAUGES.values():
            get_registry().gauge(gauge).set(5)  # another engine's
        engine = _engine()
        assert [get_registry().gauge(gauge).value for gauge in DELTA_STEP_GAUGES.values()] == [0, 0]
        engine.run([{"id": "a", "prompt": [3, 17, 42], "max_new_tokens": 3}])
        stats = engine.stats()
        engine.close()
    finally:
        set_registry(previous)
    assert [stats[gauge] for gauge in DELTA_STEP_GAUGES.values()] == [0, 0]
    assert not [line for line in _serving_section(stats) if line.startswith("one-token delta rule")]
