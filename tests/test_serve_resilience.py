"""Serving-tier resilience tests (docs/serving.md#resilience): deadlines
and load shedding in the jax-free scheduler, the request journal's
replay/dedupe contract, graceful drain + supervised replay token identity
(the tier-1 pin behind the precommit serve-drain gate), hot weight reload
with generation-tagged chunks, chaos serve faults, and the `== Serving ==`
resilience counters."""

import json
import os
import signal
import time

import pytest

from llm_training_tpu.serve.journal import RequestJournal, replay_journal
from llm_training_tpu.serve.paged_cache import BlockAllocator
from llm_training_tpu.serve.scheduler import (
    Scheduler,
    SchedulerConfig,
    ServeRequest,
)

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, attention_impl="xla",
    compute_dtype="float32", param_dtype="float32",
)


def _scheduler(max_batch=2, blocks=8, block_size=8, max_len=32, chunk=4,
               max_queue=None, shed_ttft_ms=None):
    return Scheduler(
        SchedulerConfig(
            max_batch=max_batch, max_model_len=max_len,
            block_size=block_size, prefill_chunk=chunk,
            max_queue=max_queue, shed_ttft_ms=shed_ttft_ms,
        ),
        BlockAllocator(blocks + 1),
    )


def _request(rid, prompt_len=2, n=4, priority=0, arrival=None, deadline_s=None):
    request = ServeRequest(
        id=rid, prompt=[1] * prompt_len, max_new_tokens=n, priority=priority,
        **({"arrival_s": arrival} if arrival is not None else {}),
    )
    request.deadline_s = deadline_s
    return request


# ------------------------------------------------------------- deadlines


def test_deadline_expires_in_queue():
    """A queued request past its deadline terminates with 'deadline'
    before costing a prefill FLOP; an undeadlined neighbor is untouched."""
    scheduler = _scheduler()
    now = time.perf_counter()
    late = _request("late", arrival=now - 10.0, deadline_s=now - 1.0)
    fine = _request("fine", arrival=now - 10.0)
    scheduler.submit(late)
    scheduler.submit(fine)
    scheduler.expire_deadlines(now)
    assert late.stop_reason == "deadline"
    assert late in scheduler.completed
    assert list(scheduler.waiting) == [fine] and fine.stop_reason is None
    assert scheduler.deadline_total == 1


def test_deadline_expires_mid_decode_frees_blocks():
    """A DECODING request past its deadline finishes (slot + blocks
    released) and its streamed-so-far tokens stand as the partial
    result."""
    scheduler = _scheduler()
    now = time.perf_counter()
    request = _request("r", prompt_len=4, n=8, deadline_s=now + 60.0)
    scheduler.submit(request)
    scheduler.admit()
    assert scheduler.allocator.blocks_in_use >= 1
    request.generated = [7, 8]
    scheduler.expire_deadlines(now + 120.0)  # deadline long blown
    assert request.stop_reason == "deadline"
    assert request.slot is None and scheduler.allocator.blocks_in_use == 0
    assert request.generated == [7, 8]
    assert scheduler.deadline_total == 1
    # expiry is idempotent: a second sweep finds nothing
    scheduler.expire_deadlines(now + 200.0)
    assert scheduler.deadline_total == 1


# ---------------------------------------------------------- load shedding


def test_shed_order_is_eviction_priority_order():
    """Over the queue bound, victims fall in eviction-priority order:
    lowest priority first, ties to the YOUNGEST arrival — under overload
    the queue keeps exactly the requests eviction would have kept."""
    scheduler = _scheduler(max_queue=1)
    now = 100.0
    vip = _request("vip", priority=2, arrival=now + 0)
    old = _request("old", priority=0, arrival=now + 1)
    young = _request("young", priority=0, arrival=now + 2)
    for request in (vip, old, young):
        scheduler.waiting.append(request)
    scheduler.shed()
    # two must go to reach max_queue=1: both priority-0s, youngest first
    assert young.stop_reason == "overloaded"
    assert old.stop_reason == "overloaded"
    assert vip.stop_reason is None and list(scheduler.waiting) == [vip]
    assert scheduler.shed_total == 2


def test_bounded_queue_backpressure_at_submit():
    """With every decode slot busy, submit itself sheds over the bound —
    an honest synchronous 'overloaded', never a wedged or unbounded
    intake. With a slot free the bound waits for the next admit pass."""
    scheduler = _scheduler(max_batch=1, max_queue=1)
    running = _request("running", prompt_len=4, n=8)
    scheduler.submit(running)
    scheduler.admit()
    assert not scheduler._free_slots
    first = _request("q1")
    second = _request("q2", arrival=time.perf_counter() + 1)
    scheduler.submit(first)
    assert first.stop_reason is None  # within the bound
    scheduler.submit(second)
    # over the bound while saturated: the lowest-priority/youngest queued
    # request is shed immediately
    assert second.stop_reason == "overloaded"
    assert list(scheduler.waiting) == [first]
    # free-slot case: no shed at submit even over the bound
    relaxed = _scheduler(max_batch=2, max_queue=0)
    queued = _request("q")
    relaxed.submit(queued)
    assert queued.stop_reason is None and list(relaxed.waiting) == [queued]


def test_projected_ttft_shedding():
    """With a service-time estimate, a queue tail projecting past
    shed_ttft_ms is shed; without an estimate TTFT shedding never fires
    (no guess, no drop)."""
    scheduler = _scheduler(max_batch=2, shed_ttft_ms=1500.0)
    for n in range(4):
        scheduler.waiting.append(_request(f"r{n}", arrival=100.0 + n))
    scheduler.shed()  # no EMA yet: nothing sheds
    assert scheduler.shed_total == 0 and len(scheduler.waiting) == 4
    scheduler._service_ema_s = 1.0  # 1s/request, batch 2
    # tail at position 3 -> (3//2 + 1) * 1000ms = 2000ms > 1500ms
    assert scheduler.projected_ttft_ms(3) == pytest.approx(2000.0)
    scheduler.shed()
    # shedding stops once the tail projects inside the bound (position 1
    # -> 1000ms)
    assert len(scheduler.waiting) == 2
    assert scheduler.shed_total == 2
    assert [r.id for r in scheduler.waiting] == ["r0", "r1"]


def test_finish_seeds_service_time_ema():
    scheduler = _scheduler()
    request = _request("r", prompt_len=4, n=2,
                       arrival=time.perf_counter() - 2.0)
    scheduler.submit(request)
    scheduler.admit()
    scheduler.finish(request, "max_tokens")
    assert scheduler._service_ema_s == pytest.approx(2.0, abs=0.5)
    # failures never feed the estimate
    failed = _request("f", arrival=time.perf_counter() - 50.0)
    scheduler.submit(failed)
    scheduler.admit()
    scheduler.finish(failed, "deadline")
    assert scheduler._service_ema_s == pytest.approx(2.0, abs=0.5)


# ---------------------------------------------------------------- journal


def test_journal_roundtrip_dedupe_and_done_exclusion(tmp_path):
    path = tmp_path / "journal.jsonl"
    journal = RequestJournal(path)
    a = _request("a", prompt_len=3, n=8)
    journal.accepted(a)
    a.generated = [5, 6]
    a.emitted = 2
    journal.progress(a)
    b = _request("b", prompt_len=1, n=2)
    journal.accepted(b)
    b.stop_reason = "max_tokens"
    journal.finished(b)
    # id reuse: a NEW 'a' accepted after the first — last acceptance wins
    a2 = _request("a", prompt_len=2, n=4)
    journal.accepted(a2)
    journal.close()
    entries = replay_journal(path)
    assert [e["id"] for e in entries] == ["a"]
    assert entries[0]["prompt"] == [1, 1]  # the reused acceptance
    assert entries[0]["generated"] == [] and entries[0]["emitted"] == 0
    # replay is a pure read: a second fold sees the same remainder
    assert replay_journal(path) == entries


def test_journal_survives_torn_tail_and_junk(tmp_path):
    path = tmp_path / "journal.jsonl"
    journal = RequestJournal(path)
    request = _request("a", prompt_len=2, n=8)
    request.deadline_s = request.arrival_s + 0.25
    journal.accepted(request)
    request.generated = [9]
    request.emitted = 1
    journal.progress(request)
    journal.close()
    with open(path, "a") as f:
        f.write('["not", "a", "record"]\n')
        f.write('{"event": "done", "id": 42}\n')  # non-str id: skipped
        f.write('{"event": "progress", "id": "a", "gen')  # torn tail
    entries = replay_journal(path)
    assert len(entries) == 1
    assert entries[0]["generated"] == [9] and entries[0]["emitted"] == 1
    assert entries[0]["deadline_ms"] == pytest.approx(250.0, abs=1.0)
    assert replay_journal(tmp_path / "absent.jsonl") == []


def test_journal_progress_delta_encoding_folds_back(tmp_path):
    """Progress records are deltas (O(tokens) journal growth, not
    O(tokens^2)); the fold re-concatenates, and a gap from a dropped
    record degrades to the shorter known prefix — re-stream, never
    invent."""
    path = tmp_path / "journal.jsonl"
    journal = RequestJournal(path)
    request = _request("a", prompt_len=2, n=16)
    journal.accepted(request)
    request.generated = [1, 2, 3]
    request.emitted = 3
    journal.progress(request)
    request.generated = [1, 2, 3, 4, 5]
    request.emitted = 5
    journal.progress(request)
    journal.close()
    records = [json.loads(line) for line in path.read_text().splitlines()]
    deltas = [r for r in records if r["event"] == "progress"]
    assert [r["generated_from"] for r in deltas] == [0, 3]
    assert deltas[1]["generated"] == [4, 5]  # only the new tokens
    entries = replay_journal(path)
    assert entries[0]["generated"] == [1, 2, 3, 4, 5]
    assert entries[0]["emitted"] == 5
    # a gap (dropped record): later delta starts past the known prefix
    with open(path, "a") as f:
        f.write(json.dumps({
            "event": "progress", "id": "a", "generated_from": 9,
            "generated": [9], "emitted": 10,
        }) + "\n")
    gapped = replay_journal(path)
    assert gapped[0]["generated"] == [1, 2, 3, 4, 5]
    assert gapped[0]["emitted"] == 5


def test_journal_done_retires_on_next_step(tiny_model, tmp_path):
    """`done` records are deferred one step (the terminal chunk must reach
    the emitter first): right after a completion the journal still
    replays the request; after the next step it is retired."""
    model, variables = tiny_model
    engine = _engine(model, variables, max_batch=1)
    engine.attach_journal(RequestJournal(tmp_path / "j.jsonl"))
    events = list(engine.submit("r", [3, 17], max_new_tokens=2))
    while not any(e["type"] == "done" for e in events):
        events += engine.step()
    # terminal built and returned, not yet retired: a death here would
    # re-deliver (duplicate), never lose
    assert [e["id"] for e in replay_journal(tmp_path / "j.jsonl")] == ["r"]
    engine.step()  # the caller has emitted by now: retire
    assert replay_journal(tmp_path / "j.jsonl") == []


def test_journal_progress_skips_unchanged_state(tmp_path):
    path = tmp_path / "journal.jsonl"
    journal = RequestJournal(path)
    request = _request("a", prompt_len=2, n=8)
    journal.accepted(request)
    request.generated = [3]
    request.emitted = 1
    journal.progress(request)
    journal.progress(request)  # unchanged: no record
    request.generated = [3, 4]
    journal.progress(request)
    journal.close()
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["event"] for r in records] == ["accepted", "progress", "progress"]


# ------------------------------------------------------------ chaos faults


def test_chaos_serve_env_overlay(monkeypatch):
    from llm_training_tpu.resilience.chaos import ChaosConfig, config_from_env

    monkeypatch.setenv("LLMT_CHAOS_SERVE_STALL_STEP", "4")
    monkeypatch.setenv("LLMT_CHAOS_SERVE_SIGTERM_STEP", "6")
    monkeypatch.setenv("LLMT_CHAOS_SERVE_MALFORMED_FLOOD", "3")
    config = config_from_env(ChaosConfig())
    assert config.serve_stall_step == 4
    assert config.serve_sigterm_step == 6
    assert config.serve_malformed_flood == 3
    assert config.any_active()


def test_chaos_serve_faults_fire_once_first_attempt_only(monkeypatch):
    from llm_training_tpu.resilience.chaos import Chaos, ChaosConfig
    from llm_training_tpu.resilience.elastic import ATTEMPT_ENV

    class _Registry:
        def counter(self, name):
            class _C:
                def inc(self):
                    pass
            return _C()

    slept = []
    chaos = Chaos(
        ChaosConfig(serve_stall_step=3, serve_malformed_flood=2),
        registry=_Registry(),
    )
    monkeypatch.setenv(ATTEMPT_ENV, "1")
    assert not chaos.maybe_serve_stall(2, sleep=slept.append)
    assert chaos.maybe_serve_stall(3, sleep=slept.append)
    assert slept == [3600.0]
    assert not chaos.maybe_serve_stall(3, sleep=slept.append)  # once
    assert len(chaos.serve_malformed_lines()) == 2
    # attempt 2 (the supervised relaunch): every serve fault is inert
    monkeypatch.setenv(ATTEMPT_ENV, "2")
    relaunch = Chaos(
        ChaosConfig(serve_stall_step=3, serve_sigterm_step=3,
                    serve_malformed_flood=2),
        registry=_Registry(),
    )
    assert not relaunch.maybe_serve_stall(3, sleep=slept.append)
    assert not relaunch.maybe_serve_sigterm_mid_stream(3)
    assert relaunch.serve_malformed_lines() == []
    assert slept == [3600.0]


def test_chaos_serve_sigterm_delivers_signal(monkeypatch):
    from llm_training_tpu.resilience.chaos import Chaos, ChaosConfig
    from llm_training_tpu.resilience.elastic import ATTEMPT_ENV

    monkeypatch.setenv(ATTEMPT_ENV, "1")
    received = []
    previous = signal.signal(signal.SIGTERM, lambda s, f: received.append(s))
    try:
        chaos = Chaos(ChaosConfig(serve_sigterm_step=2))
        assert not chaos.maybe_serve_sigterm_mid_stream(1)
        assert chaos.maybe_serve_sigterm_mid_stream(2)
        assert received == [signal.SIGTERM]
        assert not chaos.maybe_serve_sigterm_mid_stream(2)  # once
    finally:
        signal.signal(signal.SIGTERM, previous)


# ------------------------------------------------- report + trace summary


def test_report_serving_resilience_counters():
    from llm_training_tpu.telemetry.report import _serving_section

    text = "\n".join(_serving_section({
        "serve/requests_completed": 3, "serve/tokens_per_sec": 10.0,
        "serve/shed_total": 2, "serve/deadline_total": 1,
        "serve/weights_generation": 4, "serve/replayed_requests": 5,
    }))
    assert "resilience: 2 shed (overloaded), 1 deadline-expired, " \
        "weights generation 4, 5 replayed from journal" in text
    # absent -> the whole resilience line is omitted (older telemetry)
    legacy = "\n".join(_serving_section({
        "serve/requests_completed": 3, "serve/tokens_per_sec": 10.0,
    }))
    assert "resilience:" not in legacy
    # zero-valued counters are as good as absent
    zeros = "\n".join(_serving_section({
        "serve/requests_completed": 3, "serve/shed_total": 0,
        "serve/deadline_total": 0, "serve/weights_generation": 0,
        "serve/replayed_requests": 0,
    }))
    assert "resilience:" not in zeros


def test_summarize_trace_counts_terminal_reasons():
    from llm_training_tpu.telemetry.trace import summarize_trace

    events = [
        {"ts": 1.0, "ph": "i", "cat": "serve", "name": "done",
         "args": {"request_id": "a", "stop_reason": "max_tokens",
                  "n_tokens": 4}},
        {"ts": 2.0, "ph": "i", "cat": "serve", "name": "done",
         "args": {"request_id": "b", "stop_reason": "deadline"}},
        {"ts": 3.0, "ph": "i", "cat": "serve", "name": "done",
         "args": {"request_id": "c", "stop_reason": "overloaded"}},
    ]
    summary = summarize_trace(events)
    assert summary["terminal_reasons"] == {
        "max_tokens": 1, "deadline": 1, "overloaded": 1,
    }
    assert summary["requests_completed"] == 1


# --------------------------------------------------- engine (jax) tests


@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import numpy as np

    from llm_training_tpu.models import Llama, LlamaConfig

    model = Llama(LlamaConfig(**TINY))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    return model, variables


def _engine(model, variables, **overrides):
    from llm_training_tpu.serve import ServeConfig, ServingEngine

    config = ServeConfig(**{
        "max_batch": 2, "max_model_len": 48, "block_size": 8,
        "prefill_chunk": 4, "eos_token_id": None, **overrides,
    })
    return ServingEngine(model, variables, config)


def test_drain_then_replay_is_token_identical_exactly_once(tiny_model, tmp_path):
    """THE tier-1 drain pin (mirrored end-to-end by the precommit
    serve-drain gate): mid-stream drain journals the remainder without
    emitting terminals and frees every pool block; a second engine's
    replay continues token-identically to an uninterrupted run, streams no
    token twice, and emits exactly one terminal per request."""
    model, variables = tiny_model
    prompts = {"a": [3, 17, 42], "b": [5, 9]}
    n = 10
    baseline = _engine(model, variables)
    base_done = {
        e["id"]: e for e in baseline.run([
            {"id": rid, "prompt": p, "max_new_tokens": n}
            for rid, p in prompts.items()
        ]) if e["type"] == "done"
    }

    journal_path = tmp_path / "serve-journal.jsonl"
    first = _engine(model, variables)
    first.attach_journal(RequestJournal(journal_path))
    events = []
    for rid, prompt in prompts.items():
        events += first.submit(rid, prompt, max_new_tokens=n)
    while sum(e["type"] == "token" for e in events) < 6:
        events += first.step()
    events += first.flush()  # the last call's tokens, to their clients before the drain
    first.drain()
    first.journal.close()
    assert first.allocator.blocks_in_use == 0, "drain leaked pool blocks"
    assert not [e for e in events if e["type"] == "done"], \
        "drain emitted a terminal it does not own"

    streamed = {
        rid: [e["token"] for e in events
              if e["type"] == "token" and e["id"] == rid]
        for rid in prompts
    }
    entries = replay_journal(journal_path)
    assert {e["id"] for e in entries} == set(prompts)
    second = _engine(model, variables)
    replay_events = []
    for entry in entries:
        replay_events += second.submit_resumed(entry)
    while not second.idle:
        replay_events += second.step()
    done = {e["id"]: e for e in replay_events if e["type"] == "done"}
    assert second.replayed_requests == 2
    for rid in prompts:
        total = streamed[rid] + [
            e["token"] for e in replay_events
            if e["type"] == "token" and e["id"] == rid
        ]
        assert total == base_done[rid]["tokens"], f"{rid} diverged across drain"
        assert done[rid]["tokens"] == base_done[rid]["tokens"]
        assert sum(
            e["type"] == "done" and e["id"] == rid for e in replay_events
        ) == 1
    assert second.allocator.blocks_in_use == 0
    stats = second.stats()
    assert stats["serve/replayed_requests"] == 2


def test_reload_weights_mid_stream_token_identity_and_tags(tiny_model):
    """Acceptance: reload_weights on a live engine neither drops nor
    corrupts the in-flight stream — post-reload tokens equal a FRESH
    engine on the new weights fed prompt + tokens-so-far (the fold-in
    point), and every chunk carries the generation it was decoded
    under."""
    import jax
    import numpy as np

    from llm_training_tpu.models import Llama, LlamaConfig

    model, v1 = tiny_model
    v2 = Llama(LlamaConfig(**TINY)).init(
        jax.random.key(1), np.zeros((1, 4), np.int32)
    )
    prompt, n = [3, 17, 42], 10
    engine = _engine(model, v1)
    events = list(engine.submit("r", prompt, max_new_tokens=n))
    while sum(e["type"] == "token" for e in events) < 4:
        events += engine.step()
    seen = sum(e["type"] == "token" for e in events)
    # the engine is a call ahead of what it has returned: one token made
    # under v1 is in flight. The reload reads it first, so it folds into the
    # requeued prompt and leaves (in the next step) tagged generation 0
    from llm_training_tpu.telemetry.registry import get_registry

    flushes = get_registry().counter("serve/pipeline_flushes")
    flushed = flushes.value
    assert engine._in_flight and engine.scheduler.running[0].in_flight == 1
    assert engine.reload_weights(v2) == 1
    assert not engine._in_flight and flushes.value == flushed + 1
    while not engine.idle:
        events += engine.step()
    token_events = [e for e in events if e["type"] == "token"]
    done = [e for e in events if e["type"] == "done"][0]
    pre_reload = [e["token"] for e in token_events[: seen + 1]]

    fresh = _engine(model, v2)
    fresh_done = [
        e for e in fresh.run([{
            "id": "f", "prompt": prompt + pre_reload,
            "max_new_tokens": n - len(pre_reload),
        }]) if e["type"] == "done"
    ][0]
    post_reload = [e["token"] for e in token_events[len(pre_reload):]]
    assert post_reload == fresh_done["tokens"], "reload corrupted the stream"
    generations = [e["generation"] for e in token_events]
    assert generations == [0] * len(pre_reload) + [1] * len(post_reload)
    assert done["generation"] == 1
    assert done["tokens"] == pre_reload + post_reload  # nothing dropped
    stats = engine.stats()
    assert stats["serve/weights_generation"] == 1


def test_reload_weights_rejects_mismatched_variables(tiny_model):
    import jax
    import numpy as np

    from llm_training_tpu.models import Llama, LlamaConfig

    model, variables = tiny_model
    engine = _engine(model, variables)
    other = Llama(LlamaConfig(**{**TINY, "num_hidden_layers": 1})).init(
        jax.random.key(2), np.zeros((1, 4), np.int32)
    )
    with pytest.raises(ValueError, match="reload_weights"):
        engine.reload_weights(other)
    assert engine.weights_generation == 0


def test_engine_deadline_mid_decode_emits_done(tiny_model):
    """A deadline blowing mid-decode surfaces as a 'deadline' done chunk
    on the next step, with the partial tokens and the generation tag."""
    model, variables = tiny_model
    engine = _engine(model, variables)
    events = list(engine.submit(
        "r", [3, 17, 42], max_new_tokens=10, deadline_ms=60_000.0
    ))
    while sum(e["type"] == "token" for e in events) < 2:
        events += engine.step()
    request = next(iter(engine.scheduler.running.values()))
    request.deadline_s = time.perf_counter() - 1.0  # blow it mid-decode
    events += engine.step()
    done = [e for e in events if e["type"] == "done"]
    assert len(done) == 1 and done[0]["stop_reason"] == "deadline"
    assert done[0]["n_tokens"] >= 2 and "generation" in done[0]
    assert engine.scheduler.idle and engine.allocator.blocks_in_use == 0
    assert engine.stats()["serve/deadline_total"] == 1


def test_engine_sheds_over_bounded_queue(tiny_model):
    """max_queue=0 with one decode slot: the queued second request is shed
    with an honest 'overloaded' terminal while the first streams to
    completion."""
    model, variables = tiny_model
    engine = _engine(model, variables, max_batch=1, max_queue=0)
    events = list(engine.submit("first", [3, 17], max_new_tokens=4))
    events += list(engine.submit("second", [5, 9], max_new_tokens=4))
    while not engine.idle:
        events += engine.step()
    done = {e["id"]: e for e in events if e["type"] == "done"}
    assert done["second"]["stop_reason"] == "overloaded"
    assert done["first"]["stop_reason"] == "max_tokens"
    assert len(done["first"]["tokens"]) == 4
    assert engine.stats()["serve/shed_total"] == 1
