"""The serving engine runs one step ahead of its fetches (docs/serving.md, "A
step ahead of its fetches"): a row's last token stays on the device, step n+1
is enqueued before step n's tokens are read, and the host's books move when a
call is enqueued. What must hold: the schedule, the calls and every request's
tokens are those of the engine that reads each call at once (`flush()` after
every `step()` IS that engine), wherever the host needs a value it reads first
and loses nothing, and a finish the host can only see late (`eos`) costs one
row-step, counted."""

import time

import numpy as np
import pytest
from conftest import full_forward_greedy
from test_serve_spans import _engine, _longcat_engine, _solar_engine, fresh  # noqa: F401

from llm_training_tpu.serve.journal import RequestJournal, replay_journal
from llm_training_tpu.telemetry import get_registry
from llm_training_tpu.telemetry.registry import TelemetryRegistry, set_registry

PROMPTS = {"a": [3, 17, 42, 7, 9, 11], "b": [5, 9, 11], "c": [4, 8, 15, 16, 23]}


def _serve(engine, requests, synchronous, before_step=None, max_steps=400):
    """Submit, then step until nothing is owed. `synchronous` reads every call
    in the step that enqueued it. -> (events in order, steps taken)."""
    events = []
    for request in requests:
        events += engine.submit(**request)
    for step in range(max_steps):
        if engine.idle:
            return events, step
        if before_step is not None:
            before_step(engine, step)
        events += engine.step()
        if synchronous:
            events += engine.flush()
    raise AssertionError("not drained")


def _outcomes(events):
    done = {e["id"]: e for e in events if e["type"] == "done"}
    streamed = {}
    for e in events:
        if e["type"] == "token":
            streamed.setdefault(e["id"], []).append(e["token"])
    return {
        rid: (d["stop_reason"], d["tokens"], [round(lp, 5) for lp in d["logprobs"]], d["evictions"],
              streamed.get(rid, []))
        for rid, d in done.items()
    }


def _counter(name):
    return get_registry().counter(f"serve/{name}").value


def _flush_reasons(tracer):
    return [e["args"]["reason"] for e in tracer.snapshot() if e["name"] == "pipeline_flush"]


# ------------------------------------------------------- two calls in flight


def test_a_decode_step_is_enqueued_before_the_one_before_is_read(fresh):
    engine = _engine(max_batch=3)
    behind, run_decode = [], engine._run_decode

    def watched(rows):
        # what the step before enqueued is unread when this one's rows are chosen
        behind.append([call.step for call in engine._in_flight if call.step < engine._step_index])
        return run_decode(rows)

    engine._run_decode = watched
    requests = [{"id": k, "prompt": p, "max_new_tokens": 12} for k, p in PROMPTS.items()]
    events, steps = _serve(engine, requests, synchronous=False)
    decode_steps = int(_counter("decode_steps"))
    assert len(behind) == decode_steps > 12
    # every decode call but the first found the step before's outputs unread
    assert sum(bool(b) for b in behind) == decode_steps - 1
    # depth two and no more: at most the calls of ONE earlier step
    assert all(len(set(b)) <= 1 for b in behind)
    assert _counter("steps_ahead") >= decode_steps - 1
    assert _counter("pipeline_flushes") == _counter("discarded_row_steps") == 0
    assert engine.stats()["serve/steps_ahead"] == _counter("steps_ahead")
    for rid, (reason, tokens, *_) in _outcomes(events).items():
        assert reason == "max_tokens"
        assert tokens == full_forward_greedy(engine.model, engine.variables, PROMPTS[rid], 12)


def test_the_carry_holds_each_rows_last_token_and_is_never_donated(fresh):
    engine = _engine(max_batch=3)
    for rid, prompt in PROMPTS.items():
        engine.submit(rid, prompt, max_new_tokens=20)
    for _ in range(10):
        engine.step()
    unread = engine._in_flight[-1].tokens  # the array the host will read later
    engine.step()  # the next call took it as an input
    assert not unread.is_deleted() and unread is not engine._last_tokens
    engine.flush()
    carry = np.asarray(engine._last_tokens)
    rows = [r for r in engine.scheduler.running.values() if r.decoding]
    assert len(rows) == 3
    for request in rows:
        assert carry[request.slot] == request.generated[-1] and request.in_flight == 0
    assert "tokens" not in engine._decode_fields  # no token travels to the device


# ---------------------------------------- a flush wherever a value is needed


def _plant(reason, state):
    """The event that needs a token's value, planted at the same point of the
    schedule on both engines: when request `a` has 4 tokens made (read or in
    flight), with one of them in flight on the engine that runs ahead."""
    def before_step(engine, step):
        request = next((r for r in engine.scheduler.running.values() if r.id == "a"), None)
        if state.get("planted") or request is None:
            return
        if len(request.generated) + request.in_flight < 4:
            return
        state["planted"] = True
        state["in_flight"] = request.in_flight
        if reason == "deadline":
            request.deadline_s = time.perf_counter() - 1.0
        elif reason == "reload_weights":
            engine.reload_weights(engine.variables)
    return before_step


@pytest.mark.parametrize("reason", ["eviction", "deadline", "reload_weights"])
def test_a_value_the_host_needs_is_read_first_and_no_token_is_lost(fresh, reason):
    # (eviction: 3 usable pages for two rows that grow past a page boundary each)
    serve = dict(max_batch=2, max_model_len=32, num_blocks=3) if reason == "eviction" else {}
    requests = [
        {"id": k, "prompt": PROMPTS[k], "max_new_tokens": 12, "priority": int(k == "b")}
        for k in ("a", "b")
    ]
    got = {}
    for synchronous in (True, False):
        previous = set_registry(TelemetryRegistry())
        try:
            state = {}
            events, _ = _serve(
                _engine(**serve), requests, synchronous,
                before_step=None if reason == "eviction" else _plant(reason, state),
            )
            got[synchronous] = (_outcomes(events), state, _counter("pipeline_flushes"),
                                _counter("decode_rows"), _counter("prefill_chunks"))
        finally:
            set_registry(previous)
    ahead, sync = got[False], got[True]
    # the same terminals, tokens, log-probabilities and evictions a request;
    # every token streamed once; the same calls
    assert ahead[0] == sync[0] and set(ahead[0]) == {"a", "b"}
    assert ahead[3:] == sync[3:]
    for rid, (stop, tokens, _, evictions, streamed) in ahead[0].items():
        assert streamed == tokens, rid
    if reason == "eviction":
        assert sum(o[3] for o in ahead[0].values()) >= 1
    else:
        assert ahead[1]["in_flight"] == 1 and sync[1]["in_flight"] == 0
    if reason == "deadline":
        assert ahead[0]["a"][0] == "deadline" and len(ahead[0]["a"][1]) == 4
    assert reason in _flush_reasons(fresh)
    assert ahead[2] >= 1


def test_a_drain_reads_the_token_in_flight_and_the_replay_streams_it(fresh, tmp_path):
    """A caller that drains without `flush()`: the token in flight is in the
    journal's `generated` and NOT under its `emitted` mark, so the relaunch
    streams it once; a request whose last token was in flight (finished by
    length, in no queue the drain walks) keeps its last journaled progress,
    and the relaunch makes the rest again and owes its terminal."""
    requests = [
        {"id": "a", "prompt": PROMPTS["a"], "max_new_tokens": 10},
        {"id": "short", "prompt": PROMPTS["b"], "max_new_tokens": 4},
    ]
    baseline, _ = _serve(_engine(), requests, synchronous=False)
    want = {rid: o[1] for rid, o in _outcomes(baseline).items()}

    first = _engine()
    first.attach_journal(RequestJournal(tmp_path / "journal.jsonl"))
    events = []
    for request in requests:
        events += first.submit(**request)
    short = first.scheduler.waiting[1]
    while not short.done:  # its last token's call is enqueued: finished by length, unread
        events += first.step()
    assert short.in_flight >= 1 and len(short.generated) < 4
    summary = first.drain()
    first.journal.close()
    assert summary["blocks_in_use"] == 0 and not first._in_flight
    assert "drain" in _flush_reasons(fresh) and not first.flush()  # read, and nobody handed an event
    entries = {e["id"]: e for e in replay_journal(tmp_path / "journal.jsonl")}
    journaled = entries["short"]["generated"]
    assert len(journaled) == entries["short"]["emitted"] < 4 and journaled == want["short"][: len(journaled)]
    assert len(entries["a"]["generated"]) == entries["a"]["emitted"] + 1

    second = _engine()
    replayed = []
    for entry in entries.values():
        replayed += second.submit_resumed(entry)
    more, _ = _serve(second, [], synchronous=False)
    replayed += more
    outcomes = _outcomes(replayed)
    for rid in ("a", "short"):
        streamed = [e["token"] for e in events + replayed if e["type"] == "token" and e["id"] == rid]
        assert streamed == want[rid] == outcomes[rid][1], rid


# ------------------------------------------------------- a finish seen late


def test_an_eos_finish_costs_one_row_step_and_the_slot_serves_its_next_tenant(fresh):
    """Solar's stack: the slot's state slab is the old tenant's when the new
    one's `fresh` chunk is enqueued behind the old one's last (wasted) decode."""
    probe = _solar_engine(max_batch=1)
    alone, _ = _serve(probe, [{"id": "a", "prompt": PROMPTS["a"], "max_new_tokens": 8}], False)
    eos = _outcomes(alone)["a"][1][2]
    assert eos not in _outcomes(alone)["a"][1][:2]
    next_alone, _ = _serve(
        _solar_engine(max_batch=1), [{"id": "c", "prompt": PROMPTS["c"], "max_new_tokens": 8}], False
    )
    engine = _solar_engine(max_batch=1, eos_token_id=eos)
    before = (_counter("discarded_row_steps"), _counter("state_resets"))
    events, _ = _serve(engine, [
        {"id": "a", "prompt": PROMPTS["a"], "max_new_tokens": 8},
        {"id": "c", "prompt": PROMPTS["c"], "max_new_tokens": 8},
    ], synchronous=False)
    outcomes = _outcomes(events)
    assert outcomes["a"][0] == "eos" and outcomes["a"][1] == _outcomes(alone)["a"][1][:3]
    assert outcomes["a"][4] == outcomes["a"][1]  # the dropped token was never streamed
    assert _counter("discarded_row_steps") - before[0] == 1
    assert _counter("state_resets") - before[1] == 2  # the slot's second tenant started fresh
    if eos not in _outcomes(next_alone)["c"][1]:
        assert outcomes["c"][:2] == _outcomes(next_alone)["c"][:2]
    assert engine.allocator.blocks_in_use == 0 and engine.idle


def test_an_eos_on_the_token_before_the_length_ran_out_stops_at_eos(fresh):
    """Both tokens in flight at once: the length ran out at the enqueue of the
    second, then the first turns out to be `eos`."""
    probe, _ = _serve(_engine(), [{"id": "a", "prompt": PROMPTS["a"], "max_new_tokens": 2}], False)
    first, second = _outcomes(probe)["a"][1]
    assert first != second
    engine = _engine(eos_token_id=first)
    events, _ = _serve(engine, [{"id": "a", "prompt": PROMPTS["a"], "max_new_tokens": 2}], False)
    assert _outcomes(events)["a"][:2] == ("eos", [first])
    assert [e["type"] for e in events] == ["token", "done"]
    assert _counter("discarded_row_steps") == 1 and engine.idle
    assert engine.stats()["serve/requests_completed"] == 1


# ------------------------------------------------- both tokens in flight at once


@pytest.mark.parametrize("n", [1, 2])
def test_a_request_of_one_or_two_tokens(fresh, n):
    engine = _engine()
    events = engine.submit("a", PROMPTS["a"], max_new_tokens=n)
    request = engine.scheduler.waiting[0]
    events += engine.step()  # 4 of 6 prompt tokens
    events += engine.step()  # the last chunk, and for n == 2 the decode step beside it
    assert not events and request.in_flight == n
    # finished by length at the enqueue: its slot and pages are free, its terminal is not out
    assert request.stop_reason == "max_tokens" and engine.scheduler.idle and not engine.idle
    assert engine.allocator.blocks_in_use == 0
    events += engine.step()  # nothing to enqueue: reads
    assert [e["type"] for e in events] == ["token"] * n + ["done"]
    assert events[-1]["tokens"] == full_forward_greedy(engine.model, engine.variables, PROMPTS["a"], n)
    assert engine.idle and engine._step_index == 3


# ------------------------------------------------------------ expert counts


def test_expert_assignment_counters_sum_to_the_synchronous_engines():
    requests = [{"id": k, "prompt": p, "max_new_tokens": 9} for k, p in PROMPTS.items()]
    totals = {}
    for synchronous in (True, False):
        previous = set_registry(TelemetryRegistry())
        try:
            events, _ = _serve(_longcat_engine(max_batch=3), requests, synchronous)
            totals[synchronous] = (
                [_counter(f"moe_{kind}_assignments") for kind in ("held", "zero", "elsewhere")],
                {rid: o[1] for rid, o in _outcomes(events).items()},
            )
        finally:
            set_registry(previous)
    assert totals[True] == totals[False]
    # every real token of every call, twice (2 layers), three choices each:
    # 14 prompt tokens and 3 x 8 decoded ones (the last token is not fed back)
    assert sum(totals[False][0]) == (14 + 24) * 2 * 3 and all(totals[False][0])
