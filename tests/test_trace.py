"""Request/step tracing + flight recorder (docs/observability.md#tracing):
`TraceRecorder` units (ring bound, sampling, sink gating), the Chrome-trace
export and its Perfetto track mapping, `summarize_trace` aggregates, the
`trace` CLI, `report`'s `== Trace ==` section + `--format json` schema, and
the flight-dump hooks (watchdog hang dumps, anomaly dumps)."""

import json
import threading

import pytest

from llm_training_tpu.telemetry.trace import (
    TraceRecorder,
    clock_anchor,
    get_tracer,
    merge_traces,
    read_trace_events,
    resolve_trace_file,
    set_tracer,
    summarize_trace,
    to_chrome_trace,
    trace_main,
    wall_align,
)


@pytest.fixture()
def tracer():
    """A fresh recorder installed as process-current, restored afterwards
    (engine/scheduler/trainer code paths all emit through get_tracer())."""
    recorder = TraceRecorder(capacity=256, sample_every=1, train_steps=False,
                             enabled=True)
    previous = set_tracer(recorder)
    try:
        yield recorder
    finally:
        recorder.detach_sink()
        set_tracer(previous)


# ------------------------------------------------------------- recorder


def test_ring_is_bounded_and_keeps_newest():
    recorder = TraceRecorder(capacity=4, enabled=True)
    for n in range(10):
        recorder.instant("train", f"e{n}")
    names = [e["name"] for e in recorder.snapshot()]
    assert names == ["e6", "e7", "e8", "e9"]
    assert recorder.counts()["recorded"] == 10


def test_span_and_measure_record_duration(tracer):
    tracer.span("serve", "queue", 1.0, 1.5, request_id="r0")
    with tracer.measure("train", "compile"):
        pass
    spans = tracer.snapshot()
    assert spans[0]["ph"] == "X" and spans[0]["dur"] == pytest.approx(0.5)
    assert spans[0]["args"]["request_id"] == "r0"
    assert spans[1]["name"] == "compile" and spans[1]["dur"] >= 0.0


def test_sink_writes_only_sampled_events(tmp_path, tracer):
    path = tmp_path / "trace.jsonl"
    assert tracer.attach_sink(path)
    # the first owner keeps the sink; a second attach is refused
    assert not tracer.attach_sink(tmp_path / "other.jsonl")
    tracer.instant("serve", "submit", write=True, request_id="a")
    tracer.instant("serve", "submit", write=False, request_id="b")
    tracer.detach_sink()
    written = read_trace_events(path)
    # attaching always writes the clock anchor first — the wall/monotonic
    # pair `trace --merge` aligns replicas on — then sampled events only
    assert written[0]["cat"] == "meta" and written[0]["name"] == "clock_anchor"
    assert [e["args"]["request_id"] for e in written[1:]] == ["a"]
    counts = tracer.counts()
    assert counts["recorded"] == 3 and counts["written"] == 2


def test_request_sampling_every_nth():
    recorder = TraceRecorder(sample_every=3, enabled=True)
    decisions = [recorder.sample_request() for _ in range(7)]
    assert decisions == [True, False, False, True, False, False, True]
    assert recorder.counts()["requests_sampled"] == 3


def test_env_knobs_override_defaults(monkeypatch):
    monkeypatch.setenv("LLMT_TRACE_RING", "7")
    monkeypatch.setenv("LLMT_TRACE_SAMPLE", "4")
    monkeypatch.setenv("LLMT_TRACE_TRAIN", "1")
    recorder = TraceRecorder()
    assert recorder.capacity == 7
    assert recorder.sample_every == 4
    assert recorder.train_steps is True
    monkeypatch.setenv("LLMT_TRACE", "0")
    disabled = TraceRecorder()
    assert disabled.enabled is False
    disabled.instant("train", "e")
    assert disabled.snapshot() == []
    assert not disabled.attach_sink("/dev/null")


def test_malformed_env_degrades_to_default(monkeypatch):
    monkeypatch.setenv("LLMT_TRACE_RING", "banana")
    assert TraceRecorder().capacity == 2048


def test_recorder_is_thread_safe(tracer):
    def emit(tag):
        for n in range(200):
            tracer.instant("serve", f"{tag}-{n}")

    threads = [threading.Thread(target=emit, args=(t,)) for t in range(4)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    assert tracer.counts()["recorded"] == 800
    assert len(tracer.snapshot()) == 256  # capacity


def test_flight_dump_writes_ring(tmp_path, tracer):
    for n in range(5):
        tracer.instant("train", "train_step", step=n)
    path = tracer.flight_dump(tmp_path, "hang-test")
    assert path is not None and path.name == "trace-flight-hang-test.jsonl"
    events = read_trace_events(path)
    # a flight dump is mergeable too: its head line is a fresh clock anchor
    assert events[0]["cat"] == "meta" and events[0]["name"] == "clock_anchor"
    assert [e["args"]["step"] for e in events[1:]] == list(range(5))
    assert tracer.counts()["flight_dumps"] == 1


# --------------------------------------------------------------- export


def _sample_events():
    return [
        {"ts": 1.0, "dur": 0.5, "ph": "X", "cat": "serve", "name": "queue",
         "args": {"request_id": "r0", "residency": 0}},
        {"ts": 1.5, "dur": 1.0, "ph": "X", "cat": "serve", "name": "prefill",
         "args": {"request_id": "r0", "residency": 0}},
        {"ts": 2.5, "ph": "i", "cat": "serve", "name": "first_token",
         "args": {"request_id": "r0", "ttft_ms": 1500.0}},
        {"ts": 2.5, "dur": 0.7, "ph": "X", "cat": "serve", "name": "decode",
         "args": {"request_id": "r0", "residency": 0}},
        {"ts": 3.2, "ph": "i", "cat": "serve", "name": "done",
         "args": {"request_id": "r0", "stop_reason": "max_tokens",
                  "n_tokens": 8, "evictions": 0, "queue_wait_ms": 500.0}},
        {"ts": 0.9, "dur": 2.4, "ph": "X", "cat": "serve", "name": "engine_step",
         "args": {"step": 1}},
        {"ts": 0.0, "dur": 0.8, "ph": "X", "cat": "train", "name": "compile"},
        {"ts": 0.8, "dur": 0.1, "ph": "X", "cat": "train", "name": "train_step",
         "args": {"step": 0}},
        {"ts": 4.0, "ph": "i", "cat": "resilience", "name": "rollback",
         "args": {"failed_step": 3}},
    ]


def test_chrome_export_tracks_and_units():
    doc = to_chrome_trace(_sample_events())
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    names = {(e["pid"], e["tid"]): e["args"]["name"] for e in meta
             if e["name"] == "thread_name"}
    # the request got its own named track, distinct from the engine's
    request_tids = {e["tid"] for e in events
                    if e.get("args", {}).get("request_id") == "r0"}
    assert len(request_tids) == 1
    request_tid = request_tids.pop()
    assert names[(1, request_tid)] == "req r0"
    engine = next(e for e in events if e["name"] == "engine_step")
    assert engine["tid"] != request_tid
    # train and resilience land on their own pids
    assert next(e for e in events if e["name"] == "compile")["pid"] == 2
    assert next(e for e in events if e["name"] == "rollback")["pid"] == 3
    # µs conversion + instant scoping
    queue = next(e for e in events if e["name"] == "queue")
    assert queue["ts"] == pytest.approx(1.0e6) and queue["dur"] == pytest.approx(0.5e6)
    first = next(e for e in events if e["name"] == "first_token")
    assert first["ph"] == "i" and first["s"] == "t"


def test_chrome_export_skips_malformed_records():
    events = _sample_events() + [{"ts": "junk", "ph": "X", "name": "bad"}]
    doc = to_chrome_trace(events)
    assert all(e["name"] != "bad" for e in doc["traceEvents"])


def test_summarize_trace_aggregates_and_slowest():
    summary = summarize_trace(_sample_events())
    assert summary["events"] == 9
    assert summary["spans"]["serve/queue"]["count"] == 1
    assert summary["spans"]["train/train_step"]["total_s"] == pytest.approx(0.1)
    assert summary["requests_traced"] == 1
    assert summary["requests_completed"] == 1
    (slowest,) = summary["slowest_requests"]
    assert slowest["id"] == "r0"
    assert slowest["wall_ms"] == pytest.approx(2200.0)
    assert slowest["queue_ms"] == pytest.approx(500.0)
    assert slowest["prefill_ms"] == pytest.approx(1000.0)
    assert slowest["decode_ms"] == pytest.approx(700.0)
    assert slowest["ttft_ms"] == pytest.approx(1500.0)
    assert slowest["n_tokens"] == 8


def test_summarize_splits_reused_ids_across_appended_runs():
    """trace.jsonl appends across runs and the loadgen reuses req-0 per
    run: a second submit for an already-completed id must open a NEW
    logical request, not merge phases across runs (review finding)."""
    run1 = _sample_events()
    run2 = [
        {"ts": 10.0, "ph": "i", "cat": "serve", "name": "submit",
         "args": {"request_id": "r0", "prompt_len": 4}},
        {"ts": 10.0, "dur": 0.2, "ph": "X", "cat": "serve", "name": "queue",
         "args": {"request_id": "r0", "residency": 0}},
        {"ts": 10.2, "dur": 0.3, "ph": "X", "cat": "serve", "name": "prefill",
         "args": {"request_id": "r0", "residency": 0}},
        {"ts": 10.5, "ph": "i", "cat": "serve", "name": "first_token",
         "args": {"request_id": "r0", "ttft_ms": 500.0}},
        {"ts": 10.5, "dur": 0.1, "ph": "X", "cat": "serve", "name": "decode",
         "args": {"request_id": "r0", "residency": 0}},
        {"ts": 10.6, "ph": "i", "cat": "serve", "name": "done",
         "args": {"request_id": "r0", "stop_reason": "eos", "n_tokens": 2,
                  "evictions": 0, "queue_wait_ms": 200.0}},
    ]
    summary = summarize_trace(run1 + run2, top_k=5)
    assert summary["requests_traced"] == 2
    assert summary["requests_completed"] == 2
    by_id = {r["id"]: r for r in summary["slowest_requests"]}
    assert by_id["r0"]["wall_ms"] == pytest.approx(2200.0)  # run 1 alone
    assert by_id["r0#2"]["wall_ms"] == pytest.approx(600.0)  # run 2 alone
    assert by_id["r0#2"]["ttft_ms"] == pytest.approx(500.0)


def test_read_trace_events_tolerates_torn_tail(tmp_path):
    path = tmp_path / "trace.jsonl"
    path.write_text(
        json.dumps({"ts": 1.0, "ph": "i", "cat": "train", "name": "a"})
        + "\n[not json\n" + '{"no_ts": true}\n'
        + json.dumps({"ts": 2.0, "ph": "i", "cat": "train", "name": "b"})[:-4]
        + "\n"
    )
    events = read_trace_events(path)
    assert [e["name"] for e in events] == ["a"]


# ------------------------------------------------------------------ CLI


def test_trace_cli_exports_run_dir(tmp_path, capsys):
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    with open(run_dir / "trace.jsonl", "w") as f:
        for event in _sample_events():
            f.write(json.dumps(event) + "\n")
    assert resolve_trace_file(run_dir) == run_dir / "trace.jsonl"
    assert trace_main(str(run_dir)) == 0
    out = capsys.readouterr().out
    assert "perfetto" in out.lower()
    doc = json.loads((run_dir / "trace-export.json").read_text())
    assert doc["traceEvents"] and doc["displayTimeUnit"] == "ms"


def test_trace_cli_exit_2_on_missing_or_empty(tmp_path, capsys):
    assert trace_main(str(tmp_path)) == 2
    empty = tmp_path / "trace.jsonl"
    empty.write_text("not json\n")
    assert trace_main(str(tmp_path)) == 2
    capsys.readouterr()


# --------------------------------------------------------------- report


def _write_run_dir(tmp_path, with_trace=True):
    run_dir = tmp_path / "run"
    run_dir.mkdir(exist_ok=True)
    with open(run_dir / "metrics.jsonl", "w") as f:
        for step in (1, 2):
            f.write(json.dumps({"step": step, "loss": 2.0 - step * 0.1,
                                "steps_per_sec": 1.5}) + "\n")
    with open(run_dir / "telemetry.jsonl", "w") as f:
        f.write(json.dumps({
            "step": 2, "goodput/total_s": 10.0, "goodput/step_compute_s": 8.0,
            "goodput/goodput_pct": 80.0, "serve/requests_completed": 1.0,
            "trace/events_recorded": 9.0,
        }) + "\n")
    if with_trace:
        with open(run_dir / "trace.jsonl", "w") as f:
            for event in _sample_events():
                f.write(json.dumps(event) + "\n")
    return run_dir


def test_report_trace_section_renders_and_omits(tmp_path):
    from llm_training_tpu.telemetry.report import render_report

    run_dir = _write_run_dir(tmp_path)
    text = render_report(run_dir)
    assert "== Trace ==" in text
    assert "serve/queue" in text
    assert "slowest requests:" in text
    assert "r0:" in text
    # no trace.jsonl -> section omitted entirely
    (run_dir / "trace.jsonl").unlink()
    assert "== Trace ==" not in render_report(run_dir)


def test_report_trace_section_degrades_on_garbage(tmp_path):
    from llm_training_tpu.telemetry.report import render_report

    run_dir = _write_run_dir(tmp_path, with_trace=False)
    (run_dir / "trace.jsonl").write_text("not json at all\n{{{\n")
    text = render_report(run_dir)
    assert "== Trace ==" in text
    assert "no parseable events" in text


def test_report_json_schema(tmp_path):
    """`report --format json` (CI trend tracking): pin the top-level
    schema — every section key present, absent sections null, numbers
    where CI expects them."""
    from llm_training_tpu.telemetry.report import (
        REPORT_SCHEMA_VERSION,
        render_report_data,
    )

    run_dir = _write_run_dir(tmp_path)
    doc = render_report_data(run_dir)
    assert doc["schema_version"] == REPORT_SCHEMA_VERSION == 2
    for key in (
        "run_dir", "world", "training", "goodput", "device_memory",
        "health", "audit", "inference", "serving", "slo",
        "elastic", "trace", "recovery", "flash", "telemetry", "fleet",
    ):
        assert key in doc, key
    # no fleet.json snapshot in the fixture -> null block
    # (tests/test_fleet.py pins the populated shape)
    assert doc["fleet"] is None
    # no SLO config armed in the fixture -> null block, like the omitted
    # text section (tests/test_exporter.py pins the armed shape)
    assert doc["slo"] is None
    assert doc["training"]["records"] == 2
    assert doc["training"]["loss_last"] == pytest.approx(1.8)
    assert doc["goodput"]["goodput/goodput_pct"] == 80.0
    assert doc["serving"] == {"serve/requests_completed": 1.0}
    assert doc["trace"]["events"] == 9
    assert doc["health"] is None
    # the raw record rides along so no numeric key is lost to shaping
    assert doc["telemetry"]["trace/events_recorded"] == 9.0
    json.dumps(doc)  # the whole document must be JSON-serializable


def test_report_json_carries_supervisor_segments(tmp_path):
    """`--format json` must not drop the per-segment elastic data text
    mode renders from supervisor.jsonl (review finding)."""
    from llm_training_tpu.telemetry.report import render_report_data

    run_dir = _write_run_dir(tmp_path, with_trace=False)
    with open(run_dir / "supervisor.jsonl", "w") as f:
        f.write(json.dumps({
            "event": "segment_topology", "attempt": 0, "device_count": 8,
            "mesh": {"data": 8}, "decision": "fresh",
        }) + "\n")
        f.write(json.dumps({
            "event": "exit", "attempt": 0, "rc": -9, "signal": "SIGKILL",
            "runtime_s": 12.5,
        }) + "\n")
        f.write(json.dumps({
            "event": "segment_topology", "attempt": 1, "device_count": 4,
            "mesh": {"data": 4}, "decision": "scaled data 8->4",
        }) + "\n")
    doc = render_report_data(run_dir)
    segments = doc["elastic"]["segments"]
    assert [s["attempt"] for s in segments] == [0, 1]
    assert segments[0]["device_count"] == 8 and segments[0]["exit"] == "SIGKILL"
    assert segments[0]["runtime_s"] == 12.5
    assert segments[1]["decision"] == "scaled data 8->4"
    json.dumps(doc)


def test_report_json_requires_run_dir(tmp_path):
    from llm_training_tpu.telemetry.report import render_report_data

    with pytest.raises(FileNotFoundError):
        render_report_data(tmp_path)


# ------------------------------------------------------- flight recorder


def test_watchdog_dump_flushes_flight_recorder(tmp_path, tracer):
    from llm_training_tpu.resilience.watchdog import HangWatchdog

    tracer.instant("train", "train_step", step=41)
    tracer.instant("train", "train_step", step=42)
    watchdog = HangWatchdog(timeout_s=60.0, run_dir=tmp_path)
    watchdog.beat("train_loop", step=42)
    assert watchdog.dump(123.0) is not None
    flights = list(tmp_path.glob("trace-flight-hang-*.jsonl"))
    assert len(flights) == 1
    events = read_trace_events(flights[0])
    assert events[0]["name"] == "clock_anchor"
    assert [e["args"]["step"] for e in events[1:3]] == [41, 42]


def test_anomaly_dump_flushes_flight_recorder(tmp_path, tracer):
    from llm_training_tpu.telemetry.anomaly import dump_anomaly

    tracer.instant("train", "train_step", step=7)
    path = dump_anomaly(tmp_path, 7, "non_finite", {"loss": float("nan")})
    assert path is not None
    flight = tmp_path / "trace-flight-anomaly-7.jsonl"
    assert flight.is_file()
    events = read_trace_events(flight)
    assert events[0]["name"] == "clock_anchor"
    assert events[1]["args"]["step"] == 7


def test_flight_dumps_export_to_chrome(tmp_path, tracer):
    """A flight dump is itself a valid `trace` CLI source — post-mortems
    open straight in Perfetto."""
    tracer.instant("serve", "submit", request_id="r9")
    dump = tracer.flight_dump(tmp_path, "rollback-3")
    assert trace_main(str(dump), out=str(tmp_path / "out.json")) == 0
    doc = json.loads((tmp_path / "out.json").read_text())
    assert any(
        e.get("args", {}).get("request_id") == "r9" for e in doc["traceEvents"]
    )


# ----------------------------------------- cross-replica merge (#fleet)


def _anchor_line(mono_s, wall_s, err_s=0.0, attempt=0, pid=1):
    return {"ts": mono_s, "ph": "i", "cat": "meta", "name": "clock_anchor",
            "args": {"mono_s": mono_s, "wall_s": wall_s, "err_s": err_s,
                     "pid": pid, "attempt": attempt}}


def _serve_span(ts, rid, dur=0.5, name="decode"):
    return {"ts": ts, "dur": dur, "ph": "X", "cat": "serve", "name": name,
            "args": {"request_id": rid}}


def _write_trace(run_dir, lines):
    run_dir.mkdir(parents=True, exist_ok=True)
    path = run_dir / "trace.jsonl"
    path.write_text("".join(json.dumps(line) + "\n" for line in lines))
    return path


def test_clock_anchor_pairs_wall_and_monotonic(monkeypatch):
    anchor = clock_anchor(clock=lambda: 5.0)
    assert anchor["mono_s"] == 5.0 and anchor["err_s"] == 0.0
    assert anchor["pid"] > 0 and anchor["attempt"] == 0
    import time as _time
    live = clock_anchor()
    assert abs(live["wall_s"] - _time.time()) < 5.0
    assert live["err_s"] >= 0.0
    monkeypatch.setenv("LLMT_SUPERVISOR_ATTEMPT", "3")
    assert clock_anchor()["attempt"] == 3
    monkeypatch.setenv("LLMT_SUPERVISOR_ATTEMPT", "banana")
    assert clock_anchor()["attempt"] == 0  # malformed degrades, never raises


def test_attach_sink_leads_with_anchor_and_round_trips(tmp_path, tracer):
    """The satellite round-trip: the anchor the sink writes is the anchor
    wall_align reads back, so |aligned - wall| <= err_s by construction."""
    path = tmp_path / "trace.jsonl"
    assert tracer.attach_sink(path)
    tracer.instant("serve", "submit", write=True, request_id="r0")
    tracer.detach_sink()
    events = read_trace_events(path)
    anchor = events[0]["args"]
    aligned, max_err = wall_align(events)
    assert len(aligned) == 1  # the meta event steers, never renders
    want_wall = events[1]["ts"] + (anchor["wall_s"] - anchor["mono_s"])
    assert aligned[0]["ts"] == pytest.approx(want_wall, abs=1e-9)
    assert max_err == anchor["err_s"] >= 0.0


def test_wall_align_is_segment_wise():
    """A supervised relaunch appends a fresh anchor mid-file: events after
    it must align by the NEW pair, events before it by the old one."""
    events = [
        _anchor_line(10.0, 1000.0, attempt=0),
        _serve_span(11.0, "a"),        # old segment: wall 1001
        _anchor_line(3.0, 2000.0, attempt=1),  # relaunch: clock restarted
        _serve_span(4.0, "b"),         # new segment: wall 2001
    ]
    # the relaunch anchor has the SMALLER mono — nearest-preceding must
    # key on mono order, not file order
    aligned, _ = wall_align(events)
    by_rid = {e["args"]["request_id"]: e["ts"] for e in aligned}
    assert by_rid["a"] == pytest.approx(1001.0)
    assert by_rid["b"] == pytest.approx(2001.0)


def test_wall_align_returns_none_without_anchor():
    assert wall_align([_serve_span(1.0, "a")]) is None


def test_to_chrome_trace_merge_hooks():
    events = [_anchor_line(0.0, 50.0), _serve_span(1.0, "r1")]
    doc = to_chrome_trace(events, pid_base=300, label="replica-3")
    names = [e for e in doc["traceEvents"] if e.get("name") == "process_name"]
    assert all(e["args"]["name"].startswith("replica-3/") for e in names)
    assert all(e["pid"] >= 300 for e in doc["traceEvents"])
    assert not any(e.get("cat") == "meta" for e in doc["traceEvents"])


def test_merge_traces_aligns_and_namespaces(tmp_path):
    """Two replicas with wildly different monotonic bases but overlapping
    wall time merge into one timeline: same-wall-instant events land at
    the same merged ts, each under its own pid namespace and label."""
    a = _write_trace(tmp_path / "replica-0", [
        _anchor_line(100.0, 5000.0, err_s=0.002),
        _serve_span(101.0, "req-0"),   # wall 5001 -> merged t=0
        _serve_span(103.0, "req-1"),   # wall 5003
    ])
    _write_trace(tmp_path / "replica-1", [
        _anchor_line(7.0, 4994.0, err_s=0.003),
        _serve_span(14.0, "req-2"),    # wall 5001 too — same instant
    ])
    document, info = merge_traces(
        [tmp_path / "replica-0", tmp_path / "replica-1"]
    )
    assert info["labels"] == ["replica-0", "replica-1"]
    assert info["events"] == 3 and info["t0_wall_s"] == pytest.approx(5001.0)
    # the skew bound is the SUM of the two worst per-file anchor errors
    assert info["skew_bound_s"] == pytest.approx(0.005)
    spans = {e["args"]["request_id"]: e for e in document["traceEvents"]
             if e.get("ph") == "X"}
    assert spans["req-0"]["ts"] == pytest.approx(0.0)
    assert spans["req-2"]["ts"] == pytest.approx(0.0)       # wall-aligned
    assert spans["req-1"]["ts"] == pytest.approx(2e6)       # +2s in µs
    assert spans["req-0"]["pid"] != spans["req-2"]["pid"]   # pid namespaces
    assert str(a) in info["sources"][0]


def test_merge_traces_dedupes_labels_and_rejects_bad_sources(tmp_path):
    _write_trace(tmp_path / "a" / "run", [
        _anchor_line(0.0, 100.0), _serve_span(1.0, "x")])
    _write_trace(tmp_path / "b" / "run", [
        _anchor_line(0.0, 100.0), _serve_span(1.0, "y")])
    _, info = merge_traces([tmp_path / "a" / "run", tmp_path / "b" / "run"])
    assert info["labels"] == ["run", "run#1"]

    missing = tmp_path / "nope"
    with pytest.raises(ValueError) as excinfo:
        merge_traces([missing])
    # exit-2 contract: the error names EVERY searched path
    assert str(missing) in str(excinfo.value)
    assert str(missing / "trace.jsonl") in str(excinfo.value)

    anchorless = tmp_path / "old"
    _write_trace(anchorless, [_serve_span(1.0, "z")])
    with pytest.raises(ValueError, match="clock_anchor"):
        merge_traces([anchorless])


def test_trace_cli_merge_and_exit_2_paths(tmp_path, capsys):
    _write_trace(tmp_path / "r0", [
        _anchor_line(0.0, 100.0, err_s=0.001), _serve_span(1.0, "req-0")])
    _write_trace(tmp_path / "r1", [
        _anchor_line(50.0, 100.5, err_s=0.001), _serve_span(51.0, "req-1")])
    assert trace_main(merge=[str(tmp_path / "r0"), str(tmp_path / "r1")]) == 0
    out = capsys.readouterr().out
    assert "merged" in out and "|skew| <=" in out
    # default out lands in the FIRST source dir
    merged = json.loads((tmp_path / "r0" / "trace-merged.json").read_text())
    rids = {e.get("args", {}).get("request_id") for e in merged["traceEvents"]}
    assert {"req-0", "req-1"} <= rids

    assert trace_main(merge=[str(tmp_path / "gone")]) == 2
    err = capsys.readouterr().err
    assert str(tmp_path / "gone") in err
    assert str(tmp_path / "gone" / "trace.jsonl") in err

    assert trace_main() == 2  # no source, no --merge
    assert "--merge" in capsys.readouterr().err

    assert trace_main(str(tmp_path / "void")) == 2
    err = capsys.readouterr().err
    assert str(tmp_path / "void") in err
    assert str(tmp_path / "void" / "trace.jsonl") in err
