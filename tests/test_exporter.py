"""Live-telemetry tests: the /metrics//statusz//healthz exporter and the
SLO burn-rate monitor (docs/observability.md#live-telemetry, #slo).

Everything here is jax-free host code (the exporter and the SLO monitor
carry graftlint jax-free contracts), so these tests cost milliseconds. HTTP
tests bind ephemeral ports on localhost; clock-driven tests inject fake
clocks — no sleeps.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import pytest

from llm_training_tpu.resilience.watchdog import HangWatchdog
from llm_training_tpu.telemetry.exporter import (
    MetricsExporter,
    parse_prometheus_text,
    prometheus_name,
    render_prometheus,
    resolve_metrics_port,
    start_exporter,
    watch_main,
)
from llm_training_tpu.telemetry.goodput import GoodputLedger
from llm_training_tpu.telemetry.registry import TelemetryRegistry
from llm_training_tpu.telemetry.slo import (
    SLOMonitor,
    build_slo_monitor,
    slo_config_from_env,
    specs_from_config,
)
from llm_training_tpu.telemetry.trace import TraceRecorder, set_tracer

# the shared strict parser IS the validator under test: render->parse must
# round-trip, and every malformed shape must raise ValueError (the loadgen
# cross-check and the precommit exporter smoke rely on exactly that)
parse_prometheus = parse_prometheus_text


def _get(port: int, path: str):
    return urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5.0)


@pytest.fixture
def exporter_factory():
    started = []

    def make(**kwargs) -> MetricsExporter:
        exporter = MetricsExporter(0, **kwargs)
        # bind an OS-assigned ephemeral port directly (requested_port 0)
        assert exporter.start()
        started.append(exporter)
        return exporter

    yield make
    for exporter in started:
        exporter.stop()


# ------------------------------------------------------------ /metrics


def test_metrics_endpoint_is_parse_valid_prometheus(exporter_factory):
    registry = TelemetryRegistry()
    registry.counter("serve/requests_completed").inc(5)
    registry.gauge("hbm/peak_bytes_in_use").set(1.5e9)
    with registry.timer("data/produce").time():
        pass
    ledger = GoodputLedger()
    ledger.start()
    exporter = exporter_factory(registry=registry, ledger=ledger)
    with _get(exporter.port, "/metrics") as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        metrics = parse_prometheus(resp.read().decode())
    assert metrics["llmt_serve_requests_completed"] == 5.0
    assert metrics["llmt_hbm_peak_bytes_in_use"] == 1.5e9
    # timers flatten to the _s/_n accumulator pair
    assert "llmt_data_produce_s" in metrics and metrics["llmt_data_produce_n"] == 1.0
    # the ledger summary rides along
    assert "llmt_goodput_total_s" in metrics
    # the exporter's own counters count THIS scrape
    assert metrics["llmt_exporter_scrapes"] == 1.0
    # and land in the registry so telemetry.jsonl shows whether anyone
    # scraped the run
    assert registry.snapshot()["exporter/scrapes"] == 1.0


def test_metrics_includes_live_extras_and_survives_extra_fn_crash(exporter_factory):
    calls = {"n": 0}

    def extra():
        calls["n"] += 1
        if calls["n"] == 1:
            return {"serve/queue_depth": 3.0}
        raise RuntimeError("live gauge bug")

    exporter = exporter_factory(registry=TelemetryRegistry(), extra_fn=extra)
    with _get(exporter.port, "/metrics") as resp:
        assert parse_prometheus(resp.read().decode())["llmt_serve_queue_depth"] == 3.0
    # a crashing extra_fn costs its gauges, never the scrape
    with _get(exporter.port, "/metrics") as resp:
        metrics = parse_prometheus(resp.read().decode())
    assert "llmt_serve_queue_depth" not in metrics
    assert metrics["llmt_exporter_scrapes"] == 2.0


def test_parse_prometheus_text_rejects_malformed_lines():
    """The strict parser must raise on every drift shape — including the
    3-token sample line (a trailing timestamp) that float()/unpack paths
    can miss."""
    good = render_prometheus({"a/b": 1.0})
    assert parse_prometheus_text(good)["llmt_a_b"] == 1.0
    for bad in (
        "llmt_x 1.0 1699999999\n",     # trailing timestamp (3 tokens)
        "llmt_x\n",                     # no value
        "llmt_x junk\n",                # non-float value
        "9bad_name 1.0\n",              # illegal name
        "# COMMENT not a type line\n llmt_x 1.0\n",  # bad comment
        "",                             # no samples at all
    ):
        with pytest.raises(ValueError):
            parse_prometheus_text(bad)


def test_parse_prometheus_text_labeled_series_are_opt_in():
    """The fleet federation surface re-renders per-replica series with a
    `{replica=...,role=...}` label block: `labels=True` accepts exactly
    that strict shape (keyed by the FULL labeled name); the default
    parser keeps rejecting, so child-exporter scrapes stay label-free."""
    body = (
        '# TYPE llmt_serve_queue_depth gauge\n'
        'llmt_serve_queue_depth{replica="serve-0-42",role="serve"} 3.0\n'
        'llmt_fleet_replicas 1.0\n'
    )
    parsed = parse_prometheus_text(body, labels=True)
    assert parsed[
        'llmt_serve_queue_depth{replica="serve-0-42",role="serve"}'
    ] == 3.0
    assert parsed["llmt_fleet_replicas"] == 1.0
    with pytest.raises(ValueError):
        parse_prometheus_text(body)  # labels stay opt-in
    for bad in (
        'llmt_x{replica=serve-0-42} 1.0\n',      # unquoted value
        'llmt_x{replica="a" role="b"} 1.0\n',    # missing comma
        'llmt_x{replica="a",} 1.0\n',            # trailing comma
        'llmt_x{} 1.0\n',                        # empty block
        'llmt_x{replica="a"\n',                  # unterminated, no value
    ):
        with pytest.raises(ValueError):
            parse_prometheus_text(bad, labels=True)


def test_parse_prometheus_kinds():
    registry = TelemetryRegistry()
    registry.counter("exporter/scrapes").inc()
    registry.gauge("serve/queue_depth").set(1.0)
    from llm_training_tpu.telemetry.exporter import parse_prometheus_kinds

    snapshot, kinds = registry.snapshot_with_kinds()
    body = render_prometheus(snapshot, kinds=kinds)
    parsed_kinds = parse_prometheus_kinds(body)
    assert parsed_kinds["llmt_exporter_scrapes"] == "counter"
    assert parsed_kinds["llmt_serve_queue_depth"] == "gauge"
    # same strictness posture as the sample parser: drift raises
    for bad in ("# TYPE llmt_x histogram\n", "# TYPE too many words here\n"):
        with pytest.raises(ValueError):
            parse_prometheus_kinds(bad)
    assert parse_prometheus_kinds("llmt_x 1.0\n") == {}  # no TYPE lines: fine


def test_render_prometheus_handles_non_finite_and_junk():
    text = render_prometheus(
        {"a/nan": float("nan"), "a/inf": float("inf"), "a/ok": 1.0,
         "a/junk": "not-a-number"},
    )
    assert "llmt_a_nan NaN" in text
    assert "llmt_a_inf +Inf" in text
    assert "llmt_a_ok 1.0" in text
    assert "junk" not in text  # skipped, not crashed


def test_prometheus_name_sanitization():
    assert prometheus_name("goodput/total_s") == "llmt_goodput_total_s"
    assert prometheus_name("slo/serve/ttft_p99_ms/target") == (
        "llmt_slo_serve_ttft_p99_ms_target"
    )


# ----------------------------------------------------------- /healthz


def test_healthz_turns_red_on_stale_heartbeat(exporter_factory):
    t = [0.0]
    watchdog = HangWatchdog(timeout_s=10.0, clock=lambda: t[0])
    watchdog.beat()  # fresh beat at t=0 (never start()ed — no poll thread)
    exporter = exporter_factory(
        registry=TelemetryRegistry(), watchdog=watchdog,
    )
    assert exporter.stale_after_s == 5.0  # half the watchdog window
    with _get(exporter.port, "/healthz") as resp:
        assert resp.status == 200
        assert json.loads(resp.read())["status"] == "ok"
    # wedge: the beat goes stale past timeout/2 but BEFORE the watchdog's
    # own 10s abort — the probe must already be red
    t[0] = 6.0
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(exporter.port, "/healthz")
    assert err.value.code == 503
    detail = json.loads(err.value.read())
    assert detail["status"] == "unhealthy"
    assert "heartbeat" in detail["reason"]
    # progress re-arms the probe
    t[0] = 7.0
    watchdog.beat()
    with _get(exporter.port, "/healthz") as resp:
        assert resp.status == 200


def test_healthz_without_watchdog_is_alive_probe_only(exporter_factory):
    exporter = exporter_factory(registry=TelemetryRegistry())
    with _get(exporter.port, "/healthz") as resp:
        assert resp.status == 200
        assert json.loads(resp.read())["watchdog"] == "none"


def test_healthz_names_the_open_goodput_phase(exporter_factory):
    ledger = GoodputLedger()
    ledger.start()
    exporter = exporter_factory(ledger=ledger)
    with ledger.measure("checkpoint_save"):
        with _get(exporter.port, "/healthz") as resp:
            assert json.loads(resp.read())["phase"] == "checkpoint_save"


# ----------------------------------------------------------- /statusz


def test_statusz_renders_status_fn_and_slo_alert(exporter_factory):
    registry = TelemetryRegistry()
    specs = specs_from_config({"serve": {"ttft_p99_ms": 10.0}})
    t = [0.0]
    monitor = SLOMonitor(
        specs, registry=registry, clock=lambda: t[0],
        fast_window_s=10, slow_window_s=60, fast_burn=2, slow_burn=2,
        min_events=2, cooldown_s=100,
    )
    exporter = exporter_factory(
        registry=registry, slo=monitor,
        status_fn=lambda: {"step": 7, "segment": 1},
    )
    body = _get(exporter.port, "/statusz").read().decode()
    assert "step: 7" in body and "segment: 1" in body
    assert "slo: no breaches" in body
    for _ in range(4):
        t[0] += 1.0
        monitor.observe_request(ttft_ms=100.0)
    body = _get(exporter.port, "/statusz").read().decode()
    assert "last alert: serve/ttft_p99_ms" in body


def test_unknown_path_404s(exporter_factory):
    exporter = exporter_factory()
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(exporter.port, "/nope")
    assert err.value.code == 404


# ----------------------------------------------------- lifecycle / env


def test_port_zero_disables(monkeypatch):
    monkeypatch.delenv("LLMT_METRICS_PORT", raising=False)
    assert resolve_metrics_port() == 0
    assert start_exporter() is None
    monkeypatch.setenv("LLMT_METRICS_PORT", "0")
    assert start_exporter() is None
    monkeypatch.setenv("LLMT_METRICS_PORT", "junk")
    assert resolve_metrics_port() == 0  # warned, not crashed


def test_port_collision_degrades_to_warning(exporter_factory, caplog):
    import logging

    first = exporter_factory(registry=TelemetryRegistry())
    second = MetricsExporter(first.port, registry=TelemetryRegistry())
    with caplog.at_level(logging.WARNING):
        assert second.start() is False
    assert any("cannot bind port" in r.message for r in caplog.records)
    assert start_exporter(port=first.port) is None
    # the first exporter keeps serving
    with _get(first.port, "/metrics") as resp:
        assert resp.status == 200


def test_watch_once_roundtrip_and_unreachable(exporter_factory, capsys):
    exporter = exporter_factory(registry=TelemetryRegistry())
    assert watch_main(port=exporter.port, once=True) == 0
    assert "statusz" in capsys.readouterr().out
    exporter.stop()
    assert watch_main(port=exporter.port, once=True) == 2


# ------------------------------------------------------------------ SLO


@pytest.fixture
def tracer(tmp_path):
    recorder = TraceRecorder(capacity=64, enabled=True)
    previous = set_tracer(recorder)
    yield recorder
    set_tracer(previous)


def _monitor(registry, tmp_path=None, clock=None, **kwargs):
    specs = specs_from_config({
        "serve": {"ttft_p99_ms": 50.0, "error_rate": 0.1},
        "train": {"step_time_p99_s": 1.0, "goodput_pct_min": 40.0},
    })
    defaults = dict(
        fast_window_s=10.0, slow_window_s=60.0, fast_burn=5.0, slow_burn=3.0,
        min_events=4, cooldown_s=30.0,
    )
    defaults.update(kwargs)
    return SLOMonitor(
        specs, registry=registry, run_dir=tmp_path, clock=clock, **defaults
    )


def test_slo_no_breach_on_healthy_traffic(tracer, tmp_path):
    registry = TelemetryRegistry()
    t = [0.0]
    monitor = _monitor(registry, tmp_path, clock=lambda: t[0])
    for _ in range(50):
        t[0] += 0.1
        monitor.observe_request(ttft_ms=10.0, tpot_ms=None, ok=True)
        monitor.observe_step(0.1)
        monitor.observe_goodput(80.0)
    assert monitor.breach_count() == 0
    snap = registry.snapshot()
    assert snap["slo/serve/ttft_p99_ms/target"] == 50.0
    assert snap["slo/serve/ttft_p99_ms/burn_fast"] == 0.0
    assert not list(tmp_path.glob("trace-flight-slo-*.jsonl"))


def test_slo_breach_emits_counter_instant_and_flight_dump(tracer, tmp_path):
    registry = TelemetryRegistry()
    t = [0.0]
    monitor = _monitor(registry, tmp_path, clock=lambda: t[0])
    for _ in range(6):
        t[0] += 0.5
        monitor.observe_request(ttft_ms=500.0, ok=True)
    assert monitor.breach_count() == 1  # cooldown holds repeats
    snap = registry.snapshot()
    assert snap["slo/breaches_total"] == 1.0
    assert snap["slo/serve/ttft_p99_ms/breaches"] == 1.0
    assert snap["slo/serve/ttft_p99_ms/worst"] == 500.0
    assert snap["slo/last_breach_request_n"] >= 4.0
    # trace instant in the ring
    breach_events = [
        e for e in tracer.snapshot() if e.get("name") == "breach"
    ]
    assert breach_events and breach_events[0]["cat"] == "slo"
    assert breach_events[0]["args"]["target"] == "serve/ttft_p99_ms"
    # and the ring flight-dumped next to the run artifacts
    dumps = list(tmp_path.glob("trace-flight-slo-serve-ttft_p99_ms-*.jsonl"))
    assert len(dumps) == 1
    dumped = [json.loads(line) for line in dumps[0].read_text().splitlines()]
    assert any(e.get("name") == "breach" for e in dumped)


def test_slo_multiwindow_gate_needs_both_windows(tracer, tmp_path):
    """A burst that burns the fast window but not the slow one must NOT
    page — the slow window is the straggler guard."""
    registry = TelemetryRegistry()
    t = [0.0]
    monitor = _monitor(
        registry, tmp_path, clock=lambda: t[0],
        fast_window_s=2.0, slow_window_s=60.0, fast_burn=5.0, slow_burn=8.0,
        min_events=4,
    )
    # 40 healthy observations spread over the slow window...
    for _ in range(40):
        t[0] += 1.0
        monitor.observe_request(ttft_ms=1.0, ok=True)
    # ...then a short violation burst: fast-window burn is 100x, but the
    # slow window still holds ~40 good events -> slow burn < 8x
    for _ in range(3):
        t[0] += 0.4
        monitor.observe_request(ttft_ms=500.0, ok=True)
    assert monitor.breach_count() == 0


def test_slo_step_and_goodput_breaches_record_step(tracer, tmp_path):
    registry = TelemetryRegistry()
    t = [0.0]
    monitor = _monitor(registry, tmp_path, clock=lambda: t[0])
    for step in range(1, 6):
        t[0] += 2.0
        monitor.observe_step(3.0, step=step)
    assert monitor.breach_count() == 1
    assert registry.snapshot()["slo/last_breach_step"] == 4.0
    for step in range(6, 12):
        t[0] += 2.0
        monitor.observe_goodput(5.0, step=step)
    assert monitor.breach_count() == 2
    assert registry.snapshot()["slo/train/goodput_pct_min/worst"] == 5.0


def test_slo_error_rate_budget_is_the_target(tracer, tmp_path):
    registry = TelemetryRegistry()
    t = [0.0]
    monitor = _monitor(registry, tmp_path, clock=lambda: t[0])
    # 10% failures == the budget exactly -> burn 1x, no breach
    for i in range(40):
        t[0] += 0.2
        monitor.observe_request(ttft_ms=1.0, ok=i % 10 != 0)
    assert monitor.breach_count() == 0
    # sustained 100% failures: the fast window fills with failures and the
    # slow window's fraction climbs past 3x the 10% budget -> breach
    for _ in range(40):
        t[0] += 0.2
        monitor.observe_request(ttft_ms=None, ok=False)
    assert monitor.breach_count() >= 1
    assert registry.snapshot()["slo/serve/error_rate/breaches"] >= 1.0


def test_slo_specs_are_domain_scoped(tracer, tmp_path):
    """A serve spec must never eat train observations (and vice versa):
    an error-rate SLO armed fleet-wide while a FIT runs would otherwise
    count every healthy step as a healthy request, diluting the real
    request-error fraction and masking a breach."""
    registry = TelemetryRegistry()
    t = [0.0]
    monitor = _monitor(registry, tmp_path, clock=lambda: t[0])
    # a training fit's observations only...
    for step in range(30):
        t[0] += 0.2
        monitor.observe_step(0.01, step=step)
        monitor.observe_goodput(90.0, step=step)
    # ...leave the serve windows EMPTY (no burn gauges published at all)
    snap = registry.snapshot()
    assert "slo/serve/error_rate/burn_fast" not in snap
    assert "slo/serve/ttft_p99_ms/burn_fast" not in snap
    # now 100% request failures breach immediately — undiluted by the 60
    # healthy train events that preceded them
    for _ in range(8):
        t[0] += 0.2
        monitor.observe_request(ttft_ms=None, ok=False)
    assert registry.snapshot()["slo/serve/error_rate/breaches"] >= 1.0


def test_slo_env_knobs_honor_explicit_zero(monkeypatch, tracer, tmp_path):
    """`LLMT_SLO_COOLDOWN_S=0` means count EVERY breach — a falsy-`or`
    fallback would silently revert it to the 30s default."""
    monkeypatch.setenv("LLMT_SLO_COOLDOWN_S", "0")
    monitor = SLOMonitor(
        specs_from_config({"serve": {"ttft_p99_ms": 10.0}}),
        registry=TelemetryRegistry(), clock=lambda: 0.0,
    )
    assert monitor.cooldown_s == 0.0
    monkeypatch.setenv("LLMT_SLO_BURN_FAST", "0")
    monitor = SLOMonitor(
        specs_from_config({"serve": {"ttft_p99_ms": 10.0}}),
        registry=TelemetryRegistry(), clock=lambda: 0.0,
    )
    assert monitor.fast_burn == 0.0


def test_slo_config_from_env(monkeypatch):
    for name in (
        "LLMT_SLO_TTFT_P99_MS", "LLMT_SLO_TPOT_P99_MS", "LLMT_SLO_ERROR_RATE",
        "LLMT_SLO_STEP_TIME_P99_S", "LLMT_SLO_GOODPUT_PCT_MIN",
    ):
        monkeypatch.delenv(name, raising=False)
    assert slo_config_from_env() == {}
    assert build_slo_monitor() is None  # no config -> zero cost
    monkeypatch.setenv("LLMT_SLO_TTFT_P99_MS", "75.5")
    monkeypatch.setenv("LLMT_SLO_GOODPUT_PCT_MIN", "junk")  # warn + ignore
    config = slo_config_from_env({"train": {"step_time_p99_s": 2.0}})
    assert config == {
        "serve": {"ttft_p99_ms": 75.5}, "train": {"step_time_p99_s": 2.0}
    }
    specs = specs_from_config(config)
    assert {s.key for s in specs} == {"serve/ttft_p99_ms", "train/step_time_p99_s"}
    monitor = build_slo_monitor()
    assert monitor is not None and len(monitor.specs) == 1


# -------------------------------------------------------- report == SLO ==


def _slo_run_dir(tmp_path, with_slo=True):
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    (run_dir / "metrics.jsonl").write_text(
        json.dumps({"step": 1, "loss": 2.0, "steps_per_sec": 1.0}) + "\n"
    )
    record = {"step": 1, "goodput/total_s": 10.0, "goodput/goodput_pct": 50.0}
    if with_slo:
        record.update({
            "slo/serve/ttft_p99_ms/target": 50.0,
            "slo/serve/ttft_p99_ms/worst": 312.5,
            "slo/serve/ttft_p99_ms/breaches": 3.0,
            "slo/serve/ttft_p99_ms/burn_fast": 16.2,
            "slo/serve/ttft_p99_ms/burn_slow": 7.1,
            "slo/train/step_time_p99_s/target": 1.0,
            "slo/breaches_total": 3.0,
            "slo/last_breach_step": 7.0,
            "slo/last_breach_request_n": 12.0,
        })
    (run_dir / "telemetry.jsonl").write_text(json.dumps(record) + "\n")
    return run_dir


def test_report_slo_section_renders(tmp_path):
    from llm_training_tpu.telemetry.report import render_report, render_report_data

    run_dir = _slo_run_dir(tmp_path)
    text = render_report(run_dir)
    assert "== SLO ==" in text
    assert "serve/ttft_p99_ms: target 50  worst 312.5  breaches 3" in text
    # a target armed but never violated renders with zero breaches
    assert "train/step_time_p99_s: target 1  breaches 0" in text
    assert "breaches: 3 total  last at step 7  last at request #12" in text
    doc = render_report_data(run_dir)
    assert doc["slo"]["slo/breaches_total"] == 3.0
    assert doc["slo"]["slo/serve/ttft_p99_ms/worst"] == 312.5


def test_report_slo_section_omitted_without_config(tmp_path):
    from llm_training_tpu.telemetry.report import render_report, render_report_data

    run_dir = _slo_run_dir(tmp_path, with_slo=False)
    assert "== SLO ==" not in render_report(run_dir)
    assert render_report_data(run_dir)["slo"] is None


# ------------------------------------------- supervisor port passthrough


def test_supervisor_env_carries_metrics_port(monkeypatch):
    """`supervise` relaunches inherit LLMT_METRICS_PORT (plain env
    passthrough), so a scrape target survives drain/replay and elastic
    resume boundaries — the dead child released the port, the relaunch
    re-binds it."""
    from llm_training_tpu.resilience.supervisor import Supervisor, SupervisorConfig

    monkeypatch.setenv("LLMT_METRICS_PORT", "9109")
    supervisor = Supervisor(
        ["true"], SupervisorConfig(log_path=None), run_child=lambda argv: 0
    )
    assert supervisor.env["LLMT_METRICS_PORT"] == "9109"
