"""Fleet observability plane (docs/observability.md#fleet): replica
discovery cards + their arm/stop/SIGKILL lifecycle, the multi-target
aggregator (rollups, verdict, stale-card handling, SLO feed), the
federation/`/fleetz` surfaces, the `fleet` CLI exit-2 contracts, and
`report`'s `fleet` block.

Everything here is jax-free host code (fleet.py carries a graftlint
jax-free contract — the aggregator is a scrape *parent* like the
loadgen), so these tests cost milliseconds. Real-replica scrapes run
against in-process `MetricsExporter`s on ephemeral localhost ports.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import pytest

from llm_training_tpu.telemetry.exporter import (
    MetricsExporter,
    parse_prometheus_text,
)
from llm_training_tpu.telemetry.fleet import (
    FleetAggregator,
    discover_replicas,
    fleet_main,
    parse_targets,
    remove_replica_card,
    resolve_fleet_dir,
    resolve_scrape_interval,
    write_replica_card,
)
from llm_training_tpu.telemetry.registry import TelemetryRegistry


def _dead_pid() -> int:
    """A pid that WAS a real process and is now gone — the SIGKILL/OOM
    card signature (`os.kill(pid, 0)` raises ProcessLookupError)."""
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    return proc.pid


@pytest.fixture
def serve_exporter():
    """An armed serve-shaped exporter on an ephemeral port, stopped after."""
    registry = TelemetryRegistry()
    registry.counter("exporter/scrapes")  # a counter for sum rollups
    registry.gauge("serve/queue_depth").set(3.0)
    registry.gauge("serve/running").set(2.0)
    registry.gauge("serve/requests_completed").set(5.0)
    registry.gauge("serve/ttft_p99_ms").set(40.0)
    exporter = MetricsExporter(0, registry=registry, role="serve")
    assert exporter.start()
    try:
        yield exporter
    finally:
        exporter.stop()


# ------------------------------------------------------- discovery cards


def test_card_lifecycle_arm_and_clean_stop(tmp_path):
    card = write_replica_card(tmp_path / "fleet", 9100, role="serve")
    assert card is not None and card.name == f"replica-{os.getpid()}.json"
    loaded = json.loads(card.read_text())
    assert loaded["schema"] == 1
    assert loaded["replica_id"] == f"serve-0-{os.getpid()}"
    assert loaded["pid"] == os.getpid() and loaded["port"] == 9100
    # the wall+mono anchor pair rides the card like the trace anchor
    assert loaded["start_wall_s"] > 0 and loaded["start_mono_s"] >= 0
    replicas = discover_replicas(tmp_path / "fleet")
    assert len(replicas) == 1 and replicas[0]["stale"] is False
    remove_replica_card(card)  # clean stop
    assert not card.exists()
    assert discover_replicas(tmp_path / "fleet") == []
    remove_replica_card(card)  # idempotent
    remove_replica_card(None)  # never armed


def test_card_tags_supervisor_attempt(tmp_path, monkeypatch):
    """A supervised relaunch re-registers under a fresh attempt-tagged id
    (the dead predecessor's id must not be reused)."""
    monkeypatch.setenv("LLMT_SUPERVISOR_ATTEMPT", "2")
    card = write_replica_card(tmp_path, 9100, role="train")
    loaded = json.loads(card.read_text())
    assert loaded["replica_id"] == f"train-2-{os.getpid()}"
    assert loaded["attempt"] == 2
    monkeypatch.setenv("LLMT_SUPERVISOR_ATTEMPT", "banana")
    assert json.loads(write_replica_card(tmp_path, 9100).read_text())[
        "attempt"
    ] == 0  # malformed degrades, never raises


def test_card_write_failure_degrades(tmp_path):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("")
    assert write_replica_card(blocker / "fleet", 9100) is None


def test_discover_flags_dead_pid_stale(tmp_path):
    """A SIGKILLed replica never removed its card: flagged stale."""
    card = write_replica_card(tmp_path, 9100, role="serve")
    doctored = json.loads(card.read_text())
    doctored["pid"] = _dead_pid()
    card.write_text(json.dumps(doctored))
    replicas = discover_replicas(tmp_path)
    assert len(replicas) == 1 and replicas[0]["stale"] is True


def test_discover_tolerates_torn_and_junk_cards(tmp_path):
    (tmp_path / "replica-1.json").write_text("{torn mid-wri")
    (tmp_path / "replica-2.json").write_text(json.dumps({"no": "port"}))
    (tmp_path / "replica-3.json").write_text(json.dumps([1, 2]))
    assert discover_replicas(tmp_path) == []
    assert discover_replicas(tmp_path / "absent") == []


def test_exporter_start_stop_drops_and_removes_card(tmp_path, monkeypatch):
    """The integration the whole plane hangs on: arming ANY exporter with
    LLMT_FLEET_DIR set registers the replica; a clean stop deregisters."""
    fleet_dir = tmp_path / "fleet"
    monkeypatch.setenv("LLMT_FLEET_DIR", str(fleet_dir))
    assert resolve_fleet_dir() == fleet_dir
    exporter = MetricsExporter(0, registry=TelemetryRegistry(), role="serve")
    assert exporter.start()
    try:
        replicas = discover_replicas(fleet_dir)
        assert len(replicas) == 1
        assert replicas[0]["port"] == exporter.port
        assert replicas[0]["role"] == "serve"
    finally:
        exporter.stop()
    assert discover_replicas(fleet_dir) == []
    monkeypatch.delenv("LLMT_FLEET_DIR")
    assert resolve_fleet_dir() is None


def test_parse_targets():
    targets = parse_targets("127.0.0.1:9100, :9101,junk,host:nan,")
    assert [(t["host"], t["port"]) for t in targets] == [
        ("127.0.0.1", 9100), ("127.0.0.1", 9101),
    ]
    assert targets[0]["replica_id"] == "target-127.0.0.1:9100"
    assert all(t["static"] and not t["stale"] for t in targets)
    assert parse_targets("") == []


def test_resolve_scrape_interval(monkeypatch):
    assert resolve_scrape_interval() == 2.0
    monkeypatch.setenv("LLMT_FLEET_SCRAPE_S", "0.5")
    assert resolve_scrape_interval() == 0.5
    monkeypatch.setenv("LLMT_FLEET_SCRAPE_S", "banana")
    assert resolve_scrape_interval() == 2.0
    monkeypatch.setenv("LLMT_FLEET_SCRAPE_S", "-1")
    assert resolve_scrape_interval() == 2.0


# ------------------------------------------------------------ aggregator


def test_sweep_green_fleet_and_rollups(serve_exporter, tmp_path, monkeypatch):
    monkeypatch.setenv("LLMT_FLEET_DIR", str(tmp_path))
    card = write_replica_card(tmp_path, serve_exporter.port, role="serve")
    try:
        aggregator = FleetAggregator(fleet_dir=tmp_path)
        snapshot = aggregator.sweep()
        assert snapshot["verdict"] == "green"
        (rid, entry), = snapshot["replicas"].items()
        assert entry["healthy"] and entry["error"] is None
        assert entry["metrics"]["llmt_serve_queue_depth"] == 3.0
        rollup = snapshot["rollup"]
        # serve load gauges sum unsuffixed; every gauge spreads min/mean/max
        assert rollup["llmt_fleet_serve_queue_depth"] == 3.0
        assert rollup["llmt_fleet_serve_queue_depth_max"] == 3.0
        assert rollup["llmt_fleet_replicas"] == 1.0
        assert rollup["llmt_fleet_replicas_healthy"] == 1.0
        assert rollup["llmt_fleet_stale_cards"] == 0.0
        healthy, _ = aggregator.health()
        assert healthy
    finally:
        remove_replica_card(card)


def test_sweep_two_replicas_sums_counters_spreads_gauges(tmp_path):
    """Two serve replicas via static targets (two exporters in ONE process
    share a card path, so the 2-replica discovery leg lives in the fleet
    smoke): counters sum, gauges min/mean/max, serve load keys ALSO sum."""
    exporters = []
    try:
        for completed in (5.0, 7.0):
            registry = TelemetryRegistry()
            registry.gauge("serve/queue_depth").set(completed - 4.0)
            registry.gauge("serve/requests_completed").set(completed)
            exporter = MetricsExporter(0, registry=registry, role="serve")
            assert exporter.start()
            exporters.append(exporter)
        targets = ",".join(f"127.0.0.1:{e.port}" for e in exporters)
        aggregator = FleetAggregator(targets=targets)
        # prime each exporter's scrape counter, then sweep again so the
        # counter-sum rollup sees nonzero values
        snapshot = aggregator.sweep()
        assert snapshot["verdict"] == "green"
        snapshot = aggregator.sweep()
        rollup = snapshot["rollup"]
        assert rollup["llmt_fleet_replicas"] == 2.0
        assert rollup["llmt_fleet_serve_requests_completed"] == 12.0
        assert rollup["llmt_fleet_serve_queue_depth"] == 4.0  # 1 + 3
        assert rollup["llmt_fleet_serve_queue_depth_min"] == 1.0
        assert rollup["llmt_fleet_serve_queue_depth_max"] == 3.0
        assert rollup["llmt_fleet_serve_queue_depth_mean"] == 2.0
        # exporter/scrapes is a `# TYPE ... counter`: sums, no spread
        assert rollup["llmt_fleet_exporter_scrapes"] >= 2.0
        assert "llmt_fleet_exporter_scrapes_mean" not in rollup

        # federation render round-trips the shared strict parser
        body = aggregator.render_metrics()
        federated = parse_prometheus_text(body, labels=True)
        labeled = {k for k in federated if "{replica=" in k}
        assert len(labeled) >= 4  # both replicas' series, labeled
        assert federated["llmt_fleet_serve_requests_completed"] == 12.0
        assert federated["llmt_fleet_sweeps"] == 2.0
        with pytest.raises(ValueError):
            parse_prometheus_text(body)  # labels are opt-in, still strict
    finally:
        for exporter in exporters:
            exporter.stop()


def test_sweep_red_on_unscrapeable_and_unhealthy(serve_exporter):
    dead_port = serve_exporter.port  # live now; dead after stop below
    aggregator = FleetAggregator(
        targets=f"127.0.0.1:{dead_port}", timeout_s=0.5
    )
    assert aggregator.sweep()["verdict"] == "green"
    serve_exporter.stop()
    snapshot = aggregator.sweep()
    assert snapshot["verdict"] == "red"
    assert snapshot["red"] == [f"target-127.0.0.1:{dead_port}"]
    entry = snapshot["replicas"][f"target-127.0.0.1:{dead_port}"]
    assert entry["error"] and not entry["healthy"]
    healthy, _ = aggregator.health()
    assert not healthy
    assert "RED" in aggregator.render_fleetz()


def test_sweep_flags_stale_card_and_never_scrapes_it(tmp_path):
    """The SIGKILL signature: dead pid's card -> red verdict naming the
    stale replica, no scrape attempted (the port may be anyone's now)."""
    card = write_replica_card(tmp_path, 1, role="serve")  # port 1: nobody's
    doctored = json.loads(card.read_text())
    doctored["pid"] = _dead_pid()
    card.write_text(json.dumps(doctored))
    aggregator = FleetAggregator(fleet_dir=tmp_path, timeout_s=0.5)
    snapshot = aggregator.sweep()
    assert snapshot["verdict"] == "red"
    (rid,) = snapshot["stale_cards"]
    assert rid == doctored["replica_id"]
    entry = snapshot["replicas"][rid]
    assert "stale card" in entry["error"]
    assert entry["metrics"] == {}  # never scraped
    assert snapshot["rollup"]["llmt_fleet_stale_cards"] == 1.0
    fleetz = aggregator.render_fleetz()
    assert "STALE CARD" in fleetz and rid in fleetz


def test_sweep_empty_fleet(tmp_path):
    snapshot = FleetAggregator(fleet_dir=tmp_path / "nobody").sweep()
    assert snapshot["verdict"] == "empty" and snapshot["replicas"] == {}
    healthy, _ = FleetAggregator(fleet_dir=tmp_path / "nobody").health()
    assert not healthy  # an empty fleet is not a healthy fleet


def test_sweep_feeds_fleet_slo(serve_exporter):
    class _SpySLO:
        observed = []

        def observe_request(self, ttft_ms=None, tpot_ms=None, ok=True):
            self.observed.append((ttft_ms, tpot_ms, ok))

        def breach_count(self):
            return 0

    slo = _SpySLO()
    aggregator = FleetAggregator(
        targets=f"127.0.0.1:{serve_exporter.port}", slo=slo
    )
    snapshot = aggregator.sweep()
    # one observation per serve replica per sweep: the rolling p99 as the
    # latency sample, the health verdict as ok
    assert slo.observed == [(40.0, None, True)]
    assert snapshot["slo_breaches"] == 0


def test_aggregator_serves_federation_endpoints(serve_exporter, tmp_path):
    aggregator = FleetAggregator(
        targets=f"127.0.0.1:{serve_exporter.port}", interval_s=0.05
    )
    assert aggregator.start(port=0)
    try:
        deadline_sweeps = 50
        while aggregator.sweep_count() < 2 and deadline_sweeps:
            deadline_sweeps -= 1
            time.sleep(0.05)
        base = f"http://127.0.0.1:{aggregator.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5.0).read()
        federated = parse_prometheus_text(body.decode(), labels=True)
        assert federated["llmt_fleet_replicas"] == 1.0
        fleetz = urllib.request.urlopen(f"{base}/fleetz", timeout=5.0).read()
        assert b"GREEN" in fleetz or b"green" in fleetz
        health = urllib.request.urlopen(f"{base}/healthz", timeout=5.0)
        assert health.status == 200
        with pytest.raises(urllib.error.HTTPError) as excinfo:
            urllib.request.urlopen(f"{base}/nope", timeout=5.0)
        assert excinfo.value.code == 404
    finally:
        aggregator.stop()


# --------------------------------------------------------------- fleet CLI


def test_fleet_main_once_json_and_out(serve_exporter, tmp_path, capsys):
    card = write_replica_card(tmp_path, serve_exporter.port, role="serve")
    out = tmp_path / "fleet.json"
    try:
        rc = fleet_main(
            fleet_dir=str(tmp_path), once=True, as_json=True, out=str(out)
        )
    finally:
        remove_replica_card(card)
    assert rc == 0
    printed = json.loads(capsys.readouterr().out)
    assert printed["verdict"] == "green"
    assert json.loads(out.read_text()) == printed


def test_fleet_main_once_exit_2_names_searched_paths(tmp_path, capsys):
    empty = tmp_path / "empty"
    empty.mkdir()
    assert fleet_main(fleet_dir=str(empty), once=True) == 2
    err = capsys.readouterr().err
    assert f"{empty}/replica-*.json" in err

    absent = tmp_path / "absent"
    assert fleet_main(fleet_dir=str(absent), once=True) == 2
    err = capsys.readouterr().err
    assert f"{absent}/replica-*.json" in err and "(dir absent)" in err


def test_fleet_main_nowhere_to_look_exit_2(monkeypatch, capsys):
    monkeypatch.delenv("LLMT_FLEET_DIR", raising=False)
    assert fleet_main() == 2
    assert "LLMT_FLEET_DIR" in capsys.readouterr().err


# ---------------------------------------------------- report fleet block


def test_report_fleet_block_and_section(tmp_path):
    """`fleet --out <run_dir>/fleet.json` surfaces in report; the shape
    CI reads (tests/test_trace.py pins the null-when-absent case)."""
    from llm_training_tpu.telemetry.report import (
        REPORT_SCHEMA_VERSION,
        render_report,
        render_report_data,
    )

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    (run_dir / "metrics.jsonl").write_text(
        json.dumps({"step": 1, "loss": 1.0}) + "\n"
    )
    (run_dir / "fleet.json").write_text(json.dumps({
        "verdict": "red",
        "sweeps": 9,
        "replicas": {
            "serve-0-11": {"role": "serve", "healthy": True, "stale": False,
                           "error": None, "attempt": 0},
            "serve-1-22": {"role": "serve", "healthy": False, "stale": True,
                           "error": "stale card", "attempt": 1},
        },
        "red": [],
        "stale_cards": ["serve-1-22"],
        "rollup": {"llmt_fleet_serve_requests_completed": 4.0,
                   "llmt_fleet_replicas": 2.0},
    }))
    doc = render_report_data(run_dir)
    assert doc["schema_version"] == REPORT_SCHEMA_VERSION == 2
    fleet = doc["fleet"]
    assert fleet["verdict"] == "red" and fleet["sweeps"] == 9
    assert fleet["stale_cards"] == ["serve-1-22"]
    assert fleet["replicas"]["serve-1-22"]["stale"] is True
    text = render_report(run_dir)
    assert "== Fleet ==" in text and "serve-1-22" in text

    (run_dir / "fleet.json").write_text("{torn")
    assert "error" in render_report_data(run_dir)["fleet"]
