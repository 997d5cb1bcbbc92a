#!/usr/bin/env python
"""Precommit fleet-smoke gate (docs/observability.md#fleet).

Proves the fleet observability plane end to end on CPU, on every commit:

1. **2-replica census** — the loadgen's `--replicas 2` mode drives two
   real serve children (own run roots, own exporter ports, discovery
   cards in a shared `LLMT_FLEET_DIR`) and asserts the fleet census at
   the all-terminal moment: aggregator rollup == summed per-replica
   client censuses, terminals exactly-once fleet-wide, verdict green.
   After the clean stop, the discovery dir must hold ZERO cards.
2. **cross-replica trace merge** — `trace --merge` over both replica run
   dirs must emit ONE Chrome-trace JSON where both replicas' request
   tracks render side by side (wall-anchor aligned; every request id
   appears exactly once, under its own replica's pid namespace).
3. **replica kill** — two cheap stub exporters (no backend) under a
   fresh discovery dir: the aggregator sweeps green, one stub is
   SIGKILLed, and the fleet verdict must flip red within ONE scrape
   interval with `/fleetz` naming the dead replica's stale card; the
   federation `/metrics` must parse as labeled Prometheus text
   throughout. A `fleet --once --out` snapshot then surfaces as report
   --format json's `fleet` block (additive: no schema_version bump), and
   `fleet --once` against an empty dir exits 2 naming the searched path.

This parent is jax-free (children own any backend) by the same contract
as the exporter smoke.

Usage: python scripts/fleet_smoke.py <scratch_dir> [seed_run_dir]

`seed_run_dir` is an existing run dir whose `checkpoints/` seeds every
replica's run root (precommit passes its CPU-fit smoke dir so no extra
fit is paid); standalone invocations omit it and a tiny fit runs first.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from llm_training_tpu.telemetry.exporter import (  # noqa: E402
    parse_prometheus_text,
)
from llm_training_tpu.telemetry.fleet import FleetAggregator  # noqa: E402

# a serve-shaped exporter with no backend: the kill leg needs replicas
# cheap enough to SIGKILL without paying two more jax boots
_STUB = """
import sys, time
from llm_training_tpu.telemetry.exporter import MetricsExporter
from llm_training_tpu.telemetry.registry import TelemetryRegistry
reg = TelemetryRegistry()
reg.gauge("serve/queue_depth").set(0.0)
reg.gauge("serve/running").set(0.0)
reg.gauge("serve/requests_completed").set(float(sys.argv[1]))
exporter = MetricsExporter(0, registry=reg, role="serve")
assert exporter.start()
print("READY", exporter.port, flush=True)
time.sleep(600)
"""


def _seed_checkpoints(scratch: Path, seed_run_dir: str | None, env) -> Path:
    """The serve children restore a checkpoint from their own run roots:
    reuse the caller's fit-smoke run dir when given, else pay one tiny
    CPU fit here."""
    if seed_run_dir:
        seed = Path(seed_run_dir)
        if (seed / "checkpoints").is_dir():
            return seed
        print(f"fleet smoke: {seed}/checkpoints absent — fitting fresh",
              file=sys.stderr)
    seed_root = scratch / "seed"
    fit = subprocess.run(
        [
            sys.executable, "-m", "llm_training_tpu", "fit",
            "--config", "config/examples/smoke/cpu-smoke.yaml",
            f"run_root={seed_root}",
        ],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if fit.returncode != 0:
        print(fit.stdout[-2000:], file=sys.stderr)
        print(fit.stderr[-2000:], file=sys.stderr)
        raise SystemExit("fleet smoke: seed fit failed")
    return seed_root / "smoke" / "cpu-smoke"


def main() -> int:
    if len(sys.argv) not in (2, 3):
        print(__doc__, file=sys.stderr)
        return 2
    scratch = Path(sys.argv[1])
    scratch.mkdir(parents=True, exist_ok=True)
    fleet_dir = scratch / "fleet"
    # a previous (crashed) invocation's cards must not pollute this census
    shutil.rmtree(fleet_dir, ignore_errors=True)
    shutil.rmtree(scratch / "fleet-kill", ignore_errors=True)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}

    # --- 0. every replica run root starts from the same tiny checkpoint
    seed = _seed_checkpoints(
        scratch, sys.argv[2] if len(sys.argv) == 3 else None, env
    )
    for index in range(2):
        dst = scratch / f"replica-{index}" / "smoke" / "cpu-smoke"
        if not (dst / "checkpoints").is_dir():
            dst.mkdir(parents=True, exist_ok=True)
            shutil.copytree(seed / "checkpoints", dst / "checkpoints")

    # --- 1. two real serve replicas, fleet census at the terminal moment
    print("fleet smoke: 2-replica loadgen census...", flush=True)
    loadgen = subprocess.run(
        [
            sys.executable, "scripts/serve_loadgen.py",
            "--config", "config/examples/smoke/cpu-smoke.yaml",
            "--requests", "4", "--max-new-tokens", "16",
            "--replicas", "2",
            "--replica-run-root", str(scratch),
            "--fleet-dir", str(fleet_dir),
            "--out", str(scratch / "fleet_loadgen.json"),
            # `--` so argparse keeps the serve flags (with their values)
            # intact in serve_args instead of stealing "2" as a positional
            "--", "--max-batch", "2", "--max-model-len", "64",
            "--prefill-chunk", "4", "--eos-token-id", "-1",
        ],
        env=env, capture_output=True, text=True, timeout=600,
    )
    if loadgen.returncode != 0:
        print(loadgen.stdout[-2000:], file=sys.stderr)
        print(loadgen.stderr[-2000:], file=sys.stderr)
        print("fleet smoke: multi-replica loadgen failed", file=sys.stderr)
        return 1
    summary = json.loads((scratch / "fleet_loadgen.json").read_text())
    assert not summary["errors"], summary["errors"]
    assert summary["replicas"] == 2 and summary["completed"] == 4, summary
    fleet = summary["fleet"]
    assert fleet and fleet["verdict"] == "green", fleet
    assert fleet["rollup"]["llmt_fleet_serve_requests_completed"] == 4.0, fleet
    assert fleet["rollup"]["llmt_fleet_replicas"] == 2.0, fleet
    leftovers = list(fleet_dir.glob("replica-*.json"))
    assert not leftovers, (
        f"clean stop left discovery cards behind: {leftovers}"
    )
    print("fleet smoke: census OK —", fleet["rollup"], flush=True)

    # --- 2. cross-replica trace merge: one Perfetto file, both tracks
    run_dirs = [
        scratch / f"replica-{i}" / "smoke" / "cpu-smoke" for i in range(2)
    ]
    merged_path = scratch / "trace_merged.json"
    merge = subprocess.run(
        [
            sys.executable, "-m", "llm_training_tpu", "trace",
            "--merge", *map(str, run_dirs), "--out", str(merged_path),
        ],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert merge.returncode == 0, merge.stderr
    document = json.loads(merged_path.read_text())
    events = document["traceEvents"]
    assert isinstance(events, list) and events, "empty merged trace"
    # both replicas' serve tracks: distinct pid namespaces, labeled
    # process names, and every request id exactly once fleet-wide
    serve_pids = {
        e["pid"] for e in events
        if e.get("name") == "process_name" and "/serve" in e["args"]["name"]
    }
    assert len(serve_pids) == 2, f"want 2 serve process tracks: {serve_pids}"
    request_tracks: dict[str, set[int]] = {}
    for event in events:
        rid = (event.get("args") or {}).get("request_id")
        if rid is not None:
            request_tracks.setdefault(str(rid), set()).add(event["pid"])
    assert set(request_tracks) == {f"req-{n}" for n in range(4)}, (
        f"merged trace lost requests: {sorted(request_tracks)}"
    )
    for rid, pids in request_tracks.items():
        assert len(pids) == 1, f"{rid} rendered under {pids} — pid bleed"
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans and all(e["ts"] >= 0 for e in spans), "bad merged rebase"
    print(
        f"fleet smoke: merge OK — {len(events)} events, "
        f"{len(request_tracks)} request tracks over {len(serve_pids)} "
        "replicas", flush=True,
    )

    # --- 3. kill leg: green fleet -> SIGKILL one stub -> red within one
    # scrape interval, /fleetz names the stale card
    print("fleet smoke: replica-kill verdict flip...", flush=True)
    kill_dir = scratch / "fleet-kill"
    stub_env = {**os.environ, "LLMT_FLEET_DIR": str(kill_dir)}
    stubs = [
        subprocess.Popen(
            [sys.executable, "-c", _STUB, str(7 * (i + 1))],
            env=stub_env, stdout=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    try:
        for stub in stubs:
            ready = stub.stdout.readline()
            assert ready.startswith("READY"), f"stub never armed: {ready!r}"
        interval_s = 1.0
        aggregator = FleetAggregator(fleet_dir=kill_dir, interval_s=interval_s)
        aggregator.start(port=0)
        try:
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                snapshot = aggregator.snapshot()
                if snapshot["verdict"] == "green" and len(
                    snapshot["replicas"]
                ) == 2:
                    break
                time.sleep(0.05)
            assert snapshot["verdict"] == "green", snapshot
            # federation surface is parse-valid LABELED Prometheus
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{aggregator.port}/metrics", timeout=3.0
            ).read().decode()
            federated = parse_prometheus_text(body, labels=True)
            labeled = [k for k in federated if "{replica=" in k]
            assert labeled, sorted(federated)[:10]
            assert federated["llmt_fleet_serve_requests_completed"] == 21.0, (
                {k: v for k, v in federated.items() if "fleet" in k}
            )
            victim_pid = stubs[1].pid
            os.kill(victim_pid, signal.SIGKILL)
            stubs[1].wait()
            killed_at = time.monotonic()
            while time.monotonic() < killed_at + interval_s + 2.0:
                snapshot = aggregator.snapshot()
                if snapshot["verdict"] == "red":
                    break
                time.sleep(0.05)
            flip_s = time.monotonic() - killed_at
            assert snapshot["verdict"] == "red", (
                f"verdict never flipped red after SIGKILL: {snapshot}"
            )
            assert flip_s <= interval_s + 2.0, (
                f"flip took {flip_s:.1f}s (> one {interval_s}s interval "
                "+ sweep slack)"
            )
            dead = [
                rid for rid in snapshot["stale_cards"]
                if rid.endswith(str(victim_pid))
            ]
            assert dead, (victim_pid, snapshot["stale_cards"])
            fleetz = urllib.request.urlopen(
                f"http://127.0.0.1:{aggregator.port}/fleetz", timeout=3.0
            ).read().decode()
            assert "RED" in fleetz and dead[0] in fleetz, fleetz
            print(
                f"fleet smoke: kill OK — verdict red {flip_s:.2f}s after "
                f"SIGKILL, /fleetz names {dead[0]}", flush=True,
            )

            # --- fleet --once snapshot -> report --format json fleet block
            # (the SEED run dir: report wants a fit-shaped metrics.jsonl,
            # which the serve replicas' run dirs deliberately lack)
            fleet_out = seed / "fleet.json"
            once = subprocess.run(
                [
                    sys.executable, "-m", "llm_training_tpu", "fleet",
                    "--dir", str(kill_dir), "--once", "--json",
                    "--out", str(fleet_out),
                ],
                env=env, capture_output=True, text=True, timeout=60,
            )
            assert once.returncode == 0, once.stderr
            assert json.loads(once.stdout)["verdict"] == "red"
        finally:
            aggregator.stop()
    finally:
        for stub in stubs:
            if stub.poll() is None:
                stub.kill()
                stub.wait()
    report = subprocess.run(
        [
            sys.executable, "-m", "llm_training_tpu", "report",
            str(seed), "--format", "json",
        ],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert report.returncode == 0, report.stderr
    doc = json.loads(report.stdout)
    assert doc["schema_version"] == 2, doc.get("schema_version")
    assert doc["fleet"] and doc["fleet"]["verdict"] == "red", doc.get("fleet")
    assert doc["fleet"]["stale_cards"], doc["fleet"]

    # --- exit-2 contract: an empty discovery dir names the searched path
    empty = scratch / "fleet-empty"
    empty.mkdir(exist_ok=True)
    nobody = subprocess.run(
        [
            sys.executable, "-m", "llm_training_tpu", "fleet",
            "--dir", str(empty), "--once",
        ],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert nobody.returncode == 2, (nobody.returncode, nobody.stderr)
    assert str(empty) in nobody.stderr, nobody.stderr

    print(
        "fleet smoke: OK — census, merge, kill-flip, report fleet block, "
        "exit-2 paths"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
