#!/usr/bin/env python
"""Precommit exporter-smoke gate (docs/observability.md#live-telemetry).

Proves the whole live-observability layer end to end on CPU, on every
commit:

1. launches the cpu-smoke fit as a child with the exporter armed
   (`LLMT_METRICS_PORT`), a train-cadence SLO target, and the slow-step
   chaos hook (`LLMT_CHAOS_SLOW_STEP_S`) injecting a sustained slow
   regime the burn-rate alert must page on;
2. scrapes `/metrics` + `/healthz` MID-FIT: at least one scrape must
   parse as valid Prometheus text containing goodput series, and
   `/healthz` must answer (the fit is healthy — slow, not wedged);
3. after the fit exits 0, asserts the chaos-injected SLO breach produced
   the alert counter in telemetry.jsonl AND a `trace-flight-slo-*.jsonl`
   ring dump in the run dir, and that the run's `report` renders the
   `== SLO ==` section.

This parent is jax-free (the child owns the backend) — it must keep
scraping while the fit computes, exactly like a real Prometheus would.

Usage: python scripts/exporter_smoke.py <scratch_dir>
"""

from __future__ import annotations

import json
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# the ONE strict scrape parser (raises ValueError on any malformed line)
# and ephemeral-port probe, shared with the loadgen and the unit
# tests so format drift and probe fixes land once — jax-free
# by graftlint contract
from llm_training_tpu.telemetry.exporter import (  # noqa: E402
    find_free_port,
    parse_prometheus_text,
)


def main() -> int:
    if len(sys.argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    scratch = Path(sys.argv[1])
    scratch.mkdir(parents=True, exist_ok=True)
    port = find_free_port()
    env = {
        "JAX_PLATFORMS": "cpu",
        "LLMT_METRICS_PORT": str(port),
        # the breach injection: every step past 1 drags an extra 0.6s...
        "LLMT_CHAOS_SLOW_STEP_S": "0.6",
        "LLMT_CHAOS_SLOW_STEP_FROM": "1",
        # ...against a 50ms cadence target, with windows sized so the
        # multi-window gate fires within the smoke's 6 steps
        "LLMT_SLO_STEP_TIME_P99_S": "0.05",
        "LLMT_SLO_MIN_SAMPLES": "3",
        "LLMT_SLO_WINDOW_FAST_S": "30",
        "LLMT_SLO_WINDOW_SLOW_S": "120",
    }
    import os

    child_env = {**os.environ, **env}
    child = subprocess.Popen(
        [
            sys.executable, "-m", "llm_training_tpu", "fit",
            "--config", "config/examples/smoke/cpu-smoke.yaml",
            f"run_root={scratch}",
        ],
        env=child_env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )

    # scrape results flow back through a queue (the sanctioned cross-thread
    # handoff): ("scrape", metrics) / ("scrape_error", msg) / ("health", code)
    import queue

    results: queue.Queue = queue.Queue()
    stop = threading.Event()

    def scrape_loop() -> None:
        base = f"http://127.0.0.1:{port}"
        while not stop.wait(0.3):
            try:
                with urllib.request.urlopen(base + "/metrics", timeout=2.0) as resp:
                    body = resp.read().decode("utf-8", "replace")
            except OSError:
                continue  # exporter not up yet / fit finished
            try:
                results.put(("scrape", parse_prometheus_text(body)))
            except ValueError as e:
                # format drift must surface as a recorded error, never a
                # silently-dead scraper thread
                results.put(("scrape_error", str(e)))
                continue
            try:
                with urllib.request.urlopen(base + "/healthz", timeout=2.0) as resp:
                    results.put(("health", resp.status))
            except urllib.error.HTTPError as e:
                results.put(("health", e.code))
            except OSError:
                pass

    scraper = threading.Thread(target=scrape_loop, daemon=True)
    scraper.start()
    try:
        out, _ = child.communicate(timeout=600)
    except subprocess.TimeoutExpired:
        child.kill()
        out, _ = child.communicate()
        print(out[-2000:], file=sys.stderr)
        print("exporter smoke: fit wedged", file=sys.stderr)
        return 1
    finally:
        stop.set()
        scraper.join(timeout=5.0)
    if child.returncode != 0:
        print(out[-2000:], file=sys.stderr)
        print(f"exporter smoke: fit exited {child.returncode}", file=sys.stderr)
        return 1

    scrapes: list[dict[str, float]] = []
    health_codes: list[int] = []
    scrape_errors: list[str] = []
    while True:
        try:
            kind, payload = results.get_nowait()
        except queue.Empty:
            break
        if kind == "scrape":
            scrapes.append(payload)
        elif kind == "scrape_error":
            scrape_errors.append(payload)
        else:
            health_codes.append(payload)

    # --- mid-fit scrape validity
    assert not scrape_errors, f"scrapes failed to parse: {scrape_errors[:3]}"
    assert scrapes, "the fit was never scrapeable mid-run (/metrics)"
    assert health_codes and all(code == 200 for code in health_codes), (
        f"/healthz must answer 200 for a slow-but-alive fit: {health_codes}"
    )
    last = scrapes[-1]
    assert "llmt_goodput_total_s" in last, sorted(last)[:20]
    assert "llmt_slo_train_step_time_p99_s_target" in last, (
        "armed SLO targets must be scrapeable live"
    )
    assert last.get("llmt_exporter_scrapes", 0) >= 1.0

    # --- the chaos-injected breach left its full paper trail
    run_dir = scratch / "smoke" / "cpu-smoke"
    records = [
        json.loads(line)
        for line in (run_dir / "telemetry.jsonl").read_text().splitlines()
        if line.strip()
    ]
    final = records[-1]
    assert final.get("slo/breaches_total", 0) >= 1, (
        f"slow-step chaos produced no SLO breach counter: "
        f"{ {k: v for k, v in final.items() if k.startswith('slo/')} }"
    )
    assert final.get("slo/train/step_time_p99_s/breaches", 0) >= 1
    dumps = list(run_dir.glob("trace-flight-slo-*.jsonl"))
    assert dumps, "SLO breach produced no trace-flight-slo-*.jsonl ring dump"
    dumped = [
        json.loads(line)
        for line in dumps[0].read_text().splitlines() if line.strip()
    ]
    assert any(e.get("name") == "breach" for e in dumped), (
        "the flight dump must hold the breach instant"
    )

    # --- report renders the section
    report = subprocess.run(
        [sys.executable, "-m", "llm_training_tpu", "report", str(run_dir)],
        env=child_env, capture_output=True, text=True,
    )
    assert report.returncode == 0, report.stderr
    assert "== SLO ==" in report.stdout, report.stdout[-1500:]
    assert "train/step_time_p99_s" in report.stdout

    print(
        f"exporter smoke: OK — {len(scrapes)} parse-valid scrape(s), "
        f"healthz {len(health_codes)}x200, breach counter "
        f"{int(final['slo/breaches_total'])}, flight dump {dumps[0].name}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
