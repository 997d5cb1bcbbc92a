#!/usr/bin/env python
"""Synthetic-traffic load driver for the `serve` CLI (docs/serving.md).

Spawns `llm-training-tpu serve` (or, with `--supervised`, `supervise
--child serve` — the drain/replay harness) as a child process and drives
the real JSONL stdin/stdout protocol. Two arrival modes:

- **overlap** (default): the first request goes in immediately; every
  later request is held until the first streamed token chunk proves decode
  is in flight, then submitted with a small gap — so continuous batching
  (admission mid-decode) is what the run exercises, not a closed batch;
- **burst**: every request is written up front, as fast as the pipe takes
  them — the overload shape that drives the intake bound / projected-TTFT
  shedding (`--max-batch`/`--max-queue` small → `overloaded` terminals).

`--deadline-ms N --deadline-every K` stamps every K-th request with a
latency budget (mixed traffic: some requests carry deadlines, some don't),
and `--malformed N` interleaves N junk lines the server must answer with
`{"type": "error"}` chunks while everything well-formed still terminates.
`--metrics-port N` additionally scrapes the child's live-telemetry
exporter (/metrics + /healthz, docs/observability.md#live-telemetry)
throughout the run: every scrape must parse as Prometheus text, and at
the moment every request has its terminal the final scrape's
`serve/requests_completed` and queue-depth gauges must MATCH this
driver's client-side census — exporter/engine drift is a failure.

The terminal contract this driver enforces (exit nonzero on violation) is
the serving tier's resilience acceptance: every submitted request must end
in EXACTLY ONE `done` chunk — stop_reason ∈ eos / max_tokens / deadline /
overloaded / rejected / capacity — across the whole run, including a
supervised drain/replay boundary (the relaunched child inherits this
driver's pipes, so duplicate or missing terminals are visible here).
Additional failures: a done with no token chunks for a FULL completion
(eos/max_tokens), a pool-block leak in the last stats record, fewer error
chunks than injected malformed lines, and (overlap mode only) arrivals
that never overlapped (`serve/peak_running` < 2).

Client-side latency is measured per request from its submit time: TTFT to
the first token chunk, TPOT across subsequent chunks. The summary merges
the engine's own `serve/*` stats record (throughput, shed/deadline/replay
counters, pool pressure) with the client percentiles and a per-stop_reason
terminal census, prints one JSON object, and exits nonzero on any failure.

`--router` drives the `route` fleet tier instead of a bare serve child
(docs/serving.md#router): the same exactly-once-terminal audit applies
across a chaos-injected mid-stream replica SIGKILL
(LLMT_CHAOS_ROUTER_KILL_REPLICA), and with `--fleet-dir` the all-terminal
moment additionally sweeps the fleet and asserts the rollup's
`router_requests_completed` still equals the client census after the
failover replay.

Usage:
    python scripts/serve_loadgen.py --config <yaml> [overrides...] \
        [--requests 4] [--max-new-tokens 8] [--arrival {overlap,burst}] \
        [--deadline-ms 0 --deadline-every 2] [--malformed 0] \
        [--supervised | --router] [--out summary.json] \
        [-- <extra serve args>]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

# the ONE strict scrape parser, shared with the precommit exporter smoke
# and the unit tests so format drift fails identically everywhere; the
# telemetry package surface is jax-free at import time by contract, so
# this parent stays backend-free
from llm_training_tpu.serve.router import require_one_process_per_chip  # noqa: E402
from llm_training_tpu.telemetry.exporter import parse_prometheus_text  # noqa: E402

# the terminal states the protocol may end a request in — anything else
# (or anything twice, or nothing at all) is a dropped/duplicated stream
TERMINAL_REASONS = (
    "eos", "max_tokens", "deadline", "overloaded", "rejected", "capacity"
)


class ExporterScraper:
    """Polls the serve child's /metrics + /healthz during the run
    (docs/observability.md#live-telemetry). Connection failures are
    expected (child starting up / relaunching) and only counted; a scrape
    that ANSWERS but fails to parse is a recorded error. `scrape_final()`
    is called synchronously the moment every request has its terminal —
    at that instant the engine is quiescent (nothing queued or running),
    so the gauge cross-check against the client census is exact."""

    def __init__(self, port: int, interval_s: float = 0.2):
        import urllib.request as _request

        self._request = _request
        self.base = f"http://127.0.0.1:{port}"
        self.interval_s = interval_s
        self._lock = threading.Lock()
        self.ok = 0  # guarded by: _lock
        self.failed = 0  # guarded by: _lock
        self.parse_errors: list[str] = []  # guarded by: _lock
        self.unhealthy_observed = False  # guarded by: _lock
        self.max_queue_depth = 0.0  # guarded by: _lock
        self.final: dict[str, float] | None = None  # guarded by: _lock
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self) -> "ExporterScraper":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)

    def _get(self, path: str):
        return self._request.urlopen(self.base + path, timeout=2.0)

    def scrape_once(self) -> dict[str, float] | None:
        """One scrape (network I/O outside the lock; called from both the
        poll thread and the main thread's final-census moment)."""
        try:
            with self._get("/metrics") as resp:
                body = resp.read().decode("utf-8", "replace")
        except OSError:
            with self._lock:
                self.failed += 1  # child starting/relaunching: expected
            return None
        try:
            metrics = parse_prometheus_text(body)
        except ValueError as e:
            with self._lock:
                self.parse_errors.append(str(e))
            return None
        with self._lock:
            self.ok += 1
            self.max_queue_depth = max(
                self.max_queue_depth, metrics.get("llmt_serve_queue_depth", 0.0)
            )
        return metrics

    def _check_health(self) -> None:
        try:
            with self._get("/healthz"):
                pass  # 200
        except OSError as e:
            if getattr(e, "code", None) == 503:
                with self._lock:
                    self.unhealthy_observed = True
            # anything else: child down/starting — not a health verdict

    def _loop(self) -> None:
        while not self._stop.wait(self.interval_s):
            self.scrape_once()
            self._check_health()

    def scrape_final(self) -> None:
        # bounded retry: the child races from its last terminal through
        # stats/telemetry-write to exporter.stop(), and losing that race
        # must not turn a healthy run into a spurious census failure — the
        # engine is quiescent, so a slightly later scrape reads the same
        # gauges
        metrics = None
        for _ in range(10):
            metrics = self.scrape_once()
            if metrics is not None:
                break
            time.sleep(0.1)
        with self._lock:
            self.final = metrics

    def summary(self) -> dict:
        with self._lock:
            return {
                "scrapes_ok": self.ok,
                "scrapes_failed": self.failed,
                "parse_errors": list(self.parse_errors),
                "unhealthy_observed": self.unhealthy_observed,
                "max_queue_depth": self.max_queue_depth,
                "final": dict(self.final) if self.final else None,
            }


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile; avoids a numpy import in this jax-free
    parent (the child owns the devices)."""
    ordered = sorted(values)
    rank = max(0, min(len(ordered) - 1, round(q / 100.0 * (len(ordered) - 1))))
    return ordered[rank]


def build_requests(args) -> list[dict]:
    rng = random.Random(args.seed)
    requests = []
    for n in range(args.requests):
        length = rng.randint(args.min_prompt, args.max_prompt)
        request = {
            "id": f"req-{n}",
            "prompt": [rng.randint(3, args.vocab - 1) for _ in range(length)],
            "max_new_tokens": args.max_new_tokens,
        }
        if args.deadline_ms and args.deadline_every and n % args.deadline_every == 0:
            request["deadline_ms"] = args.deadline_ms
        requests.append(request)
    return requests


def check_misplaced_flags(
    serve_args: list[str], passthrough: list[str], argv: list[str]
) -> None:
    """The PR 16 argparse watch-out, made loud: with an otherwise-empty
    `serve_args` positional, `parse_known_args` assigns the token FOLLOWING
    the first unknown flag to the positional — the flag's value silently
    vanishes into serve_args while the flag itself lands in passthrough
    (`--max-batch 2` becomes serve_args=['2'] + passthrough=['--max-batch']).
    Any positional token that appears AFTER the first unknown flag on the
    command line is that swallow; error loudly and demand `--`. Flags after
    genuine positionals (the precommit idiom: `run_root=/x --max-batch 2`)
    keep order and stay legal."""
    if "--" in argv:
        return  # explicit separator: everything after it is intentional
    # only FLAG tokens locate the unknown region: a passthrough VALUE
    # (`--prefill-chunk 4`) can repeat a known flag's value (`--requests 4`)
    # earlier on the line, and its first occurrence is not where it sits
    unknown_flags = [
        argv.index(tok) for tok in passthrough
        if tok.startswith("--") and tok in argv
    ]
    if not unknown_flags:
        return
    first_unknown = min(unknown_flags)
    after_unknown = argv[first_unknown + 1:]
    for token in serve_args:
        if token in after_unknown:
            raise SystemExit(
                f"error: positional {token!r} follows the unknown flag "
                f"{argv[first_unknown]!r} — argparse would silently swallow "
                "the flag's value into serve_args. Put child flags after "
                "an explicit `--` separator (e.g. `-- "
                f"{argv[first_unknown]} {token}`)."
            )


def build_child_argv(args) -> list[str]:
    """The plain `serve` command, the `route` fleet tier (`--router`), or
    the supervised wrapper that relaunches serve on exit 75 / signal deaths
    (drain + journal replay, docs/serving.md#resilience)."""
    if args.router:
        argv = [
            sys.executable, "-m", "llm_training_tpu", "route",
            "--config", args.config,
            "--replicas", str(args.router_replicas),
        ]
        if args.router_max_replicas:
            argv += ["--max-replicas", str(args.router_max_replicas)]
        if args.hedge_ttft_ms:
            argv += ["--hedge-ttft-ms", str(args.hedge_ttft_ms)]
        if args.serve_args:
            argv += ["--", *args.serve_args]
        return argv
    if not args.supervised:
        return [
            sys.executable, "-m", "llm_training_tpu", "serve",
            "--config", args.config, *args.serve_args,
        ]
    import shlex

    return [
        sys.executable, "-m", "llm_training_tpu", "supervise",
        "--child", "serve", "--config", args.config,
        "--max-restarts", str(args.max_restarts),
        "--backoff-base-s", "0.2", "--backoff-max-s", "1.0",
        "--child-args", shlex.join(args.serve_args),
    ]


class ReplicaDriver:
    """One serve child of the multi-replica loadgen (`--replicas N`):
    owns the child process, a feeder thread (this replica's share of the
    traffic), and a reader thread folding protocol chunks into the
    per-replica census. The feeder deliberately does NOT close stdin —
    the fleet census must sweep live exporters AFTER every terminal, so
    the children idle until `finish()` releases them."""

    def __init__(self, index: int, args, requests: list[dict], env: dict,
                 run_root: str):
        self.index = index
        self.args = args
        self.requests = requests
        self.child = subprocess.Popen(
            [
                sys.executable, "-m", "llm_training_tpu", "serve",
                "--config", args.config, *args.serve_args,
                f"run_root={run_root}",
            ],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1, env=env,
        )
        self._lock = threading.Lock()
        self.done: dict[str, dict] = {}  # guarded by: _lock
        self.done_counts: dict[str, int] = {}  # guarded by: _lock
        self.chunks: dict[str, int] = {}  # guarded by: _lock
        self.stats: dict[str, float] = {}  # guarded by: _lock
        self.error_chunks = 0  # guarded by: _lock
        self.all_terminal = threading.Event()
        self.first_token_seen = threading.Event()
        self._feeder = threading.Thread(target=self._feed, daemon=True)
        self._reader = threading.Thread(target=self._read, daemon=True)

    def start(self) -> "ReplicaDriver":
        self._reader.start()
        self._feeder.start()
        return self

    def _send(self, request: dict) -> None:
        self.child.stdin.write(json.dumps(request) + "\n")
        self.child.stdin.flush()

    def _feed(self) -> None:
        try:
            self._send(self.requests[0])
            if self.args.arrival == "overlap":
                self.first_token_seen.wait()
            for n, request in enumerate(self.requests[1:]):
                if n and self.args.arrival == "overlap":
                    time.sleep(self.args.arrival_gap_s)
                self._send(request)
        except (BrokenPipeError, OSError):
            pass  # child died; the terminal audit reports the hole

    def _read(self) -> None:
        expected = {r["id"] for r in self.requests}
        for line in self.child.stdout:
            line = line.strip()
            if not line:
                continue
            try:
                event = json.loads(line)
            except json.JSONDecodeError:
                continue  # interleaved logging, not a protocol chunk
            kind = event.get("type")
            if kind == "token":
                rid = event["id"]
                with self._lock:
                    self.chunks[rid] = self.chunks.get(rid, 0) + 1
                self.first_token_seen.set()
            elif kind == "done":
                rid = event["id"]
                with self._lock:
                    self.done[rid] = event
                    self.done_counts[rid] = self.done_counts.get(rid, 0) + 1
                    terminal = expected <= set(self.done)
                self.first_token_seen.set()
                if terminal:
                    self.all_terminal.set()
            elif kind == "stats":
                with self._lock:
                    self.stats = event["stats"]
            elif kind == "error":
                with self._lock:
                    self.error_chunks += 1
                self.first_token_seen.set()
        # stdout EOF: the child is gone. Unblock the census waiter NOW —
        # the rc audit and the exactly-once terminal audit report the
        # holes; hanging out the idle timeout helps nobody.
        self.first_token_seen.set()
        self.all_terminal.set()

    def finish(self) -> int:
        """Release the idling child (close stdin), collect its exit."""
        self.first_token_seen.set()  # unwedge the feeder on a dead child
        try:
            self.child.stdin.close()
        except OSError:
            pass
        try:
            rc = self.child.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            self.child.kill()
            rc = self.child.wait()
        self._reader.join(timeout=10.0)
        self._feeder.join(timeout=10.0)
        return rc

    def census(self) -> dict:
        with self._lock:
            reasons: dict[str, int] = {}
            for event in self.done.values():
                reason = str(event.get("stop_reason"))
                reasons[reason] = reasons.get(reason, 0) + 1
            return {
                "replica": self.index,
                "requests": len(self.requests),
                "completed": reasons.get("eos", 0) + reasons.get("max_tokens", 0),
                "terminal_reasons": reasons,
                "streamed_chunks": sum(self.chunks.values()),
                "error_chunks": self.error_chunks,
                "done_counts": dict(self.done_counts),
                "engine": dict(self.stats),
            }


def run_multi(args) -> int:
    """`--replicas N`: split the traffic round-robin across N serve
    children (each with its own run_root, metrics port, and fleet card)
    and assert the FLEET census at the all-terminal moment: the
    aggregator's rollup must equal the sum of the per-replica client
    censuses, terminals exactly-once fleet-wide, verdict green."""
    from llm_training_tpu.telemetry.exporter import find_free_port
    from llm_training_tpu.telemetry.fleet import FleetAggregator

    if args.supervised or args.malformed:
        print(
            "--replicas composes with neither --supervised nor "
            "--malformed (drive those single-replica)", file=sys.stderr,
        )
        return 2
    if not args.replica_run_root:
        print("--replicas needs --replica-run-root", file=sys.stderr)
        return 2
    requests = build_requests(args)
    if len(requests) < args.replicas:
        print(
            f"--requests {len(requests)} < --replicas {args.replicas}",
            file=sys.stderr,
        )
        return 2
    fleet_dir = args.fleet_dir or os.environ.get("LLMT_FLEET_DIR")
    drivers: list[ReplicaDriver] = []
    ports: list[int] = []
    for index in range(args.replicas):
        port = find_free_port()
        env = {**os.environ, "LLMT_METRICS_PORT": str(port)}
        if fleet_dir:
            env["LLMT_FLEET_DIR"] = str(fleet_dir)
        drivers.append(ReplicaDriver(
            index, args, requests[index::args.replicas], env,
            str(Path(args.replica_run_root) / f"replica-{index}"),
        ))
        ports.append(port)
    for driver in drivers:
        driver.start()

    failures: list[str] = []
    deadline = time.monotonic() + args.idle_timeout_s
    for driver in drivers:
        remaining = max(0.0, deadline - time.monotonic())
        if not driver.all_terminal.wait(remaining):
            failures.append(
                f"replica-{driver.index}: not every request terminal "
                f"within {args.idle_timeout_s}s"
            )

    # --- THE fleet census moment: every engine quiescent (all terminals
    # in), every exporter still armed (stdin held open) — one sweep must
    # see the whole fleet green and its rollup equal the client truth
    fleet_snapshot = None
    if not failures:
        aggregator = FleetAggregator(
            fleet_dir=fleet_dir,
            targets="" if fleet_dir else ",".join(
                f"127.0.0.1:{port}" for port in ports
            ),
        )
        fleet_snapshot = aggregator.sweep()
        if len(fleet_snapshot["replicas"]) != args.replicas:
            failures.append(
                f"fleet census: {len(fleet_snapshot['replicas'])} "
                f"replica(s) visible, want {args.replicas} "
                f"(dir={fleet_dir!r})"
            )
        if fleet_snapshot["verdict"] != "green":
            failures.append(
                f"fleet verdict {fleet_snapshot['verdict']!r} at the "
                f"census moment (red={fleet_snapshot['red']}, "
                f"stale={fleet_snapshot['stale_cards']})"
            )
        rollup = fleet_snapshot["rollup"]
        client_completed = sum(d.census()["completed"] for d in drivers)
        scraped = rollup.get("llmt_fleet_serve_requests_completed")
        if scraped != float(client_completed):
            failures.append(
                f"fleet census drift: rollup requests_completed "
                f"{scraped} != summed client censuses {client_completed}"
            )
        for gauge in (
            "llmt_fleet_serve_queue_depth", "llmt_fleet_serve_running"
        ):
            if rollup.get(gauge, 0.0) != 0.0:
                failures.append(
                    f"fleet not quiescent at census: {gauge} = "
                    f"{rollup[gauge]}"
                )

    rcs = [driver.finish() for driver in drivers]
    for index, rc in enumerate(rcs):
        if rc != 0:
            failures.append(f"replica-{index}: serve exited {rc}")

    # --- exactly-once terminals FLEET-WIDE: each request was routed to
    # one replica and must have exactly one done chunk anywhere
    fleet_done: dict[str, int] = {}
    per_replica = [driver.census() for driver in drivers]
    for census in per_replica:
        for rid, count in census.pop("done_counts").items():
            fleet_done[rid] = fleet_done.get(rid, 0) + count
    for request in requests:
        count = fleet_done.get(request["id"], 0)
        if count != 1:
            failures.append(
                f"{request['id']}: {count} terminal(s) fleet-wide — "
                "want exactly one"
            )

    summary = {
        "replicas": args.replicas,
        "requests": len(requests),
        "completed": sum(c["completed"] for c in per_replica),
        "per_replica": per_replica,
        "fleet": {
            "verdict": fleet_snapshot["verdict"],
            "red": fleet_snapshot["red"],
            "stale_cards": fleet_snapshot["stale_cards"],
            "rollup": {
                key: value
                for key, value in fleet_snapshot["rollup"].items()
                if key.startswith(("llmt_fleet_serve_", "llmt_fleet_replicas"))
            },
        } if fleet_snapshot else None,
        "errors": failures,
    }
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f)
    return 1 if failures else 0


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--requests", type=int, default=4)
    parser.add_argument("--max-new-tokens", type=int, default=8)
    parser.add_argument("--min-prompt", type=int, default=2)
    parser.add_argument("--max-prompt", type=int, default=6)
    parser.add_argument("--vocab", type=int, default=64, help="synthetic token id bound")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--arrival", default="overlap", choices=("overlap", "burst"),
        help="overlap = follow-ups wait for the first token (continuous-"
        "batching proof); burst = everything up front (overload/shedding)",
    )
    parser.add_argument(
        "--arrival-gap-s", type=float, default=0.05,
        help="gap between follow-up arrivals (overlap mode)",
    )
    parser.add_argument(
        "--deadline-ms", type=float, default=0.0,
        help="latency budget stamped on every --deadline-every-th request "
        "(0 = no deadlines)",
    )
    parser.add_argument(
        "--deadline-every", type=int, default=2,
        help="which requests carry --deadline-ms (every K-th, from the "
        "first) — mixed deadline traffic by default",
    )
    parser.add_argument(
        "--malformed", type=int, default=0,
        help="junk lines interleaved into the stream; the server owes an "
        "error chunk for each and every real request still a terminal",
    )
    parser.add_argument(
        "--supervised", action="store_true",
        help="drive `supervise --child serve` instead of bare `serve`: "
        "SIGTERM/SIGABRT deaths relaunch and replay the request journal "
        "(pair with LLMT_CHAOS_SERVE_* faults)",
    )
    parser.add_argument(
        "--max-restarts", type=int, default=3,
        help="supervise restart budget (--supervised only)",
    )
    parser.add_argument(
        "--idle-timeout-s", type=float, default=600.0,
        help="kill the child when no stdout line lands for this long",
    )
    parser.add_argument(
        "--metrics-port", type=int, default=0,
        help="scrape the serve child's /metrics + /healthz exporter "
        "(docs/observability.md#live-telemetry) on this port during the "
        "run and cross-check serve/requests_completed + queue-depth "
        "gauges against the client-side census (exporter/engine drift is "
        "a failure). The child must run with LLMT_METRICS_PORT set to the "
        "same port; 0 = no scraping",
    )
    parser.add_argument(
        "--replicas", type=int, default=1,
        help="multi-replica mode (docs/observability.md#fleet): split the "
        "traffic round-robin across N serve children and assert the FLEET "
        "census (aggregator rollup == summed per-replica client censuses, "
        "terminals exactly-once fleet-wide)",
    )
    parser.add_argument(
        "--replica-run-root", default=None,
        help="base directory for per-replica run roots "
        "(<base>/replica-<i>; required with --replicas > 1)",
    )
    parser.add_argument(
        "--fleet-dir", default=None,
        help="discovery directory for the fleet census (sets "
        "LLMT_FLEET_DIR for the children; default: inherit the env; "
        "unset = census by static --targets over the child ports)",
    )
    parser.add_argument(
        "--router", action="store_true",
        help="drive the `route` fleet tier instead of a bare serve child "
        "(docs/serving.md#router): same protocol audit, but the child is "
        "the router over --router-replicas serve replicas — pair with "
        "LLMT_CHAOS_ROUTER_* faults to prove exactly-once terminals "
        "across a mid-stream replica kill",
    )
    parser.add_argument(
        "--router-replicas", type=int, default=2,
        help="serve replicas behind the router (--router only)",
    )
    parser.add_argument(
        "--router-max-replicas", type=int, default=None,
        help="router elasticity ceiling (--router only; default: "
        "--router-replicas)",
    )
    parser.add_argument(
        "--hedge-ttft-ms", type=float, default=0.0,
        help="router hedge budget (--router only; 0 = hedging off)",
    )
    parser.add_argument("--out", default=None, help="also write the summary JSON here")
    parser.add_argument(
        "serve_args", nargs="*",
        help="config overrides and extra `serve` flags (e.g. run_root=... "
        "--max-batch 2)",
    )
    return parser


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    """Unknown flags (e.g. --max-batch) pass through to the serve child —
    but a flag whose value argparse swallowed into the positional slot must
    error loudly, not vanish (see check_misplaced_flags)."""
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    args, passthrough = build_parser().parse_known_args(argv)
    check_misplaced_flags(args.serve_args, passthrough, argv)
    args.serve_args += passthrough
    return args


def main() -> int:
    args = parse_args()

    if args.router and (args.supervised or args.malformed or args.replicas > 1):
        print(
            "--router composes with none of --supervised / --malformed / "
            "--replicas (the router owns its own fleet)", file=sys.stderr,
        )
        return 2
    # several children on one accelerator is an immediate, named error —
    # never a hang at the second child's backend start-up
    require_one_process_per_chip(
        max(args.router_replicas, args.router_max_replicas or 0)
        if args.router else args.replicas
    )
    if args.replicas > 1:
        return run_multi(args)

    requests = build_requests(args)
    env_updates: dict[str, str] = {}
    if args.metrics_port:
        # the child reads LLMT_METRICS_PORT itself; setting it here keeps
        # one flag driving both sides (and supervise's env passthrough
        # carries it across relaunches)
        env_updates["LLMT_METRICS_PORT"] = str(args.metrics_port)
    if args.router and args.fleet_dir:
        env_updates["LLMT_FLEET_DIR"] = str(args.fleet_dir)
    child_env = {**os.environ, **env_updates} if env_updates else None
    child = subprocess.Popen(
        build_child_argv(args),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, bufsize=1,
        env=child_env,
    )
    scraper = (
        ExporterScraper(args.metrics_port).start() if args.metrics_port else None
    )

    submit_s: dict[str, float] = {}
    first_token_s: dict[str, float] = {}
    last_token_s: dict[str, float] = {}
    chunks: dict[str, int] = {}
    done: dict[str, dict] = {}
    done_counts: dict[str, int] = {}
    stats: dict[str, float] = {}
    error_chunks: list[str] = []
    failures: list[str] = []
    first_token_seen = threading.Event()

    def send_line(line: str) -> None:
        child.stdin.write(line + "\n")
        child.stdin.flush()

    def send(request: dict) -> None:
        submit_s[request["id"]] = time.perf_counter()
        send_line(json.dumps(request))

    def feed() -> None:
        malformed_left = args.malformed
        try:
            send(requests[0])
            if args.arrival == "overlap":
                # hold the rest until decode is demonstrably in flight, so
                # every later arrival exercises mid-stream admission; the
                # first follow-up goes immediately (a warm decode step is
                # ~ms — any fixed gap risks outliving the first generation)
                first_token_seen.wait()
            for n, request in enumerate(requests[1:]):
                if malformed_left > 0:
                    send_line('{"garbage: true')  # interleaved junk
                    malformed_left -= 1
                if n and args.arrival == "overlap":
                    time.sleep(args.arrival_gap_s)
                send(request)
            while malformed_left > 0:
                send_line('{"garbage: true')
                malformed_left -= 1
        except BrokenPipeError:
            pass  # child died; the reader loop reports it
        finally:
            if not args.router:
                # router mode holds stdin open: the fleet census must sweep
                # the router's live exporters AFTER every terminal (the
                # reader's all-done moment closes it)
                try:
                    child.stdin.close()
                except OSError:
                    pass

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()

    router_fleet: dict | None = None
    census_taken = False

    def router_all_done_census() -> None:
        """The --router census moment: every request just went terminal,
        the router and its replicas are quiescent but still alive (stdin
        held open) — sweep the fleet NOW, then release the router."""
        nonlocal router_fleet
        if scraper is not None:
            scraper.scrape_final()
        if args.fleet_dir:
            from llm_training_tpu.telemetry.fleet import FleetAggregator

            router_fleet = FleetAggregator(fleet_dir=args.fleet_dir).sweep()
        try:
            child.stdin.close()
        except OSError:
            pass

    timer = threading.Timer(args.idle_timeout_s, child.kill)
    timer.start()
    try:
        for line in child.stdout:
            timer.cancel()
            timer = threading.Timer(args.idle_timeout_s, child.kill)
            timer.start()
            line = line.strip()
            if not line:
                continue
            try:
                event = json.loads(line)
            except json.JSONDecodeError:
                continue  # interleaved logging, not a protocol chunk
            now = time.perf_counter()
            kind = event.get("type")
            if kind == "token":
                rid = event["id"]
                chunks[rid] = chunks.get(rid, 0) + 1
                first_token_s.setdefault(rid, now)
                last_token_s[rid] = now
                first_token_seen.set()
            elif kind == "done":
                rid = event["id"]
                done[rid] = event
                done_counts[rid] = done_counts.get(rid, 0) + 1
                # a token-less termination (rejected / capacity / deadline /
                # overloaded) must also unblock the feeder, or a first
                # request that never streams wedges the run until the idle
                # timeout
                first_token_seen.set()
                if not census_taken and all(r["id"] in done for r in requests):
                    # every request just went terminal: the engine is
                    # quiescent NOW (nothing queued or running), so this
                    # synchronous scrape is the exact-census moment
                    census_taken = True
                    if args.router:
                        router_all_done_census()
                    elif scraper is not None:
                        scraper.scrape_final()
            elif kind == "stats":
                stats = event["stats"]  # last record wins across relaunches
            elif kind == "error":
                error_chunks.append(event.get("error", "unknown"))
                first_token_seen.set()
    finally:
        timer.cancel()
        first_token_seen.set()  # unblock the feeder if the child died early
    rc = child.wait()

    # --- the terminal contract: exactly one honest terminal per request
    for request in requests:
        rid = request["id"]
        count = done_counts.get(rid, 0)
        if count == 0:
            failures.append(f"{rid}: no done chunk (rc {rc})")
            continue
        if count > 1:
            failures.append(
                f"{rid}: {count} done chunks — a terminal must arrive "
                "exactly once (duplicate across a drain/replay boundary?)"
            )
        reason = done[rid].get("stop_reason")
        if reason not in TERMINAL_REASONS:
            failures.append(f"{rid}: unknown stop_reason {reason!r}")
        elif reason in ("eos", "max_tokens") and not chunks.get(rid):
            failures.append(f"{rid}: done without any streamed token chunks")
    if args.router:
        # the final stats record is router/*-shaped: the pool-leak check
        # belongs to the replicas (the router audits its own census)
        if not stats:
            failures.append("no stats record from the router")
        else:
            total = stats.get("requests_total", -1)
            terminals = stats.get("requests_completed", 0) + stats.get(
                "requests_failed", 0
            )
            if terminals != total:
                failures.append(
                    f"router census not exactly-once: {terminals} terminals "
                    f"for {total} routed requests"
                )
    else:
        leaked = stats.get("decode/cache_blocks_in_use")
        if leaked is None:
            failures.append("no stats record from the child")
        elif leaked:
            failures.append(f"pool leak: {int(leaked)} blocks still in use at exit")
    # the serve process also answers chaos-injected junk
    # (LLMT_CHAOS_SERVE_MALFORMED_FLOOD) with error chunks on this stream
    expected_errors = args.malformed + int(
        os.environ.get("LLMT_CHAOS_SERVE_MALFORMED_FLOOD", "0") or 0
    )
    if len(error_chunks) < expected_errors:
        failures.append(
            f"only {len(error_chunks)} error chunk(s) for "
            f"{expected_errors} malformed line(s)"
        )
    peak = (
        stats.get("peak_inflight", 0) if args.router
        else stats.get("serve/peak_running", 0)
    )
    if args.arrival == "overlap" and len(requests) > 1 and peak < 2:
        failures.append(
            f"arrivals never overlapped (peak_running {peak}) — raise "
            "--max-new-tokens or check --max-batch > 1"
        )

    # --- exporter cross-check (--metrics-port): the live gauges must agree
    # with this driver's own census — scraped-vs-client drift means the
    # exporter (or the engine state it renders) is lying to the fleet
    scrape_summary = None
    if scraper is not None:
        scraper.stop()
        scrape_summary = scraper.summary()
        if scrape_summary["parse_errors"]:
            failures.append(
                "scrape parse errors (exporter format drift?): "
                f"{scrape_summary['parse_errors'][:3]}"
            )
        if scrape_summary["scrapes_ok"] == 0:
            failures.append(
                "--metrics-port set but /metrics was never scrapeable"
            )
        final = scrape_summary["final"]
        if final is None:
            failures.append(
                "no parse-valid scrape at the all-terminal moment"
            )
        elif args.router:
            for gauge in ("llmt_router_queue_depth", "llmt_router_inflight"):
                if final.get(gauge, 0.0) != 0.0:
                    failures.append(
                        f"router not quiescent at the final scrape: "
                        f"{gauge} = {final[gauge]}"
                    )
            client_completed = sum(
                1 for event in done.values()
                if event.get("stop_reason") in ("eos", "max_tokens")
            )
            scraped = final.get("llmt_router_requests_completed")
            if scraped != float(client_completed):
                failures.append(
                    f"exporter/router drift: scraped requests_completed "
                    f"{scraped} != client census {client_completed}"
                )
        else:
            for gauge in ("llmt_serve_queue_depth", "llmt_serve_running"):
                if final.get(gauge, 0.0) != 0.0:
                    failures.append(
                        f"engine not quiescent at the final scrape: "
                        f"{gauge} = {final[gauge]}"
                    )
            if not args.supervised:
                # a supervised run's relaunched engine only counts its own
                # segment's completions; the strict census equality is an
                # unsupervised-run contract
                client_completed = sum(
                    1 for event in done.values()
                    if event.get("stop_reason") in ("eos", "max_tokens")
                )
                scraped = final.get("llmt_serve_requests_completed")
                if scraped != float(client_completed):
                    failures.append(
                        f"exporter/engine drift: scraped "
                        f"requests_completed {scraped} != client census "
                        f"{client_completed}"
                    )

    # --- --router + --fleet-dir: the fleet rollup at the all-terminal
    # sweep must still match the client census even after a mid-stream
    # replica kill and failover replay (satellite of the failover proof)
    if args.router and args.fleet_dir:
        if router_fleet is None:
            failures.append(
                "--fleet-dir set but the all-terminal fleet sweep never ran "
                "(did every request get a terminal?)"
            )
        else:
            if router_fleet["verdict"] != "green":
                failures.append(
                    f"fleet verdict {router_fleet['verdict']!r} at the "
                    f"census moment (red={router_fleet['red']}, "
                    f"stale={router_fleet['stale_cards']})"
                )
            client_completed = sum(
                1 for event in done.values()
                if event.get("stop_reason") in ("eos", "max_tokens")
            )
            rolled = router_fleet["rollup"].get(
                "llmt_fleet_router_requests_completed"
            )
            if rolled != float(client_completed):
                failures.append(
                    f"fleet census drift after failover: rollup "
                    f"router_requests_completed {rolled} != client census "
                    f"{client_completed}"
                )

    ttft = [
        1000.0 * (first_token_s[r] - submit_s[r]) for r in first_token_s
    ]
    tpot = [
        1000.0 * (last_token_s[r] - first_token_s[r]) / (chunks[r] - 1)
        for r in first_token_s if chunks.get(r, 0) > 1
    ]
    reasons: dict[str, int] = {}
    for event in done.values():
        reason = str(event.get("stop_reason"))
        reasons[reason] = reasons.get(reason, 0) + 1
    summary = {
        "requests": len(requests),
        "completed": reasons.get("eos", 0) + reasons.get("max_tokens", 0),
        "terminal_reasons": reasons,
        "streamed_chunks": sum(chunks.values()),
        "error_chunks": len(error_chunks),
        "errors": failures,
        "engine": stats,
    }
    if scrape_summary is not None:
        summary["scrape"] = scrape_summary
    if router_fleet is not None:
        summary["fleet"] = {
            "verdict": router_fleet["verdict"],
            "red": router_fleet["red"],
            "stale_cards": router_fleet["stale_cards"],
            "rollup": {
                key: value
                for key, value in router_fleet["rollup"].items()
                if key.startswith(("llmt_fleet_router_", "llmt_fleet_serve_",
                                   "llmt_fleet_replicas"))
            },
        }
    if ttft:
        summary["client_ttft_p50_ms"] = round(percentile(ttft, 50), 3)
        summary["client_ttft_p99_ms"] = round(percentile(ttft, 99), 3)
    if tpot:
        summary["client_tpot_p50_ms"] = round(percentile(tpot, 50), 3)
        summary["client_tpot_p99_ms"] = round(percentile(tpot, 99), 3)
    if "serve/tokens_per_sec_per_chip" in stats:
        summary["tokens_per_sec_per_chip"] = stats["serve/tokens_per_sec_per_chip"]
    print(json.dumps(summary))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
