#!/usr/bin/env bash
# Commit gate: graftlint, racecheck, shardcheck, the not-slow test tier, then
# the CPU smokes (report, generate/evaluate, serve, serve drain, trace,
# exporter, profile, fleet, router, rl, forced-NaN, kill-and-resume,
# durability). Run before EVERY commit — round 4 shipped a broken HEAD
# because a mid-edit tree was committed without this. A CPU gate: no leg needs
# or reaches for a chip (the chip check is `python chip_smoke.py`, README).
#
# Usage: scripts/precommit.sh [extra pytest args]
set -euo pipefail
cd "$(dirname "$0")/.."

SMOKE_ROOT=$(mktemp -d)
trap 'rm -rf "${SMOKE_ROOT}"' EXIT

# graftlint FIRST: pure-AST, never imports jax, fails in seconds — the
# pallas-arity / jax-free-import / host-sync / telemetry-prefix /
# env-doc-drift / logical-axis-literal / thread-jax-free invariants
# (docs/static-analysis.md). A violation message names the rule;
# `python -m llm_training_tpu.analysis --list-rules` lists them, and
# `# lint: allow(<rule>): <reason>` suppresses a deliberate one.
# PRECOMMIT_LINT_CHANGED=1 narrows the lint + race gates to the git diff
# for quick local commits; this script's default (and the CI/nightly
# path) stays full-tree so nothing rots outside the diff.
LINT_SCOPE=""
if [ "${PRECOMMIT_LINT_CHANGED:-0}" = "1" ]; then
    LINT_SCOPE="--changed-only"
fi
echo "== precommit: graftlint (static analysis, pre-jax) =="
python -m llm_training_tpu.analysis ${LINT_SCOPE}

# racecheck SECOND (docs/static-analysis.md#racecheck): the thread-model
# audit — unguarded shared mutation vs the `# guarded by:` contract
# registry, lock-order inversions, signal-handler safety. Still jax-free
# and pure-AST; its JSON lands in SMOKE_ROOT so the report gate below
# renders the race-gate line in == Audit ==.
echo "== precommit: racecheck (thread-model audit, pre-jax) =="
if ! python -m llm_training_tpu.analysis --races --json ${LINT_SCOPE} \
    | tee "${SMOKE_ROOT}/race.json" >/dev/null; then
    echo "racecheck FAILED — findings:" >&2
    python -m json.tool "${SMOKE_ROOT}/race.json" >&2 \
        || cat "${SMOKE_ROOT}/race.json" >&2
    exit 1
fi

# shardcheck THIRD (docs/static-analysis.md#audit): abstract-eval every
# registered family's init (jax.eval_shape, CPU, zero FLOPs) and resolve
# the param/opt-state/KV-cache trees against the mesh matrix — unknown
# logical axes, duplicate-axis drops, indivisible dims, large replicated
# tensors, per-chip HBM fit. The JSON lands in SMOKE_ROOT so the report
# gate below renders == Audit == from it.
echo "== precommit: shardcheck (family x mesh sharding/HBM audit) =="
if ! JAX_PLATFORMS=cpu python -m llm_training_tpu.analysis --audit --json \
    | tee "${SMOKE_ROOT}/audit.json" >/dev/null; then
    # the findings went only to the teed JSON, and the EXIT trap deletes
    # SMOKE_ROOT — print them before dying or the failure is undebuggable
    echo "shardcheck FAILED — findings:" >&2
    python -m json.tool "${SMOKE_ROOT}/audit.json" >&2 \
        || cat "${SMOKE_ROOT}/audit.json" >&2
    exit 1
fi

echo "== precommit: not-slow test tier =="
python -m pytest tests/ -x -q -m "not slow" "$@"

# telemetry/report gate: the tiny CPU config must produce a run dir whose
# metrics.jsonl/telemetry.jsonl render into a goodput table with exit 0
echo "== precommit: report smoke (CPU fit -> report) =="
# LLMT_TRACE_TRAIN=1: the fit also exercises per-step trace spans so the
# trace-smoke gate below covers the training track (docs/observability.md)
JAX_PLATFORMS=cpu LLMT_TRACE_TRAIN=1 python -m llm_training_tpu fit \
    --config config/examples/smoke/cpu-smoke.yaml "run_root=${SMOKE_ROOT}"
test -s "${SMOKE_ROOT}/smoke/cpu-smoke/trace.jsonl" \
    || { echo "fit produced no trace.jsonl"; exit 1; }
JAX_PLATFORMS=cpu python -m llm_training_tpu report "${SMOKE_ROOT}/smoke/cpu-smoke" \
    --audit-dir "${SMOKE_ROOT}" | tee "${SMOKE_ROOT}/report_smoke.log"
grep -q "goodput" "${SMOKE_ROOT}/report_smoke.log"
# the smoke config sets health.every_n_steps on a tiny MoE model, so the
# report must render the model-health section (per-layer norms + router
# stats flowed registry -> telemetry.jsonl -> report)
grep -q "== Health ==" "${SMOKE_ROOT}/report_smoke.log"
# the shardcheck gate above wrote audit.json into SMOKE_ROOT; report must
# render it as == Audit == (with the measured-HBM cross-reference when the
# run recorded the hbm gauge)
grep -q "== Audit ==" "${SMOKE_ROOT}/report_smoke.log"
grep -q "shardcheck: OK" "${SMOKE_ROOT}/report_smoke.log"
# the racecheck gate above teed race.json into SMOKE_ROOT; report renders
# its one-line race-gate summary in the same == Audit == section
grep -q "racecheck: OK" "${SMOKE_ROOT}/report_smoke.log"

# inference gate (docs/inference.md): generate + evaluate must run
# end-to-end from the smoke fit's checkpoint, emit nonzero output, and land
# their decode/eval gauges in telemetry.jsonl so report renders them
echo "== precommit: generate/evaluate smoke (checkpoint -> decode -> report) =="
JAX_PLATFORMS=cpu python -m llm_training_tpu generate \
    --config config/examples/smoke/cpu-smoke.yaml "run_root=${SMOKE_ROOT}" \
    --prompt-tokens 3,17,42 --max-new-tokens 8 \
    | tee "${SMOKE_ROOT}/generate_smoke.log"
python - "${SMOKE_ROOT}/generate_smoke.log" <<'EOF'
import json, sys
rows = [json.loads(l) for l in open(sys.argv[1]) if l.strip().startswith("{")]
tokens = [r["tokens"] for r in rows if "tokens" in r]
# nonzero output, capped at the requested 8 (the model's scalar eos — the
# LlamaConfig default id 2 — may legitimately stop a greedy row early)
assert tokens and all(0 < len(t) <= 8 for t in tokens), f"bad token output: {tokens}"
stats = [r["stats"] for r in rows if "stats" in r]
assert stats and stats[0]["decode/tokens_per_sec"] > 0, f"no decode rate: {stats}"
print("generate smoke: OK", tokens)
EOF
JAX_PLATFORMS=cpu python -m llm_training_tpu evaluate \
    --config config/examples/smoke/cpu-smoke.yaml "run_root=${SMOKE_ROOT}" \
    --limit-batches 2
JAX_PLATFORMS=cpu python -m llm_training_tpu report "${SMOKE_ROOT}/smoke/cpu-smoke" \
    | tee "${SMOKE_ROOT}/report_infer.log"
grep -q "== Inference ==" "${SMOKE_ROOT}/report_infer.log"
grep -q "decode_tokens_per_sec" "${SMOKE_ROOT}/report_infer.log"
grep -q "perplexity" "${SMOKE_ROOT}/report_infer.log"

# serving gate (docs/serving.md): synthetic overlapping traffic through the
# real `serve` CLI + JSONL protocol. The loadgen itself exits nonzero when
# any request fails to terminate, a done arrives with no streamed chunks,
# the pool leaks blocks at exit, or arrivals never overlapped
# (serve/peak_running < 2 — i.e. continuous batching demonstrably admitted
# a request while another was mid-decode); then the merged serve/* gauges
# must render as report's == Serving == section
echo "== precommit: serve smoke (continuous-batching loadgen -> report) =="
# --metrics-port: the loadgen scrapes the child's /metrics exporter
# throughout and cross-checks serve/requests_completed + queue-depth
# gauges against its own client census at the all-terminal moment —
# exporter/engine drift exits nonzero (docs/observability.md#live-telemetry).
# Ports are OS-assigned free ones (bind-then-release), never hardcoded: a
# stale holder on a fixed port would fail a healthy commit — or worse,
# answer scrapes for the wrong process
free_port() {
    python -c 'from llm_training_tpu.telemetry.exporter import find_free_port; print(find_free_port())'
}
SERVE_METRICS_PORT=$(free_port)
JAX_PLATFORMS=cpu python scripts/serve_loadgen.py \
    --config config/examples/smoke/cpu-smoke.yaml \
    --requests 4 --max-new-tokens 16 \
    --metrics-port "${SERVE_METRICS_PORT}" \
    --out "${SMOKE_ROOT}/serve_loadgen.json" \
    "run_root=${SMOKE_ROOT}" --max-batch 2 --max-model-len 64 \
    --prefill-chunk 4 --eos-token-id -1 \
    | tee "${SMOKE_ROOT}/serve_smoke.log"
python - "${SMOKE_ROOT}/serve_loadgen.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
scrape = doc["scrape"]
assert scrape["scrapes_ok"] >= 1, scrape
assert not scrape["parse_errors"], scrape["parse_errors"]
final = scrape["final"]
assert final["llmt_serve_requests_completed"] == doc["completed"], (final, doc)
assert "llmt_serve_ttft_p50_ms" in final and "llmt_serve_tpot_p50_ms" in final
print("serve scrape cross-check: OK —", scrape["scrapes_ok"], "scrapes")
EOF
JAX_PLATFORMS=cpu python -m llm_training_tpu report "${SMOKE_ROOT}/smoke/cpu-smoke" \
    | tee "${SMOKE_ROOT}/report_serve.log"
grep -q "== Serving ==" "${SMOKE_ROOT}/report_serve.log"
grep -q "ttft" "${SMOKE_ROOT}/report_serve.log"

# serve-drain gate (docs/serving.md#resilience): the full drain + supervised
# replay + watchdog story, end to end through the real CLI. Leg 1: chaos
# SIGTERM mid-stream (+ a malformed flood the error boundary must answer)
# -> graceful drain (timeout 0 forces journaling) -> exit 75 -> `supervise
# --child serve` relaunch replays the journal -> the loadgen's terminal
# contract holds: every request exactly ONE done chunk across the boundary,
# zero pool-block leaks. Leg 2: chaos stall wedges an engine step -> the
# serve watchdog flight-dumps the trace ring and SIGABRTs -> another
# supervised relaunch replays -> same contract, and the flight dump exists.
echo "== precommit: serve drain (SIGTERM -> 75 -> replay; stall -> watchdog -> replay) =="
JAX_PLATFORMS=cpu LLMT_CHAOS_SERVE_SIGTERM_STEP=6 LLMT_CHAOS_SERVE_MALFORMED_FLOOD=2 \
    python scripts/serve_loadgen.py \
    --config config/examples/smoke/cpu-smoke.yaml \
    --requests 4 --max-new-tokens 16 --supervised \
    --deadline-ms 60000 --deadline-every 2 \
    --out "${SMOKE_ROOT}/serve_drain.json" \
    "run_root=${SMOKE_ROOT}" --max-batch 2 --max-model-len 64 \
    --prefill-chunk 4 --eos-token-id -1 --drain-timeout-s 0 \
    | tee "${SMOKE_ROOT}/serve_drain.log"
grep -q '"drain"' "${SMOKE_ROOT}/smoke/cpu-smoke/trace.jsonl" \
    || { echo "no drain event reached trace.jsonl"; exit 1; }
grep -q '"rc": 75' "${SMOKE_ROOT}/smoke/cpu-smoke/supervisor.jsonl" \
    || { echo "supervisor never saw the resumable drain exit"; exit 1; }
python - "${SMOKE_ROOT}/serve_drain.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert not doc["errors"], doc["errors"]
assert doc["engine"]["serve/replayed_requests"] >= 1, \
    f"relaunch replayed nothing: {doc['engine']}"
assert doc["error_chunks"] >= 2, f"malformed flood unanswered: {doc}"
print("serve drain: OK —", int(doc["engine"]["serve/replayed_requests"]),
      "replayed,", doc["terminal_reasons"])
EOF
# --metrics-port on the stall leg: while the chaos stall wedges the
# engine, /healthz must flip 503 BEFORE the 5s watchdog SIGABRTs — the
# scraper records the red window (docs/observability.md#live-telemetry)
STALL_METRICS_PORT=$(free_port)
JAX_PLATFORMS=cpu LLMT_CHAOS_SERVE_STALL_STEP=4 \
    python scripts/serve_loadgen.py \
    --config config/examples/smoke/cpu-smoke.yaml \
    --requests 3 --max-new-tokens 12 --supervised \
    --metrics-port "${STALL_METRICS_PORT}" \
    --out "${SMOKE_ROOT}/serve_stall.json" \
    "run_root=${SMOKE_ROOT}" --max-batch 2 --max-model-len 64 \
    --prefill-chunk 4 --eos-token-id -1 --drain-timeout-s 0 \
    --watchdog-timeout-s 5 \
    | tee "${SMOKE_ROOT}/serve_stall.log"
ls "${SMOKE_ROOT}"/smoke/cpu-smoke/trace-flight-hang-*.jsonl >/dev/null 2>&1 \
    || { echo "watchdog stall produced no trace flight dump"; exit 1; }
python - "${SMOKE_ROOT}/serve_stall.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert not doc["errors"], doc["errors"]
assert doc["engine"]["serve/replayed_requests"] >= 1, doc["engine"]
assert doc["scrape"]["unhealthy_observed"], (
    "healthz never flipped red during the stall: %s" % doc["scrape"])
print("serve stall: OK —", doc["terminal_reasons"],
      "| healthz flipped red before the watchdog fired")
EOF

# trace gate (docs/observability.md#tracing): the fit (train track) and the
# serve loadgen (request tracks) both appended to the run dir's
# trace.jsonl; `trace` must export valid Chrome-trace JSON with both
# layers present, report must render == Trace ==, and report --format json
# must emit the machine-readable schema
echo "== precommit: trace smoke (fit+serve spans -> Perfetto export -> report) =="
JAX_PLATFORMS=cpu python -m llm_training_tpu trace \
    "${SMOKE_ROOT}/smoke/cpu-smoke" --out "${SMOKE_ROOT}/trace_export.json"
python - "${SMOKE_ROOT}/trace_export.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
events = doc["traceEvents"]
assert isinstance(events, list) and events, "no trace events exported"
for e in events:
    assert {"ph", "pid", "tid", "name"} <= set(e), f"bad chrome event: {e}"
spans = [e for e in events if e["ph"] == "X"]
assert spans and all("ts" in e and "dur" in e for e in spans), "no complete spans"
names = {e["name"] for e in events}
assert "train_step" in names, f"no training track: {sorted(names)}"
assert {"queue", "prefill", "decode"} <= names, f"no request lifecycle: {sorted(names)}"
req_tracks = {e["tid"] for e in events if e.get("args", {}).get("request_id")}
assert req_tracks, "no per-request tracks"
print("trace export: OK", len(events), "events,", len(req_tracks), "request tracks")
EOF
grep -q "== Trace ==" "${SMOKE_ROOT}/report_serve.log"
JAX_PLATFORMS=cpu python -m llm_training_tpu report "${SMOKE_ROOT}/smoke/cpu-smoke" \
    --format json > "${SMOKE_ROOT}/report.json"
python - "${SMOKE_ROOT}/report.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema_version"] == 2, doc.get("schema_version")
for key in ("training", "goodput", "serving", "slo", "trace", "telemetry"):
    assert key in doc, f"report json missing {key!r}"
assert doc["goodput"]["goodput/total_s"] > 0
assert doc["trace"]["events"] > 0 and doc["trace"]["requests_completed"] > 0
assert doc["serving"]["serve/requests_completed"] > 0
print("report json: OK", doc["trace"]["events"], "trace events")
EOF

# exporter-smoke gate (docs/observability.md#live-telemetry): a cpu-smoke
# fit with the exporter armed is scraped MID-FIT (/metrics must be
# parse-valid Prometheus with goodput + slo series, /healthz 200 for a
# slow-but-alive fit), while the slow-step chaos hook injects a sustained
# slow regime the SLO burn-rate monitor must page on — asserting the
# breach counter in telemetry.jsonl, a trace-flight-slo-*.jsonl ring
# dump, and report's == SLO == section
echo "== precommit: exporter smoke (live scrape + chaos SLO breach) =="
python scripts/exporter_smoke.py "${SMOKE_ROOT}/exporter-smoke"

# profile-smoke gate (docs/observability.md#profiling): the same chaos
# slow-step breach on a virtual fsdp=2 mesh must now ALSO fire the device
# profile trigger — a jax.profiler capture whose profile-<tag>/ trace dir
# + manifest carry the SAME tag as the breach's flight dump, the next
# breach refused inside the profile cooldown (profile/suppressed), the
# compiled step's attr/ comm-fraction gauges and the HBM timeline in
# telemetry.jsonl, and report's == Profiling == section (text + json)
echo "== precommit: profile smoke (triggered device capture + attribution) =="
python scripts/profile_smoke.py "${SMOKE_ROOT}/profile-smoke"

# fleet-smoke gate (docs/observability.md#fleet): two serve replicas under
# one discovery dir — the aggregator census must equal the summed client
# censuses with terminals exactly-once fleet-wide; `trace --merge` must
# render both replicas' request tracks in ONE wall-aligned Perfetto file;
# and a SIGKILLed replica must flip the fleet verdict red within one
# scrape interval with /fleetz naming its stale card
echo "== precommit: fleet smoke (2-replica census + kill-flip + trace merge) =="
python scripts/fleet_smoke.py "${SMOKE_ROOT}/fleet-smoke" \
    "${SMOKE_ROOT}/smoke/cpu-smoke"

# router-smoke gate (docs/serving.md#router): the fleet resilience tier —
# two serve replicas behind the `route` CLI; a SIGKILLed replica
# mid-stream must fail over with exactly-once terminals (>= 1
# router/replays, report's == Router == line green) and the fleet verdict
# green again once the replacement replica arms; a chaos-blackholed
# submission must hedge onto the second replica and deliver exactly one
# terminal
echo "== precommit: router smoke (failover exactly-once + hedged blackhole) =="
python scripts/router_smoke.py "${SMOKE_ROOT}/router-smoke" \
    "${SMOKE_ROOT}/smoke/cpu-smoke"

# rl-smoke gate (docs/post-training.md): the on-policy GRPO loop riding
# the serving engine — a tiny policy must STRICTLY improve mean reward
# over 10 rounds (rollouts through the real engine scheduler, behavior
# logprobs, fused weight sync every round); a chaos SIGTERM mid-rollout
# must journal in-flight rollouts and exit 75, and the relaunch must
# replay+adopt them (host-oracle sync mode) and finish; the run dir must
# render report's == RL == section text and JSON
echo "== precommit: rl smoke (GRPO reward improvement + SIGTERM resume) =="
python scripts/rl_smoke.py "${SMOKE_ROOT}/rl-smoke"

# NaN-provenance + auto-recovery gates: a forced non-finite micro-fit must
# name the offending layer path in the NonFiniteLossError AND write an
# anomaly-<step>.json dump; then a chaos-injected NaN with
# trainer.resilience.recovery set must self-heal IN-PROCESS (rollback to
# the last checkpoint + skip the poisoned window, no relaunch) with
# resilience/rollbacks == 1 and a "== Recovery ==" report section
echo "== precommit: forced-NaN anomaly dump + auto-recovery smoke =="
JAX_PLATFORMS=cpu python scripts/force_nan_smoke.py "${SMOKE_ROOT}/nan-smoke"

# resilience gate (docs/resilience.md): chaos SIGTERM mid-fit -> committed
# emergency checkpoint + resumable exit code + loss-exact resume; injected
# checkpoint I/O error retried; corrupt latest checkpoint falls back on
# restore; injected loss spike exits with exactly 77 (the documented
# divergence code); a child SIGKILLed mid-fit is relaunched by `supervise`
# and completes; ELASTIC: a child killed on 8 simulated devices resumes on
# 4 (LLMT_CHAOS_DEVICES=8,4), the planner scales data 8->4, losses match a
# clean shrunken-topology run, and report renders == Elastic == with
# goodput-per-dollar; a forced stall produces the watchdog's stack dump
echo "== precommit: kill-and-resume + supervise + elastic smoke =="
JAX_PLATFORMS=cpu python scripts/crash_resume_smoke.py "${SMOKE_ROOT}/resilience"

# durability gate (docs/resilience.md#durability): hashed manifests at save
# commit + async mirror; a chaos byte-flip in the newest primary step must
# be NAMED by `ckpt verify` (exit 1), the relaunch must heal the step from
# the mirror and resume with losses EXACTLY equal to the clean same-seed
# run, a SIGKILL inside the force-save swap window must leave a restorable
# staged copy, and the manifest+drain critical-path cost must stay < 2% of
# wall
echo "== precommit: durability smoke (manifests + mirror heal + chaos corruption) =="
JAX_PLATFORMS=cpu python scripts/durability_smoke.py "${SMOKE_ROOT}/durability"

echo "== precommit: OK =="
