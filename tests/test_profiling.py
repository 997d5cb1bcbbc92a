"""Device-plane observability tests: the ProfileTrigger capture service,
compiled-program compute/comm attribution, the per-device HBM rollup +
timeline, the /profilez endpoint, and the report profiling section
(docs/observability.md#profiling, #device-plane).

The trigger's request surface is jax-free host code; the capture side is
exercised against a monkeypatched `jax.profiler` (no real traces — the
real capture is the profile-smoke gate's job). Attribution parses
synthetic HLO text: on a single-device CPU backend the compiled step
contains no collectives, so the regex walk is pinned directly.
"""

from __future__ import annotations

import json
import threading
import urllib.error
import urllib.request

import pytest

from llm_training_tpu.telemetry.device import (
    HBMTimeline,
    _gauges_from_stats,
    compiled_attribution_gauges,
    hbm_gauges,
    parse_hlo_collectives,
)
from llm_training_tpu.telemetry.exporter import MetricsExporter, profile_main
from llm_training_tpu.telemetry.profiling import (
    ProfileTrigger,
    build_profile_trigger,
    get_profile_trigger,
    sanitize_tag,
    set_profile_trigger,
)
from llm_training_tpu.telemetry.registry import TelemetryRegistry
from llm_training_tpu.telemetry.report import (
    _profiling_section,
    _profiling_summary,
)
from llm_training_tpu.telemetry.trace import TraceRecorder, set_tracer


class _FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class _ProfilerRecorder:
    """Patches jax.profiler start/stop so capture transitions are pinned
    without writing real traces (same idiom as tests/test_callbacks.py)."""

    def __init__(self, monkeypatch, fail_start: bool = False):
        import jax

        self.calls: list[tuple] = []

        def start(trace_dir, *a, **k):
            if fail_start:
                raise RuntimeError("profiler backend unavailable")
            self.calls.append(("start", trace_dir))

        monkeypatch.setattr(jax.profiler, "start_trace", start)
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: self.calls.append(("stop",))
        )


# ------------------------------------------------------ request admission


def test_request_budget_cooldown_and_counters(tmp_path):
    clock = _FakeClock()
    registry = TelemetryRegistry()
    trigger = ProfileTrigger(
        run_dir=tmp_path, registry=registry,
        budget=2, cooldown_s=60.0, clock=clock,
    )
    assert trigger.request("first")["accepted"]
    # a second request while the first is still pending: busy (jax forbids
    # nested start_trace — one window at a time is the invariant)
    second = trigger.request("second")
    assert not second["accepted"] and second["reason"] == "busy"
    # consume the pending window so admission state, not the open window,
    # drives the next refusals
    trigger._pending = None
    within = trigger.request("third")
    assert not within["accepted"] and within["reason"] == "cooldown"
    clock.t += 61.0
    assert trigger.request("fourth")["accepted"]
    trigger._pending = None
    trigger._captures = 2  # budget spent
    clock.t += 61.0
    spent = trigger.request("fifth")
    assert not spent["accepted"] and spent["reason"] == "budget"
    snap = registry.snapshot()
    assert snap["profile/requested"] == 5.0
    assert snap["profile/suppressed"] == 3.0
    assert snap["profile/suppressed/busy"] == 1.0
    assert snap["profile/suppressed/cooldown"] == 1.0
    assert snap["profile/suppressed/budget"] == 1.0


def test_budget_zero_refuses_everything():
    trigger = ProfileTrigger(budget=0, cooldown_s=0.0)
    result = trigger.request("never")
    assert not result["accepted"] and result["reason"] == "budget"


def test_concurrent_requests_admit_exactly_one():
    trigger = ProfileTrigger(budget=8, cooldown_s=0.0)
    results: list[dict] = []
    barrier = threading.Barrier(8)

    def fire(i):
        barrier.wait()
        results.append(trigger.request(f"race-{i}"))

    threads = [threading.Thread(target=fire, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    accepted = [r for r in results if r["accepted"]]
    assert len(accepted) == 1
    assert all(r["reason"] == "busy" for r in results if not r["accepted"])


def test_tag_sanitization():
    assert sanitize_tag("slo/train/step_time_p99_s #1") == "slo-train-step_time_p99_s-1"
    assert sanitize_tag("///") == "capture"


# ------------------------------------------------------ capture lifecycle


def test_poll_drives_start_stop_and_manifest(tmp_path, monkeypatch):
    rec = _ProfilerRecorder(monkeypatch)
    registry = TelemetryRegistry()
    trigger = ProfileTrigger(
        run_dir=tmp_path, registry=registry,
        budget=4, cooldown_s=0.0, window_steps=2,
    )
    assert trigger.request("slo-step-1", source="slo")["accepted"]
    trigger.poll(5)  # starts: window [5, 7)
    assert rec.calls == [("start", str(tmp_path / "profile-slo-step-1"))]
    assert trigger.status()["active"] == "slo-step-1"
    trigger.poll(6)  # inside the window: no transition
    assert len(rec.calls) == 1
    trigger.poll(7)  # stop boundary
    assert rec.calls[-1] == ("stop",)
    assert trigger.status()["active"] is None
    manifest = json.loads((tmp_path / "profile-slo-step-1.json").read_text())
    assert manifest["tag"] == "slo-step-1"
    assert manifest["source"] == "slo"
    assert manifest["start_step"] == 5 and manifest["stop_step"] == 7
    assert (tmp_path / "profile-slo-step-1").is_dir()
    snap = registry.snapshot()
    assert snap["profile/captures"] == 1.0
    assert snap["profile/last_capture_step"] == 5.0
    history = trigger.status()["history"]
    assert [h["tag"] for h in history] == ["slo-step-1"]


def test_failed_start_clears_active_and_counts_error(tmp_path, monkeypatch):
    _ProfilerRecorder(monkeypatch, fail_start=True)
    registry = TelemetryRegistry()
    trigger = ProfileTrigger(run_dir=tmp_path, registry=registry, cooldown_s=0.0)
    assert trigger.request("doomed")["accepted"]
    trigger.poll(1)
    assert trigger.status()["active"] is None
    assert registry.snapshot()["profile/errors"] == 1.0
    # the trigger recovers: a later request can still capture
    assert trigger.request("retry")["accepted"]


def test_scheduled_window_clamps_and_drops_past_windows(tmp_path, monkeypatch):
    rec = _ProfilerRecorder(monkeypatch)
    trigger = ProfileTrigger(run_dir=tmp_path, budget=4, cooldown_s=0.0)
    # clamped to max_steps: [3, 5) -> [3, 4)
    assert trigger.schedule(3, 2, max_steps=4)
    # zero after clamping: refused up front, like the old callback
    assert not trigger.schedule(5, 2, max_steps=5)
    trigger.poll(3)
    assert rec.calls == [("start", str(tmp_path / "profile-window-3"))]
    trigger.poll(4)
    assert rec.calls[-1] == ("stop",)
    # a resume landing PAST a scheduled window must drop it silently,
    # never open a trace only teardown would close
    assert trigger.schedule(6, 2)
    trigger.poll(50)
    assert len(rec.calls) == 2
    assert trigger.status()["scheduled"] == []


def test_scheduled_window_honors_explicit_trace_dir(tmp_path, monkeypatch):
    rec = _ProfilerRecorder(monkeypatch)
    trigger = ProfileTrigger(run_dir=tmp_path, cooldown_s=0.0)
    explicit = tmp_path / "bench-trace"
    assert trigger.schedule(2, 1, trace_dir=str(explicit))
    trigger.poll(2)
    assert rec.calls == [("start", str(explicit))]


def test_teardown_closes_dangling_capture_and_refuses(tmp_path, monkeypatch):
    rec = _ProfilerRecorder(monkeypatch)
    trigger = ProfileTrigger(run_dir=tmp_path, cooldown_s=0.0)
    trigger.request("dangling")
    trigger.poll(1)
    trigger.teardown()
    assert rec.calls[-1] == ("stop",)
    trigger.teardown()  # idempotent
    assert rec.calls[-1] == ("stop",)
    refused = trigger.request("late")
    assert not refused["accepted"] and refused["reason"] == "torn-down"
    # the teardown-stopped capture still writes its manifest
    assert (tmp_path / "profile-dangling.json").exists()


def test_process_global_publication():
    set_profile_trigger(None)
    assert get_profile_trigger() is None
    trigger = build_profile_trigger(budget=1)
    try:
        assert get_profile_trigger() is trigger
    finally:
        set_profile_trigger(None)


# ----------------------------------------------------- /profilez endpoint


@pytest.fixture
def exporter_factory():
    started = []

    def make(**kwargs) -> MetricsExporter:
        exporter = MetricsExporter(0, **kwargs)
        assert exporter.start()
        started.append(exporter)
        return exporter

    yield make
    for exporter in started:
        exporter.stop()


def test_profilez_round_trip_and_refusal(exporter_factory):
    registry = TelemetryRegistry()
    trigger = ProfileTrigger(registry=registry, budget=4, cooldown_s=0.0)
    exporter = exporter_factory(registry=registry, profile=trigger)
    url = f"http://127.0.0.1:{exporter.port}/profilez?tag=operator-look"
    with urllib.request.urlopen(url, timeout=5.0) as resp:
        assert resp.status == 200
        body = json.loads(resp.read().decode())
    assert body["accepted"] and body["tag"] == "operator-look"
    assert body["status"]["pending"] == "operator-look"
    # second request while the first is pending: 429, the refusal IS the
    # budget/cooldown/busy machinery answering honestly
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(url, timeout=5.0)
    assert err.value.code == 429
    refused = json.loads(err.value.read().decode())
    assert not refused["accepted"] and refused["reason"] == "busy"


def test_profilez_without_trigger_is_404(exporter_factory):
    exporter = exporter_factory(registry=TelemetryRegistry())
    with pytest.raises(urllib.error.HTTPError) as err:
        urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/profilez", timeout=5.0
        )
    assert err.value.code == 404


def test_profile_main_cli(exporter_factory, capsys, monkeypatch):
    monkeypatch.delenv("LLMT_METRICS_PORT", raising=False)
    trigger = ProfileTrigger(budget=4, cooldown_s=0.0)
    exporter = exporter_factory(registry=TelemetryRegistry(), profile=trigger)
    assert profile_main(port=exporter.port, tag="from-cli") == 0
    assert trigger.status()["pending"] == "from-cli"
    # suppressed (busy) maps to exit 3, unreachable to exit 2
    assert profile_main(port=exporter.port, tag="again") == 3
    exporter.stop()
    assert profile_main(port=exporter.port, tag="dead", timeout_s=0.5) == 2
    assert profile_main(port=None) == 2  # no port resolvable
    capsys.readouterr()


# ------------------------------------------------------------ attribution

_SYNTHETIC_HLO = """\
HloModule train_step

fused_computation {
  ROOT mul = f32[128,64] multiply(f32[128,64] a, f32[128,64] b)
}

ENTRY main {
  ar = f32[1024,8] all-reduce(f32[1024,8] g), replica_groups={{0,1,2,3}}, to_apply=add
  ag.s = (bf16[256], bf16[512]) all-gather-start(bf16[256] p), replica_groups=[4,2]<=[8], dimensions={0}
  ag.d = bf16[512] all-gather-done((bf16[256], bf16[512]) ag.s)
  rs = f16[64,32] reduce-scatter(f16[128,32] h), replica_groups={{0,1},{2,3}}, dimensions={0}
  cp = u8[16] collective-permute(u8[16] x), source_target_pairs={{0,1},{1,0}}
  no = f32[4] add(f32[4] y, f32[4] z)
}
"""


def test_parse_hlo_collectives_kinds_groups_and_payloads():
    colls = parse_hlo_collectives(_SYNTHETIC_HLO)
    by_kind = {c["kind"]: c for c in colls}
    assert len(colls) == 4  # the -done half and plain adds never match
    assert by_kind["all_reduce"]["bytes"] == 1024 * 8 * 4
    assert by_kind["all_reduce"]["group_size"] == 4
    # tuple result shape: every element counts; iota replica_groups parse
    assert by_kind["all_gather"]["bytes"] == (256 + 512) * 2
    assert by_kind["all_gather"]["group_size"] == 2
    assert by_kind["reduce_scatter"]["bytes"] == 64 * 32 * 2
    assert by_kind["reduce_scatter"]["group_size"] == 2
    # source_target_pairs form says nothing about group cardinality
    assert by_kind["collective_permute"]["group_size"] is None
    assert by_kind["collective_permute"]["bytes"] == 16


class _FakeCompiled:
    def __init__(self, hlo: str | None, cost: dict | None = None):
        self._hlo = hlo
        self._cost = cost or {}

    def cost_analysis(self):
        return self._cost

    def as_text(self):
        if self._hlo is None:
            raise RuntimeError("no HLO")
        return self._hlo


def test_compiled_attribution_gauges_split_by_axis():
    compiled = _FakeCompiled(
        _SYNTHETIC_HLO, {"flops": 1.0e9, "bytes accessed": 1.0e6}
    )
    gauges = compiled_attribution_gauges(
        compiled, mesh_axes={"data": 2, "fsdp": 4}
    )
    total = (1024 * 8 * 4) + (256 + 512) * 2 + 64 * 32 * 2 + 16
    assert gauges["attr/flops_per_step"] == 1.0e9
    assert gauges["attr/collective_bytes_per_step"] == total
    assert gauges["attr/collective_ops"] == 4.0
    assert gauges["attr/comm_fraction"] == pytest.approx(
        min(1.0, total / 1.0e6)
    )
    # group size 4 -> fsdp, group size 2 -> data; the pair-form permute
    # cannot be matched on a two-axis mesh and stays unattributed
    assert gauges["attr/mesh/fsdp/collective_bytes"] == 1024 * 8 * 4
    assert gauges["attr/mesh/data/collective_bytes"] == (
        (256 + 512) * 2 + 64 * 32 * 2
    )
    assert gauges["attr/mesh/unattributed/collective_bytes"] == 16


def test_attribution_single_axis_mesh_claims_everything():
    gauges = compiled_attribution_gauges(
        _FakeCompiled(_SYNTHETIC_HLO), mesh_axes={"data": 1, "fsdp": 8}
    )
    # one non-trivial axis: even unmatched group sizes belong to it
    assert "attr/mesh/unattributed/collective_bytes" not in gauges
    assert gauges["attr/mesh/fsdp/collective_bytes"] == gauges[
        "attr/collective_bytes_per_step"
    ]


def test_attribution_no_collectives_publishes_stable_zero_record():
    gauges = compiled_attribution_gauges(
        _FakeCompiled("ENTRY main { ROOT a = f32[2] add(f32[2] x, f32[2] y) }",
                      {"flops": 10.0, "bytes accessed": 100.0}),
        mesh_axes={"data": 1, "fsdp": 1},
    )
    assert gauges["attr/comm_fraction"] == 0.0
    assert gauges["attr/collective/all_reduce_bytes"] == 0.0
    assert gauges["attr/collective_ops"] == 0.0


def test_attribution_without_hlo_text_returns_nothing():
    assert compiled_attribution_gauges(_FakeCompiled(None)) == {}


# ------------------------------------------------------- per-device HBM


def _stats(in_use, limit=0, peak=None):
    stats = {"bytes_in_use": in_use, "peak_bytes_in_use": peak or in_use}
    if limit:
        stats["bytes_limit"] = limit
    return stats


def test_hbm_rollup_reports_worst_device_and_per_device_gauges():
    per_device = [
        (0, _stats(4.0e9, limit=16.0e9)),
        (1, _stats(12.0e9, limit=16.0e9)),  # the one that OOMs first
    ]
    gauges = _gauges_from_stats(per_device)
    # legacy flat keys = the WORST device, coherently
    assert gauges["hbm/bytes_in_use"] == 12.0e9
    assert gauges["hbm/bytes_limit"] == 16.0e9
    assert gauges["hbm/worst_device"] == 1.0
    assert gauges["hbm/devices"] == 2.0
    assert gauges["hbm/mean_bytes_in_use"] == 8.0e9
    assert gauges["hbm/device0/bytes_in_use"] == 4.0e9
    assert gauges["hbm/device1/bytes_in_use"] == 12.0e9
    assert "hbm/host_fallback" not in gauges


def test_hbm_gauges_fall_back_to_host_rss_on_cpu():
    gauges = hbm_gauges()  # CPU backend: no allocator stats
    assert gauges.get("hbm/host_fallback") == 1.0
    assert gauges["hbm/bytes_in_use"] > 0


def test_hbm_timeline_records_bound_and_highwater(tmp_path, monkeypatch):
    samples = [
        [(0, _stats(4.0e9, limit=16.0e9)), (1, _stats(5.0e9, limit=16.0e9))],
        [(0, _stats(15.0e9, limit=16.0e9)), (1, _stats(5.0e9, limit=16.0e9))],
        [(0, _stats(15.1e9, limit=16.0e9)), (1, _stats(5.0e9, limit=16.0e9))],
        [(0, _stats(3.0e9, limit=16.0e9)), (1, _stats(5.0e9, limit=16.0e9))],
    ]
    feed = iter(samples)
    monkeypatch.setattr(
        "llm_training_tpu.telemetry.device.local_device_memory_stats",
        lambda: next(feed),
    )
    tracer = TraceRecorder(capacity=64)
    previous = set_tracer(tracer)
    try:
        registry = TelemetryRegistry()
        timeline = HBMTimeline(
            run_dir=tmp_path, registry=registry,
            max_records=3, highwater_frac=0.9, clock=lambda: 1.0,
        )
        gauges = timeline.sample(1)
        assert gauges["hbm/worst_device"] == 1.0
        assert gauges["hbm_timeline/records"] == 1.0
        timeline.sample(2)  # device 0 crosses 90% -> ONE instant
        timeline.sample(3)  # still over: no re-fire
        gauges = timeline.sample(4)  # back below: re-armed, capped file
        assert gauges["hbm_timeline/highwater_events"] == 1.0
        assert gauges["hbm_timeline/truncated"] == 1.0
        assert registry.snapshot()["hbm_timeline/highwater_events"] == 1.0
        instants = [
            e for e in tracer.snapshot() if e.get("name") == "highwater"
        ]
        assert len(instants) == 1
        assert instants[0]["args"]["device"] == 0
        lines = (tmp_path / "hbm.jsonl").read_text().splitlines()
        assert len(lines) == 3  # the bound held
        first = json.loads(lines[0])
        assert first["step"] == 1
        assert {d["id"] for d in first["devices"]} == {0, 1}
    finally:
        set_tracer(previous)


def test_hbm_timeline_host_fallback_record(tmp_path):
    timeline = HBMTimeline(run_dir=tmp_path, clock=lambda: 2.0)
    gauges = timeline.sample(7)  # CPU: host-RSS fallback
    assert gauges["hbm/host_fallback"] == 1.0
    record = json.loads((tmp_path / "hbm.jsonl").read_text())
    assert record["host_fallback"] is True and record["step"] == 7


# -------------------------------------------------------- report section


def test_report_profiling_section_renders(tmp_path):
    (tmp_path / "profile-slo-x-1.json").write_text(json.dumps({
        "tag": "slo-x-1", "source": "slo", "start_step": 5, "stop_step": 7,
        "duration_s": 0.42, "trace_dir": str(tmp_path / "profile-slo-x-1"),
    }))
    telemetry = {
        "profile/requested": 3.0, "profile/captures": 1.0,
        "profile/suppressed": 2.0, "attr/comm_fraction": 0.25,
        "attr/flops_per_step": 1.0e9,
        "attr/collective_bytes_per_step": 4096.0,
        "attr/collective_ops": 2.0,
        "attr/mesh/fsdp/collective_bytes": 4096.0,
        "hbm_timeline/records": 12.0, "hbm_timeline/highwater_events": 1.0,
    }
    summary = _profiling_summary(tmp_path, telemetry)
    assert summary is not None
    assert summary["captures"][0]["tag"] == "slo-x-1"
    text = "\n".join(_profiling_section(summary))
    assert "== Profiling ==" in text
    assert "captures: 1 (requested 3, suppressed 2)" in text
    assert "profile-slo-x-1.json: steps 5..7, 0.42s (slo)" in text
    assert "comm fraction: 25.0% of bytes accessed" in text
    assert "mesh fsdp: 4,096 B" in text
    assert "hbm timeline: 12 record(s), 1 high-water crossing(s)" in text


def test_report_profiling_section_omitted_when_run_never_profiled(tmp_path):
    assert _profiling_summary(tmp_path, {"loss": 1.0}) is None
    assert _profiling_section(None) == []


def test_report_profiling_torn_manifest_degrades_to_error_line(tmp_path):
    (tmp_path / "profile-torn.json").write_text('{"tag": "torn", "sta')
    (tmp_path / "profile-empty.json").write_text("{}")
    summary = _profiling_summary(tmp_path, {})
    text = "\n".join(_profiling_section(summary))
    assert "profile-torn.json: unreadable manifest" in text
    # parsed-but-incomplete manifest: its own honest line, never the section
    assert "profile-empty.json: unreadable manifest — malformed fields" in text


# -------------------------------------------- ProfilerCallback absorption


def test_profiler_callback_exposes_window_and_goes_passive(monkeypatch):
    import jax

    from llm_training_tpu.callbacks import ProfilerCallback, ProfilerCallbackConfig

    calls: list = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    cb = ProfilerCallback(ProfilerCallbackConfig(start_step=2, num_steps=3))
    assert cb.profile_window() == (2, 3, None)
    cb._absorbed = True  # what the trainer sets after trigger.schedule()
    for step in range(1, 7):
        cb.on_train_step(None, step)
    assert calls == []  # the trigger owns the capture now
    cb.teardown()


def test_profiler_callback_standalone_resolves_default_dir(monkeypatch):
    import jax

    from llm_training_tpu.callbacks import ProfilerCallback, ProfilerCallbackConfig
    from llm_training_tpu.callbacks.profiler import DEFAULT_TRACE_DIR

    calls: list = []
    monkeypatch.setattr(
        jax.profiler, "start_trace", lambda d: calls.append(("start", d))
    )
    monkeypatch.setattr(
        jax.profiler, "stop_trace", lambda: calls.append(("stop",))
    )
    cb = ProfilerCallback(ProfilerCallbackConfig(start_step=1, num_steps=1))
    cb.on_train_step(None, 1)
    cb.on_train_step(None, 2)
    # unset trace_dir resolves to the standalone default AND is written
    # back so callers read the actual capture location off the config
    assert cb.config.trace_dir == DEFAULT_TRACE_DIR
    assert calls == [("start", DEFAULT_TRACE_DIR), ("stop",)]


def test_parse_hlo_kernels_names_the_mosaic_kernels():
    """The compiled-program walk that lets a run OBSERVE its Pallas kernels
    (attr/kernel/<name> gauges; chip_smoke.py asserts on them)."""
    from llm_training_tpu.telemetry.device import parse_hlo_kernels

    text = (
        '  %a = bf16[4,8,4,128] custom-call(%t, %l), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(decode_step)/Llama/layers/paged_decode/pallas_call" '
        'stack_frame_id=4}, backend_config={}\n'
        '  %b = bf16[32,8192,128] custom-call(%q), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(train_step)/transpose(jvp(Llama))/flash_bwd_dq/pallas_call"}\n'
        '  %c = bf16[32,8192,128] custom-call(%q), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(fn)/transpose(jvp(flash_bwd_dq))/pallas_call"}\n'
        '  %d = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", metadata={}\n'
        '  %e = f32[8] custom-call(%x), custom_call_target="Sharding"\n'
        '  %f = f32[8] all-reduce(%x), replica_groups={{0,1}}\n'
    )
    assert parse_hlo_kernels(text) == {"paged_decode": 1, "flash_bwd_dq": 2, "unnamed": 1}
    assert parse_hlo_kernels("") == {}
