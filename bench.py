"""Benchmark: CLM train-step throughput + MFU on the attached TPU chip(s).

Multi-stage harness: round r04 died inside the flash backward and r05 hung
at backend init, leaving zero perf signal for two rounds — so each stage
runs in a SUPERVISED CHILD process (the PR 3/PR 5 `Supervisor` +
`HangWatchdog` machinery), one after another, so one process holds the
chip at a time:

  backend_init  prove the jax backend answers at all (the r05 wedge)
  train         the headline MFU fit
  health        A/B fit with the model-health layer on (health_overhead_pct)
  trace         A/B fit with host tracing fully on (trace_overhead_pct)
  exporter      A/B fit with the /metrics exporter scraped at Prometheus
                cadence (exporter_overhead_pct)
  decode        tiny-model generate (decode-program overhead trend)
  serve         tiny-model continuous batching (serve tokens/s/chip + TTFT)

`--check-regression` runs no bench at all: it parses the BENCH_r*.json
rounds under `--bench-dir` (telemetry/perf_ledger.py), prints the
round-over-round trend table, and exits nonzero when the newest
same-backend round regressed MFU / decode tokens-per-sec / serve TTFT
beyond BENCH_REGRESSION_TOLERANCE_PCT.

The PARENT never imports jax — it would hold the chip its children need,
and a hung backend can then only hang a child, which the per-stage timeout
kills (and the fit stages arm the in-process
`HangWatchdog` with action=abort as defense in depth). Each finished stage
emits a partial JSON line `{"stage": ..., "partial": true, ...}` as it
lands, so a crash later in the run cannot erase earlier results; the final
line is the summary record (`"stage": "summary", "partial": false`) with
the per-stage status map — an MFU number (or an honest per-stage error)
lands on the board every round.

Prints the summary as the LAST JSON line: {"metric", "value", "unit",
"vs_baseline", "stage", "partial", "stages", ...extras}. Target
(BASELINE.md): >=55% MFU on Llama-3-8B class workloads; on the single
bench chip we measure a scaled-down Llama with the same arithmetic shape
and report MFU fraction with vs_baseline = mfu / 0.55.

A measurement needs a TPU: outside `--dry` a non-TPU backend, or a
`device_kind` with no entry in the peak table
(`callbacks/time_estimator.py`), fails the backend_init stage and nothing
else runs. `--dry` exercises the full stage/subprocess/partial-JSON
plumbing on CPU with the tiny proxy (wired into scripts/precommit.sh); it
reports counts only — its MFU value is null, because a CPU has no peak.
Every stage child keeps its compiles in the one persistent cache
(`llm_training_tpu/compile_cache.py`), so the four fit stages compile the
shared step once. Chaos hooks for
tests: BENCH_CHAOS_WEDGE=<stage> wedges that stage (killed at its
timeout), BENCH_CHAOS_CRASH=<stage> crashes it; either degrades that one
stage to an error record while the rest of the bench completes. Env
reference: docs/performance.md.

Exit codes: 0 = every attempted stage ok; 1 = the train stage (headline
metric) failed; 2 = train ok but an auxiliary stage failed.
"""

import argparse
import json
import os
import subprocess
import sys
import time

STAGES = (
    "backend_init", "train", "health", "trace", "exporter", "decode", "serve"
)

def _chaos(stage: str) -> None:
    """Env-triggered fault hooks so the degrade-not-die plumbing is testable
    (and tested — precommit wedges a stage on every commit)."""
    if os.environ.get("BENCH_CHAOS_WEDGE") == stage:
        print(f"bench chaos: wedging stage {stage}", file=sys.stderr, flush=True)
        while True:
            time.sleep(60)
    if os.environ.get("BENCH_CHAOS_CRASH") == stage:
        raise SystemExit(f"bench chaos: crashing stage {stage}")


# --------------------------------------------------------------- model setup


def _model_setup(dry: bool):
    """(model_kwargs, seq, batch, steps, warmup) for the fit stages — the
    BENCH_* knob surface is shared so train and health measure the same
    program. `dry` swaps in the tiny plumbing proxy; a real run never
    shrinks (backend_init has already refused a non-TPU backend)."""
    bench_model = os.environ.get("BENCH_MODEL", "8b-layer")
    if bench_model == "8b-layer":
        # north-star layer proxy (the DEFAULT bench): the EXACT Llama-3-8B
        # per-layer shape (h4096, inter 14336, 32q+8kv heads, head_dim 128)
        # at seq 8192 — few layers so params + fp32 Adam masters fit 16G HBM.
        # This measures the matmul/attention mix the 8B runs, per layer;
        # depth only amortizes the (already-small) embed/CE ends. r4 sweep:
        # SELECTIVE remat (save flash_out+lse — attention never recomputes)
        # at batch 3 wins: 0.716-0.721 > B3/full 0.69-0.70 > B4/selective
        # 0.681 > B2/selective 0.674 ≈ B2/full 0.654-0.673 > B4/full 0.632
        # > L3/B1 0.509 (B6/selective and L3/B2 OOM; batch response is
        # non-monotone — XLA scheduling). The h4096 shapes beat the 697M
        # proxy (0.567): bigger MXU tiles win, and selective remat breaks
        # the ~0.75 full-remat convention ceiling.
        model_kwargs = dict(
            vocab_size=32000,
            hidden_size=4096,
            intermediate_size=14336,
            num_hidden_layers=2,
            num_attention_heads=32,
            num_key_value_heads=8,
            head_dim=128,
            max_position_embeddings=8192,
            enable_gradient_checkpointing=True,
            recompute_granularity="selective",
        )
        default_seq, default_batch = 8192, 3
    elif bench_model == "697m":
        # ~700M-param Llama (largest that fits 16G HBM with fp32 Adam masters):
        # hidden 2048 pushes arithmetic intensity toward the 8B north star —
        # attention + elementwise cost shrinks relative to matmul FLOPs as hidden
        # grows, worth +0.018 MFU over the 317M/hidden-1024 proxy (r3 sweep:
        # 697M@B16 0.5665 > 697M@B20 0.5638 > 317M@B64 0.549; B24+ and an
        # 824M/hidden-2560 variant OOM). head_dim 128 is the MXU-native
        # contraction (22% faster than head_dim 64 at equal params, r1).
        model_kwargs = dict(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=8,
            head_dim=128,
            max_position_embeddings=2048,
            # full remat is mandatory on a 16G-HBM chip: no-remat needs 22G even
            # at batch 8; selective (save flash_out+lse) compiles to 15.9-18.5G
            # at batch 56-64 (r3 — XLA fragmentation varies non-monotonically
            # with batch) vs the 15.75G budget. MFU ceiling under the
            # no-recompute-credit convention is ~0.75 with full remat
            enable_gradient_checkpointing=True,
            recompute_granularity="full",
        )
        default_seq, default_batch = 2048, 16
    elif bench_model == "moe":
        # MoE proxy at the 697M-class shape: 8 experts,
        # top-2, expert width sized so TOTAL expert params/layer match the
        # 697M dense MLP (8·3·h·704 == 3·h·5632) — measures the dropless
        # sort/ragged_dot/scatter dispatch against the same memory budget.
        model_kwargs = dict(
            vocab_size=32000,
            hidden_size=2048,
            intermediate_size=5632,
            num_hidden_layers=12,
            num_attention_heads=16,
            num_key_value_heads=8,
            head_dim=128,
            max_position_embeddings=2048,
            num_experts=8,
            num_experts_per_tok=2,
            moe_intermediate_size=704,
            enable_gradient_checkpointing=True,
            recompute_granularity="full",
        )
        default_seq, default_batch = 2048, 16
    else:
        raise SystemExit(
            f"unknown BENCH_MODEL {bench_model!r}; use 8b-layer, 697m or moe"
        )
    # sweep overrides (experiments only; defaults above are the recorded bench)
    remat = os.environ.get("BENCH_REMAT")
    if remat == "none":
        model_kwargs.update(enable_gradient_checkpointing=False)
    elif remat in ("full", "selective"):
        model_kwargs.update(enable_gradient_checkpointing=True,
                            recompute_granularity=remat)
    for env, key in (("BENCH_HIDDEN", "hidden_size"), ("BENCH_INTER", "intermediate_size"),
                     ("BENCH_LAYERS", "num_hidden_layers"), ("BENCH_HEADS", "num_attention_heads"),
                     ("BENCH_KV", "num_key_value_heads")):
        if os.environ.get(env):
            model_kwargs[key] = int(os.environ[env])
    if os.environ.get("BENCH_SCAN"):
        model_kwargs["scan_layers"] = os.environ["BENCH_SCAN"] == "1"
    if os.environ.get("BENCH_MOE_IMPL"):  # ragged | bucketed | dense
        model_kwargs["moe_impl"] = os.environ["BENCH_MOE_IMPL"]
    if os.environ.get("BENCH_MOE_CAP"):  # bucketed per-expert capacity factor
        model_kwargs["moe_capacity_factor"] = float(os.environ["BENCH_MOE_CAP"])
    if dry:  # plumbing run: tiny
        model_kwargs.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                            num_attention_heads=4, num_key_value_heads=2, head_dim=None,
                            vocab_size=2048)

    seq = int(os.environ.get("BENCH_SEQ", 2048 if dry else default_seq))
    batch = 4 if dry else int(os.environ.get("BENCH_BATCH", default_batch))
    model_kwargs["max_position_embeddings"] = max(
        model_kwargs["max_position_embeddings"], seq
    )
    # BENCH_STEPS/BENCH_WARMUP: more measured intervals tighten the A/B
    # overhead stages' medians (the CPU default keeps precommit fast)
    steps = int(os.environ.get("BENCH_STEPS", 3 if dry else 10))
    warmup = int(os.environ.get("BENCH_WARMUP", 1 if dry else 2))
    return model_kwargs, seq, batch, steps, warmup


def _timed_fit(model_kwargs, seq, batch, steps, warmup, dry, health_every=None):
    """One measured fit; `health_every` turns the model-health layer on
    (the A/B for `health_overhead_pct`). Returns (trainer, objective,
    sec_per_step)."""
    import jax
    import numpy as np

    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.optim import OptimConfig
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    objective = CLM(
        CLMConfig(
            model=ModelProvider(
                model_class="llm_training_tpu.models.Llama", model_kwargs=model_kwargs
            ),
            optim=OptimConfig(learning_rate=1e-4, warmup_steps=2),
            ce_chunk_size=int(os.environ.get("BENCH_CE_CHUNK", 2048)),
        )
    )
    n_dev = len(jax.devices())
    datamodule = DummyDataModule(
        DummyDataModuleConfig(
            batch_size=batch * max(1, n_dev), max_length=seq,
            num_samples=batch * max(1, n_dev) * 2, vocab_size=model_kwargs["vocab_size"],
        )
    )

    # Default timing syncs once per step (block on the step's metrics, one
    # batched transfer) and reports the median step latency — the
    # conservative measure: it bills one host round trip per step.
    # BENCH_TIMING=pipelined syncs ONCE after warmup and ONCE at the end,
    # which is the loop `fit` runs (log cadence is sparse, host dispatch
    # overlaps device compute). Which of the two a cell reports is ROADMAP
    # S2's decision; neither has been timed on the chip at HEAD.
    sync_mode = os.environ.get("BENCH_TIMING", "sync") == "sync"
    window = {}
    sync_times = []

    class Timer:
        # the fence fetches a real scalar: a data round trip proves the
        # step completed
        def on_train_step(self, trainer, step):
            if sync_mode:
                jax.device_get(trainer.last_metrics["loss"])
                sync_times.append(time.perf_counter())
            elif step == warmup:
                jax.device_get(trainer.last_metrics["loss"])
                window["t0"] = time.perf_counter()

        def on_step_end(self, trainer, step, metrics):
            # fires on log steps only; by config that is the final step,
            # and metrics arrive here already device_get (i.e. synced)
            if step == steps:
                window["t1"] = time.perf_counter()

    callbacks = [Timer()]
    if os.environ.get("BENCH_PROFILE") and health_every is None:
        # capture a jax.profiler trace window (headline run only)
        from llm_training_tpu.callbacks import ProfilerCallback, ProfilerCallbackConfig

        callbacks.append(ProfilerCallback(ProfilerCallbackConfig(
            trace_dir=os.environ["BENCH_PROFILE"], start_step=4, num_steps=2,
        )))
    # in-fit wedge defense (PR 3 machinery): a stalled step/collective dumps
    # stacks and SIGABRTs the CHILD, which the parent records as a stage
    # error — the parent's timeout is the backstop, this is the fast path.
    # Off under --dry unless explicitly set (interpret-mode steps are slow).
    watchdog_s = float(os.environ.get("BENCH_WATCHDOG", 0 if dry else 600))
    trainer = Trainer(
        TrainerConfig(
            max_steps=steps, log_every_n_steps=steps, mesh=MeshConfig(),
            # BENCH_OFFLOAD=1 parks fp32 mu/nu in pinned host memory (XLA
            # host offloading) — frees 8 bytes/param of HBM for bigger
            # models at a per-step transfer cost (recorded in BASELINE.md)
            offload_optimizer_state=bool(os.environ.get("BENCH_OFFLOAD")),
            # BENCH_OFFLOAD_DTYPE=int8|bfloat16 compresses the offloaded
            # state storage (quantized_state.py) to cut the host round trip
            offload_state_dtype=os.environ.get("BENCH_OFFLOAD_DTYPE", "float32"),
            health={"every_n_steps": health_every},
            resilience={
                "watchdog_timeout_s": watchdog_s or None,
                "watchdog_action": "abort",
            },
        ),
        callbacks=callbacks,
    )
    trainer.fit(objective, datamodule)

    if sync_mode:
        # intervals between consecutive post-warmup syncs; the slice
        # starts at warmup-1 so the first post-warmup interval is kept
        sec = float(np.median(np.diff(sync_times[warmup - 1:])))
    else:
        sec = (window["t1"] - window["t0"]) / (steps - warmup)
    return trainer, objective, sec


def _count_params(cfg, seq):
    """(n_params, flops_per_token) under the standard MFU convention (PaLM
    appendix B): model FLOPs only — 6N per token fwd+bwd plus the attention
    quadratic 12·L·h·S; rematerialization is NOT credited (overhead, not
    useful work). MoE credits ACTIVATED params only."""
    attn_params = (
        cfg.hidden_size * (cfg.num_attention_heads + 2 * cfg.num_key_value_heads)
        * cfg.resolved_head_dim
        + cfg.num_attention_heads * cfg.resolved_head_dim * cfg.hidden_size
        + 2 * cfg.hidden_size
    )
    if cfg.num_experts:
        expert_mlp = 3 * cfg.hidden_size * cfg.moe_intermediate_size
        router = cfg.hidden_size * cfg.num_experts
        n_params = (
            cfg.vocab_size * cfg.hidden_size * 2
            + cfg.num_hidden_layers
            * (attn_params + router + cfg.num_experts * expert_mlp)
        )
        # MoE MFU credits ACTIVATED params only (top-k experts per token) —
        # the standard sparse-model convention; total params still reported
        n_active = (
            cfg.vocab_size * cfg.hidden_size * 2
            + cfg.num_hidden_layers
            * (attn_params + router + cfg.num_experts_per_tok * expert_mlp)
        )
    else:
        n_params = n_active = (
            cfg.vocab_size * cfg.hidden_size * 2
            + cfg.num_hidden_layers
            * (attn_params + 3 * cfg.hidden_size * cfg.intermediate_size)
        )
    flops_per_token = 6 * n_active + 12 * cfg.num_hidden_layers * cfg.hidden_size * seq
    return n_params, flops_per_token


# ------------------------------------------------------------------- stages


def stage_backend_init(dry: bool) -> dict:
    """Prove the backend answers: import jax, enumerate devices, run one
    trivial device computation (round r05 froze exactly here). Outside
    `--dry` it also refuses what cannot be measured: a non-TPU backend, or
    a device with no peak in the one table."""
    import jax
    import jax.numpy as jnp

    from llm_training_tpu.callbacks.time_estimator import peak_flops_per_device

    devices = jax.devices()
    if not dry:
        if jax.default_backend() != "tpu":
            raise SystemExit(
                f"bench needs a TPU; jax gave backend {jax.default_backend()!r} "
                "(--dry runs the plumbing on CPU and reports counts only)"
            )
        if peak_flops_per_device() is None:
            raise SystemExit(
                f"no peak FLOP/s for device_kind {devices[0].device_kind!r} in "
                "callbacks/time_estimator.py's table — add it with its source"
            )
    # a real device round trip, not just enumeration — a hung backend can
    # list devices and then hang the first execute
    value = float(jax.device_get(jnp.ones(()) + 1.0))
    if value != 2.0:
        raise SystemExit(f"device round trip returned {value}, expected 2.0")
    return {
        "backend": jax.default_backend(),
        "n_devices": len(devices),
        "device_kind": devices[0].device_kind,
    }


def stage_train(dry: bool) -> dict:
    """The headline MFU fit."""
    import jax

    from llm_training_tpu.callbacks.time_estimator import peak_flops_per_device

    model_kwargs, seq, batch, steps, warmup = _model_setup(dry)
    trainer, objective, sec_per_step = _timed_fit(
        model_kwargs, seq, batch, steps, warmup, dry
    )
    n_dev = len(jax.devices())
    tokens_per_step = batch * max(1, n_dev) * seq
    tokens_per_sec = tokens_per_step / sec_per_step
    tokens_per_sec_chip = tokens_per_sec / max(1, n_dev)

    n_params, flops_per_token = _count_params(objective.model.config, seq)
    # a dry run has no peak to divide by: counts only, MFU null
    mfu = (
        None if dry
        else tokens_per_sec_chip * flops_per_token / peak_flops_per_device()
    )

    # goodput/telemetry extras so BENCH_* rounds can attribute regressions
    # to compile/data/step shifts, not just the MFU headline
    goodput = trainer.ledger.summary()
    snapshot = trainer.telemetry.snapshot()
    # which flash tiles the compiled step actually ran with (tuning layer
    # gauges; absent on the CPU/XLA path)
    blocks = {
        kind: [snapshot[f"flash/{kind}/block_q"], snapshot[f"flash/{kind}/block_k"]]
        for kind in ("fwd", "bwd")
        if f"flash/{kind}/block_q" in snapshot
    }
    block_sources = {
        key.rsplit("/", 1)[-1]: int(value)
        for key, value in snapshot.items()
        if key.startswith("flash/tuning_table_hit/")
    }
    return {
        "value": None if mfu is None else round(mfu, 4),
        "vs_baseline": None if mfu is None else round(mfu / 0.55, 4),
        "tokens_per_sec_per_chip": round(tokens_per_sec_chip, 1),
        "sec_per_step": round(sec_per_step, 4),
        "n_params": n_params,
        "model": os.environ.get("BENCH_MODEL", "8b-layer"),
        "n_devices": n_dev,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "goodput_pct": round(goodput["goodput/goodput_pct"], 2),
        "compile_time_s": round(snapshot.get("compile_time_s", 0.0), 2),
        "blocks": blocks,
        "block_sources": block_sources,
        # global per OPTIMIZER step (the gauge is per-device per train_step
        # invocation), same units as the estimator's perf/xla_flops_per_step
        "xla_flops_per_step": (
            snapshot["xla/flops_per_step"]
            * trainer.config.accumulate_grad_batches * max(1, n_dev)
            if "xla/flops_per_step" in snapshot else None
        ),
        # static collective-payload share of the compiled step's bytes
        # (attr/ gauges from the HLO walk, docs/observability.md#device-plane)
        # — tracked round over round so a sharding regression that trades
        # FLOPs for traffic shows up even when MFU barely moves
        "comm_fraction": (
            round(snapshot["attr/comm_fraction"], 4)
            if "attr/comm_fraction" in snapshot else None
        ),
    }


def stage_health(dry: bool) -> dict:
    """Same fit with health.every_n_steps=1; the parent divides against the
    train stage's sec_per_step for health_overhead_pct (back-to-back child
    processes on the same chip — the cross-process noise is the same
    run-to-run noise the in-process A/B had)."""
    model_kwargs, seq, batch, steps, warmup = _model_setup(dry)
    _, _, sec_health = _timed_fit(
        model_kwargs, seq, batch, steps, warmup, dry, health_every=1
    )
    return {"sec_per_step_health": round(sec_health, 4)}


def stage_trace(dry: bool) -> dict:
    """Same fit as the train stage with host tracing AT ITS DEFAULT
    deployment — ring recording every step + an attached trace.jsonl sink
    receiving the coarse lifecycle events (per-step span WRITES stay off,
    exactly as a production run defaults). The parent divides against the
    train stage's sec_per_step for trace_overhead_pct, the gauge that
    proves the event layer stays under its <2% budget at default sampling
    (docs/observability.md#tracing). LLMT_TRACE_TRAIN=1 on this stage
    additionally prices the per-step sink writes."""
    import shutil
    import tempfile

    from llm_training_tpu.telemetry.trace import get_tracer

    tracer = get_tracer()
    sink_dir = tempfile.mkdtemp(prefix="bench-trace-")
    tracer.attach_sink(os.path.join(sink_dir, "trace.jsonl"))
    model_kwargs, seq, batch, steps, warmup = _model_setup(dry)
    try:
        _, _, sec_trace = _timed_fit(
            model_kwargs, seq, batch, steps, warmup, dry
        )
    finally:
        counts = tracer.counts()
        tracer.detach_sink()
        shutil.rmtree(sink_dir, ignore_errors=True)
    return {
        "sec_per_step_trace": round(sec_trace, 4),
        "trace_events_written": counts["written"],
    }


def stage_exporter(dry: bool) -> dict:
    """Same fit as the train stage with the live-telemetry exporter ON and
    a Prometheus-cadence scraper polling /metrics throughout — the A/B
    for `exporter_overhead_pct` (docs/observability.md#live-telemetry).
    The scraper runs in-process (a daemon thread hitting localhost), so
    the measured overhead includes both the serving thread and the
    registry snapshots each scrape takes."""
    import threading
    import urllib.request

    from llm_training_tpu.telemetry.exporter import find_free_port

    # ephemeral port chosen here (bind-then-release) rather than port 0:
    # the trainer reads LLMT_METRICS_PORT and the scraper must know where
    # to point before the fit starts
    port = find_free_port()
    os.environ["LLMT_METRICS_PORT"] = str(port)

    stop = threading.Event()
    scrapes = {"ok": 0, "failed": 0, "last": ""}

    def scrape_loop():
        url = f"http://127.0.0.1:{port}/metrics"
        while not stop.wait(0.5):
            try:
                with urllib.request.urlopen(url, timeout=2.0) as resp:
                    scrapes["last"] = resp.read().decode("utf-8", "replace")
                scrapes["ok"] += 1
            except OSError:
                scrapes["failed"] += 1  # exporter not up yet / fit finished

    scraper = threading.Thread(target=scrape_loop, daemon=True)
    scraper.start()
    model_kwargs, seq, batch, steps, warmup = _model_setup(dry)
    try:
        _, _, sec_exporter = _timed_fit(
            model_kwargs, seq, batch, steps, warmup, dry
        )
    finally:
        stop.set()
        scraper.join(timeout=5.0)
        os.environ.pop("LLMT_METRICS_PORT", None)
    return {
        "sec_per_step_exporter": round(sec_exporter, 4),
        "exporter_scrapes": scrapes["ok"],
        "exporter_scrape_series": scrapes["last"].count("# TYPE"),
    }


def stage_decode(dry: bool) -> dict:
    """Decode-path gauge (docs/inference.md): a TINY-model generate run —
    the gauge tracks the decode program's dispatch/step overhead trend, not
    model-scale decode throughput."""
    import jax
    import numpy as np

    from llm_training_tpu.infer import GenerateConfig, InferenceEngine
    from llm_training_tpu.models import Llama, LlamaConfig

    tiny = Llama(LlamaConfig(
        vocab_size=2048, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512,
        compute_dtype="float32" if dry else "bfloat16",
    ))
    variables = tiny.init(jax.random.key(0), np.zeros((1, 4), np.int32))
    engine = InferenceEngine(tiny, variables)
    prompts = [[int(t) for t in np.arange(1, 17) + 7 * row] for row in range(4)]
    # warm-up generate absorbs the prefill/decode compiles so the recorded
    # prefill_time_s is a run number, not a compile number; max_length
    # pinned so both runs share one cache shape (one compiled program)
    engine.generate(prompts, GenerateConfig(max_new_tokens=4, max_length=48))
    decode_stats = engine.generate(
        prompts, GenerateConfig(max_new_tokens=32, max_length=48)
    )["stats"]
    return {
        "prefill_time_s": round(decode_stats["decode/prefill_time_s"], 4),
        "decode_tokens_per_sec": round(decode_stats["decode/tokens_per_sec"], 1),
    }


def stage_serve(dry: bool) -> dict:
    """Serving-path gauge (docs/serving.md): a TINY-model continuous-
    batching run through the `ServingEngine` — paged pool, chunked prefill,
    per-slot ragged decode. Like the decode stage this tracks the serve
    program's dispatch/step overhead trend, not model-scale throughput.
    A warm-up run absorbs the prefill/decode compiles, so the measured
    run's TTFT percentiles are scheduling numbers, not compile numbers."""
    import jax
    import numpy as np

    from llm_training_tpu.models import Llama, LlamaConfig
    from llm_training_tpu.serve import ServeConfig, ServingEngine

    tiny = Llama(LlamaConfig(
        vocab_size=2048, hidden_size=128, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=512,
        compute_dtype="float32" if dry else "bfloat16",
    ))
    variables = tiny.init(jax.random.key(0), np.zeros((1, 4), np.int32))
    engine = ServingEngine(tiny, variables, ServeConfig(
        max_batch=4, max_model_len=96, prefill_chunk=16, eos_token_id=None,
    ))

    def traffic(tag, n_tokens):
        return [
            {"id": f"{tag}{row}", "prompt": [int(t) for t in np.arange(1, 9 + 4 * row)],
             "max_new_tokens": n_tokens}
            for row in range(4)
        ]

    engine.run(traffic("warm", 4))
    t0 = time.perf_counter()
    events = engine.run(traffic("r", 32))
    wall = time.perf_counter() - t0
    done = [e for e in events if e["type"] == "done"]
    assert len(done) == 4, f"serve bench dropped requests: {done}"
    tokens = sum(e["n_tokens"] for e in done)
    ttft = [e["ttft_ms"] for e in done if "ttft_ms" in e]
    tps_chip = tokens / wall / max(1, len(jax.devices()))
    return {
        "serve_tokens_per_sec_per_chip": round(tps_chip, 1),
        "serve_ttft_p50_ms": round(float(np.percentile(ttft, 50)), 3),
        "serve_ttft_p99_ms": round(float(np.percentile(ttft, 99)), 3),
    }


_STAGE_FNS = {
    "backend_init": stage_backend_init,
    "train": stage_train,
    "health": stage_health,
    "trace": stage_trace,
    "exporter": stage_exporter,
    "decode": stage_decode,
    "serve": stage_serve,
}


def run_stage(stage: str, dry: bool) -> int:
    """Child-process entry: run one stage, print its partial record last."""
    from llm_training_tpu.compile_cache import configure_compile_cache

    _chaos(stage)
    configure_compile_cache()
    payload = _STAGE_FNS[stage](dry)
    print(json.dumps({"stage": stage, "partial": True, "status": "ok", **payload}),
          flush=True)
    return 0


# ------------------------------------------------------------------- parent


def _stage_timeout(stage: str) -> float:
    def env(name, default):
        return float(os.environ.get(name, default))

    run_timeout = env("BENCH_RUN_TIMEOUT", 2400)
    return {
        # the r5 wedge incidents struck backend init AND remote compiles —
        # short fuse for init, long one covering compile+run
        "backend_init": env("BENCH_BACKEND_TIMEOUT", 300),
        "train": run_timeout,
        "health": env("BENCH_HEALTH_TIMEOUT", run_timeout),
        "trace": env("BENCH_TRACE_TIMEOUT", run_timeout),
        "exporter": env("BENCH_EXPORTER_TIMEOUT", run_timeout),
        "decode": env("BENCH_DECODE_TIMEOUT", 600),
        "serve": env("BENCH_SERVE_TIMEOUT", 600),
    }[stage]


def _stage_enabled(stage: str) -> bool:
    if stage == "health":
        return os.environ.get("BENCH_HEALTH", "1") != "0"
    if stage == "trace":
        return os.environ.get("BENCH_TRACE", "1") != "0"
    if stage == "exporter":
        return os.environ.get("BENCH_EXPORTER", "1") != "0"
    if stage == "decode":
        return os.environ.get("BENCH_DECODE", "1") != "0"
    if stage == "serve":
        return os.environ.get("BENCH_SERVE", "1") != "0"
    return True


def run_supervised_stage(stage: str, dry: bool) -> dict:
    """Run one stage as a supervised child; returns its partial record
    (status ok with the stage payload, or status error with diagnostics).
    Reuses the PR 5 `Supervisor` for launch/exit/restart bookkeeping (its
    jsonl event log + signal decoding); the injected `run_child` adds the
    per-stage timeout kill the Supervisor's plain `subprocess.call` lacks."""
    from llm_training_tpu.resilience.supervisor import Supervisor, SupervisorConfig

    argv = [sys.executable, os.path.abspath(__file__), "--stage", stage]
    if dry:
        argv.append("--dry")
    timeout = _stage_timeout(stage)
    cell = {"out": "", "err": "", "timed_out": False}

    def run_child(child_argv):
        cell["timed_out"] = False
        proc = subprocess.Popen(
            child_argv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=child_env(dry),
        )
        try:
            out, err = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            cell["timed_out"] = True
        cell["out"], cell["err"] = out or "", err or ""
        return proc.returncode

    # a backend-init hang is sometimes transient: one free relaunch (a
    # timeout kill is a signal death, which Supervisor restarts);
    # fit/decode stages never auto-rerun — a crashed fit would only recrash.
    retries = int(os.environ.get("BENCH_STAGE_RETRIES", 1 if stage == "backend_init" else 0))
    supervisor = Supervisor(
        argv,
        SupervisorConfig(
            max_restarts=retries,
            restart_codes=(),
            restart_on_signals=retries > 0,
            backoff_base_s=1.0,
            healthy_runtime_s=timeout,
            log_path=os.environ.get("BENCH_SUPERVISOR_LOG"),
        ),
        run_child=run_child,
    )
    t0 = time.monotonic()
    rc = supervisor.run()
    runtime_s = round(time.monotonic() - t0, 2)

    payload = None
    for line in reversed(cell["out"].splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            candidate = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(candidate, dict) and candidate.get("stage") == stage:
            payload = candidate
            break

    if rc == 0 and payload is not None:
        payload["runtime_s"] = runtime_s
        return payload
    if cell["timed_out"]:
        error = (f"stage wedged: no completion within {timeout:.0f}s "
                 "(child killed)")
    elif rc == 0:
        error = "stage exited 0 without emitting its record"
    else:
        error = f"stage failed (exit {rc})"
    tail = ("\n".join((cell["err"] + "\n" + cell["out"]).splitlines()[-6:]))[-500:]
    return {
        "stage": stage, "partial": True, "status": "error",
        "error": error, "rc": rc, "runtime_s": runtime_s, "tail": tail,
    }


def child_env(dry: bool) -> dict:
    env = dict(os.environ)
    if dry:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def summarize(results: dict) -> dict:
    """Assemble the final summary record (the driver parses the LAST JSON
    line; `stages` carries per-stage status so a partially-failed round is
    still attributable)."""
    def ok(stage):
        return results.get(stage, {}).get("status") == "ok"

    train = results.get("train", {})
    summary = {
        "metric": "llama_clm_train_mfu",
        "value": train.get("value") if ok("train") else None,
        "unit": "mfu_fraction",
        "vs_baseline": train.get("vs_baseline") if ok("train") else None,
        "stage": "summary",
        "partial": False,
    }
    if ok("train"):
        for key in ("tokens_per_sec_per_chip", "sec_per_step", "n_params", "model",
                    "n_devices", "backend", "device_kind", "goodput_pct",
                    "compile_time_s",
                    "xla_flops_per_step", "comm_fraction", "blocks",
                    "block_sources"):
            if key in train:
                summary[key] = train[key]
    elif "train" in results:
        summary["error"] = train.get("error", "train stage failed")
    elif results.get("backend_init", {}).get("status") == "error":
        summary["error"] = results["backend_init"].get("error", "backend init failed")

    # step-time cost of health.every_n_steps=1 vs disabled (None when
    # skipped or either fit failed)
    health = results.get("health", {})
    if ok("train") and ok("health") and train.get("sec_per_step"):
        overhead = (health["sec_per_step_health"] - train["sec_per_step"]) \
            / train["sec_per_step"]
        summary["health_overhead_pct"] = round(100.0 * overhead, 2)
    else:
        summary["health_overhead_pct"] = None
    # step-time cost of the event layer at its DEFAULT deployment (ring
    # recording + coarse sink events; per-step writes only if the stage ran
    # with LLMT_TRACE_TRAIN=1) vs untraced; the <2% acceptance gauge
    trace = results.get("trace", {})
    if ok("train") and ok("trace") and train.get("sec_per_step"):
        overhead = (trace["sec_per_step_trace"] - train["sec_per_step"]) \
            / train["sec_per_step"]
        summary["trace_overhead_pct"] = round(100.0 * overhead, 2)
    else:
        summary["trace_overhead_pct"] = None
    # step-time cost of the live-telemetry exporter under a steady scrape
    # (docs/observability.md#live-telemetry) vs unexported
    exporter = results.get("exporter", {})
    if ok("train") and ok("exporter") and train.get("sec_per_step"):
        overhead = (exporter["sec_per_step_exporter"] - train["sec_per_step"]) \
            / train["sec_per_step"]
        summary["exporter_overhead_pct"] = round(100.0 * overhead, 2)
        summary["exporter_scrapes"] = exporter.get("exporter_scrapes")
    else:
        summary["exporter_overhead_pct"] = None
    decode = results.get("decode", {})
    summary["prefill_time_s"] = decode.get("prefill_time_s")
    summary["decode_tokens_per_sec"] = decode.get("decode_tokens_per_sec")
    serve = results.get("serve", {})
    for key in ("serve_tokens_per_sec_per_chip", "serve_ttft_p50_ms",
                "serve_ttft_p99_ms"):
        summary[key] = serve.get(key)

    summary["stages"] = {
        stage: {
            key: record[key]
            for key in ("status", "error", "rc", "runtime_s")
            if key in record
        }
        for stage, record in results.items()
    }
    return summary


def orchestrate(dry: bool) -> int:
    results: dict[str, dict] = {}
    backend_dead = False
    for stage in STAGES:
        if not _stage_enabled(stage):
            results[stage] = {"stage": stage, "partial": True, "status": "skipped"}
            continue
        if backend_dead and stage != "backend_init":
            results[stage] = {
                "stage": stage, "partial": True, "status": "skipped",
                "error": "backend init failed — stage not attempted",
            }
            print(json.dumps(results[stage]), flush=True)
            continue
        record = run_supervised_stage(stage, dry)
        results[stage] = record
        print(json.dumps(record), flush=True)
        if stage == "backend_init" and record.get("status") != "ok":
            # don't burn the full run timeout re-wedging on a dead backend;
            # the summary still lands with every stage accounted for
            backend_dead = True

    summary = summarize(results)
    print(json.dumps(summary), flush=True)
    out_path = os.environ.get("BENCH_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(summary, f, indent=2)
            f.write("\n")

    attempted = [s for s, r in results.items() if r.get("status") != "skipped"]
    if results.get("train", {}).get("status") != "ok":
        return 1
    if any(results[s].get("status") != "ok" for s in attempted):
        return 2
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description="wedge-proof multi-stage bench")
    parser.add_argument("--stage", choices=STAGES,
                        help="internal: run ONE stage in this process")
    parser.add_argument("--dry", action="store_true",
                        help="CPU dry run of the full stage/subprocess/"
                             "partial-JSON plumbing with the tiny proxy")
    parser.add_argument("--check-regression", action="store_true",
                        help="no bench run: parse the BENCH_r*.json rounds "
                             "under --bench-dir, print the trend table, and exit "
                             "nonzero when the newest same-backend round "
                             "regressed MFU / decode tokens-per-sec / serve "
                             "TTFT beyond BENCH_REGRESSION_TOLERANCE_PCT "
                             "(docs/performance.md#perf-ledger)")
    parser.add_argument("--bench-dir", default=".",
                        help="directory holding BENCH_r*.json rounds "
                             "(--check-regression only; default: cwd)")
    parser.add_argument("--tolerance-pct", type=float, default=None,
                        help="regression tolerance override "
                             "(default: BENCH_REGRESSION_TOLERANCE_PCT or 40)")
    args = parser.parse_args()
    if args.check_regression:
        # jax-free by contract, like the whole bench parent: the regression
        # gate must run on any machine the repo is checked out on
        from llm_training_tpu.telemetry.perf_ledger import ledger_main

        return ledger_main(args.bench_dir, tolerance_pct=args.tolerance_pct)
    if args.stage:
        # a dry child arrives with JAX_PLATFORMS=cpu from child_env
        return run_stage(args.stage, args.dry)
    return orchestrate(args.dry)


if __name__ == "__main__":
    sys.exit(main())
