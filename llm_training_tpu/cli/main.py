"""Console entry: fit / validate / generate / serve / rl-fit / evaluate /
report / trace / watch / fleet / supervise.

Capability parity: reference `cli/main.py:4-5` + LightningCLI wiring
(`lightning/cli/cli.py:17-83`): YAML -> instantiated Trainer / objective /
DataModule -> run, with seed_everything, logging-level control, and the
resolved config handed to the checkpointer for embedding. `report` is a
TPU-native addition: render a finished run's goodput/MFU/HBM summary from
its run directory (docs/observability.md) — no config or backend needed.
`generate` / `evaluate` (docs/inference.md) restore the run's checkpoint
read-only and drive the inference subsystem (`llm_training_tpu.infer`):
batched KV-cache decoding with sampling, and packed-perplexity held-out
scoring; both merge their `decode/*` / `eval/*` telemetry into the run
directory's telemetry.jsonl so `report` renders it. `serve`
(docs/serving.md) is the continuous-batching tier over the same restored
checkpoint: JSONL requests on stdin, streamed token/done chunks on stdout,
paged KV cache with mid-flight admission — its `serve/*` gauges merge the
same way and render as `== Serving ==`. `supervise`
(docs/resilience.md) runs `fit` as a child process and relaunches it on
preemption (exit 75) and hard deaths (SIGKILL/segfault/SIGABRT), with a
restart budget, backoff, and a supervisor.jsonl event log.

Exit-code contract for `fit` (docs/resilience.md#exit-codes): 0 complete,
75 preempted-but-resumable, 76 recovery budget exhausted, 77 loss spike
(unrecovered), 78 non-finite divergence (unrecovered); anything else is an
unclassified failure.
"""

from __future__ import annotations

import argparse
import logging
import random
import sys

import numpy as np

from llm_training_tpu.cli.config import instantiate_from_config, load_config


def _seed_everything(seed: int) -> None:
    random.seed(seed)
    np.random.seed(seed)


def _apply_extra_config(config: dict) -> None:
    """Top-level runtime flags (the reference ExtraConfig callback,
    `lightning/callbacks/extra_config.py:13-45`): matmul precision (its
    `float32_matmul_precision`) and the persistent XLA compilation cache
    (its per-rank TRITON_CACHE_DIR analogue — one dir is safe for all
    hosts, unlike Triton's). The cache directory is not a config key: it
    is `JAX_COMPILATION_CACHE_DIR` where set, else the fixed in-checkout
    path (`compile_cache.py`) — every command that touches a device passes
    here, so they all share one cache."""
    import jax

    from llm_training_tpu.compile_cache import ENV_CACHE_DIR, configure_compile_cache

    precision = config.get("matmul_precision") or config.get("float32_matmul_precision")
    if precision:
        # torch names -> XLA precisions
        precision = {"highest": "float32", "high": "tensorfloat32", "medium": "bfloat16"}.get(
            str(precision), str(precision)
        )
        jax.config.update("jax_default_matmul_precision", precision)
    if "compilation_cache_dir" in config:
        raise ValueError(
            "`compilation_cache_dir` is no longer a config key: place the "
            f"compile cache with the {ENV_CACHE_DIR} environment variable "
            "(unset, it lives at <repo>/.jax_cache)"
        )
    configure_compile_cache()


def _build(config: dict):
    from llm_training_tpu.trainer import Trainer, TrainerConfig
    from llm_training_tpu.trainer.checkpoint import CheckpointConfig, Checkpointer

    trainer_node = dict(config.get("trainer", {}))
    checkpoint_node = trainer_node.pop("checkpoint", None)
    callbacks_node = trainer_node.pop("callbacks", [])
    loggers_node = trainer_node.pop("loggers", [])

    checkpointer = None
    if checkpoint_node:
        checkpointer = Checkpointer(
            CheckpointConfig(**checkpoint_node), run_config=config
        )

    callbacks = [instantiate_from_config(node) for node in callbacks_node]
    callbacks += [instantiate_from_config(node) for node in loggers_node]

    trainer = Trainer(
        TrainerConfig(**trainer_node), callbacks=callbacks, checkpointer=checkpointer
    )
    objective = instantiate_from_config(
        config["model"], default_class="llm_training_tpu.lms.CLM"
    )
    datamodule = instantiate_from_config(config["data"])
    return trainer, objective, datamodule


def _jsonl_run_dir(config: dict):
    """Run directory of the config's JsonlLogger, or None when the run has
    no deterministic on-disk location (no JsonlLogger node, or a
    timestamped name). Derived through JsonlLoggerConfig itself so the
    save_dir/project defaults can never drift from what the fit used."""
    from pathlib import Path

    from llm_training_tpu.callbacks.loggers import JsonlLoggerConfig

    for node in config.get("trainer", {}).get("loggers", []) or []:
        if str(node.get("class_path", "")).endswith("JsonlLogger"):
            logger_config = JsonlLoggerConfig(**node.get("init_args", {}))
            if logger_config.name:
                return (
                    Path(logger_config.save_dir)
                    / logger_config.project
                    / logger_config.name
                )
    return None


def _jsonl_run_dir_jaxfree(config: dict):
    """`_jsonl_run_dir` for the SUPERVISOR path: importing
    `callbacks.loggers` executes the callbacks package __init__, which
    module-level imports jax (profiler/time_estimator) — and the
    supervisor must never load jax or it holds the TPU its child needs.
    The two default strings mirror JsonlLoggerConfig (save_dir="runs",
    project="llm-training-tpu"); keep them in sync."""
    from pathlib import Path

    for node in config.get("trainer", {}).get("loggers", []) or []:
        if str(node.get("class_path", "")).endswith("JsonlLogger"):
            init = node.get("init_args", {}) or {}
            if init.get("name"):
                return (
                    Path(init.get("save_dir", "runs"))
                    / str(init.get("project", "llm-training-tpu"))
                    / str(init["name"])
                )
    return None


def _publish_run_telemetry(config: dict, gauges: dict) -> None:
    """Merge `decode/*` / `eval/*` gauges into the run dir's newest
    telemetry.jsonl record (same step, keys overlaid), so `report` renders
    them next to the fit's goodput/health numbers instead of a bare record
    shadowing them. No-op when the config has no addressable run dir.
    Process 0 only — run-dir artifacts follow the JsonlLogger policy
    (N hosts appending would duplicate and interleave records)."""
    import json

    from llm_training_tpu.callbacks.loggers import _primary_host

    run_dir = _jsonl_run_dir(config)
    if run_dir is None or not gauges or not _primary_host():
        return
    path = run_dir / "telemetry.jsonl"
    last: dict = {}
    if path.exists():
        for line in path.read_text().splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                last = json.loads(line)
            except json.JSONDecodeError:
                continue  # torn tail from a killed run
    run_dir.mkdir(parents=True, exist_ok=True)
    record = {**last, **{k: float(v) for k, v in gauges.items()}}
    record.setdefault("step", 0)
    with open(path, "a") as f:
        f.write(json.dumps(record) + "\n")
    logging.getLogger(__name__).info("telemetry merged into %s", path)


def _parse_prompts(args, config: dict) -> list[list[int]]:
    """--prompt-tokens '3,17,42' (repeatable) and/or --prompt 'text...'
    (repeatable; needs a resolvable tokenizer in the data config node)."""
    prompts: list[list[int]] = []
    for raw in args.prompt_tokens or []:
        prompts.append([int(t) for t in raw.replace(" ", "").split(",") if t])
    if args.prompt:
        tokenizer_node = config.get("data", {}).get("init_args", {}).get("tokenizer")
        if tokenizer_node is None:
            raise SystemExit(
                "--prompt needs a tokenizer in the config's data node; "
                "use --prompt-tokens with raw token ids instead"
            )
        from llm_training_tpu.data.tokenizer import resolve_tokenizer

        tokenizer = resolve_tokenizer(tokenizer_node)
        for text in args.prompt:
            prompts.append(list(tokenizer(text)["input_ids"]))
    if not prompts:
        raise SystemExit("generate needs --prompt-tokens and/or --prompt")
    return prompts


def _require_single_model_objective(objective, command: str) -> None:
    """generate/evaluate drive ONE causal LM over CLM-keyed batches;
    preference objectives (DPO's policy+ref trees, ORPO's chosen_/rejected_
    batch keys) would fail with a KeyError deep in shape evaluation — fail
    up front with a clear message instead."""
    from llm_training_tpu.lms import CLM

    if not isinstance(objective, CLM):
        raise SystemExit(
            f"{command} supports the CLM objective only; the config's model "
            f"node builds {type(objective).__name__} — point {command} at a "
            "config whose model node is llm_training_tpu.lms.CLM wrapping "
            "the (policy) model"
        )


def _run_generate(args, config: dict) -> int:
    import json

    from llm_training_tpu.infer import GenerateConfig, InferenceEngine, SamplingConfig
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    trainer, objective, _ = _build(config)
    _require_single_model_objective(objective, "generate")
    prompts = _parse_prompts(args, config)
    state = trainer.restore_for_inference(
        objective, int(args.ckpt_path) if args.ckpt_path else None
    )
    engine = InferenceEngine(
        objective.model, state.params, mesh=trainer.mesh, rules=LOGICAL_AXIS_RULES
    )
    generate_config = GenerateConfig(
        max_new_tokens=args.max_new_tokens,
        max_length=args.max_length,
        cache_dtype=args.cache_dtype,
        seed=args.seed,
        eos_token_id=(
            args.eos_token_id if args.eos_token_id is not None
            else _scalar_eos(objective.model.config)
        ),
        sampling=SamplingConfig(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p
        ),
    )
    result = engine.generate(prompts, generate_config)
    for row, tokens in enumerate(result["tokens"]):
        record = {
            "prompt": prompts[row],
            "tokens": tokens,
            "sequence": result["sequences"][row],
            "n_tokens": result["lengths"][row],
            "stop_reason": result["stop_reasons"][row],
        }
        if args.logprobs:
            # per-token logprob of each CHOSEN token under the sampled
            # distribution (temperature+filter applied; raw log_softmax
            # when greedy) — docs/inference.md#logprobs
            record["logprobs"] = result["logprobs"][row]
        print(json.dumps(record))
    print(json.dumps({"stats": result["stats"]}))
    _publish_run_telemetry(config, result["stats"])
    return 0


def _run_serve(args, config: dict) -> int:
    """`serve`: continuous-batching generation over a JSONL stdin/stdout
    protocol (docs/serving.md#protocol). One request per input line
    ({"id", "prompt": [ids], "max_new_tokens"?, "priority"?,
    "deadline_ms"?}); the engine streams {"type": "token"} chunks and a
    {"type": "done"} terminator per request as they land, interleaving new
    admissions with in-flight decodes. A {"type": "reload"} control line
    hot-swaps the weights from the newest (or a named) checkpoint between
    steps. stdin EOF drains the queue, then a final {"type": "stats"}
    record carries the serve/* gauges (also merged into the run dir's
    telemetry.jsonl for `report`).

    Resilience (docs/serving.md#resilience): SIGTERM stops intake,
    finishes what `--drain-timeout-s` allows, evicts-and-journals the
    rest, and exits 75 so `supervise --child serve` relaunches; the
    relaunch replays the journal before touching stdin. A wedged engine
    step trips the `--watchdog-timeout-s` HangWatchdog (flight-dump +
    SIGABRT — another supervised relaunch). `LLMT_CHAOS_SERVE_*` faults
    inject all of it."""
    import json
    import queue
    import threading
    import time as _time

    from llm_training_tpu.infer import SamplingConfig
    from llm_training_tpu.serve import ServeConfig, ServingEngine
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    from llm_training_tpu.telemetry.trace import get_tracer

    with get_tracer().measure("setup", "model_build", pin=True):
        trainer, objective, _ = _build(config)
    _require_single_model_objective(objective, "serve")
    with get_tracer().measure("setup", "weights", pin=True):
        state = trainer.restore_for_inference(
            objective, int(args.ckpt_path) if args.ckpt_path else None
        )
    serve_config = ServeConfig(
        max_batch=args.max_batch,
        max_model_len=args.max_model_len,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        prefill_chunk=args.prefill_chunk,
        max_queue=args.max_queue,
        shed_ttft_ms=args.shed_ttft_ms,
        cache_dtype=args.cache_dtype,
        seed=args.seed,
        eos_token_id=(
            args.eos_token_id if args.eos_token_id is not None
            else _scalar_eos(objective.model.config)
        ),
        sampling=SamplingConfig(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p
        ),
    )
    engine = ServingEngine(
        objective.model, state.params, serve_config,
        mesh=trainer.mesh, rules=LOGICAL_AXIS_RULES,
    )

    # request-lifecycle tracing (docs/observability.md#tracing): sampled
    # spans land in the run dir's trace.jsonl for `trace` export / the
    # report's == Trace == section. Process 0 only, like every run-dir
    # artifact; a run with no addressable run dir keeps ring-only tracing.
    from llm_training_tpu.callbacks.loggers import _primary_host
    from llm_training_tpu.resilience import (
        RESUMABLE_EXIT_CODE,
        GracefulShutdown,
        HangWatchdog,
        config_from_env,
        install_chaos,
        uninstall_chaos,
    )
    from llm_training_tpu.serve import RequestJournal, replay_journal

    log = logging.getLogger(__name__)
    run_dir = _jsonl_run_dir(config)
    primary = _primary_host()
    trace_attached = False
    if run_dir is not None and primary:
        trace_attached = get_tracer().attach_sink(run_dir / "trace.jsonl")

    # serve chaos is env-only (LLMT_CHAOS_SERVE_*, docs/resilience.md#chaos)
    # — serve has no trainer.resilience YAML node to carry a config
    chaos = install_chaos(config_from_env())
    shutdown = GracefulShutdown().install()
    watchdog = None
    if args.watchdog_timeout_s:
        watchdog = HangWatchdog(
            args.watchdog_timeout_s, run_dir=run_dir, action="abort",
            primary_source="engine_step",
        ).start()

    # live telemetry (docs/observability.md#live-telemetry): the SLO
    # monitor (LLMT_SLO_* targets; fed per done event below) and the
    # /metrics//statusz//healthz exporter (LLMT_METRICS_PORT; 0 = off —
    # the supervisor's env passthrough keeps the port across relaunches,
    # so scrapes survive a drain/replay boundary). The exporter's live
    # gauges come from engine.live_stats(): queue depth, in-flight rows,
    # rolling TTFT/TPOT — the answer to "is this server healthy NOW"
    # rather than the end-of-run stats record.
    from llm_training_tpu.telemetry import get_registry
    from llm_training_tpu.telemetry.exporter import start_exporter
    from llm_training_tpu.telemetry.slo import build_slo_monitor

    # flight dumps are run-dir artifacts: process 0 only, like the journal
    slo = build_slo_monitor(
        registry=get_registry(), run_dir=run_dir if primary else None
    )
    # device-profile trigger (docs/observability.md#profiling): armed by
    # SLO breaches, the watchdog, `{"type": "profile"}` control lines, and
    # /profilez; only this serve loop's poll() below touches jax.profiler
    from llm_training_tpu.telemetry.profiling import (
        build_profile_trigger,
        set_profile_trigger,
    )

    profile_trigger = build_profile_trigger(
        registry=get_registry(), run_dir=run_dir if primary else None
    )
    exporter = start_exporter(
        registry=get_registry(),
        watchdog=watchdog,
        slo=slo,
        profile=profile_trigger,
        role="serve",
        extra_fn=engine.live_stats,
        status_fn=lambda: {
            "engine step": engine._step_index,
            "queue depth": len(engine.scheduler.waiting),
            "running": len(engine.scheduler.running),
            "completed": len(engine.scheduler.completed),
        },
    )

    # request journal (docs/serving.md#resilience): a relaunch replays
    # accepted-but-unfinished work so no accepted request is silently
    # lost. The previous journal is rotated into a durable backup that
    # survives until every entry has been re-accepted into the FRESH
    # journal — a death anywhere in the replay window still replays on
    # the next relaunch (appending handles a relaunch that itself died
    # mid-replay; the fold's last-acceptance-wins dedupe keeps it exact).
    journal_path = (
        run_dir / "serve-journal.jsonl"
        if run_dir is not None and primary else None
    )
    backup_path = None
    resumed = []
    if journal_path is not None:
        backup_path = journal_path.with_name("serve-journal.replaying.jsonl")
        if journal_path.exists():
            with open(backup_path, "a") as backup:
                backup.write(journal_path.read_text())
            journal_path.unlink()
        if backup_path.exists():
            resumed = replay_journal(backup_path)
        engine.attach_journal(
            RequestJournal(journal_path), every=args.journal_every
        )

    # a reader thread feeds stdin lines into a queue so request intake
    # never blocks the decode loop — that interleave IS continuous
    # batching: a request arriving mid-decode is admitted at the next step
    lines: queue.Queue = queue.Queue()
    _EOF = object()

    def parse_line(line: str):
        """One raw protocol line -> (record, error), parsed exactly once —
        the reader journals from the same parse the serve loop submits
        from. None for blank lines."""
        line = line.strip()
        if not line:
            return None
        try:
            record = json.loads(line)
            if not isinstance(record, dict):
                raise ValueError("request line must be a JSON object")
            return (record, None)
        except (json.JSONDecodeError, ValueError) as e:
            return (None, f"bad request line: {e}")

    def journal_delivery(record: dict) -> None:
        """Journal a well-formed request the moment it is READ: a hard
        death (watchdog SIGABRT) between read and submit would vaporize
        the intake queue, and a delivered request must replay, not vanish.
        Malformed/control lines are the ingest path's problem."""
        if engine.journal is None or record.get("type"):
            return
        try:
            engine.journal.delivered(
                id=record["id"], prompt=record["prompt"],
                max_new_tokens=record.get(
                    "max_new_tokens", args.max_new_tokens
                ),
                priority=record.get("priority", 0),
                deadline_ms=record.get("deadline_ms"),
            )
        except (KeyError, TypeError, ValueError):
            pass

    def read_stdin():
        for line in sys.stdin:
            item = parse_line(line)
            if item is None:
                continue
            if item[0] is not None:
                journal_delivery(item[0])
            lines.put(item)
        lines.put(_EOF)

    threading.Thread(target=read_stdin, daemon=True).start()

    # chaos malformed flood: garbage on the intake path must cost error
    # chunks, never the batch
    if chaos is not None:
        for bad in chaos.serve_malformed_lines():
            item = parse_line(bad)
            if item is not None:
                lines.put(item)

    def emit(events):
        for event in events:
            print(json.dumps(event), flush=True)
            if slo is not None and event.get("type") == "done":
                # every terminal feeds the SLO windows: full completions
                # carry their latency numbers, everything else burns the
                # error-rate budget
                slo.observe_request(
                    ttft_ms=event.get("ttft_ms"),
                    tpot_ms=event.get("tpot_ms"),
                    ok=event.get("stop_reason") in ("eos", "max_tokens"),
                )

    def reload_from_checkpoint(request: dict) -> None:
        """{"type": "reload", "ckpt_path"?}: restore (newest checkpoint
        when unnamed) and hot-swap between steps. A failed reload answers
        an error chunk and the CURRENT weights keep serving."""
        step = request.get("ckpt_path")
        try:
            new_state = trainer.restore_for_inference(
                objective, int(step) if step is not None else None
            )
            generation = engine.reload_weights(new_state.params)
        except Exception as e:  # noqa: BLE001 — the server must keep serving
            print(json.dumps({
                "type": "error", "error": f"reload failed: {e}"
            }), flush=True)
            return
        finally:
            if watchdog is not None:
                # the restore is legitimate blocking host work between
                # engine steps — it must not age the engine_step beat into
                # an abort of a healthy server
                watchdog.beat()
        print(json.dumps({
            "type": "weights", "generation": generation,
            "ckpt_path": step,
        }), flush=True)

    def ingest(item) -> bool:
        """One parsed stdin item -> submit (or reload control); False at
        EOF. Coercion failures deep in submit (junk field values inside
        valid JSON) answer an error chunk like a parse failure."""
        if item is _EOF:
            return False
        record, error = item
        if error is None and record.get("type") == "profile":
            # {"type": "profile", "tag"?}: arm a device-profile capture
            # over the next engine steps. The ack chunk reports whether
            # the trigger accepted (budget/cooldown/busy refusals answer
            # accepted=false with the reason) — the capture itself starts
            # at the next poll in the serve loop below.
            tag = str(record.get("tag") or f"serve-{engine._step_index}")
            result = profile_trigger.request(tag, source="serve")
            print(json.dumps({"type": "profile", **result}), flush=True)
            return True
        if error is None and record.get("type") == "reload":
            reload_from_checkpoint(record)
            return True
        if error is None:
            try:
                deadline_ms = record.get("deadline_ms")
                emit(engine.submit(
                    id=record["id"], prompt=record["prompt"],
                    max_new_tokens=int(
                        record.get("max_new_tokens", args.max_new_tokens)
                    ),
                    priority=int(record.get("priority", 0)),
                    deadline_ms=(
                        float(deadline_ms) if deadline_ms is not None else None
                    ),
                ))
                return True
            except (KeyError, TypeError, ValueError) as e:
                error = f"bad request line: {e}"
        print(json.dumps({"type": "error", "error": error}), flush=True)
        return True

    def flush_delivered() -> None:
        """Drain everything the reader thread has already pulled off stdin
        into submissions. Lines sitting in this queue are DELIVERED
        requests: they must reach the engine (and so the journal), never
        die with the process — the drain path depends on this."""
        nonlocal open_stdin
        try:
            while open_stdin:
                open_stdin = ingest(lines.get_nowait()) and open_stdin
        except queue.Empty:
            pass

    # journal replay precedes any stdin work: the relaunch owes the
    # journaled requests their terminals first (their clients are oldest)
    if resumed:
        log.warning(
            "replaying %d journaled request(s) from the previous serve "
            "process", len(resumed),
        )
        for entry in resumed:
            emit(engine.submit_resumed(entry))
    if backup_path is not None and backup_path.exists():
        # every journaled request is now re-accepted in the FRESH journal
        # (or already terminal) — the rotation backup has done its job
        backup_path.unlink()

    open_stdin = True
    rc = 0
    # (`engine.idle`, not the scheduler's: the engine runs a call ahead of
    # what it has returned, and the step after the last one reads its tokens)
    while open_stdin or not engine.idle:
        if shutdown.requested:
            break
        if engine.idle:
            if watchdog is not None:
                # a quiet server is healthy, not hung: the engine-step
                # beat only moves under traffic
                watchdog.beat()
            try:  # nothing in flight: wait, but stay SIGTERM-responsive
                open_stdin = ingest(lines.get(timeout=0.2))
            except queue.Empty:
                pass
            continue
        # in flight: drain whatever arrived, never stall the batch
        flush_delivered()
        emit(engine.step())
        profile_trigger.poll(engine._step_index)
        if watchdog is not None:
            watchdog.beat(step=engine._step_index)

    if shutdown.requested:
        # graceful drain (docs/serving.md#drain): stop taking NEW stdin,
        # finish what the budget allows, evict-and-journal the rest, exit
        # resumable so `supervise --child serve` relaunches into a replay
        log.warning(
            "%s: draining in-flight requests for up to %.1fs, then "
            "journaling the remainder and exiting %d",
            shutdown.reason, args.drain_timeout_s, RESUMABLE_EXIT_CODE,
        )
        deadline = _time.monotonic() + args.drain_timeout_s
        while True:
            flush_delivered()
            if engine.idle or _time.monotonic() >= deadline:
                break
            emit(engine.step())
            profile_trigger.poll(engine._step_index)
            if watchdog is not None:
                watchdog.beat(step=engine._step_index)
        _time.sleep(0.05)  # let a mid-read reader line land in the queue
        flush_delivered()
        # the last call's tokens go to their clients; what drain() would read
        # itself it could only journal as not streamed
        emit(engine.flush())
        engine.drain()
        rc = RESUMABLE_EXIT_CODE

    stats = engine.stats()
    # closes any dangling capture and unpublishes the process-wide trigger
    # (a later fit in this process builds its own)
    profile_trigger.teardown()
    set_profile_trigger(None)
    if watchdog is not None:
        watchdog.stop()
    if trace_attached:
        get_tracer().detach_sink()
    print(json.dumps({"type": "stats", "stats": stats}), flush=True)
    _publish_run_telemetry(config, stats)
    if engine.journal is not None and rc == 0:
        # clean completion (stdin at EOF, reader thread done): every
        # accepted request got its terminal — a stale journal must not
        # resurrect them in the next run. On the drain path the journal
        # stays OPEN until process exit: the daemon reader may pull one
        # last line off the shared pipe in this window, and its delivery
        # record must hit the journal, not a closed file (records are
        # flushed as written, so exit loses nothing)
        engine.journal.close()
        if journal_path is not None:
            journal_path.unlink(missing_ok=True)
    if exporter is not None:
        # LAST, after the stats line and the telemetry merge: the loadgen's
        # final cross-check scrape fires the moment the last terminal lands
        # on stdout, and the exporter must still be answering then
        exporter.stop()
    uninstall_chaos()
    shutdown.uninstall()
    return rc


def _run_rl_fit(args, config: dict) -> int:
    """`rl-fit`: on-policy GRPO post-training riding the serving engine
    (docs/post-training.md). Each round collects N samples per prompt
    through the `ServingEngine` scheduler (rollouts are a dedicated
    priority class below user traffic), scores them with a verifiable
    reward, applies one group-relative policy-gradient update, then syncs
    the new weights into the engine (`rl/sync.py` — fused on-device by
    default). Per-round {"type": "rl_round"} records stream on stdout; a
    final {"type": "stats"} record carries the rl/* + serve/* gauges
    (merged into the run dir's telemetry.jsonl for `report`'s == RL ==
    section).

    Resilience mirrors serve: SIGTERM drains in-flight rollouts into the
    request journal, checkpoints the weights they were sampled under
    (plus the round cursor), and exits 75; the relaunch restores the
    checkpoint, replays the journal, and ADOPTS the replayed rollouts as
    current-generation — sound because the checkpoint always follows the
    sync, so restored weights match the rollouts' weights."""
    import json

    from llm_training_tpu.callbacks.loggers import _primary_host
    from llm_training_tpu.infer import SamplingConfig
    from llm_training_tpu.lms import GRPO
    from llm_training_tpu.resilience import (
        RESUMABLE_EXIT_CODE,
        GracefulShutdown,
        config_from_env,
        install_chaos,
        uninstall_chaos,
    )
    from llm_training_tpu.rl.loop import RLLoop, RLLoopOptions
    from llm_training_tpu.serve import RequestJournal, ServeConfig, replay_journal
    from llm_training_tpu.telemetry import get_registry
    from llm_training_tpu.telemetry.exporter import start_exporter
    from llm_training_tpu.telemetry.slo import build_slo_monitor
    from llm_training_tpu.telemetry.trace import get_tracer

    log = logging.getLogger(__name__)
    trainer, objective, _ = _build(config)
    if not isinstance(objective, GRPO):
        raise SystemExit(
            "rl-fit drives the GRPO objective; the config's model node "
            f"builds {type(objective).__name__} — point rl-fit at a config "
            "whose model node is llm_training_tpu.lms.GRPO wrapping the "
            "policy model"
        )
    run_dir = _jsonl_run_dir(config)
    primary = _primary_host()
    trace_attached = False
    if run_dir is not None and primary:
        trace_attached = get_tracer().attach_sink(run_dir / "trace.jsonl")

    serve_config = ServeConfig(
        max_batch=args.max_batch,
        max_model_len=args.max_model_len,
        block_size=args.block_size,
        num_blocks=args.num_blocks,
        prefill_chunk=args.prefill_chunk,
        max_queue=args.max_queue,
        cache_dtype=args.cache_dtype,
        seed=args.seed,
        eos_token_id=(
            args.eos_token_id if args.eos_token_id is not None
            else _scalar_eos(objective.model.config)
        ),
        sampling=SamplingConfig(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p
        ),
    )
    # serve chaos (LLMT_CHAOS_SERVE_*) fires inside engine.step, so the
    # SIGTERM-mid-rollout drill exercises the drain/journal/adopt path
    install_chaos(config_from_env())
    shutdown = GracefulShutdown().install()
    slo = build_slo_monitor(
        registry=get_registry(), run_dir=run_dir if primary else None
    )

    loop = RLLoop(
        trainer, objective, serve_config,
        RLLoopOptions(
            rounds=args.rounds,
            prompts_per_round=args.prompts_per_round,
            prompt_len=args.prompt_len,
            max_new_tokens=args.max_new_tokens,
            sync_mode=args.sync_mode,
            reward=args.reward,
            prompt_style=args.prompt_style,
            rollout_priority=args.rollout_priority,
            updates_per_round=args.updates_per_round,
            user_traffic=args.user_traffic,
            yield_steps=args.yield_steps,
            resume_step=int(args.ckpt_path) if args.ckpt_path else None,
        ),
        slo=slo,
    )
    loop.setup()
    engine = loop.engine
    exporter = start_exporter(
        registry=get_registry(),
        slo=slo,
        role="rl-fit",
        extra_fn=lambda: {**engine.live_stats(), **loop.collector.stats()},
        status_fn=lambda: {
            "engine step": engine._step_index,
            "queue depth": len(engine.scheduler.waiting),
            "running": len(engine.scheduler.running),
        },
    )

    # rollout journal: same rotation contract as serve — the backup
    # survives until every entry is re-accepted into the fresh journal
    journal_path = (
        run_dir / "rl-journal.jsonl"
        if run_dir is not None and primary else None
    )
    backup_path = None
    resumed = []
    if journal_path is not None:
        backup_path = journal_path.with_name("rl-journal.replaying.jsonl")
        if journal_path.exists():
            with open(backup_path, "a") as backup:
                backup.write(journal_path.read_text())
            journal_path.unlink()
        if backup_path.exists():
            resumed = replay_journal(backup_path)
        engine.attach_journal(
            RequestJournal(journal_path), every=args.journal_every
        )
    if resumed:
        log.warning(
            "replaying %d journaled rollout(s) from the previous rl-fit "
            "process", len(resumed),
        )
        # adopt FIRST so the replayed token events route into the
        # collector's pending entries instead of the foreign path
        loop.collector.adopt(resumed)
        for entry in resumed:
            loop.collector.ingest(engine.submit_resumed(entry))
    if backup_path is not None and backup_path.exists():
        backup_path.unlink()

    result = loop.run(
        shutdown=shutdown,
        emit=lambda record: print(json.dumps(record), flush=True),
    )
    rc = RESUMABLE_EXIT_CODE if result["interrupted"] else 0
    if rc:
        log.warning(
            "%s: rollouts journaled and round cursor checkpointed — "
            "exiting %d (resumable)",
            shutdown.reason, RESUMABLE_EXIT_CODE,
        )
    stats = result["gauges"]
    if trace_attached:
        get_tracer().detach_sink()
    print(json.dumps({"type": "stats", "stats": stats}), flush=True)
    _publish_run_telemetry(config, stats)
    if engine.journal is not None and rc == 0:
        engine.journal.close()
        if journal_path is not None:
            journal_path.unlink(missing_ok=True)
    if exporter is not None:
        exporter.stop()
    uninstall_chaos()
    shutdown.uninstall()
    return rc


def _scalar_eos(model_config) -> int | None:
    """The config's eos id when it is a single int (list-valued eos —
    Llama-3.x instruct — would need multi-token stop support; decode then
    runs to max_new_tokens)."""
    eos = getattr(model_config, "eos_token_id", None)
    return eos if isinstance(eos, int) else None


def _run_evaluate(args, config: dict) -> int:
    import json

    from llm_training_tpu.infer import run_evaluation

    trainer, objective, datamodule = _build(config)
    _require_single_model_objective(objective, "evaluate")
    state = trainer.restore_for_inference(
        objective, int(args.ckpt_path) if args.ckpt_path else None
    )
    result = run_evaluation(
        objective, state, datamodule, trainer.mesh,
        state_shardings=trainer.state_shardings,
        limit_batches=args.limit_batches,
        split=args.split,
    )
    print(json.dumps(result))
    _publish_run_telemetry(config, result)
    return 0


def _run_supervise(args) -> int:
    """`supervise`: relaunch `fit` — or, with `--child serve`, the serving
    tier — on exit 75 and hard deaths (docs/resilience.md#supervise). Pure
    subprocess driving — no jax. A relaunched serve child replays its
    request journal (docs/serving.md#resilience) before reading stdin,
    which the children inherit from this process."""
    import shlex

    from llm_training_tpu.resilience.supervisor import (
        Supervisor,
        SupervisorConfig,
        build_fit_argv,
        build_serve_argv,
    )

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s",
        # the serve protocol owns stdout: supervisor chatter on it would
        # interleave with the child's JSONL chunk stream
        stream=sys.stderr if args.child == "serve" else sys.stdout,
    )
    child_args = list(args.overrides) + shlex.split(args.child_args or "")
    log_path = args.log
    if log_path is None:
        # no explicit --log: land the churn log in the run directory (when
        # the config names one) so `report <run_dir>` finds it without
        # --supervisor-log — otherwise supervise would write to cwd and
        # report look in the run dir, and they'd never meet. load_config
        # and _jsonl_run_dir_jaxfree are yaml/stdlib-only, preserving the
        # no-jax-in-supervisor invariant
        log_path = "supervisor.jsonl"
        try:
            # dotted overrides may ride in --child-args (the serve path,
            # where positional overrides and serve flags share one
            # channel); only override-shaped tokens matter for the run dir
            overrides = [
                token for token in child_args
                if "=" in token and not token.startswith("-")
            ]
            run_dir = _jsonl_run_dir_jaxfree(
                load_config(args.config, overrides)
            )
            if run_dir is not None:
                log_path = str(run_dir / "supervisor.jsonl")
        except Exception:
            pass  # unparseable config: the child will report it properly
    log_path = log_path or None  # '' disables
    config = SupervisorConfig(
        max_restarts=args.max_restarts,
        backoff_base_s=args.backoff_base_s,
        backoff_max_s=args.backoff_max_s,
        log_path=log_path,
        min_devices=args.min_devices,
        probe_backoff_s=args.probe_backoff_s,
        probe_max_wait_s=args.probe_max_wait_s,
    )
    build = build_serve_argv if args.child == "serve" else build_fit_argv
    supervisor = Supervisor(
        build(args.config, child_args, ckpt_path=args.ckpt_path),
        config=config,
        # relaunches drop any explicit --ckpt-path: they must restore the
        # NEWEST checkpoint, not rewind to the pinned step every restart
        relaunch_argv=build(args.config, child_args),
    )
    return supervisor.run()


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="llm-training-tpu")
    sub = parser.add_subparsers(dest="command", required=True)
    for command in ("fit", "validate"):
        p = sub.add_parser(command)
        p.add_argument("--config", required=True)
        p.add_argument("--ckpt-path", default=None, help="checkpoint dir/step to resume")
        p.add_argument(
            "overrides", nargs="*", help="dotted config overrides: trainer.max_steps=100"
        )
    generate = sub.add_parser(
        "generate", help="KV-cache decoding from a run's checkpoint"
    )
    generate.add_argument("--config", required=True)
    generate.add_argument("--ckpt-path", default=None, help="checkpoint step to restore")
    generate.add_argument(
        "--prompt-tokens", action="append", default=None,
        metavar="IDS", help="comma-separated token ids (repeatable)",
    )
    generate.add_argument(
        "--prompt", action="append", default=None,
        help="text prompt (repeatable; needs a tokenizer in the data config)",
    )
    generate.add_argument("--max-new-tokens", type=int, default=32)
    generate.add_argument(
        "--max-length", type=int, default=None,
        help="KV-cache capacity (default: prompt width + max_new_tokens)",
    )
    generate.add_argument(
        "--cache-dtype", default=None, choices=("param", "float32", "bfloat16"),
        help="KV-cache storage dtype (default: the model's param dtype)",
    )
    generate.add_argument("--temperature", type=float, default=0.0)
    generate.add_argument("--top-k", type=int, default=None)
    generate.add_argument("--top-p", type=float, default=None)
    generate.add_argument("--seed", type=int, default=0)
    generate.add_argument(
        "--eos-token-id", type=int, default=None,
        help="stop token (default: the model config's scalar eos, if any)",
    )
    generate.add_argument(
        "--logprobs", action="store_true",
        help="include each generated token's logprob (under the sampled "
        "temperature/top-k/top-p distribution; raw log-softmax when "
        "greedy) in the output records",
    )
    generate.add_argument("overrides", nargs="*")
    serve = sub.add_parser(
        "serve",
        help="continuous-batching generation server: JSONL requests on "
        "stdin, streamed token/done chunks on stdout (docs/serving.md)",
    )
    serve.add_argument("--config", required=True)
    serve.add_argument("--ckpt-path", default=None, help="checkpoint step to restore")
    serve.add_argument(
        "--max-batch", type=int, default=4, help="decode slots (static batch)"
    )
    serve.add_argument(
        "--max-model-len", type=int, default=256,
        help="per-request cap: prompt + generated tokens",
    )
    serve.add_argument(
        "--block-size", type=int, default=None,
        help="KV-pool tokens per block (default: PAGED_BLOCK_K env > "
        "tuning table > 16)",
    )
    serve.add_argument(
        "--num-blocks", type=int, default=None,
        help="KV-pool capacity in blocks (default: max_batch full-length "
        "requests — no block pressure)",
    )
    serve.add_argument(
        "--prefill-chunk", type=int, default=32,
        help="prompt tokens prefilled per step (interleaved with decode)",
    )
    serve.add_argument(
        "--max-new-tokens", type=int, default=32,
        help="default generation budget for requests that omit it",
    )
    serve.add_argument(
        "--max-queue", type=int, default=None,
        help="intake bound: queued requests past this are shed with "
        "stop_reason='overloaded' (lowest priority first); default "
        "unbounded (docs/serving.md#resilience)",
    )
    serve.add_argument(
        "--shed-ttft-ms", type=float, default=None,
        help="shed queued requests whose projected TTFT (EMA service-time "
        "estimate) crosses this many ms; default off",
    )
    serve.add_argument(
        "--drain-timeout-s", type=float, default=30.0,
        help="SIGTERM grace: finish in-flight requests for up to this "
        "long, then evict-and-journal the rest and exit 75 (resumable)",
    )
    serve.add_argument(
        "--watchdog-timeout-s", type=float, default=0.0,
        help="abort (SIGABRT, after a flight dump) when an engine step "
        "makes no progress for this long, so `supervise` can relaunch; "
        "0 disables (default)",
    )
    serve.add_argument(
        "--journal-every", type=int, default=1,
        help="engine steps between request-journal progress checkpoints "
        "(1 = every step; drain always journals)",
    )
    serve.add_argument(
        "--cache-dtype", default=None, choices=("param", "float32", "bfloat16")
    )
    serve.add_argument("--temperature", type=float, default=0.0)
    serve.add_argument("--top-k", type=int, default=None)
    serve.add_argument("--top-p", type=float, default=None)
    serve.add_argument("--seed", type=int, default=0)
    serve.add_argument(
        "--eos-token-id", type=int, default=None,
        help="stop token (default: the model config's scalar eos, if any)",
    )
    serve.add_argument("overrides", nargs="*")
    rl_fit = sub.add_parser(
        "rl-fit",
        help="on-policy GRPO post-training: rollouts through the serving "
        "engine, group-relative policy-gradient updates, on-device weight "
        "sync each round (docs/post-training.md)",
    )
    rl_fit.add_argument("--config", required=True)
    rl_fit.add_argument(
        "--ckpt-path", default=None,
        help="checkpoint step to restore the policy from (default: newest; "
        "fresh seed-init when none exists)",
    )
    rl_fit.add_argument("--rounds", type=int, default=4)
    rl_fit.add_argument(
        "--prompts-per-round", type=int, default=2,
        help="prompt groups per round (x the objective's group_size "
        "samples each)",
    )
    rl_fit.add_argument(
        "--prompt-len", type=int, default=4,
        help="synthetic prompt length (deterministic in seed and round)",
    )
    rl_fit.add_argument("--max-new-tokens", type=int, default=8)
    rl_fit.add_argument(
        "--sync-mode", default="fused", choices=("fused", "host"),
        help="trainer->engine weight sync: fused = on-device resharding "
        "(default), host = device_get/device_put round-trip (the "
        "correctness oracle; docs/post-training.md#weight-sync)",
    )
    rl_fit.add_argument(
        "--reward", default=None,
        help="verifiable reward name (copy_digit/regex/numeric_answer/"
        "length; default: LLMT_RL_REWARD, else copy_digit)",
    )
    rl_fit.add_argument(
        "--prompt-style", default="uniform", choices=("uniform", "repeat"),
        help="synthetic prompt shape: uniform random tokens, or one digit "
        "repeated (the copy-the-digit smoke task)",
    )
    rl_fit.add_argument(
        "--updates-per-round", type=int, default=1,
        help="PPO-style epochs over each round's batch (the clipped "
        "importance ratio keeps >1 sound)",
    )
    rl_fit.add_argument(
        "--rollout-priority", type=int, default=-1,
        help="scheduler priority class for rollout requests (default -1: "
        "below user traffic's 0, so contention sheds rollouts first)",
    )
    rl_fit.add_argument(
        "--user-traffic", type=int, default=0,
        help="synthetic priority-0 user requests submitted per round "
        "alongside the rollouts (their latencies feed the serve SLO "
        "windows; rollout latencies do not)",
    )
    rl_fit.add_argument(
        "--yield-steps", type=int, default=50,
        help="engine steps rollout submission backs off after a NEW serve "
        "SLO burn-rate breach (LLMT_SLO_* targets arm the monitor)",
    )
    rl_fit.add_argument(
        "--max-batch", type=int, default=4, help="decode slots (static batch)"
    )
    rl_fit.add_argument("--max-model-len", type=int, default=256)
    rl_fit.add_argument("--block-size", type=int, default=None)
    rl_fit.add_argument("--num-blocks", type=int, default=None)
    rl_fit.add_argument("--prefill-chunk", type=int, default=32)
    rl_fit.add_argument(
        "--max-queue", type=int, default=None,
        help="intake bound; overflow sheds lowest-priority (rollouts) first",
    )
    rl_fit.add_argument(
        "--journal-every", type=int, default=1,
        help="engine steps between rollout-journal progress checkpoints",
    )
    rl_fit.add_argument(
        "--cache-dtype", default=None, choices=("param", "float32", "bfloat16")
    )
    rl_fit.add_argument(
        "--temperature", type=float, default=1.0,
        help="rollout sampling temperature (must be > 0 for exploration)",
    )
    rl_fit.add_argument("--top-k", type=int, default=None)
    rl_fit.add_argument("--top-p", type=float, default=None)
    rl_fit.add_argument("--seed", type=int, default=0)
    rl_fit.add_argument("--eos-token-id", type=int, default=None)
    rl_fit.add_argument("overrides", nargs="*")
    evaluate = sub.add_parser(
        "evaluate", help="packed perplexity / per-token NLL from a checkpoint"
    )
    evaluate.add_argument("--config", required=True)
    evaluate.add_argument("--ckpt-path", default=None, help="checkpoint step to restore")
    evaluate.add_argument("--limit-batches", type=int, default=None)
    evaluate.add_argument("--split", default="val", choices=("val", "train"))
    evaluate.add_argument("overrides", nargs="*")
    report = sub.add_parser("report", help="render a run summary from a run directory")
    report.add_argument("run_dir", help="dir holding metrics.jsonl / telemetry.jsonl")
    report.add_argument(
        "--supervisor-log", default=None,
        help="supervisor.jsonl with per-segment topology events "
        "(== Elastic == section); default: <run_dir>/supervisor.jsonl",
    )
    report.add_argument(
        "--audit-dir", default=None,
        help="dir searched first for the newest audit*.json shardcheck "
        "record (== Audit == section); falls back to run_dir",
    )
    report.add_argument(
        "--format", default="text", choices=("text", "json"),
        help="json emits every section as one machine-readable object "
        "(schema_version-pinned — for CI trend tracking)",
    )
    watch = sub.add_parser(
        "watch",
        help="poll a live run's /statusz (the LLMT_METRICS_PORT exporter) "
        "and print each snapshot (docs/observability.md#live-telemetry)",
    )
    watch.add_argument(
        "--port", type=int, default=None,
        help="exporter port (default: LLMT_METRICS_PORT)",
    )
    watch.add_argument("--host", default="127.0.0.1")
    watch.add_argument(
        "--interval-s", type=float, default=2.0, help="poll cadence",
    )
    watch.add_argument(
        "--once", action="store_true",
        help="one snapshot then exit (exit 2 when unreachable)",
    )
    profile = sub.add_parser(
        "profile",
        help="arm a device-profile capture on a live run via its exporter's "
        "/profilez endpoint (docs/observability.md#profiling); exit 0 when "
        "armed, 3 when the trigger refused (budget/cooldown/busy), 2 when "
        "unreachable",
    )
    profile.add_argument(
        "--port", type=int, default=None,
        help="exporter port (default: LLMT_METRICS_PORT)",
    )
    profile.add_argument("--host", default="127.0.0.1")
    profile.add_argument(
        "--tag", default=None,
        help="artifact tag (profile-<tag>/ in the run dir; default: a "
        "profilez-<n> serial)",
    )
    trace = sub.add_parser(
        "trace",
        help="export a run's trace.jsonl as Chrome-trace JSON viewable in "
        "Perfetto (docs/observability.md#tracing)",
    )
    trace.add_argument(
        "source", nargs="?", default=None,
        help="run directory holding trace.jsonl, or a trace/flight-dump "
        "jsonl file directly",
    )
    trace.add_argument(
        "--out", default=None,
        help="output path (default: trace-export.json next to the source)",
    )
    trace.add_argument(
        "--merge", nargs="+", default=None, metavar="DIR",
        help="instead of one source: wall-align N run dirs (via their "
        "clock_anchor events) into ONE Perfetto file with per-replica "
        "tracks (docs/observability.md#fleet)",
    )
    fleet = sub.add_parser(
        "fleet",
        help="sweep a fleet of replicas (LLMT_FLEET_DIR cards or static "
        "--targets) and render rollups + health verdict; optionally "
        "re-export federation /metrics (docs/observability.md#fleet)",
    )
    fleet.add_argument(
        "--dir", default=None,
        help="discovery directory holding replica-*.json cards "
        "(default: LLMT_FLEET_DIR)",
    )
    fleet.add_argument(
        "--targets", default="",
        help="static host:port,host:port replica list (skips discovery)",
    )
    fleet.add_argument(
        "--interval-s", type=float, default=None,
        help="sweep cadence (default: LLMT_FLEET_SCRAPE_S, else 2s)",
    )
    fleet.add_argument(
        "--port", type=int, default=None,
        help="also serve the aggregator's /metrics //fleetz //healthz "
        "federation endpoint on this port",
    )
    fleet.add_argument(
        "--once", action="store_true",
        help="one sweep then exit (exit 2, naming the searched paths, "
        "when no replicas are found)",
    )
    fleet.add_argument(
        "--json", action="store_true",
        help="emit the raw snapshot JSON instead of the fleetz one-pager",
    )
    fleet.add_argument(
        "--out", default=None,
        help="also write the snapshot JSON here (a run dir's fleet.json "
        "is what `report --format json` surfaces as its fleet block)",
    )
    supervise = sub.add_parser(
        "supervise",
        help="run fit (or, with --child serve, the serving tier) as a "
        "supervised child process; restart it on preemption (exit 75) and "
        "hard deaths (SIGKILL/segfault/SIGABRT)",
    )
    supervise.add_argument("--config", required=True)
    supervise.add_argument(
        "--child", default="fit", choices=("fit", "serve"),
        help="the supervised subcommand; a relaunched serve child replays "
        "its request journal before reading stdin (docs/serving.md)",
    )
    supervise.add_argument(
        "--child-args", default="",
        help="extra flags/overrides for the child, as one shell-quoted "
        "string (e.g. --child-args '--max-batch 2 run_root=/tmp/x') — the "
        "channel for serve flags the supervise parser does not know",
    )
    supervise.add_argument(
        "--ckpt-path", default=None,
        help="explicit resume step for the FIRST launch only (relaunches "
        "always restore the newest checkpoint)",
    )
    supervise.add_argument("--max-restarts", type=int, default=10)
    supervise.add_argument("--backoff-base-s", type=float, default=1.0)
    supervise.add_argument("--backoff-max-s", type=float, default=300.0)
    supervise.add_argument(
        "--min-devices", type=int, default=None,
        help="elastic capacity gate: before each relaunch, probe the "
        "visible device count (in a subprocess) and wait while it is below "
        "this minimum (docs/resilience.md#elastic); default: relaunch blind",
    )
    supervise.add_argument(
        "--probe-backoff-s", type=float, default=5.0,
        help="sleep between capacity probes while below --min-devices",
    )
    supervise.add_argument(
        "--probe-max-wait-s", type=float, default=300.0,
        help="give up (propagating the child's exit code) after waiting "
        "this long for --min-devices",
    )
    supervise.add_argument(
        "--log", default=None,
        help="supervisor event log path ('' disables). Default: "
        "supervisor.jsonl in the config's run directory when it names one "
        "(where `report` looks), else the cwd; an explicit path — "
        "including './supervisor.jsonl' — is used as given",
    )
    supervise.add_argument("overrides", nargs="*")
    ckpt = sub.add_parser(
        "ckpt",
        help="checkpoint durability operations over a checkpoint root "
        "(and its mirror): verify manifests, list steps, retention GC, "
        "force a mirror pass (docs/resilience.md#durability)",
    )
    ckpt_sub = ckpt.add_subparsers(dest="ckpt_command", required=True)
    for name, help_text in (
        ("verify", "check every committed step against its integrity "
         "manifest; exit 1 with each offending file named on findings"),
        ("ls", "list committed steps and their manifest status"),
        ("gc", "apply the retention policy (keep-last-N + keep-every-K; "
         "never the newest step, never the last intact copy)"),
        ("mirror", "mirror every manifested step now (tmp-then-rename + "
         "manifest re-verification on the copy)"),
    ):
        p = ckpt_sub.add_parser(name, help=help_text)
        p.add_argument("dir", help="checkpoint root (the orbax step parent)")
        p.add_argument(
            "--mirror-dir", default=None,
            help="mirror root (default: LLMT_CKPT_MIRROR_DIR)",
        )
        if name == "verify":
            p.add_argument(
                "--mode", default="fast", choices=("fast", "full"),
                help="fast = file set + sizes; full = re-hash every file",
            )
            p.add_argument(
                "--step", type=int, default=None,
                help="verify only this step (default: every committed step)",
            )
        if name == "gc":
            p.add_argument("--keep-last", type=int, default=3)
            p.add_argument(
                "--keep-every", type=int, default=None,
                help="also keep every step divisible by K",
            )
            p.add_argument("--dry-run", action="store_true")
    route = sub.add_parser(
        "route",
        help="health-aware router over N serve replicas: same JSONL "
        "protocol as serve on stdin/stdout, least-loaded routing with "
        "eviction on red/stale health, failover replay with exactly-once "
        "terminals, hedged retries, and SLO-driven elasticity "
        "(docs/serving.md#router)",
    )
    route.add_argument("--config", required=True)
    route.add_argument(
        "--ckpt-path", default=None,
        help="checkpoint step each serve replica restores",
    )
    route.add_argument(
        "--replicas", type=int, default=2,
        help="initial AND minimum serve replica count",
    )
    route.add_argument(
        "--max-replicas", type=int, default=None,
        help="elasticity ceiling (default: --replicas, i.e. scale-out off)",
    )
    route.add_argument(
        "--hedge-ttft-ms", type=float, default=0.0,
        help="hedge a request onto a second replica when its projected "
        "TTFT crosses this budget (deadline_ms, when set on the request, "
        "takes precedence); 0 disables (default)",
    )
    route.add_argument(
        "--scrape-interval-s", type=float, default=None,
        help="fleet health sweep cadence (default: LLMT_FLEET_SCRAPE_S, "
        "else 2s)",
    )
    route.add_argument(
        "--idle-retire-s", type=float, default=0.0,
        help="drain-and-retire one replica (down to --replicas) after this "
        "long with no traffic; 0 disables (default)",
    )
    route.add_argument(
        "--scale-cooldown-s", type=float, default=30.0,
        help="minimum seconds between scale events",
    )
    route.add_argument(
        "--drain-timeout-s", type=float, default=30.0,
        help="SIGTERM grace before journaling the remainder and exiting 75",
    )
    route.add_argument(
        "--replica-run-root", default=None,
        help="parent dir for per-replica run roots (default: "
        "<run_dir>/replicas); each replica gets run_root=<root>/rN",
    )
    route.add_argument(
        "--seed-run-dir", default=None,
        help="run dir whose checkpoints/ seeds each fresh replica "
        "(default: the router's own run dir when it has one)",
    )
    route.add_argument(
        "serve_args", nargs="*",
        help="flags/overrides forwarded to every serve replica — pass "
        "after `--` (e.g. -- --max-batch 2 --eos-token-id -1)",
    )
    args = parser.parse_args(argv)

    if args.command == "report":
        from llm_training_tpu.telemetry.report import report_main

        return report_main(
            args.run_dir,
            supervisor_log=args.supervisor_log,
            audit_dir=args.audit_dir,
            format=args.format,
        )
    if args.command == "trace":
        # stdlib-only like report: exports run anywhere the dir is mounted
        from llm_training_tpu.telemetry.trace import trace_main

        return trace_main(args.source, out=args.out, merge=args.merge)
    if args.command == "fleet":
        # stdlib-only: the aggregator is a scrape parent — it must run on
        # operator machines with no backend while replicas own theirs
        from llm_training_tpu.telemetry.fleet import fleet_main

        return fleet_main(
            fleet_dir=args.dir, targets=args.targets,
            interval_s=args.interval_s, port=args.port,
            once=args.once, as_json=args.json, out=args.out,
        )
    if args.command == "watch":
        # stdlib-only: the watcher polls a running process's exporter and
        # must never pay a backend import (or it could not watch a wedged
        # run from the same machine)
        from llm_training_tpu.telemetry.exporter import watch_main

        return watch_main(
            port=args.port, host=args.host,
            interval_s=args.interval_s, once=args.once,
        )
    if args.command == "profile":
        # stdlib-only: one GET against the live run's /profilez — the run
        # process owns jax.profiler; this side only arms the trigger
        from llm_training_tpu.telemetry.exporter import profile_main

        return profile_main(port=args.port, host=args.host, tag=args.tag)
    if args.command == "ckpt":
        # jax-free like report/fleet: verifying or mirroring a checkpoint
        # tree must work on operator machines with no backend (and must
        # never hold the devices of the run it is inspecting)
        from llm_training_tpu.resilience.durability import ckpt_main

        return ckpt_main(args)
    if args.command == "supervise":
        # the supervisor must never initialize jax — it would hold the TPU
        # its child needs; hand off before any backend-touching import
        return _run_supervise(args)
    if args.command == "route":
        # the router is a jax-free control plane over serve children — the
        # children own the backend; initializing jax here would hold the
        # very devices the replicas need
        from llm_training_tpu.serve.router import route_main

        return route_main(args)

    # the start-up timeline (docs/observability.md#tracing): pinned spans,
    # which the run directory's trace.jsonl takes when its sink is attached
    from llm_training_tpu.telemetry.trace import get_tracer

    tracer = get_tracer()
    with tracer.measure("setup", "config", pin=True):
        config = load_config(args.config, args.overrides)
        logging.basicConfig(
            level=getattr(logging, str(config.get("logging_level", "INFO")).upper()),
            format="%(asctime)s %(levelname)s %(name)s: %(message)s",
            stream=sys.stdout,
        )
        _seed_everything(int(config.get("seed_everything", 42)))

    with tracer.measure("setup", "backend", pin=True):
        # multi-host rendezvous must precede any jax use
        from llm_training_tpu.parallel import initialize_distributed

        initialize_distributed()
        _apply_extra_config(config)
        import jax

        # the backend's bring-up: here, not inside whoever asks first
        jax.devices()

    if args.command == "generate":
        return _run_generate(args, config)
    if args.command == "serve":
        return _run_serve(args, config)
    if args.command == "rl-fit":
        return _run_rl_fit(args, config)
    if args.command == "evaluate":
        return _run_evaluate(args, config)

    with tracer.measure("setup", "model_build", pin=True):
        trainer, objective, datamodule = _build(config)

    resume_step = int(args.ckpt_path) if args.ckpt_path else None
    if args.command == "fit":
        from llm_training_tpu.callbacks.nan_guard import (
            LossSpikeError,
            NonFiniteLossError,
        )
        from llm_training_tpu.resilience import (
            LOSS_SPIKE_EXIT_CODE,
            NON_FINITE_EXIT_CODE,
            RECOVERY_EXHAUSTED_EXIT_CODE,
            RESUMABLE_EXIT_CODE,
            PreemptionInterrupt,
            RecoveryExhaustedError,
        )

        log = logging.getLogger(__name__)
        try:
            trainer.fit(objective, datamodule, resume_step=resume_step)
        except PreemptionInterrupt as e:
            # supervisor contract (docs/resilience.md#exit-codes): exit 75
            # = the run was preempted AFTER committing a resumable
            # checkpoint — relaunch this same command to continue
            log.warning("%s — exiting with resumable code %d", e, RESUMABLE_EXIT_CODE)
            return RESUMABLE_EXIT_CODE
        except RecoveryExhaustedError as e:
            # in-process recovery gave up: a blind relaunch would reproduce
            # the failure — a human (or a config change) is needed
            log.error("%s — exiting %d", e, RECOVERY_EXHAUSTED_EXIT_CODE)
            return RECOVERY_EXHAUSTED_EXIT_CODE
        except LossSpikeError as e:
            log.error("%s — exiting %d", e, LOSS_SPIKE_EXIT_CODE)
            return LOSS_SPIKE_EXIT_CODE
        except NonFiniteLossError as e:
            log.error("%s — exiting %d", e, NON_FINITE_EXIT_CODE)
            return NON_FINITE_EXIT_CODE
    else:
        trainer.validate_from_checkpoint(objective, datamodule, resume_step=resume_step)
    return 0


if __name__ == "__main__":
    sys.exit(main())
