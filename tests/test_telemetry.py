"""Telemetry subsystem: registry, goodput ledger, device gauges, report CLI.

The ledger tests use an injected fake clock, so phase classification is
asserted deterministically — no sleeps. The integration test runs a real
tiny fit and checks the acceptance contract: telemetry.jsonl carries
goodput%, per-phase seconds, HBM gauges, and compile_time_s; phases sum to
the ledger total; and `report` renders it with exit code 0.
"""

import json
import threading

import pytest

from llm_training_tpu.telemetry import (
    GoodputLedger,
    TelemetryRegistry,
    get_registry,
    hbm_gauges,
    set_registry,
)
from llm_training_tpu.telemetry.goodput import PHASES
from llm_training_tpu.telemetry.report import render_report, report_main


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now

    def advance(self, seconds):
        self.now += seconds


# ------------------------------------------------------------ registry


def test_registry_counters_gauges_timers():
    reg = TelemetryRegistry()
    reg.counter("events").inc()
    reg.counter("events").inc(2)
    reg.gauge("hbm/peak").set(42.0)
    timer = reg.timer("io")
    timer.add(0.5)
    timer.add(1.5)
    snap = reg.snapshot()
    assert snap["events"] == 3.0
    assert snap["hbm/peak"] == 42.0
    assert snap["io_s"] == 2.0
    assert snap["io_n"] == 2.0
    # unset gauges are omitted, not emitted as None
    reg.gauge("unset")
    assert "unset" not in reg.snapshot()


def test_registry_timer_context_manager_counts_on_exception():
    reg = TelemetryRegistry(clock=FakeClock())
    timer = reg.timer("t")
    with pytest.raises(RuntimeError):
        with timer.time():
            raise RuntimeError("boom")
    assert timer.count == 1


def test_registry_thread_safety():
    reg = TelemetryRegistry()
    counter = reg.counter("n")

    def hammer():
        for _ in range(1000):
            counter.inc()
            reg.timer("t").add(0.001)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["n"] == 8000.0
    assert snap["t_n"] == 8000.0


def test_current_registry_install_and_restore():
    mine = TelemetryRegistry()
    previous = set_registry(mine)
    try:
        assert get_registry() is mine
    finally:
        set_registry(previous)
    assert get_registry() is previous


# ------------------------------------------------------------ goodput ledger


def test_ledger_phase_classification_sums_to_total():
    """Satellite: fake-clock phase classification — injected checkpoint,
    validation, and data-stall phases must land in their buckets, sum (with
    `other`) to total wall time, and yield the right goodput%."""
    clock = FakeClock()
    ledger = GoodputLedger(clock=clock)
    ledger.start()

    with ledger.measure("compile"):
        clock.advance(10.0)
    for _ in range(4):
        with ledger.measure("data_wait"):
            clock.advance(2.0)  # injected data stall
        with ledger.measure("step_compute"):
            clock.advance(15.0)
    with ledger.measure("checkpoint_save"):
        clock.advance(5.0)
    with ledger.measure("validation"):
        clock.advance(7.0)
    clock.advance(10.0)  # unattributed host time -> other

    s = ledger.summary()
    assert s["goodput/compile_s"] == 10.0
    assert s["goodput/data_wait_s"] == 8.0
    assert s["goodput/step_compute_s"] == 60.0
    assert s["goodput/checkpoint_save_s"] == 5.0
    assert s["goodput/validation_s"] == 7.0
    assert s["goodput/other_s"] == 10.0
    assert s["goodput/total_s"] == 100.0
    phase_sum = sum(s[f"goodput/{p}_s"] for p in PHASES + ("other",))
    assert phase_sum == pytest.approx(s["goodput/total_s"])
    assert s["goodput/goodput_pct"] == pytest.approx(60.0)


def test_ledger_restart_zeroes_and_unknown_phase_rejected():
    clock = FakeClock()
    ledger = GoodputLedger(clock=clock)
    # summary before start: all zeros, no division by zero
    s = ledger.summary()
    assert s["goodput/total_s"] == 0.0 and s["goodput/goodput_pct"] == 0.0
    ledger.start()
    with ledger.measure("step_compute"):
        clock.advance(3.0)
    ledger.start()  # restart zeroes phases
    assert ledger.summary()["goodput/step_compute_s"] == 0.0
    with pytest.raises(KeyError):
        ledger.note("not_a_phase", 1.0)


def test_ledger_note_accumulates_externally_measured_time():
    clock = FakeClock()
    ledger = GoodputLedger(clock=clock)
    ledger.start()
    ledger.note("checkpoint_save", 2.5)
    ledger.note("checkpoint_save", 1.5)
    clock.advance(8.0)
    s = ledger.summary()
    assert s["goodput/checkpoint_save_s"] == 4.0
    assert s["goodput/other_s"] == pytest.approx(4.0)


# ------------------------------------------------------------ device gauges


def test_hbm_gauges_present_on_cpu():
    """CPU backends expose no memory_stats; the host-RSS fallback must still
    produce the gauges the acceptance contract asserts on."""
    gauges = hbm_gauges()
    assert "hbm/bytes_in_use" in gauges
    assert "hbm/peak_bytes_in_use" in gauges
    assert gauges["hbm/peak_bytes_in_use"] > 0


def test_compiled_cost_gauges_from_aot_step():
    import jax
    import numpy as np

    from llm_training_tpu.telemetry import compiled_cost_gauges

    compiled = jax.jit(lambda x: (x @ x).sum()).lower(
        np.ones((16, 16), np.float32)
    ).compile()
    gauges = compiled_cost_gauges(compiled)
    assert gauges.get("xla/flops_per_step", 0) > 0


# ------------------------------------------------------------ report


def _write_run_dir(tmp_path, with_telemetry=True):
    run_dir = tmp_path / "run1"
    run_dir.mkdir()
    metrics = [
        {"step": 2, "loss": 5.0, "grad_norm": 1.0, "steps_per_sec": 2.0,
         "consumed_tokens": 512, "consumed_samples": 16},
        {"step": 4, "loss": 4.0, "grad_norm": 0.9, "steps_per_sec": 2.5,
         "consumed_tokens": 1024, "consumed_samples": 32},
        {"step": 4, "val_loss": 4.2},
    ]
    (run_dir / "metrics.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in metrics)
    )
    if with_telemetry:
        telemetry = {
            "step": 4,
            "goodput/compile_s": 3.0,
            "goodput/data_wait_s": 1.0,
            "goodput/step_compute_s": 14.0,
            "goodput/checkpoint_save_s": 1.0,
            "goodput/validation_s": 0.5,
            "goodput/other_s": 0.5,
            "goodput/total_s": 20.0,
            "goodput/goodput_pct": 70.0,
            "hbm/peak_bytes_in_use": 2.0 * 1024**3,
            "hbm/bytes_limit": 16.0 * 1024**3,
            "compile_time_s": 3.0,
            "perf/mfu": 0.55,
        }
        (run_dir / "telemetry.jsonl").write_text(json.dumps(telemetry) + "\n")
    return run_dir


def test_report_renders_goodput_table(tmp_path):
    text = render_report(_write_run_dir(tmp_path))
    assert "goodput: 70.0%" in text
    for phase in PHASES + ("other", "total"):
        assert phase in text
    assert "loss: first 5.0000 -> last 4.0000" in text
    assert "MFU (analytic 6N+attention): 0.5500" in text
    assert "peak: 2.00 GiB (HBM) of 16.00 GiB limit (12%)" in text
    assert "val_loss: 4.2000" in text


def test_report_falls_back_to_metrics_embedded_telemetry(tmp_path):
    run_dir = _write_run_dir(tmp_path, with_telemetry=False)
    # goodput keys embedded in metrics.jsonl (W&B-style single stream)
    with open(run_dir / "metrics.jsonl", "a") as f:
        f.write(json.dumps({"step": 6, "loss": 3.5,
                            "goodput/step_compute_s": 9.0,
                            "goodput/total_s": 10.0,
                            "goodput/goodput_pct": 90.0}) + "\n")
    assert "goodput: 90.0%" in render_report(run_dir)


def test_report_uses_only_the_last_run_segment(tmp_path):
    """Re-running a fixed-name config appends a second run to the same
    files; a step-number reset marks the new run and the summary must not
    pool the two."""
    run_dir = _write_run_dir(tmp_path)
    with open(run_dir / "metrics.jsonl", "a") as f:  # second run, steps reset
        f.write(json.dumps({"step": 2, "loss": 9.0, "steps_per_sec": 1.0}) + "\n")
        f.write(json.dumps({"step": 4, "loss": 8.0, "steps_per_sec": 1.0}) + "\n")
    text = render_report(run_dir)
    assert "loss: first 9.0000 -> last 8.0000" in text
    assert "(2 records)" in text


def test_report_main_exit_codes(tmp_path, capsys):
    run_dir = _write_run_dir(tmp_path)
    assert report_main(str(run_dir)) == 0
    assert "Run report" in capsys.readouterr().out
    assert report_main(str(tmp_path / "nope")) == 2


def test_report_cli_subcommand(tmp_path, capsys):
    from llm_training_tpu.cli.main import main

    run_dir = _write_run_dir(tmp_path)
    assert main(["report", str(run_dir)]) == 0
    assert "== Goodput ==" in capsys.readouterr().out


def test_report_reads_no_bench_record(tmp_path, monkeypatch):
    """The repo's one measurement is the driver's benchmark: a record a
    deleted bench left in the run directory, or in the directory `report`
    is called from, is no section of the text form and no key of the JSON
    form."""
    from llm_training_tpu.telemetry.report import render_report_data

    run_dir = _write_run_dir(tmp_path)
    stray = json.dumps({"metric": "llama_clm_train_mfu", "value": 0.61, "stages": {}})
    (run_dir / "bench_x.json").write_text(stray)
    (tmp_path / "BENCH_r07.json").write_text(stray)
    monkeypatch.chdir(tmp_path)
    text = render_report(run_dir)
    assert "== Perf ==" not in text and "bench record" not in text
    assert "MFU (analytic 6N+attention): 0.5500" in text  # the run's own gauge stays
    assert "perf" not in render_report_data(run_dir)


def test_report_cli_refuses_a_bench_dir(tmp_path, capsys):
    from llm_training_tpu.cli.main import main

    run_dir = _write_run_dir(tmp_path)
    with pytest.raises(SystemExit) as refused:
        main(["report", str(run_dir), "--bench-dir", str(tmp_path)])
    assert refused.value.code == 2
    assert "unrecognized arguments: --bench-dir" in capsys.readouterr().err


# ------------------------------------------------------------ multihost guard


def test_jsonl_logger_silent_on_secondary_hosts(tmp_path, monkeypatch):
    """Satellite: only process 0 writes run-dir artifacts — N hosts
    appending to one metrics.jsonl corrupts multi-host runs."""
    import jax

    from llm_training_tpu.callbacks import JsonlLogger, JsonlLoggerConfig

    monkeypatch.setattr(jax, "process_index", lambda: 1)
    logger = JsonlLogger(JsonlLoggerConfig(save_dir=str(tmp_path), name="r"))
    logger.on_fit_start(None, None, None, 0)
    logger.on_step_end(None, 2, {"loss": 1.0, "goodput/total_s": 1.0})
    logger.on_fit_end(None, None)
    assert not logger.run_dir.exists()  # nothing written, not even the dir

    monkeypatch.setattr(jax, "process_index", lambda: 0)
    logger.on_step_end(None, 4, {"loss": 1.0, "goodput/total_s": 1.0})
    assert (logger.run_dir / "metrics.jsonl").exists()
    assert (logger.run_dir / "telemetry.jsonl").exists()


# ------------------------------------------------------------ integration


@pytest.mark.slow
def test_fit_writes_telemetry_and_report_renders(tmp_path):
    """Acceptance: a real tiny fit (with validation + checkpointing) must
    persist goodput%, per-phase seconds, HBM gauges, and compile_time_s to
    both JSONL streams; phase seconds must sum to the ledger total (within
    5%); and `report` must render the run dir with exit code 0."""
    from llm_training_tpu.callbacks import JsonlLogger, JsonlLoggerConfig
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig
    from llm_training_tpu.trainer.checkpoint import CheckpointConfig, Checkpointer

    objective = CLM(CLMConfig(model=ModelProvider(
        model_class="Llama",
        model_kwargs=dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
            max_position_embeddings=64, attention_impl="xla",
            param_dtype="float32", compute_dtype="float32",
        ),
    )))
    datamodule = DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=32, num_samples=128, vocab_size=128,
        validation_split=16,
    ))
    jsonl = JsonlLogger(JsonlLoggerConfig(save_dir=str(tmp_path), name="telem"))
    trainer = Trainer(
        TrainerConfig(
            max_steps=6, log_every_n_steps=2, val_check_interval=3,
            limit_val_batches=2, checkpoint_every_n_steps=5, mesh=MeshConfig(),
        ),
        callbacks=[jsonl],
        checkpointer=Checkpointer(CheckpointConfig(
            dirpath=str(tmp_path / "ckpt"), async_save=False,
        )),
    )
    trainer.fit(objective, datamodule)

    run_dir = jsonl.run_dir
    telemetry_lines = (run_dir / "telemetry.jsonl").read_text().splitlines()
    last = json.loads(telemetry_lines[-1])
    for key in (
        ["goodput/goodput_pct", "goodput/total_s", "goodput/other_s",
         "compile_time_s", "hbm/peak_bytes_in_use"]
        + [f"goodput/{p}_s" for p in PHASES]
    ):
        assert key in last, f"missing {key}"
    phase_sum = sum(last[f"goodput/{p}_s"] for p in PHASES + ("other",))
    assert phase_sum == pytest.approx(last["goodput/total_s"], rel=0.05)
    assert last["goodput/step_compute_s"] > 0
    assert last["goodput/compile_s"] > 0
    assert last["compile_time_s"] > 0
    assert 0 < last["goodput/goodput_pct"] <= 100
    # checkpoint (step 5) and validation (step 3) ran before the final log
    assert last["goodput/checkpoint_save_s"] > 0
    assert last["goodput/validation_s"] > 0
    # metrics.jsonl carries the same telemetry keys alongside loss/grad_norm
    records = [json.loads(l) for l in (run_dir / "metrics.jsonl").read_text().splitlines()]
    train_records = [r for r in records if "loss" in r]
    assert all("goodput/goodput_pct" in r for r in train_records)
    # the report CLI renders it
    from llm_training_tpu.cli.main import main

    assert main(["report", str(run_dir)]) == 0


@pytest.mark.slow
def test_variable_length_batches_fall_back_from_aot_step():
    """Pad-to-longest collators emit per-batch sequence lengths; the AOT
    executable is pinned to sample_batch's shapes, so the trainer must fall
    back to the jitted step (which recompiles) instead of aborting."""
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    class VarLenDataModule(DummyDataModule):
        def train_batches(self, start_step=0):
            for i, batch in enumerate(super().train_batches(start_step)):
                if i % 2 == 1:  # every other batch pads shorter
                    batch = {k: v[:, :24] for k, v in batch.items()}
                yield batch

    objective = CLM(CLMConfig(model=ModelProvider(
        model_class="Llama",
        model_kwargs=dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
            max_position_embeddings=64, attention_impl="xla",
            param_dtype="float32", compute_dtype="float32",
        ),
    )))
    datamodule = VarLenDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=32, num_samples=128, vocab_size=128,
    ))
    trainer = Trainer(
        TrainerConfig(max_steps=4, log_every_n_steps=2, mesh=MeshConfig()),
    )
    trainer.fit(objective, datamodule)
    assert trainer.last_step == 4
    assert float(trainer.last_metrics["loss"]) > 0


@pytest.mark.slow
def test_first_log_window_excludes_compile_time(tmp_path):
    """Satellite: steps_per_sec must not be dragged down by JIT compile —
    the window resets after step 1, and compile lands in compile_time_s."""
    from llm_training_tpu.callbacks import JsonlLogger, JsonlLoggerConfig
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig, ModelProvider
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    objective = CLM(CLMConfig(model=ModelProvider(
        model_class="Llama",
        model_kwargs=dict(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
            max_position_embeddings=64, attention_impl="xla",
            param_dtype="float32", compute_dtype="float32",
        ),
    )))
    datamodule = DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=32, num_samples=128, vocab_size=128,
    ))
    jsonl = JsonlLogger(JsonlLoggerConfig(save_dir=str(tmp_path), name="sps"))
    Trainer(
        TrainerConfig(max_steps=4, log_every_n_steps=2, mesh=MeshConfig()),
        callbacks=[jsonl],
    ).fit(objective, datamodule)
    records = [json.loads(l) for l in
               (jsonl.run_dir / "metrics.jsonl").read_text().splitlines()]
    first = records[0]
    assert first["compile_time_s"] > 0
    # window [1 -> 2] covers one compiled step; if compile leaked in, the
    # implied per-step time would exceed compile_time_s
    assert 1.0 / first["steps_per_sec"] < first["compile_time_s"]
