"""The program keeps its own start-up timeline (docs/observability.md#tracing,
"Start-up timeline"): the recorder's pinned store, the compile listener, and
the `setup/*` spans the serving engine and `Trainer.fit` leave in it."""

import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.serve import ServeConfig, ServingEngine
from llm_training_tpu.telemetry import (
    compile_totals,
    get_registry,
    install_compile_listener,
)
from llm_training_tpu.telemetry import profiling
from llm_training_tpu.telemetry.registry import TelemetryRegistry, set_registry
from llm_training_tpu.telemetry.trace import (
    PINNED_CAPACITY,
    TraceRecorder,
    read_trace_events,
    set_tracer,
    startup_lines,
    startup_summary,
)

ROOT = Path(__file__).resolve().parent.parent
TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, attention_impl="xla",
    compute_dtype="float32", param_dtype="float32",
)
SERVE = dict(max_batch=2, max_model_len=48, block_size=8, prefill_chunk=4, eos_token_id=None)
TRACE = "/jax/core/compile/jaxpr_trace_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.fixture()
def fresh():
    """A fresh process tracer and registry, restored afterwards."""
    tracer = TraceRecorder(capacity=8192, enabled=True)
    previous_tracer = set_tracer(tracer)
    previous_registry = set_registry(TelemetryRegistry())
    try:
        yield tracer
    finally:
        tracer.detach_sink()
        set_tracer(previous_tracer)
        set_registry(previous_registry)


def _names(events):
    return [f"{e['cat']}/{e['name']}" for e in events]


def _funs(totals, kind):
    return {fun: row["count"] for fun, row in totals["by_fun"].get(kind, {}).items()}


# ---------------------------------------------------------- the pinned store


def test_a_pinned_event_survives_the_ring_and_the_store_stays_bounded():
    tracer = TraceRecorder(capacity=64, enabled=True)
    tracer.instant("setup", "ready", pin=True, ready_s=1.0)
    with tracer.measure("setup", "engine_init", pin=True):
        pass
    for step in range(10_000):
        tracer.span("serve", "engine_step", 0.0, 1.0, write=False, step=step)
    assert _names(tracer.pinned()) == ["setup/ready", "setup/engine_init"]
    assert "setup/ready" not in _names(tracer.snapshot())  # the ring forgot it
    assert len(tracer.snapshot()) == 64
    for n in range(PINNED_CAPACITY + 100):
        tracer.instant("serve", "stall", pin=True, n=n)
    pinned = tracer.pinned()
    assert len(pinned) == PINNED_CAPACITY
    assert pinned[-1]["args"] == {"n": PINNED_CAPACITY + 99}
    pinned.clear()  # a copy
    assert len(tracer.pinned()) == PINNED_CAPACITY


def test_an_unpinned_event_never_enters_the_store():
    tracer = TraceRecorder(enabled=True)
    tracer.span("serve", "engine_step", 0.0, 1.0)
    tracer.instant("serve", "submit")
    with tracer.measure("serve", "schedule"):
        pass
    assert tracer.pinned() == [] and len(tracer.snapshot()) == 3


def test_flight_dump_leads_with_the_pinned_events_each_once(tmp_path):
    tracer = TraceRecorder(capacity=4, enabled=True)
    tracer.span("compile", "trace", 1.0, 2.0, pin=True, fun="decode_step")
    for step in range(6):  # the ring turns over: the pinned span leaves it
        tracer.span("serve", "engine_step", 0.0, 1.0, step=step)
    tracer.instant("serve", "stall", pin=True, step=5)  # pinned AND still in the ring
    path = tracer.flight_dump(tmp_path, "hang")
    names = _names(read_trace_events(path))
    assert names[:3] == ["meta/clock_anchor", "compile/trace", "serve/stall"]
    assert names[3:] == ["serve/engine_step"] * 3
    assert names.count("serve/stall") == 1


def test_a_late_sink_takes_what_was_pinned_before_it_once(tmp_path):
    tracer = TraceRecorder(enabled=True)
    tracer.span("setup", "config", 0.0, 0.1, pin=True)
    tracer.span("serve", "engine_step", 0.0, 1.0)  # unpinned, before the sink: ring only
    tracer.span("setup", "backend", 0.1, 2.0, pin=True)
    assert tracer.attach_sink(tmp_path / "trace.jsonl")
    tracer.span("setup", "engine_init", 2.0, 2.1, pin=True)  # the sink is there: written as it comes
    tracer.detach_sink()
    tracer.span("setup", "first_call", 2.1, 9.0, pin=True, program="prefill_chunk")
    assert tracer.attach_sink(tmp_path / "trace.jsonl")  # a second run directory owner
    tracer.detach_sink()
    names = _names(read_trace_events(tmp_path / "trace.jsonl"))
    assert names == [
        "meta/clock_anchor", "setup/config", "setup/backend", "setup/engine_init",
        "meta/clock_anchor", "setup/first_call",
    ]


def test_trace_still_imports_without_jax():
    """`analysis/contracts.py`: the scheduler and `report` import the tracer."""
    code = (
        "import sys; import llm_training_tpu.telemetry.trace as t, "
        "llm_training_tpu.telemetry.profiling as p; "
        "t.TraceRecorder().instant('setup', 'ready', pin=True); "
        "assert not [m for m in sys.modules if m == 'jax' or m.startswith('jax.')]"
    )
    subprocess.run([sys.executable, "-c", code], check=True, cwd=ROOT)


# ------------------------------------------------------------- the listener


def test_the_listener_registered_twice_hears_an_event_once(fresh):
    from jax._src import monitoring

    install_compile_listener()
    install_compile_listener()
    assert monitoring.get_event_duration_listeners().count(
        profiling._compile_listener.on_duration
    ) == 1
    before = compile_totals()
    monitoring.record_event_duration_secs(TRACE, 0.25, fun_name="planted_program")
    monitoring.record_event_duration_secs(TRACE, 0.001, fun_name="planted_inner")
    monitoring.record_event_duration_secs("/jax/some/other_duration", 9.0)
    after = compile_totals()
    assert _funs(after, "trace")["planted_program"] == _funs(before, "trace").get("planted_program", 0) + 1
    assert after["heard"] == before["heard"] + 2
    assert after["kinds"]["trace"]["short_count"] == before["kinds"].get("trace", {}).get("short_count", 0) + 1
    # 0.1 s or more: a pinned span [now - duration, now) on the recorder's clock
    (span,) = fresh.pinned()
    assert (span["cat"], span["name"], span["args"]) == ("compile", "trace", {"fun": "planted_program"})
    assert span["dur"] == 0.25 and span["ts"] + span["dur"] <= fresh.clock()


def test_a_cache_read_is_given_to_the_backend_event_that_holds_it(fresh):
    from jax._src import monitoring

    install_compile_listener()
    before = compile_totals()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event_duration_secs("/jax/compilation_cache/cache_retrieval_time_sec", 0.2)
    monitoring.record_event_duration_secs(BACKEND, 0.3, fun_name="jit(planted_cached)")
    assert get_registry().counter("compile/cache_hits").value == 1
    assert _names(fresh.pinned()) == ["compile/cache_read", "compile/backend"]
    assert fresh.pinned()[1]["args"] == {"fun": "planted_cached", "cache_read_s": 0.2}
    after = compile_totals()
    assert _funs(after, "cache_read")["planted_cached"] == _funs(before, "cache_read").get("planted_cached", 0) + 1


# ---------------------------------------------------------------- the loops


def test_an_engine_leaves_its_start_up_in_the_pinned_store(fresh):
    model = Llama(LlamaConfig(**TINY))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    before = compile_totals()
    engine = ServingEngine(model, variables, ServeConfig(**SERVE))
    engine.submit("a", [3, 17, 42, 7, 9, 11], max_new_tokens=6)
    decode_steps = get_registry().counter("serve/decode_steps")
    while decode_steps.value < 2:  # construction, the first chunk, two decode steps
        engine.step()
    after = compile_totals()
    setup = [e for e in fresh.pinned() if e["cat"] == "setup"]
    assert sorted(_names(setup)) == [
        "setup/engine_init", "setup/first_call", "setup/first_call", "setup/ready",
    ]
    assert sorted(e["args"]["program"] for e in setup if e["name"] == "first_call") == [
        "decode_step", "prefill_chunk",
    ]
    for program in ("prefill_chunk", "decode_step"):
        assert _funs(after, "trace")[program] == _funs(before, "trace").get(program, 0) + 1
    (ready,) = [e for e in setup if e["name"] == "ready"]
    assert ready["ts"] >= max(e["ts"] + e["dur"] for e in setup if e["ph"] == "X")
    assert ready["args"]["loop"] == "serve" and ready["args"]["ready_s"] > ready["args"]["pre_loop_s"] > 0
    assert ready["args"]["trace_n"] >= 2 and "trace_short_s" in ready["args"]
    while not engine.idle:
        engine.step()
    stats = engine.stats()
    assert stats["setup/ready_s"] == ready["args"]["ready_s"]
    assert stats["compile/after_ready"] == 0
    lines = startup_lines(startup_summary(fresh.pinned()))
    assert lines[0].startswith("start-up: ready after ") and lines[1] == "recompiled after ready: 0"
    engine.close()
    # the second engine of a process: its own spans, and the FIRST ready stays first
    engine = ServingEngine(model, variables, ServeConfig(**SERVE))
    engine.run([{"id": "b", "prompt": [5, 9, 11], "max_new_tokens": 3}])
    assert _names(fresh.pinned()).count("setup/ready") == 2
    assert startup_summary(fresh.pinned())["ready_s"] == ready["args"]["ready_s"]


class _ShorterLater:
    """Batches of the datamodule, the fourth and later cut to half their length."""

    def __init__(self, datamodule):
        self._inner = datamodule

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def train_batches(self, start_step=0):
        for index, batch in enumerate(self._inner.train_batches(start_step=start_step)):
            yield batch if index < 3 else {k: v[:, :8] for k, v in batch.items()}


def test_a_fit_leaves_its_start_up_and_a_later_shape_is_a_pinned_recompile(fresh, tmp_path):
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    objective = CLM(CLMConfig(model=ModelProvider(
        model_class="Llama", model_kwargs=dict(TINY, vocab_size=128, num_hidden_layers=1),
    )))
    datamodule = _ShorterLater(DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=16, num_samples=64, vocab_size=128,
    )))
    seen = {}

    class Watch:
        def on_step_end(self, trainer, step, metrics):
            seen.setdefault(step, get_registry().counter("compile/after_ready").value)

    trainer = Trainer(
        TrainerConfig(max_steps=5, log_every_n_steps=2, mesh=MeshConfig(), prefetch_batches=0),
        callbacks=[Watch()],
    )
    trainer.fit(objective, datamodule)
    pinned = fresh.pinned()
    loop = [e for e in pinned if _names([e])[0] in (
        "setup/fit_prepare", "train/compile", "setup/first_step", "setup/ready")]
    assert _names(loop) == ["setup/fit_prepare", "train/compile", "setup/first_step", "setup/ready"]
    for earlier, later in zip(loop, loop[1:]):  # in that order, nested in none of each other
        assert earlier["ts"] + earlier["dur"] <= later["ts"]
    ready = loop[-1]
    assert ready["args"]["loop"] == "fit" and ready["args"]["step"] == 2
    assert loop[2]["args"] == {"step": 2}
    # the step's own compile lies in `train/compile`, by containment
    (traced,) = [e for e in pinned if _names([e]) == ["compile/trace"]
                 and e["args"]["fun"] == "train_step" and e["ts"] < ready["ts"]]
    assert loop[1]["ts"] <= traced["ts"] and traced["ts"] + traced["dur"] <= loop[1]["ts"] + loop[1]["dur"]
    # the fourth batch is shorter: the AOT step refuses it and the jitted one
    # compiles inside the step: counted, and pinned with the program's name
    assert seen[4] > seen[2]
    recompiled = [e for e in pinned if _names([e]) == ["compile/backend"] and e["ts"] > ready["ts"]]
    (again,) = [e for e in recompiled if e["args"]["fun"] == "train_step"]
    assert again["args"]["after_ready"] and again["args"]["trace_s"] > 0 and again["args"]["lower_s"] > 0
    assert startup_summary(pinned)["after_ready"] == len(recompiled) >= 1
    assert trainer.telemetry.gauge("setup/ready_s").value == ready["args"]["ready_s"]
