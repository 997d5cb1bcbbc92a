"""The program reports itself (docs/observability.md#tracing): the tracer's
optional profiler annotator, the serving engine's step spans and counts on
the profiler's clock, block scopes in the lowered programs, the program
names three readers match, and the two hooks the benchmark will use
(`trainer.live_state`, `ServingEngine.close`)."""

import ast
import json
import re
from contextlib import contextmanager
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.analysis.contracts import JAX_FREE_CONTRACTS
from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.serve import ServeConfig, ServingEngine
from llm_training_tpu.telemetry import get_registry, install_trace_annotator
from llm_training_tpu.telemetry.registry import TelemetryRegistry, set_registry
from llm_training_tpu.telemetry.trace import (
    ANNOTATION_PREFIX,
    TraceRecorder,
    read_trace_events,
    set_tracer,
)

ROOT = Path(__file__).resolve().parent.parent

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, attention_impl="xla",
    compute_dtype="float32", param_dtype="float32",
)
TINY_MOE = dict(
    TINY, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=16,
    moe_impl="ragged",
)
SERVE = dict(
    max_batch=2, max_model_len=48, block_size=8, prefill_chunk=4,
    eos_token_id=None,
)
STEP_CHILDREN = (
    "housekeeping", "schedule", "prefill_chunk", "decode_blocks",
    "decode_inputs", "decode_dispatch", "decode_fetch", "decode_emit",
)
PROMPTS = [[3, 17, 42, 7, 9, 11], [5, 9, 11], [4, 8, 15, 16, 23]]


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


def _engine(config=TINY, **serve):
    model = Llama(LlamaConfig(**config))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    return ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))


def _requests(n=8):
    return [
        {"id": f"r{i}", "prompt": PROMPTS[i % len(PROMPTS)], "max_new_tokens": n}
        for i in range(len(PROMPTS))
    ]


# ------------------------------------------------------------- annotator


def test_measure_without_annotator_yields_late_args():
    tracer = TraceRecorder(enabled=True)
    with tracer.measure("serve", "engine_step", step=1) as late:
        late["decode_rows"] = 2
    (event,) = tracer.snapshot()
    assert event["name"] == "engine_step" and event["ph"] == "X"
    assert event["args"] == {"step": 1, "decode_rows": 2}


def test_annotator_sees_prefixed_name_args_and_late_args():
    seen = []

    class Annotation:
        def set_metadata(self, **late):
            seen.append(("late", late))

    @contextmanager
    def annotator(name, args):
        seen.append(("open", name, dict(args)))
        yield Annotation()
        seen.append(("close", name))

    tracer = TraceRecorder(enabled=True, annotator=annotator)
    with tracer.measure("serve", "engine_step", step=7) as late:
        with tracer.measure("serve", "schedule", step=7):
            pass
        late["live_tokens"] = 9
    assert seen == [
        ("open", "llmt/serve/engine_step", {"step": 7}),
        ("open", "llmt/serve/schedule", {"step": 7}),
        ("close", "llmt/serve/schedule"),
        ("late", {"live_tokens": 9}),
        ("close", "llmt/serve/engine_step"),
    ]
    assert ANNOTATION_PREFIX == "llmt/"
    # removed again, and a disabled recorder annotates nothing
    tracer.set_annotator(None)
    with tracer.measure("serve", "schedule"):
        pass
    assert len(seen) == 5
    off = TraceRecorder(enabled=False, annotator=annotator)
    with off.measure("serve", "schedule"):
        pass
    assert len(seen) == 5 and off.snapshot() == []


def test_trace_module_stays_jax_free():
    """The annotator is how jax reaches the tracer; the module itself and
    the scheduler still import without it (graftlint holds the contract)."""
    for module in ("llm_training_tpu/telemetry/trace.py", "llm_training_tpu/serve/scheduler.py"):
        assert module in JAX_FREE_CONTRACTS
        tree = ast.parse((ROOT / module).read_text())
        imported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported |= {alias.name.split(".")[0] for alias in node.names}
            elif isinstance(node, ast.ImportFrom) and node.module:
                imported.add(node.module.split(".")[0])
        assert not imported & {"jax", "jaxlib", "flax"}, module


# ------------------------------------------------------ the engine's step


def _count_from_outside(engine):
    """The benchmark harness's way (benchmarks/runners/serve_closed.py): wrap
    the two calls and count, per step, chunks, rows and live tokens."""
    steps = []
    run_prefill, run_decode = engine._run_prefill, engine._run_decode

    def counted_prefill(*args):
        steps[-1]["prefill_chunks"] += 1
        steps[-1]["prefill_tokens"] += len(args[1])
        return run_prefill(*args)

    def counted_decode(rows):
        steps[-1]["decode_rows"] += len(rows)
        steps[-1]["live_tokens"] += sum(r.cache_len + 1 for r in rows)
        return run_decode(rows)

    engine._run_prefill, engine._run_decode = counted_prefill, counted_decode

    def step():
        steps.append(dict.fromkeys(
            ("prefill_chunks", "prefill_tokens", "decode_rows", "live_tokens"), 0
        ))
        return engine.step()

    return steps, step


def test_engine_step_spans_nest_and_count_like_the_harness(fresh, tmp_path):
    assert fresh.attach_sink(tmp_path / "trace.jsonl")
    engine = _engine()
    outside, step = _count_from_outside(engine)
    for request in _requests():
        engine.submit(**request)
    while not engine.idle:
        step()
    ring = [e for e in fresh.snapshot() if e.get("ph") == "X"]
    parents = [e for e in ring if e["name"] == "engine_step"]
    assert len(parents) == len(outside) == engine._step_index
    # step for step: the span's closing args are the outside count
    for parent, counted in zip(parents, outside):
        assert {k: parent["args"][k] for k in counted} == counted
    assert [p["args"]["step"] for p in parents] == list(range(1, len(parents) + 1))
    # every child lies inside its step, carries its index, and no two overlap
    children = [e for e in ring if e["name"] in STEP_CHILDREN]
    assert {e["name"] for e in children} == set(STEP_CHILDREN)
    by_step = {p["args"]["step"]: p for p in parents}
    for step_index, parent in by_step.items():
        mine = sorted(
            (e for e in children if e["args"]["step"] == step_index),
            key=lambda e: e["ts"],
        )
        assert mine[0]["name"] == "housekeeping"
        assert mine[0]["ts"] >= parent["ts"]
        assert mine[-1]["ts"] + mine[-1]["dur"] <= parent["ts"] + parent["dur"] + 1e-9
        for a, b in zip(mine, mine[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9, (a["name"], b["name"])
    # the prefill chunk's own child lies inside it, with the request's id
    chunks = [e for e in ring if e["name"] == "prefill_chunk"]
    inner = [e for e in ring if e["name"] == "prefill_dispatch"]
    assert len(inner) == len(chunks) > 0
    for e in inner:
        chunk = next(
            c for c in chunks
            if c["args"]["step"] == e["args"]["step"]
        )
        assert e["args"]["request_id"] == chunk["args"]["request_id"]
        assert chunk["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= chunk["ts"] + chunk["dur"] + 1e-9
    # no chunk waits for its token: a prompt's last chunk is read with the
    # decode step beside it, by the NEXT step's decode_fetch
    assert not [e for e in ring if e["name"] == "prefill_fetch"]
    fetches = {e["args"]["step"] for e in ring if e["name"] == "decode_fetch"}
    assert all(c["args"]["step"] + 1 in fetches for c in chunks if c["args"]["final"])
    # a step ahead: it enqueued a call while the step before's tokens (its
    # decode rows', or its prompt's last chunk's) were unread
    final = {c["args"]["step"] for c in chunks if c["args"]["final"]}
    made_tokens = [bool(p["args"]["decode_rows"]) or p["args"]["step"] in final for p in parents]
    enqueues = [bool(p["args"]["decode_rows"] or p["args"]["prefill_chunks"]) for p in parents]
    ahead = [int(a and b) for a, b in zip([False] + made_tokens, enqueues)]
    assert [p["args"]["steps_ahead"] for p in parents] == ahead and sum(ahead) >= len(parents) - 3
    assert not any(p["args"]["pipeline_flushes"] or p["args"]["discarded_row_steps"] for p in parents)
    # the sink keeps one engine_step a step and a sampled request's chunks:
    # the step's new children stay in the ring and the profiler
    fresh.flush()
    persisted = [e["name"] for e in read_trace_events(tmp_path / "trace.jsonl")]
    assert persisted.count("engine_step") == len(parents)
    assert "prefill_chunk" in persisted
    assert not set(persisted) & (
        set(STEP_CHILDREN) - {"prefill_chunk"} | {"prefill_dispatch", "prefill_fetch"}
    )
    # one bookkeeping, two readers: the registry's counters are the sums
    registry = get_registry()
    decode = [c for c in outside if c["decode_rows"]]
    assert registry.counter("serve/steps").value == len(outside)
    assert registry.counter("serve/prefill_chunks").value == sum(
        c["prefill_chunks"] for c in outside
    )
    assert registry.counter("serve/decode_steps").value == len(decode)
    assert registry.counter("serve/decode_rows").value == sum(
        c["decode_rows"] for c in decode
    )
    assert registry.counter("serve/live_tokens").value == sum(
        c["live_tokens"] for c in decode
    )
    # a token comes from a decoding row or from a prompt's last chunk
    assert engine.tokens_generated == sum(c["decode_rows"] for c in decode) + sum(
        c["args"]["final"] for c in chunks
    )


@pytest.mark.parametrize("build", ["llama", "afmoe"])
def test_table_writes_count_pages_taken_not_rows_rebuilt(fresh, build):
    """`table_writes` closes `serve/engine_step` and `serve/table_writes` is
    its sum: the block-table entries the host wrote, which on a stretch of
    decode steps is a page a row every `block_size` tokens (and, of a window
    group's ring, the slot of each page given back), not rows x pages."""
    engine = {"llama": _engine, "afmoe": _afmoe_engine}[build](max_batch=3, max_model_len=64)
    for i, prompt in enumerate(PROMPTS):
        engine.submit(f"r{i}", prompt, max_new_tokens=40)
    while any(not r.decoding for r in engine.scheduler.running.values()) or engine.scheduler.waiting:
        engine.step()
    prefill_steps = engine._step_index
    for _ in range(32):  # 3 rows x 32 tokens: 4 pages of 8 a row
        engine.step()
    steps = [e["args"] for e in fresh.snapshot() if e.get("ph") == "X" and e["name"] == "engine_step"]
    assert all("table_writes" in args for args in steps)
    stretch = steps[prefill_steps:]
    assert all(args["decode_rows"] == 3 and not args["prefill_chunks"] for args in stretch)
    rows, pages = sum(args["decode_rows"] for args in stretch), engine.pages_per_request
    taken = rows // SERVE["block_size"]  # 12
    given_back = sum(args.get("window_pages_released", 0) for args in stretch)
    assert (given_back > 0) == (build == "afmoe")
    # the window group writes its own taken page, and the slot of each page given back
    groups = 2 if build == "afmoe" else 1
    assert sum(args["table_writes"] for args in stretch) == taken * groups + given_back
    assert max(args["table_writes"] for args in stretch) < rows * pages / len(stretch)
    # admission wrote each row once, whole: its pages of each group
    assert sum(args["table_writes"] for args in steps[:prefill_steps]) >= len(PROMPTS)
    registry = get_registry()
    assert registry.counter("serve/table_writes").value == sum(args["table_writes"] for args in steps)
    stats = engine.stats()
    assert stats["serve/table_writes"] == registry.counter("serve/table_writes").value
    assert stats["serve/decode_rows"] == registry.counter("serve/decode_rows").value
    from llm_training_tpu.telemetry.report import _serving_section

    assert f"{int(stats['serve/table_writes']):,} block-table entries written" in "\n".join(
        _serving_section(stats)
    )


def test_allocator_gauges_are_set_once_a_step_from_its_counts(fresh):
    """`alloc` and `free` no longer touch the registry: after every step the
    gauges of each group read what its allocator counts."""
    engine = _afmoe_engine()
    for request in _requests(14):
        engine.submit(**request)
    registry = get_registry()
    seen = set()
    while not engine.idle:
        engine.step()
        for allocator in (engine.allocator, engine.window_allocator):
            in_use = registry.gauge(f"decode/{allocator.group}_blocks_in_use").value
            peak = registry.gauge(f"decode/{allocator.group}_peak_blocks_in_use").value
            assert (in_use, peak) == (allocator.blocks_in_use, allocator.peak_in_use)
            seen.add((allocator.group, in_use))
    assert len(seen) > 4  # they moved
    blocks = engine.allocator.alloc(2)
    assert registry.gauge("decode/cache_blocks_in_use").value == 0  # until someone asks
    engine.allocator.free(blocks)


def test_profiler_capture_holds_the_engines_spans(fresh, tmp_path):
    """Under jax.profiler the same spans land in the profiler's host plane,
    `llmt/`-prefixed, with their args; benchmarks/span_reduce.py reads them
    back and its counts are the ring's."""
    from benchmarks import span_reduce

    engine = _engine()
    for request in _requests(4):
        engine.submit(**request)
    engine.step()  # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        first = engine._step_index + 1
        while not engine.idle:
            engine.step()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    trace = span_reduce.load(xplane)
    ring = [
        e for e in fresh.snapshot()
        if e.get("ph") == "X" and e["name"] == "engine_step"
        and e["args"]["step"] >= first
    ]
    steps = span_reduce.spans_named(trace, "serve/engine_step")
    assert len(steps) == len(ring) > 2
    for span, event in zip(steps, ring):
        assert span["args"] == event["args"]
        assert span["dur"] == pytest.approx(event["dur"] * 1e9, rel=0.2, abs=2e5)
    counts = span_reduce.step_counts(trace)
    assert counts["steps"] == len(ring)
    assert counts["decode_rows"] == sum(e["args"]["decode_rows"] for e in ring)
    assert counts["live_tokens"] == sum(e["args"]["live_tokens"] for e in ring)
    assert counts["prefill_steps"] == sum(e["args"]["prefill_chunks"] for e in ring)
    names = {s["name"] for s in trace["spans"]}
    assert {f"serve/{n}" for n in STEP_CHILDREN} <= names
    # self time: a step less what its children cover is what no child names
    for span in steps:
        assert 0 <= span_reduce.self_ns(trace, span) <= span["dur"]


# ------------------------------------------------- scopes and program names


def _decode_args(engine):
    """A decode call's arguments: the packed int32 inputs (lengths, the call
    index, the block tables: `engine._decode_fields`), the pool, the engine's
    one key, the slots' last tokens (which never leave the device)."""
    return (
        engine.variables, jnp.asarray(engine._decode_packed), engine._pool_k,
        engine._pool_v, engine._rng, engine._last_tokens,
    )


def _op_names(text, pattern):
    """The names of a lowered program's OPS that match `pattern`: their name
    stacks, `scope/.../primitive`. The debug text holds two more kinds of
    quoted location, a frame's file (`loc("<path>":line:col)`) and its
    function (`loc("<qualname>"(...))`, no `/` in it), and they say where
    an inner jitted function was FIRST traced in this process: jax keeps
    that trace, so after `tests/test_mla_prefill.py` on the same worker the
    frames of a `jnp` helper read `test_mla_prefill_...` and `mla_decode.py`
    inside a program that never ran them. Neither is an op."""
    return [name for name in re.findall(rf'loc\("([^"]*{pattern}[^"]*)"\(', text) if "/" in name]


def _prefill_args(engine):
    return (
        engine.variables, jnp.asarray(engine._prefill_packed), engine._pool_k,
        engine._pool_v, engine._rng, engine._last_tokens,
    )


def test_lowered_programs_carry_block_scopes_and_their_names(fresh):
    engine = _engine(TINY_MOE)
    decode = engine._decode_jit.lower(*_decode_args(engine))
    prefill = engine._prefill_jit.lower(*_prefill_args(engine))
    # three readers match these (docs, chip_smoke.py, the benchmark's trace
    # readers): the jitted functions' names are the programs' names
    assert "jit_decode_step" in decode.as_text()[:200]
    assert "jit_prefill_chunk" in prefill.as_text()[:200]
    for lowered in (decode, prefill):
        text = lowered.as_text(debug_info=True)
        for scope in (
            "moe_route", "moe_sort", "moe_gather", "moe_experts", "moe_scatter",
            "self_attn", "mlp", "sample",
        ):
            assert f"/{scope}" in text, scope
    # the MoE phases sit inside the block's own module scope
    assert "mlp/moe_sort" in decode.as_text(debug_info=True)


# ------------------------------------------- a stack with a second cache kind

TINY_SOLAR = dict(
    vocab_size=64, hidden_size=32, intermediate_size=48, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    linear_num_heads=2, linear_head_dim=8, n_routed_experts=8, experts_held=4,
    num_experts_per_tok=2, moe_intermediate_size=16, moe_impl="ragged",
    attention_impl="xla", compute_dtype="float32", param_dtype="float32",
)


def _solar_engine(**serve):
    from llm_training_tpu.models import SolarOpen2, SolarOpen2Config

    model = SolarOpen2(SolarOpen2Config(**TINY_SOLAR))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    return ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))


def test_linear_attention_stack_names_its_scopes_in_both_programs(fresh):
    """What the benchmark's per-layer readers match (`/linear_attn/`,
    `kda_recurrence`, the MoE phases and `moe_shared`), in the lowered
    programs of a stack that carries the state slab."""
    engine = _solar_engine()
    decode = engine._decode_jit.lower(*_decode_args(engine), slab=engine._slab)
    prefill = engine._prefill_jit.lower(*_prefill_args(engine), slab=engine._slab)
    assert "jit_decode_step" in decode.as_text()[:200]
    assert "jit_prefill_chunk" in prefill.as_text()[:200]
    shared = (
        "slot0/self_attn/", "slot1/linear_attn/", "linear_attn/kda_conv", "linear_attn/kda_gates",
        "mlp/moe_route", "mlp/moe_sort", "mlp/moe_gather", "mlp/moe_experts",
        "mlp/moe_scatter", "mlp/moe_shared", "/sample",
    )
    for lowered, own, other in (
        (decode, "linear_attn/kda_recurrence", "kda_chunk"),
        (prefill, "linear_attn/kda_chunk", "kda_recurrence"),
    ):
        text = lowered.as_text(debug_info=True)
        for scope in shared + (own,):
            assert scope in text, scope
        assert other not in text


def test_state_slab_reports_its_bytes_slots_and_resets(fresh):
    engine = _solar_engine()
    engine.run(_requests(6))
    registry = get_registry()
    slab_bytes = 3 * 2 * (2 * 8 * 8 * 4 + 3 * 48 * 4)  # 3 KDA layers, 2 slots: state + conv tail
    assert registry.gauge("decode/state_bytes").value == slab_bytes
    assert engine.stats()["decode/state_bytes"] == slab_bytes
    assert registry.gauge("decode/state_slots_in_use").value == 0  # drained
    # three requests through two slots, no eviction: one first chunk each
    assert registry.counter("serve/state_resets").value == 3
    steps = [e["args"] for e in fresh.snapshot() if e.get("ph") == "X" and e["name"] == "engine_step"]
    assert sum(a["state_resets"] for a in steps) == 3
    assert max(a["state_slots_in_use"] for a in steps) == 2
    # a stack without linear-attention layers reports none of it
    llama_steps = []
    plain = _engine()
    plain.run(_requests(2))
    llama_steps = [
        e["args"] for e in fresh.snapshot() if e.get("ph") == "X" and e["name"] == "engine_step"
    ][len(steps):]
    assert llama_steps and all("state_resets" not in a for a in llama_steps)
    assert plain._slab is None and plain.stats()["decode/state_bytes"] == 0
    engine.close()
    assert engine._slab is None and engine._pool_k is None


# ------------------------- a stack whose slab is the larger cache of its layers

TINY_OLMOH = dict(
    vocab_size=64, hidden_size=36, intermediate_size=48, num_hidden_layers=4,
    num_attention_heads=6, num_key_value_heads=6,
    linear_num_key_heads=6, linear_num_value_heads=6,
    linear_key_head_dim=4, linear_value_head_dim=64, delta_chunk_size=4,
    attention_impl="xla", compute_dtype="float32", param_dtype="float32",
)


def _olmoh_engine(**serve):
    from llm_training_tpu.models import OlmoHybrid, OlmoHybridConfig

    model = OlmoHybrid(OlmoHybridConfig(**TINY_OLMOH))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    return ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))


def test_gated_delta_stack_names_its_scopes_in_both_programs(fresh):
    """What the benchmark's per-layer readers match (`/linear_attn/` and
    inside it `gdn_conv`, `gdn_gates`, `gdn_recurrence` or `gdn_chunk`,
    `gdn_out`; `/self_attn/`, `/mlp/`, `rms_norm`, `sample`), in the lowered
    programs of a stack with three delta-rule layers to one softmax layer."""
    engine = _olmoh_engine()
    decode = engine._decode_jit.lower(*_decode_args(engine), slab=engine._slab)
    prefill = engine._prefill_jit.lower(*_prefill_args(engine), slab=engine._slab)
    assert "jit_decode_step" in decode.as_text()[:200]
    assert "jit_prefill_chunk" in prefill.as_text()[:200]
    shared = (
        "slot3/self_attn/", "slot0/linear_attn/", "slot2/linear_attn/", "linear_attn/gdn_conv",
        "linear_attn/gdn_gates", "linear_attn/gdn_out", "gdn_out/o_norm/rms_norm", "slot1/mlp/",
        "self_attn/q_norm/rms_norm", "post_feedforward_layernorm/rms_norm", "/sample",
    )
    # the slab's write carries the recurrence's scope: a decode step's update
    # of the state fuses into it, and a fusion lands where its root does
    for lowered, own, write, other in (
        (decode, "linear_attn/gdn_recurrence", "gdn_recurrence/dynamic_update_slice", "gdn_chunk"),
        (prefill, "linear_attn/gdn_chunk", "gdn_chunk/scatter", "gdn_recurrence"),
    ):
        text = lowered.as_text(debug_info=True)
        for scope in shared + (own, write):
            assert scope in text, scope
        assert other not in text
    # the state rides the programs as it is STORED: two heads abreast, whole tiles
    assert engine._slab[0].shape == (3, 2, 3, 4, 128)
    assert "tensor<3x2x3x4x128xf32>" in decode.as_text() and "6x4x64xf32" not in decode.as_text()


def test_stored_slab_reports_what_it_occupies_and_what_it_holds(fresh):
    engine = _olmoh_engine()
    engine.run(_requests(6))
    registry = get_registry()
    # 3 linear layers, 2 slots: 6 heads of 4 x 64 floats (3 rows of 4 x 128) + the conv tail
    slab_bytes = 3 * 2 * (6 * 4 * 64 * 4 + 3 * 6 * (4 + 4 + 64) * 4)
    for name in ("decode/state_bytes", "decode/state_logical_bytes"):
        assert registry.gauge(name).value == slab_bytes
        assert engine.stats()[name] == slab_bytes
    assert registry.gauge("decode/state_slots_in_use").value == 0  # drained
    assert registry.counter("serve/state_resets").value == 3
    steps = [e["args"] for e in fresh.snapshot() if e.get("ph") == "X" and e["name"] == "engine_step"]
    assert sum(a["state_resets"] for a in steps) == 3
    assert max(a["state_slots_in_use"] for a in steps) == 2
    # `report` prints the slab beside the pool
    from llm_training_tpu.telemetry.report import _serving_section

    lines = _serving_section({k: v for k, v in engine.stats().items()} | {
        "serve/state_resets": registry.counter("serve/state_resets").value,
    })
    slab_line = next(line for line in lines if line.startswith("state slab:"))
    assert f"{slab_bytes / 2**20:.1f} MiB" in slab_line and "beside" in slab_line and "3 resets" in slab_line
    engine.close()
    assert engine._slab is None and engine._pool_k is None


# --------------------------------------------- a stack with a latent cache

TINY_LONGCAT = dict(
    vocab_size=64, hidden_size=32, ffn_hidden_size=48, expert_ffn_hidden_size=16, num_layers=2,
    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24, qk_rope_head_dim=8, qk_nope_head_dim=8,
    v_head_dim=8, n_routed_experts=8, zero_expert_num=4, moe_topk=3, experts_held=4,
    moe_impl="ragged", attention_impl="xla", compute_dtype="float32", param_dtype="float32",
)


def _longcat_engine(**serve):
    from llm_training_tpu.models import LongcatFlash, LongcatFlashConfig

    model = LongcatFlash(LongcatFlashConfig(**TINY_LONGCAT))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    return ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))


def test_latent_attention_stack_names_its_scopes_in_both_programs(fresh):
    """What the benchmark's readers match: every op of an MLA block under
    `/self_attn/` with its parts named, the dense FFNs and the MoE under
    `/mlp/`, the MoE branch whole under `scmoe` with `moe_zero`; the decode
    step attends absorbed, the chunk expanded."""
    engine = _longcat_engine()
    assert engine._pool_v is None and engine._pool_k.shape == (4, 13, 1, 8, 128)
    decode = engine._decode_jit.lower(*_decode_args(engine))
    prefill = engine._prefill_jit.lower(*_prefill_args(engine))
    assert "jit_decode_step" in decode.as_text()[:200]
    assert "jit_prefill_chunk" in prefill.as_text()[:200]
    shared = (
        "sub_0/self_attn/mla_q", "sub_1/self_attn/mla_kv", "self_attn/mla_attend", "self_attn/mla_out",
        "sub_0/mlp/", "sub_1/mlp/", "layer/scmoe/mlp/moe_route", "scmoe/mlp/moe_sort",
        "scmoe/mlp/moe_gather", "scmoe/mlp/moe_experts", "scmoe/mlp/moe_scatter", "scmoe/mlp/moe_zero",
        "/sample",
    )
    for lowered, own, other in (
        # (a chunk walks its row's pages in a loop, whose body's scopes follow `while/body`)
        (decode, "self_attn/mla_absorb", "mla_expand"), (prefill, "/mla_expand/", "mla_absorb"),
    ):
        text = lowered.as_text(debug_info=True)
        for scope in shared + (own,):
            assert scope in text, scope
        assert other not in text
        # no op of an MLA block lies outside the block's module scope (a name that
        # starts at a loop's body is the location of a call's wrapper there, not an op's)
        named = _op_names(text, "mla_")
        assert named and all("/self_attn/" in name for name in named if not name.startswith("while/body/"))


def test_latent_pool_and_expert_assignments_are_counted_with_the_tokens(fresh):
    """`decode/latent_pool_bytes`, and each call's expert assignments (held
    here, zero-compute, held elsewhere): counted on the device, returned by
    the call as an int32 output of its own beside log-probabilities of the
    batch's own length, read in the `device_get` that fetches the call's
    tokens (a step later: no sync of their own) and summed by the counters."""
    from llm_training_tpu.telemetry.report import _serving_section

    engine = _longcat_engine()
    *_, token, logprob, _, counts = jax.eval_shape(
        engine._decode_jit, *_decode_args(engine)
    )
    assert token.shape == logprob.shape == (SERVE["max_batch"],)
    assert (counts.shape, counts.dtype) == ((3,), np.int32)
    engine.run(_requests(6))
    registry = get_registry()
    pool_bytes = 4 * 13 * 8 * 128 * 4  # 4 MLA blocks, 12 blocks and the trash one, pages of 8 rows stored 128 wide
    assert registry.gauge("decode/latent_pool_bytes").value == pool_bytes
    stats = engine.stats()
    assert stats["decode/latent_pool_bytes"] == stats["decode/cache_bytes"] == pool_bytes
    kinds = ("held", "zero", "elsewhere")
    totals = {k: int(registry.counter(f"serve/moe_{k}_assignments").value) for k in kinds}
    # every real token of every call, twice (2 layers), three choices each:
    # 14 prompt tokens and 3 x 5 decoded ones (the last token is not fed back)
    assert sum(totals.values()) == (14 + 15) * 2 * 3
    assert all(totals.values())
    for kind in kinds:
        assert stats[f"serve/moe_{kind}_assignments"] == totals[kind]
    said = "\n".join(_serving_section(stats))
    assert "latent (MLA) pool" in said and f"{totals['zero']} zero-compute" in said
    # a stack with keys and values reports none of it
    plain = _engine()
    plain.run(_requests(2))
    assert plain.stats()["decode/latent_pool_bytes"] == 0
    assert "serve/moe_held_assignments" not in plain.stats()
    assert registry.gauge("decode/latent_pool_bytes").value == 0
    engine.close()
    assert engine._pool_k is None


# ------------------------------------------------ a stack with two page groups

TINY_AFMOE = dict(
    vocab_size=64, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=8, num_dense_layers=2, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, sliding_window=8, num_experts=8, num_experts_per_tok=2, experts_held=4,
    moe_impl="ragged", attention_impl="xla", compute_dtype="float32", param_dtype="float32",
)


def _afmoe_engine(**serve):
    from llm_training_tpu.models import Afmoe, AfmoeConfig

    model = Afmoe(AfmoeConfig(**TINY_AFMOE))
    variables = jax.jit(model.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    return ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))


def _window_args(engine):
    return {"window_pool": engine._window_pool}  # its tables travel in the packed inputs


def test_window_and_global_layers_name_their_scopes_in_both_programs(fresh):
    """What the benchmark's readers match: a layer's append and attention
    under `attn_window` or `attn_global` inside `/self_attn/`, the output gate
    under `attn_gate`, the MoE phases and `moe_shared` under `/mlp/`, in the
    looped layers in front and in the scanned periods alike."""
    engine = _afmoe_engine()
    decode = engine._decode_jit.lower(*_decode_args(engine), **_window_args(engine))
    prefill = engine._prefill_jit.lower(*_prefill_args(engine), **_window_args(engine))
    assert "jit_decode_step" in decode.as_text()[:200]
    assert "jit_prefill_chunk" in prefill.as_text()[:200]
    for lowered in (decode, prefill):
        text = lowered.as_text(debug_info=True)
        for scope in (
            "front/slot0/self_attn/attn_window", "front/slot3/self_attn/attn_global",
            "slot0/self_attn/attn_window", "slot3/self_attn/attn_global", "self_attn/attn_gate",
            "front/slot0/mlp/", "slot3/mlp/moe_route", "mlp/moe_sort", "mlp/moe_gather",
            "mlp/moe_experts", "mlp/moe_scatter", "mlp/moe_shared", "/sample",
        ):
            assert scope in text, scope
        # no attention group's op lies outside the block's module scope
        named = _op_names(text, "attn_(?:window|global)")
        assert named and all("/self_attn/" in name for name in named if not name.startswith("while/body/"))


def test_window_group_reports_its_pool_its_pages_and_what_it_reads(fresh):
    """`decode/global_pool_bytes` and `decode/window_pool_bytes`, the window
    group's own block gauges, `window_live_tokens` (of each decoding row, its
    window at most) beside `live_tokens` in `engine_step`'s closing args, and
    the pages given back: all in `stats()`, the counters and `report`."""
    from llm_training_tpu.telemetry.report import _serving_section

    engine = _afmoe_engine()
    # 2 layers keep everything (a row of 6 pages), 6 keep a window of 8: 8 + a chunk of 4, in pages of 8, + 1
    assert engine._pool_k.shape == (2, 2 * 6 + 1, 2, 8, 8)
    assert engine.window_pages == 3 and engine._window_pool[0].shape == (6, 2 * 3 + 1, 2, 8, 8)
    *_, window = jax.eval_shape(
        engine._decode_jit, *_decode_args(engine), **_window_args(engine))
    assert [leaf.shape for leaf in window] == [engine._window_pool[0].shape] * 2
    engine.run(_requests(14))  # rows of 20, 17 and 19 tokens: each gives its first page back
    registry = get_registry()
    stats = engine.stats()
    full, window = 2 * 2 * 13 * 2 * 8 * 8 * 4, 2 * 6 * 7 * 2 * 8 * 8 * 4
    assert stats["decode/global_pool_bytes"] == stats["decode/cache_bytes"] == full
    assert stats["decode/window_pool_bytes"] == registry.gauge("decode/window_pool_bytes").value == window
    assert stats["decode/window_blocks_total"] == registry.gauge("decode/window_blocks_total").value == 6
    assert stats["decode/window_blocks_in_use"] == registry.gauge("decode/window_blocks_in_use").value == 0
    assert 0 < stats["decode/window_peak_blocks_in_use"] <= 6
    assert registry.gauge("decode/window_peak_blocks_in_use").value == stats["decode/window_peak_blocks_in_use"]
    assert stats["decode/cache_blocks_in_use"] == 0
    steps = [e["args"] for e in fresh.snapshot() if e.get("ph") == "X" and e["name"] == "engine_step"]
    decode = [a for a in steps if a["decode_rows"]]
    assert decode and all(0 < a["window_live_tokens"] <= a["live_tokens"] for a in decode)
    assert all(a["window_live_tokens"] <= 8 * a["decode_rows"] for a in decode)
    assert any(a["window_live_tokens"] < a["live_tokens"] for a in decode)  # a row past its window
    for name in ("window_live_tokens", "window_pages_released"):
        total = sum(a[name] for a in steps)
        assert total > 0 and registry.counter(f"serve/{name}").value == stats[f"serve/{name}"] == total
    said = "\n".join(_serving_section(stats))
    assert "window page group: 6 blocks" in said and f"{int(stats['serve/window_pages_released'])} pages given back" in said
    # a stack with one group reports none of it
    plain = _engine()
    plain.run(_requests(2))
    assert plain.stats()["decode/window_pool_bytes"] == 0 == registry.gauge("decode/window_pool_bytes").value
    assert plain.stats()["decode/global_pool_bytes"] == plain.stats()["decode/cache_bytes"]
    assert "serve/window_live_tokens" not in plain.stats() and plain.window_allocator is None
    assert "window page group" not in "\n".join(_serving_section(plain.stats()))
    engine.close()
    assert engine._pool_k is None and engine._window_pool is None


@pytest.mark.parametrize("config,layers", [
    pytest.param(dict(TINY_MOE, num_hidden_layers=3), 3, id="engaged"),
    pytest.param(dict(TINY_MOE, num_hidden_layers=3, moe_impl="dense"), 0, id="dense-impl"),
    pytest.param(dict(TINY_MOE, num_hidden_layers=3, moe_impl="auto"), 0, id="auto-off-the-chip"),
    pytest.param(dict(TINY_MOE, num_hidden_layers=1), 1, id="one-layer"),
    pytest.param(dict(TINY_MOE, num_hidden_layers=3, scan_layers=False), 3, id="looped"),
    pytest.param(TINY, 0, id="no-experts"),
])
def test_gauge_counts_the_layers_whose_experts_are_read_in_place(fresh, config, layers):
    """`decode/experts_in_place_layers`: counted when the serving programs are
    traced, every expert layer of a decoding stack on the ragged path once
    (a scan's body for each of its repeats, a looped layer for itself,
    however many programs trace it), 0 on every fallback; `stats()` holds it
    and `report` says it."""
    from llm_training_tpu.telemetry.report import _serving_section

    get_registry().gauge("decode/experts_in_place_layers").set(5)  # another engine's
    engine = _engine(config)
    assert get_registry().gauge("decode/experts_in_place_layers").value == 0  # nothing traced yet
    engine.run(_requests(2))
    assert get_registry().gauge("decode/experts_in_place_layers").value == layers
    stats = engine.stats()
    assert stats["decode/experts_in_place_layers"] == layers
    said = f"expert weights: read in place in {layers} layers" in _serving_section(stats)
    assert said == bool(layers)


def test_engine_step_says_where_its_chunk_starts(fresh):
    """`prefill_start` beside `prefill_tokens` in `engine_step`'s closing
    args: the tokens the chunk's row held before the chunk, so a reader can
    count the (query, key) pairs the chunk's attention may see."""
    engine = _engine()
    engine.run(_requests())
    ring = [e for e in fresh.snapshot() if e.get("ph") == "X"]
    steps = {e["args"]["step"]: e["args"] for e in ring if e["name"] == "engine_step"}
    chunks = {e["args"]["step"]: e["args"] for e in ring if e["name"] == "prefill_chunk"}
    assert chunks and all("prefill_start" in args for args in steps.values())
    for step, args in steps.items():
        chunk = chunks.get(step, {"start": 0, "tokens": 0})
        assert (args["prefill_start"], args["prefill_tokens"]) == (chunk["start"], chunk["tokens"])
    # a prompt of 6 in chunks of 4: its second chunk starts at 4
    assert sorted({args["prefill_start"] for args in steps.values()}) == [0, 4]


@pytest.mark.parametrize("build,impl,layers,kernel", [
    pytest.param("llama", None, 0, "paged_prefill", id="xla-off-the-chip"),
    pytest.param("llama", "pallas", 2, "paged_prefill", id="kernel"),
    pytest.param("afmoe", "pallas", 8, "paged_prefill", id="kernel-two-page-groups"),
    pytest.param("longcat", None, 0, "mla_prefill", id="latent-xla-off-the-chip"),
    pytest.param("longcat", "pallas", 4, "mla_prefill", id="latent-kernel"),
])
def test_gauge_counts_the_layers_whose_chunk_attention_runs_in_the_kernel(
    fresh, monkeypatch, build, impl, layers, kernel
):
    """`decode/chunk_attention_kernel_layers`: set when the prefill program is
    traced, every key/value layer of the stack (both page groups' where it has
    two) when a chunk attends in `paged_prefill`, every latent (MLA) block
    when it attends in `mla_prefill`, 0 on the CPU's XLA paths; `stats()`
    holds it and `report` says it, with the kernel's name."""
    from llm_training_tpu.ops import latent_attention, paged_attention
    from llm_training_tpu.telemetry.report import _serving_section

    if impl is not None:
        for module, name in (
            (paged_attention, "paged_cached_attention"), (latent_attention, "paged_latent_attention"),
        ):
            attend = getattr(module, name)
            monkeypatch.setattr(
                module, name,
                lambda *args, attend=attend, **kwargs: attend(*args, **{**kwargs, "impl": impl}),
            )
    get_registry().gauge("decode/chunk_attention_kernel_layers").set(5)  # another engine's
    engine = {"llama": _engine, "afmoe": _afmoe_engine, "longcat": _longcat_engine}[build]()
    assert get_registry().gauge("decode/chunk_attention_kernel_layers").value == 0  # nothing traced yet
    engine.run(_requests(2))
    assert get_registry().gauge("decode/chunk_attention_kernel_layers").value == layers
    stats = engine.stats()
    assert stats["decode/chunk_attention_kernel_layers"] == layers
    said = f"chunk attention: in the {kernel} kernel in {layers} layers" in _serving_section(stats)
    assert said == bool(layers)


def test_lowered_train_loss_carries_loss_ce():
    from llm_training_tpu.ops.cross_entropy import fused_linear_cross_entropy

    def loss(hidden, weight, labels):
        total, count = fused_linear_cross_entropy(hidden, weight, labels, chunk_size=4)
        return total / count

    lowered = jax.jit(jax.grad(loss)).lower(
        jnp.ones((8, 16)), jnp.ones((16, 32)), jnp.zeros((8,), jnp.int32)
    )
    text = lowered.as_text(debug_info=True)
    assert "loss_ce" in text
    # forward and backward alike: the transposed ops keep the scope
    assert any(
        "transpose" in line and "loss_ce" in line for line in text.splitlines()
    )


# ------------------------------------------------------------ the trainer


def _fit(tmp_path, callbacks=(), max_steps=3):
    from llm_training_tpu.callbacks.loggers import JsonlLogger, JsonlLoggerConfig
    from llm_training_tpu.data import DummyDataModule, DummyDataModuleConfig
    from llm_training_tpu.lms import CLM, CLMConfig
    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.parallel import MeshConfig
    from llm_training_tpu.trainer import Trainer, TrainerConfig

    objective = CLM(CLMConfig(model=ModelProvider(
        model_class="Llama",
        model_kwargs=dict(TINY, vocab_size=128, num_hidden_layers=1),
    )))
    datamodule = DummyDataModule(DummyDataModuleConfig(
        batch_size=8, max_length=16, num_samples=64, vocab_size=128,
    ))
    jsonl = JsonlLogger(JsonlLoggerConfig(save_dir=str(tmp_path), name="spans"))
    trainer = Trainer(
        TrainerConfig(max_steps=max_steps, log_every_n_steps=2, mesh=MeshConfig()),
        callbacks=[jsonl, *callbacks],
    )
    trainer.fit(objective, datamodule)
    return trainer, jsonl.run_dir


def test_trainer_step_spans_reach_the_sink_and_the_annotator(tmp_path, monkeypatch):
    monkeypatch.setenv("LLMT_TRACE_TRAIN", "1")
    annotated = []

    @contextmanager
    def annotator(name, args):
        annotated.append(name)
        yield None

    tracer = TraceRecorder(enabled=True)
    previous = set_tracer(tracer)
    installs = []
    monkeypatch.setattr(
        "llm_training_tpu.trainer.trainer.install_trace_annotator",
        lambda t: (installs.append(t), t.set_annotator(annotator)),
    )
    try:
        _, run_dir = _fit(tmp_path)
    finally:
        tracer.detach_sink()
        set_tracer(previous)
    assert installs == [tracer]  # fit installs the profiler side on the process tracer
    events = read_trace_events(run_dir / "trace.jsonl")
    spans = [e for e in events if e.get("ph") == "X" and e["cat"] == "train"]
    for name in ("data_load", "train_step"):
        mine = [e for e in spans if e["name"] == name]
        assert [e["args"]["step"] for e in mine] == [0, 1, 2], name
    assert any(e["name"] == "compile" for e in spans)
    # one measure each: every span has its annotation, under the prefix
    assert annotated.count("llmt/train/data_load") == 3
    assert annotated.count("llmt/train/train_step") == 3
    assert "llmt/train/compile" in annotated
    # a step's data_load ends before its train_step begins
    for step in range(3):
        load = next(e for e in spans if e["name"] == "data_load" and e["args"]["step"] == step)
        compute = next(e for e in spans if e["name"] == "train_step" and e["args"]["step"] == step)
        assert load["ts"] + load["dur"] <= compute["ts"] + 1e-9


def test_install_trace_annotator_opens_profiler_annotations():
    tracer = TraceRecorder(enabled=True)
    install_trace_annotator(tracer)
    with tracer.measure("train", "train_step", step=0) as late:
        late["tokens"] = 4  # no capture open: a flag test, and no error
    assert tracer.snapshot()[0]["args"] == {"step": 0, "tokens": 4}


def test_live_state_is_the_loops_state_inside_the_hooks_only(tmp_path):
    seen = []

    class Probe:
        def on_train_step(self, trainer, step):
            seen.append(("train_step", step, int(trainer.live_state.step)))

        def on_step_end(self, trainer, step, metrics):
            seen.append(("step_end", step, int(trainer.live_state.step)))

    trainer, _ = _fit(tmp_path, callbacks=[Probe()])
    assert trainer.live_state is None
    assert [s for s in seen if s[0] == "train_step"] == [
        ("train_step", 1, 1), ("train_step", 2, 2), ("train_step", 3, 3),
    ]
    assert [s for s in seen if s[0] == "step_end"] == [("step_end", 2, 2), ("step_end", 3, 3)]


def test_live_state_is_cleared_when_a_hook_raises(tmp_path):
    class Boom:
        def on_train_step(self, trainer, step):
            assert trainer.live_state is not None
            raise RuntimeError("boom")

    from llm_training_tpu.trainer import Trainer

    held = {}
    original = Trainer._fit_inner

    def keep(self, *args, **kwargs):
        held["trainer"] = self
        return original(self, *args, **kwargs)

    Trainer._fit_inner = keep
    try:
        with pytest.raises(RuntimeError, match="boom"):
            _fit(tmp_path, callbacks=[Boom()])
    finally:
        Trainer._fit_inner = original
    assert held["trainer"].live_state is None


def test_engine_close_frees_the_pool_and_keeps_stats(fresh):
    engine = _engine()
    engine.run(_requests(3))
    pool_k, pool_v = engine._pool_k, engine._pool_v
    cache_bytes = engine.stats()["decode/cache_bytes"]
    assert cache_bytes == pool_k.size * pool_k.dtype.itemsize * 2
    engine.close()
    assert pool_k.is_deleted() and pool_v.is_deleted()
    assert engine._pool_k is None and engine._pool_v is None
    engine.close()  # idempotent
    assert engine.stats()["decode/cache_bytes"] == cache_bytes
    assert json.dumps(engine.stats())  # still a plain record


def test_the_longest_fetch_is_kept_and_a_stall_outlives_the_ring(fresh, monkeypatch):
    """ROADMAP S8's probe: the longest `serve/decode_fetch` of the engine's
    life with its wall seconds, the process's CPU seconds across it and its
    step in `stats()`; one over `STALL_SECONDS` is a pinned `serve/stall`."""
    import time

    from llm_training_tpu.serve import engine as serve_engine

    engine = _engine()
    for request in _requests(6):
        engine.submit(**request)
    for _ in range(4):
        engine.step()
    quiet = engine.stats()
    assert 0 < quiet["serve/longest_fetch_s"] < serve_engine.STALL_SECONDS
    assert 1 <= quiet["serve/longest_fetch_step"] <= 4
    assert "serve/stall" not in [f"{e['cat']}/{e['name']}" for e in fresh.pinned()]
    device_get = jax.device_get

    def asleep(tree):  # the planted stall: the process sleeps, its CPU does not run
        time.sleep(serve_engine.STALL_SECONDS + 0.1)
        return device_get(tree)

    monkeypatch.setattr(jax, "device_get", asleep)
    engine.step()
    monkeypatch.setattr(jax, "device_get", device_get)
    while not engine.idle:
        engine.step()
    stats = engine.stats()
    assert stats["serve/longest_fetch_step"] == 5
    assert stats["serve/longest_fetch_s"] >= serve_engine.STALL_SECONDS + 0.1
    assert stats["serve/longest_fetch_cpu_s"] < stats["serve/longest_fetch_s"] / 2
    for _ in range(fresh.capacity):  # the ring turns over
        fresh.instant("serve", "submit", write=False)
    (stall,) = [e for e in fresh.pinned() if (e["cat"], e["name"]) == ("serve", "stall")]
    assert stall["args"] == {
        "step": 5, "fetch_s": stats["serve/longest_fetch_s"],
        "cpu_s": stats["serve/longest_fetch_cpu_s"],
    }
    assert "stall" not in [e["name"] for e in fresh.snapshot()]
