"""The five readers of the program's start-up timeline
(`layer_metrics/setup_*_s.py`, `_setup_timeline.py`) on a hand-made pinned
list whose numbers are worked by hand here; on a program older than the
pinned store; and as `BENCHMARK.json` lists them."""

import json
from pathlib import Path

import pytest

from benchmarks import common, span_reduce as sr
from llm_training_tpu.telemetry import trace as program_trace

REPO = Path(__file__).resolve().parents[2]
LAYER_METRICS = REPO / "benchmarks" / "layer_metrics"
READERS = (
    "setup_pre_loop_s", "setup_trace_s", "setup_lower_s", "setup_backend_compile_s",
    "setup_to_ready_s",
)
T0 = 1000.0  # the process's start on the recorder's clock


def span(cat, name, start, dur, **args):
    event = {"ts": T0 + start, "dur": dur, "ph": "X", "cat": cat, "name": name}
    if args:
        event["args"] = args
    return event


def pinned_list():
    """A serving process: 19 s before the engine, the first chunk's call
    20..29 (its trace 20..26.8, an inner function's long trace 22..22.5 inside
    it, its lowering 26.8..28.7, a cache read of 0.3 s in a backend event of
    0.3 s), the first decode step's 29..36.5 (trace 29..34.6, lowering
    34.6..36.1, backend 0.4), ready at 36.5; then the reference's compiles."""
    return [
        span("setup", "engine_init", 19.0, 0.06),
        span("compile", "trace", 22.0, 0.5, fun="layer_body"),
        span("compile", "trace", 20.0, 6.8, fun="prefill_chunk"),
        span("compile", "lower", 26.8, 1.9, fun="prefill_chunk"),
        span("compile", "cache_read", 28.7, 0.3),
        span("compile", "backend", 28.7, 0.3, fun="prefill_chunk", cache_read_s=0.3),
        span("setup", "first_call", 20.0, 9.0, program="prefill_chunk"),
        span("compile", "trace", 29.0, 5.6, fun="decode_step"),
        span("compile", "lower", 34.6, 1.5, fun="decode_step"),
        span("compile", "backend", 36.1, 0.4, fun="decode_step"),
        span("setup", "first_call", 29.0, 7.5, program="decode_step"),
        {"ts": T0 + 36.5, "ph": "i", "cat": "setup", "name": "ready", "args": {
            "loop": "serve", "trace_short_s": 0.9, "trace_short_n": 700, "lower_short_s": 0.05,
            "lower_short_n": 4, "backend_short_s": 0.25, "backend_short_n": 9,
            "cache_read_short_s": 0.2, "cache_read_short_n": 9}},
        # after the window: the reference, and a second loop of the process
        span("compile", "trace", 100.0, 30.0, fun="reference_logits"),
        span("compile", "lower", 130.0, 8.0, fun="reference_logits"),
        span("compile", "backend", 138.0, 50.0, fun="reference_logits"),
        span("setup", "engine_init", 200.0, 0.05),
        {"ts": T0 + 230.0, "ph": "i", "cat": "setup", "name": "ready", "args": {"loop": "serve"}},
    ]


class Recorder:
    def __init__(self, events):
        self._events = events

    def pinned(self):
        return list(self._events)


@pytest.fixture()
def program(monkeypatch):
    """`read(name, events)`: one reader, as run.py calls it, on a process
    whose tracer pinned `events`."""
    monkeypatch.setattr(common, "T_PROCESS_START", T0)

    def read(name, events):
        previous = program_trace.set_tracer(Recorder(events))
        try:
            reader = common.load_module(LAYER_METRICS / f"{name}.py")
            return reader.read({}, {}, None)
        finally:
            program_trace.set_tracer(previous)

    return read


def test_every_reader_gives_the_hand_worked_number(program):
    events = pinned_list()
    assert program("setup_pre_loop_s", events) == pytest.approx(19.0)
    # the inner function's 0.5 s lie inside the chunk's trace: once
    assert program("setup_trace_s", events) == pytest.approx(6.8 + 5.6)
    assert program("setup_lower_s", events) == pytest.approx(1.9 + 1.5)
    # backend events do not nest: the pinned ones and what the short ones summed to
    assert program("setup_backend_compile_s", events) == pytest.approx(0.3 + 0.4 + 0.25)
    assert program("setup_to_ready_s", events) == pytest.approx(36.5)


def test_what_came_after_the_first_ready_is_left_out(program):
    events = pinned_list()
    first_ready = next(i for i, e in enumerate(events) if e["name"] == "ready")
    for name in READERS:
        assert program(name, events) == pytest.approx(program(name, events[: first_ready + 1]))
    # and what was still running when the loop got ready is not start-up's either
    events.insert(0, span("compile", "trace", 30.0, 20.0, fun="a_thread_of_its_own"))
    assert program("setup_trace_s", events) == pytest.approx(6.8 + 5.6)


def test_what_a_reader_logs_beside_its_number(program, capsys):
    events = pinned_list()
    program("setup_trace_s", events)
    program("setup_backend_compile_s", events)
    program("setup_to_ready_s", events)
    out = capsys.readouterr().out
    assert "prefill_chunk 6.800 (1), decode_step 5.600 (1), layer_body 0.500 (1)" in out
    assert "beside them 700 events under 0.1 s, 0.900 s" in out
    assert "by fun: decode_step 0.400 (1), prefill_chunk 0.300 (1); plus 9 under 0.1 s, 0.250 s" in out
    assert "cache reads by fun: prefill_chunk 0.300 (1) (9 reads under 0.1 s, 0.200 s)" in out
    assert "setup/first_call[prefill_chunk] 20.000+9.000, setup/first_call[decode_step] 29.000+7.500" in out
    assert "the loop's spans cover 16.560" in out


def test_a_train_cells_timeline_reads_the_fits_spans(program, capsys):
    events = [
        span("compile", "trace", 9.5, 0.5, fun="make_state"),
        span("setup", "fit_prepare", 9.0, 3.0),
        span("compile", "trace", 12.1, 4.0, fun="train_step"),
        span("compile", "backend", 17.0, 0.97, fun="train_step"),
        span("train", "compile", 12.0, 6.0),
        span("setup", "first_step", 18.5, 5.0, step=4),
        {"ts": T0 + 23.5, "ph": "i", "cat": "setup", "name": "ready", "args": {"loop": "fit", "step": 4}},
    ]
    assert program("setup_pre_loop_s", events) == pytest.approx(9.0)
    assert program("setup_trace_s", events) == pytest.approx(4.5)
    assert program("setup_lower_s", events) == 0.0  # no pinned lowering: a reading of none
    assert program("setup_backend_compile_s", events) == pytest.approx(0.97)
    assert program("setup_to_ready_s", events) == pytest.approx(23.5)
    assert "setup/fit_prepare 9.000+3.000, train/compile 12.000+6.000, setup/first_step 18.500+5.000" in capsys.readouterr().out


@pytest.mark.parametrize("name", READERS)
def test_a_program_older_than_the_pinned_store_gets_one_answer(program, name, capsys, monkeypatch):
    """The driver lays this benchmark over the PR's parent, whose recorder has
    no `pinned`: NOT_A_READING, logged, never a raise and never None."""

    class OldRecorder:
        def snapshot(self):
            return []

    previous = program_trace.set_tracer(OldRecorder())
    try:
        reader = common.load_module(LAYER_METRICS / f"{name}.py")
        assert reader.read({}, {}, None) == sr.NOT_A_READING < 0
    finally:
        program_trace.set_tracer(previous)
    assert "older than the start-up timeline" in capsys.readouterr().out


@pytest.mark.parametrize("name", READERS)
def test_a_store_without_its_span_ends_the_run(program, name):
    """The store is there and the program no longer says what the reader needs:
    None, on which run.py ends a traced run."""
    events = [e for e in pinned_list() if e["name"] != "ready"]
    assert program(name, events) is None
    if name == "setup_pre_loop_s":
        no_opener = [e for e in pinned_list() if e["name"] != "engine_init"]
        assert program(name, no_opener) is None


def test_the_program_pins_what_the_readers_read():
    """The names the readers match are the program's own (a rename on either
    side turns this red, not a traced run on the chip)."""
    timeline = common.load_module(LAYER_METRICS / "_setup_timeline.py")
    from llm_training_tpu.telemetry import profiling

    assert tuple(f"setup/{name}" for name in profiling.LOOP_OPENERS) == timeline.LOOP_OPENERS
    assert set(profiling.COMPILE_EVENTS.values()) == {"trace", "lower", "backend", "cache_read"}
    assert profiling.PIN_SECONDS == 0.1


def test_benchmark_json_lists_each_new_reader_with_the_ten_cells():
    """LISTED, wherever in `per_layer`: a later PR's entries go after these."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = listed["compile_s"]["workloads"]
    assert len(cells) >= 10 and cells[:10] == [w["name"] for w in bench["workloads"]][:10]
    for name in READERS:
        reader = common.load_module(LAYER_METRICS / f"{name}.py")
        entry = listed[name]
        assert (entry["layer"], entry["unit"], entry["moves"]) == (reader.LAYER, reader.UNIT, reader.MOVES)
        assert (entry["source"], entry["better"]) == ("program_span", "lower")
        assert entry["workloads"][:10] == cells[:10]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for cell in cells[:10]:
        assert set(READERS) <= {m["name"] for m in common.Cell(REPO, cell).metrics("per_layer")}
