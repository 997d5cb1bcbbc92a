"""A cell made only of new files and new entries is found by name and
rehearsed at a tiny size on the CPU: counts, and no device metric. The
control of each kind comes out not correct, and a run whose timed path is
broken underneath reports `correct` false."""

import json

import pytest

from benchmarks import common
from benchmarks.run import run_cell


@pytest.mark.parametrize("cell", ["tiny-phi3-serve", "tiny-olmoe-serve", "tiny-phi3-train"])
def test_a_cell_of_new_files_is_found_and_rehearsed(tiny_root, cell):
    result = run_cell(tiny_root, cell, seed=3_000_000_019, seconds=2.0, trace=False, require_tpu=False)
    common.restore_host()
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {} and result["device"]["platform"] == "cpu"
    assert result["counters"]["steps"] > 0 and result["counters"]["tokens"] > 0


def test_without_a_chip_a_measuring_run_prints_no_result(tiny_root):
    with pytest.raises(SystemExit):
        run_cell(tiny_root, "tiny-phi3-serve", seed=1, seconds=1.0, trace=False)


def test_a_new_per_layer_metric_is_a_new_file_and_a_new_entry(tiny_root):
    (tiny_root / "benchmarks" / "layer_metrics" / "steps_per_s.py").write_text(
        "LAYER, UNIT, MOVES = 'serving', '1/s', 'serve_tok_s'\n"
        "def read(trace, counters, cell):\n    return counters['steps'] / counters['span_s']\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "steps_per_s", "unit": "1/s", "better": "higher",
                               "source": "program_counter", "layer": "serving",
                               "moves": "serve_tok_s", "workloads": ["tiny-phi3-serve"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = common.Cell(tiny_root, "tiny-phi3-serve")
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert "steps_per_s" in names and "flash_roofline_pct" not in names
    reader = cell.module("layer_metrics", "steps_per_s")
    assert reader.read(None, {"steps": 10, "span_s": 2.0}, cell) == 5.0


@pytest.mark.parametrize("cell", ["tiny-phi3-serve", "tiny-olmoe-serve"])
def test_serve_control_in_lower_precision_is_not_correct(tiny_root, cell):
    the_cell = common.Cell(tiny_root, cell)
    runner = the_cell.module("runners", "serve_closed")
    outcome = runner.run(the_cell, 3_000_000_023, 2.0, False, require_tpu=False)
    common.restore_host()
    limit = the_cell.config["check"]["served_logit_gap"]
    sound, control = outcome["readings"], outcome["control"]("fp8")
    assert sound["requests"] == outcome["attempted"] > 0  # every finished request is compared
    assert sound["served_logit_gap"] == control["served_logit_gap"] <= limit < control["control_fp8"]


def test_train_control_in_lower_precision_is_not_correct(tiny_root):
    cell = common.Cell(tiny_root, "tiny-phi3-train")
    runner = cell.module("runners", "train_fit")
    datamodule = runner.packed_datamodule(cell.traffic, cell.config["vocab_size"], 5)
    datamodule.setup()
    stream = datamodule.train_batches(start_step=0)
    batches = [next(stream) for _ in range(3)]
    reference = runner.reference_readings(cell, 5, batches)
    control = runner.reference_readings(cell, 5, batches, "fp8")
    sound, _, _ = runner.compare(reference, reference, cell.config["check"])
    broken, _, lines = runner.compare(control, reference, cell.config["check"])
    assert sound and not broken, lines


def test_an_altered_served_token_is_not_correct(tiny_root, monkeypatch):
    from llm_training_tpu.serve.engine import ServingEngine

    done_event = ServingEngine._done_event

    def altered(self, request):
        event = done_event(self, request)
        if event["id"] == "r5":  # one request among those the window finishes
            at = len(event["tokens"]) // 2
            event["tokens"][at] = (event["tokens"][at] + 1) % 256
        return event

    monkeypatch.setattr(ServingEngine, "_done_event", altered)
    result = run_cell(tiny_root, "tiny-phi3-serve", 3_000_000_029, 2.0, False, require_tpu=False)
    common.restore_host()
    assert result["correct"] is False and result["failed"] == 0


def frozen_state(step):
    def frozen(state, batch):
        new_state, metrics = step(state, batch)
        return state.replace(step=new_state.step, opt_state=new_state.opt_state), metrics

    return frozen


def half_the_rows(step):
    def halved(state, batch):
        # the second half of the rows repeats the first: a part of the batch is left out
        half = {k: v.at[v.shape[0] // 2 :].set(v[: v.shape[0] // 2]) for k, v in batch.items()}
        return step(state, half)

    return halved


@pytest.mark.parametrize("broken,number", [
    (frozen_state, "param_change_norm_rel"), (half_the_rows, "loss_abs"),
])
def test_a_broken_train_step_is_not_correct(tiny_root, monkeypatch, broken, number):
    from llm_training_tpu.trainer import Trainer

    build = Trainer._build_step
    monkeypatch.setattr(
        Trainer, "_build_step", lambda self, objective, tx: broken(build(self, objective, tx))
    )
    result = run_cell(tiny_root, "tiny-phi3-train", 3_000_000_031, 2.0, False, require_tpu=False)
    common.restore_host()
    limits = common.Cell(tiny_root, "tiny-phi3-train").config["check"]
    assert result["correct"] is False and result["failed"] == 0
    assert result["readings"][number] > limits[number], result["readings"]


def test_a_loop_that_keeps_no_state_ends_the_run(tiny_root):
    runner = common.Cell(tiny_root, "tiny-phi3-train").module("runners", "train_fit")

    def loop_without_state():
        def on_train_step():
            return runner.loop_state(None)

        return on_train_step()

    with pytest.raises(SystemExit, match="no train state named `state` in loop_without_state"):
        loop_without_state()


def test_a_listed_reader_that_finds_nothing_ends_a_traced_run(tiny_root, monkeypatch):
    """BENCHMARK.json lists the cell for the metric, so nothing to read is a
    renamed program or kernel, not a metric to leave out."""
    from benchmarks import run, trace_reduce

    trace = {"devices": {"0": {"ops": [["fusion.1 bf16[8]", 0.0, 5e6]], "programs": []}}, "host": []}
    cell = common.Cell(tiny_root, "tiny-phi3-serve")
    runner = cell.module("runners", "serve_closed")
    outcome = {"correct": True, "attempted": 1, "failed": 0, "measured": {}, "trace_dir": tiny_root,
               "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
               "counters": {"compile_s": 1.0, "steps": 4, "prefill_steps": 1, "decode_steps": 4,
                            "decode_rows": 16, "max_batch": 4}}
    monkeypatch.setattr(runner, "run", lambda *a, **k: outcome)
    monkeypatch.setattr(trace_reduce, "load", lambda path: trace)
    monkeypatch.setattr(common, "newest_xplane", lambda trace_dir: trace_dir)
    with pytest.raises(SystemExit, match="decode_step_device_ms found nothing to read"):
        run.run_cell(tiny_root, "tiny-phi3-serve", 1, 1.0, True)
