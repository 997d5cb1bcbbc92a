"""What PR 45 adds to the benchmark, on the CPU: the copy of the Phi-4-mini-flash
reference against the package's (byte for byte) and against a hand-written
two-token recurrence, the blocked runner's text against `serve_closed`'s and a
whole rehearsal of a tiny copy of `phi4flash-serve-reasoning` through it
(sound, with the fp8 control, and with a fault planted in the program), the
selective scan's decode cost against a hand count, the four new readers on a
hand-made trace (and -1 on one without their scopes), and the configuration's
file against the sizes it states. (The benchmark's older test files are not
edited by a `model_config` PR, so these cases live here.)"""

import copy
import inspect
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import common, span_reduce
from benchmarks.costs import ssm_decode
from benchmarks.references import phi4flash
from benchmarks.run import run_cell
from conftest import REPO, make_root

LAYER_METRICS = REPO / "benchmarks" / "layer_metrics"
REAL_CELL = "phi4flash-serve-reasoning"
# two query pairs on ONE key/value pair, a window of 8 under rows of 32, 128 channels in one run of lanes
TINY_PHI4 = {
    "source": "test", "model_type": "phi4flash", "hidden_size": 64, "intermediate_size": 96,
    "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 8, "head_dim": 16,
    "vocab_size": 256, "max_position_embeddings": 4096, "layer_norm_eps": 1e-05, "hidden_act": "silu",
    "sliding_window": 8, "mb_per_layer": 2, "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": 4,
    "layer_types": ["mamba", "sliding_attention"] * 2 + ["mamba", "full_attention", "gated_memory_unit", "full_attention"],
    "initializer_range": 0.02, "initializer_scales": {"conv_kernel": {"std": 1.0}, "D": {"value": 1.0}},  # the real file: 0.29, at 40 times the width
    "reference": "phi4flash", "control_precision": "fp8",
    # read here: sound 0.0036 to 0.0042, the fp8 control over it (the rehearsal holds that); the faults below 0.31,
    # 0.084, 0.017. The second limit is open: at this size the first sees every fault (shares over 0.001: sound 0.003,
    # the faults 0.27, 0.044, 0.012)
    "check": {"served_logit_gap": 0.008, "far_level": 0.001, "far_token_share": 1.0},
    "program": {"model_class": "Phi4Flash", "model_kwargs": {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}},
}
TINY_TRAFFIC = {
    "kind": "serve_closed_blocked", "clients": 4,
    "engine": {"max_batch": 4, "prefill_chunk": 4, "max_model_len": 32, "block_size": 8},
    "prompt_lengths": [2, 6, 3, 5], "output_lengths": [4, 16, 8, 12, 10],
    "stagger_first_output": True, "eos": None,
}
CELL = "tiny-phi4flash-serve"


@pytest.fixture
def phi4_root(tmp_path):
    """The tiny checkout of conftest.py with one more configuration and cell,
    added as the real one is: a file, and entries at the ends of the lists."""
    root = make_root(tmp_path)
    (root / "benchmarks" / "configs" / "tiny-phi4flash.json").write_text(json.dumps(TINY_PHI4))
    (root / "benchmarks" / "traffic" / "tiny-phi4flash-closed.json").write_text(json.dumps(TINY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-phi4flash", "source": "test", "file": "benchmarks/configs/tiny-phi4flash.json",
                             "reduced": [], "why": "tiny, for the CPU"})
    bench["workloads"].append({"name": CELL, "config": "tiny-phi4flash", "traffic": "tiny-phi4flash-closed", "chips": 1,
                               "why": "tiny, for the CPU"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if REAL_CELL in metric.get("workloads", ()):
                metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


# -------------------------------------------------------------- the reference


def test_the_copy_of_the_reference_is_the_packages_byte_for_byte():
    package = REPO / "llm_training_tpu/models/phi4flash/reference.py"
    assert (REPO / "benchmarks/references/phi4flash.py").read_bytes() == package.read_bytes()
    assert "llm_training_tpu" not in [line.split()[1].split(".")[0] for line in package.read_text().splitlines()
                                      if line.startswith(("import ", "from "))]


def test_reference_recurrence_is_the_two_token_hand_count():
    """One channel, two state values, two tokens, every number worked here:
    s_1 = (delta_1 x_1) B_1 (from zero); s_2 = exp(delta_2 A) * s_1 + (delta_2
    x_2) B_2; y_t = s_t . C_t + D x_t; out = y * silu(z). The projections are
    identities or picks, the convolution one tap of 1 (so x is silu(u))."""
    cfg = {"mamba_d_state": 2, "mamba_d_conv": 1, "mamba_dt_rank": 1}
    u = np.array([[[0.5], [-1.0]]], np.float32)  # [B=1, S=2, hidden=1]
    x = u / (1 + np.exp(-u))  # silu
    w = {
        "in_proj": {"kernel": jnp.asarray([[1.0, 1.0]])},  # x = z = u
        "conv_kernel": jnp.ones((1, 1)), "conv_bias": jnp.zeros((1,)),
        "x_proj": {"kernel": jnp.asarray([[1.0, 2.0, -1.0, 0.5, 3.0]])},  # dt = x; B = (2x, -x); C = (0.5x, 3x)
        "dt_proj": {"kernel": jnp.asarray([[1.0]])}, "dt_bias": jnp.asarray([0.25]),
        "A_log": jnp.log(jnp.asarray([[1.0, 2.0]])), "D": jnp.asarray([0.5]),
        "out_proj": {"kernel": jnp.asarray([[1.0]])},
    }
    xs = x[0, :, 0]
    delta = np.log1p(np.exp(xs + 0.25))
    a = np.array([-1.0, -2.0])
    s1 = delta[0] * xs[0] * np.array([2 * xs[0], -xs[0]])
    s2 = np.exp(delta[1] * a) * s1 + delta[1] * xs[1] * np.array([2 * xs[1], -xs[1]])
    y = np.array([s1 @ (np.array([0.5, 3.0]) * xs[0]), s2 @ (np.array([0.5, 3.0]) * xs[1])]) + 0.5 * xs
    out, memory = phi4flash.mamba(jnp.asarray(u), w, cfg, jnp.ones((1, 2), jnp.int32))
    assert np.allclose(np.asarray(memory)[0, :, 0], y, atol=1e-6)  # the memory: before the gate
    assert np.allclose(np.asarray(out)[0, :, 0], y * xs, atol=1e-6)  # silu(z) = silu(u) = x
    # a start in front of the second token: it sees a zero state
    _, alone = phi4flash.mamba(jnp.asarray(u), w, cfg, jnp.asarray([[1, 2]], jnp.int32))
    fresh = delta[1] * xs[1] * np.array([2 * xs[1], -xs[1]]) @ (np.array([0.5, 3.0]) * xs[1]) + 0.5 * xs[1]
    assert np.allclose(np.asarray(alone)[0, 1, 0], fresh, atol=1e-6)


def test_reference_differential_attention_is_two_softmaxes_subtracted():
    """One query pair, one key/value pair of heads of 2, three tokens, window
    2: a_r by hand from two softmaxes over the window, then the subtraction,
    the norm over the 4 values and the factor."""
    cfg = {"num_attention_heads": 2, "num_key_value_heads": 2, "hidden_size": 4, "head_dim": 2, "layer_norm_eps": 1e-5}
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 3, 4)).astype(np.float32)
    eye = {"kernel": jnp.eye(4), "bias": jnp.zeros((4,))}
    lam = {k: jnp.asarray(rng.normal(size=2), jnp.float32) for k in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")}
    w = {"q_proj": eye, "k_proj": eye, "v_proj": eye, "o_proj": eye, "subln": {"weight": jnp.ones((4,))}, **lam}
    got, _ = phi4flash.diff_attention(jnp.asarray(x), w, cfg, jnp.ones((1, 3), jnp.int32), jnp.float32(3.0), 2)
    start = 0.8 - 0.6 * np.exp(-0.9)
    lam_v = np.exp(np.dot(lam["lambda_q1"], lam["lambda_k1"])) - np.exp(np.dot(lam["lambda_q2"], lam["lambda_k2"])) + start
    want = np.zeros((3, 4))
    for t in range(3):
        a = []
        for r in (0, 1):  # q_r = k_r = x[2r : 2r + 2]; the value is the whole x (v1 ; v2)
            keys = range(max(0, t - 1), t + 1)  # a window of 2: itself and the one before
            scores = np.array([x[0, t, 2 * r:2 * r + 2] @ x[0, s, 2 * r:2 * r + 2] / np.sqrt(2) for s in keys])
            p = np.exp(scores - scores.max())
            a.append((p / p.sum()) @ x[0, list(keys)])
        d = a[0] - lam_v * a[1]
        want[t] = (1 - start) * d / np.sqrt(np.mean(d * d) + 1e-5)
    assert np.allclose(np.asarray(got)[0], want, atol=1e-5)


# ------------------------------------------------------------------- the cell


def test_the_blocked_runner_states_serve_closeds_window_word_for_word():
    """Up to the counters' line, `serve_closed_blocked.run` is `serve_closed.run`."""
    runners = REPO / "benchmarks" / "runners"
    mine = common.load_module(runners / "serve_closed_blocked.py")
    theirs = common.load_module(runners / "serve_closed.py")
    upto = lambda fn: inspect.getsource(fn).split('common.log("counters", counters)')[0]
    assert upto(mine.run) == upto(theirs.run) and len(upto(mine.run).splitlines()) > 60
    for name in ("Loop", "step_counts", "host_stalls", "TRACED_SECONDS"):
        assert getattr(mine, name) is getattr(theirs, name)
    assert inspect.getsource(mine.served_gaps).count("widest(") == inspect.getsource(theirs.served_gaps).count("widest(")


def test_the_cell_is_found_and_rehearsed_and_its_control_is_not_correct(phi4_root):
    cell = common.Cell(phi4_root, CELL)
    names = {m["name"] for m in cell.metrics("per_layer")}
    assert {"decode_ssm_device_ms", "ssm_decode_roofline_pct", "decode_cross_attn_device_ms", "decode_gmu_device_ms",
            "decode_window_attn_device_ms", "paged_decode_groups_roofline_pct", "decode_norm_device_ms",
            "engine_step_host_ms", "compile_s"} <= names
    assert not {"paged_decode_roofline_pct", "gdn_decode_roofline_pct", "moe_dispatch_device_ms"} & names
    # 64 closed clients on 64 rows are AT capacity: the rate is the end-to-end metric
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["serve_tok_s", "setup_s"]
    runner = cell.module("runners", "serve_closed_blocked")
    runner.BLOCK_POSITIONS = 8  # four blocks: the gap is a maximum over blocks
    outcome = runner.run(cell, 3_000_000_037, 2.0, False, require_tpu=False)
    common.restore_host()
    assert outcome["correct"] is True and outcome["failed"] == 0 and outcome["attempted"] > 0
    limit = cell.config["check"]["served_logit_gap"]
    sound, control = outcome["readings"], outcome["control"]("fp8")
    assert sound["served_logit_gap"] <= limit < control["control_fp8"], (sound, control)
    assert sound["tokens_compared"] == control["tokens_compared"] > 0


def test_the_blocked_check_is_the_whole_forwards(phi4_root, monkeypatch):
    """Made-up served tokens (far from the reference's best, so the gap is
    large and some block's): the maximum over blocks of 8 positions is the
    maximum over one `logits` call a request."""
    import flax.linen as nn

    cell = common.Cell(phi4_root, CELL)
    runner = cell.module("runners", "serve_closed_blocked")
    monkeypatch.setattr(runner, "BLOCK_POSITIONS", 8)
    model = common.build_model(cell.config)
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    variables = nn.meta.unbox(jax.jit(lambda k: common.seeded_tree(k, abstract, 0.3))(common.base_key(7)))
    rng = np.random.default_rng(3)
    finished = [
        {"prompt": rng.integers(0, 256, size=n).tolist(), "done": {"tokens": rng.integers(0, 256, size=m).tolist()}}
        for n, m in ((2, 4), (6, 26), (5, 12))
    ]
    below = []
    for r in finished:
        tokens = r["prompt"] + r["done"]["tokens"]
        ids, seg = np.zeros((1, 32), np.int32), np.zeros((1, 32), np.int32)
        ids[0, :len(tokens)], seg[0, :len(tokens)] = tokens, 1
        logits = np.asarray(phi4flash.logits(variables["params"], cell.config, jnp.asarray(ids), jnp.asarray(seg)))[0]
        at = np.arange(len(r["prompt"]) - 1, len(tokens) - 1)  # position p chooses token p + 1
        below += (logits[at].max(-1) - logits[at, r["done"]["tokens"]]).tolist()
    want, level = max(below), float(np.median(below)) + 1e-3
    monkeypatch.setattr(runner, "FAR_LADDER", (level,))
    got = runner.served_gaps(cell, variables, finished)
    assert got["tokens_compared"] == 42 and got["requests"] == 3
    assert want > 1.0 and abs(got["served_logit_gap"] - want) < 1e-4
    # the second number: the tokens farther off than a level, of all compared
    far = sum(b > level for b in below)
    assert 0 < far < 42 and got["far_shares"][str(level)] == far / 42
    assert got["far_token_share"] == got["far_shares"][str(cell.config["check"]["far_level"])] >= far / 42


def test_the_engine_serves_and_the_reference_reads_the_scaled_leaves(phi4_root):
    """`initializer_scales`: the named vectors drawn anew from the run's seed,
    every other leaf the array `serve_closed.build_engine` made, and the
    engine bound to the new tree."""
    cell = common.Cell(phi4_root, CELL)
    runner = cell.module("runners", "serve_closed_blocked")
    theirs = common.load_module(REPO / "benchmarks" / "runners" / "serve_closed.py")
    plain, engine = theirs.build_engine(cell, 11)
    engine.close()
    variables, engine = runner.build_engine(cell, 11)
    assert engine.variables is variables and engine.weights_generation == 1
    engine.close()
    again = runner.scaled_leaves(plain, cell.config["initializer_scales"], 11)
    other = runner.scaled_leaves(plain, cell.config["initializer_scales"], 12)
    changed = []
    for (path, new), old, same, differs in zip(
        jax.tree_util.tree_leaves_with_path(variables), *map(jax.tree.leaves, (plain, again, other))
    ):
        assert new.shape == old.shape and new.dtype == old.dtype and np.array_equal(new, same)
        if not np.array_equal(new, old):
            changed.append(common.path_str(path).rsplit("/", 1)[-1])
            assert changed[-1] == "D" or not np.array_equal(new, differs)  # the seed's draw
    assert sorted(changed) == ["D", "D", "conv_kernel", "conv_kernel"]  # the self-decoder's stack and layer L/2
    mamba = variables["params"]["self_decoder"]["slot0"]["mamba"]
    assert np.all(np.asarray(mamba["D"]) == 1.0) and mamba["D"].shape == (2, 128)
    assert 0.9 < float(jnp.std(mamba["conv_kernel"].astype(jnp.float32))) < 1.1


def _misread_shared_cache(monkeypatch):
    """The cross layer reading the window group's first layer (its ring, its
    window) where it should read the full layer's pages."""
    from llm_training_tpu.models import cache as cache_module

    proper = cache_module.LayerCache.attend

    def misread(self, layer, q, k, v, segment_ids, **kwargs):
        if k is None:  # a layer that appends nothing: `window` sends it to the window group
            kwargs["window"] = 8
        return proper(self, layer, q, k, v, segment_ids, **kwargs)

    monkeypatch.setattr(cache_module.LayerCache, "attend", misread)


def _slab_never_written(monkeypatch):
    """A Mamba layer's new state and convolution tail dropped: every token
    starts from the slot's zeros."""
    from llm_training_tpu.models import cache as cache_module

    monkeypatch.setattr(
        cache_module.LayerCache, "put_recurrent_rows", lambda self, layer, rows, in_place=False: self
    )


def _memory_zeroed(monkeypatch):
    """The gated memory units gating zeros where layer L/2's scan output belongs."""
    from llm_training_tpu.models.phi4flash import model as module

    proper = module.GatedMemoryUnit.__call__
    monkeypatch.setattr(
        module.GatedMemoryUnit, "__call__", lambda self, hidden, memory: proper(self, hidden, jnp.zeros_like(memory))
    )


@pytest.mark.parametrize("plant", [_misread_shared_cache, _slab_never_written, _memory_zeroed, None])
def test_a_planted_fault_is_not_correct(phi4_root, monkeypatch, plant):
    """Each through the whole cell as the harness runs it, by `served_logit_gap`
    alone (the second limit stands open at this size). The last case plants
    nothing and closes the SECOND limit under the sound reading: `correct`
    holds both numbers."""
    limits = TINY_PHI4["check"]
    if plant is None:
        limits = {"served_logit_gap": 1.0, "far_level": 0.0005, "far_token_share": 0.0005}
        (phi4_root / "benchmarks/configs/tiny-phi4flash.json").write_text(json.dumps({**TINY_PHI4, "check": limits}))
    else:
        plant(monkeypatch)
    result = run_cell(phi4_root, CELL, 3_000_000_041, 2.0, False, require_tpu=False)
    common.restore_host()
    assert result["correct"] is False and result["failed"] == 0 and result["attempted"] > 0
    gap, share = result["readings"]["served_logit_gap"], result["readings"]["far_token_share"]
    assert (gap > limits["served_logit_gap"]) == (plant is not None) and (plant is not None or share > limits["far_token_share"])


# ---------------------------------------------------------- cost and readers


def test_ssm_decode_cost_is_the_hand_count():
    # the cell: 64 rows, 5120 channels, 16 state values a channel, float32
    one = ssm_decode.cost(64, 5120, 16)
    state = 64 * 5120 * 16 * 4
    assert state == 20_971_520
    vectors = 64 * (3 * 5120 + 2 * 16) * 4  # x, delta, y a channel; B, C a state value
    assert one["bytes"] == 2 * state + vectors + 5120 * 16 * 4 == 46_211_072
    assert one["flops"] == 64 * 5120 * 16 * 7 == 36_700_160
    # the chip's 819 GB/s: 0.0564 ms a layer, 0.51 ms the nine
    assert one["bytes"] / 819e9 == pytest.approx(0.05642e-3, rel=1e-3)
    # half the rows idle: half the rows' work (A is read once a call either way)
    assert ssm_decode.cost(32, 5120, 16)["bytes"] * 2 - 5120 * 16 * 4 == one["bytes"]


def _scope(body, block, part=""):
    return f"jit(decode_step)/jit(main)/Phi4Flash/{body}/{block}/{part}"


def _step(at):
    """One decode step's ops from `at` ns: conv 100, step 300 + 200 (the
    update and the readout), a Mamba projection 60, the gated unit 150, the
    window layer's kernel 400, the full layer's 500 with its append 50, the
    cross layer's 700, the difference 80, an MLP 200."""
    attn = lambda body, scope: _scope(body, "slot1/self_attn", f"diff_attn/{scope}/")
    return [
        ["while.1 s32[]", at, 2740.0, ""],
        ["fusion.1 bf16[4,256]", at, 60.0, _scope("self_decoder/while/body", "slot0/mamba", "in_proj/dot_general")],
        ["fusion.2 f32[4,128]", at + 60, 100.0, _scope("self_decoder/while/body", "slot0/mamba", "ssm_conv/mul")],
        ["fusion.3 f32[12,1,16,128]", at + 160, 300.0, _scope("self_decoder/while/body", "slot0", "mamba/ssm_step/dynamic_update_slice")],
        ["fusion.4 f32[4,1,128]", at + 460, 200.0, _scope("self_decoder/while/body", "slot0/mamba", "ssm_step/reduce_sum")],
        ["paged_decode.1 bf16[4,4,32]", at + 660, 400.0, attn("self_decoder/while/body", "attn_window")],
        ["kv_page_write.1 bf16[5,1,8,32]", at + 1060, 50.0, attn("between", "attn_global")],
        ["paged_decode.2 bf16[4,4,32]", at + 1110, 500.0, attn("between", "attn_global")],
        ["fusion.5 bf16[4,128]", at + 1610, 150.0, _scope("cross_decoder/while/body", "slot0/gmu", "out_proj/dot_general")],
        ["paged_decode.3 bf16[4,4,32]", at + 1760, 700.0, attn("cross_decoder/while/body", "attn_global/attn_cross")],
        ["fusion.6 bf16[4,2,32]", at + 2460, 80.0, _scope("cross_decoder/while/body", "slot1/self_attn", "diff_attn/sub")],
        ["fusion.7 bf16[4,64]", at + 2540, 200.0, _scope("cross_decoder/while/body", "slot1/mlp", "down_proj/dot_general")],
    ]


TRACE = {
    "spans": [
        {"name": "serve/engine_step", "thread": "python3", "start": 0.0, "dur": 3000.0,
         "args": {"step": 1, "decode_rows": 4, "live_tokens": 40, "window_live_tokens": 30, "shared_kv_reads": 6}},
        {"name": "serve/engine_step", "thread": "python3", "start": 3000.0, "dur": 3000.0,
         "args": {"step": 2, "decode_rows": 4, "live_tokens": 44, "window_live_tokens": 32, "shared_kv_reads": 8}},
    ],
    "devices": {"0": {
        "programs": [["jit_decode_step(1)", 100.0, 2740.0], ["jit_decode_step(1)", 3100.0, 2740.0]],
        "ops": _step(100.0) + _step(3100.0),
    }},
}
COUNTERS = {"traced": {"decode_steps": 2, "decode_rows": 8, "live_tokens": 84}}


def _read(name, trace, monkeypatch):
    monkeypatch.setattr(span_reduce, "for_cell", lambda cell: trace)
    cell = SimpleNamespace(
        config=TINY_PHI4, traffic=TINY_TRAFFIC, device={"kind": "TPU v5 lite"},
        peaks=lambda kind: {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    reader = common.load_module(LAYER_METRICS / f"{name}.py")
    return reader.read({"devices": trace["devices"]}, COUNTERS, cell)


def test_the_new_readers_give_the_hand_worked_numbers(monkeypatch, capsys):
    assert _read("decode_ssm_device_ms", TRACE, monkeypatch) == pytest.approx(600e-6)  # conv 100 + step 500 ns, in ms
    assert "ssm_conv 0.0001, ssm_step 0.0005, the rest of /mamba/ 0.0001 of 0.0007" in capsys.readouterr().out
    # 4 rows a step, 128 channels of 16, 3 Mamba layers: bytes a layer over the chip's 819 GB/s,
    # 2 steps x 3 layers of it over the 1000 ns the two steps' recurrences took
    one = ssm_decode.cost(4, 128, 16)
    assert one["bytes"] == 2 * 4 * 128 * 16 * 4 + 4 * (3 * 128 + 32) * 4 + 128 * 16 * 4 == 80_384
    want = 100.0 * (one["bytes"] / 819e9) * 2 * 3 / 1000e-9
    assert _read("ssm_decode_roofline_pct", TRACE, monkeypatch) == pytest.approx(want)
    assert "ssm_step: 2 decode steps x 3 layers, 0.0002 ms a layer" in capsys.readouterr().out
    # the full layer's append and kernel 550 ns and the cross layer's kernel 700, a step
    assert _read("decode_cross_attn_device_ms", TRACE, monkeypatch) == pytest.approx(1250e-6)
    logged = capsys.readouterr().out
    # 7 pages a step of 8 tokens x 2 heads x 16 x 2 (k, v) x 2 B = 1,024 B each
    assert "of it attn_cross 0.0007 (the readers) and 0.0005 the writing layer; shared_kv_reads 7 pages a step" in logged
    assert _read("decode_gmu_device_ms", TRACE, monkeypatch) == pytest.approx(150e-6)
    # and the accepted readers the cell lists find their scopes: the window group's 400 ns
    assert _read("decode_window_attn_device_ms", TRACE, monkeypatch) == pytest.approx(400e-6)


@pytest.mark.parametrize("name", ["decode_ssm_device_ms", "ssm_decode_roofline_pct", "decode_gmu_device_ms",
                                  "decode_cross_attn_device_ms"])
def test_a_program_without_the_scopes_is_not_a_reading(monkeypatch, name):
    """The parent, or any stack without these layers: -1 or nothing, never a number."""
    plain = copy.deepcopy(TRACE)
    for op in plain["devices"]["0"]["ops"]:
        op[3] = op[3].replace("ssm_", "gdn_").replace("/gmu/", "/mlp/").replace("attn_cross", "attn_own")
    for span in plain["spans"]:
        span["args"].pop("shared_kv_reads")
    got = _read(name, plain, monkeypatch)
    assert got is None if name == "decode_cross_attn_device_ms" else got == span_reduce.NOT_A_READING < 0
    # and with no decode step in the trace at all there is nothing to read
    empty = copy.deepcopy(TRACE)
    empty["devices"]["0"]["programs"] = []
    assert _read(name, empty, monkeypatch) is None


# ------------------------------------------------------------ the configuration


def test_the_configuration_states_the_published_widths_uncut():
    import flax.linen as nn

    cfg = json.loads((REPO / "benchmarks/configs/phi4-mini-flash-reasoning.json").read_text())
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "phi4-mini-flash-reasoning")
    assert entry["reduced"] == [] and "reduced_from" not in cfg and len(entry["why"]) <= 200
    assert entry["source"] == cfg["source"] == "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
    published = {  # the catalog's row, every key
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
    }
    assert {k: cfg[k] for k in published} == published
    for key in ("assumed", "deployment", "arithmetic", "check", "stated_precision"):
        assert cfg[key]
    kinds = cfg["layer_types"]
    assert len(kinds) == 32 and kinds == [
        {"mamba": "mamba", "memory": "mamba", "window": "sliding_attention", "full": "full_attention",
         "cross": "full_attention", "gmu": "gated_memory_unit"}[k] for k in phi4flash.layer_kinds(cfg)
    ]
    model = common.build_model(cfg)
    assert model.config.layer_kinds == phi4flash.layer_kinds(cfg)
    shapes = nn.meta.unbox(jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    size = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))
    mamba = shapes["self_decoder"]["slot0"]["mamba"]
    assert mamba["in_proj"]["kernel"].shape == (8, 2560, 10240) and mamba["A_log"].shape == (8, 5120, 16)
    assert mamba["x_proj"]["kernel"].shape == (8, 5120, 192) and mamba["conv_kernel"].shape == (8, 4, 5120)
    assert shapes["cross_decoder"]["slot0"]["gmu"]["in_proj"]["kernel"].shape == (7, 2560, 5120)
    assert set(shapes["cross_decoder"]["slot1"]["self_attn"]) == {
        "q_proj", "o_proj", "subln", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"}  # no keys, no values
    assert size(shapes["between"]["slot0"]) == 119_895_040 and size(shapes["between"]["slot1"]) == 98_322_304
    assert size(shapes) == 3_852_562_944  # the arithmetic the file states
    assert "3,852,562,944" in cfg["arithmetic"]["parameters"]
    # the cell and its traffic, as the issue names them
    cell = next(w for w in bench["workloads"] if w["name"] == REAL_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("phi4-mini-flash-reasoning", "serve-reasoning-closed", 1)
    assert len(cell["why"]) <= 200 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1 and len(bench["workloads"]) == 9
    traffic = json.loads((REPO / "benchmarks/traffic/serve-reasoning-closed.json").read_text())
    longgen = json.loads((REPO / "benchmarks/traffic/serve-longgen-closed.json").read_text())
    assert traffic["kind"] == "serve_closed_blocked" and traffic["clients"] == 64
    assert traffic["engine"] == {"max_batch": 64, "prefill_chunk": 512, "max_model_len": 3072, "block_size": 16}
    assert traffic["prompt_lengths"] == longgen["prompt_lengths"] == [256, 1024, 512, 768]
    assert traffic["output_lengths"] == longgen["output_lengths"] == [1024, 2048, 1536, 1280, 1792]
    assert traffic["eos"] is None and traffic["stagger_first_output"] is True
    # the caches for 64 rows of 3,072: 5,120 B a token a key/value layer, however the heads are laid
    from llm_training_tpu.infer.cache import cache_specs
    from llm_training_tpu.serve.paged_cache import window_page_budget

    (full, window), recurrent = cache_specs(model.config)
    assert 2 * full.kv_heads * full.head_dim * 2 == 2 * 20 * 64 * 2 == 5120
    assert window_page_budget(512, 512, 16, 192) == 65
    assert (64 * 192 + 1) * 16 * 5120 == 1_006_714_880 and 8 * (64 * 65 + 1) * 16 * 5120 == 2_726_952_960
    assert recurrent.layers * 64 * 5120 * 16 * 4 == 188_743_680 and recurrent.stored == (40, 16, 128)
