"""Flash-attention block-size tuning layer.

Covers `ops/pallas/tuning.py` (resolution order: call > env > table >
default, all read at CALL time — the old import-time FLASH_BLOCK_* read
made overrides require a re-import), the telemetry gauges recording what
each compiled step ran with, and the one table of chip peaks the MFU
callback divides by."""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.ops.pallas import tuning
from llm_training_tpu.ops.pallas.flash_attention import flash_attention
from llm_training_tpu.telemetry import TelemetryRegistry, set_registry


@pytest.fixture(autouse=True)
def _isolate_tuning(monkeypatch, tmp_path):
    """Each test sees an empty table (not the committed one) unless it
    installs its own, and a clean cache before AND after."""
    monkeypatch.setenv(tuning.ENV_TABLE, str(tmp_path / "absent.json"))
    monkeypatch.delenv("FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("FLASH_BLOCK_K", raising=False)
    monkeypatch.delenv("FLASH_BLOCK_Q_BWD", raising=False)
    monkeypatch.delenv("FLASH_BLOCK_K_BWD", raising=False)
    tuning.clear_table_cache()
    yield
    tuning.clear_table_cache()


def _write_table(path: Path, entries: dict) -> None:
    path.write_text(json.dumps({"version": 1, "entries": entries}))


SHAPE = dict(seq_len=2048, head_dim=128, dtype=jnp.bfloat16, causal=True)


# ------------------------------------------------------------ resolution


def test_default_resolution():
    choice = tuning.resolve_block_sizes("fwd", **SHAPE)
    assert (choice.block_q, choice.block_k) == (tuning.DEFAULT_BLOCK,) * 2
    assert choice.source == "default"


def test_call_args_win_over_env_and_table(monkeypatch, tmp_path):
    table = tmp_path / "t.json"
    _write_table(table, {tuning.table_key("fwd", 2048, 128, jnp.bfloat16, True, None):
                         {"block_q": 512, "block_k": 512}})
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    monkeypatch.setenv("FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("FLASH_BLOCK_K", "256")
    choice = tuning.resolve_block_sizes("fwd", block_q=128, block_k=128, **SHAPE)
    assert (choice.block_q, choice.block_k, choice.source) == (128, 128, "call")


def test_env_wins_over_table_and_is_read_at_call_time(monkeypatch, tmp_path):
    table = tmp_path / "t.json"
    _write_table(table, {tuning.table_key("fwd", 2048, 128, jnp.bfloat16, True, None):
                         {"block_q": 512, "block_k": 512}})
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    tuning.clear_table_cache()
    assert tuning.resolve_block_sizes("fwd", **SHAPE).source == "table"
    # env set AFTER import/first resolution still takes effect: no
    # module-level constant involved anywhere
    monkeypatch.setenv("FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("FLASH_BLOCK_K", "384")
    choice = tuning.resolve_block_sizes("fwd", **SHAPE)
    assert (choice.block_q, choice.block_k, choice.source) == (256, 384, "env")


def test_bwd_env_knobs_fall_back_to_shared(monkeypatch):
    monkeypatch.setenv("FLASH_BLOCK_Q", "256")
    monkeypatch.setenv("FLASH_BLOCK_K", "256")
    assert tuning.resolve_block_sizes("bwd", **SHAPE).block_q == 256
    monkeypatch.setenv("FLASH_BLOCK_Q_BWD", "512")
    choice = tuning.resolve_block_sizes("bwd", **SHAPE)
    assert (choice.block_q, choice.block_k) == (512, 256)  # bwd-specific > shared


def test_fwd_and_bwd_table_entries_are_independent(monkeypatch, tmp_path):
    table = tmp_path / "t.json"
    _write_table(table, {
        tuning.table_key("fwd", 2048, 128, jnp.bfloat16, True, None):
            {"block_q": 1024, "block_k": 512},
        tuning.table_key("bwd", 2048, 128, jnp.bfloat16, True, None):
            {"block_q": 256, "block_k": 1024},
    })
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    fwd = tuning.resolve_block_sizes("fwd", **SHAPE)
    bwd = tuning.resolve_block_sizes("bwd", **SHAPE)
    assert (fwd.block_q, fwd.block_k) == (1024, 512)
    assert (bwd.block_q, bwd.block_k) == (256, 1024)
    assert fwd.source == bwd.source == "table"


def test_nearest_seq_fallback(monkeypatch, tmp_path):
    table = tmp_path / "t.json"
    _write_table(table, {
        tuning.table_key("fwd", 1024, 128, jnp.bfloat16, True, None):
            {"block_q": 256, "block_k": 256},
        tuning.table_key("fwd", 8192, 128, jnp.bfloat16, True, None):
            {"block_q": 2048, "block_k": 1024},
    })
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    near_small = tuning.resolve_block_sizes("fwd", **{**SHAPE, "seq_len": 1536})
    assert (near_small.block_q, near_small.source) == (256, "table")
    near_big = tuning.resolve_block_sizes("fwd", **{**SHAPE, "seq_len": 7000})
    assert (near_big.block_q, near_big.block_k) == (2048, 1024)
    # a different head_dim/dtype/window must NOT borrow these entries
    assert tuning.resolve_block_sizes("fwd", **{**SHAPE, "head_dim": 64}).source == "default"
    assert tuning.resolve_block_sizes(
        "fwd", **{**SHAPE, "sliding_window": 4096}).source == "default"


def test_missing_or_corrupt_table_degrades_to_default(monkeypatch, tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv(tuning.ENV_TABLE, str(bad))
    assert tuning.resolve_block_sizes("fwd", **SHAPE).source == "default"


def test_malformed_table_entries_degrade_not_crash(monkeypatch, tmp_path):
    """A structurally-valid table whose ENTRY is bad (missing knob, non-int,
    non-lane-multiple, wrong type) must degrade like a corrupt table —
    skipped at lookup, never a trace-time ValueError in a training run."""
    table = tmp_path / "t.json"
    _write_table(table, {
        tuning.table_key("fwd", 2048, 128, jnp.bfloat16, True, None):
            {"block_q": 100, "block_k": 512},        # not lane-aligned
        tuning.table_key("fwd", 1024, 128, jnp.bfloat16, True, None):
            {"block_q": 256},                        # missing block_k
        tuning.table_key("fwd", 4096, 128, jnp.bfloat16, True, None):
            ["not", "a", "dict"],
        tuning.table_key("bwd", 2048, 128, jnp.bfloat16, True, None):
            {"block_q": "huge", "block_k": 512},     # non-int
    })
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    tuning.clear_table_cache()
    assert tuning.resolve_block_sizes("fwd", **SHAPE).source == "default"
    assert tuning.resolve_block_sizes("bwd", **SHAPE).source == "default"
    # a valid entry at another seq still wins via nearest-seq over the
    # malformed exact hit
    _write_table(table, {
        tuning.table_key("fwd", 2048, 128, jnp.bfloat16, True, None):
            {"block_q": 100, "block_k": 512},
        tuning.table_key("fwd", 1024, 128, jnp.bfloat16, True, None):
            {"block_q": 256, "block_k": 256},
    })
    tuning.clear_table_cache()
    choice = tuning.resolve_block_sizes("fwd", **SHAPE)
    assert (choice.block_q, choice.source) == (256, "table")


def test_non_lane_multiple_rejected(monkeypatch):
    with pytest.raises(ValueError, match="multiple of 128"):
        tuning.resolve_block_sizes("fwd", block_q=100, block_k=128, **SHAPE)
    monkeypatch.setenv("FLASH_BLOCK_Q", "77")
    with pytest.raises(ValueError, match="multiple of 128"):
        tuning.resolve_block_sizes("fwd", **SHAPE)


def test_fit_block():
    assert tuning.fit_block(1024, 512) == 512
    assert tuning.fit_block(1024, 1536) == 768   # largest <=1024 dividing 1536
    assert tuning.fit_block(256, 384) == 128     # 256 doesn't divide 384
    assert tuning.fit_block(128, 2048) == 128
    with pytest.raises(ValueError, match="multiple of 128"):
        tuning.fit_block(128, 200)


def test_divisibility_error_for_explicit_blocks():
    """Explicit (call-site) blocks stay strict: the existing
    `_check_block_divisibility` message, not a silent degrade."""
    from llm_training_tpu.ops.pallas.flash_attention import flash_fwd_flat

    q = jnp.zeros((2, 384, 64), jnp.float32)
    seg = jnp.ones((1, 384), jnp.int32)
    with pytest.raises(ValueError, match="must be multiples of the blocks"):
        flash_fwd_flat(q, q, q, seg, seg, num_q_heads=2, num_kv_heads=2,
                       scale=1.0, causal=True, block_q=256, block_k=256,
                       interpret=True)


# ------------------------------------------------------------ end-to-end


def test_table_blocks_reach_kernel_and_telemetry(monkeypatch, tmp_path):
    """A table entry changes the compiled tiles AND is visible in telemetry
    (flash/* gauges + tuning_table_hit counters), numerics unchanged."""
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    try:
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.standard_normal((1, 512, 4, 64)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 512, 2, 64)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 512, 2, 64)), jnp.float32)
        cot = jnp.asarray(rng.standard_normal((1, 512, 4, 64)), jnp.float32)

        def grad_norm():
            g = jax.jit(jax.grad(
                lambda q, k, v: (flash_attention(q, k, v, causal=True) * cot).sum(),
                argnums=(0, 1, 2),
            ))(q, k, v)
            return [np.asarray(x) for x in g]

        base = grad_norm()
        snap = registry.snapshot()
        # gauges record the POST-clamp tiles (what actually compiled): the
        # 1024 default clamps to the 512-long sequence
        assert snap["flash/fwd/block_q"] == 512
        assert snap["flash/bwd/block_q"] == 512
        assert snap["flash/tuning_table_hit/default"] >= 2.0  # fwd + bwd

        table = tmp_path / "t.json"
        _write_table(table, {
            tuning.table_key("fwd", 512, 64, jnp.float32, True, None):
                {"block_q": 128, "block_k": 256},
            tuning.table_key("bwd", 512, 64, jnp.float32, True, None):
                {"block_q": 256, "block_k": 128},
        })
        monkeypatch.setenv(tuning.ENV_TABLE, str(table))
        tuning.clear_table_cache()
        tuned = grad_norm()
        snap = registry.snapshot()
        assert (snap["flash/fwd/block_q"], snap["flash/fwd/block_k"]) == (128, 256)
        assert (snap["flash/bwd/block_q"], snap["flash/bwd/block_k"]) == (256, 128)
        assert snap["flash/tuning_table_hit/table"] >= 2.0
        for a, b in zip(base, tuned):
            np.testing.assert_allclose(b, a, rtol=1e-5, atol=1e-5)
    finally:
        set_registry(previous)


def test_explicit_fwd_blocks_tile_both_passes():
    """The pre-tuning-layer contract: explicit block_q/block_k with no bwd
    override tile the backward too (scripts/microbench_flash.py's sweep
    depends on this); independent bwd tiles are an explicit opt-in."""
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    try:
        q = jnp.ones((1, 512, 2, 64), jnp.float32)
        jax.jit(jax.grad(
            lambda q: flash_attention(
                q, q, q, causal=True, block_q=256, block_k=128, interpret=True
            ).sum()
        ))(q)
        snap = registry.snapshot()
        assert (snap["flash/fwd/block_q"], snap["flash/fwd/block_k"]) == (256, 128)
        assert (snap["flash/bwd/block_q"], snap["flash/bwd/block_k"]) == (256, 128)
        assert snap["flash/tuning_table_hit/call"] >= 2.0
    finally:
        set_registry(previous)


def test_single_explicit_bwd_knob_keeps_env_for_other(monkeypatch):
    """Pinning ONE bwd knob in the call must not discard the env/table
    resolution of the OTHER: bwd_block_q=256 + FLASH_BLOCK_K_BWD=128 has to
    compile the backward at 256x128, not 256x<fwd tile>."""
    monkeypatch.setenv("FLASH_BLOCK_K_BWD", "128")
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    try:
        q = jnp.ones((1, 512, 2, 64), jnp.float32)
        jax.jit(jax.grad(
            lambda q: flash_attention(
                q, q, q, causal=True, bwd_block_q=256, interpret=True
            ).sum()
        ))(q)
        snap = registry.snapshot()
        assert (snap["flash/bwd/block_q"], snap["flash/bwd/block_k"]) == (256, 128)
    finally:
        set_registry(previous)


def test_explicit_fwd_blocks_respect_bwd_env(monkeypatch):
    """Explicit fwd tiles inherit to the backward ONLY when no bwd-specific
    source claims a knob: the documented FLASH_BLOCK_{Q,K}_BWD env override
    must still retile the backward of a pinned-fwd call (a bwd sweep that
    pins fwd tiles per call would otherwise measure the fwd tiles twice)."""
    monkeypatch.setenv("FLASH_BLOCK_Q_BWD", "128")
    monkeypatch.setenv("FLASH_BLOCK_K_BWD", "128")
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    try:
        q = jnp.ones((1, 512, 2, 64), jnp.float32)
        jax.jit(jax.grad(
            lambda q: flash_attention(
                q, q, q, causal=True, block_q=256, block_k=256, interpret=True
            ).sum()
        ))(q)
        snap = registry.snapshot()
        assert (snap["flash/fwd/block_q"], snap["flash/fwd/block_k"]) == (256, 256)
        assert (snap["flash/bwd/block_q"], snap["flash/bwd/block_k"]) == (128, 128)
        assert snap["flash/tuning_table_hit/env"] >= 1.0  # the bwd resolution
    finally:
        set_registry(previous)

    monkeypatch.setenv("FLASH_BLOCK_Q_BWD", "100")
    with pytest.raises(ValueError, match="multiple of 128"):
        flash_attention(q, q, q, causal=True, block_q=256, block_k=256,
                        interpret=True)


def test_explicit_bwd_blocks_validated_with_explicit_fwd():
    """The lane-multiple check must hold on EVERY path: explicit bwd tiles
    are rejected whether or not the fwd tiles are also explicit (a 192
    tile would otherwise slip past divisibility on a 384-long seq and die
    in Mosaic instead of a clean ValueError)."""
    q = jnp.ones((1, 384, 2, 64), jnp.float32)
    for extra in ({}, {"block_q": 128, "block_k": 128}):
        with pytest.raises(ValueError, match="multiple of 128"):
            flash_attention(q, q, q, causal=True, bwd_block_q=192,
                            bwd_block_k=192, interpret=True, **extra)


def test_single_explicit_fwd_knob_inherits_per_knob(monkeypatch, tmp_path):
    """Pinning ONLY block_q still pins the backward's q tile (per-knob
    inheritance); the unpinned k knob resolves through the shared chain —
    here a bwd table entry."""
    table = tmp_path / "t.json"
    _write_table(table, {
        tuning.table_key("bwd", 512, 64, jnp.float32, True, None):
            {"block_q": 128, "block_k": 128},
    })
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    tuning.clear_table_cache()
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    try:
        q = jnp.ones((1, 512, 2, 64), jnp.float32)
        jax.jit(jax.grad(
            lambda q: flash_attention(
                q, q, q, causal=True, block_q=256, interpret=True
            ).sum()
        ))(q)
        snap = registry.snapshot()
        assert (snap["flash/bwd/block_q"], snap["flash/bwd/block_k"]) == (256, 128)
    finally:
        set_registry(previous)


def test_explicit_fwd_blocks_ignore_bwd_table(monkeypatch, tmp_path):
    """...but a TABLE entry is not an override under explicit fwd tiles: a
    pinned microbench must measure the tiles it pinned, never a stale
    table's (env is deliberate per-run intent; the table is ambient)."""
    table = tmp_path / "t.json"
    _write_table(table, {
        tuning.table_key("bwd", 512, 64, jnp.float32, True, None):
            {"block_q": 128, "block_k": 128},
    })
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    tuning.clear_table_cache()
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    try:
        q = jnp.ones((1, 512, 2, 64), jnp.float32)
        jax.jit(jax.grad(
            lambda q: flash_attention(
                q, q, q, causal=True, block_q=256, block_k=256, interpret=True
            ).sum()
        ))(q)
        snap = registry.snapshot()
        assert (snap["flash/bwd/block_q"], snap["flash/bwd/block_k"]) == (256, 256)
    finally:
        set_registry(previous)


def test_hardware_table_entries_skipped_off_tpu(monkeypatch, tmp_path):
    """backend-tagged entries only apply to the runtime they were measured
    on: a v5e entry must not drive interpret-mode runs (and cpu-interpret
    placeholders must never drive a compiled TPU step)."""
    table = tmp_path / "t.json"
    _write_table(table, {
        tuning.table_key("fwd", 2048, 128, jnp.bfloat16, True, None):
            {"block_q": 512, "block_k": 512, "backend": "v5e"},
        tuning.table_key("bwd", 2048, 128, jnp.bfloat16, True, None):
            {"block_q": 256, "block_k": 256, "backend": "cpu-interpret"},
    })
    monkeypatch.setenv(tuning.ENV_TABLE, str(table))
    # this suite runs off-TPU: the v5e fwd entry is ignored, the
    # cpu-interpret bwd entry applies
    assert tuning.resolve_block_sizes("fwd", **SHAPE).source == "default"
    bwd = tuning.resolve_block_sizes("bwd", **SHAPE)
    assert (bwd.block_q, bwd.source) == (256, "table")


def test_forward_only_trace_records_no_bwd_gauges():
    """The bwd gauges say what the compiled step ACTUALLY ran with — a
    forward-only trace (eval/validation) compiles no backward kernel, so
    it must not report bwd tiles or count a bwd resolution."""
    registry = TelemetryRegistry()
    previous = set_registry(registry)
    try:
        q = jnp.ones((1, 256, 2, 64), jnp.float32)
        flash_attention(q, q, q, causal=True, interpret=True)
        snap = registry.snapshot()
        assert "flash/fwd/block_q" in snap
        assert not any(k.startswith("flash/bwd/") for k in snap), snap
        assert snap.get("flash/tuning_table_hit/default", 0) == 1.0  # fwd only
        # ...and the backward records exactly once a grad trace exists
        jax.jit(jax.grad(lambda q: flash_attention(
            q, q, q, causal=True, interpret=True).sum()))(q)
        snap = registry.snapshot()
        assert snap["flash/bwd/block_q"] == 256
    finally:
        set_registry(previous)


def test_resolved_blocks_fit_sequence():
    """Default 1024 tiles on a 256-long input must degrade to runnable
    tiles (no divisibility crash) — the wrapper clamps fwd, fits bwd."""
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    assert out.shape == q.shape


# ------------------------------------------------ the one table of chip peaks


def test_there_is_one_peak_table_and_no_cpu_peak(monkeypatch):
    """MFU divides by `callbacks/time_estimator.py`'s table and by nothing
    else: no other module of the package spells a chip's peak FLOP/s, and a
    device the table does not know (this CPU) has none, never a default."""
    from llm_training_tpu.callbacks import time_estimator

    package = Path(time_estimator.__file__).resolve().parents[1]
    spelled = sorted(
        str(path.relative_to(package)) for path in package.rglob("*.py")
        if re.search(r"\b\d+(?:\.\d+)?e1[2-5]\b", path.read_text())
    )
    assert spelled == ["callbacks/time_estimator.py"]
    assert time_estimator.peak_flops_per_device() is None  # this CPU: unknown kind

    class Device:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(time_estimator.jax, "devices", lambda: [Device()])
    assert time_estimator.peak_flops_per_device() == 197e12
