"""chip_smoke.py's contract off the chip, and where the compile cache goes.

The smoke itself needs a TPU (the driver and builders run it through the
chip tool); what can be held here is that it refuses to run without one,
that its parent stays off jax (graftlint's jax-free contract covers the
import graph), and the pure pieces of its checks.
"""

import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402  (jax-free at import: a lint contract)


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """In the CPU sandbox the first phase fails at the device check: exit
    code non-zero, the phase named, and no result line."""
    env = {k: v for k, v in __import__("os").environ.items()}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode != 0
    assert "phase kernels FAILED" in proc.stdout
    assert "jax found platform 'cpu'" in proc.stdout
    assert '"ok": true' not in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


@pytest.mark.parametrize("serve,dense,expected", [
    # identical streams: every position compared
    (([5, 6, 7], [-1.0, -2.0, -3.0]), ([5, 6, 7], [-1.01, -2.0, -2.98]), (3, 0.02, True)),
    # an argmax tie at position 1: compared up to and including it, not past
    (([5, 6, 7], [-1.0, -2.0, -3.0]), ([5, 9, 1], [-1.0, -2.03, -9.0]), (2, 0.03, False)),
])
def test_compare_logprobs_stops_at_the_first_differing_token(serve, dense, expected):
    compared, worst, identical = chip_smoke.compare_logprobs(*serve, *dense)
    assert (compared, round(worst, 6), identical) == expected


def test_serve_requests_are_seeded_and_sized():
    first, second = chip_smoke.build_requests(), chip_smoke.build_requests()
    assert first == second
    assert [len(r["prompt"]) for r in first] == list(chip_smoke.SERVE_PROMPT_LENS)
    assert len(first) >= 8 and all(r["max_new_tokens"] >= 32 for r in first)
    assert all(0 <= t < 32000 for r in first for t in r["prompt"])


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    keyed_on_metadata = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", keyed_on_metadata)


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "in-checkout"])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path, restore_cache_dir, from_env):
    """`_apply_extra_config` — the one place every device-touching command
    passes — keeps the cache where JAX_COMPILATION_CACHE_DIR says and sets
    no other in code; unset, it resolves to the one fixed in-checkout path.
    Either way entries are keyed on the programs' metadata too, so a cached
    program never carries an older checkout's scopes into a device profile.
    Pure config assertions: nothing compiles."""
    from llm_training_tpu.cli.main import _apply_extra_config
    from llm_training_tpu.compile_cache import DEFAULT_CACHE_DIR, compile_cache_dir

    jax.config.update("jax_compilation_cache_dir", None)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        _apply_extra_config({})
        # nothing was set in code: jax reads the variable itself at import
        assert jax.config.jax_compilation_cache_dir is None
        assert compile_cache_dir() == str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        _apply_extra_config({})
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
        assert DEFAULT_CACHE_DIR == REPO / ".jax_cache"
        assert compile_cache_dir() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_include_metadata_in_key is True


def test_compilation_cache_dir_config_key_is_refused():
    from llm_training_tpu.cli.main import _apply_extra_config

    with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
        _apply_extra_config({"compilation_cache_dir": "/somewhere"})
