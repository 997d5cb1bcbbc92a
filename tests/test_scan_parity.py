"""Loop-vs-scan parity for the dense-prefix + scanned-MoE-suffix families.

VERDICT r3 #3: deepseek / glm4_moe / ernie45_moe now scan their uniform MoE
suffix (compile time ~flat in depth). The same HF weights loaded through
both layouts must produce identical logits, and the scan->HF export must
byte-match the loop->HF export (same state dict, different flax trees).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _deepseek():
    from tests.test_deepseek import _hf_tiny

    hf_model, hf_config = _hf_tiny(
        "DeepseekV3", n_group=4, topk_group=2, num_hidden_layers=3
    )
    return hf_model, hf_config, "deepseek"


def _glm4_moe():
    from tests.test_glm4_moe import _hf_tiny

    return (*_hf_tiny(num_hidden_layers=3), "glm4_moe")


def _ernie45_moe():
    from tests.test_ernie45_moe import _hf_tiny

    return (*_hf_tiny(num_hidden_layers=3), "ernie45_moe")


def _gpt_oss():
    from tests.test_gpt_oss import _hf_tiny

    # 4 layers = 2 cycles of the (sliding, full) pair — the scan needs a
    # proper repetition (detect_period returns 0 at 2 layers)
    return (*_hf_tiny(num_hidden_layers=4), "gpt_oss")


def _qwen3_next():
    from tests.test_qwen3_next import _hf_tiny

    # 8 layers = 2 cycles of the 3×linear+full period
    return (*_hf_tiny(num_hidden_layers=8), "qwen3_next")


def _minimax():
    from tests.test_minimax import _hf_tiny

    return (*_hf_tiny(), "minimax")  # 4 layers alternating = period 2


def _bamba():
    from tests.test_bamba import _hf_tiny

    # (mamba, attention) × 2 — slope-free periodic hybrid
    return (*_hf_tiny(num_hidden_layers=4, attn_layer_indices=[1, 3]), "bamba")


@pytest.mark.parametrize(
    "build",
    [_deepseek, _glm4_moe, _ernie45_moe, _gpt_oss, _qwen3_next, _minimax, _bamba],
)
def test_loop_vs_scan_parity(build):
    torch = pytest.importorskip("torch")
    hf_model, hf_config, family = build()
    mod = importlib.import_module(f"llm_training_tpu.models.{family}")
    conv = importlib.import_module(
        f"llm_training_tpu.models.{family}.hf_conversion"
    )
    model_cls = next(
        getattr(mod, n) for n in dir(mod)
        if n.lower().replace("_", "") == family.replace("_", "")
    )

    sd = hf_model.state_dict()
    outs, cfgs, trees = [], [], []
    for scan in (True, False):
        overrides = {"compute_dtype": "float32", "scan_layers": scan}
        if "moe_impl" in type(conv.config_from_hf(hf_config)).model_fields:
            overrides["moe_impl"] = "dense"
        cfg = conv.config_from_hf(hf_config, **overrides)
        active = getattr(cfg, "num_scanned_layers", 0) or getattr(cfg, "scan_period", 0)
        assert bool(active) == scan
        params = conv.params_from_hf(sd, cfg)
        ids = np.random.default_rng(7).integers(0, cfg.vocab_size, (2, 16))
        # jitted: one compile instead of an eager op-by-op forward
        logits = jax.jit(model_cls(cfg).apply)(params, jnp.asarray(ids)).logits
        outs.append(np.asarray(logits))
        cfgs.append(cfg)
        trees.append(params)

    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-5)

    # exports from both layouts must agree key-for-key, value-for-value
    hf_scan = conv.params_to_hf(trees[0], cfgs[0])
    hf_loop = conv.params_to_hf(trees[1], cfgs[1])
    assert set(hf_scan) == set(hf_loop)
    for key in hf_scan:
        np.testing.assert_array_equal(hf_scan[key], hf_loop[key], err_msg=key)


@pytest.mark.slow
def test_scan_compile_time_flat_in_depth():
    """The point of the scanned suffix: tracing+lowering a deepseek-v3-shaped
    stack must not grow linearly with depth (61 layers would otherwise
    compile 58 copies of the MoE body)."""
    import time

    import jax

    from llm_training_tpu.models.deepseek import Deepseek, DeepseekConfig
    from tests.test_deepseek import TINY

    def lower_seconds(n_layers):
        cfg = DeepseekConfig(**{**TINY, "num_hidden_layers": n_layers},
                             n_group=4, topk_group=2)
        model = Deepseek(cfg)
        ids = jnp.zeros((1, 16), jnp.int32)
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)
        t0 = time.perf_counter()
        jax.jit(model.apply).lower(params, ids)
        return time.perf_counter() - t0

    lower_seconds(3)  # warm import/caches
    t_short, t_deep = lower_seconds(4), lower_seconds(22)
    # 18 extra scanned layers must not add ~6x trace work; allow generous
    # slack for wall-clock noise
    assert t_deep < 3 * t_short, (t_short, t_deep)
