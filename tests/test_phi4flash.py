"""Phi-4-mini-flash (`models/phi4flash`) and Mamba-1's selective scan
(`ops/selective_scan.py`): the scan's two forms against the equation, the
module against its plain reference (logits, loss and gradients), the faults
a reader of the paper could plant, and the declaration of its three caches.
Serving is in `tests/test_phi4flash_serve.py`. Float32 on the CPU, every
forward jitted. Small widths: 4 query and 2 key/value heads of 16 (two query
pairs on ONE key/value pair), a window of 16 under rows of 72, an inner
width of 128 (one run of 128 lanes).

Tolerances, with their reasons:
- float32 against float32 (`highest` products on both sides): 2e-4 on logits
  of magnitude 1 to 4. The two sides sum in different orders (the state as it
  is stored against `[C, N]`, a paired 2d-wide softmax
  attention against two d-wide ones); read here 1e-6 to 2e-5. A planted fault
  reads 1e-2 and more.
- the scan against a float64 numpy oracle: 2e-5 of the output's scale.
- gradients: 1e-3 of each leaf's largest entry (float32 through 8 layers and
  a token-by-token `lax.scan` on the reference's side).
"""

import sys
import zlib
from pathlib import Path

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig, reference
from llm_training_tpu.ops.selective_scan import selective_scan, selective_step

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

F32_TOL = 2e-4

TINY = dict(
    vocab_size=256, hidden_size=64, intermediate_size=96, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, sliding_window=16,
    param_dtype="float32", compute_dtype="float32", attention_impl="xla",
)
# the same model as the reference's mapping (the published keys)
REFERENCE_CFG = {
    **{k: v for k, v in TINY.items() if k.startswith(("num_", "hidden_", "sliding_"))},
    "mb_per_layer": 2, "layer_norm_eps": 1e-5,
}


def seeded_variables(model, scale=0.2, seed=1):
    """Random weights that exercise every term: biases, `D`, the conv bias
    and the four lambda vectors drawn like the rest, decays spread from slow
    to fast."""
    variables = nn.meta.unbox(
        jax.jit(lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32)))(jax.random.key(0))
    )

    def draw(path, leaf):
        name = path[-1].key
        # (crc32, not hash(): a str's hash differs from one process to the next)
        key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(jax.tree_util.keystr(path).encode()))
        if name in ("A_log", "dt_bias"):
            return (jax.random.normal(key, leaf.shape) * 0.7).astype(leaf.dtype)
        if name == "weight":
            return leaf
        return (jax.random.normal(key, leaf.shape) * scale).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(draw, variables)


@pytest.fixture(scope="module")
def tiny():
    model = Phi4Flash(Phi4FlashConfig(**TINY))
    return model, seeded_variables(model)


def packed_batch(seed=0):
    """Two rows of 72: a document of 30 (longer than the window), one of 35
    and 7 padded positions."""
    ids = np.random.default_rng(seed).integers(0, 256, size=(2, 72)).astype(np.int32)
    seg = np.tile(np.concatenate([np.full(30, 1), np.full(35, 2), np.zeros(7)]).astype(np.int32), (2, 1))
    return jnp.asarray(ids), jnp.asarray(seg)


@pytest.fixture(scope="module")
def reference_logits(tiny):
    _, variables = tiny
    ids, seg = packed_batch()
    return np.asarray(reference.logits(variables["params"], REFERENCE_CFG, ids, seg))


def module_logits(model, variables, ids, seg):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda v: model.apply(v, input_ids=ids, segment_ids=seg).logits)(variables))


# ------------------------------------------------------------------- the scan


def oracle(x, delta, a, b, c, state, starts=None):
    """s_t = exp(delta_t A) * s_{t-1} + (delta_t x_t) B_t^T, y_t = s_t C_t, a
    channel's state a vector of N, in float64. state [B, C, N]."""
    x, delta, a, b, c, state = (np.asarray(t, np.float64) for t in (x, delta, a, b, c, state))
    batch, seq, _ = x.shape
    y, state = np.zeros(x.shape), state.copy()
    for row in range(batch):
        s = state[row]
        for t in range(seq):
            if starts is not None and starts[row, t]:
                s = np.zeros_like(s)
            s = np.exp(delta[row, t][:, None] * a) * s + (delta[row, t] * x[row, t])[:, None] * b[row, t][None, :]
            y[row, t] = s @ c[row, t]
        state[row] = s
    return y, state


def scan_inputs(seq=150, carried=False, seed=0):
    rng = np.random.default_rng(seed)
    batch, runs, lanes, n = 2, 2, 128, 16  # 256 channels in two runs of 128 lanes
    channels = runs * lanes
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    x = f32(rng.normal(size=(batch, seq, channels)))
    delta = f32(np.log1p(np.exp(rng.normal(size=(batch, seq, channels)))))
    a = f32(-np.exp(rng.normal(size=(channels, n))))
    b, c = (f32(rng.normal(size=(batch, seq, n))) for _ in range(2))
    state = f32(rng.normal(size=(batch, runs, n, lanes)) if carried else np.zeros((batch, runs, n, lanes)))
    return x, delta, a, b, c, state


def as_oracle(state):
    """The stored state `[B, H, N, V]` as the oracle holds it, `[B, C, N]`."""
    batch, runs, n, lanes = state.shape
    return np.asarray(state).transpose(0, 1, 3, 2).reshape(batch, runs * lanes, n)


@pytest.mark.parametrize("case", ["from_zero", "incoming_state", "starts", "starts_and_state", "padded_tail"])
def test_scan_is_the_token_by_token_recurrence(case):
    """150 tokens eight a trip (a ragged last trip), with an incoming state,
    with packed documents starting inside a trip and at a trip's first
    position, and with positions that change nothing."""
    x, delta, a, b, c, state = scan_inputs(carried="state" in case)
    starts = None
    if "starts" in case:
        starts = np.zeros((2, 150), bool)
        starts[0, [0, 40, 41, 96]] = True  # 40, 96: a trip's first position
        starts[1, [17, 149]] = True
    if case == "padded_tail":
        delta = delta.at[:, 120:].set(0.0)
    got_y, got_state = jax.jit(lambda *t: selective_scan(*t, starts=None if starts is None else jnp.asarray(starts)))(x, delta, a, b, c, state)
    want_y, want_state = oracle(x, delta, a, b, c, as_oracle(state), starts)
    scale = np.abs(want_y).max()
    assert np.abs(np.asarray(got_y) - want_y).max() < 2e-5 * scale
    assert np.abs(as_oracle(got_state) - want_state).max() < 2e-5 * max(np.abs(want_state).max(), 1.0)
    if case == "padded_tail":  # delta 0: the state after 150 is the state after 120
        _, at_120 = oracle(x[:, :120], delta[:, :120], a, b[:, :120], c[:, :120], as_oracle(state))
        assert np.abs(as_oracle(got_state) - at_120).max() < 2e-5 * np.abs(at_120).max()


def test_one_token_step_is_the_recurrence_on_the_stored_state():
    x, delta, a, b, c, state = scan_inputs(seq=3, carried=True)
    step = jax.jit(selective_step)
    ys = []
    for t in range(3):
        state, y = step(state, x[:, t], delta[:, t], a, b[:, t], c[:, t])
        ys.append(np.asarray(y))
    _, _, _, _, _, first = scan_inputs(seq=3, carried=True)
    want_y, want_state = oracle(x, delta, a, b, c, as_oracle(first))
    assert np.abs(np.stack(ys, axis=1) - want_y).max() < 2e-5 * np.abs(want_y).max()
    assert np.abs(as_oracle(state) - want_state).max() < 2e-5 * np.abs(want_state).max()
    # an idle slot (delta 0) is left exactly as it was
    same, _ = step(state, x[:, 0], jnp.zeros_like(delta[:, 0]), a, b[:, 0], c[:, 0])
    assert np.array_equal(np.asarray(same), np.asarray(state))


# ---------------------------------------------------------- module == reference


def test_module_logits_are_the_references(tiny, reference_logits):
    model, variables = tiny
    ids, seg = packed_batch()
    got = module_logits(model, variables, ids, seg)
    real = np.asarray(seg) > 0
    assert np.abs(got - reference_logits)[real].max() < F32_TOL
    assert np.abs(reference_logits)[real].max() > 1.0  # logits of a size that a fault would move


def test_loss_and_gradients_are_the_references(tiny):
    model, variables = tiny
    ids, seg = packed_batch()
    labels = jnp.roll(ids, -1, axis=1)
    valid = (seg > 0) & (seg == jnp.roll(seg, -1, axis=1))

    def cross_entropy(logits):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)

    with jax.default_matmul_precision("highest"):
        got_loss, got = jax.jit(jax.value_and_grad(
            lambda v: cross_entropy(model.apply(v, input_ids=ids, segment_ids=seg).logits)
        ))(variables)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: cross_entropy(reference.logits(p, REFERENCE_CFG, ids, seg))
        ))(variables["params"])
    assert abs(float(got_loss) - float(want_loss)) < 1e-5
    flat_got = dict(jax.tree_util.tree_leaves_with_path(got["params"]))
    for path, leaf in jax.tree_util.tree_leaves_with_path(want):
        scale = float(jnp.abs(leaf).max())
        assert scale > 0, path  # every parameter reaches the loss
        # (a key bias moves no softmax: its gradient is rounding on both sides, 1e-9)
        assert float(jnp.abs(flat_got[path] - leaf).max()) < 1e-3 * scale + 1e-7, path


def test_remat_changes_nothing(tiny, reference_logits):
    _, variables = tiny
    model = Phi4Flash(Phi4FlashConfig(**TINY, enable_gradient_checkpointing=True))
    ids, seg = packed_batch()
    got = module_logits(model, variables, ids, seg)
    assert np.abs(got - reference_logits)[np.asarray(seg) > 0].max() < F32_TOL


# -------------------------------------------------------------- planted faults


def _lambda_dropped(program, monkeypatch):
    monkeypatch.setattr(program, "lambda_init", lambda depth: jnp.float32(0.0) * depth)


def _memory_after_the_gate(program, monkeypatch):
    proper = program.Mamba.__call__

    def gated(self, hidden, segment_ids=None, rows=None):
        out, y, rows = proper(self, hidden, segment_ids, rows)
        return out, y * 0.5, rows  # what a gate would do to it: another memory

    monkeypatch.setattr(program.Mamba, "__call__", gated)


def _window_off_by_one(program, monkeypatch):
    proper = program.dot_product_attention

    def wider(*args, sliding_window=None, **kwargs):
        return proper(*args, sliding_window=None if sliding_window is None else sliding_window + 1, **kwargs)

    monkeypatch.setattr(program, "dot_product_attention", wider)


@pytest.mark.parametrize("plant", [_lambda_dropped, _memory_after_the_gate, _window_off_by_one])
def test_a_planted_fault_is_caught(tiny, reference_logits, monkeypatch, plant):
    """`lambda_init` taken as 0 (the subtraction's weight and the output's
    factor with it), the memory scaled as a gate would scale it, a window of
    17 for 16: each moves the logits a hundred tolerances."""
    from llm_training_tpu.models.phi4flash import model as program

    plant(program, monkeypatch)
    _, variables = tiny
    model = Phi4Flash(Phi4FlashConfig(**TINY))  # traced anew, with the fault in
    ids, seg = packed_batch()
    got = module_logits(model, variables, ids, seg)
    assert np.abs(got - reference_logits)[np.asarray(seg) > 0].max() > 50 * F32_TOL


# ------------------------------------------------------------------ the caches


def test_one_declaration_gives_two_page_groups_and_a_slab():
    from llm_training_tpu.infer.cache import cache_specs, init_decode_state, kv_groups, slab_logical_bytes
    from llm_training_tpu.serve.paged_cache import init_paged_pool, init_state_slab, init_window_pool

    cfg = Phi4FlashConfig(**TINY)
    assert cfg.layer_kinds == ["mamba", "window", "mamba", "window", "memory", "full", "gmu", "cross"]
    (full, window), recurrent = cache_specs(cfg)
    assert kv_groups(cfg) == (full, window)
    # ONE layer's pages, read by that layer and the cross layer; a pair of heads a row
    assert (full.layers, full.kv_heads, full.head_dim, full.window, full.readers) == (1, 1, 32, None, 2)
    assert (window.layers, window.kv_heads, window.head_dim, window.window) == (2, 1, 32, 16)
    assert (recurrent.layers, recurrent.stored, recurrent.conv_taps, recurrent.conv_channels) == (3, (1, 16, 128), 3, 128)
    k, v = init_paged_pool(cfg, num_blocks=5, block_size=8)
    wk, wv = init_window_pool(cfg, num_blocks=4, block_size=8)
    state, tail = init_state_slab(cfg, slots=3)
    assert k.shape == v.shape == (1, 5, 1, 8, 32) and wk.shape == wv.shape == (2, 4, 1, 8, 32)
    assert state.shape == (3, 3, 1, 16, 128) and state.dtype == jnp.float32 and tail.shape == (3, 3, 3, 128)
    assert slab_logical_bytes(recurrent, 3, tail.dtype) == state.size * 4 + tail.size * 4
    dense = init_decode_state(cfg, batch_size=3, max_length=32)
    assert dense.k.shape == (1, 3, 32, 1, 32) and dense.window_k.shape == (2, 3, 32, 1, 32)
    assert dense.state.shape == state.shape
    # the published widths: 20 heads of 64 as 10 pairs of 128, [5120, 16] as [40, 16, 128]
    (full, window), recurrent = Phi4FlashConfig().cache_specs()
    assert (full.layers, full.kv_heads, full.head_dim, full.readers) == (1, 10, 128, 8)
    assert (window.layers, window.kv_heads, window.head_dim, window.window) == (8, 10, 128, 512)
    assert (recurrent.layers, recurrent.stored, recurrent.abreast) == (9, (40, 16, 128), 1)
    assert (recurrent.conv_taps, recurrent.conv_channels) == (3, 5120)
    assert Phi4FlashConfig().resolved_dt_rank == 160 and Phi4FlashConfig().mamba_inner == 5120


def test_the_published_shapes_count_the_published_parameters():
    model = Phi4Flash(Phi4FlashConfig(param_dtype="bfloat16"))
    abstract = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    leaves = jax.tree.leaves(nn.meta.unbox(abstract))
    assert sum(leaf.size for leaf in leaves) == 3_852_562_944  # the published 3.8 B
    assert sum(leaf.size * leaf.dtype.itemsize for leaf in leaves) == 7_706_885_120


def test_config_refuses_what_is_not_implemented():
    for bad in (dict(mb_per_layer=3), dict(num_hidden_layers=6), dict(num_attention_heads=3),
                dict(mlp_bias=True), dict(tie_word_embeddings=False)):
        with pytest.raises(ValueError):
            Phi4FlashConfig(**{**TINY, **bad})


def test_cli_model_provider_takes_the_family():
    import json

    from llm_training_tpu.lms.base import ModelProvider
    from llm_training_tpu.models.hf_io import conversion_module, model_class_for_hf
    from llm_training_tpu.models.phi4flash.hf_conversion import config_from_hf, config_to_hf

    provider = ModelProvider(model_class="llm_training_tpu.models.Phi4Flash", model_kwargs=TINY)
    assert isinstance(provider.get_model(), Phi4Flash)
    assert model_class_for_hf({"model_type": "phi4flash"}).endswith("Phi4Flash")
    published = json.loads((ROOT / "benchmarks/configs/phi4-mini-flash-reasoning.json").read_text())
    cfg = config_from_hf(published)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.hidden_size, cfg.sliding_window) == (32, 200064, 2560, 512)
    kinds = cfg.layer_kinds
    assert [kinds.count(k) for k in ("mamba", "window", "memory", "full", "gmu", "cross")] == [8, 8, 1, 1, 7, 7]
    assert (kinds[16], kinds[17], kinds[18], kinds[19]) == ("memory", "full", "gmu", "cross")
    back = config_to_hf(cfg)
    assert all(back[k] == published[k] for k in ("model_type", "mb_per_layer", "layer_norm_eps", "sliding_window"))
    with pytest.raises(NotImplementedError, match="no HuggingFace weight map"):
        conversion_module(cfg).params_from_hf({}, cfg)
