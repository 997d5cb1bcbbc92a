"""Phi-4-mini-flash through its three caches (`models/phi4flash`,
`models/cache.py`): chunked prefill then paged decode against the plain
reference's full forward, with rows longer than the window and longer than
one chunk; the window group's ring gives its pages back; the cross layers
append nothing and read the ONE full layer's pages; the dense cache serves
the same tokens; a fault planted in each cache is caught; bfloat16 passes
the tolerance the fp8 control fails; the scopes both serving programs name.
The model against its reference without a cache is in
`tests/test_phi4flash.py`.

Tolerances, with their reasons:
- float32 against float32 (`highest` products on both sides): 2e-4 on
  logits of magnitude 1 to 4 and on logprobs: another order of summation
  (paged gathers and a scan from a carried state against [S, S]
  scores and one token at a time); read here 1e-5. A planted fault reads
  1e-2 and more.
- bfloat16 compute against the float32 reference: the served token's
  reference logit may lie at most `BF16_GAP` below the reference's best, and
  the fp8 control must lie further off.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
for path in (str(ROOT), str(Path(__file__).resolve().parent)):
    if path not in sys.path:
        sys.path.insert(0, path)

from test_phi4flash import REFERENCE_CFG, TINY, seeded_variables  # noqa: E402

from llm_training_tpu.infer import GenerateConfig, InferenceEngine  # noqa: E402
from llm_training_tpu.infer.engine import supports_decoding  # noqa: E402
from llm_training_tpu.models.phi4flash import Phi4Flash, Phi4FlashConfig, reference  # noqa: E402
from llm_training_tpu.serve import ServeConfig, ServingEngine  # noqa: E402
from llm_training_tpu.telemetry import get_registry  # noqa: E402

F32_TOL = 2e-4
BF16_GAP = 0.1  # read here: bfloat16 0.011, the fp8 control 0.48 (tied head, LayerNorm: small logits)

# rows of up to 56 tokens under a window of 16 and chunks of 8: longer than
# the window, longer than one chunk, chunks of unequal length
REQUESTS = [(19, 30), (5, 40), (11, 9), (30, 26), (3, 14)]  # (prompt, new tokens)
SERVE = dict(max_batch=2, max_model_len=64, block_size=8, prefill_chunk=8, num_blocks=9, eos_token_id=None)


@pytest.fixture(scope="module")
def tiny():
    model = Phi4Flash(Phi4FlashConfig(**TINY))
    return model, seeded_variables(model)


def serve_requests():
    rng = np.random.default_rng(5)
    return [
        {"id": f"r{i}", "prompt": rng.integers(0, 256, size=n).tolist(), "max_new_tokens": m}
        for i, (n, m) in enumerate(REQUESTS)
    ]


def served_against_reference(variables, requests, done, quant=None):
    """Over every served position of every request: (the widest gap by which
    the served token's reference logit lies below the reference's best, the
    widest difference between the served logprob and the reference's, the
    gap of the tokens the reference computed through `quant` puts first)."""
    from benchmarks.references import _common

    gaps, logprob_gaps, control = [], [], []
    for r in requests:
        served = done[r["id"]]["tokens"]
        tokens = r["prompt"] + served
        ids, seg = np.zeros((1, 64), np.int32), np.zeros((1, 64), np.int32)
        ids[0, : len(tokens)] = tokens
        seg[0, : len(tokens)] = 1
        args = (variables["params"], REFERENCE_CFG, jnp.asarray(ids), jnp.asarray(seg))
        at = np.arange(len(r["prompt"]) - 1, len(tokens) - 1)  # position p chooses token p + 1
        rows = np.asarray(reference.logits(*args))[0][at]
        gaps.append(float((rows.max(-1) - rows[np.arange(len(at)), served]).max()))
        logprobs = np.asarray(jax.nn.log_softmax(rows))[np.arange(len(at)), served]
        logprob_gaps.append(float(np.abs(logprobs - np.asarray(done[r["id"]]["logprobs"])).max()))
        if quant is not None:
            low = np.asarray(reference.logits(*args, None, _common.QUANTS[quant]))[0][at].argmax(-1)
            control.append(float((rows.max(-1) - rows[np.arange(len(at)), low]).max()))
    return max(gaps), max(logprob_gaps), max(control, default=None)


def run_engine(model, variables, **serve):
    engine = ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))
    requests = serve_requests()
    events = []
    # two at once, the others join mid-flight into recycled slots
    for r in requests[:2]:
        events += engine.submit(**r)
    for _ in range(6):
        events += engine.step()
    for r in requests[2:]:
        events += engine.submit(**r)
    while not engine.idle:
        events += engine.step()
    done = {e["id"]: e for e in events if e["type"] == "done"}
    return engine, requests, done


@pytest.fixture(scope="module")
def served(tiny):
    model, variables = tiny
    registry = get_registry()
    before = {n: registry.counter(f"serve/{n}").value for n in ("shared_kv_reads", "window_pages_released", "state_resets")}
    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables)
    counted = {n: registry.counter(f"serve/{n}").value - v for n, v in before.items()}
    return engine, requests, done, counted


def test_chunked_prefill_then_paged_decode_is_the_reference_forward(tiny, served):
    """Prompts of 19, 5, 11, 30 and 3 tokens in chunks of 8, five requests
    through two slots (a recycled slot holds its last tenant's state until
    the first chunk reads it as zeros), a pool of 9 blocks (so a request is
    evicted mid-decode and re-prefilled from a zero state): every served
    position against the reference's full forward."""
    _, variables = tiny
    engine, requests, done, counted = served
    assert supports_decoding(tiny[0])
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert engine.scheduler.evictions >= 1
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    stats = engine.stats()
    # three Mamba layers, two slots: [1, 16, 128] float32 a state, the tails [3, 128]
    assert stats["decode/state_bytes"] == 3 * 2 * (16 * 128 * 4 + 3 * 128 * 4)
    assert stats["decode/state_logical_bytes"] == stats["decode/state_bytes"]
    # a first chunk for every admission: five requests and each requeue
    assert counted["state_resets"] >= len(requests) + engine.scheduler.evictions


def test_the_window_ring_gives_its_pages_back(served):
    engine, _, _, counted = served
    # a window of 16 and a chunk of 8 in pages of 8: 4 pages a request at most, whatever its length
    assert engine.window_pages == 4 < engine.pages_per_request == 8
    assert counted["window_pages_released"] > 0
    assert engine.allocator.blocks_in_use == 0 and engine.window_allocator.blocks_in_use == 0
    # rows reached 49 and 56 tokens: 7 pages of the full group, never more than 4 of the window's
    assert engine.window_allocator.peak_in_use <= 2 * 4 < engine.allocator.peak_in_use + 1


def test_cross_layers_append_nothing_and_read_the_full_layers_pages(tiny, served, monkeypatch):
    """The declaration has ONE full layer read by two; the engine counts the
    reader's page reads; and of a chunk's calls into the cache exactly one
    appends to the full group, while the cross layer's call carries no keys."""
    model, variables = tiny
    engine, _, _, counted = served
    assert engine._shared_readers == 1 and counted["shared_kv_reads"] > 0
    assert engine._pool_k.shape[0] == 1 and engine._window_pool[0].shape[0] == 2
    from llm_training_tpu.models import cache as cache_module

    calls = []
    proper = cache_module.LayerCache.attend

    def watched(self, layer, q, k, v, segment_ids, **kwargs):
        out, cache = proper(self, layer, q, k, v, segment_ids, **kwargs)
        written = cache.k is not self.k, cache.window_k is not self.window_k
        calls.append((kwargs.get("window"), k is None, written))
        return out, cache

    monkeypatch.setattr(cache_module.LayerCache, "attend", watched)
    from llm_training_tpu.infer.cache import init_decode_state

    state = init_decode_state(model.config, batch_size=1, max_length=32)
    jax.eval_shape(lambda v, s: model.apply(v, jnp.zeros((1, 8), jnp.int32), decode_state=s).decode_state, variables, state)
    # the scanned period's window layer, the full layer, the cross layer (a scan's body is traced twice)
    assert list(dict.fromkeys(calls)) == [(16, False, (False, True)), (None, False, (True, False)), (None, True, (False, False))]


def test_generate_through_the_dense_cache_serves_the_same_tokens(tiny, served):
    model, variables = tiny
    _, requests, done, _ = served
    requests = requests[:3]
    with jax.default_matmul_precision("highest"):
        out = InferenceEngine(model, variables).generate(
            [r["prompt"] for r in requests], GenerateConfig(max_new_tokens=9)
        )
    for row, r in enumerate(requests):  # left-padded rows of 19, 5 and 11 tokens
        assert out["tokens"][row] == done[r["id"]]["tokens"][:9]
        assert np.allclose(out["logprobs"][row], done[r["id"]]["logprobs"][:9], atol=F32_TOL)


def _shared_pages_of_the_wrong_group(monkeypatch):
    from llm_training_tpu.models import cache as cache_module

    proper = cache_module.LayerCache.attend

    def misread(self, layer, q, k, v, segment_ids, **kwargs):
        if k is None:  # `window` sends the reader to the window group's first layer
            kwargs["window"] = 16
        return proper(self, layer, q, k, v, segment_ids, **kwargs)

    monkeypatch.setattr(cache_module.LayerCache, "attend", misread)


def _state_not_reset(monkeypatch):
    from llm_training_tpu.models import cache as cache_module

    monkeypatch.setattr(cache_module, "_slot_rows", lambda slab, slots, fresh: slab if slots is None else slab[slots])
    monkeypatch.setattr(cache_module.LayerCache.recurrent_rows, "__defaults__", (cache_module._slot_rows, False))


def _window_off_by_one(monkeypatch):
    from llm_training_tpu.models import cache as cache_module

    proper = cache_module.LayerCache.attend

    def wider(self, layer, q, k, v, segment_ids, *, window=None, **kwargs):
        return proper(self, layer, q, k, v, segment_ids, window=window and window + 1, **kwargs)

    monkeypatch.setattr(cache_module.LayerCache, "attend", wider)


@pytest.mark.parametrize("plant", [_shared_pages_of_the_wrong_group, _state_not_reset, _window_off_by_one])
def test_a_planted_fault_in_a_cache_is_caught(tiny, monkeypatch, plant):
    """The cross layer reading the window group's pages; a recycled slot's
    state and tail read as its last tenant left them; a window of 17 for 16
    through the ring."""
    plant(monkeypatch)
    model, variables = tiny
    with jax.default_matmul_precision("highest"):
        _, requests, done = run_engine(Phi4Flash(model.config), variables)
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert max(gap, logprob_gap) > 50 * F32_TOL


def test_bfloat16_serving_passes_and_the_fp8_control_does_not():
    model = Phi4Flash(Phi4FlashConfig(**{**TINY, "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}))
    variables = seeded_variables(model, scale=0.1)
    _, requests, done = run_engine(model, variables)
    gap, _, control = served_against_reference(variables, requests, done, quant="fp8")
    assert gap <= BF16_GAP < control, (gap, control)


def test_both_serving_programs_name_the_stacks_scopes(tiny):
    """`ssm_conv`, `ssm_scan` (a chunk) / `ssm_step` (a token), `gmu`,
    `diff_attn` with the window's, the full layer's and the readers' calls
    told apart, in the lowered text of both programs (op names only:
    `tests/test_serve_spans.py:_op_names`)."""
    import re

    model, variables = tiny
    engine = ServingEngine(model, variables, ServeConfig(**SERVE))
    slab = {"slab": engine._slab, "window_pool": engine._window_pool}
    for jitted, packed, mine, other in (
        (engine._decode_jit, engine._decode_packed, "ssm_step", "ssm_scan"),
        (engine._prefill_jit, engine._prefill_packed, "ssm_scan", "ssm_step"),
    ):
        text = jitted.lower(
            variables, packed, engine._pool_k, engine._pool_v, engine._rng, engine._last_tokens, **slab
        ).as_text(debug_info=True)
        names = set(re.findall(r'loc\("([^"]+)"', text))
        under = lambda *parts: any(all(p in n for p in parts) for n in names)
        assert under("/mamba/", "ssm_conv") and under("mamba/" + mine) and not under(other)
        assert under("/gmu/") and under("/self_attn/", "diff_attn/attn_window")
        assert under("between", "diff_attn/attn_global") and not under("between", "attn_cross")
        assert under("cross_decoder", "diff_attn/attn_global/attn_cross")
        assert under("/self_attn/", "diff_attn", "rms_norm") and under("/mlp/")
    engine.close()
