"""Segment-boundary state reset for the recurrent families (VERDICT r3 #6).

With `segment_state_reset=True`, a document packed after another must see
EXACTLY the hidden states it would see alone: the DeltaNet fast-weight /
Mamba-2 SSD state resets at the boundary (attention already segment-masks).
Default (False) keeps HF parity, where state leaks across packed documents.

The boundary is placed INSIDE a recurrence chunk, so the in-chunk masking
paths (triangular corrections, decay matrices) are exercised, not just the
cross-chunk carry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _models(family, reset):
    if family == "qwen3_next":
        from llm_training_tpu.models.qwen3_next import Qwen3Next, Qwen3NextConfig
        from tests.test_qwen3_next import TINY

        cfg = Qwen3NextConfig(
            **TINY, moe_impl="dense", delta_chunk_size=16,
            segment_state_reset=reset,
        )
        return Qwen3Next(cfg), cfg
    from llm_training_tpu.models.bamba import Bamba, BambaConfig
    from tests.test_bamba import TINY

    cfg = BambaConfig(**TINY, segment_state_reset=reset)
    return Bamba(cfg), cfg


def _run(model, params, ids, seg, pos):
    # jitted: one compile per shape instead of an eager op-by-op forward
    # (the eager scan stacks cost tens of seconds on CPU)
    logits = jax.jit(
        lambda params, ids, seg, pos: model.apply(
            params, ids, segment_ids=seg, position_ids=pos
        ).logits
    )(params, jnp.asarray(ids), jnp.asarray(seg), jnp.asarray(pos))
    return np.asarray(logits, np.float32)


@pytest.mark.parametrize("family", ["qwen3_next", "bamba"])
def test_packed_matches_separate_docs(family):
    # 27 + 37 tokens: the boundary falls mid-chunk (chunk 16/8), and doc 2
    # spans multiple chunks
    l1, l2 = 27, 37
    rng = np.random.default_rng(0)
    doc1 = rng.integers(1, 128, (1, l1))
    doc2 = rng.integers(1, 128, (1, l2))
    packed_ids = np.concatenate([doc1, doc2], axis=1)
    packed_seg = np.concatenate(
        [np.ones((1, l1), np.int32), np.full((1, l2), 2, np.int32)], axis=1
    )
    packed_pos = np.concatenate(
        [np.arange(l1)[None], np.arange(l2)[None]], axis=1
    )

    model, cfg = _models(family, reset=True)
    params = jax.jit(model.init)(jax.random.key(0), jnp.asarray(packed_ids))

    packed = _run(model, params, packed_ids, packed_seg, packed_pos)
    solo = _run(
        model, params, doc2, np.ones((1, l2), np.int32), np.arange(l2)[None]
    )
    np.testing.assert_allclose(
        packed[:, l1:], solo, rtol=2e-5, atol=2e-5,
        err_msg="doc 2 logits differ between packed and standalone runs",
    )

    # and doc 1 must be unaffected by what follows it (causality sanity)
    solo1 = _run(
        model, params, doc1, np.ones((1, l1), np.int32), np.arange(l1)[None]
    )
    np.testing.assert_allclose(packed[:, :l1], solo1, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("family", ["qwen3_next", "bamba"])
def test_default_keeps_hf_leak_parity(family):
    """Without the flag, the recurrent state leaks across documents (HF
    parity) — the packed doc-2 logits must NOT match the standalone run."""
    l1, l2 = 27, 37
    rng = np.random.default_rng(1)
    doc1 = rng.integers(1, 128, (1, l1))
    doc2 = rng.integers(1, 128, (1, l2))
    packed_ids = np.concatenate([doc1, doc2], axis=1)
    packed_seg = np.concatenate(
        [np.ones((1, l1), np.int32), np.full((1, l2), 2, np.int32)], axis=1
    )
    packed_pos = np.concatenate(
        [np.arange(l1)[None], np.arange(l2)[None]], axis=1
    )

    model, cfg = _models(family, reset=False)
    params = jax.jit(model.init)(jax.random.key(0), jnp.asarray(packed_ids))
    packed = _run(model, params, packed_ids, packed_seg, packed_pos)
    solo = _run(
        model, params, doc2, np.ones((1, l2), np.int32), np.arange(l2)[None]
    )
    assert np.max(np.abs(packed[:, l1:] - solo)) > 1e-4
