"""Block-quantized offloaded optimizer state (offload_state_dtype).

The r5 chip measurement showed the fp32 offload round trip is host-link
bandwidth-bound (overlap buys nothing: 0.3035 vs 0.313 MFU), so the int8
codec exists to shrink the bytes 4x. These tests pin the codec's numerics
(including the safety property that quantized nu never underestimates),
the field-name -> codec routing, the trained-step behaviour vs exact fp32
state, and the checkpoint round trip of the compressed layout. Memory-kind
placement itself needs the chip; everything here runs with device kinds
(same discipline as test_blocked_offload_update_matches_whole_tree).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from llm_training_tpu.optim.quantized_state import (
    QuantArray,
    decode_state,
    dequantize_array,
    encode_state,
    quantize_array,
)


def test_sym_codec_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 1024)) * rng.uniform(1e-4, 10), jnp.float32)
    qa = quantize_array(x, "sym", 256)
    assert qa.q.dtype == jnp.int8 and qa.q.shape == x.shape
    assert qa.scale.shape == (4, 4)
    err = np.abs(np.asarray(dequantize_array(qa) - x))
    # error bound: half a quantization step per block
    bound = np.repeat(np.asarray(qa.scale), 256, axis=-1) * 0.5 + 1e-12
    assert (err <= bound).all()


def test_sqrt_codec_never_underestimates():
    """Ceil rounding: dequantized nu >= true nu (minus the 5e-4-step
    idempotency slack — negligible) — a real underestimate would blow up
    Adam's per-coordinate step by sqrt(nu)/eps."""
    rng = np.random.default_rng(1)
    # high dynamic range within a block: the dangerous case
    x = jnp.asarray(
        10.0 ** rng.uniform(-12, 0, (8, 512)), jnp.float32
    )
    qa = quantize_array(x, "sqrt", 256)
    assert qa.q.dtype == jnp.uint8
    deq = np.asarray(dequantize_array(qa))
    # bound: sqrt may be under by <= 5e-4 grid steps -> nu under by
    # <= ~2*sqrt(nu)*5e-4*scale; assert in sqrt space where it is linear
    r, dr = np.sqrt(np.asarray(x)), np.sqrt(deq)
    step = np.repeat(np.asarray(qa.scale), 256, axis=-1)
    assert (dr >= r - 1e-3 * step).all()
    # and NEVER to zero for nonzero input — dequantized nu = 0 would blow
    # up the Adam step by sqrt(nu_true)/eps
    assert (deq[np.asarray(x) > 0] > 0).all()
    # and it is still a useful approximation for values near the block max
    big = np.asarray(x) > np.asarray(x).max(-1, keepdims=True) * 0.1
    rel = np.abs(deq - np.asarray(x)) / np.asarray(x)
    assert rel[big].max() < 0.05


def test_codecs_are_grid_idempotent():
    """decode -> re-encode must be a FIXED POINT for both codecs: the
    serialized offload path re-encodes the (unchanged) state every
    accumulation micro-step, so any per-cycle drift would ratchet nu
    upward across training."""
    rng = np.random.default_rng(3)
    for kind, data in (
        ("sym", rng.standard_normal((4, 1024)) * 3.0),
        ("sqrt", 10.0 ** rng.uniform(-10, 2, (4, 1024))),
    ):
        x = jnp.asarray(data, jnp.float32)
        qa = quantize_array(x, kind, 256)
        for cycle in range(10):
            qa2 = quantize_array(dequantize_array(qa), kind, 256)
            np.testing.assert_array_equal(
                np.asarray(qa2.q), np.asarray(qa.q),
                err_msg=f"{kind} codes drifted at cycle {cycle}",
            )
            np.testing.assert_array_equal(
                np.asarray(qa2.scale), np.asarray(qa.scale),
                err_msg=f"{kind} scales drifted at cycle {cycle}",
            )
            qa = qa2


def test_encode_state_routes_fields_and_skips_ineligible():
    params = {
        "w": jnp.zeros((4, 512)),
        "v": jnp.zeros((2, 512)),  # param NAMED v — must not get sqrt codec
        "tiny": jnp.zeros((7,)),  # last axis % block != 0 — stays fp32
    }
    tx = optax.adamw(1e-3)
    state = tx.init(params)
    # make mu signed and nu non-negative, as in real training
    state = jax.tree.map(lambda x: x, state)
    enc = encode_state(state, block=256)
    leaves = jax.tree_util.tree_flatten_with_path(
        enc, is_leaf=lambda x: isinstance(x, QuantArray)
    )[0]
    kinds = {}
    for path, leaf in leaves:
        names = [
            str(
                getattr(p, "name", None)
                or getattr(p, "key", None)
                or getattr(p, "idx", None)
            )
            for p in path
        ]
        if isinstance(leaf, QuantArray):
            kinds["/".join(names)] = leaf.kind
    assert kinds["0/mu/w"] == "sym"
    assert kinds["0/mu/v"] == "sym"  # param name must not flip the codec
    assert kinds["0/nu/w"] == "sqrt"
    assert kinds["0/nu/v"] == "sqrt"
    assert not any(k.endswith("/tiny") for k in kinds)  # ineligible skipped
    # decode restores the exact original structure and dtypes
    dec = decode_state(enc)
    assert jax.tree.structure(dec) == jax.tree.structure(state)
    assert all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(dec)
               if hasattr(leaf, "ndim") and leaf.ndim >= 1)


def test_adam_with_quantized_state_tracks_exact(devices):
    """Run Adam 20 steps on a quadratic with the state quantized between
    every step (the offload storage pattern); trajectory must track the
    exact-state run closely and reach a comparably low loss."""
    tx = optax.adam(5e-2)
    target = jnp.asarray(np.random.default_rng(2).standard_normal((4, 512)), jnp.float32)

    def loss_fn(p):
        return jnp.mean((p - target) ** 2)

    p_a = p_b = jnp.zeros_like(target)
    st_a = st_b = tx.init(p_a)
    for _ in range(20):
        g_a = jax.grad(loss_fn)(p_a)
        upd, st_a = tx.update(g_a, st_a, p_a)
        p_a = optax.apply_updates(p_a, upd)

        g_b = jax.grad(loss_fn)(p_b)
        upd, st_fp = tx.update(g_b, decode_state(encode_state(st_b, 256)), p_b)
        st_b = st_fp
        p_b = optax.apply_updates(p_b, upd)

    la, lb = float(loss_fn(p_a)), float(loss_fn(p_b))
    assert lb < float(loss_fn(jnp.zeros_like(target))) * 0.2  # actually optimizes
    assert lb < la * 1.5 + 1e-4  # and not much worse than exact Adam
    # per-coordinate trajectories may drift (ceil-rounded nu shrinks steps
    # on small-nu coordinates by design); the aggregate path must track
    diff = np.abs(np.asarray(p_b) - np.asarray(p_a))
    travel = np.abs(np.asarray(p_a)).mean()  # ~1.0: distance optimized so far
    assert diff.mean() < 0.05 * travel + 1e-3
    cos = float(
        (p_a.ravel() @ p_b.ravel())
        / (jnp.linalg.norm(p_a.ravel()) * jnp.linalg.norm(p_b.ravel()))
    )
    assert cos > 0.995


def _offloadable_trainer(offload_dtype, block=16, max_steps=6):
    from tests.test_trainer import _make

    trainer, objective, dm = _make(max_steps=max_steps)
    trainer.config = trainer.config.model_copy(
        update={
            "offload_optimizer_state": True,
            "offload_state_dtype": offload_dtype,
            "offload_quant_block": block,
        }
    )
    return trainer, objective, dm


@pytest.mark.parametrize("offload_dtype", ["bfloat16", "int8"])
def test_blocked_compressed_step_matches_fp32(devices, offload_dtype):
    """One blocked-offload step with compressed state storage vs the fp32
    blocked step: params must agree tightly (fresh state: mu/nu leave the
    first step nearly unquantized), opt state must hold the compressed
    dtypes. Device memory kinds — the codec math is placement-agnostic."""
    import flax.linen as nn

    from llm_training_tpu.optim.builder import build_optimizer
    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh
    from llm_training_tpu.trainer.state import TrainState
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    results = {}
    for dtype in ("float32", offload_dtype):
        trainer, objective, dm = _offloadable_trainer(dtype)
        trainer.mesh = build_mesh(MeshConfig(fsdp_size=4, tensor_parallel_size=2))
        dm.setup()
        batch = next(dm.train_batches(start_step=0))
        clip_free = objective.config.optim.model_copy(update={"grad_clip_norm": None})
        tx, _ = build_optimizer(clip_free, num_total_steps=4)
        with trainer.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
            trainer._blocked_offload = True
            trainer._clip_norm = objective.config.optim.grad_clip_norm
            params = jax.jit(
                lambda rng: nn.meta.unbox(objective.init_params(rng, batch))
            )(jax.random.key(0))
            blocks = trainer._opt_init(tx, params)
            state = TrainState.create(params, blocks, jax.random.key(7))
            dev = jax.sharding.NamedSharding(trainer.mesh, jax.sharding.PartitionSpec())
            opt_sh = tuple(jax.tree.map(lambda _: dev, blk) for blk in blocks)
            step = trainer._build_blocked_offload_step(objective, tx, opt_sh, opt_sh)
            new_state, metrics = jax.jit(step)(state, batch)
        results[dtype] = (new_state, metrics)

    new_fp, m_fp = results["float32"]
    new_q, m_q = results[offload_dtype]
    np.testing.assert_allclose(
        float(m_fp["grad_norm"]), float(m_q["grad_norm"]), rtol=1e-6
    )
    for a, b in zip(jax.tree.leaves(new_fp.params), jax.tree.leaves(new_q.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3)
    # storage really is compressed
    q_dtypes = {
        leaf.q.dtype
        for blk in new_q.opt_state
        for leaf in jax.tree.leaves(
            blk, is_leaf=lambda x: isinstance(x, QuantArray)
        )
        if isinstance(leaf, QuantArray)
    }
    if offload_dtype == "int8":
        assert q_dtypes == {jnp.dtype(jnp.int8), jnp.dtype(jnp.uint8)}
    else:
        bf_leaves = [
            leaf for blk in new_q.opt_state for leaf in jax.tree.leaves(blk)
            if hasattr(leaf, "dtype") and leaf.ndim >= 1
        ]
        assert all(leaf.dtype == jnp.bfloat16 for leaf in bf_leaves)


def test_compressed_dtype_requires_offload(devices):
    trainer, objective, dm = _offloadable_trainer("int8")
    trainer.config = trainer.config.model_copy(
        update={"offload_optimizer_state": False}
    )
    with pytest.raises(ValueError, match="offload_optimizer_state"):
        trainer._build_tx(objective)


def _acc_grad_leaves(opt_state):
    return [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            opt_state, is_leaf=lambda x: isinstance(x, QuantArray)
        )[0]
        if any(getattr(p, "name", None) == "acc_grads" for p in path)
    ]


def test_serialized_int8_with_accumulation_matches_fp32(devices):
    """Grad accumulation forces the serialized (whole-tree) layout; the
    codec's field whitelist must leave MultiSteps' acc_grads exact while
    still compressing mu/nu, and the accumulated update must track the
    fp32-state run. Runs the REAL serialized train_step (device memory
    kinds) for two micro-steps = one optimizer step."""
    import flax.linen as nn

    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh
    from llm_training_tpu.trainer.state import TrainState
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    runs = {}
    for dtype in ("float32", "int8"):
        trainer, objective, dm = _offloadable_trainer(dtype)
        trainer.config = trainer.config.model_copy(
            update={"accumulate_grad_batches": 2}
        )
        trainer.mesh = build_mesh(MeshConfig(fsdp_size=4, tensor_parallel_size=2))
        dm.setup()
        it = dm.train_batches(start_step=0)
        b1, b2 = next(it), next(it)
        with trainer.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
            tx, _ = trainer._build_tx(objective)
            assert not trainer._blocked_offload  # accumulation -> serialized
            params = jax.jit(
                lambda rng: nn.meta.unbox(objective.init_params(rng, b1))
            )(jax.random.key(0))
            opt_state = trainer._opt_init(tx, params)
            state = TrainState.create(params, opt_state, jax.random.key(7))
            dev = jax.sharding.NamedSharding(
                trainer.mesh, jax.sharding.PartitionSpec()
            )
            trainer.state_shardings = jax.tree.map(
                lambda _: dev, jax.eval_shape(lambda: state)
            )
            step = jax.jit(trainer._build_step(objective, tx))
            s1, _ = step(state, b1)
            s2, _ = step(s1, b2)
        runs[dtype] = (opt_state, s1, s2)

    init_q, s1_q, s2_q = runs["int8"]
    init_f, s1_f, s2_f = runs["float32"]
    # mu/nu compressed, accumulators exact fp32 arrays
    flat_q = jax.tree_util.tree_flatten_with_path(
        init_q, is_leaf=lambda x: isinstance(x, QuantArray)
    )[0]
    assert any(isinstance(leaf, QuantArray) for _, leaf in flat_q)
    accs = _acc_grad_leaves(init_q)
    assert accs and all(
        not isinstance(a, QuantArray) and a.dtype == jnp.float32 for a in accs
    )
    # after micro-step 1 (accumulate only) the accumulators match BITWISE
    for a, b in zip(_acc_grad_leaves(s1_q.opt_state), _acc_grad_leaves(s1_f.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # after micro-step 2 the optimizer fired: params track the fp32 run
    for a, b in zip(jax.tree.leaves(s2_q.params), jax.tree.leaves(s2_f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3)


def test_checkpoint_roundtrip_int8_state(tmp_path, devices):
    """Orbax save/restore of the compressed per-leaf state layout: the
    QuantArray pytree (int8 q + fp32 scale, static kind/block) must survive
    a round trip against the abstract target."""
    import flax.linen as nn

    from llm_training_tpu.optim.builder import build_optimizer
    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh
    from llm_training_tpu.trainer.checkpoint import CheckpointConfig, Checkpointer
    from llm_training_tpu.trainer.state import TrainState
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    trainer, objective, dm = _offloadable_trainer("int8")
    trainer.mesh = build_mesh(MeshConfig(fsdp_size=4, tensor_parallel_size=2))
    dm.setup()
    batch = next(dm.train_batches(start_step=0))
    tx, _ = build_optimizer(objective.config.optim, num_total_steps=4)
    trainer._blocked_offload = True
    with trainer.mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        params = jax.jit(
                lambda rng: nn.meta.unbox(objective.init_params(rng, batch))
            )(jax.random.key(0))
        state = TrainState.create(
            params, trainer._opt_init(tx, params), jax.random.key(7)
        )
        abstract = jax.eval_shape(lambda: state)
        shardings = jax.tree.map(
            lambda _: jax.sharding.NamedSharding(
                trainer.mesh, jax.sharding.PartitionSpec()
            ),
            abstract,
        )

    ckpt = Checkpointer(CheckpointConfig(dirpath=str(tmp_path), max_to_keep=1))
    ckpt.save(0, state, {})
    ckpt.wait()
    restored, _ = ckpt.maybe_restore(abstract, shardings, 0)
    ckpt.close()

    for a, b in zip(
        jax.tree.leaves(state.opt_state, is_leaf=lambda x: isinstance(x, QuantArray)),
        jax.tree.leaves(restored.opt_state, is_leaf=lambda x: isinstance(x, QuantArray)),
    ):
        if isinstance(a, QuantArray):
            assert a.kind == b.kind and a.block == b.block
            np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
            np.testing.assert_array_equal(np.asarray(a.scale), np.asarray(b.scale))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
