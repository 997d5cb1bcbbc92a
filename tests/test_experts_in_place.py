"""The experts' grouped matmul reads layer i's weights inside the stacked
parameter (docs/inference.md, "How a layer meets a stacked weight"): the
helper against `jax.lax.ragged_dot` on the layer's slice, the decoding stack
that engages it against the dense cache and the looped model, the traced
decode program's structure at any number of layers, and the fallbacks."""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.interpreters import partial_eval as pe

from llm_training_tpu.infer import GenerateConfig, InferenceEngine
from llm_training_tpu.models import Llama, LlamaConfig
from llm_training_tpu.models.moe import EXPERT_LEAVES, _gmm_tiling, grouped_matmul
from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh
from llm_training_tpu.serve import ServeConfig, ServingEngine
from tests.test_serve_spans import (
    PROMPTS, SERVE, TINY_MOE, _decode_args, _engine, _prefill_args, _requests,
)

LAYERS, EXPERTS, K, N, ROWS = 3, 4, 32, 48, 40
DEEP_MOE = dict(TINY_MOE, num_hidden_layers=LAYERS)
# the per-layer shapes of DEEP_MOE's expert leaves, as a slice of the stack has them
STACKS = sorted([(LAYERS, 4, 32, 16), (LAYERS, 4, 32, 16), (LAYERS, 4, 16, 32)])
EXPERT_SLICE = re.compile(r"dynamic_slice.*-> tensor<1x4x(?:32x16|16x32)xf32>")


# ------------------------------------------------------------- the helper

@pytest.mark.parametrize("dtype,tolerance", [
    pytest.param(jnp.float32, 1e-5, id="float32"),
    pytest.param(jnp.bfloat16, 2e-2, id="bfloat16"),
])
@pytest.mark.parametrize("layers,sizes", [
    pytest.param(LAYERS, [13, 0, 0, 27], id="empty-groups"),
    pytest.param(LAYERS, [0, 0, ROWS, 0], id="one-group-holds-every-row"),
    pytest.param(LAYERS, [9, 4, 0, 6], id="trailing-rows-in-no-group"),
    pytest.param(LAYERS, [0, 0, 0, 0], id="no-row-in-any-group"),
    # a stack of ONE layer at `layer=0`: a one-period scan's, a looped layer's own
    pytest.param(1, [10, 10, 10, 10], id="one-layer-every-row-in-a-group"),
    pytest.param(1, [11, 0, 0, 29], id="one-layer-empty-groups-in-the-middle"),
    pytest.param(1, [17, 23, 0, 0], id="one-layer-empty-groups-at-the-end"),
    pytest.param(1, [3, 0, 2, 0], id="one-layer-a-held-shares-stray-rows"),
    pytest.param(1, [0, 0, 0, 0], id="one-layer-no-row-in-any-group"),
])
def test_in_place_grouped_matmul_equals_ragged_dot_on_the_layers_slice(layers, sizes, dtype, tolerance):
    """Every layer i of a stack `[L, E, K, N]`, L of one too: the product
    with the stack in place is `ragged_dot`'s with layer i cut out, rows past
    the last group (a held share's) zero as `ragged_dot` leaves them. Off
    the chip the Pallas kernel runs in the interpreter."""
    keys = jax.random.split(jax.random.key(len(sizes) + sum(sizes)), 2)
    stack = jax.random.normal(keys[0], (layers, EXPERTS, K, N), jnp.float32).astype(dtype)
    xs = jax.random.normal(keys[1], (ROWS, K), jnp.float32).astype(dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    in_place = jax.jit(lambda layer: grouped_matmul(xs, stack, sizes, layer))
    cut_out = jax.jit(lambda layer: grouped_matmul(xs, stack[layer], sizes))
    for layer in range(layers):
        got, want = in_place(jnp.int32(layer)), cut_out(layer)
        assert got.dtype == want.dtype == dtype and got.shape == (ROWS, N)
        want = np.asarray(want, np.float32)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), want, rtol=tolerance,
            atol=tolerance * max(1.0, float(np.abs(want).max())),
        )
        assert not np.asarray(got, np.float32)[int(sizes.sum()):].any()


@pytest.mark.parametrize("k,n,tiling", [
    pytest.param(2048, 1024, (2048, 1024), id="olmoe-trinity-gate-up: one expert's whole matrix"),
    pytest.param(1024, 2048, (1024, 2048), id="olmoe-trinity-down"),
    pytest.param(6144, 2048, (6144, 256), id="longcat-gate-up"),
    pytest.param(2048, 6144, (2048, 768), id="longcat-down"),
    pytest.param(7680, 2048, (7680, 256), id="pangu-gate-up"),
    # N halved stops at 5 and 15 lane tiles, too large: a divisor of N beside the whole of K
    pytest.param(4096, 1280, (4096, 256), id="solar-gate-up"),
    pytest.param(1280, 4096, (1280, 1024), id="solar-down"),
    pytest.param(2048, 7680, (2048, 768), id="pangu-down"),
    # no lane tile fits beside the whole of K: K halved
    pytest.param(65536, 1280, (2048, 640), id="k-too-long-for-any-tile"),
    pytest.param(32, 48, (32, 48), id="tiny"),
])
def test_gmm_tiling_keeps_k_whole_where_a_tile_holds_it(k, n, tiling):
    """A pure function of the shapes, at every serve cell's widths in
    bfloat16: a weight tile of at most 4 MiB, the whole of K wherever some
    divisor of N in whole 128-lane tiles fits beside it."""
    for rows, tm in ((256, 128), (4096, 128), (40, 48)):
        assert _gmm_tiling(rows, k, n, 2) == (tm, *tiling)
    assert tiling[0] * tiling[1] * 2 <= 4 << 20 and k % tiling[0] == 0 and n % tiling[1] == 0


# ------------------------------------------- a decoding stack that engages

def _unstacked(variables):
    """A scanned Llama's parameters in the looped model's layout."""
    params = dict(nn.meta.unbox(variables)["params"])
    stacked = params.pop("layers")["layer"]
    layers = jax.tree.leaves(stacked)[0].shape[0]
    for i in range(layers):
        params[f"layers_{i}"] = jax.tree.map(lambda leaf: leaf[i], stacked)
    return {"params": params}


def _served(model, variables, n):
    engine = ServingEngine(model, variables, ServeConfig(**SERVE))
    done = {e["id"]: e["tokens"] for e in engine.run(_requests(n)) if e["type"] == "done"}
    return [done[f"r{i}"] for i in range(len(PROMPTS))]


def test_paged_decode_in_place_serves_the_dense_caches_and_the_looped_models_tokens():
    """Chunked prefill (prompts of 6, 3 and 5 in chunks of 4) and decode
    steps of a scanned MoE stack, `moe_impl="ragged"` forced, three layers:
    the paged path, which reads the experts in place, serves what the dense
    `DecodeState` path does and what the looped model (its own per-layer
    parameters, each a stack of one) does through both caches."""
    n = 8
    scanned = Llama(LlamaConfig(**DEEP_MOE))
    looped = Llama(LlamaConfig(**DEEP_MOE, scan_layers=False))
    variables = jax.jit(scanned.init)(jax.random.key(0), np.zeros((1, 4), np.int32))
    per_layer = _unstacked(variables)
    generate = GenerateConfig(max_new_tokens=n)
    dense_cache = InferenceEngine(scanned, variables).generate(PROMPTS, generate)["tokens"]
    assert InferenceEngine(looped, per_layer).generate(PROMPTS, generate)["tokens"] == dense_cache
    assert _served(scanned, variables, n) == dense_cache
    assert _served(looped, per_layer, n) == dense_cache


# ------------------------------------------------ the traced program's form

def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def _primitives(jaxpr):
    return {eqn.primitive.name for eqn in _eqns(jaxpr)}


def _layer_scans(jaxpr):
    return [
        eqn for eqn in _eqns(jaxpr)
        if eqn.primitive.name == "scan" and eqn.params["length"] == LAYERS
    ]


def _layer_scan(jitted, args, **kwargs):
    """Of the layer loop's scan as the compiler gets it, dead code dropped:
    (the expert stacks among its constants, among its scanned inputs, the
    program's primitives)."""
    traced = jax.make_jaxpr(jitted)(*args, **kwargs).jaxpr
    live, _ = pe.dce_jaxpr(traced, [True] * len(traced.outvars))
    (scan,) = _layer_scans(live)
    consts, carry = scan.params["num_consts"], scan.params["num_carry"]
    shapes = lambda invars: sorted(v.aval.shape for v in invars if v.aval.shape in STACKS)
    return shapes(scan.invars[:consts]), shapes(scan.invars[consts + carry:]), _primitives(live)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_expert_leaves_enter_the_layer_scan_as_constants(program):
    """The built serving program: the three expert leaves `[L, E, ...]` are
    constants of the layer scan, none of its scanned inputs has their shape
    once dead code is gone, the experts run in a Pallas call and no
    `ragged_dot`, and no `dynamic_slice` of the lowered program yields a
    layer's experts."""
    engine = _engine(DEEP_MOE)
    jitted, args = (
        (engine._decode_jit, _decode_args(engine)) if program == "decode"
        else (engine._prefill_jit, _prefill_args(engine))
    )
    consts, scanned, primitives = _layer_scan(jitted, args)
    assert consts == STACKS and scanned == []
    assert "pallas_call" in primitives and "ragged_dot_general" not in primitives
    assert not EXPERT_SLICE.search(jitted.lower(*args).as_text())


# ----------------------------------------------------------- the fallbacks

@pytest.fixture()
def ep_mesh(devices):
    return build_mesh(MeshConfig(fsdp_size=4, expert_parallel_size=2))


@pytest.fixture()
def fsdp_mesh(devices):
    return build_mesh(MeshConfig(fsdp_size=8))


def _decode_form(config, mesh=None):
    engine = _engine(config)
    args = _decode_args(engine)
    if mesh is None:
        return _layer_scan(engine._decode_jit, args), engine._decode_jit.lower(*args).as_text()
    with mesh:
        return _layer_scan(engine._decode_jit, args), engine._decode_jit.lower(*args).as_text()


@pytest.mark.parametrize("case,impl,mesh", [
    ("dense-impl", "dense", None),
    ("auto-off-the-chip", "auto", None),
    ("expert-mesh", "ragged", "ep_mesh"),
    ("sharded-mesh", "ragged", "fsdp_mesh"),
])
def test_fallbacks_keep_the_scans_slices(case, impl, mesh, request):
    """Off the ragged path, where `auto` does not mean ragged, on an expert
    mesh and on any mesh of several devices (a Mosaic kernel cannot be
    partitioned for it), the decode program is the one it was: the expert
    leaves are the scan's sliced inputs, nothing of their shape is closed
    over, no Pallas call multiplies them."""
    mesh = mesh and request.getfixturevalue(mesh)
    (consts, scanned, primitives), text = _decode_form(dict(DEEP_MOE, moe_impl=impl), mesh)
    assert consts == [] and scanned == STACKS
    assert "pallas_call" not in primitives
    assert ("ragged_dot_general" in primitives) == (impl == "ragged")
    assert len(EXPERT_SLICE.findall(text)) == len(EXPERT_LEAVES)


def test_training_trace_keeps_the_scans_slices():
    """No `decode_state`, no stack: the training forward and backward of
    the ragged path multiply the scan's slices with `ragged_dot`."""
    model = Llama(LlamaConfig(**DEEP_MOE))
    ids = jnp.zeros((2, 8), jnp.int32)
    variables = jax.jit(model.init)(jax.random.key(0), ids)
    loss = lambda v: model.apply(v, ids).logits.astype(jnp.float32).mean()
    traced = jax.make_jaxpr(jax.value_and_grad(loss))(variables).jaxpr
    primitives = _primitives(traced)
    assert "ragged_dot_general" in primitives and "pallas_call" not in primitives
    forward = _layer_scans(traced)[0]
    consts = forward.params["num_consts"]
    assert not [v for v in forward.invars[:consts] if v.aval.shape in STACKS]


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("config", [
    pytest.param(dict(DEEP_MOE, num_hidden_layers=1), id="a-scan-of-one-layer"),
    pytest.param(dict(DEEP_MOE, scan_layers=False), id="looped"),
])
def test_a_stack_of_one_layer_reads_its_experts_in_place(config, program):
    """Which grouped product a decoding layer uses does not depend on how
    many layers its stack has: a scan of one layer (its stack, index 0) and a
    looped layer (its own leaves seen as `[1, E, ...]`) multiply through the
    Pallas grouped matmul, which skips the groups with no rows, and no
    `ragged_dot` is left in either serving program."""
    engine = _engine(config)
    jitted, args = (
        (engine._decode_jit, _decode_args(engine)) if program == "decode"
        else (engine._prefill_jit, _prefill_args(engine))
    )
    primitives = _primitives(jax.make_jaxpr(jitted)(*args).jaxpr)
    assert "pallas_call" in primitives and "ragged_dot_general" not in primitives
