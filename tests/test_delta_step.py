"""The one-token delta-rule kernel (`ops/pallas/delta_step.py`), interpreted on
the CPU, against the XLA steps it stands in for (`kda_step`,
`gated_delta_step`) on the states of a decode slab as they are stored; the
rule by which a layer's step takes the kernel or the XLA form
(`ops/delta_rule.py:slab_rows`, through `LayerCache.recurrent_rows(...,
delta_step=True)`) and the count it leaves; and that the layers of a program
share ONE trace of the kernel. (A chunk never asks: `tests/test_serve_spans.py`
holds that its program has no recurrence scope. Whole engines on either path:
`tests/test_serve_tables.py`.)

Float32 against float32: the kernel sums a head's key axis in another order
than XLA's fusion does, 2e-6 of the largest value compared.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.models.cache import LayerCache, _slot_rows
from llm_training_tpu.models.solar_open2.kda import kda_step
from llm_training_tpu.ops import delta_rule
from llm_training_tpu.ops.delta_rule import SlabRows, gated_delta_step, one_token_step
from llm_training_tpu.ops.pallas.delta_step import delta_step
from llm_training_tpu.ops.pallas.tuning import delta_step_heads
from llm_training_tpu.telemetry.registry import TelemetryRegistry, get_registry, set_registry

LAYERS, LAYER, ROWS, IDLE_ROW = 3, 1, 4, 2
TOL = 2e-6
# (heads, dk, dv, heads abreast, a decay a key channel): the two serve cells'
# states and two small ones
SHAPES = {
    "kda-solar-64x128x128": (64, 128, 128, 1, True),
    "gated-olmo-hybrid-15x96x384": (30, 96, 192, 2, False),
    "kda-small": (4, 16, 128, 1, True),
    "gated-small-two-abreast": (6, 8, 64, 2, False),
}
bits = lambda x: np.asarray(x).view(np.uint32)


@pytest.fixture()
def registry():
    previous = set_registry(TelemetryRegistry())
    try:
        yield get_registry()
    finally:
        set_registry(previous)


@pytest.fixture()
def on_kernels(monkeypatch):
    """The chip's choice of path on the CPU: the kernel, interpreted."""
    monkeypatch.setattr(delta_rule, "_on_kernels", lambda impl: True)


def calls(registry):
    return tuple(registry.gauge(delta_rule.DELTA_STEP_GAUGES[path]).value or 0 for path in ("kernel", "xla"))


def token(heads, dk, dv, per_channel, rows=ROWS, seed=0):
    """One token's vectors for `rows` rows; row `IDLE_ROW` decodes nothing."""
    keys = jax.random.split(jax.random.key(seed), 5)
    q = delta_rule.l2norm(jax.random.normal(keys[0], (rows, heads, dk))) * dk**-0.5
    k = delta_rule.l2norm(jax.random.normal(keys[1], (rows, heads, dk)))
    v = jax.random.normal(keys[2], (rows, heads, dv))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(keys[3], (rows, heads)))
    log_decay = -jax.nn.softplus(
        jax.random.normal(keys[4], (rows, heads, dk) if per_channel else (rows, heads))
    )
    idle = jnp.arange(rows) == IDLE_ROW
    beta = jnp.where(idle[:, None], 0.0, beta)
    log_decay = jnp.where(idle.reshape(-1, *[1] * (log_decay.ndim - 1)), 0.0, log_decay)
    return q, k, v, log_decay, beta


def slab_of(heads, dk, dv, abreast, rows=ROWS, dtype=jnp.float32):
    shape = (LAYERS, rows, heads // abreast, dk, abreast * dv)
    return jax.random.normal(jax.random.key(7), shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
def test_kernel_is_the_xla_step_on_the_slab_where_it_lies(shape):
    heads, dk, dv, abreast, per_channel = SHAPES[shape]
    slab, vectors = slab_of(heads, dk, dv, abreast), token(heads, dk, dv, per_channel)
    step = kda_step if per_channel else gated_delta_step

    # the layer rides as a traced scalar, as under a layer scan
    @jax.jit
    def stepped(slab, layer):
        state, out = one_token_step(SlabRows(slab, layer), step, *vectors)
        return state.slab, out

    new, out = stepped(slab, jnp.int32(LAYER))
    want_state, want_out = jax.jit(step)(slab[LAYER], *vectors)
    for got, want in ((out, want_out), (new[LAYER], want_state)):
        assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL * np.abs(np.asarray(want)).max()
    # an idle row (beta 0, log decay 0) keeps its state bit for bit
    assert np.array_equal(bits(new[LAYER, IDLE_ROW]), bits(slab[LAYER, IDLE_ROW]))
    # and the other layers' rows are not touched
    for other in set(range(LAYERS)) - {LAYER}:
        assert np.array_equal(bits(new[other]), bits(slab[other]))


@pytest.mark.parametrize("shape,blocks", [("kda-small", (4, 2, 1)), ("gated-small-two-abreast", (3, 1))])
def test_every_block_of_stored_heads_gives_the_same_bits(shape, blocks):
    """The grid's block is the unit of the copies in and out, nothing of the
    arithmetic: a head's sums do not see how many heads came in with it."""
    heads, dk, dv, abreast, per_channel = SHAPES[shape]
    slab, (q, k, v, log_decay, beta) = slab_of(heads, dk, dv, abreast), token(heads, dk, dv, per_channel)
    assert delta_step_heads(*slab.shape[2:], 3 if per_channel else 4) == blocks[0]
    results = [
        delta_step(slab, jnp.int32(LAYER), q, k, v, jnp.exp(log_decay), beta, block=block, interpret=True)
        for block in blocks
    ]
    for out, new in results[1:]:
        assert np.array_equal(bits(out), bits(results[0][0])) and np.array_equal(bits(new), bits(results[0][1]))


def test_block_sizes_are_the_tables_and_else_follow_from_the_shape():
    assert delta_step_heads(64, 128, 128, 3) == 16  # KDA at Solar's widths: 1 MB a step
    assert delta_step_heads(15, 96, 384, 4) == 5  # the gated rule, two heads abreast: 0.72 MB
    assert delta_step_heads(64, 128, 256, 3) == 8  # a shape not listed: within 1 MB
    assert delta_step_heads(7, 8, 128, 3) == 7  # every stored head, where they fit
    assert delta_step_heads(96, 8, 128, 3) == 32  # the turned vectors fit the 128 lanes: 3 x 32
    assert delta_step_heads(13, 512, 1024, 3) == 1  # at least one


def through_the_cache(cache, step, vectors, read=_slot_rows):
    """A layer's one-token turn as the families write it: -> (out, the cache after)."""
    rows = cache.recurrent_rows(LAYER, read, delta_step=True)
    state, out = one_token_step(rows[0], step, *vectors)
    if not isinstance(state, SlabRows):
        state = state.astype(cache.state.dtype)
    return out, cache.put_recurrent_rows(LAYER, (state, rows[1] + 1)), rows[0]


@pytest.mark.parametrize("shape", ["kda-small", "gated-small-two-abreast"])
def test_a_layer_meets_the_kernel_through_its_cache(shape, registry, monkeypatch):
    """`recurrent_rows(delta_step=True)` hands out the slab where it lies,
    `one_token_step` advances the layer in the kernel, `put_recurrent_rows`
    carries the new slab and writes the conv tail as ever: the cache after is
    the XLA path's, and the counter says which path it was."""
    heads, dk, dv, abreast, per_channel = SHAPES[shape]
    slab, vectors = slab_of(heads, dk, dv, abreast), token(heads, dk, dv, per_channel)
    step = kda_step if per_channel else gated_delta_step
    conv = jnp.zeros((LAYERS, ROWS, 3, 8), jnp.float32)
    cache = LayerCache(k=None, v=None, state=slab, conv=conv, paged=True)
    monkeypatch.setattr(delta_rule, "_on_kernels", lambda impl: True)
    out, after, handed = through_the_cache(cache, step, vectors)
    assert isinstance(handed, SlabRows) and handed.slab is slab
    assert calls(registry) == (LAYERS, 0)
    delta_rule.reset_delta_step_calls()
    assert calls(registry) == (0, 0)

    monkeypatch.setattr(delta_rule, "_on_kernels", lambda impl: False)
    want_out, want, rows = through_the_cache(cache, step, vectors)
    assert not isinstance(rows, SlabRows) and calls(registry) == (0, LAYERS)
    for got, ref in ((out, want_out), (after.state, want.state)):
        assert np.abs(np.asarray(got) - np.asarray(ref)).max() < TOL * np.abs(np.asarray(ref)).max()
    assert np.array_equal(np.asarray(after.conv), np.asarray(want.conv)) and float(after.conv[LAYER].min()) == 1.0


REFUSED = {
    "a stored head that is not whole tiles: 64 lanes": dict(shape=(4, 16, 64, 1)),
    "a stored head that is not whole tiles: 12 key channels": dict(shape=(6, 12, 64, 2)),
    "a state that is not float32": dict(dtype=jnp.bfloat16),
    "picked slots": dict(slots=True),
    "fresh rows": dict(fresh=True),
    "a planted read": dict(read=lambda slab, slots, fresh: _slot_rows(slab, slots, fresh)),
    "off the chip": dict(on_kernels=False),
}


@pytest.mark.parametrize("why", REFUSED)
def test_what_the_kernel_refuses_takes_the_xla_step_and_the_counter_says_so(why, registry, monkeypatch):
    case = REFUSED[why]
    monkeypatch.setattr(delta_rule, "_on_kernels", lambda impl: case.get("on_kernels", True))
    heads, dk, dv, abreast = case.get("shape", (6, 8, 64, 2))
    slab = slab_of(heads, dk, dv, abreast, dtype=case.get("dtype", jnp.float32))
    vectors = token(heads, dk, dv, per_channel=False)
    cache = LayerCache(
        k=None, v=None, state=slab, conv=jnp.zeros((LAYERS, ROWS, 3, 8), jnp.float32), paged=True,
        slots=jnp.arange(ROWS) if case.get("slots") else None,
        fresh=jnp.arange(ROWS) == 1 if case.get("fresh") else None,
    )
    out, after, rows = through_the_cache(cache, gated_delta_step, vectors, case.get("read", _slot_rows))
    assert not isinstance(rows, SlabRows) and calls(registry) == (0, LAYERS)
    old = slab[LAYER].astype(jnp.float32)
    if case.get("fresh"):
        old = old.at[1].set(0.0)
    want_state, want_out = gated_delta_step(old, *vectors)
    assert np.array_equal(bits(out), bits(want_out))
    assert np.array_equal(np.asarray(after.state[LAYER]), np.asarray(want_state.astype(slab.dtype)))


def test_the_layers_of_a_program_share_one_trace_of_the_kernel(on_kernels, monkeypatch):
    """Three layers of a looped body, each a call site with a layer index of
    its own (a Python int): the kernel's body is traced ONCE, and the lowered
    program holds one function of it and three calls. What a process start
    pays for the kernel does not grow with the layers (PERF.md section 6, PR
    46 and 47)."""
    from llm_training_tpu.ops.pallas import delta_step as module

    heads, dk, dv, abreast, per_channel = SHAPES["kda-small"]
    rows = 5  # a shape no other test of this file traces: the jit's cache does not hold it
    slab, vectors = slab_of(heads, dk, dv, abreast, rows), token(heads, dk, dv, per_channel, rows)
    kernel, traced = module._delta_step_kernel, []
    monkeypatch.setattr(
        module, "_delta_step_kernel", lambda *refs, **static: (traced.append(1), kernel(*refs, **static))[1]
    )

    def body(slab):
        outs = []
        for layer in range(LAYERS):
            state, out = one_token_step(SlabRows(slab, layer), kda_step, *vectors)
            slab = state.slab
            outs.append(out)
        return slab, outs

    lowered = jax.jit(body).lower(slab)
    assert len(traced) == 1
    text = lowered.as_text()
    assert text.count("func.func private @delta_step(") == 1 and text.count("call @delta_step(") == LAYERS
    new, outs = jax.jit(body)(slab)
    for layer in range(LAYERS):
        want_state, want_out = kda_step(slab[layer], *vectors)
        for got, want in ((new[layer], want_state), (outs[layer], want_out)):
            assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL * np.abs(np.asarray(want)).max()
