"""KV-cache construction + sharding for the inference subsystem.

The `DecodeState` pytree itself lives in `models/base.py` (next to
`CausalLMOutput`, so model files never import `infer/`); this module owns
everything about *building* one: sizing from a model config, the cache
dtype policy, the mesh placement (k/v heads shard over 'tensor', batch over
'data'/'fsdp' — the same rule table the attention activations use), and the
HBM-footprint gauge the decode telemetry publishes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from llm_training_tpu.models.base import (
    DecodeState,
    KVCacheSpec,
    LatentCacheSpec,
    RecurrentCacheSpec,
    resolve_dtype,
)
from llm_training_tpu.parallel.sharding import LogicalAxisRules, logical_to_spec

# cache buffer layout: [num_layers, batch, max_length, num_kv_heads, head_dim]
KV_LOGICAL_AXES = ("layers", "batch", None, "kv_heads", None)
# a latent cache's one row a token is shared by the heads: nothing to shard there
LATENT_LOGICAL_AXES = ("layers", "batch", None, None, None)
SEG_LOGICAL_AXES = ("batch", None)
# recurrent slab: state [layers, slots, *RecurrentCacheSpec.stored] (heads,
# key_dim, value_dim; some heads side by side where that fills the chip's
# tiles) and the conv tail [layers, slots, taps, channels]; a slot is a batch row
STATE_LOGICAL_AXES = ("layers", "batch", "heads", None, None)
CONV_LOGICAL_AXES = ("layers", "batch", None, "heads")


def cache_specs(
    config,
) -> tuple[
    KVCacheSpec | LatentCacheSpec | tuple[KVCacheSpec, KVCacheSpec],
    RecurrentCacheSpec | None,
]:
    """What a stack caches, as its config declares it
    (`BaseModelConfig.cache_specs`): every pool, dense buffer, slab and
    sharding below derives from it."""
    declared = config.cache_specs()
    if declared is None:
        raise NotImplementedError(
            f"{type(config).__name__} declares no cache (cache_specs() is None): "
            "the family does not decode"
        )
    return declared


def kv_groups(config) -> tuple[KVCacheSpec | LatentCacheSpec, KVCacheSpec | None]:
    """(the layers that keep every token, the layers that keep a window or
    None): the attention part of the declaration. A stack declares the second
    group only where its layers differ in how much of the past they keep; a
    lone spec is the first group whatever its `window` (a window every layer
    shares is a mask over one pool, as it always was)."""
    spec, _ = cache_specs(config)
    return spec if isinstance(spec, tuple) else (spec, None)


def token_rows(config) -> tuple[int, int, int, int]:
    """(buffers, layers, heads, width): what a token leaves in each layer of
    the attention cache, as the pools and dense buffers are shaped
    `[layers, ..., heads, ..., width]`. Keys and values: two buffers of
    `kv_heads` rows of `head_dim`. Latent rows: ONE buffer of one row. Of a
    stack with two groups (`kv_groups`) these are the first group's layers."""
    spec, _ = kv_groups(config)
    if isinstance(spec, LatentCacheSpec):
        return 1, spec.layers, 1, spec.width
    return 2, spec.layers, spec.kv_heads, spec.head_dim


def dense_cache_axes(config) -> tuple[str | None, ...]:
    """The logical axes of the dense attention buffers `token_rows` shapes."""
    latent = isinstance(kv_groups(config)[0], LatentCacheSpec)
    return LATENT_LOGICAL_AXES if latent else KV_LOGICAL_AXES


def slab_shapes(
    spec: RecurrentCacheSpec, slots: int
) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """(state shape, conv-tail shape) for `slots` decode slots."""
    return (
        (spec.layers, slots, *spec.stored),
        (spec.layers, slots, spec.conv_taps, spec.conv_channels),
    )


def slab_logical_bytes(spec: RecurrentCacheSpec, slots: int, tail_dtype) -> int:
    """What the slab HOLDS for `slots` slots: the float32 states as declared
    (`heads x key_dim x value_dim`) and the tails. What it occupies is its
    arrays' size, the `decode/state_bytes` gauge: the same where the stored
    layout fills the chip's tiles."""
    state = spec.heads * spec.key_dim * spec.value_dim * 4
    tail = spec.conv_taps * spec.conv_channels * jnp.dtype(tail_dtype).itemsize
    return spec.layers * slots * (state + tail)


def slab_shardings(spec: RecurrentCacheSpec, slots: int, mesh: Mesh, rules):
    state_shape, conv_shape = slab_shapes(spec, slots)
    return (
        NamedSharding(mesh, _divisible_spec(state_shape, STATE_LOGICAL_AXES, mesh, rules)),
        NamedSharding(mesh, _divisible_spec(conv_shape, CONV_LOGICAL_AXES, mesh, rules)),
    )


def _slab_zeros(spec: RecurrentCacheSpec, slots: int, tail_dtype):
    state_shape, conv_shape = slab_shapes(spec, slots)
    return jnp.zeros(state_shape, jnp.float32), jnp.zeros(conv_shape, tail_dtype)


def init_state_slab(
    config, slots: int, mesh: Mesh | None = None, rules=None,
    cache_dtype: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray] | None:
    """Fresh all-zeros (state, conv tail) for a stack with linear-attention
    layers, None for one without. The state is float32 whatever the cache
    dtype (it is summed into over the whole sequence); the tail holds
    activations and takes the cache dtype."""
    _, spec = cache_specs(config)
    if spec is None:
        return None
    dtype = resolve_cache_dtype(config, cache_dtype)

    def build():
        return _slab_zeros(spec, slots, dtype)

    if mesh is None:
        return build()
    return jax.jit(build, out_shardings=slab_shardings(spec, slots, mesh, rules or ()))()


def resolve_cache_dtype(config, cache_dtype: str | None) -> jnp.dtype:
    """None / 'param' -> the model's param dtype; otherwise an explicit
    dtype name ('float32' for an exactness oracle, 'bfloat16' to halve the
    cache HBM)."""
    if cache_dtype in (None, "param"):
        return config.param_jnp_dtype
    return resolve_dtype(cache_dtype)


def _divisible_spec(
    shape: tuple[int, ...],
    logical_axes: tuple[str | None, ...],
    mesh: Mesh,
    rules: LogicalAxisRules,
) -> PartitionSpec:
    """logical axes -> PartitionSpec, dropping any mesh axis whose ways do
    not divide the dimension (a 1-prompt batch on an 8-way data mesh must
    replicate, not error)."""
    spec = logical_to_spec(logical_axes, rules)
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        ways = 1
        for axis in axes:
            ways *= mesh.shape[axis]
        out.append(entry if ways and dim % ways == 0 else None)
    return PartitionSpec(*out)


def decode_state_shardings(
    config,
    batch_size: int,
    max_length: int,
    mesh: Mesh,
    rules: LogicalAxisRules,
    rope_length: int | None = None,
) -> DecodeState:
    """A DecodeState-shaped tree of NamedShardings for jit in/out.
    `rope_length` must match the state the shardings are used with — it is
    static pytree metadata, so a mismatch is a structure mismatch."""
    buffers, num_layers, kv_heads, head_dim = token_rows(config)
    kv_shape = (num_layers, batch_size, max_length, kv_heads, head_dim)
    kv = NamedSharding(mesh, _divisible_spec(kv_shape, dense_cache_axes(config), mesh, rules))
    seg = NamedSharding(
        mesh,
        _divisible_spec((batch_size, max_length), SEG_LOGICAL_AXES, mesh, rules),
    )
    _, recurrent = cache_specs(config)
    state = conv = None
    if recurrent is not None:
        state, conv = slab_shardings(recurrent, batch_size, mesh, rules)
    window = None
    if (group := kv_groups(config)[1]) is not None:
        window = NamedSharding(mesh, _divisible_spec(
            (group.layers, *kv_shape[1:]), KV_LOGICAL_AXES, mesh, rules
        ))
    return DecodeState(
        k=kv, v=kv if buffers == 2 else None,
        index=NamedSharding(mesh, PartitionSpec()), segment_ids=seg,
        state=state, conv=conv, window_k=window, window_v=window,
        rope_length=rope_length,
    )


def init_decode_state(
    config,
    batch_size: int,
    max_length: int,
    mesh: Mesh | None = None,
    rules: LogicalAxisRules | None = None,
    cache_dtype: str | None = None,
    rope_length: int | None = None,
) -> DecodeState:
    """Fresh all-zeros cache (index 0, no slot filled). With a mesh the
    buffers are created ALREADY sharded (jit with out_shardings), so the
    first prefill never materializes a replicated cache. `rope_length` is
    the planned total sequence length when it is shorter than the cache
    capacity (length-dependent RoPE variants select tables from it)."""
    buffers, num_layers, kv_heads, head_dim = token_rows(config)
    dtype = resolve_cache_dtype(config, cache_dtype)

    _, recurrent = cache_specs(config)
    window = kv_groups(config)[1]

    def build() -> DecodeState:
        kv_shape = (num_layers, batch_size, max_length, kv_heads, head_dim)
        state, conv = (
            (None, None) if recurrent is None else _slab_zeros(recurrent, batch_size, dtype)
        )
        # the window group's layers at full length: the dense path masks
        window_k, window_v = (None, None) if window is None else (
            jnp.zeros((window.layers, *kv_shape[1:]), dtype) for _ in range(2)
        )
        return DecodeState(
            k=jnp.zeros(kv_shape, dtype),
            v=jnp.zeros(kv_shape, dtype) if buffers == 2 else None,
            index=jnp.int32(0),
            segment_ids=jnp.zeros((batch_size, max_length), jnp.int32),
            state=state, conv=conv, window_k=window_k, window_v=window_v,
            rope_length=rope_length,
        )

    if mesh is None:
        state = build()
    else:
        shardings = decode_state_shardings(
            config, batch_size, max_length, mesh, rules or (), rope_length=rope_length
        )
        state = jax.jit(build, out_shardings=shardings)()
    _publish_cache_bytes(state)
    return state


def _publish_cache_bytes(state: DecodeState) -> None:
    """Every cache construction lands its HBM footprint in telemetry
    (`decode/cache_bytes`) — callers used to re-publish this themselves,
    which left non-engine constructions (eval, serve warm-up) invisible in
    telemetry.jsonl and `report`."""
    from llm_training_tpu.telemetry import get_registry

    get_registry().gauge("decode/cache_bytes").set(cache_bytes(state))


def cache_bytes(state: DecodeState) -> int:
    """Global HBM footprint of the cache buffers (the `decode/cache_bytes`
    gauge)."""
    return sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in (state.k, state.v, state.state, state.conv, state.window_k, state.window_v)
        if leaf is not None
    )
