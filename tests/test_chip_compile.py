"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached (guide `on-chip-measurement` §2.3).

The interpreter enforces none of Mosaic's rules — block tiling, VMEM, that a
kernel cannot be partitioned by GSPMD — so a kernel can pass every
interpret-mode test and still be refused by the chip's compiler. These
compiles run what that compiler runs, at the real serving/training widths,
on the CPU sandbox: a refusal fails here and costs no chip time. Nothing
executes, so they say nothing about results or speed.

One file, one process: a second process asking for the topology while this
one holds it aborts on libtpu's multi-process lock file (tier-1 runs
`-p no:xdist`). Where the topology cannot be described, or the lock is held,
the compiles skip. The persistent compile cache is off for the whole test
process (tests/conftest.py): such an entry could be written but never read
back without a chip.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from llm_training_tpu.ops.pallas import resolve_interpret
from llm_training_tpu.ops.pallas.flash_attention import flash_bwd_flat, flash_fwd_flat
from llm_training_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_prefill_attention,
)
from llm_training_tpu.telemetry.device import parse_hlo_kernels

# Llama-3.1-8B attention at the chip smoke's shape: 32 query / 8 kv heads of
# 128, sequence 8192, bf16 (config/examples/smoke/chip-smoke.yaml)
SEQ, Q_HEADS, KV_HEADS, HEAD_DIM = 8192, 32, 8, 128


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or its lock file is held
        pytest.skip(f"no described v5e topology here: {type(e).__name__}: {str(e)[:200]}")


@pytest.fixture
def as_on_tpu(monkeypatch):
    """Steer the backend-keyed dispatch the chip's way: a described device
    leaves `jax.default_backend()` at 'cpu', which would pick the
    interpreter and skip the v5e tuning-table entries."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _kernels(fn, *shapes) -> dict:
    return parse_hlo_kernels(jax.jit(fn).lower(*shapes).compile().as_text())


def _flat_shapes(device):
    """Flat flash operands: q/do [B*Hq, S, D], k/v [B*Hkv, S, D], seg [B, S],
    lse/delta [B*Hq, S]."""
    one = SingleDeviceSharding(device)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    q = shape((Q_HEADS, SEQ, HEAD_DIM), jnp.bfloat16)
    kv = shape((KV_HEADS, SEQ, HEAD_DIM), jnp.bfloat16)
    seg = shape((1, SEQ), jnp.int32)
    row = shape((Q_HEADS, SEQ), jnp.float32)
    return q, kv, seg, row


_HEADS = dict(num_q_heads=Q_HEADS, num_kv_heads=KV_HEADS, scale=1.0, causal=True)


def test_flash_forward_compiles_for_v5e(v5e, as_on_tpu):
    q, kv, seg, _ = _flat_shapes(v5e.devices[0])
    found = _kernels(
        lambda q, k, v, sq, sk: flash_fwd_flat(q, k, v, sq, sk, **_HEADS),
        q, kv, kv, seg, seg,
    )
    assert found == {"flash_fwd": 1}, found


@pytest.mark.parametrize("kernel,outputs", [
    ("flash_bwd_dq", slice(0, 1)), ("flash_bwd_dkv", slice(1, 3)),
])
def test_flash_backward_compiles_for_v5e(v5e, as_on_tpu, kernel, outputs):
    """dq and dk/dv are separate pallas_calls; keeping only one's outputs
    lets the compiler drop the other, so each is compiled (and timed out of
    the other's way) on its own."""
    q, kv, seg, row = _flat_shapes(v5e.devices[0])
    found = _kernels(
        lambda q, k, v, sq, sk, do, lse, delta: flash_bwd_flat(
            q, k, v, sq, sk, do, lse, delta, **_HEADS
        )[outputs],
        q, kv, kv, seg, seg, q, row, row,
    )
    assert found == {kernel: 1}, found


@pytest.mark.parametrize("q_heads,kv_heads,page_size,batch,blocks,max_len", [
    pytest.param(Q_HEADS, KV_HEADS, 16, 4, 512, 2048, id="llama8b-page16"),
    pytest.param(Q_HEADS, KV_HEADS, 128, 4, 512, 2048, id="llama8b-page128"),
    # the two serve cells of BENCHMARK.json as the kernel sees them: 32 rows,
    # a table of 96 pages over a pool of 3,073 blocks; 10 kv heads in groups
    # of 4 (not a power of two) and 16 heads of their own
    pytest.param(40, 10, 16, 32, 3073, 1536, id="phi3-medium-cell"),
    pytest.param(16, 16, 16, 32, 3073, 1536, id="olmoe-cell"),
])
def test_paged_decode_compiles_for_v5e(
    v5e, q_heads, kv_heads, page_size, batch, blocks, max_len
):
    """The serving decode kernel at head_dim 128 in bf16, at the page size
    `resolve_paged_block_size` picks (16) and one other. The seed's
    `[blocks, page, kv_heads, head_dim]` pool was refused here: a
    (1, page, 1, head_dim) block is not an (8, 128) tile of it. The pools go
    to the kernel in place, so the program may produce no copy of one."""
    one = SingleDeviceSharding(v5e.devices[0])
    pool = jax.ShapeDtypeStruct(
        (blocks, kv_heads, page_size, HEAD_DIM), jnp.bfloat16, sharding=one
    )
    text = jax.jit(
        lambda q, k, v, tables, lens: paged_decode_attention(
            q, k, v, tables, lens, interpret=False
        )
    ).lower(
        jax.ShapeDtypeStruct((batch, q_heads, HEAD_DIM), jnp.bfloat16, sharding=one),
        pool, pool,
        jax.ShapeDtypeStruct((batch, max_len // page_size), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one),
    ).compile().as_text()
    assert parse_hlo_kernels(text) == {"paged_decode": 1}
    pool_shape = f"= bf16[{blocks},{kv_heads},{page_size},{HEAD_DIM}]"
    produced = [
        line for line in text.splitlines()
        if pool_shape in line and " parameter(" not in line
    ]
    assert not produced, produced


def test_flash_compiles_on_a_sharded_mesh_for_v5e(v5e, as_on_tpu):
    """GSPMD refuses any sharded program that holds a bare Mosaic kernel
    ("Mosaic kernels cannot be automatically partitioned") — every fsdp or
    tensor-parallel fit on a TPU. `dot_product_attention` therefore runs the
    kernel in a shard_map over the active mesh; this is the compile that
    refusal came from, on fsdp=2 x tensor=2."""
    import numpy as np

    from llm_training_tpu.ops.attention import dot_product_attention
    from llm_training_tpu.parallel.mesh import MESH_AXIS_NAMES

    mesh = Mesh(np.asarray(v5e.devices).reshape(1, 1, 2, 1, 2, 1), MESH_AXIS_NAMES)
    qkv = NamedSharding(mesh, P("fsdp", None, "tensor", None))

    def shape(heads):
        return jax.ShapeDtypeStruct((4, 2048, heads, HEAD_DIM), jnp.bfloat16, sharding=qkv)

    seg = jax.ShapeDtypeStruct(
        (4, 2048), jnp.int32, sharding=NamedSharding(mesh, P("fsdp", None))
    )
    with mesh:
        found = _kernels(
            lambda q, k, v, seg: dot_product_attention(
                q, k, v, segment_ids=seg, impl="auto"
            ),
            shape(Q_HEADS), shape(KV_HEADS), shape(KV_HEADS), seg,
        )
    assert found == {"flash_fwd": 1}, found


def test_paged_decode_compiles_on_a_sharded_mesh_for_v5e(v5e, as_on_tpu):
    """The serving twin of the test above: `paged_cached_attention` with kv
    heads sharded over `tensor`, as the pool is under a serving mesh."""
    import numpy as np

    from llm_training_tpu.ops.paged_attention import paged_cached_attention
    from llm_training_tpu.parallel.mesh import MESH_AXIS_NAMES

    mesh = Mesh(np.asarray(v5e.devices).reshape(1, 1, 2, 1, 2, 1), MESH_AXIS_NAMES)

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    batch, blocks, page, pages = 4, 512, 16, 128
    heads = P(None, None, "tensor", None)
    pool = shape((blocks, KV_HEADS, page, HEAD_DIM), jnp.bfloat16, P(None, "tensor", None, None))
    with mesh:
        found = _kernels(
            lambda q, k, v, pk, pv, lens, tables: paged_cached_attention(
                q, k, v, (pk, pv), lens, tables, impl="auto"
            )[0],
            shape((batch, 1, Q_HEADS, HEAD_DIM), jnp.bfloat16, heads),
            shape((batch, 1, KV_HEADS, HEAD_DIM), jnp.bfloat16, heads),
            shape((batch, 1, KV_HEADS, HEAD_DIM), jnp.bfloat16, heads),
            pool, pool,
            shape((batch,), jnp.int32, P()), shape((batch, pages), jnp.int32, P()),
        )
    # the append's page writer and the decode kernel, each in its shard_map
    assert found == {"kv_page_write": 1, "paged_decode": 1}, found


def test_paged_decode_refuses_untileable_shapes_when_compiled():
    """Small head_dims (the CPU smoke's 8, 16) need not compile for the
    chip, but must fail with a clear error there, not be routed elsewhere."""
    q = jnp.zeros((2, 4, 16))
    pool = jnp.zeros((5, 2, 8, 16))
    tables = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="head_dim 16"):
        paged_decode_attention(q, pool, pool, tables, jnp.ones((2,), jnp.int32),
                               interpret=False)


@pytest.mark.parametrize("q_heads,kv_heads,blocks,pages,window,ring", [
    # the serve cells of BENCHMARK.json as the chunk kernel sees them: one row,
    # 512 queries, kv heads x group x table width
    pytest.param(32, 4, 4 * 12801, 800, None, False, id="trinity-global-4x8x12800"),
    pytest.param(32, 4, 12 * 2577, 161, 2048, True, id="trinity-window-ring-2576"),
    pytest.param(40, 10, 13 * 3073, 96, 2047, False, id="phi3-10x4x1536"),
    pytest.param(16, 16, 9 * 3073, 96, None, False, id="olmoe-16x1x1536"),
    pytest.param(64, 8, 3073, 192, None, False, id="solar-8x8x3072"),
])
def test_paged_prefill_compiles_for_v5e(v5e, q_heads, kv_heads, blocks, pages, window, ring):
    """The chunk attention kernel at head_dim 128, pages of 16, bf16: the
    pools go to it in place (a layer stack seen as one pool), and nothing as
    wide as the table comes out of the program, in float32 or gathered."""
    import re

    one = SingleDeviceSharding(v5e.devices[0])
    pool = jax.ShapeDtypeStruct((blocks, kv_heads, 16, HEAD_DIM), jnp.bfloat16, sharding=one)
    compiled = jax.jit(
        lambda q, k, v, tables, lens: paged_prefill_attention(
            q, k, v, tables, lens, sliding_window=window, ring=ring, interpret=False
        )
    ).lower(
        jax.ShapeDtypeStruct((1, 512, q_heads, HEAD_DIM), jnp.bfloat16, sharding=one),
        pool, pool,
        jax.ShapeDtypeStruct((1, pages), jnp.int32, sharding=one),
        jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one),
    ).compile()
    text = compiled.as_text()
    assert parse_hlo_kernels(text) == {"paged_prefill": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 20e6  # q and the output, regrouped
    produced = [
        line for line in text.splitlines()
        if " parameter(" not in line and (
            f"= bf16[{blocks},{kv_heads},16,{HEAD_DIM}]" in line
            or re.search(rf"= \w+\[[\d,]*\b{pages * 16}\b[\d,]*\]", line)
        )
    ]
    assert not produced, produced


def test_paged_prefill_compiles_on_a_sharded_mesh_for_v5e(v5e, as_on_tpu):
    """A chunk through `paged_cached_attention` with kv heads sharded over
    `tensor`: the page writer and the chunk kernel, each in its shard_map
    (`_over_heads`), as the decode kernel above."""
    import numpy as np

    from llm_training_tpu.ops.paged_attention import paged_cached_attention
    from llm_training_tpu.parallel.mesh import MESH_AXIS_NAMES

    mesh = Mesh(np.asarray(v5e.devices).reshape(1, 1, 2, 1, 2, 1), MESH_AXIS_NAMES)

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    batch, seq, blocks, page, pages = 2, 512, 512, 16, 128
    heads = P(None, None, "tensor", None)
    pool = shape((blocks, KV_HEADS, page, HEAD_DIM), jnp.bfloat16, P(None, "tensor", None, None))
    with mesh:
        found = _kernels(
            lambda q, k, v, pk, pv, lens, tables, seg: paged_cached_attention(
                q, k, v, (pk, pv), lens, tables, segment_ids=seg, impl="auto"
            )[0],
            shape((batch, seq, Q_HEADS, HEAD_DIM), jnp.bfloat16, heads),
            shape((batch, seq, KV_HEADS, HEAD_DIM), jnp.bfloat16, heads),
            shape((batch, seq, KV_HEADS, HEAD_DIM), jnp.bfloat16, heads),
            pool, pool,
            shape((batch,), jnp.int32, P()), shape((batch, pages), jnp.int32, P()),
            shape((batch, seq), jnp.int32, P()),
        )
    assert found == {"kv_page_write": 1, "paged_prefill": 1}, found


def test_paged_prefill_refuses_untileable_shapes_when_compiled():
    """As the decode kernel: a head_dim or page Mosaic cannot tile raises on
    the chip's path, and is not routed to the gather path."""
    q = jnp.zeros((2, 8, 4, 16))
    pool = jnp.zeros((5, 2, 8, 16))
    tables = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="paged-prefill kernel .* head_dim 16"):
        paged_prefill_attention(q, pool, pool, tables, jnp.ones((2,), jnp.int32), interpret=False)


@pytest.mark.parametrize("heads,blocks,pages", [
    # the two latent serve cells as the chunk kernel sees them: one row, 512
    # queries, every MLA block's pool seen as one, the table's width
    pytest.param(128, 5 * (32 * 544 + 1), 544, id="pangu-128-heads-8704"),
    pytest.param(64, 8 * (32 * 352 + 1), 352, id="longcat-64-heads-5632"),
])
def test_mla_prefill_compiles_for_v5e(v5e, heads, blocks, pages):
    """The latent chunk kernel at rows of 640 (512 + 64 and zeros), heads of
    128 + 64 / 128, pages of 16, bf16: the pool goes to it in place, 16 heads a
    grid step by the shapes, and no float32 array of a trip's scores, of its
    statistics or of all heads' accumulators comes out of the program, nor
    one with more cached tokens than a trip for an axis."""
    import re

    from llm_training_tpu.ops.pallas.mla_prefill import head_block, mla_prefill_attention

    assert head_block(heads, 512, 512, 128, 128, 128, 2) == 16
    one = SingleDeviceSharding(v5e.devices[0])
    shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    compiled = jax.jit(
        lambda q_nope, q_rope, w_kvb, pool, tables, lens: mla_prefill_attention(
            q_nope, q_rope, w_kvb, pool, tables, lens, scale=192 ** -0.5, interpret=False
        )
    ).lower(
        shape((1, 512, heads, 128)), shape((1, 512, heads, 64)), shape((512, heads, 256)),
        shape((blocks, 1, 16, 640)), shape((1, pages), jnp.int32), shape((1,), jnp.int32),
    ).compile()
    text = compiled.as_text()
    assert parse_hlo_kernels(text) == {"mla_prefill": 1}
    # the queries and the output laid out a block of heads abreast: copies, no more
    assert compiled.memory_analysis().temp_size_in_bytes < 0.3e6 * heads
    produced = [
        line for line in text.splitlines()
        if " parameter(" not in line and (
            f"= bf16[{blocks},1,16,640]" in line
            or re.search(rf"= f32\[(?:1,)?{heads},512(?:,\d+)?\]", line)
            or re.search(rf"= \w+\[[\d,]*\b{pages * 16}\b[\d,]*\]", line)
        )
    ]
    assert not produced, produced


def test_mla_prefill_compiles_on_a_sharded_mesh_for_v5e(v5e, as_on_tpu):
    """A chunk through `paged_latent_attention` with the heads sharded over
    `tensor`: the page writer and the chunk kernel, each in its shard_map
    (`_over_heads`; the pool has no head axis and is replicated), 32 heads a
    chip of LongCat's 64."""
    import numpy as np

    from llm_training_tpu.ops.latent_attention import paged_latent_attention
    from llm_training_tpu.parallel.mesh import MESH_AXIS_NAMES

    mesh = Mesh(np.asarray(v5e.devices).reshape(1, 1, 2, 1, 2, 1), MESH_AXIS_NAMES)

    def shape(dims, dtype, spec):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=NamedSharding(mesh, spec))

    batch, seq, heads, blocks, pages = 2, 512, 64, 512, 128
    by_head = P(None, None, "tensor", None)
    with mesh:
        found = _kernels(
            lambda q_nope, q_rope, row, w_kvb, pool, lens, tables, seg: paged_latent_attention(
                q_nope, q_rope, row, w_kvb, pool, lens, tables, segment_ids=seg,
                scale=192 ** -0.5, impl="auto",
            )[0],
            shape((batch, seq, heads, 128), jnp.bfloat16, by_head),
            shape((batch, seq, heads, 64), jnp.bfloat16, by_head),
            shape((batch, seq, 640), jnp.bfloat16, P()),
            shape((512, heads, 256), jnp.bfloat16, P(None, "tensor", None)),
            shape((blocks, 1, 16, 640), jnp.bfloat16, P()),
            shape((batch,), jnp.int32, P()), shape((batch, pages), jnp.int32, P()),
            shape((batch, seq), jnp.int32, P()),
        )
    assert found == {"latent_page_write": 1, "mla_prefill": 1}, found


def test_mla_prefill_refuses_untileable_shapes_when_compiled():
    """As the paged kernels: a width Mosaic cannot tile raises on the chip's
    path, and is not routed to the XLA path."""
    from llm_training_tpu.ops.pallas.mla_prefill import mla_prefill_attention

    with pytest.raises(ValueError, match="mla_prefill kernel .* tail 64"):
        mla_prefill_attention(
            jnp.zeros((1, 16, 4, 128)), jnp.zeros((1, 16, 4, 64)), jnp.zeros((512, 4, 256)),
            jnp.zeros((3, 1, 16, 576)), jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            scale=1.0, interpret=False,
        )


@pytest.mark.parametrize("layers,stored,key_dim,heads,value_dim,per_channel", [
    pytest.param(3, 64, 128, 64, 128, True, id="kda-solar2-serve-longdoc"),
    pytest.param(6, 15, 96, 30, 192, False, id="gated-olmoh-serve-longgen-two-abreast"),
])
def test_delta_step_compiles_for_v5e(v5e, layers, stored, key_dim, heads, value_dim, per_channel):
    """The one-token delta rules' kernel at the two serve cells' slabs (32
    slots): it fits VMEM at the table's block, the slab is its own result
    (nothing of its size is made), and its module holds a trip's stored heads,
    a loop over the block, not the block's."""
    import math

    from llm_training_tpu.ops.pallas import delta_step as kernel
    from llm_training_tpu.ops.pallas.delta_step import delta_step
    from llm_training_tpu.ops.pallas.tuning import delta_step_heads

    rows, lanes = 32, heads // stored * value_dim
    one = SingleDeviceSharding(v5e.devices[0])
    shape = lambda *dims, dtype=jnp.float32: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    args = (
        shape(layers, rows, stored, key_dim, lanes), shape(dtype=jnp.int32),
        shape(rows, heads, key_dim), shape(rows, heads, key_dim), shape(rows, heads, value_dim),
        shape(rows, heads, key_dim) if per_channel else shape(rows, heads), shape(rows, heads),
    )
    block = delta_step_heads(stored, key_dim, lanes, (3 if per_channel else 2) * (heads // stored))
    lowered = jax.jit(
        lambda *a: delta_step(*a, block=block, interpret=False), donate_argnums=0
    ).lower(*args)
    compiled = lowered.compile()
    assert parse_hlo_kernels(compiled.as_text()).get("delta_step") == 1
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == layers * rows * stored * key_dim * lanes * 4
    assert memory.temp_size_in_bytes < 1e6
    (module,) = _mosaic_modules(lowered)
    text = module.operation.get_asm(enable_debug_info=False)
    # the block's stored heads are a loop of trips of `_HELD` heads (those of
    # them that divide the block): a stored head's state is written by one
    # store a head of a TRIP, not one a head of the block
    held = math.gcd(kernel._HELD, block)
    assert text.count("scf.for") == (1 if held < block else 0)
    state = f"memref<1x1x{block}x{key_dim}x{lanes}xf32"
    assert sum("vector_store" in line and state in line for line in text.splitlines()) == held


def test_interpret_is_impossible_on_a_tpu_backend(monkeypatch):
    assert resolve_interpret(None) is True  # the CPU test path
    assert resolve_interpret(False) is False  # compiling for a described device
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret(None) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        resolve_interpret(True)


# ------------------------------------------------------- the serve cells
#
# Both serving programs of every serve cell (BENCHMARK.json), at the cell's
# own configuration and traffic files, compiled for the v5e. What is pinned:
# they fit the chip's 16 GB beside the weights, the decode step keeps one
# `paged_decode` call a pool layer, the caches are aliased in place, and how
# many arrays of a pool's, the stacked pools' or a state slab's shape each
# program PRODUCES on the way: in every computation it runs (the entry, the
# layer loop's body), views and in-place writes left out. Since PR 28 the
# caches ride the layer loop as its carry and the append writes whole pages
# in place, so no program produces a pool at all; the parent's counts are in
# CHANGES.md (PR 28).

# program -> {shape kind: arrays produced}; "state" is a KDA layer's (or all
# three layers') float32 states. A decode step advances them where they lie,
# in the `delta_step` kernel, whose result IS its operand (`_produced` leaves
# an aliased custom call out): none since PR 47 (6 until then: each layer's
# new state, 134 MB, written and then copied into the carried slab). A chunk
# scatters one slot a layer in place (7 -> 3).
_PRODUCED = {
    "phi3m-serve-rollout": {"decode": {"pool": 0, "stack": 0}, "prefill": {"pool": 0, "stack": 0}},
    # "experts": one layer's expert matrices `[E, in, out]`. Until PR 31 the
    # scan cut each of the three out of the stacked parameter by copy, once a
    # layer; the grouped matmul now reads them inside the stack
    "olmoe-serve-rollout": {
        "decode": {"pool": 0, "stack": 0, "experts": 0},
        "prefill": {"pool": 0, "stack": 0, "experts": 0},
    },
    "solar2-serve-longdoc": {
        "decode": {"pool": 0, "stack": 0, "state": 0},
        "prefill": {"pool": 0, "stack": 0, "state": 3},
    },
    # six delta-rule layers in two periods: a call site a layer of the
    # period. A chunk's is an update of the WHOLE carried slab in place
    # (`_IN_PLACE` below holds that they are, and that no layer's states are
    # made); a decode step's is the kernel's aliased result (3 until PR 47)
    "olmoh-serve-longgen": {
        "decode": {"pool": 0, "stack": 0, "state": 0},
        "prefill": {"pool": 0, "stack": 0, "state": 3},
    },
}
# (layers a loop of the program runs, `gmm` calls a program): every expert
# layer's three products in the grouped matmul that skips what has no rows,
# a one-period scan's (Solar's, since PR 43) like a scan of nine layers
_GMM_CALLS = {"olmoe-serve-rollout": (9, 3 * 9), "solar2-serve-longdoc": (1, 3 * 4)}
# cells (and their programs) that update a slot's state where it lies (the
# `delta_step` kernel, or `LayerCache.put_recurrent_rows(in_place=True)`):
# every array of the slab's shape a program produces is the kernel's aliased
# result or a fusion rooted in a dynamic-update-slice of its own operand, and
# none has the shape of one layer's states
_IN_PLACE = {
    "olmoh-serve-longgen": ("decode", "prefill"),
    "solar2-serve-longdoc": ("decode",),  # a chunk scatters one picked slot
}
# (layers a loop of the program runs, `delta_step` calls a decode step): every
# delta-rule layer's one-token step in the kernel, under its recurrence's scope
_DELTA_STEP_CALLS = {
    "solar2-serve-longdoc": (1, 3, "kda_recurrence"),
    "olmoh-serve-longgen": (2, 6, "gdn_recurrence"),
}
# the programs' temporaries, GB, which hold that Solar's slab updates ARE in
# place (one more copy of a layer's states is 0.13 GB, of a pool as much).
# OLMoE's chunk holds its expert activations (0.013 GB; one layer's expert
# weights cut out of the stack, 0.27 GB, until PR 31), Solar's chunk one
# layer's new state (0.134 GB; its step too until PR 47, 0.074 GB since: the
# kernel writes where the state lies). No chunk holds attention scores since
# PR 35 (`paged_prefill`; before it Phi-3's held 0.126 GB, Solar's 0.40)
_TEMP_GB = {
    "phi3m-serve-rollout": {"decode": 0.01, "prefill": 0.02},
    "olmoe-serve-rollout": {"decode": 0.01, "prefill": 0.03},
    "solar2-serve-longdoc": {"decode": 0.1, "prefill": 0.2},
    # one layer's states are 0.071 GB: the step holds none. The chunk's
    # temporaries are its float32 logits' and the chunked rule's
    "olmoh-serve-longgen": {"decode": 0.03, "prefill": 1.0},
}
_NOT_PRODUCED = (
    "parameter", "bitcast", "get-tuple-element", "tuple", "while", "conditional", "call",
)
# the compiler's own asynchronous prefetch of a weight ahead of its reader (into
# memory space `S(1)`, the layout kept; a `ConcatBitcast` joins its pieces): no
# copy the program asked for (Olmo-Hybrid's `slice-done bf16[1,3840,5760]`)
_PREFETCH = ("copy-start", "copy-done", "slice-start", "slice-done")


def _run_computations(text):
    """{name: lines} of the computations a compiled program runs instruction
    by instruction: the entry, loop bodies and conditions, branches. A fused
    computation is one device op, counted where it is called."""
    import re

    computations, fused, name = {}, set(), None
    for line in text.splitlines():
        opening = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if opening and not line.startswith(" "):
            name = opening.group(1)
            computations[name] = []
        elif name is not None:
            computations[name].append(line)
            if " fusion(" in line or "to_apply=" in line:
                fused.update(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line))
    return {k: v for k, v in computations.items() if k not in fused}


def _produced(text, pattern, prefetch=True):
    """Arrays whose shape matches `pattern` that some instruction of the
    program produces. Left out: views (`_NOT_PRODUCED`), a custom call whose
    outputs alias its operands (the page writer: the same memory) and, with
    `prefetch=False`, the compiler's asynchronous prefetch (`_PREFETCH`)."""
    import re

    skipped = _NOT_PRODUCED if prefetch else _NOT_PRODUCED + _PREFETCH
    total = 0
    for lines in _run_computations(text).values():
        for line in lines:
            _, found, rest = line.partition(" = ")
            kind = re.match(r"(?:\(.*?\)|\S+) ([\w\-]+)\(", rest)
            if not found or not kind or kind.group(1) in skipped:
                continue
            if kind.group(1) == "custom-call" and "output_to_operand_aliasing=" in rest:
                continue
            if not prefetch and 'custom_call_target="ConcatBitcast"' in rest:
                continue
            total += len(re.findall(pattern, rest[: kind.start(1)]))
    return total


def _projection_slices(text, config):
    """Arrays a compiled serving program produces that have the shape of a
    head projection's slice of the layer stack, `bf16[1, in, out]`, or of a
    looped layer's own leaf, `bf16[in, out]`: the leaves whose product is
    reshaped to heads and goes through `models/llama/model.py:_plain_rows`
    (`q_proj`, `k_proj`, `v_proj`, Trinity's `gate_proj`; `q_b_proj` of a
    latent stack), widths from the cell's configuration. Each such array was
    the leaf cut out of the stack or transposed for a product that wanted it
    `[heads, head_dim, in]`, once a layer a step (PR 48); 0 says every such
    product reads its weight where it lies."""
    heads = config["num_attention_heads"]
    if config.get("q_lora_rank"):
        wide = heads * (config["qk_nope_head_dim"] + config["qk_rope_head_dim"])
        leaves = {(config["q_lora_rank"], wide)}
    else:
        dim = config.get("head_dim") or config["hidden_size"] // heads
        leaves = {(config["hidden_size"], n * dim) for n in (heads, config["num_key_value_heads"])}
    shapes = "|".join(f"{rows},{out}" for rows, out in sorted(leaves))
    return _produced(text, rf"bf16\[(?:1,)?(?:{shapes})\]", prefetch=False)


def _kv_b_slices(text, config):
    """What PR 48 LEFT in a latent stack's programs: arrays of `kv_b_proj`'s
    shape, `[latent, heads * (nope + v)]` or LongCat's `[latent, heads, nope +
    v]`. It is a weight with a head axis, a batch dimension of the absorbed
    products, which want it heads-major: no product's result to put a barrier
    on, and the einsum's dimension order does not move it."""
    latent, heads = config["kv_lora_rank"], config["num_attention_heads"]
    width = config["qk_nope_head_dim"] + config["v_head_dim"]
    shapes = rf"bf16\[(?:1,)?{latent},(?:{heads * width}|{heads},{width})\]"
    return _produced(text, shapes, prefetch=False)


def _cell_config(cell):
    from pathlib import Path

    from benchmarks import common

    return common.Cell(Path(__file__).resolve().parent.parent, cell).config


def _updates_in_place(text, pattern):
    """Whether every array matching `pattern` that the program produces is
    the result of a fusion whose root is a dynamic-update-slice, or of a
    custom call that aliases it to an operand: an update of its operand's own
    memory."""
    import re

    roots = dict(re.findall(r"^%?([\w.\-]+) \([^\n]*\{\n(?:[^\n]*\n)*?\s*ROOT [^\n]*? ([\w\-]+)\(", text, re.M))
    for lines in _run_computations(text).values():
        for line in lines:
            _, found, rest = line.partition(" = ")
            kind = re.match(r"(?:\(.*?\)|\S+) ([\w\-]+)\(", rest)
            if not found or not kind or kind.group(1) in _NOT_PRODUCED:
                continue
            if not re.search(pattern, rest[: kind.start(1)]):
                continue
            if kind.group(1) == "custom-call" and "output_to_operand_aliasing=" in rest:
                continue  # a kernel's aliased result: the same memory
            called = re.search(r"calls=%?([\w.\-]+)", rest)
            if kind.group(1) != "fusion" or not called or roots.get(called.group(1)) != "dynamic-update-slice":
                return False
    return True


def _kernel_calls(text, kernel, repeats, under=""):
    """How often a compiled program calls a Mosaic kernel (under a named
    scope, where given): a call site in a loop's body runs `repeats` times, any
    other once."""
    import re

    sites = {
        name: sum(kernel in line and "tpu_custom_call" in line and under in line for line in lines)
        for name, lines in _run_computations(text).items()
    }
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))
    return sum(n * (repeats if name in bodies else 1) for name, n in sites.items())


def _mosaic_modules(lowered):
    """The Mosaic kernels a lowered program's text holds, each parsed: one a
    `tpu_custom_call` of the text, so a kernel called through one jitted
    function is there ONCE however many its call sites."""
    import base64
    import re

    from jax._src.lib.mlir import ir

    bodies = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered.as_text())
    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        return [ir.Module.parse(base64.b64decode(body)) for body in bodies]


def _lowered_bodies(lowered, kernel):
    """How many Mosaic modules named `kernel` (a kernel's `name=`) a lowered
    program's text holds."""
    from jax._src.lib.mlir import ir

    return sum(
        ir.StringAttr(module.operation.attributes["sym_name"]).value == kernel
        for module in _mosaic_modules(lowered)
    )


def _serve_program(v5e, cell, program):
    """(lowered program, the engine's cache shapes) of a serve cell."""
    from pathlib import Path

    import flax.linen as nn

    from benchmarks import common
    from llm_training_tpu.serve.engine import ServeConfig, ServingEngine

    found = common.Cell(Path(__file__).resolve().parent.parent, cell)
    serve = found.traffic["engine"]
    model = common.build_model(found.config)
    one = SingleDeviceSharding(v5e.devices[0])
    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree
    )
    shape = lambda dims, dtype=jnp.int32: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    variables = on(nn.meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )))
    engine = ServingEngine(model, None, ServeConfig(**serve))  # its caches give the shapes
    pool, slab = on(engine._pool_k), on(engine._slab)
    pool_v = None if engine._pool_v is None else pool  # a latent stack has ONE pool
    key = on(jax.eval_shape(lambda: jax.random.key(0)))
    slab_rows = {} if slab is None else {"slab": slab}
    if engine._window_pool is not None:  # the layers that keep a window: a pool (its short table is packed)
        slab_rows["window_pool"] = on(engine._window_pool)
    # a call's int32 inputs travel packed (lengths, tables, the call index: `_decode_fields`);
    # each slot's last token stays on the device, an array of its own
    jitted, packed = (
        (engine._decode_jit, engine._decode_packed) if program == "decode"
        else (engine._prefill_jit, engine._prefill_packed)
    )
    lowered = jitted.lower(
        variables, shape(packed.shape), pool, pool_v, key, shape(engine._last_tokens.shape), **slab_rows
    )
    engine.close()
    return lowered, pool, slab


def _check_serve_program(v5e, cell, program):
    lowered, pool, slab = _serve_program(v5e, cell, program)
    compiled = lowered.compile()  # raises what the chip's compiler would: it fits
    text, memory = compiled.as_text(), compiled.memory_analysis()
    layers, blocks, *page = pool.shape
    dims = ",".join(str(d) for d in page)
    patterns = {
        "pool": rf"bf16\[(?:1,)?{blocks},{dims}\]",
        # the stack, as it is declared and as the append sees it (one run of blocks)
        "stack": rf"bf16\[(?:{layers},{blocks}|{layers * blocks}),{dims}\]" if layers > 1 else None,
    }
    config = _cell_config(cell)
    if "experts" in _PRODUCED[cell][program]:
        experts, wide = config["num_experts"], config["hidden_size"]
        narrow = config["program"]["model_kwargs"]["moe_intermediate_size"]
        patterns["experts"] = rf"bf16\[(?:1,)?{experts},(?:{wide},{narrow}|{narrow},{wide})\]"
    caches = 2 * pool.size * 2
    if slab is not None:
        state, tail = slab
        # a layer's states, every layer's, and every layer's as one run of slots
        layers_slots = f"{state.shape[0]},{state.shape[1]}|{state.shape[0] * state.shape[1]}"
        patterns["state"] = (
            rf"f32\[(?:(?:1,)?{state.shape[1]}|{layers_slots}),"
            + ",".join(str(d) for d in state.shape[2:]) + r"\]"
        )
        caches += state.size * 4 + tail.size * 2
    assert memory.alias_size_in_bytes >= caches  # pools and slab are written in place
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9
    assert memory.temp_size_in_bytes < _TEMP_GB[cell][program] * 1e9

    # one `paged_decode` call a pool layer: a call site in the layer loop's
    # body runs once a layer; an unrolled loop has a call site a layer
    assert _kernel_calls(text, "paged_decode", layers) == (layers if program == "decode" else 0)
    # and a chunk attends in `paged_prefill`, once a pool layer
    assert _kernel_calls(text, "paged_prefill", layers) == (0 if program == "decode" else layers)
    assert parse_hlo_kernels(text).get("kv_page_write", 0) >= 1  # the append's writer
    # no latent cache here: none of the latent kernels, nor their page writer
    assert not {"mla_prefill", "mla_decode", "latent_page_write"} & set(parse_hlo_kernels(text))

    if cell in _GMM_CALLS:
        repeats, calls = _GMM_CALLS[cell]
        assert _kernel_calls(text, "moe_experts/jit(gmm)", repeats) == calls and "ragged-dot" not in text
        _check_combine_gathers(text)

    counts = {k: _produced(text, p) for k, p in patterns.items() if p}
    counts.setdefault("stack", 0)
    print(f"{cell} {program}: produced {counts}, temp {memory.temp_size_in_bytes / 1e9:.3f} GB")
    assert counts == _PRODUCED[cell][program], counts
    # no head projection's leaf cut out of the stack or transposed for its
    # product (until PR 48 Phi-3: q_proj and k_proj, slice and transpose, 4 a
    # program; OLMoE, Olmo-Hybrid: none, a full-width norm stands before the
    # head reshape; Solar: its one looped GQA layer's q_proj went `[out, in]`)
    assert _projection_slices(text, config) == 0
    if cell in _DELTA_STEP_CALLS:
        # the one-token steps run in the kernel, each under its recurrence's
        # scope (where `kda_decode_roofline_pct` / `gdn_decode_roofline_pct`
        # find it), and the layers share ONE lowered body: what a process
        # start traces and lowers does not grow with the call sites
        repeats, calls, scope = _DELTA_STEP_CALLS[cell]
        calls = calls if program == "decode" else 0
        assert _kernel_calls(text, "delta_step", repeats, under=f"{scope}/jit(delta_step)") == calls
        assert _lowered_bodies(lowered, "delta_step") == min(calls, 1)
    if program in _IN_PLACE.get(cell, ()):
        state = slab[0]
        rest = ",".join(str(d) for d in state.shape[2:])
        assert _produced(text, rf"f32\[(?:1,)?{state.shape[1]},{rest}\]") == 0  # no layer's states
        assert _updates_in_place(text, patterns["state"])


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_solar_open2_serve_cell_compiles_for_v5e(v5e, as_on_tpu, program):
    _check_serve_program(v5e, "solar2-serve-longdoc", program)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_olmo_hybrid_serve_cell_writes_its_state_in_place_for_v5e(v5e, as_on_tpu, program):
    """`olmoh-serve-longgen`: 8 layers at the published widths fit the chip (12
    do too; it is the check's reference that stops the cell at 8: PERF.md),
    `paged_decode` and `paged_prefill` take 30 key/value heads with a group of
    ONE query head, and the slab (`[6, 32, 15, 96, 384]` float32, two heads
    abreast) is carried and written in place: the step holds no second array
    of a layer's states."""
    _check_serve_program(v5e, "olmoh-serve-longgen", program)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("cell", ["phi3m-serve-rollout", "olmoe-serve-rollout"])
def test_rollout_cells_update_the_pool_in_place_for_v5e(v5e, as_on_tpu, cell, program):
    _check_serve_program(v5e, cell, program)


# ------------------------- a third slab whose programs the delta rules' kernel
# must not move: Phi-4-mini-flash's state-space layers read and write theirs
# through the same `LayerCache.recurrent_rows / put_recurrent_rows` (ROADMAP
# S13's rest: `ssm_step` stays XLA). Its two lowered programs, every Mosaic
# kernel's body printed without its source locations (they hold the line
# numbers of `models/cache.py`'s frames), by digest: PR 48's own, which put
# the attention layers' q, k and v projections behind `_plain_rows`'s barrier
# (until then the parent's of PR 47, `1665a1a0...` and `4736d805...`, taken
# from `git archive` of it with this same function). A PR that means to
# change these programs replaces the digests and says so.
_PHI4FLASH_PROGRAMS = {
    "decode": "12d3ba0df046aaa4a0ef95beabe804613f7328442df3ac607e2e7bd5e005b58b",
    "prefill": "4bfd1f6fd2824beff51222a3c78e20d58b0b4bce93179491228696a165cb1842",
}


def _program_digest(lowered):
    import hashlib
    import re

    bodies = iter(_mosaic_modules(lowered))
    text = re.sub(
        r'\\22body\\22: \\22[A-Za-z0-9+/=]+\\22',
        lambda _: next(bodies).operation.get_asm(enable_debug_info=False),
        lowered.as_text(),
    )
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_phi4flash_serve_programs_are_the_text_they_were(v5e, as_on_tpu, program):
    lowered, _, slab = _serve_program(v5e, "phi4flash-serve-reasoning", program)
    assert slab is not None and _lowered_bodies(lowered, "delta_step") == 0
    assert _program_digest(lowered) == _PHI4FLASH_PROGRAMS[program]
    # compiled since PR 48 (11 and 14 s): the two scans' and the looped
    # layers' q_proj, k_proj and v_proj are read where they lie (5 arrays of
    # a leaf's shape a program until then)
    text = lowered.compile().as_text()
    assert _projection_slices(text, _cell_config("phi4flash-serve-reasoning")) == 0


# ------------------------------------------- the cell with a latent (MLA) pool
#
# `longcat-serve-longctx`: ONE pool (a latent row a token for each of the 8 MLA
# blocks, `_pool_v` None), the held experts' stacked weights read in place by
# the grouped product. Pinned: both programs fit beside 10.3 GB of weights,
# the decode step holds its `mla_decode` kernel (two call sites in the layer
# loop's body: 8 calls a step), the append is the in-place page writer, and
# neither program produces an array of the pool's shape, of the stacked
# pools', or of one layer's expert matrices.


def _check_combine_gathers(text):
    """The experts' combine (`models/moe.py:_combine`) gathers the sorted rows
    and sums them: no `scatter` instruction under `moe_scatter` (until PR 50 a
    `scatter(bf16[512, hidden], s32[4096], bf16[4096, hidden])` a layer, which
    the chip walks a row at a time), and the scope still holds ops, the gather
    among them: `moe_dispatch_device_ms` reads it by that name."""
    under = [line for line in text.splitlines() if "moe_scatter" in line.partition(" metadata=")[2]]
    assert any(" gather(" in line for line in under)
    assert not [line for line in under if " scatter(" in line]


def _check_chunk_attends_in_mla_prefill(text, program, heads, blocks, repeats):
    """A chunk program calls `mla_prefill` once a latent block, under
    `mla_attend`, and the gauge says so; it holds no float32 array of a
    trip's scores `[heads, 512, 512]`, of their statistics `[heads, 512]` or
    of all heads' accumulators `[1, heads, 512, 128]`, and nothing under
    `mla_expand`: a trip's latents are expanded inside the kernel. A decode
    step calls it never."""
    import re

    from llm_training_tpu.telemetry import get_registry

    chunk = program == "prefill"
    assert _kernel_calls(text, "mla_prefill", repeats) == (blocks if chunk else 0)
    assert _kernel_calls(text, "mla_prefill", repeats, under="mla_attend") == (blocks if chunk else 0)
    if chunk:
        # set when the program was traced: every block of the stack
        assert get_registry().gauge("decode/chunk_attention_kernel_layers").value == blocks
        assert "mla_expand" not in text  # the expansion runs inside the kernel
        trips = re.compile(rf"= f32\[(?:1,)?{heads},512(?:,\d+)?\]")
        made = [
            line for line in text.splitlines()
            if " parameter(" not in line and trips.search(line.split(" metadata=")[0])
        ]
        assert not made, made[:3]


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_longcat_serve_cell_compiles_for_v5e(v5e, as_on_tpu, program):
    lowered, pool, slab = _serve_program(v5e, "longcat-serve-longctx", program)
    assert slab is None
    config = _cell_config("longcat-serve-longctx")
    compiled = lowered.compile()  # raises what the chip's compiler would: it fits
    text, memory = compiled.as_text(), compiled.memory_analysis()
    layers, blocks, *page = pool.shape
    assert (layers, blocks, page) == (8, 32 * 352 + 1, [1, 16, 640])
    dims = ",".join(str(d) for d in page)
    wide, narrow = config["hidden_size"], config["expert_ffn_hidden_size"]
    patterns = {
        "pool": rf"bf16\[(?:1,)?{blocks},{dims}\]",
        "stack": rf"bf16\[(?:{layers},{blocks}|{layers * blocks}),{dims}\]",
        "experts": rf"bf16\[(?:1,)?16,(?:{wide},{narrow}|{narrow},{wide})\]",
    }
    counts = {k: _produced(text, p) for k, p in patterns.items()}
    print(f"longcat-serve-longctx {program}: produced {counts}, "
          f"temp {memory.temp_size_in_bytes / 1e9:.3f} GB, "
          f"arguments {memory.argument_size_in_bytes / 1e9:.3f} GB")
    assert counts == {"pool": 0, "stack": 0, "experts": 0}, counts
    # `q_b_proj` is read where it lies (4 a program until PR 48: two blocks'
    # slice and transpose); `kv_b_proj [512, 64, 256]` is still cut out and laid
    # heads-major for the absorbed products, two blocks a loop body
    assert _projection_slices(text, config) == 0
    assert _kv_b_slices(text, config) == {"decode": 4, "prefill": 3}[program]
    assert memory.alias_size_in_bytes >= pool.size * 2  # the pool is written in place
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9
    # a step's temporaries are its rows' activations, and so are a chunk's since
    # its attention runs in `mla_prefill` (0.093 GB; 0.2 were allowed until PR 42: a trip's
    # [64, 512, 512] float32 scores and the expanded keys and values of all heads)
    assert memory.temp_size_in_bytes < {"decode": 0.02, "prefill": 0.12}[program] * 1e9

    # two call sites in the layer loop's body: 8 calls a step, 8 a chunk
    assert _kernel_calls(text, "mla_decode", layers // 2) == (layers if program == "decode" else 0)
    _check_chunk_attends_in_mla_prefill(text, program, heads=64, blocks=layers, repeats=layers // 2)
    kernels = parse_hlo_kernels(text)
    assert kernels.get("latent_page_write", 0) >= 1 and kernels.get("gmm", 0) >= 3
    assert not {"kv_page_write", "paged_decode", "paged_prefill"} & set(kernels)
    _check_combine_gathers(text)


# ----------------------------- the latent pool at 128 heads, under `Deepseek`
#
# `pangu-serve-longctx8k`: ONE pool (a latent row a token for each of the 5
# layers' MLA blocks), the looped dense layer's block first and the scanned
# MoE suffix's four after it; the suffix's held experts go through `gmm` in
# place. Pinned: both programs fit beside 6.82 GB of weights; the decode step
# calls `mla_decode` once a block (one call site in the looped layer, one in
# the scan's body: 5 a step) at 128 heads; the append is the in-place page
# writer; neither program produces an array of the pool's shape, of the
# stacked pools', or of one layer's expert matrices; a chunk attends in
# `mla_prefill`, 5 calls, and holds no float32 array of a trip's scores.


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_pangu_serve_cell_compiles_for_v5e(v5e, as_on_tpu, program):
    lowered, pool, slab = _serve_program(v5e, "pangu-serve-longctx8k", program)
    assert slab is None
    config = _cell_config("pangu-serve-longctx8k")
    compiled = lowered.compile()  # raises what the chip's compiler would: it fits
    text, memory = compiled.as_text(), compiled.memory_analysis()
    layers, blocks, *page = pool.shape
    assert (layers, blocks, page) == (5, 32 * 544 + 1, [1, 16, 640])
    dims = ",".join(str(d) for d in page)
    wide, narrow = config["hidden_size"], config["moe_intermediate_size"]
    patterns = {
        "pool": rf"bf16\[(?:1,)?{blocks},{dims}\]",
        "stack": rf"bf16\[(?:{layers},{blocks}|{layers * blocks}),{dims}\]",
        "experts": rf"bf16\[(?:1,)?8,(?:{wide},{narrow}|{narrow},{wide})\]",
    }
    counts = {k: _produced(text, p) for k, p in patterns.items()}
    print(f"pangu-serve-longctx8k {program}: produced {counts}, "
          f"temp {memory.temp_size_in_bytes / 1e9:.3f} GB, "
          f"arguments {memory.argument_size_in_bytes / 1e9:.3f} GB")
    assert counts == {"pool": 0, "stack": 0, "experts": 0}, counts
    # `q_b_proj` is read where it lies, the scanned suffix's and the looped
    # layer's (3 a program until PR 48); `kv_b_proj [512, 32768]` is still cut
    # out and transposed for the absorbed products (the loop's slice and copy,
    # the looped layer's copy), and cut out once for a chunk's kernel
    assert _projection_slices(text, config) == 0
    assert _kv_b_slices(text, config) == {"decode": 3, "prefill": 1}[program]
    assert memory.alias_size_in_bytes >= pool.size * 2  # the pool is written in place
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9
    # (a chunk 0.087 GB; 0.175 until PR 42, 0.5 allowed: a trip's [128, 512, 512] float32 scores)
    assert memory.temp_size_in_bytes < {"decode": 0.1, "prefill": 0.12}[program] * 1e9

    # the looped dense layer's call, and one call site in the scan's body: 4 more
    in_loop = _kernel_calls(text, "mla_decode", 4)
    assert in_loop == (5 if program == "decode" else 0)
    _check_chunk_attends_in_mla_prefill(text, program, heads=128, blocks=5, repeats=4)
    kernels = parse_hlo_kernels(text)
    assert kernels.get("latent_page_write", 0) >= 1 and kernels.get("gmm", 0) >= 3
    assert not {"kv_page_write", "paged_decode", "paged_prefill"} & set(kernels)
    _check_combine_gathers(text)


# ------------------------------- a latent pool BESIDE a slab, under `GigaChat35`
#
# `gigachat35-serve-longgen-doctail`: ONE MLA layer's latent pool (64 rows of
# up to 10,240 tokens: 64 x 640 + 1 pages) and four delta-rule layers' slab
# (`[4, 64, 64, 128, 128]` float32, one head a stored head) in one engine.
# Pinned: both programs fit beside 9.46 GB of weights; the decode step calls
# `mla_decode` once (the scanned period's first slot) and `delta_step` four
# times (the looped dense layer's and three call sites in the period's body)
# under `gdn_recurrence`, one lowered body; the pool and the slab are written
# in place, and no program makes an array of a layer's states, of the pool's
# shape or of a layer's held experts; a chunk attends in `mla_prefill`, once.


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_gigachat35_serve_cell_holds_a_latent_pool_beside_a_slab_for_v5e(v5e, as_on_tpu, program):
    lowered, pool, slab = _serve_program(v5e, "gigachat35-serve-longgen-doctail", program)
    config = _cell_config("gigachat35-serve-longgen-doctail")
    state, tail = slab
    assert pool.shape == (1, 64 * 640 + 1, 1, 16, 640)
    assert state.shape == (4, 64, 64, 128, 128) and tail.shape == (4, 64, 3, 16384)
    compiled = lowered.compile()  # raises what the chip's compiler would: it fits
    text, memory = compiled.as_text(), compiled.memory_analysis()
    wide, narrow = config["hidden_size"], config["moe_intermediate_size"]
    patterns = {
        "pool": r"bf16\[(?:1,)?40961,1,16,640\]",
        "state": r"f32\[(?:(?:1,)?64|4,64|256),64,128,128\]",
        "experts": rf"bf16\[(?:1,)?16,(?:{wide},{narrow}|{narrow},{wide})\]",
    }
    counts = {k: _produced(text, p) for k, p in patterns.items()}
    print(f"gigachat35-serve-longgen-doctail {program}: produced {counts}, "
          f"temp {memory.temp_size_in_bytes / 1e9:.3f} GB, "
          f"arguments {memory.argument_size_in_bytes / 1e9:.3f} GB")
    # a chunk scatters ONE picked slot's new state a delta-rule layer (as Solar's chunk does)
    assert counts == {"pool": 0, "state": {"decode": 0, "prefill": 4}[program], "experts": 0}, counts
    assert memory.alias_size_in_bytes >= pool.size * 2 + state.size * 4 + tail.size * 2  # written in place
    assert 11.3e9 < memory.argument_size_in_bytes < 11.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14e9
    assert memory.temp_size_in_bytes < {"decode": 0.1, "prefill": 0.2}[program] * 1e9

    assert _kernel_calls(text, "mla_decode", 1) == (1 if program == "decode" else 0)
    _check_chunk_attends_in_mla_prefill(text, program, heads=64, blocks=1, repeats=1)
    calls = 4 if program == "decode" else 0
    assert _kernel_calls(text, "delta_step", 1, under="gdn_recurrence/jit(delta_step)") == calls
    assert _lowered_bodies(lowered, "delta_step") == min(calls, 1)
    kernels = parse_hlo_kernels(text)
    assert kernels.get("latent_page_write", 0) >= 1 and kernels.get("gmm", 0) >= 3
    assert not {"kv_page_write", "paged_decode", "paged_prefill"} & set(kernels)
    assert _projection_slices(text, config) == 0
    _check_combine_gathers(text)


# --------------------------------------------- the cell with two page groups
#
# `trinity-serve-mixedlen`: 4 layers keep every token of 16 requests of 12,800
# (a pool of 16 x 800 + 1 pages), 12 keep a window of 2,048 (a pool of 16 x 161
# + 1: window + one chunk of 512, in pages of 16, + 1). Pinned: both programs
# fit beside 4.23 GB of weights; both pools are written in place; a window
# layer never reads past its group's budget, whatever `max_model_len` is: no
# array computed under `attn_window` is 12,800 (or 800 pages) wide; the decode
# step calls `paged_decode` once a layer (16: 12 under `attn_window`, 4 under
# `attn_global`) and a chunk `paged_prefill` as often, so the chunk program
# holds no float32 scores 2,576 or 12,800 wide (until PR 35 it gathered each
# table whole: 0.89 GB of temporaries); the scanned periods' held experts go
# through `gmm` in place.


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_trinity_serve_cell_keeps_window_layers_inside_their_budget_for_v5e(v5e, as_on_tpu, program):
    import re

    from llm_training_tpu.telemetry import get_registry

    lowered, pool, slab = _serve_program(v5e, "trinity-serve-mixedlen", program)
    assert slab is None and pool.shape == (4, 16 * 800 + 1, 4, 16, 128)
    registry = get_registry()
    assert round(registry.gauge("decode/global_pool_bytes").value / 1e9, 2) == 1.68
    assert round(registry.gauge("decode/window_pool_bytes").value / 1e9, 2) == 1.01
    assert registry.gauge("decode/window_blocks_total").value == 16 * 161
    window_pool = "bf16[12,2577,4,16,128]"  # [12, 16 x 161 + 1, 4, 16, 128]
    assert lowered.as_text().count(window_pool.replace("bf16[", "tensor<").replace(",", "x").replace("]", "xbf16>")) >= 2
    compiled = lowered.compile()  # raises what the chip's compiler would: it fits
    text, memory = compiled.as_text(), compiled.memory_analysis()
    print(f"trinity-serve-mixedlen {program}: arguments {memory.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temp {memory.temp_size_in_bytes / 1e9:.3f} GB")
    assert memory.alias_size_in_bytes >= 2 * (pool.size + 12 * 2577 * 4 * 16 * 128) * 2  # both pools in place
    assert 6.9e9 < memory.argument_size_in_bytes < 6.95e9
    # a step's temporaries are its rows' activations, and so are a chunk's since
    # its attention runs in `paged_prefill` (0.89 GB until PR 35: a global layer's
    # [1, 4, 8, 512, 12800] float32 scores)
    assert memory.temp_size_in_bytes < {"decode": 0.15, "prefill": 0.2}[program] * 1e9
    wide = re.compile(r"\w+\[[\d,]*\b(?:12800|800)\b[\d,]*\]")
    window_lines = [line for line in text.splitlines() if "attn_window" in line]
    global_lines = [line for line in text.splitlines() if "attn_global" in line]
    assert window_lines and global_lines
    assert not [line for line in window_lines if wide.search(line.split(" metadata=")[0])]
    assert any(wide.search(line.split(" metadata=")[0]) for line in global_lines)  # its table
    if program == "prefill":
        # no scores as wide as a table, the ring's or the global group's, anywhere
        scores = re.compile(r"f32\[[\d,]*\b(?:12800|2576)\b[\d,]*\]")
        assert not [line for line in text.splitlines() if scores.search(line.split(" metadata=")[0])]
    # one attention kernel a layer, three scanned periods behind the looped one:
    # a decode step's `paged_decode`, a chunk's `paged_prefill`
    mine, other = ("paged_decode", "paged_prefill") if program == "decode" else ("paged_prefill", "paged_decode")
    assert _kernel_calls(text, mine, 3) == 16 and _kernel_calls(text, other, 3) == 0
    assert _kernel_calls(text, mine, 3, under="attn_window") == 12
    assert _kernel_calls(text, mine, 3, under="attn_global") == 4
    kernels = parse_hlo_kernels(text)
    assert kernels.get("kv_page_write", 0) >= 2
    # all fourteen expert layers' three products in `gmm`: the looped front's two
    # (each its own stack of one, since PR 43) and four a period in three periods
    assert _kernel_calls(text, "moe_experts/jit(gmm)", 3) == 3 * 14 and "ragged-dot" not in text
    assert not {"mla_prefill", "mla_decode", "latent_page_write"} & set(kernels)
    _check_combine_gathers(text)
    # q_proj, k_proj, v_proj and the gate's `gate_proj` are read where they lie,
    # the scanned periods' and the looped front's (16 a program until PR 48)
    assert _projection_slices(text, _cell_config("trinity-serve-mixedlen")) == 0


def test_mla_decode_refuses_a_row_that_is_not_whole_lanes(v5e):
    """576 values a row is what the mathematics needs and not what the chip
    stores: the array is laid out in tiles of 128 lanes (as 640) and Mosaic
    refuses a page copy that is not whole tiles, so the pool is declared 640
    wide (`LatentCacheSpec.width`) and the zeros are real."""
    from llm_training_tpu.ops.pallas.mla_decode import mla_decode_attention

    one = SingleDeviceSharding(v5e.devices[0])
    shape = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def compile_at(width):
        return jax.jit(
            lambda q, pool, tables, lens: mla_decode_attention(
                q, pool, tables, lens, latent_dim=512, scale=192 ** -0.5, interpret=False
            )
        ).lower(
            shape((32, 64, width)), shape((4096, 1, 16, width)),
            shape((32, 352), jnp.int32), shape((32,), jnp.int32),
        ).compile()

    assert "mla_decode" in parse_hlo_kernels(compile_at(640).as_text())
    with pytest.raises(Exception, match="aligned to tiling"):
        compile_at(576)


# --------------------------------------------------- the four-chip train cell
#
# `phi3m-train-4k-fsdp4`'s whole train step, partitioned for the 2x2. Pinned:
# it fits, and the chunked loss keeps each chip's tokens on that chip: under
# `loss_ce` no collective sits inside a while body (GSPMD alone all-reduced
# `f32[2048,32064]` partial logits every chunk, forward and recomputed
# backward, and re-split each chunk with an all-to-all), the head is gathered
# ONCE and in bf16 (328 MB: the cast comes before the gather), and its
# gradient is reduced once, after the backward scan.


def test_train_cell_keeps_the_loss_on_its_chip_for_v5e(v5e, as_on_tpu):
    import re
    from pathlib import Path

    import flax.linen as nn
    import numpy as np

    from benchmarks import common
    from benchmarks.runners import train_fit
    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh
    from llm_training_tpu.trainer import Trainer, TrainerConfig
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES, _batch_shardings
    from tests.test_ce_sharding import _collectives

    cell = common.Cell(Path(__file__).resolve().parent.parent, "phi3m-train-4k-fsdp4")
    objective = train_fit.seeded_objective(cell, 1)
    devices = list(v5e.devices)
    trainer = Trainer(
        TrainerConfig(mesh=MeshConfig(**cell.config["train"]["mesh"])), devices=devices
    )
    mesh = trainer.mesh = build_mesh(trainer.config.mesh, devices)
    rows, seq = cell.traffic["global_batch_rows"], cell.traffic["seq_len"]
    keys = ("input_ids", "labels", "segment_ids", "position_ids")
    sample = {k: np.zeros((rows, seq), np.int32) for k in keys}
    with mesh, nn.logical_axis_rules(LOGICAL_AXIS_RULES):
        tx, _ = trainer._build_tx(objective)
        boxed = trainer._abstract_state(objective, sample, tx)
        trainer.state_shardings = trainer._state_shardings(boxed)
        step = jax.jit(
            trainer._build_step(objective, tx),
            in_shardings=(trainer.state_shardings, _batch_shardings(sample, mesh)),
            out_shardings=(trainer.state_shardings, None),
            donate_argnums=0,
        )
        compiled = step.lower(
            nn.meta.unbox(boxed),
            {k: jax.ShapeDtypeStruct((rows, seq), jnp.int32) for k in keys},
        ).compile()  # raises what the chip's compiler would: it fits
    text, memory = compiled.as_text(), compiled.memory_analysis()
    print(f"phi3m-train-4k-fsdp4 step: arguments {memory.argument_size_in_bytes / 1e9:.3f} GB, "
          f"temp {memory.temp_size_in_bytes / 1e9:.3f} GB")
    # the parent's step: 13.653 GB of temporaries (donated state counted in)
    assert memory.temp_size_in_bytes < 13.7e9

    embed, vocab = cell.config["hidden_size"], cell.config["vocab_size"]
    chunk = cell.config["train"]["ce_chunk_size"]
    of_loss = _collectives(text, scope="loss_ce")
    assert [c for c in of_loss if c[2]] == [], "a collective of the loss inside a scan"
    assert not any(op == "all-to-all" for op, _, _ in of_loss)
    everywhere = _collectives(text)
    assert not any(s[-2:] == (chunk, vocab) for _, shapes, _ in everywhere for s in shapes)
    head = [(op, looped) for op, shapes, looped in everywhere if (embed, vocab) in shapes]
    assert sorted(head) == [("all-gather", False), ("all-reduce", False)], head
    gather = next(
        line for line in text.splitlines()
        if re.search(rf"= \S*\[{embed},{vocab}\]\S* all-gather", line)
    )
    assert gather.split("= ")[1].startswith("bf16["), gather[:200]
