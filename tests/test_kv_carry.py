"""The serving programs update their caches in place (docs/serving.md, "How
the cache is carried"): the page-granular append against the row scatter it
replaced, and the stack of pools carried through the layer loop against one
pool a layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.ops.paged_attention import paged_append, paged_cached_attention

PAGE, HEADS, DIM, BLOCKS = 8, 2, 4, 12


def _row_scatter(pool, x, lengths, tables, seg):
    """The append as it was before the page writer: one scatter over
    (block, slot), strays sent to slot 0 of block 0."""
    batch, seq = x.shape[:2]
    pages = tables.shape[1]
    pos = lengths[:, None] + jnp.arange(seq, dtype=jnp.int32)[None, :]
    valid = jnp.ones((batch, seq), bool) if seg is None else seg > 0
    valid &= pos < pages * PAGE
    page = jnp.take_along_axis(tables, jnp.minimum(pos // PAGE, pages - 1), axis=1)
    page = jnp.where(valid, page, 0)
    offset = jnp.where(valid, pos % PAGE, 0)
    return pool.at[page, :, offset].set(x.astype(pool.dtype)), valid


# name -> (lengths, tables, segment ids or None, chunk width)
APPENDS = {
    "single_token": ([5], [[3, 4]], None, 1),
    "single_token_opens_a_page": ([8], [[3, 4]], None, 1),
    "page_aligned_chunk": ([8], [[2, 5, 7]], None, 16),
    "chunk_from_mid_page": ([3], [[2, 5, 7]], None, 16),
    "last_padded_chunk": ([16], [[2, 5, 7]], [[1, 1, 1, 0, 0, 0, 0, 0]], 8),
    "out_of_table_position": ([14], [[6, 9]], None, 4),
    "two_rows_sharing_no_page": ([7, 0], [[1, 2], [10, 11]], [[1, 1, 1], [1, 1, 0]], 3),
    "idle_row_on_the_trash_table": ([9, 0], [[4, 8], [0, 0]], None, 1),
}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("case", sorted(APPENDS))
def test_page_append_matches_the_row_scatter(case, impl):
    lengths, tables, seg, seq = APPENDS[case]
    lengths, tables = jnp.asarray(lengths, jnp.int32), jnp.asarray(tables, jnp.int32)
    seg = None if seg is None else jnp.asarray(seg, jnp.int32)
    keys = jax.random.split(jax.random.key(len(case)), 4)
    pool_k = jax.random.normal(keys[0], (BLOCKS, HEADS, PAGE, DIM))
    pool_v = jax.random.normal(keys[1], (BLOCKS, HEADS, PAGE, DIM))
    k = jax.random.normal(keys[2], (len(lengths), seq, HEADS, DIM))
    v = jax.random.normal(keys[3], (len(lengths), seq, HEADS, DIM))
    new_k, new_v = jax.jit(paged_append, static_argnames="impl")(
        pool_k, pool_v, k, v, lengths, tables, seg, impl=impl
    )
    for new, pool, x in ((new_k, pool_k, k), (new_v, pool_v, v)):
        ref, valid = _row_scatter(pool, x, lengths, tables, seg)
        # every block but the trash block: bit for bit
        np.testing.assert_array_equal(np.asarray(new[1:]), np.asarray(ref[1:]))
        # the trash block: only slot 0 may change, and only to a stray's row
        np.testing.assert_array_equal(np.asarray(new[0, :, 1:]), np.asarray(pool[0, :, 1:]))
        idle = (tables == 0).all(axis=1)  # a row on the trash table writes there as of right
        strays = [x[b, i] for b, i in zip(*np.nonzero(~np.asarray(valid)))]
        strays += [x[b, 0] for b in np.nonzero(np.asarray(idle))[0]]
        allowed = strays + [pool[0, :, 0]]
        assert any(np.array_equal(np.asarray(new[0, :, 0]), np.asarray(a)) for a in allowed)
        if strays and not idle.any():
            assert not np.array_equal(np.asarray(new[0, :, 0]), np.asarray(pool[0, :, 0]))


def test_page_append_keeps_kv_heads_sharded_over_tensor(devices):
    """Under a serving mesh the page writer runs in a shard_map over
    `tensor`, as the decode kernel does: each shard writes its own heads."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(fsdp_size=4, tensor_parallel_size=2), devices)
    lengths, tables, seg, seq = APPENDS["two_rows_sharing_no_page"]
    lengths, tables, seg = (jnp.asarray(a, jnp.int32) for a in (lengths, tables, seg))
    keys = jax.random.split(jax.random.key(7), 3)
    pool = jax.random.normal(keys[0], (BLOCKS, HEADS, PAGE, DIM))
    k = jax.random.normal(keys[1], (2, seq, HEADS, DIM))
    v = jax.random.normal(keys[2], (2, seq, HEADS, DIM))
    heads = NamedSharding(mesh, P(None, "tensor", None, None))
    append = jax.jit(
        lambda pk, pv, k, v: paged_append(pk, pv, k, v, lengths, tables, seg, "pallas"),
        out_shardings=(heads, heads),
    )
    with mesh:
        placed = jax.device_put(pool, heads)
        new_k, new_v = append(placed, placed, k, v)
        text = append.lower(placed, placed, k, v).compile().as_text()
    assert "all-gather" not in text, "the append gathered the pool's heads"
    assert new_k.sharding.is_equivalent_to(heads, 4)
    for new, x in ((new_k, k), (new_v, v)):
        ref, _ = _row_scatter(pool, x, lengths, tables, seg)
        np.testing.assert_array_equal(np.asarray(new[1:]), np.asarray(ref[1:]))


# ------------------------------------------------ the stack carried by the loop

LAYERS = 3


def _stack_inputs(seq):
    keys = jax.random.split(jax.random.key(seq), 5)
    pools = tuple(
        jax.random.normal(key, (LAYERS, BLOCKS, HEADS, PAGE, DIM)) for key in keys[:2]
    )
    batch = 2
    q = jax.random.normal(keys[2], (LAYERS, batch, seq, 2 * HEADS, DIM))
    k = jax.random.normal(keys[3], (LAYERS, batch, seq, HEADS, DIM))
    v = jax.random.normal(keys[4], (LAYERS, batch, seq, HEADS, DIM))
    tables = jnp.asarray([[1, 2, 3], [9, 10, 11]], jnp.int32)
    lengths = jnp.asarray([6, 13], jnp.int32)
    seg = None if seq == 1 else jnp.asarray([[1] * seq, [1] * (seq - 2) + [0, 0]], jnp.int32)
    return pools, q, k, v, tables, lengths, seg


@pytest.mark.parametrize("loop", ["scan", "python"])
@pytest.mark.parametrize("seq", [1, 8], ids=["decode_step", "chunk"])
def test_carried_stack_equals_one_pool_a_layer(seq, loop):
    """Three layers' pools carried whole through the layer loop, each layer
    addressing its own blocks, against each layer's pool on its own: the
    outputs and every leaf of the cache, the trash blocks' slot 0 aside."""
    pools, q, k, v, tables, lengths, seg = _stack_inputs(seq)
    impl = "pallas" if seq == 1 else "xla"

    def one(pools, layer, q, k, v):
        return paged_cached_attention(
            q, k, v, pools, lengths, tables, layer=layer, segment_ids=seg, impl=impl
        )

    @jax.jit
    def carried(pools):
        if loop == "python":
            outs = []
            for layer in range(LAYERS):
                out, pools = one(pools, layer, q[layer], k[layer], v[layer])
                outs.append(out)
            return jnp.stack(outs), pools

        def body(pools, xs):
            out, pools = one(pools, *xs)
            return pools, out

        pools, outs = jax.lax.scan(body, pools, (jnp.arange(LAYERS), q, k, v))
        return outs, pools

    outs, (new_k, new_v) = carried(pools)
    for layer in range(LAYERS):
        ref, (ref_k, ref_v) = jax.jit(lambda pk, pv, q, k, v: paged_cached_attention(
            q, k, v, (pk, pv), lengths, tables, segment_ids=seg, impl="xla"
        ))(pools[0][layer], pools[1][layer], q[layer], k[layer], v[layer])
        np.testing.assert_allclose(np.asarray(outs[layer]), np.asarray(ref), rtol=2e-5, atol=2e-5)
        for new, old in ((new_k, ref_k), (new_v, ref_v)):
            np.testing.assert_array_equal(np.asarray(new[layer, 1:]), np.asarray(old[1:]))
            np.testing.assert_array_equal(
                np.asarray(new[layer, 0, :, 1:]), np.asarray(old[0, :, 1:])
            )


@pytest.mark.parametrize("seq", [1, 8], ids=["decode_step", "chunk"])
def test_donated_stack_is_consumed_and_only_the_rows_own_blocks_change(seq):
    pools, q, k, v, tables, lengths, seg = _stack_inputs(seq)
    before = [np.array(pool) for pool in pools]  # copies: a view would hold the buffer

    @jax.jit
    def step(pools):
        def body(pools, xs):
            out, pools = paged_cached_attention(
                xs[1], xs[2], xs[3], pools, lengths, tables, layer=xs[0], segment_ids=seg,
            )
            return pools, out

        return jax.lax.scan(body, pools, (jnp.arange(LAYERS), q, k, v))[0]

    new = jax.jit(step, donate_argnums=0)(pools)
    assert all(pool.is_deleted() for pool in pools)
    # the blocks a row's chunk lies in: positions lengths .. lengths + real tokens
    real = [seq, seq if seq == 1 else seq - 2]
    touched = {0}  # the trash block takes the padded positions
    for row, (start, count) in enumerate(zip(np.asarray(lengths), real)):
        touched |= {int(tables[row, p // PAGE]) for p in range(start, start + count)}
    untouched = [b for b in range(BLOCKS) if b not in touched]
    assert len(touched) < BLOCKS and untouched
    for pool, old in zip(new, before):
        np.testing.assert_array_equal(np.asarray(pool)[:, untouched], old[:, untouched])
        for layer in range(LAYERS):
            for block in touched - {0}:
                assert not np.array_equal(np.asarray(pool)[layer, block], old[layer, block])
