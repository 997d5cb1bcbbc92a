"""Paged KV-cache pool + host-side block allocator (docs/serving.md).

The pool is the device half: `[kv_layers, num_blocks, kv_heads,
block_size, head_dim]` k/v buffers over the layers that cache keys and
values (`infer/cache.py:cache_specs`; a stack's linear-attention layers keep
a fixed slab a decode slot instead, `init_state_slab` below) (one kv head's page is the trailing
(block_size, head_dim) tile the paged-decode kernel streams), built from
the SAME training rule table `infer/cache.py` uses (kv heads shard over 'tensor'; the block axis stays
replicated — each data-parallel serving replica owns its whole pool).
Physical block 0 is a reserved TRASH block: idle decode slots and padded
chunk positions write there, so a garbage row can never touch a live
request's cache.

A stack whose layers differ in how much of the past they keep (`infer/cache.py:
kv_groups`) has a pool a GROUP: the one above for the layers that keep every
token, and `init_window_pool`'s for the layers that keep a window, with its
own trash block 0, its own allocator and a page budget a request that does not
grow with `max_model_len` (`window_page_budget`).

The `BlockAllocator` is the host half: a free list handing fixed-size
blocks to requests and taking them back on completion/eviction, publishing
(once an engine step: `publish`) pool occupancy as `decode/cache_blocks_total` / `decode/cache_blocks_in_use`
/ `decode/cache_peak_blocks_in_use` gauges so telemetry.jsonl and `report`
show block pressure (and the serve-smoke gate can assert leak-freedom).

The block size is the paged-decode kernel's tile knob and resolves through
`ops/pallas/tuning.py` (config > PAGED_BLOCK_K env > tuning table > 16)
when the serve config leaves it unset.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

# jax — and everything that drags it in: the infer.cache helpers AND the
# `llm_training_tpu.ops` package (whose __init__ loads every kernel) —
# loads lazily inside the pool constructors so the allocator stays
# importable from jax-free host processes (loadgen / router parents), the
# package docstring's contract
if TYPE_CHECKING:
    import jax.numpy as jnp
    from jax.sharding import Mesh

# pool layout: [num_layers, num_blocks, num_kv_heads, block_size, head_dim]
POOL_LOGICAL_AXES = ("layers", None, "kv_heads", None, None)

TRASH_BLOCK = 0  # physical block 0 is never allocated


def resolve_block_size(
    model_config, max_model_len: int, block_size: int | None = None,
    cache_dtype: str | None = None,
) -> int:
    """The pool's tokens-per-block, via the tuning layer (kind='paged')."""
    from llm_training_tpu.infer.cache import resolve_cache_dtype, token_rows
    from llm_training_tpu.ops.pallas.tuning import resolve_paged_block_size

    _, _, _, head_dim = token_rows(model_config)
    choice = resolve_paged_block_size(
        max_model_len=max_model_len, head_dim=head_dim,
        dtype=resolve_cache_dtype(model_config, cache_dtype),
        block_size=block_size,
    )
    return choice.block_k


def _zero_pools(shape, dtype, buffers: int, mesh, rules) -> tuple:
    """`buffers` all-zeros pools of `shape`, created already sharded under a
    mesh (kv heads over 'tensor', like the dense cache; a latent pool's one
    row a token is shared by the heads: it has no head axis to shard, and
    `_divisible_spec` drops the rule)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from llm_training_tpu.infer.cache import _divisible_spec

    def build():
        return tuple(jnp.zeros(shape, dtype) for _ in range(buffers))

    if mesh is None:
        return build()
    spec = NamedSharding(
        mesh, _divisible_spec(shape, POOL_LOGICAL_AXES, mesh, rules or ())
    )
    return jax.jit(build, out_shardings=(spec,) * buffers)()


def init_paged_pool(
    model_config,
    num_blocks: int,
    block_size: int,
    mesh: Mesh | None = None,
    rules=None,
    cache_dtype: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fresh all-zeros (k, v) pool, created ALREADY sharded under a mesh
    (kv heads over 'tensor', like the dense cache). A stack that caches
    latent rows (`LatentCacheSpec`) has ONE pool, `[mla_blocks, num_blocks, 1,
    block_size, width]`: it comes back as `k`, and `v` is None. Publishes the
    pool footprint as the `decode/cache_bytes` gauge (and as
    `decode/global_pool_bytes`: of a stack with two groups this is the pool of
    the layers that keep every token), and a latent pool's as
    `decode/latent_pool_bytes` too."""
    from llm_training_tpu.infer.cache import resolve_cache_dtype, token_rows

    buffers, num_layers, kv_heads, head_dim = token_rows(model_config)
    pools = _zero_pools(
        (num_layers, num_blocks, kv_heads, block_size, head_dim),
        resolve_cache_dtype(model_config, cache_dtype), buffers, mesh, rules,
    )
    k, v = pools if buffers == 2 else (pools[0], None)
    _publish_pool_gauges(k, v, num_blocks)
    return k, v


def window_page_budget(
    sliding_window: int, prefill_chunk: int, block_size: int, pages_per_request: int
) -> int:
    """Pages of the window group a request holds at most, so also the width
    of its table there: the window and one prefill chunk, rounded up to pages,
    and one more for a chunk that starts inside a page; never more than the
    other group's. A row's logical page `p` lives in slot `p % budget`."""
    return min(-(-(sliding_window + prefill_chunk) // block_size) + 1, pages_per_request)


def init_window_pool(model_config, num_blocks: int, block_size: int, mesh=None,
                     rules=None, cache_dtype: str | None = None):
    """The (k, v) pool of the layers that keep a window (`infer/cache.py:
    kv_groups`), shaped and sharded as `init_paged_pool`'s, or None for a stack
    with one group. Publishes `decode/window_pool_bytes` and
    `decode/window_blocks_total`."""
    from llm_training_tpu.infer.cache import kv_groups, resolve_cache_dtype
    from llm_training_tpu.telemetry import get_registry

    registry = get_registry()
    _, group = kv_groups(model_config)
    pools = None if group is None else _zero_pools(
        (group.layers, num_blocks, group.kv_heads, block_size, group.head_dim),
        resolve_cache_dtype(model_config, cache_dtype), 2, mesh, rules,
    )
    registry.gauge("decode/window_pool_bytes").set(0 if pools is None else pool_bytes(*pools))
    registry.gauge("decode/window_blocks_total").set(0 if pools is None else num_blocks - 1)
    return pools


def init_state_slab(model_config, slots: int, mesh=None, rules=None,
                    cache_dtype: str | None = None):
    """The second kind of cache, for a stack with linear-attention layers:
    `(state, conv tail)` with one slot a decode row (`infer/cache.py:
    init_state_slab`, from the same `cache_specs` declaration the pool's
    layer count comes from), or None for a stack without such layers.
    Publishes its footprint as the `decode/state_bytes` gauge and what it
    holds as `decode/state_logical_bytes`."""
    from llm_training_tpu.infer import cache
    from llm_training_tpu.telemetry import get_registry

    slab = cache.init_state_slab(
        model_config, slots, mesh=mesh, rules=rules, cache_dtype=cache_dtype
    )
    registry = get_registry()
    registry.gauge("decode/state_bytes").set(0 if slab is None else pool_bytes(*slab))
    # what it holds beside what it occupies: the stored layout's padding, if any
    registry.gauge("decode/state_logical_bytes").set(0 if slab is None else cache.slab_logical_bytes(
        cache.cache_specs(model_config)[1], slots, slab[1].dtype
    ))
    return slab


def pool_bytes(k: jnp.ndarray, v: jnp.ndarray | None) -> int:
    return sum(leaf.size * leaf.dtype.itemsize for leaf in (k, v) if leaf is not None)


def _publish_pool_gauges(k, v, num_blocks: int) -> None:
    from llm_training_tpu.telemetry import get_registry

    registry = get_registry()
    registry.gauge("decode/cache_bytes").set(pool_bytes(k, v))
    registry.gauge("decode/global_pool_bytes").set(pool_bytes(k, v))
    registry.gauge("decode/latent_pool_bytes").set(0 if v is not None else pool_bytes(k, v))
    registry.gauge("decode/cache_blocks_total").set(num_blocks - 1)  # minus trash


class BlockAllocator:
    """Host-side free list over the pool's physical blocks (block 0
    reserved as trash). All-or-nothing `alloc`, idempotence-free `free`
    (double-free is a bug and raises), occupancy gauges where the owner asks
    (`publish`; the engine does once a step):
    `decode/<group>_blocks_in_use` and `decode/<group>_peak_blocks_in_use`
    (`cache`: the pool of the layers that keep every token; `window`: the
    window group's)."""

    def __init__(self, num_blocks: int, group: str = "cache"):
        self.group = group
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 usable + trash), got {num_blocks}"
            )
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, TRASH_BLOCK, -1))  # pop() -> low ids first
        self._in_use: set[int] = set()
        self.peak_in_use = 0
        self.publish()

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def blocks_in_use(self) -> int:
        return len(self._in_use)

    def alloc(self, n: int) -> list[int] | None:
        """n blocks, or None (nothing allocated) when fewer are free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        self._in_use.update(blocks)
        self.peak_in_use = max(self.peak_in_use, len(self._in_use))
        return blocks

    def free(self, blocks: list[int]) -> None:
        for block in blocks:
            if block not in self._in_use:
                raise ValueError(f"free of unallocated block {block}")
            self._in_use.remove(block)
            self._free.append(block)

    def publish(self) -> None:
        """Set the two occupancy gauges from the allocator's own counts: once
        an engine step (and when its summary is taken), not on every `alloc`
        and `free` of the step."""
        from llm_training_tpu.telemetry import get_registry

        registry = get_registry()
        registry.gauge(f"decode/{self.group}_blocks_in_use").set(len(self._in_use))
        registry.gauge(f"decode/{self.group}_peak_blocks_in_use").set(self.peak_in_use)
