"""Base model config + output types.

Capability parity: reference `models/base_model/base_model.py:14-74`
(config-carrying module, init_weights gate, parallelize hooks — the hooks
dissolve into logical-axis metadata here) and
`models/utils/modeling_outputs.py:11-13` (`CausalLMOutput`).
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import flax.struct
import jax.numpy as jnp
from pydantic import BaseModel, ConfigDict, field_validator


_DTYPE_MAP = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float64": jnp.float64,
}

DTypeName = Literal["float32", "bfloat16", "float16", "float64"]


def resolve_dtype(name: str) -> jnp.dtype:
    try:
        return _DTYPE_MAP[name]
    except KeyError:
        raise ValueError(f"unknown dtype {name!r}; expected one of {sorted(_DTYPE_MAP)}")


class BaseModelConfig(BaseModel):
    """Common model-config surface.

    `pre_trained_weights` mirrors the reference's weight-source field
    (`base_model.py:32-33`); dtype fields replace its str→torch.dtype
    validator (`base_model_config.py`) with str→jnp names resolved lazily.

    The master-weights scheme of the reference (`optim/master_weight_wrapper.py`)
    is expressed here directly: params live in `param_dtype` (fp32), the
    forward runs in `compute_dtype` (bf16), optimizer state stays fp32.
    """

    model_config = ConfigDict(extra="forbid")

    pre_trained_weights: str | None = None
    compute_dtype: DTypeName = "bfloat16"
    param_dtype: DTypeName = "float32"

    @field_validator("compute_dtype")
    @classmethod
    def _no_fp16_compute(cls, value: str) -> str:
        # fp16 without dynamic loss scaling silently under/overflows; TPUs are
        # bf16-native (same exponent range as fp32), so the reference's fp16 +
        # DeepSpeed loss-scale path (deepspeed_strategy.py:104-108) has no TPU
        # analogue — reject rather than train broken
        if value == "float16":
            raise ValueError(
                "compute_dtype='float16' is not supported: fp16 requires "
                "dynamic loss scaling, which TPUs don't need — use 'bfloat16' "
                "(same exponent range as fp32, MXU-native)"
            )
        return value

    @property
    def compute_jnp_dtype(self) -> jnp.dtype:
        return resolve_dtype(self.compute_dtype)

    @property
    def param_jnp_dtype(self) -> jnp.dtype:
        return resolve_dtype(self.param_dtype)

    def cache_specs(
        self,
    ) -> (
        tuple[
            KVCacheSpec | LatentCacheSpec | tuple[KVCacheSpec, KVCacheSpec],
            RecurrentCacheSpec | None,
        ]
        | None
    ):
        """What this family's stack caches when it decodes: first what a
        token leaves behind in the attention layers (keys and values a head,
        `KVCacheSpec`, or one latent row shared by the heads,
        `LatentCacheSpec`), then the slab of its linear-attention layers, if
        it has any. A stack whose layers differ in how much of the past they
        keep declares its key/value layers in TWO groups, `(those that keep
        every token, those that keep a window)`: a pool, a block table and a
        page budget each (`infer/cache.py:kv_groups`). Every pool, dense
        buffer, slab and sharding derives from it (`infer/cache.py`). None,
        the default, says the family does not decode: its `__call__` takes no
        `decode_state`."""
        return None


@flax.struct.dataclass
class RouterStats:
    """Per-MoE-layer router statistics, threaded out of every MoE family
    for the model-health layer (`telemetry/health.py:moe_router_health`).

    `sel_frac [L, E]`: fraction of (token, slot) assignments routed to each
    expert per MoE layer (rows sum to ~top_k — each of the K selections per
    token counts, HF `load_balancing_loss_func` scale). `mean_prob [L, E]`:
    mean fp32 routing probability per expert (sigmoid-routed families —
    DeepSeek-V3 — normalize scores per token first so entropy stays
    meaningful). `dropped`: scalar total of (token, expert) assignments
    lost to capacity buffers across layers. `layer_ids` is STATIC (not a
    pytree leaf): the absolute decoder-layer index of each row, so metric
    keys name real layers even when only a suffix of the stack is MoE
    (DeepSeek's dense prefix). The arrays already exist pre-pooling in
    every family's aux-loss computation, so populating this costs nothing
    when unused — XLA dead-code-eliminates the extra outputs."""

    sel_frac: jnp.ndarray
    mean_prob: jnp.ndarray
    dropped: jnp.ndarray
    layer_ids: tuple[int, ...] = flax.struct.field(pytree_node=False, default=())


@dataclasses.dataclass(frozen=True)
class KVCacheSpec:
    """The cache of a stack's softmax-attention layers: keys and values a
    token, in pages (`serve/paged_cache.py`) or a dense buffer
    (`infer/cache.py`). `layers` counts the layers of THIS kind only. `window`
    is how many of a row's newest tokens these layers ever read: None, all of
    them; a number, and the layers are a group of their own whose pages in
    front of the window go back to their pool (`serve/scheduler.py`).
    `readers` counts the layers that READ these pages where that is more
    than the `layers` that write them: a layer that attends over another
    layer's keys and values and appends nothing (`LayerCache.attend` without
    k and v) has no part of the cache, and the serving engine counts what it
    reads (`serve/shared_kv_reads`). None: every layer reads its own."""

    layers: int
    kv_heads: int
    head_dim: int
    window: int | None = None
    readers: int | None = None


@dataclasses.dataclass(frozen=True)
class LatentCacheSpec:
    """The cache of a stack's latent-attention (MLA) blocks: ONE row a token,
    shared by every head: the compressed key/value latent (`latent_dim`,
    normalised and scaled as the block's `kv_b` projection reads it) and the
    rotated positional key (`rope_dim`). It is read once, as keys and, its
    first `latent_dim` values, as values: there is no second buffer. `layers`
    counts the MLA blocks (two a double layer)."""

    layers: int
    latent_dim: int
    rope_dim: int

    @property
    def width(self) -> int:
        """The row as it is stored: whole 128-lane tiles, zeros past
        `latent_dim + rope_dim` (the chip lays an array out in such tiles
        whatever it is declared as, and Mosaic refuses a page copy that is
        not whole ones: 576 values are stored as 640)."""
        return -(-(self.latent_dim + self.rope_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class RecurrentCacheSpec:
    """The cache of a stack's linear-attention layers: a fixed slab a decode
    slot, whatever the request's length: the float32 fast-weight state, a
    head `[key_dim, value_dim]`, and the short convolution's tail `[layers,
    slots, conv_taps, conv_channels]` (the last pre-conv inputs; `conv_taps`
    = kernel width - 1). The state is STORED `[layers, slots, *stored]`
    (below), which is what every slab, sharding and gauge is sized from."""

    layers: int
    heads: int
    key_dim: int
    value_dim: int
    conv_taps: int
    conv_channels: int

    @property
    def abreast(self) -> int:
        """Heads side by side on the stored state's value axis: as many as
        make its rows whole 128-lane tiles (the chip lays a float32 array
        out in tiles of 8 x 128 whatever it is declared as: 192 values a row
        would occupy 256 lanes, two heads' 384 occupy 384), 1 where the rows
        are whole already or the head count does not divide by it
        (`ops/delta_rule.py:pack_heads` is the layout)."""
        n = 1
        while (n * self.value_dim) % 128:
            n += 1
        return n if self.heads % n == 0 else 1

    @property
    def stored(self) -> tuple[int, int, int]:
        """A slot's state of one layer as it is stored: `[heads / abreast,
        key_dim, abreast * value_dim]`."""
        n = self.abreast
        return self.heads // n, self.key_dim, n * self.value_dim


@flax.struct.dataclass
class DecodeState:
    """Static-shape, mesh-sharded KV cache threaded through the decoder
    stack for autoregressive decoding (`infer/` subsystem, docs/inference.md).

    `k`/`v` are `[num_layers, batch, max_length, num_kv_heads, head_dim]`
    buffers in the cache dtype (param dtype by default, fp32/bf16
    configurable); the leading layer axis is the scan axis under
    `scan_layers` and an indexed axis on the looped path, sharded like the
    scanned param stacks (replicated), while heads shard over 'tensor' and
    batch over 'data'/'fsdp' exactly like attention activations.

    `index` is a traced int32 scalar: the number of tokens already written,
    i.e. the absolute kv position the incoming chunk appends at. It is
    SHARED across the batch — prompts are LEFT-padded to a common width so
    every row appends at the same slot (per-row write offsets would need a
    scatter instead of one `dynamic_update_slice`). `segment_ids [batch,
    max_length]` marks which cache slots hold real tokens (1) vs left-pad /
    not-yet-written garbage (0); the attention mask's `seg > 0` term makes
    unwritten slots unreachable, and the causal term (`q_offset = index`)
    keeps the chunk from seeing slots written after it."""

    k: jnp.ndarray
    # None for a latent cache (`LatentCacheSpec`): `k` is then `[mla_blocks,
    # batch, max_length, 1, width]`, the one row a token
    v: jnp.ndarray | None
    index: jnp.ndarray
    segment_ids: jnp.ndarray
    # linear-attention layers' slab (`RecurrentCacheSpec`), one slot a batch
    # row; None for a stack that has no such layer
    state: jnp.ndarray | None = None
    conv: jnp.ndarray | None = None
    # the window group's layers (`KVCacheSpec.window`), `[window_layers,
    # batch, max_length, num_kv_heads, head_dim]`: held at full length here
    # and masked; None for a stack with one group
    window_k: jnp.ndarray | None = None
    window_v: jnp.ndarray | None = None
    # STATIC (not a pytree leaf): the sequence length the generation will
    # actually reach (padded prompt width + max_new_tokens). Length-
    # dependent RoPE variants (longrope short/long factor selection,
    # dynamic NTK) must key off THIS, not the cache capacity — a cache
    # over-allocated for reuse (max_length >> planned length) must not
    # flip a Phi-3 checkpoint onto its long-context tables. None = fall
    # back to the cache capacity.
    rope_length: int | None = flax.struct.field(pytree_node=False, default=None)

    @property
    def max_length(self) -> int:
        return self.k.shape[2]

    @property
    def table_length(self) -> int:
        """The length RoPE table selection should see (static)."""
        return self.rope_length or self.max_length


@flax.struct.dataclass
class PagedDecodeState:
    """Block-table KV cache for the serving subsystem (`serve/`,
    docs/serving.md) — the continuous-batching successor to `DecodeState`'s
    shared-append-index layout.

    `k`/`v` are `[num_layers, num_blocks, num_kv_heads, block_size,
    head_dim]` POOL buffers: fixed-size blocks allocated to requests by the
    host-side `serve.paged_cache.BlockAllocator` (physical block 0 is a
    reserved trash block — idle decode slots and padded chunk positions
    write there, so garbage rows can never corrupt a live request's cache).
    `block_tables [batch, max_blocks_per_request]` maps each row's logical
    block index to a physical pool block; `lengths [batch]` is each row's
    token count already written — per-row, unlike `DecodeState.index`,
    which is what lets a finished request's blocks be recycled and a new
    request join mid-flight without left-padding anyone.

    The decoder stacks open either state into the same `LayerCache`
    (`models/cache.py`), which is where the two kinds part.

    A stack with linear-attention layers carries their slab beside the pool
    (`state`, `conv`: `RecurrentCacheSpec`), indexed by decode SLOT, not by
    block. `slots [batch]` names each row's slot (None: row i is slot i, the
    decode step); `fresh [batch]` marks rows whose request starts here, so
    the slot's state and tail are read as zeros whatever the slot held.

    A stack that keeps a window in some of its layers has a SECOND pool for
    them (`window_k`, `window_v`: `[window_layers, window_blocks, kv_heads,
    block_size, head_dim]`, block 0 its own trash block) with its own table,
    `window_tables [batch, window_pages]`, as short as the window's page
    budget: a row's logical page `p` is at `window_tables[b, p % window_pages]`
    (a ring; `ops/paged_attention.py`), and a slot whose page went back to
    the pool names the trash block."""

    k: jnp.ndarray
    # None for a latent cache (`LatentCacheSpec`): `k` is then the latent
    # pool `[mla_blocks, num_blocks, 1, block_size, width]`
    v: jnp.ndarray | None
    block_tables: jnp.ndarray
    lengths: jnp.ndarray
    state: jnp.ndarray | None = None
    conv: jnp.ndarray | None = None
    slots: jnp.ndarray | None = None
    fresh: jnp.ndarray | None = None
    window_k: jnp.ndarray | None = None
    window_v: jnp.ndarray | None = None
    window_tables: jnp.ndarray | None = None
    # STATIC: planned total sequence length for length-dependent RoPE table
    # selection (same contract as DecodeState.rope_length); None = the
    # per-request capacity block_tables can address.
    rope_length: int | None = flax.struct.field(pytree_node=False, default=None)

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_length(self) -> int:
        """Per-request addressable capacity (blocks per table x block size)."""
        return self.block_tables.shape[1] * self.block_size

    @property
    def table_length(self) -> int:
        """The length RoPE table selection should see (static)."""
        return self.rope_length or self.max_length


@flax.struct.dataclass
class CausalLMOutput:
    """Forward output (reference `modeling_outputs.py:11-13`).

    `logits` is None when the objective requests hidden states only (for
    fused-linear-CE, which needs the pre-head activations). `aux_loss` is
    the unscaled MoE load-balancing loss (None for dense models).
    `ep_dropped_rows` counts (token, expert) assignments lost to the
    expert-parallel capacity buffer this step, summed over layers (None for
    dense models; exactly 0 when ep=1 or routing fits the buffer) — the
    observability VERDICT r4 asked for on the static-capacity EP path.
    `router_stats` carries the pre-pooled per-layer router statistics
    (None for dense models) for the health-metric layer. `decode_state` is
    the updated KV cache when the forward was called with one (None on the
    training path). `moe_assignments` is `[3]` int32 from a stack that holds
    a SHARE of its experts and has zero-compute ones (longcat_flash): the
    call's (token, slot) assignments to experts held here, to zero-compute
    experts, and to experts held elsewhere, over all layers, padding left
    out; a `Deepseek` share counts the same three (no zero-compute ones);
    None from every other family. `mtp_hidden_states` (and, under
    `compute_logits`, `mtp_logits`) are a multi-token-prediction module's
    final-normed output, position i for the token at i + 2, only from a call
    that asked for them (`Deepseek.__call__(return_mtp=True)`)."""

    logits: jnp.ndarray | None = None
    last_hidden_states: jnp.ndarray | None = None
    aux_loss: jnp.ndarray | None = None
    ep_dropped_rows: jnp.ndarray | None = None
    router_stats: RouterStats | None = None
    decode_state: DecodeState | None = None
    moe_assignments: jnp.ndarray | None = None
    mtp_hidden_states: jnp.ndarray | None = None
    mtp_logits: jnp.ndarray | None = None
