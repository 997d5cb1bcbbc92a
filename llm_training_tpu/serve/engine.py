"""Continuous-batching serving engine (docs/serving.md).

Two jitted programs over the SAME sharded decoder stack the trainer runs,
both against the paged pool (donated — the cache mutates in place in HBM:
the stack carries the pools through its layer loop and the append writes
only the pages the new tokens lie in, so neither program produces an array
of a pool's shape; docs/serving.md, "How the cache is carried and appended"):

- `prefill_chunk`: ONE request's next prompt chunk (batch 1, static chunk
  width) written into its own blocks; samples the first new token when the
  chunk completes the prompt;
- `decode_step`: one token for EVERY decoding slot (static `max_batch`
  rows) through the ragged paged-attention path — each row at its own
  length, no shared append index, no left padding. Idle slots carry the
  trash-block table and cost one garbage row.

The token a row feeds to its next decode step never visits the host on the
way: both programs return `_last_tokens` (`[max_batch]` int32, on the device,
never donated) updated, and `decode_step` reads its input ids from it. So the
engine runs ONE STEP AHEAD OF ITS FETCHES (docs/serving.md, "A step ahead of
its fetches"): `step()` n enqueues its chunk and its decode step, THEN reads
and emits what step n-1 enqueued, while the chip runs on. What needs no
token's value (`cache_len`, the window's pages, the finish by length) is
booked when a call is enqueued, so the scheduler sees what it would have seen;
what needs one reads first (`flush()`: an eviction, a running row's deadline,
`reload_weights`, `drain`, `close`), and an `eos` is seen a call late (its
row's extra step is dropped and counted).

A stack with latent attention (`LatentCacheSpec`) has ONE pool, a latent row
a token: `_pool_k` is that pool and `_pool_v` is None, through both programs
(`decode/latent_pool_bytes`). Such a stack, holding a share of its experts,
also counts each call's expert assignments on the device (`CausalLMOutput.
moe_assignments`): each call returns its three int32 counts as an output of
its own, which the host reads in the `device_get` that fetches the call's
tokens (no sync of its own) and adds to `serve/moe_{held,zero,elsewhere}_assignments`.

A stack with linear-attention layers (`infer/cache.py:cache_specs`) has a
second cache beside the pool: the STATE SLAB, a fixed float32 state and a
short conv tail for every decode slot, donated through both programs like
the pool. A request's first chunk reads its slot as zeros (admission and
the fold-in requeue after an eviction alike: `serve/state_resets`), later
chunks and decode steps carry the slot's state on, and padded positions,
idle slots and slots still prefilling are left exactly as they were.

A stack whose layers differ in how much of the past they keep
(`infer/cache.py:kv_groups`: some keep a sliding window, the others every
token) has a pool, an allocator and a block table a GROUP. The window group's
table is `window_pages` wide (`serve/paged_cache.py:window_page_budget`:
window + one chunk, in pages, + 1) whatever `max_model_len` is, a ring the
programs address by `page % window_pages`; after every chunk and every decode
step a request gives back the pages that fell wholly in front of its window
(`Scheduler.release_window`; `serve/window_pages_released`), and the slot of
a page given back names the trash block.

The host loop (`step()`) executes what the `Scheduler` decides: admission
when free blocks suffice, one prefill chunk interleaved between decode
steps, eviction/requeue under block pressure, slot recycling on max-tokens
(at the enqueue of a request's last call) / eos (when the host reads it).
Per-request TTFT/TPOT and engine throughput publish as
`serve/*` gauges (rendered by `report`'s `== Serving ==` section).

Resilience seams (docs/serving.md#resilience):

- every step first expires deadlines and re-evaluates shedding, so a
  terminal chunk (`deadline` / `overloaded`) is never more than one step
  late;
- `reload_weights` hot-swaps the model variables BETWEEN steps: every
  running request is evicted through the standard fold-in requeue (its
  paged cache was built under the old weights and must not mix), the new
  buffers are bound, and `serve/weights_generation` bumps — every chunk
  carries the `generation` it was decoded under, so a client can see
  exactly where the swap landed in its stream;
- an attached `RequestJournal` (`attach_journal`) records accept/progress/
  done so `drain()` — the SIGTERM path — can evict-and-journal everything
  in flight (freeing every pool block) and a relaunch can `submit_resumed`
  the remainder, continuing token-identically without re-streaming;
- chaos serve faults (`LLMT_CHAOS_SERVE_*`, resilience/chaos.py) hook the
  top of `step()` so a wedged step and a mid-stream SIGTERM are injectable
  exactly where they would really land.
"""

from __future__ import annotations

import logging
import math
import os
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import BaseModel, ConfigDict, model_validator

from llm_training_tpu.infer.cache import cache_specs, kv_groups, slab_logical_bytes
from llm_training_tpu.infer.sampling import (
    SamplingConfig,
    sample_tokens_with_logprob,
)
from llm_training_tpu.models.base import PagedDecodeState
from llm_training_tpu.models.moe import IN_PLACE_GAUGE, reset_in_place_layers
from llm_training_tpu.ops.delta_rule import DELTA_STEP_GAUGES, reset_delta_step_calls
from llm_training_tpu.ops.paged_attention import CHUNK_KERNEL_GAUGE, reset_chunk_kernel_layers
from llm_training_tpu.resilience.chaos import get_chaos
from llm_training_tpu.serve.paged_cache import (
    TRASH_BLOCK,
    BlockAllocator,
    init_paged_pool,
    init_state_slab,
    init_window_pool,
    pool_bytes,
    resolve_block_size,
    window_page_budget,
)
from llm_training_tpu.serve.scheduler import (
    Scheduler,
    SchedulerConfig,
    ServeRequest,
    WindowGroup,
)
from llm_training_tpu.telemetry.profiling import (
    install_compile_listener,
    install_trace_annotator,
    mark_setup_ready,
)
from llm_training_tpu.telemetry.registry import get_registry
from llm_training_tpu.telemetry.trace import get_tracer, startup_lines, startup_summary

logger = logging.getLogger(__name__)

# newest terminals live_stats() scans for its rolling TTFT/TPOT
# percentiles: bounds the per-scrape cost on a long-lived server whose
# completed list grows without bound
_LIVE_WINDOW = 512

# terminals that are the engine SHEDDING load to protect its SLO, not
# request failures: counted as serve/requests_shed, never requests_failed
_SHED_REASONS = ("deadline", "overloaded")

# a wait for the device this long is a stall worth keeping past the ring's
# turnover (a step is 10 to 55 ms; the stalls met are 1 to 5 s: ROADMAP S8)
STALL_SECONDS = 0.5

# every call of a program but its first opens no `setup/first_call` span
_NO_SPAN = nullcontext()


def _split(packed, fields: dict[str, tuple[int, ...]]) -> dict:
    """The named fields of a call's packed int32 inputs, in the order and
    shapes of `fields`: views of the host's staging buffer, which the engine
    writes through, and slices of the traced array, which the program reads.
    One layout for both sides, one transfer a call."""
    out, at = {}, 0
    for name, shape in fields.items():
        size = math.prod(shape)
        out[name] = packed[at:at + size].reshape(shape)
        at += size
    return out


@dataclass(slots=True)
class _InFlight:
    """One enqueued call's outputs that the host has not read: the step that
    enqueued it, the rows it makes a token for as (request, slot) in the order
    they are emitted (a decode step's rows; a prompt's last chunk's one;
    none for any other chunk, which is kept only for its counts), and the
    device arrays: the engine's token carry as the call left it, the chosen
    tokens' log-probabilities ([max_batch], or a chunk's scalar) and a
    counting stack's three expert-assignment counts."""

    step: int
    rows: list[tuple[ServeRequest, int]]
    tokens: Any
    logprobs: Any
    moe: Any


@dataclass(slots=True)
class _KeptRow:
    """What the engine last wrote into a decode slot's rows of the kept block
    tables: whose pages (a request's residency is the request and its
    `evictions`), how many of the first group, and of the window group's ring
    the first page and how many."""

    request: ServeRequest
    residency: int
    blocks: int
    window_first: int
    window_blocks: int


class ServeConfig(BaseModel):
    """Serving knobs (docs/serving.md#knobs)."""

    model_config = ConfigDict(extra="forbid")

    max_batch: int = 4  # decode slots (static decode-program batch)
    max_model_len: int = 256  # per-request cap: prompt + generation
    # tokens per KV block; None resolves via ops/pallas/tuning.py
    # (PAGED_BLOCK_K env > tuning table > 16)
    block_size: int | None = None
    # pool capacity in blocks (excl. the trash block); None sizes for
    # max_batch full-length requests — no block pressure by default
    num_blocks: int | None = None
    prefill_chunk: int = 32  # tokens per prefill-chunk program call
    # intake bound: queued requests past this are shed with an honest
    # stop_reason='overloaded' terminal; None = unbounded
    max_queue: int | None = None
    # shed when the queue tail's projected TTFT (EMA service-time
    # estimate) crosses this many ms; None disables
    shed_ttft_ms: float | None = None
    cache_dtype: str | None = None
    seed: int = 0
    eos_token_id: int | None = None
    sampling: SamplingConfig = SamplingConfig()

    @model_validator(mode="after")
    def _validate(self) -> "ServeConfig":
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_model_len < 2:
            raise ValueError(
                f"max_model_len must be >= 2, got {self.max_model_len}"
            )
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}"
            )
        if self.max_queue is not None and self.max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {self.max_queue}")
        if self.shed_ttft_ms is not None and self.shed_ttft_ms <= 0:
            raise ValueError(
                f"shed_ttft_ms must be > 0, got {self.shed_ttft_ms}"
            )
        if self.block_size is not None and self.block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {self.block_size}")
        if self.num_blocks is not None and self.num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {self.num_blocks}")
        return self


class ServingEngine:
    """Drives a restored model under continuous batching. Construction
    mirrors `InferenceEngine` (model, variables, optional mesh+rules);
    traffic goes through `submit()` + `step()` (or `run()` for a closed
    request set)."""

    def __init__(
        self,
        model: Any,
        variables: Any,
        config: ServeConfig | None = None,
        mesh: Any | None = None,
        rules: Any = (),
    ):
        # the start-up timeline (docs/observability.md#tracing): jax's compile
        # events heard from here on, construction as one pinned span
        install_compile_listener()
        with get_tracer().measure("setup", "engine_init", pin=True):
            self._construct(model, variables, config, mesh, rules)

    def _construct(self, model, variables, config, mesh, rules) -> None:
        from llm_training_tpu.infer.engine import supports_decoding

        if not supports_decoding(model):
            raise NotImplementedError(
                f"{type(model).__name__} does not support KV-cache decoding "
                "(no decode_state in its __call__) — see docs/inference.md"
            )
        self.model = model
        self.variables = variables
        self.mesh = mesh
        self.rules = rules
        self.config = config or ServeConfig()

        model_config = model.config
        self.block_size = resolve_block_size(
            model_config, self.config.max_model_len,
            self.config.block_size, self.config.cache_dtype,
        )
        self.pages_per_request = math.ceil(
            self.config.max_model_len / self.block_size
        )
        num_blocks = self.config.num_blocks
        if num_blocks is None:
            num_blocks = self.config.max_batch * self.pages_per_request
        # the layers that keep a window, where the stack has such a group:
        # the window, the pages of it a request may hold, the pool's blocks
        # (as many as every row at its budget needs, at most `num_blocks`)
        first_group, window_group = kv_groups(model_config)
        # layers that read the first group's pages and append nothing
        # (`KVCacheSpec.readers`): what they read is counted a step
        self._shared_readers = max(
            (getattr(first_group, "readers", None) or 0) - first_group.layers, 0
        )
        self.sliding_window = self.window_pages = None
        window_blocks = 0
        if window_group is not None:
            self.sliding_window = window_group.window
            self.window_pages = window_page_budget(
                self.sliding_window, self.config.prefill_chunk, self.block_size,
                self.pages_per_request,
            )
            window_blocks = min(num_blocks, self.config.max_batch * self.window_pages)
        with self._ctx():
            self._pool_k, self._pool_v = init_paged_pool(
                model_config, num_blocks + 1, self.block_size,
                mesh=self.mesh, rules=self.rules,
                cache_dtype=self.config.cache_dtype,
            )
            # None for a stack with one group
            self._window_pool = init_window_pool(
                model_config, window_blocks + 1, self.block_size,
                mesh=self.mesh, rules=self.rules,
                cache_dtype=self.config.cache_dtype,
            )
            # None for a stack whose layers all cache keys and values
            self._slab = init_state_slab(
                model_config, self.config.max_batch, mesh=self.mesh,
                rules=self.rules, cache_dtype=self.config.cache_dtype,
            )
        self._cache_bytes = pool_bytes(self._pool_k, self._pool_v)  # outlives close()
        self._latent_pool = self._pool_v is None
        self._state_bytes = 0 if self._slab is None else pool_bytes(*self._slab)
        self._state_logical_bytes = 0 if self._slab is None else slab_logical_bytes(
            cache_specs(model_config)[1], self.config.max_batch, self._slab[1].dtype)
        self._window_bytes = (
            0 if self._window_pool is None else pool_bytes(*self._window_pool)
        )
        self.allocator = BlockAllocator(num_blocks + 1)
        self.window_allocator = (
            None if self._window_pool is None
            else BlockAllocator(window_blocks + 1, group="window")
        )
        self.scheduler = Scheduler(
            SchedulerConfig(
                max_batch=self.config.max_batch,
                max_model_len=self.config.max_model_len,
                block_size=self.block_size,
                prefill_chunk=self.config.prefill_chunk,
                max_queue=self.config.max_queue,
                shed_ttft_ms=self.config.shed_ttft_ms,
            ),
            self.allocator,
            None if self.window_allocator is None else WindowGroup(
                self.window_allocator, self.sliding_window, self.window_pages
            ),
        )
        # a stack that counts its expert assignments (`CausalLMOutput.
        # moe_assignments`: held here, zero-compute, held elsewhere): every
        # call returns its own three counts, read with the call's tokens
        self._counts_experts = bool(getattr(model_config, "counts_expert_assignments", False))
        self.scheduler.before_evict = lambda: self._flush("eviction")
        self._build_tables()
        self._build_programs()
        # a profiler capture of this process holds step()'s spans beside the
        # device's ops (docs/observability.md#tracing)
        install_trace_annotator()
        # the programs that have not run yet: the first invocation of each is
        # a pinned `setup/first_call` span, and the engine is ready (the
        # pinned instant `setup/ready`) once both have returned
        self._unrun = {"prefill_chunk", "decode_step"}
        # the longest wait for the device of the engine's life, as (wall
        # seconds, the process's CPU seconds across it, the step): ROADMAP S8
        self._longest_fetch = (0.0, 0.0, 0)
        # the running step's counts: filled where the work is decided, closed
        # into the engine_step span and the serve/* counters by step()
        self._step_counts: dict[str, int] = {}
        # the token each decode slot feeds to its next decode step, on the
        # device: both programs return it updated (a decode step the sampled
        # tokens of the rows that decoded, a chunk its token at its slot), so
        # a token never visits the host on its way into the next call. Never
        # donated: the array a call returned is what the host fetches later,
        # and it must outlive the next call
        self._last_tokens = jnp.zeros((self.config.max_batch,), jnp.int32)
        # enqueued calls whose outputs the host has not read, oldest first:
        # when a step begins, at most the calls of the step before
        self._in_flight: list[_InFlight] = []
        # events made and not yet returned: what the running step has made so
        # far, and what a flush inside `reload_weights`, `drain` or `close`
        # left for the next `step()` or `flush()` to return
        self._events: list[dict] = []
        self._rng = jax.random.key(self.config.seed)
        self._call = 0
        self._t0: float | None = None
        self._step_index = 0
        self.tokens_generated = 0
        self.peak_running = 0
        # hot weight reload (docs/serving.md#resilience): bumps on every
        # reload_weights; every emitted chunk carries the generation it was
        # decoded under
        self.weights_generation = 0
        # request journal (attach_journal): accepted/progress/done records
        # that let a supervised relaunch replay accepted-but-unfinished work
        self.journal = None
        self._journal_every = 1
        # terminals built but possibly not yet delivered to the caller:
        # their journal `done` records are deferred to the NEXT step (or
        # drain), by which point the CLI has flushed the chunks — a death
        # in between re-delivers a detectable duplicate terminal on replay
        # instead of silently losing one the journal claims was delivered
        self._unretired: list[ServeRequest] = []
        self.replayed_requests = 0
        # protocol-truth terminal counters (bumped in _done_event, the one
        # place every terminal passes): live_stats reads them so a scrape
        # never pays O(full completion history) — and they match the
        # client-side census by construction. Shed load (deadline/
        # overloaded — the engine protecting its SLO) is tallied apart
        # from real failures: conflating them poisons both RL rollout
        # accounting and the SLO error-rate stream
        self._done_full = 0
        self._done_shed = 0
        self._done_failed = 0
        # one-shot decode-step attribution (LLMT_PROFILE_ATTR_DECODE=1,
        # docs/observability.md#device-plane): the first real decode batch
        # supplies the concrete avals needed to AOT-lower the step for
        # cost/HLO analysis; off by default — it pays one extra XLA compile
        self._decode_attr_done = not bool(
            os.environ.get("LLMT_PROFILE_ATTR_DECODE")
        )

    # the pool, as the two attributes every caller knows. A caller that
    # drops the pool (`close()`, or setting either to None) drops the state
    # slab and the window group's pool with it: the caches live and die
    # together, whatever else still holds the engine.
    @property
    def _pool_k(self):
        return self._k

    @_pool_k.setter
    def _pool_k(self, value):
        self._k = value
        if value is None:
            self._slab = self._window_pool = None

    @property
    def _pool_v(self):
        return self._v

    @_pool_v.setter
    def _pool_v(self, value):
        self._v = value
        if value is None:
            self._slab = self._window_pool = None

    # ------------------------------------------------------------ programs

    def _ctx(self):
        from llm_training_tpu.infer.engine import mesh_context

        return mesh_context(self.mesh, self.rules)

    def _build_programs(self) -> None:
        model = self.model
        # a sparse MLP that reads its stacked experts in place says in how
        # many layers, when a program below is traced (models/moe.py)
        reset_in_place_layers()
        # and so does a chunk's attention that runs in the kernel
        # (ops/paged_attention.py)
        reset_chunk_kernel_layers()
        # and a delta-rule layer's one-token step, which of its two paths it
        # took (ops/delta_rule.py)
        reset_delta_step_calls()
        sampling = self.config.sampling
        rope_length = self.config.max_model_len

        def slab_fields(slab, **rows):
            if slab is None:
                return {}
            return {"state": slab[0], "conv": slab[1], **rows}

        def slab_of(state):
            return None if state.state is None else (state.state, state.conv)

        def window_fields(pool, tables):
            if pool is None:
                return {}
            return {"window_k": pool[0], "window_v": pool[1], "window_tables": tables}

        def with_window(outs, state):
            """A program's outputs, and last the window group's pool where the
            stack has one: any other stack returns what it always did."""
            if state.window_k is None:
                return outs
            return (*outs, (state.window_k, state.window_v))

        prefill_fields, decode_fields = self._prefill_fields, self._decode_fields
        last_position = self.config.max_model_len - 1
        counts_experts = self._counts_experts

        def prefill_chunk(variables, packed, pool_k, pool_v, rng, last_tokens, slab=None,
                          window_pool=None):
            sent = _split(packed, prefill_fields)
            tokens, start = sent["tokens"], sent["start"]
            # the chunk's first `tokens` columns are the row's positions
            # `start ..`; the others are padding (segment 0)
            column = jnp.arange(prefill_fields["ids"][1], dtype=jnp.int32)[None, :]
            state = PagedDecodeState(
                k=pool_k, v=pool_v, block_tables=sent["tables"], lengths=start[None],
                rope_length=rope_length,
                # the request's slot of the slab, read as zeros on its first chunk
                **slab_fields(slab, slots=sent["slot"][None], fresh=sent["fresh"][None] != 0),
                **window_fields(window_pool, sent.get("window_tables")),
            )
            out = model.apply(
                variables, input_ids=sent["ids"],
                segment_ids=(column < tokens).astype(jnp.int32),
                position_ids=jnp.minimum(start + column, last_position),
                decode_state=state,
            )
            logits = jax.lax.dynamic_index_in_dim(
                out.logits[0], tokens - 1, axis=0, keepdims=False
            ).astype(jnp.float32)
            with jax.named_scope("sample"):
                token, logprob = sample_tokens_with_logprob(
                    logits[None], jax.random.fold_in(rng, sent["call"]), sampling
                )
            state = out.decode_state
            # the slot's next decode step reads the token from here: only a
            # prompt's LAST chunk is followed by one, and it writes last
            last_tokens = last_tokens.at[sent["slot"]].set(token[0])
            moe = out.moe_assignments if counts_experts else None
            return with_window(
                (state.k, state.v, last_tokens, logprob[0], slab_of(state), moe), state
            )

        def decode_step(variables, packed, pool_k, pool_v, rng, last_tokens, slab=None,
                        window_pool=None):
            sent = _split(packed, decode_fields)
            lengths = sent["lengths"]
            state = PagedDecodeState(
                k=pool_k, v=pool_v, block_tables=sent["tables"], lengths=lengths,
                rope_length=rope_length, **slab_fields(slab),
                **window_fields(window_pool, sent.get("window_tables")),
            )
            # row i is slot i. A slot that does not decode this step (idle, or
            # its prompt still prefilling) has length 0 here: token 0 and
            # segment 0, so its state and tail come out as they went in, and a
            # stack that counts its expert assignments leaves it out
            alive = lengths > 0
            rows = {} if slab is None and not counts_experts else {
                "segment_ids": alive.astype(jnp.int32)[:, None]
            }
            out = model.apply(
                variables, input_ids=jnp.where(alive, last_tokens, 0)[:, None],
                position_ids=lengths[:, None], decode_state=state, **rows,
            )
            logits = out.logits[:, -1].astype(jnp.float32)
            with jax.named_scope("sample"):
                token, logprob = sample_tokens_with_logprob(
                    logits, jax.random.fold_in(rng, sent["call"]), sampling
                )
            state = out.decode_state
            last_tokens = jnp.where(alive, token, last_tokens)
            moe = out.moe_assignments if counts_experts else None
            return with_window(
                (state.k, state.v, last_tokens, logprob, slab_of(state), moe), state
            )

        # the function names ARE the programs' names (`jit_prefill_chunk`,
        # `jit_decode_step` in HLO module names and in a device profile):
        # docs, chip_smoke.py and the benchmark's trace readers match them.
        # A contract, pinned by tests/test_serve_spans.py.
        # (the slab and the window group's pool go by keyword: a stack
        # without one is called as before)
        self._prefill_jit = jax.jit(
            prefill_chunk, donate_argnums=(2, 3), donate_argnames=("slab", "window_pool")
        )
        self._decode_jit = jax.jit(
            decode_step, donate_argnums=(2, 3), donate_argnames=("slab", "window_pool")
        )

    def _first_call(self, program: str):
        """What a program's invocation runs under: nothing, but for the FIRST
        one, which is the pinned span `setup/first_call` (its trace, lowering,
        compile or cache read and dispatch; the device's work is not waited
        for). A flag an engine, no wrapper left around the jitted call."""
        return self._first_call_span(program) if program in self._unrun else _NO_SPAN

    @contextmanager
    def _first_call_span(self, program: str):
        with get_tracer().measure("setup", "first_call", pin=True, program=program):
            yield
        self._unrun.discard(program)
        if not self._unrun:
            mark_setup_ready(loop="serve")

    def _build_tables(self) -> None:
        """What the engine keeps a decode slot for its lifetime and edits
        where it changes, instead of rebuilding it from the requests every
        step: the block tables (a group), and the staging buffer of each
        program's packed inputs with the views the host writes through."""
        batch, pages, window = self.config.max_batch, self.pages_per_request, self.window_pages
        self._tables = np.zeros((batch, pages), np.int32)
        self._window_tables = None if window is None else np.zeros((batch, window), np.int32)
        self._held: list[_KeptRow | None] = [None] * batch
        self._alive = np.zeros((batch, 1), bool)
        self._decode_fields = {"lengths": (batch,), "call": (), "tables": (batch, pages)}
        self._prefill_fields = {
            "ids": (1, self.config.prefill_chunk), "tokens": (), "start": (), "call": (),
            "slot": (), "fresh": (), "tables": (1, pages),
        }
        if window is not None:
            self._decode_fields["window_tables"] = (batch, window)
            self._prefill_fields["window_tables"] = (1, window)
        self._decode_packed, self._prefill_packed = (
            np.zeros((sum(math.prod(shape) for shape in fields.values()),), np.int32)
            for fields in (self._decode_fields, self._prefill_fields)
        )
        self._decode_sent = _split(self._decode_packed, self._decode_fields)
        self._prefill_sent = _split(self._prefill_packed, self._prefill_fields)

    def _table_row(self, request: ServeRequest, window: bool = False) -> np.ndarray:
        """The request's row of a group's block table, built whole: what a
        slot's kept row starts from when a residency begins, and what it must
        equal after every edit (tests/test_serve_tables.py). The window
        group's is a ring: logical page `p` in slot `p % window_pages`, and a
        slot whose page was given back (or never taken) names the trash block."""
        if not window:
            row = np.zeros((self.pages_per_request,), np.int32)
            row[: len(request.blocks)] = request.blocks
            return row
        row = np.zeros((self.window_pages,), np.int32)
        pages = request.window_first + np.arange(len(request.window_blocks))
        row[pages % self.window_pages] = request.window_blocks
        return row

    def _sync_row(self, request: ServeRequest) -> None:
        """Bring the kept rows of the request's slot up to its pages. A
        residency's first sight (a new tenant, or the same request back after
        an eviction) builds them whole; after that only what changed is
        written: the pages `_grow` appended, and of the window group's ring
        the slots whose pages `release_window` gave back (the trash block
        again). The scheduler does not know the tables: the engine compares
        what it wrote last with what the request holds."""
        slot, held = request.slot, self._held[request.slot]
        count, first = len(request.blocks), request.window_first
        window_count = len(request.window_blocks)
        if held is None or held.request is not request or held.residency != request.evictions:
            self._tables[slot] = self._table_row(request)
            if self._window_tables is not None:
                self._window_tables[slot] = self._table_row(request, window=True)
            self._held[slot] = _KeptRow(request, request.evictions, count, first, window_count)
            self._step_counts["table_writes"] += count + window_count
            return
        if count != held.blocks:
            self._tables[slot, held.blocks:count] = request.blocks[held.blocks:]
            self._step_counts["table_writes"] += count - held.blocks
            held.blocks = count
        if first != held.window_first or window_count != held.window_blocks:
            row, ring = self._window_tables[slot], self.window_pages
            written = held.window_first + held.window_blocks
            given_back = range(held.window_first, min(written, first))
            taken = range(max(written, first), first + window_count)
            # given back first: a page taken since may lie in the same slot
            for page in given_back:
                row[page % ring] = TRASH_BLOCK
            for page in taken:
                row[page % ring] = request.window_blocks[page - first]
            self._step_counts["table_writes"] += len(given_back) + len(taken)
            held.window_first, held.window_blocks = first, window_count

    # -------------------------------------------------------------- intake

    def submit(
        self,
        id: str,
        prompt: Sequence[int],
        max_new_tokens: int = 32,
        priority: int = 0,
        deadline_ms: float | None = None,
    ) -> list[dict]:
        """Queue one request; returns immediately-emittable events — a
        rejection completes synchronously, and enqueueing over the intake
        bound may shed a (possibly different) queued request with
        stop_reason='overloaded'. `deadline_ms` is a latency budget
        anchored at arrival; a non-positive one is already expired and
        terminates with stop_reason='deadline' on the spot."""
        if self._t0 is None:
            self._t0 = time.perf_counter()
        request = ServeRequest(
            # coerce every token NOW: a non-int prompt (e.g. a JSON string
            # that slipped through the CLI) must fail at submit — where the
            # caller's error handling lives — not steps later inside the
            # decode loop, taking every in-flight request with it
            id=str(id), prompt=[int(t) for t in prompt],
            max_new_tokens=int(max_new_tokens), priority=int(priority),
        )
        if deadline_ms is not None:
            request.deadline_s = request.arrival_s + float(deadline_ms) / 1000.0
        tracer = get_tracer()
        request.traced = tracer.sample_request()
        tracer.instant(
            "serve", "submit", ts=request.arrival_s, write=request.traced,
            request_id=request.id, prompt_len=len(request.prompt),
            max_new_tokens=request.max_new_tokens, priority=request.priority,
            **({"deadline_ms": float(deadline_ms)} if deadline_ms is not None else {}),
        )
        return self._ingest(request)

    def submit_resumed(self, entry: dict) -> list[dict]:
        """Resubmit one `replay_journal` entry after a relaunch: the
        journaled continuation folds in exactly like an eviction requeue
        (re-prefill of prompt + generated under the CURRENT weights), and
        the `emitted` watermark keeps already-streamed tokens from being
        re-sent. Deadlines re-anchor at the resumed arrival — the original
        clock died with the original process."""
        if self._t0 is None:
            self._t0 = time.perf_counter()
        request = ServeRequest(
            id=str(entry["id"]),
            prompt=[int(t) for t in entry["prompt"]],
            max_new_tokens=int(entry["max_new_tokens"]),
            priority=int(entry.get("priority", 0)),
        )
        request.generated = [int(t) for t in entry.get("generated", [])]
        # restore the per-token logprobs alongside the tokens; a journal
        # written before logprob collection pads with None (the rollout
        # collector treats such samples as unusable, never as zeros)
        logprobs = [
            None if lp is None else float(lp)
            for lp in (entry.get("logprobs") or [])
        ][: len(request.generated)]
        logprobs += [None] * (len(request.generated) - len(logprobs))
        request.logprobs = logprobs
        request.emitted = min(int(entry.get("emitted", 0)), len(request.generated))
        if entry.get("deadline_ms") is not None:
            request.deadline_s = (
                request.arrival_s + float(entry["deadline_ms"]) / 1000.0
            )
        tracer = get_tracer()
        request.traced = tracer.sample_request()
        tracer.instant(
            "serve", "submit", ts=request.arrival_s, write=request.traced,
            request_id=request.id, prompt_len=len(request.prompt),
            max_new_tokens=request.max_new_tokens, priority=request.priority,
            replayed=True, generated=len(request.generated),
        )
        self.replayed_requests += 1
        if len(request.generated) >= request.max_new_tokens:
            # the journal caught the final token but not the done record:
            # nothing left to decode — retire it here
            request.stop_reason = "max_tokens"
            request.advance_phase("done")
            self.scheduler.completed.append(request)
            return [self._done_event(request)]
        return self._ingest(request)

    def _ingest(self, request: ServeRequest) -> list[dict]:
        """Hand one constructed request to the scheduler and emit the
        terminals submission itself produced (rejection, shed victims)."""
        before = len(self.scheduler.completed)
        self.scheduler.submit(request)
        if not request.done and self.journal is not None:
            self.journal.accepted(request)
            if request.generated or request.emitted:
                # a replayed request's folded continuation must survive a
                # SECOND death immediately: acceptance alone records only
                # the prompt, and the rotation backup is deleted once
                # replay completes
                self.journal.progress(request)
        return [
            self._done_event(completed)
            for completed in self.scheduler.completed[before:]
        ]

    # ---------------------------------------------------------- resilience

    def attach_journal(self, journal, every: int = 1) -> None:
        """Record request lifetimes into `journal` (serve/journal.py);
        progress checkpoints are written every `every` engine steps (and
        always at drain)."""
        self.journal = journal
        self._journal_every = max(1, int(every))

    def _journal_in_flight(self) -> None:
        """Checkpoint the requests an unread call makes a token for, as their
        clients have them: among them the ones that finished by length when
        that call was enqueued, which are in no queue any more and whose
        terminal waits for it."""
        if self.journal is not None:
            for call in self._in_flight:
                for request, _ in call.rows:
                    self.journal.progress(request)

    def _retire_finished(self) -> None:
        """Write the deferred `done` records for terminals the caller has
        had a chance to deliver (everything built before this step)."""
        if self.journal is None or not self._unretired:
            return
        retired, self._unretired = self._unretired, []
        for request in retired:
            self.journal.finished(request)

    def reload_weights(self, variables: Any) -> int:
        """Hot-swap the model weights between engine steps
        (docs/serving.md#reload): every running request is evicted through
        the standard fold-in requeue — its paged KV was computed under the
        OLD weights and must not be decoded against the new ones — then the
        new buffers are bound and `serve/weights_generation` bumps. In-
        flight streams continue token-identically to a fresh engine on the
        new weights fed prompt + tokens-so-far; nothing is dropped or
        re-streamed. `variables` must be restore_for_inference-shaped: the
        same tree/shapes/dtypes (and shardings under a mesh) the engine was
        built with. Returns the new generation."""
        old = jax.tree.structure(self.variables)
        new = jax.tree.structure(variables)
        if old != new:
            raise ValueError(
                "reload_weights: variable tree mismatch — the reload must "
                "be the same architecture restored the same way "
                f"(got {new}, engine holds {old})"
            )
        for old_leaf, new_leaf in zip(
            jax.tree.leaves(self.variables), jax.tree.leaves(variables)
        ):
            if (
                getattr(old_leaf, "shape", None) != getattr(new_leaf, "shape", None)
                or getattr(old_leaf, "dtype", None) != getattr(new_leaf, "dtype", None)
            ):
                raise ValueError(
                    "reload_weights: leaf shape/dtype mismatch "
                    f"({getattr(new_leaf, 'shape', None)}/"
                    f"{getattr(new_leaf, 'dtype', None)} vs engine's "
                    f"{getattr(old_leaf, 'shape', None)}/"
                    f"{getattr(old_leaf, 'dtype', None)})"
                )
        # the tokens in flight were made under the old weights: read first, so
        # they carry the old generation and fold into the requeued prompts
        self._flush("reload_weights")
        evicted = 0
        for request in list(self.scheduler.running.values()):
            self.scheduler.evict(request)
            evicted += 1
        self.variables = variables
        self.weights_generation += 1
        get_registry().gauge("serve/weights_generation").set(
            float(self.weights_generation)
        )
        logger.info(
            "weights reloaded: generation %d (%d in-flight request(s) "
            "folded for re-prefill)", self.weights_generation, evicted,
        )
        return self.weights_generation

    def drain(self) -> dict:
        """Evict-and-journal everything in flight — the graceful-shutdown
        tail (docs/serving.md#drain). Running requests fold their progress
        through the standard eviction requeue (freeing EVERY pool block, so
        a drained engine never leaks), then every queued request is
        checkpointed to the journal for a relaunch to `submit_resumed`. No
        terminal chunks are emitted: the relaunch owes them. Returns a
        summary for the drain trace event."""
        # the drain caller has emitted every returned event by now
        self._retire_finished()
        # what the last call made is journaled with the rest. A caller that
        # wants those tokens streamed takes them with `flush()` BEFORE it
        # drains; what is read here has nobody to go to, so it is journaled
        # as generated and not streamed, and the relaunch streams it
        self._journal_in_flight()
        self._flush("drain", stream=False)
        for request in list(self.scheduler.running.values()):
            self.scheduler.evict(request)
        journaled = 0
        for request in self.scheduler.waiting:
            if self.journal is not None:
                self.journal.progress(request)
                journaled += 1
        in_use = self.allocator.blocks_in_use + (
            0 if self.window_allocator is None else self.window_allocator.blocks_in_use
        )
        summary = {"journaled": journaled, "blocks_in_use": in_use, "step": self._step_index}
        get_tracer().instant("serve", "drain", **summary)
        logger.warning(
            "drain: %d unfinished request(s) journaled for replay "
            "(%d pool blocks in use)", journaled, in_use,
        )
        return summary

    def close(self) -> None:
        """Wait for the pool's last write, then give the pool's (and the
        state slab's) device memory back: for a caller that keeps the
        weights and needs the room (a reference pass after serving, a
        reload into a larger pool). The engine cannot step afterwards;
        `stats()` still answers. Idempotent."""
        if self._pool_k is None:
            return
        self._flush("close")
        jax.block_until_ready((self._pool_k, self._pool_v, self._slab, self._window_pool))
        for buffer in (self._pool_k, self._pool_v, *(self._slab or ()),
                       *(self._window_pool or ())):
            if buffer is not None:
                buffer.delete()
        self._pool_k = self._pool_v = None

    # ---------------------------------------------------------------- step

    def step(self) -> list[dict]:
        """One scheduler round: deadline expiry, admissions, shedding, at
        most one prefill chunk and one decode step over every decoding row,
        both ENQUEUED; then the outputs of the calls the step before enqueued
        are read and emitted, while this step's run. Returns the streamed
        events ({'type': 'token', ...} per new token, {'type': 'done', ...}
        per completion — deadline/overloaded terminations included): a token
        leaves in the step after the one whose call made it, and `flush()`
        gives a caller that stops stepping the last ones."""
        tracer = get_tracer()
        self._step_index += 1
        step = self._step_index
        # one bookkeeping, two readers: the engine_step span's closing args
        # and the cumulative serve/* counters (docs/observability.md#tracing)
        counts = self._step_counts = {
            # `prefill_start`: the tokens the chunk's row held before the chunk
            "prefill_chunks": 0, "prefill_tokens": 0, "prefill_start": 0,
            "decode_rows": 0, "live_tokens": 0,
            # block-table entries the host wrote, both groups (`_sync_row`)
            "table_writes": 0,
            # the step ahead of its fetches: 1 where a call was enqueued while
            # an earlier step's outputs were unread, the times the host had to
            # read before it could go on, and the rows decoded for nothing (a
            # request that had stopped at `eos` a call earlier)
            "steps_ahead": 0, "pipeline_flushes": 0, "discarded_row_steps": 0,
        }
        if self._slab is not None:
            counts["state_resets"] = 0
        if self.window_allocator is not None:
            counts["window_live_tokens"] = counts["window_pages_released"] = 0
        if self._shared_readers:
            # layer-reads of pages by layers that appended nothing to them
            counts["shared_kv_reads"] = 0
        # every child span below runs on this thread inside engine_step and
        # carries the step index; nesting by time gives the parent. Children
        # go to the ring and the profiler only (`write=False`): trace.jsonl
        # keeps one engine_step a step, and a sampled request's prefill_chunk
        child = {"step": step, "write": False}
        with tracer.measure(
            "serve", "engine_step", step=step,
            running=len(self.scheduler.running),
            waiting=len(self.scheduler.waiting),
        ) as closing, self._ctx():
            with tracer.measure("serve", "housekeeping", **child):
                # terminals and token chunks returned from the PREVIOUS step
                # have been delivered by now (the caller emits between
                # steps): retire finished ids and checkpoint progress/emitted
                # watermarks before this step can wedge or die. Journaling
                # either at build time would let a death between build and
                # flush lose a terminal (or skip re-streaming tokens the
                # client never saw): so not while events that a flush between
                # two steps left behind wait to be returned. A token in flight
                # is in no record: a replay makes it again.
                if not self._events:
                    self._retire_finished()
                    if self.journal is not None and step % self._journal_every == 0:
                        for request in self.scheduler.running.values():
                            self.journal.progress(request)
                        self._journal_in_flight()
                # chaos serve faults (docs/resilience.md#chaos): a wedged
                # step and a mid-stream SIGTERM are injected exactly where
                # the real ones land — the top of an engine step, heartbeat
                # already owed
                chaos = get_chaos()
                if chaos is not None:
                    chaos.maybe_serve_stall(step)
                    chaos.maybe_serve_sigterm_mid_stream(step)
            with tracer.measure("serve", "schedule", **child):
                # deadlines first: expired queued work never costs a FLOP
                # and an expired decode row frees its blocks before
                # admission looks at the pool. Its terminal carries its
                # tokens: the ones in flight are read first
                now = time.perf_counter()
                if any(
                    r.in_flight and r.deadline_s is not None and now >= r.deadline_s
                    for r in self.scheduler.running.values()
                ):
                    self._flush("deadline")
                before = len(self.scheduler.completed)
                self.scheduler.expire_deadlines(now)
                self.scheduler.admit()
                # the service-time EMA moves with every completion, so the
                # projected-TTFT shed decision is re-evaluated each step too
                self.scheduler.shed()
                # scheduler-side completions (capacity/deadline/overloaded)
                # are completions — the protocol owes each a done chunk like
                # any other
                for request in self.scheduler.completed[before:]:
                    self._events.append(self._done_event(request))
                self.peak_running = max(
                    self.peak_running, len(self.scheduler.running)
                )
                plan = self.scheduler.next_prefill()
            if plan is not None:
                self._run_prefill(*plan)
            # after the prefill: a prompt completed this step decodes this step
            with tracer.measure("serve", "schedule", **child):
                rows = self.scheduler.decode_rows()
            if rows:
                self._run_decode(rows)
            # the chip has this step's calls to run: now read the step
            # before's, which are done or nearly
            self._read([call for call in self._in_flight if call.step < step])
            if self._slab is not None:
                # slots whose state belongs to a live request after this step
                counts["state_slots_in_use"] = len(self.scheduler.running)
            closing.update(counts)
        registry = get_registry()
        registry.counter("serve/steps").inc()
        self.allocator.publish()
        if self.window_allocator is not None:
            self.window_allocator.publish()
        if counts["table_writes"]:
            registry.counter("serve/table_writes").inc(counts["table_writes"])
        if self._slab is not None:
            registry.gauge("decode/state_slots_in_use").set(counts["state_slots_in_use"])
            if counts["state_resets"]:
                registry.counter("serve/state_resets").inc(counts["state_resets"])
        for name in ("window_live_tokens", "window_pages_released", "shared_kv_reads"):
            if counts.get(name):
                registry.counter(f"serve/{name}").inc(counts[name])
        if counts["prefill_chunks"]:
            registry.counter("serve/prefill_chunks").inc(counts["prefill_chunks"])
        if counts["decode_rows"]:
            registry.counter("serve/decode_steps").inc()
            registry.counter("serve/decode_rows").inc(counts["decode_rows"])
            registry.counter("serve/live_tokens").inc(counts["live_tokens"])
        return self._take_events()

    # ------------------------------------------------- a step ahead of its fetches

    def flush(self) -> list[dict]:
        """Read what the enqueued calls made and return every event not yet
        returned: for a caller that stops stepping (its last tokens are one
        call behind), and after `reload_weights`, `drain` or `close`, which
        read for themselves and leave their events here. After it the
        engine's books are those of an engine that read every call at once;
        `flush()` after every `step()` IS that engine."""
        self._flush("caller")
        return self._take_events()

    @property
    def idle(self) -> bool:
        """Nothing queued, nothing running, and no token or event still
        owed: what a loop that steps until the work is done waits for (the
        scheduler alone is idle one call before the last tokens are read)."""
        return self.scheduler.idle and not self._in_flight and not self._events

    def _take_events(self) -> list[dict]:
        events, self._events = self._events, []
        return events

    def _flush(self, reason: str, stream: bool = True) -> bool:
        """The host needs a token's value (or stops): read every call in
        flight, the running step's too. False when there was none."""
        if not self._in_flight:
            return False
        self._count("pipeline_flushes")
        with get_tracer().measure(
            "serve", "pipeline_flush", step=self._step_index, write=False, reason=reason,
        ):
            self._read(list(self._in_flight), stream)
        return True

    def _count(self, name: str) -> None:
        """One of the step ahead's three counts, which can move outside a
        step (a flush between two): into the registry at once, and into the
        closing args of the step that is running or ran last."""
        get_registry().counter(f"serve/{name}").inc()
        self._step_counts[name] = self._step_counts.get(name, 0) + 1

    def _enqueued(self, rows: list[tuple[ServeRequest, int]], tokens, logprobs, moe) -> None:
        """The books of a call just enqueued, which need no token's value:
        each row's token is in flight, and a request whose last token it is
        gives its slot and pages back now (what the device still does with
        them lies in front of any later call in its queue). The done EVENT
        waits for the tokens."""
        if (self._in_flight and self._in_flight[0].step < self._step_index
                and not self._step_counts["steps_ahead"]):
            self._count("steps_ahead")
        if rows or moe is not None:
            self._in_flight.append(_InFlight(self._step_index, rows, tokens, logprobs, moe))
        for request, _ in rows:
            request.in_flight += 1
            if len(request.generated) + request.in_flight >= request.max_new_tokens:
                self.scheduler.finish(request, "max_tokens")

    def _read(self, calls: list[_InFlight], stream: bool = True) -> None:
        """Fetch the given calls' outputs (the oldest in flight) in one
        `device_get` and emit their tokens in the order they were enqueued
        (`stream` False: into `generated` only, no event made: `drain`)."""
        if not calls:
            return
        child = {"step": self._step_index, "write": False}
        del self._in_flight[: len(calls)]
        # the wait for the device, where the newest of them still runs
        cpu, t = time.process_time(), time.perf_counter()
        with get_tracer().measure("serve", "decode_fetch", **child):
            fetched = jax.device_get([(c.tokens, c.logprobs, c.moe) for c in calls])
        waited = time.perf_counter() - t
        if waited > self._longest_fetch[0]:
            # where a step that reads far off lost its time: a wait that the
            # process spent on the CPU is the host's, one it slept through is
            # the device's or the runtime's
            self._longest_fetch = (waited, time.process_time() - cpu, self._step_index)
            if waited > STALL_SECONDS:
                get_tracer().instant(
                    "serve", "stall", pin=True, step=self._step_index,
                    fetch_s=waited, cpu_s=self._longest_fetch[1],
                )
        with get_tracer().measure("serve", "decode_emit", **child):
            for call, (tokens, logprobs, moe) in zip(calls, fetched):
                if moe is not None:
                    for kind, n in zip(("held", "zero", "elsewhere"), moe):
                        get_registry().counter(f"serve/moe_{kind}_assignments").inc(int(n))
                for request, slot in call.rows:
                    self._emit_token(
                        request, int(tokens[slot]),
                        float(logprobs[slot] if logprobs.ndim else logprobs), stream,
                    )

    def _emit_token(
        self, request: ServeRequest, token: int, logprob: float, stream: bool = True
    ) -> None:
        request.in_flight -= 1
        if request.stop_reason == "eos":
            # it had stopped a call earlier, which the host saw only after
            # this row was enqueued: a row-step for nothing, its write in a
            # page (and slot) the request still held then
            self._count("discarded_row_steps")
            return
        now = time.perf_counter()
        request.generated.append(token)
        # parallel to `generated`: the chosen token's logprob under the
        # sampled distribution (rollout collection trains on these). None
        # only for tokens restored from a pre-logprob journal.
        request.logprobs.append(logprob)
        self.tokens_generated += 1
        if request.first_token_s is None:
            request.first_token_s = now
            get_tracer().instant(
                "serve", "first_token", ts=now, write=request.traced,
                request_id=request.id,
                # the same arrival-anchored value stats()/done events carry:
                # an evicted-then-resumed request's TTFT is measured from
                # its ORIGINAL arrival, never the requeue
                ttft_ms=round(1000.0 * (now - request.arrival_s), 3),
            )
        request.last_token_s = now
        # an evicted-then-resumed request regenerates nothing (its progress
        # rode along in the re-prefill), so every append past `emitted` is
        # genuinely new — emit it
        while stream and request.emitted < len(request.generated):
            self._events.append({
                "type": "token", "id": request.id,
                "token": request.generated[request.emitted],
                "logprob": request.logprobs[request.emitted],
                # the weights generation this token was decoded under — a
                # mid-stream reload_weights is visible exactly where it
                # landed (docs/serving.md#reload)
                "generation": self.weights_generation,
            })
            request.emitted += 1
        eos = self.config.eos_token_id
        if eos is not None and token == eos:
            if request.done:
                # its length ran out at the enqueue of a later call, whose
                # token is dropped when it comes
                request.stop_reason = "eos"
            else:
                self.scheduler.finish(request, "eos")
            finished = True
        else:
            # by length: it finished when its last call was enqueued
            finished = len(request.generated) >= request.max_new_tokens
        if finished and stream:
            self._events.append(self._done_event(request))

    def _run_prefill(self, request: ServeRequest, chunk: list[int], start: int) -> None:
        tracer = get_tracer()
        ids = {"step": self._step_index, "request_id": request.id}
        final = start + len(chunk) >= len(request.prefill_tokens)
        self._step_counts["prefill_chunks"] = 1
        self._step_counts["prefill_tokens"] = len(chunk)
        self._step_counts["prefill_start"] = start
        # a residency's first chunk (admission, or the requeue after an
        # eviction: both restart at 0) starts from a zero state, whatever the
        # slot's last tenant left
        fresh = start == 0
        if fresh and self._slab is not None:
            self._step_counts["state_resets"] = 1
        if self._shared_readers:
            self._step_counts["shared_kv_reads"] += self._shared_readers * math.ceil(
                (start + len(chunk)) / self.block_size
            )
        with tracer.measure(
            "serve", "prefill_chunk", write=request.traced, **ids,
            start=start, tokens=len(chunk), final=final,
        ):
            # inputs and the enqueue; the device's time shows in a later
            # decode_fetch
            with tracer.measure("serve", "prefill_dispatch", write=False, **ids):
                self._sync_row(request)
                sent = self._prefill_sent
                sent["ids"][0, : len(chunk)] = chunk
                sent["ids"][0, len(chunk):] = 0
                sent["tokens"][...] = len(chunk)
                sent["start"][...] = start
                self._call += 1
                sent["call"][...] = self._call
                sent["slot"][...] = request.slot
                sent["fresh"][...] = fresh
                sent["tables"][0] = self._tables[request.slot]
                caches = {} if self._slab is None else {"slab": self._slab}
                if self._window_pool is not None:
                    caches["window_pool"] = self._window_pool
                    sent["window_tables"][0] = self._window_tables[request.slot]
                # a COPY of the staging buffer travels with the call, the one
                # transfer: the runtime may read a numpy argument after the
                # call returns (the CPU backend aliases an aligned one
                # outright), and the next chunk fills the buffer while this
                # one may still be in the device's queue
                with self._first_call("prefill_chunk"):
                    (self._pool_k, self._pool_v, self._last_tokens, logprob, self._slab,
                     moe) = self._take_window_pool(self._prefill_jit(
                        self.variables, self._prefill_packed.copy(), self._pool_k, self._pool_v,
                        self._rng, self._last_tokens, **caches,
                    ))
            request.prefilled += len(chunk)
            request.cache_len += len(chunk)
            self._release_window(request)
            # the prompt's last chunk makes the first new token: in flight
            self._enqueued(
                [(request, request.slot)] if final else [], self._last_tokens, logprob, moe
            )
        if final and not request.done:
            # decode (one token per engine step) starts here: at this call's
            # own clock reading, a few microseconds after prefill_chunk closed
            request.advance_phase("decode")

    def _take_window_pool(self, outs: tuple) -> tuple:
        """A program's outputs less the window group's pool, which a stack
        with such a group returns last: kept, as the program left it."""
        if self._window_pool is None:
            return outs
        *outs, self._window_pool = outs
        return outs

    def _release_window(self, request: ServeRequest) -> None:
        """After a chunk or a decode step is enqueued: the request's pages of
        the window group that no later token reads go back to that group's
        allocator."""
        if self.window_allocator is not None:
            self._step_counts["window_pages_released"] += (
                self.scheduler.release_window(request)
            )

    def _run_decode(self, rows: list[ServeRequest]) -> None:
        tracer = get_tracer()
        child = {"step": self._step_index, "write": False}
        with tracer.measure("serve", "decode_blocks", **child):
            # grow each row's blocks for this step's write; under pool
            # pressure this evicts lowest-priority requests (possibly out of
            # `rows`), after the tokens in flight are read
            survivors = []
            for request in rows:
                if request.slot is not None and self.scheduler.ensure_decode_blocks(request):
                    survivors.append(request)
            # a LATER row's block-pressure eviction can take an EARLIER
            # survivor (lower priority, mid-page so its own check passed) —
            # its slot is gone and its blocks may already belong to the
            # evictor, so it must not decode this step
            survivors = [r for r in survivors if r.slot is not None]
        if not survivors:
            return
        with tracer.measure("serve", "decode_inputs", **child):
            sent, alive = self._decode_sent, self._alive
            lengths = sent["lengths"]
            lengths[:] = 0
            alive[:] = False
            for request in survivors:
                self._sync_row(request)
                lengths[request.slot] = request.cache_len
                alive[request.slot] = True
            # a slot that does not decode this step (idle, its prompt still
            # prefilling, evicted a moment ago) is handed a row of zeros, as
            # its length: its append lands in the trash block, not in position
            # 0 of a page its kept row names
            np.multiply(self._tables, alive, out=sent["tables"])
            self._call += 1
            sent["call"][...] = self._call
            self._step_counts["decode_rows"] = len(survivors)
            # what the paged kernel reads this call: each row's cache and its
            # new token
            self._step_counts["live_tokens"] = int(lengths.sum()) + len(survivors)
            if self._shared_readers:
                self._step_counts["shared_kv_reads"] += self._shared_readers * sum(
                    r.cache_len // self.block_size + 1 for r in survivors
                )
            step_slab = {} if self._slab is None else {"slab": self._slab}
            if self._window_pool is not None:
                step_slab["window_pool"] = self._window_pool
                np.multiply(self._window_tables, alive, out=sent["window_tables"])
                # what a window layer's call reads: of each row, its window
                self._step_counts["window_live_tokens"] = sum(
                    min(r.cache_len + 1, self.sliding_window) for r in survivors
                )
            # (a copy, as a chunk's: what a call was handed is never written
            # again; each row's token is on the device already)
            step_args = (
                self.variables, self._decode_packed.copy(), self._pool_k, self._pool_v, self._rng,
                self._last_tokens,
            )
        if not self._decode_attr_done:
            # before the donating call below: lowering only reads avals,
            # while the jit consumes the pool buffers
            self._decode_attr_done = True
            self._publish_decode_attribution(step_args, step_slab)
        # the enqueue and the books that need no token's value: a step that
        # reads far off shows whether its seconds went here or in the wait
        with tracer.measure("serve", "decode_dispatch", **child):
            with self._first_call("decode_step"):
                (self._pool_k, self._pool_v, self._last_tokens, logprobs, self._slab,
                 moe) = self._take_window_pool(self._decode_jit(*step_args, **step_slab))
            for request in survivors:
                request.cache_len += 1
                self._release_window(request)
            self._enqueued([(r, r.slot) for r in survivors], self._last_tokens, logprobs, moe)

    def _publish_decode_attribution(self, step_args, step_slab) -> None:
        """AOT-lower the decode step against the first real batch's avals
        and publish its compute/comm split as attr/decode/* gauges
        (docs/observability.md#device-plane). The lowering pays one extra
        XLA compile — why LLMT_PROFILE_ATTR_DECODE gates this off by
        default; any failure degrades to a warning, never a dropped step."""
        try:
            from llm_training_tpu.telemetry.device import (
                compiled_attribution_gauges,
            )

            with self._ctx():
                compiled = self._decode_jit.lower(*step_args, **step_slab).compile()
            mesh_axes = None
            if self.mesh is not None:
                mesh_axes = dict(
                    zip(self.mesh.axis_names, self.mesh.devices.shape)
                )
            registry = get_registry()
            for name, value in compiled_attribution_gauges(
                compiled, mesh_axes
            ).items():
                registry.gauge(
                    "attr/decode/" + name.removeprefix("attr/")
                ).set(value)
        except Exception as e:  # noqa: BLE001 — attribution is best-effort
            logger.warning("decode-step attribution unavailable: %s", e)

    def _done_event(self, request: ServeRequest) -> dict:
        if request.stop_reason in ("eos", "max_tokens"):
            self._done_full += 1
        elif request.stop_reason in _SHED_REASONS:
            self._done_shed += 1
        else:
            self._done_failed += 1
        if self.journal is not None:
            self._unretired.append(request)
        event = {
            "type": "done", "id": request.id,
            "stop_reason": request.stop_reason,
            "tokens": list(request.generated),
            "logprobs": list(request.logprobs),
            "n_tokens": len(request.generated),
            "evictions": request.evictions,
            "generation": self.weights_generation,
        }
        if request.first_token_s is not None:
            event["ttft_ms"] = round(
                1000.0 * (request.first_token_s - request.arrival_s), 3
            )
        if request.last_token_s is not None and len(request.generated) > 1:
            event["tpot_ms"] = round(
                1000.0 * (request.last_token_s - request.first_token_s)
                / (len(request.generated) - 1), 3,
            )
        get_tracer().instant(
            "serve", "done", write=request.traced, request_id=request.id,
            stop_reason=request.stop_reason, n_tokens=len(request.generated),
            evictions=request.evictions,
            queue_wait_ms=round(1000.0 * request.queue_wait_s, 3),
            **({"ttft_ms": event["ttft_ms"]} if "ttft_ms" in event else {}),
        )
        return event

    # ----------------------------------------------------------------- run

    def run(self, requests: Sequence[dict], max_steps: int = 100_000) -> list[dict]:
        """Submit a closed request set and step until drained. Each request
        dict: {'id', 'prompt', 'max_new_tokens'?, 'priority'?}. Returns all
        events in emission order."""
        events: list[dict] = []
        for request in requests:
            events.extend(self.submit(**request))
        for _ in range(max_steps):
            if self.scheduler.idle:
                break
            events.extend(self.step())
        else:
            raise RuntimeError(f"serve loop not drained after {max_steps} steps")
        events.extend(self.flush())  # the last call's tokens, a call behind
        return events

    # --------------------------------------------------------------- stats

    def _completed_latencies(self) -> tuple[list, list, list[float], list[float]]:
        """(all terminals, full completions, ttft_ms, tpot_ms) over the
        requests finished so far — the ONE filter + latency math both
        `stats()` and `live_stats()` render, so the scraped live
        percentiles can never disagree with the end-of-run record. Pure
        host reads (list snapshot under the GIL) — safe from the
        exporter's scrape threads."""
        completed_all = list(self.scheduler.completed)
        completed = [
            r for r in completed_all if r.stop_reason in ("eos", "max_tokens")
        ]
        ttft = [
            1000.0 * (r.first_token_s - r.arrival_s)
            for r in completed if r.first_token_s is not None
        ]
        tpot = [
            1000.0 * (r.last_token_s - r.first_token_s) / (len(r.generated) - 1)
            for r in completed
            if r.last_token_s is not None and len(r.generated) > 1
        ]
        return completed_all, completed, ttft, tpot

    def live_stats(self) -> dict[str, float]:
        """Scrape-time gauges for the live-telemetry exporter
        (docs/observability.md#live-telemetry): queue depth, in-flight
        rows, and rolling completion/latency numbers. The latency scan is
        bounded to the newest `_LIVE_WINDOW` terminals — on a long-lived
        server `scheduler.completed` grows without bound, and a 2 Hz
        Prometheus scrape must not pay O(full request history) per scrape
        (rolling percentiles over recent completions are also the more
        honest live signal). Counts stay exact (len() is O(1); the
        failed tally rides the schedulers' terminal counters). Called
        from the exporter's handler threads — read-only over host state,
        never a jax call, so a scrape can never perturb or block the
        decode loop."""
        recent = self.scheduler.completed[-_LIVE_WINDOW:]
        completed = [
            r for r in recent if r.stop_reason in ("eos", "max_tokens")
        ]
        ttft = [
            1000.0 * (r.first_token_s - r.arrival_s)
            for r in completed if r.first_token_s is not None
        ]
        tpot = [
            1000.0 * (r.last_token_s - r.first_token_s) / (len(r.generated) - 1)
            for r in completed
            if r.last_token_s is not None and len(r.generated) > 1
        ]
        out = {
            "serve/queue_depth": float(len(self.scheduler.waiting)),
            "serve/running": float(len(self.scheduler.running)),
            "serve/engine_steps": float(self._step_index),
            "serve/requests_completed": float(self._done_full),
            "serve/requests_failed": float(self._done_failed),
            "serve/requests_shed": float(self._done_shed),
            "serve/tokens_generated": float(self.tokens_generated),
            "serve/weights_generation": float(self.weights_generation),
            "decode/cache_blocks_in_use": float(self.allocator.blocks_in_use),
        }
        if ttft:
            out["serve/ttft_p50_ms"] = float(np.percentile(ttft, 50))
            out["serve/ttft_p99_ms"] = float(np.percentile(ttft, 99))
        if tpot:
            out["serve/tpot_p50_ms"] = float(np.percentile(tpot, 50))
            out["serve/tpot_p99_ms"] = float(np.percentile(tpot, 99))
        return out

    def stats(self) -> dict[str, float]:
        """Engine/latency summary, published as `serve/*` gauges (merged
        into telemetry.jsonl by the CLI; `report` renders `== Serving ==`)."""
        completed_all, completed, ttft, tpot = self._completed_latencies()
        wall = (time.perf_counter() - self._t0) if self._t0 is not None else 0.0
        n_chips = max(1, jax.device_count())
        tps = self.tokens_generated / wall if wall > 0 else 0.0
        # shed load (deadline/overloaded) is the engine protecting its SLO;
        # requests_failed is what remains — real errors (rejection etc.)
        shed = sum(
            1 for r in completed_all if r.stop_reason in _SHED_REASONS
        )
        stats = {
            "serve/requests_completed": float(len(completed)),
            "serve/requests_failed": float(
                len(completed_all) - len(completed) - shed
            ),
            "serve/requests_shed": float(shed),
            "serve/requests_evicted": float(self.scheduler.evictions),
            "serve/shed_total": float(self.scheduler.shed_total),
            "serve/deadline_total": float(self.scheduler.deadline_total),
            "serve/weights_generation": float(self.weights_generation),
            "serve/replayed_requests": float(self.replayed_requests),
            "serve/tokens_generated": float(self.tokens_generated),
            "serve/tokens_per_sec": tps,
            "serve/tokens_per_sec_per_chip": tps / n_chips,
            "serve/peak_running": float(self.peak_running),
            "decode/cache_bytes": float(self._cache_bytes),
            "decode/global_pool_bytes": float(self._cache_bytes),
            "decode/window_pool_bytes": float(self._window_bytes),
            "decode/state_bytes": float(self._state_bytes),
            "decode/state_logical_bytes": float(self._state_logical_bytes),
            "decode/latent_pool_bytes": float(
                self._cache_bytes if self._latent_pool else 0
            ),
            IN_PLACE_GAUGE: get_registry().gauge(IN_PLACE_GAUGE).value or 0.0,
            CHUNK_KERNEL_GAUGE: get_registry().gauge(CHUNK_KERNEL_GAUGE).value or 0.0,
            **{
                gauge: get_registry().gauge(gauge).value or 0.0
                for gauge in DELTA_STEP_GAUGES.values()
            },
            "decode/cache_blocks_total": float(self.allocator.num_blocks - 1),
            "decode/cache_blocks_in_use": float(self.allocator.blocks_in_use),
            "decode/cache_peak_blocks_in_use": float(self.allocator.peak_in_use),
            # the longest wait for the device of the engine's life (ROADMAP S8)
            "serve/longest_fetch_s": self._longest_fetch[0],
            "serve/longest_fetch_cpu_s": self._longest_fetch[1],
            "serve/longest_fetch_step": float(self._longest_fetch[2]),
            # process start to the engine's first useful step (0: not yet)
            "setup/ready_s": get_registry().gauge("setup/ready_s").value or 0.0,
        }
        if ttft:
            stats["serve/ttft_p50_ms"] = float(np.percentile(ttft, 50))
            stats["serve/ttft_p99_ms"] = float(np.percentile(ttft, 99))
        if tpot:
            stats["serve/tpot_p50_ms"] = float(np.percentile(tpot, 50))
            stats["serve/tpot_p99_ms"] = float(np.percentile(tpot, 99))
        counts = get_tracer().counts()
        stats["trace/events_recorded"] = float(counts["recorded"])
        stats["trace/events_written"] = float(counts["written"])
        stats["trace/requests_sampled"] = float(counts["requests_sampled"])
        registry = get_registry()
        for key, value in stats.items():
            registry.gauge(key).set(value)
        # what step() counted (counters already: read into the summary, not
        # published twice): the rows decoded and the block-table entries the
        # host wrote for them, `rows / block_size` where nothing else happens
        counted = [
            "serve/steps", "serve/decode_rows", "serve/table_writes",
            # the step ahead of its fetches (docs/serving.md, "A step ahead")
            "serve/steps_ahead", "serve/pipeline_flushes", "serve/discarded_row_steps",
            # programs handed to the backend after `setup/ready`: recompiles
            "compile/after_ready",
        ]
        if self._counts_experts:
            counted += [f"serve/moe_{kind}_assignments" for kind in ("held", "zero", "elsewhere")]
        self.allocator.publish()
        if self.window_allocator is not None:
            # the window group's allocator publishes its own gauges
            self.window_allocator.publish()
            stats["decode/window_blocks_total"] = float(self.window_allocator.num_blocks - 1)
            stats["decode/window_blocks_in_use"] = float(self.window_allocator.blocks_in_use)
            stats["decode/window_peak_blocks_in_use"] = float(self.window_allocator.peak_in_use)
            counted += ["serve/window_live_tokens", "serve/window_pages_released"]
        for key in counted:
            stats[key] = float(registry.counter(key).value)
        logger.info(
            "serve: %d completed (%d evictions) | %.1f tokens/s (%.1f/chip)",
            len(completed), self.scheduler.evictions, tps, stats["serve/tokens_per_sec_per_chip"],
        )
        startup = startup_summary(get_tracer().pinned())
        for line in startup_lines(startup) if startup else ():
            logger.info(line)
        return stats
