"""Continuous-batching request scheduler (docs/serving.md). Pure host
logic — no jax — so policy is unit-testable without a model.

Per engine step the scheduler decides three things:

- **admission**: the head of the waiting queue joins when a decode slot is
  free AND the pool has blocks for its whole (re)prefill plus one decode
  block of headroom — all-or-nothing, so a half-admitted request can never
  deadlock the pool;
- **chunked prefill**: at most ONE fixed-width prompt chunk per step, so a
  long prompt streams into its blocks across steps while every in-flight
  decode row keeps producing a token per step (the interleave that keeps
  TTFT of short requests flat under long-prompt traffic);
- **eviction**: when a decode row needs its next block and the pool is
  dry, the LOWEST-priority running request (ties: youngest arrival) is
  evicted — blocks freed, request requeued at the FRONT of the waiting
  queue with its progress folded into the prompt (`prompt + generated`),
  so on re-admission it re-prefills and CONTINUES; greedy decode makes the
  continuation token-identical to an uninterrupted run.

A stack whose layers differ in how much of the past they keep has a pool a
GROUP (`serve/paged_cache.py`), and the scheduler an allocator a group:
`allocator` for the layers that keep every token, and `window` (a
`WindowGroup`: the allocator of the layers that keep `sliding_window`, that
window and the page budget, which the engine derives together). A request takes pages of both or of
neither; its pages of the first group it holds from admission on, as ever;
of the window group it holds the pages its window and its next chunk reach,
taken chunk by chunk (`next_prefill`) and token by token
(`ensure_decode_blocks`) and GIVEN BACK (`release_window`) as soon as they lie
wholly in front of `cache_len - sliding_window + 1`: at most `window.pages`
at a time, however long the request.

Two admission-control policies ride the same machinery
(docs/serving.md#resilience):

- **deadlines**: a request may carry `deadline_ms` (a latency budget
  anchored at arrival). `expire_deadlines` — called at the top of every
  engine step — terminates past-deadline work with
  `stop_reason='deadline'` wherever it sits: still queued (never cost a
  FLOP) or mid-decode (blocks freed, the tokens already streamed stand as
  the partial result);
- **load shedding**: the waiting queue is bounded (`max_queue`) and,
  when a service-time estimate exists, projected TTFT is capped
  (`shed_ttft_ms`). Over either threshold the LOWEST-priority queued
  request (ties: youngest arrival — the eviction order) is shed with
  `stop_reason='overloaded'`: an honest immediate terminal instead of a
  queue that grows without bound while every resident deadline burns.
  Intake itself never blocks.


Slots recycle on eos / max-tokens: blocks return to the pool and the row
becomes admissible immediately (the "slot stranding" the dense
`InferenceEngine` batch could not avoid).

Every lifecycle transition additionally emits a trace span
(docs/observability.md#tracing): a request moves queue → prefill → decode
(→ back to queue on eviction) and each phase it leaves becomes one span on
its Perfetto track, so queue-wait and eviction-loss are derivable per
request. The tracer is jax-free (`telemetry/trace.py` — same graftlint
contract as this module), so the import costs this host-only policy layer
nothing.
"""

from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

from llm_training_tpu.telemetry.trace import get_tracer


@dataclass
class ServeRequest:
    """One generation request plus its scheduler-owned runtime state."""

    id: str
    prompt: list[int]
    max_new_tokens: int
    priority: int = 0  # higher = more important (evicted last)
    arrival_s: float = field(default_factory=time.perf_counter)
    # absolute (arrival-anchored, perf_counter clock) completion deadline;
    # None = no deadline. Set from the protocol's relative `deadline_ms`.
    deadline_s: float | None = None

    # runtime (scheduler-owned)
    generated: list[int] = field(default_factory=list)
    # chosen-token logprob per generated token (parallel to `generated`;
    # the engine appends both together). None marks a token whose logprob
    # is unknown — e.g. restored from a pre-logprob journal. RL rollout
    # collection (rl/rollout.py) trains on these; eviction preserves them
    # with `generated` so a fold-in requeue loses nothing.
    logprobs: list[float | None] = field(default_factory=list)
    emitted: int = 0  # tokens already streamed (an evict/resume never re-emits)
    # tokens an enqueued call is making whose values the host has not read
    # yet (the engine runs a step ahead of its fetches: at most a final
    # chunk's and the same step's decode, two). Everything that does not
    # need a token's VALUE counts them at the enqueue: `cache_len`, the
    # window's pages, the finish by length
    in_flight: int = 0
    slot: int | None = None
    blocks: list[int] = field(default_factory=list)
    # the window group's pages this request holds: its logical pages
    # `window_first ..`, the ones in front given back (`release_window`)
    window_blocks: list[int] = field(default_factory=list)
    window_first: int = 0
    prefill_tokens: list[int] = field(default_factory=list)  # this residency's prefill
    prefilled: int = 0  # prefill_tokens positions already written
    cache_len: int = 0  # tokens whose KV is in the pool
    first_token_s: float | None = None
    last_token_s: float | None = None
    evictions: int = 0
    stop_reason: str | None = None
    # tracing (docs/observability.md#tracing): whether this request's
    # events reach the trace.jsonl sink (sampling — the ring records all),
    # the lifecycle phase currently open, when it opened, and the total
    # time spent waiting in the queue (initial + post-eviction)
    traced: bool = True
    phase: str = "queue"
    phase_start_s: float | None = None
    queue_wait_s: float = 0.0

    def advance_phase(self, new_phase: str, now: float | None = None) -> None:
        """Close the open lifecycle phase as a trace span and enter
        `new_phase`. Phases tile the request's residency wall-clock
        exactly: each span starts where the previous one ended."""
        if now is None:
            now = time.perf_counter()
        start = self.phase_start_s if self.phase_start_s is not None else self.arrival_s
        get_tracer().span(
            "serve", self.phase, start, now, write=self.traced,
            request_id=self.id, residency=self.evictions,
        )
        if self.phase == "queue":
            self.queue_wait_s += max(0.0, now - start)
        self.phase = new_phase
        self.phase_start_s = now

    @property
    def done(self) -> bool:
        return self.stop_reason is not None

    @property
    def decoding(self) -> bool:
        """Prefill complete for the current residency — the row produces
        one token per decode step."""
        return (
            self.slot is not None
            and not self.done
            and self.prefilled >= len(self.prefill_tokens)
        )


@dataclass
class SchedulerConfig:
    max_batch: int  # decode slots (the decode program's static batch)
    max_model_len: int  # per-request cap: len(prompt) + max_new_tokens
    block_size: int
    prefill_chunk: int  # tokens per prefill-chunk program call
    # intake bound: queued (not running) requests past this are shed with
    # stop_reason='overloaded'; None = unbounded (the pre-resilience
    # behavior)
    max_queue: int | None = None
    # projected-TTFT bound: when the tail of the queue projects past this
    # many milliseconds to its first token (estimated from completed
    # requests' service times), shed until it doesn't; None disables
    shed_ttft_ms: float | None = None


class WindowGroup(NamedTuple):
    """The window group as the engine derives it from the model's
    declaration: its allocator, how many of a row's newest tokens its layers
    read, and the pages of it a request may hold at once
    (`serve/paged_cache.py:window_page_budget`). Not an option of a
    deployment: it comes with the allocator or not at all."""

    allocator: Any
    sliding_window: int
    pages: int


class Scheduler:
    """Owns the waiting queue, the slot map, and the block accounting
    policy; the `ServingEngine` executes what `admit`/`next_prefill`/
    `ensure_decode_blocks` decide."""

    def __init__(self, config: SchedulerConfig, allocator, window: WindowGroup | None = None):
        if config.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.config = config
        self.allocator = allocator
        self.window = window
        self.window_allocator = None if window is None else window.allocator
        self.window_pages_released = 0
        # called before a request is evicted under block pressure: the engine
        # reads the tokens it has in flight (an eviction folds `generated`
        # into the requeued prompt). True when it read any: a finish seen
        # late (`eos`) may have freed the pages, so the taking is tried again
        self.before_evict: Callable[[], bool] | None = None
        self.waiting: deque[ServeRequest] = deque()
        self.running: dict[int, ServeRequest] = {}  # slot -> request
        self._free_slots = list(range(config.max_batch - 1, -1, -1))
        self.completed: list[ServeRequest] = []
        self.evictions = 0
        self.shed_total = 0  # 'overloaded' terminations (load shedding)
        self.deadline_total = 0  # 'deadline' terminations (queue + decode)
        # EMA of completed requests' residency seconds (arrival -> done),
        # the service-time estimate behind projected-TTFT shedding; None
        # until the first completion (no estimate -> no TTFT shedding)
        self._service_ema_s: float | None = None

    # ------------------------------------------------------------ intake

    def submit(self, request: ServeRequest) -> ServeRequest | None:
        """Queue a request; returns it REJECTED (stop_reason='rejected')
        instead when it can never fit max_model_len. Enqueueing may shed
        (`stop_reason='overloaded'`) — the victim is the lowest-priority
        QUEUED request, not necessarily this one — so callers must emit
        terminals for everything newly in `completed`, not just the return
        value."""
        total = len(request.prompt) + request.max_new_tokens
        if len(request.prompt) == 0 or request.max_new_tokens < 1:
            request.stop_reason = "rejected"
        elif total > self.config.max_model_len:
            request.stop_reason = "rejected"
        if request.done:
            self.completed.append(request)
            return request
        self.waiting.append(request)
        if not self._free_slots:
            # saturated: nothing will drain this queue before the next
            # decode completes, so the intake bound applies NOW (an honest
            # synchronous 'overloaded'). With a slot free, the next step's
            # admit -> shed pass decides — a burst that fits the free slots
            # must not be shed on arrival order alone.
            self.shed()
        return None

    @property
    def idle(self) -> bool:
        return not self.waiting and not self.running

    def _blocks_for(self, tokens: int) -> int:
        return math.ceil(tokens / self.config.block_size)

    # ------------------------------------------- deadlines + load shedding

    def expire_deadlines(self, now: float | None = None) -> None:
        """Terminate past-deadline requests with stop_reason='deadline' —
        queued ones before they cost a prefill FLOP, decoding ones with
        their blocks freed and the already-streamed tokens standing as the
        partial result. Callers emit terminals via the `completed` diff."""
        if now is None:
            now = time.perf_counter()

        def expired(request: ServeRequest) -> bool:
            return request.deadline_s is not None and now >= request.deadline_s

        for request in [r for r in self.waiting if expired(r)]:
            self.waiting.remove(request)
            self._terminate_queued(request, "deadline", now)
        for request in [r for r in self.running.values() if expired(r)]:
            self.finish(request, "deadline")
            self.deadline_total += 1

    def shed(self) -> None:
        """Shed lowest-priority queued work (stop_reason='overloaded')
        while the queue is over `max_queue` or its tail projects past
        `shed_ttft_ms` to a first token. Reuses the eviction-priority
        order, so under overload the queue keeps exactly the requests
        eviction would have kept."""
        while self.waiting and self._over_intake_limits():
            victim = min(
                self.waiting, key=lambda r: (r.priority, -r.arrival_s)
            )
            self.waiting.remove(victim)
            self._terminate_queued(victim, "overloaded")

    def _over_intake_limits(self) -> bool:
        cfg = self.config
        if cfg.max_queue is not None and len(self.waiting) > cfg.max_queue:
            return True
        projected = self.projected_ttft_ms(len(self.waiting) - 1)
        return (
            cfg.shed_ttft_ms is not None
            and projected is not None
            and projected > cfg.shed_ttft_ms
        )

    def projected_ttft_ms(self, queue_position: int) -> float | None:
        """Estimated milliseconds to first token for the request at
        `queue_position` (0 = head of the waiting queue): each max_batch-
        sized wave ahead of it costs ~one EMA service time. A coarse,
        monotone-in-depth estimate — None until a completion has seeded
        the EMA."""
        if self._service_ema_s is None or queue_position < 0:
            return None
        waves = queue_position // self.config.max_batch + 1
        return 1000.0 * waves * self._service_ema_s

    def _terminate_queued(
        self, request: ServeRequest, stop_reason: str,
        now: float | None = None,
    ) -> None:
        """Complete a never-admitted (or no-longer-resident) request from
        the queue: no slot or blocks to release."""
        request.stop_reason = stop_reason
        request.advance_phase("done", now)
        self.completed.append(request)
        if stop_reason == "overloaded":
            self.shed_total += 1
        elif stop_reason == "deadline":
            self.deadline_total += 1

    # --------------------------------------------------------- admission

    def _take(self, pages: int, window_pages: int) -> tuple[list[int], list[int]] | None:
        """`pages` of the first group and `window_pages` of the window group,
        or None and nothing taken."""
        blocks = self.allocator.alloc(pages)
        if blocks is None or not window_pages:
            return None if blocks is None else (blocks, [])
        window = self.window_allocator.alloc(window_pages)
        if window is None:
            self.allocator.free(blocks)
            return None
        return blocks, window

    def admit(self) -> list[ServeRequest]:
        """Admit waiting requests while a slot is free and the pool covers
        each one's (re)prefill + one decode-step write: of the window group,
        its first chunk's pages. A head-of-queue request the pool can NEVER
        satisfy (even with everything else drained) fails with
        stop_reason='capacity' rather than starving the queue behind it; so
        does one whose window would not fit the window pool when alone."""
        admitted = []
        while self.waiting and self._free_slots:
            request = self.waiting[0]
            resident = request.prompt + request.generated
            needed = self._blocks_for(len(resident) + 1)
            window_needed, fits = 0, True
            if self.window_allocator is not None:
                window_needed = self._blocks_for(
                    min(len(resident) + 1, self.config.prefill_chunk)
                )
                fits = min(needed, self.window.pages) < self.window_allocator.num_blocks
            taken = self._take(needed, window_needed) if fits else None
            if taken is None:
                if not fits or (not self.running and not admitted):
                    # nothing left to drain — this request cannot ever fit
                    self.waiting.popleft()
                    request.stop_reason = "capacity"
                    request.advance_phase("done")
                    self.completed.append(request)
                    continue
                break
            self.waiting.popleft()
            request.slot = self._free_slots.pop()
            request.blocks, request.window_blocks = taken
            request.window_first = 0
            request.prefill_tokens = resident
            request.prefilled = 0
            request.cache_len = 0
            request.advance_phase("prefill")
            self.running[request.slot] = request
            admitted.append(request)
        return admitted

    # ----------------------------------------------------------- prefill

    def next_prefill(self) -> tuple[ServeRequest, list[int], int] | None:
        """(request, chunk_tokens, chunk_start) for the oldest running
        request with prompt left to prefill, or None. The chunk's pages of
        the window group are taken here, evicting under pressure as a decode
        row's growth does; a request that got evicted itself is passed over."""
        while True:
            pending = [
                r for r in self.running.values()
                if r.prefilled < len(r.prefill_tokens)
            ]
            if not pending:
                return None
            request = min(pending, key=lambda r: r.arrival_s)
            start = request.prefilled
            chunk = request.prefill_tokens[start:start + self.config.prefill_chunk]
            if self._grow(request, start + len(chunk)):
                return request, chunk, start

    # ------------------------------------------------------------ decode

    def decode_rows(self) -> list[ServeRequest]:
        return [r for r in self.running.values() if r.decoding]

    def ensure_decode_blocks(self, request: ServeRequest) -> bool:
        """Guarantee the row's next token has a cache slot in every group,
        evicting under block pressure. False when the request itself got
        evicted. A row takes a page once in `block_size` tokens: every
        other step the pages it holds reach, which is one compare a group."""
        tokens = request.cache_len + 1
        size = self.config.block_size
        if tokens <= len(request.blocks) * size and (
            self.window_allocator is None
            or tokens <= (request.window_first + len(request.window_blocks)) * size
        ):
            return True
        return self._grow(request, tokens)

    def _grow(self, request: ServeRequest, tokens: int) -> bool:
        """Pages for the request's first `tokens` positions: of the first
        group all of them, of the window group those from `window_first` on.
        Both groups' or neither's; under pressure the eviction victim goes.
        False when that was the request itself."""
        pages = self._blocks_for(tokens)
        while True:
            short = max(0, pages - len(request.blocks))
            window_short = 0
            if self.window_allocator is not None:
                window_short = max(
                    0, pages - request.window_first - len(request.window_blocks)
                )
            if not short and not window_short:
                return True
            taken = self._take(short, window_short)
            if taken is not None:
                request.blocks.extend(taken[0])
                request.window_blocks.extend(taken[1])
                return True
            if self.before_evict is not None and self.before_evict():
                if request.slot is None:  # it finished among the tokens read
                    return False
                continue
            victim = self._eviction_victim()
            self.evict(victim)
            if victim is request:
                return False

    def release_window(self, request: ServeRequest) -> int:
        """Give back the request's pages of the window group that lie wholly
        in front of `cache_len - sliding_window + 1`: no later token reads
        them. After every chunk and every decode step; returns how many."""
        if self.window_allocator is None or request.slot is None:
            return 0
        keep_from = max(0, request.cache_len - self.window.sliding_window + 1)
        gone = keep_from // self.config.block_size - request.window_first
        if gone <= 0:
            return 0
        self.window_allocator.free(request.window_blocks[:gone])
        del request.window_blocks[:gone]
        request.window_first += gone
        self.window_pages_released += gone
        return gone

    def _eviction_victim(self) -> ServeRequest:
        return min(
            self.running.values(), key=lambda r: (r.priority, -r.arrival_s)
        )

    def evict(self, request: ServeRequest) -> None:
        """Free the request's residency and requeue it (front) with its
        progress folded in; already-streamed tokens are never re-emitted."""
        lost_cache = request.cache_len
        request.advance_phase("queue")
        get_tracer().instant(
            "serve", "evicted", write=request.traced, request_id=request.id,
            lost_cache_tokens=lost_cache, generated=len(request.generated),
        )
        self._release(request)
        request.evictions += 1
        self.evictions += 1
        request.prefill_tokens = []
        request.prefilled = 0
        request.cache_len = 0
        self.waiting.appendleft(request)

    # -------------------------------------------------------- completion

    def finish(self, request: ServeRequest, stop_reason: str) -> None:
        request.advance_phase("done")
        self._release(request)
        request.stop_reason = stop_reason
        self.completed.append(request)
        if stop_reason in ("eos", "max_tokens"):
            # successful completions seed the service-time estimate behind
            # projected-TTFT shedding (beta 0.8: a few requests converge it,
            # one outlier doesn't own it)
            service_s = max(0.0, time.perf_counter() - request.arrival_s)
            if self._service_ema_s is None:
                self._service_ema_s = service_s
            else:
                self._service_ema_s = 0.8 * self._service_ema_s + 0.2 * service_s

    def _release(self, request: ServeRequest) -> None:
        del self.running[request.slot]
        self._free_slots.append(request.slot)
        self.allocator.free(request.blocks)
        if self.window_allocator is not None:
            self.window_allocator.free(request.window_blocks)
        request.slot = None
        request.blocks = []
        request.window_blocks = []
        request.window_first = 0
