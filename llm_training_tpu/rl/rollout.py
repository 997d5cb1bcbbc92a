"""Rollout collection through the serving engine
(docs/post-training.md#rollouts).

The GRPO loop does not own a decode path: rollouts are ordinary
`ServingEngine` requests — N samples per prompt, submitted as a dedicated
priority class (default BELOW user traffic, so under contention the
scheduler's existing eviction/shedding order arbitrates in favor of
serving) — and the collector drives `engine.step()` exactly like the
serve CLI does, routing non-rollout events back to the caller.

Two correctness properties live here:

- **behavior logprobs**: every token event carries the chosen token's
  logprob under the distribution it was sampled from (engine-collected
  in-stream — satellite of this PR); the GRPO importance ratio is
  computed against exactly these, never against a re-forward;
- **generation tagging**: every token event carries the serve weights
  generation it was decoded under. A sample is usable only when ALL its
  tokens came from the CURRENT generation — a mid-collection
  `reload_weights` (or a sample finishing just before a sync) makes the
  sample stale, and stale samples are dropped and counted
  (`rl/rollouts_stale_dropped`), never silently trained on. This is the
  "no rollout generated under generation N enters a batch applied at
  generation > N" acceptance criterion: the loop builds its batch at the
  engine's current generation and syncs (bumping the generation) only
  AFTER the update.

SLO arbitration (docs/post-training.md#slo): when an `SLOMonitor` is
attached and a NEW serve-domain burn-rate breach fires (TTFT/TPOT —
PR 14's monitor), the collector stops submitting further rollout groups
for `yield_steps` engine steps (`rl/rollout_yields` counts the waves);
in-flight rollouts keep their slots (the scheduler may still evict or
shed them under pressure), user traffic keeps flowing.

Counter reads (`stats()`) come from the exporter's scrape threads, so the
counter dict is lock-guarded ("rl" slots into the racecheck LOCK_ORDER);
everything else is single-threaded host state driven between engine
steps.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

from llm_training_tpu.telemetry.trace import get_tracer

logger = logging.getLogger(__name__)

ID_PREFIX = "rl:"
_FULL_REASONS = ("eos", "max_tokens")


def rollout_id(round_idx: int, prompt_idx: int, sample_idx: int) -> str:
    return f"{ID_PREFIX}r{round_idx}:p{prompt_idx}:s{sample_idx}"


def parse_rollout_id(id: str) -> tuple[int, int, int] | None:
    """-> (round, prompt, sample) for a collector-issued id, else None."""
    if not id.startswith(ID_PREFIX):
        return None
    try:
        r, p, s = id[len(ID_PREFIX):].split(":")
        return int(r[1:]), int(p[1:]), int(s[1:])
    except (ValueError, IndexError):
        return None


@dataclass
class Rollout:
    """One harvested sample: the training-ready (prompt, completion,
    behavior logprobs) triple plus its provenance."""

    id: str
    round_idx: int
    prompt_idx: int
    sample_idx: int
    prompt: list[int]
    tokens: list[int]
    logprobs: list[float]
    generation: int
    stop_reason: str
    reward: float | None = None


@dataclass
class _Pending:
    prompt: list[int]
    round_idx: int
    prompt_idx: int
    sample_idx: int
    generations: set[int] = field(default_factory=set)
    adopted: bool = False
    done: dict | None = None


class RolloutCollector:
    """Submits prompt groups into `engine`, drives steps, harvests
    generation-clean samples. `on_foreign_event` receives every event that
    is not a rollout's (user traffic riding the same engine)."""

    def __init__(
        self,
        engine: Any,
        group_size: int = 4,
        max_new_tokens: int = 16,
        priority: int = -1,
        slo: Any | None = None,
        yield_steps: int = 50,
        on_foreign_event: Callable[[dict], None] | None = None,
    ):
        if group_size < 1:
            raise ValueError(f"group_size must be >= 1, got {group_size}")
        self.engine = engine
        self.group_size = group_size
        self.max_new_tokens = max_new_tokens
        self.priority = priority
        self.slo = slo
        self.yield_steps = max(0, int(yield_steps))
        self.on_foreign_event = on_foreign_event
        # collection-loop-thread only; exporter scrape threads call
        # stats(), which reads _counters under _lock and never touches
        # the pending table
        # lint: allow(race-unguarded-shared): collection-thread-only state
        self._pending: dict[str, _Pending] = {}
        self._lock = threading.Lock()
        # scrape-visible counters (exporter threads read via stats())
        self._counters = {  # guarded by: _lock
            "rollouts_submitted": 0,
            "rollouts_collected": 0,
            "rollouts_stale_dropped": 0,
            "rollouts_failed": 0,
            "rollout_yields": 0,
        }
        # SLO arbitration state: read/written only between engine steps on
        # the collection thread, never scrape-visible
        # lint: allow(race-unguarded-shared): collection-thread-only state
        self._seen_breaches = (
            self.slo.breach_count() if self.slo is not None else 0
        )
        # lint: allow(race-unguarded-shared): collection-thread-only
        self._yield_left = 0

    # ------------------------------------------------------------ counters

    def _bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._counters[key] += n

    def stats(self) -> dict[str, float]:
        """Scrape-safe counter snapshot, `rl/`-prefixed (the loop publishes
        these as gauges; the exporter's extra_fn may read them live)."""
        with self._lock:
            return {f"rl/{k}": float(v) for k, v in self._counters.items()}

    # -------------------------------------------------------------- intake

    def adopt(self, entries: Sequence[dict]) -> int:
        """Register journal-replayed rollout requests (the caller has
        already `submit_resumed` them into the engine). Their journaled
        tokens were generated by the pre-death process under the SAME
        weights this relaunch restored (the loop checkpoints after every
        sync, so a mid-rollout death always resumes weight-consistent) —
        they count as current-generation by construction. Returns how many
        entries were rollouts."""
        adopted = 0
        for entry in entries:
            parsed = parse_rollout_id(str(entry.get("id", "")))
            if parsed is None:
                continue
            round_idx, prompt_idx, sample_idx = parsed
            self._pending[entry["id"]] = _Pending(
                prompt=[int(t) for t in entry["prompt"]],
                round_idx=round_idx,
                prompt_idx=prompt_idx,
                sample_idx=sample_idx,
                adopted=True,
            )
            adopted += 1
        if adopted:
            logger.info("rollout collector adopted %d replayed sample(s)", adopted)
        return adopted

    def _submit_group(
        self, round_idx: int, prompt_idx: int, prompt: Sequence[int]
    ) -> list[dict]:
        events: list[dict] = []
        for sample_idx in range(self.group_size):
            id = rollout_id(round_idx, prompt_idx, sample_idx)
            if id in self._pending:  # adopted from a replayed journal
                continue
            self._pending[id] = _Pending(
                prompt=list(prompt), round_idx=round_idx,
                prompt_idx=prompt_idx, sample_idx=sample_idx,
            )
            self._bump("rollouts_submitted")
            events.extend(self.engine.submit(
                id=id, prompt=prompt, max_new_tokens=self.max_new_tokens,
                priority=self.priority,
            ))
        return events

    # ------------------------------------------------------------- routing

    def ingest(self, events: Sequence[dict]) -> None:
        """Feed externally-obtained engine events (submit() returns,
        journal-replay `submit_resumed` returns) through the same routing
        as step() output."""
        self._route(events)

    def _route(self, events: Sequence[dict]) -> None:
        for event in events:
            pending = self._pending.get(event.get("id"))
            if pending is None:
                if self.on_foreign_event is not None:
                    self.on_foreign_event(event)
                continue
            if event.get("type") == "token":
                pending.generations.add(int(event["generation"]))
            elif event.get("type") == "done":
                pending.generations.add(int(event["generation"]))
                pending.done = event

    # --------------------------------------------------------- arbitration

    def _slo_gate(self) -> bool:
        """True while rollout submission must yield to serve traffic: a
        NEW serve-domain breach opens (or re-arms) a `yield_steps` window."""
        if self.slo is not None:
            breaches = self.slo.breach_count()
            if breaches > self._seen_breaches:
                self._seen_breaches = breaches
                alert = self.slo.last_alert() or {}
                if str(alert.get("key", "")).startswith("serve/"):
                    self._yield_left = self.yield_steps
                    self._bump("rollout_yields")
                    get_tracer().instant(
                        "rl", "rollout_yield",
                        key=alert.get("key"),
                        burn_fast=alert.get("burn_fast"),
                        yield_steps=self.yield_steps,
                    )
                    logger.warning(
                        "rollout submission yielding %d engine steps to "
                        "serve traffic (SLO breach on %s)",
                        self.yield_steps, alert.get("key"),
                    )
        if self._yield_left > 0:
            self._yield_left -= 1
            return True
        return False

    # ------------------------------------------------------------- collect

    def collect(
        self,
        round_idx: int,
        prompts: Sequence[Sequence[int]],
        max_steps: int = 100_000,
        should_stop: Callable[[], bool] | None = None,
    ) -> list[Rollout]:
        """One round: submit `group_size` samples per prompt (groups are
        deferred while the SLO gate is closed), drive the engine until
        every rollout is terminal, harvest generation-clean samples.
        Adopted (journal-replayed) samples for this round slot into their
        original (prompt, sample) positions instead of resubmitting.
        `should_stop` (e.g. GracefulShutdown) breaks out between engine
        steps — the caller drains/journals and the round replays."""
        tracer = get_tracer()
        queue = list(enumerate(prompts))
        with tracer.measure("rl", "collect_round", round=round_idx,
                            prompts=len(prompts), group=self.group_size):
            for step in range(max_steps):
                if should_stop is not None and should_stop():
                    # the last call's tokens: the caller drains next, and
                    # what it reads itself nobody is handed
                    self._route(self.engine.flush())
                    break
                while queue and not self._slo_gate():
                    prompt_idx, prompt = queue.pop(0)
                    self._route(self._submit_group(round_idx, prompt_idx, prompt))
                    if self._yield_left > 0:
                        break
                round_pending = [
                    p for p in self._pending.values()
                    if p.round_idx == round_idx and p.done is None
                ]
                if not queue and not round_pending:
                    break
                self._route(self.engine.step())
            else:
                raise RuntimeError(
                    f"rollout round {round_idx} not drained after "
                    f"{max_steps} engine steps"
                )
        return self._harvest(round_idx)

    def _harvest(self, round_idx: int) -> list[Rollout]:
        current = self.engine.weights_generation
        rollouts: list[Rollout] = []
        for id in [
            i for i, p in self._pending.items() if p.round_idx == round_idx
        ]:
            pending = self._pending.pop(id)
            done = pending.done
            if done is None:
                continue  # drained away (drain() journals it for replay)
            if done.get("stop_reason") not in _FULL_REASONS:
                # shed/expired/evicted-to-death rollouts are load the
                # engine refused, not trainable samples
                self._bump("rollouts_failed")
                continue
            logprobs = done.get("logprobs") or []
            stale = pending.generations - {current}
            if stale or (not pending.generations and not pending.adopted):
                # tokens decoded under old weights (or of unknown
                # provenance): NEVER train on them
                self._bump("rollouts_stale_dropped")
                get_tracer().instant(
                    "rl", "rollout_stale_dropped", request_id=id,
                    generations=sorted(pending.generations), current=current,
                )
                continue
            if (
                len(logprobs) != len(done.get("tokens", []))
                or any(lp is None for lp in logprobs)
            ):
                # a logprob gap (pre-logprob journal tail) poisons the
                # importance ratio — treat like staleness
                self._bump("rollouts_stale_dropped")
                continue
            self._bump("rollouts_collected")
            rollouts.append(Rollout(
                id=id, round_idx=round_idx,
                prompt_idx=pending.prompt_idx,
                sample_idx=pending.sample_idx,
                prompt=pending.prompt,
                tokens=[int(t) for t in done["tokens"]],
                logprobs=[float(lp) for lp in logprobs],
                generation=current,
                stop_reason=done["stop_reason"],
            ))
        return rollouts
