"""Router tier unit tests: cross-replica journal folding (colliding ids
namespace independently; torn tails skipped), exactly-once terminals across
the replica-death window, hedging winner/loser suppression, leg adoption,
health-driven eviction, least-loaded assignment, and SLO-burn elasticity.

All jax-free: the Router core is exercised directly with stub replica
handles — no serve children, no subprocesses.
"""

from __future__ import annotations

import json

import pytest

from llm_training_tpu.serve.journal import RequestJournal, replay_journal
from llm_training_tpu.serve.router import (
    Router,
    fold_replica_journals,
    namespaced_id,
    split_namespaced_id,
)


class _StubHandle:
    """Bare-minimum stand-in for ReplicaHandle (rid/port are all Router reads)."""

    def __init__(self, rid: str, port: int):
        self.rid = rid
        self.port = port


class _Clock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


def _router(clock=None, **kw) -> Router:
    return Router(clock=clock or _Clock(), **kw)


def _add_replicas(router: Router, *specs) -> None:
    for rid, port in specs:
        router.register_replica(_StubHandle(rid, port))


def _snapshot(*entries) -> dict:
    """Build an aggregator-shaped snapshot: entries are (port, healthy,
    stale, metrics)."""
    replicas = {}
    for i, (port, healthy, stale, metrics) in enumerate(entries):
        replicas[f"replica-{i}"] = {
            "port": port,
            "healthy": healthy,
            "stale": stale,
            "metrics": metrics,
        }
    return {"replicas": replicas}


def _intake(router: Router, rid_id: str = "req-0", n: int = 8):
    req = router.intake({"id": rid_id, "prompt": [1, 2], "max_new_tokens": n})
    assert req is not None
    return req


# --------------------------------------------------------------- namespacing


def test_namespaced_id_roundtrip():
    nsid = namespaced_id("r0", "req-0")
    assert nsid == "r0::req-0"
    assert split_namespaced_id(nsid) == ("r0", "req-0")
    # client ids containing "::" split at the FIRST separator (replica ids
    # never contain "::", so the remainder is the verbatim client id)
    assert split_namespaced_id("r1::a::b") == ("r1", "a::b")


def test_fold_replica_journals_namespaces_colliding_ids(tmp_path):
    """The ISSUE case: `req-0` from replica A and replica B must fold
    independently — distinct namespaced ids, distinct watermarks."""
    for rid, toks in (("rA", [10, 11, 12]), ("rB", [20])):
        j = RequestJournal(tmp_path / f"{rid}.jsonl")
        j.delivered("req-0", [1, 2], 8)

        class R:
            id = "req-0"
            generated = toks
            emitted = len(toks)

        j.progress(R)
        j.close()

    folded = fold_replica_journals(
        {"rA": tmp_path / "rA.jsonl", "rB": tmp_path / "rB.jsonl"}
    )
    by_id = {e["id"]: e for e in folded}
    assert set(by_id) == {"rA::req-0", "rB::req-0"}
    assert by_id["rA::req-0"]["client_id"] == "req-0"
    assert by_id["rA::req-0"]["source_replica"] == "rA"
    assert by_id["rA::req-0"]["generated"] == [10, 11, 12]
    assert by_id["rB::req-0"]["generated"] == [20]


def test_fold_replica_journals_skips_torn_tail(tmp_path):
    """A SIGKILL mid-append leaves a torn last line; the fold keeps every
    complete record before it."""
    path = tmp_path / "torn.jsonl"
    j = RequestJournal(path)
    j.delivered("req-0", [1], 8)

    class R:
        id = "req-0"
        generated = [5, 6]
        emitted = 2

    j.progress(R)
    j.close()
    with open(path, "a") as f:
        f.write('{"event": "progress", "id": "req-0", "genera')  # torn
    folded = fold_replica_journals({"rX": path})
    assert len(folded) == 1
    assert folded[0]["id"] == "rX::req-0"
    assert folded[0]["generated"] == [5, 6]


def test_fold_replica_journals_missing_journal_is_empty(tmp_path):
    assert fold_replica_journals({"rZ": tmp_path / "absent.jsonl"}) == []


# ----------------------------------------------------- stream fold / terminals


def test_token_and_done_flow_exactly_once():
    router = _router()
    _add_replicas(router, ("r0", 9001))
    req = _intake(router)
    assert router.assign(req)[0] == "r0"

    ev = router.record_token("r0", {"id": "r0::req-0", "token": 7, "generation": 3})
    assert [e["token"] for e in ev] == [7]
    assert ev[0]["id"] == "req-0"  # de-namespaced for the client
    assert ev[0]["generation"] == 3  # weights generation passes through

    done = router.record_done(
        "r0", {"id": "r0::req-0", "type": "done", "stop_reason": "eos", "generation": 3}
    )
    assert len(done) == 1
    assert done[0]["id"] == "req-0"
    assert done[0]["tokens"] == [7]
    assert done[0]["n_tokens"] == 1
    assert done[0]["replica"] == "r0"
    assert router.stats()["requests_completed"] == 1
    assert router.inflight() == 0


def test_duplicate_terminal_in_death_window_suppressed():
    """Replica emits done, then dies before the router sees EOF; the
    journal fold (or a raced second done) must not produce a second
    terminal."""
    router = _router()
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    req = _intake(router)
    router.assign(req)
    first = router.record_done(
        "r0", {"id": "r0::req-0", "type": "done", "stop_reason": "eos"}
    )
    assert len(first) == 1
    # raced duplicate done for the same client id → suppressed
    second = router.record_done(
        "r0", {"id": "r0::req-0", "type": "done", "stop_reason": "eos"}
    )
    assert second == []
    assert router.stats()["duplicate_terminals_suppressed"] == 1
    # the death-window fold: the dead replica's journal still lists req-0
    # as unfinished (done chunk emitted but never journaled) — fail_replica
    # must not resurrect an already-terminal request
    folded = [
        {
            "id": "r0::req-0",
            "client_id": "req-0",
            "source_replica": "r0",
            "prompt": [1, 2],
            "generated": [7, 8],
            "emitted": 2,
            "max_new_tokens": 8,
            "priority": 0,
        }
    ]
    result = router.fail_replica("r0", folded)
    assert result["events"] == []
    assert result["orphans"] == []
    # and a replayed client record for the finished id dedupes at intake
    assert router.intake({"id": "req-0", "prompt": [1, 2]}) is None


def test_synthesize_done_is_terminal_and_unique():
    router = _router()
    _add_replicas(router, ("r0", 9001))
    req = _intake(router)
    router.assign(req)
    ev = router.synthesize_done(req, "max_tokens")
    assert len(ev) == 1 and ev[0]["stop_reason"] == "max_tokens"
    assert router.synthesize_done(req, "max_tokens") == []
    assert router.record_done("r0", {"id": "r0::req-0", "stop_reason": "eos"}) == []


# ------------------------------------------------------------------- failover


def test_fail_replica_folds_journal_extension_and_orphans():
    """Dead replica got further than the client saw: the journal watermark
    prefix-extends `generated`, recovered tokens are emitted once, and the
    request is orphaned for resubmission."""
    router = _router()
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    req = _intake(router)
    router.assign(req)
    router.record_token("r0", {"id": "r0::req-0", "token": 5})
    folded = [
        {
            "id": "r0::req-0",
            "client_id": "req-0",
            "source_replica": "r0",
            "prompt": [1, 2],
            "generated": [5, 6, 7],
            "emitted": 1,
            "max_new_tokens": 8,
            "priority": 0,
        }
    ]
    result = router.fail_replica("r0", folded)
    assert [e["token"] for e in result["events"]] == [6, 7]
    assert [o.id for o in result["orphans"]] == ["req-0"]
    assert req.generated == [5, 6, 7]
    assert req.emitted == 3
    stats = router.stats()
    assert stats["recovered_tokens"] == 2
    assert stats["failovers"] == 1
    # the dead replica is out of rotation: reassignment lands on r1
    req.legs.pop("r0", None)
    assert router.assign(req, exclude=("r0",))[0] == "r1"


def test_fail_replica_divergent_journal_not_folded():
    """A journal watermark that does NOT prefix-extend what the client has
    seen is discarded (greedy decode means agreement; divergence means a
    torn/competing record) — never re-stream different tokens."""
    router = _router()
    _add_replicas(router, ("r0", 9001))
    req = _intake(router)
    router.assign(req)
    router.record_token("r0", {"id": "r0::req-0", "token": 5})
    folded = [
        {
            "id": "r0::req-0",
            "client_id": "req-0",
            "generated": [9, 9, 9],
            "emitted": 3,
        }
    ]
    result = router.fail_replica("r0", folded)
    assert result["events"] == []
    assert req.generated == [5]
    assert [o.id for o in result["orphans"]] == ["req-0"]


def test_fail_replica_adopts_surviving_hedge_leg():
    """Winner dies while a hedge leg holds a superset of the stream: the
    survivor is adopted and only the unseen suffix is emitted."""
    clock = _Clock()
    router = _router(clock=clock, hedge_ttft_ms=10.0)
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    router.update_fleet(
        _snapshot(
            (9001, True, False, {"llmt_serve_ttft_p99_ms": 500.0}),
            (9002, True, False, {"llmt_serve_queue_depth": 0.0}),
        )
    )
    req = _intake(router)
    router.assign(req)
    clock.t = 1.0  # 1000ms elapsed > 10ms hedge budget
    hedged = router.maybe_hedge(clock.t)
    assert [(r.id, rid) for r, rid in hedged] == [("req-0", "r1")]
    # r0 wins (first token), emits 2; r1 trails with 3 cached (suppressed)
    router.record_token("r0", {"id": "r0::req-0", "token": 1})
    router.record_token("r0", {"id": "r0::req-0", "token": 2})
    for tok in (1, 2, 3):
        assert router.record_token("r1", {"id": "r1::req-0", "token": tok}) == []
    assert req.winner == "r0"
    result = router.fail_replica("r0", [])
    assert [e["token"] for e in result["events"]] == [3]
    assert result["orphans"] == []
    assert req.winner == "r1"
    assert router.stats()["leg_adoptions"] == 1
    # survivor finishes the stream normally
    done = router.record_done("r1", {"id": "r1::req-0", "stop_reason": "eos"})
    assert len(done) == 1 and done[0]["tokens"] == [1, 2, 3]


def test_fail_replica_adopted_leg_with_done_finishes_immediately():
    clock = _Clock()
    router = _router(clock=clock, hedge_ttft_ms=10.0)
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    router.update_fleet(
        _snapshot(
            (9001, True, False, {"llmt_serve_ttft_p99_ms": 500.0}),
            (9002, True, False, {"llmt_serve_queue_depth": 0.0}),
        )
    )
    req = _intake(router)
    router.assign(req)
    clock.t = 1.0
    router.maybe_hedge(clock.t)
    router.record_token("r0", {"id": "r0::req-0", "token": 1})
    # hedge leg races ahead and even finishes — all suppressed while r0 wins
    router.record_token("r1", {"id": "r1::req-0", "token": 1})
    router.record_token("r1", {"id": "r1::req-0", "token": 2})
    assert (
        router.record_done("r1", {"id": "r1::req-0", "stop_reason": "eos"}) == []
    )
    result = router.fail_replica("r0", [])
    tokens = [e for e in result["events"] if e.get("type") == "token"]
    dones = [e for e in result["events"] if e.get("type") == "done"]
    assert [e["token"] for e in tokens] == [2]
    assert len(dones) == 1 and dones[0]["tokens"] == [1, 2]
    assert router.inflight() == 0
    assert router.stats()["requests_completed"] == 1


# -------------------------------------------------------------------- hedging


def test_hedge_loser_terminal_suppressed_winner_unique():
    """First token wins; the loser's entire stream — including its done —
    is suppressed. Never two terminals."""
    clock = _Clock()
    router = _router(clock=clock, hedge_ttft_ms=10.0)
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    router.update_fleet(
        _snapshot(
            (9001, True, False, {"llmt_serve_ttft_p99_ms": 500.0}),
            (9002, True, False, {"llmt_serve_queue_depth": 0.0}),
        )
    )
    req = _intake(router)
    router.assign(req)
    clock.t = 1.0
    assert len(router.maybe_hedge(clock.t)) == 1
    # no re-hedge while two legs are open
    assert router.maybe_hedge(clock.t) == []
    # hedge replica answers first → it becomes winner
    ev = router.record_token("r1", {"id": "r1::req-0", "token": 4})
    assert [e["token"] for e in ev] == [4]
    assert router.record_token("r0", {"id": "r0::req-0", "token": 4}) == []
    assert router.record_done("r0", {"id": "r0::req-0", "stop_reason": "eos"}) == []
    done = router.record_done("r1", {"id": "r1::req-0", "stop_reason": "eos"})
    assert len(done) == 1
    stats = router.stats()
    assert stats["hedges"] == 1
    assert stats["hedge_wins"] == 1
    assert stats["requests_completed"] == 1
    assert stats["duplicate_terminals_suppressed"] == 0


def test_hedge_requires_idle_candidate():
    clock = _Clock()
    router = _router(clock=clock, hedge_ttft_ms=10.0)
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    router.update_fleet(
        _snapshot(
            (9001, True, False, {"llmt_serve_ttft_p99_ms": 500.0}),
            (9002, True, False, {"llmt_serve_queue_depth": 3.0}),
        )
    )
    req = _intake(router)
    router.assign(req)
    clock.t = 1.0
    assert router.maybe_hedge(clock.t) == []  # r1 busy → no hedge


# ------------------------------------------------- health / eviction / routing


def test_update_fleet_evicts_red_and_stale_then_restores():
    router = _router()
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    evicted = router.update_fleet(
        _snapshot((9001, False, False, {}), (9002, True, True, {}))
    )
    assert sorted(evicted) == ["r0", "r1"]
    req = _intake(router)
    assert router.assign(req) is None  # nothing in rotation
    # recovery un-evicts without double-counting
    assert router.update_fleet(
        _snapshot((9001, True, False, {}), (9002, True, False, {}))
    ) == []
    assert router.assign(req) is not None
    assert router.stats()["evictions"] == 2


def test_assign_least_loaded_uses_scrape_and_intra_scrape_delta():
    router = _router()
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    router.update_fleet(
        _snapshot(
            (9001, True, False, {"llmt_serve_queue_depth": 4.0, "llmt_serve_running": 1.0}),
            (9002, True, False, {"llmt_serve_queue_depth": 0.0, "llmt_serve_running": 1.0}),
        )
    )
    picks = []
    for i in range(5):
        req = _intake(router, f"req-{i}")
        picks.append(router.assign(req)[0])
    # r1 soaks the first 4 (scraped load 1 vs 5), then the intra-scrape
    # delta tips the 5th to r0
    assert picks == ["r1", "r1", "r1", "r1", "r0"]


# --------------------------------------------------------------- router journal


def test_router_journal_roundtrip_resume(tmp_path):
    """Router dies mid-stream; its own journal folds back into a resumable
    entry whose watermark resumes without re-streaming."""
    path = tmp_path / "router-journal.jsonl"
    journal = RequestJournal(path)
    router = _router()
    router.journal = journal
    _add_replicas(router, ("r0", 9001))
    req = _intake(router)
    router.assign(req)
    router.record_token("r0", {"id": "r0::req-0", "token": 5})
    router.record_token("r0", {"id": "r0::req-0", "token": 6})
    journal.close()  # simulate router death (no done journaled)

    entries = replay_journal(path)
    assert len(entries) == 1
    assert entries[0]["generated"] == [5, 6]
    assert entries[0]["emitted"] == 2

    incarnation2 = _router()
    resumed = incarnation2.resume(entries[0])
    assert resumed.emitted == 2
    assert resumed.generated == [5, 6]
    assert resumed.replays == 1
    assert incarnation2.stats()["resumed"] == 1


def test_router_journal_done_drops_entry(tmp_path):
    path = tmp_path / "router-journal.jsonl"
    journal = RequestJournal(path)
    router = _router()
    router.journal = journal
    _add_replicas(router, ("r0", 9001))
    req = _intake(router)
    router.assign(req)
    router.record_token("r0", {"id": "r0::req-0", "token": 5})
    router.record_done("r0", {"id": "r0::req-0", "stop_reason": "eos"})
    journal.close()
    assert replay_journal(path) == []
    # assignment notes ride the stream without affecting the fold
    events = [json.loads(l)["event"] for l in path.read_text().splitlines()]
    assert "assigned" in events


# ----------------------------------------------------------------- elasticity


def test_scale_decision_out_on_burn_in_on_idle():
    clock = _Clock(100.0)
    router = _router(
        clock=clock,
        min_replicas=1,
        max_replicas=3,
        scale_cooldown_s=5.0,
        idle_retire_s=10.0,
    )
    _add_replicas(router, ("r0", 9001))
    # sustained burn → scale out (once per cooldown)
    assert router.scale_decision(100.0, breaches=1) == ("out", None)
    _add_replicas(router, ("r1", 9002))
    assert router.scale_decision(101.0, breaches=2) is None  # cooldown
    assert router.scale_decision(106.0, breaches=2) == ("out", None)
    _add_replicas(router, ("r2", 9003))
    assert router.target() == 3
    # traffic at t=112 re-arms the idle clock
    req = _intake(router)
    clock.t = 112.0
    router.assign(req)
    router.record_done("r0", {"id": "r0::req-0", "stop_reason": "eos"})
    # steady breach count (not growing), not yet idle long enough → hold
    assert router.scale_decision(115.0, breaches=2) is None
    # idle → retire the youngest ordinal, down to min_replicas
    decision = router.scale_decision(130.0, breaches=2)
    assert decision == ("in", "r2")
    router.retire_replica("r2")
    assert router.scale_decision(140.0, breaches=2) == ("in", "r1")
    router.retire_replica("r1")
    assert router.scale_decision(150.0, breaches=2) is None  # at floor
    stats = router.stats()
    assert stats["scale_out_total"] == 2
    assert stats["scale_in_total"] == 2


def test_scale_in_blocked_by_inflight_traffic():
    clock = _Clock(0.0)
    router = _router(clock=clock, min_replicas=1, max_replicas=2,
                     scale_cooldown_s=0.0, idle_retire_s=5.0)
    _add_replicas(router, ("r0", 9001), ("r1", 9002))
    req = _intake(router)
    clock.t = 1.0
    router.assign(req)  # traffic at t=1, in flight
    assert router.scale_decision(20.0, breaches=0) is None  # inflight != 0
    router.record_done("r0", {"id": "r0::req-0", "stop_reason": "eos"})
    assert router.scale_decision(20.0, breaches=0) == ("in", "r1")


# -------------------------------------------------------------- observability


def test_live_stats_shape_and_prefix():
    router = _router()
    _add_replicas(router, ("r0", 9001))
    req = _intake(router)
    router.assign(req)
    live = router.live_stats()
    assert live["router/replicas"] == 1.0
    assert live["router/inflight"] == 1.0
    assert live["router/requests_total"] == 1.0
    assert all(k.startswith("router/") for k in live)
    flat = router.stats()
    assert flat["requests_total"] == 1
    assert not any(k.startswith("router/") for k in flat)


def test_intake_dedupes_inflight_ids():
    # dedupe keys off registered requests: intake alone doesn't register
    # (the runtime assigns or parks immediately after), so assign first
    router = _router()
    _add_replicas(router, ("r0", 9001))
    req = _intake(router)
    router.assign(req)
    assert router.intake({"id": "req-0", "prompt": [1]}) is None
    assert router.stats()["duplicate_requests"] == 1


# ------------------------------------------------------------------ chaos env


def test_chaos_router_hooks_parse_and_fire_once(monkeypatch):
    from llm_training_tpu.resilience.chaos import ChaosConfig, config_from_env

    monkeypatch.setenv("LLMT_CHAOS_ROUTER_KILL_REPLICA", "3")
    monkeypatch.setenv("LLMT_CHAOS_ROUTER_BLACKHOLE", "2")
    cfg = config_from_env()
    assert cfg.router_kill_replica_at == 3
    assert cfg.router_blackhole_at == 2
    assert cfg.any_active()

    from llm_training_tpu.resilience.chaos import Chaos

    chaos = Chaos(cfg)
    assert not chaos.maybe_router_kill_replica(2)
    assert chaos.maybe_router_kill_replica(3)
    assert not chaos.maybe_router_kill_replica(4)  # fire-once
    assert not chaos.maybe_router_blackhole(1)
    assert chaos.maybe_router_blackhole(2)
    assert not chaos.maybe_router_blackhole(2)  # fire-once

    inert = Chaos(ChaosConfig())
    assert not inert.maybe_router_kill_replica(10**6)
    assert not inert.maybe_router_blackhole(1)


# ------------------------------------------------- one process per chip


@pytest.mark.parametrize("platforms,replicas,refused", [
    ("cpu", 2, False), ("cpu", 1, False), (None, 1, False), ("tpu", 1, False),
    (None, 2, True), ("tpu", 2, True), ("tpu,cpu", 4, True),
])
def test_replica_spawns_never_share_an_accelerator(monkeypatch, platforms, replicas, refused):
    """Replica spawns assign no chips (ROADMAP R11): more than one serve
    child is refused at once, by name, unless the environment itself states
    the CPU test path — never a hang at the second child's backend init."""
    from llm_training_tpu.serve.router import require_one_process_per_chip

    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    if refused:
        with pytest.raises(SystemExit, match="a chip belongs to one process"):
            require_one_process_per_chip(replicas)
    else:
        require_one_process_per_chip(replicas)
