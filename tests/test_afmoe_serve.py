"""AFMoE (Trinity-Mini) through `serve/` and `infer/`: the two PAGE GROUPS at
work. Chunked prefill then decode through the paged cache against the plain
reference's full forward, on rows that end inside the window, cross it during
prefill and cross it during decode, with a window of 2 pages and a window pool
so small that pages are given back and reused by another request mid-run; a
page that was given back is never read (poisoned, nothing changes); the same
tokens through the dense `infer/` path; and the scheduler's two allocators:
both groups or neither on admit, no double free, the window group never over
its budget, the count of pages given back is what the lengths say, eviction
and replay leave both allocators empty. (The family's own tests, and the
tolerances' reasons, are in `tests/test_afmoe.py`, whose tiny model and
weights these use.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_training_tpu.infer import GenerateConfig, InferenceEngine
from llm_training_tpu.models.afmoe import Afmoe, AfmoeConfig, reference
from llm_training_tpu.serve import ServeConfig, ServingEngine
from llm_training_tpu.serve.paged_cache import BlockAllocator
from llm_training_tpu.serve.scheduler import Scheduler, SchedulerConfig, ServeRequest, WindowGroup
from test_afmoe import (  # noqa: F401  (`tiny` is a fixture)
    BF16_GAP,
    F32_TOL,
    REFERENCE_CFG,
    TINY,
    seeded_variables,
    tiny,
)

SERVE = dict(max_batch=2, max_model_len=64, block_size=8, prefill_chunk=8)
# (prompt, new tokens): ends inside the window; crosses it during prefill; crosses it
# during decode; crosses it in both; short
REQUESTS = [(5, 6), (30, 9), (11, 20), (19, 30), (3, 9)]


def serve_requests():
    rng = np.random.default_rng(5)
    return [
        {"id": f"r{i}", "prompt": rng.integers(0, 256, size=n).tolist(), "max_new_tokens": m}
        for i, (n, m) in enumerate(REQUESTS)
    ]


def served_against_reference(variables, requests, done, quant=None):
    """For each request, over every served position: (the widest gap by which
    the served token's reference logit lies below the reference's best, the
    widest difference between the served log-probability and the reference's:
    the logits up to the constant a softmax removes)."""
    from benchmarks.references import _common, afmoe as copy

    gaps, logprob_gaps, control = [], [], []
    for r in requests:
        served = done[r["id"]]["tokens"]
        tokens = r["prompt"] + served
        ids, seg = np.zeros((1, 64), np.int32), np.zeros((1, 64), np.int32)
        ids[0, : len(tokens)] = tokens
        seg[0, : len(tokens)] = 1
        pos = jnp.arange(64)[None]
        logits = np.asarray(reference.logits(variables["params"], REFERENCE_CFG, jnp.asarray(ids), jnp.asarray(seg)))[0]
        at = np.arange(len(r["prompt"]) - 1, len(tokens) - 1)  # position p chooses token p + 1
        rows = logits[at]
        gaps.append(float((rows.max(-1) - rows[np.arange(len(at)), served]).max()))
        logprobs = np.asarray(jax.nn.log_softmax(rows))[np.arange(len(at)), served]
        logprob_gaps.append(float(np.abs(logprobs - np.asarray(done[r["id"]]["logprobs"])).max()))
        if quant is not None:
            low = np.asarray(copy.logits(
                variables["params"], REFERENCE_CFG, jnp.asarray(ids), jnp.asarray(seg), pos, _common.QUANTS[quant]
            ))[0][at].argmax(-1)
            control.append(float((rows.max(-1) - rows[np.arange(len(at)), low]).max()))
    return max(gaps), max(logprob_gaps), max(control, default=None)


def run_engine(model, variables, between_steps=None, **serve):
    engine = ServingEngine(model, variables, ServeConfig(**{**SERVE, **serve}))
    requests = serve_requests()
    events = []

    def step():
        out = engine.step()
        if between_steps is not None:
            between_steps(engine)
        return out

    # two at once, the others join mid-flight into recycled blocks
    for r in requests[:2]:
        events += engine.submit(**r)
    for _ in range(6):
        events += step()
    for r in requests[2:]:
        events += engine.submit(**r)
    while not engine.idle:
        events += step()
    done = {e["id"]: e for e in events if e["type"] == "done"}
    return engine, requests, done


@pytest.mark.parametrize("variant", ["dense_experts", "grouped_experts_in_place"])
def test_chunked_prefill_then_paged_decode_is_the_reference_forward(tiny, variant):
    """Prompts of 5, 30, 11, 19 and 3 tokens in chunks of 8 through two slots
    with a window of 2 pages: rows that end inside the window, cross it during
    prefill and cross it during decode. The window pool has 7 pages (a row's
    budget is 4: window 16 + chunk 8, in pages of 8, + 1), so a row only gets
    on by the pages another row gave back, the same physical page serves
    several rows in turn, and one request is evicted and re-prefilled with its
    progress folded in. Every served position against the reference's full
    forward: a page mapped to the wrong slot, a stale page read as live, a
    rotary position off by one all fail. Also with the held experts multiplied
    in place by the grouped product (`moe_impl='ragged'`, the chip's path)."""
    _, variables = tiny
    over = {"dense_experts": {}, "grouped_experts_in_place": {"moe_impl": "ragged"}}[variant]
    model = Afmoe(AfmoeConfig(**{**TINY, **over}))
    seen = {"owners": {}, "peak": 0}

    def watch(engine):
        for request in engine.scheduler.running.values():
            assert len(request.window_blocks) <= engine.window_pages  # never over its budget
            for block in request.window_blocks:
                seen["owners"].setdefault(block, set()).add(request.id)

    with jax.default_matmul_precision("highest"):
        engine, requests, done = run_engine(model, variables, between_steps=watch, num_blocks=7)
    assert all(done[r["id"]]["stop_reason"] == "max_tokens" for r in requests)
    assert engine.window_pages == 4 and engine._window_pool[0].shape == (9, 8, 2, 8, 16)
    assert engine.scheduler.evictions >= 1
    assert engine.allocator.blocks_in_use == 0 and engine.window_allocator.blocks_in_use == 0
    assert max(len(owners) for owners in seen["owners"].values()) >= 3  # one page, several rows
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    stats = engine.stats()
    assert stats["decode/global_pool_bytes"] == stats["decode/cache_bytes"] == 2 * 3 * 8 * 2 * 8 * 16 * 4
    assert stats["decode/window_pool_bytes"] == 2 * 9 * 8 * 2 * 8 * 16 * 4
    assert stats["decode/window_blocks_total"] == 7 and stats["decode/window_blocks_in_use"] == 0
    assert 5 <= stats["decode/window_peak_blocks_in_use"] <= 7  # more than one row's budget of 4
    assert stats["serve/window_pages_released"] > 0 and stats["serve/window_live_tokens"] > 0
    if variant == "grouped_experts_in_place":
        # every expert layer: the looped front's two (each its own stack of
        # one) and the four of a period in each of the two scanned periods
        assert stats["decode/experts_in_place_layers"] == 2 + 4 * 2


def test_a_page_that_was_given_back_is_never_read(tiny):
    """Every page the window group's allocator takes back is overwritten with
    huge values before the next step, in every layer: what the short table no
    longer maps is masked by position, so nothing served changes."""
    model, variables = tiny
    freed = []

    def poison(engine):
        # (a page given back early in a step may have a new owner by its end)
        still_free = sorted(set(freed) & set(engine.window_allocator._free))
        freed.clear()
        if still_free:
            wk, wv = engine._window_pool
            at = jnp.asarray(still_free)
            engine._window_pool = (wk.at[:, at].set(1e4), wv.at[:, at].set(1e4))

    free = BlockAllocator.free

    def recording_free(self, blocks):
        if self.group == "window":
            freed.extend(blocks)
        free(self, blocks)

    BlockAllocator.free = recording_free
    try:
        with jax.default_matmul_precision("highest"):
            engine, requests, done = run_engine(model, variables, between_steps=poison)
    finally:
        BlockAllocator.free = free
    assert engine.stats()["serve/window_pages_released"] >= 5
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL


def test_a_page_given_back_one_too_early_is_caught(tiny, monkeypatch):
    """The planted fault: the window group gives a page back while its last
    keys are still inside the window, and the poisoned page is read."""
    model, variables = tiny
    release = Scheduler.release_window

    def early(self, request):
        request.cache_len += 8  # one page early
        try:
            return release(self, request)
        finally:
            request.cache_len -= 8

    monkeypatch.setattr(Scheduler, "release_window", early)

    def poison(engine):
        held = {b for r in engine.scheduler.running.values() for b in r.window_blocks}
        at = jnp.asarray(sorted(set(range(1, engine.window_allocator.num_blocks)) - held))
        wk, wv = engine._window_pool
        engine._window_pool = (wk.at[:, at].set(7.0), wv.at[:, at].set(7.0))

    with jax.default_matmul_precision("highest"):
        _, requests, done = run_engine(model, variables, between_steps=poison)
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert max(gap, logprob_gap) > 100 * F32_TOL


def test_generate_through_the_dense_cache_serves_the_same_tokens(tiny):
    """The dense path holds the window layers at full length and masks."""
    model, variables = tiny
    requests = serve_requests()[:3]
    with jax.default_matmul_precision("highest"):
        _, _, done = run_engine(model, variables)
        out = InferenceEngine(model, variables).generate(
            [r["prompt"] for r in requests], GenerateConfig(max_new_tokens=6)
        )
    gap, logprob_gap, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL and logprob_gap < F32_TOL
    for row, r in enumerate(requests):  # left-padded rows of 5, 30 and 11 tokens
        assert out["tokens"][row] == done[r["id"]]["tokens"][:6]
        assert np.allclose(out["logprobs"][row], done[r["id"]]["logprobs"][:6], atol=F32_TOL)


def test_bfloat16_serving_passes_and_the_fp8_control_does_not(monkeypatch):
    router = {"num_experts_per_tok": 8, "experts_first": 8}
    model = Afmoe(AfmoeConfig(**{
        **TINY, **router, "num_experts": 64, "experts_held": 16, "param_dtype": "bfloat16",
        "compute_dtype": "bfloat16"}))
    monkeypatch.setitem(REFERENCE_CFG, "num_experts_per_tok", 8)
    monkeypatch.setitem(REFERENCE_CFG, "experts_first", 8)
    variables = seeded_variables(model, scale=0.1)
    engine, requests, done = run_engine(model, variables)
    gap, _, control = served_against_reference(variables, requests, done, quant="fp8")
    assert gap <= BF16_GAP < control, (gap, control)


# -------------------------------------------------------- the two allocators


def scheduler(blocks=16, window_blocks=8, window_pages=4, prefill_chunk=8):
    return Scheduler(
        SchedulerConfig(
            max_batch=2, max_model_len=64, block_size=8, prefill_chunk=prefill_chunk,
        ),
        BlockAllocator(blocks + 1),
        WindowGroup(BlockAllocator(window_blocks + 1, group="window"), 16, window_pages),
    )


def prefill(s, request):
    """Every chunk of the request's prompt, as the engine walks them."""
    while request.prefilled < len(request.prefill_tokens):
        plan = s.next_prefill()
        assert plan is not None and plan[0] is request
        _, chunk, _ = plan
        request.prefilled += len(chunk)
        request.cache_len += len(chunk)
        s.release_window(request)


def test_admission_takes_pages_of_both_groups_or_of_neither():
    s = scheduler(blocks=16, window_blocks=3)
    s.submit(ServeRequest(id="a", prompt=[1] * 20, max_new_tokens=4))
    (a,) = s.admit()  # 3 pages of the first group, the first chunk's one of the window group
    assert len(a.blocks) == 3 and len(a.window_blocks) == 1 and a.window_first == 0
    prefill(s, a)  # 20 tokens, all inside the window: three pages, the window pool's all
    assert len(a.window_blocks) == 3 and s.window_allocator.free_blocks == 0
    s.submit(ServeRequest(id="b", prompt=[1] * 20, max_new_tokens=4))
    # b: the first group has pages for it, the window group has none: nothing is taken
    assert s.admit() == []
    assert s.allocator.blocks_in_use == 3 and s.window_allocator.blocks_in_use == 3
    assert [r.id for r in s.waiting] == ["b"] and s.waiting[0].blocks == []
    with pytest.raises(ValueError, match="unallocated"):
        s.window_allocator.free([5])  # no double free, in either group
    s.finish(a, "max_tokens")
    assert s.allocator.blocks_in_use == 0 and s.window_allocator.blocks_in_use == 0
    with pytest.raises(ValueError, match="unallocated"):
        s.window_allocator.free([1])
    (b,) = s.admit()
    assert b.id == "b" and len(b.blocks) == 3 and len(b.window_blocks) == 1


def test_a_window_that_can_never_fit_is_refused_for_capacity():
    s = scheduler(blocks=16, window_blocks=3)  # a row's window needs 4 pages at once
    s.submit(ServeRequest(id="long", prompt=[1] * 40, max_new_tokens=4))
    s.submit(ServeRequest(id="short", prompt=[1] * 10, max_new_tokens=4))
    admitted = s.admit()
    assert [r.id for r in admitted] == ["short"]
    assert [(r.id, r.stop_reason) for r in s.completed] == [("long", "capacity")]
    assert s.allocator.blocks_in_use == 2 and s.window_allocator.blocks_in_use == 1


def test_the_window_group_gives_back_what_the_lengths_say_and_stays_in_budget():
    s = scheduler(blocks=16, window_blocks=8)
    s.submit(ServeRequest(id="a", prompt=[1] * 50, max_new_tokens=13))
    (a,) = s.admit()
    assert len(a.blocks) == 7  # 51 positions: held from admission on
    held = []
    while a.prefilled < len(a.prefill_tokens):
        _, chunk, _ = s.next_prefill()
        held.append(len(a.window_blocks))
        a.prefilled += len(chunk)
        a.cache_len += len(chunk)
        s.release_window(a)
    for _ in range(13):
        assert s.ensure_decode_blocks(a)
        held.append(len(a.window_blocks))
        a.cache_len += 1
        s.release_window(a)
    assert max(held) <= 4 and len(a.blocks) == 8
    # 63 cached tokens: the pages wholly in front of 63 - 16 + 1 = 48 went back, 6 of them
    assert s.window_pages_released == 6 == a.window_first
    assert len(a.window_blocks) == 2 and s.window_allocator.blocks_in_use == 2
    assert s.window_allocator.peak_in_use <= 4
    s.finish(a, "max_tokens")
    assert s.allocator.blocks_in_use == 0 and s.window_allocator.blocks_in_use == 0


def test_window_pressure_evicts_and_the_replay_leaves_both_allocators_empty():
    """Two rows whose windows do not fit the window pool together: the younger
    is evicted when the older's next chunk needs a page, comes back with its
    progress folded in, and both finish; nothing is left in either group.
    (Chunks of 12 in pages of 8: a row holds up to 4 pages of its budget of 5.)"""
    s = scheduler(blocks=32, window_blocks=5, window_pages=5, prefill_chunk=12)
    old = ServeRequest(id="old", prompt=[1] * 40, max_new_tokens=2, arrival_s=1.0)
    young = ServeRequest(id="young", prompt=[2] * 40, max_new_tokens=2, arrival_s=2.0)
    s.submit(old)
    s.submit(young)
    assert len(s.admit()) == 2
    prefill(s, old)  # takes the pool's pages chunk by chunk: the younger row goes
    assert s.evictions == 1 and young.slot is None and young.window_blocks == []
    assert s.window_allocator.blocks_in_use == len(old.window_blocks) <= 4 == s.window_allocator.peak_in_use - 1
    old.generated = [5, 6]
    s.finish(old, "max_tokens")
    (again,) = s.admit()
    assert again is young and young.evictions == 1 and young.window_first == 0
    prefill(s, young)
    s.finish(young, "max_tokens")
    assert s.allocator.blocks_in_use == 0 and s.window_allocator.blocks_in_use == 0
    assert s.allocator.free_blocks == 32 and s.window_allocator.free_blocks == 5


def test_drain_and_replay_through_the_engine_leave_both_pools_empty(tiny, tmp_path):
    from llm_training_tpu.serve.journal import RequestJournal, replay_journal

    model, variables = tiny
    requests = serve_requests()[:3]
    with jax.default_matmul_precision("highest"):
        first = ServingEngine(model, variables, ServeConfig(**SERVE))
        first.attach_journal(RequestJournal(tmp_path / "journal.jsonl"))
        events = []
        for r in requests:
            events += first.submit(**r)
        for _ in range(7):
            events += first.step()
        assert first.window_allocator.blocks_in_use > 0
        summary = first.drain()
        assert summary["blocks_in_use"] == 0 == first.window_allocator.blocks_in_use
        first.journal.close()
        second = ServingEngine(model, variables, ServeConfig(**SERVE))
        for entry in replay_journal(tmp_path / "journal.jsonl"):
            events += second.submit_resumed(entry)
        while not second.idle:
            events += second.step()
    assert second.allocator.blocks_in_use == 0 and second.window_allocator.blocks_in_use == 0
    done = {e["id"]: e for e in events if e["type"] == "done"}
    gap, _, _ = served_against_reference(variables, requests, done)
    assert gap < F32_TOL
