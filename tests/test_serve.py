"""Serving subsystem tests (docs/serving.md): paged-decode parity against
the dense `DecodeState` path and the full-forward oracle, continuous-
batching behaviours (mid-stream admission, eviction-then-resume, slot
recycling), the block allocator / scheduler policy units, the ragged
paged-decode kernel vs the XLA gather fallback, and the `== Serving ==`
report section."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import full_forward_greedy as _full_forward_greedy

from llm_training_tpu.infer import GenerateConfig, InferenceEngine
from llm_training_tpu.models import Gemma, GemmaConfig, Llama, LlamaConfig
from llm_training_tpu.serve import (
    BlockAllocator,
    Scheduler,
    SchedulerConfig,
    ServeConfig,
    ServeRequest,
    ServingEngine,
)
from llm_training_tpu.serve.paged_cache import TRASH_BLOCK, resolve_block_size
from llm_training_tpu.telemetry import get_registry

TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=64,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    max_position_embeddings=64, attention_impl="xla",
    compute_dtype="float32", param_dtype="float32",
)


def _init(model, seed=0):
    return jax.jit(model.init)(jax.random.key(seed), np.zeros((1, 4), np.int32))


def _serve_all(model, variables, prompts, n, **overrides):
    """Drain `prompts` through a ServingEngine; -> ({id: tokens}, engine)."""
    config = ServeConfig(**{
        "max_batch": 2, "max_model_len": 48, "block_size": 8,
        "prefill_chunk": 4, "eos_token_id": None, **overrides,
    })
    engine = ServingEngine(model, variables, config)
    events = engine.run([
        {"id": str(row), "prompt": list(p), "max_new_tokens": n}
        for row, p in enumerate(prompts)
    ])
    done = {e["id"]: e for e in events if e["type"] == "done"}
    assert engine.allocator.blocks_in_use == 0, "pool leak after drain"
    return done, engine


# ------------------------------------------------------- allocator unit


def test_allocator_alloc_free_roundtrip():
    allocator = BlockAllocator(num_blocks=5)  # 4 usable + trash
    assert allocator.free_blocks == 4
    blocks = allocator.alloc(3)
    assert len(blocks) == 3 and TRASH_BLOCK not in blocks
    # all-or-nothing: asking past the remaining 1 allocates NOTHING
    assert allocator.alloc(2) is None
    assert allocator.free_blocks == 1
    allocator.free(blocks)
    assert allocator.free_blocks == 4 and allocator.blocks_in_use == 0
    assert allocator.peak_in_use == 3
    with pytest.raises(ValueError):
        allocator.free([blocks[0]])  # double free is a bug, not a no-op
    with pytest.raises(ValueError):
        BlockAllocator(num_blocks=1)  # trash block only — unusable


def test_allocator_occupancy_gauges():
    """Set where the owner asks (`publish`: the engine does once a step),
    not on every `alloc` and `free`."""
    allocator = BlockAllocator(num_blocks=4)
    blocks = allocator.alloc(2)
    registry = get_registry()
    assert registry.gauge("decode/cache_blocks_in_use").value == 0
    allocator.publish()
    assert registry.gauge("decode/cache_blocks_in_use").value == 2
    allocator.free(blocks)
    allocator.publish()
    assert registry.gauge("decode/cache_blocks_in_use").value == 0
    assert registry.gauge("decode/cache_peak_blocks_in_use").value == 2


# ------------------------------------------------------- scheduler unit


def _scheduler(max_batch=2, blocks=8, block_size=8, max_len=32, chunk=4):
    return Scheduler(
        SchedulerConfig(
            max_batch=max_batch, max_model_len=max_len,
            block_size=block_size, prefill_chunk=chunk,
        ),
        BlockAllocator(blocks + 1),
    )


def test_scheduler_rejects_impossible_requests():
    scheduler = _scheduler(max_len=16)
    over = ServeRequest(id="over", prompt=[1] * 10, max_new_tokens=10)
    assert scheduler.submit(over) is over and over.stop_reason == "rejected"
    empty = ServeRequest(id="empty", prompt=[], max_new_tokens=4)
    assert scheduler.submit(empty) is empty
    ok = ServeRequest(id="ok", prompt=[1, 2], max_new_tokens=4)
    assert scheduler.submit(ok) is None and scheduler.waiting[0] is ok


def test_scheduler_admission_is_all_or_nothing():
    scheduler = _scheduler(blocks=2, max_len=32)
    long = ServeRequest(id="long", prompt=[1] * 20, max_new_tokens=4)
    short = ServeRequest(id="short", prompt=[1, 2], max_new_tokens=4)
    scheduler.submit(long)
    scheduler.submit(short)
    # head of queue needs ceil(21/8)=3 blocks, pool holds 2, nothing is
    # running to drain -> head fails with 'capacity' instead of starving
    # the queue; the short request behind it admits normally
    admitted = scheduler.admit()
    assert long.stop_reason == "capacity"
    assert admitted == [short] and short.slot is not None
    assert scheduler.allocator.blocks_in_use == 1


def test_scheduler_chunked_prefill_is_oldest_first():
    scheduler = _scheduler(chunk=4)
    first = ServeRequest(id="first", prompt=[1] * 6, max_new_tokens=2, arrival_s=1.0)
    second = ServeRequest(id="second", prompt=[2] * 3, max_new_tokens=2, arrival_s=2.0)
    scheduler.submit(first)
    scheduler.submit(second)
    scheduler.admit()
    request, chunk, start = scheduler.next_prefill()
    assert request is first and chunk == [1, 1, 1, 1] and start == 0
    request.prefilled += len(chunk)
    request, chunk, start = scheduler.next_prefill()
    assert request is first and chunk == [1, 1] and start == 4
    request.prefilled += len(chunk)
    request.cache_len = 6
    assert first.decoding
    request, chunk, start = scheduler.next_prefill()
    assert request is second


def test_scheduler_evicts_lowest_priority_then_youngest():
    scheduler = _scheduler(blocks=2, max_batch=3, chunk=8)
    vip = ServeRequest(id="vip", prompt=[1] * 4, max_new_tokens=8,
                       priority=1, arrival_s=1.0)
    old = ServeRequest(id="old", prompt=[2] * 4, max_new_tokens=8, arrival_s=2.0)
    young = ServeRequest(id="young", prompt=[3] * 4, max_new_tokens=8, arrival_s=3.0)
    for request in (vip, old, young):
        scheduler.submit(request)
    # blocks=2 admits exactly two 1-block residencies; 'young' waits
    assert scheduler.admit() == [vip, old]
    vip.cache_len = old.cache_len = 8  # both pages now full
    # vip's next token needs a second block: pool dry -> the LOWEST
    # priority running request is the victim ('old', not the vip)
    assert scheduler.ensure_decode_blocks(vip)
    assert old.slot is None and old.evictions == 1
    assert scheduler.waiting[0] is old  # requeued at the FRONT
    assert len(vip.blocks) == 2


def test_scheduler_eviction_folds_progress_into_prompt():
    scheduler = _scheduler(blocks=1, max_batch=2)
    request = ServeRequest(id="r", prompt=[1, 2, 3], max_new_tokens=8)
    scheduler.submit(request)
    scheduler.admit()
    request.generated = [7, 8]
    request.cache_len = 5
    scheduler.evict(request)
    assert scheduler.allocator.blocks_in_use == 0
    readmitted = scheduler.admit()
    assert readmitted == [request]
    # the re-prefill replays prompt + generated, so greedy continuation
    # is token-identical to the uninterrupted run
    assert request.prefill_tokens == [1, 2, 3, 7, 8]
    assert request.prefilled == 0 and request.cache_len == 0


# -------------------------------------------------- paged tuning / pool


def test_resolve_block_size_paged_kind(monkeypatch):
    config = LlamaConfig(**TINY)
    monkeypatch.delenv("PAGED_BLOCK_K", raising=False)
    assert resolve_block_size(config, max_model_len=64) == 16  # paged default
    monkeypatch.setenv("PAGED_BLOCK_K", "32")
    assert resolve_block_size(config, max_model_len=64) == 32
    # explicit config wins over env; sublane (8) alignment enforced
    assert resolve_block_size(config, 64, block_size=8) == 8
    with pytest.raises(ValueError):
        resolve_block_size(config, 64, block_size=12)


def test_paged_append_pads_go_to_trash():
    from llm_training_tpu.ops.paged_attention import paged_append

    pool = jnp.zeros((4, 1, 8, 4))  # [blocks, h, page, d]
    k = jnp.ones((1, 4, 1, 4))
    seg = jnp.asarray([[1, 1, 0, 0]])  # 2 real tokens, 2 pads
    tables = jnp.asarray([[2, 3]])
    new_k, _ = paged_append(
        pool, pool, k, k, jnp.asarray([7]), tables, seg
    )
    # row length 7: real tokens land at block 2 slot 7 then block 3 slot 0
    assert float(new_k[2, 0, 7, 0]) == 1.0
    assert float(new_k[3, 0, 0, 0]) == 1.0
    # pads went to the trash block, nowhere else
    assert float(jnp.sum(new_k[1:])) == 2 * 4  # two real tokens x head_dim
    assert float(jnp.sum(new_k[TRASH_BLOCK])) > 0


# One geometry of `test_paged_kernel_matches_gather_fallback`; a case names
# what it changes. `lengths` are the rows' token counts BEFORE this step's
# append (the kernel sees one more); `trip` forces that many pages a trip
# (None: what the shapes give, here the whole table); `idle` rows get a trash
# table.
_KERNEL_CASE = dict(
    lengths=(0, 7, 20), window=None, cap=None, group=2, kv_heads=2, pages=3,
    trip=None, idle=(), page=8, head_dim=8, dtype="float32",
)


@pytest.mark.parametrize("case", [
    pytest.param({}, id="plain"),
    pytest.param(dict(window=5), id="window"),
    pytest.param(dict(cap=4.0, group=1), id="cap-group1"),
    pytest.param(dict(window=5, cap=4.0, group=4), id="window-cap-group4"),
    # rows of 1, 1, 3 and 6 live pages at 2 pages a trip: 1, 1, 2 and 3 trips
    pytest.param(dict(lengths=(0, 7, 20, 40), pages=8, trip=2), id="several-trips"),
    # 32 tokens end on the second trip's last slot, 33 open a third, 64 fill
    # the table, 16 end on a page's
    pytest.param(dict(lengths=(31, 32, 63, 15), pages=8, trip=2),
                 id="trip-boundary-and-full-table"),
    pytest.param(dict(lengths=(47, 0, 30), pages=6, trip=3, idle=(1,)),
                 id="idle-row-beside-long-rows"),
    pytest.param(dict(lengths=(3, 9, 12), pages=12, trip=4),
                 id="table-wider-than-any-row"),
    # the window opens past the first trips: their pages are never fetched
    pytest.param(dict(lengths=(60, 41, 5), window=12, cap=4.0, pages=8, trip=2),
                 id="window-skips-leading-trips"),
    pytest.param(dict(lengths=(0, 21, 37), kv_heads=10, group=4, pages=5, trip=2),
                 id="10-kv-heads-group4"),
    pytest.param(dict(lengths=(0, 21, 37), kv_heads=1, group=4, pages=5, trip=2),
                 id="1-kv-head-group4"),
    pytest.param(dict(lengths=(0, 21, 37), kv_heads=1, group=1, pages=5, trip=3),
                 id="1-kv-head-group1"),
    # the cells' tile (page 16, head_dim 128, bf16 pool) at Phi-3's heads,
    # pages a trip from the shapes (the whole table of 6), against the
    # gather path computed in float32
    pytest.param(dict(lengths=(0, 50, 95), kv_heads=10, group=4, pages=6,
                      page=16, head_dim=128, dtype="bfloat16"),
                 id="bf16-page16-dim128"),
])
def test_paged_kernel_matches_gather_fallback(case, monkeypatch):
    """The interpreted Pallas kernel and the XLA gather path must agree on
    ragged single-token decode — GQA groups, sliding windows, soft cap, and
    every way a row's live pages can fall across the kernel's trips."""
    from llm_training_tpu.ops.pallas import paged_attention as kernel
    from llm_training_tpu.ops.paged_attention import paged_cached_attention

    case = {**_KERNEL_CASE, **case}
    kv_heads, group, head_dim = case["kv_heads"], case["group"], case["head_dim"]
    page, pages, dtype = case["page"], case["pages"], jnp.dtype(case["dtype"])
    lengths = jnp.asarray(case["lengths"], jnp.int32)
    batch = len(case["lengths"])
    if case["trip"] is not None:
        page_bytes = kv_heads * page * head_dim * dtype.itemsize
        monkeypatch.setattr(kernel, "_KV_SCRATCH_BYTES", 4 * page_bytes * case["trip"])
        assert kernel.pages_per_trip(
            kv_heads, page, head_dim, dtype.itemsize, pages
        ) == case["trip"]
    keys = jax.random.split(jax.random.key(0), 4)
    pool_shape = (1 + batch * pages, kv_heads, page, head_dim)
    pool_k = jax.random.normal(keys[0], pool_shape).astype(dtype)
    pool_v = jax.random.normal(keys[1], pool_shape).astype(dtype)
    q = jax.random.normal(keys[2], (batch, 1, kv_heads * group, head_dim)).astype(dtype)
    k = jax.random.normal(keys[3], (batch, 1, kv_heads, head_dim)).astype(dtype)
    v = (jax.random.normal(keys[3], (batch, 1, kv_heads, head_dim)) + 1.0).astype(dtype)
    tables = jnp.arange(1, 1 + batch * pages, dtype=jnp.int32).reshape(batch, pages)
    for row in case["idle"]:
        tables = tables.at[row].set(TRASH_BLOCK)

    def attend(impl, *operands):
        q, k, v, pool_k, pool_v = operands
        return paged_cached_attention(
            q, k, v, (pool_k, pool_v), lengths, tables,
            sliding_window=case["window"], logits_soft_cap=case["cap"], impl=impl,
        )[0]

    operands = (q, k, v, pool_k, pool_v)
    got = attend("pallas", *operands)
    ref = attend("xla", *(x.astype(jnp.float32) for x in operands))
    assert got.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 1e-2  # the bf16 output's own rounding
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("kv_heads,page,head_dim,itemsize,table,expect", [
    (10, 16, 128, 2, 96, 12),   # Phi-3-medium's cache
    (16, 16, 128, 2, 96, 8),    # OLMoE's
    (8, 16, 128, 2, 8, 8),      # the table is narrower than the budget
    (1, 16, 128, 2, 96, 96),    # a tensor shard's single head: the whole table
    (8, 128, 128, 2, 16, 2),    # pages of 128 tokens
    (4, 16, 256, 4, 4096, 8),   # float32 at head_dim 256
    (64, 512, 256, 4, 32, 1),   # one page overflows the budget: still one
])
def test_paged_kernel_pages_per_trip_follows_shapes(
    kv_heads, page, head_dim, itemsize, table, expect
):
    from llm_training_tpu.ops.pallas.paged_attention import (
        _KV_SCRATCH_BYTES,
        pages_per_trip,
    )

    n = pages_per_trip(kv_heads, page, head_dim, itemsize, table)
    assert n == expect
    assert 1 <= n <= table
    scratch = 4 * n * kv_heads * page * head_dim * itemsize  # 2 slots x K, V
    assert scratch <= _KV_SCRATCH_BYTES or n == 1


# -------------------------------------------- paged == dense greedy parity


@pytest.mark.parametrize("stack", [
    dict(scan_layers=True),
    dict(scan_layers=False),
    # the looped path on which every layer has its own window
    dict(scan_layers=False, sliding_window=4,
         layer_types=["sliding_attention", "full_attention"]),
], ids=["scan", "looped", "layer_types"])
def test_paged_greedy_matches_dense_and_oracle(stack):
    """Continuous-batching greedy decode through the paged pool must be
    token-identical to BOTH the dense `DecodeState` engine and the full-
    forward oracle, with ragged prompts spanning page boundaries."""
    model = Llama(LlamaConfig(**TINY, **stack))
    variables = _init(model)
    prompts = [[3, 17, 42, 7, 11], [5, 9], [1, 2, 3]]
    n = 8
    done, _ = _serve_all(model, variables, prompts, n)
    dense = InferenceEngine(model, variables).generate(
        prompts, GenerateConfig(max_new_tokens=n, eos_token_id=None)
    )
    for row, prompt in enumerate(prompts):
        expected = _full_forward_greedy(model, variables, prompt, n)
        assert done[str(row)]["tokens"] == expected, f"row {row} vs oracle"
        assert dense["tokens"][row] == expected, f"row {row} dense vs oracle"


def test_paged_greedy_moe_and_sliding_window():
    model = Llama(LlamaConfig(
        **TINY, num_experts=4, num_experts_per_tok=2, moe_intermediate_size=32,
        sliding_window=4,
    ))
    variables = _init(model)
    prompts = [[3, 17, 42, 7, 11, 2], [9, 4, 6]]
    done, _ = _serve_all(model, variables, prompts, 6)
    for row, prompt in enumerate(prompts):
        assert done[str(row)]["tokens"] == _full_forward_greedy(
            model, variables, prompt, 6
        ), f"row {row}"


def test_paged_greedy_gemma():
    model = Gemma(GemmaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, max_position_embeddings=64, attention_impl="xla",
        compute_dtype="float32",
    ))
    variables = _init(model)
    prompts = [[3, 17, 42], [5, 9, 11, 13]]
    done, _ = _serve_all(model, variables, prompts, 5)
    for row, prompt in enumerate(prompts):
        assert done[str(row)]["tokens"] == _full_forward_greedy(
            model, variables, prompt, 5
        ), f"row {row}"


def test_eos_recycles_slot_and_reports_stop_reason():
    """A row hitting eos frees its slot/blocks immediately; the engine
    reports 'eos' and the dense engine satellite reports the same per-row
    lengths/stop_reasons split."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    prompt = [3, 17, 42, 7]
    oracle = _full_forward_greedy(model, variables, prompt, 6)
    eos = oracle[2]  # force an early deterministic stop
    config = ServeConfig(max_batch=1, max_model_len=32, block_size=8,
                         prefill_chunk=4, eos_token_id=eos)
    engine = ServingEngine(model, variables, config)
    events = engine.run([{"id": "r", "prompt": prompt, "max_new_tokens": 6}])
    done = [e for e in events if e["type"] == "done"]
    assert done[0]["stop_reason"] == "eos"
    assert done[0]["tokens"] == oracle[:3]  # up to and including eos
    assert engine.allocator.blocks_in_use == 0

    dense_engine = InferenceEngine(model, variables)  # one compile set
    dense = dense_engine.generate(
        [prompt], GenerateConfig(max_new_tokens=6, eos_token_id=eos)
    )
    assert dense["stop_reasons"] == ["eos"] and dense["lengths"] == [3]
    full = dense_engine.generate(
        [prompt], GenerateConfig(max_new_tokens=6, eos_token_id=None)
    )
    assert full["stop_reasons"] == ["max_tokens"] and full["lengths"] == [6]


# ------------------------------------- the parent's rebuild path, recorded

# sha256 of the event stream of `_event_stream` for each family's tiny
# config, recorded at PR 37's tree (b15c5df): the engine that rebuilt every
# row, transfer and key a step from the requests. To record anew, print
# `_stream_hash(_event_stream(build(**STREAM_SERVE)))` under the tree to trust.
RECORDED = {
    "llama": "1e3ceb80af323c92",
    "olmoe": "e393c7c6238fb690",
    "solar": "e80e81a66ef70fab",
    "longcat": "49c2aacf8cc3ecc2",
    "afmoe": "003f5ac484ecc69d",
    "llama-sampled": "c8948767af2e47dc",
}
STREAM_SERVE = dict(max_batch=3, max_model_len=48, block_size=8, prefill_chunk=4, num_blocks=10)


def _stream_builders():
    from test_serve_spans import (
        TINY_MOE, _afmoe_engine, _engine, _longcat_engine, _solar_engine,
    )

    from llm_training_tpu.infer.sampling import SamplingConfig

    return {
        "llama": _engine,
        "olmoe": lambda **serve: _engine(TINY_MOE, **serve),
        "solar": _solar_engine,
        "longcat": _longcat_engine,
        "afmoe": _afmoe_engine,
        "llama-sampled": lambda **serve: _engine(
            seed=7, sampling=SamplingConfig(temperature=0.8, top_p=0.9), **serve
        ),
    }


def _stream_entry(e):
    if e["type"] == "token":
        return [e["id"], e["token"], round(e["logprob"], 4)]
    return [e["id"], e["stop_reason"], e["tokens"], e["evictions"]]


def _event_stream(engine, steps=200, seed=0):
    """Seeded arrivals over `steps` engine steps of a pool too small for its
    rows (evictions, requeues, every slot taken again and again): each token
    with its logprob, each done event, and what the run counted."""
    import random

    rng = random.Random(seed)
    stream, submitted = [], 0
    for _ in range(steps):
        events = []
        if len(engine.scheduler.waiting) < 2 and rng.random() < 0.45:
            prompt = [rng.randrange(1, 64) for _ in range(rng.choice((3, 6, 10, 15)))]
            events += engine.submit(f"r{submitted}", prompt, max_new_tokens=rng.choice((5, 12, 24)))
            submitted += 1
        events += engine.step()
        stream += [_stream_entry(e) for e in events]
    # a token leaves a call after the one that made it: the last step's, here
    stream += [_stream_entry(e) for e in engine.flush()]
    counters = get_registry().counter
    stream.append([
        engine.scheduler.evictions, counters("serve/state_resets").value,
        counters("serve/prefill_chunks").value, counters("serve/decode_rows").value,
    ])
    return stream


def _stream_hash(stream):
    import hashlib
    import json

    return hashlib.sha256(json.dumps(stream).encode()).hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(RECORDED))
def test_event_stream_equals_the_rebuild_paths_recording(case):
    """Tables kept a slot, the key folded inside the program and one packed
    transfer a call change no token, logprob or done event of 200 steps: for
    every family's stack (Solar's with its slots reused, so `fresh` and
    `serve/state_resets` are in it), and for a SAMPLED run, which draws what
    `fold_in(rng, call)` outside the program drew."""
    from llm_training_tpu.telemetry.registry import TelemetryRegistry, set_registry

    previous = set_registry(TelemetryRegistry())
    try:
        stream = _event_stream(_stream_builders()[case](**STREAM_SERVE))
    finally:
        set_registry(previous)
    assert stream[-1][0] > 0 and stream[-1][3] > 200, stream[-1]  # evictions; rows decoded
    if case == "solar":
        assert stream[-1][1] > STREAM_SERVE["max_batch"]  # a slot's second tenant started fresh
    assert _stream_hash(stream) == RECORDED[case], stream[:5] + stream[-3:]


# ------------------------------------------------- continuous batching


def test_mid_stream_admission_is_token_identical():
    """A request submitted while another is mid-decode joins the SAME
    batch (continuous batching) and both finish token-identical to the
    oracle — the dense engine's closed-batch limitation, lifted."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    first, second = [3, 17, 42, 7], [5, 9, 11]
    n = 8
    config = ServeConfig(max_batch=2, max_model_len=48, block_size=8,
                         prefill_chunk=4, eos_token_id=None)
    engine = ServingEngine(model, variables, config)
    events = list(engine.submit("first", first, max_new_tokens=n))
    while sum(e["type"] == "token" for e in events) < 2:
        events.extend(engine.step())  # 'first' is now mid-decode
    events.extend(engine.submit("second", second, max_new_tokens=n))
    events.extend(engine.step())
    assert len(engine.scheduler.running) == 2, "second not admitted mid-flight"
    while not engine.idle:
        events.extend(engine.step())
    done = {e["id"]: e for e in events if e["type"] == "done"}
    assert done["first"]["tokens"] == _full_forward_greedy(model, variables, first, n)
    assert done["second"]["tokens"] == _full_forward_greedy(model, variables, second, n)
    assert engine.peak_running == 2
    assert engine.allocator.blocks_in_use == 0


def test_eviction_then_resume_is_token_identical():
    """Under pool pressure the lowest-priority request is evicted, its
    blocks freed, and after re-admission its greedy continuation matches
    the uninterrupted oracle exactly (progress re-prefilled, already-
    streamed tokens never re-emitted)."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    prompts = [[3, 17, 42, 7], [5, 9, 11]]
    n = 12
    # 3 usable blocks of 8 for two requests reaching 15-16 tokens: growth
    # past each page boundary forces an eviction instead of a clean alloc
    done, engine = _serve_all(
        model, variables, prompts, n,
        max_batch=2, max_model_len=32, num_blocks=3, prefill_chunk=4,
    )
    assert engine.scheduler.evictions >= 1, "pool pressure never evicted"
    assert sum(d["evictions"] for d in done.values()) >= 1
    for row, prompt in enumerate(prompts):
        assert done[str(row)]["tokens"] == _full_forward_greedy(
            model, variables, prompt, n
        ), f"row {row} diverged across eviction"
    # token chunks stream exactly once per generated token despite the
    # evict/resume round trip
    assert engine.allocator.blocks_in_use == 0


def test_cross_survivor_eviction_mid_decode_step():
    """A LATER decode row's block growth can evict an EARLIER row that
    already passed its own ensure_decode_blocks this step (lower priority,
    mid-page). The evicted row must be dropped from the step's batch — its
    blocks may already belong to the evictor — and still finish
    token-identically after re-admission."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    # pool of 2: A (priority 0, prompt 4) and B (priority 1, prompt 6)
    # admit with one block each. B hits its page boundary (cache 8) while
    # A sits mid-page — B's growth needs a block, the pool is dry, and the
    # victim is A, processed EARLIER in the same decode step.
    config = ServeConfig(max_batch=2, max_model_len=16, block_size=8,
                         num_blocks=2, prefill_chunk=8, eos_token_id=None)
    engine = ServingEngine(model, variables, config)
    events = engine.run([
        {"id": "a", "prompt": [3, 17, 42, 7], "max_new_tokens": 8, "priority": 0},
        {"id": "b", "prompt": [5, 9, 11, 13, 2, 6], "max_new_tokens": 8, "priority": 1},
    ])
    done = {e["id"]: e for e in events if e["type"] == "done"}
    assert done["a"]["evictions"] >= 1, "priority eviction never fired"
    assert done["b"]["evictions"] == 0
    assert done["a"]["tokens"] == _full_forward_greedy(model, variables, [3, 17, 42, 7], 8)
    assert done["b"]["tokens"] == _full_forward_greedy(
        model, variables, [5, 9, 11, 13, 2, 6], 8
    )
    assert engine.allocator.blocks_in_use == 0


def test_capacity_failure_emits_done_event():
    """A request that fits max_model_len but can NEVER fit the pool ends
    with stop_reason='capacity' — and the protocol owes the client that
    done chunk (an interactive client would otherwise block forever)."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    engine = ServingEngine(model, variables, ServeConfig(
        max_batch=2, max_model_len=32, block_size=8, num_blocks=1,
        prefill_chunk=4, eos_token_id=None,
    ))
    events = engine.run([
        # needs ceil(13/8) = 2 blocks against a 1-block pool
        {"id": "big", "prompt": [1] * 12, "max_new_tokens": 4},
        {"id": "ok", "prompt": [3, 5], "max_new_tokens": 2},
    ])
    done = {e["id"]: e for e in events if e["type"] == "done"}
    assert done["big"]["stop_reason"] == "capacity"
    assert done["ok"]["stop_reason"] == "max_tokens"
    assert engine.allocator.blocks_in_use == 0


def test_submit_rejects_non_int_prompt():
    """A syntactically valid request with a junk prompt must fail AT
    SUBMIT (where the CLI's error contract lives), never inside a later
    engine.step() taking the whole batch down."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    engine = ServingEngine(model, variables, ServeConfig(
        max_batch=1, max_model_len=32, block_size=8, eos_token_id=None,
    ))
    with pytest.raises((TypeError, ValueError)):
        engine.submit("junk", "abc", max_new_tokens=4)
    # numeric strings coerce; the queue stays serviceable
    events = engine.run([{"id": "ok", "prompt": ["3", 17], "max_new_tokens": 2}])
    assert [e["id"] for e in events if e["type"] == "done"] == ["ok"]


def test_serve_config_validators():
    with pytest.raises(ValueError):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError):
        ServeConfig(max_model_len=1)
    with pytest.raises(ValueError):
        ServeConfig(prefill_chunk=0)
    with pytest.raises(ValueError):
        ServeConfig(block_size=0)
    with pytest.raises(ValueError):
        ServeConfig(unknown_knob=1)


def test_engine_stats_and_pool_gauges():
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    done, engine = _serve_all(model, variables, [[3, 5, 7]], 4, max_batch=1)
    stats = engine.stats()
    assert stats["serve/requests_completed"] == 1
    assert stats["serve/tokens_generated"] == 4
    assert stats["serve/tokens_per_sec"] > 0
    assert stats["decode/cache_blocks_in_use"] == 0
    assert stats["decode/cache_peak_blocks_in_use"] >= 1
    assert stats["serve/ttft_p50_ms"] > 0 and stats["serve/tpot_p50_ms"] >= 0
    registry = get_registry()
    assert registry.gauge("serve/tokens_per_sec").value == stats["serve/tokens_per_sec"]
    # pool construction published its footprint (the cache_bytes satellite)
    assert registry.gauge("decode/cache_bytes").value is not None


def test_init_decode_state_publishes_cache_bytes():
    """Satellite: EVERY dense cache construction lands decode/cache_bytes
    in the registry — not just engine.generate's."""
    from llm_training_tpu.infer import cache_bytes, init_decode_state

    state = init_decode_state(LlamaConfig(**TINY), batch_size=2, max_length=16)
    assert get_registry().gauge("decode/cache_bytes").value == cache_bytes(state)


# ----------------------------------------------------------- reporting


def test_report_serving_section():
    from llm_training_tpu.telemetry.report import _serving_section

    lines = _serving_section({
        "serve/requests_completed": 3, "serve/requests_evicted": 1,
        "serve/peak_running": 2, "serve/tokens_per_sec": 123.4,
        "serve/tokens_per_sec_per_chip": 30.85, "serve/tokens_generated": 96,
        "serve/ttft_p50_ms": 12.5, "serve/ttft_p99_ms": 80.0,
        "serve/tpot_p50_ms": 3.1, "decode/cache_blocks_total": 16,
        "decode/cache_peak_blocks_in_use": 9, "decode/cache_blocks_in_use": 0,
    })
    text = "\n".join(lines)
    assert "== Serving ==" in text
    assert "3 completed" in text and "1 evictions" in text
    assert "123.4 tokens/s" in text and "(30.9/chip)" in text
    assert "ttft: p50 12.5 ms  p99 80.0 ms" in text
    assert "16 blocks, peak 9 in use (56%)" in text
    assert "leak" not in text
    leaky = "\n".join(_serving_section({
        "serve/requests_completed": 1, "decode/cache_blocks_total": 8,
        "decode/cache_blocks_in_use": 2,
    }))
    assert "2 still held at exit (leak?)" in leaky
    assert _serving_section({"goodput/total_s": 1.0}) == []


# ------------------------------------------------- stats edges + tracing


@pytest.fixture()
def fresh_tracer():
    """A fresh process tracer so span/ttft assertions see only this test's
    events (engine + scheduler emit through the module-global tracer)."""
    from llm_training_tpu.telemetry.trace import TraceRecorder, set_tracer

    recorder = TraceRecorder(capacity=4096, sample_every=1, enabled=True)
    previous = set_tracer(recorder)
    try:
        yield recorder
    finally:
        set_tracer(previous)


def test_stats_zero_completed_requests():
    """Percentile edge: a fresh engine (and one holding only failed
    requests) must not crash on empty ttft/tpot lists — the keys are
    simply absent."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    engine = ServingEngine(model, variables, ServeConfig(
        max_batch=1, max_model_len=16, block_size=8, eos_token_id=None,
    ))
    stats = engine.stats()
    assert stats["serve/requests_completed"] == 0
    assert stats["serve/tokens_per_sec"] == 0.0
    assert "serve/ttft_p50_ms" not in stats and "serve/tpot_p50_ms" not in stats
    # a rejected request is a failure, never a latency sample
    engine.run([{"id": "big", "prompt": [1] * 20, "max_new_tokens": 4}])
    stats = engine.stats()
    assert stats["serve/requests_completed"] == 0
    assert stats["serve/requests_failed"] == 1
    assert "serve/ttft_p50_ms" not in stats


def test_stats_single_request_percentiles():
    """Percentile edge: with one completed request p50 == p99 == its own
    latency, and both match the done event's ttft_ms."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    done, engine = _serve_all(model, variables, [[3, 5, 7]], 6, max_batch=1)
    stats = engine.stats()
    assert stats["serve/requests_completed"] == 1
    assert stats["serve/ttft_p50_ms"] == pytest.approx(stats["serve/ttft_p99_ms"])
    # (the done event rounds to a microsecond: 5e-4 of a tpot that can be 0.3 ms)
    assert stats["serve/ttft_p50_ms"] == pytest.approx(done["0"]["ttft_ms"], rel=1e-3, abs=6e-4)
    assert stats["serve/tpot_p50_ms"] == pytest.approx(stats["serve/tpot_p99_ms"])
    assert stats["serve/tpot_p50_ms"] == pytest.approx(done["0"]["tpot_ms"], rel=1e-3, abs=6e-4)


def test_stats_evicted_request_ttft_from_original_arrival(fresh_tracer):
    """Percentile edge (the subtle one): an evicted-then-resumed request's
    TTFT is measured from its ORIGINAL arrival — never from the requeue —
    and is never double-counted (exactly one first_token per request)."""
    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    prompts = [[3, 17, 42, 7], [5, 9, 11]]
    done, engine = _serve_all(
        model, variables, prompts, 12,
        max_batch=2, max_model_len=32, num_blocks=3, prefill_chunk=4,
    )
    assert engine.scheduler.evictions >= 1
    ring = fresh_tracer.snapshot()
    by_request = {}
    for event in ring:
        args = event.get("args") or {}
        if "request_id" in args:
            by_request.setdefault(args["request_id"], []).append(event)
    evicted = [r for r in engine.scheduler.completed if r.evictions]
    assert evicted, "pool pressure never evicted"
    for request in engine.scheduler.completed:
        events = by_request[request.id]
        firsts = [e for e in events if e["name"] == "first_token"]
        assert len(firsts) == 1, "first_token double-counted across residencies"
        submit = next(e for e in events if e["name"] == "submit")
        # arrival-anchored: the instant's ttft equals first_token - submit
        measured = 1000.0 * (firsts[0]["ts"] - submit["ts"])
        assert firsts[0]["args"]["ttft_ms"] == pytest.approx(measured, abs=1.0)
        assert done[request.id]["ttft_ms"] == pytest.approx(measured, abs=1.0)
    for request in evicted:
        events = by_request[request.id]
        evict_ts = [e["ts"] for e in events if e["name"] == "evicted"]
        first_ts = next(e for e in events if e["name"] == "first_token")["ts"]
        if any(t < first_ts for t in evict_ts):
            # evicted before its first token: a requeue-anchored TTFT would
            # be smaller than first_token - requeue; the reported one spans
            # the whole wait from original arrival
            requeue_anchored = 1000.0 * (first_ts - min(evict_ts))
            assert done[request.id]["ttft_ms"] > requeue_anchored - 1.0
    # stats percentiles are computed over those same arrival-anchored values
    stats = engine.stats()
    ttfts = sorted(d["ttft_ms"] for d in done.values())
    assert min(ttfts) - 1e-3 <= stats["serve/ttft_p50_ms"] <= max(ttfts) + 1e-3


def test_request_lifecycle_spans_tile_wall_clock(fresh_tracer):
    """Acceptance: every completed request's queue -> prefill -> decode
    spans sum to its wall time (arrival -> completion), across evictions,
    and the sink receives only sampled requests."""
    import time as _time

    model = Llama(LlamaConfig(**TINY))
    variables = _init(model)
    prompts = [[3, 17, 42, 7], [5, 9, 11]]
    done, engine = _serve_all(
        model, variables, prompts, 12,
        max_batch=2, max_model_len=32, num_blocks=3, prefill_chunk=4,
    )
    t_end = _time.perf_counter()
    ring = fresh_tracer.snapshot()
    for request in engine.scheduler.completed:
        phase_sum = sum(
            e["dur"] for e in ring
            if e.get("ph") == "X"
            and (e.get("args") or {}).get("request_id") == request.id
            and e["name"] in ("queue", "prefill", "decode")
        )
        wall = t_end - request.arrival_s
        # phases tile arrival -> finish exactly; only the post-finish slice
        # of `wall` (bookkeeping after the last done event) is uncovered
        assert 0 < phase_sum <= wall + 1e-6
        last = request.last_token_s - request.arrival_s
        assert phase_sum == pytest.approx(last, abs=0.05)
    engine_steps = [e for e in ring if e["name"] == "engine_step"]
    assert engine_steps and all(e["ph"] == "X" for e in engine_steps)


def test_request_sampling_gates_sink_not_ring(tmp_path):
    """LLMT_TRACE_SAMPLE=N: only every Nth request reaches trace.jsonl;
    the ring (flight recorder) still sees all of them."""
    from llm_training_tpu.telemetry.trace import (
        TraceRecorder,
        read_trace_events,
        set_tracer,
    )

    recorder = TraceRecorder(capacity=4096, sample_every=2, enabled=True)
    previous = set_tracer(recorder)
    try:
        recorder.attach_sink(tmp_path / "trace.jsonl")
        model = Llama(LlamaConfig(**TINY))
        variables = _init(model)
        done, _ = _serve_all(
            model, variables, [[3, 5, 7], [9, 11], [4, 8]], 2, max_batch=2
        )
        assert len(done) == 3
        recorder.detach_sink()
        written = {
            (e.get("args") or {}).get("request_id")
            for e in read_trace_events(tmp_path / "trace.jsonl")
            if (e.get("args") or {}).get("request_id")
        }
        assert written == {"0", "2"}  # every 2nd submit, starting at the first
        ring_ids = {
            (e.get("args") or {}).get("request_id")
            for e in recorder.snapshot()
            if (e.get("args") or {}).get("request_id")
        }
        assert ring_ids == {"0", "1", "2"}
    finally:
        recorder.detach_sink()
        set_tracer(previous)


# ------------------------------------------------- loadgen argument guard


def _loadgen():
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parent.parent / "scripts" / "serve_loadgen.py"
    spec = importlib.util.spec_from_file_location("serve_loadgen", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("argv,swallowed", [
    # the precommit form: the value of --prefill-chunk repeats the value of
    # --requests EARLIER on the line, which the old guard took for its place
    (["--config", "c", "--requests", "4", "--max-new-tokens", "16", "run_root=x",
      "--max-batch", "2", "--max-model-len", "64", "--prefill-chunk", "4",
      "--eos-token-id", "-1"], None),
    # no earlier positional: argparse hands --max-batch's value to serve_args
    (["--config", "c", "--max-batch", "2", "run_root=x"], "2"),
    # ... also when that value repeats a known flag's
    (["--config", "c", "--requests", "2", "--max-batch", "2"], "2"),
    # the explicit separator makes everything after it intentional
    (["--config", "c", "--", "--max-batch", "2", "run_root=x"], None),
])
def test_loadgen_misplaced_flag_guard(argv, swallowed):
    loadgen = _loadgen()
    if swallowed is None:
        args = loadgen.parse_args(argv)
        assert "--max-batch" in args.serve_args and "2" in args.serve_args
    else:
        with pytest.raises(SystemExit, match=f"positional '{swallowed}' follows the "
                                             "unknown flag '--max-batch'"):
            loadgen.parse_args(argv)


def test_paged_kernel_under_a_sharded_mesh_matches_gather(devices):
    """On a multi-device mesh the kernel runs in a shard_map over `tensor`
    (GSPMD cannot partition a Mosaic kernel — tests/test_chip_compile.py
    holds the compile); the result must still equal the gather path."""
    from llm_training_tpu.ops.paged_attention import paged_cached_attention
    from llm_training_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(fsdp_size=4, tensor_parallel_size=2), devices)
    batch, kv_heads, group, head_dim, page, pages = 3, 2, 2, 8, 8, 3
    keys = jax.random.split(jax.random.key(1), 5)
    pool_shape = (1 + batch * pages, kv_heads, page, head_dim)
    pool_k = jax.random.normal(keys[0], pool_shape)
    pool_v = jax.random.normal(keys[1], pool_shape)
    q = jax.random.normal(keys[2], (batch, 1, kv_heads * group, head_dim))
    k = jax.random.normal(keys[3], (batch, 1, kv_heads, head_dim))
    v = jax.random.normal(keys[4], (batch, 1, kv_heads, head_dim))
    tables = jnp.arange(1, 1 + batch * pages, dtype=jnp.int32).reshape(batch, pages)
    lengths = jnp.asarray([0, 7, 20], jnp.int32)

    def attend(impl):
        return jax.jit(lambda q, k, v, pk, pv: paged_cached_attention(
            q, k, v, (pk, pv), lengths, tables, impl=impl
        )[0])

    with mesh:
        got = attend("pallas")(q, k, v, pool_k, pool_v)
    ref = attend("xla")(q, k, v, pool_k, pool_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)
