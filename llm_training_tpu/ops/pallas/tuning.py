"""Block-size autotuning for the Pallas flash-attention kernels.

"Scalable Training of Language Models using JAX pjit and TPUv4" (PAPERS.md)
makes the point this module operationalizes: TPU kernel throughput is won
or lost in per-shape block/layout choices. The flash kernels used to read
one import-time ``FLASH_BLOCK_Q/K = 1024`` default shared by the forward
and both backward kernels — but the backward kernels carry different
scratch footprints (dq: one [block_q, d] accumulator; dkv: two [block_k, d]
accumulators over a 4-D grid), so their VMEM-optimal tiles are generally
not the forward's.

This module resolves `(block_q, block_k)` **at call time**, separately for
the forward (`kind="fwd"`) and backward (`kind="bwd"`) kernels, in priority
order:

1. **call** — explicit `block_q=`/`block_k=` arguments win unconditionally
   (tests, microbenchmarks, the sweep itself);
2. **env** — `FLASH_BLOCK_Q` / `FLASH_BLOCK_K` (both kinds) and
   `FLASH_BLOCK_Q_BWD` / `FLASH_BLOCK_K_BWD` (backward only), read per
   call so a sweep or test can override without re-importing anything;
3. **table** — the persisted tuning table (JSON under `config/tuning/`,
   written by `scripts/tune_flash_blocks.py`), keyed by
   `(kind, seq_len, head_dim, dtype, causal, sliding_window)`; an exact
   key wins, else the nearest `seq_len` among entries matching every other
   field (block choice varies slowly and monotonically with seq);
4. **default** — 1024x1024, the v5e measurement at seq 2048 the old
   constant encoded.

Every resolution is recorded into the active telemetry registry
(`flash/<kind>/block_q|block_k` gauges + a `flash/tuning_table_hit/<source>`
counter), so `telemetry.jsonl` shows which blocks each compiled step
actually ran with — resolution happens at trace time, which is exactly
once per compiled program.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
from pathlib import Path

DEFAULT_BLOCK = 1024
_LANES = 128
_SUBLANES = 8

# env knobs (read at CALL time, never at import)
ENV_FWD = {"block_q": "FLASH_BLOCK_Q", "block_k": "FLASH_BLOCK_K"}
ENV_BWD = {"block_q": "FLASH_BLOCK_Q_BWD", "block_k": "FLASH_BLOCK_K_BWD"}
# the ragged paged-decode kernel (ops/pallas/paged_attention.py): block_k is
# the KV-pool page size — the pool's unit and what one async copy of the
# kernel brings (how many pages it reduces at a time follows from the call's
# shapes, not from here); block_q is reserved (decode q_len == 1)
ENV_PAGED = {"block_q": "PAGED_BLOCK_Q", "block_k": "PAGED_BLOCK_K"}
ENV_TABLE = "FLASH_TUNING_TABLE"

# a page sits in the SUBLANE dimension of the paged kernel's K/V scratch
# (lanes carry head_dim), so its knobs align to 8, not 128 — and serving
# pools want small pages (16-64 tokens) anyway
_KIND_ALIGN = {"fwd": _LANES, "bwd": _LANES, "paged": _SUBLANES}
_KIND_DEFAULT = {
    "fwd": (DEFAULT_BLOCK, DEFAULT_BLOCK),
    "bwd": (DEFAULT_BLOCK, DEFAULT_BLOCK),
    "paged": (_SUBLANES, 16),
}

_REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_TABLE_PATH = _REPO_ROOT / "config" / "tuning" / "flash_blocks.json"

_table_lock = threading.Lock()
_table_cache: dict[str, dict | None] = {}


SOURCE_ORDER = ("call", "env", "table", "default")  # most specific first


@dataclasses.dataclass(frozen=True)
class BlockChoice:
    """A resolved (block_q, block_k) pair plus where it came from
    (`source` in {"call", "env", "table", "default"} — the most specific
    origin that contributed a knob). `source_q`/`source_k` carry the
    per-knob origin when the resolver produced them (None on fabricated
    choices)."""

    block_q: int
    block_k: int
    source: str
    source_q: str | None = None
    source_k: str | None = None


def dtype_tag(dtype) -> str:
    """Canonical short dtype tag for table keys (bf16/f32/f16/...)."""
    import numpy as np

    name = np.dtype(dtype).name if not isinstance(dtype, str) else str(dtype)
    return {
        "bfloat16": "bf16",
        "float32": "f32",
        "float16": "f16",
        "float64": "f64",
    }.get(name, name)


def table_key(
    kind: str,
    seq_len: int,
    head_dim: int,
    dtype,
    causal: bool,
    sliding_window: int | None,
) -> str:
    """Stable string key for one tuned shape. `sliding_window=None` -> 0."""
    return (
        f"{kind}/seq{int(seq_len)}/d{int(head_dim)}/{dtype_tag(dtype)}/"
        f"causal{int(bool(causal))}/win{int(sliding_window or 0)}"
    )


def _parse_key(key: str) -> dict | None:
    try:
        kind, seq, d, dt, causal, win = key.split("/")
        return {
            "kind": kind,
            "seq_len": int(seq.removeprefix("seq")),
            "head_dim": int(d.removeprefix("d")),
            "dtype": dt,
            "causal": causal == "causal1",
            "win": int(win.removeprefix("win")),
        }
    except (ValueError, AttributeError):
        return None


def table_path() -> Path:
    """Active tuning-table path (env override, else the committed table)."""
    return Path(os.environ.get(ENV_TABLE) or DEFAULT_TABLE_PATH)


def load_table(path: str | Path | None = None) -> dict | None:
    """Load (and cache) the tuning table; None when absent/unreadable — a
    missing table must never fail a training run, it only loses tuning."""
    p = Path(path) if path is not None else table_path()
    key = str(p)
    with _table_lock:
        if key in _table_cache:
            return _table_cache[key]
    try:
        table = json.loads(p.read_text())
        if not isinstance(table.get("entries"), dict):
            table = None
    except (OSError, json.JSONDecodeError, AttributeError):
        table = None
    with _table_lock:
        _table_cache[key] = table
    return table


def clear_table_cache() -> None:
    """Drop cached tables (tests and the sweep rewrite the file in place)."""
    with _table_lock:
        _table_cache.clear()


def _entry_blocks(entry, align: int = _LANES) -> tuple[int, int] | None:
    """Blocks from one table entry, or None when the entry is malformed
    (not a dict, missing/non-int blocks, or not aligned to `align` — the
    lane width for the flash kinds, the sublane width for paged). A bad
    entry must degrade exactly like a corrupt table — skipped, never a
    trace-time crash in a training run (env/call-sourced values raising IS
    correct: those are deliberate per-run intent, this file is ambient
    state)."""
    try:
        bq, bk = int(entry["block_q"]), int(entry["block_k"])
    except (KeyError, TypeError, ValueError):
        return None
    if bq < align or bq % align or bk < align or bk % align:
        return None
    return bq, bk


def _entry_applies(entry: dict) -> bool:
    """cpu-interpret sweep entries are plumbing placeholders — interpreter
    wall-clock says nothing about Mosaic tiles, so they must never drive a
    compiled TPU run (and hardware entries must not drive interpret-mode
    block choice either). Entries without a backend tag apply anywhere."""
    backend = entry.get("backend")
    if not backend:
        return True
    import jax  # deferred: table lookups only happen on kernel call paths

    on_tpu = jax.default_backend() == "tpu"
    is_interpret = "interpret" in str(backend)
    return is_interpret != on_tpu


def _table_lookup(
    kind: str,
    seq_len: int,
    head_dim: int,
    dtype,
    causal: bool,
    sliding_window: int | None,
) -> tuple[int, int] | None:
    table = load_table()
    if table is None:
        return None
    align = _KIND_ALIGN.get(kind, _LANES)
    entries = table["entries"]
    exact = entries.get(table_key(kind, seq_len, head_dim, dtype, causal, sliding_window))
    if exact is not None:
        blocks = _entry_blocks(exact, align)
        if blocks is not None and _entry_applies(exact):
            return blocks
    # nearest-seq fallback among entries matching every other field: ties go
    # to the SMALLER seq (its blocks certainly fit VMEM at the query shape)
    want = {
        "kind": kind,
        "head_dim": int(head_dim),
        "dtype": dtype_tag(dtype),
        "causal": bool(causal),
        "win": int(sliding_window or 0),
    }
    best = None
    for key, entry in entries.items():
        parsed = _parse_key(key)
        blocks = _entry_blocks(entry, align)
        if parsed is None or blocks is None or not _entry_applies(entry):
            continue
        if {k: parsed[k] for k in want} != want:
            continue
        rank = (abs(parsed["seq_len"] - seq_len), parsed["seq_len"])
        if best is None or rank < best[0]:
            best = (rank, blocks)
    if best is None:
        return None
    return best[1]


def _env_int(name: str) -> int | None:
    raw = os.environ.get(name)
    if not raw:
        return None
    try:
        return int(raw)
    except ValueError:
        raise ValueError(f"{name} must be an int, got {raw!r}") from None


def resolve_block_sizes(
    kind: str,
    *,
    seq_len: int,
    head_dim: int,
    dtype,
    causal: bool,
    sliding_window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
) -> BlockChoice:
    """Resolve `(block_q, block_k)` for one kernel kind at one shape.

    Priority per knob: explicit arg > env > tuning table > the kind's
    default. The reported `source` is the most specific origin that
    contributed either knob (call > env > table > default). `kind="paged"`
    resolves the ragged paged-decode kernel's knobs: block_k is the KV-pool
    page size (the unit the kernel fetches), sublane-aligned (8) instead of
    lane-aligned; block_q is reserved (decode q_len == 1).
    """
    if kind not in ("fwd", "bwd", "paged"):
        raise ValueError(f"kind must be 'fwd', 'bwd' or 'paged', got {kind!r}")
    env = {"fwd": ENV_FWD, "bwd": ENV_BWD, "paged": ENV_PAGED}[kind]

    def knob(explicit: int | None, env_name: str, fallback_env: str | None):
        if explicit is not None:
            return int(explicit), "call"
        value = _env_int(env_name)
        # bwd falls back to the shared FLASH_BLOCK_* knobs when no
        # bwd-specific override is set (the pre-tuning-layer semantics)
        if value is None and fallback_env is not None:
            value = _env_int(fallback_env)
        if value is not None:
            return value, "env"
        return None, None

    fb_q = ENV_FWD["block_q"] if kind == "bwd" else None
    fb_k = ENV_FWD["block_k"] if kind == "bwd" else None
    bq, q_src = knob(block_q, env["block_q"], fb_q)
    bk, k_src = knob(block_k, env["block_k"], fb_k)

    if q_src is None or k_src is None:
        hit = _table_lookup(kind, seq_len, head_dim, dtype, causal, sliding_window)
        default_q, default_k = _KIND_DEFAULT[kind]
        if q_src is None:
            bq, q_src = (hit[0], "table") if hit else (default_q, "default")
        if k_src is None:
            bk, k_src = (hit[1], "table") if hit else (default_k, "default")

    align = _KIND_ALIGN[kind]
    for name, value in (("block_q", bq), ("block_k", bk)):
        if value < align or value % align:
            raise ValueError(
                f"{kind} {name} must be a positive multiple of {align}, got {value}"
            )
    source = min((q_src, k_src), key=SOURCE_ORDER.index)
    return BlockChoice(
        block_q=bq, block_k=bk, source=source, source_q=q_src, source_k=k_src
    )


def bwd_env_override(knob: str) -> int | None:
    """The bwd-SPECIFIC env knob (`FLASH_BLOCK_{Q,K}_BWD`), WITHOUT the
    shared `FLASH_BLOCK_*` fallback — for callers that interleave
    explicit-fwd-tile inheritance between the bwd-specific env and the
    shared resolution chain (see `flash_attention`)."""
    return _env_int(ENV_BWD[knob])


def fit_block(requested: int, length: int) -> int:
    """Largest lane-multiple block <= `requested` that divides `length`
    (itself assumed lane-aligned). The flat kernels require exact
    divisibility; 128 always divides a lane-aligned length, so this never
    fails — a tuned/override block that doesn't divide a padded sequence
    degrades to the nearest dividing tile instead of crashing the trace."""
    if length % _LANES:
        raise ValueError(f"length {length} is not a multiple of {_LANES}")
    block = min(int(requested), length)
    block -= block % _LANES
    while length % block:
        block -= _LANES
    return block


def record_block_choice(kind: str, choice: BlockChoice) -> None:
    """Publish the resolved blocks into the active telemetry registry so
    telemetry.jsonl records what each compiled step actually ran with."""
    try:
        from llm_training_tpu.telemetry import get_registry
    except ImportError:  # telemetry is optional for standalone kernel use
        return
    registry = get_registry()
    registry.gauge(f"flash/{kind}/block_q").set(choice.block_q)
    registry.gauge(f"flash/{kind}/block_k").set(choice.block_k)
    registry.counter(f"flash/tuning_table_hit/{choice.source}").inc()


def resolve_paged_block_size(
    *,
    max_model_len: int,
    head_dim: int,
    dtype,
    block_size: int | None = None,
) -> BlockChoice:
    """Resolve the serving pool's KV block (page) size — the unit the
    paged-decode kernel fetches (`block_k` of the "paged" kind): explicit config >
    PAGED_BLOCK_K env > tuning table > 16. Recorded into telemetry like
    every other kernel tile resolution."""
    choice = resolve_block_sizes(
        "paged", seq_len=max_model_len, head_dim=head_dim, dtype=dtype,
        causal=True, block_k=block_size,
    )
    record_block_choice("paged", choice)
    return choice


# ---------------------------------------------------------------------------
# the one-token delta-rule kernel (ops/pallas/delta_step.py)

# stored heads a grid step, by the slab's stored shape (P, dk, n * dv). Tried
# on a v5e (32 rows, ms a layer and the state's GB/s in and out; PR 46's
# kernel, a block unrolled, and PR 47's, a loop of trips, read alike). KDA
# `[64, 128, 128]`, 64 KB a stored head: 1 head a step 1.200 ms (224 GB/s), 2
# 0.786, 4 0.566, 8 0.474, 16 0.450 (597), 32 0.450; 64 (4 MB, 16 MB
# double-buffered both ways) is refused: out of VMEM. The gated rule `[15,
# 96, 384]`, 147 KB a stored pair: 1 pair 0.360 ms (394 GB/s), 3 0.264, 5
# 0.243 (582), 15 0.241. The XLA steps: 0.831 and 0.336.
DELTA_STEP_HEADS = {(64, 128, 128): 16, (15, 96, 384): 5}
# any other shape: the state bytes a step moves each way, under which the
# step's fixed cost shows and over which nothing was gained above
_DELTA_STEP_BYTES = 1024 * 1024


def delta_step_heads(stored_heads: int, key_dim: int, lanes: int, turned: int) -> int:
    """Stored heads one grid step of `delta_step` takes: the table's, else
    the most that divide `stored_heads`, keep the step's states within
    `_DELTA_STEP_BYTES` and the `turned` key-axis vectors a stored head within
    the 128 lanes they are turned onto (at least one)."""
    listed = DELTA_STEP_HEADS.get((stored_heads, key_dim, lanes))
    if listed is not None:
        return listed
    fit = max(1, min(_DELTA_STEP_BYTES // (key_dim * lanes * 4), _LANES // turned))
    return max(d for d in range(1, fit + 1) if stored_heads % d == 0)
