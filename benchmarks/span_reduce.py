"""What the program says of itself, read from the same profiler trace that
`trace_reduce` reads: its own spans on the device trace's clock, and the
block each device operation belongs to.

`load(path)` turns an `.xplane.pb` into a plain dict (so a small recorded
trace can be a JSON fixture), once a process:

    {"spans": [{"name": "serve/engine_step", "thread": "python3",
                "start": ns, "dur": ns, "args": {"step": 3, ...}}, ...],
     "devices": {"0": {"ops": [[name, start_ns, dur_ns, scope], ...],
                        "programs": [[name, start_ns, dur_ns], ...]}}}

Spans are the host events whose name starts with `llmt/` (every host
thread; the prefix is dropped): the program opens them through
`TraceRecorder.measure` and an installed annotator doubles each as a
`jax.profiler.TraceAnnotation` (llm_training_tpu/telemetry/trace.py). An
op's `scope` is the HLO `op_name` the device plane keeps in the stat
`SCOPE_STAT` of the op's metadata: the path of `jax.named_scope`s and flax
module names the op was traced under (`jit(decode_step)/.../layers/layer/self_attn/...`), transforms
included (`transpose(jvp(...))`), so forward, backward and recomputation of
one block all hold its name. Containers (`while`, `call`) are left out of
sums, as in `trace_reduce`.

A trace with no `llmt/` span at all comes from a program older than the
annotator (the driver traces a PR's parent with the PR's benchmark laid over
it, and `run.py` ends a run on a reader that returns nothing). Every reader
of what came with the annotator answers that one way, `older_program(trace)`:
`NOT_A_READING`, logged. A span or scope gone from a program that does
annotate is `None`, which ends the traced run.
"""

from __future__ import annotations

import bisect
import importlib.util
import re
from collections import defaultdict
from pathlib import Path

from benchmarks import common, trace_reduce
from benchmarks.trace_reduce import CONTAINER, DEVICE_PLANE, NS, OPS_LINE, PROGRAMS_LINE

PREFIX = "llmt/"
SCOPE_STAT = "tf_op"
ATTN, MLP = "/self_attn/", "/mlp/"
# the grouped matmul of the sparse MLP: the chip's compiler rebuilds
# `lax.ragged_dot` as custom calls that keep no scope (seen on the chip, PR
# 25: `tf_op` reads `ragged-dot-none:`), so these are known by their own name
GROUPED_MATMUL = re.compile(r"^ragged-dot")
MOE_DISPATCH = ("moe_route", "moe_sort", "moe_gather", "moe_scatter")
MOE_SCOPES = MOE_DISPATCH + ("moe_experts",)
FETCHES = ("serve/prefill_fetch", "serve/decode_fetch")
SAMPLE = "/sample/"
# what a reader of this PR's spans and scopes gives for a program that has
# none of them: below every reading a metric can take, so never one
NOT_A_READING = -1.0

_LOADED: dict[str, dict] = {}


def load(path) -> dict:
    key = str(path)
    if key not in _LOADED:
        _LOADED[key] = _read(key)
    return _LOADED[key]


def for_cell(cell) -> dict:
    """The trace of the cell's traced run (where the runners put it)."""
    return load(common.newest_xplane(cell.root / ".bench_trace" / cell.name))


def _xplane_pb2():
    """The generated messages of tsl/profiler/protobuf/xplane.proto. This
    installation ships them inside tensorflow and the file needs only
    google.protobuf, so it is loaded by its path: by its dotted name it would
    bring the whole of tensorflow (10 s) into a process that holds the chip."""
    package = importlib.util.find_spec("tensorflow")  # finds it, imports nothing
    path = Path(package.submodule_search_locations[0]) / "tsl/profiler/protobuf/xplane_pb2.py"
    spec = importlib.util.spec_from_file_location("benchmarks._xplane_pb2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _stat_value(stat, stat_names: dict):
    kind = stat.WhichOneof("value")
    # a string, or a reference to the stat metadata whose name holds it
    return stat_names.get(stat.ref_value, "") if kind == "ref_value" else getattr(stat, kind)


def _read(path: str) -> dict:
    """`ProfileData` (which `trace_reduce` reads) shows an event's own stats
    only, and the device plane keeps an op's scope with the op's METADATA
    (one record per distinct instruction): so the file is parsed whole, as the
    XSpace message it is."""
    space = _xplane_pb2().XSpace()
    with open(path, "rb") as handle:
        space.ParseFromString(handle.read())
    out = {"spans": [], "devices": {}}
    for plane in space.planes:
        stat_names = {key: meta.name for key, meta in plane.stat_metadata.items()}
        names = {key: meta.name for key, meta in plane.event_metadata.items()}
        device = DEVICE_PLANE.match(plane.name)
        if device:
            scopes = {
                key: next((
                    _stat_value(stat, stat_names) for stat in meta.stats
                    if stat_names.get(stat.metadata_id) == SCOPE_STAT
                ), "").rstrip(":")
                for key, meta in plane.event_metadata.items()
            }
            ops, programs = [], []
            for line in plane.lines:
                for e in line.events:
                    start = line.timestamp_ns + e.offset_ps / 1e3
                    if line.name == OPS_LINE:
                        text = names[e.metadata_id]
                        ops.append([trace_reduce.own_name(text), start, e.duration_ps / 1e3, scopes[e.metadata_id]])
                    elif line.name == PROGRAMS_LINE:
                        programs.append([names[e.metadata_id], start, e.duration_ps / 1e3])
            out["devices"][device.group(1)] = {"ops": ops, "programs": programs}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    name = names[e.metadata_id]
                    if name.startswith(PREFIX):
                        out["spans"].append({
                            "name": name[len(PREFIX):], "thread": line.name,
                            "start": line.timestamp_ns + e.offset_ps / 1e3, "dur": e.duration_ps / 1e3,
                            "args": {stat_names[s.metadata_id]: _stat_value(s, stat_names) for s in e.stats},
                        })
    out["spans"].sort(key=lambda s: (s["start"], -s["dur"]))
    return out


# ------------------------------------------------------------------ spans


def older_program(trace: dict) -> float | None:
    """`NOT_A_READING` for a trace with no `llmt/` span at all (a program older
    than the annotator and the scopes that came with it), None otherwise."""
    if trace["spans"]:
        return None
    common.log(f"no llmt/ span in this trace: a program older than the annotator, {NOT_A_READING} is not a reading")
    return NOT_A_READING


def spans_named(trace: dict, name: str) -> list[dict]:
    return [s for s in trace["spans"] if s["name"] == name]


def inside(trace: dict, span: dict) -> list[dict]:
    """The spans of the same thread that lie within `span`, itself left out."""
    end = span["start"] + span["dur"]
    return [
        s for s in trace["spans"]
        if s is not span and s["thread"] == span["thread"]
        and s["start"] >= span["start"] and s["start"] + s["dur"] <= end
    ]


def _union_ns(intervals) -> float:
    return trace_reduce.union_ns([(None, s["start"], s["dur"]) for s in intervals])


def self_ns(trace: dict, span: dict) -> float:
    """The span's duration less what the spans inside it cover."""
    return span["dur"] - _union_ns(inside(trace, span))


def less_ns(trace: dict, span: dict, names) -> float:
    """The span's duration less its inner spans of the given names."""
    return span["dur"] - _union_ns([s for s in inside(trace, span) if s["name"] in names])


def step_counts(trace: dict) -> dict:
    """The engine's own per-step counts, summed over the traced steps: the
    closing args of `serve/engine_step` (llm_training_tpu/serve/engine.py)."""
    steps = [s["args"] for s in spans_named(trace, "serve/engine_step")]
    decode = [a for a in steps if a.get("decode_rows")]
    return {
        "steps": len(steps),
        "prefill_steps": sum(1 for a in steps if a.get("prefill_chunks")),
        "prefill_tokens": sum(int(a.get("prefill_tokens", 0)) for a in steps),
        "decode_steps": len(decode),
        "decode_rows": sum(int(a["decode_rows"]) for a in decode),
        "live_tokens": sum(int(a["live_tokens"]) for a in decode),
    }


def longest_steps(trace: dict, n: int = 3) -> list[dict]:
    """The longest engine steps with the seconds of each span inside them."""
    steps = sorted(spans_named(trace, "serve/engine_step"), key=lambda s: -s["dur"])[:n]
    out = []
    for step in steps:
        parts = defaultdict(float)
        for child in inside(trace, step):
            parts[child["name"]] += child["dur"] * NS
        out.append({
            "step": step["args"].get("step"), "seconds": step["dur"] * NS,
            "inside": {k: round(v, 6) for k, v in sorted(parts.items(), key=lambda kv: -kv[1])},
        })
    return out


# ------------------------------------------------------------------- idle


def idle_gaps(trace: dict, device: str = "0") -> list[tuple[float, float]]:
    """Every interval between the first and the last op of the device in
    which no op runs: together they are the window less the busy union."""
    gaps, end = [], None
    for _, start, dur, _ in sorted(trace["devices"][device]["ops"], key=lambda e: e[1]):
        if end is not None and start > end:
            gaps.append((end, start))
        end = max(end or 0.0, start + dur)
    return gaps


def innermost_segments(spans: list[dict]) -> list[tuple[float, float, str]]:
    """(lo, hi, name) pieces tiling the time in which any of `spans` is open,
    each under the latest-started (of those, the shortest) span open through
    it: the innermost one, where spans nest."""
    cuts = sorted({s["start"] for s in spans} | {s["start"] + s["dur"] for s in spans})
    pieces = []
    for lo, hi in zip(cuts, cuts[1:]):
        open_ = [s for s in spans if s["start"] <= lo and s["start"] + s["dur"] >= hi]
        if open_:
            pieces.append((lo, hi, max(open_, key=lambda s: (s["start"], -s["dur"]))["name"]))
    return pieces


def idle_by_span(trace: dict, device: str = "0", prefix: str = "") -> dict[str, float]:
    """Idle seconds of the device by what the program was doing meanwhile:
    each gap is cut along the innermost program spans (of names starting with
    `prefix`) open through it, and what no span covers goes under `outside`.
    The rows sum to the idle time."""
    pieces = innermost_segments([s for s in trace["spans"] if s["name"].startswith(prefix)])
    ends = [hi for _, hi, _ in pieces]
    totals: dict[str, float] = defaultdict(float)
    for lo, hi in idle_gaps(trace, device):
        covered = 0.0
        for piece_lo, piece_hi, name in pieces[bisect.bisect_right(ends, lo):]:
            if piece_lo >= hi:
                break
            shared = min(hi, piece_hi) - max(lo, piece_lo)
            totals[name] += shared * NS
            covered += shared
        totals["outside"] += (hi - lo - covered) * NS
    return dict(sorted(((k, v) for k, v in totals.items() if v), key=lambda kv: -kv[1]))


# ----------------------------------------------------------------- scopes


def scoped_ops(trace: dict, device: str = "0", program: str | None = None):
    """(ops that are not containers, executions): of the whole device line,
    or of what ran inside executions of programs matching `program`."""
    ops, calls = trace["devices"][device]["ops"], 0
    if program is not None:
        ops, calls = trace_reduce.ops_inside(trace, device, program)
    return [e for e in ops if not CONTAINER.match(e[0])], calls


def seconds_under(ops, *needles: str) -> float:
    """Device seconds of the ops whose scope holds any of `needles`; with
    `MLP` or `moe_experts` among them, the scopeless grouped matmuls too."""
    grouped = MLP in needles or "moe_experts" in needles
    return NS * sum(
        e[2] for e in ops
        if any(n in e[3] for n in needles) or (grouped and GROUPED_MATMUL.match(e[0]))
    )


def has_scopes(ops) -> bool:
    return any(e[3] for e in ops)


def decode_split_ms(trace: dict) -> dict | None:
    """Device milliseconds a `decode_step` execution spends under the
    attention block, under the MLP block, and under neither; of the last,
    what lies under `sample`."""
    ops, calls = scoped_ops(trace, program=r"decode_step")
    if not calls or not has_scopes(ops):
        return None
    attn = seconds_under(ops, ATTN)
    mlp = seconds_under([e for e in ops if ATTN not in e[3]], MLP)
    total = sum(e[2] for e in ops) * NS
    per_call = 1e3 / calls
    return {
        "attn": attn * per_call, "mlp": mlp * per_call,
        "rest": (total - attn - mlp) * per_call,
        "sample": seconds_under(ops, SAMPLE) * per_call, "calls": calls,
    }


def train_share_pct(trace: dict, needle: str, device: str = "0"):
    """Share of the device's busy time spent in ops whose scope holds
    `needle`; None where no op holds it."""
    ops, _ = scoped_ops(trace, device)
    under = seconds_under(ops, needle)
    if not under:
        return None
    busy = trace_reduce.union_ns([e[:3] for e in ops]) * NS
    common.log(f"device {device}: {under:.4f} s under {needle!r} of {busy:.4f} s busy")
    return 100.0 * under / busy
