"""Where a train step's device time goes, by block and by pass, and what a
serving program spends in its norms: read from the scope each device op was
traced under, as `span_reduce` reads it, with nothing left over.

`load(path)` parses the `.xplane.pb` as `span_reduce._read` does and keeps,
an op, two things more from the instruction's own record: its WHOLE result
type (every element of a tuple: `trace_reduce.own_name` keeps the first, so a
multi-output fusion whose first output is a norm's statistic and whose work
is a matmul reads `fusion f32[4,4096]`), the compiler's category for it
(`convolution fusion`, `loop fusion`, ...) and the operations the compiler
counts for one execution of it (its cost analysis: executed, not required):

    {"devices": {"0": {"ops": [[name, start_ns, dur_ns, scope, result, category, flops], ...],
                        "programs": [[name, start_ns, dur_ns], ...]}}}

`table` sorts every op that is not a container, inside device 0's
executions of the train step, into ONE bucket, innermost first (`BUCKETS`: a collective by its op name, then the scopes
`loss_ce`, `rms_norm`, `/self_attn/`, `/mlp/`, `optimizer`, `embed_tokens`,
then `none`) and, independently, into one pass by jax's own markers in the
name stack: `rematted_computation` (the forward run again inside the
backward under `jax.checkpoint`), else `transpose(jvp(` (backward), else
forward. A fusion carries its root's scope: what the compiler fused across a
boundary lands whole on the root's side.

`rms_norm` (llm_training_tpu/ops/rms_norm.py) and `optimizer`
(trainer/trainer.py) are scopes this module's readers brought with them. A
trace in which NO op holds either comes from a program older than they are
(the driver lays a PR's benchmark over its parent): `older_program` answers
`span_reduce.NOT_A_READING` for it, logged. One of them gone from a program
that holds the other is `None`, which ends the traced run.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmarks import common, span_reduce, trace_reduce
from benchmarks.span_reduce import ATTN, GROUPED_MATMUL, MLP, NOT_A_READING, SCOPE_STAT
from benchmarks.trace_reduce import COLLECTIVE, DEVICE_PLANE, NS, OPS_LINE, PROGRAMS_LINE

TRAIN_STEP = r"train_step"
NORM, OPTIMIZER, LOSS, EMBED = "rms_norm", "optimizer", "loss_ce", "embed_tokens"
NEW_SCOPES = (NORM, OPTIMIZER)
# bucket -> the needle its scope holds; the order is the order of the search
SCOPED = {"loss_ce": LOSS, "rms_norm": NORM, "self_attn": ATTN, "mlp": MLP, "optimizer": OPTIMIZER, "embed_tokens": EMBED}
BUCKETS = ("collective", *SCOPED, "none")
REMAT, BACKWARD = "rematted_computation", "transpose(jvp("
PASSES = ("forward", "backward", "recompute")
TOP_NAMES = 8  # as many of the step's longest ops, by first result, are followed into their buckets
CATEGORY_STAT, FLOPS_STAT = "hlo_category", "flops"
_LAYOUT, _INDEX = re.compile(r"\{[^{}]*\}"), re.compile(r"/\*index=\d+\*/")

_LOADED: dict[str, dict] = {}


def load(path) -> dict:
    key = str(path)
    if key not in _LOADED:
        _LOADED[key] = _read(key)
    return _LOADED[key]


def for_cell(cell) -> dict:
    """The trace of the cell's traced run (where the runners put it)."""
    return load(common.newest_xplane(cell.root / ".bench_trace" / cell.name))


def whole_result(instruction: str) -> str:
    """`%f = (f32[4,4096]{1,0:T(4,128)S(1)}, /*index=1*/bf16[4,4096,5120]{...}) fusion(...)`
    -> `(f32[4,4096], bf16[4,4096,5120])`: the result type, every element, layouts dropped."""
    _, found, rest = instruction.partition(" = ")
    if not found:
        return ""
    rest = _INDEX.sub("", _LAYOUT.sub("", rest))
    return rest[: rest.index(")") + 1] if rest.startswith("(") and ")" in rest else rest.split(" ")[0]


def _read(path: str) -> dict:
    space = span_reduce._xplane_pb2().XSpace()
    with open(path, "rb") as handle:
        space.ParseFromString(handle.read())
    out = {"devices": {}}
    for plane in space.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device:
            continue
        stat_names = {key: meta.name for key, meta in plane.stat_metadata.items()}

        def stat(meta, name):
            return next((
                span_reduce._stat_value(s, stat_names) for s in meta.stats if stat_names.get(s.metadata_id) == name
            ), "")

        # one record per distinct instruction: [own name, scope, whole result type, category, flops]
        known = {
            key: [trace_reduce.own_name(meta.name), stat(meta, SCOPE_STAT).rstrip(":"),
                  whole_result(meta.name), stat(meta, CATEGORY_STAT), float(stat(meta, FLOPS_STAT) or 0)]
            for key, meta in plane.event_metadata.items()
        }
        ops, programs = [], []
        for line in plane.lines:
            for e in line.events:
                start = line.timestamp_ns + e.offset_ps / 1e3
                if line.name == OPS_LINE:
                    name, *rest = known[e.metadata_id]
                    ops.append([name, start, e.duration_ps / 1e3, *rest])
                elif line.name == PROGRAMS_LINE:
                    programs.append([plane.event_metadata[e.metadata_id].name, start, e.duration_ps / 1e3])
        out["devices"][device.group(1)] = {"ops": ops, "programs": programs}
    return out


# ---------------------------------------------------------------- sorting


def bucket_of(op) -> str:
    own = op[0].split(" ")[0]
    if COLLECTIVE.search(own):
        return "collective"
    for bucket, needle in SCOPED.items():
        if needle in op[3] or (bucket == "mlp" and GROUPED_MATMUL.match(own)):
            return bucket
    return "none"


def pass_of(scope: str) -> str:
    return "recompute" if REMAT in scope else "backward" if BACKWARD in scope else "forward"


def older_program(trace: dict) -> float | None:
    """`NOT_A_READING` for a trace in which no op of any device holds one of
    `NEW_SCOPES` (a program older than both), None otherwise."""
    if any(n in op[3] for d in trace["devices"].values() for op in d["ops"] for n in NEW_SCOPES):
        return None
    common.log(
        f"no op under {' or '.join(NEW_SCOPES)} in this trace: a program older than these scopes, "
        f"{NOT_A_READING} is not a reading"
    )
    return NOT_A_READING


def _first_result_name(op) -> str:
    """The op as `trace_reduce.top_ops` names it: own name less its number, first result."""
    own, _, first = op[0].partition(" ")
    return f"{re.sub(r'[.0-9]+$', '', own)} {first}".strip()


def longest_ops(ops, n: int = 3) -> list[tuple]:
    """The `n` longest of `ops` once the executions of one instruction are
    summed: (own name less its number, whole result type, category, scope,
    seconds, calls)."""
    summed = defaultdict(lambda: [0.0, 0])
    for op in ops:
        entry = summed[(_first_result_name(op).split(" ")[0], op[4], op[5], op[3])]
        entry[0] += op[2] * NS
        entry[1] += 1
    return [(*key, *value) for key, value in sorted(summed.items(), key=lambda kv: -kv[1][0])[:n]]


def table(trace: dict, device: str = "0", program: str = TRAIN_STEP) -> dict | None:
    """Of the ops inside the device's executions of `program`: seconds and
    executed operations by bucket and pass, each bucket's longest ops, and
    where each of the step's longest ops BY THE NAME `trace_reduce.top_ops`
    GIVES THEM (`fusion f32[4,4096]`: the first result alone) landed. None
    where the program did not run."""
    ops, steps = span_reduce.scoped_ops(trace, device, program)
    if not steps or not ops:
        return None
    seconds = {b: dict.fromkeys(PASSES, 0.0) for b in BUCKETS}
    flops = {b: dict.fromkeys(PASSES, 0.0) for b in BUCKETS}
    held = {b: [] for b in BUCKETS}
    landed = defaultdict(lambda: defaultdict(float))
    for op in ops:
        bucket, pass_ = bucket_of(op), pass_of(op[3])
        seconds[bucket][pass_] += op[2] * NS
        flops[bucket][pass_] += op[6]
        held[bucket].append(op)
        landed[_first_result_name(op)][(bucket, pass_)] += op[2] * NS
    return {
        "steps": steps, "seconds": seconds, "flops": flops,
        "longest": {b: longest_ops(held[b]) for b in BUCKETS},
        "landed": dict(sorted(landed.items(), key=lambda kv: -sum(kv[1].values()))[:TOP_NAMES]),
        "total_s": sum(sum(row.values()) for row in seconds.values()),
        "busy_s": trace_reduce.union_ns([e[:3] for e in ops]) * NS,
    }


def _sum(rows: dict, bucket: str | None, pass_: str | None) -> float:
    rows = rows.values() if bucket is None else [rows[bucket]]
    return sum(sum(row.values()) if pass_ is None else row[pass_] for row in rows)


def seconds_of(found: dict, bucket: str | None = None, pass_: str | None = None) -> float:
    return _sum(found["seconds"], bucket, pass_)


def flops_of(found: dict, bucket: str | None = None, pass_: str | None = None) -> float:
    """Operations the compiler counts for the ops that ran: executed ones,
    the second pass included; a Pallas kernel counts none."""
    return _sum(found["flops"], bucket, pass_)


def share_pct(found: dict, bucket: str | None = None, pass_: str | None = None) -> float:
    """Share of the step's device time in a bucket, in a pass, or in both:
    over the SUM of the table, so the buckets' shares add to 100."""
    return 100.0 * seconds_of(found, bucket, pass_) / found["total_s"]


def log_table(found: dict) -> None:
    """The bucket x pass table in seconds a step (and the compiler's count of
    executed operations beside it), each bucket's longest ops with their
    whole result type, and where the step's longest first-result names landed."""
    steps, total = found["steps"], found["total_s"]
    common.log(
        f"train step on device 0: {steps} executions, {total / steps:.4f} s of device ops a step; the table sums to "
        f"{total:.4f} s, the ops' union is {found['busy_s']:.4f} s ({100.0 * (total / found['busy_s'] - 1.0):+.4f}%)"
    )
    common.log(f"{'s a step':>14} " + " ".join(f"{p:>10}" for p in (*PASSES, "all", "% of step", "TFLOP run")))
    for bucket in (*BUCKETS, None):
        cells = [seconds_of(found, bucket, p) / steps for p in (*PASSES, None)]
        common.log(
            f"{bucket or 'all':>14} " + " ".join(f"{c:10.4f}" for c in cells)
            + f" {share_pct(found, bucket):10.3f} {flops_of(found, bucket) / steps / 1e12:10.3f}"
        )
    for bucket in BUCKETS:
        for own, result, category, scope, took, calls in found["longest"][bucket]:
            common.log(
                f"  {bucket}: {took / steps:.4f} s a step in {calls / steps:g} calls of {own} {result}"
                f" [{category or 'no category'}, {pass_of(scope)}] under {scope or '(no scope)'}"
            )
    for name, parts in found["landed"].items():
        common.log(
            f"  `{name}` {sum(parts.values()) / steps:.4f} s a step: " + ", ".join(
                f"{bucket}/{pass_} {took / steps:.4f}" for (bucket, pass_), took in sorted(parts.items(), key=lambda kv: -kv[1])
            )
        )


def train_table(cell) -> dict | None:
    """The cell's table, logged once a process (five readers share it)."""
    trace = for_cell(cell)
    if "table" not in trace:
        trace["table"] = table(trace)
        if trace["table"] is not None:
            log_table(trace["table"])
    return trace["table"]


def new_scope_share_pct(cell, bucket: str) -> float | None:
    """Share of the step under a bucket whose scope is one of `NEW_SCOPES`."""
    found = train_table(cell)
    if found is None:
        return None
    if not seconds_of(found, bucket):
        return older_program(for_cell(cell))  # not a reading, or None: the scope is gone
    return share_pct(found, bucket)


# ---------------------------------------------------------------- serving


def norm_device_ms(cell, program: str) -> float | None:
    """Device milliseconds an execution of a serving program spends under
    `rms_norm`; logs its share of the execution's device ops and its three
    longest ops."""
    trace = for_cell(cell)
    ops, calls = span_reduce.scoped_ops(trace, program=program)
    if not calls:
        return None
    mine = [op for op in ops if NORM in op[3]]
    if not mine:
        return older_program(trace)
    took = sum(op[2] for op in mine) * NS
    common.log(
        f"{program}: {1e3 * took / calls:.4f} ms an execution under {NORM} of "
        f"{1e3 * NS * sum(op[2] for op in ops) / calls:.4f} ms of device ops, {calls} executions"
    )
    for own, result, category, scope, seconds, n in longest_ops(mine):
        common.log(
            f"  {1e3 * seconds / calls:.4f} ms in {n / calls:g} calls of {own} {result}"
            f" [{category or 'no category'}] under {scope}"
        )
    return 1e3 * took / calls
