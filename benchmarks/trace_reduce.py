"""From a profiler trace to numbers: device busy/idle union, device time per
program and per kernel, collective time that hides no compute, and the
longest idle gaps named by what the host was running.

`load(path)` turns an `.xplane.pb` into a plain dict (so a small recorded
trace can be a JSON fixture):

    {"devices": {"0": {"ops": [[name, start_ns, dur_ns], ...],
                        "programs": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}          # the busiest host thread

Device planes are `/device:TPU:<n>`; their `XLA Ops` line holds one event per
executed HLO operation, named by the instruction's whole text
(`%fusion.3 = bf16[512,5120]{...} fusion(...)`), the `XLA Modules` line one
event per executed program (`jit_decode_step(<fingerprint>)`). `load` keeps
an op's own name and result type (`fusion.3 bf16[512,5120]`): its operands
name other ops, and would match a search for a kernel that only feeds it. A
Pallas kernel's own name is its `name=`. A `while` holds its body's ops as
events of the same line, so sums leave containers out and busy time is a
union. Asynchronous copies (`Async XLA Ops`) are not device work here.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
INSTRUCTION = re.compile(r"^%(?P<own>[^\s=]+) = (?P<type>\(?[a-z0-9]+\[[0-9,]*\])?")
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$| )")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|collective-broadcast"
)
NS = 1e-9
HOST_LOOKBACK = 4096  # host events searched back from a gap for the frame that covers it


def load(path) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    host_lines = []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            device = {"ops": [], "programs": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", PROGRAMS_LINE: "programs"}.get(line.name)
                if key is None:
                    continue
                rename = own_name if key == "ops" else str
                device[key] = [
                    [rename(e.name), float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                ]
            out["devices"][match.group(1)] = device
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                events = [
                    [e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events
                ]
                host_lines.append(events)
    if host_lines:
        out["host"] = max(host_lines, key=len)
    return out


def own_name(instruction: str) -> str:
    """`%copy.7 = bf16[13,3073,10,16,128]{...} copy(%x)` -> `copy.7 bf16[13,3073,10,16,128]`."""
    match = INSTRUCTION.match(instruction)
    if not match:
        return instruction[:80]
    result = (match.group("type") or "").lstrip("(")
    return f"{match.group('own')} {result}".strip()


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def window_ns(trace: dict) -> tuple[float, float]:
    """The traced window on the devices' clock: first op start to last op end."""
    starts, ends = [], []
    for device in trace["devices"].values():
        for _, start, dur in device["ops"]:
            starts.append(start)
            ends.append(start + dur)
    if not starts:
        raise ValueError("no operation ran on a device in this trace")
    return min(starts), max(ends)


def busy_s(trace: dict) -> dict[str, float]:
    return {d: union_ns(v["ops"]) * NS for d, v in trace["devices"].items()}


def idle_pct(trace: dict, worst: bool = True) -> float:
    lo, hi = window_ns(trace)
    shares = [100.0 * (1.0 - b / ((hi - lo) * NS)) for b in busy_s(trace).values()]
    return max(shares) if worst else sum(shares) / len(shares)


def time_by_name(events, pattern: str) -> tuple[float, int]:
    """(seconds, calls) of the events whose own name matches `pattern`."""
    rx = re.compile(pattern)
    hits = [e for e in events if rx.search(e[0].split(" ")[0])]
    return sum(e[2] for e in hits) * NS, len(hits)


def ops_inside(trace: dict, device: str, program_pattern: str):
    """The ops of `device` that ran inside executions of matching programs."""
    rx = re.compile(program_pattern)
    spans = sorted(
        (s, s + d) for n, s, d in trace["devices"][device]["programs"] if rx.search(n)
    )
    out, i = [], 0
    for event in sorted(trace["devices"][device]["ops"], key=lambda e: e[1]):
        while i < len(spans) and spans[i][1] <= event[1]:
            i += 1
        if i < len(spans) and spans[i][0] <= event[1] < spans[i][1]:
            out.append(event)
    return out, len(spans)


def program_device_ms(trace: dict, program_pattern: str, device: str = "0"):
    """Mean device-busy milliseconds of one execution of a matching program."""
    ops, calls = ops_inside(trace, device, program_pattern)
    if not calls:
        return None
    return union_ns(ops) * NS * 1e3 / calls


def exposed_collective_s(trace: dict, device: str = "0") -> float:
    """Seconds in which a collective occupied the device's op line: the ops
    of a line run one after another, so while it runs nothing computes."""
    ops = trace["devices"][device]["ops"]
    return union_ns([e for e in ops if COLLECTIVE.search(e[0].split(" ")[0])]) * NS


def top_ops(trace: dict, device: str = "0", n: int = 10):
    totals = defaultdict(float)
    for name, _, dur in trace["devices"][device]["ops"]:
        if CONTAINER.match(name):
            continue
        own, _, result = name.partition(" ")
        totals[f"{re.sub(r'[.0-9]+$', '', own)} {result}".strip()] += dur * NS
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, device: str = "0", n: int = 10, min_gap_ns: float = 2e4):
    """Idle gaps of the device, summed under the innermost host event that
    was running at each gap's middle."""
    ops = sorted(trace["devices"][device]["ops"], key=lambda e: e[1])
    host = sorted(trace["host"], key=lambda e: e[1])
    totals = defaultdict(float)
    end = None
    gaps = []
    for _, start, dur in ops:
        if end is not None and start - end >= min_gap_ns:
            gaps.append((end, start))
        end = max(end or 0.0, start + dur)
    starts = [e[1] for e in host]
    for lo, hi in gaps:
        mid = 0.5 * (lo + hi)
        name = "host_untraced"
        # the innermost event is the latest-started one still running at mid
        last = bisect.bisect_right(starts, mid) - 1
        inner = None
        for index in range(last, max(last - HOST_LOOKBACK, -1), -1):
            if host[index][1] + host[index][2] < mid:
                continue
            if inner is None:
                inner = name = host[index][0]
            elif ".py:" in host[index][0] and host[index][0] != inner:
                name = f"{host[index][0]} > {inner}"  # the nearest Python frame around it
                break
        totals[name] += (hi - lo) * NS
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: dict, device: str = "0") -> dict:
    return {"device_ops": top_ops(trace, device), "idle_gaps": idle_gaps(trace, device)}
