"""Device gauges: HBM occupancy, XLA cost/memory analysis, and static
compute-vs-collective attribution (docs/observability.md#device-plane).

`hbm_gauges()` reads `device.memory_stats()` (PJRT allocator stats — the
source of truth for how close a run is to the HBM cliff) across ALL local
devices: the `hbm/*` family reports the WORST device (the one that OOMs
first — a single-device read hides the skewed shard that actually dies),
plus a mean and per-device gauges when more than one device is local.
Backends without allocator stats (the CPU test mesh) fall back to host
RSS so the gauges — and the tests/smoke runs that assert on them —
always exist; the `hbm/` prefix then means "process memory", which
docs/observability.md spells out.

`HBMTimeline` turns the same sample into a bounded `hbm.jsonl` timeline
in the run dir with trace instants when any device crosses a high-water
fraction — the post-mortem record for "which device filled up, when".

`compiled_cost_gauges()` pulls XLA's own FLOPs estimate and buffer sizes
from an AOT-compiled step — the cross-check for the analytic 6N+attention
MFU model in callbacks/time_estimator.py (XLA counts what was actually
compiled, including remat recompute; the analytic model deliberately
doesn't credit recompute).

`compiled_attribution_gauges()` walks the same Compiled object's HLO text
and splits the program into compute (FLOPs) vs collective bytes per op
family (all-reduce / all-gather / reduce-scatter / collective-permute)
and per mesh axis — the static comm-fraction estimate the pjit/TPUv4
paper's scaling methodology is built on, and the compute-vs-collective
split the pipeline-bubble work needs. It is a STATIC estimate: payload =
result-shape bytes per collective instruction, with no overlap model. The
same walk counts the Mosaic (Pallas) kernels compiled into the program,
by name (`attr/kernel/<name>`).

jax is imported lazily so `llm_training_tpu report` (which imports this
package) stays usable without touching an accelerator backend.
"""

from __future__ import annotations

import json
import logging
import os
import re
import time
from pathlib import Path

logger = logging.getLogger(__name__)

_MEMORY_STAT_KEYS = (
    "bytes_in_use",
    "peak_bytes_in_use",
    "bytes_limit",
    "largest_alloc_size",
)


def _host_rss_bytes() -> tuple[float | None, float | None]:
    """(current, peak) resident set size of this process, or Nones."""
    current = peak = None
    try:
        import resource
        import sys

        # ru_maxrss is KiB on Linux but bytes on macOS
        scale = 1.0 if sys.platform == "darwin" else 1024.0
        peak = float(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * scale
    except Exception:  # pragma: no cover - non-POSIX
        pass
    try:
        import os

        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        current = float(pages * os.sysconf("SC_PAGE_SIZE"))
    except Exception:  # pragma: no cover - non-Linux
        pass
    return current, peak


def local_device_memory_stats() -> list[tuple[int, dict]]:
    """[(device_id, memory_stats)] for every local device that exposes
    allocator stats; [] when the backend has none (CPU) or jax is not
    importable/initialized."""
    try:
        import jax

        devices = jax.local_devices()
    except Exception as e:  # backend not initialized / no devices
        logger.debug("local_devices unavailable: %s", e)
        return []
    out: list[tuple[int, dict]] = []
    for device in devices:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 — per-device probe must not raise
            stats = None
        if stats:
            out.append((int(getattr(device, "id", len(out))), dict(stats)))
    return out


def _device_pressure(stats: dict) -> float:
    """How close a device is to ITS OWN cliff: bytes_in_use/bytes_limit
    when a limit exists, raw bytes_in_use otherwise (still orders devices
    on a homogeneous slice)."""
    used = float(stats.get("bytes_in_use", 0.0) or 0.0)
    limit = float(stats.get("bytes_limit", 0.0) or 0.0)
    return used / limit if limit > 0 else used


def _gauges_from_stats(per_device: list[tuple[int, dict]]) -> dict[str, float]:
    """`hbm/*` gauges from a per-device stats sample: worst device under
    the legacy flat keys (back-compatible single-device view, coherent —
    every `hbm/<key>` comes from the SAME device), plus rollups and
    per-device gauges when the host has more than one device."""
    out: dict[str, float] = {}
    if per_device:
        worst_id, worst = max(per_device, key=lambda kv: _device_pressure(kv[1]))
        for key in _MEMORY_STAT_KEYS:
            if key in worst:
                out[f"hbm/{key}"] = float(worst[key])
        out["hbm/devices"] = float(len(per_device))
        if len(per_device) > 1:
            in_use = [
                float(s.get("bytes_in_use", 0.0) or 0.0) for _, s in per_device
            ]
            out["hbm/worst_device"] = float(worst_id)
            out["hbm/mean_bytes_in_use"] = sum(in_use) / len(in_use)
            for device_id, stats in per_device:
                for key in ("bytes_in_use", "peak_bytes_in_use"):
                    if key in stats:
                        out[f"hbm/device{device_id}/{key}"] = float(stats[key])
        return out
    current, peak = _host_rss_bytes()
    if current is not None:
        out["hbm/bytes_in_use"] = current
    if peak is not None:
        out["hbm/peak_bytes_in_use"] = peak
    if out:
        out["hbm/host_fallback"] = 1.0
    return out


def hbm_gauges() -> dict[str, float]:
    """`hbm/*` gauges aggregated across all local devices (worst device
    first-class — it OOMs first), with a host-RSS fallback when the
    backend exposes no allocator stats."""
    return _gauges_from_stats(local_device_memory_stats())


class HBMTimeline:
    """Bounded per-device HBM timeline in the run dir
    (docs/observability.md#device-plane).

    Sampled from the owning loop on log steps (single-threaded by design
    — no locking): each sample publishes the `hbm/*` rollup gauges,
    appends one record to `<run_dir>/hbm.jsonl` (capped at
    `LLMT_HBM_TIMELINE_MAX` records so a week-long run cannot grow the
    file unboundedly), and emits a trace instant the first time any
    device crosses `LLMT_HBM_HIGHWATER_FRAC` of its own limit (re-armed
    when it drops back below)."""

    def __init__(
        self,
        run_dir=None,
        registry=None,
        max_records: int | None = None,
        highwater_frac: float | None = None,
        clock=time.time,
    ):
        self.path = Path(run_dir) / "hbm.jsonl" if run_dir else None
        self._registry = registry
        self._clock = clock
        if max_records is None:
            max_records = int(os.environ.get("LLMT_HBM_TIMELINE_MAX") or 2048)
        self.max_records = max(1, max_records)
        if highwater_frac is None:
            highwater_frac = float(
                os.environ.get("LLMT_HBM_HIGHWATER_FRAC") or 0.9
            )
        self.highwater_frac = highwater_frac
        self._records = 0
        self._truncated = False
        self._over: set[int] = set()  # devices currently above high water
        self._highwater_events = 0

    def sample(self, step: int) -> dict[str, float]:
        """One timeline sample; returns the `hbm/*` gauges for the log-step
        metrics merge (plus `hbm_timeline/*` meta-gauges)."""
        per_device = local_device_memory_stats()
        gauges = _gauges_from_stats(per_device)
        self._check_highwater(step, per_device)
        self._append(step, per_device, gauges)
        gauges["hbm_timeline/records"] = float(self._records)
        if self._truncated:
            gauges["hbm_timeline/truncated"] = 1.0
        if self._highwater_events:
            gauges["hbm_timeline/highwater_events"] = float(
                self._highwater_events
            )
        return gauges

    def _check_highwater(self, step, per_device) -> None:
        from llm_training_tpu.telemetry.trace import get_tracer

        for device_id, stats in per_device:
            limit = float(stats.get("bytes_limit", 0.0) or 0.0)
            if limit <= 0:
                continue
            frac = float(stats.get("bytes_in_use", 0.0) or 0.0) / limit
            if frac >= self.highwater_frac and device_id not in self._over:
                self._over.add(device_id)
                self._highwater_events += 1
                if self._registry is not None:
                    self._registry.counter("hbm_timeline/highwater_events").inc()
                get_tracer().instant(
                    "hbm", "highwater", device=device_id, step=step,
                    frac=round(frac, 4), limit_bytes=limit,
                )
                logger.warning(
                    "device %d HBM high water: %.1f%% of %.2f GiB at step %d",
                    device_id, frac * 100, limit / 2**30, step,
                )
            elif frac < self.highwater_frac:
                self._over.discard(device_id)

    def _append(self, step, per_device, gauges) -> None:
        if self.path is None:
            return
        if self._records >= self.max_records:
            if not self._truncated:
                self._truncated = True
                logger.warning(
                    "hbm timeline capped at %d records (%s); later samples "
                    "keep the gauges but stop appending", self.max_records,
                    self.path,
                )
            return
        record: dict = {"step": int(step), "t": self._clock()}
        if per_device:
            record["devices"] = [
                {
                    "id": device_id,
                    **{k: stats[k] for k in _MEMORY_STAT_KEYS if k in stats},
                }
                for device_id, stats in per_device
            ]
        else:
            # host-RSS fallback sample (CPU): still a timeline, the docs
            # caveat on what `hbm/` means there applies here too
            record["host_fallback"] = True
            for key in ("hbm/bytes_in_use", "hbm/peak_bytes_in_use"):
                if key in gauges:
                    record[key.split("/", 1)[1]] = gauges[key]
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            with open(self.path, "a") as f:
                f.write(json.dumps(record) + "\n")
            self._records += 1
        except OSError as e:
            logger.warning("hbm timeline append failed: %s", e)


def compiled_cost_gauges(compiled) -> dict[str, float]:
    """`xla/*` gauges from a `jax.stages.Compiled` train step."""
    out: dict[str, float] = {}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        for key, name in (
            ("flops", "xla/flops_per_step"),
            ("bytes accessed", "xla/bytes_accessed_per_step"),
        ):
            value = float(cost.get(key, 0.0) or 0.0)
            if value > 0:
                out[name] = value
    except Exception as e:
        logger.debug("cost_analysis unavailable: %s", e)
    try:
        mem = compiled.memory_analysis()
        for attr in (
            "temp_size_in_bytes",
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "generated_code_size_in_bytes",
        ):
            value = getattr(mem, attr, None)
            if value is not None:
                out[f"xla/{attr}"] = float(value)
    except Exception as e:
        logger.debug("memory_analysis unavailable: %s", e)
    return out


# ------------------------------------------- compiled-program attribution

# HLO collective instruction heads. `-start` async variants count once;
# their `-done` halves carry no new payload and never match (the regex
# requires `(` right after the optional `-start`).
_COLLECTIVE_KINDS = {
    "all-reduce": "all_reduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "collective-permute": "collective_permute",
}

_COLLECTIVE_RE = re.compile(
    r"=\s*(?P<shape>\([^)]*\)|\S+)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|collective-permute)"
    r"(?:-start)?\("
)

# `{dtype}[{dims}]` occurrences inside a result-shape string
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

# `replica_groups={{0,1},{2,3}}` (explicit) — first group's cardinality
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]*)\}")
# `replica_groups=[4,2]<=[8]` (iota form) — [n_groups, group_size]
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")

# a compiled Pallas kernel: `custom_call_target="tpu_custom_call"`, whose
# op_name metadata ends `<kernel name>/pallas_call` (the `name=` every
# pallas_call in ops/pallas/ carries)
_KERNEL_RE = re.compile(
    r'custom_call_target="tpu_custom_call".*?op_name="[^"]*?([^/"]+)/pallas_call'
)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1, "f8e4m3fnuz": 1,
    "f8e5m2": 1, "f8e5m2fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def _shape_bytes(shape_text: str) -> float:
    total = 0.0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:  # token/opaque/unknown: carries no payload
            continue
        count = 1
        for dim in dims.split(","):
            dim = dim.strip()
            if dim:
                count *= int(dim)
        total += width * count
    return total


def parse_hlo_collectives(hlo_text: str) -> list[dict]:
    """Every collective instruction in an HLO dump:
    `{"kind", "bytes", "group_size"}` per instruction. Pure text walk —
    unit-testable without a backend. `bytes` is the result-shape payload
    (the static transfer estimate); `group_size` is the participant count
    per replica group (None when the instruction does not say, e.g.
    collective-permute's source_target_pairs form)."""
    out: list[dict] = []
    for line in hlo_text.splitlines():
        match = _COLLECTIVE_RE.search(line)
        if match is None:
            continue
        group_size = None
        groups = _GROUPS_LIST_RE.search(line)
        if groups is not None:
            ids = [t for t in groups.group(1).replace(" ", "").split(",") if t]
            group_size = len(ids) or None
        else:
            iota = _GROUPS_IOTA_RE.search(line)
            if iota is not None:
                group_size = int(iota.group(2))
        out.append({
            "kind": _COLLECTIVE_KINDS[match.group("op")],
            "bytes": _shape_bytes(match.group("shape")),
            "group_size": group_size,
        })
    return out


def parse_hlo_kernels(hlo_text: str) -> dict[str, int]:
    """{kernel name: call sites} of the Mosaic kernels in a compiled
    program's text. This is how a run OBSERVES that its Pallas kernels are
    in the program the chip executes (chip_smoke.py asserts on the gauges),
    rather than trusting the backend-keyed dispatch that should have put
    them there; an interpreted or XLA-fallback program has none."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        match = _KERNEL_RE.search(line)
        name = match.group(1) if match else "unnamed"
        # autodiff wraps the scope: `transpose(jvp(flash_bwd_dq))`
        name = re.sub(r"^(?:\w+\()+|\)+$", "", name)
        out[name] = out.get(name, 0) + 1
    return out


def _axis_for_group(group_size, mesh_axes: dict[str, int] | None) -> str | None:
    """Attribute a collective to a mesh axis by matching its replica-group
    cardinality against the axis sizes. Ambiguous (two axes of equal size)
    or unmatched groups stay unattributed — an honest 'unknown' beats a
    coin flip — except on a mesh with exactly one non-trivial axis, where
    every collective can only belong to it."""
    if not mesh_axes:
        return None
    nontrivial = [name for name, size in mesh_axes.items() if size > 1]
    if group_size is not None:
        matches = [
            name for name, size in mesh_axes.items()
            if size == group_size and size > 1
        ]
        if len(matches) == 1:
            return matches[0]
    if len(nontrivial) == 1:
        return nontrivial[0]
    return None


def compiled_attribution_gauges(
    compiled, mesh_axes: dict[str, int] | None = None
) -> dict[str, float]:
    """`attr/*` gauges from a `jax.stages.Compiled` step: static FLOPs vs
    collective bytes, split per collective family and per mesh axis, plus
    the comm-fraction headline (`collective bytes / bytes accessed`,
    clamped to [0,1]) that report prints.

    Always publishes the full family set (zeros included) so a mesh with
    no collectives — the single-device CPU smoke — still writes a stable
    `attr/` record a trend tracker can diff against."""
    out: dict[str, float] = {}
    flops = bytes_accessed = 0.0
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0) or 0.0)
        bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
    except Exception as e:
        logger.debug("cost_analysis unavailable for attribution: %s", e)
    try:
        hlo_text = compiled.as_text()
    except Exception as e:
        logger.debug("HLO text unavailable; no attr/ gauges: %s", e)
        return out
    collectives = parse_hlo_collectives(hlo_text or "")
    by_kind = {kind: 0.0 for kind in _COLLECTIVE_KINDS.values()}
    by_axis: dict[str, float] = {}
    total = 0.0
    for coll in collectives:
        by_kind[coll["kind"]] += coll["bytes"]
        total += coll["bytes"]
        axis = _axis_for_group(coll["group_size"], mesh_axes) or "unattributed"
        by_axis[axis] = by_axis.get(axis, 0.0) + coll["bytes"]
    out["attr/flops_per_step"] = flops
    out["attr/collective_bytes_per_step"] = total
    out["attr/collective_ops"] = float(len(collectives))
    out["attr/comm_fraction"] = (
        min(1.0, total / bytes_accessed) if bytes_accessed > 0 else 0.0
    )
    for kind, value in by_kind.items():
        out[f"attr/collective/{kind}_bytes"] = value
    for name, size in (mesh_axes or {}).items():
        if size > 1:
            out[f"attr/mesh/{name}/collective_bytes"] = by_axis.get(name, 0.0)
    if by_axis.get("unattributed"):
        out["attr/mesh/unattributed/collective_bytes"] = by_axis["unattributed"]
    kernels = parse_hlo_kernels(hlo_text or "")
    out["attr/pallas_kernels"] = float(sum(kernels.values()))
    for name, count in kernels.items():
        out[f"attr/kernel/{name}"] = float(count)
    return out
