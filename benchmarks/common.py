"""What every runner shares: finding a cell's files by name, the device
check, the compile cache and compile counter, seeded weights, the profiler
window and the result line. Nothing here knows a cell, a configuration, a
traffic mix or a metric by name."""

from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
import zlib
from contextlib import contextmanager
from pathlib import Path

T_PROCESS_START = time.perf_counter()


def log(*parts) -> None:
    """A line of standard output before the result line, flushed so that a
    cut run still shows it."""
    print(*parts, flush=True)


# ------------------------------------------------------------------ lookup


class Cell:
    """One entry of `workloads`, with its configuration, traffic mix and the
    metrics that list it, all found by name under the benchmark's directory."""

    def __init__(self, root: Path, workload: str):
        self.root = Path(root)
        bench_file = self.root / "BENCHMARK.json"
        if not bench_file.is_file():
            raise SystemExit(f"no BENCHMARK.json in {self.root}")
        self.bench = json.loads(bench_file.read_text())
        self.dir = self.root / self.bench["paths"][0]
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = json.loads((self.root / self.config_entry["file"]).read_text())
        self.traffic = json.loads(
            self.find("traffic", self.workload["traffic"], ".json").read_text()
        )
        self.peaks_table = json.loads((self.dir / "peaks.json").read_text())

    def find(self, kind: str, name: str, suffix: str) -> Path:
        path = self.dir / kind / f"{name}{suffix}"
        if not path.is_file():
            raise SystemExit(f"no {kind}/{name}{suffix} under {self.dir}")
        return path

    def module(self, kind: str, name: str):
        return load_module(self.find(kind, name, ".py"))

    def metrics(self, group: str) -> list[dict]:
        """The metrics of `group` ('end_to_end' / 'per_layer') this cell reports:
        those that list it, and of those that list no cell the end-to-end ones,
        and the per-layer ones whose `moves` this cell reports."""
        mine = lambda m: self.name in m["workloads"] if "workloads" in m else True
        end_to_end = [m for m in self.bench["end_to_end"] if mine(m)]
        if group == "end_to_end":
            return end_to_end
        moved = {m["name"] for m in end_to_end}
        return [
            m for m in self.bench[group]
            if mine(m) and ("workloads" in m or m["moves"] in moved)
        ]

    def peaks(self, device_kind: str) -> dict:
        if device_kind not in self.peaks_table:
            raise SystemExit(
                f"device kind {device_kind!r} is not in peaks.json: no peak, no share"
            )
        return self.peaks_table[device_kind]


def load_module(path: Path):
    """Import a benchmark file by path (names with '-' or '.' are fine)."""
    tag = "bench_" + "".join(c if c.isalnum() else "_" for c in str(path))
    if tag in sys.modules:
        return sys.modules[tag]
    spec = importlib.util.spec_from_file_location(tag, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[tag] = module
    spec.loader.exec_module(module)
    return module


# ------------------------------------------------------------------ device


def device_record(chips: int, require_tpu: bool = True) -> dict:
    """Platform, kind and count as JAX reports them. Without a TPU, or with
    fewer chips than the cell asks for, a measuring run ends here."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < chips):
        raise SystemExit(
            f"need {chips} TPU chip(s); JAX reports {len(devices)} x {platform}"
        )
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": min(chips, len(devices)) if require_tpu else len(devices),
    }


def memory_peak_bytes(n_devices: int) -> int:
    import jax

    peak = 0
    for device in jax.devices()[:n_devices]:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# ------------------------------------------------------------ compilation


class CompileCounter:
    """Programs built and compiler seconds, through jax.monitoring. `builds`
    counts every program handed to the backend (a persistent-cache hit too:
    inside the window there may be none); `seconds` is the time that took,
    reading from the cache included; `hits` are the persistent cache's."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring

        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_):
        if event == self.BUILD:
            self.compiles += 1
            self.seconds += duration

    def _on_event(self, event: str, **_):
        if event == self.HIT:
            self.hits += 1

    def mark(self) -> tuple[int, float, int]:
        return self.compiles, self.seconds, self.hits


def configure_cache() -> str:
    """The repo's own persistent cache (inside the checkout unless
    JAX_COMPILATION_CACHE_DIR places it), with every program written: the
    small ones too, so a second run compiles nothing."""
    import jax

    from llm_training_tpu.compile_cache import configure_compile_cache

    if jax.default_backend() == "cpu":
        # a rehearsal: programs with collectives read back from the CPU's
        # cache hang, and the repo's own tests keep it off too
        return ""
    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def quiet_host() -> None:
    """Nothing of the harness or the program's telemetry runs in the window:
    trace sink off, garbage collection frozen after warm-up."""
    from llm_training_tpu.telemetry.trace import get_tracer

    get_tracer().detach_sink()
    gc.collect()
    gc.freeze()
    gc.disable()


def restore_host() -> None:
    """Undo `quiet_host` (a readings process runs several seeds)."""
    gc.enable()
    gc.unfreeze()
    gc.collect()


# ---------------------------------------------------------------- weights


def base_key(seed: int):
    """The run's key. Passed INTO the jitted initialisers as an argument, so
    that no program's text, and no cache entry, depends on the seed."""
    import jax

    return jax.random.key(seed % (2**31 - 1))


def seeded_leaf(key, path: str, shape, dtype, std: float):
    """The benchmark's own initialiser: norm scales (leaves named 'weight' /
    'scale') are ones, every other leaf N(0, std) drawn in float32 from
    (key, path) and cast. The program and the reference are both handed
    these; neither makes its own."""
    import jax
    import jax.numpy as jnp

    leaf_name = path.rsplit("/", 1)[-1]
    if leaf_name in ("weight", "scale"):
        return jnp.ones(shape, dtype)
    if leaf_name == "bias":
        return jnp.zeros(shape, dtype)
    leaf_key = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(leaf_key, shape, jnp.float32) * std).astype(dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def seeded_tree(key, abstract, std: float):
    """Fill an abstract (eval_shape) tree, flax Partitioned boxes kept."""
    import flax.linen as nn
    import jax

    def fill(path, leaf):
        if isinstance(leaf, nn.Partitioned):
            inner = leaf.value
            return leaf.replace_boxed(
                seeded_leaf(key, path_str(path), inner.shape, inner.dtype, std)
            )
        return seeded_leaf(key, path_str(path), leaf.shape, leaf.dtype, std)

    return jax.tree_util.tree_map_with_path(
        fill, abstract, is_leaf=lambda x: isinstance(x, nn.Partitioned)
    )


# ------------------------------------------------------------------ model


def build_model(config: dict, extra_kwargs: dict | None = None):
    """The program's module for a configuration file: the published keys its
    config class knows, then the file's `program.model_kwargs`."""
    from llm_training_tpu.lms.base import ModelProvider

    program = config["program"]
    provider = ModelProvider(model_class=program["model_class"])
    _, config_cls = provider._resolve()
    kwargs = {
        k: v for k, v in config.items()
        if k in config_cls.model_fields and not isinstance(v, dict)
    }
    kwargs.update(program.get("model_kwargs", {}))
    kwargs.update(extra_kwargs or {})
    provider = ModelProvider(model_class=program["model_class"], model_kwargs=kwargs)
    return provider.get_model()


# ---------------------------------------------------------------- numbers


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (serve_loadgen.py's, copied)."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    k = (len(ordered) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (k - lo)


# ----------------------------------------------------------------- tracing


@contextmanager
def profiled(trace_dir: Path | None):
    """The profiler runs only in a --trace 1 run."""
    if trace_dir is None:
        yield
        return
    import shutil

    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def newest_xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise SystemExit(f"the profiler wrote no trace under {trace_dir}")
    return found[-1]


def result_line(correct, attempted, failed, metrics, device, breakdown=None) -> str:
    out = {
        "correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
        "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    return json.dumps(out)
