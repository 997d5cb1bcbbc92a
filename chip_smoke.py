#!/usr/bin/env python
"""The chip smoke: prove the main path still starts and runs on a TPU.

    python chip_smoke.py             one chip:  device -> kernels -> fit -> serve -> generate -> report
    python chip_smoke.py --chips 4   four chips: the sharded fit and the one-device fit it is
                                     compared with, and nothing else

It drives the program through the entry points a user calls — the CLI's
`main(["fit" | "serve" | "generate", ...])` and `python -m llm_training_tpu
report` — on `config/examples/smoke/chip-smoke.yaml`: Llama-3.1-8B at its
published widths, depth and vocabulary cut to what one 16 GB chip holds (the
cuts are printed as `reduced`). Weights are random, made from the config's
seed. It is not a benchmark: every line it prints names the device it came
from and none is a result.

One process holds a chip at a time. This parent never imports jax; each
phase is a child (`--phase`, this same file) run strictly after the one
before it has exited, and every child is killed when it outlives its time limit
or the parent is stopped.

The last line of stdout is, only when every phase passed,
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
with the device as JAX reported it. A failed phase is named and the exit
code is non-zero; without a TPU the first phase fails and no result is
printed. JAX_PLATFORMS is never set or defaulted here. Compiles are cached
where JAX_COMPILATION_CACHE_DIR says, else at <repo>/.jax_cache
(llm_training_tpu/compile_cache.py): a second call compiles (almost)
nothing, and the `cache` lines show it.

Multi-GB checkpoints go to <repo>/.chip_smoke/ (git-ignored, removed at the
end); the small records (logs, metrics, telemetry) go to
<repo>/chiprun_out/chip_smoke/, which the chip tool brings back.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
CONFIG = REPO / "config" / "examples" / "smoke" / "chip-smoke.yaml"
WORK = REPO / ".chip_smoke"
OUT = REPO / "chiprun_out" / "chip_smoke"
RUN_DIR = WORK / "smoke" / "chip-smoke"  # the config's JsonlLogger dir under run_root

# what a rehearsal off the chip patches (a scratch driver or a test sets
# these on the imported module and calls the phase functions in-process;
# the command line has no switch for any of them)
PLATFORM = "tpu"
CONFIG_OVERRIDES: list[str] = []
FLASH_SHAPE = dict(batch=1, seq=2048, q_heads=32, kv_heads=8, head_dim=128)
PAGED_SHAPE = dict(batch=4, q_heads=32, kv_heads=8, head_dim=128, max_len=512)
PAGED_CHUNK = 128  # queries a row of the chunk the prefill kernels are checked on
# LongCat-Flash's latent attention: 64 heads over rows of 512 + 64 values, stored 640 wide
LATENT_SHAPE = dict(batch=4, heads=64, latent=512, nope=128, rope=64, v=128, width=640, max_len=512)
SERVE_PROMPT_LENS = (200, 320, 480, 700, 1024, 1400, 1800, 2000)
SERVE_NEW_TOKENS = 32
SERVE_FLAGS = [
    "--max-batch", "4", "--max-model-len", "2304", "--prefill-chunk", "256",
    "--cache-dtype", "bfloat16", "--eos-token-id", "-1",
]
MESH_STEPS = 3

# the cuts of config/examples/smoke/chip-smoke.yaml against the published
# meta-llama/Llama-3.1-8B config.json; widths are never cut
REDUCED = {
    "num_hidden_layers": {"published": 32, "run": 2,
                          "why": "fp32 params + fp32 Adam state on one 16 GB chip"},
    "vocab_size": {"published": 128256, "run": 32000,
                   "why": "the published embedding + head alone outgrow the chip under fp32 Adam"},
}

# bf16 tolerances: kernel vs XLA reference as a share of the reference's
# largest magnitude; logprobs and losses absolute (values near ln 32000)
KERNEL_TOL = 3e-2
LOGPROB_TOL = 5e-2
LOSS_TOL = 5e-2

RESULT_TAG = "CHIP_SMOKE_RESULT "
PHASE_LIMIT_S = {"kernels": 300, "fit": 600, "serve": 420, "generate": 300, "mesh": 1500}


class PhaseFailed(Exception):
    """A check of the running phase did not hold."""


def say(phase: str, message: str) -> None:
    print(f"[chip_smoke:{phase}] {message}", flush=True)


def check(condition, message: str) -> None:
    if not condition:
        raise PhaseFailed(message)


# ------------------------------------------------------------------ children
# Everything below this line down to `run_phase` runs inside a `--phase`
# child: the only processes that import jax.


def device_info() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def describe(device: dict) -> str:
    return f"{device['count']} x {device['kind']} ({device['platform']})"


def check_device(phase: str, chips: int) -> dict:
    """The device phase: the platform is the one asked for or the run stops
    here, before anything else; then one matmul round trip to the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    device = device_info()
    say(phase, f"device: {describe(device)}")
    check(
        device["platform"] == PLATFORM,
        f"device: jax found platform {device['platform']!r}, this run needs "
        f"{PLATFORM!r} — no accelerator, no result",
    )
    check(
        device["count"] >= chips,
        f"device: {device['count']} device(s), this run needs {chips}",
    )
    x = jnp.full((1024, 1024), 0.5, jnp.bfloat16)
    got = np.asarray(jax.jit(lambda a: a @ a)(x)[0, :4], np.float32)
    check(
        np.allclose(got, 256.0),
        f"device: matmul round trip returned {got.tolist()}, expected 256",
    )
    say(phase, "device: bf16 matmul round trip fetched to the host: ok")
    return device


class CompileLog:
    """Counts this process's compile requests against the persistent cache
    (jax.monitoring events) — the warm/cold line of every phase."""

    def __init__(self):
        import jax

        from llm_training_tpu.compile_cache import configure_compile_cache

        self.directory = configure_compile_cache()
        self.requests = self.hits = 0
        self.compile_s = self.saved_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, seconds: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds
        elif event == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved_s += seconds

    def summary(self) -> dict:
        return {
            "dir": self.directory,
            "requests": self.requests,
            "hits": self.hits,
            "compiled": self.requests - self.hits,
            "compile_s": round(self.compile_s, 2),
            "saved_s": round(self.saved_s, 2),
        }

    def report(self, phase: str, device: dict) -> None:
        s = self.summary()
        say(
            phase,
            f"cache [{describe(device)}]: {s['requests']} compile requests, "
            f"{s['hits']} persistent-cache hits, {s['compiled']} compiled "
            f"({s['compile_s']}s in the compiler, {s['saved_s']}s saved by "
            f"hits) — dir {s['dir']}",
        )


def peak_memory(phase: str, device: dict) -> int | None:
    """Worst device's peak bytes in use, from the allocator (never RSS)."""
    from llm_training_tpu.telemetry.device import local_device_memory_stats

    peaks = [
        stats["peak_bytes_in_use"] for _, stats in local_device_memory_stats()
        if "peak_bytes_in_use" in stats
    ]
    if not peaks:
        say(phase, f"peak device memory [{describe(device)}]: not reported by this backend")
        return None
    say(
        phase,
        f"peak device memory [{describe(device)}]: {max(peaks) / 2**30:.2f} GiB "
        "(device.memory_stats)",
    )
    return max(peaks)


def relative_error(got, ref) -> float:
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / max(float(np.abs(ref).max()), 1e-6))


def kernels_in(compiled) -> dict:
    from llm_training_tpu.telemetry.device import parse_hlo_kernels

    return parse_hlo_kernels(compiled.as_text())


def phase_kernels(chips: int) -> dict:
    """The compiled Pallas kernels against their XLA references, at the
    serving/training widths: flash forward + gradients with packed segment
    ids, and the paged kernels (a decoded token, a chunk of queries) on a
    ragged batch."""
    phase = "kernels"
    log = CompileLog()
    device = check_device(phase, chips)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_training_tpu.ops.attention import dot_product_attention
    from llm_training_tpu.ops.latent_attention import paged_latent_attention
    from llm_training_tpu.ops.paged_attention import paged_cached_attention
    from llm_training_tpu.ops.pallas.flash_attention import flash_attention

    # ---- flash: two packed documents and a padded tail
    shape = FLASH_SHAPE
    b, s, d = shape["batch"], shape["seq"], shape["head_dim"]
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (b, s, shape["q_heads"], d), jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, s, shape["kv_heads"], d), jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, s, shape["kv_heads"], d), jnp.bfloat16)
    cot = jax.random.normal(keys[3], q.shape, jnp.bfloat16)
    cuts = (int(s * 0.4), int(s * 0.9))
    seg = np.zeros((b, s), np.int32)
    seg[:, : cuts[0]] = 1
    seg[:, cuts[0] : cuts[1]] = 2
    seg = jnp.asarray(seg)

    def objective(attend):
        def fn(q, k, v):
            out = attend(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32)), out

        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2), has_aux=True))

    # interpret is left to the program (compiled on a TPU): what ran is
    # read from the compiled text below, not trusted from a flag
    flash = objective(lambda q, k, v: flash_attention(q, k, v, segment_ids=seg))
    reference = objective(
        lambda q, k, v: dot_product_attention(q, k, v, segment_ids=seg, impl="xla")
    )
    found = kernels_in(flash.lower(q, k, v).compile())
    say(phase, f"flash: kernels in the compiled program [{describe(device)}]: {found}")
    check(
        PLATFORM != "tpu" or {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= set(found),
        f"flash: expected the three flash kernels in the compiled text, found {found}",
    )
    (_, out), grads = flash(q, k, v)
    (_, ref_out), ref_grads = reference(q, k, v)
    errors = {"out": relative_error(out, ref_out)}
    for name, got, ref in zip(("dq", "dk", "dv"), grads, ref_grads):
        errors[name] = relative_error(got, ref)
    say(
        phase,
        f"flash vs xla at (b{b}, s{s}, {shape['q_heads']}/{shape['kv_heads']} heads, "
        f"d{d}) bf16, segment ids, max error / max |ref| [{describe(device)}]: "
        + ", ".join(f"{n} {e:.2e}" for n, e in errors.items()),
    )
    for name, err in errors.items():
        check(
            math.isfinite(err) and err <= KERNEL_TOL,
            f"flash: {name} differs from the xla reference by {err:.3e} (> {KERNEL_TOL})",
        )

    # ---- paged decode: ragged lengths, shuffled block tables
    shape = PAGED_SHAPE
    batch, d = shape["batch"], shape["head_dim"]
    lengths = np.linspace(0, shape["max_len"] - 1, batch).astype(np.int32)
    lengths[1:] += 5  # off the page boundaries
    lengths = np.minimum(lengths, shape["max_len"] - 1)
    paged_errors = {}
    for page, dtype in ((16, jnp.bfloat16), (16, jnp.float32), (128, jnp.bfloat16)):
        pages = -(-shape["max_len"] // page)
        n_blocks = 1 + batch * pages
        keys = jax.random.split(jax.random.key(page), 6)
        pool_shape = (n_blocks, shape["kv_heads"], page, d)
        pool_k = jax.random.normal(keys[0], pool_shape, dtype)
        pool_v = jax.random.normal(keys[1], pool_shape, dtype)
        tables = jnp.asarray(
            1 + np.random.default_rng(page).permutation(batch * pages)
            .reshape(batch, pages).astype(np.int32)
        )
        # a decoded token a row (`paged_decode`), then a chunk of queries a
        # row (`paged_prefill`), started where the chunk still fits the table
        for seq, kernel in ((1, "paged_decode"), (PAGED_CHUNK, "paged_prefill")):
            starts = jnp.asarray(np.minimum(lengths, shape["max_len"] - seq))
            q = jax.random.normal(keys[2], (batch, seq, shape["q_heads"], d), jnp.bfloat16)
            k = jax.random.normal(keys[3], (batch, seq, shape["kv_heads"], d), jnp.bfloat16)
            v = jax.random.normal(keys[4], (batch, seq, shape["kv_heads"], d), jnp.bfloat16)

            def attend(impl):
                return jax.jit(
                    lambda q, k, v, pk, pv: paged_cached_attention(
                        q, k, v, (pk, pv), starts, tables, impl=impl
                    )[0]
                )

            if page == 16 and dtype == jnp.bfloat16:
                found = kernels_in(attend("auto").lower(q, k, v, pool_k, pool_v).compile())
                say(
                    phase,
                    f"paged: impl='auto' with {seq} quer{'y' if seq == 1 else 'ies'} a row "
                    f"compiles to [{describe(device)}]: {found}",
                )
                check(
                    PLATFORM != "tpu" or kernel in found,
                    f"paged: impl='auto' did not put {kernel} in the program: {found}",
                )
            got = attend("pallas")(q, k, v, pool_k, pool_v)
            ref = attend("xla")(q, k, v, pool_k, pool_v)
            tag = f"{kernel}/page{page}/{jnp.dtype(dtype).name}"
            paged_errors[tag] = relative_error(got, ref)
            check(
                math.isfinite(paged_errors[tag]) and paged_errors[tag] <= KERNEL_TOL,
                f"paged: {tag} differs from the gather reference by "
                f"{paged_errors[tag]:.3e} (> {KERNEL_TOL})",
            )
    say(
        phase,
        f"paged kernels vs gather at ({shape['q_heads']}/{shape['kv_heads']} "
        f"heads, d{d}), ragged lengths {lengths.tolist()}, max error / max |ref| "
        f"[{describe(device)}]: "
        + ", ".join(f"{n} {e:.2e}" for n, e in paged_errors.items()),
    )
    # ---- the latent (MLA) pool: a decoded token a row (`mla_decode`, the
    # absorbed form), then a chunk of queries a row (`mla_prefill`, the
    # expanded one), each against `attend_rows` in XLA on the same pool
    shape = LATENT_SHAPE
    latent, width, page = shape["latent"], shape["width"], 16
    pages = -(-shape["max_len"] // page)
    keys = jax.random.split(jax.random.key(latent), 5)
    rows = lambda key, dims: jax.random.normal(key, dims, jnp.bfloat16).at[
        ..., latent + shape["rope"]:
    ].set(0)  # a stored row is `[c_kv | k_r | zeros]`
    pool = rows(keys[0], (1 + batch * pages, 1, page, width))
    w_kvb = (
        jax.random.normal(keys[1], (latent, shape["heads"], shape["nope"] + shape["v"])) * latent**-0.5
    ).astype(jnp.bfloat16)
    tables = jnp.asarray(
        1 + np.random.default_rng(latent).permutation(batch * pages).reshape(batch, pages).astype(np.int32)
    )
    latent_errors = {}
    for seq, kernel in ((1, "mla_decode"), (PAGED_CHUNK, "mla_prefill")):
        starts = jnp.asarray(np.minimum(lengths, shape["max_len"] - seq))
        q_nope = jax.random.normal(keys[2], (batch, seq, shape["heads"], shape["nope"]), jnp.bfloat16)
        q_rope = jax.random.normal(keys[3], (batch, seq, shape["heads"], shape["rope"]), jnp.bfloat16)
        row = rows(keys[4], (batch, seq, width))

        def attend(impl):
            return jax.jit(
                lambda q_nope, q_rope, row, pool: paged_latent_attention(
                    q_nope, q_rope, row, w_kvb, pool, starts, tables,
                    scale=(shape["nope"] + shape["rope"]) ** -0.5, impl=impl,
                )[0]
            )

        found = kernels_in(attend("auto").lower(q_nope, q_rope, row, pool).compile())
        say(
            phase,
            f"latent: impl='auto' with {seq} quer{'y' if seq == 1 else 'ies'} a row "
            f"compiles to [{describe(device)}]: {found}",
        )
        check(
            PLATFORM != "tpu" or kernel in found,
            f"latent: impl='auto' did not put {kernel} in the program: {found}",
        )
        latent_errors[kernel] = relative_error(
            attend("pallas")(q_nope, q_rope, row, pool), attend("xla")(q_nope, q_rope, row, pool)
        )
        check(
            math.isfinite(latent_errors[kernel]) and latent_errors[kernel] <= KERNEL_TOL,
            f"latent: {kernel} differs from the xla reference by {latent_errors[kernel]:.3e} (> {KERNEL_TOL})",
        )
    say(
        phase,
        f"latent kernels vs xla at ({shape['heads']} heads, rows of {latent} + {shape['rope']}), "
        f"ragged lengths {lengths.tolist()}, max error / max |ref| [{describe(device)}]: "
        + ", ".join(f"{n} {e:.2e}" for n, e in latent_errors.items()),
    )
    log.report(phase, device)
    return {"device": device, "cache": log.summary(),
            "flash_errors": errors, "paged_errors": paged_errors, "latent_errors": latent_errors}


def cli(argv: list[str]) -> int:
    from llm_training_tpu.cli.main import main

    return main(argv + CONFIG_OVERRIDES + [f"run_root={WORK}"])


def read_jsonl(path: Path) -> list[dict]:
    return [json.loads(line) for line in path.read_text().splitlines() if line.strip()]


def program_warnings(call) -> tuple[int, list[str]]:
    """(call's return, the package's WARNING+ log messages while it ran) —
    tapped on the package logger, so the CLI's own root logging config is
    untouched."""
    import logging

    messages: list[str] = []

    class Tap(logging.Handler):
        def emit(self, record):
            messages.append(record.getMessage())

    tap = Tap(level=logging.WARNING)
    logger = logging.getLogger("llm_training_tpu")
    logger.addHandler(tap)
    try:
        return call(), messages
    finally:
        logger.removeHandler(tap)


def phase_fit(chips: int) -> dict:
    """`fit` through the CLI: finite losses falling from about ln(vocab), the
    AOT step compiled with the flash kernels in it, device memory from the
    allocator, run metadata naming the backend."""
    phase = "fit"
    log = CompileLog()
    device = check_device(phase, chips)

    from llm_training_tpu import native
    from llm_training_tpu.cli.config import load_config

    config = load_config(CONFIG, CONFIG_OVERRIDES)
    model = config["model"]["init_args"]["model"]["model_kwargs"]
    say(
        phase,
        "model: Llama-3.1-8B widths — hidden {hidden_size}, intermediate "
        "{intermediate_size}, {num_attention_heads}q/{num_key_value_heads}kv heads "
        "of {head_dim}, rope theta {rope_theta} ({rope}), layers "
        "{num_hidden_layers}, vocab {vocab_size}; batch {batch} x seq {seq}".format(
            rope=(model.get("rope_scaling") or {}).get("rope_type"),
            batch=config["data"]["init_args"]["batch_size"],
            seq=config["data"]["init_args"]["max_length"], **model,
        ),
    )
    say(phase, f"reduced: {json.dumps(REDUCED)}")
    say(
        phase,
        "packing library: "
        + ("native (built from native/packing.cc)" if native.lib() is not None
           else "python fallback")
        + " — loaded, not exercised by the dummy data module",
    )

    shutil.rmtree(RUN_DIR, ignore_errors=True)
    rc, warnings = program_warnings(lambda: cli(["fit", "--config", str(CONFIG)]))
    check(rc == 0, f"fit: the CLI returned {rc}")
    for message in warnings:
        say(phase, f"warning from the program: {message}")
    check(
        not any("falling back to jit recompilation" in m for m in warnings),
        "fit: the AOT step was abandoned for jit recompilation",
    )

    metrics = read_jsonl(RUN_DIR / "metrics.jsonl")
    losses = [float(r["loss"]) for r in metrics if "loss" in r]
    val = [float(r["val_loss"]) for r in metrics if "val_loss" in r]
    step_s = {r["step"]: round(1.0 / r["steps_per_sec"], 3) for r in metrics if "steps_per_sec" in r}
    telemetry = read_jsonl(RUN_DIR / "telemetry.jsonl")[-1]
    metadata = json.loads((RUN_DIR / "run_metadata.json").read_text())
    expect = math.log(model["vocab_size"])
    say(phase, f"losses [{describe(device)}]: {[round(x, 4) for x in losses]} "
               f"(ln vocab = {expect:.3f}); val_loss {val}")
    say(phase, f"seconds between synced logs, by step [{describe(device)}]: {step_s} "
               "(host clock; step 1 and the first health step include a compile, the "
               "step after the validation includes it) — a smoke observation, not a benchmark")
    check(len(losses) >= 6, f"fit: {len(losses)} logged losses, expected 6")
    check(all(math.isfinite(x) for x in losses + val), f"fit: non-finite loss in {losses} {val}")
    # random logits of variance hidden * initializer_range^2 sit that
    # variance / 2 above ln(vocab): about +0.8 at hidden 4096, std 0.02
    check(expect - 0.1 <= losses[0] <= expect + 1.2,
          f"fit: first loss {losses[0]:.3f} is not within [-0.1, +1.2] of "
          f"ln(vocab) = {expect:.3f}")
    check(losses[-1] < losses[0], f"fit: loss did not fall ({losses[0]:.4f} -> {losses[-1]:.4f})")

    kernels = {k.removeprefix("attr/kernel/"): int(v)
               for k, v in telemetry.items() if k.startswith("attr/kernel/")}
    say(phase, f"AOT train step [{describe(device)}]: compile_time_s "
               f"{telemetry.get('compile_time_s')}, kernels in the compiled text {kernels}, "
               f"xla flops/step {telemetry.get('xla/flops_per_step')}")
    check(telemetry.get("compile_time_s", 0) > 0, "fit: no compile_time_s gauge — the AOT step did not compile")
    if PLATFORM == "tpu":
        check({"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= set(kernels),
              f"fit: the compiled train step holds {kernels}, not the three flash kernels")
        check("hbm/host_fallback" not in telemetry and telemetry.get("hbm/devices"),
              "fit: hbm/* came from the RSS fallback, not device.memory_stats()")
    say(phase, f"hbm [{describe(device)}]: peak_bytes_in_use "
               f"{telemetry.get('hbm/peak_bytes_in_use', 0) / 2**30:.2f} GiB of "
               f"{telemetry.get('hbm/bytes_limit', 0) / 2**30:.2f} GiB "
               f"({'device.memory_stats' if 'hbm/host_fallback' not in telemetry else 'host RSS fallback'})")
    backend = metadata["world"]["backend"]
    check(backend == PLATFORM, f"fit: run_metadata records backend {backend!r}")
    check(any((RUN_DIR / "checkpoints").iterdir()), "fit: no checkpoint was written")
    peak = peak_memory(phase, device)
    log.report(phase, device)
    return {"device": device, "cache": log.summary(), "losses": losses,
            "val_loss": val, "step_s": step_s, "kernels": kernels,
            "compile_time_s": telemetry.get("compile_time_s"), "peak_bytes": peak}


def build_requests() -> list[dict]:
    """Seeded serve traffic: prompts of a few hundred to ~2k tokens."""
    import random

    from llm_training_tpu.cli.config import load_config

    vocab = load_config(CONFIG, CONFIG_OVERRIDES)["data"]["init_args"]["vocab_size"]
    rng = random.Random(21)
    return [
        {"id": f"req-{n}", "prompt": [rng.randint(3, vocab - 1) for _ in range(length)],
         "max_new_tokens": SERVE_NEW_TOKENS}
        for n, length in enumerate(SERVE_PROMPT_LENS)
    ]


def run_cli_captured(argv: list[str], stdin_text: str = "") -> tuple[int, list[dict], str]:
    """One CLI command in this process with stdin fed and stdout captured:
    (rc, the JSON records it printed, everything it printed)."""
    import contextlib
    import io

    buffer = io.StringIO()
    real_stdin = sys.stdin
    sys.stdin = io.StringIO(stdin_text)
    try:
        with contextlib.redirect_stdout(buffer):
            rc = cli(argv)
    finally:
        sys.stdin = real_stdin
    text = buffer.getvalue()
    records = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                pass
    return rc, records, text


def phase_serve(chips: int) -> dict:
    """`serve` through the CLI from fit's checkpoint: every request
    completes, nothing leaks, the paged kernel is in the compiled decode
    step; request 0's tokens and logprobs are kept for the generate phase."""
    phase = "serve"
    log = CompileLog()
    device = check_device(phase, chips)
    # publish the decode step's compiled-program attribution (the kernel
    # count among it) — docs/observability.md#device-plane
    os.environ["LLMT_PROFILE_ATTR_DECODE"] = "1"

    requests = build_requests()
    payload = "".join(json.dumps(r) + "\n" for r in requests)
    t0 = time.perf_counter()
    rc, records, text = run_cli_captured(
        ["serve", "--config", str(CONFIG), "--max-new-tokens", str(SERVE_NEW_TOKENS)]
        + SERVE_FLAGS, payload,
    )
    wall = time.perf_counter() - t0
    (OUT / "serve.stdout").write_text(text)
    check(rc == 0, f"serve: the CLI returned {rc}")

    done = {r["id"]: r for r in records if r.get("type") == "done"}
    errors = [r for r in records if r.get("type") == "error"]
    stats = next((r["stats"] for r in records if r.get("type") == "stats"), None)
    check(stats is not None, "serve: no final stats record")
    check(not errors, f"serve: error chunks {errors[:2]}")
    check(len(done) == len(requests), f"serve: {len(done)} of {len(requests)} requests completed")
    for request in requests:
        event = done[request["id"]]
        check(event["stop_reason"] == "max_tokens" and event["n_tokens"] == SERVE_NEW_TOKENS,
              f"serve: {request['id']} ended {event['stop_reason']} after {event['n_tokens']} tokens")
        check(all(lp is not None and math.isfinite(lp) for lp in event["logprobs"]),
              f"serve: {request['id']} has non-finite logprobs")
    check(stats["serve/requests_completed"] == len(requests),
          f"serve: engine counted {stats['serve/requests_completed']} completions")
    check(stats["decode/cache_blocks_in_use"] == 0,
          f"serve: {stats['decode/cache_blocks_in_use']} pool blocks still held at the end")
    check(stats["serve/peak_running"] >= 2,
          "serve: requests never overlapped — no admission mid-decode")

    from llm_training_tpu.telemetry import get_registry

    snapshot = get_registry().snapshot()
    kernels = {k.removeprefix("attr/decode/kernel/"): int(v)
               for k, v in snapshot.items() if k.startswith("attr/decode/kernel/")}
    say(phase, f"{len(done)}/{len(requests)} requests, prompts {list(SERVE_PROMPT_LENS)} tokens, "
               f"{SERVE_NEW_TOKENS} new tokens each, peak running {int(stats['serve/peak_running'])}, "
               f"blocks in use at the end {int(stats['decode/cache_blocks_in_use'])} "
               f"(peak {int(stats['decode/cache_peak_blocks_in_use'])} of "
               f"{int(stats['decode/cache_blocks_total'])}), KV pool "
               f"{stats['decode/cache_bytes'] / 2**20:.0f} MiB [{describe(device)}]")
    say(phase, f"compiled decode step [{describe(device)}]: kernels {kernels}; "
               f"serve wall {wall:.1f}s including restore and compiles, engine reported "
               f"ttft p50 {stats.get('serve/ttft_p50_ms', 0):.0f} ms, tpot p50 "
               f"{stats.get('serve/tpot_p50_ms', 0):.1f} ms — smoke observations, not a benchmark")
    if PLATFORM == "tpu":
        check(kernels.get("paged_decode", 0) >= 1,
              f"serve: the compiled decode step holds {kernels}, not the paged kernel")
    peak = peak_memory(phase, device)
    log.report(phase, device)
    first = requests[0]
    (WORK / "serve_first.json").write_text(json.dumps({
        "prompt": first["prompt"],
        "tokens": done[first["id"]]["tokens"],
        "logprobs": done[first["id"]]["logprobs"],
    }))
    return {"device": device, "cache": log.summary(), "completed": len(done),
            "kernels": kernels, "peak_bytes": peak}


def compare_logprobs(serve_tokens, serve_logprobs, dense_tokens, dense_logprobs):
    """(positions compared, max |difference|, tokens identical). Greedy
    streams are comparable up to and INCLUDING their first differing token:
    there both paths report the maximum of (nearly) the same distribution;
    past it the contexts differ."""
    compared = 0
    worst = 0.0
    for st, sl, dt, dl in zip(serve_tokens, serve_logprobs, dense_tokens, dense_logprobs):
        compared += 1
        worst = max(worst, abs(sl - dl))
        if st != dt:
            break
    return compared, worst, list(serve_tokens) == list(dense_tokens)


def phase_generate(chips: int) -> dict:
    """`generate` (dense cache) on serve's first prompt: per-token greedy
    logprobs agree with the paged path within bf16 tolerance."""
    phase = "generate"
    log = CompileLog()
    device = check_device(phase, chips)
    first = json.loads((WORK / "serve_first.json").read_text())
    rc, records, text = run_cli_captured([
        "generate", "--config", str(CONFIG),
        "--prompt-tokens", ",".join(str(t) for t in first["prompt"]),
        "--max-new-tokens", str(SERVE_NEW_TOKENS), "--logprobs",
        "--cache-dtype", "bfloat16", "--eos-token-id", "-1",
    ])
    (OUT / "generate.stdout").write_text(text)
    check(rc == 0, f"generate: the CLI returned {rc}")
    record = next((r for r in records if "tokens" in r), None)
    check(record is not None, "generate: no output record")
    check(len(record["tokens"]) == SERVE_NEW_TOKENS,
          f"generate: {len(record['tokens'])} tokens, expected {SERVE_NEW_TOKENS}")
    compared, worst, identical = compare_logprobs(
        first["tokens"], first["logprobs"], record["tokens"], record["logprobs"]
    )
    say(phase, f"paged serve vs dense generate, prompt of {len(first['prompt'])} tokens "
               f"[{describe(device)}]: {compared}/{SERVE_NEW_TOKENS} positions comparable, "
               f"max |logprob difference| {worst:.4f} (tolerance {LOGPROB_TOL}), "
               f"tokens identical: {identical}")
    check(math.isfinite(worst) and worst <= LOGPROB_TOL,
          f"generate: paged and dense logprobs differ by {worst:.4f} (> {LOGPROB_TOL})")
    peak = peak_memory(phase, device)
    log.report(phase, device)
    return {"device": device, "cache": log.summary(), "compared": compared,
            "max_logprob_diff": worst, "tokens_identical": identical, "peak_bytes": peak}


def phase_mesh(chips: int) -> dict:
    """Four chips: chip-smoke.yaml's model fitted on a 4-device mesh against
    the same seed and global batch on one device, in this one process."""
    phase = "mesh"
    log = CompileLog()
    device = check_device(phase, chips)

    import gc

    import jax
    import numpy as np

    from llm_training_tpu.cli.config import load_config
    from llm_training_tpu.cli.main import _build

    def leg(name: str, mesh: dict, devices) -> dict:
        overrides = CONFIG_OVERRIDES + [
            f"trainer.max_steps={MESH_STEPS}", "trainer.val_check_interval=null",
            "trainer.health.every_n_steps=null",
            "data.init_args.batch_size=4", "data.init_args.num_samples=12",
            "data.init_args.validation_split=4",
        ] + [f"trainer.mesh.{axis}={size}" for axis, size in mesh.items()]
        config = load_config(CONFIG, overrides)
        config["trainer"].pop("checkpoint")
        config["trainer"]["loggers"] = []
        trainer, objective, datamodule = _build(config)
        trainer.devices = list(devices)
        losses: list[float] = []

        class Track:
            def on_step_end(self, trainer, step, metrics):
                losses.append(float(metrics["loss"]))

        trainer.callbacks.append(Track())
        t0 = time.perf_counter()
        state = trainer.fit(objective, datamodule)
        wall = time.perf_counter() - t0
        # where the parameters and optimizer state actually live
        per_device: dict[int, int] = {}
        total = 0
        for leaf in jax.tree.leaves((state.params, state.opt_state)):
            if not hasattr(leaf, "addressable_shards"):
                continue
            total += leaf.size * leaf.dtype.itemsize
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] = (
                    per_device.get(shard.device.id, 0) + shard.data.nbytes
                )
        snapshot = trainer.telemetry.snapshot()
        result = {
            "losses": losses,
            "state_bytes": total,
            "per_device_share": {d: round(n / total, 4) for d, n in sorted(per_device.items())},
            "kernels": {k.removeprefix("attr/kernel/"): int(v)
                        for k, v in snapshot.items() if k.startswith("attr/kernel/")},
            "collective_bytes": {
                kind: snapshot.get(f"attr/collective/{kind}_bytes", 0.0)
                for kind in ("all_gather", "reduce_scatter", "all_reduce")
            },
            "compile_time_s": snapshot.get("compile_time_s"),
        }
        say(phase, f"{name} mesh {dict(trainer.mesh.shape)} [{describe(device)}]: losses "
                   f"{[round(x, 4) for x in losses]}, state {total / 2**30:.2f} GiB, share per "
                   f"device {result['per_device_share']}, kernels {result['kernels']}, "
                   f"collective bytes/step {result['collective_bytes']}, compile "
                   f"{result['compile_time_s']}s, wall {wall:.0f}s")
        del state, trainer, objective, datamodule
        gc.collect()
        jax.clear_caches()
        return result

    everything = jax.devices()[:4]
    single = leg("one device", {"fsdp_size": 1}, everything[:1])
    legs = {"fsdp=4": leg("fsdp=4", {"fsdp_size": 4}, everything)}
    legs["fsdp=2 x tensor=2"] = leg(
        "fsdp=2 x tensor=2", {"fsdp_size": 2, "tensor_parallel_size": 2}, everything
    )
    check(len(single["losses"]) == MESH_STEPS and all(map(math.isfinite, single["losses"])),
          f"mesh: one-device losses {single['losses']}")
    for name, result in legs.items():
        diffs = np.abs(np.asarray(result["losses"]) - np.asarray(single["losses"]))
        say(phase, f"{name} vs one device: |loss difference| by step "
                   f"{[round(float(x), 5) for x in diffs]} (tolerance {LOSS_TOL})")
        check(len(result["losses"]) == MESH_STEPS and bool((diffs <= LOSS_TOL).all()),
              f"mesh: {name} losses {result['losses']} leave the one-device losses "
              f"{single['losses']} by more than {LOSS_TOL}")
        shares = result["per_device_share"]
        check(len(shares) == 4 and all(0.2 <= s <= 0.3 for s in shares.values()),
              f"mesh: {name} does not hold about a quarter of the state on every "
              f"device: {shares}")
        gathered = result["collective_bytes"]["all_gather"]
        reduced = (result["collective_bytes"]["reduce_scatter"]
                   + result["collective_bytes"]["all_reduce"])
        check(gathered > 0 and reduced > 0,
              f"mesh: {name} compiled step has no all-gather / reduce collectives: "
              f"{result['collective_bytes']}")
        if PLATFORM == "tpu":
            check({"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"} <= set(result["kernels"]),
                  f"mesh: {name} compiled step holds {result['kernels']}, not the flash kernels")
    peak_memory(phase, device)
    log.report(phase, device)
    return {"device": device, "cache": log.summary(), "single": single, "legs": legs}


PHASES = {
    "kernels": phase_kernels,
    "fit": phase_fit,
    "serve": phase_serve,
    "generate": phase_generate,
    "mesh": phase_mesh,
}


def child_main(phase: str, chips: int) -> int:
    OUT.mkdir(parents=True, exist_ok=True)
    WORK.mkdir(parents=True, exist_ok=True)
    try:
        result = PHASES[phase](chips)
    except PhaseFailed as failure:
        say(phase, f"FAILED — {failure}")
        print(RESULT_TAG + json.dumps({"phase": phase, "ok": False, "error": str(failure)}),
              flush=True)
        return 1
    print(RESULT_TAG + json.dumps({"phase": phase, "ok": True, **result}), flush=True)
    return 0


# -------------------------------------------------------------------- parent


def run_phase(phase: str, chips: int) -> dict | None:
    """One child, alone on the chip: echo its report lines, keep its whole
    output under OUT, kill its process group at the time limit."""
    log_path = OUT / f"{phase}.log"
    t0 = time.monotonic()
    # the child stays in this process group: whatever stops the parent's
    # group stops it too, and the parent kills it on every way out
    child = subprocess.Popen(
        [sys.executable, str(Path(__file__).resolve()), "--phase", phase,
         "--chips", str(chips)],
        cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    result = None
    deadline = t0 + PHASE_LIMIT_S[phase]

    def kill(*_):
        if child.poll() is None:
            child.kill()

    timer = threading.Timer(PHASE_LIMIT_S[phase], kill)
    timer.start()
    previous = signal.signal(signal.SIGTERM, lambda *_: (kill(), sys.exit(143)))
    try:
        with open(log_path, "w") as log:
            for line in child.stdout:
                log.write(line)
                if line.startswith(RESULT_TAG):
                    result = json.loads(line[len(RESULT_TAG):])
                elif line.startswith("[chip_smoke:"):
                    print(line, end="", flush=True)
        rc = child.wait()
    finally:
        timer.cancel()
        kill()
        signal.signal(signal.SIGTERM, previous)
    elapsed = time.monotonic() - t0
    if rc != 0 or result is None or not result.get("ok"):
        timed_out = time.monotonic() >= deadline
        print(f"chip_smoke: phase {phase} FAILED (exit {rc}"
              + (f", killed at its {PHASE_LIMIT_S[phase]}s limit" if timed_out else "")
              + f") after {elapsed:.0f}s — tail of {log_path}:", flush=True)
        for line in log_path.read_text().splitlines()[-30:]:
            print("    " + line[:400], flush=True)
        return None
    print(f"chip_smoke: phase {phase} ok in {elapsed:.0f}s", flush=True)
    return result


def run_report() -> bool:
    """`report` on fit's run dir, the way a user reads a run: a jax-free
    subprocess, rc 0."""
    proc = subprocess.run(
        [sys.executable, "-m", "llm_training_tpu", "report", str(RUN_DIR)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    (OUT / "report.txt").write_text(proc.stdout + proc.stderr)
    if proc.returncode != 0:
        print(f"chip_smoke: phase report FAILED (exit {proc.returncode}):\n"
              + (proc.stdout + proc.stderr)[-2000:], flush=True)
        return False
    sections = [l for l in proc.stdout.splitlines() if l.startswith("== ")]
    print(f"chip_smoke: phase report ok — sections {sections}", flush=True)
    return True


def keep_small_records() -> None:
    """Bring the run dir's small records under OUT; drop the checkpoints."""
    if RUN_DIR.is_dir():
        for path in RUN_DIR.iterdir():
            if path.is_file() and path.stat().st_size < 8 * 2**20:
                shutil.copy2(path, OUT / path.name)
    shutil.rmtree(WORK, ignore_errors=True)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 runs ONLY the sharded fit and the one-device "
                        "fit it is compared with")
    parser.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.phase:
        return child_main(args.phase, args.chips)

    OUT.mkdir(parents=True, exist_ok=True)
    shutil.rmtree(WORK, ignore_errors=True)
    t0 = time.monotonic()
    phases = ["mesh"] if args.chips == 4 else ["kernels", "fit", "serve", "generate"]
    device = None
    try:
        for phase in phases:
            result = run_phase(phase, args.chips)
            if result is None:
                return 1
            device = device or result["device"]
        if args.chips == 1 and not run_report():
            return 1
    finally:
        keep_small_records()
    print(f"chip_smoke: all phases ok in {time.monotonic() - t0:.0f}s on "
          f"{describe(device)}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
