"""The benchmark's own tests run on the CPU (`python -m pytest benchmarks/tests -q`),
outside tier-1. Tiny configurations live here, never under configs/."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

OPTIM = {
    "optimizer": "adamw", "learning_rate": 1e-05, "lr_scheduler": "constant", "warmup_steps": 0,
    "grad_clip_norm": 1.0,
    "optimizer_kwargs": {"b1": 0.9, "b2": 0.999, "eps": 1e-08, "weight_decay": 0.0001},
}
TINY = {
    "configs": {
        "tiny-phi3": {
            "source": "test", "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
            "vocab_size": 256, "max_position_embeddings": 4096, "rope_theta": 10000.0,
            "rms_norm_eps": 1e-05, "sliding_window": 31, "tie_word_embeddings": False,
            "initializer_range": 0.02, "reference": "phi3", "control_precision": "fp8",
            "check": {"served_logit_gap": 0.008},
            "program": {"model_class": "Phi3", "model_kwargs": {
                "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "head_dim": 16}},
        },
        "tiny-olmoe": {
            "source": "test", "hidden_size": 64, "intermediate_size": 32,
            "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 2,
            "vocab_size": 256, "max_position_embeddings": 4096, "rope_theta": 10000,
            "rms_norm_eps": 1e-05, "tie_word_embeddings": False, "initializer_range": 0.02,
            "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
            "reference": "olmoe", "control_precision": "fp8",
            "check": {"served_logit_gap": 0.008},
            "program": {"model_class": "Llama", "model_kwargs": {
                "param_dtype": "bfloat16", "compute_dtype": "bfloat16", "head_dim": 16,
                "moe_intermediate_size": 32, "qk_norm": True, "qk_norm_scope": "full"}},
        },
        "tiny-phi3-train": {
            "source": "test", "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 2,
            "vocab_size": 256, "max_position_embeddings": 4096, "rope_theta": 10000.0,
            "rms_norm_eps": 1e-05, "sliding_window": 31, "tie_word_embeddings": False,
            "initializer_range": 0.02, "reference": "phi3", "control_precision": "fp8",
            "check": {"loss_abs": 0.001, "first_grad_norm_rel": 0.0015,
                      "param_change_norm_rel": 0.01},
            "program": {"model_class": "Phi3", "model_kwargs": {
                "param_dtype": "float32", "compute_dtype": "bfloat16", "head_dim": 16,
                "enable_gradient_checkpointing": True, "recompute_granularity": "selective"}},
            "train": {"mesh": {"fsdp_size": 4}, "log_every_n_steps": 2, "ce_chunk_size": 64,
                      "optim": OPTIM},
        },
    },
    "traffic": {
        "tiny-closed": {
            "kind": "serve_closed", "clients": 4,
            "engine": {"max_batch": 4, "prefill_chunk": 16, "max_model_len": 64, "block_size": 16},
            "prompt_lengths": [8, 32, 16, 24], "output_lengths": [4, 16, 8, 12, 10],
            "stagger_first_output": True, "eos": None,
        },
        "tiny-train": {
            "kind": "train_fit", "seq_len": 64, "documents": [32, 16, 8, 4, 4],
            "global_batch_rows": 8, "global_batch_tokens": 512, "distinct_batches": 4,
            "warmup_steps": 3, "check": {"steps": 3, "reference_rows_per_block": 4},
        },
    },
    "cells": {
        "tiny-phi3-serve": ("tiny-phi3", "tiny-closed", 1),
        "tiny-olmoe-serve": ("tiny-olmoe", "tiny-closed", 1),
        "tiny-phi3-train": ("tiny-phi3-train", "tiny-train", 4),
    },
}


def make_root(tmp_path: Path) -> Path:
    """A directory that holds only a BENCHMARK.json and the benchmark's files,
    with the tiny cells ADDED as new files and new entries."""
    root = tmp_path / "checkout"
    shutil.copytree(
        REPO / "benchmarks", root / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__", "tests"),
    )
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, config in TINY["configs"].items():
        (root / "benchmarks" / "configs" / f"{name}.json").write_text(json.dumps(config))
        bench["configs"].append({
            "name": name, "source": "test", "file": f"benchmarks/configs/{name}.json",
            "reduced": [], "why": "tiny, for the CPU"})
    for name, traffic in TINY["traffic"].items():
        (root / "benchmarks" / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
    for name, (config, traffic, chips) in TINY["cells"].items():
        bench["workloads"].append({
            "name": name, "config": config, "traffic": traffic, "chips": chips,
            "why": "tiny, for the CPU"})
    for group in ("end_to_end", "per_layer"):
        for metric in bench[group]:
            if "workloads" in metric:
                kind = "train" if any("train" in w for w in metric["workloads"]) else "serve"
                metric["workloads"] += [c for c in TINY["cells"] if c.endswith(kind)]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
