"""required_ops and each costs/* against counts worked by hand at the three
configurations' sizes."""

import json
from pathlib import Path

import pytest

from benchmarks import required_ops
from benchmarks.costs import flash, paged_decode

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_paged_decode_counts_live_tokens_only():
    # phi3-medium: batch 32 rows of 1000 live tokens, 40 q / 10 kv heads of 128, bf16
    one = paged_decode.cost(32 * 1000, 32, 40, 10, 128, 2)
    kv = 32000 * 10 * 128 * 2 * 2  # K and V
    q_out = 2 * 32 * 40 * 128 * 2
    assert one["bytes"] == kv + q_out == 164_495_360
    assert one["flops"] == 4 * 32000 * 40 * 128 == 655_360_000
    # olmoe: full multi-head cache, 16 kv heads
    assert paged_decode.cost(32000, 32, 16, 16, 128, 2)["bytes"] == 32000 * 16 * 128 * 4 + 2 * 32 * 16 * 128 * 2


def test_flash_counts_causal_pairs_per_document_with_the_window():
    assert flash.visible_pairs(4, None) == 10
    assert flash.visible_pairs(2048, 2047) == 2048 * 2049 // 2 - 1  # the last query loses one key
    documents = [2048, 1024, 512, 256, 256]
    pairs = (2048 * 2049 // 2 - 1) + 1024 * 1025 // 2 + 512 * 513 // 2 + 2 * (256 * 257 // 2)
    assert pairs == 2_820_095
    fwd = flash.cost("flash_fwd", 4, documents, 40, 10, 128, 2, 2047)
    assert fwd["flops"] == 2 * 2 * 4 * pairs * 40 * 128
    assert flash.cost("flash_bwd_dq", 4, documents, 40, 10, 128, 2, 2047)["flops"] == fwd["flops"] * 3 // 2
    assert flash.cost("flash_bwd_dkv", 4, documents, 40, 10, 128, 2, 2047)["flops"] == fwd["flops"] * 2
    q = 4 * 4096 * 40 * 128 * 2
    kv = 4 * 4096 * 10 * 128 * 2
    assert fwd["bytes"] == 2 * q + 2 * kv + 4 * 4096 * 40 * 4


@pytest.mark.parametrize("name,per_layer,head", [
    # phi3: q,k,v 5120x(40+20)x128, o 5120x5120, mlp 3x5120x17920
    ("phi3-medium-4k", 5120 * 60 * 128 + 5120 * 5120 + 3 * 5120 * 17920, 5120 * 32064),
    ("phi3-medium-4k-fsdp4", 5120 * 60 * 128 + 5120 * 5120 + 3 * 5120 * 17920, 5120 * 32064),
    # olmoe: attention 4x2048x2048, 8 of 64 experts of 3x2048x1024, router 2048x64
    ("olmoe-1b-7b", 4 * 2048 * 2048 + 8 * 3 * 2048 * 1024 + 2048 * 64, 2048 * 50304),
])
def test_matmul_parameters_per_token(name, per_layer, head):
    cfg = config(name)
    assert required_ops.matmul_params_per_token(cfg) == cfg["num_hidden_layers"] * per_layer + head


def test_train_operations_per_token_phi3():
    cfg = config("phi3-medium-4k-fsdp4")
    documents = [2048, 1024, 512, 256, 256]
    attention = 2 * (2 + 3 + 4) * 2_820_095 * 40 * 128 / 4096  # per layer per token
    want = 6 * required_ops.matmul_params_per_token(cfg) + cfg["num_hidden_layers"] * attention
    assert required_ops.train_flops_per_token(cfg, documents) == pytest.approx(want, rel=1e-12)
