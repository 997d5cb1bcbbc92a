"""Phi-4-mini-flash-reasoning model config (`model_type: phi4flash`,
https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json;
the family's paper: arXiv:2507.06607, SambaY).

A decoder-hybrid-decoder stack: the first half (the self-decoder) alternates
Mamba-1 layers and differential attention over a sliding window; layer `L/2`
is one more Mamba layer whose scan output is the MEMORY, layer `L/2 + 1` the
ONE full-attention layer; from `L/2 + 2` on (the cross-decoder) gated memory
units, which read the memory, alternate with cross-attention layers, which
read layer `L/2 + 1`'s keys and values and have none of their own.

The published keys give the sizes. Which layer is which, the differential
attention, the Mamba sizes (`mamba_*`: Mamba's own defaults) and the biases
are this family's assumption, listed in docs/models.md and in the benchmark's
configuration file under `assumed`.
"""

from __future__ import annotations

import math
from typing import Literal

from pydantic import model_validator

from llm_training_tpu.models.base import (
    BaseModelConfig,
    KVCacheSpec,
    RecurrentCacheSpec,
)

# the kinds of layer, as `layer_kinds` names them
MAMBA, WINDOW, MEMORY, FULL, GMU, CROSS = "mamba", "window", "memory", "full", "gmu", "cross"


class Phi4FlashConfig(BaseModelConfig):
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    head_dim: int | None = None  # None: hidden_size / num_attention_heads
    hidden_act: Literal["silu"] = "silu"
    max_position_embeddings: int = 262144  # read by nothing: no positional term
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    initializer_range: float = 0.02
    pad_token_id: int | None = None
    bos_token_id: int | None = None
    eos_token_id: int | list[int] | None = None

    # --- the Mamba layers (not keys of the source: Mamba's defaults)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None  # None: ceil(hidden_size / 16)

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"

    @model_validator(mode="after")
    def _validate(self) -> "Phi4FlashConfig":
        if self.mb_per_layer != 2:
            raise ValueError("mb_per_layer other than 2 is not implemented (published: 2)")
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 4:
            raise ValueError(
                "num_hidden_layers must be a multiple of 4: both halves alternate two kinds of layer"
            )
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs its heads: both head counts must be even")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if self.mlp_bias or self.lm_head_bias:
            raise ValueError("mlp_bias / lm_head_bias are not implemented (published: false)")
        if not self.tie_word_embeddings:
            raise ValueError("an untied head is not implemented (published: tied)")
        return self

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def resolved_dt_rank(self) -> int:
        return self.mamba_dt_rank or math.ceil(self.hidden_size / 16)

    @property
    def layer_kinds(self) -> list[str]:
        half = self.num_hidden_layers // 2
        first = [MAMBA if i % self.mb_per_layer == 0 else WINDOW for i in range(half)]
        second = [GMU if i % 2 == 0 else CROSS for i in range(half + 2, self.num_hidden_layers)]
        return first + [MEMORY, FULL] + second

    def cache_specs(self) -> tuple[tuple[KVCacheSpec, KVCacheSpec], RecurrentCacheSpec]:
        """The one declaration the pools, the slab and their shardings derive
        from (`infer/cache.py:cache_specs`): ONE layer's pages at full length,
        read by that layer and every cross-attention layer; the window layers'
        ring; a slab for the Mamba layers. Keys and values are cached a PAIR
        of heads (`model.py`: heads `2j, 2j + 1` side by side, twice as wide),
        which is the same bytes a token. The slab is the state `[inner,
        d_state]` in runs of 128 channels (`ops/selective_scan.py`)."""
        kinds = self.layer_kinds
        pairs, width = self.num_key_value_heads // 2, 2 * self.resolved_head_dim
        inner = self.mamba_inner
        runs = inner // 128 if inner % 128 == 0 else 1
        return (
            (
                KVCacheSpec(1, pairs, width, readers=1 + kinds.count(CROSS)),
                KVCacheSpec(kinds.count(WINDOW), pairs, width, window=self.sliding_window),
            ),
            RecurrentCacheSpec(
                layers=kinds.count(MAMBA) + 1, heads=runs, key_dim=self.mamba_d_state,
                value_dim=inner // runs, conv_taps=self.mamba_d_conv - 1, conv_channels=inner,
            ),
        )
