"""Olmo-Hybrid model config (`model_type: olmo_hybrid`,
https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json).

A hybrid stack, `layer_types` says which: `linear_attention` layers are a
gated delta rule with ONE decay a head and a RECTANGULAR state
(`linear_key_head_dim` 96 x `linear_value_head_dim` 192 as published),
`full_attention` layers plain multi-head softmax attention with an RMSNorm
over the whole q and the whole k projection and NO positional term
(`rope_parameters.rope_theta: null`); every layer's MLP is a dense SwiGLU,
and the norms sit on a sub-block's OUTPUT (Olmo 2/3's order).

What the published keys do not settle is this family's assumption, listed
in docs/models.md and in the benchmark's configuration file under
`assumed`: the norm order on both layer kinds, the q/k norm's shape, no
rotary, separate (not fused) convolutions, `A_log` / `dt_bias`, the output
gate's shape.
"""

from __future__ import annotations

from typing import ClassVar, Literal

from pydantic import model_validator

from llm_training_tpu.models.base import (
    BaseModelConfig,
    KVCacheSpec,
    RecurrentCacheSpec,
)

LINEAR, FULL = "linear_attention", "full_attention"


class OlmoHybridConfig(BaseModelConfig):
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    head_dim: int | None = None  # None: hidden_size / num_attention_heads
    hidden_act: Literal["silu"] = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-6
    pad_token_id: int | None = None
    bos_token_id: int | None = None
    eos_token_id: int | list[int] | None = None
    tie_word_embeddings: bool = False
    # published as `rope_parameters.rope_theta: null`: no positional term
    rope_theta: float | None = None

    # --- layer kinds. None: [linear x 3, full] repeated (as published). A
    # list longer than the depth is cut to it, so a depth cut keeps the list.
    layer_types: list[Literal["linear_attention", "full_attention"]] | None = None

    # --- the linear layers' gated delta rule
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    delta_chunk_size: int = 64

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"
    scan_layers: bool = True
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"

    # what `llama.LlamaMLP` reads besides the widths
    mlp_bias: ClassVar[bool] = False

    @model_validator(mode="after")
    def _validate(self) -> "OlmoHybridConfig":
        if self.rope_theta is not None:
            raise ValueError("olmo_hybrid with a rope_theta is not implemented (published: null)")
        if self.attention_bias:
            raise ValueError("attention_bias=true is not implemented (published: false)")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError(
                "linear_num_key_heads other than linear_num_value_heads is not "
                "implemented (published: 30 and 30)"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if self.layer_types is not None and len(self.layer_types) < self.num_hidden_layers:
            raise ValueError("layer_types is shorter than num_hidden_layers")
        return self

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def layer_kinds(self) -> list[bool]:
        """True = full attention, False = the delta rule, a layer."""
        if self.layer_types is None:
            return [i % 4 == 3 for i in range(self.num_hidden_layers)]
        return [kind == FULL for kind in self.layer_types[: self.num_hidden_layers]]

    @property
    def scan_period(self) -> int:
        """Scan-body depth: the pattern's period (4 as published); a stack of
        one period scans once over itself; 0 = loop."""
        if not self.scan_layers:
            return 0
        from llm_training_tpu.models.moe_scan_io import detect_period

        return detect_period(self.layer_kinds) or self.num_hidden_layers

    def cache_specs(self) -> tuple[KVCacheSpec, RecurrentCacheSpec]:
        """The one declaration the pool, the slab and their shardings derive
        from (`infer/cache.py:cache_specs`): pages for the full layers, a
        fixed slab a decode slot for the linear ones."""
        kinds = self.layer_kinds
        heads = self.linear_num_value_heads
        return (
            KVCacheSpec(sum(kinds), self.num_key_value_heads, self.resolved_head_dim),
            RecurrentCacheSpec(
                layers=len(kinds) - sum(kinds), heads=heads,
                key_dim=self.linear_key_head_dim, value_dim=self.linear_value_head_dim,
                conv_taps=self.linear_conv_kernel_dim - 1,
                conv_channels=heads * (2 * self.linear_key_head_dim + self.linear_value_head_dim),
            ),
        )
