"""Gemma 1/2/3 model config.

Family member beyond the reference's named models (it covered Gemma only
through `HFCausalLM`'s torch wrapping, `hf_causal_lm.py:22`); here the
computation graph is native. `version=2` adds the Gemma-2 graph changes:
pre+post sandwich norms, attention/final logit soft-capping, alternating
sliding-window layers, and the query_pre_attn_scalar attention scale.
`version=3` (Gemma3 text) additionally: per-head zero-centered qk-norm, an
explicit `layer_types` sliding/full pattern (5:1, not alternating), and DUAL
rotary tables — sliding layers use `rope_local_base_freq` unscaled, full
layers use `rope_theta` with the optional `rope_scaling`.
"""

from __future__ import annotations

from typing import Literal

from pydantic import model_validator

from llm_training_tpu.models.base import BaseModelConfig, KVCacheSpec


class GemmaConfig(BaseModelConfig):
    version: Literal[1, 2, 3] = 1

    vocab_size: int = 256000
    hidden_size: int = 2048
    intermediate_size: int = 16384
    num_hidden_layers: int = 18
    num_attention_heads: int = 8
    num_key_value_heads: int = 1
    head_dim: int = 256
    max_position_embeddings: int = 8192
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    attention_bias: bool = False
    pad_token_id: int | None = 0
    bos_token_id: int | None = 2
    eos_token_id: int | None = 1
    tie_word_embeddings: bool = True  # always, both versions

    # --- gemma 2 graph features
    query_pre_attn_scalar: int | None = None  # None -> head_dim
    attn_logit_softcapping: float | None = None
    final_logit_softcapping: float | None = None
    # sliding window on even layer indices (HF layer_types pattern); for
    # version=3 the pattern comes from `layer_types` instead
    sliding_window: int | None = None

    # --- gemma 3 graph features
    # per-layer 'sliding_attention' / 'full_attention' (HF Gemma3 layer_types)
    layer_types: list[str] | None = None
    # rope for sliding layers; full layers use rope_theta (+ rope_scaling)
    rope_local_base_freq: float = 10000.0
    rope_scaling: dict | None = None
    use_qk_norm: bool = False

    enable_gradient_checkpointing: bool = False
    recompute_granularity: Literal["full", "selective"] = "full"
    scan_layers: bool = True
    attention_impl: Literal["auto", "xla", "pallas"] = "auto"
    # context parallelism: shard the sequence axis and run ring attention
    # (sliding windows and sinks compose; see parallel/ring_attention.py)
    ring_attention: bool = False

    @model_validator(mode="after")
    def _validate(self) -> "GemmaConfig":
        if self.num_attention_heads % self.num_key_value_heads != 0:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must be divisible "
                f"by num_key_value_heads ({self.num_key_value_heads})"
            )
        if self.version == 1 and (
            self.attn_logit_softcapping or self.final_logit_softcapping or self.sliding_window
        ):
            raise ValueError("softcapping/sliding_window are Gemma-2 (version=2) features")
        if self.version == 2 and self.scan_layers and self.num_hidden_layers % 2 != 0:
            raise ValueError(
                "gemma-2 scan_layers scans (sliding, full) layer pairs; "
                "num_hidden_layers must be even (disable scan_layers otherwise)"
            )
        if self.version == 3:
            if self.layer_types is not None and len(self.layer_types) != self.num_hidden_layers:
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries for "
                    f"{self.num_hidden_layers} layers"
                )
            if self.sliding_window and self.layer_types is None:
                # refuse the ambiguous case: HF re-derives a 5:1 pattern from
                # a null layer_types on reload, which would silently diverge
                # from an all-global trained model
                raise ValueError(
                    "version=3 with sliding_window requires an explicit "
                    "layer_types pattern"
                )
            if "use_qk_norm" not in self.model_fields_set:
                # HF Gemma3 text models always apply q/k norms; defaulting
                # False would train without them yet export as gemma3_text,
                # whose HF reload random-initializes the missing norm keys
                self.use_qk_norm = True
            # the 5:1 sliding/full pattern is aperiodic vs the layer count on
            # real checkpoints (e.g. 26 layers), so layers are looped, not
            # scanned — each gets its own window/rope statically
            self.scan_layers = False
        if self.layer_types is not None and self.version != 3:
            raise ValueError("layer_types is a Gemma-3 (version=3) feature")
        return self

    @property
    def rope_config(self):
        """Global rope: rope_theta, plus Gemma3's optional rope_scaling
        (linear factor 8 on the 4B+ checkpoints)."""
        from llm_training_tpu.ops.rope_utils import rope_config_from_hf

        return rope_config_from_hf(
            self.rope_scaling, self.rope_theta, self.head_dim,
            self.max_position_embeddings,
        )

    @property
    def attention_scale(self) -> float:
        base = self.query_pre_attn_scalar if self.query_pre_attn_scalar else self.head_dim
        return float(base) ** -0.5

    def cache_specs(self) -> tuple[KVCacheSpec, None]:
        """Every layer caches keys and values (`BaseModelConfig.cache_specs`)."""
        return KVCacheSpec(self.num_hidden_layers, self.num_key_value_heads, self.head_dim), None

    def layer_sliding_window(self, layer_idx: int) -> int | None:
        """HF Gemma2: 'sliding_attention' on even indices; Gemma3: explicit
        `layer_types` pattern."""
        if self.version == 3:
            if self.sliding_window and self.layer_types is not None:
                if self.layer_types[layer_idx] == "sliding_attention":
                    return self.sliding_window
            return None
        if self.version == 2 and self.sliding_window and layer_idx % 2 == 0:
            return self.sliding_window
        return None

    @property
    def local_rope_config(self):
        """Gemma3 sliding layers: rope_local_base_freq, never scaled."""
        from llm_training_tpu.ops.rope_utils import RoPEConfig

        return RoPEConfig(
            type="default",
            base=self.rope_local_base_freq,
            dim=self.head_dim,
            max_position_embeddings=self.max_position_embeddings,
        )
