"""HuggingFace checkpoint IO: streamed loading + safetensors export.

Capability parity: the reference's pre-trained-weight path
(`lms/base_lm.py:175-193` — rank-0 `torch.load` + broadcast/scatter) and the
export half of `scripts/convert_to_hf.py:101-162`. TPU-native design: instead
of loading everything on one rank and broadcasting over NCCL, each tensor is
read lazily from safetensors and `jax.device_put` with its `NamedSharding` —
every host reads only once, XLA scatters the shards over ICI, and the host
working set stays one-tensor-sized.

Reading goes through the torch framework of `safetensors` (torch is CPU-only
here) so bf16 files round-trip exactly; writing uses `safetensors.torch` with
`{"format": "pt"}` metadata, which is what `transformers.from_pretrained`
expects.
"""

from __future__ import annotations

import functools
import json
import logging
from collections.abc import Mapping
from pathlib import Path
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

_SAFE_INDEX = "model.safetensors.index.json"
_SAFE_SINGLE = "model.safetensors"

# config-class name -> conversion module; each module provides
# params_from_hf / params_to_hf / config_from_hf / config_to_hf
_FAMILIES: dict[str, str] = {
    "LlamaConfig": "llm_training_tpu.models.llama.hf_conversion",
    "Phi3Config": "llm_training_tpu.models.phi3.hf_conversion",
    "GemmaConfig": "llm_training_tpu.models.gemma.hf_conversion",
    "DeepseekConfig": "llm_training_tpu.models.deepseek.hf_conversion",
    "GptOssConfig": "llm_training_tpu.models.gpt_oss.hf_conversion",
    "Qwen3NextConfig": "llm_training_tpu.models.qwen3_next.hf_conversion",
    "SolarOpen2Config": "llm_training_tpu.models.solar_open2.hf_conversion",
    "LongcatFlashConfig": "llm_training_tpu.models.longcat_flash.hf_conversion",
    "AfmoeConfig": "llm_training_tpu.models.afmoe.hf_conversion",
    "OlmoHybridConfig": "llm_training_tpu.models.olmo_hybrid.hf_conversion",
    "Phi4FlashConfig": "llm_training_tpu.models.phi4flash.hf_conversion",
    "GigaChat35Config": "llm_training_tpu.models.gigachat35.hf_conversion",
    "MiniMaxConfig": "llm_training_tpu.models.minimax.hf_conversion",
    "BambaConfig": "llm_training_tpu.models.bamba.hf_conversion",
    "Glm4MoeConfig": "llm_training_tpu.models.glm4_moe.hf_conversion",
    "Ernie45MoeConfig": "llm_training_tpu.models.ernie45_moe.hf_conversion",
    "HunYuanMoeConfig": "llm_training_tpu.models.hunyuan_moe.hf_conversion",
}


def conversion_module(config: Any):
    import importlib

    name = type(config).__name__
    if name not in _FAMILIES:
        raise ValueError(
            f"no HF conversion registered for {name}; known: {sorted(_FAMILIES)}"
        )
    return importlib.import_module(_FAMILIES[name])


class LazyStateDict(Mapping):
    """Mapping over one or more safetensors files that reads each tensor on
    first access (and never holds more than the caller keeps alive)."""

    def __init__(self, path: str | Path):
        self.path = Path(path)
        self._key_to_file: dict[str, Path] = {}
        self._handles: dict[Path, Any] = {}
        for file, keys in self._discover():
            for key in keys:
                self._key_to_file[key] = file

    def _discover(self) -> Iterator[tuple[Path, list[str]]]:
        from safetensors import safe_open

        if self.path.is_file():
            files = [self.path]
        elif (self.path / _SAFE_INDEX).exists():
            index = json.loads((self.path / _SAFE_INDEX).read_text())
            files = sorted({self.path / f for f in index["weight_map"].values()})
        elif (self.path / _SAFE_SINGLE).exists():
            files = [self.path / _SAFE_SINGLE]
        else:
            files = sorted(self.path.glob("*.safetensors"))
        if not files:
            raise FileNotFoundError(
                f"no safetensors found under {self.path} "
                f"(expected {_SAFE_SINGLE} or {_SAFE_INDEX})"
            )
        for file in files:
            with safe_open(file, framework="pt") as f:
                yield file, list(f.keys())

    def _handle(self, file: Path):
        from safetensors import safe_open

        if file not in self._handles:
            self._handles[file] = safe_open(file, framework="pt")
        return self._handles[file]

    def __getitem__(self, key: str):
        return self._handle(self._key_to_file[key]).get_tensor(key)

    def __iter__(self):
        return iter(self._key_to_file)

    def __len__(self) -> int:
        return len(self._key_to_file)


def load_hf_config(path: str | Path) -> dict:
    config_file = Path(path) / "config.json" if Path(path).is_dir() else Path(path)
    return json.loads(config_file.read_text())


@functools.lru_cache(maxsize=None)
def _device_cast(dtype_name: str):
    # one compiled cast per (dtype, shape/sharding) via the jit cache —
    # astype preserves the operand's sharding, so no out_shardings needed
    return jax.jit(lambda x: x.astype(jnp.dtype(dtype_name)))


def _pp_stages(config: Any) -> int:
    return int(getattr(config, "pipeline_stages", 1) or 1)


def _pp_wrap_leaf_fn(config: Any, leaf_fn):
    """Pipeline-layout load adapter (models/pipeline.py): conversions emit
    the scan layout — stacked leaves [L, ...] under ('layers', ...) — but a
    pipelined model stores [S, L/S, ...] under ('pipeline', 'ticks',
    'layers', ...). Reshape on host BEFORE placement (so the device_put
    lands on the stage-sharded buffers) and look shardings up under the
    pipeline path; `_pp_relocate` moves the subtree afterwards."""
    stages = _pp_stages(config)
    per = config.num_hidden_layers // stages

    def wrapped(path: tuple[str, ...], value):
        if path and path[0] == "layers":
            value = value.reshape((stages, per) + value.shape[1:])
            path = ("pipeline", "ticks") + path
        return leaf_fn(path, value) if leaf_fn is not None else value

    return wrapped


def _pp_relocate(tree: Any, config: Any) -> Any:
    """Move the converted scan stack to its pipeline-layout position (the
    conversion's `_set_path` keyed it by the original 'layers' path)."""
    params = tree.get("params", tree)
    if "layers" in params:
        params.setdefault("pipeline", {}).setdefault("ticks", {})[
            "layers"
        ] = params.pop("layers")
    return tree


def _pp_as_scan(params: Mapping, config: Any) -> Mapping:
    """Pipeline-layout export adapter: present the [S, L/S, ...] stage
    stacks as the [L, ...] scan layout the conversions consume. The
    reshape merges the stage axis lazily; values cross to host once,
    inside the conversion's own per-path fetch."""
    import flax.linen as nn

    p = params.get("params", params)
    if "pipeline" not in p:
        return params
    p = dict(p)
    stack = nn.meta.unbox(p.pop("pipeline"))["ticks"]["layers"]
    p["layers"] = jax.tree.map(
        lambda v: v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:]), stack
    )
    return {"params": p} if "params" in params else p


def load_pretrained_params(
    config: Any,
    hf_path: str | Path | Mapping,
    shardings: Any | None = None,
    dtypes: Any | None = None,
) -> Any:
    """HF checkpoint dir -> flax param tree `{'params': ...}`.

    When `shardings` (a matching pytree of NamedSharding) is given, each leaf
    is `device_put` straight to its shards and the host copy is dropped —
    the memory-safe analogue of the reference's broadcast distribution
    (`base_lm.py:175-193`). `dtypes` (matching pytree or single dtype) casts
    leaves on the way in (e.g. fp32 master params from a bf16 checkpoint).

    `hf_path` may also be an in-memory Mapping of HF keys -> tensors
    (tests / already-open checkpoints) instead of a directory.
    """
    conv = conversion_module(config)
    state_dict = (
        hf_path if isinstance(hf_path, Mapping) else LazyStateDict(hf_path)
    )

    pipelined = _pp_stages(config) > 1

    if shardings is None and dtypes is None:
        if pipelined:
            tree = conv.params_from_hf(
                state_dict, config, leaf_fn=_pp_wrap_leaf_fn(config, None)
            )
            return _pp_relocate(tree, config)
        return conv.params_from_hf(state_dict, config)

    by_path = _flatten_by_path(shardings)
    dtypes_by_path = (
        _flatten_by_path(dtypes) if _is_pytree(dtypes) else None
    )

    def leaf_fn(path: tuple[str, ...], value: np.ndarray):
        key = ("params",) + path
        dtype = dtypes_by_path[key] if dtypes_by_path is not None else dtypes
        sharding = by_path.get(key) if by_path is not None else None
        if sharding is not None:
            target = jnp.dtype(dtype) if dtype is not None else None
            if target is not None and target.itemsize < value.dtype.itemsize:
                # NARROWING (e.g. fp32 checkpoint -> bf16 leaves): cast on
                # host so the transfer ships the small copy
                value = value.astype(target)
            # WIDENING (bf16 checkpoint -> fp32 masters) happens on device:
            # a host-side astype would hold checkpoint + widened copies
            # simultaneously (at 70B geometry a scanned mlp stack is ~37 GB
            # bf16 — the fp32 cast would transiently need ~112 GB of host
            # RAM; on device the transient is per-chip and freed per leaf)
            placed = jax.device_put(value, sharding)
            if target is not None and placed.dtype != target:
                placed = _device_cast(target.name)(placed)
            return placed
        if dtype is not None:
            value = value.astype(dtype)
        return value

    # each converted leaf is placed (device_put) inside the conversion walk,
    # so the host never holds more than one (stacked) tensor at a time
    if pipelined:
        tree = conv.params_from_hf(
            state_dict, config, leaf_fn=_pp_wrap_leaf_fn(config, leaf_fn)
        )
        return _pp_relocate(tree, config)
    return conv.params_from_hf(state_dict, config, leaf_fn=leaf_fn)


def _is_pytree(value: Any) -> bool:
    return isinstance(value, (dict, list, tuple))


def _flatten_by_path(tree: Any) -> dict[tuple[str, ...], Any] | None:
    """pytree -> {('params', 'embed_tokens', ...): leaf} with string keys."""
    if tree is None:
        return None
    flat: dict[tuple[str, ...], Any] = {}
    for key_path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[tuple(str(getattr(k, "key", k)) for k in key_path)] = leaf
    return flat


def _as_torch_state_dict(state_dict: Mapping[str, np.ndarray], dtype: str):
    import torch

    torch_dtype = getattr(torch, dtype)
    out = {}
    for key, value in state_dict.items():
        array = np.asarray(value)
        if array.dtype.name == "bfloat16":  # ml_dtypes bf16: torch can't ingest it
            array = array.astype(np.float32)
        out[key] = torch.from_numpy(np.ascontiguousarray(array)).to(torch_dtype)
    return out


def save_hf_checkpoint(
    params: Mapping,
    config: Any,
    output_dir: str | Path,
    dtype: str = "bfloat16",
    max_shard_bytes: int = 5 * 1024**3,
    generation_config: dict | None = None,
) -> Path:
    """flax params + config -> HF-layout dir (safetensors shards + index +
    config.json). Reference: `scripts/convert_to_hf.py:76-97`."""
    from safetensors.torch import save_file

    conv = conversion_module(config)
    output_dir = Path(output_dir)
    output_dir.mkdir(parents=True, exist_ok=True)

    if _pp_stages(config) > 1:
        params = _pp_as_scan(params, config)
    state_dict = _as_torch_state_dict(conv.params_to_hf(params, config), dtype)

    # shard greedily in key order, HF-style file naming
    shards: list[dict[str, Any]] = [{}]
    sizes = [0]
    for key, tensor in state_dict.items():
        nbytes = tensor.numel() * tensor.element_size()
        if sizes[-1] + nbytes > max_shard_bytes and shards[-1]:
            shards.append({})
            sizes.append(0)
        shards[-1][key] = tensor
        sizes[-1] += nbytes

    if len(shards) == 1:
        save_file(shards[0], output_dir / _SAFE_SINGLE, metadata={"format": "pt"})
    else:
        weight_map = {}
        for i, shard in enumerate(shards):
            name = f"model-{i + 1:05d}-of-{len(shards):05d}.safetensors"
            save_file(shard, output_dir / name, metadata={"format": "pt"})
            weight_map.update({key: name for key in shard})
        index = {
            "metadata": {"total_size": sum(sizes)},
            "weight_map": weight_map,
        }
        (output_dir / _SAFE_INDEX).write_text(json.dumps(index, indent=2))

    hf_config = conv.config_to_hf(config, torch_dtype=dtype)
    (output_dir / "config.json").write_text(json.dumps(hf_config, indent=2) + "\n")
    if generation_config:
        (output_dir / "generation_config.json").write_text(
            json.dumps(generation_config, indent=2) + "\n"
        )
    return output_dir


_ARCH_TO_FAMILY = {
    # HF model_type -> our (model class path, conversion config name)
    "llama": "llm_training_tpu.models.Llama",
    "mistral": "llm_training_tpu.models.Llama",  # same graph: GQA + SwiGLU + RMSNorm
    "ministral": "llm_training_tpu.models.Llama",  # + per-layer sliding/full pattern
    "helium": "llm_training_tpu.models.Llama",  # llama graph (o_proj bias hardcoded off)
    "arcee": "llm_training_tpu.models.Llama",  # non-gated relu^2 MLP under rmsnorm
    "seed_oss": "llm_training_tpu.models.Llama",  # qkv bias + separate o-bias flag
    "qwen2": "llm_training_tpu.models.Llama",  # + attention_bias (in config.json)
    "qwen3": "llm_training_tpu.models.Llama",  # + per-head qk-norm
    "olmo": "llm_training_tpu.models.Llama",  # OLMo-1: non-parametric LayerNorm, clip_qkv
    "olmo2": "llm_training_tpu.models.Llama",  # + post-norm blocks, full qk-norm
    "olmo3": "llm_training_tpu.models.Llama",  # + per-layer sliding, dual rope
    "granite": "llm_training_tpu.models.Llama",  # + 4 scalar multipliers
    "starcoder2": "llm_training_tpu.models.Llama",  # LayerNorm + gelu MLP + biases
    "stablelm": "llm_training_tpu.models.Llama",  # biased LayerNorm + swiglu + partial rope
    "cohere": "llm_training_tpu.models.Llama",  # parallel blocks, interleaved rope
    "cohere2": "llm_training_tpu.models.Llama",  # + sliding/full pattern, NoPE full layers
    "code_llama": "llm_training_tpu.models.Llama",  # llama graph verbatim
    "phi": "llm_training_tpu.models.Llama",  # parallel + partial rotary + biases
    "nemotron": "llm_training_tpu.models.Llama",  # layernorm1p + relu^2 MLP
    "ernie4_5": "llm_training_tpu.models.Llama",  # interleaved full-dim rope
    "ernie4_5_moe": "llm_training_tpu.models.Ernie45Moe",  # + aux-free softmax MoE
    "hunyuan_v1_dense": "llm_training_tpu.models.Llama",  # post-rope qk-norm
    "hunyuan_v1_moe": "llm_training_tpu.models.HunYuanMoe",  # + softmax top-k MoE
    "gpt2": "llm_training_tpu.models.Llama",  # learned positions, fused qkv
    "gpt_neox": "llm_training_tpu.models.Llama",  # Pythia: two-norm parallel, interleaved fused qkv
    "smollm3": "llm_training_tpu.models.Llama",  # per-layer NoPE
    "exaone4": "llm_training_tpu.models.Llama",  # post-norm + head qk-norm + hybrid NoPE
    "apertus": "llm_training_tpu.models.Llama",  # non-gated xIELU MLP + head qk-norm
    "glm": "llm_training_tpu.models.Llama",  # interleaved partial rope, fused gate_up
    "glm4": "llm_training_tpu.models.Llama",  # + sandwich norms
    "glm4_moe": "llm_training_tpu.models.Glm4Moe",  # GLM-4.5: V3-style noaux MoE
    "dots1": "llm_training_tpu.models.Glm4Moe",  # + full rotary, qk-norm, sliding pattern
    "deepseek_v2": "llm_training_tpu.models.Deepseek",  # MLA + grouped MoE
    "deepseek_v3": "llm_training_tpu.models.Deepseek",  # + sigmoid noaux routing
    "kimi_k2": "llm_training_tpu.models.Deepseek",  # Kimi-K2: V3 graph verbatim
    "pangu_ultra_moe": "llm_training_tpu.models.Deepseek",  # openPangu: V3 without groups + sandwich norms + MTP
    "gpt_oss": "llm_training_tpu.models.GptOss",  # sink attention + clamped-swiglu MoE
    "qwen3_next": "llm_training_tpu.models.Qwen3Next",  # hybrid gated DeltaNet
    "solar_open2": "llm_training_tpu.models.SolarOpen2",  # KDA + gated NoPE GQA, config only
    "longcat_flash": "llm_training_tpu.models.LongcatFlash",  # MLA double layers + zero-compute experts, config only
    "afmoe": "llm_training_tpu.models.Afmoe",  # window and full layers in two page groups, config only
    "olmo_hybrid": "llm_training_tpu.models.OlmoHybrid",  # gated delta rule (96 x 192 state) + NoPE MHA, config only
    "phi4flash": "llm_training_tpu.models.Phi4Flash",  # Mamba-1 + differential window / full / cross attention + gated memory units, config only
    "gigachat3_5": "llm_training_tpu.models.GigaChat35",  # MLA (gated) on one layer in four + gated delta rule, DeepSeek-V3 experts, config only
    "minimax": "llm_training_tpu.models.MiniMax",  # hybrid lightning attention
    "bamba": "llm_training_tpu.models.Bamba",  # Mamba-2 SSD + attention hybrid
    # sparse MoE variants: stacked-expert MoEMLP block (models/moe.py)
    "mixtral": "llm_training_tpu.models.Llama",
    "phimoe": "llm_training_tpu.models.Llama",  # Phi-3.5-MoE: SparseMixer routing + biased LN
    "granitemoe": "llm_training_tpu.models.Llama",  # granite multipliers + fused-stack MoE
    "granitemoeshared": "llm_training_tpu.models.Llama",  # + always-on shared MLP
    "qwen2_moe": "llm_training_tpu.models.Llama",
    "qwen3_moe": "llm_training_tpu.models.Llama",
    "olmoe": "llm_training_tpu.models.Llama",  # full qk-norm + qwen-style MoE
    "flex_olmo": "llm_training_tpu.models.Llama",  # OLMoE MoE under olmo2 post-norm
    "phi3": "llm_training_tpu.models.Phi3",
    "gemma": "llm_training_tpu.models.Gemma",
    "gemma2": "llm_training_tpu.models.Gemma",  # version=2 graph features
    "gemma3_text": "llm_training_tpu.models.Gemma",  # version=3 graph features
}


def model_class_for_hf(hf_config: dict, assume_llama_layout: bool = False) -> str:
    """HF `config.json` -> our model class path (the `HFCausalLM` analogue,
    reference `models/hf_causal_lm/hf_causal_lm.py:22`, for architectures
    whose computation graph one of our TPU modules reproduces).

    `assume_llama_layout=True` routes UNKNOWN model_types to the Llama
    family: many fine-tune forks only rename a llama-graph architecture, and
    the llama conversion fails loudly on any state-dict key or hparam it
    does not recognize, so a wrong assumption cannot load silently."""
    model_type = hf_config.get("model_type")
    if model_type not in _ARCH_TO_FAMILY:
        if assume_llama_layout:
            logger.warning(
                "unknown HF model_type %r routed to the Llama family "
                "(assume_llama_layout=True): correctness depends on the "
                "checkpoint really using the llama graph/key layout",
                model_type,
            )
            return "llm_training_tpu.models.Llama"
        raise ValueError(
            f"unsupported HF model_type {model_type!r}; supported: "
            f"{sorted(_ARCH_TO_FAMILY)}. If the architecture is a renamed "
            "llama-layout fork, set assume_llama_layout=true on HFCausalLM"
        )
    return _ARCH_TO_FAMILY[model_type]
