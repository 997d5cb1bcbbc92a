"""shardcheck: abstract-eval sharding, layout, and HBM-fit audit.

`python -m llm_training_tpu.analysis --audit` runs `jax.eval_shape` over
every registered model family's init — zero FLOPs, CPU-only, no devices —
to get the REAL param / optimizer-state / KV-cache shape trees with their
logical-axis metadata, then resolves them through the rule table
(`parallel/sharding.py`) against a matrix of mesh configurations (the
full data/pipe/fsdp/expert/tensor/sequence axis space, including the
multichip-dryrun 8-device shapes). It is the regression gate under which
the declarative rule table (`parallel/sharding.py`) may change: a change
must keep every family × mesh cell green.

Finding types (all prefixed `shard-`; docs/static-analysis.md#audit):

  shard-unknown-axis    a logical-axis name no rule knows — the class
                        `logical_to_spec` used to swallow by silently
                        replicating the tensor on every chip
  shard-duplicate-drop  a mesh axis silently dropped because an earlier
                        dim of the same tensor consumed it
  shard-indivisible     a sharded dim that does not divide its mesh-axis
                        product (ragged shards pad on every chip)
  shard-replicated      a tensor above the size threshold resolving to
                        fully-replicated on a mesh that has param-capable
                        axes to offer
  shard-hbm-budget      the per-chip estimate (params + Adam state +
                        activations proxy + KV cache) exceeds the stated
                        chip budget
  shard-audit-error     a family whose init could not be abstract-evaled
                        (never baselinable — fix it)

Unlike the AST rules this module DOES import jax (lazily, inside
`run_audit`) — the CLI only loads it under `--audit`, so the plain lint
gate stays jax-free and millisecond-cheap.

NOTE: the audit evaluates the IMPORTED `llm_training_tpu` package (it
calls the real model inits), so it must run with the tree under test on
sys.path — `--root` only relocates the baseline file. To audit a scratch
copy, run with cwd (or PYTHONPATH) inside that copy, as the precommit
gate and the seeded-typo acceptance test do.
"""

from __future__ import annotations

import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Iterable

from llm_training_tpu.analysis import hbm_budget
from llm_training_tpu.analysis.engine import Finding

# the audit has its own baseline (same schema + update workflow as the lint
# baseline, `engine.load_baseline`/`write_baseline`): audit findings carry
# no source line, so inline `# lint: allow` suppressions do not apply —
# grandfathering goes through this file only
DEFAULT_AUDIT_BASELINE = "config/audit_baseline.json"
# a family whose init cannot even be abstract-evaled must be fixed, not
# grandfathered (mirrors engine.NON_BASELINABLE_RULES)
AUDIT_NON_BASELINABLE = ("shard-audit-error",)

# ------------------------------------------------------------ the matrix
#
# Every entry is an 8-device shape (the CPU test harness' virtual mesh and
# the dryrun topology both use 8): unset axes are 1. The three dryrun_*
# entries reproduce `__graft_entry__.dryrun_multichip(8)`'s real fits.
MESH_MATRIX: dict[str, dict[str, int]] = {
    "fsdp8": {"fsdp": 8},
    "data8": {"data": 8},
    "data2_fsdp4": {"data": 2, "fsdp": 4},
    "dryrun_fsdp2_tp2_sp2": {"fsdp": 2, "tensor": 2, "sequence": 2},
    "dryrun_fsdp2_ep2_tp2": {"fsdp": 2, "expert": 2, "tensor": 2},
    "dryrun_pipe2_fsdp2_tp2": {"pipe": 2, "fsdp": 2, "tensor": 2},
}

# mesh axes that can hold parameter shards; a large tensor replicating on a
# mesh where all of these are 1 (pure DP) is the expected posture, not a
# finding
PARAM_CAPABLE_AXES = ("fsdp", "tensor", "expert", "pipe")
# mesh axes the 'batch' logical axis shards over (activations proxy)
BATCH_AXES = ("data", "fsdp", "expert")


# ------------------------------------------------------------ the families
@dataclass(frozen=True)
class FamilySpec:
    """One registered family: tiny-but-representative hyperparameters whose
    dims keep the proportions that matter for layout (dims divisible by the
    matrix's 2/4/8-way axes exactly where the real checkpoints are)."""

    name: str
    module: str  # python module holding the model + config classes
    model_class: str
    source: str  # repo-relative file findings attach to
    config: dict = field(default_factory=dict)
    batch: int = 1  # sample batch width for init (pipeline needs >= stages)
    seq: int = 16

    def build(self):
        """The family's module at this spec's config (`model.config`)."""
        import importlib

        module = importlib.import_module(self.module)
        config = getattr(module, self.model_class + "Config")(**self.config)
        return getattr(module, self.model_class)(config)


def _llama_tiny(**extra) -> dict:
    base = dict(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64,
    )
    base.update(extra)
    return base


FAMILY_REGISTRY: tuple[FamilySpec, ...] = (
    FamilySpec(
        "llama", "llm_training_tpu.models.llama", "Llama",
        "llm_training_tpu/models/llama/model.py", _llama_tiny(),
    ),
    FamilySpec(
        "llama_moe", "llm_training_tpu.models.llama", "Llama",
        "llm_training_tpu/models/llama/model.py",
        _llama_tiny(num_experts=4, num_experts_per_tok=2,
                    moe_intermediate_size=32),
    ),
    FamilySpec(
        "llama_pp", "llm_training_tpu.models.llama", "Llama",
        "llm_training_tpu/models/pipeline.py",
        _llama_tiny(pipeline_stages=2), batch=2,
    ),
    FamilySpec(
        "phi3", "llm_training_tpu.models.phi3", "Phi3",
        "llm_training_tpu/models/phi3/model.py",
        dict(vocab_size=160, hidden_size=64, intermediate_size=96,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, max_position_embeddings=64),
    ),
    FamilySpec(
        "gemma", "llm_training_tpu.models.gemma", "Gemma",
        "llm_training_tpu/models/gemma/model.py",
        dict(version=2, vocab_size=128, hidden_size=64,
             intermediate_size=112, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             max_position_embeddings=64, query_pre_attn_scalar=24,
             attn_logit_softcapping=50.0, final_logit_softcapping=30.0,
             sliding_window=8),
    ),
    FamilySpec(
        "bamba", "llm_training_tpu.models.bamba", "Bamba",
        "llm_training_tpu/models/bamba/model.py",
        dict(vocab_size=128, hidden_size=32, intermediate_size=64,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, max_position_embeddings=128,
             attn_layer_indices=[1], mamba_n_heads=8, mamba_d_head=8,
             mamba_n_groups=2, mamba_d_state=16, mamba_expand=2,
             mamba_d_conv=4, mamba_chunk_size=8),
    ),
    FamilySpec(
        "deepseek", "llm_training_tpu.models.deepseek", "Deepseek",
        "llm_training_tpu/models/deepseek/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             moe_intermediate_size=48, num_hidden_layers=2,
             num_attention_heads=4, max_position_embeddings=64,
             q_lora_rank=24, kv_lora_rank=32, qk_rope_head_dim=16,
             qk_nope_head_dim=32, v_head_dim=32, n_routed_experts=8,
             n_shared_experts=2, num_experts_per_tok=2,
             first_k_dense_replace=1, n_group=4, topk_group=2),
    ),
    FamilySpec(
        "ernie45_moe", "llm_training_tpu.models.ernie45_moe", "Ernie45Moe",
        "llm_training_tpu/models/ernie45_moe/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             moe_intermediate_size=32, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             max_position_embeddings=64, moe_num_experts=8, moe_k=2,
             moe_num_shared_experts=1, moe_layer_start_index=1,
             use_bias=True, tie_word_embeddings=True),
    ),
    FamilySpec(
        "glm4_moe", "llm_training_tpu.models.glm4_moe", "Glm4Moe",
        "llm_training_tpu/models/glm4_moe/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             moe_intermediate_size=32, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             max_position_embeddings=64, n_routed_experts=8,
             n_shared_experts=1, num_experts_per_tok=2,
             first_k_dense_replace=1, n_group=4, topk_group=2,
             routed_scaling_factor=1.5),
    ),
    FamilySpec(
        "gpt_oss", "llm_training_tpu.models.gpt_oss", "GptOss",
        "llm_training_tpu/models/gpt_oss/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=48,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16,
             max_position_embeddings=64, sliding_window=8,
             num_local_experts=4, num_experts_per_tok=2),
    ),
    FamilySpec(
        "hunyuan_moe", "llm_training_tpu.models.hunyuan_moe", "HunYuanMoe",
        "llm_training_tpu/models/hunyuan_moe/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=48,
             num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16,
             max_position_embeddings=64, num_experts=4, moe_topk=2),
    ),
    FamilySpec(
        "minimax", "llm_training_tpu.models.minimax", "MiniMax",
        "llm_training_tpu/models/minimax/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=48,
             moe_intermediate_size=48, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             max_position_embeddings=128, block_size=16,
             layer_types=["linear_attention", "full_attention",
                          "linear_attention", "full_attention"],
             num_experts=4, num_experts_per_tok=2,
             linear_attn_alpha_factor=1.0, linear_attn_beta_factor=1.0),
    ),
    FamilySpec(
        "qwen3_next", "llm_training_tpu.models.qwen3_next", "Qwen3Next",
        "llm_training_tpu/models/qwen3_next/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             num_hidden_layers=4, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16,
             max_position_embeddings=128, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=16,
             linear_value_head_dim=16, num_experts=4,
             num_experts_per_tok=2, moe_intermediate_size=32,
             shared_expert_intermediate_size=48),
    ),
    FamilySpec(
        "solar_open2", "llm_training_tpu.models.solar_open2", "SolarOpen2",
        "llm_training_tpu/models/solar_open2/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             moe_intermediate_size=32, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             linear_num_heads=4, linear_head_dim=16,
             max_position_embeddings=128, n_routed_experts=8,
             num_experts_per_tok=2, n_shared_experts=1),
    ),
    FamilySpec(
        "longcat_flash", "llm_training_tpu.models.longcat_flash", "LongcatFlash",
        "llm_training_tpu/models/longcat_flash/model.py",
        dict(vocab_size=128, hidden_size=64, ffn_hidden_size=112,
             expert_ffn_hidden_size=32, num_layers=2, num_attention_heads=4,
             kv_lora_rank=32, q_lora_rank=48, qk_rope_head_dim=8,
             qk_nope_head_dim=16, v_head_dim=16, n_routed_experts=8,
             zero_expert_num=4, moe_topk=3, max_position_embeddings=128),
    ),
    FamilySpec(
        "afmoe", "llm_training_tpu.models.afmoe", "Afmoe",
        "llm_training_tpu/models/afmoe/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             moe_intermediate_size=32, num_hidden_layers=8, num_dense_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             sliding_window=16, num_experts=8, num_experts_per_tok=2,
             max_position_embeddings=128),
    ),
    FamilySpec(
        "olmo_hybrid", "llm_training_tpu.models.olmo_hybrid", "OlmoHybrid",
        "llm_training_tpu/models/olmo_hybrid/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
             linear_num_key_heads=4, linear_num_value_heads=4,
             linear_key_head_dim=8, linear_value_head_dim=16,
             max_position_embeddings=128),
    ),
    FamilySpec(
        "phi4flash", "llm_training_tpu.models.phi4flash", "Phi4Flash",
        "llm_training_tpu/models/phi4flash/model.py",
        dict(vocab_size=128, hidden_size=128, intermediate_size=112,
             num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
             sliding_window=16, max_position_embeddings=128),
    ),
    FamilySpec(
        "gigachat3_5", "llm_training_tpu.models.gigachat35", "GigaChat35",
        "llm_training_tpu/models/gigachat35/model.py",
        dict(vocab_size=128, hidden_size=64, intermediate_size=112,
             moe_intermediate_size=32, num_hidden_layers=5, first_k_dense_replace=1,
             full_attention_layers=[1], num_attention_heads=4, q_lora_rank=32,
             kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=8, num_experts_per_tok=2, linear_num_key_heads=2,
             linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=16,
             num_nextn_predict_layers=0, rope_scaling=None, max_position_embeddings=128),
    ),
)


@dataclass
class AuditConfig:
    families: tuple[str, ...] | None = None  # None = all registered
    meshes: tuple[str, ...] | None = None  # None = the full matrix
    hbm_budget_gib: float = 32.0
    replicated_threshold_mib: float = 4.0
    # training-shape proxies for the activation estimate and KV cache
    train_batch: int = 8
    decode_batch: int = 8


@dataclass
class AuditResult:
    findings: list[Finding]
    baselined: list[Finding]
    estimates: dict[str, Any]
    elapsed_s: float
    families_run: tuple[str, ...] = ()
    meshes_run: tuple[str, ...] = ()


@dataclass(frozen=True)
class _Leaf:
    """One audited tensor: a Partitioned param leaf or a cache proxy."""

    path: str
    names: tuple[str | None, ...]
    shape: tuple[int, ...]
    itemsize: int
    kind: str  # "param" | "kv"


def _select(
    requested: tuple[str, ...] | None, known: Iterable[str], what: str
) -> tuple[str, ...]:
    known = tuple(known)
    if requested is None:
        return known
    unknown = sorted(set(requested) - set(known))
    if unknown:
        raise ValueError(f"unknown {what}(s) {unknown}; known: {sorted(known)}")
    return tuple(name for name in known if name in set(requested))


def _family_leaves(spec: FamilySpec) -> tuple[list[_Leaf], int, Any]:
    """(audited leaves, abstract opt-state bytes BEFORE sharding is known,
    model config). jax/flax/optax imports live here — `--audit` is the only
    CLI path that pays them."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import optax

    model = spec.build()
    config = model.config

    sample = jax.ShapeDtypeStruct((spec.batch, spec.seq), jnp.int32)
    variables = jax.eval_shape(model.init, jax.random.key(0), sample)

    def boxed(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: isinstance(x, nn.Partitioned)
        )
        return flat

    leaves: list[_Leaf] = []
    for path, leaf in boxed(variables):
        if not isinstance(leaf, nn.Partitioned):
            # un-annotated leaves shard as replicated scalars in the trainer;
            # surface them through the unknown-axis path only if they are
            # real arrays (none exist today — every param carries names)
            continue
        leaves.append(
            _Leaf(
                path=jax.tree_util.keystr(path),
                names=tuple(leaf.names),
                shape=tuple(leaf.value.shape),
                itemsize=leaf.value.dtype.itemsize,
                kind="param",
            )
        )

    # the REAL abstract optimizer state, exactly like Trainer._abstract_state:
    # optax maps zeros_like through the Partitioned boxes, so mu/nu inherit
    # the param specs — per-chip opt bytes therefore scale with the params'
    # resolved sharding (2x for Adam) plus replicated scalars
    opt_state = jax.eval_shape(lambda v: optax.adam(1e-3).init(v), variables)
    boxed_param_bytes = sum(
        hbm_budget.global_bytes(l.shape, l.itemsize) for l in leaves
    )
    opt_scalar_bytes = 0
    opt_boxed_bytes = 0
    for path, leaf in boxed(opt_state):
        if isinstance(leaf, nn.Partitioned):
            opt_boxed_bytes += hbm_budget.global_bytes(
                tuple(leaf.value.shape), leaf.value.dtype.itemsize
            )
        elif hasattr(leaf, "shape"):
            opt_scalar_bytes += hbm_budget.global_bytes(
                tuple(leaf.shape), leaf.dtype.itemsize
            )
    # sanity-pin the "opt shards like params" assumption the per-mesh loop
    # leans on (2 x params per chip): Adam's boxed mu/nu must be exactly two
    # copies of the boxed params
    if opt_boxed_bytes != 2 * boxed_param_bytes:
        raise RuntimeError(
            f"{spec.name}: abstract opt state is {opt_boxed_bytes} boxed "
            f"bytes, expected exactly 2x the {boxed_param_bytes} param "
            "bytes — the audit's Adam-state model no longer matches the "
            "optimizer; update shard_audit's opt accounting"
        )

    # the caches a decoding family declares (`BaseModelConfig.cache_specs`),
    # under infer/cache's layouts: the key/value buffers, k and v both (or the
    # one buffer of latent rows), and a linear-attention stack's slab. The batch (= slots) dimension is a
    # placeholder; run_audit fills it from AuditConfig. A family that
    # declares none has none to audit
    declared = config.cache_specs()
    if declared is not None:
        import numpy as np

        from llm_training_tpu.infer.cache import (
            CONV_LOGICAL_AXES,
            STATE_LOGICAL_AXES,
            dense_cache_axes,
            kv_groups,
            slab_shapes,
            token_rows,
        )

        _, recurrent = declared
        buffers, layers, heads, width = token_rows(config)
        kv_full = (layers, 0, spec.config.get("max_position_embeddings", 64), heads, width)
        itemsize = np.dtype(config.param_jnp_dtype).itemsize
        caches = [
            (f"<kv-cache {name}>", dense_cache_axes(config), kv_full, itemsize)
            for name in ("k", "v")[:buffers]
        ]
        if (window := kv_groups(config)[1]) is not None:
            # the layers that keep a window: a second group of the same buffers
            caches += [
                (f"<kv-cache window {name}>", dense_cache_axes(config),
                 (window.layers, *kv_full[1:]), itemsize)
                for name in ("k", "v")
            ]
        if recurrent is not None:
            state_shape, conv_shape = slab_shapes(recurrent, 0)
            caches += [
                ("<state-slab state>", STATE_LOGICAL_AXES, state_shape, 4),
                ("<state-slab conv>", CONV_LOGICAL_AXES, conv_shape, itemsize),
            ]
        leaves += [
            _Leaf(path=path, names=tuple(names), shape=shape, itemsize=size, kind="kv")
            for path, names, shape, size in caches
        ]

    return leaves, opt_scalar_bytes, config


def run_audit(root: Path, config: AuditConfig | None = None) -> AuditResult:
    """The audit core: eval_shape each family once, then resolve the leaf
    trees against every mesh in the matrix. Pure table math per mesh — the
    whole run costs seconds on CPU."""
    from llm_training_tpu.parallel.sharding import resolve_spec
    from llm_training_tpu.trainer.trainer import LOGICAL_AXIS_RULES

    cfg = config or AuditConfig()
    t0 = time.monotonic()
    families = _select(
        cfg.families, (f.name for f in FAMILY_REGISTRY), "family"
    )
    meshes = _select(cfg.meshes, MESH_MATRIX, "mesh")
    registry = {f.name: f for f in FAMILY_REGISTRY}

    budget_bytes = int(cfg.hbm_budget_gib * hbm_budget.GIB)
    threshold_bytes = int(cfg.replicated_threshold_mib * 1024 * 1024)
    rules_table = {name for name, _ in LOGICAL_AXIS_RULES}

    findings: list[Finding] = []
    estimates: dict[str, Any] = {}

    for family_name in families:
        spec = registry[family_name]
        try:
            leaves, opt_scalar_bytes, model_config = _family_leaves(spec)
        except Exception as exc:  # a broken family must not hide the rest
            findings.append(
                Finding(
                    rule="shard-audit-error",
                    path=spec.source,
                    line=1,
                    message=(
                        f"{family_name}: abstract-eval of init failed: "
                        f"{exc.__class__.__name__}: {exc}"
                    ),
                )
            )
            continue

        family_json: dict[str, Any] = {
            "source": spec.source,
            "param_leaves": sum(1 for l in leaves if l.kind == "param"),
            "meshes": {},
        }

        # ---- mesh-independent findings: unknown axes + duplicate drops
        resolved: list[tuple[_Leaf, tuple]] = []
        for leaf in leaves:
            shape = leaf.shape
            if leaf.kind == "kv":
                shape = (shape[0], cfg.decode_batch, *shape[2:])
                leaf = _Leaf(leaf.path, leaf.names, shape, leaf.itemsize, "kv")
            unknown = [
                axis for axis in leaf.names
                if axis is not None and axis not in rules_table
            ]
            if unknown:
                for axis in unknown:
                    findings.append(
                        Finding(
                            rule="shard-unknown-axis",
                            path=spec.source,
                            line=1,
                            message=(
                                f"{family_name}: leaf {leaf.path} uses unknown "
                                f"logical axis '{axis}' — logical_to_spec "
                                "silently REPLICATES this tensor onto every "
                                "chip; affected mesh configs: "
                                # the FULL matrix, not the run's selection: an
                                # unknown axis replicates on every mesh by
                                # construction, and a --meshes-narrowed run
                                # must produce the same baseline key as the
                                # full precommit run
                                f"{', '.join(MESH_MATRIX)} (every mesh in "
                                "the matrix). Fix the typo or register the "
                                "axis in KNOWN_LOGICAL_AXES "
                                "(llm_training_tpu/parallel/sharding.py)."
                            ),
                        )
                    )
            part_spec, drops = resolve_spec(leaf.names, LOGICAL_AXIS_RULES)
            for drop in drops:
                findings.append(
                    Finding(
                        rule="shard-duplicate-drop",
                        path=spec.source,
                        line=1,
                        message=(
                            f"{family_name}: leaf {leaf.path} dim "
                            f"{drop.position} (logical '{drop.axis}') drops "
                            f"duplicate mesh axes {list(drop.mesh_axes)} — an "
                            "earlier dim already consumed them; the dim stays "
                            "wider per chip than the rule table suggests"
                        ),
                    )
                )
            resolved.append((leaf, tuple(part_spec)))

        # ---- per-mesh: divisibility, replication, HBM fit
        indivisible: dict[str, list[str]] = {}  # leaf-message -> meshes
        replicated: dict[str, list[str]] = {}
        for mesh_name in meshes:
            axis_sizes = MESH_MATRIX[mesh_name]
            param_capable = any(
                axis_sizes.get(a, 1) > 1 for a in PARAM_CAPABLE_AXES
            )
            params_bytes = opt_sharded = kv_bytes = 0
            for leaf, part_spec in resolved:
                ways = hbm_budget.shard_ways(part_spec, leaf.shape, axis_sizes)
                chip = hbm_budget.per_chip_bytes(leaf.shape, leaf.itemsize, ways)
                total = hbm_budget.global_bytes(leaf.shape, leaf.itemsize)
                if leaf.kind == "param":
                    params_bytes += chip
                    opt_sharded += 2 * chip  # Adam mu+nu shard like params
                else:
                    kv_bytes += chip
                padded_spec = tuple(part_spec) + (None,) * (
                    len(leaf.shape) - len(part_spec)
                )
                for dim, way, entry in zip(leaf.shape, ways, padded_spec):
                    if way > 1 and dim % way != 0:
                        # the stable part of the message must not mention the
                        # mesh-dependent shard count — baseline keys strip
                        # only the " on mesh(es) ..." suffix
                        key = (
                            f"{family_name}: leaf {leaf.path} dim of size "
                            f"{dim} does not divide its sharding "
                            f"(spec entry {entry!r})"
                        )
                        indivisible.setdefault(key, []).append(
                            f"{mesh_name} ({way}-way)"
                        )
                        break
                if (
                    leaf.kind == "param"
                    and param_capable
                    and total > threshold_bytes
                    and all(way == 1 for way in ways)
                ):
                    key = (
                        f"{family_name}: large tensor {leaf.path} "
                        f"({total / (1024 * 1024):.1f} MiB) resolves to "
                        "fully-replicated despite param-capable mesh axes"
                    )
                    replicated.setdefault(key, []).append(mesh_name)

            batch_ways = 1
            for axis in BATCH_AXES:
                batch_ways *= axis_sizes.get(axis, 1)
            estimate = hbm_budget.HbmEstimate(
                params_bytes=params_bytes,
                opt_state_bytes=opt_sharded + opt_scalar_bytes,
                kv_cache_bytes=kv_bytes,
                activation_bytes=hbm_budget.activation_proxy_bytes(
                    batch=cfg.train_batch,
                    seq=int(getattr(model_config, "max_position_embeddings", 64)),
                    hidden=int(getattr(model_config, "hidden_size", 0)),
                    num_layers=int(getattr(model_config, "num_hidden_layers", 0)),
                    itemsize=2,  # compute_dtype bf16 in every real config
                    batch_ways=batch_ways,
                    seq_ways=axis_sizes.get("sequence", 1),
                ),
            )
            cell = estimate.to_json()
            cell["fits"] = estimate.fits(budget_bytes)
            family_json["meshes"][mesh_name] = cell
            if not estimate.fits(budget_bytes):
                findings.append(
                    Finding(
                        rule="shard-hbm-budget",
                        path=spec.source,
                        line=1,
                        # everything mesh-dependent (the mesh name AND the
                        # per-mesh estimate numbers) lives after the
                        # " on mesh(es) " marker so the baseline key stays
                        # stable across --meshes selections and small
                        # accounting changes
                        message=(
                            f"{family_name}: estimated per-chip HBM exceeds "
                            f"the {cfg.hbm_budget_gib:.1f} GiB budget"
                            f" on mesh(es) {mesh_name} — "
                            f"{estimate.total_bytes / hbm_budget.GIB:.2f} GiB "
                            f"(params {cell['params_gib']} + opt "
                            f"{cell['opt_state_gib']} + kv "
                            f"{cell['kv_cache_gib']} + act "
                            f"{cell['activation_gib']}); cross-check against "
                            "the measured hbm/peak_bytes_in_use gauge"
                        ),
                    )
                )

        for message, mesh_names in indivisible.items():
            findings.append(
                Finding(
                    rule="shard-indivisible",
                    path=spec.source,
                    line=1,
                    message=(
                        f"{message} on mesh(es) {', '.join(mesh_names)}; the "
                        "shard goes ragged and pads on every chip"
                    ),
                )
            )
        for message, mesh_names in replicated.items():
            findings.append(
                Finding(
                    rule="shard-replicated",
                    path=spec.source,
                    line=1,
                    message=f"{message} on mesh(es) {', '.join(mesh_names)}",
                )
            )
        estimates[family_name] = family_json

    return AuditResult(
        findings=findings,
        baselined=[],
        estimates=estimates,
        elapsed_s=time.monotonic() - t0,
        families_run=families,
        meshes_run=meshes,
    )


# shard-indivisible / shard-replicated messages end in a mesh-list suffix
# that depends on which meshes the run audited; baseline keys strip it so a
# --meshes-narrowed `--update-baseline` and the full precommit run agree on
# the key (shard-unknown-axis messages are already mesh-selection-stable —
# they always name the full matrix)
_MESH_SUFFIX = " on mesh(es) "


def _baseline_key(finding: Finding) -> str:
    message = finding.message
    cut = message.find(_MESH_SUFFIX)
    if cut != -1:
        message = message[:cut]
    return f"{finding.rule}::{finding.path}::{message}"


def worst_estimate(estimates: dict[str, Any]) -> tuple[str, str, float] | None:
    """(family, mesh, total_gib) of the largest per-chip estimate."""
    worst: tuple[str, str, float] | None = None
    for family, family_json in estimates.items():
        for mesh, cell in family_json.get("meshes", {}).items():
            total = float(cell.get("total_gib", 0.0))
            if worst is None or total > worst[2]:
                worst = (family, mesh, total)
    return worst


def audit_main(args, root: Path) -> int:
    """`python -m llm_training_tpu.analysis --audit` — same exit codes and
    --json/baseline conventions as the lint gate (engine.main delegates
    here before any rule runs)."""
    from llm_training_tpu.analysis.engine import load_baseline, write_baseline

    baseline_path = args.baseline or (root / DEFAULT_AUDIT_BASELINE)
    baseline_keys = set() if args.no_baseline else load_baseline(baseline_path)
    # unset CLI knobs fall through to AuditConfig's defaults (the engine
    # parses them as None so it can reject audit flags without --audit)
    kwargs: dict[str, Any] = {}
    if args.families is not None:
        kwargs["families"] = tuple(args.families.split(","))
    if args.meshes is not None:
        kwargs["meshes"] = tuple(args.meshes.split(","))
    if args.hbm_budget_gib is not None:
        kwargs["hbm_budget_gib"] = args.hbm_budget_gib
    if args.replicated_threshold_mib is not None:
        kwargs["replicated_threshold_mib"] = args.replicated_threshold_mib
    config = AuditConfig(**kwargs)
    try:
        result = run_audit(root, config)
    except ValueError as exc:
        print(f"shardcheck: {exc}", file=sys.stderr)
        return 2

    active: list[Finding] = []
    baselined: list[Finding] = []
    for finding in sorted(
        result.findings, key=lambda f: (f.path, f.rule, f.message)
    ):
        if (
            finding.rule not in AUDIT_NON_BASELINABLE
            and baseline_keys
            and _baseline_key(finding) in baseline_keys
        ):
            baselined.append(finding)
        else:
            active.append(finding)
    result.findings, result.baselined = active, baselined

    if args.update_baseline:
        keep_keys = {
            _baseline_key(f)
            for f in active + baselined
            if f.rule not in AUDIT_NON_BASELINABLE
        }
        if args.families or args.meshes:
            # a narrowed run cannot see the other cells' findings; their
            # grandfathered entries must survive untouched
            keep_keys |= baseline_keys
        write_baseline(baseline_path, keep_keys)
        print(
            f"shardcheck: audit baseline updated with {len(keep_keys)} "
            f"finding(s) ({len(baselined)} still firing, carried over) at "
            f"{baseline_path}"
        )
        return 0

    if args.json:
        print(
            json.dumps(
                {
                    "version": 1,
                    "mode": "audit",
                    "findings": [
                        {
                            "rule": f.rule,
                            "path": f.path,
                            "line": f.line,
                            "message": f.message,
                            # the baseline key (mesh-list suffix stripped), so
                            # what --json shows is what the baseline stores
                            "key": _baseline_key(f),
                        }
                        for f in active
                    ],
                    "baselined": len(baselined),
                    "families": list(result.families_run),
                    "meshes": list(result.meshes_run),
                    "hbm_budget_gib": config.hbm_budget_gib,
                    "estimates": result.estimates,
                    "elapsed_s": round(result.elapsed_s, 3),
                }
            )
        )
        return 1 if active else 0

    for finding in active:
        print(finding.render())
    status = "FAIL" if active else "OK"
    summary = (
        f"shardcheck: {status} — {len(result.families_run)} family(ies) x "
        f"{len(result.meshes_run)} mesh(es), {len(active)} finding(s) "
        f"({len(baselined)} baselined) in {result.elapsed_s:.2f}s"
    )
    worst = worst_estimate(result.estimates)
    if worst is not None:
        summary += (
            f"; worst per-chip HBM estimate {worst[2]:.3f} GiB "
            f"({worst[0]} @ {worst[1]}, budget {config.hbm_budget_gib:.1f})"
        )
    print(summary)
    if active:
        print(
            "hint: fix the layout drift (docs/static-analysis.md#audit), or "
            "grandfather deliberate debt with --audit --update-baseline "
            f"(baseline: {baseline_path})."
        )
    return 1 if active else 0
