"""Causal language modeling objective.

Capability parity: reference `lms/clm/clm.py:25-188` — label shifting
(`clm.py:137`), fused-linear CE so full logits never materialize
(`clm.py:113-126` via liger; here `ops.fused_linear_cross_entropy`), NEFTune
embedding noise during training (`clm.py:45-82`), and the loss/perplexity/
consumed-counter metrics (`clm.py:84-99,155-167`).

Under tensor parallelism the reference switches to `loss_parallel` with
vocab-sharded logits (`clm.py:113-126`); here the same effect falls out of
GSPMD: the lm_head kernel is vocab-sharded ('vocab' → tensor axis) and the
chunked CE's matmul+logsumexp lower to sharded HLO with a psum — no separate
code path.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from pydantic import ConfigDict

from llm_training_tpu.lms.base import BaseLMConfig, ModelProvider
from llm_training_tpu.ops import fused_linear_cross_entropy, shift_labels


class CLMConfig(BaseLMConfig):
    """Reference `lms/clm/clm_config.py:5-9`."""

    model_config = ConfigDict(extra="forbid")

    model: ModelProvider | None = None
    ignore_index: int = -100
    neftune_alpha: float | None = None
    log_perplexity: bool = True
    ce_chunk_size: int = 1024
    # what the multi-token-prediction loss of a model that has the module
    # (`num_nextn_predict_layers`, models/deepseek) weighs in the total:
    # `loss = CE + mtp_loss_weight * CE_mtp`. 0.3 is DeepSeek-V3's first
    # value (its report lowers it to 0.1 late in training); the module's
    # publishers give none
    mtp_loss_weight: float = 0.3


def _get_path(tree: Any, path: str) -> jnp.ndarray:
    import flax.linen as nn

    node = tree
    for key in path.split("/"):
        node = node[key]
    if isinstance(node, nn.Partitioned):
        node = node.value
    return node


def _get_path_or_none(tree: Any, path: str) -> jnp.ndarray | None:
    try:
        return _get_path(tree, path)
    except KeyError:
        return None


def head_and_bias(model: Any, p: Any) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """(lm-head matrix [embed, vocab], optional bias [vocab]) for the fused
    CE/log-prob objectives. Handles tied embeddings (transposed), explicit
    standalone bias paths (get_output_bias_path — e.g. a bias riding on a
    TIED head), and the Phi-style bias-next-to-kernel convention."""
    head_path = model.get_output_embeddings_path()
    head = _get_path(p, head_path)
    bias_path = getattr(model, "get_output_bias_path", lambda: None)()
    if head_path == model.get_input_embeddings_path():
        head = head.T  # tied embeddings: [vocab, embed] -> [embed, vocab]
        bias = _get_path(p, bias_path) if bias_path else None
    elif bias_path:
        bias = _get_path(p, bias_path)
    else:
        bias = _get_path_or_none(p, head_path.rsplit("/", 1)[0] + "/bias")
    return head, bias


class CLM:
    """The CLM objective as a pure-function bundle.

    `loss_and_metrics` is the jit-traced hot path; everything else is setup.
    """

    def __init__(self, config: CLMConfig, model: Any | None = None):
        self.config = config
        self.model = model if model is not None else config.model.get_model()

    def init_params(self, rng: jax.Array, batch: dict[str, jnp.ndarray]) -> Any:
        return self.model.init(rng, batch["input_ids"][:1])

    def pretrained_source(self) -> str | None:
        from llm_training_tpu.lms.base import resolve_pretrained_source

        return resolve_pretrained_source(self)

    def pretrained_params(self, shardings: Any, dtypes: Any) -> Any:
        from llm_training_tpu.lms.base import load_single_model_pretrained

        return load_single_model_pretrained(self, shardings, dtypes)

    def loss_and_metrics(
        self,
        params: Any,
        batch: dict[str, jnp.ndarray],
        rng: jax.Array | None = None,
        train: bool = True,
        with_health: bool = False,
    ) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
        """batch: input_ids [B,S]; optional labels (pre-shift), segment_ids,
        position_ids. Returns (mean loss fp32, metrics dict).

        `with_health=True` (the trainer's health-step variant,
        docs/observability.md) additionally derives per-MoE-layer router
        health metrics (`health/moe/*`) from the model's `router_stats`;
        the default False path is trace-identical to before the flag
        existed."""
        cfg = self.config
        model = self.model
        input_ids = batch["input_ids"]
        labels = batch.get("labels", input_ids)
        segment_ids = batch.get("segment_ids")
        position_ids = batch.get("position_ids")

        labels = shift_labels(labels, cfg.ignore_index)
        if segment_ids is not None:
            # mask padding AND packed-document boundaries: after the shift,
            # position i's label must belong to the same document (the
            # reference gets this via BOS masking in its collators,
            # pre_training_datacollator.py:32-46; doing it here makes the
            # no-cross-contamination guarantee independent of the collator)
            next_seg = jnp.concatenate(
                [segment_ids[:, 1:], jnp.zeros_like(segment_ids[:, :1])], axis=1
            )
            valid = (segment_ids > 0) & (segment_ids == next_seg)
            labels = jnp.where(valid, labels, cfg.ignore_index)

        # a model with multi-token-prediction modules: through module k (the
        # first is 0) position i also predicts the token at i + k + 2, where
        # that lies in its own document
        with_mtp = getattr(model.config, "num_nextn_predict_layers", 0)
        mtp_labels = []
        for k in range(with_mtp):
            ahead_labels = shift_labels(mtp_labels[-1] if mtp_labels else labels, cfg.ignore_index)
            if segment_ids is not None:
                ahead = jnp.concatenate(
                    [segment_ids[:, k + 2:], jnp.zeros_like(segment_ids[:, :k + 2])], axis=1
                )
                ahead_labels = jnp.where(
                    (segment_ids > 0) & (segment_ids == ahead), ahead_labels, cfg.ignore_index
                )
            mtp_labels.append(ahead_labels)

        p = params["params"] if "params" in params else params

        inputs_embeds = None
        if train and cfg.neftune_alpha:
            # NEFTune (reference clm.py:45-82): uniform noise on the input
            # embeddings, scale alpha / sqrt(tokens * dim).
            embed_table = _get_path(p, model.get_input_embeddings_path())
            inputs_embeds = embed_table[input_ids].astype(model.config.compute_jnp_dtype)
            tokens = input_ids.shape[1]
            dim = inputs_embeds.shape[-1]
            mag = cfg.neftune_alpha / math.sqrt(tokens * dim)
            noise = jax.random.uniform(
                rng, inputs_embeds.shape, dtype=inputs_embeds.dtype, minval=-mag, maxval=mag
            )
            inputs_embeds = inputs_embeds + noise

        out = model.apply(
            params,
            # the module embeds the tokens itself, noise or no noise
            input_ids=None if inputs_embeds is not None and not with_mtp else input_ids,
            segment_ids=segment_ids,
            position_ids=position_ids,
            inputs_embeds=inputs_embeds,
            compute_logits=False,
            return_last_hidden_states=True,
            **({"return_mtp": True} if with_mtp else {}),
        )
        head, head_bias = head_and_bias(model, p)

        def token_loss(hidden, targets):
            total, count = fused_linear_cross_entropy(
                hidden,
                head.astype(hidden.dtype),
                targets,
                ignore_index=cfg.ignore_index,
                chunk_size=cfg.ce_chunk_size,
                bias=head_bias,
                # Gemma-2 caps the final logits; the fused path must apply the
                # same cap or training loss diverges from the compute_logits path
                logits_soft_cap=getattr(model.config, "final_logit_softcapping", None),
            )
            return total / jnp.maximum(count, 1).astype(jnp.float32), count

        loss, count = token_loss(out.last_hidden_states, labels)

        metrics = {
            "loss": loss,
            "target_tokens": count,
        }
        if self.config.log_perplexity:
            # exp of the TOKEN-LEVEL cross entropy only — never the MoE
            # balancing penalty, so curves stay comparable to dense/HF evals
            metrics["perplexity"] = jnp.exp(loss)
        if with_mtp:
            # (its ops land under `loss_ce` beside the main loss's: the fused
            # cross entropy is a custom_vjp, whose ops keep no outer scope; the
            # module itself runs under `mtp`, docs/observability.md)
            # one module's states, or a tuple of the chained modules': their mean
            mtp_hidden = out.mtp_hidden_states
            if not isinstance(mtp_hidden, (tuple, list)):
                mtp_hidden = (mtp_hidden,)
            mtp_loss = sum(
                token_loss(h, targets)[0] for h, targets in zip(mtp_hidden, mtp_labels, strict=True)
            ) / len(mtp_hidden)
            metrics["mtp_loss"] = mtp_loss
            loss = loss + cfg.mtp_loss_weight * mtp_loss
            metrics["loss"] = loss
        if out.aux_loss is not None:
            # MoE load-balancing loss (HF load_balancing_loss_func analogue):
            # the model returns it unscaled; the coefficient lives in the
            # model config (mixtral/qwen-moe: router_aux_loss_coef)
            coef = getattr(model.config, "router_aux_loss_coef", 0.0)
            metrics["aux_loss"] = out.aux_loss
            loss = loss + coef * out.aux_loss
            metrics["loss"] = loss
        if out.ep_dropped_rows is not None:
            # (token, expert) assignments lost to the expert-parallel
            # capacity buffer this step (0 when ep=1 / routing fits): the
            # drop-rate signal for tuning ep_capacity_factor
            metrics["ep_dropped_rows"] = out.ep_dropped_rows
        if with_health and out.router_stats is not None:
            from llm_training_tpu.telemetry.health import moe_router_health

            metrics.update(
                moe_router_health(
                    out.router_stats, n_tokens=labels.shape[0] * labels.shape[1]
                )
            )
        return loss, metrics
