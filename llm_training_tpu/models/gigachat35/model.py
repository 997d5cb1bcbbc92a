"""GigaChat 3.5 decoder (`model_type: gigachat3_5`), TPU-native: built from
the blocks the DeepSeek and Olmo-Hybrid families run.

Every layer is `h = x + N2(mixer(N1 x)); y = h + N4(ffn(N3 h))` (`pre_post`),
`N` a zero-centred gated norm, `x / rms(x) * 2 sigmoid(w)`
(`ops/rms_norm.py:gated_rms_norm`); `logits = Head(N_f y)`, the head untied.

- the mixer of a layer in `full_attention_layers` is DeepSeek-V3's latent
  attention (`deepseek.MLAttention`: low-rank q, one latent row a token,
  interleaved rotary on 64 of 192 dimensions under yarn) with an output gate,
  `o_proj(attn * sigmoid(gate_proj N1 x))` (scope `attn_gate`);
- of every other layer Qwen3-Next's gated delta rule
  (`olmo_hybrid.GatedDeltaNet`, the one mixer both families decode with): ONE
  projection and ONE causal convolution of 4 taps over q, k (32 heads x 128)
  and v (64 heads x 128), SiLU; q and k L2-normalised a key head, each key
  head serving two value heads; `beta = sigmoid(W_b x)`, one decay a value
  head; the recurrence on a float32 [128, 128] state a value head; then
  `o_proj(N_o(o) * 2 sigmoid(g_proj x))`;
- the feed-forward is a clamped SwiGLU (`swiglu_limit`) of `intermediate_size`
  on the first `first_k_dense_replace` layers and `DeepseekMoE` (sigmoid
  router, top 8 normalised x 2.5, one ungated shared expert) on the others;
- `num_nextn_predict_layers` modules (`deepseek.MTPModule`) chain after the
  stack: module k reads module k-1's output and the embedding of the token k
  + 1 ahead; each is an MLA layer with a dense SwiGLU. A training loss only.

Decoding (docs/inference.md, docs/serving.md): the two kinds of layer keep two
kinds of cache, declared once by `GigaChat35Config.cache_specs()`: an MLA
layer appends ONE latent row a token to its part of the latent pool (or the
dense buffer) and attends through `LayerCache.attend_latent`; a delta-rule
layer reads and writes its decode slot's slab, one token by the step on the
stored state (the `delta_step` kernel where the slab can be advanced where it
lies), a chunk by the chunked rule. Each layer addresses its own kind by its
own index.

The stack (`GigaChat35Config.scan_plan`): the leading dense layers are looped;
the layers after them scan in whole periods of the mixer pattern ([MLA, delta
rule x 3] as published) with the latent buffer AND the slab in the carry
beside `hidden` and the held experts' stacked weights read where they lie
(`models/cache.py:scan_layers`); fewer layers than a period left at the end
are looped again.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import (
    CausalLMOutput,
    DecodeState,
    PagedDecodeState,
    RouterStats,
)
from llm_training_tpu.models.cache import _slot_rows, close_cache, open_cache, scan_layers
from llm_training_tpu.models.deepseek.model import (
    DeepseekMLP,
    DeepseekMoE,
    MLAttention,
    MTPModule,
)
from llm_training_tpu.models.gigachat35.config import GigaChat35Config
from llm_training_tpu.models.llama.model import _dense
from llm_training_tpu.models.moe import EXPERT_LEAVES, decoding_experts
from llm_training_tpu.models.olmo_hybrid.model import GatedDeltaNet
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.ops.rms_norm import gated_rms_norm
from llm_training_tpu.ops.rope_utils import compute_rope_cos_sin, compute_rope_frequencies


class ZeroCenteredGatedNorm(nn.Module):
    """`x / rms(x) * gating_weight * sigmoid(w)`, `w` learned from 0."""

    eps: float
    gating_weight: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        weight = self.param(
            "weight",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        return gated_rms_norm(x, weight, self.eps, self.gating_weight)


def _norm(cfg: GigaChat35Config, eps: float | None = None):
    """`name -> the family's norm`."""
    return lambda name: ZeroCenteredGatedNorm(
        cfg.rms_norm_eps if eps is None else eps, cfg.layernorm_gating_weight,
        cfg.param_jnp_dtype, name=name,
    )


class GigaChat35DecoderLayer(nn.Module):
    """Returns `(hidden, ys, cache)`, `ys` as `DeepseekDecoderLayer`'s: on a
    layer with experts `(router health triple, a share's assignment counts or
    None)`, None on a dense one. `layer` is this layer's index among the
    stack's layers of its kind: an MLA layer's part of the latent buffer, a
    delta-rule layer's rows of the slab. `stack = (leaves, index)`: what a
    decoding layer's experts are read from (`models/moe.py:decoding_experts`)."""

    config: GigaChat35Config
    is_full: bool
    is_moe: bool

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin, cache=None, layer=None, stack=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = _norm(cfg)
        normed = norm("input_layernorm")(hidden)
        if self.is_full:
            mixed, cache = MLAttention(
                cfg, scale=cfg.attention_scale, interleaved=cfg.rope_interleave,
                gated=cfg.gated_attention, name="self_attn",
            )(normed, segment_ids, cos, sin, cache, layer)
        else:
            rows = None
            if cache is not None:
                # `_slot_rows` by this module's name: where the benchmark's
                # tests plant their fault (`LayerCache.recurrent_rows`)
                # one token a slot: the state is advanced where it lies
                one_token = hidden.shape[1] == 1
                rows = cache.recurrent_rows(
                    layer, _slot_rows, in_place=one_token, delta_step=one_token
                )
            scale = cfg.linear_sigmoid_gate_scale
            mixed, rows = GatedDeltaNet(
                cfg, joint=True, beta_max=1.0, out_norm=_norm(cfg, cfg.linear_attn_o_norm_eps),
                out_gate=lambda gate: scale * jax.nn.sigmoid(gate), name="linear_attn",
            )(normed, segment_ids, rows)
            if rows is not None:
                # the write belongs to the recurrence's scope: in a decode step
                # the state's update fuses into it (`olmo_hybrid/model.py`)
                with jax.named_scope("linear_attn/" + ("gdn_recurrence" if one_token else "gdn_chunk")):
                    cache = cache.put_recurrent_rows(layer, rows, in_place=one_token)
        hidden = hidden + norm("post_attention_layernorm")(mixed)
        normed = norm("pre_mlp_layernorm")(hidden)
        ys = None
        if self.is_moe:
            pad_mask = None if segment_ids is None else segment_ids > 0
            counts = None
            # a looped decoding layer: its own experts, a stack of one
            experts = decoding_experts(cache, None, 0) if stack is None else stack
            if cfg.counts_expert_assignments:
                mlp_out, stats, counts = DeepseekMoE(cfg, True, name="mlp")(normed, pad_mask, experts)
            else:
                mlp_out, stats = DeepseekMoE(cfg, name="mlp")(normed, pad_mask, experts)
            ys = (stats, counts)
        else:
            mlp_out = DeepseekMLP(cfg, cfg.intermediate_size, name="mlp")(normed)
        return hidden + norm("post_mlp_layernorm")(mlp_out), ys, cache


class _PeriodBody(nn.Module):
    """Scan body: one period of the mixer pattern, every layer with experts.
    The carry is `hidden` or, when decoding, `(hidden, the cache's buffers)`:
    the latent buffer with a leading axis over ALL the stack's MLA layers, the
    slab over all its delta-rule layers. `first = (delta-rule layers, MLA
    layers)` before the scanned part, `cycle` which period this is
    (`models/cache.py:scan_layers`); `stack` holds the periods' expert leaves
    whole."""

    config: GigaChat35Config
    kinds: tuple[bool, ...]
    first: tuple[int, int]

    @nn.compact
    def __call__(self, carry, segment_ids, cos, sin, cache=None, cycle=None, stack=None):
        cfg = self.config
        hidden = carry
        if cache is not None:
            hidden, buffers = carry
            cache = cache.holding(buffers)
        ys = []
        for j, is_full in enumerate(self.kinds):
            # this layer's index among the stack's layers of its kind
            index = None if cache is None else (
                self.first[is_full] + cycle * self.kinds.count(is_full)
                + self.kinds[:j].count(is_full)
            )
            hidden, layer_ys, cache = GigaChat35DecoderLayer(cfg, is_full, True, name=f"slot{j}")(
                hidden, segment_ids, cos, sin, cache, index,
                decoding_experts(cache, stack, cycle, f"slot{j}", "mlp"),
            )
            ys.append(layer_ys)
        ys = jax.tree.map(lambda *leaves: jnp.stack(leaves), *ys)
        return (hidden if cache is None else (hidden, cache.buffers)), ys


class GigaChat35(nn.Module):
    """GigaChat 3.5 causal LM with the `CausalLMProto` surface, decoding
    through `decode_state` (dense or paged) like the Llama stack."""

    config: GigaChat35Config

    def _layers(self, hidden, segment_ids, cos, sin, cache):
        """-> (hidden, [(router stats, counts)] of the layers with experts in
        layer order, each stacked over the layers it covers, their layer ids,
        cache)."""
        cfg = self.config
        kinds = cfg.layer_kinds
        prefix, period, periods = cfg.scan_plan
        policy = _remat_policy(cfg)
        layer_cls = GigaChat35DecoderLayer
        if policy is not None:
            layer_cls = nn.remat(GigaChat35DecoderLayer, policy=policy)
        ys, moe_ids = [], []

        def looped(i, hidden, cache):
            is_moe = cfg.layer_is_moe(i)
            hidden, layer_ys, cache = layer_cls(cfg, kinds[i], is_moe, name=f"layers_{i}")(
                hidden, segment_ids, cos, sin, cache, kinds[:i].count(kinds[i])
            )
            if is_moe:
                ys.append(jax.tree.map(lambda leaf: leaf[None], layer_ys))
                moe_ids.append(i)
            return hidden, cache

        for i in range(prefix):
            hidden, cache = looped(i, hidden, cache)
        end = prefix + period * periods
        if periods:
            body = _PeriodBody
            if policy is not None:
                body = nn.remat(_PeriodBody, policy=policy, prevent_cse=False)
            first = (kinds[:prefix].count(False), kinds[:prefix].count(True))
            hidden, scanned, cache = scan_layers(
                body, (cfg, tuple(kinds[prefix:prefix + period]), first), periods,
                hidden, (segment_ids, cos, sin), cache, whole=EXPERT_LEAVES, name="periods",
            )
            # [periods, period, ...] -> [layers, ...]
            ys.append(jax.tree.map(lambda leaf: leaf.reshape(-1, *leaf.shape[2:]), scanned))
            moe_ids.extend(range(prefix, end))
        for i in range(end, cfg.num_hidden_layers):
            hidden, cache = looped(i, hidden, cache)
        return hidden, ys, moe_ids, cache

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,
        inputs_embeds: jnp.ndarray | None = None,
        compute_logits: bool = True,
        return_last_hidden_states: bool = False,
        decode_state: DecodeState | PagedDecodeState | None = None,
        return_mtp: bool = False,
    ) -> CausalLMOutput:
        """`return_mtp` (a config with `num_nextn_predict_layers`; needs
        `input_ids`): the multi-token-prediction modules run too, chained, and
        their final-normed hidden states come back as `mtp_hidden_states`, a
        tuple, one a module (module k's row i is for the token at i + k + 2),
        with `mtp_logits` beside them under `compute_logits`. No other call
        runs them."""
        cfg = self.config
        if return_mtp and not cfg.num_nextn_predict_layers:
            raise ValueError("return_mtp needs a config with num_nextn_predict_layers")
        embed_tokens = nn.Embed(
            num_embeddings=cfg.vocab_size,
            features=cfg.hidden_size,
            dtype=cfg.compute_jnp_dtype,
            param_dtype=cfg.param_jnp_dtype,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), ("vocab", "embed")
            ),
            name="embed_tokens",
        )
        if inputs_embeds is None:
            if input_ids is None:
                raise ValueError("one of input_ids / inputs_embeds is required")
            inputs_embeds = embed_tokens(input_ids)
        hidden = inputs_embeds
        batch, seq = hidden.shape[:2]

        if position_ids is None:
            if decode_state is not None:
                raise ValueError("decoding needs position_ids: a chunk's place in its row")
            position_ids = jnp.arange(seq)[None, :]
        # a cache sets the length the rotary tables are chosen for, not the chunk in hand
        table_length = seq if decode_state is None else decode_state.table_length
        inv_freq, attention_scaling = compute_rope_frequencies(
            cfg.rope_config, seq_len=table_length
        )
        cos, sin = compute_rope_cos_sin(inv_freq, position_ids, attention_scaling)
        if cfg.rope_interleave:
            half = cos.shape[-1] // 2
            cos = jnp.repeat(cos[..., :half], 2, axis=-1)
            sin = jnp.repeat(sin[..., :half], 2, axis=-1)

        cache, segment_ids = open_cache(decode_state, segment_ids, batch, seq)
        hidden, ys, moe_ids, cache = self._layers(hidden, segment_ids, cos, sin, cache)
        new_decode_state = close_cache(cache, decode_state, segment_ids)
        stats, counts = jax.tree.map(lambda *leaves: jnp.concatenate(leaves), *ys)
        sel_frac, mean_prob, dropped = stats
        ep_dropped = dropped.sum()

        final_norm = _norm(cfg)("norm")
        mtp_hidden = None
        # `init` makes the modules' parameters whatever it was asked to return
        if return_mtp or (self.is_initializing() and cfg.num_nextn_predict_layers):
            if input_ids is None or decode_state is not None:
                raise ValueError("the MTP modules read input_ids, and are no part of decoding")
            mtp_hidden, carried = [], hidden
            block = lambda name: GigaChat35DecoderLayer(cfg, True, False, name=name)
            with jax.named_scope("mtp"):
                for k in range(cfg.num_nextn_predict_layers):
                    # position i is given the token k + 1 after it; a row's last
                    # positions have none, and predict nothing (`lms/clm.py`)
                    next_embeds = embed_tokens(jnp.roll(input_ids, -(k + 1), axis=1))
                    carried, _ = MTPModule(cfg, _norm(cfg), block, name=f"mtp_{k}")(
                        carried, next_embeds, segment_ids, cos, sin
                    )
                    mtp_hidden.append(final_norm(carried))
            mtp_hidden = tuple(mtp_hidden)
        hidden = final_norm(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        logits = mtp_logits = None
        if compute_logits:
            if cfg.tie_word_embeddings:
                head = embed_tokens.attend
            else:
                head = _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head", False)
            logits = nn.with_logical_constraint(head(hidden), ("batch", "act_seq", "act_vocab"))
            if mtp_hidden is not None:
                mtp_logits = tuple(head(h) for h in mtp_hidden)

        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            ep_dropped_rows=ep_dropped,
            router_stats=RouterStats(
                sel_frac=sel_frac, mean_prob=mean_prob, dropped=ep_dropped,
                layer_ids=tuple(moe_ids),
            ),
            decode_state=new_decode_state,
            # only a share of the experts has assignments held elsewhere to count
            moe_assignments=None if counts is None else counts.sum(axis=0),
            mtp_hidden_states=mtp_hidden,
            mtp_logits=mtp_logits,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        if self.config.tie_word_embeddings:
            return "embed_tokens/embedding"
        return "lm_head/kernel"
