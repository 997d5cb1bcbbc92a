"""Qwen3-Next decoder, TPU-native.

Graph verified against HF `modeling_qwen3_next.py`:

- hybrid layer stack: 3-of-4 layers are gated DeltaNet linear attention,
  every 4th is gated full attention; every layer's MLP is the qwen-style
  sparse MoE (softmax top-k + shared expert with sigmoid gate — the shared
  `MoEMLP` block).
- gated full attention: q_proj emits [q | gate] per head, zero-centered
  (1+w) per-head qk-norms, PARTIAL rotary (factor 0.25), and the attention
  output multiplies sigmoid(gate) before o_proj.
- gated DeltaNet: fused qkvz/ba projections, a depthwise causal conv (silu)
  over the concatenated q|k|v channels, per-head decay
  g = -exp(A_log) * softplus(a + dt_bias) and write strength
  beta = sigmoid(b), then the CHUNKED gated delta rule. The reference's
  per-row forward-substitution loop is a unit-lower-triangular inverse,
  computed here as ONE `solve_triangular` per chunk (the TPU-idiomatic
  form); the cross-chunk recurrence is a `lax.scan` over the running
  [dk, dv] state. All delta-rule math runs in fp32 like the HF kernel.
- norms are zero-centered (1+w) RMSNorms; the DeltaNet output norm is the
  gated variant (norm(x) * w * silu(z)).

Padding semantics mirror HF: padded tokens are zeroed at the layer input,
but the recurrent state still decays THROUGH padding and across packed
documents by default (HF parity). `segment_state_reset=True` (opt-in)
resets the fast-weight state at document boundaries via the log-decay
trick (`segment_reset_decay`) — packing is this framework's default
pre-training mode, so the no-cross-contamination guarantee can extend to
the recurrence where HF cannot offer it.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import CausalLMOutput, RouterStats
from llm_training_tpu.models.moe import MoEMLP
from llm_training_tpu.models.qwen3_next.config import Qwen3NextConfig
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.models.llama.model import _dense
from llm_training_tpu.ops import apply_rope, dot_product_attention
from llm_training_tpu.ops.delta_rule import gated_delta_chunked, l2norm as _l2norm
from llm_training_tpu.ops.rope_utils import compute_rope_cos_sin, compute_rope_frequencies


class ZeroCenteredRMSNorm(nn.Module):
    """(1 + w) RMSNorm with fp32 stats, product BEFORE the downcast (HF
    Qwen3NextRMSNorm)."""

    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        weight = self.param(
            "weight",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        return (normed * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


class GatedRMSNorm(nn.Module):
    """norm(x) * w * silu(z) (HF Qwen3NextRMSNormGated; NON-zero-centered
    weight, gate applied after the weighted norm in fp32)."""

    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x: jnp.ndarray, gate: jnp.ndarray) -> jnp.ndarray:
        weight = self.param(
            "weight",
            nn.with_logical_partitioning(nn.initializers.ones, ("norm",)),
            (x.shape[-1],),
            self.param_dtype,
        )
        x32 = x.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + self.eps
        )
        out = (weight.astype(jnp.float32) * normed).astype(x.dtype)
        return (
            out.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
        ).astype(x.dtype)


_RESET_LOG_DECAY = -1e4  # exp() underflows to exactly 0.0 in fp32


def segment_reset_decay(segment_ids: jnp.ndarray) -> jnp.ndarray:
    """[B, S] extra log-decay: `_RESET_LOG_DECAY` at each document START.

    Adding this to a recurrence's log-decay sequence makes every cross-
    boundary decay product underflow to zero (a k-boundary gap accumulates
    k·(-1e4)) while within-document terms are untouched — an EXACT state
    reset that needs no change to the chunked scan structure. Packing is
    this framework's default pre-training mode, so opt-in resets extend the
    no-cross-contamination guarantee (ops/attention.py) to the recurrent
    families, which HF faithfully leaks across documents."""
    prev = jnp.concatenate(
        [segment_ids[:, :1], segment_ids[:, :-1]], axis=1
    )
    return jnp.where(segment_ids != prev, _RESET_LOG_DECAY, 0.0)


def chunk_gated_delta_rule(
    q: jnp.ndarray,  # [B, S, H, dk]
    k: jnp.ndarray,  # [B, S, H, dk]
    v: jnp.ndarray,  # [B, S, H, dv]
    g: jnp.ndarray,  # [B, S, H] log-decay (negative)
    beta: jnp.ndarray,  # [B, S, H] write strength in (0, 1)
    chunk_size: int = 64,
    reset_decay: jnp.ndarray | None = None,  # [B, S] from segment_reset_decay
) -> jnp.ndarray:
    """Chunked gated delta rule (HF `torch_chunk_gated_delta_rule`), fp32:
    q and k L2-normalised a head, q scaled, then the shared rule
    (`ops/delta_rule.py:gated_delta_chunked`) from a zero state.
    """
    in_dtype = q.dtype
    q = _l2norm(q.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    k = _l2norm(k.astype(jnp.float32))
    g = g.astype(jnp.float32)
    if reset_decay is not None:
        g = g + reset_decay.astype(jnp.float32)[..., None]
    batch, _, heads, dk = q.shape
    out, _ = gated_delta_chunked(
        q, k, v.astype(jnp.float32), g, beta.astype(jnp.float32),
        jnp.zeros((batch, heads, dk, v.shape[-1]), jnp.float32),
        chunk_size=chunk_size, precision=None,
    )
    return out.astype(in_dtype)


class GatedDeltaNet(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, hidden, pad_mask, segment_ids=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        kh, vh = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        group = vh // kh
        key_dim, value_dim = kh * dk, vh * dv

        if pad_mask is not None:  # HF zeroes padded tokens at the layer input
            hidden = hidden * pad_mask[..., None].astype(hidden.dtype)

        qkvz = _dense(
            cfg, key_dim * 2 + value_dim * 2, ("embed", "heads"),
            "in_proj_qkvz", False,
        )(hidden)
        ba = _dense(cfg, vh * 2, ("embed", "heads"), "in_proj_ba", False)(hidden)

        # HF interleaves per k-head: [q(dk) | k(dk) | v(group*dv) | z(group*dv)]
        qkvz = qkvz.reshape(batch, seq, kh, 2 * dk + 2 * group * dv)
        qh = qkvz[..., :dk]
        khd = qkvz[..., dk:2 * dk]
        vhd = qkvz[..., 2 * dk:2 * dk + group * dv].reshape(batch, seq, vh, dv)
        z = qkvz[..., 2 * dk + group * dv:].reshape(batch, seq, vh, dv)
        ba = ba.reshape(batch, seq, kh, 2 * group)
        b = ba[..., :group].reshape(batch, seq, vh)
        a = ba[..., group:].reshape(batch, seq, vh)

        # depthwise causal conv (kernel 4, no bias) + silu over q|k|v channels
        mixed = jnp.concatenate(
            [qh.reshape(batch, seq, key_dim), khd.reshape(batch, seq, key_dim),
             vhd.reshape(batch, seq, value_dim)],
            axis=-1,
        )
        conv_w = self.param(
            "conv_kernel",
            nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), (None, "heads")
            ),
            (cfg.linear_conv_kernel_dim, mixed.shape[-1]),
            cfg.param_jnp_dtype,
        ).astype(mixed.dtype)
        k_conv = cfg.linear_conv_kernel_dim
        padded = jnp.pad(mixed, ((0, 0), (k_conv - 1, 0), (0, 0)))
        reset_on = (
            getattr(cfg, "segment_state_reset", False) and segment_ids is not None
        )
        if reset_on:
            # the causal conv window must not cross document boundaries: a
            # cross-segment tap is replaced by the zero a standalone run's
            # left-padding would supply
            seg_p = jnp.pad(segment_ids, ((0, 0), (k_conv - 1, 0)))
            conv = sum(
                padded[:, i:i + seq]
                * conv_w[i]
                * (seg_p[:, i:i + seq] == segment_ids)[..., None]
                for i in range(k_conv)
            )
        else:
            conv = sum(padded[:, i:i + seq] * conv_w[i] for i in range(k_conv))
        mixed = jax.nn.silu(conv)

        qh = mixed[..., :key_dim].reshape(batch, seq, kh, dk)
        khd = mixed[..., key_dim:2 * key_dim].reshape(batch, seq, kh, dk)
        vhd = mixed[..., 2 * key_dim:].reshape(batch, seq, vh, dv)

        a_log = self.param(
            "A_log",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("heads",)),
            (vh,),
            jnp.float32,
        )
        dt_bias = self.param(
            "dt_bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("heads",)),
            (vh,),
            jnp.float32,
        )
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)

        # broadcast k-heads over the value-head groups
        qh = jnp.repeat(qh, group, axis=2)
        khd = jnp.repeat(khd, group, axis=2)

        reset = None
        if getattr(cfg, "segment_state_reset", False) and segment_ids is not None:
            reset = segment_reset_decay(segment_ids)
        out = chunk_gated_delta_rule(
            qh, khd, vhd, g, beta, chunk_size=cfg.delta_chunk_size,
            reset_decay=reset,
        )
        out = GatedRMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")(out, z)
        out = out.reshape(batch, seq, value_dim)
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "out_proj", False)(out)


class GatedAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, d = cfg.num_attention_heads, cfg.head_dim

        qg = _dense(cfg, heads * d * 2, ("embed", "heads"), "q_proj",
                    cfg.attention_bias)(hidden)
        qg = qg.reshape(batch, seq, heads, 2 * d)
        q, gate = qg[..., :d], qg[..., d:]
        gate = gate.reshape(batch, seq, heads * d)
        k = _dense(cfg, cfg.num_key_value_heads * d, ("embed", "kv_heads"),
                   "k_proj", cfg.attention_bias)(hidden)
        v = _dense(cfg, cfg.num_key_value_heads * d, ("embed", "kv_heads"),
                   "v_proj", cfg.attention_bias)(hidden)
        k = k.reshape(batch, seq, cfg.num_key_value_heads, d)
        v = v.reshape(batch, seq, cfg.num_key_value_heads, d)

        norm = lambda name: ZeroCenteredRMSNorm(
            cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name
        )
        q = norm("q_norm")(q)
        k = norm("k_norm")(k)

        rot = int(d * cfg.partial_rotary_factor)
        q_rot, k_rot = apply_rope(q[..., :rot], k[..., :rot], cos, sin)
        q = jnp.concatenate([q_rot, q[..., rot:]], axis=-1)
        k = jnp.concatenate([k_rot, k[..., rot:]], axis=-1)

        out = dot_product_attention(
            q, k, v, segment_ids=segment_ids, causal=True,
            impl=cfg.attention_impl,
        )
        out = out.astype(hidden.dtype).reshape(batch, seq, heads * d)
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj",
                      cfg.attention_bias)(out)


class Qwen3NextDecoderLayer(nn.Module):
    config: Qwen3NextConfig
    is_linear: bool

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: ZeroCenteredRMSNorm(
            cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name
        )
        pad_mask = None if segment_ids is None else segment_ids > 0

        normed = norm("input_layernorm")(hidden)
        if self.is_linear:
            attn = GatedDeltaNet(cfg, name="linear_attn")(
                normed, pad_mask, segment_ids
            )
        else:
            attn = GatedAttention(cfg, name="self_attn")(normed, segment_ids, cos, sin)
        hidden = hidden + attn

        normed = norm("post_attention_layernorm")(hidden)
        if cfg.num_experts:
            mlp_out, stats = MoEMLP(cfg, name="mlp")(normed, pad_mask)
        else:
            from llm_training_tpu.models.llama.model import LlamaMLP

            mlp_out, stats = LlamaMLP(cfg, name="mlp")(normed), jnp.float32(0.0)
        return hidden + mlp_out, stats


class _PeriodicBody(nn.Module):
    """Scan body: one period of the linear/full pattern (`scan_period`
    layers, stock Qwen3-Next: linear, linear, linear, full)."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin):
        cfg = self.config
        stats = []
        for j in range(cfg.scan_period):
            hidden, layer_stats = Qwen3NextDecoderLayer(
                cfg, cfg.layer_is_linear(j), name=f"slot{j}"
            )(hidden, segment_ids, cos, sin)
            stats.append(layer_stats)
        return hidden, jax.tree.map(lambda *xs: jnp.stack(xs), *stats)


class Qwen3Next(nn.Module):
    """Qwen3-Next causal LM with the `CausalLMProto` surface."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,
        inputs_embeds: jnp.ndarray | None = None,
        compute_logits: bool = True,
        return_last_hidden_states: bool = False,
    ) -> CausalLMOutput:
        cfg = self.config
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
        seq = hidden.shape[1]

        if position_ids is None:
            position_ids = jnp.arange(seq)[None, :]
        inv_freq, attention_scaling = compute_rope_frequencies(
            cfg.rope_config, seq_len=seq
        )
        cos, sin = compute_rope_cos_sin(inv_freq, position_ids, attention_scaling)

        policy = _remat_policy(cfg)
        period = cfg.scan_period
        if period:
            body = _PeriodicBody
            if policy is not None:
                body = nn.remat(_PeriodicBody, policy=policy, prevent_cse=False)
            scanned = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast, nn.broadcast, nn.broadcast),
                length=cfg.num_hidden_layers // period,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, name="layers")
            hidden, stacked_stats = scanned(hidden, segment_ids, cos, sin)
            # [cycles, period, ...] -> [L, ...]; depth order is irrelevant to
            # the mean-pooled aux loss below
            pooled = jax.tree.map(
                lambda x: x.reshape(-1, *x.shape[2:]), stacked_stats
            )
        else:
            stats = []
            for i in range(cfg.num_hidden_layers):
                layer_cls = Qwen3NextDecoderLayer
                if policy is not None:
                    layer_cls = nn.remat(Qwen3NextDecoderLayer, policy=policy)
                hidden, layer_stats = layer_cls(
                    cfg, cfg.layer_is_linear(i), name=f"layers_{i}"
                )(hidden, segment_ids, cos, sin)
                stats.append(layer_stats)
            pooled = jax.tree.map(lambda *xs: jnp.stack(xs), *stats)

        hidden = ZeroCenteredRMSNorm(
            cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm"
        )(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        aux_loss = ep_dropped = router_stats = None
        if cfg.num_experts:
            sel_frac, mean_prob, dropped = pooled
            aux_loss = cfg.num_experts * jnp.sum(
                sel_frac.mean(axis=0) * mean_prob.mean(axis=0)
            )
            ep_dropped = dropped.sum()
            router_stats = RouterStats(
                sel_frac=sel_frac,
                mean_prob=mean_prob,
                dropped=ep_dropped,
                layer_ids=tuple(range(cfg.num_hidden_layers)),
            )

        logits = None
        if compute_logits:
            if cfg.tie_word_embeddings:
                logits = embed_tokens.attend(hidden)
            else:
                logits = _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head", False)(hidden)
            logits = nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))

        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            aux_loss=aux_loss,
            ep_dropped_rows=ep_dropped,
            router_stats=router_stats,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        if self.config.tie_word_embeddings:
            return "embed_tokens/embedding"
        return "lm_head/kernel"
