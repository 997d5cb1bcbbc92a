"""Arcee AFMoE decoder (`model_type: afmoe`; Trinity-Mini), TPU-native.

`h = E[ids] * sqrt(hidden_size)` (`mup_enabled`), then every layer is

    a = Attn(N1 h);  h = h + N2 a;  m = MLP(N3 h);  h = h + N4 m

(four RMSNorms: `input_layernorm`, `post_attention_layernorm`,
`pre_mlp_layernorm`, `post_mlp_layernorm`), then `logits = Head(N_f h)`, the
head untied.

- `Attn`: q, k, v and a gate from four bias-free projections; an RMSNorm
  over each q and k head (a weight over `head_dim`); on a
  `sliding_attention` layer rotary positions on q and k and a causal window
  (`q_pos - k_pos < sliding_window`), on a `full_attention` layer NO
  positional term and causal attention over everything; `o_proj(attn *
  sigmoid(gate))`.
- `MLP`: SwiGLU of `intermediate_size` on the first `num_dense_layers`
  layers; on the others `deepseek.DeepseekMoE` as it stands (sigmoid scores
  in float32, a bias for the choice only, the top k normalised and scaled by
  `route_scale`, one shared expert always on), which a config with
  `experts_held` turns into an expert-parallel share.

Decoding (docs/inference.md, docs/serving.md): the two kinds of attention
layer keep their keys and values in two GROUPS, declared once by
`AfmoeConfig.cache_specs()`: the full layers every token, the sliding layers
a window. A layer appends to and reads its own group by the `window` it
attends with (`models/cache.py:LayerCache.attend`), and counts itself among
its group's layers: two counters ride the layer loop.

The layers in front (the whole periods of the window pattern that hold the
dense layers) are looped; what follows scans over periods of `[sliding,
sliding, sliding, full]`, the caches riding the loop as its carry and the
expert leaves read where they lie in the stack (`models/cache.py:
scan_layers(whole=EXPERT_LEAVES)`).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.afmoe.config import AfmoeConfig
from llm_training_tpu.models.base import (
    CausalLMOutput,
    DecodeState,
    PagedDecodeState,
    RouterStats,
)
from llm_training_tpu.models.cache import close_cache, open_cache, scan_layers
from llm_training_tpu.models.deepseek.model import DeepseekMLP, DeepseekMoE
from llm_training_tpu.models.llama.model import RMSNorm, _dense, _plain_rows
from llm_training_tpu.models.moe import EXPERT_LEAVES, decoding_experts
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.ops import apply_rope, dot_product_attention


class AfmoeAttention(nn.Module):
    """Gated attention with an RMSNorm a q and k head. Returns `(out,
    cache)`: with a `cache` (`models/cache.py`) k/v are appended to part
    `layer` of this layer's group (its index among the stack's layers of its
    kind) and attention runs against that part."""

    config: AfmoeConfig
    is_window: bool

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin, cache=None, layer=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, kv_heads, dim = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(cfg, heads * dim, ("embed", "heads"), "q_proj", False)(hidden)
        k = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "k_proj", False)(hidden)
        v = _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), "v_proj", False)(hidden)
        q, k, v = _plain_rows(cache, (q, k, v))
        q = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="q_norm")(
            q.reshape(batch, seq, heads, dim)
        )
        k = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="k_norm")(
            k.reshape(batch, seq, kv_heads, dim)
        )
        v = v.reshape(batch, seq, kv_heads, dim)
        window = None
        if self.is_window:
            # positions enter the layers that keep a window, and no other
            q, k = apply_rope(q, k, cos, sin)
            window = cfg.sliding_window
        with jax.named_scope("attn_window" if self.is_window else "attn_global"):
            if cache is not None:
                out, cache = cache.attend(layer, q, k, v, segment_ids, window=window)
            else:
                out = dot_product_attention(
                    q, k, v, segment_ids=segment_ids, causal=True,
                    sliding_window=window, impl=cfg.attention_impl,
                )
        out = out.astype(hidden.dtype).reshape(batch, seq, heads * dim)
        with jax.named_scope("attn_gate"):
            gate = _plain_rows(
                cache, _dense(cfg, heads * dim, ("embed", "heads"), "gate_proj", False)(hidden)
            )
            out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", False)(out), cache


class AfmoeDecoderLayer(nn.Module):
    """Returns (hidden, router stats or None, cache). `layer` is this layer's
    index among the stack's layers of its attention kind; `stack = (leaves,
    index)` the scanned stack's expert leaves whole, for a decoding MoE layer
    of a scanned period."""

    config: AfmoeConfig
    is_window: bool
    is_moe: bool

    @nn.compact
    def __call__(self, hidden, segment_ids, cos, sin, cache=None, layer=None, stack=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name=name)
        attn, cache = AfmoeAttention(cfg, self.is_window, name="self_attn")(
            norm("input_layernorm")(hidden), segment_ids, cos, sin, cache, layer
        )
        hidden = hidden + norm("post_attention_layernorm")(attn)
        normed = norm("pre_mlp_layernorm")(hidden)
        stats = None
        if self.is_moe:
            pad_mask = None if segment_ids is None else segment_ids > 0
            mlp_out, stats = DeepseekMoE(cfg, name="mlp")(normed, pad_mask, stack)
        else:
            mlp_out = DeepseekMLP(cfg, cfg.intermediate_size, name="mlp")(normed)
        return hidden + norm("post_mlp_layernorm")(mlp_out), stats, cache


class _Layers(nn.Module):
    """Consecutive layers of the stack, `kinds[j] = (keeps a window, has
    experts)`: a scan body (one period) or a looped run. The carry is
    `hidden` or, when decoding, `(hidden, the cache's buffers)`: both groups'
    with a leading axis over ALL the group's layers. `first = (full, window)`
    counts the layers of each group in front of this run, and `cycle` says
    which repeat of it this is (`models/cache.py:scan_layers`). Returns the
    carry and the MoE layers' router stats, stacked."""

    config: AfmoeConfig
    kinds: tuple[tuple[bool, bool], ...]
    first: tuple[int, int] = (0, 0)

    @nn.compact
    def __call__(self, carry, segment_ids, cos, sin, cache=None, cycle=0, stack=None):
        cfg = self.config
        hidden = carry
        if cache is not None:
            hidden, buffers = carry
            cache = cache.holding(buffers)
        windows = [is_window for is_window, _ in self.kinds]
        stats = []
        for j, (is_window, is_moe) in enumerate(self.kinds):
            # this layer's index among the stack's layers of its group
            index = None if cache is None else (
                self.first[is_window] + cycle * windows.count(is_window)
                + windows[:j].count(is_window)
            )
            experts = decoding_experts(cache, stack, cycle, f"slot{j}", "mlp") if is_moe else None
            hidden, layer_stats, cache = AfmoeDecoderLayer(
                cfg, is_window, is_moe, name=f"slot{j}"
            )(hidden, segment_ids, cos, sin, cache, index, experts)
            if is_moe:
                stats.append(layer_stats)
        stats = jax.tree.map(lambda *leaves: jnp.stack(leaves), *stats) if stats else None
        return (hidden if cache is None else (hidden, cache.buffers)), stats


def _run(body, args, name, hidden, inputs, cache):
    """A looped run of layers under one module: `-> (hidden, stats, cache)`."""
    if cache is None:
        hidden, stats = body(*args, name=name)(hidden, *inputs)
        return hidden, stats, None
    (hidden, buffers), stats = body(*args, name=name)((hidden, cache.buffers), *inputs, cache)
    return hidden, stats, cache.holding(buffers)


class Afmoe(nn.Module):
    """AFMoE causal LM with the `CausalLMProto` surface, decoding through
    `decode_state` (dense or paged) like the Llama stack."""

    config: AfmoeConfig

    def _layers(self, hidden, segment_ids, cos, sin, cache):
        """-> (hidden, router stats [MoE layers, ...], cache or None)."""
        cfg = self.config
        kinds = tuple(cfg.layer_kinds)
        front, periods, period = cfg.scan_plan
        body = _Layers
        policy = _remat_policy(cfg)
        if policy is not None:
            body = nn.remat(_Layers, policy=policy, prevent_cse=False)
        inputs = (segment_ids, cos, sin)
        hidden, stats, cache = _run(body, (cfg, kinds[:front]), "front", hidden, inputs, cache)
        if not periods:
            return hidden, stats, cache
        windows = sum(is_window for is_window, _ in kinds[:front])
        hidden, scanned, cache = scan_layers(
            body, (cfg, kinds[front:front + period], (front - windows, windows)), periods,
            hidden, inputs, cache, whole=EXPERT_LEAVES,
        )
        # [periods, MoE layers a period, ...] -> [MoE layers, ...], in depth order
        scanned = jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), scanned)
        if stats is not None:
            scanned = jax.tree.map(lambda a, b: jnp.concatenate([a, b]), stats, scanned)
        return hidden, scanned, cache

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
        if cfg.mup_enabled:
            hidden = hidden * jnp.asarray(cfg.hidden_size ** 0.5, hidden.dtype)
        batch, seq = hidden.shape[:2]

        if position_ids is None:
            if decode_state is not None:
                raise ValueError("decoding needs position_ids: a chunk's place in its row")
            position_ids = jnp.arange(seq)[None, :]
        # plain rotary, no scaling; pairs (i, i + head_dim / 2) rotate together
        dim = cfg.head_dim
        inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
        angles = position_ids.astype(jnp.float32)[..., None] * inv_freq
        angles = jnp.concatenate([angles, angles], axis=-1)
        cos, sin = jnp.cos(angles), jnp.sin(angles)

        cache, segment_ids = open_cache(decode_state, segment_ids, batch, seq)
        hidden, stats, cache = self._layers(hidden, segment_ids, cos, sin, cache)
        new_decode_state = close_cache(cache, decode_state, segment_ids)

        hidden = RMSNorm(cfg.rms_norm_eps, cfg.param_jnp_dtype, name="norm")(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        logits = None
        if compute_logits:
            logits = _dense(cfg, cfg.vocab_size, ("embed", "vocab"), "lm_head", False)(hidden)
            logits = nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))

        router_stats = ep_dropped = None
        if stats is not None:
            sel_frac, mean_prob, dropped = stats
            ep_dropped = dropped.sum()
            router_stats = RouterStats(
                sel_frac=sel_frac, mean_prob=mean_prob, dropped=ep_dropped,
                layer_ids=tuple(range(cfg.num_dense_layers, cfg.num_hidden_layers)),
            )
        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            aux_loss=None,  # the expert bias balances the experts
            ep_dropped_rows=ep_dropped,
            router_stats=router_stats,
            decode_state=new_decode_state,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        return "lm_head/kernel"
