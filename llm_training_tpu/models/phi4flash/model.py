"""Phi-4-mini-flash-reasoning decoder (`model_type: phi4flash`), TPU-native:
the decoder-hybrid-decoder stack of arXiv:2507.06607 (SambaY).

Every layer is `h = x + mixer(LN(x)); y = h + MLP(LN(h))`, LayerNorm with
weight and bias, the MLP a SwiGLU without bias; then `logits = Emb^T LN_f y`,
the head tied. No layer has a positional term. With `L` layers, `i` from 0,
the mixer is (`Phi4FlashConfig.layer_kinds`)

- `i` even, `i <= L/2`: **Mamba-1**. `[x, z] = W_in u`; x through a causal
  depthwise convolution of 4 taps with bias and SiLU; `[dt, B, C] = W_x x`;
  `delta = softplus(W_dt dt + b_dt)`, `A = -exp(A_log)`; the selective scan
  (`ops/selective_scan.py`) on a float32 `[inner, 16]` state; `y = scan +
  D x`; out `W_out (y silu(z))`. Layer `L/2`'s `y` is the MEMORY `m`;
- `i` odd, `i < L/2`: **differential attention** over a window of
  `sliding_window` tokens (a query sees itself and the 511 before it).
  Query heads `(2j, 2j + 1)` are `q1_j, q2_j`, key heads `k1, k2` and value
  heads `v1, v2` likewise; `a_r = softmax(q_r k_r^T / sqrt(d)) [v1 ; v2]`,
  `o_j = (1 - lambda_init) RMSNorm_2d(a_1 - lambda a_2)` with `lambda =
  exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`, `lambda_init = 0.8 - 0.6
  exp(-0.3 i)`; projections with bias;
- `i = L/2 + 1`: the same attention with NO window: the model's one full
  key/value cache;
- `i` even, `i >= L/2 + 2`: a **gated memory unit**, `W_out (silu(W_in u) *
  m_t)`: no cache, no recurrence;
- `i` odd, `i >= L/2 + 3`: **cross attention**: a query projection only,
  against layer `L/2 + 1`'s keys and values, the same differential form.

The PAIRED layout. Keys and values are held a pair of heads, `[k1 ; k2]` and
`[v1 ; v2]`, twice as wide: half as many heads, the same bytes. A query head
`q_r` is laid into its half of a row of that width, zeros in the other, so
that `q_r . [k1 ; k2] = q_r . k_r` and the softmax's weights fall on `[v1 ;
v2]` whole: differential attention is then plain grouped-query attention at
twice the head size, scale `d^-1/2` given, and runs through the flash and
paged kernels as they are. The subtraction, the norm and `lambda` follow in
`diff_attn`.

Decoding (docs/inference.md, docs/serving.md): `Phi4FlashConfig.cache_specs()`
declares three caches. A window layer appends to and reads its part of the
window group's ring; layer `L/2 + 1` appends to the one full layer's pages,
and every cross layer READS those pages and appends nothing
(`LayerCache.attend` with no keys); a Mamba layer reads and writes its decode
slot's slab, state and convolution tail, one token in place. The two
alternating halves scan (`models/cache.py:scan_layers`), the two layers
between them loop, and the memory and the caches ride across. Scopes:
`ssm_conv`, `ssm_scan` (a chunk) / `ssm_step` (a token) inside `/mamba/`;
`gmu`; `diff_attn` around a differential attention, inside it `attn_window`
or `attn_global` (the full layer's pages: layer `L/2 + 1` and, under
`attn_cross` inside it, the layers that only read them)
(docs/observability.md).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from llm_training_tpu.models.base import CausalLMOutput, DecodeState, PagedDecodeState
from llm_training_tpu.models.cache import close_cache, open_cache, scan_layers
from llm_training_tpu.models.llama.model import (
    LayerNorm,
    LlamaMLP,
    RMSNorm,
    _dense,
    _plain_rows,
)
from llm_training_tpu.models.olmo_hybrid.model import _dt_bias_init  # Mamba's own: dt log-uniform in [1e-3, 1e-1]
from llm_training_tpu.models.phi4flash.config import (
    CROSS, FULL, GMU, MAMBA, MEMORY, WINDOW, Phi4FlashConfig,
)
from llm_training_tpu.models.remat import remat_policy as _remat_policy
from llm_training_tpu.ops import dot_product_attention
from llm_training_tpu.ops.delta_rule import short_conv
from llm_training_tpu.ops.selective_scan import selective_scan, selective_step
from llm_training_tpu.ops.swiglu import silu_mul


def _a_log_init(key, shape, dtype=jnp.float32):
    """Mamba's (S4D-real): A = -(1 .. N), the same for every channel."""
    return jnp.log(jnp.broadcast_to(jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape)).astype(dtype)


def lambda_init(depth):
    """DIFF Transformer's: `0.8 - 0.6 exp(-0.3 i)` at layer `i`, which a scan
    over layers knows as a traced number."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


class Mamba(nn.Module):
    """`rows` is this layer's `(state [B, *RecurrentCacheSpec.stored] float32,
    tail [B, taps, inner])` for the batch's rows, or None (training: zero
    state, zero tail). Returns `(out, y, new rows)`: `y` the scan's output
    with the `D` term, before the gate (the memory, where this is layer
    `L/2`), the rows None without any."""

    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, hidden, segment_ids=None, rows=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        inner, n, rank = cfg.mamba_inner, cfg.mamba_d_state, cfg.resolved_dt_rank
        taps = cfg.mamba_d_conv - 1
        valid = jnp.ones((batch, seq), bool) if segment_ids is None else segment_ids > 0
        vector = lambda name, init: self.param(
            name, nn.with_logical_partitioning(init, ("heads",)), (inner,), jnp.float32
        )
        x, z = jnp.split(
            _dense(cfg, 2 * inner, ("embed", "heads"), "in_proj", False)(hidden), 2, axis=-1
        )
        with jax.named_scope("ssm_conv"):
            conv_w = self.param(
                "conv_kernel",
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.initializer_range), (None, "heads")
                ),
                (taps + 1, inner), cfg.param_jnp_dtype,
            ).astype(jnp.float32)
            (x,), new_tail = short_conv(
                x, conv_w, None if rows is None else rows[1], segment_ids, valid, (),
                vector("conv_bias", nn.initializers.zeros_init()),
            )
        low = _dense(cfg, rank + 2 * n, ("heads", None), "x_proj", False)(x.astype(hidden.dtype))
        dt, b, c = jnp.split(low, (rank, rank + n), axis=-1)
        b, c = b.astype(jnp.float32), c.astype(jnp.float32)
        delta = jax.nn.softplus(
            _dense(cfg, inner, (None, "heads"), "dt_proj", False)(dt).astype(jnp.float32)
            + vector("dt_bias", _dt_bias_init)
        )
        delta = jnp.where(valid[..., None], delta, 0.0)  # a padded position changes nothing
        a = -jnp.exp(self.param(
            "A_log", nn.with_logical_partitioning(_a_log_init, ("heads", None)),
            (inner, n), jnp.float32,
        ))
        if rows is not None and seq == 1:
            with jax.named_scope("ssm_step"):
                state, y = selective_step(rows[0], x[:, 0], delta[:, 0], a, b[:, 0], c[:, 0])
                y = y[:, None]
        else:
            starts = None
            if segment_ids is not None:  # a packed document starts from a zero state
                before = jnp.concatenate([segment_ids[:, :1], segment_ids[:, :-1]], axis=1)
                starts = valid & (segment_ids != before)
            with jax.named_scope("ssm_scan"):
                state = (
                    jnp.zeros((batch, *cfg.cache_specs()[1].stored), jnp.float32)
                    if rows is None else rows[0]
                )
                y, state = selective_scan(x, delta, a, b, c, state, starts)
        y = (y + vector("D", nn.initializers.ones) * x).astype(hidden.dtype)
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "out_proj", False)(silu_mul(z, y))
        return out, y, None if rows is None else (state, new_tail.astype(rows[1].dtype))


class GatedMemoryUnit(nn.Module):
    """`W_out (silu(W_in u) * m)`, `m` the memory at the same positions."""

    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, hidden, memory):
        cfg = self.config
        gate = _dense(cfg, cfg.mamba_inner, ("embed", "heads"), "in_proj", False)(hidden)
        return _dense(cfg, cfg.hidden_size, ("heads", "embed"), "out_proj", False)(
            silu_mul(gate, memory)
        )


class DiffAttention(nn.Module):
    """Differential attention in the paired layout (the module docstring).
    `kind`: `window` / `full` append their keys and values to part `layer`
    of their group of the `cache` and attend against it; `cross` has a query
    projection only and reads part `layer` of the full group. `depth` is the
    layer's index in the whole stack (`lambda_init`). Without a cache
    (training) a `cross` layer is handed the full layer's keys and values,
    `shared`. Returns `(out, cache, this layer's (k, v) where no cache
    holds them)`."""

    config: Phi4FlashConfig
    kind: str

    @nn.compact
    def __call__(self, hidden, segment_ids, depth, cache=None, layer=None, shared=None):
        cfg = self.config
        batch, seq, _ = hidden.shape
        heads, kv_heads, dim = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.resolved_head_dim
        q = _plain_rows(cache, _dense(cfg, heads * dim, ("embed", "heads"), "q_proj", True)(hidden))
        # a query head in its half of a pair's row, zeros in the other
        half = jnp.eye(2, dtype=q.dtype)[:, :, None]
        q = (q.reshape(batch, seq, heads // 2, 2, 1, dim) * half).reshape(batch, seq, heads, 2 * dim)
        k = v = None  # a cross layer under a cache: the keys and values are in the pages
        if self.kind != CROSS:
            k, v = (
                _plain_rows(
                    cache, _dense(cfg, kv_heads * dim, ("embed", "kv_heads"), name, True)(hidden)
                ).reshape(batch, seq, kv_heads // 2, 2 * dim)
                for name in ("k_proj", "v_proj")
            )
        elif cache is None:
            k, v = shared
        window = cfg.sliding_window if self.kind == WINDOW else None
        scope = {WINDOW: "attn_window", FULL: "attn_global", CROSS: "attn_global/attn_cross"}
        with jax.named_scope("diff_attn"):
            with jax.named_scope(scope[self.kind]):
                if cache is not None:
                    out, cache = cache.attend(
                        layer, q, k, v, segment_ids, window=window, scale=dim ** -0.5
                    )
                else:
                    out = dot_product_attention(
                        q, k, v, segment_ids=segment_ids, causal=True, sliding_window=window,
                        scale=dim ** -0.5, impl=cfg.attention_impl,
                    )
            lam = [
                self.param(
                    name, nn.with_logical_partitioning(nn.initializers.normal(0.1), (None,)),
                    (dim,), jnp.float32,
                )
                for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
            ]
            start = lambda_init(depth)
            lam = jnp.exp(jnp.sum(lam[0] * lam[1])) - jnp.exp(jnp.sum(lam[2] * lam[3])) + start
            out = out.astype(jnp.float32).reshape(batch, seq, heads // 2, 2, 2 * dim)
            out = (out[:, :, :, 0] - lam * out[:, :, :, 1]).astype(hidden.dtype)
            out = RMSNorm(cfg.layer_norm_eps, cfg.param_jnp_dtype, name="subln")(out)
            out = (out.astype(jnp.float32) * (1.0 - start)).astype(hidden.dtype)
            out = out.reshape(batch, seq, heads * dim)
        out = _dense(cfg, cfg.hidden_size, ("heads", "embed"), "o_proj", True)(out)
        return out, cache, (k, v) if cache is None else None


class Phi4FlashLayer(nn.Module):
    """Returns `(hidden, what later layers read of this one or None, cache)`:
    the memory layer's scan output; without a cache, the full layer's keys
    and values. `shared = (memory, (k, v))` is what the layers between the
    halves made. `layer` is this layer's part of its cache: a Mamba layer's
    rows of the slab, a window or full layer's part of its page group, for a
    cross layer the part it READS."""

    config: Phi4FlashConfig
    kind: str

    @nn.compact
    def __call__(self, hidden, segment_ids, depth, shared=None, cache=None, layer=None):
        cfg = self.config
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))
        norm = lambda name: LayerNorm(cfg.layer_norm_eps, cfg.param_jnp_dtype, name=name)
        normed = norm("input_layernorm")(hidden)
        made = None
        if self.kind in (MAMBA, MEMORY):
            rows = None
            one_token = hidden.shape[1] == 1
            if cache is not None:
                # one token a slot: the state is updated where it lies
                rows = cache.recurrent_rows(layer, in_place=one_token)
            mixed, made, rows = Mamba(cfg, name="mamba")(normed, segment_ids, rows)
            if rows is not None:
                # the write belongs to the recurrence's scope: in a decode step
                # the state's update fuses INTO it (`models/olmo_hybrid/model.py`)
                with jax.named_scope("mamba/" + ("ssm_step" if one_token else "ssm_scan")):
                    cache = cache.put_recurrent_rows(layer, rows, in_place=one_token)
        elif self.kind == GMU:
            mixed = GatedMemoryUnit(cfg, name="gmu")(normed, shared[0])
        else:
            mixed, cache, made = DiffAttention(cfg, self.kind, name="self_attn")(
                normed, segment_ids, depth, cache, layer, shared and shared[1]
            )
        hidden = hidden + mixed
        hidden = hidden + LlamaMLP(cfg, name="mlp")(norm("post_attention_layernorm")(hidden))
        return hidden, made if self.kind in (MEMORY, FULL) else None, cache


class _Layers(nn.Module):
    """Consecutive layers of the kinds `kinds`: a scan's body (one period of
    a half of the stack) or, called once, the two layers between the halves.
    The carry is `(hidden, depth)`, `depth` the first layer's index in the
    whole stack, or, when decoding, `((hidden, depth), the cache's buffers)`:
    each with a leading axis over ALL the stack's layers of its kind, of
    which `cycle` says which period this is (`models/cache.py:scan_layers`).
    `shared` is what the layers between the halves made for the cross-decoder:
    `(memory, the full layer's (k, v))`, the last None where a cache holds
    them. Returns `(carry, what the layers here made of it)`."""

    config: Phi4FlashConfig
    kinds: tuple[str, ...]

    @nn.compact
    def __call__(self, carry, segment_ids, shared, cache=None, cycle=None):
        cfg = self.config
        if cache is not None:
            carry, buffers = carry
            cache = cache.holding(buffers)
        hidden, depth = carry
        # a layer's part of its cache: a self-decoder layer's own, by its
        # period; the memory layer's rows follow theirs; the one full layer
        # is part 0 of its group, which every cross layer reads
        parts = {MAMBA: cycle, WINDOW: cycle, MEMORY: cfg.layer_kinds.count(MAMBA), FULL: 0, CROSS: 0}
        made = {}
        for j, kind in enumerate(self.kinds):
            hidden, made[kind], cache = Phi4FlashLayer(cfg, kind, name=f"slot{j}")(
                hidden, segment_ids, depth + j, shared, cache,
                None if cache is None else parts.get(kind),
            )
        carry = (hidden, depth + len(self.kinds))
        made = (made[MEMORY], made[FULL]) if MEMORY in made else None
        return (carry if cache is None else (carry, cache.buffers)), made


class Phi4Flash(nn.Module):
    """Phi-4-mini-flash causal LM with the `CausalLMProto` surface, decoding
    through `decode_state` (dense or paged) like the Llama stack."""

    config: Phi4FlashConfig

    def _layers(self, hidden, segment_ids, cache):
        """-> (hidden, cache or None)."""
        cfg = self.config
        kinds = cfg.layer_kinds
        half = cfg.num_hidden_layers // 2
        body = _Layers
        policy = _remat_policy(cfg)
        if policy is not None:
            body = nn.remat(_Layers, policy=policy, prevent_cse=False)
        carry = (hidden, jnp.int32(0))
        carry, _, cache = scan_layers(
            body, (cfg, (MAMBA, WINDOW)), half // 2, carry, (segment_ids, None), cache,
            name="self_decoder",
        )
        # the two layers between the halves: the memory and the full layer's
        # keys and values are made here
        between = body(cfg, (MEMORY, FULL), name="between")
        if cache is None:
            carry, shared = between(carry, segment_ids, None)
        else:
            (carry, buffers), shared = between((carry, cache.buffers), segment_ids, None, cache, 0)
            cache = cache.holding(buffers)
        if half > 2:
            carry, _, cache = scan_layers(
                body, (cfg, tuple(kinds[half + 2:half + 4])), (half - 2) // 2, carry,
                (segment_ids, shared), cache, name="cross_decoder",
            )
        return carry[0], cache

    @nn.compact
    def __call__(
        self,
        input_ids: jnp.ndarray | None = None,
        segment_ids: jnp.ndarray | None = None,
        position_ids: jnp.ndarray | None = None,  # no layer reads a position
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

        cache, segment_ids = open_cache(decode_state, segment_ids, *hidden.shape[:2])
        hidden, cache = self._layers(hidden, segment_ids, cache)
        new_decode_state = close_cache(cache, decode_state, segment_ids)

        hidden = LayerNorm(cfg.layer_norm_eps, cfg.param_jnp_dtype, name="norm")(hidden)
        hidden = nn.with_logical_constraint(hidden, ("batch", "act_seq", "act_embed"))

        logits = None
        if compute_logits:
            logits = embed_tokens.attend(hidden)
            logits = nn.with_logical_constraint(logits, ("batch", "act_seq", "act_vocab"))

        return CausalLMOutput(
            logits=logits,
            last_hidden_states=hidden if return_last_hidden_states else None,
            decode_state=new_decode_state,
        )

    def get_input_embeddings_path(self) -> str:
        return "embed_tokens/embedding"

    def get_output_embeddings_path(self) -> str:
        return "embed_tokens/embedding"
