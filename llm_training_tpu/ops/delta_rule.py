"""The gated delta rule with ONE decay a head (Gated DeltaNet): the
recurrence `qwen3_next` trains with and `olmo_hybrid` serves with. Also what
stands in front of a delta rule in every family here, KDA's too: `l2norm`
and the short causal convolution with its decode tail (`short_conv`).

A head's state S is [key_dim, value_dim], float32:

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - (alpha_t S_{t-1})^T k_t)^T
    o_t = S_t^T q_t

with alpha_t = exp(g_t) in (0, 1] a scalar a head and beta_t in [0, 2] (above
1 the transition has a negative eigenvalue). (`models/solar_open2/kda.py` is
the rule whose decay is a vector a head, one factor a KEY CHANNEL: its
chunked form needs a [C, C, key_dim] cube; this one plain matrix products.)

`gated_delta_step` is that equation for one token on a decode slot's state,
`gated_delta_chunked` a whole sequence from a state to a state. Positions
that must change nothing (padding, idle decode slots, slots still
prefilling) carry beta = 0 and g = 0: then S_t = S_{t-1} exactly. `starts`
marks the first token of a packed document: the state is zero before it,
which the chunked form gets by dropping every term that crosses a start.

The chunked form (chunks of 64). With G_i the running sum of g inside a
chunk, u_i = beta_i (v_i - (alpha_i S_{i-1})^T k_i) and S_0 the chunk's
incoming state:

    (I + tril(Diag(beta) K K^T * e^(G_i - G_j), -1)) U = beta V - (beta K e^G) S_0
    O = (Q e^G) S_0 + tril(Q K^T * e^(G_i - G_j)) U
    S_C = e^(G_C) S_0 + (K e^(G_C - G))^T U

Every exponent is a difference of running sums with the later position
first, so it is <= 0 and nothing overflows.

The STORED state. The chip lays a float32 array out in tiles of 8 x 128 over
its last two axes, so a [96, 192] state occupies 256 lanes a row: a third
more than it holds. `RecurrentCacheSpec.abreast` (`models/base.py`) is how
many heads sit side by side on the value axis so that the rows are whole
tiles (2 for 192: 384 lanes; 1 for 128), `pack_heads` / `unpack_heads` go
between `[..., H, dk, dv]` and `[..., H / n, dk, n * dv]`, and
`gated_delta_step` works on the stored form directly: it never reshapes the
state, only the token's vectors.

ONE token on a decode slab (`one_token_step`). Where a layer's states can be
advanced where they lie, the cache hands the layer not its rows but the slab
and its place in it (`SlabRows`, from `slab_rows`), and the step runs in the
Pallas kernel that reads a block of stored heads once and writes it back to
the rows it came from (`ops/pallas/delta_step.py`: KDA's rule and this one,
by the decay's shape). Everywhere else (off a TPU, a mesh of several devices,
a state that is not float32 or whose block is not whole 8 x 128 tiles, picked
or fresh slots, training) the XLA step runs on the rows. The gauges
`decode/delta_step_calls/{kernel,xla}` say which of the two the layers of a
slab were traced into.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from llm_training_tpu.ops.paged_attention import _on_kernels
from llm_training_tpu.ops.pallas import resolve_interpret
from llm_training_tpu.ops.pallas.tuning import delta_step_heads
from llm_training_tpu.parallel.mesh import active_mesh
from llm_training_tpu.telemetry.registry import get_registry

# float32 end to end: a TPU otherwise multiplies float32 in bfloat16 passes,
# and the state is summed into over the whole sequence
EXACT = jax.lax.Precision.HIGHEST


def l2norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def short_conv(mixed, conv_w, tail, segment_ids, valid, parts, bias=None):
    """The causal depthwise convolution in front of a delta rule (or of a
    selective scan, whose convolution has a `bias [C]`), then SiLU.
    mixed `[B, S, C]` (the projections side by side), conv_w `[taps + 1, C]`
    float32, tail `[B, taps, C]` (the inputs before this call's first
    position: a decode slot's, or None for zeros), segment_ids `[B, S]` or
    None, valid `[B, S]`, `parts` as `jnp.split` takes them -> (`silu(conv)`
    float32 split into its projections, the new tail: the last `taps` inputs
    up to the last real position). A padded position feeds nothing, not the conv and
    not the tail; with segment ids a tap never crosses a document boundary,
    and the tail counts as the first position's own document (a request's
    earlier chunk)."""
    batch, seq, _ = mixed.shape
    taps = conv_w.shape[0] - 1
    mixed = jnp.where(valid[..., None], mixed, 0)
    if tail is None:
        tail = jnp.zeros((batch, taps, mixed.shape[-1]), mixed.dtype)
    padded = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)
    if segment_ids is not None:
        seg_p = jnp.concatenate(
            [jnp.broadcast_to(segment_ids[:, :1], (batch, taps)), segment_ids], axis=1
        )
    conv = 0.0
    for i in range(taps + 1):
        tap = padded[:, i:i + seq].astype(jnp.float32) * conv_w[i]
        if segment_ids is not None:
            tap = jnp.where((seg_p[:, i:i + seq] == segment_ids)[..., None], tap, 0.0)
        conv = conv + tap
    if bias is not None:
        conv = conv + bias
    out = jnp.split(jax.nn.silu(conv), parts, axis=-1)
    end = jnp.max(jnp.where(valid, jnp.arange(1, seq + 1), 0), axis=1)
    new_tail = jax.vmap(
        lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, taps, axis=0)
    )(padded, end)
    return out, new_tail


def pack_heads(state: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """`[..., H, dk, dv]` -> `[..., H / n, dk, n * dv]`: heads `n p .. n p +
    n - 1` side by side in row `p`."""
    if abreast == 1:
        return state
    *lead, heads, dk, dv = state.shape
    paired = state.reshape(*lead, heads // abreast, abreast, dk, dv)
    return jnp.moveaxis(paired, -3, -2).reshape(*lead, heads // abreast, dk, abreast * dv)


def unpack_heads(stored: jnp.ndarray, abreast: int) -> jnp.ndarray:
    """The inverse of `pack_heads`."""
    if abreast == 1:
        return stored
    *lead, rows, dk, wide = stored.shape
    paired = stored.reshape(*lead, rows, dk, abreast, wide // abreast)
    return jnp.moveaxis(paired, -2, -3).reshape(*lead, rows * abreast, dk, wide // abreast)


def _abreast(x: jnp.ndarray, rows: int, dv: int) -> jnp.ndarray:
    """A value a head `[B, H, *rest]` as the stored state sees it: `[B, H / n,
    *rest, n * dv]`, head `n p + j`'s value on lanes `j dv .. (j + 1) dv` of
    row `p`. A select over an iota, not a reshape: it fuses into the reader
    of the state and no array of the state's size is made for it."""
    batch, heads = x.shape[:2]
    n = heads // rows
    x = x.reshape(batch, rows, n, *x.shape[2:])
    if n == 1:
        return x[:, :, 0, ..., None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (n * dv,), 0) // dv
    out = x[:, :, 0, ..., None]
    for j in range(1, n):
        out = jnp.where(lane == j, x[:, :, j, ..., None], out)
    return out


def gated_delta_step(state, q, k, v, g, beta):
    """One token on the STORED state. state `[B, H / n, dk, n * dv]`; q, k
    `[B, H, dk]`; v `[B, H, dv]`; g, beta `[B, H]`; all float32 -> (state,
    out `[B, H, dv]`). The state is read twice and written once: both
    reductions run over the OLD state,

        o_t = alpha (S_{t-1}^T q) + (k . q) u,   u = beta (v - alpha S_{t-1}^T k),

    so they share one pass, and the update `alpha S + k u^T` is elementwise:
    it can be written where the state lies."""
    batch, heads, dv = v.shape
    rows = state.shape[1]
    alpha = jnp.exp(g)
    k_wide, q_wide = _abreast(k, rows, dv), _abreast(q, rows, dv)  # [B, P, dk, n dv]
    wide = lambda x: x.reshape(batch, rows, -1)  # [B, H, dv] <-> [B, P, n dv]
    per_lane = lambda x: wide(jnp.broadcast_to(x[..., None], (batch, heads, dv)))
    seen = jnp.sum(state * k_wide, axis=2)  # S^T k
    read = jnp.sum(state * q_wide, axis=2)  # S^T q
    alpha_wide = per_lane(alpha)
    write = per_lane(beta) * (wide(v) - alpha_wide * seen)
    out = alpha_wide * read + per_lane(jnp.sum(k * q, axis=-1)) * write
    state = alpha_wide[:, :, None] * state + k_wide * write[:, :, None]
    return state, out.reshape(batch, heads, dv)


# the layers of a decode slab whose one-token step the serving programs traced
# last run into the Pallas kernel, and into the XLA step: counted where the
# path is picked (`slab_rows`), in the trace the jit makes anyway.
# `serve/engine.py` zeroes both before it builds its programs
DELTA_STEP_GAUGES = {path: f"decode/delta_step_calls/{path}" for path in ("kernel", "xla")}


def reset_delta_step_calls() -> None:
    for gauge in DELTA_STEP_GAUGES.values():
        get_registry().gauge(gauge).set(0)


@flax.struct.dataclass
class SlabRows:
    """A recurrent layer's states where they lie: the whole slab `[layers,
    slots, P, dk, n * dv]` and which layer of it, row i the state of slot i."""

    slab: jnp.ndarray
    layer: jnp.ndarray | int


def slab_rows(slab, layer, as_they_lie: bool) -> SlabRows | None:
    """Which path a layer's one-token step takes: `SlabRows` where the kernel
    takes this slab's states, else None (the XLA step, on rows read out of
    the slab): rows that are not the slab's slots `as_they_lie` (picked or
    fresh ones), off a TPU, a mesh of several devices (a Mosaic kernel cannot
    be partitioned), a state that is not float32, a stored head that is not
    whole 8 x 128 tiles. Nothing here hangs on what a layer has of its own:
    the answer, and the count it leaves, stand for every layer of the slab."""
    mesh = active_mesh()
    kernel = (
        as_they_lie and _on_kernels("auto") and slab.dtype == jnp.float32
        and not (mesh is not None and mesh.size > 1)
        and slab.shape[-2] % 8 == 0 and slab.shape[-1] % 128 == 0
    )
    get_registry().gauge(DELTA_STEP_GAUGES["kernel" if kernel else "xla"]).set(slab.shape[0])
    return SlabRows(slab, layer) if kernel else None


def one_token_step(state, xla_step, q, k, v, log_decay, beta):
    """One token a row of a delta rule: -> `(state, out [B, H, dv])`. `state`
    is what `LayerCache.recurrent_rows(..., delta_step=True)` handed out: the
    rows `[B, ...]`, on which `xla_step` (`kda_step`, `gated_delta_step`)
    runs, or a `SlabRows`, whose layer the kernel advances where it lies
    (the `SlabRows` that comes back holds the new slab). q, k `[B, H, dk]`; v
    `[B, H, dv]`; beta `[B, H]`; `log_decay` `[B, H, dk]` (KDA: a key channel)
    or `[B, H]` (one a head); float32."""
    if not isinstance(state, SlabRows):
        return xla_step(state, q, k, v, log_decay, beta)
    from llm_training_tpu.ops.pallas.delta_step import delta_step

    slab = state.slab
    # the token's vectors are whole before the kernel lays them out. Laid out
    # straight from the block's projections, the compiler cuts the product
    # that feeds KDA's decay loose from the float32 chain behind it and
    # rounds it to bfloat16 on the way, which in front of the XLA step it
    # does not: the states then part from the XLA step's by 3e-4 a token
    # where they are bit for bit the same with this line (chip, PR 46:
    # PERF.md section 6)
    q, k, v, log_decay, beta = jax.lax.optimization_barrier((q, k, v, log_decay, beta))
    # the vectors the kernel turns a stored head: k, q (and a key channel's
    # decay) of each head abreast
    turned = (3 if log_decay.ndim == 3 else 2) * (k.shape[1] // slab.shape[2])
    out, slab = delta_step(
        slab, jnp.asarray(state.layer, jnp.int32), q, k, v, jnp.exp(log_decay), beta,
        block=delta_step_heads(*slab.shape[2:], turned), interpret=resolve_interpret(),
    )
    return state.replace(slab=slab), out


def gated_delta_chunked(q, k, v, g, beta, state, starts=None, chunk_size: int = 64,
                        precision=EXACT):
    """A sequence, from `state` to the state after it. q, k `[B, S, H, dk]`
    (normalised and scaled by the caller); v `[B, S, H, dv]`; g, beta `[B, S,
    H]`; state `[B, H, dk, dv]` (not packed); starts `[B, S]` bool or None;
    all float32 -> (out `[B, S, H, dv]`, state)."""
    batch, seq, heads, _ = q.shape
    dv = v.shape[-1]
    c = chunk_size
    pad = (-seq) % c
    if pad:  # zeros: beta 0 and g 0 change nothing
        widen = lambda x: jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        q, k, v, g, beta = map(widen, (q, k, v, g, beta))
        starts = None if starts is None else widen(starts)
    nc = (seq + pad) // c

    def chunked(x):  # [B, S, H, d] -> [B, H, nc, c, d]
        return x.reshape(batch, nc, c, heads, -1).transpose(0, 3, 1, 2, 4)

    q, k, v = chunked(q), chunked(k), chunked(v)
    g = g.reshape(batch, nc, c, heads).transpose(0, 3, 1, 2)  # [B, H, nc, c]
    beta = beta.reshape(batch, nc, c, heads).transpose(0, 3, 1, 2)
    v_beta = v * beta[..., None]
    k_beta = k * beta[..., None]

    g = jnp.cumsum(g, axis=-1)
    tril = jnp.tril(jnp.ones((c, c), bool))
    pair = tril
    from_state = to_state = keep = None
    if starts is not None:
        # documents begun inside the chunk up to each position
        begun = jnp.cumsum(starts.reshape(batch, 1, nc, c).astype(jnp.int32), axis=-1)
        pair = tril & (begun[..., :, None] == begun[..., None, :])
        from_state = (begun == 0)[..., None]  # still the incoming document
        to_state = (begun == begun[..., -1:])[..., None]  # the outgoing one
        keep = begun[..., -1] == 0  # [B, 1, nc]
    # e^(G_i - G_j) for j <= i: the later position first, so never above 1
    decay = jnp.where(pair, jnp.exp(jnp.minimum(g[..., :, None] - g[..., None, :], 0.0)), 0.0)

    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a_mat = jnp.where(
        strict, -jnp.einsum("bhncd,bhnmd->bhncm", k_beta, k, precision=precision) * decay, 0.0
    )
    eye = jnp.eye(c, dtype=jnp.float32)
    t_mat = jax.scipy.linalg.solve_triangular(
        eye - a_mat, jnp.broadcast_to(eye, a_mat.shape), lower=True, unit_diagonal=True
    )
    from_g = jnp.exp(g)[..., None]
    k_in, q_in = k_beta * from_g, q * from_g
    if from_state is not None:
        k_in, q_in = jnp.where(from_state, k_in, 0.0), jnp.where(from_state, q_in, 0.0)
    v_corr = jnp.einsum("bhncm,bhnmd->bhncd", t_mat, v_beta, precision=precision)
    k_cumdecay = jnp.einsum("bhncm,bhnmd->bhncd", t_mat, k_in, precision=precision)
    attn = jnp.einsum("bhncd,bhnmd->bhncm", q, k, precision=precision) * decay
    g_last = g[..., -1]  # [B, H, nc]
    k_out = k * jnp.exp(g_last[..., None] - g)[..., None]
    s_keep = jnp.exp(g_last)
    if to_state is not None:
        k_out = jnp.where(to_state, k_out, 0.0)
        s_keep = jnp.where(keep, s_keep, 0.0)

    lead = lambda x: jnp.moveaxis(x, 2, 0)  # [nc, B, H, ...] for the scan over chunks

    def step(s, xs):
        v_i, kc_i, q_i, attn_i, k_out_i, keep_i = xs
        v_new = v_i - jnp.einsum("bhcd,bhdv->bhcv", kc_i, s, precision=precision)
        out_i = jnp.einsum("bhcd,bhdv->bhcv", q_i, s, precision=precision) + jnp.einsum(
            "bhcm,bhmv->bhcv", attn_i, v_new, precision=precision
        )
        s = s * keep_i[..., None, None] + jnp.einsum(
            "bhcd,bhcv->bhdv", k_out_i, v_new, precision=precision
        )
        return s, out_i

    state, out = jax.lax.scan(
        step, state, tuple(map(lead, (v_corr, k_cumdecay, q_in, attn, k_out, s_keep)))
    )
    # [nc, B, H, c, dv] -> [B, S, H, dv]
    out = jnp.moveaxis(out, 0, 2).reshape(batch, heads, nc * c, dv)
    return out.transpose(0, 2, 1, 3)[:, :seq], state
