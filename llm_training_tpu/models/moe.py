"""Mixture-of-experts MLP block, TPU-native.

Family member beyond the reference's named models (it reaches MoE — Mixtral,
Qwen2/3-MoE — only through `HFCausalLM`'s torch wrapping,
`hf_causal_lm.py:22`); here the computation graph is native and dropless:

- router: fp32 softmax over expert logits, top-k, optional renormalization
  (HF `Qwen2MoeSparseMoeBlock`/`MixtralSparseMoeBlock` semantics).
- experts: ONE stacked parameter per projection ([E, H, I] / [E, I, H],
  logical axes ('expert', 'embed', 'mlp')), never E separate modules — the
  stacked layout is what makes both impls below a single large MXU op.
- 'ragged' impl (TPU training path): sort the T*K (token, expert-slot)
  assignments by expert, run the three projections as `jax.lax.ragged_dot`
  grouped matmuls, gather the rows back into the assignments' order and sum
  each token's K of them with the router's weights in float32 (`_combine`: a
  permutation and a reduction, no scatter-add, forward or backward: the chip
  walks a scatter's rows one after another). Static shapes
  ([T*K, ...] regardless of routing), no token dropping, no capacity factor
  — the modern JAX MoE formulation, vs the GShard one-hot dispatch einsum
  whose [T, E, C] tensors waste HBM at high expert counts.
- 'dense' impl (parity/debug): run every expert on every token and combine
  with the routing weights — exact, E/K-times the FLOPs; default off-TPU
  where tiny parity tests run.
- decoding (`grouped_matmul(..., layer=i)`): the ragged path reads layer i's
  experts INSIDE the layer-stacked parameter [L, E, ...], which reaches the
  block whole under a layer scan (`MoEMLP(..., stack=)`), so the stack is
  never cut up by copy; a looped layer's own matrices are a stack of one.
  Either way the product skips the experts no row chose and the rows held
  elsewhere (docs/inference.md, "How a layer meets a stacked weight").
- optional shared expert + sigmoid gate (Qwen2-MoE).
- load-balancing auxiliary loss (Switch/Mixtral form): E * sum_e f_e * P_e
  with f_e the fraction of (token, slot) assignments routed to e and P_e
  the mean fp32 router probability. Returned UNSCALED; the CLM objective
  applies `router_aux_loss_coef` (HF `load_balancing_loss_func` analogue).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from llm_training_tpu.ops.pallas import resolve_interpret
from llm_training_tpu.parallel.mesh import EXPERT_AXIS, active_mesh
from llm_training_tpu.telemetry.registry import get_registry

# the stacked expert leaves [E, K, N] of `MoEMLP`, in `ragged_fn`'s order:
# what a decoding layer loop hands the block whole (`models/cache.py:
# scan_layers`, `whole=`)
EXPERT_LEAVES = ("experts_gate_proj", "experts_up_proj", "experts_down_proj")
# how many expert layers of the serving programs traced last multiply through
# the in-place grouped matmul, block by block (a scan body's block stands for
# every repeat of it); `serve/engine.py` zeroes it before it builds its programs
IN_PLACE_GAUGE = "decode/experts_in_place_layers"
_in_place_layers: dict[tuple, int] = {}


def router_block_stats(topk_idx, probs, num_experts: int, pad_mask=None):
    """Shared per-layer router statistics: (sel_frac [E], mean_prob [E]).

    sel_frac counts each of the K selections per token (sums to ~top_k when
    balanced — HF `load_balancing_loss_func` scale); mean_prob is the mean
    fp32 routing probability. Padding tokens are excluded when `pad_mask`
    (flattenable to [T] bool) is given, like HF's attention-mask weighting —
    every MoE family routes its stats through here so the health metrics
    (`health/moe/*`, telemetry/health.py) are comparable across families."""
    n_tokens, top_k = topk_idx.shape
    if pad_mask is None:
        valid = jnp.ones((n_tokens,), jnp.float32)
    else:
        valid = pad_mask.reshape(-1).astype(jnp.float32)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    sel_frac = (
        jnp.zeros((num_experts,), jnp.float32)
        .at[topk_idx.reshape(-1)]
        .add(jnp.repeat(valid, top_k))
        / n_valid
    )
    mean_prob = (probs.astype(jnp.float32) * valid[:, None]).sum(axis=0) / n_valid
    return sel_frac, mean_prob


def _ep_group_size() -> int:
    """Size of the expert-parallel axis on the active mesh (1 = no EP)."""
    mesh = active_mesh()
    if mesh is None or EXPERT_AXIS not in mesh.shape:
        return 1
    return mesh.shape[EXPERT_AXIS]


def _resolved_impl(impl: str) -> str:
    if impl == "auto":
        return "ragged" if jax.default_backend() == "tpu" else "dense"
    return impl


# rows of `xs` one grid step of the in-place grouped matmul multiplies, and
# the bytes of one expert's matrix it brings: two such tiles, the rows, the
# output tile and its float32 accumulator fit the kernel's default VMEM.
# Tried on the chip (PR 31) at OLMoE's [9, 64, 2048, 1024], nine layers of
# three matmuls: 128 rows and the whole 4 MiB matrix a step were the fastest
# at 256 rows (10.0 ms; 10.0 to 10.9 for 32 to 128 rows and tiles of 1 to 4
# MiB, up to 12.5 at 256 rows a step) and at 4,096 (13.3 ms; up to 15.9).
# And (PR 43, `scripts/gmm_sweep.py`) at Solar's held share, 40 experts of
# [4096, 1280], one product: the whole of K beside 256 columns beat K halved
# beside 640 at 256 rows (0.322 for 0.370 ms) and at 4,096 (0.672 for 0.792);
# 32 to 128 rows a step within 3% of each other, 256 slower
_GMM_ROWS = 128
_GMM_WEIGHT_TILE = 4 << 20


def _gmm_tiling(rows: int, k: int, n: int, itemsize: int) -> tuple[int, int, int]:
    """(tm, tk, tn) of the in-place grouped matmul. The whole of K where a
    tile holds it, so a row's sum is ONE float32 accumulation, as
    `ragged_dot`'s is: N halved until a weight tile fits; where that stops
    at an odd count of 128-lane tiles still too large, N's largest divisor
    of whole lane tiles that fits beside the whole of K; K halved only
    when there is none."""
    tm = min(_GMM_ROWS, -(-rows // 16) * 16)
    fits = lambda tk, tn: tk * tn * itemsize <= _GMM_WEIGHT_TILE
    tk, tn = k, n
    while not fits(tk, tn) and tn % 256 == 0:
        tn //= 2
    if not fits(tk, tn):
        beside_whole_k = [d for d in range(128, tn, 128) if n % d == 0 and fits(k, d)]
        tn = max(beside_whole_k, default=tn)
    while not fits(tk, tn) and tk % 256 == 0:
        tk //= 2
    return tm, tk, tn


def assignment_counts(topk_idx, first: int, num_held: int, pad_mask=None, is_zero=None):
    """`[3]` int32 for a SHARE of the experts (`CausalLMOutput.
    moe_assignments`): of `topk_idx [T, K]`, the assignments to the experts
    held here (`first .. first + num_held`), to zero-compute experts
    (`is_zero [T, K]`; a router without them: 0) and to experts held
    elsewhere; tokens `pad_mask` marks as padding are left out."""
    live = jnp.ones((topk_idx.shape[0], 1), bool) if pad_mask is None else pad_mask.reshape(-1, 1)
    held_here = (topk_idx >= first) & (topk_idx < first + num_held)
    held = jnp.sum(live & held_here)
    if is_zero is None:
        zero, elsewhere = jnp.int32(0), live & ~held_here
    else:
        zero, elsewhere = jnp.sum(live & is_zero), live & ~held_here & ~is_zero
    return jnp.stack([held, zero, jnp.sum(elsewhere)]).astype(jnp.int32)


def reset_in_place_layers() -> None:
    _in_place_layers.clear()
    get_registry().gauge(IN_PLACE_GAUGE).set(0)


def decoding_experts(cache, stack, layer, *path):
    """What a decoder layer hands its MoE block as `stack`. No cache open
    (training): None, and the block multiplies its own matrices with
    `ragged_dot`, whose backward pass wants them. Decoding under a layer
    scan: `(leaves, layer)`, the scan's stacked `EXPERT_LEAVES` found under
    `path` in `stack` (`models/cache.py:scan_layers(whole=)`) and this
    layer's index among them. Decoding in a loop (no `stack`): `(None, 0)`,
    the block's own three matrices seen as a stack of one."""
    if cache is None:
        return None
    if stack is None:
        return None, 0
    for name in path:
        stack = stack[name]
    return stack, layer


def experts_in_place(stack, local, impl: str, compute_dtype, block: tuple):
    """`(weights, layer)` for a layer's grouped products (`grouped_matmul`).
    `stack` is a decoding layer's (`decoding_experts`), `local` this layer's
    own three matrices `[E, ...]`. Where the products can go through the
    grouped matmul that reads the stack where it lies (the ragged path, one
    device, leaves in the compute dtype): the leaves `[L, E, ...]` (`local`
    as `[1, E, ...]` for a looped layer, a free reshape) and the index;
    otherwise `local` and None. `block` is the calling module's path: the
    gauge counts each block's layers once, whichever programs trace it."""
    if (
        stack is not None
        and _resolved_impl(impl) == "ragged"
        # an expert mesh shards its slices (`_ep_ragged_apply`); any
        # other would have to partition a Mosaic kernel, and cannot
        and (active_mesh() is None or active_mesh().size == 1)
    ):
        leaves, layer = stack
        whole = (
            tuple(w[None] for w in local) if leaves is None
            else tuple(leaves[name] for name in EXPERT_LEAVES)
        )
        # a cast would copy the whole stack
        if all(w.dtype == compute_dtype for w in whole):
            _in_place_layers[block] = whole[0].shape[0]
            get_registry().gauge(IN_PLACE_GAUGE).set(sum(_in_place_layers.values()))
            return whole, layer
    return local, None


def grouped_matmul(xs, w, group_sizes, layer=None):
    """`xs [rows, K]`, sorted by expert, times the experts' matrices `w [E,
    K, N]`, `group_sizes [E]` rows each -> `[rows, N]`: `jax.lax.ragged_dot`.

    With `layer`, `w` is the layer-stacked parameter `[L, E, K, N]` and the
    product is with its layer `layer`, read where it lies: `ragged_dot`
    wants its operand as a buffer of its own, so layer i's slice of the
    stack is a 268 MB copy at OLMoE's widths, three a layer. Instead the
    stack is seen as `[L*E, K, N]` (a free reshape) with the sizes written
    into a zero `[L*E]` at `layer*E`, and given to jax's Pallas grouped
    matmul (megablox `gmm`), which picks each grid step's weight block
    through scalar prefetch and walks the row tiles of the NON-EMPTY groups
    only: what it fetches is layer i's experts that have rows. The
    arithmetic is `ragged_dot`'s: operands as they come, float32
    accumulation, `xs`'s dtype out. Rows past the last group (a held share
    leaves such rows) come out zero; `ragged_dot` defines nothing there
    (on the chip they are not zero), so a caller selects them away."""
    if layer is None:
        return jax.lax.ragged_dot(xs, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    num_layers, num_experts, k, n = w.shape
    rows = xs.shape[0]
    tm, tk, tn = _gmm_tiling(rows, k, n, w.dtype.itemsize)
    sizes = lax.dynamic_update_slice(
        jnp.zeros((num_layers * num_experts,), jnp.int32),
        group_sizes.astype(jnp.int32), (layer * num_experts,),
    )
    out = gmm(
        jnp.pad(xs, ((0, -rows % tm), (0, 0))), w.reshape(-1, k, n), sizes,
        preferred_element_type=xs.dtype, tiling=(tm, tk, tn),
        interpret=resolve_interpret(),
    )
    # the kernel stores a group's rows and nothing else
    in_a_group = jnp.arange(rows) < group_sizes.sum()
    return jnp.where(in_a_group[:, None], out[:rows], 0)


def _ep_ragged_apply(
    x, topk_idx, topk_weights, num_experts, ragged_fn, weights,
    ep: int, capacity_factor: float,
):
    """Expert-parallel dropless-ish dispatch under `shard_map` (manual over
    the expert axis only; data/fsdp/tensor/sequence stay GSPMD-auto).

    Each EP rank owns E/ep experts (stacks sharded on their leading dim by
    the `expert` rule). Tokens are batch-sharded across EP ranks, so the
    dispatch is: all-gather the EP group's tokens + routing, pick the rows
    routed to local experts into a STATIC per-rank capacity buffer
    (ceil(T_group·K/ep · capacity_factor) rows — overflow beyond the buffer
    is dropped, which the factor makes vanishingly rare for balanced
    routing), run the grouped matmuls on the local stacks, scatter-add the
    weighted outputs into the group buffer, and reduce-scatter every rank's
    combined tokens back home. Per-rank compute is capacity rows — true
    EP scaling — at 2 collectives (gather fwd, scatter fwd ⇒ mirrored in
    the backward) per MoE layer, riding ICI on the `expert` axis.
    """
    mesh = active_mesh()
    e_local = num_experts // ep
    hidden = x.shape[-1]
    top_k = topk_idx.shape[-1]
    t_all = x.shape[0]
    # a factor > ep would exceed the total row count; sel below slices
    # exactly `capacity` rows, so clamp to keep shapes consistent
    capacity = min(
        math.ceil(t_all * top_k / ep * capacity_factor), t_all * top_k
    )

    w_leaves, w_def = jax.tree.flatten(weights)
    # XLA:CPU cannot compile bf16 crossing this partial-auto shard_map
    # boundary ("invalid binary instruction opcode copy" compiler CHECK, jax
    # 0.9.0) — tests and the multichip dryrun run the EP math in f32 there;
    # the TPU backend keeps the compute dtype.
    out_dtype = x.dtype
    if jax.default_backend() == "cpu":
        as_f32 = lambda a: (
            a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating) else a
        )
        x, topk_weights = as_f32(x), as_f32(topk_weights)
        w_leaves = [as_f32(leaf) for leaf in w_leaves]

    def body(x_all, idx_all, wts_all, *w_leaves):
        # token/routing arrays arrive replicated over the expert axis — the
        # in_spec makes GSPMD insert the all-gather as an auto collective.
        # (A manual lax.all_gather of bf16 inside partial-auto shard_map
        # crashes the XLA CPU backend — "invalid binary instruction opcode
        # copy" — while the auto gather and the manual psum_scatter below
        # compile everywhere.)
        w_local = jax.tree.unflatten(w_def, w_leaves)
        lo = lax.axis_index(EXPERT_AXIS) * e_local

        with jax.named_scope("moe_sort"):
            flat_e = idx_all.reshape(-1)
            flat_w = wts_all.reshape(-1)
            flat_tok = jnp.arange(t_all * top_k) // top_k
            rel = flat_e - lo
            local = (rel >= 0) & (rel < e_local)
            # local rows first (sorted by expert), non-local rows pushed last
            order = jnp.argsort(jnp.where(local, rel, e_local))
            sel = order[:capacity]
            sel_tok = flat_tok[sel]

            counts = jnp.bincount(
                jnp.where(local, rel, e_local), length=e_local + 1
            )[:e_local]
            start = jnp.cumsum(counts) - counts
            # rows are expert-sorted, so clipping to the buffer drops exactly
            # the rows that did not fit
            gs = jnp.clip(jnp.minimum(counts, capacity - start), 0)
            total = gs.sum()

        with jax.named_scope("moe_gather"):
            xs = x_all[sel_tok]
        with jax.named_scope("moe_experts"):
            ys = ragged_fn(
                xs,
                gs.astype(jnp.int32),
                jnp.clip(rel[sel], 0, e_local - 1),
                w_local,
            )
        with jax.named_scope("moe_scatter"):
            valid = jnp.arange(capacity) < total  # local rows sort first
            ys = ys * (flat_w[sel] * valid).astype(ys.dtype)[:, None]
            out_all = jnp.zeros((t_all, hidden), ys.dtype).at[sel_tok].add(ys)
        # (token, expert) rows routed to this rank's experts that did not
        # fit the capacity buffer — the silent quality hazard of static
        # capacity; summed over the EP group and surfaced as a train metric
        dropped = lax.psum(
            (counts.sum() - total).astype(jnp.float32), EXPERT_AXIS
        )
        return (
            lax.psum_scatter(out_all, EXPERT_AXIS, scatter_dimension=0, tiled=True),
            dropped,
        )

    out, dropped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(), P()) + tuple(P(EXPERT_AXIS) for _ in w_leaves),
        out_specs=(P(EXPERT_AXIS), P()),
        axis_names={EXPERT_AXIS},
        check_vma=False,
    )(x, topk_idx, topk_weights, *w_leaves)
    return out.astype(out_dtype), dropped


def sparsemixer_topk(logits, jitter_eps: float, top_k: int = 2):
    """Phi-3.5-MoE SparseMixer routing, deterministic (inference) form.

    HF's `sparsemixer` (modeling_phimoe.py) selects experts sequentially:
    pick the argmax, weight it by a softmax over only the logits within a
    2*jitter_eps relative band of the max (everything else masked to -inf),
    then mask the picked expert out and repeat. Weights are NOT
    renormalized across the k picks. The training-time extras (Gumbel
    sampling + the Heun third-order gradient estimator of
    arXiv 2409.12136) are stochastic-estimation machinery, not a different
    function; fine-tuning here differentiates the deterministic form
    through the softmax weights like every other routed family.
    """
    if top_k != 2:
        raise ValueError("sparsemixer routing is defined for top_k=2")

    def pick(scores):
        m = scores.max(axis=-1, keepdims=True)
        factor = jnp.maximum(jnp.abs(scores), m)
        mask = ((m - scores) / factor) > (2 * jitter_eps)
        gates = jax.nn.softmax(jnp.where(mask, -jnp.inf, scores), axis=-1)
        idx = scores.argmax(axis=-1)
        w = jnp.take_along_axis(gates, idx[:, None], axis=-1)[:, 0]
        return idx, w

    i1, w1 = pick(logits)
    masked = jnp.where(
        jax.nn.one_hot(i1, logits.shape[-1], dtype=bool), -jnp.inf, logits
    )
    i2, w2 = pick(masked)
    return jnp.stack([w1, w2], axis=-1), jnp.stack([i1, i2], axis=-1)


@jax.named_scope("moe_sort")
def _sorted_dispatch(topk_idx, topk_weights, num_experts):
    """Shared dispatch prelude: (flat_weight, flat_token, order, gs) for the
    expert-sorted row layout both the ragged and bucketed paths consume."""
    n_tokens, top_k = topk_idx.shape
    flat_expert = topk_idx.reshape(-1)
    flat_weight = topk_weights.reshape(-1)
    flat_token = jnp.arange(n_tokens * top_k) // top_k
    order = jnp.argsort(flat_expert)  # stable: rows sorted by expert
    gs = jnp.bincount(flat_expert, length=num_experts).astype(jnp.int32)
    return flat_expert, flat_weight, flat_token, order, gs


@jax.custom_vjp
def _unsort(rows, order):
    """`rows [T*K, H]`, sorted by expert (`rows[j]` is assignment `order[j]`'s),
    back in the assignments' own order: a gather of rows by the inverse
    permutation. The transpose of a gather is a scatter-add, which the chip
    walks a row at a time, so the backward pass is told that a permutation's
    is a gather too, `g[order]`."""
    return rows[jnp.argsort(order)]


def _unsort_fwd(rows, order):
    return _unsort(rows, order), order


def _unsort_bwd(order, g):
    return g[order], None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def _combine(ys, order, topk_weights, mine, dtype):
    """The experts' rows `ys [T*K, H]` (sorted by expert: `order`, of
    `_sorted_dispatch`) summed a token with the router's `topk_weights [T,
    K]` -> `[T, H]` in `dtype`. Every token has exactly K rows, so the sum
    needs no scatter: the rows are gathered back into the assignments' order
    and reduced over K, ONE float32 sum a token, rounded once. `mine [T, K]`
    or None: the assignments whose rows count (a held share's); the others
    are selected to zero before the product (0 x NaN is NaN)."""
    n_tokens, top_k = topk_weights.shape
    with jax.named_scope("moe_scatter"):
        rows = _unsort(ys, order).reshape(n_tokens, top_k, -1)
        if mine is not None:
            rows = jnp.where(mine[..., None], rows, 0)
        weighted = rows.astype(jnp.float32) * topk_weights.astype(jnp.float32)[..., None]
        return weighted.sum(axis=1).astype(dtype)


def _bucketed_apply(
    x, topk_idx, topk_weights, num_experts, bmm_fn, capacity_factor: float
):
    """Fixed-capacity bucket dispatch: sort the (token, slot) assignments by
    expert, gather bucket e's first C rows into a dense [E, C, H] operand,
    run ONE batched matmul stack (`bmm_fn`), weighted-scatter back. Rows
    beyond an expert's capacity are DROPPED (classic GShard/Switch
    semantics — counted and returned, cf. the ep path); in exchange every
    matmul is a dense MXU bmm, for shapes where `ragged_dot`'s grouped
    lowering underperforms (never measured on the chip: PERF.md section 7).
    """
    n_tokens, top_k = topk_idx.shape
    hidden = x.shape[-1]
    rows = n_tokens * top_k
    capacity = min(math.ceil(rows / num_experts * capacity_factor), rows)

    _, flat_weight, flat_token, order, gs = _sorted_dispatch(
        topk_idx, topk_weights, num_experts
    )
    with jax.named_scope("moe_gather"):
        start = jnp.cumsum(gs) - gs
        offs = jnp.arange(capacity)
        # bucket e, slot c -> index into the sorted rows (clamped; invalid
        # slots masked to zero contribution)
        src_sorted = jnp.clip(start[:, None] + offs[None, :], 0, rows - 1)
        valid = offs[None, :] < gs[:, None]  # [E, capacity]
        src = order[src_sorted.reshape(-1)]  # -> original (token, slot) rows
        tok = flat_token[src]
        xb = jnp.where(
            valid.reshape(-1)[:, None], x[tok], 0
        ).reshape(num_experts, capacity, hidden)
    with jax.named_scope("moe_experts"):
        yb = bmm_fn(xb)  # [E, capacity, H]
    with jax.named_scope("moe_scatter"):
        w = (flat_weight[src] * valid.reshape(-1).astype(flat_weight.dtype))
        ys = yb.reshape(-1, hidden) * w.astype(yb.dtype)[:, None]
        out = jnp.zeros((n_tokens, hidden), x.dtype).at[tok].add(
            ys.astype(x.dtype)
        )
    dropped = (rows - jnp.minimum(gs, capacity).sum()).astype(jnp.float32)
    return out, dropped


def dropless_moe_apply(
    x: jnp.ndarray,
    topk_idx: jnp.ndarray,
    topk_weights: jnp.ndarray,
    num_experts: int,
    impl: str,
    dense_fn,
    ragged_fn,
    weights=None,
    ep_capacity_factor: float = 2.0,
    bmm_fn=None,
    moe_capacity_factor: float = 1.25,
    held: tuple[int, int] | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Shared dropless dispatch/combine for every MoE family.

    `held = (first, count)`: an expert-parallel SHARE run without its
    exchange. The router chose among `num_experts`; this call holds experts
    `first .. first + count` only (`weights`, `dense_fn` and `ragged_fn` see
    `count` experts) and computes their part of the sum. Assignments to
    experts held elsewhere are left out, their weights having been
    normalised over all the chosen ones by the caller.

    x: [T, H] compute-dtype tokens; topk_idx/topk_weights: [T, K].
    dense_fn(x) -> [T, E, H] (every expert on every token — exact path);
    ragged_fn(xs, group_sizes, expert_order, weights) -> [rows, H] where xs
    are the (token, slot) rows sorted by expert and expert_order the
    matching (stack-relative) expert id per row (for per-expert bias
    lookups). `weights` is the pytree of stacked expert parameters (leading
    dim E) that ragged_fn consumes — passed explicitly so the
    expert-parallel path can hand each rank its local slice.

    `bmm_fn(xb [E, C, H]) -> [E, C, H]` (batched dense expert stack) enables
    `impl='bucketed'`; families that do not provide it reject that impl.

    Every path names its phases for a device profile (`jax.named_scope`, op
    metadata only): `moe_sort` (argsort + group sizes), `moe_gather`,
    `moe_experts` (the grouped or batched matmuls), `moe_scatter` (the
    weighted combine: on the ragged path of one device a gather of the
    sorted rows and a float32 sum over each token's K, `_combine`; on the
    dense path an einsum; a scatter-add into the capacity buffer on the
    expert-parallel and bucketed paths); the router's `moe_route` is the
    calling module's (docs/observability.md#tracing; benchmarks read them by
    name).

    Returns (out [T, H], dropped_rows fp32 scalar): dropped_rows counts
    (token, slot) assignments lost to a capacity buffer (expert-parallel
    rank buffer, or the per-expert buckets of impl='bucketed') this call —
    exactly 0 on the truly-dropless dense/ragged single-rank paths.
    """
    n_tokens, top_k = topk_idx.shape
    no_drops = jnp.float32(0.0)
    impl = _resolved_impl(impl)
    mine = None  # of a held share: the assignments to the experts held here
    if held is not None:
        if impl not in ("dense", "ragged") or _ep_group_size() > 1:
            raise ValueError(
                "a held share of the experts runs the dense or ragged path "
                f"off an expert mesh; got moe_impl={impl!r}"
            )
        first, count = held
        local = topk_idx - first
        mine = (local >= 0) & (local < count)
        # what is held elsewhere sorts into one group past the held ones,
        # with no weight: no expert here computes it
        topk_idx = jnp.where(mine, local, count)
        topk_weights = jnp.where(mine, topk_weights, 0)
        num_experts = count + 1
    if impl not in ("dense", "ragged", "bucketed"):
        # fail loudly: a typo'd impl silently measuring the ragged path
        # would corrupt exactly the A/B comparisons this knob exists for
        raise ValueError(
            f"unknown moe_impl {impl!r}; expected auto/dense/ragged/bucketed"
        )
    if impl == "bucketed":
        if bmm_fn is None:
            raise ValueError(
                "moe_impl='bucketed' needs the family to provide bmm_fn "
                "(currently: the Llama-family MoEMLP)"
            )
        if _ep_group_size() > 1:
            raise ValueError(
                "moe_impl='bucketed' does not compose with expert "
                "parallelism yet; use 'ragged' on EP meshes"
            )
        return _bucketed_apply(
            x, topk_idx, topk_weights, num_experts, bmm_fn, moe_capacity_factor
        )
    if impl == "dense":
        with jax.named_scope("moe_experts"):
            y = dense_fn(x)
        with jax.named_scope("moe_scatter"):
            combine = jnp.zeros((n_tokens, num_experts), x.dtype)
            combine = combine.at[
                jnp.arange(n_tokens)[:, None], topk_idx
            ].set(topk_weights)
            if held is not None:
                combine = combine[:, :-1]
            return jnp.einsum("teh,te->th", y, combine), no_drops
    ep = _ep_group_size()
    if ep > 1:
        if num_experts % ep:
            raise ValueError(
                f"num_experts ({num_experts}) must divide by the expert mesh "
                f"axis ({ep})"
            )
        return _ep_ragged_apply(
            x, topk_idx, topk_weights, num_experts, ragged_fn, weights,
            ep, ep_capacity_factor,
        )
    flat_expert, _, flat_token, order, group_sizes = _sorted_dispatch(
        topk_idx, topk_weights, num_experts
    )
    with jax.named_scope("moe_gather"):
        token_order = flat_token[order]
        xs, expert_order = x[token_order], flat_expert[order]
    if held is not None:
        # the rows past the held groups belong to no group: whatever the
        # product leaves there, the combine's select drops
        group_sizes = group_sizes[:-1]
    with jax.named_scope("moe_experts"):
        ys = ragged_fn(xs, group_sizes, expert_order, weights)
    return _combine(ys, order, topk_weights, mine, x.dtype), no_drops


class MoEMLP(nn.Module):
    """Sparse MoE block with the (config-driven) surface of LlamaMLP.

    __call__(hidden [B, S, H], pad_mask [B, S] bool | None) ->
    (out [B, S, H], (sel_frac [E], mean_prob [E], dropped scalar) fp32
    router stats — `dropped` counts EP capacity-buffer losses, 0 off-EP).
    `stack = (leaves, layer)`, from a decoding layer (`decoding_experts`):
    under a scan this module's `EXPERT_LEAVES` with every layer's experts,
    `[L, E, ...]`, and which layer this is; `(None, 0)` from a looped one.
    The ragged path on one device then multiplies with the stack in place
    (`grouped_matmul`) and, under a scan, this layer's slices of those
    three parameters go unread, so the compiler drops the cut.
    The caller pools the per-layer stats across depth and applies the
    Switch/Mixtral formula E * sum(f * P) — pooling BEFORE the product is
    what HF's `load_balancing_loss_func` does (it concatenates every
    layer's gate logits first), and it keeps the loss ~top_k when balanced
    regardless of depth (HF counts each of the K selections per token, and
    its coefficient is calibrated against that scale). Padding tokens are
    excluded from both statistics, like HF's attention-mask weighting.
    """

    config: object  # LlamaConfig with num_experts set

    @nn.compact
    def __call__(
        self,
        hidden: jnp.ndarray,
        pad_mask: jnp.ndarray | None = None,
        stack: tuple | None = None,
    ) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
        cfg = self.config
        num_experts = cfg.num_experts
        top_k = cfg.num_experts_per_tok
        inter = cfg.moe_intermediate_size
        compute_dtype = cfg.compute_jnp_dtype
        param_dtype = cfg.param_jnp_dtype
        batch, seq, embed = hidden.shape
        x = hidden.reshape(-1, embed)  # [T, H]
        n_tokens = x.shape[0]

        # ---- router (fp32 softmax: HF computes routing in float)
        router = nn.Dense(
            num_experts,
            use_bias=False,
            dtype=compute_dtype,
            param_dtype=param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.normal(cfg.initializer_range), ("embed", "expert")
            ),
            name="gate",
        )
        with jax.named_scope("moe_route"):
            logits = router(x).astype(jnp.float32)  # [T, E]
            probs = jax.nn.softmax(logits, axis=-1)  # full softmax (router stats)
            if getattr(cfg, "moe_router_impl", "softmax") == "sparsemixer":
                # Phi-3.5-MoE's deterministic (eval-mode) SparseMixer selection
                topk_probs, topk_idx = sparsemixer_topk(
                    logits, getattr(cfg, "router_jitter_eps", 0.01), top_k
                )
            else:
                topk_probs, topk_idx = jax.lax.top_k(probs, top_k)  # [T, K]
                if cfg.norm_topk_prob:
                    topk_probs = topk_probs / topk_probs.sum(
                        axis=-1, keepdims=True
                    )
            topk_probs = topk_probs.astype(compute_dtype)

        # ---- stacked expert weights
        def expert_param(name, shape, axes):
            return self.param(
                name,
                nn.with_logical_partitioning(
                    nn.initializers.normal(cfg.initializer_range), axes
                ),
                shape,
                param_dtype,
            ).astype(compute_dtype)

        w_gate = expert_param(
            "experts_gate_proj", (num_experts, embed, inter), ("expert", "embed", "mlp")
        )
        w_up = expert_param(
            "experts_up_proj", (num_experts, embed, inter), ("expert", "embed", "mlp")
        )
        w_down = expert_param(
            "experts_down_proj", (num_experts, inter, embed), ("expert", "mlp", "embed")
        )

        def dense_fn(xc):
            gate = jnp.einsum("th,ehi->tei", xc, w_gate)
            up = jnp.einsum("th,ehi->tei", xc, w_up)
            return jnp.einsum("tei,eih->teh", nn.silu(gate) * up, w_down)

        weights, layer = experts_in_place(
            stack, (w_gate, w_up, w_down), cfg.moe_impl, compute_dtype, self.path
        )

        def ragged_fn(xs, group_sizes, expert_order, w):
            wg, wu, wd = w
            gate = grouped_matmul(xs, wg, group_sizes, layer)
            up = grouped_matmul(xs, wu, group_sizes, layer)
            return grouped_matmul(nn.silu(gate) * up, wd, group_sizes, layer)

        def bmm_fn(xb):  # [E, C, H] dense bucket stack (moe_impl='bucketed')
            gate = jnp.einsum(
                "ech,ehi->eci", xb, w_gate, preferred_element_type=compute_dtype
            )
            up = jnp.einsum(
                "ech,ehi->eci", xb, w_up, preferred_element_type=compute_dtype
            )
            return jnp.einsum(
                "eci,eih->ech", nn.silu(gate) * up, w_down,
                preferred_element_type=compute_dtype,
            )

        out, dropped = dropless_moe_apply(
            x.astype(compute_dtype), topk_idx, topk_probs, num_experts,
            cfg.moe_impl, dense_fn, ragged_fn,
            weights=weights,
            ep_capacity_factor=getattr(cfg, "ep_capacity_factor", 2.0),
            bmm_fn=bmm_fn,
            moe_capacity_factor=getattr(cfg, "moe_capacity_factor", 1.25),
        )

        # ---- shared expert: dense SwiGLU, gated per token by a sigmoid
        # (Qwen2-MoE) or always-on (granitemoeshared)
        if cfg.shared_expert_intermediate_size:
            xc = x.astype(compute_dtype)
            si = cfg.shared_expert_intermediate_size
            sw_gate = expert_param("shared_gate_proj", (embed, si), ("embed", "mlp"))
            sw_up = expert_param("shared_up_proj", (embed, si), ("embed", "mlp"))
            sw_down = expert_param("shared_down_proj", (si, embed), ("mlp", "embed"))
            shared = (nn.silu(xc @ sw_gate) * (xc @ sw_up)) @ sw_down
            if getattr(cfg, "shared_expert_gated", True):
                gate_w = self.param(
                    "shared_expert_gate",
                    nn.with_logical_partitioning(
                        nn.initializers.normal(cfg.initializer_range), ("embed", None)
                    ),
                    (embed, 1),
                    param_dtype,
                ).astype(compute_dtype)
                shared = jax.nn.sigmoid(xc @ gate_w) * shared
            out = out + shared

        # ---- router statistics for the load-balancing loss (fp32),
        # excluding padding tokens. NOT divided by top_k: HF's
        # load_balancing_loss_func counts each of the K selections per token
        # (its balanced loss value is top_k, not 1.0), and
        # router_aux_loss_coef is imported verbatim from HF configs, so the
        # fraction must carry the same scale
        sel_frac, mean_prob = router_block_stats(
            topk_idx, probs, num_experts, pad_mask
        )

        return (
            out.reshape(batch, seq, embed).astype(hidden.dtype),
            (sel_frac, mean_prob, dropped),
        )
