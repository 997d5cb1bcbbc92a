"""Mamba-1's selective scan: the state-space recurrence `phi4flash` trains
and serves with. A CHANNEL's state is a vector of `N` values, float32:

    s_t = exp(delta_t A) * s_{t-1} + (delta_t x_t) B_t
    y_t = s_t . C_t

with `A [C, N]` negative (one decay a channel AND a state value, which is
what keeps this rule out of matrix products: Mamba-2 made the decay a scalar
a head to get them back), `delta_t [C]` positive, `B_t`, `C_t [N]` shared by
the channels. Positions that must change nothing (padding, idle decode slots,
slots still prefilling) carry delta = 0: the decay is 1 and nothing is
written. `starts` marks the first token of a packed document: the state is
zero before it.

The state is held as the slab stores it (`models/base.py:RecurrentCacheSpec`
with `key_dim = N`): the channels cut into `H` runs of `V` lanes, `[B, H, N,
V]`, `C = H * V`, so that a row is whole 128-lane tiles (`[5120, 16]` is
`[40, 16, 128]`) and every operation below is elementwise over full lanes or
a sum over the `N` axis. `selective_step` is the equation for one token on a
decode slot's state, written where the state lies; `selective_scan` a whole
sequence from a state to a state: a loop over time, eight tokens a trip, so
that nothing of the size `[S, C, N]` is made. (An associative scan over time
in chunks of 128, `(a1, b1) . (a2, b2) = (a1 a2, a2 b1 + b2)`, was this
function until the chip timed it: its `[128, C, N]` products are padded and
sliced fourteen times a chunk, 30% of a serving window's device time for 6%
of its steps; PERF.md section 6, PR 45.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

TOKENS_A_TRIP = 8  # of `selective_scan`'s loop over time


def _stored(a, heads: int):
    """`A [C, N]` as the stored state sees it: `[H, N, V]`."""
    channels, n = a.shape
    return a.reshape(heads, channels // heads, n).swapaxes(1, 2)


def selective_step(state, x, delta, a, b, c):
    """One token. state `[B, H, N, V]`; x, delta `[B, C]`; a `[C, N]`; b, c
    `[B, N]`; all float32 -> (state, y `[B, C]`). The update is elementwise
    in the state and the readout a sum over `N` of the NEW state: one pass
    in, one pass out."""
    batch, heads, _, lanes = state.shape
    wide = lambda t: t.reshape(batch, heads, 1, lanes)
    state = jnp.exp(wide(delta) * _stored(a, heads)) * state + wide(delta * x) * b[:, None, :, None]
    y = jnp.sum(state * c[:, None, :, None], axis=2)
    return state, y.reshape(batch, heads * lanes)


def selective_scan(x, delta, a, b, c, state, starts=None):
    """A sequence, from `state` to the state after it. x, delta `[B, S, C]`;
    a `[C, N]`; b, c `[B, S, N]`; state `[B, H, N, V]`; starts `[B, S]` bool
    or None; all float32 -> (y `[B, S, C]`, state)."""
    by_time = lambda t: None if t is None else jnp.moveaxis(t, 1, 0)

    def one_token(state, token):
        x_t, delta_t, b_t, c_t, start_t = token
        if start_t is not None:
            state = jnp.where(start_t[:, None, None, None], 0.0, state)
        return selective_step(state, x_t, delta_t, a, b_t, c_t)

    state, y = jax.lax.scan(
        one_token, state, tuple(map(by_time, (x, delta, b, c, starts))), unroll=TOKENS_A_TRIP
    )
    return by_time(y), state
