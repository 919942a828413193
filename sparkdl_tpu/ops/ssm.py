"""The Mamba-2 recurrence (a selective state space with a scalar decay a
head) over ragged rows, in two forms that give the same numbers:

- :func:`ssd_chunked` — whole segments in the chunked matmul form (the
  "state-space dual"): inside a chunk of ``chunk`` positions the
  decay-masked ``C Bᵀ`` product, between chunks the carried state;
- :func:`ssm_update` — one token a row against the state (decode);

and the depthwise causal convolution in front of it, likewise
(:func:`causal_conv`, :func:`conv_update`).

Per head, with ``a = -exp(A_log) < 0`` a scalar and ``dt_t > 0``::

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * x_t (outer) B_t      # [P, N]
    y_t = S_t C_t                                               # [P]

(the skip ``D * x_t`` is the caller's).  One group: every head reads the same
``B_t`` and ``C_t`` [N].

Ragged rows: a position with ``dt = 0`` leaves the state as it was
(``exp(0 * a) = 1``, ``0 * x (outer) B = 0``), so the caller zeroes ``dt`` at
a row's pad positions and the state that comes back is the state after the
row's OWN last real token, wherever in the segment that was.  The conv window
that comes back is the row's last ``K - 1`` real inputs (``lengths``).

The state is float32 throughout.  The products take their operands in the
inputs' dtype (``computeDtype``) and accumulate in float32; the decays, their
running sums and the exponentials are float32.  Plain ``jax.numpy``: the XLA
compiler's own fusions, no kernel of this repo.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_conv(xbc, window, weight, bias, lengths):
    """``silu(bias + sum_j weight[:, j] * input[t - (K-1) + j])`` along a
    segment: ``xbc`` [r, n, C], ``window`` [r, K-1, C] the inputs just before
    the segment (zeros at a row's start), ``weight`` [C, K], ``bias`` [C],
    ``lengths`` [r] the real positions of each row in this segment.

    Returns ``(out [r, n, C], window [r, K-1, C])``; the new window holds the
    inputs at ``lengths - (K-1) .. lengths - 1`` (reaching back into the old
    window where the row has fewer)."""
    k = weight.shape[-1]
    n = xbc.shape[1]
    full = jnp.concatenate([window.astype(xbc.dtype), xbc], axis=1)
    w = weight.astype(jnp.float32)
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + full[:, j:j + n].astype(jnp.float32) * w[:, j]
    kept = jax.vmap(
        lambda row, at: jax.lax.dynamic_slice_in_dim(row, at, k - 1, axis=0)
    )(full, lengths)
    return jax.nn.silu(out).astype(xbc.dtype), kept


def conv_update(xbc, window, weight, bias):
    """One position: ``xbc`` [r, C] against ``window`` [r, K-1, C].  Returns
    ``(out [r, C], window)`` with the window moved on by one."""
    full = jnp.concatenate([window.astype(xbc.dtype), xbc[:, None]], axis=1)
    out = jnp.sum(
        full.astype(jnp.float32) * weight.astype(jnp.float32).T, axis=1
    ) + bias.astype(jnp.float32)
    return jax.nn.silu(out).astype(xbc.dtype), full[:, 1:]


def ssd_chunked(x, dt, a, b, c, state, chunk: int):
    """The recurrence over a segment, chunk by chunk.

    ``x`` [r, n, H, P]; ``dt`` [r, n, H] float32, zero at pads; ``a`` [H]
    float32, negative; ``b`` and ``c`` [r, n, N]; ``state`` [r, H, P, N]
    float32, the state before the segment's first position.  ``n`` need not
    be a multiple of ``chunk``: the tail is padded with ``dt = 0``.

    Returns ``(y [r, n, H, P] float32, state [r, H, P, N] float32)``.

    Inside a chunk, with ``cum_t = sum_{s <= t} dt_s a`` (float32, <= 0 and
    falling): ``y_t = exp(cum_t) S_in C_t + sum_{s <= t} exp(cum_t - cum_s)
    (C_t . B_s) dt_s x_s`` — every exponent is <= 0, nothing overflows — and
    ``S_out = exp(cum_Q) S_in + sum_s exp(cum_Q - cum_s) dt_s x_s (outer)
    B_s``."""
    r, n, h, p = x.shape
    q = min(int(chunk), n)
    pad = -n % q
    if pad:
        x, dt, b, c = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (x, dt, b, c))
    chunks = (n + pad) // q

    def split(t):  # [r, chunks * q, ...] -> [chunks, r, q, ...]
        return jnp.swapaxes(t.reshape(r, chunks, q, *t.shape[2:]), 0, 1)

    causal = jnp.tril(jnp.ones((q, q), bool))  # [t, s]: s <= t

    def one_chunk(state, xs):
        x, dt, b, c = xs
        cum = jnp.cumsum(dt * a, axis=1).transpose(0, 2, 1)  # [r, H, q]
        span = cum[:, :, :, None] - cum[:, :, None, :]  # [r, H, t, s]
        decay = jnp.exp(jnp.where(causal, span, -jnp.inf))
        cb = jnp.einsum("rtn,rsn->rts", c, b,
                        preferred_element_type=jnp.float32)
        weights = (cb[:, None] * decay).astype(x.dtype)
        xdt = (x.astype(jnp.float32) * dt[..., None])  # [r, q, H, P]
        inside = jnp.einsum("rhts,rshp->rthp", weights, xdt.astype(x.dtype),
                            preferred_element_type=jnp.float32)
        carried = jnp.einsum("rtn,rhpn->rthp", c, state.astype(c.dtype),
                             preferred_element_type=jnp.float32)
        y = inside + carried * jnp.exp(cum).transpose(0, 2, 1)[..., None]
        to_end = jnp.exp(cum[:, :, -1:] - cum).transpose(0, 2, 1)  # [r, q, H]
        state = (
            state * jnp.exp(cum[:, :, -1])[:, :, None, None]
            + jnp.einsum("rshp,rsn->rhpn",
                         (xdt * to_end[..., None]).astype(x.dtype), b,
                         preferred_element_type=jnp.float32))
        return state, y

    state, y = jax.lax.scan(
        one_chunk, state.astype(jnp.float32), tuple(map(split, (x, dt, b, c))))
    y = jnp.swapaxes(y, 0, 1).reshape(r, n + pad, h, p)
    return y[:, :n], state


def ssm_update(x, dt, a, b, c, state):
    """One position a row: ``x`` [r, H, P], ``dt`` [r, H] float32, ``a`` [H],
    ``b`` and ``c`` [r, N], ``state`` [r, H, P, N] float32.  Returns
    ``(y [r, H, P] float32, state)``; all of it float32 on the vector unit
    (the state's bytes bound it, not the arithmetic)."""
    decay = jnp.exp(dt * a)
    xdt = x.astype(jnp.float32) * dt[..., None]
    state = (state * decay[..., None, None]
             + xdt[..., None] * b.astype(jnp.float32)[:, None, None, :])
    y = jnp.sum(state * c.astype(jnp.float32)[:, None, None, :], axis=-1)
    return y, state
