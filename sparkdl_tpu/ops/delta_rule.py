"""The gated delta rule with a decay per channel (Kimi Delta Attention,
arXiv:2510.26692) over ragged rows, in two forms that give the same numbers:

- :func:`kda_chunked` — whole segments, chunk by chunk: inside a chunk a
  unit-lower-triangular system over the chunk's positions, between chunks the
  carried matrix state;
- :func:`kda_update` — one token a row against the state (decode).

Per head, with the state ``S`` [K, V] float32, ``alpha_t = exp(a_t)`` in
(0, 1]^K the decay (``a_t <= 0`` the log-decay, one a key channel) and
``beta_t`` in [0, 2] the write strength::

    Sbar_t = Diag(alpha_t) S_{t-1}
    S_t    = Sbar_t + beta_t k_t (v_t - Sbar_t^T k_t)^T
    o_t    = S_t^T q_t

so a write is a rank-one CORRECTION that depends on the state itself
(``(I - beta k k^T) Diag(alpha) S + beta k v^T``), not an addition to it.

**The chunked form.**  With ``G_t = sum_{s <= t} a_s`` inside a chunk (float32,
<= 0 and falling) and ``S_0`` the state before the chunk, every state of the
chunk is ``S_t = Diag(e^{G_t}) S_0 + sum_{i <= t} Diag(e^{G_t - G_i}) k_i
w_i^T`` with the pseudo-values ``w_t = beta_t (v_t - Sbar_t^T k_t)``, which
solve ``(I + B A) W = B (V - Ktilde S_0)``: ``B = Diag(beta)``, ``Ktilde_t =
e^{G_t} k_t``, and ``A_ti = sum_c k_tc k_ic e^{G_tc - G_ic}`` for ``i < t``
(strictly lower).  ``T = (I + B A)^{-1} B`` does not depend on the state, so
``U = T V`` and ``Wk = T Ktilde`` are made for all chunks at once, and the
scan over chunks is four products a chunk: ``W = U - Wk S_0``, ``O = Qtilde
S_0 + Aqk W`` (``Aqk_ti`` as ``A`` with ``q_t`` for ``k_t``, diagonal
included) and ``S_C = Diag(e^{G_C}) S_0 + Khat^T W`` with ``Khat_i = e^{G_C -
G_i} k_i``.

**Every exponent is <= 0.**  The decay is per channel, so ``A_ti`` is not the
product of ``k_t e^{G_t}`` and ``k_i e^{-G_i}``: ``e^{-G_i}`` overflows
float32 (a channel's log-decay reaches -100 over 64 positions).  A chunk is
cut into sub-blocks of ``sub`` positions (the paper's secondary chunking):

- inside a sub-block ``A`` is summed over the pairwise differences ``G_t -
  G_i`` themselves (elementwise, float32, [sub, sub, K] a sub-block);
- a later sub-block ``j`` reads the positions before it against ITS OWN
  reference point ``g_j`` (``G`` at the last position before it): ``e^{G_t -
  G_i} = e^{G_t - g_j} e^{g_j - G_i}``, both exponents <= 0, a product of two
  decayed operands on the matrix unit.

The triangular system is inverted in float32: each sub-block's diagonal
block by forward substitution over its ``sub`` rows, the blocks below by
forward substitution over sub-blocks.

**Ragged rows.**  A position with ``beta = 0`` and log-decay 0 leaves the
state as it was, so the caller zeroes both at a row's pads and the state that
comes back is the state after the row's OWN last real token.

The state, the decays, their running sums, the exponentials and the
triangular system are float32; the products take their operands in the
inputs' dtype (``computeDtype``) and accumulate in float32.  Plain
``jax.numpy``: the XLA compiler's own fusions, no kernel of this repo.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a sub-block (see the module's docstring)
SUB_BLOCK = 16

_EXACT = jax.lax.Precision.HIGHEST


def _unit_lower_inverse(lower):
    """``(I + L)^{-1}`` for ``lower`` = L [..., J, s, J, s], strictly lower
    triangular as a [J * s, J * s] matrix, float32.  The diagonal blocks by
    forward substitution over their rows (row t of the inverse is ``e_t -
    sum_{i < t} L_ti row_i``), the blocks under them by forward substitution
    over the blocks (``X_ji = -X_jj sum_{i <= m < j} L_jm X_mi``)."""
    blocks, s = lower.shape[-4], lower.shape[-3]
    eye = jnp.eye(s, dtype=lower.dtype)
    inverse = [[None] * blocks for _ in range(blocks)]
    for j in range(blocks):
        block = lower[..., j, :, j, :]
        rows = []
        for t in range(s):
            row = jnp.broadcast_to(eye[t], block.shape[:-2] + (s,))
            for i in range(t):
                row = row - block[..., t, i, None] * rows[i]
            rows.append(row)
        inverse[j][j] = jnp.stack(rows, axis=-2)
    for j in range(1, blocks):
        for i in range(j - 1, -1, -1):
            below = sum(
                jnp.matmul(lower[..., j, :, m, :], inverse[m][i],
                           precision=_EXACT)
                for m in range(i, j))
            inverse[j][i] = -jnp.matmul(inverse[j][j], below, precision=_EXACT)
    zero = jnp.zeros_like(inverse[0][0])
    return jnp.stack([
        jnp.stack([inverse[j][i] if i <= j else zero for i in range(blocks)],
                  axis=-2)
        for j in range(blocks)], axis=-4)  # [..., J, s, J, s]


def _decayed_pairs(q, k, cum, dtype):
    """``(Aqk, A)``: ``sum_c left_tc k_ic e^{G_tc - G_ic}`` with ``left = q``
    for ``i <= t`` and with ``left = k`` for ``i < t``, zero elsewhere.  ``q``
    and ``k`` [..., J, s, K] in float32, ``cum`` = G likewise; ``dtype`` is
    what the matrix unit's operands are rounded to.  Both [..., J, s, J, s]
    float32; they share every exponential."""
    blocks, s = k.shape[-3], k.shape[-2]
    at, before = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    # inside a sub-block: on the pairwise differences themselves
    span = cum[..., :, None, :] - cum[..., None, :, :]  # [..., J, t, i, K]
    decayed = k[..., None, :, :] * jnp.exp(
        jnp.where((before <= at)[..., None], span, -jnp.inf))
    inside = [jnp.sum(left[..., :, None, :] * decayed, axis=-1)
              for left in (q, k)]
    inside[1] = jnp.where(before < at, inside[1], 0.0)
    own = jnp.eye(blocks, dtype=bool)[:, None, :, None]  # [J, 1, J, 1]
    out = [jnp.where(own, part[..., :, :, None, :], 0.0) for part in inside]
    if blocks == 1:
        return out
    # a later sub-block j against its own reference point g_j: G at the last
    # position before it (sub-block 0 has nothing before it)
    point = cum[..., :-1, -1, :]  # [..., J-1, K]: g_1 .. g_{J-1}
    since = jnp.exp(cum[..., 1:, :, :] - point[..., :, None, :])
    # [..., J-1 (reader j), J (of i), s, K]: zero from sub-block j on
    earlier = (jnp.arange(blocks)[None, :]
               < jnp.arange(1, blocks)[:, None])[:, :, None, None]
    early = (k[..., None, :, :, :] * jnp.exp(jnp.where(
        earlier, point[..., :, None, None, :] - cum[..., None, :, :, :],
        -jnp.inf))).astype(dtype)
    return [
        whole.at[..., 1:, :, :, :].add(jnp.einsum(
            "...jtc,...jmic->...jtmi",
            (left[..., 1:, :, :] * since).astype(dtype), early,
            preferred_element_type=jnp.float32))
        for whole, left in zip(out, (q, k))]


def kda_chunked(q, k, v, log_decay, beta, state, chunk: int,
                sub: int = SUB_BLOCK):
    """The rule over a segment, chunk by chunk.

    ``q`` and ``k`` [r, n, H, K] (normalised and scaled by the caller), ``v``
    [r, n, H, V]; ``log_decay`` [r, n, H, K] float32, <= 0, zero at pads;
    ``beta`` [r, n, H] float32, zero at pads; ``state`` [r, H, K, V] float32,
    the state before the segment's first position.  ``n`` need be a multiple
    of neither ``chunk`` nor ``sub``: the tail is padded with positions that
    leave the state alone.  ``chunk`` must be a multiple of ``sub``.

    Returns ``(o [r, n, H, V] float32, state [r, H, K, V] float32)``; exact
    for any ``chunk`` and ``sub`` (a schedule, not mathematics)."""
    r, n, h, dk = k.shape
    dv = v.shape[-1]
    sub = min(int(sub), int(chunk))
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of sub-block {sub}")
    size = min(int(chunk), -(-n // sub) * sub)
    pad = -n % size
    if pad:
        q, k, v, log_decay, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, log_decay, beta))
    chunks, blocks = (n + pad) // size, size // sub
    dtype = v.dtype

    def split(t):  # [r, chunks * size, H, ...] -> [r, H, chunks, J, sub, ...]
        t = t.reshape(r, chunks, blocks, sub, h, *t.shape[3:])
        return jnp.moveaxis(t, 4, 1)

    qf, kf = split(q.astype(jnp.float32)), split(k.astype(jnp.float32))
    decay = split(log_decay.astype(jnp.float32))
    cum = jnp.cumsum(decay.reshape(r, h, chunks, size, dk), axis=3)
    total = cum[:, :, :, -1]  # [r, H, chunks, K]: G_C
    cum = cum.reshape(decay.shape)
    strength = split(beta.astype(jnp.float32))  # [r, H, chunks, J, sub]

    a_qk, a_kk = _decayed_pairs(qf, kf, cum, dtype)
    solve = _unit_lower_inverse(strength[..., None, None] * a_kk)
    solve = solve * strength[..., None, None, :, :]  # T = (I + B A)^{-1} B

    def flat(t):  # [..., J, sub, J, sub] -> [..., size, size]
        return t.reshape(*t.shape[:-4], size, size)

    def rows(t):  # [..., J, sub, X] -> [..., size, X]
        return t.reshape(*t.shape[:-3], size, t.shape[-1])

    solve, a_qk = flat(solve).astype(dtype), flat(a_qk).astype(dtype)
    grown = jnp.exp(rows(cum))  # e^{G_t}
    k_in = (rows(kf) * grown).astype(dtype)  # Ktilde
    q_in = (rows(qf) * grown).astype(dtype)  # Qtilde
    k_out = (rows(kf) * jnp.exp(total[..., None, :] - rows(cum))
             ).astype(dtype)  # Khat
    values = rows(split(v))
    u = jnp.einsum("rhcti,rhciv->rhctv", solve, values,
                   preferred_element_type=jnp.float32)
    wk = jnp.einsum("rhcti,rhcik->rhctk", solve, k_in,
                    preferred_element_type=jnp.float32).astype(dtype)

    def one_chunk(state, xs):
        u, wk, q_in, a_qk, k_out, total = xs
        held = state.astype(dtype)
        w = u - jnp.einsum("rhtk,rhkv->rhtv", wk, held,
                           preferred_element_type=jnp.float32)
        w_in = w.astype(dtype)
        o = (jnp.einsum("rhtk,rhkv->rhtv", q_in, held,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("rhti,rhiv->rhtv", a_qk, w_in,
                          preferred_element_type=jnp.float32))
        state = (state * jnp.exp(total)[..., None]
                 + jnp.einsum("rhtk,rhtv->rhkv", k_out, w_in,
                              preferred_element_type=jnp.float32))
        return state, o

    by_chunk = tuple(jnp.moveaxis(t, 2, 0)
                     for t in (u, wk, q_in, a_qk, k_out, total))
    state, o = jax.lax.scan(one_chunk, state.astype(jnp.float32), by_chunk)
    # [chunks, r, H, size, V] -> [r, n, H, V]
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(r, n + pad, h, dv)
    return o[:, :n], state


def kda_update(q, k, v, log_decay, beta, state):
    """One position a row: ``q`` and ``k`` [r, H, K], ``v`` [r, H, V],
    ``log_decay`` [r, H, K] float32, ``beta`` [r, H] float32, ``state`` [r, H,
    K, V] float32.  Returns ``(o [r, H, V] float32, state)``; all of it
    float32 on the vector unit (the state's bytes bound it, not the
    arithmetic).  ``o = S_t^T q = Sbar^T q + (k . q) w`` reads the decayed
    state in the pass that predicts ``Sbar^T k``."""
    qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
    decayed = state * jnp.exp(log_decay.astype(jnp.float32))[..., None]
    predicted = jnp.sum(decayed * kf[..., None], axis=-2)
    read = jnp.sum(decayed * qf[..., None], axis=-2)
    w = beta.astype(jnp.float32)[..., None] * (vf - predicted)
    state = decayed + kf[..., None] * w[..., None, :]
    o = read + jnp.sum(qf * kf, axis=-1, keepdims=True) * w
    return o, state
