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
block by forward substitution over its ``sub`` rows, the blocks below from
the blocks above them (forward substitution over sub-blocks in the plain
form; the block-inverse identity over 1, 2, 4, ... sub-blocks in the kernel).

**Ragged rows.**  A position with ``beta = 0`` and log-decay 0 leaves the
state as it was, so the caller zeroes both at a row's pads and the state that
comes back is the state after the row's OWN last real token.

The state, the decays, their running sums, the exponentials and the
triangular system are float32; the products take their operands in the
inputs' dtype (``computeDtype``) and accumulate in float32.

**Two forms of the chunked form**, picked by what :func:`fused_chunks` can
see (the backend and the shapes; no argument, no environment variable):

- on the TPU, at head widths that are whole lane tiles (``d_k`` and ``d_v``
  multiples of 128) and head counts in whole blocks of ``HEAD_BLOCK``, ONE
  Pallas kernel (:func:`_rule_kernel`): a grid step is one row x one block of
  heads x one chunk, the chunk axis innermost and sequential with the
  carried state in a VMEM scratch, so of everything above only the inputs,
  ``o`` and the state after the row's last chunk cross HBM, once each.  It
  reads ``q``, ``k``, ``v`` and the log-decay as ``[rows, n, H d]`` (a head
  is one lane tile of a block; the caller's projections have that layout);
- elsewhere (the tier-1 tests' CPU, the rehearsal's 16-wide heads) plain
  ``jax.numpy``, the XLA compiler's own fusions: ``T``, ``U`` and ``Wk`` for
  all chunks at once, then a scan over the chunks.

Both run the same schedule (``chunk``, ``sub``) with the same rounding points;
:func:`kda_update` is plain ``jax.numpy`` everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a sub-block (see the module's docstring)
SUB_BLOCK = 16
#: heads a grid step of the kernel
HEAD_BLOCK = 8

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


def _schedule(n: int, chunk: int, sub: int):
    """``(size, sub)``: positions a chunk and a sub-block as a segment of
    ``n`` positions is run (a segment shorter than ``chunk`` is one chunk of
    whole sub-blocks)."""
    sub = min(int(sub), int(chunk))
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is no multiple of sub-block {sub}")
    return min(int(chunk), -(-n // sub) * sub), sub


def _padded(size: int, *inputs):
    """The segment's ``inputs`` [r, n, ...] padded to whole chunks of ``size``
    with positions that leave the state alone."""
    pad = -inputs[0].shape[1] % size
    if not pad:
        return inputs
    return tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in inputs)


def fused_chunks(rows: int, n: int, heads: int, dk: int, dv: int, chunk: int,
                 sub: int = SUB_BLOCK):
    """``(chunks, fused)``: the (row, chunk) pairs :func:`kda_chunked` runs
    over a segment of ``n`` positions of ``rows`` rows, and how many of them
    go through the kernel: all of them on the TPU at shapes the kernel takes
    (head widths that are whole lane tiles, heads in whole blocks, sub-blocks
    of whole sublane tiles), none elsewhere."""
    size, sub = _schedule(n, chunk, sub)
    chunks = rows * -(-n // size)
    taken = (jax.default_backend() == "tpu"
             and dk % 128 == 0 and dv % 128 == 0
             and heads % HEAD_BLOCK == 0 and sub % 8 == 0)
    return chunks, chunks if taken else 0


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
    for any ``chunk`` and ``sub`` (a schedule, not mathematics).  One kernel
    on the TPU at shapes it takes (:func:`fused_chunks`), plain
    ``jax.numpy`` elsewhere."""
    r, n, h, dk = k.shape
    _, fused = fused_chunks(r, n, h, dk, v.shape[-1], chunk, sub)
    run = _kda_chunked_kernel if fused else _kda_chunked_plain
    return run(q, k, v, log_decay, beta, state, chunk, sub)


def _kda_chunked_plain(q, k, v, log_decay, beta, state, chunk: int,
                       sub: int = SUB_BLOCK):
    """:func:`kda_chunked` in plain ``jax.numpy``: ``T``, ``U`` and ``Wk`` for
    all chunks at once, then a scan over the chunks."""
    r, n, h, dk = k.shape
    dv = v.shape[-1]
    size, sub = _schedule(n, chunk, sub)
    q, k, v, log_decay, beta = _padded(size, q, k, v, log_decay, beta)
    padded = k.shape[1]
    chunks, blocks = padded // size, size // sub
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
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(r, padded, h, dv)
    return o[:, :n], state


def _rule_kernel(q_ref, k_ref, v_ref, decay_ref, strength_ref, across_ref,
                 before_ref, o_ref, after_ref, state, *, heads, dk, dv, size,
                 sub, dtype):
    """One (row, block of ``heads`` heads, chunk) of the rule: the module's
    docstring from ``G`` to ``O`` and ``S_C`` with nothing written but those
    two.  ``q_ref``, ``k_ref``, ``decay_ref`` [size, heads dk] and ``v_ref``,
    ``o_ref`` [size, heads dv]: a head is one lane tile of them;
    ``strength_ref`` [size, heads] and ``across_ref`` [heads, size] are both
    ``beta`` (down the rows and along them); ``state`` [heads, dk, dv] is the
    scratch that carries ``S`` over a row's chunks (the grid's innermost,
    sequential axis), filled from ``before_ref`` at the row's first chunk and
    written to ``after_ref`` at its last.

    The triangular system: a sub-block's columns come one at a time out of
    the pairwise sums, so its diagonal block is inverted by the same forward
    substitution in its outer-product order (column i takes ``L_ti row_i``
    off every row under it); the blocks under the diagonal by the
    block-inverse identity ``X - X C X`` over 1, 2, 4, ... sub-blocks."""
    f32 = jnp.float32
    blocks = size // sub

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = before_ref[...]

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def exact(a, b):
        return jnp.dot(a, b, precision=_EXACT, preferred_element_type=f32)

    def dot(a, b, contract=((1,), (0,))):
        return jax.lax.dot_general(
            a, b, (contract, ((), ())), preferred_element_type=f32)

    def running(x):  # down the rows: x_t <- sum_{s <= t} x_s, float32
        rows, step = iota(x.shape, 0), 1
        while step < size:
            x = x + jnp.where(rows >= step, pltpu.roll(x, step, 0), 0.0)
            step *= 2
        return x

    at = iota((size, dk), 0)
    position = iota((8, size), 0)
    column = iota((8, size), 1)
    tiles = [slice(lo, lo + 8) for lo in range(0, size, 8)]
    nothing = jnp.zeros((8, size), f32)
    for h in range(heads):
        keys, values = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        qf, kf = q_ref[:, keys].astype(f32), k_ref[:, keys].astype(f32)
        cum = running(decay_ref[:, keys])  # G
        beta_t = [jnp.broadcast_to(strength_ref[rows, h:h + 1], (8, size))
                  for rows in tiles]

        # a later sub-block against its own reference point
        a_qk = [nothing] * len(tiles)
        under = [nothing] * len(tiles)  # B A under the diagonal blocks
        for j in range(1, blocks):
            rows = slice(j * sub, (j + 1) * sub)
            point = cum[j * sub - 1:j * sub]  # g_j
            since = jnp.exp(cum[rows] - point)
            early = (kf * jnp.exp(jnp.where(
                at < j * sub, point - cum, -jnp.inf))).astype(dtype)
            left = jnp.concatenate(
                [qf[rows] * since, kf[rows] * since]).astype(dtype)
            both = dot(left, early, ((1,), (1,)))  # [2 sub, size]
            for lo in range(0, sub, 8):
                tile = (j * sub + lo) // 8
                a_qk[tile] = both[lo:lo + 8]
                under[tile] = beta_t[tile] * both[sub + lo:sub + lo + 8]

        # inside the sub-blocks, a column (one earlier position) at a time,
        # over the tiles of 8 rows that hold a position at or after it
        q_t, k_t, cum_t = ([x[rows] for rows in tiles] for x in (qf, kf, cum))
        solved = [(position + 8 * tile == column).astype(f32)
                  for tile in range(len(tiles))]
        for p in range(size):
            own, at_p = p // 8, slice(p % 8, p % 8 + 1)
            last = ((p // sub + 1) * sub) // 8  # the sub-block's tiles end
            row = solved[own][at_p]
            for tile in range(own, last):
                since = cum_t[tile] - cum_t[own][at_p]
                if tile == own:  # the positions before p: nothing
                    since = jnp.where(at[:8] >= p % 8, since, -jnp.inf)
                decayed = k_t[own][at_p] * jnp.exp(since)
                with_q = jnp.sum(q_t[tile] * decayed, axis=1, keepdims=True)
                with_k = jnp.sum(k_t[tile] * decayed, axis=1, keepdims=True)
                a_qk[tile] = jnp.where(column == p, with_q, a_qk[tile])
                below = beta_t[tile] * with_k
                if tile == own:  # strictly under the diagonal
                    below = jnp.where(position > p % 8, below, 0.0)
                solved[tile] = solved[tile] - below * row
        a_qk = jnp.concatenate(a_qk)
        solve = jnp.concatenate(solved)
        under = jnp.concatenate(under)
        # the blocks under the diagonal: [[A, 0], [C, D]]^-1 has -D^-1 C A^-1
        # under A^-1, over spans of 1, 2, 4, ... sub-blocks
        span = sub
        while span < size:
            rows = []
            for lo in range(0, size, 2 * span):
                mid, hi = lo + span, min(lo + 2 * span, size)
                rows.append(solve[lo:mid])
                if mid < hi:
                    c_a = exact(under[mid:hi, lo:mid], solve[lo:mid])
                    rows.append(
                        solve[mid:hi] - exact(solve[mid:hi, mid:hi], c_a))
            solve = jnp.concatenate(rows)
            span *= 2
        solve = (solve * across_ref[h:h + 1, :]).astype(dtype)  # T

        total = cum[size - 1:size]  # G_C
        grown = jnp.exp(cum)
        k_in = (kf * grown).astype(dtype)  # Ktilde
        q_in = (qf * grown).astype(dtype)  # Qtilde
        k_out = (kf * jnp.exp(total - cum)).astype(dtype)  # Khat
        u = dot(solve, v_ref[:, values])
        wk = dot(solve, k_in).astype(dtype)

        carried = state[h]
        held = carried.astype(dtype)
        w = u - dot(wk, held)
        w_in = w.astype(dtype)
        o_ref[:, values] = dot(q_in, held) + dot(a_qk.astype(dtype), w_in)
        # Diag(e^{G_C}) S: the decay of key channel c down row c
        kept = jnp.broadcast_to(jnp.exp(total), (dv, dk)).T
        state[h] = carried * kept + dot(k_out, w_in, ((0,), (0,)))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        after_ref[...] = state[...]


def _kda_chunked_kernel(q, k, v, log_decay, beta, state, chunk: int,
                        sub: int = SUB_BLOCK, interpret: bool = False):
    """:func:`kda_chunked` as one Pallas kernel: the grid is (row, block of
    ``HEAD_BLOCK`` heads, chunk), and of the chunked form only ``o`` and the
    state after the row's last chunk reach HBM."""
    r, n, h, dk = k.shape
    dv = v.shape[-1]
    size, sub = _schedule(n, chunk, sub)
    q, k, v, log_decay, beta = _padded(size, q, k, v, log_decay, beta)
    padded = k.shape[1]
    chunks, groups = padded // size, h // HEAD_BLOCK
    # beta per (row, head block, chunk), down the rows and along them
    strength = beta.astype(jnp.float32).reshape(
        r, chunks, size, groups, HEAD_BLOCK).transpose(0, 3, 1, 2, 4)
    across = strength.swapaxes(-1, -2)

    def wide(width):  # [r, n, H * width]: a head is one lane tile
        return pl.BlockSpec((None, size, HEAD_BLOCK * width),
                            lambda row, group, c: (row, c, group))

    def small(*shape):
        return pl.BlockSpec((None, None, None) + shape,
                            lambda row, group, c: (row, group, c, 0, 0))

    carried = pl.BlockSpec((None, HEAD_BLOCK, dk, dv),
                           lambda row, group, c: (row, group, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(
            _rule_kernel, heads=HEAD_BLOCK, dk=dk, dv=dv, size=size, sub=sub,
            dtype=v.dtype),
        grid=(r, groups, chunks),
        in_specs=[wide(dk), wide(dk), wide(dv), wide(dk),
                  small(size, HEAD_BLOCK), small(HEAD_BLOCK, size), carried],
        out_specs=[wide(dv), carried],
        out_shape=[jax.ShapeDtypeStruct((r, padded, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct((r, h, dk, dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((HEAD_BLOCK, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunked",
    )(q.reshape(r, padded, h * dk), k.reshape(r, padded, h * dk),
      v.reshape(r, padded, h * dv),
      log_decay.astype(jnp.float32).reshape(r, padded, h * dk),
      strength, across, state.astype(jnp.float32))
    return o.reshape(r, padded, h, dv)[:, :n], state


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
