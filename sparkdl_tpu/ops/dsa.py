"""Learned sparse attention (DeepSeek Sparse Attention, DSA; ``model_type
glm_moe_dsa``) over multi-head latent attention (:mod:`sparkdl_tpu.ops.mla`).

A lightning indexer scores every visible key for every query, and each
query's attention runs over the ``topk`` keys it scores highest.  With ``u``
the layer's normed input at position ``t`` and ``c_q`` the low-rank query's
latent (:func:`~sparkdl_tpu.ops.mla.query_latent`), the indexer's shapes
(:class:`Indexer`) are ``heads`` J query heads of ``dim`` channels and ONE
key a position that every head shares::

    q_j = c_q W_qI                  [J, dim]
    k   = LayerNorm(u W_kI)         [dim]   (gain and bias; its own eps)
    RoPE on the first ``rope`` channels of q_j and k (the interleaved
    pairing of :func:`~sparkdl_tpu.ops.mla.rope`), the others as they are
    w_j = (u W_w)_j * J^(-1/2) * dim^(-1/2)          (float32)
    I(t, s) = sum_j w_j ReLU(q_j . k_s)              s <= t, float32
    S_t = the topk positions s <= t of largest I(t, s) (every s <= t where
          t + 1 <= topk); ties go to the earlier position

and the MLA layer's softmax runs over ``S_t`` only.  The cache holds ``k``
of every position beside the latent: a second leaf ``index_keys`` [layers,
rows, span, dim] in the compute dtype, slot-major (``dim`` is one lane tile,
so that a write moves whole slots and the selection kernel takes the leaf as
it lies), written where it lies by one scatter of whole slots
(:func:`write_slots`); the latent as
:func:`~sparkdl_tpu.ops.mla.write_positions` writes it.  A prefill dispatch
may hold consecutive segments of one row: each pair's latent window is its
own segment (``aligned``), so the windows of one call do not overlap.

**Selection is exact**: the topk-th largest score of each query is found by
bisection over the bits of an order-preserving integer view of its float32
value (:func:`order_key`), and the ties at it are taken from the earliest
position on.  A query's kept slots are a mask [.., span] (1: kept) that the
attention reads beside causality; unselected keys take no part in any
softmax.  The attention itself is MLA's absorbed form over whole blocks of
the cache (:func:`~sparkdl_tpu.ops.mla.segment_attention`) with the
unselected slots masked, so its work is that of dense attention over the
visible slots (the models' ``attention_paths`` call it ``"masked"``).  On
the TPU that fold is one kernel, which holds its scores, statistics and
accumulator in fast memory and reads the mask a block at a time beside the
latent; a form that scores the selected slots only, and so saves what
selection could, is still open.

**Two forms of the prefill's selection**, picked by what
:func:`select_block` can see (the backend and the shapes; no argument, no
environment variable):

- on the TPU, where the span is whole lane tiles, ONE Pallas kernel
  (:func:`_select_kernel`, named ``dsa_select`` in a trace): a grid step a
  (row, segment) pair, whose row's index keys come into fast memory once,
  whose scores stay there through the bisection, and which writes the
  pair's mask;
- elsewhere (the tier-1 tests' CPU, the rehearsal) plain ``jax.numpy``
  (:func:`select_plain` on the scores of every slot of the span).

The decode scores and selects in plain ``jax.numpy`` on every backend: a
step's scores are [rows, span], small beside the weights it reads.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparkdl_tpu.ops import mla

#: below the order key of every float32 score: a slot no query may keep
FLOOR = np.iinfo(np.int32).min
#: slots a block of the selection kernel's walk: one lane tile, so that any
#: span the stage makes (whole lane tiles) is whole blocks
SELECT_BLOCK = 128


class Indexer(NamedTuple):
    """One layer's lightning indexer (``glm_moe_dsa``'s ``index_*`` keys)."""

    heads: int  # index_n_heads
    dim: int  # index_head_dim
    rope: int  # the leading channels RoPE turns (qk_rope_head_dim)
    theta: float  # rope_theta
    topk: int  # index_topk
    eps: float  # the key's LayerNorm eps


def layer_norm(x, gain, bias, eps: float):
    """LayerNorm with gain and bias, statistics in float32."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * gain.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _turned(x, position, ix: Indexer):
    """RoPE on the first ``ix.rope`` channels of ``x``, the rest kept."""
    return jnp.concatenate(
        [mla.rope(x[..., :ix.rope], position, ix.theta), x[..., ix.rope:]],
        axis=-1)


def index_project(lp, u, c_q, position, ix: Indexer):
    """``(q [..., J, dim], k [..., dim], w [..., J] float32)`` of ``u`` [...,
    D] and ``c_q`` [..., q_lora_rank] at ``position`` [...]."""
    q = jnp.dot(c_q, lp["ix_wq"]).reshape(*u.shape[:-1], ix.heads, ix.dim)
    q = _turned(q, position[..., None], ix)
    k = layer_norm(jnp.dot(u, lp["ix_wk"]), lp["ix_norm"], lp["ix_bias"],
                   ix.eps)
    k = _turned(k, position, ix)
    w = jnp.dot(u, lp["ix_w"], preferred_element_type=jnp.float32)
    return q, k, w * (ix.heads ** -0.5 * ix.dim ** -0.5)


def order_key(scores):
    """An int32 whose order is the float32 ``scores``' order (``-0`` taken
    as ``+0``): the sign bit kept, the other bits flipped where it is
    set."""
    scores = jnp.where(scores == 0, 0.0, scores).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _relu(x):
    """The indexer's ReLU on each head's products."""
    return jnp.maximum(x, 0.0)


def _weighted(logits, w, heads: int, axis: int):
    """``sum_j w_j ReLU(logits_j)`` over the heads along ``axis`` of
    ``logits`` (``w`` [..., J]), head after head in order (the kernel's
    sum, term for term)."""
    out = None
    for j in range(heads):
        term = w[..., j, None] * _relu(jnp.take(logits, j, axis=axis))
        out = term if out is None else out + term
    return out


def _kth_key(count, shape, k: int):
    """The largest key K with ``count(K) >= k`` (``count(c)``: how many keys
    of each query are at least ``c``), bit by bit from the top: the k-th
    largest key, or :data:`FLOOR` where fewer than k are visible."""
    top = jnp.full(shape, FLOOR, jnp.int32)
    top = jnp.where(count(jnp.zeros(shape, jnp.int32)) >= k, 0, top)

    def bit(b, top):
        cand = top | jnp.left_shift(jnp.int32(1), 30 - b)
        return jnp.where(count(cand) >= k, cand, top)

    return jax.lax.fori_loop(0, 31, bit, top)


def select_plain(keys, k: int):
    """The kept slots [..., span] (bool) of queries whose slots have order
    keys ``keys`` [..., span] (:data:`FLOOR` where not visible): the ``k``
    largest, ties to the earlier slot; every visible one where fewer than
    ``k`` are."""
    kth = _kth_key(
        lambda c: jnp.sum(keys >= c[..., None], axis=-1), keys.shape[:-1], k)
    above = keys > kth[..., None]
    need = k - jnp.sum(above, axis=-1)
    tie = keys == kth[..., None]
    rank = jnp.cumsum(tie.astype(jnp.int32), axis=-1)
    return (above | (tie & (rank <= need[..., None]))) & (keys != FLOOR)


def select_block(span: int) -> int:
    """The slots of a block of :func:`_select_kernel`'s walk on the TPU,
    where the span is whole lane tiles; elsewhere 0: the plain form
    (:func:`select_plain`)."""
    taken = jax.default_backend() == "tpu" and span % SELECT_BLOCK == 0
    return SELECT_BLOCK if taken else 0


def _select_kernel(layer_ref, rows_ref, start_ref, q_ref, w_ref, keys_ref,
                   upper_ref, out_ref, score_ref, *, topk: int, heads: int,
                   block: int):
    """One pair's selection: its ``n`` queries (``q_ref`` [J n, dim], head
    after head; ``w_ref`` [J, n, 1]) score the blocks of its row's index
    keys (``keys_ref`` [span, dim]) that hold one they can see; the scores' order keys stay in ``score_ref`` [n, span];
    the topk-th largest key of each query is found by bisection over them;
    the kept slots go to ``out_ref`` [n, span] (1.0 kept, 0.0 not), ties at
    the topk-th taken in slot order by a running count and a product with
    the upper triangle ``upper_ref`` [block, block] of ones (a prefix sum
    within the block)."""
    del layer_ref, rows_ref  # in the keys' index map
    pair = pl.program_id(0)
    n, span = out_ref.shape
    start = start_ref[pair]
    blocks = (start + n + block - 1) // block
    position = start + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    within = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    query = q_ref[...]

    def at(j):
        return pl.multiple_of(j * block, block)

    def score(j, carry):
        keys = keys_ref[pl.ds(at(j), block), :]  # [block, dim]
        logits = jax.lax.dot_general(
            query, keys, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        total = None
        for h in range(heads):
            term = w_ref[h] * _relu(logits[h * n:(h + 1) * n])
            total = term if total is None else total + term
        seen = j * block + within
        score_ref[:, pl.ds(at(j), block)] = jnp.where(
            seen <= position, order_key(total), FLOOR)
        return carry

    jax.lax.fori_loop(0, blocks, score, 0)

    def count(cand, strict=False):
        def add(j, acc):
            keys = score_ref[:, pl.ds(at(j), block)]
            hit = keys > cand if strict else keys >= cand
            return acc + jnp.where(hit, 1.0, 0.0)

        acc = jax.lax.fori_loop(0, blocks, add,
                                jnp.zeros((n, block), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    kth = _kth_key(count, (n, 1), topk)
    need = topk - count(kth, strict=True)

    def emit(j, ties):
        keys = score_ref[:, pl.ds(at(j), block)]
        tie = keys == kth
        prefix = jnp.dot(jnp.where(tie, 1.0, 0.0).astype(jnp.bfloat16),
                         upper_ref[...], preferred_element_type=jnp.float32)
        kept = (keys > kth) | (tie & (ties + prefix <= need))
        kept = kept & (keys != FLOOR)
        out_ref[:, pl.ds(at(j), block)] = jnp.where(
            kept, 1.0, 0.0).astype(out_ref.dtype)
        return ties + jnp.sum(jnp.where(tie, 1.0, 0.0), axis=1,
                              keepdims=True)

    jax.lax.fori_loop(0, blocks, emit, jnp.zeros((n, 1), jnp.float32))

    def clear(j, carry):
        out_ref[:, pl.ds(at(j), block)] = jnp.zeros((n, block),
                                                     out_ref.dtype)
        return carry

    jax.lax.fori_loop(blocks, span // block, clear, 0)


def _select(q, w, keys, layer, rows, start, topk: int, block: int,
            interpret=False):
    """The kept slots [c, n, span] (bfloat16 1/0) of each pair's queries
    (``q`` [c, n, J, dim], ``w`` [c, n, J]) at ``start[c] + arange(n)``
    against layer ``layer`` of the index leaf ``keys`` [layers, rows, span,
    dim] at the rows ``rows`` [c] (past the last: the last): ONE Pallas
    kernel (:func:`_select_kernel`), a grid step a pair."""
    c, n, heads, dim = q.shape
    total, span = keys.shape[1], keys.shape[2]
    upper = (np.arange(block)[:, None] <= np.arange(block)[None, :])
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, heads=heads,
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(c,),
            in_specs=[
                pl.BlockSpec((None, heads * n, dim),
                             lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((None, heads, n, 1),
                             lambda i, *_: (i, 0, 0, 0)),
                pl.BlockSpec(
                    (None, None, span, dim),
                    lambda i, layer, rows, start: (
                        layer[0], jnp.minimum(rows[i], total - 1), 0, 0)),
                pl.BlockSpec((block, block), lambda i, *_: (0, 0)),
            ],
            out_specs=pl.BlockSpec((None, n, span), lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((n, span), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((c, n, span), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="dsa_select",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      start.astype(jnp.int32),
      q.transpose(0, 2, 1, 3).reshape(c, heads * n, dim).astype(keys.dtype),
      w.transpose(0, 2, 1)[..., None].astype(jnp.float32),
      keys, jnp.asarray(upper, jnp.bfloat16))


def _scores_plain(q, w, cache, heads: int):
    """``I`` [..., n, span] of queries ``q`` [..., n, J, dim] (``w`` [..., n,
    J]) against index keys ``cache`` [..., span, dim]."""
    logits = jnp.einsum("...njd,...sd->...njs", q, cache,
                        preferred_element_type=jnp.float32)
    return _weighted(logits, w, heads, axis=-2)


def _select_plain_segment(q, w, keys, layer, rows, start, topk: int):
    """:func:`_select` in plain ``jax.numpy``: every slot of the span
    scored, those past each query's position left out."""
    n, heads = q.shape[1], q.shape[2]
    cache = keys[layer][jnp.minimum(rows, keys.shape[1] - 1)]
    scores = _scores_plain(q, w, cache, heads)
    position = start[:, None, None] + jnp.arange(n)[None, :, None]
    seen = jnp.arange(keys.shape[2])[None, None, :]
    ordered = jnp.where(seen <= position, order_key(scores), FLOOR)
    return select_plain(ordered, topk).astype(jnp.bfloat16)


def write_slots(keys, new, layer, rows, start, lengths):
    """``new`` [c, n, dim] into ``keys[layer, rows[c], start[c] + j]`` for
    ``j < lengths[c]``: one scatter of whole slots (a slot-major leaf whose
    channels are one lane tile), in place where the leaf is donated; a pad
    and a row past the last are written nowhere.  Each slot is written once,
    so consecutive segments of one row may share the call.  (The window
    read-modify-write of the latent made the chip's compiler hold this leaf
    in another layout, copied whole in and out of every layer.)"""
    j = jnp.arange(new.shape[1], dtype=jnp.int32)[None, :]
    slot = jnp.where(j < lengths[:, None], start[:, None] + j, keys.shape[2])
    return keys.at[layer, rows[:, None], slot].set(
        new.astype(keys.dtype), mode="drop")


def dsa_segment(lp, u, latent, keys, layer, rows, start, lengths,
                shape: mla.Shape, ix: Indexer):
    """A segment ``u`` [c, n, D] of ``n`` positions a row at ``start[c] +
    arange(n)``, ``lengths[c]`` of them real, through layer ``layer`` of the
    leaves ``latent`` and ``keys`` where they lie: every pair's latents and
    index keys go into the rows ``rows`` [c] first (a pad writes nothing),
    then each query selects its slots (on the TPU: :func:`_select`) and
    attends over them (:func:`~sparkdl_tpu.ops.mla.segment_attention`).
    Each ``start`` is a multiple of ``n`` with ``start + n`` within the span
    (the stage's segments), so two pairs may be consecutive segments of one
    row.  Returns (the output projection's input [c, n, H v], latent,
    keys)."""
    c, n = u.shape[:2]
    if rows is None:
        rows = jnp.arange(c, dtype=jnp.int32)
    position = start[:, None] + jnp.arange(n, dtype=jnp.int32)
    c_q = mla.query_latent(lp, u, shape)
    q_nope, q_pe, new = mla.project(lp, u, position, shape, c_q)
    q_ix, k_ix, w = index_project(lp, u, c_q, position, ix)
    latent = mla.write_positions(latent, new, layer, rows, start, lengths,
                                 aligned=True)
    keys = write_slots(keys, k_ix, layer, rows, start, lengths)
    block = select_block(keys.shape[2])
    if block:
        chosen = _select(q_ix, w, keys, layer, rows, start, ix.topk, block)
    else:
        chosen = _select_plain_segment(q_ix, w, keys, layer, rows, start,
                                       ix.topk)
    out = mla.segment_attention(lp, q_nope, q_pe, latent, layer, rows, start,
                                shape, chosen=chosen)
    return out, latent, keys


def dsa_token(lp, u, latent, keys, layer, position, shape: mla.Shape,
              ix: Indexer):
    """One position a row, ``u`` [r, D] at ``position`` [r], every row at
    once: the position's latent and index key go into their slots, the
    row's visible index keys are scored, the topk kept, and the absorbed
    attention runs over those.  Returns (the output projection's input [r,
    H v], latent, keys)."""
    r = u.shape[0]
    rows, one = jnp.arange(r, dtype=jnp.int32), jnp.ones((r,), jnp.int32)
    c_q = mla.query_latent(lp, u, shape)
    q_nope, q_pe, new = mla.project(lp, u, position, shape, c_q)
    q_ix, k_ix, w = index_project(lp, u, c_q, position, ix)
    latent = mla.write_positions(latent, new[:, None], layer, rows, position,
                                 one)
    keys = write_slots(keys, k_ix[:, None], layer, rows, position, one)
    cache = jax.lax.dynamic_index_in_dim(keys, layer, 0, keepdims=False)
    scores = _scores_plain(q_ix[:, None], w[:, None], cache, ix.heads)[:, 0]
    seen = jnp.arange(keys.shape[2])[None, :]
    chosen = select_plain(
        jnp.where(seen <= position[:, None], order_key(scores), FLOOR),
        ix.topk)
    w_uk, w_uv = mla._up(lp, shape)
    query = mla.absorbed_query(q_nope, q_pe, w_uk)
    out = mla._token_attention_plain(query, latent, layer, position,
                                     shape.scale, chosen)
    out = mla.from_latent(out, w_uv)
    return out.reshape(r, shape.heads * shape.v), latent, keys


def keys_indexed(start, n: int, span: int) -> int:
    """The index keys a query of segments of ``n`` positions at ``start``
    scores under a span of ``span``, summed over the segments: whole blocks
    of :func:`select_block` up to each segment's own end where the kernel
    runs, the whole span where the plain form does.  Host arithmetic, for
    the stage's span attributes."""
    block = select_block(span)
    start = np.asarray(start, np.int64)
    if not block:
        return int(len(start) * span)
    return int((-(-(start + n) // block)).sum() * block)
