"""Multi-head latent attention (MLA; DeepSeek-V2/V3, ``model_type
deepseek_v3``) through ONE latent cache.

A layer's shapes (:class:`Shape`): ``heads`` H; a query/key head of ``nope``
channels without a position term and ``rope`` channels rotated by RoPE; a
value head of ``v`` channels; a latent of ``rank`` r.  With ``u`` the
layer's normed input::

    q = u W_q                       [H, nope + rope] -> q_nope, q_pe
    [c_raw | k_pe] = u W_kva        [r + rope]
    c = RMSNorm_kv(c_raw)           (its own eps)
    [k_nope | v] = c W_kvb          [H, nope + v]
    q_pe, k_pe rotated by :func:`rope` at the position (k_pe is ONE head
    that every head shares)
    score_h(t, s) = (q_nope_h . k_nope_h,s + q_pe_h . k_pe_s) * scale
    out = concat_h(softmax(score_h) v_h) W_o

The cache holds ``[c | k_pe]`` of every position: ONE state leaf ``latent``
[layers, rows, span, r + rope] in the compute dtype, ``c`` after its norm
and ``k_pe`` after RoPE, written where it lies (in place under donation) and
never re-laid.

Both programs take the **absorbed** form: ``W_kvb``'s key half folds into
the query (``q_lat_h = q_nope_h W_UK_h^T``), ``[q_lat | q_pe]`` is scored
against ``[c | k_pe]`` as it is stored, ``softmax . [c | k_pe]`` is taken in
latent space, and ``W_UV_h`` comes after: the heads share every byte of the
cache read.  In prefill it was timed on the chip against the published
decompressed form (every visited slot's ``c`` expanded by ``W_kvb`` into the
heads' keys and values) and was the faster (PERF.md section 5, PR 41).
Softmaxes, the norm's statistics and the rotation are float32; everything
else runs in the weights' dtype.

**Two forms of the decode's attention**, picked by what :func:`token_block`
can see (the backend and the shapes; no argument, no environment variable):

- on the TPU, where a slot's channels are whole sublane tiles and the span
  whole lane tiles, ONE Pallas kernel (:func:`_token_kernel`): a grid step
  a row, which copies the row's blocks of ``TOKEN_BLOCK`` slots up to its
  position, each once, out of the leaf where it lies and scores, softmaxes
  and sums all heads against that one copy, after it has put the row's new
  latent into its slot there and sent the tile that holds it back to the
  leaf, in place;
- elsewhere (the tier-1 tests' CPU, the rehearsal) plain ``jax.numpy``
  after :func:`write_positions`: every slot of the span scored, the slots
  past a row's position masked.

**Two forms of the prefill's attention** (:func:`segment_attention`),
picked by what :func:`segment_group` can see (the backend and the shapes):

- on the TPU, where the latent and its ``rank`` channels are whole sublane
  tiles, a segment too, and the span whole lane tiles, ONE Pallas kernel
  (:func:`_segment_kernel`, ``mla_segment`` in a trace): a grid step a
  (pair, head group), whose group's query rows stay in fast memory while
  the pair's visible blocks of ``block`` slots are copied in, each once for
  the group, out of the leaf where it lies (and the pair's tiles of a
  selection's mask beside them), and whose scores, running statistics and
  float32 accumulator never leave fast memory; the sum takes the ``rank``
  channels only (the rope channels meet zero rows of ``W_UV``, so the
  output's are left zero);
- elsewhere the same fold in plain ``jax.numpy``, a row at a time.

All four keep the same precision: float32 scores and softmax statistics,
probabilities in the cache's dtype against the cache, float32 sums divided
by the softmax's sum at the end.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparkdl_tpu.models.hybrid import NEG, keys_scored, rms_norm

class Shape(NamedTuple):
    """One MLA layer's widths and constants (``deepseek_v3``'s keys)."""

    heads: int
    nope: int  # qk_nope_head_dim
    rope: int  # qk_rope_head_dim
    v: int  # v_head_dim
    rank: int  # kv_lora_rank
    theta: float  # rope_theta
    kv_eps: float  # the latent norm's eps (and the low-rank query's)
    scale: float  # the scores' factor, (nope + rope) ** -0.5 as published

    @property
    def latent(self) -> int:
        """Channels a cached position holds: ``c`` and ``k_pe``."""
        return self.rank + self.rope


def rope(x, position, theta: float):
    """RoPE over the last axis of ``x`` [..., d] at ``position`` (broadcast
    against ``x.shape[:-1]``) in DeepSeek-V3's interleaved pairing: channels
    ``2i`` and ``2i + 1`` turn together by ``position * theta^(-2i/d)``, and
    come out de-interleaved, the turned even channels first (the published
    code's ``view(..., d/2, 2).transpose`` before ``rotate_half``).  Float32
    angles; the result in ``x``'s dtype."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = position.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate(
        [even * cos - odd * sin, odd * cos + even * sin], axis=-1
    ).astype(x.dtype)


def query_latent(lp, u, shape: Shape):
    """``c_q = RMSNorm_q(u W_qa)`` [..., q_lora_rank]: a low-rank query's
    latent (``q_a_proj``, ``q_a_layernorm`` at the latent norm's eps)."""
    return rms_norm(jnp.dot(u, lp["wqa"]), lp["q_norm"], shape.kv_eps)


def project(lp, u, position, shape: Shape, c_q=None):
    """``(q_nope [..., H, nope], q_pe [..., H, rope], latent [..., r +
    rope])`` of ``u`` [..., D] at ``position`` [...]: the queries, and what
    the cache keeps of the position (``c`` normed, ``k_pe`` turned).  The
    query is ``u W_q``, or ``c_q W_qb`` where the layer has a low-rank query
    (``wqa``; ``c_q``: :func:`query_latent`'s, where the caller has it)."""
    if "wqa" in lp:
        if c_q is None:
            c_q = query_latent(lp, u, shape)
        q = jnp.dot(c_q, lp["wqb"])
    else:
        q = jnp.dot(u, lp["wq"])
    q = q.reshape(*u.shape[:-1], shape.heads, shape.nope + shape.rope)
    q_pe = rope(q[..., shape.nope:], position[..., None], shape.theta)
    kva = jnp.dot(u, lp["wkva"])
    c = rms_norm(kva[..., :shape.rank], lp["kv_norm"], shape.kv_eps)
    k_pe = rope(kva[..., shape.rank:], position, shape.theta)
    return q[..., :shape.nope], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def _up(lp, shape: Shape):
    """``W_kvb`` [r, H, nope + v] as ``(W_UK [r, H, nope], W_UV [r, H,
    v])``."""
    w = lp["wkvb"].reshape(shape.rank, shape.heads, shape.nope + shape.v)
    return w[..., :shape.nope], w[..., shape.nope:]


def from_latent(out, w_uv):
    """``out`` [..., H, r + rope] (``softmax . [c | k_pe]``) times ``W_UV``
    [r, H, v], with ``W_UV`` given zero rows for the rope channels rather
    than ``out`` sliced: XLA moves a slice of a product's result into its
    operand, and in decode that operand is the whole layer's cache."""
    rank, heads, v = w_uv.shape
    padded = jnp.concatenate(
        [w_uv, jnp.zeros((out.shape[-1] - rank, heads, v), w_uv.dtype)])
    return jnp.einsum("...hc,chd->...hd", out, padded)


def absorbed_query(q_nope, q_pe, w_uk):
    """``[q_nope_h W_UK_h^T | q_pe_h]`` [..., H, r + rope]: the query that
    scores the cache as it is stored."""
    q_lat = jnp.einsum("...hd,chd->...hc", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], axis=-1)


#: slots a write moves at once: the TPU's lane tile.  The chip lays the leaf
#: out with the span minor (576 channels would pad to 640, a span of whole
#: tiles pads nothing), and a scatter whose window is a slot's channels
#: makes XLA copy the whole leaf into another layout and back; a window of
#: whole lane tiles of the channel-major view is written where it lies
#: (``tests/test_tpu_compile.py``)
WRITE_BLOCK = 128


def write_positions(latent, new, layer, rows, start, lengths,
                    aligned: bool = False):
    """``new`` [c, n, r + rope] into ``latent[layer, rows[c], start[c] +
    j]`` for ``j < lengths[c]``, in place where the leaf is donated; a pad
    (``j >= lengths[c]``) and a row past the last are written nowhere.

    As a read-modify-write of one window a row, ``WRITE_BLOCK``-aligned and
    wide enough for ``n`` positions at any start, in the channel-major view
    ``[layers, rows, r + rope, span]`` (what the chip stores): the window is
    gathered, the real positions are put in, and it goes back by a scatter
    of whole lane tiles.  ``aligned``: each ``start`` is a multiple of ``n``
    and ``start + n`` lies within the span (the stage's segments: whole lane
    tiles on the chip), and the window is the segment's own, so that
    consecutive segments of one row in one call write windows that do not
    overlap."""
    c, n, width = new.shape
    span = latent.shape[2]
    if aligned:
        wide, base = n, start
    else:
        wide = min(span,
                   -(-(n + WRITE_BLOCK - 1) // WRITE_BLOCK) * WRITE_BLOCK)
        base = jnp.minimum(start // WRITE_BLOCK * WRITE_BLOCK, span - wide)
    index = jnp.stack([jnp.full((c,), layer, jnp.int32), rows,
                       jnp.zeros((c,), jnp.int32), base], axis=-1)
    channel_major = jnp.swapaxes(latent, 2, 3)
    window = jax.lax.gather(
        channel_major, index,
        jax.lax.GatherDimensionNumbers(
            offset_dims=(1, 2), collapsed_slice_dims=(0, 1),
            start_index_map=(0, 1, 2, 3)),
        (1, 1, width, wide), mode=jax.lax.GatherScatterMode.CLIP)
    # window slot k holds position base + k; new position j goes to
    # k = start - base + j where j is real
    k = jnp.arange(wide, dtype=jnp.int32)[None, :]
    j = k - (start - base)[:, None]
    real = (j >= 0) & (j < lengths[:, None])
    taken = jnp.take_along_axis(
        new.astype(latent.dtype), jnp.clip(j, 0, n - 1)[..., None], axis=1)
    window = jnp.where(real[:, None, :], jnp.swapaxes(taken, 1, 2), window)
    channel_major = jax.lax.scatter(
        channel_major, index, window,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2, 3)),
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)
    return jnp.swapaxes(channel_major, 2, 3)


def mla_segment(lp, u, latent, layer, rows, start, lengths, shape: Shape,
                block: int = 512):
    """A segment ``u`` [c, n, D] of ``n`` positions a row at ``start[c] +
    arange(n)``, ``lengths[c]`` of them real, through layer ``layer`` of the
    leaf ``latent`` [layers, rows, span, r + rope] where it lies: the real
    positions' latents go into the rows ``rows`` [c] (None: all, in order;
    an index past the last row writes nothing and reads the last row)
    first, then every position sees its row's cache up to itself
    (:func:`segment_attention`).  Returns (the output projection's input
    [c, n, H v], latent)."""
    c, n = u.shape[:2]
    if rows is None:
        rows = jnp.arange(c, dtype=jnp.int32)
    position = start[:, None] + jnp.arange(n, dtype=jnp.int32)
    q_nope, q_pe, new = project(lp, u, position, shape)
    latent = write_positions(latent, new, layer, rows, start, lengths)
    return segment_attention(lp, q_nope, q_pe, latent, layer, rows, start,
                             shape, block), latent


def segment_attention(lp, q_nope, q_pe, latent, layer, rows, start,
                      shape: Shape, block: int = 512, chosen=None):
    """The attention of a segment's queries (``q_nope``, ``q_pe`` [c, n, H,
    ...] at ``start[c] + arange(n)``) over their rows ``rows`` [c] of layer
    ``layer`` of the leaf, as written: of each row's cache only the blocks
    of ``block`` slots that hold one the segment can see (as
    :func:`~sparkdl_tpu.models.hybrid.attention_segment`, so
    :func:`~sparkdl_tpu.models.hybrid.keys_scored` counts them), each
    folded into a running maximum, sum and float32 accumulator, in the
    absorbed form: ONE kernel on the TPU at the shapes it takes
    (:func:`segment_group`, :func:`_segment_attention`), the plain fold
    elsewhere (:func:`_segment_attention_plain`).  ``chosen`` [c, n, span]
    (nonzero: kept), where given, leaves out of each query's softmax the
    visible slots it does not keep (a learned sparse attention's selection,
    ``ops/dsa.py``).  Returns the output projection's input [c, n, H v]."""
    n = q_nope.shape[1]
    w_uk, w_uv = _up(lp, shape)
    heads = shape.heads
    query = absorbed_query(q_nope, q_pe, w_uk)  # [c, n, H, r + rope]
    group = segment_group(shape, n, latent.shape[2])
    if group:
        out = _segment_attention(
            query.transpose(0, 2, 1, 3), latent, layer, rows, start,
            shape.scale, shape.rank, min(block, latent.shape[2]), group,
            chosen)
        out = from_latent(out.transpose(0, 2, 1, 3), w_uv)
        return out.reshape(*out.shape[:2], heads * shape.v)

    def finish(out):
        # back from latent space: the value half of W_kvb
        out = from_latent(out.transpose(1, 0, 2), w_uv)
        return out.reshape(n, heads * shape.v)

    return _segment_attention_plain(query, latent, layer, rows, start,
                                    shape.scale, block, chosen, finish)


def _segment_attention_plain(query, latent, layer, rows, start,
                             scale: float, block: int, chosen=None,
                             finish=None):
    """The fold of :func:`segment_attention` in plain ``jax.numpy``, one row
    at a time (``lax.map``) for the absorbed ``query`` [c, n, H, width]:
    ``finish`` (of a pair's ``softmax . [c | k_pe]`` [H, n, width] in the
    cache's dtype) of every pair; without it [c, H, n, width], the kernel's
    output (:func:`_segment_attention`)."""
    n, heads = query.shape[1], query.shape[2]
    span, width = latent.shape[2], latent.shape[3]
    block = min(block, span)
    ahead = jnp.arange(n, dtype=jnp.int32)[:, None]
    within = jnp.arange(block, dtype=jnp.int32)

    def one_row(pair):
        query, row, start = pair[:3]

        def fold(j, carry):
            top, total, acc = carry
            # the last block is moved back to end with the span, and what it
            # then shares with the block before is masked
            at = jnp.minimum(j * block, span - block)
            slots = jax.lax.dynamic_slice(
                latent, (layer, row, at, 0), (1, 1, block, width))[0, 0]
            seen = at + within
            visible = (seen >= j * block) & (seen <= start + ahead)
            if chosen is not None:
                visible = visible & (jax.lax.dynamic_slice(
                    pair[3], (0, at), (n, block)) != 0)
            scores = jnp.einsum("nhc,mc->hnm", query, slots,
                                preferred_element_type=jnp.float32)
            scores = jnp.where(visible, scores * scale, NEG)
            new_top = jnp.maximum(top, scores.max(axis=-1))
            probs = jnp.exp(scores - new_top[..., None])
            kept = jnp.exp(top - new_top)
            acc = acc * kept[..., None] + jnp.einsum(
                "hnm,mc->hnc", probs.astype(slots.dtype), slots,
                preferred_element_type=jnp.float32)
            return new_top, total * kept + probs.sum(axis=-1), acc

        _, total, acc = jax.lax.fori_loop(
            0, (start + n + block - 1) // block, fold,
            (jnp.full((heads, n), NEG, jnp.float32),
             jnp.zeros((heads, n), jnp.float32),
             jnp.zeros((heads, n, width), jnp.float32)))
        out = (acc / total[..., None]).astype(latent.dtype)
        return out if finish is None else finish(out)

    pairs = (query, jnp.minimum(rows, latent.shape[1] - 1), start)
    if chosen is not None:
        pairs = pairs + (chosen,)
    return jax.lax.map(one_row, pairs)


#: query rows (a head group's heads x a segment's positions) that a grid step
#: of the prefill kernel folds against each block of the cache: the block is
#: the matrix unit's stationary operand, and the rows stream through it.  At
#: the GLM-5 cell's shape (64 heads, segments of 128, a selection's mask) a
#: dispatch's fold took 32.7 / 31.8 / 31.3 ms on the chip with groups of
#: 1,024 / 2,048 / 4,096 rows, against 38.0 for the plain fold (PERF.md
#: section 5)
SEGMENT_QUERY_ROWS = 4096


def segment_group(shape: Shape, n: int, span: int) -> int:
    """The heads a grid step of :func:`segment_attention`'s kernel takes for
    segments of ``n`` positions under a span of ``span``: on the TPU, where
    the latent and its ``rank`` channels are whole sublane tiles, a segment
    too, and the span whole lane tiles, the largest divisor of the heads
    whose group holds at most :data:`SEGMENT_QUERY_ROWS` query rows (at
    least one head); elsewhere 0, the plain fold."""
    taken = (jax.default_backend() == "tpu" and shape.latent % 16 == 0
             and shape.rank % 16 == 0 and n % 16 == 0 and span % 128 == 0)
    if not taken:
        return 0
    group = max(1, min(shape.heads, SEGMENT_QUERY_ROWS // n))
    while shape.heads % group:
        group -= 1
    return group


def keys_fused(shape: Shape, start, n: int, span: int) -> int:
    """The cache slots :func:`segment_attention`'s kernel folds for segments
    of ``n`` positions at ``start`` under a span of ``span``: every slot
    the fold scores (:func:`~sparkdl_tpu.models.hybrid.keys_scored`) where
    the kernel runs (:func:`segment_group`), none where the plain fold
    does.  Host arithmetic, for the stage's span attributes."""
    return keys_scored(start, n, span) if segment_group(shape, n, span) else 0


def _segment_kernel(layer_ref, rows_ref, start_ref, query_ref, latent_ref,
                    *refs, block, span, scale, rank, masked):
    """One (pair, head group)'s attention over its row's cache: the blocks
    of ``block`` slots that hold one the segment can see, each copied once
    from the leaf in HBM (``latent_ref`` [layers, rows, width, span], the
    channel-major view) into one half of ``buffer`` [2, width, block] while
    the block before is folded, and with it the pair's ``[n, block]`` tile
    of ``chosen`` (``masked``) into ``tiles``.  Each block's scores of the
    group's query rows (``query_ref`` [heads, n, width]) are folded into a
    running maximum, sum and float32 accumulator held in fast memory (``top``,
    ``total``, ``acc``); the sum takes the first ``rank`` channels only (the
    rope channels meet zero rows of ``W_UV``), and the rope channels of the
    output stay zero.  The last block of the span is moved back to end with
    it, and what it shares with the block before is masked.  The copies run
    on across grid steps (they are sequential): a step's last block starts
    the next step's first, and ``first`` (SMEM) says which half that went
    to."""
    if masked:
        chosen_ref, out_ref, buffer, tiles, copies, first, top, total, acc = (
            refs)
    else:
        out_ref, buffer, copies, first, top, total, acc = refs
    pair, group = pl.program_id(0), pl.program_id(1)
    pairs, groups = pl.num_programs(0), pl.num_programs(1)
    layer, start = layer_ref[0], start_ref[pair]
    heads, n, width = query_ref.shape
    last = latent_ref.shape[1] - 1

    def copies_of(p, j, half):
        at = jnp.minimum(j * block, span - block)
        row = jnp.minimum(rows_ref[p], last)
        made = [pltpu.make_async_copy(
            latent_ref.at[layer, row, :, pl.ds(at, block)], buffer.at[half],
            copies.at[0, half])]
        if masked:
            made.append(pltpu.make_async_copy(
                chosen_ref.at[p, :, pl.ds(at, block)], tiles.at[half],
                copies.at[1, half]))
        return made

    @pl.when((pair == 0) & (group == 0))
    def _():
        first[0] = 0
        for made in copies_of(0, 0, 0):
            made.start()

    blocks = (start + n + block - 1) // block
    following = jnp.where(group + 1 < groups, pair, pair + 1)
    more = (group + 1 < groups) | (pair + 1 < pairs)
    position = start + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    within = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    top[...] = jnp.full(top.shape, NEG, jnp.float32)
    total[...] = jnp.zeros(total.shape, jnp.float32)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)

    def fold(j, carry):
        half = (first[0] + j) % 2

        @pl.when(j + 1 < blocks)
        def _():
            for made in copies_of(pair, j + 1, 1 - half):
                made.start()

        @pl.when((j + 1 == blocks) & more)
        def _():
            for made in copies_of(following, 0, 1 - half):
                made.start()

        for made in copies_of(pair, j, half):
            made.wait()
        query = query_ref[...].reshape(heads * n, width)
        scores = jax.lax.dot_general(
            query, buffer[half], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        seen = jnp.minimum(j * block, span - block) + within
        visible = (seen >= j * block) & (seen <= position)  # [n, block]
        if masked:
            visible = visible & (tiles[half].astype(jnp.float32) != 0)
        scores = jnp.where(visible[None], scores.reshape(heads, n, block),
                           NEG).reshape(heads * n, block)
        was = top[...]
        new_top = jnp.maximum(was, scores.max(axis=1, keepdims=True))
        probs = jnp.exp(scores - new_top)
        kept = jnp.exp(was - new_top)
        top[...] = new_top
        total[...] = total[...] * kept + probs.sum(axis=1, keepdims=True)
        acc[...] = acc[...] * kept + jax.lax.dot_general(
            probs.astype(buffer.dtype), buffer[half, :rank, :],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, blocks, fold, 0)
    out = (acc[...] / total[...]).reshape(heads, n, rank)
    out_ref[:, :, :rank] = out.astype(out_ref.dtype)
    out_ref[:, :, rank:] = jnp.zeros((heads, n, width - rank), out_ref.dtype)
    first[0] = (first[0] + blocks) % 2


def _segment_attention(query, latent, layer, rows, start, scale: float,
                       rank: int, block: int, group: int, chosen=None,
                       interpret=False):
    """``softmax . [c | k_pe]`` [c, H, n, width] in the cache's dtype (the
    rope channels zero) for the absorbed ``query`` [c, H, n, width] of every
    pair, head-major, at ``start[c] + arange(n)`` against layer ``layer`` of
    the leaf ``latent`` [layers, rows, span, width] at the rows ``rows``
    [c] (past the last: the last), ``chosen`` [c, n, span] (nonzero: kept)
    masking where given: ONE Pallas kernel (:func:`_segment_kernel`), a grid
    step a (pair, group of ``group`` heads), that reads of the leaf only
    each pair's blocks up to its segment's end, in the channel-major view
    the chip stores."""
    c, heads, n, width = query.shape
    span = latent.shape[2]
    masked = chosen is not None
    group_block = pl.BlockSpec((None, group, n, width),
                               lambda i, g, *_: (i, g, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scratch = [pltpu.VMEM((2, width, block), latent.dtype)]
    if masked:
        scratch.append(pltpu.VMEM((2, n, block), chosen.dtype))
    scratch += [pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((group * n, 1), jnp.float32),
                pltpu.VMEM((group * n, 1), jnp.float32),
                pltpu.VMEM((group * n, rank), jnp.float32)]
    operands = [jnp.swapaxes(latent, 2, 3)] + ([chosen] if masked else [])
    return pl.pallas_call(
        functools.partial(_segment_kernel, block=block, span=span,
                          scale=scale, rank=rank, masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(c, heads // group),
            in_specs=[group_block] + [hbm] * len(operands),
            out_specs=group_block, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct(query.shape, latent.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=100 * 1024 * 1024),
        interpret=interpret,
        name="mla_segment",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      start.astype(jnp.int32), query.astype(latent.dtype), *operands)


#: slots a block of the decode kernel's walk over a row's cache.  Each block
#: passes the matrix unit twice for 16 query rows, so a block costs about
#: what its copy does; at the Moonlight cell's shape a layer's call took
#: 1.13 / 0.84 / 0.78 ms with blocks of 256 / 512 / 1,024 on the chip, and
#: 1,024 reads twice the slots past the rows' positions (visible 0.86 of
#: what is read, against 0.93; PERF.md section 5)
TOKEN_BLOCK = 512


def token_block(width: int, span: int) -> int:
    """The slots of a block in which :func:`mla_token`'s attention walks each
    row's cache: on the TPU, where a slot's ``width`` channels are whole
    sublane tiles and the ``span`` whole lane tiles of the leaf as the chip
    stores it, the kernel's block (:func:`_token_attention`); elsewhere 0,
    the plain form (:func:`_token_attention_plain`), which reads the whole
    span."""
    taken = (jax.default_backend() == "tpu"
             and width % 16 == 0 and span % 128 == 0)
    return min(TOKEN_BLOCK, span) if taken else 0


def token_slots_read(positions, width: int, span: int) -> int:
    """The cache slots :func:`mla_token`'s attention reads for rows that
    take in ``positions`` (any shape, one a row and step) from a cache of
    ``width`` channels a slot and ``span`` slots: whole blocks up to each
    position where the kernel runs (:func:`token_block`), the whole span
    where the plain form does."""
    positions = np.asarray(positions, np.int64)
    block = token_block(width, span)
    if not block:
        return int(positions.size) * span
    return int((positions // block + 1).sum()) * block


def token_slots_written(rows: int, width: int, span: int) -> int:
    """The new slots a call of :func:`mla_token` for ``rows`` rows writes
    inside its attention kernel: every row's where the kernel runs
    (:func:`token_block`), none where :func:`write_positions` writes them
    before the plain form."""
    return rows if token_block(width, span) else 0


def _token_kernel(layer_ref, position_ref, query_ref, new_ref, latent_ref,
                  out_ref, leaf_ref, buffer, copies, written, first, *, block,
                  span, scale):
    """One row's attention over its cache: the blocks of ``block`` slots
    that hold one at or before the row's position, each copied once from
    the leaf in HBM (``latent_ref`` [layers, rows, width, span], the
    channel-major view) into one of the two halves of ``buffer`` [2, width,
    block] while the block before is scored, then folded into a running
    maximum, sum and float32 accumulator.  The last block of the span is
    moved back to end with it, and what it shares with the block before is
    masked.  The copies run on across rows (the grid is sequential): a
    row's last block starts the next row's first, and ``first`` (SMEM) says
    which half that went to.

    The row's new latent (``new_ref`` [width, WRITE_BLOCK]: the latents of
    the ``WRITE_BLOCK`` rows from this one's tile of rows on, channel-major)
    goes into its slot in the last block once that block has landed, before
    the block is scored; the tile of ``WRITE_BLOCK`` slots (of the block,
    where that is narrower) that holds the slot then goes back from fast
    memory to the leaf (``leaf_ref``, aliased to ``latent_ref``) while the
    block is scored, its other slots as they were read."""
    row, rows = pl.program_id(0), pl.num_programs(0)
    layer, position = layer_ref[0], position_ref[row]
    heads = query_ref.shape[0]
    width = buffer.shape[1]
    wide = min(WRITE_BLOCK, block)  # the slots written back

    def copy(r, j, half):
        at = jnp.minimum(j * block, span - block)
        return pltpu.make_async_copy(
            latent_ref.at[layer, r, :, pl.ds(at, block)], buffer.at[half],
            copies.at[half])

    def tile_of(j):
        # the first slot of the tile of ``wide`` that holds the row's
        # position, in the leaf and in block j's copy
        at = jnp.minimum(j * block, span - block)
        tile = pl.multiple_of(position // wide * wide, wide)
        return tile, pl.multiple_of(tile - at, wide)

    def write_back(j, half):
        tile, held = tile_of(j)
        return pltpu.make_async_copy(
            buffer.at[half, :, pl.ds(held, wide)],
            leaf_ref.at[layer, row, :, pl.ds(tile, wide)], written)

    @pl.when(row == 0)
    def _():
        first[0] = 0
        copy(0, 0, 0).start()

    blocks = position // block + 1
    last_half = (first[0] + blocks - 1) % 2
    query = query_ref[...]
    within = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

    def fold(j, carry):
        top, total, acc = carry
        half = (first[0] + j) % 2

        @pl.when(j + 1 < blocks)
        def _():
            copy(row, j + 1, 1 - half).start()

        @pl.when((j + 1 == blocks) & (row + 1 < rows))
        def _():
            copy(row + 1, 0, 1 - half).start()

        copy(row, j, half).wait()

        @pl.when(j + 1 == blocks)
        def _():
            # the row's column of its tile of rows, on every lane (a
            # maximum over one real value: exact), into the slot's lane
            lanes = jax.lax.broadcasted_iota(jnp.int32, new_ref.shape, 1)
            column = jnp.max(
                jnp.where(lanes == row % WRITE_BLOCK,
                          new_ref[...].astype(jnp.float32), -jnp.inf),
                axis=1, keepdims=True)
            tile, held = tile_of(j)
            slots = buffer.at[half, :, pl.ds(held, wide)]
            slots[...] = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (1, wide), 1)
                == position - tile,
                jnp.broadcast_to(column, (width, wide)).astype(slots.dtype),
                slots[...])
            write_back(j, half).start()

        slots = buffer[half]  # [width, block]
        scores = jax.lax.dot_general(
            query, slots, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        seen = jnp.minimum(j * block, span - block) + within
        visible = (seen >= j * block) & (seen <= position)
        scores = jnp.where(visible, scores, NEG)
        new_top = jnp.maximum(top, scores.max(axis=1, keepdims=True))
        probs = jnp.exp(scores - new_top)
        kept = jnp.exp(top - new_top)
        acc = acc * kept + jax.lax.dot_general(
            probs.astype(slots.dtype), slots, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return new_top, total * kept + probs.sum(axis=1, keepdims=True), acc

    _, total, acc = jax.lax.fori_loop(
        0, blocks, fold,
        (jnp.full((heads, 1), NEG, jnp.float32),
         jnp.zeros((heads, 1), jnp.float32),
         jnp.zeros(query_ref.shape, jnp.float32)))
    out_ref[...] = (acc / total).astype(out_ref.dtype)
    # the half is the next row's second block's: the tile has to be out
    write_back(blocks - 1, last_half).wait()
    first[0] = (first[0] + blocks) % 2


def _token_attention(query, new, latent, layer, position, scale: float,
                     block: int, interpret=False):
    """``(out, leaf)``: ``softmax . [c | k_pe]`` [r, H, width] in the
    cache's dtype for the absorbed ``query`` [r, H, width] of every row
    against layer ``layer`` of the leaf ``latent`` [layers, r, span, width]
    with each row's ``new`` [r, width] latent written at its ``position``
    [r] first, slots past it masked, and the leaf so written: ONE Pallas
    kernel (:func:`_token_kernel`), a grid step a row, that reads of the
    leaf only each row's blocks up to its position, once, in the
    channel-major view the chip stores, and writes back of it only the
    ``WRITE_BLOCK``-slot tile that holds the new slot, in place (the leaf is
    aliased)."""
    r, heads, width = query.shape
    span = latent.shape[2]
    row_block = pl.BlockSpec((None, heads, width), lambda i, *_: (i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    # channel-major, WRITE_BLOCK rows a block: a row's latent is a column of
    # its tile of rows
    tiles = -(-r // WRITE_BLOCK) * WRITE_BLOCK
    new = jnp.pad(new.astype(latent.dtype).T, ((0, 0), (0, tiles - r)))
    out, leaf = pl.pallas_call(
        functools.partial(_token_kernel, block=block, span=span, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(r,),
            in_specs=[row_block,
                      pl.BlockSpec((width, WRITE_BLOCK),
                                   lambda i, *_: (0, i // WRITE_BLOCK)),
                      hbm],
            out_specs=[row_block, hbm],
            scratch_shapes=[pltpu.VMEM((2, width, block), latent.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(query.shape, latent.dtype),
                   jax.ShapeDtypeStruct(
                       (*latent.shape[:2], width, span), latent.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mla_token",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), position.astype(jnp.int32),
      query.astype(latent.dtype), new, jnp.swapaxes(latent, 2, 3))
    return out, jnp.swapaxes(leaf, 2, 3)


def _token_attention_plain(query, latent, layer, position, scale: float,
                           chosen=None):
    """:func:`_token_attention` in plain ``jax.numpy``: every slot of the
    span scored, those past the row's position masked, one softmax.
    ``chosen`` [r, span] (nonzero: kept), where given, masks the slots a
    row does not keep as well (``ops/dsa.py``)."""
    cache = jax.lax.dynamic_index_in_dim(latent, layer, 0, keepdims=False)
    # the cache is the LEFT operand of the scores' product, so that both
    # products take it with its channels minor, as it is stored
    scores = jnp.einsum("rsc,rhc->rsh", cache, query,
                        preferred_element_type=jnp.float32) * scale
    visible = jnp.arange(cache.shape[1])[None, :] <= position[:, None]
    if chosen is not None:
        visible = visible & (chosen != 0)
    probs = jax.nn.softmax(
        jnp.where(visible[..., None], scores, NEG), axis=1)
    return jnp.einsum("rsh,rsc->rhc", probs.astype(cache.dtype), cache)


def mla_token(lp, u, latent, layer, position, shape: Shape):
    """One position a row, ``u`` [r, D] at ``position`` [r], through layer
    ``layer`` of the leaf, every row at once, in the absorbed form: the
    position's latent goes into its slot first, then ``[q_lat | q_pe]``
    scores the row's slots up to its position, softmax in float32,
    ``softmax . [c | k_pe]`` in latent space and ``W_UV`` after.  One
    kernel on the TPU at widths it takes (:func:`token_block`), which
    writes the slot itself; plain ``jax.numpy`` elsewhere, after
    :func:`write_positions`.  Returns (the output projection's input [r,
    H v], latent)."""
    r = u.shape[0]
    q_nope, q_pe, new = project(lp, u, position, shape)
    block = token_block(shape.latent, latent.shape[2])
    if not block:
        latent = write_positions(
            latent, new[:, None], layer, jnp.arange(r, dtype=jnp.int32),
            position, jnp.ones((r,), jnp.int32))
    w_uk, w_uv = _up(lp, shape)
    query = absorbed_query(q_nope, q_pe, w_uk)  # [r, H, r + rope]
    if block:
        out, latent = _token_attention(query, new, latent, layer, position,
                                       shape.scale, block)
    else:
        out = _token_attention_plain(query, latent, layer, position,
                                     shape.scale)
    out = from_latent(out, w_uv)
    return out.reshape(r, shape.heads * shape.v), latent
