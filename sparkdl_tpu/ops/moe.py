"""The sparse-expert feed-forward: routing over every expert, renormalised
top-k, and a grouped product over the experts HELD here, with no capacity
and no dropped token.

``moe_ffn`` is told which experts it holds (``experts_held=(lo, hi)``, a
half-open range of the router's outputs): it routes over all of them and
computes the part of the result that its own experts give.  The parts of all
the shares add up to the whole layer (``tests/test_sdar_moe.py``), which is
what expert parallelism asks of the layer; on one chip it runs without the
exchange.

The grouped product sorts the (token, expert) pairs by expert, gathers the
tokens' rows in that order and multiplies each expert's rows by that expert's
weights (:func:`grouped_dot`): every pair is computed once, whatever the load
of its expert, so nothing is dropped and nothing is padded to a capacity.
Pairs routed to experts held elsewhere sort behind the last group, where the
product leaves rows that are never read.

Around the three products a pair's row crosses the chip's memory once on the
way in and once on the way out (PERF.md section 6, PR 36):

- **Pairs are numbered k-major**: pair ``j * n + t`` is token ``t``'s
  ``j``-th choice, so the token of a pair is ``pair % n`` and a token's k
  parts are k slabs of ``[n, D]``.  Numbered token-major, the k parts are the
  rows of an ``[n, k, D]`` array, and where k is no multiple of a tile's 8
  rows (Granite's 10) laying that out is a copy of every row, in float32.
- **Both gathers promise that their indices are in bounds**
  (:func:`_rows_at`).  They are by construction, ``order % n < n`` and a
  permutation's inverse; ``jnp.take``'s default mode does not know it and
  puts a select over all ``[n * k, D]`` rows behind the gather, to fill the
  rows of indices that are out of bounds.
- **The k parts are combined in one pass**: each slab is read in the
  weights' dtype, widened to float32, multiplied by the pair's float32
  weight, masked and added; no float32 ``[n * k, D]`` exists in memory.
- **The mask on the rows behind the last group stays**: the grouped product
  leaves them undefined, and NaN times a zero weight is NaN.
- The index work beside them uses no scatter, which runs pair by pair on the
  chip: the counts are a compare-and-sum, the way back is a second sort.

Which grouped product, from a trace (PERF.md section 6, PR 31): on the TPU
the Pallas grouped matmul that ships with JAX (``megablox.gmm``) with row
tiles of 128 and an expert's whole matrix as one tile; elsewhere
``jax.lax.ragged_dot``.  The TPU compiler lowers ``ragged_dot`` to a kernel of
its own too, but with row tiles of 512: at the ~64 rows an expert gets from a
block step of 1,024 tokens every (tile, expert) visit multiplies a whole tile,
and it measured 2.25 ms where ``gmm`` takes 0.90 ms and the weights' bytes
0.49 ms (8,192 rows, 128 of 768 groups, 2048 x 768).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _tile(dim: int, cap: int = 2048) -> int:
    """The whole dimension where it fits a tile, else its largest divisor
    under ``cap`` that the TPU's lanes take (a multiple of 128)."""
    if dim <= cap:
        return dim
    fitting = [t for t in range(cap, 0, -128) if dim % t == 0]
    return fitting[0] if fitting else dim


def gmm_grouped_dot(rows, weights, sizes, interpret: bool = False):
    """``megablox.gmm`` over row tiles of 128 (the rows padded up to whole
    tiles; the pads lie behind the last group)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    m, k = rows.shape
    tile_m = min(128, -(-m // 16) * 16)
    padded = -(-m // tile_m) * tile_m
    if padded != m:
        rows = jnp.pad(rows, ((0, padded - m), (0, 0)))
    out = gmm(
        rows, weights, sizes, preferred_element_type=rows.dtype,
        tiling=(tile_m, _tile(k), _tile(weights.shape[-1])),
        interpret=interpret,
    )
    return out[:m]


def grouped_dot(rows, weights, sizes):
    """``rows[group g's rows] @ weights[g]`` for every group: ``rows`` [m, k]
    sorted by group, ``weights`` [G, k, n], ``sizes`` [G] int32.  Rows behind
    the last group come back undefined."""
    if jax.default_backend() == "tpu":
        return gmm_grouped_dot(rows, weights, sizes)
    return jax.lax.ragged_dot(rows, weights, sizes)


def route(x, router, top_k: int, norm_topk: bool = True,
          scoring: str = "softmax", select_bias=None):
    """``(weights [n, k] float32, experts [n, k] int32)``: every one of the
    router's outputs scored in float32, the ``top_k`` largest (ties to the
    lower expert, as ``jax.lax.top_k`` breaks them), renormalised to sum to
    one where ``norm_topk``.

    ``scoring="softmax"``: the scores are the softmax over ALL the outputs.
    ``scoring="sigmoid"``: each output's own sigmoid; the experts are chosen
    by ``score + select_bias`` ([E] float32, the router's selection bias)
    and weighted by the score alone."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        biased = scores if select_bias is None else (
            scores + select_bias.astype(jnp.float32))
        _, experts = jax.lax.top_k(biased, top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights, experts.astype(jnp.int32)
    if scoring != "softmax" or select_bias is not None:
        raise ValueError(
            f"scoring={scoring!r} with select_bias "
            f"{'set' if select_bias is not None else 'unset'}: softmax (no "
            "selection bias) or sigmoid")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


#: From this many tokens on, the k parts come back by one gather a slab (the
#: compiler then keeps slabs in fast memory between the gather and the sum:
#: 0.10 ms where one gather of all the pairs and a sum over its slabs take
#: 0.22 ms, at 2,048 tokens of width 4,096); under it by ONE gather, where
#: ten gathers of 64 rows cost ten starts (46 us against 9).  Equal at 1,024
#: tokens of width 2,048.  PERF.md section 6, PR 36.
_SLAB_ROWS = 1024


def _rows_at(table, index):
    """``table[index]`` for row numbers that are in bounds by construction:
    told so, the gather has no pass behind it that fills the rows of
    out-of-bounds indices (``jnp.take``'s default mode)."""
    return table.at[index].get(mode="promise_in_bounds")


def moe_ffn(
    x,
    router,
    experts: Dict[str, jax.Array],
    *,
    top_k: int,
    experts_held: Optional[Tuple[int, int]] = None,
    norm_topk: bool = True,
    stack_index=None,
    scoring: str = "softmax",
    select_bias=None,
):
    """The held experts' part of ``sum_e w_e * down_e(silu(gate_e x) * up_e x)``.

    ``x`` [n, D]; ``router`` [D, E] over all E experts; ``experts`` holds
    ``w_gate`` and ``w_up`` [H, D, F] and ``w_down`` [H, F, D] for the
    H = hi - lo experts of ``experts_held`` (default: all E).

    With ``stack_index`` (a traced scalar) the weights are a STACK
    [S, H, ...] of S layers' experts, of which this call uses layer
    ``stack_index``: the grouped product is handed the whole stack as S * H
    groups with every other layer's empty, so a scan over layers does not
    copy a layer's experts (most of the model's bytes) out of the stack.

    Returns ``(out [n, D] in x's dtype, counts [E] int32)``; ``counts[e]`` is
    the number of tokens routed to expert e (held here or not), so
    ``counts.sum() == n * top_k`` and nothing was dropped.  ``scoring`` and
    ``select_bias`` are :func:`route`'s.
    """
    n, d = x.shape
    n_experts = router.shape[-1]
    lo, hi = (0, n_experts) if experts_held is None else experts_held
    n_held = hi - lo
    stacked = stack_index is not None
    if experts["w_gate"].shape[1 if stacked else 0] != n_held:
        raise ValueError(
            f"experts_held={lo, hi} names {n_held} experts, the weights "
            f"hold {experts['w_gate'].shape[1 if stacked else 0]}"
        )
    weights, chosen = route(
        x, router, top_k, norm_topk, scoring, select_bias)
    pair_expert = chosen.T.reshape(-1)  # k-major: pair j * n + t
    # counted by comparison: a scatter-add of n * k ones runs pair by pair
    counts = jnp.sum(
        pair_expert[:, None] == jnp.arange(n_experts, dtype=jnp.int32),
        axis=0, dtype=jnp.int32)

    held = (pair_expert >= lo) & (pair_expert < hi)
    group = jnp.where(held, pair_expert - lo, n_held)  # elsewhere: last
    order = jnp.argsort(group, stable=True)
    rows = _rows_at(x, order % n)  # [n*k, D], by expert
    sizes = jax.lax.dynamic_slice_in_dim(counts, lo, n_held)
    w_gate, w_up, w_down = (
        experts[name] for name in ("w_gate", "w_up", "w_down"))
    if stacked:
        layers = w_gate.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * n_held,), sizes.dtype), sizes,
            (stack_index * n_held,))
        w_gate, w_up, w_down = (
            w.reshape(layers * n_held, *w.shape[2:])
            for w in (w_gate, w_up, w_down))

    gate = grouped_dot(rows, w_gate, sizes)
    up = grouped_dot(rows, w_up, sizes)
    hidden = (jax.nn.silu(gate.astype(jnp.float32))
              * up.astype(jnp.float32)).astype(x.dtype)
    down = grouped_dot(hidden, w_down, sizes)

    # back to pair order by a gather (``order`` is a permutation: sorting it
    # gives its inverse); a token's k parts are then k slabs of [n, D], each
    # read once, weighted, masked and added in float32
    back = jnp.argsort(order)
    if n >= _SLAB_ROWS:
        parts = [_rows_at(down, slab) for slab in back.reshape(top_k, n)]
    else:
        parts = _rows_at(down, back).reshape(top_k, n, d)
    pair_weight = weights.T  # [k, n]
    held = held.reshape(top_k, n)
    out = jnp.zeros((n, d), jnp.float32)
    for j in range(top_k):
        # rows behind the last group are whatever the product left there
        out = out + jnp.where(
            held[j][:, None],
            parts[j].astype(jnp.float32) * pair_weight[j][:, None], 0.0)
    return out.astype(x.dtype), counts
