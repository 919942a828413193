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


def route(x, router, top_k: int, norm_topk: bool = True):
    """``(weights [n, k] float32, experts [n, k] int32)``: softmax over ALL
    the router's outputs in float32, the ``top_k`` largest (ties to the lower
    expert, as ``jax.lax.top_k`` breaks them), renormalised to sum to one
    where ``norm_topk``."""
    logits = jnp.dot(x, router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if norm_topk:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights, experts.astype(jnp.int32)


def moe_ffn(
    x,
    router,
    experts: Dict[str, jax.Array],
    *,
    top_k: int,
    experts_held: Optional[Tuple[int, int]] = None,
    norm_topk: bool = True,
    stack_index=None,
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
    ``counts.sum() == n * top_k`` and nothing was dropped.
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
    weights, chosen = route(x, router, top_k, norm_topk)
    pair_expert = chosen.reshape(-1)
    counts = jnp.zeros((n_experts,), jnp.int32).at[pair_expert].add(1)

    held = (pair_expert >= lo) & (pair_expert < hi)
    group = jnp.where(held, pair_expert - lo, n_held)  # elsewhere: last
    order = jnp.argsort(group, stable=True)
    rows = jnp.take(x, order // top_k, axis=0)  # [n*k, D], by expert
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

    pair_weight = weights.reshape(-1)
    # back to (token, k) order by a gather, then the k parts of a token add
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    parts = jnp.take(down, back, axis=0).astype(jnp.float32)
    # rows behind the last group are whatever the product left there
    parts = jnp.where(held[:, None], parts * pair_weight[:, None], 0.0)
    out = jnp.sum(parts.reshape(n, top_k, d), axis=1)
    return out.astype(x.dtype), counts
