"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no sequence dimension at all (fixed-size image CNNs —
SURVEY.md §5.7), but this framework treats long-context as first-class: when
a sequence is too long for one chip's HBM, attention must run with the
sequence sharded across the mesh.  Two standard schemes, both as pure
``shard_map``-compatible functions over a sequence axis:

- :func:`ring_attention` — K/V blocks rotate around the ring via
  ``lax.ppermute`` while each device holds its Q shard; softmax is
  accumulated online (flash-attention style running max/denominator), so
  memory stays O(block²) and the sequence dim never materializes whole.
  Communication rides neighbor links (ICI-friendly), overlapping with the
  per-block matmuls.
- :func:`ulysses_attention` — ``lax.all_to_all`` reshards seq-parallel
  Q/K/V to *head*-parallel, runs dense local attention per head group, and
  reshards back.  Cheaper compute schedule when heads >= mesh axis, at the
  cost of two all-to-alls.

Both are numerically oracle-tested against single-device full attention
(``tests/test_context.py``).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, shard_map


def full_attention(
    q,
    k,
    v,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_len: Optional[int] = None,
):
    """Plain softmax attention — the single-device oracle.

    Shapes: ``q/k/v: (batch, seq, heads, head_dim)`` -> same.
    ``kv_len`` masks out key positions >= kv_len (token-padding support).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k)
    s_q, s_k = logits.shape[-2], logits.shape[-1]
    mask = jnp.ones((s_q, s_k), bool)
    if causal:
        mask &= jnp.tril(jnp.ones((s_q, s_k), bool))
    if kv_len is not None:
        mask &= (jnp.arange(s_k) < kv_len)[None, :]
    logits = jnp.where(mask, logits, -jnp.inf)
    # NaN-safe softmax: fully-masked query rows (padded tokens) yield zeros
    m = jnp.max(logits, axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    e = jnp.where(mask[None, None], jnp.exp(logits - m), 0.0)
    denom = e.sum(axis=-1, keepdims=True)
    probs = e / jnp.where(denom == 0.0, 1.0, denom)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def ring_attention(
    q,
    k,
    v,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_len: Optional[int] = None,
):
    """Blockwise ring attention over a sharded sequence axis.

    Call inside ``shard_map`` with ``q/k/v`` sharded on ``seq`` (shapes per
    device: ``(batch, seq/n, heads, head_dim)``).  Every device computes its
    Q block against all K/V blocks as they rotate around the ring; the
    softmax normalizer is accumulated online so the result is *exactly*
    (up to float assoc) full attention over the global sequence.

    ``causal=True`` masks by global position (block offsets derived from
    ``lax.axis_index``), supporting autoregressive use.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, s_blk, h, d = q.shape
    q = q * scale

    # online-softmax accumulators, marked device-varying over the ring
    # axis so the fori_loop carry types stay consistent
    def _varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    acc = _varying(jnp.zeros((b, s_blk, h, d), jnp.float32))
    denom = _varying(jnp.zeros((b, h, s_blk), jnp.float32))
    running_max = _varying(jnp.full((b, h, s_blk), -jnp.inf, jnp.float32))

    q_pos = idx * s_blk + jnp.arange(s_blk)  # global positions of our Q rows

    def body(i, carry):
        acc, denom, running_max, k_blk, v_blk = carry
        # which device's block are we holding at ring step i?
        src = (idx + i) % n
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        )
        k_pos = src * s_blk + jnp.arange(s_blk)
        mask = None
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        if kv_len is not None:
            kv_mask = jnp.broadcast_to(
                (k_pos < kv_len)[None, :], (s_blk, s_blk)
            )
            mask = kv_mask if mask is None else (mask & kv_mask)
        if mask is not None:
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(running_max, blk_max)
        # guard: fully-masked rows keep -inf max; exp(-inf - -inf) -> use 0
        correction = jnp.where(
            jnp.isneginf(running_max), 0.0, jnp.exp(running_max - new_max)
        )
        probs = jnp.exp(
            logits - jnp.where(jnp.isneginf(new_max), 0.0, new_max)[..., None]
        )
        probs = jnp.where(jnp.isneginf(logits), 0.0, probs)
        acc = acc * correction.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", probs, v_blk.astype(jnp.float32)
        )
        denom = denom * correction + probs.sum(axis=-1)
        # rotate K/V to the next device (neighbor exchange over ICI)
        perm = [(j, (j - 1) % n) for j in range(n)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return acc, denom, jnp.maximum(running_max, new_max), k_blk, v_blk

    acc, denom, running_max, _, _ = lax.fori_loop(
        0, n, body, (acc, denom, running_max, k, v)
    )
    safe = jnp.where(denom == 0.0, 1.0, denom)
    out = acc / safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    kv_len: Optional[int] = None,
    local_attn=None,
):
    """Ulysses-style sequence parallelism: all-to-all seq->head resharding.

    Call inside ``shard_map`` with ``q/k/v`` sharded on ``seq``; requires
    ``heads % axis_size == 0``.  Each device ends up with the *full*
    sequence for ``heads/n`` heads, runs dense attention, and the result is
    resharded back to the sequence axis.

    ``local_attn`` swaps the per-device dense step — e.g.
    :func:`sparkdl_tpu.ops.flash_attention` to keep the local (s, s)
    score matrix out of HBM on long sequences (``impl="ulysses-flash"``
    in :func:`make_sp_attention`).
    """
    n = lax.axis_size(axis_name)
    b, s_blk, h, d = q.shape
    if h % n != 0:
        raise ValueError(
            f"ulysses_attention requires heads ({h}) divisible by the "
            f"sequence-axis size ({n}); use ring_attention instead"
        )

    def to_heads(x):
        # (b, s/n, h, d) -> all_to_all over h -> (b, s, h/n, d)
        x = x.reshape(b, s_blk, n, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=False)
        return x.reshape(b, s_blk * n, h // n, d)

    def to_seq(x):
        # (b, s, h/n, d) -> (b, s/n, h, d); heads reassemble as (n, h/n)
        # to invert to_heads' (dev, local) head indexing
        x = x.reshape(b, n, s_blk, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=False)
        return x.reshape(b, s_blk, h, d)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    attn = local_attn if local_attn is not None else full_attention
    out = attn(qh, kh, vh, causal=causal, scale=scale, kv_len=kv_len)
    return to_seq(out)


def make_sp_attention(mesh, axis_name: str = "seq", impl: str = "ring",
                      causal: bool = False, kv_len: Optional[int] = None):
    """Wrap ring/ulysses attention as a jittable global-array function:
    ``fn(q, k, v)`` with inputs/outputs sharded on ``axis_name`` along the
    sequence dim (dim 1 of ``(batch, seq, heads, head_dim)``)."""
    from jax.sharding import PartitionSpec as P

    check_vma = True
    if impl == "ring":
        inner = ring_attention
    elif impl == "ulysses-flash":
        from sparkdl_tpu.ops import flash_attention

        inner = partial(ulysses_attention, local_attn=flash_attention)
        # pallas INTERPRET mode mixes varying/plain values inside the
        # kernel, which the vma checker rejects; on real TPU the kernel
        # mirrors vma in its out_shape, so keep the checker there
        check_vma = jax.default_backend() == "tpu"
    elif impl == "ulysses":
        inner = ulysses_attention
    else:
        raise ValueError(
            f"unknown SP attention impl {impl!r}; expected 'ring', "
            "'ulysses', or 'ulysses-flash'"
        )
    spec = P(None, axis_name, None, None)

    @jax.jit
    def fn(q, k, v):
        return shard_map(
            partial(inner, axis_name=axis_name, causal=causal, kv_len=kv_len),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=check_vma,
        )(q, k, v)

    return fn


def pad_tokens_for_sp(mesh, axis_name: str = "seq", impl: str = "ring",
                      causal: bool = False):
    """Sequence-parallel attention for token counts that don't divide the
    mesh axis (a ViT's CLS token breaks divisibility by design): pads the
    token axis up to a multiple, masks the pad *keys* out of the softmax
    (``kv_len``), runs the sharded schedule, and slices the pad queries off.
    Returns ``fn(q, k, v)`` usable as a model's ``attn_impl``."""
    n = int(np.prod([mesh.shape[a] for a in ([axis_name])]))
    # one jitted schedule per real sequence length: every encoder block
    # (and every forward) reuses the same jit object, so XLA compiles the
    # ring program once instead of once per call
    inner_cache = {}

    def fn(q, k, v):
        s = q.shape[1]
        pad = (-s) % n
        if pad:
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            q_p = jnp.pad(q, widths)
            k_p = jnp.pad(k, widths)
            v_p = jnp.pad(v, widths)
        else:
            q_p, k_p, v_p = q, k, v
        if s not in inner_cache:
            inner_cache[s] = make_sp_attention(
                mesh, axis_name=axis_name, impl=impl, causal=causal, kv_len=s
            )
        out = inner_cache[s](q_p, k_p, v_p)
        return out[:, :s] if pad else out

    return fn
