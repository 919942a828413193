"""Sequence/context-parallel attention oracle tests (8-device CPU mesh).

Pattern per SURVEY.md §4: framework output ≡ plain single-device oracle on
the same arrays — here sharded ring/Ulysses attention vs dense
``full_attention``, causal and not, plus gradient flow.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map
from sparkdl_tpu.parallel.context import (
    full_attention,
    make_sp_attention,
    ring_attention,
    ulysses_attention,
)

BATCH, SEQ, HEADS, DIM = 2, 64, 8, 16


@pytest.fixture(scope="module")
def seq_mesh():
    return Mesh(np.asarray(jax.devices()[:8]).reshape(8), ("seq",))


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    shape = (BATCH, SEQ, HEADS, DIM)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32)) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(seq_mesh, causal):
    q, k, v = _qkv()
    want = full_attention(q, k, v, causal=causal)
    fn = make_sp_attention(seq_mesh, impl="ring", causal=causal)
    got = np.asarray(fn(q, k, v))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(seq_mesh, causal):
    q, k, v = _qkv(1)
    want = full_attention(q, k, v, causal=causal)
    fn = make_sp_attention(seq_mesh, impl="ulysses", causal=causal)
    got = np.asarray(fn(q, k, v))
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


def test_ring_attention_grads_match(seq_mesh):
    """SP must be trainable: d(loss)/d(q,k,v) through the ring equals the
    dense-attention gradients."""
    q, k, v = _qkv(2)

    def loss_full(q, k, v):
        return (full_attention(q, k, v) ** 2).sum()

    spec = P(None, "seq", None, None)

    @jax.jit
    def loss_ring(q, k, v):
        out = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis_name="seq"),
            mesh=seq_mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, k, v)
        return (out**2).sum()

    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_full, g_ring):
        np.testing.assert_allclose(
            np.asarray(gr), np.asarray(gf), atol=5e-4, rtol=1e-4
        )


def test_ring_attention_output_stays_sharded(seq_mesh):
    q, k, v = _qkv(3)
    spec = P(None, "seq", None, None)
    sharded = jax.device_put(q, NamedSharding(seq_mesh, spec))
    fn = make_sp_attention(seq_mesh, impl="ring")
    out = fn(sharded, k, v)
    assert out.sharding.spec == spec  # no implicit gather of the sequence


def test_ulysses_rejects_indivisible_heads(seq_mesh):
    rng = np.random.RandomState(0)
    shape = (1, 16, 4, 8)  # 4 heads on an 8-way axis
    q = jnp.asarray(rng.randn(*shape).astype(np.float32))
    spec = P(None, "seq", None, None)
    with pytest.raises(ValueError, match="divisible"):
        shard_map(
            lambda a, b, c: ulysses_attention(a, b, c, axis_name="seq"),
            mesh=seq_mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )(q, q, q)
