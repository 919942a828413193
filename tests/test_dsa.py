"""``ops/dsa.py`` on its own: the order key, exact top-k selection with its
tie rule against ``jax.lax.top_k``, the selection kernel under Pallas' TPU
interpreter against its plain form, and writes of consecutive segments of
one row in one dispatch to either cache, at the tests' segments of 8 and the
stage's of 128."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops import dsa, mla


def test_the_order_key_keeps_the_floats_order():
    values = np.array([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, 7.25,
                       np.inf], np.float32)
    keys = np.asarray(dsa.order_key(jnp.asarray(values)))
    assert (np.diff(keys[:3]) > 0).all() and keys[3] == keys[4]
    assert (np.diff(keys[4:]) > 0).all() and keys[2] < keys[3]
    assert keys.min() > dsa.FLOOR


@pytest.mark.parametrize("k", [1, 5, 64, 200])
def test_selection_is_top_k_with_ties_to_the_earlier_slot(k):
    """Against ``jax.lax.top_k`` (ties to the lower index) on scores with
    many ties, some slots not visible; fewer visible than ``k`` keeps every
    visible one."""
    rng = np.random.default_rng(k)
    scores = rng.integers(-6, 6, (7, 150)).astype(np.float32) / 4
    visible = np.arange(150)[None, :] <= np.array(
        [0, 3, 63, 64, 100, 149, 149])[:, None]
    keys = jnp.where(jnp.asarray(visible), dsa.order_key(scores), dsa.FLOOR)
    got = np.asarray(dsa.select_plain(keys, k))
    masked = np.where(visible, scores, -np.inf)
    top = np.asarray(jax.lax.top_k(jnp.asarray(masked), min(k, 150))[1])
    want = np.zeros_like(got)
    np.put_along_axis(want, top, True, axis=1)
    np.testing.assert_array_equal(got, want & visible)
    np.testing.assert_array_equal(got.sum(1), np.minimum(visible.sum(1), k))


def _pairs(rng, c, n, heads, dim, layers, rows, span):
    """Integer-valued queries, weights and keys: every score is a sum of
    small integers, exact in float32 in any order, so the kernel and the
    plain form must agree to the bit, ties and all."""
    q = jnp.asarray(rng.integers(-2, 3, (c, n, heads, dim)), jnp.bfloat16)
    w = jnp.asarray(rng.integers(-2, 3, (c, n, heads)), jnp.float32)
    keys = jnp.asarray(rng.integers(-2, 3, (layers, rows, span, dim)),
                       jnp.bfloat16)
    return q, w, keys


@pytest.mark.parametrize("topk", [3, 20, 300])
def test_the_selection_kernel_is_its_plain_form(topk):
    """Pairs of three rows (one spare, past the last row) at starts in the
    first, a middle and the last block of a span of 256, queries in blocks
    of 128, under the interpreter."""
    rng = np.random.default_rng(topk)
    q, w, keys = _pairs(rng, 4, 16, 4, 128, 2, 3, 256)
    rows = jnp.array([2, 0, 3, 1], jnp.int32)
    start = jnp.array([0, 100, 240, 16], jnp.int32)
    got = np.asarray(dsa._select(q, w, keys, jnp.int32(1), rows, start, topk,
                                 128, interpret=True))
    want = np.asarray(dsa._select_plain_segment(
        q, w, keys, jnp.int32(1), rows, start, topk))
    np.testing.assert_array_equal(got, want)
    visible = np.asarray(start)[:, None] + np.arange(16)[None, :] + 1
    np.testing.assert_array_equal(got.sum(-1), np.minimum(visible, topk))


WRITES = {"write_positions": functools.partial(mla.write_positions,
                                               aligned=True),
          "write_slots": dsa.write_slots}


@pytest.mark.parametrize("write", sorted(WRITES))
def test_writes_of_one_rows_consecutive_segments_keep_both(write):
    """Two segments of 8 of row 1 and one of row 0 in one dispatch, and a
    spare pair past the last row: every real position lands, pads and the
    spare pair write nothing (the latent's aligned windows, the index keys'
    slots)."""
    leaf = jnp.full((2, 2, 32, 6), 9.0, jnp.float32)
    new = jnp.arange(4 * 8 * 6, dtype=jnp.float32).reshape(4, 8, 6)
    rows = jnp.array([1, 1, 0, 2], jnp.int32)
    start = jnp.array([0, 8, 0, 0], jnp.int32)
    lengths = jnp.array([8, 5, 3, 8], jnp.int32)
    out = np.asarray(WRITES[write](leaf, new, jnp.int32(1), rows, start,
                                   lengths))
    np.testing.assert_array_equal(out[1, 1, :8], new[0])
    np.testing.assert_array_equal(out[1, 1, 8:13], new[1, :5])
    np.testing.assert_array_equal(out[1, 0, :3], new[2, :3])
    assert (out[1, 1, 13:] == 9).all() and (out[1, 0, 3:] == 9).all()
    assert (out[0] == 9).all()


def test_a_rows_segments_of_128_are_written_in_one_call_bit_for_bit():
    """The stage's segments: row 1's three consecutive segments of 128 (the
    last 77 real) and row 0's first in ONE aligned write of the latent's
    channel-major leaf, in bfloat16: each real position holds exactly its
    value, every other slot what it held."""
    rng = np.random.default_rng(7)
    leaf = jnp.asarray(rng.normal(size=(2, 2, 512, 20)), jnp.bfloat16)
    new = jnp.asarray(rng.normal(size=(4, 128, 20)), jnp.bfloat16)
    rows = jnp.array([1, 1, 1, 0], jnp.int32)
    start = jnp.array([0, 128, 256, 0], jnp.int32)
    lengths = jnp.array([128, 128, 77, 128], jnp.int32)
    out = np.asarray(mla.write_positions(leaf, new, jnp.int32(1), rows, start,
                                         lengths, aligned=True))
    want = np.asarray(leaf).copy()
    for pair in range(4):
        at = int(start[pair])
        real = int(lengths[pair])
        want[1, int(rows[pair]), at:at + real] = np.asarray(new[pair, :real])
    np.testing.assert_array_equal(out.view(np.uint16), want.view(np.uint16))


def test_the_index_projection_turns_the_leading_channels_only():
    rng = np.random.default_rng(2)
    ix = dsa.Indexer(heads=2, dim=8, rope=4, theta=1e4, topk=4, eps=1e-6)
    lp = {"ix_wq": jnp.asarray(rng.normal(size=(6, 16)), jnp.float32),
          "ix_wk": jnp.asarray(rng.normal(size=(5, 8)), jnp.float32),
          "ix_norm": jnp.ones(8), "ix_bias": jnp.zeros(8),
          "ix_w": jnp.asarray(rng.normal(size=(5, 2)), jnp.float32)}
    u = jnp.asarray(rng.normal(size=(3, 5)), jnp.float32)
    c_q = jnp.asarray(rng.normal(size=(3, 6)), jnp.float32)
    at0 = dsa.index_project(lp, u, c_q, jnp.zeros(3, jnp.int32), ix)
    at5 = dsa.index_project(lp, u, c_q, jnp.full(3, 5, jnp.int32), ix)
    for a, b in zip(at0[:2], at5[:2]):
        np.testing.assert_allclose(a[..., 4:], b[..., 4:])
        assert np.abs(np.asarray(a[..., :4] - b[..., :4])).max() > 1e-2
    k = np.asarray(at0[1])
    np.testing.assert_allclose(k.mean(-1), 0, atol=1e-5)  # LayerNorm'd
    np.testing.assert_allclose(
        np.asarray(at0[2]), np.asarray(u @ lp["ix_w"]) / 4, rtol=1e-6)
