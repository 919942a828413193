"""``models/hybrid.attention_segment``: a prefill segment's attention through
the cache's leaves where they lie, by blocks of keys up to each row's own
``start + n``, against the plain form it replaced — every row's whole span
read out of the leaf, scored, masked, softmaxed at once and written back
whole — which lives on here as the reference.

float32 throughout and the products at ``highest`` precision, so that the
two differ by the order of the softmax's float32 additions only."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import granite_hybrid as granite_reference
from chipbench.reference import solar_open2 as solar_reference
from sparkdl_tpu.models import granite_hybrid, hybrid, solar_open2
from test_granite_hybrid import CONFIG as GRANITE
from test_solar_open2 import CONFIG as SOLAR

KV, DH, N = 2, 16, 8
ROWS, SPAN, BLOCK = 5, 80, 24  # 80 = 3.33 blocks of 24: the span has a tail
LAYERS, LAYER = 2, 1


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(hybrid, "KEY_BLOCK", BLOCK)


def whole_span(q, k, v, cache_k, cache_v, layer, rows, start, scale):
    """The form before PR 38, on the same arguments."""
    n = q.shape[1]
    if rows is None:
        rows = jnp.arange(q.shape[0])
    mine_k, mine_v = (hybrid.read(c, layer, rows) for c in (cache_k, cache_v))
    put = jax.vmap(lambda cache, new, at: jax.lax.dynamic_update_slice(
        cache, new.transpose(1, 0, 2), (0, at, 0)))
    mine_k, mine_v = put(mine_k, k, start), put(mine_v, v, start)
    slots = jnp.arange(mine_k.shape[2])

    def one_row(row):
        q, keys, values, start = row
        scores = jnp.einsum("nkgd,kmd->kgnm", q, keys,
                            preferred_element_type=jnp.float32) * scale
        visible = slots[None, :] <= start + jnp.arange(n)[:, None]
        probs = jax.nn.softmax(jnp.where(visible, scores, hybrid.NEG), axis=-1)
        out = jnp.einsum("kgnm,kmd->nkgd", probs.astype(values.dtype), values)
        return out.reshape(n, -1)

    out = jax.lax.map(one_row, (q, mine_k, mine_v, start))
    return (out, hybrid.write(cache_k, mine_k, layer, rows),
            hybrid.write(cache_v, mine_v, layer, rows))


def _inputs(group, pairs, rows=ROWS, span=SPAN, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return (normal(pairs, N, KV, group, DH), normal(pairs, N, KV, DH),
            normal(pairs, N, KV, DH), normal(LAYERS, rows, KV, span, DH),
            normal(LAYERS, rows, KV, span, DH))


@pytest.mark.parametrize("group", [4, 8])
@pytest.mark.parametrize("start", [0, 48, 40, 72], ids=[
    "from-the-first-slot", "at-a-multiple-of-the-block",
    "inside-a-block", "the-last-segment-of-the-span"])
def test_a_segment_is_the_whole_spans_softmax(start, group, small_blocks):
    """Two rows a call, each at this ``start`` or at another: what a row
    sees ends at ITS ``start + n``, in whole blocks, the span's last block
    moved back to end with the span; the cache comes back with the two new
    segments in it and not a bit changed elsewhere."""
    q, k, v, cache_k, cache_v = _inputs(group, 2, seed=start + group)
    rows = jnp.array([3, 0], jnp.int32)
    starts = jnp.array([start, 16], jnp.int32)
    want, want_k, want_v = whole_span(
        q, k, v, cache_k, cache_v, LAYER, rows, starts, DH ** -0.5)
    got, got_k, got_v = jax.jit(
        hybrid.attention_segment, static_argnums=(5, 8))(
        q, k, v, cache_k, cache_v, LAYER, rows, starts, DH ** -0.5)
    assert got.shape == (2, N, KV * group * DH)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)
    # the segment went in, and only there
    np.testing.assert_array_equal(
        got_k[LAYER, 3, :, start:start + N], k[0].transpose(1, 0, 2))
    changed = np.asarray(got_k != cache_k).any(axis=(2, 4))
    assert changed.sum() == 2 * N and changed[LAYER, 3, start:start + N].all()


@pytest.mark.parametrize("group", [4, 8])
@pytest.mark.parametrize(
    "block", [512, 6], ids=["one-block", "blocks-and-a-tail"])
def test_whole_rows_from_an_empty_cache(block, group, monkeypatch):
    """``forward_logits``'s path: every row in order (``rows`` None), ``span
    == n`` of any length, under a block larger than the span and under one
    that does not divide it."""
    monkeypatch.setattr(hybrid, "KEY_BLOCK", block)
    q, k, v, _, _ = _inputs(group, 3, seed=group)
    empty = jnp.zeros((LAYERS, 3, KV, N, DH), jnp.float32)
    starts = jnp.zeros(3, jnp.int32)
    want, want_k, want_v = whole_span(
        q, k, v, empty, empty, LAYER, None, starts, 0.25)
    got, got_k, got_v = hybrid.attention_segment(
        q, k, v, empty, empty, LAYER, None, starts, 0.25)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)


def test_a_spare_pair_leaves_every_rows_cache_untouched(small_blocks):
    """A dispatch's spare pairs name the row past the last (``start`` 0):
    they read the last row and write nothing — not into the last row
    either, which an in-place update that clamps would overwrite."""
    q, k, v, cache_k, cache_v = _inputs(4, 3)
    rows = jnp.array([ROWS, 2, ROWS], jnp.int32)
    starts = jnp.array([0, 32, 0], jnp.int32)
    want, want_k, want_v = whole_span(
        q, k, v, cache_k, cache_v, LAYER, rows, starts, 0.25)
    got, got_k, got_v = hybrid.attention_segment(
        q, k, v, cache_k, cache_v, LAYER, rows, starts, 0.25)
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=2e-6)
    np.testing.assert_array_equal(got_k, want_k)
    np.testing.assert_array_equal(got_v, want_v)
    untouched = [r for r in range(ROWS) if r != 2]
    np.testing.assert_array_equal(got_k[:, untouched], cache_k[:, untouched])
    np.testing.assert_array_equal(got_v[:, untouched], cache_v[:, untouched])
    # all of a dispatch spare: nothing at all is written
    nobody = jnp.full(3, ROWS, jnp.int32)
    _, same_k, same_v = hybrid.attention_segment(
        q, k, v, cache_k, cache_v, LAYER, nobody, jnp.zeros(3, jnp.int32),
        0.25)
    np.testing.assert_array_equal(same_k, cache_k)
    np.testing.assert_array_equal(same_v, cache_v)


def test_keys_scored_counts_whole_blocks_up_to_each_segments_end(
        small_blocks):
    # ends 8, 48, 56 and 80 of a span of 80 under blocks of 24
    assert hybrid.keys_scored([0, 40, 48, 72], N, SPAN) == 24 * (1 + 2 + 3 + 4)
    # a span shorter than the block is one block of its own length
    assert hybrid.keys_scored([0, 0], N, 16) == 2 * 16
    assert hybrid.keys_scored([], N, SPAN) == 0


# -- the shapes of the two models' prefill -----------------------------------

PREFILL_SPAN = 88  # no other axis of either small model is 88 long


def _arrays(jaxpr):
    """Every (dtype, shape) an equation of ``jaxpr`` makes, loops' and
    branches' bodies included."""
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            if hasattr(var.aval, "shape"):
                yield var.aval.dtype, tuple(var.aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _arrays(sub)


def _small(name):
    if name == "granite":
        module, config, reference = granite_hybrid, GRANITE, granite_reference
        cfg = granite_hybrid.GraniteHybridConfig.from_dict(config)
    else:
        module, config, reference = solar_open2, SOLAR, solar_reference
        cfg = solar_open2.SolarOpen2Config.from_dict(config)
    return module, cfg, reference.make_params(config, 3, "float32")


@pytest.mark.parametrize("name", ["granite", "solar"])
def test_prefill_scores_no_span_and_is_one_program_for_every_start(
        name, small_blocks):
    """The prefill of a small model of either kind holds no float32 array
    whose last axis is the span (the ``[.., n, span]`` scores of the form
    before PR 38, and the ``[c, KV, span, dh]`` copies of the rows' cache,
    are gone), and ``start`` is a value, not a shape: one trace, one
    program."""
    module, cfg, params = _small(name)
    state = module.empty_state(cfg, ROWS, PREFILL_SPAN, jnp.float32)
    tokens = jnp.ones((2, N), jnp.int32)
    rows = jnp.array([1, ROWS], jnp.int32)
    lengths = jnp.array([N, 0], jnp.int32)

    def prefill(params, state, tokens, rows, start, lengths):
        return module.prefill(params, cfg, state, tokens, rows, start, lengths)

    starts = [jnp.array([at, 0], jnp.int32) for at in (0, 24, 40, 80)]
    jaxpr = jax.make_jaxpr(prefill)(
        params, state, tokens, rows, starts[0], lengths).jaxpr
    spans = [(dtype, shape) for dtype, shape in _arrays(jaxpr)
             if shape[-1:] == (PREFILL_SPAN,) and dtype == jnp.float32]
    assert not spans, spans
    assert any(shape[-1:] == (BLOCK,) and dtype == jnp.float32
               for dtype, shape in _arrays(jaxpr))
    # the cache's rows are never read out whole: the only arrays with the
    # span on an axis are the leaves themselves
    assert {shape for _, shape in _arrays(jaxpr) if PREFILL_SPAN in shape} == {
        state["k"].shape}
    program = jax.jit(prefill)
    for start in starts:
        state, logp, _ = program(params, state, tokens, rows, start, lengths)
    assert program._cache_size() == 1 and bool(jnp.isfinite(logp[0]).all())
