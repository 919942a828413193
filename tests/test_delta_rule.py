"""``ops/delta_rule.py``: the chunked form of the gated delta rule with a
decay per channel and the one-token update against the recurrence written out
token by token (float64), over ragged rows.  float32 on the CPU: the forms
differ by rounding order and by the triangular solve's, so the tolerance is a
few float32 ulps of the values' size through a chunk's substitution (2e-5
relative to the largest value; a wrong decay, a missed sub-block or a
dropped correction term is off by 1e-2 and more).

Every case of the chunked form runs twice: the plain ``jax.numpy`` form (what
``kda_chunked`` picks here, off the TPU) at heads of 8 channels, and the
kernel under Pallas ``interpret=True`` (``_kda_chunked_kernel``, the private
entry that forces it) at a head width it takes (128) and two blocks of
heads, against the same recurrence and tolerance."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops import delta_rule, ssm

TOLERANCE = 2e-5
#: heads a grid step of the kernel HERE (the module's constant is what the
#: chip prefers; the interpreter traces every head of a block)
HEAD_BLOCK = 2
#: form -> (the chunked form, (H, K, V))
FORMS = {
    "plain": (delta_rule.kda_chunked, (3, 8, 6)),
    "kernel": (functools.partial(delta_rule._kda_chunked_kernel,
                                 interpret=True),
               (2 * HEAD_BLOCK, 128, 128)),
}
H, K, V = FORMS["plain"][1]


@pytest.fixture(autouse=True)
def small_head_blocks(monkeypatch):
    monkeypatch.setattr(delta_rule, "HEAD_BLOCK", HEAD_BLOCK)


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(rows, n, seed=0, steep=False, dims=(H, K, V)):
    """Unit keys, scaled unit queries, a write strength up to 2 and a
    log-decay per channel; ``steep``: some channels fall by ~3 a position,
    below -100 over a chunk of 32 and more."""
    H, K, V = dims
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)

    q = unit(rng.normal(size=(rows, n, H, K))) * K ** -0.5
    k = unit(rng.normal(size=(rows, n, H, K)))
    v = rng.normal(size=(rows, n, H, V))
    rate = np.exp(rng.uniform(np.log(1e-3), np.log(1.6), (rows, n, H, K)))
    if steep:
        rate[..., ::3] = rng.uniform(2.5, 3.5, rate[..., ::3].shape)
    beta = rng.uniform(0, 2, (rows, n, H))
    beta[:, ::5] = 2.0  # the neg-eigenvalue end of the range itself
    state = rng.normal(size=(rows, H, K, V))
    return [a.astype(np.float32) for a in (q, k, v, -rate, beta, state)]


def _token_by_token(q, k, v, log_decay, beta, state, lengths):
    """The recurrence as written; row r stops at ``lengths[r]``."""
    rows, n, H = q.shape[:3]
    o = np.zeros(v.shape, np.float64)
    state = state.astype(np.float64).copy()
    q, k, v, log_decay, beta = (
        a.astype(np.float64) for a in (q, k, v, log_decay, beta))
    for r in range(rows):
        for t in range(int(lengths[r])):
            for h in range(H):
                s = np.exp(log_decay[r, t, h])[:, None] * state[r, h]
                s = s + beta[r, t, h] * np.outer(
                    k[r, t, h], v[r, t, h] - s.T @ k[r, t, h])
                state[r, h] = s
                o[r, t, h] = s.T @ q[r, t, h]
    return o, state


def _close(got, want):
    np.testing.assert_allclose(
        got, want, atol=TOLERANCE * max(np.abs(want).max(), 1.0))


@pytest.mark.parametrize("form,chunk,sub", [
    ("plain", 8, 4), ("plain", 32, 16), ("kernel", 16, 8), ("kernel", 32, 16)],
    ids=["chunk8-sub4", "chunk32-sub16", "kernel-chunk16-sub8",
         "kernel-chunk32-sub16"])
@pytest.mark.parametrize("n", [64, 32, 45, 5],
                         ids=["whole-chunks", "one-or-four-chunks",
                              "not-a-multiple", "shorter-than-a-sub-block"])
def test_chunked_form_is_the_recurrence(n, form, chunk, sub):
    """From a non-zero initial state, ``beta`` up to 2."""
    chunked, dims = FORMS[form]
    inputs = _inputs(2, n, seed=n, dims=dims)
    want_o, want_state = _token_by_token(*inputs, [n] * 2)
    o, got = chunked(*map(jnp.asarray, inputs), chunk, sub)
    assert o.dtype == got.dtype == jnp.float32
    _close(o, want_o)
    _close(got, want_state)


@pytest.mark.parametrize("form", list(FORMS))
@pytest.mark.parametrize("chunk,sub", [(32, 16), (64, 16), (32, 32)])
def test_channels_that_fall_below_minus_100_stay_finite_and_equal(
        chunk, sub, form):
    """A third of the channels fall by ~3 a position: -100 after 32, -200
    over a chunk of 64.  ``e^{-G}`` of such a channel is not a float32; the
    pairwise differences and the sub-blocks' own reference points are."""
    chunked, dims = FORMS[form]
    inputs = _inputs(2, 64, seed=11, steep=True, dims=dims)
    assert np.cumsum(inputs[3], axis=1).min() < -180
    want_o, want_state = _token_by_token(*inputs, [64] * 2)
    o, got = chunked(*map(jnp.asarray, inputs), chunk, sub)
    assert np.isfinite(o).all() and np.isfinite(got).all()
    _close(o, want_o)
    _close(got, want_state)


@pytest.mark.parametrize("form", list(FORMS))
def test_a_ragged_rows_state_is_that_of_its_own_last_token(form):
    chunked, dims = FORMS[form]
    n, lengths = 40, np.array([40, 17, 1, 0])
    q, k, v, log_decay, beta, state = _inputs(4, n, seed=7, dims=dims)
    real = np.arange(n)[None, :] < lengths[:, None]
    want_o, want_state = _token_by_token(
        q, k, v, log_decay, beta, state, lengths)
    o, got = chunked(*map(jnp.asarray, (
        q, k, v, np.where(real[..., None, None], log_decay, 0),
        np.where(real[..., None], beta, 0), state)), 16, 8)
    _close(got, want_state)
    _close(np.where(real[..., None, None], o, 0), want_o)
    # the row with nothing real keeps the state it came with, to the bit
    np.testing.assert_array_equal(got[3], state[3])
    # and a state taken at the padded end is another state
    _, at_the_end = chunked(
        *map(jnp.asarray, (q, k, v, log_decay, beta, state)), 16, 8)
    assert np.abs(at_the_end[1] - want_state[1]).max() > 0.01


@pytest.mark.parametrize("form,sub", [("plain", 4), ("kernel", 8)])
def test_a_segment_carries_on_where_the_one_before_stopped(form, sub):
    chunked, dims = FORMS[form]
    inputs = _inputs(2, 48, seed=3, dims=dims)
    want_o, want_state = _token_by_token(*inputs, [48] * 2)
    q, k, v, log_decay, beta, state = map(jnp.asarray, inputs)
    first, carried = chunked(
        q[:, :20], k[:, :20], v[:, :20], log_decay[:, :20], beta[:, :20],
        state, 16, sub)
    second, got = chunked(
        q[:, 20:], k[:, 20:], v[:, 20:], log_decay[:, 20:], beta[:, 20:],
        carried, 16, sub)
    _close(np.concatenate([first, second], axis=1), want_o)
    _close(got, want_state)


def test_the_one_token_update_is_the_recurrence():
    inputs = _inputs(3, 6, seed=5, steep=True)
    want_o, want_state = _token_by_token(*inputs, [6] * 3)
    q, k, v, log_decay, beta, state = map(jnp.asarray, inputs)
    for t in range(6):
        o, state = delta_rule.kda_update(
            q[:, t], k[:, t], v[:, t], log_decay[:, t], beta[:, t], state)
        _close(o, want_o[:, t])
    _close(state, want_state)
    # no write and no decay: the state as it was, to the bit
    o, kept = delta_rule.kda_update(
        q[:, 0], k[:, 0], v[:, 0], jnp.zeros_like(log_decay[:, 0]),
        jnp.zeros_like(beta[:, 0]), state)
    np.testing.assert_array_equal(kept, state)


def test_the_chunk_must_be_whole_sub_blocks():
    inputs = list(map(jnp.asarray, _inputs(1, 8)))
    with pytest.raises(ValueError, match="sub-block"):
        delta_rule.kda_chunked(*inputs, 24, 16)


@pytest.mark.parametrize("form", list(FORMS))
def test_bfloat16_operands_keep_the_state_float32_and_stay_near(form):
    """The products' operands in bfloat16 (2**-8 relative), accumulated in
    float32, through two chunks and their triangular systems: a few
    hundredths of the largest value, where float32 gives 2e-5."""
    chunked, dims = FORMS[form]
    inputs = _inputs(2, 64, seed=9, dims=dims)
    want_o, want_state = _token_by_token(*inputs, [64] * 2)
    q, k, v, log_decay, beta, state = map(jnp.asarray, inputs)
    o, got = chunked(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), log_decay, beta, state, 32, 16)
    assert o.dtype == got.dtype == jnp.float32
    assert np.abs(o - want_o).max() < 0.03 * np.abs(want_o).max()
    assert np.abs(got - want_state).max() < 0.03 * np.abs(want_state).max()


@pytest.mark.parametrize("backend,heads,width,fused", [
    ("tpu", 2 * HEAD_BLOCK, 128, True),
    ("tpu", 2 * HEAD_BLOCK, 16, False),   # the rehearsal's heads
    ("tpu", HEAD_BLOCK + 1, 128, False),  # no whole blocks
    ("cpu", 2 * HEAD_BLOCK, 128, False)],
    ids=["tpu-128", "tpu-16-wide", "tpu-odd-heads", "cpu-128"])
def test_the_kernel_is_picked_by_backend_and_shapes_alone(
        monkeypatch, backend, heads, width, fused):
    """``fused_chunks`` is what ``kda_chunked`` asks and what the prefill
    span reports: 16 rows of 128 positions in chunks of 64 are 32 (row,
    chunk) pairs, all through the kernel or none."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert delta_rule.fused_chunks(16, 128, heads, width, width, 64) == (
        32, 32 if fused else 0)
    # a segment shorter than a chunk is one chunk of whole sub-blocks
    assert delta_rule.fused_chunks(3, 20, heads, width, width, 64)[0] == 3
    assert delta_rule.fused_chunks(3, 130, heads, width, width, 64)[0] == 9
    ran = []
    for name in ("_kda_chunked_kernel", "_kda_chunked_plain"):
        monkeypatch.setattr(
            delta_rule, name,
            lambda *args, name=name: ran.append(name) or args[:2])
    shape = (2, 8, heads, width)
    delta_rule.kda_chunked(
        *(jnp.zeros(shape),) * 4, jnp.zeros(shape[:3]),
        jnp.zeros((2, heads, width, width)), 64)
    assert ran == ["_kda_chunked_kernel" if fused else "_kda_chunked_plain"]


def test_three_conv_windows_are_those_of_the_rows_own_last_tokens():
    """``ops/ssm.causal_conv`` serves q, k and v: a ragged row's window is
    its last K - 1 real inputs, whichever of the three it belongs to."""
    rng = np.random.default_rng(2)
    lengths = jnp.asarray([9, 4, 1])
    for channels in (5, 7, 7):
        x = jnp.asarray(rng.normal(size=(3, 9, channels)), jnp.float32)
        weight = jnp.asarray(rng.normal(size=(channels, 4)), jnp.float32)
        window = jnp.asarray(rng.normal(size=(3, 3, channels)), jnp.float32)
        _, kept = ssm.causal_conv(
            x, window, weight, jnp.zeros((channels,)), lengths)
        full = np.concatenate([window, x], axis=1)
        for r, n in enumerate([9, 4, 1]):
            np.testing.assert_array_equal(kept[r], full[r, n:n + 3])
