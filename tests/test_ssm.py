"""``ops/ssm.py``: the chunked matmul form of the Mamba-2 recurrence and the
one-token update against the recurrence written out token by token, over
ragged rows.  float32 on the CPU: the two forms differ by rounding order
only, so the tolerance is a few float32 ulps of the values' size (1e-5
relative to the largest value)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops import ssm

H, P, N, K = 4, 8, 16, 4
C = H * P + 2 * N


def _inputs(rows, n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, n, H, P)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.5), (rows, n, H))
                ).astype(np.float32)
    a = -rng.uniform(1, 16, H).astype(np.float32)
    b = rng.normal(size=(rows, n, N)).astype(np.float32)
    c = rng.normal(size=(rows, n, N)).astype(np.float32)
    state = rng.normal(size=(rows, H, P, N)).astype(np.float32)
    return x, dt, a, b, c, state


def _token_by_token(x, dt, a, b, c, state, lengths):
    """The recurrence as written; row r stops at ``lengths[r]``."""
    rows, n = x.shape[:2]
    y = np.zeros((rows, n, H, P), np.float64)
    state = state.astype(np.float64).copy()
    for r in range(rows):
        for t in range(int(lengths[r])):
            decay = np.exp(dt[r, t] * a)[:, None, None]
            state[r] = decay * state[r] + (
                dt[r, t][:, None] * x[r, t])[:, :, None] * b[r, t]
            y[r, t] = state[r] @ c[r, t]
    return y, state


@pytest.mark.parametrize("n,chunk", [(16, 8), (24, 8), (13, 8), (5, 8),
                                     (19, 256)],
                         ids=["two-chunks", "three-chunks", "not-a-multiple",
                              "shorter-than-a-chunk", "one-chunk"])
def test_chunked_form_is_the_recurrence(n, chunk):
    x, dt, a, b, c, state = _inputs(3, n, seed=n)
    want_y, want_state = _token_by_token(x, dt, a, b, c, state, [n] * 3)
    y, got = ssm.ssd_chunked(*map(jnp.asarray, (x, dt, a, b, c, state)), chunk)
    scale = np.abs(want_y).max()
    np.testing.assert_allclose(y, want_y, atol=1e-5 * scale)
    np.testing.assert_allclose(got, want_state,
                               atol=1e-5 * np.abs(want_state).max())


def test_a_ragged_rows_state_is_that_of_its_own_last_token():
    n, lengths = 24, np.array([24, 9, 1, 0])
    x, dt, a, b, c, state = _inputs(4, n, seed=7)
    real = np.arange(n)[None, :] < lengths[:, None]
    want_y, want_state = _token_by_token(x, dt, a, b, c, state, lengths)
    y, got = ssm.ssd_chunked(
        *map(jnp.asarray, (x, np.where(real[..., None], dt, 0), a, b, c,
                           state)), 8)
    np.testing.assert_allclose(got, want_state,
                               atol=1e-5 * np.abs(want_state).max())
    np.testing.assert_allclose(np.where(real[..., None, None], y, 0), want_y,
                               atol=1e-5 * np.abs(want_y).max())
    # the row with nothing real keeps the state it came with, to the bit
    np.testing.assert_array_equal(got[3], state[3])
    # and a state taken at the padded end is another state
    _, at_the_end = ssm.ssd_chunked(
        *map(jnp.asarray, (x, dt, a, b, c, state)), 8)
    assert np.abs(at_the_end[1] - want_state[1]).max() > 0.01


def test_the_one_token_update_is_the_recurrence():
    x, dt, a, b, c, state = _inputs(3, 6, seed=3)
    want_y, want_state = _token_by_token(x, dt, a, b, c, state, [6] * 3)
    got = jnp.asarray(state)
    for t in range(6):
        y, got = ssm.ssm_update(
            *map(jnp.asarray, (x[:, t], dt[:, t], a, b[:, t], c[:, t])), got)
        np.testing.assert_allclose(y, want_y[:, t],
                                   atol=1e-5 * np.abs(want_y).max())
    np.testing.assert_allclose(got, want_state,
                               atol=1e-5 * np.abs(want_state).max())


def _conv_reference(xbc, window, weight, bias, lengths):
    rows, n, _ = xbc.shape
    full = np.concatenate([window, xbc], axis=1).astype(np.float64)
    out = np.zeros_like(xbc, dtype=np.float64)
    for t in range(n):
        out[:, t] = bias + sum(
            weight[:, j] * full[:, t + j] for j in range(K))
    out = out / (1 + np.exp(-out))
    kept = np.stack([full[r, lengths[r]:lengths[r] + K - 1]
                     for r in range(rows)])
    return out, kept


def test_the_conv_keeps_each_rows_last_real_inputs():
    rng = np.random.default_rng(5)
    rows, n, lengths = 4, 8, np.array([8, 5, 2, 0])
    xbc = rng.normal(size=(rows, n, C)).astype(np.float32)
    window = rng.normal(size=(rows, K - 1, C)).astype(np.float32)
    weight = rng.uniform(-.5, .5, (C, K)).astype(np.float32)
    bias = rng.uniform(-.5, .5, C).astype(np.float32)
    want, kept = _conv_reference(xbc, window, weight, bias, lengths)
    out, got = ssm.causal_conv(
        *map(jnp.asarray, (xbc, window, weight, bias, lengths)))
    real = (np.arange(n)[None, :] < lengths[:, None])[..., None]
    np.testing.assert_allclose(np.where(real, out, 0), np.where(real, want, 0),
                               atol=1e-5)
    # row 1's window: its inputs 2, 3, 4; row 2's: the old window's last and
    # its own two; row 3's: the window it came with
    np.testing.assert_array_equal(got, kept.astype(np.float32))
    np.testing.assert_array_equal(got[1], xbc[1, 2:5])
    np.testing.assert_array_equal(got[3], window[3])
    # one position at a time gives the same outputs and the same window
    step_window = jnp.asarray(window)
    for t in range(n):
        step, step_window = ssm.conv_update(
            jnp.asarray(xbc[:, t]), step_window, jnp.asarray(weight),
            jnp.asarray(bias))
        np.testing.assert_allclose(step, want[:, t], atol=1e-5)
    np.testing.assert_array_equal(step_window[0], got[0])


def test_the_state_stays_float32_under_bfloat16_inputs():
    x, dt, a, b, c, state = _inputs(2, 16, seed=9)
    low = [jnp.asarray(t, jnp.bfloat16) for t in (x, b, c)]
    y, got = ssm.ssd_chunked(low[0], jnp.asarray(dt), jnp.asarray(a), low[1],
                             low[2], jnp.asarray(state), 8)
    assert y.dtype == jnp.float32 and got.dtype == jnp.float32
    want_y, want_state = _token_by_token(
        *(np.asarray(t, np.float32) for t in (low[0], dt, a, low[1], low[2])),
        state, [16, 16])
    # bfloat16 operands (8 bits of mantissa) into float32 sums: 2**-8 of the
    # values' size a product, growing as the root of the terms
    np.testing.assert_allclose(got, want_state,
                               atol=0.03 * np.abs(want_state).max())
    np.testing.assert_allclose(y, want_y, atol=0.03 * np.abs(want_y).max())
