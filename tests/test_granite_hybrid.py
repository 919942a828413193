"""``models/granite_hybrid.py`` against the plain reference
(``chipbench/reference/granite_hybrid.py``) at a small size on the CPU, both
kinds of layer present, seeded weights.

float32 weights, and the program's products at ``highest`` precision here, so
that program and reference differ by rounding order only: logits agree to
1e-5 of their spread (a few float32 ulps through four layers).  The
bfloat16 test holds the noise of the compute dtype instead, with its reason
beside it."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import granite_hybrid as reference
from sparkdl_tpu.models import granite_hybrid as gh
from sparkdl_tpu.models import hybrid
from sparkdl_tpu.transformers.ar_generate import SegmentPlan

CONFIG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba", "attention"],
    num_attention_heads=4, num_key_value_heads=2, num_local_experts=8,
    num_experts_per_tok=2, intermediate_size=16, shared_intermediate_size=24,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=8,
    attention_multiplier=0.125, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5,
)
LENGTHS = [5, 30, 16, 9]  # shorter than a segment of 8 ... longer than three


@pytest.fixture(scope="module")
def params():
    return reference.make_params(CONFIG, 41, "float32")


@pytest.fixture(scope="module")
def cfg():
    return gh.GraniteHybridConfig.from_dict(CONFIG)


@pytest.fixture(scope="module")
def rows():
    rng = np.random.default_rng(0)
    return [rng.integers(0, 96, n).astype(np.int32) for n in LENGTHS]


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


def _padded(rows, n):
    tokens = np.zeros((len(rows), n), np.int32)
    for i, row in enumerate(rows):
        tokens[i, :len(row)] = row
    return jnp.asarray(tokens), jnp.asarray([len(r) for r in rows])


def _spread(logits):
    return float(np.std(logits, axis=-1).mean())


def test_the_config_reads_the_published_keys_and_the_share(cfg):
    assert cfg.layer_types == ("mamba", "attention", "mamba", "mamba")
    assert cfg.runs == [("mamba", 0, 0, 1), ("attention", 1, 0, 1),
                        ("mamba", 2, 1, 2)]
    assert (cfg.inner, cfg.conv_width, cfg.attention_head_dim) == (64, 96, 8)
    assert cfg.routed == 8 and cfg.held == (0, 8)
    share = gh.GraniteHybridConfig.from_dict(dict(
        CONFIG, num_local_experts=4, experts_held=[4, 8],
        published={"num_local_experts": 8}))
    assert share.routed == 8 and share.held == (4, 8)
    assert gh.param_shapes(share)["ffn"]["w_gate"] == (4, 4, 32, 16)
    assert gh.param_shapes(share)["ffn"]["router"] == (4, 32, 8)
    with pytest.raises(ValueError, match="experts_held"):
        gh.GraniteHybridConfig.from_dict(dict(CONFIG, experts_held=[0, 4]))
    with pytest.raises(NotImplementedError, match="group"):
        gh.GraniteHybridConfig.from_dict(dict(CONFIG, mamba_n_groups=2))
    with pytest.raises(ValueError, match="mamba_expand"):
        gh.GraniteHybridConfig.from_dict(dict(CONFIG, mamba_d_head=4))


def test_params_have_the_references_shapes(params, cfg):
    shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape), params)
    assert shapes == gh.param_shapes(cfg) == reference.shapes(CONFIG)
    own = gh.init_params(cfg, seed=3, dtype=jnp.bfloat16)
    assert jax.tree_util.tree_map(lambda a: tuple(a.shape), own) == shapes
    assert own["mamba"]["w_in"].dtype == jnp.bfloat16
    # what feeds an exponential stays float32
    assert {own["mamba"][k].dtype for k in ("a_log", "dt_bias", "d")} == {
        jnp.dtype("float32")}
    steps = jax.nn.softplus(own["mamba"]["dt_bias"])
    assert 0.99e-3 <= float(steps.min()) and float(steps.max()) <= 0.101
    assert 0 <= float(own["mamba"]["a_log"].min())
    assert float(own["mamba"]["a_log"].max()) <= np.log(16)


def test_forward_logits_is_the_references_forward(params, cfg, rows):
    tokens, lengths = _padded(rows, 32)
    got = np.asarray(gh.forward_logits(params, cfg, tokens, lengths))
    for i, row in enumerate(rows):
        want = np.asarray(reference.forward(params, CONFIG, row))
        assert np.abs(got[i, :len(row)] - want).max() < 1e-5 * _spread(want)


def _prefill_in_segments(params, cfg, rows, state, segment, count):
    """The rows' prompts through ``prefill`` segment by segment, ``count``
    pairs a dispatch as the stage lays them out (spare pairs name nobody, a
    row never twice in a dispatch).  Returns (state, the log-probabilities
    after each row's last token)."""
    plan = SegmentPlan(rows, len(rows), segment, count, gen=8)
    first = {}
    for arrays, last in plan.dispatches:
        state, logp, _ = gh.prefill(
            params, cfg, state, *map(jnp.asarray, arrays))
        for slot, r in last:
            first[r] = np.asarray(logp[slot])
    return state, first


def test_prefill_in_segments_then_decode_is_the_full_forward(
        params, cfg, rows):
    """Rows of 5, 30, 16 and 9 tokens in one state, segments of 8, three
    pairs a dispatch, from a state full of another batch's leavings: the
    log-probabilities at every one of 6 generated positions are the
    reference's full forward over prompt + generated tokens."""
    state = jax.tree_util.tree_map(
        lambda a: a + 3 if a.dtype != jnp.int32 else a + 5,
        gh.empty_state(cfg, len(rows), 48, jnp.float32))
    state, first = _prefill_in_segments(params, cfg, rows, state, 8, 3)
    np.testing.assert_array_equal(state["position"], LENGTHS)
    logps = [np.stack([first[r] for r in range(len(rows))])]
    tokens = [np.asarray(state["token"])]
    for _ in range(5):
        state, logp, counts = gh.decode_step(params, cfg, state)
        logps.append(np.asarray(logp))
        tokens.append(np.asarray(state["token"]))
        assert counts.shape == (4, 8) and int(counts.sum()) == 4 * 4 * 2
    np.testing.assert_array_equal(state["position"], np.array(LENGTHS) + 5)
    for r, row in enumerate(rows):
        generated = [int(t[r]) for t in tokens]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        got = np.stack([step[r] for step in logps])
        # float32 both sides, rounding order only: a log-probability near
        # -4.57 has an ulp of 4.8e-7 (1e-4 of the spread at this tiny
        # width), so four of those
        assert np.abs(got - want).max() < 2e-6, r
        assert [int(g.argmax()) for g in got] == generated


def test_several_steps_a_dispatch_are_the_single_steps(params, cfg, rows):
    state = gh.empty_state(cfg, len(rows), 48, jnp.float32)
    state, _ = _prefill_in_segments(params, cfg, rows, state, 16, 4)
    one = state
    singles = []
    for _ in range(3):
        one, logp, _ = gh.decode_step(params, cfg, one)
        singles.append((np.asarray(one["token"]), np.asarray(logp.max(-1))))
    many, tokens, logprobs, counts = gh.decode(params, cfg, state, 3)
    np.testing.assert_array_equal(tokens, np.stack([s[0] for s in singles], 1))
    np.testing.assert_allclose(
        logprobs, np.stack([s[1] for s in singles], 1), atol=1e-6)
    assert int(counts.sum()) == 3 * 4 * 4 * 2
    for name in one:
        np.testing.assert_allclose(many[name], one[name], atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(params, cfg):
    """``model-configs`` section 4: the parts that the shares (0, E/2) and
    (E/2, E) give, with what both chips compute alike (the mixer's residual
    stream, the router, the shared expert) counted once, add up to the uncut
    reference's layer; float32, so to rounding."""
    rng = np.random.default_rng(2)
    # small, so that a part read back off the residual stream (y - x) keeps
    # its digits; the norm in front makes the layer's output the same size
    x = jnp.asarray(0.01 * rng.normal(size=(7, 32)), jnp.float32)
    layer = 2  # a Mamba layer's feed-forward; every layer's is alike
    fp = {k: params["ffn"][k][layer] for k in reference.FFN_KEYS}
    u = reference.rms_norm(x, fp["post_norm"], 1e-5)
    whole = np.asarray(reference.feed_forward(CONFIG, fp, u))
    alike = np.asarray(reference._mm(
        jax.nn.silu(reference._mm(u, fp["shared_gate"], None))
        * reference._mm(u, fp["shared_up"], None), fp["shared_down"], None))
    ffn, experts = hybrid.split_ffn(params)
    parts = []
    for lo, hi in ((0, 4), (4, 8)):
        share = dataclasses.replace(
            cfg, num_local_experts=4, routed_experts=8, experts_held=(lo, hi))
        held = {k: v[:, lo:hi] for k, v in experts.items()}
        y, counts = gh._feed_forward(
            share, hybrid.at(ffn, layer), held, jnp.int32(layer), x)
        assert int(counts.sum()) == 7 * 2  # routed over all 8 experts
        # y = x + r * (routed part + shared): the routed part alone
        parts.append((np.asarray(y - x) / 0.22) - alike)
    # an expert left out or counted twice is off by 1e-1 of the largest value
    np.testing.assert_allclose(parts[0] + parts[1] + alike, whole,
                               atol=1e-4 * np.abs(whole).max())
    assert np.abs(parts[0]).max() > 0 and np.abs(parts[1]).max() > 0


def test_a_sliced_vocabularys_logits_are_the_slice_of_the_wholes(
        params, cfg, rows):
    """The embedding is tied: with the ids drawn from the slice, the sliced
    model's logits are the first rows of the whole model's."""
    low = [row % 48 for row in rows[:2]]
    tokens, lengths = _padded(low, 32)
    whole = np.asarray(gh.forward_logits(params, cfg, tokens, lengths))
    sliced = gh.forward_logits(
        dict(params, embed=params["embed"][:48]),
        dataclasses.replace(cfg, vocab_size=48), tokens, lengths)
    np.testing.assert_allclose(sliced, whole[..., :48], atol=1e-7)


def test_bfloat16_keeps_the_state_float32_and_stays_near_the_reference(cfg):
    params = reference.make_params(CONFIG, 43, "bfloat16")
    rng = np.random.default_rng(1)
    rows = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 6)]
    state = gh.empty_state(cfg, 2, 32, jnp.bfloat16)
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].dtype == state["k"].dtype == jnp.bfloat16
    state, first = _prefill_in_segments(params, cfg, rows, state, 8, 2)
    state, tokens, logprobs, _ = gh.decode(params, cfg, state, 4)
    assert state["ssm"].dtype == jnp.float32 and logprobs.dtype == jnp.float32
    for r, row in enumerate(rows):
        generated = [int(first[r].argmax())] + [int(t) for t in tokens[r]]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        said = [first[r].max()] + [float(v) for v in logprobs[r]]
        gap = max(abs(said[i] - want[i, generated[i]])
                  for i in range(len(generated)))
        # bfloat16 activations (2**-8 relative) through four layers against
        # float32: a few hundredths of the logits' spread; fp8 would be 0.1+
        assert gap < 0.08 * _spread(want), gap


def test_the_fingerprint_covers_every_module_the_programs_compile(
        params, monkeypatch):
    import inspect

    from sparkdl_tpu.ops import moe, ssm

    model = gh.GraniteHybridModel(CONFIG, params)
    before = model.fingerprint
    assert before.startswith("granite_hybrid:") and "hidden_size=32" in before
    assert gh.GraniteHybridModel(CONFIG, params).fingerprint == before
    assert gh.GraniteHybridModel(
        dict(CONFIG, residual_multiplier=1.0), params).fingerprint != before
    sound = inspect.getsource
    for module in (moe, ssm, hybrid, gh):
        gh._source_digest.cache_clear()
        monkeypatch.setattr(
            inspect, "getsource",
            lambda m, module=module: sound(m) + ("# edited" if m is module else ""))
        assert gh.GraniteHybridModel(CONFIG, params).fingerprint != before, module
    monkeypatch.setattr(inspect, "getsource", sound)
    gh._source_digest.cache_clear()
    assert model.fingerprint == before
    assert model.recurrent_bytes(4) == 4 * 3 * (8 * 8 * 16 * 4 + 3 * 96 * 4)
