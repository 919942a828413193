"""``models/solar_open2.py`` against the plain reference
(``chipbench/reference/solar_open2.py``) at a small size on the CPU, both
kinds of layer present in the published order (attention, KDA x3), seeded
weights.

float32 weights, and the program's products at ``highest`` precision here, so
that program and reference differ by rounding order only (and by the chunked
form's triangular solve): logits agree to 2e-5 of their spread.  The bfloat16
test holds the noise of the compute dtype instead, with its reason beside
it."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import solar_open2 as reference
from sparkdl_tpu.models import solar_open2 as so
from sparkdl_tpu.transformers.ar_generate import SegmentPlan

CONFIG = dict(
    model_type="solar_open2", vocab_size=96, hidden_size=32,
    num_hidden_layers=5, gqa_layers=[0, 4, 8], num_attention_heads=4,
    num_key_value_heads=2, head_dim=8,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8,
                            num_heads=4, num_kv_heads=None),
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
    moe_intermediate_size=16, norm_topk_prob=True, routed_scaling_factor=1,
    rms_norm_eps=1e-5, use_rope=False, use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True,
    first_k_dense_replace=0, kda_chunk_size=8,
)
LENGTHS = [5, 30, 16, 9]  # shorter than a segment of 8 ... longer than three


@pytest.fixture(scope="module")
def params():
    return reference.make_params(CONFIG, 41, "float32")


@pytest.fixture(scope="module")
def cfg():
    return so.SolarOpen2Config.from_dict(CONFIG)


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
    assert cfg.layer_types == ("attention", "kda", "kda", "kda", "attention")
    assert (cfg.inner, cfg.kda_heads, cfg.kda_head_dim, cfg.head_dim) == (
        32, 4, 8, 8)
    assert cfg.routed == 8 and cfg.held == (0, 8)
    assert reference.layer_types(CONFIG) == list(cfg.layer_types)
    share = so.SolarOpen2Config.from_dict(dict(
        CONFIG, n_routed_experts=2, experts_held=[4, 6],
        published={"n_routed_experts": 8}))
    assert share.routed == 8 and share.held == (4, 6)
    assert so.param_shapes(share)["ffn"]["w_gate"] == (5, 2, 32, 16)
    assert so.param_shapes(share)["ffn"]["router"] == (5, 32, 8)
    assert so.param_shapes(share)["ffn"]["router_bias"] == (5, 8)
    with pytest.raises(ValueError, match="experts_held"):
        so.SolarOpen2Config.from_dict(dict(CONFIG, experts_held=[0, 4]))
    for refused in (dict(kda_use_full_proj=True), dict(use_rope=True),
                    dict(first_k_dense_replace=1),
                    dict(routed_scaling_factor=2.5),
                    dict(linear_attn_config=dict(
                        CONFIG["linear_attn_config"], num_kv_heads=2))):
        with pytest.raises(NotImplementedError):
            so.SolarOpen2Config.from_dict(dict(CONFIG, **refused))


def test_params_have_the_references_shapes(params, cfg):
    shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape), params)
    assert shapes == so.param_shapes(cfg) == reference.shapes(CONFIG)
    own = so.init_params(cfg, seed=3, dtype=jnp.bfloat16)
    assert jax.tree_util.tree_map(lambda a: tuple(a.shape), own) == shapes
    assert own["kda"]["wq"].dtype == jnp.bfloat16
    # what feeds an exponential, and the bias added to float32 scores
    for made in (own, reference.make_params(CONFIG, 5, "bfloat16")):
        assert {made["kda"]["a_log"].dtype, made["kda"]["dt_bias"].dtype,
                made["ffn"]["router_bias"].dtype} == {jnp.dtype("float32")}
        assert not np.asarray(made["kda"]["g_bias"], np.float32).any()
        assert 0 < np.abs(made["ffn"]["router_bias"]).max() < 0.06
    steps = jax.nn.softplus(own["kda"]["dt_bias"])
    assert 0.99e-3 <= float(steps.min()) and float(steps.max()) <= 0.101
    assert 0 <= float(own["kda"]["a_log"].min())
    assert float(own["kda"]["a_log"].max()) <= np.log(16)


def test_forward_logits_is_the_references_forward(params, cfg, rows):
    tokens, lengths = _padded(rows, 32)
    got = np.asarray(so.forward_logits(params, cfg, tokens, lengths))
    for i, row in enumerate(rows):
        want = np.asarray(reference.forward(params, CONFIG, row))
        assert np.abs(got[i, :len(row)] - want).max() < 2e-5 * _spread(want)


def test_each_part_of_the_mathematics_has_a_say(params, cfg, rows):
    """Leaving a part out moves the logits by far more than the tolerance
    of the comparisons above: the attention gate, the doubled write strength,
    the selection bias (scaled up, so that it changes who is chosen), the
    decay."""
    tokens, lengths = _padded(rows[:2], 32)
    sound = np.asarray(so.forward_logits(params, cfg, tokens, lengths))

    def moved(cfg=cfg, **replaced):
        other = jax.tree_util.tree_map(lambda a: a, params)
        for path, value in replaced.items():
            group, name = path.split("__")
            other[group] = dict(other[group], **{name: value})
        got = np.asarray(so.forward_logits(other, cfg, tokens, lengths))
        return np.abs(got - sound)[0, :5].max() / _spread(sound[0, :5])

    assert moved(dataclasses.replace(cfg, use_gqa_gate=False)) > 1e-2
    assert moved(dataclasses.replace(cfg, kda_allow_neg_eigval=False)) > 1e-3
    assert moved(ffn__router_bias=40 * params["ffn"]["router_bias"]) > 1e-3
    assert moved(kda__a_log=params["kda"]["a_log"] - 9.0) > 1e-4


def _prefill_in_segments(params, cfg, rows, state, segment, count):
    """The rows' prompts through ``prefill`` segment by segment, ``count``
    pairs a dispatch as the stage lays them out.  Returns (state, the
    log-probabilities after each row's last token)."""
    plan = SegmentPlan(rows, len(rows), segment, count, gen=8)
    first = {}
    for arrays, last in plan.dispatches:
        state, logp, _ = so.prefill(
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
        so.empty_state(cfg, len(rows), 48, jnp.float32))
    state, first = _prefill_in_segments(params, cfg, rows, state, 8, 3)
    np.testing.assert_array_equal(state["position"], LENGTHS)
    logps = [np.stack([first[r] for r in range(len(rows))])]
    tokens = [np.asarray(state["token"])]
    for _ in range(5):
        state, logp, counts = so.decode_step(params, cfg, state)
        logps.append(np.asarray(logp))
        tokens.append(np.asarray(state["token"]))
        assert counts.shape == (5, 8) and int(counts.sum()) == 5 * 4 * 2
    np.testing.assert_array_equal(state["position"], np.array(LENGTHS) + 5)
    for r, row in enumerate(rows):
        generated = [int(t[r]) for t in tokens]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        got = np.stack([step[r] for step in logps])
        # float32 both sides, rounding order only: a log-probability near
        # -4.57 has an ulp of 4.8e-7 (1e-4 of the spread at this tiny
        # width), so a few of those through five layers and the solve
        assert np.abs(got - want).max() < 4e-6, r
        assert [int(g.argmax()) for g in got] == generated


def test_several_steps_a_dispatch_are_the_single_steps(params, cfg, rows):
    state = so.empty_state(cfg, len(rows), 48, jnp.float32)
    state, _ = _prefill_in_segments(params, cfg, rows, state, 16, 4)
    one = state
    singles = []
    for _ in range(3):
        one, logp, _ = so.decode_step(params, cfg, one)
        singles.append((np.asarray(one["token"]), np.asarray(logp.max(-1))))
    many, tokens, logprobs, counts = so.decode(params, cfg, state, 3)
    np.testing.assert_array_equal(tokens, np.stack([s[0] for s in singles], 1))
    np.testing.assert_allclose(
        logprobs, np.stack([s[1] for s in singles], 1), atol=1e-6)
    assert int(counts.sum()) == 3 * 5 * 4 * 2
    for name in one:
        np.testing.assert_allclose(many[name], one[name], atol=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer(params, cfg):
    """``model-configs`` section 4: the parts that the eight shares of one
    expert each give, with what every chip computes alike (the router, the
    shared expert) counted once, add up to the uncut reference's layer;
    float32, so to rounding."""
    from sparkdl_tpu.models import hybrid

    rng = np.random.default_rng(2)
    # small, so that a part read back off the residual stream (y - x) keeps
    # its digits; the norm in front makes the layer's output the same size
    x = jnp.asarray(0.01 * rng.normal(size=(7, 32)), jnp.float32)
    layer = 2  # a KDA layer's feed-forward; every layer's is alike
    fp = {k: params["ffn"][k][layer] for k in reference.FFN_KEYS}
    u = reference.rms_norm(x, fp["post_norm"], 1e-5)
    whole = np.asarray(reference.feed_forward(CONFIG, fp, u))
    alike = np.asarray(reference.shared_expert(fp, u))
    ffn, experts = hybrid.split_ffn(params)
    parts = []
    for lo in range(8):
        share = dataclasses.replace(
            cfg, n_routed_experts=1, routed_experts=8,
            experts_held=(lo, lo + 1))
        held = {k: v[:, lo:lo + 1] for k, v in experts.items()}
        y, counts = so._feed_forward(
            share, hybrid.at(ffn, layer), held, jnp.int32(layer), x)
        assert int(counts.sum()) == 7 * 2  # routed over all 8 experts
        parts.append(np.asarray(y - x) - alike)  # the routed part alone
    # an expert left out or counted twice is off by 1e-1 of the largest value
    np.testing.assert_allclose(sum(parts) + alike, whole,
                               atol=1e-4 * np.abs(whole).max())
    assert sum(np.abs(p).max() > 0 for p in parts) >= 6


def test_a_sliced_vocabularys_logits_are_the_slice_of_the_wholes(
        params, cfg, rows):
    """Embedding and head are untied and both sliced: with the ids drawn
    from the slice, the sliced model's logits are the first columns of the
    whole model's."""
    low = [row % 48 for row in rows[:2]]
    tokens, lengths = _padded(low, 32)
    whole = np.asarray(so.forward_logits(params, cfg, tokens, lengths))
    sliced = so.forward_logits(
        dict(params, embed=params["embed"][:48], head=params["head"][:48]),
        dataclasses.replace(cfg, vocab_size=48), tokens, lengths)
    np.testing.assert_allclose(sliced, whole[..., :48], atol=1e-6)


def test_bfloat16_keeps_the_state_float32_and_stays_near_the_reference(cfg):
    params = reference.make_params(CONFIG, 43, "bfloat16")
    rng = np.random.default_rng(1)
    rows = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 6)]
    state = so.empty_state(cfg, 2, 32, jnp.bfloat16)
    assert state["kda"].dtype == jnp.float32
    assert state["conv"].dtype == state["k"].dtype == jnp.bfloat16
    assert state["conv"].shape == (3, 2, 3, 3, 32)
    state, first = _prefill_in_segments(params, cfg, rows, state, 8, 2)
    state, tokens, logprobs, _ = so.decode(params, cfg, state, 4)
    assert state["kda"].dtype == jnp.float32 and logprobs.dtype == jnp.float32
    for r, row in enumerate(rows):
        generated = [int(first[r].argmax())] + [int(t) for t in tokens[r]]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        said = [first[r].max()] + [float(v) for v in logprobs[r]]
        gap = max(abs(said[i] - want[i, generated[i]])
                  for i in range(len(generated)))
        # bfloat16 activations (2**-8 relative) through five layers against
        # float32: a few hundredths of the logits' spread; fp8 would be 0.1+
        assert gap < 0.08 * _spread(want), gap


def test_the_fingerprint_covers_every_module_the_programs_compile(
        params, monkeypatch):
    import inspect

    from sparkdl_tpu.models import granite_hybrid, hybrid
    from sparkdl_tpu.ops import delta_rule, moe, ssm

    model = so.SolarOpen2Model(CONFIG, params)
    assert model.name == "solar"
    before = model.fingerprint
    assert before.startswith("solar_open2:") and "hidden_size=32" in before
    assert so.SolarOpen2Model(CONFIG, params).fingerprint == before
    assert so.SolarOpen2Model(
        dict(CONFIG, use_gqa_gate=False), params).fingerprint != before
    sound = inspect.getsource
    for module in (moe, delta_rule, ssm, hybrid, so):
        so._source_digest.cache_clear()
        monkeypatch.setattr(
            inspect, "getsource",
            lambda m, module=module: sound(m) + ("# edited" if m is module else ""))
        assert so.SolarOpen2Model(CONFIG, params).fingerprint != before, module
    # granite's digest gained the shared helpers' module
    granite_before = granite_hybrid._source_digest()
    granite_hybrid._source_digest.cache_clear()
    monkeypatch.setattr(
        inspect, "getsource",
        lambda m: sound(m) + ("# edited" if m is hybrid else ""))
    assert granite_hybrid._source_digest() != granite_before
    monkeypatch.setattr(inspect, "getsource", sound)
    so._source_digest.cache_clear()
    granite_hybrid._source_digest.cache_clear()
    assert model.fingerprint == before
    assert granite_hybrid._source_digest() == granite_before
    # 3 KDA layers x 4 rows of a float32 [4, 8, 8] state and three float32
    # [3, 32] conv windows (the weights' dtype)
    assert model.recurrent_bytes(4) == 4 * 3 * (4 * 8 * 8 * 4 + 3 * 3 * 32 * 4)
    assert model.experts_held == (0, 8) and model.experts_per_token == 2
