"""``models/moonlight.py`` and ``ops/mla.py`` against the plain reference
(``chipbench/reference/moonlight.py``: the published, decompressed form) at a
small size on the CPU, with a leading dense layer and two expert layers,
seeded weights; and the same model through ``AutoregressiveTransformer``.

float32 weights, and the program's products at ``highest`` precision here, so
that program and reference differ by rounding order only (and, in the
absorbed form, by the order of the latent products): logits agree to 2e-5 of
their spread.  The bfloat16 test holds the noise of the compute dtype
instead, with its reason beside it.  Last, the programs of the two other
models of the stage, whose ``models/hybrid.py`` path this model shares, are
pinned: their jaxprs are the ones they were before it came."""

import dataclasses
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import moonlight as reference
from sparkdl_tpu.models import hybrid
from sparkdl_tpu.models import moonlight as ml
from sparkdl_tpu.ops import mla
from sparkdl_tpu.transformers import ar_generate
from sparkdl_tpu.transformers.ar_generate import SegmentPlan

CONFIG = dict(
    model_type="deepseek_v3", vocab_size=96, hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    kv_lora_rank=32, q_lora_rank=None, intermediate_size=96,
    moe_intermediate_size=24, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1,
    routed_scaling_factor=2.446, norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_theta=50000, n_group=1, topk_group=1, scoring_func="sigmoid",
    topk_method="noaux_tc", num_nextn_predict_layers=0, moe_layer_freq=1,
    hidden_act="silu", tie_word_embeddings=False, rope_scaling=None,
)
LENGTHS = [5, 30, 16, 9]  # shorter than a segment of 8 ... longer than three


@pytest.fixture(scope="module")
def params():
    return reference.make_params(CONFIG, 41, "float32")


@pytest.fixture(scope="module")
def cfg():
    return ml.MoonlightConfig.from_dict(CONFIG)


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


def test_the_config_reads_the_published_keys(cfg):
    assert cfg.layer_types == ("mla",) * 3 and cfg.dense == 1
    assert cfg.held == (0, 8) and cfg.routed_scaling_factor == 2.446
    assert cfg.mla == mla.Shape(
        heads=4, nope=16, rope=16, v=16, rank=32, theta=50000.0,
        kv_eps=1e-6, scale=32 ** -0.5)
    assert cfg.mla.latent == 48
    assert reference.layer_types(CONFIG) == ["dense", "moe", "moe"]


@pytest.mark.parametrize("refused", [
    dict(q_lora_rank=384), dict(n_group=8), dict(topk_group=4),
    dict(rope_scaling={"type": "yarn", "factor": 40}),
    dict(num_nextn_predict_layers=1), dict(scoring_func="softmax"),
    dict(topk_method="greedy"), dict(moe_layer_freq=2),
    dict(tie_word_embeddings=True),
])
def test_the_config_refuses_what_it_does_not_implement(refused):
    key = next(iter(refused))
    with pytest.raises(NotImplementedError, match=key):
        ml.MoonlightConfig.from_dict(dict(CONFIG, **refused))


def test_params_have_the_references_shapes(params, cfg):
    shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape), params)
    assert shapes == ml.param_shapes(cfg) == reference.shapes(CONFIG)
    assert shapes["dense"]["gate"] == (1, 64, 96)
    assert shapes["ffn"]["w_gate"] == (2, 8, 64, 24)
    assert shapes["mla"]["wkva"] == (3, 64, 48)
    own = ml.init_params(cfg, seed=3, dtype=jnp.bfloat16)
    assert jax.tree_util.tree_map(lambda a: tuple(a.shape), own) == shapes
    assert own["mla"]["wq"].dtype == jnp.bfloat16
    for made in (own, reference.make_params(CONFIG, 5, "bfloat16")):
        assert made["ffn"]["router_bias"].dtype == jnp.float32
        assert 0 < np.abs(made["ffn"]["router_bias"]).max() < 0.06
    spec = ml.state_spec(cfg, 5, 40, jnp.bfloat16)
    assert spec["latent"].shape == (3, 5, 40, 48)
    assert spec["latent"].dtype == jnp.bfloat16


def test_rope_is_the_published_interleaved_pairing():
    """The program's rotation (float32 angles of ``position``) is the
    published one, which the reference writes as the HF code does; the
    rotate-half pairing is another result."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(11, 3, 16)), jnp.float32)
    want = reference.rope(x, 50000.0)
    got = mla.rope(x, jnp.arange(11)[:, None], 50000.0)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # pairs (2i, 2i + 1) turn together: the norm of each pair is kept
    pairs = np.asarray(x).reshape(11, 3, 8, 2)
    turned = np.stack([np.asarray(got)[..., :8], np.asarray(got)[..., 8:]], -1)
    np.testing.assert_allclose(np.linalg.norm(turned, axis=-1),
                               np.linalg.norm(pairs, axis=-1), rtol=1e-5)
    # the rotate-half pairing turns channels i and i + 8 together instead
    angle = np.arange(11)[:, None, None] * 5e4 ** (-np.arange(0, 16, 2) / 16)
    a, b = np.asarray(x)[..., :8], np.asarray(x)[..., 8:]
    half = np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                           b * np.cos(angle) + a * np.sin(angle)], -1)
    assert np.abs(half - np.asarray(want)).max() > 0.1


def test_forward_logits_is_the_references_forward(params, cfg, rows):
    tokens, lengths = _padded(rows, 32)
    got = np.asarray(ml.forward_logits(params, cfg, tokens, lengths))
    for i, row in enumerate(rows):
        want = np.asarray(reference.forward(params, CONFIG, row))
        assert np.abs(got[i, :len(row)] - want).max() < 2e-5 * _spread(want)


def test_each_part_of_the_mathematics_has_a_say(params, cfg, rows):
    """Leaving a part out moves the logits by far more than the tolerance
    above: the routed scale, the softmax's scale, the latent norm's gain,
    the selection bias (scaled up, so that it changes who is chosen)."""
    tokens, lengths = _padded(rows[:2], 32)
    sound = np.asarray(ml.forward_logits(params, cfg, tokens, lengths))

    def moved(cfg=cfg, **replaced):
        other = jax.tree_util.tree_map(lambda a: a, params)
        for path, value in replaced.items():
            group, name = path.split("__")
            other[group] = dict(other[group], **{name: value})
        got = np.asarray(ml.forward_logits(other, cfg, tokens, lengths))
        return np.abs(got - sound)[0, :5].max() / _spread(sound[0, :5])

    assert moved(dataclasses.replace(cfg, routed_scaling_factor=1.0)) > 1e-2
    assert moved(ffn__router_bias=40 * params["ffn"]["router_bias"]) > 1e-3
    assert moved(mla__kv_norm=2 * params["mla"]["kv_norm"]) > 1e-3
    assert moved(dataclasses.replace(cfg, rope_theta=10.0)) > 1e-3


def _prefill_in_segments(params, cfg, rows, state, segment, count):
    """The rows' prompts through ``prefill`` segment by segment, ``count``
    pairs a dispatch as the stage lays them out.  Returns (state, the
    log-probabilities after each row's last token)."""
    plan = SegmentPlan(rows, len(rows), segment, count, gen=8)
    first = {}
    for arrays, last in plan.dispatches:
        state, logp, counts = ml.prefill(
            params, cfg, state, *map(jnp.asarray, arrays))
        # layer 0 is dense: its row of the routing counts is zeros
        assert counts.shape == (3, 8) and not np.asarray(counts[0]).any()
        for slot, r in last:
            first[r] = np.asarray(logp[slot])
    return state, first


def test_prefill_in_segments_then_decode_is_the_full_forward(
        params, cfg, rows):
    """Rows of 5, 30, 16 and 9 tokens in one state, segments of 8, three
    pairs a dispatch, from a state full of another batch's leavings, through
    the latent cache: the log-probabilities at every one of 6 generated
    positions are the reference's full forward over prompt + generated
    tokens."""
    state = jax.tree_util.tree_map(
        lambda a: a + 3 if a.dtype != jnp.int32 else a + 5,
        ml.empty_state(cfg, len(rows), 48, jnp.float32))
    state, first = _prefill_in_segments(params, cfg, rows, state, 8, 3)
    np.testing.assert_array_equal(state["position"], LENGTHS)
    logps = [np.stack([first[r] for r in range(len(rows))])]
    tokens = [np.asarray(state["token"])]
    for _ in range(5):
        state, logp, counts = ml.decode_step(params, cfg, state)
        logps.append(np.asarray(logp))
        tokens.append(np.asarray(state["token"]))
        assert counts.shape == (3, 8) and int(counts.sum()) == 2 * 4 * 2
    np.testing.assert_array_equal(state["position"], np.array(LENGTHS) + 5)
    for r, row in enumerate(rows):
        generated = [int(t[r]) for t in tokens]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        got = np.stack([step[r] for step in logps])
        # float32 both sides, rounding order only: a log-probability near
        # -4.57 has an ulp of 4.8e-7, so a few of those through three layers
        assert np.abs(got - want).max() < 4e-6, r
        assert [int(g.argmax()) for g in got] == generated


def test_a_pad_writes_no_latent(params, cfg, rows):
    """Ragged rows: the slots at and past a row's length keep what the
    state held before (another batch's leavings), in every layer; the slots
    before it are written."""
    fill = 7.0
    state = ml.empty_state(cfg, len(rows), 48, jnp.float32)
    state = dict(state, latent=state["latent"] + fill)
    state, _ = _prefill_in_segments(params, cfg, rows, state, 8, 3)
    latent = np.asarray(state["latent"])
    for r, n in enumerate(LENGTHS):
        assert (latent[:, r, n:] == fill).all(), r
        assert not (latent[:, r, :n] == fill).any(), r


def test_several_steps_a_dispatch_are_the_single_steps(params, cfg, rows):
    state = ml.empty_state(cfg, len(rows), 48, jnp.float32)
    state, _ = _prefill_in_segments(params, cfg, rows, state, 16, 4)
    one = state
    singles = []
    for _ in range(3):
        one, logp, _ = ml.decode_step(params, cfg, one)
        singles.append((np.asarray(one["token"]), np.asarray(logp.max(-1))))
    many, tokens, logprobs, counts = ml.decode(params, cfg, state, 3)
    np.testing.assert_array_equal(tokens, np.stack([s[0] for s in singles], 1))
    np.testing.assert_allclose(
        logprobs, np.stack([s[1] for s in singles], 1), atol=1e-6)
    assert counts.shape == (3, 3, 8) and int(counts.sum()) == 3 * 2 * 4 * 2
    for name in one:
        np.testing.assert_allclose(many[name], one[name], atol=1e-6)


def test_the_absorbed_token_is_the_decompressed_form_on_one_cache(params,
                                                                  cfg):
    """``mla_token`` (latent space: the key half of ``W_kvb`` folded into the
    query, ``W_UV`` after the softmax) against the published form written
    here on the SAME cache: every visible slot's ``c`` expanded into the
    heads' keys and values."""
    shape = cfg.mla
    rng = np.random.default_rng(8)
    r, span, layer = 3, 20, 2
    lp = {k: params["mla"][k][layer] for k in reference.MLA_KEYS}
    latent = jnp.asarray(rng.normal(size=(3, r, span, 48)), jnp.float32)
    u = jnp.asarray(rng.normal(size=(r, 64)), jnp.float32)
    position = jnp.array([0, 7, 19], jnp.int32)
    got, written = mla.mla_token(lp, u, latent, jnp.int32(layer), position,
                                 shape)
    _, _, new = mla.project(lp, u, position, shape)
    np.testing.assert_array_equal(
        written[layer, np.arange(r), position], new)
    # nothing else moved
    mask = np.ones(latent.shape, bool)
    mask[layer, np.arange(r), np.asarray(position)] = False
    np.testing.assert_array_equal(np.asarray(written)[mask],
                                  np.asarray(latent)[mask])
    q_nope, q_pe, _ = mla.project(lp, u, position, shape)
    cache = written[layer]  # [r, span, 48]
    kv = jnp.einsum("rsc,cx->rsx", cache[..., :32], lp["wkvb"]).reshape(
        r, span, 4, 32)
    scores = (jnp.einsum("rhd,rshd->rhs", q_nope, kv[..., :16])
              + jnp.einsum("rhd,rsd->rhs", q_pe, cache[..., 32:])) * shape.scale
    visible = jnp.arange(span)[None, :] <= position[:, None]
    probs = jax.nn.softmax(jnp.where(visible[:, None], scores, -jnp.inf), -1)
    want = jnp.einsum("rhs,rshd->rhd", probs, kv[..., 16:]).reshape(r, -1)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(
        jnp.abs(want).max()))


def test_the_dense_layer_and_the_routed_scale_on_their_own(params, cfg):
    """Layer 0's feed-forward is the dense SwiGLU alone; an expert layer's
    ``routed_scale`` multiplies the routed part and leaves the shared one."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(0.01 * rng.normal(size=(7, 64)), jnp.float32)
    dp = {k: params["dense"][k][0] for k in reference.DENSE_KEYS}
    u = reference.rms_norm(x, dp["post_norm"], 1e-5)
    got = hybrid.dense_feed_forward(dp, x, eps=1e-5)
    want = x + reference.swiglu(u, dp["gate"], dp["up"], dp["down"])
    np.testing.assert_allclose(got, want, atol=1e-6)

    ffn, experts = hybrid.split_ffn(params)
    fp = hybrid.at(ffn, 1)
    layer = jnp.int32(1)
    parts = {}
    for scale in (1.0, 2.446):
        y, counts = ml._feed_forward(
            dataclasses.replace(cfg, routed_scaling_factor=scale), fp,
            experts, layer, x)
        parts[scale] = np.asarray(y - x)
        assert int(counts.sum()) == 7 * 2
    ref_fp = {k: params["ffn"][k][1] for k in reference.FFN_KEYS}
    u = reference.rms_norm(x, ref_fp["post_norm"], 1e-5)
    shared = np.asarray(reference.swiglu(
        u, ref_fp["shared_gate"], ref_fp["shared_up"], ref_fp["shared_down"]))
    routed = parts[1.0] - shared
    np.testing.assert_allclose(parts[2.446], shared + 2.446 * routed,
                               atol=1e-6)
    np.testing.assert_allclose(
        parts[2.446], np.asarray(reference.moe(CONFIG, ref_fp, u)),
        atol=1e-6)
    assert np.abs(routed).max() > 10 * 1e-6


def test_bfloat16_stays_near_the_reference(cfg):
    params = reference.make_params(CONFIG, 43, "bfloat16")
    rng = np.random.default_rng(1)
    rows = [rng.integers(0, 96, n).astype(np.int32) for n in (13, 6)]
    state = ml.empty_state(cfg, 2, 32, jnp.bfloat16)
    assert state["latent"].dtype == jnp.bfloat16
    state, first = _prefill_in_segments(params, cfg, rows, state, 8, 2)
    state, tokens, logprobs, _ = ml.decode(params, cfg, state, 4)
    assert logprobs.dtype == jnp.float32
    for r, row in enumerate(rows):
        generated = [int(first[r].argmax())] + [int(t) for t in tokens[r]]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        said = [first[r].max()] + [float(v) for v in logprobs[r]]
        gap = max(abs(said[i] - want[i, generated[i]])
                  for i in range(len(generated)))
        # bfloat16 activations and cache (2**-8 relative) through three
        # layers against float32: a few hundredths of the spread
        assert gap < 0.08 * _spread(want), gap


def test_the_fingerprint_covers_the_mla_module(
        params, monkeypatch):
    import inspect

    from sparkdl_tpu.ops import moe

    model = ml.MoonlightModel(CONFIG, params)
    assert model.name == "moonlight" and model.dense_layers == 1
    before = model.fingerprint
    assert before.startswith("deepseek_v3:")
    assert ml.MoonlightModel(
        dict(CONFIG, routed_scaling_factor=1.0), params).fingerprint != before
    sound = inspect.getsource
    for module in (moe, mla, hybrid, ml):
        ml._source_digest.cache_clear()
        monkeypatch.setattr(
            inspect, "getsource",
            lambda m, module=module: sound(m) + ("# edited" if m is module else ""))
        assert ml.MoonlightModel(CONFIG, params).fingerprint != before, module
    monkeypatch.setattr(inspect, "getsource", sound)
    ml._source_digest.cache_clear()
    assert model.fingerprint == before
    assert model.recurrent_bytes(4) == 0
    # 3 layers x 4 rows x 40 slots x 48 float32 channels
    assert model.latent_bytes(4, 40) == 3 * 4 * 40 * 48 * 4


# -- through the stage --------------------------------------------------------

@pytest.fixture
def small_dispatches(monkeypatch):
    monkeypatch.setattr(ar_generate, "SEGMENT_LENGTH", 8)
    monkeypatch.setattr(ar_generate, "SEGMENT_ROWS", 2)
    monkeypatch.setattr(ar_generate, "DECODE_STEPS", 2)


def test_the_model_generates_through_the_stage(
        tpu_session, params, rows, small_dispatches):
    """``AutoregressiveTransformer.transform()`` with no branch of its own:
    every generated position is the reference's; the spans say which form
    each program took and how many latent slots decode read of those it
    could see; the routing counters leave the dense layer out."""
    from sparkdl_tpu import AutoregressiveTransformer
    from sparkdl_tpu.obs.trace import tracer
    from sparkdl_tpu.utils.metrics import metrics

    gen = 6
    model = ml.MoonlightModel(CONFIG, params)
    counters = ("moe.tokens_routed", "moe.tokens_dropped",
                "mla.latent_cache_bytes", "ssm.state_bytes")
    before = {c: metrics.counter(c).value for c in counters}
    frame = tpu_session.createDataFrame(
        list(enumerate(rows)), ["rowId", "prompt"], numPartitions=1)
    out = AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=model, genLength=gen, batchSize=4).transform(frame).collect()
    assert [r["rowId"] for r in out] == [0, 1, 2, 3]
    for prompt, row in zip(rows, out):
        tokens = np.asarray(row["generated"])
        want = reference.teacher_forced(params, CONFIG, prompt, tokens)
        np.testing.assert_array_equal(want.argmax(axis=-1), tokens)
        np.testing.assert_allclose(np.asarray(row["record"])[:, 1],
                                   want.max(axis=-1), atol=4e-6)
    delta = {c: metrics.counter(c).value - before[c] for c in counters}
    # 2 + 4 + 2 + 2 segments of 8 in five dispatches of two pairs, then 5
    # decode steps of 4 rows, through the 2 expert layers at 2 a token
    assert delta["moe.tokens_routed"] == (5 * 16 + 5 * 4) * 2 * 2
    assert delta["moe.tokens_dropped"] == 0
    assert delta["ssm.state_bytes"] == 0
    plan = SegmentPlan(rows, 4, 8, 2, gen)
    assert plan.span == 512 + 128
    assert delta["mla.latent_cache_bytes"] == 3 * 4 * plan.span * 48 * 4
    spans = tracer.recent()
    (prefill,) = [r for r in spans if r.name == "ar_generate.prefill"][-1:]
    assert prefill.attributes["attention_path"] == "absorbed"
    # the plain fold: no slot through the prefill's kernel
    assert prefill.attributes["keys_fused"] == 0
    decodes = [r for r in spans if r.name == "ar_generate.decode"][-3:]
    assert [d.attributes["steps"] for d in decodes] == [2, 2, 1]
    assert all(d.attributes["attention_path"] == "absorbed" for d in decodes)
    assert sum(d.attributes["slots_read"] for d in decodes) == (
        4 * plan.span * (gen - 1))
    # write_positions writes the new slots: none inside the attention
    assert [d.attributes["slots_written_fused"] for d in decodes] == [0] * 3
    # at decode step t a row of L tokens sees L + t + 1 slots
    assert sum(d.attributes["slots_visible"] for d in decodes) == sum(
        n + t + 1 for n in LENGTHS for t in range(gen - 1))


def test_the_kernels_branch_through_the_stage_reads_whole_blocks(
        tpu_session, params, rows, small_dispatches, monkeypatch):
    """The decode on the kernel's branch, as on the chip: ``token_block``
    says blocks of 8 slots (the chip's are 512 at the published width) and
    the kernel runs in Pallas' TPU interpreter inside the layer scans.
    Every generated position is still the reference's, ``slots_read``
    counts whole blocks up to each row's position, where the plain branch
    above counts the whole span, and ``slots_written_fused`` every row's new
    slot in every layer, where the plain branch counts none."""
    from jax.experimental.pallas import tpu as pltpu

    from sparkdl_tpu import AutoregressiveTransformer
    from sparkdl_tpu.obs.trace import tracer

    # no executable of the plain branch from the engine (a fingerprint of its
    # own) or its store, and none of this one to the store
    monkeypatch.setattr(ml, "_source_digest", lambda: "the-kernels-branch")
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "off")
    monkeypatch.setattr(mla, "token_block", lambda width, span: 8)
    ran = []
    kernel = mla._token_attention

    def interpreted(*args):
        ran.append(args[-1])
        return kernel(*args, interpret=pltpu.InterpretParams())

    monkeypatch.setattr(mla, "_token_attention", interpreted)
    gen = 6
    frame = tpu_session.createDataFrame(
        list(enumerate(rows)), ["rowId", "prompt"], numPartitions=1)
    out = AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=ml.MoonlightModel(CONFIG, params), genLength=gen,
        batchSize=4).transform(frame).collect()
    # traced once a decode program (2 steps and 1) and layer scan
    assert ran and set(ran) == {8}
    for prompt, row in zip(rows, out):
        tokens = np.asarray(row["generated"])
        want = reference.teacher_forced(params, CONFIG, prompt, tokens)
        np.testing.assert_array_equal(want.argmax(axis=-1), tokens)
        np.testing.assert_allclose(np.asarray(row["record"])[:, 1],
                                   want.max(axis=-1), atol=4e-6)
    decodes = [r for r in tracer.recent() if r.name == "ar_generate.decode"]
    # at decode step t a row of L tokens takes in position L + t, in block
    # (L + t) // 8 of the walk
    assert sum(d.attributes["slots_read"] for d in decodes[-3:]) == sum(
        8 * ((n + t) // 8 + 1) for n in LENGTHS for t in range(gen - 1))
    assert sum(d.attributes["slots_visible"] for d in decodes[-3:]) == sum(
        n + t + 1 for n in LENGTHS for t in range(gen - 1))
    # the kernel writes each row's new slot itself: rows x steps x layers
    assert [d.attributes["slots_written_fused"] for d in decodes[-3:]] == [
        4 * steps * CONFIG["num_hidden_layers"] for steps in (2, 2, 1)]


def test_a_model_without_paths_leaves_the_spans_without_one(
        tpu_session, small_dispatches):
    """Granite's decode spans carry the slot counts too, and no path."""
    from chipbench.reference import granite_hybrid
    from sparkdl_tpu import AutoregressiveTransformer
    from sparkdl_tpu.models.granite_hybrid import GraniteHybridModel
    from sparkdl_tpu.obs.trace import tracer
    from test_granite_hybrid import CONFIG as GRANITE

    model = GraniteHybridModel(
        GRANITE, granite_hybrid.make_params(GRANITE, 3, "float32"))
    prompts = [np.arange(n, dtype=np.int32) % 90 for n in (3, 11)]
    frame = tpu_session.createDataFrame(
        list(enumerate(prompts)), ["rowId", "prompt"], numPartitions=1)
    AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", model=model, genLength=3,
        batchSize=2).transform(frame).collect()
    spans = tracer.recent()
    prefill = [r for r in spans if r.name == "ar_generate.prefill"][-1]
    decode = [r for r in spans if r.name == "ar_generate.decode"][-1]
    assert "attention_path" not in prefill.attributes
    assert "attention_path" not in decode.attributes
    # a span of 512 + 128; genLength 3: one dispatch of 2 steps
    assert decode.attributes["slots_read"] == 2 * (512 + 128) * 2
    assert decode.attributes["slots_visible"] == (3 + 1) + (11 + 1) + (
        3 + 2) + (11 + 2)


# -- the other models' programs are what they were ---------------------------

#: sha256 of the jaxprs of granite's and solar's programs at the small sizes
#: of their tests, traced on the tree before this model came (PR 40's)
PINNED = {
    "granite_prefill":
        "cfbfe1fb279f710acb2936785146c5a16eac72d7103e1a5ff7b04047de9265eb",
    "granite_decode":
        "63e935c7292ca7d426bb559bf03d653f8d72235cac1b21eac59b8c8a6ea8d5bb",
    "solar_prefill":
        "82274cb6991b1334f43cd2e1b7c6323872b58bd08d6f0c2367a1bfe831324067",
    "solar_decode":
        "96affb097e3a17bf92fe7b04108519c5027d905188cf2c2981186c3e716390b9",
}


@pytest.mark.parametrize("program", sorted(PINNED))
def test_granites_and_solars_programs_are_unchanged(program):
    from chipbench.reference import granite_hybrid as granite_reference
    from chipbench.reference import solar_open2 as solar_reference
    from sparkdl_tpu.models import granite_hybrid, solar_open2
    from test_granite_hybrid import CONFIG as GRANITE
    from test_solar_open2 import CONFIG as SOLAR

    name, kind = program.split("_")
    module, config, ref, cls = {
        "granite": (granite_hybrid, GRANITE, granite_reference,
                    granite_hybrid.GraniteHybridConfig),
        "solar": (solar_open2, SOLAR, solar_reference,
                  solar_open2.SolarOpen2Config),
    }[name]
    cfg = cls.from_dict(config)
    params = ref.make_params(config, 3, "bfloat16")
    state = module.empty_state(cfg, 4, 64, jnp.bfloat16)

    def ints(*shape):
        return jnp.zeros(shape, jnp.int32)

    with jax.default_matmul_precision(None):
        if kind == "prefill":
            text = str(jax.make_jaxpr(
                lambda p, s, *a: module.prefill(p, cfg, s, *a))(
                    params, state, ints(2, 8), ints(2), ints(2), ints(2)))
        else:
            text = str(jax.make_jaxpr(
                lambda p, s: module.decode(p, cfg, s, 3))(params, state))
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[program]
