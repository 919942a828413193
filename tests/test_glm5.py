"""``models/glm5.py`` and ``ops/dsa.py`` against the plain reference
(``chipbench/reference/glm5.py``: the published, decompressed MLA under the
indexer's top-k) at a small size on the CPU, with a leading dense layer and
two expert layers, seeded weights and ``index_topk`` 8 against rows of up to
30 tokens, so that selection keeps a share of the keys; and the same model
through ``AutoregressiveTransformer``, whose plan packs a row's segments.

float32 weights, and the program's products at ``highest`` precision here, so
that program and reference differ by rounding order only: logits agree to
2e-5 of their spread.  Last, Moonlight's programs, which share
``ops/mla.py``, and the other models' prefill plans are pinned: they are the
ones they were before this model came."""

import dataclasses
import functools
import hashlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import glm5 as reference
from sparkdl_tpu.models import glm5
from sparkdl_tpu.ops import dsa, mla
from sparkdl_tpu.transformers import ar_generate
from sparkdl_tpu.transformers.ar_generate import SegmentPlan

CONFIG = dict(
    model_type="glm_moe_dsa", vocab_size=96, hidden_size=64,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=24,
    kv_lora_rank=32, q_lora_rank=48, intermediate_size=96,
    moe_intermediate_size=24, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=2, first_k_dense_replace=1,
    routed_scaling_factor=2.5, norm_topk_prob=True, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
    n_group=1, topk_group=1, scoring_func="sigmoid", topk_method="noaux_tc",
    num_nextn_predict_layers=0, moe_layer_freq=1, hidden_act="silu",
    tie_word_embeddings=False, index_n_heads=4, index_head_dim=32,
    index_topk=8, rope_interleave=True, indexer_rope_interleave=True,
    init_std={"wqb": 1.0},
)
LENGTHS = [5, 30, 16, 9]  # shorter than a segment of 8 ... longer than three


@pytest.fixture(scope="module")
def params():
    return reference.make_params(CONFIG, 44, "float32")


@pytest.fixture(scope="module")
def cfg():
    return glm5.Glm5Config.from_dict(CONFIG)


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
    assert cfg.held == (0, 8) and cfg.routed_scaling_factor == 2.5
    assert cfg.rope_theta == 1000000 and cfg.q_lora_rank == 48
    assert cfg.mla == mla.Shape(
        heads=4, nope=16, rope=16, v=24, rank=32, theta=1e6, kv_eps=1e-6,
        scale=32 ** -0.5)
    assert cfg.indexer == dsa.Indexer(
        heads=4, dim=32, rope=16, theta=1e6, topk=8, eps=1e-6)
    assert reference.layer_types(CONFIG) == ["dense", "moe", "moe"]


@pytest.mark.parametrize("refused", [
    dict(q_lora_rank=None), dict(n_group=8), dict(topk_group=4),
    dict(rope_scaling={"type": "yarn", "factor": 40}),
    dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
    dict(num_nextn_predict_layers=1), dict(scoring_func="softmax"),
    dict(topk_method="greedy"), dict(tie_word_embeddings=True),
    dict(rope_interleave=False),
])
def test_the_config_refuses_what_it_does_not_implement(refused):
    key = next(iter(refused))
    if key == "rope_parameters":
        key = "rope_scaling"
    with pytest.raises(NotImplementedError, match=key):
        glm5.Glm5Config.from_dict(dict(CONFIG, **refused))


def test_params_have_the_references_shapes(params, cfg):
    shapes = jax.tree_util.tree_map(lambda a: tuple(a.shape), params)
    assert shapes == glm5.param_shapes(cfg) == reference.shapes(CONFIG)
    assert shapes["mla"]["wqb"] == (3, 48, 4 * 32)
    assert shapes["mla"]["wkvb"] == (3, 32, 4 * (16 + 24))
    assert shapes["mla"]["ix_wq"] == (3, 48, 4 * 32)
    own = glm5.init_params(cfg, seed=3, dtype=jnp.bfloat16)
    assert jax.tree_util.tree_map(lambda a: tuple(a.shape), own) == shapes
    assert not np.asarray(own["mla"]["ix_bias"]).any()
    assert not np.asarray(params["mla"]["ix_bias"]).any()
    spec = glm5.state_spec(cfg, 5, 40, jnp.bfloat16)
    assert spec["latent"].shape == (3, 5, 40, 48)
    assert spec["index_keys"].shape == (3, 5, 40, 32)


def test_forward_logits_is_the_references_forward(params, cfg, rows):
    tokens, lengths = _padded(rows, 32)
    got = np.asarray(glm5.forward_logits(params, cfg, tokens, lengths))
    for i, row in enumerate(rows):
        want = np.asarray(reference.forward(params, CONFIG, row))
        assert np.abs(got[i, :len(row)] - want).max() < 2e-5 * _spread(want)


def test_selection_has_a_say(params, cfg, rows):
    """With ``index_topk`` 8 a row of 30 positions attends over a share of
    its keys: keeping them all (dense MLA), or scoring without the ReLU,
    moves the logits past the eighth position by far more than the
    tolerance above, and leaves the first eight as they are."""
    tokens, lengths = _padded(rows[1:2], 32)
    sound = np.asarray(glm5.forward_logits(params, cfg, tokens, lengths))[0]
    dense = np.asarray(glm5.forward_logits(
        params, dataclasses.replace(cfg, index_topk=64), tokens, lengths))[0]
    spread = _spread(sound[:30])
    assert np.abs(dense[:8] - sound[:8]).max() < 2e-5 * spread
    assert np.abs(dense[8:30] - sound[8:30]).max() > 0.05 * spread


def _jitted(function):
    """``function(params, cfg, ...)`` compiled once a shape (the config is
    static)."""
    return jax.jit(function, static_argnums=(1,))


def _prefill(params, cfg, rows, state, segment, count, packed=True):
    """The rows' prompts through ``prefill`` as the stage lays them out.
    Returns (state, the log-probabilities after each row's last token)."""
    plan = SegmentPlan(rows, len(rows), segment, count, gen=8, packed=packed)
    first = {}
    for arrays, last in plan.dispatches:
        state, logp, counts = _jitted(glm5.prefill)(
            params, cfg, state, *map(jnp.asarray, arrays))
        assert counts.shape == (3, 8) and not np.asarray(counts[0]).any()
        for slot, r in last:
            first[r] = np.asarray(logp[slot])
    return state, first, plan


@pytest.mark.parametrize("packed", [True, False], ids=["packed", "one_a_row"])
def test_prefill_in_segments_then_decode_is_the_full_forward(
        params, cfg, rows, packed):
    """Rows of 5, 30, 16 and 9 tokens in one state, segments of 8, three
    pairs a dispatch (packed: the longest row's segments share dispatches),
    from a state full of another batch's leavings, through both caches: the
    log-probabilities at every one of 6 generated positions are the
    reference's full forward over prompt + generated tokens."""
    state = jax.tree_util.tree_map(
        lambda a: a + 3 if a.dtype != jnp.int32 else a + 5,
        glm5.empty_state(cfg, len(rows), 48, jnp.float32))
    state, first, plan = _prefill(params, cfg, rows, state, 8, 3, packed)
    assert plan.packed == (2 if packed else 1)
    np.testing.assert_array_equal(state["position"], LENGTHS)
    logps = [np.stack([first[r] for r in range(len(rows))])]
    tokens = [np.asarray(state["token"])]
    for _ in range(5):
        state, logp, counts = _jitted(glm5.decode_step)(params, cfg, state)
        logps.append(np.asarray(logp))
        tokens.append(np.asarray(state["token"]))
    for r, row in enumerate(rows):
        generated = [int(t[r]) for t in tokens]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        got = np.stack([step[r] for step in logps])
        assert np.abs(got - want).max() < 4e-6, r
        assert [int(g.argmax()) for g in got] == generated


def test_a_pad_writes_nothing_in_either_cache(params, cfg, rows):
    fill = 7.0
    state = glm5.empty_state(cfg, len(rows), 48, jnp.float32)
    state = dict(state, latent=state["latent"] + fill,
                 index_keys=state["index_keys"] + fill)
    state, _, _ = _prefill(params, cfg, rows, state, 8, 3)
    latent = np.asarray(state["latent"])
    keys = np.asarray(state["index_keys"])
    for r, n in enumerate(LENGTHS):
        assert (latent[:, r, n:] == fill).all() and (keys[:, r, n:]
                                                      == fill).all(), r
        assert not (latent[:, r, :n] == fill).any(), r
        assert not (keys[:, r, :n] == fill).any(), r


def test_a_rows_segments_of_128_in_one_dispatch_are_the_one_at_a_time(
        params, cfg):
    """``dsa_segment`` at the stage's segments of 128: a row's two
    consecutive segments (the second 100 real) in ONE call leave both
    caches as the two calls one after the other do, to the bit, and the
    second segment's queries see the first's keys alike."""
    rng = np.random.default_rng(8)
    lp = {k: params["mla"][k][1] for k in reference.MLA_KEYS}
    u = jnp.asarray(rng.normal(size=(2, 128, 64)), jnp.float32)
    state = glm5.empty_state(cfg, 2, 512, jnp.float32)
    latent, keys = state["latent"] + 5, state["index_keys"] + 5
    rows = jnp.array([1, 1], jnp.int32)
    start = jnp.array([0, 128], jnp.int32)
    lengths = jnp.array([128, 100], jnp.int32)
    step = jax.jit(functools.partial(dsa.dsa_segment, shape=cfg.mla,
                                     ix=cfg.indexer))
    out, both_latent, both_keys = step(lp, u, latent, keys, jnp.int32(1),
                                       rows, start, lengths)
    one_latent, one_keys, outs = latent, keys, []
    for i in range(2):
        o, one_latent, one_keys = step(
            lp, u[i:i + 1], one_latent, one_keys, jnp.int32(1), rows[i:i + 1],
            start[i:i + 1], lengths[i:i + 1])
        outs.append(o)
    np.testing.assert_array_equal(both_latent, one_latent)
    np.testing.assert_array_equal(both_keys, one_keys)
    assert (np.asarray(both_latent)[1, 1, 228:] == 5).all()
    np.testing.assert_allclose(out, np.concatenate(outs), rtol=1e-5,
                               atol=1e-5)


def test_several_steps_a_dispatch_are_the_single_steps(params, cfg, rows):
    state = glm5.empty_state(cfg, len(rows), 48, jnp.float32)
    state, _, _ = _prefill(params, cfg, rows, state, 16, 4)
    one, singles = state, []
    for _ in range(3):
        one, logp, _ = _jitted(glm5.decode_step)(params, cfg, one)
        singles.append((np.asarray(one["token"]), np.asarray(logp.max(-1))))
    many, tokens, logprobs, counts = jax.jit(
        glm5.decode, static_argnums=(1, 3))(params, cfg, state, 3)
    np.testing.assert_array_equal(tokens, np.stack([s[0] for s in singles], 1))
    np.testing.assert_allclose(
        logprobs, np.stack([s[1] for s in singles], 1), atol=1e-6)
    assert counts.shape == (3, 3, 8)


def test_bfloat16_stays_near_the_reference(cfg):
    params = reference.make_params(CONFIG, 45, "bfloat16")
    rng = np.random.default_rng(1)
    rows = [rng.integers(0, 96, n).astype(np.int32) for n in (21, 6)]
    state = glm5.empty_state(cfg, 2, 32, jnp.bfloat16)
    state, first, _ = _prefill(params, cfg, rows, state, 8, 2)
    state, tokens, logprobs, _ = jax.jit(
        glm5.decode, static_argnums=(1, 3))(params, cfg, state, 4)
    for r, row in enumerate(rows):
        generated = [int(first[r].argmax())] + [int(t) for t in tokens[r]]
        want = reference.teacher_forced(params, CONFIG, row, generated)
        said = [first[r].max()] + [float(v) for v in logprobs[r]]
        gap = max(abs(said[i] - want[i, generated[i]])
                  for i in range(len(generated)))
        # bfloat16 activations and caches through three layers against
        # float32 (0.13 of the spread at seed 45), and of 8 keys kept, one
        # chosen by a hair at one precision and not at the other
        assert gap < 0.25 * float(want.std(-1).mean()), gap


def test_the_expert_shares_add_up_to_the_whole_layer(params):
    """Four chips of two experts each: their routed parts, with the shared
    expert counted once, add up to the uncut layer's (model-configs
    section 4), in the reference and in the program's feed-forward."""
    from sparkdl_tpu.models import hybrid

    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(9, 64)), jnp.float32)
    fp = {k: params["ffn"][k][0] for k in reference.FFN_KEYS}
    u = reference.rms_norm(h, fp["post_norm"], CONFIG["rms_norm_eps"])
    whole = np.asarray(reference.moe(CONFIG, fp, u))
    shared = np.asarray(reference.swiglu(
        u, fp["shared_gate"], fp["shared_up"], fp["shared_down"]))
    parts, program = [], []
    for lo in range(0, 8, 2):
        share = dict(CONFIG, n_routed_experts=2, experts_held=[lo, lo + 2],
                     published={"n_routed_experts": 8})
        held = dict(fp, **{k: fp[k][lo:lo + 2]
                           for k in ("w_gate", "w_up", "w_down")})
        parts.append(np.asarray(reference.moe(share, held, u, shared=False)))
        ffn, experts = hybrid.split_ffn(
            {"ffn": {k: v[None] for k, v in held.items()}})
        y, _ = glm5._feed_forward(glm5.Glm5Config.from_dict(share),
                                  hybrid.at(ffn, 0), experts, jnp.int32(0), h)
        program.append(np.asarray(y - h) - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5)
    np.testing.assert_allclose(sum(program) + shared, whole, atol=1e-5)
    assert min(np.abs(p).max() for p in parts) > 1e-3  # every share adds


def test_the_model_object_counts_what_it_holds(params):
    model = glm5.Glm5Model(CONFIG, params)
    assert model.name == "glm5" and model.dense_layers == 1
    assert model.packs_segments
    assert model.fingerprint.startswith("glm_moe_dsa:")
    assert model.recurrent_bytes(4) == 0
    assert model.latent_bytes(4, 40) == 3 * 4 * 40 * 48 * 4
    assert model.index_bytes(4, 40) == 3 * 4 * 40 * 32 * 4
    # four pairs of 8 queries under a span of 1,024: the plain indexer
    # scores the whole span, the masked fold one block of 512 a pair
    starts = [0, 8, 0, 16]
    assert model.index_pairs(starts, 8, 1024) == (
        3 * 8 * 4 * 1024, 3 * 8 * 4 * 512)
    # the kernel's walk: blocks up to each pair's end
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(dsa, "select_block", lambda span: 8)
        assert model.index_pairs(starts, 8, 1024)[0] == 3 * 8 * (
            8 + 16 + 8 + 24)
    # the masked decode scores the whole span, as it reads it
    positions = np.array([[3, 20], [4, 21]])
    assert model.slots_selected(positions, 40) == model.slots_read(
        positions, 40) == 2 * 2 * 40


# -- through the stage --------------------------------------------------------

@pytest.fixture
def small_dispatches(monkeypatch):
    monkeypatch.setattr(ar_generate, "SEGMENT_LENGTH", 8)
    monkeypatch.setattr(ar_generate, "SEGMENT_ROWS", 3)
    monkeypatch.setattr(ar_generate, "DECODE_STEPS", 2)


def test_the_model_generates_through_the_stage(
        tpu_session, params, rows, small_dispatches):
    """``AutoregressiveTransformer.transform()`` with no branch of its own:
    every generated position is the reference's; the plan packs the longest
    row's segments; the spans carry what the indexer scored and selection
    kept, and the counters the two caches."""
    from sparkdl_tpu import AutoregressiveTransformer
    from sparkdl_tpu.obs.trace import tracer
    from sparkdl_tpu.utils.metrics import metrics

    gen = 6
    model = glm5.Glm5Model(CONFIG, params)
    counters = ("moe.tokens_routed", "moe.tokens_dropped",
                "mla.latent_cache_bytes", "dsa.index_cache_bytes")
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
    plan = SegmentPlan(rows, 4, 8, 3, gen, packed=True)
    # 1 + 4 + 2 + 2 segments: three full dispatches of three pairs
    assert len(plan.dispatches) == 3 and plan.pad_tokens == 3 * 24 - 60
    assert delta["moe.tokens_routed"] == (3 * 24 + 5 * 4) * 2 * 2
    assert delta["moe.tokens_dropped"] == 0
    assert delta["mla.latent_cache_bytes"] == 3 * 4 * plan.span * 48 * 4
    assert delta["dsa.index_cache_bytes"] == 3 * 4 * plan.span * 32 * 4
    spans = tracer.recent()
    (planned,) = [r for r in spans if r.name == "ar_generate.plan"][-1:]
    assert planned.attributes["packed"] == 2
    (prefill,) = [r for r in spans if r.name == "ar_generate.prefill"][-1:]
    assert prefill.attributes["attention_path"] == "masked"
    # the plain fold: no slot through the prefill's kernel
    assert prefill.attributes["keys_fused"] == 0
    assert (prefill.attributes["keys_indexed"],
            prefill.attributes["keys_selected"]) == model.index_pairs(
                plan.starts, 8, plan.span)
    decodes = [r for r in spans if r.name == "ar_generate.decode"][-3:]
    assert {d.attributes["attention_path"] for d in decodes} == {"masked"}
    assert sum(d.attributes["slots_selected"] for d in decodes) == sum(
        d.attributes["slots_read"] for d in decodes) == (
            4 * plan.span * (gen - 1))


def test_the_selection_kernel_through_the_stage(
        tpu_session, params, rows, small_dispatches, monkeypatch):
    """The prefill on the kernel's branch, as on the chip: ``select_block``
    says blocks of 8 slots and the kernel runs in Pallas' interpreter inside
    the layer scans; every generated position is still the reference's."""
    from sparkdl_tpu import AutoregressiveTransformer

    monkeypatch.setattr(glm5, "_source_digest", lambda: "the-kernels-branch")
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "off")
    monkeypatch.setattr(dsa, "select_block", lambda span: 8)
    ran = []
    kernel = dsa._select

    def interpreted(*args):
        ran.append(args[-1])
        return kernel(*args, interpret=True)

    monkeypatch.setattr(dsa, "_select", interpreted)
    frame = tpu_session.createDataFrame(
        list(enumerate(rows)), ["rowId", "prompt"], numPartitions=1)
    out = AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=glm5.Glm5Model(CONFIG, params), genLength=4,
        batchSize=4).transform(frame).collect()
    assert ran and set(ran) == {8}
    for prompt, row in zip(rows, out):
        tokens = np.asarray(row["generated"])
        want = reference.teacher_forced(params, CONFIG, prompt, tokens)
        np.testing.assert_array_equal(want.argmax(axis=-1), tokens)
        np.testing.assert_allclose(np.asarray(row["record"])[:, 1],
                                   want.max(axis=-1), atol=4e-6)


def test_the_attention_kernel_through_the_stage(
        tpu_session, params, rows, small_dispatches, monkeypatch):
    """The prefill's attention on the kernel's branch, as on the chip:
    ``segment_group`` says two heads a grid step (two groups) and the kernel
    runs in Pallas' TPU interpreter inside the layer scans, over packed
    segments of one row and the selection's mask.  Every generated position
    is still the reference's, and the prefill span's ``keys_fused`` is its
    ``keys_scored``: every slot the fold scores went through the kernel."""
    from jax.experimental.pallas import tpu as pltpu

    from sparkdl_tpu import AutoregressiveTransformer
    from sparkdl_tpu.obs.trace import tracer

    # a fingerprint of its own: no executable of another branch from the
    # engine's memory or store
    monkeypatch.setattr(glm5, "_source_digest",
                        lambda: "the-attention-kernels-branch")
    monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "off")
    monkeypatch.setattr(mla, "segment_group", lambda shape, n, span: 2)
    ran = []
    kernel = mla._segment_attention

    def interpreted(*args):
        ran.append((args[-2], args[-1] is not None))
        return kernel(*args, interpret=pltpu.InterpretParams())

    monkeypatch.setattr(mla, "_segment_attention", interpreted)
    frame = tpu_session.createDataFrame(
        list(enumerate(rows)), ["rowId", "prompt"], numPartitions=1)
    out = AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=glm5.Glm5Model(CONFIG, params), genLength=4,
        batchSize=4).transform(frame).collect()
    # traced once a layer scan, each with the selection's mask
    assert ran and set(ran) == {(2, True)}
    for prompt, row in zip(rows, out):
        tokens = np.asarray(row["generated"])
        want = reference.teacher_forced(params, CONFIG, prompt, tokens)
        np.testing.assert_array_equal(want.argmax(axis=-1), tokens)
        np.testing.assert_allclose(np.asarray(row["record"])[:, 1],
                                   want.max(axis=-1), atol=4e-6)
    (prefill,) = [r for r in tracer.recent()
                  if r.name == "ar_generate.prefill"][-1:]
    plan = SegmentPlan(rows, 4, 8, 3, 4, packed=True)
    assert prefill.attributes["keys_fused"] == (
        prefill.attributes["keys_scored"]) == plan.keys_scored > 0


def test_the_packed_plan_keeps_each_row_in_order():
    """Every segment after its predecessor, every dispatch full but the
    last, the same pairs as the plan that takes one segment a row a
    dispatch; at the cell's lengths (1,737 segments), 109 dispatches where
    one segment a row a dispatch takes 240."""
    from chipbench import prompt_traffic

    rng = np.random.default_rng(5)
    prompts = [np.zeros(n, np.int32) for n in rng.integers(1, 2000, 16)]
    plan = SegmentPlan(prompts, 16, 128, 16, 64, packed=True)
    apart = SegmentPlan(prompts, 16, 128, 16, 64)
    seen = {}
    for d, ((tokens, index, start, held), last) in enumerate(plan.dispatches):
        for slot in range(16):
            row = int(index[slot])
            if row == 16:
                assert d == len(plan.dispatches) - 1
                continue
            assert start[slot] == 128 * seen.get(row, (0, -1))[0]
            assert seen.get(row, (0, -1))[1] <= d
            seen[row] = (seen.get(row, (0, -1))[0] + 1, d)
    assert {r: s for r, (s, _) in seen.items()} == {
        r: -(-len(p) // 128) for r, p in enumerate(prompts)}
    assert plan.pad_tokens < apart.pad_tokens
    assert plan.span == apart.span
    lengths = prompt_traffic.lengths(dict(
        rows=16, min_length=4096, max_length=32768))
    cell = [np.zeros(n, np.int32) for n in lengths]
    segments = int((-(-lengths // 128)).sum())
    packed = SegmentPlan(cell, 16, 128, 16, 64, packed=True)
    assert len(packed.dispatches) == -(-segments // 16) == 109
    # each row's last segment partly pads, and the last dispatch's spare
    # pairs: 2,776 of 223,232 positions, where one a row pads 55%
    assert packed.pad_tokens / (109 * 16 * 128) < 0.0125
    apart = SegmentPlan(cell, 16, 128, 16, 64)
    assert len(apart.dispatches) == 240
    assert apart.pad_tokens / (240 * 16 * 128) > 0.5


# -- what the other models run is what it was ------------------------------

HERE = os.path.dirname(os.path.abspath(__file__))
#: sha256 of Moonlight's two jaxprs at the small size of its tests, and of the
#: prefill plans of the granite, solar and moonlight cells' traffic (seed 5),
#: as traced and laid out on the tree before this model came
PINNED = {
    "moonlight_prefill":
        "0e1cfc354fc7bc8e8c9938e0392cb94c7bb59c719ac617108673240066ba08b4",
    "moonlight_decode":
        "26e62ce82dec680c9342c637bc20210b51bf56da56507e3689b5665f7d58bcc0",
    "long_prompt_frame":
        "136feacf50ef3edc9087ba32ced1c79d7ffc4afd28ad3f8422925c8edfa926cf",
    "document_frame":
        "1621faf073b0a3522e62ec4301797914545fac8b2aa2e16e234f691b966fa134",
    "summary_frame":
        "4ab44525e3c5174172c22d73971eeefcc0911b2709361d06266db5d9ff8996e9",
}


@pytest.mark.parametrize("program", ["moonlight_prefill", "moonlight_decode"])
def test_moonlights_programs_are_unchanged(program):
    from chipbench.reference import moonlight as moonlight_reference
    from sparkdl_tpu.models import moonlight
    from test_moonlight import CONFIG as MOONLIGHT

    cfg = moonlight.MoonlightConfig.from_dict(MOONLIGHT)
    params = moonlight_reference.make_params(MOONLIGHT, 3, "bfloat16")
    state = moonlight.empty_state(cfg, 4, 64, jnp.bfloat16)

    def ints(*shape):
        return jnp.zeros(shape, jnp.int32)

    with jax.default_matmul_precision(None):
        if program == "moonlight_prefill":
            text = str(jax.make_jaxpr(
                lambda p, s, *a: moonlight.prefill(p, cfg, s, *a))(
                    params, state, ints(2, 8), ints(2), ints(2), ints(2)))
        else:
            text = str(jax.make_jaxpr(
                lambda p, s: moonlight.decode(p, cfg, s, 3))(params, state))
    assert hashlib.sha256(text.encode()).hexdigest() == PINNED[program]


@pytest.mark.parametrize("traffic, batch", [
    ("long_prompt_frame", 64), ("document_frame", 128),
    ("summary_frame", 128)])
def test_the_other_models_plans_are_unchanged(traffic, batch):
    """The plan a stage makes for a model with recurrent state, or without
    ``packs_segments``, takes one segment a row a dispatch as before."""
    from chipbench import prompt_traffic

    with open(os.path.join(os.path.dirname(HERE), "chipbench", "traffic",
                           traffic + ".json")) as fh:
        mix = json.load(fh)
    prompts = prompt_traffic.prompt_frame(mix, 5, 1000, 1000)
    digest = hashlib.sha256()
    for lo in range(0, len(prompts), batch):
        plan = SegmentPlan(prompts[lo:lo + batch], batch, 128, 16,
                           mix["genLength"])
        assert plan.packed == 1
        for arrays, last in plan.dispatches:
            for array in arrays:
                digest.update(np.ascontiguousarray(array).tobytes())
            digest.update(repr(last).encode())
        digest.update(repr((plan.span, plan.pad_tokens, plan.keys_scored,
                            plan.keys_spanned)).encode())
    assert digest.hexdigest() == PINNED[traffic]
