"""``AutoregressiveTransformer`` over a DataFrame of prompts, at a tiny size
on the CPU: every generated position teacher-forced against the plain
reference (``chipbench/reference/granite_hybrid.py``), the layout of a
batch's prefill, the state's reuse, the spans and the counters; and the
stage's second model (``SolarOpen2Model`` against
``chipbench/reference/solar_open2.py``), alone and after the first in one
process.

float32 weights, and the programs compiled at ``highest`` precision: program
and reference differ by rounding order only, so a log-probability (near
-4.57: an ulp of 4.8e-7) agrees to 2e-6.
"""

import numpy as np
import pytest

import jax

from chipbench.reference import granite_hybrid as reference
from sparkdl_tpu import AutoregressiveTransformer
from sparkdl_tpu.models.granite_hybrid import GraniteHybridModel
from sparkdl_tpu.obs.trace import tracer
from sparkdl_tpu.transformers import ar_generate
from sparkdl_tpu.transformers.ar_generate import SegmentPlan
from sparkdl_tpu.utils.metrics import metrics

CONFIG = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=4,
    layer_types=["mamba", "attention", "mamba", "mamba"],
    num_attention_heads=4, num_key_value_heads=2, num_local_experts=8,
    num_experts_per_tok=2, intermediate_size=16, shared_intermediate_size=24,
    mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16, mamba_d_conv=4,
    mamba_expand=2, mamba_n_groups=1, mamba_chunk_size=8,
    attention_multiplier=0.125, embedding_multiplier=12,
    residual_multiplier=0.22, logits_scaling=16, rms_norm_eps=1e-5,
)
GEN = 6
COUNTERS = (
    "ar_generate.prefill_tokens", "ar_generate.prefill_pad_tokens",
    "ar_generate.decode_steps", "ar_generate.decode_dispatches",
    "ar_generate.decode_expert_reads", "ar_generate.tokens_generated", "ssm.state_bytes", "moe.tokens_routed",
    "moe.pairs_held", "moe.tokens_dropped", "moe.expert_load_max",
    "moe.expert_load_mean")


@pytest.fixture(scope="module")
def params():
    return reference.make_params(CONFIG, 41, "float32")


@pytest.fixture(scope="module")
def model(params):
    return GraniteHybridModel(CONFIG, params)


@pytest.fixture(autouse=True)
def exact_products():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def small_dispatches(monkeypatch):
    """The stage's one prefill shape and its decode dispatch are constants
    (128 x 16, 8 steps); at this size 8 x 2 and 2 steps put several
    segments, several dispatches and a left-over decode shape into prompts
    of a few tokens."""
    monkeypatch.setattr(ar_generate, "SEGMENT_LENGTH", 8)
    monkeypatch.setattr(ar_generate, "SEGMENT_ROWS", 2)
    monkeypatch.setattr(ar_generate, "DECODE_STEPS", 2)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, n).astype(np.int32) for n in lengths]


def _stage(model, batch=4, gen=GEN):
    return AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=model, genLength=gen, batchSize=batch)


def _frame(session, prompts, partitions=1):
    return session.createDataFrame(
        list(enumerate(prompts)), ["rowId", "prompt"],
        numPartitions=partitions)


def _teacher_forced(params, prompt, row, gen=GEN):
    """Every generated token is the reference's likeliest, given the prompt
    and the tokens before it, with the reference's log-probability."""
    record = np.asarray(row["record"])
    assert record.shape == (gen, 2) and record.dtype == np.float64
    tokens = np.asarray(row["generated"])
    assert tokens.dtype == np.int32 and tokens.shape == (gen,)
    np.testing.assert_array_equal(record[:, 0], tokens)
    want = reference.teacher_forced(params, CONFIG, prompt, tokens)
    np.testing.assert_array_equal(want.argmax(axis=-1), tokens)
    np.testing.assert_allclose(record[:, 1], want.max(axis=-1), atol=2e-6)


def test_generation_is_the_references_at_every_position(
        tpu_session, params, model):
    """Rows of different lengths in one batch: one shorter than a segment of
    8, one longer than three, one a whole number of segments."""
    prompts = _prompts([5, 30, 16, 9], seed=1)
    rows = _stage(model).transform(_frame(tpu_session, prompts)).collect()
    assert [r["rowId"] for r in rows] == [0, 1, 2, 3]
    for prompt, row in zip(prompts, rows):
        np.testing.assert_array_equal(row["prompt"], prompt)
        _teacher_forced(params, prompt, row)


def test_a_short_last_batch_and_a_second_batch_untouched_by_the_first(
        tpu_session, params, model):
    lengths = [5, 18, 3, 12, 9, 16, 2]  # batches of 4 and 3 (+1 dummy row)
    prompts = _prompts(lengths, seed=7)
    rows = _stage(model).transform(
        _frame(tpu_session, prompts, partitions=1)).collect()
    assert [r["rowId"] for r in rows] == list(range(len(prompts)))
    for prompt, row in zip(prompts, rows):
        _teacher_forced(params, prompt, row)
    # the second batch took the first's state from the pool: a row's result
    # does not depend on the batch it sat in, nor on what sat there before
    (runner,) = vars(model)["_ar_generate_runners"].values()
    assert len(runner.states) == 1
    alone = _stage(model, batch=1).transform(
        _frame(tpu_session, prompts[5:6])).collect()[0]
    np.testing.assert_array_equal(alone["generated"], rows[5]["generated"])
    np.testing.assert_allclose(alone["record"], rows[5]["record"], atol=2e-6)


def test_two_partitions_and_no_record_column(tpu_session, params, model):
    prompts = _prompts([4, 11, 7], seed=3)
    plain = AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", model=model, genLength=3,
        batchSize=2)
    rows = plain.transform(_frame(tpu_session, prompts, partitions=2)).collect()
    assert sorted(r["rowId"] for r in rows) == [0, 1, 2]
    for row in rows:
        assert "record" not in row.asDict() and len(row["generated"]) == 3
        want = reference.teacher_forced(
            params, CONFIG, prompts[row["rowId"]], row["generated"])
        np.testing.assert_array_equal(want.argmax(-1), row["generated"])


def test_one_token_a_row_needs_no_decode(tpu_session, params, model):
    prompts = _prompts([9, 3], seed=4)
    before = metrics.counter("ar_generate.decode_dispatches").value
    rows = _stage(model, batch=2, gen=1).transform(
        _frame(tpu_session, prompts)).collect()
    for prompt, row in zip(prompts, rows):
        _teacher_forced(params, prompt, row, gen=1)
    assert metrics.counter("ar_generate.decode_dispatches").value == before


def test_the_plan_of_a_batch():
    prompts = _prompts([5, 30, 16, 9])
    plan = SegmentPlan(prompts, rows=6, segment=8, count=3, gen=GEN)
    assert plan.real_tokens == 60
    # rows 4 and 5 pad the batch: one token each
    pairs = [[(int(r), int(s), int(n)) for r, s, n in zip(*arrays[1:])]
             for arrays, _ in plan.dispatches]
    assert pairs == [
        # the rows with most still to go first: 1 (4 segments), 2 (2), 3 (2)
        [(1, 0, 8), (2, 0, 8), (3, 0, 8)],
        [(1, 8, 8), (0, 0, 5), (2, 8, 8)],
        [(1, 16, 8), (3, 8, 1), (4, 0, 1)],
        # a row's segments follow each other, never two in one dispatch:
        # the spare pair names row 6, which nobody is
        [(1, 24, 6), (5, 0, 1), (6, 0, 0)],
    ]
    assert [last for _, last in plan.dispatches] == [
        [], [(1, 0), (2, 2)], [(1, 3), (2, 4)], [(0, 1), (1, 5)]]
    assert plan.pad_tokens == 4 * 3 * 8 - 60
    # attention_segment writes a dispatch's segments into the cache in
    # place, row by row: no row twice in a dispatch
    for group in pairs:
        named = [row for row, _, _ in group if row < 6]
        assert len(set(named)) == len(named)
    tokens = plan.dispatches[2][0][0]
    np.testing.assert_array_equal(tokens[0], prompts[1][16:24])
    np.testing.assert_array_equal(tokens[1], [prompts[3][8]] + [0] * 7)
    # as many dispatches as the pairs fill (13 + 7 x 2, two a dispatch) or
    # as the longest row has segments (13), whichever is more
    long = SegmentPlan(_prompts([100] + [9] * 7), 8, 8, 2, GEN)
    assert len(long.dispatches) == 14
    assert len(SegmentPlan(_prompts([100] + [9] * 3), 4, 8, 2, GEN)
               .dispatches) == 13
    # the prompts' part of the span in steps of 512, the generated in 128
    assert plan.span == 512 + 128
    assert SegmentPlan(_prompts([513]), 1, 8, 1, gen=130).span == 1024 + 256
    with pytest.raises(ValueError, match="empty prompt"):
        SegmentPlan(_prompts([4, 0]), 2, 8, 2, GEN)


def test_spans_and_counters_exist_without_tracing(tpu_session, model):
    assert not tracer.enabled
    before = {c: metrics.counter(c).value for c in COUNTERS}
    prompts = _prompts([9, 6, 13], seed=2)
    _stage(model, batch=4).transform(_frame(tpu_session, prompts)).collect()
    mine = tracer.recent()
    root = [r for r in mine if r.name == "ar_generate.partition"][-1]
    assert root.parent_id is None
    assert root.attributes == {
        "model": "granite", "rows": 3, "batches": 1, "prompt_tokens": 28,
        "generated_tokens": 3 * GEN}
    inside = [r for r in mine if r.parent_id == root.span_id]
    names = [r.name for r in inside]
    for name in ("ar_generate.plan", "engine.place", "ar_generate.prefill",
                 "ar_generate.decode", "engine.fetch_wait",
                 "ar_generate.postprocess"):
        assert name in names, name
    # pairs: two segments of rows 0 and 2, one of row 1 and of the dummy
    # row (its one token): three dispatches of 2 x 8 positions
    prefill = [r for r in inside if r.name == "ar_generate.prefill"][-1]
    # six pairs under a span of 512 + 128, each scoring one block of 512
    assert prefill.attributes == {
        "tokens": 28, "pad_tokens": 3 * 16 - 28, "segments": 3,
        "keys_scored": 6 * 512, "keys_spanned": 6 * 640}
    decodes = [r for r in inside if r.name == "ar_generate.decode"]
    # a row of L tokens (the dummy row's 1) sees L + t + 1 slots at decode
    # step t, of the 640 its attention reads: 29 + 4 (t + 1) a step
    assert [d.attributes for d in decodes] == [
        {"steps": 2, "rows": 4, "slots_read": 2 * 4 * 640,
         "slots_visible": 33 + 37},
        {"steps": 2, "rows": 4, "slots_read": 2 * 4 * 640,
         "slots_visible": 41 + 45},
        {"steps": 1, "rows": 4, "slots_read": 4 * 640, "slots_visible": 49}]
    delta = {c: metrics.counter(c).value - before[c] for c in COUNTERS}
    assert delta["ar_generate.prefill_tokens"] == 28
    assert delta["ar_generate.prefill_pad_tokens"] == 20
    assert delta["ar_generate.decode_steps"] == GEN - 1
    assert delta["ar_generate.decode_dispatches"] == 3
    assert delta["ar_generate.tokens_generated"] == 3 * GEN
    # 4 rows x 2 experts a token: 2 to 8 of a layer's 8 experts a step
    assert 5 * 4 * 2 <= delta["ar_generate.decode_expert_reads"] <= 5 * 4 * 8
    # 3 Mamba layers x 4 rows of a float32 [8, 8, 16] state and a float32
    # [3, 96] conv window (the weights' dtype)
    assert delta["ssm.state_bytes"] == 3 * 4 * (8 * 8 * 16 * 4 + 3 * 96 * 4)
    # 48 prefill positions and 5 steps of 4 rows through 4 layers with 2
    # experts a token, pads and the dummy row routed like any other — and
    # nothing dropped
    assert delta["moe.tokens_routed"] == (48 + 5 * 4) * 4 * 2
    # all 8 experts are held here: the whole of the routed work
    assert delta["moe.pairs_held"] == delta["moe.tokens_routed"]
    assert delta["moe.tokens_dropped"] == 0
    assert delta["moe.expert_load_max"] >= delta["moe.expert_load_mean"] > 0


def test_slots_read_follow_the_attention_that_runs(model, monkeypatch):
    """A decode dispatch's ``slots_read`` is the model's count at the plan's
    positions: granite's (and solar's) attention scores the whole span on
    every backend; Moonlight's reads whole blocks up to each row's position
    where its kernel runs (the TPU, a latent of whole tiles: 512 + 64
    channels here) and the whole span where its plain form does."""
    from sparkdl_tpu.models.moonlight import MoonlightModel
    from test_moonlight import CONFIG as MOONLIGHT

    plan = SegmentPlan(_prompts([9, 600, 13]), 4, 8, 2, gen=3)
    assert plan.span == 1024 + 128
    # the dummy row's prompt is one token; decode steps 1 and 2
    positions = plan.positions(1, 2)
    np.testing.assert_array_equal(
        positions, [[10, 601, 14, 2], [11, 602, 15, 3]])
    wide = MoonlightModel(
        dict(MOONLIGHT, kv_lora_rank=512, qk_rope_head_dim=64), None)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert model.slots_read(positions, plan.span) == 2 * 4 * 1152
    assert wide.slots_read(positions, plan.span) == (6 + 2 * 2) * 512
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert wide.slots_read(positions, plan.span) == 2 * 4 * 1152


def test_every_dispatch_gets_a_device_span_under_the_partitions_root(
        tpu_session, model):
    """The engine's watcher writes one ``engine.device`` a dispatch, named by
    program, under the root of the partition whose rows it carries."""
    from sparkdl_tpu.engine import executor

    prompts = _prompts([9, 6, 13], seed=2)
    _stage(model, batch=4).transform(_frame(tpu_session, prompts)).collect()
    assert executor._watcher.settle(timeout=60)
    mine = tracer.recent()
    root = [r for r in mine if r.name == "ar_generate.partition"][-1]
    device = [r for r in mine
              if r.name == "engine.device" and r.parent_id == root.span_id]
    # three prefill dispatches of 2 pairs, then 2 + 2 + 1 decode steps
    assert [(d.attributes["program"], d.attributes["rows"],
             d.attributes.get("steps")) for d in device] == [
        ("granite_prefill", 2, None)] * 3 + [
        ("granite_decode", 4, 2), ("granite_decode", 4, 2),
        ("granite_decode", 4, 1)]
    for before, after in zip(device, device[1:]):
        assert before.end_ns <= after.start_ns  # one program at a time
    assert root.start_ns <= device[0].start_ns
    assert device[-1].end_ns <= root.end_ns
    assert {d.thread_id for d in device} == {executor._watcher._thread.ident}
    assert root.thread_id not in {d.thread_id for d in device}
    # the stage hands no placed batch over: nothing to donate, ~100 arrays
    assert not [r for r in mine if r.name == "engine.transfer"
                and r.parent_id == root.span_id]


def test_keys_scored_and_spanned_are_the_plans_sums(
        tpu_session, params, model, monkeypatch):
    """What the prefill's attention scores, from the plan alone: every pair
    of every dispatch, the spare one (row 3 of 3, ``start`` 0) too, whole
    blocks up to ITS ``start + 8`` — against pairs x span, which the form
    before PR 38 scored."""
    from sparkdl_tpu.models import hybrid

    monkeypatch.setattr(hybrid, "KEY_BLOCK", 24)
    prompts = _prompts([30, 5, 9], seed=5)
    rows = _stage(model, batch=3).transform(
        _frame(tpu_session, prompts)).collect()
    plan = SegmentPlan(prompts, 3, 8, 2, GEN)
    starts = [int(s) for arrays, _ in plan.dispatches for s in arrays[2]]
    spare = sum(int(r) == 3 for arrays, _ in plan.dispatches
                for r in arrays[1])
    assert spare == 1 and len(starts) == 2 * len(plan.dispatches) == 8
    scored = sum(-(-(start + 8) // 24) * 24 for start in starts)
    spanned = len(starts) * 640
    assert 0 < scored < spanned
    prefill = [r for r in tracer.recent()
               if r.name == "ar_generate.prefill"][-1]
    assert prefill.attributes["keys_scored"] == scored
    assert prefill.attributes["keys_spanned"] == spanned
    # the span is the only place they are written: no counter repeats them
    assert (plan.keys_scored, plan.keys_spanned) == (scored, spanned)
    assert not [name for name in metrics.snapshot("ar_generate.")
                if "keys_" in name]
    # and the blocks of 24 generate what the reference does
    for row, prompt in zip(rows, prompts):
        _teacher_forced(params, prompt, row)


def test_programs_and_state_are_shared_across_models_of_one_config(
        tpu_session, params):
    from sparkdl_tpu.engine import engine

    prompts = _prompts([6, 9], seed=5)
    frame = _frame(tpu_session, prompts)
    first = GraniteHybridModel(CONFIG, params)
    _stage(first, batch=2).transform(frame).collect()
    compiled = metrics.counter("engine.cache_miss").value
    other = reference.make_params(CONFIG, 43, "float32")
    second = GraniteHybridModel(CONFIG, other)
    rows = _stage(second, batch=2).transform(frame).collect()
    # weights are arguments: other weights of the same config compile nothing
    assert metrics.counter("engine.cache_miss").value == compiled
    for prompt, row in zip(prompts, rows):
        _teacher_forced(other, prompt, row)
    (runner,) = vars(second)["_ar_generate_runners"].values()
    assert sorted(key[0] for key in runner.programs) == [
        "decode", "decode", "prefill"]  # steps 2 and the 1 left over
    assert engine is not None


def test_settings_are_checked(tpu_session, model):
    frame = _frame(tpu_session, _prompts([5]))
    with pytest.raises(ValueError, match="at least 1"):
        _stage(model, gen=0).transform(frame)
    with pytest.raises(ValueError, match="at least 1"):
        _stage(model, batch=0).transform(frame)
    with pytest.raises(ValueError, match="empty prompt"):
        _stage(model).transform(
            _frame(tpu_session, _prompts([5, 0]))).collect()


def test_in_a_pipeline_after_a_cached_frame(tpu_session, params, model):
    from sparkdl_tpu.ml.pipeline import Pipeline

    prompts = _prompts([7, 10, 4], seed=9)
    frame = _frame(tpu_session, prompts).cache()
    fitted = Pipeline(stages=[_stage(model)]).fit(frame)
    for prompt, row in zip(prompts, fitted.transform(frame).collect()):
        _teacher_forced(params, prompt, row)


# -- the stage's second model (PR 37) ------------------------------------------

SOLAR = dict(
    model_type="solar_open2", vocab_size=96, hidden_size=32,
    num_hidden_layers=4, gqa_layers=[0, 4], num_attention_heads=4,
    num_key_value_heads=2, head_dim=8,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=8,
                            num_heads=4, num_kv_heads=None),
    n_routed_experts=2, experts_held=[2, 4], published={"n_routed_experts": 8},
    n_shared_experts=1, num_experts_per_tok=2, moe_intermediate_size=16,
    norm_topk_prob=True, routed_scaling_factor=1, rms_norm_eps=1e-5,
    use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, first_k_dense_replace=0, kda_chunk_size=8,
)


@pytest.fixture(scope="module")
def solar():
    from chipbench.reference import solar_open2
    from sparkdl_tpu.models.solar_open2 import SolarOpen2Model

    params = solar_open2.make_params(SOLAR, 47, "float32")
    return SolarOpen2Model(SOLAR, params), params


def _solar_teacher_forced(params, prompt, row, gen=GEN):
    from chipbench.reference import solar_open2

    tokens = np.asarray(row["generated"])
    record = np.asarray(row["record"])
    assert tokens.shape == (gen,) and record.shape == (gen, 2)
    np.testing.assert_array_equal(record[:, 0], tokens)
    want = solar_open2.teacher_forced(params, SOLAR, prompt, tokens)
    np.testing.assert_array_equal(want.argmax(axis=-1), tokens)
    # as ``_teacher_forced``, with the chunked rule's triangular solve
    # between the two: a few ulps more
    np.testing.assert_allclose(record[:, 1], want.max(axis=-1), atol=4e-6)


def test_the_second_model_generates_through_the_same_stage(
        tpu_session, solar):
    """A quarter of the experts held (2 of 8): the counters read the share,
    the root span names the model, ``ssm.state_bytes`` counts the KDA states
    and the three conv windows."""
    model, params = solar
    prompts = _prompts([5, 30, 16, 9], seed=1)
    before = {c: metrics.counter(c).value for c in COUNTERS}
    rows = _stage(model).transform(_frame(tpu_session, prompts)).collect()
    assert [r["rowId"] for r in rows] == [0, 1, 2, 3]
    for prompt, row in zip(prompts, rows):
        _solar_teacher_forced(params, prompt, row)
    root = [r for r in tracer.recent()
            if r.name == "ar_generate.partition"][-1]
    assert root.attributes["model"] == "solar"
    delta = {c: metrics.counter(c).value - before[c] for c in COUNTERS}
    # 2 + 4 + 2 + 2 segments of 8 in five dispatches of two pairs, then 5
    # decode steps of 4 rows, through 4 layers with 2 experts a token
    assert delta["moe.tokens_routed"] == (5 * 16 + 5 * 4) * 4 * 2
    assert delta["moe.tokens_dropped"] == 0
    # experts 2 and 3 of 8: about a quarter of the pairs, never all of them
    assert 0 < delta["moe.pairs_held"] < 0.6 * delta["moe.tokens_routed"]
    # 3 KDA layers x 4 rows of a float32 [4, 8, 8] state and three float32
    # [3, 32] conv windows
    assert delta["ssm.state_bytes"] == 3 * 4 * (4 * 8 * 8 * 4 + 3 * 3 * 32 * 4)
    (runner,) = vars(model)["_ar_generate_runners"].values()
    assert sorted(key[0] for key in runner.programs) == [
        "decode", "decode", "prefill"]


def test_a_prefill_span_carries_the_rules_chunks_where_the_model_has_a_rule(
        tpu_session, model, solar, monkeypatch):
    """``rule_chunks``: dispatches x pairs x chunks a segment x KDA layers,
    from the plan's shapes; ``rule_chunks_fused``: those that went through
    the kernel, none at these widths (heads of 8 channels) whatever the
    backend.  A model without a chunked rule of its own gets neither."""
    from sparkdl_tpu.ops import delta_rule

    solar_model, _ = solar
    prompts = _prompts([5, 30, 16, 9], seed=1)
    frame = _frame(tpu_session, prompts)

    def prefill_span(stage_model):
        _stage(stage_model).transform(frame).collect()
        return [r for r in tracer.recent()
                if r.name == "ar_generate.prefill"][-1].attributes

    # 2 + 4 + 2 + 2 segments of 8 positions in five dispatches of two pairs;
    # a segment is one chunk of 8 (kda_chunk_size) in each of 3 KDA layers
    plan = SegmentPlan(prompts, 4, 8, 2, GEN)
    assert len(plan.dispatches) == 5
    assert solar_model.rule_chunks(2, 8) == (3 * 2, 0)
    assert solar_model.rule_chunks(2, 20) == (3 * 2 * 3, 0)
    got = prefill_span(solar_model)
    assert (got["rule_chunks"], got["rule_chunks_fused"]) == (5 * 2 * 3, 0)
    assert got["segments"] == 5
    assert not [key for key in prefill_span(model) if key.startswith("rule")]
    # the model asks the function the rule itself picks its path by: at the
    # published widths on the chip every chunk of the stage's one prefill
    # shape goes through the kernel
    from sparkdl_tpu.models.solar_open2 import SolarOpen2Model

    wide = SolarOpen2Model(dict(
        SOLAR, kda_chunk_size=64, linear_attn_config=dict(
            SOLAR["linear_attn_config"], head_dim=128,
            num_heads=2 * delta_rule.HEAD_BLOCK)), None)
    assert wide.rule_chunks(16, 128) == (3 * 32, 0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert wide.rule_chunks(16, 128) == (3 * 32, 3 * 32)
    assert solar_model.rule_chunks(2, 8) == (3 * 2, 0)


def test_two_models_one_after_the_other_in_one_process(
        tpu_session, params, solar):
    """Neither's state or programs reach the other: each model object keeps
    its own runner, its programs carry its own name and fingerprint, and a
    row's result is what it was before the other model ran."""
    solar_model, solar_params = solar
    granite = GraniteHybridModel(CONFIG, params)
    prompts = _prompts([7, 12, 3], seed=6)
    frame = _frame(tpu_session, prompts)
    first = _stage(granite).transform(frame).collect()
    between = _stage(solar_model).transform(frame).collect()
    again = _stage(granite).transform(frame).collect()
    for prompt, a, b, c in zip(prompts, first, between, again):
        _teacher_forced(params, prompt, a)
        _solar_teacher_forced(solar_params, prompt, b)
        np.testing.assert_array_equal(a["generated"], c["generated"])
        np.testing.assert_array_equal(a["record"], c["record"])
    (granite_runner,) = vars(granite)["_ar_generate_runners"].values()
    (solar_runner,) = vars(solar_model)["_ar_generate_runners"].values()
    assert granite_runner is not solar_runner
    assert granite_runner.model is granite
    assert solar_runner.model is solar_model
    # a spare state of one model's shapes is no state of the other's
    assert not set(granite_runner.states) & set(solar_runner.states)
    names = [r.attributes["model"] for r in tracer.recent()
             if r.name == "ar_generate.partition"][-3:]
    assert names == ["granite", "solar", "granite"]
    assert granite.fingerprint.split(":")[0] != (
        solar_model.fingerprint.split(":")[0])
