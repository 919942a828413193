"""``BlockDiffusionTransformer`` over a DataFrame of prompts, at a tiny size
on the CPU: every step of the generation teacher-forced against the plain
reference (``chipbench/reference/sdar_moe.py``), the layout of a batch, the
programs' reuse, the spans and the counters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import sdar_moe as reference
from sparkdl_tpu import BlockDiffusionTransformer
from sparkdl_tpu.models.sdar_moe import SdarMoeModel
from sparkdl_tpu.obs.trace import tracer
from sparkdl_tpu.transformers.block_diffusion import BatchPlan
from sparkdl_tpu.utils.metrics import metrics

CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32, norm_topk_prob=True,
    rope_theta=1e6, rms_norm_eps=1e-6,
)
BLOCK, MASK, GEN = 4, 255, 8


@pytest.fixture(scope="module")
def params():
    return reference.make_params(CONFIG, 41, "float32")


@pytest.fixture(scope="module")
def model(params):
    return SdarMoeModel(CONFIG, params)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, MASK, n).astype(np.int32) for n in lengths]


def _stage(model, steps=4, batch=4, **more):
    return BlockDiffusionTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=model, genLength=GEN, blockLength=BLOCK, denoisingSteps=steps,
        maskTokenId=MASK, batchSize=batch, **more)


def _frame(session, prompts, partitions=1):
    return session.createDataFrame(
        list(enumerate(prompts)), ["rowId", "prompt"],
        numPartitions=partitions)


def _teacher_forced(params, prompt, row, steps, gen=GEN):
    """Every step of every block of a row is what the reference, put in the
    same state, would have done; returns the forwards that takes: ``steps``
    a block, and a commit for every block but the last."""
    record = np.asarray(row["record"])
    rest = len(prompt) % BLOCK
    blocks = -(-(rest + gen) // BLOCK)
    assert record.shape == (blocks * BLOCK, 3)
    np.testing.assert_array_equal(record[:rest, 1], -1)
    np.testing.assert_array_equal(record[:rest, 0], prompt[len(prompt) - rest:])
    np.testing.assert_array_equal(
        row["generated"], record[rest:rest + gen, 0].astype(np.int32))
    assert len(row["generated"]) == gen and MASK not in row["generated"]
    for block in range(blocks):
        at = record[block * BLOCK:(block + 1) * BLOCK]
        for step in range(steps):
            logp, masked = reference.replay(
                params, CONFIG, prompt, record, block, step, BLOCK, MASK,
                pad_to=32)
            if not any(masked):
                assert not (at[:, 1] == step).any()
                continue
            fixed, tokens = reference.choose(logp, masked, steps - step)
            assert sorted(np.flatnonzero(at[:, 1] == step)) == fixed
            assert [int(at[i, 0]) for i in fixed] == tokens
            np.testing.assert_allclose(
                at[fixed, 2], [logp[i, t] for i, t in zip(fixed, tokens)],
                atol=1e-5)
        assert (at[:, 1] < steps).all()
    return blocks * (steps + 1) - 1


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rest", [0, 1, 3])
def test_generation_is_the_references_at_every_step(
        tpu_session, params, model, steps, rest):
    prompts = _prompts([8 + rest, 4 + rest], seed=10 * steps + rest)
    before = {c: metrics.counter(c).value for c in (
        "generate.denoise_forwards", "generate.commit_forwards")}
    rows = _stage(model, steps, batch=2).transform(
        _frame(tpu_session, prompts)).collect()
    forwards = sum(
        _teacher_forced(params, prompt, row, steps)
        for prompt, row in zip(prompts, rows))
    counted = sum(metrics.counter(c).value - before[c] for c in before)
    assert counted == forwards  # blocks x (steps + 1) - 1, a row


def test_mixed_lengths_a_padded_last_batch_and_rows_in_order(
        tpu_session, params, model):
    lengths = [5, 18, 3, 12, 9, 16, 2]  # batches of 4 and 3 (+1 dummy row)
    prompts = _prompts(lengths, seed=7)
    out = _stage(model).transform(_frame(tpu_session, prompts, partitions=1))
    rows = out.collect()
    assert [r["rowId"] for r in rows] == list(range(len(prompts)))
    for prompt, row in zip(prompts, rows):
        np.testing.assert_array_equal(row["prompt"], prompt)
        _teacher_forced(params, prompt, row, 4)
    # a row's result does not depend on the batch it sat in
    alone = _stage(model, batch=1).transform(
        _frame(tpu_session, prompts[1:2])).collect()[0]
    np.testing.assert_array_equal(alone["generated"], rows[1]["generated"])
    np.testing.assert_allclose(alone["record"], rows[1]["record"], atol=1e-5)


def test_two_partitions_and_an_empty_frame(tpu_session, model):
    prompts = _prompts([6, 7, 9, 4], seed=3)
    rows = _stage(model, batch=2).transform(
        _frame(tpu_session, prompts, partitions=2)).collect()
    assert [r["rowId"] for r in rows] == [0, 1, 2, 3]
    assert all(len(r["generated"]) == GEN for r in rows)
    empty = _stage(model).transform(
        _frame(tpu_session, prompts).filter(lambda r: r["rowId"] < 0))
    assert empty.collect() == []


def test_the_plan_of_a_batch():
    prompts = _prompts([130, 5, 260, 64, 17, 33, 40])
    plan = BatchPlan(prompts, 8, BLOCK, GEN, prefill_tokens=512)
    assert list(plan.order[:7]) == [2, 0, 3, 6, 5, 4, 1]  # longest first
    assert list(plan.whole) == [260, 128, 64, 40, 32, 16, 4, 0]
    assert plan.longest == 384 and plan.span == 512 and plan.blocks == 3
    # chunks of ~512 tokens at each one's own padded length; the last one
    # would run past the batch and starts earlier
    assert plan.chunks == [(0, 1, 384), (1, 4, 128), (4, 4, 128)]
    # rows 130, 5, 17 and 33 keep 2, 1, 1 and 1 prompt tokens for block 0
    assert plan.fixed_in_block(0) == 7 * BLOCK - 5
    assert plan.fixed_in_block(2) == 4 * BLOCK  # those four need a third
    assert plan.known[7].all() and plan.blocks_of_row[7] == 0  # the dummy


def test_weights_are_arguments_placed_once(tpu_session, params):
    """Two models of one config share their executables; device-resident
    weights are not sent again; a model's host weights are sent once."""
    frame = _frame(tpu_session, _prompts([6, 9], seed=5))
    first = SdarMoeModel(CONFIG, params)
    _stage(first, batch=2).transform(frame).collect()
    misses = metrics.counter("engine.cache_miss").value
    other = jax.tree_util.tree_map(lambda a: a * 1.5, params)
    second = SdarMoeModel(CONFIG, other)
    rows = _stage(second, batch=2).transform(frame).collect()
    assert metrics.counter("engine.cache_miss").value == misses
    placed = [r for r in tracer.recent() if r.name == "engine.place_params"]
    assert placed[-1].attributes["bytes"] == 0  # handed device arrays
    _teacher_forced(other, _prompts([6, 9], seed=5)[0], rows[0], 4)
    host = SdarMoeModel(CONFIG, jax.tree_util.tree_map(np.asarray, params))
    stage = _stage(host, batch=2)
    stage.transform(frame).collect()
    stage.transform(frame).collect()
    placed = [r for r in tracer.recent() if r.name == "engine.place_params"]
    sent = sum(np.asarray(a).nbytes for a in jax.tree_util.tree_leaves(params))
    assert [r.attributes["bytes"] for r in placed[-2:]] == [0, sent]


def test_in_a_pipeline_after_a_cached_frame(tpu_session, params, model):
    from sparkdl_tpu.ml.pipeline import Pipeline

    prompts = _prompts([7, 10, 4], seed=9)
    frame = _frame(tpu_session, prompts).cache()
    fitted = Pipeline(stages=[_stage(model, steps=2)]).fit(frame)
    rows = fitted.transform(frame).collect()
    for prompt, row in zip(prompts, rows):
        _teacher_forced(params, prompt, row, 2)


def test_spans_and_counters_exist_without_tracing(tpu_session, model):
    assert not tracer.enabled
    counters = (
        "generate.denoise_forwards", "generate.commit_forwards",
        "generate.tokens_fixed", "moe.tokens_routed", "moe.pairs_held",
        "moe.tokens_dropped",
        "moe.expert_load_max", "moe.expert_load_mean")
    before = {c: metrics.counter(c).value for c in counters}
    prompts = _prompts([9, 6, 13], seed=2)
    _stage(model, batch=4).transform(_frame(tpu_session, prompts)).collect()
    mine = tracer.recent()
    root = [r for r in mine if r.name == "generate.partition"][-1]
    assert root.parent_id is None
    assert root.attributes == {
        "rows": 3, "batches": 1, "prompt_tokens": 28,
        "generated_tokens": 3 * GEN}
    inside = [r for r in mine if r.parent_id == root.span_id]
    names = [r.name for r in inside]
    for name in ("generate.plan", "engine.place", "generate.prefill",
                 "generate.block", "engine.fetch_wait",
                 "generate.postprocess"):
        assert name in names, name
    blocks = [r for r in inside if r.name == "generate.block"]
    assert [b.attributes["index"] for b in blocks] == [0, 1, 2]
    # block 0 has nothing to commit; each later step commits the block
    # before it inside its first forward; nothing follows the last
    assert [b.attributes["commit_forwards"] for b in blocks] == [0, 1, 1]
    assert [b.attributes["fused"] for b in blocks] == [0, 1, 1]
    assert all(b.attributes["denoise_forwards"] == 4
               and b.attributes["weight_passes"] == 4 for b in blocks)
    assert sum(b.attributes["fixed"] for b in blocks) == 9 * BLOCK - 4
    prefill = [r for r in inside if r.name == "generate.prefill"][-1]
    assert prefill.attributes == {"tokens": 8 + 4 + 12, "chunks": 1}
    delta = {c: metrics.counter(c).value - before[c] for c in counters}
    # 3 rows x 3 blocks, a row's last never committed
    assert delta["generate.commit_forwards"] == 3 * (3 - 1)
    # every commit shared its forward (``fused``): the spans say so, block
    # by block, and no counter repeats them
    assert (sum(b.attributes["fused"] * 3 for b in blocks)
            == delta["generate.commit_forwards"])
    assert delta["generate.denoise_forwards"] == 9 * 4
    # passes over the weights the real rows paid for: 3 rows in each block
    assert [b.attributes["row_passes"] for b in blocks] == [3 * 4] * 3
    assert delta["generate.tokens_fixed"] == 9 * BLOCK - 4
    # prefill 4 rows x 128 padded tokens; block 0 four forwards of 4 x 4
    # tokens, blocks 1 and 2 five each (the first forward carries the block
    # before as well); through 2 layers with 2 experts a token — and
    # nothing dropped
    assert delta["moe.tokens_routed"] == (512 + (4 + 5 + 5) * 16) * 2 * 2
    # every expert is held here: the whole of the routed work
    assert delta["moe.pairs_held"] == delta["moe.tokens_routed"]
    assert delta["moe.tokens_dropped"] == 0
    assert delta["moe.expert_load_max"] >= delta["moe.expert_load_mean"] > 0


def test_every_dispatch_gets_a_device_span_under_the_partitions_root(
        tpu_session, model):
    from sparkdl_tpu.engine import executor

    prompts = _prompts([9, 6, 13], seed=2)
    _stage(model, batch=4).transform(_frame(tpu_session, prompts)).collect()
    assert executor._watcher.settle(timeout=60)
    mine = tracer.recent()
    root = [r for r in mine if r.name == "generate.partition"][-1]
    device = [r for r in mine
              if r.name == "engine.device" and r.parent_id == root.span_id]
    # one prefill chunk of the 4 padded rows, then three block steps
    assert [(d.attributes["program"], d.attributes["rows"])
            for d in device] == [("sdar_prefill", 4)] + [("sdar_block", 4)] * 3
    for before, after in zip(device, device[1:]):
        assert before.end_ns <= after.start_ns  # one program at a time
    assert root.start_ns <= device[0].start_ns
    assert device[-1].end_ns <= root.end_ns
    assert root.thread_id not in {d.thread_id for d in device}
    # and the reader's ratio from the blocks' attributes: 4 passes a block
    # over 3 live rows, for the positions those rows fixed
    blocks = [r for r in mine
              if r.name == "generate.block" and r.parent_id == root.span_id]
    assert sum(b.attributes["row_passes"] for b in blocks) == 9 * 4
    assert sum(b.attributes["fixed"] for b in blocks) == 9 * BLOCK - 4


def test_a_batch_of_one_block_dispatches_the_plain_shape_once(
        tpu_session, params):
    """A prompt of whole blocks and ``genLength`` <= ``blockLength``: one
    block, nothing pending before it and nothing after it to commit it."""
    own = SdarMoeModel(CONFIG, params)  # a runner, and so a program table, of its own
    prompts = _prompts([8, 4], seed=6)
    before = {c: metrics.counter(c).value for c in (
        "generate.commit_forwards",)}
    stage = BlockDiffusionTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=own, genLength=BLOCK, blockLength=BLOCK, denoisingSteps=4,
        maskTokenId=MASK, batchSize=2)
    rows = stage.transform(_frame(tpu_session, prompts)).collect()
    for prompt, row in zip(prompts, rows):
        assert _teacher_forced(params, prompt, row, 4, gen=BLOCK) == 4
    root = [r for r in tracer.recent() if r.name == "generate.partition"][-1]
    blocks = [r for r in tracer.recent()
              if r.name == "generate.block" and r.parent_id == root.span_id]
    assert [(b.attributes["index"], b.attributes["commit_forwards"],
             b.attributes["fused"], b.attributes["row_passes"])
            for b in blocks] == [(0, 0, 0, 2 * 4)]
    delta = {c: metrics.counter(c).value - before[c] for c in before}
    assert delta == {"generate.commit_forwards": 0}
    (runner,) = vars(own)["_block_diffusion_runners"].values()
    # the last element of a block program's key: whether a block is pending
    assert [key[-1] for key in runner.programs if key[0] == "block"] == [False]


def test_settings_are_checked(tpu_session, model):
    frame = _frame(tpu_session, _prompts([5]))
    with pytest.raises(ValueError, match="denoisingSteps"):
        _stage(model, steps=5).transform(frame)
    unmasked = BlockDiffusionTransformer(
        inputCol="prompt", outputCol="generated", model=model)
    with pytest.raises(ValueError, match="maskTokenId"):
        unmasked.transform(frame)
    plain = BlockDiffusionTransformer(
        inputCol="prompt", outputCol="generated", model=model,
        maskTokenId=MASK, genLength=4, batchSize=2)
    row = plain.transform(frame).collect()[0]
    assert "record" not in row.asDict() and len(row["generated"]) == 4


def test_an_executable_jaxs_cache_served_is_not_stored_again(tmp_path):
    """The same program under another fingerprint is served by JAX's own
    persistent cache; a stored copy of such a LOADED executable fails at its
    first run on the CPU ("Function ... not found"), so the engine's store
    keeps only what was really compiled."""
    from sparkdl_tpu.engine import ExecutionEngine, PersistentCompileCache
    from sparkdl_tpu.engine import core

    engine = ExecutionEngine(cache=PersistentCompileCache(str(tmp_path)))
    x = np.ones((4,), np.float32)

    def compiled_afresh(x):
        return x + 1

    def served_by_jax(x):
        core._jax_cache_hits.count += 1  # what the cache's event does
        return x + 2

    fresh = engine.program(compiled_afresh, (x,), fingerprint="t:fresh")
    served = engine.program(served_by_jax, (x,), fingerprint="t:served")
    assert fresh.source == served.source == "compile"
    assert fresh.key in engine.cache and served.key not in engine.cache
    np.testing.assert_array_equal(served(x), x + 2)
    # and only chosen arguments are donated when positions are given
    state = jnp.zeros((4,), jnp.float32)
    step = engine.program(
        lambda w, s: s + w, (x, state), fingerprint="t:donate", donate=(1,))
    out = step(x, state)
    np.testing.assert_array_equal(out, x)
    np.testing.assert_array_equal(x, np.ones(4, np.float32))  # not donated
