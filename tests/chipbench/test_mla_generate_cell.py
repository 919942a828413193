"""The cell ``generate-moonlight-summaries``: its rehearsal prints the
contract's last line with the cell's exact metric set, its control and the
three planted faults come out not correct, and its configuration, traffic,
counts and metrics do what their files say.

The runs are subprocesses with ONE CPU device, as in ``test_rehearse.py``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import harness, moonlight_counts, prompt_traffic  # noqa: E402

CELL = "generate-moonlight-summaries"
METRICS = {
    "mfu.mla_generate", "moonlight_prefill_program_roofline",
    "moonlight_decode_program_roofline", "prefill_ms_per_image.mla_generate",
    "decode_ms_per_image.mla_generate", "prefill_pad_share.mla_generate",
    "dispatches_per_token.mla_generate",
    "expert_load_max_over_mean.mla_generate",
    "compiles_in_window.mla_generate", "engine_starved_share.mla_generate",
    "device_idle_share.mla_generate", "device_busy_share.mla_generate",
    "prefill_device_ms_per_image.mla_generate",
    "decode_device_ms_per_image.mla_generate",
    "keys_scored_share.mla_generate",
    "decode_slots_visible_share.mla_generate"}


def _run(args, timeout=900):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, *args], cwd=ROOT, env=env, timeout=timeout,
        capture_output=True, text=True)


def _last_json(proc):
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


@pytest.fixture(scope="module")
def cell():
    return harness.Cell(CELL, ROOT)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace):
    proc = _run(["chipbench/run.py", "--workload", CELL, "--seed",
                 str(2**31 + 4141), "--seconds", "1", "--trace", str(trace),
                 "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = _last_json(proc)
    assert list(line)[-1] == "compared"
    assert line["rehearse"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["compared"]) == {
        "rows_out_of_place", "rows_malformed", "tokens_dropped",
        "logprob_gap", "token_regret", "logprob_gap_mean"}
    assert line["compared_rows"] == 6  # the rehearsal's frame, whole
    assert line["compiles_in_window"]["backend_compiles"] == 0
    assert line["compiles_in_window"]["engine_cache_miss"] == 0
    if trace:
        metrics = line["metrics"]
        # a CPU run is never written under the name of a device metric
        assert not any("mfu" in m or "roofline" in m or "idle" in m
                       for m in metrics)
        assert set(metrics) == {
            m for m in METRICS
            if not ("mfu" in m or "roofline" in m or "idle" in m)}
        assert metrics["compiles_in_window.mla_generate"]["value"] == 0
        # 7 decode steps a batch in one dispatch (up to 8 a dispatch)
        assert metrics["dispatches_per_token.mla_generate"]["value"] == (
            pytest.approx(1 / 7))
        assert 0 < metrics["prefill_pad_share.mla_generate"]["value"] < 1
        assert metrics["expert_load_max_over_mean.mla_generate"][
            "value"] >= 1
        # a rehearsal's prompts of 3-40 tokens under a span of 640: a small
        # share of what decode reads is visible, and of what prefill
        # scores, the one block of 512 a segment can see
        share = metrics["decode_slots_visible_share.mla_generate"]["value"]
        assert 0 < share < 0.1
        assert metrics["keys_scored_share.mla_generate"]["value"] == (
            pytest.approx(512 / 640))
        for name in ("prefill_ms_per_image.mla_generate",
                     "decode_ms_per_image.mla_generate",
                     "engine_starved_share.mla_generate",
                     "device_busy_share.mla_generate",
                     "prefill_device_ms_per_image.mla_generate",
                     "decode_device_ms_per_image.mla_generate"):
            assert metrics[name]["value"] >= 0, name
    else:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_control_and_the_three_faults_come_out_not_correct():
    proc = _run([os.path.join(HERE, "faulty_mla_generate.py"),
                 "--workload", CELL])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = _last_json(proc)
    assert got["sound"] is True, got["sound_compared"]
    assert got["control"] is False, got["control_compared"]
    assert set(got["faults"]) == {
        "scale_of_nope_only", "routed_scale_dropped", "rope_rotate_half"}
    for name, correct in got["faults"].items():
        assert correct is False, (name, got["compared"][name])
        assert got["compared"][name]["rows_malformed"]["value"] == 0


def test_every_seed_gets_the_same_lengths_in_another_order(cell):
    mix = cell.traffic
    assert mix["kind"] == "prompt_frame" and mix["partitions"] == 1
    assert mix["genLength"] == 256 and cell.workload["batchSize"] == 128
    fixed = prompt_traffic.lengths(mix)
    assert len(fixed) == 128
    assert fixed.min() == 1032 and fixed.max() == 7114
    assert fixed.sum() == 404139  # mean 3,157
    assert len(set(fixed % 128)) > 60  # not rounded to any segment
    # prompt and generated tokens within the model's context
    assert fixed.max() + mix["genLength"] <= cell.config[
        "max_position_embeddings"]
    from sparkdl_tpu.transformers.ar_generate import SegmentPlan

    plan = SegmentPlan([np.zeros(n, np.int32) for n in fixed], 128, 128, 16,
                       mix["genLength"])
    assert plan.span == 7424
    vocab = cell.config["vocab_size"]
    a = prompt_traffic.prompt_frame(mix, 2**31 + 5, vocab, vocab)
    b = prompt_traffic.prompt_frame(mix, 7, vocab, vocab)
    assert sorted(map(len, a)) == sorted(map(len, b)) == sorted(fixed)
    assert list(map(len, a)) != list(map(len, b))
    assert all(p.max() < vocab and p.min() >= 0 for p in a)


def test_the_configuration_is_the_published_one_but_for_reduced(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(catalog) as fh:
        published = next(
            row for row in map(json.loads, fh)
            if row["name"] == "Moonlight-16B-A3B")
    config = cell.config
    assert config["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items() if config.get(k) != v]
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 27}
    assert config["num_hidden_layers"] == 5
    # as published: every width, 64 experts top-6, 2 shared, the vocabulary
    assert (config["n_routed_experts"], config["num_experts_per_tok"],
            config["n_shared_experts"], config["vocab_size"]) == (
                64, 6, 2, 163840)
    # the floors: the leading dense layer and four expert layers after it
    from chipbench.reference import moonlight as reference

    assert reference.layer_types(config) == ["dense"] + ["moe"] * 4
    assert set(config["assumed"]) >= {
        "kv_a_layernorm_eps", "rope_pairing", "router", "sampling",
        "weights"}
    assert "six" in config["deployment"]
    manifest = harness.load_manifest(ROOT)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "moonlight_16b_a3b-generate")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the program reads the file as the reference does
    from sparkdl_tpu.models.moonlight import MoonlightConfig, param_shapes

    cfg = MoonlightConfig.from_dict(config)
    assert (cfg.dense, cfg.held, cfg.num_hidden_layers) == (1, (0, 64), 5)
    assert cfg.mla.latent == 576 and cfg.mla.scale == 192 ** -0.5
    assert param_shapes(cfg) == reference.shapes(config)
    tiny = dict(config, **config["rehearse"])
    small = MoonlightConfig.from_dict(tiny)
    assert param_shapes(small) == reference.shapes(tiny)
    assert small.dense == 1 and small.num_hidden_layers == 3


def test_counts_from_shapes(cell):
    config = cell.config
    c = moonlight_counts
    # the issue's arithmetic: MLA 13.77 M a layer, the dense layer 82.97 M,
    # an expert layer 584.85 M (31.20 M outside its 64 x 8.65 M experts)
    assert c.mla_params(config) == (
        2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048)
    assert c.mla_params(config) + c.dense_params(config) == 82_968_576
    assert c.expert_params(config) == 3 * 2048 * 1408
    assert c.mla_params(config) + c.ffn_params_outside_experts(config) + (
        64 * c.expert_params(config)) == 584_843_264
    # 3,093.5 M parameters with embedding and head: 6.19 GB
    assert c.weight_bytes(config) + 2 * c.head_bytes(config) == (
        pytest.approx(2 * 3_093_455_616, rel=1e-4))
    assert c.latent_bytes(config) == 1152
    assert c.cache_bytes_per_slot(config) == 5 * 1152
    # a query against a key: 2 x 16 x (192 + 128)
    assert c.attention_flops_per_key(config) == 10_240
    step = c.decode_dispatch(config, 128, 1, visible=3285.0)
    # weights and head once (5.5 GB), the visible latents (2.4 GB)
    assert 7.9e9 < step["bytes"] < 8.0e9
    assert c.decode_dispatch(config, 128, 8, 3285.0)["bytes"] == (
        8 * step["bytes"])
    # a step that read 200 of the 256 expert matrices: 56 x 17.3 MB less
    assert step["bytes"] - c.decode_dispatch(
        config, 128, 1, 3285.0, experts_read=200)["bytes"] == (
            56 * 2 * c.expert_params(config))
    segment = c.prefill_dispatch(config, [0] * 16, 128)
    assert 1.7e12 < segment["flops"] < 1.75e12
    later = c.prefill_dispatch(config, [3072] * 16, 128)
    assert later["flops"] > segment["flops"] and later["bytes"] > segment["bytes"]
    need = c.needed_flops(config, prompt_traffic.lengths(cell.traffic), 256)
    # 404 k prompt tokens and 33 k decoded ones at ~1 GFLOP each
    assert 4.2e14 < need < 4.5e14


def test_the_cells_metrics_have_their_files_and_readers(cell):
    names = {entry["name"] for entry, _, _ in cell.per_layer}
    assert names == METRICS
    assert {m["name"] for m in cell.end_to_end} == {"images_per_s", "setup_s"}
    assert all(entry["unit"] == "%" for entry, _, _ in cell.per_layer
               if "roofline" in entry["name"] or "mfu" in entry["name"]
               or "idle" in entry["name"] or "starved" in entry["name"]
               or "busy" in entry["name"])
    assert all(entry["moves"] == "images_per_s"
               and entry["workloads"] == [CELL]
               for entry, _, _ in cell.per_layer)
    readers = {reader.__name__.rsplit(".", 1)[1]
               for _, _, reader in cell.per_layer}
    assert readers == {"mfu", "program_roofline", "span_self_time",
                       "fact_per_unit", "compiles_in_window",
                       "device_idle_share", "span_overlap",
                       "span_attr_ratio"}
    assert cell.workload["control"] == "fp8"
    assert cell.workload["driver"] == "mla_generate"
    assert set(cell.workload["limits"]) == {
        "rows_out_of_place", "rows_malformed", "tokens_dropped",
        "logprob_gap", "token_regret", "logprob_gap_mean"}
    assert set(cell.workload["rehearse_limits"]) == {
        "logprob_gap", "token_regret", "logprob_gap_mean"}
    manifest = harness.load_manifest(ROOT)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "moonlight_16b_a3b-generate", "summary_frame", 1)
    images = next(m for m in manifest["end_to_end"]
                  if m["name"] == "images_per_s")
    assert images["workloads"][-1] == CELL


def test_the_programs_facts_come_from_the_stages_own_plan(cell):
    from chipbench.drivers import ar_generate, mla_generate

    assert issubclass(mla_generate.Job, ar_generate.Job)
    job = mla_generate.Job(cell, seed=5, rehearse=True, workdir="")
    vocab = job.config["vocab_size"]
    job.prompts = prompt_traffic.prompt_frame(job.mix, 5, vocab, vocab)
    programs = job._programs(experts_read=10.0)
    assert programs["moonlight_prefill"]["name"] == "jit_moonlight_prefill"
    assert programs["moonlight_decode"]["name"] == "jit_moonlight_decode"
    assert len(programs["moonlight_decode"]["dispatches"]) == 1
    assert len(programs["moonlight_prefill"]["dispatches"]) == 2
    assert all(d["flops"] > 0 and d["bytes"] > 0
               for p in programs.values() for d in p["dispatches"])
    fewer = job._programs(experts_read=4.0)["moonlight_decode"]["dispatches"]
    assert fewer[0]["bytes"] < (
        programs["moonlight_decode"]["dispatches"][0]["bytes"])
    lengths = [len(p) for p in job.prompts]
    sample = job.sample()
    assert int(np.argmax(lengths)) in sample and int(np.argmin(lengths)) in sample


def test_the_parent_fails_the_cell_at_once_on_the_import(cell, monkeypatch):
    """A program without the model (the parent of the PR that added it)
    fails in set-up's first line, before any weight is made."""
    from chipbench.drivers import mla_generate

    import sparkdl_tpu.models

    job = mla_generate.Job(cell, seed=5, rehearse=True, workdir="")
    monkeypatch.setitem(sys.modules, "sparkdl_tpu.models.moonlight", None)
    monkeypatch.delattr(sparkdl_tpu.models, "moonlight", raising=False)
    with pytest.raises(ImportError):
        job.setup()
    assert not hasattr(job, "params")
