"""The cell ``generate-glm5-long-documents``: its rehearsal prints the
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

from chipbench import glm5_counts, harness, prompt_traffic  # noqa: E402

CELL = "generate-glm5-long-documents"
METRICS = {
    "mfu.dsa_generate", "glm5_prefill_program_roofline",
    "glm5_decode_program_roofline",
    "prefill_ms_per_image.dsa_generate", "decode_ms_per_image.dsa_generate",
    "prefill_pad_share.dsa_generate", "dispatches_per_token.dsa_generate",
    "expert_load_max_over_mean.dsa_generate",
    "pairs_held_share.dsa_generate", "keys_selected_share.dsa_generate",
    "decode_slots_selected_share.dsa_generate",
    "compiles_in_window.dsa_generate", "engine_starved_share.dsa_generate",
    "device_idle_share.dsa_generate", "device_busy_share.dsa_generate",
    "prefill_device_ms_per_image.dsa_generate",
    "decode_device_ms_per_image.dsa_generate"}


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


def test_rehearsal_prints_the_contracts_last_line():
    proc = _run(["chipbench/run.py", "--workload", CELL, "--seed",
                 str(2**31 + 4444), "--seconds", "1", "--trace", "1",
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
    assert line["compared_rows"] == 4
    assert line["compiles_in_window"]["backend_compiles"] == 0
    assert line["compiles_in_window"]["engine_cache_miss"] == 0
    metrics = line["metrics"]
    # a CPU run is never written under the name of a device metric
    assert set(metrics) == {
        m for m in METRICS
        if not ("mfu" in m or "roofline" in m or "idle" in m)}
    assert metrics["compiles_in_window.dsa_generate"]["value"] == 0
    # 7 decode steps a batch in one dispatch (up to 8 a dispatch)
    assert metrics["dispatches_per_token.dsa_generate"]["value"] == (
        pytest.approx(1 / 7))
    # the rehearsal holds 4 of the router's 16 experts
    assert 0 < metrics["pairs_held_share.dsa_generate"]["value"] < 1
    # the masked fold scores whole blocks of the span, the plain indexer
    # (the CPU's) all of it; the masked decode reads the whole span, more
    # than the slots each row sees
    assert 0 < metrics["keys_selected_share.dsa_generate"]["value"] <= 1
    assert metrics["decode_slots_selected_share.dsa_generate"]["value"] > 1
    for name in ("prefill_ms_per_image.dsa_generate",
                 "decode_ms_per_image.dsa_generate",
                 "engine_starved_share.dsa_generate",
                 "device_busy_share.dsa_generate",
                 "prefill_device_ms_per_image.dsa_generate",
                 "decode_device_ms_per_image.dsa_generate"):
        assert metrics[name]["value"] >= 0, name


def test_the_control_and_the_three_faults_come_out_not_correct():
    proc = _run([os.path.join(HERE, "faulty_dsa_generate.py"),
                 "--workload", CELL])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = _last_json(proc)
    assert got["sound"] is True, got["sound_compared"]
    assert got["control"] is False, got["control_compared"]
    assert set(got["faults"]) == {
        "selection_left_out", "relu_left_out", "rope_left_out"}
    for name, correct in got["faults"].items():
        assert correct is False, (name, got["compared"][name])
        assert got["compared"][name]["rows_malformed"]["value"] == 0


def test_every_seed_gets_the_same_lengths_in_another_order(cell):
    mix = cell.traffic
    assert mix["kind"] == "prompt_frame" and mix["partitions"] == 1
    assert mix["genLength"] == 64 and cell.workload["batchSize"] == 16
    fixed = prompt_traffic.lengths(mix)
    assert len(fixed) == 16
    assert fixed.min() == 4371 and fixed.max() == 30706
    assert fixed.sum() == 220456  # mean 13,779
    # every row longer than the keys a query keeps
    assert fixed.min() > cell.config["index_topk"]
    from sparkdl_tpu.transformers.ar_generate import SegmentPlan

    plan = SegmentPlan([np.zeros(n, np.int32) for n in fixed], 16, 128, 16,
                       mix["genLength"], packed=True)
    assert plan.span == 30848 and len(plan.dispatches) == 109
    vocab = cell.config["vocab_size"]
    a = prompt_traffic.prompt_frame(mix, 2**31 + 5, vocab, vocab)
    b = prompt_traffic.prompt_frame(mix, 7, vocab, vocab)
    assert sorted(map(len, a)) == sorted(map(len, b)) == sorted(fixed)
    assert list(map(len, a)) != list(map(len, b))
    assert all(p.max() < vocab and p.min() >= 0 for p in a)


def test_the_configuration_is_the_published_one_but_for_reduced(cell):
    config = cell.config
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "n_routed_experts", "vocab_size", "num_nextn_predict_layers"]
    assert config["reduced"] == reduced
    assert config["published"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880,
        "num_nextn_predict_layers": 1}
    assert [config[k] for k in reduced] == [5, 1, 16, 19360, 0]
    assert config["experts_held"] == [0, 16]
    assert config["source"] == (
        "https://huggingface.co/zai-org/GLM-5/blob/main/config.json")
    from chipbench.reference import glm5 as reference

    assert reference.layer_types(config) == ["dense"] + ["moe"] * 4
    assert set(config["assumed"]) >= {
        "latent_norm_eps", "index_k_norm_eps", "index_channels",
        "hadamard_and_fp8", "selection_ties", "multi_token_prediction",
        "sampling", "weights"}
    assert "sixteen" in config["deployment"]
    manifest = harness.load_manifest(ROOT)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "glm_5_744b_a40b-generate")
    assert entry["reduced"] == reduced and entry["source"] == config["source"]
    # the program reads the file as the reference does
    from sparkdl_tpu.models.glm5 import Glm5Config, param_shapes

    cfg = Glm5Config.from_dict(config)
    assert (cfg.dense, cfg.held, cfg.routed, cfg.num_hidden_layers) == (
        1, (0, 16), 256, 5)
    assert cfg.mla.latent == 576 and cfg.mla.scale == 256 ** -0.5
    assert cfg.indexer.topk == 2048 and cfg.rope_theta == 1000000
    assert param_shapes(cfg) == reference.shapes(config)
    tiny = dict(config, **config["rehearse"])
    small = Glm5Config.from_dict(tiny)
    assert param_shapes(small) == reference.shapes(tiny)
    assert small.held == (4, 8) and small.index_topk == 8


def test_counts_from_shapes(cell):
    config = cell.config
    c = glm5_counts
    # a layer's parameters: MLA 165,022,208, the indexer
    # 9,371,904, an expert (or the shared one) 37,748,736
    assert c.mla_params(config) == 165_022_208
    assert c.indexer_params(config) == 9_371_904
    assert c.expert_params(config) == 37_748_736
    # 3,909.6 M parameters with embedding and head: 7.82 GB
    assert c.weight_bytes(config) + 2 * c.head_bytes(config) == (
        pytest.approx(2 * 3_909_632_768, rel=1e-6))
    assert c.cache_bytes_per_slot(config) == 5 * (1152 + 256) == 7040
    assert c.attention_flops_per_key(config) == 2 * 64 * 512
    assert c.index_flops_per_key(config) == 2 * 32 * 128
    lengths = prompt_traffic.lengths(cell.traffic)
    need = c.needed_flops(config, lengths, 64)
    # ~3.7 GFLOP a prompt token on this cut, 220 k of them
    assert 0.78e15 < need < 0.86e15
    # in decode a step reads the held experts that got a token
    step = c.decode_dispatch(config, 16, 1, visible=13_800.0)
    assert step["bytes"] - c.decode_dispatch(
        config, 16, 1, 13_800.0, experts_read=40)["bytes"] == (
            24 * 2 * c.expert_params(config))
    assert 7.8e9 < step["bytes"] < 8.5e9
    early = c.prefill_dispatch(config, [0] * 16, 128)
    late = c.prefill_dispatch(config, [28_672] * 16, 128)
    assert late["flops"] > early["flops"] and late["bytes"] > early["bytes"]


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
    assert readers == {"mfu", "program_roofline", "span_self_time", "fact_per_unit",
                       "compiles_in_window", "device_idle_share",
                       "span_overlap", "span_attr_ratio"}
    assert cell.workload["control"] == "fp8"
    assert cell.workload["driver"] == "dsa_generate"
    manifest = harness.load_manifest(ROOT)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "glm_5_744b_a40b-generate", "long_document_frame", 1)
    images = next(m for m in manifest["end_to_end"]
                  if m["name"] == "images_per_s")
    assert CELL in images["workloads"]


def test_the_programs_facts_come_from_the_stages_own_plan(cell):
    from chipbench.drivers import ar_generate, dsa_generate

    assert issubclass(dsa_generate.Job, ar_generate.Job)
    job = dsa_generate.Job(cell, seed=5, rehearse=True, workdir="")
    vocab = job.config["vocab_size"]
    job.prompts = prompt_traffic.prompt_frame(job.mix, 5, vocab, vocab)
    programs = job._programs(experts_read=3.0)
    assert programs["glm5_prefill"]["name"] == "jit_glm5_prefill"
    assert programs["glm5_decode"]["name"] == "jit_glm5_decode"
    assert all(d["flops"] > 0 and d["bytes"] > 0
               for p in programs.values() for d in p["dispatches"])
    lengths = [len(p) for p in job.prompts]
    sample = job.sample()
    assert int(np.argmax(lengths)) in sample and int(np.argmin(lengths)) in sample


def test_the_parent_fails_the_cell_at_once_on_the_import(cell, monkeypatch):
    """A program without the model (the parent of the PR that added it)
    fails in set-up's first line, before any weight is made."""
    from chipbench.drivers import dsa_generate

    import sparkdl_tpu.models

    job = dsa_generate.Job(cell, seed=5, rehearse=True, workdir="")
    monkeypatch.setitem(sys.modules, "sparkdl_tpu.models.glm5", None)
    monkeypatch.delattr(sparkdl_tpu.models, "glm5", raising=False)
    with pytest.raises(ImportError):
        job.setup()
    assert not hasattr(job, "params")
