"""The cell ``generate-solar-documents``: its rehearsal prints the contract's
last line, its control and both planted faults come out not correct, and its
configuration, traffic, counts and metrics do what their files say.

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

from chipbench import harness, prompt_traffic, solar_counts  # noqa: E402

CELL = "generate-solar-documents"
METRICS = {
    "mfu.kda_generate", "solar_prefill_program_roofline",
    "solar_decode_program_roofline", "prefill_ms_per_image.kda_generate",
    "decode_ms_per_image.kda_generate", "prefill_pad_share.kda_generate",
    "dispatches_per_token.kda_generate",
    "expert_load_max_over_mean.kda_generate", "pairs_held_share.kda_generate",
    "compiles_in_window.kda_generate", "engine_starved_share.kda_generate",
    "device_idle_share.kda_generate"}


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
                 str(2**31 + 12345), "--seconds", "1", "--trace", str(trace),
                 "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = _last_json(proc)
    assert list(line)[-1] == "compared"
    assert line["rehearse"] is True and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["compared"]) == {
        "rows_out_of_place", "rows_malformed", "tokens_dropped",
        "logprob_gap", "token_regret"}
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
        assert metrics["compiles_in_window.kda_generate"]["value"] == 0
        # 7 decode steps a batch in one dispatch (up to 8 a dispatch)
        assert metrics["dispatches_per_token.kda_generate"]["value"] == (
            pytest.approx(1 / 7))
        assert 0 < metrics["prefill_pad_share.kda_generate"]["value"] < 1
        assert metrics["expert_load_max_over_mean.kda_generate"]["value"] >= 1
        # 4 of the tiny router's 16 experts are held: a quarter of the
        # pairs, give or take what 16 experts make of a few hundred tokens
        assert 0.1 < metrics["pairs_held_share.kda_generate"]["value"] < 0.4
        for name in ("prefill_ms_per_image.kda_generate",
                     "decode_ms_per_image.kda_generate",
                     "engine_starved_share.kda_generate"):
            assert metrics[name]["value"] >= 0, name
    else:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_control_and_both_faults_come_out_not_correct():
    proc = _run([os.path.join(HERE, "faulty_kda_generate.py"),
                 "--workload", CELL])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = _last_json(proc)
    assert got["sound"] is True, got["sound_compared"]
    assert got["control"] is False, got["control_compared"]
    assert set(got["faults"]) == {"decay_after_write", "beta_not_doubled"}
    for name, correct in got["faults"].items():
        assert correct is False, (name, got["compared"][name])
        assert got["compared"][name]["rows_malformed"]["value"] == 0


def test_every_seed_gets_the_same_lengths_in_another_order(cell):
    mix = cell.traffic
    assert mix["kind"] == "prompt_frame" and mix["partitions"] == 1
    assert mix["genLength"] == 64 and cell.workload["batchSize"] == 128
    fixed = prompt_traffic.lengths(mix)
    assert len(fixed) == 256 and fixed.min() >= 128
    assert fixed.min() == 129 and fixed.max() == 4068
    assert fixed.sum() == 293102
    assert 1140 <= fixed.mean() <= 1150  # log-uniform: 3968 / ln 32 = 1144.9
    assert len(set(fixed % 128)) > 100  # not rounded to any segment
    # log-uniform: as many rows in each octave (but for the rounding at an
    # octave's edge)
    octaves = [int(((fixed >= lo) & (fixed < 2 * lo)).sum())
               for lo in (128, 256, 512, 1024, 2048)]
    assert sum(octaves) == 256 and all(50 <= n <= 52 for n in octaves)
    vocab = cell.config["vocab_size"]
    a = prompt_traffic.prompt_frame(mix, 2**31 + 5, vocab, vocab)
    b = prompt_traffic.prompt_frame(mix, 7, vocab, vocab)
    assert sorted(map(len, a)) == sorted(map(len, b)) == sorted(fixed)
    assert list(map(len, a)) != list(map(len, b))
    # ids from the vocabulary slice
    assert all(p.max() < vocab and p.min() >= 0 for p in a)
    again = prompt_traffic.prompt_frame(mix, 7, vocab, vocab)
    assert all(np.array_equal(x, y) for x, y in zip(b, again))


def test_the_configuration_is_the_published_one_but_for_reduced(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(catalog) as fh:
        published = next(
            row for row in map(json.loads, fh)
            if row["name"] == "Solar-Open2-250B")
    config = cell.config
    assert config["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items() if config.get(k) != v]
    assert sorted(differs) == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {
        k: published["config"][k] for k in config["reduced"]}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 40, 24576)
    assert config["experts_held"] == [0, 40]
    # the floors of a cut: a whole period and four layers, >= 8 experts,
    # >= 1/8 of the vocabulary
    from chipbench.reference import solar_open2 as reference

    assert reference.layer_types(config) == ["attention", "kda", "kda", "kda"]
    whole = reference.layer_types(dict(config, num_hidden_layers=48))
    assert whole == ["attention", "kda", "kda", "kda"] * 12
    assert config["vocab_size"] * 8 == published["config"]["vocab_size"]
    assert config["n_routed_experts"] * 8 == (
        published["config"]["n_routed_experts"])
    assert set(config["assumed"]) >= {
        "router", "kda_low_rank", "attention_gate", "attention_norms",
        "scales", "gqa_layers", "sampling", "weights"}
    manifest = harness.load_manifest(ROOT)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "solar_open2_250b-generate")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the program reads the file as the reference does
    from sparkdl_tpu.models.solar_open2 import SolarOpen2Config, param_shapes

    cfg = SolarOpen2Config.from_dict(config)
    assert (cfg.routed, cfg.held, cfg.num_hidden_layers) == (320, (0, 40), 4)
    assert cfg.layer_types == ("attention", "kda", "kda", "kda")
    assert param_shapes(cfg) == reference.shapes(config)
    tiny = dict(config, **config["rehearse"])
    small = SolarOpen2Config.from_dict(tiny)
    assert param_shapes(small) == reference.shapes(tiny)
    # the tiny preset keeps both kinds of layer in the published order
    assert small.layer_types == cfg.layer_types
    assert (small.routed, small.held) == (16, (4, 8))


def test_counts_from_shapes(cell):
    config = cell.config
    # the issue's arithmetic: a KDA mixer 137.7 M parameters, an attention
    # mixer 109.05 M, an expert 15.73 M, shared + router 17.04 M
    assert solar_counts.kda_mixer_params(config) == (
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64)
    assert solar_counts.attention_mixer_params(config) == 109_051_904
    assert solar_counts.expert_params(config) == 15_728_640
    assert solar_counts.ffn_params_outside_experts(config) == (
        4096 * 320 + 15_728_640)
    assert solar_counts.pairs_here_per_token(config) == 1.0
    # 3,107 M parameters in the four layers, 6.21 GB; the head 0.20
    assert 6.21e9 < solar_counts.weight_bytes(config) < 6.22e9
    assert solar_counts.head_bytes(config) == 2 * 24576 * 4096
    # a row: three float32 states of 64 x 128 x 128 and nine conv windows
    assert solar_counts.recurrent_bytes_per_row(config) == 3 * (
        4 * 1_048_576 + 2 * 3 * 3 * 8192)
    assert solar_counts.cache_bytes_per_entry(config) == 4096
    # a KDA layer's token: 0.341 GFLOP of products + 7.3 MFLOP of the rule
    assert solar_counts.layer_flops_per_token(config, "kda") == (
        2 * (solar_counts.kda_mixer_params(config) + 17_039_360 + 15_728_640)
        + 7 * 1_048_576)
    # 1.33 GFLOP a token through the four layers, 79% of it in the KDA ones
    token = solar_counts.token_flops(config, keys=0)
    assert 1.32e9 < token < 1.34e9
    assert 0.78 < 3 * solar_counts.layer_flops_per_token(
        config, "kda") / token < 0.80
    step = solar_counts.decode_dispatch(config, 128, 1, visible=1177.0)
    # weights and head once (6.4 GB), 3.3 GB of state read and written,
    # 0.6 GB of cache
    assert 10.2e9 < step["bytes"] < 10.4e9
    assert 0.19e12 < step["flops"] < 0.21e12
    assert solar_counts.decode_dispatch(config, 128, 8, 1177.0)["bytes"] == (
        8 * step["bytes"])
    # a step that read 150 of the 160 held expert matrices: 10 x 31.5 MB less
    assert step["bytes"] - solar_counts.decode_dispatch(
        config, 128, 1, 1177.0, experts_read=150)["bytes"] == (
            10 * 2 * 15_728_640)
    segment = solar_counts.prefill_dispatch(config, [0] * 16, 128)
    assert 2.7e12 < segment["flops"] < 2.8e12
    # weights and head once (6.4 GB), 16 rows' state read and written (0.4)
    assert 6.8e9 < segment["bytes"] < 6.9e9
    later = solar_counts.prefill_dispatch(config, [2048] * 16, 128)
    assert later["flops"] > segment["flops"] and later["bytes"] > segment["bytes"]
    need = solar_counts.needed_flops(
        config, prompt_traffic.lengths(cell.traffic), 64)
    # 293.1 k prompt tokens and 16.1 k decoded ones at ~1.35 GFLOP
    assert 4.0e14 < need < 4.3e14


def test_the_cells_metrics_have_their_files_and_readers(cell):
    names = {entry["name"] for entry, _, _ in cell.per_layer}
    assert names == METRICS
    assert {m["name"] for m in cell.end_to_end} == {"images_per_s", "setup_s"}
    assert all(entry["unit"] == "%" for entry, _, _ in cell.per_layer
               if "roofline" in entry["name"] or "mfu" in entry["name"]
               or "idle" in entry["name"] or "starved" in entry["name"])
    assert all(entry["moves"] == "images_per_s"
               and entry["workloads"] == [CELL]
               for entry, _, _ in cell.per_layer)
    # the readers are the ones the benchmark had: this cell brings none
    readers = {reader.__name__.rsplit(".", 1)[1]
               for _, _, reader in cell.per_layer}
    assert readers == {"mfu", "program_roofline", "span_self_time",
                       "fact_per_unit", "compiles_in_window",
                       "device_idle_share"}
    assert cell.workload["control"] == "fp8"
    assert cell.workload["driver"] == "kda_generate"
    assert set(cell.workload["limits"]) == {
        "rows_out_of_place", "rows_malformed", "tokens_dropped",
        "logprob_gap", "token_regret"}
    manifest = harness.load_manifest(ROOT)
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "solar_open2_250b-generate", "document_frame", 1)
    images = next(m for m in manifest["end_to_end"]
                  if m["name"] == "images_per_s")
    assert images["workloads"][-1] == CELL


def test_the_programs_facts_come_from_the_stages_own_plan(cell):
    from chipbench.drivers import ar_generate, kda_generate

    assert issubclass(kda_generate.Job, ar_generate.Job)
    job = kda_generate.Job(cell, seed=5, rehearse=True, workdir="")
    vocab = job.config["vocab_size"]
    job.prompts = prompt_traffic.prompt_frame(job.mix, 5, vocab, vocab)
    programs = job._programs(experts_read=10.0)
    assert programs["solar_prefill"]["name"] == "jit_solar_prefill"
    assert programs["solar_decode"]["name"] == "jit_solar_decode"
    # 6 rows in batches of 4: 7 decode steps in one dispatch, and each
    # batch's prompts (3-40 tokens) in one prefill dispatch of 4 pairs
    assert len(programs["solar_decode"]["dispatches"]) == 1
    assert len(programs["solar_prefill"]["dispatches"]) == 2
    assert all(d["flops"] > 0 and d["bytes"] > 0
               for p in programs.values() for d in p["dispatches"])
    # a decode step that read fewer expert matrices moved fewer bytes
    fewer = job._programs(experts_read=4.0)["solar_decode"]["dispatches"]
    assert fewer[0]["bytes"] < programs["solar_decode"]["dispatches"][0]["bytes"]
    # the sample holds the longest and the shortest prompt
    lengths = [len(p) for p in job.prompts]
    sample = job.sample()
    assert int(np.argmax(lengths)) in sample and int(np.argmin(lengths)) in sample
