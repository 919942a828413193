"""The cell ``generate-granite-prompts``: its rehearsal prints the contract's
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

from chipbench import granite_counts, harness, prompt_traffic  # noqa: E402

CELL = "generate-granite-prompts"


def _run(args, timeout=600):
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
        assert metrics["compiles_in_window.ar_generate"]["value"] == 0
        # 7 decode steps a batch in one dispatch (up to 8 a dispatch)
        assert metrics["dispatches_per_token.ar_generate"]["value"] == (
            pytest.approx(1 / 7))
        assert 0 < metrics["prefill_pad_share.ar_generate"]["value"] < 1
        assert metrics["expert_load_max_over_mean.ar_generate"]["value"] >= 1
        for name in ("prefill_ms_per_image.ar_generate",
                     "decode_ms_per_image.ar_generate",
                     "engine_starved_share.ar_generate"):
            assert metrics[name]["value"] >= 0, name
    else:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_control_and_both_faults_come_out_not_correct():
    proc = _run([os.path.join(HERE, "faulty_ar_generate.py"),
                 "--workload", CELL])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = _last_json(proc)
    assert got["sound"] is True, got["sound_compared"]
    assert got["control"] is False, got["control_compared"]
    assert set(got["faults"]) == {
        "state_at_padded_end", "residual_multiplier_dropped"}
    for name, correct in got["faults"].items():
        assert correct is False, (name, got["compared"][name])
    # the state kept at the padded end leaves each row's first token right
    # (it is read at the right position): only the log-probabilities of what
    # decodes from the state tell, by one limit and not by each
    kept = got["compared"]["state_at_padded_end"]
    assert kept["token_regret"]["value"] <= kept["token_regret"]["limit"]
    assert kept["logprob_gap"]["value"] > kept["logprob_gap"]["limit"]


def test_every_seed_gets_the_same_lengths_in_another_order(cell):
    mix = cell.traffic
    assert mix["kind"] == "prompt_frame" and mix["partitions"] == 1
    assert mix["genLength"] == 64 and cell.workload["batchSize"] == 64
    fixed = prompt_traffic.lengths(mix)
    assert len(fixed) == 128 and fixed.min() >= 128
    assert fixed.min() == 129 and fixed.max() == 2026 and fixed.sum() == 88635
    assert 685 <= fixed.mean() <= 700  # log-uniform: 1920 / ln 16 = 692.5
    assert len(set(fixed % 128)) > 64  # not rounded to any segment
    # log-uniform: as many rows in each octave
    assert [int(((fixed >= lo) & (fixed < 2 * lo)).sum())
            for lo in (128, 256, 512, 1024)] == [32, 32, 32, 32]
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
            if row["name"] == "granite-4.0-h-small")
    config = cell.config
    assert config["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items() if config.get(k) != v]
    assert differs == config["reduced"] == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert config["published"] == {
        k: published["config"][k] for k in config["reduced"]}
    assert (config["num_hidden_layers"], config["num_local_experts"],
            config["vocab_size"]) == (10, 36, 50176)
    assert config["experts_held"] == [0, 36]
    # the floors of a cut: a whole period, >= 8 experts, >= 1/8 vocabulary
    run = config["layer_types"][:10]
    assert run == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["layer_types"][10:20] == run
    assert config["vocab_size"] * 8 >= published["config"]["vocab_size"]
    manifest = harness.load_manifest(ROOT)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "granite_4.0_h_small-generate")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    # the program reads the file as the reference does
    from chipbench.reference import granite_hybrid as reference
    from sparkdl_tpu.models.granite_hybrid import (
        GraniteHybridConfig, param_shapes)

    cfg = GraniteHybridConfig.from_dict(config)
    assert (cfg.routed, cfg.held, cfg.num_hidden_layers) == (72, (0, 36), 10)
    assert param_shapes(cfg) == reference.shapes(config)
    tiny = dict(config, **config["rehearse"])
    assert param_shapes(GraniteHybridConfig.from_dict(tiny)) == (
        reference.shapes(tiny))
    assert set(tiny["layer_types"]) == {"mamba", "attention"}


def test_counts_from_shapes(cell):
    config = cell.config
    # the issue's arithmetic: a Mamba mixer 102.29 M parameters, an
    # attention mixer 41.94 M, an expert 9.44 M, shared + router 19.17 M
    assert granite_counts.mamba_mixer_params(config) == 102_236_160
    assert granite_counts.attention_mixer_params(config) == 41_943_040
    assert granite_counts.expert_params(config) == 9_437_184
    assert granite_counts.ffn_params_outside_experts(config) == 19_169_280
    assert granite_counts.pairs_here_per_token(config) == 5.0
    # 4,551.7 M parameters in the ten layers, 9.10 GB; the embedding 0.41
    assert 9.10e9 < granite_counts.weight_bytes(config) < 9.11e9
    assert granite_counts.embedding_bytes(config) == 2 * 50176 * 4096
    # a row: nine float32 states of 128 x 64 x 128 and nine conv windows
    assert granite_counts.recurrent_bytes_per_row(config) == 9 * (
        4 * 1_048_576 + 2 * 3 * 8448)
    assert granite_counts.cache_bytes_per_entry(config) == 4096
    # a Mamba layer's token: 0.337 GFLOP of products + 4.2 MFLOP of recurrence
    assert granite_counts.layer_flops_per_token(config, "mamba") == (
        2 * (102_236_160 + 19_169_280 + 5 * 9_437_184) + 4 * 1_048_576)
    step = granite_counts.decode_dispatch(config, 64, 1, visible=724.0)
    # weights and embedding once (9.5 GB), 2.4 GB of state read and written
    assert 14.5e9 < step["bytes"] < 14.7e9
    assert 0.23e12 < step["flops"] < 0.25e12
    assert granite_counts.decode_dispatch(config, 64, 8, 724.0)["bytes"] == (
        8 * step["bytes"])
    # a step that read 300 of the 360 held expert matrices: 60 x 18.9 MB less
    assert step["bytes"] - granite_counts.decode_dispatch(
        config, 64, 1, 724.0, experts_read=300)["bytes"] == 60 * 2 * 9_437_184
    segment = granite_counts.prefill_dispatch(config, [0] * 16, 128)
    assert 6.7e12 < segment["flops"] < 6.8e12
    assert 10.7e9 < segment["bytes"] < 10.9e9
    later = granite_counts.prefill_dispatch(config, [1024] * 16, 128)
    assert later["flops"] > segment["flops"] and later["bytes"] > segment["bytes"]
    need = granite_counts.needed_flops(
        config, prompt_traffic.lengths(cell.traffic), 64)
    # 88.6 k prompt tokens and 8.1 k decoded ones at ~3.3 GFLOP
    assert 3.1e14 < need < 3.3e14


def test_the_cells_metrics_have_their_files_and_readers(cell):
    names = {entry["name"] for entry, _, _ in cell.per_layer}
    assert names == {
        "mfu.ar_generate", "granite_prefill_program_roofline",
        "granite_decode_program_roofline", "prefill_ms_per_image.ar_generate",
        "decode_ms_per_image.ar_generate", "prefill_pad_share.ar_generate",
        "dispatches_per_token.ar_generate",
        "expert_load_max_over_mean.ar_generate",
        "compiles_in_window.ar_generate", "engine_starved_share.ar_generate",
        "device_idle_share.ar_generate"}
    assert {m["name"] for m in cell.end_to_end} == {"images_per_s", "setup_s"}
    assert all(entry["unit"] == "%" for entry, _, _ in cell.per_layer
               if "roofline" in entry["name"] or "mfu" in entry["name"]
               or "idle" in entry["name"] or "starved" in entry["name"])
    assert all(entry["moves"] == "images_per_s"
               and entry["workloads"] == [CELL]
               for entry, _, _ in cell.per_layer)
    assert cell.workload["control"] == "fp8"
    assert set(cell.workload["limits"]) == {
        "rows_out_of_place", "rows_malformed", "tokens_dropped",
        "logprob_gap", "token_regret"}


def test_the_programs_facts_come_from_the_stages_own_plan(cell):
    from chipbench.drivers import ar_generate

    job = ar_generate.Job(cell, seed=5, rehearse=True, workdir="")
    vocab = job.config["vocab_size"]
    job.prompts = prompt_traffic.prompt_frame(job.mix, 5, vocab, vocab)
    programs = job._programs(experts_read=10.0)
    assert programs["granite_prefill"]["name"] == "jit_granite_prefill"
    assert programs["granite_decode"]["name"] == "jit_granite_decode"
    # 6 rows in batches of 4: 7 decode steps in one dispatch, and each
    # batch's prompts (3-40 tokens) in one prefill dispatch of 4 pairs
    assert len(programs["granite_decode"]["dispatches"]) == 1
    assert len(programs["granite_prefill"]["dispatches"]) == 2
    assert all(d["flops"] > 0 and d["bytes"] > 0
               for p in programs.values() for d in p["dispatches"])
    # a decode step that read fewer expert matrices moved fewer bytes
    fewer = job._programs(experts_read=4.0)["granite_decode"]["dispatches"]
    assert fewer[0]["bytes"] < programs["granite_decode"]["dispatches"][0]["bytes"]
