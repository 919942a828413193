"""The cell ``generate-sdar-prompts``: its rehearsal prints the contract's
last line, its control and both planted faults come out not correct, and its
traffic, counts and reader do what their files say.

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

from chipbench import harness, prompt_traffic, sdar_counts  # noqa: E402
from chipbench.readers import program_roofline  # noqa: E402

CELL = "generate-sdar-prompts"


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
        "logprob_gap", "token_regret", "position_regret"}
    assert line["compiles_in_window"]["backend_compiles"] == 0
    assert line["compiles_in_window"]["engine_cache_miss"] == 0
    if trace:
        metrics = line["metrics"]
        # a CPU run is never written under the name of a device metric
        assert not any("mfu" in m or "roofline" in m or "idle" in m
                       for m in metrics)
        assert metrics["compiles_in_window.generate"]["value"] == 0
        assert 1.25 <= metrics["forwards_per_token.generate"]["value"] <= 1.7
        assert metrics["expert_load_max_over_mean.generate"]["value"] >= 1
        for name in ("block_ms_per_image.generate",
                     "prefill_ms_per_image.generate",
                     "engine_starved_share.generate"):
            assert metrics[name]["value"] >= 0, name
    else:
        assert set(line["metrics"]) == {"images_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())


def test_the_control_and_both_faults_come_out_not_correct():
    proc = _run([os.path.join(HERE, "faulty_generate.py"), "--workload", CELL])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = _last_json(proc)
    assert got["sound"] is True, got["sound_compared"]
    assert got["control"] is False, got["control_compared"]
    assert set(got["faults"]) == {"commit_left_out", "token_altered"}
    for name, correct in got["faults"].items():
        assert correct is False, (name, got["compared"][name])


def test_every_seed_gets_the_same_lengths_in_another_order(cell):
    mix = cell.traffic
    fixed = prompt_traffic.lengths(mix)
    assert len(fixed) == 256 and fixed.min() == 64 and 505 <= fixed.max() <= 512
    assert len(set(fixed % 4)) == 4  # not rounded to the block
    # log-uniform: as many rows in each octave
    assert [int(((fixed >= lo) & (fixed < 2 * lo)).sum())
            for lo in (64, 128, 256)] == [85, 85, 86]
    mask = cell.config["mask_token_id"]
    vocab = cell.config["vocab_size"]
    a = prompt_traffic.prompt_frame(mix, 2**31 + 5, vocab, mask)
    b = prompt_traffic.prompt_frame(mix, 7, vocab, mask)
    assert sorted(map(len, a)) == sorted(map(len, b)) == sorted(fixed)
    assert list(map(len, a)) != list(map(len, b))
    assert all(mask not in p and p.max() < vocab and p.min() >= 0 for p in a)
    again = prompt_traffic.prompt_frame(mix, 7, vocab, mask)
    assert all(np.array_equal(x, y) for x, y in zip(b, again))


def test_the_configuration_is_the_published_one_cut_in_depth_only(cell):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(catalog) as fh:
        published = next(
            row for row in map(json.loads, fh)
            if row["name"] == "SDAR-30B-A3B-Chat")
    config = cell.config
    assert config["source"] == published["source_url"]
    differs = [k for k, v in published["config"].items() if config.get(k) != v]
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 6
    assert config["published"]["num_hidden_layers"] == 48
    assert config["experts_held"] == [0, config["num_experts"]]


def test_counts_from_shapes(cell):
    config = cell.config
    # a layer outside its experts 19.1 M parameters, an expert 4.72 M
    assert sdar_counts.layer_weight_bytes(config, 0) == 2 * 19_140_864
    assert (sdar_counts.layer_weight_bytes(config, 1)
            - sdar_counts.layer_weight_bytes(config, 0)) == 2 * 4_718_592
    # 56.9 M active parameters a token a layer
    assert sdar_counts.layer_matmul_flops_per_token(config) == 2 * 56_885_248
    assert sdar_counts.cache_bytes_per_entry(config) == 2048
    block = sdar_counts.block_dispatch(config, 256, 4, 4, visible=250.0)
    # every expert is touched by 1,024 tokens: all weights read 5 times
    assert 43e9 < block["bytes"] < 45e9 and 6.0e12 < block["flops"] < 6.3e12
    few = sdar_counts.block_dispatch(config, 1, 4, 4, visible=250.0)
    assert few["bytes"] < block["bytes"] / 3  # 32 pairs touch 32 experts
    chunk = sdar_counts.prefill_dispatch(config, 16, 512, 4)
    assert 5.7e12 < chunk["flops"] < 5.9e12 and 7.5e9 < chunk["bytes"] < 7.7e9
    need = sdar_counts.needed_flops(
        config, prompt_traffic.lengths(cell.traffic), 64, 4, 4)
    assert 1.40e14 < need < 1.43e14


def test_the_program_roofline_reader_takes_the_programs_name():
    peaks = {"flops_per_s": {"bfloat16": 100.0}, "hbm_bytes_per_s": 10.0}
    facts = {
        "peaks": peaks,
        "programs": {"a": {"name": "jit_a", "dispatches": [
            {"flops": 100.0, "bytes": 1.0},   # 1 s by operations
            {"flops": 10.0, "bytes": 30.0}]}},  # 3 s by bytes
        "trace": {"programs": {
            "jit_a": {"whole_runs": 4, "whole_seconds": 16.0},
            "jit_b": {"whole_runs": 9, "whole_seconds": 1.0}}},
    }
    args = {"program": "a", "peak": "bfloat16"}
    assert program_roofline.read(facts, args) == pytest.approx(50.0)
    # a program without facts, or without a dispatch in the trace, or a
    # parent without the program at all, reads as nothing and never raises
    assert program_roofline.read(facts, dict(args, program="b")) is None
    assert program_roofline.read(dict(facts, trace={"programs": {}}), args) is None
    assert program_roofline.read(dict(facts, trace=None), args) is None
    assert program_roofline.read({"peaks": peaks}, args) is None
    assert program_roofline.read(dict(facts, peaks=None), args) is None


def test_the_cells_metrics_have_their_files_and_readers(cell):
    names = {entry["name"] for entry, _, _ in cell.per_layer}
    assert names == {
        "mfu.generate", "sdar_block_program_roofline",
        "sdar_prefill_program_roofline", "block_ms_per_image.generate",
        "prefill_ms_per_image.generate", "forwards_per_token.generate",
        "expert_load_max_over_mean.generate", "compiles_in_window.generate",
        "engine_starved_share.generate", "device_idle_share.generate"}
    assert {m["name"] for m in cell.end_to_end} == {"images_per_s", "setup_s"}
    assert all(entry["unit"] == "%" for entry, _, _ in cell.per_layer
               if "roofline" in entry["name"] or "mfu" in entry["name"]
               or "share" in entry["name"])
