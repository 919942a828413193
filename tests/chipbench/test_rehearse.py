"""``run.py --rehearse`` drives each driver at tiny shapes on the CPU and
prints the contract's last line; without ``--rehearse`` and without a TPU it
exits non-zero and prints no metric; with the timed path broken underneath,
``correct`` comes out false.

Every run is a subprocess with ONE CPU device (the program then takes its
one-chip path); nothing here describes a TPU topology.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _env():
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", KERAS_BACKEND="jax",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    return env


def _run(args, cwd=ROOT, timeout=600):
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=_env(), timeout=timeout,
        capture_output=True, text=True,
    )


def _last_json(proc):
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert lines, proc.stderr[-2000:]
    return json.loads(lines[-1])


with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    CELLS = {w["name"] for w in json.load(_fh)["workloads"]}


def _in_manifest(params):
    """A driver whose cell BENCHMARK.json does not hold yet (the fit driver,
    PERF.md section 7) is rehearsed from the PR that adds its entries."""
    return [p for p in params if p.values[0] in CELLS]


REHEARSALS = _in_manifest([
    pytest.param("featurize-cached", 1, id="featurize-cached-traced"),
    pytest.param("featurize-files", 0, id="featurize-files"),
    pytest.param("finetune-resnet50-files", 0, id="fit"),
])


@pytest.mark.parametrize("cell, trace", REHEARSALS)
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    proc = _run(["chipbench/run.py", "--workload", cell, "--seed",
                 str(2**31 + 12345), "--seconds", "1", "--trace", str(trace),
                 "--rehearse"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = _last_json(proc)
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert list(line)[-1] == "compared"  # the numbers compared come last
    assert line["rehearse"] is True and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    for name, entry in line["compared"].items():
        assert set(entry) == {"value", "limit"}, name
        # each number compared stands beside its limit at the end of stderr
        assert f"compared {name}:" in proc.stderr[-4000:]
    if trace:
        # a CPU run is never written under the name of a device metric
        assert not any(
            "mfu" in m or "roofline" in m or "idle" in m
            for m in line["metrics"])
        assert "busy_s" not in line["device"]
    else:
        assert "setup_s" in line["metrics"]
        assert len(line["metrics"]) == 2
        assert all(v["value"] > 0 for v in line["metrics"].values())
    if cell.startswith("featurize"):
        assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("extra", [[], ["--trace", "1"]], ids=["t0", "t1"])
def test_without_a_tpu_it_refuses_and_prints_no_metric(extra):
    proc = _run(["chipbench/run.py", "--workload", "featurize-cached",
                 "--seed", "1", "--seconds", "1", *extra])
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and "images_per_s" not in proc.stdout
    assert "not a TPU" in proc.stderr


def test_in_a_directory_with_only_the_benchmark_it_refuses(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(["chipbench/run.py", "--workload", "featurize-cached",
                 "--seed", "1", "--seconds", "1", "--rehearse"], cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


FAULTS = _in_manifest([
    pytest.param("featurize-cached", {"answer_altered", "half_left_out"},
                 id="featurize"),
    pytest.param("finetune-resnet50-files",
                 {"state_unchanged", "half_left_out", "answer_altered"},
                 id="fit", marks=pytest.mark.slow),
])


@pytest.mark.parametrize("cell, faults", FAULTS)
def test_the_control_and_every_fault_come_out_not_correct(cell, faults):
    proc = _run([os.path.join(HERE, "faulty_run.py"), "--workload", cell])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = _last_json(proc)
    assert got["sound"] is True, got["sound_compared"]
    assert got["control"] is False, got["control_compared"]
    assert set(got["faults"]) == faults
    for name, correct in got["faults"].items():
        assert correct is False, (name, got["compared"][name])
