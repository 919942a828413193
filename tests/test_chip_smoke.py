"""``chip_smoke.py --rehearse`` end to end on the CPU platform.

The script is the quickest proof that the system starts on the chip; its
rehearsal option runs the same phases, through the same entry points, at
tiny shapes on whatever platform JAX finds — here the CPU — and is what CI
runs in place of a chip.  One test, two children in turn: a clean run, and
a run whose first phase is made to fail through the fault-injection plan.
"""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rehearse(**extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), "--rehearse"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=1200,
    )
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.strip()]
    return proc, lines


def test_rehearsal_runs_every_phase_in_order_and_a_failed_phase_fails_it():
    proc, lines = _rehearse()
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert [l["phase"] for l in lines[:-1]] == [
        "setup", "featurize", "udf", "serve", "fit", "fleet",
    ]
    assert all(l["ok"] for l in lines)
    # the last line is the contract's, names the platform it really ran
    # on, and carries nothing else
    assert lines[-1] == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    by_phase = {l["phase"]: l for l in lines[:-1]}
    for phase in ("featurize", "udf", "serve", "fit", "fleet"):
        assert by_phase[phase]["err"] <= by_phase[phase]["tol"], phase
    assert by_phase["featurize"]["shape"] == [16, 2048]
    assert by_phase["serve"]["compiles_after_warmup"] == 0
    assert by_phase["serve"]["device_probe"]["ok"] is True
    # the replica hosted the endpoint the serve phase compiled, from the
    # cache, in a process of its own; the supervisor's never touched a
    # backend and nothing was left behind
    (replica,) = by_phase["fleet"]["replicas"]
    assert replica["compiles"] == 0
    assert set(replica["programs"].values()) == {"disk"}
    assert by_phase["fleet"]["exit_codes"] == {"replica-0": 0}
    assert by_phase["fleet"]["shm_segments_left"] == []
    assert by_phase["fleet"]["parent_backend_initialised"] is False

    # a phase that fails is a non-zero exit, not a warning: the first
    # decode chunk of the featurize phase raises, and nothing runs after it
    plan = json.dumps([{"site": "data.map", "error": "permanent", "at": 1}])
    proc, lines = _rehearse(SPARKDL_FAULT_PLAN=plan)
    assert proc.returncode != 0
    assert [l.get("phase") for l in lines[:-1]] == ["setup"]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"


def test_without_the_rehearsal_option_a_host_without_a_chip_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "refusing to run" in proc.stderr
