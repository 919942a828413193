"""The runnable examples must stay runnable — each is a documented user
flow (README "examples/" pointer), so rot there is a user-facing break.

Each example runs in a fresh subprocess on the virtual CPU mesh (the same
forced-platform pattern as ``__graft_entry__.dryrun_multichip``) and must
exit 0 after printing its success line.
"""

import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)


def _run_example(name: str, timeout: int = 900, extra_env=None) -> str:
    env = dict(os.environ)
    env.update(extra_env or {})
    env["JAX_PLATFORMS"] = "cpu"
    env["KERAS_BACKEND"] = "jax"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    code = (
        "import runpy; runpy.run_path("
        f"{os.path.join(_REPO, 'examples', name)!r}, run_name='__main__')"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"{name} failed (rc={proc.returncode}):\n{proc.stderr[-4000:]}"
    )
    return proc.stdout


@pytest.mark.slow
def test_transfer_learning_example():
    out = _run_example("transfer_learning.py")
    assert "transfer-learning accuracy" in out
    assert "reloaded pipeline reproduces accuracy" in out


@pytest.mark.slow
def test_udf_serving_example():
    out = _run_example("udf_serving.py")
    assert "SQL-UDF scored 12 rows" in out
    assert "centered means of first rows" in out


@pytest.mark.slow
def test_distributed_finetune_example(tmp_path):
    out = _run_example(
        "distributed_finetune.py",
        extra_env={"SPARKDL_DEMO_DIR": str(tmp_path / "demo")},
    )
    assert "fitMultiple trained 2 models" in out
    assert "train accuracy" in out


@pytest.mark.slow
def test_online_serving_example():
    out = _run_example("online_serving.py")
    assert "online serving OK" in out
    assert "served 24 requests" in out


@pytest.mark.slow
def test_tracing_example():
    out = _run_example("tracing.py")
    assert "tracing OK" in out
    assert "captured" in out and "estimator.fit" in out
    assert "request spans coalesced into" in out


def test_block_diffusion_example():
    """Tiny and quick (one process, ~15 s): not marked slow."""
    out = _run_example("block_diffusion.py", timeout=300)
    assert "generated 48 tokens for 6 prompts" in out
    assert out.count("mean confidence") == 6


def test_ar_generate_example():
    """Tiny and quick (one process, ~20 s): not marked slow."""
    out = _run_example("ar_generate.py", timeout=300)
    assert "generated 48 tokens for 6 prompts" in out
    assert out.count("mean confidence") == 6


@pytest.mark.slow
def test_sql_analytics_example():
    out = _run_example("sql_analytics.py")
    assert "sql analytics OK" in out


@pytest.mark.slow
def test_streaming_scoring_example():
    out = _run_example("streaming_scoring.py")
    assert "streaming scoring OK" in out
    assert "stop_reason=preempted" in out
    assert "scored 60 events exactly once across a SIGTERM" in out


@pytest.mark.slow
def test_continuous_query_example():
    out = _run_example("continuous_query.py")
    assert "continuous query OK" in out
    assert "stop_reason=preempted" in out
    assert "closed 20 windows exactly once across a SIGTERM" in out
    assert "2 late rows preserved in the side output" in out


@pytest.mark.slow
def test_telemetry_example():
    out = _run_example("telemetry.py")
    assert "telemetry plane up at http://127.0.0.1:" in out
    assert "SLO breach detected: serving.demo.latency ->" in out
    assert "flight recorder dump:" in out
    assert "telemetry example complete" in out
