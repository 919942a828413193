"""Driver-contract tests for ``__graft_entry__.dryrun_multichip``.

Round-1 failure mode (VERDICT.md Missing #1): the driver called
``dryrun_multichip(8)`` from a process whose default jax backend was already
initialized (and broken), and the in-process CPU fallback came too late —
arrays still landed on the default device.  These tests run the dryrun from
subprocesses that deliberately do NOT have conftest's forced-CPU virtual
8-device environment, so a regression in the subprocess isolation fails here
rather than only in the driver.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env(**extra):
    env = dict(os.environ)
    # strip conftest's forcing so the child sees a "driver-like" world
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env.update(extra)
    return env


def _run(code, env):
    return subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )


def test_dryrun_after_backend_already_initialized():
    """The exact round-1 trap: the calling process initializes a 1-device
    backend *before* calling dryrun_multichip(8). Must still pass."""
    code = (
        "import jax; "
        "jax.config.update('jax_platforms', 'cpu'); "
        "assert len(jax.devices()) == 1, jax.devices(); "
        "import __graft_entry__ as g; "
        "g.dryrun_multichip(8); "
        "print('CONTRACT-OK')"
    )
    proc = _run(code, _clean_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "CONTRACT-OK" in proc.stdout
    assert "dryrun_multichip OK" in proc.stdout


def test_dryrun_with_default_platform_env():
    """Driver-shaped call: whatever JAX_PLATFORMS the outer env carries
    (tpu in production), dryrun_multichip must not touch that backend —
    the subprocess forces CPU before any jax init."""
    code = (
        "import __graft_entry__ as g; "
        "g.dryrun_multichip(8); "
        "print('CONTRACT-OK')"
    )
    proc = _run(code, _clean_env())
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "CONTRACT-OK" in proc.stdout


def test_dryrun_respects_requested_device_count():
    code = (
        "import __graft_entry__ as g; "
        "g.dryrun_multichip(4); "
        "print('CONTRACT-OK')"
    )
    proc = _run(code, _clean_env())
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "4-device mesh" in proc.stdout
