"""Bounded out-of-process liveness probes.

For a caller that does NOT hold the device and is about to create a
client of its own: the native-stack tests run JAX on the CPU platform and
probe the native PJRT runner's client creation in a child first, turning
a creation that never returns into a loud bounded diagnostic.  A process
that already holds the chip must not use this for the chip — the child
could not open it (see :func:`sparkdl_tpu.resilience.watchdog
.check_device` for that case).

Deliberately jax-free: the probe must be importable and runnable before
any in-process device initialization.
"""

from __future__ import annotations

import subprocess
import sys


def bounded_subprocess_probe(code: str, timeout_s: int) -> "tuple[bool, str]":
    """Run ``code`` in a fresh interpreter with a hard timeout.

    Returns ``(ok, message)``: on success the probe's stdout, on
    timeout/failure a diagnostic (stderr tail).  One implementation so
    the kill/timeout/truncation behavior cannot drift between callers.
    """
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return False, f"probe hung > {timeout_s}s"
    if proc.returncode != 0:
        return False, (proc.stderr or proc.stdout).strip()[-200:]
    return True, proc.stdout.strip()
