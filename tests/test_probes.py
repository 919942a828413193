"""The bounded liveness probe (``utils/probes.py``) and the opt-in
profiler wrapper (``utils/profiler.py``) — the two observability
helpers older than ``sparkdl_tpu/obs`` that the subsystem builds on.

The probe turns a client creation that never returns into a bounded
loud failure; these tests pin each of its three exits (success, nonzero,
timeout) plus the diagnostic-truncation contract.  The profiler tests
pin the no-env no-op and the first-entrant-wins reentrancy rule —
without importing jax (``maybe_trace`` must stay cheap to call from
the hot loop when profiling is off).
"""

import os

import pytest

from sparkdl_tpu.utils import profiler
from sparkdl_tpu.utils.probes import bounded_subprocess_probe


class TestBoundedSubprocessProbe:
    def test_success_returns_stdout(self):
        ok, msg = bounded_subprocess_probe(
            "print('alive on 8 devices')", timeout_s=60
        )
        assert ok
        assert msg == "alive on 8 devices"

    def test_failure_returns_stderr_diagnostic(self):
        ok, msg = bounded_subprocess_probe(
            "raise RuntimeError('no backend: plugin refused')", timeout_s=60
        )
        assert not ok
        assert "no backend: plugin refused" in msg

    def test_failure_prefers_stderr_but_falls_back_to_stdout(self):
        ok, msg = bounded_subprocess_probe(
            "import sys; print('detail on stdout'); sys.exit(3)",
            timeout_s=60,
        )
        assert not ok
        assert "detail on stdout" in msg

    def test_hang_is_bounded_and_says_so(self):
        ok, msg = bounded_subprocess_probe(
            "import time; time.sleep(60)", timeout_s=1
        )
        assert not ok
        assert "probe hung > 1s" in msg

    def test_diagnostic_is_truncated_to_tail(self):
        # a crashing probe can dump pages; callers embed the message in
        # status()/bench JSON so it is capped at the last 200 chars
        ok, msg = bounded_subprocess_probe(
            "raise RuntimeError('x' * 2000)", timeout_s=60
        )
        assert not ok
        assert len(msg) <= 200

    def test_probe_is_importable_without_jax(self):
        """The probe must run before any in-process device init — a jax
        import at probe time could itself wedge."""
        ok, msg = bounded_subprocess_probe(
            "import sys\n"
            "import sparkdl_tpu.utils.probes\n"
            "assert 'jax' not in sys.modules, 'probes.py imported jax'\n"
            "print('jax-free')",
            timeout_s=120,
        )
        assert ok, msg
        assert msg == "jax-free"


class TestProfiler:
    def test_maybe_trace_is_noop_without_env(self, monkeypatch):
        monkeypatch.delenv("SPARKDL_PROFILE_DIR", raising=False)
        with profiler.maybe_trace():
            pass  # nullcontext: no jax import, no capture dir

    def test_maybe_trace_env_selects_dir(self, monkeypatch):
        # don't start a real capture — just pin the routing decision
        captured = {}

        def fake_trace(log_dir):
            captured["dir"] = log_dir
            from contextlib import nullcontext
            return nullcontext()

        monkeypatch.setattr(profiler, "trace", fake_trace)
        monkeypatch.setenv("SPARKDL_PROFILE_DIR", "/tmp/prof-here")
        with profiler.maybe_trace():
            pass
        assert captured["dir"] == "/tmp/prof-here"
        # explicit argument beats the env var
        with profiler.maybe_trace("/tmp/explicit"):
            pass
        assert captured["dir"] == "/tmp/explicit"

    def test_trace_reentrancy_degrades_to_noop(self, tmp_path):
        """Only one jax profiler capture may exist per process: the
        first entrant wins, nested entry runs untraced, and the flag
        resets so a later capture can start."""
        import jax  # noqa: F401  (profiler.trace imports it lazily)

        with profiler.trace(str(tmp_path / "a")):
            assert profiler._trace_active
            with profiler.trace(str(tmp_path / "b")):
                pass  # no-op, must not raise
            assert profiler._trace_active
        assert not profiler._trace_active
        # the lock released: a fresh capture is allowed again
        with profiler.trace(str(tmp_path / "c")):
            assert profiler._trace_active
        assert not profiler._trace_active
        assert os.path.isdir(tmp_path / "a")

    def test_annotate_inside_trace(self, tmp_path):
        with profiler.trace(str(tmp_path / "t")):
            with profiler.annotate("decode_batch"):
                pass
