"""The fault-tolerance subsystem: taxonomy, retry/backoff/deadline,
circuit breaking, watchdogged device calls, preemption delivery, and the
deterministic fault-injection harness — plus its integrations into the
data pipeline and online serving.

Acceptance contracts pinned here:

(a) an injected transient device error is retried to success, with the
    backoff counted in ``resilience.retries``;
(b) a permanent error fails FAST with its typed class — zero retries;
(c) an injected stall trips the watchdog within the hard timeout
    instead of hanging the caller;
(d) (in ``test_fault_injection.py``) a simulated preemption mid-epoch
    checkpoints and a re-fit resumes to bit-identical weights.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from sparkdl_tpu.resilience import (
    CircuitBreaker,
    CircuitOpen,
    Deadline,
    DeadlineExceeded,
    DeviceUnresponsive,
    FaultPlan,
    PermanentError,
    Preempted,
    RetryPolicy,
    TransientError,
    active_plan,
    classify,
    is_transient,
    preemption_scope,
    request_preemption,
    watchdogged,
)
from sparkdl_tpu.resilience import errors as rerrors
from sparkdl_tpu.resilience import inject
from sparkdl_tpu.resilience.inject import (
    InjectedPermanentError,
    InjectedTransientError,
)
from sparkdl_tpu.resilience.watchdog import check_device
from sparkdl_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def no_sleep(_):
    """Injectable RetryPolicy sleep: record nothing, wait nothing."""


def fast_policy(**kw):
    return RetryPolicy(base_delay_s=0.001, sleep=no_sleep, **kw)


# ---------------------------------------------------------------------------
# taxonomy / classification
# ---------------------------------------------------------------------------


class TestClassify:
    def test_taxonomy_members_answer_for_themselves(self):
        assert classify(TransientError("x")) is TransientError
        assert classify(PermanentError("x")) is PermanentError
        assert classify(DeviceUnresponsive("x")) is PermanentError
        assert classify(DeadlineExceeded("x")) is PermanentError
        assert classify(CircuitOpen("x")) is TransientError

    def test_repo_exceptions_participate_via_inheritance(self):
        from sparkdl_tpu.image.imageIO import ImageDecodeError
        from sparkdl_tpu.serving.errors import (
            DeadlineExceeded as ServingDeadline,
            ServerClosed,
            ServerOverloaded,
        )

        # corrupt bytes don't heal on retry
        assert not is_transient(ImageDecodeError("f.png"))
        # shed at admission: server alive, retry elsewhere/later
        assert is_transient(ServerOverloaded("shed"))
        assert not is_transient(ServingDeadline("expired"))
        assert not is_transient(ServerClosed("closed"))
        # serving's DeadlineExceeded IS the resilience one (one type to
        # catch at either layer)
        assert issubclass(ServingDeadline, DeadlineExceeded)

    def test_xla_status_words_by_type_name(self):
        # matched by exception type NAME so the taxonomy never imports jax
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        assert is_transient(XlaRuntimeError("RESOURCE_EXHAUSTED: hbm oom"))
        assert is_transient(XlaRuntimeError("UNAVAILABLE: socket closed"))
        assert not is_transient(XlaRuntimeError("INVALID_ARGUMENT: shape"))
        # no status word at all = the torn-connection shape
        assert is_transient(XlaRuntimeError("connection reset mid-stream"))
        # same message on an unknown type stays permanent (fail-fast)
        assert not is_transient(RuntimeError("UNAVAILABLE: socket closed"))

    def test_os_error_split(self):
        assert not is_transient(FileNotFoundError("gone"))
        assert not is_transient(PermissionError("denied"))
        assert is_transient(ConnectionError("reset"))
        assert is_transient(TimeoutError("slow"))
        # residual OSError (EIO, ENOSPC...) = transient I/O
        assert is_transient(OSError("I/O error"))

    def test_unknown_is_permanent_and_register_overrides(self):
        class VendorBlip(Exception):
            pass

        assert not is_transient(VendorBlip("burp"))
        rerrors.register(VendorBlip, transient=True)
        try:
            assert is_transient(VendorBlip("burp"))
        finally:
            rerrors._REGISTERED.remove((VendorBlip, True))

    def test_error_class_is_leaf_type_name(self):
        assert rerrors.error_class(DeviceUnresponsive("x")) == (
            "DeviceUnresponsive"
        )
        assert rerrors.error_class(None) == "None"


# ---------------------------------------------------------------------------
# RetryPolicy / Deadline
# ---------------------------------------------------------------------------


class TestRetryPolicy:
    def test_transient_retried_to_success_with_metered_backoff(self):
        """Acceptance (a): transient fault -> backoff -> success, with
        the retries counted in ``resilience.retries``."""
        delays = []
        policy = RetryPolicy(
            max_attempts=4, base_delay_s=0.05, jitter=0.0,
            sleep=delays.append,
        )
        attempts = {"n": 0}

        def flaky():
            attempts["n"] += 1
            if attempts["n"] < 3:
                raise InjectedTransientError("device busy")
            return "landed"

        assert policy.call(flaky) == "landed"
        assert attempts["n"] == 3
        # exponential: 0.05, 0.10 (jitter disabled for exactness)
        assert delays == pytest.approx([0.05, 0.10])
        assert metrics.counter("resilience.retries").value == 2
        assert metrics.counter("resilience.retry_exhausted").value == 0

    def test_permanent_fails_fast_typed(self):
        """Acceptance (b): permanent error -> ONE attempt, typed class
        intact, zero retries metered."""
        attempts = {"n": 0}

        def doomed():
            attempts["n"] += 1
            raise InjectedPermanentError("bad request")

        with pytest.raises(InjectedPermanentError):
            fast_policy(max_attempts=5).call(doomed)
        assert attempts["n"] == 1
        assert metrics.counter("resilience.retries").value == 0

    def test_exhaustion_raises_last_underlying_error(self):
        def always(n={"i": 0}):
            n["i"] += 1
            raise InjectedTransientError(f"blip {n['i']}")

        with pytest.raises(InjectedTransientError, match="blip 3"):
            fast_policy(max_attempts=3).call(always)
        assert metrics.counter("resilience.retries").value == 2
        assert metrics.counter("resilience.retry_exhausted").value == 1

    def test_jitter_is_seeded_and_deterministic(self):
        p = RetryPolicy(max_attempts=5, jitter=0.5, seed=7, sleep=no_sleep)
        assert list(p.delays()) == list(p.delays())
        q = RetryPolicy(max_attempts=5, jitter=0.5, seed=8, sleep=no_sleep)
        assert list(p.delays()) != list(q.delays())

    def test_budget_caps_total_sleep(self):
        slept = []
        policy = RetryPolicy(
            max_attempts=10, base_delay_s=1.0, multiplier=1.0, jitter=0.0,
            budget_s=2.5, sleep=slept.append,
        )

        def always():
            raise InjectedTransientError("blip")

        with pytest.raises(InjectedTransientError):
            policy.call(always)
        assert sum(slept) <= 2.5 + 1e-9
        assert metrics.counter("resilience.retry_exhausted").value == 1

    def test_deadline_clips_and_stops_retries(self):
        clock = {"t": 0.0}
        deadline = Deadline(5.0, clock=lambda: clock["t"], what="req")

        def sleeper(d):
            clock["t"] += d

        policy = RetryPolicy(
            max_attempts=50, base_delay_s=2.0, multiplier=1.0, jitter=0.0,
            sleep=sleeper,
        )

        def always():
            raise InjectedTransientError("blip")

        with pytest.raises(DeadlineExceeded, match="req"):
            policy.call(always, deadline=deadline)
        # 2.0 + 2.0 + 1.0(clipped) = 5.0, then the deadline gate raises
        assert clock["t"] == pytest.approx(5.0)

    def test_expired_deadline_raises_typed_before_first_attempt(self):
        deadline = Deadline.after(-1.0, what="already late")
        with pytest.raises(DeadlineExceeded, match="already late"):
            fast_policy().call(lambda: "never", deadline=deadline)

    def test_wrap_bakes_policy_into_plain_callable(self):
        n = {"v": 0}

        def flaky(x):
            n["v"] += 1
            if n["v"] < 2:
                raise InjectedTransientError("blip")
            return x * 2

        wrapped = fast_policy().wrap(flaky)
        assert wrapped(21) == 42


class TestDeadline:
    def test_remaining_and_expiry_with_fake_clock(self):
        clock = {"t": 100.0}
        d = Deadline.after(3.0, clock=lambda: clock["t"], what="fetch")
        assert d.remaining() == pytest.approx(3.0)
        assert not d.expired()
        d.check()  # no raise
        clock["t"] += 3.5
        assert d.expired()
        with pytest.raises(DeadlineExceeded, match="fetch"):
            d.check()

    def test_unbounded(self):
        d = Deadline.after(None)
        assert d.remaining() is None and not d.expired()
        d.check()


# ---------------------------------------------------------------------------
# CircuitBreaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def make(self, **kw):
        clock = {"t": 0.0}
        kw.setdefault("failure_threshold", 3)
        kw.setdefault("recovery_s", 10.0)
        br = CircuitBreaker(
            name=kw.pop("name", "dep"), clock=lambda: clock["t"], **kw
        )
        return br, clock

    def test_trips_after_consecutive_failures_only(self):
        br, _ = self.make()
        for _ in range(2):
            br.record_failure()
        br.record_success()  # success resets the consecutive count
        for _ in range(2):
            br.record_failure()
        assert br.state == "closed"
        br.record_failure()
        assert br.state == "open"
        assert metrics.counter("resilience.breaker_trips").value == 1

    def test_open_rejects_then_half_open_probe_recloses(self):
        br, clock = self.make(name="dep2")
        for _ in range(3):
            br.record_failure()
        assert not br.allow()
        with pytest.raises(CircuitOpen):
            br.check()
        assert metrics.counter("resilience.breaker_rejections").value >= 2
        clock["t"] += 10.0
        assert br.allow()  # the half-open probe slot
        assert not br.allow()  # only half_open_max=1 probe in flight
        br.record_success()
        assert br.state == "closed"
        assert br.allow()

    def test_half_open_failure_reopens(self):
        br, clock = self.make(name="dep3")
        for _ in range(3):
            br.record_failure()
        clock["t"] += 10.0
        assert br.allow()
        br.record_failure()  # the probe failed
        assert br.state == "open"
        assert not br.allow()

    def test_call_wraps_outcomes(self):
        br, _ = self.make(name="dep4", failure_threshold=1)
        with pytest.raises(ValueError):
            br.call(lambda: (_ for _ in ()).throw(ValueError("boom")))
        with pytest.raises(CircuitOpen):
            br.call(lambda: "unreached")
        snap = br.snapshot()
        assert snap["state"] == "open"
        assert snap["consecutive_failures"] == 1

    def test_state_gauge_tracks_transitions(self):
        br, clock = self.make(name="dep5", failure_threshold=1)
        g = metrics.gauge("resilience.breaker_state.dep5")
        assert g.value == 0.0
        br.record_failure()
        assert g.value == 2.0
        clock["t"] += 10.0
        br.allow()
        assert g.value == 1.0
        br.record_success()
        assert g.value == 0.0


# ---------------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------------


class TestWatchdog:
    def test_fast_call_passes_through(self):
        assert watchdogged(lambda: 42, hard_timeout_s=30.0) == 42

    def test_worker_exception_reaches_the_caller(self):
        def boom():
            raise InjectedPermanentError("from worker")

        with pytest.raises(InjectedPermanentError, match="from worker"):
            watchdogged(boom, hard_timeout_s=30.0)

    def test_injected_stall_trips_hard_timeout_not_a_hang(self):
        """Acceptance (c): a stalled device call raises the typed
        DeviceUnresponsive within the hard timeout — the caller's
        thread never blocks on the wedged work."""
        plan = FaultPlan().add("watchdog.stall_test", stall_s=15.0, at=1)
        start = time.monotonic()
        with active_plan(plan):
            with pytest.raises(DeviceUnresponsive, match="hard timeout"):
                watchdogged(
                    lambda: "never lands",
                    soft_timeout_s=0.05,
                    hard_timeout_s=0.6,
                    name="stall_test",
                )
        elapsed = time.monotonic() - start
        assert elapsed < 10.0, f"watchdog took {elapsed:.1f}s to give up"
        assert metrics.counter(
            "resilience.watchdog_hard_timeouts"
        ).value == 1
        assert metrics.counter(
            "resilience.watchdog_soft_timeouts"
        ).value == 1

    def test_check_device_structured_record(self):
        # JAX_PLATFORMS=cpu (conftest): the held device answers "cpu"
        rec = check_device(timeout_s=60)
        assert rec == {"ok": True, "error_class": None, "detail": "cpu"}

    def test_check_device_failure_has_error_class(self):
        """A dispatch that does not return is a typed record, in bounded
        time — not a raise and not a hang."""
        plan = FaultPlan().add("watchdog.device_probe", stall_s=15.0, at=1)
        start = time.monotonic()
        with active_plan(plan):
            rec = check_device(timeout_s=0.5)
        assert time.monotonic() - start < 10.0
        assert rec["ok"] is False
        assert rec["error_class"] == "DeviceUnresponsive"

    def test_check_device_starts_no_process(self, monkeypatch):
        """The caller holds the chip; a child that asked for it would
        fail where the parent is healthy.  ``status(probe_device=True)``
        therefore must never start one."""
        from sparkdl_tpu.serving import ModelServer

        def refuse(*args, **kwargs):
            raise AssertionError(f"child process started: {args!r}")

        for mod, name in (
            (subprocess, "Popen"), (os, "fork"), (os, "posix_spawn"),
            (os, "system"),
        ):
            monkeypatch.setattr(mod, name, refuse)
        with ModelServer() as server:
            server.register(
                "m", lambda x: x, item_shape=(2,), compile=False
            )
            status = server.status(probe_device=True)
        assert status["device"] == {
            "ok": True, "error_class": None, "detail": "cpu",
        }
        assert status["healthy"] is True


# ---------------------------------------------------------------------------
# fault-injection harness
# ---------------------------------------------------------------------------


class TestInject:
    def test_no_plan_is_a_no_op(self):
        inject.fire("anything")  # must not raise

    def test_nth_call_trigger_is_deterministic(self):
        plan = FaultPlan().add("s", error="transient", at=2, times=2)
        for _ in range(2):  # a reused plan refires identically
            with active_plan(plan):
                inject.fire("s")  # 1st: clean
                for _ in range(2):  # 2nd, 3rd: fault
                    with pytest.raises(InjectedTransientError):
                        inject.fire("s")
                inject.fire("s")  # 4th: clean again
                assert plan.count("s") == 4

    def test_probabilistic_trigger_is_seeded(self):
        def run(seed):
            plan = FaultPlan(seed=seed).add("s", error="transient", p=0.5)
            hits = []
            with active_plan(plan):
                for i in range(64):
                    try:
                        inject.fire("s")
                        hits.append(False)
                    except InjectedTransientError:
                        hits.append(True)
            return hits

        assert run(3) == run(3)
        assert run(3) != run(4)

    def test_error_shorthands(self):
        from sparkdl_tpu.image.imageIO import ImageDecodeError

        cases = {
            "transient": InjectedTransientError,
            "permanent": InjectedPermanentError,
            "device": TransientError,
            "decode": ImageDecodeError,
        }
        for shorthand, exc_type in cases.items():
            plan = FaultPlan().add("s", error=shorthand, at=1)
            with active_plan(plan), pytest.raises(exc_type):
                inject.fire("s")

    def test_rule_validation(self):
        with pytest.raises(ValueError, match="exactly one action"):
            FaultPlan().add("s", at=1)
        with pytest.raises(ValueError, match="exactly one action"):
            FaultPlan().add("s", error="transient", stall_s=1.0, at=1)
        with pytest.raises(ValueError, match="exactly one trigger"):
            FaultPlan().add("s", error="transient")
        with pytest.raises(ValueError, match="exactly one trigger"):
            FaultPlan().add("s", error="transient", at=1, p=0.5)

    def test_from_json_and_env_hook(self, monkeypatch):
        text = (
            '[{"site": "a", "error": "transient", "at": 1},'
            ' {"site": "b", "kill": true, "at": 2}]'
        )
        plan = FaultPlan.from_json(text)
        assert [r["site"] for r in plan.describe()] == ["a", "b"]
        monkeypatch.setenv(inject.ENV_VAR, text)
        env_plan = inject.plan_from_env()
        with active_plan(env_plan), pytest.raises(InjectedTransientError):
            inject.fire("a")
        monkeypatch.setenv(inject.ENV_VAR, '{"not": "a list"}')
        with pytest.raises(ValueError, match="JSON list"):
            inject.plan_from_env()

    def test_env_plan_installs_at_import_in_fresh_process(self, tmp_path):
        """The subprocess hook: a worker started with SPARKDL_FAULT_PLAN
        set runs under the plan with no code changes."""
        code = (
            "from sparkdl_tpu.resilience import inject\n"
            "try:\n"
            "    inject.fire('boot')\n"
            "    print('CLEAN')\n"
            "except Exception as e:\n"
            "    print('FAULT', type(e).__name__)\n"
        )
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            SPARKDL_FAULT_PLAN=(
                '[{"site": "boot", "error": "transient", "at": 1}]'
            ),
        )
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, capture_output=True,
            text=True, timeout=120, cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            ),
        )
        assert "FAULT InjectedTransientError" in out.stdout, out.stdout

    def test_metrics_count_injected_faults(self):
        plan = FaultPlan().add("s", error="transient", at=1)
        with active_plan(plan):
            with pytest.raises(InjectedTransientError):
                inject.fire("s")
        assert metrics.counter("resilience.injected_faults").value == 1


# ---------------------------------------------------------------------------
# preemption delivery
# ---------------------------------------------------------------------------


class TestPreemption:
    def test_flag_then_safe_point_raise(self):
        with preemption_scope(install_signal_handler=False) as token:
            token.check()  # clean
            request_preemption("scheduler says so")
            assert token.requested
            with pytest.raises(Preempted, match="scheduler says so"):
                token.check()
        assert metrics.counter("resilience.preemptions").value == 1

    def test_no_scope_raises_directly(self):
        with pytest.raises(Preempted):
            request_preemption()

    def test_innermost_scope_wins(self):
        with preemption_scope(install_signal_handler=False) as outer:
            with preemption_scope(install_signal_handler=False) as inner:
                request_preemption()
                assert inner.requested and not outer.requested

    def test_preempted_escapes_broad_except_exception(self):
        try:
            try:
                raise Preempted("shutdown")
            except Exception:  # the handler that must NOT swallow it
                pytest.fail("except Exception swallowed Preempted")
        except Preempted:
            pass

    def test_sigterm_flags_token_and_disposition_restored(self):
        before = signal.getsignal(signal.SIGTERM)
        with preemption_scope() as token:
            signal.raise_signal(signal.SIGTERM)
            assert token.requested
            with pytest.raises(Preempted, match="SIGTERM"):
                token.check()
        assert signal.getsignal(signal.SIGTERM) == before


# ---------------------------------------------------------------------------
# integrations: data pipeline
# ---------------------------------------------------------------------------


class TestDataIntegration:
    def test_map_retries_injected_transients(self):
        from sparkdl_tpu.data import Dataset

        plan = FaultPlan().add("data.map", error="transient", at=2, times=2)
        ds = Dataset.from_items([1, 2, 3]).map(
            lambda v: v * 10, retry=fast_policy(max_attempts=5)
        )
        with active_plan(plan):
            assert list(ds) == [10, 20, 30]
        # the faulted item re-fires the site on each retry
        assert plan.count("data.map") == 5
        assert metrics.counter("resilience.retries").value == 2

    def test_map_threaded_retries_too(self):
        from sparkdl_tpu.data import Dataset

        plan = FaultPlan().add("data.map", error="transient", at=1)
        ds = Dataset.from_items(list(range(8))).map(
            lambda v: v + 1, num_workers=2,
            retry=fast_policy(max_attempts=3),
        )
        with active_plan(plan):
            assert list(ds) == list(range(1, 9))

    def test_map_permanent_decode_error_fails_fast(self):
        from sparkdl_tpu.data import Dataset

        plan = FaultPlan().add("data.map", error="decode", at=1)
        ds = Dataset.from_items([1]).map(
            lambda v: v, retry=fast_policy(max_attempts=5)
        )
        from sparkdl_tpu.image.imageIO import ImageDecodeError

        with active_plan(plan), pytest.raises(ImageDecodeError):
            list(ds)
        assert plan.count("data.map") == 1  # no retry burned
        assert metrics.counter("resilience.retries").value == 0

    def test_from_files_source_read_with_retry(self, tmp_path):
        from sparkdl_tpu.data import Dataset

        p = tmp_path / "blob.bin"
        p.write_bytes(b"payload")
        plan = FaultPlan().add("data.source", error="transient", at=1)
        ds = Dataset.from_files([str(p)], retry=fast_policy())
        with active_plan(plan):
            assert list(ds) == [(str(p), b"payload")]
        assert len(ds) == 1

    def test_from_files_missing_file_is_permanent(self, tmp_path):
        from sparkdl_tpu.data import Dataset

        ds = Dataset.from_files(
            [str(tmp_path / "nope.bin")], retry=fast_policy(max_attempts=4)
        )
        with pytest.raises(FileNotFoundError):
            list(ds)
        assert metrics.counter("resilience.retries").value == 0

    def test_streaming_shard_loader_retries_uri_loads(self):
        from sparkdl_tpu.estimators.data import StreamingShardLoader

        plan = FaultPlan().add("data.source", error="transient", at=2)
        loader = StreamingShardLoader(
            uris=[f"u{i}" for i in range(4)],
            y=np.arange(4, dtype=np.float32),
            loader=lambda u: np.full((2,), float(u[1:]), np.float32),
            local_bs=2,
            weighted=False,
            retry=fast_policy(),
        )
        with active_plan(plan):
            batches = list(loader.epoch(np.arange(4), steps=2))
        assert len(batches) == 2
        np.testing.assert_array_equal(
            batches[0]["x"], [[0.0, 0.0], [1.0, 1.0]]
        )
        assert metrics.counter("resilience.retries").value == 1


# ---------------------------------------------------------------------------
# integrations: online serving
# ---------------------------------------------------------------------------


class TestServingIntegration:
    def test_forward_transient_retried_under_batch_deadline(self):
        """Acceptance (a) on the serving path: the injected transient
        forward failure is retried inside the worker and the request
        still succeeds."""
        from sparkdl_tpu.serving import ModelServer, ServingConfig

        cfg = ServingConfig(
            max_wait_ms=1.0,
            retry=fast_policy(max_attempts=3),
        )
        plan = FaultPlan().add(
            "serving.forward", error="transient", at=1, times=2
        )
        with active_plan(plan):
            with ModelServer(cfg) as server:
                server.register(
                    "m", lambda x: x * 2.0, item_shape=(2,), compile=False
                )
                out = server.predict(
                    np.ones((2,), np.float32), timeout=30.0,
                    deadline_ms=30000.0,
                )
        np.testing.assert_allclose(out, 2.0)
        assert metrics.counter("resilience.retries").value == 2
        assert metrics.counter("serving.errors").value == 0

    def test_forward_permanent_fails_request_without_retry(self):
        from sparkdl_tpu.serving import ModelServer, ServingConfig

        cfg = ServingConfig(max_wait_ms=1.0, retry=fast_policy())
        plan = FaultPlan().add(
            "serving.forward", error="permanent", at=1
        )
        with active_plan(plan):
            with ModelServer(cfg) as server:
                server.register(
                    "m", lambda x: x, item_shape=(2,), compile=False
                )
                fut = server.submit(np.ones((2,), np.float32))
                with pytest.raises(InjectedPermanentError):
                    fut.result(timeout=30.0)
        assert metrics.counter("resilience.retries").value == 0
        assert metrics.counter("serving.errors").value == 1

    def test_breaker_trips_into_degraded_status(self):
        from sparkdl_tpu.serving import ModelServer, ServingConfig

        cfg = ServingConfig(
            max_batch=1, max_wait_ms=0.0,
            breaker_threshold=2, breaker_recovery_s=300.0,
        )
        with ModelServer(cfg) as server:
            server.register(
                "m",
                lambda x: (_ for _ in ()).throw(
                    InjectedPermanentError("dead forward")
                ),
                item_shape=(2,), compile=False,
            )
            for _ in range(2):
                with pytest.raises(InjectedPermanentError):
                    server.predict(np.ones((2,), np.float32), timeout=30.0)
            # circuit now open: the next batch fails FAST with the typed
            # (transient — retry later) CircuitOpen, not the model error
            with pytest.raises(CircuitOpen):
                server.predict(np.ones((2,), np.float32), timeout=30.0)

            status = server.status()
            assert status["degraded"] == ["m"]
            ep = status["endpoints"]["m"]
            assert ep["degraded"] is True
            assert ep["breaker"]["state"] == "open"
            # degraded, not dead: orchestrators restart on healthy=false
            assert status["healthy"] is True
        assert metrics.counter("resilience.breaker_trips").value == 1
        assert metrics.counter("serving.errors").value == 2

    def test_breaker_recloses_after_recovery_probe(self):
        from sparkdl_tpu.serving import ModelServer, ServingConfig

        cfg = ServingConfig(
            max_batch=1, max_wait_ms=0.0,
            breaker_threshold=1, breaker_recovery_s=0.05,
        )
        boom = {"on": True}

        def forward(x):
            if boom["on"]:
                raise InjectedPermanentError("down")
            return x + 1.0

        with ModelServer(cfg) as server:
            server.register("m", forward, item_shape=(2,), compile=False)
            with pytest.raises(InjectedPermanentError):
                server.predict(np.ones((2,), np.float32), timeout=30.0)
            assert server.status()["degraded"] == ["m"]
            boom["on"] = False
            time.sleep(0.1)  # recovery window elapses -> half-open probe
            out = server.predict(np.ones((2,), np.float32), timeout=30.0)
            np.testing.assert_allclose(out, 2.0)
            assert server.status()["degraded"] == []

    def test_status_probe_device_routes_through_watchdog(self):
        from sparkdl_tpu.serving import ModelServer

        with ModelServer() as server:
            server.register(
                "m", lambda x: x, item_shape=(2,), compile=False
            )
            status = server.status(probe_device=True, probe_timeout_s=120)
        # JAX_PLATFORMS=cpu (conftest): the probe answers "cpu"
        assert status["device"]["ok"] is True
        assert status["device"]["error_class"] is None
        assert status["healthy"] is True
