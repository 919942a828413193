"""Crash flight recorder (``obs/blackbox.py``).

The in-process tests drive the rings and dump paths directly; the
subprocess tests prove the two contracts that matter in production —
an unhandled crash leaves an exception dump, and **SIGKILL** (which no
handler can observe) still leaves the last periodic persist with final
spans and thread stacks, readable as plain JSON.  Subprocess workers
import only ``sparkdl_tpu``'s env-armed obs path (no jax), so they
start in milliseconds.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from sparkdl_tpu.obs import tracer
from sparkdl_tpu.obs.blackbox import FlightRecorder
from sparkdl_tpu.utils.metrics import MetricsRegistry, metrics


@pytest.fixture(autouse=True)
def clean_slate():
    tracer.disable()
    metrics.reset()
    yield
    tracer.disable()
    metrics.reset()


@pytest.fixture()
def registry():
    return MetricsRegistry()


def _read_json(path):
    with open(path) as fh:
        return json.load(fh)


# ----------------------------------------------------------------------
# rings + dump files (in process)
# ----------------------------------------------------------------------
class TestFlightRecorder:
    def test_rings_are_bounded(self, tmp_path, registry):
        rec = FlightRecorder(
            str(tmp_path), span_capacity=4, event_capacity=3,
            sample_capacity=2, registry=registry,
        )
        for i in range(10):
            rec({"name": f"span{i}"})
            rec.note(f"event{i}", i=i)
            rec.sample_metrics()
        path = rec.dump("manual")
        payload = _read_json(path)
        assert [s["name"] for s in payload["spans"]] == [
            "span6", "span7", "span8", "span9",
        ]
        assert [e["name"] for e in payload["events"]] == [
            "event7", "event8", "event9",
        ]
        assert len(payload["metric_samples"]) == 2

    def test_dump_payload_shape(self, tmp_path, registry):
        registry.counter("serving.requests").add(7)
        rec = FlightRecorder(str(tmp_path), registry=registry)
        rec.note("breadcrumb", detail="x")
        rec.sample_metrics()
        path = rec.dump("watchdog_probe")
        assert os.path.basename(path).startswith(
            f"blackbox-{os.getpid()}-watchdog_probe-"
        )
        payload = _read_json(path)
        assert payload["reason"] == "watchdog_probe"
        assert payload["pid"] == os.getpid()
        assert payload["metrics_now"]["serving.requests"] == 7
        assert payload["metric_samples"][0]["metrics"][
            "serving.requests"] == 7
        # every dump carries all-thread stacks
        assert any("MainThread" in name for name in payload["threads"])
        stacks = list(payload["threads"].values())
        assert any(
            "test_blackbox" in line for st in stacks for line in st
        )

    def test_dump_reason_is_sanitized(self, tmp_path, registry):
        rec = FlightRecorder(str(tmp_path), registry=registry)
        path = rec.dump("breaker open: a/b")
        assert "breaker_open__a_b" in os.path.basename(path)

    def test_dump_with_exception(self, tmp_path, registry):
        rec = FlightRecorder(str(tmp_path), registry=registry)
        try:
            raise ValueError("device wedged")
        except ValueError as err:
            path = rec.dump("crash", exc=err)
        payload = _read_json(path)
        assert payload["exception"]["type"] == "ValueError"
        assert payload["exception"]["message"] == "device wedged"
        assert any(
            "device wedged" in line
            for line in payload["exception"]["traceback"]
        )

    def test_event_dumps_capped(self, tmp_path, registry):
        rec = FlightRecorder(str(tmp_path), max_dumps=3, registry=registry)
        paths = [rec.dump("crash") for _ in range(6)]
        assert sum(p is not None for p in paths) == 3
        # the periodic persist is NOT capped (it overwrites one file)
        assert rec.dump("periodic") is not None
        assert rec.dump("periodic") is not None

    def test_periodic_overwrites_single_file(self, tmp_path, registry):
        rec = FlightRecorder(str(tmp_path), registry=registry)
        rec.note("first")
        p1 = rec.dump("periodic")
        rec.note("second")
        p2 = rec.dump("periodic")
        assert p1 == p2
        names = [e["name"] for e in _read_json(p1)["events"]]
        assert names == ["first", "second"]
        assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]

    def test_is_a_tracer_sink(self, tmp_path, registry):
        rec = FlightRecorder(str(tmp_path), registry=registry)
        tracer.enable(rec)
        with tracer.span("unit.work", step=3):
            pass
        payload = _read_json(rec.dump("manual"))
        assert payload["spans"][0]["name"] == "unit.work"
        assert payload["spans"][0]["attributes"]["step"] == 3

    def test_background_persist_thread(self, tmp_path, registry):
        rec = FlightRecorder(
            str(tmp_path), interval_s=0.02, registry=registry,
        )
        registry.counter("serving.requests").add(1)
        rec.start()
        try:
            path = os.path.join(tmp_path, f"blackbox-{os.getpid()}.json")
            deadline = time.monotonic() + 10.0
            while not os.path.exists(path):
                if time.monotonic() > deadline:
                    pytest.fail("periodic persist never wrote")
                time.sleep(0.01)
        finally:
            rec.stop()
        payload = _read_json(path)
        assert payload["reason"] == "periodic"
        assert payload["metric_samples"]  # sampled before persisting

    def test_validation(self, tmp_path, registry):
        with pytest.raises(ValueError):
            FlightRecorder(str(tmp_path), interval_s=0, registry=registry)

    def test_module_api_noop_while_disarmed(self):
        from sparkdl_tpu.obs import blackbox

        assert blackbox.recorder() is None
        blackbox.note("ignored")          # must not raise
        assert blackbox.dump("ignored") is None


# ----------------------------------------------------------------------
# resilience layer crossings (cold-path, armed via the module global)
# ----------------------------------------------------------------------
class TestResilienceCrossings:
    @pytest.fixture()
    def armed(self, tmp_path, registry, monkeypatch):
        from sparkdl_tpu.obs import blackbox

        rec = FlightRecorder(str(tmp_path), registry=registry)
        monkeypatch.setattr(blackbox, "_recorder", rec)
        return rec, tmp_path

    def test_breaker_open_dumps(self, armed):
        from sparkdl_tpu.resilience.policy import CircuitBreaker

        rec, out_dir = armed
        breaker = CircuitBreaker(
            name="device", failure_threshold=2, recovery_s=60.0,
        )
        breaker.record_failure()
        breaker.record_failure()  # trips open -> event dump
        dumps = [f for f in os.listdir(out_dir)
                 if "breaker_open_device" in f]
        assert len(dumps) == 1
        payload = _read_json(os.path.join(out_dir, dumps[0]))
        names = [e["name"] for e in payload["events"]]
        assert "breaker_open_device" in names

    def test_preempted_dumps(self, armed):
        from sparkdl_tpu.resilience.preempt import PreemptionToken
        from sparkdl_tpu.resilience.errors import Preempted

        rec, out_dir = armed
        token = PreemptionToken()
        token.request("maintenance event")
        with pytest.raises(Preempted):
            token.check()
        dumps = [f for f in os.listdir(out_dir) if "preempted" in f]
        assert len(dumps) == 1


# ----------------------------------------------------------------------
# subprocess post-mortems (the production contracts)
# ----------------------------------------------------------------------
_CRASH_WORKER = """
import sparkdl_tpu  # SPARKDL_BLACKBOX_DIR arms the recorder at import
from sparkdl_tpu.obs import blackbox

assert blackbox.recorder() is not None
blackbox.note("about_to_fail", step=42)
raise RuntimeError("unhandled worker crash")
"""

_KILL_WORKER = """
import sys
import time

import sparkdl_tpu  # SPARKDL_BLACKBOX_DIR arms the recorder at import
from sparkdl_tpu.obs import blackbox, tracer

rec = blackbox.recorder()
assert rec is not None
tracer.enable()  # enable_from_env added rec as a sink; spans now flow
with tracer.span("worker.step", step=1):
    pass
blackbox.note("worker_ready")
print("READY", flush=True)
while True:  # spin until SIGKILLed; the periodic persist keeps writing
    time.sleep(0.05)
"""


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _worker_env(out_dir):
    env = dict(os.environ)
    env.update({
        "SPARKDL_BLACKBOX_DIR": str(out_dir),
        "SPARKDL_BLACKBOX_INTERVAL_S": "0.05",
        # keep the worker light: no jax import anywhere on this path
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": _REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    return env


class TestSubprocessPostMortems:
    def test_unhandled_crash_leaves_exception_dump(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-c", _CRASH_WORKER],
            capture_output=True, text=True, timeout=120,
            env=_worker_env(tmp_path), cwd="/",
        )
        assert proc.returncode != 0
        assert "unhandled worker crash" in proc.stderr  # hook chained
        dumps = [f for f in os.listdir(tmp_path)
                 if "-crash-" in f and f.endswith(".json")]
        assert len(dumps) == 1
        payload = _read_json(os.path.join(tmp_path, dumps[0]))
        assert payload["reason"] == "crash"
        assert payload["exception"]["type"] == "RuntimeError"
        assert payload["exception"]["message"] == "unhandled worker crash"
        assert [e["name"] for e in payload["events"]] == ["about_to_fail"]
        assert payload["events"][0]["step"] == 42

    def test_sigkill_leaves_readable_periodic_dump(self, tmp_path):
        # the ISSUE-8 acceptance scenario: kill -9 a worker mid-flight;
        # the periodic atomic persist must leave a parseable dump with
        # the final spans and thread stacks
        proc = subprocess.Popen(
            [sys.executable, "-c", _KILL_WORKER],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_worker_env(tmp_path), cwd="/",
        )
        try:
            assert proc.stdout.readline().strip() == "READY"
            path = os.path.join(tmp_path, f"blackbox-{proc.pid}.json")
            deadline = time.monotonic() + 60.0
            while True:  # wait for a persist that includes the span
                if os.path.exists(path):
                    try:
                        if _read_json(path)["spans"]:
                            break
                    except (json.JSONDecodeError, KeyError):
                        pytest.fail("periodic dump was torn mid-write")
                if time.monotonic() > deadline:
                    pytest.fail("worker never persisted its telemetry")
                time.sleep(0.02)
            os.kill(proc.pid, signal.SIGKILL)
        finally:
            proc.kill()
            proc.wait(timeout=30)
        assert proc.returncode == -signal.SIGKILL
        payload = _read_json(path)  # still parseable after the kill
        assert payload["reason"] == "periodic"
        assert [s["name"] for s in payload["spans"]] == ["worker.step"]
        assert any(e["name"] == "worker_ready"
                   for e in payload["events"])
        assert any("MainThread" in name for name in payload["threads"])
        # the faulthandler fault file was armed alongside
        assert os.path.exists(
            os.path.join(tmp_path, f"fault-{proc.pid}.txt")
        )
