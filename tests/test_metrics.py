"""Metrics/profiling subsystem tests (SURVEY.md §5.1/§5.5 — the
observability the reference lacked)."""

import glob
import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from sparkdl_tpu.transformers.utils import device_resize, run_batched
from sparkdl_tpu.utils import profiler
from sparkdl_tpu.utils.metrics import MetricsRegistry, metrics
import importlib

metrics_mod = importlib.import_module("sparkdl_tpu.utils.metrics")


def test_counter_and_timer_accumulate():
    reg = MetricsRegistry()
    reg.counter("c").add(3)
    reg.counter("c").add(2)
    assert reg.counter("c").value == 5
    assert reg.counter("c").updates == 2
    with reg.timer("t").time():
        pass
    snap = reg.snapshot()
    assert snap["c"] == 5
    assert snap["t.seconds"] >= 0
    reg.reset()
    assert reg.snapshot() == {}


def test_snapshot_prefix_filters_by_subsystem():
    reg = MetricsRegistry()
    reg.counter("serving.requests").add(4)
    reg.gauge("serving.queue_depth.m").set(1)
    reg.timer("data.producer_busy").add_seconds(0.5)
    reg.histogram("data.device_stall_ms").observe(2.0)
    serving = reg.snapshot(prefix="serving.")
    assert serving == {
        "serving.requests": 4.0,
        "serving.queue_depth.m": 1.0,
    }
    data = reg.snapshot(prefix="data.")
    assert data["data.producer_busy.seconds"] == 0.5
    assert data["data.device_stall_ms.count"] == 1.0
    assert "serving.requests" not in data
    # no prefix -> everything, same keys
    assert set(reg.snapshot()) == set(serving) | set(data)


def test_collect_is_the_typed_registry_view():
    """collect() is the sanctioned enumeration for exporters: live
    metric objects keyed by kind, insulated from later registrations."""
    reg = MetricsRegistry()
    c = reg.counter("data.rows_out")
    t = reg.timer("data.producer_busy")
    g = reg.gauge("data.queue_depth")
    h = reg.histogram("data.device_stall_ms")
    view = reg.collect()
    assert view["counters"]["data.rows_out"] is c
    assert view["timers"]["data.producer_busy"] is t
    assert view["gauges"]["data.queue_depth"] is g
    assert view["histograms"]["data.device_stall_ms"] is h
    # the view is a copy of the name->metric maps: registering after
    # collect() must not mutate an exporter's in-flight iteration
    reg.counter("data.decode_errors")
    assert "data.decode_errors" not in view["counters"]
    # but the objects stay live — updates through them are visible
    c.add(7)
    assert view["counters"]["data.rows_out"].value == 7


def test_gauge_set_add_and_snapshot():
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    g.set(5)
    g.add(-2)
    assert reg.gauge("depth").value == 3
    assert reg.snapshot()["depth"] == 3
    reg.reset()
    assert reg.snapshot() == {}


def test_histogram_quantiles_and_lifetime_stats():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in range(1, 101):  # 1..100
        h.observe(float(v))
    assert h.count == 100
    assert h.total == sum(range(1, 101))
    assert h.mean == pytest.approx(50.5)
    assert h.quantile(0.0) == 1.0
    assert h.quantile(1.0) == 100.0
    assert h.quantile(0.5) == pytest.approx(50.5)
    assert h.quantile(0.95) == pytest.approx(95.05)
    snap = reg.snapshot()
    assert snap["lat.count"] == 100
    assert snap["lat.p50"] == pytest.approx(50.5)
    assert snap["lat.p95"] <= snap["lat.p99"]


def test_histogram_sliding_window_vs_lifetime():
    # quantiles reflect the recent window; count/mean are lifetime
    reg = MetricsRegistry()
    h = reg.histogram("w", window=4)
    for v in (1000.0, 1000.0, 1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 6  # lifetime
    assert h.quantile(1.0) == 4.0  # the 1000s rolled out of the window


def test_empty_histogram_not_exported():
    reg = MetricsRegistry()
    h = reg.histogram("never")
    assert h.quantile(0.5) is None
    assert "never.count" not in reg.snapshot()


def test_histogram_exemplar_is_windowed_max():
    reg = MetricsRegistry()
    h = reg.histogram("lat", window=4)
    assert h.exemplar() is None
    h.observe(50.0)           # no exemplar attached
    assert h.exemplar() is None
    h.observe(9.0, exemplar=111)
    h.observe(30.0, exemplar=222)
    h.observe(12.0, exemplar=333)
    # of the exemplar-carrying samples, the largest value wins
    assert h.exemplar() == (30.0, 222)
    # the window slides: two more samples roll 50.0 and 111 out
    h.observe(1.0, exemplar=444)
    h.observe(2.0, exemplar=555)
    assert h.exemplar() == (30.0, 222)
    h.observe(3.0)  # now 222 itself rolled out; 333 is the window max
    assert h.exemplar() == (12.0, 333)


def test_histogram_exemplar_in_snapshot_exact_int():
    reg = MetricsRegistry()
    # trace ids are 63-bit: the snapshot must carry them as exact ints
    # (a float cast silently corrupts the low bits)
    big = (1 << 62) + 12345
    reg.histogram("lat").observe(7.5, exemplar=big)
    snap = reg.snapshot()
    assert snap["lat.exemplar_value"] == 7.5
    assert snap["lat.exemplar_trace_id"] == big
    assert isinstance(snap["lat.exemplar_trace_id"], int)


def test_histogram_without_exemplars_has_no_snapshot_keys():
    reg = MetricsRegistry()
    reg.histogram("lat").observe(7.5)
    snap = reg.snapshot()
    assert "lat.exemplar_value" not in snap
    assert "lat.exemplar_trace_id" not in snap


def test_counters_thread_safe():
    reg = MetricsRegistry()

    def bump():
        for _ in range(1000):
            reg.counter("x").add(1)

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("x").value == 8000


def test_run_batched_advances_row_counter():
    before = metrics.counter("sparkdl.rows_processed").value
    before_s = metrics.timer("sparkdl.forward").seconds
    x = np.random.RandomState(0).randn(10, 4).astype(np.float32)
    run_batched(lambda a: a * 2.0, x, batch_size=4)
    assert metrics.counter("sparkdl.rows_processed").value == before + 10
    assert metrics.timer("sparkdl.forward").seconds > before_s
    assert metrics.images_per_sec() is not None


def test_device_resize_advances_stage_metrics():
    before = metrics.timer("sparkdl.resize").entries
    imgs = [np.zeros((6, 7, 3), np.float32), np.zeros((5, 4, 3), np.float32)]
    out = device_resize(imgs, (8, 8))
    assert out.shape == (2, 8, 8, 3)
    assert metrics.timer("sparkdl.resize").entries == before + 1


def test_image_transformer_advances_image_counter(tpu_session, image_dir):
    """The flagship image path advances the first-class image counter and
    the decode-stage timer (SURVEY.md §5.5 — images/sec as a real metric)."""
    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.transformers.named_image import DeepImagePredictor

    before = metrics.counter("sparkdl.images_processed").value
    before_decode = metrics.timer("sparkdl.decode").entries
    df = imageIO.readImages(image_dir, tpu_session, numPartitions=2)
    n = df.count()
    predictor = DeepImagePredictor(
        inputCol="image",
        outputCol="preds",
        modelName="MobileNetV2",
        modelWeights="random",
    )
    predictor.transform(df).collect()
    assert metrics.counter("sparkdl.images_processed").value == before + n
    assert metrics.timer("sparkdl.decode").entries > before_decode


def test_trace_is_reentrant_safe(tmp_path):
    with profiler.trace(str(tmp_path / "outer")):
        # nested trace degrades to a no-op instead of raising
        with profiler.trace(str(tmp_path / "inner")):
            jnp.ones((4,)).sum().block_until_ready()


def test_profiler_trace_writes_capture(tmp_path):
    log_dir = str(tmp_path / "trace")
    with profiler.trace(log_dir):
        with profiler.annotate("tiny_op"):
            jnp.ones((8, 8)).sum().block_until_ready()
    written = glob.glob(os.path.join(log_dir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in written), written


def test_maybe_trace_env_gate(tmp_path, monkeypatch):
    # off by default: no-op context
    monkeypatch.delenv("SPARKDL_PROFILE_DIR", raising=False)
    with profiler.maybe_trace():
        pass
    # on when env var set
    log_dir = str(tmp_path / "envtrace")
    monkeypatch.setenv("SPARKDL_PROFILE_DIR", log_dir)
    with profiler.maybe_trace():
        jnp.zeros((4,)).sum().block_until_ready()
    written = glob.glob(os.path.join(log_dir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in written), written


class TestMFU:
    """MFU helpers (VERDICT r2 #9): XLA-cost-model FLOPs / peak."""

    def test_compiled_flops_exact_for_matmul(self):
        import jax

        f = jax.jit(lambda a, b: a @ b)
        a = jnp.zeros((128, 64), jnp.float32)
        b = jnp.zeros((64, 32), jnp.float32)
        flops = metrics_mod.compiled_flops(f.lower(a, b).compile())
        # CPU backend may not expose cost analysis; when it does, the
        # matmul count is exact: 2*M*N*K
        if flops is not None:
            assert flops == 2 * 128 * 32 * 64

    def test_peak_flops_known_tpu_kinds(self):
        class FakeDev:
            def __init__(self, kind):
                self.device_kind = kind

        assert metrics_mod.peak_flops_per_sec(FakeDev("TPU v5 lite")) == 197e12
        assert metrics_mod.peak_flops_per_sec(FakeDev("TPU v4")) == 275e12
        assert metrics_mod.peak_flops_per_sec(FakeDev("cpu")) is None

    def test_mfu_composes_and_handles_unknown(self):
        class FakeDev:
            device_kind = "TPU v5e"

        # 197e12 flops in 2s on a 197e12-peak chip -> 0.5
        assert metrics_mod.mfu(197e12, 2.0, FakeDev()) == pytest.approx(0.5)
        assert metrics_mod.mfu(None, 1.0, FakeDev()) is None

        class Unknown:
            device_kind = "cpu"

        assert metrics_mod.mfu(1e12, 1.0, Unknown()) is None


def test_paired_trials_interleaves_and_summarizes():
    """benchlib.paired_trials: A/B interleaving within rounds (drift
    robustness), median + IQR per label."""
    from sparkdl_tpu.utils.benchlib import paired_trials

    calls = []
    trials = paired_trials(
        {
            "a": lambda: calls.append("a") or float(len(calls)),
            "b": lambda: calls.append("b") or float(len(calls)),
        },
        k=3,
    )
    # strict interleaving: a,b,a,b,a,b — each round runs every label once
    assert calls == ["a", "b", "a", "b", "a", "b"]
    assert trials["a"]["samples"] == [1.0, 3.0, 5.0]
    assert trials["b"]["samples"] == [2.0, 4.0, 6.0]
    assert trials["a"]["median"] == 3.0 and trials["b"]["median"] == 4.0
    lo, hi = trials["a"]["iqr"]
    assert lo <= trials["a"]["median"] <= hi


# ---------------------------------------------------------------------------
# Histogram — empty sliding window must not fabricate quantiles
# ---------------------------------------------------------------------------


def test_histogram_quantiles_none_when_empty():
    from sparkdl_tpu.utils.metrics import Histogram

    h = Histogram("t.empty")
    assert h.quantile(0.5) is None
    assert h.quantile(0.95) is None
    assert h.quantile(0.99) is None
    assert h.mean is None and h.count == 0


def test_snapshot_skips_empty_histogram():
    """An empty histogram contributes nothing — no p50/p95/p99 keys, no
    zero-count placeholders (a dashboard reading 0ms p99 would be a lie)."""
    r = MetricsRegistry()
    r.histogram("t.lat")
    snap = r.snapshot()
    assert not any(k.startswith("t.lat") for k in snap)
    r.histogram("t.lat").observe(5.0)
    snap = r.snapshot()
    assert snap["t.lat.count"] == 1.0
    for q in ("p50", "p95", "p99"):
        assert snap[f"t.lat.{q}"] == 5.0


def test_histogram_quantile_rejects_out_of_range():
    from sparkdl_tpu.utils.metrics import Histogram

    h = Histogram("t.range")
    with pytest.raises(ValueError, match="quantile"):
        h.quantile(1.5)


def test_timer_add_seconds_accumulates():
    from sparkdl_tpu.utils.metrics import Timer

    t = Timer("t.ext")
    t.add_seconds(0.25)
    t.add_seconds(0.75)
    assert t.seconds == 1.0 and t.entries == 2


def test_benchmarks_refuse_to_measure_without_an_accelerator(capsys):
    """A number under a device unit comes from a device: on a host where
    JAX finds only CPUs the shared benchmark guard prints the refusal
    record (naming the device it did find) and tells the script to exit —
    there is no smaller CPU workload to fall back to."""
    import json

    from sparkdl_tpu.utils import benchlib

    assert benchlib.accelerator_or_refuse("m", mfu=None) is None
    record = json.loads(capsys.readouterr().out.strip())
    assert record["ok"] is False and record["value"] is None
    assert record["mfu"] is None
    assert record["error_class"] == "NoAccelerator"
    assert record["device"]["platform"] == "cpu"
    assert not hasattr(benchlib, "scale_featurizer_workload")
