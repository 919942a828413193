"""Online serving tests: dynamic micro-batching, warm program cache,
admission control, and ``serving.*`` metrics.

Acceptance shape (ISSUE): N concurrent single-item submissions coalesce
into far fewer forward calls (proved via ``serving.batches``); a warmed
endpoint serves a burst with zero new compiles (``serving.compiles``);
latency quantiles and batch occupancy export through
:mod:`sparkdl_tpu.utils.metrics`.  Load-shedding / deadline / crash
behavior lives in ``test_fault_injection.py``.
"""

import threading
import time

import numpy as np
import pytest

from sparkdl_tpu.serving import (
    ModelServer,
    ServerClosed,
    ServingConfig,
)
from sparkdl_tpu.transformers.utils import (
    bucket_ladder,
    pad_to_batch,
    shape_bucket,
)
from sparkdl_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def fresh_metrics():
    """Serving assertions count metric deltas from zero."""
    metrics.reset()
    yield
    metrics.reset()


def make_server(**config_kw):
    cfg = ServingConfig(**{
        "max_batch": 16, "max_wait_ms": 25.0, "queue_capacity": 64,
        **config_kw,
    })
    server = ModelServer(cfg)
    server.register("double", lambda x: x * 2.0, item_shape=(4,))
    return server


# ----------------------------------------------------------------------
# batching core (factored out of transformers/utils.py's run loops)
# ----------------------------------------------------------------------
class TestBatchingCore:
    def test_shape_bucket_rounds_to_power_of_two(self):
        assert [shape_bucket(n, 32) for n in (1, 2, 3, 5, 8, 9, 31)] == [
            1, 2, 4, 8, 8, 16, 32,
        ]

    def test_shape_bucket_caps_at_max_batch(self):
        assert shape_bucket(33, 32) == 32
        assert shape_bucket(6, 6) == 6

    def test_shape_bucket_rejects_non_positive(self):
        with pytest.raises(ValueError):
            shape_bucket(0, 32)

    def test_bucket_ladder(self):
        assert bucket_ladder(32) == (1, 2, 4, 8, 16, 32)
        assert bucket_ladder(6) == (1, 2, 4, 6)
        assert bucket_ladder(1) == (1,)

    def test_pad_to_batch_repeats_last_row(self):
        x = np.arange(6, dtype=np.float32).reshape(3, 2)
        padded = pad_to_batch(x, 5)
        assert padded.shape == (5, 2)
        np.testing.assert_array_equal(padded[:3], x)
        np.testing.assert_array_equal(padded[3], x[-1])
        np.testing.assert_array_equal(padded[4], x[-1])

    def test_pad_to_batch_noop_when_full(self):
        x = np.zeros((4, 2), np.float32)
        assert pad_to_batch(x, 4) is x
        assert pad_to_batch(x, 2) is x


# ----------------------------------------------------------------------
# coalescing + warm cache (the tentpole acceptance tests)
# ----------------------------------------------------------------------
class TestCoalescing:
    def test_concurrent_submissions_coalesce(self):
        """N concurrent single-item submissions land in ≪ N forward
        calls — the whole point of the micro-batcher."""
        n = 16
        with make_server(max_wait_ms=50.0) as server:
            server.warmup()
            batches_before = metrics.counter("serving.batches").value

            barrier = threading.Barrier(n)
            results = [None] * n

            def one(i):
                barrier.wait()
                results[i] = server.predict(
                    np.full((4,), float(i), np.float32), timeout=30.0
                )

            threads = [
                threading.Thread(target=one, args=(i,)) for i in range(n)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

            for i in range(n):
                np.testing.assert_allclose(results[i], 2.0 * i)
            batches = metrics.counter("serving.batches").value - batches_before
            assert metrics.counter("serving.requests").value == n
            # all n arrive within one 50ms linger window; typical is 1-3
            # batches, and anything ≥ n/2 means no coalescing happened
            assert 1 <= batches < n / 2, f"{n} requests took {batches} batches"

    def test_zero_recompiles_after_warmup(self):
        with make_server() as server:
            assert server.warmup() == {"double": (1, 2, 4, 8, 16)}
            compiles = metrics.counter("serving.compiles").value
            assert compiles == 5  # one program per ladder bucket
            # bursts of every size bucket differently; none may retrace
            for burst in (1, 3, 7, 16):
                futs = [
                    server.submit(np.full((4,), float(i), np.float32))
                    for i in range(burst)
                ]
                for i, f in enumerate(futs):
                    np.testing.assert_allclose(f.result(30.0), 2.0 * i)
            assert metrics.counter("serving.compiles").value == compiles

    def test_results_unscrambled_across_batches(self):
        """Padding and bucketing must never leak a neighbor's row."""
        with make_server(max_batch=4, max_wait_ms=5.0) as server:
            futs = [
                server.submit(np.full((4,), float(i), np.float32))
                for i in range(23)
            ]
            for i, f in enumerate(futs):
                np.testing.assert_allclose(f.result(30.0), 2.0 * i)


class TestMetricsExport:
    def test_latency_quantiles_and_occupancy_exported(self):
        with make_server() as server:
            server.warmup()
            futs = [
                server.submit(np.ones((4,), np.float32)) for _ in range(12)
            ]
            for f in futs:
                f.result(30.0)
            snap = server.status()["metrics"]
        for q in ("p50", "p95", "p99", "mean", "count"):
            assert f"serving.latency_ms.{q}" in snap
        assert snap["serving.latency_ms.count"] == 12
        assert (
            snap["serving.latency_ms.p50"]
            <= snap["serving.latency_ms.p95"]
            <= snap["serving.latency_ms.p99"]
        )
        assert 0.0 < snap["serving.batch_occupancy.mean"] <= 1.0
        assert snap["serving.queue_depth.double"] == 0
        assert snap["serving.requests"] == 12

    def test_status_shape(self):
        server = make_server()
        try:
            st = server.status()
            assert st["healthy"] and not st["closed"]
            assert st["uptime_s"] >= 0
            ep = st["endpoints"]["double"]
            assert ep["item_shape"] == [4] and ep["dtype"] == "float32"
            assert st["program_cache"]["programs"] == 0  # nothing traced
        finally:
            server.close()
        assert server.status()["closed"]

    @pytest.mark.slow
    def test_status_probe_device(self):
        """probe_device=True bounds a tiny dispatch on the held device
        (resilience.watchdog.check_device) — healthy on a working
        backend."""
        with make_server() as server:
            st = server.status(probe_device=True, probe_timeout_s=120)
        assert st["device"]["ok"], st["device"]
        assert st["healthy"]


@pytest.mark.slow
def test_sustained_soak_no_recompiles_no_leaks():
    """~6s of sustained concurrent traffic: zero post-warmup compiles,
    zero sheds at a sane queue size, queue drains to empty, and lifetime
    counters stay coherent (requests == latency observations)."""
    import time

    with make_server(max_batch=8, max_wait_ms=2.0,
                     queue_capacity=256) as server:
        server.warmup()
        compiles = metrics.counter("serving.compiles").value
        stop = threading.Event()
        served = [0] * 8

        def client(i):
            x = np.full((4,), float(i), np.float32)
            while not stop.is_set():
                np.testing.assert_allclose(
                    server.predict(x, timeout=30.0), 2.0 * i
                )
                served[i] += 1

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        time.sleep(6.0)
        stop.set()
        for t in threads:
            t.join()

        snap = server.status()["metrics"]
        total = sum(served)
        assert total > 100
        assert metrics.counter("serving.compiles").value == compiles
        assert snap["serving.requests"] == total
        assert snap["serving.latency_ms.count"] == total
        assert snap["serving.shed"] == 0
        assert snap["serving.queue_depth.double"] == 0


# ----------------------------------------------------------------------
# endpoint contract / lifecycle
# ----------------------------------------------------------------------
class TestEndpointContract:
    def test_duplicate_register_rejected(self):
        with make_server() as server:
            with pytest.raises(ValueError, match="already registered"):
                server.register("double", lambda x: x)

    def test_item_shape_is_enforced(self):
        with make_server() as server:
            server.predict(np.ones((4,), np.float32), timeout=30.0)
            with pytest.raises(ValueError, match="shape"):
                server.submit(np.ones((5,), np.float32))

    def test_first_request_binds_shape(self):
        with ModelServer(ServingConfig(max_wait_ms=1.0)) as server:
            server.register("id", lambda x: x)  # no item_shape
            with pytest.raises(ValueError, match="no item shape"):
                server.warmup()
            out = server.predict(np.ones((3,), np.float32), timeout=30.0)
            np.testing.assert_allclose(out, 1.0)
            with pytest.raises(ValueError, match="shape"):
                server.submit(np.ones((7,), np.float32))

    def test_model_id_routing(self):
        with make_server() as server:
            server.register("triple", lambda x: x * 3.0, item_shape=(4,))
            with pytest.raises(ValueError, match="model_id is required"):
                server.submit(np.ones((4,), np.float32))
            out = server.predict(
                np.ones((4,), np.float32), model_id="triple", timeout=30.0
            )
            np.testing.assert_allclose(out, 3.0)
            with pytest.raises(KeyError, match="nope"):
                server.submit(np.ones((4,), np.float32), model_id="nope")

    def test_submit_after_close_raises(self):
        server = make_server()
        server.close()
        with pytest.raises(ServerClosed):
            server.submit(np.ones((4,), np.float32))

    def test_program_cache_lru_eviction(self):
        # cache_size=2 with a 3-bucket ladder: warmup itself evicts, and
        # the evicted bucket retraces on demand (bounded memory, still
        # correct)
        with ModelServer(
            ServingConfig(max_batch=4, max_wait_ms=1.0, cache_size=2)
        ) as server:
            server.register("d", lambda x: x * 2.0, item_shape=(2,))
            server.warmup()  # traces buckets 1, 2, 4 through a 2-slot LRU
            assert server.status()["program_cache"]["programs"] == 2
            out = server.predict(np.ones((2,), np.float32), timeout=30.0)
            np.testing.assert_allclose(out, 2.0)


# ----------------------------------------------------------------------
# program-cache single-flight: compiles happen OUTSIDE the cache lock
# (regression for the lock-blocking finding sparkdl_check surfaced:
# ProgramCache.program used to hold self._lock across a multi-second
# XLA compile, stalling stats()/status() and every other endpoint)
# ----------------------------------------------------------------------
class _SlowEngineStub:
    """Engine stand-in whose program() blocks until released, counting
    calls — lets the test hold a 'compile' in flight deterministically."""

    def __init__(self):
        self.release = threading.Event()
        self.calls = []
        self.evicted = []
        self.cache = None

    def program(self, forward, specs, fingerprint=None, donate=False,
                name=None):
        self.calls.append(name)
        if not self.release.wait(timeout=30.0):
            raise TimeoutError("slow-compile stub never released")

        class Handle:
            callable = staticmethod(forward)
            source = "compile"
            key = f"stub:{name}"

        return Handle()

    def evict(self, key):
        self.evicted.append(key)


class TestProgramCacheSingleFlight:
    def _cache(self, maxsize=4):
        from sparkdl_tpu.serving.cache import ProgramCache

        cache = ProgramCache(maxsize=maxsize)
        stub = _SlowEngineStub()
        cache._engine = stub
        return cache, stub

    def test_stats_not_blocked_while_a_compile_is_in_flight(self):
        cache, stub = self._cache()
        t = threading.Thread(
            target=cache.program,
            args=("m", lambda x: x, 4, (2,), np.float32),
            daemon=True,
        )
        t.start()
        # wait until the resolve has actually claimed the key
        deadline = time.monotonic() + 5.0
        while not stub.calls and time.monotonic() < deadline:
            time.sleep(0.005)
        assert stub.calls, "stub compile never started"
        # the health-probe path must answer while the compile hangs
        start = time.monotonic()
        stats = cache.stats()
        elapsed = time.monotonic() - start
        assert elapsed < 1.0, f"stats() stalled {elapsed:.2f}s behind compile"
        assert stats["programs"] == 0  # not admitted yet
        stub.release.set()
        t.join(timeout=10.0)
        assert not t.is_alive()
        assert cache.stats()["programs"] == 1

    def test_same_key_callers_share_one_compile(self):
        cache, stub = self._cache()
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(
                    cache.program("m", lambda x: x, 4, (2,), np.float32)
                ),
                daemon=True,
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5.0
        while not stub.calls and time.monotonic() < deadline:
            time.sleep(0.005)
        stub.release.set()
        for t in threads:
            t.join(timeout=10.0)
        assert len(results) == 4
        assert len(stub.calls) == 1, (
            f"single-flight broken: {len(stub.calls)} compiles for one key"
        )

    def test_distinct_keys_resolve_concurrently(self):
        # a cold bucket must not serialize other buckets behind it
        cache, stub = self._cache()
        stub.release.set()  # compiles return immediately
        cache.program("m", lambda x: x, 4, (2,), np.float32)
        stub.release.clear()
        slow = threading.Thread(
            target=cache.program,
            args=("m", lambda x: x, 8, (2,), np.float32),
            daemon=True,
        )
        slow.start()
        deadline = time.monotonic() + 5.0
        while len(stub.calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.005)
        # the already-cached bucket serves instantly despite the in-flight
        # compile of bucket 8
        start = time.monotonic()
        fn = cache.program("m", lambda x: x, 4, (2,), np.float32)
        assert time.monotonic() - start < 1.0
        assert fn is not None
        stub.release.set()
        slow.join(timeout=10.0)

    def test_eviction_contract_preserved(self):
        cache, stub = self._cache(maxsize=2)
        stub.release.set()
        for bucket in (1, 2, 4):
            cache.program("m", lambda x: x, bucket, (2,), np.float32)
        stats = cache.stats()
        assert stats["programs"] == 2
        assert len(stub.evicted) == 1  # LRU slot left BOTH maps


# ----------------------------------------------------------------------
# constructors: XlaFunction / registered-UDF round trips
# ----------------------------------------------------------------------
class TestConstructors:
    def test_from_xla_function(self):
        from sparkdl_tpu.graph.function import XlaFunction

        w = np.arange(12, dtype=np.float32).reshape(4, 3)
        fn = XlaFunction(
            lambda p, x: x @ p["w"], params={"w": w}, name="linear"
        )
        fn.input_specs = [((8, 4), np.float32)]
        with ModelServer.from_xla_function(
            fn, config=ServingConfig(max_wait_ms=1.0)
        ) as server:
            assert server.warmup() == {"linear": (1, 2, 4, 8, 16, 32)}
            x = np.ones((4,), np.float32)
            np.testing.assert_allclose(
                server.predict(x, timeout=30.0), x @ w, rtol=1e-6
            )

    def test_from_registered_udf_serves_model_udf(self, tpu_session):
        keras = pytest.importorskip("keras")

        rng = np.random.RandomState(3)
        model = keras.Sequential(
            [
                keras.layers.Input((8, 8, 3)),
                keras.layers.Conv2D(2, 3, activation="relu"),
                keras.layers.GlobalAveragePooling2D(),
                keras.layers.Dense(3),
            ]
        )
        model.set_weights(
            [
                rng.randn(*w.shape).astype(np.float32) * 0.1
                for w in model.get_weights()
            ]
        )
        from sparkdl_tpu.udf import registerKerasImageUDF

        udf = registerKerasImageUDF(
            "serving_rt_udf", model, session=tpu_session
        )
        # the serving hook survives the registry's re-wrap
        meta = tpu_session.udf.get("serving_rt_udf")._serving_endpoint
        assert meta["model_id"] == "serving_rt_udf"
        assert meta["item_shape"] == (8, 8, 3)
        assert udf._serving_endpoint["item_shape"] == (8, 8, 3)

        with ModelServer.from_registered_udf(
            "serving_rt_udf",
            session=tpu_session,
            config=ServingConfig(max_batch=4, max_wait_ms=1.0),
        ) as server:
            server.warmup(buckets=(1, 2))
            x = rng.rand(8, 8, 3).astype(np.float32) * 255.0
            got = server.predict(x, timeout=60.0)
            want = np.asarray(model(x[None].astype(np.float32)))[0]
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_from_registered_udf_rejects_plain_udf(self, tpu_session):
        tpu_session.udf.register("plain_py_udf", lambda x: x)
        try:
            with pytest.raises(ValueError, match="registerKerasImageUDF"):
                ModelServer.from_registered_udf(
                    "plain_py_udf", session=tpu_session
                )
        finally:
            del tpu_session.udf._udfs["plain_py_udf"]


# ----------------------------------------------------------------------
# offer_wait races (ISSUE-10 satellite): a blocked backpressure poller
# must wake with a TYPED error when the server closes or the breaker
# opens underneath it — never hang on a queue nobody will drain again
# ----------------------------------------------------------------------
class TestIdleDeviceFlush:
    """ISSUE-18 regression: the coalesce linger must not hold a batch
    while the device sits idle.  Both tests run on an injectable FROZEN
    clock, so the linger's remaining-time computation never counts down
    — without the early flush they would hang, not just run slow."""

    def test_take_flush_early_cuts_linger_on_frozen_clock(self):
        from sparkdl_tpu.serving.admission import AdmissionQueue, Request

        q = AdmissionQueue(8, clock=lambda: 1000.0)
        q.offer(Request(value=np.zeros(4, np.float32),
                        enqueued_at=1000.0))
        t0 = time.monotonic()
        batch = q.take(
            max_n=8, max_wait_s=3600.0, flush_early=lambda: True,
        )
        assert len(batch) == 1
        assert time.monotonic() - t0 < 5.0
        assert metrics.counter("batcher.flush_early").value == 1

    def test_lone_request_resolves_without_serving_full_linger(self):
        """A single submission against an idle endpoint must dispatch
        immediately even with an (effectively infinite) coalesce
        window — the dispatch window is free, so waiting buys nothing."""
        from sparkdl_tpu.serving.batcher import MicroBatcher
        from sparkdl_tpu.serving.cache import ProgramCache

        batcher = MicroBatcher(
            "flush",
            lambda x: x * 2.0,
            ServingConfig(max_batch=16, max_wait_ms=3_600_000.0),
            ProgramCache(4),
            item_shape=(4,),
            compile=False,
            clock=lambda: 1000.0,
        )
        try:
            fut = batcher.submit(np.full((4,), 3.0, np.float32))
            np.testing.assert_allclose(fut.result(timeout=10.0), 6.0)
            assert metrics.counter("batcher.flush_early").value >= 1
        finally:
            batcher.close()


class TestOfferWaitRaces:
    def _full_queue(self, capacity=1):
        from sparkdl_tpu.serving.admission import AdmissionQueue, Request

        q = AdmissionQueue(capacity)
        for _ in range(capacity):
            q.offer(Request(value=np.zeros(4, np.float32)))
        return q, Request

    def test_blocked_offer_wait_wakes_on_close_with_typed_error(self):
        q, Request = self._full_queue()
        outcome = {}
        blocked = threading.Event()

        def poller():
            blocked.set()
            try:
                q.offer_wait(Request(value=np.zeros(4, np.float32)))
                outcome["returned"] = True
            except BaseException as exc:  # noqa: BLE001
                outcome["error"] = exc

        t = threading.Thread(target=poller, daemon=True)
        t.start()
        assert blocked.wait(5)
        time.sleep(0.1)  # let the poller reach the Condition wait
        assert not outcome, "poller should be blocked on the full queue"
        q.close()
        t.join(timeout=5)
        assert not t.is_alive(), "offer_wait hung across close()"
        assert isinstance(outcome.get("error"), ServerClosed)

    def test_offer_wait_timeout_on_full_queue_returns_false(self):
        q, Request = self._full_queue()
        t0 = time.monotonic()
        admitted = q.offer_wait(
            Request(value=np.zeros(4, np.float32)), timeout_s=0.2
        )
        assert admitted is False
        assert time.monotonic() - t0 < 5.0

    def test_offer_wait_unblocks_when_take_frees_space(self):
        q, Request = self._full_queue()
        result = {}

        def poller():
            result["admitted"] = q.offer_wait(
                Request(value=np.zeros(4, np.float32)), timeout_s=10.0
            )

        t = threading.Thread(target=poller, daemon=True)
        t.start()
        time.sleep(0.1)
        assert q.take(1, max_wait_s=0.0)  # frees one slot
        t.join(timeout=5)
        assert result.get("admitted") is True

    def test_blocked_offer_wait_drains_through_open_breaker(self):
        """End to end: the forward starts failing, the breaker trips,
        the worker fast-fails the backlog — and the poller blocked in
        ``offer_wait`` is admitted (queue drained) with its request
        resolved as a typed ``CircuitOpen``, not stranded."""
        from sparkdl_tpu.resilience.errors import CircuitOpen
        from sparkdl_tpu.serving.admission import Request

        gate = threading.Event()

        def failing_forward(x):
            gate.wait(30.0)
            raise RuntimeError("forward is down")

        server = ModelServer(ServingConfig(
            max_batch=1, max_wait_ms=1.0, queue_capacity=2,
            breaker_threshold=2,
        ))
        server.register(
            "down", failing_forward, item_shape=(4,), compile=False
        )
        batcher = server._endpoints["down"]
        try:
            # r1 is taken by the worker (blocked in forward on the
            # gate); r2 + r3 then fill the queue to capacity
            futures = [server.submit(np.zeros(4, np.float32))]
            deadline = time.monotonic() + 10.0
            while len(batcher._queue) and time.monotonic() < deadline:
                time.sleep(0.01)
            assert not len(batcher._queue), "worker never took r1"
            futures += [
                server.submit(np.zeros(4, np.float32)) for _ in range(2)
            ]
            blocked_req = Request(value=np.zeros(4, np.float32))
            admitted = {}

            def poller():
                admitted["ok"] = batcher._queue.offer_wait(
                    blocked_req, timeout_s=30.0
                )

            t = threading.Thread(target=poller, daemon=True)
            t.start()
            time.sleep(0.2)
            assert not admitted, "queue should be full, poller blocked"

            gate.set()  # failures flow: 2 failed batches open the breaker
            t.join(timeout=20)
            assert admitted.get("ok") is True, (
                "poller not admitted after the breaker drained the queue"
            )
            assert batcher.breaker.state == "open"
            # the admitted request is resolved, typed — not stranded
            assert isinstance(
                blocked_req.future.exception(timeout=10), CircuitOpen
            )
            # the backlog got typed failures too, not hangs
            for fut in futures:
                assert fut.exception(timeout=10) is not None
        finally:
            gate.set()
            server.close()


# ----------------------------------------------------------------------
# ragged slot-block dispatch (ISSUE-20)
# ----------------------------------------------------------------------
class TestRaggedDispatch:
    """One-shot slot-block dispatch: admission into any free slot, a
    bool occupancy mask instead of pad rows, the padded ladder kept as
    the SPARKDL_RAGGED=0 kill switch and the fallback for compiled
    endpoints without a durable fingerprint."""

    DIM = 4

    def _matrix_server(self):
        import jax.numpy as jnp

        from sparkdl_tpu.transformers.utils import make_input_prologue

        w = np.linspace(-1.0, 1.0, self.DIM * self.DIM,
                        dtype=np.float32).reshape(self.DIM, self.DIM)
        pro = make_input_prologue(preprocess=lambda x: x / 2.0)
        server = ModelServer(ServingConfig(
            max_batch=8, max_wait_ms=5.0, queue_capacity=64,
        ))
        server.register(
            "plain", lambda x, _w=w: np.tanh(np.asarray(x) @ _w),
            item_shape=(self.DIM,), compile=False,
        )
        server.register(
            "plain_pro", lambda x, _w=w: np.tanh(np.asarray(x) @ _w),
            item_shape=(self.DIM,), compile=False, prologue=pro,
        )
        server.register(
            "jit", lambda x, _w=w: jnp.tanh(x @ _w),
            item_shape=(self.DIM,), compile=True,
            fingerprint="test:ragged:jit:v1",
        )
        server.register(
            "jit_pro", lambda x, _w=w: jnp.tanh(x @ _w),
            item_shape=(self.DIM,), compile=True,
            fingerprint="test:ragged:jit-pro:v1", prologue=pro,
        )
        return server

    def test_ragged_and_padded_outputs_byte_identical(self, monkeypatch):
        """THE equivalence matrix: the same 20 inputs through plain,
        plain+prologue, compiled-fingerprinted, and compiled+prologue
        endpoints, ragged on then off.  Dispatch shape (mask vs pad) must
        never leak into results: byte-identical wherever the arithmetic
        is the same — the host endpoints across the two modes, and a
        compiled endpoint across two passes of the one slot-block shape,
        however the rows fell into blocks.  A compiled endpoint's padded
        ladder runs OTHER programs (one a bucket), and XLA may tile a
        (1..8, 4) product differently from the (8, 4) block's: across the
        modes those agree to float32's last bits, not to the bit."""
        rng = np.random.default_rng(7)
        xs = [rng.standard_normal(self.DIM).astype(np.float32)
              for _ in range(20)]
        endpoints = ("plain", "plain_pro", "jit", "jit_pro")

        def run(server, ep):
            futs = [server.submit(x, model_id=ep) for x in xs]
            return np.stack([
                np.asarray(f.result(timeout=30.0)) for f in futs])

        outs = {}
        for mode in ("1", "0"):
            monkeypatch.setenv("SPARKDL_RAGGED", mode)
            server = self._matrix_server()
            try:
                outs[mode] = {ep: run(server, ep) for ep in endpoints}
                if mode == "1":
                    again = {ep: run(server, ep) for ep in endpoints}
            finally:
                server.close()
        for ep in endpoints:
            assert outs["1"][ep].tobytes() == again[ep].tobytes(), ep
            assert outs["1"][ep].dtype == outs["0"][ep].dtype
            if ep.startswith("plain"):
                assert outs["1"][ep].tobytes() == outs["0"][ep].tobytes(), ep
            else:
                # tanh's outputs lie in [-1, 1]: a few float32 ulps of 1
                np.testing.assert_allclose(
                    outs["1"][ep], outs["0"][ep], rtol=1e-6, atol=1e-6,
                    err_msg=ep)

    def test_ragged_active_and_fallback_rules(self, monkeypatch):
        """Plain and fingerprinted-compiled endpoints serve ragged;
        unfingerprinted-compiled endpoints and SPARKDL_RAGGED=0 fall
        back to the padded ladder (and stay correct)."""
        import jax.numpy as jnp

        monkeypatch.setenv("SPARKDL_RAGGED", "1")
        server = ModelServer(ServingConfig(max_batch=4, max_wait_ms=5.0))
        server.register("plain", lambda x: np.asarray(x) * 2.0,
                        item_shape=(4,), compile=False)
        server.register("anon_jit", lambda x: jnp.asarray(x) * 2.0,
                        item_shape=(4,), compile=True)
        server.register("fp_jit", lambda x: jnp.asarray(x) * 2.0,
                        item_shape=(4,), compile=True,
                        fingerprint="test:fallback:v1")
        try:
            eps = server.status()["endpoints"]
            assert eps["plain"]["ragged"] is True
            assert eps["fp_jit"]["ragged"] is True
            # anonymous slot-block executables can't persist — padded
            assert eps["anon_jit"]["ragged"] is False
            x = np.full((4,), 1.5, np.float32)
            for ep in ("plain", "anon_jit", "fp_jit"):
                np.testing.assert_allclose(
                    server.submit(x, model_id=ep).result(timeout=30.0),
                    3.0,
                )
            monkeypatch.setenv("SPARKDL_RAGGED", "0")  # live kill switch
            eps = server.status()["endpoints"]
            assert all(not e["ragged"] for e in eps.values())
            np.testing.assert_allclose(
                server.submit(x, model_id="plain").result(timeout=30.0),
                3.0,
            )
        finally:
            server.close()

    def test_ragged_computes_no_pad_rows(self, monkeypatch):
        """rows_computed == rows_real on the ragged plain lane (pad
        fraction 0), while the padded ladder computes bucket-rounded
        rows for the same traffic."""
        monkeypatch.setenv("SPARKDL_RAGGED", "1")
        gate = threading.Event()
        server = ModelServer(ServingConfig(
            max_batch=8, max_wait_ms=5.0, queue_capacity=64,
        ))

        def forward(x):
            gate.wait(10.0)
            return np.asarray(x) * 2.0

        server.register("ep", forward, item_shape=(4,), compile=False)
        try:
            first = server.submit(np.ones(4, np.float32))
            time.sleep(0.3)  # worker blocked in forward on batch #1
            rest = [server.submit(np.ones(4, np.float32))
                    for _ in range(3)]
            gate.set()
            for f in [first] + rest:
                np.testing.assert_allclose(f.result(timeout=10.0), 2.0)
            real = metrics.counter("batcher.rows_real").value
            computed = metrics.counter("batcher.rows_computed").value
            assert real == computed == 4.0
            assert metrics.gauge("batcher.pad_fraction").value == 0.0
        finally:
            gate.set()
            server.close()

    def test_padded_ladder_counts_pad_rows(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_RAGGED", "0")
        gate = threading.Event()
        server = ModelServer(ServingConfig(
            max_batch=8, max_wait_ms=5.0, queue_capacity=64,
        ))

        def forward(x):
            gate.wait(10.0)
            return np.asarray(x) * 2.0

        server.register("ep", forward, item_shape=(4,), compile=False)
        try:
            first = server.submit(np.ones(4, np.float32))
            time.sleep(0.3)
            rest = [server.submit(np.ones(4, np.float32))
                    for _ in range(2)]
            gate.set()
            for f in [first] + rest:
                np.testing.assert_allclose(f.result(timeout=10.0), 2.0)
            # batch #1: 1 row in bucket 1; batch #2: 2 rows in bucket 2
            # ... unless the two queued requests split — either way the
            # ladder computed at least the real rows, and the counters
            # agree with the gauge
            real = metrics.counter("batcher.rows_real").value
            computed = metrics.counter("batcher.rows_computed").value
            assert real == 3.0 and computed >= real
            assert metrics.gauge("batcher.pad_fraction").value == round(
                1.0 - real / computed, 4
            )
        finally:
            gate.set()
            server.close()

    def test_freed_slots_admit_waiting_requests(self, monkeypatch):
        """More requests than slots: a 2-slot pool serves 6 requests by
        admitting into freed slots, never batching beyond the pool."""
        monkeypatch.setenv("SPARKDL_RAGGED", "1")
        seen = []
        server = ModelServer(ServingConfig(
            max_batch=2, max_wait_ms=5.0, queue_capacity=64,
        ))

        def forward(x):
            x = np.asarray(x)
            seen.append(int(x.shape[0]))
            return x * 2.0

        server.register("ep", forward, item_shape=(4,), compile=False)
        try:
            futs = [server.submit(np.ones(4, np.float32))
                    for _ in range(6)]
            for f in futs:
                np.testing.assert_allclose(f.result(timeout=10.0), 2.0)
            assert sum(seen) == 6
            assert max(seen) <= 2, (
                f"dispatch exceeded the slot pool: {seen}"
            )
            snap = server.status()["endpoints"]["ep"]["slot_pool"]
            assert snap["n_slots"] == 2
        finally:
            server.close()

    def test_single_request_dispatches_without_coalesce_wait(
        self, monkeypatch
    ):
        """Slot dispatch admits the moment a request arrives — a lone
        request against an effectively-infinite coalesce window must
        still resolve immediately."""
        monkeypatch.setenv("SPARKDL_RAGGED", "1")
        server = ModelServer(ServingConfig(
            max_batch=8, max_wait_ms=3_600_000.0,
        ))
        server.register("ep", lambda x: np.asarray(x) * 2.0,
                        item_shape=(4,), compile=False)
        try:
            t0 = time.monotonic()
            fut = server.submit(np.ones(4, np.float32))
            np.testing.assert_allclose(fut.result(timeout=10.0), 2.0)
            assert time.monotonic() - t0 < 5.0
        finally:
            server.close()

    def test_prologue_fused_matches_host_application(self):
        """The fused prologue must equal applying the same callable on
        the host before the forward — one program, same bytes."""
        from sparkdl_tpu.transformers.utils import make_input_prologue

        pro = make_input_prologue(preprocess=lambda x: x / 255.0)
        x = np.arange(4, dtype=np.float32)
        server = ModelServer(ServingConfig(max_batch=4, max_wait_ms=5.0))
        server.register("fused", lambda b: np.asarray(b) + 1.0,
                        item_shape=(4,), compile=False, prologue=pro)
        server.register("host", lambda b: np.asarray(b) + 1.0,
                        item_shape=(4,), compile=False)
        try:
            fused = np.asarray(
                server.submit(x, model_id="fused").result(timeout=10.0)
            )
            host_in = np.asarray(pro(x[None]))[0]
            host = np.asarray(
                server.submit(host_in, model_id="host").result(
                    timeout=10.0
                )
            )
            assert fused.tobytes() == host.tobytes()
        finally:
            server.close()


class TestWarmStartResultIntegrity:
    """The r20 warm-start corruption regression: a disk-loaded
    executable may hand later calls the same output buffer (and
    zero-copy-alias host inputs), so fetched results must leave the
    dispatch window as owned copies — a request's future must keep its
    row even after later batches run through the same executable."""

    DIM = 4

    @pytest.mark.parametrize("ragged", ["1", "0"])
    def test_warm_loaded_endpoint_serves_correct_rows(
        self, tmp_path, monkeypatch, ragged
    ):
        import jax.numpy as jnp

        monkeypatch.setenv("SPARKDL_RAGGED", ragged)
        monkeypatch.setenv("SPARKDL_COMPILE_CACHE", str(tmp_path / "exe"))
        scale = np.linspace(0.5, 1.5, self.DIM, dtype=np.float32)
        xs = [np.full(self.DIM, float(i + 1), np.float32)
              for i in range(24)]

        def serve_all():
            server = ModelServer(ServingConfig(
                max_batch=8, max_wait_ms=2.0, queue_capacity=64,
            ))
            server.register(
                "jit", lambda x, _s=scale: jnp.tanh(x * _s),
                item_shape=(self.DIM,), compile=True,
                fingerprint="test:warmstart:v1",
            )
            try:
                futs = [server.submit(x, model_id="jit") for x in xs]
                return np.stack([
                    np.asarray(f.result(timeout=30.0)) for f in futs
                ])
            finally:
                server.close()

        expect = np.stack([np.tanh(x * scale) for x in xs])
        cold = serve_all()   # compiles, persists under the fingerprint
        warm = serve_all()   # fresh ProgramCache in-process -> disk load
        np.testing.assert_allclose(cold, expect, atol=1e-6)
        # every request reads ITS row — not a later batch's rewrite of
        # a shared output buffer
        np.testing.assert_array_equal(warm, cold)

    def test_fetched_results_own_their_memory(self):
        import jax.numpy as jnp

        from sparkdl_tpu.engine.executor import _fetch_host

        host = _fetch_host(jnp.arange(8, dtype=jnp.float32))
        assert isinstance(host, np.ndarray)
        assert host.base is None and host.flags.owndata
