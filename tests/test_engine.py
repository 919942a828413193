"""Execution engine (``sparkdl_tpu/engine``): content-addressed cache
keys (stable across processes, sensitive to every component), the
in-memory executable LRU, persistent disk roundtrips that survive a
fresh engine, ``engine.compile`` spans only on true compiles, the
depth-N dispatch window, and serving's compile-vs-cache-load warmup
report.

Acceptance shape (ISSUE 5): cache-key stability incl. a cross-process
check; LRU eviction under a small ``maxsize``; a second engine *loads*
a fingerprinted executable instead of recompiling (closure weights come
back intact); anonymous functions never persist; a traced warm start
shows zero ``engine.compile`` spans; ``serving.cache_load`` counts the
restart-warmup fast path.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax.numpy as jnp

from sparkdl_tpu.engine import (
    DispatchWindow,
    ExecutionEngine,
    FetchFailure,
    PersistentCompileCache,
    cache_key,
    default_cache_dir,
)
from sparkdl_tpu.engine.cache import _runtime_descriptor
from sparkdl_tpu.obs import JsonlTraceSink, tracer
from sparkdl_tpu.utils.metrics import metrics


@pytest.fixture(autouse=True)
def clean_slate():
    tracer.disable()
    metrics.reset()
    yield
    tracer.disable()
    metrics.reset()


_SPEC = (((8, 4), "<f4", None),)
_RUNTIME = {
    "jax": "0.0.test", "jaxlib": "0.0.test", "platform": "cpu",
    "device_kind": "cpu", "device_count": 8,
}


# ----------------------------------------------------------------------
# cache keys
# ----------------------------------------------------------------------
class TestCacheKey:
    def test_deterministic_and_hex(self):
        a = cache_key("fp:m1", _SPEC, (0,), runtime=_RUNTIME)
        b = cache_key("fp:m1", _SPEC, (0,), runtime=_RUNTIME)
        assert a == b
        assert len(a) == 64 and set(a) <= set("0123456789abcdef")

    def test_every_component_changes_the_key(self):
        base = cache_key("fp:m1", _SPEC, (0,), runtime=_RUNTIME)
        variants = [
            cache_key("fp:m2", _SPEC, (0,), runtime=_RUNTIME),
            cache_key("fp:m1", (((16, 4), "<f4", None),), (0,),
                      runtime=_RUNTIME),
            cache_key("fp:m1", (((8, 4), "<f2", None),), (0,),
                      runtime=_RUNTIME),
            cache_key(
                "fp:m1",
                (((8, 4), "<f4", {"axes": {"data": 8}, "spec": "P('data',)"}),),
                (0,), runtime=_RUNTIME,
            ),
            cache_key("fp:m1", _SPEC, (), runtime=_RUNTIME),  # donation
            cache_key("fp:m1", _SPEC, (0,),
                      runtime={**_RUNTIME, "jax": "9.9.9"}),
            cache_key("fp:m1", _SPEC, (0,),
                      runtime={**_RUNTIME, "device_count": 1}),
        ]
        assert len({base, *variants}) == len(variants) + 1

    def test_cross_process_stability(self):
        """The same components hash to the same address in a separate
        interpreter — the contract that lets a second process (or a
        restarted server) find executables this one stored."""
        code = textwrap.dedent(
            """
            from sparkdl_tpu.engine.cache import cache_key
            runtime = {
                "jax": "0.0.test", "jaxlib": "0.0.test", "platform": "cpu",
                "device_kind": "cpu", "device_count": 8,
            }
            print(cache_key(
                "fp:m1", (((8, 4), "<f4", None),), (0,), runtime=runtime
            ))
            """
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == cache_key(
            "fp:m1", _SPEC, (0,), runtime=_RUNTIME
        )

    def test_real_runtime_descriptor_is_stable_in_process(self):
        assert cache_key("fp", _SPEC, ()) == cache_key("fp", _SPEC, ())
        rt = _runtime_descriptor()
        assert rt["platform"] == "cpu" and rt["device_count"] == 8

    def test_default_cache_dir_env(self, monkeypatch):
        monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "/tmp/somewhere")
        assert default_cache_dir() == "/tmp/somewhere"
        monkeypatch.setenv("SPARKDL_COMPILE_CACHE", "off")
        assert default_cache_dir() is None

    def test_cache_root_is_placed_from_outside_or_fixed_in_checkout(
        self, monkeypatch
    ):
        """One root for every compile cache: ``JAX_COMPILATION_CACHE_DIR``
        where it is set — and then the code sets no directory anywhere —
        else a fixed, git-ignored directory inside the checkout, handed to
        JAX's own cache.  The executable store sits under the same root;
        nothing lands under ``~`` or a temporary name."""
        import jax

        from sparkdl_tpu.engine import compile_cache_root, enable_jax_cache

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.delenv("SPARKDL_COMPILE_CACHE", raising=False)
        was = jax.config.jax_compilation_cache_dir
        try:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
            assert compile_cache_root() == "/placed/outside"
            assert default_cache_dir() == "/placed/outside/executables"
            jax.config.update("jax_compilation_cache_dir", None)
            enable_jax_cache()  # JAX reads the variable itself
            assert jax.config.jax_compilation_cache_dir is None

            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
            fixed = os.path.join(repo, ".compile_cache")
            assert compile_cache_root() == fixed == compile_cache_root()
            assert default_cache_dir() == os.path.join(fixed, "executables")
            enable_jax_cache()
            assert jax.config.jax_compilation_cache_dir == fixed
            with open(os.path.join(repo, ".gitignore")) as fh:
                assert ".compile_cache/" in fh.read().split()
        finally:
            jax.config.update("jax_compilation_cache_dir", was)


# ----------------------------------------------------------------------
# in-memory LRU
# ----------------------------------------------------------------------
class TestEngineLRU:
    def test_eviction_under_small_maxsize(self):
        eng = ExecutionEngine(maxsize=2, persistent=False)

        def fn(x):
            return x + 1.0

        keys = []
        for n in (2, 3, 4):
            h = eng.program(
                fn, (np.zeros((n,), np.float32),), fingerprint="lru:t"
            )
            assert h.source == "compile"
            keys.append(h.key)
        assert eng.stats()["programs"] == 2
        assert eng.lookup(keys[0]) is None          # oldest evicted
        assert eng.lookup(keys[1]) is not None
        assert eng.lookup(keys[2]) is not None

        # the evicted signature recompiles (no disk tier here) ...
        h = eng.program(
            fn, (np.zeros((2,), np.float32),), fingerprint="lru:t"
        )
        assert h.key == keys[0] and h.source == "compile"
        # ... which in turn evicted the now-oldest middle entry
        assert eng.lookup(keys[1]) is None

    def test_memory_hit_is_free_and_recency_updates(self):
        eng = ExecutionEngine(maxsize=2, persistent=False)

        def fn(x):
            return x * 2.0

        k2 = eng.program(fn, (np.zeros((2,), np.float32),)).key
        eng.program(fn, (np.zeros((3,), np.float32),))
        # touch k2 so it is most-recent, then insert a third program
        h = eng.program(fn, (np.zeros((2,), np.float32),))
        assert h.source == "memory" and h.seconds == 0.0
        eng.program(fn, (np.zeros((4,), np.float32),))
        assert eng.lookup(k2) is not None           # survived via recency


# ----------------------------------------------------------------------
# persistent roundtrip
# ----------------------------------------------------------------------
class TestPersistentCache:
    def test_second_engine_loads_instead_of_recompiling(self, tmp_path):
        disk = str(tmp_path / "exe")
        w = np.arange(12, dtype=np.float32).reshape(4, 3)

        def forward(x):
            return x @ w                           # closure-captured weights

        x = np.ones((2, 4), np.float32)
        e1 = ExecutionEngine(cache=PersistentCompileCache(disk))
        h1 = e1.program(forward, (x,), fingerprint="roundtrip:w:v1")
        assert h1.source == "compile"
        assert e1.cache.stats()["entries"] == 1
        assert metrics.counter("engine.cache_miss").value == 1

        e2 = ExecutionEngine(cache=PersistentCompileCache(disk))
        h2 = e2.program(forward, (x,), fingerprint="roundtrip:w:v1")
        assert h2.source == "disk"
        assert h2.key == h1.key
        assert metrics.counter("engine.cache_hit").value == 1
        np.testing.assert_allclose(
            np.asarray(h2(x)), np.asarray(h1(x)), rtol=1e-6
        )
        np.testing.assert_allclose(np.asarray(h2(x)), x @ w, rtol=1e-6)

    def test_anonymous_functions_never_persist(self, tmp_path):
        cache = PersistentCompileCache(str(tmp_path / "exe"))
        eng = ExecutionEngine(cache=cache)
        h = eng.program(lambda x: x + 1, (np.zeros((2,), np.float32),))
        assert h.source == "compile"
        assert cache.stats()["entries"] == 0
        # in-memory reuse still works for the same function object
        fn = lambda x: x * 3  # noqa: E731
        k1 = eng.program(fn, (np.zeros((2,), np.float32),)).key
        assert eng.program(fn, (np.zeros((2,), np.float32),)).source == "memory"
        assert k1 == eng.program(fn, (np.zeros((2,), np.float32),)).key

    def test_donation_changes_the_key(self, tmp_path):
        eng = ExecutionEngine(persistent=False)

        def fn(x):
            return x + 1.0

        a = eng.program(fn, (np.zeros((2,), np.float32),),
                        fingerprint="d:t", donate=False)
        b = eng.program(fn, (np.zeros((2,), np.float32),),
                        fingerprint="d:t", donate=True)
        assert a.key != b.key

    def test_corrupt_entry_is_a_miss_not_a_failure(self, tmp_path):
        disk = str(tmp_path / "exe")
        eng = ExecutionEngine(cache=PersistentCompileCache(disk))
        h = eng.program(
            lambda x: x - 1, (np.zeros((2,), np.float32),),
            fingerprint="corrupt:t",
        )
        (key, exe_path, _, _), = eng.cache.entries()
        with open(exe_path, "wb") as fh:
            fh.write(b"not a pickle")
        e2 = ExecutionEngine(cache=PersistentCompileCache(disk))
        h2 = e2.program(
            lambda x: x - 1, (np.zeros((2,), np.float32),),
            fingerprint="corrupt:t",
        )
        assert h2.key == h.key and h2.source == "compile"


# ----------------------------------------------------------------------
# spans: engine.compile only on true compiles
# ----------------------------------------------------------------------
class TestCompileSpans:
    def test_warm_start_emits_no_compile_span(self, tmp_path):
        disk = str(tmp_path / "exe")

        def fn(x):
            return jnp.tanh(x)

        cold_sink = JsonlTraceSink()
        tracer.enable(cold_sink)
        e1 = ExecutionEngine(cache=PersistentCompileCache(disk))
        e1.program(fn, (np.zeros((2,), np.float32),), fingerprint="span:t",
                   name="span_fn")
        tracer.disable()
        compiles = [
            s for s in cold_sink.spans() if s["name"] == "engine.compile"
        ]
        assert len(compiles) == 1
        assert compiles[0]["attributes"]["program"] == "span_fn"
        assert compiles[0]["attributes"]["fingerprint"] == "span:t"

        warm_sink = JsonlTraceSink()
        tracer.enable(warm_sink)
        e2 = ExecutionEngine(cache=PersistentCompileCache(disk))
        h = e2.program(fn, (np.zeros((2,), np.float32),),
                       fingerprint="span:t", name="span_fn")
        tracer.disable()
        assert h.source == "disk"
        assert not [
            s for s in warm_sink.spans() if s["name"] == "engine.compile"
        ]


# ----------------------------------------------------------------------
# dispatch window
# ----------------------------------------------------------------------
class TestDispatchWindow:
    def test_strict_order_and_meta_passthrough(self):
        window = DispatchWindow(depth=2)
        got = []
        for i in range(5):
            for host, meta in window.submit(jnp.full((3,), i), meta=i):
                got.append((host, meta))
        assert [m for _, m in got] == [0, 1, 2]      # depth 2 held back
        assert len(window) == 2
        for host, meta in window.drain():
            got.append((host, meta))
        assert [m for _, m in got] == [0, 1, 2, 3, 4]
        for host, meta in got:
            assert isinstance(host, np.ndarray)
            np.testing.assert_array_equal(host, np.full((3,), meta))
        assert metrics.gauge("engine.inflight").value == 0

    def test_depth_zero_is_serial(self):
        window = DispatchWindow(depth=0)
        out = window.submit(jnp.ones((2,)), meta="only")
        assert len(out) == 1 and out[0][1] == "only"
        assert len(window) == 0

    def test_abandon_clears_without_fetching(self):
        window = DispatchWindow(depth=4)
        for i in range(3):
            window.submit(jnp.zeros((1,)), meta=i)
        assert len(window) == 3
        window.abandon()
        assert len(window) == 0
        assert metrics.gauge("engine.inflight").value == 0
        assert list(window.drain()) == []

    @pytest.mark.parametrize("asked,depth", [(None, 2), (5, 5), (-1, 0)])
    def test_depth_is_the_constant_unless_the_caller_names_one(
            self, asked, depth):
        from sparkdl_tpu.engine.executor import DEFAULT_DEPTH

        assert DEFAULT_DEPTH == 2
        assert DispatchWindow().depth == DEFAULT_DEPTH
        assert DispatchWindow(depth=asked).depth == depth

    def test_capture_errors_delivers_fetch_failure_with_meta(self):
        class Boom:
            def __array__(self, *a, **k):
                raise ValueError("device said no")

        window = DispatchWindow(depth=4, capture_errors=True)
        window.submit(jnp.ones((2,)), meta="ok")
        window.submit(Boom(), meta="doomed")
        out = list(window.drain())
        assert [m for _, m in out] == ["ok", "doomed"]
        assert isinstance(out[0][0], np.ndarray)
        failure = out[1][0]
        assert isinstance(failure, FetchFailure)
        assert "device said no" in str(failure.error)

    def test_uncaptured_fetch_failure_raises(self):
        class Boom:
            def __array__(self, *a, **k):
                raise ValueError("boom")

        window = DispatchWindow(depth=0)
        with pytest.raises(ValueError):
            window.submit(Boom(), meta=None)


# ----------------------------------------------------------------------
# serving warmup report (compile vs cache load)
# ----------------------------------------------------------------------
class TestServingWarmupReport:
    def test_restarted_cache_loads_and_reports(self, tmp_path, monkeypatch):
        from sparkdl_tpu.serving.cache import ProgramCache

        monkeypatch.setenv(
            "SPARKDL_COMPILE_CACHE", str(tmp_path / "serving-exe")
        )

        def forward(x):
            return x * 2.0

        cold = ProgramCache(
            maxsize=8, compile_counter=metrics.counter("serving.compiles")
        )
        buckets = cold.warmup(
            "m1", forward, item_shape=(4,), dtype=np.float32,
            buckets=(1, 2), fingerprint="warm:test:v1",
        )
        assert buckets == (1, 2)
        report = cold.stats()["warmup"]["m1"]
        assert {b: r["source"] for b, r in report.items()} == {
            1: "compile", 2: "compile"
        }
        assert all(r["seconds"] >= 0 for r in report.values())
        assert metrics.counter("serving.compiles").value == 2
        assert metrics.counter("serving.cache_load").value == 0

        # "restart": a fresh ProgramCache in the same process, same disk
        warm = ProgramCache(
            maxsize=8, compile_counter=metrics.counter("serving.compiles")
        )
        warm.warmup(
            "m1", forward, item_shape=(4,), dtype=np.float32,
            buckets=(1, 2), fingerprint="warm:test:v1",
        )
        report = warm.stats()["warmup"]["m1"]
        assert {b: r["source"] for b, r in report.items()} == {
            1: "disk", 2: "disk"
        }
        assert metrics.counter("serving.compiles").value == 2  # unchanged
        assert metrics.counter("serving.cache_load").value == 2
        assert warm.stats()["persistent"]["entries"] == 2

    def test_unfingerprinted_warmup_stays_off_disk(self, tmp_path,
                                                   monkeypatch):
        from sparkdl_tpu.serving.cache import ProgramCache

        monkeypatch.setenv(
            "SPARKDL_COMPILE_CACHE", str(tmp_path / "anon-exe")
        )
        cache = ProgramCache(maxsize=4)
        cache.warmup(
            "anon", lambda x: x + 1, item_shape=(3,), dtype=np.float32,
            buckets=(1,),
        )
        assert cache.stats()["persistent"]["entries"] == 0
        assert cache.stats()["warmup"]["anon"][1]["source"] == "compile"
