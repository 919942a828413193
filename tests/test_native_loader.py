"""Single-flight contract of the native loaders (``native/__init__.py``
and ``native/pjrt.py``).

Regression for the lock-blocking finding sparkdl_check's interprocedural
pass pinned down: ``_load()`` used to hold the module lock across the
g++ subprocess and the dlopen, so *every* thread that merely asked
``is_available()`` — reachable from the transformer hot path via
``decode_image_batch`` — stalled behind a multi-second build.  The fix
mirrors ``serving/cache.py``: one thread claims the build via an Event,
the build runs with no lock held, waiters block on the Event only.
"""

import threading

import pytest

from sparkdl_tpu import native
from sparkdl_tpu.native import pjrt


@pytest.mark.parametrize("mod", [native, pjrt], ids=["batchpack", "pjrt"])
def test_load_builds_once_outside_the_lock(mod, monkeypatch, tmp_path):
    calls = []
    build_started = threading.Event()
    release_build = threading.Event()

    def slow_build():
        calls.append(1)
        build_started.set()
        assert release_build.wait(timeout=30.0), "test never released build"
        return False  # "toolchain unavailable": loader must yield None

    src = tmp_path / "src.cpp"
    src.write_text("// never compiled")
    monkeypatch.setattr(mod, "_build", slow_build)
    monkeypatch.setattr(mod, "_SRC_PATH", str(src))
    monkeypatch.setattr(mod, "_SO_PATH", str(tmp_path / "missing.so"))
    monkeypatch.setattr(mod, "_lib", None)
    monkeypatch.setattr(mod, "_tried", False)
    monkeypatch.setattr(mod, "_inflight", None)
    monkeypatch.delenv("SPARKDL_NO_NATIVE", raising=False)

    results = []
    threads = [
        threading.Thread(target=lambda: results.append(mod._load()))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    assert build_started.wait(timeout=30.0), "no thread reached the build"

    # THE regression assertion: while the build runs, the module lock is
    # free — an availability check can take it without waiting seconds
    assert mod._lock.acquire(timeout=5.0), (
        "module lock held across the native build — the single-flight "
        "pattern regressed to build-under-lock"
    )
    mod._lock.release()

    release_build.set()
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads)
    assert len(calls) == 1, "concurrent first callers must share one build"
    assert results == [None] * 4
    # the verdict is memoized: no second build attempt afterwards
    assert mod._load() is None
    assert len(calls) == 1


@pytest.mark.parametrize(
    "cxx, raises",
    [("false", True), ("/nonexistent/c++", False)],
    ids=["compiler-fails", "no-compiler"],
)
def test_failed_build_is_an_error_only_where_a_compiler_exists(
    cxx, raises, monkeypatch, tmp_path
):
    """No C++ compiler: the Python path is the path (``_build`` -> False).
    A compiler that fails: an error, not a quiet switch to the slower
    path."""
    src = tmp_path / "src.cpp"
    src.write_text("int main() { return 0; }")
    monkeypatch.setattr(native, "_SRC_PATH", str(src))
    monkeypatch.setattr(native, "_SO_PATH", str(tmp_path / "out.so"))
    monkeypatch.setenv("CXX", cxx)
    if raises:
        with pytest.raises(native.NativeBuildError, match="build failed"):
            native._build()
    else:
        assert native._build() is False
