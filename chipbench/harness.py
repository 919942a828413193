"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the clock of a run, compile counting, the traced window,
the device's report and the result line.

Nothing here knows a cell, a configuration or a metric by name: a later PR
adds entries to ``BENCHMARK.json`` and files under ``chipbench/`` and edits
nothing that is there.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import shutil
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of ``workloads`` with everything found by name: its
    configuration, traffic mix, workload file, driver, the end-to-end metrics
    it reports and the per-layer metrics with their readers."""

    def __init__(self, name: str, root: str = ROOT):
        manifest = load_manifest(root)
        bench = os.path.join(root, "chipbench")
        entries = {w["name"]: w for w in manifest["workloads"]}
        if name not in entries:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json: {sorted(entries)}"
            )
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.run_seconds = int(manifest["run_seconds"])
        config = {c["name"]: c for c in manifest["configs"]}[self.entry["config"]]
        self.config = _read_json(os.path.join(root, config["file"]))
        self.traffic = _read_json(
            os.path.join(bench, "traffic", self.entry["traffic"] + ".json")
        )
        self.workload = _read_json(
            os.path.join(bench, "workloads", name + ".json")
        )
        self.driver = importlib.import_module(
            "chipbench.drivers." + self.workload["driver"]
        )
        self.end_to_end = [
            m for m in manifest["end_to_end"]
            if name in m.get("workloads", [name])
        ]
        self.per_layer = []
        for m in manifest["per_layer"]:
            if name not in m.get("workloads", [name]):
                continue
            spec = _read_json(os.path.join(bench, "metrics", m["name"] + ".json"))
            reader = importlib.import_module("chipbench.readers." + spec["reader"])
            self.per_layer.append((m, spec.get("args", {}), reader))


def peaks_for(device_kind: str) -> dict:
    table = _read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in chipbench/peaks.json "
            f"({sorted(table)}): add its published peaks with their source"
        )
    return table[device_kind]


def place_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent cache (and, under it, the program's executable
    store): where ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed
    directory inside the checkout — the path is part of the cache's key.
    Must run before jax is imported."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not path:
        path = os.path.join(root, ".compile_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.makedirs(path, exist_ok=True)
    return path


def device_report() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes():
    """The peak on the fullest chip (None where the backend keeps none):
    the buffers' peak (``peak_bytes_in_use``) plus what the runtime reserved
    for the compiled programs' temporaries (``peak_bytes_reserved``) — on a
    TPU the two are counted apart, and a program's temporaries are most of
    what it occupies (PERF.md section 4)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use") is not None:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


class CompileMeter:
    """Compilations and the seconds JAX spent on them (tracing, lowering,
    backend compile or a fetch from the persistent cache), from JAX's own
    monitoring events — so it covers every program, not only the engine's."""

    _DURATIONS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.backend_compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self._DURATIONS:
            self.seconds += seconds
            if event == self._DURATIONS[-1]:
                self.backend_compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {
            "seconds": self.seconds, "backend_compiles": self.backend_compiles,
            "cache_hits": self.cache_hits, "cache_misses": self.cache_misses,
        }

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: after[k] - before[k] for k in after}


class TracedWindow:
    """Runs the JAX profiler for ``seconds`` from ``delay`` seconds after
    :meth:`start`, on a thread of its own, so that it can sit inside one
    long call (a ``fit``).  On this chip ``stop_trace`` comes back only once
    the device has gone quiet, and a device that stays busy for long after
    the stop was asked for overflows the profiler, which then brings back no
    device data at all (PERF.md section 6).  So the traced span is put at the
    END of the measured window (:func:`trace_delay`): the stop is asked for a
    few seconds before the window closes."""

    def __init__(self, directory: str, delay: float, seconds: float):
        self.directory, self.delay, self.seconds = directory, delay, seconds
        self._thread = None
        self.error = None

    def _run(self):
        import jax

        try:
            time.sleep(self.delay)
            shutil.rmtree(self.directory, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.directory, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation("chipbench.trace_window"):
                    time.sleep(self.seconds)
            finally:
                jax.profiler.stop_trace()
        except Exception as exc:  # reported by the run, which then fails
            self.error = exc

    def start(self):
        self._thread = threading.Thread(target=self._run, name="chipbench-trace")
        self._thread.start()

    def finish(self):
        self._thread.join()
        if self.error is not None:
            raise self.error


def trace_delay(window_s: float, span_s: float, tail_s: float = 4.0) -> float:
    """Seconds into the window at which a traced run starts the profiler, so
    that the span ends ``tail_s`` before the window closes."""
    return max(0.0, window_s - span_s - tail_s)


def span(name: str):
    """A host span in the profiler's trace, for naming idle gaps."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench." + name)


class Comparison:
    """The numbers compared for ``correct``, each beside its limit."""

    def __init__(self):
        self.items = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.items.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        import math

        return bool(self.items) and all(
            math.isfinite(v) and v <= limit for _, v, limit in self.items
        )

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def lines(self) -> list:
        return [
            f"compared {n}: {v!r} (limit {lim!r}) "
            f"{'ok' if v <= lim else 'OVER'}"
            for n, v, lim in self.items
        ]
