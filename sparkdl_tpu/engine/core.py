"""ExecutionEngine — one owner for compile, caching, and dispatch.

Before this module, five call sites each reinvented a slice of program
management: ``transformers/utils.py`` kept ad-hoc jit caches,
``serving/cache.py`` owned its own per-bucket jit wrappers,
``udf/keras_image_model.py`` and the estimators jitted inline, and every
one of them paid lazy trace+compile on first touch in every process.
The engine replaces all of that with:

- **AOT compile** — programs are built eagerly via
  ``jax.jit(fn, donate_argnums).lower(*specs).compile()``, so compile
  cost is visible (``engine.compile`` span + timer) instead of hiding
  inside the first batch;
- **two-level caching** — a bounded in-memory LRU of live executables
  (process-wide, evictable) in front of the content-addressed
  :class:`~sparkdl_tpu.engine.cache.PersistentCompileCache` on disk
  (cross-process: a second process loads executables instead of
  recompiling);
- **donation** — ``donate=True`` donates the input batch buffers to the
  program (legal on the inference hot path: every loop builds a fresh
  padded batch per call and never touches it after dispatch), halving
  peak HBM for the batch and letting XLA alias input/output;
- **watchdogged** device-touching compile/load — a wedged backend turns
  into a typed ``DeviceUnresponsive`` instead of an unbounded hang
  (:mod:`sparkdl_tpu.resilience`).

Metrics: ``engine.cache_hit`` / ``engine.cache_miss`` count persistent
cache outcomes (in-memory hits are free and uncounted),
``engine.compile`` / ``engine.cache_load`` time the slow paths, and
``engine.inflight`` gauges the dispatch window.  ``engine.compile``
spans appear only on actual compiles — a traced warm start shows none.
What the device did with the dispatches is the dispatch window's to tell
(:mod:`~sparkdl_tpu.engine.executor`): ``engine.device`` /
``engine.transfer`` spans, the device's own timeline, and
``engine.starved``, the host's count of nothing dispatched.
"""

from __future__ import annotations

import itertools
import logging
import os
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import numpy as np

from sparkdl_tpu.engine.cache import (
    PersistentCompileCache,
    _runtime_descriptor,
    _sharding_descriptor,
    cache_key,
    enable_jax_cache,
)
from sparkdl_tpu.utils.lru import LRUCache

logger = logging.getLogger(__name__)

#: per-process stream distinguishing anonymous (non-persistable) functions
_anon_ids = itertools.count(1)

_COMPILE_TIMEOUT_ENV = "SPARKDL_ENGINE_COMPILE_TIMEOUT_S"
_DEFAULT_COMPILE_SOFT_S = 300.0
_DEFAULT_COMPILE_HARD_S = 1800.0


class _JaxCacheHits:
    """Counts the compiles that JAX's own persistent cache served (its
    monitoring event), so that :meth:`ExecutionEngine._resolve` can tell a
    fresh executable from a loaded one."""

    _EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.count = 0
        self._listening = False

    def listen(self) -> None:
        if not self._listening:
            import jax

            self._listening = True
            jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == self._EVENT:
            self.count += 1


_jax_cache_hits = _JaxCacheHits()


def _donate_argnums(donate, n_args: int) -> Tuple[int, ...]:
    """``donate`` as positions: True is every argument, a sequence itself."""
    if isinstance(donate, (tuple, list)):
        return tuple(int(i) for i in donate)
    return tuple(range(n_args)) if donate else ()


def _compile_timeouts() -> Tuple[float, float]:
    spec = os.environ.get(_COMPILE_TIMEOUT_ENV, "").strip()
    if not spec:
        return _DEFAULT_COMPILE_SOFT_S, _DEFAULT_COMPILE_HARD_S
    hard = float(spec)
    return min(hard, _DEFAULT_COMPILE_SOFT_S), hard


class ProgramHandle:
    """One resolved executable plus how it was obtained.

    ``source`` is ``"memory"`` (in-process LRU hit), ``"disk"``
    (persistent-cache load), or ``"compile"``; ``seconds`` is the
    resolve cost (0.0 for memory hits) — what serving's warmup report
    surfaces per bucket.
    """

    __slots__ = ("callable", "source", "seconds", "key")

    def __init__(self, callable: Callable, source: str, seconds: float,
                 key: str):
        self.callable = callable
        self.source = source
        self.seconds = seconds
        self.key = key

    def __call__(self, *args):
        return self.callable(*args)

    def __repr__(self):
        return (
            f"ProgramHandle(source={self.source!r}, "
            f"seconds={self.seconds:.3f}, key={self.key[:12]})"
        )


def _donation_safe_loaded(compiled) -> Callable:
    """Guard a disk-loaded executable that donates its inputs.

    XLA will take a host numpy buffer zero-copy, and donation then
    executes IN PLACE in memory the caller still owns — asynchronously,
    so the caller can read pre-execution bytes through a result view,
    watch its input array be rewritten underneath it, or hand the same
    (now-consumed) buffer to the next dispatch.  Freshly-compiled
    executables copy host inputs at device_put; loaded ones must get
    the same treatment: re-home every numpy leaf into a jax-owned
    buffer before the call so donation consumes memory jax controls."""

    def call(*args):
        import jax
        import jax.numpy as jnp

        safe = jax.tree_util.tree_map(
            lambda leaf: (
                jnp.array(leaf, copy=True)
                if isinstance(leaf, np.ndarray) else leaf
            ),
            args,
        )
        return compiled(*safe)

    return call


def _leaf_spec(leaf) -> Tuple[Tuple[int, ...], Any, Any]:
    """(shape, dtype, sharding) of one argument leaf.  jax arrays carry
    their committed sharding into the compiled program's calling
    convention; host arrays use default placement."""
    sharding = getattr(leaf, "sharding", None)
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return tuple(leaf.shape), leaf.dtype, sharding
    arr = np.asarray(leaf)
    return arr.shape, arr.dtype, None


class ExecutionEngine:
    """Process-wide program manager: bounded live-executable LRU over the
    persistent on-disk cache.

    One default instance (:data:`sparkdl_tpu.engine.engine`) serves the
    transformer/UDF/estimator hot paths; serving constructs its own per
    ``ProgramCache`` so its ``cache_size`` eviction contract stays real
    (an evicted program's executable is actually released).
    """

    def __init__(
        self,
        maxsize: int = 64,
        cache: Optional[PersistentCompileCache] = None,
        persistent: bool = True,
    ):
        # every program the package compiles comes through an engine, so
        # this is where JAX's own persistent cache gets its directory
        enable_jax_cache()
        self._programs = LRUCache(maxsize)
        self._meta: Dict[str, Dict[str, Any]] = {}
        self.cache = (
            cache if cache is not None
            else (PersistentCompileCache() if persistent else None)
        )

    # ------------------------------------------------------------------
    # resolution
    # ------------------------------------------------------------------
    def lookup(self, key: str):
        """The live executable for ``key``, or None (no side effects
        beyond LRU recency)."""
        return self._programs.get(key)

    def program(
        self,
        fn: Callable,
        example_args: Sequence[Any],
        fingerprint: Optional[str] = None,
        donate: Union[bool, Sequence[int]] = False,
        name: Optional[str] = None,
    ) -> ProgramHandle:
        """Resolve the executable for ``fn`` at the concrete signature of
        ``example_args`` (arrays or ShapeDtypeStructs; pytree args
        supported): in-memory LRU → persistent cache → AOT compile.

        ``donate`` is True (every argument), False, or the positions of
        the arguments to donate — a program that takes its weights as
        arguments donates its carried state and not them.

        ``fingerprint`` must durably identify the function *and any
        weights it closes over*; without one the program is compiled and
        LRU-cached but never persisted (baking unknown weights into a
        shared disk entry would be silently wrong).
        """
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(tuple(example_args))
        leaf_specs = [_leaf_spec(leaf) for leaf in leaves]
        key = self._key(fingerprint, treedef, leaf_specs, donate, fn)

        hit = self._programs.get(key)
        if hit is not None:
            return ProgramHandle(hit, "memory", 0.0, key)
        return self._resolve(
            fn, treedef, leaf_specs, key,
            fingerprint=fingerprint, donate=donate,
            name=name or getattr(fn, "__name__", "program"),
        )

    def function(
        self,
        fn: Callable,
        fingerprint: Optional[str] = None,
        donate: bool = False,
        name: Optional[str] = None,
    ) -> "EngineFunction":
        """Wrap ``fn`` so every call runs the engine-resolved executable
        for its concrete argument signature — the ``jax.jit`` replacement
        for the hot-path modules (which the ``ci/lint_no_raw_jit.py``
        gate keeps honest)."""
        return EngineFunction(self, fn, fingerprint=fingerprint,
                              donate=donate, name=name)

    # ------------------------------------------------------------------
    def _key(self, fingerprint, treedef, leaf_specs, donate, fn) -> str:
        fp = fingerprint
        if fp is None:
            # anonymous: key on the function object's engine-assigned id
            # (assigned once, never reused — id() could be recycled)
            fp = getattr(fn, "_engine_anon_id", None)
            if fp is None:
                fp = f"anon:{next(_anon_ids)}"
                try:
                    fn._engine_anon_id = fp
                except AttributeError:  # bound methods etc.
                    fp = f"anon:id:{id(fn)}"
        arg_specs = [
            (shape, np.dtype(dtype).str, _sharding_descriptor(sharding))
            for shape, dtype, sharding in leaf_specs
        ]
        arg_specs.append(((0,), str(treedef), None))  # pytree structure
        # True keeps the key it always had, (0,); positions are their own
        return cache_key(
            fp, arg_specs, donate_argnums=_donate_argnums(donate, 1))

    def _resolve(
        self, fn, treedef, leaf_specs, key, fingerprint, donate, name
    ) -> ProgramHandle:
        from sparkdl_tpu.utils.metrics import metrics

        persistable = fingerprint is not None and self.cache is not None
        soft_s, hard_s = _compile_timeouts()

        # --- persistent cache load (cross-process warm start) ----------
        if persistable and key in self.cache:
            from sparkdl_tpu.resilience.watchdog import watchdogged

            start = time.perf_counter()
            with metrics.timer("engine.cache_load").time():
                compiled = watchdogged(
                    self.cache.load, key,
                    soft_timeout_s=soft_s, hard_timeout_s=hard_s,
                    name="engine_cache_load",
                )
            if compiled is not None:
                if donate:
                    compiled = _donation_safe_loaded(compiled)
                elapsed = time.perf_counter() - start
                metrics.counter("engine.cache_hit").add(1)
                self._record_event("engine.cache_hit", key, name, elapsed)
                self._remember(key, compiled, fingerprint, name, "disk")
                return ProgramHandle(compiled, "disk", elapsed, key)
            # unloadable entry was evicted by the cache; fall through

        # --- AOT compile ----------------------------------------------
        import jax

        specs = jax.tree_util.tree_unflatten(
            treedef,
            [
                jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                if sharding is not None
                else jax.ShapeDtypeStruct(shape, dtype)
                for shape, dtype, sharding in leaf_specs
            ],
        )

        def build():
            jitted = jax.jit(
                fn, donate_argnums=_donate_argnums(donate, len(specs)))
            return jitted.lower(*specs).compile()

        from sparkdl_tpu.obs.trace import tracer
        from sparkdl_tpu.resilience.watchdog import watchdogged

        metrics.counter("engine.cache_miss").add(1)
        _jax_cache_hits.listen()
        hits_before = _jax_cache_hits.count
        start = time.perf_counter()
        with metrics.timer("engine.compile").time():
            if tracer.enabled:
                with tracer.span(
                    "engine.compile", program=name, key=key[:16],
                    fingerprint=fingerprint or "anonymous",
                    donate=donate,
                ):
                    compiled = watchdogged(
                        build, soft_timeout_s=soft_s, hard_timeout_s=hard_s,
                        name="engine_compile",
                    )
            else:
                compiled = watchdogged(
                    build, soft_timeout_s=soft_s, hard_timeout_s=hard_s,
                    name="engine_compile",
                )
        elapsed = time.perf_counter() - start
        self._remember(key, compiled, fingerprint, name, "compile")
        # An executable that JAX's own persistent cache served (the same
        # program under another fingerprint) is not stored again: that cache
        # serves it to the next process too, and an executable that was
        # loaded does not always serialise whole — on the CPU a stored copy
        # of one fails at its first run with "Function ... not found".
        if persistable and _jax_cache_hits.count == hits_before:
            self.cache.store(
                key, compiled,
                meta={
                    "fingerprint": fingerprint,
                    "program": name,
                    "args": [
                        [list(shape), np.dtype(dtype).str]
                        for shape, dtype, _ in leaf_specs
                    ],
                    "donate": donate,
                    "compile_seconds": round(elapsed, 3),
                    "runtime": _runtime_descriptor(),
                },
            )
        return ProgramHandle(compiled, "compile", elapsed, key)

    @staticmethod
    def _record_event(event: str, key: str, name: str, seconds: float):
        from sparkdl_tpu.obs.trace import record_event, tracer

        if tracer.enabled:
            record_event(event, key=key[:16], program=name,
                         seconds=round(seconds, 4))

    def _remember(self, key, compiled, fingerprint, name, source) -> None:
        self._programs[key] = compiled
        self._meta[key] = {
            "fingerprint": fingerprint, "program": name, "source": source,
        }
        if len(self._meta) > 4 * max(self._programs.maxsize, 1):
            self._meta = {
                k: v for k, v in self._meta.items() if k in self._programs
            }

    # ------------------------------------------------------------------
    # introspection / lifecycle
    # ------------------------------------------------------------------
    def evict(self, key: str) -> bool:
        """Drop one live executable (persistent entry untouched)."""
        if key in self._programs:
            del self._programs[key]
            self._meta.pop(key, None)
            return True
        return False

    def clear_memory(self) -> int:
        """Release every live executable (persistent entries untouched);
        returns how many were dropped."""
        keys = list(self._programs)
        for k in keys:
            del self._programs[k]
        self._meta.clear()
        return len(keys)

    def stats(self) -> Dict[str, Any]:
        live = list(self._programs)
        out = {
            "programs": len(live),
            "maxsize": self._programs.maxsize,
            "entries": [
                {
                    "key": k[:16],
                    **{
                        f: self._meta.get(k, {}).get(f)
                        for f in ("program", "fingerprint", "source")
                    },
                }
                for k in live
            ],
        }
        if self.cache is not None:
            out["persistent"] = self.cache.stats()
        return out


class EngineFunction:
    """Callable façade over engine-resolved executables: one compiled
    program per concrete (pytree structure, leaf shape/dtype/sharding)
    signature, resolved through the engine's LRU + persistent cache.

    Call with arrays (host or device-placed); the signature→key mapping
    is memoized so steady-state calls cost one dict lookup before the
    executable runs.
    """

    def __init__(self, engine: ExecutionEngine, fn: Callable,
                 fingerprint: Optional[str] = None, donate: bool = False,
                 name: Optional[str] = None):
        self._engine = engine
        self._fn = fn
        self.fingerprint = fingerprint
        self.donate = bool(donate)
        self.name = name or getattr(fn, "__name__", "engine_fn")
        self._keys: Dict[Any, str] = {}
        self.last_source: Optional[str] = None

    def _signature(self, args) -> Any:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(args)
        return (
            treedef,
            tuple(
                (
                    tuple(getattr(l, "shape", np.shape(l))),
                    str(getattr(l, "dtype", None) or np.asarray(l).dtype),
                    getattr(l, "sharding", None),
                )
                for l in leaves
            ),
        )

    def __call__(self, *args):
        sig = self._signature(args)
        key = self._keys.get(sig)
        if key is not None:
            compiled = self._engine.lookup(key)
            if compiled is not None:
                return compiled(*args)
        handle = self._engine.program(
            self._fn, args, fingerprint=self.fingerprint,
            donate=self.donate, name=self.name,
        )
        self._keys[sig] = handle.key
        self.last_source = handle.source
        return handle(*args)

    def __repr__(self):
        return (
            f"EngineFunction(name={self.name!r}, donate={self.donate}, "
            f"fingerprint={self.fingerprint!r}, "
            f"signatures={len(self._keys)})"
        )
