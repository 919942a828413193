"""Structured tracing: spans with parent/child nesting and *explicit*
cross-thread propagation.

The ``metrics.*`` counters PRs 1–3 grew answer "how much / how fast" but
not "where did THIS request/step spend its time" — the question the
tf.data paper's stall attribution (arXiv:2101.12127) and TensorFlow's
first-class tracing layer (arXiv:1605.08695) exist to answer.  A
:class:`Span` is one timed region with attributes and point-in-time
events; a :class:`Tracer` maintains the context-local current span and
delivers finished spans to sinks (:mod:`sparkdl_tpu.obs.export`).

Design rules:

- **disabled by default, pay-nothing**: every instrumentation site is
  gated on one attribute read (``tracer.enabled``); with tracing off the
  hot loops see a single branch, no allocation (acceptance gate: <5%
  overhead on ``benchmarks/bench_data_pipeline.py``);
- **explicit propagation across threads**: the current span lives in a
  ``contextvars.ContextVar``, which deliberately does NOT leak into
  worker threads — a pipeline stage that moves work across a queue must
  ``capture()`` the span on the submitting side and re-attach it with
  :meth:`Tracer.use_span` on the worker (``data.prefetch`` / the
  threaded ``data.map`` / the serving micro-batcher all do; no ambient
  thread-local crosses a queue boundary silently);
- **monotonic timing, wall anchoring**: durations come from
  ``time.perf_counter`` (immune to clock steps); each span also records
  one ``time.time`` start so exported traces can be correlated with
  logs;
- **tail-aware sampling**: at production rates exporting every healthy
  span is waste — :meth:`Tracer.configure_sampling` keeps error spans
  and slow spans (``duration >= slow_ms``) unconditionally and samples
  the rest by a deterministic per-*trace* hash, so a kept trace is kept
  whole (no orphaned children).  Dropped spans count into
  ``sparkdl.spans_sampled_out``; context propagation is unaffected
  (sampling gates delivery to sinks, not span creation);
- **layer boundaries are always recorded**: :meth:`Tracer.boundary`
  spans sit only where work crosses a layer (a handful per batch, not
  per item or request), so they are made whether or not the tracer is
  enabled, kept in one bounded in-memory ring (:meth:`Tracer.recent`)
  and, while open, mirrored into any running ``jax.profiler`` session
  as ``sparkdl.<name>`` annotations — the program's spans then share
  the profiler's clock with the device plane.
"""

from __future__ import annotations

import os
import random
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

ENV_SEED = "SPARKDL_TRACE_SEED"

#: boundary spans the ring holds: a cached featurize pass makes about 25
#: a second, so ten 30 s windows of it
BOUNDARY_RING_SIZE = 8192
#: what a boundary span is called in a ``jax.profiler`` trace
ANNOTATION_PREFIX = "sparkdl."

#: a remote span reference carried over the wire: ``(trace_id, span_id)``
RemoteParent = Tuple[int, int]


class _IdSource:
    """Process-seeded random 64-bit span/trace ids.

    Sequential per-process counters collide the moment traces are
    stitched across processes (every replica starts at 1), so ids come
    from a per-process ``random.Random``: seeded from ``os.urandom``
    normally, or — under ``SPARKDL_TRACE_SEED`` — deterministically from
    the seed mixed with ``os.getpid()``, so tests get reproducible ids
    per process while two replicas under the same seed still cannot
    collide.  The pid is re-checked on every draw: a fork gets a fresh
    stream instead of replaying the parent's.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._rng: Optional[random.Random] = None
        self._pid: Optional[int] = None

    def _reseed(self, pid: int) -> random.Random:
        seed_spec = os.environ.get(ENV_SEED, "").strip()
        if seed_spec:
            rng = random.Random(f"{seed_spec}:{pid}")
        else:
            rng = random.Random(int.from_bytes(os.urandom(8), "big") ^ pid)
        self._rng = rng
        self._pid = pid
        return rng

    def next_id(self) -> int:
        """A nonzero random 63-bit id (always positive, JSON-safe)."""
        pid = os.getpid()
        with self._lock:
            rng = self._rng
            if rng is None or pid != self._pid:
                rng = self._reseed(pid)
            return rng.getrandbits(63) | 1


_ids = _IdSource()


class BoundaryRecord(NamedTuple):
    """One finished boundary span as the ring keeps it (times on the
    ``time.perf_counter_ns`` clock)."""

    name: str
    span_id: int
    parent_id: Optional[int]
    thread_id: int
    start_ns: int
    end_ns: int
    attributes: Dict[str, Any]


def _profiler_annotation(name: str):
    """An open ``jax.profiler.TraceAnnotation`` for a boundary span, or
    None where jax is not loaded (this module never imports it: a
    process that has not touched jax has no profiler session to feed)."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return None
    annotation = profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
    annotation.__enter__()
    return annotation


class Span:
    """One timed region of work.

    Created through :meth:`Tracer.span` / :meth:`Tracer.start_span` —
    never directly.  Thread-safe for ``event``/``set_attribute`` (a
    serving request span is touched by the submitter and the batch
    worker); ``end()`` is idempotent.
    """

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "attributes",
        "events", "start_wall", "_start", "_end", "_tracer", "_lock",
        "boundary", "thread_id", "start_ns", "end_ns",
    )

    def __init__(self, tracer: "Tracer", name: str,
                 parent: Optional["Span"], attributes: Dict[str, Any],
                 remote: Optional[RemoteParent] = None,
                 boundary: bool = False, start_ns: Optional[int] = None):
        self._tracer = tracer
        self.boundary = boundary
        self.thread_id = threading.get_ident()
        self.name = name
        self.span_id = _ids.next_id()
        if parent is not None:
            self.trace_id = parent.trace_id
            self.parent_id = parent.span_id
        elif remote is not None:
            # a parent in another process: its (trace_id, span_id) rode
            # the wire envelope — this span joins that trace
            self.trace_id = int(remote[0])
            self.parent_id = int(remote[1])
        else:
            self.trace_id = _ids.next_id()
            self.parent_id = None
        self.attributes = dict(attributes)
        self.events: List[Dict[str, Any]] = []
        self.start_wall = time.time()
        now_ns = tracer.clock_ns()
        if start_ns is None:
            start_ns = now_ns
        else:
            # a boundary span backdated to a stamp its caller took from
            # the same clock: the wall anchor moves back with it
            self.start_wall -= (now_ns - start_ns) / 1e9
        self.start_ns = start_ns
        self.end_ns: Optional[int] = None
        self._start = start_ns / 1e9
        self._end: Optional[float] = None
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def context(self) -> RemoteParent:
        """The wire form of this span: ``(trace_id, span_id)`` — what a
        client injects into the envelope so the remote side can open a
        child with ``start_span(..., remote=...)``."""
        return (self.trace_id, self.span_id)

    def set_attribute(self, key: str, value: Any) -> None:
        with self._lock:
            self.attributes[key] = value

    def event(self, name: str, **attrs: Any) -> None:
        """Record a point-in-time event (retry attempt, breaker flip,
        coalescing decision) with its offset from span start."""
        evt = {"name": name, "offset_ms": self.offset_ms(), **attrs}
        with self._lock:
            self.events.append(evt)

    def offset_ms(self) -> float:
        return (time.perf_counter() - self._start) * 1000.0

    @property
    def ended(self) -> bool:
        return self._end is not None

    @property
    def duration_ms(self) -> Optional[float]:
        if self._end is None:
            return None
        return (self._end - self._start) * 1000.0

    def end(self, end_ns: Optional[int] = None) -> None:
        """Close the span and deliver it to the tracer's sinks.
        Idempotent — a double end keeps the first timestamp.  ``end_ns``
        closes it at a ``clock_ns`` stamp already past, for a maker that
        learnt of the end late."""
        with self._lock:
            if self._end is not None:
                return
            self.end_ns = self._tracer.clock_ns() if end_ns is None else end_ns
            self._end = self.end_ns / 1e9
        self._tracer._deliver(self)

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        """The export form (what :class:`~sparkdl_tpu.obs.export.
        JsonlTraceSink` writes, one JSON object per line)."""
        with self._lock:
            return {
                "name": self.name,
                "trace_id": self.trace_id,
                "span_id": self.span_id,
                "parent_id": self.parent_id,
                "start_unix_s": round(self.start_wall, 6),
                "duration_ms": (
                    round(self.duration_ms, 4) if self.ended else None
                ),
                "attributes": dict(self.attributes),
                "events": list(self.events),
            }

    def __repr__(self):
        state = f"{self.duration_ms:.2f}ms" if self.ended else "open"
        return (
            f"<Span {self.name!r} id={self.span_id} "
            f"parent={self.parent_id} {state}>"
        )


class Tracer:
    """Process-wide span factory + context-local current span.

    Off by default: :meth:`span` returns a no-op context and
    :meth:`current` returns None until :meth:`enable` installs at least
    the enabled flag (sinks are optional — spans without a sink still
    propagate context, e.g. for tests reading ``current()``).
    :meth:`boundary` spans alone are made either way, and kept in the
    ring :meth:`recent` reads.
    """

    def __init__(self):
        # contextvars (not threading.local): nested spans restore the
        # previous current on exit, and NEW threads start with no
        # current span — cross-thread propagation is explicit by design
        import contextvars

        self._current: "contextvars.ContextVar[Optional[Span]]" = (
            contextvars.ContextVar("sparkdl_current_span", default=None)
        )
        self._lock = threading.Lock()
        self._sinks: tuple = ()
        self.enabled = False
        # tail-aware sampling: 1.0 = keep everything (the default);
        # slow_ms None = no slow-span exemption configured
        self._sample_rate = 1.0
        self._sample_slow_ms: Optional[float] = None
        #: the spans' clock; a test puts a scripted one here
        self.clock_ns: Callable[[], int] = time.perf_counter_ns
        self._ring: "deque[BoundaryRecord]" = deque(maxlen=BOUNDARY_RING_SIZE)
        self._ring_lock = threading.Lock()

    # -- lifecycle -----------------------------------------------------
    def enable(self, sink: Optional[Callable[[Dict[str, Any]], None]] = None
               ) -> "Tracer":
        """Turn tracing on, optionally adding ``sink`` (a callable
        receiving each finished span's ``to_dict()``)."""
        with self._lock:
            if sink is not None and sink not in self._sinks:
                self._sinks = self._sinks + (sink,)
            self.enabled = True
        return self

    def disable(self) -> None:
        """Turn tracing off, drop all sinks, and reset sampling (tests
        use this to restore the pay-nothing default)."""
        with self._lock:
            self.enabled = False
            self._sinks = ()
            self._sample_rate = 1.0
            self._sample_slow_ms = None

    def add_sink(self, sink: Callable[[Dict[str, Any]], None]) -> None:
        with self._lock:
            if sink not in self._sinks:
                self._sinks = self._sinks + (sink,)

    def remove_sink(self, sink: Callable[[Dict[str, Any]], None]) -> None:
        """Detach one sink; unknown sinks are ignored (teardown paths
        must be idempotent)."""
        with self._lock:
            self._sinks = tuple(s for s in self._sinks if s is not sink)

    def configure_sampling(
        self, rate: float, slow_ms: Optional[float] = None,
    ) -> None:
        """Tail-aware sampling policy for finished spans.

        ``rate`` is the keep probability for *healthy* traces in
        ``[0, 1]``; spans with an error attribute, and spans at least
        ``slow_ms`` long, are always kept — the tail is the signal.
        The keep decision hashes ``trace_id`` (Knuth multiplicative
        hash), so every span of a sampled trace is kept and every span
        of a dropped trace is dropped — no orphaned parents."""
        if not 0.0 <= rate <= 1.0:
            raise ValueError(f"sample rate must be in [0, 1], got {rate}")
        if slow_ms is not None and slow_ms < 0:
            raise ValueError(f"slow_ms must be >= 0, got {slow_ms}")
        with self._lock:
            self._sample_rate = float(rate)
            self._sample_slow_ms = None if slow_ms is None else float(slow_ms)

    def _sampled_out(self, span: Span) -> bool:
        """True when tail-aware sampling says to drop this span."""
        rate = self._sample_rate
        if rate >= 1.0:
            return False
        attrs = span.attributes
        if any(k in attrs for k in ("error", "error_class", "exception")):
            return False
        slow_ms = self._sample_slow_ms
        if slow_ms is not None:
            dur = span.duration_ms
            if dur is not None and dur >= slow_ms:
                return False
        # deterministic per-trace coin: Knuth multiplicative hash mapped
        # onto [0, 1) — same trace, same verdict, any process
        coin = ((span.trace_id * 2654435761) & 0xFFFFFFFF) / 2**32
        return coin >= rate

    def _deliver(self, span: Span) -> None:
        if span.boundary:
            record = BoundaryRecord(
                span.name, span.span_id, span.parent_id, span.thread_id,
                span.start_ns, span.end_ns, span.attributes,
            )
            with self._ring_lock:
                self._ring.append(record)
            if not self.enabled:
                return
        if self._sampled_out(span):
            from sparkdl_tpu.utils.metrics import metrics

            metrics.counter("sparkdl.spans_sampled_out").add(1)
            return
        for sink in self._sinks:
            try:
                sink(span.to_dict())
            except Exception:  # pragma: no cover - a sink must not
                pass           # break the traced code path

    # -- context -------------------------------------------------------
    def current(self) -> Optional[Span]:
        """The context-local current span (None when tracing is off or
        no span is open on this thread/context)."""
        return self._current.get()

    def capture(self) -> Optional[Span]:
        """Explicit handle for crossing a queue/thread boundary: grab it
        on the submitting side, re-attach on the worker with
        :meth:`use_span`.  None when there is nothing to propagate —
        callers skip their wrapping entirely then (zero overhead)."""
        if not self.enabled:
            return None
        return self._current.get()

    @contextmanager
    def use_span(self, span: Optional[Span]):
        """Attach an EXISTING span as current for the block without
        ending it on exit — the cross-thread propagation primitive."""
        if span is None:
            yield None
            return
        token = self._current.set(span)
        try:
            yield span
        finally:
            self._current.reset(token)

    # -- cross-process stitching ---------------------------------------
    def ingest(self, span_dict: Dict[str, Any]) -> None:
        """Deliver an already-finished FOREIGN span dict straight to the
        sinks — the router calls this with replica spans piggybacked on
        a reply envelope.  No re-sampling: the emitting process already
        applied its tail-aware policy, and re-flipping the coin here
        could orphan a trace the replica chose to keep."""
        if not self.enabled:
            return
        for sink in self._sinks:
            try:
                sink(dict(span_dict))
            except Exception:  # pragma: no cover - a sink must not
                pass           # break the ingest path

    # -- span creation -------------------------------------------------
    def start_span(self, name: str, parent: Optional[Span] = None,
                   remote: Optional[RemoteParent] = None,
                   **attributes: Any) -> Optional[Span]:
        """A manually-ended span (serving request spans end from a
        future callback, not a ``with`` block).  Child of ``parent``
        (explicit), else of ``remote`` (a ``(trace_id, span_id)`` pair
        from another process's envelope), else of the current span;
        None when disabled."""
        if not self.enabled:
            return None
        if parent is None and remote is None:
            parent = self._current.get()
        return Span(self, name, parent, attributes, remote=remote)

    @contextmanager
    def span(self, name: str, parent: Optional[Span] = None,
             **attributes: Any):
        """Open a child span for the block: becomes the current span,
        ends (and is delivered) on exit.  With tracing disabled, yields
        None at the cost of one branch."""
        if not self.enabled:
            yield None
            return
        sp = self.start_span(name, parent=parent, **attributes)
        token = self._current.set(sp)
        try:
            yield sp
        finally:
            self._current.reset(token)
            sp.end()


    @contextmanager
    def boundary(self, name: str, parent: Optional[Span] = None,
                 start_ns: Optional[int] = None,
                 end_ns: Optional[int] = None, **attributes: Any):
        """Open a span where work crosses a LAYER boundary (a partition,
        a pack, a dispatch, a fetch: a handful per batch).  An ordinary
        :class:`Span` — current for the block, child of ``parent`` or of
        the current span, delivered to the sinks while the tracer is
        enabled — that is made also while it is disabled: every finished
        one goes into the ring :meth:`recent` returns.  While open it is
        a ``jax.profiler.TraceAnnotation("sparkdl.<name>")`` too, so a
        profiler session holds it on the device plane's clock.

        Across threads pass ``parent`` explicitly: :meth:`capture`
        returns None while tracing is disabled.  ``start_ns`` backdates
        the span to a ``clock_ns`` stamp taken when an interval began
        that only its end reveals (``engine.starved``); such a span
        cannot be annotated.  ``end_ns`` closes it at a stamp already past
        (``engine.device`` under a partition that ended before the
        watcher's thread got to write it): the ring takes the record when
        it is written, so such a one stands behind a span that ended
        after it."""
        if parent is None:
            parent = self._current.get()
        sp = Span(self, name, parent, attributes, boundary=True,
                  start_ns=start_ns)
        annotation = _profiler_annotation(name) if start_ns is None else None
        token = self._current.set(sp)
        try:
            yield sp
        finally:
            self._current.reset(token)
            if annotation is not None:
                annotation.__exit__(None, None, None)
            sp.end(end_ns)

    def start_boundary(self, name: str, **attributes: Any) -> Span:
        """A ROOT boundary span that its maker ends by hand
        (``span.end()``, from any thread), for a stretch no ``with``
        block can hold: two partitions of one dispatch loop are open at
        once on one thread.  It is never the current span, so its
        children name it as ``parent`` explicitly, and it is no profiler
        annotation (annotations of one thread nest, these overlap);
        ended, it goes into the ring like any other."""
        return Span(self, name, None, attributes, boundary=True)

    def recent(self) -> List[BoundaryRecord]:
        """A snapshot of the ring: the newest finished boundary spans,
        oldest first by END time (a parent follows its children, but for
        one closed at a stamp already past: ``boundary(end_ns=)``)."""
        with self._ring_lock:
            return list(self._ring)


#: the process-wide tracer (analog of ``utils.metrics.metrics``)
tracer = Tracer()


def current_span() -> Optional[Span]:
    """Module-level convenience for :meth:`Tracer.current`."""
    return tracer.current()


def record_event(name: str, **attrs: Any) -> None:
    """Attach an event to the current span, if any.

    The one-line hook low layers (``resilience``) call from cold paths:
    with tracing off it is a single attribute read, and with no span
    open it is a no-op — so a retry loop can always call it without
    knowing whether anyone is watching.
    """
    if not tracer.enabled:
        return
    span = tracer.current()
    if span is not None:
        span.event(name, **attrs)
