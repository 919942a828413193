"""Async dispatch window: keep N batches in flight, fetch without stalls.

jax dispatch is asynchronous — ``fn(batch)`` returns a future-like
device array immediately — but a naive loop squanders that by calling
``jax.device_get`` right after dispatch, serializing host transfer
behind device compute.  This window is the one engine-owned discipline
for that, in the transformers' loop
(``transformers/utils.run_batched_partitions``), serving and streaming:

- ``submit(result, meta)`` enqueues a dispatched device result and
  immediately starts its **device→host copy in the background**
  (``copy_to_host_async`` on every array leaf), then pops-and-fetches
  only what exceeds the window depth;
- with depth N, batch i's host fetch happens while batches i+1..i+N are
  still computing, so the transfer fully hides behind device compute;
- depth 0 degrades to strict dispatch→fetch serialization.

The window is deliberately not a thread: jax's own runtime provides the
asynchrony; this class only decides *when* to synchronize.

It is also where the program keeps its two accounts of the device, both
as boundary spans in the tracer's ring (:mod:`sparkdl_tpu.obs.trace`).
``engine.starved`` (:class:`_Outstanding`) is what the dispatching
thread can tell by counting: the stretches in which no result of any
window was dispatched and unfetched.  It sees every moment the host
left the device without work and misses the rest: a batch still on its
way to an idle device, a result that was ready long before its fetch,
which program ran when.  ``engine.device`` and ``engine.transfer``
(:class:`_CompletionWatcher`) are the device's own timeline, taken by
one thread that only waits: when each dispatch a caller names
(``submit(..., program=)``) became ready and, from stream order, when it
began to compute; when each placed batch arrived.  They see what the
device did and miss only what a wait cannot be begun for: an input
that its program's donation deleted before the wait started
(``observed=False``).
Read ``engine.device`` for how busy the device was and with what;
``engine.starved`` stays as the count that needs no second thread.
"""

from __future__ import annotations

import logging
import sys
import threading
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from sparkdl_tpu.obs.trace import Span, tracer

logger = logging.getLogger(__name__)

#: the in-flight window depth of a :class:`DispatchWindow` made without
#: one: one batch computing, one transferring
DEFAULT_DEPTH = 2


def _start_host_copy(result: Any) -> None:
    """Kick off the async device→host copy for every array leaf of a
    dispatched result, so the later blocking fetch finds the bytes
    already (or nearly) on host."""
    import jax

    for leaf in jax.tree_util.tree_leaves(result):
        copy = getattr(leaf, "copy_to_host_async", None)
        if copy is not None:
            try:
                copy()
            except Exception:
                # fetch will surface any real error; the async copy is
                # purely an overlap optimization
                pass


def _fetch_host(result: Any) -> Any:
    """Blocking device→host materialization of a dispatched result
    (numpy leaves).  Single arrays come back as one ndarray; pytrees
    keep their structure.

    Fetched leaves must be process-OWNED, never views into device
    buffers: on CPU ``device_get`` is zero-copy, and an executable —
    disk-loaded ones in particular — may hand later calls the same
    output buffer, silently rewriting any view a caller still holds
    (request futures read their rows long after the next batch ran).
    A view (``base`` set) is therefore copied; a genuine transfer
    (owned array, the device path) is returned as-is."""
    import jax

    def leaf_to_host(leaf):
        arr = np.asarray(jax.device_get(leaf))
        if arr.base is not None or not arr.flags.owndata:
            arr = np.array(arr)
        return arr

    return jax.tree_util.tree_map(leaf_to_host, result)


class _Outstanding:
    """Results dispatched and not yet fetched, over every
    :class:`DispatchWindow` of the process.

    While the count is zero the device provably has no work from this
    process.  The clock is stamped when the count falls to zero, and the
    next ``submitted`` that finds it zero records the boundary span
    ``engine.starved`` from the stamp to now (none before the first
    submit of the process).  That is the host's account: what the
    dispatching thread knows without waiting for anything.  It leaves
    out a device that waits for a dispatched batch's input and one that
    finished long before the fetch; ``engine.device``
    (:class:`_CompletionWatcher`) measures those.  A window dropped with
    results in flight and never ``abandon``-ed holds the count up, which
    only shortens the stretches."""

    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0
        self._empty_since_ns: Optional[int] = None

    def submitted(self) -> None:
        with self._lock:
            since = self._empty_since_ns if self._count == 0 else None
            self._count += 1
        if since is not None:
            with tracer.boundary("engine.starved", start_ns=since):
                pass

    def fetched(self, n: int = 1) -> None:
        if n <= 0:
            return
        with self._lock:
            self._count -= n
            if self._count == 0:
                self._empty_since_ns = tracer.clock_ns()


_outstanding = _Outstanding()


class _Watched:
    """One entry of the watcher's queue: an array to wait for, and the
    span to write when it is ready."""

    __slots__ = ("leaf", "name", "stamp_ns", "parent", "attributes",
                 "input", "ready_ns", "dropped")

    def __init__(self, leaf: Any, name: str, stamp_ns: int, parent: Span,
                 attributes: Dict[str, Any],
                 input: Optional["_Watched"] = None):
        self.leaf, self.name, self.stamp_ns = leaf, name, stamp_ns
        self.parent, self.attributes = parent, attributes
        #: the transfer whose batch this dispatch computes on
        self.input = input
        #: when the wait returned; None until then, and for a wait that failed
        self.ready_ns: Optional[int] = None
        self.dropped = False


class _CompletionWatcher:
    """The device's timeline, from ONE daemon thread that only waits.

    A device runs one program at a time in the order of dispatch.  So
    the thread calls ``block_until_ready()`` (which releases the GIL) on
    one leaf of every watched result, in submission order, and writes at
    each return the backdated boundary span ``engine.device``: from the
    latest of the entry before's ready time, the arrival of this
    entry's input where that was seen, and its own dispatch stamp, to
    now; ``queued_ms`` is what lay between the dispatch and that start.
    A placed batch handed over before its dispatch is waited for the
    same way and gives ``engine.transfer``, from the start of its
    ``engine.place`` to its arrival; the thread reaches it when the
    dispatch before is ready, which is early enough: a batch that had
    arrived by then exposed nothing.  A donation that XLA takes up (an
    output can live in the input's buffer) deletes the array at
    dispatch: a wait begun before goes on to the arrival, one begun
    after fails at once, and the span closes there with
    ``observed=False`` (counter ``engine.transfers_unobserved``).  One
    that XLA drops (the featurizer's uint8 batch fits no float32
    output) leaves the array alive, and every arrival is seen.

    Both are children of the span the caller names (a partition's root)
    on this thread, so no reader of the dispatching thread's self times
    sees them.  The thread observes and decides nothing: nobody waits
    for it, and it holds its lock across no wait.  What it cannot give
    better than the GIL's switch interval (5 ms while another thread
    computes in Python) moves time between neighbouring spans and
    cancels in their sum; a span whose parent ended meanwhile ends with
    the parent, so the newest span of the ring is never this thread's."""

    def __init__(self):
        self._cond = threading.Condition()
        self._entries: "deque[_Watched]" = deque()
        self._thread: Optional[threading.Thread] = None
        self._waiting_for: Optional[_Watched] = None
        self._ready_ns = 0  # when the dispatch before became ready

    def watch(self, entry: _Watched) -> None:
        with self._cond:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="sparkdl-completion-watcher",
                    daemon=True)
                self._thread.start()
            self._entries.append(entry)
            self._cond.notify_all()

    def drop(self, entries: List[_Watched]) -> None:
        """Forget ``entries``: none of them gets a span, not even the one
        being waited for."""
        with self._cond:
            for entry in entries:
                entry.dropped = True
            self._entries = deque(
                e for e in self._entries if not e.dropped)

    def settle(self, timeout: Optional[float] = None) -> bool:
        """Wait (the tests do, no dispatching thread ever) until every
        entry handed over so far has its span; False on timeout."""
        with self._cond:
            return self._cond.wait_for(
                lambda: not self._entries and self._waiting_for is None,
                timeout)

    def _run(self) -> None:
        while True:
            with self._cond:
                self._waiting_for = None
                self._cond.notify_all()
                while not self._entries:
                    self._cond.wait()
                entry = self._waiting_for = self._entries.popleft()
            try:
                self._wait_for(entry)
            except Exception:  # the thread outlives whatever one entry does
                logger.exception("completion watcher: %s", entry.name)

    def _wait_for(self, entry: _Watched) -> None:
        leaf, entry.leaf = entry.leaf, None
        failed = False
        try:
            wait = getattr(leaf, "block_until_ready", None)
            if wait is not None:
                wait()
        except Exception:
            # a donated input deleted before the wait began, or a
            # computation that failed (which the fetch raises as before)
            failed = True
        del leaf
        attributes = dict(entry.attributes)
        if entry.name == "engine.device":
            arrived = entry.input.ready_ns if entry.input else None
            start = max(self._ready_ns, arrived or 0, entry.stamp_ns)
            attributes["queued_ms"] = (start - entry.stamp_ns) / 1e6
            if failed:
                attributes["error"] = True
        else:
            start = entry.stamp_ns
            attributes["observed"] = not failed
            if failed:
                from sparkdl_tpu.utils.metrics import metrics

                metrics.counter("engine.transfers_unobserved").add(1)
        # ready before its fetch came back, and that before its partition
        # ended: where the GIL kept this thread longer, the parent's end
        # is the nearer bound (and no span of a partition outlives it)
        end = min(tracer.clock_ns(), entry.parent.end_ns or sys.maxsize)
        start = min(start, end)
        if not entry.dropped:
            with tracer.boundary(entry.name, parent=entry.parent,
                                 start_ns=start, end_ns=end, **attributes):
                pass
        if entry.name == "engine.device":
            self._ready_ns = end
        elif not failed:
            entry.ready_ns = end


_watcher = _CompletionWatcher()


def _host_nbytes(host: Any) -> int:
    import jax

    return sum(
        int(getattr(leaf, "nbytes", 0))
        for leaf in jax.tree_util.tree_leaves(host)
    )


class FetchFailure:
    """A fetch that raised, delivered in-order with its ``meta`` instead of
    aborting the window (``capture_errors=True`` mode — serving needs the
    meta back to fail the right requests' futures)."""

    __slots__ = ("error",)

    def __init__(self, error: BaseException):
        self.error = error

    def __repr__(self):
        return f"FetchFailure({self.error!r})"


class DispatchWindow:
    """A depth-N in-flight executor for dispatched device results.

    Usage::

        window = DispatchWindow(depth=2)
        for chunk in chunks:
            for host, meta in window.submit(fn(chunk), meta=k):
                consume(host, meta)          # arrives depth batches late
        for host, meta in window.drain():
            consume(host, meta)

    Results come back strictly in submission order.  ``meta`` rides
    through untouched (callers pass the unpadded row count).  With
    ``capture_errors=True`` a failed fetch yields ``(FetchFailure(exc),
    meta)`` instead of raising, so the caller never loses the meta of a
    failed batch.  The ``engine.inflight`` gauge tracks the live window
    depth.

    A caller that knows what it dispatched names it:
    ``submit(result, meta, program=name, parent=span, rows=n)`` has the
    process's :class:`_CompletionWatcher` write an ``engine.device`` span
    under ``span`` when the result is ready, and ``watch_transfer`` an
    ``engine.transfer`` for the batch the next submit computes on.  The
    window waits for neither; without ``program`` nothing is watched.
    """

    def __init__(self, depth: Optional[int] = None,
                 capture_errors: bool = False):
        self.depth = DEFAULT_DEPTH if depth is None else max(0, int(depth))
        self.capture_errors = bool(capture_errors)
        #: (result, meta, what the watcher holds of it)
        self._inflight: "deque[Tuple[Any, Any, Tuple[_Watched, ...]]]" = (
            deque())
        #: the transfer handed over for the next submit
        self._transfer: Optional[_Watched] = None
        from sparkdl_tpu.utils.metrics import metrics

        self._gauge = metrics.gauge("engine.inflight")

    def __len__(self) -> int:
        return len(self._inflight)

    @property
    def has_room(self) -> bool:
        """True while another ``submit`` would not force a blocking
        fetch — the "device could take this batch NOW" signal that both
        the coalesce linger (`flush_early`) and the ragged slot loop
        key off."""
        return len(self._inflight) <= self.depth

    def pop_ready(self) -> List[Tuple[Any, Any]]:
        """Fetch-and-return only what exceeds the window depth (what
        ``submit`` would have returned, without submitting anything) —
        the ragged loop's way to free slots held by overflowing batches
        before admitting more work."""
        out = []
        while len(self._inflight) > self.depth:
            out.append(self._pop())
        return out

    def _pop(self) -> Tuple[Any, Any]:
        result, meta, _ = self._inflight.popleft()
        self._gauge.set(len(self._inflight))
        try:
            with tracer.boundary("engine.fetch_wait") as span:
                host = _fetch_host(result)
                span.set_attribute("bytes", _host_nbytes(host))
            return host, meta
        except Exception as exc:
            if not self.capture_errors:
                raise
            return FetchFailure(exc), meta  # delivered, not raised
        finally:
            _outstanding.fetched()

    def watch_transfer(self, placed: Any, start_ns: int,
                       parent: Optional[Span], **attributes: Any) -> None:
        """Hand the batch just placed, BEFORE its dispatch, to the
        watcher: ``engine.transfer`` under ``parent`` from ``start_ns``
        (the placing's start) to the arrival of its last leaf, which the
        next watched ``submit`` takes as its input's."""
        import jax

        leaves = jax.tree_util.tree_leaves(placed)
        if parent is None or not leaves:
            return
        self._transfer = _Watched(
            leaves[-1], "engine.transfer", start_ns, parent, attributes)
        _watcher.watch(self._transfer)

    def submit(self, result: Any, meta: Any = None, *,
               program: Optional[str] = None, parent: Optional[Span] = None,
               **attributes: Any) -> List[Tuple[Any, Any]]:
        """Enqueue a dispatched result; returns the (host_result, meta)
        pairs that just fell out of the window (possibly empty).
        ``program`` (with ``parent``, the span its ``engine.device`` goes
        under, and what else the span should say: ``rows``, ``steps``)
        has the result's completion watched."""
        watched: Tuple[_Watched, ...] = ()
        if program is not None and parent is not None:
            import jax

            transfer, self._transfer = self._transfer, None
            entry = _Watched(
                jax.tree_util.tree_leaves(result)[0], "engine.device",
                tracer.clock_ns(), parent,
                dict(attributes, program=program), input=transfer)
            _watcher.watch(entry)
            watched = (entry,) if transfer is None else (transfer, entry)
        _outstanding.submitted()
        _start_host_copy(result)
        self._inflight.append((result, meta, watched))
        self._gauge.set(len(self._inflight))
        out = []
        while len(self._inflight) > self.depth:
            out.append(self._pop())
        return out

    def drain(self) -> Iterator[Tuple[Any, Any]]:
        """Fetch everything still in flight, in order."""
        while self._inflight:
            yield self._pop()

    def abandon(self) -> None:
        """Drop in-flight results without fetching (error-path cleanup;
        the device arrays are released to GC, and the watcher forgets
        them: a result nobody fetched gets no span)."""
        unfetched = [e for _, _, watched in self._inflight for e in watched]
        if self._transfer is not None:
            unfetched.append(self._transfer)
            self._transfer = None
        if unfetched:
            _watcher.drop(unfetched)
        _outstanding.fetched(len(self._inflight))
        self._inflight.clear()
        self._gauge.set(0)
