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

It is also where the host can tell that the device has nothing to do:
every window counts into one process-wide number of results dispatched
and not yet fetched (:class:`_Outstanding`), and the time that number
spends at zero is recorded as ``engine.starved`` boundary spans
(:mod:`sparkdl_tpu.obs.trace`).
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Iterator, List, Optional, Tuple

import numpy as np

from sparkdl_tpu.obs.trace import tracer

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
    submit of the process).  That is a lower bound on the device's idle
    time — exact where the host blocks on the last result of a
    partition — and, being a span in the tracer's ring, it can be laid
    over what the dispatching thread ran meanwhile.  A window dropped
    with results in flight and never ``abandon``-ed holds the count up,
    which only lowers the bound."""

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
    """

    def __init__(self, depth: Optional[int] = None,
                 capture_errors: bool = False):
        self.depth = DEFAULT_DEPTH if depth is None else max(0, int(depth))
        self.capture_errors = bool(capture_errors)
        self._inflight: "deque[Tuple[Any, Any]]" = deque()
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
        result, meta = self._inflight.popleft()
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

    def submit(self, result: Any, meta: Any = None) -> List[Tuple[Any, Any]]:
        """Enqueue a dispatched result; returns the (host_result, meta)
        pairs that just fell out of the window (possibly empty)."""
        _outstanding.submitted()
        _start_host_copy(result)
        self._inflight.append((result, meta))
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
        the device arrays are released to GC)."""
        _outstanding.fetched(len(self._inflight))
        self._inflight.clear()
        self._gauge.set(0)
