"""Lazy ``Dataset`` graph: sources + composable pipeline operators.

A :class:`Dataset` is a recipe, not a container — each node holds its
upstream and its parameters, and ``iter(ds)`` materializes a fresh
iterator chain.  Iterating twice re-runs the pipeline (and draws the next
permutation from a ``shuffle`` node's seeded stream, exactly like the
estimators' per-epoch ``rng.permutation`` draws).

Design rules (tf.data — arxiv 2101.12127 — adapted to this engine):

- **lazy and re-iterable**: nothing runs until iteration; epochs are
  repeated iterations of one graph;
- **deterministic**: every operator is order-preserving (``map`` with
  workers keeps submission order); ``shuffle``/``batch`` reproduce the
  estimator path's permutation stream and cyclic-pad policy bit-for-bit,
  preserving the streaming-vs-in-memory determinism contract;
- **clean shutdown**: closing a pipeline iterator mid-stream closes the
  whole chain — prefetch threads are joined, pools are released (pinned
  by ``tests/test_data_pipeline.py``).

Consumers: ``estimators/data.py`` (``StreamingShardLoader`` and both
``_fit`` loops), the transformer run loop's chunked decode
(``transformers/utils.run_batched_partitions``), and anything user-side that
wants a saturated device.
"""

from __future__ import annotations

from typing import (
    Any,
    Callable,
    Dict,
    Iterable,
    Iterator,
    List,
    NamedTuple,
    Optional,
    Sequence,
)

import numpy as np

from sparkdl_tpu.resilience import inject


class Batch(NamedTuple):
    """One fixed-size batch: ``items`` (list or stacked array, length =
    configured batch size after padding) and ``n_real`` — how many leading
    entries are real rows (the rest are cyclic padding)."""

    items: Any
    n_real: int


def _counter():
    from sparkdl_tpu.utils.metrics import metrics

    return metrics.counter("data.rows_out")


class Dataset:
    """One node of the lazy pipeline graph.  Build with the ``from_*``
    sources, chain operators, iterate to run.

    ``len(ds)`` is available when the source size is known and no operator
    changed cardinality in a data-dependent way.
    """

    def __init__(
        self,
        iter_factory: Callable[[], Iterator],
        length: Optional[int] = None,
        name: str = "dataset",
        unbounded: bool = False,
    ):
        self._iter_factory = iter_factory
        self._length = length
        self._name = name
        self._unbounded = bool(unbounded)

    @property
    def unbounded(self) -> bool:
        """True for stream-backed datasets (``from_stream``): iteration
        may never end, so whole-stream operators (``shuffle``, cyclic
        padding) are unavailable."""
        return self._unbounded

    # ------------------------------------------------------------------
    # sources
    # ------------------------------------------------------------------
    @staticmethod
    def from_items(items: Sequence, name: str = "from_items") -> "Dataset":
        """Dataset over any finite sequence (kept by reference)."""
        return Dataset(lambda: iter(items), length=len(items), name=name)

    @staticmethod
    def from_uris(uris: Sequence[str]) -> "Dataset":
        """Dataset of URI strings — the estimator ingest source (only URIs
        stay in host memory; pair with ``map(loader)`` to decode)."""
        return Dataset.from_items(list(uris), name="from_uris")

    @staticmethod
    def from_arrays(*arrays: np.ndarray) -> "Dataset":
        """Row-wise dataset over aligned arrays: one array yields its rows,
        several yield row tuples (all must share the leading dim)."""
        if not arrays:
            raise ValueError("from_arrays requires at least one array")
        arrays = tuple(np.asarray(a) for a in arrays)
        n = arrays[0].shape[0]
        for a in arrays[1:]:
            if a.shape[0] != n:
                raise ValueError(
                    "from_arrays requires aligned leading dims: "
                    f"{[a.shape[0] for a in arrays]}"
                )
        if len(arrays) == 1:
            arr = arrays[0]
            return Dataset(
                lambda: iter(arr), length=n, name="from_arrays"
            )
        return Dataset(
            lambda: zip(*arrays), length=n, name="from_arrays"
        )

    @staticmethod
    def from_files(paths: Sequence[str], retry=None) -> "Dataset":
        """Dataset of ``(path, bytes)`` pairs read lazily at iteration
        time — the source-read stage.  ``retry`` (a
        :class:`~sparkdl_tpu.resilience.policy.RetryPolicy`) re-attempts
        reads that fail transiently (``OSError`` I/O hiccups, flaky
        network filesystems); ``FileNotFoundError`` / ``PermissionError``
        are classified permanent and fail immediately."""
        paths = list(paths)

        def read_one(path: str) -> bytes:
            inject.fire("data.source")
            with open(path, "rb") as fh:
                return fh.read()

        reader = retry.wrap(read_one) if retry is not None else read_one

        def rows():
            return ((p, reader(p)) for p in paths)

        return Dataset(rows, length=len(paths), name="from_files")

    @staticmethod
    def from_dataframe(df, *cols: str) -> "Dataset":
        """Dataset over a :class:`sparkdl_tpu.sql.dataframe.DataFrame`'s
        rows.  With ``cols``, yields tuples of those columns (one column
        yields bare values); without, yields the full ``Row``s.  Collects
        once per iteration — pair with ``shard()`` so each host keeps only
        its strided split."""
        if cols:
            selected = df.select(*cols)

            def rows():
                collected = selected.collect()
                if len(cols) == 1:
                    return iter([r[cols[0]] for r in collected])
                return iter([tuple(r[c] for c in cols) for r in collected])

        else:

            def rows():
                return iter(df.collect())

        return Dataset(rows, length=df.count(), name="from_dataframe")

    @staticmethod
    def from_stream(
        source,
        poll_batch: int = 64,
        idle_wait_ms: float = 10.0,
        max_records: Optional[int] = None,
    ) -> "Dataset":
        """Unbounded dataset over a :class:`~sparkdl_tpu.streaming.
        sources.StreamSource`: each iteration polls the source and yields
        record *values* as they arrive, waiting ``idle_wait_ms`` between
        empty polls.  Iteration ends only when the source reports
        ``finished()`` (never, for a true stream) or after
        ``max_records`` (a bounded window onto the stream — handy for
        tests and snapshot jobs).

        The resulting dataset is :attr:`unbounded`: ``shuffle`` and
        cyclic padding are rejected, and ``batch`` defaults to ragged
        finals (or ``drop_remainder=True``).  For scored, exactly-once
        consumption use :class:`~sparkdl_tpu.streaming.runner.
        StreamRunner` instead — this operator is the read-only view.
        """
        import threading

        def rows():
            waiter = threading.Event()  # interruptible idle wait
            emitted = 0
            while True:
                inject.fire("streaming.poll")
                records = source.poll(poll_batch)
                if not records:
                    if source.finished():
                        return
                    waiter.wait(idle_wait_ms / 1000.0)
                    continue
                for rec in records:
                    yield rec.value
                    emitted += 1
                    if max_records is not None and emitted >= max_records:
                        return

        return Dataset(
            rows,
            length=None,
            name="from_stream",
            unbounded=max_records is None,
        )

    # ------------------------------------------------------------------
    # operators
    # ------------------------------------------------------------------
    def map(
        self,
        fn: Callable[[Any], Any],
        num_workers: int = 0,
        buffer: Optional[int] = None,
        retry=None,
    ) -> "Dataset":
        """Apply ``fn`` per item.  ``num_workers > 0`` runs ``fn`` on a
        thread pool with a bounded in-flight window (``buffer``, default
        ``2 * num_workers``) while **preserving order** — results are
        yielded in submission order, so downstream determinism contracts
        hold regardless of per-item latency.

        ``retry`` (a :class:`~sparkdl_tpu.resilience.policy.RetryPolicy`)
        re-attempts per-item transient failures with backoff; permanent
        failures (e.g. :class:`~sparkdl_tpu.image.imageIO.ImageDecodeError`
        — corrupt bytes don't heal on retry) propagate immediately.  The
        classification is ``isinstance`` against the resilience taxonomy,
        no string matching."""
        src = self

        def apply(item):
            inject.fire("data.map")
            return fn(item)

        item_fn = retry.wrap(apply) if retry is not None else apply

        if num_workers <= 0:

            def sequential():
                it = iter(src)
                try:
                    for item in it:
                        yield item_fn(item)
                finally:
                    _close_iter(it)

            return Dataset(sequential, length=self._length, name="map",
                           unbounded=self._unbounded)

        window = int(buffer) if buffer is not None else 2 * int(num_workers)
        window = max(1, window)

        def threaded():
            from collections import deque
            from concurrent.futures import ThreadPoolExecutor

            from sparkdl_tpu.obs.trace import tracer

            # explicit trace propagation: capture the current span HERE
            # (the thread driving the pipeline) and re-attach it around
            # each pool task — pool threads never inherit context
            # silently.  With tracing off, capture() is None and the
            # unwrapped item_fn runs at zero extra cost.
            span = tracer.capture()
            if span is None:
                run = item_fn
            else:
                def run(item):
                    with tracer.use_span(span):
                        return item_fn(item)

            it = iter(src)
            pending: "deque" = deque()
            pool = ThreadPoolExecutor(
                max_workers=int(num_workers),
                thread_name_prefix="sparkdl-data-map",
            )
            try:
                for item in it:
                    pending.append(pool.submit(run, item))
                    if len(pending) >= window:
                        yield pending.popleft().result()
                while pending:
                    yield pending.popleft().result()
            finally:
                for f in pending:
                    f.cancel()
                _close_iter(it)
                pool.shutdown(wait=True)

        return Dataset(threaded, length=self._length, name="map",
                       unbounded=self._unbounded)

    def shuffle(self, seed: int) -> "Dataset":
        """Seeded whole-dataset shuffle reproducing the estimators'
        permutation stream: one ``np.random.RandomState(seed % 2**32)`` is
        created per *pipeline* (first iteration), and each iteration draws
        the next ``rng.permutation(n)`` — so epoch ``e`` of this dataset
        sees exactly the estimator loop's ``e``-th epoch order.

        Materializes the upstream items per iteration (a shuffle is a
        global reorder; upstream sources here are URI/index lists, not
        decoded tensors — shuffle *before* the expensive ``map``)."""
        if self._unbounded:
            raise ValueError(
                "shuffle() is a whole-dataset reorder and cannot apply "
                "to an unbounded stream; window the stream first "
                "(from_stream(max_records=...))"
            )
        src = self
        state: Dict[str, Any] = {}

        def shuffled():
            items = list(_iterate_fully(src))
            if "rng" not in state:
                state["rng"] = np.random.RandomState(int(seed) % 2**32)
            order = state["rng"].permutation(len(items))
            return iter([items[i] for i in order])

        return Dataset(shuffled, length=self._length, name="shuffle")

    def shard(
        self,
        index: Optional[int] = None,
        count: Optional[int] = None,
    ) -> "Dataset":
        """Keep the strided split ``index::count`` — per-host sharding as a
        first-class pipeline stage (the GSPMD framing, arxiv 2105.04663)
        instead of ad-hoc index math in each caller.

        With no arguments, uses this process's position in the
        ``jax.distributed`` job via :func:`parallel.runner.host_shard_indices`
        semantics (identity when single-process)."""
        src = self

        def strided():
            if index is None or count is None:
                from sparkdl_tpu.parallel import runner

                if not runner.is_distributed():
                    return iter(_iterate_fully(src))
                import jax

                i, c = jax.process_index(), jax.process_count()
            else:
                i, c = int(index), int(count)
            if not 0 <= i < c:
                raise ValueError(f"shard index {i} outside [0, {c})")
            return (
                item
                for j, item in enumerate(_iterate_fully(src))
                if j % c == i
            )

        length = None
        if self._length is not None and index is not None and count:
            length = len(range(int(index), self._length, int(count)))
        return Dataset(strided, length=length, name="shard",
                       unbounded=self._unbounded)

    def batch(
        self,
        batch_size: int,
        pad: Optional[str] = None,
        min_batches: Optional[int] = None,
        drop_remainder: bool = False,
    ) -> "Dataset":
        """Group items into :class:`Batch` tuples of exactly ``batch_size``.

        ``pad=None`` drops nothing and emits a ragged final batch
        (``n_real < batch_size`` with ``items`` shorter).  ``pad="cyclic"``
        pads the ragged final batch by cycling from the stream's start —
        ``np.resize(all_items, k)`` — the estimator path's exact policy, so
        batch composition is bit-identical to the in-memory ``_fit`` loop.
        ``min_batches`` (with ``pad="cyclic"``) keeps emitting fully-padded
        ``n_real=0`` batches after exhaustion up to that count — the
        multi-host case where every host must run the same step count.

        ``drop_remainder=True`` discards the ragged final instead — the
        fixed-shape option for **unbounded** streams, where cyclic padding
        is impossible (it replays from a start the stream no longer holds
        and assumes an end that never comes).  On an unbounded dataset
        only ``pad=None`` semantics apply, and items are NOT retained
        after they leave their batch (a stream must run in O(batch)
        memory, not O(stream)).
        """
        if pad not in (None, "cyclic"):
            raise ValueError(f"pad must be None or 'cyclic', got {pad!r}")
        if min_batches is not None and pad != "cyclic":
            raise ValueError("min_batches requires pad='cyclic'")
        if drop_remainder and pad is not None:
            raise ValueError("drop_remainder and pad are mutually exclusive")
        if self._unbounded and pad is not None:
            raise ValueError(
                "pad='cyclic' assumes a finite source and cannot apply to "
                "an unbounded stream; use pad=None (ragged final) or "
                "drop_remainder=True"
            )
        bs = int(batch_size)
        if bs < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        src = self
        keep_seen = pad == "cyclic"

        def batched():
            it = iter(src)
            seen: List[Any] = []
            buf: List[Any] = []
            emitted = 0
            try:
                for item in it:
                    buf.append(item)
                    if keep_seen:
                        seen.append(item)
                    if len(buf) == bs:
                        yield Batch(_pack(buf), bs)
                        emitted += 1
                        buf = []
                if buf and not drop_remainder:
                    k = len(buf)
                    if pad == "cyclic":
                        # the estimator policy: np.resize over the full
                        # stream (== np.resize(order, pad) when upstream is
                        # the epoch permutation)
                        buf = buf + _cycle_pad(seen, bs - k)
                    yield Batch(_pack(buf), k)
                    emitted += 1
                if min_batches is not None:
                    if not seen and emitted < min_batches:
                        raise ValueError(
                            "batch(min_batches=...) on an empty stream"
                        )
                    while emitted < min_batches:
                        yield Batch(_pack(_cycle_pad(seen, bs)), 0)
                        emitted += 1
            finally:
                _close_iter(it)

        length = None
        if self._length is not None:
            if drop_remainder:
                length = self._length // bs
            else:
                length = max(-(-self._length // bs), min_batches or 0)
        return Dataset(batched, length=length, name="batch",
                       unbounded=self._unbounded)

    def prefetch(self, size: int = 2) -> "Dataset":
        """Decouple producer from consumer: a background thread runs the
        upstream pipeline ``size`` items ahead through a bounded queue.
        Clean shutdown on generator close (cancel → drain → join; see
        :mod:`sparkdl_tpu.data.prefetch`).  Advances ``data.queue_depth``
        and the ``data.device_stall_ms`` wait histogram."""
        src = self

        def prefetched():
            from sparkdl_tpu.data.prefetch import PrefetchIterator
            from sparkdl_tpu.obs.trace import tracer
            from sparkdl_tpu.utils.metrics import metrics

            stall = metrics.histogram("data.device_stall_ms")
            depth = metrics.gauge("data.queue_depth")
            busy = metrics.timer("data.producer_busy")
            it = PrefetchIterator(
                lambda: iter(src),
                size,
                on_wait_ms=stall.observe,
                on_depth=depth.set,
                on_busy_s=lambda s: busy.add_seconds(s),
                # consumer-side capture: the producer thread re-attaches
                # this span, so upstream stages (and their retries) land
                # in the consumer's trace instead of an orphan context
                context_span=tracer.capture(),
            )
            try:
                for item in it:
                    yield item
            finally:
                it.close()

        return Dataset(prefetched, length=self._length, name="prefetch",
                       unbounded=self._unbounded)

    def prefetch_to_device(
        self, place: Optional[Callable[[Any], Any]] = None
    ) -> "Dataset":
        """Double-buffered device placement: dispatch batch ``i+1``'s
        host→device transfer (``place``, default
        :func:`sparkdl_tpu.data.device.default_device_placer` — mesh-aware
        like the transformer run loop) *before* yielding batch ``i``, so
        the transfer rides under the consumer's compute on ``i`` (jax
        dispatch is async).  Terminal stage: counts ``data.rows_out``."""
        src = self

        def doubled():
            from sparkdl_tpu.data.device import default_device_placer

            placer = place if place is not None else default_device_placer()
            rows_out = _counter()
            it = iter(src)
            pending = None
            try:
                for item in it:
                    placed = placer(item)  # async dispatch of i+1 ...
                    if pending is not None:
                        rows_out.add(_row_count(pending))
                        yield pending  # ... overlaps consumer compute on i
                    pending = placed
                if pending is not None:
                    rows_out.add(_row_count(pending))
                    yield pending
            finally:
                _close_iter(it)

        return Dataset(doubled, length=self._length,
                       name="prefetch_to_device", unbounded=self._unbounded)

    # ------------------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self._iter_factory()

    def __len__(self) -> int:
        if self._length is None:
            raise TypeError(f"len() of unsized dataset ({self._name})")
        return self._length

    def __repr__(self) -> str:
        size = "?" if self._length is None else str(self._length)
        return f"<Dataset {self._name} n={size}>"


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _close_iter(it) -> None:
    close = getattr(it, "close", None)
    if close is not None:
        close()


def _iterate_fully(src: Iterable) -> Iterator:
    it = iter(src)
    try:
        for item in it:
            yield item
    finally:
        _close_iter(it)


def _pack(items: List[Any]):
    """Stack scalar/array items into one ndarray (what batch consumers
    index with), leave heterogeneous items as a list."""
    first = items[0]
    if isinstance(first, (int, np.integer, float, np.floating)) or (
        isinstance(first, np.ndarray)
    ):
        try:
            return np.asarray(items)
        except ValueError:  # ragged shapes: keep the list
            return list(items)
    return list(items)


def _cycle_pad(seen: List[Any], k: int) -> List[Any]:
    """``k`` pad items cycling from the stream start (``np.resize``
    semantics over arbitrary items)."""
    if k <= 0:
        return []
    if not seen:
        raise ValueError("cannot cyclically pad an empty stream")
    reps = -(-k // len(seen))
    return (seen * reps)[:k]


def _row_count(item) -> int:
    if isinstance(item, Batch):
        return int(item.n_real)
    if isinstance(item, dict):
        for v in item.values():
            return _row_count(v)
        return 1
    shape = getattr(item, "shape", None)
    if shape:
        return int(shape[0])
    return 1
