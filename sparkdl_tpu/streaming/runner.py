"""StreamRunner: micro-batched continuous inference with exactly-once commits.

The execution layer of the streaming subsystem, grafted onto the seams
the batch/online stacks already expose:

- a **poller thread** pulls :class:`~sparkdl_tpu.streaming.sources.
  Record` batches from the source and admits them one-by-one into a
  bounded :class:`~sparkdl_tpu.serving.admission.AdmissionQueue` — via
  the blocking :meth:`~sparkdl_tpu.serving.admission.AdmissionQueue.
  offer_wait`, so a full queue *stalls the poller* and backpressure
  reaches the source instead of shedding rows (a stream must not drop);
- the **run loop** coalesces requests with the serving layer's
  first-item-then-linger ``take`` (flush on max-batch-or-max-wait),
  scores each micro-batch, and pipelines results through the engine's
  :class:`~sparkdl_tpu.engine.DispatchWindow` so batch ``i``'s commit
  overlaps batch ``i+1``'s compute;
- each completed micro-batch becomes one **epoch** committed through the
  payload-then-marker :class:`~sparkdl_tpu.streaming.commit.CommitLog`
  (the epoch's *outputs* ride in the payload, so recovery re-emits them
  bit-identically without re-scoring), with the source's ``end_offset``
  checkpointed in the same payload;
- **recovery** on entry: replay every pending (payload-without-marker)
  epoch into the sink idempotently, then ``seek`` the source to the last
  payload's ``end_offset`` and continue numbering from there;
- **preemption**: the loop runs in a
  :func:`~sparkdl_tpu.resilience.preempt.preemption_scope` — SIGTERM
  stops polling, flushes everything already admitted (queue + dispatch
  window) into committed epochs, and returns with
  ``stop_reason="preempted"``; a restarted runner resumes from the last
  committed offset.

Fault sites ``streaming.poll`` (before each source poll),
``streaming.sink`` (between payload and sink write), and
``streaming.commit`` (between sink write and marker) hook the
:mod:`~sparkdl_tpu.resilience.inject` harness; a ``kill`` at any of them
must not lose or duplicate records — pinned by ``tests/test_streaming.py``.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from sparkdl_tpu.engine import DispatchWindow
from sparkdl_tpu.resilience import inject
from sparkdl_tpu.resilience.errors import Preempted
from sparkdl_tpu.resilience.preempt import preemption_scope
from sparkdl_tpu.serving.admission import AdmissionQueue, Request
from sparkdl_tpu.streaming.commit import CommitLog, Sink
from sparkdl_tpu.streaming.sources import StreamSource, WatermarkTracker
from sparkdl_tpu.utils.metrics import metrics


def _env_int(name: str, default: int) -> int:
    spec = os.environ.get(name, "").strip()
    return int(spec) if spec else default


def _env_float(name: str, default: float) -> float:
    spec = os.environ.get(name, "").strip()
    return float(spec) if spec else default


@dataclass
class StreamConfig:
    """Knobs for one :class:`StreamRunner`.

    The flush policy is max-batch-OR-max-wait: a micro-batch closes the
    moment it has ``max_batch`` rows or the oldest row has waited
    ``max_wait_ms`` — the serving coalescing window applied to a stream.
    Env overrides (read at construction): ``SPARKDL_STREAM_MAX_BATCH``,
    ``SPARKDL_STREAM_MAX_WAIT_MS``, ``SPARKDL_STREAM_QUEUE_CAPACITY``.
    """

    #: rows per micro-batch (flush threshold and scoring batch size)
    max_batch: int = field(
        default_factory=lambda: _env_int("SPARKDL_STREAM_MAX_BATCH", 32)
    )
    #: linger before flushing a non-full micro-batch
    max_wait_ms: float = field(
        default_factory=lambda: _env_float("SPARKDL_STREAM_MAX_WAIT_MS", 50.0)
    )
    #: admission-queue bound — the backpressure depth (a full queue
    #: blocks the poller, which stops polling the source)
    queue_capacity: int = field(
        default_factory=lambda: _env_int("SPARKDL_STREAM_QUEUE_CAPACITY", 256)
    )
    #: records per source poll
    poll_batch: int = 64
    #: idle wait between empty polls
    poll_interval_ms: float = 10.0
    #: watermark bounded-lateness allowance
    allowed_lateness_ms: float = 0.0
    #: dispatch-window depth (None → the engine's ``DEFAULT_DEPTH``)
    dispatch_depth: Optional[int] = None
    #: how long a blocked poller waits per offer attempt before
    #: re-checking for shutdown
    offer_timeout_s: float = 0.2
    #: optional RetryPolicy wrapped around each micro-batch score call
    retry: Any = None


def _jsonable(v: Any) -> Any:
    """Coerce ``v`` to something ``json.dump`` accepts (payloads and sink
    records must survive a round-trip through the commit log)."""
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return v


def _default_encode(record, output) -> Dict[str, Any]:
    """One sink record per input row: the source offset (the row's
    identity for set-equality checks), the input value, and the scored
    output."""
    return {
        "offset": int(record.offset),
        "input": _jsonable(record.value),
        "output": _jsonable(output),
    }


def _split_outputs(host_out: Any, n: int) -> List[Any]:
    """Per-row outputs from one scored micro-batch: arrays split on the
    leading dim, sequences pass through; anything else must already be
    row-aligned."""
    if isinstance(host_out, np.ndarray):
        if host_out.shape and host_out.shape[0] == n:
            return list(host_out)
        raise ValueError(
            f"scored batch has leading dim {host_out.shape[:1]} for "
            f"{n} input rows — fn must return one output per row"
        )
    if isinstance(host_out, (list, tuple)):
        if len(host_out) != n:
            raise ValueError(
                f"scored batch returned {len(host_out)} outputs for "
                f"{n} input rows"
            )
        return list(host_out)
    raise TypeError(
        f"fn must return an array or sequence of per-row outputs, got "
        f"{type(host_out).__name__}"
    )


class StreamRunner:
    """Pull → micro-batch → score → commit, with exactly-once delivery.

    ``fn`` scores one micro-batch: it receives the batch as a stacked
    ``np.ndarray`` when the values stack cleanly (``pack=True``, the
    default — what a jitted forward wants) or as a plain list otherwise,
    and returns one output per row (array with matching leading dim, or
    a sequence).  Dispatch may be asynchronous (a jax device array):
    fetches go through the engine's :class:`DispatchWindow`, never
    inline.
    """

    def __init__(
        self,
        source: StreamSource,
        fn: Callable[[Any], Any],
        sink: Sink,
        log_dir: str,
        config: Optional[StreamConfig] = None,
        encode: Optional[Callable[[Any, Any], Dict[str, Any]]] = None,
        pack: bool = True,
    ):
        self.source = source
        self.sink = sink
        self.config = config or StreamConfig()
        self.log = CommitLog(log_dir)
        self._encode = encode or _default_encode
        self._pack = bool(pack)
        self._score = (
            self.config.retry.wrap(fn) if self.config.retry is not None
            else fn
        )
        self._queue = AdmissionQueue(
            self.config.queue_capacity,
            depth_gauge=metrics.gauge("streaming.queue_depth"),
            shed_counter=metrics.counter("streaming.shed"),
        )
        self._watermark = WatermarkTracker(
            allowed_lateness_ms=self.config.allowed_lateness_ms
        )
        self._stop_poller = threading.Event()
        self._source_done = threading.Event()
        self._poller_error: Optional[BaseException] = None
        self._next_epoch = (self.log.last_committed() or 0) + 1
        # metrics — all under the sanctioned streaming. prefix
        self._m_records_in = metrics.counter("streaming.records_in")
        self._m_sink_records = metrics.counter("streaming.sink_records")
        self._m_epochs = metrics.counter("streaming.epochs_committed")
        self._m_replays = metrics.counter("streaming.replays")
        self._m_late = metrics.counter("streaming.late_records")
        self._m_wm_lag = metrics.gauge("streaming.watermark_lag_ms")
        self._m_consumer_lag = metrics.gauge("streaming.consumer_lag")
        self._m_offset = metrics.gauge("streaming.committed_offset")
        self._m_latency = metrics.histogram("streaming.record_latency_ms")

    # ------------------------------------------------------------------
    # constructors
    # ------------------------------------------------------------------
    @classmethod
    def from_server(
        cls,
        source: StreamSource,
        server,
        sink: Sink,
        log_dir: str,
        model_id: Optional[str] = None,
        config: Optional[StreamConfig] = None,
        encode: Optional[Callable[[Any, Any], Dict[str, Any]]] = None,
    ) -> "StreamRunner":
        """Score through a :class:`~sparkdl_tpu.serving.server.
        ModelServer` endpoint: each micro-batch row is submitted to the
        endpoint (riding its admission control, shape buckets, and warm
        program cache) and the futures are gathered in order.  The
        endpoint's own micro-batcher coalesces them back into device
        batches, so the stream shares capacity fairly with interactive
        traffic."""

        def fn(values):
            futures = [
                server.submit(v, model_id=model_id) for v in values
            ]
            return [f.result() for f in futures]

        return cls(
            source, fn, sink, log_dir,
            config=config, encode=encode, pack=False,
        )

    # ------------------------------------------------------------------
    # poller thread
    # ------------------------------------------------------------------
    def _poll_loop(self, run_span) -> None:
        from sparkdl_tpu.obs.trace import tracer

        # explicit cross-thread propagation: the run span was captured on
        # the run() thread; everything here re-enters it lexically
        with tracer.use_span(run_span):
            try:
                while not self._stop_poller.is_set():
                    inject.fire("streaming.poll")
                    records = self.source.poll(self.config.poll_batch)
                    if not records:
                        self._observe_lag()
                        if self.source.finished():
                            self._source_done.set()
                            return
                        self._stop_poller.wait(
                            self.config.poll_interval_ms / 1000.0
                        )
                        continue
                    self._m_records_in.add(len(records))
                    # a child of the run span: creating NEW spans in a
                    # worker is sanctioned; only implicit context reads
                    # are not (contextvar-leak rule)
                    with tracer.span("streaming.poll", rows=len(records)):
                        for rec in records:
                            if self._watermark.observe(rec.event_time_ms):
                                self._m_late.add(1)
                            req = Request(value=rec)
                            while not self._queue.offer_wait(
                                req, timeout_s=self.config.offer_timeout_s
                            ):
                                if self._stop_poller.is_set():
                                    return
                    self._observe_lag()
            except BaseException as exc:  # surface in run(), don't vanish
                self._poller_error = exc
                self._source_done.set()

    def _observe_lag(self) -> None:
        lag = self._watermark.lag_ms(time.time() * 1000.0)
        if lag is not None:
            self._m_wm_lag.set(lag)
        backlog = self.source.backlog()
        if backlog is not None:
            self._m_consumer_lag.set(backlog)

    # ------------------------------------------------------------------
    # recovery
    # ------------------------------------------------------------------
    def _recover(self) -> int:
        """Replay pending epochs into the sink and seek the source to the
        checkpointed offset.  Returns the number of epochs replayed."""
        from sparkdl_tpu.obs.trace import tracer

        pending = self.log.pending()
        with tracer.span("streaming.recover", pending=len(pending)):
            for epoch in pending:
                payload = self.log.payload(epoch)
                inject.fire("streaming.sink")
                self.sink.write(epoch, payload["records"])
                inject.fire("streaming.commit")
                self.log.commit(epoch)
                self._m_replays.add(1)
            offset = self.log.resume_offset()
            if offset is not None:
                self.source.seek(int(offset))
            last = self.log.last_committed()
            self._next_epoch = (last or 0) + 1
        return len(pending)

    # ------------------------------------------------------------------
    # commit path
    # ------------------------------------------------------------------
    def _commit_epoch(self, epoch: int, requests: List[Request],
                      host_out: Any) -> None:
        outputs = _split_outputs(host_out, len(requests))
        records = [
            self._encode(req.value, out)
            for req, out in zip(requests, outputs)
        ]
        end_offset = int(requests[-1].value.offset)
        self.log.write_payload(epoch, {
            "epoch": epoch,
            "end_offset": end_offset,
            "watermark_ms": self._watermark.watermark_ms,
            "records": records,
        })
        inject.fire("streaming.sink")
        self.sink.write(epoch, records)
        inject.fire("streaming.commit")
        self.log.commit(epoch)
        now = time.monotonic()
        for req in requests:
            self._m_latency.observe((now - req.enqueued_at) * 1000.0)
        self._m_epochs.add(1)
        self._m_sink_records.add(len(records))
        self._m_offset.set(end_offset)

    def _flush_batch(self, window: DispatchWindow,
                     requests: List[Request]) -> List:
        """Score one micro-batch and submit it to the dispatch window;
        returns the (host, meta) pairs that fell out."""
        from sparkdl_tpu.obs.trace import tracer

        epoch = self._next_epoch
        self._next_epoch += 1
        values = [req.value.value for req in requests]
        if self._pack:
            try:
                values = np.asarray(values)
            except ValueError:  # ragged rows: score as a list
                pass
        with tracer.span(
            "streaming.epoch", epoch=epoch, rows=len(requests)
        ):
            result = self._score(values)
        return window.submit(result, meta=(epoch, requests))

    # ------------------------------------------------------------------
    # run loop
    # ------------------------------------------------------------------
    def run(
        self,
        max_epochs: Optional[int] = None,
        idle_timeout_s: Optional[float] = None,
    ) -> Dict[str, Any]:
        """Recover, then pull-score-commit until a stop condition.

        Stops when the source reports ``finished()`` and everything
        admitted has committed (``stop_reason="source_finished"``), after
        ``max_epochs`` fresh commits (``"max_epochs"``), after
        ``idle_timeout_s`` with no records anywhere in flight
        (``"idle_timeout"``), or on SIGTERM/preemption (``"preempted"``
        — in-flight work is flushed and committed first).
        """
        from sparkdl_tpu.obs.trace import tracer

        epochs_start = self._next_epoch
        stop_reason = "source_finished"
        replayed = 0
        with preemption_scope() as token:
            with tracer.span(
                "streaming.run", source=type(self.source).__name__
            ) as run_span:
                replayed = self._recover()
                window = DispatchWindow(depth=self.config.dispatch_depth)
                poller = threading.Thread(
                    target=self._poll_loop,
                    args=(tracer.capture() if run_span else None,),
                    name="sparkdl-stream-poller",
                    daemon=True,
                )
                poller.start()
                idle_since: Optional[float] = None
                try:
                    while True:
                        try:
                            token.check()
                        except Preempted:
                            stop_reason = "preempted"
                            break
                        if (max_epochs is not None
                                and self._next_epoch - epochs_start
                                >= max_epochs):
                            stop_reason = "max_epochs"
                            break
                        batch = self._queue.take(
                            self.config.max_batch,
                            self.config.max_wait_ms / 1000.0,
                        )
                        if batch:
                            idle_since = None
                            for host, meta in self._flush_batch(
                                window, batch
                            ):
                                self._commit_epoch(meta[0], meta[1], host)
                            continue
                        # idle tick: let in-flight work land
                        for host, meta in window.drain():
                            self._commit_epoch(meta[0], meta[1], host)
                        if self._poller_error is not None:
                            raise self._poller_error
                        if (self._source_done.is_set()
                                and len(self._queue) == 0):
                            break
                        if idle_timeout_s is not None:
                            now = time.monotonic()
                            if idle_since is None:
                                idle_since = now
                            elif now - idle_since >= idle_timeout_s:
                                stop_reason = "idle_timeout"
                                break
                finally:
                    self._stop_poller.set()
                    poller.join()
                # flush: everything already admitted becomes committed
                # epochs before we return (the preemption contract)
                while True:
                    batch = self._queue.take(self.config.max_batch, 0.0,
                                             poll_s=0.0)
                    if not batch:
                        break
                    for host, meta in self._flush_batch(window, batch):
                        self._commit_epoch(meta[0], meta[1], host)
                for host, meta in window.drain():
                    self._commit_epoch(meta[0], meta[1], host)
                if run_span is not None:
                    run_span.set_attribute("stop_reason", stop_reason)
        return {
            "stop_reason": stop_reason,
            "epochs": self._next_epoch - epochs_start,
            "replayed": replayed,
            "last_committed": self.log.last_committed(),
            "committed_offset": self.log.resume_offset(),
            "watermark_ms": self._watermark.watermark_ms,
        }

    # ------------------------------------------------------------------
    def slos(
        self,
        max_watermark_lag_ms: Optional[float] = None,
        lag_objective: float = 0.95,
        min_commit_rate: Optional[float] = None,
        **overrides,
    ):
        """The streaming SLO bundle for this runner
        (:func:`~sparkdl_tpu.obs.slo.streaming_slos`): bounded
        ``streaming.watermark_lag_ms`` (threshold defaults to 5 s, never
        below the configured ``allowed_lateness_ms`` — lag the watermark
        tolerates by design must not burn the budget) and, when
        ``min_commit_rate`` is given, a committed-epoch throughput
        floor.  Register on an SLO engine::

            engine.add(*runner.slos(min_commit_rate=0.5))
        """
        from sparkdl_tpu.obs.slo import streaming_slos

        if max_watermark_lag_ms is None:
            max_watermark_lag_ms = max(
                5000.0, float(self.config.allowed_lateness_ms)
            )
        return streaming_slos(
            max_watermark_lag_ms=max_watermark_lag_ms,
            lag_objective=lag_objective,
            min_commit_rate=min_commit_rate,
            **overrides,
        )

    def close(self) -> None:
        self._stop_poller.set()
        self._queue.close()
        self.sink.close()
        self.source.close()

    def __enter__(self) -> "StreamRunner":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
