"""Dynamic micro-batcher: ragged slot-block dispatch, with the padded
bucket ladder as kill switch and fallback.

**Ragged path (default).** Each endpoint owns a fixed
``(n_slots, *item)`` slot block (:class:`~sparkdl_tpu.engine.SlotPool`,
``n_slots = max_batch`` — the one-shot twin of the ISSUE-18 decode
pool).  A request is admitted into any free slot the moment it arrives:
no bucket pad, no coalesce-window linger while the device idles.
Compiled endpoints run ONE executable — a masked fused forward over the
whole block, occupancy riding a bool mask instead of the shape — and
results scatter back by slot index; plain (``compile=False``) endpoints
gather exactly the occupied rows, so the device computes zero pad rows.
Slots stay occupied while their block is in flight in the dispatch
window and free at completion, so traffic keeps admitting into the
remaining slots mid-flight.

**Padded fallback.** ``SPARKDL_RAGGED=0`` (read at dispatch time — the
kill switch is live) or a compiled endpoint with no durable fingerprint
(an anonymous slot-block executable could never persist) falls back to
the original discipline, the online analog of ``run_batched``
(transformers/utils.py): coalesce, pad to a
:func:`~sparkdl_tpu.transformers.utils.bucket_ladder` bucket with
:func:`~sparkdl_tpu.transformers.utils.pad_to_batch`, one warm program
per bucket (tf.data pipelining logic — PAPERS.md — applied to a request
stream instead of an input pipeline).

Either way: one worker thread per endpoint; the warm
:class:`ProgramCache` program runs the batch and per-request futures
resolve.  A forward that raises fails only that batch's futures — the
worker survives and keeps serving (the crash case is
fault-injection-tested).  ``batcher.rows_real`` / ``rows_computed``
counters and the ``batcher.pad_fraction`` gauge account for every row
the device computed vs every row a caller asked for — the measured
padding waste, federated per-version into ``/debug/fleet``.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from concurrent.futures import Future
from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np

from sparkdl_tpu.engine import DispatchWindow, FetchFailure, SlotPool
from sparkdl_tpu.obs.slo import sanitize_name
from sparkdl_tpu.obs.trace import tracer
from sparkdl_tpu.resilience import inject
from sparkdl_tpu.resilience.errors import CircuitOpen
from sparkdl_tpu.resilience.policy import CircuitBreaker, Deadline, RetryPolicy
from sparkdl_tpu.serving.admission import (
    AdmissionQueue,
    Request,
    TenantPolicy,
)
from sparkdl_tpu.serving.cache import ProgramCache
from sparkdl_tpu.serving.errors import DeadlineExceeded, ServerClosed
from sparkdl_tpu.transformers.utils import pad_to_batch, shape_bucket
from sparkdl_tpu.utils.metrics import metrics

logger = logging.getLogger(__name__)

#: kill switch for ragged one-shot dispatch — ``SPARKDL_RAGGED=0``
#: forces every endpoint onto the padded bucket ladder
ENV_RAGGED = "SPARKDL_RAGGED"


def ragged_enabled() -> bool:
    """Ragged slot-block dispatch is on unless ``SPARKDL_RAGGED=0``.
    Read per dispatch cycle, so flipping the env mid-process takes
    effect on the next batch (what the byte-identity tests and the
    bench A/B rely on)."""
    return os.environ.get(ENV_RAGGED, "1").strip() != "0"


class ServingConfig:
    """Knobs of one online endpoint (shared by every endpoint of a
    :class:`~sparkdl_tpu.serving.server.ModelServer`)."""

    def __init__(
        self,
        max_batch: int = 32,
        max_wait_ms: float = 2.0,
        queue_capacity: int = 256,
        cache_size: int = 32,
        default_deadline_ms: Optional[float] = None,
        retry: Optional[RetryPolicy] = None,
        breaker_threshold: int = 5,
        breaker_recovery_s: float = 30.0,
        tenant_policy: Optional[TenantPolicy] = None,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_wait_ms < 0:
            raise ValueError(f"max_wait_ms must be >= 0, got {max_wait_ms}")
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.queue_capacity = int(queue_capacity)
        self.cache_size = int(cache_size)
        self.default_deadline_ms = default_deadline_ms
        # resilience knobs: `retry` re-attempts *transient* forward
        # failures (resilience taxonomy) within the batch's deadline;
        # `breaker_threshold` consecutive forward failures trip the
        # endpoint's circuit breaker into degraded mode (visible in
        # ModelServer.status()) for `breaker_recovery_s`.
        self.retry = retry
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_recovery_s = float(breaker_recovery_s)
        # per-tenant fair-share admission (ISSUE-12); None falls back to
        # the SPARKDL_TENANT_* env knobs at endpoint construction
        self.tenant_policy = tenant_policy

    def __repr__(self):
        return (
            f"ServingConfig(max_batch={self.max_batch}, "
            f"max_wait_ms={self.max_wait_ms}, "
            f"queue_capacity={self.queue_capacity}, "
            f"cache_size={self.cache_size}, "
            f"default_deadline_ms={self.default_deadline_ms}, "
            f"retry={self.retry}, "
            f"breaker_threshold={self.breaker_threshold}, "
            f"breaker_recovery_s={self.breaker_recovery_s}, "
            f"tenant_policy={self.tenant_policy})"
        )


def _end_request_span(span):
    """Future callback closing a request span with its outcome."""

    def done(future):
        exc = future.exception()
        if exc is not None:
            span.set_attribute("error", type(exc).__name__)
        span.end()

    return done


class MicroBatcher:
    """One online endpoint: admission queue + worker + warm programs for a
    single model ``forward(batch) -> batch`` callable.

    ``compile=False`` runs ``forward`` as plain Python instead of jitting
    per bucket — the escape hatch for non-JAX callables, and what the
    fault-injection tests use to make worker behavior deterministic.
    """

    def __init__(
        self,
        model_id: str,
        forward: Callable[[Any], Any],
        config: ServingConfig,
        cache: ProgramCache,
        item_shape: Optional[Sequence[int]] = None,
        dtype: Any = np.float32,
        compile: bool = True,
        fingerprint: Optional[str] = None,
        prologue: Optional[Callable[[Any], Any]] = None,
        clock=time.monotonic,
    ):
        self.model_id = model_id
        self._forward = forward
        self._config = config
        self._cache = cache
        # fused on-device input prologue (cast/resize/normalize —
        # transformers.utils.make_input_prologue): composed IN FRONT of
        # the forward so compiled endpoints trace prologue+model as one
        # donation-friendly XLA program and the host-side device_resize
        # round-trips leave the hot path.  Plain endpoints apply it
        # eagerly (same math, no fusion).
        self._prologue = prologue
        if prologue is None:
            self._fused_forward = forward
        else:
            def _fused_forward(x, _fwd=forward, _pro=prologue):
                return _fwd(_pro(x))

            self._fused_forward = _fused_forward
        #: injectable time source — the sim drives the endpoint in
        #: virtual time; live serving keeps the monotonic default
        self._clock = clock
        # per-endpoint instruments alongside the process-wide serving.*
        # aggregates: the sampled `serving.latency_ms.<id>.p99` /
        # `serving.errors.<id>` / `serving.requests.<id>` series are what
        # obs.slo.serving_slos() evaluates per endpoint
        mid = sanitize_name(model_id)
        self._m_requests = metrics.counter(f"serving.requests.{mid}")
        self._m_errors = metrics.counter(f"serving.errors.{mid}")
        self._m_latency = metrics.histogram(f"serving.latency_ms.{mid}")
        # durable model identity (saved-file path+mtime, blob hash) —
        # makes this endpoint's per-bucket executables persistable
        self._fingerprint = fingerprint
        # batch i's device->host fetch streams while batch i+1 computes;
        # drained eagerly whenever the queue goes idle so a lone request
        # never waits on the window
        self._window = DispatchWindow(capture_errors=True)
        self._item_shape: Optional[Tuple[int, ...]] = (
            tuple(int(d) for d in item_shape) if item_shape is not None
            else None
        )
        self._dtype = np.dtype(dtype)
        self._compile = bool(compile)
        # the one-shot slot block: a request holds a slot from admission
        # until its result is scattered back (i.e. across its block's
        # time in the dispatch window), so the occupancy gauge reads
        # "requests resident on the device" — the same meaning as
        # decode.slots_occupied.  Worker-owned (single-owner discipline,
        # like the decode pool); the gauge is the only cross-thread read.
        self._pool = SlotPool(
            config.max_batch,
            occupied_gauge=metrics.gauge("batcher.slot_occupancy"),
        )
        # pad accounting: rows callers asked for vs rows the device
        # computed — counters so the fleet federation can sum them
        # across replicas; the gauge is this process's lifetime ratio
        self._m_rows_real = metrics.counter("batcher.rows_real")
        self._m_rows_computed = metrics.counter("batcher.rows_computed")
        self._m_pad_gauge = metrics.gauge("batcher.pad_fraction")
        self._queue = AdmissionQueue(
            config.queue_capacity,
            depth_gauge=metrics.gauge(f"serving.queue_depth.{model_id}"),
            shed_counter=metrics.counter("serving.shed"),
            tenant_policy=(
                config.tenant_policy
                if config.tenant_policy is not None
                else TenantPolicy.from_env()
            ),
            clock=clock,
        )
        self._breaker = CircuitBreaker(
            name=f"serving.{model_id}",
            failure_threshold=config.breaker_threshold,
            recovery_s=config.breaker_recovery_s,
        )
        self._closed = False
        self._worker_lock = threading.Lock()
        self._worker: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    # submission
    # ------------------------------------------------------------------
    def submit(
        self,
        value,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> Future:
        """Admit one item; returns a Future resolving to the model output
        row.  Raises :class:`ServerOverloaded` when the queue is full
        (``TenantThrottled`` when only ``tenant`` is over its fair-share
        cap) and :class:`ServerClosed` after :meth:`close`; a deadline
        that expires while queued fails the future with
        :class:`DeadlineExceeded`."""
        if self._closed:
            raise ServerClosed(f"endpoint {self.model_id!r} is closed")
        arr = np.asarray(value, dtype=self._dtype)
        if self._item_shape is None:
            # first request binds the endpoint's item shape (same
            # one-fixed-shape contract as make_loader_decode_plan)
            self._item_shape = tuple(arr.shape)
        elif tuple(arr.shape) != self._item_shape:
            raise ValueError(
                f"endpoint {self.model_id!r} serves items of shape "
                f"{self._item_shape}; got {tuple(arr.shape)} — one "
                "endpoint serves one item shape (register another for a "
                "second shape)"
            )
        if deadline_ms is None:
            deadline_ms = self._config.default_deadline_ms
        deadline = (
            self._clock() + deadline_ms / 1000.0
            if deadline_ms is not None
            else None
        )
        if deadline is not None and deadline <= self._clock():
            # expired on arrival (upstream ships *remaining* budget):
            # fail fast without burning a queue slot or a batch seat
            metrics.counter("serving.expired").add(1)
            fut: Future = Future()
            fut.set_exception(DeadlineExceeded(
                f"request to {self.model_id!r} expired before submit "
                f"({deadline_ms}ms budget)"
            ))
            return fut
        req = Request(
            value=arr, deadline=deadline, tenant=tenant,
            enqueued_at=self._clock(),
        )
        if tracer.enabled:
            # one span per request, child of the caller's current span;
            # it ends when the future resolves (on the worker thread),
            # recording queue+batch+forward as one client-visible region
            rspan = tracer.start_span(
                "serving.request", model_id=self.model_id
            )
            req.span = rspan
            req.future.add_done_callback(_end_request_span(rspan))
        metrics.counter("serving.requests").add(1)
        self._m_requests.add(1)
        self._ensure_worker()
        self._queue.offer(req)
        return req.future

    def predict(self, value, timeout: Optional[float] = None,
                deadline_ms: Optional[float] = None,
                tenant: Optional[str] = None):
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(
            value, deadline_ms=deadline_ms, tenant=tenant
        ).result(timeout)

    # ------------------------------------------------------------------
    # warmup
    # ------------------------------------------------------------------
    def warmup(self, buckets: Optional[Sequence[int]] = None) -> Tuple[int, ...]:
        """Pre-trace the endpoint's hot buckets (default: the whole
        ladder up to ``max_batch``) so first-request latency is not a
        compile.  Requires a known item shape (pass one at registration
        for cold warmup)."""
        if self._item_shape is None:
            raise ValueError(
                f"endpoint {self.model_id!r} has no item shape yet; "
                "register with item_shape=... to warm up before traffic"
            )
        if not self._compile:
            return ()
        warmed = self._cache.warmup(
            self.model_id,
            self._fused_forward,
            self._item_shape,
            self._dtype,
            buckets=buckets,
            max_batch=self._config.max_batch,
            fingerprint=self._fingerprint,
        )
        if self._ragged_active():
            # pre-compile the slot-block executable too, so the first
            # ragged dispatch is not a compile; the padded ladder above
            # stays warm as the SPARKDL_RAGGED=0 fallback
            import jax

            n = self._pool.n_slots
            fn = self._cache.ragged_program(
                self.model_id, self._masked_fused(), n,
                self._item_shape, self._dtype,
                fingerprint=self._fingerprint,
            )
            x = np.zeros((n, *self._item_shape), dtype=self._dtype)
            mask = np.zeros(n, dtype=bool)
            # warmup WANTS to block — off the request path
            jax.block_until_ready(fn(x, mask))  # sparkdl: disable=host-sync
        return warmed

    # ------------------------------------------------------------------
    # worker
    # ------------------------------------------------------------------
    def _ensure_worker(self) -> None:
        """Start (or restart after an unexpected death) the batch worker —
        a crashed worker must not strand queued futures forever."""
        with self._worker_lock:
            if self._closed:
                return
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(
                    target=self._worker_loop,
                    name=f"sparkdl-serving-{self.model_id}",
                    daemon=True,
                )
                self._worker.start()

    def _worker_loop(self) -> None:
        try:
            while not self._closed:
                try:
                    if self._ragged_active():
                        self._ragged_tick()
                    else:
                        batch = self._queue.take(
                            self._config.max_batch,
                            self._config.max_wait_ms / 1000.0,
                            flush_early=self._device_free,
                        )
                        if batch:
                            self._run_batch(batch)
                    if len(self._window) and not len(self._queue):
                        # nothing left to overlap with — complete the
                        # in-flight batches now rather than holding their
                        # futures until the next poll
                        for host, meta in self._window.drain():
                            self._complete(host, meta)
                except Exception:  # pragma: no cover - defensive
                    # the per-batch path already routes model errors to the
                    # batch's futures; anything landing here is a batcher
                    # bug — log it and keep serving rather than silently
                    # dying
                    logger.exception(
                        "serving worker for %r survived an internal error",
                        self.model_id,
                    )
        finally:
            # a closing worker must resolve every in-flight future
            try:
                for host, meta in self._window.drain():
                    self._complete(host, meta)
            except Exception:  # pragma: no cover - defensive
                logger.exception(
                    "serving worker for %r failed draining in-flight "
                    "batches at shutdown",
                    self.model_id,
                )

    def _device_free(self) -> bool:
        """True while the dispatch window can absorb another batch
        without blocking on an older fetch — the idle-device signal
        that cuts the coalesce linger short (holding a batch while the
        device sits idle buys no occupancy, only latency)."""
        return self._window.has_room

    # ------------------------------------------------------------------
    # ragged slot-block dispatch
    # ------------------------------------------------------------------
    def _ragged_active(self) -> bool:
        """Ragged dispatch, unless the kill switch says padded or the
        endpoint is compiled without a durable fingerprint (the
        sanctioned fallback: an anonymous slot-block executable could
        neither persist nor be shared across restarts)."""
        if not ragged_enabled():
            return False
        if self._compile and self._fingerprint is None:
            return False
        return True

    def _masked_fused(self) -> Callable:
        """The single ragged executable body: the (prologue-fused)
        forward over the whole ``(n_slots, *item)`` block, vacant rows
        zeroed by the occupancy mask — occupancy is data, never shape,
        so every dispatch runs this one program."""
        forward = self._fused_forward

        def fused(block, mask):
            import jax.numpy as jnp

            out = forward(block)
            m = mask.reshape(mask.shape + (1,) * (out.ndim - 1))
            return jnp.where(m, out, jnp.zeros_like(out))

        return fused

    def _ragged_tick(self) -> None:
        """One ragged worker cycle: free slots whose blocks have
        overflowed the window, admit arrivals straight into free slots
        (no coalesce linger), and dispatch them as one masked block."""
        pool = self._pool
        # complete what the window no longer needs in flight — these
        # batches' slots free here, which is what lets the admission
        # below proceed while older blocks are still fetching
        for host, meta in self._window.pop_ready():
            self._complete(host, meta)
        if pool.n_free == 0:
            # every slot is riding an in-flight block: completing the
            # oldest batch is the only way to free one
            if len(self._window):
                host, meta = next(self._window.drain())
                self._complete(host, meta)
            return
        busy = pool.n_occupied > 0 or len(self._window) > 0
        reqs = self._queue.take(
            pool.n_free,
            0.0,
            poll_s=0.0 if busy else 0.05,
            flush_early=self._device_free,
        )
        if not reqs:
            return
        now = self._clock()
        live = []
        for r in reqs:
            if r.expired(now):
                metrics.counter("serving.expired").add(1)
                r.future.set_exception(
                    DeadlineExceeded(
                        f"request to {self.model_id!r} expired after "
                        f"{(now - r.enqueued_at) * 1000:.1f}ms in queue"
                    )
                )
            else:
                live.append(r)
        if not live:
            return
        slots = []
        for r in live:
            slot = pool.acquire(r, r.value, now=now)
            assert slot is not None  # take() was capped at n_free
            slots.append(slot)
            if r.span is not None:
                r.span.event("slot_acquired", slot=slot.index)

        if not self._compile:
            # plain endpoints gather exactly the occupied rows — no pad
            # rows computed at all — and stay fully synchronous (the
            # fault-injection tests rely on deterministic ordering)
            x = np.stack([r.value for r in live])

            def forward_once():
                inject.fire("serving.forward")
                return np.asarray(self._forward(self._prep_host(x)))

            try:
                if not tracer.enabled:
                    self._forward_batch(live, len(live), forward_once, now)
                    return
                with self._batch_span(live, len(live)):
                    self._forward_batch(live, len(live), forward_once, now)
                return
            finally:
                for s in slots:
                    pool.release(s)

        # compiled: dispatch the ONE slot-block program over the pool's
        # block; this dispatch's rows ride the mask (NOT pool.mask() —
        # slots of still-in-flight older blocks must stay masked out of
        # this one's scatter)
        mask = np.zeros(pool.n_slots, dtype=bool)
        for s in slots:
            mask[s.index] = True

        def dispatch_once():
            inject.fire("serving.forward")
            fn = self._cache.ragged_program(
                self.model_id, self._masked_fused(), pool.n_slots,
                self._item_shape, self._dtype,
                fingerprint=self._fingerprint,
            )
            # the program donates its block argument, and on CPU a
            # device_put of a host array may be zero-copy — so the
            # output block can ALIAS the buffer we pass in.  The pool's
            # carry stack is mutable (release() zeroes freed rows while
            # result views may still be unread), so it must never be
            # that buffer: dispatch a private copy of the block
            return fn(pool.carries().copy(), mask)

        bspan = None
        if tracer.enabled:
            bspan = tracer.start_span(
                "serving.batch",
                model_id=self.model_id,
                bucket=pool.n_slots,
                n_real=len(live),
                ragged=True,
                member_span_ids=[
                    r.span.span_id for r in live if r.span is not None
                ],
            )
            for r in live:
                if r.span is not None:
                    r.span.event(
                        "coalesced", batch_span=bspan.span_id,
                        bucket=pool.n_slots,
                    )
        try:
            self._breaker.check()
            retry = self._config.retry
            if retry is not None:
                dls = [r.deadline for r in live if r.deadline is not None]
                deadline = (
                    Deadline(min(dls), what=f"batch to {self.model_id!r}")
                    if dls
                    else None
                )
                out_dev = retry.call(dispatch_once, deadline=deadline)
            else:
                out_dev = dispatch_once()
        except CircuitOpen as e:
            self._fail_batch(live, bspan, e, record=False)
            for s in slots:
                pool.release(s)
            return
        except Exception as e:
            metrics.counter("serving.errors").add(1)
            self._m_errors.add(len(live))
            self._fail_batch(live, bspan, e, record=True)
            for s in slots:
                pool.release(s)
            return
        t_dispatched = self._clock()
        for host, meta in self._window.submit(
            out_dev, meta=(live, pool.n_slots, bspan, now, t_dispatched,
                           slots)
        ):
            self._complete(host, meta)

    def _prep_host(self, x):
        """Eager (plain-endpoint) application of the input prologue —
        same math as the fused trace, materialized back to numpy for
        arbitrary non-JAX forwards."""
        if self._prologue is None:
            return x
        return np.asarray(self._prologue(x))

    def _run_batch(self, reqs) -> None:
        now = self._clock()
        live = []
        for r in reqs:
            if r.expired(now):
                metrics.counter("serving.expired").add(1)
                r.future.set_exception(
                    DeadlineExceeded(
                        f"request to {self.model_id!r} expired after "
                        f"{(now - r.enqueued_at) * 1000:.1f}ms in queue"
                    )
                )
            else:
                live.append(r)
        if not live:
            return
        bucket = shape_bucket(len(live), self._config.max_batch)
        # the sanctioned pad site: the SPARKDL_RAGGED=0 /
        # unfingerprinted-endpoint fallback lane
        x = pad_to_batch(  # sparkdl: disable=bucket-pad
            np.stack([r.value for r in live]), bucket
        )

        if not self._compile:
            # plain-Python endpoints stay fully synchronous — the fault-
            # injection tests rely on deterministic attempt ordering, and
            # there is no async dispatch to overlap anyway
            def forward_once():
                inject.fire("serving.forward")
                return np.asarray(self._forward(self._prep_host(x)))

            if not tracer.enabled:
                self._forward_batch(live, bucket, forward_once, now)
                return
            with self._batch_span(live, bucket) as bspan:  # noqa: F841
                self._forward_batch(live, bucket, forward_once, now)
            return

        # compiled path: dispatch through the engine program now; the
        # blocking fetch happens when this batch falls out of the dispatch
        # window (its device->host copy streams while later batches
        # compute).  Retry wraps the dispatch: injected/trace-time faults
        # raise here synchronously and re-attempt within the deadline;
        # device-side async failures surface at fetch and fail the batch.
        def dispatch_once():
            inject.fire("serving.forward")
            fn = self._cache.program(
                self.model_id, self._fused_forward, bucket,
                self._item_shape, self._dtype,
                fingerprint=self._fingerprint,
            )
            return fn(x)

        bspan = None
        if tracer.enabled:
            bspan = tracer.start_span(
                "serving.batch",
                model_id=self.model_id,
                bucket=bucket,
                n_real=len(live),
                member_span_ids=[
                    r.span.span_id for r in live if r.span is not None
                ],
            )
            for r in live:
                if r.span is not None:
                    r.span.event(
                        "coalesced", batch_span=bspan.span_id, bucket=bucket
                    )
        try:
            self._breaker.check()
            retry = self._config.retry
            if retry is not None:
                dls = [r.deadline for r in live if r.deadline is not None]
                deadline = (
                    Deadline(min(dls), what=f"batch to {self.model_id!r}")
                    if dls
                    else None
                )
                out_dev = retry.call(dispatch_once, deadline=deadline)
            else:
                out_dev = dispatch_once()
        except CircuitOpen as e:
            self._fail_batch(live, bspan, e, record=False)
            return
        except Exception as e:
            metrics.counter("serving.errors").add(1)
            self._m_errors.add(len(live))
            self._fail_batch(live, bspan, e, record=True)
            return
        t_dispatched = self._clock()
        for host, meta in self._window.submit(
            out_dev, meta=(live, bucket, bspan, now, t_dispatched, None)
        ):
            self._complete(host, meta)

    def _batch_span(self, live, bucket):
        """The span fan-in: one batch span per coalesced device call,
        carrying its member requests' span ids (and each member span gets
        a "coalesced" event pointing back) — so a trace can walk
        request -> batch -> retry events in either direction."""
        span_cm = tracer.span(
            "serving.batch",
            model_id=self.model_id,
            bucket=bucket,
            n_real=len(live),
            member_span_ids=[
                r.span.span_id for r in live if r.span is not None
            ],
        )

        class _WithEvents:
            def __enter__(self_inner):
                bspan = span_cm.__enter__()
                for r in live:
                    if r.span is not None:
                        r.span.event(
                            "coalesced", batch_span=bspan.span_id,
                            bucket=bucket,
                        )
                return bspan

            def __exit__(self_inner, *exc):
                return span_cm.__exit__(*exc)

        return _WithEvents()

    def _fail_batch(self, live, bspan, exc, record: bool) -> None:
        if record:
            self._breaker.record_failure()
        if bspan is not None:
            bspan.set_attribute("error", type(exc).__name__)
            bspan.end()
        for r in live:
            r.future.set_exception(exc)

    def _complete(self, host, meta) -> None:
        """Resolve one batch that fell out of the dispatch window.
        ``meta[-1]`` discriminates the lanes: the padded ladder passes
        ``None`` (request i reads row i), the ragged path passes the
        batch's slots (request j reads its slot's row, then frees it)."""
        live, n_computed, bspan, t_batch, t_dispatched, slots = meta
        if isinstance(host, FetchFailure):
            metrics.counter("serving.errors").add(1)
            self._m_errors.add(len(live))
            self._fail_batch(live, bspan, host.error, record=True)
            if slots is not None:
                for s in slots:
                    self._pool.release(s)
            return
        self._breaker.record_success()
        done = self._clock()
        latency = metrics.histogram("serving.latency_ms")
        for i, r in enumerate(live):
            # the phase decomposition rides the future (set BEFORE the
            # result so a reader woken by set_result always sees it):
            # queue wait, device dispatch, device->host fetch — what the
            # replica stamps into the reply envelope's "phases"
            r.future.sparkdl_phases = {
                "replica_queue": (t_batch - r.enqueued_at) * 1000.0,
                "forward": (t_dispatched - t_batch) * 1000.0,
                "fetch": (done - t_dispatched) * 1000.0,
            }
            r.future.set_result(
                host[slots[i].index] if slots is not None else host[i]
            )
            ms = (done - r.enqueued_at) * 1000.0
            ex = r.span.trace_id if r.span is not None else None
            latency.observe(ms, exemplar=ex)
            self._m_latency.observe(ms, exemplar=ex)
        if slots is not None:
            for s in slots:
                self._pool.release(s)
        self._observe_batch(len(live), n_computed)
        if bspan is not None:
            bspan.end()

    def _forward_batch(self, live, bucket, forward_once, t_batch) -> None:
        try:
            # breaker first: while open, fail the batch fast with the
            # typed (transient) CircuitOpen instead of hammering a dead
            # forward path — callers may retry elsewhere / later
            self._breaker.check()
            retry = self._config.retry
            if retry is not None:
                # retries must fit inside the batch's tightest request
                # deadline — backing off past it would compute an answer
                # nobody reads
                dls = [r.deadline for r in live if r.deadline is not None]
                # request deadlines are absolute time.monotonic stamps —
                # Deadline's clock — so wrap the tightest one directly
                deadline = (
                    Deadline(min(dls), what=f"batch to {self.model_id!r}")
                    if dls
                    else None
                )
                out = retry.call(forward_once, deadline=deadline)
            else:
                out = forward_once()
        except CircuitOpen as e:
            for r in live:
                r.future.set_exception(e)
            return
        except Exception as e:
            self._breaker.record_failure()
            metrics.counter("serving.errors").add(1)
            self._m_errors.add(len(live))
            for r in live:
                r.future.set_exception(e)
            return
        self._breaker.record_success()
        done = self._clock()
        latency = metrics.histogram("serving.latency_ms")
        for i, r in enumerate(live):
            # synchronous path: forward and fetch are one region
            r.future.sparkdl_phases = {
                "replica_queue": (t_batch - r.enqueued_at) * 1000.0,
                "forward": (done - t_batch) * 1000.0,
                "fetch": 0.0,
            }
            r.future.set_result(out[i])
            ms = (done - r.enqueued_at) * 1000.0
            ex = r.span.trace_id if r.span is not None else None
            latency.observe(ms, exemplar=ex)
            self._m_latency.observe(ms, exemplar=ex)
        self._observe_batch(len(live), bucket)

    def _observe_batch(self, n_real: int, n_computed: int) -> None:
        """Per-batch padding accounting, shared by every completion
        path: ``n_real`` rows a caller asked for rode a device call of
        ``n_computed`` rows (== n_real on the ragged plain lane, the
        full slot block on the ragged compiled lane, the bucket on the
        padded fallback)."""
        metrics.counter("serving.batches").add(1)
        metrics.histogram("serving.batch_occupancy").observe(
            n_real / n_computed
        )
        metrics.histogram("batcher.pad_fraction").observe(
            (n_computed - n_real) / n_computed
        )
        self._m_rows_real.add(n_real)
        self._m_rows_computed.add(n_computed)
        computed = self._m_rows_computed.value
        if computed:
            self._m_pad_gauge.set(
                round(1.0 - self._m_rows_real.value / computed, 4)
            )

    # ------------------------------------------------------------------
    # lifecycle / introspection
    # ------------------------------------------------------------------
    def close(self) -> None:
        """Stop admitting, fail queued requests with ``ServerClosed``, and
        join the worker."""
        self._closed = True
        for r in self._queue.close():
            r.future.set_exception(
                ServerClosed(f"endpoint {self.model_id!r} closed")
            )
        with self._worker_lock:
            worker = self._worker
        if worker is not None and worker.is_alive():
            worker.join(timeout=5.0)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    @property
    def worker_alive(self) -> bool:
        with self._worker_lock:
            return self._worker is not None and self._worker.is_alive()

    @property
    def breaker(self) -> CircuitBreaker:
        return self._breaker

    @property
    def fingerprint(self) -> Optional[str]:
        """The durable model identity this endpoint was registered with
        (None = uncacheable: no persistent compile cache AND no
        result-cache keying)."""
        return self._fingerprint

    @property
    def degraded(self) -> bool:
        """True while the endpoint's circuit is not closed — new batches
        fail fast with ``CircuitOpen`` (or are probing, when half-open)."""
        return self._breaker.state != "closed"

    def describe(self) -> dict:
        return {
            "model_id": self.model_id,
            "item_shape": (
                list(self._item_shape) if self._item_shape else None
            ),
            "dtype": self._dtype.name,
            "compiled": self._compile,
            "fingerprint": self._fingerprint,
            "ragged": self._ragged_active(),
            "slot_pool": self._pool.snapshot(),
            "prologue": self._prologue is not None,
            "queue_depth": self.queue_depth,
            "queue_capacity": self._queue.capacity,
            "worker_alive": self.worker_alive,
            "closed": self._closed,
            "degraded": self.degraded,
            "breaker": self._breaker.snapshot(),
            "tenants": (
                self._queue.tenants()
                if self._queue.tenant_policy is not None
                else None
            ),
        }
