"""ModelServer — turn registered models into online endpoints.

The front door of :mod:`sparkdl_tpu.serving`: any jax-traceable
``forward(batch) -> batch`` callable, :class:`XlaFunction`, Keras model,
or a UDF registered through ``registerKerasImageUDF`` becomes an endpoint
with dynamic micro-batching, a warm program cache, admission control, and
first-class metrics — the serving layer the ROADMAP's
"heavy traffic from millions of users" north star needs in front of the
existing batch machinery.

Typical flow (see ``examples/online_serving.py``)::

    server = ModelServer.from_registered_udf("my_cnn", session=spark)
    server.warmup()                      # pre-trace the hot buckets
    fut = server.submit(image_array)     # per-request Future
    probs = fut.result(timeout=5.0)
    server.status()                      # /healthz-style snapshot
"""

from __future__ import annotations

import time
from concurrent.futures import Future
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from sparkdl_tpu.serving.batcher import MicroBatcher, ServingConfig
from sparkdl_tpu.serving.cache import ProgramCache
from sparkdl_tpu.serving.decode import DecodeEndpoint, DecodeRequest
from sparkdl_tpu.utils.metrics import metrics


class ModelServer:
    """A set of online endpoints sharing one config and one warm
    :class:`ProgramCache` (LRU over (model, bucket) programs)."""

    def __init__(self, config: Optional[ServingConfig] = None):
        self.config = config or ServingConfig()
        self._cache = ProgramCache(
            maxsize=self.config.cache_size,
            compile_counter=metrics.counter("serving.compiles"),
        )
        self._endpoints: Dict[str, MicroBatcher] = {}
        self._default: Optional[str] = None
        self._started_at = time.monotonic()
        self._closed = False
        self._telemetry: Optional[Dict[str, Any]] = None

    # ------------------------------------------------------------------
    # registration
    # ------------------------------------------------------------------
    def register(
        self,
        model_id: str,
        forward: Callable[[Any], Any],
        item_shape: Optional[Sequence[int]] = None,
        dtype: Any = np.float32,
        compile: bool = True,
        fingerprint: Optional[str] = None,
        prologue: Optional[Callable[[Any], Any]] = None,
    ) -> "ModelServer":
        """Register ``forward(batch) -> batch`` as endpoint ``model_id``.

        ``item_shape`` (one item, no leading batch dim) enables cold
        :meth:`warmup`; without it the first request binds the shape.
        ``fingerprint`` — a durable identity of the model and its weights
        (e.g. a saved-file path+mtime) — lets the program cache persist
        this endpoint's compiled executables to disk, so a restarted
        server's :meth:`warmup` loads instead of recompiling; it also
        gates ragged slot-block dispatch for compiled endpoints
        (unfingerprinted ones serve on the padded bucket ladder).
        ``prologue`` — a jnp-traceable, batch-row-independent input
        stage (see :func:`~sparkdl_tpu.transformers.utils.
        make_input_prologue`, or a registry entry's
        ``serving_prologue()``) — fuses decode-output cast/resize/
        normalize INTO the endpoint executable, replacing the host-side
        ``device_resize`` round-trips.  Returns ``self`` for
        chaining."""
        if model_id in self._endpoints:
            raise ValueError(f"endpoint {model_id!r} already registered")
        self._endpoints[model_id] = MicroBatcher(
            model_id,
            forward,
            self.config,
            self._cache,
            item_shape=item_shape,
            dtype=dtype,
            compile=compile,
            fingerprint=fingerprint,
            prologue=prologue,
        )
        if self._default is None:
            self._default = model_id
        return self

    def register_decode(
        self,
        model_id: str,
        step_fn: Callable[[Any], Tuple[Any, Any]],
        init_fn: Callable[[Any], Any],
        max_steps: int,
        eos_fn: Optional[Callable] = None,
        n_slots: int = 8,
        dtype: Any = np.float32,
        compile: bool = True,
        fingerprint: Optional[str] = None,
    ) -> "ModelServer":
        """Register an autoregressive decode endpoint (ISSUE-18).

        ``step_fn(carries) -> (new_carries, tokens)`` runs fused over
        the endpoint's fixed ``(n_slots, *carry_shape)`` pool every
        step — one compiled executable per slot-pool shape, resolved
        through the engine cache exactly like the one-shot buckets.
        ``init_fn(prompt) -> carry`` seeds a slot; ``eos_fn(token,
        step) -> bool`` ends a stream early; ``max_steps`` caps every
        stream (requests may ask for fewer).  Serve with
        :meth:`decode` / :meth:`submit_decode`."""
        if model_id in self._endpoints:
            raise ValueError(f"endpoint {model_id!r} already registered")
        self._endpoints[model_id] = DecodeEndpoint(
            model_id,
            step_fn,
            init_fn,
            max_steps,
            eos_fn=eos_fn,
            n_slots=n_slots,
            queue_capacity=self.config.queue_capacity,
            dtype=dtype,
            compile=compile,
            fingerprint=fingerprint,
        )
        if self._default is None:
            self._default = model_id
        return self

    @classmethod
    def from_xla_function(
        cls,
        fn,
        model_id: Optional[str] = None,
        config: Optional[ServingConfig] = None,
        device=None,
    ) -> "ModelServer":
        """Serve an :class:`~sparkdl_tpu.graph.function.XlaFunction`
        (first output).  Params are pinned to one device once — online
        batches are latency-bound single-device work, unlike the
        SPMD batch path."""
        import jax

        params = jax.device_put(
            fn.params, device or jax.local_devices()[0]
        )

        def forward(x, _apply=fn.apply, _params=params):
            return _apply(_params, x)[0]

        item_shape = None
        if getattr(fn, "input_specs", None):
            shape, _ = fn.input_specs[0]
            item_shape = tuple(shape[1:])
        server = cls(config=config)
        server.register(
            model_id or fn.name,
            forward,
            item_shape=item_shape,
            fingerprint=getattr(fn, "fingerprint", None),
        )
        return server

    @classmethod
    def from_keras(
        cls,
        model_or_file,
        model_id: Optional[str] = None,
        config: Optional[ServingConfig] = None,
        compute_dtype: Optional[str] = None,
    ) -> "ModelServer":
        """Serve a Keras model or saved ``.keras``/``.h5`` file."""
        from sparkdl_tpu.graph.function import XlaFunction

        fn = XlaFunction.from_keras(
            model_or_file, compute_dtype=compute_dtype
        )
        return cls.from_xla_function(fn, model_id=model_id, config=config)

    @classmethod
    def from_registered_udf(
        cls,
        udf_name: str,
        session=None,
        config: Optional[ServingConfig] = None,
    ) -> "ModelServer":
        """Serve a UDF registered with ``registerKerasImageUDF`` as an
        online endpoint: the same fused forward (cast + resize + model in
        one program) the SQL path runs, fed by the micro-batcher instead
        of a DataFrame partition."""
        from sparkdl_tpu.sql.session import TPUSession

        session = session or TPUSession.getActiveSession()
        udf = session.udf.get(udf_name)
        meta = getattr(udf, "_serving_endpoint", None)
        if meta is None:
            raise ValueError(
                f"UDF {udf_name!r} was not registered by "
                "registerKerasImageUDF (only model UDFs carry a serving "
                "forward); register the model directly with "
                "ModelServer.register instead"
            )
        server = cls(config=config)
        server.register(
            meta["model_id"],
            meta["forward"],
            item_shape=meta["item_shape"],
            dtype=meta["dtype"],
            fingerprint=meta.get("fingerprint"),
        )
        return server

    # ------------------------------------------------------------------
    # request path
    # ------------------------------------------------------------------
    def _endpoint(self, model_id: Optional[str]) -> MicroBatcher:
        if model_id is None:
            if len(self._endpoints) != 1:
                raise ValueError(
                    "model_id is required when the server hosts "
                    f"{len(self._endpoints)} endpoints "
                    f"({sorted(self._endpoints)})"
                )
            model_id = self._default
        try:
            return self._endpoints[model_id]
        except KeyError:
            raise KeyError(
                f"no endpoint {model_id!r}; registered: "
                f"{sorted(self._endpoints)}"
            ) from None

    def fingerprints(self) -> Dict[str, str]:
        """Endpoint id -> durable fingerprint, for every endpoint that
        has one.  What a replica advertises in its ready line — the
        version half of the router's result-cache keys; endpoints
        without a fingerprint are simply absent (uncacheable)."""
        return {
            mid: ep.fingerprint
            for mid, ep in self._endpoints.items()
            if ep.fingerprint
        }

    def submit(
        self,
        value,
        model_id: Optional[str] = None,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
    ) -> Future:
        """Admit one item for ``model_id`` (optional when the server
        hosts exactly one endpoint); returns the request's Future."""
        return self._endpoint(model_id).submit(
            value, deadline_ms=deadline_ms, tenant=tenant
        )

    def predict(
        self,
        value,
        model_id: Optional[str] = None,
        timeout: Optional[float] = None,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
    ):
        return self._endpoint(model_id).predict(
            value, timeout=timeout, deadline_ms=deadline_ms, tenant=tenant
        )

    def _decode_endpoint(self, model_id: Optional[str]) -> DecodeEndpoint:
        ep = self._endpoint(model_id)
        if not isinstance(ep, DecodeEndpoint):
            raise TypeError(
                f"endpoint {ep.model_id!r} is a one-shot endpoint; "
                "decode ops need register_decode"
            )
        return ep

    def submit_decode(
        self,
        prompt,
        model_id: Optional[str] = None,
        emit: Optional[Callable[[dict], Any]] = None,
        max_steps: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
        trace: Optional[Tuple[int, int]] = None,
    ) -> DecodeRequest:
        """Admit one decode stream; ``emit`` receives incremental
        stream-frame dicts as tokens land (None for collect-all).  The
        returned request's ``future`` resolves with the stacked token
        output — byte-identical to the streamed sequence."""
        return self._decode_endpoint(model_id).submit(
            prompt,
            emit=emit,
            max_steps=max_steps,
            deadline_ms=deadline_ms,
            tenant=tenant,
            trace=trace,
        )

    def decode(
        self,
        prompt,
        model_id: Optional[str] = None,
        max_steps: Optional[int] = None,
        deadline_ms: Optional[float] = None,
        tenant: Optional[str] = None,
        timeout: Optional[float] = None,
    ) -> np.ndarray:
        """Blocking decode: the full ``(steps, *token_shape)`` output."""
        return self._decode_endpoint(model_id).decode(
            prompt,
            max_steps=max_steps,
            deadline_ms=deadline_ms,
            tenant=tenant,
            timeout=timeout,
        )

    # ------------------------------------------------------------------
    # warmup / observability / lifecycle
    # ------------------------------------------------------------------
    def warmup(
        self,
        model_id: Optional[str] = None,
        buckets: Optional[Sequence[int]] = None,
    ) -> Dict[str, Tuple[int, ...]]:
        """Pre-trace hot buckets for one endpoint (or all of them);
        returns ``{model_id: buckets_traced}``."""
        targets = (
            [self._endpoint(model_id)] if model_id is not None
            else list(self._endpoints.values())
        )
        out: Dict[str, Tuple] = {}
        for ep in targets:
            if isinstance(ep, DecodeEndpoint):
                # decode endpoints have exactly one program (the pool
                # shape); warmable only once a request/example bound it
                try:
                    src = ep.warmup()
                    out[ep.model_id] = (src,) if src else ()
                except ValueError:
                    out[ep.model_id] = ()
            else:
                out[ep.model_id] = ep.warmup(buckets=buckets)
        return out

    def status(self, probe_device: bool = False,
               probe_timeout_s: float = 60.0) -> Dict[str, Any]:
        """A ``/healthz``-style snapshot: endpoints, queue depths, cache
        occupancy, and the ``serving.*`` metrics.

        An endpoint whose circuit breaker is not closed reports as
        ``degraded`` (its batches fail fast with ``CircuitOpen`` until
        the recovery window elapses and a probe succeeds); a degraded
        server stays "healthy" — it is serving, just shedding one
        endpoint — so orchestrators restart on ``healthy: false`` only.

        ``probe_device=True`` additionally checks device liveness with a
        watchdogged tiny dispatch on the device this process holds
        (:func:`sparkdl_tpu.resilience.watchdog.check_device`; in-process,
        because the server owns the chip and a child could not open it)
        — a device call that does not return reports as unhealthy with a
        typed ``error_class`` instead of hanging the health endpoint."""
        degraded = sorted(
            mid for mid, ep in self._endpoints.items() if ep.degraded
        )
        out: Dict[str, Any] = {
            "healthy": not self._closed and all(
                ep.worker_alive or ep.queue_depth == 0
                for ep in self._endpoints.values()
            ),
            "degraded": degraded,
            "closed": self._closed,
            "uptime_s": round(time.monotonic() - self._started_at, 3),
            "endpoints": {
                mid: ep.describe() for mid, ep in self._endpoints.items()
            },
            "program_cache": self._cache.stats(),
            # one consistent point-in-time read (registry.snapshot with
            # a prefix filter), not ad-hoc key picking
            "metrics": metrics.snapshot(prefix="serving."),
        }
        if probe_device:
            from sparkdl_tpu.resilience.watchdog import check_device

            out["device"] = check_device(timeout_s=probe_timeout_s)
            out["healthy"] = out["healthy"] and out["device"]["ok"]
        return out

    def metrics_text(self, serving_only: bool = False) -> str:
        """The process metrics in the Prometheus text exposition format
        — what an HTTP front-end returns from ``/metrics``.  By default
        the FULL registry (a serving process wants its ``data.*`` /
        ``resilience.*`` series scraped too); ``serving_only=True``
        restricts to ``serving.*``."""
        from sparkdl_tpu.obs.export import prometheus_text

        return prometheus_text(
            metrics, prefix="serving." if serving_only else None
        )

    def start_telemetry(
        self,
        port: int = 0,
        host: str = "127.0.0.1",
        sample_interval_s: float = 1.0,
        slo_interval_s: float = 5.0,
        latency_threshold_ms: float = 250.0,
        latency_objective: float = 0.99,
        error_objective: float = 0.999,
        extra_slos: Optional[Sequence] = None,
        **slo_overrides,
    ):
        """Start the telemetry plane for this server; returns the
        :class:`~sparkdl_tpu.obs.server.ObsServer` (its ``.url`` is the
        scrape target; ``port=0`` picks an ephemeral port).

        Wires, per the ISSUE-8 plane: a
        :class:`~sparkdl_tpu.obs.timeseries.TimeSeriesRecorder` sampling
        the registry every ``sample_interval_s``; an
        :class:`~sparkdl_tpu.obs.slo.SLOEngine` with the per-endpoint
        latency + error-rate objectives
        (:func:`~sparkdl_tpu.obs.slo.serving_slos`, thresholds/windows
        tunable via the keyword knobs and ``slo_overrides``) plus any
        ``extra_slos``; a span sink feeding ``/debug/spans`` (spans flow
        only while tracing is enabled); and ``/healthz`` backed by
        :meth:`status` — 200 while healthy, 503 when not.  Everything
        tears down in :meth:`close`.  Idempotent: a second call returns
        the running server."""
        if self._telemetry is not None:
            return self._telemetry["server"]
        from sparkdl_tpu.obs import (
            JsonlTraceSink,
            ObsServer,
            SLOEngine,
            TimeSeriesRecorder,
            serving_slos,
            tracer,
        )

        recorder = TimeSeriesRecorder(
            interval_s=sample_interval_s
        ).start()
        engine = SLOEngine(recorder)
        for mid in self._endpoints:
            engine.add(*serving_slos(
                mid,
                latency_threshold_ms=latency_threshold_ms,
                latency_objective=latency_objective,
                error_objective=error_objective,
                **slo_overrides,
            ))
        if extra_slos:
            engine.add(*extra_slos)
        engine.start(interval_s=slo_interval_s)
        sink = JsonlTraceSink(capacity=1024)
        tracer.add_sink(sink)
        server = ObsServer(
            port=port,
            host=host,
            recorder=recorder,
            slo_engine=engine,
            span_sink=sink,
            health_fn=self.status,
        ).start()
        self._telemetry = {
            "server": server,
            "recorder": recorder,
            "engine": engine,
            "sink": sink,
        }
        return server

    @property
    def telemetry(self) -> Optional[Dict[str, Any]]:
        """The live plane (``server``/``recorder``/``engine``/``sink``)
        or None before :meth:`start_telemetry`."""
        return self._telemetry

    def close(self) -> None:
        self._closed = True
        if self._telemetry is not None:
            plane, self._telemetry = self._telemetry, None
            from sparkdl_tpu.obs import tracer

            plane["engine"].stop()
            plane["recorder"].stop()
            plane["server"].close()
            tracer.remove_sink(plane["sink"])
        for ep in self._endpoints.values():
            ep.close()

    def __enter__(self) -> "ModelServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self):
        return (
            f"ModelServer(endpoints={sorted(self._endpoints)}, "
            f"config={self.config})"
        )
