"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the normal entry points once at published width on a TPU v5e and
checks every output against a plain float32 reference computed on the CPU
backend of the same process:

    python chip_smoke.py              one chip: featurize, udf, serve, fit, fleet
    python chip_smoke.py --chips 4    four chips: dp_fit, dp_transform, fleet (x4)
    python chip_smoke.py --rehearse   tiny shapes on whatever JAX finds (the CPU
                                      here); reports the platform it really ran on

Every phase prints one JSON line (seconds split into compile / run /
reference, rows, error against the reference and the tolerance it is held
to, pack path, dispatch lane, each program's ``source`` of compile / disk /
memory, peak device bytes, and what it found wrong).  A phase that fails
raises — after its line is printed, so a failed run still says by how much —
and nothing catches it: the exit code is non-zero and no later phase runs.
The LAST line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` with the device
as JAX reported it.  Without ``--rehearse`` the script refuses to run
(``"ok": false``, exit code 1) unless that platform is ``tpu``.  Inputs come
from ``--seed``; there is no network.

One process per chip.  A chip belongs to one process at a time, so at no
moment may two live processes have initialised the TPU backend:

- THIS process (the parent) never initialises a JAX backend.  It starts
  the in-process phases in ONE child, which holds the chip(s), reports the
  device, writes the fleet's requests and reference answers into the work
  directory, and exits;
- only after that child is gone does the parent run the ``fleet`` phase:
  the :class:`ReplicaSupervisor` and its router live here, and each replica
  process it spawns is restricted to its own chip by the supervisor.

The work directory (``.chip_smoke/``, git-ignored, rebuilt every run) and the
compile cache (``sparkdl_tpu.engine.cache.compile_cache_root``) are the only
places the script writes besides ``TMPDIR``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, ".chip_smoke")
IMAGES = os.path.join(WORK, "images")
UDF_MODEL = os.path.join(WORK, "mobilenet_v2.keras")
FIT_MODEL = os.path.join(WORK, "resnet50.keras")
SERVE_CONFIG = os.path.join(WORK, "serve.json")
FLEET_REQUESTS = os.path.join(WORK, "fleet_requests.npz")
DEVICE_FILE = os.path.join(WORK, "device.json")
UDF_NAME = "smoke_mnv2"

#: published widths, real image sizes; depth is never cut here because
#: every model below fits one chip whole
FULL = dict(
    n_images=256, image_px=(200, 520), featurize_batch=32,
    udf_hw=224, udf_alpha=1.0, udf_classes=1000,
    serve_max_batch=8, serve_requests=64,
    fit_hw=224, fit_batch=32, fit_classes=1000, fit_lr=1e-4,
    fleet_requests=32,
)
#: the rehearsal: same models, same code paths, tiny shapes
TINY = dict(
    n_images=16, image_px=(40, 96), featurize_batch=4,
    udf_hw=96, udf_alpha=0.35, udf_classes=10,
    serve_max_batch=2, serve_requests=8,
    fit_hw=64, fit_batch=16, fit_classes=10, fit_lr=1e-4,
    fleet_requests=8,
)

#: Tolerances.  All are max|y - ref| over a scale of the reference, so they
#: are dimensionless.  TOL_BF16 and the DP bounds stand as fixed before the
#: first chip run (PERF.md, Findings, PR 21); TOL_PROBS and TOL_LOSS were
#: re-set after it, from what that run and a precision probe measured.
#:
#: bf16 keeps 8 bits of mantissa (2^-9 = 2e-3 per rounding); ~50 layers deep
#: that accumulates to well under a tenth of the feature scale (4.3e-3 on
#: the chip).
TOL_BF16 = 6e-2
#: A "float32" program on the TPU multiplies in ONE bf16 pass by default.
#: For the MobileNetV2 softmax that is 0.11-0.15 of the reference's spread
#: around its row mean (8e-6 at "highest" precision, same program, same
#: inputs: it is the precision, not a fault), with every row still
#: correlated > 0.99 with its reference.  A wrong channel order, resize or
#: row order is an error of order one and a correlation far below this.
TOL_PROBS = 0.3
MIN_ROW_CORR = 0.98
#: a loss is a mean over the batch: 8e-4 on the chip at default precision
TOL_LOSS = 1e-2
#: DP over four chips against the same step shard by shard on one chip,
#: each step from the same state: the same arithmetic at the same shapes,
#: only the all-reduce's order differs — float tolerance.
TOL_DP_LOSS = 1e-3
TOL_DP_UPDATE = 1e-2
FIT_STEPS = 3


# ---------------------------------------------------------------------------
# in-process phases — run in the ONE child that holds the chip(s)
# ---------------------------------------------------------------------------


class _Meter:
    """Seconds JAX spent tracing, lowering and compiling (or fetching a
    compiled program from its persistent cache), and how often that cache
    hit or was written, by listening to JAX's own monitoring events — so
    the split covers every program, not only those of the engine."""

    _COMPILE_EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.reference_s = 0.0
        self.reference_compile_s = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event in self._COMPILE_EVENTS:
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.writes

    @contextlib.contextmanager
    def reference(self):
        """Time a reference computation; what it spends (its own compiles
        included) is reported apart from the phase's compile and run."""
        compile0, start = self.compile_s, time.perf_counter()
        try:
            yield
        finally:
            self.reference_s += time.perf_counter() - start
            self.reference_compile_s += self.compile_s - compile0


def _memory_stat(key: str):
    """One memory statistic of every local device (None where the backend
    keeps none, as the CPU's)."""
    import jax

    return [(d.memory_stats() or {}).get(key) for d in jax.local_devices()]


def _allocs():
    return _memory_stat("num_allocs")


def _alloc_delta(before, after):
    return [
        None if a is None or b is None else b - a
        for a, b in zip(before, after)
    ]


class Checks:
    """What a phase found wrong.  A phase measures first and judges last:
    its record is printed whole, ``failures`` included, and only then does
    :func:`finish` end the run — so a failed run still says by how much."""

    def __init__(self):
        self.failures = []

    def within(self, name: str, err: float, tol: float) -> None:
        if not (math.isfinite(err) and err <= tol):
            self.failures.append(
                f"{name}: error {err!r} against the reference exceeds the "
                f"stated tolerance {tol}"
            )

    def require(self, ok: bool, message: str) -> None:
        if not ok:
            self.failures.append(message)


def finish(record: dict) -> None:
    """Print a phase's record; a phase with failures ends the run here,
    with a non-zero exit code and no later phase."""
    failures = record.get("failures", [])
    print(json.dumps({**record, "ok": not failures}), flush=True)
    if failures:
        raise AssertionError("; ".join(failures))


def run_phases(phases, ctx) -> None:
    """Run ``(name, fn)`` phases in order, one JSON line each.  ``fn(ctx)``
    returns the phase's record; a failed phase raises (here, after its
    record is printed, or earlier where it could not go on) — there is
    deliberately no ``except``."""
    meter = ctx["meter"]
    for name, fn in phases:
        compile0, hits0, writes0 = meter.snapshot()
        meter.reference_s = meter.reference_compile_s = 0.0
        start = time.perf_counter()
        record = fn(ctx)
        total = time.perf_counter() - start
        compile1, hits1, writes1 = meter.snapshot()
        reference_s = meter.reference_s
        compile_s = compile1 - compile0 - meter.reference_compile_s
        finish({
            "phase": name,
            **record,
            "seconds": {
                "total": round(total, 3),
                "compile": round(compile_s, 3),
                "reference": round(reference_s, 3),
                "run": round(max(total - compile_s - reference_s, 0.0), 3),
            },
            "jax_cache": {
                "hits": hits1 - hits0, "writes": writes1 - writes0,
            },
            "peak_device_bytes": _memory_stat("peak_bytes_in_use"),
        })


def _scaled_max_err(got, ref, scale=None) -> float:
    """max|got - ref| over a scale of the reference (default max|ref|)."""
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    if got.shape != ref.shape:
        raise AssertionError(f"shape {got.shape} != reference {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values in the output")
    scale = float(np.abs(ref).max()) if scale is None else float(scale)
    if not scale > 0.0:
        raise AssertionError("the reference is identically zero")
    return float(np.abs(got - ref).max() / scale)


def _make_images(cfg, seed: int):
    """``n_images`` JPEG/PNG files of mixed sizes, smooth enough that a
    resize is well-conditioned (a coarse random grid blown up, plus a
    little noise), all from ``seed``."""
    import numpy as np
    from PIL import Image

    rng = np.random.RandomState(seed)
    os.makedirs(IMAGES, exist_ok=True)
    lo, hi = cfg["image_px"]
    # a dozen distinct (h, w): mixed enough that no partition is uniform,
    # few enough that the references' host resize compiles a dozen times
    palette = rng.randint(lo, hi + 1, size=(12, 2))
    paths = []
    for i in range(cfg["n_images"]):
        h, w = (int(v) for v in palette[rng.randint(len(palette))])
        coarse = rng.randint(0, 256, (6, 6, 3)).astype(np.uint8)
        img = np.asarray(
            Image.fromarray(coarse).resize((w, h), Image.BICUBIC), np.int16
        )
        img = np.clip(img + rng.randint(-6, 7, img.shape), 0, 255)
        ext = "png" if i % 4 == 0 else "jpg"
        path = os.path.join(IMAGES, f"img_{i:04d}.{ext}")
        Image.fromarray(img.astype(np.uint8)).save(path, quality=92)
        paths.append(path)
    return paths


def _load_rgb(path):
    import numpy as np
    from PIL import Image

    return np.asarray(Image.open(path).convert("RGB"), np.float32)


def _cpu():
    import jax

    return jax.local_devices(backend="cpu")[0]


def _host_resized(paths, hw):
    """Decoded RGB float images resized on the host with plain
    ``jax.image.resize`` on the CPU backend — independent of the native
    pack and of the device-side prologue."""
    import jax
    import numpy as np

    out = np.empty((len(paths), hw[0], hw[1], 3), np.float32)
    with jax.default_device(_cpu()):
        for i, p in enumerate(paths):
            out[i] = np.asarray(
                jax.image.resize(_load_rgb(p), (hw[0], hw[1], 3), "bilinear")
            )
    return out


def _keras_on_cpu(model, x, training: bool = False, chunk: int = 32):
    """The Keras model's float32 forward on the CPU backend, whatever
    device its own variables live on."""
    import jax
    import numpy as np

    cpu = _cpu()
    tr = [jax.device_put(np.asarray(v), cpu) for v in model.trainable_variables]
    nt = [
        jax.device_put(np.asarray(v), cpu)
        for v in model.non_trainable_variables
    ]

    @jax.jit
    def forward(tr, nt, xb):
        out, _ = model.stateless_call(tr, nt, xb, training=training)
        return out

    with jax.default_device(cpu):
        return np.concatenate([
            np.asarray(forward(tr, nt, jax.device_put(x[lo:lo + chunk], cpu)))
            for lo in range(0, len(x), chunk)
        ])


def _engine_sources():
    from sparkdl_tpu.engine import engine

    return {
        e["program"]: e["source"] for e in engine.stats()["entries"]
    }


def _pack_path() -> str:
    from sparkdl_tpu import native

    return "native" if native.is_available() else "python"


def _inception_reference(paths):
    """InceptionV3 features in float32 on the CPU backend, in plain
    jax.numpy: the transformer's documented ``"random"`` weights
    (``module.init(PRNGKey(0), zeros)``), host-resized RGB inputs, the
    model's "tf" preprocessing written out."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.models import get_keras_application_model

    entry = get_keras_application_model("InceptionV3")
    h, w = entry.input_size
    x = _host_resized(paths, (h, w))
    with jax.default_device(_cpu()):
        variables = entry.make_module().init(
            jax.random.PRNGKey(0), jnp.zeros((1, h, w, 3), jnp.float32)
        )
        module = entry.make_module(dtype=jnp.float32)

        @jax.jit
        def forward(xb):
            return module.apply(
                variables, xb / 127.5 - 1.0, features_only=True
            )

        return np.concatenate([
            np.asarray(forward(x[lo:lo + 32])) for lo in range(0, len(x), 32)
        ])


def _featurize(ctx, phase_tag: str):
    import numpy as np

    from sparkdl_tpu import DeepImageFeaturizer

    cfg = ctx["cfg"]
    allocs0 = _allocs()
    featurizer = DeepImageFeaturizer(
        inputCol="image", outputCol="features", modelName="InceptionV3",
        modelWeights="random", batchSize=cfg["featurize_batch"],
    )
    rows = featurizer.transform(ctx["df"]).select(
        "filePath", "features"
    ).collect()
    allocs1 = _allocs()
    by_path = {r["filePath"]: np.asarray(r["features"].toArray()) for r in rows}
    got = np.stack([by_path[p] for p in ctx["paths"]])
    with ctx["meter"].reference():
        ref = _inception_reference(ctx["paths"])
    err = _scaled_max_err(got, ref)
    checks = Checks()
    checks.within(phase_tag, err, TOL_BF16)
    checks.require(
        got.shape == (cfg["n_images"], 2048),
        f"{phase_tag}: features have shape {got.shape}",
    )
    return {
        "rows": len(rows), "shape": list(got.shape), "dtype": "bfloat16",
        "err": err, "tol": TOL_BF16, "err_is": "max|y-ref|/max|ref|",
        # how far the reference moves from image to image, on the same
        # scale: an error below it means rows were told apart
        "ref_row_spread": float(ref.std(axis=0).max() / np.abs(ref).max()),
        "pack": _pack_path(), "programs": _engine_sources(),
        "allocs_per_device": _alloc_delta(allocs0, allocs1),
        "failures": checks.failures,
    }


def phase_featurize(ctx):
    return _featurize(ctx, "featurize")


def _build_udf_model(cfg, seed: int):
    """Seeded Keras MobileNetV2 (``weights=None``), built and saved on the
    CPU backend.  Left at its initial batch-norm statistics the net shrinks
    every input to the same uniform softmax, and a comparison of two
    constants proves nothing — so the moving statistics are set once from a
    seeded batch (momentum 0: moving := batch), which makes the answer
    depend on the image."""
    import jax
    import keras
    import numpy as np

    hw = cfg["udf_hw"]
    with jax.default_device(_cpu()):
        keras.utils.set_random_seed(seed)
        model = keras.applications.MobileNetV2(
            weights=None, input_shape=(hw, hw, 3), alpha=cfg["udf_alpha"],
            classes=cfg["udf_classes"],
        )
        norms = [
            layer for layer in model._flatten_layers()
            if isinstance(layer, keras.layers.BatchNormalization)
        ]
        momenta = [layer.momentum for layer in norms]
        for layer in norms:
            layer.momentum = 0.0
        model(
            np.random.RandomState(seed).randint(0, 256, (8, hw, hw, 3))
            .astype(np.float32),
            training=True,
        )
        for layer, momentum in zip(norms, momenta):
            layer.momentum = momentum
        model.save(UDF_MODEL)
    with open(SERVE_CONFIG, "w") as fh:
        json.dump({"max_batch": cfg["serve_max_batch"]}, fh)
    return model


def _check_probs(checks: Checks, name: str, got, ref) -> dict:
    """Softmax outputs of a randomly initialised net sit near 1/classes, so
    the error is scaled by how far the reference moves around its row mean,
    not by its absolute size; and every row must correlate with ITS
    reference row."""
    import numpy as np

    ref = np.asarray(ref, np.float64)
    spread = np.abs(ref - ref.mean(axis=-1, keepdims=True)).max()
    err = _scaled_max_err(got, ref, scale=spread)
    corr = float(min(np.corrcoef(g, r)[0, 1] for g, r in zip(got, ref)))
    checks.within(name, err, TOL_PROBS)
    checks.require(
        corr >= MIN_ROW_CORR,
        f"{name}: a row correlates {corr!r} with its reference, below "
        f"the stated {MIN_ROW_CORR}",
    )
    return {
        "err": err, "tol": TOL_PROBS,
        "err_is": "max|y-ref|/max|ref-rowmean(ref)|",
        "row_corr_min": corr, "row_corr_floor": MIN_ROW_CORR,
    }


def _udf_reference(ctx):
    """Keras on the CPU backend over host-resized inputs; also leaves the
    fleet's requests and their reference answers in the work directory
    (the parent that routes them never touches JAX)."""
    import numpy as np

    cfg = ctx["cfg"]
    hw = cfg["udf_hw"]
    x = _host_resized(ctx["paths"], (hw, hw))
    ref = _keras_on_cpu(ctx["udf_model"], x)
    n = cfg["fleet_requests"]
    np.savez(FLEET_REQUESTS, x=x[:n], ref=ref[:n])
    ctx["udf_inputs"], ctx["udf_ref"] = x, ref


def phase_udf(ctx):
    import numpy as np

    from sparkdl_tpu import registerKerasImageUDF

    ctx["udf_model"] = _build_udf_model(ctx["cfg"], ctx["seed"])
    registerKerasImageUDF(UDF_NAME, UDF_MODEL, session=ctx["spark"])
    ctx["df"].createOrReplaceTempView("smoke_images")
    rows = ctx["spark"].sql(
        f"SELECT filePath, {UDF_NAME}(image) AS probs FROM smoke_images"
    ).collect()
    by_path = {r["filePath"]: np.asarray(r["probs"].toArray()) for r in rows}
    got = np.stack([by_path[p] for p in ctx["paths"]])
    with ctx["meter"].reference():
        _udf_reference(ctx)
    checks = Checks()
    return {
        "rows": len(rows), "shape": list(got.shape), "dtype": "float32",
        **_check_probs(checks, "udf", got, ctx["udf_ref"]),
        "pack": _pack_path(), "programs": _engine_sources(),
        "failures": checks.failures,
    }


def phase_serve(ctx):
    import numpy as np

    from sparkdl_tpu.serving import ModelServer, ServingConfig
    from sparkdl_tpu.utils.metrics import metrics

    cfg = ctx["cfg"]
    n = cfg["serve_requests"]
    x, ref = ctx["udf_inputs"][:n], ctx["udf_ref"][:n]
    compiles = metrics.counter("serving.compiles")
    with ModelServer.from_registered_udf(
        UDF_NAME, session=ctx["spark"],
        config=ServingConfig(max_batch=cfg["serve_max_batch"]),
    ) as server:
        warmed = server.warmup()
        after_warmup = compiles.value
        with ThreadPoolExecutor(max_workers=n) as pool:
            got = np.stack(list(
                pool.map(lambda v: server.predict(v, timeout=300.0), x)
            ))
        status = server.status(probe_device=True)
    checks = Checks()
    checked = _check_probs(checks, "serve", got, ref)
    checks.require(
        compiles.value == after_warmup,
        f"serving.compiles moved after warm-up: {after_warmup} -> "
        f"{compiles.value}",
    )
    checks.require(
        status["healthy"] and status["device"]["ok"],
        f"status(probe_device=True) is not healthy: {status['device']}",
    )
    endpoint = status["endpoints"][UDF_NAME]
    return {
        "requests": n, "shape": list(got.shape), **checked,
        "lane": "ragged" if endpoint["ragged"] else "padded",
        "warmed_buckets": {m: list(b) for m, b in warmed.items()},
        "compiles_after_warmup": compiles.value - after_warmup,
        "programs": {
            f"{k['model']}:{k['bucket']}": k["source"]
            for k in status["program_cache"]["keys"]
        },
        "batches": metrics.counter("serving.batches").value,
        "device_probe": status["device"],
        "failures": checks.failures,
    }


def _fit_loader(hw: int):
    def load(uri):
        import numpy as np
        from PIL import Image

        img = Image.open(uri).convert("RGB").resize((hw, hw), Image.BILINEAR)
        x = np.asarray(img, np.float32)[..., ::-1]  # caffe mode: BGR,
        return x - np.asarray([103.939, 116.779, 123.68], np.float32)

    return load


def _build_fit_model(cfg, seed: int):
    import jax
    import keras

    hw = cfg["fit_hw"]
    with jax.default_device(_cpu()):
        keras.utils.set_random_seed(seed + 1)
        model = keras.applications.ResNet50(
            weights=None, input_shape=(hw, hw, 3),
            classes=cfg["fit_classes"],
        )
        model.save(FIT_MODEL)
    return model


def _fit(ctx, steps: int = FIT_STEPS):
    """``KerasImageFileEstimator.fit`` for ``steps`` steps (one step per
    epoch, so the tracer's epoch events carry every step's loss) with the
    streaming ``data/`` pipeline feeding it.  Returns (losses, labels,
    tuned model path, step milliseconds)."""
    import numpy as np

    from sparkdl_tpu import KerasImageFileEstimator
    from sparkdl_tpu.obs import tracer

    cfg = ctx["cfg"]
    n = cfg["fit_batch"]
    labels = np.random.RandomState(ctx["seed"] + 2).randint(
        0, cfg["fit_classes"], n
    )
    frame = ctx["spark"].createDataFrame(
        [(p, int(y)) for p, y in zip(ctx["paths"][:n], labels)],
        ["filePath", "label"],
    )
    spans = []
    sink = spans.append
    tracer.enable(sink)
    try:
        fitted = KerasImageFileEstimator(
            inputCol="filePath", outputCol="pred", labelCol="label",
            imageLoader=_fit_loader(cfg["fit_hw"]), modelFile=FIT_MODEL,
            kerasOptimizer="sgd", kerasLoss="sparse_categorical_crossentropy",
            kerasFitParams={
                "epochs": steps, "batch_size": n, "streaming": True,
                "learning_rate": cfg["fit_lr"], "seed": ctx["seed"],
            },
        ).fit(frame)
    finally:
        tracer.disable()
        tracer.remove_sink(sink)
    (fit_span,) = [s for s in spans if s["name"] == "estimator.fit"]
    losses = [
        e["loss"] for e in fit_span["events"] if e["name"] == "epoch"
    ]
    step_ms = [
        s["duration_ms"] for s in spans if s["name"] == "estimator.step"
    ]
    if len(losses) != steps:
        raise AssertionError(f"fit: {steps} steps, losses {losses}")
    return losses, labels, fitted.getModelFile(), step_ms


def _weights(path_or_model):
    import keras
    import numpy as np

    model = path_or_model
    if isinstance(model, str):
        model = keras.saving.load_model(model, compile=False)
    return (
        [np.asarray(v) for v in model.trainable_variables],
        [np.asarray(v) for v in model.non_trainable_variables],
    )


def _l2(arrays) -> float:
    import numpy as np

    return float(np.sqrt(sum(float(np.sum(np.square(a))) for a in arrays)))


def phase_fit(ctx):
    import numpy as np

    cfg = ctx["cfg"]
    model = _build_fit_model(cfg, ctx["seed"])
    before, _ = _weights(model)
    losses, labels, tuned, step_ms = _fit(ctx)
    after, _ = _weights(tuned)
    moved = _l2([a - b for a, b in zip(after, before)])
    # first step's loss: float32 CPU forward of the same model on the same
    # batch (training mode, as the step runs it; a mean over the batch, so
    # the step's permutation of the rows does not matter)
    with ctx["meter"].reference():
        load = _fit_loader(cfg["fit_hw"])
        x = np.stack([load(p) for p in ctx["paths"][:cfg["fit_batch"]]])
        probs = _keras_on_cpu(model, x, training=True, chunk=len(x))
        picked = np.clip(probs[np.arange(len(x)), labels], 1e-7, 1 - 1e-7)
        ref_loss = float(-np.log(picked).mean())
    err = abs(losses[0] - ref_loss) / abs(ref_loss)
    checks = Checks()
    checks.within("fit", err, TOL_LOSS)
    checks.require(
        bool(np.isfinite(losses).all()), f"fit: losses per step {losses}"
    )
    checks.require(moved > 0.0, "fit: the trainable parameters did not move")
    return {
        "steps": FIT_STEPS, "batch": cfg["fit_batch"], "losses": losses,
        "reference_first_loss": ref_loss, "err": err,
        "tol": TOL_LOSS, "err_is": "|loss0-ref|/|ref|",
        "update_l2": moved, "step_ms": step_ms,
        "failures": checks.failures,
    }


# ---------------------------------------------------------------------------
# the four-chip phases (--chips 4): what exists only across chips
# ---------------------------------------------------------------------------


def _shard_by_shard(ctx, model, labels, n_dev: int, starts):
    """The reference of :func:`phase_dp_fit`: the package's own train step
    on a ONE-device mesh, run once per shard from the same state, losses
    and resulting states averaged — exact for plain SGD, which is linear in
    the gradient.  ``starts[s]`` is the (trainable, non_trainable) state
    step ``s`` starts from; returns each step's loss and resulting state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sparkdl_tpu.estimators.losses import (
        get_optimizer,
        get_per_sample_loss_fn,
    )
    from sparkdl_tpu.parallel.keras_train import (
        KerasTrainState,
        make_keras_train_step,
    )
    from sparkdl_tpu.parallel.trainer import make_mesh

    cfg = ctx["cfg"]
    n = cfg["fit_batch"]
    per = n // n_dev
    load = _fit_loader(cfg["fit_hw"])
    x = np.stack([load(p) for p in ctx["paths"][:n]])
    y = labels.astype(np.int32)
    tx = get_optimizer("sgd", cfg["fit_lr"])
    step = make_keras_train_step(
        model, get_per_sample_loss_fn("sparse_categorical_crossentropy"),
        tx, make_mesh(devices=jax.devices()[:1]), weighted=True,
    )
    # the estimator's documented batch order: one permutation per epoch
    # from RandomState(seed * 7919 + process_index), rows split contiguously
    rng = np.random.RandomState((ctx["seed"] * 7919) % 2**32)
    losses, states = [], []
    for trainable, non_trainable in starts:
        order = rng.permutation(n)
        outs = []
        for k in range(n_dev):
            idx = order[k * per:(k + 1) * per]
            # fresh device copies: the step donates its state
            tr = [jnp.array(a) for a in trainable]
            new, loss = step(
                KerasTrainState(
                    tr, [jnp.array(a) for a in non_trainable],
                    tx.init(tr), jnp.zeros((), jnp.int32),
                ),
                {"x": x[idx], "y": y[idx], "w": np.ones(per, np.float32)},
            )
            outs.append((
                [np.asarray(a) for a in new.trainable],
                [np.asarray(a) for a in new.non_trainable],
                float(loss),
            ))
        losses.append(float(np.mean([o[2] for o in outs])))
        states.append((
            [np.mean(leaves, axis=0) for leaves in zip(*[o[0] for o in outs])],
            [np.mean(leaves, axis=0) for leaves in zip(*[o[1] for o in outs])],
        ))
    return losses, states


def phase_dp_fit(ctx):
    """``fit`` data-parallel over every chip against the same steps on a
    one-device mesh.  ResNet50 normalises with batch statistics, which DP
    computes per shard (keras_train's documented non-sync-BN semantics), so
    "the same steps" are :func:`_shard_by_shard`.  A deep batch-normalised
    net amplifies a last-bit difference by orders of magnitude per step, so
    every step is compared from the state the DP run itself was in before
    it: fits of 1, 2 and 3 steps give the states in between (each retraces
    the one before, which the repeated losses show).  Each step's loss,
    update and batch-norm statistics are then held to float tolerance, and
    an update N times too large cannot hide."""
    import jax
    import numpy as np

    cfg = ctx["cfg"]
    n_dev = len(jax.devices())
    model = _build_fit_model(cfg, ctx["seed"])
    allocs0 = _allocs()
    fits = [_fit(ctx, steps=s) for s in range(1, FIT_STEPS + 1)]
    allocs1 = _allocs()
    losses, labels, _, step_ms = fits[-1]
    states = [_weights(model)] + [_weights(tuned) for _, _, tuned, _ in fits]
    with ctx["meter"].reference():
        ref_losses, ref_states = _shard_by_shard(
            ctx, model, labels, n_dev, states[:-1]
        )

    checks = Checks()
    steps = []
    for s in range(FIT_STEPS):
        (start_tr, _), (dp_tr, dp_nt) = states[s], states[s + 1]
        ref_tr, ref_nt = ref_states[s]
        ref_update = [a - b for a, b in zip(ref_tr, start_tr)]
        dp_update = [a - b for a, b in zip(dp_tr, start_tr)]
        found = {
            "loss": losses[s], "reference_loss": ref_losses[s],
            "loss_err": abs(losses[s] - ref_losses[s]) / abs(ref_losses[s]),
            "update_err": _l2(
                [a - b for a, b in zip(dp_update, ref_update)]
            ) / _l2(ref_update),
            "update_ratio": _l2(dp_update) / _l2(ref_update),
            "stats_err": _l2(
                [a - b for a, b in zip(dp_nt, ref_nt)]
            ) / _l2(ref_nt),
        }
        steps.append(found)
        checks.within(f"dp_fit step {s + 1}: loss", found["loss_err"],
                      TOL_DP_LOSS)
        checks.within(f"dp_fit step {s + 1}: update", found["update_err"],
                      TOL_DP_UPDATE)
        checks.within(f"dp_fit step {s + 1}: batch-norm statistics",
                      found["stats_err"], TOL_DP_UPDATE)
    repeated = [f[0] for f in fits]
    checks.require(
        all(
            math.isclose(a, b, rel_tol=1e-5)
            for run in repeated for a, b in zip(run, losses)
        ),
        f"dp_fit: the fits do not retrace each other, losses {repeated}",
    )
    checks.require(bool(np.isfinite(losses).all()), f"dp_fit: losses {losses}")
    return {
        "devices": n_dev, "batch": cfg["fit_batch"], "steps": steps,
        "loss_tol": TOL_DP_LOSS, "update_tol": TOL_DP_UPDATE,
        "err_is": "|dp-ref|/|ref| per step from the DP run's own state "
                  "(update, stats: L2)",
        "losses_of_each_fit": repeated, "step_ms": step_ms,
        "allocs_per_device": _alloc_delta(allocs0, allocs1),
        "failures": checks.failures,
    }


def phase_dp_transform(ctx):
    """``DeepImageFeaturizer.transform`` over the ``data`` mesh against the
    float32 reference; every chip must have allocated while it ran."""
    import jax

    from sparkdl_tpu.transformers.utils import data_parallel_mesh

    mesh = data_parallel_mesh()
    n_dev = len(jax.devices())
    if mesh is None or mesh.devices.size != n_dev:
        raise AssertionError(f"no data mesh over {n_dev} devices: {mesh}")
    record = _featurize(ctx, "dp_transform")
    if not all(a is None or a > 0 for a in record["allocs_per_device"]):
        record["failures"].append(
            "dp_transform: a chip held nothing: allocations per device "
            f"{record['allocs_per_device']}"
        )
    record["mesh"] = [str(d) for d in mesh.devices.flat]
    return record


def phase_fleet_reference(ctx):
    """Nothing runs on the chip here: the four-chip fleet's requests and
    the answers Keras gives on the CPU backend, left for the parent."""
    with ctx["meter"].reference():
        ctx["udf_model"] = _build_udf_model(ctx["cfg"], ctx["seed"])
        _udf_reference(ctx)
    return {"requests": ctx["cfg"]["fleet_requests"]}


def child_main(args) -> int:
    """The one process that holds the chip(s) for the in-process phases."""
    os.environ.setdefault("KERAS_BACKEND", "jax")
    import jax

    from sparkdl_tpu.engine.cache import compile_cache_root, enable_jax_cache

    enable_jax_cache()
    first = jax.devices()[0]
    device = {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices()),
    }
    with open(DEVICE_FILE, "w") as fh:
        json.dump(device, fh)
    if not args.rehearse and first.platform != "tpu":
        print(
            f"chip_smoke: JAX found {device}, not a TPU; refusing to run "
            "(--rehearse runs tiny shapes on whatever there is)",
            file=sys.stderr,
        )
        return 1
    if device["count"] != args.chips and not args.rehearse:
        print(
            f"chip_smoke: --chips {args.chips} but JAX holds "
            f"{device['count']} device(s)", file=sys.stderr,
        )
        return 1

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.sql.session import TPUSession

    cfg = TINY if args.rehearse else FULL
    spark = TPUSession.builder.master("local[*]").appName("smoke").getOrCreate()
    paths = _make_images(cfg, args.seed)
    ctx = {
        "cfg": cfg, "seed": args.seed, "spark": spark, "paths": paths,
        "df": imageIO.readImages(IMAGES, spark, numPartitions=4),
        "meter": _Meter(),
    }
    finish({
        "phase": "setup", "device": device,
        "rehearse": args.rehearse, "images": len(paths),
        "cache_root": compile_cache_root(),
        "jax": jax.__version__,
    })
    if args.chips == 4:
        phases = [
            ("dp_fit", phase_dp_fit),
            ("dp_transform", phase_dp_transform),
            ("fleet_reference", phase_fleet_reference),
        ]
    else:
        phases = [
            ("featurize", phase_featurize),
            ("udf", phase_udf),
            ("serve", phase_serve),
            ("fit", phase_fit),
        ]
    run_phases(phases, ctx)
    return 0


# ---------------------------------------------------------------------------
# fleet — runs in the parent, which never initialises a JAX backend
# ---------------------------------------------------------------------------


def serve_factory():
    """Replica factory (``chip_smoke:serve_factory``): hosts the ``serve``
    phase's endpoint from the same model file — the same fingerprint — so
    its warm-up finds the programs that phase compiled.  Also leaves the
    evidence of which chip this process holds."""
    import jax

    from sparkdl_tpu import registerKerasImageUDF
    from sparkdl_tpu.serving import ModelServer, ServingConfig
    from sparkdl_tpu.sql.session import TPUSession

    with open(SERVE_CONFIG) as fh:
        max_batch = json.load(fh)["max_batch"]
    session = TPUSession.builder.master("local[*]").getOrCreate()
    registerKerasImageUDF(UDF_NAME, UDF_MODEL, session=session)
    server = ModelServer.from_registered_udf(
        UDF_NAME, session=session,
        config=ServingConfig(max_batch=max_batch),
    )
    fds = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith("/dev/vfio/") or target.startswith("/dev/accel"):
            fds.add(target)
    chip = os.environ.get("TPU_VISIBLE_CHIPS")
    with open(os.path.join(WORK, f"replica-chip{chip}.json"), "w") as fh:
        json.dump({
            "pid": os.getpid(), "TPU_VISIBLE_CHIPS": chip,
            "devices": [str(d) for d in jax.devices()],
            "device_files": sorted(fds),
        }, fh)
    return server


def _get_json(url: str):
    import urllib.request

    with urllib.request.urlopen(url, timeout=10.0) as resp:
        return json.loads(resp.read().decode())


def phase_fleet(args) -> dict:
    import numpy as np

    from sparkdl_tpu.serving import transport
    from sparkdl_tpu.serving.replica import ReplicaSpec
    from sparkdl_tpu.serving.supervisor import ReplicaSupervisor

    data = np.load(FLEET_REQUESTS)
    x, ref = data["x"], data["ref"]
    n_replicas = args.chips
    spec = ReplicaSpec(
        factory="chip_smoke:serve_factory", pythonpath=(REPO,),
        request_timeout_s=120.0,
    )
    t0 = time.perf_counter()
    sup = ReplicaSupervisor(spec, replicas=n_replicas)
    served = {}
    try:
        sup.start()
        spawn_s = time.perf_counter() - t0
        handles = sup.handles()
        live = [h for h in handles if h.state == "live"]
        if len(live) != n_replicas:
            raise AssertionError(
                f"fleet: {len(live)}/{n_replicas} replicas live: "
                f"{[h.describe() for h in handles]}"
            )
        got = None
        # least-inflight placement: only concurrent traffic spreads, and
        # every replica has to have answered at least once
        for _ in range(5):
            with ThreadPoolExecutor(max_workers=len(x)) as pool:
                got = np.stack(list(pool.map(
                    lambda v: sup.router.route(
                        v, model_id=UDF_NAME, timeout_s=120.0
                    ),
                    x,
                )))
            health = {h.name: _get_json(h.obs_url() + "/healthz") for h in live}
            served = {
                name: st["metrics"].get("serving.requests", 0)
                for name, st in health.items()
            }
            if all(v >= 1 for v in served.values()):
                break
        replicas = []
        for h in live:
            st = health[h.name]
            replicas.append({
                "name": h.name, "chip": h.chip, "pid": h.proc.pid,
                "served": served[h.name],
                "compiles": st["metrics"].get("serving.compiles", 0),
                "cache_loads": st["metrics"].get("serving.cache_load", 0),
                "programs": {
                    f"{k['model']}:{k['bucket']}": k["source"]
                    for k in st["program_cache"]["keys"]
                },
                "warmup_s": round(sum(
                    b["seconds"]
                    for b in h.warmup.get("sources", {}).get(UDF_NAME, {})
                    .values()
                ), 3),
            })
    finally:
        sup.close()
    exits = {h.name: h.last_exit for h in sup.handles()}
    for rep in replicas:
        path = os.path.join(WORK, f"replica-chip{rep['chip']}.json")
        with open(path) as fh:
            rep["holds"] = json.load(fh)
    from jax._src import xla_bridge

    checks = Checks()
    checked = _check_probs(checks, "fleet", got, ref)
    checks.require(
        all(v >= 1 for v in served.values()),
        f"fleet: a replica served nothing: {served}",
    )
    checks.require(
        all(code == 0 for code in exits.values()),
        f"fleet: replica exit codes {exits}",
    )
    segments = transport.active_segments()
    checks.require(not segments, f"fleet: shm segments left: {segments}")
    backend = xla_bridge.backends_are_initialized()
    checks.require(
        not backend, "fleet: the supervisor's process initialised a backend"
    )
    if args.chips == 1:
        # the serve phase compiled this endpoint a moment ago, in a process
        # that has exited: the replica's warm-up must come from the cache
        (rep,) = replicas
        checks.require(
            not rep["compiles"]
            and set(rep["programs"].values()) == {"disk"},
            "fleet: the replica recompiled the endpoint the serve phase had "
            f"compiled: {rep['programs']} ({rep['compiles']} compiles)",
        )
    held = [tuple(r["holds"]["device_files"]) for r in replicas]
    checks.require(
        not any(held) or len(set(held)) == len(held),
        f"fleet: two replicas hold the same chip: {held}",
    )
    return {
        "replicas": replicas, "requests": len(x), **checked,
        "exit_codes": exits, "spawn_s": round(spawn_s, 3),
        "shm_segments_left": segments,
        "parent_backend_initialised": backend,
        "failures": checks.failures,
    }


def _with_cpu_devices(n: int) -> str:
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        os.environ.get("XLA_FLAGS", ""),
    )
    return f"{flags} --xla_force_host_platform_device_count={n}".strip()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny shapes on whatever platform JAX finds (no TPU needed)",
    )
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child:
        return child_main(args)

    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    device = None
    ok = False
    try:
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--seed", str(args.seed), "--chips", str(args.chips)]
        if args.rehearse:
            cmd.append("--rehearse")
        env = dict(os.environ)
        if args.rehearse:
            # where the platform is the CPU: as many virtual devices as
            # chips for the in-process child, and one for each replica, as
            # a replica restricted to its chip sees
            env["XLA_FLAGS"] = _with_cpu_devices(args.chips)
            os.environ["XLA_FLAGS"] = _with_cpu_devices(1)
        child = subprocess.run(cmd, cwd=REPO, env=env)
        if os.path.exists(DEVICE_FILE):
            with open(DEVICE_FILE) as fh:
                device = json.load(fh)
        if child.returncode != 0:
            print(f"chip_smoke: the in-process phases failed "
                  f"(exit code {child.returncode})", file=sys.stderr)
            return 1
        # the child is gone, and with it its hold on the chip(s)
        start = time.perf_counter()
        record = phase_fleet(args)
        finish({
            "phase": "fleet", **record,
            "seconds": {"total": round(time.perf_counter() - start, 3)},
        })
        ok = True
        return 0
    finally:
        print(json.dumps({"ok": ok, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
