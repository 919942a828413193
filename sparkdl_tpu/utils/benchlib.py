"""Shared featurizer-benchmark harness (bench.py + benchmarks/bench_zoo.py).

One implementation of the measurement methodology so the headline and the
zoo numbers cannot drift: the fused uint8 -> BGR-fold/flip -> preprocess ->
CNN forward, K applications inside one jitted ``lax.scan`` over distinct
batches generated on the device, with a scalar fetch (per-call host timing
of an asynchronous dispatch is wrong in both directions), plus MFU from
XLA's cost analysis.

Every script that measures calls :func:`accelerator_or_refuse` first, in
the process that measures: a number under a device unit comes from a
device, and a host that has none gets a refusal and exit code 2, never a
smaller workload on the CPU.

The While-body FLOP-counting convention (cost_analysis may count a scan
body once or trip-count times depending on XLA version) is determined
empirically ONCE per process by a tiny known-FLOPs scan probe — a
guess-by-plausibility heuristic would silently mis-scale models whose true
MFU is below 1/scan.
"""

from __future__ import annotations

import json
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from sparkdl_tpu.utils.metrics import compiled_flops, mfu

_SCAN_COUNTS_BODY_ONCE: Optional[bool] = None


def accelerator_or_refuse(
    metric: str, unit: str = "images/sec/chip", **null_fields
) -> Optional[dict]:
    """The device this process measures on, as every result names it
    (``{"platform", "kind", "count"}``) — or, where JAX found only CPUs,
    None after printing the canonical refusal record
    ``{"metric", "value": null, "unit", "ok": false, "error_class",
    "error", "device"}`` (plus ``null_fields``), so the script can exit 2.
    One implementation so benchmark scripts cannot drift in how they
    refuse to measure without a chip."""
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "cpu":
        return device
    print(
        json.dumps(
            {
                "metric": metric,
                "value": None,
                "unit": unit,
                **null_fields,
                "ok": False,
                "error_class": "NoAccelerator",
                "error": "jax.devices() holds only CPUs; this benchmark "
                "measures on the chip and has no CPU mode",
                "device": device,
            }
        ),
        flush=True,
    )
    return None


def scan_body_counted_once() -> Optional[bool]:
    """True when ``cost_analysis`` on a compiled ``lax.scan`` program counts
    the body's FLOPs once, False when it multiplies by trip count, None
    when the backend exposes no cost analysis.  Probed once per process
    with a known-FLOPs matmul scan (length 8, 128³: one body = 4.2 MFLOP,
    trip-multiplied = 33.6 MFLOP — unambiguous either way)."""
    global _SCAN_COUNTS_BODY_ONCE
    if _SCAN_COUNTS_BODY_ONCE is not None:
        return _SCAN_COUNTS_BODY_ONCE
    length = 8
    body_flops = 2 * 128**3

    def run(c, w):
        def body(carry, _):
            return (carry @ w).astype(carry.dtype), None

        out, _ = jax.lax.scan(body, c, None, length=length)
        return out.sum()

    c = jnp.zeros((128, 128), jnp.float32)
    flops = compiled_flops(jax.jit(run).lower(c, c).compile())
    if not flops:
        return None
    # attribute non-body overhead (the sum) generously; the two readings
    # differ 8x so a 2x threshold cannot misclassify
    _SCAN_COUNTS_BODY_ONCE = flops < 2 * body_flops
    return _SCAN_COUNTS_BODY_ONCE


def time_compiled(compiled, args, repeats: int = 3) -> float:
    """Min-of-``repeats`` wall time of one compiled call, fetch-forced —
    the scan-amortized methodology's timing primitive (shared by the
    experiment scripts so a methodology change cannot drift between
    them and the headline harness)."""
    np.asarray(compiled(*args))  # warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(compiled(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def fill_variables(module, example, value: float = 0.01):
    """Deterministic nonzero variables for throughput probes (values do
    not change the FLOP rate) via ``eval_shape`` — no real init pass."""
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), example)
    return jax.tree_util.tree_map(
        lambda l: jnp.full(l.shape, value, l.dtype), shapes
    )


def device_random_stack(shape, dtype, scan: int, *, as_uint8=False, seed=0):
    """A ``(scan, *shape)`` stack of DISTINCT random batches generated
    ON DEVICE by jitted PRNG (the anti-caching requirement; nothing is
    staged from the host)."""
    device = jax.devices()[0]

    def gen(key):
        keys = jax.random.split(key, scan)

        def body(_, k):
            x = jax.random.uniform(k, shape)
            if as_uint8:
                return None, (x * 255).astype(jnp.uint8)
            return None, x.astype(dtype)

        _, out = jax.lax.scan(body, None, keys)
        return out

    with jax.default_device(device):
        stack = jax.jit(gen)(jax.random.PRNGKey(seed))
        stack.block_until_ready()
    return stack


def summarize_samples(vals) -> dict:
    """``{"samples": [...], "median": m, "iqr": [q1, q3]}`` — the one
    summary shape every benchmark reports (single definition so the
    quantile method cannot drift between benchmarks)."""
    import statistics

    vals = [float(v) for v in vals]
    if len(vals) >= 2:
        q = statistics.quantiles(vals, n=4, method="inclusive")
        q1, q3 = q[0], q[2]
    else:
        q1 = q3 = vals[0]
    return {
        # samples stay unrounded: downstream math (e.g. the marginal-cost
        # differences in bench_native_marginal) must not compound display
        # quantization
        "samples": vals,
        "median": round(statistics.median(vals), 3),
        "iqr": [round(q1, 3), round(q3, 3)],
    }


def paired_trials(measurers, k: int = 5) -> dict:
    """Interleaved repeated trials — the measurement protocol that
    survives slow drift of the rig (single-shot host-clock numbers can
    swing severalfold run-to-run, which makes regressions invisible and
    wins unprovable).

    ``measurers`` is an ordered ``{label: thunk}``; each round runs every
    thunk once (A/B/A/B...), so slow rig drift hits all labels equally
    within a round.  Returns per label::

        {"samples": [...], "median": m, "iqr": [q1, q3]}

    Medians of interleaved rounds are robust to exactly the drift that
    makes single-shot comparisons meaningless; the IQR is the honesty
    bar a reader needs to judge any claimed difference.
    """
    samples: dict = {name: [] for name in measurers}
    for _ in range(k):
        for name, fn in measurers.items():
            samples[name].append(float(fn()))
    return {name: summarize_samples(vals) for name, vals in samples.items()}


def measure_featurizer(
    model_name: str, batch: int, scan: int, repeats: int = 3,
    trials: int = 1,
) -> dict:
    """Sustained on-chip throughput + MFU of ``model_name``'s fused
    featurize program.

    ``trials`` independent samples share ONE compile (each trial is
    min-of-``repeats`` timed runs — re-compiling per sample would buy no
    statistical independence since compile time is excluded anyway).
    Returns ``{images_per_sec, mfu, input_hw, samples, mfu_samples}``;
    ``images_per_sec``/``mfu`` are the first trial (back-compatible for
    ``trials=1`` callers like bench.py)."""
    from sparkdl_tpu.models import get_keras_application_model
    from sparkdl_tpu.models.registry import fold_bgr_flip_into_stem

    entry = get_keras_application_model(model_name)
    module = entry.make_module(dtype=jnp.bfloat16)
    h, w = entry.input_size
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jnp.zeros((1, h, w, 3), jnp.float32),
    )
    # deterministic nonzero weights; values don't change the FLOP rate
    variables = jax.tree_util.tree_map(
        lambda l: jnp.full(l.shape, 0.01, l.dtype), shapes
    )
    # fold the BGR flip into the stem conv where preprocessing is
    # channel-symmetric (drops a pure-bandwidth rev op; the mode gate
    # lives inside the helper)
    folded = fold_bgr_flip_into_stem(variables, entry.preprocess_mode)
    flip_in_program = folded is None
    if folded is not None:
        variables = folded
    device = jax.devices()[0]
    variables = jax.device_put(variables, device)

    # the input stack is GENERATED on device (jitted PRNG, one scan slot
    # at a time to bound the f32 intermediate) rather than staged from
    # host, so scan depth costs no transfer.  Batches stay distinct
    # across slots (the anti-caching requirement).
    def gen_stack(key):
        keys = jax.random.split(key, scan)

        def body(_, k):
            xb = (
                jax.random.uniform(k, (batch, h, w, 3)) * 255
            ).astype(jnp.uint8)
            return None, xb

        _, out = jax.lax.scan(body, None, keys)
        return out

    with jax.default_device(device):
        stack = jax.jit(gen_stack)(jax.random.PRNGKey(0))
        stack.block_until_ready()

    def forward(v, x):
        if flip_in_program:
            x = x[..., ::-1]  # stored BGR -> RGB
        x = entry.preprocess(x.astype(jnp.bfloat16))
        return module.apply(
            v, x.astype(jnp.bfloat16), features_only=True
        ).astype(jnp.float32)

    def run_many(v, stack):
        def body(carry, xb):
            return carry + forward(v, xb).sum(), None

        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), stack)
        return acc

    compiled = jax.jit(run_many).lower(variables, stack).compile()
    np.asarray(compiled(variables, stack))  # compile + warm

    flops = compiled_flops(compiled)
    per_call = None
    if flops:
        once = scan_body_counted_once()
        if once is not None:
            per_call = flops * scan if once else flops

    rates, mfus = [], []
    for _ in range(max(1, trials)):
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            np.asarray(compiled(variables, stack))  # fetch forces the chain
            times.append(time.perf_counter() - t0)
        t = min(times)
        rates.append(scan * batch / t)
        mfus.append(mfu(per_call, t, device) if per_call else None)

    return {
        "images_per_sec": rates[0],
        "mfu": mfus[0],
        "input_hw": (h, w),
        "samples": rates,
        "mfu_samples": mfus,
    }
