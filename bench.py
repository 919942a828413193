"""Headline benchmark: DeepImageFeaturizer (InceptionV3) images/sec/chip.

Measures sustained on-chip throughput of the flagship featurizer's fused
device program (uint8 decode -> BGR flip -> preprocess -> InceptionV3 ->
2048-d features, bf16 compute) — the hot loop of the reference's
``DeepImageFeaturizer.transform`` (SURVEY.md §3.1) rebuilt for TPU.

Methodology (shared harness — ``sparkdl_tpu.utils.benchlib``): K model
applications inside one jitted ``lax.scan`` over distinct batches generated
on the device, scalar reduction fetched to host.  This amortizes the
per-call host round trip and forces real execution of every batch.  The
MFU field uses an empirical probe of cost_analysis's While-body counting
convention (benchlib), not a plausibility guess.

Baseline: the reference publishes no numbers; the target is ">= V100
images/sec/chip".  ``V100_IMAGES_PER_SEC`` uses 1000 img/s — the commonly
cited TF-fp32 InceptionV3 V100 batch-inference figure — so ``vs_baseline =
measured / 1000``.

Prints exactly one JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N,
"device": {...}, "ok": true}``.  It measures in the process that holds the
chip and has no CPU mode: where JAX finds no accelerator it prints the same
shape with ``value``/``vs_baseline``/``mfu`` null plus ``"ok": false``,
``"error_class"`` and ``"error"``, and exits with code 2.

``--cold-start`` measures the execution engine's executable store instead
of throughput: two fresh interpreter processes, one after the other, share
one store directory (``coldstart/`` under the compile-cache root, emptied
first) and each times its FIRST featurizer batch (InceptionV3, batch 1 —
the latency-critical serving shape).  The first process compiles; the
second loads the serialized executable.  JAX's own persistent cache is off
in both, so "cold" is a real compile.  One JSON line with ``cold_s`` /
``warm_s`` / ``speedup`` plus the resolve-only split (``compile_s`` vs
``cache_load_s``).  This process stays off JAX: a chip belongs to one
process at a time, and the two children need it in turn.
"""

import faulthandler
import json
import os
import sys

V100_IMAGES_PER_SEC = 1000.0
BATCH = 512
SCAN_LEN = 24  # deeper scan -> the host-fetch round trip amortizes; the
# input stack is generated ON DEVICE (benchlib), so depth costs no staging
REPEATS = 3

#: the per-process probe --cold-start runs twice against one shared
#: cache dir.  Batch 1 (not BATCH): cold start is a latency story —
#: "first request after restart" — and the resolve cost is
#: shape-independent anyway.  Weights are the deterministic "random"
#: init, so the fingerprint is durable without an imagenet download.
_COLD_START_CHILD = """
import json, os, sys, time, warnings

warnings.filterwarnings("ignore")
import numpy as np
import jax
import jax.numpy as jnp

if jax.devices()[0].platform == "cpu":
    sys.exit("no accelerator: jax.devices() holds only CPUs")
jax.config.update("jax_enable_compilation_cache", False)

from sparkdl_tpu.engine import ExecutionEngine
from sparkdl_tpu.models import get_keras_application_model
from sparkdl_tpu.transformers.named_image import _resolve_variables

entry = get_keras_application_model("InceptionV3")
module = entry.make_module(dtype=jnp.bfloat16)
variables = _resolve_variables("InceptionV3", "random")
preprocess = entry.preprocess


def forward(x):
    x = preprocess(x.astype(np.float32))
    out = module.apply(variables, x.astype(jnp.bfloat16),
                       features_only=True)
    return out.reshape(out.shape[0], -1).astype(jnp.float32)


h, w = entry.input_size
x = np.random.RandomState(0).rand(1, h, w, 3).astype(np.float32)
engine = ExecutionEngine()
t0 = time.perf_counter()
handle = engine.program(
    forward, (x,),
    fingerprint="bench:coldstart:InceptionV3:random:bf16:v1",
    donate=True, name="bench_coldstart",
)
np.asarray(handle(x))
print(json.dumps({
    "source": handle.source,
    "first_batch_s": round(time.perf_counter() - t0, 4),
    "resolve_s": round(handle.seconds, 4),
    "device": {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    },
}))
"""


#: repeating all-thread stack dump interval while the bench runs: a run
#: that stops making progress narrates where it is stuck to stderr
STALL_DUMP_S = float(os.environ.get("SPARKDL_BENCH_STALL_S", "240") or 240)


def _arm_stall_dump() -> None:
    """faulthandler: native stacks on hard faults, plus a REPEATING
    all-thread dump every STALL_DUMP_S so a silent hang leaves a
    narrative on stderr instead of nothing."""
    faulthandler.enable()
    faulthandler.dump_traceback_later(STALL_DUMP_S, repeat=True)


def _cold_start() -> int:
    import shutil
    import subprocess

    from sparkdl_tpu.engine.cache import compile_cache_root

    metric = (
        "DeepImageFeaturizer(InceptionV3) cold-start first-batch latency"
    )
    cache_dir = os.path.join(compile_cache_root(), "coldstart")
    shutil.rmtree(cache_dir, ignore_errors=True)
    runs = []
    for _phase in ("cleared", "warmed"):
        proc = subprocess.run(
            [sys.executable, "-c", _COLD_START_CHILD],
            capture_output=True, text=True, timeout=1800,
            env={**os.environ, "SPARKDL_COMPILE_CACHE": cache_dir},
        )
        if proc.returncode != 0:
            print(json.dumps({
                "metric": metric, "value": None, "unit": "seconds",
                "ok": False, "error_class": "ChildFailed",
                "error": proc.stderr.strip()[-500:],
            }))
            return 2
        runs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    result = {
        "metric": metric,
        "value": round(warm["first_batch_s"], 3),
        "unit": "seconds",
        "cold_s": round(cold["first_batch_s"], 3),
        "warm_s": round(warm["first_batch_s"], 3),
        "speedup": round(
            cold["first_batch_s"] / max(warm["first_batch_s"], 1e-9), 2
        ),
        "compile_s": cold["resolve_s"],
        "cache_load_s": warm["resolve_s"],
        "cold_source": cold["source"],
        "warm_source": warm["source"],
        "device": warm["device"],
        "ok": cold["source"] == "compile" and warm["source"] == "disk",
    }
    print(json.dumps(result))
    return 0 if result["ok"] else 1


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--trace-out", default=None, metavar="PATH",
        help="append a JSONL span trace of the run to PATH (obs "
        "subsystem) alongside the one-line JSON result",
    )
    ap.add_argument(
        "--cold-start", action="store_true",
        help="measure first-batch latency with an emptied vs warmed "
        "executable store (two fresh processes in turn) instead of "
        "throughput",
    )
    args = ap.parse_args()

    _arm_stall_dump()

    if args.cold_start:
        return _cold_start()

    metric = "DeepImageFeaturizer(InceptionV3) bf16 batch inference throughput"
    from sparkdl_tpu.engine.cache import enable_jax_cache
    from sparkdl_tpu.utils.benchlib import (
        accelerator_or_refuse,
        measure_featurizer,
    )

    device = accelerator_or_refuse(metric, vs_baseline=None, mfu=None)
    if device is None:
        return 2
    enable_jax_cache()

    from sparkdl_tpu.obs import JsonlTraceSink, tracer

    sink = None
    if args.trace_out:
        sink = JsonlTraceSink(path=args.trace_out)
        tracer.enable(sink)
    with tracer.span(
        "bench.featurizer", batch=BATCH, scan_len=SCAN_LEN, repeats=REPEATS
    ):
        out = measure_featurizer("InceptionV3", BATCH, SCAN_LEN, REPEATS)
    if sink is not None:
        sink.flush()
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(out["images_per_sec"], 1),
                "unit": "images/sec/chip",
                "vs_baseline": round(
                    out["images_per_sec"] / V100_IMAGES_PER_SEC, 3
                ),
                "mfu": round(out["mfu"], 4) if out["mfu"] is not None
                else None,
                "batch": BATCH,
                "scan": SCAN_LEN,
                "device": device,
                "ok": True,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
