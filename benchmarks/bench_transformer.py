"""BASELINE config #3: ``KerasImageFileTransformer`` batch-inference throughput.

The distinctive path vs ``bench.py``: the model arrives as a *saved Keras
file* and runs through ``XlaFunction.from_keras`` — the transformer's
``load_keras_function`` product (the reference's ``.h5`` -> frozen-graph
flow, SURVEY.md §2 "KerasImageFileTransformer") — not a hand-built Flax
module.  Measures the sustained on-chip rate of that jitted program with
scan-amortized timing (see bench.py for why: per-call host timing of an
asynchronous dispatch is wrong in both directions).

Prints one JSON line; same V100 reference point as bench.py.  Measures on
the chip only: without an accelerator it prints a refusal and exits 2.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("KERAS_BACKEND", "jax")

V100_IMAGES_PER_SEC = 1000.0
BATCH = 256
SCAN_LEN = 4
REPEATS = 3
IMAGE = 299


def main():
    from sparkdl_tpu.utils.benchlib import accelerator_or_refuse

    if accelerator_or_refuse(
        "KerasImageFileTransformer(InceptionV3 .keras) bf16 batch "
        "inference throughput"
    ) is None:
        return 2

    import jax
    import jax.numpy as jnp
    import keras

    from sparkdl_tpu.transformers.utils import load_keras_function

    keras.utils.set_random_seed(0)
    model = keras.applications.InceptionV3(
        weights=None, include_top=False, pooling="avg",
        input_shape=(IMAGE, IMAGE, 3),
    )
    path = os.path.join(tempfile.mkdtemp(prefix="bench_kift_"), "m.keras")
    model.save(path)

    # the transformer's computeDtype="bfloat16" path: mixed_bfloat16
    # policy at load (f32 variables, bf16 compute) — saved models default
    # to f32 compute, which halves MXU throughput
    fn = load_keras_function(path, compute_dtype="bfloat16")
    device = jax.devices()[0]
    params = jax.device_put(fn.params, device)
    inner = fn._jitted()

    rng = np.random.RandomState(0)
    stack = jax.device_put(
        jnp.asarray(
            rng.rand(SCAN_LEN, BATCH, IMAGE, IMAGE, 3).astype(np.float32)
        ),
        device,
    )

    @jax.jit
    def run_many(p, stack):
        def body(carry, xb):
            return carry + inner(p, xb)[0].sum(), None

        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), stack)
        return acc

    np.asarray(run_many(params, stack))  # compile + warm
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        np.asarray(run_many(params, stack))
        times.append(time.perf_counter() - t0)

    images_per_sec = SCAN_LEN * BATCH / min(times)
    print(
        json.dumps(
            {
                "metric": "KerasImageFileTransformer(InceptionV3 .keras) "
                "bf16 batch inference throughput",
                "value": round(images_per_sec, 1),
                "unit": "images/sec/chip",
                "vs_baseline": round(images_per_sec / V100_IMAGES_PER_SEC, 3),
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
