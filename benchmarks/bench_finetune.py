"""BASELINE config #2: ``KerasImageFileEstimator`` fine-tune step time.

Measures the wall-time of one data-parallel training step of the estimator's
real engine (:func:`sparkdl_tpu.parallel.keras_train.make_keras_train_step`)
on a ResNet50 being fine-tuned for 5 classes (the tf-flowers transfer-learn
shape) — forward, loss, backward, gradient allreduce, optax update, all one
jitted shard_map program.

Methodology: K successive steps are dispatched (each consuming the donated
state of the previous, so the chain cannot be elided) and the final loss is
fetched; wall/K is the sustained step time.  This amortizes the per-call
host round trip exactly like ``bench.py``.

Prints one JSON line.  The target is "record & minimize" — there is no
reference number, so ``vs_baseline`` is null.  Measures on the chip only:
without an accelerator it prints a refusal and exits with code 2.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("KERAS_BACKEND", "jax")

BATCH = 64
CLASSES = 5
IMAGE = 224
STEPS = 10


def main():
    from sparkdl_tpu.utils.benchlib import accelerator_or_refuse

    if accelerator_or_refuse(
        "KerasImageFileEstimator(ResNet50->5cls) DP fine-tune step time",
        unit=f"ms/step (batch {BATCH})",
    ) is None:
        return 2

    import jax
    import jax.numpy as jnp
    import keras

    from sparkdl_tpu.estimators.losses import get_optimizer, get_per_sample_loss_fn
    from sparkdl_tpu.parallel.keras_train import (
        init_keras_train_state,
        make_keras_train_step,
    )
    from sparkdl_tpu.parallel.trainer import make_mesh, shard_batch

    keras.utils.set_random_seed(0)
    base = keras.applications.ResNet50(
        weights=None, include_top=False, pooling="avg",
        input_shape=(IMAGE, IMAGE, 3),
    )
    model = keras.Sequential(
        [base, keras.layers.Dense(CLASSES, activation="softmax")]
    )

    loss_fn = get_per_sample_loss_fn("sparse_categorical_crossentropy")
    tx = get_optimizer("sgd", 0.01)
    mesh = make_mesh()
    state = init_keras_train_state(model, tx)
    step_fn = make_keras_train_step(model, loss_fn, tx, mesh, weighted=True)

    rng = np.random.RandomState(0)
    batch = {
        "x": jnp.asarray(rng.rand(BATCH, IMAGE, IMAGE, 3).astype(np.float32)),
        "y": jnp.asarray(rng.randint(0, CLASSES, BATCH).astype(np.int32)),
        "w": jnp.ones((BATCH,), jnp.float32),
    }
    batch = shard_batch(batch, mesh)

    # warm TWO steps: the first compiles for host-resident init state; the
    # second recompiles once for the device-resident donated-state layouts
    # every subsequent step reuses
    for _ in range(2):
        state, loss = step_fn(state, batch)
        float(loss)

    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, loss = step_fn(state, batch)
    float(loss)  # forces the whole donated-state chain
    per_step = (time.perf_counter() - t0) / STEPS

    print(
        json.dumps(
            {
                "metric": "KerasImageFileEstimator(ResNet50->5cls) DP "
                "fine-tune step time",
                "value": round(per_step * 1000, 2),
                "unit": f"ms/step (batch {BATCH})",
                "images_per_sec": round(BATCH / per_step, 1),
                "vs_baseline": None,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
