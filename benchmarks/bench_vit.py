"""BASELINE config #5 (stretch): ViT-B/16 fine-tune step time.

Measures one data-parallel fine-tune step of the ``FlaxImageFileEstimator``
engine on ViT-B/16 at 224² (197 tokens), bf16 compute — forward, loss,
backward, gradient allreduce, optax update in one jitted shard_map program.
The pod-scale shardings of the same step (DP×TP GSPMD + sequence-parallel
ring attention) are validated by ``__graft_entry__.dryrun_multichip`` on the
virtual mesh; this bench records the per-chip step time on real hardware.

Methodology matches ``bench_finetune.py``: K donated-state-chained steps,
final loss fetched, wall/K.  ``vs_baseline`` is null — the reference has no
ViT at all (SURVEY.md §2: the zoo is CNN-only), so there is no number to
beat; this row exists to fill BASELINE.json config #5.

Weights: the bench uses constant-filled parameters because step time is
weight-VALUE-invariant (same flops, same layouts); the actual pretrained
path — google-research ``.npz`` / HF torch ingestion + pos-embed/head
adaptation — is ``sparkdl_tpu/models/vit_port.py``, exercised end-to-end
by ``examples/distributed_finetune.py`` and oracle-tested in
``tests/test_vit_port.py``, and plugs into this same engine via
``FlaxImageFileEstimator(initialVariables=...)``.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("KERAS_BACKEND", "jax")

BATCH = 32
CLASSES = 5
IMAGE = 224
STEPS = 10


def main():
    from sparkdl_tpu.utils.benchlib import accelerator_or_refuse

    if accelerator_or_refuse(
        "FlaxImageFileEstimator(ViT-B/16->5cls) DP fine-tune step time",
        unit=f"ms/step (batch {BATCH})",
    ) is None:
        return 2

    import jax.numpy as jnp
    import optax

    from sparkdl_tpu.models.vit import ViT
    from sparkdl_tpu.parallel.trainer import (
        init_train_state,
        make_mesh,
        make_train_step,
        shard_batch,
    )

    module = ViT(
        variant="ViT-B/16", num_classes=CLASSES, image_size=IMAGE,
        dtype=jnp.bfloat16,
    )
    import jax

    x0 = jnp.zeros((1, IMAGE, IMAGE, 3), jnp.float32)
    variables = jax.tree_util.tree_map(
        lambda l: jnp.full(l.shape, 0.01, l.dtype),
        jax.eval_shape(module.init, jax.random.PRNGKey(0), x0),
    )

    def per_sample_loss(params, batch):
        logits = module.apply(params, batch["x"]).astype(jnp.float32)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]
        )

    tx = optax.adamw(1e-4)
    mesh = make_mesh()
    state = init_train_state(variables, tx)
    step_fn = make_train_step(per_sample_loss, tx, mesh, weighted=True)

    rng = np.random.RandomState(0)
    batch = {
        "x": jnp.asarray(rng.rand(BATCH, IMAGE, IMAGE, 3).astype(np.float32)),
        "y": jnp.asarray(rng.randint(0, CLASSES, BATCH).astype(np.int32)),
        "w": jnp.ones((BATCH,), jnp.float32),
    }
    batch = shard_batch(batch, mesh)

    # two warmup steps: see bench_finetune.py (the donated state changes
    # layout after the first step)
    for _ in range(2):
        state, loss = step_fn(state, batch)
        float(loss)

    t0 = time.perf_counter()
    for _ in range(STEPS):
        state, loss = step_fn(state, batch)
    float(loss)  # forces the donated-state chain
    per_step = (time.perf_counter() - t0) / STEPS

    print(
        json.dumps(
            {
                "metric": "FlaxImageFileEstimator(ViT-B/16->5cls) DP "
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
