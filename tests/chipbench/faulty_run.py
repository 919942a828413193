"""Drives the rest of a run with the timed path broken underneath.

Run as a script (one CPU device, so that the program takes its one-chip
path): it skips the harness's look for a chip (tiny shapes), sets a cell up
once, checks that the sound program comes out correct and the control (the
reference in the next lower precision, put in the program's place) does not,
then plants each fault IN THE PROGRAM, runs the timed path again and records ``correct``.
Prints one JSON object: ``{"sound": bool, "faults": {name: bool}, ...}``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def featurize_faults():
    import numpy as np

    from sparkdl_tpu.transformers import named_image

    sound = named_image.DeepImageFeaturizer._postprocess

    def answer_altered(self, result):
        result = np.array(result)
        result[1] += 0.5 * np.abs(result).max()
        return sound(self, result)

    def half_left_out(self, result):
        result = np.array(result)
        half = len(result) // 2
        result[half:] = result[:len(result) - half]
        return sound(self, result)

    def plant(fault):
        named_image.DeepImageFeaturizer._postprocess = fault

    return {"answer_altered": answer_altered, "half_left_out": half_left_out}, \
        plant, lambda: plant(sound)


def fit_faults():
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.estimators import keras_image_file_estimator as est

    sound = est.make_keras_train_step

    def wrapping(alter):
        def make(*args, **kwargs):
            step = sound(*args, **kwargs)
            return lambda state, batch: alter(step, state, batch)
        return make

    def state_unchanged(step, state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state)
        _, loss = step(state, batch)
        return kept, loss

    def half_left_out(step, state, batch):
        w = jnp.asarray(batch["w"])
        batch = dict(batch, w=w.at[len(w) // 2:].set(0.0))
        return step(state, batch)

    def answer_altered(step, state, batch):
        new, loss = step(state, batch)
        trainable = list(new.trainable)
        big = max(range(len(trainable)), key=lambda i: trainable[i].size)
        trainable[big] = trainable[big] * 1.05
        return new._replace(trainable=trainable), loss

    def plant(fault):
        est.make_keras_train_step = wrapping(fault)

    def heal():
        est.make_keras_train_step = sound

    return {"state_unchanged": state_unchanged, "half_left_out": half_left_out,
            "answer_altered": answer_altered}, plant, heal


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    os.environ.setdefault("KERAS_BACKEND", "jax")
    from chipbench import harness

    harness.place_compile_cache(ROOT)
    cell = harness.Cell(args.workload, ROOT)
    workdir = os.path.join(ROOT, ".chipbench", "faults-" + cell.name)
    os.makedirs(workdir, exist_ok=True)
    job = cell.driver.Job(cell, args.seed, True, workdir)
    job.setup()
    job.window(0.2)
    first = job.compare()
    control = job.compare(cell.workload["control"])
    out = {"sound": first.correct, "sound_compared": first.as_dict(),
           "control": control.correct, "control_compared": control.as_dict(),
           "faults": {}, "compared": {}}
    faults, plant, heal = (
        featurize_faults() if cell.workload["driver"] == "featurize"
        else fit_faults()
    )
    for name, fault in faults.items():
        plant(fault)
        try:
            job.timed_path_again()
        finally:
            heal()
        compared = job.compare()
        out["faults"][name] = compared.correct
        out["compared"][name] = compared.as_dict()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
