"""Driver ``fit``: one ``KerasImageFileEstimator.fit(frame)`` call over image
files is the window; its wall time over the steps it ran is ``fit_step_ms``.

Set-up writes the files and labels from the seed, builds the Keras model from
the seed on the CPU backend and saves it (the entry point takes a file), and
drives the estimator through its first steps: a fit of one step and a fit of
three, on one batch of files, whose tuned models and losses are what
``correct`` compares with the plain reference; then one epoch over all files,
whose time sets the window's ``epochs``.  Every ``fit`` builds its own step
inside the program, so what the set-up fits and the window share is the
estimator object, the model file, the loader and (same shapes) the compiled
programs.
"""

from __future__ import annotations

import os
import shutil
import time

import numpy as np

from chipbench import flops, harness, traffic
from chipbench.reference import keras_train as reference

_BGR_MEAN = np.asarray([103.939, 116.779, 123.68], np.float32)


def make_loader(hw: int):
    """The user's ``imageLoader``: PIL decode, bilinear resize to ``hw``,
    "caffe" preprocessing (BGR, ImageNet means off)."""

    def load(uri):
        from PIL import Image

        img = Image.open(uri).convert("RGB").resize((hw, hw), Image.BILINEAR)
        return np.asarray(img, np.float32)[..., ::-1] - _BGR_MEAN

    return load


class Job:
    def __init__(self, cell, seed: int, rehearse: bool, workdir: str):
        self.cell, self.seed, self.rehearse = cell, int(seed), rehearse
        self.config = dict(cell.config)
        self.mix = dict(cell.traffic)
        if rehearse:
            self.mix.update(self.mix.get("rehearse", {}))
            self.config.update(self.config.get("rehearse", {}))
        self.images_dir = os.path.join(workdir, "images")
        self.model_file = os.path.join(workdir, "model.keras")
        self.batch = int(self.config["batch_size"])
        self.hw = int(self.config["input_height"])
        self.lr = float(self.config["learning_rate"])

    # -- set-up -----------------------------------------------------------
    def setup(self, shared=None) -> None:
        from sparkdl_tpu import KerasImageFileEstimator
        from sparkdl_tpu.sql.session import TPUSession

        self.spark = (
            TPUSession.builder.master("local[*]").appName("chipbench")
            .getOrCreate()
        )
        self.files = traffic.image_files(
            self.mix, self.seed, self.images_dir,
            classes=int(self.config["classes"]),
        )
        self._build_model()
        self.estimator = KerasImageFileEstimator(
            inputCol="filePath", outputCol="pred", labelCol="label",
            imageLoader=make_loader(self.hw), modelFile=self.model_file,
            kerasOptimizer=self.config["optimizer"],
            kerasLoss=self.config["loss"],
        )
        paths, labels = self.files["paths"], self.files["labels"]
        self.frame = self._frame(paths, labels)
        self.steps_per_epoch = -(-len(paths) // self.batch)
        n = self.batch
        self.check_paths, self.check_labels = paths[:n], labels[:n]
        # the timed path's own first steps (which also compile the step,
        # twice: the donated state changes layout after step one)
        t1, t3 = self.timed_path_again()
        t_epoch, _ = self._fit(self.frame, epochs=1)
        # every fit pays its fixed costs (model load, two program fetches,
        # model save) anew: a one-step fit less one unpipelined step.  The
        # first fit of a cold run compiles and says nothing; the estimate
        # then falls back to "an epoch costs a whole one-epoch fit", and an
        # epoch is never taken for less than a quarter of that: the window
        # may come out short, never runaway (PERF.md section 7)
        self.fixed_s = max(0.0, min(t1, t3) - abs(t3 - t1) / 2)
        self.epoch_s = max(t_epoch - self.fixed_s, t_epoch / 4)

    def timed_path_again(self) -> float:
        """The estimator's first steps on one batch of files: a fit of one
        step and a fit of three.  Returns the two calls' seconds."""
        check = self._frame(self.check_paths, self.check_labels)
        t1, self.fit1 = self._fit(check, epochs=1, read=True)
        t3, self.fit3 = self._fit(check, epochs=3, read=True)
        return t1, t3

    def _build_model(self) -> None:
        import jax
        import keras

        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            keras.utils.set_random_seed(self.seed % (2**31))
            model = getattr(keras.applications, self.config["keras_application"])(
                weights=None, input_shape=(self.hw, self.hw, 3),
                classes=int(self.config["classes"]),
            )
            model.save(self.model_file)
        self.forward = flops.keras_forward(model)

    def _frame(self, paths, labels):
        return self.spark.createDataFrame(
            [(p, int(y)) for p, y in zip(paths, labels)],
            ["filePath", "label"],
        )

    def _fit(self, frame, epochs: int, read: bool = False):
        """One ``fit`` call: (wall seconds, what it left behind).  The tuned
        model it wrote is read back where asked, and deleted."""
        from sparkdl_tpu.obs import tracer

        spans = []
        sink = spans.append
        self.estimator.setParams(kerasFitParams={
            "epochs": int(epochs), "batch_size": self.batch,
            "streaming": bool(self.config["streaming"]),
            "learning_rate": self.lr, "seed": self.seed % (2**31),
        })
        tracer.enable(sink)
        start = time.perf_counter()
        try:
            with harness.span("fit"):
                fitted = self.estimator.fit(frame)
        finally:
            wall = time.perf_counter() - start
            tracer.disable()
            tracer.remove_sink(sink)
        fit_span = [s for s in spans if s["name"] == "estimator.fit"][-1]
        epochs_seen = [e for e in fit_span["events"] if e["name"] == "epoch"]
        tuned = fitted.getModelFile()
        weights = reference.load_weights(tuned)[1:] if read else None
        shutil.rmtree(os.path.dirname(tuned), ignore_errors=True)
        return wall, {
            "weights": weights,
            "losses": [e["loss"] for e in epochs_seen],
            "host_stall_ms": sum(e["host_stall_ms"] for e in epochs_seen),
            "steps": sum(1 for s in spans if s["name"] == "estimator.step"),
        }

    # -- the measured window ------------------------------------------------
    def window(self, seconds: float) -> dict:
        """One ``fit`` call sized to last ``seconds``."""
        epochs = max(1, round((seconds - self.fixed_s) / self.epoch_s))
        wall, left = self._fit(self.frame, epochs=epochs)
        steps = left["steps"]
        expected = epochs * self.steps_per_epoch
        step = flops.train_step(
            self.forward, self.batch, self.hw * self.hw * 3 * 4)
        return {
            "end_to_end": {"fit_step_ms": 1e3 * wall / max(steps, 1)},
            "attempted": expected * self.batch,
            "failed": (expected - steps) * self.batch,
            "facts": {
                "wall_s": wall, "steps": steps, "epochs": epochs,
                "images": steps * self.batch, "chips": 1,
                "host_stall_ms": left["host_stall_ms"],
                "needed_flops": step["flops"] * steps,
                "dispatch": step,
                "program": self.cell.workload["program"],
            },
        }

    def release(self) -> None:
        """Drop what the program's fits left on the device before the
        reference takes its place."""
        import gc

        import jax

        self.frame = None
        gc.collect()
        jax.clear_caches()

    # -- correct -----------------------------------------------------------
    def produced(self) -> dict:
        """What the program's own first steps left: each step's loss, the
        model after one step and after three."""
        tr1, _ = self.fit1["weights"]
        tr3, nt3 = self.fit3["weights"]
        return {
            "losses": list(self.fit3["losses"]),
            "after_one": tr1, "after_three": tr3, "stats_after_three": nt3,
        }

    def compare(self, control: str = "") -> harness.Comparison:
        """Every number this driver can read is worked out; those the cell's
        file gives a limit are compared, the others go to the look.
        ``control`` puts the reference in the program's place: ``bf16`` (the
        train state kept and computed in bfloat16), or with a fault planted:
        ``half_left_out`` (the mean taken over the first half of the batch),
        ``answer_altered`` (the largest leaf of the returned state scaled by
        1.05)."""
        import jax.numpy as jnp

        limits = self.cell.workload["limits"]
        if self.rehearse:  # tiny shapes read differently (PERF.md section 2)
            limits = self.cell.workload.get("rehearse_limits", limits)
        model, tr0, nt0 = reference.load_weights(self.model_file)
        load = make_loader(self.hw)
        x = np.stack([load(p) for p in self.check_paths])
        y = np.asarray(self.check_labels, np.int32)
        ref = reference.sgd_steps(model, tr0, nt0, x, y, self.lr, steps=3)
        if control:
            half = len(x) // 2
            xs, ys = (x[:half], y[:half]) if control == "half_left_out" else (x, y)
            low = reference.sgd_steps(
                model, tr0, nt0, xs, ys, self.lr, steps=3,
                state_dtype=jnp.bfloat16 if control == "bf16" else None)
            if control == "answer_altered":
                big = max(range(len(tr0)), key=lambda i: tr0[i].size)
                low["after_one"][big] = low["after_one"][big] * 1.05
                low["trainable"][big] = low["trainable"][big] * 1.05
            if control not in ("bf16", "half_left_out", "answer_altered"):
                raise KeyError(control)
            got = {
                "losses": low["losses"], "after_one": low["after_one"],
                "after_three": low["trainable"],
                "stats_after_three": low["non_trainable"],
            }
        else:
            got = self.produced()
        numbers = {}
        for s in range(3):
            loss = got["losses"][s] if s < len(got["losses"]) else float("nan")
            numbers[f"loss_gap_step{s + 1}"] = (
                abs(loss - ref["losses"][s]) / abs(ref["losses"][s]))
        ref_grad = reference.leaf_norms(ref["first_grads"])
        # leaves whose gradient is nought to rounding in the reference (a
        # conv's bias before batch norm) move by round-off alone: left out
        # by a rule on the reference's gradient, not by name
        keep = ref_grad >= 1e-3 * np.median(ref_grad)
        got_grad = reference.leaf_norms(
            [(a - b) / self.lr for a, b in zip(tr0, got["after_one"])])
        ref_change = reference.leaf_norms(
            [a - b for a, b in zip(ref["trainable"], tr0)])
        got_change = reference.leaf_norms(
            [a - b for a, b in zip(got["after_three"], tr0)])
        for name, got_n, ref_n in (("first_grad", got_grad, ref_grad),
                                   ("change", got_change, ref_change)):
            gaps = reference.norm_gaps(got_n, ref_n, keep)
            numbers[name + "_norm_gap_worst"] = float(np.nanmax(gaps))
            numbers[name + "_norm_gap_median"] = float(np.nanmedian(gaps))
        float_stats = [i for i, n in enumerate(nt0)
                       if np.issubdtype(n.dtype, np.floating)]
        numbers["bn_stats_norm_gap_worst"] = float(np.nanmax(reference.norm_gaps(
            reference.leaf_norms(
                [got["stats_after_three"][i] - nt0[i] for i in float_stats]),
            reference.leaf_norms(
                [ref["non_trainable"][i] - nt0[i] for i in float_stats]))))
        out = harness.Comparison()
        for name, limit in limits.items():
            out.add(name, numbers[name], limit)
        self.compared_rows = len(x)
        names = [v.path for v in model.trainable_variables]
        self.leaf_report = {
            "not_compared": {k: v for k, v in numbers.items() if k not in limits},
            **{what: self._leaf_report(names, tr0, got_n, ref_n, keep)
               for what, got_n, ref_n in (("first_grad", got_grad, ref_grad),
                                          ("change", got_change, ref_change))},
        }
        return out

    @staticmethod
    def _leaf_report(names, tr0, got_norms, ref_norms, keep) -> dict:
        """Which leaves read far off, for looking before a limit is set."""
        gaps = reference.norm_gaps(got_norms, ref_norms, keep)
        order = np.argsort(-np.nan_to_num(gaps, nan=-1.0))[:6]
        kept = gaps[~np.isnan(gaps)]
        return {
            "kept": int(keep.sum()), "of": len(keep),
            "median_ref_norm": float(np.median(ref_norms[keep])),
            "gap_quantiles_50_90_99_max": [
                float(np.quantile(kept, q)) for q in (0.5, 0.9, 0.99, 1.0)],
            "worst": [
                {"leaf": names[i], "shape": list(tr0[i].shape),
                 "gap": float(gaps[i]), "ref_norm": float(ref_norms[i]),
                 "got_norm": float(got_norms[i])}
                for i in order
            ],
        }
