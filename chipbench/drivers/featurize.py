"""Driver ``featurize``: ``DeepImageFeaturizer.transform(df).collect()``
repeated for the window, over a cached DataFrame (traffic of the kind
``cached_frame``) or over files read anew in every pass (``image_files``).

From the program it takes the entry points, its timers and counters.  The
weights, the images, the clock and the comparison are the benchmark's own.
"""

from __future__ import annotations

import os
import time

import numpy as np

from chipbench import flops, harness, traffic
from chipbench.reference import inception_v3 as reference


class Job:
    def __init__(self, cell, seed: int, rehearse: bool, workdir: str):
        self.cell, self.seed, self.rehearse = cell, int(seed), rehearse
        self.config = cell.config
        self.mix = dict(cell.traffic)
        if rehearse:
            self.mix.update(self.mix.get("rehearse", {}))
        self.from_files = {"cached_frame": False, "image_files": True}[
            self.mix["kind"]]
        self.key = "filePath" if self.from_files else "rowId"
        self.images_dir = os.path.join(workdir, "images")
        self.batch = int(
            cell.workload["rehearse_batch"] if rehearse
            else cell.workload["batchSize"]
        )
        self.read_s = 0.0

    # -- set-up -----------------------------------------------------------
    def setup(self, shared=None) -> None:
        """``shared``: a dict in which a process that sets up several seeds
        keeps the weights and the transformer (``chipbench/readings.py``)."""
        from sparkdl_tpu import DeepImageFeaturizer
        from sparkdl_tpu.sql.session import TPUSession

        self.spark = (
            TPUSession.builder.master("local[*]").appName("chipbench")
            .getOrCreate()
        )
        shared = {} if shared is None else shared
        if "featurizer" not in shared:
            shared["params"] = reference.make_params(self.config["weights_seed"])
            shared["featurizer"] = DeepImageFeaturizer(
                inputCol="image", outputCol="features",
                modelName=self.config["modelName"],
                modelWeights=reference.as_flax_variables(shared["params"]),
                batchSize=self.batch, computeDtype=self.config["computeDtype"],
            )
        self.params, self.featurizer = shared["params"], shared["featurizer"]
        if self.from_files:
            self.files = traffic.image_files(
                self.mix, self.seed, self.images_dir, classes=1
            )
            self.rows_per_pass = len(self.files["paths"])
        else:
            self._make_frame()
            self.rows_per_pass = len(self.order)
        # warm-up: one whole pass, the window's own call — compiles (or
        # fetches) the one program this cell's batch shape needs, builds the
        # native pack at first use, fills the page cache for the files
        self.last_rows = self._one_pass()

    def _make_frame(self) -> None:
        from sparkdl_tpu.image import imageIO

        made = traffic.cached_frame(self.mix, self.seed)
        self.images, self.order = made["images"], made["order"]
        # the struct stores BGR; every row has an origin of its own
        stored = self.images[..., ::-1]
        rows = [
            (r, imageIO.imageArrayToStruct(stored[i], origin=f"mem://row/{r}"))
            for r, i in enumerate(self.order)
        ]
        self.frame = self.spark.createDataFrame(
            rows, ["rowId", "image"],
            numPartitions=int(self.mix["partitions"]),
        )

    def _one_pass(self):
        from sparkdl_tpu.image import imageIO

        frame = None if self.from_files else self.frame
        if self.from_files:
            start = time.perf_counter()
            with harness.span("readImages"):
                frame = imageIO.readImages(
                    self.images_dir, self.spark,
                    numPartitions=int(self.mix["partitions"]),
                )
            self.read_s += time.perf_counter() - start
        with harness.span("transform"):
            out = self.featurizer.transform(frame).select(self.key, "features")
        with harness.span("collect"):
            return out.collect()

    # -- the measured window ------------------------------------------------
    def window(self, seconds: float) -> dict:
        """Whole passes until ``seconds`` have passed."""
        from sparkdl_tpu.utils.metrics import metrics

        timers = ("sparkdl.load", "sparkdl.decode", "sparkdl.forward")
        before = {t: metrics.timer(t).seconds for t in timers}
        compiles_before = metrics.counter("engine.cache_miss").value
        self.read_s = 0.0
        returned = passes = 0
        start = time.perf_counter()
        end = start
        while end - start < seconds:
            self.last_rows = self._one_pass()
            returned += len(self.last_rows)
            passes += 1
            end = time.perf_counter()
        wall = end - start
        per_image = flops.inception_v3_forward(reference.INPUT_HW)["flops"]
        in_bytes = self._in_bytes_per_image()
        return {
            "end_to_end": {"images_per_s": returned / wall},
            "attempted": passes * self.rows_per_pass,
            "failed": passes * self.rows_per_pass - returned,
            "facts": {
                "wall_s": wall, "images": returned, "passes": passes,
                "read_s": self.read_s,
                "timer_s": {
                    t: metrics.timer(t).seconds - before[t] for t in timers
                },
                "engine_compiles":
                    metrics.counter("engine.cache_miss").value - compiles_before,
                "batch": self.batch, "chips": 1,
                "needed_flops": per_image * returned,
                # the program as dispatched: its whole batch shape, padding
                # rows included (mfu counts the rows really returned)
                "dispatch": {
                    "flops": per_image * self.batch,
                    "bytes": flops.inception_v3_program_bytes(
                        self.batch, in_bytes, reference.INPUT_HW),
                },
                "program": self.cell.workload["program"],
            },
        }

    def _in_bytes_per_image(self) -> int:
        h, w = reference.INPUT_HW
        if self.from_files:  # mixed sizes: resized on the host to float32
            return h * w * 3 * 4
        return int(self.mix["height"]) * int(self.mix["width"]) * 3  # uint8

    def timed_path_again(self) -> None:
        """One more pass through the window's own call (the fault tests)."""
        self.last_rows = self._one_pass()

    def release(self) -> None:
        self.featurizer = self.frame = None

    # -- correct -----------------------------------------------------------
    def produced(self):
        """(keys, features) of the window's last pass, in collect order."""
        rows = self.last_rows
        return (
            [r[self.key] for r in rows],
            np.stack([np.asarray(r["features"].toArray(), np.float32)
                      for r in rows]),
        )

    def reference_inputs(self):
        """(row positions compared, index of each one's reference image,
        float32 RGB images at 299x299) — every row of a cached frame through
        its distinct image, a sample of the files drawn from the seed."""
        if not self.from_files:
            rgb = self.images.astype(np.float32)
            if rgb.shape[1:3] != reference.INPUT_HW:
                rgb = np.stack([reference.resize_bilinear(x) for x in rgb])
            return np.arange(len(self.order)), self.order, rgb
        paths = self.files["paths"]
        n = min(int(self.cell.workload["sample_rows"]), len(paths))
        picked = np.sort(
            np.random.default_rng([self.seed, 6]).choice(len(paths), n, False)
        )
        rgb = np.stack([
            reference.resize_bilinear(traffic.load_rgb(paths[i]))
            for i in picked
        ])
        return picked, np.arange(n), rgb

    def expected_keys(self):
        if self.from_files:
            return list(self.files["paths"])
        return list(range(len(self.order)))

    def compare(self, control: str = "") -> harness.Comparison:
        limits = self.cell.workload["limits"]
        keys, got = self.produced()
        expected = self.expected_keys()
        out = harness.Comparison()
        misplaced = (
            abs(len(keys) - len(expected))
            + sum(a != b for a, b in zip(keys, expected))
        )
        out.add("rows_out_of_place", misplaced, limits["rows_out_of_place"])
        positions, image_of, rgb = self.reference_inputs()
        ref = reference.features(self.params, rgb)
        if control:
            operand = {"fp8": reference.fp8_operand}[control]
            got_cmp = reference.features(self.params, rgb, operand=operand)[image_of]
        elif got.shape[0] == len(expected):
            got_cmp = got[positions]
        else:
            got_cmp = np.full((len(positions), ref.shape[1]), np.nan, np.float32)
        want = ref[image_of]
        scale = float(np.abs(ref).max())
        diff = np.abs(got_cmp.astype(np.float64) - want)
        out.add("feature_gap_max", diff.max() / scale, limits["feature_gap_max"])
        out.add(
            "feature_gap_rms",
            float(np.sqrt((diff ** 2).mean()) / np.sqrt((want.astype(np.float64) ** 2).mean())),
            limits["feature_gap_rms"],
        )
        self.compared_rows = len(positions)
        return out
