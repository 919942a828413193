"""Driver ``ar_generate``: ``AutoregressiveTransformer.transform(df).collect()``
repeated for the window, over a cached DataFrame of prompts (traffic of the
kind ``prompt_frame``).

From the program it takes the entry point, its spans and counters.  The
weights, the prompts, the clock and the comparison are the benchmark's own.

**How ``correct`` is decided** (teacher-forced: with random weights the
largest logit changes on rounding, so sampled tokens cannot be compared).
After the window, on the last pass's own output, for a sample of rows drawn
from the seed (the longest and the shortest prompt among them) the plain
reference (``chipbench/reference/granite_hybrid.py``) runs ONE full forward
over prompt + generated tokens and gives the float32 log-probabilities at
every generated position: position 0 checks the state prefill left at the
row's own length, the last one every state update and the cache in between.
Compared, each in units of the spread (standard deviation over the
vocabulary, mean over the row's positions) of the reference's
log-probabilities:

- ``logprob_gap``: |the log-probability the program chose a token with - the
  reference's of that token there|, the largest over the sample;
- ``token_regret``: the reference's largest log-probability at a position -
  the reference's of the token the program chose, the largest over the
  sample;

and exactly: ``rows_out_of_place`` (keys against the input's order),
``rows_malformed`` (a row without exactly ``genLength`` tokens of the
vocabulary) and ``tokens_dropped`` (the program's own count of (token,
expert) pairs its expert layers left out, over the whole process).  Under
``--control fp8`` the reference with every matmul's operands rounded to e4m3
chooses, at each compared position, the token and its log-probability in the
program's place.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from chipbench import granite_counts, harness, prompt_traffic
from chipbench.reference import granite_hybrid as reference

COUNTERS = (
    "ar_generate.prefill_tokens", "ar_generate.prefill_pad_tokens",
    "ar_generate.decode_steps", "ar_generate.decode_dispatches",
    "ar_generate.decode_expert_reads", "ar_generate.tokens_generated", "ssm.state_bytes", "moe.tokens_routed",
    "moe.tokens_dropped", "moe.expert_load_max", "moe.expert_load_mean",
)


class Job:
    def __init__(self, cell, seed: int, rehearse: bool, workdir: str):
        self.cell, self.seed, self.rehearse = cell, int(seed), rehearse
        self.config = dict(cell.config)
        self.mix = dict(cell.traffic)
        if rehearse:
            self.config.update(self.config.get("rehearse", {}))
            self.mix.update(self.mix.get("rehearse", {}))
        workload = cell.workload
        self.batch = int(
            workload["rehearse_batch"] if rehearse else workload["batchSize"])
        self.gen = int(self.mix["genLength"])
        #: what builds the stage's model (the fault tests plant theirs here)
        self.make_model = None

    # -- set-up -----------------------------------------------------------
    def setup(self, shared=None) -> None:
        """``shared`` is accepted for ``chipbench/readings.py``; nothing is
        kept in it: the weights come from the seed, and the programs take
        them as arguments, so the process's compiled programs serve every
        seed by themselves."""
        # first of all, so that a program without the stage (the parent of
        # the PR that added it) fails at once and not after making weights
        from sparkdl_tpu import AutoregressiveTransformer  # noqa: F401
        from sparkdl_tpu.sql.session import TPUSession

        self.spark = (
            TPUSession.builder.master("local[*]").appName("chipbench")
            .getOrCreate()
        )
        self.params = reference.make_params(
            self.config, self.seed, self.config["computeDtype"])
        vocab = self.config["vocab_size"]
        # no token is ruled out: ``mask_id`` past the vocabulary
        self.prompts = prompt_traffic.prompt_frame(
            self.mix, self.seed, vocab, vocab)
        self.frame = self.spark.createDataFrame(
            list(enumerate(self.prompts)), ["rowId", "prompt"],
            numPartitions=int(self.mix["partitions"]),
        )
        self.rows_per_pass = len(self.prompts)
        self.build_stage()
        # warm-up: one whole pass, the window's own call — compiles (or
        # fetches) the prefill and both decode shapes, places the weights
        self.last_rows = self._one_pass()

    def build_stage(self) -> None:
        from sparkdl_tpu import AutoregressiveTransformer
        from sparkdl_tpu.models.granite_hybrid import GraniteHybridModel

        make = self.make_model or GraniteHybridModel
        self.stage = None  # a stage before this one gives its state back
        gc.collect()
        self.stage = AutoregressiveTransformer(
            inputCol="prompt", outputCol="generated", recordCol="record",
            model=make(self.config, self.params), genLength=self.gen,
            batchSize=self.batch,
        )

    def _one_pass(self):
        with harness.span("transform"):
            out = self.stage.transform(self.frame).select(
                "rowId", "generated", "record")
        with harness.span("collect"):
            return out.collect()

    # -- the measured window ------------------------------------------------
    def window(self, seconds: float) -> dict:
        """Whole passes until ``seconds`` have passed."""
        from sparkdl_tpu.utils.metrics import metrics

        before = {c: metrics.counter(c).value for c in COUNTERS}
        compiles_before = metrics.counter("engine.cache_miss").value
        returned = passes = 0
        start = time.perf_counter()
        end = start
        while end - start < seconds:
            self.last_rows = self._one_pass()
            returned += len(self.last_rows)
            passes += 1
            end = time.perf_counter()
        wall = end - start
        counted = {c: metrics.counter(c).value - before[c] for c in COUNTERS}
        per_pass = granite_counts.needed_flops(
            self.config, [len(p) for p in self.prompts], self.gen)
        return {
            "end_to_end": {"images_per_s": returned / wall},
            "attempted": passes * self.rows_per_pass,
            "failed": passes * self.rows_per_pass - returned,
            "facts": {
                "wall_s": wall, "images": returned, "passes": passes,
                "engine_compiles":
                    metrics.counter("engine.cache_miss").value - compiles_before,
                "batch": self.batch, "chips": 1,
                "needed_flops": per_pass * returned / self.rows_per_pass,
                "prefill_pad_tokens": counted["ar_generate.prefill_pad_tokens"],
                "prefill_positions": counted["ar_generate.prefill_tokens"]
                                     + counted["ar_generate.prefill_pad_tokens"],
                "decode_steps": counted["ar_generate.decode_steps"],
                "decode_dispatches": counted["ar_generate.decode_dispatches"],
                "tokens_generated": counted["ar_generate.tokens_generated"],
                "state_bytes": counted["ssm.state_bytes"],
                "tokens_routed": counted["moe.tokens_routed"],
                "tokens_dropped": counted["moe.tokens_dropped"],
                "expert_load_max": counted["moe.expert_load_max"],
                "expert_load_mean": counted["moe.expert_load_mean"],
                "programs": self._programs(
                    counted["ar_generate.decode_expert_reads"]
                    / max(counted["ar_generate.decode_steps"], 1)),
            },
        }

    def _programs(self, experts_read: float) -> dict:
        """What each program's dispatches of one pass count, from the plan
        the stage itself makes of the prompts (shapes only) and the expert
        matrices a decode step read (the program's own count)."""
        from sparkdl_tpu.transformers import ar_generate
        from sparkdl_tpu.transformers.ar_generate import SegmentPlan

        segment = ar_generate.SEGMENT_LENGTH
        count = min(ar_generate.SEGMENT_ROWS, self.batch)
        steps = ar_generate.DECODE_STEPS
        prefill, visible = [], []
        for lo in range(0, len(self.prompts), self.batch):
            batch = self.prompts[lo:lo + self.batch]
            plan = SegmentPlan(batch, self.batch, segment, count, self.gen)
            prefill += [
                granite_counts.prefill_dispatch(
                    self.config, [int(s) for s in arrays[2]], segment)
                for arrays, _ in plan.dispatches
            ]
            dummies = self.batch - len(batch)
            visible.append(
                (sum(len(p) for p in batch) + dummies) / self.batch
                + self.gen / 2)
        left, decode = self.gen - 1, []
        while left:  # the dispatches of one batch: the step loop's shapes
            now = min(steps, left)
            decode.append(granite_counts.decode_dispatch(
                self.config, self.batch, now, float(np.mean(visible)),
                experts_read))
            left -= now
        return {
            "granite_prefill": {
                "name": "jit_granite_prefill", "dispatches": prefill},
            "granite_decode": {
                "name": "jit_granite_decode", "dispatches": decode},
        }

    def timed_path_again(self) -> None:
        """One more pass through the window's own call (the fault tests)."""
        self.last_rows = self._one_pass()

    def release(self) -> None:
        """Drops the stage, and with it the model's runner and its state;
        the weights stay (the reference reads them)."""
        self.stage = self.frame = None
        gc.collect()

    # -- correct -----------------------------------------------------------
    def sample(self):
        """Rows drawn from the seed, the longest and the shortest prompt
        among them."""
        n = min(int(self.cell.workload["sample_rows"]), len(self.prompts))
        lengths = [len(p) for p in self.prompts]
        picked = {int(np.argmax(lengths)), int(np.argmin(lengths))}
        for row in np.random.default_rng([self.seed, 36]).permutation(
                len(self.prompts)):
            if len(picked) >= n:
                break
            picked.add(int(row))
        return sorted(picked)

    def compare(self, control: str = "") -> harness.Comparison:
        limits = dict(self.cell.workload["limits"])
        if self.rehearse:  # the tiny model has its own noise, so its own limits
            limits.update(self.cell.workload.get("rehearse_limits", {}))
        rows = self.last_rows
        out = harness.Comparison()
        keys = [r["rowId"] for r in rows]
        expected = list(range(len(self.prompts)))
        out.add(
            "rows_out_of_place",
            abs(len(keys) - len(expected))
            + sum(a != b for a, b in zip(keys, expected)),
            limits["rows_out_of_place"])
        vocab = self.config["vocab_size"]

        def malformed(row) -> bool:
            tokens, record = np.asarray(row["generated"]), np.asarray(row["record"])
            return (tokens.shape != (self.gen,) or record.shape != (self.gen, 2)
                    or not np.array_equal(record[:, 0], tokens)
                    or tokens.min() < 0 or tokens.max() >= vocab)

        n_malformed = sum(malformed(r) for r in rows)
        out.add("rows_malformed", n_malformed, limits["rows_malformed"])
        from sparkdl_tpu.utils.metrics import metrics

        out.add("tokens_dropped",
                metrics.counter("moe.tokens_dropped").value,
                limits["tokens_dropped"])
        seen = {"logprob_gap": [], "token_regret": []}
        operand = {"": None, "fp8": reference.fp8_operand}[control]
        self.compared_rows = 0
        if len(rows) == len(expected) and not n_malformed:
            started, sample = time.perf_counter(), self.sample()
            where = []
            for row in sample:
                where.append(self._compare_row(rows[row], operand, seen))
            self.compared_rows = len(sample)
            print(f"chipbench: {len(sample)} rows forwarded by the reference "
                  f"in {time.perf_counter() - started:.1f} s",
                  file=sys.stderr, flush=True)
            compared = {name: max(values) for name, values in seen.items()}
            #: for ``chipbench/readings.py``: what no limit is set on
            self.leaf_report = {
                "positions_compared": len(seen["logprob_gap"]),
                "logprob_gap_mean": float(np.mean(seen["logprob_gap"])),
                "worst_position_of_row": where,
            }
        else:
            compared = dict.fromkeys(seen, float("nan"))
        for name, value in compared.items():
            out.add(name, value, limits[name])
        return out

    def _compare_row(self, row, operand, seen) -> int:
        """Adds one row's positions to ``seen``; returns the position of its
        largest ``logprob_gap``."""
        prompt = self.prompts[row["rowId"]]
        record = np.asarray(row["record"], np.float64)
        tokens = [int(t) for t in row["generated"]]
        said = record[:, 1]
        pad_to = int(self.cell.workload["pad_to"])
        ref = reference.teacher_forced(
            self.params, self.config, prompt, tokens, pad_to=pad_to)
        if operand is not None:  # the control chooses in the program's place
            low = reference.teacher_forced(
                self.params, self.config, prompt, tokens, operand=operand,
                pad_to=pad_to)
            tokens = [int(t) for t in low.argmax(axis=-1)]
            said = low.max(axis=-1)
        spread = float(ref.std(axis=-1).mean())
        at = np.arange(len(tokens))
        gaps = np.abs(said - ref[at, tokens]) / spread
        seen["logprob_gap"] += [float(g) for g in gaps]
        seen["token_regret"] += [
            float(r) for r in (ref.max(axis=-1) - ref[at, tokens]) / spread]
        return int(np.argmax(gaps))
