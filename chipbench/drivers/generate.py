"""Driver ``generate``: ``BlockDiffusionTransformer.transform(df).collect()``
repeated for the window, over a cached DataFrame of prompts (traffic of the
kind ``prompt_frame``).

From the program it takes the entry point, its spans and counters.  The
weights, the prompts, the clock and the comparison are the benchmark's own.

**How ``correct`` is decided** (teacher-forced: with random weights the
largest logit changes on rounding, so sampled tokens cannot be compared).
After the window, on the last pass's output, for a sample of rows drawn from
the seed and for each the first block (with its known prompt positions), a
middle block and the last block at every denoising step, and the blocks that
follow the first and the middle one at their first step (which see, through
the cache, the state the commit before left): the plain reference
(``chipbench/reference/sdar_moe.py``) rebuilds the state the program's record
says it was in and gives that step's float32 log-probabilities.  Compared,
each in units of the spread (standard deviation over the vocabulary) of the
reference's logits at that step:

- ``logprob_gap``: |the log-probability the program fixed a token with - the
  reference's of that token there|, the largest over the sample;
- ``token_regret``: the reference's largest log-probability at a fixed
  position - the reference's of the token the program fixed, the largest
  over the sample;
- ``position_regret``: the reference's highest greedy log-probability among
  the still-masked positions the program did not fix - its lowest among
  those the program fixed (0 when they agree on the positions), the MEAN
  over the compared steps that had a position to pass over.  Which of two
  near-tied positions goes first turns on rounding, and one such step's
  regret is as large at any precision (it is bounded by how far the
  positions' confidences lie apart, not by the noise), so the largest over
  the sample does not tell bfloat16 from fp8; how OFTEN the order differs
  does (``PERF.md`` section 2).  The largest is kept in ``leaf_report``;

and exactly: ``rows_out_of_place`` (keys against the input's order),
``rows_malformed`` (a row without exactly ``genLength`` tokens, or with a
``[MASK]`` among them) and ``tokens_dropped`` (the program's own count of
(token, expert) pairs its expert layers left out, over the whole process).
Under ``--control fp8`` the reference with every matmul's operands rounded to
e4m3 decides, at each compared step, what is fixed and with what
log-probability, in the program's place.
"""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from chipbench import harness, prompt_traffic, sdar_counts
from chipbench.reference import sdar_moe as reference

COUNTERS = (
    "generate.denoise_forwards", "generate.commit_forwards",
    "generate.tokens_fixed", "moe.tokens_routed", "moe.tokens_dropped",
    "moe.expert_load_max", "moe.expert_load_mean",
)


class Job:
    def __init__(self, cell, seed: int, rehearse: bool, workdir: str):
        self.cell, self.seed, self.rehearse = cell, int(seed), rehearse
        self.config = dict(cell.config)
        self.mix = dict(cell.traffic)
        if rehearse:
            self.config.update(self.config.get("rehearse", {}))
            self.mix.update(self.mix.get("rehearse", {}))
        self.batch = int(
            cell.workload["rehearse_batch"] if rehearse
            else cell.workload["batchSize"]
        )
        self.gen = int(self.mix["genLength"])
        self.block = int(self.mix["blockLength"])
        self.steps = int(self.mix["denoisingSteps"])
        self.mask_id = int(self.config["mask_token_id"])
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
        from sparkdl_tpu import BlockDiffusionTransformer  # noqa: F401
        from sparkdl_tpu.sql.session import TPUSession

        self.spark = (
            TPUSession.builder.master("local[*]").appName("chipbench")
            .getOrCreate()
        )
        self.params = reference.make_params(
            self.config, self.seed, self.config["computeDtype"])
        self.prompts = prompt_traffic.prompt_frame(
            self.mix, self.seed, self.config["vocab_size"], self.mask_id)
        self.frame = self.spark.createDataFrame(
            list(enumerate(self.prompts)), ["rowId", "prompt"],
            numPartitions=int(self.mix["partitions"]),
        )
        self.rows_per_pass = len(self.prompts)
        self.build_stage()
        # warm-up: one whole pass, the window's own call — compiles (or
        # fetches) every chunk shape and the block step, places the weights
        self.last_rows = self._one_pass()

    def build_stage(self) -> None:
        from sparkdl_tpu import BlockDiffusionTransformer
        from sparkdl_tpu.models.sdar_moe import SdarMoeModel

        make = self.make_model or SdarMoeModel
        self.stage = None  # a stage before this one gives its cache back
        gc.collect()
        self.stage = BlockDiffusionTransformer(
            inputCol="prompt", outputCol="generated", recordCol="record",
            model=make(self.config, self.params),
            genLength=self.gen, blockLength=self.block,
            denoisingSteps=self.steps, maskTokenId=self.mask_id,
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
        lengths = [len(p) for p in self.prompts]
        per_pass = sdar_counts.needed_flops(
            self.config, lengths, self.gen, self.block, self.steps)
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
                "forwards": counted["generate.denoise_forwards"]
                            + counted["generate.commit_forwards"],
                "tokens_fixed": counted["generate.tokens_fixed"],
                "tokens_routed": counted["moe.tokens_routed"],
                "tokens_dropped": counted["moe.tokens_dropped"],
                "expert_load_max": counted["moe.expert_load_max"],
                "expert_load_mean": counted["moe.expert_load_mean"],
                "programs": self._programs(),
            },
        }

    def _programs(self) -> dict:
        """What each program's dispatches of one pass count, from the plan
        the stage itself makes of the prompts (shapes only)."""
        from sparkdl_tpu.transformers.block_diffusion import BatchPlan

        prefill, visible = [], []
        for lo in range(0, len(self.prompts), self.batch):
            plan = BatchPlan(
                self.prompts[lo:lo + self.batch], self.batch, self.block,
                self.gen)
            prefill += [
                sdar_counts.prefill_dispatch(
                    self.config, count, length, self.block)
                for _, count, length in plan.chunks
            ]
            visible += [
                float(np.mean(plan.whole)) + index * self.block
                for index in range(plan.blocks)
            ]
        return {
            "sdar_prefill": {"name": "jit_sdar_prefill", "dispatches": prefill},
            "sdar_block": {"name": "jit_sdar_block", "dispatches": [
                sdar_counts.block_dispatch(
                    self.config, self.batch, self.block, self.steps,
                    float(np.mean(visible)))
            ]},
        }

    def timed_path_again(self) -> None:
        """One more pass through the window's own call (the fault tests)."""
        self.last_rows = self._one_pass()

    def release(self) -> None:
        """Drops the stage, and with it the model's runner and its cache;
        the weights stay (the reference reads them)."""
        self.stage = self.frame = None
        gc.collect()

    # -- correct -----------------------------------------------------------
    def sample(self):
        """[(row, block, steps compared)] drawn from the seed."""
        rows = self.last_rows
        n = min(int(self.cell.workload["sample_rows"]), len(rows))
        picked = np.sort(np.random.default_rng([self.seed, 23]).choice(
            len(rows), n, replace=False))
        out = []
        for row in picked:
            blocks = len(rows[row]["record"]) // self.block
            whole = {0, blocks // 2, blocks - 1}
            after = {b + 1 for b in (0, blocks // 2) if b + 1 < blocks}
            for b in sorted(whole | after):
                steps = range(self.steps) if b in whole else range(1)
                out.append((int(row), b, list(steps)))
        return out

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
        out.add(
            "rows_malformed",
            sum(len(r["generated"]) != self.gen
                or bool(np.any(np.asarray(r["generated"]) == self.mask_id))
                for r in rows),
            limits["rows_malformed"])
        from sparkdl_tpu.utils.metrics import metrics

        out.add("tokens_dropped",
                metrics.counter("moe.tokens_dropped").value,
                limits["tokens_dropped"])
        seen = {"logprob_gap": [], "token_regret": [], "position_regret": []}
        operand = {"": None, "fp8": reference.fp8_operand}[control]
        self.compared_rows = 0
        if len(rows) == len(expected):
            started, sample = time.perf_counter(), self.sample()
            for row, block, steps in sample:
                for step in steps:
                    self._compare_step(rows[row], block, step, operand, seen)
            self.compared_rows = len({row for row, _, _ in sample})
            print(f"chipbench: {sum(len(s) for _, _, s in sample)} steps "
                  f"replayed by the reference in "
                  f"{time.perf_counter() - started:.1f} s",
                  file=sys.stderr, flush=True)
            choices = seen["position_regret"]
            compared = {
                "logprob_gap": max(seen["logprob_gap"], default=0.0),
                "token_regret": max(seen["token_regret"], default=0.0),
                "position_regret": float(np.mean(choices)) if choices else 0.0,
            }
            #: for ``chipbench/readings.py``: what no limit is set on
            self.leaf_report = {
                "position_regret_max": float(max(choices, default=0.0)),
                "steps_with_a_choice": len(choices),
                "steps_out_of_order": int(sum(c > 0 for c in choices)),
                "positions_compared": len(seen["logprob_gap"]),
            }
        else:
            compared = dict.fromkeys(seen, float("nan"))
        for name, value in compared.items():
            out.add(name, value, limits[name])
        return out

    def _compare_step(self, row, block, step, operand, seen) -> None:
        prompt = self.prompts[row["rowId"]]
        record = np.asarray(row["record"], np.float64)
        lo = block * self.block
        replay = dict(
            params=self.params, config=self.config, prompt=prompt,
            record=record, block_index=block, step=step,
            block_length=self.block, mask_id=self.mask_id,
            pad_to=int(self.cell.workload["pad_to"]),
        )
        ref, masked = reference.replay(**replay)
        if operand is None:
            fixed = [i for i in range(self.block)
                     if int(record[lo + i, 1]) == step]
            tokens = [int(record[lo + i, 0]) for i in fixed]
            said = [float(record[lo + i, 2]) for i in fixed]
        else:  # the control decides in the program's place
            low, _ = reference.replay(operand=operand, **replay)
            fixed, tokens = reference.choose(
                low, masked, self.steps - step)
            said = [float(low[i, t]) for i, t in zip(fixed, tokens)]
        if not fixed:
            return
        finite = np.where(np.isfinite(ref), ref, np.nan)
        spread = float(np.nanstd(finite, axis=-1).mean())
        greedy = ref.max(axis=-1)
        for i, token, logprob in zip(fixed, tokens, said):
            seen["logprob_gap"].append(abs(logprob - ref[i, token]) / spread)
            seen["token_regret"].append((greedy[i] - ref[i, token]) / spread)
        passed_over = [greedy[i] for i in range(self.block)
                       if masked[i] and i not in fixed]
        if passed_over:
            seen["position_regret"].append(max(
                0.0,
                (max(passed_over) - min(greedy[i] for i in fixed)) / spread))
