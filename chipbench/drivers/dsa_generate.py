"""Driver ``dsa_generate``: the ``ar_generate`` driver
(``AutoregressiveTransformer.transform(df).collect()`` repeated for the
window, over a cached DataFrame of prompts) for the stage's fourth model,
``Glm5Model`` (MLA under a learned sparse attention: a lightning indexer
picks ``index_topk`` keys a query).

It inherits from ``chipbench.drivers.mla_generate.Job`` (an ``ar_generate``
Job) what does not name a model — the pass, the window's loop, the sample,
the exact comparisons and ``logprob_gap_mean`` — and brings what does: the
plain reference (``chipbench/reference/glm5.py``, the published decompressed
MLA over each query's selected keys), the counts (``chipbench/glm5_counts.py``),
the model class, the programs' names in the trace (``jit_glm5_prefill``,
``jit_glm5_decode``), the stage's packed plan, and the program's counts of the caches a batch holds
(``mla.latent_cache_bytes``, ``dsa.index_cache_bytes``) and of the routed
pairs whose expert is held here (``moe.pairs_held``).  How ``correct`` is
decided is the ``mla_generate`` driver's: teacher-forced, logits not tokens,
ONE full reference forward a sampled row, the MEAN gap over the sampled
positions deciding (``PERF.md`` section 2).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import glm5_counts as counts
from chipbench import prompt_traffic
from chipbench.drivers import ar_generate, mla_generate
from chipbench.reference import glm5 as reference

COUNTERS = ar_generate.COUNTERS + (
    "mla.latent_cache_bytes", "dsa.index_cache_bytes", "moe.pairs_held")


class Job(mla_generate.Job):
    def setup(self, shared=None) -> None:
        # first of all, so that a program without the model (the parent of
        # the PR that added it) fails at once and not after making weights
        from sparkdl_tpu.models import glm5  # noqa: F401
        from sparkdl_tpu.sql.session import TPUSession

        self.spark = (
            TPUSession.builder.master("local[*]").appName("chipbench")
            .getOrCreate()
        )
        self.params = reference.make_params(
            self.config, self.seed, self.config["computeDtype"])
        vocab = self.config["vocab_size"]
        # no token is ruled out: ``mask_id`` past the vocabulary (the slice)
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
        from sparkdl_tpu.models.glm5 import Glm5Model

        make = self.make_model or Glm5Model
        self.stage = None  # a stage before this one gives its state back
        gc.collect()
        self.stage = AutoregressiveTransformer(
            inputCol="prompt", outputCol="generated", recordCol="record",
            model=make(self.config, self.params), genLength=self.gen,
            batchSize=self.batch,
        )

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
        per_pass = counts.needed_flops(
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
                "latent_cache_bytes": counted["mla.latent_cache_bytes"],
                "index_cache_bytes": counted["dsa.index_cache_bytes"],
                "tokens_routed": counted["moe.tokens_routed"],
                "pairs_held": counted["moe.pairs_held"],
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
        the stage itself makes of the prompts (shapes only, packed as the
        stage packs them for a model of caches alone) and the expert matrices
        a decode step read (the program's own count)."""
        from sparkdl_tpu.transformers import ar_generate as stage
        from sparkdl_tpu.transformers.ar_generate import SegmentPlan

        segment = stage.SEGMENT_LENGTH
        count = min(stage.SEGMENT_ROWS, self.batch)
        prefill, visible = [], []
        for lo in range(0, len(self.prompts), self.batch):
            batch = self.prompts[lo:lo + self.batch]
            plan = SegmentPlan(batch, self.batch, segment, count, self.gen,
                               packed=True)
            prefill += [
                counts.prefill_dispatch(
                    self.config, [int(s) for s in arrays[2]], segment)
                for arrays, _ in plan.dispatches
            ]
            dummies = self.batch - len(batch)
            visible.append(
                (sum(len(p) for p in batch) + dummies) / self.batch
                + self.gen / 2)
        left, decode = self.gen - 1, []
        while left:  # the dispatches of one batch: the step loop's shapes
            now = min(stage.DECODE_STEPS, left)
            decode.append(counts.decode_dispatch(
                self.config, self.batch, now, float(np.mean(visible)),
                experts_read))
            left -= now
        return {
            "glm5_prefill": {
                "name": "jit_glm5_prefill", "dispatches": prefill},
            "glm5_decode": {
                "name": "jit_glm5_decode", "dispatches": decode},
        }

    # -- correct -----------------------------------------------------------
    def _compare_row(self, row, operand, seen) -> int:
        """Adds one row's positions to ``seen``; returns the position of its
        largest ``logprob_gap`` (the ``ar_generate`` driver's arithmetic on
        this model's reference)."""
        prompt = self.prompts[row["rowId"]]
        record = np.asarray(row["record"], np.float64)
        tokens = [int(t) for t in row["generated"]]
        said = record[:, 1]
        workload = self.cell.workload
        pad_to = int(workload["rehearse_pad_to"] if self.rehearse
                     else workload["pad_to"])
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
