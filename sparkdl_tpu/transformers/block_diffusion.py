"""BlockDiffusionTransformer — fixed-length generation by diffusion over
blocks, over a DataFrame column of prompts (arrays of token ids).

Every other stage is one dispatch a batch.  This one is one prefill (in
chunks) and then a chain of block steps over a key/value cache that stays on
the device, donated from dispatch to dispatch; a step yields up to
``blockLength`` tokens a row, not one.  A step is ``denoisingSteps``
forwards and no more: a finished block's tokens stay on the device and ride
the NEXT step's first forward into the cache (one pass over the weights for
the commit and the denoising forward together), and a batch's last block is
never committed, since nobody reads it.  So the block step has two shapes,
chosen by the block's index: block 0 has nothing pending, every later one
does.  Between the batch's placement and its last fetch only token ids, the
per-block record and scalars cross to the host.  The model's weights are
program ARGUMENTS, placed once per model object
(:func:`~sparkdl_tpu.transformers.utils.place_params_once`): an executable
holds no weight constants, and two models of one config share their
executables.

How a batch is laid out: its rows are sorted by prompt length, longest
first.  The prompt's whole blocks are prefilled in chunks of about
``PREFILL_TOKENS`` tokens, each chunk padded to its longest row's length
rounded up to ``_LENGTH_STEP`` (one compile per distinct chunk shape); pad
positions are never attended (a row's cache is read up to its own length).
The ``P mod blockLength`` tokens left open the row's first generated block
as known positions.  Then ``ceil((P mod B + genLength) / B)`` block steps
run for the whole batch; the last batch of a partition is padded with
one-block dummy rows.

Spans (``obs.trace`` boundaries, made whether or not tracing is enabled):
``generate.partition`` (root) > ``generate.plan``, ``engine.place``,
``generate.prefill``, ``generate.block``, ``engine.fetch_wait``,
``generate.postprocess``; ``generate.block`` carries ``denoise_forwards``,
``commit_forwards`` (1 where the step committed the block before it, 0 on
block 0), ``fused`` (the same: that commit shared a forward),
``weight_passes`` (passes over the layers' weights: ``denoisingSteps``),
``row_passes`` (those, times the real rows still generating in the block:
what a fixed token is paid for with) and ``fixed``.  On the engine's watcher thread, one ``engine.device`` a dispatch
(``program``: ``sdar_prefill`` / ``sdar_block``; ``rows``, ``queued_ms``):
when the device computed it.  Counters, per real row-block:
``generate.denoise_forwards``,
``generate.commit_forwards`` (blocks whose final tokens went through the
layers for the cache: all but a row's last, each inside a denoising
forward), ``generate.tokens_fixed``; and
``moe.tokens_routed``, ``moe.pairs_held``, ``moe.tokens_dropped``,
``moe.expert_load_max``, ``moe.expert_load_mean`` (from the routing counts
that come back with every program's result).
"""

from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

import jax

from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.param.base import Param, TypeConverters, keyword_only
from sparkdl_tpu.param.shared import HasInputCol, HasOutputCol
from sparkdl_tpu.transformers.generation import (
    ProgramRunner,
    count_routing,
    runner_for,
)

#: chunk lengths and the cache's span are multiples of this (one compile
#: per distinct shape; the TPU tiles the span by it)
_LENGTH_STEP = 128
#: tokens (rows x padded length) of one prefill chunk: enough rows for every
#: expert to get hundreds, few enough for the float32 scores (16 rows of 512
#: are 0.5 GB a layer) to fit beside the weights
PREFILL_TOKENS = 8192


def _round_up(n: int, step: int) -> int:
    return -(-int(n) // step) * step


class BatchPlan:
    """The layout of one batch of prompts: which row sits where, what is
    prefilled in which chunk, what opens each row's first block."""

    def __init__(self, prompts: List[np.ndarray], rows: int, block: int,
                 gen: int, prefill_tokens: int = PREFILL_TOKENS):
        real = len(prompts)
        lengths = np.zeros(rows, np.int64)
        lengths[:real] = [len(p) for p in prompts]
        whole = lengths // block * block
        self.rest = (lengths - whole).astype(np.int32)
        # a dummy row (batch padding) is one block of known zeros
        self.blocks_of_row = -(-(self.rest + gen) // block)
        self.blocks_of_row[real:] = 0
        self.blocks = int(self.blocks_of_row[:real].max())
        self.order = np.argsort(-whole, kind="stable")  # longest first
        self.whole = whole[self.order].astype(np.int32)
        #: the cache's slots: the prompts' up to ``longest``, then every
        #: row's generated blocks in the same slots
        longest = _round_up(max(int(self.whole[0]), block), _LENGTH_STEP)
        self.longest = longest
        self.span = _round_up(longest + self.blocks * block, _LENGTH_STEP)
        self.tokens = np.zeros((rows, longest), np.int32)
        self.first = np.zeros((rows, block), np.int32)
        self.known = np.zeros((rows, block), bool)
        for at, row in enumerate(self.order):
            if row >= real:
                self.known[at] = True
                continue
            prompt = np.asarray(prompts[row], np.int32)
            w, r = int(self.whole[at]), int(self.rest[row])
            self.tokens[at, :w] = prompt[:w]
            self.first[at, :r] = prompt[w:]
            self.known[at, :r] = True
        #: (first row, rows, padded length) of each prefill chunk; a chunk
        #: that would run past the batch's end starts earlier instead and
        #: writes some rows' first positions again, with the same values
        self.chunks = []
        at = 0
        while at < rows and self.whole[at] > 0:
            length = _round_up(int(self.whole[at]), _LENGTH_STEP)
            count = int(min(rows, max(1, prefill_tokens // length)))
            self.chunks.append((min(at, rows - count), count, length))
            at += count
        self.prefilled = int(self.whole.sum())

    def live_rows(self, index: int) -> int:
        """Real rows that still generate in block ``index``."""
        return int((self.blocks_of_row > index).sum())

    def fixed_in_block(self, index: int) -> int:
        """Positions the real rows fix in block ``index``."""
        live = self.blocks_of_row[self.order] > index
        unknown = (~self.known).sum(axis=1) if index == 0 else (
            np.full(len(live), self.known.shape[1]))
        return int(unknown[live].sum())


class _Runner(ProgramRunner):
    """One model's placed params, programs and spare caches
    (:class:`~sparkdl_tpu.transformers.generation.ProgramRunner`) at one
    generation setting."""

    def __init__(self, model, block: int, steps: int, mask_id: int):
        super().__init__(model)
        self.block, self.steps, self.mask_id = block, steps, mask_id

    def cache(self, rows: int, span: int):
        """The (key, value) cache pair; what a cache holds past a row's
        length is never read."""
        shape, dtype = self.model.cache_spec(rows, span)
        spec = jax.ShapeDtypeStruct(shape, dtype)
        return self.take_state((spec, spec))

    def prefill(self, cache_k, cache_v, tokens, whole, first_row, count,
                length):
        model, block = self.model, self.block

        def make():
            def sdar_prefill(params, cache_k, cache_v, tokens, whole,
                             first_row):
                chunk = jax.lax.dynamic_slice(
                    tokens, (first_row, 0), (count, length))
                lengths = jax.lax.dynamic_slice(whole, (first_row,), (count,))
                k, v, counts = model.prefill(params, chunk, lengths, block)
                at = (0, first_row, 0, 0, 0)
                return (jax.lax.dynamic_update_slice(cache_k, k, at),
                        jax.lax.dynamic_update_slice(cache_v, v, at), counts)

            return sdar_prefill

        args = (self.params, cache_k, cache_v, tokens, whole,
                np.int32(first_row))
        key = ("prefill", count, length, block, tuple(cache_k.shape),
               tuple(tokens.shape))
        return self.program(
            key, make, args, "sdar_prefill", donate=(1, 2))(*args)

    def block_step(self, cache_k, cache_v, prefix, start, where, tokens,
                   known, pending):
        """One block for every row.  ``pending``: the block before's final
        tokens (still on the device), which this step commits inside its
        first forward; None for a batch's first block, which is the
        program's other shape."""
        model, steps, mask_id = self.model, self.steps, self.mask_id

        def make():
            def sdar_block(params, cache_k, cache_v, prefix, start, where,
                           tokens, known, pending):
                return model.block_step(
                    params, cache_k, cache_v, prefix, start, where, tokens,
                    known, pending, steps, mask_id)

            return sdar_block

        args = (self.params, cache_k, cache_v, prefix, start, where, tokens,
                known, pending)
        key = ("block", steps, mask_id, tuple(cache_k.shape),
               tuple(tokens.shape), pending is not None)
        return self.program(
            key, make, args, "sdar_block", donate=(1, 2))(*args)


def _runner(model, block: int, steps: int, mask_id: int) -> _Runner:
    """One runner per generation setting, kept ON the model object."""
    return runner_for(
        model, "_block_diffusion_runners", (block, steps, mask_id),
        lambda: _Runner(model, block, steps, mask_id))


class BlockDiffusionTransformer(Transformer, HasInputCol, HasOutputCol):
    """Generates ``genLength`` tokens after every prompt of ``inputCol`` by
    diffusion over blocks of ``blockLength`` positions: each block starts as
    ``maskTokenId`` at its unknown positions and ``denoisingSteps`` forwards
    fix the most confident ones (greedy, static low-confidence remasking);
    the finished block enters the cache inside the next block's first
    forward.  ``denoisingSteps`` is the trade of quality against steps: a
    block costs ``denoisingSteps`` passes over the model's weights.

    ``outputCol`` gets an int32 array of ``genLength`` tokens a row.
    ``recordCol`` (optional) gets a float64 array [positions, 3] a row —
    (token, the step it was fixed at, the log-probability it was fixed
    with) for every position of the row's generated blocks, from the first
    block (which the prompt's last ``P mod blockLength`` tokens open: step
    -1) to the end of the last one (which may run past ``genLength``).
    """

    model = Param(
        "undefined", "model",
        "the model's functions and params: an object with .params, "
        ".fingerprint, .cache_spec(rows, span), .prefill(...) and "
        ".block_step(...) (sparkdl_tpu.models.sdar_moe.SdarMoeModel)",
    )
    recordCol = Param(
        "undefined", "recordCol",
        "optional column for the per-position record", TypeConverters.toString,
    )
    genLength = Param(
        "undefined", "genLength", "tokens generated a row",
        TypeConverters.toInt,
    )
    blockLength = Param(
        "undefined", "blockLength", "positions a block", TypeConverters.toInt,
    )
    denoisingSteps = Param(
        "undefined", "denoisingSteps", "denoising forwards a block",
        TypeConverters.toInt,
    )
    maskTokenId = Param(
        "undefined", "maskTokenId", "the [MASK] token's id",
        TypeConverters.toInt,
    )
    batchSize = Param(
        "undefined", "batchSize", "rows per device batch",
        TypeConverters.toInt,
    )

    @keyword_only
    def __init__(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        recordCol: Optional[str] = None,
        model: Any = None,
        genLength: int = 64,
        blockLength: int = 4,
        denoisingSteps: int = 4,
        maskTokenId: Optional[int] = None,
        batchSize: int = 64,
    ):
        super().__init__()
        self._setDefault(genLength=64, blockLength=4, denoisingSteps=4,
                         batchSize=64)
        self.setParams(**self._input_kwargs)

    @keyword_only
    def setParams(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        recordCol: Optional[str] = None,
        model: Any = None,
        genLength: int = 64,
        blockLength: int = 4,
        denoisingSteps: int = 4,
        maskTokenId: Optional[int] = None,
        batchSize: int = 64,
    ):
        given = {k: v for k, v in self._input_kwargs.items() if v is not None}
        return self._set(**given)

    def _transform(self, dataset):
        from sparkdl_tpu.obs.trace import tracer
        from sparkdl_tpu.utils.metrics import metrics

        input_col, output_col = self.getInputCol(), self.getOutputCol()
        record_col = (self.getOrDefault(self.recordCol)
                      if self.isDefined(self.recordCol) else None)
        model = self.getOrDefault(self.model)
        gen = self.getOrDefault(self.genLength)
        block = self.getOrDefault(self.blockLength)
        steps = self.getOrDefault(self.denoisingSteps)
        rows = self.getOrDefault(self.batchSize)
        if not self.isDefined(self.maskTokenId):
            raise ValueError("maskTokenId is required: the model's [MASK] id")
        mask_id = self.getOrDefault(self.maskTokenId)
        if not 1 <= steps <= block:
            raise ValueError(
                f"denoisingSteps={steps} must lie in 1..blockLength={block}: "
                "a step fixes at least one position")
        runner = _runner(model, block, steps, mask_id)

        def process_partition(part):
            prompts = part[input_col]
            out = dict(part)
            if not prompts:
                out[output_col] = []
                if record_col:
                    out[record_col] = []
                return out
            bounds = range(0, len(prompts), rows)
            with tracer.boundary(
                "generate.partition", rows=len(prompts), batches=len(bounds),
                prompt_tokens=int(sum(len(p) for p in prompts)),
                generated_tokens=len(prompts) * gen,
            ):
                tokens: List[np.ndarray] = []
                records: List[np.ndarray] = []
                for lo in bounds:
                    got = _generate_batch(
                        runner, prompts[lo:lo + rows], rows, gen)
                    tokens.extend(got[0])
                    records.extend(got[1])
            metrics.counter("sparkdl.rows_processed").add(len(prompts))
            out[output_col] = tokens
            if record_col:
                out[record_col] = records
            return out

        return dataset.mapPartitions(process_partition)


def _generate_batch(runner: _Runner, prompts, rows: int, gen: int):
    """(tokens [gen] a prompt, record [positions, 3] a prompt), in order.
    Every dispatch's ``engine.device`` span goes under the span the call
    finds open: the partition's root."""
    from sparkdl_tpu.engine import DispatchWindow
    from sparkdl_tpu.obs.trace import tracer
    from sparkdl_tpu.utils.metrics import metrics

    block, steps = runner.block, runner.steps
    root = tracer.current()
    with tracer.boundary("generate.plan", rows=len(prompts)) as span:
        plan = BatchPlan(prompts, rows, block, gen)
        span.set_attribute("chunks", len(plan.chunks))
        span.set_attribute("blocks", plan.blocks)
        span.set_attribute("span", plan.span)
    host = (plan.tokens, plan.whole, plan.first, plan.known,
            np.zeros_like(plan.first), np.zeros_like(plan.known),
            np.array([plan.longest, plan.longest], np.int32))
    with tracer.boundary("engine.place", bytes=sum(a.nbytes for a in host)):
        tokens, whole, first, known, later, unknown, where = (
            runner.place(a) for a in host)
    cache_k, cache_v = runner.cache(rows, plan.span)
    window = DispatchWindow()
    fetched: List[Any] = []

    def landed(pairs):
        for result, (kind, routed_tokens) in pairs:
            count_routing(result[-1], routed_tokens,
                          runner.model.experts_per_token,
                          runner.model.experts_held)
            if kind == "block":
                fetched.append(result)

    try:
        with tracer.boundary("generate.prefill", tokens=plan.prefilled,
                             chunks=len(plan.chunks)):
            for first_row, count, length in plan.chunks:
                cache_k, cache_v, counts = runner.prefill(
                    cache_k, cache_v, tokens, whole, first_row, count, length)
                landed(window.submit(
                    (counts,), meta=("prefill", count * length),
                    program="sdar_prefill", parent=root, rows=count))
        start = whole
        fixed = [plan.fixed_in_block(index) for index in range(plan.blocks)]
        # a block's final tokens stay on the device and ride the next
        # block's first forward into the cache; the last block's ride
        # nowhere, nobody would read them
        pending = None
        for index in range(plan.blocks):
            chained = int(pending is not None)
            with tracer.boundary(
                "generate.block", index=index, denoise_forwards=steps,
                commit_forwards=chained, fused=chained, weight_passes=steps,
                row_passes=steps * plan.live_rows(index), fixed=fixed[index],
            ):
                cache_k, cache_v, start, where, record = runner.block_step(
                    cache_k, cache_v, whole, start, where,
                    first if index == 0 else later,
                    known if index == 0 else unknown, pending)
            pending = record[0]
            landed(window.submit(
                record, meta=("block", (steps + chained) * rows * block),
                program="sdar_block", parent=root, rows=rows))
        landed(window.drain())
    finally:
        window.abandon()
    runner.keep_state((cache_k, cache_v))

    with tracer.boundary("generate.postprocess", rows=len(prompts)):
        # [rows, blocks * B] in the plan's order, then back to the input's
        record = np.stack([
            np.concatenate([np.asarray(r[i], np.float64) for r in fetched], 1)
            for i in range(3)
        ], axis=-1)
        back = np.empty(rows, np.int64)
        back[plan.order] = np.arange(rows)
        record = record[back[:len(prompts)]]
        tokens_out, records_out = [], []
        for row in range(len(prompts)):
            rest = int(plan.rest[row])
            kept = record[row, :int(plan.blocks_of_row[row]) * block]
            tokens_out.append(kept[rest:rest + gen, 0].astype(np.int32))
            records_out.append(kept)
    needed = int(plan.blocks_of_row.sum())
    # a row's last block is never read again, so never committed; every
    # other commit shared its pass over the weights with a denoising forward
    committed = needed - len(prompts)
    metrics.counter("generate.denoise_forwards").add(needed * steps)
    metrics.counter("generate.commit_forwards").add(committed)
    metrics.counter("generate.tokens_fixed").add(sum(fixed))
    return tokens_out, records_out
