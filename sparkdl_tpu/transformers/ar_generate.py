"""AutoregressiveTransformer — fixed-length greedy generation, one token a row
a decode step, over a DataFrame column of prompts (arrays of token ids), for
a model whose per-row state is a pytree that stays on the device: recurrent
states beside a growing key/value cache
(:class:`~sparkdl_tpu.models.granite_hybrid.GraniteHybridModel`,
:class:`~sparkdl_tpu.models.solar_open2.SolarOpen2Model`).

A batch is one state, donated from dispatch to dispatch, and two programs:

- **prefill**, ONE fixed shape whatever the prompts' lengths: a dispatch
  takes ``SEGMENT_ROWS`` (row, segment) pairs — ``SEGMENT_LENGTH`` tokens of
  a row's prompt each, the last of a row partly pads — and carries each
  row's state from its segment before.  The pairs go segment by segment
  (every row's first, then every longer row's second, ...), so rows of
  different lengths finish at different dispatches and a pad costs at most
  the rest of one segment; a dispatch's spare pairs name no row.  A row's
  state comes back as of ITS last real token; prefill overwrites every
  row's state (the dummy rows that pad a partition's last batch are prompts
  of one token), so nothing of the batch before is ever read.  The row's
  first generated token is the likeliest after its last prompt token;
- **decode**, ``DECODE_STEPS`` greedy tokens a row a dispatch (and one more
  shape for what ``genLength - 1`` leaves over): every row takes in the
  token chosen last and chooses the next.

Between the batch's placement and its last fetch only token ids, the record
and scalars cross to the host.  The weights are program ARGUMENTS, placed
once per model object; an executable holds no weight constants.

Spans (``obs.trace`` boundaries, made whether or not tracing is enabled):
``ar_generate.partition`` (root; ``model``: the model's ``.name``, ``rows``,
``batches``, ``prompt_tokens``, ``generated_tokens``) > ``ar_generate.plan``, ``engine.place``,
``ar_generate.prefill`` (``tokens``, ``pad_tokens``, ``segments``,
``keys_scored``, ``keys_spanned``; ``rule_chunks``, ``rule_chunks_fused``
where the model has a chunked rule),
``ar_generate.decode`` (one a dispatch; ``steps``, ``rows``),
``engine.fetch_wait``, ``ar_generate.postprocess``; and, on the engine's
watcher thread, one ``engine.device`` a dispatch (``program``:
``<model>_prefill`` / ``<model>_decode``; ``rows``, ``steps``,
``queued_ms``): when the device computed it.  ``keys_scored`` counts the
cache slots the dispatches' pairs, spare ones too, score in an attention
layer (whole blocks up to each pair's own end,
:func:`~sparkdl_tpu.models.hybrid.keys_scored`), ``keys_spanned`` pairs x
span: what scoring the whole span would come to.  ``rule_chunks`` counts
the (row, chunk) pairs the dispatches' layers put through a chunked
recurrence and ``rule_chunks_fused`` those that went through its kernel,
both as the model's ``rule_chunks(pairs, segment)`` gives them for a dispatch
(a model without that method gets neither).  Counters:
``ar_generate.prefill_tokens`` (real prompt tokens),
``ar_generate.prefill_pad_tokens`` (positions pushed through the layers that
were pads, spare pairs or dummy rows), ``ar_generate.decode_steps`` and
``ar_generate.decode_dispatches`` (a batch),
``ar_generate.decode_expert_reads`` (the (step, layer, held expert) triples
that got a token: the expert matrices a batch's decode steps had to read),
``ar_generate.tokens_generated`` (real rows), ``ssm.state_bytes`` (bytes of recurrent state a batch holds on
the device), and ``moe.tokens_routed``, ``moe.pairs_held`` (the routed pairs
whose expert is held here), ``moe.tokens_dropped``, ``moe.expert_load_max``,
``moe.expert_load_mean`` (from the routing counts that come back with every
program's result).
"""

from __future__ import annotations

from typing import Any, List, Optional

import numpy as np

import jax.numpy as jnp

from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.models.hybrid import keys_scored
from sparkdl_tpu.param.base import Param, TypeConverters, keyword_only
from sparkdl_tpu.param.shared import HasInputCol, HasOutputCol
from sparkdl_tpu.transformers.generation import (
    ProgramRunner,
    count_routing,
    runner_for,
)

#: the one shape of a prefill dispatch: tokens of one row's prompt a pair, and
#: pairs a dispatch.  Longer segments pad more (a row's last one is half
#: empty on average); fewer tokens a dispatch feed the experts fewer rows each
#: and re-read the weights more often; more pairs a dispatch leave the tail,
#: where only the longest rows still have segments, mostly empty (PERF.md
#: section 6, PR 35: 32 pairs padded 43% of the positions, 16 pad 10%)
SEGMENT_LENGTH = 128
SEGMENT_ROWS = 16
#: tokens a row a decode dispatch
DECODE_STEPS = 8
#: the prompts' part of the cache's span is a multiple of this (one compile
#: per distinct span, so the step is coarse), the generated part of
#: ``_LENGTH_STEP`` (the TPU tiles the span by it)
SPAN_STEP = 512
_LENGTH_STEP = 128


def _round_up(n: int, step: int) -> int:
    return -(-int(n) // step) * step


class SegmentPlan:
    """The layout of one batch's prefill: which (row, segment) pairs go in
    which dispatch."""

    def __init__(self, prompts: List[np.ndarray], rows: int, segment: int,
                 count: int, gen: int):
        real = len(prompts)
        # a dummy row (batch padding) is a prompt of one token
        prompts = [np.asarray(p, np.int32) for p in prompts] + [
            np.zeros(1, np.int32)] * (rows - real)
        lengths = np.array([len(p) for p in prompts], np.int64)
        if lengths.min() < 1:
            raise ValueError(
                "an empty prompt has no last token to generate after")
        self.real_tokens = int(lengths[:real].sum())
        self.span = (_round_up(max(int(lengths.max()), segment), SPAN_STEP)
                     + _round_up(gen, _LENGTH_STEP))
        #: a dispatch's (tokens [count, segment], rows, start, lengths) and
        #: the (slot, row) of the rows whose LAST segment it holds
        self.dispatches = []
        done = np.zeros(rows, np.int64)  # segments a row has behind it
        left = -(-lengths // segment)
        while left.any():
            # a row's segments follow each other, so a dispatch takes one of
            # each row at most: the rows with most still to go first (ties
            # to the lower row), so that the longest row, whose segments no
            # packing can shorten, never waits
            group = [int(row) for row in np.argsort(-left, kind="stable")[:count]
                     if left[row]]
            tokens = np.zeros((count, segment), np.int32)
            index = np.full(count, rows, np.int32)  # past the last: nobody
            start = np.zeros(count, np.int32)
            held = np.zeros(count, np.int32)
            last = []
            for slot, row in enumerate(group):
                at = int(done[row]) * segment
                part = prompts[row][at:at + segment]
                tokens[slot, :len(part)] = part
                index[slot], start[slot], held[slot] = row, at, len(part)
                done[row] += 1
                left[row] -= 1
                if not left[row]:
                    last.append((slot, row))
            self.dispatches.append(((tokens, index, start, held), last))
        self.pad_tokens = (
            len(self.dispatches) * count * segment - self.real_tokens)
        starts = np.concatenate(
            [start for (_, _, start, _), _ in self.dispatches])
        self.keys_scored = keys_scored(starts, segment, self.span)
        self.keys_spanned = len(starts) * self.span


class _Runner(ProgramRunner):
    """One model's placed params, programs and spare states
    (:class:`~sparkdl_tpu.transformers.generation.ProgramRunner`) at one
    prefill shape."""

    def __init__(self, model, segment: int, count: int):
        super().__init__(model)
        self.segment, self.count = segment, count

    def prefill(self, state, tokens, rows, start, lengths):
        model = self.model

        def make():
            def prefill(params, state, tokens, rows, start, lengths):
                state, logp, counts = model.prefill(
                    params, state, tokens, rows, start, lengths)
                return (state, jnp.argmax(logp, axis=-1).astype(jnp.int32),
                        jnp.max(logp, axis=-1), counts)

            prefill.__name__ = f"{model.name}_prefill"
            return prefill

        args = (self.params, state, tokens, rows, start, lengths)
        key = ("prefill", tuple(tokens.shape), self.shapes_of(state))
        return self.program(
            key, make, args, f"{model.name}_prefill", donate=(1,))(*args)

    def decode(self, state, steps: int):
        model = self.model

        def make():
            def decode(params, state):
                return model.decode(params, state, steps)

            decode.__name__ = f"{model.name}_decode"
            return decode

        args = (self.params, state)
        key = ("decode", steps, self.shapes_of(state))
        return self.program(
            key, make, args, f"{model.name}_decode", donate=(1,))(*args)


def _runner(model, segment: int, count: int) -> _Runner:
    """One runner per prefill shape, kept ON the model object."""
    return runner_for(
        model, "_ar_generate_runners", (segment, count),
        lambda: _Runner(model, segment, count))


class AutoregressiveTransformer(Transformer, HasInputCol, HasOutputCol):
    """Generates ``genLength`` tokens after every prompt of ``inputCol``,
    greedily, one token a row a decode step.

    ``outputCol`` gets an int32 array of ``genLength`` tokens a row.
    ``recordCol`` (optional) gets a float64 array [genLength, 2] a row:
    (token, the float32 log-probability it was chosen with).
    """

    model = Param(
        "undefined", "model",
        "the model's functions and params: an object with .params, .name, "
        ".fingerprint, .experts_held, .state_spec(rows, span), "
        ".recurrent_bytes(rows), "
        ".experts_per_token, .prefill(...) and .decode(...), and "
        ".rule_chunks(pairs, segment) where it has a chunked rule "
        "(sparkdl_tpu.models.granite_hybrid.GraniteHybridModel, "
        "sparkdl_tpu.models.solar_open2.SolarOpen2Model)",
    )
    recordCol = Param(
        "undefined", "recordCol",
        "optional column for the per-token record", TypeConverters.toString,
    )
    genLength = Param(
        "undefined", "genLength", "tokens generated a row",
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
        batchSize: int = 64,
    ):
        super().__init__()
        self._setDefault(genLength=64, batchSize=64)
        self.setParams(**self._input_kwargs)

    @keyword_only
    def setParams(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        recordCol: Optional[str] = None,
        model: Any = None,
        genLength: int = 64,
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
        rows = self.getOrDefault(self.batchSize)
        if min(gen, rows) < 1:
            raise ValueError("genLength and batchSize must be at least 1")
        # a segment's keys and values are written whole into the cache
        assert SPAN_STEP % SEGMENT_LENGTH == 0
        runner = _runner(model, SEGMENT_LENGTH, min(SEGMENT_ROWS, rows))

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
                "ar_generate.partition", model=model.name,
                rows=len(prompts), batches=len(bounds),
                prompt_tokens=int(sum(len(p) for p in prompts)),
                generated_tokens=len(prompts) * gen,
            ):
                tokens: List[np.ndarray] = []
                records: List[np.ndarray] = []
                for lo in bounds:
                    got = _generate_batch(
                        runner, prompts[lo:lo + rows], rows, gen, DECODE_STEPS)
                    tokens.extend(got[0])
                    records.extend(got[1])
            metrics.counter("sparkdl.rows_processed").add(len(prompts))
            out[output_col] = tokens
            if record_col:
                out[record_col] = records
            return out

        return dataset.mapPartitions(process_partition)


def _generate_batch(runner: _Runner, prompts, rows: int, gen: int,
                    steps: int):
    """(tokens [gen] a prompt, record [gen, 2] a prompt), in order.  Every
    dispatch's ``engine.device`` span goes under the span the call finds
    open: the partition's root."""
    from sparkdl_tpu.engine import DispatchWindow
    from sparkdl_tpu.obs.trace import tracer
    from sparkdl_tpu.utils.metrics import metrics

    model = runner.model
    root = tracer.current()
    with tracer.boundary("ar_generate.plan", rows=len(prompts)) as span:
        plan = SegmentPlan(prompts, rows, runner.segment, runner.count, gen)
        span.set_attribute("segments", len(plan.dispatches))
        span.set_attribute("span", plan.span)
    host = [arrays for arrays, _ in plan.dispatches]
    with tracer.boundary(
            "engine.place", bytes=sum(a.nbytes for group in host for a in group)):
        placed = [tuple(runner.place(a) for a in group) for group in host]
    state = runner.take_state(model.state_spec(rows, plan.span))
    window = DispatchWindow()
    first = np.zeros((rows, 2), np.float64)
    later: List[Any] = []

    lo, hi = model.experts_held
    reads = 0

    def landed(pairs):
        nonlocal reads
        for result, (last, routed_tokens) in pairs:
            counts = np.asarray(result[-1])
            if last is None:  # a decode dispatch: counts [steps, L, E]
                reads += int((counts[..., lo:hi] > 0).sum())
                counts = counts.sum(axis=0)
                later.append(result)
            for slot, row in last or ():
                first[row] = result[0][slot], result[1][slot]
            count_routing(counts, routed_tokens, model.experts_per_token,
                          (lo, hi))

    rule = {}
    if hasattr(model, "rule_chunks"):
        chunks, fused = model.rule_chunks(runner.count, runner.segment)
        rule = dict(rule_chunks=chunks * len(plan.dispatches),
                    rule_chunks_fused=fused * len(plan.dispatches))
    try:
        with tracer.boundary(
                "ar_generate.prefill", tokens=plan.real_tokens,
                pad_tokens=plan.pad_tokens, segments=len(plan.dispatches),
                keys_scored=plan.keys_scored, keys_spanned=plan.keys_spanned,
                **rule):
            for arrays, (_, last) in zip(placed, plan.dispatches):
                state, token, logprob, counts = runner.prefill(state, *arrays)
                landed(window.submit(
                    (token, logprob, counts),
                    meta=(last, runner.count * runner.segment),
                    program=f"{model.name}_prefill", parent=root,
                    rows=runner.count))
        dispatches, left = 0, gen - 1
        while left:
            now = min(steps, left)
            with tracer.boundary("ar_generate.decode", steps=now, rows=rows):
                state, token, logprob, counts = runner.decode(state, now)
            landed(window.submit(
                (token, logprob, counts), meta=(None, now * rows),
                program=f"{model.name}_decode", parent=root, rows=rows,
                steps=now))
            dispatches, left = dispatches + 1, left - now
        landed(window.drain())
    finally:
        window.abandon()
    runner.keep_state(state)

    with tracer.boundary("ar_generate.postprocess", rows=len(prompts)):
        # [rows, gen, 2]: the token after the prompt, then the decoded ones
        record = np.concatenate([first[:, None]] + [
            np.stack([np.asarray(r[0], np.float64),
                      np.asarray(r[1], np.float64)], axis=-1)
            for r in later], axis=1)
        records_out = [record[row] for row in range(len(prompts))]
        tokens_out = [r[:, 0].astype(np.int32) for r in records_out]
    metrics.counter("ar_generate.prefill_tokens").add(plan.real_tokens)
    metrics.counter("ar_generate.prefill_pad_tokens").add(plan.pad_tokens)
    metrics.counter("ar_generate.decode_steps").add(gen - 1)
    metrics.counter("ar_generate.decode_dispatches").add(dispatches)
    metrics.counter("ar_generate.decode_expert_reads").add(reads)
    metrics.counter("ar_generate.tokens_generated").add(len(prompts) * gen)
    metrics.counter("ssm.state_bytes").add(model.recurrent_bytes(rows))
    return tokens_out, records_out
