"""What the stage's decoders share (``granite_hybrid``, ``solar_open2``,
``moonlight``, ``glm5``): a stack of layers of one or two kinds — a recurrent mixer
with a per-row state, grouped-query softmax attention with NO position term
through a key/value cache, latent attention through a latent cache
(``ops/mla.py``) — each followed by a sparse-expert feed-forward with a
shared expert (after leading dense layers, where a model has them), over ONE
per-row state pytree whose leaves are stacked [layers of a kind, rows, ...].

- :func:`rms_norm`; :func:`at`, :func:`read`, :func:`write` (a layer's slice
  of stacked weights and of a state leaf);
- :func:`grouped_qkv`, :func:`attention_segment`, :func:`attention_token`
  (the attention through the cache, up to but not including the output
  projection, so that a model may gate what it projects), and
  :func:`keys_scored` (how many slots a plan's segments score);
- :func:`feed_forward` (``h + r * (s * MoE(u) + Shared(u))``),
  :func:`dense_feed_forward`, :func:`swiglu`;
- :func:`layer_runs`, :class:`Mixer`, :func:`run_layers` (every layer in its
  published order: the runs of other kinds scanned, the grouped-query
  attention layers written out, leading dense layers apart);
- :func:`source_digest` (what identifies a model's mathematics in a
  program's fingerprint) and :class:`HybridModel` (what the stage takes).

Softmaxes, the router and the norms' statistics are float32; everything else
runs in the weights' dtype.
"""

from __future__ import annotations

import hashlib
import inspect
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops.moe import moe_ffn

#: stands for "not visible" in a score; finite, so that a row that sees
#: nothing softmaxes to a uniform garbage and not to NaN
NEG = -1e30
#: slots of a row's cache that a prefill segment scores at once
#: (:func:`attention_segment`, :func:`keys_scored`); chosen on the chip
#: (PERF.md section 6, PR 38)
KEY_BLOCK = 512


def rms_norm(x, gain, eps: float):
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * gain.astype(jnp.float32)).astype(x.dtype)


def at(tree, index):
    """Layer ``index`` (a traced scalar) of every stacked leaf."""
    return jax.tree_util.tree_map(
        lambda w: jax.lax.dynamic_index_in_dim(w, index, 0, keepdims=False),
        tree)


def read(stack, layer, rows):
    """Layer ``layer`` of a state leaf [layers, rows, ...]: whole, or the
    rows ``rows`` of it (an index past the last row reads the last)."""
    if rows is None:
        return jax.lax.dynamic_index_in_dim(stack, layer, 0, keepdims=False)
    return stack.at[layer, rows].get(mode="clip")


def write(stack, value, layer, rows):
    """The counterpart of :func:`read`, in place where the state is
    donated (an index past the last row writes nothing)."""
    value = value.astype(stack.dtype)
    if rows is None:
        return jax.lax.dynamic_update_index_in_dim(stack, value, layer, 0)
    return stack.at[layer, rows].set(value, mode="drop")


def grouped_qkv(lp, u, kv_heads: int, head_dim: int):
    """q [..., KV, G, dh], k and v [..., KV, dh] by the bias-free projections
    ``wq``, ``wk``, ``wv`` of ``u``; G query heads read each key/value
    head."""
    lead = u.shape[:-1]
    q = jnp.dot(u, lp["wq"])
    return (q.reshape(*lead, kv_heads, q.shape[-1] // (kv_heads * head_dim),
                      head_dim),
            jnp.dot(u, lp["wk"]).reshape(*lead, kv_heads, head_dim),
            jnp.dot(u, lp["wv"]).reshape(*lead, kv_heads, head_dim))


def _put_segments(cache, new, layer, rows, start):
    """``new`` [c, n, KV, dh] into ``cache[layer, rows[c], :, start[c] +
    arange(n)]``, in place where the cache is donated; a row past the last
    is written nowhere."""
    index = jnp.stack([jnp.full_like(rows, layer), rows, start], axis=-1)
    return jax.lax.scatter(
        cache, index, new.transpose(0, 2, 1, 3).astype(cache.dtype),
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(1, 2, 3), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 3)),
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def attention_segment(q, k, v, cache_k, cache_v, layer, rows, start,
                      scale: float):
    """A segment of ``n`` positions a row at ``start[c] + arange(n)``,
    through layer ``layer`` of the cache's leaves ([layers, rows, KV, span,
    dh]) where they lie: the segment's keys and values (``grouped_qkv``'s,
    [c, n, ...]) go into the rows ``rows`` [c] (None: all, in order; an index
    past the last row writes nothing and reads the last row) first, then
    every position sees its row's cache up to itself; scores ``q.k * scale``,
    softmax in float32.  One row at a time (``lax.map``), and of its cache
    only the blocks of ``KEY_BLOCK`` slots that hold one the segment can see:
    a loop of ``ceil((start + n) / block)`` steps, so ONE program whatever
    the ``start``, each folding float32 scores [heads, n, block] into a
    running maximum, sum and float32 accumulator (the softmax over the
    visible slots, in its online form).  Returns (the heads' outputs [c, n,
    heads * dh], cache_k, cache_v)."""
    c, n, kv, group, dh = q.shape
    span = cache_k.shape[3]
    if rows is None:
        rows = jnp.arange(c, dtype=jnp.int32)
    cache_k = _put_segments(cache_k, k, layer, rows, start)
    cache_v = _put_segments(cache_v, v, layer, rows, start)
    block = min(KEY_BLOCK, span)
    ahead = jnp.arange(n, dtype=jnp.int32)[:, None]
    within = jnp.arange(block, dtype=jnp.int32)

    def one_row(pair):
        q, row, start = pair

        def fold(j, carry):
            top, total, acc = carry
            # the span need not be a multiple of the block: its last block
            # is moved back to end with the span, and what it then shares
            # with the block before is masked
            at = jnp.minimum(j * block, span - block)
            keys, values = (
                jax.lax.dynamic_slice(
                    cache, (layer, row, 0, at, 0), (1, 1, kv, block, dh))[0, 0]
                for cache in (cache_k, cache_v))
            slots = at + within
            visible = (slots >= j * block) & (slots <= start + ahead)
            scores = jnp.einsum("nkgd,kmd->kgnm", q, keys,
                                preferred_element_type=jnp.float32)
            scores = jnp.where(visible, scores * scale, NEG)
            new_top = jnp.maximum(top, scores.max(axis=-1))
            probs = jnp.exp(scores - new_top[..., None])
            kept = jnp.exp(top - new_top)
            acc = acc * kept[..., None] + jnp.einsum(
                "kgnm,kmd->kgnd", probs.astype(values.dtype), values,
                preferred_element_type=jnp.float32)
            return new_top, total * kept + probs.sum(axis=-1), acc

        _, total, acc = jax.lax.fori_loop(
            0, (start + n + block - 1) // block, fold,
            (jnp.full((kv, group, n), NEG, jnp.float32),
             jnp.zeros((kv, group, n), jnp.float32),
             jnp.zeros((kv, group, n, dh), jnp.float32)))
        out = (acc / total[..., None]).astype(cache_v.dtype)
        return out.transpose(2, 0, 1, 3).reshape(n, -1)

    out = jax.lax.map(
        one_row, (q, jnp.minimum(rows, cache_k.shape[1] - 1), start))
    return out, cache_k, cache_v


def keys_scored(start, n: int, span: int) -> int:
    """The cache slots :func:`attention_segment` scores for segments of ``n``
    positions at the positions ``start`` under a span of ``span``: whole
    blocks up to each segment's own end (the whole span would be ``len(start)
    * span``).  Host arithmetic, for the stage's counters."""
    block = min(KEY_BLOCK, span)
    return int((-(-(np.asarray(start, np.int64) + n) // block)).sum() * block)


def attention_token(q, k, v, cache_k, cache_v, position, scale: float):
    """One position a row at ``position[r]`` (``grouped_qkv``'s of ``u`` [r,
    D]).  Returns (the heads' outputs [r, heads * dh], cache_k, cache_v)."""
    r = q.shape[0]
    rows = jnp.arange(r)
    cache_k = cache_k.at[rows, :, position].set(k)
    cache_v = cache_v.at[rows, :, position].set(v)
    scores = jnp.einsum("rkgd,rkmd->rkgm", q, cache_k,
                        preferred_element_type=jnp.float32)
    scores = scores * scale
    visible = jnp.arange(cache_k.shape[2])[None, :] <= position[:, None]
    probs = jax.nn.softmax(
        jnp.where(visible[:, None, None], scores, NEG), axis=-1)
    out = jnp.einsum("rkgm,rkmd->rkgd", probs.astype(cache_v.dtype), cache_v)
    return out.reshape(r, -1), cache_k, cache_v


def split_ffn(params):
    """(what a layer reads at its index, the experts' stacks, left whole)."""
    ffn = dict(params["ffn"])
    experts = {k: ffn.pop(k) for k in ("w_gate", "w_up", "w_down")}
    return ffn, experts


def swiglu(u, gate, up, down):
    """``(silu(u W_gate) * u W_up) W_down``, the products' float32 results
    multiplied before they are rounded back."""
    g = jnp.dot(u, gate, preferred_element_type=jnp.float32)
    v = jnp.dot(u, up, preferred_element_type=jnp.float32)
    return jnp.dot((jax.nn.silu(g) * v).astype(u.dtype), down)


def feed_forward(fp, experts, layer, h, *, eps: float, top_k: int, held,
                 residual: float = 1.0, scoring: str = "softmax",
                 routed_scale: float = 1.0):
    """``h + residual * (routed_scale * MoE(u) + Shared(u))`` with ``u =
    RMSNorm_post(h)``, and the layer's routing counts; ``fp`` is the layer's
    own slice (its ``router_bias``, where it has one, is the router's
    selection bias), the experts' weights the whole stack
    (``moe_ffn(stack_index=layer)``).  ``routed_scale`` multiplies the
    routed pairs' float32 weights, not the shared expert."""
    lead, d = h.shape[:-1], h.shape[-1]
    u = rms_norm(h, fp["post_norm"], eps).reshape(-1, d)
    routed, counts = moe_ffn(
        u, fp["router"], experts, top_k=top_k, experts_held=held,
        norm_topk=True, stack_index=layer, scoring=scoring,
        select_bias=fp.get("router_bias"), scale=routed_scale)
    shared = swiglu(u, fp["shared_gate"], fp["shared_up"], fp["shared_down"])
    out = (routed + shared).reshape(*lead, d)
    return h + (residual * out).astype(h.dtype), counts


def dense_feed_forward(dp, h, *, eps: float):
    """A leading dense layer's ``h + SwiGLU(RMSNorm_post(h))`` (``dp``: the
    layer's ``post_norm``, ``gate``, ``up``, ``down``)."""
    u = rms_norm(h, dp["post_norm"], eps)
    return h + swiglu(u, dp["gate"], dp["up"], dp["down"]).astype(h.dtype)


def layer_runs(kinds):
    """``(kind, first layer, first of its kind, count)`` of every run of
    consecutive layers of one kind."""
    out, seen = [], {}
    for index, kind in enumerate(kinds):
        if out and out[-1][0] == kind:
            out[-1][3] += 1
        else:
            out.append([kind, index, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(run) for run in out]


class Mixer(NamedTuple):
    """A kind of layer's mixer in :func:`run_layers`: the names of the state
    leaves a layer of the kind owns ([layers of the kind, rows, ...]) and
    ``mix``, the mixer at the caller's shape (a segment or a token) on the
    layer's weights: ``mix(lp, u, *slices) -> (out, *new slices)`` on the
    layer's rows of each leaf, read before it and written after it; or, where
    ``in_place``, ``mix(lp, u, *leaves, layer, rows) -> (out, *leaves)`` on
    the leaves whole, of which it reads and writes what it needs itself."""

    leaves: tuple
    mix: Callable
    in_place: bool = False


def run_layers(params, kinds, x, state, rows, mixers, ffn_of, *, eps: float,
               residual: float = 1.0, dense_of=None):
    """Every layer over ``x``, in the order of ``kinds``.

    ``mixers[kind]`` is the kind's :class:`Mixer`, run on the layer's weights
    ``params[kind]`` at its index; ``"attention"`` layers are written out,
    every run of another kind is one ``lax.scan`` over its indices.
    ``ffn_of(fp, experts, layer, h)`` is the feed-forward
    (:func:`feed_forward` with the model's settings).  ``dense_of(dp, h)``,
    where given, is the feed-forward of the leading dense layers, one a
    layer of ``params["dense"]`` (stacked [k, ...]: the first k layers);
    their routing counts are rows of zeros, and the expert layers' stacks
    begin at layer k.  ``x`` is about the rows ``rows`` of ``state`` (None:
    all of them, in order); a layer reads and writes only its own slice of
    the state, so no copy of more than one layer's rows is ever alive.
    Returns (x, state, counts [L, E])."""
    ffn, experts = split_ffn(params)
    dense = 0 if dense_of is None else params["dense"]["post_norm"].shape[0]
    state = dict(state)
    counts = []

    def one_layer(kind, lp, x, held, of_kind, layer, leading=False):
        mixer = mixers[kind]
        u = rms_norm(x, lp["in_norm"], eps)
        if mixer.in_place:
            out, *held = mixer.mix(lp, u, *held, of_kind, rows)
        else:
            out, *new = mixer.mix(
                lp, u, *(read(leaf, of_kind, rows) for leaf in held))
            held = [write(leaf, value, of_kind, rows)
                    for leaf, value in zip(held, new)]
        h = x + (residual * out).astype(x.dtype)
        if leading:
            routed = jnp.zeros((ffn["router"].shape[-1],), jnp.int32)
            return (dense_of(at(params["dense"], layer), h), tuple(held),
                    routed)
        if dense:
            layer = layer - dense
        y, routed = ffn_of(at(ffn, layer), experts, layer, h)
        return y, tuple(held), routed

    runs = []
    for kind, first, of_kind, count in layer_runs(kinds):
        # a run is cut where the leading dense layers end
        cut = min(max(dense - first, 0), count)
        if cut:
            runs.append((kind, first, of_kind, cut, True))
        if count - cut:
            runs.append((kind, first + cut, of_kind + cut, count - cut, False))
    for kind, first, of_kind, count, leading in runs:
        names = mixers[kind].leaves
        held = tuple(state[name] for name in names)
        if kind == "attention":
            for offset in range(count):
                index = of_kind + offset
                lp = jax.tree_util.tree_map(lambda w: w[index], params[kind])
                x, held, routed = one_layer(
                    kind, lp, x, held, index, jnp.int32(first + offset),
                    leading)
                counts.append(routed[None])
        else:
            def scanned(carry, index, kind=kind, leading=leading):
                x, held = carry
                of_kind, layer = index
                x, held, routed = one_layer(
                    kind, at(params[kind], of_kind), x, held, of_kind, layer,
                    leading)
                return (x, held), routed

            (x, held), routed = jax.lax.scan(
                scanned, (x, held),
                (jnp.arange(of_kind, of_kind + count, dtype=jnp.int32),
                 jnp.arange(first, first + count, dtype=jnp.int32)))
            counts.append(routed)
        state.update(zip(names, held))
    return x, state, jnp.concatenate(counts, axis=0)


def source_digest(*modules) -> str:
    """Identifies a model's mathematics in a program's fingerprint: an
    executable kept on disk must not outlive a change to any module it
    compiled (this one and ``ops/moe.py`` among them, always)."""
    from sparkdl_tpu.ops import moe

    text = "".join(inspect.getsource(module) for module in (
        moe, inspect.getmodule(source_digest), *modules))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


class HybridModel:
    """The ``model`` of an
    :class:`~sparkdl_tpu.transformers.ar_generate.AutoregressiveTransformer`:
    a decoder's functions bound to a config, and the params they run on.  The
    params are arguments of every program, never constants in one, so two
    models of one config share their executables.  A model's module gives
    ``name`` (its programs are ``<name>_prefill`` and ``<name>_decode``),
    ``family`` (the fingerprint's first word), ``config_class``,
    ``recurrent_leaves`` (the state leaves that ``ssm.state_bytes`` counts)
    and ``functions``: the module's ``_source_digest``, ``state_spec``,
    ``prefill`` and ``decode``."""

    name = family = config_class = functions = None
    recurrent_leaves = ()
    #: the state leaves that ``mla.latent_cache_bytes`` counts
    latent_leaves = ()
    #: the state leaves that ``dsa.index_cache_bytes`` counts (a learned
    #: sparse attention's indexer keys)
    index_leaves = ()
    #: a prefill dispatch may take several consecutive segments of one row
    #: (its programs write every pair's caches before any pair reads, and
    #: the per-row state is caches alone)
    packs_segments = False
    #: leading layers whose feed-forward is dense: their rows of a
    #: program's routing counts are zeros, and the stage skips them
    dense_layers = 0
    #: ``{"prefill": ..., "decode": ...}``: which form of its attention each
    #: program takes, where the attention has more than one published form
    #: (MLA's absorbed or decompressed; the stage's spans carry it as
    #: ``attention_path``)
    attention_paths = None

    def __init__(self, config, params):
        self.config = (
            config if isinstance(config, self.config_class)
            else self.config_class.from_dict(config)
        )
        self.params = params

    @property
    def fingerprint(self) -> str:
        return f"{self.family}:{self.functions._source_digest()}:{self.config}"

    @property
    def experts_per_token(self) -> int:
        return self.config.num_experts_per_tok

    @property
    def experts_held(self):
        return self.config.held

    def state_spec(self, rows: int, span: int):
        return self.functions.state_spec(
            self.config, rows, span, self.params["embed"].dtype)

    def recurrent_bytes(self, rows: int) -> int:
        """Bytes of recurrent state (``recurrent_leaves``) that ``rows`` rows
        hold on the device."""
        return self._bytes(self.recurrent_leaves, rows, 1)

    def latent_bytes(self, rows: int, span: int) -> int:
        """Bytes of latent cache (``latent_leaves``) that ``rows`` rows hold
        on the device under a span of ``span`` positions."""
        return self._bytes(self.latent_leaves, rows, span)

    def index_bytes(self, rows: int, span: int) -> int:
        """Bytes of indexer keys (``index_leaves``) that ``rows`` rows hold
        on the device under a span of ``span`` positions."""
        return self._bytes(self.index_leaves, rows, span)

    def slots_read(self, positions, span: int) -> int:
        """The cache slots a decode dispatch's attention reads for rows that
        take in ``positions`` [steps, rows] under a span of ``span`` slots:
        the whole span a row and step (:func:`attention_token`)."""
        return int(np.size(positions)) * span

    def _bytes(self, leaves, rows: int, span: int) -> int:
        spec = self.state_spec(rows, span)
        return sum(
            spec[name].size * spec[name].dtype.itemsize for name in leaves)

    def prefill(self, params, state, tokens, rows, start, lengths):
        return self.functions.prefill(
            params, self.config, state, tokens, rows, start, lengths)

    def decode(self, params, state, steps: int):
        return self.functions.decode(params, self.config, state, steps)
