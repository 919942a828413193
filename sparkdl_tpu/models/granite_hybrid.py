"""Granite 4.0-H (``model_type: granitemoehybrid``, IBM granite-4.0-h-small):
a state-space / attention hybrid with a sparse-expert feed-forward and a
shared expert in every layer, as pure functions over a params pytree and a
per-row state pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g`` (statistics in float32).
``x_0 = embedding_multiplier * Embed[ids]``.  For layer i of kind
``layer_types[i]``, with ``r = residual_multiplier``::

    h = x + r * Mixer(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + r * (MoE(u) + Shared(u))

and ``logits = RMSNorm_f(y) Embed^T / logits_scaling`` (tied, float32).

- **Mamba-2 mixer** (``d_inner = mamba_expand * hidden = heads * d_head``; N
  = ``mamba_d_state``; one group; conv width ``C = d_inner + 2 N``):
  ``[z | xBC | dt] = u W_in`` (no bias); ``xBC`` through the depthwise causal
  conv of width ``mamba_d_conv`` and silu; ``[x | B | C] = xBC``; per head
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t (outer) B_t`` (float32), ``y_t = S_t C_t + D x_t``;
  gated norm over all of d_inner at once: ``g = y * silu(z)``, ``o =
  RMSNorm(g)``; out ``= o W_out`` (:mod:`sparkdl_tpu.ops.ssm`).
- **Attention mixer**: grouped-query softmax attention, bias-free
  projections, NO position term (``position_embedding_type: nope``), scores
  ``q.k * attention_multiplier``, causal, softmax in float32.
- **MoE**: :func:`sparkdl_tpu.ops.moe.moe_ffn` — Granite's gate (softmax over
  the top-k LOGITS) is ``route(..., norm_topk=True)``'s numbers (the top-k of
  the softmax over all, renormalised: ``exp(l_e) / sum_top exp(l) = p_e /
  sum_top p``); an expert's ``input_linear`` halves are ``w_gate``, ``w_up``.
  **Shared**: ``(silu(u W_a) * u W_b) W_c``, added for every token.

Two kinds of layer in one stack: the Mamba mixers are stacked on a leading
axis of their own (``params["mamba"]``), the attention mixers on theirs, and
every RUN of consecutive Mamba layers is one ``lax.scan`` over its indices
(the published pattern, five Mamba, one attention, four Mamba, compiles two
scan bodies and one attention layer).  The feed-forwards of ALL layers are one
stack, read at the layer's index; the experts' weights are handed to the
grouped product whole (``moe_ffn(stack_index=...)``).

The per-row state is ONE pytree, donated from program to program:

- ``conv`` [M, rows, K-1, C]: each Mamba layer's last K-1 conv inputs;
- ``ssm`` [M, rows, heads, d_head, N] float32: its recurrent state;
- ``k``, ``v`` [A, rows, KV, span, head_dim]: the attention layers' cache;
- ``position`` [rows]: the tokens a row has taken in so far;
- ``token`` [rows]: the token chosen last, which the next step takes in.

Entry points, all with fixed shapes:

- :func:`forward_logits` — whole sequences from an empty state (tests);
- :func:`prefill` — ONE fixed-shape segment of some rows' prompts against
  their carried state; rows of a segment are named by index, have their own
  start and their own number of real positions, and come back with the state
  as of their own last real token;
- :func:`decode_step` / :func:`decode` — one / several greedy tokens a row.

Softmaxes, the router, the norms' statistics, the decays and the recurrent
state are float32; everything else runs in the weights' dtype
(``computeDtype``, bfloat16 on the chip).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.hybrid import (
    HybridModel,
    Mixer,
    attention_segment,
    attention_token,
    feed_forward,
    grouped_qkv,
    layer_runs,
    rms_norm,
    run_layers,
    source_digest,
)
from sparkdl_tpu.ops import ssm


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """The published ``config.json`` keys the mathematics reads, and the
    chip's share (``model-configs`` section 4): ``num_local_experts`` counts
    the experts HELD here, ``experts_held`` says which of the
    ``routed_experts`` the router scores they are."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    num_local_experts: int
    num_experts_per_tok: int
    intermediate_size: int
    shared_intermediate_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    attention_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    head_dim: Optional[int] = None
    routed_experts: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "GraniteHybridConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in config.items() if k in names}
        kept["layer_types"] = tuple(
            config["layer_types"][:config["num_hidden_layers"]])
        kept.setdefault("routed_experts", config.get("published", {}).get(
            "num_local_experts"))
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    def __post_init__(self):
        if self.mamba_expand * self.hidden_size != self.inner:
            raise ValueError(
                f"mamba_expand * hidden_size = "
                f"{self.mamba_expand * self.hidden_size} is not mamba_n_heads"
                f" * mamba_d_head = {self.inner}")
        if self.mamba_n_groups != 1:
            raise NotImplementedError("one group of B and C only")
        if set(self.layer_types) - {"mamba", "attention"}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types does not name every layer")
        lo, hi = self.held
        if hi - lo != self.num_local_experts:
            raise ValueError(
                f"experts_held {lo, hi} is not the {self.num_local_experts} "
                "experts num_local_experts says are held here")

    @property
    def routed(self) -> int:
        return self.routed_experts or self.num_local_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.routed)

    @property
    def inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self) -> int:
        return self.inner + 2 * self.mamba_d_state

    @property
    def attention_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def runs(self):
        """``(kind, first layer, first of its kind, count)`` of every run of
        consecutive layers of one kind."""
        return layer_runs(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)


def param_shapes(cfg: GraniteHybridConfig) -> Dict[str, Any]:
    """The params pytree's shapes (see the module's docstring)."""
    m, a, n = cfg.count("mamba"), cfg.count("attention"), cfg.num_hidden_layers
    d, dh = cfg.hidden_size, cfg.attention_head_dim
    q, kv = cfg.num_attention_heads * dh, cfg.num_key_value_heads * dh
    f, fs, held = (cfg.intermediate_size, cfg.shared_intermediate_size,
                   cfg.num_local_experts)
    return {
        "embed": (cfg.vocab_size, d),
        "mamba": {
            "in_norm": (m, d),
            "w_in": (m, d, cfg.inner + cfg.conv_width + cfg.mamba_n_heads),
            "conv_w": (m, cfg.conv_width, cfg.mamba_d_conv),
            "conv_b": (m, cfg.conv_width),
            "dt_bias": (m, cfg.mamba_n_heads), "a_log": (m, cfg.mamba_n_heads),
            "d": (m, cfg.mamba_n_heads), "gate_norm": (m, cfg.inner),
            "w_out": (m, cfg.inner, d),
        },
        "attention": {
            "in_norm": (a, d), "wq": (a, d, q), "wk": (a, d, kv),
            "wv": (a, d, kv), "wo": (a, q, d),
        },
        "ffn": {
            "post_norm": (n, d), "router": (n, d, cfg.routed),
            "w_gate": (n, held, d, f), "w_up": (n, held, d, f),
            "w_down": (n, held, f, d),
            "shared_gate": (n, d, fs), "shared_up": (n, d, fs),
            "shared_down": (n, fs, d),
        },
        "final_norm": (d,),
    }


def init_params(cfg: GraniteHybridConfig, seed: int = 0, dtype=jnp.bfloat16,
                std: float = 0.02):
    """Seeded random params: normal(0, std) matrices, gains of one, ``D`` =
    1, ``A_log = log(U[1, 16])``, ``dt_bias`` the inverse softplus of a
    log-uniform step in [0.001, 0.1], the conv U(+-1/sqrt(K)) (the Mamba-2
    reference initialisation); the three per-head vectors float32."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    bound = cfg.mamba_d_conv ** -0.5

    def draw(name, shape, key):
        if "norm" in name:
            return jnp.ones(shape, dtype)
        if name == "d":
            return jnp.ones(shape, jnp.float32)
        if name in ("conv_w", "conv_b"):
            return jax.random.uniform(
                key, shape, jnp.float32, -bound, bound).astype(dtype)
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1, 16))
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    made = [draw(path[-1].key, shape, key)
            for (path, shape), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, made)


def state_spec(cfg: GraniteHybridConfig, rows: int, span: int, dtype):
    """The state pytree as ``jax.ShapeDtypeStruct``s: ``rows`` rows, an
    attention cache of ``span`` positions."""
    m, a = cfg.count("mamba"), cfg.count("attention")
    cache = (a, rows, cfg.num_key_value_heads, span, cfg.attention_head_dim)
    spec = jax.ShapeDtypeStruct
    return {
        "conv": spec((m, rows, cfg.mamba_d_conv - 1, cfg.conv_width), dtype),
        "ssm": spec((m, rows, cfg.mamba_n_heads, cfg.mamba_d_head,
                     cfg.mamba_d_state), jnp.float32),
        "k": spec(cache, dtype), "v": spec(cache, dtype),
        "position": spec((rows,), jnp.int32),
        "token": spec((rows,), jnp.int32),
    }


def empty_state(cfg: GraniteHybridConfig, rows: int, span: int, dtype):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        state_spec(cfg, rows, span, dtype))


# -- the pieces -------------------------------------------------------------

def _feed_forward(cfg, fp, experts, layer, h):
    """``h + r * (MoE(u) + Shared(u))`` with ``u = RMSNorm_post(h)``, and
    the layer's routing counts; ``fp`` is the layer's own slice."""
    return feed_forward(
        fp, experts, layer, h, eps=cfg.rms_norm_eps,
        top_k=cfg.num_experts_per_tok, held=cfg.held,
        residual=cfg.residual_multiplier)


def _mamba_inputs(cfg, lp, u):
    """``(z, xBC, dt)`` of the in-projection of ``u`` [..., D]."""
    proj = jnp.dot(u, lp["w_in"])
    inner, conv = cfg.inner, cfg.conv_width
    return (proj[..., :inner], proj[..., inner:inner + conv],
            proj[..., inner + conv:])


def _mamba_split(cfg, lp, xbc, dt):
    """``(x [..., H, P], B, C, dt float32, A)`` after the conv."""
    inner, n = cfg.inner, cfg.mamba_d_state
    x = xbc[..., :inner].reshape(
        *xbc.shape[:-1], cfg.mamba_n_heads, cfg.mamba_d_head)
    dt = jax.nn.softplus(
        dt.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(lp["a_log"].astype(jnp.float32))
    return x, xbc[..., inner:inner + n], xbc[..., inner + n:], dt, a


def _mamba_out(cfg, lp, y, x, z):
    """``D`` skip, gated norm over all of d_inner, out-projection: ``y`` and
    ``x`` [..., H, P] (``y`` float32), ``z`` [..., d_inner]."""
    y = y + lp["d"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
    g = y.reshape(*z.shape) * jax.nn.silu(z.astype(jnp.float32))
    scale = jax.lax.rsqrt(
        jnp.mean(g * g, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    o = (g * scale * lp["gate_norm"].astype(jnp.float32)).astype(z.dtype)
    return jnp.dot(o, lp["w_out"])


def _mamba_segment(cfg, lp, u, window, state, lengths):
    """The mixer over a segment ``u`` [c, n, D] of rows with ``lengths`` real
    positions each.  Returns (out, conv window, recurrent state)."""
    z, xbc, dt = _mamba_inputs(cfg, lp, u)
    xbc, window = ssm.causal_conv(
        xbc, window, lp["conv_w"], lp["conv_b"], lengths)
    x, b, c, dt, a = _mamba_split(cfg, lp, xbc, dt)
    real = jnp.arange(u.shape[1])[None, :] < lengths[:, None]
    dt = jnp.where(real[..., None], dt, 0.0)  # a pad leaves the state alone
    y, state = ssm.ssd_chunked(x, dt, a, b, c, state, cfg.mamba_chunk_size)
    return _mamba_out(cfg, lp, y, x, z), window, state


def _mamba_token(cfg, lp, u, window, state):
    """The mixer on one position a row, ``u`` [r, D]."""
    z, xbc, dt = _mamba_inputs(cfg, lp, u)
    xbc, window = ssm.conv_update(xbc, window, lp["conv_w"], lp["conv_b"])
    x, b, c, dt, a = _mamba_split(cfg, lp, xbc, dt)
    y, state = ssm.ssm_update(x, dt, a, b, c, state)
    return _mamba_out(cfg, lp, y, x, z), window, state


def _attention_segment(cfg, lp, u, cache_k, cache_v, layer, rows, start):
    """The mixer over a segment ``u`` [c, n, D] at positions ``start[c] +
    arange(n)``, on the cache's leaves where they lie
    (:func:`~sparkdl_tpu.models.hybrid.attention_segment`)."""
    out, cache_k, cache_v = attention_segment(
        *grouped_qkv(lp, u, cfg.num_key_value_heads, cfg.attention_head_dim),
        cache_k, cache_v, layer, rows, start, cfg.attention_multiplier)
    return jnp.dot(out, lp["wo"]), cache_k, cache_v


def _attention_token(cfg, lp, u, cache_k, cache_v, position):
    """The mixer on one position a row, ``u`` [r, D] at ``position[r]``."""
    out, cache_k, cache_v = attention_token(
        *grouped_qkv(lp, u, cfg.num_key_value_heads, cfg.attention_head_dim),
        cache_k, cache_v, position, cfg.attention_multiplier)
    return jnp.dot(out, lp["wo"]), cache_k, cache_v


def _layers(params, cfg, x, state, rows, mamba, attention, in_place=False):
    """Every layer over ``x``
    (:func:`~sparkdl_tpu.models.hybrid.run_layers`): ``mamba(lp, u, window,
    state)`` and ``attention(lp, u, cache_k, cache_v)`` are the two mixers at
    the caller's shape (a segment or a token); ``in_place``: ``attention``
    takes the cache's leaves whole (``..., layer, rows``).  Returns (x,
    state, counts [L, E])."""
    return run_layers(
        params, cfg.layer_types, x, state, rows,
        {"mamba": Mixer(("conv", "ssm"), mamba),
         "attention": Mixer(("k", "v"), attention, in_place)},
        functools.partial(_feed_forward, cfg), eps=cfg.rms_norm_eps,
        residual=cfg.residual_multiplier)


def _embed(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return (cfg.embedding_multiplier * x).astype(x.dtype)


def _log_probs(params, cfg, x):
    """Float32 log-probabilities of the next token from hidden ``x``."""
    xn = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    logits = jnp.dot(xn, params["embed"].T,
                     preferred_element_type=jnp.float32)
    return jax.nn.log_softmax(logits / cfg.logits_scaling, axis=-1)


def _segment(params, cfg, state, tokens, rows, start, lengths):
    """A segment ``tokens`` [c, n] of the rows ``rows`` of ``state`` (None:
    all, in order) at positions ``start[c] + arange(n)``, ``lengths[c]`` of
    them real.  A row whose ``start`` is 0 begins from an empty recurrent
    state, whatever the state holds (its cache is simply overwritten)."""
    fresh = (start == 0)[:, None, None]

    def mamba(lp, u, window, rec):
        return _mamba_segment(
            cfg, lp, u, jnp.where(fresh, 0, window),
            jnp.where(fresh[..., None], 0, rec), lengths)

    return _layers(
        params, cfg, _embed(params, cfg, tokens), state, rows, mamba,
        functools.partial(_attention_segment, cfg, start=start),
        in_place=True)


# -- entry points -------------------------------------------------------------

def forward_logits(params, cfg: GraniteHybridConfig, tokens, lengths):
    """Float32 logits [r, n, V] of whole sequences ``tokens`` [r, n] from an
    empty state; positions at or past ``lengths`` are pads nobody sees."""
    r, n = tokens.shape
    state = empty_state(cfg, r, n, params["embed"].dtype)
    x, _, _ = _segment(params, cfg, state, tokens, None,
                       jnp.zeros((r,), jnp.int32), lengths)
    xn = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(xn, params["embed"].T,
                   preferred_element_type=jnp.float32) / cfg.logits_scaling


def prefill(params, cfg: GraniteHybridConfig, state, tokens, rows, start,
            lengths):
    """One segment of some rows' prompts.

    ``tokens`` [c, n]: the segment's tokens of the rows ``rows`` [c] (indices
    into the state; an index past its last row names nobody: nothing of that
    entry is kept); ``start`` [c]: the position of each row's first token
    here; ``lengths`` [c]: how many of the n are real.  A row whose ``start``
    is 0 begins from an empty state, whatever the state held.

    Returns ``(state, log-probabilities [c, V] float32, counts [L, E])``:
    the state with these rows' entries as of their own last real token here,
    their ``position`` at ``start + lengths`` and their ``token`` the
    likeliest next one; the log-probabilities of the next token at each
    row's last real position.  Pad positions are routed like any other."""
    x, state, counts = _segment(
        params, cfg, state, tokens, rows, start, lengths)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    logp = _log_probs(params, cfg, last)
    state["position"] = state["position"].at[rows].set(
        start + lengths, mode="drop")
    state["token"] = state["token"].at[rows].set(
        jnp.argmax(logp, axis=-1).astype(jnp.int32), mode="drop")
    return state, logp, counts


def decode_step(params, cfg: GraniteHybridConfig, state):
    """Every row takes in its ``token`` at its ``position``.  Returns
    ``(state, log-probabilities [rows, V] float32 of the token after it,
    counts [L, E])``; the state's ``token`` is then the likeliest one."""
    position = state["position"]
    x, new, counts = _layers(
        params, cfg, _embed(params, cfg, state["token"]), state, None,
        lambda lp, u, window, rec: _mamba_token(cfg, lp, u, window, rec),
        lambda lp, u, cache_k, cache_v: _attention_token(
            cfg, lp, u, cache_k, cache_v, position))
    logp = _log_probs(params, cfg, x)
    new = dict(new, position=position + 1,
               token=jnp.argmax(logp, axis=-1).astype(jnp.int32))
    return new, logp, counts


def decode(params, cfg: GraniteHybridConfig, state, steps: int):
    """``steps`` greedy tokens a row, one loop body.  Returns ``(state,
    tokens [rows, steps], their log-probabilities [rows, steps] float32,
    counts [steps, L, E])``: a step's routing apart, since which experts a
    step of few tokens reads at all is the data's."""

    def step(state, _):
        state, logp, counts = decode_step(params, cfg, state)
        return state, (state["token"], jnp.max(logp, axis=-1), counts)

    state, (tokens, logp, counts) = jax.lax.scan(
        step, state, None, length=steps)
    return state, tokens.T, logp.T, counts


# -- what a stage takes -----------------------------------------------------

@functools.lru_cache(maxsize=1)
def _source_digest() -> str:
    """Identifies this mathematics in a program's fingerprint
    (:func:`~sparkdl_tpu.models.hybrid.source_digest`)."""
    return source_digest(ssm, inspect.getmodule(prefill))


class GraniteHybridModel(HybridModel):
    """Granite 4.0-H for an
    :class:`~sparkdl_tpu.transformers.ar_generate.AutoregressiveTransformer`
    (:class:`~sparkdl_tpu.models.hybrid.HybridModel`): programs
    ``granite_prefill`` and ``granite_decode``; the recurrent state is the
    conv windows and the SSM states."""

    name = "granite"
    family = "granite_hybrid"
    config_class = GraniteHybridConfig
    recurrent_leaves = ("conv", "ssm")
    functions = sys.modules[__name__]
