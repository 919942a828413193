"""Solar-Open2 (``model_type: solar_open2``, upstage Solar-Open2-250B): a
delta-rule linear-attention / gated-attention hybrid with a sparse-expert
feed-forward (sigmoid router with a selection bias) and a shared expert in
every layer, as pure functions over a params pytree and a per-row state
pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g`` (statistics in float32).
``x_0 = Embed[ids]``.  For layer i::

    h = x + Mixer_i(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + MoE(u) + Shared(u)

and ``logits = RMSNorm_f(y) W_head`` (untied, float32, over the chip's slice
of the vocabulary).

- **KDA mixer** (Kimi Delta Attention, arXiv:2510.26692; the layers not in
  ``gqa_layers``; H heads of d_k = d_v = ``linear_attn_config.head_dim``, R =
  that head_dim too, the low rank of ``kda_use_full_proj: false``): ``q~ = u
  W_q``, ``k~ = u W_k``, ``v~ = u W_v`` (no bias); each through its own
  depthwise causal conv of width ``short_conv_kernel_size`` and silu; per
  head ``q = q~ / sqrt(sum q~^2 + 1e-6) * d_k^(-1/2)``, ``k = k~ / sqrt(sum
  k~^2 + 1e-6)``, ``v = v~``; log-decay per head and channel ``a_t =
  -exp(A_log_h) * softplus((u W_fa) W_fb + dt_bias)``; write strength
  ``beta_t = 2 sigmoid(u W_beta)`` (the 2 is ``kda_allow_neg_eigval``); the
  gated delta rule on a float32 state [d_k, d_v] a head
  (:mod:`sparkdl_tpu.ops.delta_rule`): ``Sbar_t = Diag(e^{a_t}) S_{t-1}``,
  ``S_t = Sbar_t + beta_t k_t (v_t - Sbar_t^T k_t)^T``, ``o_t = S_t^T q_t``;
  output gate ``z_t = (u W_ga) W_gb + b_g``, per head ``o^_t = RMSNorm(o_t;
  w_o) * sigmoid(z_t)``; out ``= o^ W_o``.
- **Attention mixer** (``gqa_layers``): grouped-query softmax attention,
  bias-free projections, NO position term (``use_rope: false``), scores ``q.k
  / sqrt(head_dim)``, causal, softmax in float32, and an output gate
  (``use_gqa_gate``): out ``= (attn * sigmoid(u W_g)) W_o``.
- **MoE**: :func:`sparkdl_tpu.ops.moe.moe_ffn` with ``scoring="sigmoid"``:
  ``s = sigmoid(u W_r)``, the top-k of ``s + b`` (``router_bias``), weights
  ``s_e / sum_chosen s`` times ``routed_scaling_factor``.  **Shared**:
  ``(silu(u W_a) * u W_b) W_c``, added for every token.

Each choice the published config does not settle is listed under ``assumed``
in ``chipbench/configs/solar_open2_250b-generate.json``.

Two kinds of layer in one stack (:func:`sparkdl_tpu.models.hybrid.run_layers`,
shared with ``granite_hybrid``): the KDA mixers are stacked on a leading axis
of their own (``params["kda"]``), the attention mixers on theirs, every run
of consecutive KDA layers is one ``lax.scan``, and the feed-forwards of ALL
layers are one stack whose experts go to the grouped product whole.

The per-row state is ONE pytree, donated from program to program:

- ``conv`` [M, rows, 3, K-1, H d_k]: each KDA layer's three conv windows (of
  q, k and v);
- ``kda`` [M, rows, H, d_k, d_v] float32: its matrix state;
- ``k``, ``v`` [A, rows, KV, span, head_dim]: the attention layers' cache;
- ``position`` [rows], ``token`` [rows]: as ``granite_hybrid``'s.

Entry points, all with fixed shapes and ``granite_hybrid``'s signatures:
:func:`forward_logits`, :func:`prefill`, :func:`decode_step`,
:func:`decode`.

Softmaxes, the router's sigmoids, the norms' statistics, the decays and the
KDA state are float32; everything else runs in the weights' dtype
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
    rms_norm,
    run_layers,
    source_digest,
)
from sparkdl_tpu.ops import delta_rule, ssm


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    """The published ``config.json`` keys the mathematics reads, and the
    chip's share (``model-configs`` section 4): ``n_routed_experts`` counts
    the experts HELD here, ``experts_held`` says which of the
    ``routed_experts`` the router scores they are."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    gqa_layers: Tuple[int, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    kda_heads: int
    kda_head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int = 1
    short_conv_kernel_size: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_gqa_gate: bool = True
    kda_allow_neg_eigval: bool = True
    kda_chunk_size: int = 64
    rms_norm_eps: float = 1e-5
    routed_experts: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "SolarOpen2Config":
        linear = config["linear_attn_config"]
        if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
            raise NotImplementedError("as many key/value heads as heads only")
        if config.get("kda_use_full_proj", False):
            raise NotImplementedError(
                "low-rank decay and gate projections only")
        if config.get("use_rope", False) or config.get(
                "first_k_dense_replace", 0):
            raise NotImplementedError(
                "no rotary term and no leading dense layer")
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in config.items() if k in names}
        kept.update(
            gqa_layers=tuple(config["gqa_layers"]),
            kda_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
            short_conv_kernel_size=linear["short_conv_kernel_size"])
        kept.setdefault("routed_experts", config.get("published", {}).get(
            "n_routed_experts"))
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    def __post_init__(self):
        if not self.norm_topk_prob or self.routed_scaling_factor != 1:
            raise NotImplementedError(
                "renormalised top-k weights at routed_scaling_factor 1 (the "
                "published values) only")
        lo, hi = self.held
        if hi - lo != self.n_routed_experts:
            raise ValueError(
                f"experts_held {lo, hi} is not the {self.n_routed_experts} "
                "experts n_routed_experts says are held here")

    @property
    def routed(self) -> int:
        return self.routed_experts or self.n_routed_experts

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.routed)

    @property
    def layer_types(self) -> Tuple[str, ...]:
        """``"attention"`` for the layers of ``gqa_layers``, ``"kda"`` for
        the others, for the layers that are run."""
        return tuple("attention" if i in self.gqa_layers else "kda"
                     for i in range(self.num_hidden_layers))

    @property
    def inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)


def param_shapes(cfg: SolarOpen2Config) -> Dict[str, Any]:
    """The params pytree's shapes (see the module's docstring)."""
    m, a, n = cfg.count("kda"), cfg.count("attention"), cfg.num_hidden_layers
    d, inner, rank, k = (cfg.hidden_size, cfg.inner, cfg.kda_head_dim,
                         cfg.short_conv_kernel_size)
    q = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    f, fs, held = (cfg.moe_intermediate_size,
                   cfg.n_shared_experts * cfg.moe_intermediate_size,
                   cfg.n_routed_experts)
    return {
        "embed": (cfg.vocab_size, d),
        "kda": {
            "in_norm": (m, d), "wq": (m, d, inner), "wk": (m, d, inner),
            "wv": (m, d, inner), "conv_q": (m, inner, k),
            "conv_k": (m, inner, k), "conv_v": (m, inner, k),
            "f_a": (m, d, rank), "f_b": (m, rank, inner),
            "dt_bias": (m, inner), "a_log": (m, cfg.kda_heads),
            "w_beta": (m, d, cfg.kda_heads), "g_a": (m, d, rank),
            "g_b": (m, rank, inner), "g_bias": (m, inner),
            "o_norm": (m, cfg.kda_head_dim), "wo": (m, inner, d),
        },
        "attention": {
            "in_norm": (a, d), "wq": (a, d, q), "wk": (a, d, kv),
            "wv": (a, d, kv), "wg": (a, d, q), "wo": (a, q, d),
        },
        "ffn": {
            "post_norm": (n, d), "router": (n, d, cfg.routed),
            "router_bias": (n, cfg.routed),
            "w_gate": (n, held, d, f), "w_up": (n, held, d, f),
            "w_down": (n, held, f, d),
            "shared_gate": (n, d, fs), "shared_up": (n, d, fs),
            "shared_down": (n, fs, d),
        },
        "final_norm": (d,),
        "head": (cfg.vocab_size, d),
    }


#: leaves that stay float32 whatever the compute dtype: they feed
#: exponentials, or (the selection bias) are added to float32 scores
FLOAT32_LEAVES = ("dt_bias", "a_log", "router_bias")


def init_params(cfg: SolarOpen2Config, seed: int = 0, dtype=jnp.bfloat16,
                std: float = 0.02):
    """Seeded random params: normal(0, std) matrices, gains of one, ``A_log
    = log(U[1, 16])``, ``dt_bias`` the inverse softplus of a log-uniform step
    in [0.001, 0.1], the convs U(+-1/sqrt(K)), the gate's bias zero, the
    router's selection bias normal(0, 0.01); :data:`FLOAT32_LEAVES`
    float32."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    bound = cfg.short_conv_kernel_size ** -0.5

    def draw(name, shape, key):
        if "norm" in name:
            return jnp.ones(shape, dtype)
        if name == "g_bias":
            return jnp.zeros(shape, dtype)
        if name.startswith("conv_"):
            return jax.random.uniform(
                key, shape, jnp.float32, -bound, bound).astype(dtype)
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1, 16))
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        if name == "router_bias":
            return 0.01 * jax.random.normal(key, shape, jnp.float32)
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    made = [draw(path[-1].key, shape, key)
            for (path, shape), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, made)


def state_spec(cfg: SolarOpen2Config, rows: int, span: int, dtype):
    """The state pytree as ``jax.ShapeDtypeStruct``s: ``rows`` rows, an
    attention cache of ``span`` positions."""
    m, a = cfg.count("kda"), cfg.count("attention")
    cache = (a, rows, cfg.num_key_value_heads, span, cfg.head_dim)
    spec = jax.ShapeDtypeStruct
    return {
        "conv": spec((m, rows, 3, cfg.short_conv_kernel_size - 1, cfg.inner),
                     dtype),
        "kda": spec((m, rows, cfg.kda_heads, cfg.kda_head_dim,
                     cfg.kda_head_dim), jnp.float32),
        "k": spec(cache, dtype), "v": spec(cache, dtype),
        "position": spec((rows,), jnp.int32),
        "token": spec((rows,), jnp.int32),
    }


def empty_state(cfg: SolarOpen2Config, rows: int, span: int, dtype):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        state_spec(cfg, rows, span, dtype))


# -- the pieces -------------------------------------------------------------

def _heads(cfg, x):
    return x.reshape(*x.shape[:-1], cfg.kda_heads, cfg.kda_head_dim)


def _kda_rule_inputs(cfg, lp, u, q, k):
    """``(q, k)`` normalised per head (float32 statistics), the log-decay
    [..., H, d_k] and the write strength [..., H], both float32; ``q`` and
    ``k`` [..., H d_k] after their convs."""
    def unit(x):
        xf = _heads(cfg, x).astype(jnp.float32)
        return xf * jax.lax.rsqrt(
            jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)

    q = (unit(q) * cfg.kda_head_dim ** -0.5).astype(u.dtype)
    k = unit(k).astype(u.dtype)
    step = jax.nn.softplus(
        jnp.dot(jnp.dot(u, lp["f_a"]), lp["f_b"],
                preferred_element_type=jnp.float32)
        + lp["dt_bias"].astype(jnp.float32))
    log_decay = -jnp.exp(lp["a_log"].astype(jnp.float32))[:, None] * _heads(
        cfg, step)
    strength = 2.0 if cfg.kda_allow_neg_eigval else 1.0
    beta = strength * jax.nn.sigmoid(jnp.dot(
        u, lp["w_beta"], preferred_element_type=jnp.float32))
    return q, k, log_decay, beta


def _kda_out(cfg, lp, u, o):
    """Per-head norm, output gate, out-projection: ``o`` [..., H, d_v]
    float32."""
    z = jnp.dot(jnp.dot(u, lp["g_a"]), lp["g_b"],
                preferred_element_type=jnp.float32
                ) + lp["g_bias"].astype(jnp.float32)
    scale = jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
    gated = (o * scale * lp["o_norm"].astype(jnp.float32)
             * jax.nn.sigmoid(_heads(cfg, z)))
    return jnp.dot(gated.reshape(*u.shape[:-1], -1).astype(u.dtype), lp["wo"])


def _kda_segment(cfg, lp, u, windows, state, lengths):
    """The mixer over a segment ``u`` [c, n, D] of rows with ``lengths`` real
    positions each.  Returns (out, conv windows [c, 3, K-1, H d_k], state)."""
    no_bias = jnp.zeros((cfg.inner,), jnp.float32)
    q, k, v = jnp.dot(u, lp["wq"]), jnp.dot(u, lp["wk"]), jnp.dot(u, lp["wv"])
    (q, window_q), (k, window_k), (v, window_v) = (
        ssm.causal_conv(x, windows[:, i], lp[w], no_bias, lengths)
        for i, (x, w) in enumerate(
            ((q, "conv_q"), (k, "conv_k"), (v, "conv_v"))))
    q, k, log_decay, beta = _kda_rule_inputs(cfg, lp, u, q, k)
    real = jnp.arange(u.shape[1])[None, :] < lengths[:, None]
    # a pad leaves the state alone: no decay and no write
    log_decay = jnp.where(real[..., None, None], log_decay, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    o, state = delta_rule.kda_chunked(
        q, k, _heads(cfg, v), log_decay, beta, state, cfg.kda_chunk_size)
    windows = jnp.stack([window_q, window_k, window_v], axis=1)
    return _kda_out(cfg, lp, u, o), windows, state


def _kda_token(cfg, lp, u, windows, state):
    """The mixer on one position a row, ``u`` [r, D]."""
    no_bias = jnp.zeros((cfg.inner,), jnp.float32)
    (q, window_q), (k, window_k), (v, window_v) = (
        ssm.conv_update(jnp.dot(u, lp[w]), windows[:, i], lp[c], no_bias)
        for i, (w, c) in enumerate(
            (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v"))))
    q, k, log_decay, beta = _kda_rule_inputs(cfg, lp, u, q, k)
    o, state = delta_rule.kda_update(
        q, k, _heads(cfg, v), log_decay, beta, state)
    windows = jnp.stack([window_q, window_k, window_v], axis=1)
    return _kda_out(cfg, lp, u, o), windows, state


def _gated(cfg, lp, u, out):
    """``(attn * sigmoid(u W_g)) W_o``."""
    if cfg.use_gqa_gate:
        gate = jax.nn.sigmoid(jnp.dot(
            u, lp["wg"], preferred_element_type=jnp.float32))
        out = (out.astype(jnp.float32) * gate).astype(u.dtype)
    return jnp.dot(out, lp["wo"])


def _attention_segment(cfg, lp, u, cache_k, cache_v, layer, rows, start):
    """The mixer over a segment, on the cache's leaves where they lie
    (:func:`~sparkdl_tpu.models.hybrid.attention_segment`)."""
    out, cache_k, cache_v = attention_segment(
        *grouped_qkv(lp, u, cfg.num_key_value_heads, cfg.head_dim),
        cache_k, cache_v, layer, rows, start, cfg.head_dim ** -0.5)
    return _gated(cfg, lp, u, out), cache_k, cache_v


def _attention_token(cfg, lp, u, cache_k, cache_v, position):
    out, cache_k, cache_v = attention_token(
        *grouped_qkv(lp, u, cfg.num_key_value_heads, cfg.head_dim),
        cache_k, cache_v, position, cfg.head_dim ** -0.5)
    return _gated(cfg, lp, u, out), cache_k, cache_v


def _feed_forward(cfg, fp, experts, layer, h):
    """``h + MoE(u) + Shared(u)`` and the layer's routing counts."""
    return feed_forward(
        fp, experts, layer, h, eps=cfg.rms_norm_eps,
        top_k=cfg.num_experts_per_tok, held=cfg.held, scoring="sigmoid")


def _layers(params, cfg, x, state, rows, kda, attention, in_place=False):
    """Every layer over ``x``
    (:func:`~sparkdl_tpu.models.hybrid.run_layers`): ``kda(lp, u, windows,
    state)`` and ``attention(lp, u, cache_k, cache_v)`` are the two mixers at
    the caller's shape (a segment or a token); ``in_place``: ``attention``
    takes the cache's leaves whole (``..., layer, rows``)."""
    return run_layers(
        params, cfg.layer_types, x, state, rows,
        {"kda": Mixer(("conv", "kda"), kda),
         "attention": Mixer(("k", "v"), attention, in_place)},
        functools.partial(_feed_forward, cfg), eps=cfg.rms_norm_eps)


def _embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _logits(params, cfg, x):
    xn = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,vd->...v", xn, params["head"],
                      preferred_element_type=jnp.float32)


def _segment(params, cfg, state, tokens, rows, start, lengths):
    """A segment ``tokens`` [c, n] of the rows ``rows`` of ``state`` (None:
    all, in order) at positions ``start[c] + arange(n)``, ``lengths[c]`` of
    them real.  A row whose ``start`` is 0 begins from an empty recurrent
    state, whatever the state holds (its cache is simply overwritten)."""
    fresh = start == 0

    def kda(lp, u, windows, held):
        return _kda_segment(
            cfg, lp, u, jnp.where(fresh[:, None, None, None], 0, windows),
            jnp.where(fresh[:, None, None, None], 0, held), lengths)

    return _layers(
        params, cfg, _embed(params, tokens), state, rows, kda,
        functools.partial(_attention_segment, cfg, start=start),
        in_place=True)


# -- entry points -------------------------------------------------------------

def forward_logits(params, cfg: SolarOpen2Config, tokens, lengths):
    """Float32 logits [r, n, V] of whole sequences ``tokens`` [r, n] from an
    empty state; positions at or past ``lengths`` are pads nobody sees."""
    r, n = tokens.shape
    state = empty_state(cfg, r, n, params["embed"].dtype)
    x, _, _ = _segment(params, cfg, state, tokens, None,
                       jnp.zeros((r,), jnp.int32), lengths)
    return _logits(params, cfg, x)


def prefill(params, cfg: SolarOpen2Config, state, tokens, rows, start,
            lengths):
    """One segment of some rows' prompts; arguments and results as
    :func:`sparkdl_tpu.models.granite_hybrid.prefill`: ``(state,
    log-probabilities [c, V] float32 of the next token at each row's last
    real position, counts [L, E])``."""
    x, state, counts = _segment(
        params, cfg, state, tokens, rows, start, lengths)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    logp = jax.nn.log_softmax(_logits(params, cfg, last), axis=-1)
    state["position"] = state["position"].at[rows].set(
        start + lengths, mode="drop")
    state["token"] = state["token"].at[rows].set(
        jnp.argmax(logp, axis=-1).astype(jnp.int32), mode="drop")
    return state, logp, counts


def decode_step(params, cfg: SolarOpen2Config, state):
    """Every row takes in its ``token`` at its ``position``.  Returns
    ``(state, log-probabilities [rows, V] float32 of the token after it,
    counts [L, E])``; the state's ``token`` is then the likeliest one."""
    position = state["position"]
    x, new, counts = _layers(
        params, cfg, _embed(params, state["token"]), state, None,
        functools.partial(_kda_token, cfg),
        lambda lp, u, cache_k, cache_v: _attention_token(
            cfg, lp, u, cache_k, cache_v, position))
    logp = jax.nn.log_softmax(_logits(params, cfg, x), axis=-1)
    new = dict(new, position=position + 1,
               token=jnp.argmax(logp, axis=-1).astype(jnp.int32))
    return new, logp, counts


def decode(params, cfg: SolarOpen2Config, state, steps: int):
    """``steps`` greedy tokens a row, one loop body.  Returns ``(state,
    tokens [rows, steps], their log-probabilities [rows, steps] float32,
    counts [steps, L, E])``."""

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
    return source_digest(delta_rule, ssm, inspect.getmodule(prefill))


class SolarOpen2Model(HybridModel):
    """Solar-Open2 for an
    :class:`~sparkdl_tpu.transformers.ar_generate.AutoregressiveTransformer`
    (:class:`~sparkdl_tpu.models.hybrid.HybridModel`): programs
    ``solar_prefill`` and ``solar_decode``; the recurrent state is the conv
    windows and the KDA states."""

    name = "solar"
    family = "solar_open2"
    config_class = SolarOpen2Config
    recurrent_leaves = ("conv", "kda")
    functions = sys.modules[__name__]

    def rule_chunks(self, pairs: int, segment: int) -> Tuple[int, int]:
        """``(chunks, fused)`` of one prefill dispatch of ``pairs`` segments
        of ``segment`` positions: the (row, chunk) pairs its KDA layers put
        through the chunked rule, and those of them that go through the
        kernel (:func:`sparkdl_tpu.ops.delta_rule.fused_chunks`)."""
        cfg = self.config
        chunks, fused = delta_rule.fused_chunks(
            pairs, segment, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim,
            cfg.kda_chunk_size)
        return cfg.count("kda") * chunks, cfg.count("kda") * fused
