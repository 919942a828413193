"""Moonlight (``model_type: deepseek_v3``, moonshotai Moonlight-16B-A3B): a
decoder whose every layer is multi-head latent attention (MLA) through one
latent cache, the first ``first_k_dense_replace`` layers followed by a dense
SwiGLU and every later one by sigmoid-routed experts scaled by
``routed_scaling_factor`` beside unscaled shared experts, as pure functions
over a params pytree and a per-row state pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g`` (statistics in float32).
``x_0 = Embed[ids]``.  For layer i::

    h = x + MLA_i(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + Dense(u)                                    (i < k)
    y = h + routed_scaling_factor * MoE(u) + Shared(u)  (i >= k)

and ``logits = RMSNorm_f(y) W_head`` (untied, float32).

- **MLA** (:mod:`sparkdl_tpu.ops.mla`; ``q_lora_rank`` null: the query is
  one projection): H heads of ``qk_nope_head_dim`` + ``qk_rope_head_dim``
  query/key channels and ``v_head_dim`` value channels; ``[c | k_pe] = u
  W_kva``, ``c`` through its own RMSNorm (eps 1e-6), ``[k_nope | v] = c
  W_kvb``; RoPE at ``rope_theta`` on the rope channels in the interleaved
  pairing, ``k_pe`` one head all heads share; scores times
  ``(qk_nope_head_dim + qk_rope_head_dim)^(-1/2)``, causal, softmax in
  float32; out ``= attn W_o``.
- **Dense** (the first ``first_k_dense_replace`` layers): ``(silu(u W_a) * u
  W_b) W_c`` of width ``intermediate_size``.
- **MoE**: :func:`sparkdl_tpu.ops.moe.moe_ffn` with ``scoring="sigmoid"``:
  ``s = sigmoid(u W_r)``, the top-k of ``s + b`` (``router_bias``, the
  published ``e_score_correction_bias``; ``n_group`` 1 restricts nothing),
  weights ``s_e / sum_chosen s`` times ``routed_scaling_factor``.
  **Shared**: the SwiGLU of width ``n_shared_experts * moe_intermediate_size``,
  not scaled.

Each choice the published config does not settle is listed under
``assumed`` in ``chipbench/configs/moonlight_16b_a3b-generate.json``.

Every layer is of ONE kind, ``"mla"``, and every run of them is one
``lax.scan`` (:func:`sparkdl_tpu.models.hybrid.run_layers`, cut where the
dense layers end).  The per-row state is ONE pytree, donated from program to
program:

- ``latent`` [L, rows, span, kv_lora_rank + qk_rope_head_dim]: every layer's
  ``[c | k_pe]`` of every position, in the compute dtype, written in place;
- ``position`` [rows], ``token`` [rows]: as ``granite_hybrid``'s.

Entry points, with ``granite_hybrid``'s signatures: :func:`forward_logits`,
:func:`prefill`, :func:`decode_step`, :func:`decode`; both programs take
MLA's absorbed form (:mod:`sparkdl_tpu.ops.mla`).
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import sys
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.hybrid import (
    HybridModel,
    Mixer,
    dense_feed_forward,
    feed_forward,
    rms_norm,
    run_layers,
    source_digest,
)
from sparkdl_tpu.ops import mla


@dataclasses.dataclass(frozen=True)
class MoonlightConfig:
    """The published ``config.json`` keys the mathematics reads."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    n_shared_experts: int = 1
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: ``kv_a_layernorm``'s eps: the published modeling code builds it with
    #: RMSNorm's default, not ``rms_norm_eps``
    kv_norm_eps: float = 1e-6

    #: (key, the values the mathematics here implements, what else means)
    _ONLY = (
        ("q_lora_rank", (None,), "a low-rank query (q_a_proj, q_a_layernorm)"),
        ("n_group", (None, 1), "group-limited routing"),
        ("topk_group", (None, 1), "group-limited routing"),
        ("rope_scaling", (None,), "scaled RoPE and its softmax mscale"),
        ("num_nextn_predict_layers", (None, 0), "multi-token prediction"),
        ("scoring_func", (None, "sigmoid"), "another router"),
        ("topk_method", (None, "noaux_tc"), "another expert selection"),
        ("moe_layer_freq", (None, 1), "dense layers between expert layers"),
        ("hidden_act", (None, "silu"), "another activation"),
        ("attention_bias", (None, False), "biased projections"),
        ("tie_word_embeddings", (None, False), "a tied head"),
    )

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "MoonlightConfig":
        for key, allowed, meaning in cls._ONLY:
            if config.get(key) not in allowed:
                raise NotImplementedError(
                    f"{key} = {config.get(key)!r}: {meaning} is not "
                    f"implemented (only {allowed})")
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in config.items() if k in names})

    def __post_init__(self):
        if not self.norm_topk_prob:
            raise NotImplementedError("renormalised top-k weights only")
        if not 0 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} leaves "
                f"no expert layer of {self.num_hidden_layers}")

    @property
    def held(self) -> Tuple[int, int]:
        return (0, self.n_routed_experts)

    @property
    def dense(self) -> int:
        return self.first_k_dense_replace

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return ("mla",) * self.num_hidden_layers

    @property
    def mla(self) -> mla.Shape:
        return mla.Shape(
            heads=self.num_attention_heads, nope=self.qk_nope_head_dim,
            rope=self.qk_rope_head_dim, v=self.v_head_dim,
            rank=self.kv_lora_rank, theta=float(self.rope_theta),
            kv_eps=self.kv_norm_eps, scale=softmax_scale(self))


def softmax_scale(cfg: MoonlightConfig) -> float:
    """The scores' factor: one over the root of a query head's width."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def param_shapes(cfg: MoonlightConfig) -> Dict[str, Any]:
    """The params pytree's shapes (see the module's docstring)."""
    n, k, d, h = (cfg.num_hidden_layers, cfg.dense, cfg.hidden_size,
                  cfg.num_attention_heads)
    m, e = n - k, cfg.n_routed_experts
    f, fs, dense = (cfg.moe_intermediate_size,
                    cfg.n_shared_experts * cfg.moe_intermediate_size,
                    cfg.intermediate_size)
    shape = cfg.mla
    return {
        "embed": (cfg.vocab_size, d),
        "mla": {
            "in_norm": (n, d),
            "wq": (n, d, h * (shape.nope + shape.rope)),
            "wkva": (n, d, shape.latent), "kv_norm": (n, shape.rank),
            "wkvb": (n, shape.rank, h * (shape.nope + shape.v)),
            "wo": (n, h * shape.v, d),
        },
        "dense": {
            "post_norm": (k, d), "gate": (k, d, dense), "up": (k, d, dense),
            "down": (k, dense, d),
        },
        "ffn": {
            "post_norm": (m, d), "router": (m, d, e), "router_bias": (m, e),
            "w_gate": (m, e, d, f), "w_up": (m, e, d, f),
            "w_down": (m, e, f, d),
            "shared_gate": (m, d, fs), "shared_up": (m, d, fs),
            "shared_down": (m, fs, d),
        },
        "final_norm": (d,),
        "head": (cfg.vocab_size, d),
    }


#: leaves that stay float32 whatever the compute dtype: the selection bias
#: is added to float32 scores
FLOAT32_LEAVES = ("router_bias",)


def init_params(cfg: MoonlightConfig, seed: int = 0, dtype=jnp.bfloat16,
                std: float = 0.02):
    """Seeded random params: normal(0, std) matrices, gains of one, the
    router's selection bias normal(0, 0.01) in float32."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))

    def draw(name, shape, key):
        if "norm" in name:
            return jnp.ones(shape, dtype)
        if name == "router_bias":
            return 0.01 * jax.random.normal(key, shape, jnp.float32)
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    made = [draw(path[-1].key, shape, key)
            for (path, shape), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, made)


def state_spec(cfg: MoonlightConfig, rows: int, span: int, dtype):
    """The state pytree as ``jax.ShapeDtypeStruct``s: ``rows`` rows, a
    latent cache of ``span`` positions."""
    spec = jax.ShapeDtypeStruct
    return {
        "latent": spec(
            (cfg.num_hidden_layers, rows, span, cfg.mla.latent), dtype),
        "position": spec((rows,), jnp.int32),
        "token": spec((rows,), jnp.int32),
    }


def empty_state(cfg: MoonlightConfig, rows: int, span: int, dtype):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        state_spec(cfg, rows, span, dtype))


# -- the pieces -------------------------------------------------------------

def _feed_forward(cfg, fp, experts, layer, h):
    """``h + routed_scaling_factor * MoE(u) + Shared(u)`` and the layer's
    routing counts."""
    return feed_forward(
        fp, experts, layer, h, eps=cfg.rms_norm_eps,
        top_k=cfg.num_experts_per_tok, held=cfg.held, scoring="sigmoid",
        routed_scale=cfg.routed_scaling_factor)


def _layers(params, cfg, x, state, rows, attention):
    """Every layer over ``x``: ``attention(lp, u, latent, layer, rows)`` is
    the mixer at the caller's shape (a segment or a token) on the leaf
    whole (:func:`~sparkdl_tpu.models.hybrid.run_layers`)."""
    def mix(lp, u, latent, layer, rows):
        out, latent = attention(lp, u, latent, layer, rows)
        return jnp.dot(out, lp["wo"]), latent

    return run_layers(
        params, cfg.layer_types, x, state, rows,
        {"mla": Mixer(("latent",), mix, in_place=True)},
        functools.partial(_feed_forward, cfg), eps=cfg.rms_norm_eps,
        dense_of=(functools.partial(dense_feed_forward, eps=cfg.rms_norm_eps)
                  if cfg.dense else None))


def _embed(params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _logits(params, cfg, x):
    xn = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum("...d,vd->...v", xn, params["head"],
                      preferred_element_type=jnp.float32)


def _segment(params, cfg, state, tokens, rows, start, lengths):
    """A segment ``tokens`` [c, n] of the rows ``rows`` of ``state`` (None:
    all, in order) at positions ``start[c] + arange(n)``, ``lengths[c]`` of
    them real (a pad writes no latent)."""
    shape = cfg.mla

    def attention(lp, u, latent, layer, rows):
        return mla.mla_segment(lp, u, latent, layer, rows, start, lengths,
                               shape)

    return _layers(params, cfg, _embed(params, tokens), state, rows,
                   attention)


# -- entry points -------------------------------------------------------------

def forward_logits(params, cfg: MoonlightConfig, tokens, lengths):
    """Float32 logits [r, n, V] of whole sequences ``tokens`` [r, n] from an
    empty state; positions at or past ``lengths`` are pads nobody sees."""
    r, n = tokens.shape
    state = empty_state(cfg, r, n, params["embed"].dtype)
    x, _, _ = _segment(params, cfg, state, tokens, None,
                       jnp.zeros((r,), jnp.int32), lengths)
    return _logits(params, cfg, x)


def prefill(params, cfg: MoonlightConfig, state, tokens, rows, start,
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


def decode_step(params, cfg: MoonlightConfig, state):
    """Every row takes in its ``token`` at its ``position``.  Returns
    ``(state, log-probabilities [rows, V] float32 of the token after it,
    counts [L, E])``; the state's ``token`` is then the likeliest one."""
    position, shape = state["position"], cfg.mla

    def attention(lp, u, latent, layer, rows):
        return mla.mla_token(lp, u, latent, layer, position, shape)

    x, new, counts = _layers(
        params, cfg, _embed(params, state["token"]), state, None, attention)
    logp = jax.nn.log_softmax(_logits(params, cfg, x), axis=-1)
    new = dict(new, position=position + 1,
               token=jnp.argmax(logp, axis=-1).astype(jnp.int32))
    return new, logp, counts


def decode(params, cfg: MoonlightConfig, state, steps: int):
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
    return source_digest(mla, inspect.getmodule(prefill))


class MoonlightModel(HybridModel):
    """Moonlight for an
    :class:`~sparkdl_tpu.transformers.ar_generate.AutoregressiveTransformer`
    (:class:`~sparkdl_tpu.models.hybrid.HybridModel`): programs
    ``moonlight_prefill`` and ``moonlight_decode``; no recurrent state, the
    latent cache (``latent_leaves``) is what a row holds."""

    name = "moonlight"
    family = "deepseek_v3"
    config_class = MoonlightConfig
    latent_leaves = ("latent",)
    functions = sys.modules[__name__]
    attention_paths = {"prefill": "absorbed", "decode": "absorbed"}

    @property
    def dense_layers(self) -> int:
        return self.config.dense

    def keys_fused(self, start, segment: int, span: int) -> int:
        """The cache slots a prefill's attention folds in its kernel, as
        :func:`~sparkdl_tpu.ops.mla.keys_fused` counts them."""
        return mla.keys_fused(self.config.mla, start, segment, span)

    def slots_read(self, positions, span: int) -> int:
        """What :func:`~sparkdl_tpu.ops.mla.mla_token` reads: whole blocks up
        to each row's position where its kernel runs, the whole span where
        the plain form does (:func:`~sparkdl_tpu.ops.mla.token_slots_read`)."""
        return mla.token_slots_read(positions, self.config.mla.latent, span)

    def slots_written_fused(self, rows: int, steps: int, span: int) -> int:
        """The new latent slots a decode dispatch of ``steps`` steps writes
        inside :func:`~sparkdl_tpu.ops.mla.mla_token`'s kernel, over every
        layer (:func:`~sparkdl_tpu.ops.mla.token_slots_written`)."""
        cfg = self.config
        return steps * cfg.num_hidden_layers * mla.token_slots_written(
            rows, cfg.mla.latent, span)
