"""GLM-5 (``model_type: glm_moe_dsa``, zai-org GLM-5): a decoder whose every
layer is multi-head latent attention (MLA) with a low-rank query under a
learned sparse attention (DSA: a lightning indexer picks ``index_topk`` keys
a query), the first ``first_k_dense_replace`` layers followed by a dense
SwiGLU and every later one by sigmoid-routed experts scaled by
``routed_scaling_factor`` beside an unscaled shared expert, as pure functions
over a params pytree and a per-row state pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g`` (statistics in float32).
``x_0 = Embed[ids]``.  For layer i::

    h = x + MLA_DSA_i(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + Dense(u)                                    (i < k)
    y = h + routed_scaling_factor * MoE(u) + Shared(u)  (i >= k)

and ``logits = RMSNorm_f(y) W_head`` (untied, float32).

- **MLA** (:mod:`sparkdl_tpu.ops.mla`): the query through ``q_lora_rank``:
  ``c_q = RMSNorm_q(u W_qa)``, ``q = c_q W_qb`` [H, nope + rope]; the rest as
  Moonlight's (``models/moonlight.py``), values ``v_head_dim`` wide.
- **DSA** (:mod:`sparkdl_tpu.ops.dsa`): ``I(t, s) = sum_j w_j ReLU(q_j .
  k_s)`` with ``q = c_q W_qI`` (``index_n_heads`` of ``index_head_dim``),
  ``k = LayerNorm(u W_kI)``, RoPE on the first ``qk_rope_head_dim``
  channels of both, ``w = u W_w / sqrt(J dim)``; each query's softmax runs
  over its ``index_topk`` highest-scored visible keys (all of them where it
  sees no more).
- **Dense**, **MoE**, **Shared**: as Moonlight's; the router scores all
  ``routed`` experts and the chip holds ``experts_held`` of them
  (``model-configs`` section 4: the absent experts' part is left out).

Each choice the published config does not settle is listed under
``assumed`` in ``chipbench/configs/glm_5_744b_a40b-generate.json``.

Every layer is of ONE kind, ``"mla"`` (under DSA), and every run of them is one
``lax.scan`` (:func:`sparkdl_tpu.models.hybrid.run_layers`, cut where the
dense layers end).  The per-row state is ONE pytree of caches, donated from
program to program:

- ``latent`` [L, rows, span, kv_lora_rank + qk_rope_head_dim]: as
  Moonlight's;
- ``index_keys`` [L, rows, span, index_head_dim]: every layer's indexer key
  of every position;
- ``position`` [rows], ``token`` [rows]: as ``granite_hybrid``'s.

Entry points, with ``granite_hybrid``'s signatures: :func:`forward_logits`,
:func:`prefill`, :func:`decode_step`, :func:`decode`.
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
    dense_feed_forward,
    feed_forward,
    keys_scored,
    rms_norm,
    run_layers,
    source_digest,
)
from sparkdl_tpu.ops import dsa, mla


@dataclasses.dataclass(frozen=True)
class Glm5Config:
    """The published ``config.json`` keys the mathematics reads, and the
    chip's share (``model-configs`` section 4): ``n_routed_experts`` counts
    the experts HELD here, ``experts_held`` says which of the
    ``routed_experts`` the router scores they are."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    q_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    index_n_heads: int
    index_head_dim: int
    index_topk: int
    n_shared_experts: int = 1
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: ``q_a_layernorm``'s and ``kv_a_layernorm``'s eps: built with the
    #: norm's default, not ``rms_norm_eps``
    kv_norm_eps: float = 1e-6
    #: the indexer key's LayerNorm eps (the norm's default)
    index_norm_eps: float = 1e-6
    routed_experts: Optional[int] = None
    experts_held: Optional[Tuple[int, int]] = None

    #: (key, the values the mathematics here implements, what else means)
    _ONLY = (
        ("q_lora_rank", None, "a one-projection query (q_lora_rank null)"),
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
        ("rope_interleave", (None, True), "the rotate-half RoPE pairing"),
        ("indexer_rope_interleave", (None, True),
         "the indexer's rotate-half RoPE pairing"),
    )

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "Glm5Config":
        for key, allowed, meaning in cls._ONLY:
            value = config.get(key)
            if (value is None) if allowed is None else value not in allowed:
                raise NotImplementedError(
                    f"{key} = {value!r}: {meaning} is not implemented")
        rope = dict(config.get("rope_parameters") or {})
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(
                f"rope_scaling = {rope!r}: scaled RoPE is not implemented")
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in config.items() if k in names}
        if "rope_theta" in rope:
            kept.setdefault("rope_theta", rope["rope_theta"])
        kept.setdefault("routed_experts", config.get("published", {}).get(
            "n_routed_experts"))
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    def __post_init__(self):
        if not self.norm_topk_prob:
            raise NotImplementedError("renormalised top-k weights only")
        if not 0 <= self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} leaves "
                f"no expert layer of {self.num_hidden_layers}")
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

    @property
    def indexer(self) -> dsa.Indexer:
        return dsa.Indexer(
            heads=self.index_n_heads, dim=self.index_head_dim,
            rope=self.qk_rope_head_dim, theta=float(self.rope_theta),
            topk=self.index_topk, eps=self.index_norm_eps)


def softmax_scale(cfg: Glm5Config) -> float:
    """The scores' factor: one over the root of a query head's width."""
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def param_shapes(cfg: Glm5Config) -> Dict[str, Any]:
    """The params pytree's shapes (see the module's docstring)."""
    n, k, d, h = (cfg.num_hidden_layers, cfg.dense, cfg.hidden_size,
                  cfg.num_attention_heads)
    m, e, routed = n - k, cfg.n_routed_experts, cfg.routed
    f, fs, dense = (cfg.moe_intermediate_size,
                    cfg.n_shared_experts * cfg.moe_intermediate_size,
                    cfg.intermediate_size)
    shape, ix, qr = cfg.mla, cfg.indexer, cfg.q_lora_rank
    return {
        "embed": (cfg.vocab_size, d),
        "mla": {
            "in_norm": (n, d),
            "wqa": (n, d, qr), "q_norm": (n, qr),
            "wqb": (n, qr, h * (shape.nope + shape.rope)),
            "wkva": (n, d, shape.latent), "kv_norm": (n, shape.rank),
            "wkvb": (n, shape.rank, h * (shape.nope + shape.v)),
            "wo": (n, h * shape.v, d),
            "ix_wq": (n, qr, ix.heads * ix.dim), "ix_wk": (n, d, ix.dim),
            "ix_norm": (n, ix.dim), "ix_bias": (n, ix.dim),
            "ix_w": (n, d, ix.heads),
        },
        "dense": {
            "post_norm": (k, d), "gate": (k, d, dense), "up": (k, d, dense),
            "down": (k, dense, d),
        },
        "ffn": {
            "post_norm": (m, d), "router": (m, d, routed),
            "router_bias": (m, routed),
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


def init_params(cfg: Glm5Config, seed: int = 0, dtype=jnp.bfloat16,
                std: float = 0.02):
    """Seeded random params: normal(0, std) matrices, gains of one, the
    indexer key's norm bias zero, the router's selection bias normal(0,
    0.01) in float32."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))

    def draw(name, shape, key):
        if "norm" in name:
            return jnp.ones(shape, dtype)
        if name == "ix_bias":
            return jnp.zeros(shape, dtype)
        if name == "router_bias":
            return 0.01 * jax.random.normal(key, shape, jnp.float32)
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    made = [draw(path[-1].key, shape, key)
            for (path, shape), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, made)


def state_spec(cfg: Glm5Config, rows: int, span: int, dtype):
    """The state pytree as ``jax.ShapeDtypeStruct``s: ``rows`` rows, caches
    of ``span`` positions."""
    spec = jax.ShapeDtypeStruct
    n = cfg.num_hidden_layers
    return {
        "latent": spec((n, rows, span, cfg.mla.latent), dtype),
        "index_keys": spec((n, rows, span, cfg.index_head_dim), dtype),
        "position": spec((rows,), jnp.int32),
        "token": spec((rows,), jnp.int32),
    }


def empty_state(cfg: Glm5Config, rows: int, span: int, dtype):
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
    """Every layer over ``x``: ``attention(lp, u, latent, keys, layer,
    rows)`` is the mixer at the caller's shape (a segment or a token) on the
    leaves whole (:func:`~sparkdl_tpu.models.hybrid.run_layers`)."""
    def mix(lp, u, latent, keys, layer, rows):
        out, latent, keys = attention(lp, u, latent, keys, layer, rows)
        return jnp.dot(out, lp["wo"]), latent, keys

    return run_layers(
        params, cfg.layer_types, x, state, rows,
        {"mla": Mixer(("latent", "index_keys"), mix, in_place=True)},
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
    them real (a pad writes nothing); two pairs may be consecutive segments
    of one row."""
    shape, ix = cfg.mla, cfg.indexer

    def attention(lp, u, latent, keys, layer, rows):
        return dsa.dsa_segment(lp, u, latent, keys, layer, rows, start,
                               lengths, shape, ix)

    return _layers(params, cfg, _embed(params, tokens), state, rows,
                   attention)


# -- entry points -------------------------------------------------------------

def forward_logits(params, cfg: Glm5Config, tokens, lengths):
    """Float32 logits [r, n, V] of whole sequences ``tokens`` [r, n] from an
    empty state; positions at or past ``lengths`` are pads nobody sees."""
    r, n = tokens.shape
    state = empty_state(cfg, r, n, params["embed"].dtype)
    x, _, _ = _segment(params, cfg, state, tokens, None,
                       jnp.zeros((r,), jnp.int32), lengths)
    return _logits(params, cfg, x)


def prefill(params, cfg: Glm5Config, state, tokens, rows, start, lengths):
    """One dispatch of (row, segment) pairs; arguments and results as
    :func:`sparkdl_tpu.models.granite_hybrid.prefill`: ``(state,
    log-probabilities [c, V] float32 of the next token at each pair's last
    real position, counts [L, E])``.  A row's ``position`` and ``token``
    come from its last pair in the dispatch."""
    x, state, counts = _segment(
        params, cfg, state, tokens, rows, start, lengths)
    last = jnp.take_along_axis(
        x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
    logp = jax.nn.log_softmax(_logits(params, cfg, last), axis=-1)
    # pairs of one row come in order of their segments: the later wins
    after = start + lengths
    latest = jnp.zeros_like(state["position"]).at[rows].max(
        after, mode="drop")
    mine = after == latest.at[rows].get(mode="clip")
    kept = jnp.where(mine, rows, state["position"].shape[0])
    state["position"] = state["position"].at[kept].set(after, mode="drop")
    state["token"] = state["token"].at[kept].set(
        jnp.argmax(logp, axis=-1).astype(jnp.int32), mode="drop")
    return state, logp, counts


def decode_step(params, cfg: Glm5Config, state):
    """Every row takes in its ``token`` at its ``position``.  Returns
    ``(state, log-probabilities [rows, V] float32 of the token after it,
    counts [L, E])``; the state's ``token`` is then the likeliest one."""
    position, shape, ix = state["position"], cfg.mla, cfg.indexer

    def attention(lp, u, latent, keys, layer, rows):
        return dsa.dsa_token(lp, u, latent, keys, layer, position, shape, ix)

    x, new, counts = _layers(
        params, cfg, _embed(params, state["token"]), state, None, attention)
    logp = jax.nn.log_softmax(_logits(params, cfg, x), axis=-1)
    new = dict(new, position=position + 1,
               token=jnp.argmax(logp, axis=-1).astype(jnp.int32))
    return new, logp, counts


def decode(params, cfg: Glm5Config, state, steps: int):
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
    return source_digest(mla, dsa, inspect.getmodule(prefill))


class Glm5Model(HybridModel):
    """GLM-5 for an
    :class:`~sparkdl_tpu.transformers.ar_generate.AutoregressiveTransformer`
    (:class:`~sparkdl_tpu.models.hybrid.HybridModel`): programs
    ``glm5_prefill`` and ``glm5_decode``; no recurrent state, the latent
    and index caches (``latent_leaves``, ``index_leaves``) are what a row
    holds, so a prefill dispatch may take several segments of one row
    (``packs_segments``).  Both programs fold the attention over every
    visible slot with the unselected ones masked (``attention_paths``
    ``"masked"``), and the span attributes count that work."""

    name = "glm5"
    family = "glm_moe_dsa"
    config_class = Glm5Config
    latent_leaves = ("latent",)
    index_leaves = ("index_keys",)
    packs_segments = True
    functions = sys.modules[__name__]
    attention_paths = {"prefill": "masked", "decode": "masked"}

    @property
    def dense_layers(self) -> int:
        return self.config.dense

    def keys_fused(self, start, segment: int, span: int) -> int:
        """The cache slots a prefill's attention folds in its kernel, as
        :func:`~sparkdl_tpu.ops.mla.keys_fused` counts them."""
        return mla.keys_fused(self.config.mla, start, segment, span)

    def index_pairs(self, start, segment: int, span: int) -> Tuple[int, int]:
        """``(keys_indexed, keys_selected)`` of a prefill whose pairs of
        ``segment`` positions start at ``start`` under a span of ``span``,
        over the layers: the (query, key) pairs the indexer scores
        (:func:`~sparkdl_tpu.ops.dsa.keys_indexed`), and those the attention
        scores after selection: every slot of the blocks the masked fold
        walks, kept or not (:func:`~sparkdl_tpu.models.hybrid.keys_scored`).
        """
        queries = self.config.num_hidden_layers * segment
        return (queries * dsa.keys_indexed(start, segment, span),
                queries * keys_scored(start, segment, span))

    def slots_selected(self, positions, span: int) -> int:
        """The latent slots a decode dispatch's attention scores after
        selection for rows that take in ``positions``: the masked form
        scores the whole span, kept or not, as :meth:`slots_read` counts."""
        return self.slots_read(positions, span)
