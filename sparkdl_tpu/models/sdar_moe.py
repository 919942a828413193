"""SDAR-MoE (``model_type: sdar_moe``, JetLM SDAR-30B-A3B): a sparse-expert
decoder that generates by diffusion over blocks, as pure functions over a
params pytree.

Per layer, on the residual stream: ``h = x + Attn(RMSNorm(x))``,
``y = h + MoE(RMSNorm(h))``.  Attention is grouped-query (query head j reads
key/value head j // group), with q and k RMS-normalised over the head and
RoPE (rotate-half, over the whole head) at each token's own position, under
the BLOCK-CAUSAL mask: position i sees j iff ``j // B <= i // B``.  The
feed-forward is :func:`sparkdl_tpu.ops.moe.moe_ffn` in every layer; no shared
expert.  Final RMSNorm, untied head.

The layers are stacked on a leading axis and scanned, so the compile does not
grow with depth.  The experts' weights stay in their stack: the grouped
product is handed all ``L * H`` groups with every other layer's empty, so no
layer's experts are copied out of the stack inside the loop.

Three entry points, all with fixed shapes:

- :func:`forward_logits` — a full forward of whole sequences (tests, small
  uses);
- :func:`prefill` — the keys and values (after norm and RoPE) of a chunk of
  prompts, for the cache; no head;
- :func:`block_step` — one block of generation against the cache: ``steps``
  denoising forwards that fix the most confident masked positions.  The
  first of them also carries the block BEFORE, whose tokens are final,
  through the layers and writes its keys and values into the cache: a
  block's commit rides the next block's first forward, so a block costs
  ``steps`` passes over the weights and a row's last block is never
  committed (nobody reads it).

Softmaxes, the router and the norms' statistics are float32; everything else
runs in the weights' dtype (``computeDtype``, bfloat16 on the chip).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import inspect
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sparkdl_tpu.ops.moe import moe_ffn

#: stands for "not visible" in a score; finite, so that a row that sees
#: nothing (a pad row) softmaxes to a uniform garbage and not to NaN
NEG = -1e30


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    """The published ``config.json`` keys the mathematics reads."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    norm_topk_prob: bool = True
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    #: the half-open range of experts whose weights are held here
    #: (``model-configs`` section 4); None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    @classmethod
    def from_dict(cls, config: Dict[str, Any]) -> "SdarMoeConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in config.items() if k in names}
        if kept.get("experts_held") is not None:
            kept["experts_held"] = tuple(kept["experts_held"])
        return cls(**kept)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)


def param_shapes(cfg: SdarMoeConfig) -> Dict[str, Any]:
    """The params pytree's shapes (the layers stacked on axis 0)."""
    n, d, dh = cfg.num_hidden_layers, cfg.hidden_size, cfg.head_dim
    q, kv = cfg.num_attention_heads * dh, cfg.num_key_value_heads * dh
    held, f = cfg.held[1] - cfg.held[0], cfg.moe_intermediate_size
    return {
        "embed": (cfg.vocab_size, d),
        "layers": {
            "attn_norm": (n, d), "wq": (n, d, q), "wk": (n, d, kv),
            "wv": (n, d, kv), "wo": (n, q, d),
            "q_norm": (n, dh), "k_norm": (n, dh),
            "ffn_norm": (n, d), "router": (n, d, cfg.num_experts),
            "w_gate": (n, held, d, f), "w_up": (n, held, d, f),
            "w_down": (n, held, f, d),
        },
        "final_norm": (d,),
        "head": (d, cfg.vocab_size),
    }


def init_params(cfg: SdarMoeConfig, seed: int = 0, dtype=jnp.bfloat16,
                std: float = 0.02):
    """Seeded random params: normal(0, std) matrices, gains of one."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    made = [
        jnp.ones(shape, dtype) if "norm" in jax.tree_util.keystr(path)
        else (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
        for (path, shape), key in zip(leaves, keys)
    ]
    return jax.tree_util.tree_unflatten(treedef, made)


# -- the pieces -------------------------------------------------------------

def rms_norm(x, gain, eps: float):
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * gain.astype(jnp.float32)).astype(x.dtype)


def rope(x, positions, theta: float):
    """Rotate-half RoPE over the whole head: ``x`` [..., n, heads, dh] at
    ``positions`` [..., n]."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _qkv(cfg: SdarMoeConfig, lp, x, positions):
    """q [r, n, KV, G, dh], k and v [r, n, KV, dh] of one layer, normalised
    and rotated; G query heads read each key/value head."""
    r, n, _ = x.shape
    dh, kv = cfg.head_dim, cfg.num_key_value_heads
    group = cfg.num_attention_heads // kv
    xn = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
    q = jnp.dot(xn, lp["wq"]).reshape(r, n, kv * group, dh)
    k = jnp.dot(xn, lp["wk"]).reshape(r, n, kv, dh)
    v = jnp.dot(xn, lp["wv"]).reshape(r, n, kv, dh)
    q = rope(rms_norm(q, lp["q_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta)
    k = rope(rms_norm(k, lp["k_norm"], cfg.rms_norm_eps), positions,
             cfg.rope_theta)
    return q.reshape(r, n, kv, group, dh), k, v


def _ffn(cfg: SdarMoeConfig, lp, experts, layer, h):
    """``h + MoE(RMSNorm(h))`` and the layer's routing counts."""
    r, n, d = h.shape
    hn = rms_norm(h, lp["ffn_norm"], cfg.rms_norm_eps)
    out, counts = moe_ffn(
        hn.reshape(r * n, d), lp["router"], experts,
        top_k=cfg.num_experts_per_tok, experts_held=cfg.held,
        norm_topk=cfg.norm_topk_prob, stack_index=layer,
    )
    return h + out.reshape(r, n, d), counts


def _split_layers(params):
    """(what the scan slices a layer at a time, the experts' stacks, which
    it leaves whole)."""
    layers = dict(params["layers"])
    experts = {k: layers.pop(k) for k in ("w_gate", "w_up", "w_down")}
    return layers, experts


def _attend(q, k, v, visible):
    """Softmax attention in float32: q [r, n, KV, G, dh], k and v
    [r, m, KV, dh], ``visible`` [r, n, m] -> [r, n, KV*G*dh]."""
    r, n, kv, group, dh = q.shape
    scores = jnp.einsum("rnkgd,rmkd->rkgnm", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (dh ** -0.5)
    scores = jnp.where(visible[:, None, None], scores, NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("rkgnm,rmkd->rnkgd", probs, v)
    return out.reshape(r, n, kv * group * dh)


# -- whole sequences --------------------------------------------------------

def _sequence_layers(params, cfg, tokens, lengths, block_length):
    """The layers over whole sequences ``tokens`` [r, n] (row c real up to
    ``lengths[c]``): the last hidden state and every layer's k and v."""
    r, n = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (r, n))
    blocks = positions // block_length
    visible = (blocks[:, None, :] <= blocks[:, :, None]) & (
        positions[:, None, :] < lengths[:, None, None])
    layers, experts = _split_layers(params)

    def layer(x, scanned):
        lp, index = scanned
        q, k, v = _qkv(cfg, lp, x, positions)
        h = x + jnp.dot(_attend(q, k, v, visible), lp["wo"])
        y, counts = _ffn(cfg, lp, experts, index, h)
        return y, (k, v, counts)

    x = jnp.take(params["embed"], tokens, axis=0)
    index = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    return jax.lax.scan(layer, x, (layers, index))


def _logits(params, cfg, x):
    xn = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.dot(xn, params["head"], preferred_element_type=jnp.float32)


def forward_logits(params, cfg: SdarMoeConfig, tokens, lengths,
                   block_length: int):
    """Float32 logits [r, n, V] of whole sequences under the block-causal
    mask; positions at or past ``lengths`` are pads nobody sees."""
    x, _ = _sequence_layers(params, cfg, tokens, lengths, block_length)
    return _logits(params, cfg, x)


def prefill(params, cfg: SdarMoeConfig, tokens, lengths, block_length: int):
    """``(k, v, counts)``: the cache entries [L, r, KV, n, dh] of a chunk of
    prompts ``tokens`` [r, n] and the routing counts [L, E].  Pad positions
    are routed like any other (their entries are never read)."""
    _, (k, v, counts) = _sequence_layers(
        params, cfg, tokens, lengths, block_length)
    # [L, r, n, KV, dh] -> [L, r, KV, n, dh]: a head's positions contiguous
    return k.transpose(0, 1, 3, 2, 4), v.transpose(0, 1, 3, 2, 4), counts


# -- a block against the cache ----------------------------------------------

def write_block(cache, new, slot):
    """The block's entries ``new`` [L, r, KV, B, dh] into ``cache``
    [L, r, KV, S, dh] at slot ``slot`` of every row: one contiguous update,
    in place where the cache is donated."""
    return jax.lax.dynamic_update_slice(cache, new, (0, 0, 0, slot, 0))


def _block_forward(params, cfg, cache_k, cache_v, prefix, start, where,
                   tokens, pending=None):
    """One denoising forward of a block ``tokens`` [r, B] at positions
    ``start + arange(B)``: every position sees the row's cache and the whole
    block.  Returns (logits [r, B, V], counts [L, E], k, v).

    With ``pending`` [r, B] — the block before, fixed but not yet in the
    cache — the forward runs over both blocks at ``start - B + arange(2 B)``,
    one pass over the weights for the two: the pending positions see the
    cache and their own block (never the new one), the new positions see
    those and themselves.  ``k`` and ``v`` [L, r, KV, B, dh] are then the
    pending block's cache entries (else None); the head runs on the new
    block alone.

    A cache entry carries its position in its rotation, so where it lies is
    free: row c's prompt fills slots ``[0, prefix[c])`` and every row's
    generated blocks follow each other from slot ``where[0]``; those before
    the pending one (or before this one) are in the cache, up to slot
    ``where[1] - B`` (or ``where[1]``) — the same slots in every row, so that
    a commit is one contiguous write."""
    r, b = tokens.shape
    lead = 0 if pending is None else b
    n = lead + b
    if lead:
        tokens = jnp.concatenate([pending.astype(tokens.dtype), tokens], 1)
        of_block = jnp.arange(n, dtype=jnp.int32) // b
        seen = of_block[None, :] <= of_block[:, None]  # [n, n]: block-causal
    span = cache_k.shape[3]
    positions = start[:, None] - lead + jnp.arange(n, dtype=jnp.int32)
    slots = jnp.arange(span, dtype=jnp.int32)[None, :]
    cached = (slots < prefix[:, None]) | (
        (slots >= where[0]) & (slots < where[1] - lead))
    layers, experts = _split_layers(params)

    def layer(x, scanned):
        lp, index = scanned
        q, k, v = _qkv(cfg, lp, x, positions)
        ck = jax.lax.dynamic_index_in_dim(cache_k, index, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cache_v, index, 0, keepdims=False)
        dh = cfg.head_dim
        # the softmax runs over the cache and the block(s) together, in two
        # parts that share their maximum and their denominator
        past = jnp.einsum("rnkgd,rkmd->rkgnm", q, ck,
                          preferred_element_type=jnp.float32) * (dh ** -0.5)
        past = jnp.where(cached[:, None, None, None, :], past, NEG)
        own = jnp.einsum("rnkgd,rmkd->rkgnm", q, k,
                         preferred_element_type=jnp.float32) * (dh ** -0.5)
        if lead:
            own = jnp.where(seen, own, NEG)
        top = jnp.maximum(past.max(-1), own.max(-1))[..., None]
        p_past, p_own = jnp.exp(past - top), jnp.exp(own - top)
        total = p_past.sum(-1) + p_own.sum(-1)
        out = (
            jnp.einsum("rkgnm,rkmd->rkgnd", p_past.astype(cv.dtype), cv,
                       preferred_element_type=jnp.float32)
            + jnp.einsum("rkgnm,rmkd->rkgnd", p_own.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        ) / total[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(r, n, -1).astype(x.dtype)
        h = x + jnp.dot(out, lp["wo"])
        y, counts = _ffn(cfg, lp, experts, index, h)
        entries = (k[:, :lead].transpose(0, 2, 1, 3),
                   v[:, :lead].transpose(0, 2, 1, 3)) if lead else ()
        return y, (counts, *entries)

    x = jnp.take(params["embed"], tokens, axis=0)
    index = jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32)
    x, (counts, *entries) = jax.lax.scan(layer, x, (layers, index))
    k, v = entries or (None, None)
    return _logits(params, cfg, x[:, lead:]), counts, k, v


def fix_most_confident(logits, tokens, masked, steps_left, mask_id: int):
    """One denoising step's decision.  Of the still-masked positions of each
    row, the ``ceil(masked / steps_left)`` whose greedy token has the highest
    softmax probability are fixed, ties to the lower position; the mask
    token itself is never predicted.

    ``logits`` [r, B, V] float32, ``tokens`` [r, B], ``masked`` [r, B] bool;
    ``steps_left`` an int or an int32 scalar (the step loop's counter).
    Returns (tokens, masked, fixed-now [r, B] bool, log-probability [r, B]
    of each position's greedy token)."""
    b = tokens.shape[1]
    is_mask = jnp.arange(logits.shape[-1]) == mask_id
    logits = jnp.where(is_mask, -jnp.inf, logits)
    greedy = jnp.argmax(logits, axis=-1).astype(tokens.dtype)
    logprob = jnp.max(logits, axis=-1) - jax.nn.logsumexp(logits, axis=-1)
    n_masked = jnp.sum(masked, axis=-1)
    n_fix = (n_masked + steps_left - 1) // steps_left
    conf = jnp.where(masked, logprob, -jnp.inf)
    lower = jnp.arange(b)[None, :, None] > jnp.arange(b)[None, None, :]
    # ahead[r, i, j]: position j is taken before position i
    ahead = (conf[:, None, :] > conf[:, :, None]) | (
        (conf[:, None, :] == conf[:, :, None]) & lower)
    rank = jnp.sum(ahead & masked[:, None, :], axis=-1)
    fixed = masked & (rank < n_fix[:, None])
    return (jnp.where(fixed, greedy, tokens), masked & ~fixed, fixed, logprob)


def block_step(params, cfg: SdarMoeConfig, cache_k, cache_v, prefix, start,
               where, tokens, known, pending=None, *, steps: int,
               mask_id: int):
    """Generate one block for every row against the cache, and commit the
    block before it on the way.

    ``prefix`` [r]: the slots each row's prompt fills; ``start`` [r]: the
    block's first position; ``where`` [2]: the slot the generated blocks
    begin at and the slot this one will go to (see :func:`_block_forward`).
    ``tokens`` [r, B] holds the known positions' tokens (a prompt's last
    ``P mod B`` open its first block), ``known`` [r, B] says which; the rest
    start as ``mask_id``.  ``pending`` [r, B] holds the final tokens of the
    block before (the ``record[0]`` of its step), which are not in the cache
    yet; None for a row's first block.

    ``steps`` denoising forwards and nothing after them.  The first runs
    over the pending block and this one together and writes the pending
    block's keys and values into the cache at slot ``where[1] - B``: the
    entries a forward of that block alone would give, at one pass over the
    weights for the two.  The others run against the cache, which now holds
    the pending block.  This block's own tokens are committed by the next
    step — or never, when no block follows: nobody would read them.

    Returns ``(cache_k, cache_v, start + B, where + (0, B), record)`` with
    ``record`` = (tokens [r, B], the step each position was fixed at [r, B]
    (-1: known), the log-probability it was fixed with [r, B] float32,
    routing counts [L, E] summed over the ``steps`` forwards, the pending
    block's tokens among the first's).
    """
    b = tokens.shape[1]
    tokens = jnp.where(known, tokens, mask_id).astype(jnp.int32)
    state = (tokens, ~known, jnp.full(tokens.shape, -1, jnp.int32),
             jnp.zeros(tokens.shape, jnp.float32),
             jnp.zeros((cfg.num_hidden_layers, cfg.num_experts), jnp.int32))

    def denoise(step, state, cache_k, cache_v, pending=None):
        tokens, masked, fixed_at, fixed_lp, routed = state
        logits, counts, k, v = _block_forward(
            params, cfg, cache_k, cache_v, prefix, start, where, tokens,
            pending)
        tokens, masked, fixed, logprob = fix_most_confident(
            logits, tokens, masked, steps - step, mask_id)
        return (tokens, masked, jnp.where(fixed, step, fixed_at),
                jnp.where(fixed, logprob, fixed_lp), routed + counts), k, v

    first = 0
    if pending is not None:
        state, k, v = denoise(0, state, cache_k, cache_v, pending)
        cache_k = write_block(cache_k, k, where[1] - b)
        cache_v = write_block(cache_v, v, where[1] - b)
        first = 1
    # the forwards of one shape are ONE loop body, not a copy a step: the
    # executable holds each layer scan (and its kernels) once, and loads
    # and compiles in that much less time
    state = jax.lax.fori_loop(
        first, steps,
        lambda step, state: denoise(step, state, cache_k, cache_v)[0], state)
    tokens, _, fixed_at, fixed_lp, routed = state
    return (cache_k, cache_v, start + b,
            where + jnp.array([0, b], where.dtype),
            (tokens, fixed_at, fixed_lp, routed))


# -- what a stage takes -----------------------------------------------------

@functools.lru_cache(maxsize=1)
def _source_digest() -> str:
    """Identifies this mathematics in a program's fingerprint: an executable
    kept on disk must not outlive a change to the functions it compiled."""
    from sparkdl_tpu.ops import moe

    text = inspect.getsource(moe) + inspect.getsource(inspect.getmodule(prefill))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


class SdarMoeModel:
    """The ``model`` of a
    :class:`~sparkdl_tpu.transformers.block_diffusion.BlockDiffusionTransformer`:
    the decoder's functions bound to a config, and the params they run on.
    The params are arguments of every program, never constants in one, so
    two models of one config share their executables."""

    def __init__(self, config, params):
        self.config = (
            config if isinstance(config, SdarMoeConfig)
            else SdarMoeConfig.from_dict(config)
        )
        self.params = params

    @property
    def fingerprint(self) -> str:
        return f"sdar_moe:{_source_digest()}:{self.config}"

    @property
    def experts_per_token(self) -> int:
        return self.config.num_experts_per_tok

    @property
    def experts_held(self):
        return self.config.held

    def cache_spec(self, rows: int, span: int):
        """Shape and dtype of the key (and of the value) cache."""
        cfg = self.config
        return (
            (cfg.num_hidden_layers, rows, cfg.num_key_value_heads, span,
             cfg.head_dim),
            self.params["embed"].dtype,
        )

    def prefill(self, params, tokens, lengths, block_length: int):
        return prefill(params, self.config, tokens, lengths, block_length)

    def block_step(self, params, cache_k, cache_v, prefix, start, where,
                   tokens, known, pending, steps: int, mask_id: int):
        return block_step(params, self.config, cache_k, cache_v, prefix,
                          start, where, tokens, known, pending, steps=steps,
                          mask_id=mask_id)
