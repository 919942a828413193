"""The plain reference of ``moonlight_16b_a3b-generate``: Moonlight
(``model_type: deepseek_v3``), multi-head latent attention (MLA) in its
PUBLISHED, decompressed form in every layer, a dense SwiGLU after the first
``first_k_dense_replace`` layers and sigmoid-routed experts scaled by
``routed_scaling_factor`` beside shared experts after the others, written
out in ``jax.numpy``, float32, every matmul at ``highest`` precision, one
row at a time, no cache: every position's keys and values are expanded from
its latent, attention runs over the whole row in blocks of queries (a
7k-position float32 score matrix of 16 heads does not fit at once), the
experts are a loop over all of them.  A layer's weights are upcast to
float32 one layer (and one expert) at a time.

It takes nothing the program made: the weights are drawn here from a seed
and rounded to ``dtype``, so that program and reference hold the same values,
and are handed to the program as a pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.  ``x_0 = Embed[ids]``.  For
layer i::

    h = x + MLA(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + Dense(u)                                    (i < k)
    y = h + routed_scaling_factor * MoE(u) + Shared(u)  (i >= k)

and after the last layer ``logits = RMSNorm_f(y) W_head`` (untied).

- **MLA** (``q_lora_rank`` null): ``q = u W_q`` [H, nope + rope] split into
  ``q_nope``, ``q_pe``; ``[c~ | k_pe] = u W_kva``; ``c = RMSNorm_kv(c~)``
  (eps 1e-6: ``kv_a_layernorm`` is built with the norm's default);
  ``[k_nope | v] = c W_kvb`` [H, nope + v]; ``q_pe`` and ``k_pe`` rotated by
  RoPE at ``rope_theta`` as the published ``apply_rotary_pos_emb`` does it
  (``view(d/2, 2).transpose`` then ``rotate_half``), ``k_pe`` ONE head every
  head shares; ``score = (q_nope . k_nope + q_pe . k_pe) /
  sqrt(nope + rope)``, causal softmax; out ``= concat_h(attn_h) W_o``.
- **Dense**: ``(silu(u W_gate) * u W_up) W_down`` of width
  ``intermediate_size``.
- **MoE**: ``s = sigmoid(u W_r)`` over all routed experts; the
  ``num_experts_per_tok`` largest ``s + b`` (``b`` the router's
  ``e_score_correction_bias``; ``n_group`` 1: no group restricts them; ties
  to the lower expert); weights ``s_e / (sum_chosen s + 1e-20) *
  routed_scaling_factor``; expert e ``(silu(u W_gate,e) * u W_up,e)
  W_down,e``; nothing dropped.  **Shared**: the same form, width
  ``n_shared_experts * moe_intermediate_size``, not scaled.

Departures and choices the published config does not settle, all under
``assumed`` in ``chipbench/configs/moonlight_16b_a3b-generate.json``: the
latent norm's eps, the RoPE pairing, the ``1e-20``, greedy sampling, the
seeded initialisation; the loop over experts computes every expert on every
position and weighs positions that did not choose it by zero: the same sum,
and no shape that depends on the routing.

``operand`` (the control): a function applied to both operands of every
matmul, the attention's products included; :func:`fp8_operand` rounds them
to e4m3, the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from chipbench.reference.granite_hybrid import (  # noqa: F401
    _f32,
    _mm,
    fp8_operand,
    log_probs,
    rms_norm,
)

MLA_KEYS = ("in_norm", "wq", "wkva", "kv_norm", "wkvb", "wo")
DENSE_KEYS = ("post_norm", "gate", "up", "down")
FFN_KEYS = ("post_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down")
#: ``kv_a_layernorm``'s eps, as the published modeling code constructs it
KV_NORM_EPS = 1e-6
#: queries a block of the attention (its float32 scores are [H, block, n])
QUERY_BLOCK = 464


def dims(config: dict) -> dict:
    """The sizes the mathematics reads, from the published keys."""
    if config.get("q_lora_rank") is not None:
        raise NotImplementedError("q_lora_rank null (one query projection)")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise NotImplementedError("n_group = topk_group = 1 only")
    if config.get("rope_scaling") is not None:
        raise NotImplementedError("no rope_scaling")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError("the sigmoid router only")
    h = config["num_attention_heads"]
    return {
        "d": config["hidden_size"], "heads": h,
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"], "rank": config["kv_lora_rank"],
        "experts": config["n_routed_experts"],
        "f": config["moe_intermediate_size"],
        "fs": config["n_shared_experts"] * config["moe_intermediate_size"],
        "dense_width": config["intermediate_size"],
        "dense": config.get("first_k_dense_replace", 0),
        "layers": config["num_hidden_layers"],
    }


def layer_types(config: dict) -> list:
    """``"dense"`` for the first ``first_k_dense_replace`` layers, ``"moe"``
    for the others (of the ``num_hidden_layers`` that are run)."""
    s = dims(config)
    return ["dense" if i < s["dense"] else "moe" for i in range(s["layers"])]


def shapes(config: dict) -> dict:
    """The params pytree: the attention of every layer stacked, the dense
    feed-forwards over the first layers, the expert ones over the rest."""
    s = dims(config)
    n, k, d, h = s["layers"], s["dense"], s["d"], s["heads"]
    m, e = n - k, s["experts"]
    return {
        "embed": (config["vocab_size"], d),
        "mla": {
            "in_norm": (n, d), "wq": (n, d, h * (s["nope"] + s["rope"])),
            "wkva": (n, d, s["rank"] + s["rope"]), "kv_norm": (n, s["rank"]),
            "wkvb": (n, s["rank"], h * (s["nope"] + s["v"])),
            "wo": (n, h * s["v"], d),
        },
        "dense": {
            "post_norm": (k, d), "gate": (k, d, s["dense_width"]),
            "up": (k, d, s["dense_width"]), "down": (k, s["dense_width"], d),
        },
        "ffn": {
            "post_norm": (m, d), "router": (m, d, e), "router_bias": (m, e),
            "w_gate": (m, e, d, s["f"]), "w_up": (m, e, d, s["f"]),
            "w_down": (m, e, s["f"], d),
            "shared_gate": (m, d, s["fs"]), "shared_up": (m, d, s["fs"]),
            "shared_down": (m, s["fs"], d),
        },
        "final_norm": (d,),
        "head": (config["vocab_size"], d),
    }


def make_params(config: dict, seed: int, dtype="bfloat16", std: float = 0.02):
    """Seeded weights, drawn on JAX's default device (gigabytes of them at
    the published widths).  Matrices normal(0, std) rounded to ``dtype``,
    but those named in ``config["init_std"]`` (leaf name: its std): the
    configuration gives the query projection ``wq`` its own, so that the
    scores are as peaked as a trained model's and the attention has a say
    in the logits (under 0.02 they spread over thousands of keys almost
    evenly, and what a row attends to hardly moves what comes out); gains
    of one; the router's selection bias normal(0, 0.01) in float32, so that
    selecting by ``s + b`` and weighting by ``s`` are told apart.  ``seed``
    may be any whole number."""
    import jax
    import jax.numpy as jnp

    word = int(np.random.default_rng([int(seed), 41]).integers(0, 2**31 - 1))
    stds = {name: float(v) for name, v in config.get("init_std", {}).items()}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, name):
        if name == "router_bias":
            return 0.01 * jax.random.normal(key, shape, jnp.float32)
        scale = stds.get(name, std)
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def make(tree, key):
        out = {}
        for i, (name, value) in enumerate(sorted(tree.items())):
            sub = jax.random.fold_in(key, i)
            if isinstance(value, dict):
                out[name] = make(value, sub)
            elif "norm" in name:
                out[name] = jnp.ones(value, dtype)
            else:
                out[name] = draw(sub, value, name)
        return out

    return make(shapes(config), jax.random.key(word))


def rope(x, theta):
    """The published ``apply_rotary_pos_emb`` on ``x`` [n, ..., d] at the
    positions ``0 .. n - 1``: the channels de-interleaved (``view(d/2,
    2).transpose(4, 3).reshape``), then ``x cos + rotate_half(x) sin``."""
    import jax.numpy as jnp

    n, d = x.shape[0], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(n, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [n, d]
    cos, sin = (f(emb).reshape(n, *([1] * (x.ndim - 2)), d)
                for f in (jnp.cos, jnp.sin))
    x = x.reshape(*x.shape[:-1], d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(*x.shape[:-2], d)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def mla_mixer(config, lp, u, operand=None):
    """``u`` [n, D] -> [n, D]: every position's keys and values decompressed
    from its latent, causal softmax attention over the whole row in blocks
    of :data:`QUERY_BLOCK` queries."""
    import jax
    import jax.numpy as jnp

    s = dims(config)
    n, h, nope, rp, dv, r = (u.shape[0], s["heads"], s["nope"], s["rope"],
                             s["v"], s["rank"])
    q = _mm(u, _f32(lp["wq"]), operand).reshape(n, h, nope + rp)
    kva = _mm(u, _f32(lp["wkva"]), operand)
    c = rms_norm(kva[:, :r], _f32(lp["kv_norm"]), KV_NORM_EPS)
    kv = _mm(c, _f32(lp["wkvb"]), operand).reshape(n, h, nope + dv)
    theta = float(config["rope_theta"])
    q_pe = rope(q[..., nope:], theta)
    k_pe = rope(kva[:, r:], theta)  # [n, rope]: one head all heads share
    keys = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (n, h, rp))], -1)
    queries = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    values = kv[..., nope:]
    if operand is not None:
        queries, keys, values = (operand(t) for t in (queries, keys, values))
    block = min(QUERY_BLOCK, n)
    blocks = -(-n // block)
    queries = jnp.pad(queries, ((0, blocks * block - n), (0, 0), (0, 0)))
    scale = (nope + rp) ** -0.5

    def attend(i):  # [block, h, dv]: one block of queries over the row
        q = jax.lax.dynamic_slice_in_dim(queries, i * block, block)
        scores = jnp.einsum("ihd,jhd->hij", q, keys, precision="highest")
        causal = (jnp.arange(n)[None, :]
                  <= (i * block + jnp.arange(block))[:, None])
        probs = jax.nn.softmax(
            jnp.where(causal, scores * scale, -jnp.inf), axis=-1)
        if operand is not None:
            probs = operand(probs)
        return jnp.einsum("hij,jhd->ihd", probs, values, precision="highest")

    out = jax.lax.map(attend, jnp.arange(blocks))
    out = out.reshape(blocks * block, h * dv)[:n]
    return _mm(out, _f32(lp["wo"]), operand)


def swiglu(u, gate, up, down, operand=None):
    import jax

    return _mm(jax.nn.silu(_mm(u, _f32(gate), operand))
               * _mm(u, _f32(up), operand), _f32(down), operand)


def route(config, fp, u, operand=None):
    """(weight [n, E] of every routed expert for every position, zero outside
    the top-k of score + selection bias; counts [E])."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.sigmoid(_mm(u, _f32(fp["router"]), operand))
    k = config["num_experts_per_tok"]
    chosen = jnp.argsort(
        -(scores + _f32(fp["router_bias"])), axis=-1, stable=True)[:, :k]
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], chosen].set(True)
    weight = jnp.where(picked, scores, 0.0)
    if config.get("norm_topk_prob", True):
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return (weight * config.get("routed_scaling_factor", 1),
            jnp.sum(picked, axis=0))


def moe(config, fp, u, operand=None):
    """``routed_scaling_factor * MoE(u) + Shared(u)``: a loop over every
    routed expert, and the shared experts."""
    import jax
    import jax.numpy as jnp

    weight = route(config, fp, u, operand)[0]

    def add_expert(e, out):
        down = swiglu(u, fp["w_gate"][e], fp["w_up"][e], fp["w_down"][e],
                      operand)
        return out + weight[:, e, None] * down

    routed = jax.lax.fori_loop(
        0, dims(config)["experts"], add_expert, jnp.zeros_like(u))
    return routed + swiglu(u, fp["shared_gate"], fp["shared_up"],
                           fp["shared_down"], operand)


def layer(config, kind, mp, fp, x, operand=None):
    eps = config["rms_norm_eps"]
    h = x + mla_mixer(config, mp, rms_norm(x, _f32(mp["in_norm"]), eps),
                      operand)
    u = rms_norm(h, _f32(fp["post_norm"]), eps)
    if kind == "dense":
        return h + swiglu(u, fp["gate"], fp["up"], fp["down"], operand)
    return h + moe(config, fp, u, operand)


#: the keys of a configuration file that change the mathematics
_MATH_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
    "q_lora_rank", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "first_k_dense_replace", "routed_scaling_factor", "norm_topk_prob",
    "rms_norm_eps", "rope_theta", "vocab_size", "scoring_func", "n_group",
    "topk_group", "rope_scaling",
)


@functools.lru_cache(maxsize=16)
def _compiled(kind, frozen_config, operand):
    """A layer of one kind, or the head, jitted: plain as written above,
    compiled once a shape instead of dispatched one operation at a time."""
    import jax

    config = json.loads(frozen_config)
    if kind == "head":
        return jax.jit(lambda gain, head, x: _mm(
            rms_norm(x, _f32(gain), config["rms_norm_eps"]),
            _f32(head).T, operand))
    return jax.jit(lambda mp, fp, x: layer(config, kind, mp, fp, x, operand))


def forward(params, config, tokens, want=None, operand=None):
    """Float32 logits [len(want), V] of ONE row ``tokens`` [n] at the
    positions ``want`` (default: all).  Every layer is causal, so positions
    appended after ``want``'s last change nothing it sees."""
    import jax.numpy as jnp

    frozen = json.dumps(
        {k: config[k] for k in _MATH_KEYS if k in config}, sort_keys=True)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _f32(params["embed"][tokens])
    dense = dims(config)["dense"]
    for index, kind in enumerate(layer_types(config)):
        mp = {k: params["mla"][k][index] for k in MLA_KEYS}
        if kind == "dense":
            fp = {k: params["dense"][k][index] for k in DENSE_KEYS}
        else:
            fp = {k: params["ffn"][k][index - dense] for k in FFN_KEYS}
        x = _compiled(kind, frozen, operand)(mp, fp, x)
    if want is not None:
        x = x[jnp.asarray(list(want))]
    return _compiled("head", frozen, operand)(
        params["final_norm"], params["head"], x)


def teacher_forced(params, config, prompt, generated, operand=None,
                   pad_to=None):
    """The float32 log-probabilities [len(generated), V] of the next token
    at every generated position: ONE full forward over ``prompt +
    generated[:-1]``, read at the prompt's last position and at every
    generated position but the last.  ``pad_to``: run the forward on a
    sequence padded with zeros to a multiple of it (fewer distinct shapes to
    compile); the pads lie after everything read."""
    sequence = [int(t) for t in prompt] + [int(t) for t in generated[:-1]]
    at = range(len(prompt) - 1, len(sequence))
    if pad_to:
        sequence = sequence + [0] * (-len(sequence) % pad_to)
    return log_probs(forward(params, config, sequence, at, operand))
