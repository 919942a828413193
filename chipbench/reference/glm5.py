"""The plain reference of ``glm_5_744b_a40b-generate``: GLM-5 (``model_type:
glm_moe_dsa``), multi-head latent attention (MLA) with a low-rank query in
its PUBLISHED, decompressed form, under a learned sparse attention (DSA: a
lightning indexer picks ``index_topk`` keys a query) in every layer, a dense
SwiGLU after the first ``first_k_dense_replace`` layers and sigmoid-routed
experts scaled by ``routed_scaling_factor`` beside a shared expert after the
others, written out in ``jax.numpy``, float32, every matmul at ``highest``
precision, one row at a time, no cache, no kernel.  Every position's keys
and values are expanded from its latent, attention runs over each query's
selected keys, a block of queries and a group of heads at a time (a 30k-row
float32 score matrix of 64 heads does not fit at once), the experts are a
loop over those held here.  A layer's weights are upcast to float32 one
layer (and one expert) at a time.

It takes nothing the program made: the weights are drawn here from a seed
and rounded to ``dtype``, so that program and reference hold the same values,
and are handed to the program as a pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``; ``LayerNorm(x) = (x -
mean) / sqrt(var + eps) * g + b``.  ``x_0 = Embed[ids]``.  For layer i::

    h = x + MLA_DSA(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + Dense(u)                                    (i < k)
    y = h + routed_scaling_factor * MoE(u) + Shared(u)  (i >= k)

and after the last layer ``logits = RMSNorm_f(y) W_head`` (untied).

- **MLA**: ``c_q = RMSNorm_q(u W_qa)`` (eps 1e-6); ``q = c_q W_qb`` [H, nope
  + rope]; ``[c~ | k_pe] = u W_kva``; ``c = RMSNorm_kv(c~)`` (eps 1e-6);
  ``[k_nope | v] = c W_kvb`` [H, nope + v]; RoPE at ``rope_theta`` on
  ``q_pe`` and ``k_pe`` as the published ``apply_rotary_pos_emb`` does it
  with ``rope_interleave`` (``view(d/2, 2).transpose`` then
  ``rotate_half``), ``k_pe`` ONE head every head shares; ``score = (q_nope .
  k_nope + q_pe . k_pe) / sqrt(nope + rope)`` over the selected keys
  ``S_t`` only; out ``= concat_h(softmax . v) W_o``.
- **Indexer** (DeepSeek-V3.2-Exp's ``Indexer``, which ``glm_moe_dsa``
  names): ``q_I = c_q W_qI`` [J, dim]; ``k_I = LayerNorm(u W_kI)`` [dim]
  (eps 1e-6); RoPE on the first ``qk_rope_head_dim`` channels of both (the
  same pairing), the rest as they are; ``w = u W_w * J^(-1/2) *
  dim^(-1/2)``; ``I(t, s) = sum_j w_j ReLU(q_I,j,t . k_I,s)`` for s <= t;
  ``S_t`` = the ``index_topk`` largest (``jax.lax.top_k``: ties to the
  lower position), every s <= t where fewer are visible.
- **Dense**: ``(silu(u W_gate) * u W_up) W_down`` of width
  ``intermediate_size``.
- **MoE**: ``s = sigmoid(u W_r)`` over all ``published.n_routed_experts``;
  the ``num_experts_per_tok`` largest ``s + b`` (ties to the lower expert);
  weights ``s_e / (sum_chosen s + 1e-20) * routed_scaling_factor``; of them
  the experts ``experts_held`` (lo, hi) add their part: expert e ``(silu(u
  W_gate,e) * u W_up,e) W_down,e``.  **Shared**: the same form, width
  ``n_shared_experts * moe_intermediate_size``, not scaled.

Departures from the published model and choices the published config does
not settle, all under ``assumed`` in
``chipbench/configs/glm_5_744b_a40b-generate.json``: the multi-token
prediction layer is left out (``num_nextn_predict_layers`` 0: it drafts
tokens, the main model's greedy tokens are the output); the indexer's keys
and queries are in the configuration's bfloat16, without the fp8 storage and
its Hadamard rotation (orthogonal and applied to both, so the dot products
are the same in exact arithmetic); the norms' eps; the indexer's rope /
no-rope channel order; the tie rule; greedy sampling; the seeded weights.

``operand`` (the control): a function applied to both operands of every
matmul, the attention's and the indexer's products included;
:func:`fp8_operand` rounds them to e4m3, the nearest precision below the
configuration's bfloat16.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from chipbench.reference.moonlight import (  # noqa: F401
    _f32,
    _mm,
    fp8_operand,
    log_probs,
    rms_norm,
    rope,
    swiglu,
)

MLA_KEYS = ("in_norm", "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb",
            "wo", "ix_wq", "ix_wk", "ix_norm", "ix_bias", "ix_w")
DENSE_KEYS = ("post_norm", "gate", "up", "down")
FFN_KEYS = ("post_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down")
#: ``q_a_layernorm``'s and ``kv_a_layernorm``'s eps (the norm's default)
LATENT_NORM_EPS = 1e-6
#: the indexer key's LayerNorm eps (the norm's default)
INDEX_NORM_EPS = 1e-6
#: queries a block of the attention and of the indexer
QUERY_BLOCK = 128
#: heads a group of the attention (its keys and values are expanded a
#: group at a time)
HEAD_GROUP = 16


def dims(config: dict) -> dict:
    """The sizes the mathematics reads, from the published keys."""
    if config.get("q_lora_rank") is None:
        raise NotImplementedError("a low-rank query (q_lora_rank) only")
    if config.get("n_group", 1) != 1 or config.get("topk_group", 1) != 1:
        raise NotImplementedError("n_group = topk_group = 1 only")
    rope_parameters = config.get("rope_parameters") or {}
    if (config.get("rope_scaling") is not None
            or rope_parameters.get("rope_type", "default") != "default"):
        raise NotImplementedError("no rope_scaling")
    if config.get("scoring_func", "sigmoid") != "sigmoid":
        raise NotImplementedError("the sigmoid router only")
    if config.get("num_nextn_predict_layers", 0):
        raise NotImplementedError("no multi-token prediction layer")
    routed = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    lo, hi = config.get("experts_held") or (0, routed)
    if hi - lo != config["n_routed_experts"]:
        raise ValueError(
            f"experts_held {lo, hi} is not the {config['n_routed_experts']} "
            "experts n_routed_experts says are held here")
    return {
        "d": config["hidden_size"], "heads": config["num_attention_heads"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"], "rank": config["kv_lora_rank"],
        "q_rank": config["q_lora_rank"],
        "ix_heads": config["index_n_heads"], "ix_dim": config["index_head_dim"],
        "topk": config["index_topk"],
        "theta": float(rope_parameters.get(
            "rope_theta", config.get("rope_theta", 10000.0))),
        "experts": config["n_routed_experts"], "routed": routed,
        "held": (lo, hi), "f": config["moe_intermediate_size"],
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
    m, e, qr = n - k, s["experts"], s["q_rank"]
    return {
        "embed": (config["vocab_size"], d),
        "mla": {
            "in_norm": (n, d), "wqa": (n, d, qr), "q_norm": (n, qr),
            "wqb": (n, qr, h * (s["nope"] + s["rope"])),
            "wkva": (n, d, s["rank"] + s["rope"]), "kv_norm": (n, s["rank"]),
            "wkvb": (n, s["rank"], h * (s["nope"] + s["v"])),
            "wo": (n, h * s["v"], d),
            "ix_wq": (n, qr, s["ix_heads"] * s["ix_dim"]),
            "ix_wk": (n, d, s["ix_dim"]), "ix_norm": (n, s["ix_dim"]),
            "ix_bias": (n, s["ix_dim"]), "ix_w": (n, d, s["ix_heads"]),
        },
        "dense": {
            "post_norm": (k, d), "gate": (k, d, s["dense_width"]),
            "up": (k, d, s["dense_width"]), "down": (k, s["dense_width"], d),
        },
        "ffn": {
            "post_norm": (m, d), "router": (m, d, s["routed"]),
            "router_bias": (m, s["routed"]),
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
    but those named in ``config["init_std"]`` (leaf name: its std); gains
    of one; the indexer key's LayerNorm bias zero; the router's selection
    bias normal(0, 0.01) in float32.  ``seed`` may be any whole number."""
    import jax
    import jax.numpy as jnp

    word = int(np.random.default_rng([int(seed), 44]).integers(0, 2**31 - 1))
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
            elif name == "ix_bias":
                out[name] = jnp.zeros(value, dtype)
            else:
                out[name] = draw(sub, value, name)
        return out

    return make(shapes(config), jax.random.key(word))


def layer_norm(x, gain, bias, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _rope_first(x, channels, theta):
    """RoPE on the first ``channels`` of ``x`` [n, ..., d], the rest kept."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [rope(x[..., :channels], theta), x[..., channels:]], axis=-1)


def selected(config, lp, u, c_q, operand=None):
    """The indexer's choice: ``S_t`` of every position of the row, as
    indices [n, min(index_topk, n)] (entries past ``t`` where fewer than
    ``index_topk`` keys are visible: the caller masks them by position)."""
    import jax
    import jax.numpy as jnp

    s = dims(config)
    n, J, dim = u.shape[0], s["ix_heads"], s["ix_dim"]
    q = _mm(c_q, _f32(lp["ix_wq"]), operand).reshape(n, J, dim)
    q = _rope_first(q, s["rope"], s["theta"])
    k = layer_norm(_mm(u, _f32(lp["ix_wk"]), operand), _f32(lp["ix_norm"]),
                   _f32(lp["ix_bias"]), INDEX_NORM_EPS)
    k = _rope_first(k, s["rope"], s["theta"])
    w = _mm(u, _f32(lp["ix_w"]), operand) * (J ** -0.5 * dim ** -0.5)
    if operand is not None:
        q, k = operand(q), operand(k)
    block = min(QUERY_BLOCK, n)
    blocks = n // block
    top = min(s["topk"], n)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        wb = jax.lax.dynamic_slice_in_dim(w, i * block, block)
        logits = jax.nn.relu(
            jnp.einsum("ijd,sd->ijs", qb, k, precision="highest"))
        if operand is not None:
            logits, wb = operand(logits), operand(wb)
        score = jnp.einsum("ijs,ij->is", logits, wb, precision="highest")
        causal = (jnp.arange(n)[None, :]
                  <= (i * block + jnp.arange(block))[:, None])
        return jax.lax.top_k(jnp.where(causal, score, -jnp.inf), top)[1]

    return jax.lax.map(one, jnp.arange(blocks)).reshape(n, top)


def mla_dsa_mixer(config, lp, u, operand=None):
    """``u`` [n, D] -> [n, D]: every position's keys and values decompressed
    from its latent, softmax attention of each query over its selected keys,
    in blocks of :data:`QUERY_BLOCK` queries and groups of
    :data:`HEAD_GROUP` heads (``n`` a multiple of the block)."""
    import jax
    import jax.numpy as jnp

    s = dims(config)
    n, h, nope, rp, dv, r = (u.shape[0], s["heads"], s["nope"], s["rope"],
                             s["v"], s["rank"])
    c_q = rms_norm(_mm(u, _f32(lp["wqa"]), operand), _f32(lp["q_norm"]),
                   LATENT_NORM_EPS)
    kva = _mm(u, _f32(lp["wkva"]), operand)
    c = rms_norm(kva[:, :r], _f32(lp["kv_norm"]), LATENT_NORM_EPS)
    k_pe = rope(kva[:, r:], s["theta"])  # [n, rope]: one head all heads share
    chosen = selected(config, lp, u, c_q, operand)
    group = min(HEAD_GROUP, h)
    groups = h // group
    w_qb = _f32(lp["wqb"]).reshape(-1, groups, group, nope + rp)
    w_kvb = _f32(lp["wkvb"]).reshape(r, groups, group, nope + dv)
    w_o = _f32(lp["wo"]).reshape(groups, group * dv, -1)
    block = min(QUERY_BLOCK, n)
    blocks = n // block
    scale = (nope + rp) ** -0.5

    def heads(g, out):
        q = _mm(c_q, w_qb[:, g].reshape(-1, group * (nope + rp)), operand)
        q = q.reshape(n, group, nope + rp)
        kv = _mm(c, w_kvb[:, g].reshape(r, group * (nope + dv)), operand)
        kv = kv.reshape(n, group, nope + dv)
        keys = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (n, group, rp))],
            -1)
        queries = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], s["theta"])], axis=-1)
        values = kv[..., nope:]
        if operand is not None:
            queries, keys, values = (operand(t)
                                     for t in (queries, keys, values))

        def attend(i):  # [block, group, dv]
            qb = jax.lax.dynamic_slice_in_dim(queries, i * block, block)
            at = i * block + jnp.arange(block)
            keep = jnp.zeros((block, n), bool).at[
                jnp.arange(block)[:, None],
                jax.lax.dynamic_slice_in_dim(chosen, i * block, block)
            ].set(True)
            keep = keep & (jnp.arange(n)[None, :] <= at[:, None])
            scores = jnp.einsum("ihd,jhd->hij", qb, keys, precision="highest")
            probs = jax.nn.softmax(
                jnp.where(keep, scores * scale, -jnp.inf), axis=-1)
            if operand is not None:
                probs = operand(probs)
            return jnp.einsum("hij,jhd->ihd", probs, values,
                              precision="highest")

        att = jax.lax.map(attend, jnp.arange(blocks)).reshape(n, group * dv)
        return out + _mm(att, w_o[g], operand)

    return jax.lax.fori_loop(0, groups, heads, jnp.zeros_like(u))


def route(config, fp, u, operand=None):
    """(weight [n, routed] of every routed expert for every position, zero
    outside the top-k of score + selection bias; counts [routed])."""
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


def moe(config, fp, u, operand=None, shared=True):
    """``routed_scaling_factor * MoE(u) + Shared(u)``: a loop over the
    experts held here (``experts_held``), and the shared expert (left out
    where ``shared`` is false)."""
    import jax
    import jax.numpy as jnp

    s = dims(config)
    lo = s["held"][0]
    weight = route(config, fp, u, operand)[0]

    def add_expert(e, out):
        down = swiglu(u, fp["w_gate"][e], fp["w_up"][e], fp["w_down"][e],
                      operand)
        return out + jax.lax.dynamic_index_in_dim(
            weight, lo + e, axis=1, keepdims=True) * down

    routed = jax.lax.fori_loop(0, s["experts"], add_expert, jnp.zeros_like(u))
    if not shared:
        return routed
    return routed + swiglu(u, fp["shared_gate"], fp["shared_up"],
                           fp["shared_down"], operand)


def layer(config, kind, mp, fp, x, operand=None):
    eps = config["rms_norm_eps"]
    h = x + mla_dsa_mixer(
        config, mp, rms_norm(x, _f32(mp["in_norm"]), eps), operand)
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
    "rms_norm_eps", "rope_parameters", "rope_theta", "vocab_size",
    "scoring_func", "n_group", "topk_group", "rope_scaling",
    "index_n_heads", "index_head_dim", "index_topk", "experts_held",
    "published", "num_nextn_predict_layers",
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
    """Float32 logits [len(want), V] of ONE row ``tokens`` [n] (``n`` a
    multiple of :data:`QUERY_BLOCK`, or less than it) at the positions
    ``want`` (default: all).  Every layer is causal, so positions appended
    after ``want``'s last change nothing it sees."""
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
    generated position but the last.  The sequence is padded with zeros to
    a multiple of ``pad_to`` (default: of :data:`QUERY_BLOCK`, where it is
    longer than one); the pads lie after everything read."""
    sequence = [int(t) for t in prompt] + [int(t) for t in generated[:-1]]
    at = range(len(prompt) - 1, len(sequence))
    step = pad_to or QUERY_BLOCK
    if len(sequence) > QUERY_BLOCK or pad_to:
        sequence = sequence + [0] * (-len(sequence) % step)
    return log_probs(forward(params, config, sequence, at, operand))
