"""The plain reference of ``solar_open2_250b-generate``: Solar-Open2
(``model_type: solar_open2``), periods of one gated grouped-query attention
layer and three Kimi Delta Attention layers (KDA, arXiv:2510.26692), each
followed by a sparse-expert feed-forward with a sigmoid router and a shared
expert, written out in ``jax.numpy``, float32, every matmul at ``highest``
precision, one row at a time, no cache, no chunking: the delta rule is the
token-by-token recurrence under ``lax.scan``, attention runs over the whole
row, the experts are a loop over the held ones, one at a time.  A layer's
weights are upcast to float32 one layer (and one expert) at a time, so that
gigabytes of bfloat16 weights fit beside it.

It takes nothing the program made: the weights are drawn here from a seed
and rounded to ``dtype``, so that program and reference hold the same values,
and are handed to the program as a pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.  ``x_0 = Embed[ids]``.  For
layer i::

    h = x + Mixer_i(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + MoE(u) + Shared(u)

and after the last layer ``logits = RMSNorm_f(y) W_head`` (untied).

- **KDA mixer** (layers not in ``gqa_layers``; H = ``linear_attn_config
  .num_heads`` heads, d_k = d_v = its ``head_dim``, R = the low rank, below):
  ``q~ = u W_q``, ``k~ = u W_k``, ``v~ = u W_v`` (no bias); each through its
  own depthwise causal convolution of width ``short_conv_kernel_size`` (no
  bias, zeros before the row's start) and then silu; per head ``q = q~ /
  sqrt(sum q~^2 + 1e-6) * d_k^(-1/2)``, ``k = k~ / sqrt(sum k~^2 + 1e-6)``,
  ``v = v~``.  Log-decay per head and channel ``a_t = -exp(A_log_h) *
  softplus((u W_fa) W_fb + dt_bias)`` (D -> R -> H d_k; ``A_log`` one scalar
  a head), ``alpha_t = exp(a_t)``.  Write strength ``beta_t = 2 sigmoid(u
  W_beta)`` (the 2 is ``kda_allow_neg_eigval``).  State ``S`` [d_k, d_v] a
  head, ``S_{-1} = 0``: ``Sbar_t = Diag(alpha_t) S_{t-1}``; ``S_t = Sbar_t +
  beta_t k_t (v_t - Sbar_t^T k_t)^T``; ``o_t = S_t^T q_t``.  Output gate
  ``z_t = (u W_ga) W_gb + b_g`` (D -> R -> H d_v); per head ``o^_t =
  RMSNorm(o_t; w_o) * sigmoid(z_t)``; out ``= o^ W_o``.
- **Attention mixer** (``gqa_layers``): q (heads x head_dim), k, v (kv heads
  x head_dim) and a gate z (heads x head_dim) by bias-free projections of u;
  no rotary and no position term (``use_rope: false``); query head j reads
  key/value head ``j // (heads / kv)``; scores ``q.k / sqrt(head_dim)``,
  causal softmax; out ``= (attn * sigmoid(z)) W_o``.
- **MoE**: ``s = sigmoid(u W_r)`` over all routed experts; the
  ``num_experts_per_tok`` largest ``s + b`` (``b`` the router's selection
  bias; ties to the lower expert); weights ``s_e / sum_chosen s *
  routed_scaling_factor``; expert e ``(silu(u W_gate,e) * u W_up,e)
  W_down,e``; no capacity, nothing dropped; only the experts of
  ``experts_held`` add their part.  **Shared**: the same form, width
  ``n_shared_experts * moe_intermediate_size``, added for every token.

Departures and choices the published config does not settle, all under
``assumed`` in ``chipbench/configs/solar_open2_250b-generate.json``: the
sigmoid router with a selection bias; the low rank R = ``linear_attn_config
.head_dim`` of the two ``kda_use_full_proj: false`` projections; the
attention gate as an elementwise sigmoid on the attention output before
``W_o``; no q/k norm in the attention layer; the ``1/sqrt(head_dim)``
scales; the seeded initialisation; greedy sampling; ``experts_held`` (lo, hi)
and the sliced vocabulary (the chip's share of the deployment); the loop over
experts computes every held expert on every position and weighs positions
that did not choose it by zero: the same sum, and no shape that depends on
the routing.

``operand`` (the control): a function applied to both operands of every
matmul; :func:`fp8_operand` rounds them to e4m3, the nearest precision below
the configuration's bfloat16.
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

KDA_KEYS = ("in_norm", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "f_a",
            "f_b", "dt_bias", "a_log", "w_beta", "g_a", "g_b", "g_bias",
            "o_norm", "wo")
ATTENTION_KEYS = ("in_norm", "wq", "wk", "wv", "wg", "wo")
FFN_KEYS = ("post_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down")


def dims(config: dict) -> dict:
    """The sizes the mathematics reads, from the published keys."""
    linear = config["linear_attn_config"]
    if linear.get("num_kv_heads") not in (None, linear["num_heads"]):
        raise NotImplementedError("as many key/value heads as heads only")
    if config.get("kda_use_full_proj", False):
        raise NotImplementedError("low-rank decay and gate projections only")
    if config.get("use_rope", False) or config.get("first_k_dense_replace"):
        raise NotImplementedError("no rotary term and no leading dense layer")
    routed = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    lo, hi = config.get("experts_held") or (0, routed)
    if hi - lo != config["n_routed_experts"]:
        raise ValueError(
            f"experts_held {lo, hi} is not the {config['n_routed_experts']} "
            "experts n_routed_experts says are held here")
    kinds = layer_types(config)
    return {
        "d": config["hidden_size"], "heads": linear["num_heads"],
        "dk": linear["head_dim"], "rank": linear["head_dim"],
        "k": linear["short_conv_kernel_size"],
        "inner": linear["num_heads"] * linear["head_dim"],
        "dh": config["head_dim"], "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "routed": routed, "held": (lo, hi),
        "f": config["moe_intermediate_size"],
        "fs": config["n_shared_experts"] * config["moe_intermediate_size"],
        "kda_layers": kinds.count("kda"),
        "attention_layers": kinds.count("attention"),
    }


def layer_types(config: dict) -> list:
    """``"attention"`` for the layers of ``gqa_layers``, ``"kda"`` for the
    others, for the ``num_hidden_layers`` layers that are run."""
    gqa = set(config["gqa_layers"])
    return ["attention" if i in gqa else "kda"
            for i in range(config["num_hidden_layers"])]


def shapes(config: dict) -> dict:
    """The params pytree: the KDA mixers stacked over the KDA layers, the
    attention mixers over the attention layers, the feed-forwards over all."""
    s = dims(config)
    m, a, n = s["kda_layers"], s["attention_layers"], config["num_hidden_layers"]
    d, inner, rank = s["d"], s["inner"], s["rank"]
    held = s["held"][1] - s["held"][0]
    q, kv = s["q_heads"] * s["dh"], s["kv_heads"] * s["dh"]
    return {
        "embed": (config["vocab_size"], d),
        "kda": {
            "in_norm": (m, d), "wq": (m, d, inner), "wk": (m, d, inner),
            "wv": (m, d, inner), "conv_q": (m, inner, s["k"]),
            "conv_k": (m, inner, s["k"]), "conv_v": (m, inner, s["k"]),
            "f_a": (m, d, rank), "f_b": (m, rank, inner),
            "dt_bias": (m, inner), "a_log": (m, s["heads"]),
            "w_beta": (m, d, s["heads"]), "g_a": (m, d, rank),
            "g_b": (m, rank, inner), "g_bias": (m, inner),
            "o_norm": (m, s["dk"]), "wo": (m, inner, d),
        },
        "attention": {
            "in_norm": (a, d), "wq": (a, d, q), "wk": (a, d, kv),
            "wv": (a, d, kv), "wg": (a, d, q), "wo": (a, q, d),
        },
        "ffn": {
            "post_norm": (n, d), "router": (n, d, s["routed"]),
            "router_bias": (n, s["routed"]),
            "w_gate": (n, held, d, s["f"]), "w_up": (n, held, d, s["f"]),
            "w_down": (n, held, s["f"], d),
            "shared_gate": (n, d, s["fs"]), "shared_up": (n, d, s["fs"]),
            "shared_down": (n, s["fs"], d),
        },
        "final_norm": (d,),
        "head": (config["vocab_size"], d),
    }


def make_params(config: dict, seed: int, dtype="bfloat16", std: float = 0.02):
    """Seeded weights, drawn on JAX's default device (gigabytes of them at
    the published widths).  Matrices normal(0, std) rounded to ``dtype``;
    gains of one; ``A_log = log(U[1, 16])`` and ``dt_bias`` the inverse
    softplus of a log-uniform step in [0.001, 0.1] (the published KDA layer's
    initialisation), float32; the depthwise convs U(-1/sqrt(K), 1/sqrt(K))
    (normal(0, std) there would leave q, k and v near zero and the state
    without a say); the output gate's bias zero; the router's selection bias
    normal(0, 0.01) in float32, so that selecting by ``s + b`` and weighting
    by ``s`` are told apart.  ``seed`` may be any whole number."""
    import jax
    import jax.numpy as jnp

    word = int(np.random.default_rng([int(seed), 37]).integers(0, 2**31 - 1))
    bound = dims(config)["k"] ** -0.5

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, name):
        if name.startswith("conv_"):
            return jax.random.uniform(
                key, shape, jnp.float32, -bound, bound).astype(dtype)
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        if name == "router_bias":
            return 0.01 * jax.random.normal(key, shape, jnp.float32)
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def make(tree, key):
        out = {}
        for i, (name, value) in enumerate(sorted(tree.items())):
            sub = jax.random.fold_in(key, i)
            if isinstance(value, dict):
                out[name] = make(value, sub)
            elif "norm" in name:
                out[name] = jnp.ones(value, dtype)
            elif name == "g_bias":
                out[name] = jnp.zeros(value, dtype)
            else:
                out[name] = draw(sub, value, name)
        return out

    return make(shapes(config), jax.random.key(word))


def delta_rule(q, k, v, log_decay, beta):
    """The recurrence one token at a time: ``q``, ``k`` [n, H, K], ``v`` [n,
    H, V], ``log_decay`` [n, H, K], ``beta`` [n, H] -> ``o`` [n, H, V]."""
    import jax
    import jax.numpy as jnp

    def token(state, at):
        q_t, k_t, v_t, a_t, b_t = at
        state = jnp.exp(a_t)[:, :, None] * state  # Diag(alpha) S
        seen = jnp.einsum("hkv,hk->hv", state, k_t, precision="highest")
        state = state + (b_t[:, None] * k_t)[:, :, None] * (
            v_t - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision="highest")

    heads, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(
        token, jnp.zeros((heads, dk, dv), jnp.float32),
        (q, k, v, log_decay, beta))
    return o


def kda_mixer(config, lp, u, operand=None):
    """``u`` [n, D] -> [n, D]."""
    import jax
    import jax.numpy as jnp

    s = dims(config)
    n, heads, dk, width = u.shape[0], s["heads"], s["dk"], s["k"]

    def conv(x, w):
        before = jnp.concatenate([jnp.zeros((width - 1, x.shape[1])), x])
        return jax.nn.silu(sum(
            _f32(w)[:, j] * before[j:j + n] for j in range(width)))

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = (
        conv(_mm(u, _f32(lp[w]), operand), lp[c]).reshape(n, heads, dk)
        for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v")))
    q, k = unit(q) * dk ** -0.5, unit(k)
    step = jax.nn.softplus(
        _mm(_mm(u, _f32(lp["f_a"]), operand), _f32(lp["f_b"]), operand)
        + _f32(lp["dt_bias"])).reshape(n, heads, dk)
    log_decay = -jnp.exp(_f32(lp["a_log"]))[:, None] * step
    strength = 1.0 + bool(config.get("kda_allow_neg_eigval", False))
    beta = strength * jax.nn.sigmoid(_mm(u, _f32(lp["w_beta"]), operand))
    o = delta_rule(q, k, v, log_decay, beta)
    z = (_mm(_mm(u, _f32(lp["g_a"]), operand), _f32(lp["g_b"]), operand)
         + _f32(lp["g_bias"])).reshape(n, heads, dk)
    gated = rms_norm(o, _f32(lp["o_norm"]), config["rms_norm_eps"]) * (
        jax.nn.sigmoid(z))
    return _mm(gated.reshape(n, heads * dk), _f32(lp["wo"]), operand)


def attention_mixer(config, lp, u, operand=None):
    """``u`` [n, D] -> [n, D]: causal softmax attention over the whole row
    (a head at a time, so that a long row's scores fit), no position term, an
    output gate before ``W_o``."""
    import jax
    import jax.numpy as jnp

    s = dims(config)
    n, heads, kv, dh = u.shape[0], s["q_heads"], s["kv_heads"], s["dh"]
    q = _mm(u, _f32(lp["wq"]), operand).reshape(n, heads, dh)
    k = _mm(u, _f32(lp["wk"]), operand).reshape(n, kv, dh)
    v = _mm(u, _f32(lp["wv"]), operand).reshape(n, kv, dh)
    # query head j reads key/value head j // (heads / kv)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    if operand is not None:
        q, k, v = operand(q), operand(k), operand(v)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def head(qkv):  # one head at a time: its scores are [n, n]
        q, k, v = qkv
        scores = jnp.einsum("id,jd->ij", q, k, precision="highest")
        probs = jax.nn.softmax(
            jnp.where(causal, scores * dh ** -0.5, -jnp.inf), axis=-1)
        if operand is not None:
            probs = operand(probs)
        return jnp.einsum("ij,jd->id", probs, v, precision="highest")

    out = jax.lax.map(head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    out = out.transpose(1, 0, 2)
    out = out.reshape(n, heads * dh)
    if config.get("use_gqa_gate", False):
        out = out * jax.nn.sigmoid(_mm(u, _f32(lp["wg"]), operand))
    return _mm(out, _f32(lp["wo"]), operand)


def route(config, fp, u, operand=None):
    """(weight [n, E] of every routed expert for every position: its sigmoid
    score over the sum of the chosen ones', times ``routed_scaling_factor``,
    zero outside the top-k of score + selection bias; counts [E])."""
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
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return (weight * config.get("routed_scaling_factor", 1),
            jnp.sum(picked, axis=0))


def shared_expert(fp, u, operand=None):
    import jax

    return _mm(
        jax.nn.silu(_mm(u, _f32(fp["shared_gate"]), operand))
        * _mm(u, _f32(fp["shared_up"]), operand),
        _f32(fp["shared_down"]), operand)


def feed_forward(config, fp, u, operand=None):
    """``MoE(u) + Shared(u)``: the held experts' part of the routed sum, a
    loop over them (``fp["w_gate"]`` etc. hold the held experts only), and
    the shared expert."""
    import jax
    import jax.numpy as jnp

    lo, hi = dims(config)["held"]
    weight = route(config, fp, u, operand)[0][:, lo:hi]

    def add_expert(e, out):
        gate = _mm(u, _f32(fp["w_gate"][e]), operand)
        up = _mm(u, _f32(fp["w_up"][e]), operand)
        down = _mm(jax.nn.silu(gate) * up, _f32(fp["w_down"][e]), operand)
        return out + weight[:, e, None] * down

    routed = jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(u))
    return routed + shared_expert(fp, u, operand)


def layer(config, kind, mp, fp, x, operand=None):
    eps = config["rms_norm_eps"]
    mixer = kda_mixer if kind == "kda" else attention_mixer
    h = x + mixer(config, mp, rms_norm(x, _f32(mp["in_norm"]), eps), operand)
    return h + feed_forward(
        config, fp, rms_norm(h, _f32(fp["post_norm"]), eps), operand)


#: the keys of a configuration file that change the mathematics
_MATH_KEYS = (
    "hidden_size", "num_hidden_layers", "gqa_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "linear_attn_config",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "moe_intermediate_size", "norm_topk_prob", "routed_scaling_factor",
    "rms_norm_eps", "use_rope", "use_gqa_gate", "kda_use_full_proj",
    "kda_allow_neg_eigval", "first_k_dense_replace", "vocab_size",
    "experts_held", "published",
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
    at = {"kda": 0, "attention": 0}
    keys = {"kda": KDA_KEYS, "attention": ATTENTION_KEYS}
    for index, kind in enumerate(layer_types(config)):
        mp = {k: params[kind][k][at[kind]] for k in keys[kind]}
        fp = {k: params["ffn"][k][index] for k in FFN_KEYS}
        x = _compiled(kind, frozen, operand)(mp, fp, x)
        at[kind] += 1
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
