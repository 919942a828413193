"""The plain reference of ``granite_4.0_h_small-generate``: Granite 4.0-H
(``model_type: granitemoehybrid``), a stack of Mamba-2 and attention layers
with a sparse-expert feed-forward and a shared expert in every layer, written
out in ``jax.numpy``, float32, every matmul at ``highest`` precision, one row
at a time, no cache, no chunking: the state-space layer is the token-by-token
recurrence under ``lax.scan``, attention runs over the whole row, the experts
are a loop over the held ones, one at a time.  A layer's weights are upcast
to float32 one layer (and one expert) at a time, so that gigabytes of
bfloat16 weights fit beside it.

It takes nothing the program made: the weights are drawn here from a seed
and rounded to ``dtype``, so that program and reference hold the same values,
and are handed to the program as a pytree.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.  ``x_0 = embedding_multiplier
* Embed[ids]``.  For layer i of kind ``layer_types[i]``, with ``r =
residual_multiplier``::

    h = x + r * Mixer(RMSNorm_in(x));  u = RMSNorm_post(h)
    y = h + r * (MoE(u) + Shared(u))

and after the last layer ``logits = RMSNorm_f(y) Embed^T / logits_scaling``
(tied embeddings).

- **Mamba-2 mixer** (``d_inner = mamba_expand * hidden = mamba_n_heads *
  mamba_d_head``; N = ``mamba_d_state``; one group; conv width ``C = d_inner +
  2 N``): ``[z | xBC | dt] = u W_in`` (widths d_inner | C | heads, no bias);
  ``xBC_t <- silu(b_c + sum_{j<K} w_c[:, j] * xBC_{t-(K-1)+j})`` (depthwise,
  causal, zeros before the row's start); ``[x | B | C] = xBC`` (d_inner | N |
  N); per head ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``;
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t`` ([d_head, N] a head,
  ``S_{-1} = 0``); ``y_t = S_t C_t + D x_t``; gated norm over all of d_inner
  at once: ``g = y * silu(z)``, ``o = g / sqrt(mean(g^2) + eps) * w_n``; out
  ``= o W_out``.
- **Attention mixer**: q (heads x head_dim), k and v (kv heads x head_dim)
  by bias-free projections; NO position term (``position_embedding_type:
  nope``); query head j reads key/value head ``j // (heads / kv)``; scores
  ``q.k * attention_multiplier`` (not ``1/sqrt(head_dim)``), causal softmax;
  ``W_o``.
- **MoE**: ``l = u W_r`` over all routed experts; the ``num_experts_per_tok``
  largest ``l`` (ties to the lower expert); weights: softmax over those;
  expert e: ``(silu(u W_gate,e) * u W_up,e) W_down,e`` (``[W_gate,e |
  W_up,e]`` the two halves of the published ``input_linear``); no capacity,
  nothing dropped; only the experts of ``experts_held`` add their part.
  **Shared**: ``(silu(u W_a) * u W_b) W_c``, added for every token.

Departures, all under ``assumed`` in
``chipbench/configs/granite_4.0_h_small-generate.json``: the width of one
expert is read from ``intermediate_size``; ``head_dim = hidden / heads``; the
seeded initialisation; greedy sampling; ``experts_held`` (lo, hi) and the
sliced vocabulary (the chip's share of the deployment); the loop over experts
computes every held expert on every position and weighs positions that did
not choose it by zero: the same sum, and no shape that depends on the routing.

``operand`` (the control): a function applied to both operands of every
matmul; :func:`fp8_operand` rounds them to e4m3, the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import json

import numpy as np

from chipbench.reference.sdar_moe import fp8_operand  # noqa: F401

MAMBA_KEYS = ("in_norm", "w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d",
              "gate_norm", "w_out")
ATTENTION_KEYS = ("in_norm", "wq", "wk", "wv", "wo")
FFN_KEYS = ("post_norm", "router", "w_gate", "w_up", "w_down", "shared_gate",
            "shared_up", "shared_down")


def dims(config: dict) -> dict:
    """The sizes the mathematics reads, from the published keys."""
    d = config["hidden_size"]
    heads, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
                   config["mamba_d_state"])
    inner = config["mamba_expand"] * d
    if inner != heads * p:
        raise ValueError(f"mamba_expand * hidden_size = {inner} is not "
                         f"mamba_n_heads * mamba_d_head = {heads * p}")
    if config.get("mamba_n_groups", 1) != 1:
        raise NotImplementedError("one group of B and C only")
    routed = config.get("published", {}).get(
        "num_local_experts", config["num_local_experts"])
    lo, hi = config.get("experts_held") or (0, routed)
    if hi - lo != config["num_local_experts"]:
        raise ValueError(
            f"experts_held {lo, hi} is not the {config['num_local_experts']} "
            "experts num_local_experts says are held here")
    return {
        "d": d, "inner": inner, "heads": heads, "p": p, "n": n,
        "conv": inner + 2 * n, "k": config["mamba_d_conv"],
        "dh": config.get("head_dim") or d // config["num_attention_heads"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "routed": routed, "held": (lo, hi),
        "f": config["intermediate_size"],
        "fs": config["shared_intermediate_size"],
        "mamba_layers": sum(t == "mamba" for t in layer_types(config)),
        "attention_layers": sum(t == "attention" for t in layer_types(config)),
    }


def layer_types(config: dict) -> list:
    return list(config["layer_types"][:config["num_hidden_layers"]])


def shapes(config: dict) -> dict:
    """The params pytree: the Mamba mixers stacked over the Mamba layers, the
    attention mixers over the attention layers, the feed-forwards over all."""
    s = dims(config)
    m, a, n = s["mamba_layers"], s["attention_layers"], config["num_hidden_layers"]
    d, held = s["d"], s["held"][1] - s["held"][0]
    q, kv = s["q_heads"] * s["dh"], s["kv_heads"] * s["dh"]
    return {
        "embed": (config["vocab_size"], d),
        "mamba": {
            "in_norm": (m, d),
            "w_in": (m, d, s["inner"] + s["conv"] + s["heads"]),
            "conv_w": (m, s["conv"], s["k"]), "conv_b": (m, s["conv"]),
            "dt_bias": (m, s["heads"]), "a_log": (m, s["heads"]),
            "d": (m, s["heads"]), "gate_norm": (m, s["inner"]),
            "w_out": (m, s["inner"], d),
        },
        "attention": {
            "in_norm": (a, d), "wq": (a, d, q), "wk": (a, d, kv),
            "wv": (a, d, kv), "wo": (a, q, d),
        },
        "ffn": {
            "post_norm": (n, d), "router": (n, d, s["routed"]),
            "w_gate": (n, held, d, s["f"]), "w_up": (n, held, d, s["f"]),
            "w_down": (n, held, s["f"], d),
            "shared_gate": (n, d, s["fs"]), "shared_up": (n, d, s["fs"]),
            "shared_down": (n, s["fs"], d),
        },
        "final_norm": (d,),
    }


def make_params(config: dict, seed: int, dtype="bfloat16", std: float = 0.02):
    """Seeded weights, drawn on JAX's default device (gigabytes of them at
    the published widths).  Matrices normal(0, std) rounded to ``dtype``;
    gains of one; ``D`` = 1; ``A_log = log(U[1, 16])``; ``dt_bias`` the
    inverse softplus of a log-uniform step in [0.001, 0.1]; the depthwise
    conv's weight and bias U(-1/sqrt(K), 1/sqrt(K)) — the Mamba-2 reference
    initialisation (``config.json`` gives none).  The three per-head vectors
    stay float32 (they feed exponentials).  ``seed`` may be any whole
    number."""
    import jax
    import jax.numpy as jnp

    word = int(np.random.default_rng([int(seed), 35]).integers(0, 2**31 - 1))
    bound = config["mamba_d_conv"] ** -0.5

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def draw(key, shape, name):
        if name in ("conv_w", "conv_b"):
            return jax.random.uniform(
                key, shape, jnp.float32, -bound, bound).astype(dtype)
        if name == "a_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1., 16.))
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def make(tree, key):
        out = {}
        for i, (name, value) in enumerate(sorted(tree.items())):
            sub = jax.random.fold_in(key, i)
            if isinstance(value, dict):
                out[name] = make(value, sub)
            elif "norm" in name:
                out[name] = jnp.ones(value, dtype)
            elif name == "d":
                out[name] = jnp.ones(value, jnp.float32)
            else:
                out[name] = draw(sub, value, name)
        return out

    return make(shapes(config), jax.random.key(word))


def _mm(a, b, operand):
    import jax.numpy as jnp

    if operand is not None:
        a, b = operand(a), operand(b)
    return jnp.matmul(a, b, precision="highest")


def _f32(x):
    import jax.numpy as jnp

    return jnp.asarray(x, jnp.float32)


def rms_norm(x, gain, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def mamba_mixer(config, lp, u, operand=None):
    """``u`` [n, D] -> [n, D]: the recurrence one token at a time."""
    import jax
    import jax.numpy as jnp

    s = dims(config)
    n, heads, p, k = u.shape[0], s["heads"], s["p"], s["k"]
    proj = _mm(u, _f32(lp["w_in"]), operand)
    z = proj[:, :s["inner"]]
    xbc = proj[:, s["inner"]:s["inner"] + s["conv"]]
    dt = proj[:, s["inner"] + s["conv"]:]
    before = jnp.concatenate([jnp.zeros((k - 1, s["conv"])), xbc], axis=0)
    w = _f32(lp["conv_w"])
    xbc = jax.nn.silu(_f32(lp["conv_b"]) + sum(
        w[:, j] * before[j:j + n] for j in range(k)))
    x = xbc[:, :s["inner"]].reshape(n, heads, p)
    b = xbc[:, s["inner"]:s["inner"] + s["n"]]
    c = xbc[:, s["inner"] + s["n"]:]
    dt = jax.nn.softplus(dt + _f32(lp["dt_bias"]))  # [n, heads]
    a = -jnp.exp(_f32(lp["a_log"]))
    skip = _f32(lp["d"])

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y_t = jnp.einsum("hpn,n->hp", state, c_t, precision="highest")
        return state, y_t + skip[:, None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((heads, p, s["n"]), jnp.float32), (x, b, c, dt))
    g = y.reshape(n, s["inner"]) * jax.nn.silu(z)
    o = rms_norm(g, _f32(lp["gate_norm"]), config["rms_norm_eps"])
    return _mm(o, _f32(lp["w_out"]), operand)


def attention_mixer(config, lp, u, operand=None):
    """``u`` [n, D] -> [n, D]: causal softmax attention over the whole row,
    no position term."""
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
        q, k = operand(q), operand(k)
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision="highest")
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    probs = jax.nn.softmax(jnp.where(
        causal, scores * config["attention_multiplier"], -jnp.inf), axis=-1)
    if operand is not None:
        probs, v = operand(probs), operand(v)
    out = jnp.einsum("hij,jhd->ihd", probs, v, precision="highest")
    return _mm(out.reshape(n, heads * dh), _f32(lp["wo"]), operand)


def route(config, fp, u, operand=None):
    """(weight [n, E] of every routed expert for every position: the softmax
    over the position's top-k logits, zero outside them; counts [E])."""
    import jax
    import jax.numpy as jnp

    logits = _mm(u, _f32(fp["router"]), operand)
    k = config["num_experts_per_tok"]
    chosen = jnp.argsort(-logits, axis=-1, stable=True)[:, :k]
    picked = jnp.zeros(logits.shape, bool).at[
        jnp.arange(logits.shape[0])[:, None], chosen].set(True)
    weight = jax.nn.softmax(jnp.where(picked, logits, -jnp.inf), axis=-1)
    return weight, jnp.sum(picked, axis=0)


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
    shared = _mm(
        jax.nn.silu(_mm(u, _f32(fp["shared_gate"]), operand))
        * _mm(u, _f32(fp["shared_up"]), operand),
        _f32(fp["shared_down"]), operand)
    return routed + shared


def layer(config, kind, mp, fp, x, operand=None):
    eps, r = config["rms_norm_eps"], config["residual_multiplier"]
    mixer = mamba_mixer if kind == "mamba" else attention_mixer
    h = x + r * mixer(config, mp, rms_norm(x, _f32(mp["in_norm"]), eps),
                      operand)
    u = rms_norm(h, _f32(fp["post_norm"]), eps)
    return h + r * feed_forward(config, fp, u, operand)


#: the keys of a configuration file that change the mathematics
_MATH_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_types", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_local_experts",
    "num_experts_per_tok", "intermediate_size", "shared_intermediate_size",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
    "mamba_expand", "mamba_n_groups", "attention_multiplier",
    "embedding_multiplier", "residual_multiplier", "logits_scaling",
    "rms_norm_eps", "vocab_size", "experts_held", "published",
)


@functools.lru_cache(maxsize=16)
def _compiled(kind, frozen_config, operand):
    """A layer of one kind, or the head, jitted: plain as written above,
    compiled once a shape instead of dispatched one operation at a time."""
    import jax

    config = json.loads(frozen_config)
    if kind == "head":
        return jax.jit(lambda gain, embed, x: _mm(
            rms_norm(x, _f32(gain), config["rms_norm_eps"]),
            _f32(embed).T, operand) / config["logits_scaling"])
    return jax.jit(lambda mp, fp, x: layer(config, kind, mp, fp, x, operand))


def forward(params, config, tokens, want=None, operand=None):
    """Float32 logits [len(want), V] of ONE row ``tokens`` [n] at the
    positions ``want`` (default: all).  Every layer is causal, so positions
    appended after ``want``'s last change nothing it sees."""
    import jax.numpy as jnp

    frozen = json.dumps(
        {k: config[k] for k in _MATH_KEYS if k in config}, sort_keys=True)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = config["embedding_multiplier"] * _f32(params["embed"][tokens])
    at = {"mamba": 0, "attention": 0}
    keys = {"mamba": MAMBA_KEYS, "attention": ATTENTION_KEYS}
    for index, kind in enumerate(layer_types(config)):
        mp = {k: params[kind][k][at[kind]] for k in keys[kind]}
        fp = {k: params["ffn"][k][index] for k in FFN_KEYS}
        x = _compiled(kind, frozen, operand)(mp, fp, x)
        at[kind] += 1
    if want is not None:
        x = x[jnp.asarray(list(want))]
    return _compiled("head", frozen, operand)(
        params["final_norm"], params["embed"], x)


def log_probs(logits):
    """Float32 log-softmax [n, V]."""
    import jax

    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


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
