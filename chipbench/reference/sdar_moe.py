"""The plain reference of ``sdar_30b_a3b-blockdiffusion``: SDAR-MoE
(``model_type: sdar_moe``) and its generation by diffusion over blocks,
written out in ``jax.numpy``, float32, every matmul at ``highest`` precision,
one row at a time, no cache (every step is a full forward over all positions
so far under the block-causal mask), a loop over the experts, one at a time.

It takes nothing the program made: the weights are drawn here from a seed and
rounded to bfloat16, so that program and reference hold the same values, and
are handed to the program as a pytree.

Per layer, on a residual stream x [n, D]: ``h = x + Attn(RMSNorm(x))``,
``y = h + MoE(RMSNorm(h))``; ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``.
Attention: q = xWq -> H heads of dh, k = xWk and v = xWv -> KV heads of dh,
no biases; q and k are RMS-normalised over the head with learned gains; RoPE
(theta, rotate-half over the whole head) at each token's position; query
head j reads key/value head j // (H / KV); scores q.k / sqrt(dh), softmax
under the block-causal mask (i sees j iff j // B <= i // B); output through
Wo.  MoE: p = softmax(xWr) over all experts; the top-k, weights p_e / sum of
the top-k's p; out = sum_e w_e * (silu(x Wgate_e) * x Wup_e) Wdown_e; no
capacity, no token dropped, no shared expert.  Final RMSNorm, logits =
x Wout.

Departures from the published model, all listed under ``assumed`` in
``chipbench/configs/sdar_30b_a3b-blockdiffusion.json``:

- q/k norm is the Qwen3 family's convention (the config does not say);
- the mask token's logit is set to -inf before the softmax, so that a
  position is never fixed to ``[MASK]`` (with trained weights that does not
  arise; with random weights it would, once in ~150,000 positions);
- ``experts_held`` (lo, hi): only these experts' parts are added, as the
  program's layer is told which experts it holds; here all of them;
- the loop over experts computes every expert on every position and weighs
  positions that did not choose it by zero: the same sum, and no shape that
  depends on the routing.

``operand`` (the control): a function applied to both operands of every
matmul; :func:`fp8_operand` rounds them to e4m3, the nearest precision below
the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import numpy as np

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "ffn_norm", "router", "w_gate", "w_up", "w_down")


def shapes(config: dict) -> dict:
    n, d, dh = (config["num_hidden_layers"], config["hidden_size"],
                config["head_dim"])
    q = config["num_attention_heads"] * dh
    kv = config["num_key_value_heads"] * dh
    e, f = config["num_experts"], config["moe_intermediate_size"]
    return {
        "embed": (config["vocab_size"], d),
        "layers": {
            "attn_norm": (n, d), "wq": (n, d, q), "wk": (n, d, kv),
            "wv": (n, d, kv), "wo": (n, q, d), "q_norm": (n, dh),
            "k_norm": (n, dh), "ffn_norm": (n, d), "router": (n, d, e),
            "w_gate": (n, e, d, f), "w_up": (n, e, d, f),
            "w_down": (n, e, f, d),
        },
        "final_norm": (d,),
        "head": (d, config["vocab_size"]),
    }


def make_params(config: dict, seed: int, dtype="bfloat16", std: float = 0.02):
    """Seeded weights, drawn on JAX's default device (gigabytes of them at
    the published widths): normal(0, std) matrices rounded to ``dtype``,
    gains of one.  ``seed`` may be any whole number."""
    import jax
    import jax.numpy as jnp

    word = int(np.random.default_rng([int(seed), 31]).integers(0, 2**31 - 1))
    key = jax.random.key(word)

    @functools.partial(jax.jit, static_argnums=(1,))
    def draw(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    def make(tree, key):
        out = {}
        for i, (name, value) in enumerate(sorted(tree.items())):
            sub = jax.random.fold_in(key, i)
            if isinstance(value, dict):
                out[name] = make(value, sub)
            elif "norm" in name:
                out[name] = jnp.ones(value, dtype)
            else:
                out[name] = draw(sub, value)
        return out

    return make(shapes(config), key)


def fp8_operand(x):
    """Round to float8_e4m3fn with one scale per tensor, and back."""
    import jax.numpy as jnp

    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


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


def rope(x, positions, theta):
    """x [n, heads, dh]; rotate-half over the whole head."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(config, lp, x, block_length, operand):
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    heads, kv, dh = (config["num_attention_heads"],
                     config["num_key_value_heads"], config["head_dim"])
    eps = config["rms_norm_eps"]
    positions = jnp.arange(n)
    q = _mm(x, _f32(lp["wq"]), operand).reshape(n, heads, dh)
    k = _mm(x, _f32(lp["wk"]), operand).reshape(n, kv, dh)
    v = _mm(x, _f32(lp["wv"]), operand).reshape(n, kv, dh)
    q = rope(rms_norm(q, _f32(lp["q_norm"]), eps), positions,
             config["rope_theta"])
    k = rope(rms_norm(k, _f32(lp["k_norm"]), eps), positions,
             config["rope_theta"])
    blocks = positions // block_length
    visible = blocks[None, :] <= blocks[:, None]  # [i, j]
    # query head j reads key/value head j // (heads / kv)
    k, v = (jnp.repeat(t, heads // kv, axis=1) for t in (k, v))
    if operand is not None:
        q, k = operand(q), operand(k)
    scores = jnp.einsum("ihd,jhd->hij", q, k, precision="highest")
    probs = jax.nn.softmax(
        jnp.where(visible, scores / math.sqrt(dh), -jnp.inf), axis=-1)
    if operand is not None:
        probs, v = operand(probs), operand(v)
    out = jnp.einsum("hij,jhd->ihd", probs, v, precision="highest")
    return _mm(out.reshape(n, heads * dh), _f32(lp["wo"]), operand)


def route(config, lp, x, operand):
    """(weight [n, E] of every expert for every position: p_e / the sum over
    the position's top-k, and zero outside the top-k; counts [E])."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(_mm(x, _f32(lp["router"]), operand), axis=-1)
    k = config["num_experts_per_tok"]
    # ties go to the lower expert
    chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :k]
    picked = jnp.zeros(probs.shape, bool).at[
        jnp.arange(probs.shape[0])[:, None], chosen].set(True)
    weight = jnp.where(picked, probs, 0.0)
    if config.get("norm_topk_prob", True):
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return weight, jnp.sum(picked, axis=0)


def moe(config, lp, x, operand=None, experts_held=None):
    """The held experts' part of the layer's feed-forward: a loop over them,
    one expert at a time on every position (``jax.lax.fori_loop``, so that
    the loop compiles once and not once an expert).  ``lp["w_gate"]`` etc.
    hold the held experts only."""
    import jax
    import jax.numpy as jnp

    weight, _ = route(config, lp, x, operand)
    lo, hi = experts_held or (0, config["num_experts"])
    weight = weight[:, lo:hi]

    def add_expert(e, out):
        gate = _mm(x, _f32(lp["w_gate"][e]), operand)
        up = _mm(x, _f32(lp["w_up"][e]), operand)
        down = _mm(jax.nn.silu(gate) * up, _f32(lp["w_down"][e]), operand)
        return out + weight[:, e, None] * down

    return jax.lax.fori_loop(0, hi - lo, add_expert, jnp.zeros_like(x))


def layer(config, lp, x, block_length, operand=None, experts_held=None):
    eps = config["rms_norm_eps"]
    h = x + attention(config, lp, rms_norm(x, _f32(lp["attn_norm"]), eps),
                      block_length, operand)
    return h + moe(config, lp, rms_norm(h, _f32(lp["ffn_norm"]), eps),
                   operand, experts_held)


@functools.lru_cache(maxsize=16)
def _compiled(kind, frozen_config, block_length, operand, experts_held):
    """``layer`` or the head, jitted: plain as written above, compiled once
    a shape instead of dispatched one operation at a time."""
    import jax

    config = dict(frozen_config)
    if kind == "layer":
        return jax.jit(lambda lp, x: layer(
            config, lp, x, block_length, operand, experts_held))
    return jax.jit(lambda gain, head, x: _mm(
        rms_norm(x, _f32(gain), config["rms_norm_eps"]), _f32(head), operand))


def _frozen(config):
    return tuple(sorted(
        (k, v) for k, v in config.items()
        if isinstance(v, (int, float, bool, str))))


def forward(params, config, tokens, block_length, want=None, operand=None,
            experts_held=None):
    """Float32 logits [len(want), V] of ONE row ``tokens`` [n] at the
    positions ``want`` (default: all).  Positions appended after ``want``'s
    last block change nothing it sees: they lie in later blocks."""
    import jax.numpy as jnp

    key = (_frozen(config), block_length, operand,
           tuple(experts_held) if experts_held else None)
    one_layer, head = _compiled("layer", *key), _compiled("head", *key)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _f32(params["embed"][tokens])
    for index in range(config["num_hidden_layers"]):
        x = one_layer(
            {k: params["layers"][k][index] for k in LAYER_KEYS}, x)
    if want is not None:
        x = x[jnp.asarray(list(want))]
    return head(params["final_norm"], params["head"], x)


# -- generation ---------------------------------------------------------------

def log_probs(logits, mask_id):
    """Float32 log-softmax [B, V] with the mask token ruled out."""
    import jax
    import jax.numpy as jnp

    logits = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                       logits)
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


def choose(logp, masked, steps_left):
    """One denoising step's decision from the block's log-probabilities
    ``logp`` [B, V]: of the positions still ``masked`` the
    ceil(masked / steps_left) whose greedy token is most probable, ties to
    the lower position.  Returns (positions fixed, their tokens)."""
    greedy = logp.argmax(axis=-1)
    conf = logp.max(axis=-1)
    candidates = [i for i in range(len(masked)) if masked[i]]
    n_fix = -(-len(candidates) // steps_left)
    candidates.sort(key=lambda i: (-conf[i], i))
    fixed = sorted(candidates[:n_fix])
    return fixed, [int(greedy[i]) for i in fixed]


def generate(params, config, prompt, gen_length, block_length, steps,
             mask_id, operand=None):
    """Block diffusion for one prompt: (tokens [gen_length], record
    [positions, 3] of (token, step fixed at, log-probability fixed with) for
    every position from the first generated block's start)."""
    prompt = [int(t) for t in prompt]
    whole = len(prompt) // block_length * block_length
    sequence = prompt[:whole]
    record = []
    while len(sequence) < len(prompt) + gen_length:
        known = prompt[len(sequence):len(sequence) + block_length]
        block = known + [mask_id] * (block_length - len(known))
        rows = [[t, -1, 0.0] for t in block]
        for step in range(steps):
            masked = [t == mask_id for t in block]
            if not any(masked):
                break
            at = range(len(sequence), len(sequence) + block_length)
            logp = log_probs(forward(params, config, sequence + block,
                                     block_length, at, operand), mask_id)
            fixed, tokens = choose(logp, masked, steps - step)
            for i, token in zip(fixed, tokens):
                block[i] = token
                rows[i] = [token, step, float(logp[i, token])]
        sequence += block  # the commit: later blocks see the block
        record += rows
    rest = len(prompt) - whole
    tokens = np.asarray(sequence[len(prompt):len(prompt) + gen_length], np.int32)
    assert len(record) >= rest + gen_length
    return tokens, np.asarray(record, np.float64)


def replay(params, config, prompt, record, block_index, step, block_length,
           mask_id, operand=None, pad_to=None):
    """Teacher forcing: rebuild the state a trajectory (``record``, as
    :func:`generate` or the program returns it) was in before ``step`` of its
    block ``block_index`` and return that step's float32 log-probabilities
    [B, V] and which positions were still masked.

    ``pad_to``: run the forward on a sequence padded with zeros to a multiple
    of it (fewer distinct shapes to compile); the pads lie in later blocks,
    which no earlier position sees."""
    prompt = [int(t) for t in prompt]
    whole = len(prompt) // block_length * block_length
    lo = block_index * block_length
    before = [int(t) for t in record[:lo, 0]]
    block = record[lo:lo + block_length]
    masked = [int(s) >= step for s in block[:, 1]]
    state = [mask_id if m else int(t) for m, t in zip(masked, block[:, 0])]
    sequence = prompt[:whole] + before + state
    at = range(len(sequence) - block_length, len(sequence))
    if pad_to:
        sequence = sequence + [0] * (-len(sequence) % pad_to)
    logits = forward(params, config, sequence, block_length, at, operand)
    return log_probs(logits, mask_id), masked
