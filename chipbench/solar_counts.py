"""Operations and bytes of the Solar-Open2 programs, counted from shapes
alone (never from ``cost_analysis()``).  A multiply-add counts as two
operations; counted are the matrix products, the attention scores and sums,
and the delta rule in its OWN form (``Sbar = Diag(alpha) S``, ``Sbar^T k``,
``S = Sbar + k w^T``, ``S^T q``: a multiply and three multiply-adds an element
of the state a token) — whatever the chunked form does on top of that (the
chunk's triangular system, its decayed operands) is the program's choice, not
what the rows need.  Norms, the convs, softmaxes, the router's top-k and the
sort are well under 1% of a forward.

A routed pair is counted where its expert is HELD here: of a token's
``num_experts_per_tok`` pairs, ``held / routed`` on average (the router of
seeded weights is even; the program's own ``moe.pairs_held`` over
``moe.tokens_routed`` reads the share it really got).

Two kinds of count, as ``granite_counts``:

- what the returned rows NEED (:func:`needed_flops`, for
  ``mfu.kda_generate``): every real prompt token and every generated token
  but a row's last through the layers once, the head once a generated token;
- what one dispatch of a program does at its own shape, pads and dummy rows
  included (:func:`prefill_dispatch`, :func:`decode_dispatch`, for the
  programs' rooflines): the least the chip could do for it — every weight it
  touches read once a pass over the layers (the held experts' once; in a
  decode step those that got a token, by the program's own count), the rows'
  KDA state and conv windows read and written once a pass, the cache read at
  the entries that are visible and written at the entries that are new, the
  untied head read once where logits are made, temporaries not counted.
"""

from __future__ import annotations

from chipbench.reference.solar_open2 import dims

BYTES = 2  # bfloat16 weights, activations, conv windows and cache
STATE_BYTES = 4  # the float32 KDA state
RULE_OPS = 7  # a multiply and three multiply-adds an element of the state


def _dims(config: dict) -> dict:
    """The reference's reading of the published keys, and what the counts
    take from it."""
    s = dims(config)
    return dict(
        s, state=s["heads"] * s["dk"] * s["dk"], q=s["q_heads"] * s["dh"],
        kv=s["kv_heads"] * s["dh"], n_held=s["held"][1] - s["held"][0],
        top_k=config["num_experts_per_tok"], window=s["k"] - 1)


def kda_mixer_params(config: dict) -> int:
    """The matrices a token is multiplied by: q, k, v and out, the decay's
    and the output gate's low-rank pairs, the write strength's."""
    s = _dims(config)
    return (4 * s["d"] * s["inner"]
            + 2 * (s["d"] * s["rank"] + s["rank"] * s["inner"])
            + s["d"] * s["heads"])


def attention_mixer_params(config: dict) -> int:
    """q, the gate and out; k and v."""
    s = _dims(config)
    return 3 * s["d"] * s["q"] + 2 * s["d"] * s["kv"]


def expert_params(config: dict) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["f"]


def ffn_params_outside_experts(config: dict) -> int:
    """Router and shared expert."""
    s = _dims(config)
    return s["d"] * s["routed"] + 3 * s["d"] * s["fs"]


def pairs_here_per_token(config: dict) -> float:
    s = _dims(config)
    return s["top_k"] * s["n_held"] / s["routed"]


def layer_flops_per_token(config: dict, kind: str, keys: float = 0.0) -> float:
    """One layer's operations for one token; an attention layer's token
    reads ``keys`` visible cache entries."""
    s = _dims(config)
    ffn = (ffn_params_outside_experts(config)
           + pairs_here_per_token(config) * expert_params(config))
    if kind == "kda":
        return 2 * (kda_mixer_params(config) + ffn) + RULE_OPS * s["state"]
    return 2 * (attention_mixer_params(config) + ffn) + 4 * s["q"] * keys


def head_flops_per_token(config: dict) -> int:
    return 2 * config["hidden_size"] * config["vocab_size"]


def token_flops(config: dict, keys: float) -> float:
    """Every layer's operations for one token."""
    s = _dims(config)
    return (s["kda_layers"] * layer_flops_per_token(config, "kda")
            + s["attention_layers"]
            * layer_flops_per_token(config, "attention", keys))


def weight_bytes(config: dict, experts_read=None) -> float:
    """The layers' weights without embedding and head, with ``experts_read``
    expert matrices in all the layers together (default: every held expert
    of every layer)."""
    s = _dims(config)
    layers = s["kda_layers"] + s["attention_layers"]
    if experts_read is None:
        experts_read = layers * s["n_held"]
    # norm gains, conv weights, dt_bias and the gate's bias, A_log, the
    # router's selection bias: vectors, a thousandth of the matrices
    vectors = layers * 2 * s["d"] + s["d"] + s["kda_layers"] * (
        s["inner"] * (3 * s["k"] + 2) + s["dk"] + s["heads"]
    ) + layers * s["routed"]
    return BYTES * (
        s["kda_layers"] * kda_mixer_params(config)
        + s["attention_layers"] * attention_mixer_params(config)
        + layers * ffn_params_outside_experts(config)
        + experts_read * expert_params(config)
        + vectors)


def head_bytes(config: dict) -> int:
    """The untied head over the chip's slice of the vocabulary."""
    return BYTES * config["vocab_size"] * config["hidden_size"]


def recurrent_bytes_per_row(config: dict) -> int:
    """KDA states and the three conv windows of one row, all KDA layers."""
    s = _dims(config)
    return s["kda_layers"] * (
        STATE_BYTES * s["state"] + BYTES * 3 * s["window"] * s["inner"])


def cache_bytes_per_entry(config: dict) -> int:
    """Key and value of one position, all attention layers."""
    s = _dims(config)
    return s["attention_layers"] * 2 * BYTES * s["kv"]


def needed_flops(config: dict, prompt_lengths, gen: int) -> float:
    """What the rows returned for these prompts need (see the module's
    docstring).  A prompt token sees on average half the prompt, a generated
    one the row so far."""
    total = 0.0
    for length in prompt_lengths:
        total += length * token_flops(config, (length + 1) / 2)
        total += (gen - 1) * token_flops(config, length + gen / 2)
        total += gen * head_flops_per_token(config)
    return total


def prefill_dispatch(config: dict, starts, segment: int) -> dict:
    """One prefill dispatch: a pair (spare ones too: ``start`` 0) for every
    entry of ``starts``, ``segment`` positions each, pads included.  A
    position sees the row's prefix and, on average, half its own segment."""
    pairs = len(starts)
    tokens = pairs * segment
    flops = sum(
        segment * token_flops(config, start + (segment + 1) / 2)
        for start in starts
    ) + pairs * head_flops_per_token(config)
    moved = (
        weight_bytes(config) + head_bytes(config)
        + tokens * config["hidden_size"] * BYTES  # embedding rows read
        + 2 * pairs * recurrent_bytes_per_row(config)  # read and written
        + cache_bytes_per_entry(config) * (sum(starts) + tokens)
    )
    return {"flops": flops, "bytes": moved}


def decode_dispatch(config: dict, rows: int, steps: int, visible: float,
                    experts_read=None) -> dict:
    """One decode dispatch of ``rows`` rows: ``steps`` passes over the
    layers and the head, each reading ``visible`` cache entries a row (the
    mean over the rows and the steps of a pass) and writing one.  A step of
    few tokens need not read every held expert: ``experts_read`` is the
    number of expert matrices a step read in all its layers together, the
    program's own count (``ar_generate.decode_expert_reads`` a step; default:
    all of them)."""
    flops = steps * rows * (
        token_flops(config, visible) + head_flops_per_token(config))
    moved = steps * (
        weight_bytes(config, experts_read) + head_bytes(config)
        + rows * config["hidden_size"] * BYTES
        + 2 * rows * recurrent_bytes_per_row(config)
        + rows * cache_bytes_per_entry(config) * (visible + 1)
    )
    return {"flops": flops, "bytes": moved}
