"""Operations and bytes of the Moonlight programs, counted from shapes alone
(never from ``cost_analysis()``), in ONE form whatever implements it.  A
multiply-add counts as two operations; counted are the matrix products and
the attention's scores and sums.  Norms, the rotation, softmaxes, the
router's top-k and the sort are well under 1% of a forward.

The form counted is the PUBLISHED one (``chipbench/reference/moonlight.py``):
every position's keys and values are decompressed from its latent ONCE (``c
W_kvb`` is a product of the position's own), and a query then pays ``2 H
(nope + rope)`` for its scores and ``2 H v`` for its sum a visible key.  The
absorbed form the program takes does more operations a pair (it scores and
sums 576 latent channels a head) and none a position; a switch of the
prefill's path leaves this yardstick where it is, and no correct
implementation reads over 100% of it.

Two kinds of count, as ``solar_counts``:

- what the returned rows NEED (:func:`needed_flops`, for
  ``mfu.mla_generate``): every real prompt token and every generated token
  but a row's last through the layers once, the head once a generated token;
- what one dispatch of a program does at its own shape, pads and dummy rows
  included (:func:`prefill_dispatch`, :func:`decode_dispatch`, for the
  programs' rooflines): the least the chip could do for it — every weight it
  touches read once a pass over the layers (in a decode step the experts
  that got a token, by the program's own count), the latent cache as stored
  (:data:`LATENT_BYTES` a slot and layer) read at the visible slots and
  written at the new ones, the untied head read once where logits are made,
  temporaries not counted.
"""

from __future__ import annotations

from chipbench.reference.moonlight import dims

BYTES = 2  # bfloat16 weights, activations and latent cache


def _dims(config: dict) -> dict:
    s = dims(config)
    return dict(s, top_k=config["num_experts_per_tok"],
                moe_layers=s["layers"] - s["dense"])


def latent_bytes(config: dict) -> int:
    """A cached position of one layer: ``c`` and ``k_pe`` (1,152 B)."""
    s = _dims(config)
    return BYTES * (s["rank"] + s["rope"])


def mla_params(config: dict) -> int:
    """``W_q``, ``W_kva``, ``W_kvb``, ``W_o``."""
    s = _dims(config)
    h = s["heads"]
    return (s["d"] * h * (s["nope"] + s["rope"])
            + s["d"] * (s["rank"] + s["rope"])
            + s["rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * s["d"])


def dense_params(config: dict) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["dense_width"]


def expert_params(config: dict) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["f"]


def ffn_params_outside_experts(config: dict) -> int:
    """Router and shared experts."""
    s = _dims(config)
    return s["d"] * s["experts"] + 3 * s["d"] * s["fs"]


def attention_flops_per_key(config: dict) -> int:
    """One query against one visible key, all heads: scores and sum."""
    s = _dims(config)
    return 2 * s["heads"] * (s["nope"] + s["rope"] + s["v"])


def layer_flops_per_token(config: dict, kind: str, keys: float = 0.0) -> float:
    """One layer's operations for one token that sees ``keys`` slots."""
    s = _dims(config)
    if kind == "dense":
        ffn = dense_params(config)
    else:
        ffn = (ffn_params_outside_experts(config)
               + s["top_k"] * expert_params(config))
    return (2 * (mla_params(config) + ffn)
            + attention_flops_per_key(config) * keys)


def head_flops_per_token(config: dict) -> int:
    return 2 * config["hidden_size"] * config["vocab_size"]


def token_flops(config: dict, keys: float) -> float:
    s = _dims(config)
    return (s["dense"] * layer_flops_per_token(config, "dense", keys)
            + s["moe_layers"] * layer_flops_per_token(config, "moe", keys))


def weight_bytes(config: dict, experts_read=None) -> float:
    """The layers' weights without embedding and head, with ``experts_read``
    expert matrices in all the expert layers together (default: all)."""
    s = _dims(config)
    if experts_read is None:
        experts_read = s["moe_layers"] * s["experts"]
    # norm gains and the selection bias: vectors, a ten-thousandth
    vectors = (s["layers"] * (2 * s["d"] + s["rank"]) + s["d"]
               + s["moe_layers"] * s["experts"])
    return BYTES * (
        s["layers"] * mla_params(config)
        + s["dense"] * dense_params(config)
        + s["moe_layers"] * ffn_params_outside_experts(config)
        + experts_read * expert_params(config) + vectors)


def head_bytes(config: dict) -> int:
    return BYTES * config["vocab_size"] * config["hidden_size"]


def cache_bytes_per_slot(config: dict) -> int:
    """One position's latents, all layers."""
    return _dims(config)["layers"] * latent_bytes(config)


def needed_flops(config: dict, prompt_lengths, gen: int) -> float:
    """What the rows returned for these prompts need.  A prompt token sees
    on average half the prompt, a generated one the row so far."""
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
        + cache_bytes_per_slot(config) * (sum(starts) + tokens)
    )
    return {"flops": flops, "bytes": moved}


def decode_dispatch(config: dict, rows: int, steps: int, visible: float,
                    experts_read=None) -> dict:
    """One decode dispatch of ``rows`` rows: ``steps`` passes over the
    layers and the head, each reading ``visible`` cached slots a row (the
    mean over the rows and the steps of a pass) and writing one.
    ``experts_read``: the expert matrices a step read in all its layers
    together, the program's own count (default: all of them)."""
    flops = steps * rows * (
        token_flops(config, visible) + head_flops_per_token(config))
    moved = steps * (
        weight_bytes(config, experts_read) + head_bytes(config)
        + rows * config["hidden_size"] * BYTES
        + rows * cache_bytes_per_slot(config) * (visible + 1)
    )
    return {"flops": flops, "bytes": moved}
