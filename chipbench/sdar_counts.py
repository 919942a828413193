"""Operations and bytes of the SDAR-MoE programs, counted from shapes alone
(never from ``cost_analysis()``).  A multiply-add counts as two operations;
only matrix products are counted (norms, rotations, softmaxes, the router's
top-k and the sort are well under 1% of a forward).

Two kinds of count:

- what the returned rows NEED (:func:`needed_flops`, for ``mfu.generate``):
  real prompt tokens through the layers once, and ``steps + 1`` forwards of
  every block a row needs at ACTIVE parameters (the experts a token is
  routed to), the head only on the denoising forwards, where logits are
  needed;
- what one dispatch of a program does at its own shape, padding included
  (:func:`prefill_dispatch`, :func:`block_dispatch`, for the programs'
  rooflines): the least the chip could do for it — every weight it touches
  read once a forward, the cache read at the entries that are visible and
  written at the entries that are new, temporaries not counted at all.
"""

from __future__ import annotations

BYTES = 2  # bfloat16 weights, activations and cache


def _dims(config: dict):
    d, dh = config["hidden_size"], config["head_dim"]
    return (d, dh, config["num_attention_heads"] * dh,
            config["num_key_value_heads"] * dh)


def layer_matmul_flops_per_token(config: dict) -> int:
    """Projections, router and the routed experts of one layer."""
    d, _, q, kv = _dims(config)
    experts = (config["num_experts_per_tok"] * 3 * d
               * config["moe_intermediate_size"])
    return 2 * (d * q + 2 * d * kv + q * d + d * config["num_experts"]
                + experts)


def attention_flops_per_token(config: dict, keys: float) -> float:
    """Scores and the weighted sum against ``keys`` visible entries."""
    _, _, q, _ = _dims(config)
    return 2 * 2 * q * keys


def head_flops_per_token(config: dict) -> int:
    return 2 * config["hidden_size"] * config["vocab_size"]


def layer_weight_bytes(config: dict, experts: int) -> int:
    """One layer's weights with ``experts`` of its experts."""
    d, dh, q, kv = _dims(config)
    outside = d * q + 2 * d * kv + q * d + d * config["num_experts"] + 2 * d + 2 * dh
    return BYTES * (
        outside + experts * 3 * d * config["moe_intermediate_size"])


def cache_bytes_per_entry(config: dict) -> int:
    """Key and value of one position in one layer."""
    return 2 * BYTES * config["num_key_value_heads"] * config["head_dim"]


def _experts_touched(config: dict, tokens: int) -> int:
    """Held experts that get a token when ``tokens`` tokens are routed
    evenly: all of them once there are pairs enough."""
    lo, hi = config.get("experts_held") or (0, config["num_experts"])
    return min(hi - lo, tokens * config["num_experts_per_tok"])


def needed_flops(config: dict, prompt_lengths, gen: int, block: int,
                 steps: int) -> float:
    """What the rows returned for these prompts need (see the module's
    docstring).  A prompt position sees its own and every earlier block, on
    average half the prompt; a block position sees the row so far."""
    layers = config["num_hidden_layers"]
    per_token = layer_matmul_flops_per_token(config)
    total = 0.0
    for length in prompt_lengths:
        whole = length // block * block
        total += layers * whole * (
            per_token + attention_flops_per_token(config, (whole + block) / 2))
        blocks = -(-(length - whole + gen) // block)
        for index in range(blocks):
            keys = whole + (index + 1) * block
            total += (steps + 1) * block * layers * (
                per_token + attention_flops_per_token(config, keys))
        total += steps * blocks * block * head_flops_per_token(config)
    return total


def prefill_dispatch(config: dict, rows: int, length: int, block: int) -> dict:
    """One prefill chunk of ``rows`` x ``length`` tokens (pads included)."""
    layers, tokens = config["num_hidden_layers"], rows * length
    flops = layers * tokens * (
        layer_matmul_flops_per_token(config)
        + attention_flops_per_token(config, (length + block) / 2))
    moved = (
        layers * layer_weight_bytes(config, _experts_touched(config, tokens))
        + tokens * config["hidden_size"] * BYTES  # embedding rows read
        + layers * tokens * cache_bytes_per_entry(config)  # entries written
    )
    return {"flops": flops, "bytes": moved}


def block_dispatch(config: dict, rows: int, block: int, steps: int,
                   visible: float) -> dict:
    """One block step of ``rows`` rows: ``steps`` denoising forwards and the
    commit, each reading ``visible`` cache entries a row (the mean over the
    rows and the blocks of a pass) besides the block itself."""
    layers, tokens = config["num_hidden_layers"], rows * block
    forward = layers * tokens * (
        layer_matmul_flops_per_token(config)
        + attention_flops_per_token(config, visible + block))
    flops = (steps + 1) * forward + steps * tokens * head_flops_per_token(config)
    weights = layers * layer_weight_bytes(
        config, _experts_touched(config, tokens))
    moved = (
        (steps + 1) * (
            weights
            + tokens * config["hidden_size"] * BYTES
            + layers * rows * visible * cache_bytes_per_entry(config))
        + steps * config["hidden_size"] * config["vocab_size"] * BYTES
        + layers * tokens * cache_bytes_per_entry(config)  # the commit
    )
    return {"flops": flops, "bytes": moved}
