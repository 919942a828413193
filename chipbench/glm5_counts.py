"""Operations and bytes of the GLM-5 programs, counted from shapes alone
(never from ``cost_analysis()``), in ONE form whatever implements it.  A
multiply-add counts as two operations; counted are the matrix products, the
indexer's scores and the attention's scores and sums.  Norms, the rotation,
softmaxes, the indexer's ReLU and weighting, the selection, the router's
top-k and the sort are well under 1% of a forward.

The form counted is the PUBLISHED one (``chipbench/reference/glm5.py``):

- the indexer scores EVERY visible key: ``2 J dim`` a query and key;
- MLA is the decompressed form at ``min(visible, index_topk)`` keys a query:
  every position's keys and values are decompressed from its latent once
  (``c W_kvb``, a product of the position's own, counted with the weights),
  and a query pays ``2 H (nope + rope)`` for its scores and ``2 H v`` for
  its sum a selected key;
- the weights are read once a pass over the layers, and in a decode step
  only the held experts that got a token (the program's own count);
- the caches as stored: an indexer key (``dim`` channels) at every visible
  slot, a latent (``kv_lora_rank + qk_rope_head_dim``) at the selected ones;
- the untied head once where logits are made; temporaries not counted.

The program's own path (the absorbed form over whole blocks of the cache,
the selection's bisection) does more; a switch of its path leaves this
yardstick where it is, and no correct implementation reads over 100% of it.

Two kinds of count, as ``moonlight_counts``: what the returned rows NEED
(:func:`needed_flops`, for ``mfu.dsa_generate``) and what one dispatch of a
program does at its own shape, pads and spare pairs included
(:func:`prefill_dispatch`, :func:`decode_dispatch`).
"""

from __future__ import annotations

from chipbench.reference.glm5 import dims

BYTES = 2  # bfloat16 weights, activations and caches


def _dims(config: dict) -> dict:
    s = dims(config)
    return dict(s, top_k=config["num_experts_per_tok"],
                moe_layers=s["layers"] - s["dense"])


def latent_bytes(config: dict) -> int:
    """A cached position's latent in one layer: ``c`` and ``k_pe``."""
    s = _dims(config)
    return BYTES * (s["rank"] + s["rope"])


def index_key_bytes(config: dict) -> int:
    """A cached position's indexer key in one layer."""
    return BYTES * _dims(config)["ix_dim"]


def mla_params(config: dict) -> int:
    """``W_qa``, the query's norm, ``W_qb``, ``W_kva``, the latent's norm,
    ``W_kvb``, ``W_o`` (165,022,208 at the published widths)."""
    s = _dims(config)
    h, qr = s["heads"], s["q_rank"]
    return (s["d"] * qr + qr + qr * h * (s["nope"] + s["rope"])
            + s["d"] * (s["rank"] + s["rope"]) + s["rank"]
            + s["rank"] * h * (s["nope"] + s["v"]) + h * s["v"] * s["d"])


def indexer_params(config: dict) -> int:
    """``W_qI``, ``W_kI``, the key's LayerNorm, ``W_w`` (9,371,904)."""
    s = _dims(config)
    return (s["q_rank"] * s["ix_heads"] * s["ix_dim"] + s["d"] * s["ix_dim"]
            + 2 * s["ix_dim"] + s["d"] * s["ix_heads"])


def dense_params(config: dict) -> int:
    s = _dims(config)
    return 3 * s["d"] * s["dense_width"]


def expert_params(config: dict) -> int:
    """One routed expert, or the shared one (37,748,736)."""
    s = _dims(config)
    return 3 * s["d"] * s["f"]


def ffn_params_outside_experts(config: dict) -> int:
    """Router (all routed experts' outputs) and shared expert."""
    s = _dims(config)
    return s["d"] * s["routed"] + 3 * s["d"] * s["fs"]


def index_flops_per_key(config: dict) -> int:
    """One query against one visible key: the indexer's heads' products."""
    s = _dims(config)
    return 2 * s["ix_heads"] * s["ix_dim"]


def attention_flops_per_key(config: dict) -> int:
    """One query against one selected key, all heads: scores and sum."""
    s = _dims(config)
    return 2 * s["heads"] * (s["nope"] + s["rope"] + s["v"])


def _selected(config: dict, visible: float) -> float:
    return min(visible, _dims(config)["topk"])


def layer_flops_per_token(config: dict, kind: str, visible: float = 0.0,
                          selected: float = None) -> float:
    """One layer's operations for one token that sees ``visible`` slots and
    keeps ``selected`` of them (default: ``min(visible, index_topk)``)."""
    s = _dims(config)
    if selected is None:
        selected = _selected(config, visible)
    if kind == "dense":
        ffn = dense_params(config)
    else:
        # the routed pairs whose expert is held here: top_k x held / routed
        held = s["top_k"] * s["experts"] / s["routed"]
        ffn = ffn_params_outside_experts(config) + held * expert_params(config)
    return (2 * (mla_params(config) + indexer_params(config) + ffn)
            + index_flops_per_key(config) * visible
            + attention_flops_per_key(config) * selected)


def head_flops_per_token(config: dict) -> int:
    return 2 * config["hidden_size"] * config["vocab_size"]


def token_flops(config: dict, visible: float, selected: float = None
                ) -> float:
    s = _dims(config)
    return (s["dense"] * layer_flops_per_token(config, "dense", visible,
                                               selected)
            + s["moe_layers"] * layer_flops_per_token(config, "moe", visible,
                                                      selected))


def weight_bytes(config: dict, experts_read=None) -> float:
    """The layers' weights without embedding and head, with ``experts_read``
    held expert matrices in all the expert layers together (default: all
    held)."""
    s = _dims(config)
    if experts_read is None:
        experts_read = s["moe_layers"] * s["experts"]
    vectors = s["layers"] * 2 * s["d"] + s["d"] + s["moe_layers"] * s["routed"]
    return BYTES * (
        s["layers"] * (mla_params(config) + indexer_params(config))
        + s["dense"] * dense_params(config)
        + s["moe_layers"] * ffn_params_outside_experts(config)
        + experts_read * expert_params(config) + vectors)


def head_bytes(config: dict) -> int:
    return BYTES * config["vocab_size"] * config["hidden_size"]


def cache_bytes_per_slot(config: dict) -> int:
    """One position's latent and indexer key, all layers (7,040 B)."""
    return _dims(config)["layers"] * (latent_bytes(config)
                                      + index_key_bytes(config))


def _prompt_sums(length: int, topk: int):
    """(sum of visible, sum of selected) keys over a prompt's positions."""
    full = min(length, topk)
    visible = length * (length + 1) / 2
    selected = full * (full + 1) / 2 + (length - full) * topk
    return visible, selected


def needed_flops(config: dict, prompt_lengths, gen: int) -> float:
    """What the rows returned for these prompts need: every prompt position
    and every generated one but a row's last through the layers once, the
    indexer over every visible key and MLA over the selected ones, the head
    once a generated token."""
    s = _dims(config)
    total = 0.0
    per_key = s["layers"] * (index_flops_per_key(config))
    per_selected = s["layers"] * attention_flops_per_key(config)
    for length in prompt_lengths:
        visible, selected = _prompt_sums(int(length), s["topk"])
        total += length * token_flops(config, 0.0, 0.0)
        total += per_key * visible + per_selected * selected
        for t in range(gen - 1):
            total += token_flops(config, length + t + 1)
        total += gen * head_flops_per_token(config)
    return total


def _pair_sums(start: int, segment: int, topk: int):
    """(visible, selected) keys summed over a pair's positions ``start`` ..
    ``start + segment - 1``."""
    visible = sum(start + i + 1 for i in range(segment))
    selected = sum(min(start + i + 1, topk) for i in range(segment))
    return visible, selected


def prefill_dispatch(config: dict, starts, segment: int) -> dict:
    """One prefill dispatch: a pair (spare ones too: ``start`` 0) for every
    entry of ``starts``, ``segment`` positions each, pads included."""
    s = _dims(config)
    pairs = len(starts)
    tokens = pairs * segment
    flops = pairs * head_flops_per_token(config)
    moved = (weight_bytes(config) + head_bytes(config)
             + tokens * config["hidden_size"] * BYTES)  # embedding rows read
    for start in starts:
        visible, selected = _pair_sums(int(start), segment, s["topk"])
        flops += (segment * token_flops(config, 0.0, 0.0)
                  + s["layers"] * (index_flops_per_key(config) * visible
                                   + attention_flops_per_key(config)
                                   * selected))
        # the pair's visible index keys once, the latents a query can keep
        # (at least the topk of its last position), its own slots written
        slots = int(start) + segment
        moved += s["layers"] * (
            index_key_bytes(config) * slots
            + latent_bytes(config) * min(slots, s["topk"]))
        moved += cache_bytes_per_slot(config) * segment
    return {"flops": flops, "bytes": moved}


def decode_dispatch(config: dict, rows: int, steps: int, visible: float,
                    experts_read=None) -> dict:
    """One decode dispatch of ``rows`` rows: ``steps`` passes over the
    layers and the head, each reading ``visible`` cached slots' indexer keys
    and ``min(visible, index_topk)`` latents a row (the mean over the rows
    and the steps of a pass) and writing one slot.  ``experts_read``: the
    held expert matrices a step read in all its layers together, the
    program's own count (default: all of them)."""
    s = _dims(config)
    selected = _selected(config, visible)
    flops = steps * rows * (
        token_flops(config, visible, selected) + head_flops_per_token(config))
    moved = steps * (
        weight_bytes(config, experts_read) + head_bytes(config)
        + rows * config["hidden_size"] * BYTES
        + rows * s["layers"] * (index_key_bytes(config) * visible
                                + latent_bytes(config) * selected)
        + rows * cache_bytes_per_slot(config))
    return {"flops": flops, "bytes": moved}

