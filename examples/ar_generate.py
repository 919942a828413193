"""Autoregressive generation over a table of long prompts — annotate every
row of a DataFrame of token-id arrays with ``genLength`` greedily generated
tokens, with a state-space / attention hybrid whose feed-forwards are sparse
experts (``AutoregressiveTransformer`` over ``models/granite_hybrid.py``).

Offline-safe (a tiny random-init model; the published widths of
granite-4.0-h-small are ``chipbench/configs/granite_4.0_h_small-generate.json``).
Works on the real TPU or the virtual CPU mesh:

    JAX_PLATFORMS=cpu python examples/ar_generate.py
"""

import numpy as np


def main():
    import jax.numpy as jnp

    from sparkdl_tpu import AutoregressiveTransformer
    from sparkdl_tpu.models.granite_hybrid import (
        GraniteHybridConfig, GraniteHybridModel, init_params,
    )
    from sparkdl_tpu.sql.session import TPUSession

    config = GraniteHybridConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=4,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        num_attention_heads=4, num_key_value_heads=2,
        # this chip's share of a layer: 4 of the 8 experts the router scores
        num_local_experts=4, routed_experts=8, experts_held=(0, 4),
        num_experts_per_tok=2, intermediate_size=32,
        shared_intermediate_size=48, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, attention_multiplier=0.0625,
        embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=16,
    )
    # the weights are program ARGUMENTS, placed on the device once per model
    model = GraniteHybridModel(
        config, init_params(config, seed=0, dtype=jnp.float32))

    spark = TPUSession.builder.master("local[*]").getOrCreate()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 300, 7, 21, 130, 3)]
    df = spark.createDataFrame(
        list(enumerate(prompts)), ["id", "prompt"], numPartitions=2)

    stage = AutoregressiveTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        # ONE prefill shape whatever the prompts' lengths: (row, segment)
        # pairs of 128 positions, a row's state carried from segment to
        # segment (the prompt of 300 tokens takes three); then decode
        # dispatches of 8 tokens a row
        model=model, genLength=8, batchSize=4,
    )
    rows = stage.transform(df).collect()
    for row in rows:
        confidence = np.exp(row["record"][:, 1])
        print(f"row {row['id']}: prompt of {len(row['prompt'])} -> "
              f"{row['generated'].tolist()} "
              f"(mean confidence {confidence.mean():.4f})")
    assert all(len(r["generated"]) == 8 for r in rows)
    print(f"generated {8 * len(rows)} tokens for {len(rows)} prompts")


if __name__ == "__main__":
    main()
