"""Fixed-length generation over a table of prompts — annotate every row of a
DataFrame of token-id arrays with ``genLength`` generated tokens, by
diffusion over blocks with a sparse-expert decoder
(``BlockDiffusionTransformer`` over ``models/sdar_moe.py``).

Offline-safe (a tiny random-init model; the published widths of
SDAR-30B-A3B-Chat are ``chipbench/configs/sdar_30b_a3b-blockdiffusion.json``).
Works on the real TPU or the virtual CPU mesh:

    JAX_PLATFORMS=cpu python examples/block_diffusion.py
"""

import numpy as np


def main():
    import jax.numpy as jnp

    from sparkdl_tpu import BlockDiffusionTransformer
    from sparkdl_tpu.models.sdar_moe import (
        SdarMoeConfig, SdarMoeModel, init_params,
    )
    from sparkdl_tpu.sql.session import TPUSession

    config = SdarMoeConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    )
    # the weights are program ARGUMENTS, placed on the device once per model
    model = SdarMoeModel(config, init_params(config, seed=0, dtype=jnp.float32))

    spark = TPUSession.builder.master("local[*]").getOrCreate()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 255, n).astype(np.int32)
               for n in (5, 12, 7, 20, 9, 3)]
    df = spark.createDataFrame(
        list(enumerate(prompts)), ["id", "prompt"], numPartitions=2)

    stage = BlockDiffusionTransformer(
        inputCol="prompt", outputCol="generated", recordCol="record",
        model=model, maskTokenId=255, batchSize=4,
        genLength=8, blockLength=4,
        # quality against steps: a block costs `denoisingSteps` passes over
        # the weights; a finished block enters the cache inside the next
        # block's first forward, and a row's last block is never committed
        denoisingSteps=2,
    )
    rows = stage.transform(df).collect()
    for row in rows:
        confidence = np.exp(row["record"][:, 2][row["record"][:, 1] >= 0])
        print(f"row {row['id']}: prompt of {len(row['prompt'])} -> "
              f"{row['generated'].tolist()} "
              f"(mean confidence {confidence.mean():.4f})")
    assert all(len(r["generated"]) == 8 and 255 not in r["generated"]
               for r in rows)
    print(f"generated {8 * len(rows)} tokens for {len(rows)} prompts")


if __name__ == "__main__":
    main()
