"""Subprocess worker for ``tests/test_multihost.py``.

One process of an N-process multi-host job on the virtual CPU platform:
4 local devices per process, gloo TCP collectives between processes (the
CPU stand-in for ICI/DCN — SURVEY.md §4 "Implication", §5.8).

Phases (``meta.json`` ``"phase"``):
- ``"fit"`` (default): ``KerasImageFileEstimator.fit`` end-to-end — per-host
  data shard loading, global-mesh shard_map step, cross-process gradient psum.
- ``"transform"``: multi-host *inference*, the Spark-executor analog — each
  host transforms only its own row shard (``runner.host_shard_indices``),
  embarrassingly parallel, no collectives in the hot path; the test
  reassembles the shards and compares to a single-process transform.

Usage: ``python multihost_worker.py <pid> <nproc> <port> <workdir>``
"""

import json
import os
import sys


def load_vector(uri):
    import numpy as np

    return np.load(uri)


def main():
    pid, nproc, port, workdir = (
        int(sys.argv[1]),
        int(sys.argv[2]),
        sys.argv[3],
        sys.argv[4],
    )
    os.environ["KERAS_BACKEND"] = "jax"
    import jax

    # JAX_PLATFORMS=cpu comes with the environment the test starts this
    # worker in (conftest.py sets it for the whole run)
    jax.config.update("jax_num_cpu_devices", 4)

    from sparkdl_tpu.parallel import runner

    runner.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nproc,
        process_id=pid,
    )
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.device_count() == 4 * nproc, jax.device_count()
    assert runner.is_distributed()

    import numpy as np

    from sparkdl_tpu.estimators import KerasImageFileEstimator
    from sparkdl_tpu.sql.session import TPUSession

    with open(os.path.join(workdir, "meta.json")) as f:
        meta = json.load(f)
    spark = TPUSession.builder.master("local[*]").getOrCreate()

    import logging

    logging.basicConfig(level=logging.INFO, stream=sys.stdout)

    if meta.get("phase") == "transform":
        _transform_phase(pid, workdir, meta, spark, runner)
        return
    if meta.get("phase") == "flax_tp":
        _flax_tp_phase(pid, workdir, meta, spark, runner)
        return

    df = spark.createDataFrame(
        [{"uri": u, "label": [float(l)]} for u, l in meta["rows"]]
    )

    est = KerasImageFileEstimator(
        inputCol="uri",
        outputCol="out",
        labelCol="label",
        imageLoader=load_vector,
        modelFile=os.path.join(workdir, "model.keras"),
        kerasOptimizer="sgd",
        kerasLoss="mse",
        kerasFitParams=meta["fit_params"],
        checkpointDir=meta.get("checkpoint_dir"),
    )
    fitted = est.fit(df)

    import keras

    m = keras.saving.load_model(fitted.getModelFile(), compile=False)
    np.savez(
        os.path.join(workdir, f"weights_proc{pid}.npz"),
        *[np.asarray(w) for w in m.get_weights()],
    )
    runner.barrier("multihost_worker_done")
    print(f"MULTIHOST_WORKER_OK {pid}", flush=True)


def _flax_tp_phase(pid, workdir, meta, spark, runner):
    """Multi-process GSPMD DP x TP: a 2-process global ("data", "model")
    mesh trains a tiny ViT with Megatron sharding rules — the pod-scale
    configuration (VERDICT r3 weak #3a).  Each host loads its own strided
    shard; the global batch assembles from per-host rows; XLA inserts the
    cross-process collectives."""
    import jax
    import numpy as np

    from sparkdl_tpu.estimators import FlaxImageFileEstimator
    from sparkdl_tpu.models.vit import ViT
    from sparkdl_tpu.parallel.tp import VIT_TP_RULES

    rows = meta["rows"]
    df = spark.createDataFrame(
        [{"uri": u, "label": int(l)} for u, l in rows]
    )
    est = FlaxImageFileEstimator(
        inputCol="uri",
        outputCol="out",
        labelCol="label",
        imageLoader=load_vector,
        module=ViT(variant="ViT-Ti/16", num_classes=2,
                   image_size=meta["img"]),
        optimizer="sgd",
        fitParams=meta["fit_params"],
        shardingRules=VIT_TP_RULES,
        meshShape=tuple(meta["mesh_shape"]),
        checkpointDir=meta.get("checkpoint_dir"),
    )
    fitted = est.fit(df)
    leaves = jax.tree_util.tree_leaves_with_path(fitted.variables)
    np.savez(
        os.path.join(workdir, f"flax_tp_proc{pid}.npz"),
        **{jax.tree_util.keystr(p): np.asarray(v) for p, v in leaves},
    )
    runner.barrier("multihost_flax_tp_done")
    print(f"MULTIHOST_WORKER_OK {pid}", flush=True)


def _transform_phase(pid, workdir, meta, spark, runner):
    """Per-host-shard batch inference: the reference's executors-each-run-
    their-partitions flow (SURVEY.md §3.1), one host per shard."""
    import numpy as np

    from sparkdl_tpu.transformers.keras_image import KerasImageFileTransformer

    rows = meta["rows"]
    shard = runner.host_shard_indices(len(rows))
    df = spark.createDataFrame([{"uri": rows[i][0]} for i in shard])
    t = KerasImageFileTransformer(
        inputCol="uri",
        outputCol="out",
        modelFile=os.path.join(workdir, "model.keras"),
        imageLoader=load_vector,
    )
    got = t.transform(df).collect()
    np.savez(
        os.path.join(workdir, f"transform_proc{pid}.npz"),
        indices=np.asarray(shard),
        outputs=np.stack([np.asarray(r.out.toArray()) for r in got]),
    )
    runner.barrier("multihost_transform_done")
    print(f"MULTIHOST_WORKER_OK {pid}", flush=True)


if __name__ == "__main__":
    main()
