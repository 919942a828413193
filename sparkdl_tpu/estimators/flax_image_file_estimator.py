"""FlaxImageFileEstimator — fine-tune a Flax module over image files.

The ViT stretch config's estimator (SURVEY.md §7 step 8): same param
surface and outer flow as :class:`KerasImageFileEstimator` (imageLoader /
optimizer / loss / fitParams; collect URIs, load via the user's loader,
train with orbax checkpoint/resume, return a fitted transformer),
but the model is a ``flax.linen.Module`` — e.g.
``sparkdl_tpu.models.ViT(variant="ViT-B/16")``
— so the training step can also run tensor-parallel: pass
``shardingRules`` (e.g. ``sparkdl_tpu.parallel.tp.VIT_TP_RULES``) and the
step becomes the GSPMD DP x TP program over a ``("data", "model")`` mesh
instead of pure shard_map DP.

The fitted model is a :class:`FlaxImageFileTransformer` running the tuned
params through one jitted program (same hot loop as every other
transformer).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from sparkdl_tpu.estimators import checkpointing
from sparkdl_tpu.obs.hooks import fit_profiler
from sparkdl_tpu.resilience import inject
from sparkdl_tpu.resilience.preempt import preemption_scope
from sparkdl_tpu.estimators.data import (
    in_memory_epoch_dataset,
    load_host_shard,
)
from sparkdl_tpu.estimators.losses import (
    get_optimizer,
    get_per_sample_loss_fn,
)
from sparkdl_tpu.ml.base import Estimator, Transformer
from sparkdl_tpu.param.base import Param, keyword_only
from sparkdl_tpu.param.shared import (
    CanLoadImage,
    HasInputCol,
    HasLabelCol,
    HasOutputCol,
)
from sparkdl_tpu.parallel.trainer import (
    init_train_state,
    make_mesh,
    make_train_step,
    shard_batch,
)
from sparkdl_tpu.transformers.utils import (
    DEFAULT_BATCH_SIZE,
    make_loader_decode_plan,
    place_params,
    to_vectors,
    transform_batched,
)

logger = logging.getLogger(__name__)


class FlaxImageFileTransformer(
    Transformer, HasInputCol, HasOutputCol, CanLoadImage
):
    """Fitted model: user loader -> one jitted ``module.apply`` program."""

    def __init__(
        self,
        inputCol: str,
        outputCol: str,
        imageLoader,
        module,
        variables,
        batchSize: int = DEFAULT_BATCH_SIZE,
        features_only: bool = False,
    ):
        super().__init__()
        self._set(inputCol=inputCol, outputCol=outputCol,
                  imageLoader=imageLoader)
        self.module = module
        self.variables = variables
        self.batchSize = int(batchSize)
        self.features_only = bool(features_only)
        self._jitted = None

    # -- persistence (module pickle + variables pytree pickle) ---------
    # The module is a flax dataclass (picklable as long as custom
    # ``attn_impl`` callables are module-level); variables pickle as
    # numpy pytrees.  Matches the DefaultParamsWritable analog the other
    # stages use (tests/test_persistence.py).
    def _save_artifacts(self, path: str):
        import os
        import pickle

        host_vars = jax.tree_util.tree_map(np.asarray, self.variables)
        with open(os.path.join(path, "flax_model.pkl"), "wb") as fh:
            pickle.dump({"module": self.module, "variables": host_vars}, fh)
        return {
            "batchSize": self.batchSize,
            "features_only": self.features_only,
        }

    @classmethod
    def _load_instance(cls, metadata, path: str):
        import os
        import pickle

        extra = metadata["extra"]
        with open(os.path.join(path, "flax_model.pkl"), "rb") as fh:
            payload = pickle.load(fh)
        params = metadata["params"]
        from sparkdl_tpu.ml.util import _decode_param

        return cls(
            inputCol=_decode_param(params["inputCol"], path),
            outputCol=_decode_param(params["outputCol"], path),
            imageLoader=_decode_param(params["imageLoader"], path),
            module=payload["module"],
            variables=payload["variables"],
            batchSize=extra["batchSize"],
            features_only=extra["features_only"],
        )

    def _forward(self):
        if self._jitted is None:
            module = self.module
            feats = self.features_only
            variables = place_params(self.variables)

            def forward(x):
                out = module.apply(variables, x, features_only=feats)
                if isinstance(out, (tuple, list)):
                    # first-output semantics for multi-output modules
                    # (what the pre-pipeline run_batched engine returned)
                    out = out[0]
                return out

            # AOT through the engine with input-batch donation; fine-tuned
            # in-memory variables have no durable identity, so the program
            # is LRU-cached in process but never persisted to disk.
            from sparkdl_tpu.engine import engine as _engine

            self._jitted = _engine.function(
                forward, donate=True, name="flax_eval_forward"
            )
        return self._jitted

    def _transform(self, dataset):
        loader = self.getImageLoader()
        # same contract as KerasImageFileTransformer: one fixed loader
        # shape bound across the chunks of a partition
        return transform_batched(
            dataset, self.getInputCol(), self.getOutputCol(), self._forward(),
            lambda uris: make_loader_decode_plan(loader), to_vectors,
            self.batchSize,
        )


class FlaxImageFileEstimator(
    Estimator, HasInputCol, HasOutputCol, HasLabelCol, CanLoadImage
):
    module = Param("undefined", "module", "flax.linen.Module to fine-tune")
    optimizer = Param("undefined", "optimizer", "optax optimizer name")
    loss = Param("undefined", "loss", "loss name (per-example labels)")
    fitParams = Param(
        "undefined", "fitParams",
        "dict: epochs / batch_size / learning_rate / seed",
    )
    initialVariables = Param(
        "undefined", "initialVariables",
        "optional pretrained variables pytree (None: module.init)",
    )
    shardingRules = Param(
        "undefined", "shardingRules",
        "optional (regex, spec) tensor-parallel rules "
        "(parallel.tp.VIT_TP_RULES); None trains pure-DP",
    )
    meshShape = Param(
        "undefined", "meshShape",
        "optional (dp, tp) device-count split for the DPxTP mesh; None "
        "picks dp=2 when the device count is even, else dp=1",
    )
    checkpointDir = Param(
        "undefined", "checkpointDir",
        "orbax checkpoint directory for mid-training save/resume "
        "(None disables checkpointing); same semantics as "
        "KerasImageFileEstimator: per-configuration namespace (epochs "
        "excluded — a re-fit with more epochs resumes, a shorter one "
        "restores the exact earlier epoch), async commits",
    )

    @keyword_only
    def __init__(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        labelCol: Optional[str] = None,
        imageLoader=None,
        module=None,
        optimizer: str = "adam",
        loss: str = "sparse_categorical_crossentropy",
        fitParams: Optional[Dict[str, Any]] = None,
        initialVariables=None,
        shardingRules: Optional[Sequence] = None,
        meshShape: Optional[Sequence[int]] = None,
        checkpointDir: Optional[str] = None,
    ):
        super().__init__()
        self._setDefault(
            optimizer="adam",
            loss="sparse_categorical_crossentropy",
            fitParams={"epochs": 1, "batch_size": 32},
            initialVariables=None,
            shardingRules=None,
            meshShape=None,
            checkpointDir=None,
        )
        kwargs = self._input_kwargs
        self.setParams(**kwargs)

    @keyword_only
    def setParams(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        labelCol: Optional[str] = None,
        imageLoader=None,
        module=None,
        optimizer: str = "adam",
        loss: str = "sparse_categorical_crossentropy",
        fitParams: Optional[Dict[str, Any]] = None,
        initialVariables=None,
        shardingRules: Optional[Sequence] = None,
        meshShape: Optional[Sequence[int]] = None,
        checkpointDir: Optional[str] = None,
    ):
        kwargs = self._input_kwargs
        return self._set(**kwargs)

    # ------------------------------------------------------------------
    def _load_shard(self, dataset):
        x, labels, n_global = load_host_shard(
            dataset,
            self.getInputCol(),
            self.getLabelCol(),
            self.getImageLoader(),
        )
        raw = np.asarray(labels)
        if not np.issubdtype(raw.dtype, np.integer):
            as_int = raw.astype(np.int64)
            if not np.array_equal(raw, as_int):
                raise ValueError(
                    f"labelCol {self.getLabelCol()!r} holds non-integral "
                    f"values (dtype {raw.dtype}); this estimator trains "
                    "with integer class labels"
                )
        return x, raw.astype(np.int32), n_global

    def _fit(self, dataset):
        for p in (self.inputCol, self.outputCol, self.labelCol,
                  self.imageLoader, self.module):
            if not self.isDefined(p):
                raise ValueError(f"Required param not set: {p.name}")

        module = self.getOrDefault(self.module)
        fit_params = dict(self.getOrDefault(self.fitParams) or {})
        epochs = int(fit_params.get("epochs", 1))
        batch_size = int(fit_params.get("batch_size", 32))
        lr = fit_params.get("learning_rate")
        seed = int(fit_params.get("seed", 0))

        from sparkdl_tpu.parallel import runner

        distributed = runner.is_distributed()
        nprocs = jax.process_count()
        x, y, n_global = self._load_shard(dataset)
        loss_name = self.getOrDefault(self.loss)
        tx = get_optimizer(self.getOrDefault(self.optimizer), lr)

        variables = self.getOrDefault(self.initialVariables)
        if variables is None:
            variables = module.init(
                jax.random.PRNGKey(seed),
                jnp.zeros((1,) + x.shape[1:], jnp.float32),
            )
        else:
            # defensive copy: the train step donates its state buffers, and
            # donating the CALLER's pretrained pytree would leave them
            # holding deleted arrays after fit returns
            variables = jax.tree_util.tree_map(
                lambda a: jnp.array(a), variables
            )

        def per_sample(params, batch):
            """Per-sample losses -> exact zero-weight ragged padding."""
            logits = module.apply(params, batch["x"])
            if loss_name == "sparse_categorical_crossentropy":
                # logits-space CE (Flax modules emit logits, unlike the
                # Keras estimator's softmax outputs)
                import optax

                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, batch["y"]
                )
            per = get_per_sample_loss_fn(loss_name)
            if per is None:
                raise ValueError(
                    f"loss {loss_name!r} has no per-sample form; use a "
                    "named loss"
                )
            return per(batch["y"], logits)

        rules = self.getOrDefault(self.shardingRules)
        if rules is not None:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

            from sparkdl_tpu.parallel.tp import (
                init_tp_train_state,
                make_tp_train_step,
                param_path_specs,
            )

            def weighted_loss(params, batch):
                # global arrays under GSPMD: the weighted mean is exact
                per = per_sample(params, batch)
                w = batch["w"]
                return (per * w).sum() / w.sum()

            from sparkdl_tpu.parallel.trainer import current_device_slice

            devices = np.asarray(current_device_slice() or jax.devices())
            shape = self.getOrDefault(self.meshShape)
            if shape is not None:
                dp, tp = (int(s) for s in shape)
                if dp * tp != devices.size:
                    raise ValueError(
                        f"meshShape {tuple(shape)} needs {dp * tp} devices, "
                        f"have {devices.size}"
                    )
            else:
                dp = 2 if devices.size % 2 == 0 and devices.size > 1 else 1
            mesh = Mesh(
                devices.reshape(dp, devices.size // dp), ("data", "model")
            )
            if distributed and dp % nprocs:
                raise ValueError(
                    f"multi-host DP x TP needs the data axis ({dp}) to be "
                    f"a multiple of the process count ({nprocs}) so every "
                    "host's batch shard lives on its own chips"
                )
            specs = param_path_specs(variables, rules, model_axis="model")
            if distributed:
                # every process holds identical initial variables (same
                # init seed / same pretrained file); each materializes
                # only its addressable shards of the global placement
                placed = runner.place_global(variables, mesh, specs)
                state = init_train_state(placed, tx)
            else:
                state = init_tp_train_state(variables, tx, mesh, specs)
            step_fn = make_tp_train_step(weighted_loss, tx, mesh, specs)

            def place_batch(b):
                return {
                    "x": jax.device_put(
                        jnp.asarray(b["x"]),
                        NamedSharding(mesh, P("data", None, None, None)),
                    ),
                    "y": jax.device_put(
                        jnp.asarray(b["y"]), NamedSharding(mesh, P("data"))
                    ),
                    "w": jax.device_put(
                        jnp.asarray(b["w"]), NamedSharding(mesh, P("data"))
                    ),
                }
        else:
            mesh = runner.make_global_mesh() if distributed else make_mesh()
            state = init_train_state(variables, tx)
            step_fn = make_train_step(per_sample, tx, mesh, weighted=True)

            def place_batch(b):
                return shard_batch(
                    {
                        "x": jnp.asarray(b["x"]),
                        "y": jnp.asarray(b["y"]),
                        "w": jnp.asarray(b["w"]),
                    },
                    mesh,
                )

        if distributed:
            # same placement for both arms: host-local rows assemble into
            # global data-sharded arrays on the (global) mesh
            def place_batch(b):  # noqa: F811 - deliberate override
                return runner.global_batch(b, mesh)

        n_dev = int(mesh.devices.size)
        # global batch splits evenly across the mesh (and hence hosts)
        batch_size = max(batch_size - batch_size % n_dev, n_dev)
        local_bs = batch_size // nprocs if distributed else batch_size
        n = x.shape[0]  # this host's rows
        if distributed:
            # identical step count on every host, derived from the global
            # row count — hosts running different numbers of collective
            # steps would wedge the job (same contract as
            # KerasImageFileEstimator)
            max_local_rows = -(-n_global // nprocs)
            steps_per_epoch = max(1, -(-max_local_rows // local_bs))
        else:
            steps_per_epoch = max(1, -(-n // local_bs))

        ckpt_dir = self.getOrDefault(self.checkpointDir)
        start_epoch = 0
        namespace = None
        if ckpt_dir:
            # computed once per fit: the fingerprint sums every
            # initialVariables leaf, so per-epoch recomputation would
            # re-scan the full pretrained pytree each save
            namespace = self._ckpt_namespace()
            start_epoch, state = self._maybe_restore(
                ckpt_dir, namespace, state, max_epoch=epochs
            )
            if start_epoch >= epochs and start_epoch > 0:
                logger.info(
                    "checkpoint already at epoch %d == requested epochs=%d; "
                    "returning the checkpointed weights without training",
                    start_epoch,
                    epochs,
                )
        if distributed and rules is None:
            # params start host-local (same init on every process) — lift
            # onto the global mesh, replicated (after restore, which works
            # on host arrays)
            state = runner.replicate(state, mesh)
        # per-host permutation when each host shuffles only its own shard
        rng = np.random.RandomState(
            (seed * 7919 + jax.process_index()) % 2**32
            if distributed
            else seed % 2**32
        )
        # replay restored epochs' draws: epoch e always trains on the e-th
        # permutation, so a resumed fit is step-for-step identical to an
        # uninterrupted one (same contract as KerasImageFileEstimator)
        for _ in range(start_epoch):
            rng.permutation(n)
        last_loss = None
        ckptr = self._make_checkpointer() if ckpt_dir else None
        # preemption contract: SIGTERM flags the token, the loop raises the
        # typed Preempted at the next step boundary, the finally flush
        # commits the last completed epoch, and a re-fit resumes
        # bit-identically (permutation replay above) — same as
        # KerasImageFileEstimator
        try:
            with preemption_scope() as ptoken, fit_profiler(
                "FlaxImageFileEstimator",
                epochs=epochs,
                steps_per_epoch=steps_per_epoch,
            ) as prof:
                for epoch in range(start_epoch, epochs):
                    order = rng.permutation(n)
                    # the epoch as a sparkdl_tpu.data Dataset (cyclic-pad
                    # batch composition; pad rows carry zero weight, so the
                    # update is the exact mean over the real rows)
                    epoch_ds = in_memory_epoch_dataset(
                        order, x, y, local_bs, steps_per_epoch, weighted=True
                    )
                    for batch in epoch_ds:
                        ptoken.check()
                        inject.fire("estimator.step")
                        with prof.step():
                            state, loss = step_fn(state, place_batch(batch))
                    inject.fire("estimator.epoch")
                    last_loss = float(loss)
                    prof.epoch(epoch + 1, last_loss)
                    logger.info(
                        "epoch %d/%d loss=%.4f", epoch + 1, epochs, last_loss
                    )
                    if ckptr is not None:
                        with prof.checkpoint(epoch=epoch + 1):
                            checkpointing.save_epoch(
                                ckptr, ckpt_dir, namespace, epoch + 1,
                                self._ckpt_payload(state),
                            )
                        inject.fire("estimator.checkpoint_saved")
        finally:
            if ckptr is not None:
                ckptr.wait_until_finished()
                ckptr.close()

        def to_host(a):
            # multi-host TP leaves have non-addressable shards: assemble
            # the full value via allgather; replicated/local leaves read
            # directly
            if (
                getattr(a, "is_fully_addressable", True)
                or getattr(a.sharding, "is_fully_replicated", False)
            ):
                return np.asarray(a)
            from jax.experimental import multihost_utils

            return np.asarray(multihost_utils.process_allgather(a, tiled=True))

        tuned = jax.tree_util.tree_map(to_host, state.params)
        transformer = FlaxImageFileTransformer(
            inputCol=self.getInputCol(),
            outputCol=self.getOutputCol(),
            imageLoader=self.getImageLoader(),
            module=module,
            variables=tuned,
        )
        transformer._training_loss = last_loss
        return transformer

    # ------------------------------------------------------------------
    # orbax checkpoint / resume — same contract as KerasImageFileEstimator
    # (namespaced per configuration, epochs excluded, async commits,
    # epoch-capped restore); works for both the DP and the GSPMD DP x TP
    # state (restored leaves are re-placed onto the fresh state's
    # shardings, so TP-sharded opt states land back where they belong).
    # ------------------------------------------------------------------
    @staticmethod
    def _ckpt_payload(state):
        payload = {
            "params": state.params,
            "opt_state": state.opt_state,
            "step": state.step,
        }
        if getattr(state, "batch_stats", None) is not None:
            payload["batch_stats"] = state.batch_stats
        return payload

    def _ckpt_namespace(self) -> str:
        """Deterministic per-configuration subdirectory.  The trajectory
        fingerprint covers the module (via
        :func:`checkpointing.stable_description` — process-stable even
        for callable attn_impl / optax closures), optimizer,
        loss, trajectory fitParams (epochs excluded — a stopping point,
        not a trajectory parameter) and a cheap digest of the initial
        variables (shapes + per-leaf sums), so different pretrained
        starting points never restore each other's state.  Sharding knobs
        (shardingRules/meshShape) are excluded: TP == DP numerics is a
        pinned invariant, so placement does not change the trajectory."""
        import hashlib
        import json

        stable = checkpointing.stable_description

        fit_params = {
            k: v
            for k, v in (self.getOrDefault(self.fitParams) or {}).items()
            if k != "epochs"
        }
        init_vars = self.getOrDefault(self.initialVariables)
        if init_vars is None:
            vars_digest = "init"
        else:
            leaves = jax.tree_util.tree_leaves_with_path(init_vars)
            vars_digest = hashlib.sha256(
                json.dumps(
                    [
                        (
                            jax.tree_util.keystr(k),
                            list(np.shape(v)),
                            float(np.asarray(v, np.float64).sum()),
                        )
                        for k, v in leaves
                    ],
                    sort_keys=True,
                ).encode()
            ).hexdigest()[:16]
        payload = json.dumps(
            {
                "module": stable(self.getOrDefault(self.module)),
                "optimizer": stable(self.getOrDefault(self.optimizer)),
                "loss": stable(self.getOrDefault(self.loss)),
                "fitParams": sorted(
                    (str(k), stable(v)) for k, v in fit_params.items()
                ),
                "initialVariables": vars_digest,
                "labelCol": self.getLabelCol(),
                "inputCol": self.getInputCol(),
            },
            sort_keys=True,
        )
        return "fit_" + hashlib.sha256(payload.encode()).hexdigest()[:12]

    @staticmethod
    def _make_checkpointer():
        return checkpointing.make_async_checkpointer()

    def _maybe_restore(self, ckpt_dir: str, namespace: str, state,
                       max_epoch: int):
        epochs = checkpointing.committed_epochs(
            ckpt_dir, namespace, max_epoch=max_epoch
        )
        if not epochs:
            return 0, state
        latest = epochs[-1]

        payload = self._ckpt_payload(state)

        def shape_template(a):
            # orbax only reads the template's structure/shape/dtype, so a
            # zeros array suffices — and unlike np.asarray it neither
            # copies values nor trips over multi-host TP leaves whose
            # shards live on peer hosts
            return np.zeros(
                getattr(a, "shape", np.shape(a)),
                getattr(a, "dtype", None) or np.asarray(a).dtype,
            )

        template = jax.tree_util.tree_map(shape_template, payload)
        restored = checkpointing.restore_epoch(
            ckpt_dir, namespace, latest, template
        )
        # GSPMD (TP) leaves are re-placed onto the fresh state's
        # NamedShardings; everything else goes back to HOST arrays — a
        # single-device-committed restore would be rejected against the
        # mesh-sharded batch (the same trap KerasImageFileEstimator
        # documents), while plain numpy lets the shard_map step place it.
        # Cross-process placements go through make_array_from_callback
        # (each process materializes only its addressable shards); local
        # NamedShardings keep the direct device_put.
        from jax.sharding import NamedSharding as _NS

        def _place(tmpl, arr):
            if hasattr(tmpl, "sharding") and isinstance(tmpl.sharding, _NS):
                if getattr(tmpl, "is_fully_addressable", True):
                    return jax.device_put(jnp.asarray(arr), tmpl.sharding)
                arr = np.asarray(arr)
                return jax.make_array_from_callback(
                    arr.shape, tmpl.sharding,
                    lambda idx, _a=arr: _a[idx],
                )
            return np.asarray(arr)

        placed = jax.tree_util.tree_map(_place, payload, restored)
        import dataclasses

        new_state = dataclasses.replace(
            state,
            params=placed["params"],
            opt_state=placed["opt_state"],
            step=placed["step"],
            batch_stats=placed.get("batch_stats", state.batch_stats),
        )
        logger.info("resuming from checkpoint epoch %d", latest)
        return latest, new_state
