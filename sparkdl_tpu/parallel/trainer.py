"""Mesh construction + data-parallel training step.

Replaces the reference's driver-local ``keras model.fit`` hot loop
(``keras_image_file_estimator.py``† — SURVEY.md §3.2: "training never leaves
the driver") with the TPU-native design: the batch is sharded over the
``data`` mesh axis, each device computes grads on its shard under
``shard_map``, and ``lax.pmean`` allreduces them over ICI before the optax
update.  Multi-host runs reuse the same step — ``jax.distributed`` initializes
the global mesh and per-host data loading feeds each host's addressable
shard.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import optax

# Thread-local device restriction: the slice analog for trial-parallel
# tuning (SURVEY.md §2 "trial-parallel across pod slices").  A tuning
# driver binds each worker thread to a disjoint subset of the local
# devices; every make_mesh() an estimator issues on that thread then builds
# its training mesh from the slice instead of all local devices, so k
# trials train concurrently without sharing chips.
_DEVICE_SLICE = threading.local()


def current_device_slice() -> Optional[List]:
    """The devices this thread is restricted to, or None (all local)."""
    return getattr(_DEVICE_SLICE, "devices", None)


@contextmanager
def device_slice(devices: Sequence):
    """Restrict ``make_mesh`` on this thread to ``devices`` for the scope."""
    prev = current_device_slice()
    _DEVICE_SLICE.devices = list(devices)
    try:
        yield
    finally:
        _DEVICE_SLICE.devices = prev


def bind_device_slice(devices: Optional[Sequence]) -> None:
    """Non-scoped form of :func:`device_slice` for pool-thread initializers
    (a ThreadPoolExecutor binds each worker thread once, for its life)."""
    _DEVICE_SLICE.devices = list(devices) if devices is not None else None


def partition_devices(k: int, devices: Optional[Sequence] = None):
    """Split the local devices into ``k`` disjoint, equal, contiguous
    slices (contiguity keeps each slice's collectives on neighboring
    chips).  Raises when the devices don't divide evenly — a ragged split
    would give trials different DP widths and different batch math."""
    devices = list(devices if devices is not None else jax.devices())
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    n = len(devices)
    if n % k:
        raise ValueError(
            f"{n} devices do not partition into {k} equal slices"
        )
    per = n // k
    return [devices[i * per : (i + 1) * per] for i in range(k)]


def make_mesh(
    n_devices: Optional[int] = None,
    axis_names: Sequence[str] = ("data",),
    axis_shape: Optional[Sequence[int]] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a device mesh.  Default: the thread's :func:`device_slice` when
    bound, else all local devices, on one ``data`` axis (pure DP).  For
    DP x TP pass e.g. ``axis_names=("data", "model"), axis_shape=(2, 4)``."""
    if devices is None:
        devices = current_device_slice()
    devices = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        devices = devices[:n_devices]
    if axis_shape is None:
        axis_shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    return Mesh(
        np.asarray(devices).reshape(tuple(axis_shape)),
        axis_names=tuple(axis_names),
    )


@dataclass
class TrainState:
    """Carries everything a training step mutates (flax/optax convention)."""

    params: Any
    opt_state: Any
    step: jnp.ndarray
    batch_stats: Any = None

    def tree_flatten(self):  # pragma: no cover - registered below
        return (
            (self.params, self.opt_state, self.step, self.batch_stats),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):  # pragma: no cover
        return cls(*children)


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt_state, s.step, s.batch_stats), None),
    lambda aux, c: TrainState(*c),
)


def shard_batch(batch, mesh: Mesh, axis: str = "data"):
    """Place a host batch onto the mesh sharded along its leading dim."""
    spec = P(axis)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(
            x, NamedSharding(mesh, P(*([axis] + [None] * (x.ndim - 1))))
        ),
        batch,
    )


def make_train_step(
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    data_axis: str = "data",
    donate: bool = True,
    weighted: bool = False,
):
    """Build the jitted DP training step.

    Default: ``loss_fn(params, batch) -> scalar loss`` computes the
    *per-shard* loss; the step averages gradients across the ``data`` axis
    with ``lax.pmean`` (the NCCL-allreduce analog, riding ICI) and applies
    the optax update identically on every device, keeping params replicated.

    With ``weighted=True``, ``loss_fn(params, batch) -> (local_bs,)``
    per-sample losses and ``batch`` carries a ``"w"`` weight vector; the
    step optimizes the exact global weighted mean, so zero-weight rows
    (ragged-batch padding) contribute nothing to loss or gradient.
    """

    def step(state: TrainState, batch):
        def sharded_grads(params, local_batch):
            # params come in replicated (unvarying over the data axis).
            # Differentiating a per-shard loss w.r.t. an unvarying value
            # makes the transpose psum the cotangents by itself, so a
            # pmean/psum after it would count the shards a second time.
            # Cast the params to varying first: the grads are then truly
            # shard-local and the ONE allreduce below (the NCCL-allreduce
            # analog, riding ICI) is explicit.
            params = jax.lax.pcast(params, (data_axis,), to="varying")
            if weighted:

                def local_weighted(p):
                    per = loss_fn(p, local_batch)
                    w = local_batch["w"]
                    w_total = jax.lax.psum(w.sum(), axis_name=data_axis)
                    return (per * w).sum() / w_total

                # each shard's loss is its share of the global weighted
                # mean; psum of both loss and grads, together with the
                # global w_total normalization, is the exact weighted mean
                loss, grads = jax.value_and_grad(local_weighted)(params)
                return jax.lax.psum((loss, grads), axis_name=data_axis)
            loss, grads = jax.value_and_grad(loss_fn)(params, local_batch)
            # equal-sized shards: mean of per-shard mean-loss grads == the
            # global-mean gradient
            return jax.lax.pmean((loss, grads), axis_name=data_axis)

        batch_spec = jax.tree_util.tree_map(
            lambda x: P(*([data_axis] + [None] * (x.ndim - 1))), batch
        )
        loss, grads = shard_map(
            sharded_grads,
            mesh=mesh,
            in_specs=(P(), batch_spec),
            out_specs=(P(), P()),
        )(state.params, batch)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return (
            TrainState(params, opt_state, state.step + 1, state.batch_stats),
            loss,
        )

    donate_argnums = (0,) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def init_train_state(params, tx: optax.GradientTransformation) -> TrainState:
    return TrainState(
        params=params,
        opt_state=tx.init(params),
        step=jnp.zeros((), jnp.int32),
    )
