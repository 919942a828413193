"""Data-parallel training step for Keras-3 models (JAX backend).

The estimator-side replacement for the reference's driver-local
``keras model.fit`` hot loop (SURVEY.md §3.2): the model's
``stateless_call`` is jax-traceable, so the whole update — forward,
loss, backward, ICI gradient allreduce, optax update — runs as one jitted
shard_map program over the ``data`` mesh axis.

Non-trainable variables (BN moving stats etc.) are carried through the step:
float stats are ``pmean``-averaged across shards (the standard non-sync-BN
DP approximation); non-float state (RNG seeds, counters) advances identically
on every shard and passes through.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import optax

from sparkdl_tpu.parallel.trainer import Mesh


class KerasTrainState(NamedTuple):
    trainable: Sequence
    non_trainable: Sequence
    opt_state: optax.OptState
    step: jnp.ndarray


def init_keras_train_state(model, tx: optax.GradientTransformation):
    trainable = [jnp.asarray(v.value) for v in model.trainable_variables]
    non_trainable = [
        jnp.asarray(v.value) for v in model.non_trainable_variables
    ]
    return KerasTrainState(
        trainable=trainable,
        non_trainable=non_trainable,
        opt_state=tx.init(trainable),
        step=jnp.zeros((), jnp.int32),
    )


def make_keras_train_step(
    model,
    loss_fn: Callable,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    data_axis: str = "data",
    weighted: bool = False,
):
    """``step(state, batch) -> (state, loss)`` with ``batch = {"x": ...,
    "y": ...}`` sharded along the ``data`` axis; params stay replicated.

    With ``weighted=True``, ``loss_fn`` must return *per-sample* losses
    (shape ``(batch,)``) and ``batch`` must carry a ``"w"`` weight vector;
    the step optimizes the exact global weighted mean — zero-weight rows
    (ragged-final-batch padding) contribute nothing to loss or gradient.
    (They still pass through the forward, so BN moving stats see them; that
    bias is one padded batch per epoch and vanishes in the average.)
    """
    def step(state: KerasTrainState, batch):
        def sharded(trainable, non_trainable, local_batch):
            def local_loss(tr):
                outputs, new_nt = model.stateless_call(
                    tr, non_trainable, local_batch["x"], training=True
                )
                if weighted:
                    w = local_batch["w"]
                    w_total = jax.lax.psum(w.sum(), axis_name=data_axis)
                    per = loss_fn(local_batch["y"], outputs)
                    return (per * w).sum() / w_total, new_nt
                return loss_fn(local_batch["y"], outputs), new_nt

            # cast the replicated params to varying so the grads are
            # shard-local and the one allreduce below is explicit (see
            # trainer.make_train_step: left unvarying, the transpose has
            # already summed them over the data axis)
            (loss, new_nt), grads = jax.value_and_grad(
                local_loss, has_aux=True
            )(jax.lax.pcast(trainable, (data_axis,), to="varying"))
            if weighted:
                # each shard's loss is its share of the global weighted
                # mean; psum of loss and grads, with the global w_total
                # normalization, is the exact weighted-mean gradient
                loss, grads = jax.lax.psum(
                    (loss, grads), axis_name=data_axis
                )
            else:
                # equal-sized shards: mean of per-shard mean-loss grads ==
                # the global-mean gradient
                loss, grads = jax.lax.pmean(
                    (loss, grads), axis_name=data_axis
                )
            # float stats (BN moving averages) averaged across shards;
            # integer state (RNG counters) is shard-invariant already
            new_nt = jax.tree_util.tree_map(
                lambda v: jax.lax.pmean(v, axis_name=data_axis)
                if jnp.issubdtype(v.dtype, jnp.floating)
                else v,
                new_nt,
            )
            return loss, new_nt, grads

        batch_spec = jax.tree_util.tree_map(
            lambda x: P(*([data_axis] + [None] * (x.ndim - 1))), batch
        )
        loss, new_nt, grads = shard_map(
            sharded,
            mesh=mesh,
            in_specs=(P(), P(), batch_spec),
            out_specs=(P(), P(), P()),
        )(list(state.trainable), list(state.non_trainable), batch)
        updates, opt_state = tx.update(
            grads, state.opt_state, list(state.trainable)
        )
        trainable = optax.apply_updates(list(state.trainable), updates)
        return (
            KerasTrainState(trainable, new_nt, opt_state, state.step + 1),
            loss,
        )

    return jax.jit(step, donate_argnums=(0,))
