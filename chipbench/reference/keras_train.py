"""The plain training step for a Keras model file: loss, gradients, batch-norm
statistics and the plain-SGD update, written out in jax.numpy at float32 with
matmuls at ``highest`` precision.  Keras (a library the user's model comes
in) evaluates the layers; nothing here imports the program.
"""

from __future__ import annotations

import numpy as np


def load_weights(path: str):
    """(trainable, non_trainable) numpy lists of a ``.keras`` file."""
    import jax
    import keras

    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        model = keras.saving.load_model(path, compile=False)
        return (
            model,
            [np.asarray(v) for v in model.trainable_variables],
            [np.asarray(v) for v in model.non_trainable_variables],
        )


def sparse_categorical_crossentropy(y, probs):
    """Keras's loss on softmax outputs, per row."""
    import jax.numpy as jnp

    picked = jnp.take_along_axis(probs, y[:, None].astype(jnp.int32), axis=1)[:, 0]
    return -jnp.log(jnp.clip(picked, 1e-7, 1.0 - 1e-7))


def sgd_steps(model, trainable, non_trainable, x, y, learning_rate: float,
              steps: int, state_dtype=None):
    """``steps`` plain-SGD steps on the one batch ``(x, y)``.  Returns each
    step's loss, the first step's gradient, and the state after the first
    and after the last step.  ``state_dtype`` (the control) keeps the
    trainable state, and computes, in a lower precision: every update is
    rounded to it, as a train state stored in bfloat16 would be."""
    import jax
    import jax.numpy as jnp

    def loss_of(tr, nt, xb, yb):
        if state_dtype is not None:
            xb = xb.astype(state_dtype)
        probs, new_nt = model.stateless_call(tr, nt, xb, training=True)
        per_row = sparse_categorical_crossentropy(yb, probs.astype(jnp.float32))
        return per_row.mean(), new_nt

    @jax.jit
    def step(tr, nt, xb, yb):
        with jax.default_matmul_precision("highest"):
            (loss, new_nt), grads = jax.value_and_grad(
                loss_of, has_aux=True)(tr, nt, xb, yb)
        new_tr = [(t - learning_rate * g).astype(t.dtype)
                  for t, g in zip(tr, grads)]
        new_nt = [n.astype(o.dtype) for n, o in zip(new_nt, nt)]
        return loss, grads, new_tr, new_nt

    dtype = jnp.float32 if state_dtype is None else state_dtype
    tr = [jnp.asarray(t).astype(dtype) for t in trainable]
    nt = [jnp.asarray(n) for n in non_trainable]
    xb, yb = jnp.asarray(x), jnp.asarray(y)
    losses, first_grads, after_one = [], None, None

    def as_float32(leaves):
        return [np.asarray(leaf.astype(jnp.float32)) for leaf in leaves]

    for s in range(steps):
        loss, grads, tr, nt = step(tr, nt, xb, yb)
        losses.append(float(loss))
        if s == 0:
            first_grads, after_one = as_float32(grads), as_float32(tr)
    return {
        "losses": losses, "first_grads": first_grads, "after_one": after_one,
        "trainable": as_float32(tr),
        "non_trainable": [np.asarray(n) for n in nt],
    }


def leaf_norms(leaves) -> np.ndarray:
    return np.asarray([
        float(np.sqrt(np.sum(np.square(np.asarray(a, np.float64)))))
        for a in leaves
    ])


def norm_gaps(got_norms, ref_norms, keep=None) -> np.ndarray:
    """Every leaf's gap between the program's norm and the reference's (not
    the norm of their difference), against the reference's norm of that leaf
    or of the median leaf, whichever is larger; NaN where the leaf is left
    out."""
    got_norms, ref_norms = np.asarray(got_norms), np.asarray(ref_norms)
    if keep is None:
        keep = np.ones(len(ref_norms), bool)
    scale = np.maximum(ref_norms, float(np.median(ref_norms[keep])))
    return np.where(keep, np.abs(got_norms - ref_norms) / scale, np.nan)
