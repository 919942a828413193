"""Keras -> Flax weight porting.

The reference reused ``keras.applications`` weights directly (its models *were*
Keras models, frozen to GraphDefs — ``keras_applications.py``†,
``keras_utils.py``†).  Here pretrained/user Keras weights are ported into the
Flax model zoo's parameter pytrees.

Mapping strategy: Keras auto-generated layer names (``conv2d_37``,
``batch_normalization_5``...) shift by a global uid offset between
constructions, but their per-type *ordering* in ``model.layers`` is stable.
``normalized_layer_names`` renumbers each auto-named type from zero in layer
order, which yields deterministic names the Flax modules hardcode.  Explicitly
named layers (``conv1_conv``, ``block1_sepconv1``...) pass through unchanged.
"""

from __future__ import annotations

import re
from typing import Any, Dict

import jax.numpy as jnp

# Keras auto-name prefixes that get renumbered per type.
_AUTO_PREFIXES = frozenset(
    {
        "conv2d",
        "batch_normalization",
        "dense",
        "depthwise_conv2d",
        "separable_conv2d",
        "activation",
        "concatenate",
        "max_pooling2d",
        "average_pooling2d",
        "global_average_pooling2d",
        "dropout",
        "input_layer",
        "zero_padding2d",
        "add",
        "flatten",
        "rescaling",
    }
)

_SUFFIX_RE = re.compile(r"^(.*?)(?:_(\d+))?$")


def normalized_layer_names(model) -> Dict[str, str]:
    """Map each Keras layer's session-dependent name to a deterministic one.

    Keras uid suffixes increment in layer *creation* order (which matches the
    application code order the Flax modules mirror), while ``model.layers`` is
    topologically sorted — so normalization subtracts the per-prefix minimum
    suffix rather than renumbering by list position.
    """
    minima: Dict[str, int] = {}
    parsed: Dict[str, tuple] = {}
    for layer in model.layers:
        m = _SUFFIX_RE.match(layer.name)
        base, suffix = m.group(1), int(m.group(2) or 0)
        parsed[layer.name] = (base, suffix)
        if base in _AUTO_PREFIXES:
            minima[base] = min(minima.get(base, suffix), suffix)
    out: Dict[str, str] = {}
    for layer in model.layers:
        base, suffix = parsed[layer.name]
        if base in _AUTO_PREFIXES:
            idx = suffix - minima[base]
            out[layer.name] = base if idx == 0 else f"{base}_{idx}"
        else:
            out[layer.name] = layer.name
    return out


def port_keras_weights(model) -> Dict[str, Any]:
    """Convert a built Keras model's weights to Flax variable collections.

    Returns ``{"params": {...}, "batch_stats": {...}}`` keyed by normalized
    layer name, with per-layer leaves following Flax conventions
    (``kernel``/``bias`` for convs and dense, ``scale``/``bias`` +
    ``mean``/``var`` for batch norm, ``depthwise_kernel``/``pointwise_kernel``
    for separable convs).
    """
    names = normalized_layer_names(model)
    params: Dict[str, Any] = {}
    batch_stats: Dict[str, Any] = {}
    for layer in model.layers:
        weights = layer.get_weights()
        if not weights:
            continue
        name = names[layer.name]
        cls = type(layer).__name__
        if cls == "Conv2D":
            entry = {"kernel": jnp.asarray(weights[0])}
            if getattr(layer, "use_bias", False):
                entry["bias"] = jnp.asarray(weights[1])
            params[name] = entry
        elif cls == "DepthwiseConv2D":
            # Keras (kh, kw, cin, mult=1) -> flax grouped-conv HWIO (kh, kw, 1, cin)
            kernel = weights[0]
            entry = {"kernel": jnp.asarray(kernel.transpose(0, 1, 3, 2))}
            if getattr(layer, "use_bias", False):
                entry["bias"] = jnp.asarray(weights[1])
            params[name] = entry
        elif cls == "SeparableConv2D":
            entry = {
                "depthwise_kernel": jnp.asarray(weights[0].transpose(0, 1, 3, 2)),
                "pointwise_kernel": jnp.asarray(weights[1]),
            }
            if getattr(layer, "use_bias", False):
                entry["bias"] = jnp.asarray(weights[2])
            params[name] = entry
        elif cls == "Dense":
            entry = {"kernel": jnp.asarray(weights[0])}
            if getattr(layer, "use_bias", False):
                entry["bias"] = jnp.asarray(weights[1])
            params[name] = entry
        elif cls == "BatchNormalization":
            idx = 0
            entry = {}
            if layer.scale:
                entry["scale"] = jnp.asarray(weights[idx])
                idx += 1
            if layer.center:
                entry["bias"] = jnp.asarray(weights[idx])
                idx += 1
            batch_stats[name] = {
                "mean": jnp.asarray(weights[idx]),
                "var": jnp.asarray(weights[idx + 1]),
            }
            if entry:
                params[name] = entry
        else:
            raise NotImplementedError(
                f"No porting rule for Keras layer {layer.name} of type {cls}"
            )
    return {"params": params, "batch_stats": batch_stats}


def pad_variables_to_module(variables, module, input_size):
    """Zero-pad ported Keras weights up to a widened TPU-layout module.

    Some registry modules widen channel trunks for MXU lane alignment
    (e.g. Xception's 728 -> 768 = 6x128 middle flow, +20% throughput
    measured on the chip in r4).  The target shapes come from
    ``jax.eval_shape(module.init)``; every leaf whose target is wider
    pads at the high end of the differing axes with zeros — except BN
    running variances, which pad with ones (identity statistics).  The
    padded channels then stay exactly zero through depthwise convs
    (zero kernels), pointwise convs (zero rows/columns), BN (zero
    scale/bias on zero-mean unit-var stats) and relu, so the widened
    model computes bit-for-bit what the Keras weights define on the
    original channels.
    """
    import jax

    h, w = input_size
    target = jax.eval_shape(
        module.init,
        jax.random.PRNGKey(0),
        jnp.zeros((1, h, w, 3), jnp.float32),
    )
    # lookup by path rather than strict structure matching: ported
    # variables may be a SUBSET of the module tree (a topless Keras
    # model has no 'predictions' layer, which featurization never uses)
    target_shapes = {
        jax.tree_util.keystr(p): tuple(l.shape)
        for p, l in jax.tree_util.tree_leaves_with_path(target)
    }

    def pad(path, leaf):
        key = jax.tree_util.keystr(path)
        if key not in target_shapes:
            raise ValueError(
                f"ported weight {key} has no counterpart in the module"
            )
        tshape = target_shapes[key]
        if tuple(leaf.shape) == tshape:
            return leaf
        if leaf.ndim != len(tshape):
            raise ValueError(
                f"rank mismatch at {key}: {leaf.shape} vs {tshape}"
            )
        pads = []
        for have, want in zip(leaf.shape, tshape):
            if want < have:
                raise ValueError(
                    f"target narrower than ported weights at "
                    f"{key}: {leaf.shape} vs {tshape}"
                )
            pads.append((0, want - have))
        is_var = getattr(path[-1], "key", None) == "var"
        return jnp.pad(
            jnp.asarray(leaf), pads,
            constant_values=1.0 if is_var else 0.0,
        )

    return jax.tree_util.tree_map_with_path(pad, variables)
