"""Model registry — the ``keras_applications.py``† analog.

Maps model name -> Flax module constructor, Keras oracle constructor, input
geometry, preprocessing mode, and featurization cut-point size, mirroring the
reference's ``KERAS_APPLICATION_MODELS`` / ``getKerasApplicationModel`` and
its ``SUPPORTED_MODELS`` list (``python/sparkdl/transformers/named_image.py``†
consumed the same registry).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.inception_v3 import InceptionV3
from sparkdl_tpu.models.mobilenet_v2 import MobileNetV2
from sparkdl_tpu.models.resnet import ResNet50
from sparkdl_tpu.models.vgg import VGG16, VGG19
from sparkdl_tpu.models.xception import Xception

_CAFFE_MEAN_BGR = (103.939, 116.779, 123.68)
_TORCH_MEAN = (0.485, 0.456, 0.406)
_TORCH_STD = (0.229, 0.224, 0.225)


def preprocess_input(x, mode: str):
    """Keras ``preprocess_input`` parity, jnp-traceable.

    ``x``: float RGB in [0, 255], NHWC.
    """
    if mode == "tf":
        return x / 127.5 - 1.0
    if mode == "caffe":
        x = x[..., ::-1]  # RGB -> BGR
        return x - jnp.asarray(_CAFFE_MEAN_BGR, dtype=x.dtype)
    if mode == "torch":
        x = x / 255.0
        return (x - jnp.asarray(_TORCH_MEAN, dtype=x.dtype)) / jnp.asarray(
            _TORCH_STD, dtype=x.dtype
        )
    raise ValueError(f"Unknown preprocessing mode: {mode!r}")


class KerasApplicationModel:
    """One registry entry: everything the transformers need to run a named
    pretrained CNN (the per-model class pattern of ``keras_applications.py``†).
    """

    def __init__(
        self,
        name: str,
        flax_cls,
        keras_name: str,
        input_size: Tuple[int, int],
        feature_size: int,
        preprocess_mode: str,
        num_classes: int = 1000,
        module_kwargs: Optional[Dict[str, Any]] = None,
    ):
        self.name = name
        self.flax_cls = flax_cls
        self.keras_name = keras_name
        self.input_size = input_size
        self.feature_size = feature_size
        self.preprocess_mode = preprocess_mode
        self.module_kwargs = dict(module_kwargs or {})
        self.num_classes = num_classes

    # -- geometry / preprocessing ------------------------------------
    def inputShape(self) -> Tuple[int, int]:
        return self.input_size

    def preprocess(self, x):
        return preprocess_input(x, self.preprocess_mode)

    # -- online serving hooks ----------------------------------------
    def serving_item_spec(self) -> Tuple[Tuple[int, int, int], Any]:
        """The per-item ``(shape, dtype)`` an online endpoint for this
        model serves — what ``ModelServer.register(item_shape=...)`` and
        a cold ``warmup()`` need before any request has arrived."""
        import numpy as np

        h, w = self.input_size
        return (h, w, 3), np.float32

    def warmup_buckets(self, max_batch: int = 32) -> Tuple[int, ...]:
        """The shape buckets an endpoint for this model should pre-trace
        (the full serving ladder; one program per bucket)."""
        from sparkdl_tpu.transformers.utils import bucket_ladder

        return bucket_ladder(max_batch)

    def serving_prologue(self):
        """The fused on-device input prologue for an online endpoint of
        this model: cast/bilinear-resize to the model's input size +
        Keras-parity :func:`preprocess_input`, as one jnp-traceable
        callable for ``ModelServer.register(prologue=...)`` — the
        decode-output → model-input pipeline compiles *into* the
        endpoint executable instead of round-tripping through the
        host-side ``device_resize`` shape groups."""
        from sparkdl_tpu.transformers.utils import make_input_prologue

        return make_input_prologue(
            size=self.input_size, preprocess=self.preprocess
        )

    # -- model construction ------------------------------------------
    def make_module(self, dtype: Optional[Any] = None, include_top: bool = True):
        return self.flax_cls(
            include_top=include_top, dtype=dtype, **self.module_kwargs
        )

    def keras_model(self, weights: Optional[str] = "imagenet"):
        """Build the Keras oracle/weight-source model (lazy keras import)."""
        import keras

        ctor = getattr(keras.applications, self.keras_name)
        return ctor(weights=weights, classifier_activation=None)

    def load_variables(self, weights="imagenet"):
        """Flax variables for this model.

        ``weights``: ``"imagenet"`` / ``None`` (delegated to Keras) or an
        already-built Keras model to port from.
        """
        from sparkdl_tpu.models.keras_port import port_keras_weights

        model = (
            weights
            if not isinstance(weights, (str, type(None)))
            else self.keras_model(weights)
        )
        variables = port_keras_weights(model)
        if self.module_kwargs:
            # TPU-layout module variants (e.g. Xception's lane-aligned
            # 768-wide middle flow) hold the Keras weights zero-padded;
            # numerics are unchanged (zero channels stay zero end to end)
            from sparkdl_tpu.models.keras_port import pad_variables_to_module

            variables = pad_variables_to_module(
                variables, self.make_module(), self.input_size
            )
        return variables

    def __repr__(self):
        return (
            f"KerasApplicationModel({self.name}, input={self.input_size}, "
            f"features={self.feature_size}, mode={self.preprocess_mode!r})"
        )


KERAS_APPLICATION_MODELS: Dict[str, KerasApplicationModel] = {
    m.name: m
    for m in [
        KerasApplicationModel("InceptionV3", InceptionV3, "InceptionV3",
                              (299, 299), 2048, "tf"),
        # middle_width=768 (vs Keras's 728): 6x128 MXU lane alignment
        # buys +20% throughput on this chip for +5.6% padded FLOPs
        # (measured on the chip in r4); Keras weights port zero-padded,
        # numerics unchanged
        KerasApplicationModel("Xception", Xception, "Xception",
                              (299, 299), 2048, "tf",
                              module_kwargs={"middle_width": 768}),
        KerasApplicationModel("ResNet50", ResNet50, "ResNet50",
                              (224, 224), 2048, "caffe"),
        KerasApplicationModel("VGG16", VGG16, "VGG16",
                              (224, 224), 4096, "caffe"),
        KerasApplicationModel("VGG19", VGG19, "VGG19",
                              (224, 224), 4096, "caffe"),
        KerasApplicationModel("MobileNetV2", MobileNetV2, "MobileNetV2",
                              (224, 224), 1280, "tf"),
    ]
}

# The reference's SUPPORTED_MODELS (named_image.py†) plus MobileNetV2.
SUPPORTED_MODELS = tuple(KERAS_APPLICATION_MODELS)


def get_keras_application_model(name: str) -> KerasApplicationModel:
    if name not in KERAS_APPLICATION_MODELS:
        raise ValueError(
            f"Unsupported model: {name!r}. Supported: {sorted(SUPPORTED_MODELS)}"
        )
    return KERAS_APPLICATION_MODELS[name]


# Reference-spelling alias (sparkdl.transformers.keras_applications†).
getKerasApplicationModel = get_keras_application_model


def fold_bgr_flip_into_stem(variables, preprocess_mode: str):
    """Fold the BGR->RGB input flip into the stem conv's weights.

    The transformers' fused forward flips the stored-BGR batch before the
    CNN (``x[..., ::-1]``) — a pure-bandwidth op XLA cannot elide.  When
    the model's preprocessing is channel-symmetric (``"tf"`` mode: the same
    affine per channel), reversing the *input-channel axis of the first
    conv kernel* is mathematically identical, and the flip disappears from
    the program entirely.

    Pass the entry's ``preprocess_mode``: folding under channel-asymmetric
    preprocessing (``"caffe"`` per-channel mean subtraction) would change
    the numerics, so any mode other than ``"tf"`` returns ``None`` here —
    the gate lives in this helper precisely so call sites cannot forget it
    (benchmarks/profile_ops.py once did, and profiled a numerically wrong
    program for VGG/ResNet).

    Returns the folded variables, or ``None`` when folding is unsafe
    (non-'tf' preprocessing, or not exactly one 3-input-channel conv
    kernel — caller keeps the runtime flip).
    """
    if preprocess_mode != "tf":
        return None
    flat, treedef = jax.tree_util.tree_flatten_with_path(variables)
    hits = [
        i
        for i, (path, leaf) in enumerate(flat)
        if getattr(leaf, "ndim", 0) == 4
        and leaf.shape[2] == 3
        and any(getattr(k, "key", None) == "kernel" for k in path)
    ]
    if len(hits) != 1:
        return None
    leaves = [leaf for _, leaf in flat]
    i = hits[0]
    leaves[i] = leaves[i][:, :, ::-1, :]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def decode_predictions(preds, top: int = 5):
    """``imagenet_utils.decode_predictions`` analog.

    Label priority: Keras's cached ``imagenet_class_index.json`` (real
    wnids + names) when present, else the vendored class-name list
    (:mod:`sparkdl_tpu.models.imagenet_labels` — real names, synthetic
    wnid placeholders; no network needed).  Accepts logits or
    probabilities, shape (batch, 1000).
    """
    import numpy as np

    preds = np.asarray(preds)
    class_index = None
    try:  # pragma: no cover - depends on local keras cache
        import json
        import os

        path = os.path.expanduser(
            "~/.keras/models/imagenet_class_index.json"
        )
        if os.path.exists(path):
            with open(path) as fh:
                class_index = json.load(fh)
    except Exception:
        class_index = None

    from sparkdl_tpu.models.imagenet_labels import IMAGENET_CLASS_NAMES

    results = []
    for row in preds:
        top_idx = row.argsort()[-top:][::-1]
        entries = []
        is_imagenet_shaped = row.shape[-1] == 1000
        for i in top_idx:
            i = int(i)
            if class_index is not None and is_imagenet_shaped:
                wnid, label = class_index[str(i)]
            elif is_imagenet_shaped and i < len(IMAGENET_CLASS_NAMES):
                wnid, label = f"n{i:08d}", IMAGENET_CLASS_NAMES[i]
            else:
                wnid, label = f"n{i:08d}", f"class_{i}"
            entries.append((wnid, label, float(row[i])))
        results.append(entries)
    return results
