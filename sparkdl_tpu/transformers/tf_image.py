"""TFImageTransformer — run an arbitrary XlaFunction over an image column.

Reference analog: ``python/sparkdl/transformers/tf_image.py``† (SURVEY.md §2,
§3.1): applies a TF graph to an image-struct column via TensorFrames,
outputting an MLlib Vector or a new image struct.  Here the graph is an
:class:`~sparkdl_tpu.graph.function.XlaFunction`; decode happens host-side
(zero-copy ``frombuffer``), resize + channel handling + model run happen
on-device in one jitted program per batch shape.

The reference name is kept (``TFImageTransformer``); ``TPUImageTransformer``
is the native spelling.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.param.base import Param, TypeConverters, keyword_only
from sparkdl_tpu.param.converters import SparkDLTypeConverters
from sparkdl_tpu.param.shared import (
    HasInputCol,
    HasOutputCol,
    HasOutputMode,
)
from sparkdl_tpu.transformers.utils import (
    DEFAULT_BATCH_SIZE,
    cast_and_resize_on_device,
    make_image_decode_plan,
    place_params,
    to_image_structs,
    to_vectors,
    transform_batched,
)


class TFImageTransformer(Transformer, HasInputCol, HasOutputCol, HasOutputMode):
    """Applies an :class:`XlaFunction` to an image-struct column.

    ``channelOrder`` is the order the function expects its input channels in
    ('RGB', 'BGR', or 'L'); stored image structs are BGR (Spark convention),
    and the conversion happens on device.
    """

    graph = Param(
        "undefined",
        "graph",
        "XlaFunction to apply to the image column",
        SparkDLTypeConverters.toXlaFunction,
    )
    inputShape = Param(
        "undefined",
        "inputShape",
        "(height, width) the function expects; images are resized on device. "
        "None runs images at their stored size (must then be uniform).",
    )
    channelOrder = Param(
        "undefined",
        "channelOrder",
        "channel order the function expects: 'RGB', 'BGR' or 'L'",
        SparkDLTypeConverters.toChannelOrder,
    )
    batchSize = Param(
        "undefined",
        "batchSize",
        "rows per device batch (one XLA program per batch shape)",
        TypeConverters.toInt,
    )

    @keyword_only
    def __init__(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        graph=None,
        inputShape: Optional[Tuple[int, int]] = None,
        channelOrder: str = "RGB",
        outputMode: str = "vector",
        batchSize: int = DEFAULT_BATCH_SIZE,
    ):
        super().__init__()
        self._setDefault(
            inputShape=None,
            channelOrder="RGB",
            outputMode="vector",
            batchSize=DEFAULT_BATCH_SIZE,
        )
        kwargs = self._input_kwargs
        self.setParams(**kwargs)

    @keyword_only
    def setParams(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        graph=None,
        inputShape: Optional[Tuple[int, int]] = None,
        channelOrder: str = "RGB",
        outputMode: str = "vector",
        batchSize: int = DEFAULT_BATCH_SIZE,
    ):
        kwargs = self._input_kwargs
        return self._set(**kwargs)

    def setGraph(self, value):
        return self._set(graph=value)

    def getGraph(self):
        return self.getOrDefault(self.graph)

    # ------------------------------------------------------------------
    def _transform(self, dataset):
        fn = self.getGraph()
        size = self.getOrDefault(self.inputShape)
        order = self.getOrDefault(self.channelOrder)

        if len(fn.output_names) != 1:
            raise ValueError(
                "TFImageTransformer requires a single-output XlaFunction "
                f"(got outputs {fn.output_names}); use TFTransformer with an "
                "outputMapping for multi-output functions."
            )
        params = place_params(fn.params)
        want_bgr = order == "BGR"

        def model_fn(x):
            # cast + resize + flip fuse with the fn into one program (so
            # uint8 source-size batches work — link bytes are the serving
            # bottleneck)
            x = cast_and_resize_on_device(x, size)
            # stored order is BGR; flip on device if the fn wants RGB
            if not want_bgr and x.shape[-1] == 3:
                x = x[..., ::-1]
            return fn.apply(params, x)[0]

        # AOT through the engine: persistable when the XlaFunction carries a
        # durable fingerprint (saved file / StableHLO blob).  No donation —
        # outputMode="image" hands the output back row-by-row and the fn is
        # caller-supplied, so aliasing input with output is not provably safe.
        from sparkdl_tpu.engine import engine as _engine

        base_fp = getattr(fn, "fingerprint", None)
        jitted = _engine.function(
            model_fn,
            fingerprint=(
                f"tf_image:{base_fp}:{size}:{order}" if base_fp else None
            ),
            name=f"tf_image_{fn.name}",
        )

        n_channels = 1 if order == "L" else 3

        # the decode plan (shape + dtype) is decided over a whole partition
        # so one program compiles (raises MixedImageSizesError when sizes
        # mix and no input size is set)
        def plan(rows):
            return make_image_decode_plan(rows, n_channels, size)

        as_vectors = self.getOutputMode() == "vector"
        return transform_batched(
            dataset, self.getInputCol(), self.getOutputCol(), jitted, plan,
            to_vectors if as_vectors else to_image_structs,
            self.getOrDefault(self.batchSize),
        )


# Native spelling.
TPUImageTransformer = TFImageTransformer
