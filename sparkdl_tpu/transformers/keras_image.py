"""KerasImageFileTransformer — URI column → loaded images → Keras model.

Reference analog: ``python/sparkdl/transformers/keras_image.py``† (SURVEY.md
§2): a user ``imageLoader(uri) -> ndarray`` loads + preprocesses each file;
the ``.h5``/``.keras`` model (Keras 3 on its JAX backend) then runs jitted on
TPU — the reference's load-h5-freeze-to-GraphDef step
(``keras_utils.KSessionWrap``†) has no analog because ``stateless_call`` is
already jax-traceable.
"""

from __future__ import annotations

from typing import Optional

import jax

from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.param.base import Param, TypeConverters, keyword_only
from sparkdl_tpu.param.shared import (
    CanLoadImage,
    HasInputCol,
    HasKerasModel,
    HasOutputCol,
    HasOutputMode,
)
from sparkdl_tpu.transformers.utils import (
    DEFAULT_BATCH_SIZE,
    load_keras_function,
    make_loader_decode_plan,
    place_params,
    to_image_structs,
    to_vectors,
    transform_batched,
)


class KerasImageFileTransformer(
    Transformer, HasInputCol, HasOutputCol, HasOutputMode, CanLoadImage,
    HasKerasModel
):
    batchSize = Param(
        "undefined", "batchSize", "rows per device batch", TypeConverters.toInt
    )
    computeDtype = Param(
        "undefined", "computeDtype",
        "'float32' (saved-model default) or 'bfloat16' (mixed policy: f32 "
        "variables, bf16 compute - ~2x MXU throughput on TPU)",
        TypeConverters.toString,
    )

    @keyword_only
    def __init__(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        modelFile: Optional[str] = None,
        imageLoader=None,
        outputMode: str = "vector",
        batchSize: int = DEFAULT_BATCH_SIZE,
        computeDtype: str = "float32",
    ):
        super().__init__()
        self._setDefault(outputMode="vector", batchSize=DEFAULT_BATCH_SIZE,
                         computeDtype="float32")
        kwargs = self._input_kwargs
        self.setParams(**kwargs)

    @keyword_only
    def setParams(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        modelFile: Optional[str] = None,
        imageLoader=None,
        outputMode: str = "vector",
        batchSize: int = DEFAULT_BATCH_SIZE,
        computeDtype: str = "float32",
    ):
        kwargs = self._input_kwargs
        return self._set(**kwargs)

    def _transform(self, dataset):
        loader = self.getImageLoader()
        fn = load_keras_function(
            self.getModelFile(),
            compute_dtype=self.getOrDefault(self.computeDtype),
        )
        params = place_params(fn.params)
        inner = fn._jitted()  # per-instance jit cache -> compile once

        def jitted(x):
            return inner(params, x)[0]

        # the one-fixed-shape loader contract binds across the chunks of
        # a partition
        as_vectors = self.getOutputMode() == "vector"
        return transform_batched(
            dataset, self.getInputCol(), self.getOutputCol(), jitted,
            lambda uris: make_loader_decode_plan(loader),
            to_vectors if as_vectors else to_image_structs,
            self.getOrDefault(self.batchSize),
        )
