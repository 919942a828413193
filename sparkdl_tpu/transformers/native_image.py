"""NativeDeepImageFeaturizer — the second-stack featurizer as a pipeline
stage.

The reference shipped TWO featurizer stacks: the Python
``DeepImageFeaturizer`` and a JVM-native Scala one that resized rows with
``ImageUtils`` (awt) and ran a pre-frozen GraphDef through TensorFrames
``mapRows`` (`src/main/scala/com/databricks/sparkdl/DeepImageFeaturizer.scala`†,
SURVEY.md §3.5).  This stage is the Scala stack's analog: image structs are
decoded/resized by the native C++ columnar bridge (``native/batchpack.cpp``,
the ImageUtils analog) and the frozen model — an exported StableHLO program
directory — executes through the C++ PJRT runner (``native/pjrt_runner.cpp``,
the TensorFrames/JNI analog).  Python only orchestrates partitions; decode,
packing, and model execution are native.

Numerics match the Python stack's fused forward by construction (the
exported program IS that forward — ``native/featurizer.export_featurizer``),
modulo uint8 rounding when a resize is needed (the Scala stack's awt resize
was also uint8).
"""

from __future__ import annotations

import logging
import os
import tempfile
from typing import Any, Optional

import numpy as np

from sparkdl_tpu.ml.base import Transformer
from sparkdl_tpu.ml.linalg import DenseVector
from sparkdl_tpu.param.base import Param, keyword_only
from sparkdl_tpu.param.shared import HasInputCol, HasOutputCol
from sparkdl_tpu.transformers.utils import decode_image_batch

logger = logging.getLogger(__name__)


class _ClosingLRU:
    """Tiny LRU that closes evicted values — each cached NativeProgram
    holds a PJRT client plus full model params in HBM, so eviction must
    release them, not just drop the Python reference."""

    def __init__(self, maxsize: int):
        from collections import OrderedDict

        self.maxsize = maxsize
        self._data = OrderedDict()

    def get(self, key):
        if key in self._data:
            self._data.move_to_end(key)
            return self._data[key]
        return None

    def put(self, key, value):
        self._data[key] = value
        self._data.move_to_end(key)
        while len(self._data) > self.maxsize:
            _, evicted = self._data.popitem(last=False)
            try:
                evicted.close()
            except Exception:  # release best-effort; never fail a transform
                logger.warning("failed to close evicted native program",
                               exc_info=True)


# One live NativeProgram (compiled executable + resident params) per
# (model, weights-key, batch).
_PROGRAM_CACHE = _ClosingLRU(2)


def _program_cache_dir() -> str:
    root = os.environ.get(
        "SPARKDL_NATIVE_PROGRAM_CACHE",
        os.path.join(tempfile.gettempdir(), "sparkdl_native_programs"),
    )
    os.makedirs(root, exist_ok=True)
    return root


class NativeDeepImageFeaturizer(Transformer, HasInputCol, HasOutputCol):
    """Penultimate-layer CNN features via the native (C++ PJRT) stack.

    Same output contract as :class:`DeepImageFeaturizer`; requires the
    native runner (``sparkdl_tpu.native.pjrt.is_available()``) and a PJRT
    plugin (``SPARKDL_PJRT_PLUGIN``).
    """

    modelName = Param("undefined", "modelName", "named CNN to featurize with")
    modelWeights = Param(
        "undefined", "modelWeights",
        "'imagenet' (default), 'random', or a weights path — as in "
        "DeepImageFeaturizer",
    )
    batchSize = Param(
        "undefined", "batchSize",
        "fixed device batch (the exported program's static shape)",
    )
    programDir = Param(
        "undefined", "programDir",
        "optional pre-exported program directory (skips export)",
    )

    @keyword_only
    def __init__(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        modelName: Optional[str] = None,
        modelWeights: Any = None,
        batchSize: int = 32,
        programDir: Optional[str] = None,
    ):
        super().__init__()
        self._setDefault(modelWeights=None, batchSize=32, programDir=None)
        kwargs = self._input_kwargs
        self.setParams(**kwargs)

    @keyword_only
    def setParams(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        modelName: Optional[str] = None,
        modelWeights: Any = None,
        batchSize: int = 32,
        programDir: Optional[str] = None,
    ):
        kwargs = self._input_kwargs
        return self._set(**kwargs)

    # ------------------------------------------------------------------
    def _program(self):
        from sparkdl_tpu.models import get_keras_application_model
        from sparkdl_tpu.native import pjrt
        from sparkdl_tpu.native.featurizer import export_featurizer

        if not pjrt.is_available():
            raise RuntimeError(
                "NativeDeepImageFeaturizer needs the native PJRT runner "
                "(pjrt_c_api.h + g++); use DeepImageFeaturizer instead"
            )
        model_name = self.getOrDefault(self.modelName)
        weights = self.getOrDefault(self.modelWeights) or "imagenet"
        batch = int(self.getOrDefault(self.batchSize))
        get_keras_application_model(model_name)  # validate the name early

        explicit = self.getOrDefault(self.programDir)
        if explicit:
            key = (os.path.abspath(explicit),)
            prog = _PROGRAM_CACHE.get(key)
            if prog is None:
                prog = pjrt.NativeProgram(explicit)
                _PROGRAM_CACHE.put(key, prog)
            return prog

        if not isinstance(weights, str):
            raise ValueError(
                "NativeDeepImageFeaturizer supports string modelWeights "
                "('imagenet', 'random', or a weights-file path) — exported "
                "programs are cached on disk by that key; pass in-memory "
                "weights to DeepImageFeaturizer, or pre-export with "
                "native.featurizer.export_featurizer and set programDir"
            )
        # key the on-disk cache by content identity: a weights *file*
        # contributes its mtime+size so retraining in place re-exports
        import hashlib

        parts = [model_name, f"b{batch}", weights]
        if os.path.exists(weights):
            st = os.stat(weights)
            parts.append(f"{st.st_mtime_ns}:{st.st_size}")
        digest = hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]
        key = (model_name, weights, batch, digest)
        prog = _PROGRAM_CACHE.get(key)
        if prog is not None:
            return prog
        d = os.path.join(
            _program_cache_dir(), f"{model_name}_b{batch}_{digest}"
        )
        if not os.path.exists(os.path.join(d, "manifest.json")):
            logger.info("exporting native featurizer program to %s", d)
            export_featurizer(
                model_name, batch_size=batch, out_dir=d,
                model_weights=weights,
            )
        prog = pjrt.NativeProgram(d)
        _PROGRAM_CACHE.put(key, prog)
        return prog

    def _transform(self, dataset):
        input_col = self.getInputCol()
        output_col = self.getOutputCol()
        prog = self._program()
        # the program's static input shape is the truth (an explicit
        # programDir may have been exported with any batch/source size)
        batch, height, width, _ = prog.manifest["inputs"][0]["shape"]

        def process_partition(part):
            rows = part[input_col]
            out = dict(part)
            if not rows:
                out[output_col] = []
                return out
            from sparkdl_tpu.utils.metrics import metrics

            # 'sparkdl.serve' covers decode through fetch so the sustained
            # images_per_sec means the same thing here as in the flax
            # serving paths (end-to-end, load included); 'sparkdl.forward'
            # is the dispatch+fetch subset — see metrics.py
            with metrics.timer("sparkdl.serve").time():
                # native decode + resize to the program's fixed source
                # size; rounded back to uint8 (awt-resize parity — the
                # program ingests u8)
                x = decode_image_batch(
                    rows, 3, (height, width), to_rgb=False,
                    always_resize=True, prefer_uint8=True,
                )
                if x.dtype != np.uint8:
                    x = np.clip(np.rint(x), 0, 255).astype(np.uint8)
                # Not run_batched: that engine stages chunks onto the
                # *jax* device, which here would round-trip every batch
                # through the jax client before the native client ships
                # it again.  Same chunk/pad/slice policy and the same
                # metrics counters though; batches stream double-buffered
                # (NativeProgram.stream: batch i+1's transfer+execute
                # enqueue before batch i's fetch).
                n = x.shape[0]

                def chunks():
                    for lo in range(0, n, batch):
                        chunk = x[lo:lo + batch]
                        if chunk.shape[0] < batch:  # pad the ragged tail
                            chunk = np.concatenate(
                                [chunk,
                                 np.repeat(chunk[-1:],
                                           batch - chunk.shape[0], axis=0)]
                            )
                        yield chunk

                feats = []
                with metrics.timer("sparkdl.forward").time():
                    for i, outs in enumerate(prog.stream(chunks())):
                        k = min(batch, n - i * batch)
                        feats.append(np.asarray(outs[0])[:k])
            metrics.counter("sparkdl.rows_processed").add(n)
            flat = np.concatenate(feats).astype(np.float64)
            out[output_col] = [DenseVector(v) for v in flat]
            return out

        return dataset.mapPartitions(process_partition)
