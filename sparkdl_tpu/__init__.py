"""sparkdl_tpu — TPU-native Deep Learning Pipelines.

A brand-new, TPU-first framework with the capabilities of Databricks' Deep
Learning Pipelines (``sparkdl``; reference mirror
``codealphago/spark-deep-learning`` — see SURVEY.md): pretrained-CNN
featurization/prediction over image dataframes, arbitrary-model batch
inference, SQL-UDF model serving, and distributed fine-tuning with
hyperparameter search — rebuilt on JAX/XLA/PJRT with jit-compiled Flax models,
``jax.sharding`` data/model parallelism over TPU ICI, Pallas kernels for hot
host↔device preprocessing, and orbax checkpointing.

Public API (reference analog: ``python/sparkdl/__init__.py``† ``__all__``).
Exports resolve lazily (PEP 562) so importing the package stays cheap and
partial installs remain usable.
"""

import importlib
import os

# Keras (used only for model ingestion) must run on its JAX backend so
# imported models jit straight onto TPU. Must be set before keras is imported
# anywhere in the process.
os.environ.setdefault("KERAS_BACKEND", "jax")

VERSION = __version__ = "0.1.0"

_EXPORTS = {
    "XlaFunction": "sparkdl_tpu.graph.function",
    "imageSchema": "sparkdl_tpu.image.imageIO",
    "imageType": "sparkdl_tpu.image.imageIO",
    "readImages": "sparkdl_tpu.image.imageIO",
    "TPUImageTransformer": "sparkdl_tpu.transformers.tf_image",
    "TFImageTransformer": "sparkdl_tpu.transformers.tf_image",
    "DeepImagePredictor": "sparkdl_tpu.transformers.named_image",
    "DeepImageFeaturizer": "sparkdl_tpu.transformers.named_image",
    "NativeDeepImageFeaturizer": "sparkdl_tpu.transformers.native_image",
    "KerasImageFileTransformer": "sparkdl_tpu.transformers.keras_image",
    "TPUTransformer": "sparkdl_tpu.transformers.tf_tensor",
    "TFTransformer": "sparkdl_tpu.transformers.tf_tensor",
    "KerasTransformer": "sparkdl_tpu.transformers.keras_tensor",
    "BlockDiffusionTransformer": "sparkdl_tpu.transformers.block_diffusion",
    "AutoregressiveTransformer": "sparkdl_tpu.transformers.ar_generate",
    "KerasImageFileEstimator": "sparkdl_tpu.estimators.keras_image_file_estimator",
    "registerKerasImageUDF": "sparkdl_tpu.udf.keras_image_model",
    "makeGraphUDF": "sparkdl_tpu.graph.tensorframes_udf",
    "TPUSession": "sparkdl_tpu.sql.session",
    "Batch": "sparkdl_tpu.data",
    "Dataset": "sparkdl_tpu.data",
    "ImageDecodeError": "sparkdl_tpu.image.imageIO",
    "ModelServer": "sparkdl_tpu.serving",
    "ServingConfig": "sparkdl_tpu.serving",
    "ServerOverloaded": "sparkdl_tpu.serving",
    "RetryPolicy": "sparkdl_tpu.resilience",
    "Deadline": "sparkdl_tpu.resilience",
    "CircuitBreaker": "sparkdl_tpu.resilience",
    "TransientError": "sparkdl_tpu.resilience",
    "PermanentError": "sparkdl_tpu.resilience",
    "DeviceUnresponsive": "sparkdl_tpu.resilience",
    "Preempted": "sparkdl_tpu.resilience",
    "FaultPlan": "sparkdl_tpu.resilience",
    "StreamRunner": "sparkdl_tpu.streaming",
    "StreamConfig": "sparkdl_tpu.streaming",
    "StreamSource": "sparkdl_tpu.streaming",
    "QueueSource": "sparkdl_tpu.streaming",
    "FileTailSource": "sparkdl_tpu.streaming",
    "WatermarkTracker": "sparkdl_tpu.streaming",
    "CommitLog": "sparkdl_tpu.streaming",
    "JsonlSink": "sparkdl_tpu.streaming",
    "CallbackSink": "sparkdl_tpu.streaming",
    "Span": "sparkdl_tpu.obs",
    "Tracer": "sparkdl_tpu.obs",
    "tracer": "sparkdl_tpu.obs",
    "JsonlTraceSink": "sparkdl_tpu.obs",
    "prometheus_text": "sparkdl_tpu.obs",
    "TimeSeriesRecorder": "sparkdl_tpu.obs",
    "SLO": "sparkdl_tpu.obs",
    "SLOEngine": "sparkdl_tpu.obs",
    "ObsServer": "sparkdl_tpu.obs",
    "FlightRecorder": "sparkdl_tpu.obs",
    "serving_slos": "sparkdl_tpu.obs",
    "streaming_slos": "sparkdl_tpu.obs",
    "availability_slo": "sparkdl_tpu.obs",
}

__all__ = ["VERSION", *sorted(_EXPORTS)]

# Zero-code trace capture (mirrors SPARKDL_FAULT_PLAN / profiler's
# SPARKDL_PROFILE_DIR): SPARKDL_TRACE_OUT=<path.jsonl> enables the
# tracer with a bounded JSONL sink flushed (append) at interpreter
# exit, so subprocess workers capture into the same file with no code
# changes; SPARKDL_TRACE_SAMPLE arms tail-aware sampling for it.
# No env var -> no obs import -> zero cost.
if os.environ.get("SPARKDL_TRACE_OUT") or os.environ.get(
    "SPARKDL_TRACE_SAMPLE"
):
    from sparkdl_tpu.obs import enable_from_env as _obs_enable_from_env

    _obs_enable_from_env()

# Zero-code flight recorder: SPARKDL_BLACKBOX_DIR=<dir> arms the crash
# flight recorder (periodic atomic persist + crash/stall hooks), so any
# worker subprocess leaves a post-mortem dump even on SIGKILL.
if os.environ.get("SPARKDL_BLACKBOX_DIR"):
    from sparkdl_tpu.obs.blackbox import (
        enable_from_env as _blackbox_enable_from_env,
    )

    _blackbox_enable_from_env()

# Zero-code introspection server: SPARKDL_OBS_PORT=<port> serves
# /metrics, /healthz, /slo, /debug/* on localhost (0 = ephemeral).
if os.environ.get("SPARKDL_OBS_PORT"):
    from sparkdl_tpu.obs.server import (
        enable_from_env as _obs_server_enable_from_env,
    )

    _obs_server_enable_from_env()


def __getattr__(name):
    if name in _EXPORTS:
        module = importlib.import_module(_EXPORTS[name])
        value = getattr(module, name)
        globals()[name] = value
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(_EXPORTS))
