"""Model transformers — Spark-ML-pipeline-stage analogs over the TPU engine.

Reference analog: ``python/sparkdl/transformers/``† (SURVEY.md §2):
``TFImageTransformer`` → :class:`~sparkdl_tpu.transformers.tf_image.TFImageTransformer`,
``DeepImageFeaturizer``/``DeepImagePredictor`` → ``named_image``,
``KerasImageFileTransformer`` → ``keras_image``, ``TFTransformer`` →
``tf_tensor``, ``KerasTransformer`` → ``keras_tensor``.  Without a reference
analog: ``block_diffusion`` (:class:`BlockDiffusionTransformer`, fixed-length
generation by diffusion over blocks from a column of prompts) and
``ar_generate`` (:class:`AutoregressiveTransformer`, greedy generation one
token a row a step over a device-resident state pytree); ``generation`` holds
what the two share.
"""
