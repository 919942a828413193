"""Xception in Flax.

Parity target: ``keras.applications.xception`` — explicit names for the
separable-conv blocks (``blockN_sepconvM``) and Keras auto-names for the four
1x1 residual projections (``conv2d``..``conv2d_3`` + matching
``batch_normalization*``), normalized per ``keras_port``.  Featurization cut
point: global-average-pool output, 2048 features.  Input 299x299x3, "tf"
preprocessing.
"""

from __future__ import annotations

from typing import Any, Optional

import jax.numpy as jnp
from flax import linen as nn

from sparkdl_tpu.models.layers import SeparableConv, global_avg_pool, max_pool


class Xception(nn.Module):
    """``middle_width`` widens the 728-channel middle-flow trunk (e.g. to
    768 = 6x128 for MXU lane alignment — the r3 open-headroom
    experiment).  At the default 728 the module is exactly the Keras
    architecture; widened variants hold the Keras weights zero-padded
    (zero channels propagate as zeros through depthwise/pointwise/BN/relu
    and the residual adds, so numerics are unchanged)."""

    num_classes: int = 1000
    include_top: bool = True
    dtype: Optional[Any] = None
    middle_width: int = 728

    @nn.compact
    def __call__(self, x, train: bool = False, features_only: bool = False):
        def bn(y, name):
            return nn.BatchNorm(
                use_running_average=not train,
                epsilon=1e-3,
                dtype=self.dtype,
                name=name,
            )(y)

        def sep(y, filters, name):
            y = SeparableConv(filters, (3, 3), dtype=self.dtype, name=name)(y)
            return bn(y, f"{name}_bn")

        # ---- entry flow: stem ----
        x = nn.Conv(32, (3, 3), strides=(2, 2), padding="VALID", use_bias=False,
                    dtype=self.dtype, name="block1_conv1")(x)
        x = nn.relu(bn(x, "block1_conv1_bn"))
        x = nn.Conv(64, (3, 3), padding="VALID", use_bias=False,
                    dtype=self.dtype, name="block1_conv2")(x)
        x = nn.relu(bn(x, "block1_conv2_bn"))

        # ---- entry flow: 3 downsampling residual blocks ----
        width = self.middle_width
        for i, (filters, block) in enumerate(
            ((128, 2), (256, 3), (width, 4))
        ):
            res_conv = "conv2d" if i == 0 else f"conv2d_{i}"
            res_bn = ("batch_normalization" if i == 0
                      else f"batch_normalization_{i}")
            residual = nn.Conv(filters, (1, 1), strides=(2, 2), padding="SAME",
                               use_bias=False, dtype=self.dtype,
                               name=res_conv)(x)
            residual = bn(residual, res_bn)
            if block > 2:
                x = nn.relu(x)
            x = sep(x, filters, f"block{block}_sepconv1")
            x = nn.relu(x)
            x = sep(x, filters, f"block{block}_sepconv2")
            x = max_pool(x, 3, 2, "SAME")
            x = x + residual

        # ---- middle flow: 8 residual blocks of 3 sepconvs ----
        for block in range(5, 13):
            residual = x
            for j in (1, 2, 3):
                x = nn.relu(x)
                x = sep(x, width, f"block{block}_sepconv{j}")
            x = x + residual

        # ---- exit flow ----
        residual = nn.Conv(1024, (1, 1), strides=(2, 2), padding="SAME",
                           use_bias=False, dtype=self.dtype, name="conv2d_3")(x)
        residual = bn(residual, "batch_normalization_3")
        x = nn.relu(x)
        x = sep(x, width, "block13_sepconv1")
        x = nn.relu(x)
        x = sep(x, 1024, "block13_sepconv2")
        x = max_pool(x, 3, 2, "SAME")
        x = x + residual

        x = sep(x, 1536, "block14_sepconv1")
        x = nn.relu(x)
        x = sep(x, 2048, "block14_sepconv2")
        x = nn.relu(x)

        x = global_avg_pool(x)
        if features_only or not self.include_top:
            return x
        return nn.Dense(self.num_classes, dtype=self.dtype, name="predictions")(x)
