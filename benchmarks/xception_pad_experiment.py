"""Lane-alignment experiment: Xception middle flow at 728 vs 768 channels.

An r3 profile left ONE open compute headroom: the middle flow's K=728
1x1-conv fusions run at 59 TF/s = 42% of the chip's conv-demonstrated
~139 TF/s, and 728 = 5.69 x 128 is not MXU-lane-aligned.  This measures
whether zero-padding the trunk to 768 = 6 x 128 (+5.6% FLOPs, numerics
unchanged — zero channels propagate as zeros) unlocks the conv emitter's
tiling.

Two reads per width, both with the scan-amortized methodology (per-call
host timing of an asynchronous dispatch is wrong in both directions):

- the full fused featurize program (what bench.py measures), and
- a middle-flow-only program (8 residual blocks at 19x19xW), where the
  effect is undiluted and the achieved TF/s is the direct receipt.

Usage (real TPU):  python benchmarks/xception_pad_experiment.py

Note: ``full_model`` here keeps the BGR flip in-program (production folds
it into the stem for 'tf'-mode models), so its absolute img/s sits ~2-3%
under the production ``bench_zoo`` figure; the W=728 vs W=768 *delta* is
what this script is for — the authoritative production number is
``bench_zoo.py Xception``.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from sparkdl_tpu.models.xception import Xception
from sparkdl_tpu.utils.benchlib import measure_featurizer  # noqa: F401  (methodology ref)
from sparkdl_tpu.utils.metrics import compiled_flops


def time_compiled(compiled, args, repeats=3):
    np.asarray(compiled(*args))  # warm
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        np.asarray(compiled(*args))
        times.append(time.perf_counter() - t0)
    return min(times)


def full_model(width: int, batch=512, scan=4):
    module = Xception(dtype=jnp.bfloat16, middle_width=width)
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 299, 299, 3), jnp.float32),
    )
    variables = jax.tree_util.tree_map(
        lambda l: jnp.full(l.shape, 0.01, l.dtype), shapes
    )
    device = jax.devices()[0]
    variables = jax.device_put(variables, device)
    rng = np.random.RandomState(0)
    stack = jax.device_put(
        jnp.asarray((rng.rand(scan, batch, 299, 299, 3) * 255)
                    .astype(np.uint8)),
        device,
    )

    def forward(v, x):
        x = x[..., ::-1].astype(jnp.bfloat16)
        x = x / 127.5 - 1.0  # "tf" preprocessing
        return module.apply(
            v, x.astype(jnp.bfloat16), features_only=True
        ).astype(jnp.float32)

    def run_many(v, stack):
        def body(carry, xb):
            return carry + forward(v, xb).sum(), None

        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), stack)
        return acc

    compiled = jax.jit(run_many).lower(variables, stack).compile()
    t = time_compiled(compiled, (variables, stack))
    return scan * batch / t


def middle_flow_only(width: int, batch=512, scan=8):
    """The 8 middle-flow residual blocks in isolation at 19x19xW."""
    from flax import linen as nn

    from sparkdl_tpu.models.layers import SeparableConv

    class Middle(nn.Module):
        width: int

        @nn.compact
        def __call__(self, x):
            def sep(y, name):
                y = SeparableConv(self.width, (3, 3), dtype=jnp.bfloat16,
                                  name=name)(y)
                return nn.BatchNorm(use_running_average=True, epsilon=1e-3,
                                    dtype=jnp.bfloat16,
                                    name=f"{name}_bn")(y)

            for block in range(5, 13):
                residual = x
                for j in (1, 2, 3):
                    x = nn.relu(x)
                    x = sep(x, f"block{block}_sepconv{j}")
                x = x + residual
            return x

    module = Middle(width)
    x0 = jnp.zeros((1, 19, 19, width), jnp.bfloat16)
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), x0)
    variables = jax.tree_util.tree_map(
        lambda l: jnp.full(l.shape, 0.01, l.dtype), shapes
    )
    device = jax.devices()[0]
    variables = jax.device_put(variables, device)
    rng = np.random.RandomState(0)
    stack = jax.device_put(
        jnp.asarray(rng.rand(scan, batch, 19, 19, width).astype(np.float32)
                    .astype(jnp.bfloat16)),
        device,
    )

    def run_many(v, stack):
        def body(carry, xb):
            return carry + module.apply(v, xb).astype(jnp.float32).sum(), None

        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), stack)
        return acc

    compiled = jax.jit(run_many).lower(variables, stack).compile()
    t = time_compiled(compiled, (variables, stack))
    flops = compiled_flops(compiled)
    # cost analysis may count the scan body once; scale by measured probe
    from sparkdl_tpu.utils.benchlib import scan_body_counted_once

    if flops and scan_body_counted_once():
        flops *= scan
    tf_s = (flops / t / 1e12) if flops else float("nan")
    ms_per_batch = t / scan * 1e3
    return ms_per_batch, tf_s


def main():
    print(f"device: {jax.devices()[0].device_kind}")
    for width in (728, 768):
        ms, tf_s = middle_flow_only(width)
        print(
            f"middle flow W={width}: {ms:.2f} ms/batch(512) "
            f"{tf_s:.1f} TF/s (analytic FLOPs incl. +{(width/728)**2-1:.1%}"
            " pad work)" if width != 728 else
            f"middle flow W={width}: {ms:.2f} ms/batch(512) {tf_s:.1f} TF/s"
        )
    for width in (728, 768):
        ips = full_model(width)
        print(f"full Xception W={width}: {ips:.0f} img/s")


if __name__ == "__main__":
    main()
