"""Layout-alignment experiment: InceptionV3's factorized 1x7/7x1 convs.

An r3 profile found the two worst InceptionV3 ops — the factorized
1x7/7x1 convs at (512,17,17,192) — at 22 TF/s / 32 GB/s and attributed
it to T(8,128) sublane padding at W=17 (~30% waste); three Pallas
kernels at the exact shape lost to XLA (r3, recorded negatives — do not
retry).  r4's Xception result showed the cheap lever for this op class
is LAYOUT PADDING, not custom kernels: K=728→768 lane alignment bought
1.48x with zero kernel work.  This runs the analogous experiments here:

- **spatial pad**: W 17→24 before a 1x7 (H before a 7x1), crop right
  after the conv+BN+relu — 3 exact sublane tiles instead of 2+9/17.
  Zero-padded SAME conv + immediate crop is numerics-preserving (the
  pad region only ever reads zeros), at +41% padded conv FLOPs.
- **channel pad**: C 192→256 = 2x128 lane tiles instead of 128+64.
  Zero-padded weights propagate zeros through conv/BN(beta=0)/relu —
  the same in-model-safe trick as Xception's middle_width — at +78%
  padded FLOPs on the touched convs.

Both are measured ISOLATED (one 1x7+7x1 conv_bn pair, where the effect
is undiluted and achieved-TF/s is the receipt) and IN-MODEL (the full
fused featurize program, what bench.py measures).  Effective TF/s is
always computed on the USEFUL (unpadded) FLOPs so variants compare
apples-to-apples.

Usage (real TPU):  python benchmarks/inception_1x7_experiment.py
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from flax import linen as nn

from sparkdl_tpu.models.layers import avg_pool, global_avg_pool, max_pool
from sparkdl_tpu.utils.benchlib import (
    device_random_stack,
    fill_variables,
    time_compiled,
)

BATCH = 512


# ---------------------------------------------------------------------------
# isolated probe: one factorized 1x7 + 7x1 conv_bn pair at 17x17
# ---------------------------------------------------------------------------
def conv_bn(y, filters, kh, kw, *, name, spatial_pad=False):
    """InceptionV3's conv2d+BN(+relu) unit, optionally with the
    pad-conv-crop spatial trick on the kernel's long axis."""
    orig_h, orig_w = y.shape[1], y.shape[2]
    if spatial_pad:
        if kw > 1:  # 1x7: pad W 17 -> 24 = 3 exact sublane tiles
            y = jnp.pad(y, ((0, 0), (0, 0), (0, 24 - orig_w), (0, 0)))
        if kh > 1:  # 7x1: pad H
            y = jnp.pad(y, ((0, 0), (0, 24 - orig_h), (0, 0), (0, 0)))
    y = nn.Conv(filters, (kh, kw), padding="SAME", use_bias=False,
                dtype=jnp.bfloat16, name=name)(y)
    y = nn.BatchNorm(use_running_average=True, use_scale=False,
                     epsilon=1e-3, dtype=jnp.bfloat16,
                     name=f"{name}_bn")(y)
    y = nn.relu(y)
    if spatial_pad:
        # crop straight back: the padded region never feeds a later conv,
        # so zero-padded SAME semantics are preserved exactly
        y = y[:, :orig_h, :orig_w, :]
    return y


class FactorizedPair(nn.Module):
    channels: int
    spatial_pad: bool = False

    @nn.compact
    def __call__(self, x):
        x = conv_bn(x, self.channels, 1, 7, name="c1x7",
                    spatial_pad=self.spatial_pad)
        x = conv_bn(x, self.channels, 7, 1, name="c7x1",
                    spatial_pad=self.spatial_pad)
        return x


def isolated(channels: int, spatial_pad: bool, scan=24, useful_c=192):
    module = FactorizedPair(channels, spatial_pad)
    x0 = jnp.zeros((1, 17, 17, channels), jnp.bfloat16)
    variables = jax.device_put(
        fill_variables(module, x0), jax.devices()[0]
    )
    stack = device_random_stack(
        (BATCH, 17, 17, channels), jnp.bfloat16, scan
    )

    def run_many(v, stack):
        def body(carry, xb):
            return carry + module.apply(v, xb).astype(jnp.float32).sum(), None

        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), stack)
        return acc

    compiled = jax.jit(run_many).lower(variables, stack).compile()
    t = time_compiled(compiled, (variables, stack))
    ms = t / scan * 1e3
    # useful work: two convs at the ORIGINAL shape (B,17,17,192)x(7,192)
    useful_flops = 2 * 2 * BATCH * 17 * 17 * useful_c * useful_c * 7
    return ms, useful_flops / (t / scan) / 1e12


# ---------------------------------------------------------------------------
# in-model probe: full InceptionV3 featurize with the variant knobs
# ---------------------------------------------------------------------------
class InceptionV3Variant(nn.Module):
    """InceptionV3 with the two 1x7-alignment knobs under test.

    ``pad_c192``: intermediate widths of the c=192 factorized towers
    (mixed7 + mixed8's b7x3) run at 256 channels (final 192-channel
    outputs unchanged — zero-padded weights keep numerics, as the
    production Xception ``middle_width=768``).
    ``spatial_pad``: every 1x7/7x1 runs pad-conv-crop on its long axis.
    """

    pad_c192: bool = False
    spatial_pad: bool = False

    @nn.compact
    def __call__(self, x):
        counter = [0]

        def cb(y, filters, kh, kw, strides=(1, 1), padding="SAME"):
            i = counter[0]
            counter[0] += 1
            sp = self.spatial_pad and (kh, kw) in ((1, 7), (7, 1))
            orig_h, orig_w = y.shape[1], y.shape[2]
            if sp and kw == 7:
                y = jnp.pad(y, ((0, 0), (0, 0), (0, 24 - orig_w), (0, 0)))
            if sp and kh == 7:
                y = jnp.pad(y, ((0, 0), (0, 24 - orig_h), (0, 0), (0, 0)))
            y = nn.Conv(filters, (kh, kw), strides=strides, padding=padding,
                        use_bias=False, dtype=jnp.bfloat16,
                        name=f"conv2d_{i}")(y)
            y = nn.BatchNorm(use_running_average=True, use_scale=False,
                             epsilon=1e-3, dtype=jnp.bfloat16,
                             name=f"bn_{i}")(y)
            y = nn.relu(y)
            if sp:
                y = y[:, :orig_h, :orig_w, :]
            return y

        def c_pad(c):
            return 256 if (self.pad_c192 and c == 192) else c

        x = cb(x, 32, 3, 3, strides=(2, 2), padding="VALID")
        x = cb(x, 32, 3, 3, padding="VALID")
        x = cb(x, 64, 3, 3)
        x = max_pool(x, 3, 2)
        x = cb(x, 80, 1, 1, padding="VALID")
        x = cb(x, 192, 3, 3, padding="VALID")
        x = max_pool(x, 3, 2)
        for pool_features in (32, 64, 64):
            b1 = cb(x, 64, 1, 1)
            b5 = cb(x, 48, 1, 1)
            b5 = cb(b5, 64, 5, 5)
            b3d = cb(x, 64, 1, 1)
            b3d = cb(b3d, 96, 3, 3)
            b3d = cb(b3d, 96, 3, 3)
            bp = avg_pool(x, 3, 1, "SAME")
            bp = cb(bp, pool_features, 1, 1)
            x = jnp.concatenate([b1, b5, b3d, bp], axis=-1)
        b3 = cb(x, 384, 3, 3, strides=(2, 2), padding="VALID")
        b3d = cb(x, 64, 1, 1)
        b3d = cb(b3d, 96, 3, 3)
        b3d = cb(b3d, 96, 3, 3, strides=(2, 2), padding="VALID")
        bp = max_pool(x, 3, 2)
        x = jnp.concatenate([b3, b3d, bp], axis=-1)
        for c in (128, 160, 160, 192):
            ci = c_pad(c)
            b1 = cb(x, 192, 1, 1)
            b7 = cb(x, ci, 1, 1)
            b7 = cb(b7, ci, 1, 7)
            b7 = cb(b7, 192, 7, 1)
            b7d = cb(x, ci, 1, 1)
            b7d = cb(b7d, ci, 7, 1)
            b7d = cb(b7d, ci, 1, 7)
            b7d = cb(b7d, ci, 7, 1)
            b7d = cb(b7d, 192, 1, 7)
            bp = avg_pool(x, 3, 1, "SAME")
            bp = cb(bp, 192, 1, 1)
            x = jnp.concatenate([b1, b7, b7d, bp], axis=-1)
        b3 = cb(x, 192, 1, 1)
        b3 = cb(b3, 320, 3, 3, strides=(2, 2), padding="VALID")
        ci = c_pad(192)
        b7x3 = cb(x, ci, 1, 1)
        b7x3 = cb(b7x3, ci, 1, 7)
        b7x3 = cb(b7x3, ci, 7, 1)
        b7x3 = cb(b7x3, 192, 3, 3, strides=(2, 2), padding="VALID")
        bp = max_pool(x, 3, 2)
        x = jnp.concatenate([b3, b7x3, bp], axis=-1)
        for _ in range(2):
            b1 = cb(x, 320, 1, 1)
            b3 = cb(x, 384, 1, 1)
            b3 = jnp.concatenate(
                [cb(b3, 384, 1, 3), cb(b3, 384, 3, 1)], axis=-1
            )
            b3d = cb(x, 448, 1, 1)
            b3d = cb(b3d, 384, 3, 3)
            b3d = jnp.concatenate(
                [cb(b3d, 384, 1, 3), cb(b3d, 384, 3, 1)], axis=-1
            )
            bp = avg_pool(x, 3, 1, "SAME")
            bp = cb(bp, 192, 1, 1)
            x = jnp.concatenate([b1, b3, b3d, bp], axis=-1)
        return global_avg_pool(x)


def full_model(pad_c192: bool, spatial_pad: bool, scan=8):
    module = InceptionV3Variant(pad_c192=pad_c192, spatial_pad=spatial_pad)
    variables = jax.device_put(
        fill_variables(module, jnp.zeros((1, 299, 299, 3), jnp.float32)),
        jax.devices()[0],
    )
    stack = device_random_stack(
        (BATCH, 299, 299, 3), jnp.uint8, scan, as_uint8=True
    )

    def forward(v, x):
        x = x.astype(jnp.bfloat16) / 127.5 - 1.0
        return module.apply(v, x.astype(jnp.bfloat16)).astype(jnp.float32)

    def run_many(v, stack):
        def body(carry, xb):
            return carry + forward(v, xb).sum(), None

        acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), stack)
        return acc

    compiled = jax.jit(run_many).lower(variables, stack).compile()
    t = time_compiled(compiled, (variables, stack))
    return scan * BATCH / t


def check_spatial_pad_numerics():
    """Pad-conv-crop must be bit-for-bit-close to the plain pair."""
    x = jnp.asarray(
        np.random.RandomState(0).rand(4, 17, 17, 192), jnp.float32
    )
    base = FactorizedPair(192, spatial_pad=False)
    padded = FactorizedPair(192, spatial_pad=True)
    v = base.init(jax.random.PRNGKey(1), x)
    a = base.apply(v, x)
    b = padded.apply(v, x)
    err = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32))))
    assert err < 1e-5, f"spatial pad changed numerics: {err}"
    return err


ISOLATED_VARIANTS = {
    "base": (192, False),   # W=17 C=192
    "wpad": (192, True),    # W/H padded to 24
    "cpad": (256, False),   # C padded to 256
    "both": (256, True),
}
FULL_VARIANTS = {
    "base": (False, False),
    "spatial-pad": (False, True),
    "c192-256": (True, False),
    "both": (True, True),
}


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--stage", choices=("check", "isolated", "full"),
                    required=True)
    ap.add_argument("--variant", default=None,
                    help="one variant name; default = all in the stage")
    args = ap.parse_args(argv)
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    if args.stage == "check":
        err = check_spatial_pad_numerics()
        print(f"spatial pad-conv-crop numerics: max|delta| = {err:.2e}")
        return
    if args.stage == "isolated":
        names = [args.variant] if args.variant else list(ISOLATED_VARIANTS)
        for name in names:
            channels, sp = ISOLATED_VARIANTS[name]
            ms, tf_s = isolated(channels, sp)
            print(
                f"isolated {name} (C={channels} spatial_pad={sp}): "
                f"{ms:6.2f} ms/batch  {tf_s:6.1f} TF/s effective",
                flush=True,
            )
        return
    names = [args.variant] if args.variant else list(FULL_VARIANTS)
    for name in names:
        pc, sp = FULL_VARIANTS[name]
        ips = full_model(pc, sp)
        print(f"full {name}: {ips:7.0f} img/s", flush=True)


if __name__ == "__main__":
    main()
