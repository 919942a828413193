"""InceptionV3 written out once, plainly (Szegedy et al., arXiv:1512.00567;
layer list as ``keras.applications.InceptionV3``: 94 conv + batch-norm (no
scale, eps 1e-3) + relu units, global average pool, 2048 features).

The architecture is one function over a small set of operations, and runs on
two backends: :class:`Shapes` follows (h, w, c) tuples and records every
conv's shape — which gives the weights' shapes, the FLOPs and the bytes —
and :class:`Arrays` computes it in jax.numpy.  Nothing here imports the
program; the weights are made here from a seed.
"""

from __future__ import annotations

import math

EPS = 1e-3
INPUT_HW = (299, 299)
FEATURES = 2048


def _out(size: int, k: int, stride: int, padding: str) -> int:
    if padding == "SAME":
        return -(-size // stride)
    return (size - k) // stride + 1


class Shapes:
    """Follows shapes; ``convs`` lists (kh, kw, cin, cout, out_h, out_w)."""

    def __init__(self):
        self.convs = []
        self.pool_elems = 0

    def conv_bn(self, x, filters, kh, kw, stride=1, padding="SAME"):
        h, w, c = x
        oh, ow = _out(h, kh, stride, padding), _out(w, kw, stride, padding)
        self.convs.append((kh, kw, c, filters, oh, ow))
        return (oh, ow, filters)

    def max_pool(self, x, k=3, stride=2):
        h, w, c = x
        out = (_out(h, k, stride, "VALID"), _out(w, k, stride, "VALID"), c)
        self.pool_elems += out[0] * out[1] * c
        return out

    def avg_pool_same(self, x):
        self.pool_elems += x[0] * x[1] * x[2]
        return x

    def concat(self, xs):
        return (xs[0][0], xs[0][1], sum(x[2] for x in xs))

    def global_avg_pool(self, x):
        return (x[2],)


class Arrays:
    """Computes in jax.numpy at float32.  ``params`` is the list of
    ``(kernel HWIO, bias, mean, var)`` per conv unit, in call order.
    ``operand`` optionally rounds every conv's two operands (the control:
    a lower precision put in the program's place)."""

    def __init__(self, params, operand=None):
        self.params = params
        self.operand = operand
        self.i = 0

    def conv_bn(self, x, filters, kh, kw, stride=1, padding="SAME"):
        import jax.numpy as jnp
        from jax import lax

        kernel, bias, mean, var = self.params[self.i]
        self.i += 1
        if kernel.shape != (kh, kw, x.shape[-1], filters):
            raise ValueError(f"conv {self.i - 1}: kernel {kernel.shape}")
        if self.operand is not None:
            x, kernel = self.operand(x), self.operand(kernel)
        y = lax.conv_general_dilated(
            x, kernel, (stride, stride), padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST,
        )
        y = (y - mean) / jnp.sqrt(var + EPS) + bias
        return jnp.maximum(y, 0.0)

    def max_pool(self, x, k=3, stride=2):
        import jax.numpy as jnp
        from jax import lax

        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, k, k, 1), (1, stride, stride, 1), "VALID"
        )

    def avg_pool_same(self, x):
        """3x3, stride 1, SAME, dividing by the cells that are not padding
        (TensorFlow's and Keras's average pooling)."""
        import jax.numpy as jnp
        from jax import lax

        def window_sum(v):
            return lax.reduce_window(
                v, 0.0, lax.add, (1, 3, 3, 1), (1, 1, 1, 1), "SAME"
            )

        ones = jnp.ones((1, x.shape[1], x.shape[2], 1), x.dtype)
        return window_sum(x) / window_sum(ones)

    def concat(self, xs):
        import jax.numpy as jnp

        return jnp.concatenate(xs, axis=-1)

    def global_avg_pool(self, x):
        return x.mean(axis=(1, 2))


def network(ops, x):
    """The layer list.  ``x``: NHWC in [-1, 1] for :class:`Arrays`, an
    (h, w, c) tuple for :class:`Shapes`."""
    c = ops.conv_bn
    x = c(x, 32, 3, 3, 2, "VALID")
    x = c(x, 32, 3, 3, 1, "VALID")
    x = c(x, 64, 3, 3)
    x = ops.max_pool(x)
    x = c(x, 80, 1, 1, 1, "VALID")
    x = c(x, 192, 3, 3, 1, "VALID")
    x = ops.max_pool(x)
    for pool_features in (32, 64, 64):  # mixed0..2, 35x35
        b1 = c(x, 64, 1, 1)
        b5 = c(c(x, 48, 1, 1), 64, 5, 5)
        b3 = c(c(c(x, 64, 1, 1), 96, 3, 3), 96, 3, 3)
        bp = c(ops.avg_pool_same(x), pool_features, 1, 1)
        x = ops.concat([b1, b5, b3, bp])
    b3 = c(x, 384, 3, 3, 2, "VALID")  # mixed3, to 17x17
    b3d = c(c(c(x, 64, 1, 1), 96, 3, 3), 96, 3, 3, 2, "VALID")
    x = ops.concat([b3, b3d, ops.max_pool(x)])
    for m in (128, 160, 160, 192):  # mixed4..7, factorised 7x7
        b1 = c(x, 192, 1, 1)
        b7 = c(c(c(x, m, 1, 1), m, 1, 7), 192, 7, 1)
        b7d = c(c(c(c(c(x, m, 1, 1), m, 7, 1), m, 1, 7), m, 7, 1), 192, 1, 7)
        bp = c(ops.avg_pool_same(x), 192, 1, 1)
        x = ops.concat([b1, b7, b7d, bp])
    b3 = c(c(x, 192, 1, 1), 320, 3, 3, 2, "VALID")  # mixed8, to 8x8
    b7 = c(c(c(c(x, 192, 1, 1), 192, 1, 7), 192, 7, 1), 192, 3, 3, 2, "VALID")
    x = ops.concat([b3, b7, ops.max_pool(x)])
    for _ in range(2):  # mixed9, mixed10
        b1 = c(x, 320, 1, 1)
        b3 = c(x, 384, 1, 1)
        b3 = ops.concat([c(b3, 384, 1, 3), c(b3, 384, 3, 1)])
        b3d = c(c(x, 448, 1, 1), 384, 3, 3)
        b3d = ops.concat([c(b3d, 384, 1, 3), c(b3d, 384, 3, 1)])
        bp = c(ops.avg_pool_same(x), 192, 1, 1)
        x = ops.concat([b1, b3, b3d, bp])
    return ops.global_avg_pool(x)


def conv_shapes(hw=INPUT_HW):
    shapes = Shapes()
    network(shapes, (hw[0], hw[1], 3))
    return shapes.convs


def make_params(weights_seed: int, hw=INPUT_HW):
    """Every conv unit's ``(kernel, bias, mean, var)`` in one jitted call on
    the default device: He-normal kernels, so that the signal neither dies
    nor blows up through 94 relu layers, and batch-norm statistics that are
    not the identity."""
    import jax
    import jax.numpy as jnp

    convs = conv_shapes(hw)

    @jax.jit
    def make(key):
        out = []
        for i, (kh, kw, cin, cout, _, _) in enumerate(convs):
            k = jax.random.split(jax.random.fold_in(key, i), 4)
            std = math.sqrt(2.0 / (kh * kw * cin))
            out.append((
                std * jax.random.normal(k[0], (kh, kw, cin, cout), jnp.float32),
                0.1 * jax.random.normal(k[1], (cout,), jnp.float32),
                0.1 * jax.random.normal(k[2], (cout,), jnp.float32),
                jax.random.uniform(k[3], (cout,), jnp.float32, 0.5, 1.5),
            ))
        return out

    return make(jax.random.PRNGKey(int(weights_seed) % (2**31)))


def as_flax_variables(params) -> dict:
    """The documented pytree a caller may hand ``modelWeights``: Keras's
    normalised layer names (``conv2d``, ``conv2d_1``, ...)."""
    tree = {"params": {}, "batch_stats": {}}
    for i, (kernel, bias, mean, var) in enumerate(params):
        suffix = "" if i == 0 else f"_{i}"
        tree["params"][f"conv2d{suffix}"] = {"kernel": kernel}
        tree["params"][f"batch_normalization{suffix}"] = {"bias": bias}
        tree["batch_stats"][f"batch_normalization{suffix}"] = {
            "mean": mean, "var": var,
        }
    return tree


def fp8_operand(x):
    """Round to float8_e4m3fn with one scale per tensor, and back: what a
    conv's operands keep in the nearest precision below bfloat16."""
    import jax.numpy as jnp

    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 448.0 / amax
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def features(params, rgb, operand=None, block: int = 64):
    """2048 features for float32 RGB images ``rgb`` (n, 299, 299, 3) in
    [0, 255], in blocks of rows, with the "tf" preprocessing written out."""
    import jax
    import numpy as np

    @jax.jit
    def forward(params, xb):
        return network(Arrays(params, operand), xb / 127.5 - 1.0)

    out = [
        np.asarray(forward(params, rgb[lo:lo + block]))
        for lo in range(0, len(rgb), block)
    ]
    return np.concatenate(out)


def resize_bilinear(rgb, hw=INPUT_HW):
    """One float32 (h, w, 3) image resized with plain ``jax.image.resize``
    — independent of the program's native pack and device prologue."""
    import jax
    import numpy as np

    return np.asarray(
        jax.image.resize(rgb, (hw[0], hw[1], 3), "bilinear"), np.float32
    )
