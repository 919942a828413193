"""Operations and bytes that a step NEEDS, counted from shapes alone.

Never from ``cost_analysis()`` of the program under test: a program that
pads a conv would raise its own utilisation.  A multiply-add counts as two
operations; only convolutions and dense layers are counted (pooling,
normalisation and activations are well under 1% of either model).
"""

from __future__ import annotations


def conv_flops(convs) -> int:
    """``convs``: (kh, kw, cin, cout, out_h, out_w) per conv."""
    return sum(
        2 * kh * kw * cin * cout * oh * ow
        for kh, kw, cin, cout, oh, ow in convs
    )


def inception_v3_forward(hw=(299, 299)) -> dict:
    """Per image: FLOPs, weight elements, activation elements written."""
    from chipbench.reference import inception_v3

    convs = inception_v3.conv_shapes(hw)
    return {
        "flops": conv_flops(convs),
        "weight_elems": sum(kh * kw * ci * co for kh, kw, ci, co, _, _ in convs),
        "activation_elems": sum(co * oh * ow for _, _, _, co, oh, ow in convs),
    }


def inception_v3_program_bytes(batch: int, in_bytes_per_image: int,
                               hw=(299, 299), act_bytes: int = 2) -> int:
    """Least bytes one dispatch moves through HBM: the batch in, the weights
    once, every conv's output written once and read once, features out."""
    per = inception_v3_forward(hw)
    return (
        batch * in_bytes_per_image
        + per["weight_elems"] * act_bytes
        + batch * per["activation_elems"] * act_bytes * 2
        + batch * 2048 * 4
    )


def keras_forward(model) -> dict:
    """Per image, from a built Keras model's layer shapes: Conv2D and Dense
    layers only."""
    flops = weights = acts = 0
    for layer in model._flatten_layers(include_self=False):
        kind = type(layer).__name__
        if kind == "Conv2D":
            kh, kw, cin, cout = (int(v) for v in layer.kernel.shape)
            _, oh, ow, _ = layer.output.shape
            flops += 2 * kh * kw * cin * cout * int(oh) * int(ow)
            weights += kh * kw * cin * cout
            acts += cout * int(oh) * int(ow)
        elif kind == "Dense":
            cin, cout = (int(v) for v in layer.kernel.shape)
            flops += 2 * cin * cout
            weights += cin * cout
            acts += cout
    return {"flops": flops, "weight_elems": weights, "activation_elems": acts}


def train_step(forward: dict, batch: int, in_bytes_per_image: int,
               param_bytes: int = 4, act_bytes: int = 4) -> dict:
    """A training step: three times the forward's FLOPs (forward, and the
    backward's two products per layer; recomputation is not counted).
    Bytes: the batch in; weights read forward and backward, gradient
    written, and the update's read-modify-write; every activation written
    forward and read backward, and its gradient written and read."""
    return {
        "flops": 3 * forward["flops"] * batch,
        "bytes": (
            batch * in_bytes_per_image
            + forward["weight_elems"] * param_bytes * 5
            + batch * forward["activation_elems"] * act_bytes * 4
        ),
    }
