"""The one general generator: every input the benchmark feeds the program.

A traffic mix is a JSON file of parameters under ``chipbench/traffic/``; its
``kind`` names the function here that reads it (``cached_frame``,
``image_files``).  Every seed gets the SAME
multiset of image sizes and formats in another order, with other pixels, so
that the seed changes the data and never the amount of work.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np


def _rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent stream per (seed, purpose, index); seeds past 2**32
    are fine."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def smooth_image(rng: np.random.Generator, h: int, w: int) -> np.ndarray:
    """An (h, w, 3) uint8 RGB image smooth enough that a resize is
    well-conditioned: a coarse random grid blown up, plus a little noise."""
    from PIL import Image

    coarse = rng.integers(0, 256, (6, 6, 3), dtype=np.uint8)
    img = np.asarray(
        Image.fromarray(coarse).resize((w, h), Image.BICUBIC), np.int16
    )
    img = img + rng.integers(-6, 7, img.shape, dtype=np.int16)
    return np.clip(img, 0, 255).astype(np.uint8)


def cached_frame(params: dict, seed: int) -> dict:
    """``distinct`` uniform-size images and the order in which ``rows`` rows
    repeat them (each image equally often).  ``images`` is RGB; the struct
    the program reads stores BGR."""
    distinct, rows = int(params["distinct"]), int(params["rows"])
    h, w = int(params["height"]), int(params["width"])
    if rows % distinct:
        raise ValueError("rows must be a multiple of distinct")
    images = np.stack(
        [smooth_image(_rng(seed, 1, i), h, w) for i in range(distinct)]
    )
    order = np.tile(np.arange(distinct), rows // distinct)
    _rng(seed, 2).shuffle(order)
    return {"images": images, "order": order}


def file_plan(params: dict, seed: int) -> list:
    """[(file name, (h, w), format)] — a fixed multiset of sizes and formats
    (the traffic file's ``palette``, every ``png_every``-th a PNG), shuffled
    by the seed."""
    n = int(params["files"])
    palette = [tuple(int(v) for v in hw) for hw in params["palette"]]
    slots = [
        (palette[i % len(palette)],
         "png" if i % int(params["png_every"]) == 0 else "jpg")
        for i in range(n)
    ]
    order = _rng(seed, 3).permutation(n)
    return [
        (f"img_{pos:05d}.{slots[k][1]}", slots[k][0], slots[k][1])
        for pos, k in enumerate(order)
    ]


def image_files(params: dict, seed: int, directory: str, classes: int) -> dict:
    """Write the plan's files under ``directory`` (emptied first) and draw a
    label in ``[0, classes)`` for each.  Returns paths (sorted by name, the
    order ``readImages`` lists them in) and labels."""
    from PIL import Image

    if os.path.isdir(directory):
        for name in os.listdir(directory):
            os.unlink(os.path.join(directory, name))
    os.makedirs(directory, exist_ok=True)
    plan = file_plan(params, seed)

    def write(item):
        i, (name, (h, w), fmt) = item
        path = os.path.join(directory, name)
        img = Image.fromarray(smooth_image(_rng(seed, 4, i), h, w))
        if fmt == "png":
            img.save(path, compress_level=1)
        else:
            img.save(path, quality=int(params.get("jpeg_quality", 92)))
        return path

    with ThreadPoolExecutor(max_workers=int(params.get("writers", 8))) as pool:
        paths = list(pool.map(write, enumerate(plan)))
    labels = _rng(seed, 5).integers(0, int(classes), len(paths))
    return {"paths": paths, "labels": labels.astype(np.int64)}


def load_rgb(path: str) -> np.ndarray:
    """A file decoded to float32 RGB with PIL — the reference's decode."""
    from PIL import Image

    return np.asarray(Image.open(path).convert("RGB"), np.float32)

