"""The generator of the traffic kind ``prompt_frame``: a DataFrame's worth of
prompts (arrays of token ids) for the ``generate`` driver.

Every seed gets the SAME multiset of prompt lengths in another order, with
other tokens, so that the seed changes the data and never the amount of
work: the lengths are the log-uniform quantiles between ``min_length`` and
``max_length``, whole numbers and not rounded to any block.
"""

from __future__ import annotations

import numpy as np


def lengths(params: dict) -> np.ndarray:
    """The fixed multiset: ``rows`` lengths, log-uniform by quantile."""
    rows = int(params["rows"])
    lo, hi = float(params["min_length"]), float(params["max_length"])
    quantile = (np.arange(rows) + 0.5) / rows
    return np.rint(lo * (hi / lo) ** quantile).astype(np.int64)


def prompt_frame(params: dict, seed: int, vocab_size: int, mask_id: int) -> list:
    """``rows`` prompts: the multiset of :func:`lengths` shuffled by the
    seed, token ids uniform over the vocabulary without ``mask_id``."""
    order = np.random.default_rng([int(seed), 21]).permutation(lengths(params))
    rng = np.random.default_rng([int(seed), 22])
    prompts = []
    for n in order:
        ids = rng.integers(0, vocab_size - 1, int(n))
        prompts.append((ids + (ids >= mask_id)).astype(np.int32))
    return prompts
