"""Native-stack (pjrt_tool) marginal batch cost under the paired-trial
protocol.

An early probe measured t(9)-t(5) marginal cost twice on the same day and
got 1.1 s/batch and 4.4 s/batch — single-shot CLI timings on the host's
clock cannot support a steady-state-throughput claim.  This runs k
interleaved (few, many) invocation pairs; each round's marginal cost is
(t_many - t_few) / (n_many - n_few), which cancels the ~27 s one-time
setup (client create + cached compile + params upload) within the round,
and the median over rounds cancels the rig drift between them.

    python benchmarks/bench_native_marginal.py [-k 5] [--model InceptionV3]

Prints one JSON line (record-only; vs_baseline null).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("KERAS_BACKEND", "jax")

BATCH = 64


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-k", type=int, default=5)
    ap.add_argument("--model", default="InceptionV3")
    ap.add_argument("--few", type=int, default=2)
    ap.add_argument("--many", type=int, default=20,
                    help="wider few/many delta -> more signal vs the "
                         "~27s per-invocation setup variance")
    args = ap.parse_args()
    N_FEW, N_MANY = args.few, args.many

    from sparkdl_tpu.models.registry import get_keras_application_model
    from sparkdl_tpu.native.featurizer import (
        export_featurizer,
        run_featurizer_cli,
    )
    from sparkdl_tpu.utils.benchlib import paired_trials

    entry = get_keras_application_model(args.model)
    h, w = entry.input_size
    prog_dir = tempfile.mkdtemp(prefix="native_marginal_")
    # random weights: the FLOP rate is weight-independent and the rig is
    # offline (no imagenet cache)
    export_featurizer(
        args.model, batch_size=BATCH, out_dir=prog_dir,
        model_weights="random",
    )

    rng = np.random.RandomState(0)
    # randint(dtype=uint8) — rand() would allocate a ~2.7 GB float64
    # intermediate at the default 20-batch stack
    stack = rng.randint(
        0, 256, size=(N_MANY, BATCH, h, w, 3), dtype=np.uint8
    )

    def run(n_batches: int) -> float:
        t0 = time.perf_counter()
        feats = run_featurizer_cli(prog_dir, stack[:n_batches])
        elapsed = time.perf_counter() - t0
        assert feats.shape[0] == n_batches
        return elapsed

    trials = paired_trials(
        {"few": lambda: run(N_FEW), "many": lambda: run(N_MANY)}, k=args.k
    )
    from sparkdl_tpu.utils.benchlib import summarize_samples

    marginals = [
        (m - f) / (N_MANY - N_FEW)
        for f, m in zip(trials["few"]["samples"], trials["many"]["samples"])
    ]
    summary = summarize_samples(marginals)
    med, iqr = summary["median"], summary["iqr"]
    print(
        json.dumps(
            {
                "metric": f"pjrt_tool({args.model}) marginal batch cost",
                "value": round(med, 3),
                "unit": f"sec/batch({BATCH})",
                "images_per_sec": round(BATCH / med, 1) if med > 0 else None,
                "iqr": iqr,
                "per_round": summary["samples"],
                "k": args.k,
                "vs_baseline": None,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
