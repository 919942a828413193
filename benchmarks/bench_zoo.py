"""Model-zoo breadth benchmark: one row per registry model, reproducibly.

Measures every registry model through the same fused uint8->preprocess->CNN
program and scan-amortized methodology as ``bench.py`` (one shared harness:
``sparkdl_tpu.utils.benchlib.measure_featurizer``), printing one JSON line
per model with images/sec/chip and MFU.

    python benchmarks/bench_zoo.py [--batch 512] [--scan 24] [Model ...]

Defaults to the full registry at the HEADLINE methodology (scan 24 —
zoo numbers and bench.py numbers are directly comparable; the input stack
is generated on the device, so depth costs no staging).  Measures on the
chip only: without an accelerator it prints a refusal and exits 2.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("KERAS_BACKEND", "jax")


def main():
    from sparkdl_tpu.utils.benchlib import (
        accelerator_or_refuse,
        measure_featurizer,
        summarize_samples,
    )

    device = accelerator_or_refuse("model-zoo bf16 featurize throughput")
    if device is None:
        return 2

    from sparkdl_tpu.models.registry import SUPPORTED_MODELS

    ap = argparse.ArgumentParser()
    ap.add_argument("models", nargs="*", default=None)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--scan", type=int, default=24)
    ap.add_argument("-k", type=int, default=3,
                    help="trials per model; JSON reports median + IQR")
    args = ap.parse_args()
    names = args.models or sorted(SUPPORTED_MODELS)
    for name in names:
        # one compile per model; k timed trial groups share the program
        out = measure_featurizer(
            name, args.batch, args.scan, trials=args.k
        )
        summary = summarize_samples(out["samples"])
        # mfu from the trial closest to the median, so the two headline
        # numbers come from the same measurement
        med_i = min(
            range(len(out["samples"])),
            key=lambda i: abs(out["samples"][i] - summary["median"]),
        )
        mfu_val = out["mfu_samples"][med_i]
        h, w = out["input_hw"]
        print(
            json.dumps(
                {
                    "metric": f"{name} bf16 featurize throughput",
                    "value": summary["median"],
                    "unit": "images/sec/chip",
                    "iqr": summary["iqr"],
                    "k": args.k,
                    "input": f"{h}x{w}",
                    "mfu": round(mfu_val, 4) if mfu_val is not None else None,
                    "device": device,
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    sys.exit(main())
