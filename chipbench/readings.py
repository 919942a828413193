"""The readings that limits are set from: a cell's program and its control
on many seeds in ONE process (set-up is long; the compiled programs, the
weights and the transformer are shared across the seeds).

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 --control fp8 [--seconds 3]

Prints one JSON line per seed: the numbers compared for the program
(``program``) and for each control put in its place (``control:<name>``,
``--control a,b``), beside the limits the cell's file holds.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    os.environ.setdefault("KERAS_BACKEND", "jax")
    from chipbench import harness

    harness.place_compile_cache(ROOT)
    cell = harness.Cell(args.workload, ROOT)
    device = harness.device_report()
    if not args.rehearse and device["platform"] != "tpu":
        print(f"readings: {device} is not a TPU", file=sys.stderr)
        return 2
    workdir = os.path.join(ROOT, ".chipbench", cell.name)
    os.makedirs(workdir, exist_ok=True)
    shared = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        job = cell.driver.Job(cell, seed, args.rehearse, workdir)
        job.setup(shared)
        window = (job.window(args.seconds) if args.seconds > 0
                  else {"end_to_end": {}})
        job.release()
        line = {
            "workload": cell.name, "seed": seed, "device": device,
            "end_to_end": window["end_to_end"],
            "program": job.compare().as_dict(),
            "look": getattr(job, "leaf_report", None),
        }
        for control in filter(None, args.control.split(",")):
            line["control:" + control] = job.compare(control).as_dict()
            line["control_look:" + control] = getattr(job, "leaf_report", None)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
