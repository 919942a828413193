"""The benchmark's command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip.  It finds the cell's files by the names in
``BENCHMARK.json``, sets the cell up (inputs and weights from the seed, every
shape warmed up: all of it ``setup_s``), measures for ``--seconds``, reads
the device's memory peak, then decides ``correct`` against the plain
reference, and prints one JSON object as its last line.  ``--trace 0`` gives
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
few traced seconds inside the window.

Without a TPU (or with fewer chips than the cell asks for, or on a device that
``chipbench/peaks.json`` does not know) it exits non-zero and prints no
result.  ``--rehearse`` runs tiny shapes on whatever JAX finds, for the CPU
tests; its line says so and carries no device metric.
``--control <name>`` puts the reference, computed in a lower precision, in
the program's place in the comparison (it has to come out not correct); the
benchmark's own runs never use it.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _refuse(message: str) -> int:
    print(f"chipbench: {message}", file=sys.stderr)
    return 2


def run(args) -> int:
    if not os.path.isdir(os.path.join(ROOT, "sparkdl_tpu")):
        return _refuse(f"no program (sparkdl_tpu/) beside the benchmark in {ROOT}")
    sys.path.insert(0, ROOT)
    os.environ.setdefault("KERAS_BACKEND", "jax")
    from chipbench import harness, trace_reduce

    cache_dir = harness.place_compile_cache(ROOT)
    cell = harness.Cell(args.workload, ROOT)
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)
    device = harness.device_report()
    if not args.rehearse:
        if device["platform"] != "tpu":
            return _refuse(f"JAX found {device}, not a TPU (--rehearse runs tiny shapes)")
        if device["count"] < cell.chips:
            return _refuse(f"{cell.name} needs {cell.chips} chip(s), JAX holds {device['count']}")
        peaks = harness.peaks_for(device["kind"])
    else:
        peaks = None
    print(f"chipbench: {cell.name} seed {args.seed} on {device}; cache {cache_dir}",
          file=sys.stderr, flush=True)

    workdir = os.path.join(ROOT, ".chipbench", cell.name)
    os.makedirs(workdir, exist_ok=True)
    meter = harness.CompileMeter()
    job = cell.driver.Job(cell, args.seed, args.rehearse, workdir)
    job.setup()
    setup_compiles = meter.snapshot()
    setup_s = time.perf_counter() - _PROCESS_START
    print(f"chipbench: set-up {setup_s:.1f} s, compile {setup_compiles}",
          file=sys.stderr, flush=True)

    traced = None
    trace_dir = os.path.join(workdir, "trace")
    if args.trace:
        span_s = min(float(cell.workload["trace_seconds"]),
                     max(args.seconds / 2, 0.2))
        traced = harness.TracedWindow(
            trace_dir, harness.trace_delay(args.seconds, span_s), span_s)
        traced.start()
    window = job.window(args.seconds)
    in_window = harness.CompileMeter.delta(setup_compiles, meter.snapshot())
    if traced is not None:
        traced.finish()
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    job.release()

    comparison = job.compare(args.control)
    facts = dict(window["facts"], compiles=in_window, peaks=peaks,
                 config=cell.config)
    result = {
        "correct": comparison.correct,
        "attempted": window["attempted"],
        "failed": window["failed"],
        "workload": cell.name, "seed": args.seed, "seconds": args.seconds,
        "rehearse": bool(args.rehearse), "control": args.control,
    }
    end_to_end = dict(window["end_to_end"], setup_s=setup_s)
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    if not args.trace:
        result["metrics"] = {
            name: {"value": end_to_end[name], "unit": unit}
            for name, unit in units.items()
        }
    else:
        xplane = trace_reduce.newest_xplane(trace_dir)
        events = trace_reduce.load_xplane(xplane)
        if args.keep_trace:
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, f"trace_{cell.name}.json"), "w") as fh:
                json.dump({"structure": trace_reduce.structure(xplane),
                           "events": events}, fh)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if trace_reduce.device_planes(events):
            facts["trace"] = trace_reduce.reduce(events)
            device["busy_s"] = facts["trace"]["busy_s"]
            device["window_s"] = facts["trace"]["window_s"]
            result["breakdown"] = {
                "device_ops": facts["trace"]["device_ops"],
                "idle_gaps": facts["trace"]["idle_gaps"],
            }
        elif not args.rehearse:
            return _refuse("the trace has no device plane: nothing ran on the chip")
        metrics = {}
        for entry, reader_args, reader in cell.per_layer:
            value = reader.read(facts, reader_args)
            if value is not None:
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        result["metrics"] = metrics
        result["traced_end_to_end"] = end_to_end
    result["compiles_in_setup"] = setup_compiles
    result["compiles_in_window"] = dict(
        in_window, engine_cache_miss=facts.get("engine_compiles"))
    result["compared_rows"] = getattr(job, "compared_rows", None)
    result["device"] = device
    result["compared"] = comparison.as_dict()
    sys.stdout.flush()
    for line in comparison.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: run_seconds of BENCHMARK.json")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default="")
    ap.add_argument("--keep-trace", action="store_true",
                    help="also leave the trace's events under chiprun_out/")
    return run(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
