"""Drives the rest of a ``dsa_generate`` cell's run with the timed path
broken underneath (the ``dsa_generate`` driver's counterpart of
``faulty_mla_generate.py``).

Run as a script: it sets the cell up once, checks that the sound program
comes out correct and the control (the reference with every matmul's
operands in fp8, put in the program's place) does not, then plants each
fault IN THE PROGRAM, runs the timed path again and records ``correct``:

- ``selection_left_out``: every visible key kept (dense MLA: ``index_topk``
  past any row's length), in prefill and decode alike;
- ``relu_left_out``: the indexer's scores summed over its heads without the
  ReLU on each head's products;
- ``rope_left_out``: the indexer's queries and keys without their RoPE.

Prints one JSON object: ``{"sound": bool, "faults": {name: bool}, ...}``.
Tiny shapes on whatever JAX finds by default (``--rehearse 0``: the cell's
real size, on the chip, where ``--control 0`` leaves the control to
``chipbench/readings.py``: at that size its two reference forwards take
longer than the three faults' passes).
"""

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def faults():
    from sparkdl_tpu.models import glm5
    from sparkdl_tpu.ops import dsa

    @contextlib.contextmanager
    def planted(module, name, value):
        sound = getattr(module, name)
        setattr(module, name, value)
        try:
            yield
        finally:
            setattr(module, name, sound)

    def model_with(label, module, name, value):
        class Planted(glm5.Glm5Model):
            @property
            def fingerprint(self):  # another program than the sound one's
                return super().fingerprint + ":" + label

            def prefill(self, *args):
                with planted(module, name, value):  # while it is traced
                    return super().prefill(*args)

            def decode(self, *args):
                with planted(module, name, value):
                    return super().decode(*args)

        return Planted

    def selection_left_out(config, params):
        return glm5.Glm5Model(dict(config, index_topk=2**30), params)

    return {
        "selection_left_out": selection_left_out,
        "relu_left_out": model_with(
            "relu_left_out", dsa, "_relu", lambda x: x),
        "rope_left_out": model_with(
            "rope_left_out", dsa, "_turned", lambda x, position, ix: x),
    }


def _drop_stage(job):
    """The stage, and with it the runner's caches (3.5 GB at the cell's
    size), go before the reference runs, as ``run.py``'s ``job.release()``
    does; the frame stays for the next stage's pass."""
    job.stage = None
    gc.collect()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearse", type=int, default=1)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    os.environ.setdefault("KERAS_BACKEND", "jax")
    from chipbench import harness

    harness.place_compile_cache(ROOT)
    cell = harness.Cell(args.workload, ROOT)
    workdir = os.path.join(ROOT, ".chipbench", "faults-" + cell.name)
    os.makedirs(workdir, exist_ok=True)
    job = cell.driver.Job(cell, args.seed, bool(args.rehearse), workdir)
    job.setup()
    job.window(0.2)
    _drop_stage(job)
    first = job.compare()
    out = {"sound": first.correct, "sound_compared": first.as_dict(),
           "faults": {}, "compared": {}}
    if args.control:
        control = job.compare(cell.workload["control"])
        out.update(control=control.correct,
                   control_compared=control.as_dict())
    for name, make_model in faults().items():
        job.make_model = make_model
        job.build_stage()
        try:
            job.timed_path_again()
        finally:
            job.make_model = None
        _drop_stage(job)
        compared = job.compare()
        out["faults"][name] = compared.correct
        out["compared"][name] = compared.as_dict()
        print(f"fault {name}: correct {compared.correct}, "
              f"{json.dumps(compared.as_dict())}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
