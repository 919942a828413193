"""Drives the rest of a ``generate`` cell's run with the timed path broken
underneath (the ``generate`` driver's counterpart of ``faulty_run.py``).

Run as a script: it sets the cell up once, checks that the sound program
comes out correct and the control (the reference with every matmul's
operands in fp8, put in the program's place) does not, then plants each
fault IN THE PROGRAM, runs the timed path again and records ``correct``:

- ``commit_left_out``: the block step keeps the cache it was given, so the
  next block reads a cache without its predecessor;
- ``token_altered``: in every row, the first generated token is another.

Prints one JSON object: ``{"sound": bool, "faults": {name: bool}, ...}``.
Tiny shapes on whatever JAX finds by default (``--rehearse 0``: the cell's
real size, on the chip).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def faults():
    from sparkdl_tpu.models.sdar_moe import SdarMoeModel
    from sparkdl_tpu.transformers import block_diffusion

    class CommitLeftOut(SdarMoeModel):
        @property
        def fingerprint(self):  # another program than the sound one's
            return super().fingerprint + ":commit_left_out"

        def block_step(self, params, cache_k, cache_v, *rest):
            _, _, *out = super().block_step(params, cache_k, cache_v, *rest)
            return (cache_k, cache_v, *out)

    sound = block_diffusion._generate_batch

    def token_altered(runner, prompts, rows, gen):
        tokens, records = sound(runner, prompts, rows, gen)
        block = runner.block
        for prompt, row_tokens, record in zip(prompts, tokens, records):
            row_tokens[0] = (row_tokens[0] + 1) % (runner.mask_id - 1)
            record[len(prompt) % block, 0] = row_tokens[0]
        return tokens, records

    def plant(job, name):
        if name == "commit_left_out":
            job.make_model = CommitLeftOut
        else:
            block_diffusion._generate_batch = token_altered
        job.build_stage()

    def heal(job):
        job.make_model = None
        block_diffusion._generate_batch = sound

    return ("commit_left_out", "token_altered"), plant, heal


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--rehearse", type=int, default=1)
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
    first = job.compare()
    control = job.compare(cell.workload["control"])
    out = {"sound": first.correct, "sound_compared": first.as_dict(),
           "control": control.correct, "control_compared": control.as_dict(),
           "faults": {}, "compared": {}}
    names, plant, heal = faults()
    for name in names:
        plant(job, name)
        try:
            job.timed_path_again()
        finally:
            heal(job)
        compared = job.compare()
        out["faults"][name] = compared.correct
        out["compared"][name] = compared.as_dict()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
