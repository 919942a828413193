"""Drives the rest of an ``ar_generate`` cell's run with the timed path
broken underneath (the ``ar_generate`` driver's counterpart of
``faulty_generate.py``).

Run as a script: it sets the cell up once, checks that the sound program
comes out correct and the control (the reference with every matmul's
operands in fp8, put in the program's place) does not, then plants each
fault IN THE PROGRAM, runs the timed path again and records ``correct``:

- ``state_at_padded_end``: prefill keeps each row's recurrent state and conv
  window as they stand after the segment's LAST position, pads and all,
  instead of after the row's own last token (its first generated token,
  read at the right position, is still right: only what decodes from the
  state can tell);
- ``residual_multiplier_dropped``: the model takes ``residual_multiplier``
  as 1.

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
    import jax.numpy as jnp

    from sparkdl_tpu.models import granite_hybrid

    class StateAtPaddedEnd(granite_hybrid.GraniteHybridModel):
        @property
        def fingerprint(self):  # another program than the sound one's
            return super().fingerprint + ":state_at_padded_end"

        def prefill(self, params, state, tokens, rows, start, lengths):
            # the model's own prefill, but for what the layers are told of
            # the rows' lengths: every position of the segment counts as
            # real.  What is read (the hidden state at the row's own last
            # token, causal and so untouched by the pads; position; token)
            # is read where it should be
            cfg = self.config
            whole = jnp.where(lengths > 0, tokens.shape[1], 0)
            x, state, counts = granite_hybrid._segment(
                params, cfg, state, tokens, rows, start, whole)
            last = jnp.take_along_axis(
                x, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)[:, 0]
            logp = granite_hybrid._log_probs(params, cfg, last)
            state["position"] = state["position"].at[rows].set(
                start + lengths, mode="drop")
            state["token"] = state["token"].at[rows].set(
                jnp.argmax(logp, axis=-1).astype(jnp.int32), mode="drop")
            return state, logp, counts

    def residual_multiplier_dropped(config, params):
        return granite_hybrid.GraniteHybridModel(
            dict(config, residual_multiplier=1.0), params)

    return {"state_at_padded_end": StateAtPaddedEnd,
            "residual_multiplier_dropped": residual_multiplier_dropped}


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
    for name, make_model in faults().items():
        job.make_model = make_model
        job.build_stage()
        try:
            job.timed_path_again()
        finally:
            job.make_model = None
        compared = job.compare()
        out["faults"][name] = compared.correct
        out["compared"][name] = compared.as_dict()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
