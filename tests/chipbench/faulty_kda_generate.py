"""Drives the rest of a ``kda_generate`` cell's run with the timed path
broken underneath (the ``kda_generate`` driver's counterpart of
``faulty_ar_generate.py``).

Run as a script: it sets the cell up once, checks that the sound program
comes out correct and the control (the reference with every matmul's
operands in fp8, put in the program's place) does not, then plants each
fault IN THE PROGRAM, runs the timed path again and records ``correct``:

- ``decay_after_write``: the delta rule decays the state after the write
  instead of before it (``S_t = Diag(alpha_t) (S_{t-1} + beta_t k_t (v_t -
  S_{t-1}^T k_t)^T)``), in prefill and in decode alike;
- ``beta_not_doubled``: the model takes ``kda_allow_neg_eigval`` as false,
  a write strength in (0, 1).

Prints one JSON object: ``{"sound": bool, "faults": {name: bool}, ...}``.
Tiny shapes on whatever JAX finds by default (``--rehearse 0``: the cell's
real size, on the chip).
"""

import argparse
import contextlib
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def faults():
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models import solar_open2

    def update(q, k, v, log_decay, beta, state):
        """``ops/delta_rule.kda_update`` with the decay after the write."""
        qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
        predicted = jnp.sum(state * kf[..., None], axis=-2)
        w = beta[..., None] * (vf - predicted)
        state = jnp.exp(log_decay)[..., None] * (
            state + kf[..., None] * w[..., None, :])
        return jnp.sum(state * qf[..., None], axis=-2), state

    def chunked(q, k, v, log_decay, beta, state, chunk):
        """The same, token by token over the segment (a pad's ``beta`` and
        log-decay are zero: it leaves the state alone here too)."""
        def token(state, at):
            o, state = update(*at, state)
            return state, o

        state, o = jax.lax.scan(token, state, tuple(
            jnp.swapaxes(t, 0, 1) for t in (q, k, v, log_decay, beta)))
        return jnp.swapaxes(o, 0, 1), state

    planted = types.SimpleNamespace(kda_chunked=chunked, kda_update=update)

    @contextlib.contextmanager
    def rule():
        sound = solar_open2.delta_rule
        solar_open2.delta_rule = planted
        try:
            yield
        finally:
            solar_open2.delta_rule = sound

    class DecayAfterWrite(solar_open2.SolarOpen2Model):
        @property
        def fingerprint(self):  # another program than the sound one's
            return super().fingerprint + ":decay_after_write"

        def prefill(self, *args):
            with rule():  # in force while the program is traced
                return super().prefill(*args)

        def decode(self, *args):
            with rule():
                return super().decode(*args)

    def beta_not_doubled(config, params):
        return solar_open2.SolarOpen2Model(
            dict(config, kda_allow_neg_eigval=False), params)

    return {"decay_after_write": DecayAfterWrite,
            "beta_not_doubled": beta_not_doubled}


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
