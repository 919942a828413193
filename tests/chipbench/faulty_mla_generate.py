"""Drives the rest of a ``mla_generate`` cell's run with the timed path
broken underneath (the ``mla_generate`` driver's counterpart of
``faulty_kda_generate.py``).

Run as a script: it sets the cell up once, checks that the sound program
comes out correct and the control (the reference with every matmul's
operands in fp8, put in the program's place) does not, then plants each
fault IN THE PROGRAM, runs the timed path again and records ``correct``:

- ``scale_of_nope_only``: the scores scaled by ``1/sqrt(qk_nope_head_dim)``
  (``1/sqrt(128)`` at the published widths) in place of ``1/sqrt(qk_nope +
  qk_rope)`` (``1/sqrt(192)``), in prefill and decode alike;
- ``routed_scale_dropped``: the model takes ``routed_scaling_factor`` as 1;
- ``rope_rotate_half``: RoPE pairs channel ``i`` with ``i + d/2`` (the
  rotate-half pairing of Llama-style models) in place of DeepSeek-V3's
  interleaved ``2i``, ``2i + 1``.

Prints one JSON object: ``{"sound": bool, "faults": {name: bool}, ...}``.
Tiny shapes on whatever JAX finds by default (``--rehearse 0``: the cell's
real size, on the chip).
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def faults():
    import jax.numpy as jnp

    from sparkdl_tpu.models import moonlight
    from sparkdl_tpu.ops import mla

    def rotate_half(x, position, theta):
        """RoPE with channel i turned together with i + d/2."""
        d = x.shape[-1]
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        angle = position.astype(jnp.float32)[..., None] * inv
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        xf = x.astype(jnp.float32)
        a, b = xf[..., :d // 2], xf[..., d // 2:]
        return jnp.concatenate(
            [a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)

    @contextlib.contextmanager
    def planted(module, name, value):
        sound = getattr(module, name)
        setattr(module, name, value)
        try:
            yield
        finally:
            setattr(module, name, sound)

    def model_with(label, module, name, value):
        class Planted(moonlight.MoonlightModel):
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

    def routed_scale_dropped(config, params):
        return moonlight.MoonlightModel(
            dict(config, routed_scaling_factor=1.0), params)

    return {
        "scale_of_nope_only": model_with(
            "scale_of_nope_only", moonlight, "softmax_scale",
            lambda cfg: cfg.qk_nope_head_dim ** -0.5),
        "routed_scale_dropped": routed_scale_dropped,
        "rope_rotate_half": model_with(
            "rope_rotate_half", mla, "rope", rotate_half),
    }


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
