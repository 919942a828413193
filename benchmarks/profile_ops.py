"""Per-op device-trace profiler for the zoo featurizer programs.

Produces a per-op device-trace profile: captures a ``jax.profiler`` trace of the fused uint8→preprocess→
CNN program (the bench.py hot loop), joins every ``fusion.N`` duration on
the TPU "XLA Ops" track with its compiled-HLO instruction (op_name
metadata + called-computation body), and prints an op-class / per-layer
breakdown with achieved GB/s per fusion — the roofline diagnosis tool.

Usage (real TPU):
    python benchmarks/profile_ops.py InceptionV3 [--batch 512] [--iters 3]

Methodology notes (hard-won):
- durations come from the device track of the trace, not host timing —
  host wall time of an asynchronous dispatch is noise;
- achieved GB/s = (operand bytes + output bytes) / device time, an
  *upper bound* on true traffic (operands may come from on-chip reuse);
- compare TF/s against the chip's *demonstrated* conv ceiling (~139 TF/s,
  measured via VGG19's 3x3 convs on a v5e chip in July 2026), not the
  197 TF/s spec.  The earlier 76 TF/s
  figure was XLA's DOT-emitter plateau at 8192³, not the chip limit.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import tempfile
from collections import defaultdict

import numpy as np

import jax
import jax.numpy as jnp

DTYPE_BYTES = {
    "bf16": 2, "f32": 4, "f16": 2, "u8": 1, "s8": 1,
    "u32": 4, "s32": 4, "pred": 1, "f64": 8,
}


def build_forward(model_name: str, batch: int):
    from sparkdl_tpu.models import get_keras_application_model
    from sparkdl_tpu.models.registry import fold_bgr_flip_into_stem

    entry = get_keras_application_model(model_name)
    module = entry.make_module(dtype=jnp.bfloat16)
    h, w = entry.inputShape()
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jnp.zeros((1, h, w, 3), jnp.float32),
    )
    variables = jax.tree_util.tree_map(
        lambda l: jnp.full(l.shape, 0.01, l.dtype), shapes
    )
    # the mode gate (fold only under channel-symmetric 'tf' preprocessing)
    # lives inside the helper, so this profiles exactly the production
    # program for every model
    folded = fold_bgr_flip_into_stem(variables, entry.preprocess_mode)
    flip = folded is None
    if folded is not None:
        variables = folded
    device = jax.devices()[0]
    variables = jax.device_put(variables, device)
    rng = np.random.RandomState(0)
    x = jax.device_put(
        jnp.asarray((rng.rand(batch, h, w, 3) * 255).astype(np.uint8)),
        device,
    )

    @jax.jit
    def forward(v, xb):
        if flip:
            xb = xb[..., ::-1]
        xb = entry.preprocess(xb.astype(jnp.bfloat16))
        return (
            module.apply(v, xb.astype(jnp.bfloat16), features_only=True)
            .astype(jnp.float32)
            .sum()
        )

    return forward, variables, x


def capture(forward, variables, x, out_dir: str, iters: int):
    np.asarray(forward(variables, x))  # compile + warm
    np.asarray(forward(variables, x))
    with jax.profiler.trace(out_dir):
        for _ in range(iters):
            np.asarray(forward(variables, x))
    (trace,) = glob.glob(
        os.path.join(out_dir, "plugins/profile/*/*.trace.json.gz")
    )
    return trace


def device_op_durations(trace_path: str):
    """name -> total seconds on the TPU 'XLA Ops' track."""
    with gzip.open(trace_path) as f:
        events = json.load(f)["traceEvents"]
    pid_names, tid_names = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pid_names[e["pid"]] = e["args"].get("name", "")
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            tid_names[(e["pid"], e["tid"])] = e["args"].get("name", "")
    durs: dict = defaultdict(float)
    for e in events:
        if e.get("ph") != "X":
            continue
        if "TPU" not in pid_names.get(e["pid"], ""):
            continue
        if tid_names.get((e["pid"], e["tid"])) != "XLA Ops":
            continue
        durs[e["name"].lstrip("%")] += e.get("dur", 0) / 1e6
    return durs


def parse_hlo(hlo: str):
    """(computations, top-level instruction lines)."""
    comps: dict = {}
    cur = None
    for line in hlo.splitlines():
        if (
            not line.startswith(" ")
            and line.rstrip().endswith("{")
            and line.lstrip().startswith("%")
        ):
            cur = re.match(r"%([\w.\d_-]+)", line.lstrip()).group(1)
            comps[cur] = []
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line.strip())
    instrs = {
        m.group(1): m.group(0)
        for m in re.finditer(r"%([\w.\d_-]+) = [^\n]+", hlo)
    }
    return comps, instrs


def shape_bytes(s: str) -> int:
    m = re.match(r"(\w+)\[([\d,]*)\]", s)
    if not m or m.group(1) not in DTYPE_BYTES:
        return 0
    n = DTYPE_BYTES[m.group(1)]
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def classify(name: str, comps, instrs):
    line = instrs.get(name, "")
    cm = re.search(r"calls=%([\w.\d_-]+)", line)
    body = comps.get(cm.group(1), []) if cm else []
    convs = [l for l in body if "convolution(" in l]
    if not convs and "convolution" in line:
        convs = [line]
    if convs:
        grouped = any(
            (g := re.search(r"feature_group_count=(\d+)", c))
            and int(g.group(1)) > 1
            for c in convs
        )
        windows = [
            w.group(1)
            for c in convs
            if (w := re.search(r"window={size=([\dx]+)", c))
        ]
        kind = "conv:depthwise" if grouped else (
            "conv:pointwise"
            if windows and all(w == "1x1" for w in windows)
            else "conv:spatial"
        )
        return kind
    if any("reduce-window" in l for l in body) or "reduce-window" in line:
        return "pool"
    if any(" dot(" in l for l in body) or " dot(" in line:
        return "dot"
    if "copy" in name or "transpose" in name:
        return "datamove"
    return "elementwise"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("model")
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--top", type=int, default=14)
    args = ap.parse_args()

    forward, variables, x = build_forward(args.model, args.batch)
    hlo = forward.lower(variables, x).compile().as_text()
    comps, instrs = parse_hlo(hlo)

    out_dir = tempfile.mkdtemp(prefix=f"prof_{args.model}_")
    trace = capture(forward, variables, x, out_dir, args.iters)
    durs = device_op_durations(trace)
    total = sum(durs.values())
    per_iter = total / args.iters

    print(
        f"{args.model}: {per_iter * 1e3:.1f} ms/iter on-device "
        f"({args.batch / per_iter:.0f} img/s), trace {trace}"
    )
    cls_time: dict = defaultdict(float)
    for name, t in durs.items():
        cls_time[classify(name, comps, instrs)] += t
    for k, v in sorted(cls_time.items(), key=lambda kv: -kv[1]):
        print(f"  {k:16s} {v / args.iters * 1e3:8.2f} ms {100 * v / total:5.1f}%")

    print(f"top {args.top} fusions (ms/iter, approx GB/s, layer):")
    for name, t in sorted(durs.items(), key=lambda kv: -kv[1])[: args.top]:
        line = instrs.get(name, "")
        out_b = shape_bytes(line.split(" = ", 1)[1]) if " = " in line else 0
        in_b = 0
        argm = re.search(r"fusion\(([^)]*)\)", line)
        if argm:
            for a in re.findall(r"%([\w.\d_-]+)", argm.group(1)):
                al = instrs.get(a, "")
                if " = " in al:
                    in_b += shape_bytes(al.split(" = ", 1)[1])
        ms = t / args.iters * 1e3
        gbps = (out_b + in_b) / 1e9 / (ms / 1e3) if ms else 0
        om = re.search(r'op_name="([^"]*)"', line)
        layer = (
            om.group(1).split("/")[-2]
            if om and om.group(1).count("/") >= 2
            else ""
        )
        kind = classify(name, comps, instrs)
        print(f"  {ms:7.2f} {gbps:6.0f} GB/s {kind:15s} {name:26s} {layer}")


if __name__ == "__main__":
    main()
