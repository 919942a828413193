"""The reduction from a trace to numbers, on a small trace recorded on the
chip (``recorded_trace.json``: a cut of a traced ``featurize-cached`` run),
and the FLOP counts against XLA's count of the plain reference."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import flops, trace_reduce  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE

#: a hand-made trace whose answers can be worked out on paper (times in ns)
TOY = [
    (HOST, "main", "chipbench.trace_window", 1000, 1000),
    (HOST, "main", "chipbench.collect", 900, 700),
    (HOST, "main", "chipbench.transform", 1000, 300),
    (HOST, "main", "chipbench.readImages", 1650, 300),
    (DEV, MODS, "jit_forward(123)", 1100, 400),
    (DEV, MODS, "jit_forward(123)", 1900, 400),   # its start ends the window
    (DEV, MODS, "jit_other(7)", 1600, 50),
    (DEV, OPS, "%fusion.1", 1100, 300),
    (DEV, OPS, "%fusion.2", 1300, 200),           # overlaps fusion.1
    (DEV, OPS, "%fusion.1", 1600, 50),
    (DEV, OPS, "%copy.3", 1900, 400),             # after the window
    (DEV, "Steps", "0", 0, 5000),                 # not an op line: ignored
]


def test_window_is_on_the_devices_own_clock():
    # first dispatch's start to the last one's start: whole dispatch periods
    assert trace_reduce.window_of(TOY) == (1100, 1900)
    for too_few in ([e for e in TOY if e[1] != MODS],
                    [e for e in TOY if e[0] != DEV]):
        with pytest.raises(ValueError):  # fewer than two dispatches
            trace_reduce.window_of(too_few)


@pytest.mark.parametrize("intervals, want", [
    ([], 0), ([(0, 10)], 10), ([(0, 10), (5, 15)], 15),
    ([(0, 10), (20, 30), (25, 26)], 20), ([(5, 6), (0, 10)], 10),
])
def test_union(intervals, want):
    assert trace_reduce.union_ns(intervals) == want


def test_busy_and_idle_on_the_toy_trace():
    got = trace_reduce.busy(TOY)
    # [1100,1500) + [1600,1650) = 450 of the 800 ns from 1100 to 1900
    assert got["busy_s"] == pytest.approx(450e-9)
    assert got["window_s"] == pytest.approx(800e-9)
    assert got["idle_share"] == pytest.approx(0.4375)
    assert got["chips"] == 1


def test_program_seconds_counts_every_dispatch_whole():
    got = trace_reduce.program_seconds(TOY)
    assert got["jit_forward"]["whole_runs"] == 2
    assert got["jit_forward"]["whole_seconds"] == pytest.approx(800e-9)
    assert got["jit_other"]["whole_runs"] == 1


def test_gaps_are_named_by_the_innermost_span_or_by_their_neighbours():
    gaps = dict(trace_reduce.idle_gaps(TOY))
    # [1500,1600) mid 1550: collect; [1650,1900) mid 1775: readImages
    assert gaps == pytest.approx({"collect": 100e-9, "readImages": 250e-9})
    alone = dict(trace_reduce.idle_gaps([e for e in TOY if e[0] == DEV]))
    assert alone == pytest.approx({
        "host between jit_forward and jit_other (<1 ms each)": 100e-9,
        "host between jit_other and jit_forward (<1 ms each)": 250e-9,
    })
    ops = dict(trace_reduce.top_ops(TOY))
    assert ops == pytest.approx({"fusion.1": 350e-9, "fusion.2": 200e-9})


@pytest.mark.parametrize("raw, want", [
    ("jit_forward(10746848674645932515)", "jit_forward"),
    ("%fusion.36 = bf16[1024,71,71,192]{0,3,2,1:T(8,128)(2,1)} fusion(bf16[3,3,80,192]{3} %c)",
     "fusion.36 bf16[1024,71,71,192]"),
    ("%copy-start = (bf16[8,3]{1,0}, u32[]) copy-start(%x)", "copy-start bf16[8,3]"),
    ("%fusion.1", "fusion.1"),
])
def test_names_are_cut_to_what_a_reader_needs(raw, want):
    assert trace_reduce._base_name(raw) == want


def test_recorded_trace_reduces():
    with open(os.path.join(HERE, "recorded_trace.json")) as fh:
        recorded = json.load(fh)
    events = [tuple(e) for e in recorded["events"]]
    got = trace_reduce.reduce(events)
    want = recorded["expected"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0.0 < got["busy_s"] < got["window_s"]
    assert set(got["programs"]) == set(want["programs"])
    for name, rec in want["programs"].items():
        assert got["programs"][name]["whole_runs"] == rec["whole_runs"]
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    assert got["idle_gaps"][0][0] == want["longest_gap"]


def test_inception_flops_against_xlas_count_of_the_plain_reference():
    """The count from shapes against XLA's count of the plain reference
    (never of the program under test) at a tiny size."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import inception_v3 as ref

    hw = (75, 75)
    convs = ref.conv_shapes(hw)
    params = [
        (jax.ShapeDtypeStruct((kh, kw, ci, co), jnp.float32),)
        + (jax.ShapeDtypeStruct((co,), jnp.float32),) * 3
        for kh, kw, ci, co, _, _ in convs
    ]
    x = jax.ShapeDtypeStruct((2, hw[0], hw[1], 3), jnp.float32)
    compiled = jax.jit(
        lambda p, x: ref.network(ref.Arrays(p), x)).lower(params, x).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    counted = 2 * flops.conv_flops(convs)  # two images
    # XLA leaves out the taps that fall on padding (a large share on 75x75
    # maps of 1x1 to 3x3 cells, 3% at 299x299) and adds the normalisation,
    # pooling and relu: its count stays within that share of ours
    assert 0.75 * counted <= cost["flops"] <= 1.05 * counted
    assert flops.inception_v3_forward()["flops"] == pytest.approx(11.42e9, rel=2e-3)
    assert len(ref.conv_shapes()) == 94


def test_train_step_is_three_forwards():
    forward = {"flops": 10, "weight_elems": 3, "activation_elems": 5}
    step = flops.train_step(forward, batch=4, in_bytes_per_image=100)
    assert step["flops"] == 120
    assert step["bytes"] == 400 + 3 * 4 * 5 + 4 * 5 * 4 * 4
