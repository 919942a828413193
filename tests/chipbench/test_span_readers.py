"""The readers of the program's boundary spans (``chipbench/readers/
span_self_time.py``, ``span_attr_sum.py``) on a hand-written span list, and
through ``run.py --rehearse --trace 1`` of both featurize cells."""

import collections
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.readers import span_attr_sum, span_self_time  # noqa: E402

Rec = collections.namedtuple(
    "Rec", "name span_id parent_id thread_id start_ns end_ns attributes")
MS = 1_000_000
MAIN, WORKER = 1, 2


def _pass(first_id, at_ms, rows=100):
    """One pass of one partition, 100 ms long, as the program records it (in
    order of END times): plan 2 ms, the wait for a chunk 22 ms with a child
    of 20 ms on the WORKER's thread (the pack), place 5 ms with a child of
    1 ms on its own thread, dispatch 1 ms, a starved interval backdated to
    4 ms before the partition, fetch 50 ms, postprocess 10 ms; then collect
    8 ms."""
    t = at_ms * MS
    part = first_id

    def rec(name, offset, parent, start_ms, end_ms, thread=MAIN, **attrs):
        return Rec(name, first_id + offset, parent, thread,
                   t + int(start_ms * MS), t + int(end_ms * MS), attrs)

    return [
        rec("featurize.plan", 1, part, 0, 2, rows=rows),
        rec("data.pack", 2, first_id + 3, 2, 22, thread=WORKER, rows=rows,
            padded_rows=128, bytes=128 * 10),
        rec("engine.load_wait", 3, part, 2, 24),
        rec("inner.copy", 4, first_id + 5, 25, 26),
        rec("engine.place", 5, part, 24, 29, bytes=128 * 10),
        rec("engine.dispatch", 6, part, 29, 30, program="p"),
        rec("engine.starved", 7, part, -4, 30),
        rec("engine.fetch_wait", 8, part, 30, 80, bytes=128 * 4),
        rec("featurize.postprocess", 9, part, 80, 90, rows=rows),
        rec("featurize.partition", 0, None, 0, 90, rows=rows, batches=1),
        rec("sql.collect", 10, None, 92, 100, rows=rows),
    ]


def _ring(passes=10):
    """A warm-up pass, then ``passes`` passes of 100 ms back to back: a
    window of ``passes / 10`` seconds that ends where the last span ends."""
    records = _pass(1000, -150)
    for i in range(passes):
        records += _pass(2000 + 100 * i, 100 * i)
    return records


def test_cover_takes_whole_roots_of_the_window_less_the_tail():
    records = _ring()
    roots, lo, hi = span_self_time.cover(records, wall_s=1.0)
    assert len(roots) == 20 and (lo, hi) == (0, 1000 * MS)  # no warm-up
    roots, lo, hi = span_self_time.cover(records, 1.0, skip_tail_s=0.35)
    # the tail begins at 650 ms: the partition that ends at 690 is cut, the
    # collect before it (592..600) is the last whole root
    assert (lo, hi) == (0, 600 * MS)
    assert [r.name for r in roots].count("featurize.partition") == 6
    assert span_self_time.units(
        roots, {"span": "featurize.partition", "attr": "rows"}) == 600
    # a window that starts inside a pass counts only the roots whole in it
    roots, lo, hi = span_self_time.cover(records, wall_s=0.95)
    assert lo == 92 * MS and roots[0].name == "sql.collect"
    assert span_self_time.cover([], 1.0) is None
    assert span_self_time.cover(records, 0.0) is None


def test_tail_longer_than_the_window_counts_the_tail_too(capsys):
    roots, lo, hi = span_self_time.cover(_ring(), 1.0, skip_tail_s=8.0)
    assert len(roots) == 20 and (lo, hi) == (0, 1000 * MS)
    assert "the tail is counted too" in capsys.readouterr().err


def test_ring_that_does_not_reach_the_windows_start(capsys):
    records = [r for r in _ring() if r.end_ns > 330 * MS]  # the oldest fell out
    roots, lo, hi = span_self_time.cover(records, wall_s=1.0)
    # the oldest record left ends at 380 ms (a fetch): the partition around
    # it has lost children and is not counted; the next whole root is
    assert lo == 392 * MS and hi == 1000 * MS
    assert all(r.start_ns >= 380 * MS for r in roots)
    assert "does not reach back" in capsys.readouterr().err


@pytest.fixture
def program_ring(monkeypatch):
    from sparkdl_tpu.obs.trace import tracer

    monkeypatch.setattr(tracer, "recent", _ring)
    return {"wall_s": 1.0}


PART = {"span": "featurize.partition", "attr": "rows"}


@pytest.mark.parametrize("args, want", [
    # 34 ms a pass of 100 ms, the 4 ms before the first partition clipped
    ({"spans": ["engine.starved"], "per": "wall"}, 100.0 * (340 - 4) / 1000),
    ({"spans": ["engine.fetch_wait"], "per": PART}, 0.5),
    # place 5 ms less its same-thread child of 1 ms, plus dispatch 1 ms
    ({"spans": ["engine.place", "engine.dispatch"], "per": PART}, 0.05),
    # the pack's 20 ms ran on the worker's thread: the wait keeps them
    ({"spans": ["engine.load_wait"], "per": PART}, 0.22),
    ({"spans": ["featurize.postprocess"], "per": PART}, 0.1),
    ({"spans": ["sql.collect"],
      "per": {"span": "sql.collect", "attr": "rows"}}, 0.08),
    # its children on its own thread cover the partition whole
    ({"spans": ["featurize.partition"], "per": PART}, 0.0),
    ({"spans": ["image.decode"],
      "per": {"span": "image.decode", "attr": "rows"}}, None),
])
def test_self_time_reader(program_ring, args, want):
    got = span_self_time.read(program_ring, args)
    assert got is None if want is None else got == pytest.approx(want)


def test_self_time_reader_skips_the_tail(program_ring):
    args = {"spans": ["engine.starved"], "per": "wall", "skip_tail_s": 0.35}
    # six passes' intervals over 600 ms, the first clipped at the covered
    # wall's start and the seventh's (596..630) at its end
    assert span_self_time.read(program_ring, args) == pytest.approx(
        100.0 * (30 + 5 * 34 + 4) / 600)


def test_attr_reader(program_ring):
    args = {"spans": ["engine.place"], "attr": "bytes", "per": PART}
    assert span_attr_sum.read(program_ring, args) == 12.8  # 128 padded rows
    assert span_attr_sum.read(
        program_ring, dict(args, skip_tail_s=0.35)) == 12.8


def test_a_program_without_the_ring_reads_as_nothing(monkeypatch):
    from sparkdl_tpu.obs import trace

    monkeypatch.setattr(trace, "tracer", object())  # the parent's tracer
    args = {"spans": ["engine.place"], "attr": "bytes", "per": PART}
    assert span_attr_sum.read({"wall_s": 1.0}, args) is None
    assert span_self_time.read(
        {"wall_s": 1.0}, {"spans": ["engine.starved"], "per": "wall"}) is None


# ----------------------------------------------------------------------
# through the command
# ----------------------------------------------------------------------
MANIFEST = harness.load_manifest(ROOT)
SPAN_READERS = {"span_self_time", "span_attr_sum"}


def _span_metrics(cell):
    names = []
    for entry in MANIFEST["per_layer"]:
        with open(os.path.join(
                ROOT, "chipbench", "metrics", entry["name"] + ".json")) as fh:
            spec = json.load(fh)
        if spec["reader"] in SPAN_READERS and cell in entry["workloads"]:
            names.append(entry["name"])
    return names


@pytest.mark.parametrize("cell, count, h2d", [
    ("featurize-cached", 7, 75 * 75 * 3),  # uint8, batch 8 divides the 16 rows
    ("featurize-files", 9, None),
])
def test_traced_rehearsal_prints_every_span_metric(cell, count, h2d):
    env = dict(os.environ, JAX_PLATFORMS="cpu", KERAS_BACKEND="jax",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         str(2**31 + 4321), "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, timeout=600, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    names = _span_metrics(cell)
    assert len(names) == count
    for name in names:
        value = line["metrics"][name]["value"]
        assert isinstance(value, float) and value >= 0, name
    if h2d is not None:
        assert line["metrics"]["h2d_bytes_per_image.featurize"]["value"] == h2d
    starved = line["metrics"]["engine_starved_share.featurize"]["value"]
    assert 0 < starved < 100
    # a window of 1 s is shorter than the tail the metric files skip
    assert "the tail is counted too" in proc.stderr
