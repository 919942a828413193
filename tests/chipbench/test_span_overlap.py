"""The readers of the device's timeline in the program's ring
(``chipbench/readers/span_overlap.py``, ``span_attr_ratio.py``) on a
hand-written span list; that the spans they read (``engine.device``,
``engine.transfer``: children of a partition's root on a thread of their
own) change nothing any other span metric reads; and that every metric of
theirs in ``BENCHMARK.json`` is found by ``harness.Cell``."""

import collections
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.readers import (  # noqa: E402
    span_attr_ratio, span_attr_sum, span_overlap, span_self_time)

Rec = collections.namedtuple(
    "Rec", "name span_id parent_id thread_id start_ns end_ns attributes")
MS = 1_000_000
MAIN, PACKER, WATCHER = 1, 2, 3
PART = {"span": "featurize.partition", "attr": "rows"}


def _pass(first_id, at_ms, rows=100, watched=True):
    """One pass of one partition of two batches, 100 ms long, in order of
    END times.  The host: the first pack 20 ms (wait 0..20), place 20..21,
    dispatch 21..22; the second batch's wait 22..24, place 24..25, dispatch
    25..26; fetches 26..50 and 50..80; postprocess 80..90; collect 92..100.
    The device, as the watcher saw it: the first batch arrives at 30 and is
    computed 30..50 (``a``), the second, there since 28, 50..78 (``b``); the
    second transfer's wait began after its dispatch (unobserved at 50)."""
    t = at_ms * MS
    part = first_id

    def rec(name, offset, parent, start_ms, end_ms, thread=MAIN, **attrs):
        return Rec(name, first_id + offset, parent, thread,
                   t + int(start_ms * MS), t + int(end_ms * MS), attrs)

    records = [
        rec("data.pack", 1, part, 0, 20, thread=PACKER, rows=rows // 2),
        rec("engine.load_wait", 2, part, 0, 20),
        rec("engine.place", 3, part, 20, 21, bytes=640),
        rec("engine.dispatch", 4, part, 21, 22, program="a"),
        rec("engine.starved", 5, part, -8, 22),
        rec("engine.load_wait", 6, part, 22, 24),
        rec("engine.place", 7, part, 24, 25, bytes=640),
        rec("engine.dispatch", 8, part, 25, 26, program="b"),
        rec("engine.transfer", 20, part, 20, 30, thread=WATCHER, bytes=640,
            observed=True),
        rec("engine.fetch_wait", 9, part, 26, 50, bytes=64),
        rec("engine.device", 21, part, 30, 50, thread=WATCHER, program="a",
            rows=rows // 2, queued_ms=8.0),
        rec("engine.transfer", 22, part, 24, 50, thread=WATCHER, bytes=640,
            observed=False),
        rec("engine.device", 23, part, 50, 78, thread=WATCHER, program="b",
            rows=rows // 2, queued_ms=24.0),
        rec("engine.fetch_wait", 10, part, 50, 80, bytes=64),
        rec("featurize.postprocess", 11, part, 80, 90, rows=rows, scored=3,
            spanned=8),
        rec("featurize.partition", 0, None, 0, 90, rows=rows, batches=2),
        rec("sql.collect", 12, None, 92, 100, rows=rows),
    ]
    if not watched:
        records = [r for r in records if r.thread_id != WATCHER]
    return records


def _ring(passes=10, watched=True):
    """A warm-up pass, then ``passes`` passes of 100 ms back to back: a
    window of ``passes / 10`` seconds that ends where the last span ends."""
    records = _pass(1000, -150, watched=watched)
    for i in range(passes):
        records += _pass(2000 + 100 * i, 100 * i, watched=watched)
    return records


@pytest.fixture
def program_ring(monkeypatch):
    from sparkdl_tpu.obs.trace import tracer

    monkeypatch.setattr(tracer, "recent", _ring)
    return {"wall_s": 1.0}


BOTH = ["engine.device", "engine.transfer"]


@pytest.mark.parametrize("args, want", [
    # the device computes 30..78 of every 100 ms
    ({"of": ["engine.device"], "per": "wall"}, 48.0),
    ({"of": ["engine.device"], "per": PART}, 0.48),
    # by program, through ``where`` on the spans' attributes
    ({"of": ["engine.device"], "where": {"program": "a"}, "per": PART}, 0.20),
    ({"of": ["engine.device"], "where": {"program": "b"}, "per": PART}, 0.28),
    ({"of": ["engine.device"], "where": {"program": "c"}, "per": PART}, 0.0),
    # a transfer with the device idle: 20..30; the second hid under ``a``
    ({"of": ["engine.transfer"], "minus": ["engine.device"], "per": PART},
     0.10),
    # the unobserved one alone: 24..50, of which ``a`` covers 30..50
    ({"of": ["engine.transfer"], "where": {"observed": False},
      "minus": ["engine.device"], "per": PART}, 0.06),
    # the host's spans while the device neither computed nor received: the
    # first wait 0..20 (the second, 22..24, lies under the first transfer)
    ({"of": ["engine.load_wait"], "minus": BOTH, "per": PART}, 0.20),
    # postprocess 80..90 and collect 92..100 find the device done at 78
    ({"of": ["featurize.postprocess"], "minus": BOTH, "per": PART}, 0.10),
    ({"of": ["sql.collect"], "minus": BOTH, "per": PART}, 0.08),
    # a fetch waits 26..80: all but 26..30 and 78..80 under the device or
    # the transfer
    ({"of": ["engine.fetch_wait"], "minus": BOTH, "per": PART}, 0.02),
    # unions: overlapping spans of ``of`` count once
    ({"of": BOTH, "per": "wall"}, 58.0),
    ({"of": ["engine.device"], "per": {"span": "no.such", "attr": "rows"}},
     None),
])
def test_overlap_reader(program_ring, args, want):
    got = span_overlap.read(program_ring, args)
    assert got is None if want is None else got == pytest.approx(want)


def test_overlap_is_clipped_at_the_covered_walls_edges(program_ring):
    # the covered wall runs 0..600 ms: the starved stretch backdated to
    # -8 ms is cut at 0, the seventh pass's (592..622) at 600; 22 ms a pass
    # less what lies under the first transfer (20..22)
    args = {"of": ["engine.starved"], "minus": BOTH, "per": "wall",
            "skip_tail_s": 0.35}
    assert span_overlap.read(program_ring, args) == pytest.approx(
        100.0 * (6 * 20 + 5 * 8 + 8) / 600)
    # a device span that reaches over the edge counts up to it
    records = _ring() + [
        Rec("engine.device", 1, 2000, WATCHER, 995 * MS, 1005 * MS, {})]
    records.sort(key=lambda r: r.end_ns)
    roots, lo, hi = span_self_time.cover(records[:-1], 1.0)
    assert span_overlap.overlap_ns(
        records, lo, hi, ["engine.device"]) == (480 + 5) * MS


def test_a_ring_without_the_devices_timeline_reads_as_nothing(monkeypatch):
    """The parent of the PR that added ``engine.device``: its ring has the
    host's spans alone, and every metric of this reader is left out."""
    from sparkdl_tpu.obs import trace
    from sparkdl_tpu.obs.trace import tracer

    monkeypatch.setattr(tracer, "recent", lambda: _ring(watched=False))
    for args in ({"of": ["engine.device"], "per": "wall"},
                 {"of": ["sql.collect"], "minus": BOTH, "per": PART}):
        assert span_overlap.read({"wall_s": 1.0}, args) is None
    monkeypatch.setattr(trace, "tracer", object())  # no ring at all
    assert span_overlap.read(
        {"wall_s": 1.0}, {"of": ["engine.device"], "per": "wall"}) is None
    assert span_attr_ratio.read(
        {"wall_s": 1.0},
        {"spans": ["featurize.postprocess"], "attr": "a", "over": "b"}) is None


def test_attr_ratio_reader(program_ring):
    args = {"spans": ["featurize.postprocess"], "attr": "scored",
            "over": "spanned"}
    assert span_attr_ratio.read(program_ring, args) == 3 / 8
    assert span_attr_ratio.read(
        program_ring, dict(args, skip_tail_s=0.35)) == 3 / 8
    # spans that do not carry the attributes (an older program's)
    assert span_attr_ratio.read(
        program_ring, dict(args, over="never_written")) is None
    assert span_attr_ratio.read(
        program_ring, dict(args, spans=["no.such"])) is None


# ----------------------------------------------------------------------
# what the benchmark had reads what it read
# ----------------------------------------------------------------------
MANIFEST = harness.load_manifest(ROOT)


def _spec(name):
    with open(os.path.join(ROOT, "chipbench", "metrics", name + ".json")) as fh:
        return json.load(fh)


OLD_READERS = {"span_self_time": span_self_time, "span_attr_sum": span_attr_sum}
NEW_READERS = {"span_overlap", "span_attr_ratio"}
OLD_SPAN_METRICS = sorted(
    e["name"] for e in MANIFEST["per_layer"]
    if _spec(e["name"])["reader"] in OLD_READERS)
#: every metric file of the two readers; those of the generate cells wait
#: for a ``benchmark`` PR to list them (the cells' own tests pin their sets)
NEW_FILES = sorted(
    name[:-len(".json")]
    for name in os.listdir(os.path.join(ROOT, "chipbench", "metrics"))
    if _spec(name[:-len(".json")])["reader"] in NEW_READERS)
NEW_METRICS = [e for e in MANIFEST["per_layer"] if e["name"] in NEW_FILES]
PARTITION_OF = {
    "featurize": "featurize.partition", "generate": "generate.partition",
    "ar_generate": "ar_generate.partition",
    "kda_generate": "ar_generate.partition"}


def test_cover_picks_the_same_roots_with_and_without_the_new_spans():
    for tail in (0.0, 0.35):
        with_roots, *with_wall = span_self_time.cover(_ring(), 1.0, tail)
        roots, *wall = span_self_time.cover(_ring(watched=False), 1.0, tail)
        assert with_wall == wall and with_roots == roots
        assert not [r for r in with_roots if r.name in BOTH]


def _as_featurize(spec):
    """The metric file's arguments over the hand-made ring, whose spans
    have the featurize path's names: another path's root is put in terms
    of it, and the tail is one the ring has."""
    args = json.loads(json.dumps(spec["args"]))
    args["skip_tail_s"] = 0.35
    if isinstance(args.get("per"), dict):
        args["per"] = PART
    return args


@pytest.mark.parametrize("name", OLD_SPAN_METRICS)
def test_every_span_metric_reads_the_same_with_and_without_the_new_spans(
        name, monkeypatch):
    from sparkdl_tpu.obs.trace import tracer

    spec = _spec(name)
    reader, args = OLD_READERS[spec["reader"]], _as_featurize(spec)
    values = []
    for watched in (True, False):
        monkeypatch.setattr(
            tracer, "recent", lambda watched=watched: _ring(watched=watched))
        values.append(reader.read({"wall_s": 1.0}, args))
    assert values[0] == values[1]
    if any(span.startswith(("engine.", "featurize.", "sql."))
           for span in args["spans"]):
        assert values[0] is not None and values[0] > 0


def test_the_files_are_seventeen_and_the_manifest_lists_the_featurize_five():
    assert len(NEW_FILES) == 17 and len(OLD_SPAN_METRICS) >= 18
    assert [e["name"] for e in NEW_METRICS] == [
        "device_busy_share.featurize",
        "transfer_exposed_ms_per_image.featurize",
        "gap_under_load_wait_ms_per_image.featurize",
        "gap_under_postprocess_ms_per_image.featurize",
        "gap_under_collect_ms_per_image.featurize"]
    assert MANIFEST["per_layer"][-5:] == NEW_METRICS  # appended, in order
    for entry in NEW_METRICS:
        assert entry["source"] == "program_span"
        assert entry["moves"] == "images_per_s"
        assert entry["workloads"] == ["featurize-cached", "featurize-files"]
    layers = {e["layer"] for e in MANIFEST["per_layer"]
              if e not in NEW_METRICS}
    assert {e["layer"] for e in NEW_METRICS} <= layers  # no new layer name
    # a rehearsal on the CPU may print none of these under a device
    # metric's name (tests/chipbench/test_rehearse.py)
    assert not [n for n in NEW_FILES
                if "mfu" in n or "roofline" in n or "idle" in n]


@pytest.mark.parametrize("name", NEW_FILES)
def test_every_new_metric_file_names_its_reader_and_its_paths_spans(name):
    spec = _spec(name)
    args, suffix = spec["args"], name.rsplit(".", 1)[1]
    assert spec["reader"] in NEW_READERS
    assert args["skip_tail_s"] == (18.0 if suffix == "featurize" else 15.0)
    if spec["reader"] == "span_overlap":
        assert "engine.device" in args["of"] + args.get("minus", [])
        assert args["per"] == "wall" or args["per"] == {
            "span": PARTITION_OF[suffix], "attr": "rows"}
    else:
        assert set(args) == {"spans", "attr", "over", "skip_tail_s"}


@pytest.mark.parametrize("entry", NEW_METRICS, ids=lambda e: e["name"])
def test_every_new_entry_is_found_through_the_cell(entry):
    for workload in entry["workloads"]:
        cell = harness.Cell(workload, ROOT)
        (found,) = [(args, reader) for m, args, reader in cell.per_layer
                    if m["name"] == entry["name"]]
        args, reader = found
        assert reader is span_overlap
        assert args == _spec(entry["name"])["args"]
        # the tail the cell's other span metrics skip
        others = {a["skip_tail_s"] for m, a, r in cell.per_layer
                  if r is span_self_time}
        assert {args["skip_tail_s"]} == others
