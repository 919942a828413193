"""Layer-boundary spans (``Tracer.boundary``): ordinary spans that are
recorded whether or not the tracer is enabled, kept in one bounded ring
(``tracer.recent()``) and mirrored into the profiler as ``sparkdl.*``
annotations; and the engine's account of a device with nothing to do
(``engine.starved``)."""

import collections
import subprocess
import sys
import threading

import numpy as np
import pytest

from sparkdl_tpu.engine import DispatchWindow, executor
from sparkdl_tpu.obs import JsonlTraceSink, tracer
from sparkdl_tpu.obs import trace as trace_mod
from sparkdl_tpu.obs.trace import BOUNDARY_RING_SIZE, Tracer


@pytest.fixture(autouse=True)
def tracing_off_and_an_empty_ring(monkeypatch):
    tracer.disable()
    monkeypatch.setattr(
        tracer, "_ring", collections.deque(maxlen=BOUNDARY_RING_SIZE))
    yield
    tracer.disable()


def since(mark):
    """The global ring's records that started after ``mark``."""
    return [r for r in tracer.recent() if r.start_ns >= mark]


# ----------------------------------------------------------------------
# the primitive
# ----------------------------------------------------------------------
def test_recorded_while_disabled_and_not_delivered_to_sinks():
    sink = JsonlTraceSink(capacity=16)
    tracer.add_sink(sink)  # a sink alone does not enable
    assert not tracer.enabled
    mark = tracer.clock_ns()
    with tracer.boundary("layer.work", rows=3) as span:
        assert tracer.current() is span
        assert tracer.capture() is None  # disabled: nothing to propagate
    assert tracer.current() is None
    (rec,) = since(mark)
    assert rec.name == "layer.work" and rec.attributes == {"rows": 3}
    assert rec.span_id == span.span_id and rec.parent_id is None
    assert rec.thread_id == threading.get_ident()
    assert rec.end_ns > rec.start_ns >= mark
    assert sink.spans() == []
    # per-item spans stay behind ``enabled``
    with tracer.span("item") as item:
        assert item is None
    assert len(since(mark)) == 1


def test_delivered_to_sinks_when_enabled_and_nests_with_ordinary_spans():
    sink = JsonlTraceSink(capacity=16)
    tracer.enable(sink)
    mark = tracer.clock_ns()
    with tracer.span("request") as outer:
        with tracer.boundary("layer.work") as mid:
            with tracer.span("inner") as inner:
                pass
    assert mid.parent_id == outer.span_id and mid.trace_id == outer.trace_id
    assert inner.parent_id == mid.span_id
    assert [s["name"] for s in sink.spans()] == [
        "inner", "layer.work", "request"]
    assert [r.name for r in since(mark)] == ["layer.work"]  # ring: boundaries only


def test_sampled_out_of_the_sinks_but_never_out_of_the_ring():
    sink = JsonlTraceSink(capacity=16)
    tracer.enable(sink)
    tracer.configure_sampling(0.0)
    mark = tracer.clock_ns()
    with tracer.boundary("layer.work"):
        pass
    assert sink.spans() == []
    assert [r.name for r in since(mark)] == ["layer.work"]


def test_nesting_explicit_cross_thread_parent_and_same_thread_self_time():
    own = Tracer()
    ticks = iter(range(0, 10_000, 10))
    own.clock_ns = lambda: next(ticks)
    seen = {}

    def pack(parent):
        seen["inherited"] = own.current()  # a new thread has no context
        with own.boundary("data.pack", parent=parent):
            pass

    with own.boundary("partition") as part:       # 0 ..
        with own.boundary("plan"):                # 10 .. 20
            pass
        worker = threading.Thread(target=pack, args=(part,))
        worker.start()                            # 30 .. 40 on the worker
        worker.join(timeout=10)
        assert not worker.is_alive()
        with own.boundary("dispatch"):            # 50 .. 60
            pass
    recs = {r.name: r for r in own.recent()}      # partition ends at 70
    assert seen["inherited"] is None
    assert [r.name for r in own.recent()] == [
        "plan", "data.pack", "dispatch", "partition"]  # order of END times
    for child in ("plan", "data.pack", "dispatch"):
        assert recs[child].parent_id == recs["partition"].span_id
    assert recs["data.pack"].thread_id != recs["partition"].thread_id
    assert recs["plan"].thread_id == recs["partition"].thread_id
    # self time: duration less the children on the SAME thread; the pack
    # ran beside the partition's thread and takes nothing from it
    part_rec = recs["partition"]
    same_thread = sum(
        r.end_ns - r.start_ns for r in own.recent()
        if r.parent_id == part_rec.span_id
        and r.thread_id == part_rec.thread_id)
    assert part_rec.end_ns - part_rec.start_ns == 70
    assert (part_rec.end_ns - part_rec.start_ns) - same_thread == 50


def test_backdated_start():
    own = Tracer()
    own.clock_ns = lambda: 5_000
    with own.boundary("engine.starved", start_ns=1_000) as span:
        pass
    (rec,) = own.recent()
    assert (rec.start_ns, rec.end_ns) == (1_000, 5_000)
    assert span.duration_ms == pytest.approx(0.004)


def test_ring_is_bounded_and_recent_is_a_snapshot():
    own = Tracer()
    for i in range(BOUNDARY_RING_SIZE + 10):
        with own.boundary("b", i=i):
            pass
    snap = own.recent()
    assert len(snap) == BOUNDARY_RING_SIZE
    assert snap[0].attributes == {"i": 10}  # the oldest ten fell out
    with own.boundary("later"):
        pass
    assert len(snap) == BOUNDARY_RING_SIZE and snap[-1].name == "b"
    assert own.recent()[-1].name == "later"


def test_obs_trace_alone_does_not_import_jax():
    code = (
        "import sys\n"
        "from sparkdl_tpu.obs.trace import tracer\n"
        "with tracer.boundary('layer.work'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'a boundary span imported jax'\n"
        "print(len(tracer.recent()))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "1"


def test_open_boundary_is_a_profiler_annotation(monkeypatch):
    import jax

    log = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name))

        def __exit__(self, *exc):
            log.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with tracer.boundary("engine.dispatch"):
        log.append("body")
    # a backdated span cannot be annotated
    with tracer.boundary("engine.starved", start_ns=tracer.clock_ns() - 10):
        pass
    assert log == [("enter", "sparkdl.engine.dispatch"), "body",
                   ("exit", "sparkdl.engine.dispatch")]
    assert trace_mod.ANNOTATION_PREFIX == "sparkdl."


# ----------------------------------------------------------------------
# engine.starved
# ----------------------------------------------------------------------
@pytest.fixture
def scripted(monkeypatch):
    """A fresh process-wide count and a clock that ticks 1 µs a reading and
    moves on by hand."""
    now = [0]

    def clock():
        now[0] += 1_000
        return now[0]

    monkeypatch.setattr(executor, "_outstanding", executor._Outstanding())
    monkeypatch.setattr(tracer, "clock_ns", clock)
    return now


def starved(mark=0):
    return [r for r in since(mark) if r.name == "engine.starved"]


def test_starved_none_before_first_submit_then_one_per_emptying(scripted):
    now = scripted
    window = DispatchWindow(depth=1)
    now[0] = 1_000_000
    assert window.submit(np.zeros(4), meta=0) == []
    assert starved() == []  # nothing before the process's first submit
    (host, meta), = window.submit(np.ones(4), meta=1)  # count 2 -> 1
    assert meta == 0 and starved() == []
    list(window.drain())  # count 0: the clock is stamped
    last_fetch = [r for r in since(0) if r.name == "engine.fetch_wait"][-1]
    assert last_fetch.attributes == {"bytes": 32}
    now[0] += 5_000_000  # the host does something else
    window.submit(np.zeros(4), meta=2)
    (gap,) = starved()
    assert gap.start_ns == last_fetch.end_ns + 1_000  # the very next reading
    assert 5_000_000 < gap.end_ns - gap.start_ns < 5_010_000
    window.submit(np.zeros(4), meta=3)  # not empty: no new interval
    assert len(starved()) == 1
    window.abandon()  # dropping the rest empties the count too
    now[0] += 2_000_000
    window.submit(np.zeros(4), meta=4)
    assert len(starved()) == 2
    assert starved()[-1].end_ns - starved()[-1].start_ns > 2_000_000
    list(window.drain())


def test_starved_two_windows_share_the_count(scripted):
    now = scripted
    first, second = DispatchWindow(depth=2), DispatchWindow(depth=2)
    first.submit(np.zeros(2))
    second.submit(np.zeros(2))
    list(first.drain())  # the second window still has a result out
    now[0] += 1_000_000
    first.submit(np.zeros(2))
    assert starved() == []
    list(first.drain())
    list(second.drain())  # now the process has nothing dispatched
    now[0] += 3_000_000
    second.submit(np.zeros(2))
    (gap,) = starved()
    assert gap.end_ns - gap.start_ns > 3_000_000
    list(second.drain())


def test_a_failed_fetch_still_counts_as_fetched(scripted, monkeypatch):
    def boom(result):
        raise RuntimeError("fetch failed")

    monkeypatch.setattr(executor, "_fetch_host", boom)
    delivering = DispatchWindow(depth=0, capture_errors=True)
    (failure, meta), = delivering.submit(np.zeros(2), meta="m")
    assert isinstance(failure, executor.FetchFailure) and meta == "m"
    assert executor._outstanding._count == 0
    raising = DispatchWindow(depth=0)
    with pytest.raises(RuntimeError):
        raising.submit(np.zeros(2))
    assert executor._outstanding._count == 0
    assert len(starved()) == 1  # between the two submits


# ----------------------------------------------------------------------
# the spans of the batched loop
# ----------------------------------------------------------------------
def test_place_bytes_are_exact_for_a_padded_last_batch():
    import jax.numpy as jnp

    from sparkdl_tpu.transformers.utils import run_batched_rows

    rows = [np.full((5, 3), i, np.float32) for i in range(20)]
    mark = tracer.clock_ns()
    with tracer.boundary("featurize.partition", rows=len(rows)) as part:
        out = run_batched_rows(
            lambda x: jnp.sum(x, axis=(1, 2)), rows, np.stack, batch_size=8)
    np.testing.assert_allclose(out, [15.0 * i for i in range(20)])
    recs = since(mark)
    by_name = {}
    for r in recs:
        by_name.setdefault(r.name, []).append(r)
    row_bytes = 5 * 3 * 4
    # 20 rows in batches of 8: the last one holds 4 rows padded to 8, and
    # all 8 rows' bytes go to the device
    assert [r.attributes["bytes"] for r in by_name["engine.place"]] == [
        8 * row_bytes] * 3
    packs = sorted(by_name["data.pack"], key=lambda r: r.start_ns)
    assert [p.attributes["rows"] for p in packs] == [8, 8, 4]
    assert [p.attributes["padded_rows"] for p in packs] == [8, 8, 8]
    assert all(p.parent_id == part.span_id for p in packs)
    assert all(p.thread_id != threading.get_ident() for p in packs)
    assert len(by_name["engine.dispatch"]) == 3
    assert len(by_name["engine.load_wait"]) == 4  # the last finds the end
    assert sum(r.attributes["bytes"] for r in by_name["engine.fetch_wait"]) \
        == 3 * 8 * 4
    (partition,) = by_name["featurize.partition"]
    assert partition.attributes == {"rows": 20, "batches": 3}


def test_two_inputs_and_two_outputs_ride_the_one_loop():
    """``run_batched_multi`` is a call of the one loop: a batch is the
    tuple of its arrays' row chunks, packed on the prefetch thread, padded
    and placed together (``engine.place``'s ``bytes`` is their sum), and
    the ragged last chunk is sliced back in every output."""
    from sparkdl_tpu.transformers.utils import run_batched_multi

    a = np.arange(20 * 3, dtype=np.float32).reshape(20, 3)
    b = np.arange(20 * 4, dtype=np.int32).reshape(20, 2, 2)
    mark = tracer.clock_ns()
    with tracer.boundary("caller") as caller:
        total, doubled = run_batched_multi(
            lambda x, y: (x.sum(axis=1) + y.sum(axis=(1, 2)), y * 2),
            [a, b], batch_size=8)
    np.testing.assert_array_equal(total, a.sum(axis=1) + b.sum(axis=(1, 2)))
    np.testing.assert_array_equal(doubled, b * 2)
    assert doubled.dtype == np.int32
    by_name = {}
    for r in since(mark):
        by_name.setdefault(r.name, []).append(r)
    in_row, out_row = 3 * 4 + 4 * 4, 4 + 4 * 4
    assert [r.attributes["bytes"] for r in by_name["engine.place"]] == [
        8 * in_row] * 3
    packs = sorted(by_name["data.pack"], key=lambda r: r.start_ns)
    assert [(p.attributes["rows"], p.attributes["padded_rows"],
             p.attributes["bytes"]) for p in packs] == [
        (8, 8, 8 * in_row), (8, 8, 8 * in_row), (4, 8, 8 * in_row)]
    assert all(p.parent_id == caller.span_id for p in packs)
    assert all(p.thread_id != threading.get_ident() for p in packs)
    assert [r.attributes["program"] for r in by_name["engine.dispatch"]] == [
        "<lambda>"] * 3
    assert sum(r.attributes["bytes"] for r in by_name["engine.fetch_wait"]) \
        == 3 * 8 * out_row
    (root,) = by_name["caller"]
    assert root.attributes == {"batches": 3}


def test_one_array_in_must_give_one_array_out(monkeypatch):
    from sparkdl_tpu.transformers.utils import run_batched_rows

    monkeypatch.setattr(executor, "_outstanding", executor._Outstanding())
    rows = [np.full((2,), i, np.float32) for i in range(20)]
    with pytest.raises(TypeError, match="single-output fn"):
        run_batched_rows(lambda x: (x, x), rows, np.stack, batch_size=8)
    assert executor._outstanding._count == 0  # nothing left dispatched


def test_start_boundary_is_a_root_ended_by_hand_and_never_current():
    own = Tracer()
    with own.boundary("outer") as outer:
        first = own.start_boundary("partition", rows=3)
        second = own.start_boundary("partition", rows=4)  # both open at once
        assert own.current() is outer
        with own.boundary("child", parent=first):
            pass
        first.end()
        second.end()
        second.end()  # idempotent
    recs = own.recent()
    assert [r.name for r in recs] == ["child", "partition", "partition", "outer"]
    assert [r.parent_id for r in recs] == [first.span_id, None, None, None]
    assert [r.attributes for r in recs[1:3]] == [{"rows": 3}, {"rows": 4}]


@pytest.fixture
def three_partition_transform(monkeypatch):
    """A ``DeepImageFeaturizer.transform`` over partitions of 20, 7 and 12
    uint8 rows in batches of 8, with a small program in the model's place:
    ``(rows, transform)``."""
    import types

    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.sql.session import TPUSession
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer
    from sparkdl_tpu.transformers.utils import cast_and_resize_on_device

    monkeypatch.setattr(executor, "_outstanding", executor._Outstanding())
    rng = np.random.RandomState(1)
    sizes = [20, 7, 12]
    images = [
        imageIO.imageArrayToStruct(
            rng.randint(0, 255, (12, 12, 3)).astype(np.uint8), origin=f"o{i}")
        for i in range(sum(sizes))
    ]
    session = TPUSession.builder.master("local[*]").getOrCreate()
    df = session.createDataFrame([(im,) for im in images], ["image"],
                                 numPartitions=1)
    parts, lo = [], 0
    for n in sizes:
        parts.append({"image": images[lo:lo + n]})
        lo += n
    df = df._with_partitions(parts)

    @jax.jit
    def forward(x):
        x = cast_and_resize_on_device(x, (12, 12))
        return jnp.tanh(x / 255.0).mean(axis=(1, 2))

    featurizer = DeepImageFeaturizer(
        inputCol="image", outputCol="features", modelName="InceptionV3",
        batchSize=8)
    monkeypatch.setattr(
        featurizer, "_build_forward",
        lambda: (forward, types.SimpleNamespace(input_size=(12, 12))))
    want = np.asarray(forward(np.stack(
        [imageIO.imageStructToArray(im) for im in images])))
    return sizes, want, lambda: featurizer.transform(df)


def test_the_device_is_fed_across_partition_borders(three_partition_transform):
    """The mechanism, from the ring: one window over all partitions of a
    ``transform``, so the next partition's first batches are dispatched
    before this one's rows are built."""
    from sparkdl_tpu.utils.metrics import metrics

    sizes, want, transform = three_partition_transform
    depth = executor.DEFAULT_DEPTH
    assert depth == 2
    borders = metrics.counter("engine.borders").value
    fed = metrics.counter("engine.borders_fed").value
    mark = tracer.clock_ns()
    out = transform()
    recs = since(mark)
    assert [len(p["features"]) for p in out._partitions] == sizes
    got = np.stack([np.asarray(r.features.toArray()) for r in out.collect()])
    np.testing.assert_array_equal(got, want.astype(np.float64))

    partitions = sorted((r for r in recs if r.name == "featurize.partition"),
                        key=lambda r: r.start_ns)
    assert [r.attributes for r in partitions] == [
        {"rows": n, "batch_size": 8, "batches": -(-n // 8)} for n in sizes]
    assert all(r.parent_id is None for r in partitions)
    ids = [r.span_id for r in partitions]

    def children(name, partition):
        return sorted((r for r in recs
                       if r.name == name and r.parent_id == ids[partition]),
                      key=lambda r: r.start_ns)

    for name, count in [("featurize.plan", [1, 1, 1]),
                        ("data.pack", [3, 1, 2]),
                        ("engine.load_wait", [3, 1, 3]),  # the last finds the end
                        ("engine.place", [3, 1, 2]),
                        ("engine.dispatch", [3, 1, 2]),
                        ("engine.fetch_wait", [3, 1, 2]),
                        ("featurize.postprocess", [1, 1, 1])]:
        assert [len(children(name, p)) for p in range(3)] == count, name
        # and none of them anywhere else: not a root, not another's child
        assert sum(count) == len([r for r in recs if r.name == name]), name
    post = [children("featurize.postprocess", p)[0] for p in range(3)]
    assert [r.attributes for r in post] == [
        {"rows": 20, "inflight": depth},  # one batch of each later partition
        {"rows": 7, "inflight": depth},
        {"rows": 12, "inflight": 0}]
    for p in range(2):
        first_dispatch_of_next = children("engine.dispatch", p + 1)[0]
        assert first_dispatch_of_next.start_ns < post[p].start_ns
        assert partitions[p + 1].start_ns < partitions[p].end_ns  # overlap
    assert metrics.counter("engine.borders").value - borders == 2
    assert metrics.counter("engine.borders_fed").value - fed == 2
    dispatches = [r for r in recs if r.name == "engine.dispatch"]
    fetches = [r for r in recs if r.name == "engine.fetch_wait"]
    first, last = (min(r.start_ns for r in dispatches),
                   max(r.end_ns for r in fetches))
    assert not [r for r in starved(mark)
                if r.end_ns > first and r.start_ns < last]


def test_long_partitions_find_the_window_full_at_their_border(monkeypatch):
    """``inflight`` reads ``depth`` wherever the next partition has that
    many batches, and the benchmark's readers count every row once."""
    import os

    import jax.numpy as jnp

    from sparkdl_tpu.transformers.utils import run_batched_partitions

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(root)
    from chipbench.readers import span_self_time

    monkeypatch.setattr(executor, "_outstanding", executor._Outstanding())
    depth = executor.DEFAULT_DEPTH
    data = np.arange(200, dtype=np.float32).reshape(100, 2)
    partitions = [list(range(0, 30)), list(range(30, 64)), list(range(64, 100))]
    seen = []

    def finish(done):
        with tracer.boundary("featurize.postprocess", parent=done.span,
                             rows=len(done.result), inflight=done.inflight):
            seen.append((done.index, done.inflight, done.result))

    with tracer.boundary("sql.collect", rows=0):
        pass  # the ring reaches back beyond the window's start
    mark = tracer.clock_ns()
    run_batched_partitions(
        lambda x: jnp.asarray(x) + 1.0, partitions,
        lambda rows: (lambda chunk: data[np.asarray(chunk)]), finish, 8,
        span_name="featurize.partition")
    wall_s = (max(r.end_ns for r in tracer.recent()) - mark) / 1e9
    assert [(p, inflight) for p, inflight, _ in seen] == [
        (0, depth), (1, depth), (2, 0)]
    np.testing.assert_array_equal(
        np.concatenate([result for _, _, result in seen]), data + 1.0)
    roots, lo, hi = span_self_time.cover(tracer.recent(), wall_s)
    assert [r.name for r in roots] == ["featurize.partition"] * 3
    assert span_self_time.units(
        roots, {"span": "featurize.partition", "attr": "rows"}) == 100
    # self times as the readers take them: every child counted once
    waits = [r for r in since(mark) if r.name == "engine.load_wait"]
    assert span_self_time.self_ns(
        tracer.recent(), {"engine.load_wait"}, lo, hi) == sum(
            r.end_ns - r.start_ns for r in waits)


# ----------------------------------------------------------------------
# the spans of readImages, whose loops run on a pool of threads
# ----------------------------------------------------------------------
@pytest.mark.parametrize("files,partitions,width", [
    (24, 1, 4), (24, 3, 4), (24, 2, 1), (3, 1, None),
], ids=["pooled-1", "pooled-3", "inline-2", "three-files"])
def test_read_images_spans_are_roots_on_the_calling_thread(
        tmp_path, monkeypatch, files, partitions, width):
    """One ``image.read_files`` a call and one ``image.decode`` a partition,
    on the calling thread, with nothing of the workers' under them: their
    self time (what ``file_read/decode_ms_per_image.featurize`` read) is
    their whole duration.  ``workers`` says how wide the decode's pool was;
    the files are read inline."""
    from PIL import Image

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.sql.session import TPUSession

    rng = np.random.RandomState(0)
    for i in range(files):
        Image.fromarray(rng.randint(0, 255, (8 + i, 12, 3), dtype=np.uint8)).save(
            tmp_path / f"f{i:02d}.png")
    (tmp_path / "f01.png").write_bytes(b"corrupt")
    if width is not None:
        monkeypatch.setattr(imageIO, "_pool_width", lambda n_items: width)
    else:  # the real rule: three files are decoded inline
        width = 1
    session = TPUSession.builder.master("local[*]").getOrCreate()
    tracer.enable()  # worker threads would deliver their spans too
    sink = JsonlTraceSink(capacity=256)
    tracer.add_sink(sink)
    mark = tracer.clock_ns()
    try:
        df = imageIO.readImages(str(tmp_path), session, numPartitions=partitions)
    finally:
        tracer.remove_sink(sink)
    recs = since(mark)
    assert sorted(r.name for r in recs) == (
        ["image.decode"] * partitions + ["image.read_files"])
    assert all(r.parent_id is None for r in recs)
    assert all(r.thread_id == threading.get_ident() for r in recs)
    assert len(sink.spans()) == len(recs)  # and no span from a worker
    (read,) = [r for r in recs if r.name == "image.read_files"]
    on_disk = sum(p.stat().st_size for p in tmp_path.iterdir())
    assert read.attributes == {"files": files, "bytes": on_disk, "workers": 1}
    decodes = sorted((r for r in recs if r.name == "image.decode"),
                     key=lambda r: r.start_ns)
    assert sum(r.attributes["rows"] for r in decodes) == df.count() == files - 1
    assert [r.attributes["errors"] for r in decodes] == [1] + [0] * (partitions - 1)
    assert all(r.attributes["workers"] == width for r in decodes)
    assert all(set(r.attributes) == {"rows", "errors", "workers"} for r in decodes)
    # the reader's rule: self time = duration less same-thread children
    spans = {r.span_id for r in recs}
    assert not [r for r in tracer.recent() if r.parent_id in spans]


# ----------------------------------------------------------------------
# engine.device / engine.transfer: the completion watcher
# ----------------------------------------------------------------------
WATCHER = "sparkdl-completion-watcher"


def settled():
    assert executor._watcher.settle(timeout=30)


def watcher_threads():
    return [t for t in threading.enumerate() if t.name == WATCHER]


class Slow:
    """A dispatched result that becomes ready when the test says so."""

    def __init__(self, error=None, nbytes=8):
        self.ready, self.waited = threading.Event(), threading.Event()
        self.error, self.nbytes = error, nbytes

    def block_until_ready(self):
        self.waited.set()
        assert self.ready.wait(timeout=30)
        if self.error is not None:
            raise self.error

    def __array__(self, dtype=None, copy=None):
        self.block_until_ready()  # a fetch raises what the computation did
        return np.zeros(1)


def named(name, mark):
    return [r for r in since(mark) if r.name == name]


def test_one_device_span_a_watched_submit_in_submission_order():
    mark = tracer.clock_ns()
    window = DispatchWindow(depth=3)
    results = [Slow() for _ in range(3)]
    with tracer.boundary("featurize.partition", rows=12) as root:
        for i, result in enumerate(results):
            assert window.submit(
                result, meta=i, program="p", parent=root, rows=4 + i) == []
        assert results[0].waited.wait(timeout=30)
        assert not results[1].waited.is_set()  # one wait at a time, in order
        for result in reversed(results):  # ready in any order: spans in this
            result.ready.set()
        settled()
        assert [meta for _, meta in window.drain()] == [0, 1, 2]
    spans = named("engine.device", mark)
    assert [s.attributes["rows"] for s in spans] == [4, 5, 6]
    assert all(s.attributes["program"] == "p" for s in spans)
    assert all(s.attributes["queued_ms"] >= 0 for s in spans)
    for before, after in zip(spans, spans[1:]):
        assert before.start_ns <= before.end_ns <= after.start_ns
    # children of the partition's root, on the watcher's thread, never roots
    assert {s.parent_id for s in spans} == {root.span_id}
    thread = executor._watcher._thread  # the one the process has
    assert thread in watcher_threads()
    assert {s.thread_id for s in spans} == {thread.ident}
    assert thread.daemon and thread.ident != threading.get_ident()
    roots = [r for r in since(mark) if r.parent_id is None]
    assert [r.name for r in roots] == ["featurize.partition"]
    assert spans[-1].end_ns <= roots[0].end_ns
    ends = [r.end_ns for r in tracer.recent()]
    assert ends == sorted(ends)  # written as they end: the ring's order


def test_a_device_span_starts_at_the_latest_of_dispatch_input_and_the_one_before():
    mark = tracer.clock_ns()
    window = DispatchWindow(depth=2)
    batch, result = Slow(nbytes=64), Slow()
    with tracer.boundary("featurize.partition") as root:
        with tracer.boundary("engine.place") as placing:
            window.watch_transfer(batch, placing.start_ns, root, bytes=64)
        window.submit(result, program="p", parent=root, rows=1)
        assert batch.waited.wait(timeout=30)
        batch.ready.set()  # the input arrives ...
        assert result.waited.wait(timeout=30)
        arrived = tracer.clock_ns()
        result.ready.set()  # ... and only then can the device compute
        settled()
        list(window.drain())
    (transfer,) = named("engine.transfer", mark)
    (device,) = named("engine.device", mark)
    (place,) = named("engine.place", mark)
    assert transfer.attributes == {"bytes": 64, "observed": True}
    assert transfer.start_ns == place.start_ns
    assert transfer.parent_id == device.parent_id == root.span_id
    assert device.start_ns == transfer.end_ns <= arrived
    assert device.attributes["queued_ms"] > 0


def test_a_submit_without_a_program_starts_no_thread(monkeypatch):
    own = executor._CompletionWatcher()
    monkeypatch.setattr(executor, "_watcher", own)
    mark = tracer.clock_ns()
    window = DispatchWindow(depth=0)
    with tracer.boundary("caller") as caller:
        window.submit(np.zeros(2), meta="m")
        window.submit(np.zeros(2), program="p")  # nobody to hang it under
        window.watch_transfer(np.zeros(2), mark, None)
    assert own._thread is None and not own._entries
    assert {r.name for r in since(mark)} <= {
        "engine.fetch_wait", "engine.starved", "caller"}
    window.submit(np.zeros(2), program="p", parent=caller)
    assert own._thread is not None and own.settle(timeout=30)
    assert len(named("engine.device", mark)) == 1


def test_abandon_leaves_no_entry_no_late_span_and_no_second_thread():
    mark = tracer.clock_ns()
    window = DispatchWindow(depth=4)
    fetched, waited_for, queued = Slow(), Slow(), Slow()
    batch = Slow()
    with tracer.boundary("featurize.partition") as root:
        fetched.ready.set()
        window.submit(fetched, program="p", parent=root, rows=1)
        settled()
        threads = watcher_threads()
        window._pop()  # fetched: its span stays
        window.submit(waited_for, program="p", parent=root, rows=2)
        window.submit(queued, program="p", parent=root, rows=3)
        window.watch_transfer(batch, mark, root, bytes=8)  # never dispatched
        assert waited_for.waited.wait(timeout=30)
        window.abandon()
        assert not executor._watcher._entries  # nothing left in the queue
        assert len(window) == 0
        waited_for.ready.set()  # the wait under way returns: no span for it
        settled()
    assert not queued.waited.is_set() and not batch.waited.is_set()
    assert [s.attributes["rows"] for s in named("engine.device", mark)] == [1]
    assert named("engine.transfer", mark) == []
    assert watcher_threads() == threads and executor._watcher._thread.is_alive()


def test_a_failed_computation_raises_at_the_fetch_and_the_watcher_goes_on():
    mark = tracer.clock_ns()
    window = DispatchWindow(depth=1)
    failing, sound = Slow(error=RuntimeError("device fault")), Slow()
    with tracer.boundary("featurize.partition") as root:
        window.submit(failing, program="p", parent=root, rows=1)
        failing.ready.set()
        sound.ready.set()
        with pytest.raises(RuntimeError, match="device fault"):
            window.submit(sound, program="p", parent=root, rows=2)
        window.abandon()  # as every loop's ``finally`` does
        settled()
        again = DispatchWindow(depth=0)
        again.submit(np.ones(2), program="q", parent=root, rows=3)
        settled()
    spans = named("engine.device", mark)
    assert [(s.attributes["rows"], s.attributes.get("error"))
            for s in spans] == [(1, True), (3, None)]
    assert executor._watcher._thread.is_alive()


def _donating_partitions_run(span_name):
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.transformers.utils import run_batched_partitions

    program = jax.jit(lambda x: jnp.tanh(x) * 2.0, donate_argnums=0)
    parts = [[np.full((3, 5), 10 * p + i, np.float32) for i in range(n)]
             for p, n in enumerate([20, 7])]
    out = []
    run_batched_partitions(
        program, parts, lambda rows: np.stack,
        lambda done: out.append(done.result), 8, span_name=span_name)
    return out


def test_a_donated_input_neither_raises_nor_blocks_and_changes_no_result():
    """The featurizer donates its batch: whichever of the watcher's wait
    and the dispatch comes first, the loop's results are those of a run
    nobody watches (no span open: nothing to hang a span under)."""
    from sparkdl_tpu.utils.metrics import metrics

    unwatched_mark = tracer.clock_ns()
    unwatched = _donating_partitions_run(None)
    settled()
    assert named("engine.device", unwatched_mark) == []
    assert named("engine.transfer", unwatched_mark) == []
    unobserved = metrics.counter("engine.transfers_unobserved").value
    mark = tracer.clock_ns()
    watched = _donating_partitions_run("featurize.partition")
    settled()
    assert len(watched) == len(unwatched) == 2
    for a, b in zip(watched, unwatched):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    roots = {r.span_id: r for r in named("featurize.partition", mark)}
    transfers, devices = (named(n, mark) for n in (
        "engine.transfer", "engine.device"))
    # 3 + 1 batches, each under the partition whose rows it carries
    assert sorted(roots[s.parent_id].attributes["rows"] for s in devices) \
        == [7, 20, 20, 20]
    assert sorted(s.attributes["rows"] for s in devices) == [4, 7, 8, 8]
    assert {s.attributes["program"] for s in devices} == {"<lambda>"}
    assert [s.attributes["bytes"] for s in transfers] == [8 * 3 * 5 * 4] * 4
    assert [s.parent_id for s in transfers] == [s.parent_id for s in devices]
    missed = [s for s in transfers if not s.attributes["observed"]]
    assert (metrics.counter("engine.transfers_unobserved").value
            - unobserved) == len(missed)


def test_a_wait_that_begins_after_the_donation_closes_the_span_unobserved():
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.utils.metrics import metrics

    program = jax.jit(lambda x: x + 1.0, donate_argnums=0)
    unobserved = metrics.counter("engine.transfers_unobserved").value
    mark = tracer.clock_ns()
    window = DispatchWindow(depth=1)
    ahead = Slow()  # holds the watcher while the batch is placed and donated
    with tracer.boundary("featurize.partition") as root:
        window.submit(ahead, program="p", parent=root, rows=1)
        assert ahead.waited.wait(timeout=30)
        placed = jnp.ones((4, 4))
        window.watch_transfer(placed, tracer.clock_ns(), root, bytes=64)
        result = program(placed)
        assert placed.is_deleted()
        ahead.ready.set()
        window.submit(result, program="p", parent=root, rows=4)
        settled()
        host = [host for host, _ in window.drain()][-1]
    np.testing.assert_array_equal(host, np.full((4, 4), 2.0, np.float32))
    (transfer,) = named("engine.transfer", mark)
    assert transfer.attributes == {"bytes": 64, "observed": False}
    first, second = named("engine.device", mark)
    assert "error" not in second.attributes
    # an arrival nobody saw bounds nothing: stream order and the stamp do
    assert second.start_ns >= first.end_ns
    assert metrics.counter("engine.transfers_unobserved").value \
        == unobserved + 1


def test_a_span_the_watcher_writes_late_ends_with_its_partition():
    """The watcher may get to write a span only after the dispatching
    thread fetched the result and ended the partition: the result was
    ready before that, so the span ends with its parent, and the newest
    span of the ring (the readers' mark of the window's end) is never the
    watcher's."""
    mark = tracer.clock_ns()
    window = DispatchWindow(depth=1)
    result = Slow()
    with tracer.boundary("featurize.partition") as root:
        window.submit(result, program="p", parent=root, rows=1)
        assert result.waited.wait(timeout=30)
    with tracer.boundary("sql.collect"):
        pass
    result.ready.set()  # as if the GIL had kept the watcher until now
    settled()
    list(window.drain())
    (device,) = named("engine.device", mark)
    (partition,) = named("featurize.partition", mark)
    (collect,) = named("sql.collect", mark)
    assert device.start_ns <= device.end_ns == partition.end_ns
    assert max(r.end_ns for r in since(mark)
               if r.name != "engine.fetch_wait") == collect.end_ns
