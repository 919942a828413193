"""Shared transformer runtime: batched, padded, jit-cached model execution.

This is the engine's hot loop — the analog of the reference's per-block
``Session::Run`` inside TensorFrames executors (SURVEY.md §3.1).  TPU-first
rules applied here:

- **static shapes**: partitions are run in fixed-size batches, the ragged
  final batch padded up (then sliced), so XLA compiles one program per
  (batch, H, W, C) instead of one per row count;
- **device-resident params**: model params are ``device_put`` once per
  transform, never re-shipped per batch;
- **device-side resize**: images are grouped by source shape and resized in
  batched jitted calls (the reference resized per-row inside its TF graph);
- **data-parallel inference**: with more than one local chip, params are
  replicated over a 1-D ``data`` mesh and every batch's leading dim is
  sharded across it, so the one jitted program runs SPMD over ICI — the
  analog of the reference fanning inference out across Spark executors
  (SURVEY.md §2 "Data-parallel inference").

There is ONE row-batch loop, :func:`run_batched_partitions`, and ONE stage
body over it, :func:`transform_batched`, which every batched DataFrame stage
calls; :func:`run_batched_multi` (pre-decoded arrays) and
:func:`run_batched_rows` (the UDF's one column) are one-partition calls of
the loop.  Its load/decode side — chunking, background prefetch, clean
shutdown — is :mod:`sparkdl_tpu.data`; this module owns what happens once a
batch is decoded.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from typing import (
    Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple,
)

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DEFAULT_BATCH_SIZE = 32


class MixedImageSizesError(ValueError):
    """A partition mixes (H, W) shapes and no target size is configured.

    Typed so callers (e.g. the UDF layer) can catch this specific case and
    reword the guidance, without string-matching the message."""


# Moved to utils.lru so the execution engine can share it without a
# layering cycle; re-exported because serving and the transformers import
# it from here.
from sparkdl_tpu.utils.lru import LRUCache  # noqa: E402

_resize_cache = LRUCache(16)


# ---------------------------------------------------------------------------
# batching core — the pad/bucket discipline shared by the offline loops
# (run_batched*) and the online micro-batcher (sparkdl_tpu.serving): every
# batch the device sees has one of a small, fixed set of leading dims, so
# XLA compiles a bounded program set and steady state never recompiles.
# ---------------------------------------------------------------------------


def pad_to_batch(batch: np.ndarray, batch_size: int) -> np.ndarray:
    """Pad ``batch``'s leading dim up to ``batch_size`` by repeating the
    last row (sliced back by the caller).  Repeating a real row — rather
    than zero-filling — keeps the padding numerically inert for
    row-independent forwards while never feeding the model out-of-
    distribution values."""
    k = batch.shape[0]
    if k >= batch_size:
        return batch
    return np.concatenate(
        [batch, np.repeat(batch[-1:], batch_size - k, axis=0)], axis=0
    )


def shape_bucket(n: int, max_batch: int) -> int:
    """The padded leading dim for an ``n``-row micro-batch: the smallest
    power of two >= n, capped at ``max_batch`` (which is always its own
    bucket, power of two or not)."""
    if n <= 0:
        raise ValueError(f"shape_bucket requires n >= 1, got {n}")
    if n >= max_batch:
        return int(max_batch)
    return min(1 << (int(n) - 1).bit_length(), int(max_batch))


def bucket_ladder(max_batch: int) -> Tuple[int, ...]:
    """Every bucket :func:`shape_bucket` can produce for ``max_batch`` —
    the full set a serving warmup must pre-trace so no request shape
    compiles at request time."""
    if max_batch <= 0:
        raise ValueError(f"bucket_ladder requires max_batch >= 1, got {max_batch}")
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b <<= 1
    out.append(int(max_batch))
    return tuple(out)

# Resolved once per process (a 1-tuple holding the Mesh or None): callers
# place params at build/registration time but batches are placed per call,
# so the decision must not drift between the two (e.g. a UDF registered,
# then the env var changed, then a query run would mix placements and jit
# would reject the incompatible devices).
_dp_mesh_choice: Optional[Tuple[Optional[Mesh]]] = None


def data_parallel_mesh() -> Optional[Mesh]:
    """The inference mesh: a 1-D ``data`` axis over the local devices of the
    default backend, or ``None`` when inference should stay single-device.

    The reference scaled inference by giving every Spark executor its own TF
    session over a partition of the DataFrame (SURVEY.md §2).  The TPU-native
    analog is one SPMD program per batch shape whose leading dim is sharded
    across all local chips; XLA lays the collective-free per-row compute out
    over ICI with zero cross-chip traffic.

    ``SPARKDL_INFERENCE_DEVICES`` controls it: unset/empty/``all`` uses every
    local device, ``0``/``1``/``off``/``none`` forces single-device (``0``
    and ``1`` are aliases for ``off`` — there is no zero-device mesh), an
    integer ``N >= 2`` uses the first N.  Read once per process — params
    placed at stage build / UDF registration time and batches placed per
    call must agree.
    """
    global _dp_mesh_choice
    if _dp_mesh_choice is not None:
        return _dp_mesh_choice[0]
    spec = os.environ.get("SPARKDL_INFERENCE_DEVICES", "all").strip().lower()
    if spec in ("0", "1", "none", "off"):
        _dp_mesh_choice = (None,)
        return None
    if spec in ("", "all"):
        devices = jax.local_devices()
    elif spec.isdigit():
        devices = jax.local_devices()[: int(spec)]
    else:
        raise ValueError(
            "SPARKDL_INFERENCE_DEVICES must be 'all', 'off', or a device "
            f"count; got {spec!r}"
        )
    mesh = Mesh(np.asarray(devices), ("data",)) if len(devices) > 1 else None
    _dp_mesh_choice = (mesh,)
    return mesh


def _reset_data_parallel_mesh_for_testing() -> None:
    """Drop the process-cached mesh decision (tests flip the env var)."""
    global _dp_mesh_choice
    _dp_mesh_choice = None


def _host_resize_one(img: np.ndarray, height: int, width: int) -> np.ndarray:
    """``jax.image.resize`` of one HWC float array on the CPU backend — the
    *same* resampler as the batched device path, so features are invariant to
    how images were partitioned/shape-grouped (PIL bilinear differs
    numerically: corner-aligned sampling vs half-pixel centers)."""
    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        return np.asarray(
            jax.image.resize(
                jnp.asarray(img, jnp.float32),
                (height, width, img.shape[-1]),
                method="bilinear",
            )
        )


# A new XLA program per distinct source shape is ~10-40s on cold TPU; beyond
# this many distinct shapes the host path wins outright.
_MAX_DEVICE_RESIZE_SHAPES = 2


def device_resize(
    images: Sequence[np.ndarray], size: Tuple[int, int]
) -> np.ndarray:
    """Resize a list of HWC float arrays to ``size``.

    Same-shaped sources are batched and resized on device (fused, jitted —
    one compile per distinct source shape).  Partitions with many distinct
    source shapes fall back to host PIL resize: compiling one XLA program per
    shape would dwarf the resize itself, and keeping ragged decode/resize on
    the host is how a TPU input pipeline stays fed (the reference likewise
    resized per-row on CPU — ``ImageUtils.scala``†).
    """
    from sparkdl_tpu.utils.metrics import metrics

    height, width = int(size[0]), int(size[1])
    resize_timer = metrics.timer("sparkdl.resize")
    with resize_timer.time():
        return _device_resize_timed(images, height, width)


def _device_resize_timed(
    images: Sequence[np.ndarray], height: int, width: int
) -> np.ndarray:
    out: List[Optional[np.ndarray]] = [None] * len(images)
    groups: Dict[Tuple[int, ...], List[int]] = {}
    for i, img in enumerate(images):
        groups.setdefault(tuple(img.shape), []).append(i)

    to_resize = [s for s in groups if s[0] != height or s[1] != width]
    use_host = len(to_resize) > _MAX_DEVICE_RESIZE_SHAPES

    device_groups: List[Tuple[List[int], np.ndarray]] = []
    for shape, idxs in groups.items():
        if shape[0] == height and shape[1] == width:
            for i in idxs:
                out[i] = np.asarray(images[i], dtype=np.float32)
            continue
        if use_host:
            from sparkdl_tpu import native

            group = np.stack(
                [np.asarray(images[i], dtype=np.float32) for i in idxs]
            )
            resized = native.resize_batch(group, (height, width))
            if resized is None:  # no native lib: same resampler on CPU jax
                resized = np.stack(
                    [_host_resize_one(g, height, width) for g in group]
                )
            for j, i in enumerate(idxs):
                out[i] = resized[j]
            continue
        # the resize program closes over no weights, so its target size IS
        # its fingerprint — every process shares one persistent entry per
        # (source shape, target size)
        key = (height, width)
        if key not in _resize_cache:
            from sparkdl_tpu.engine import engine as _engine

            def _resize(batch, _h=height, _w=width):
                n, _, _, c = batch.shape
                return jax.image.resize(
                    batch, (n, _h, _w, c), method="bilinear"
                )

            _resize_cache[key] = _engine.function(
                _resize,
                fingerprint=f"builtin.resize:{height}x{width}:bilinear",
                name=f"device_resize_{height}x{width}",
            )
        batch = np.stack(
            [np.asarray(images[i], dtype=np.float32) for i in idxs]
        )
        device_groups.append((idxs, batch))

    if device_groups:
        # dispatch EVERY shape group (at most _MAX_DEVICE_RESIZE_SHAPES)
        # before fetching any: a fetch between two dispatches would make
        # each resize wait for the one before it
        resize_fn = _resize_cache[(height, width)]
        results = [resize_fn(batch) for _, batch in device_groups]
        for (idxs, _), result in zip(device_groups, results):
            host = np.asarray(result)
            for j, i in enumerate(idxs):
                out[i] = host[j]
    return np.stack(out)  # type: ignore[arg-type]


def decode_image_batch(
    rows: Sequence,
    n_channels: int,
    target_hw: Optional[Tuple[int, int]] = None,
    to_rgb: bool = False,
    always_resize: bool = False,
    prefer_uint8: bool = False,
) -> np.ndarray:
    """Decode image-struct Rows into one float32 NHWC batch.

    Shape policy (TPU-first): partitions whose rows share one (H, W) are
    packed at *source* size — the caller's fused device program owns the
    resize (MXU-adjacent, zero extra host work).  Mixed-shape partitions
    are resized to ``target_hw`` while packing, on the native C++ bridge
    when available (threaded decode+resize in one call — the TensorFrames
    "blocked mode" analog), else via the Python path.  ``target_hw=None``
    requires uniform shapes.  ``always_resize=True`` resizes even uniform
    partitions to ``target_hw`` (for programs that do not fuse their own
    resize).
    """
    from sparkdl_tpu import native
    from sparkdl_tpu.utils.metrics import metrics

    hws = {(int(r["height"]), int(r["width"])) for r in rows}
    uniform = len(hws) == 1
    source_hw = next(iter(hws)) if uniform else None
    if not uniform and target_hw is None:
        raise MixedImageSizesError(
            f"partition mixes image sizes {sorted(hws)} and no target size "
            "is configured; resize upstream or set an input size"
        )
    if uniform and not (always_resize and target_hw is not None):
        out_hw = source_hw
    else:
        out_hw = (int(target_hw[0]), int(target_hw[1]))

    metrics.counter("sparkdl.images_processed").add(len(rows))

    will_resize = out_hw != source_hw
    # uint8 fast path: when the batch packs at source size from uint8 rows,
    # ship uint8 and let the device program cast — the host<->device link
    # is the serving bottleneck, and this quarters the bytes.  The caller
    # must opt in (its jitted program casts to float itself).
    if (
        prefer_uint8
        and not will_resize
        and n_channels in (1, 3)
        and native.is_available()
    ):
        with metrics.timer("sparkdl.decode").time():
            batch = native.pack_image_rows_u8(
                rows, out_hw, n_channels, bgr_to_rgb=to_rgb
            )
        if batch is not None:
            return batch
    if prefer_uint8 and not will_resize and n_channels == 3:
        # python uint8 pack (no native lib): replicate/drop channels and
        # flip work on uint8 without precision loss
        u8_modes = {0, 16, 24}
        if all(int(r["mode"]) in u8_modes for r in rows):
            from sparkdl_tpu.image import imageIO

            with metrics.timer("sparkdl.decode").time():
                imgs = [
                    normalize_channels(
                        imageIO.imageStructToArray(r), n_channels
                    )
                    for r in rows
                ]
                if to_rgb:
                    imgs = [img[..., ::-1] for img in imgs]
                return np.stack(imgs)

    if native.is_available():
        with metrics.timer("sparkdl.decode").time():
            try:
                batch = native.pack_image_rows(
                    rows, out_hw, n_channels, bgr_to_rgb=to_rgb
                )
            except ValueError:
                batch = None  # unsupported mode combo -> Python fallback
        if batch is not None:
            return batch

    from sparkdl_tpu.image import imageIO

    with metrics.timer("sparkdl.decode").time():
        images = [
            normalize_channels(
                imageIO.imageStructToArray(r).astype(np.float32), n_channels
            )
            for r in rows
        ]
        if to_rgb and n_channels >= 3:
            images = [img[..., ::-1] for img in images]
    # device_resize passes already-target-sized groups straight through,
    # so this is a pure pack for uniform partitions at source size
    return device_resize(images, out_hw)


#: image-struct modes whose pixel data is uint8 (CV_8UC1/3/4) — the only
#: modes the uint8 fast path may ship un-decoded
_U8_MODES = frozenset({0, 16, 24})


def make_loader_decode_plan(
    load_one: Callable, what: str = "imageLoader"
) -> Callable[[Sequence], np.ndarray]:
    """Chunked decode plan for user-loader inputs (``load_one(uri) ->
    ndarray``), for :func:`run_batched_partitions`.

    Enforces the one-fixed-shape loader contract ACROSS chunks (the first
    chunk's shape binds the partition), so a chunk-aligned shape change
    still raises the contract error instead of a raw concatenate failure.
    Advances the ``sparkdl.load`` timer and the images counter.
    """
    from sparkdl_tpu.utils.metrics import metrics

    expected_shape: List[Optional[Tuple[int, ...]]] = [None]

    def decode(chunk):
        with metrics.timer("sparkdl.load").time():
            arrays = [
                np.asarray(load_one(v), dtype=np.float32) for v in chunk
            ]
        metrics.counter("sparkdl.images_processed").add(len(arrays))
        shapes = {a.shape for a in arrays}
        if expected_shape[0] is not None:
            shapes.add(expected_shape[0])
        if len(shapes) > 1:
            raise ValueError(
                f"{what} must produce one fixed array shape per image; "
                f"this partition mixes {sorted(shapes)} — resize inside "
                f"the {what}"
            )
        expected_shape[0] = arrays[0].shape
        return np.stack(arrays)

    return decode


def make_image_decode_plan(
    rows: Sequence,
    n_channels: int,
    size: Optional[Tuple[int, int]],
    to_rgb: bool = False,
) -> Callable[[Sequence], np.ndarray]:
    """One whole-partition decode policy for the chunked serving pipeline.

    The policy — (a) pack at source size vs resize-while-packing and
    (b) uint8 fast path vs float32 — must be decided over ALL rows, not
    per chunk: a chunk-local decision could alternate (mixed sizes where
    one chunk is incidentally uniform; uniform sizes where only some
    chunks' OpenCV modes are uint8), feeding two dtypes/shapes — two XLA
    programs — to the one jitted forward.

    Returns a ``decode(chunk) -> np.ndarray`` closure for
    :func:`run_batched_partitions`.  Raises :class:`MixedImageSizesError`
    when the partition mixes sizes and ``size`` is None.
    """
    from sparkdl_tpu.obs.trace import tracer

    with tracer.boundary("featurize.plan", rows=len(rows)) as span:
        hws = {(int(r["height"]), int(r["width"])) for r in rows}
        uniform = len(hws) == 1
        if not uniform and size is None:
            raise MixedImageSizesError(
                f"partition mixes image sizes {sorted(hws)} and no target "
                "size is configured; resize upstream or set an input size"
            )
        prefer_u8 = (
            uniform
            and n_channels in (1, 3)
            and all(int(r["mode"]) in _U8_MODES for r in rows)
        )
        # what the plan decides: the dtype and (H, W, C) of every packed row
        packed_hw = next(iter(hws)) if uniform else size
        span.set_attribute("dtype", "uint8" if prefer_u8 else "float32")
        span.set_attribute("shape", (*map(int, packed_hw), n_channels))

    def decode(chunk):
        return decode_image_batch(
            chunk,
            n_channels,
            size,
            to_rgb=to_rgb,
            prefer_uint8=prefer_u8,
            always_resize=not uniform,
        )

    return decode


def cast_and_resize_on_device(x, size: Optional[Tuple[int, int]] = None):
    """The device half of :func:`decode_image_batch`'s uint8 contract — to
    be called at the top of a jitted forward: cast (uint8 ingest) and
    bilinear-resize to ``size`` when the batch arrived at source size, so
    both fuse with the model into one XLA program."""
    x = x.astype(jnp.float32)
    if size is not None:
        h, w = int(size[0]), int(size[1])
        if x.shape[1:3] != (h, w):
            x = jax.image.resize(
                x, (x.shape[0], h, w, x.shape[3]), "bilinear"
            )
    return x


def make_input_prologue(
    size: Optional[Tuple[int, int]] = None,
    preprocess: Optional[Callable] = None,
):
    """Build the fused on-device input prologue of an online endpoint:
    cast (uint8 ingest) → optional bilinear resize to ``size`` → optional
    ``preprocess`` (e.g. a registry entry's Keras-parity normalize), as
    ONE jnp-traceable callable the micro-batcher composes *into* the
    endpoint executable.

    This is :func:`cast_and_resize_on_device` promoted from "call it
    yourself at the top of your forward" to a first-class registration
    hook (``ModelServer.register(prologue=...)``): the whole
    decode-output → normalized-model-input pipeline compiles with the
    model into a single donation-friendly XLA program, so the per-shape-
    group :func:`device_resize` host round-trips disappear from the
    serving hot path.  ``preprocess`` must be jnp-traceable and
    batch-row-independent (row i of the output depends only on row i of
    the input) — the same contract as the forward itself, and what keeps
    ragged and padded dispatch byte-identical per row."""

    def prologue(x):
        x = cast_and_resize_on_device(x, size)
        if preprocess is not None:
            x = preprocess(x)
        return x

    return prologue


def run_batched_multi(
    fn: Callable,
    arrays: Sequence[np.ndarray],
    batch_size: int = DEFAULT_BATCH_SIZE,
) -> Tuple[np.ndarray, ...]:
    """Run ``fn(*inputs)`` (jitted, device-params already bound) over row-
    aligned, pre-decoded input arrays: one partition of
    :func:`run_batched_partitions`, whose batch is the tuple of the arrays'
    row chunks and whose result is the tuple of ``fn``'s outputs.  The last
    chunk is padded up to ``batch_size`` (and sliced back) so only one batch
    shape is ever compiled — small partitions also pad up rather than
    compiling their own shape; with a multi-device
    :func:`data_parallel_mesh` ``batch_size`` is rounded up to a mesh
    multiple, so e.g. ``batchSize=10`` runs as 16-row chunks on 8 chips.
    Row count and output order are unaffected.

    Returns one concatenated array per function output.
    """
    n = arrays[0].shape[0]
    if n == 0:
        raise ValueError("run_batched requires non-empty inputs")

    def call(inputs):
        results = fn(*inputs)
        if isinstance(results, (tuple, list)):
            return tuple(results)
        return (results,)

    call.__name__ = _program_name(fn)

    def cut(rows: range):
        return tuple(a[rows.start : rows.stop] for a in arrays)

    out: List[Tuple[np.ndarray, ...]] = []
    run_batched_partitions(
        call, [range(n)], lambda _: cut,
        lambda done: out.append(done.result), batch_size)
    return out[0]


def run_batched(
    fn: Callable,
    batch: np.ndarray,
    batch_size: int = DEFAULT_BATCH_SIZE,
) -> np.ndarray:
    """Single-input, single-output convenience wrapper of
    :func:`run_batched_multi`."""
    return run_batched_multi(fn, [batch], batch_size)[0]


def _program_name(fn: Callable) -> str:
    """What a dispatched callable is called in spans: an engine
    function's ``name``, else the function's own."""
    return str(
        getattr(fn, "name", None) or getattr(fn, "__name__", type(fn).__name__)
    )


class FinishedPartition(NamedTuple):
    """What :func:`run_batched_partitions` hands ``finish`` when the last
    result of a partition is back on the host."""

    #: the partition's place in the ``partitions`` it came from
    index: int
    #: the outputs of its rows, in row order (a tuple of arrays where the
    #: dispatched function returns one)
    result: Any
    #: its boundary span, still open: what ``finish`` does nests under it
    #: by naming it as ``parent`` (None where the caller had none open)
    span: Any
    #: results of LATER partitions dispatched and unfetched now: what the
    #: device has to do while ``finish`` keeps the dispatching thread
    inflight: int


def run_batched_partitions(
    fn: Callable,
    partitions: Sequence[Sequence],
    plan: Callable[[Sequence], Callable[[Sequence], Any]],
    finish: Callable[[FinishedPartition], None],
    batch_size: int = DEFAULT_BATCH_SIZE,
    span_name: Optional[str] = None,
) -> None:
    """Decode+forward pipeline over the row chunks of ALL ``partitions``
    of one call — the serving-path transfer/compute overlap (the reference
    delegated this to TensorFrames' blocked pipelining; SURVEY.md §2), kept
    going across the border between two partitions:

    - host decode of chunk i+1 runs on a prefetch thread while chunk i is
      on device (the inference analog of the estimator's
      ``StreamingShardLoader``) — straight through a border: the packer
      turns to the next partition while the last chunks of this one are
      still on the device;
    - dispatched results ride ONE :class:`~sparkdl_tpu.engine.DispatchWindow`
      of the engine's depth N (``DEFAULT_DEPTH``, 2): chunk i's device→host
      copy streams asynchronously while chunks i+1..i+N compute, so the
      fetch finds the bytes already on host;
    - when the LAST result of a partition falls out of the window,
      ``finish(FinishedPartition)`` runs on the dispatching thread — by
      then N chunks of the next partition are dispatched, so the device
      works while the caller post-processes.  ``engine.borders`` counts
      the partitions finished with a non-empty one still to come in the
      same call, ``engine.borders_fed`` those of them that found
      ``inflight`` > 0.

    A partition border is metadata: a chunk never spans two partitions.
    ``plan(rows) -> decode`` is called once a non-empty partition, when
    the packer reaches it (the decode policy — dtype, packed shape — is
    decided over the partition's rows), and ``decode(chunk_rows)`` gives
    the batch: one row-aligned array, or a tuple of them (any pytree),
    which ``fn`` takes whole and answers with one array, or a pytree of
    them, a row each.  One array in must give one array out (a
    ``TypeError`` otherwise).  Chunks are ``batch_size`` rows — rounded up
    to a multiple of a multi-device :func:`data_parallel_mesh`, over which
    every batch's leading dim is then sharded — and a partition's ragged
    final chunk pads by repeating its last row, so exactly one batch shape
    is ever compiled per decode shape, and every batch holds the rows it
    would hold were the partitions run one call each.  Empty partitions
    are passed over (no ``finish``).  Partitions finish in order.

    Host memory: at most 2 packed chunks ahead, N results in flight and
    the fetched results of the one partition not yet finished.  An error —
    in ``plan``, ``decode``, ``fn`` or ``finish`` — raises out of the call
    with the window abandoned and the prefetch thread joined.

    The load/decode prefix is a :mod:`sparkdl_tpu.data` pipeline
    (``from_items(chunk indexes) → map(pack) → prefetch(2)``), so the
    background decode thread follows the package's clean-shutdown protocol
    and feeds the ``data.*`` metrics.

    Spans (:mod:`sparkdl_tpu.obs.trace`): with ``span_name`` every
    non-empty partition gets a ROOT boundary span of that name (``rows``,
    ``batch_size``, ``batches``) from the moment either thread turns to it
    until its ``finish`` returns — two of them overlap at a border — and
    ``data.pack``, ``engine.load_wait``, ``engine.place``,
    ``engine.dispatch`` and ``engine.fetch_wait`` are its children by
    explicit parent.  Without, they hang under the caller's current span,
    which gets ``batches``.  Under the same parent, on the engine's
    watcher thread: ``engine.transfer`` (``bytes``, ``observed``) a placed
    batch and ``engine.device`` (``program``, ``rows``, ``queued_ms``) a
    dispatch — when the batch arrived, and when the device computed it
    (:class:`~sparkdl_tpu.engine.executor._CompletionWatcher`); none where
    the caller has no span open.
    """
    from sparkdl_tpu.data import Dataset
    from sparkdl_tpu.engine import DispatchWindow
    from sparkdl_tpu.obs.trace import tracer
    from sparkdl_tpu.utils.metrics import metrics
    from sparkdl_tpu.utils.profiler import maybe_trace

    tree_map, tree_leaves = jax.tree_util.tree_map, jax.tree_util.tree_leaves
    asked_batch_size = batch_size
    mesh = data_parallel_mesh()
    if mesh is not None:
        # padded chunks are always exactly batch_size rows; round the batch
        # up to a mesh multiple so the shards are equal-sized
        n_dev = int(mesh.devices.size)
        batch_size = -(-batch_size // n_dev) * n_dev
        # P("data") shards the leading dim; unmentioned trailing dims are
        # replicated, so one sharding serves every input rank
        sharding = NamedSharding(mesh, PartitionSpec("data"))

        def _place(c):
            return jax.device_put(c, sharding)

    else:
        _place = jnp.asarray

    # every chunk of the call, in the order it is packed, dispatched and
    # fetched: (partition, lo, hi)
    chunks = [
        (p, lo, min(lo + batch_size, len(rows)))
        for p, rows in enumerate(partitions)
        for lo in range(0, len(rows), batch_size)
    ]
    if not chunks:
        return
    last_chunk = {p: i for i, (p, _, _) in enumerate(chunks)}

    # The packs run on the prefetch thread, which inherits no context, and
    # two partitions are open at once on the dispatching thread: every
    # span takes its partition's span as parent explicitly.  Whichever
    # thread turns to a partition first opens its span (the packer, a few
    # chunks ahead, but for the first).
    ambient = tracer.current()
    if span_name is None and ambient is not None:
        ambient.set_attribute("batches", len(chunks))
    spans: Dict[int, Any] = {}
    spans_lock = threading.Lock()

    def span_of(p):
        if span_name is None:
            return ambient
        with spans_lock:
            span = spans.get(p)
            if span is None:
                n = len(partitions[p])
                span = spans[p] = tracer.start_boundary(
                    span_name, rows=n, batch_size=asked_batch_size,
                    batches=-(-n // batch_size))
            return span

    decodes: Dict[int, Callable] = {}

    def pack(i):
        p, lo, hi = chunks[i]
        parent = span_of(p)
        if lo == 0:
            with tracer.use_span(parent):
                decodes[p] = plan(partitions[p])
        with tracer.boundary("data.pack", parent=parent) as span:
            batch = tree_map(
                lambda a: pad_to_batch(a, batch_size),
                decodes[p](partitions[p][lo:hi]))
            nbytes = sum(a.nbytes for a in tree_leaves(batch))
            span.set_attribute("rows", hi - lo)
            span.set_attribute("padded_rows", batch_size)
            span.set_attribute("bytes", nbytes)
        if i == last_chunk[p]:
            del decodes[p]
        return batch, nbytes

    # prefetch(2) bounds host memory at ~2 extra decoded chunks; the
    # pipeline's close protocol (cancel -> drain -> join) means a failed
    # call can't leak the decode thread plus its chunks
    packed = iter(
        Dataset.from_items(range(len(chunks)), name="chunk_indexes")
        .map(pack)
        .prefetch(2)
    )

    # (images_processed is advanced by the decode layer — e.g.
    # decode_image_batch — not here, to avoid double counting)
    window = DispatchWindow()
    collected: List[Any] = []  # of the one partition not yet finished
    fetched = 0  # results come back in order: the next is chunks[fetched]'s
    away_s = 0.0  # spent in ``finish``: the caller's time, not the loop's
    borders = metrics.counter("engine.borders")
    borders_fed = metrics.counter("engine.borders_fed")

    def oldest():
        """The span of the partition whose result leaves the window next
        (the chunk about to be submitted's, when the window is empty): a
        fetch waits, and a starved stretch ends, under that one."""
        return span_of(chunks[fetched][0])

    def take(host):
        nonlocal fetched, away_s
        p, lo, hi = chunks[fetched]
        collected.append(tree_map(lambda a: a[: hi - lo], host))
        fetched += 1
        if fetched <= last_chunk[p]:
            return
        result = tree_map(
            lambda *parts: np.concatenate(parts, axis=0), *collected)
        collected.clear()
        inflight = len(window)
        if fetched < len(chunks):  # a border: a partition is still to come
            borders.add(1)
            if inflight:
                borders_fed.add(1)
        span = span_of(p)
        began = time.perf_counter()
        try:
            finish(FinishedPartition(p, result, span, inflight))
        finally:
            away_s += time.perf_counter() - began
            if span_name is not None:
                span.end()

    # 'sparkdl.forward' is the HOST's time in place + dispatch + blocking
    # on fetches — not the device's: the device works on while the host
    # packs, and waits while the host is here placing.  The engine.*
    # boundary spans split it by layer.  The decode closure advances
    # 'sparkdl.load' on the prefetch thread, so timing the whole loop
    # would double-count load under forward.  The whole loop — load waits
    # included, ``finish`` left out — runs under 'sparkdl.serve', the
    # sustained end-to-end rate images_per_sec() reports.
    forward_timer = metrics.timer("sparkdl.forward")
    program = _program_name(fn)
    started = time.perf_counter()
    try:
        with maybe_trace():
            for p, lo, hi in chunks:
                span = span_of(p)
                with tracer.boundary("engine.load_wait", parent=span):
                    batch, nbytes = next(packed)
                with forward_timer.time():
                    with tracer.boundary(
                        "engine.place", parent=span, bytes=nbytes
                    ) as placing:
                        placed = tree_map(_place, batch)
                        # before the dispatch: ``fn`` may donate the batch
                        window.watch_transfer(
                            placed, placing.start_ns, span, bytes=nbytes)
                    with tracer.boundary(
                        "engine.dispatch", parent=span, program=program
                    ):
                        result = fn(placed)  # async dispatch
                    if isinstance(result, (tuple, list)) and isinstance(
                            batch, np.ndarray):
                        raise TypeError(
                            "a batch of one array requires a single-output "
                            f"fn (got {len(result)} outputs); unwrap the "
                            "output in the forward, or use "
                            "run_batched_multi"
                        )
                    with tracer.use_span(oldest()):
                        fell_out = window.submit(
                            result, program=program, parent=span,
                            rows=hi - lo)
                for host, _ in fell_out:
                    take(host)
            # the wait that finds the end (and the prefetch thread gone)
            with tracer.boundary("engine.load_wait", parent=span):
                next(packed, None)
            in_order = window.drain()
            while len(window):
                with forward_timer.time(), tracer.use_span(oldest()):
                    host, _ = next(in_order)
                take(host)
    finally:
        window.abandon()
        packed.close()
        for span in spans.values():  # an error left them open
            span.end()
        metrics.timer("sparkdl.serve").add_seconds(
            time.perf_counter() - started - away_s)
    metrics.counter("sparkdl.rows_processed").add(
        sum(len(rows) for rows in partitions))


def run_batched_rows(
    fn: Callable,
    rows: Sequence,
    decode: Callable[[Sequence], np.ndarray],
    batch_size: int = DEFAULT_BATCH_SIZE,
) -> np.ndarray:
    """:func:`run_batched_partitions` over ONE partition, for the UDF, which
    the SQL engine hands one partition's column a call:
    ``decode(chunk_rows) -> np.ndarray`` is the partition's decode plan,
    made by the caller; the outputs of all ``rows`` come back as one array.
    The loop's spans hang under the caller's current span."""
    if len(rows) == 0:
        raise ValueError("run_batched_rows requires non-empty rows")
    out: List[np.ndarray] = []
    run_batched_partitions(
        fn, [rows], lambda _: decode, lambda done: out.append(done.result),
        batch_size)
    return out[0]


def to_vectors(result: np.ndarray) -> List:
    """One float64 ``DenseVector`` a row of ``result``, each row flattened:
    what a vector-valued output column holds."""
    from sparkdl_tpu.ml.linalg import DenseVector

    flat = result.reshape(result.shape[0], -1).astype(np.float64)
    return [DenseVector(v) for v in flat]


def to_image_structs(result: np.ndarray) -> List:
    """One float32 image struct a row of ``result``: what an image-valued
    output column holds."""
    from sparkdl_tpu.image import imageIO

    return [
        imageIO.imageArrayToStruct(np.asarray(img, dtype=np.float32))
        for img in result
    ]


def transform_batched(
    dataset,
    input_col: str,
    output_col: str,
    fn: Callable,
    plan: Callable[[Sequence], Callable[[Sequence], np.ndarray]],
    to_column: Callable[[np.ndarray], List],
    batch_size: int = DEFAULT_BATCH_SIZE,
):
    """The body of every batched DataFrame stage: ``dataset`` with
    ``output_col`` added, made by ONE :func:`run_batched_partitions` over
    the ``input_col`` of all partitions, so chunk i+1 packs on a prefetch
    thread and dispatches before chunk i's fetch across the border between
    two partitions too.  ``plan(rows) -> decode`` decides how a partition's
    rows are packed, ``fn`` is dispatched a batch and ``to_column(result)``
    builds a partition's output values from the outputs of its rows — while
    the device works on the next partition's first batches.  An empty
    partition gets an empty column.

    The boundary spans name where a partition's time goes (obs.trace): a
    root ``featurize.partition`` a non-empty partition, with the plan,
    pack, wait, place, dispatch and fetch spans and ``to_column``'s
    ``featurize.postprocess`` under it; ``inflight`` says what the device
    had to do meanwhile."""
    from sparkdl_tpu.obs.trace import tracer

    def process_partitions(parts):
        outs = [{**part, output_col: []} for part in parts]

        def postprocess(done):
            with tracer.boundary(
                "featurize.postprocess", parent=done.span,
                rows=len(done.result), inflight=done.inflight,
            ):
                outs[done.index][output_col] = to_column(done.result)

        run_batched_partitions(
            fn, [part[input_col] for part in parts], plan, postprocess,
            batch_size, span_name="featurize.partition",
        )
        return outs

    return dataset.mapAllPartitions(process_partitions)


def normalize_channels(img: np.ndarray, n_channels: int) -> np.ndarray:
    """Coerce an HWC float array to ``n_channels`` (3: replicate gray / drop
    alpha; 1: ITU-R 601 luminance) so a partition with mixed image modes
    still forms one static-shaped batch."""
    if img.ndim == 2:
        img = img[:, :, None]
    c = img.shape[-1]
    if c == n_channels:
        return img
    if n_channels == 3:
        if c == 1:
            return np.repeat(img, 3, axis=-1)
        if c == 4:
            return img[:, :, :3]
    if n_channels == 1:
        if c >= 3:
            # stored order is BGR
            return (
                0.114 * img[:, :, :1]
                + 0.587 * img[:, :, 1:2]
                + 0.299 * img[:, :, 2:3]
            ).astype(img.dtype)
    raise ValueError(
        f"Cannot convert image with {c} channels to {n_channels} channels"
    )


def place_params(params, device=None):
    """Pin a params pytree to the accelerator(s) once per transform: with
    more than one local device (and no explicit ``device``) the pytree is
    replicated over the :func:`data_parallel_mesh` so batches sharded on the
    ``data`` axis run SPMD; otherwise it lands on the one default device.

    Passing an explicit ``device`` on a multi-chip host requires
    ``SPARKDL_INFERENCE_DEVICES=off``: :func:`run_batched_multi` shards
    batches over the process mesh, and jit rejects mesh-sharded batches
    against single-device params."""
    if device is None:
        mesh = data_parallel_mesh()
        if mesh is not None:
            return jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        device = jax.devices()[0]
    return jax.device_put(params, device)


_PLACED_ONCE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def place_params_once(owner, params, device=None):
    """:func:`place_params`, once per ``owner`` (the model object that holds
    ``params``): the placed pytree is remembered with the owner, so a second
    ``transform`` of the same model sends nothing — gigabytes of weights are
    program ARGUMENTS and must not cross again on every pass.  Leaves that
    are device arrays already are not sent by ``device_put`` either.  The
    ``engine.place_params`` span's ``bytes`` counts what really crossed: the
    host leaves."""
    from sparkdl_tpu.obs.trace import tracer

    held = _PLACED_ONCE.get(owner)
    if held is not None and held[0] is params and held[1] == device:
        return held[2]
    sent = sum(
        int(np.asarray(leaf).nbytes)
        for leaf in jax.tree_util.tree_leaves(params)
        if not isinstance(leaf, jax.Array)
    )
    with tracer.boundary("engine.place_params", bytes=sent):
        placed = place_params(params, device)
    _PLACED_ONCE[owner] = (params, device, placed)
    return placed


_KERAS_FN_CACHE = LRUCache(8)


def load_keras_function(path: str, compute_dtype: Optional[str] = None):
    """``XlaFunction.from_keras`` cached per (path, mtime, dtype): repeated
    transforms of the same saved model reuse one XlaFunction instance — and
    therefore its per-instance jit cache / compiled XLA program."""
    import os

    from sparkdl_tpu.graph.function import XlaFunction

    if compute_dtype == "float32":
        compute_dtype = None  # same artifact as the default: share the entry
    key = (os.path.abspath(path), os.path.getmtime(path), compute_dtype)
    if key not in _KERAS_FN_CACHE:
        _KERAS_FN_CACHE[key] = XlaFunction.from_keras(
            path, compute_dtype=compute_dtype
        )
    return _KERAS_FN_CACHE[key]
