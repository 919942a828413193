"""Image I/O and Spark-compatible image schema.

Reference analog: ``python/sparkdl/image/imageIO.py``† and Scala
``ImageUtils.scala``† (SURVEY.md §1 L1, §2 "Image I/O").  Field layout and
conventions match Spark 2.3+ ``pyspark.ml.image.ImageSchema``: struct
``(origin, height, width, nChannels, mode, data)`` with OpenCV type codes and
**BGR channel order** in ``data`` — so downstream graph pieces must (and do)
handle BGR↔RGB exactly like the reference's ``buildSpImageConverter``.
"""

from __future__ import annotations

import contextlib
import glob
import io
import logging
import os
from collections import namedtuple
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np
from PIL import Image

from sparkdl_tpu.resilience.errors import PermanentError as _PermanentError
from sparkdl_tpu.sql.types import (
    BinaryType,
    IntegerType,
    Row,
    StringType,
    StructField,
    StructType,
)

logger = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# Schema (Spark ImageSchema-compatible)
# ---------------------------------------------------------------------------

imageSchema = StructType(
    [
        StructField("origin", StringType()),
        StructField("height", IntegerType()),
        StructField("width", IntegerType()),
        StructField("nChannels", IntegerType()),
        StructField("mode", IntegerType()),
        StructField("data", BinaryType()),
    ]
)

_OcvType = namedtuple("_OcvType", ["name", "ord", "nChannels", "dtype"])

_OCV_TYPES = [
    _OcvType(name="Undefined", ord=-1, nChannels=-1, dtype="N/A"),
    _OcvType(name="CV_8UC1", ord=0, nChannels=1, dtype="uint8"),
    _OcvType(name="CV_8UC3", ord=16, nChannels=3, dtype="uint8"),
    _OcvType(name="CV_8UC4", ord=24, nChannels=4, dtype="uint8"),
    _OcvType(name="CV_32FC1", ord=5, nChannels=1, dtype="float32"),
    _OcvType(name="CV_32FC3", ord=21, nChannels=3, dtype="float32"),
    _OcvType(name="CV_32FC4", ord=29, nChannels=4, dtype="float32"),
]

ocvTypes = {t.name: t.ord for t in _OCV_TYPES}


class imageType:
    """Lookup helpers between OpenCV type codes and (nChannels, dtype)."""

    @staticmethod
    def byOrdinal(ord_: int) -> _OcvType:
        for t in _OCV_TYPES:
            if t.ord == ord_:
                return t
        raise KeyError(f"Unknown OpenCV type ordinal: {ord_}")

    @staticmethod
    def byName(name: str) -> _OcvType:
        for t in _OCV_TYPES:
            if t.name == name:
                return t
        raise KeyError(f"Unknown OpenCV type name: {name}")

    @staticmethod
    def forArray(arr: np.ndarray) -> _OcvType:
        if arr.ndim == 2:
            n_channels = 1
        elif arr.ndim == 3:
            n_channels = arr.shape[2]
        else:
            raise ValueError(f"Image array must be 2-d or 3-d, got shape {arr.shape}")
        dtype = str(arr.dtype)
        for t in _OCV_TYPES:
            if t.nChannels == n_channels and t.dtype == dtype:
                return t
        raise ValueError(
            f"Unsupported image array: {n_channels} channels, dtype {dtype}"
        )


imageTypeByOrdinal = imageType.byOrdinal
imageTypeByName = imageType.byName

# ---------------------------------------------------------------------------
# Array <-> struct codecs
# ---------------------------------------------------------------------------


def imageArrayToStruct(imgArray: np.ndarray, origin: str = "") -> Row:
    """Pack a (H, W[, C]) array into an image struct Row.

    Array is assumed already channel-ordered the way it should be stored
    (Spark stores BGR); use :func:`rgbArrayToStruct` for RGB input.
    """
    if imgArray.ndim == 2:
        imgArray = imgArray[:, :, None]
    ocv = imageType.forArray(imgArray)
    height, width, n_channels = imgArray.shape
    contiguous = np.ascontiguousarray(imgArray)
    return Row(
        origin=origin,
        height=int(height),
        width=int(width),
        nChannels=int(n_channels),
        mode=int(ocv.ord),
        data=contiguous.tobytes(),
    )


def imageStructToArray(imageRow: Row) -> np.ndarray:
    """Unpack an image struct Row into a (H, W, C) numpy array (stored
    channel order, i.e. BGR for color images)."""
    ocv = imageType.byOrdinal(imageRow["mode"])
    shape = (imageRow["height"], imageRow["width"], imageRow["nChannels"])
    return np.frombuffer(imageRow["data"], dtype=ocv.dtype).reshape(shape)


def rgbArrayToStruct(rgbArray: np.ndarray, origin: str = "") -> Row:
    """Pack an RGB(A) array, converting to the stored BGR(A) order."""
    arr = rgbArray
    if arr.ndim == 3 and arr.shape[2] >= 3:
        arr = arr[:, :, ::-1] if arr.shape[2] == 3 else arr[:, :, [2, 1, 0, 3]]
    return imageArrayToStruct(arr, origin)


def imageStructToRGBArray(imageRow: Row) -> np.ndarray:
    """Unpack to RGB(A) order (undoing the stored BGR(A))."""
    arr = imageStructToArray(imageRow)
    if arr.shape[2] == 3:
        return arr[:, :, ::-1]
    if arr.shape[2] == 4:
        return arr[:, :, [2, 1, 0, 3]]
    return arr


class ImageDecodeError(ValueError, _PermanentError):
    """A file's bytes could not be decoded into an image.

    Carries ``origin`` (the file path / URI) and the underlying ``cause``
    so ``on_error="raise"`` callers see *which* input was corrupt, not
    just a bare PIL traceback.  Classified :class:`PermanentError` in the
    resilience taxonomy: corrupt bytes do not heal on retry — skip the
    row (``on_error="skip"``) or fail fast, never back off."""

    def __init__(self, origin: str, cause: Optional[BaseException] = None):
        self.origin = origin
        self.cause = cause
        detail = f": {cause}" if cause is not None else ""
        super().__init__(f"cannot decode image {origin!r}{detail}")


# PIL mode → (raw packing that gives the stored channel order, nChannels,
# OpenCV type): ``tobytes("raw", "BGR")`` is the bytes of asarray → reverse
# the channels → ascontiguousarray → tobytes in one copy, not three
_STORED_AS = {
    "L": ("L", 1, ocvTypes["CV_8UC1"]),
    "RGB": ("BGR", 3, ocvTypes["CV_8UC3"]),
    "RGBA": ("BGRA", 4, ocvTypes["CV_8UC4"]),
}


def _decode_image_bytes(raw: bytes, origin: str = "") -> Optional[Row]:
    """Decode compressed image bytes (PNG/JPEG/...) → image struct, or None
    if undecodable (matching the reference's null-tolerant decode)."""
    try:
        img = Image.open(io.BytesIO(raw))
        if img.mode not in _STORED_AS:
            img = img.convert("RGB")
        packing, n_channels, ocv_type = _STORED_AS[img.mode]
        data = img.tobytes("raw", packing)
    except Exception:
        return None
    return Row(
        origin=origin,
        height=img.height,
        width=img.width,
        nChannels=n_channels,
        mode=ocv_type,
        data=data,
    )


def PIL_decode_and_resize(size):
    """Return decoder fn bytes → RGB float array resized to ``size`` (H, W)."""

    def decode(raw: bytes) -> np.ndarray:
        img = Image.open(io.BytesIO(raw)).convert("RGB")
        img = img.resize((size[1], size[0]), Image.BILINEAR)
        return np.asarray(img, dtype=np.float32)

    return decode


def resizeImage(size):
    """Row-wise image-struct resize UDF factory (analog of the reference's
    PIL resize udf / Scala ``ImageUtils.resizeImage``†)."""

    height, width = size

    def resize(imageRow: Row) -> Row:
        arr = imageStructToArray(imageRow)
        n = arr.shape[2]
        pil_mode = {1: "L", 3: "RGB", 4: "RGBA"}[n]
        img = Image.fromarray(arr.squeeze() if n == 1 else arr, mode=pil_mode)
        resized = np.asarray(
            img.resize((width, height), Image.BILINEAR), dtype=np.uint8
        )
        if resized.ndim == 2:
            resized = resized[:, :, None]
        return imageArrayToStruct(resized, imageRow["origin"])

    return resize


# ---------------------------------------------------------------------------
# Readers
# ---------------------------------------------------------------------------

_IMG_EXTENSIONS = (".jpg", ".jpeg", ".png", ".gif", ".bmp", ".webp")


def _list_files(path: str) -> List[str]:
    if os.path.isdir(path):
        # the entry's own type where the listing carries it: no stat a file
        with os.scandir(path) as entries:
            files = sorted(e.path for e in entries if e.is_file())
    else:
        files = sorted(glob.glob(path))
    return files


def _read_file(path: str) -> Tuple[str, bytes]:
    # unbuffered: no isatty() and no tell() before readall(), which a
    # buffered open().read() ends in too — two system calls a file fewer
    with open(path, "rb", buffering=0) as fh:
        return path, fh.readall()


def filesToDF(session, path: str, numPartitions: int = 4):
    """Read files from a directory/glob → DataFrame (filePath, fileData).

    Eager and in listing order, on the calling thread: a pool of threads
    was measured here and lost to the serial loop wherever the files come
    from the page cache (PERF.md §6, PR 30).

    Reference analog: ``imageIO.filesToDF`` over ``sc.binaryFiles``†.
    """
    from sparkdl_tpu.obs.trace import tracer
    from sparkdl_tpu.sql.session import TPUSession

    session = session or TPUSession.getActiveSession()
    with tracer.boundary("image.read_files") as span:
        rows = [_read_file(f) for f in _list_files(path)]
        span.set_attribute("files", len(rows))
        span.set_attribute("bytes", sum(len(raw) for _, raw in rows))
        span.set_attribute("workers", 1)
    return session.createDataFrame(
        rows, ["filePath", "fileData"], numPartitions=numPartitions
    )


def _pool_width(n_items: int) -> int:
    """Worker threads for decoding a partition of ``n_items`` files: one
    per CPU this process may run on, and never fewer than two files a
    worker — below that (1) the loop runs inline on the calling thread."""
    try:
        cpus = len(os.sched_getaffinity(0))
    except AttributeError:  # not on this platform
        cpus = os.cpu_count() or 1
    return max(1, min(cpus, n_items // 2))


@contextlib.contextmanager
def _ordered_map(
    fn: Callable, items: Sequence
) -> Iterator[Tuple[Iterable, int]]:
    """``(results, workers)``: ``fn`` over ``items`` with the results in
    input order, on a pool of :func:`_pool_width` threads that lives for
    the ``with`` block (threads, not processes: PIL's decoders release
    the GIL, a struct pickled back costs what a decode saves, and a fork
    after the TPU runtime is up is not safe).  An exception of ``fn`` is
    raised where its result is consumed, so the first one in input order
    wins; leaving the block early cancels what has not started and joins
    every thread."""
    workers = _pool_width(len(items))
    if workers == 1:
        yield map(fn, items), 1
        return
    pool = ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="sparkdl-image-io"
    )
    try:
        yield pool.map(fn, items), workers
    finally:
        pool.shutdown(wait=True, cancel_futures=True)


def readImages(
    path: str,
    session=None,
    numPartitions: int = 4,
    on_error: str = "skip",
):
    """Read images from a directory/glob → DataFrame with an ``image``
    struct column (Spark ``ImageSchema.readImages`` analog).

    ``on_error="skip"`` (the reference's null-tolerant behavior) drops
    undecodable files — but no longer silently: each drop advances the
    ``data.decode_errors`` counter and logs the origin.
    ``on_error="raise"`` fails the read with :class:`ImageDecodeError`
    naming the corrupt file — for pipelines where a dropped row means a
    silently wrong join downstream.

    Each partition's files are decoded on a pool of threads sized from the
    host's CPUs (inline for a few files); rows keep the listing order, and
    every struct's bytes, the counter and the error raised are those of a
    serial decode."""
    return readImagesWithCustomFn(
        path,
        decode_f=_decode_image_bytes,
        numPartitions=numPartitions,
        session=session,
        on_error=on_error,
    )


def readImagesWithCustomFn(
    path: str,
    decode_f: Callable[[bytes, str], Optional[Row]],
    numPartitions: int = 4,
    session=None,
    on_error: str = "skip",
):
    """Like :func:`readImages` with a custom ``decode_f(bytes, origin) ->
    Optional[Row]``; a None return (or a raise) from ``decode_f`` is a
    decode failure, handled per ``on_error`` ("skip" counts it in
    ``data.decode_errors`` and drops the row, "raise" aborts with
    :class:`ImageDecodeError` for the first such file in listing order).

    ``decode_f`` is called from several threads at once (as Spark calls it
    from several executors): it must not depend on shared mutable state or
    on call order.  Rows come back in listing order all the same; skips
    are logged and counted on the calling thread."""
    if on_error not in ("skip", "raise"):
        raise ValueError(
            f'on_error must be "skip" or "raise", got {on_error!r}'
        )
    from sparkdl_tpu.sql.session import TPUSession

    session = session or TPUSession.getActiveSession()
    files_df = filesToDF(session, path, numPartitions=numPartitions)

    def decode_one(item):
        """On a worker: the struct or None, and what ``decode_f`` raised."""
        fp, raw = item
        try:
            return decode_f(raw, fp), None
        except Exception as exc:
            return None, exc

    def decode_partition(part):
        from sparkdl_tpu.obs.trace import tracer
        from sparkdl_tpu.utils.metrics import metrics

        decode_errors = metrics.counter("data.decode_errors")
        files = list(zip(part["filePath"], part["fileData"]))
        images, origins = [], []
        with tracer.boundary("image.decode") as span:
            with _ordered_map(decode_one, files) as (results, workers):
                for (fp, _), (struct, exc) in zip(files, results):
                    if struct is None:
                        if on_error == "raise":
                            raise ImageDecodeError(fp, exc) from exc
                        decode_errors.add(1)
                        logger.warning("dropping undecodable image %s", fp)
                        continue
                    images.append(struct)
                    origins.append(fp)
            span.set_attribute("rows", len(images))
            span.set_attribute("errors", len(files) - len(images))
            span.set_attribute("workers", workers)
        return {"filePath": origins, "image": images}

    schema = StructType(
        [StructField("filePath", StringType()), StructField("image", imageSchema)]
    )
    return files_df.mapPartitions(decode_partition, schema=schema)
