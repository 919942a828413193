"""Image I/O tests (reference analog: tests around ``imageIO.py``† and
``ImageUtilsSuite.scala``† — SURVEY.md §4)."""

import io
import logging
import threading

import numpy as np
import pytest
from PIL import Image

from sparkdl_tpu.image import imageIO
from sparkdl_tpu.image.imageIO import (
    ImageDecodeError,
    filesToDF,
    imageArrayToStruct,
    imageSchema,
    imageStructToArray,
    imageStructToRGBArray,
    imageType,
    readImages,
    resizeImage,
    rgbArrayToStruct,
)


def test_array_struct_roundtrip():
    arr = np.random.RandomState(0).randint(0, 255, (7, 5, 3), dtype=np.uint8)
    struct = imageArrayToStruct(arr, origin="mem")
    assert struct.height == 7 and struct.width == 5 and struct.nChannels == 3
    assert struct.mode == 16  # CV_8UC3
    np.testing.assert_array_equal(imageStructToArray(struct), arr)


def test_rgb_bgr_channel_order():
    rgb = np.zeros((2, 2, 3), dtype=np.uint8)
    rgb[..., 0] = 255  # pure red in RGB
    struct = rgbArrayToStruct(rgb)
    stored = imageStructToArray(struct)
    # stored order is BGR: red lands in the last channel
    assert stored[0, 0, 2] == 255 and stored[0, 0, 0] == 0
    np.testing.assert_array_equal(imageStructToRGBArray(struct), rgb)


def test_grayscale_roundtrip():
    arr = np.random.RandomState(1).randint(0, 255, (4, 6), dtype=np.uint8)
    struct = imageArrayToStruct(arr)
    assert struct.mode == 0 and struct.nChannels == 1
    np.testing.assert_array_equal(imageStructToArray(struct)[:, :, 0], arr)


def test_image_type_for_array_rejects_bad():
    with pytest.raises(ValueError):
        imageType.forArray(np.zeros((2, 2, 2), dtype=np.uint8))
    with pytest.raises(ValueError):
        imageType.forArray(np.zeros((2, 2, 3), dtype=np.int64))


def test_files_to_df(tpu_session, image_dir):
    df = filesToDF(tpu_session, image_dir, numPartitions=3)
    assert df.columns == ["filePath", "fileData"]
    assert df.count() == 7
    row = df.collect()[0]
    assert isinstance(row.fileData, bytes) and len(row.fileData) > 0


def test_read_images(tpu_session, image_dir):
    df = readImages(image_dir, session=tpu_session, numPartitions=2)
    assert "image" in df.columns
    rows = df.collect()
    assert len(rows) == 7
    color = [r for r in rows if r.image.nChannels == 3]
    assert len(color) == 6
    img = color[0].image
    arr = imageStructToArray(img)
    assert arr.shape == (img.height, img.width, 3)
    # decoded PNG content must match PIL ground truth (BGR stored)
    pil = np.asarray(Image.open(img.origin).convert("RGB"))
    np.testing.assert_array_equal(imageStructToRGBArray(img), pil)


def test_read_images_drops_undecodable(tpu_session, tmp_path):
    (tmp_path / "bad.png").write_bytes(b"not an image")
    arr = np.zeros((4, 4, 3), dtype=np.uint8)
    Image.fromarray(arr).save(tmp_path / "ok.png")
    df = readImages(str(tmp_path), session=tpu_session)
    assert df.count() == 1


def test_resize_udf():
    arr = np.random.RandomState(2).randint(0, 255, (10, 8, 3), dtype=np.uint8)
    struct = imageArrayToStruct(arr)
    resized = resizeImage((5, 4))(struct)
    assert (resized.height, resized.width) == (5, 4)
    out = imageStructToArray(resized)
    ref = np.asarray(
        Image.fromarray(arr, "RGB").resize((4, 5), Image.BILINEAR)
    )
    np.testing.assert_array_equal(out, ref)


def test_read_images_skip_counts_decode_errors(tpu_session, tmp_path):
    """on_error="skip" (default) drops corrupt files but advances the
    data.decode_errors counter — drops are observable, never silent."""
    from sparkdl_tpu.utils.metrics import metrics

    (tmp_path / "bad1.png").write_bytes(b"not an image")
    (tmp_path / "bad2.png").write_bytes(b"\x89PNG\r\n but truncated")
    Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(tmp_path / "ok.png")
    before = metrics.counter("data.decode_errors").value
    df = readImages(str(tmp_path), session=tpu_session)
    assert df.count() == 1
    assert metrics.counter("data.decode_errors").value == before + 2


def test_read_images_raise_names_corrupt_file(tpu_session, tmp_path):
    from sparkdl_tpu.image.imageIO import ImageDecodeError

    (tmp_path / "corrupt.png").write_bytes(b"nope")
    Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(tmp_path / "ok.png")
    # this engine's mapPartitions evaluates eagerly, so the read itself
    # raises (on Spark it would surface at the first action)
    with pytest.raises(ImageDecodeError, match="corrupt.png"):
        readImages(str(tmp_path), session=tpu_session, on_error="raise")


def test_read_images_rejects_bad_on_error(tpu_session, image_dir):
    with pytest.raises(ValueError, match="on_error"):
        readImages(image_dir, session=tpu_session, on_error="ignore")


def test_custom_decode_fn_exception_is_wrapped(tpu_session, tmp_path):
    """A decode_f that raises (instead of returning None) follows the same
    policy: counted+skipped by default, ImageDecodeError with the origin
    and cause under on_error="raise"."""
    from sparkdl_tpu.image.imageIO import ImageDecodeError, readImagesWithCustomFn

    Image.fromarray(np.zeros((4, 4, 3), np.uint8)).save(tmp_path / "a.png")

    def angry_decode(raw, origin):
        raise RuntimeError("decoder exploded")

    df = readImagesWithCustomFn(
        str(tmp_path), decode_f=angry_decode, session=tpu_session
    )
    assert df.count() == 0  # skipped, not raised

    with pytest.raises(ImageDecodeError, match="a.png") as ei:
        readImagesWithCustomFn(
            str(tmp_path), decode_f=angry_decode, session=tpu_session,
            on_error="raise",
        )
    assert isinstance(ei.value.cause, RuntimeError)


# ----------------------------------------------------------------------
# the listing, the read, and the decode on a pool of threads
# ----------------------------------------------------------------------
def _numpy_round_trip_decode(raw, origin=""):
    """The decode as it was before the raw packing (asarray → reverse the
    channels → ascontiguousarray → tobytes): the reference every struct's
    bytes are held to."""
    try:
        img = Image.open(io.BytesIO(raw))
        if img.mode not in ("L", "RGB", "RGBA"):
            img = img.convert("RGB")
        arr = np.asarray(img)
    except Exception:
        return None
    if arr.ndim == 3:
        return rgbArrayToStruct(arr, origin)
    return imageArrayToStruct(arr, origin)


def _encoded(mode, fmt, seed=0, size=(23, 17)):
    """One image of PIL ``mode`` (``P``: a palette image) as file bytes."""
    rng = np.random.RandomState(seed)
    channels = {"L": (), "RGB": (3,), "RGBA": (4,), "P": (3,), "CMYK": (4,)}
    arr = rng.randint(0, 255, size + channels[mode], dtype=np.uint8)
    if mode == "P":
        img = Image.fromarray(arr, "RGB").quantize(16)
    else:
        img = Image.fromarray(arr, mode)
    buf = io.BytesIO()
    img.save(buf, format=fmt)
    return buf.getvalue()


@pytest.mark.parametrize("mode,fmt", [
    ("L", "PNG"), ("L", "JPEG"), ("RGB", "PNG"), ("RGB", "JPEG"),
    ("RGBA", "PNG"), ("P", "PNG"), ("P", "GIF"), ("CMYK", "JPEG"),
])
def test_decode_bytes_equal_the_numpy_round_trip(mode, fmt):
    raw = _encoded(mode, fmt, seed=3)
    assert Image.open(io.BytesIO(raw)).mode == mode
    got = imageIO._decode_image_bytes(raw, "mem://x")
    want = _numpy_round_trip_decode(raw, "mem://x")
    assert got == want  # every field, and ``data`` byte for byte
    assert got.__fields__() == list(imageSchema.fieldNames)
    assert len(got.data) == got.height * got.width * got.nChannels
    assert all(type(got[f]) is int
               for f in ("height", "width", "nChannels", "mode"))


@pytest.mark.parametrize("raw", [b"not an image", b"\x89PNG\r\n cut", b""],
                         ids=["garbage", "truncated", "empty"])
def test_decode_bytes_none_for_undecodable(raw):
    assert imageIO._decode_image_bytes(raw, "x") is None


@pytest.mark.parametrize("cpus,n_items,width", [
    (8, 0, 1), (8, 3, 1), (8, 4, 2), (8, 15, 7), (8, 16, 8), (8, 1024, 8),
    (13, 1024, 13), (1, 1024, 1), (2, 1024, 2),
])
def test_pool_width_follows_the_cpus_and_the_partition(
        monkeypatch, cpus, n_items, width):
    monkeypatch.setattr(
        imageIO.os, "sched_getaffinity", lambda pid: set(range(cpus)),
        raising=False)
    assert imageIO._pool_width(n_items) == width


def test_pool_width_without_affinity_uses_cpu_count(monkeypatch):
    monkeypatch.delattr(imageIO.os, "sched_getaffinity", raising=False)
    monkeypatch.setattr(imageIO.os, "cpu_count", lambda: 6)
    assert imageIO._pool_width(100) == 6
    monkeypatch.setattr(imageIO.os, "cpu_count", lambda: None)
    assert imageIO._pool_width(100) == 1


@pytest.mark.parametrize("trailing_slash", [False, True])
def test_list_files_keeps_files_and_links_to_files_sorted(tmp_path, trailing_slash):
    import os

    (tmp_path / "b.png").write_bytes(b"b")
    (tmp_path / "a.jpg").write_bytes(b"a")
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "inner.png").write_bytes(b"i")
    (tmp_path / "c_link.png").symlink_to(tmp_path / "a.jpg")
    (tmp_path / "d_dangling.png").symlink_to(tmp_path / "gone.png")
    (tmp_path / "e_dirlink").symlink_to(tmp_path / "sub")
    path = str(tmp_path) + ("/" if trailing_slash else "")
    want = sorted(
        os.path.join(path, f) for f in os.listdir(path)
        if os.path.isfile(os.path.join(path, f)))
    assert [os.path.basename(f) for f in want] == ["a.jpg", "b.png", "c_link.png"]
    assert imageIO._list_files(path) == want
    assert imageIO._list_files(str(tmp_path / "[bc]*.png")) == [
        str(tmp_path / n) for n in ("b.png", "c_link.png")]


@pytest.fixture
def mixed_dir(tmp_path):
    """40 files above the inline threshold: mixed sizes, JPEG and PNG, L,
    RGB, RGBA and palette — in a listing order that mixes them."""
    kinds = [("RGB", "JPEG", "jpg"), ("RGB", "PNG", "png"), ("L", "PNG", "png"),
             ("RGBA", "PNG", "png"), ("P", "PNG", "png"), ("L", "JPEG", "jpg")]
    for i in range(40):
        mode, fmt, ext = kinds[i % len(kinds)]
        size = (9 + 5 * (i % 7), 11 + 3 * (i % 5))
        (tmp_path / f"f{i:03d}.{ext}").write_bytes(
            _encoded(mode, fmt, seed=i, size=size))
    return tmp_path


def _inline(monkeypatch):
    monkeypatch.setattr(imageIO, "_pool_width", lambda n_items: 1)


def _pooled(monkeypatch, width=4):
    monkeypatch.setattr(
        imageIO, "_pool_width", lambda n_items: width if n_items else 1)


def _io_threads():
    return [t for t in threading.enumerate() if t.name.startswith("sparkdl-image-io")]


@pytest.mark.parametrize("partitions", [1, 3])
def test_pooled_decode_equals_inline(tpu_session, mixed_dir, monkeypatch, partitions):
    seen = set()
    decode = imageIO._decode_image_bytes

    def watching(raw, origin):
        seen.add(threading.get_ident())
        return decode(raw, origin)

    monkeypatch.setattr(imageIO, "_decode_image_bytes", watching)
    _pooled(monkeypatch)
    pooled = readImages(
        str(mixed_dir), session=tpu_session, numPartitions=partitions).collect()
    assert len(seen) > 1 and threading.get_ident() not in seen
    assert _io_threads() == []  # no thread outlives the call
    _inline(monkeypatch)
    seen.clear()
    inline = readImages(
        str(mixed_dir), session=tpu_session, numPartitions=partitions).collect()
    assert seen == {threading.get_ident()}
    assert [r.filePath for r in pooled] == sorted(
        str(p) for p in mixed_dir.iterdir())
    assert pooled == inline  # order, origins and every struct's bytes
    assert {r.image.nChannels for r in pooled} == {1, 3, 4}
    for r in pooled:  # and both equal the decode as it was
        with open(r.filePath, "rb") as fh:
            assert r.image == _numpy_round_trip_decode(fh.read(), r.filePath)


def test_files_to_df_rows_are_the_files_in_listing_order(tpu_session, mixed_dir):
    rows = filesToDF(tpu_session, str(mixed_dir), numPartitions=2).collect()
    paths = sorted(mixed_dir.iterdir())
    assert [r.filePath for r in rows] == [str(p) for p in paths]
    assert [r.fileData for r in rows] == [p.read_bytes() for p in paths]
    assert all(type(r.fileData) is bytes for r in rows)


def test_files_to_df_raises_the_first_unreadable_file(
        tpu_session, mixed_dir, monkeypatch):
    names = sorted(str(p) for p in mixed_dir.iterdir())
    unreadable = {names[7], names[21]}
    read = imageIO._read_file

    def failing(path):
        if path in unreadable:
            raise PermissionError(path)
        return read(path)

    monkeypatch.setattr(imageIO, "_read_file", failing)
    with pytest.raises(PermissionError) as ei:
        filesToDF(tpu_session, str(mixed_dir))
    assert ei.value.args == (names[7],)


@pytest.mark.parametrize("width", [1, 4], ids=["inline", "pooled"])
def test_raise_names_the_first_corrupt_file_in_listing_order(
        tpu_session, mixed_dir, monkeypatch, width):
    for name in ("f005.png", "f020.png", "f031.jpg"):
        (mixed_dir / name).write_bytes(b"corrupt " + name.encode())
    _pooled(monkeypatch, width)
    with pytest.raises(ImageDecodeError, match="f005.png") as ei:
        readImages(str(mixed_dir), session=tpu_session, numPartitions=1,
                   on_error="raise")
    assert ei.value.origin == str(mixed_dir / "f005.png")
    assert ei.value.cause is None  # the null-tolerant decode returned None
    assert _io_threads() == []

    def angry(raw, origin):
        if raw.startswith(b"corrupt"):
            raise RuntimeError(f"exploded on {origin}")
        return imageIO._decode_image_bytes(raw, origin)

    with pytest.raises(ImageDecodeError, match="f005.png") as ei:
        imageIO.readImagesWithCustomFn(
            str(mixed_dir), decode_f=angry, session=tpu_session,
            numPartitions=1, on_error="raise")
    assert isinstance(ei.value.cause, RuntimeError)
    assert ei.value.__cause__ is ei.value.cause
    assert "f005.png" in str(ei.value.cause)
    assert _io_threads() == []


@pytest.mark.parametrize("width", [1, 4], ids=["inline", "pooled"])
def test_skip_counts_each_corrupt_file_and_keeps_the_survivors_order(
        tpu_session, mixed_dir, monkeypatch, caplog, width):
    from sparkdl_tpu.utils.metrics import metrics

    corrupt = ["f002.png", "f003.png", "f017.jpg", "f039.png"]
    for name in corrupt:
        (mixed_dir / name).write_bytes(b"corrupt")
    _pooled(monkeypatch, width)
    before = metrics.counter("data.decode_errors").value
    logged_from = set()

    class Where(logging.Handler):
        def emit(self, record):
            logged_from.add(record.thread)

    handler = Where()
    imageIO.logger.addHandler(handler)
    try:
        with caplog.at_level(logging.WARNING, logger=imageIO.logger.name):
            rows = readImages(
                str(mixed_dir), session=tpu_session, numPartitions=2).collect()
    finally:
        imageIO.logger.removeHandler(handler)
    assert metrics.counter("data.decode_errors").value == before + len(corrupt)
    survivors = sorted(
        str(p) for p in mixed_dir.iterdir() if p.name not in corrupt)
    assert [r.filePath for r in rows] == survivors
    assert [r.image.origin for r in rows] == survivors
    dropped = [m for m in caplog.messages if "dropping undecodable" in m]
    assert [m.rsplit("/", 1)[1] for m in dropped] == corrupt
    assert logged_from == {threading.get_ident()}  # the consuming thread


def test_custom_decode_fn_keeps_order_under_the_pool(
        tpu_session, mixed_dir, monkeypatch):
    import time

    names = sorted(str(p) for p in mixed_dir.iterdir())
    threads = set()

    def slow_then_fast(raw, origin):
        # early files finish last: order must not follow completion
        time.sleep(0.02 if names.index(origin) < 8 else 0.0)
        threads.add(threading.get_ident())
        return imageArrayToStruct(
            np.full((1, 1, 3), names.index(origin), np.uint8), origin)

    _pooled(monkeypatch, 8)
    rows = imageIO.readImagesWithCustomFn(
        str(mixed_dir), decode_f=slow_then_fast, session=tpu_session,
        numPartitions=1).collect()
    assert len(threads) > 1
    assert [r.filePath for r in rows] == names
    assert [r.image.data[0] for r in rows] == list(range(len(names)))
    assert _io_threads() == []


def test_pool_survives_a_stress_of_switches(tpu_session, mixed_dir, monkeypatch):
    """More workers than cores and a short switch interval: every result
    still lands in its own slot."""
    import sys

    _inline(monkeypatch)
    want = readImages(str(mixed_dir), session=tpu_session, numPartitions=1).collect()
    _pooled(monkeypatch, 20)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(5):
            got = readImages(
                str(mixed_dir), session=tpu_session, numPartitions=1).collect()
            assert got == want
    finally:
        sys.setswitchinterval(interval)
    assert _io_threads() == []
