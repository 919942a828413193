"""Transformer integration tests (local engine + CPU jax).

Reference pattern (SURVEY.md §4): transformer output is compared against
directly running the same model on the same decoded arrays — the oracle is
plain Keras / numpy, tolerance-based (``named_image_test.py``†,
``tf_image_test.py``†, ``keras_tensor_test.py``†).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from sparkdl_tpu.graph.function import XlaFunction
from sparkdl_tpu.image import imageIO
from sparkdl_tpu.ml.classification import LogisticRegression
from sparkdl_tpu.ml.evaluation import MulticlassClassificationEvaluator
from sparkdl_tpu.ml.pipeline import Pipeline
from sparkdl_tpu.models import get_keras_application_model

keras = pytest.importorskip("keras")


@pytest.fixture(scope="module")
def mobilenet_oracle():
    entry = get_keras_application_model("MobileNetV2")
    km = entry.keras_model(weights=None)
    return entry, km, entry.load_variables(km)


@pytest.fixture()
def image_df(tpu_session, image_dir):
    return imageIO.readImages(image_dir, tpu_session, numPartitions=2)


def _decoded_rgb_images(df, input_col="image"):
    out = []
    for row in df.collect():
        arr = imageIO.imageStructToArray(row[input_col]).astype(np.float32)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        if arr.shape[-1] == 1:
            arr = np.repeat(arr, 3, axis=-1)
        arr = arr[:, :, ::-1]  # stored BGR -> RGB
        out.append(arr)
    return out


# ---------------------------------------------------------------------------
# TFImageTransformer
# ---------------------------------------------------------------------------


def test_tf_image_transformer_vector_vs_numpy_oracle(image_df):
    from sparkdl_tpu.transformers.tf_image import TFImageTransformer

    fn = XlaFunction.from_callable(
        lambda x: jnp.mean(x, axis=(1, 2)), name="chan_mean"
    )
    t = TFImageTransformer(
        inputCol="image",
        outputCol="out",
        graph=fn,
        inputShape=(64, 64),
        channelOrder="RGB",
        batchSize=4,
    )
    result = t.transform(image_df)
    got = {r["filePath"]: np.asarray(r["out"]) for r in
           result.select("filePath", "out").collect()}

    # oracle: same decode -> same resize -> channel mean, plain jax on host
    from sparkdl_tpu.transformers.utils import normalize_channels

    rows = image_df.collect()
    for row in rows:
        arr = normalize_channels(
            imageIO.imageStructToArray(row["image"]).astype(np.float32), 3
        )
        rgb = arr[:, :, ::-1]
        resized = np.asarray(
            jax.image.resize(
                jnp.asarray(rgb)[None],
                (1, 64, 64, rgb.shape[-1]),
                "bilinear",
            )
        )[0]
        want = resized.mean(axis=(0, 1))
        np.testing.assert_allclose(
            got[row["filePath"]], want, rtol=1e-4, atol=1e-3
        )


def test_tf_image_transformer_image_output_mode(image_df):
    from sparkdl_tpu.transformers.tf_image import TFImageTransformer

    fn = XlaFunction.from_callable(lambda x: x * 0.5, name="halve")
    t = TFImageTransformer(
        inputCol="image",
        outputCol="out",
        graph=fn,
        inputShape=(32, 32),
        outputMode="image",
    )
    # only 3-channel rows: drop the grayscale fixture
    df = image_df.filter(lambda r: r["image"]["nChannels"] == 3)
    out_rows = t.transform(df).collect()
    assert out_rows
    for r in out_rows:
        struct = r["out"]
        assert struct["height"] == 32 and struct["width"] == 32
        arr = imageIO.imageStructToArray(struct)
        assert arr.dtype == np.float32


# ---------------------------------------------------------------------------
# DeepImageFeaturizer / DeepImagePredictor
# ---------------------------------------------------------------------------


def test_deep_image_featurizer_vs_keras_oracle(image_df, mobilenet_oracle):
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    entry, km, variables = mobilenet_oracle
    featurizer = DeepImageFeaturizer(
        inputCol="image",
        outputCol="features",
        modelName="MobileNetV2",
        modelWeights=variables,
        computeDtype="float32",
        batchSize=4,
    )
    result = featurizer.transform(image_df)
    got = {r["filePath"]: np.asarray(r["features"]) for r in
           result.select("filePath", "features").collect()}
    assert all(v.shape == (entry.feature_size,) for v in got.values())

    # oracle: same decode -> jax resize -> preprocess -> features cut
    rows = image_df.collect()
    h, w = entry.input_size
    for row in rows:
        arr = imageIO.imageStructToArray(row["image"]).astype(np.float32)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        if arr.shape[-1] == 1:
            arr = np.repeat(arr, 3, axis=-1)
        rgb = arr[:, :, ::-1]
        resized = np.asarray(
            jax.image.resize(jnp.asarray(rgb)[None], (1, h, w, 3), "bilinear")
        )
        pre = np.asarray(entry.preprocess(jnp.asarray(resized)))
        fm = entry.make_module()
        want = np.asarray(
            jax.jit(lambda v, a: fm.apply(v, a, features_only=True))(
                variables, jnp.asarray(pre)
            )
        )[0]
        np.testing.assert_allclose(
            got[row["filePath"]], want, rtol=1e-3, atol=1e-3
        )


def test_deep_image_predictor_decoded(image_df, mobilenet_oracle):
    from sparkdl_tpu.transformers.named_image import DeepImagePredictor

    entry, km, variables = mobilenet_oracle
    predictor = DeepImagePredictor(
        inputCol="image",
        outputCol="preds",
        modelName="MobileNetV2",
        modelWeights=variables,
        decodePredictions=True,
        topK=3,
        computeDtype="float32",
    )
    rows = predictor.transform(image_df).collect()
    for r in rows:
        preds = r["preds"]
        assert len(preds) == 3
        probs = [p["probability"] for p in preds]
        assert probs == sorted(probs, reverse=True)
        assert all(0.0 <= p <= 1.0 for p in probs)


def test_named_transformer_rejects_unknown_model():
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    with pytest.raises(ValueError, match="Unsupported model name"):
        DeepImageFeaturizer(
            inputCol="image", outputCol="f", modelName="NopeNet"
        )._build_forward()


# ---------------------------------------------------------------------------
# TFTransformer / KerasTransformer (tensor columns)
# ---------------------------------------------------------------------------


def test_tf_transformer_mappings(tpu_session):
    from sparkdl_tpu.transformers.tf_tensor import TFTransformer

    rng = np.random.RandomState(0)
    vecs = [rng.rand(8).astype(np.float32) for _ in range(11)]
    df = tpu_session.createDataFrame([{"x": v} for v in vecs])

    fn = XlaFunction.from_callable(
        lambda x: (x * 2.0, jnp.sum(x, axis=-1)),
        input_names=("inp",),
        output_names=("doubled", "total"),
        name="double_sum",
    )
    t = TFTransformer(
        tfInputGraph=fn,
        inputMapping={"x": "inp"},
        outputMapping={"doubled": "x2", "total": "sum"},
        batchSize=4,
    )
    rows = t.transform(df).collect()
    for row, v in zip(rows, vecs):
        np.testing.assert_allclose(row["x2"], v * 2, rtol=1e-6)
        np.testing.assert_allclose(row["sum"], v.sum(), rtol=1e-5)


def test_tf_transformer_bad_mapping(tpu_session):
    from sparkdl_tpu.transformers.tf_tensor import TFTransformer

    df = tpu_session.createDataFrame([{"x": np.zeros(3, np.float32)}])
    fn = XlaFunction.from_callable(lambda x: x, name="id")
    with pytest.raises(ValueError, match="Unknown function outputs"):
        TFTransformer(
            tfInputGraph=fn,
            inputMapping={"x": "input"},
            outputMapping={"nope": "y"},
        ).transform(df)


def test_keras_transformer_vs_keras_oracle(tpu_session, tmp_path):
    from sparkdl_tpu.transformers.keras_tensor import KerasTransformer

    model = keras.Sequential(
        [
            keras.layers.Input(shape=(10,)),
            keras.layers.Dense(7, activation="relu"),
            keras.layers.Dense(3),
        ]
    )
    path = str(tmp_path / "model.keras")
    model.save(path)

    rng = np.random.RandomState(1)
    vecs = [rng.rand(10).astype(np.float32) for _ in range(9)]
    df = tpu_session.createDataFrame([{"x": v} for v in vecs])
    t = KerasTransformer(inputCol="x", outputCol="y", modelFile=path,
                         batchSize=4)
    rows = t.transform(df).collect()
    want = np.asarray(model(np.stack(vecs)))
    got = np.stack([np.asarray(r["y"]) for r in rows])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_keras_image_file_transformer(tpu_session, image_dir, tmp_path):
    from sparkdl_tpu.transformers.keras_image import KerasImageFileTransformer
    from PIL import Image

    model = keras.Sequential(
        [
            keras.layers.Input(shape=(16, 16, 3)),
            keras.layers.Flatten(),
            keras.layers.Dense(4),
        ]
    )
    path = str(tmp_path / "img_model.keras")
    model.save(path)

    def loader(uri):
        img = Image.open(uri).convert("RGB").resize((16, 16))
        return np.asarray(img, dtype=np.float32) / 255.0

    df = imageIO.filesToDF(tpu_session, image_dir, numPartitions=2)
    t = KerasImageFileTransformer(
        inputCol="filePath",
        outputCol="out",
        modelFile=path,
        imageLoader=loader,
        batchSize=4,
    )
    rows = t.transform(df).select("filePath", "out").collect()
    for r in rows:
        want = np.asarray(model(loader(r["filePath"])[None]))[0]
        np.testing.assert_allclose(
            np.asarray(r["out"]), want, rtol=1e-4, atol=1e-5
        )


def test_keras_image_file_transformer_bf16(tpu_session, image_dir, tmp_path):
    """computeDtype='bfloat16' loads the saved model under the
    mixed_bfloat16 policy; outputs match f32 within bf16 tolerance."""
    from PIL import Image

    from sparkdl_tpu.transformers.keras_image import KerasImageFileTransformer

    model = keras.Sequential(
        [
            keras.layers.Input(shape=(16, 16, 3)),
            keras.layers.Conv2D(8, 3, padding="same", activation="relu"),
            keras.layers.GlobalAveragePooling2D(),
        ]
    )
    path = str(tmp_path / "bf16_model.keras")
    model.save(path)

    def loader(uri):
        img = Image.open(uri).convert("RGB").resize((16, 16))
        return np.asarray(img, dtype=np.float32) / 255.0

    df = imageIO.filesToDF(tpu_session, image_dir, numPartitions=2)

    def run(dtype):
        t = KerasImageFileTransformer(
            inputCol="filePath", outputCol="out", modelFile=path,
            imageLoader=loader, batchSize=4, computeDtype=dtype,
        )
        rows = t.transform(df).select("filePath", "out").collect()
        return {r["filePath"]: np.asarray(r["out"]) for r in rows}

    f32 = run("float32")
    bf16 = run("bfloat16")
    assert f32.keys() == bf16.keys()
    for k in f32:
        np.testing.assert_allclose(bf16[k], f32[k], rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# LogisticRegression head + flagship pipeline slice
# ---------------------------------------------------------------------------


def test_logistic_regression_separable(tpu_session):
    rng = np.random.RandomState(0)
    x0 = rng.randn(30, 4).astype(np.float32) + 3
    x1 = rng.randn(30, 4).astype(np.float32) - 3
    data = [{"features": v, "label": 0} for v in x0] + [
        {"features": v, "label": 1} for v in x1
    ]
    df = tpu_session.createDataFrame(data).repartition(3)
    lr = LogisticRegression(maxIter=200, stepSize=0.5)
    model = lr.fit(df)
    pred = model.transform(df)
    acc = MulticlassClassificationEvaluator(metricName="accuracy").evaluate(pred)
    assert acc == 1.0
    f1 = MulticlassClassificationEvaluator(metricName="f1").evaluate(pred)
    assert f1 == 1.0


def test_flagship_pipeline_featurizer_plus_lr(image_df, mobilenet_oracle):
    """The minimum end-to-end slice (SURVEY.md §7 step 4): DeepImageFeaturizer
    -> LogisticRegression as a Pipeline, mirroring the reference's tf-flowers
    transfer-learning flow."""
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    entry, km, variables = mobilenet_oracle
    labeled = image_df.withColumn(
        "label", lambda p: hash(p) % 2, "filePath"
    )
    pipeline = Pipeline(
        stages=[
            DeepImageFeaturizer(
                inputCol="image",
                outputCol="features",
                modelName="MobileNetV2",
                modelWeights=variables,
                computeDtype="float32",
            ),
            LogisticRegression(maxIter=100, stepSize=0.5),
        ]
    )
    model = pipeline.fit(labeled)
    scored = model.transform(labeled)
    assert "prediction" in scored.columns and "features" in scored.columns
    # plumbing correctness, not learning quality (random-noise fixtures give
    # near-identical GAP features — the reference's estimator tests assert
    # plumbing the same way, SURVEY.md §4)
    preds = {r["prediction"] for r in scored.collect()}
    assert preds <= {0.0, 1.0}
    acc = MulticlassClassificationEvaluator().evaluate(scored)
    assert 0.0 <= acc <= 1.0


def test_featurizer_missing_imagenet_weights_raises(image_df):
    """Offline with no Keras weight cache: default 'imagenet' weights must
    fail loudly, not silently random-initialize (random features posing as
    imagenet features look valid but are garbage)."""
    from sparkdl_tpu.transformers import named_image
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    if named_image._imagenet_cache_present("MobileNetV2"):
        pytest.skip("local imagenet cache exists; raise path not reachable")
    featurizer = DeepImageFeaturizer(
        inputCol="image", outputCol="features", modelName="MobileNetV2"
    )
    with pytest.raises(RuntimeError, match="imagenet weights"):
        featurizer.transform(image_df).collect()


def test_featurizer_random_weights_opt_in(image_df):
    """modelWeights='random' is the explicit, deterministic opt-in."""
    from sparkdl_tpu.transformers.named_image import DeepImageFeaturizer

    kwargs = dict(
        inputCol="image",
        outputCol="features",
        modelName="MobileNetV2",
        modelWeights="random",
        computeDtype="float32",
        batchSize=4,
    )
    a = DeepImageFeaturizer(**kwargs).transform(image_df).collect()
    b = DeepImageFeaturizer(**kwargs).transform(image_df).collect()
    va = np.asarray(a[0]["features"])
    assert np.isfinite(va).all() and va.shape == (1280,)
    np.testing.assert_array_equal(va, np.asarray(b[0]["features"]))


def test_tf_transformer_preserves_integer_columns(tpu_session):
    """Integer tensor columns must keep integral dtype through the engine
    (previously cast to float32 silently)."""
    from sparkdl_tpu.graph.function import XlaFunction
    from sparkdl_tpu.transformers.tf_tensor import TFTransformer

    fn = XlaFunction.from_callable(
        lambda x: x * 2, input_names=("ids",), output_names=("doubled",)
    )
    df = tpu_session.createDataFrame(
        [([1, 2, 3],), ([4, 5, 6],)], ["ids"]
    )
    t = TFTransformer(
        tfInputGraph=fn,
        inputMapping={"ids": "ids"},
        outputMapping={"doubled": "out"},
    )
    rows = t.transform(df).collect()
    out = np.asarray(rows[0]["out"])
    assert np.issubdtype(out.dtype, np.integer), out.dtype
    np.testing.assert_array_equal(out, [2, 4, 6])


def test_keras_image_transformer_ragged_loader_raises(
    tpu_session, image_dir, tmp_path
):
    """A loader producing mixed shapes must fail with a named error, not a
    cryptic np.stack failure."""
    import keras

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.transformers.keras_image import KerasImageFileTransformer

    model = keras.Sequential(
        [keras.layers.Input((8, 8, 3)), keras.layers.Flatten()]
    )
    path = str(tmp_path / "flat.keras")
    model.save(path)

    sizes = iter([(8, 8), (9, 9), (8, 8), (9, 9), (8, 8), (9, 9), (8, 8)])

    def ragged_loader(uri):
        from PIL import Image

        return np.asarray(
            Image.open(uri).convert("RGB").resize(next(sizes)),
            dtype=np.float32,
        )

    df = imageIO.filesToDF(tpu_session, image_dir, numPartitions=1)
    t = KerasImageFileTransformer(
        inputCol="filePath",
        outputCol="out",
        modelFile=path,
        imageLoader=ragged_loader,
    )
    with pytest.raises(ValueError, match="imageLoader"):
        t.transform(df).collect()


# ---------------------------------------------------------------------------
# LRUCache — eviction order (the process-lifetime program/model caches)
# ---------------------------------------------------------------------------


def test_lru_cache_evicts_least_recently_used():
    from sparkdl_tpu.transformers.utils import LRUCache

    c = LRUCache(maxsize=2)
    c["a"], c["b"] = 1, 2
    _ = c["a"]  # touch: "a" is now most recent
    c["c"] = 3  # evicts "b", not "a"
    assert "a" in c and "c" in c and "b" not in c


def test_lru_cache_setitem_refreshes_recency():
    from sparkdl_tpu.transformers.utils import LRUCache

    c = LRUCache(maxsize=2)
    c["a"], c["b"] = 1, 2
    c["a"] = 10  # overwrite counts as use
    c["c"] = 3
    assert c.get("a") == 10 and "b" not in c
    # iteration runs LRU -> MRU; the get("a") above refreshed "a"
    assert list(c) == ["c", "a"]


def test_lru_cache_eviction_is_fifo_without_touches():
    from sparkdl_tpu.transformers.utils import LRUCache

    c = LRUCache(maxsize=3)
    for i, k in enumerate("abcde"):
        c[k] = i
    assert list(c) == ["c", "d", "e"]  # a then b evicted, in order


# ---------------------------------------------------------------------------
# mixed-shape device resize
# (regression for the host-sync finding sparkdl_check surfaced:
# _device_resize_timed used to np.asarray each shape group's result
# before dispatching the next, serializing the groups; it now dispatches
# every group, then fetches them in order)
# ---------------------------------------------------------------------------


def test_mixed_shape_resize_correct_per_image():
    from sparkdl_tpu.transformers.utils import device_resize as _resize_images

    rng = np.random.default_rng(7)
    # two distinct source shapes (= _MAX_DEVICE_RESIZE_SHAPES, so the
    # device path runs) plus images already at target size, interleaved
    # so scatter order matters
    shapes = [(8, 6, 3), (4, 4, 3), (6, 8, 3), (8, 6, 3), (4, 4, 3),
              (6, 8, 3), (8, 6, 3)]
    images = [rng.uniform(0, 255, s).astype(np.float32) for s in shapes]

    out = _resize_images(images, (4, 4))
    assert out.shape == (len(images), 4, 4, 3)

    for i, img in enumerate(images):
        if img.shape[:2] == (4, 4):
            want = img
        else:
            want = np.asarray(jax.image.resize(
                jnp.asarray(img)[None], (1, 4, 4, 3), method="bilinear"
            ))[0]
        np.testing.assert_allclose(
            out[i], want, rtol=1e-5, atol=1e-4,
            err_msg=f"row {i} (source shape {img.shape}) scrambled or wrong",
        )


# ---------------------------------------------------------------------------
# the one stage seam: every batched DataFrame stage hands ALL partitions of
# a transform to one loop (transformers.utils.transform_batched)
# ---------------------------------------------------------------------------

# three unequal partitions, one of them empty; in batches of 8 the first
# has three chunks (the last ragged) and the last has one
_STAGE_PARTITIONS = [20, 0, 7]


def _stage_loader(uri):
    return np.random.RandomState(int(uri)).rand(6, 6, 3).astype(np.float32)


@pytest.fixture
def stage_case(request, tpu_session, tmp_path, monkeypatch):
    """``(stage, frame, partitions)`` for the stage named by the parameter:
    the stage at batch size 8, what makes a DataFrame of given partitions,
    and the partitions (column dicts) of ``_STAGE_PARTITIONS`` rows."""
    import types

    name = request.param
    rng = np.random.RandomState(5)
    n = sum(_STAGE_PARTITIONS)
    if name in ("keras-file", "flax-file"):
        col, values = "uri", [str(i) for i in range(n)]
    else:
        col, values = "image", [
            imageIO.imageArrayToStruct(
                rng.randint(0, 255, (12, 12, 3)).astype(np.uint8),
                origin=f"o{i}")
            for i in range(n)
        ]
    parts, lo = [], 0
    for k in _STAGE_PARTITIONS:
        parts.append({col: values[lo:lo + k]})
        lo += k

    if name in ("tf-vector", "tf-image"):
        from sparkdl_tpu.transformers.tf_image import TFImageTransformer

        stage = TFImageTransformer(
            inputCol=col, outputCol="out", inputShape=(8, 8), batchSize=8,
            outputMode=name[3:],
            graph=XlaFunction.from_callable(
                lambda x: jnp.tanh(x / 255.0), name="squash"),
        )
    elif name == "keras-file":
        from sparkdl_tpu.transformers.keras_image import (
            KerasImageFileTransformer,
        )

        model = keras.Sequential([
            keras.layers.Input(shape=(6, 6, 3)),
            keras.layers.Flatten(),
            keras.layers.Dense(4),
        ])
        path = str(tmp_path / "stage_model.keras")
        model.save(path)
        stage = KerasImageFileTransformer(
            inputCol=col, outputCol="out", modelFile=path,
            imageLoader=_stage_loader, batchSize=8)
    elif name == "flax-file":
        import flax.linen as nn

        from sparkdl_tpu.estimators import FlaxImageFileTransformer

        class Head(nn.Module):
            @nn.compact
            def __call__(self, x, features_only=False):
                return nn.Dense(3)(x.reshape(x.shape[0], -1))

        module = Head()
        variables = module.init(
            jax.random.PRNGKey(0), np.zeros((1, 6, 6, 3), np.float32))
        stage = FlaxImageFileTransformer(
            inputCol=col, outputCol="out", imageLoader=_stage_loader,
            module=module, variables=variables, batchSize=8)
    else:
        from sparkdl_tpu.transformers.named_image import DeepImagePredictor
        from sparkdl_tpu.transformers.utils import cast_and_resize_on_device

        @jax.jit
        def forward(x):  # a small program in the CNN's place
            x = cast_and_resize_on_device(x, (12, 12))
            return jnp.tanh(x / 255.0).mean(axis=1).reshape(x.shape[0], -1)

        stage = DeepImagePredictor(
            inputCol=col, outputCol="out", modelName="InceptionV3",
            batchSize=8)
        monkeypatch.setattr(
            stage, "_build_forward",
            lambda: (forward, types.SimpleNamespace(input_size=(12, 12))))
    df = tpu_session.createDataFrame([(v,) for v in values[:1]], [col])
    return stage, df._with_partitions, parts


def _cell_bytes(value):
    """The bytes of one output value: a vector's, or an image struct's."""
    if hasattr(value, "toArray"):
        return value.toArray().tobytes()
    return (value["height"], value["width"], value["mode"],
            bytes(value["data"]))


@pytest.mark.parametrize(
    "stage_case",
    ["tf-vector", "tf-image", "keras-file", "flax-file", "predictor"],
    indirect=True)
def test_stage_over_partitions_equals_a_call_a_partition(stage_case):
    """One ``transform`` over three unequal partitions gives, byte for
    byte and partition for partition, what one ``transform`` a partition
    gives: a border is metadata, every batch holds the rows it held."""
    stage, frame, parts = stage_case
    out = stage.transform(frame(parts))._partitions
    assert [len(p["out"]) for p in out] == _STAGE_PARTITIONS
    for part, got in zip(parts, out):
        (alone,) = stage.transform(frame([part]))._partitions
        assert list(got) == list(alone)  # the columns, in order
        assert [_cell_bytes(v) for v in got["out"]] == [
            _cell_bytes(v) for v in alone["out"]]
        assert all(got[c] == part[c] for c in part)  # inputs carried over


@pytest.mark.parametrize(
    "stage_case", ["tf-vector", "keras-file", "flax-file"], indirect=True)
def test_stage_feeds_the_device_across_a_border(stage_case):
    """The stages that used to build and drain a pipeline a partition: the
    last partition's batch is dispatched before the first one's rows are
    built (``engine.borders_fed``), under ``featurize.partition`` roots."""
    from sparkdl_tpu.obs.trace import tracer
    from sparkdl_tpu.utils.metrics import metrics

    stage, frame, parts = stage_case
    borders = metrics.counter("engine.borders").value
    fed = metrics.counter("engine.borders_fed").value
    mark = tracer.clock_ns()
    stage.transform(frame(parts))
    assert metrics.counter("engine.borders").value - borders == 1
    assert metrics.counter("engine.borders_fed").value - fed == 1
    recs = [r for r in tracer.recent() if r.start_ns >= mark]
    roots = sorted((r for r in recs if r.name == "featurize.partition"),
                   key=lambda r: r.start_ns)
    assert [r.attributes["rows"] for r in roots] == [20, 7]
    post = sorted((r for r in recs if r.name == "featurize.postprocess"),
                  key=lambda r: r.start_ns)
    assert [(r.parent_id, r.attributes["inflight"]) for r in post] == [
        (roots[0].span_id, 1), (roots[1].span_id, 0)]
