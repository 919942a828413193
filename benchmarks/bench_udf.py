"""BASELINE config #4: ``registerKerasImageUDF`` SQL-path throughput.

End-to-end: image structs in a DataFrame temp view, ``SELECT udf(image)``
through the SQL layer — struct decode, channel fix, device resize, jitted
CNN, DenseVector results collected to host.  Unlike bench.py/bench_transformer
this is the *whole* serving path including host-side decode and per-batch
result fetches, so it reports the honest end-to-end
rate a SQL user sees (the reference's equivalent was TensorFrames per-block
``Session::Run`` — SURVEY.md §3.3).

Measurement protocol: ``k`` interleaved pipelined/serial trial pairs
(``benchlib.paired_trials``) with median + IQR — single-shot host-clock
numbers drift severalfold, so only interleaved medians can support (or
honestly refuse to support) the decode/dispatch-overlap claim.

Prints one JSON line; ``vs_baseline`` is null (record-only config).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

os.environ.setdefault("KERAS_BACKEND", "jax")

ROWS = 1024
BATCH = 256
IMAGE = 299


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-k", type=int, default=5,
                    help="interleaved pipelined/serial trial pairs")
    args = ap.parse_args()

    import keras

    from sparkdl_tpu.image import imageIO
    from sparkdl_tpu.sql.session import TPUSession
    from sparkdl_tpu.udf.keras_image_model import registerKerasImageUDF

    keras.utils.set_random_seed(0)
    model = keras.applications.MobileNetV2(
        weights=None, include_top=False, pooling="avg",
        input_shape=(224, 224, 3),
    )

    spark = TPUSession.builder.master("local[*]").getOrCreate()
    rng = np.random.RandomState(0)
    rows = [
        {
            "image": imageIO.imageArrayToStruct(
                rng.randint(0, 255, (IMAGE, IMAGE, 3), dtype=np.uint8)
            )
        }
        for _ in range(ROWS)
    ]
    df = spark.createDataFrame(rows).repartition(4)
    df.createOrReplaceTempView("images")

    registerKerasImageUDF(
        "bench_udf", model, session=spark, batchSize=BATCH
    )

    # warm with the real partition/batch shapes so the timed runs are
    # compile-free (a LIMIT query would warm a different batch shape)
    spark.sql("SELECT bench_udf(image) AS f FROM images").collect()

    from sparkdl_tpu.utils.benchlib import paired_trials

    def run_query(serial: bool) -> float:
        os.environ["SPARKDL_SERIAL_INFERENCE"] = "1" if serial else ""
        try:
            t0 = time.perf_counter()
            out = spark.sql("SELECT bench_udf(image) AS f FROM images").collect()
            elapsed = time.perf_counter() - t0
            assert len(out) == ROWS
            return ROWS / elapsed
        finally:
            os.environ.pop("SPARKDL_SERIAL_INFERENCE", None)

    trials = paired_trials(
        {
            "pipelined": lambda: run_query(serial=False),
            "serial": lambda: run_query(serial=True),
        },
        k=args.k,
    )
    piped, serial = trials["pipelined"], trials["serial"]
    print(
        json.dumps(
            {
                "metric": "registerKerasImageUDF(MobileNetV2) end-to-end "
                "SQL inference throughput",
                "value": piped["median"],
                "unit": "images/sec (incl. decode+collect)",
                "iqr": piped["iqr"],
                "samples": piped["samples"],
                "serial_median": serial["median"],
                "serial_iqr": serial["iqr"],
                "overlap_speedup": round(
                    piped["median"] / serial["median"], 3
                )
                if serial["median"]
                else None,
                "k": args.k,
                "vs_baseline": None,
            }
        )
    )


if __name__ == "__main__":
    sys.exit(main())
