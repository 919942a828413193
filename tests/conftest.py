"""Test harness: virtual 8-device CPU mesh + keras-jax backend.

Reference analog: ``python/tests/tests.py``† ``SparkDLTestCase`` creates a
``local[*]`` SparkSession so distributed behavior is testable in-process
(SURVEY.md §4).  Here the analog is an 8-device virtual CPU platform
(``--xla_force_host_platform_device_count=8``) so ``Mesh``/``psum``/DP paths
are exercised without TPU hardware.  These env vars must be set before jax
initializes its backends, hence module import time in conftest; nothing
imports jax before this file does, so the environment alone is enough.
"""

import os

os.environ.setdefault("KERAS_BACKEND", "jax")
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tpu_session():
    """A fresh engine session (SparkSession analog) shared per test session."""
    from sparkdl_tpu.sql.session import TPUSession

    return TPUSession.builder.master("local[*]").appName("tests").getOrCreate()


@pytest.fixture(scope="session")
def image_dir(tmp_path_factory):
    """Generate a handful of small JPEG/PNG fixtures (reference keeps real
    files under ``python/tests/resources/images/``†; we synthesize
    deterministically instead of committing binaries)."""
    from PIL import Image

    root = tmp_path_factory.mktemp("images")
    rng = np.random.RandomState(0)
    for i in range(6):
        arr = rng.randint(0, 255, size=(60 + 10 * i, 80, 3), dtype=np.uint8)
        img = Image.fromarray(arr)
        if i % 2 == 0:
            img.save(root / f"img_{i}.png")
        else:
            img.save(root / f"img_{i}.jpg", quality=95)
    # one grayscale
    Image.fromarray(rng.randint(0, 255, (40, 50), dtype=np.uint8)).save(
        root / "gray.png"
    )
    return str(root)
