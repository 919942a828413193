"""ctypes bridge to the native PJRT runner (``pjrt_runner.cpp``).

The second execution stack (SURVEY.md §2 "Native components", §3.5): where
the reference ran frozen GraphDefs through TensorFrames' JNI bridge into
the TF C++ runtime, this drives a PJRT plugin (any ``GetPjrtApi`` .so,
named by ``SPARKDL_PJRT_PLUGIN``) from C++ — compile a StableHLO program
once, keep params device-resident, stream batches.  Python is only the
orchestration layer here; the standalone CLI (``pjrt_tool.cpp``) removes it
entirely.

The runner opens a PJRT client of its own, so it cannot share a chip with
a process that runs JAX on it: one stack per chip at a time.

Program artifacts are directories written by :func:`export_program`:

    program.mlir         StableHLO (MLIR text), params as leading args
    params.bin           concatenated raw little-endian param leaves
    compile_options.pb   serialized xla CompileOptionsProto
    manifest.json        arg dtypes/shapes (params then data inputs), outputs

so the C++ side needs no protobuf, no Python, and no model code.
"""

from __future__ import annotations

import ctypes
import json
import logging
import os
import subprocess
import threading
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO_PATH = os.path.join(_HERE, "_pjrt_runner.so")
_SRC_PATH = os.path.join(_HERE, "pjrt_runner.cpp")

#: the plugin the stack drives; empty = none configured, and every entry
#: point then raises :class:`PjrtUnavailable`
DEFAULT_PLUGIN = os.environ.get("SPARKDL_PJRT_PLUGIN", "")


class PjrtUnavailable(RuntimeError):
    """The native stack cannot run here: no plugin is configured
    (``SPARKDL_PJRT_PLUGIN``) or the runner library could not be built."""

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
#: set while one thread runs the build/dlopen; later callers wait on it
_inflight: Optional[threading.Event] = None


def _xla_include_dir() -> Optional[str]:
    """The PJRT C API header ships inside the tensorflow wheel."""
    try:
        import tensorflow as _tf  # noqa: F401  (heavy; only for the path)

        cand = os.path.join(os.path.dirname(_tf.__file__), "include")
    except Exception:
        import sysconfig

        cand = os.path.join(
            sysconfig.get_paths()["purelib"], "tensorflow", "include"
        )
    header = os.path.join(cand, "xla", "pjrt", "c", "pjrt_c_api.h")
    return cand if os.path.exists(header) else None


def _build() -> bool:
    include = _xla_include_dir()
    if include is None:
        logger.info("pjrt runner: no pjrt_c_api.h available; skipping build")
        return False
    tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O2", "-std=c++17", "-fPIC", "-shared",
        f"-I{include}",
        "-o", tmp, _SRC_PATH, "-ldl",
    ]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        logger.info("pjrt runner build unavailable: %s", e)
        return False
    if proc.returncode != 0:
        logger.warning("pjrt runner build failed:\n%s", proc.stderr[-2000:])
        return False
    os.replace(tmp, _SO_PATH)
    return True


def _load() -> Optional[ctypes.CDLL]:
    """Resolve the runner library, building at most once (single-flight).

    Mirrors ``native/__init__.py``: the g++ subprocess and the dlopen
    run with NO lock held — the first caller claims the build via an
    Event planted under ``_lock``, later callers wait on the Event, and
    the handle is admitted under the lock once ready.
    """
    global _lib, _tried, _inflight
    while True:
        with _lock:
            if _tried:
                return _lib
            if _inflight is None:
                _inflight = claim = threading.Event()
                break
            waiter = _inflight
        waiter.wait()
    lib = None
    try:
        lib = _resolve()
    finally:
        with _lock:
            _lib = lib
            _tried = True
            _inflight = None
        claim.set()
    return lib


def _resolve() -> Optional[ctypes.CDLL]:
    """Build (if needed) + dlopen + bind signatures.  Runs with no lock
    held, in exactly one thread per process (see :func:`_load`)."""
    if os.environ.get("SPARKDL_NO_NATIVE") == "1":
        return None
    stale = (
        not os.path.exists(_SO_PATH)
        or os.path.getmtime(_SO_PATH) < os.path.getmtime(_SRC_PATH)
    )
    if stale and not _build():
        return None
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        logger.warning("pjrt runner dlopen failed: %s", e)
        return None
    lib.pjrt_runner_create_opts.restype = ctypes.c_void_p
    lib.pjrt_runner_create_opts.argtypes = [
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.pjrt_runner_last_error.restype = ctypes.c_char_p
    lib.pjrt_runner_last_error.argtypes = [ctypes.c_void_p]
    lib.pjrt_runner_platform.restype = ctypes.c_int
    lib.pjrt_runner_platform.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.pjrt_runner_compile.restype = ctypes.c_int64
    lib.pjrt_runner_compile.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_int64,
    ]
    lib.pjrt_runner_num_outputs.restype = ctypes.c_int64
    lib.pjrt_runner_num_outputs.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.pjrt_runner_put.restype = ctypes.c_int64
    lib.pjrt_runner_put.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
    ]
    lib.pjrt_runner_put_async.restype = ctypes.c_int64
    lib.pjrt_runner_put_async.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
    ]
    lib.pjrt_runner_await_buffer.restype = ctypes.c_int
    lib.pjrt_runner_await_buffer.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.pjrt_runner_free_buffer.restype = ctypes.c_int
    lib.pjrt_runner_free_buffer.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.pjrt_runner_execute.restype = ctypes.c_int64
    lib.pjrt_runner_execute.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.pjrt_runner_execute_async.restype = ctypes.c_int64
    lib.pjrt_runner_execute_async.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int32,
        ctypes.POINTER(ctypes.c_int64),
    ]
    lib.pjrt_runner_buffer_size.restype = ctypes.c_int64
    lib.pjrt_runner_buffer_size.argtypes = [
        ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.pjrt_runner_get.restype = ctypes.c_int
    lib.pjrt_runner_get.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.pjrt_runner_destroy.restype = None
    lib.pjrt_runner_destroy.argtypes = [ctypes.c_void_p]
    return lib


def is_available() -> bool:
    return _load() is not None


# Short dtype names shared with the C++ side (dtype_to_pjrt) and the
# manifest format.  bfloat16 maps through ml_dtypes (numpy has no native).
_DTYPE_NAMES = {
    np.dtype(np.float32): "f32",
    np.dtype(np.float64): "f64",
    np.dtype(np.float16): "f16",
    np.dtype(np.uint8): "u8",
    np.dtype(np.int8): "s8",
    np.dtype(np.int16): "s16",
    np.dtype(np.uint16): "u16",
    np.dtype(np.int32): "s32",
    np.dtype(np.int64): "s64",
    np.dtype(np.uint32): "u32",
    np.dtype(np.uint64): "u64",
    np.dtype(np.bool_): "pred",
}


def _dtype_name(dtype) -> str:
    dtype = np.dtype(dtype)
    if dtype.name == "bfloat16":
        return "bf16"
    try:
        return _DTYPE_NAMES[dtype]
    except KeyError:
        raise ValueError(f"unsupported dtype for native runner: {dtype}")


def _np_dtype(name: str):
    if name == "bf16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    rev = {v: k for k, v in _DTYPE_NAMES.items()}
    return rev[name]


class PjrtRunner:
    """In-process handle on the native runner (one plugin, one device)."""

    def __init__(self, plugin_path: str = DEFAULT_PLUGIN, options=None):
        """``options`` are the plugin's client-create NamedValues (str or
        int), for a plugin that refuses a bare create."""
        if not plugin_path:
            raise PjrtUnavailable(
                "no PJRT plugin configured: set SPARKDL_PJRT_PLUGIN to a "
                "GetPjrtApi shared object"
            )
        lib = _load()
        if lib is None:
            raise PjrtUnavailable("native pjrt runner unavailable")
        self._lib = lib
        keys, svals, ivals, is_int = [], [], [], []
        for k, v in (options or {}).items():
            keys.append(k.encode())
            if isinstance(v, int):
                svals.append(b"")
                ivals.append(int(v))
                is_int.append(1)
            else:
                svals.append(str(v).encode())
                ivals.append(0)
                is_int.append(0)
        n = len(keys)
        err = ctypes.create_string_buffer(4096)
        self._h = lib.pjrt_runner_create_opts(
            plugin_path.encode(),
            (ctypes.c_char_p * n)(*keys) if n else None,
            (ctypes.c_char_p * n)(*svals) if n else None,
            (ctypes.c_int64 * n)(*ivals) if n else None,
            (ctypes.c_int32 * n)(*is_int) if n else None,
            n, err, len(err),
        )
        if not self._h:
            raise RuntimeError(
                f"pjrt_runner_create({plugin_path}) failed: "
                f"{err.value.decode(errors='replace')}"
            )

    def _err(self) -> str:
        return self._lib.pjrt_runner_last_error(self._h).decode(
            errors="replace"
        )

    @property
    def platform(self) -> str:
        buf = ctypes.create_string_buffer(64)
        n = self._lib.pjrt_runner_platform(self._h, buf, len(buf))
        if n < 0:
            raise RuntimeError(self._err())
        return buf.value.decode()

    def compile(self, mlir: bytes, compile_options: bytes) -> int:
        exec_id = self._lib.pjrt_runner_compile(
            self._h, mlir, len(mlir), compile_options, len(compile_options)
        )
        if exec_id < 0:
            raise RuntimeError(f"compile failed: {self._err()}")
        return int(exec_id)

    def num_outputs(self, exec_id: int) -> int:
        return int(self._lib.pjrt_runner_num_outputs(self._h, exec_id))

    def put(self, array: np.ndarray) -> int:
        array = np.ascontiguousarray(array)
        dims = (ctypes.c_int64 * array.ndim)(*array.shape)
        buf_id = self._lib.pjrt_runner_put(
            self._h,
            array.ctypes.data_as(ctypes.c_void_p),
            _dtype_name(array.dtype).encode(),
            dims,
            array.ndim,
        )
        if buf_id < 0:
            raise RuntimeError(f"put failed: {self._err()}")
        return int(buf_id)

    def put_async(self, array: np.ndarray) -> int:
        """Start a host->device copy and return immediately (the plugin
        stages the bytes during the call; the device transfer overlaps
        subsequent work).  Consumers order themselves after the transfer
        via PJRT buffer definition events."""
        array = np.ascontiguousarray(array)
        dims = (ctypes.c_int64 * array.ndim)(*array.shape)
        buf_id = self._lib.pjrt_runner_put_async(
            self._h,
            array.ctypes.data_as(ctypes.c_void_p),
            _dtype_name(array.dtype).encode(),
            dims,
            array.ndim,
        )
        if buf_id < 0:
            raise RuntimeError(f"put_async failed: {self._err()}")
        return int(buf_id)

    def await_buffer(self, buf_id: int) -> None:
        """Block until the buffer's contents are defined on device
        (surfaces asynchronous transfer/compute errors)."""
        if self._lib.pjrt_runner_await_buffer(self._h, buf_id) != 0:
            raise RuntimeError(f"await_buffer failed: {self._err()}")

    def free(self, buf_id: int) -> None:
        self._lib.pjrt_runner_free_buffer(self._h, buf_id)

    def execute(self, exec_id: int, arg_buf_ids: Sequence[int]) -> List[int]:
        n_out = max(self.num_outputs(exec_id), 1)
        args = (ctypes.c_int64 * len(arg_buf_ids))(*arg_buf_ids)
        outs = (ctypes.c_int64 * n_out)()
        got = self._lib.pjrt_runner_execute(
            self._h, exec_id, args, len(arg_buf_ids), outs
        )
        if got < 0:
            raise RuntimeError(f"execute failed: {self._err()}")
        return [int(outs[i]) for i in range(got)]

    def execute_async(
        self, exec_id: int, arg_buf_ids: Sequence[int]
    ) -> List[int]:
        """Enqueue an execution and return immediately; fetching an
        output (or await_buffer) blocks until compute completes.  Pairs
        with put_async for double-buffered batch streaming."""
        n_out = max(self.num_outputs(exec_id), 1)
        args = (ctypes.c_int64 * len(arg_buf_ids))(*arg_buf_ids)
        outs = (ctypes.c_int64 * n_out)()
        got = self._lib.pjrt_runner_execute_async(
            self._h, exec_id, args, len(arg_buf_ids), outs
        )
        if got < 0:
            raise RuntimeError(f"execute_async failed: {self._err()}")
        return [int(outs[i]) for i in range(got)]

    def fetch(self, buf_id: int, shape, dtype) -> np.ndarray:
        """Copy a device buffer into a new host array of shape/dtype."""
        out = np.empty(shape, _np_dtype(dtype) if isinstance(dtype, str)
                       else dtype)
        size = self._lib.pjrt_runner_buffer_size(self._h, buf_id)
        if size < 0:
            raise RuntimeError(f"size query failed: {self._err()}")
        if size != out.nbytes:
            raise RuntimeError(
                f"buffer is {size} bytes; {out.nbytes} expected for "
                f"{out.shape} {out.dtype}"
            )
        rc = self._lib.pjrt_runner_get(
            self._h, buf_id, out.ctypes.data_as(ctypes.c_void_p), out.nbytes
        )
        if rc != 0:
            raise RuntimeError(f"fetch failed: {self._err()}")
        return out

    def close(self) -> None:
        if getattr(self, "_h", None):
            self._lib.pjrt_runner_destroy(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# ----------------------------------------------------------------------
# Program export (Python side; consumed by PjrtRunner and the C++ CLI)
# ----------------------------------------------------------------------

def default_compile_options() -> bytes:
    """A single-replica/single-device CompileOptionsProto, serialized via
    jaxlib (so the native side needs no protobuf).  Uses jax's canonical
    builder so the executable_build_options (device assignment etc.) match
    what the plugin sees from jax itself.

    Argument/result layouts are deliberately NOT pinned: absent
    ``mhlo.layout_mode`` attributes mean *default* layouts, which is
    exactly what ``PJRT_Client_BufferFromHostBuffer`` (device_layout
    nullptr) produces for the runner's uploads — verified against a
    TPU plugin (u8 NHWC default is the transposed-tiled
    ``{2,1,3,0:T(8,128)(4,1)}`` on BOTH sides).  Pinning row-major here
    would *create* a mismatch and fail execution with InvalidArgument.
    """
    try:
        from jax._src import compiler

        opts = compiler.get_compile_options(
            num_replicas=1,
            num_partitions=1,
            device_assignment=np.asarray([[0]]),
        )
    except Exception:  # jax internals moved: fall back to a bare proto
        from jaxlib import _jax

        opts = _jax.CompileOptions()
        opts.num_replicas = 1
        opts.num_partitions = 1
    return opts.SerializeAsString()


def export_program(
    fn,
    params,
    example_inputs: Sequence[Any],
    out_dir: str,
    input_names: Optional[Sequence[str]] = None,
    donate_params: bool = False,
) -> dict:
    """Export ``fn(params, *inputs)`` for the native runner.

    Lowers to StableHLO **with the flattened param leaves as leading
    arguments** (the opposite of :meth:`XlaFunction.export_stablehlo`,
    which freezes them as constants): the native runner uploads
    ``params.bin`` once and keeps the leaves device-resident across
    batches — constants would bloat the MLIR by the full weight size and
    re-ship on every compile.

    Returns the manifest dict (also written to ``manifest.json``).
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(params)

    def flat_fn(*args):
        p = jax.tree_util.tree_unflatten(treedef, args[: len(leaves)])
        out = fn(p, *args[len(leaves):])
        return tuple(jax.tree_util.tree_leaves(out))

    avals = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves] + [
        jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype)
        for x in example_inputs
    ]
    # keep_unused: the computation's parameter list must stay 1:1 with the
    # manifest's params + inputs (the runner uploads every leaf by
    # position; silent arg pruning would shift the mapping)
    lowered = jax.jit(flat_fn, keep_unused=True).lower(*avals)
    mlir_text = lowered.as_text().encode()
    out_avals = jax.eval_shape(flat_fn, *avals)

    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "program.mlir"), "wb") as fh:
        fh.write(mlir_text)
    with open(os.path.join(out_dir, "compile_options.pb"), "wb") as fh:
        fh.write(default_compile_options())
    with open(os.path.join(out_dir, "params.bin"), "wb") as fh:
        for leaf in leaves:
            fh.write(np.ascontiguousarray(np.asarray(leaf)).tobytes())

    manifest = {
        "params": [
            {"dtype": _dtype_name(np.asarray(l).dtype),
             "shape": [int(d) for d in l.shape]}
            for l in leaves
        ],
        "inputs": [
            {"name": (input_names[i] if input_names else f"input_{i}"),
             "dtype": _dtype_name(np.asarray(x).dtype),
             "shape": [int(d) for d in np.shape(x)]}
            for i, x in enumerate(example_inputs)
        ],
        "outputs": [
            {"dtype": _dtype_name(a.dtype),
             "shape": [int(d) for d in a.shape]}
            for a in out_avals
        ],
    }
    with open(os.path.join(out_dir, "manifest.json"), "w") as fh:
        json.dump(manifest, fh, indent=1)
    # plain-text twin for the C++ CLI (no JSON parser native-side)
    with open(os.path.join(out_dir, "manifest.txt"), "w") as fh:
        for kind in ("params", "inputs", "outputs"):
            for spec in manifest[kind]:
                dims = ",".join(str(d) for d in spec["shape"]) or "scalar"
                fh.write(f"{kind[:-1]} {spec['dtype']} {dims}\n")
    return manifest


class NativeProgram:
    """Load an exported program dir and stream batches through it.

    The in-process counterpart of the ``pjrt_tool`` CLI: params are
    uploaded once at construction, ``__call__`` ships one batch and
    returns the outputs.
    """

    def __init__(self, program_dir: str, plugin_path: str = DEFAULT_PLUGIN):
        with open(os.path.join(program_dir, "manifest.json")) as fh:
            self.manifest = json.load(fh)
        with open(os.path.join(program_dir, "program.mlir"), "rb") as fh:
            mlir = fh.read()
        with open(os.path.join(program_dir, "compile_options.pb"), "rb") as fh:
            copts = fh.read()
        self.runner = PjrtRunner(plugin_path)
        self.exec_id = self.runner.compile(mlir, copts)
        self.param_ids: List[int] = []
        with open(os.path.join(program_dir, "params.bin"), "rb") as fh:
            for spec in self.manifest["params"]:
                dtype = _np_dtype(spec["dtype"])
                count = int(np.prod(spec["shape"])) if spec["shape"] else 1
                arr = np.frombuffer(
                    fh.read(count * dtype.itemsize), dtype=dtype
                ).reshape(spec["shape"])
                self.param_ids.append(self.runner.put(arr))

    def __call__(self, *inputs: np.ndarray) -> List[np.ndarray]:
        specs = self.manifest["inputs"]
        if len(inputs) != len(specs):
            raise ValueError(
                f"program takes {len(specs)} inputs, got {len(inputs)}"
            )
        input_ids, out_ids = [], []
        for x, spec in zip(inputs, specs):
            arr = np.ascontiguousarray(x, dtype=_np_dtype(spec["dtype"]))
            if list(arr.shape) != spec["shape"]:
                raise ValueError(
                    f"input {spec['name']} expects shape {spec['shape']}, "
                    f"got {list(arr.shape)}"
                )
            input_ids.append(self.runner.put(arr))
        try:
            out_ids = self.runner.execute(
                self.exec_id, self.param_ids + input_ids
            )
            outs = [
                self.runner.fetch(oid, spec["shape"], spec["dtype"])
                for oid, spec in zip(out_ids, self.manifest["outputs"])
            ]
        finally:
            for bid in input_ids + out_ids:
                self.runner.free(bid)
        return outs

    def stream(self, batches):
        """Double-buffered batch streaming (generator): batch i+1's
        host->device transfer and execute are ENQUEUED (put_async /
        execute_async) before batch i's outputs are fetched, so transfer
        and compute of consecutive batches overlap — the in-process
        analog of pjrt_tool's pipelined loop.  Yields one output list per
        input batch, in order.  ``batches`` yields a single array (or a
        tuple for multi-input programs) per step."""
        specs = self.manifest["inputs"]
        out_specs = self.manifest["outputs"]
        pending = None  # (input_ids, out_ids)

        def fetch(entry):
            input_ids, out_ids = entry
            try:
                return [
                    self.runner.fetch(oid, spec["shape"], spec["dtype"])
                    for oid, spec in zip(out_ids, out_specs)
                ]
            finally:
                for bid in input_ids + out_ids:
                    self.runner.free(bid)

        try:
            for inputs in batches:
                if not isinstance(inputs, (tuple, list)):
                    inputs = (inputs,)
                if len(inputs) != len(specs):
                    raise ValueError(
                        f"program takes {len(specs)} inputs, got "
                        f"{len(inputs)}"
                    )
                input_ids = []
                try:
                    for x, spec in zip(inputs, specs):
                        arr = np.ascontiguousarray(
                            x, dtype=_np_dtype(spec["dtype"])
                        )
                        if list(arr.shape) != spec["shape"]:
                            raise ValueError(
                                f"input {spec['name']} expects shape "
                                f"{spec['shape']}, got {list(arr.shape)}"
                            )
                        input_ids.append(self.runner.put_async(arr))
                    out_ids = self.runner.execute_async(
                        self.exec_id, self.param_ids + input_ids
                    )
                except BaseException:
                    # free THIS batch's already-placed inputs; `pending`
                    # (the previous batch) is freed by the outer finally
                    for bid in input_ids:
                        self.runner.free(bid)
                    raise
                prev, pending = pending, (input_ids, out_ids)
                if prev is not None:
                    yield fetch(prev)
            if pending is not None:
                prev, pending = pending, None
                yield fetch(prev)
        finally:
            if pending is not None:  # consumer abandoned the generator
                for bid in pending[0] + pending[1]:
                    self.runner.free(bid)

    def close(self):
        self.runner.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
