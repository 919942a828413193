"""Persistent, content-addressed cache of compiled XLA executables.

First-touch latency of every hot program in this repo is dominated by
XLA compilation (``serving/cache.py`` measured 10-40s per program on
TPU), and lazy ``jax.jit`` pays it once *per process*.  This cache makes
the compile a per-*artifact* cost: an AOT-compiled executable
(``jax.jit(...).lower(...).compile()``) is serialized through
``jax.experimental.serialize_executable`` and stored on disk under a
content-addressed key, so a second process — or a serving ``warmup()``
after a restart — loads executables in ~cache-load time instead of
recompiling.

Key discipline (what :func:`cache_key` hashes):

- the caller's **model fingerprint** — the engine only persists programs
  whose weights/semantics the caller can identify durably (a saved-model
  path+mtime, a StableHLO blob hash, a named pretrained model).  A
  closure over anonymous in-memory params gets NO disk entry: reusing an
  executable with the wrong baked-in weights would be silently wrong,
  which is worse than recompiling;
- per-argument **(shape, dtype, sharding)** — one executable per shape
  bucket, exactly the program set the batching discipline already bounds;
- **donation** argnums — a donating program has a different calling
  convention than a non-donating one;
- **mesh/topology** — platform, device kind, device count, and the mesh
  axis layout; an executable compiled for an 8-chip ``data`` mesh must
  never load into a single-chip process;
- **jax/jaxlib versions** — serialized executables are not stable across
  runtime upgrades, so a version bump simply misses and recompiles.

Placement: :func:`compile_cache_root` is the one root of everything this
package caches on disk — ``$JAX_COMPILATION_CACHE_DIR`` where it is set
(JAX reads it itself and the code sets no directory anywhere), otherwise
the fixed, git-ignored ``.compile_cache/`` of the checkout, handed to
JAX's own persistent compilation cache by :func:`enable_jax_cache`.  This
store lives in the ``executables/`` subdirectory of that root
(``SPARKDL_COMPILE_CACHE`` redirects or disables the store alone)::

    <dir>/<key[:2]>/<key>.exe    pickled (payload, in_tree, out_tree,
                                 device ids the program was compiled for)
    <dir>/<key[:2]>/<key>.json   human-readable key components

Writes are atomic (tmp + rename), loads are best-effort: a corrupt,
truncated, or version-incompatible entry is deleted and treated as a
miss.  The cache never makes a run fail — it only makes cold starts
fast.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import tempfile
from typing import Any, Dict, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)

_ENV_VAR = "SPARKDL_COMPILE_CACHE"
_OFF_VALUES = ("off", "none", "0", "disabled")
_JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout root.  Fixed on purpose: the directory is part of
#: JAX's cache key, so a root that moves (a temporary name, a pid, the
#: time) never hits.
_CHECKOUT_CACHE_ROOT = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".compile_cache",
)

#: soft disk budget; oldest entries are pruned past it at store time
DEFAULT_MAX_BYTES = 20 * 1024**3


def _placed_from_outside() -> str:
    return os.environ.get(_JAX_CACHE_ENV, "").strip()


def compile_cache_root() -> str:
    """The root of every compile cache the package writes:
    ``$JAX_COMPILATION_CACHE_DIR`` where it is set, else the fixed
    ``.compile_cache/`` inside the checkout."""
    return _placed_from_outside() or _CHECKOUT_CACHE_ROOT


def enable_jax_cache() -> None:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_root`.  Called from the places that compile
    first (engine construction, the replica's ``main()``,
    ``chip_smoke.py``, ``bench.py``) and idempotent: with
    ``JAX_COMPILATION_CACHE_DIR`` set JAX has read the directory itself,
    and a directory somebody already configured is left alone."""
    if _placed_from_outside():
        return
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_ROOT)


def default_cache_dir() -> Optional[str]:
    """The executable store's directory, or None when it is disabled.

    Reads the environment on every call so tests (and operators
    mid-process) can redirect or disable it without rebuilding engines.
    """
    spec = os.environ.get(_ENV_VAR, "").strip()
    if spec.lower() in _OFF_VALUES:
        return None
    if spec:
        return spec
    return os.path.join(compile_cache_root(), "executables")


def _runtime_descriptor() -> Dict[str, Any]:
    """Everything about the runtime that invalidates an executable."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "device_count": len(devices),
    }


def _sharding_descriptor(sharding) -> Any:
    """A stable, hashable description of an input sharding (mesh axis
    names/shape + partition spec), or None for default placement."""
    if sharding is None:
        return None
    mesh = getattr(sharding, "mesh", None)
    spec = getattr(sharding, "spec", None)
    if mesh is None:
        return repr(sharding)
    return {
        "axes": {
            str(name): int(size)
            for name, size in zip(mesh.axis_names, mesh.devices.shape)
        },
        "spec": repr(spec),
    }


def cache_key(
    fingerprint: str,
    arg_specs: Sequence[Tuple[Tuple[int, ...], str, Any]],
    donate_argnums: Sequence[int] = (),
    runtime: Optional[Dict[str, Any]] = None,
) -> str:
    """The content address of one executable: a sha256 over the canonical
    JSON of every component that must match for reuse to be sound.

    ``arg_specs`` is per-argument ``(shape, dtype_str, sharding_desc)``.
    Pure and deterministic — the same components hash identically in any
    process (the cross-process contract ``tests/test_engine.py`` pins).
    """
    payload = {
        "fingerprint": str(fingerprint),
        "args": [
            [list(int(d) for d in shape), str(dtype), sharding]
            for shape, dtype, sharding in arg_specs
        ],
        "donate": sorted(int(i) for i in donate_argnums),
        "runtime": runtime if runtime is not None else _runtime_descriptor(),
    }
    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()


class PersistentCompileCache:
    """Best-effort on-disk executable store addressed by :func:`cache_key`.

    ``cache_dir=None`` (the default) re-resolves the directory from the
    environment on every access; pass an explicit directory to pin it.
    Every method degrades to a no-op/miss on I/O or deserialization
    failure — the cache is an accelerator, never a dependency.
    """

    def __init__(
        self,
        cache_dir: Optional[str] = None,
        max_bytes: int = DEFAULT_MAX_BYTES,
    ):
        self._pinned = cache_dir
        self.max_bytes = int(max_bytes)

    @property
    def directory(self) -> Optional[str]:
        return self._pinned if self._pinned is not None else default_cache_dir()

    @property
    def enabled(self) -> bool:
        return self.directory is not None

    def _paths(self, key: str) -> Tuple[str, str]:
        root = self.directory
        assert root is not None
        shard = os.path.join(root, key[:2])
        return os.path.join(shard, f"{key}.exe"), os.path.join(
            shard, f"{key}.json"
        )

    # ------------------------------------------------------------------
    def load(self, key: str):
        """The deserialized-and-loaded executable for ``key``, or None.

        A present-but-unloadable entry (corrupt file, runtime drift the
        key missed) is deleted so it cannot fail every future start.
        """
        if not self.enabled:
            return None
        exe_path, _ = self._paths(key)
        if not os.path.exists(exe_path):
            return None
        try:
            with open(exe_path, "rb") as fh:
                payload, in_tree, out_tree, device_ids = pickle.load(fh)
            import jax
            from jax.experimental import serialize_executable

            # onto the devices it was compiled for: left to its default
            # the loader spreads the program over EVERY device of the
            # backend, and a one-device program then wants a shard per
            # local device
            by_id = {d.id: d for d in jax.devices()}
            return serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids],
            )
        except Exception as exc:
            logger.warning(
                "compile cache entry %s unloadable (%s); evicting it",
                key[:12], exc,
            )
            for path in self._paths(key):
                try:
                    os.remove(path)
                except OSError:
                    pass
            return None

    def store(self, key: str, compiled, meta: Optional[Dict] = None) -> bool:
        """Serialize ``compiled`` under ``key`` (atomic write); True on
        success.  Refusals (unserializable executable, disk trouble) are
        logged and swallowed."""
        if not self.enabled:
            return False
        exe_path, meta_path = self._paths(key)
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            device_ids = [
                d.id for d in compiled.runtime_executable().local_devices()
            ]
            os.makedirs(os.path.dirname(exe_path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(
                dir=os.path.dirname(exe_path), suffix=".tmp"
            )
            with os.fdopen(fd, "wb") as fh:
                pickle.dump((payload, in_tree, out_tree, device_ids), fh)
            os.replace(tmp, exe_path)
            with open(meta_path + ".tmp", "w") as fh:
                json.dump(meta or {}, fh, indent=1, default=str)
            os.replace(meta_path + ".tmp", meta_path)
        except Exception as exc:
            logger.warning(
                "compile cache store for %s failed: %s", key[:12], exc
            )
            return False
        self._prune()
        return True

    def __contains__(self, key: str) -> bool:
        if not self.enabled:
            return False
        return os.path.exists(self._paths(key)[0])

    # ------------------------------------------------------------------
    def entries(self):
        """(key, exe_path, bytes, mtime) for every stored executable."""
        root = self.directory
        if root is None or not os.path.isdir(root):
            return []
        out = []
        for shard in sorted(os.listdir(root)):
            sub = os.path.join(root, shard)
            if not os.path.isdir(sub):
                continue
            for name in sorted(os.listdir(sub)):
                if not name.endswith(".exe"):
                    continue
                path = os.path.join(sub, name)
                try:
                    st = os.stat(path)
                except OSError:
                    continue
                out.append((name[:-4], path, st.st_size, st.st_mtime))
        return out

    def stats(self) -> Dict[str, Any]:
        entries = self.entries()
        return {
            "dir": self.directory,
            "enabled": self.enabled,
            "entries": len(entries),
            "bytes": sum(e[2] for e in entries),
        }

    def clear(self) -> int:
        """Delete every entry; returns how many were removed."""
        removed = 0
        for key, path, _, _ in self.entries():
            for p in (path, path[:-4] + ".json"):
                try:
                    os.remove(p)
                    removed += p.endswith(".exe")
                except OSError:
                    pass
        return removed

    def _prune(self) -> None:
        """Drop oldest entries until the store fits ``max_bytes`` — the
        disk analog of the in-memory LRU (mtime approximates recency)."""
        try:
            entries = self.entries()
            total = sum(e[2] for e in entries)
            if total <= self.max_bytes:
                return
            for key, path, size, _ in sorted(entries, key=lambda e: e[3]):
                for p in (path, path[:-4] + ".json"):
                    try:
                        os.remove(p)
                    except OSError:
                        pass
                total -= size
                logger.info("compile cache pruned %s (%d bytes)", key[:12],
                            size)
                if total <= self.max_bytes:
                    return
        except Exception:  # pragma: no cover - prune must never raise
            logger.exception("compile cache prune failed")
