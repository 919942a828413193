"""sparkdl_tpu.engine — AOT compilation, persistent executable caching,
and async dispatch for every inference hot path.

Three pieces, one owner:

- :class:`ExecutionEngine` / :data:`engine` — resolve (function,
  signature) → compiled executable through an in-memory LRU and the
  on-disk :class:`PersistentCompileCache`; ``engine.function(...)`` is
  the hot-path replacement for bare ``jax.jit``
  (``ci/lint_no_raw_jit.py`` enforces this in ``transformers/``,
  ``serving/``, ``udf/``);
- :class:`DispatchWindow` — depth-N in-flight execution with async
  device→host copies, replacing ad-hoc one-deep overlap;
- :func:`cache_key` — the content address binding an executable to
  (model fingerprint, shapes/dtypes/shardings, donation, mesh,
  jax/jaxlib versions).
"""

from sparkdl_tpu.engine.cache import (
    PersistentCompileCache,
    cache_key,
    compile_cache_root,
    default_cache_dir,
    enable_jax_cache,
)
from sparkdl_tpu.engine.core import EngineFunction, ExecutionEngine, ProgramHandle
from sparkdl_tpu.engine.executor import DispatchWindow, FetchFailure
from sparkdl_tpu.engine.slots import Slot, SlotPool, slot_block_fingerprint

#: the process-wide engine used by transformers, UDFs, and estimators
#: (serving's ProgramCache builds its own so cache_size eviction is real)
engine = ExecutionEngine()

__all__ = [
    "DispatchWindow",
    "EngineFunction",
    "FetchFailure",
    "ExecutionEngine",
    "PersistentCompileCache",
    "ProgramHandle",
    "Slot",
    "SlotPool",
    "slot_block_fingerprint",
    "cache_key",
    "compile_cache_root",
    "default_cache_dir",
    "enable_jax_cache",
    "engine",
]
