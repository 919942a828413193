"""Typed failure taxonomy: every fault is *transient* or *permanent*.

The split drives every policy decision downstream: a
:class:`TransientError` may be retried under a
:class:`~sparkdl_tpu.resilience.policy.RetryPolicy`; a
:class:`PermanentError` must fail fast with its typed class intact —
retrying corrupt input bytes or an invalid program shape only hides the
bug and burns the retry budget.

Exceptions this repo already defines participate directly: the serving
errors (``ServerOverloaded``/``DeadlineExceeded``/``ServerClosed``) and
``ImageDecodeError`` inherit from this module's bases, so
``isinstance`` IS the classification.  Foreign exceptions — jax/PJRT
runtime errors, OS-level I/O errors — go through :func:`classify`,
which maps them by type and (for XLA's string-coded runtime errors) by
the embedded grpc-style status word.

Deliberately import-light: no jax, no serving, no PIL at module level —
the taxonomy must be importable before any device initialization.
"""

from __future__ import annotations

import re
from typing import Optional, Type, Union


class FaultError(RuntimeError):
    """Base of the resilience taxonomy."""


class TransientError(FaultError):
    """A retry may succeed: the fault is in the environment (overload,
    connection reset, device busy), not in the request."""


class PermanentError(FaultError):
    """Retrying cannot help: the request, program, or data is wrong.
    Fail fast with the typed class."""


class DeviceUnresponsive(PermanentError):
    """A device-touching call exceeded the watchdog's hard timeout: a
    device call that does not return.  Permanent: an in-process retry
    would hang against the same dead device; recovery needs a new
    process, which is the *caller's* (or the scheduler's) move, not a
    backoff loop's."""


class DeadlineExceeded(PermanentError):
    """The work's deadline expired.  Permanent by definition: the answer
    is worthless now, so no retry policy should re-attempt under the
    same deadline.  ``sparkdl_tpu.serving.errors.DeadlineExceeded``
    subclasses this, so serving deadline shedding is classified without
    the taxonomy importing the serving layer."""


class CircuitOpen(TransientError):
    """A :class:`~sparkdl_tpu.resilience.policy.CircuitBreaker` is open:
    the dependency has been failing and calls are being rejected without
    attempting it.  Transient — the breaker re-probes after its recovery
    window, so backing off and retrying later is exactly right."""


class Preempted(BaseException):
    """The process received (or simulated) a preemption notice — SIGTERM
    from the scheduler.  Inherits ``BaseException`` (like
    ``KeyboardInterrupt``) so broad ``except Exception`` recovery paths
    cannot swallow a shutdown request; only the estimator's preemption
    handler, which flushes the final checkpoint, handles it."""


# ---------------------------------------------------------------------------
# classification of foreign exceptions
# ---------------------------------------------------------------------------

#: grpc-style status words XLA/PJRT embed in RuntimeError messages.
#: Transient: the environment may heal.  Everything else in the coded
#: set is permanent (bad program / bad argument / missing capability).
_XLA_TRANSIENT_STATUS = re.compile(
    r"\b(RESOURCE_EXHAUSTED|UNAVAILABLE|ABORTED|CANCELLED|INTERNAL"
    r"|DEADLINE_EXCEEDED)\b"
)
_XLA_STATUS = re.compile(
    r"\b(RESOURCE_EXHAUSTED|UNAVAILABLE|ABORTED|CANCELLED|INTERNAL"
    r"|DEADLINE_EXCEEDED|INVALID_ARGUMENT|NOT_FOUND|FAILED_PRECONDITION"
    r"|UNIMPLEMENTED|PERMISSION_DENIED|ALREADY_EXISTS|OUT_OF_RANGE"
    r"|DATA_LOSS)\b"
)

#: exception type names (not types — jax must stay unimported) whose
#: instances carry an XLA status word worth grepping
_XLA_ERROR_NAMES = frozenset(
    {"XlaRuntimeError", "JaxRuntimeError", "RpcError"}
)

#: OS-level exceptions where the environment, not the caller, failed
_TRANSIENT_OS_TYPES = (
    ConnectionError,
    TimeoutError,
    InterruptedError,
    BlockingIOError,
)

#: OS-level exceptions where retrying re-asks the same doomed question
_PERMANENT_OS_TYPES = (
    FileNotFoundError,
    PermissionError,
    IsADirectoryError,
    NotADirectoryError,
)

#: caller-registered overrides, consulted before the built-in rules
_REGISTERED: "list[tuple[Type[BaseException], bool]]" = []


def register(exc_type: Type[BaseException], transient: bool) -> None:
    """Teach :func:`classify` about a foreign exception type.  Later
    registrations win (consulted most-recent-first), so a caller can
    narrow an earlier, broader registration."""
    _REGISTERED.insert(0, (exc_type, bool(transient)))


def classify(
    exc: BaseException,
) -> "Type[Union[TransientError, PermanentError]]":
    """Map any exception to :class:`TransientError` or
    :class:`PermanentError`.

    Order: taxonomy members answer for themselves; caller registrations;
    XLA/PJRT status words; OS I/O types; everything unknown is
    **permanent** — retrying an unclassified failure masks bugs, and a
    genuinely transient source earns a :func:`register` entry instead.
    """
    if isinstance(exc, TransientError):
        return TransientError
    if isinstance(exc, PermanentError):
        return PermanentError
    for exc_type, transient in _REGISTERED:
        if isinstance(exc, exc_type):
            return TransientError if transient else PermanentError
    for klass in type(exc).__mro__:
        if klass.__name__ in _XLA_ERROR_NAMES:
            msg = str(exc)
            if _XLA_TRANSIENT_STATUS.search(msg):
                return TransientError
            if _XLA_STATUS.search(msg):
                return PermanentError
            # an XLA runtime error with no status word is the torn-
            # connection shape — environment, not program
            return TransientError
    if isinstance(exc, _PERMANENT_OS_TYPES):
        return PermanentError
    if isinstance(exc, _TRANSIENT_OS_TYPES):
        return TransientError
    if isinstance(exc, OSError):
        # residual OSError (ENOSPC, EIO, ...): the device/filesystem
        # hiccuped — the canonical transient I/O class
        return TransientError
    return PermanentError


def is_transient(exc: BaseException) -> bool:
    return classify(exc) is TransientError


def error_class(exc: Optional[BaseException]) -> str:
    """The structured-record label for an exception: its leaf type name
    (what bench/serving emit as ``"error_class"``)."""
    return type(exc).__name__ if exc is not None else "None"
