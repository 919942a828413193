"""Watchdogged device calls: turn unbounded hangs into typed failures.

What this bounds: a device call that does not return — ``jax.devices()``,
a dispatch, a fetch, a compile.  :func:`watchdogged` runs the call on a
worker thread and watches it from the caller's thread:

- **soft timeout** — the call is slow but may still land: count it, leave
  a breadcrumb, log, keep waiting;
- **hard timeout** — give up: raise the typed
  :class:`~sparkdl_tpu.resilience.errors.DeviceUnresponsive`.  The worker
  thread cannot be killed (CPython), so it is abandoned as a daemon — the
  POINT is that the caller's thread, and therefore the job, stays in
  control instead of hanging with it.

Nothing here starts a process.  A chip belongs to one process at a time:
the process that runs these calls holds it, and a child that asked for it
would fail where the parent is healthy.  :func:`check_device` therefore
bounds a tiny dispatch on the device this process already holds (one
structured ``{"ok": ..., "error_class": ...}`` shape for
``ModelServer.status(probe_device=True)`` and anything else that asks
"does my device still answer").
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable

from sparkdl_tpu.resilience import inject
from sparkdl_tpu.resilience.errors import DeviceUnresponsive, error_class
from sparkdl_tpu.utils.metrics import metrics

logger = logging.getLogger(__name__)


def _blackbox_note(name: str, **attrs) -> None:
    """Breadcrumb into the armed flight recorder, if any.

    Lazy cold-path import on purpose: ``resilience`` stays below ``obs``
    in the layering (same pattern as ``policy._span_event``), and both
    watchdog timeout paths are seconds in already — an import is noise
    there.  No-op while no recorder is armed."""
    from sparkdl_tpu.obs import blackbox

    blackbox.note(name, **attrs)


def _blackbox_dump(reason: str, **attrs) -> None:
    """Trip the armed flight recorder (breadcrumb + event dump): a hard
    watchdog timeout IS the silent-wedge moment the recorder exists for.
    No-op while no recorder is armed."""
    from sparkdl_tpu.obs import blackbox

    blackbox.note(reason, **attrs)
    blackbox.dump(reason)


def watchdogged(
    fn: Callable[..., Any],
    *args: Any,
    soft_timeout_s: float = 30.0,
    hard_timeout_s: float = 120.0,
    name: str = "device_call",
    **kwargs: Any,
) -> Any:
    """Run ``fn(*args, **kwargs)`` bounded by a two-stage watchdog.

    Returns ``fn``'s result, re-raises its exception, or raises
    :class:`DeviceUnresponsive` after ``hard_timeout_s``.  The
    fault-injection site ``watchdog.<name>`` fires inside the worker, so
    an injected stall exercises the real timeout path."""
    if hard_timeout_s <= 0:
        raise ValueError(f"hard_timeout_s must be > 0, got {hard_timeout_s}")
    soft_timeout_s = min(soft_timeout_s, hard_timeout_s)
    done = threading.Event()
    box: dict = {}

    def run():
        try:
            inject.fire(f"watchdog.{name}")
            box["result"] = fn(*args, **kwargs)
        except BaseException as exc:  # noqa: BLE001 - re-raised in the caller
            box["error"] = exc
        finally:
            done.set()

    worker = threading.Thread(
        target=run, name=f"sparkdl-watchdog-{name}", daemon=True
    )
    start = time.monotonic()
    worker.start()
    if not done.wait(soft_timeout_s):
        metrics.counter("resilience.watchdog_soft_timeouts").add(1)
        _blackbox_note(
            "watchdog_soft_timeout", what=name, timeout_s=soft_timeout_s
        )
        logger.warning(
            "%s exceeded soft timeout (%.1fs); waiting up to %.1fs more",
            name, soft_timeout_s, hard_timeout_s - soft_timeout_s,
        )
        remaining = hard_timeout_s - (time.monotonic() - start)
        if remaining > 0:
            done.wait(remaining)
    if not done.is_set():
        metrics.counter("resilience.watchdog_hard_timeouts").add(1)
        _blackbox_dump(
            f"watchdog_{name}", what=name, timeout_s=hard_timeout_s
        )
        raise DeviceUnresponsive(
            f"{name} still running after hard timeout "
            f"{hard_timeout_s:.1f}s (the device call did not return)"
        )
    if "error" in box:
        raise box["error"]
    return box["result"]


def _touch_device() -> str:
    """One tiny dispatch on the default device, fetched back: the whole
    host -> device -> host round trip.  Returns the platform it ran on."""
    import jax
    import numpy as np

    device = jax.devices()[0]
    out = jax.device_put(np.float32(1.0), device) + np.float32(1.0)
    if float(out) != 2.0:  # the fetch is the point; the value is a bonus
        raise RuntimeError(f"device answered {float(out)!r} to 1 + 1")
    return device.platform


def check_device(timeout_s: float = 60.0) -> dict:
    """Bounded liveness check of the device THIS process holds, as a
    structured record: ``{"ok": bool, "error_class": str|None, "detail":
    str}`` — ``detail`` is the platform name on success, the failure on
    error.  In-process by design (module docstring): the dispatch runs
    under :func:`watchdogged`, so a device that does not answer within
    ``timeout_s`` reports ``DeviceUnresponsive`` instead of hanging the
    caller."""
    try:
        platform = watchdogged(
            _touch_device,
            soft_timeout_s=timeout_s / 2.0,
            hard_timeout_s=timeout_s,
            name="device_probe",
        )
    except Exception as exc:  # a health record, never a raise
        return {
            "ok": False,
            "error_class": error_class(exc),
            "detail": str(exc),
        }
    return {"ok": True, "error_class": None, "detail": platform}
