"""Fault-tolerance subsystem: typed failures, bounded device calls,
retry/deadline/breaker policies, preemption-safe resume, and a
deterministic fault-injection harness.

The failure mode this layer exists for: a device call that does not
return turns every caller into an unbounded hang.  Serving heavy traffic
needs failures to be
*classified* (:mod:`errors`), *bounded* (:mod:`watchdog`), *retried
under a budget* (:mod:`policy`), and *recovered from*
(:mod:`preempt` + the estimators' commit-marker checkpoints) — the same
checkpoint-based posture TensorFlow (Abadi et al., 2016) treats as core
to large-scale training, with tf.data's (Murray et al., 2021)
per-stage error policies applied to this engine's pipelines.

Layering: :mod:`resilience` depends only on :mod:`utils` (metrics) —
never on estimators/serving/data, which all import *it*.  The
one deliberate exception is ``classify``'s lazy imports of the typed
errors those layers already define — plus ``policy``'s lazy cold-path
import of :func:`sparkdl_tpu.obs.trace.record_event`, so retry attempts
and breaker state changes surface as span events when tracing is on.
"""

from sparkdl_tpu.resilience.errors import (
    CircuitOpen,
    DeadlineExceeded,
    DeviceUnresponsive,
    FaultError,
    PermanentError,
    Preempted,
    TransientError,
    classify,
    error_class,
    is_transient,
)
from sparkdl_tpu.resilience.inject import FaultPlan, active_plan, fire
from sparkdl_tpu.resilience.policy import (
    CircuitBreaker,
    Deadline,
    RetryPolicy,
)
from sparkdl_tpu.resilience.preempt import (
    preemption_scope,
    request_preemption,
)
from sparkdl_tpu.resilience.watchdog import check_device, watchdogged

__all__ = [
    "CircuitBreaker",
    "CircuitOpen",
    "Deadline",
    "DeadlineExceeded",
    "DeviceUnresponsive",
    "FaultError",
    "FaultPlan",
    "PermanentError",
    "Preempted",
    "RetryPolicy",
    "TransientError",
    "active_plan",
    "check_device",
    "classify",
    "error_class",
    "fire",
    "is_transient",
    "preemption_scope",
    "request_preemption",
    "watchdogged",
]
