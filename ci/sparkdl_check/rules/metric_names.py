"""``metric-name`` — migrated from ``ci/lint_metric_names.py``.

Same convention, same diagnostics (the script is now a thin shim over
this rule): names registered through ``metrics.<factory>("...")`` are a
public contract — dashboards key on them, ``snapshot(prefix=...)``
filters on the dotted prefix — so they must start with a sanctioned
``subsystem.`` prefix, be lowercase ``[a-z0-9_.]``, and carry no empty
dotted segments; f-strings are checked on their leading literal and a
fully-dynamic name is unauditable, hence flagged.
"""

from __future__ import annotations

import ast
import re

from ci.sparkdl_check.core import FileContext, Rule, rule

#: one entry per subsystem that owns metrics; grow this list when a new
#: subsystem earns a namespace, not to whitelist a one-off name.
#: "slo" (burn-rate gauges/transitions) and "ts" (time-series recorder
#: self-metrics) joined with the PR-8 telemetry plane; "supervisor"
#: (replica lifecycle) and "router" (request plane) with the ISSUE-10
#: replica supervisor; "wire" (frame codec + transport lanes) with the
#: ISSUE-11 zero-copy data plane; "rollout" (blue/green shift state)
#: and "tenant" (per-tenant fair-share admission) with the ISSUE-12
#: zero-downtime fleet; "fleet" (supervisor-side metrics federation —
#: scrape health plus the ``fleet.replica.*`` / ``fleet.version.*``
#: federated series) with the ISSUE-13 fleet observability plane
#: (``router.phase.*`` latency-decomposition histograms ride the
#: existing "router" prefix).  "replica" (replica-process request-path
#: counters like ``replica.expired_shed``) and "faultnet" (injected
#: network-fault accounting) joined with the ISSUE-14 Byzantine-wire
#: hardening.
#: "diag" (trace-analytics report gauges) and "profile" (sampling-
#: profiler accounting) joined with the ISSUE-15 diagnosis plane.
#: "cache" (replica-tier single-flight / negative-cache accounting;
#: the router tier rides the existing "router" prefix as
#: ``router.cache.*``) joined with the ISSUE-16 result cache.
#: "decode" (slot-pool occupancy, TTFT/step latency, token/eviction
#: counters of the continuous-batching decode plane) and "batcher"
#: (one-shot coalescing internals: pad fraction, early-flush count)
#: joined with the ISSUE-18 token-streaming decode plane.
#: "csql" (open windows, rows/s, late-row counter, watermark-to-emit
#: latency with exemplars) joined with the ISSUE-19 continuous-SQL
#: plane.
#: "generate" (denoising and commit forwards, tokens fixed) and "moe"
#: (tokens routed and dropped, expert load) joined with the ISSUE-31
#: block-diffusion stage over a sparse-expert decoder.
#: "ar_generate" (prefill tokens and pads, decode steps, dispatches and
#: expert reads, tokens generated) and "ssm" (bytes of recurrent state
#: a batch holds) joined with the ISSUE-35 autoregressive stage over a
#: state-space / attention hybrid.
ALLOWED_PREFIXES = (
    "sparkdl", "data", "serving", "resilience", "estimator", "engine",
    "streaming", "slo", "ts", "supervisor", "router", "wire",
    "rollout", "tenant", "fleet", "replica", "faultnet", "diag",
    "profile", "cache", "decode", "batcher", "csql", "generate", "moe",
    "ar_generate", "ssm",
)

METRIC_FACTORIES = {"counter", "timer", "gauge", "histogram"}

_LITERAL_RE = re.compile(r"[a-z0-9_.]*")


def _metric_call_name(call: ast.Call):
    fn = call.func
    if not (isinstance(fn, ast.Attribute) and fn.attr in METRIC_FACTORIES):
        return None
    if not (isinstance(fn.value, ast.Name) and fn.value.id == "metrics"):
        return None
    if not call.args:
        return None
    return call.args[0]


def _leading_literal(node: ast.AST):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value, True
    if isinstance(node, ast.JoinedStr) and node.values:
        head = node.values[0]
        if isinstance(head, ast.Constant) and isinstance(head.value, str):
            return head.value, False
    return None, False


def _check_name(literal: str, complete: bool):
    if _LITERAL_RE.fullmatch(literal) is None:
        return (
            f"metric name {literal!r} has characters outside [a-z0-9_.] — "
            "use lowercase dotted names"
        )
    prefix = literal.split(".", 1)[0]
    if "." not in literal or prefix not in ALLOWED_PREFIXES:
        return (
            f"metric name {literal!r} must start with a subsystem prefix "
            f"({', '.join(p + '.' for p in ALLOWED_PREFIXES)})"
        )
    segments = literal.split(".")
    body = segments if complete else segments[:-1]
    if any(not s for s in body):
        return f"metric name {literal!r} has an empty dotted segment"
    return None


@rule
class MetricNameRule(Rule):
    id = "metric-name"
    severity = "error"
    doc = ("metric names follow 'subsystem.metric_name' — lowercase, "
           "dotted, sanctioned prefix")

    def applies(self, relpath: str) -> bool:
        return not relpath.startswith("tests/")

    def check(self, ctx: FileContext):
        findings = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name_arg = _metric_call_name(node)
            if name_arg is None:
                continue
            literal, complete = _leading_literal(name_arg)
            if literal is None:
                findings.append(self.finding(
                    ctx, node,
                    "metric name is fully dynamic — start it with a "
                    "literal 'subsystem.' prefix so the registry key is "
                    "auditable",
                ))
                continue
            msg = _check_name(literal, complete)
            if msg is not None:
                findings.append(self.finding(ctx, node, msg))
        return findings
