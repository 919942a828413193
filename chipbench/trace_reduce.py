"""From a profiler trace to numbers: device busy time, idle gaps named by
what the host was doing, per-program and per-operation device time.

The reduction works on a neutral list of events
``(plane, line, name, start_ns, duration_ns)`` so that it can be checked on a
small recorded trace (``tests/chipbench/recorded_trace.json``);
:func:`load_xplane` makes that list from the ``.xplane.pb`` the JAX profiler
writes, with nothing but JAX.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: spans the benchmark's own drivers place around calls into the program
HOST_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.trace_window"


def newest_xplane(trace_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime,
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> list:
    """Device operations, device modules and the benchmark's own host spans
    of one trace, as neutral events."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIX):
                    events.append((
                        plane.name, line.name, ev.name,
                        int(ev.start_ns), int(ev.duration_ns),
                    ))
    return events


def structure(path: str, names: int = 4) -> list:
    """What a trace holds — planes, lines, event counts and a few names —
    for looking at one by hand before trusting the reduction."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            out.append({
                "plane": plane.name, "line": line.name, "events": len(evs),
                "names": sorted({e.name for e in evs[:200]})[:names],
            })
    return out


def union_ns(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals, overlaps once."""
    total, cur_start, cur_end = 0, None, None
    for start, end in sorted(intervals):
        if cur_end is None or start > cur_end:
            if cur_end is not None:
                total += cur_end - cur_start
            cur_start, cur_end = start, end
        elif end > cur_end:
            cur_end = end
    if cur_end is not None:
        total += cur_end - cur_start
    return total


def _clip(start, end, lo, hi):
    start, end = max(start, lo), min(end, hi)
    return (start, end) if end > start else None


def window_of(events):
    """(start, end) of the traced steady window ON THE DEVICE'S CLOCK: from
    the first program dispatch's start to the last one's start, a whole
    number of dispatch periods.  The profiler starts and stops the device's
    tracer seconds after the host's (on this chip the device's data began
    where the host's 3 s span ended, PERF.md section 6), so the benchmark's
    own host span cannot bound the device's data."""
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace has no device plane")
    starts = sorted(
        s for p, line, _, s, _ in events
        if p == planes[0] and line == MODULES_LINE
    )
    if len(starts) < 2:
        raise ValueError(
            f"the trace holds {len(starts)} program dispatch(es): no steady "
            "window can be read from fewer than two")
    return starts[0], starts[-1]


def device_planes(events) -> list:
    return sorted({p for p, _, _, _, _ in events if DEVICE_PLANE.match(p)})


def _ops(events, plane, lo, hi):
    for p, line, name, s, d in events:
        if p == plane and line == OPS_LINE:
            clipped = _clip(s, s + d, lo, hi)
            if clipped:
                yield name, clipped


def busy(events) -> dict:
    """``busy_s`` (the union of device-operation intervals inside the window,
    averaged over the device planes), ``window_s`` and the idle share."""
    lo, hi = window_of(events)
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace has no device plane")
    busy_ns = [
        union_ns([iv for _, iv in _ops(events, p, lo, hi)]) for p in planes
    ]
    busy_s = sum(busy_ns) / len(planes) / 1e9
    window_s = (hi - lo) / 1e9
    return {
        "busy_s": busy_s, "window_s": window_s, "chips": len(planes),
        "idle_share": 1.0 - busy_s / window_s,
    }


def _base_name(name: str) -> str:
    """``jit_forward(123456)`` → ``jit_forward``; the HLO text of an
    operation, ``%fusion.12 = bf16[8,35,35,64]{...} fusion(...)``, →
    ``fusion.12 bf16[8,35,35,64]``."""
    hlo = re.match(r"%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?", name)
    if hlo:
        return " ".join(part for part in hlo.groups() if part)
    return re.sub(r"\(\d+\)$", "", name.lstrip("%")).strip()


def program_seconds(events) -> dict:
    """Device seconds of every program's dispatches (``XLA Modules``) on the
    first device plane.  The profiler records a dispatch whole or not at
    all, so every one in the trace counts, also the last, whose start ends
    the steady window."""
    planes = device_planes(events)
    out = defaultdict(lambda: {"whole_runs": 0, "whole_seconds": 0.0})
    for p, line, name, _, d in events:
        if p == planes[0] and line == MODULES_LINE:
            rec = out[_base_name(name)]
            rec["whole_runs"] += 1
            rec["whole_seconds"] += d / 1e9
    return dict(out)


def top_ops(events, n: int = 10) -> list:
    """[[operation, seconds]] — the device operations that took most time."""
    lo, hi = window_of(events)
    planes = device_planes(events)
    total = defaultdict(float)
    for name, (s, e) in _ops(events, planes[0], lo, hi):
        total[_base_name(name)] += (e - s) / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def idle_gaps(events, n: int = 10) -> list:
    """[[name, seconds]] — idle time of the first device inside the window,
    summed by name.  A gap is given to the innermost benchmark span
    (``chipbench.*``) that covers its middle; where the host's spans do not
    reach the device's data (see :func:`window_of`) it is named by the
    programs that ran before and after it and by its length's class, which
    tells the gaps between partitions from those between batches."""
    lo, hi = window_of(events)
    planes = device_planes(events)
    intervals = sorted(iv for _, iv in _ops(events, planes[0], lo, hi))
    gaps, cursor = [], lo
    for s, e in intervals:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    spans = [
        (s, s + d, name) for _, _, name, s, d in events
        if name.startswith(HOST_PREFIX) and name != WINDOW_SPAN
        and s + d > lo and s < hi
    ]
    modules = sorted(
        (s, s + d, _base_name(name)) for p, line, name, s, d in events
        if p == planes[0] and line == MODULES_LINE
    )
    total = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        if covering:
            name = min(covering, key=lambda sp: sp[1] - sp[0])[2][len(HOST_PREFIX):]
        else:
            inside = [m for m in modules if m[0] <= mid <= m[1]]
            before = [m for m in modules if m[1] <= mid]
            after = [m for m in modules if m[0] >= mid]
            if inside:
                name = "inside " + inside[0][2]
            else:
                name = "host between {} and {}".format(
                    before[-1][2] if before else "start",
                    after[0][2] if after else "end")
            ms = (e - s) / 1e6
            name += " (" + ("<1 ms" if ms < 1 else "1-50 ms" if ms < 50
                            else ">50 ms") + " each)"
        total[name] += (e - s) / 1e9
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, seconds] for name, seconds in ranked]


def reduce(events) -> dict:
    """Everything the readers and the result line take from a trace."""
    return {
        **busy(events),
        "programs": program_seconds(events),
        "device_ops": top_ops(events),
        "idle_gaps": idle_gaps(events),
    }
