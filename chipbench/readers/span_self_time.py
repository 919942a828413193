"""Self time of the program's boundary spans (``sparkdl_tpu.obs.trace``:
``tracer.recent()``, a ring the program keeps whether or not anything traces),
over the undisturbed part of the measured window: per image, in ms, or as a
share of the covered wall time, in %.

Which spans count (:func:`cover`): the driver's clock and the ring's are both
``perf_counter``, and the program runs nothing between the window's last
``collect()`` and the readers, so the window ends where the newest span ends
and starts ``facts["wall_s"]`` before.  ``skip_tail_s`` leaves out the seconds
before the end in which a traced run has the profiler on.  Only WHOLE root
spans (no parent: a partition, a file read, a decode, a collect) between those
two marks count; the covered wall runs from the first one's start to the last
one's end, and a per-image denominator is the sum of an attribute (``rows``,
``files``) over the roots of one name.  A span's self time is its duration
less what its children ON THE SAME THREAD cover; a span that reaches over the
covered wall's edge (``engine.starved`` is backdated) is clipped to it.

A program without the ring (the parent of the PR that added it) reads as
nothing: the metric is left out of the line.
"""

import sys

from chipbench import trace_reduce

def _say(message):
    print(f"chipbench: span readers: {message}", file=sys.stderr)


def cover(records, wall_s, skip_tail_s=0.0):
    """``(roots, lo_ns, hi_ns)``: the whole root spans of the window's
    undisturbed part and the wall they cover; None without any."""
    if not records or not wall_s:
        return None
    end = max(r.end_ns for r in records)
    start = end - int(wall_s * 1e9)
    # the ring is in order of END times, so it holds every span that ended
    # after its oldest one: a root that starts later has all its children
    if records[0].end_ns > start:
        _say("the ring does not reach back to the window's start: "
             f"{(records[0].end_ns - start) / 1e9:.1f} s of it are not covered")
        start = records[0].end_ns

    def whole_roots(until):
        return [r for r in records if r.parent_id is None
                and r.start_ns >= start and r.end_ns <= until]

    roots = whole_roots(end - int(skip_tail_s * 1e9))
    if not roots and skip_tail_s:
        _say(f"no whole root span ends {skip_tail_s} s before the window "
             "does: the tail is counted too")
        roots = whole_roots(end)
    if not roots:
        return None
    return (roots, min(r.start_ns for r in roots),
            max(r.end_ns for r in roots))


def covered(facts, args):
    """``(records, roots, lo_ns, hi_ns)`` from the program's ring, or None
    where the program keeps none."""
    try:
        from sparkdl_tpu.obs.trace import tracer
    except ImportError:
        return None
    recent = getattr(tracer, "recent", None)
    if recent is None:
        return None
    records = recent()
    found = cover(records, facts.get("wall_s"), args.get("skip_tail_s", 0.0))
    return None if found is None else (records, *found)


def units(roots, per):
    """The denominator: ``per["attr"]`` summed over the roots named
    ``per["span"]``."""
    return sum(r.attributes.get(per["attr"], 0)
               for r in roots if r.name == per["span"])


def self_ns(records, names, lo, hi):
    """Summed self time, inside ``[lo, hi]``, of the spans called ``names``."""
    children = {}
    for r in records:
        if r.parent_id is not None:
            children.setdefault(r.parent_id, []).append(r)
    total = 0
    for r in records:
        if r.name not in names:
            continue
        start, end = max(r.start_ns, lo), min(r.end_ns, hi)
        if end <= start:
            continue
        inside = [
            (max(c.start_ns, start), min(c.end_ns, end))
            for c in children.get(r.span_id, ())
            if c.thread_id == r.thread_id
            and c.end_ns > start and c.start_ns < end
        ]
        total += (end - start) - trace_reduce.union_ns(inside)
    return total


def read(facts, args):
    found = covered(facts, args)
    if found is None:
        return None
    records, roots, lo, hi = found
    spent = self_ns(records, set(args["spans"]), lo, hi)
    if args["per"] == "wall":
        return 100.0 * spent / (hi - lo)
    images = units(roots, args["per"])
    return spent / 1e6 / images if images else None
