"""Time the program's boundary spans cover, as intervals on the ring's one
clock and whatever thread recorded them: the union of the spans called
``args["of"]`` (only those whose attributes hold every pair of
``args["where"]``, if given), less the union of those called
``args["minus"]``, clipped to the covered wall; as a share of that wall in %
(``per`` = ``"wall"``) or in ms a unit of a root's attribute (``per`` =
``{"span", "attr"}``).  Which spans count, and the denominators:
``span_self_time.cover``.

It reads the device's timeline as the program keeps it since PR 39
(``engine.device``: one span a dispatch, from when the device began to
compute it to when its result was ready; ``engine.transfer``: a placed batch
on its way), so ``of: engine.device`` is the device's busy time, by program
with ``where``, and ``of: <a host span>, minus: engine.device`` is the time
the device computed nothing while the host was in that span.  A ring without
any ``engine.device`` (the parent of the PR that added it) reads as nothing:
the metric is left out of the line.
"""

from chipbench import trace_reduce
from chipbench.readers import span_self_time


def _clipped(records, names, lo, hi, where=None):
    names, where = set(names), (where or {}).items()
    out = []
    for r in records:
        if r.name not in names:
            continue
        if any(r.attributes.get(key) != value for key, value in where):
            continue
        start, end = max(r.start_ns, lo), min(r.end_ns, hi)
        if end > start:
            out.append((start, end))
    return out


def overlap_ns(records, lo, hi, of, minus=(), where=None):
    """Length, inside ``[lo, hi]``, of the union of ``of`` outside the
    union of ``minus``."""
    kept = _clipped(records, of, lo, hi, where)
    taken = _clipped(records, minus, lo, hi)
    return (trace_reduce.union_ns(kept + taken)
            - trace_reduce.union_ns(taken))


def read(facts, args):
    found = span_self_time.covered(facts, args)
    if found is None:
        return None
    records, roots, lo, hi = found
    if not any(r.name == "engine.device" for r in records):
        return None
    spent = overlap_ns(records, lo, hi, args["of"], args.get("minus", ()),
                       args.get("where"))
    if args["per"] == "wall":
        return 100.0 * spent / (hi - lo)
    units = span_self_time.units(roots, args["per"])
    return spent / 1e6 / units if units else None
