"""A count the program wrote on its boundary spans (an attribute, e.g. the
``bytes`` of ``engine.place``), summed over the spans of the window's
undisturbed part and divided by the images of the same part.  Which spans
count: ``span_self_time.cover``."""

from chipbench.readers import span_self_time


def read(facts, args):
    found = span_self_time.covered(facts, args)
    if found is None:
        return None
    records, roots, lo, hi = found
    names = set(args["spans"])
    total = sum(
        r.attributes.get(args["attr"], 0) for r in records
        if r.name in names and lo <= r.start_ns and r.end_ns <= hi
    )
    images = span_self_time.units(roots, args["per"])
    return total / images if images else None
