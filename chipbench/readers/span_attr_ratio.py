"""Two counts the program wrote on its boundary spans, one over the other:
the attribute ``args["attr"]`` summed over the spans called ``args["spans"]``
of the window's undisturbed part, over ``args["over"]`` summed over the same
spans (``keys_scored`` over ``keys_spanned`` of ``ar_generate.prefill``).
Which spans count: ``span_self_time.cover``.  Spans without the attributes
(a program from before they were written) read as nothing."""

from chipbench.readers import span_self_time


def read(facts, args):
    found = span_self_time.covered(facts, args)
    if found is None:
        return None
    records, _, lo, hi = found
    names = set(args["spans"])
    spans = [r for r in records
             if r.name in names and lo <= r.start_ns and r.end_ns <= hi]
    total = sum(r.attributes.get(args["attr"], 0) for r in spans)
    over = sum(r.attributes.get(args["over"], 0) for r in spans)
    return total / over if over else None
