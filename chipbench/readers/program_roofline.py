"""One named program's share of its roofline, in %, for a cell that
dispatches several programs (``roofline`` reads the one program of a cell).

``facts["programs"][args.program]`` holds ``name`` (the program's name in
the trace's ``XLA Modules`` line) and ``dispatches``: the counted operations
and bytes of each dispatch of one pass (one entry where every dispatch is
alike; a program compiled at several shapes lists the pass's mix).  The
least time a dispatch can take is the larger of operations over peak FLOP/s
and bytes over peak bytes/s, averaged over that list, over the mean device
time of the program's whole dispatches in the trace.  A program without such
facts, or with no dispatch in the trace, reads as nothing.
"""


def read(facts, args):
    peaks = facts.get("peaks")
    counted = (facts.get("programs") or {}).get(args["program"])
    programs = (facts.get("trace") or {}).get("programs", {})
    if not peaks or not counted or not counted.get("dispatches"):
        return None
    runs = [p for name, p in programs.items()
            if name.startswith(counted["name"]) and p["whole_runs"]]
    if not runs:
        return None
    seconds = sum(p["whole_seconds"] for p in runs)
    count = sum(p["whole_runs"] for p in runs)
    least = [
        max(d["flops"] / peaks["flops_per_s"][args["peak"]],
            d["bytes"] / peaks["hbm_bytes_per_s"])
        for d in counted["dispatches"]
    ]
    return 100.0 * (sum(least) / len(least)) * count / seconds
