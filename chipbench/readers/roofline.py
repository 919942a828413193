"""A program's share of its roofline, in %: the least time one dispatch can
take — the larger of counted operations over peak FLOP/s and counted bytes
over peak bytes/s (``facts["dispatch"]``, counted from shapes) — over the
mean device time of the program's whole dispatches in the trace."""


def read(facts, args):
    peaks, dispatch = facts.get("peaks"), facts.get("dispatch")
    programs = (facts.get("trace") or {}).get("programs", {})
    if not peaks or not dispatch:
        return None
    runs = [p for name, p in programs.items()
            if name.startswith(facts["program"]) and p["whole_runs"]]
    if not runs:
        return None
    seconds = sum(p["whole_seconds"] for p in runs)
    count = sum(p["whole_runs"] for p in runs)
    least = max(
        dispatch["flops"] / peaks["flops_per_s"][args["peak"]],
        dispatch["bytes"] / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least * count / seconds
