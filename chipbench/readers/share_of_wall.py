"""Seconds of the window that the benchmark's own clock put on one call
(``facts[args.seconds_key]``), as a share of the window's wall time, in %."""


def read(facts, args):
    seconds = facts.get(args["seconds_key"])
    if seconds is None or not facts.get("wall_s"):
        return None
    return 100.0 * seconds / facts["wall_s"]
