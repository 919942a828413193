"""A quantity the driver read from the program's counters or histograms
(``facts[args.key]``), divided by the window's steps or images."""


def read(facts, args):
    value, units = facts.get(args["key"]), facts.get(args["per"])
    if value is None or not units:
        return None
    return float(value) / units
