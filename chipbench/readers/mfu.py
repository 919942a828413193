"""The whole step's share of the chip's peak, in %: the operations the
window's work NEEDS (counted by ``chipbench/flops.py`` from shapes, for the
images really returned or trained) over the window's wall time and the
peak of the device from ``chipbench/peaks.json``."""


def read(facts, args):
    peaks, needed = facts.get("peaks"), facts.get("needed_flops")
    if not peaks or not needed or not facts.get("wall_s"):
        return None
    peak = peaks["flops_per_s"][args["peak"]] * facts.get("chips", 1)
    return 100.0 * needed / facts["wall_s"] / peak
