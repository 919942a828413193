"""The program's own host timers (``utils/metrics``), their delta over the
window summed and divided by the window's images or steps, in ms."""


def read(facts, args):
    timers = facts.get("timer_s", {})
    units = facts.get(args["per"])
    if not units or not all(t in timers for t in args["timers"]):
        return None
    return 1e3 * sum(timers[t] for t in args["timers"]) / units
