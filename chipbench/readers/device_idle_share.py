"""1 - the union of device-operation intervals over the traced steady
window, in %."""


def read(facts, args):
    trace = facts.get("trace")
    return None if not trace else 100.0 * trace["idle_share"]
