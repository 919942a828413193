"""Backend compilations inside the measured window, counted from JAX's own
monitoring events (every program, not only the engine's)."""


def read(facts, args):
    compiles = facts.get("compiles")
    return None if compiles is None else float(compiles["backend_compiles"])
