"""What the generation stages share (``block_diffusion``, ``ar_generate``):
one model's placed weights, its programs and its spare device state, kept on
the model object; and the counting of the routing that comes back with every
program's result.

A generation program takes the weights as arguments (placed once per model
object, :func:`~sparkdl_tpu.transformers.utils.place_params_once`) and a
state that lives on the device from dispatch to dispatch and is DONATED: a
key/value cache pair, or any pytree of arrays.  A state that a batch is done
with goes back to the pool and the next batch of the same shapes takes it,
so a second batch allocates nothing.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from sparkdl_tpu.transformers.utils import place_params_once


class ProgramRunner:
    """One model's placed params, programs and spare states."""

    def __init__(self, model):
        self.model = model
        self.device = jax.local_devices()[0]
        self.params = place_params_once(model, model.params, self.device)
        self.programs: Dict[Any, Any] = {}
        self.states: Dict[Any, Any] = {}

    def place(self, array):
        return jax.device_put(array, self.device)

    @staticmethod
    def shapes_of(tree):
        return tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(tree))

    def take_state(self, spec):
        """A state of ``spec`` (a pytree of ``jax.ShapeDtypeStruct``): the
        spare one of these shapes, else zeros.  What a state holds is the
        taker's to overwrite before it reads."""
        held = self.states.pop(self.shapes_of(spec), None)
        if held is None:
            held = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype, device=self.device),
                spec)
        return held

    def keep_state(self, state) -> None:
        self.states[self.shapes_of(state)] = state

    def program(self, key, make_fn: Callable, example: Sequence[Any],
                name: str, donate: Sequence[int]):
        """The engine's executable for ``key``; ``make_fn`` builds the
        function only when this runner has not resolved it yet.  The
        arguments at ``donate`` (the state) are donated, the weights are
        not."""
        from sparkdl_tpu.engine import engine

        handle = self.programs.get(key)
        if handle is None:
            handle = self.programs[key] = engine.program(
                make_fn(), example, donate=tuple(donate), name=name,
                fingerprint=f"{self.model.fingerprint}:{name}:{key}",
            )
        return handle


def runner_for(model, attribute: str, key, make: Callable):
    """One runner per generation setting, kept ON the model object (under
    ``attribute``): the placed weights, the programs and the spare states
    live and die with it."""
    held = vars(model).setdefault(attribute, {})
    if key not in held:
        held[key] = make()
    return held[key]


def count_routing(counts, tokens: int, per_token: int, held) -> None:
    """``counts`` [L, E]: the (token, expert) pairs each expert of each
    layer got in one program's forwards, through each layer of which
    ``tokens`` tokens went with ``per_token`` experts each; ``held`` = (lo,
    hi): the experts whose part is computed here (``moe.pairs_held`` beside
    ``moe.tokens_routed`` is the share of the routed work this chip does)."""
    from sparkdl_tpu.utils.metrics import metrics

    counts = np.asarray(counts)
    routed = int(counts.sum())
    metrics.counter("moe.tokens_routed").add(routed)
    metrics.counter("moe.pairs_held").add(
        int(counts[..., held[0]:held[1]].sum()))
    metrics.counter("moe.tokens_dropped").add(
        tokens * counts.shape[0] * per_token - routed)
    metrics.counter("moe.expert_load_max").add(float(counts.max()))
    metrics.counter("moe.expert_load_mean").add(float(counts.mean()))
