"""What the families' ``make_train_step`` share beyond ``models/
llama.py``'s own (which shards its update over dp and pipelines): the
default optimizer, the set-up spans, the ``loss`` / ``optimizer``
scopes, the donated state ``{"params", "opt", "step"}`` and, where a
step counts something on the device, the one host callback that takes
the counts out.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..observability import emit, in_setup_span, setup_span
from .llama import default_train_optimizer

# the group a train step's counters land under in
# ``observability.step_counters()``
TRAIN_COUNTERS = "train"


def make_family_train_step(place_params, loss_and_counts, optimizer=None,
                           counters=()):
    """``(step_fn, init_fn)``. ``place_params(key)``: the family's
    seeded parameters, placed on the mesh; ``loss_and_counts(params,
    batch) -> (loss, counts)``, ``counts`` an int array named by
    ``counters`` (``None`` where the family counts nothing): each step
    sends it to the process's step counters by ONE unordered host
    callback, which nothing in the program waits for."""
    with setup_span("train.setup.build"):
        import optax
        if optimizer is None:
            optimizer = default_train_optimizer()

        @in_setup_span("train.setup.init", ready=True)
        def init_fn(key):
            params = place_params(key)
            return {"params": params, "opt": optimizer.init(params),
                    "step": jnp.zeros((), jnp.int32)}

        @partial(jax.jit, donate_argnums=(0,))
        def step_fn(state, batch):
            with jax.named_scope("loss"):
                (loss, counts), grads = jax.value_and_grad(
                    loss_and_counts, has_aux=True)(state["params"], batch)
            if counters:
                emit(TRAIN_COUNTERS, counters, counts)
            with jax.named_scope("optimizer"):
                updates, opt = optimizer.update(grads, state["opt"],
                                                state["params"])
                params = optax.apply_updates(state["params"], updates)
            return {"params": params, "opt": opt,
                    "step": state["step"] + 1}, loss

    return step_fn, init_fn
