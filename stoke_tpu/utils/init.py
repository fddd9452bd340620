"""Model initialization helpers.

``flax.linen.Module.init`` run eagerly executes hundreds of small ops, each
its own dispatch.  :func:`init_module` runs the whole init as ONE compiled
program on the process's default device; the facade then places the result
onto its device or mesh according to the sharding rules."""

from __future__ import annotations

from typing import Any

import jax


def init_module(module, rng, *args, **kwargs) -> Any:
    """Initialize a flax module's variables in one compiled call.

    The full tree lands on the default backend's first local device (the
    chip when there is one), so the model must fit there unsharded.

    Usage:
        variables = init_module(model, jax.random.PRNGKey(0), dummy_batch,
                                train=False)
    """
    return jax.jit(lambda r: module.init(r, *args, **kwargs))(rng)
