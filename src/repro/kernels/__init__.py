"""The join's compute hot spot: tile-pair distance kernels and their wrappers.

``distance_tile`` / ``dense_tile`` are the Pallas TPU kernels of the indexed
and dense tiers, ``ops`` dispatches between them and their XLA twins, and
``ref`` holds the pure-jnp oracles.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(
    requested: Optional[bool] = None, platform: Optional[str] = None
) -> bool:
    """Pallas interpret mode for ``platform`` (default: JAX's backend).

    Off on a TPU, on everywhere else.  ``requested=False`` forces it off (an
    ahead-of-time compile for a described TPU from a CPU host); asking for
    the interpreter on a TPU raises instead of silently running it there.
    """
    on_tpu = (platform or jax.default_backend()) == "tpu"
    if requested is None:
        return not on_tpu
    if requested and on_tpu:
        raise ValueError("Pallas interpret mode was requested on a TPU")
    return bool(requested)
