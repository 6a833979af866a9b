"""The paper's synthetic generator, the benchmark's own copy.

Gowanlock & Karsin (arXiv:1809.09930) Sec. 5.1: every coordinate is drawn
from an exponential distribution with lambda = 40 and clipped to [0, 1].
The copy lives here so that no change to the program can move the data a
cell is measured on.  A configuration names it as ``"generator":
"exponential"`` with ``lambda``, ``clip``, ``num_points`` and ``num_dims``.
"""
from __future__ import annotations

import numpy as np


def exponential_dataset(
    num_points: int, num_dims: int, lam: float = 40.0, seed: int = 0
) -> np.ndarray:
    """Paper Sec. 5.1 synthetic: exponential(lambda) per dim, in [0, 1]."""
    rng = np.random.default_rng(seed)
    x = rng.exponential(scale=1.0 / lam, size=(num_points, num_dims))
    return np.clip(x, 0.0, 1.0).astype(np.float32)


def generate(config: dict, seed: int) -> np.ndarray:
    lo, hi = config["clip"]
    if (lo, hi) != (0.0, 1.0):
        raise ValueError(f"the exponential generator clips to [0, 1], not {config['clip']}")
    return exponential_dataset(
        config["num_points"], config["num_dims"], config["lambda"], seed
    )
