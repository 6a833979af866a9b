"""A configuration's data, from its generator and the seed.

A configuration file names its generator (``"generator": "<name>"``); the
generator is ``bench/generators/<name>.py``, found by that name, with a
``generate(config, seed)`` that returns the points as a float32
``(num_points, num_dims)`` array.  A new kind of data is a new file there.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

GENERATORS = Path(__file__).resolve().parent / "generators"


def generator(name: str):
    path = GENERATORS / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"unknown generator {name!r}: no {path}")
    spec = importlib.util.spec_from_file_location(f"bench_generator_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(config: dict, seed: int) -> np.ndarray:
    """The configuration's dataset at its benchmark size, from ``seed``."""
    return generator(config["generator"]).generate(config, seed)
