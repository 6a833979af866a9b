"""One whole self-join on one chip through the library's entry point.

``repro.core.self_join(d, SelfJoinConfig(eps, k), return_pairs=...)`` with
the repository's defaults (XLA backend, indexed tier): REORDER, grid,
tile plan and device placement on every call, then the chunk programs,
then the answers back on the host as numpy.
"""
from __future__ import annotations


def make_join(data, config: dict, traffic: dict, devices):
    from repro.core import SelfJoinConfig, self_join

    cfg = SelfJoinConfig(eps=traffic["eps"], k=config["index_dims"])
    want_pairs = traffic["mode"] == "pairs"

    def join():
        return self_join(data, cfg, return_pairs=want_pairs)

    return join
