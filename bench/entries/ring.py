"""One whole self-join on the fused ring over a mesh of chips.

A new ``DistributedSelfJoinEngine(d, cfg, mesh=..., fused=True)`` per
join, as a user that joins a new dataset builds one: shard indexes, the
host packer, the per-engine trace of the ring program and its run, then
the answers back on the host with global ids (paper Sec. 6).
"""
from __future__ import annotations


def make_join(data, config: dict, traffic: dict, devices):
    import jax

    from repro.core import DistributedSelfJoinEngine, SelfJoinConfig

    workers = int(traffic["workers"])
    if len(devices) < workers:
        raise RuntimeError(f"the ring needs {workers} devices, found {len(devices)}")
    mesh = jax.make_mesh((workers,), ("data",), devices=devices[:workers])
    cfg = SelfJoinConfig(eps=traffic["eps"], k=config["index_dims"])
    want_pairs = traffic["mode"] == "pairs"

    def join():
        eng = DistributedSelfJoinEngine(
            data, cfg, mesh=mesh, fused=True, assignment=traffic["assignment"]
        )
        return eng.self_join_pairs() if want_pairs else eng.count()

    return join
