"""Device-fused distributed ring join (DESIGN.md #7 addendum).

Parity matrix: the fused one-program ring must equal the host-driven
``DistributedSelfJoinEngine`` (its differential oracle), the single-device
``SelfJoinEngine``, and the brute-force oracle -- exactly, on 8 simulated
devices over both the 1-axis and the joint ("pod", "data") meshes, with a
non-divisible |D| (unequal shards -> padded tile tables + sentinel masking).

The 8-device matrix runs in a subprocess (the device-count flag must
precede jax init); the in-process tests cover the 1-device mesh, the
compile-once contract, and fused edge cases.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from oracles import brute_counts, make_dataset
from repro.core import (
    DistributedSelfJoinEngine,
    SelfJoinConfig,
    SelfJoinEngine,
)


def _mesh1():
    import jax

    return jax.make_mesh((1,), ("data",))


def test_fused_one_device_parity_and_compile_once():
    d = make_dataset("exponential", 403, 16, seed=5)
    cfg = SelfJoinConfig(eps=0.06, k=4, tile_size=16)
    de = DistributedSelfJoinEngine(d, cfg, mesh=_mesh1(), fused=True)
    res = de.count()
    np.testing.assert_array_equal(res.counts, brute_counts(d, cfg.eps))
    np.testing.assert_array_equal(
        res.counts, SelfJoinEngine(d, cfg).count().counts
    )
    assert res.stats.num_device_dispatches == 1
    assert de.fused_traces == 1 and de.fused_executions == 1
    # an eps sweep at or below the packed radius re-executes the SAME
    # compiled program: no retrace, no repack
    res_small = de.count(0.03)
    np.testing.assert_array_equal(res_small.counts, brute_counts(d, 0.03))
    assert de.fused_traces == 1 and de.fused_executions == 2


def test_fused_matches_host_driven_oracle_exactly():
    d = make_dataset("clustered", 301, 8, seed=7)
    cfg = SelfJoinConfig(eps=0.1, k=4, tile_size=16)
    fused = DistributedSelfJoinEngine(d, cfg, mesh=_mesh1(), fused=True).count()
    host = DistributedSelfJoinEngine(d, cfg, num_workers=1).count()
    np.testing.assert_array_equal(fused.counts, host.counts)
    # same index, same plans: the work counters agree too
    assert fused.stats.num_candidates == host.stats.num_candidates
    assert fused.stats.num_tile_pairs_evaluated == host.stats.num_tile_pairs_evaluated
    # the fused join is one dispatch; the host loop is one per chunk
    assert fused.stats.num_device_dispatches == 1
    assert host.stats.num_device_dispatches >= 1


@pytest.mark.parametrize(
    "kind,n,dims,eps",
    [
        ("duplicated", 90, 6, 0.0),      # eps == 0 duplicate join
        ("uniform", 1, 5, 0.1),          # single point
        ("constant_dims", 120, 6, 0.2),  # degenerate dimensions
    ],
)
def test_fused_edge_cases_one_device(kind, n, dims, eps):
    d = make_dataset(kind, n, dims, seed=3)
    cfg = SelfJoinConfig(eps=eps, k=3, tile_size=8, dim_block=8)
    de = DistributedSelfJoinEngine(d, cfg, mesh=_mesh1(), fused=True)
    np.testing.assert_array_equal(de.count().counts, brute_counts(d, eps))


def test_fused_pallas_backend_parity():
    # pallas_call has no shard_map varying-axes rule: the fused program must
    # run with the check off for this backend (check_vma=False)
    import dataclasses

    d = make_dataset("exponential", 180, 16, seed=9)
    cfg = SelfJoinConfig(
        eps=0.08, k=4, tile_size=16, dim_block=8, use_pallas=True
    )
    de = DistributedSelfJoinEngine(d, cfg, mesh=_mesh1(), fused=True)
    np.testing.assert_array_equal(de.count().counts, brute_counts(d, 0.08))
    jnp_cfg = dataclasses.replace(cfg, use_pallas=False)
    np.testing.assert_array_equal(
        de.count().counts,
        DistributedSelfJoinEngine(d, jnp_cfg, mesh=_mesh1(), fused=True)
        .count().counts,
    )


def test_fused_requires_matching_mesh():
    d = make_dataset("uniform", 64, 4, seed=1)
    with pytest.raises(ValueError, match="fused"):
        DistributedSelfJoinEngine(
            d, SelfJoinConfig(eps=0.1, k=2), num_workers=8, fused=True
        )
    with pytest.raises(ValueError, match="ring size"):
        DistributedSelfJoinEngine(
            d, SelfJoinConfig(eps=0.1, k=2), mesh=_mesh1(), num_workers=8,
            fused=True,
        )


SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, sys.argv[1])
    sys.path.insert(0, sys.argv[2])
    import numpy as np, jax
    from oracles import brute_counts, make_dataset
    from repro.core import DistributedSelfJoinEngine, SelfJoinConfig, SelfJoinEngine

    D = make_dataset("exponential", 1003, 16, seed=5)  # 1003 % 8 != 0
    cfg = SelfJoinConfig(eps=0.06, k=4, tile_size=16)
    truth = brute_counts(D, cfg.eps)
    single = SelfJoinEngine(D, cfg).count().counts

    meshes = [
        (jax.make_mesh((8,), ("data",)), "data"),
        (jax.make_mesh((2, 4), ("pod", "data")), ("pod", "data")),
    ]
    for mesh, axes in meshes:
        for assignment in ("round_robin", "dynamic"):
            fused_eng = DistributedSelfJoinEngine(
                D, cfg, mesh=mesh, axes=axes, assignment=assignment, fused=True
            )
            fused = fused_eng.count()
            host = DistributedSelfJoinEngine(
                D, cfg, mesh=mesh, axes=axes, assignment=assignment
            ).count()
            tag = f"{axes}/{assignment}"
            assert np.array_equal(fused.counts, host.counts), f"{tag}: fused != host"
            assert np.array_equal(fused.counts, single), f"{tag}: fused != single"
            assert np.array_equal(fused.counts, truth), f"{tag}: fused != brute"
            assert fused_eng.fused_traces == 1, f"{tag}: retraced"
            assert fused.stats.num_device_dispatches == 1
            assert fused.stats.num_workers == 8 and fused.stats.num_rounds == 8
            assert fused.stats.comm_elements == 7 * 1003

    # eps sweep on one mesh: same program, still exact at every radius
    eng = DistributedSelfJoinEngine(D, cfg, mesh=meshes[0][0], fused=True)
    for eps in (0.06, 0.04, 0.02):
        assert np.array_equal(eng.count(eps).counts, brute_counts(D, eps)), eps
    assert eng.fused_traces == 1 and eng.fused_executions == 3

    # workers with zero query batches (|D| < |p|), on a real 8-ring
    tiny = make_dataset("uniform", 5, 4, seed=4)
    tcfg = SelfJoinConfig(eps=0.3, k=2, tile_size=8)
    teng = DistributedSelfJoinEngine(tiny, tcfg, mesh=meshes[0][0], fused=True)
    assert np.array_equal(teng.count().counts, brute_counts(tiny, 0.3))
    print("FUSED_RING_OK")
    """
)


def test_fused_ring_8_devices():
    here = os.path.dirname(__file__)
    src = os.path.join(here, "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, src, here],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "FUSED_RING_OK" in out.stdout
