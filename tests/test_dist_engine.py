"""Grid-indexed distributed self-join (DESIGN.md #7, paper Sec. 6).

In-process tests cover the bipartite query sub-plan and the BSP ring
schedule on one device; the subprocess test runs the engine against meshes
of 8 simulated host devices (the device-count flag must precede jax init).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np

from oracles import bipartite_counts as _bipartite_truth, brute_counts
from repro.core import (
    DistributedSelfJoinEngine,
    SelfJoinConfig,
    SelfJoinEngine,
)
from repro.data import clustered_dataset, exponential_dataset

CFG = SelfJoinConfig(eps=0.06, k=4, tile_size=16)


def test_count_query_matches_brute_bipartite():
    d = exponential_dataset(900, 16, seed=3)
    q = exponential_dataset(400, 16, seed=11)
    eng = SelfJoinEngine(d, CFG)
    res = eng.count_query(q)
    assert np.array_equal(res.counts, _bipartite_truth(q, d, CFG.eps))
    # index filtering active: fewer candidates than the dense |Q| x |D|
    assert 0 < res.stats.num_candidates < q.shape[0] * d.shape[0]


def test_count_query_self_equals_count():
    d = clustered_dataset(700, 8, seed=2)
    cfg = SelfJoinConfig(eps=0.08, k=5, tile_size=16)
    eng = SelfJoinEngine(d, cfg)
    assert np.array_equal(eng.count_query(d).counts, eng.count().counts)


def test_count_query_smaller_eps_reuses_index():
    d = exponential_dataset(600, 16, seed=4)
    q = exponential_dataset(200, 16, seed=5)
    eng = SelfJoinEngine(d, CFG)
    res = eng.count_query(q, eps=0.03)   # index built at 0.06, queried below
    assert np.array_equal(res.counts, _bipartite_truth(q, d, 0.03))


def test_count_query_empty_query():
    d = exponential_dataset(100, 16, seed=1)
    eng = SelfJoinEngine(d, CFG)
    assert eng.count_query(np.zeros((0, 16), np.float32)).counts.shape == (0,)


def test_dist_engine_parity_nondivisible():
    d = exponential_dataset(1003, 16, seed=5)   # 1003 % 8 != 0 (uneven shards)
    truth = brute_counts(d, CFG.eps)
    de = DistributedSelfJoinEngine(d, CFG, num_workers=8)
    res = de.count()
    assert np.array_equal(res.counts, truth)
    assert np.array_equal(res.counts, SelfJoinEngine(d, CFG).count().counts)
    s = res.stats
    assert s.num_workers == 8 and s.num_rounds == 8
    assert s.num_candidates_dense == 1003 * 1003
    assert 0 < s.num_candidates < s.num_candidates_dense
    assert s.comm_elements == 7 * 1003


def test_dist_engine_single_worker_equals_engine():
    d = exponential_dataset(500, 16, seed=7)
    de = DistributedSelfJoinEngine(d, CFG, num_workers=1)
    assert np.array_equal(de.count().counts, SelfJoinEngine(d, CFG).count().counts)


def test_dist_engine_shards_share_the_dataset_reorder_frame():
    """Every shard uses the whole dataset's REORDER permutation, so shard
    distances sum coordinates in the single-device engine's order."""
    rng = np.random.default_rng(0)
    # each quarter of the rows has a different widest dimension
    d = rng.random((800, 6)).astype(np.float32) * 0.1
    for j in range(4):
        d[200 * j : 200 * (j + 1), j] *= 5 + j
    de = DistributedSelfJoinEngine(d, SelfJoinConfig(eps=0.05, k=3), num_workers=4)
    want = SelfJoinEngine(d, SelfJoinConfig(eps=0.05, k=3)).snapshot.perm
    for shard in de.shards:
        assert np.array_equal(shard.snapshot.perm, want)
    assert np.array_equal(de.count().counts, brute_counts(d, 0.05))


def test_dist_engine_dynamic_assignment_parity_and_balance():
    d = exponential_dataset(800, 16, seed=9)
    truth = brute_counts(d, CFG.eps)
    rr = DistributedSelfJoinEngine(d, CFG, num_workers=8, num_batches=32)
    dyn = DistributedSelfJoinEngine(
        d, CFG, num_workers=8, num_batches=32, assignment="dynamic"
    )
    assert np.array_equal(rr.count().counts, truth)
    assert np.array_equal(dyn.count().counts, truth)
    # LPT on cost estimates never loads the max worker more than round-robin
    assert dyn.worker_loads().max() <= rr.worker_loads().max() + 1e-9


def test_dist_engine_ring_schedule_covers_all_shards():
    de = DistributedSelfJoinEngine(
        exponential_dataset(64, 4, seed=0), SelfJoinConfig(eps=0.1, k=2),
        num_workers=4,
    )
    seen = {k: set() for k in range(4)}
    for round_sched in de.ring_schedule():
        for k, j in round_sched:
            seen[k].add(j)
    assert all(seen[k] == {0, 1, 2, 3} for k in range(4))


SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, sys.argv[1])
    import numpy as np, jax
    from repro.core import DistributedSelfJoinEngine, SelfJoinConfig, SelfJoinEngine
    from repro.core.brute import brute_counts
    from repro.data import exponential_dataset

    D = exponential_dataset(1003, 16, seed=5)   # non-divisible -> uneven shards
    eps = 0.06
    cfg = SelfJoinConfig(eps=eps, k=4, tile_size=16)
    truth = brute_counts(D, eps)

    mesh1 = jax.make_mesh((8,), ("data",))
    de1 = DistributedSelfJoinEngine(D, cfg, mesh=mesh1)
    r1 = de1.count()
    assert de1.num_workers == 8
    assert np.array_equal(r1.counts, truth), "1-axis mesh mismatch"
    assert np.array_equal(r1.counts, SelfJoinEngine(D, cfg).count().counts)
    assert 0 < r1.stats.num_candidates < r1.stats.num_candidates_dense

    mesh2 = jax.make_mesh((2, 4), ("pod", "data"))
    de2 = DistributedSelfJoinEngine(D, cfg, mesh=mesh2, axes=("pod", "data"))
    assert de2.num_workers == 8
    assert np.array_equal(de2.count().counts, truth), "2-axis mesh mismatch"

    dyn = DistributedSelfJoinEngine(
        D, cfg, mesh=mesh1, num_batches=32, assignment="dynamic"
    )
    assert np.array_equal(dyn.count().counts, truth), "dynamic mismatch"
    rr = DistributedSelfJoinEngine(D, cfg, mesh=mesh1, num_batches=32)
    assert dyn.worker_loads().max() <= rr.worker_loads().max() + 1e-9
    print("DIST_ENGINE_OK")
    """
)


def test_dist_engine_8_devices():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, src],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "DIST_ENGINE_OK" in out.stdout
