"""The online query service (DESIGN.md #8): SimilarityIndex + QueryService.

Correctness is pinned exactly against the shared oracles (``bipartite_counts``
for range queries, ``brute_topk`` for kNN -- float64, ties by data id) over
every dataset kind in the shared matrix, and the serving contracts are pinned
as hard counters: a mixed-shape request stream compiles at most one count
executable per shape bucket (``ServiceStats.num_traces``), and an index
reloaded from disk serves bit-identically to the one that was saved.
"""
import numpy as np
import pytest

from oracles import (
    bipartite_counts,
    brute_topk,
    make_dataset,
    pair_set,
)
from repro.core import EngineConfig, SelfJoinConfig, select_k
from repro.core.grid import bucket_rows
from repro.join import QueryService, SimilarityIndex


def _cfg(eps, **kw):
    kw.setdefault("k", 4)
    kw.setdefault("tile_size", 16)
    kw.setdefault("dim_block", 8)
    return SelfJoinConfig(eps=eps, **kw)


def _queries(d, seed, n_extra=24):
    """Mixed batch: dataset rows (exact hits, duplicates) + fresh points."""
    extra = make_dataset("uniform", n_extra, d.shape[1], seed=seed)
    return np.concatenate([d[: min(41, len(d))], extra])


# -- range queries -----------------------------------------------------------


def test_range_count_matches_oracle_and_engine(dataset_case):
    name, d, eps = dataset_case
    idx = SimilarityIndex(d, _cfg(eps))
    svc = QueryService(idx)
    q = _queries(d, seed=31)
    res = svc.range_count(q, eps)
    np.testing.assert_array_equal(res.counts, bipartite_counts(q, d, eps))
    # the service's bucket-padded path equals the engine's unpadded one
    np.testing.assert_array_equal(
        res.counts, idx.engine.count_query(q, eps).counts
    )
    assert res.stats.num_queries == q.shape[0]
    assert res.stats.bucket >= q.shape[0]
    assert res.stats.num_results == int(res.counts.sum())


def test_range_pairs_matches_oracle(dataset_case):
    name, d, eps = dataset_case
    svc = QueryService(SimilarityIndex(d, _cfg(eps)))
    q = _queries(d, seed=32)
    res = svc.range_pairs(q, eps)
    d2 = (
        (q[:, None, :].astype(np.float64) - d[None, :, :].astype(np.float64))
        ** 2
    ).sum(-1)
    want = set(zip(*map(list, np.nonzero(d2 <= np.float64(eps) ** 2))))
    assert pair_set(res.pairs) == want
    np.testing.assert_array_equal(res.counts, bipartite_counts(q, d, eps))
    # rows are lexsorted: deterministic across buffer layouts
    assert res.pairs.shape[0] == len(want)
    if res.pairs.shape[0] > 1:
        keys = res.pairs[:, 0].astype(np.int64) * (len(d) + 1) + res.pairs[:, 1]
        assert (np.diff(keys) > 0).all()


def test_smaller_eps_than_index_reuses_it(dataset_case):
    name, d, eps = dataset_case
    idx = SimilarityIndex(d, _cfg(eps))
    svc = QueryService(idx)
    q = _queries(d, seed=33)
    res = svc.range_count(q, eps / 2)
    np.testing.assert_array_equal(res.counts, bipartite_counts(q, d, eps / 2))
    assert res.stats.index_rebuilds == 0


# -- kNN ---------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 5])
def test_knn_matches_bruteforce_topk(dataset_case, k):
    name, d, eps = dataset_case
    svc = QueryService(SimilarityIndex(d, _cfg(eps)))
    q = _queries(d, seed=34)
    res = svc.knn(q, k)
    want_idx, want_dist = brute_topk(q, d, k)
    np.testing.assert_array_equal(res.indices, want_idx)
    np.testing.assert_array_equal(res.distances, want_dist)
    assert res.stats.eps_rounds >= 1
    # the final radius really held >= k candidates for every query
    assert (res.counts >= min(k, len(d))).all()


def test_knn_k_at_least_dataset_size_pads():
    d = make_dataset("uniform", 23, 6, seed=40)
    svc = QueryService(SimilarityIndex(d, _cfg(0.2)))
    q = _queries(d, seed=41)[:9]
    k = 40  # > |D|
    res = svc.knn(q, k)
    want_idx, want_dist = brute_topk(q, d, k)
    np.testing.assert_array_equal(res.indices, want_idx)
    np.testing.assert_array_equal(res.distances, want_dist)
    assert (res.indices[:, 23:] == -1).all()
    assert np.isinf(res.distances[:, 23:]).all()
    # reaching every point forced the radius up to the full-domain cap
    assert res.stats.eps_rounds > 1


def test_knn_duplicated_points_break_ties_by_id():
    d = make_dataset("duplicated", 90, 6, seed=42)
    svc = QueryService(SimilarityIndex(d, _cfg(0.1)))
    q = d[:31]  # exact duplicates of indexed points: maximal tie pressure
    res = svc.knn(q, 7)
    want_idx, want_dist = brute_topk(q, d, 7)
    np.testing.assert_array_equal(res.indices, want_idx)
    np.testing.assert_array_equal(res.distances, want_dist)


def test_knn_eps_expansion_from_tiny_radius():
    # queries sit far from the data: the initial radius finds nothing and
    # the expansion loop must double out to the bounding-box diagonal cap
    d = make_dataset("clustered", 120, 8, seed=43)
    svc = QueryService(SimilarityIndex(d, _cfg(0.01)))
    q = np.ones((5, 8), np.float32)  # corner of the domain
    res = svc.knn(q, 3)
    want_idx, want_dist = brute_topk(q, d, 3)
    np.testing.assert_array_equal(res.indices, want_idx)
    np.testing.assert_array_equal(res.distances, want_dist)
    assert res.stats.eps_rounds > 3
    assert res.stats.index_rebuilds >= 1  # grew past the build radius


def test_knn_eps0_index_still_terminates():
    # an index built at eps == 0 (duplicate join) must still answer kNN:
    # doubling from 0 would never grow, so the service seeds from the cap
    d = make_dataset("duplicated", 60, 6, seed=44)
    svc = QueryService(SimilarityIndex(d, _cfg(0.0)))
    q = d[:8]
    res = svc.knn(q, 4)
    want_idx, want_dist = brute_topk(q, d, 4)
    np.testing.assert_array_equal(res.indices, want_idx)
    np.testing.assert_array_equal(res.distances, want_dist)


# -- serving contracts -------------------------------------------------------


def test_compile_reuse_contract_mixed_stream():
    """100 mixed-shape range requests compile <= one program per bucket."""
    d = make_dataset("exponential", 397, 16, seed=50)
    svc = QueryService(SimilarityIndex(d, _cfg(0.08)))
    pool = _queries(d, seed=51, n_extra=300)
    rng = np.random.default_rng(52)
    for i in range(100):
        nq = int(rng.integers(1, 300))
        eps = float(rng.choice([0.08, 0.05, 0.031, 0.017]))  # all <= build eps
        q = pool[rng.choice(pool.shape[0], size=nq, replace=False)]
        res = svc.range_count(q, eps)
        np.testing.assert_array_equal(res.counts, bipartite_counts(q, d, eps))
    assert svc.total.num_requests == 100
    assert svc.total.index_rebuilds == 0
    # the contract: one count executable per shape bucket, nothing more
    assert svc.total.num_traces <= len(svc.buckets_used)
    assert len(svc.buckets_used) <= 6  # pow2 buckets covering 1..299 from 16

    # a second identical-shape stream retraces NOTHING
    before = svc.total.num_traces
    for nq in (3, 40, 100, 250):
        svc.range_count(pool[:nq], 0.06)
    assert svc.total.num_traces == before


def test_chunk_count_is_not_a_trace_key():
    """Batches of one query bucket whose chunk counts fall in different
    power-of-two buckets, some past one table's rows, run one count
    executable -- before and after a compaction swap that changes the
    chunk counts again."""
    d = make_dataset("exponential", 397, 16, seed=50)
    extra = make_dataset("exponential", 20, 16, seed=51)
    idx = SimilarityIndex(
        d, _cfg(0.08), EngineConfig(count_chunk=12, pairs_chunk=12)
    )
    svc = QueryService(idx)
    batches = (d[:33], d[:64], d[10:45], d[:50])

    def num_chunks(q):
        tab = idx.engine.prepare_query(q, 0.08, pad_queries_to=64)
        return -(-tab.num_pairs // 12)

    def serve(q, live):
        res = svc.range_count(q, 0.08)
        np.testing.assert_array_equal(
            res.counts, bipartite_counts(q, live, 0.08)
        )
        assert res.stats.bucket == 64
        return num_chunks(q)

    before = [serve(q, d) for q in batches]
    assert svc.total.num_traces == 1
    idx.insert(extra)
    idx.compact()                                  # the swap
    live = np.concatenate([d, extra])
    after = [serve(q, live) for q in batches]
    seen = before + after
    assert len({bucket_rows(k) for k in seen}) >= 2
    assert max(seen) > 8 and before != after       # several tables a batch
    assert svc.total.num_traces == 1
    assert svc.buckets_used == {64}

    # the pairs step reads the same tables: its answers span them all
    q = batches[1]
    res = svc.range_pairs(q, 0.08)
    np.testing.assert_array_equal(res.counts, bipartite_counts(q, live, 0.08))
    assert len(res.pairs) == res.counts.sum()


def test_pairs_and_knn_trace_keys_are_bounded():
    d = make_dataset("uniform", 211, 8, seed=53)
    svc = QueryService(SimilarityIndex(d, _cfg(0.3)))
    q = _queries(d, seed=54)
    first = svc.range_pairs(q, 0.3)
    traces_after_first = svc.total.num_traces
    # same bucket, same pow2 pairs capacity: the repeat adds zero traces
    again = svc.range_pairs(q, 0.3)
    assert svc.total.num_traces == traces_after_first
    np.testing.assert_array_equal(first.pairs, again.pairs)
    kn1 = svc.knn(q, 4)
    knn_traces = svc.total.num_traces
    kn2 = svc.knn(q, 4)
    np.testing.assert_array_equal(kn1.indices, kn2.indices)
    assert svc.total.num_traces == knn_traces  # expansion path fully cached


def test_index_stays_pinned_at_build_radius_after_knn():
    """A far-query kNN must not degrade later requests (epoch pinning):
    over-radius rounds serve from TEMPORARY rebuilt snapshots and the
    resident snapshot -- and every warm executable -- is never touched."""
    d = make_dataset("clustered", 300, 8, seed=58)
    svc = QueryService(SimilarityIndex(d, _cfg(0.05)))
    q = _queries(d, seed=59)
    base = svc.range_count(q, 0.05)
    warm_traces = svc.total.num_traces

    far = np.ones((3, 8), np.float32)  # forces expansion out to the cap
    kn = svc.knn(far, 2)
    assert kn.stats.index_rebuilds >= 2          # one temp snapshot per round
    assert svc.index.index_eps == 0.05           # the resident never moved

    after = svc.range_count(q, 0.05)
    np.testing.assert_array_equal(after.counts, base.counts)
    # the untouched resident kept its filtering power and warm executable
    assert after.stats.num_candidates == base.stats.num_candidates
    assert after.stats.num_traces == 0
    assert svc.total.num_traces >= warm_traces   # knn traced; range did not


def test_mixed_stream_straddling_tier_boundary_compile_contract():
    """An ``execution="auto"`` stream that flips tiers per request still
    compiles at most ONE count executable per shape bucket PER TIER.

    Hot batches (cluster members, maximal grid fan-out) dispatch dense;
    cold batches (empty-corner points, no adjacency) dispatch indexed --
    same index, same bucket.  The tile tables differ per tier, so each
    tier owns its executable; the contract bounds the total at
    buckets x tiers and pins a repeat stream at zero retraces.
    """
    d = make_dataset("clustered", 300, 4, seed=60)
    svc = QueryService(SimilarityIndex(d, _cfg(0.15, execution="auto")))
    hot = d[:48]
    cold = np.full((48, 4), 0.99, np.float32)
    seen = set()
    for _ in range(3):  # repeats must hit warm executables on both tiers
        for q, want_tier in ((hot, "dense"), (cold, "indexed")):
            res = svc.range_count(q, 0.15)
            np.testing.assert_array_equal(
                res.counts, bipartite_counts(q, d, 0.15)
            )
            assert res.stats.execution == want_tier
            assert res.stats.cost_indexed > 0 and res.stats.cost_dense > 0
            seen.add(res.stats.execution)
    assert seen == {"dense", "indexed"}
    assert svc.total.execution == "mixed"  # the stream really straddled
    assert svc.total.num_requests == 6
    # <= one executable per (bucket, tier); both batches share one bucket
    assert len(svc.buckets_used) == 1
    assert svc.total.num_traces <= 2 * len(svc.buckets_used)

    # a second identical straddling stream retraces NOTHING
    before = svc.total.num_traces
    for q in (hot, cold, hot, cold):
        svc.range_count(q, 0.15)
    assert svc.total.num_traces == before

    # pairs mode honours the same per-tier dispatch and stays exact
    for q in (hot, cold):
        rp = svc.range_pairs(q, 0.15)
        np.testing.assert_array_equal(rp.counts, bipartite_counts(q, d, 0.15))


@pytest.mark.parametrize("mode", ["indexed", "dense", "auto"])
def test_save_load_roundtrips_execution_mode_bit_identically(tmp_path, mode):
    d = make_dataset("exponential", 211, 16, seed=62)
    idx = SimilarityIndex(d, _cfg(0.06, execution=mode))
    svc = QueryService(idx)
    q = _queries(d, seed=63)
    want = svc.range_count(q, 0.06)
    want_pairs = svc.range_pairs(q, 0.06).pairs

    loaded = SimilarityIndex.load(idx.save(tmp_path / f"exec_{mode}"))
    assert loaded.config == idx.config
    assert loaded.config.execution == mode  # the mode bit survived the disk
    svc2 = QueryService(loaded)
    got = svc2.range_count(q, 0.06)
    np.testing.assert_array_equal(got.counts, want.counts)
    np.testing.assert_array_equal(svc2.range_pairs(q, 0.06).pairs, want_pairs)
    # the reloaded index makes the SAME dispatch decision with the SAME costs
    assert got.stats.execution == want.stats.execution
    assert got.stats.cost_indexed == want.stats.cost_indexed
    assert got.stats.cost_dense == want.stats.cost_dense


def test_index_save_load_serves_bit_identically(tmp_path, dataset_case):
    name, d, eps = dataset_case
    idx = SimilarityIndex(d, _cfg(eps))
    svc = QueryService(idx)
    q = _queries(d, seed=55)
    want_counts = svc.range_count(q, eps).counts
    want_pairs = svc.range_pairs(q, eps).pairs
    want_knn = svc.knn(q, 3)

    path = idx.save(tmp_path / f"{name}.idx")
    loaded = SimilarityIndex.load(path)
    assert loaded.config == idx.config
    assert loaded.index_eps == idx.index_eps
    if idx.perm is not None:
        np.testing.assert_array_equal(loaded.perm, idx.perm)
    svc2 = QueryService(loaded)
    np.testing.assert_array_equal(svc2.range_count(q, eps).counts, want_counts)
    np.testing.assert_array_equal(svc2.range_pairs(q, eps).pairs, want_pairs)
    got_knn = svc2.knn(q, 3)
    np.testing.assert_array_equal(got_knn.indices, want_knn.indices)
    np.testing.assert_array_equal(got_knn.distances, want_knn.distances)


def test_auto_k_selection_is_baked_into_the_index(tmp_path):
    d = make_dataset("exponential", 500, 16, seed=56)
    ks = [2, 3, 4, 6]
    idx = SimilarityIndex(d, _cfg(0.05, k=2), k_candidates=ks)
    want_k = select_k(d, 0.05, ks, sample_frac=0.01, tile_size=16)
    assert idx.config.k == want_k
    loaded = SimilarityIndex.load(idx.save(tmp_path / "auto_k"))
    assert loaded.config.k == want_k  # no re-tuning on restart


def test_empty_edges():
    d = make_dataset("uniform", 50, 6, seed=57)
    svc = QueryService(SimilarityIndex(d, _cfg(0.2)))
    empty_q = np.zeros((0, 6), np.float32)
    assert svc.range_count(empty_q).counts.shape == (0,)
    assert svc.range_pairs(empty_q).pairs.shape == (0, 2)
    assert svc.knn(empty_q, 3).indices.shape == (0, 3)
    res = svc.knn(d[:4], 0)
    assert res.indices.shape == (4, 0)

    empty_idx = SimilarityIndex(np.zeros((0, 6), np.float32), _cfg(0.2))
    esvc = QueryService(empty_idx)
    q = d[:5]
    assert (esvc.range_count(q).counts == 0).all()
    assert esvc.range_pairs(q).pairs.shape == (0, 2)
    kn = esvc.knn(q, 3)
    assert (kn.indices == -1).all() and np.isinf(kn.distances).all()
