"""Property-based tests (hypothesis) for the self-join invariants.

Skipped gracefully when hypothesis is absent (it is a dev-only dependency;
see requirements-dev.txt).
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from oracles import brute_counts, brute_pairs, brute_topk
from repro.core import SelfJoinConfig, SelfJoinEngine, self_join
from repro.core import batching
from repro.core.grid import adjacent_cell_pairs, build_grid, build_tile_plan
from repro.core.reorder import apply_reorder, inverse_perm, variance_reorder
from repro.join import QueryService, SimilarityIndex
from repro.kernels.ref import direct_sqdist, matmul_sqdist


def _data(draw, max_n=200, max_d=12):
    n = draw(st.integers(8, max_n))
    d = draw(st.integers(2, max_d))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    kind = draw(st.sampled_from(["uniform", "exp", "clustered"]))
    if kind == "uniform":
        pts = rng.random((n, d))
    elif kind == "exp":
        pts = np.clip(rng.exponential(1 / 40.0, (n, d)), 0, 1)
    else:
        c = rng.random((4, d))
        pts = np.clip(c[rng.integers(0, 4, n)] + rng.normal(0, 0.05, (n, d)), 0, 1)
    # quantize so fp32 distance sums are exact in every formulation
    return (np.round(pts * 64) / 64).astype(np.float32)


points = st.builds(lambda: None)  # placeholder (built in @given via draw)


@st.composite
def dataset(draw):
    return _data(draw)


@settings(max_examples=25, deadline=None)
@given(dataset(), st.sampled_from([0.05, 0.11, 0.23, 0.41]), st.integers(1, 6))
def test_join_equals_brute(d, eps, k):
    cfg = SelfJoinConfig(eps=eps, k=k, tile_size=8, dim_block=8)
    res = self_join(d, cfg)
    np.testing.assert_array_equal(res.counts, brute_counts(d, eps))


@settings(max_examples=15, deadline=None)
@given(dataset(), st.integers(0, 2**31 - 1))
def test_reorder_preserves_pairwise_distances(d, seed):
    r, perm = variance_reorder(d, 0.05, seed % 1000)
    assert sorted(perm.tolist()) == list(range(d.shape[1]))
    i, j = 0, min(5, d.shape[0] - 1)
    dd = np.linalg.norm(d[i] - d[j])
    rr = np.linalg.norm(r[i] - r[j])
    assert abs(dd - rr) < 1e-5


@settings(max_examples=15, deadline=None)
@given(dataset(), st.integers(0, 2**31 - 1))
def test_apply_reorder_roundtrips_external_points(d, seed):
    """External points permute identically to the dataset, and invert back.

    The serving contract: ``variance_reorder``'s output IS ``apply_reorder``
    of its permutation, queries permuted with the persisted perm land in the
    index's frame, and ``inverse_perm`` undoes it exactly.
    """
    r, perm = variance_reorder(d, 0.05, seed % 1000)
    np.testing.assert_array_equal(r, apply_reorder(d, perm))
    external = d[:: max(1, d.shape[0] // 7)] + np.float32(1 / 64)
    round_trip = apply_reorder(apply_reorder(external, perm), inverse_perm(perm))
    np.testing.assert_array_equal(round_trip, external)
    inv = inverse_perm(perm)
    np.testing.assert_array_equal(perm[inv], np.arange(d.shape[1]))
    np.testing.assert_array_equal(inv[perm], np.arange(d.shape[1]))


@settings(max_examples=10, deadline=None)
@given(dataset(), st.integers(1, 9))
def test_knn_equals_bruteforce_topk(d, k):
    """Service kNN == float64 brute-force top-k, ties by data id, any data."""
    svc = QueryService(
        SimilarityIndex(d, SelfJoinConfig(eps=0.2, k=3, tile_size=8, dim_block=8))
    )
    q = d[: min(16, d.shape[0])]
    res = svc.knn(q, k)
    want_idx, want_dist = brute_topk(q, d, k)
    np.testing.assert_array_equal(res.indices, want_idx)
    np.testing.assert_array_equal(res.distances, want_dist)


@settings(max_examples=15, deadline=None)
@given(dataset())
def test_counts_monotone_in_eps(d):
    c1 = self_join(d, SelfJoinConfig(eps=0.1, k=3, tile_size=8)).counts
    c2 = self_join(d, SelfJoinConfig(eps=0.2, k=3, tile_size=8)).counts
    assert (c2 >= c1).all()


@settings(max_examples=15, deadline=None)
@given(dataset(), st.sampled_from([0.1, 0.25]))
def test_grid_invariants(d, eps):
    grid = build_grid(d, eps, k=3)
    # every point appears exactly once in the sorted layout
    assert sorted(grid.point_order.tolist()) == list(range(d.shape[0]))
    assert int(grid.cell_count.sum()) == d.shape[0]
    # adjacency is symmetric and includes self-pairs
    ca, cb = adjacent_cell_pairs(grid)
    pairs = set(zip(ca.tolist(), cb.tolist()))
    assert all((b, a) in pairs for a, b in pairs)
    assert all((c, c) in pairs for c in range(grid.num_cells))
    # tile plan covers each cell's points exactly once
    plan = build_tile_plan(grid, 8, sortidu=False)
    covered = np.zeros(d.shape[0], bool)
    for s, l in zip(plan.tile_start, plan.tile_len):
        assert not covered[s : s + l].any()
        covered[s : s + l] = True
    assert covered.all()


@settings(max_examples=10, deadline=None)
@given(dataset())
def test_self_pairs_always_included(d):
    res = self_join(d, SelfJoinConfig(eps=0.01, k=3, tile_size=8))
    assert (res.counts >= 1).all()  # every point finds at least itself


@settings(max_examples=20, deadline=None)
@given(dataset(), st.sampled_from([0.1, 0.25]))
def test_grid_cell_assignment_roundtrips_point_order(d, eps):
    """pts_sorted IS D[point_order], and each point lies in its owning cell."""
    grid = build_grid(d, eps, k=3)
    np.testing.assert_array_equal(grid.pts_sorted, d[grid.point_order])
    # recomputing each sorted point's cell coords (same floor rule as
    # build_grid) must land on its owning cell's stored coordinates
    coords = (
        np.floor(
            grid.pts_sorted[:, : grid.k].astype(np.float64) / grid.bin_width
        ).astype(np.int64)
        - grid.origin[None, :]
    )
    cell_of_sorted = np.repeat(
        np.arange(grid.num_cells, dtype=np.int64), grid.cell_count
    )
    np.testing.assert_array_equal(coords, grid.cell_coords[cell_of_sorted])
    # and cell runs tile the sorted layout contiguously
    starts = np.concatenate([[0], np.cumsum(grid.cell_count)[:-1]])
    np.testing.assert_array_equal(grid.cell_start, starts)


@settings(max_examples=20, deadline=None)
@given(dataset(), st.sampled_from([0.05, 0.11, 0.23]))
def test_sortidu_plan_covers_all_true_pairs(d, eps):
    """The SORTIDU-pruned tile-pair plan is a superset of all true <=eps pairs."""
    grid = build_grid(d, eps, k=3)
    plan = build_tile_plan(grid, 8, sortidu=True)
    tile_of_pos = np.empty(d.shape[0], np.int64)
    for ti, (s, l) in enumerate(zip(plan.tile_start, plan.tile_len)):
        tile_of_pos[s : s + l] = ti
    pos_of_point = np.empty(d.shape[0], np.int64)
    pos_of_point[grid.point_order] = np.arange(d.shape[0])
    plan_pairs = set(zip(plan.pair_a.tolist(), plan.pair_b.tolist()))
    for a, b in brute_pairs(d, eps):
        ta = int(tile_of_pos[pos_of_point[a]])
        tb = int(tile_of_pos[pos_of_point[b]])
        assert (ta, tb) in plan_pairs, f"true pair {(a, b)} pruned"


@settings(max_examples=10, deadline=None)
@given(dataset(), st.sampled_from([0.1, 0.25]))
def test_capacity_estimate_never_underallocates(d, eps):
    """A full-sample size estimate (and its capacity) covers the true |R|."""
    cfg = SelfJoinConfig(eps=eps, k=3, tile_size=8, dim_block=8)
    eng = SelfJoinEngine(d, cfg)
    est = batching.estimate_result_size(
        np.asarray(eng.snapshot.tiles), np.asarray(eng.snapshot.tile_len),
        eng.plan, eps=eps, backend="jnp", sample_frac=1.0,
    )
    true_r = int(brute_counts(d, eps).sum())
    assert est >= true_r
    assert batching.suggest_pairs_capacity(est, 1.0) >= true_r
    res = eng.pairs()  # auto-sized buffer must end up fitting exactly |R|
    assert res.stats.pairs_capacity >= res.stats.num_results == true_r


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 10**9), st.floats(0.0, 4.0))
def test_suggest_capacity_never_below_estimate(est, headroom):
    assert batching.suggest_pairs_capacity(est, headroom) >= est


@st.composite
def raw_point_sets(draw):
    """Un-quantized fp32 point sets for the matmul-identity property.

    Deliberately NOT pushed through the 1/64 quantizer: the dense tier's
    ``||a-b||^2 = |a|^2 + |b|^2 - 2 a.b`` identity is where fp32 rounding
    actually bites (catastrophic cancellation near zero), so the property
    must hold on arbitrary floats, not just the exact-friendly grid.  The
    two adversarial shapes are drawn explicitly: duplicated points (true
    distance exactly 0 -- the identity's worst cancellation case) and
    constant dimensions (zero-variance axes contribute |a|^2 + |b|^2 mass
    but no separation).
    """
    n = draw(st.integers(2, 48))
    dims = draw(st.integers(1, 24))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    scale = draw(st.sampled_from([1.0, 17.0]))
    pts = (rng.random((n, dims)) * scale).astype(np.float32)
    variant = draw(st.sampled_from(["plain", "duplicated", "constant_dims"]))
    if variant == "duplicated":
        src = rng.integers(0, n, n // 2 + 1)
        dst = rng.integers(0, n, n // 2 + 1)
        pts[dst] = pts[src]
    elif variant == "constant_dims":
        const_cols = rng.integers(0, dims, dims // 2 + 1)
        pts[:, const_cols] = pts[0, const_cols]
    m = draw(st.integers(1, n))
    return pts[:m], pts[rng.permutation(n)]


@settings(max_examples=60, deadline=None)
@given(raw_point_sets())
def test_matmul_identity_clamped_and_close_to_direct(ab):
    """The dense kernel's clamped matmul identity (DESIGN.md #9): never
    negative, exactly zero on duplicated rows' own pairing, and within
    fp32 tolerance of the direct ``sum((a-b)^2)`` form on arbitrary data."""
    a, b = ab
    got = np.asarray(matmul_sqdist(a, b))
    want = np.asarray(direct_sqdist(a, b))
    assert got.shape == (a.shape[0], b.shape[0])
    assert (got >= 0.0).all()
    # fp32 relative tolerance, absolute floor scaled by the norm products
    # that feed the identity (cancellation error is relative to those)
    floor = 1e-5 * float(
        np.maximum(np.square(a).sum(1).max(), np.square(b).sum(1).max()) + 1.0
    )
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=floor)
    # duplicated rows across the two sides: the direct form is exactly 0
    # there, and clamping must pin the identity's negative dust to 0 too
    eq = (a[:, None, :] == b[None, :, :]).all(-1)
    assert (got[eq] <= floor).all()


@settings(max_examples=20, deadline=None)
@given(dataset(), st.sampled_from([0.07, 0.19]))
def test_dense_tier_join_equals_brute(d, eps):
    """Forced-dense execution is oracle-exact on quantized data, any kind."""
    cfg = SelfJoinConfig(eps=eps, k=3, tile_size=8, dim_block=8,
                         execution="dense")
    res = self_join(d, cfg)
    assert res.stats.execution == "dense"
    np.testing.assert_array_equal(res.counts, brute_counts(d, eps))
