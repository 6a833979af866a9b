"""Pairs compaction's rank-select (``engine.pairs_chunk_step``) and the
engine's remainder pass.

The chunk program writes ranks first_rank..first_rank+hit_cap-1 of the
chunk's hit mask, in flat (pair, row, lane) order, as one block at
``offset``, resolving each rank per tile pair, per row, per lane.  At
every rank window it must write exactly what the flat ``cumsum`` +
``searchsorted`` reference below writes, for any mask a backend can emit.
A remainder block (first rank > 0) writes only the chunk's ranks and
leaves every other row of the buffer as it was.  A join whose chunks
outgrow the first rank window must come out identical, in order, to one
pass at a window that holds every chunk.  The exact bipartite pass that the
serving tier and the host-driven ring share widens its window instead, and
must come out exact too.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracles import brute_pairs, make_dataset, pair_set
from repro import obs
from repro.core import (
    DistributedSelfJoinEngine,
    EngineConfig,
    SelfJoinConfig,
    SelfJoinEngine,
)
from repro.core import engine as engine_mod
from repro.join import QueryService, SimilarityIndex

C, T = 128, 16
NUM_TILES = 40
CAP = 5_000

# rank windows below, at and above C*T: a hit-cap retry can widen the
# window to anything up to C*T*T
HIT_CAPS = [1024, C * T, 3072]


def _flat_select_reference(buf, offset, max_chunk_hits, mask, tile_start,
                           point_order, pa, pb, real, hit_cap, first_rank=0):
    """The flat select: a prefix sum over all C*T*T flags, then
    ``searchsorted`` of ranks first_rank+1..first_rank+hit_cap; a remainder
    block writes only the ranks the chunk holds."""
    c, t, _ = mask.shape
    cap = buf.shape[0] - hit_cap
    valid = np.arange(c) < real
    hits = ((mask != 0) & valid[:, None, None]).reshape(c, t * t)
    row_cum = np.cumsum(hits, axis=1)
    row_tot = row_cum[:, -1]
    cum = (row_cum + (np.cumsum(row_tot) - row_tot)[:, None]).reshape(-1)
    nh = int(row_tot.sum())
    ranks = np.arange(first_rank + 1, first_rank + hit_cap + 1)
    idx = np.minimum(np.searchsorted(cum, ranks), c * t * t - 1)
    p, i, j = idx // (t * t), idx // t % t, idx % t
    block = np.stack(
        [point_order[tile_start[pa[p]] + i], point_order[tile_start[pb[p]] + j]],
        axis=1,
    )
    out = buf.copy()
    woff = min(offset, cap)
    if first_rank:
        live = ranks <= nh
        out[woff:woff + hit_cap][live] = block[live]
    else:
        out[woff:woff + hit_cap] = block
    return out, offset + nh, max(max_chunk_hits, nh)


def _mask(rng, layout, real):
    """(C, T, T) int8 hit mask of one chunk."""
    kind, _, density = layout.partition("=")
    if kind == "density":
        return (rng.random((C, T, T)) < float(density)).astype(np.int8)
    mask = np.zeros((C, T, T), np.int8)
    p, i = int(rng.integers(real)), int(rng.integers(T))
    if kind == "one_row":
        mask[p, i] = 1
    elif kind == "one_pair":
        mask[p] = rng.random((T, T)) < 0.5
    else:
        raise ValueError(layout)
    return mask


# (mask layout, valid prefix of the chunk, offset before the chunk)
CASES = {
    "empty": ("density=0", C, 37),
    "d1e-4": ("density=1e-4", C, 37),
    "d2e-3": ("density=2e-3", C, 37),
    "d5e-2": ("density=5e-2", C, 37),
    "hits_past_real": ("density=2e-2", 77, 37),
    "one_row": ("one_row", C, 37),
    "one_pair": ("one_pair", C, 37),
    "nh_over_hit_cap": ("density=0.2", C, 37),
    "offset_past_cap": ("density=2e-3", C, CAP + 5),
}


def _step_and_reference(monkeypatch, rng, mask, real, offset, hit_cap,
                        first_rank=0):
    """One chunk step on ``mask`` and the flat reference's answer:
    (got buf, offset, max hits), (want buf, offset, max hits)."""
    tile_start = (rng.permutation(NUM_TILES) * T).astype(np.int32)
    point_order = rng.permutation(NUM_TILES * T).astype(np.int32)
    pa = rng.integers(0, NUM_TILES, C).astype(np.int32)
    pb = rng.integers(0, NUM_TILES, C).astype(np.int32)
    buf = rng.integers(-9, 0, (CAP + hit_cap, 2)).astype(np.int32)
    max_chunk_hits = 5

    # the kernel is replaced by one that hands back the mask passed in as
    # `tiles`, so the select sees exactly this mask
    monkeypatch.setattr(
        engine_mod.ops, "eval_tile_pairs",
        lambda tiles, *args, **kwargs: (None, None, tiles),
    )
    step = jax.jit(functools.partial(
        engine_mod.pairs_chunk_step, hit_cap=hit_cap, backend="jnp",
        first_rank=first_rank,
    ))
    got = step(
        jnp.asarray(buf), jnp.int32(offset), jnp.int32(max_chunk_hits),
        jnp.asarray(mask), jnp.zeros(NUM_TILES, jnp.int32),
        jnp.asarray(tile_start), jnp.asarray(point_order),
        jnp.asarray(pa), jnp.asarray(pb), jnp.int32(real), jnp.float32(0),
    )
    want = _flat_select_reference(
        buf, offset, max_chunk_hits, mask, tile_start, point_order,
        pa, pb, real, hit_cap, first_rank,
    )
    return [np.asarray(g) for g in got], want


@pytest.mark.parametrize("hit_cap", HIT_CAPS, ids=lambda h: f"hit_cap{h}")
@pytest.mark.parametrize("case", list(CASES))
def test_select_matches_flat_reference(case, hit_cap, monkeypatch):
    layout, real, offset = CASES[case]
    rng = np.random.default_rng([list(CASES).index(case), hit_cap])
    mask = _mask(rng, layout, real)
    if case == "hits_past_real":
        assert mask[real:].any()
    (got_buf, got_off, got_max), (want_buf, want_off, want_max) = (
        _step_and_reference(monkeypatch, rng, mask, real, offset, hit_cap)
    )
    nh = want_off - offset
    written = min(offset, CAP) + min(nh, hit_cap)
    assert int(got_off) == want_off
    assert int(got_max) == want_max
    np.testing.assert_array_equal(got_buf[:written], want_buf[:written])
    if case == "nh_over_hit_cap":
        assert nh > hit_cap
    if case in ("d2e-3", "one_pair"):
        assert 0 < nh <= hit_cap


@pytest.mark.parametrize(
    "n, eps, chunk, sizing, compiles",
    [
        (300, 0.1, 520, {}, 1),
        # a chunk holds > 4,096 hits: one remainder rung
        (700, 0.3, 528, dict(max_pairs=1 << 20), 2),
        (700, 0.3, 528, {}, 1),
    ],
    ids=["default_window", "widened_by_retry", "window_from_estimate"],
)
def test_pairs_across_hit_cap_retry(n, eps, chunk, sizing, compiles):
    # T = 8 and C >= 512: a window past 4,096 ranks is past C*T; clearing
    # the caches makes every program of this case compile here
    jax.clear_caches()
    d = make_dataset("clustered", n, 4, seed=3)
    eng = SelfJoinEngine(
        d, SelfJoinConfig(eps=eps, k=4, tile_size=8),
        EngineConfig(pairs_chunk=chunk),
    )
    with obs.capture() as cap:
        res = eng.pairs(**sizing)
    traced = [
        e for e in cap.spans("engine.trace")
        if e.attrs.get("program") == "pairs_chunk"
    ]
    assert len(traced) == compiles
    assert res.stats.overflow_retries == compiles - 1
    assert cap.span_count("engine.pairs.remainder") == compiles - 1
    window = cap.spans("engine.pairs")[-1].attrs["window"]
    if sizing or n == 300:
        assert window == 4096  # no estimate, or one below the floor
    else:
        assert window > 4096
    assert pair_set(res.pairs) == pair_set(brute_pairs(d, eps))


def _served_pairs(d, cfg, eng_cfg):
    svc = QueryService(SimilarityIndex(d, cfg, eng_cfg))
    res = svc.range_pairs(d)  # the dataset as queries: row i is point i
    return res.pairs, res.counts, svc.range_count(d).counts


def _host_ring_pairs(d, cfg, eng_cfg):
    de = DistributedSelfJoinEngine(
        d, cfg, num_workers=2, engine_config=eng_cfg
    )
    res = de.self_join_pairs(fused=False)
    return res.pairs, res.counts, de.count().counts


@pytest.mark.parametrize(
    "run, retry",
    [(_served_pairs, "service.pairs.retry"),
     (_host_ring_pairs, "ring.block.pairs.retry")],
    ids=["service", "host_ring"],
)
def test_exact_bipartite_pass_widens(run, retry):
    # T = 8, C = 528: bipartite chunks of this clustered set hold up to
    # ~4.8k hits, past the 4,096-rank first window of the exact pass
    d = make_dataset("clustered", 1400, 4, seed=3)
    cfg = SelfJoinConfig(eps=0.3, k=4, tile_size=8)
    with obs.capture() as cap:
        pairs, counts, count_pass = run(d, cfg, EngineConfig(pairs_chunk=528))
    widened = cap.spans(retry)
    assert widened and all(e.attrs["kind"] == "hit_cap" for e in widened)
    assert max(e.attrs["max_hits"] for e in widened) > 4096
    assert pair_set(pairs) == pair_set(brute_pairs(d, cfg.eps))
    np.testing.assert_array_equal(counts, count_pass)


# (first rank, rank window, hit density): a remainder block's ranks start
# past the first pass's window; rows past the chunk's hits hold the next
# chunk's pairs and must keep them
REMAINDER_BLOCKS = {
    "gap_inside_window": (1024, 1024, 0.047),
    "window_ends_at_flat": (C * T * T - 4096, 4096, 0.95),
    "no_hits_past_first_rank": (2048, 1024, 0.02),
}


@pytest.mark.parametrize("case", list(REMAINDER_BLOCKS))
def test_remainder_block_leaves_other_rows(case, monkeypatch):
    first_rank, hit_cap, density = REMAINDER_BLOCKS[case]
    rng = np.random.default_rng([99, list(REMAINDER_BLOCKS).index(case)])
    mask = (rng.random((C, T, T)) < density).astype(np.int8)
    offset = 600  # the start of the chunk's gap
    (got_buf, got_off, _), (want_buf, want_off, _) = _step_and_reference(
        monkeypatch, rng, mask, C, offset, hit_cap, first_rank,
    )
    live = min(max(want_off - offset - first_rank, 0), hit_cap)
    if case == "no_hits_past_first_rank":
        assert live == 0
    else:
        assert 0 < live < hit_cap
    assert first_rank + hit_cap <= C * T * T
    assert int(got_off) == want_off
    # every row of the buffer: the gap filled, nothing else touched
    np.testing.assert_array_equal(got_buf, want_buf)


@pytest.mark.parametrize(
    "n, flat, want",
    [(0, 1 << 20, 4096), (4096, 1 << 20, 4096), (4097, 1 << 20, 8192),
     (70_000, 1 << 20, 131072), (70_000, 65536, 65536), (10, 2048, 2048)],
)
def test_hit_window_rungs(n, flat, want):
    assert engine_mod.hit_window(n, flat) == want


# skewed exponential data, tile pairs of (T=16, C=64) or (T=8, C=128): at a
# 4,096-rank first window at least three chunks outgrow it, one by more
# than one rung; (size, eps, chunk, tile size, sizing)
REMAINDER_JOINS = {
    "explicit_capacity": (1200, 0.05, 64, 16, dict(max_pairs=1 << 20)),
    "capacity_overflow": (1200, 0.05, 64, 16, dict(_cap_hint=1)),
    "rung_ends_at_flat": (1500, 0.05, 128, 8, dict(max_pairs=1 << 20)),
}


@pytest.mark.parametrize("case", list(REMAINDER_JOINS))
def test_remainder_pass_matches_one_pass(case, monkeypatch):
    n, eps, chunk, t, sizing = REMAINDER_JOINS[case]
    flat = chunk * t * t
    d = make_dataset("exponential", n, 4, seed=11)
    eng = SelfJoinEngine(
        d, SelfJoinConfig(eps=eps, k=4, tile_size=t),
        EngineConfig(pairs_chunk=chunk),
    )
    with obs.capture() as cap:
        res = eng.pairs(**sizing)
    (rem,) = cap.spans("engine.pairs.remainder")
    rungs = [e.attrs["rung"] for e in cap.spans("engine.pairs.remainder.chunk")]
    window = cap.spans("engine.pairs")[-1].attrs["window"]
    assert window == 4096 and rem.attrs["chunks"] == len(rungs) >= 3
    if case == "rung_ends_at_flat":
        assert window + max(rungs) == flat
    else:
        assert max(rungs) > min(rungs) == 4096 and rem.attrs["rungs"] >= 2
    capacity = int(case == "capacity_overflow")
    assert res.stats.overflow_retries == 1 + capacity

    # one pass at a window that holds every chunk's hits
    monkeypatch.setattr(engine_mod, "_MIN_HIT_WINDOW", flat)
    with obs.capture() as cap:
        one = eng.pairs(**sizing)
    assert cap.span_count("engine.pairs.remainder") == 0
    assert cap.spans("engine.pairs")[-1].attrs["window"] == flat
    assert one.stats.overflow_retries == capacity
    np.testing.assert_array_equal(res.pairs, one.pairs)
    np.testing.assert_array_equal(res.counts, one.counts)
    assert res.stats.num_results == one.stats.num_results == len(res.pairs)
    assert pair_set(res.pairs) == pair_set(brute_pairs(d, eps))
