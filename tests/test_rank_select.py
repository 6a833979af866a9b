"""Pairs compaction's rank-select (``engine.pairs_chunk_step``).

The chunk program writes ranks 0..hit_cap-1 of the chunk's hit mask, in
flat (pair, row, lane) order, as one block at ``offset``, resolving each
rank per tile pair, per row, per lane.  At every rank window it must
write exactly what the flat ``cumsum`` + ``searchsorted`` reference below
writes, for any mask a backend can emit.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oracles import brute_pairs, make_dataset, pair_set
from repro import obs
from repro.core import EngineConfig, SelfJoinConfig, SelfJoinEngine
from repro.core import engine as engine_mod

C, T = 128, 16
NUM_TILES = 40
CAP = 5_000

# rank windows below, at and above C*T: a hit-cap retry can widen the
# window to anything up to C*T*T
HIT_CAPS = [1024, C * T, 3072]


def _flat_select_reference(buf, offset, max_chunk_hits, mask, tile_start,
                           point_order, pa, pb, real, hit_cap):
    """The flat select: a prefix sum over all C*T*T flags, then
    ``searchsorted`` of ranks 1..hit_cap."""
    c, t, _ = mask.shape
    cap = buf.shape[0] - hit_cap
    valid = np.arange(c) < real
    hits = ((mask != 0) & valid[:, None, None]).reshape(c, t * t)
    row_cum = np.cumsum(hits, axis=1)
    row_tot = row_cum[:, -1]
    cum = (row_cum + (np.cumsum(row_tot) - row_tot)[:, None]).reshape(-1)
    nh = int(row_tot.sum())
    idx = np.minimum(
        np.searchsorted(cum, np.arange(1, hit_cap + 1)), c * t * t - 1
    )
    p, i, j = idx // (t * t), idx // t % t, idx % t
    block = np.stack(
        [point_order[tile_start[pa[p]] + i], point_order[tile_start[pb[p]] + j]],
        axis=1,
    )
    out = buf.copy()
    woff = min(offset, cap)
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


@pytest.mark.parametrize("hit_cap", HIT_CAPS, ids=lambda h: f"hit_cap{h}")
@pytest.mark.parametrize("case", list(CASES))
def test_select_matches_flat_reference(case, hit_cap, monkeypatch):
    layout, real, offset = CASES[case]
    rng = np.random.default_rng([list(CASES).index(case), hit_cap])
    mask = _mask(rng, layout, real)
    if case == "hits_past_real":
        assert mask[real:].any()
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
    ))
    got_buf, got_off, got_max = step(
        jnp.asarray(buf), jnp.int32(offset), jnp.int32(max_chunk_hits),
        jnp.asarray(mask), jnp.zeros(NUM_TILES, jnp.int32),
        jnp.asarray(tile_start), jnp.asarray(point_order),
        jnp.asarray(pa), jnp.asarray(pb), jnp.int32(real), jnp.float32(0),
    )
    want_buf, want_off, want_max = _flat_select_reference(
        buf, offset, max_chunk_hits, mask, tile_start, point_order,
        pa, pb, real, hit_cap,
    )
    nh = want_off - offset
    written = min(offset, CAP) + min(nh, hit_cap)
    assert int(got_off) == want_off
    assert int(got_max) == want_max
    np.testing.assert_array_equal(np.asarray(got_buf)[:written], want_buf[:written])
    if case == "nh_over_hit_cap":
        assert nh > hit_cap
    if case in ("d2e-3", "one_pair"):
        assert 0 < nh <= hit_cap


@pytest.mark.parametrize(
    "n, eps, chunk, compiles",
    [
        (300, 0.1, 520, 1),
        (700, 0.3, 528, 2),  # a chunk holds > 4,096 hits: one retry
    ],
    ids=["default_window", "widened_by_retry"],
)
def test_pairs_across_hit_cap_retry(n, eps, chunk, compiles):
    # T = 8 and C >= 512: the retry widens the 4,096-rank window past C*T;
    # clearing the caches makes every program of this case compile here
    jax.clear_caches()
    d = make_dataset("clustered", n, 4, seed=3)
    eng = SelfJoinEngine(
        d, SelfJoinConfig(eps=eps, k=4, tile_size=8),
        EngineConfig(pairs_chunk=chunk),
    )
    with obs.capture() as cap:
        res = eng.pairs()
    traced = [
        e for e in cap.spans("engine.trace")
        if e.attrs.get("program") == "pairs_chunk"
    ]
    assert len(traced) == compiles
    assert res.stats.overflow_retries == compiles - 1
    assert pair_set(res.pairs) == pair_set(brute_pairs(d, eps))
