"""Host spans of a whole join's phases (DESIGN.md #11).

A join's host time is split into named leaf spans, each recorded exactly
once where its work runs:

  * ``engine.plan.reorder`` / ``.grid`` / ``.tiles`` / ``.place`` nest in
    ``engine.snapshot_build`` (or ``engine.snapshot_rebuild``);
  * ``engine.chunk_tables`` (the chunk table's one upload) nests in
    ``engine.count`` / ``engine.pairs`` on a chunk-cache miss only;
  * ``engine.pairs.estimate`` (the result-size sample) precedes
    ``engine.pairs`` in auto-sized mode, ``engine.pairs.fetch`` (the copy
    back) follows its last attempt;
  * ``engine.pairs.remainder`` (the chunks that outgrew the first rank
    window, run again for their missing ranks) sits between the last
    attempt and the fetch, once in a join that needs it.

With tracing off none of them records anything.
"""
import numpy as np
import pytest

from oracles import brute_counts, make_dataset
from repro import obs
from repro.core import (
    EngineConfig, GridSnapshot, SelfJoinConfig, SelfJoinEngine, self_join,
)
from repro.core.snapshot import CHUNK_TABLE_UPLOADS

PLAN = ("engine.plan.reorder", "engine.plan.grid", "engine.plan.tiles",
        "engine.plan.place")
JOIN = {"count": "engine.count", "pairs": "engine.pairs"}


def _data():
    return make_dataset("clustered", 301, 8, seed=7)


def _cfg(**kw):
    return SelfJoinConfig(eps=0.25, k=4, tile_size=16, **kw)


def _inside(inner, outer):
    return (
        outer.ts_us <= inner.ts_us
        and inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us
        and inner.depth == outer.depth + 1
    )


def _one(cap, name):
    (ev,) = cap.spans(name)
    return ev


@pytest.mark.parametrize("mode", ["count", "pairs"])
def test_each_phase_once_per_join_under_its_parent(mode):
    d = _data()
    with obs.capture() as cap:
        res = self_join(d, _cfg(), return_pairs=mode == "pairs")
    np.testing.assert_array_equal(res.counts, brute_counts(d, 0.25))
    build = _one(cap, "engine.snapshot_build")
    for name in PLAN:
        assert _inside(_one(cap, name), build), name
    join = _one(cap, JOIN[mode])
    assert _inside(_one(cap, "engine.chunk_tables"), join)
    if mode == "pairs":
        est, fetch = _one(cap, "engine.pairs.estimate"), _one(cap, "engine.pairs.fetch")
        assert est.depth == fetch.depth == join.depth == 0
        assert build.ts_us + build.dur_us <= est.ts_us
        assert est.ts_us + est.dur_us <= join.ts_us
        assert join.ts_us + join.dur_us <= fetch.ts_us
    else:
        assert cap.span_count("engine.pairs.estimate") == 0
        assert cap.span_count("engine.pairs.fetch") == 0


@pytest.mark.parametrize("mode", ["count", "pairs"])
def test_chunk_tables_only_on_a_cache_miss(mode):
    eng = SelfJoinEngine(_data(), _cfg())
    run = eng.count if mode == "count" else eng.pairs
    with obs.capture() as first:
        miss = run()
    with obs.capture() as second:
        hit = run()
    assert first.span_count("engine.chunk_tables") == 1
    assert second.span_count("engine.chunk_tables") == 0
    assert second.span_count(JOIN[mode]) == 1
    assert miss.stats.chunk_table_uploads == CHUNK_TABLE_UPLOADS
    assert hit.stats.chunk_table_uploads == 0
    for cap, res in ((first, miss), (second, hit)):
        assert cap.metric(
            "selfjoin_chunk_table_uploads_total", path="engine"
        ) == res.stats.chunk_table_uploads


def test_no_estimate_with_an_explicit_capacity():
    with obs.capture() as cap:
        res = self_join(_data(), _cfg(), return_pairs=True, max_pairs=1 << 16)
    assert res.stats.overflow_retries == 0
    assert cap.span_count("engine.pairs.estimate") == 0
    assert cap.span_count("engine.pairs.fetch") == 1


def test_fetch_once_across_overflow_retries():
    eng = SelfJoinEngine(_data(), _cfg())
    with obs.capture() as cap:
        res = eng.pairs(_cap_hint=1)  # undersized buffer: grow and retry
    assert res.stats.overflow_retries >= 1
    assert cap.span_count("engine.pairs") == res.stats.overflow_retries + 1
    assert cap.span_count("engine.pairs.retry") == res.stats.overflow_retries
    assert cap.span_count("engine.pairs.fetch") == 1
    # the retry reuses the chunk tables the first attempt uploaded
    assert cap.span_count("engine.chunk_tables") == 1
    last = cap.spans("engine.pairs")[-1]
    assert last.ts_us + last.dur_us <= _one(cap, "engine.pairs.fetch").ts_us


def test_query_sweep_estimates_once():
    eng = SelfJoinEngine(_data(), _cfg())
    with obs.capture() as cap:
        out = eng.query([0.1, 0.2, 0.25], return_pairs=True)
    assert cap.span_count("engine.pairs.estimate") == 1
    assert cap.span_count("engine.pairs.fetch") == len(out) == 3


def test_rebuild_and_persistence_paths():
    d = _data()
    eng = SelfJoinEngine(d, _cfg())
    with obs.capture() as cap:
        eng.count(eps=0.3)  # outgrows the index: transparent rebuild
    rebuild = _one(cap, "engine.snapshot_rebuild")
    # the rebuild reuses the permutation: applying it is the reorder phase
    for name in PLAN:
        assert _inside(_one(cap, name), rebuild), name
    snap = eng.snapshot
    with obs.capture() as cap:
        GridSnapshot.from_arrays(
            snap.pts, snap.perm, snap.grid, snap.plan, snap.index_eps, snap.config
        )
    assert [e.name for e in cap.events] == ["engine.plan.place"]


def test_no_reorder_span_without_reorder():
    with obs.capture() as cap:
        self_join(_data(), _cfg(reorder=False))
    assert cap.span_count("engine.plan.reorder") == 0
    assert all(cap.span_count(n) == 1 for n in PLAN[1:])


@pytest.mark.parametrize("mode", ["count", "pairs"])
def test_disabled_tracer_records_no_phase(mode):
    assert not obs.enabled()
    self_join(_data(), _cfg(), return_pairs=mode == "pairs")
    assert obs.event_count() == 0


def _skewed_engine():
    """Exponential data whose chunks outgrow a 4,096-rank first window."""
    d = make_dataset("exponential", 1200, 4, seed=11)
    return SelfJoinEngine(
        d, SelfJoinConfig(eps=0.05, k=4, tile_size=16),
        EngineConfig(pairs_chunk=64),
    )


@pytest.mark.parametrize("remainder", [True, False])
def test_remainder_span_once_per_join_that_needs_it(remainder):
    eng = _skewed_engine() if remainder else SelfJoinEngine(_data(), _cfg())
    with obs.capture() as cap:
        res = eng.pairs(max_pairs=1 << 20)
        eng.pairs(max_pairs=1 << 20)
    assert cap.span_count("engine.pairs.remainder") == 2 * remainder
    assert cap.span_count("engine.pairs.retry") == 2 * remainder
    assert res.stats.overflow_retries == remainder
    if remainder:
        joins, rems = cap.spans("engine.pairs"), cap.spans("engine.pairs.remainder")
        fetches = cap.spans("engine.pairs.fetch")
        for join, rem, fetch in zip(joins, rems, fetches):
            assert rem.depth == 0 and rem.attrs["chunks"] >= 3
            assert join.ts_us + join.dur_us <= rem.ts_us
            assert rem.ts_us + rem.dur_us <= fetch.ts_us
        # the remainder's dispatches count, each once
        first = cap.span_count("engine.pairs.chunk")
        again = cap.span_count("engine.pairs.remainder.chunk")
        assert first + again == 2 * res.stats.num_device_dispatches
        assert again == 2 * rems[0].attrs["chunks"]


@pytest.mark.parametrize("case", ["one_pass", "remainder", "capacity_and_remainder"])
def test_rank_rows_is_the_sum_of_dispatched_windows(case):
    eng = SelfJoinEngine(_data(), _cfg()) if case == "one_pass" else _skewed_engine()
    sizing = dict(_cap_hint=1) if case == "capacity_and_remainder" else {}
    if case == "remainder":
        sizing = dict(max_pairs=1 << 20)
    with obs.capture() as cap:
        res = eng.pairs(**sizing)
    first = sum(
        j.attrs["window"] for j in cap.spans("engine.pairs")
    ) * res.stats.num_chunks
    rungs = sum(e.attrs["rung"] for e in cap.spans("engine.pairs.remainder.chunk"))
    assert res.stats.rank_rows == first + rungs
    assert (rungs > 0) == (case != "one_pass")
    assert cap.span_count("engine.pairs") == 1 + (case == "capacity_and_remainder")
    assert cap.metric("selfjoin_rank_rows_total", path="engine") == res.stats.rank_rows
