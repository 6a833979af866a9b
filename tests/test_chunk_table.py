"""Chunk tables (``snapshot.ChunkTable``, DESIGN.md #1.5): a candidate pair
list reaches the device once per chunk size, as three tables with one row
per chunk, and the chunk programs read their row on the device.

Every case runs a plan whose chunk count is not a power of two and whose
last chunk is partial, so the table has padding rows past it.  Checked:
the table's shape is ``bucket_rows(num)``, padding rows hold ``real == 0``,
``SelfJoinStats.chunk_table_uploads`` reads the uploads on a miss and 0 on
a hit, the answers equal the brute-force oracle, the remainder pass reads
the last, partial chunk by its row, and a batch's tables of one fixed row
count (the service's) hold its pair list in order.
"""
import jax
import numpy as np
import pytest

from oracles import brute_counts, brute_pairs, make_dataset, pair_set
from repro import obs
from repro.core import EngineConfig, GridSnapshot, SelfJoinConfig, SelfJoinEngine
from repro.core import engine as engine_mod
from repro.core.grid import bucket_rows
from repro.core.snapshot import CHUNK_TABLE_UPLOADS, _chunk_table

EPS = 0.25


def _data():
    return make_dataset("clustered", 301, 8, seed=7)


def _cfg(tier):
    return SelfJoinConfig(eps=EPS, k=4, tile_size=16, execution=tier)


def _chunk_for(p):
    """A chunk size that cuts ``p`` pairs into a non-power-of-two number
    of chunks, the last one partial."""
    for want in (7, 6, 5, 3):
        c = -(-p // want)
        if -(-p // c) == want and p % c:
            return c
    raise AssertionError(f"no such chunk size for {p} pairs")


def _engine(tier):
    """An engine over the data whose chunk size suits the tier's plan."""
    snap = GridSnapshot.build(_data(), _cfg(tier))
    tables = snap if tier == "indexed" else snap.dense_tables()
    chunk = _chunk_for(tables.plan.num_pairs)
    eng = SelfJoinEngine.from_snapshot(
        snap, EngineConfig(count_chunk=chunk, pairs_chunk=chunk)
    )
    return eng, tables, chunk


@pytest.mark.parametrize("tier", ["indexed", "dense"])
@pytest.mark.parametrize("mode", ["count", "pairs"])
def test_one_upload_per_chunk_size(mode, tier):
    eng, tables, chunk = _engine(tier)
    plan = tables.plan
    p = plan.num_pairs
    num = -(-p // chunk)
    assert num & (num - 1) and p % chunk  # not a power of two, partial last

    def run():
        if mode == "count":
            return eng.count()
        return eng.pairs(max_pairs=1 << 17)

    first, second = run(), run()
    assert first.stats.execution == tier
    assert first.stats.chunk_table_uploads == CHUNK_TABLE_UPLOADS
    assert second.stats.chunk_table_uploads == 0
    assert first.stats.num_chunks == second.stats.num_chunks == num

    table, uploads = tables.chunks(chunk)
    assert uploads == 0 and table.num == num
    rows = bucket_rows(num)
    assert table.rows == rows > num
    assert table.pa.shape == table.pb.shape == (rows, chunk)
    real = np.asarray(table.real)
    np.testing.assert_array_equal(real[: num - 1], chunk)
    assert real[num - 1] == p - (num - 1) * chunk
    np.testing.assert_array_equal(real[num:], 0)
    np.testing.assert_array_equal(np.asarray(table.pa).ravel()[:p], plan.pair_a)
    np.testing.assert_array_equal(np.asarray(table.pb).ravel()[:p], plan.pair_b)

    d = _data()
    for res in (first, second):
        np.testing.assert_array_equal(res.counts, brute_counts(d, EPS))
        if mode == "pairs":
            assert pair_set(res.pairs) == pair_set(brute_pairs(d, EPS))


@pytest.mark.parametrize("tier", ["indexed", "dense"])
def test_remainder_pass_reads_the_last_partial_chunk(tier, monkeypatch):
    # a 64-rank first window: every chunk outgrows it, the last included
    monkeypatch.setattr(engine_mod, "_MIN_HIT_WINDOW", 64)
    eng, tables, chunk = _engine(tier)
    num = -(-tables.plan.num_pairs // chunk)
    with obs.capture() as cap:
        res = eng.pairs(max_pairs=1 << 17)
    assert cap.spans("engine.pairs")[-1].attrs["window"] == 64
    again = {e.attrs["chunk"] for e in cap.spans("engine.pairs.remainder.chunk")}
    assert num - 1 in again
    assert res.stats.overflow_retries == 1
    assert res.stats.chunk_table_uploads == CHUNK_TABLE_UPLOADS
    d = _data()
    np.testing.assert_array_equal(res.counts, brute_counts(d, EPS))
    assert pair_set(res.pairs) == pair_set(brute_pairs(d, EPS))


@pytest.fixture
def compiles():
    """Backend compiles while the test runs, by event name."""
    seen = []

    def listen(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    yield seen
    jax.monitoring.unregister_event_duration_listener(listen)


def test_building_a_table_compiles_nothing(compiles):
    """The tables reach the device as int32 from the host: a join's set-up
    compiles no program for them, whatever the pair lists' dtype."""
    pairs = np.arange(1000, dtype=np.int64)
    table, uploads = _chunk_table(pairs, pairs[::-1], 96, {})
    assert compiles == []
    assert uploads == CHUNK_TABLE_UPLOADS
    assert table.pa.dtype == table.pb.dtype == table.real.dtype == np.int32
    real = np.asarray(table.real)
    np.testing.assert_array_equal(real[table.num - 2 : table.num + 1], [96, 40, 0])


@pytest.mark.parametrize("tier", ["indexed", "dense"])
def test_fixed_row_tables_hold_the_pair_list(tier):
    """``QueryPlanTables.chunk_tables``: every table has the asked-for rows,
    whatever the batch's chunk count, and the tables' real rows are the
    pair list in order."""
    d = _data()
    eng = SelfJoinEngine(d[:200], _cfg(tier))
    tab = eng.prepare_query(d[200:], EPS, pad_queries_to=128)
    assert tab.execution == tier
    chunk = _chunk_for(tab.num_pairs)
    num = -(-tab.num_pairs // chunk)
    tables = tab.chunk_tables(chunk, 4)
    assert len(tables) == -(-num // 4) >= 2
    assert [t.num for t in tables[:-1]] == [4] * (len(tables) - 1)
    assert {t.rows for t in tables} == {4}
    pa = np.concatenate([np.asarray(t.pa)[: t.num].ravel() for t in tables])
    pb = np.concatenate([np.asarray(t.pb)[: t.num].ravel() for t in tables])
    real = np.concatenate([np.asarray(t.real) for t in tables])
    assert real.sum() == tab.num_pairs
    np.testing.assert_array_equal(real[num:], 0)
    p = tab.num_pairs
    np.testing.assert_array_equal(pa[:p], tab.pair_a)
    np.testing.assert_array_equal(pb[:p], tab.pair_b)
