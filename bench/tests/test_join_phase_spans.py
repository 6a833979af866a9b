"""The readers of a join's host phases, and the program's spans and instants
as the profiler's timeline holds them."""
from pathlib import Path

import numpy as np
import pytest

from bench import run as harness
from bench import trace_reduce as tr
from bench.tests._proc import rehearse

DATA = Path(__file__).resolve().parent / "data"

READERS = {
    "reorder_s": "engine.plan.reorder",
    "grid_build_s": "engine.plan.grid",
    "tile_plan_s": "engine.plan.tiles",
    "placement_s": "engine.plan.place",
    "chunk_tables_s": "engine.chunk_tables",
    "size_estimate_s": "engine.pairs.estimate",
    "result_fetch_s": "engine.pairs.fetch",
}
PLAN = ("reorder_s", "grid_build_s", "tile_plan_s", "placement_s")


def _ctx(summary, joins):
    return harness.Context(trace=summary, joins=[(1.0, None)] * joins)


def _synthetic(joins):
    """``joins`` whole joins, each with every phase span once; the phase
    with index ``i`` lasts ``i + 1`` ms in every join."""
    spans, t = [("bench.window", 0, 10 ** 9)], 0
    for _ in range(joins):
        for i, name in enumerate(READERS.values()):
            spans.append((name, t, t + (i + 1) * 10 ** 6))
            t += 10 ** 7
    return tr.TraceSummary(window_s=1.0, devices={}, spans=spans)


@pytest.mark.parametrize("joins", [1, 3])
def test_readers_give_seconds_per_join(joins):
    ctx = _ctx(_synthetic(joins), joins)
    for i, name in enumerate(READERS):
        assert ctx.value(name) == pytest.approx((i + 1) * 1e-3), name


def test_readers_without_a_trace_or_a_join():
    for name in READERS:
        assert _ctx(None, 2).value(name) is None
        assert _ctx(_synthetic(1), 0).value(name) is None


def test_readers_on_a_trace_from_before_the_spans():
    (pb,) = sorted(DATA.glob("*.xplane.pb"))
    ctx = _ctx(tr.reduce_trace(str(pb)), 2)
    assert ctx.value("plan_s") > 0
    for name in READERS:
        assert ctx.value(name) is None, name


@pytest.mark.parametrize("cell,points,listed", [
    ("syn16.pairs.e03", 3000, set(READERS)),
    ("syn64.count.e16", 1500, set(READERS) - {"size_estimate_s", "result_fetch_s"}),
])
def test_traced_rehearsal_reports_every_phase(tmp_path, cell, points, listed):
    (out,) = rehearse(tmp_path, cell, points, "--trace", "1")
    assert out["correct"] is True
    m = out["metrics"]
    assert listed <= set(m) and not (set(READERS) - listed) & set(m)
    assert all(m[k]["value"] > 0 and m[k]["unit"] == "s" for k in listed)
    plan = sum(m[k]["value"] for k in PLAN)
    assert 0.9 * m["plan_s"]["value"] <= plan <= m["plan_s"]["value"]


# -- one traced engine session on the CPU, read back from the profiler -------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    from repro import obs
    from repro.core import SelfJoinConfig, SelfJoinEngine

    x = np.random.default_rng(5).exponential(1 / 40.0, (400, 8)).clip(0, 1)
    cfg = SelfJoinConfig(eps=0.05, k=4, tile_size=16)
    SelfJoinEngine(x, cfg).pairs(_cap_hint=1)  # compile outside the trace
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            with obs.capture(jax_bridge=True) as cap:
                eng = SelfJoinEngine(x, cfg)
                auto = eng.pairs()
                grown = eng.pairs(_cap_hint=1)  # undersized: grow and retry
                eng.count()
    finally:
        jax.profiler.stop_trace()
    (pb,) = out.rglob("*.xplane.pb")
    assert grown.stats.overflow_retries >= 1
    return cap, auto.stats.overflow_retries + grown.stats.overflow_retries, str(pb)


def test_instants_reach_the_profiler_timeline(traced):
    from jax.profiler import ProfileData

    cap, retries, pb = traced
    host = [
        e for plane in ProfileData.from_file(pb).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
    ]
    instants = [e for e in host if e.name == "engine.pairs.retry"]
    assert len(instants) == cap.span_count("engine.pairs.retry") == retries
    assert all(e.duration_ns < 1e6 for e in instants)


def test_span_seconds_match_the_capture(traced):
    cap, _, pb = traced
    summary = tr.reduce_trace(pb)
    names = [n for n, _, _ in summary.spans]
    for name in {e.name for e in cap.events if e.ph == "X"}:
        spans = cap.spans(name)
        assert names.count(name) == len(spans), name
        # the annotation encloses the capture's own clock readings
        recorded = sum(e.dur_us for e in spans) * 1e-6
        got = summary.span_seconds(name)
        assert recorded - len(spans) * 1e-6 <= got <= recorded + len(spans) * 1e-3


def test_gaps_inside_each_phase_are_named_by_it(traced):
    # an idle gap whose middle falls in a phase is charged to that phase,
    # not to the join or the snapshot build that holds it
    cap, _, pb = traced
    spans = tr.reduce_trace(pb).spans
    mids = sorted(
        ((s + e) / 2, n) for n, s, e in spans if n in READERS.values()
    )
    assert {n for _, n in mids} == set(READERS.values())
    assert tr._name_gaps(spans, [t for t, _ in mids]) == [n for _, n in mids]
