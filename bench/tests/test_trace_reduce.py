"""The reduction from a profiler trace to busy time, program time and
named idle gaps (``bench/trace_reduce.py``)."""
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_merge_is_the_union_of_intervals():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9], [10, 11]], float)
    assert tr._merge(iv).tolist() == [[0, 3], [5, 9], [10, 11]]
    assert tr._clip(np.array([[0, 4], [5, 6]], float), 1, 5.5).tolist() == [
        [1, 4], [5, 5.5],
    ]


def test_gaps_take_the_innermost_open_span():
    spans = [("bench.window", 0, 100), ("bench.join", 0, 50),
             ("engine.snapshot_build", 5, 20), ("engine.pairs", 25, 45),
             ("engine.pairs.chunk", 26, 27), ("bench.join", 50, 100)]
    names = tr._name_gaps(spans, [1, 10, 24, 30, 47, 60])
    assert names == ["bench.join", "engine.snapshot_build", "bench.join",
                     "engine.pairs", "bench.join", "bench.join"]
    assert tr._name_gaps(spans, [150]) == [tr.NO_SPAN]


@pytest.fixture(scope="module")
def small():
    (pb,) = sorted(DATA.glob("*.xplane.pb"))
    return tr.reduce_trace(str(pb))


def test_recorded_chip_trace(small):
    # two whole pairs joins of 3,000 x 16 points on one TPU v5e
    assert set(small.devices) == {0}
    dev = small.devices[0]
    assert 0 < dev.busy_s < small.window_s
    progs = [k for k in dev.module_s if "pairs_chunk_step" in k]
    assert progs and sum(dev.module_s[k] for k in progs) <= dev.busy_s
    idle = sum(dev.idle_by_span.values())
    assert idle == pytest.approx(small.window_s - dev.busy_s, rel=1e-6)
    assert "engine.snapshot_build" in dev.idle_by_span
    assert sum(dev.op_s.values()) >= dev.busy_s * 0.999
    assert small.span_seconds("engine.snapshot_build") > 0
    assert [n for n, _, _ in small.spans].count("bench.join") == 2
