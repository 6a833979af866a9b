"""The pairs cells' rank-window readers: ``rank_rows_per_result`` (the
program's ``SelfJoinStats.rank_rows`` over the results) and ``remainder_s``
(the ``engine.pairs.remainder`` span), on made-up inputs and in traced
rehearsals of both pairs cells as ``BENCHMARK.json`` lists them."""
import json
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import run as harness
from bench import trace_reduce as tr
from bench.tests._proc import ROOT, cpu_env

# read from the chip's trace and memory counters, which a CPU run lacks
DEVICE_ONLY = {"tile_eval_device_s", "tile_eval_roofline", "device_idle_share",
               "peak_hbm_gb"}


def _joins(*stats):
    return [(1.0, SimpleNamespace(stats=SimpleNamespace(**s))) for s in stats]


@pytest.mark.parametrize("stats,want", [
    ([dict(rank_rows=8192, num_results=4096)], 2.0),
    ([dict(rank_rows=4096, num_results=100), dict(rank_rows=12288, num_results=300)], 40.96),
    # a program without the counter, or a window without results
    ([dict(num_results=100)], None),
    ([dict(rank_rows=4096, num_results=0)], None),
])
def test_rank_rows_per_result_sums_over_the_window(stats, want):
    ctx = harness.Context(trace=None, joins=_joins(*stats))
    assert ctx.value("rank_rows_per_result") == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("spans,joins,want", [
    ([("engine.pairs.remainder", 0, 3 * 10 ** 6)], 1, 3e-3),
    ([("engine.pairs.remainder", 0, 10 ** 6),
      ("engine.pairs.remainder", 10 ** 7, 10 ** 7 + 5 * 10 ** 6)], 2, 3e-3),
    # a join with no remainder pass, or a program without the span
    ([("engine.pairs.fetch", 0, 10 ** 6)], 1, None),
])
def test_remainder_s_per_join(spans, joins, want):
    summary = tr.TraceSummary(window_s=1.0, devices={},
                              spans=[("bench.window", 0, 10 ** 9)] + spans)
    ctx = harness.Context(trace=summary, joins=[(1.0, None)] * joins)
    got = ctx.value("remainder_s")
    assert got == (None if want is None else pytest.approx(want))
    assert harness.Context(trace=None, joins=[(1.0, None)]).value("remainder_s") is None


def _rehearse_listed(tmp, cell, points, *args):
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.rehearse_listed", cell, str(points),
         *args],
        cwd=ROOT, env=cpu_env(tmp), capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    (line,) = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("cell,points,args", [
    ("syn16.pairs.e03", 3000, ()),
    # at a tiny size no chunk outgrows the estimate's window: the floor
    # window makes the remainder pass run, as at the cell's full size
    ("syn64.pairs.e18", 1500, ("--floor-window",)),
])
def test_traced_rehearsal_prints_every_listed_metric(tmp_path, cell, points, args):
    out = _rehearse_listed(tmp_path, cell, points, "--trace", "1", *args)
    assert out["correct"] is True
    listed = {m["name"] for m in harness.load_cell(cell).metrics
              if m["kind"] == "per_layer"}
    assert "rank_rows_per_result" in listed
    assert set(out["metrics"]) - {"join_s", "setup_s"} == listed - DEVICE_ONLY
    m = {k: v["value"] for k, v in out["metrics"].items()}
    if "remainder_s" in listed:
        assert m["overflow_retries"] == 1 and m["remainder_s"] > 0
    # every result pair was selected from a rank-window row
    assert m["rank_rows_per_result"] >= 1
