"""Shared benchmark utilities.

CPU-scale note (DESIGN.md #5): paper dataset sizes (|D| up to 5M) are
shrunk by default so each figure reproduces in minutes on 1 CPU core; the
full algorithm, eps values, lambda=40 distributions and k choices are the
paper's.  ``--scale`` restores larger sizes.  Absolute wall times on CPU are
indicative only -- architecture-level performance claims live in the
roofline analysis (EXPERIMENTS.md #Roofline).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from typing import Callable, Dict, List, Sequence, Tuple

ROWS: List[Tuple[str, float, str]] = []

# Machine-readable benchmark outputs (consumed by benchmarks/compare.py).
# Written under BENCH_OUT_DIR -- NOT the repo root -- so a CI smoke run can
# never clobber the committed baselines in benchmarks/baselines/.
BENCH_OUT_DIR = os.environ.get("BENCH_OUT_DIR", ".bench_out")


def emit_bench_json(
    name: str,
    *,
    contracts: Dict[str, object],
    metrics: Dict[str, float],
    info: Dict[str, object] | None = None,
) -> str:
    """Write ``BENCH_<name>.json`` for the regression gate; return its path.

    Three sections with three comparison rules (see ``compare.py``):
    ``contracts`` are deterministic facts (dispatch decisions, trace
    counts, parity verdicts) diffed EXACTLY; ``metrics`` are wall-time
    measurements diffed within a slack factor; ``info`` is context
    (dataset sizes, measured crossover points) recorded but never gated.
    """
    out_dir = BENCH_OUT_DIR
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    payload = {
        "bench": name,
        "contracts": contracts,
        "metrics": {k: round(float(v), 1) for k, v in metrics.items()},
        "info": info or {},
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}", flush=True)
    return path

def write_trace(cap, name: str) -> str:
    """Write a capture's Chrome trace as ``TRACE_<name>.json``; return path.

    Lands next to the BENCH_*.json rows in ``BENCH_OUT_DIR`` so CI can
    upload the trace as an artifact and run ``repro.obs.report`` over it
    (a malformed trace fails the build).
    """
    os.makedirs(BENCH_OUT_DIR, exist_ok=True)
    path = os.path.join(BENCH_OUT_DIR, f"TRACE_{name}.json")
    cap.write_chrome_trace(path)
    print(f"wrote {path}", flush=True)
    return path


def span_summary(cap) -> Dict[str, object]:
    """Compact per-category span summary of a capture, for BENCH info rows.

    ``{cat: {"count": N, "total_us": T}}`` -- enough to see where a bench
    run spent its time without shipping the whole event list.
    """
    from repro.obs.report import build_report

    rep = build_report(cap.chrome_trace()["traceEvents"])
    out: Dict[str, object] = {}
    for cat, names in rep["phases"].items():
        count = sum(a["count"] for a in names.values())
        total = sum(a["total_us"] for a in names.values())
        out[cat] = {"count": count, "total_us": round(total, 1)}
    return out


# Shared fused-vs-host measurement for the distributed engine (used by
# bench_comm's contract row and bench_scaling's per-|p| rows).  Runs in a
# CPU subprocess (``cpu_child_env``): the 8-device flag must precede jax
# init.  Its times are CPU rehearsal times, not device measurements.
_FUSED_VS_HOST_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, time
    sys.path.insert(0, sys.argv[1])
    import numpy as np
    import jax
    from repro.core import DistributedSelfJoinEngine, SelfJoinConfig
    from repro.data import exponential_dataset

    n, dims = int(sys.argv[2]), int(sys.argv[3])
    ps = [int(x) for x in sys.argv[4].split(",")]
    D = exponential_dataset(n, dims, seed=5)
    cfg = SelfJoinConfig(eps=0.06, k=4, tile_size=16)
    for p in ps:
        mesh = jax.make_mesh((p,), ("data",))
        host_eng = DistributedSelfJoinEngine(D, cfg, mesh=mesh)
        host_res = host_eng.count()          # warm the chunk programs
        t0 = time.perf_counter()
        host_res = host_eng.count()
        host_us = (time.perf_counter() - t0) * 1e6
        fused_eng = DistributedSelfJoinEngine(D, cfg, mesh=mesh, fused=True)
        fused_res = fused_eng.count()        # pack + trace + compile + run
        assert np.array_equal(fused_res.counts, host_res.counts), p
        t0 = time.perf_counter()
        fused_res = fused_eng.count()        # warm: one dispatch, one program
        fused_us = (time.perf_counter() - t0) * 1e6
        assert fused_eng.fused_traces == 1, "fused ring retraced"
        assert fused_res.stats.num_device_dispatches == 1

        # pairs mode (DESIGN.md #7b): the same two drivers materializing the
        # full pair list; the fused ring packs it in ONE device dispatch
        host_pr = host_eng.self_join_pairs()   # warm the chunk programs
        t0 = time.perf_counter()
        host_pr = host_eng.self_join_pairs()
        host_pairs_us = (time.perf_counter() - t0) * 1e6
        fused_pr = fused_eng.self_join_pairs() # pack reuse + trace + run
        assert (set(map(tuple, fused_pr.pairs.tolist()))
                == set(map(tuple, host_pr.pairs.tolist()))), p
        t0 = time.perf_counter()
        fused_pr = fused_eng.self_join_pairs() # warm: converged (cap, hit_cap)
        fused_pairs_us = (time.perf_counter() - t0) * 1e6
        assert fused_eng.fused_pairs_traces == 1, "fused pairs retraced"
        assert fused_pr.stats.num_device_dispatches == 1
        assert fused_pr.stats.overflow_retries == 0

        print("ROW", p, fused_us, host_us,
              host_res.stats.num_device_dispatches,
              host_res.stats.num_candidates, flush=True)
        print("PROW", p, fused_pairs_us, host_pairs_us,
              fused_pr.stats.overflow_retries, len(fused_pr.pairs), flush=True)
    """
)


def measure_fused_vs_host(
    n: int, dims: int, workers: Sequence[int], timeout: int = 1800
) -> Tuple[
    List[Tuple[int, float, float, int, int]],
    List[Tuple[int, float, float, int, int]],
]:
    """Warm fused vs host-driven join times on |p|-device meshes.

    Returns ``(count_rows, pairs_rows)``:

    - ``count_rows``: ``[(p, fused_us, host_us, host_dispatches,
      candidates)]`` where ``candidates`` is the point-comparison volume the
      grid index actually evaluated (filter ratio = candidates / n^2,
      deterministic for a fixed dataset);
    - ``pairs_rows``: ``[(p, fused_pairs_us, host_pairs_us,
      overflow_retries, num_pairs)]`` for the pair-materializing mode.

    The subprocess asserts count AND pair-set parity plus the fused
    one-trace / one-dispatch / zero-retry contracts.
    """
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [
            sys.executable, "-c", _FUSED_VS_HOST_SCRIPT, src,
            str(n), str(dims), ",".join(str(p) for p in workers),
        ],
        capture_output=True, text=True, timeout=timeout, env=cpu_child_env(),
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"fused-vs-host subprocess failed:\n{out.stderr[-2000:]}"
        )
    rows, prows = [], []
    for line in out.stdout.splitlines():
        if line.startswith("ROW "):
            _, p, fused_us, host_us, host_disp, cand = line.split()
            rows.append(
                (int(p), float(fused_us), float(host_us), int(host_disp),
                 int(cand))
            )
        elif line.startswith("PROW "):
            _, p, fp_us, hp_us, retries, npairs = line.split()
            prows.append(
                (int(p), float(fp_us), float(hp_us), int(retries),
                 int(npairs))
            )
    return rows, prows


def cpu_child_env() -> Dict[str, str]:
    """Environment of a benchmark child process that simulates 8 devices.

    Such children are CPU rehearsals of the multi-device paths, never a
    device measurement: they pin ``JAX_PLATFORMS=cpu`` so that on a machine
    with a chip they cannot contend for it with the parent, and they drop
    the parent's ``XLA_FLAGS`` to set their own device count.
    """
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def record(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def timeit(fn: Callable, repeats: int = 1) -> float:
    """Best-of wall time in microseconds."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6
