"""Bring-up smoke of the similarity self-join on a TPU.

    python3 chip_smoke.py               # one chip: engine, Pallas tiers, service
    python3 chip_smoke.py --four-chips  # four chips: the fused ring vs one chip

One process drives the chip through the system's own entry points on the
paper's Syn16D2M generator (exponential lambda=40, n = 16, Table 1), with
eps = 0.03 (Fig. 9) and k = 6 (Sec. 5), at 500,000 points: 0.25 x the
published 2,000,000.  At the published size the index holds 2.8e8
candidate tile pairs and one XLA count pass took 283 s warm on a v5e, so
the whole smoke would take about 2,500 s; at 500,000 points it takes
about 300 s, well inside the 1200 s a run may take even with nothing in
the compile cache.  Every phase prints one ``PHASE {...}`` line (cold and
warm wall times, |R|, SHORTC blocks skipped, the tier, peak device bytes);
results are checked against each other and against a float64 host brute
force on sampled points.  No exception is caught: any failure exits
non-zero.  The last line is ``{"ok": true, "device": {...}}``.

The script refuses to run anywhere but on a TPU -- there is no CPU path.
``run_phases`` / ``run_four_chip_phases`` take their sizes as arguments so
a test can rehearse them on the CPU at a tiny size.  These are bring-up
timings of single runs, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

DATASET = "Syn16D2M"
EPS = 0.03
K_INDEXED = 6
KNN_K = 8
BAND = 1e-5          # brute-force counts bracket eps*(1 -/+ BAND)
SCALE = 0.25         # x Syn16D2M's published |D| (see the module docstring)


def _require(ok, message: str = "check failed") -> None:
    """A result check that also holds under ``python -O``."""
    if not ok:
        raise RuntimeError(message)


def _log(phase: str, **fields) -> None:
    print("PHASE " + json.dumps({"phase": phase, **fields}), flush=True)


def _peak_bytes():
    import jax

    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _timed(fn):
    """(result, seconds); the clock stops after ``block_until_ready``."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _run_twice(phase: str, fn, **fields):
    """Cold (trace + compile + run) then warm call of ``fn``; log both."""
    res, cold = _timed(fn)
    res, warm = _timed(fn)
    st = res.stats
    _log(
        phase, cold_s=cold, warm_s=warm, R=st.num_results,
        dim_blocks_skipped=st.dim_blocks_skipped,
        dim_blocks_total=st.dim_blocks_total, tier=st.execution,
        tile_pairs=st.num_tile_pairs_evaluated, chunks=st.num_chunks,
        peak_bytes_in_use=_peak_bytes(), **fields,
    )
    return res


def brute_reference(d, rows, eps, knn_rows, k, chunk=8192, workers=8):
    """Float64 host brute force, chunked over the data, independent of the
    code under test (numpy only; chunks run on ``workers`` threads).

    Returns ``(lo, hi, knn_idx, knn_dist)``: per ``rows`` point the count of
    data points within ``eps*(1-BAND)`` and within ``eps*(1+BAND)`` (float64
    matmul-form squared distances), and per ``knn_rows`` point its ``k``
    nearest ids and direct-form distances, ties broken by id.  The kNN rows
    are searched among the points within ``eps*(1+BAND)``; that set holds
    the exact k nearest whenever it holds at least k points, which is
    required.
    """
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    d64 = d.astype(np.float64)
    q = d64[np.concatenate([rows, knn_rows])]
    qn = (q * q).sum(1)
    lo2, hi2 = (eps * (1 - BAND)) ** 2, (eps * (1 + BAND)) ** 2
    nr = rows.size

    def part(s):
        x = d64[s : s + chunk]
        d2 = qn[:, None] + (x * x).sum(1)[None, :] - 2.0 * (q @ x.T)
        within = d2 <= hi2
        qi, xi = np.nonzero(within[nr:])
        return (d2[:nr] <= lo2).sum(1), within[:nr].sum(1), qi, xi + s

    with ThreadPoolExecutor(workers) as pool:
        parts = list(pool.map(part, range(0, d64.shape[0], chunk)))
    lo = sum(p[0] for p in parts)
    hi = sum(p[1] for p in parts)
    qi = np.concatenate([p[2] for p in parts])
    xi = np.concatenate([p[3] for p in parts])
    per_row = np.bincount(qi, minlength=knn_rows.size)
    _require(
        per_row.min(initial=k) >= k, "a kNN row has fewer than k points in eps"
    )
    diff = d64[knn_rows[qi]] - d64[xi]
    dist = np.sqrt(np.einsum("ij,ij->i", diff, diff))
    order = np.lexsort((xi, dist, qi))
    qi, xi, dist = qi[order], xi[order], dist[order]
    starts = np.concatenate([[0], np.cumsum(per_row)[:-1]])
    take = (starts[:, None] + np.arange(k)[None, :]).reshape(-1)
    return (
        lo, hi,
        xi[take].reshape(-1, k).astype(np.int64),
        dist[take].reshape(-1, k),
    )


def _check_bounds(name, got, lo, hi):
    import numpy as np

    bad = np.flatnonzero((got < lo) | (got > hi))
    _require(bad.size == 0, (
        f"{name}: {bad.size} sampled counts outside the float64 eps band, "
        f"e.g. row {bad[:1]}: got {got[bad[:1]]}, want [{lo[bad[:1]]}, {hi[bad[:1]]}]"
    ))


def run_phases(
    scale: float = SCALE,
    eps: float = EPS,
    *,
    n_check: int = 2048,
    n_knn: int = 1024,
    batch: int = 1024,
    dense_points: int = 65_536,
    count_chunk: int = 4096,
    pairs_chunk: int = 1024,
    seed: int = 0,
) -> dict:
    """The one-chip phases at ``scale`` x Syn16D2M; returns a summary.

    The dense tier's work grows as |D|^2 (2.5e11 point pairs at 500,000
    points), so its phase runs on the first ``dense_points`` points and is
    compared with the indexed tier on the same subset.
    """
    import jax
    import numpy as np

    from repro.core import EngineConfig, SelfJoinConfig, SelfJoinEngine
    from repro.core.engine import _count_chunk_program
    from repro.data import paper_dataset
    from repro.join import QueryService, SimilarityIndex

    on_tpu = jax.devices()[0].platform == "tpu"
    d, gen_s = _timed(lambda: paper_dataset(DATASET, scale, seed=seed))
    n = d.shape[0]
    _log("data", dataset=DATASET, n=n, dims=d.shape[1], eps=eps, seconds=gen_s)

    cfg = SelfJoinConfig(eps=eps, k=K_INDEXED)
    eng_cfg = EngineConfig(count_chunk=count_chunk, pairs_chunk=pairs_chunk)

    # -- engine, default (XLA) backend -----------------------------------
    eng, build_s = _timed(lambda: SelfJoinEngine(d, cfg, eng_cfg))
    snap = eng.snapshot
    _log(
        "index_build", seconds=build_s, cells=snap.grid.num_cells,
        tiles=snap.plan.num_tiles, tile_pairs=snap.plan.num_pairs,
        candidates=snap.plan.num_candidates,
    )
    counts = _run_twice("count_jnp", eng.count).counts
    pairs_res = _run_twice("pairs_jnp", eng.pairs)
    _require(pairs_res.stats.num_results == counts.sum(), "pairs |R| != counts")
    _require(np.array_equal(
        np.bincount(pairs_res.pairs[:, 0], minlength=n), counts
    ), "pairs histogram != counts")
    del pairs_res

    # -- Pallas kernels, indexed tier (same index, re-placed) -------------
    pl_cfg = SelfJoinConfig(eps=eps, k=K_INDEXED, use_pallas=True)
    pl_eng = SelfJoinEngine.from_prebuilt(
        snap.pts, snap.perm, snap.grid, snap.plan, snap.index_eps,
        pl_cfg, eng_cfg,
    )
    del eng, snap  # free the XLA engine's device tables and chunk lists
    pl_counts = _run_twice("count_pallas_indexed", pl_eng.count).counts
    _require(np.array_equal(pl_counts, counts), "Pallas indexed != XLA counts")
    table, _ = pl_eng.snapshot.chunks(count_chunk)
    hlo = _count_chunk_program.lower(
        jax.numpy.zeros(n, jax.numpy.int32), jax.numpy.zeros((), jax.numpy.int32),
        pl_eng.snapshot.tiles, pl_eng.snapshot.tile_len,
        pl_eng.snapshot.tile_start, table.pa, table.pb, table.real, 0,
        np.float32(eps), shortc=True, backend="pallas",
    ).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    _log("pallas_hlo", tpu_custom_call=has_kernel)
    if on_tpu:
        _require(has_kernel, "the Pallas count program holds no TPU kernel")
    del pl_eng, table

    # -- dense tier on a subset: Pallas dense kernel vs indexed XLA -------
    sub = d[: min(dense_points, n)]
    sub_counts = SelfJoinEngine(sub, cfg, eng_cfg).count().counts
    dense_eng = SelfJoinEngine(
        sub,
        SelfJoinConfig(eps=eps, k=K_INDEXED, use_pallas=True, execution="dense"),
        eng_cfg,
    )
    dense_counts = _run_twice(
        "count_pallas_dense", dense_eng.count, n=sub.shape[0]
    ).counts
    _require(np.array_equal(dense_counts, sub_counts), "dense tier != indexed")
    del dense_eng

    # -- float64 brute force on sampled points ----------------------------
    rng = np.random.default_rng(seed + 1)
    rows = rng.choice(n, size=min(n_check, n), replace=False)
    # kNN queries among points holding >= 2k neighbours, so that the served
    # kNN's eps ladder stops at the build radius
    rich = np.flatnonzero(counts >= 2 * KNN_K)
    knn_rows = rng.choice(rich, size=min(n_knn, rich.size), replace=False)
    (lo, hi, want_idx, want_dist), brute_s = _timed(
        lambda: brute_reference(d, rows, eps, knn_rows, KNN_K)
    )
    _check_bounds("engine counts", counts[rows], lo, hi)
    _log("brute_check", seconds=brute_s, rows=rows.size, knn_rows=knn_rows.size)

    # -- served path ------------------------------------------------------
    index, index_s = _timed(lambda: SimilarityIndex(d, cfg, eng_cfg))
    service = QueryService(index)
    got = []
    t0 = time.perf_counter()
    for s in range(0, rows.size, batch):
        got.append(service.range_count(d[rows[s : s + batch]], eps).counts)
    range_s = time.perf_counter() - t0
    _check_bounds("service range_count", np.concatenate(got), lo, hi)
    t0 = time.perf_counter()
    knn = [
        service.knn(d[knn_rows[s : s + batch]], KNN_K)
        for s in range(0, knn_rows.size, batch)
    ]
    knn_s = time.perf_counter() - t0
    got_idx = np.concatenate([r.indices for r in knn])
    got_dist = np.concatenate([r.distances for r in knn])
    _require(np.array_equal(got_idx, want_idx), "service kNN ids != brute force")
    _require(
        np.allclose(got_dist, want_dist, rtol=1e-12, atol=0.0),
        "service kNN distances != brute force",
    )
    _log(
        "service", index_build_s=index_s, range_count_s=range_s, knn_s=knn_s,
        batches=len(got) + len(knn), traces=service.total.num_traces,
        knn_eps_rounds=service.total.eps_rounds,
        peak_bytes_in_use=_peak_bytes(),
    )
    return {
        "n": n, "R": int(counts.sum()), "tpu_custom_call": has_kernel,
        "checked_rows": int(rows.size), "knn_rows": int(knn_rows.size),
    }


def run_four_chip_phases(
    scale: float = SCALE,
    eps: float = EPS,
    *,
    count_chunk: int = 4096,
    pairs_chunk: int = 1024,
    seed: int = 0,
) -> dict:
    """The fused ring over a 4-device mesh vs the one-chip engine."""
    import jax
    import numpy as np

    from repro.core import (
        DistributedSelfJoinEngine, EngineConfig, SelfJoinConfig, SelfJoinEngine,
    )
    from repro.data import paper_dataset

    devices = jax.devices()
    _require(len(devices) >= 4, f"four devices needed, found {len(devices)}")
    d = paper_dataset(DATASET, scale, seed=seed)
    n = d.shape[0]
    cfg = SelfJoinConfig(eps=eps, k=K_INDEXED)
    eng_cfg = EngineConfig(count_chunk=count_chunk, pairs_chunk=pairs_chunk)
    _log("data", dataset=DATASET, n=n, dims=d.shape[1], eps=eps)

    with jax.default_device(devices[0]):
        one = SelfJoinEngine(d, cfg, eng_cfg)
        res, secs = _timed(one.count)
        ref = res.counts
    _log("count_one_chip", cold_s=secs, R=res.stats.num_results)
    del one

    mesh = jax.make_mesh((4,), ("data",))
    ring, build_s = _timed(
        lambda: DistributedSelfJoinEngine(
            d, cfg, mesh=mesh, engine_config=eng_cfg, fused=True
        )
    )
    _log("ring_build", seconds=build_s)
    counts = _run_twice("count_fused_ring", ring.count).counts
    _require(np.array_equal(counts, ref), (
        f"fused ring counts != one chip at {np.count_nonzero(counts != ref)} "
        f"points (|R| {counts.sum()} vs {ref.sum()})"
    ))

    # the packed tables are spread over all four devices
    placed = ring._fused_pack["args"] + ring._fused_pack["pairs_args"]
    for arr in placed:
        shard_rows = {s.data.shape[0] for s in arr.addressable_shards}
        _require(
            len(arr.sharding.device_set) == 4
            and shard_rows == {arr.shape[0] // 4},
            f"table {arr.shape} not spread over 4 devices: {arr.sharding}",
        )
    in_use = [(dv.memory_stats() or {}).get("bytes_in_use") for dv in devices[:4]]
    _log(
        "ring_placement", tables=len(placed),
        table_bytes=sum(int(a.nbytes) for a in placed), bytes_in_use=in_use,
    )
    if devices[0].platform == "tpu":
        _require(all(b and b > 0 for b in in_use), f"idle device: {in_use}")

    pr, secs = _timed(ring.self_join_pairs)
    _log(
        "pairs_fused_ring", cold_s=secs, R=pr.stats.num_results,
        retries=pr.stats.overflow_retries,
        dispatches=pr.stats.num_device_dispatches,
    )
    _require(pr.pairs.shape[0] == ref.sum(), "fused pair total != one chip")
    _require(np.array_equal(pr.counts, ref), "fused pairs histogram != one chip")
    return {"n": n, "R": int(ref.sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the fused four-chip ring and its one-chip reference",
    )
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU found (JAX sees {dev.platform}); "
            "this smoke runs on the chip only",
            file=sys.stderr,
        )
        return 1
    _log("cache", dir=enable_compile_cache())
    if args.four_chips:
        summary = run_four_chip_phases()
    else:
        summary = run_phases()
    _log("summary", **summary)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
