"""Drive whole benchmark runs on the CPU at a tiny size, in a process of
their own (the tests start it; it never looks for a chip):

    python -m bench.tests.rehearse <cell> <points> [--fault NAME ...]

Each run prints one ``RESULT {...}`` line: the result line the harness
would print, for the cell at ``points`` points.  ``--fault`` plants a
fault in the program under the timed path first (``FAULTS``), one run per
fault, so that a test can see ``correct`` come out false.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


_ORIGINAL = {}


def _restore():
    """Put back whatever an earlier fault replaced."""
    from repro.core import dist_engine, engine

    if not _ORIGINAL:
        _ORIGINAL.update(
            count=engine.count_chunk_step, pairs=engine.pairs_chunk_step,
            ring_scan=dist_engine.ring_scan,
        )
    for mod in (engine, dist_engine):
        mod.count_chunk_step = _ORIGINAL["count"]
        mod.pairs_chunk_step = _ORIGINAL["pairs"]
    dist_engine.ring_scan = _ORIGINAL["ring_scan"]


def _patch_steps(count_wrap, pairs_wrap):
    """Replace the engine's chunk steps (and the ring's copies of them)."""
    from repro.core import dist_engine, engine

    for mod in (engine, dist_engine):
        mod.count_chunk_step = count_wrap(_ORIGINAL["count"])
        mod.pairs_chunk_step = pairs_wrap(_ORIGINAL["pairs"])


def stale_state():
    """Every chunk returns the state it was given."""
    _patch_steps(
        lambda f: lambda counts, skipped, *a, **k: (counts, skipped),
        lambda f: lambda buf, off, mh, *a, **k: (buf, off, mh),
    )


def half_work():
    """Every chunk evaluates only the first half of its tile pairs."""
    def count_wrap(f):
        def step(counts, skipped, tiles, tlen, tstart, pa, pb, real, eps, **k):
            return f(counts, skipped, tiles, tlen, tstart, pa, pb, real // 2, eps, **k)
        return step

    def pairs_wrap(f):
        def step(buf, off, mh, tiles, tlen, tstart, order, pa, pb, real, eps, **k):
            return f(buf, off, mh, tiles, tlen, tstart, order, pa, pb, real // 2, eps, **k)
        return step

    _patch_steps(count_wrap, pairs_wrap)


def altered_answer():
    """Every chunk alters one answer where it produces it: one point's
    count gains one; the first pair written gets another partner id."""
    def count_wrap(f):
        def step(*a, **k):
            counts, skipped = f(*a, **k)
            return counts.at[0].add(1), skipped
        return step

    def pairs_wrap(f):
        def step(buf, off, mh, *a, **k):
            hit_cap = k["hit_cap"]
            row = off.clip(0, buf.shape[0] - hit_cap)
            buf2, off2, mh2 = f(buf, off, mh, *a, **k)
            b = buf2[row, 1]
            n = a[3].shape[0]  # the decode table: one entry per point (or more)
            return buf2.at[row, 1].set((b + 1) % n), off2, mh2
        return step

    _patch_steps(count_wrap, pairs_wrap)


def no_exchange():
    """The ring never passes its payload on: each worker meets only the
    shard it starts with, in every round."""
    from repro.core import dist_engine

    def ring_scan(axes, body, carry, payload, **kw):
        import jax

        n = jax.lax.axis_size(axes if isinstance(axes, str) else tuple(axes))
        for r in range(n):
            carry = body(r, carry, payload)
        return carry

    dist_engine.ring_scan = ring_scan


# cells ready but not yet declared in BENCHMARK.json (PERF.md, Open
# questions): configuration file, traffic, chips, and their metrics (for
# syn64.pairs.e18, those of the declared pairs cell)
UNDECLARED = {
    "syn64.pairs.e18": (
        ROOT / "bench" / "configs" / "Syn64D2M.json", "pairs.e18", 1, "syn16.pairs.e03",
    ),
    "syn16.ring4.pairs.e03": (
        ROOT / "bench" / "configs" / "Syn16D2M.json", "ring4.pairs.e03", 4,
        [{"name": "ring_join_s", "unit": "s", "kind": "end_to_end"},
         {"name": "setup_s", "unit": "s", "kind": "end_to_end"},
         {"name": "ring_pack_s", "unit": "s", "kind": "per_layer"},
         {"name": "ring_idle_share", "unit": "%", "kind": "per_layer"}],
    ),
}

FAULTS = {
    "stale_state": stale_state,
    "half_work": half_work,
    "altered_answer": altered_answer,
    "no_exchange": no_exchange,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("points", type=int)
    ap.add_argument("--seconds", type=float, default=0.5)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 7)
    ap.add_argument("--fault", action="append", default=[])
    args = ap.parse_args(argv)

    import jax

    from bench import run as harness

    if args.cell in UNDECLARED:
        config, traffic, chips, metrics = UNDECLARED[args.cell]
        if isinstance(metrics, str):
            metrics = harness.load_cell(metrics).metrics
        cell = harness.make_cell(args.cell, config, traffic, chips, metrics)
    else:
        cell = harness.load_cell(args.cell)
    cell.config = dict(cell.config, num_points=args.points)
    for fault in args.fault or [None]:
        jax.clear_caches()
        _restore()
        if fault:
            FAULTS[fault]()
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          require_tpu=False)
        print("RESULT " + json.dumps(dict(fault=fault, **out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
