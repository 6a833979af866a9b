"""Rehearse a cell as ``BENCHMARK.json`` declares it, with the metrics it
lists there, on the CPU at a tiny size (``bench/tests/rehearse.py`` runs
the rest):

    python -m bench.tests.rehearse_listed <cell> <points> [--floor-window] [rehearse options]

``--floor-window`` runs every pairs join's first pass at the floor rank
window (4,096 ranks, as with no size estimate), so that a tiny dataset's
chunks outgrow it as the full-size ``syn64.pairs.e18`` cell's do and the
remainder pass runs.
"""
from __future__ import annotations

import sys

from bench.tests import rehearse


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--floor-window" in argv:
        argv.remove("--floor-window")
        from repro.core import engine

        engine._WINDOW_PER_MEAN = 0
    rehearse.UNDECLARED.pop(argv[0], None)  # declared: run it as listed
    return rehearse.main(argv)


if __name__ == "__main__":
    sys.exit(main())
