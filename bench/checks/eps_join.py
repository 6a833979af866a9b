"""The check of whole eps self-joins: every answer of every join against
the plain reference of the whole dataset (``bench/reference.py``).

A join's answer has ``counts`` (one per point) and, in pairs mode,
``pairs`` (an ``(R, 2)`` array of point ids).  Each join is read by
``reference.readings``; a join whose answers equal the first join's reads
the same, so it is not read again.  A traffic mix names this check as
``"check": "eps_join"``.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def check(traffic: dict, x, answers: list):
    """The reference, the worst reading of each number over the joins, and
    how many joins failed (raised, or read above a limit)."""
    limits = traffic["limits"]
    ref = reference.build(x, traffic["eps"])
    worst = {k: 0.0 for k in limits}
    failed, first = 0, None
    for res in answers:
        if res is None:
            failed += 1
            continue
        same = first is not None and _same(res.counts, first[0].counts) and (
            res.pairs is None or _same(res.pairs, first[0].pairs)
        )
        got = first[1] if same else reference.readings(ref, res.counts, res.pairs)
        if first is None:
            first = (res, got)
        for k, v in got.items():
            worst[k] = max(worst[k], v)
        if any(got[k] > limits[k] for k in limits):
            failed += 1
    return ref, worst, failed


def _same(a, b) -> bool:
    return a is not None and b is not None and np.array_equal(a, b)
