"""The control of ``correct`` (``bench/control.py``) at a size a test run
holds: the reference computed with a three-pass bfloat16 cross term, in
the program's place, has to come out not correct under the cells' limits,
where the same brute force in float32 does not.

Each flip of a pair across eps costs the lower precision a band of tens
of units; this 8,000-point Syn16D2M sample at eps = 0.1 holds ~1,250
neighbours a point, enough boundary pairs for flips on every seed.  On
the chip the control runs at the cells' own sizes
(``python3 bench/control.py``; readings in PERF.md).
"""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import control, reference
from bench.generators.exponential import exponential_dataset

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def _limits():
    out = {}
    for f in sorted(TRAFFIC.glob("*.json")):
        for k, v in json.loads(f.read_text())["limits"].items():
            out[k] = max(out.get(k, 0.0), v)
    return out


@pytest.fixture(scope="module", params=[1, 2])
def case(request):
    x = exponential_dataset(8000, 16, 40.0, request.param)
    return x, reference.build(x, 0.1)


def test_three_pass_control_is_not_correct(case):
    x, ref = case
    counts, pairs = control.control_join(x, 0.1, True)
    got = reference.readings(ref, counts, pairs)
    limits = _limits()  # the loosest limit of any cell
    assert any(got[k] > limits[k] for k in limits), got


def test_float32_brute_force_is_correct(case):
    x, ref = case
    d2 = (x * x).sum(1)[:, None] + (x * x).sum(1)[None] - 2 * (x @ x.T)
    counts = (d2 <= np.float32(0.1) ** 2).sum(1)
    assert reference.count_band(ref, counts) <= min(_limits().values())


def test_dot3_is_three_truncated_bfloat16_passes():
    import jax.numpy as jnp

    a = np.random.default_rng(0).random((4, 64), np.float32)
    exact = a.astype(np.float64) @ a.T.astype(np.float64)
    got = np.asarray(control.dot3(jnp.asarray(a), jnp.asarray(a)), np.float64)
    one = np.asarray(jnp.dot(jnp.asarray(a, jnp.bfloat16), jnp.asarray(a, jnp.bfloat16).T,
                             preferred_element_type=jnp.float32))
    err3 = np.abs(got - exact)
    # far better than one pass, far worse than float32, and never above
    # the exact product of positive operands (truncation cuts toward zero)
    assert err3.max() < np.abs(one - exact).max() / 30
    assert err3.max() > 10 * np.abs((a @ a.T) - exact).max()
    assert (got <= exact * (1 + 2 ** -22)).all()
