"""The control of ``correct``: the plain reference put in the program's
place, computed one precision below the configuration's.

The configurations state float32 distances with the contraction at
``jax.lax.Precision.HIGHEST``; the nearest precision below is ``HIGH``,
three bfloat16 passes.  ``control_join`` computes every pair's
``|a|^2 + |b|^2 - 2 a.b`` with the cross term in those three passes (on
the chip its own ``HIGH`` product; ``dot3`` spells the passes out for the
CPU), and answers as the program would: counts, and for a pairs cell the
pair list.  Its answers have to fail the comparison (``bench/reference.py``)
on every seed; ``python3 bench/control.py`` prints its readings beside
those of the reference's own answers:

    python3 bench/control.py --workload syn64.count.e16 --seeds 1,2,3
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from pathlib import Path
from typing import List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def dot3(a, b):
    """``a @ b.T`` in three bfloat16 passes, as a TPU's ``Precision.HIGH``
    reads: each float32 operand split into a bfloat16 head and tail, both
    cut by truncation, and three of the four products accumulated in
    float32.  Spelt out so that the CPU computes it too (on a CPU ``HIGH``
    is full float32).  A round-to-nearest split is several times more
    accurate than the chip's ``HIGH`` product and misses pairs in both
    directions; the chip's misses pairs only, as truncation does
    (PERF.md, "What decides correct")."""
    import jax
    import jax.numpy as jnp

    def cut(v):  # float32 -> the bfloat16 value below it, kept in float32
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    def split(v):
        hi = cut(v)
        return hi.astype(jnp.bfloat16), cut(v - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def f(p, q):
        return jnp.dot(p, q.T, preferred_element_type=jnp.float32)

    return f(ah, bh) + (f(ah, bl) + f(al, bh))


@functools.lru_cache(maxsize=None)
def _block_fn(native: bool):
    import jax
    import jax.numpy as jnp

    from bench.reference import row_hits

    def cross(a, b):
        if native:  # the chip's own three-pass product
            return jnp.dot(a, b.T, precision=jax.lax.Precision.HIGH)
        return dot3(a, b)

    @functools.partial(jax.jit, static_argnames=("k",))
    def block(xb, x, eps, *, k):
        na = (xb * xb).sum(1)
        nb = (x * x).sum(1)
        d2 = na[:, None] + nb[None, :] - 2.0 * cross(xb, x)
        return row_hits(d2 <= jnp.asarray(eps, jnp.float32) ** 2, k)

    return block


def control_join(
    x: np.ndarray, eps: float, want_pairs: bool, *, native: bool = False,
    block: int = 512,
):
    """(counts, pairs or None) of the lower-precision brute force; with
    ``native`` the cross term is the chip's own ``Precision.HIGH`` product
    (on a CPU that is full float32, so the tests use ``dot3``)."""
    import jax
    import jax.numpy as jnp

    fn = _block_fn(native)
    x = np.ascontiguousarray(x, dtype=np.float32)
    xd = jnp.asarray(x)
    n_pts = x.shape[0]
    counts = np.zeros(n_pts, np.int64)
    pairs = []
    k = 1 if not want_pairs else 256
    for s in range(0, n_pts, block):
        xb = jnp.asarray(x[s : s + block])
        while True:
            cb, cols = jax.device_get(fn(xb, xd, np.float32(eps), k=k))
            if not want_pairs or int(cb.max()) <= k:
                break
            k = 1 << int(cb.max() - 1).bit_length()
        counts[s : s + block] = cb
        if want_pairs:
            hit = np.arange(k)[None, :] < cb[:, None]
            pairs.append(np.stack([np.nonzero(hit)[0] + s, cols[hit]], axis=1))
    return counts, (np.concatenate(pairs) if want_pairs else None)


def main(argv: Optional[List[str]] = None) -> int:
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import data, reference
    from bench.run import load_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    eps, want_pairs = cell.traffic["eps"], cell.traffic["mode"] == "pairs"
    for seed in (int(s) for s in args.seeds.split(",")):
        x = data.generate(cell.config, seed)
        ref = reference.build(x, eps)
        limits = cell.traffic["limits"]
        counts, pairs = control_join(x, eps, want_pairs, native=True)
        got = reference.readings(ref, counts, pairs)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": got,
            "correct": all(got[k] <= limits[k] for k in limits),
            "R": int(ref.counts.sum()), "window_pairs": int(ref.win_g.size),
        }), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    sys.exit(main())
