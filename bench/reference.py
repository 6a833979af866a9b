"""The plain reference of the eps self-join, and the comparison that
decides ``correct``.

The reference imports nothing of the program under test.  It answers, for
every point, how many points lie within eps of it, from the distance
``d2 = sum_k (a_k - b_k)^2`` (direct differences, no matmul identity), and
it knows each pair near the eps boundary exactly, in float64:

1. On the device, every point meets every point (rows in blocks).  In
   float32 the direct form is accurate to a few units of rounding of
   ``d2``; each block returns the number of pairs that lie surely inside
   eps (below the window) and the indices of the pairs inside a window
   around eps wide enough to hold every pair whose side of the boundary
   could be in question.
2. On the host, the window pairs get their exact float64 gap
   ``g = (d2 - eps^2) / (U * (|a|^2 + |b|^2))``: the distance past eps in
   units of float32 rounding of the matmul identity the program uses
   (``U = 2^-24``).

The program computes ``|a|^2 + |b|^2 - 2 a.b`` in float32, so a pair
within a few such units of eps may fall either way.  The comparison asks,
for each answer, how wide a band of rounding it needs in order to agree
with the reference (``count_band`` for per-point counts, ``pair_band`` for
each returned pair), and ``readings`` takes the widest over all of a
join's answers.  A correct join needs a unit or so; one computed one
precision lower needs a hundred or more; a wrong answer needs the whole
window, which is the largest reading.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import numpy as np

U = 2.0 ** -24
WINDOW = 4096.0   # half-width of the exact window, in units of U * norms


@dataclasses.dataclass
class Reference:
    eps: float
    x64: np.ndarray        # (N, n) float64 points
    norms: np.ndarray      # (N,) float64 squared norms
    sure_in: np.ndarray    # (N,) pairs surely inside eps, below the window
    win_i: np.ndarray      # window pairs, sorted by (i, g)
    win_j: np.ndarray
    win_g: np.ndarray      # exact gap of each window pair, band units
    start: np.ndarray      # (N + 1,) offsets of each point's window pairs

    @property
    def counts(self) -> np.ndarray:
        """Exact float64 neighbour counts (self included)."""
        inside = np.bincount(
            self.win_i[self.win_g <= 0], minlength=self.sure_in.size
        )
        return self.sure_in + inside

    def gaps(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        """Exact gap (band units) of arbitrary pairs, in float64."""
        diff = self.x64[a] - self.x64[b]
        d2 = np.einsum("ij,ij->i", diff, diff)
        return (d2 - self.eps ** 2) / (U * (self.norms[a] + self.norms[b]))


def row_hits(mask, k: int):
    """Per row of a (B, N) mask: its number of hits and the columns of its
    first ``k`` hits (a prefix sum and a binary search per row; no scatter
    over the whole mask)."""
    import jax
    import jax.numpy as jnp

    cum = jnp.cumsum(mask, axis=1, dtype=jnp.int32)
    want = jnp.arange(1, k + 1, dtype=jnp.int32)
    cols = jax.vmap(lambda c: jnp.searchsorted(c, want))(cum)
    return cum[:, -1], cols.astype(jnp.int32)


def _device_block_fn(num_dims: int):
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("k",))
    def block(xb, nrow, xt, ncol, eps2, half_scale, slack, *, k):
        d2 = jnp.zeros((xb.shape[0], xt.shape[1]), jnp.float32)
        for d in range(num_dims):
            diff = xb[:, d][:, None] - xt[d][None, :]
            d2 = d2 + diff * diff
        half = half_scale * (nrow[:, None] + ncol[None, :]) + slack
        inside = d2 < eps2 - half
        # the window is what lies neither surely inside nor surely outside
        nwin, cols = row_hits(~inside & (d2 <= eps2 + half), k)
        sure = inside.sum(axis=1, dtype=jnp.int32)
        return sure, nwin, cols

    return block


def build(x: np.ndarray, eps: float, *, block: int = 512) -> Reference:
    """The reference of ``x`` at ``eps`` (device pass, then float64)."""
    import jax
    import jax.numpy as jnp

    x = np.ascontiguousarray(np.asarray(x, dtype=np.float32))
    n_pts, n_dims = x.shape
    x64 = x.astype(np.float64)
    norms = np.einsum("ij,ij->i", x64, x64)
    eps2 = np.float32(eps) ** 2
    # the window's float32 half-width: WINDOW units of U * norms, plus four
    # times a bound on the direct form's own rounding near the window edge
    slack = np.float32(
        4 * n_dims * U * (eps * eps + WINDOW * U * 2 * norms.max())
    )
    fn = _device_block_fn(n_dims)
    xt = jnp.asarray(x.T)
    ncol = jnp.asarray(norms.astype(np.float32))
    pad = -n_pts % block
    xp = np.concatenate([x, np.full((pad, n_dims), 1e3, np.float32)])
    np_rows = np.concatenate([norms, np.zeros(pad)]).astype(np.float32)
    sure = np.zeros(n_pts + pad, np.int64)
    wi, wj = [], []
    k = 64
    for s in range(0, n_pts + pad, block):
        while True:
            out = fn(
                jnp.asarray(xp[s : s + block]), jnp.asarray(np_rows[s : s + block]),
                xt, ncol, eps2, np.float32(WINDOW * U), slack, k=k,
            )
            sb, nwin, cols = jax.device_get(out)
            if int(nwin.max()) <= k:
                break
            k = 1 << int(nwin.max() - 1).bit_length()
        sure[s : s + block] = sb
        hit = np.arange(k)[None, :] < nwin[:, None]
        wi.append(np.nonzero(hit)[0].astype(np.int64) + s)
        wj.append(cols[hit].astype(np.int64))
    sure = sure[:n_pts]
    wi = np.concatenate(wi)
    wj = np.concatenate(wj)
    ref = Reference(eps, x64, norms, sure, wi, wj, np.zeros(0), np.zeros(0))
    g = ref.gaps(wi, wj)
    # pairs that the float32 pass put in the window but lie beyond it on
    # the inside count as sure; those beyond it outside are dropped
    sure += np.bincount(wi[g < -WINDOW], minlength=n_pts)
    keep = np.abs(g) <= WINDOW
    wi, wj, g = wi[keep], wj[keep], g[keep]
    order = np.lexsort((g, wi))
    ref.win_i, ref.win_j, ref.win_g = wi[order], wj[order], g[order]
    ref.sure_in = sure
    ref.start = np.concatenate([[0], np.cumsum(np.bincount(wi, minlength=n_pts))])
    return ref


def count_band(ref: Reference, counts: np.ndarray) -> float:
    """Widest rounding band (units of U * norms) that any point's count
    needs to agree with the reference: 0 where the count is exact, the
    gap of the furthest pair it would have to gain or lose, and
    ``WINDOW`` where no band up to the window explains it."""
    counts = np.asarray(counts, dtype=np.int64)
    if counts.shape != ref.sure_in.shape:
        return WINDOW
    n_pts = counts.size
    inside = np.bincount(ref.win_i[ref.win_g <= 0], minlength=n_pts)
    exact = ref.sure_in + inside
    off = np.flatnonzero(counts != exact)
    if off.size == 0:
        return 0.0
    diff = counts[off] - exact[off]
    pos0 = ref.start[off] + inside[off]        # first window pair outside eps
    at = np.where(diff > 0, pos0 + diff - 1, pos0 + diff)
    ok = (at >= ref.start[off]) & (at < ref.start[off + 1])
    if not ok.all():
        return WINDOW
    return float(np.abs(ref.win_g[at]).max())


def pair_band(ref: Reference, pairs: np.ndarray, *, chunk: int = 1 << 18) -> float:
    """Widest band past eps (units of U * norms) of any returned pair, in
    float64; ``WINDOW`` for an id out of range or a pair returned twice."""
    pairs = np.asarray(pairs)
    n_pts = ref.sure_in.size
    if pairs.size == 0:
        return 0.0
    if pairs.ndim != 2 or pairs.shape[1] != 2:
        return WINDOW
    a = pairs[:, 0].astype(np.int64)
    b = pairs[:, 1].astype(np.int64)
    if a.min() < 0 or b.min() < 0 or a.max() >= n_pts or b.max() >= n_pts:
        return WINDOW
    if np.unique(a * n_pts + b).size != a.size:
        return WINDOW
    worst = 0.0
    for s in range(0, a.size, chunk):
        g = ref.gaps(a[s : s + chunk], b[s : s + chunk])
        worst = max(worst, float(g.max()))
    return min(worst, WINDOW)


def readings(ref: Reference, counts: np.ndarray, pairs: Optional[np.ndarray]) -> dict:
    """The number compared for one join's answers: ``band``, the widest
    band that any of them needs -- every point's count, and in a pairs
    join the histograms of both pair columns and every returned pair."""
    vectors: Sequence[np.ndarray] = [counts]
    worst = 0.0
    if pairs is not None:
        n_pts = ref.sure_in.size
        p = np.asarray(pairs).reshape(-1, 2).astype(np.int64)
        inr = (p >= 0).all(1) & (p < n_pts).all(1)
        vectors = [
            counts,
            np.bincount(p[inr, 0], minlength=n_pts),
            np.bincount(p[inr, 1], minlength=n_pts),
        ]
        worst = pair_band(ref, pairs)
    return {"band": max([worst] + [count_band(ref, v) for v in vectors])}
