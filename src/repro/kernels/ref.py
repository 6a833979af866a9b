"""Pure-jnp oracles for the tile distance kernels.

The oracle evaluates candidate tile pairs with the *direct* (a-b)^2
formulation in float32 -- intentionally a different numeric path from the
kernel's matmul form so tests exercise both (see DESIGN.md #6; exactness
tests quantize coordinates so both forms are exact).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST  # f32 contractions stay f32 on a TPU


def matmul_sqdist(a: jax.Array, b: jax.Array) -> jax.Array:
    """Clamped matmul-form squared distances (the dense tier's identity).

    ``a``: (..., Ta, n), ``b``: (..., Tb, n) -> (..., Ta, Tb) float32 with
    ``d2 = max(|a|^2 + |b|^2 - 2 a.b^T, 0)``.  The clamp is load-bearing on
    arbitrary fp32 data: rounding of the three-term form can dip a true-zero
    distance slightly negative, which would silently survive an ``<= eps^2``
    test but corrupt any downstream sqrt.  On 1/64-quantized coordinates the
    form is exact and the clamp is a no-op (DESIGN.md #6).
    """
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    na = jnp.sum(a * a, axis=-1)[..., :, None]
    nb = jnp.sum(b * b, axis=-1)[..., None, :]
    prod = jnp.einsum("...in,...jn->...ij", a, b, precision=_HIGHEST)
    return jnp.maximum(na + nb - 2.0 * prod, 0.0)


def direct_sqdist(a: jax.Array, b: jax.Array) -> jax.Array:
    """Direct-form squared distances ``|a - b|^2``, (..., Ta, Tb) float32.

    The numerically independent oracle for ``matmul_sqdist`` (different
    rounding path; never negative by construction).
    """
    diff = a.astype(jnp.float32)[..., :, None, :] - b.astype(jnp.float32)[..., None, :, :]
    return jnp.einsum("...ijn,...ijn->...ij", diff, diff, precision=_HIGHEST)


def ref_tile_counts(
    tiles_pts: jax.Array,   # (num_tiles, NB, T, DB) float32 (ops.make_tiles)
    tile_len: jax.Array,    # (num_tiles,) int32
    pair_a: jax.Array,      # (P,) int32
    pair_b: jax.Array,      # (P,) int32
    eps: float,
) -> jax.Array:
    """Per-(pair, a-point) neighbour counts, (P, T) int32."""
    mask = ref_tile_mask(tiles_pts, tile_len, pair_a, pair_b, eps)
    return mask.sum(axis=2, dtype=jnp.int32)


def ref_attention(q, k, v, *, causal=True, scale=None):
    """Dense softmax attention oracle. q: (BH, Sq, dh), k/v: (BH, Sk, dh/dv)."""
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[1], s.shape[2]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, -1.0e30)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32)).astype(q.dtype)


def ref_tile_mask(
    tiles_pts: jax.Array,
    tile_len: jax.Array,
    pair_a: jax.Array,
    pair_b: jax.Array,
    eps: float,
) -> jax.Array:
    """Boolean (P, T, T): pair (i, j) within eps and both lanes valid.

    ``tiles_pts`` is the blocked ``(num_tiles, NB, T, DB)`` table; the dim
    blocks are summed like any other coordinate axis.
    """
    t = tiles_pts.shape[2]
    a = tiles_pts[pair_a]            # (P, NB, T, DB)
    b = tiles_pts[pair_b]
    diff = a[:, :, :, None, :] - b[:, :, None, :, :]
    d2 = jnp.einsum("pkijd,pkijd->pij", diff, diff, precision=_HIGHEST)
    la = tile_len[pair_a]            # (P,)
    lb = tile_len[pair_b]
    rows = jnp.arange(t, dtype=jnp.int32)
    valid = (rows[None, :, None] < la[:, None, None]) & (
        rows[None, None, :] < lb[:, None, None]
    )
    return (d2 <= jnp.float32(eps) ** 2) & valid
