"""Pallas TPU kernel: dense (unfiltered) tile-pair distance evaluation.

The MXU half of the hybrid dense/indexed execution tier (DESIGN.md #9).
Where ``distance_tile.py`` evaluates a *grid-filtered* candidate list with
SHORTC short-circuiting, this kernel evaluates an arbitrary (typically the
full cross-product) tile-pair list as straight-line batched matmul work:

  * each grid program evaluates one (A, B) tile pair of ``tile_size`` points
    as ``d2 = max(|a|^2 + |b|^2 - 2 a.b^T, 0)`` -- the clamped matmul
    identity (``kernels/ref.matmul_sqdist``).  The clamp matters on
    arbitrary fp32 data, where rounding of the three-term form can dip a
    true-zero distance slightly negative;
  * the n coordinate dimensions stream through in ``dim_block``-wide blocks
    with a VMEM accumulator, exactly like the indexed kernel -- but with NO
    short-circuit branch: in the regime where the dense tier wins (the grid
    has lost its filtering power, ``stats.candidate_filter_ratio`` -> 1)
    SHORTC almost never fires, and dropping the per-block min-reduction and
    SMEM flag traffic keeps the MXU pipeline saturated;
  * ``eps`` is a runtime scalar, prefetched into SMEM alongside the tile
    indices and lengths (same contract as ``distance_tile.py``): one
    compiled program serves every eps value, which is what lets the serving
    tier's kNN eps-expansion loop stay on warm executables.

Grid: ``(P, NB)`` -- P tile pairs x NB dimension blocks, dim-block axis
minor so the partial-d2 scratch carries across blocks of one pair.  The
table is the blocked ``(num_tiles, NB, T, dim_block)`` layout of
``ops.make_tiles`` (see ``distance_tile.py`` for why).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(
    a_idx_ref,      # (P,) int32  scalar prefetch: A tile index per pair
    b_idx_ref,      # (P,) int32  scalar prefetch: B tile index per pair
    tile_len_ref,   # (num_tiles,) int32 scalar prefetch: valid points per tile
    eps2_ref,       # (1,) f32    scalar prefetch: runtime eps^2
    a_ref,          # (1, 1, T, DB) f32 VMEM: current dim block of the A tile
    b_ref,          # (1, 1, T, DB) f32 VMEM: current dim block of the B tile
    counts_ref,     # (1, 1, T) int32 out: per-A-point neighbour count
    d2_ref,         # (T, T) f32 VMEM scratch: partial squared distances
    *,
    num_blocks: int,
    tile_size: int,
    out_mask_ref=None,  # optional (1, T, T) int8 out (pairs mode)
):
    p = pl.program_id(0)
    j = pl.program_id(1)
    t = tile_size

    @pl.when(j == 0)
    def _init():
        d2_ref[:, :] = jnp.zeros((t, t), jnp.float32)

    a = a_ref[0, 0]                                # (T, DB)
    b = b_ref[0, 0]
    na = jnp.sum(a * a, axis=1, keepdims=True)     # (T, 1)
    nb = jnp.sum(b * b, axis=1, keepdims=True)     # (T, 1)
    prod = jax.lax.dot_general(
        a,
        b,
        (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )                                              # (T, T) = a . b^T
    d2_ref[:, :] = d2_ref[:, :] + na + nb.T - 2.0 * prod

    @pl.when(j == num_blocks - 1)
    def _finalize():
        la = tile_len_ref[a_idx_ref[p]]
        lb = tile_len_ref[b_idx_ref[p]]
        rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
        valid = (rows < la) & (cols < lb)
        d2 = jnp.maximum(d2_ref[:, :], 0.0)        # clamp the matmul identity
        within = (d2 <= eps2_ref[0]) & valid
        counts_ref[0] = jnp.sum(within.astype(jnp.int32), axis=1)[None, :]
        if out_mask_ref is not None:
            out_mask_ref[0, :, :] = within.astype(jnp.int8)


def _mask_kernel(*refs, num_blocks, tile_size):
    (a_idx, b_idx, tl, eps2, a, b, counts, mask, d2) = refs
    _kernel(
        a_idx, b_idx, tl, eps2, a, b, counts, d2,
        num_blocks=num_blocks, tile_size=tile_size,
        out_mask_ref=mask,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "return_mask"))
def dense_tile_distance(
    tiles_pts: jax.Array,   # (num_tiles, NB, T, DB) f32 (ops.make_tiles layout)
    tile_len: jax.Array,    # (num_tiles,) int32
    pair_a: jax.Array,      # (P,) int32
    pair_b: jax.Array,      # (P,) int32
    *,
    eps: float,
    interpret: Optional[bool] = None,
    return_mask: bool = False,
):
    """Evaluate every listed tile pair densely (no SHORTC, clamped identity).

    Same calling convention as ``distance_tile.tile_pair_distance`` so
    ``kernels/ops.eval_tile_pairs`` can dispatch on ``backend=`` alone;
    ``eps`` may be a python float or a traced f32 scalar (scalar-prefetch
    operand -- distinct eps values share one executable).  Returns
    ``counts (P, T) int32`` and, when ``return_mask``, also the per-pair
    hit mask ``(P, T, T) int8``.  ``interpret`` defaults to the platform's
    mode (``resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    _, nb, t, db = tiles_pts.shape
    p = pair_a.shape[0]
    eps2 = (jnp.asarray(eps, jnp.float32) ** 2).reshape(1)

    tile_spec_a = pl.BlockSpec(
        (1, 1, t, db), lambda pp, jj, a_idx, b_idx, tl, e2: (a_idx[pp], jj, 0, 0)
    )
    tile_spec_b = pl.BlockSpec(
        (1, 1, t, db), lambda pp, jj, a_idx, b_idx, tl, e2: (b_idx[pp], jj, 0, 0)
    )
    counts_spec = pl.BlockSpec((1, 1, t), lambda pp, jj, *_: (pp, 0, 0))

    out_shapes = [jax.ShapeDtypeStruct((p, 1, t), jnp.int32)]
    out_specs = [counts_spec]
    if return_mask:
        out_shapes.append(jax.ShapeDtypeStruct((p, t, t), jnp.int8))
        out_specs.append(pl.BlockSpec((1, t, t), lambda pp, jj, *_: (pp, 0, 0)))
        body = functools.partial(_mask_kernel, num_blocks=nb, tile_size=t)
    else:
        body = functools.partial(_kernel, num_blocks=nb, tile_size=t)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(p, nb),
        in_specs=[tile_spec_a, tile_spec_b],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)],
    )
    res = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        interpret=interpret,
    )(pair_a, pair_b, tile_len, eps2, tiles_pts, tiles_pts)
    counts = res[0].reshape(p, t)
    return (counts, res[1]) if return_mask else (counts,)
