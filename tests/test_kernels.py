"""Pallas distance-tile kernel vs the pure-jnp oracle (interpret mode).

Shape/dtype sweep per the deliverable: tile sizes, dimension counts (with
padding), dim-block splits (the blocked ``(num_tiles, NB, T, DB)`` layout of
``ops.block_dims``); exactness via 1/64-quantized coordinates (all squared
distances exactly representable in fp32 in both the direct and the matmul
formulation).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import ops, resolve_interpret
from repro.kernels.distance_tile import tile_pair_distance
from repro.kernels.ref import ref_tile_counts, ref_tile_mask


def _mk(num_tiles, t, n, seed, quantize=True):
    rng = np.random.default_rng(seed)
    pts = rng.random((num_tiles, t, n), dtype=np.float32)
    if quantize:
        pts = np.round(pts * 64) / 64.0
    lens = rng.integers(1, t + 1, size=num_tiles).astype(np.int32)
    for i in range(num_tiles):
        pts[i, lens[i]:] = 0.0
    p = rng.integers(0, num_tiles, size=(24, 2)).astype(np.int32)
    return pts.astype(np.float32), lens, p[:, 0], p[:, 1]


@pytest.mark.parametrize("t", [8, 16, 32])
@pytest.mark.parametrize("n,db", [(8, 8), (16, 8), (32, 16), (64, 32)])
def test_kernel_counts_match_ref(t, n, db):
    pts, lens, pa, pb = _mk(6, t, n, seed=t * 100 + n)
    pts = ops.block_dims(pts, db)
    eps = 0.31
    counts, skipped = tile_pair_distance(
        jnp.asarray(pts), jnp.asarray(lens), jnp.asarray(pa), jnp.asarray(pb),
        eps=eps, interpret=True,
    )
    ref = ref_tile_counts(jnp.asarray(pts), jnp.asarray(lens),
                          jnp.asarray(pa), jnp.asarray(pb), eps)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(ref))
    assert skipped.shape == (24, 1)


@pytest.mark.parametrize("t", [8, 16])
def test_kernel_mask_matches_ref(t):
    pts, lens, pa, pb = _mk(5, t, 16, seed=t)
    pts = ops.block_dims(pts, 8)
    eps = 0.4
    _, _, mask = tile_pair_distance(
        jnp.asarray(pts), jnp.asarray(lens), jnp.asarray(pa), jnp.asarray(pb),
        eps=eps, interpret=True, return_mask=True,
    )
    ref = ref_tile_mask(jnp.asarray(pts), jnp.asarray(lens),
                        jnp.asarray(pa), jnp.asarray(pb), eps)
    np.testing.assert_array_equal(np.asarray(mask).astype(bool), np.asarray(ref))


def test_kernel_shortcircuit_skips_far_tiles():
    """Two clusters far apart: cross-tile pairs must skip later dim blocks."""
    t, n = 8, 32
    pts = np.zeros((2, t, n), np.float32)
    pts[0] = 0.0
    pts[1] = 0.9
    lens = np.full(2, t, np.int32)
    pa = np.array([0, 0, 1], np.int32)
    pb = np.array([0, 1, 1], np.int32)
    counts, skipped = tile_pair_distance(
        jnp.asarray(ops.block_dims(pts, 8)), jnp.asarray(lens),
        jnp.asarray(pa), jnp.asarray(pb), eps=0.05, interpret=True,
    )
    c = np.asarray(counts)
    s = np.asarray(skipped)[:, 0]
    assert c[0].sum() == t * t  # identical points all match
    assert c[1].sum() == 0      # cross pair: no matches...
    assert s[1] == 3            # ...decided after the first of 4 blocks
    assert s[0] == 0


def test_kernel_matches_jnp_backend_exactly():
    pts, lens, pa, pb = _mk(8, 16, 24, seed=42)
    # n=24 pads to 32 with dim_block=8 -> 4 blocks (padding block included)
    pts_pad = np.zeros((8, 16, 24), np.float32)
    pts_pad[:] = pts
    tiles, lens32 = ops.make_tiles(
        pts_pad.reshape(-1, 24), np.arange(0, 8 * 16, 16, dtype=np.int64),
        np.asarray(lens, np.int64), 16, 8,
    )
    for backend in ("jnp", "pallas"):
        c, s = ops.tile_counts(
            tiles, lens32, pa, pb, eps=0.25,
            shortc=True, backend=backend, chunk=16,
        )
        if backend == "jnp":
            base_c, base_s = c, s
        else:
            np.testing.assert_array_equal(c, base_c)
            np.testing.assert_array_equal(s, base_s)


def test_unquantized_f32_tolerance():
    """Unquantized coords: matmul vs direct form may differ only at the
    eps boundary; counts must agree when no distance is within 1e-5 of eps."""
    rng = np.random.default_rng(3)
    pts = ops.block_dims(rng.random((4, 8, 16), dtype=np.float32), 16)
    lens = np.full(4, 8, np.int32)
    pa = np.array([0, 1, 2], np.int32)
    pb = np.array([1, 2, 3], np.int32)
    eps = 0.437  # generic value; boundary ties have measure ~0
    counts, _ = tile_pair_distance(
        jnp.asarray(pts), jnp.asarray(lens), jnp.asarray(pa), jnp.asarray(pb),
        eps=eps, interpret=True,
    )
    ref = ref_tile_counts(jnp.asarray(pts), jnp.asarray(lens),
                          jnp.asarray(pa), jnp.asarray(pb), eps)
    diff = np.abs(np.asarray(counts) - np.asarray(ref)).sum()
    assert diff <= 2  # allow boundary straddle only


@pytest.mark.parametrize(
    "platform,requested,want",
    [("cpu", None, True), ("tpu", None, False), ("cpu", False, False),
     ("tpu", False, False), ("cpu", True, True)],
)
def test_interpret_resolves_from_platform(platform, requested, want):
    """The interpreter runs on the CPU only; a TPU always compiles."""
    assert resolve_interpret(requested, platform) is want


def test_interpret_request_on_tpu_is_an_error():
    with pytest.raises(ValueError, match="interpret"):
        resolve_interpret(True, "tpu")


@pytest.mark.parametrize("n,db", [(16, 32), (18, 8), (90, 32)])
def test_block_dims_layout(n, db):
    """One dim block of one tile is a contiguous (T, db) slab; padding is 0."""
    x = np.random.default_rng(n).random((3, 8, n)).astype(np.float32)
    got = ops.block_dims(x, db)
    nb = -(-n // db)
    assert got.shape == (3, nb, 8, db)
    flat = np.swapaxes(got, 1, 2).reshape(3, 8, nb * db)
    np.testing.assert_array_equal(flat[:, :, :n], x)
    assert not flat[:, :, n:].any()
    np.testing.assert_array_equal(
        np.asarray(ops.block_dims(jnp.asarray(x), db)), got
    )
