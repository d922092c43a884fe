"""Kernel parity sweep: ``encode_pallas`` / ``decode_pallas`` in
interpret mode vs the pure-jnp oracle across dtypes (fp32/bf16), ragged
D not a multiple of tile_d, and tile_d in {128, 512}; the fused
``encode_decode_pallas`` on (K, *shape) per-shard stacks of every leaf
rank, K in {1, 2, 8}.  The kernels never ``jnp.pad`` the input:
encode/decode mask the ragged tail tile inside the kernel, the fused
kernel needs no mask (out-of-bounds elements read NaN in interpret
mode, so any leak into a kept element shows up loudly), and outputs are
allocated at the true size."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import decode_weights, make_code
from repro.kernels import ref
from repro.kernels.gc_decode import decode_pallas
from repro.kernels.gc_encode import encode_pallas
from repro.kernels import gc_fused
from repro.kernels.gc_fused import encode_decode_pallas

TILES = [128, 512]
DTYPES = [jnp.float32, jnp.bfloat16]
# ragged widths straddling both tile sizes: below, at, and just past a
# tile boundary, plus a deliberately awkward prime
RAGGED_D = [1, 127, 129, 512, 513, 1021]


def _tol(dtype):
    return dict(rtol=2e-2, atol=1e-4) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tile_d", TILES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_encode_parity_ragged(tile_d, dtype):
    rng = np.random.default_rng(tile_d)
    for d in RAGGED_D:
        g = jnp.asarray(rng.standard_normal((5, d)), dtype)
        b = jnp.asarray(rng.standard_normal((3, 5)), dtype)
        out = encode_pallas(b, g, tile_d=tile_d, interpret=True)
        want = ref.encode_ref(b, g)
        assert out.shape == want.shape == (3, d)
        assert out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   err_msg=f"d={d}", **_tol(dtype))


@pytest.mark.parametrize("tile_d", TILES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_parity_ragged(tile_d, dtype):
    rng = np.random.default_rng(1000 + tile_d)
    for d in RAGGED_D:
        c = jnp.asarray(rng.standard_normal((6, d)), dtype)
        a = jnp.asarray(rng.standard_normal(6), dtype)
        out = decode_pallas(a, c, tile_d=tile_d, interpret=True)
        want = ref.decode_ref(a, c)
        assert out.shape == want.shape == (d,)
        assert out.dtype == dtype
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32),
                                   err_msg=f"d={d}", **_tol(dtype))


# Fused-combine leaves, (K, *shape) stacks: the benchmark models' kinds
# at reduced sizes, each with the leaf's dim order (major to minor) the
# kernel reads it in (None: the host's, row-major).  Rows that fill the
# 2 MiB block in ragged steps; a (12, 64)-tail leaf blocked over its
# major dim; wq's (L, D, H, hd) and a (768, 100) matrix, which a TPU
# lays out with D and 768 minor; a leaf whose 8 rows overflow the block,
# so C is tiled too (ragged).
FUSED_SHAPES = {
    "rank1": ((768,), None),
    "rank2-ragged-rows": ((1000, 768), None),
    "rank2-rows-minor": ((700, 100), (1, 0)),
    "rank3": ((3, 64, 384), None),
    "rank4-small-tail": ((2, 96, 12, 64), None),
    "rank4-d-minor": ((2, 384, 3, 64), (0, 2, 3, 1)),
    "c-tiled": ((9, 70000), None),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=["fp32", "bf16"])
@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("leaf", list(FUSED_SHAPES))
def test_fused_parity_ragged(leaf, k, dtype, monkeypatch):
    """encode_decode_pallas == Σ_k a·row[k]·g[k] oracle on (K, *shape)
    stacks — the fused kernel the flat training pipeline dispatches on
    TPU — with the block grid ragged along every axis it tiles."""
    shape, order = FUSED_SHAPES[leaf]
    if order is not None:  # the device's order for this leaf, not the CPU's
        monkeypatch.setattr(gc_fused, "leaf_order", lambda *_: order)
    rng = np.random.default_rng([k, len(shape)])
    g = jnp.asarray(rng.standard_normal((k,) + shape), dtype)
    row = jnp.asarray(rng.standard_normal(k), jnp.float32)
    a = jnp.float32(rng.standard_normal())
    out = encode_decode_pallas(a, row, g, interpret=True)
    want = ref.encode_decode_ref(a, row, g)
    assert out.shape == want.shape == shape
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_fused_equals_encode_then_scale():
    """The fold is exact up to fp reassociation: Σ_k a·row[k]·g[k] vs
    a * (row @ G)."""
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.standard_normal((4, 7, 100)), jnp.float32)
    row = jnp.asarray(rng.standard_normal(4), jnp.float32)
    a = jnp.asarray(rng.standard_normal(1), jnp.float32)
    fused = encode_decode_pallas(a, row, g, interpret=True)
    two_pass = np.asarray(a) * np.asarray(
        encode_pallas(row[None], g.reshape(4, -1), tile_d=128,
                      interpret=True))
    np.testing.assert_allclose(np.asarray(fused), two_pass.reshape(7, 100),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tile_d", TILES)
def test_padding_edge_matches_hand_padded(tile_d):
    """The kernel's internal pad-to-tile + trim equals padding by hand:
    the zero tail must neither leak into the kept columns nor change
    the accumulation."""
    rng = np.random.default_rng(9)
    d = tile_d + 37  # forces one ragged final tile
    g = rng.standard_normal((4, d))
    b = rng.standard_normal((4, 4))
    d_pad = 2 * tile_d
    g_hand = np.zeros((4, d_pad))
    g_hand[:, :d] = g
    out = encode_pallas(jnp.asarray(b, jnp.float32), jnp.asarray(g, jnp.float32),
                        tile_d=tile_d, interpret=True)
    out_hand = encode_pallas(jnp.asarray(b, jnp.float32),
                             jnp.asarray(g_hand, jnp.float32),
                             tile_d=tile_d, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_hand[:, :d]),
                               rtol=1e-6, atol=1e-6)
    # the padded columns beyond d are exactly zero (B @ 0 == 0)
    assert np.all(np.asarray(out_hand)[:, d:] == 0.0)


@pytest.mark.parametrize("tile_d", TILES)
def test_decode_of_encode_exact_through_kernels(tile_d):
    """Full coded round trip at the kernel level on a ragged width:
    encode with a cyclic code, strike s stragglers, decode — recovers
    sum_j g_j to fp32 tolerance.  (fp32 only: the exactness claim is an
    fp32 property — bf16 storage of the coded values loses the mass the
    decode cancellation needs; bf16 kernel/oracle parity is covered
    above.)"""
    n, s, d = 6, 2, tile_d + 129
    rng = np.random.default_rng(tile_d)
    b_mat = make_code(n, s, rng=3, prefer_fractional=False)
    g = rng.standard_normal((n, d))
    coded = encode_pallas(jnp.asarray(b_mat, jnp.float32),
                          jnp.asarray(g, jnp.float32),
                          tile_d=tile_d, interpret=True)
    stragglers = rng.choice(n, size=s, replace=False)
    fastest = np.setdiff1d(np.arange(n), stragglers)
    a = decode_weights(b_mat, fastest)
    y = decode_pallas(jnp.asarray(a, jnp.float32), coded, tile_d=tile_d,
                      interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32), g.sum(axis=0),
                               rtol=1e-4, atol=1e-4)
