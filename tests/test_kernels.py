"""Pallas kernels: interpret-mode sweeps vs the pure-jnp oracle, plus the
decode(encode(.)) exactness property at the kernel level."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.core import decode_weights, make_code
from repro.kernels import ops, ref
from repro.kernels.gc_decode import decode_pallas
from repro.kernels.gc_encode import encode_pallas
from repro.kernels.gc_fused import block_bytes, fused_block, stack_view
from repro.train.state import abstract_train_state

SHAPES = [(2, 128), (3, 1000), (5, 4096), (8, 513), (4, 131), (16, 2048)]
DTYPES = [jnp.float32, jnp.bfloat16]
TILES = [128, 256, 512]


@pytest.mark.parametrize("k,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_encode_kernel_matches_oracle(k, d, dtype):
    rng = np.random.default_rng(k * 1000 + d)
    g = jnp.asarray(rng.standard_normal((k, d)), dtype)
    b = jnp.asarray(rng.standard_normal((min(k + 2, 6), k)), dtype)
    out = encode_pallas(b, g, tile_d=256, interpret=True)
    want = ref.encode_ref(b, g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2, atol=1e-4)


@pytest.mark.parametrize("k,d", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_decode_kernel_matches_oracle(k, d, dtype):
    rng = np.random.default_rng(k * 7 + d)
    c = jnp.asarray(rng.standard_normal((k, d)), dtype)
    a = jnp.asarray(rng.standard_normal(k), dtype)
    out = decode_pallas(a, c, tile_d=256, interpret=True)
    want = ref.decode_ref(a, c)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), rtol=2e-2, atol=1e-4)


@pytest.mark.parametrize("tile", TILES)
def test_tile_sweep(tile):
    rng = np.random.default_rng(tile)
    g = jnp.asarray(rng.standard_normal((4, 3000)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
    out = encode_pallas(b, g, tile_d=tile, interpret=True)
    np.testing.assert_allclose(out, ref.encode_ref(b, g), rtol=1e-5, atol=1e-5)


def test_ops_dispatch_cpu():
    """ops.encode/decode use the oracle off-TPU, pallas when forced."""
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((3, 777)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 3)), jnp.float32)
    a = jnp.asarray(rng.standard_normal(3), jnp.float32)
    np.testing.assert_allclose(ops.encode(b, g),
                               ops.encode(b, g, force_pallas=True), rtol=1e-6)
    np.testing.assert_allclose(ops.decode(a, g),
                               ops.decode(a, g, force_pallas=True), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(3, 8), st.data())
def test_kernel_level_decode_of_encode_property(n, data):
    """Full pipeline at kernel level: encode with B rows via the pallas
    kernel, decode with the straggler-masked weights — recovers sum g."""
    s = data.draw(st.integers(0, n - 1))
    d = data.draw(st.integers(8, 600))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    b_mat = make_code(n, s, rng=0, prefer_fractional=False)
    g = rng.standard_normal((n, d))
    coded = encode_pallas(jnp.asarray(b_mat, jnp.float32),
                          jnp.asarray(g, jnp.float32), tile_d=128, interpret=True)
    stragglers = rng.choice(n, size=s, replace=False)
    fastest = np.setdiff1d(np.arange(n), stragglers)
    a = decode_weights(b_mat, fastest)
    y = decode_pallas(jnp.asarray(a, jnp.float32), coded, tile_d=128, interpret=True)
    np.testing.assert_allclose(np.asarray(y), g.sum(axis=0), rtol=1e-4, atol=1e-4)


SCOPED_VMEM_DEFAULT = 16 << 20  # Mosaic's default scoped VMEM limit, v5e


@pytest.mark.parametrize("itemsize", [4, 2], ids=["fp32", "bf16"])
@pytest.mark.parametrize("k", [1, 4, 8])
@pytest.mark.parametrize("arch,n_leaves", [("gc-lm-110m", 11),
                                           ("whisper-base", 103)])
def test_fused_block_fits_scoped_vmem(arch, n_leaves, k, itemsize):
    """Every leaf of both benchmark models: the fused kernel's block of
    its (K, *shape) stack and the output block, double-buffered, fit
    Mosaic's default scoped VMEM, and each tiled block dim is whole or a
    multiple of its tile."""
    params = abstract_train_state(get_config(arch))[0].params
    leaves = [tuple(a.shape) for a in jax.tree.leaves(params)]
    assert len(leaves) == n_leaves
    sub = 8 * 4 // itemsize
    for leaf in leaves:
        view, k_axis = stack_view(k, leaf)
        block = fused_block(view, k_axis, itemsize)
        out = tuple(b for d, b in enumerate(block) if d != k_axis)
        out_view = tuple(v for d, v in enumerate(view) if d != k_axis)
        vmem = 2 * (block_bytes(block, itemsize) + block_bytes(out, itemsize))
        assert vmem < SCOPED_VMEM_DEFAULT, (leaf, block, vmem)
        assert out[-1] == out_view[-1] or out[-1] % 128 == 0, (leaf, out)
        assert out[-2] == out_view[-2] or out[-2] % sub == 0, (leaf, out)
