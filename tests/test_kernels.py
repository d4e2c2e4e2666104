"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU — the kernel body itself is executed)."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.masks import nm_mask
from repro.core.sparsity import (
    NmCompressed, compression_ratio, pack_indices4, pack_nm,
    unpack_indices4, unpack_nm,
)
from repro.kernels import ops, ref
from repro.kernels.hessian_accum import hessian_xtx
from repro.kernels.nm_spmm import nm_matmul


def _packed(c, b, n, m, dtype, seed=0, idx_bits=4):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(c, b)), dtype)
    xn = jnp.asarray(rng.uniform(0.5, 2.0, size=(b,)), jnp.float32)
    mask = nm_mask(w.astype(jnp.float32), xn, n, m)
    wm = jnp.where(mask > 0.5, 0, w)
    return wm, pack_nm(wm, mask, n, m, idx_bits=idx_bits)


class TestPackUnpack:
    @pytest.mark.parametrize("idx_bits", [4, 8])
    @pytest.mark.parametrize("n,m", [(2, 4), (4, 8), (1, 4), (3, 4), (5, 8)])
    def test_roundtrip(self, n, m, idx_bits):
        wm, packed = _packed(32, 64, n, m, jnp.float32, idx_bits=idx_bits)
        np.testing.assert_array_equal(np.asarray(unpack_nm(packed)),
                                      np.asarray(wm))

    @pytest.mark.parametrize("c,L", [(3, 8), (5, 7), (1, 1), (4, 13)])
    def test_indices4_roundtrip(self, c, L):
        rng = np.random.default_rng(c * 31 + L)
        idx = jnp.asarray(rng.integers(0, 16, size=(L, c, 5)), jnp.int8)
        packed = pack_indices4(idx)
        assert packed.shape == ((L + 1) // 2, c, 5)
        np.testing.assert_array_equal(
            np.asarray(unpack_indices4(packed, L)), np.asarray(idx))

    def test_compression_ratio(self):
        packed_bf = _packed(32, 64, 2, 4, jnp.bfloat16)[1]
        # bf16 2:4: 50% values + ½ B packed 4-bit index per kept value —
        # the paper-style 0.625 (int8 indices would give 0.75)
        assert abs(compression_ratio(packed_bf) - 0.625) < 1e-6
        packed_f32 = _packed(32, 64, 2, 4, jnp.float32)[1]
        assert abs(compression_ratio(packed_f32) - 0.5625) < 1e-6
        packed_i8 = _packed(32, 64, 2, 4, jnp.bfloat16, idx_bits=8)[1]
        assert abs(compression_ratio(packed_i8) - 0.75) < 1e-6

    @pytest.mark.parametrize("idx_bits", [4, 8])
    def test_expand_matches_ref(self, idx_bits):
        wm, packed = _packed(16, 32, 2, 4, jnp.float32, idx_bits=idx_bits)
        dense = ref.nm_expand(packed.values, packed.indices, 2, 4, 32,
                              idx_bits)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(wm))


class TestNmSpmm:
    @pytest.mark.parametrize("idx_bits", [4, 8])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("c,b,B,n,m,bb,bc", [
        (128, 256, 8, 2, 4, 128, 64),
        (256, 512, 4, 4, 8, 256, 128),
        (64, 128, 16, 1, 4, 64, 32),
        (128, 128, 2, 2, 4, 128, 128),   # single tile
    ])
    def test_vs_oracle(self, dtype, c, b, B, n, m, bb, bc, idx_bits):
        rng = np.random.default_rng(c + b)
        wm, packed = _packed(c, b, n, m, dtype, seed=b, idx_bits=idx_bits)
        x = jnp.asarray(rng.normal(size=(B, b)), dtype)
        y_k = nm_matmul(x, packed.values, packed.indices, n=n, m=m, b=b,
                        idx_bits=idx_bits, block_b=bb, block_c=bc,
                        interpret=True)
        y_r = ref.nm_matmul_ref(x, packed.values, packed.indices, n, m, b,
                                idx_bits)
        np.testing.assert_allclose(
            np.asarray(y_k, np.float32), np.asarray(y_r, np.float32),
            rtol=2e-2 if dtype == jnp.bfloat16 else 1e-4,
            atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)

    def test_equals_dense_matmul(self):
        """Compressed matmul ≡ dense matmul on the masked matrix."""
        wm, packed = _packed(64, 128, 2, 4, jnp.float32)
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.normal(size=(4, 128)), jnp.float32)
        y_k = ops.nm_matmul(x, packed, impl="pallas", block_b=64, block_c=64)
        y_d = x @ wm.T
        np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_d),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n,m", [(2, 4), (4, 8), (3, 4), (5, 8)])
    @pytest.mark.parametrize("c,b,B", [
        (37, 24, 5),     # odd c — not a multiple of any tile
        (64, 96, 3),     # b not a multiple of the default 128 tile, odd B
        (129, 520, 7),   # b with a 4-bit-unfriendly tiling (g·keep odd cases)
    ])
    def test_parity_ref_pallas_dense_nondivisible(self, c, b, B, n, m):
        """Three-way parity — ref vs pallas-interpret vs dense — on shapes
        the tile grid does not divide (the ops wrapper pads and slices)."""
        if b % m:
            pytest.skip("b must be a multiple of m by format")
        rng = np.random.default_rng(c * 1000 + b + m)
        wm, packed = _packed(c, b, n, m, jnp.float32, seed=b + m)
        x = jnp.asarray(rng.normal(size=(B, b)), jnp.float32)
        y_dense = x @ wm.T
        y_ref = ops.nm_matmul(x, packed, impl="ref")
        y_pal = ops.nm_matmul(x, packed, impl="pallas")
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_dense),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_dense),
                                   rtol=1e-4, atol=1e-4)

    def test_ops_wrapper_leading_dims(self):
        wm, packed = _packed(32, 64, 2, 4, jnp.float32)
        rng = np.random.default_rng(10)
        x = jnp.asarray(rng.normal(size=(2, 3, 64)), jnp.float32)
        y = ops.nm_matmul(x, packed, impl="ref")
        assert y.shape == (2, 3, 32)

    def test_choose_tiles_respects_layout(self):
        """Chosen tiles divide the contraction (never padded), every block
        is (8, 128)-aligned or spans its whole dimension, and the tile set
        fits the kernel's VMEM budget."""
        for (B, c, b, m, keep, bits, nbytes) in [
            (8, 2048, 2048, 4, 2, 4, 4), (3, 37, 96, 8, 3, 4, 4),
            (1, 7, 520, 4, 3, 4, 4), (16, 512, 1024, 8, 4, 8, 4),
            (4, 2560, 6912, 4, 2, 4, 2), (128, 6912, 2560, 4, 2, 4, 2),
            (300, 1000, 4096, 4, 2, 4, 2),
        ]:
            t = ops.choose_tiles(B, c, b, m, keep, bits, nbytes, nbytes)
            g, bg = b // m, t["block_b"] // m
            assert t["block_b"] % m == 0 and g % bg == 0
            assert bg == g or bg % 128 == 0
            assert t["block_c"] == c or t["block_c"] % 128 == 0
            assert t["block_x"] == B or t["block_x"] % 8 == 0
            assert ops.nm_vmem_bytes(t["block_x"], t["block_c"], bg, m, keep,
                                     bits, nbytes, nbytes) <= ops.VMEM_BUDGET
        # b = 6912: g = 1728 has no 128-multiple divisor — taken whole
        assert ops.choose_tiles(4, 2560, 6912, 4, 2, 4, 2, 2)["block_b"] == \
            6912


class TestHessianAccum:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("t,b,bb,bt", [
        (512, 256, 128, 256),
        (256, 128, 128, 128),
        (1024, 64, 64, 256),
    ])
    def test_vs_oracle(self, dtype, t, b, bb, bt):
        rng = np.random.default_rng(t)
        x = jnp.asarray(rng.normal(size=(t, b)), dtype)
        h_k = hessian_xtx(x, block_b=bb, block_t=bt, interpret=True)
        h_r = ref.hessian_ref(x)
        np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                                   rtol=1e-3, atol=2e-2)

    def test_symmetry_and_psd(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
        h = np.asarray(hessian_xtx(x, block_b=32, block_t=128,
                                   interpret=True))
        np.testing.assert_allclose(h, h.T, rtol=1e-5)
        assert np.linalg.eigvalsh(h).min() > -1e-3
