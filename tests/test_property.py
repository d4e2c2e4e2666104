"""Hypothesis property tests on the system's invariants."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional test dep: pip install '.[test]'")
from hypothesis import given, settings, strategies as st

from repro.core import PruneConfig, prune_layer
from repro.core.masks import check_nm, nm_mask, psi_x, wanda_metric
from repro.core.sparsity import (
    pack_indices4, pack_nm, unpack_indices4, unpack_nm,
)
from repro.core.thanos import prune_unstructured
from repro.data.pipeline import SyntheticCorpus
from conftest import recon_error

SETTINGS = dict(max_examples=20, deadline=None)


def _problem(c, b, seed):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(c, b)).astype(np.float32)
    x = (rng.normal(size=(4 * b, b))
         * rng.lognormal(0, 1, size=(b,))[None, :]).astype(np.float32)
    h = 2 * x.T @ x
    return jnp.asarray(w), jnp.asarray(h)


@given(c=st.integers(4, 24), b=st.sampled_from([16, 32, 48]),
       p=st.floats(0.05, 0.85), seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_budget_exactness_any_shape(c, b, p, seed):
    """⌊pcb⌋ coordinates pruned, exactly, for any (c, b, p)."""
    w, h = _problem(c, b, seed)
    res = prune_unstructured(w, h, p=p, block_size=16)
    assert int(np.asarray(res.mask).sum()) == math.floor(p * c * b)
    assert np.all(np.asarray(res.weights)[np.asarray(res.mask) > 0.5] == 0.0)
    assert np.isfinite(np.asarray(res.weights)).all()


@given(c=st.integers(2, 16), groups=st.integers(2, 8),
       nm=st.sampled_from([(1, 2), (2, 4), (4, 8), (3, 4)]),
       seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_nm_mask_invariant(c, groups, nm, seed):
    """Every m-group of every row has exactly n ones, for any metric."""
    n, m = nm
    b = groups * m
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(c, b)), jnp.float32)
    xn = jnp.asarray(rng.uniform(0.1, 3.0, size=(b,)), jnp.float32)
    mask = nm_mask(w, xn, n, m)
    assert bool(check_nm(mask, n, m))


@given(c=st.integers(2, 12), groups=st.integers(1, 6),
       nm=st.sampled_from([(2, 4), (4, 8), (1, 4), (3, 4), (5, 8)]),
       idx_bits=st.sampled_from([4, 8]), seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_pack_unpack_roundtrip(c, groups, nm, idx_bits, seed):
    n, m = nm
    b = groups * m
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(c, b)), jnp.float32)
    xn = jnp.ones((b,), jnp.float32)
    mask = nm_mask(w, xn, n, m)
    wm = jnp.where(mask > 0.5, 0.0, w)
    packed = pack_nm(wm, mask, n, m, idx_bits=idx_bits)
    assert np.array_equal(np.asarray(unpack_nm(packed)), np.asarray(wm))


@given(c=st.integers(1, 10), length=st.integers(1, 40),
       seed=st.integers(0, 10_000))
@settings(**SETTINGS)
def test_indices4_roundtrip_any_length(c, length, seed):
    """Two-slots-per-byte nibble packing round-trips for any number of
    slot planes L over (c, 3) planes, odd L included (final high nibble is
    padding)."""
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 16, size=(length, c, 3)), jnp.int8)
    packed = pack_indices4(idx)
    assert packed.shape == ((length + 1) // 2, c, 3)
    assert np.array_equal(np.asarray(unpack_indices4(packed, length)),
                          np.asarray(idx))


@given(c=st.integers(3, 20), groups=st.integers(1, 6),
       B=st.integers(1, 9), nm=st.sampled_from([(2, 4), (4, 8), (3, 4)]),
       seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_nm_matmul_three_way_parity(c, groups, B, nm, seed):
    """ref vs pallas-interpret vs dense agree on arbitrary (c, b, B) —
    including shapes no tile divides (the ops wrapper pads and slices)."""
    from repro.kernels import ops

    n, m = nm
    b = groups * m
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(c, b)), jnp.float32)
    mask = nm_mask(w, jnp.ones((b,), jnp.float32), n, m)
    wm = jnp.where(mask > 0.5, 0.0, w)
    packed = pack_nm(wm, mask, n, m)
    x = jnp.asarray(rng.normal(size=(B, b)), jnp.float32)
    y_dense = np.asarray(x @ wm.T)
    np.testing.assert_allclose(
        np.asarray(ops.nm_matmul(x, packed, impl="ref")), y_dense,
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(ops.nm_matmul(x, packed, impl="pallas")), y_dense,
        rtol=1e-4, atol=1e-4)


@given(r=st.integers(0, 32 * 16), seed=st.integers(0, 1000))
@settings(**SETTINGS)
def test_psi_x_selects_r_smallest(r, seed):
    """ψ_X(W, r) prunes exactly r entries and they are metric-minimal."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    xn = jnp.asarray(rng.uniform(0.1, 2.0, size=(32,)), jnp.float32)
    mask = np.asarray(psi_x(w, xn, jnp.asarray(r)))
    assert int(mask.sum()) == r
    metric = np.asarray(wanda_metric(w, xn))
    if 0 < r < mask.size:
        assert metric[mask > 0.5].max() <= metric[mask <= 0.5].min() + 1e-6


@given(r=st.integers(0, 9 * 14), seed=st.integers(0, 1000))
@settings(**SETTINGS)
def test_rank_threshold_mask_equals_stable_argsort(r, seed):
    """The sort-free k-th-value selection is *bit-identical* to the stable
    argsort it replaced — including tie-breaks by flat index and masked
    +inf entries (the regime of the Thanos residual-mask loop)."""
    from repro.core.masks import rank_threshold_mask

    rng = np.random.default_rng(seed)
    # coarsely quantized values force heavy ties; a few +inf masked slots
    vals = (rng.integers(0, 6, size=(9, 14)) * 0.25).astype(np.float32)
    vals[rng.uniform(size=vals.shape) < 0.1] = np.inf
    got = np.asarray(rank_threshold_mask(jnp.asarray(vals), jnp.asarray(r)))
    order = np.argsort(vals.ravel(), kind="stable")
    ref = np.zeros(vals.size, bool)
    ref[order[:r]] = True
    assert np.array_equal(got.ravel(), ref)


@given(p=st.floats(0.1, 0.7), seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_update_monotonicity(p, seed):
    """OBS compensation never loses to naive masking (same mask)."""
    w, h = _problem(12, 32, seed)
    res = prune_unstructured(w, h, p=p, block_size=16)
    naive = jnp.where(res.mask > 0.5, 0.0, w)
    assert recon_error(w, res.weights, h) <= recon_error(w, naive, h) + 1e-3


@given(step=st.integers(0, 10_000), host=st.integers(0, 15))
@settings(max_examples=10, deadline=None)
def test_data_pipeline_deterministic(step, host):
    """batch_at(step) is a pure function of (seed, host, step)."""
    from repro.data.pipeline import TrainStream

    corpus = SyntheticCorpus(vocab_size=512, seed=7)
    s1 = TrainStream(corpus, global_batch=32, seq_len=32, num_hosts=16,
                     host_id=host, seed=3)
    s2 = TrainStream(corpus, global_batch=32, seq_len=32, num_hosts=16,
                     host_id=host, seed=3)
    np.testing.assert_array_equal(np.asarray(s1.batch_at(step)["tokens"]),
                                  np.asarray(s2.batch_at(step)["tokens"]))


@given(seed=st.integers(0, 100))
@settings(max_examples=5, deadline=None)
def test_int8_error_feedback_contracts(seed):
    """Quantization with error feedback: residual stays bounded and the
    dequantized stream converges to the true mean signal."""
    from repro.dist.compression import ErrorFeedback, compress_grads

    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.normal(size=(64,)), jnp.float32)}
    ef = ErrorFeedback.init(g)
    total_deq = np.zeros(64)
    steps = 8
    for _ in range(steps):
        payload, ef = compress_grads(g, ef)
        q, scale = payload["w"]
        total_deq += np.asarray(q, np.float32) * float(scale)
    # mean dequantized ≈ g (error feedback cancels bias)
    np.testing.assert_allclose(total_deq / steps, np.asarray(g["w"]),
                               atol=2e-2)
