"""Ladder rung 2 — Eq. 10 multi-weight OBS update vs a KKT oracle.

Removing a *set* q₁..q_s simultaneously with optimal compensation is a
linearly-constrained least-squares problem; the paper's closed form
Δ̂ = −u R̂⁻¹ R (Eq. 60) and loss S (Eq. 61) must match the KKT solution, and
the batched *padded* solver (Appendix H.1) must reproduce both for ragged
per-row index sets.  The padded systems are built by one-hot contraction;
the gather formulation kept here is the oracle they must equal bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import masks as mmod
from repro.core import solver as smod
from repro.core import thanos
from repro.core.hessian import dampen, inv_cholesky_upper
from conftest import make_problem


def kkt_multi(w_row: np.ndarray, h: np.ndarray, q: list[int]) -> np.ndarray:
    """min ½δHδᵀ s.t. δ_q = −w_q via the full KKT system."""
    b = w_row.shape[0]
    s = len(q)
    E = np.zeros((s, b))
    E[np.arange(s), q] = 1.0
    kkt = np.block([[h, E.T], [E, np.zeros((s, s))]])
    rhs = np.concatenate([np.zeros(b), -w_row[q]])
    sol = np.linalg.solve(kkt, rhs)
    return sol[:b]


@pytest.mark.parametrize("seed,qs", [
    (0, [1, 5, 9]),
    (1, [0, 2, 3, 15]),
    (2, [7]),
])
def test_closed_form_matches_kkt(seed, qs):
    w, h, _ = make_problem(c=3, b=20, a=80, seed=seed)
    hd = np.asarray(dampen(h, 0.01), np.float64)
    hinv = np.linalg.inv(hd)
    wn = np.asarray(w, np.float64)
    k = 0

    R = hinv[qs, :]
    Rhat = R[:, qs]
    u = wn[k, qs]
    delta_paper = -(u @ np.linalg.inv(Rhat)) @ R          # Eq. 60
    delta_kkt = kkt_multi(wn[k], hd, qs)
    np.testing.assert_allclose(delta_paper, delta_kkt, rtol=1e-6, atol=1e-9)

    # S (Eq. 61) = ½ u R̂⁻¹ R H Rᵀ R̂⁻ᵀ uᵀ — and the simplified ½ u R̂⁻¹ uᵀ
    lam = u @ np.linalg.inv(Rhat)
    s_full = 0.5 * lam @ R @ hd @ R.T @ lam.T
    s_simple = 0.5 * lam @ u
    actual = 0.5 * delta_paper @ hd @ delta_paper
    np.testing.assert_allclose(s_full, actual, rtol=1e-6)
    np.testing.assert_allclose(s_simple, actual, rtol=1e-6)


def test_batched_padded_solver_matches_perrow():
    """Appendix H.1: ragged rows padded to r_max — identical to row-by-row."""
    w, h, _ = make_problem(c=6, b=24, a=96, seed=4)
    hd_j = dampen(h, 0.01)
    u_hinv = inv_cholesky_upper(hd_j)
    hinv = np.asarray(u_hinv.T @ u_hinv, np.float64)
    wn = np.asarray(w, np.float64)

    per_row = [[0, 3], [5], [], [1, 2, 7, 11], [4, 9], [6]]
    r_max = 4
    q_abs = np.zeros((6, r_max), np.int32)
    valid = np.zeros((6, r_max), bool)
    for i, qs in enumerate(per_row):
        q_abs[i, : len(qs)] = qs
        valid[i, : len(qs)] = True

    w_new = smod.prune_rows_block(
        jnp.asarray(hinv, jnp.float32), w, jnp.asarray(q_abs),
        jnp.asarray(valid),
    )
    w_ref = wn.copy()
    for i, qs in enumerate(per_row):
        if not qs:
            continue
        R = hinv[qs, :]
        u = wn[i, qs]
        lam = np.linalg.solve(R[:, qs].T, u)
        w_ref[i] -= lam @ R
        w_ref[i, qs] = 0.0
    np.testing.assert_allclose(np.asarray(w_new), w_ref, rtol=2e-3, atol=2e-4)

    # padded multipliers are exactly zero (Eq. 79 property)
    lam_b = smod.batched_multipliers(
        jnp.asarray(hinv, jnp.float32), w, jnp.asarray(q_abs),
        jnp.asarray(valid))
    assert np.all(np.asarray(lam_b)[~valid] == 0.0)


def test_row_chunking_invariance():
    """Appendix H.2: vertical chunking must not change the update."""
    w, h, _ = make_problem(c=8, b=32, a=64, seed=5)
    hd = dampen(h, 0.01)
    u_hinv = inv_cholesky_upper(hd)
    hinv = u_hinv.T @ u_hinv
    q_abs = jnp.tile(jnp.asarray([1, 4, 9], jnp.int32), (8, 1))
    valid = jnp.ones((8, 3), bool)
    full = smod.prune_rows_block(hinv, w, q_abs, valid, row_chunk=0)
    chunked = smod.prune_rows_block(hinv, w, q_abs, valid, row_chunk=2)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               rtol=1e-6, atol=1e-7)


def _gathered_system(hinv, w, q, valid):
    """(R̂', u') by element-wise gather through q: the one-hot build's oracle."""
    u = jnp.where(valid, jnp.take_along_axis(w, q, axis=1), 0.0)
    rhat = hinv[q[:, :, None], q[:, None, :]]
    both = valid[:, :, None] & valid[:, None, :]
    eye = jnp.eye(q.shape[1], dtype=hinv.dtype)[None]
    rhat = jnp.where(both, rhat, 0.0) + jnp.where(
        (~valid[:, :, None]) & (~valid[:, None, :]), eye, 0.0
    )
    return rhat, u


def _gathered_solve_rows(h, w, q, valid):
    """``solver._solve_rows`` with a gather for P·h·Pᵀ and P·w and
    scatters for λ̂·P and the pruned-column mask."""
    c, width = w.shape
    rhat, u = _gathered_system(h, w, q, valid)
    lam = jnp.where(valid, smod._spd_solve(rhat, u), 0.0)
    rows = jnp.arange(c)[:, None]
    lam_dense = jnp.zeros((c, width), h.dtype).at[rows, q].add(lam)
    hit = jnp.zeros((c, width), bool).at[rows, q].max(valid)
    return lam, u, lam_dense, hit


# b, first column of the block, r_max, and (n, m) for an n:m mask and
# ``prune_nm``, or None for a ragged mask and ``prune_unstructured``
SELECTION_CASES = {
    "2:4": (256, 128, 64, (2, 4)),
    "4:8": (256, 0, 64, (4, 8)),
    "unstructured-ragged-rows": (256, 128, 128, None),
    "clamped-last-block": (200, 128, 128, None),
}


def _block_mask(w_blk, live, nm):
    """(c, B) 0/1 mask over the block's live columns."""
    c, B = w_blk.shape
    if nm:
        return mmod.nm_mask(w_blk, jnp.ones((B,)), *nm)
    rng = np.random.default_rng(7)
    mask = (rng.random((c, B)) < rng.random((c, 1))) & live[None, :]
    mask[0] = False                   # a row with nothing to prune
    mask[1] = live                    # a row pruned in every live column
    return jnp.asarray(mask, jnp.float32)


@pytest.mark.parametrize("row_chunk", [0, 2])
@pytest.mark.parametrize("case", list(SELECTION_CASES))
def test_onehot_selection_matches_gather(case, row_chunk, monkeypatch):
    """The one-hot build of u', R̂' (padded corner included), λ̂·P and the
    pruned-column mask on the block's slice equals the gather from the whole
    (b, b) inverse bit for bit, and so do Thanos's weights, mask and loss."""
    b, j1, r_max, nm = SELECTION_CASES[case]
    c, B = 8, 128
    w, h, _ = make_problem(c=c, b=b, a=2 * b, seed=6)
    u_hinv = inv_cholesky_upper(dampen(h, 0.01))
    hinv = u_hinv.T @ u_hinv
    start = min(j1, b - B)
    live = np.arange(start, start + B) >= j1
    q_loc, valid = mmod.phi_padded(
        _block_mask(w[:, start:start + B], live, nm), r_max)
    q_abs = q_loc + start
    counts = np.asarray(valid).sum(1)
    assert counts.max() == (r_max if nm else live.sum())
    assert nm or counts.min() == 0

    h_blk = hinv[start:start + B, start:start + B]
    w_blk = w[:, start:start + B]
    _, rhat, u = smod._padded_system(h_blk, w_blk, q_loc, valid)
    rhat_g, u_g = _gathered_system(hinv, w, q_abs, valid)
    assert np.array_equal(np.asarray(rhat), np.asarray(rhat_g))
    assert np.array_equal(np.asarray(u), np.asarray(u_g))

    if nm:
        fn, kw = thanos.prune_nm, dict(n=nm[0], m=nm[1])
    else:
        fn, kw = thanos.prune_unstructured, dict(p=0.5)
    kw.update(block_size=B, row_chunk=row_chunk)
    solve = jax.jit(smod._solve_rows_chunked, static_argnums=4)
    got = solve(h_blk, w_blk, q_loc, valid, row_chunk)
    got_run = fn(w, h, **kw)

    monkeypatch.setattr(smod, "_solve_rows", _gathered_solve_rows)
    # fresh jits, so neither reuses a trace of the one-hot path
    want = jax.jit(lambda *a: smod._solve_rows_chunked(*a, row_chunk))(
        h_blk, w_blk, q_loc, valid)
    want_run = jax.jit(functools.partial(fn.__wrapped__, **kw))(w, h)
    for name, g, x in zip(("lam", "u", "lam_blk", "prune_hit"), got, want):
        assert np.array_equal(np.asarray(g), np.asarray(x)), name
    for name in thanos.PruneResult._fields:
        assert np.array_equal(np.asarray(getattr(got_run, name)),
                              np.asarray(getattr(want_run, name))), name
