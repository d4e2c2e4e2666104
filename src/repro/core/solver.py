"""Batched padded multi-weight OBS solve — paper Eq. 10 + Appendix H.1/H.2.

For one row w with pruned indices q = (q_1..q_s) and trailing inverse Hessian
``Hinv``:

    R   = Hinv[q, :]            (s, b)     Eq. 7
    R̂   = R[:, q]               (s, s)     Eq. 8
    u   = w[q]                  (1, s)     Eq. 9
    λ̂   solves  λ̂ R̂ = u                   Eq. 57
    Δ̂   = -λ̂ R  = -u R̂^{-1} R              Eq. 60/10

Different rows prune different numbers of weights, so per Appendix H.1 we pad
every row's system to a common ``r_max``: R̂' gets an identity block in the
padded corner and u' gets zeros (Eq. 77–79), making padded multipliers exactly
zero.  The padded system is block-diag(R̂, I) up to a permutation — symmetric
positive definite whenever Hinv is — so the whole batch is solved with one
batched **Cholesky** solve (one factorization + two triangular solves per row
instead of a general LU with pivoting).

Appendix H.2 (GPU memory limits) is honored through ``row_chunk``: rows are
processed in vertical chunks so the (chunk, r_max, r_max) systems and
selections stay bounded.

TPU note: the systems are built by **one-hot contraction**, not by gather.
Each row's padded indices become a 0/1 selection matrix P (c, r_max, n), and
u' = P·w, R̂' = P·Hinv·Pᵀ, Λ = λ̂·P are MXU matmuls.  A per-element gather
``hinv[q[:, :, None], q[:, None, :]]`` fetches every entry of R̂ as its own
scalar from HBM, and took 83% of the prune job's solve on a v5e.  The
contractions run at ``Precision.HIGHEST``: with one operand exactly 0/1 each
output has one nonzero term, so they reproduce the gathered values bit for
bit (TPU DEFAULT would round Hinv to bf16).  The weight update is then
``Δ = -Λ @ Hinv`` — one MXU matmul, no per-row gathers — algebraically
identical to λ̂ @ R because R's rows are rows of Hinv.

The block-wise hot path (``prune_block``) exploits one structural fact:
every pruned index of block j₁ lies inside ``[j1, j1+B)``.  So P has width
B and R̂' reads only the (B, B) diagonal block of Hinv (the selection costs
c·(2rB² + 2r²B) flops), Λ has at most B nonzero columns, and the
update reads only **B rows** of Hinv — the matmul is ``(c, B) @ (B, b)``, a
b/B-fold flop reduction over the dense form.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


# Every contraction with a 0/1 selection matrix: exact, so bit-equal to a gather.
_EXACT = jax.lax.Precision.HIGHEST


def _padded_system(
    h: Array,         # (n, n) inverse Hessian on the columns q indexes
    w: Array,         # (c, n) current weights on the same columns
    q: Array,         # (c, r_max) int32 column indices into h, padded
    valid: Array,     # (c, r_max) bool
) -> tuple[Array, Array, Array]:
    """Build the padded per-row systems of Appendix H.1: (P, R̂', u').

    P (c, r_max, n) is the one-hot selection of each row's indices, zero in
    padded slots; u' = P·w (Eq. 77) and R̂' = P·h·Pᵀ with the identity in
    the padded corner (Eq. 78).
    """
    sel = (q[..., None] == jnp.arange(h.shape[0])) & valid[..., None]
    u = jnp.einsum("crk,ck->cr", sel, w, precision=_EXACT)
    rhat = jnp.einsum("crl,csl->crs",
                      jnp.einsum("crk,kl->crl", sel, h, precision=_EXACT),
                      sel, precision=_EXACT)
    pad = ~valid
    eye = jnp.eye(q.shape[1], dtype=h.dtype)[None]
    rhat = rhat + jnp.where(pad[:, :, None] & pad[:, None, :], eye, 0.0)
    return sel, rhat, u


_TRI_BASE = 16


def _tri_inv_lower(L: Array) -> Array:
    """Batched inverse of a lower-triangular (..., n, n) factor.

    XLA's batched ``triangular_solve`` degenerates to a per-system loop on
    CPU (30 MFLOP/s measured for c=2048, n=128 single-RHS solves), so we
    invert with **pure batched matmuls**: 2×2 blocked recursion
    ``inv([[A,0],[C,D]]) = [[A⁻¹,0],[−D⁻¹CA⁻¹, D⁻¹]]`` down to a base case
    solved by the log-depth Neumann product — with ``S = I − D⁻¹L`` strictly
    lower (Sⁿ = 0), ``L⁻¹ = (Σ_{j<n} Sʲ) D⁻¹ = Π_k (I + S^{2ᵏ}) D⁻¹``.
    ~7× faster than the batched triangular solve at the (2048, 128, 128)
    hot-path shape, identical result to fp roundoff.
    """
    n = L.shape[-1]
    if n <= _TRI_BASE:
        d = jnp.diagonal(L, axis1=-2, axis2=-1)
        eye = jnp.eye(n, dtype=L.dtype)
        s = eye - L / d[..., :, None]
        acc = eye + s
        p = s
        steps = 2
        while steps < n:
            p = p @ p
            acc = acc @ (eye + p)
            steps *= 2
        return acc / d[..., None, :]
    m = n // 2
    a_inv = _tri_inv_lower(L[..., :m, :m])
    d_inv = _tri_inv_lower(L[..., m:, m:])
    x = -(d_inv @ (L[..., m:, :m] @ a_inv))
    top = jnp.concatenate(
        [a_inv, jnp.zeros(L.shape[:-2] + (m, n - m), L.dtype)], axis=-1
    )
    return jnp.concatenate([top, jnp.concatenate([x, d_inv], axis=-1)],
                           axis=-2)


def _spd_solve(rhat: Array, u: Array) -> Array:
    """Batched SPD solve ``R̂' λ̂' = u'``: Cholesky + matmul-only inverse.

    (c, r, r), (c, r) → (c, r); λ̂ = L⁻ᵀ(L⁻¹u).

    Numerical-failure contract: for an R̂ that is not numerically positive
    definite (ill-conditioned trailing inverse from a singular H),
    ``jnp.linalg.cholesky`` returns NaNs instead of raising, the NaN
    multipliers poison the weight update, and the whole solve stays
    jit/shard_map-traceable.  Detection is deliberately *post-hoc* and
    host-level — ``solution_finite`` below, driven by
    ``core.api.prune_layer_guarded`` — because a host check here would
    break tracing inside ``dist.prune.prune_layer_sharded``.
    """
    linv = _tri_inv_lower(jnp.linalg.cholesky(rhat))
    # Products and sums in f32, not einsums: at DEFAULT precision the TPU
    # compiler may put a batched matvec on the MXU with bf16 operands or
    # keep it on the VPU in f32, depending on the program around it.
    y = jnp.sum(linv * u[..., None, :], axis=-1)
    return jnp.sum(linv * y[..., :, None], axis=-2)


def solution_finite(*arrays: Array) -> bool:
    """Host-level finiteness check over solve outputs (weights, loss).

    One fused reduction per array — O(c·b) reads against the solve's
    O(b³) flops, measured in BENCH_prune.json's ``guard_overhead`` entry.
    Forces a device sync, so call it once per *layer*, never per block.
    """
    return all(bool(jnp.all(jnp.isfinite(a))) for a in arrays)


def _solve_rows(
    h: Array, w: Array, q: Array, valid: Array
) -> tuple[Array, Array, Array, Array]:
    """Solve the rows' padded systems on the columns of ``h``.

    Returns λ̂ (c, r_max) (exactly zero in padded slots, Eq. 79), u', the
    multipliers scattered to their columns Λ = λ̂·P (c, n), and the (c, n)
    bool mask of pruned columns.
    """
    sel, rhat, u = _padded_system(h, w, q, valid)
    # R̂ is symmetric positive definite (principal submatrix of an SPD
    # inverse Hessian, identity in the padded corner) — Cholesky applies.
    lam = jnp.where(valid, _spd_solve(rhat, u), 0.0)
    lam_dense = jnp.einsum("cr,crk->ck", lam, sel, precision=_EXACT)
    return lam, u, lam_dense, jnp.any(sel, axis=1)


def _solve_rows_chunked(
    h: Array, w: Array, q: Array, valid: Array, row_chunk: int
) -> tuple[Array, Array, Array, Array]:
    """``_solve_rows``, chunked over rows when requested (Appendix H.2)."""
    c = w.shape[0]
    if row_chunk and c > row_chunk and c % row_chunk == 0:
        n = c // row_chunk
        out = jax.lax.map(
            lambda args: _solve_rows(h, *args),
            (
                w.reshape(n, row_chunk, -1),
                q.reshape(n, row_chunk, -1),
                valid.reshape(n, row_chunk, -1),
            ),
        )
        return tuple(x.reshape(c, -1) for x in out)
    return _solve_rows(h, w, q, valid)


def batched_multipliers(
    hinv: Array, w: Array, q_abs: Array, valid: Array
) -> Array:
    """Solve all rows' padded systems; return multipliers λ̂ (c, r_max)."""
    return _solve_rows(hinv, w, q_abs, valid)[0]


def prune_rows_block(
    hinv: Array, w: Array, q_abs: Array, valid: Array, *, row_chunk: int = 0
) -> Array:
    """Full padded solve + update Δ = -Λ @ Hinv, optionally chunked over
    rows (App. H.2); returns updated weights (c, b).

    Pruned positions are additionally zeroed exactly (the analytic update
    already sends them to 0; we clamp against fp roundoff).
    """
    _, _, lam_dense, prune_hit = _solve_rows_chunked(
        hinv, w, q_abs, valid, row_chunk
    )
    return jnp.where(prune_hit, 0.0, w - lam_dense @ hinv)


def prune_block(
    hinv: Array,      # (b, b) trailing inverse (exact on [j1:, j1:])
    w: Array,         # (c, b)
    q_abs: Array,     # (c, r_max) absolute indices, all inside [j1, j1+B)
    valid: Array,     # (c, r_max)
    j1: Array,        # () int32 — first column of the block (may be traced)
    block_size: int,  # B (static)
    *,
    row_chunk: int = 0,
) -> tuple[Array, Array]:
    """Single-solve OBS for one column block: (updated weights, Σ_rows S_k).

    The multipliers are solved **once** and reused for both the loss
    (S = ½ u R̂⁻¹ uᵀ = ½ λ̂·u, Eq. 61) and the weight update.

    Every pruned index lies inside the block, so everything is built from
    the block's slice: u' from ``W[:, s:s+B]`` and R̂' from the diagonal
    block ``Hinv[s:s+B, s:s+B]`` by one-hot contraction with the (c, r_max,
    B) selection P (module TPU note: exact, and MXU matmuls where a gather
    from the whole (b, b) inverse would fetch each entry alone), and the
    update is ``Δ = -(λ̂·P) @ Hinv[s:s+B, :]``.

    Columns left of j1 are masked out of the update: they are already
    processed (mathematically Hinv rows j1:j1+B are zero there; the
    incremental downdate that produces ``hinv`` leaves O(ε) residue which
    must not perturb — or un-zero — finished columns).

    A ragged last block (b % B ≠ 0) is handled by anchoring the slices at
    s = ``min(j1, b - B)``: the extra leading rows carry λ̂ = 0 and
    contribute nothing.
    """
    c, b = w.shape
    start = jnp.minimum(j1, b - block_size)   # == j1 except ragged last block
    hinv_rows = jax.lax.dynamic_slice(hinv, (start, 0), (block_size, b))
    h_blk = jax.lax.dynamic_slice(hinv_rows, (0, start),
                                  (block_size, block_size))
    w_blk = jax.lax.dynamic_slice(w, (0, start), (c, block_size))
    lam, u, lam_blk, prune_hit = _solve_rows_chunked(
        h_blk, w_blk, q_abs - start, valid, row_chunk
    )
    loss = 0.5 * jnp.sum(lam * u)

    delta = lam_blk @ hinv_rows
    delta = jnp.where(jnp.arange(b)[None, :] >= j1, delta, 0.0)
    w_new = w - delta
    w_new = jnp.where(
        jax.lax.dynamic_update_slice(
            jnp.zeros((c, b), dtype=bool), prune_hit, (0, start)
        ),
        0.0,
        w_new,
    )
    return w_new, loss


def obs_loss(hinv: Array, w: Array, q_abs: Array, valid: Array) -> Array:
    """S_k per row (Eq. 61): ½ u R̂⁻¹ R H Rᵀ R̂⁻ᵀ uᵀ = ½ u R̂⁻¹ uᵀ.

    (R H Rᵀ = Hinv[q,:] H Hinv[:,q] = Hinv[q,q] = R̂, so S = ½ u R̂⁻¹ uᵀ —
    we use the simplified closed form; equality asserted in tests.)

    Standalone diagnostic: the block-wise hot path gets the loss for free
    from ``prune_block``'s single solve.
    """
    lam, u, _, _ = _solve_rows(hinv, w, q_abs, valid)
    return 0.5 * jnp.sum(lam * u, axis=1)
