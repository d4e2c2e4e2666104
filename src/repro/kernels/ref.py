"""Pure-jnp oracles for every Pallas kernel in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sparsity import unpack_indices4

Array = jax.Array


def nm_expand(values: Array, indices: Array, n: int, m: int, b: int,
              idx_bits: int = 8) -> Array:
    """Dense (c, b) from slot-major n:m storage — in-group scatter.

    values: (keep, c, g) with g = b/m groups and ``keep = m − n`` kept
    weights each (plane k = slot k of every group); indices are the
    matching int8 in-group positions (0..m−1), one per byte (idx_bits=8,
    (keep, c, g)) or two slots per byte, low nibble first (idx_bits=4,
    (⌈keep/2⌉, c, g)).

    Dense column j of every group is ``Σ_k where(idx_k == j, val_k, 0)`` —
    the same per-slot masked selects the Pallas kernel runs per VMEM tile
    to build its m dense planes, and the fastest CPU variant measured (an
    XLA scatter serializes; the old one-hot formulation materialized a
    (c, g, keep, m) fp32 tensor and burned m/keep× extra FLOPs for the same
    placement).  Placement only, no arithmetic: the expansion is bit-exact
    in the stored dtype.
    """
    keep = m - n
    if idx_bits == 4:
        indices = unpack_indices4(indices, keep)
    idx = indices.astype(jnp.int32)[..., None]               # (keep, c, g, 1)
    vals = values[..., None]
    iota = jnp.arange(m)
    c = values.shape[1]
    dense = jnp.zeros((c, b // m, m), values.dtype)
    for k in range(keep):
        dense = dense + jnp.where(idx[k] == iota, vals[k], 0)
    return dense.reshape(c, b)


def nm_matmul_ref(x: Array, values: Array, indices: Array, n: int, m: int,
                  b: int, idx_bits: int = 8) -> Array:
    """y = x @ denseᵀ for n:m compressed W (c, b); x (B, b) → y (B, c).

    The expanded weight keeps the stored dtype and the matmul runs in the
    activation dtype — the identical dot XLA emits for a dense kernel, so
    serving from the compressed representation is bit-equal to serving the
    decompressed weights (asserted in tests/test_compressed_serving.py).
    """
    w = nm_expand(values, indices, n, m, b, idx_bits)
    return (x @ w.astype(x.dtype).T).astype(x.dtype)


def nm_expand_stacked(values: Array, indices: Array, n: int, m: int, b: int,
                      idx_bits: int = 8) -> Array:
    """Dense (E, c, b) from stacked slot-major n:m storage.

    The masked-select keep-loop of :func:`nm_expand` vmapped over the
    leading expert axis — placement only, bit-exact in the stored dtype,
    and the formulation a stacked Pallas kernel would run per expert tile.
    """
    return jax.vmap(
        lambda v, i: nm_expand(v, i, n, m, b, idx_bits))(values, indices)


def nm_matmul_stacked_ref(x: Array, values: Array, indices: Array, n: int,
                          m: int, b: int, idx_bits: int = 8) -> Array:
    """Batched expert matmul from compressed storage: x (E, C, b) →
    y (E, C, c) with y[e] = x[e] @ dense(e)ᵀ.

    The expansion is bit-exact and the einsum is the identical batched dot
    ``models/layers.stacked_dense`` emits for dense (E, b→in, c→out)
    kernels (same contraction dim, same order), so stacked-compressed
    serving is bit-equal to serving the decompressed expert stack
    (asserted in tests/test_stacked_compressed.py).
    """
    w = nm_expand_stacked(values, indices, n, m, b, idx_bits)   # (E, c, b)
    w = jnp.swapaxes(w.astype(x.dtype), -1, -2)                 # (E, b, c)
    return jnp.einsum("ecd,edf->ecf", x, w).astype(x.dtype)


def hessian_ref(x: Array) -> Array:
    """H = 2·XᵀX for token-major X (tokens, b) — fp32 accumulation."""
    x32 = x.astype(jnp.float32)
    return 2.0 * (x32.T @ x32)
