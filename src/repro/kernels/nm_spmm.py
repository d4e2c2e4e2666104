"""Pallas TPU kernel: n:m compressed-weight matmul (decode hot path).

Paper §4.8 accelerates 2:4 sparsity with Ampere sparse tensor cores.  TPUs
have no sparse MXU, so the transferable win is **HBM traffic** (DESIGN.md
§3): decode is memory-bound (arithmetic intensity ≈ batch), and the weight
stream dominates bytes.  This kernel streams the *compressed* representation
HBM→VMEM — ``keep/m`` of the dense values plus nibble-packed 4-bit in-group
indices — expands each tile to dense **inside VMEM** with per-slot selects
(VPU), and feeds the dense planes to the MXU.  Compute term unchanged;
memory term scales by ≈ (keep/m + index overhead).

Layout (slot-major, g = b/m groups, keep = m−n kept values per group;
owned by core/sparsity.pack_nm):
    values  (keep, c, g)  plane k = the k-th kept value of every group
    indices idx_bits=8 → (keep, c, g) int8 in-group positions ∈ [0, m)
            idx_bits=4 → (⌈keep/2⌉, c, g) int8: plane p holds slot 2p in
                         the low nibble and slot 2p+1 in the high nibble

Every operand plane is a lane-aligned (c, g) matrix, so the kernel never
reshapes across lanes.  The activations arrive split into their m strided
planes ``x_j = x[:, j::m]`` (m, B, g) — a cheap XLA transpose at decode
batch sizes — and the product is

    y = Σ_j x_j @ W_jᵀ,   W_j = Σ_k where(idx_k == j, val_k, 0)   (c, g)

where W_j is column j of every group: the same per-slot masked-select
expansion as ``kernels/ref.nm_expand``, contracted plane by plane.

Grid: (x_tiles, c_tiles, g_tiles) — g is the contraction dim, accumulated
in a fp32 VMEM scratch; the output tile is written once on the last g
step (standard Pallas accumulation pattern).  Every block is (8, 128)-
aligned or spans its whole dimension, and kernels/ops.choose_tiles keeps
the tile set inside the VMEM budget.  Compiles for TPU v5e at every
projection shape of h2o-danube-1.8b (tests/test_tpu_compile.py) and is
validated in interpret mode against ref.nm_matmul_ref
(tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.sparsity import nm_storage_shapes

Array = jax.Array


def _slot_index(idx_ref, k: int, idx_bits: int) -> Array:
    """In-group positions of kept slot k for the current tile, int32."""
    if idx_bits == 4:
        raw = idx_ref[k // 2].astype(jnp.int32)        # sign-extends
        return (raw >> (4 * (k % 2))) & 0xF
    return idx_ref[k].astype(jnp.int32)


def _nm_kernel(x_ref, val_ref, idx_ref, o_ref, acc_ref, *, m: int, keep: int,
               nsteps: int, idx_bits: int):
    """One (B_tile × c_tile) output tile; contraction step j over g tiles."""
    step = pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = [_slot_index(idx_ref, k, idx_bits) for k in range(keep)]
    acc = acc_ref[...]
    for j in range(m):
        # dense plane j (ct, gt): the kept value whose position is j, else 0
        w_j = jnp.zeros(idx[0].shape, jnp.float32)
        for k in range(keep):
            w_j = jnp.where(idx[k] == j, val_ref[k].astype(jnp.float32), w_j)
        x_j = x_ref[j]                                  # (Bt, gt)
        acc += jax.lax.dot_general(
            x_j, w_j.astype(x_j.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    acc_ref[...] = acc

    @pl.when(step == nsteps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n", "m", "b", "idx_bits", "block_b", "block_c",
                     "block_x", "interpret"),
)
def nm_matmul(
    x: Array,          # (B, b) activations
    values: Array,     # (keep, c, g)
    indices: Array,    # (keep, c, g) int8, or (⌈keep/2⌉, c, g) when idx_bits=4
    *,
    n: int,
    m: int,
    b: int,
    idx_bits: int = 8,
    block_b: int = 0,
    block_c: int = 0,
    block_x: int = 0,
    interpret: bool = False,
) -> Array:
    """y = x @ Wᵀ with W the n:m compressed (c, b) weight matrix.

    Tile sizes of 0 take the whole dimension; ``block_b`` counts dense
    columns (the kernel's contraction tile is ``block_b // m`` groups).
    """
    B = x.shape[0]
    keep = m - n
    g = b // m
    c = values.shape[1]
    vshape, ishape = nm_storage_shapes(c, b, n, m, idx_bits)
    assert b % m == 0 and values.shape == vshape, \
        f"bad compressed layout: {values.shape} for b={b} {n}:{m}"
    assert indices.shape == ishape, \
        f"bad index layout: {indices.shape} for idx_bits={idx_bits}"
    planes = ishape[0]

    bg = block_b // m if block_b else g
    bc = block_c or c
    bx = block_x or B
    assert g % bg == 0 and c % bc == 0 and B % bx == 0, (bg, bc, bx)
    nsteps = g // bg

    # the m strided activation planes x_j = x[:, j::m], lane-dense (m, B, g)
    xs = x.reshape(B, g, m).transpose(2, 0, 1)
    kernel = functools.partial(_nm_kernel, m=m, keep=keep, nsteps=nsteps,
                               idx_bits=idx_bits)
    return pl.pallas_call(
        kernel,
        grid=(B // bx, c // bc, nsteps),
        in_specs=[
            pl.BlockSpec((m, bx, bg), lambda i, k, j: (0, i, j)),
            pl.BlockSpec((keep, bc, bg), lambda i, k, j: (0, k, j)),
            pl.BlockSpec((planes, bc, bg), lambda i, k, j: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bx, bc), lambda i, k, j: (i, k)),
        out_shape=jax.ShapeDtypeStruct((B, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((bx, bc), jnp.float32)],
        interpret=interpret,
    )(xs, values, indices)
