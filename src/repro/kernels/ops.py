"""Public jit'd wrappers over the Pallas kernels, with backend dispatch.

On CPU (this container) ``impl='auto'`` resolves to the pure-jnp reference —
XLA fuses the in-group scatter + dot well, and running the Pallas kernel
body as interpreted Python per decode step would be pure overhead.  On TPU
``'auto'`` lowers the Pallas kernel through Mosaic with the declared
BlockSpecs.  Callers can force either path (``impl='ref'`` / ``'pallas'``;
'pallas' off-TPU runs in interpret mode — bit-faithful, test-only speed).

``NmKernelConfig`` is the serving-side knob bundle: the engine threads it
from ``ServeConfig`` through ``model_builder`` into ``layers.dense`` so the
compressed matmul impl and tile sizes are chosen per deployment, not
hardcoded at the layer.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.sparsity import (NmCompressed, NmStackedCompressed,
                                 nm_storage_shapes)
from repro.kernels import nm_spmm, hessian_accum, ref

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class NmKernelConfig:
    """How ``layers.dense`` runs an NmCompressed matmul.

    impl: 'auto' (pallas on TPU, ref elsewhere) | 'ref' | 'pallas'.
    block_b/block_c/block_x: Pallas tile overrides; 0 = shape-keyed
    ``choose_tiles`` defaults.  Hashable/static so it can parameterize
    jitted call sites.
    """

    impl: str = "auto"
    block_b: int = 0
    block_c: int = 0
    block_x: int = 0


@functools.cache
def _interpret() -> bool:
    """Backend probe, hoisted: one ``jax.default_backend()`` query per
    process instead of one per nm_matmul/hessian_xtx call."""
    return jax.default_backend() != "tpu"


def _resolve_impl(impl: str) -> str:
    if impl in ("auto", ""):
        return "ref" if _interpret() else "pallas"
    return impl


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


# Scoped-VMEM budget for one n:m kernel instance.  Mosaic's default scoped
# limit is 16 MiB on v5e; the margin covers what the estimate below leaves
# out (semaphores, internal scratch).
VMEM_BUDGET = 12 * 2**20


def nm_vmem_bytes(bx: int, bc: int, bg: int, m: int, keep: int,
                  idx_bits: int, x_bytes: int, w_bytes: int) -> int:
    """Scoped VMEM of one kernel instance with (bx, bc, bg) tiles.

    Double-buffered input/output blocks plus the in-kernel fp32/int32
    temporaries per (c, g) tile element: one decoded index plane per kept
    slot and the dense plane under construction (fp32 and MXU dtype).
    """
    _, (planes, _, _) = nm_storage_shapes(bc, bg * m, m - keep, m, idx_bits)
    weight = bc * bg * (2 * (keep * w_bytes + planes) + 4 * (keep + 3))
    acts = 2 * m * bx * bg * x_bytes
    out = bx * bc * (4 + 2 * x_bytes)
    return weight + acts + out


def choose_tiles(B: int, c: int, b: int, m: int, keep: int,
                 idx_bits: int = 4, x_bytes: int = 4,
                 w_bytes: int = 4) -> dict:
    """Pallas tile sizes for an (B, b) × (c, b)ᵀ n:m matmul.

    Every block the kernel declares is (8, 128)-aligned or spans its whole
    dimension, and the set fits ``VMEM_BUDGET``:

    * contraction (g = b/m groups on lanes): the whole of g when it fits,
      else the largest 128-multiple divisor — the compressed layout fixes
      b, so it is never padded (at b = 6912, g = 1728 has no such divisor
      and is taken whole);
    * rows c (output lanes): the largest 128-multiple divisor of c that
      fits, or c whole when c is below 1024; other c are zero-padded by the
      wrapper to 128-row tiles;
    * batch B (sublanes): whole up to 128 rows, else 128-row tiles (padded);
      halved in multiples of 8 only if nothing else fits.

    Tiles are preferred in that order of size: a larger batch tile re-reads
    the weights fewer times, which is what decode is bound by.
    """
    g = b // m
    bx0 = B if B <= 128 else 128
    bxs = [bx0] + [v for v in (64, 32, 16, 8) if v < bx0]
    bgs = [g] + [v for v in (1024, 512, 256, 128) if v < g and g % v == 0]
    bcs = sorted({v for v in range(128, min(c, 2048) + 1, 128) if c % v == 0}
                 | ({c} if c < 1024 else set()), reverse=True) or [128]
    for bx in bxs:
        for bg in bgs:
            for bc in bcs:
                if nm_vmem_bytes(bx, bc, bg, m, keep, idx_bits, x_bytes,
                                 w_bytes) <= VMEM_BUDGET:
                    return {"block_b": bg * m, "block_c": bc, "block_x": bx}
    return {"block_b": bgs[-1] * m, "block_c": bcs[-1], "block_x": bxs[-1]}


def nm_matmul(x: Array, packed: NmCompressed, *, impl: str = "",
              cfg: NmKernelConfig | None = None, block_b: int = 0,
              block_c: int = 0, block_x: int = 0) -> Array:
    """y = x @ Wᵀ for n:m compressed W (c, b); x (..., b) → y (..., c).

    Shapes the tiles do not divide (c off the 128 grid, B beyond one
    batch tile) are zero-padded for the Pallas path and sliced back — zero
    rows cost nothing and zero activations contribute nothing.
    """
    cfg = cfg if cfg is not None else NmKernelConfig()
    use = _resolve_impl(impl or cfg.impl)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if use == "ref":
        y = ref.nm_matmul_ref(
            x2, packed.values, packed.indices, packed.n, packed.m, packed.b,
            packed.idx_bits,
        )
        return y.reshape(*lead, -1)

    keep = packed.kept_per_group
    c = packed.values.shape[1]
    B = x2.shape[0]
    tiles = choose_tiles(B, c, packed.b, packed.m, keep, packed.idx_bits,
                         x2.dtype.itemsize, packed.values.dtype.itemsize)
    for name, override in (("block_b", block_b or cfg.block_b),
                           ("block_c", block_c or cfg.block_c),
                           ("block_x", block_x or cfg.block_x)):
        if override:
            tiles[name] = override

    c_pad = _round_up(c, tiles["block_c"]) - c
    b_pad = _round_up(B, tiles["block_x"]) - B
    values, indices = packed.values, packed.indices
    if c_pad:
        values = jnp.pad(values, ((0, 0), (0, c_pad), (0, 0)))
        indices = jnp.pad(indices, ((0, 0), (0, c_pad), (0, 0)))
    if b_pad:
        x2 = jnp.pad(x2, ((0, b_pad), (0, 0)))

    y = nm_spmm.nm_matmul(
        x2, values, indices,
        n=packed.n, m=packed.m, b=packed.b, idx_bits=packed.idx_bits,
        interpret=_interpret(), **tiles,
    )
    return y[:B, :c].reshape(*lead, -1)


def nm_matmul_stacked(x: Array, packed: NmStackedCompressed, *,
                      impl: str = "", cfg: NmKernelConfig | None = None,
                      block_b: int = 0, block_c: int = 0,
                      block_x: int = 0) -> Array:
    """Batched expert matmul over one stacked compressed leaf:
    x (E, C, b) → y (E, C, c) with y[e] = x[e] @ W_eᵀ.

    The MoE dispatch entry for ``layers.stacked_dense`` — the active
    ``NmKernelConfig`` (``layers.nm_kernel_scope``) picks the impl exactly
    as for 2-D leaves.  'ref' runs the vmapped masked-select expansion +
    one batched dot; 'pallas' launches the 2-D Pallas kernel once per
    expert slice (static E — each launch pads/tiles like the unstacked
    path, sharing ``choose_tiles``).
    """
    cfg = cfg if cfg is not None else NmKernelConfig()
    use = _resolve_impl(impl or cfg.impl)
    if use == "ref":
        return ref.nm_matmul_stacked_ref(
            x, packed.values, packed.indices, packed.n, packed.m, packed.b,
            packed.idx_bits,
        )
    outs = [
        nm_matmul(
            x[e],
            NmCompressed(packed.values[e], packed.indices[e], packed.n,
                         packed.m, packed.b, packed.idx_bits),
            impl=use, cfg=cfg, block_b=block_b, block_c=block_c,
            block_x=block_x,
        )
        for e in range(packed.E)
    ]
    return jnp.stack(outs)


def hessian_xtx(x: Array, *, impl: str = "pallas", **tiles) -> Array:
    """H = 2·XᵀX for token-major activations x (..., b)."""
    x2 = x.reshape(-1, x.shape[-1])
    if impl == "ref":
        return ref.hessian_ref(x2)
    return hessian_accum.hessian_xtx(x2, interpret=_interpret(), **tiles)
