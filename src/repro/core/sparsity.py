"""n:m compressed weight format — the TPU serving artifact of §4.8.

On Ampere GPUs 2:4 sparsity feeds sparse tensor cores.  TPUs have no sparse
MXU, so the transferable win is **HBM traffic**: we store only the m−n kept
values per group plus their 4-bit in-group positions.  With two 4-bit
positions packed per int8 byte (the default), 2:4 bf16 costs
2×2 bytes values + 1 byte packed indices per 8 bytes dense = 62.5% of dense
bytes (50% + index overhead); for fp32 it is 56.25%.

Storage is **slot-major**: with g = b/m groups per row and keep = m−n
kept values per group, ``values`` is (keep, c, g) — plane k holds the k-th
kept value (ascending in-group position) of every group — and the indices
are the matching (keep, c, g) in-group positions, two slots per byte
(slot 2p low nibble, slot 2p+1 high nibble) for 4-bit storage.  Every
plane is a lane-aligned (c, g) matrix, which is what lets the Pallas kernel
expand tiles without in-kernel reshapes (kernels/nm_spmm.py).

``NmCompressed`` is the on-disk/LHS format consumed by
``kernels/nm_spmm.py`` and the serving decode path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

Array = jax.Array

# Kernel names the serve path consumes as *reshaped raw weights* rather
# than through the NmCompressed-aware ``layers.dense`` dispatch.  MLA's
# absorbed decode (models/attention.py mla_decode) reshapes wkv_b into
# (dkv, H, dn+dv) and contracts it inside einsums — there is no x @ w to
# stream the compressed form through, so packing it can never serve.
# compress_params treats these paths as a residency downgrade (the layer
# stays dense); abstract_nm_params mirrors that in the abstract tree.
NON_STREAMABLE_KERNELS = frozenset({"wkv_b"})


def nm_storage_shapes(c: int, b: int, n: int, m: int,
                      idx_bits: int = 4) -> tuple[tuple, tuple]:
    """(values, indices) shapes of one packed (c, b) n:m matrix."""
    keep, g = m - n, b // m
    planes = (keep + 1) // 2 if idx_bits == 4 else keep
    return (keep, c, g), (planes, c, g)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class NmCompressed:
    """Pytree container for n:m-compressed weights.

    (n, m, b, idx_bits) are static aux data, so NmCompressed flows through
    jit / eval_shape / sharding machinery with only ``values``/``indices``
    traced.

    ``idx_bits`` selects the index storage: 8 = one in-group position per
    int8 byte (the debugging-friendly layout); 4 = two slots per byte,
    low nibble first (the serving layout — requires m ≤ 16).
    """

    values: Array    # (keep, c, b // m) kept weights, slot-major
    indices: Array   # int8 in-group positions; (keep, c, b // m) for
                     # idx_bits=8, (ceil(keep/2), c, b // m) nibble-packed
                     # slot pairs for idx_bits=4
    n: int
    m: int
    b: int           # original column count
    idx_bits: int = 4

    @property
    def kept_per_group(self) -> int:
        return self.m - self.n

    def unpacked_indices(self) -> Array:
        """int8 (keep, c, g) in-group positions regardless of idx_bits."""
        if self.idx_bits == 4:
            return unpack_indices4(self.indices, self.kept_per_group)
        return self.indices

    def tree_flatten(self):
        return (self.values, self.indices), (self.n, self.m, self.b,
                                             self.idx_bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


def pack_indices4(idx: Array) -> Array:
    """Pack int8 slot planes (keep, ...), values ∈ [0, 16), two per byte.

    Byte plane p holds slot 2p (low nibble) and slot 2p+1 (high nibble); an
    odd ``keep`` leaves the final high nibble zero.  → (⌈keep/2⌉, ...) int8,
    every plane keeping the (c, g) shape of a value plane.
    """
    u = idx.astype(jnp.uint8)
    lo = u[0::2]
    hi = u[1::2]
    if hi.shape[0] < lo.shape[0]:
        hi = jnp.concatenate([hi, jnp.zeros_like(lo[:1])], axis=0)
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_indices4(packed: Array, keep: int) -> Array:
    """Inverse of pack_indices4 — (⌈keep/2⌉, ...) bytes → (keep, ...) int8."""
    raw = packed.astype(jnp.int32)            # sign-extends; masked below
    lo = raw & 0xF
    hi = (raw >> 4) & 0xF
    both = jnp.stack([lo, hi], axis=1)        # (planes, 2, ...)
    both = both.reshape((-1,) + packed.shape[1:])
    return both[:keep].astype(jnp.int8)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class NmStackedCompressed:
    """Pytree container for E stacked n:m-compressed expert slices.

    The MoE analogue of :class:`NmCompressed`: one leaf holds every expert
    of a stacked ``(E, in, out)`` kernel in compressed form, so expert
    weights stay packed through jit / eval_shape / sharding machinery and
    the serving engine — a single ``NmCompressed`` cannot live *inside* an
    array leaf, but one stacked container can *replace* it.

    Every expert keeps its **own** mask (indices differ per slice); the
    ``(n, m)`` cell is shared across the stack — per-expert cells would
    make the layout ragged.  ``(n, m, b, E, idx_bits)`` are static aux
    data; only ``values``/``indices`` are traced.
    """

    values: Array    # (E, keep, c, b // m) kept weights, slot-major
    indices: Array   # int8 in-group positions; (E, keep, c, g) for
                     # idx_bits=8, (E, ⌈keep/2⌉, c, g) nibble-packed for 4
    n: int
    m: int
    b: int           # original column count (per expert)
    E: int           # number of stacked expert slices
    idx_bits: int = 4

    @property
    def kept_per_group(self) -> int:
        return self.m - self.n

    def unpacked_indices(self) -> Array:
        """int8 (E, keep, c, g) in-group positions regardless of idx_bits."""
        if self.idx_bits == 4:
            keep = self.kept_per_group
            return jax.vmap(lambda i: unpack_indices4(i, keep))(self.indices)
        return self.indices

    def tree_flatten(self):
        return (self.values, self.indices), (self.n, self.m, self.b,
                                             self.E, self.idx_bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)


def pack_nm(w: Array, mask: Array, n: int, m: int, *,
            idx_bits: int = 4) -> NmCompressed:
    """Compress an n:m-masked matrix (mask 1.0 = pruned).

    Every m-group must contain exactly n ones in ``mask``; validated by
    tests (core.masks.check_nm) rather than at trace time.  Kept positions
    are stored in ascending in-group order, slot k of every group in value
    plane k (slot-major, see the module docstring).
    """
    assert idx_bits in (4, 8), idx_bits
    assert idx_bits == 8 or m <= 16, f"4-bit indices need m ≤ 16, got {m}"
    c, b = w.shape
    keep = m - n
    g = b // m
    mk = (mask <= 0.5).reshape(c, g, m)                    # True = kept
    # stable order: kept positions first within each group
    key = jnp.where(mk, jnp.arange(m)[None, None, :], m + jnp.arange(m)[None, None, :])
    order = jnp.argsort(key, axis=-1)[..., :keep]          # (c, g, keep)
    vals = jnp.take_along_axis(w.reshape(c, g, m), order, axis=-1)
    idx8 = jnp.moveaxis(order.astype(jnp.int8), -1, 0)     # (keep, c, g)
    return NmCompressed(
        values=jnp.moveaxis(vals, -1, 0),
        indices=pack_indices4(idx8) if idx_bits == 4 else idx8,
        n=n, m=m, b=b, idx_bits=idx_bits,
    )


def unpack_nm(packed: NmCompressed) -> Array:
    """Decompress to dense (c, b) — the pure-jnp oracle for the kernel.

    A gather-free in-group scatter: each kept value lands at its stored
    position, untouched positions stay zero (no fp32 one-hot contraction).
    """
    c = packed.values.shape[1]
    g = packed.b // packed.m
    vals = jnp.moveaxis(packed.values, 0, -1)                # (c, g, keep)
    idx = jnp.moveaxis(packed.unpacked_indices(), 0, -1).astype(jnp.int32)
    dense = jnp.zeros((c, g, packed.m), packed.values.dtype)
    dense = dense.at[
        jnp.arange(c)[:, None, None], jnp.arange(g)[None, :, None], idx
    ].set(vals, unique_indices=True)
    return dense.reshape(c, packed.b)


def pack_nm_stacked(w: Array, mask: Array, n: int, m: int, *,
                    idx_bits: int = 4) -> NmStackedCompressed:
    """Compress E stacked n:m-masked expert slices (mask 1.0 = pruned).

    ``w``/``mask`` are (E, c, b) paper layout per expert; the per-slice
    packing is exactly :func:`pack_nm` vmapped over the expert axis, so
    expert e of the stacked container is bitwise ``pack_nm(w[e], mask[e])``.
    """
    assert w.ndim == 3, f"need stacked (E, c, b) weights, got {w.shape}"
    assert w.shape == mask.shape, (w.shape, mask.shape)
    per = jax.vmap(lambda we, me: pack_nm(we, me, n, m, idx_bits=idx_bits))(
        w, mask)
    return NmStackedCompressed(
        values=per.values, indices=per.indices,
        n=n, m=m, b=w.shape[-1], E=w.shape[0], idx_bits=idx_bits,
    )


def unpack_nm_stacked(packed: NmStackedCompressed) -> Array:
    """Decompress to dense (E, c, b) — the pure-jnp oracle for the stacked
    kernel path (``unpack_nm`` vmapped over the expert axis)."""
    def one(v, i):
        return unpack_nm(NmCompressed(v, i, packed.n, packed.m, packed.b,
                                      packed.idx_bits))

    return jax.vmap(one)(packed.values, packed.indices)


def compression_ratio(packed: "NmCompressed | NmStackedCompressed") -> float:
    """HBM bytes(compressed) / bytes(dense) — drives the §Roofline memory term."""
    val_bytes = packed.values.size * packed.values.dtype.itemsize
    idx_bytes = packed.indices.size  # int8 bytes (4-bit packing: 2 idx/byte)
    c = packed.values.shape[-2]
    experts = packed.E if isinstance(packed, NmStackedCompressed) else 1
    dense_bytes = experts * c * packed.b * packed.values.dtype.itemsize
    return (val_bytes + idx_bytes) / dense_bytes
