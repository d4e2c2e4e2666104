"""Row-parallel distributed pruning (DESIGN.md §3).

The layer-wise OBS problem factorizes over rows of W: the Hessian
``H = 2XXᵀ`` lives on the *input* dimension and is identical for every row
(core/hessian.py, paper Eq. 34), so with H replicated each device can run
the full block-wise solve on its slice of rows with **zero inter-row
communication** — the only collective is a scalar psum of the per-shard
OBS losses.  This holds for all four methods (Thanos, SparseGPT, Wanda,
magnitude) and all sparsity patterns.

Mask-selection semantics under sharding:

* n:m and structured patterns are row-local (the n:m mask is chosen per
  m-group per row), so the sharded *mask* is bit-exact vs single-device
  for any shard count; the OBS-updated weights agree to float tolerance
  (XLA reassociates differently for different shard shapes).
* unstructured patterns have a **global** budget ⌊p·c·b⌋ allocated by one
  argsort across all rows; under row sharding each shard spends its own
  ⌊p·c_loc·b⌋, so realized sparsity is exact to within one budget-rounding
  per shard but mask *selection* can differ from the single-device argsort
  at shard boundaries.  On a degenerate 1×1 mesh (the CI contract —
  tests/test_serving_optimizations.py) every method/pattern is bit-exact.

Row counts the mesh does not divide fall back to coarser partitions
(model-only, data-only) and finally to replication — mirroring the
divisibility contract of dist/sharding.py — rather than padding, because
zero-padded rows would poison the unstructured budget.

Perf: this wrapper adds no solve code of its own — each shard runs the
exact single-device block loop (core/thanos.py, core/solver.py), so the
DESIGN.md §8 complexity budget (incremental trailing-inverse downdates,
single-solve OBS, sort-free mask selection) applies per shard verbatim.
>1-shard parity is exercised by ``python -m repro.launch.dryrun
--prune-parity`` on the 512-device placeholder backend.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.api import PruneConfig, prune_layer
from repro.core.hessian import HessianAccumulator
from repro.core.plan import PrunePlan
from repro.core.thanos import PruneResult
from repro.dist.sharding import _entry, _size, data_axes

Array = jax.Array


def row_partition(c: int, mesh: Mesh) -> tuple[str, ...]:
    """Largest mesh-axis group whose size divides the row count ``c``.

    Candidate groups (all axes, data-only, model-only) are tried in
    decreasing size — maximal parallelism wins — with () as the
    replicated fallback for row counts nothing divides.
    """
    dp = data_axes(mesh)
    tp = ("model",) if "model" in mesh.axis_names else ()
    groups = sorted((g for g in (dp + tp, dp, tp) if g),
                    key=lambda g: -_size(mesh, g))
    for axes in groups:
        if c % _size(mesh, axes) == 0:
            return axes
    return ()


def prune_layer_sharded(
    w: Array, h: Array | None, cfg: "PruneConfig | PrunePlan", mesh: Mesh,
    *, path: tuple | str = (),
) -> PruneResult:
    """Row-parallel ``prune_layer``: rows of W sharded over ``mesh``,
    Hessian replicated, per-row block-wise solves, loss psum'd.

    ``cfg`` may be a ``PrunePlan``: the layer's ``path`` resolves through
    the plan's rules to its cell, and a skip resolution returns the layer
    untouched (zero mask, zero loss) without entering the shard_map.

    Bit-exact with single-device ``prune_layer`` on a 1×1 mesh for every
    method and pattern; on the CPU n:m/structured masks stay bit-exact at
    any shard count (weights to float-reassociation tolerance).  On a v5e
    at h2o-danube-1.8b's widths each shard's result equals the single-
    device ``prune_layer`` of that shard's rows bit for bit, while the
    single-device prune of all rows, a program compiled for another row
    count, does not: its fp32 results differ in the last bits, and
    near-tie n:m choices can fall the other way (``chip_smoke.py
    --four-chips``).
    """
    if isinstance(cfg, PrunePlan):
        if cfg.allocation is not None:
            raise ValueError(
                "plan carries an unexpanded allocation block; expand it "
                "first (plan.allocate_sparsity(collect_hessian_stats(...)))"
                " — a single layer cannot run a model-level allocation")
        cfg = cfg.cfg_for(path)
        if cfg is None:                     # skip rule — layer stays dense
            import jax.numpy as jnp

            return PruneResult(w, jnp.zeros(w.shape, jnp.float32),
                               jnp.zeros((), jnp.float32))
    c = w.shape[0]
    axes = row_partition(c, mesh)
    rows = P(_entry(axes), None)

    if h is None:        # magnitude — keep the data-free contract of core
        if cfg.method != "magnitude":
            raise ValueError(f"{cfg.method} is data-aware: Hessian required")
        import jax.numpy as jnp

        h_arg = jnp.zeros((1, 1), jnp.float32)   # never read; shard_map
    else:                                        # needs an array operand
        h_arg = h

    def local(w_blk, h_full):
        res = prune_layer(w_blk, h_full if h is not None else None, cfg)
        loss = jax.lax.psum(res.loss, axes) if axes else res.loss
        return PruneResult(res.weights, res.mask, loss)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(rows, P(None, None)),
        out_specs=PruneResult(weights=rows, mask=rows, loss=P()),
        check_vma=False,
    )
    return fn(w, h_arg)


def hessian_all_reduce(acc, mesh: Mesh, axes: tuple[str, ...] = ("data",)):
    """Cross-replica calibration reduction so multi-host calibration
    composes with the sharded prune: the summed Hessian comes back
    replicated, which is exactly what the row-parallel solve needs.

    Per-replica partials must be *distinct values*, so ``acc`` leaves
    carry a leading replica axis of size prod(axes) — ``xtx`` (n, b, b),
    ``count`` (n,) — laid out over ``axes`` (in a multi-controller run,
    via ``jax.make_array_from_process_local_data``; in-process, via
    ``jnp.stack``).  A psum of an *unstacked* replicated array would just
    multiply it by the axis size (a single-controller ``jax.Array`` is
    one logical value, already globally summed), so unstacked input is
    returned unchanged.  Host-side alternatives: ``.psum`` inside an
    existing pmap/shard_map, or ``HessianAccumulator.combine``.
    """
    axes = tuple(a for a in axes if a in mesh.axis_names)
    n = _size(mesh, axes)
    stacked = acc.xtx.ndim == 3
    if stacked and acc.xtx.shape[0] != n:
        raise ValueError(
            f"leading replica axis {acc.xtx.shape[0]} != mesh axes size {n}")
    if not stacked:
        return acc                       # already a global (replicated) sum
    if n == 1:
        return HessianAccumulator(acc.xtx.sum(0), acc.count.sum(0),
                                  acc.skipped.sum(0))

    rep = P(_entry(axes))
    fn = jax.shard_map(
        lambda a: HessianAccumulator(
            jax.lax.psum(a.xtx[0], axes), jax.lax.psum(a.count[0], axes),
            jax.lax.psum(a.skipped[0], axes)),
        mesh=mesh,
        in_specs=(HessianAccumulator(
            xtx=P(_entry(axes), None, None), count=rep, skipped=rep),),
        out_specs=HessianAccumulator(xtx=P(None, None), count=P(),
                                     skipped=P()),
        check_vma=False,
    )
    return fn(acc)
