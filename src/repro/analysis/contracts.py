"""Layer 2: abstract-eval contract sweep (zero FLOPs).

Drives ``jax.eval_shape`` over every config in ``configs/registry.py`` ×
the serve representations (dense, NmCompressed/NmStackedCompressed,
paged/contiguous caches) and checks the structural contracts the serving
stack assumes but runtime tests only probe pointwise:

* ``contract-decode-pos``      — decode accepts both ``()`` and ``(B,)``
                                 int32 positions; the registry's decode
                                 specs say so.
* ``contract-cache-geometry``  — ``init_cache`` leaves are batch-leading;
                                 ``decode_step`` returns a cache with the
                                 *identical* treedef/shapes/dtypes (the
                                 static-signature contract continuous
                                 batching relies on).
* ``contract-compressed-aux``  — compressed-leaf aux data is static and
                                 hashable (a jit cache key), values carry
                                 the model dtype, and compressed decode
                                 emits the same logits aval as dense.
* ``contract-paged-geometry``  — paged caches expose the page pool and
                                 survive a decode step structurally.
* ``contract-pspec-divides``   — every mesh axis a derived
                                 fsdp/param/cache PartitionSpec assigns
                                 actually divides that dim (the
                                 divisibility-fallback invariant).
* ``contract-recipe-drift``    — every committed n:m recipe still matches
                                 at least one linear path in the zoo.

Everything runs on ``AbstractMesh`` + ``ShapeDtypeStruct`` — no device
allocation, CPU-safe, whole-zoo sweep in seconds.
"""
from __future__ import annotations

import functools
import glob
import os
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.analysis.findings import Finding

_REG_PATH = "src/repro/configs/registry.py"
_B, _L = 4, 32                      # decode geometry for the sweep
_MESH = (("data", 2), ("model", 4))


def _finding(arch: str, rule: str, msg: str,
             path: str = _REG_PATH) -> Finding:
    return Finding(path=path, line=1, rule=rule, severity="error",
                   symbol=arch, message=msg)


def _leaves_with_paths(tree, prefix=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _leaves_with_paths(v, prefix + (k,))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _leaves_with_paths(v, prefix + (i,))
    else:
        yield prefix, tree


def _avals_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    ta = jax.tree.structure(a)
    tb = jax.tree.structure(b)
    if ta != tb or len(la) != len(lb):
        return False
    return all(x.shape == y.shape and x.dtype == y.dtype
               for x, y in zip(la, lb))


def _decode_args(cfg, a_params, a_cache, pos_shape):
    SDS = jax.ShapeDtypeStruct
    tok = SDS((_B, 1), jnp.int32)
    pos = SDS(pos_shape, jnp.int32)
    if cfg.family == "encdec":
        enc = SDS((_B, 64, cfg.d_model), cfg.jdtype)
        return (a_params, a_cache, tok, pos, enc)
    return (a_params, a_cache, tok, pos)


def _check_arch(arch: str, *, reduced: bool) -> list[Finding]:
    from repro.configs import registry
    from repro.configs.base import SHAPES, ShapeCell
    from repro.core.sparsity import NmCompressed, NmStackedCompressed
    from repro.launch.steps import abstract_nm_params, abstract_params
    from repro.models.model_builder import build_model

    out: list[Finding] = []
    cfg = registry.get_config(arch, reduced=reduced)
    model = build_model(cfg)

    # -- registry decode specs say pos is (B,) (or ()) int32 ---------------
    for cell in SHAPES.values():
        if cell.kind != "decode" or not registry.cell_supported(cfg, cell):
            continue
        spec = registry.decode_specs(cfg, cell)
        pos = spec.get("pos")
        if pos is None or pos.shape not in ((), (cell.global_batch,)) or \
                pos.dtype != jnp.int32:
            out.append(_finding(
                arch, "contract-decode-pos",
                f"registry.decode_specs[{cell.name}] pos is "
                f"{getattr(pos, 'shape', None)}/"
                f"{getattr(pos, 'dtype', None)} — contract is () or (B,) "
                "int32"))

    a_params = abstract_params(model)
    cell = ShapeCell("lint_decode", _L, _B, "decode")
    a_cache = jax.eval_shape(functools.partial(model.init_cache, _B, _L))

    # -- cache geometry: batch-leading leaves ------------------------------
    for path, leaf in _leaves_with_paths(a_cache):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] != _B:
            out.append(_finding(
                arch, "contract-cache-geometry",
                f"init_cache leaf {'/'.join(map(str, path))} has leading "
                f"dim {leaf.shape[0]} != batch {_B}"))

    # -- decode with vector pos; cache aval stability ----------------------
    try:
        logits, cache_out = jax.eval_shape(
            model.decode_step, *_decode_args(cfg, a_params, a_cache, (_B,)))
        if logits.shape != (_B, 1, cfg.vocab_size):
            out.append(_finding(
                arch, "contract-decode-pos",
                f"decode logits aval {logits.shape} != "
                f"({_B}, 1, {cfg.vocab_size})"))
        if not _avals_equal(cache_out, a_cache):
            out.append(_finding(
                arch, "contract-cache-geometry",
                "decode_step returned a cache whose treedef/shapes/dtypes "
                "differ from its input — decode signatures must be static "
                "across steps"))
    except Exception as e:  # noqa: BLE001 — any trace failure is drift
        out.append(_finding(
            arch, "contract-decode-pos",
            f"decode_step failed eval_shape with pos shape ({_B},): "
            f"{type(e).__name__}: {e}"))

    # -- compressed-leaf aux on the FULL config (real packing geometry) ----
    a_nm = abstract_nm_params(model, 2, 4)
    n_comp = 0
    for path, leaf in _leaves_with_paths(a_nm):
        if not isinstance(leaf, (NmCompressed, NmStackedCompressed)):
            continue
        n_comp += 1
        _children, aux = leaf.tree_flatten()
        try:
            hash(aux)
        except TypeError:
            out.append(_finding(
                arch, "contract-compressed-aux",
                f"compressed leaf {'/'.join(map(str, path))} aux {aux!r} "
                "is unhashable — it cannot serve as a jit cache key"))
        if leaf.values.dtype != cfg.jdtype:
            out.append(_finding(
                arch, "contract-compressed-aux",
                f"compressed leaf {'/'.join(map(str, path))} values dtype "
                f"{leaf.values.dtype} != model dtype {cfg.jdtype}"))
    if n_comp == 0:
        out.append(_finding(
            arch, "contract-compressed-aux",
            "abstract_nm_params(2, 4) produced zero compressed leaves — "
            "the arch has no compressible linears?"))

    # -- scalar-pos + compressed decode on the REDUCED config --------------
    # Both contracts are layer-count-invariant (same family code path,
    # same attention/MoE layout), so tracing the few-layer REDUCED config
    # keeps the whole-zoo sweep inside its CPU budget; everything
    # shape-specific above ran on the full config.
    out.extend(_check_reduced_decodes(arch))

    # -- paged cache (transformer families) --------------------------------
    if hasattr(model, "init_paged_cache"):
        num_pages, page_size, pps = 8, 8, _L // 8
        a_paged = jax.eval_shape(functools.partial(
            model.init_paged_cache, _B, num_pages=num_pages,
            page_size=page_size, pages_per_slot=pps))
        if not any(num_pages in getattr(leaf, "shape", ())
                   for _p, leaf in _leaves_with_paths(a_paged)):
            out.append(_finding(
                arch, "contract-paged-geometry",
                f"init_paged_cache exposes no leaf with a num_pages="
                f"{num_pages} pool dim"))
        try:
            _logits, paged_out = jax.eval_shape(
                model.decode_step,
                *_decode_args(cfg, a_params, a_paged, (_B,)))
            if not _avals_equal(paged_out, a_paged):
                out.append(_finding(
                    arch, "contract-paged-geometry",
                    "decode_step over the paged cache changed its "
                    "treedef/shapes/dtypes"))
        except Exception as e:  # noqa: BLE001
            out.append(_finding(
                arch, "contract-paged-geometry",
                f"decode_step failed eval_shape on the paged cache: "
                f"{type(e).__name__}: {e}"))

    # -- pspec divisibility -------------------------------------------------
    out.extend(_check_pspecs(arch, a_params, a_cache))
    return out


def _check_reduced_decodes(arch: str) -> list[Finding]:
    from repro.configs import registry
    from repro.launch.steps import abstract_nm_params, abstract_params
    from repro.models.model_builder import build_model

    out: list[Finding] = []
    cfg = registry.get_config(arch, reduced=True)
    model = build_model(cfg)
    a_params = abstract_params(model)
    a_cache = jax.eval_shape(functools.partial(model.init_cache, _B, _L))

    dense_logits = None
    for pos_shape in ((_B,), ()):
        try:
            dense_logits, _ = jax.eval_shape(
                model.decode_step,
                *_decode_args(cfg, a_params, a_cache, pos_shape))
        except Exception as e:  # noqa: BLE001
            out.append(_finding(
                arch, "contract-decode-pos",
                f"decode_step (reduced config) failed eval_shape with pos "
                f"shape {pos_shape}: {type(e).__name__}: {e} — the decode "
                "API contract is pos () or (B,) int32"))

    a_nm = abstract_nm_params(model, 2, 4)
    try:
        nm_logits, _ = jax.eval_shape(
            model.decode_step, *_decode_args(cfg, a_nm, a_cache, (_B,)))
        if dense_logits is not None and (
                nm_logits.shape != dense_logits.shape or
                nm_logits.dtype != dense_logits.dtype):
            out.append(_finding(
                arch, "contract-compressed-aux",
                f"compressed decode logits aval {nm_logits.shape}/"
                f"{nm_logits.dtype} != dense "
                f"{dense_logits.shape}/{dense_logits.dtype}"))
    except Exception as e:  # noqa: BLE001
        out.append(_finding(
            arch, "contract-compressed-aux",
            f"decode_step failed eval_shape on compressed params: "
            f"{type(e).__name__}: {e}"))
    return out


def _check_pspecs(arch: str, a_params, a_cache) -> list[Finding]:
    from repro.dist import sharding as D

    mesh = AbstractMesh(tuple(n for _, n in _MESH),
                        tuple(a for a, _ in _MESH))
    out: list[Finding] = []

    def check(tree, specs, what: str):
        leaves = dict(_leaves_with_paths(tree))
        for path, spec in _leaves_with_paths(
                specs):
            if not isinstance(spec, P):
                continue
            leaf = leaves.get(path)
            if leaf is None or not hasattr(leaf, "shape"):
                continue
            for dim, entry in enumerate(spec):
                if entry is None:
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                size = int(np.prod([mesh.shape[a] for a in axes]))
                if dim >= len(leaf.shape) or leaf.shape[dim] % size != 0:
                    out.append(_finding(
                        arch, "contract-pspec-divides",
                        f"{what} spec {spec} on leaf "
                        f"{'/'.join(map(str, path))} shape {leaf.shape}: "
                        f"axes {axes} (size {size}) do not divide dim "
                        f"{dim}", path="src/repro/dist/sharding.py"))

    check(a_params, D.param_pspecs(a_params, mesh), "param")
    check(a_params, D.fsdp_pspecs(a_params, mesh), "fsdp")
    check(a_cache, D.cache_pspecs(a_cache, mesh, _B), "cache")
    return out


def _check_recipes(root: str) -> list[Finding]:
    """Committed n:m recipes must still match linear paths in the zoo."""
    from repro.configs import registry
    from repro.core.plan import PrunePlan
    from repro.launch.steps import abstract_params
    from repro.models.model_builder import build_model

    recipe_dir = os.path.join(root, "examples", "recipes")
    recipes = sorted(glob.glob(os.path.join(recipe_dir, "*.json")))
    if not recipes:
        return []
    trees = {}
    for arch in ("tinyllama-1.1b", "qwen3-moe-30b-a3b"):
        model = build_model(registry.get_config(arch))
        a = abstract_params(model)
        paths = []
        for i in range(model.num_blocks()):
            paths.extend(model.block_linear_paths(a, i))
        trees[arch] = paths

    out: list[Finding] = []
    for path in recipes:
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            plan = PrunePlan.load(path)
        except Exception as e:  # noqa: BLE001
            out.append(Finding(
                path=rel, line=1, rule="contract-recipe-drift",
                severity="error", symbol="",
                message=f"recipe fails to load: {type(e).__name__}: {e}"))
            continue
        has_nm = any(
            r.cfg is not None and getattr(r.cfg, "pattern", None) == "nm"
            for r in getattr(plan, "rules", ()))
        if not has_nm:
            continue
        matched = any(
            (c := plan.cfg_for(p)) is not None and c.pattern == "nm"
            for paths in trees.values() for p in paths)
        if not matched:
            out.append(Finding(
                path=rel, line=1, rule="contract-recipe-drift",
                severity="error", symbol="",
                message="recipe's n:m rules match no linear path in the "
                        "zoo (tinyllama, qwen3-moe) — path patterns have "
                        "drifted"))
    return out


def run_contracts(archs: Iterable[str] | None = None, *,
                  reduced: bool = False,
                  repo_root: str | None = None) -> list[Finding]:
    from repro.configs import registry

    archs = tuple(archs) if archs is not None else registry.ARCHS
    findings: list[Finding] = []
    for arch in archs:
        try:
            findings.extend(_check_arch(arch, reduced=reduced))
        except Exception as e:  # noqa: BLE001 — sweep must report, not die
            findings.append(_finding(
                arch, "contract-sweep-error",
                f"contract sweep crashed: {type(e).__name__}: {e}"))
    if repo_root is not None:
        findings.extend(_check_recipes(repo_root))
    return sorted(findings)
