"""Block-wise model pruning driver — the paper's Alg. 3.

Pruning is sequential over transformer blocks: for each block we (pass 1)
forward the calibration carries through it *capturing the input of every
prunable linear layer*, accumulate per-layer Hessians ``2XXᵀ``, prune every
linear independently, then (pass 2) re-forward through the *pruned* block to
produce the next block's inputs.  Exactly two forward passes per block.

Which cell prunes which layer is a ``PrunePlan`` (core/plan.py): every
param path resolves through the plan's ordered rules to a ``PruneConfig``
or to *skip* (the layer stays dense and its Hessian is freed).  Passing a
bare ``PruneConfig`` is the compat shim — it behaves bit-exactly like
``PrunePlan.uniform(cfg)``.

Models plug in via the ``BlockwiseAdapter`` protocol (implemented once,
generically, over the model zoo in models/adapter.py).
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable, Iterable, Mapping, Protocol

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro import obs
from repro.core.api import (PruneConfig, method_spec, prune_layer,  # noqa: F401
                            prune_layer_guarded)
from repro.core.hessian import HessianAccumulator
from repro.core.plan import LayerStat, PrunePlan, as_plan, path_str
from repro.faults import CalibrationError

Array = jax.Array
Path = tuple[Any, ...]


# --------------------------------------------------------------------------
# pytree path utilities (params are nested dicts)
# --------------------------------------------------------------------------
def get_path(tree, path: Path):
    for k in path:
        tree = tree[k]
    return tree


def set_path(tree, path: Path, value):
    """Functionally replace a leaf; shares all untouched subtrees.

    Integer path elements index the leading axis of a stacked array leaf
    (e.g. per-expert kernels (E, d_in, d_out) addressed as (..., 'w', e)).
    """
    if not path:
        return value
    head, rest = path[0], path[1:]
    if not isinstance(tree, dict):
        return tree.at[head].set(set_path(tree[head], rest, value))
    new = dict(tree)
    new[head] = set_path(tree[head], rest, value)
    return new


# --------------------------------------------------------------------------
# adapter protocol
# --------------------------------------------------------------------------
class BlockwiseAdapter(Protocol):
    """What a model must expose for Alg.-3 pruning."""

    def num_blocks(self, params) -> int: ...

    def prepare(self, params, batch) -> Any:
        """Embed a calibration batch; returns the carry entering block 0."""

    def block_apply(
        self, params, i: int, carry, *, capture: bool
    ) -> tuple[Any, dict[Path, Array]]:
        """Forward block i.  With capture=True also return {path: inputs}
        where inputs are (tokens, b) activations feeding each linear."""

    def block_linear_paths(self, params, i: int) -> list[Path]:
        """Prunable linear-layer param paths inside block i (kernels stored
        (in, out))."""


@dataclasses.dataclass
class LayerReport:
    path: Path
    sparsity: float
    obs_loss: float
    seconds: float
    rule: int = -1          # index of the PrunePlan rule that claimed it
    tag: str = ""           # resolved PruneConfig.tag(), or "skip"
    params: int = 0         # kernel parameter count (rollup weighting)
    skipped: bool = False   # True = rule said dense / no rule matched
    # numerical-guard provenance (core/api.prune_layer_guarded)
    damp_attempts: int = 0  # failed solve attempts before success/fallback
    percdamp_used: float = 0.0  # damping of the attempt that produced weights
    fallback: str = ""      # "magnitude" when on_singular fell back data-free
    calib_skipped: int = 0  # non-finite calibration batches the accumulator ate

    # journal-fragment serde: path element types (str vs int expert index)
    # survive exactly, unlike the display-oriented PruneReport.to_dict
    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["path"] = list(self.path)
        return d

    @classmethod
    def from_dict(cls, d: Mapping) -> "LayerReport":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - known
        if unknown:
            raise ValueError(f"unknown LayerReport keys {sorted(unknown)}; "
                             f"known: {sorted(known)}")
        d = dict(d)
        d["path"] = tuple(d["path"])
        return cls(**d)


@dataclasses.dataclass
class PruneReport:
    layers: list[LayerReport]
    masks: dict[Path, Array]
    seconds: float
    plan: PrunePlan | None = None

    def mean_sparsity(self) -> float:
        tot = sum(m.size for m in self.masks.values())
        ones = sum(float(jnp.sum(m)) for m in self.masks.values())
        return ones / max(tot, 1)

    def rule_rollup(self) -> list[dict]:
        """Per-rule attribution: which rule claimed which layers, with a
        size-weighted sparsity / summed-loss rollup.  Rule -1 collects
        layers no rule matched (skipped)."""
        by_rule: dict[int, list[LayerReport]] = {}
        for rep in self.layers:
            by_rule.setdefault(rep.rule, []).append(rep)
        out = []
        for idx in sorted(by_rule):
            reps = by_rule[idx]
            rule = (self.plan.rules[idx]
                    if self.plan is not None and 0 <= idx < len(self.plan.rules)
                    else None)
            size = sum(r.params for r in reps)
            out.append({
                "rule": idx,
                "match": rule.match if rule else None,
                "action": ("skip" if rule is None or rule.skip else "prune"),
                "tag": (rule.cfg.tag() if rule is not None
                        and rule.cfg is not None else "skip"),
                "layers": len(reps),
                "params": size,
                "mean_sparsity": (sum(r.params * r.sparsity for r in reps)
                                  / size if size else 0.0),
                "obs_loss": sum(r.obs_loss for r in reps),
                "seconds": sum(r.seconds for r in reps),
            })
        return out

    # ------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        """JSON-able artifact: the embedded plan makes the run reproducible
        (``PrunePlan.from_dict(report['plan'])``); masks are arrays and
        stay out."""
        return {
            "plan": None if self.plan is None else self.plan.to_dict(),
            "seconds": self.seconds,
            "mean_sparsity": self.mean_sparsity(),
            "rules": self.rule_rollup(),
            "layers": [{
                "path": path_str(r.path),
                "rule": r.rule,
                "tag": r.tag,
                "skipped": r.skipped,
                "sparsity": r.sparsity,
                "obs_loss": r.obs_loss,
                "params": r.params,
                "seconds": r.seconds,
                "damp_attempts": r.damp_attempts,
                "percdamp_used": r.percdamp_used,
                "fallback": r.fallback,
                "calib_skipped": r.calib_skipped,
            } for r in self.layers],
        }

    def to_json(self, *, indent: int | None = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    def save(self, path: str) -> None:
        """Crash-safe artifact write (tmp + ``os.replace``): a report file
        on disk is always a complete, parseable JSON document."""
        from repro.util.io import atomic_write_text

        atomic_write_text(path, self.to_json() + "\n")


def _accumulate(accs: dict, caps: dict, plan: PrunePlan | None = None,
                faults=None) -> None:
    """Add one calibration batch's captured inputs ``caps`` to the
    per-layer Hessian accumulators ``accs``, opening one per new layer;
    layers the ``plan`` leaves dense are not accumulated."""
    for path, x in caps.items():
        if plan is not None and path not in accs and \
                plan.cfg_for(path) is None:
            continue                     # skip rule: layer stays dense
        # MoE expert slices tape (activations, row-validity) pairs: only
        # routed capacity rows count as calibration samples
        valid = None
        if isinstance(x, tuple):
            x, valid = x
        if path not in accs:
            accs[path] = HessianAccumulator.init(x.shape[-1])
        if faults is not None and faults.fire("hessian_accum") is not None:
            # poisoned activations: the accumulator's non-finite guard
            # must swallow the batch, not the Hessian
            x = jnp.full_like(x, jnp.nan)
        accs[path] = accs[path].update(x, valid)


def _block_jits(adapter: BlockwiseAdapter):
    """Block ``i``'s forward, and its forward capturing every linear's
    inputs, jitted; a trace shows them as ``jit_prune_forward`` and
    ``jit_prune_capture``."""
    def prune_forward(p, c, i):
        return adapter.block_apply(p, i, c, capture=False)[0]

    def prune_capture(p, c, i):
        return adapter.block_apply(p, i, c, capture=True)

    return (jax.jit(prune_forward, static_argnums=(2,)),
            jax.jit(prune_capture, static_argnums=(2,)))


def prune_model(
    params,
    adapter: BlockwiseAdapter,
    batches: Iterable[Any],
    plan: "PrunePlan | PruneConfig",
    *,
    keep_masks: bool = True,
    progress: Callable[[str], None] | None = None,
    journal=None,
    faults=None,
    mesh=None,
    on_singular: str = "escalate",
    max_escalations: int = 4,
    min_calib_samples: int = 1,
) -> tuple[Any, PruneReport]:
    """Run Alg. 3 over the whole model.  Returns (pruned params, report).

    ``plan`` may be a ``PrunePlan`` (per-layer rules) or a bare
    ``PruneConfig`` (compat shim ≡ ``PrunePlan.uniform(cfg)``).

    Robustness plumbing (PR 8 — all default-off except the guards):

    * ``journal`` — a ``core.jobs.PruneJournal``: each completed layer is
      persisted (pruned kernel + mask + ``LayerReport`` fragment, atomic
      writes) as soon as it is solved, and layers already journaled are
      *loaded* instead of re-solved — forward passes replay (cheap,
      deterministic) so downstream Hessians and carries are bitwise those
      of an uninterrupted run.  Use via ``core.jobs.PruneJob``.
    * ``faults`` — an armed ``repro.faults.FaultPlan``; prune sites
      ``calib_batch`` / ``hessian_accum`` / ``cholesky`` / ``journal_write``
      fire here and in the guarded solve (zero cost unarmed).
    * ``mesh`` — route every layer solve through
      ``dist.prune.prune_layer_sharded`` on this mesh (escalation and
      magnitude fallback included).
    * ``on_singular`` — run-level numerical-failure policy; a rule's own
      ``on_singular`` overrides it per layer.  ``max_escalations`` bounds
      the percdamp ×10 retries.
    * ``min_calib_samples`` — a data-aware layer whose accumulator closed
      with fewer calibration tokens raises ``InsufficientCalibration``.
    """
    obs.install()
    with TraceAnnotation("prune.call"):
        plan = as_plan(plan)
        t_start = time.perf_counter()
        batches = list(batches)
        if plan.allocation is not None:
            # a recipe carrying an allocation block expands itself here:
            # one extra dense calibration pass collects the per-layer
            # Hessian-trace stats, and the *expanded* plan (allocation=None)
            # is what the report embeds — replaying the artifact reproduces
            # this run without re-running the allocation.
            plan = plan.allocate_sparsity(
                collect_hessian_stats(params, adapter, batches))
        carries = [adapter.prepare(params, b) for b in batches]

        solver = None
        if mesh is not None:
            from repro.dist.prune import prune_layer_sharded

            def solver(w, h, cfg):  # noqa: F811 — row-parallel layer solve
                return prune_layer_sharded(w, h, cfg, mesh)

        block_fwd, block_cap = _block_jits(adapter)

        reports: list[LayerReport] = []
        masks: dict[Path, Array] = {}
        # Hessian accumulators persist ACROSS blocks: weight-shared layers
        # (e.g. Zamba2's interleaved shared attention) are invoked at
        # several block indices and pruned once, at their last site, with
        # statistics accumulated over every invocation — the correct
        # treatment of weight sharing under objective Eq. 1.  Entries are
        # dropped once consumed.
        accs: dict[Path, HessianAccumulator] = {}
        ordinal = 0              # global sequential layer index (journal key)

        for i in range(adapter.num_blocks(params)):
            with TraceAnnotation("prune.block", block=i):
                # ---- pass 1: capture inputs, accumulate Hessians ---------
                # Runs on resume too: journaled blocks replay their
                # (deterministic) forwards so cross-block accumulators —
                # weight-shared layers — and next-block carries are bitwise
                # those of the original run.
                with TraceAnnotation("prune.capture", block=i):
                    for bi, carry in enumerate(carries):
                        if faults is not None and faults.fire(
                                "calib_batch", uid=i) is not None:
                            raise CalibrationError(
                                f"injected calibration failure (block {i}, "
                                f"batch {bi})", site="calib_batch")
                        _, caps = block_cap(params, carry, i)
                        with TraceAnnotation("prune.hessian_update"):
                            _accumulate(accs, caps, plan, faults)

                # ---- prune every linear in the block ----------------------
                for path in adapter.block_linear_paths(params, i):
                    if journal is not None and ordinal < journal.completed:
                        rec = journal.load(ordinal)
                        if tuple(rec.report.path) != tuple(path):
                            raise ValueError(
                                f"journal layer {ordinal} is "
                                f"{path_str(rec.report.path)!r}, expected "
                                f"{path_str(path)!r} — job dir belongs to a "
                                "different model/plan")
                        if not rec.report.skipped:
                            params = set_path(params, path, rec.kernel)
                            if keep_masks and rec.mask is not None:
                                masks[path] = rec.mask
                        accs.pop(path, None)
                        reports.append(rec.report)
                        ordinal += 1
                        if progress:
                            progress(f"block {i} {path_str(path)}: "
                                     f"journaled (layer {ordinal - 1})")
                        continue

                    new_kernel = mask_t = None
                    with TraceAnnotation("prune.linear", path=path_str(path)):
                        t0 = time.perf_counter()
                        kernel = get_path(params, path)      # (in, out)
                        rule_idx, cfg = plan.resolve(path)
                        if cfg is None:              # dense: skip + free H
                            accs.pop(path, None)
                            rep = LayerReport(
                                path=path, sparsity=0.0, obs_loss=0.0,
                                seconds=time.perf_counter() - t0,
                                rule=rule_idx, tag="skip",
                                params=int(kernel.size), skipped=True,
                            )
                        else:
                            acc = accs.get(path)
                            h = None
                            calib_skipped = 0
                            if acc is not None:
                                h = acc.finalize(min_count=(
                                    min_calib_samples
                                    if method_spec(cfg.method).data_aware
                                    else 0))
                                calib_skipped = int(float(acc.skipped))
                            pol = (plan.rules[rule_idx].on_singular
                                   if rule_idx >= 0 else "") or on_singular
                            res, guard = prune_layer_guarded(  # (out, in)
                                kernel.T, h, cfg, on_singular=pol,
                                max_escalations=max_escalations,
                                solver=solver, faults=faults,
                                path=path_str(path))
                            accs.pop(path, None)         # free the Hessian
                            new_kernel = res.weights.T.astype(kernel.dtype)
                            params = set_path(params, path, new_kernel)
                            mask_t = res.mask.T      # (in, out), 1.0 = pruned
                            if keep_masks:
                                masks[path] = mask_t
                            rep = LayerReport(
                                path=path,
                                sparsity=float(jnp.mean(res.mask)),
                                obs_loss=float(res.loss),
                                seconds=time.perf_counter() - t0,
                                rule=rule_idx,
                                tag=cfg.tag(),
                                params=int(kernel.size),
                                damp_attempts=guard.damp_attempts,
                                percdamp_used=guard.percdamp_used,
                                fallback=guard.fallback,
                                calib_skipped=calib_skipped,
                            )
                    if journal is not None:
                        journal.write(ordinal, rep, kernel=new_kernel,
                                      mask=mask_t, faults=faults)
                    reports.append(rep)
                    ordinal += 1
                    if progress:
                        progress(f"block {i} {path_str(path)}: " + (
                            f"skipped (rule {rule_idx})" if rep.skipped else
                            f"sparsity={rep.sparsity:.3f} "
                            f"loss={rep.obs_loss:.3e}"))

                # ---- pass 2: propagate through the pruned block -----------
                with TraceAnnotation("prune.propagate", block=i):
                    carries = [block_fwd(params, carry, i)
                               for carry in carries]

        return params, PruneReport(
            layers=reports, masks=masks,
            seconds=time.perf_counter() - t_start, plan=plan,
        )


def collect_hessian_stats(
    params,
    adapter: BlockwiseAdapter,
    batches: Iterable[Any],
) -> dict[str, LayerStat]:
    """One dense calibration pass → {path_str: LayerStat(size, trace)}.

    Runs Alg. 3's pass 1 (capture + Hessian accumulation) through the
    *unpruned* model and reduces each layer's Hessian to its mean diagonal
    mass tr(H)/b — the saliency proxy ``PrunePlan.allocate_sparsity``
    consumes.  No pruning, no weight mutation; one forward pass per block.
    """
    batches = list(batches)
    carries = [adapter.prepare(params, b) for b in batches]
    _, block_cap = _block_jits(adapter)
    stats: dict[str, LayerStat] = {}
    accs: dict[Path, HessianAccumulator] = {}
    for i in range(adapter.num_blocks(params)):
        next_carries = []
        for carry in carries:
            out, caps = block_cap(params, carry, i)
            next_carries.append(out)
            _accumulate(accs, caps)
        carries = next_carries
        for path in adapter.block_linear_paths(params, i):
            if path not in accs:
                continue
            h = accs.pop(path).finalize()
            kernel = get_path(params, path)
            stats[path_str(path)] = LayerStat(
                size=int(kernel.size),
                trace=float(jnp.trace(h)) / h.shape[0],
            )
    return stats
