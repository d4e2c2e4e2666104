"""Pruning driver — the paper's Alg. 3 end-to-end over any zoo model.

    PYTHONPATH=src python -m repro.launch.prune \
        --arch tinyllama-1.1b --method thanos --pattern nm --n 2 --m 4

Runs: synthetic calibration → block-wise Hessian capture → per-layer pruning
→ held-out loss before/after (the perplexity-proxy comparison of Table 2).

Recipes: ``--plan recipe.json`` drives the whole run from a ``PrunePlan``
(per-layer rules, skip rules, optional sparsity allocation — DESIGN.md
§11).  Without a file, ``--skip GLOB`` / ``--mlp-pattern`` /
``--attn-pattern`` build a mixed plan from the base cell on the command
line; with none of those flags the run uses the bare-PruneConfig compat
shim (≡ ``PrunePlan.uniform``).  ``--method``/``--pattern`` choices come
straight from the ``core`` registry, so ``register_method`` extensions
appear here automatically.

Resilience (DESIGN.md §14): ``--job-dir DIR`` journals every completed
layer so a killed run restarts with ``--resume`` and produces bitwise the
same output; ``--on-singular`` picks the numerical-failure policy and
``--fault-plan`` arms deterministic fault injection (prune sites:
calib_batch, hessian_accum, cholesky, journal_write).
"""
from __future__ import annotations

import argparse
import json

import jax

from repro.configs import registry
from repro.core import (
    METHODS, ON_SINGULAR, PATTERNS, PruneConfig, PruneJob, PrunePlan,
    PruneRule, as_plan, prune_model,
)
from repro.data.pipeline import calibration_batches, heldout_loss
from repro.faults import FaultPlan
from repro.models.model_builder import build_model, ModelAdapter
from repro.util.compile_cache import enable_compile_cache

# transformer-family shorthand globs ('*' crosses '/'); moe covers both the
# stacked expert slices and the shared FFN
MLP_GLOBS = ("*/mlp/*", "*/moe/*")
ATTN_GLOBS = ("*/attn/*",)


def prune_arch(
    arch: str, plan: "PrunePlan | PruneConfig", *, reduced: bool = True,
    num_samples: int = 16, seq_len: int = 128, batch: int = 8,
    report_path: str = "", log=print, job_dir: str = "",
    resume: bool = False, on_singular: str = "escalate", faults=None,
):
    cfg = registry.get_config(arch, reduced=reduced)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    dense_loss = heldout_loss(model, params, cfg)

    batches = calibration_batches(
        cfg, num_samples=num_samples, seq_len=seq_len, batch=batch
    )
    adapter = ModelAdapter(model)
    if job_dir:
        # journaled supervision: layers persist as they complete, and a
        # killed run restarts with resume=True bitwise where it left off
        job = PruneJob(job_dir, on_singular=on_singular, faults=faults)
        pruned, report = job.run(params, adapter, batches, plan,
                                 resume=resume)
    else:
        # a recipe with an allocation block is expanded inside prune_model
        # (one extra dense calibration pass); report.plan is the expanded
        # plan
        pruned, report = prune_model(params, adapter, batches, plan,
                                     progress=None,
                                     on_singular=on_singular, faults=faults)
    pruned_loss = heldout_loss(model, pruned, cfg)
    out = {
        "arch": arch,
        "config": (plan.tag() if isinstance(plan, PruneConfig)
                   else f"plan[{len(as_plan(plan).rules)} rules]"),
        "dense_loss": dense_loss,
        "pruned_loss": pruned_loss,
        "delta": pruned_loss - dense_loss,
        "mean_sparsity": report.mean_sparsity(),
        "prune_seconds": report.seconds,
        "layers_pruned": sum(1 for r in report.layers if not r.skipped),
        "layers_skipped": sum(1 for r in report.layers if r.skipped),
        "rules": report.rule_rollup(),
    }
    if job_dir:
        out["job_dir"] = job_dir
    if report_path:
        report.save(report_path)        # atomic: never a torn artifact
        out["report"] = report_path
    if log:
        log(json.dumps(out, indent=1))
    return pruned, report, out


def build_plan(args) -> "PrunePlan | PruneConfig":
    """CLI flags → plan (or the bare-config compat shim).

    Precedence: ``--plan recipe.json`` wins outright.  Otherwise the base
    method/pattern/… flags define a catch-all cell; ``--skip`` globs
    prepend skip rules and ``--mlp-pattern``/``--attn-pattern`` prepend
    transformer-family rules that reuse the base cell's hyperparameters
    with a different sparsity pattern.  First match wins, so skips
    outrank the shorthands, which outrank the catch-all.
    """
    if args.plan:
        return PrunePlan.load(args.plan)

    def cell(pattern: str) -> PruneConfig:
        return PruneConfig(
            method=args.method, pattern=pattern, p=args.p,
            n=args.n, m=args.m, alpha=args.alpha, block_size=args.block_size,
        )

    base = cell(args.pattern)
    rules = [PruneRule(match=g, cfg=None, name="skip") for g in args.skip]
    if args.mlp_pattern:
        rules += [PruneRule(match=g, cfg=cell(args.mlp_pattern), name="mlp")
                  for g in MLP_GLOBS]
    if args.attn_pattern:
        rules += [PruneRule(match=g, cfg=cell(args.attn_pattern),
                            name="attn") for g in ATTN_GLOBS]
    if not rules:
        return base                     # compat shim: bare PruneConfig
    return PrunePlan(rules=(*rules, PruneRule(match="*", cfg=base)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=list(registry.ARCHS))
    # choices derive from the live registry (core.METHODS / core.PATTERNS):
    # third-party register_method() calls surface here with no CLI edits
    ap.add_argument("--method", default="thanos", choices=list(METHODS))
    ap.add_argument("--pattern", default="unstructured",
                    choices=list(PATTERNS))
    ap.add_argument("--p", type=float, default=0.5)
    ap.add_argument("--n", type=int, default=2)
    ap.add_argument("--m", type=int, default=4)
    ap.add_argument("--alpha", type=float, default=0.0)
    ap.add_argument("--block-size", type=int, default=64)
    ap.add_argument("--plan", default="",
                    help="PrunePlan recipe JSON (overrides the cell flags)")
    ap.add_argument("--skip", action="append", default=[], metavar="GLOB",
                    help="leave matching layers dense (repeatable; "
                         "prepended as skip rules)")
    ap.add_argument("--mlp-pattern", default="", choices=["", *PATTERNS],
                    help="sparsity pattern for MLP/MoE linears "
                         "(base cell hyperparameters)")
    ap.add_argument("--attn-pattern", default="", choices=["", *PATTERNS],
                    help="sparsity pattern for attention linears")
    ap.add_argument("--report", default="",
                    help="write the PruneReport JSON (embeds the plan) here")
    ap.add_argument("--full", action="store_true",
                    help="full config (needs real accelerators)")
    ap.add_argument("--job-dir", default="",
                    help="journal completed layers here; a killed run "
                         "restarts with --resume, bitwise identical")
    ap.add_argument("--resume", action="store_true",
                    help="continue the journaled job in --job-dir")
    ap.add_argument("--on-singular", default="escalate",
                    choices=list(ON_SINGULAR),
                    help="numerical-failure policy when a layer's Hessian "
                         "resists factorization")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault injection: JSON file or "
                         "compact specs like 'journal_write@2;cholesky@0'")
    args = ap.parse_args()

    if args.resume and not args.job_dir:
        ap.error("--resume requires --job-dir")
    faults = FaultPlan.load(args.fault_plan) if args.fault_plan else None
    plan = build_plan(args)
    enable_compile_cache()
    prune_arch(args.arch, plan, reduced=not args.full,
               report_path=args.report, job_dir=args.job_dir,
               resume=args.resume, on_singular=args.on_singular,
               faults=faults)


if __name__ == "__main__":
    main()
