"""Tracked serving benchmark → BENCH_serve.json (repo root).

Measures the decode hot path dense vs **compressed-resident** (the engine
keeps NmCompressed leaves; kernels/ops.nm_matmul consumes them in-graph)
across (model-dim, m, batch): decode tokens/s and streamed weight bytes per
step.  A third variant re-times the compressed path through the *legacy
one-hot* expansion (the pre-rework ref formulation, kept here as the
baseline) so the scatter-rework speedup is a tracked number — the ratio is
reported in DESIGN.md §9.

``--trace`` adds the **mixed-length Poisson-arrival serving trace**:
the same request trace (compressed-resident params) served end-to-end by
the continuous slot-level scheduler vs the legacy wave scheduler —
tokens/s, time-to-first-token and slot occupancy per scheduler, with a
cross-check that per-uid outputs are identical (DESIGN.md §10).  Arrivals
tick in *virtual time* (engine work units: 1/decode step, S/prefill), so
the arrival pattern is machine-independent; tokens/s and TTFT are wall
clock with a full untimed warm-up pass first.

    python -m benchmarks.bench_serve --quick --trace    # CI artifact run
    python -m benchmarks.bench_serve --trace            # full grid

Protocol (same as ``benchmarks/common.timeit``): one untimed warm-up call
compiles the jitted decode_step and is fully ``block_until_ready``'d, then
every timed iteration blocks on the result — median wall seconds per decode
step, compile excluded.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __package__ is None or __package__ == "":          # direct invocation
    sys.path.insert(0, _ROOT)
try:
    import repro  # noqa: F401 — installed or on PYTHONPATH
except ModuleNotFoundError:                           # source checkout
    sys.path.insert(0, os.path.join(_ROOT, "src"))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timeit
from repro.configs.base import ModelConfig
from repro.core import PruneConfig, prune_model
from repro.core.sparsity import unpack_indices4
from repro.data.pipeline import calibration_batches
from repro.kernels.ops import NmKernelConfig
from repro.models import layers as L
from repro.models.model_builder import ModelAdapter, build_model
from repro.serve.compressed import compress_params, compressed_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (d_model, m, batch) — quick keeps one d=128 cell: d=64 sits at the CPU
# timing noise floor (DESIGN.md §9), so the CI artifact needs d≥128 to be
# meaningful for the nm_ref-vs-onehot gate
QUICK_GRID = [(64, 4, 4), (128, 4, 8)]
FULL_GRID = [(d, m, B)
             for d in (64, 128, 256)
             for m in (4, 8)
             for B in (1, 8)]


def bench_config(d: int) -> ModelConfig:
    return ModelConfig(
        name=f"bench-{d}", family="dense", num_layers=2, d_model=d,
        num_heads=4, num_kv_heads=4, head_dim=d // 4, d_ff=2 * d,
        vocab_size=512, dtype="float32")


def moe_bench_config(d: int) -> ModelConfig:
    """MoE sibling of ``bench_config``: 8 experts top-2, expert d_ff=d/2 —
    expert stacks dominate the weight bytes, as in real MoE configs."""
    return ModelConfig(
        name=f"bench-moe-{d}", family="moe", num_layers=2, d_model=d,
        num_heads=4, num_kv_heads=4, head_dim=d // 4, d_ff=0,
        vocab_size=512, num_experts=8, num_experts_per_tok=2,
        moe_d_ff=d // 2, capacity_factor=4.0, dtype="float32")


def _onehot_matmul(x, values, indices, n, m, b, idx_bits=8):
    """The pre-rework ref formulation: fp32 one-hot expansion — O(m/keep)×
    extra FLOPs and a (c, g, keep, m) fp32 intermediate.  Benchmark-only."""
    if idx_bits == 4:
        indices = unpack_indices4(indices, m - n)
    vals = jnp.moveaxis(values, 0, -1).astype(jnp.float32)   # (c, g, keep)
    idx = jnp.moveaxis(indices, 0, -1).astype(jnp.int32)
    c = vals.shape[0]
    onehot = idx[..., None] == jnp.arange(m)[None, None, None, :]
    dense = jnp.sum(vals[..., None] * onehot, axis=2).reshape(c, b)
    return (x.astype(jnp.float32) @ dense.T).astype(x.dtype)


def _decode_seconds(model, params, B: int, *, nm_cfg=None, warmup=1,
                    iters=5) -> float:
    cache = model.init_cache(B, 64)
    tokens = jnp.zeros((B, 1), jnp.int32)
    step = jax.jit(model.decode_step)            # fresh jit per variant
    with L.nm_kernel_scope(nm_cfg):
        return timeit(lambda: step(params, cache, tokens, 8),
                      warmup=warmup, iters=iters)


def _param_bytes(params) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(params)
               if hasattr(l, "dtype"))


def run_grid(grid, *, warmup=1, iters=5, verbose=True) -> list[dict]:
    import repro.kernels.ref as ref_mod

    rows = []
    for d, m, B in grid:
        n = m // 2
        cfg = bench_config(d)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batches = calibration_batches(cfg, num_samples=4, seq_len=16, batch=4)
        pruned, report = prune_model(
            params, ModelAdapter(model), batches,
            PruneConfig(method="magnitude", pattern="nm", n=n, m=m))
        comp = compress_params(pruned, report.masks, n, m)
        cbytes, dbytes = compressed_bytes(comp)
        total_dense = _param_bytes(pruned)
        streamed_comp = total_dense - dbytes + cbytes

        t_dense = _decode_seconds(model, pruned, B, warmup=warmup,
                                  iters=iters)
        t_ref = _decode_seconds(model, comp, B,
                                nm_cfg=NmKernelConfig(impl="ref"),
                                warmup=warmup, iters=iters)
        orig = ref_mod.nm_matmul_ref
        ref_mod.nm_matmul_ref = _onehot_matmul
        try:
            t_onehot = _decode_seconds(model, comp, B,
                                       nm_cfg=NmKernelConfig(impl="ref"),
                                       warmup=warmup, iters=iters)
        finally:
            ref_mod.nm_matmul_ref = orig

        for variant, t, streamed in (
                ("dense", t_dense, total_dense),
                ("nm_ref", t_ref, streamed_comp),
                ("nm_onehot", t_onehot, streamed_comp)):
            rows.append({
                "variant": variant, "d_model": d, "n": n, "m": m, "batch": B,
                "seconds_per_step": t, "tokens_per_s": B / t,
                "streamed_weight_bytes": streamed,
                "weight_bytes_ratio": streamed / total_dense,
            })
        if verbose:
            print(f"d={d:4d} {n}:{m} B={B}: dense {t_dense*1e3:7.2f} ms  "
                  f"nm_ref {t_ref*1e3:7.2f} ms  "
                  f"nm_onehot {t_onehot*1e3:7.2f} ms  "
                  f"(scatter vs one-hot {t_onehot / t_ref:.2f}x, "
                  f"bytes {streamed_comp / total_dense:.3f} of dense)",
                  flush=True)
    return rows


def run_moe(*, d: int, B: int, warmup=1, iters=5, verbose=True) -> list[dict]:
    """MoE decode: dense expert stacks vs stacked-nm compressed-resident
    (``NmStackedCompressed`` leaves through layers.stacked_dense — the
    per-expert container that ends the experts-silently-serve-dense gap).
    Same protocol as ``run_grid``; expert + attn linears all pack 2:4."""
    from repro.core.sparsity import NmStackedCompressed

    cfg = moe_bench_config(d)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batches = calibration_batches(cfg, num_samples=4, seq_len=16, batch=4)
    pruned, report = prune_model(
        params, ModelAdapter(model), batches,
        PruneConfig(method="magnitude", pattern="nm", n=2, m=4))
    comp = compress_params(pruned, report.masks, 2, 4)
    stacked = [l for l in jax.tree.leaves(
        comp, is_leaf=lambda x: isinstance(x, NmStackedCompressed))
        if isinstance(l, NmStackedCompressed)]
    assert stacked, "MoE bench must serve stacked-compressed expert leaves"
    cbytes, dbytes = compressed_bytes(comp)
    total_dense = _param_bytes(pruned)
    streamed_comp = total_dense - dbytes + cbytes

    t_dense = _decode_seconds(model, pruned, B, warmup=warmup, iters=iters)
    t_ref = _decode_seconds(model, comp, B,
                            nm_cfg=NmKernelConfig(impl="ref"),
                            warmup=warmup, iters=iters)
    rows = []
    for variant, t, streamed in (("moe_dense", t_dense, total_dense),
                                 ("moe_nm_ref", t_ref, streamed_comp)):
        rows.append({
            "variant": variant, "d_model": d, "n": 2, "m": 4, "batch": B,
            "num_experts": cfg.num_experts,
            "experts_per_tok": cfg.num_experts_per_tok,
            "stacked_leaves": len(stacked),
            "seconds_per_step": t, "tokens_per_s": B / t,
            "streamed_weight_bytes": streamed,
            "weight_bytes_ratio": streamed / total_dense,
        })
    if verbose:
        print(f"moe d={d:4d} 2:4 B={B} E={cfg.num_experts}: "
              f"dense {t_dense*1e3:7.2f} ms  "
              f"stacked_nm {t_ref*1e3:7.2f} ms  "
              f"(bytes {streamed_comp / total_dense:.3f} of dense, "
              f"{len(stacked)} stacked leaves)", flush=True)
    return rows


# --------------------------------------------------------------------------
# mixed-length Poisson-arrival serving trace (continuous vs wave)
# --------------------------------------------------------------------------
TRACE_LENS = (4, 6, 8, 12)        # bucketed prompt lengths (bounded compiles)


MAX_NEW_MIX = ((4, 6, 8, 48), (0.4, 0.3, 0.2, 0.1))   # heavy-tailed decode


def make_arrival_trace(seed: int, n: int, vocab: int,
                       *, lam: float = 2.0) -> list[dict]:
    """Deterministic mixed-length trace with Poisson arrivals in virtual
    time (engine work units), so the pattern is machine-independent.

    ``max_new`` is heavy-tailed (mostly short, ~10% long) — the production
    mix where wave batching's lockstep-to-the-longest hurts most; ``lam``
    keeps the system loaded so slots are contended."""
    rng = np.random.default_rng(seed)
    arrival = 0
    trace = []
    for uid in range(n):
        trace.append({
            "uid": uid,
            "prompt": rng.integers(
                0, vocab, size=int(rng.choice(TRACE_LENS))).astype(np.int32),
            "max_new": int(rng.choice(MAX_NEW_MIX[0], p=MAX_NEW_MIX[1])),
            "arrival": arrival,
        })
        arrival += int(rng.poisson(lam))
    return trace


def _drive_trace(runner, trace) -> tuple[float, list]:
    """Submit requests as virtual time passes; drain; → (wall_s, requests).

    ``runner`` is a ServingEngine or a Supervisor wrapping one (same
    submit/pump/idle/run surface); virtual time lives on the engine either
    way.  Under a supervisor, read results from ``runner.results()`` — the
    returned Request objects can be stale after a rollback (the engine
    continues on internal clones)."""
    from repro.serve import Request

    engine = getattr(runner, "engine", runner)
    reqs = [Request(t["uid"], t["prompt"], max_new=t["max_new"])
            for t in trace]
    i = 0
    t0 = time.perf_counter()
    while i < len(reqs) or not runner.idle():
        while i < len(reqs) and trace[i]["arrival"] <= engine.stats["vtime"]:
            runner.submit(reqs[i])
            i += 1
        if not runner.pump():
            if i >= len(reqs):
                break
            # idle with future arrivals: fast-forward the virtual clock
            engine.stats["vtime"] = trace[i]["arrival"]
    runner.run()                       # drain bookkeeping (already idle)
    return time.perf_counter() - t0, reqs


TRACE_PAGE_SIZE = 16


def _trace_setup(d: int, n_requests: int, slots: int, seed: int):
    """Shared fixture for the trace benchmarks: compressed-resident params,
    the Poisson arrival trace, and the (contiguous, paged) geometries."""
    cfg = bench_config(d)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batches = calibration_batches(cfg, num_samples=4, seq_len=16, batch=4)
    pruned, report = prune_model(
        params, ModelAdapter(model), batches,
        PruneConfig(method="magnitude", pattern="nm", n=2, m=4))
    comp = compress_params(pruned, report.masks, 2, 4)
    trace = make_arrival_trace(seed, n_requests, cfg.vocab_size)
    max_len = max(TRACE_LENS) + max(MAX_NEW_MIX[0]) + 2

    ps = TRACE_PAGE_SIZE
    paged_max_len = max_len + (-max_len) % ps          # round up to pages
    pps = paged_max_len // ps
    # two pages short of full residency: faults/COW/preemption run for real
    num_pages = max(1 + pps, 1 + slots * pps - 2)
    return model, comp, trace, max_len, paged_max_len, num_pages


def run_trace(*, d: int, n_requests: int, slots: int, seed: int = 0,
              reps: int = 3, verbose=True) -> list[dict]:
    """Serve one trace with both schedulers on compressed-resident params,
    plus the paged KV engine (continuous scheduler, page-pool cache) on a
    deliberately constrained pool — the trace's total context exceeds the
    contiguous ``slots × max_len`` capacity, so paging is load-bearing, not
    decorative.  All three must agree per-uid (greedy bit-parity)."""
    from repro.serve import ServeConfig, ServingEngine

    model, comp, trace, max_len, paged_max_len, num_pages = _trace_setup(
        d, n_requests, slots, seed)
    total_context = sum(len(t["prompt"]) + t["max_new"] for t in trace)
    ps = TRACE_PAGE_SIZE

    def make_engine(variant):
        paged = variant == "paged"
        return ServingEngine(
            model, comp,
            ServeConfig(
                batch_slots=slots,
                max_len=paged_max_len if paged else max_len,
                scheduler="continuous" if paged else variant,
                paged=paged, page_size=ps,
                num_pages=num_pages if paged else 0))

    variants = ("continuous", "wave", "paged")
    for variant in variants:                   # untimed warm-up/compile pass
        _drive_trace(make_engine(variant), trace)

    rows, outs = [], {}
    for variant in variants:
        paged = variant == "paged"
        runs = []                 # median-of-reps (same protocol as timeit)
        for _ in range(max(1, reps)):
            eng = make_engine(variant)
            runs.append((_drive_trace(eng, trace), eng))
        runs.sort(key=lambda r: r[0][0])
        (wall, reqs), eng = runs[len(runs) // 2]
        st = eng.stats
        tokens = sum(len(r.out) for r in reqs)
        # t_first < 0 ⇒ never scheduled (bug this sweep fixes: such
        # requests used to silently vanish from the TTFT stats — and an
        # all-unserved run crashed np.mean on an empty list)
        ttfts = [r.t_first - r.t_submit for r in reqs if r.t_first >= 0]
        unserved = sum(1 for r in reqs if r.t_first < 0)
        outs[variant] = {r.uid: list(r.out) for r in reqs}
        row = {
            "variant": f"trace_{variant}",
            "d_model": d, "batch_slots": slots, "requests": n_requests,
            "trace_seed": seed,
            "wall_s": wall,
            "tokens_per_s": tokens / wall,
            "requests_per_s": n_requests / wall,
            "unserved_requests": unserved,
            "ttft_mean_s": float(np.mean(ttfts)) if ttfts else None,
            "ttft_p90_s": (float(np.quantile(ttfts, 0.9))
                           if ttfts else None),
            "ttft_p99_s": (float(np.quantile(ttfts, 0.99))
                           if ttfts else None),
            "decode_steps": st["decode_steps"],
            "slot_occupancy": (st["busy_slot_steps"]
                               / max(1, st["decode_steps"] * slots)),
        }
        if paged:
            row.update({
                "page_size": ps, "num_pages": num_pages,
                "cache_capacity_tokens": (num_pages - 1) * ps,
                "contiguous_capacity_tokens": slots * max_len,
                "trace_total_context_tokens": total_context,
                "pages_hwm": st["pages_hwm"],
                "page_faults": st["page_faults"],
                "cow_copies": st["cow_copies"],
                "prefix_hit_tokens": st["prefix_hit_tokens"],
                "preemptions": st["preemptions"],
            })
        rows.append(row)
    assert outs["continuous"] == outs["wave"] == outs["paged"], \
        "schedulers disagree on per-uid outputs"
    if verbose:
        c, w, p = rows
        print(f"trace d={d} slots={slots} n={n_requests} "
              f"(context {total_context} tok > contiguous "
              f"{slots * max_len} tok):", flush=True)
        for r in (c, w, p):
            ttft = (f"{r['ttft_mean_s']*1e3:6.1f}"
                    if r["ttft_mean_s"] is not None else "   n/a")
            print(f"  {r['variant']:18s} {r['tokens_per_s']:7.1f} tok/s  "
                  f"ttft {ttft} ms  unserved {r['unserved_requests']}",
                  flush=True)
        print(f"  paged: hwm {p['pages_hwm']}/{num_pages - 1} pages, "
              f"{p['page_faults']} faults, {p['cow_copies']} COW, "
              f"{p['preemptions']} preemptions  "
              f"(paged/continuous {p['tokens_per_s']/c['tokens_per_s']:.2f}x)",
              flush=True)
    return rows


# --------------------------------------------------------------------------
# chaos: the same paged trace under a fixed seeded fault plan
# --------------------------------------------------------------------------
# ≥3 fault types mid-trace: two NaN-logit decode steps, one admission OOM,
# and a pool-exhaustion burst long enough (2×slots) to defeat the engine's
# preempt-retry loop and escape to the supervisor twice
CHAOS_PLAN = "decode_logits@25;decode_logits@70;prefill@5;pager_fault_in@40x8"


def run_chaos(*, d: int, n_requests: int, slots: int, seed: int = 0,
              reps: int = 3, verbose=True) -> list[dict]:
    """Serve the Poisson trace on the supervised paged engine under the
    fixed ``CHAOS_PLAN`` fault schedule: every fault recovers by rollback +
    replay, zero requests are dropped or quarantined, and per-uid outputs
    stay **bitwise identical** to the fault-free run (asserted, not
    sampled).  Reported goodput is delivered tokens over wall time; the
    waste column counts decode steps discarded by rollbacks."""
    from repro.serve import (FaultPlan, ServeConfig, ServingEngine,
                             Supervisor, SupervisorConfig)

    model, comp, trace, _, paged_max_len, num_pages = _trace_setup(
        d, n_requests, slots, seed)

    def make_engine():
        return ServingEngine(
            model, comp,
            ServeConfig(batch_slots=slots, max_len=paged_max_len,
                        scheduler="continuous", paged=True,
                        page_size=TRACE_PAGE_SIZE, num_pages=num_pages))

    # fault-free oracle (also the untimed compile warm-up)
    _, oracle_reqs = _drive_trace(make_engine(), trace)
    oracle = {r.uid: list(r.out) for r in oracle_reqs}
    delivered_tokens = sum(len(o) for o in oracle.values())

    runs = []                     # median-of-reps (same protocol as timeit)
    for _ in range(max(1, reps)):
        plan = FaultPlan.parse(CHAOS_PLAN, seed=seed)
        sup = Supervisor(
            make_engine(),
            SupervisorConfig(snapshot_every=8, retry_budget=10),
            faults=plan)
        wall, _ = _drive_trace(sup, trace)
        results = {r.uid: list(r.out) for r in sup.results()}
        fired = plan.fired_by_site()
        assert len(fired) >= 3, f"chaos plan only fired {fired}"
        assert sup.quarantined == [], "chaos trace must not quarantine"
        assert results == oracle, \
            "post-recovery outputs diverged from the fault-free trace"
        runs.append((wall, sup, fired))
    runs.sort(key=lambda r: r[0])
    wall, sup, fired = runs[len(runs) // 2]
    st = sup.engine.stats
    sst = sup.stats
    row = {
        "variant": "trace_chaos",
        "d_model": d, "batch_slots": slots, "requests": n_requests,
        "trace_seed": seed, "fault_plan": CHAOS_PLAN,
        "wall_s": wall,
        "tokens_per_s": delivered_tokens / wall,
        "goodput_tokens_per_s": delivered_tokens / wall,
        "requests_per_s": n_requests / wall,
        "dropped_requests": n_requests - len(oracle),
        "quarantined": sst["quarantined"],
        "recoveries": sst["recoveries"],
        "faults_by_type": dict(sst["faults"]),
        "fired_by_site": fired,
        "decode_steps": st["decode_steps"],
        "wasted_decode_steps": sst["rollback_decode_steps"],
        "goodput_step_fraction": (
            1.0 - sst["rollback_decode_steps"] / max(1, st["decode_steps"])),
        "replayed_requests": sst["replayed_requests"],
        "snapshots": sst["snapshots"],
        "outputs_identical_to_fault_free": True,     # asserted above
    }
    if verbose:
        print(f"chaos d={d} slots={slots} n={n_requests} "
              f"plan '{CHAOS_PLAN}':", flush=True)
        print(f"  trace_chaos        {row['tokens_per_s']:7.1f} tok/s "
              f"goodput  ({row['recoveries']} recoveries, "
              f"{row['wasted_decode_steps']}/{row['decode_steps']} steps "
              f"rolled back, {row['replayed_requests']} replays, "
              f"0 dropped)", flush=True)
    return [row]


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=ROOT,
            capture_output=True, text=True, timeout=10,
        ).stdout.strip()
    except Exception:
        return "unknown"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="single small cell (CI artifact run)")
    ap.add_argument("--trace", action="store_true",
                    help="add the mixed-length Poisson-arrival serving "
                         "trace (continuous vs wave scheduler)")
    ap.add_argument("--chaos", action="store_true",
                    help="add the supervised paged trace under the fixed "
                         "CHAOS_PLAN fault schedule (goodput + recovery "
                         "accounting; outputs asserted bitwise equal to "
                         "the fault-free run)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--out", default="",
                    help="output path; defaults to repo-root BENCH_serve.json"
                         " (full grid) or BENCH_serve.quick.json (--quick, so"
                         " a quick run never clobbers the committed full-grid"
                         " perf-gate baseline)")
    args = ap.parse_args()
    if not args.out:
        name = "BENCH_serve.quick.json" if args.quick else "BENCH_serve.json"
        args.out = os.path.join(ROOT, name)

    grid = QUICK_GRID if args.quick else FULL_GRID
    rows = run_grid(grid, warmup=args.warmup, iters=args.iters)
    moe_rows = (run_moe(d=64, B=4, warmup=args.warmup, iters=args.iters)
                if args.quick else
                run_moe(d=128, B=8, warmup=args.warmup, iters=args.iters))
    rows.extend(moe_rows)

    trace_rows: list[dict] = []
    if args.trace:
        trace_rows = (run_trace(d=64, n_requests=16, slots=4) if args.quick
                      else run_trace(d=128, n_requests=32, slots=4))

    chaos_rows: list[dict] = []
    if args.chaos:
        chaos_rows = (run_chaos(d=64, n_requests=16, slots=4) if args.quick
                      else run_chaos(d=128, n_requests=32, slots=4))

    by_key: dict[tuple, dict] = {}
    for r in rows:
        by_key[(r["d_model"], r["m"], r["batch"], r["variant"])] = r
    speedups = {}
    for d, m, B in grid:
        ref = by_key[(d, m, B, "nm_ref")]["seconds_per_step"]
        oh = by_key[(d, m, B, "nm_onehot")]["seconds_per_step"]
        speedups[f"{d}/{m}/{B}"] = oh / ref

    record = {
        "meta": {
            "git": _git_rev(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "platform": platform.platform(),
            "python": platform.python_version(),
            "jax": jax.__version__,
            "device": str(jax.devices()[0]),
            "quick": args.quick,
            "protocol": "median wall s/decode step, warmed-up + "
                        "block_until_ready; compressed-resident via "
                        "layers.nm_kernel_scope",
        },
        "results": rows,
        "scatter_vs_onehot_speedup": speedups,
        "scatter_vs_onehot_median": float(np.median(list(speedups.values()))),
    }
    moe_dense = next(r for r in moe_rows if r["variant"] == "moe_dense")
    moe_nm = next(r for r in moe_rows if r["variant"] == "moe_nm_ref")
    record["moe"] = {
        "d_model": moe_dense["d_model"],
        "stacked_leaves": moe_nm["stacked_leaves"],
        "stacked_vs_dense_step_ratio": (
            moe_nm["seconds_per_step"] / moe_dense["seconds_per_step"]),
        "weight_bytes_ratio": moe_nm["weight_bytes_ratio"],
    }
    if trace_rows:
        cont = next(r for r in trace_rows
                    if r["variant"] == "trace_continuous")
        wave = next(r for r in trace_rows if r["variant"] == "trace_wave")
        paged = next(r for r in trace_rows if r["variant"] == "trace_paged")
        record["results"].extend(trace_rows)
        record["trace"] = {
            "tokens_per_s_speedup": cont["tokens_per_s"]
            / wave["tokens_per_s"],
            "ttft_mean_ratio": wave["ttft_mean_s"] / cont["ttft_mean_s"],
            "ttft_p90_ratio": wave["ttft_p90_s"] / cont["ttft_p90_s"],
            "occupancy": {"continuous": cont["slot_occupancy"],
                          "wave": wave["slot_occupancy"]},
            "outputs_identical_per_uid": True,   # asserted in run_trace
            "paged_vs_contiguous_tokens_per_s": (
                paged["tokens_per_s"] / cont["tokens_per_s"]),
            "paged": {k: paged[k] for k in (
                "requests_per_s", "ttft_p99_s", "unserved_requests",
                "pages_hwm", "page_faults", "cow_copies", "preemptions",
                "cache_capacity_tokens", "contiguous_capacity_tokens",
                "trace_total_context_tokens")},
        }
    if chaos_rows:
        (chaos,) = chaos_rows
        record["results"].extend(chaos_rows)
        record["chaos"] = {
            "fault_plan": chaos["fault_plan"],
            "goodput_tokens_per_s": chaos["goodput_tokens_per_s"],
            "goodput_step_fraction": chaos["goodput_step_fraction"],
            "recoveries": chaos["recoveries"],
            "dropped_requests": chaos["dropped_requests"],
            "quarantined": chaos["quarantined"],
            "outputs_identical_to_fault_free": True,
        }
        if trace_rows:
            paged = next(r for r in trace_rows
                         if r["variant"] == "trace_paged")
            record["chaos"]["chaos_vs_paged_tokens_per_s"] = (
                chaos["tokens_per_s"] / paged["tokens_per_s"])
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    print(f"\nwrote {args.out} ({len(rows)} rows; scatter vs one-hot median "
          f"{record['scatter_vs_onehot_median']:.2f}x)")


if __name__ == "__main__":
    main()
