"""The prune job through ``repro.core.schedule.prune_model``.

Each call prunes the first ``blocks`` blocks of the model, built with only
those blocks (the later ones take no part in pruning them), with the
calibration set of the traffic file: ``calib_sequences`` sequences of
``seq_len`` Zipf-distributed token ids from the seed, in batches of
``batch``.  Set-up makes the weights and the calibration set on the device
and runs one whole call; the window then runs whole calls back to back
until ``--seconds`` have passed, and the call in flight completes.  A
call is timed until the device has run all it dispatched: ``prune_model``
returns with the last block's propagation still queued.  One call of the
window, drawn from the seed, is kept for the check.
"""
from __future__ import annotations

import gc
import shutil
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, costs, harness, trace
from bench import weights as W


def calibration(t: dict, cfg: dict, seed: int) -> np.ndarray:
    return W.tokens(seed, 2, (t["calib_sequences"], t["seq_len"]),
                    cfg["vocab_size"], zipf_a=t["zipf_a"])


def plan(t: dict):
    from repro.core import PruneConfig, PrunePlan, PruneRule

    cell = PruneConfig(method=t["method"], pattern=t["pattern"], n=t["n"],
                       m=t["m"], block_size=t["block_size"],
                       percdamp=t["percdamp"])
    rules = [PruneRule(match=f"blocks/{i}/*", cfg=cell, name=f"block{i}",
                       on_singular=t["on_singular"])
             for i in range(t["blocks"])]
    return PrunePlan(rules=(*rules, PruneRule(match="*", name="skip")))


def drain() -> None:
    """Wait until the device has run everything dispatched so far: a
    trivial program queued behind it on the device completes only after
    it.  Without this a call's unfinished tail falls into the next call's
    time, or, after a call kept for the check, into none."""
    jax.block_until_ready(jnp.zeros((), jnp.float32) + 1.0)


def call_ok(report, t: dict) -> bool:
    """Every linear of the pruned blocks solved cleanly at the cell's
    sparsity, with no guard event."""
    pruned = [r for r in report.layers if not r.skipped]
    return (len(pruned) == 7 * t["blocks"] and all(
        not (r.damp_attempts or r.fallback or r.calib_skipped)
        and r.sparsity == t["n"] / t["m"] for r in pruned))


def run(cell: dict, seed: int, seconds: float, traced: bool,
        control: bool = False) -> dict:
    """One run; ``control`` also reads the fp8 control (bench/limits.py,
    never the benchmark's own runs)."""
    from repro.core import prune_model
    from repro.core.schedule import get_path
    from repro.models.model_builder import ModelAdapter, build_model

    t, cfg = cell["traffic"], cell["config"]
    ref = harness.reference(cfg["reference"])
    counter = harness.CompileCounter()
    rec: dict = {"seconds": seconds, "blocks": t["blocks"]}
    t_setup = time.perf_counter()
    model = build_model(harness.model_config(cfg, num_layers=t["blocks"]))
    params = W.nest(W.make(seed, W.TOP, ref.top_leaves(cfg), cfg["dtype"]))
    params["blocks"] = {
        i: W.nest(W.make(seed, i, ref.block_leaves(cfg, i), cfg["dtype"]))
        for i in range(t["blocks"])}
    tokens = calibration(t, cfg, seed)
    batches = [{"tokens": jnp.asarray(tokens[i:i + t["batch"]])}
               for i in range(0, len(tokens), t["batch"])]
    adapter, the_plan = ModelAdapter(model), plan(t)
    rec["weights_s"] = time.perf_counter() - t_setup

    def one_call():
        return prune_model(params, adapter, batches, the_plan,
                           on_singular=t["on_singular"])

    _, report = one_call()
    drain()
    warm_ok = call_ok(report, t)
    del report
    rec["setup_s"] = time.perf_counter() - t_setup
    rec["setup_compiles"] = counter.fresh()

    # ---- the window --------------------------------------------------------
    c0 = counter.fresh()
    log_dir = str(harness.ROOT / ".bench_traces" / f"{cell['name']}-{seed}")
    rng = np.random.default_rng([int(seed), 4])
    calls, kept, ok = [], None, []
    t0 = time.perf_counter()
    while not calls or time.perf_counter() - t0 < seconds:
        first_traced = traced and not calls
        if first_traced:
            shutil.rmtree(log_dir, ignore_errors=True)
            jax.profiler.start_trace(log_dir, profiler_options=trace.options())
            ann = jax.profiler.TraceAnnotation(trace.WINDOW)
            ann.__enter__()
        c_start = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.prune_call"):
            pruned, report = one_call()
            drain()
        calls.append({"wall_s": time.perf_counter() - c_start,
                      "solve_s": sum(r.seconds for r in report.layers
                                     if not r.skipped)})
        if first_traced:
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            rec["traced_call"] = calls[-1]
        ok.append(call_ok(report, t))
        if rng.random() < 1.0 / len(calls):      # one call, uniformly drawn
            kept = {"/".join(r.path[2:]): {
                "w": np.asarray(get_path(pruned, r.path)).T,
                "mask": np.asarray(report.masks[r.path]).T,
                "loss": r.obs_loss} for r in report.layers
                if not r.skipped and r.path[1] == 0}
        del pruned, report
    rec["window_s"] = time.perf_counter() - t0
    rec["window_compiles"] = counter.fresh() - c0
    rec["memory_peak_bytes"] = harness.memory_peak_bytes(1)
    rec["calls"] = calls
    rec["attempted"], rec["failed"] = len(calls), ok.count(False)
    rec["shape"] = costs.Shape.of(cfg)
    rec["job_ops"] = costs.prune_block_ops(
        rec["shape"], t["calib_sequences"], t["seq_len"], t["block_size"],
        t["n"], t["m"])["total"] * t["blocks"]
    if traced:
        rec["trace"] = trace.reduce(trace.load(trace.latest_xplane(log_dir)))
        shutil.rmtree(log_dir, ignore_errors=True)

    # ---- correctness, once the program's state is freed ---------------------
    del params, batches, adapter, model
    gc.collect()
    t_check = time.perf_counter()
    reference = check.prune_reference(cfg, seed, 0, tokens, t)
    rows = check.compare_prune(kept, reference, t["n"], t["m"],
                               t["block_size"])
    rec["linears"] = rows
    rec["compared"] = compared(rows, cell["limits"])
    rec["check_s"] = time.perf_counter() - t_check
    if control:
        quant = check.prune_reference(cfg, seed, 0, tokens, t, quant=True)
        rec["control_linears"] = check.compare_prune(
            {k: {"w": np.asarray(v["w"]), "mask": np.asarray(v["mask"]),
                 "loss": v["loss"]} for k, v in quant["linears"].items()},
            reference, t["n"], t["m"], t["block_size"])
        rec["control"] = compared(rec["control_linears"], cell["limits"])
    rec["correct"] = (warm_ok and all(ok)
                      and all(c["ok"] for c in rec["compared"].values()))
    return rec


def compared(rows: dict, lim: dict) -> dict:
    """The numbers that decide ``correct``, over the block's linears: the
    n:m pattern, exactly; the mean share of mask entries that differ from
    the reference's; the median linear's relative error of its first
    column block's weights.  Means and medians, because a near-tie that
    rounds the other way flips every later choice of its row: a single
    linear's reading scatters from seed to seed, the block's does not."""
    return {
        **harness.compare("nm_pattern_errors",
                          sum(r["pattern_errors"] for r in rows.values()),
                          lim["nm_pattern_errors"]),
        **harness.compare("mask_disagreement",
                          sum(1.0 - r["mask_agreement"]
                              for r in rows.values()) / len(rows),
                          lim["mask_disagreement"]),
        **harness.compare("first_block_err", statistics.median(
            r["first_block_err"] for r in rows.values()),
            lim["first_block_err"]),
    }
