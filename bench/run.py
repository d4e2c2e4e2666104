#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up (set-up), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON
object as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones, read from a profiler trace of part of
the window), ``device`` and, last, ``compared``: each number that decided
``correct`` beside its limit, which also end standard error.

Exits 2, with no result, without a TPU, with fewer chips than the cell
asks for, or without the program (``src/repro``) beside this directory.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(ROOT))
    from bench import harness, peaks

    try:
        cell = harness.workload(args.workload)
    except (harness.SpecError, KeyError) as e:
        return fail(f"bad workload {args.workload!r}: {e}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program at {ROOT / 'src' / 'repro'}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    if jax.default_backend() != "tpu":
        return fail(f"needs a TPU, JAX found {jax.default_backend()!r}")
    if len(jax.devices()) < cell["chips"]:
        return fail(f"cell needs {cell['chips']} chips, JAX found "
                    f"{len(jax.devices())}")
    dev = harness.device_info()
    try:
        peak = peaks.peaks(dev["kind"])
    except KeyError as e:
        return fail(str(e))
    harness.enable_compile_cache()

    rec = harness.driver(cell["traffic"]["kind"]).run(
        cell, args.seed, args.seconds, bool(args.trace))
    rec["peak"] = peak
    metrics = {}
    for name in cell["per_layer"] if args.trace else cell["end_to_end"]:
        reader = harness.metric(name)
        value = reader.read(rec)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": reader.UNIT}
    device = {**dev, "memory_peak_bytes": rec["memory_peak_bytes"]}
    extra = {k: rec[k] for k in ("weights_s", "setup_compiles",
                                 "window_compiles", "check_s", "notes",
                                 "tokens_checked", "linears") if k in rec}
    breakdown = None
    if args.trace:
        tr = rec["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
        extra["programs"] = sorted(
            ([r["name"], r["count"], r["seconds"], r["loops"]]
             for r in tr["programs"].values()), key=lambda r: -r[2])[:6]
    harness.print_compared(rec["compared"])
    print(harness.result_line(
        correct=rec["correct"], attempted=rec["attempted"],
        failed=rec["failed"], metrics=metrics, device=device,
        compared=rec["compared"], breakdown=breakdown, extra=extra),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
