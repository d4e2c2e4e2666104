#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, on the chip.

    python bench/limits.py --workload <name> --seeds <a>-<b> \\
        [--control-seeds <c>-<d>] [--seconds 15] [--out FILE]

Runs the cell once per seed in this one process (set-up, a short window at
the cell's own load, the check) and prints, per seed, each compared number:
the program's (for the lower reading) and, on the control seeds, the fp8
control's (for the upper one).  The benchmark's own runs never run the
control.  Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def seed_range(text: str) -> list[int]:
    if not text:
        return []
    a, _, b = text.partition("-")
    return list(range(int(a), int(b or a) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import harness

    if jax.default_backend() != "tpu":
        print("bench/limits.py: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = harness.workload(args.workload)
    drv = harness.driver(cell["traffic"]["kind"])
    controls = set(seed_range(args.control_seeds))
    for seed in sorted(set(seed_range(args.seeds)) | controls):
        rec = drv.run(cell, seed, args.seconds, False,
                      control=seed in controls)
        row = {"workload": args.workload, "seed": seed,
               "correct": rec["correct"], "attempted": rec["attempted"],
               "failed": rec["failed"], "setup_s": rec["setup_s"],
               "window_compiles": rec["window_compiles"],
               "program": {k: v["value"] for k, v in rec["compared"].items()},
               "control": {k: v["value"]
                           for k, v in rec.get("control", {}).items()},
               **{k: rec[k] for k in ("linears", "control_linears",
                                      "tokens_checked", "check_s",
                                      "window_s")
                  if k in rec}}
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
