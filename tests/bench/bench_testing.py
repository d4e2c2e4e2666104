"""Shared helpers of the benchmark's tests: the repository root on the
path, and cells cut to the configurations' REDUCED sizes for the CPU."""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

TRAFFIC_CPU = {
    "closed_loop": {"clients": 4, "slots": 4, "max_len": 64, "prompt_len": 8,
                    "output_len": {"dist": "lognormal", "median": 16,
                                   "sigma": 0.5, "min": 4, "max": 40,
                                   "pool": 8},
                    "check_requests": 6},
    "prune_job": {"calib_sequences": 4, "seq_len": 32, "batch": 2,
                  "block_size": 16},
}


def reduced_cell(name: str) -> dict:
    """Workload ``name`` with its configuration at the registry's REDUCED
    sizes and its traffic cut to what the CPU runs in seconds."""
    from repro.configs import registry

    cell = harness.workload(name)
    small = registry.get_config(cell["config"]["arch"], reduced=True)
    cell["config"] = {**cell["config"], **{
        f.name: getattr(small, f.name) for f in dataclasses.fields(small)
        if f.name in cell["config"]}}
    cell["traffic"] = {**cell["traffic"],
                       **TRAFFIC_CPU[cell["traffic"]["kind"]]}
    return cell
