"""The control comes out not correct: the reference evaluated in fp8
(e4m3, per-token activations and per-channel weights), put in the
program's place, fails at least one of the cell's limits, on three seeds,
while the program passes all of them.  On the CPU, one cell of each kind:
the prune cell at its REDUCED size, the serve cell at 8 layers of width
256 with outputs of ~100 tokens, so that a run checks some hundreds of
served tokens as the chip's runs do.  bench/limits.py reads the same
numbers on the chip at the cells' own sizes (PERF.md gives both)."""
from __future__ import annotations

import bench_testing
import pytest

from bench import harness

TEST_SIZE = {
    "danube-prune-2to4": ({}, {}),
    "danube-serve-2to4": (
        {"num_layers": 8, "d_model": 256, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 64, "d_ff": 512, "vocab_size": 4096},
        {"max_len": 256, "output_len": {"dist": "lognormal", "median": 96,
                                        "sigma": 0.5, "min": 32, "max": 200,
                                        "pool": 8}}),
}


@pytest.mark.parametrize("name", sorted(TEST_SIZE))
@pytest.mark.parametrize("seed", [11, 12, 2**31 + 13])
def test_control_fails_a_limit_the_program_meets(name, seed):
    cell = bench_testing.reduced_cell(name)
    cfg, traffic = TEST_SIZE[name]
    cell["config"].update(cfg)
    cell["traffic"].update(traffic)
    rec = harness.driver(cell["traffic"]["kind"]).run(
        cell, seed, 10.0, False, control=True)
    if "serve" in name:
        assert rec["tokens_checked"] >= 300
    assert all(c["ok"] for c in rec["compared"].values()), rec["compared"]
    assert not all(c["ok"] for c in rec["control"].values()), rec["control"]
