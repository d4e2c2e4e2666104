"""Each cell's control flow on the CPU at its configuration's REDUCED size,
through the harness's own functions; ``bench/run.py`` itself refuses the
CPU, and refuses to run without the program beside it.

These runs say nothing about speed: the metrics are read only to show
that every reader finds what it reads."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

import bench_testing
import pytest

from bench import harness, peaks

CPU_READABLE = {"solve_s_per_block", "calib_s_per_block", "prune_mfu"}


@pytest.mark.parametrize("name", harness.workload_names())
def test_cell_runs_and_checks_on_cpu(name):
    cell = bench_testing.reduced_cell(name)
    rec = harness.driver(cell["traffic"]["kind"]).run(
        cell, 2**31 + 99, 1.0, False)
    rec["peak"] = peaks.peaks("TPU v5 lite")
    assert rec["correct"], rec["compared"]
    assert rec["attempted"] > 0 and rec["failed"] == 0
    assert all(c["ok"] for c in rec["compared"].values())
    for m in cell["end_to_end"]:
        value = harness.metric(m).read(rec)
        assert value is not None and value > 0, m
    for m in cell["per_layer"]:
        value = harness.metric(m).read(rec)
        assert (value is not None) == (m in CPU_READABLE), m


def _run_py(cwd, *extra_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "danube-serve-2to4",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    out = _run_py(bench_testing.ROOT)
    assert out.returncode == 2 and out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(bench_testing.ROOT / "BENCHMARK.json", tmp_path)
    for p in ("bench", "tests/bench"):
        shutil.copytree(bench_testing.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path)
    assert out.returncode != 0 and out.stdout == ""


@pytest.mark.parametrize("shape", [(64, 32), (256, 640), (16384, 1024)])
def test_rowwise_compression_equals_compress_params(shape):
    """The serve cells mask and pack each linear a slice of rows at a time
    (the last shape takes two slices); that is the program's
    compress_params of the whole linear."""
    import jax
    import numpy as np
    from bench.drivers import closed_loop
    from repro.core.magnitude import prune_nm
    from repro.serve.compressed import compress_params

    k = jax.random.normal(jax.random.PRNGKey(3), shape).astype("bfloat16")
    got = closed_loop._compress_linear(k, n=2, m=4)
    mask = prune_nm(k.T, None, n=2, m=4).mask.T
    want = compress_params({"w": k}, {("w",): mask}, n=2, m=4,
                           strict=True)["w"]
    np.testing.assert_array_equal(got.values, want.values)
    np.testing.assert_array_equal(got.indices, want.indices)
    assert (got.n, got.m, got.b, got.idx_bits) == (2, 4, shape[0], 4)


def test_reference_mask_breaks_ties_as_the_program_does():
    """Equal magnitudes across the prune boundary of a group: both prune
    the lower input index first."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.magnitude import prune_nm

    from bench.references import dense_gqa

    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.integers(-3, 4, size=(64, 512)), jnp.float32)
    want = np.asarray(prune_nm(k.T, None, n=2, m=4).mask.T) > 0.5
    np.testing.assert_array_equal(dense_gqa.magnitude_nm_mask(k, 2, 4), want)
