"""chip_smoke.py's phases on the CPU at the REDUCED h2o-danube config.

The script itself refuses to run without a TPU; these tests drive the same
phase functions (not ``main()``) with the jnp reference impl, so the plan,
the report checks, the engine run, the logit comparison and the sharded
prune comparison are guarded on every change.
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke as S  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.serve import engine as engine_mod  # noqa: E402
from repro.util import compile_cache  # noqa: E402


@pytest.fixture(scope="module")
def cfg():
    return registry.get_config(S.ARCH, reduced=True)


@pytest.fixture(scope="module")
def init(cfg):
    return S.init_phase(cfg)


@pytest.fixture(scope="module")
def pruned(init):
    model, _, _ = init
    return S.prune_phase(model, reduced=True)


@pytest.fixture(scope="module")
def compressed(pruned):
    params, report, _ = pruned
    return S.compress_phase(params, report)


def test_main_refuses_without_tpu(capsys):
    assert S.main([]) != 0
    assert S.main(["--four-chips"]) != 0
    assert capsys.readouterr().out == ""


def test_init_phase_builds_every_layer(cfg, init):
    model, params, info = init
    assert info["layers"] == cfg.num_layers == len(params["blocks"])
    assert info["params"] == sum(x.size for x in jax.tree.leaves(params))


def test_smoke_plan_prunes_only_the_chosen_blocks(init):
    model, params, _ = init
    plan = S.smoke_plan(blocks=(1,))
    for i in range(len(params["blocks"])):
        for path in model.block_linear_paths(params, i):
            want = S.CELL if i == 1 else None
            assert plan.cfg_for(path) == want, path
    assert all(r.on_singular == "fail" for r in plan.rules if not r.skip)


def test_precision_phase_matches_itself_on_one_device(cfg, init):
    model, params, _ = init
    out = S.precision_phase(model, params, cfg, jax.devices("cpu")[0])
    assert out["mask_agreement"] == out["highest_mask_agreement"] == 1.0
    assert out["err_ratio"] == out["highest_err_ratio"] == 1.0
    assert out["shape"] == [cfg.d_model, cfg.d_ff]


def test_prune_phase_reports_clean_layers(init, pruned):
    model, _, _ = init
    _, report, info = pruned
    assert info["layers_pruned"] == 7 * len(S.PRUNED_BLOCKS)
    assert np.isfinite(info["pruned_loss"])


@pytest.mark.parametrize("field,value", [
    ("damp_attempts", 1), ("fallback", "magnitude"), ("calib_skipped", 1),
    ("sparsity", 0.25),
])
def test_check_reports_rejects_guard_events(init, pruned, field, value):
    model, _, _ = init
    params, report, _ = pruned
    bad = dataclasses.replace(report, layers=[
        dataclasses.replace(r, **{field: value}) if not r.skipped else r
        for r in report.layers])
    with pytest.raises(S.SmokeFailure):
        S.check_reports(bad, model, params)


def test_check_reports_rejects_a_missing_layer(init, pruned):
    model, _, _ = init
    params, report, _ = pruned
    bad = dataclasses.replace(report, layers=report.layers[1:])
    with pytest.raises(S.SmokeFailure):
        S.check_reports(bad, model, params)


def test_compress_phase_byte_ratio(compressed):
    _, info = compressed
    assert info["ratio"] == pytest.approx(9 / 16)     # fp32 2:4, 4-bit idx


def test_serve_phase_finishes_every_request(init, compressed):
    model, _, _ = init
    params, _ = compressed
    out = S.serve_phase(model, params, impl="ref", prompt_lens=(4, 9, 4, 6),
                        max_new=3)
    assert out["requests"] == 4 and out["tokens"] == 12


def _logits(model, params, impl, **kw):
    return S.logits_phase(model, params, jax.devices("cpu")[0], impl=impl,
                          prompt_len=5, decode_len=3, **kw)


def test_logits_phase_ref_matches_dense_and_has_no_kernel(init, compressed):
    model, _, _ = init
    params, _ = compressed
    out = _logits(model, params, "ref")
    # fp32 REDUCED config: the expanded (c, b) weight and the dense (b, c)
    # kernel hold the same values, so only XLA's summation order inside
    # the fused scan differs (~1e-7 per dot)
    for phase in ("prefill", "decode"):
        assert out[f"{phase}_nm_vs_dense"] < 1e-5
        assert out[f"{phase}_dense_vs_fp32"] < 1e-5
    assert out["kernel_err_over_tol"] == 0.0
    assert out["argmax_agreement"] == 1.0
    assert out["kernel_in_hlo"] is False


@pytest.fixture(scope="module")
def bf16(cfg, compressed):
    """The compressed REDUCED model in bf16, as it is served on the chip."""
    params, _ = compressed
    model = S.build_model(cfg.replace(dtype="bfloat16"))
    return model, jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)


def test_logits_phase_bf16_kernel_within_the_bf16_floor(bf16):
    """The Pallas kernel (interpreted) in a bf16 model: off the dense bf16
    path by accumulation order only, as accurate against fp32."""
    out = _logits(*bf16, "pallas")
    for phase in ("prefill", "decode"):
        floor = out[f"{phase}_dense_vs_fp32"]
        assert 0 < floor < 0.1
        assert out[f"{phase}_nm_vs_fp32"] <= S.LOGITS_ACCURACY_RATIO * floor
    assert out["kernel_err_over_tol"] <= 1.0


def _skew(monkeypatch, factor):
    """Make every non-reference n:m matmul ``factor`` × too large; the
    engine's shared jits are emptied so that its steps trace the skew."""
    real = S.ops.nm_matmul

    def skewed(x, packed, *, impl="", **kw):
        y = real(x, packed, impl=impl, **kw)
        return y if impl == "ref" else y * factor

    monkeypatch.setattr(S.ops, "nm_matmul", skewed)
    monkeypatch.setattr(engine_mod, "_JIT_CACHE", {})


def test_kernel_parity_catches_a_wrong_kernel(bf16, monkeypatch):
    _, params = bf16
    assert S.kernel_parity(params, impl="pallas") <= 1.0
    _skew(monkeypatch, 1.05)
    assert S.kernel_parity(params, impl="pallas") > 1.0
    with pytest.raises(S.SmokeFailure, match="jnp reference"):
        _logits(*bf16, "pallas")


def test_logits_phase_catches_wrong_compressed_logits(bf16, monkeypatch):
    monkeypatch.setattr(S, "kernel_parity", lambda *a, **kw: 0.0)
    _skew(monkeypatch, 1.5)
    with pytest.raises(S.SmokeFailure, match="less accurate than dense"):
        _logits(*bf16, "pallas")


def _check_four_chip_out(out, shards):
    assert out["layers_compared"] == 7 * len(S.PRUNED_BLOCKS)
    assert out["layers_with_equal_masks"] == out["layers_compared"]
    assert out["min_mask_agreement"] == 1.0
    checked = [r for r in out["layers"].values() if "shards" in r]
    assert len(checked) == len(S.SLICE_CHECKED) * len(S.PRUNED_BLOCKS)
    for r in checked:
        assert r["shards"] == shards
        assert r["equal_to_shard_solves"] and r["reproduces_prune_model"]
        assert r["max_ulps_from_shard_solves"] == 0.0


def test_four_chip_phase_control_flow_on_one_device():
    out = S.four_chip_phase(reduced=True, n_devices=1)
    _check_four_chip_out(out, shards=1)
    assert out["max_obs_loss_ratio"] == 1.0


def test_four_chip_phase_on_four_host_devices():
    """The row-parallel path proper: four CPU devices in a child process
    (the device count is fixed when JAX starts)."""
    code = ("import json, chip_smoke as S; print(json.dumps("
            "S.four_chip_phase(reduced=True, n_devices=4)))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
        [os.environ.get("XLA_FLAGS", ""),
         "--xla_force_host_platform_device_count=4"]),
        PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    _check_four_chip_out(out, shards=4)
    assert out["devices"] == 4
    # rows are solved independently: only the psum'd loss may reassociate
    assert out["max_obs_loss_ratio"] == pytest.approx(1.0, rel=1e-5)


def test_four_chip_phase_catches_a_wrong_sharded_solve(monkeypatch):
    real = S.prune_layer_sharded

    def skewed(w, h, cfg, mesh):
        res = real(w, h, cfg, mesh)
        return res._replace(mask=res.mask.at[0, 0].set(1.0 - res.mask[0, 0]))

    monkeypatch.setattr(S, "prune_layer_sharded", skewed)
    with pytest.raises(S.SmokeFailure, match="single-device solves"):
        S.four_chip_phase(reduced=True, n_devices=1)


def test_compile_cache_dir_defers_to_the_environment():
    assert compile_cache.compile_cache_dir({}) == str(ROOT / ".jax_cache")
    assert compile_cache.compile_cache_dir(
        {compile_cache.ENV_VAR: "/elsewhere"}) is None
