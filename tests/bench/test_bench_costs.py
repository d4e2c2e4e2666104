"""The benchmark's operation and byte counts against hand counts, and its
peak table."""
from __future__ import annotations

import bench_testing  # noqa: F401  (puts the repository root on the path)
import pytest

from bench import costs, peaks

TINY = costs.Shape(num_layers=2, d_model=4, num_heads=2, num_kv_heads=1,
                   head_dim=2, d_ff=8, vocab_size=10)


@pytest.mark.parametrize("idx_bits,want_bytes", [(4, 88.0), (8, 96.0)])
def test_nm_matmul_cost(idx_bits, want_bytes):
    # x (2, 8), W (4, 8) 2:4: 2 kept of each 4 → 64 ops; values 2·4·2·2 B,
    # indices 4·2 B per plane (1 plane at 4 bits, 2 at 8), x 32 B, y 16 B
    ops, nbytes = costs.nm_matmul_cost(2, 4, 8, 2, 4, idx_bits)
    assert ops == 64.0
    assert nbytes == want_bytes


def test_dense_matmul_cost():
    assert costs.dense_matmul_cost(2, 4, 8) == (128.0, 112.0)


@pytest.mark.parametrize("ops,nbytes,want", [
    (64.0, 88.0, (8.8, "memory")), (6400.0, 8.0, (64.0, "compute"))])
def test_roofline_seconds(ops, nbytes, want):
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    t, bound = costs.roofline_seconds(ops, nbytes, peak)
    assert (t, bound) == (pytest.approx(want[0]), want[1])


def test_linear_ops_per_token():
    # Σ c·b over wq 4·4, wk 2·4, wv 2·4, wo 4·4, gate/up 8·4, down 4·8 = 144
    assert [lin[1:] for lin in TINY.linears()] == [
        (4, 4), (2, 4), (2, 4), (4, 4), (8, 4), (8, 4), (4, 8)]
    assert costs.linear_ops_per_token(TINY, None) == 2 * 2 * 144 + 2 * 4 * 10
    assert costs.linear_ops_per_token(TINY, (2, 4)) == 2 * 144 + 80


@pytest.mark.parametrize("window,n_keys,want", [
    (0, 3, 2 * 4 * 3 * 2 * 2), (2, 3, 2 * 4 * 2 * 2 * 2), (2, 1, 32)])
def test_attention_ops_over_live_context(window, n_keys, want):
    s = costs.Shape(**{**TINY.__dict__, "sliding_window": window})
    assert costs.attention_ops(s, n_keys) == want


def test_decode_and_prompt_ops():
    assert costs.decode_token_ops(TINY, 2, None) == 656 + 96
    # prompt of 2: positions 0 and 1 attend 1 and 2 keys
    assert costs.prompt_ops(TINY, 2, None) == (656 + 32) + (656 + 64)


def test_prune_job_ops():
    # forward of 1 sequence of 2 tokens: linears 2·144·2, causal QKᵀ/PV over
    # 1 + 2 keys: 4·3·H·D
    assert costs.block_forward_ops(TINY, 1, 2) == 576 + 48
    # XᵀX of the inputs of widths 4 (attn), 4 (wo), 4 (mlp), 8 (down)
    assert costs.hessian_ops(TINY, 3) == 2 * 3 * (16 + 16 + 16 + 64)
    # b³ + per block (t = 8, then 4): c·(r³/3 + 2rt) + 2Bt², r = 2
    want = 512 + (2 * (8 / 3 + 32) + 512) + (2 * (8 / 3 + 16) + 128)
    assert costs.thanos_nm_ops(2, 8, 4, 2, 4) == pytest.approx(want)
    parts = costs.prune_block_ops(TINY, 1, 2, 4, 2, 4)
    assert parts["forward"] == 2 * 624
    assert parts["total"] == pytest.approx(
        parts["forward"] + parts["hessian"] + parts["solve"])


def test_peak_table_is_keyed_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["hbm_bytes_per_s"]) == (197e12, 819e9)
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
