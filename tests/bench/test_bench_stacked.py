"""The harness takes configurations whose blocks differ and whose experts
are stacked (E, in, out): the weights are scaled by each expert's fan-in,
every block is built, packed and checked from its own leaves, a stack
packs into the program's ``NmStackedCompressed`` as ``compress_params``
packs it, the reference masks each expert slice by its 2-D rule, and the
operation counts know MLA and held-share expert layers."""
from __future__ import annotations

import dataclasses
import functools
import json
import types
import zlib

import bench_testing
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, costs, harness
from bench import weights as W
from bench.drivers import closed_loop
from bench.references import dense_gqa

CONFIGS = ("h2o-danube-1.8b", "h2o-danube-1.8b-2to4",
           "mistral-large-123b-stage")


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------
def _old_leaf(key, name, shape, dtype):
    """The rule before stacked leaves: the fan-in was ``shape[0]``."""
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    std = 1.0 if name.startswith("embed") else (2.0 / shape[0]) ** 0.5
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _old_make_fn(kd, index, *, leaves, dtype):
    key = jax.random.fold_in(jax.random.wrap_key_data(kd), index + 1)
    return {n: _old_leaf(key, n, tuple(s), dtype) for n, s in leaves}


def _old_make(seed, index, leaves, dtype):
    make = jax.jit(_old_make_fn, static_argnames=("leaves", "dtype"))
    return make(jnp.asarray(W.key_data(seed)), jnp.int32(index),
                leaves=tuple(leaves), dtype=jnp.dtype(dtype).name)


@pytest.mark.parametrize("name", CONFIGS)
def test_existing_configs_keep_their_weights_bit_for_bit(name):
    """At the configurations' own shapes every leaf is drawn by the same
    computation as before (the same scale, the same program); drawn at a
    cut size, the same bits."""
    cfg = harness.config(name)
    ref = harness.reference(cfg["reference"])
    dtype = jnp.dtype(cfg["dtype"])
    kd = jnp.asarray(W.key_data(2**31 + 5))
    groups = [(W.TOP, ref.top_leaves(cfg))] + [
        (i, ref.block_leaves(cfg, i)) for i in range(cfg["num_layers"])]
    for index, leaves in groups:
        for leaf, shape in leaves:
            if not leaf.endswith("scale"):
                want = (1.0 if leaf.startswith("embed")
                        else (2.0 / shape[0]) ** 0.5)
                assert W.std(leaf, shape) == want, leaf
    for index, leaves in groups[:2]:
        args = (kd, jnp.int32(index))
        new = jax.make_jaxpr(functools.partial(
            W._make.__wrapped__, leaves=tuple(leaves), dtype=dtype.name))(*args)
        old = jax.make_jaxpr(functools.partial(
            _old_make_fn, leaves=leaves, dtype=dtype))(*args)
        assert str(new) == str(old)
        small = [(n, tuple(max(1, x // 64) for x in s)) for n, s in leaves]
        for seed in (0, 2**31 + 17):
            got = W.make(seed, index, small, dtype)
            want = _old_make(seed, index, small, dtype)
            for n, _ in small:
                np.testing.assert_array_equal(
                    np.asarray(got[n], np.float32),
                    np.asarray(want[n], np.float32), n)


def test_stacked_leaf_is_scaled_by_each_experts_fan_in():
    e, b, c = 4, 512, 256
    leaves = [("moe/gate/w", (e, b, c)), ("attn/wq/w", (b, c)),
              ("attn/bias", (c,)), ("ln1/scale", (c,)),
              ("embed/table", (64, c))]
    got = W.make(2**31 + 21, 1, leaves, jnp.float32)
    x = np.asarray(got["moe/gate/w"])
    for k in range(e):
        assert np.std(x[k]) == pytest.approx((2.0 / b) ** 0.5, rel=0.02)
    want = _old_make(2**31 + 21, 1, leaves[1:], jnp.float32)
    for n, _ in leaves[1:]:
        np.testing.assert_array_equal(got[n], want[n], n)


# --------------------------------------------------------------------------
# stacked n:m packing
# --------------------------------------------------------------------------
@pytest.mark.parametrize("shape,chunks", [((3, 64, 32), 1),
                                          ((2, 1032, 8192), 2)])
def test_stacked_packing_equals_compress_params(shape, chunks):
    """Each expert is masked and packed a chunk of rows at a time (the
    second shape takes two chunks); that is the program's
    ``compress_params`` of the whole stack under the same masks."""
    from repro.core.magnitude import prune_nm
    from repro.core.sparsity import NmStackedCompressed
    from repro.serve.compressed import compress_params

    e, b, c = shape
    assert c // closed_loop.row_chunk(c, b // 4) == chunks
    k = jax.random.normal(jax.random.PRNGKey(5), shape).astype("bfloat16")
    got = closed_loop.compress(k, 2, 4)
    masks = {("w", i): prune_nm(k[i].T, None, n=2, m=4).mask.T
             for i in range(e)}
    want = compress_params({"w": k}, masks, n=2, m=4, strict=True)["w"]
    assert isinstance(got, NmStackedCompressed)
    np.testing.assert_array_equal(got.values, want.values)
    np.testing.assert_array_equal(got.indices, want.indices)
    assert (got.n, got.m, got.b, got.E, got.idx_bits) == \
        (want.n, want.m, want.b, want.E, want.idx_bits) == (2, 4, b, e, 4)


# --------------------------------------------------------------------------
# blocks that differ
# --------------------------------------------------------------------------
def _mla_moe_reference(dense_everywhere=False):
    """The leaves of an MLA decoder with a leading dense layer and expert
    layers after it, as a reference for such a model would name them."""

    def block_leaves(cfg, i):
        d, h = cfg["d_model"], cfg["num_heads"]
        dq, dkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
        dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        dv = cfg["v_head_dim"]
        out = [("ln1/scale", (d,)), ("ln2/scale", (d,)),
               ("attn/wq_a/w", (d, dq)), ("attn/q_norm/scale", (dq,)),
               ("attn/wq_b/w", (dq, h * (dn + dr))),
               ("attn/wkv_a/w", (d, dkv + dr)), ("attn/kv_norm/scale", (dkv,)),
               ("attn/wkv_b/w", (dkv, h * (dn + dv))),
               ("attn/wo/w", (h * dv, d))]
        if dense_everywhere or i < cfg["num_dense_layers"]:
            f = cfg["d_ff"]
            return out + [("mlp/gate/w", (d, f)), ("mlp/up/w", (d, f)),
                          ("mlp/down/w", (f, d))]
        e, f = cfg["num_experts"], cfg["moe_d_ff"]
        fs = f * cfg["num_shared_experts"]
        return out + [("moe/router/w", (d, e)),
                      ("moe/gate/w", (e, d, f)), ("moe/up/w", (e, d, f)),
                      ("moe/down/w", (e, f, d)),
                      ("moe/shared/gate/w", (d, fs)),
                      ("moe/shared/up/w", (d, fs)),
                      ("moe/shared/down/w", (fs, d))]

    return types.SimpleNamespace(
        block_leaves=block_leaves, top_leaves=dense_gqa.top_leaves,
        # wkv_b is read raw by the absorbed decode; the router stays dense
        LINEARS=("attn/wq_a/w", "attn/wq_b/w", "attn/wkv_a/w", "attn/wo/w",
                 "mlp/gate/w", "mlp/up/w", "mlp/down/w",
                 "moe/gate/w", "moe/up/w", "moe/down/w",
                 "moe/shared/gate/w", "moe/shared/up/w",
                 "moe/shared/down/w"))


def _deepseek_reduced():
    from repro.configs import registry

    small = registry.get_config("deepseek-v3-671b", reduced=True)
    return {**dataclasses.asdict(small), "name": "deepseek-reduced",
            "reference": "mla_moe", "nm": [2, 4], "rms_norm_eps": 1e-6}


def test_build_packs_each_block_from_its_own_leaves(monkeypatch):
    from repro.core.sparsity import NmCompressed, NmStackedCompressed

    cfg = _deepseek_reduced()
    assert cfg["num_dense_layers"] == 1 < cfg["num_layers"]
    monkeypatch.setattr(harness, "reference",
                        lambda name: _mla_moe_reference())
    _, params = closed_loop.build(cfg, 2**31 + 3)
    dense, moe = params["blocks"][0], params["blocks"][1]
    assert "moe" not in dense and "mlp" not in moe
    assert isinstance(dense["mlp"]["down"]["w"], NmCompressed)
    gate = moe["moe"]["gate"]["w"]
    assert isinstance(gate, NmStackedCompressed)
    assert (gate.E, gate.b) == (cfg["num_experts"], cfg["d_model"])
    assert isinstance(moe["moe"]["shared"]["up"]["w"], NmCompressed)
    assert isinstance(moe["moe"]["router"]["w"], jax.Array)
    assert isinstance(moe["attn"]["wkv_b"]["w"], jax.Array)
    # the stack holds the experts of block 1's own weights, packed
    flat = W.make(2**31 + 3, 1, _mla_moe_reference().block_leaves(cfg, 1),
                  cfg["dtype"])
    want = closed_loop.compress(flat["moe/gate/w"], 2, 4)
    np.testing.assert_array_equal(gate.values, want.values)


def test_build_names_the_block_whose_leaves_disagree(monkeypatch):
    cfg = _deepseek_reduced()
    monkeypatch.setattr(harness, "reference",
                        lambda name: _mla_moe_reference(dense_everywhere=True))
    with pytest.raises(harness.SpecError, match="block 1: program leaves"):
        closed_loop.build(cfg, 1)


def test_reference_masks_each_expert_slice():
    ref = types.SimpleNamespace(LINEARS=("moe/gate/w", "attn/wq/w"),
                                magnitude_nm_mask=dense_gqa.magnitude_nm_mask)
    rng = np.random.default_rng(4)
    flat = {"moe/gate/w": jnp.asarray(rng.normal(size=(3, 16, 8)),
                                      jnp.bfloat16),
            "attn/wq/w": jnp.asarray(rng.normal(size=(16, 8)), jnp.bfloat16),
            "ln1/scale": jnp.ones((8,), jnp.bfloat16)}
    out = check._prep(ref, (2, 4))(flat)

    def masked(v):
        v = jnp.asarray(v, jnp.float32)
        return np.where(dense_gqa.magnitude_nm_mask(v, 2, 4), 0.0, v)

    for e in range(3):
        np.testing.assert_array_equal(out["moe/gate/w"][e],
                                      masked(flat["moe/gate/w"][e]))
    np.testing.assert_array_equal(out["attn/wq/w"], masked(flat["attn/wq/w"]))
    np.testing.assert_array_equal(out["ln1/scale"], np.ones(8))
    assert (np.asarray(out["moe/gate/w"]) == 0).mean() == 0.5


# --------------------------------------------------------------------------
# counts of an MLA + MoE block at DeepSeek-V3's published widths
# --------------------------------------------------------------------------
V3 = costs.Shape.of({
    "num_layers": 4, "d_model": 7168, "num_heads": 128, "num_kv_heads": 128,
    "head_dim": 128, "d_ff": 18432, "vocab_size": 129280,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "num_experts": 8,
    "num_routed_experts": 256, "num_experts_per_tok": 8,
    "num_shared_experts": 1, "moe_d_ff": 2048, "num_dense_layers": 1})

# multiply-adds of one token through each weight (c·b)
WQ_A, WQ_B = 7168 * 1536, 1536 * 128 * (128 + 64)
WKV_A, WKV_B = 7168 * (512 + 64), 512 * 128 * (128 + 128)
WO = 128 * 128 * 7168
MLA = WQ_A + WQ_B + WKV_A + WKV_B + WO
ROUTER = 7168 * 256
EXPERT = 3 * 7168 * 2048           # gate, up, down of one expert
MLP = 3 * 7168 * 18432


def test_mla_moe_block_linears():
    assert MLA == 187_105_280
    assert V3.blocks() == [(0, 1), (1, 3)]
    assert [x[1:] for x in V3.linears(1)[:5]] == [
        (1536, 7168), (24576, 1536), (576, 7168), (32768, 512),
        (7168, 16384)]
    # 5 MLA, the router, 8 held experts × 3, the shared expert's 3
    assert len(V3.linears(1)) == 5 + 1 + 24 + 3
    assert len(V3.linears(0)) == 5 + 3
    # a token visits 8 · 8/256 = 1/4 of a held expert on average
    moe = MLA + ROUTER + EXPERT // 4 + EXPERT
    assert costs.block_linear_ops(V3, 1, None) == 2 * moe
    assert costs.block_linear_ops(V3, 0, None) == 2 * (MLA + MLP)
    # 2:4 halves every linear but the router and wkv_b
    assert costs.block_linear_ops(V3, 1, (2, 4)) == 2 * (
        (moe - ROUTER - WKV_B) / 2 + ROUTER + WKV_B)
    assert costs.linear_ops_per_token(V3, None) == (
        2 * (MLA + MLP) + 3 * 2 * moe + 2 * 7168 * 129280)


def test_expert_share_defaults_to_every_expert_held():
    whole = dataclasses.replace(V3, num_experts=256, num_routed_experts=0)
    assert costs.block_linear_ops(whole, 1, None) == 2 * (
        MLA + 256 * 7168 + 8 * EXPERT + EXPERT)


def test_mla_attention_absorbed_for_decode_and_expanded_for_prompts():
    # decode: scores over kv_lora + rope, values over kv_lora, per head
    assert costs.attention_ops(V3, 10) == 4 * 2 * 10 * 128 * (512 + 64 + 512)
    assert costs.decode_token_ops(V3, 9, None) == (
        costs.linear_ops_per_token(V3, None) + 4 * 2 * 10 * 128 * 1088)
    # a prompt: keys of nope + rope, values of v_head_dim
    assert costs.prompt_ops(V3, 2, None) == (
        2 * costs.linear_ops_per_token(V3, None)
        + 4 * 2 * (1 + 2) * 128 * (128 + 64 + 128))
    # the two absorb products on one token cost what wkv_b does
    assert 128 * 128 * 512 + 128 * 512 * 128 == WKV_B


def test_mla_moe_prune_block_counts():
    t = 1000
    hess = 2 * t * (7168 ** 2 + 1536 ** 2 + 512 ** 2 + 16384 ** 2
                    + 8 / 32 * (7168 ** 2 + 2048 ** 2)
                    + 7168 ** 2 + 2048 ** 2)
    assert costs.hessian_ops(V3, t, 1) == pytest.approx(hess, rel=1e-15)
    assert costs.hessian_ops(V3, t, 0) == 2 * t * (
        7168 ** 2 + 1536 ** 2 + 512 ** 2 + 16384 ** 2 + 7168 ** 2
        + 18432 ** 2)
    keys = 1 + 2 + 3
    assert costs.block_forward_ops(V3, 1, 3, 1) == (
        2 * (MLA + ROUTER + EXPERT // 4 + EXPERT) * 3
        + 2 * keys * 128 * 320)
    parts = costs.prune_block_ops(V3, 1, 3, 128, 2, 4, 1)
    solves = [(x.c, x.b) for x in V3.layout(1) if x.input is not None]
    assert len(solves) == 5 + 24 + 3
    assert parts["solve"] == pytest.approx(sum(
        costs.thanos_nm_ops(c, b, 128, 2, 4) for c, b in solves))


def test_shape_reads_the_config_file_keys():
    cfg = json.loads((bench_testing.ROOT / "bench" / "configs"
                      / "mistral-large-123b-stage.json").read_text())
    s = costs.Shape.of(cfg)
    assert (s.num_experts, s.kv_lora_rank) == (0, 0)
    assert s.blocks() == [(0, 4)]
