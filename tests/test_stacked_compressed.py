"""Stacked per-expert n:m compression (``NmStackedCompressed``): pack/unpack
property tests, bitwise decode parity against the ``decompress_params``
oracle, the per-expert calibration fixes (routed-row sample counts, dead
experts raise), capacity-drop gate renormalization, and the qwen3-moe
engine e2e — MoE expert FFNs serve compressed-resident, bit-identical to
dense-decompressed serving."""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import PruneConfig, prune_model
from repro.core.hessian import HessianAccumulator
from repro.core.plan import PrunePlan, PruneRule
from repro.core.sparsity import (NmCompressed, NmStackedCompressed, pack_nm,
                                 pack_nm_stacked, unpack_nm_stacked,
                                 compression_ratio)
from repro.data.pipeline import calibration_batches
from repro.faults import InsufficientCalibration
from repro.models import layers as L
from repro.models import moe as M
from repro.models.model_builder import ModelAdapter, build_model
from repro.serve import Request, ServeConfig, ServingEngine
from repro.serve.compressed import (CompressionDowngrade, compress_params,
                                    compressed_bytes, decompress_params)


def _nm_mask(w, n, m):
    """(…, b) n:m mask (1.0 = pruned): drop the n smallest |w| per group."""
    shape = w.shape
    wa = np.abs(np.asarray(w)).reshape(*shape[:-1], shape[-1] // m, m)
    order = np.argsort(wa, axis=-1)
    mask = np.zeros_like(wa)
    for k in range(n):
        np.put_along_axis(mask, order[..., k:k + 1], 1.0, axis=-1)
    return jnp.asarray(mask.reshape(shape))


def _stacked_leaves(tree):
    return [l for l in jax.tree.leaves(
        tree, is_leaf=lambda x: isinstance(x, NmStackedCompressed))
        if isinstance(l, NmStackedCompressed)]


# ==========================================================================
# pack/unpack property tests
# ==========================================================================
@pytest.mark.parametrize("E", [1, 3])
@pytest.mark.parametrize("n,m", [(2, 4), (4, 8)])
@pytest.mark.parametrize("idx_bits", [4, 8])
def test_pack_unpack_roundtrip(E, n, m, idx_bits):
    c, b = 7, 2 * m                        # odd c: no tile-alignment luck
    w = jax.random.normal(jax.random.PRNGKey(E * m), (E, c, b), jnp.float32)
    mask = _nm_mask(w, n, m)
    sparse = w * (1 - mask)
    packed = pack_nm_stacked(sparse, mask, n, m, idx_bits=idx_bits)
    assert (packed.E, packed.b) == (E, b)
    keep, g = m - n, b // m
    assert packed.values.shape == (E, keep, c, g)
    assert packed.indices.shape == \
        (E, keep if idx_bits == 8 else (keep + 1) // 2, c, g)
    np.testing.assert_array_equal(np.asarray(unpack_nm_stacked(packed)),
                                  np.asarray(sparse))


def test_stacked_vmap_slices_match_pack_nm():
    """Each stacked slice is byte-identical to packing that expert alone."""
    E, c, b, n, m = 4, 5, 16, 2, 4
    w = jax.random.normal(jax.random.PRNGKey(0), (E, c, b), jnp.float32)
    mask = _nm_mask(w, n, m)
    packed = pack_nm_stacked(w * (1 - mask), mask, n, m)
    for e in range(E):
        one = pack_nm(w[e] * (1 - mask[e]), mask[e], n, m)
        np.testing.assert_array_equal(np.asarray(packed.values[e]),
                                      np.asarray(one.values))
        np.testing.assert_array_equal(np.asarray(packed.indices[e]),
                                      np.asarray(one.indices))


def test_stacked_is_pytree_with_static_aux():
    packed = pack_nm_stacked(jnp.zeros((2, 4, 8)), _nm_mask(
        jnp.arange(64, dtype=jnp.float32).reshape(2, 4, 8), 2, 4), 2, 4)
    leaves, treedef = jax.tree.flatten(packed)
    assert len(leaves) == 2                # values + indices only
    rt = jax.tree.unflatten(treedef, leaves)
    assert (rt.n, rt.m, rt.b, rt.E, rt.idx_bits) == (2, 4, 8, 2, 4)
    assert compression_ratio(packed) == 0.5625   # fp32 2:4 + 4-bit idx


# ==========================================================================
# decode parity: stacked_dense dispatch, ref + pallas(interpret)
# ==========================================================================
@pytest.fixture()
def stacked_pair():
    E, C, d_in, d_out = 3, 6, 16, 5
    w = jax.random.normal(jax.random.PRNGKey(2), (E, d_in, d_out), jnp.float32)
    mask = _nm_mask(jnp.swapaxes(w, -1, -2), 2, 4)        # groups along d_in
    sparse_cb = jnp.swapaxes(w, -1, -2) * (1 - mask)
    packed = pack_nm_stacked(sparse_cb, mask, 2, 4)
    dense = jnp.swapaxes(sparse_cb, -1, -2)               # (E, d_in, d_out)
    x = jax.random.normal(jax.random.PRNGKey(3), (E, C, d_in), jnp.float32)
    return packed, dense, x


def test_stacked_dense_bitwise_vs_dense(stacked_pair):
    packed, dense, x = stacked_pair
    y_dense = L.stacked_dense({"w": dense}, x)
    y_comp = L.stacked_dense({"w": packed}, x)
    np.testing.assert_array_equal(np.asarray(y_comp), np.asarray(y_dense))


def test_stacked_dense_pallas_interpret_parity(stacked_pair):
    from repro.kernels.ops import NmKernelConfig

    packed, dense, x = stacked_pair
    y_dense = L.stacked_dense({"w": dense}, x)
    with L.nm_kernel_scope(NmKernelConfig(impl="pallas")):
        y_pal = L.stacked_dense({"w": packed}, x)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_dense),
                               atol=1e-5, rtol=1e-5)


# ==========================================================================
# compress_params: stacked packing, downgrades, oracle inversion
# ==========================================================================
def _expert_problem(E=2, d_in=8, d_out=4):
    rng = np.random.default_rng(0)
    params = {"moe": {"gate": {"w": jnp.asarray(
        rng.normal(size=(E, d_in, d_out)), jnp.float32)}}}
    w_cb = jnp.swapaxes(params["moe"]["gate"]["w"], -1, -2)
    masks = {("moe", "gate", "w", e): jnp.swapaxes(_nm_mask(w_cb[e], 2, 4),
                                                   -1, -2)
             for e in range(E)}
    return params, masks


def test_compress_params_packs_expert_stack():
    params, masks = _expert_problem()
    nm = PruneConfig(pattern="nm", n=2, m=4)
    plan = PrunePlan(rules=(PruneRule(match="*", cfg=nm),))
    for comp in (compress_params(params, masks, 2, 4),
                 compress_params(params, masks, plan=plan)):
        leaf = comp["moe"]["gate"]["w"]
        assert isinstance(leaf, NmStackedCompressed)
        assert (leaf.E, leaf.n, leaf.m, leaf.b) == (2, 2, 4, 8)
        restored = decompress_params(comp)["moe"]["gate"]["w"]
        expect = params["moe"]["gate"]["w"] * \
            (1 - jnp.stack([masks[("moe", "gate", "w", e)] for e in range(2)]))
        np.testing.assert_array_equal(np.asarray(restored),
                                      np.asarray(expect))


def test_compress_params_partial_coverage_downgrades():
    params, masks = _expert_problem()
    del masks[("moe", "gate", "w", 1)]     # expert 1 unmasked
    with pytest.warns(CompressionDowngrade, match="experts \\[1\\]"):
        comp = compress_params(params, masks, 2, 4)
    assert isinstance(comp["moe"]["gate"]["w"], jax.Array)   # stays dense
    with pytest.raises(ValueError, match="SERVE DENSE"):
        compress_params(params, masks, 2, 4, strict=True)


def test_compress_params_mixed_cells_downgrade():
    params, masks = _expert_problem()
    plan = PrunePlan(rules=(
        PruneRule(match="*/w/0", cfg=PruneConfig(pattern="nm", n=2, m=4)),
        PruneRule(match="*/w/1", cfg=PruneConfig(pattern="nm", n=4, m=8)),
    ))
    with pytest.warns(CompressionDowngrade, match="mixed n:m cells"):
        comp = compress_params(params, masks, plan=plan)
    assert isinstance(comp["moe"]["gate"]["w"], jax.Array)
    with pytest.raises(ValueError, match="mixed n:m cells"):
        compress_params(params, masks, plan=plan, strict=True)


def test_compress_params_unstructured_experts_stay_silent():
    """An all-unstructured expert stack is intentional dense residency —
    no downgrade warning."""
    params, masks = _expert_problem()
    plan = PrunePlan(rules=(PruneRule(match="*", cfg=PruneConfig(p=0.5)),))
    with warnings.catch_warnings():
        warnings.simplefilter("error", CompressionDowngrade)
        comp = compress_params(params, masks, plan=plan)
    assert isinstance(comp["moe"]["gate"]["w"], jax.Array)


def test_compressed_bytes_counts_expert_leaves():
    params, masks = _expert_problem(E=4, d_in=16, d_out=8)
    comp = compress_params(params, masks, 2, 4)
    cbytes, dbytes = compressed_bytes(comp)
    assert dbytes == 4 * 16 * 8 * 4        # E · in · out · fp32
    assert cbytes / dbytes == 0.5625       # fp32 2:4 + 4-bit indices
    vals = comp["moe"]["gate"]["w"].values
    bf16 = NmStackedCompressed(vals.astype(jnp.bfloat16),
                               comp["moe"]["gate"]["w"].indices,
                               2, 4, 16, 4)
    cb, db = compressed_bytes({"w": bf16})
    assert cb / db == 0.625                # paper's bf16 2:4 ratio


# ==========================================================================
# per-expert calibration: routed-row counts, dead experts raise
# ==========================================================================
def test_hessian_valid_mask_counts_routed_rows_only():
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 8), jnp.float32)
    valid = jnp.asarray([True, False, True, False])
    acc = HessianAccumulator.init(8).update(x, valid)
    assert float(acc.count) == 2.0
    kept = np.asarray(x)[[0, 2]]
    np.testing.assert_allclose(np.asarray(acc.xtx), kept.T @ kept, atol=1e-5)
    # garbage in an invalid row must not poison the batch
    poisoned = x.at[1].set(jnp.nan)
    acc2 = HessianAccumulator.init(8).update(poisoned, valid)
    assert float(acc2.skipped) == 0.0
    np.testing.assert_array_equal(np.asarray(acc2.xtx), np.asarray(acc.xtx))
    # NaN in a *valid* row still skips the whole batch
    acc3 = HessianAccumulator.init(8).update(x.at[0].set(jnp.nan), valid)
    assert float(acc3.skipped) == 1.0 and float(acc3.count) == 0.0
    # no mask → bitwise the old behavior
    a = HessianAccumulator.init(8).update(x)
    b = HessianAccumulator.init(8).update(x, None)
    np.testing.assert_array_equal(np.asarray(a.xtx), np.asarray(b.xtx))
    assert float(a.count) == 4.0


def test_dead_expert_raises_insufficient_calibration():
    """Regression: capacity-buffer padding used to count as calibration
    samples, so an expert the router never selected sailed through with an
    all-zero Hessian.  With routed-row counts it raises."""
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # 4 tokens × top-2 over 8 experts: seed 0 provably leaves experts
    # unrouted (asserted below, so the fixture can't silently drift)
    batches = calibration_batches(cfg, num_samples=2, seq_len=2, batch=2)
    ad = ModelAdapter(model)
    carry = ad.prepare(params, batches[0])
    _, caps = ad.block_apply(params, 0, carry, capture=True)
    routed = [int(caps[("blocks", 0, "moe", "gate", "w", e)][1].sum())
              for e in range(cfg.num_experts)]
    assert min(routed) == 0, "fixture must contain a dead expert"
    with pytest.raises(InsufficientCalibration):
        prune_model(params, ad, batches,
                    PruneConfig(method="thanos", p=0.5, block_size=16),
                    min_calib_samples=1)


# ==========================================================================
# gate renormalization across the capacity drop
# ==========================================================================
def _moe_oracle(p, x, cfg):
    """Per-token numpy re-derivation of moe_ffn: sort-based dispatch with
    capacity C, gates renormalized over *surviving* assignments."""
    B, S, d = x.shape
    T, E, k = B * S, cfg.num_experts, cfg.num_experts_per_tok
    C = M.capacity(T, k, E, cfg.capacity_factor)
    xt = np.asarray(x.reshape(T, d))
    logits = xt @ np.asarray(p["router"]["w"])
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    gates = -np.sort(-probs, axis=-1, kind="stable")[:, :k]
    ids = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    flat_ids, flat_tok = ids.reshape(-1), np.repeat(np.arange(T), k)
    order = np.argsort(flat_ids, kind="stable")
    fill = {e: 0 for e in range(E)}
    survive = np.zeros(T * k, bool)
    for j in order:
        e = flat_ids[j]
        if fill[e] < C:
            survive[j] = True
            fill[e] += 1
    survive = survive.reshape(T, k)
    act = np.asarray
    out = np.zeros((T, d), np.float32)
    silu = lambda v: v / (1.0 + np.exp(-v))
    for t in range(T):
        g = gates[t] * survive[t]
        denom = g.sum()
        if denom > 0:
            g = g / denom
        for j in range(k):
            if not survive[t, j]:
                continue
            e = ids[t, j]
            h = silu(xt[t] @ act(p["gate"]["w"][e])) * \
                (xt[t] @ act(p["up"]["w"][e]))
            out[t] += (h @ act(p["down"]["w"][e])) * g[j]
    return out.reshape(B, S, d)


def test_gate_renorm_no_overflow_matches_plain_topk():
    """With ample capacity nothing drops and the post-drop renorm is the
    plain top-k renorm."""
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)   # cf=4: no drops
    p = M.moe_params(jax.random.PRNGKey(7), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model),
                          jnp.float32)
    y = M.moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), _moe_oracle(p, x, cfg),
                               atol=1e-5, rtol=1e-5)


def test_gate_renorm_overflow_renorms_survivors():
    """Regression: gates used to renormalize *before* the capacity drop, so
    a token losing one of its k assignments kept the dropped weight in the
    denominator and under-scaled the surviving expert."""
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    cfg = cfg.replace(capacity_factor=0.25)               # C=8: forced drops
    p = M.moe_params(jax.random.PRNGKey(9), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 32, cfg.d_model),
                          jnp.float32)
    T, E, k = 64, cfg.num_experts, cfg.num_experts_per_tok
    assert M.capacity(T, k, E, cfg.capacity_factor) < T * k // E + 8
    y = M.moe_ffn(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), _moe_oracle(p, x, cfg),
                               atol=1e-5, rtol=1e-5)


# ==========================================================================
# qwen3-moe engine e2e: expert-targeting recipe, compressed-resident
# ==========================================================================
@pytest.fixture(scope="module")
def moe_served():
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batches = calibration_batches(cfg, num_samples=8, seq_len=32, batch=8)
    with open("examples/recipes/moe_expert_2to4.json") as f:
        plan = PrunePlan.from_json(f.read())
    pruned, report = prune_model(params, ModelAdapter(model), batches, plan)
    comp = compress_params(pruned, report.masks, plan=report.plan)
    return cfg, model, pruned, report, comp


def _run_engine(model, params, cfg, n_req=3, max_new=4):
    eng = ServingEngine(model, params, ServeConfig(batch_slots=2, max_len=24))
    rng = np.random.default_rng(0)
    for uid in range(n_req):
        eng.submit(Request(uid, rng.integers(0, cfg.vocab_size, size=6),
                           max_new=max_new))
    return eng, {r.uid: r.out for r in eng.run()}


def test_moe_recipe_compresses_every_expert_stack(moe_served):
    cfg, model, pruned, report, comp = moe_served
    stacked = _stacked_leaves(comp)
    assert len(stacked) == cfg.num_layers * 3      # gate/up/down per block
    assert all(s.E == cfg.num_experts and (s.n, s.m) == (2, 4)
               for s in stacked)
    # router + attn stay dense (unstructured attn never packs)
    assert isinstance(comp["blocks"][0]["moe"]["router"]["w"], jax.Array)
    assert isinstance(comp["blocks"][0]["attn"]["wq"]["w"], jax.Array)
    cbytes, dbytes = compressed_bytes(comp)
    assert cbytes / dbytes == 0.5625               # fp32 2:4, experts only
    expert_dense = cfg.num_layers * 3 * cfg.num_experts * \
        cfg.d_model * cfg.moe_d_ff * 4
    assert dbytes == expert_dense                  # every expert leaf counted
    # the oracle inverts the stacked packing exactly
    restored = decompress_params(comp)
    np.testing.assert_array_equal(
        np.asarray(restored["blocks"][0]["moe"]["gate"]["w"]),
        np.asarray(pruned["blocks"][0]["moe"]["gate"]["w"]))


def test_moe_stacked_serving_bit_identical(moe_served):
    cfg, model, pruned, report, comp = moe_served
    _, outs_dense = _run_engine(model, pruned, cfg)
    _, outs_comp = _run_engine(model, comp, cfg)
    assert outs_dense == outs_comp


def test_moe_engine_never_decompresses(moe_served, monkeypatch):
    cfg, model, _, _, comp = moe_served

    def boom(*a, **k):
        raise AssertionError("dense materialization on the serve path")

    import repro.core.sparsity as sparsity
    import repro.serve.compressed as compressed

    monkeypatch.setattr(compressed, "decompress_params", boom)
    monkeypatch.setattr(sparsity, "unpack_nm_stacked", boom)
    eng, outs = _run_engine(model, comp, cfg)
    assert _stacked_leaves(eng.params), "engine must keep stacked leaves"
    assert all(len(v) == 4 for v in outs.values())


def test_abstract_nm_params_lowers_expert_stacks():
    from repro.core.schedule import get_path
    from repro.launch.steps import abstract_nm_params

    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    model = build_model(cfg)
    with open("examples/recipes/moe_expert_2to4.json") as f:
        plan = PrunePlan.from_json(f.read())
    a = abstract_nm_params(model, plan=plan)
    leaf = get_path(a, ("blocks", 0, "moe", "gate", "w"))
    assert isinstance(leaf, NmStackedCompressed)
    E, f, d = cfg.num_experts, cfg.moe_d_ff, cfg.d_model
    assert leaf.values.shape == (E, 2, f, d // 4)
    assert leaf.indices.shape == (E, 1, f, d // 4)
    assert (leaf.n, leaf.m, leaf.b, leaf.E) == (2, 4, d, E)
    # attn is unstructured under the recipe → dense SDS
    attn = get_path(a, ("blocks", 0, "attn", "wq", "w"))
    assert isinstance(attn, jax.ShapeDtypeStruct)
    # global (n, m) lowers the stacks too
    a2 = abstract_nm_params(model, 2, 4)
    assert isinstance(get_path(a2, ("blocks", 0, "moe", "up", "w")),
                      NmStackedCompressed)
