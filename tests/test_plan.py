"""PrunePlan recipe API (DESIGN.md §11).

* ``PrunePlan.uniform(cfg)`` + ``prune_model`` is bit-identical to the
  bare-``PruneConfig`` compat path for all four methods × three patterns.
* JSON round-trip (``from_json(to_json(plan)) == plan``) — hypothesis,
  including rule ordering, skip rules, and allocation specs.
* ``PruneConfig`` validation raises ``ValueError`` (never bare asserts —
  they vanish under ``python -O``).
* Method registry: ``register_method`` surfaces in ``METHODS``/CLI.
* Mixed recipe end-to-end: 2:4 MLPs + unstructured attention + dense
  embeddings on a zoo model, compressed-resident serving with per-layer
  residency, plan recovered from the report JSON artifact.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    METHODS, PATTERNS, AllocationSpec, LayerStat, NmCompressed, PruneConfig,
    PrunePlan, PruneRule, collect_hessian_stats, prune_layer, prune_model,
    register_method, unregister_method,
)
from repro.models import layers as L

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # optional test dep (pip '.[test]')
    HAVE_HYPOTHESIS = False


# ==========================================================================
# minimal BlockwiseAdapter — fast enough to run the full 4×3 grid twice
# ==========================================================================
class TinyBlocksAdapter:
    """Two blocks × two linears over a (B, d) carry."""

    NAMES = ("fc1", "fc2")

    def num_blocks(self, params) -> int:
        return len(params["blocks"])

    def prepare(self, params, batch):
        return batch

    def block_apply(self, params, i, carry, *, capture: bool):
        caps = {}
        x = carry
        for name in self.NAMES:
            if capture:
                caps[("blocks", i, name, "w")] = x
            x = jnp.tanh(x @ params["blocks"][i][name]["w"])
        return x, caps

    def block_linear_paths(self, params, i):
        return [("blocks", i, name, "w") for name in self.NAMES]


@pytest.fixture(scope="module")
def tiny_problem():
    d, nblocks = 16, 2
    rng = np.random.default_rng(0)
    params = {"blocks": {
        i: {n: {"w": jnp.asarray(rng.normal(size=(d, d)) / np.sqrt(d),
                                 jnp.float32)}
            for n in TinyBlocksAdapter.NAMES}
        for i in range(nblocks)
    }}
    batches = [jnp.asarray(rng.normal(size=(8, d)), jnp.float32)
               for _ in range(2)]
    return params, TinyBlocksAdapter(), batches


GRID = [(m, p) for m in ("thanos", "sparsegpt", "wanda", "magnitude")
        for p in ("unstructured", "nm", "structured")]


@pytest.mark.parametrize("method,pattern", GRID,
                         ids=[f"{m}-{p}" for m, p in GRID])
def test_uniform_plan_bit_identical_to_config_path(tiny_problem, method,
                                                   pattern):
    """PrunePlan.uniform(cfg) ≡ the pre-redesign bare-cfg path, bitwise."""
    params, adapter, batches = tiny_problem
    cfg = PruneConfig(method=method, pattern=pattern, p=0.5, n=2, m=4,
                      block_size=8)
    old, old_rep = prune_model(params, adapter, batches, cfg)
    new, new_rep = prune_model(params, adapter, batches,
                               PrunePlan.uniform(cfg))
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_leaves_with_path(old),
            jax.tree_util.tree_leaves_with_path(new)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(kp))
    assert set(old_rep.masks) == set(new_rep.masks)
    for path in old_rep.masks:
        np.testing.assert_array_equal(np.asarray(old_rep.masks[path]),
                                      np.asarray(new_rep.masks[path]))
    for ra, rb in zip(old_rep.layers, new_rep.layers):
        assert (ra.path, ra.sparsity, ra.obs_loss) == \
               (rb.path, rb.sparsity, rb.obs_loss)
        assert rb.rule == 0 and rb.tag == cfg.tag() and not rb.skipped


# ==========================================================================
# resolution semantics
# ==========================================================================
def test_first_match_wins_and_skip():
    nm = PruneConfig(pattern="nm", n=2, m=4)
    un = PruneConfig(p=0.3)
    plan = PrunePlan(rules=(
        PruneRule(match="blocks/0/*", cfg=None),          # skip outranks
        PruneRule(match="*/mlp/*", cfg=nm),
        PruneRule(match="*", cfg=un),
    ))
    assert plan.resolve("blocks/0/mlp/up/w") == (0, None)
    assert plan.resolve(("blocks", 1, "mlp", "up", "w")) == (1, nm)
    assert plan.resolve("blocks/1/attn/wq/w") == (2, un)
    # unmatched path (empty-rule plan) → (-1, None)
    assert PrunePlan(rules=()).resolve("anything") == (-1, None)


def test_regex_rule_fullmatch():
    cfg = PruneConfig()
    plan = PrunePlan(rules=(
        PruneRule(match=r"blocks/\d+/attn/w[qk]/w", cfg=cfg, regex=True),
    ))
    assert plan.cfg_for("blocks/12/attn/wq/w") is cfg
    assert plan.cfg_for("blocks/12/attn/wv/w") is None
    assert plan.cfg_for("xblocks/12/attn/wq/w") is None   # fullmatch
    with pytest.raises(ValueError, match="bad regex"):
        PruneRule(match="[", regex=True)


def test_expert_slice_paths_resolve():
    cfg = PruneConfig(pattern="nm")
    plan = PrunePlan(rules=(PruneRule(match="*/moe/*", cfg=cfg),))
    assert plan.cfg_for(("blocks", 3, "moe", "gate", "w", 7)) is cfg


# ==========================================================================
# PruneConfig validation — ValueErrors survive python -O
# ==========================================================================
@pytest.mark.parametrize("kw,msg", [
    (dict(method="nope"), "unknown method"),
    (dict(pattern="nope"), "unknown pattern"),
    (dict(p=1.0), "must be in"),
    (dict(p=-0.1), "must be in"),
    (dict(n=0), "0 < n < m"),
    (dict(n=4, m=4), "0 < n < m"),
    (dict(percdamp=0.0), "percdamp"),
    (dict(percdamp=-1.0), "percdamp"),
    (dict(alpha=1.0), "alpha"),
    (dict(alpha=-0.5), "alpha"),
])
def test_prune_config_rejections(kw, msg):
    with pytest.raises(ValueError, match=msg):
        PruneConfig(**kw)


def test_prune_config_dict_round_trip_rejects_unknown():
    cfg = PruneConfig(method="sparsegpt", p=0.25, block_size=32)
    assert PruneConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match="unknown PruneConfig fields"):
        PruneConfig.from_dict({"p": 0.5, "sparsity": 0.5})


# ==========================================================================
# registry
# ==========================================================================
def test_register_method_surfaces_everywhere():
    def half_magnitude(w, h, cfg):
        return prune_layer(w, None, PruneConfig(method="magnitude", p=cfg.p))

    try:
        register_method("halfmag", {"unstructured": half_magnitude},
                        data_aware=False)
        assert "halfmag" in METHODS            # live view: CLI choices too
        assert "halfmag" in list(METHODS)
        w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)),
                        jnp.float32)
        res = prune_layer(w, None, PruneConfig(method="halfmag", p=0.5))
        assert float(jnp.mean(res.mask)) == 0.5
        # unsupported pattern on the new method errors loudly
        with pytest.raises(ValueError, match="does not support pattern"):
            prune_layer(w, None, PruneConfig(method="halfmag", pattern="nm"))
        with pytest.raises(ValueError, match="already registered"):
            register_method("halfmag", {"unstructured": half_magnitude})
    finally:
        unregister_method("halfmag")
    assert "halfmag" not in METHODS


def test_data_aware_method_requires_hessian():
    w = jnp.zeros((4, 4), jnp.float32)
    with pytest.raises(ValueError, match="Hessian required"):
        prune_layer(w, None, PruneConfig(method="thanos", p=0.5))


def test_cli_build_plan_shorthands():
    import argparse

    from repro.launch.prune import build_plan

    ns = argparse.Namespace(
        plan="", method="thanos", pattern="unstructured", p=0.5, n=2, m=4,
        alpha=0.0, block_size=64, skip=["embed*"], mlp_pattern="nm",
        attn_pattern="")
    plan = build_plan(ns)
    assert isinstance(plan, PrunePlan)
    assert plan.cfg_for("embed/table") is None
    assert plan.cfg_for("blocks/1/mlp/up/w").pattern == "nm"
    assert plan.cfg_for("blocks/1/attn/wq/w").pattern == "unstructured"
    # no plan-ish flags → the bare-PruneConfig compat shim
    ns2 = argparse.Namespace(
        plan="", method="wanda", pattern="structured", p=0.3, n=2, m=4,
        alpha=0.0, block_size=64, skip=[], mlp_pattern="", attn_pattern="")
    assert isinstance(build_plan(ns2), PruneConfig)


# ==========================================================================
# JSON round-trip — deterministic anchors + hypothesis
# ==========================================================================
def test_plan_json_round_trip_anchor():
    """Deterministic round-trip (runs even without hypothesis): rule order,
    skip rules, regex rules, allocation, both serialization directions."""
    plan = PrunePlan(rules=(
        PruneRule(match="embed*", cfg=None, name="dense"),
        PruneRule(match="*/mlp/*",
                  cfg=PruneConfig(method="thanos", pattern="nm", n=3, m=8,
                                  block_size=512, alpha=0.1)),
        PruneRule(match=r"blocks/\d+/attn/.*", regex=True,
                  cfg=PruneConfig(method="sparsegpt", p=0.625,
                                  percdamp=0.02, row_chunk=4)),
        PruneRule(match="*", cfg=PruneConfig(method="magnitude", p=0.5)),
    ), allocation=AllocationSpec(policy="hessian_trace", budget=0.4,
                                 p_min=0.1, p_max=0.8))
    rt = PrunePlan.from_json(plan.to_json())
    assert rt == plan
    assert [r.match for r in rt.rules] == [r.match for r in plan.rules]
    assert rt.rules[0].skip and not rt.rules[1].skip
    assert PrunePlan.from_json(PrunePlan.uniform(
        PruneConfig()).to_json()) == PrunePlan.uniform(PruneConfig())


if HAVE_HYPOTHESIS:
    def _cfgs():
        return st.builds(
            lambda method, pattern, p, m, n_off, bs, alpha, damp, rc:
            PruneConfig(
                method=method, pattern=pattern, p=p,
                n=1 + n_off % (m - 1), m=m, block_size=bs, alpha=alpha,
                percdamp=damp, row_chunk=rc),
            method=st.sampled_from(tuple(METHODS)),
            pattern=st.sampled_from(tuple(PATTERNS)),
            p=st.floats(min_value=0.0, max_value=0.99, allow_nan=False),
            m=st.integers(min_value=2, max_value=16),
            n_off=st.integers(min_value=0, max_value=14),
            bs=st.sampled_from((8, 32, 64, 128, 512)),
            alpha=st.floats(min_value=0.0, max_value=0.99, allow_nan=False),
            damp=st.floats(min_value=1e-6, max_value=1.0, allow_nan=False),
            rc=st.integers(min_value=0, max_value=8),
        )

    def _rules():
        globs = st.text(alphabet="abcdw0123/*?_", min_size=1, max_size=16)
        return st.builds(
            PruneRule,
            match=globs,
            cfg=st.one_of(st.none(), _cfgs()),    # None = skip rule
            regex=st.just(False),
            name=st.text(alphabet="abc-", max_size=6),
        ) | st.builds(                            # regex rules: safe literals
            PruneRule,
            match=st.text(alphabet="abcd/_0123", min_size=1, max_size=12),
            cfg=_cfgs(),
            regex=st.just(True),
        )

    def _plans():
        allocs = st.one_of(
            st.none(),
            st.builds(
                # three sorted draws: p_min <= budget <= p_max by
                # construction (the spec rejects unattainable budgets)
                lambda policy, a, b, c: AllocationSpec(
                    policy=policy, budget=sorted((a, b, c))[1],
                    p_min=min(a, b, c), p_max=max(a, b, c)),
                policy=st.sampled_from(("uniform", "hessian_trace")),
                a=st.floats(min_value=0.0, max_value=0.99, allow_nan=False),
                b=st.floats(min_value=0.0, max_value=0.99, allow_nan=False),
                c=st.floats(min_value=0.0, max_value=0.99, allow_nan=False),
            ),
        )
        return st.builds(
            PrunePlan,
            rules=st.lists(_rules(), max_size=6).map(tuple),
            allocation=allocs,
        )

    @settings(max_examples=60, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(plan=_plans())
    def test_plan_json_round_trip(plan):
        rt = PrunePlan.from_json(plan.to_json())
        assert rt == plan                          # incl. rule order
        assert [r.skip for r in rt.rules] == [r.skip for r in plan.rules]
        # a second trip is a fixed point
        assert PrunePlan.from_json(rt.to_json()) == rt


def test_plan_json_rejects_malformed():
    with pytest.raises(ValueError, match="unknown plan keys"):
        PrunePlan.from_dict({"rules": [], "extra": 1})
    with pytest.raises(ValueError, match="schema version"):
        PrunePlan.from_dict({"version": 99, "rules": []})
    with pytest.raises(ValueError, match="needs 'cfg' or 'action'"):
        PrunePlan.from_dict({"rules": [{"match": "*"}]})
    with pytest.raises(ValueError, match="excludes 'cfg'"):
        PrunePlan.from_dict({"rules": [
            {"match": "*", "action": "skip", "cfg": {"p": 0.5}}]})
    with pytest.raises(ValueError, match="unknown rule keys"):
        PrunePlan.from_dict({"rules": [{"match": "*", "cfgg": {}}]})
    with pytest.raises(ValueError, match="unknown allocation policy"):
        AllocationSpec(policy="learned")
    with pytest.raises(ValueError, match="unattainable"):
        AllocationSpec(budget=0.8, p_max=0.5)
    with pytest.raises(ValueError, match="unattainable"):
        AllocationSpec(budget=0.05, p_min=0.3)


# ==========================================================================
# sparsity allocation
# ==========================================================================
def test_allocate_sparsity_uniform_and_trace():
    base = PrunePlan.uniform(PruneConfig(method="thanos", p=0.5,
                                         block_size=8))
    stats = {f"blocks/{i}/fc/w": LayerStat(size=1024, trace=10.0 ** i)
             for i in range(5)}

    uni = base.allocate_sparsity(stats, policy="uniform", budget=0.4)
    assert all(uni.cfg_for(p).p == 0.4 for p in stats)

    tr = base.allocate_sparsity(stats, policy="hessian_trace", budget=0.5,
                                p_min=0.05, p_max=0.95)
    ps = [tr.cfg_for(p).p for p in stats]
    assert all(a >= b for a, b in zip(ps, ps[1:]))   # salient → denser
    assert abs(sum(ps) / len(ps) - 0.5) < 1e-3       # budget preserved
    assert all(0.05 <= p <= 0.95 for p in ps)
    assert tr.allocation is None                     # consumed
    # non-p cells (n:m) and skipped layers are never reallocated
    nm_plan = PrunePlan.uniform(PruneConfig(pattern="nm", n=2, m=4))
    assert nm_plan.allocate_sparsity(stats).rules == nm_plan.rules


def test_prune_model_expands_allocation(tiny_problem):
    """A recipe with an allocation block self-expands inside prune_model;
    the report embeds the *expanded* plan (allocation consumed)."""
    params, adapter, batches = tiny_problem
    plan = PrunePlan(
        rules=(PruneRule(match="*", cfg=PruneConfig(method="wanda", p=0.5)),),
        allocation=AllocationSpec(policy="uniform", budget=0.25,
                                  p_min=0.0, p_max=0.9),
    )
    _, report = prune_model(params, adapter, batches, plan)
    assert report.plan.allocation is None
    assert len(report.plan.rules) == 4 + 1      # per-layer rules + catch-all
    for rep in report.layers:
        assert abs(rep.sparsity - 0.25) < 1e-6
    # the artifact replays bit-exactly: no re-allocation on the way back in
    rt = PrunePlan.from_json(report.plan.to_json())
    assert rt == report.plan


def test_prune_layer_sharded_rejects_unexpanded_allocation():
    from jax.sharding import Mesh

    from repro.dist.prune import prune_layer_sharded

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    plan = PrunePlan(rules=(PruneRule(match="*", cfg=PruneConfig()),),
                     allocation=AllocationSpec())
    with pytest.raises(ValueError, match="unexpanded allocation"):
        prune_layer_sharded(jnp.zeros((4, 4)), jnp.eye(4), plan, mesh,
                            path=("blocks", 0, "mlp", "up", "w"))


def test_compress_params_packs_expert_slices():
    """Stacked MoE expert slices pack into one NmStackedCompressed leaf in
    both calling modes — there is no silent dense fallback when every
    slice is masked under one (n, m) cell (partial/mixed stacks warn:
    tests/test_stacked_compressed.py)."""
    from repro.core.sparsity import NmStackedCompressed
    from repro.serve.compressed import compress_params

    rng = np.random.default_rng(0)
    d_in, d_out, E = 8, 4, 2
    params = {
        "moe": {"gate": {"w": jnp.asarray(rng.normal(size=(E, d_in, d_out)),
                                          jnp.float32)}},
        "mlp": {"up": {"w": jnp.asarray(rng.normal(size=(d_in, d_out)),
                                        jnp.float32)}},
    }
    mask_cb = jnp.tile(jnp.asarray([1.0, 1.0, 0.0, 0.0]), (d_out, d_in // 4))
    masks = {("moe", "gate", "w", 0): mask_cb.T,
             ("moe", "gate", "w", 1): mask_cb.T,
             ("mlp", "up", "w"): mask_cb.T}

    nm = PruneConfig(pattern="nm", n=2, m=4)
    plan = PrunePlan(rules=(PruneRule(match="*", cfg=nm),))
    for comp in (compress_params(params, masks, 2, 4),
                 compress_params(params, masks, plan=plan)):
        assert isinstance(comp["mlp"]["up"]["w"], NmCompressed)
        leaf = comp["moe"]["gate"]["w"]
        assert isinstance(leaf, NmStackedCompressed)
        assert (leaf.E, leaf.n, leaf.m, leaf.b) == (E, 2, 4, d_in)
        assert leaf.values.shape == (E, 2, d_out, d_in // 4)


def test_registry_view_eq_is_total():
    assert METHODS == tuple(METHODS) and METHODS == list(METHODS)
    assert not METHODS == None                   # noqa: E711 — the point
    assert METHODS != None                       # noqa: E711
    assert not METHODS == 42
    with pytest.raises(TypeError):               # mutable ⇒ unhashable
        hash(METHODS)


def test_collect_hessian_stats(tiny_problem):
    params, adapter, batches = tiny_problem
    stats = collect_hessian_stats(params, adapter, batches)
    assert set(stats) == {f"blocks/{i}/{n}/w" for i in range(2)
                          for n in ("fc1", "fc2")}
    for st_ in stats.values():
        assert st_.size == 16 * 16 and st_.trace > 0


# ==========================================================================
# mixed plan through prune_model: skip rules + attribution + report JSON
# ==========================================================================
def test_mixed_plan_prune_model_attribution(tiny_problem):
    params, adapter, batches = tiny_problem
    nm = PruneConfig(method="thanos", pattern="nm", n=2, m=4, block_size=8)
    un = PruneConfig(method="wanda", p=0.5)
    plan = PrunePlan(rules=(
        PruneRule(match="blocks/0/fc1/w", cfg=None, name="dense-outlier"),
        PruneRule(match="*/fc1/w", cfg=nm),
        PruneRule(match="*", cfg=un),
    ))
    pruned, report = prune_model(params, adapter, batches, plan)

    by_path = {r.path: r for r in report.layers}
    skipped = by_path[("blocks", 0, "fc1", "w")]
    assert skipped.skipped and skipped.rule == 0 and skipped.tag == "skip"
    assert ("blocks", 0, "fc1", "w") not in report.masks
    np.testing.assert_array_equal(                    # dense = untouched
        np.asarray(pruned["blocks"][0]["fc1"]["w"]),
        np.asarray(params["blocks"][0]["fc1"]["w"]))
    assert by_path[("blocks", 1, "fc1", "w")].rule == 1
    assert by_path[("blocks", 1, "fc1", "w")].tag == nm.tag()
    assert by_path[("blocks", 0, "fc2", "w")].rule == 2

    rollup = {r["rule"]: r for r in report.rule_rollup()}
    assert rollup[0]["layers"] == 1 and rollup[0]["action"] == "skip"
    assert rollup[1]["layers"] == 1 and rollup[1]["tag"] == nm.tag()
    assert rollup[2]["layers"] == 2
    assert abs(rollup[2]["mean_sparsity"] - 0.5) < 1e-6

    # report JSON embeds the plan → run reproducible from the artifact
    art = json.loads(report.to_json())
    assert PrunePlan.from_dict(art["plan"]) == plan
    assert {l["path"] for l in art["layers"]} == \
           {f"blocks/{i}/{n}/w" for i in range(2) for n in ("fc1", "fc2")}


def test_prune_layer_sharded_accepts_plan():
    from jax.sharding import Mesh

    from repro.dist.prune import prune_layer_sharded

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(16, 16)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    h = 2.0 * x.T @ x
    cfg = PruneConfig(method="thanos", pattern="nm", n=2, m=4, block_size=8)
    plan = PrunePlan(rules=(PruneRule(match="embed*", cfg=None),
                            PruneRule(match="*", cfg=cfg)))

    direct = prune_layer(w, h, cfg)
    via_plan = prune_layer_sharded(w, h, plan, mesh,
                                   path=("blocks", 0, "mlp", "up", "w"))
    np.testing.assert_array_equal(np.asarray(direct.mask),
                                  np.asarray(via_plan.mask))
    np.testing.assert_array_equal(np.asarray(direct.weights),
                                  np.asarray(via_plan.weights))

    skipped = prune_layer_sharded(w, h, plan, mesh, path=("embed", "table"))
    np.testing.assert_array_equal(np.asarray(skipped.weights), np.asarray(w))
    assert float(jnp.sum(skipped.mask)) == 0.0
    assert float(skipped.loss) == 0.0


def test_abstract_nm_params_mixed_plan():
    from repro.configs.registry import get_config
    from repro.core.schedule import get_path
    from repro.launch.steps import abstract_nm_params
    from repro.models.model_builder import build_model

    model = build_model(get_config("tinyllama-1.1b", reduced=True))
    plan = PrunePlan(rules=(
        PruneRule(match="*/mlp/*",
                  cfg=PruneConfig(pattern="nm", n=2, m=4)),
        PruneRule(match="*/attn/*", cfg=PruneConfig(p=0.5)),
    ))
    a = abstract_nm_params(model, plan=plan)
    mlp = get_path(a, ("blocks", 0, "mlp", "up", "w"))
    assert isinstance(mlp, NmCompressed) and (mlp.n, mlp.m) == (2, 4)
    attn = get_path(a, ("blocks", 0, "attn", "wq", "w"))
    assert isinstance(attn, jax.ShapeDtypeStruct)     # dense under the plan
    with pytest.raises(ValueError, match="needs"):
        abstract_nm_params(model)


# ==========================================================================
# acceptance: mixed recipe on a zoo model → mixed-residency serving
# ==========================================================================
@pytest.fixture(scope="module")
def zoo_mixed():
    from repro.configs.registry import get_config
    from repro.data.pipeline import calibration_batches
    from repro.models.model_builder import ModelAdapter, build_model

    cfg = get_config("tinyllama-1.1b", reduced=True)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batches = calibration_batches(cfg, num_samples=8, seq_len=32, batch=8)
    plan = PrunePlan(rules=(
        PruneRule(match="embed*", cfg=None, name="dense-embeddings"),
        PruneRule(match="*/mlp/*",
                  cfg=PruneConfig(method="thanos", pattern="nm", n=2, m=4,
                                  block_size=32), name="mlp-2to4"),
        PruneRule(match="*/attn/*",
                  cfg=PruneConfig(method="thanos", p=0.5, block_size=32),
                  name="attn-unstructured"),
    ))
    pruned, report = prune_model(params, ModelAdapter(model), batches, plan)
    return cfg, model, pruned, report, plan


def test_mixed_recipe_zoo_end_to_end(zoo_mixed):
    from repro.core.masks import check_nm
    from repro.serve.compressed import compress_params

    cfg, model, pruned, report, plan = zoo_mixed
    # attribution: every mlp layer 2:4, every attn layer ~0.5 unstructured
    for rep in report.layers:
        s = "/".join(map(str, rep.path))
        if "/mlp/" in s:
            assert rep.tag == "thanos_2:4"
            assert bool(check_nm(jnp.asarray(report.masks[rep.path]).T, 2, 4))
        elif "/attn/" in s:
            assert rep.tag == "thanos_p0.5"
            assert abs(rep.sparsity - 0.5) < 0.01

    comp = compress_params(pruned, report.masks, plan=report.plan)
    n_comp = n_dense = 0
    for kp, leaf in jax.tree_util.tree_leaves_with_path(
            comp, is_leaf=lambda x: isinstance(x, NmCompressed)):
        s = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in kp)
        if isinstance(leaf, NmCompressed):
            n_comp += 1
            assert "/mlp/" in s
        elif "/attn/" in s and s.endswith("/w"):
            n_dense += 1
    assert n_comp > 0 and n_dense > 0     # genuinely mixed residency

    # report JSON round-trips the plan (reproducible from the artifact)
    art = json.loads(report.to_json())
    assert PrunePlan.from_dict(art["plan"]) == plan


def test_mixed_residency_serving_bit_identical(zoo_mixed):
    from repro.serve import Request, ServeConfig, ServingEngine
    from repro.serve.compressed import compress_params

    cfg, model, pruned, report, plan = zoo_mixed
    comp = compress_params(pruned, report.masks, plan=report.plan)

    outs = {}
    for tag, p in (("dense", pruned), ("mixed", comp)):
        engine = ServingEngine(model, p,
                               ServeConfig(batch_slots=2, max_len=24))
        rng = np.random.default_rng(0)
        for uid in range(4):
            engine.submit(Request(
                uid, rng.integers(0, cfg.vocab_size, size=8), max_new=6))
        outs[tag] = [r.out for r in engine.run()]
    assert outs["dense"] == outs["mixed"]
