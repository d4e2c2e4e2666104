"""Every cell resolves by name to files that exist, BENCHMARK.json agrees
with them, a cell added as new files is found with no edit to a file that
is there, and the generators are pure functions of the seed."""
from __future__ import annotations

import json
import shutil

import bench_testing
import numpy as np
import pytest

from bench import harness
from bench import weights as W
from bench.drivers import closed_loop, prune_job

SPEC = json.loads((bench_testing.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", harness.workload_names())
def test_workload_resolves(name):
    cell = harness.workload(name)
    assert cell["config"]["name"] and cell["traffic"]["name"]
    for m in cell["end_to_end"] + cell["per_layer"]:
        reader = harness.metric(m)
        assert callable(reader.read) and reader.UNIT
    harness.driver(cell["traffic"]["kind"])
    harness.reference(cell["config"]["reference"])


def test_benchmark_json_agrees_with_the_workload_files():
    assert sorted(w["name"] for w in SPEC["workloads"]) == \
        harness.workload_names()
    units = {m["name"]: m["unit"] for m in SPEC["end_to_end"]
             + SPEC["per_layer"]}
    configs = {c["name"]: c for c in SPEC["configs"]}
    for w in SPEC["workloads"]:
        cell = harness.workload(w["name"])
        assert (w["config"], w["traffic"], w["chips"]) == (
            cell["config"]["name"], cell["traffic"]["name"], cell["chips"])
        assert configs[w["config"]]["file"] == \
            f"bench/configs/{w['config']}.json"
        assert configs[w["config"]]["source"] == cell["config"]["source"]
        for group in ("end_to_end", "per_layer"):
            listed = sorted(m["name"] for m in SPEC[group]
                            if w["name"] in m.get("workloads", [w["name"]]))
            assert listed == sorted(cell[group]), (w["name"], group)
        for m in cell["end_to_end"] + cell["per_layer"]:
            assert harness.metric(m).UNIT == units[m]
    for m in SPEC["per_layer"]:
        moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        for w in m["workloads"]:
            assert w in moved.get("workloads", [w]), (m["name"], w)


def test_a_cell_added_as_new_files_is_found(tmp_path, monkeypatch):
    """New configuration, traffic, cell and metric: files only."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((bench / "configs" / "h2o-danube-1.8b.json").read_text())
    (bench / "configs" / "new-model.json").write_text(
        json.dumps({**cfg, "num_layers": 2}))
    t = json.loads((bench / "traffic" / "decode-closed-16.json").read_text())
    (bench / "traffic" / "decode-closed-8.json").write_text(
        json.dumps({**t, "clients": 8, "slots": 8}))
    (bench / "metrics" / "tokens_per_request.py").write_text(
        'UNIT = "tokens"\n\n\ndef read(rec):\n'
        '    return rec["tokens"] / max(rec["attempted"], 1)\n')
    (bench / "workloads" / "new-serve.json").write_text(json.dumps({
        "config": "new-model", "traffic": "decode-closed-8", "chips": 1,
        "limits": {"served_logit_gap": 1.0},
        "end_to_end": ["decode_tok_s", "setup_s"],
        "per_layer": ["tokens_per_request"]}))
    monkeypatch.setattr(harness, "BENCH", bench)
    assert "new-serve" in harness.workload_names()
    cell = harness.workload("new-serve")
    assert cell["config"]["num_layers"] == 2
    assert cell["traffic"]["slots"] == 8
    assert harness.metric("tokens_per_request").read(
        {"tokens": 10, "attempted": 4}) == 2.5


def test_a_missing_file_is_named():
    with pytest.raises(harness.SpecError, match="no workload named"):
        harness.workload("no-such-cell")


# --------------------------------------------------------------------------
# generators
# --------------------------------------------------------------------------
def _requests(seed, n=12):
    t = harness.traffic("decode-closed-16")
    c = closed_loop.Clients(t, 32000, seed)
    return [(r.prompt.tolist(), r.max_new) for r in
            (c.next() for _ in range(n))]


SEEDS = (0, 2**31 + 12345)


@pytest.mark.parametrize("seed", SEEDS)
def test_requests_repeat_for_a_seed_and_differ_across_seeds(seed):
    assert _requests(seed) == _requests(seed)
    assert _requests(seed) != _requests(seed + 1)


def test_every_seed_deals_the_same_output_lengths():
    t = harness.traffic("decode-closed-16")
    pool = closed_loop.output_pool(t)
    assert len(pool) == t["output_len"]["pool"]
    assert pool.min() >= t["output_len"]["min"]
    assert pool.max() <= t["output_len"]["max"]
    assert abs(np.median(pool) - t["output_len"]["median"]) <= 16
    n = len(pool)
    dealt = [[r[1] for r in _requests(seed, 2 * n)] for seed in SEEDS]
    # one order for every seed: it decides how many admissions fall inside
    # a closed loop's window, and so the work of the window
    assert dealt[0] == dealt[1]
    assert sorted(dealt[0][:n]) == sorted(dealt[0][n:]) == sorted(pool.tolist())


@pytest.mark.parametrize("seed", SEEDS)
def test_calibration_tokens_repeat_and_differ(seed):
    t = {**harness.traffic("thanos-2to4-calib128x2048"),
         "calib_sequences": 4, "seq_len": 16}
    cfg = harness.config("h2o-danube-1.8b")
    a = prune_job.calibration(t, cfg, seed)
    assert a.shape == (4, 16) and a.dtype == np.int32
    np.testing.assert_array_equal(a, prune_job.calibration(t, cfg, seed))
    assert not np.array_equal(a, prune_job.calibration(t, cfg, seed + 1))


def test_weights_repeat_for_a_seed_and_differ_across_seeds():
    leaves = [("attn/wq/w", (8, 4)), ("ln1/scale", (8,))]
    a = W.make(SEEDS[1], 3, leaves)
    b = W.make(SEEDS[1], 3, leaves)
    c = W.make(SEEDS[1] + 1, 3, leaves)
    d = W.make(SEEDS[1], 4, leaves)
    np.testing.assert_array_equal(a["attn/wq/w"], b["attn/wq/w"])
    assert not np.array_equal(a["attn/wq/w"], c["attn/wq/w"])
    assert not np.array_equal(a["attn/wq/w"], d["attn/wq/w"])
    np.testing.assert_array_equal(a["ln1/scale"], np.ones(8))
    assert W.flatten(W.nest(a)).keys() == a.keys()
