"""The program's own observability on the CPU: the host spans of the
serving engine and the prune job as a profiler trace records them (names,
nesting, arguments), the names of their device programs, the compile and
collection counters of ``repro.obs``, and their export by ``GET /stats``."""
from __future__ import annotations

import asyncio
import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.base import ModelConfig
from repro.core import PruneConfig, prune_model
from repro.core.schedule import path_str
from repro.data.pipeline import calibration_batches
from repro.models.model_builder import ModelAdapter, build_model
from repro.serve import Request, ServeConfig, ServingEngine
from repro.serve.engine import _model_jits
from repro.serve.frontend import HttpFrontend, fetch_json

TINY = ModelConfig(
    name="obs-tiny", family="dense", num_layers=1, d_model=32,
    num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
    vocab_size=96, dtype="float32")
MAX_LEN = 32


@pytest.fixture(scope="module")
def setup():
    model = build_model(TINY)
    return model, model.init(jax.random.PRNGKey(0))


def _spans(log_dir) -> list[tuple[str, float, float, dict]]:
    """(name, start, end, arguments) of every program span of a trace."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "prune.")):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _parents(spans, name):
    """For each span called ``name``, the names of the spans around it."""
    return [{o[0] for o in spans if o is not s and _inside(s, o)}
            for s in spans if s[0] == name]


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served(setup, tmp_path_factory):
    """Two requests admitted into a two-slot engine, decoded to the end,
    under the profiler."""
    model, params = setup
    eng = ServingEngine(model, params, ServeConfig(batch_slots=2,
                                                   max_len=MAX_LEN))
    rng = np.random.default_rng(0)
    reqs = [Request(uid, rng.integers(0, TINY.vocab_size, size=5),
                    max_new=4) for uid in (7, 8)]
    log_dir = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(log_dir)
    try:
        for r in reqs:
            eng.submit(r)
        while eng.pump():
            pass
    finally:
        jax.profiler.stop_trace()
    return reqs, _spans(log_dir)


def test_serve_spans_and_their_nesting(served):
    reqs, spans = served
    names = [s[0] for s in spans]
    assert names.count("serve.submit") == 2
    assert names.count("serve.admit") == 2
    # four tokens each: the first at admission, three decode steps
    for kid in ("serve.decode", "serve.sample", "serve.absorb"):
        assert names.count(kid) == 3
    assert names.count("serve.pump") >= 3
    for kid in ("serve.row_init", "serve.prefill", "serve.write_slot",
                "serve.first_token"):
        assert names.count(kid) == 2
        assert all({"serve.admit", "serve.pump"} <= p
                   for p in _parents(spans, kid))
    for kid in ("serve.admit", "serve.decode", "serve.sample",
                "serve.absorb"):
        assert all(p == {"serve.pump"} for p in _parents(spans, kid))
    assert all(p == set() for p in _parents(spans, "serve.submit"))
    assert all(r.t_submit <= r.t_admit <= r.t_first for r in reqs)


def test_serve_span_arguments(served):
    _, spans = served
    admits = [s[3] for s in spans if s[0] == "serve.admit"]
    assert sorted((a["uid"], a["slot"], a["tokens"]) for a in admits) == \
        [(7, 0, 5), (8, 1, 5)]
    assert sorted(s[3]["uid"] for s in spans if s[0] == "serve.submit") == \
        [7, 8]
    steps = [s[3]["step"] for s in spans if s[0] == "serve.pump"]
    assert steps == sorted(steps) and steps[0] == 0


def test_engine_programs_are_named(setup):
    """A trace names each program after its function: decode and prefill
    are told apart by name, not by whether they loop."""
    model, params = setup
    jits = _model_jits(model, None)
    cache = model.init_cache(2, MAX_LEN)
    tokens = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    assert "module @jit_serve_decode" in jits["decode"].lower(
        params, cache, tokens, pos).as_text()
    row = model.init_cache(1, MAX_LEN)
    assert "module @jit_serve_prefill" in jits["prefill"].lower(
        params, row, jnp.zeros((1, 5), jnp.int32), 0).as_text()
    assert "module @jit_serve_write_slot" in jits["write_slot"].lower(
        cache, row, 0).as_text()


# --------------------------------------------------------------------------
# pruning
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def pruned(setup, tmp_path_factory):
    model, params = setup
    batches = calibration_batches(TINY, num_samples=4, seq_len=8, batch=2)
    log_dir = str(tmp_path_factory.mktemp("prune_trace"))
    jax.profiler.start_trace(log_dir)
    try:
        _, report = prune_model(params, ModelAdapter(model), batches,
                                PruneConfig(method="thanos", pattern="nm",
                                            n=2, m=4))
    finally:
        jax.profiler.stop_trace()
    return report, _spans(log_dir)


def test_prune_spans_and_their_nesting(pruned):
    report, spans = pruned
    names = [s[0] for s in spans]
    assert names.count("prune.call") == 1
    assert [s[3]["block"] for s in spans if s[0] == "prune.block"] == [0]
    assert names.count("prune.capture") == names.count("prune.propagate") == 1
    assert names.count("prune.hessian_update") == 2         # one per batch
    assert all(p == {"prune.call", "prune.block", "prune.capture"}
               for p in _parents(spans, "prune.hessian_update"))
    for kid in ("prune.capture", "prune.linear", "prune.propagate"):
        assert all(p == {"prune.call", "prune.block"}
                   for p in _parents(spans, kid))
    linears = [s for s in spans if s[0] == "prune.linear"]
    assert [s[3]["path"] for s in linears] == \
        [path_str(r.path) for r in report.layers]
    # each span holds the region its LayerReport.seconds times
    for s, r in zip(linears, report.layers):
        assert (s[2] - s[1]) * 1e-9 >= r.seconds


def test_prune_programs_are_named(setup):
    from repro.core.schedule import _block_jits

    model, params = setup
    fwd, cap = _block_jits(ModelAdapter(model))
    carry = ModelAdapter(model).prepare(
        params, calibration_batches(TINY, num_samples=2, seq_len=8,
                                    batch=2)[0])
    assert "module @jit_prune_forward" in fwd.lower(params, carry,
                                                   0).as_text()
    assert "module @jit_prune_capture" in cap.lower(params, carry,
                                                   0).as_text()


# --------------------------------------------------------------------------
# counters
# --------------------------------------------------------------------------
def test_a_fresh_jit_lowers_and_a_repeat_call_does_not():
    obs.install()
    obs.install()                       # idempotent: counted once
    x = jnp.arange(3.0)

    def twice(v):
        return v * 2

    f = jax.jit(twice)
    before = obs.jit_counts()
    f(x).block_until_ready()
    mid = obs.jit_counts()
    f(x).block_until_ready()
    after = obs.jit_counts()
    assert mid["lowerings"] == before["lowerings"] + 1
    assert mid["traces"] > before["traces"]
    assert mid["backend_compiles"] == before["backend_compiles"] + 1
    assert after == mid


def test_collections_are_counted():
    obs.install()
    before = obs.gc_counts()
    gc.collect()
    after = obs.gc_counts()
    assert after["gc_pauses"] == before["gc_pauses"] + 1
    assert after["gc_pause_s"] > before["gc_pause_s"]
    assert 0 < after["gc_pause_max_s"] <= after["gc_pause_s"]


def test_stats_endpoint_exports_the_counters(setup):
    model, params = setup
    eng = ServingEngine(model, params, ServeConfig(batch_slots=2,
                                                   max_len=MAX_LEN))

    async def main():
        fe = HttpFrontend(eng)
        await fe.start()
        try:
            return await fetch_json("127.0.0.1", fe.port, "/stats")
        finally:
            await fe.stop()

    stats = asyncio.run(main())
    assert set(stats["jit"]) == {"traces", "lowerings", "backend_compiles",
                                 "persistent_cache_hits"}
    assert set(stats["gc"]) == {"gc_pauses", "gc_pause_s", "gc_pause_max_s"}
    assert "decode_steps" in stats
