"""The check catches a broken timed path: each cell's run is driven on the
CPU at the REDUCED size (the harness's look for a chip is skipped by
calling the driver) with one fault planted in the program underneath, and
``correct`` must come out false (test_bench_cells.py runs the same cells
unbroken and sees it true).  The faults are those a cell can have on
one chip: a step that returns its state unchanged, half of the batch left
out, an answer altered where it is produced."""
from __future__ import annotations

import bench_testing
import jax.numpy as jnp
import pytest

from bench import harness
from repro.core import schedule
from repro.core.hessian import HessianAccumulator
from repro.serve import engine as engine_mod

SERVE_CELLS = [n for n in harness.workload_names() if "serve" in n]


def _run(name, seed=7):
    cell = bench_testing.reduced_cell(name)
    # every finished request checked, so that a fault confined to some
    # slots cannot hide behind the sample (a run checks 8)
    cell["traffic"]["check_requests"] = 10 ** 6
    return harness.driver(cell["traffic"]["kind"]).run(cell, seed, 1.0,
                                                       False)


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def _decode_keeps_cache(model, params, cache, tokens, pos):
    logits, _ = model.decode_step(params, cache, tokens, pos)
    return logits[:, -1, :], cache


def _decode_half_batch(model, params, cache, tokens, pos):
    logits, cache = model.decode_step(params, cache, tokens, pos)
    half = logits.shape[0] // 2
    logits = logits.at[half:].set(logits[:logits.shape[0] - half])
    return logits[:, -1, :], cache


def _select_altered(self, logits):
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return tok.at[0].set((tok[0] + 1) % logits.shape[-1])


@pytest.mark.parametrize("name", SERVE_CELLS)
@pytest.mark.parametrize("target,fault", [
    ("_decode_fn", _decode_keeps_cache),
    ("_decode_fn", _decode_half_batch),
    ("ServingEngine._select", _select_altered),
], ids=["state-unchanged", "half-batch", "token-altered"])
def test_serve_fault_is_not_correct(monkeypatch, name, target, fault):
    obj, attr = ((engine_mod.ServingEngine, "_select") if "." in target
                 else (engine_mod, target))
    monkeypatch.setattr(obj, attr, fault)
    monkeypatch.setattr(engine_mod, "_JIT_CACHE", {})
    rec = _run(name)
    assert not rec["correct"], rec["compared"]


# --------------------------------------------------------------------------
# pruning
# --------------------------------------------------------------------------
def _guarded(change):
    orig = schedule.prune_layer_guarded

    def guarded(w, h, cfg, **kw):
        res, guard = orig(w, h, cfg, **kw)
        return res._replace(weights=change(w, res)), guard

    return guarded


def _unchanged(w, res):
    return w


def _altered(w, res):
    kept = jnp.argmin(res.mask[0])            # first kept weight of row 0
    return res.weights.at[0, kept].add(1.0)


def _half_batch_update():
    orig = HessianAccumulator.update
    calls = [0]

    def update(self, x, valid=None):
        calls[0] += 1
        # prune_model accumulates the 7 linears of a block per batch
        return self if (calls[0] - 1) // 7 % 2 else orig(self, x, valid)

    return update


@pytest.mark.parametrize("fault", ["state-unchanged", "half-batch",
                                   "answer-altered"])
def test_prune_fault_is_not_correct(monkeypatch, fault):
    if fault == "half-batch":
        monkeypatch.setattr(HessianAccumulator, "update",
                            _half_batch_update())
    else:
        monkeypatch.setattr(schedule, "prune_layer_guarded", _guarded(
            _unchanged if fault == "state-unchanged" else _altered))
    rec = _run("danube-prune-2to4")
    assert not rec["correct"], rec["compared"]
