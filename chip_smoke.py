"""Smoke run of the main path on a TPU: prune → compress → serve
h2o-danube-1.8b at its published widths, with seeded random weights.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: the row-parallel prune
                                       # and its single-device comparison only

One chip, in one process:

    init       build the model and init all 24 layers on the chip;
    precision  prune one full-width down projection (2560×6912) with its
               calibration Hessian on the chip, at default and at
               ``highest`` matmul precision, and on the host CPU, and
               compare masks and reconstruction error;
    prune      ``prune_arch(..., reduced=False)`` with a ``PrunePlan``:
               Thanos 2:4 on every linear of blocks 0 and 1 (all seven
               linear shapes), every other layer skipped, ``on_singular=
               "fail"``; any escalation, fallback or skipped calibration
               batch fails the run;
    compress   ``compress_params`` and the compressed/dense byte ratio;
    serve      a continuous ``ServingEngine``: 4 slots, 4 requests with
               prompts of 16-64 tokens, 8 new tokens each;
    logits     every compressed linear through the n:m kernel against the
               jnp reference; prefill + decode logits through the
               engine's own jitted steps, compressed-resident against the
               dense path on ``decompress_params`` of the same tree, both
               measured against the fp32 logits of those weights on the
               host CPU; the engine's compiled prefill and decode must
               hold the n:m kernel (``tpu_custom_call``) — a silent fall
               back to the jnp reference fails the run.

Four chips (``--four-chips``): blocks 0-1 pruned by ``prune_model`` on a
("data", "model") = (1, 4) mesh and on one device.  The sharded result
must sit on four devices, and for the layers in ``SLICE_CHECKED`` the
sharded solve must equal, mask for mask, the single-device solve of each
shard's rows under the same Hessian.  Against the single-device prune of
all rows, masks are compared and reported, and each layer's OBS loss is
held to the precision phase's limit.

Per-phase wall time (compilation included), compile counts and peak device
memory are printed as they finish: this is a smoke run, not a benchmark.
The last line is one JSON object naming the device.  Without a TPU the
script exits non-zero before any phase.  Every phase is a plain function,
so tests/test_chip_smoke.py runs the same control flow on the CPU at the
``REDUCED`` config.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.core import (  # noqa: E402
    HessianAccumulator, PruneConfig, PrunePlan, PruneRule, get_path,
    prune_layer, prune_model, reconstruction_error,
)
from repro.core.sparsity import NmCompressed, unpack_nm  # noqa: E402
from repro.data.pipeline import calibration_batches  # noqa: E402
from repro.dist.prune import prune_layer_sharded, row_partition  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.prune import prune_arch  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.model_builder import ModelAdapter, build_model  # noqa: E402
from repro.serve import Request, ServeConfig, ServingEngine  # noqa: E402
from repro.serve.compressed import (  # noqa: E402
    compress_params, compressed_bytes, decompress_params,
)
from repro.util.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "h2o-danube-1.8b"
CELL = PruneConfig(method="thanos", pattern="nm", n=2, m=4, block_size=64)
PRUNED_BLOCKS = (0, 1)
CALIB = dict(num_samples=16, seq_len=128, batch=8)   # prune_arch's defaults
PRECISION_PATH = ("blocks", 0, "mlp", "down", "w")
PROMPT_LENS = (16, 64, 32, 48)
MAX_NEW = 8
SLOTS = 4

# precision: the chip's prune of one layer against the host CPU's fp32
# prune of the same weights with the same Hessian.  The reconstruction
# error ‖ΔWX‖² is what the mask and the OBS update are chosen to minimise,
# so the chip may not be materially worse than the host on it.  Masks are
# only a sanity floor: block k's 2:4 choice compares saliencies of weights
# that blocks 0..k-1 have already updated, so one rounding-level flip
# changes every later choice of its row that is near a tie.  Agreement
# therefore falls with the column position (block 0 sees the untouched
# weights) while the error does not move: on a v5e at default matmul
# precision 93% of the down projection's mask entries agreed at an error
# ratio of 1.0006.  Two unrelated 2:4 masks agree on half their entries.
MIN_MASK_AGREEMENT = 0.9
MAX_ERR_RATIO = 1.01

# kernel: every compressed linear through the n:m kernel against
# the jnp reference (XLA's dot of the same expanded bf16 weights).  Both
# form exact bf16 products, sum them in fp32 in different orders and round
# once to bf16, so they may differ by one bf16 ulp of the output (at most
# 2^-7 of its magnitude) plus the fp32 reassociation error, which for a sum
# of K ≤ 6912 terms stays far below 2^-16 of Σ|x||w|.
KERNEL_ULP_RTOL = 2.0 ** -7
KERNEL_SUM_RTOL = 2.0 ** -16

# logits: the compressed-resident model (n:m kernel) and the dense path on
# the decompressed tree are two bf16 evaluations of the same weights, and
# over 24 random-weight blocks they do not agree to a bf16 rounding: one
# rounding that falls the other way in a compressed linear's output passes
# through every later block, and each bf16 op after it flips roundings of
# its own (on a v5e the two differed by up to 6% relative L2 per position).
# Both are therefore measured against the exact logits, the same weights
# at fp32 on the host CPU, and the dense path's distance from those is the
# noise floor the limits are stated in:
#   * the kernel path may not be less accurate than XLA's dense one:
#     rel(nm, fp32) ≤ 1.5·floor, the margin for XLA keeping excess
#     precision inside fusions where the kernel's output is rounded;
#   * nm against dense: each near the floor from the fp32 logits, so at
#     most twice it from each other: rel(nm, dense) ≤ 2·floor.
# A kernel fault (a wrong slot, nibble or tile) moves a linear's output by
# O(1) and fails both.  The floor is not taken below 1e-5, the fp32
# reassociation noise of an fp32 model whose dense path is exact.
LOGITS_ACCURACY_RATIO = 1.5
LOGITS_AGREEMENT_RATIO = 2.0
LOGITS_MIN_FLOOR = 1e-5

# four chips: the row-parallel solve runs, on each device, the single-
# device solve of that device's rows.  For the layers below it must equal
# that solve under the same Hessian: masks exactly, and weights to one
# ulp of the stored dtype (bf16 at full width: the fp32 result is rounded
# once on output, so an fp32 reassociation in the compiled shard program
# can move a stored weight by at most that).  Their shard shape, (2560/4,
# 2560) at full width, is the full shape of the K/V projections, so the
# single-device reference needs no compile of its own.  Against the
# single-device prune of all rows, a program compiled for another row
# count, masks are reported, and each layer's OBS loss, the objective the
# solve minimises, is held to MAX_ERR_RATIO of the single-device one.
SLICE_CHECKED = (("attn", "wq"), ("attn", "wo"))


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def init_phase(cfg, seed: int = 0):
    """Build the model and init every layer on the default device."""
    model = build_model(cfg)
    params = jax.block_until_ready(model.init(jax.random.PRNGKey(seed)))
    require(len(params["blocks"]) == cfg.num_layers,
            f"{len(params['blocks'])} blocks initialised, want "
            f"{cfg.num_layers}")
    leaves = jax.tree.leaves(params)
    n_params = sum(x.size for x in leaves)
    require(all(bool(jnp.isfinite(x).all()) for x in leaves),
            "non-finite initial weights")
    return model, params, {"layers": cfg.num_layers, "params": n_params}


def smoke_plan(blocks=PRUNED_BLOCKS, cell: PruneConfig = CELL) -> PrunePlan:
    """Thanos on every linear of ``blocks``; every other layer skipped."""
    rules = [PruneRule(match=f"blocks/{i}/*", cfg=cell, name=f"block{i}",
                       on_singular="fail") for i in blocks]
    return PrunePlan(rules=(*rules, PruneRule(match="*", name="skip")))


def block_hessians(model, params, batches, block: int) -> dict:
    """The Hessian of every linear of ``block`` as ``prune_model``
    accumulates it: each calibration batch through the blocks before
    ``block`` as ``params`` holds them, then ``block``'s captured inputs,
    with the same jitted programs and in the same batch order."""
    adapter = ModelAdapter(model)
    fwd = jax.jit(lambda p, c, i: adapter.block_apply(p, i, c,
                                                      capture=False)[0],
                  static_argnums=(2,))
    cap = jax.jit(lambda p, c, i: adapter.block_apply(p, i, c, capture=True),
                  static_argnums=(2,))
    accs: dict = {}
    for batch in batches:
        carry = adapter.prepare(params, batch)
        for i in range(block):
            carry = fwd(params, carry, i)
        for path, x in cap(params, carry, block)[1].items():
            if path not in accs:
                accs[path] = HessianAccumulator.init(x.shape[-1])
            accs[path] = accs[path].update(x)
    return {path: acc.finalize() for path, acc in accs.items()}


def mask_agreement(a, b, block_size: int = CELL.block_size) -> dict:
    """Share of equal (c, b) mask entries: overall, in the first and last
    column block, and the first column block holding a difference."""
    same = np.asarray(a) == np.asarray(b)
    per_block = same.reshape(same.shape[0], -1, block_size).all(axis=(0, 2))
    return {"agreement": float(same.mean()),
            "first_block": float(same[:, :block_size].mean()),
            "last_block": float(same[:, -block_size:].mean()),
            "first_differing_block": (None if per_block.all()
                                      else int(np.argmin(per_block)))}


def precision_phase(model, params, cfg, reference_device):
    """Prune one layer on the default device, at default and at
    ``highest`` matmul precision, and on ``reference_device`` with the same
    weights and calibration Hessian; compare each with the reference.  The
    limits hold the default precision, which the prune path runs at."""
    h = block_hessians(model, params, calibration_batches(cfg, **CALIB),
                       PRECISION_PATH[1])[PRECISION_PATH]
    w = get_path(params, PRECISION_PATH).T             # (c, b) paper layout
    chip = prune_layer(w, h, CELL)
    with jax.default_matmul_precision("highest"):
        chip_highest = prune_layer(w, h, CELL)

    to_ref = lambda x: jax.device_put(x, reference_device)  # noqa: E731
    w_ref, h_ref = to_ref(w), to_ref(h)
    with jax.default_device(reference_device):
        host = prune_layer(w_ref, h_ref, CELL)
        err_host = float(reconstruction_error(w_ref, host.weights, h_ref))
        errs = [float(reconstruction_error(w_ref, to_ref(r.weights), h_ref))
                for r in (chip, chip_highest)]
    out = {"shape": list(w.shape), "err_host": err_host,
           "obs_loss_host": float(host.loss)}
    for prefix, r, err in (("", chip, errs[0]),
                           ("highest_", chip_highest, errs[1])):
        agree = mask_agreement(r.mask, host.mask)
        out.update({f"{prefix}mask_agreement": agree["agreement"],
                    f"{prefix}mask_agreement_first_block":
                        agree["first_block"],
                    f"{prefix}mask_agreement_last_block": agree["last_block"],
                    f"{prefix}err_chip": err,
                    f"{prefix}err_ratio": err / err_host,
                    f"{prefix}obs_loss_chip": float(r.loss)})
    require(np.isfinite(errs).all() and np.isfinite(err_host),
            f"non-finite reconstruction error: {out}")
    require(out["mask_agreement"] >= MIN_MASK_AGREEMENT,
            f"mask agreement {out['mask_agreement']} < "
            f"{MIN_MASK_AGREEMENT}: {out}")
    require(out["err_ratio"] <= MAX_ERR_RATIO,
            f"chip reconstruction error {out['err_ratio']}× the host's "
            f"(limit {MAX_ERR_RATIO}): {out}")
    return out


def check_reports(report, model, params_like, blocks=PRUNED_BLOCKS) -> int:
    """Every linear of ``blocks`` pruned 2:4 cleanly, nothing else pruned."""
    want = {p for i in blocks
            for p in model.block_linear_paths(params_like, i)}
    pruned = [r for r in report.layers if not r.skipped]
    require({r.path for r in pruned} == want,
            f"pruned {sorted(r.path for r in pruned)}, want {sorted(want)}")
    for r in pruned:
        require(not (r.damp_attempts or r.fallback or r.calib_skipped),
                f"{r.path}: damp_attempts={r.damp_attempts} "
                f"fallback={r.fallback!r} calib_skipped={r.calib_skipped}")
        require(r.sparsity == CELL.n / CELL.m,
                f"{r.path}: sparsity {r.sparsity}")
    return len(pruned)


def prune_phase(model, reduced: bool):
    """``prune_arch`` under the smoke plan; fails on any guard event."""
    pruned, report, out = prune_arch(ARCH, smoke_plan(), reduced=reduced,
                                     log=None, on_singular="fail", **CALIB)
    n = check_reports(report, model, pruned)
    require(np.isfinite(out["dense_loss"]) and np.isfinite(out["pruned_loss"]),
            f"non-finite held-out loss: {out}")
    return pruned, report, {"layers_pruned": n,
                            "dense_loss": out["dense_loss"],
                            "pruned_loss": out["pruned_loss"],
                            "prune_seconds": report.seconds}


def compress_phase(pruned, report):
    """Pack the n:m layers; the ratio must be the layout's byte count."""
    params = compress_params(pruned, report.masks, plan=report.plan,
                             strict=True)
    comp, dense = compressed_bytes(params)
    keep, m = CELL.m - CELL.n, CELL.m
    isz = get_path(pruned, next(iter(report.masks))).dtype.itemsize
    want = (keep * isz + (keep + 1) // 2) / (m * isz)
    ratio = comp / dense
    require(abs(ratio - want) < 1e-9, f"byte ratio {ratio}, want {want}")
    return params, {"compressed_bytes": comp, "dense_bytes": dense,
                    "ratio": ratio}


def serve_phase(model, params, *, impl: str = "", seed: int = 0,
                prompt_lens=PROMPT_LENS, max_new: int = MAX_NEW,
                slots: int = SLOTS):
    """Continuous batching over the compressed tree; every request ends."""
    engine = ServingEngine(model, params, ServeConfig(
        batch_slots=slots, max_len=max(prompt_lens) + max_new + 8,
        scheduler="continuous", nm_impl=impl))
    rng = np.random.default_rng(seed)
    vocab = model.cfg.vocab_size
    for uid, n in enumerate(prompt_lens):
        engine.submit(Request(uid, rng.integers(0, vocab, size=n),
                              max_new=max_new))
    t0 = time.perf_counter()
    done = engine.run()
    dt = time.perf_counter() - t0
    require(len(done) == len(prompt_lens), f"{len(done)} requests returned")
    for r in done:
        require(r.done and not r.error and len(r.out) == max_new
                and all(0 <= t < vocab for t in r.out),
                f"request {r.uid}: done={r.done} error={r.error!r} "
                f"out={r.out}")
    tokens = sum(len(r.out) for r in done)
    return {"requests": len(done), "tokens": tokens, "run_s": dt,
            "decode_steps": engine.stats["decode_steps"]}


def engine_logits(model, params, tokens, prompt_len: int, *,
                  impl: str = ""):
    """Teacher-forced logits through a ``ServingEngine``'s own jitted
    prefill and decode steps, compiled under the engine's n:m kernel
    config: the prompt's last position, then one decode step (per-slot
    positions, as the continuous scheduler runs it) for each later token.
    Returns fp32 (1 + S - prompt_len, B, V) and whether both compiled
    programs hold a ``tpu_custom_call``."""
    B, S = tokens.shape
    engine = ServingEngine(model, params, ServeConfig(
        batch_slots=B, max_len=S + 1, scheduler="continuous", nm_impl=impl))
    with L.nm_kernel_scope(engine.nm_kernel):
        cache = model.init_cache(B, engine.cfg.max_len)
        prompt = tokens[:, :prompt_len]
        prefill = engine._prefill.lower(params, cache, prompt, 0).compile()
        cache, last = prefill(params, cache, prompt, 0)
        out = [last]
        pos = jnp.full((B,), prompt_len, jnp.int32)
        decode = engine._decode.lower(params, cache, tokens[:, :1],
                                      pos).compile()
        for p in range(prompt_len, S):
            logits, cache = decode(params, cache, tokens[:, p:p + 1],
                                   jnp.full((B,), p, jnp.int32))
            out.append(logits.astype(jnp.float32))
    in_hlo = all("tpu_custom_call" in c.as_text() for c in (prefill, decode))
    return jnp.stack(out), in_hlo


def kernel_parity(params, *, impl: str = "", batch: int = 8,
                  seed: int = 0) -> float:
    """Worst error of every compressed linear through the n:m kernel
    against the jnp reference, in units of the allowed error (≤ 1)."""
    rng = np.random.default_rng(seed + 2)
    worst = 0.0
    for w in jax.tree.leaves(params["blocks"], is_leaf=lambda v:
                             isinstance(v, NmCompressed)):
        if not isinstance(w, NmCompressed):
            continue
        x = jnp.asarray(rng.standard_normal((batch, w.b)), w.values.dtype)
        got = ops.nm_matmul(x, w, impl=impl or "auto").astype(jnp.float32)
        want = ops.nm_matmul(x, w, impl="ref").astype(jnp.float32)
        sum_abs = (jnp.abs(x).astype(jnp.float32)
                   @ jnp.abs(unpack_nm(w)).astype(jnp.float32).T)
        tol = KERNEL_ULP_RTOL * jnp.abs(want) + KERNEL_SUM_RTOL * sum_abs
        worst = max(worst, float(jnp.max(jnp.abs(got - want) / tol)))
    return worst


def fp32_logits(model, params, tokens, prompt_len: int, device):
    """``engine_logits`` of the same weights at fp32 on ``device`` (the
    host CPU, whose fp32 matmuls are exact fp32)."""
    model32 = build_model(model.cfg.replace(dtype="float32"))

    def to_fp32(x):
        x = jax.device_put(x, device)
        return x.astype(jnp.float32) if jnp.issubdtype(x.dtype,
                                                       jnp.floating) else x

    with jax.default_device(device), jax.default_matmul_precision("highest"):
        logits, _ = engine_logits(
            model32, jax.tree.map(to_fp32, params),
            jax.device_put(tokens, device), prompt_len, impl="ref")
    return logits


def logits_phase(model, params, reference_device, *, impl: str = "",
                 seed: int = 0, prompt_len: int = 16, decode_len: int = 8,
                 batch: int = SLOTS):
    """Compressed-resident logits against the decompressed dense path, both
    through the engine's steps and measured against the fp32 logits on
    ``reference_device``."""
    kernel_err = kernel_parity(params, impl=impl, seed=seed)
    require(kernel_err <= 1.0, f"n:m kernel off the jnp reference by "
            f"{kernel_err}× the allowed error")
    rng = np.random.default_rng(seed + 1)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab_size,
                                      size=(batch, prompt_len + decode_len)),
                         jnp.int32)
    nm, in_hlo = engine_logits(model, params, tokens, prompt_len, impl=impl)
    dense_params = decompress_params(params)
    dense, _ = engine_logits(model, dense_params, tokens, prompt_len,
                             impl=impl)
    exact = fp32_logits(model, dense_params, tokens, prompt_len,
                        reference_device)
    del dense_params
    nm, dense, exact = map(np.asarray, (nm, dense, exact))
    require(np.isfinite(nm).all() and np.isfinite(dense).all(),
            "non-finite logits")

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    out = {"kernel_err_over_tol": kernel_err,
           "argmax_agreement": float(np.mean(nm.argmax(-1)
                                             == dense.argmax(-1))),
           "kernel_in_hlo": in_hlo}
    for name, pos in (("prefill", slice(0, 1)), ("decode", slice(1, None))):
        r = {"nm_vs_dense": rel(nm[pos], dense[pos]),
             "nm_vs_fp32": rel(nm[pos], exact[pos]),
             "dense_vs_fp32": rel(dense[pos], exact[pos])}
        out.update({f"{name}_{k}": v for k, v in r.items()})
        floor = max(r["dense_vs_fp32"], LOGITS_MIN_FLOOR)
        require(r["nm_vs_fp32"] <= LOGITS_ACCURACY_RATIO * floor,
                f"{name}: compressed logits less accurate than dense: {out}")
        require(r["nm_vs_dense"] <= LOGITS_AGREEMENT_RATIO * floor,
                f"{name}: compressed vs dense logits beyond "
                f"{LOGITS_AGREEMENT_RATIO}× the bf16 floor: {out}")
    return out


def four_chip_phase(reduced: bool, n_devices: int = 4, log=None):
    """Blocks 0-1 pruned row-parallel on a ("data", "model") = (1, n) mesh
    against the single-device prune of the same weights and batches, and,
    for the ``SLICE_CHECKED`` layers, against the single-device solve of
    each shard's rows under the Hessian the sharded run saw.

    Every measurement is taken before any limit is applied, so a failing
    run reports all of them.  Depth is cut to the two pruned blocks: the
    plan skips every later block, which would only add forward passes to
    both sides."""
    devices = jax.devices()[:n_devices]
    require(len(devices) == n_devices,
            f"{len(devices)} devices, want {n_devices}")
    mesh = Mesh(np.array(devices).reshape(1, n_devices), ("data", "model"))
    cfg = registry.get_config(ARCH, reduced=reduced).replace(
        num_layers=len(PRUNED_BLOCKS))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batches = calibration_batches(cfg, **CALIB)
    prune = functools.partial(prune_model, params, ModelAdapter(model),
                              batches, smoke_plan(), on_singular="fail",
                              progress=log)
    # the two prunes run in two threads only so that their eight cold solve
    # compiles overlap; the compiled programs are deterministic, and every
    # same-Hessian comparison below runs alone, after both have finished
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        f_one, f_many = pool.submit(prune), pool.submit(prune, mesh=mesh)
        (_, rep_one), (many, rep_many) = f_one.result(), f_many.result()
    check_reports(rep_one, model, params)
    n = check_reports(rep_many, model, params)
    loss_one = {r.path: r.obs_loss for r in rep_one.layers}

    failures, layers = [], {}
    for r in rep_many.layers:
        if r.skipped:
            continue
        wn = get_path(many, r.path)
        devs = {s.device for s in wn.addressable_shards}
        if len(devs) != n_devices or (n_devices > 1
                                      and wn.sharding.is_fully_replicated):
            failures.append(f"{r.path}: result on {len(devs)} device(s), "
                            f"not sharded over {n_devices}")
        row = mask_agreement(rep_many.masks[r.path].T,
                             rep_one.masks[r.path].T)
        row["obs_loss_ratio"] = r.obs_loss / loss_one[r.path]
        if not row["obs_loss_ratio"] <= MAX_ERR_RATIO:
            failures.append(f"{r.path}: OBS loss {row['obs_loss_ratio']}× "
                            f"the single-device one (limit {MAX_ERR_RATIO})")
        layers["/".join(map(str, r.path[1:4]))] = row

    # same Hessian: the sharded solve against each shard's rows solved on
    # one device.  Block i's Hessians come from the sharded run's params
    # with block i itself still dense, as prune_model saw them.
    for i in PRUNED_BLOCKS:
        at_capture = dict(many, blocks={
            j: many["blocks"][j] if j < i else b
            for j, b in params["blocks"].items()})
        hs = block_hessians(model, at_capture, batches, i)
        for path in model.block_linear_paths(params, i):
            if path[2:4] not in SLICE_CHECKED:
                continue
            w, h = get_path(params, path).T, hs[path]
            sharded = prune_layer_sharded(w, h, CELL, mesh)
            k = int(np.prod([mesh.shape[a]
                             for a in row_partition(w.shape[0], mesh)]))
            one = functools.partial(jax.device_put, device=devices[0])
            parts = [prune_layer(one(s), one(h), CELL)
                     for s in jnp.split(w, k)]
            mask_ref = np.concatenate([np.asarray(p.mask) for p in parts])
            w_ref = np.concatenate([np.asarray(p.weights, np.float32)
                                    for p in parts])
            dw = np.abs(np.asarray(sharded.weights, np.float32) - w_ref)
            ulp = float(jnp.finfo(w.dtype).eps)
            row = layers["/".join(map(str, path[1:4]))]
            row.update({
                "shards": k,
                "equal_to_shard_solves": bool(np.array_equal(
                    np.asarray(sharded.mask), mask_ref)),
                "max_ulps_from_shard_solves": float(
                    np.max(dw / np.maximum(np.abs(w_ref), 1e-30)) / ulp),
                "reproduces_prune_model": bool(np.array_equal(
                    np.asarray(sharded.mask),
                    np.asarray(rep_many.masks[path]).T)),
            })
            if not (row["equal_to_shard_solves"]
                    and np.all(dw <= ulp * np.abs(w_ref))):
                failures.append(f"{path}: sharded solve differs from the "
                                f"single-device solves of its shards")
    out = {"layers_compared": n, "devices": n_devices,
           "layers_with_equal_masks": sum(
               r["agreement"] == 1.0 for r in layers.values()),
           "min_mask_agreement": min(r["agreement"]
                                     for r in layers.values()),
           "max_obs_loss_ratio": max(r["obs_loss_ratio"]
                                     for r in layers.values()),
           "layers": layers}
    require(not failures, f"{failures}: {json.dumps(out)}")
    return out


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------
class PhaseMeter:
    """Per-phase wall time, backend compiles, persistent-cache hits
    (``repro.obs``) and the device's peak memory so far, printed as one
    line per phase."""

    def __init__(self):
        obs.install()

    @contextlib.contextmanager
    def phase(self, name: str, result: dict):
        j0, t0 = obs.jit_counts(), time.perf_counter()
        yield
        j1 = obs.jit_counts()
        hits = j1["persistent_cache_hits"] - j0["persistent_cache_hits"]
        stats = jax.devices()[0].memory_stats() or {}
        line = {"wall_s": round(time.perf_counter() - t0, 3),
                "compiles": (j1["backend_compiles"] - j0["backend_compiles"]
                             - hits),
                "cache_hits": hits,
                "peak_bytes": stats.get("peak_bytes_in_use"), **result}
        print(f"[smoke run, not a benchmark] {name}: {json.dumps(line)}",
              flush=True)


def run_one_chip(meter: PhaseMeter) -> None:
    cfg = registry.get_config(ARCH, reduced=False)
    r: dict = {}
    with meter.phase("init", r):
        model, params, info = init_phase(cfg)
        r.update(info)
    r = {}
    with meter.phase("precision", r):
        r.update(precision_phase(model, params, cfg, jax.devices("cpu")[0]))
    del params
    r = {}
    with meter.phase("prune", r):
        pruned, report, info = prune_phase(model, reduced=False)
        r.update(info)
    r = {}
    with meter.phase("compress", r):
        params, info = compress_phase(pruned, report)
        r.update(info)
    del pruned, report
    r = {}
    with meter.phase("serve", r):
        r.update(serve_phase(model, params))
    r = {}
    with meter.phase("logits", r):
        r.update(logits_phase(model, params, jax.devices("cpu")[0]))
        require(r["kernel_in_hlo"], "compiled compressed-resident program "
                "holds no tpu_custom_call: the n:m kernel did not run")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-parallel prune on four chips "
                         "and its single-device comparison")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = PhaseMeter()
    if args.four_chips:
        r: dict = {}
        with meter.phase("four_chip_prune", r):
            r.update(four_chip_phase(
                reduced=False, log=lambda s: print(s, flush=True)))
    else:
        run_one_chip(meter)
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
