"""Closed-loop serving through ``repro.serve.ServingEngine``.

As many clients as slots; each sends its next request when its previous
one finishes: an offline batch job at fixed concurrency.  Prompts have a
fixed length and token ids from the seed.  Output lengths come from a
fixed pool (the quantiles of a clipped lognormal), dealt in one fixed
order whatever the seed: in a closed loop the order decides how many
admissions, each a prefill that stalls every slot, fall inside the window,
so a seed-drawn order would change the work from seed to seed.  Greedy, no
stop token.

Set-up: the weights (from the seed, on the device, n:m-compressed block by
block when the configuration says so), the engine, and one request per
slot with output lengths staggered across the pool's mean, pumped until
every slot has prefilled and decoded once: every program the window runs
is then compiled, and the window opens on slots at staggered depths rather
than on sixteen simultaneous admissions.
"""
from __future__ import annotations

import functools
import gc
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, costs, harness, trace
from bench import weights as W


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------
def output_pool(t: dict) -> np.ndarray:
    """The multiset of output lengths every run deals from."""
    o = t["output_len"]
    q = (np.arange(o["pool"]) + 0.5) / o["pool"]
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
    lens = np.round(o["median"] * np.exp(o["sigma"] * z))
    return np.clip(lens, o["min"], o["max"]).astype(int)


class Clients:
    """Request stream of one run: prompts from the seed, output lengths
    from the pool in its fixed order."""

    def __init__(self, t: dict, vocab: int, seed: int):
        self.t, self.vocab, self.seed = t, vocab, int(seed)
        self.pool = output_pool(t)
        self._order = np.random.default_rng(0).permutation(self.pool)
        self._dealt = 0
        self.uid = 0

    def next(self, max_new: int | None = None):
        from repro.serve import Request

        if max_new is None:
            max_new = int(self._order[self._dealt % len(self._order)])
            self._dealt += 1
        prompt = W.tokens(self.seed, 100 + self.uid, (self.t["prompt_len"],),
                          self.vocab)
        self.uid += 1
        return Request(self.uid - 1, prompt, max_new=max_new)


# --------------------------------------------------------------------------
# set-up
# --------------------------------------------------------------------------
def row_chunk(c: int, g: int, budget: int = 2 ** 21) -> int:
    """Rows of a (c, b) weight to mask and pack at once: the largest
    divisor of ``c`` whose (rows, g) planes hold at most ``budget``
    elements.  The program's n:m masking and packing build (rows, g, ·)
    intermediates whose minor dimension a TPU pads to 128 lanes; whole, a
    Mistral-Large linear's would need 45 GB."""
    return max([1] + [r for r in range(1, c + 1)
                      if c % r == 0 and r * g <= budget])


def _pack_rows(w, n, m):
    """(values, indices) of a (c, b) weight, masked n:m by magnitude
    (core/magnitude.py) and packed compressed-resident
    (core/sparsity.pack_nm), rows at a time; masking and packing are row
    by row, so this equals one call on the whole weight."""
    from repro.core.magnitude import prune_nm
    from repro.core.sparsity import pack_nm

    c, b = w.shape
    rows = row_chunk(c, b // m)

    def one(wc):
        p = pack_nm(wc, prune_nm(wc, None, n=n, m=m).mask, n, m, idx_bits=4)
        return p.values, p.indices

    vals, idx = jax.lax.map(one, w.reshape(c // rows, rows, b))

    def join(x):                       # (chunks, planes, rows, g) → (planes, c, g)
        return jnp.moveaxis(x, 0, 1).reshape(x.shape[1], c, -1)

    return join(vals), join(idx)


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _compress_linear(kernel, *, n, m):
    """An (in, out) kernel as the program's ``NmCompressed``."""
    from repro.core.sparsity import NmCompressed

    return NmCompressed(*_pack_rows(kernel.T, n, m), n, m, kernel.shape[0], 4)


@functools.partial(jax.jit, static_argnames=("n", "m"))
def _compress_stacked(kernel, *, n, m):
    """A stack of experts (E, in, out) as the program's
    ``NmStackedCompressed``: each expert masked and packed as
    ``_compress_linear`` does a 2-D kernel, one expert after another."""
    from repro.core.sparsity import NmStackedCompressed

    vals, idx = jax.lax.map(lambda k: _pack_rows(k.T, n, m), kernel)
    e, b, _ = kernel.shape
    return NmStackedCompressed(vals, idx, n, m, b, e, 4)


def compress(kernel, n: int, m: int):
    """A kernel the reference lists in ``LINEARS``, packed n:m: 2-D
    (in, out), or 3-D (E, in, out) for a stack of experts."""
    pack = _compress_stacked if kernel.ndim == 3 else _compress_linear
    return pack(kernel, n=n, m=m)


def build(cfg: dict, seed: int):
    """(model, params) of ``cfg`` with weights from ``seed``, each block
    made from its own leaves (blocks may differ, as a leading dense layer
    differs from the expert layers after it)."""
    from repro.models.model_builder import build_model

    ref = harness.reference(cfg["reference"])
    model = build_model(harness.model_config(cfg))
    abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves = [dict(ref.block_leaves(cfg, i)) for i in range(cfg["num_layers"])]
    for i, want in enumerate(leaves):
        got = {k: tuple(v.shape) for k, v in
               W.flatten(abstract["blocks"][i]).items()}
        if got != want:
            raise harness.SpecError(f"block {i}: program leaves {got} differ "
                                    f"from the reference's {want}")
    params = W.nest(W.make(seed, W.TOP, ref.top_leaves(cfg), cfg["dtype"]))
    params["blocks"] = {}
    nm = cfg.get("nm")
    for i, want in enumerate(leaves):
        flat = W.make(seed, i, want.items(), cfg["dtype"])
        if nm:
            for name in ref.LINEARS:
                if name in flat:
                    flat[name] = compress(flat[name], *nm)
        params["blocks"][i] = W.nest(flat)
    return model, jax.block_until_ready(params)


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------
def run(cell: dict, seed: int, seconds: float, traced: bool,
        control: bool = False) -> dict:
    """One run; ``control`` also reads the fp8 control on the same sample
    (bench/limits.py, never the benchmark's own runs)."""
    from repro.serve import ServeConfig, ServingEngine

    t, cfg = cell["traffic"], cell["config"]
    counter = harness.CompileCounter()
    rec: dict = {"seconds": seconds}
    t_setup = time.perf_counter()
    model, params = build(cfg, seed)
    rec["weights_s"] = time.perf_counter() - t_setup
    engine = ServingEngine(model, params, ServeConfig(
        batch_slots=t["slots"], max_len=t["max_len"], greedy=True,
        eos_id=-1, scheduler="continuous"))
    clients = Clients(t, cfg["vocab_size"], seed)
    stamps: dict[int, list[float]] = {}

    def on_token(req, tok):
        stamps[req.uid].append(time.perf_counter())

    def submit(max_new=None):
        req = clients.next(max_new)
        req.on_token = on_token
        stamps[req.uid] = []
        engine.submit(req)
        return req

    mean = float(np.mean(clients.pool))
    for k in range(t["clients"]):
        submit(max(2, int(round(mean * (k + 1) / t["clients"]))))
    with jax.profiler.TraceAnnotation("bench.pump"):
        engine.pump()          # ends on a host read of the sampled tokens
    rec["setup_s"] = time.perf_counter() - t_setup
    rec["setup_compiles"] = counter.fresh()

    # ---- the window --------------------------------------------------------
    c0 = counter.fresh()
    log_dir = str(harness.ROOT / ".bench_traces" / f"{cell['name']}-{seed}")
    # the trace covers the window's last ``trace_seconds`` and is written
    # out after the window closes, so requests finish as in an untraced run
    t_trace = max(0.0, seconds - t["trace_seconds"]) if traced else None
    ann, trace_span = None, None
    sent: list = []            # requests submitted in the window
    done: list = []            # ... and finished in it
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if t_trace is not None and ann is None and now >= t0 + t_trace:
            shutil.rmtree(log_dir, ignore_errors=True)
            jax.profiler.start_trace(log_dir, profiler_options=trace.options())
            ann = jax.profiler.TraceAnnotation(trace.WINDOW)
            ann.__enter__()
            trace_span = [time.perf_counter(), None]
        with jax.profiler.TraceAnnotation("bench.pump"):
            engine.pump()
        if engine.finished:
            fin, engine.finished = engine.finished, []
            for r in fin:
                if r.t_submit >= t0:
                    done.append(r)
                if time.perf_counter() < t_end:
                    with jax.profiler.TraceAnnotation("bench.submit"):
                        sent.append(submit())
    t_close = time.perf_counter()
    if ann is not None:
        ann.__exit__(None, None, None)
        trace_span[1] = t_close
        jax.profiler.stop_trace()
    rec["window_compiles"] = counter.fresh() - c0
    rec["memory_peak_bytes"] = harness.memory_peak_bytes(1)
    rec["window_s"] = t_close - t0

    # ---- what the window did ------------------------------------------------
    rec["tokens"] = sum(1 for ts in stamps.values() for x in ts
                        if t0 <= x <= t_close)
    rec["ttft_s"] = [r.t_first - r.t_submit for r in sent
                     if 0 <= r.t_first <= t_close]
    rec["itl_s"] = [b - a for ts in stamps.values()
                    for a, b in zip(ts, ts[1:]) if a >= t0 and b <= t_close]
    failed = [r for r in done if r.error or len(r.out) != r.max_new]
    rec["attempted"], rec["failed"] = len(done), len(failed)
    rec["shape"] = costs.Shape.of(cfg)
    rec["nm"] = tuple(cfg["nm"]) if cfg.get("nm") else None
    if trace_span is not None:
        s0, s1 = trace_span
        ops = 0.0
        for ts in stamps.values():
            for j, x in enumerate(ts):
                if s0 <= x <= s1:
                    ops += (costs.prompt_ops(rec["shape"], t["prompt_len"],
                                             rec["nm"]) if j == 0 else
                            costs.decode_token_ops(rec["shape"],
                                                   t["prompt_len"] + j - 1,
                                                   rec["nm"]))
        rec["traced_host_s"], rec["traced_ops"] = s1 - s0, ops
        rec["trace"] = trace.reduce(trace.load(trace.latest_xplane(log_dir)),
                                    kernels=t.get("kernels", {}))
        shutil.rmtree(log_dir, ignore_errors=True)

    # ---- correctness, once the program's state is freed ---------------------
    sample = check.sample_requests([r for r in done if r not in failed],
                                   t["check_requests"], seed)
    del engine, params, model
    gc.collect()
    t_check = time.perf_counter()
    gaps = (check.served_gaps(cfg, seed, sample, t["max_len"], rec["nm"],
                              control=control)
            if sample else {"served_logit_gap": float("inf"),
                            "tokens_checked": 0})
    limit = cell["limits"]["served_logit_gap"]
    rec["compared"] = harness.compare("served_logit_gap",
                                      gaps["served_logit_gap"], limit)
    rec["tokens_checked"] = gaps["tokens_checked"]
    rec["check_s"] = time.perf_counter() - t_check
    if control:
        rec["control"] = harness.compare("served_logit_gap",
                                         gaps.get("control_logit_gap",
                                                  float("inf")), limit)
    rec["correct"] = (all(c["ok"] for c in rec["compared"].values())
                      and not failed and bool(sample))
    return rec
