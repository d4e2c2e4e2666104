"""Batched serving engine — continuous (slot-level) or wave batching over
fixed slots.

The shape discipline is TPU-grade either way: ONE resident jit'd
``decode_step`` with a static (B_slots, 1) signature runs forever; one
shared jitted prefill whose internal shape-keyed compile cache buckets
the prompt lengths (one executable per (B, S)).

**Continuous scheduler** (``ServeConfig.scheduler="continuous"``, default).
Every slot carries its own ``pos`` — the per-slot position decode API —
so heterogeneous requests decode packed in one batch.  Admission is
slot-level: a queued request prefills at B=1 into a fresh single-row cache
(bucketed by prompt length), the row is scattered into its slot of the
resident cache, and the slot joins the very next decode step.  When a slot
finishes (``max_new`` reached, ``eos_id`` sampled, or the slot's cache region
exhausted) it is freed and re-admits from the queue immediately — a long
request never holds the other ``batch_slots - 1`` slots hostage.  Idle slots
keep re-decoding their last token at a frozen position: the writes are
idempotent on their own row and invisible to every other row, so the decode
signature never changes and each active row's token stream is bit-identical
to serving that request alone at batch=1.

**Wave scheduler** (``scheduler="wave"``, the legacy correctness oracle).
Up to ``batch_slots`` same-length prompts prefill together, then decode
lock-step (scalar ``pos``) until every request in the wave is finished; the
wave ends at the first step where *every* slot is done.

Fault tolerance: ``snapshot()`` captures the whole engine — resident cache /
tokens / per-slot positions (a pytree that round-trips through the
checkpointer) plus the per-slot and queued request bookkeeping (plain
JSON-able metadata + prompt arrays) — and ``restore()`` rebuilds it, so a
preempted server resumes mid-generation with bit-identical continuations
(tests/test_continuous_batching.py).

Compressed weights: pass params whose pruned linears are ``NmCompressed``
(serve/compressed.py) — the engine keeps them **compressed-resident**: no
``decompress_params`` at load, prefill and decode stream the compressed
bytes through kernels/ops.nm_matmul (paper §4.8).  Mixed ``PrunePlan``
residency needs no engine support beyond this: ``compress_params(...,
plan=report.plan)`` leaves non-n:m layers as dense kernels, and each
``NmCompressed`` leaf carries its own static (n, m, b, idx_bits), so a
2:4-MLP / dense-attention tree decodes with per-layer geometry out of the
box (tests/test_plan.py).  MoE expert stacks ride the same contract:
``NmStackedCompressed`` leaves (all E expert slices in one container)
dispatch inside ``layers.stacked_dense``, so compressed-resident MoE
decode needs zero engine changes (tests/test_stacked_compressed.py).
Which kernel impl/tiles run is the ``ServeConfig`` nm_* knobs (falling
back to the ``build_model(..., nm_kernel=)`` config, then backend
auto-dispatch).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import obs
from repro.kernels.ops import NmKernelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.serve.faults import (DeviceOom, FaultPlan, NonFiniteLogits,
                                QueueFull)
from repro.serve.pager import Pager, PoolExhausted, SCRATCH

Array = jax.Array


@dataclasses.dataclass
class Request:
    uid: int
    prompt: Any              # (S,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # serving telemetry (time.perf_counter seconds; < 0 = not yet)
    t_submit: float = -1.0
    t_admit: float = -1.0    # popped from the queue into a slot
    t_first: float = -1.0
    t_done: float = -1.0
    # wall-clock budget measured from t_submit (0 = none); expired requests
    # finish with error="deadline" and whatever tokens they produced
    deadline_s: float = 0.0
    error: str = ""          # "" = clean; "deadline" / "cancelled" otherwise
    # streaming hook: called as on_token(req, token) after each absorbed
    # token (front-end SSE push).  Not serialized by snapshot().
    on_token: Any = dataclasses.field(default=None, repr=False, compare=False)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_slots: int = 8
    max_len: int = 512
    greedy: bool = True
    temperature: float = 1.0
    eos_id: int = -1         # < 0 = no stop token
    scheduler: str = "continuous"   # "continuous" | "wave" (legacy oracle)
    # n:m compressed-matmul dispatch (kernels/ops.NmKernelConfig fields);
    # "" / 0 defer to the model's build_model(..., nm_kernel=) config,
    # then to backend auto-dispatch + the shape-keyed tile chooser.
    nm_impl: str = ""
    nm_block_b: int = 0
    nm_block_c: int = 0
    nm_block_x: int = 0
    # paged KV cache (serve/pager.py): cache rows become page pools shared
    # across slots; memory scales with resident tokens, not slots × max_len.
    paged: bool = False
    page_size: int = 16      # tokens per page; must divide max_len
    num_pages: int = 0       # 0 = auto: 1 + batch_slots · max_len/page_size
    prefix_reuse: bool = True  # share prompt pages across requests (COW)
    # admission control: > 0 bounds the request queue — submit() raises
    # QueueFull instead of accepting unbounded backlog (the front-end maps
    # it to 503 + Retry-After; load shedding rejects new work before
    # evicting resident work)
    max_queued: int = 0
    # paranoia tier: run the pager's refcount audit after every continuous
    # step (the supervisor additionally audits after every recovery)
    debug_checks: bool = False

    def __post_init__(self):
        if self.max_queued < 0:
            raise ValueError(f"max_queued must be >= 0 (0 = unbounded), "
                             f"got {self.max_queued}")
        if not (math.isfinite(self.temperature) and self.temperature > 0):
            raise ValueError(
                f"temperature must be a finite positive float, got "
                f"{self.temperature!r} — <= 0 turns categorical sampling "
                f"into NaN/garbage silently")
        if self.batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {self.batch_slots}")
        if self.max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {self.max_len}")
        if self.paged:
            if self.scheduler != "continuous":
                raise ValueError("paged=True requires the continuous "
                                 "scheduler (wave allocates per-wave caches)")
            if self.page_size < 1:
                raise ValueError(f"page_size must be >= 1, got {self.page_size}")
            if self.max_len % self.page_size:
                raise ValueError(
                    f"page_size={self.page_size} must divide "
                    f"max_len={self.max_len} so the paged logical row and "
                    f"the contiguous row have identical length (bit-parity)")
            pps = self.max_len // self.page_size
            if self.num_pages and self.num_pages < 1 + pps:
                raise ValueError(
                    f"num_pages={self.num_pages} < {1 + pps} (scratch + one "
                    f"full slot) cannot guarantee forward progress")


# --------------------------------------------------------------------------
# shared jitted step functions
# --------------------------------------------------------------------------
# One jit per (model, nm-kernel-config): every engine over the same model
# reuses the same compiled decode/prefill executables (jax.jit re-traces per
# input *shape* internally, so the B=1 slot prefill and the B=slots wave
# prefill share one callable).  The nm config is part of the key because it
# is baked into the trace (layers.nm_kernel_scope is read at trace time).
_JIT_CACHE: dict[tuple, dict] = {}
_JIT_CACHE_MAX = 8          # FIFO-evict beyond this many (model, nm) entries


def _decode_fn(model, params, cache, tokens, pos):
    logits, cache = model.decode_step(params, cache, tokens, pos)
    return logits[:, -1, :], cache


def _prefill_fn(model, params, cache, tokens, start):
    """Cached prefill: sequential decode over the prompt, batched.

    ``start`` (traced) skips tokens already materialized in the cache by a
    shared-prefix gather — positions [start, S) are computed, [0, start)
    are assumed present.  Callers without a prefix pass 0."""

    def body(i, carry):
        cache, _ = carry
        tok = jax.lax.dynamic_slice(tokens, (0, i), (tokens.shape[0], 1))
        logits, cache = model.decode_step(params, cache, tok, i)
        # fp32 carry whatever the model dtype: exact for bf16 logits
        return cache, logits[:, -1, :].astype(jnp.float32)

    B = tokens.shape[0]
    init_logits = jnp.zeros((B, model.cfg.vocab_size), jnp.float32)
    return jax.lax.fori_loop(start, tokens.shape[1], body,
                             (cache, init_logits))


def serve_write_slot(cache, row_cache, slot):
    """Scatter a batch=1 cache into row ``slot`` of the resident cache.

    Every traced cache leaf in the model zoo is batch-leading (GQA k/v +
    pos_ids, MLA latents + per-row length, Mamba/xLSTM state), so one
    dynamic_update_slice per leaf replaces the whole row — including the
    stale tail beyond the new prompt, which the fresh row re-zeroes.
    """

    def put(full, one):
        return jax.lax.dynamic_update_slice(
            full, one.astype(full.dtype), (slot,) + (0,) * (one.ndim - 1))

    return jax.tree.map(put, cache, row_cache)


# ---- paged-cache device helpers (per-layer dispatch: paged layers use the
# pool scatter/gather primitives from models/attention.py, contiguous ring
# layers keep the whole-row dynamic_update_slice).  All indices are traced,
# so one compilation covers every slot / page assignment; unused entries of
# the fixed-length page vectors point at page 0 (the pager's scratch sink).

def _admit_write_fn(cache, row, slot, lps, pids):
    """Admission: scatter a B=1 row cache into the resident paged cache.

    Row logical page ``lps[i]`` lands in pool page ``pids[i]``; shared
    (kept) pages are absent from the vectors and stay untouched."""
    out = {}
    for i, layer in cache.items():
        if A.is_paged(layer):
            out[i] = A.paged_write_row(layer, row[i], slot, lps, pids)
        else:
            def put(full, one):
                return jax.lax.dynamic_update_slice(
                    full, one.astype(full.dtype),
                    (slot,) + (0,) * (one.ndim - 1))
            out[i] = jax.tree.map(put, layer, row[i])
    return out


def _prefix_row_fn(cache, row, pids, n_tok):
    """Materialize a shared prefix (pool pages ``pids``, first ``n_tok``
    tokens valid) into a fresh B=1 row cache ahead of the tail prefill."""
    return {i: (A.paged_prefix_to_row(layer, row[i], pids, n_tok)
                if A.is_paged(layer) else row[i])
            for i, layer in cache.items()}


def _copy_pages_fn(cache, src, dst):
    """Copy-on-write service: pool[dst[i]] = pool[src[i]] on paged layers."""
    return {i: (A.paged_copy_pages(layer, src, dst)
                if A.is_paged(layer) else layer)
            for i, layer in cache.items()}


def _model_jits(model, nm_kernel) -> dict:
    key = (id(model), nm_kernel)
    entry = _JIT_CACHE.get(key)
    if entry is None or entry["model"] is not model:   # id() reuse guard
        # named functions, not partials: a trace shows each program as
        # ``jit_<function name>`` (jit_serve_decode, jit_serve_prefill)
        def serve_decode(params, cache, tokens, pos):
            return _decode_fn(model, params, cache, tokens, pos)

        def serve_prefill(params, cache, tokens, start):
            return _prefill_fn(model, params, cache, tokens, start)

        # the resident cache is donated on both mutating steps (decode,
        # slot write): the engine always rebinds ``self._cache`` to the
        # output, and snapshot() materializes to host before capturing
        entry = {
            "model": model,      # strong ref pins id(model)
            "decode": jax.jit(serve_decode, donate_argnums=(1,)),
            "prefill": jax.jit(serve_prefill),
            "write_slot": jax.jit(serve_write_slot, donate_argnums=(0,)),
            # paged helpers: admission scatter donates the resident cache
            # (rebound immediately); the prefix gather reads cache and row
            # without donation — its outputs are fresh gather results, so
            # no input buffer is reusable anyway.
            "admit_write": jax.jit(_admit_write_fn, donate_argnums=(0,)),
            "prefix_row": jax.jit(_prefix_row_fn),
            "copy_pages": jax.jit(_copy_pages_fn, donate_argnums=(0,)),
        }
        while len(_JIT_CACHE) >= _JIT_CACHE_MAX:       # bound process RSS
            _JIT_CACHE.pop(next(iter(_JIT_CACHE)))
        _JIT_CACHE[key] = entry
    return entry


class ServingEngine:
    def __init__(self, model, params, cfg: ServeConfig, *, rng=None):
        if cfg.scheduler not in ("continuous", "wave"):
            raise ValueError(f"unknown scheduler {cfg.scheduler!r}")
        obs.install()
        self.model = model
        self.cfg = cfg
        # compressed-resident: NmCompressed leaves stay compressed; they are
        # pytree nodes, so they flow through jit like any other param leaf.
        self.params = params
        self.nm_kernel = self._resolve_nm_kernel(model, cfg)
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        # virtual time in uniform work units (1/decode step, S/prefill) —
        # machine-independent clock for trace-driven benchmarks
        self.stats = {"decode_steps": 0, "busy_slot_steps": 0,
                      "prefills": 0, "prefill_tokens": 0, "vtime": 0,
                      "preemptions": 0, "page_faults": 0, "cow_copies": 0,
                      "prefix_hit_tokens": 0, "pages_hwm": 0}
        jits = _model_jits(model, self.nm_kernel)
        self._decode = jits["decode"]
        # one shared jitted prefill; prompt-length bucketing is its
        # internal shape-keyed compile cache (one executable per (B, S))
        self._prefill = jits["prefill"]
        self._write_slot = jits["write_slot"]
        self._admit_write = jits["admit_write"]
        self._prefix_row = jits["prefix_row"]
        self._copy_pages = jits["copy_pages"]
        # continuous-scheduler per-slot state (allocated on first admission)
        self._slots: list[Request | None] = [None] * cfg.batch_slots
        self._cache = None
        self._tokens = np.zeros((cfg.batch_slots, 1), np.int32)
        self._pos = np.zeros((cfg.batch_slots,), np.int32)
        # admission recency per slot — preemption victims are LIFO
        self._seq = 0
        self._slot_seq = [0] * cfg.batch_slots
        # fault injection + watchdog: both default off and cost one
        # attribute load per step until armed (serve/faults.py contract)
        self.faults: FaultPlan | None = None
        self.watch_logits = False
        self.pager: Pager | None = None
        if cfg.paged:
            if not hasattr(model, "init_paged_cache"):
                raise ValueError(
                    f"model {type(model).__name__} has no init_paged_cache — "
                    f"paged serving covers the transformer families")
            self._pps = cfg.max_len // cfg.page_size
            self._num_pages = cfg.num_pages or 1 + cfg.batch_slots * self._pps
            # prefix reuse is unsound across sliding-window ring buffers
            # (a sharer would be missing the ring history of the skipped
            # positions), so it auto-disables for windowed models
            prefix = (cfg.prefix_reuse
                      and not getattr(model.cfg, "sliding_window", 0))
            self.pager = Pager(
                batch_slots=cfg.batch_slots, pages_per_slot=self._pps,
                num_pages=self._num_pages, page_size=cfg.page_size,
                prefix_reuse=prefix)

    def arm_faults(self, plan: FaultPlan | None) -> None:
        """Arm (or disarm with None) a fault plan on the engine and, when
        paged, on the pager's fault-in path."""
        self.faults = plan
        if self.pager is not None:
            self.pager.faults = plan

    @staticmethod
    def _resolve_nm_kernel(model, cfg: ServeConfig) -> NmKernelConfig | None:
        if cfg.nm_impl or cfg.nm_block_b or cfg.nm_block_c or cfg.nm_block_x:
            base = getattr(model, "nm_kernel", None) or NmKernelConfig()
            return dataclasses.replace(
                base,
                impl=cfg.nm_impl or base.impl,
                block_b=cfg.nm_block_b or base.block_b,
                block_c=cfg.nm_block_c or base.block_c,
                block_x=cfg.nm_block_x or base.block_x,
            )
        return getattr(model, "nm_kernel", None)

    # ----------------------------------------------------------- helpers
    def _select(self, logits: Array) -> Array:
        if self.cfg.greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.rng, k = jax.random.split(self.rng)
        return jax.random.categorical(
            k, logits.astype(jnp.float32) / self.cfg.temperature, axis=-1
        ).astype(jnp.int32)

    def _absorb(self, req: Request, token: int) -> None:
        """Record one sampled token for ``req`` unless it already finished."""
        if req.done or len(req.out) >= req.max_new:
            req.done = True
            return
        req.out.append(token)
        if req.t_first < 0:
            req.t_first = time.perf_counter()
        if token == self.cfg.eos_id or len(req.out) >= req.max_new:
            req.done = True
            req.t_done = time.perf_counter()
        if req.on_token is not None:
            req.on_token(req, token)

    # ----------------------------------------------------------- main loop
    def submit(self, req: Request, *, force: bool = False):
        with TraceAnnotation("serve.submit", uid=req.uid):
            if len(req.prompt) + 1 > self.cfg.max_len:
                raise ValueError(
                    f"request {req.uid}: prompt length {len(req.prompt)} "
                    f"does not fit max_len={self.cfg.max_len} (need "
                    f"prompt + 1)")
            if (not force and self.cfg.max_queued
                    and len(self.queue) >= self.cfg.max_queued):
                # ~one queue drain per resident generation as the backoff hint
                raise QueueFull(
                    f"request {req.uid} rejected: queue at max_queued="
                    f"{self.cfg.max_queued}",
                    retry_after_s=max(1.0, 0.1 * len(self.queue)))
            if req.t_submit < 0:
                req.t_submit = time.perf_counter()
            self.queue.append(req)

    def idle(self) -> bool:
        """No queued requests and no slot mid-generation."""
        return not self.queue and all(s is None for s in self._slots)

    def cancel(self, uid: int, *, error: str = "cancelled") -> bool:
        """Abort a queued or in-flight request; it joins ``finished`` with
        ``done=True``, its partial tokens, and ``error`` set.  Returns False
        when the uid is not resident (already finished or unknown)."""
        for i, req in enumerate(self.queue):
            if req.uid == uid:
                req.done, req.error = True, error
                if req.t_done < 0:
                    req.t_done = time.perf_counter()
                self.queue.pop(i)
                self.finished.append(req)
                return True
        for slot, req in enumerate(self._slots):
            if req is not None and req.uid == uid:
                req.done, req.error = True, error
                self._retire(slot)
                return True
        return False

    def _expire_deadlines(self) -> None:
        now = time.perf_counter()
        expired = [req.uid
                   for req in (*self.queue,
                               *(r for r in self._slots if r is not None))
                   if not req.done and req.deadline_s > 0
                   and req.t_submit >= 0
                   and now - req.t_submit > req.deadline_s]
        for uid in expired:
            self.cancel(uid, error="deadline")

    def pump(self) -> bool:
        """Process one scheduling quantum — one decode step (continuous) or
        one whole wave (wave).  Returns False when there is nothing to do."""
        with TraceAnnotation("serve.pump", step=self.stats["decode_steps"]):
            self._expire_deadlines()
            with L.nm_kernel_scope(self.nm_kernel):
                if self.cfg.scheduler == "wave":
                    wave = self._next_wave()
                    if not wave:
                        return False
                    self._serve_wave(wave)
                    now = time.perf_counter()
                    for req in wave:
                        req.done = True
                        if req.t_done < 0:
                            req.t_done = now
                        self.finished.append(req)
                    return True
                return self._continuous_step()

    def run(self, *, max_steps: int = 100_000) -> list[Request]:
        """Drain queue and slots; returns finished requests in uid order.

        If ``max_steps`` runs out first, in-flight and queued requests are
        *also* returned, flagged ``done=False`` with their partial ``out`` —
        they previously vanished from the caller's view entirely.  Partials
        stay resident in the engine: further ``pump()``/``run()`` calls
        continue them (they will be returned again once finished).
        """
        steps = 0
        while steps < max_steps and self.pump():
            steps += 1
        done, self.finished = self.finished, []
        if not self.idle():
            done += [r for r in self._slots if r is not None]
            done += list(self.queue)
        return sorted(done, key=lambda r: r.uid)

    # ------------------------------------------------- continuous scheduler
    def _ensure_state(self):
        if self._cache is None:
            if self.cfg.paged:
                self._cache = self.model.init_paged_cache(
                    self.cfg.batch_slots, num_pages=self._num_pages,
                    page_size=self.cfg.page_size, pages_per_slot=self._pps)
            else:
                self._cache = self.model.init_cache(
                    self.cfg.batch_slots, self.cfg.max_len)

    def _retire(self, slot: int) -> None:
        req = self._slots[slot]
        if req.t_done < 0:
            req.t_done = time.perf_counter()
        self.finished.append(req)
        self._slots[slot] = None
        if self.pager is not None:
            self.pager.retire(slot)
        # _pos[slot] keeps its last (< max_len) value: the freed slot keeps
        # re-decoding idempotently until the next admission overwrites it
        # (paged: the retired row points at the scratch page, a write sink).

    def _admit_into(self, slot: int) -> bool:
        """Prefill the queue head into ``slot``.  Returns False — leaving
        the request queued — when the paged pool cannot cover its pages.

        A request with partial ``out`` is a preemption resume: the engine
        re-prefills prompt + out (positions [0, S_all)), skips sampling, and
        re-enters decode at pos = S_all - 1 feeding the last emitted token —
        the next decode step rewrites that position with identical k/v, so
        the continuation is bit-identical to never having been preempted
        (under greedy; sampled runs re-split the RNG per emitted token).
        """
        req = self.queue[0]
        if self.faults is not None and \
                self.faults.fire("prefill", uid=req.uid) is not None:
            # before any engine/pager state mutation: the request stays
            # queued, exactly like a real allocator failure at prefill entry
            raise DeviceOom(
                f"injected RESOURCE_EXHAUSTED: out of memory while "
                f"prefilling request {req.uid}", site="prefill", uid=req.uid)
        self._ensure_state()
        prompt = np.asarray(req.prompt, np.int32)
        resumed = len(req.out) > 0
        tokens_all = (np.concatenate([prompt, np.asarray(req.out, np.int32)])
                      if resumed else prompt)
        S = len(tokens_all)
        plan = None
        if self.pager is not None:
            try:
                plan = self.pager.admit(slot, tokens_all)
            except PoolExhausted:
                return False
        self.queue.pop(0)
        req.t_admit = time.perf_counter()
        with TraceAnnotation("serve.admit", uid=req.uid, slot=slot, tokens=S):
            with TraceAnnotation("serve.row_init"):
                row = self.model.init_cache(1, self.cfg.max_len)
            start = 0
            if plan is not None:
                start = plan.start
                if plan.n_shared_tok:
                    pids = np.full(self._pps, SCRATCH, np.int32)
                    pids[:len(plan.gather_pids)] = plan.gather_pids
                    row = self._prefix_row(self._cache, row,
                                           jnp.asarray(pids),
                                           jnp.int32(plan.n_shared_tok))
                    self.stats["prefix_hit_tokens"] += plan.n_shared_tok
            with TraceAnnotation("serve.prefill"):
                row, last = self._prefill(self.params, row,
                                          jnp.asarray(tokens_all)[None, :],
                                          start)
            with TraceAnnotation("serve.write_slot"):
                if plan is not None:
                    lps = np.zeros(self._pps, np.int32)
                    pids = np.full(self._pps, SCRATCH, np.int32)
                    lps[:len(plan.fresh_lps)] = plan.fresh_lps
                    pids[:len(plan.fresh_pids)] = plan.fresh_pids
                    self._cache = self._admit_write(
                        self._cache, row, jnp.int32(slot), jnp.asarray(lps),
                        jnp.asarray(pids))
                    self.pager.register(slot, prompt)
                else:
                    self._cache = self._write_slot(self._cache, row, slot)
            self.stats["prefills"] += 1
            self.stats["prefill_tokens"] += S - start
            self.stats["vtime"] += S - start
            self._slots[slot] = req
            self._slot_seq[slot] = self._seq
            self._seq += 1
            if resumed:
                self._tokens[slot, 0] = int(tokens_all[-1])
                self._pos[slot] = S - 1     # re-decode the last emitted token
                return True
            with TraceAnnotation("serve.first_token"):
                tok = int(np.asarray(self._select(last))[0])
            self._absorb(req, tok)
            self._tokens[slot, 0] = tok
            self._pos[slot] = S
            if req.done or S + 1 >= self.cfg.max_len:
                req.done = True
                self._retire(slot)      # freed — caller retries the queue
            return True

    def _admit(self) -> bool:
        """Fill free slots from the queue (prefill-into-slot).  The whole
        admission — including requests that finish at their first token —
        happens before the next decode step, so a freed slot never idles
        while work is queued."""
        admitted = False
        for slot in range(self.cfg.batch_slots):
            while self._slots[slot] is None and self.queue:
                if not self._admit_into(slot):
                    return admitted     # pool exhausted — wait for retires
                admitted = True
                if self._slots[slot] is not None:
                    break
        return admitted

    # ------------------------------------------------------- paged plumbing
    def _preempt(self, slot: int) -> None:
        """Evict an active slot to free its pages: the request re-queues at
        the front with its partial output and resumes via ``_admit_into``."""
        req = self._slots[slot]
        self.pager.retire(slot)
        self._slots[slot] = None
        self.queue.insert(0, req)
        self.stats["preemptions"] += 1

    def _victim(self, exclude: int) -> int | None:
        """Most recently admitted active slot other than ``exclude`` (LIFO —
        the oldest requests keep their accumulated pages and finish first)."""
        cands = [s for s in range(self.cfg.batch_slots)
                 if s != exclude and self._slots[s] is not None]
        return max(cands, key=lambda s: self._slot_seq[s], default=None)

    def _fault_active(self) -> None:
        """Make every active slot's write page privately owned before the
        decode step: allocate on page boundaries, COW on shared pages,
        preempting LIFO victims under pool pressure."""
        ps = self.cfg.page_size
        copies: list[tuple[int, int, int, int]] = []   # (slot, lp, src, dst)
        for slot in range(self.cfg.batch_slots):
            if self._slots[slot] is None:
                continue
            pos = int(self._pos[slot])
            was_scratch = self.pager.table[slot, pos // ps] == SCRATCH
            while True:
                try:
                    copies.extend((slot, pos // ps, s, d)
                                  for s, d in self.pager.fault_in(slot, pos))
                    break
                except PoolExhausted:
                    victim = self._victim(exclude=slot)
                    if victim is None:
                        raise          # impossible: num_pages >= 1 + pps
                    self._preempt(victim)
            if was_scratch:
                self.stats["page_faults"] += 1
        # a preemption later in the loop may have freed (and re-allocated)
        # an earlier slot's COW destination — keep only copies whose slot is
        # still active and whose destination page is still mapped there
        copies = [(slot, lp, s, d) for slot, lp, s, d in copies
                  if self._slots[slot] is not None
                  and self.pager.table[slot, lp] == d]
        if copies:
            # at most one COW per slot per step → pad to a fixed (B,) shape
            src = np.zeros(self.cfg.batch_slots, np.int32)
            dst = np.zeros(self.cfg.batch_slots, np.int32)
            for j, (_, _, s, d) in enumerate(copies):
                src[j], dst[j] = s, d
            self._cache = self._copy_pages(self._cache, jnp.asarray(src),
                                           jnp.asarray(dst))
            self.stats["cow_copies"] += len(copies)

    def _sync_tables(self) -> None:
        """Mirror the host-authoritative page table to the device cache."""
        if not self.pager.dirty:
            return
        self._cache = {
            i: (layer._replace(table=jnp.asarray(self.pager.table))
                if A.is_paged(layer) else layer)
            for i, layer in self._cache.items()}
        self.pager.dirty = False

    def _continuous_step(self) -> bool:
        admitted = self._admit()
        active = [s for s in self._slots if s is not None]
        if not active:
            return admitted
        if self.pager is not None:
            self._fault_active()
            self._sync_tables()
            active = [s for s in self._slots if s is not None]  # preemptions
            if not active:
                return admitted
            used = self.pager.pool.used_pages
            if used > self.stats["pages_hwm"]:
                self.stats["pages_hwm"] = used
        with TraceAnnotation("serve.decode"):
            logits, self._cache = self._decode(
                self.params, self._cache,
                jnp.asarray(self._tokens), jnp.asarray(self._pos))
        if self.faults is not None:
            stall = self.faults.fire("decode_stall")
            if stall is not None:
                time.sleep(stall.payload)
            if self.faults.fire("decode_logits") is not None:
                logits = jnp.full_like(logits, jnp.nan)
        if self.watch_logits and not bool(jnp.isfinite(logits).all()):
            # raise BEFORE any token is absorbed: the poisoned step's cache
            # write is rolled back by the supervisor's snapshot restore, and
            # no request ever sees a garbage token
            raise NonFiniteLogits(
                f"decode step {self.stats['decode_steps']} produced "
                f"non-finite logits", site="decode_logits")
        with TraceAnnotation("serve.sample"):    # the host waits here
            nxt = np.asarray(self._select(logits))
        self.stats["decode_steps"] += 1
        self.stats["busy_slot_steps"] += len(active)
        self.stats["vtime"] += 1
        with TraceAnnotation("serve.absorb"):
            for slot, req in enumerate(self._slots):
                if req is None:
                    continue
                self._absorb(req, int(nxt[slot]))
                self._tokens[slot, 0] = nxt[slot]
                # truncate exactly where the wave oracle does: the last
                # decode position is max_len - 2 (horizon = max_len - S - 1)
                if not req.done and self._pos[slot] + 2 >= self.cfg.max_len:
                    req.done = True          # slot cache region exhausted
                if req.done:
                    self._retire(slot)
                else:
                    self._pos[slot] += 1
        if self.cfg.debug_checks and self.pager is not None:
            self.pager.check()
        return True

    # ------------------------------------------------------ wave scheduler
    def _next_wave(self) -> list[Request]:
        """Pop up to batch_slots queued requests sharing one prompt length."""
        if not self.queue:
            return []
        want = len(self.queue[0].prompt)
        wave, rest = [], []
        for r in self.queue:
            if len(r.prompt) == want and len(wave) < self.cfg.batch_slots:
                wave.append(r)
            else:
                rest.append(r)
        self.queue = rest
        return wave

    def _serve_wave(self, wave: list[Request]) -> int:
        """Prefill + decode one wave; returns decode steps executed."""
        S = len(wave[0].prompt)
        B = self.cfg.batch_slots
        prompts = jnp.zeros((B, S), jnp.int32)
        for slot, req in enumerate(wave):
            prompts = prompts.at[slot].set(
                jnp.asarray(req.prompt, jnp.int32))

        cache = self.model.init_cache(B, self.cfg.max_len)
        cache, last = self._prefill(self.params, cache, prompts, 0)
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += S * len(wave)   # tokens prefilled
        self.stats["vtime"] += S        # work units: batched ≈ one B=1 pass

        tokens = self._select(last)[:, None]               # (B, 1)
        for slot, req in enumerate(wave):
            self._absorb(req, int(tokens[slot, 0]))

        horizon = min(
            max(r.max_new for r in wave) - 1,
            self.cfg.max_len - S - 1,
        )
        steps = 0
        for t in range(horizon):
            if all(r.done for r in wave):
                break                       # early finishers end the wave
            logits, cache = self._decode(
                self.params, cache, tokens, S + t)
            nxt = self._select(logits)
            tokens = nxt[:, None]
            self.stats["decode_steps"] += 1
            self.stats["busy_slot_steps"] += sum(
                1 for r in wave if not r.done)
            self.stats["vtime"] += 1
            for slot, req in enumerate(wave):
                self._absorb(req, int(nxt[slot]))
            steps += 1
        return steps

    # ----------------------------------------------------------- ckpt hooks
    @staticmethod
    def _req_state(req: Request | None) -> dict | None:
        if req is None:
            return None
        return {"uid": int(req.uid),
                "prompt": np.asarray(req.prompt, np.int32),
                "max_new": int(req.max_new),
                "out": [int(t) for t in req.out],
                "done": bool(req.done),
                "t_submit": float(req.t_submit),
                "t_first": float(req.t_first),
                "t_done": float(req.t_done),
                "deadline_s": float(req.deadline_s),
                "error": str(req.error)}
        # on_token is deliberately dropped: callbacks don't serialize; a
        # restored server re-attaches streams when clients reconnect.

    @staticmethod
    def _req_from_state(st: dict | None) -> Request | None:
        if st is None:
            return None
        return Request(uid=int(st["uid"]),
                       prompt=np.asarray(st["prompt"], np.int32),
                       max_new=int(st["max_new"]),
                       out=[int(t) for t in st["out"]],
                       done=bool(st["done"]),
                       t_submit=float(st.get("t_submit", -1.0)),
                       t_first=float(st.get("t_first", -1.0)),
                       t_done=float(st.get("t_done", -1.0)),
                       deadline_s=float(st.get("deadline_s", 0.0)),
                       error=str(st.get("error", "")))

    def snapshot(self) -> dict:
        """Full engine state for preempt/resume.

        ``device`` is a pytree of **host** (numpy) arrays — materialized
        here both for serialization and because the live cache buffers are
        donated to the next decode/admission step — that round-trips
        through the checkpointer; ``slots``/``queue``/``finished`` are
        request bookkeeping (ints + prompt arrays + telemetry stamps);
        ``stats`` are the serving counters.  ``restore`` on a fresh engine
        (same model/params/config) continues bit-identically.
        """
        return {
            "scheduler": self.cfg.scheduler,
            "batch_slots": self.cfg.batch_slots,
            "max_len": self.cfg.max_len,
            "paged": self.cfg.paged,
            "page_size": self.cfg.page_size if self.cfg.paged else 0,
            "num_pages": self._num_pages if self.cfg.paged else 0,
            "pager": None if self.pager is None else self.pager.snapshot(),
            "device": {
                "cache": (None if self._cache is None
                          else jax.tree.map(np.asarray, self._cache)),
                "tokens": np.array(self._tokens),
                "pos": np.array(self._pos),
                "rng": np.asarray(self.rng),
            },
            "slots": [self._req_state(r) for r in self._slots],
            "queue": [self._req_state(r) for r in self.queue],
            "finished": [self._req_state(r) for r in self.finished],
            "stats": dict(self.stats),
        }

    def restore(self, snap: dict) -> None:
        """Rebuild engine state from ``snapshot()`` output (the docstring
        contract the wave-era engine promised but never shipped).

        Latency telemetry: requests whose (t_submit, t_first) pair was
        stamped before the preempt keep it (TTFT stays valid); in-flight
        requests still waiting for their first token get ``t_submit``
        re-stamped at restore time — ``perf_counter`` epochs don't
        transfer across processes, so mixing them would poison TTFT.
        """
        if snap["scheduler"] != self.cfg.scheduler:
            raise ValueError(
                f"snapshot from scheduler={snap['scheduler']!r} cannot "
                f"restore into scheduler={self.cfg.scheduler!r}")
        for field in ("batch_slots", "max_len"):
            if snap.get(field, getattr(self.cfg, field)) != \
                    getattr(self.cfg, field):
                raise ValueError(
                    f"snapshot {field}={snap[field]} does not match engine "
                    f"{field}={getattr(self.cfg, field)} — the resident "
                    f"cache geometry must be identical")
        if bool(snap.get("paged", False)) != self.cfg.paged:
            raise ValueError(
                f"snapshot paged={snap.get('paged', False)} does not match "
                f"engine paged={self.cfg.paged} — cache layouts differ")
        if self.cfg.paged and snap.get("page_size") != self.cfg.page_size:
            raise ValueError(
                f"snapshot page_size={snap.get('page_size')} does not match "
                f"engine page_size={self.cfg.page_size}")
        if self.cfg.paged and \
                snap.get("num_pages", self._num_pages) != self._num_pages:
            raise ValueError(
                f"snapshot num_pages={snap.get('num_pages')} does not match "
                f"engine num_pages={self._num_pages} — page ids in the "
                f"snapshot would mis-index this pool")
        if self.pager is not None:
            self.pager.restore(snap["pager"])
        dev = snap["device"]
        cache = dev["cache"]
        self._cache = (None if cache is None
                       else jax.tree.map(jnp.asarray, cache))
        self._tokens = np.array(np.asarray(dev["tokens"]), np.int32)
        self._pos = np.array(np.asarray(dev["pos"]), np.int32)
        self.rng = jnp.asarray(dev["rng"])
        self._slots = [self._req_from_state(s) for s in snap["slots"]]
        self.queue = [self._req_from_state(s) for s in snap["queue"]]
        self.finished = [self._req_from_state(s) for s in snap["finished"]]
        now = time.perf_counter()
        for req in [*self._slots, *self.queue]:
            if req is not None and not req.done and req.t_first < 0:
                req.t_submit = now
        self.stats = dict(snap["stats"])
