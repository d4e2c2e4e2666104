"""Mesh-aware step builders for the production dry-run and real drivers.

One builder per shape-cell kind:

* ``train``   — microbatched, remat'd train step (loss → grad-accum → AdamW).
  FSDP+TP param/optimizer sharding (dist.fsdp_pspecs), bf16 16-bit-Adam
  moments, fp32 grad accumulation over a ``lax.scan`` of microbatches sized
  so each DP replica sees one sequence at a time, residual-stream activations
  sharded over the model axis between blocks (sequence-parallel analogue).
* ``prefill`` — forward to **last-token logits only** (vLLM-style; a
  (B, S, V) logit tensor at 32k×262k vocab is half a terabyte — no serving
  system materializes it).
* ``decode``  — one-token ``serve_step`` against a seq_len-deep KV cache,
  cache sharded per dist.cache_pspecs (heads on model, else flash-decoding
  sequence sharding).

Every builder returns ``(jitted_fn, abstract_args)`` where abstract_args are
ShapeDtypeStructs — ``jitted_fn.lower(*abstract_args)`` never allocates.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.dist import sharding as D
from repro.optim import AdamW
from repro.optim.adamw import AdamWState
from repro.optim.schedules import cosine_warmup

Array = jax.Array


def _ns(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def abstract_params(model):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def _carry_constraint(mesh: Mesh, cfg):
    """Sharding constraint applied to the residual stream between blocks."""
    tp = mesh.shape["model"] if "model" in mesh.axis_names else 1
    dp = D.data_axes(mesh)

    def apply(carry):
        out = dict(carry)
        for key in ("h", "enc_h", "dec_h"):
            if key in out and hasattr(out[key], "ndim") and out[key].ndim == 3:
                d = out[key].shape[-1]
                b = out[key].shape[0]
                b_ax = dp if (dp and b % _dp_size(mesh) == 0) else None
                d_ax = "model" if d % tp == 0 else None
                out[key] = jax.lax.with_sharding_constraint(
                    out[key], _ns(mesh, P(b_ax, None, d_ax))
                )
        return out

    return apply


def _dp_size(mesh: Mesh) -> int:
    import numpy as np
    dp = D.data_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in dp])) if dp else 1


# --------------------------------------------------------------------------
# periodic layer-scan planning (compile-time scaling — MaxText-style)
# --------------------------------------------------------------------------
def _block_signature(model, a_params, i: int):
    sub = jax.eval_shape(lambda p: _get(p, model.block_param_path(i)),
                         a_params)
    shapes = tuple(
        (tuple(str(k) for k in kp), l.shape, str(l.dtype))
        for kp, l in jax.tree_util.tree_flatten_with_path(sub)[0]
    )
    return (model.behavior_key(i), shapes)


def _get(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def plan_segments(sigs: list) -> list[tuple]:
    """[('unroll', [i..])] | [('scan', start, period, count)] covering 0..L-1.

    Greedy periodic chunking: at each position find the (period, count) with
    maximal coverage where the motif of ``period`` signatures repeats
    ``count`` ≥ 2 times; unroll single layers when no repetition exists.
    """
    L = len(sigs)
    segs: list[tuple] = []
    i = 0
    pending: list[int] = []

    def flush():
        nonlocal pending
        if pending:
            segs.append(("unroll", list(pending)))
            pending = []

    while i < L:
        best = None  # (coverage, -period, period, count)
        for p in range(1, min(16, (L - i) // 2) + 1):
            motif = sigs[i:i + p]
            k = 1
            while sigs[i + k * p: i + (k + 1) * p] == motif:
                k += 1
            if k >= 2 and (best is None or (p * k, -p) > (best[0], best[1])):
                best = (p * k, -p, p, k)
        if best is not None and best[0] >= 4:
            flush()
            segs.append(("scan", i, best[2], best[3]))
            i += best[0]
        else:
            pending.append(i)
            i += 1
    flush()
    return segs


def make_block_runner(model, *, block_fn):
    """→ run(params, carry): all blocks, scanning periodic segments.

    Inside a scan segment of period p × count k, the per-layer param
    subtrees are stacked (k, ...) per sub-position j and sliced by the scan;
    ``block_fn(params_t, carry, i0)`` is called with a params tree whose
    block ``start+j`` holds iteration t's weights — behavior (windows,
    theta, moe-ness) is constant across t by construction of the signature.
    """
    a_params = abstract_params(model)
    sigs = [_block_signature(model, a_params, i)
            for i in range(model.num_blocks())]
    segments = plan_segments(sigs)

    from repro.core.schedule import get_path, set_path

    def run(params, carry):
        for seg in segments:
            if seg[0] == "unroll":
                for i in seg[1]:
                    carry = block_fn(params, carry, i)
                continue
            _, start, p, k = seg
            xs = tuple(
                jax.tree.map(
                    lambda *ls: jnp.stack(ls),
                    *[get_path(params, model.block_param_path(start + t * p + j))
                      for t in range(k)],
                )
                for j in range(p)
            )

            def body(c, x, _start=start, _p=p):
                pt = params
                for j in range(_p):
                    pt = set_path(pt, model.block_param_path(_start + j), x[j])
                    c = block_fn(pt, c, _start + j)
                return c, None

            carry, _ = jax.lax.scan(body, carry, xs)
        return carry

    return run, segments


def _remat_loss(model, mesh: Mesh, cfg):
    """Layer-scanned loss: jax.checkpoint per block + residual-stream
    sharding constraints, periodic segments scanned (compile-time ∝ distinct
    block structures, not layer count)."""
    constrain = _carry_constraint(mesh, cfg)
    policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims

    def block(params, carry, i):
        return constrain(model.block(params, i, carry))

    block_r = jax.checkpoint(block, policy=policy, static_argnums=(2,))
    run, _ = make_block_runner(model, block_fn=block_r)

    def loss(params, batch):
        carry = constrain(model.embed_batch(params, batch))
        carry = run(params, carry)
        return model.loss_from_carry(params, carry, batch)

    return loss


# ==========================================================================
# train
# ==========================================================================
def make_train_step(model, mesh: Mesh, cell, *, microbatches: int = 0,
                    optimizer: AdamW | None = None):
    """→ (jitted step, (params_sds, opt_sds, batch_sds)).

    step(params, opt, batch) → (params, opt, metrics); batch is the *global*
    batch — it is split into ``microbatches`` chunks scanned sequentially
    with fp32 grad accumulation (1 sequence per DP replica per chunk by
    default), which bounds activation memory at 32k/4k sequard lengths.
    """
    cfg = model.cfg
    optimizer = optimizer or AdamW(
        weight_decay=0.1, clip_norm=1.0, moment_dtype="bfloat16"
    )
    lr = cosine_warmup(3e-4, 2000, 100_000)
    loss_fn = _remat_loss(model, mesh, cfg)

    B = cell.global_batch
    dp = _dp_size(mesh)
    n_micro = microbatches or max(1, B // dp)
    assert B % n_micro == 0

    def step(params, opt_state, batch):
        def micro(acc, mb):
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            acc_loss, acc_g = acc
            return (acc_loss + l,
                    jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                                 acc_g, g)), None

        mbs = jax.tree.map(
            lambda x: x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:]),
            batch,
        )
        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (tot_loss, tot_g), _ = jax.lax.scan(micro, zero, mbs)
        grads = jax.tree.map(lambda g: g / n_micro, tot_g)
        new_params, new_opt = optimizer.update(
            grads, opt_state, params, lr(opt_state.step)
        )
        return new_params, new_opt, {"loss": tot_loss / n_micro}

    a_params = abstract_params(model)
    a_opt = jax.eval_shape(optimizer.init, a_params)
    a_batch = registry.input_specs(cfg, cell)
    # micro-split batch: keep the global shape; scan reshapes internally

    pspec = D.fsdp_pspecs(a_params, mesh)
    p_sh = jax.tree.map(lambda s: _ns(mesh, s), pspec,
                        is_leaf=lambda x: isinstance(x, P))
    opt_sh = AdamWState(step=_ns(mesh, P()), mu=p_sh, nu=p_sh)
    b_sh = jax.tree.map(lambda s: _ns(mesh, s),
                        D.batch_pspecs(a_batch, mesh),
                        is_leaf=lambda x: isinstance(x, P))

    jitted = jax.jit(
        step,
        in_shardings=(p_sh, opt_sh, b_sh),
        out_shardings=(p_sh, opt_sh, _ns(mesh, P())),
        donate_argnums=(0, 1),
    )
    return jitted, (a_params, a_opt, a_batch)


# ==========================================================================
# prefill
# ==========================================================================
def make_prefill_step(model, mesh: Mesh, cell):
    """→ (jitted prefill, (params_sds, batch_sds)): last-token logits."""
    cfg = model.cfg
    constrain = _carry_constraint(mesh, cfg)

    run, _ = make_block_runner(
        model,
        block_fn=lambda p, c, i: constrain(model.block(p, i, c)),
    )

    def prefill(params, batch):
        carry = constrain(model.embed_batch(params, batch))
        carry = run(params, carry)
        from repro.models import layers as L

        key = "dec_h" if "dec_h" in carry else "h"
        h = carry[key][:, -1:, :]
        norm_name = "dec_norm" if "dec_norm" in params else "final_norm"
        h = L.norm(params[norm_name], h)
        if getattr(cfg, "tie_embeddings", True) or "lm_head" not in params:
            return L.unembed(params["embed"], h)
        return h @ params["lm_head"]["w"]

    a_params = abstract_params(model)
    a_batch = registry.input_specs(cfg, cell)
    pspec = D.fsdp_pspecs(a_params, mesh)
    p_sh = jax.tree.map(lambda s: _ns(mesh, s), pspec,
                        is_leaf=lambda x: isinstance(x, P))
    b_sh = jax.tree.map(lambda s: _ns(mesh, s),
                        D.batch_pspecs(a_batch, mesh),
                        is_leaf=lambda x: isinstance(x, P))
    jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh))
    return jitted, (a_params, a_batch)


# ==========================================================================
# decode
# ==========================================================================
import dataclasses


@dataclasses.dataclass(frozen=True)
class DecodeOptions:
    """Perf-iteration levers for the decode dry-run (§Perf hillclimb).

    weight_sharding: 'fsdp' streams weight shards per step (fits anything,
        pays all-gathers); 'tp' keeps weights resident sharded on the model
        axis only (no per-step weight collectives — needs P/16 ≤ HBM).
    kv_dtype: '' = model dtype; 'int8' = quantized cache (½ bytes).
    cache_len: 0 = cell.seq_len; else architecture-aware self-cache depth
        (e.g. Whisper's decoder never exceeds dec_seq=448).
    nm: (n, m) to lower the serve step against NmCompressed linear weights
        (paper §4.8 — weight stream shrinks to keep/m + index overhead).
    enc_len: encoder-source length override for enc-dec decode.
    """

    weight_sharding: str = "fsdp"
    kv_dtype: str = ""
    cache_len: int = 0
    nm: tuple | None = None
    enc_len: int = 0
    cross_cache: bool = False   # enc-dec: precomputed per-layer cross-KV


def abstract_nm_params(model, n: int | None = None, m: int | None = None,
                       *, plan=None):
    """Abstract params with prunable linears swapped for compressed
    ShapeDtypeStruct pairs — 2-D kernels lower to ``NmCompressed`` and
    3-D MoE expert stacks to one ``NmStackedCompressed`` leaf (values
    (E, keep, d_out, g) + nibble-packed indices), mirroring what
    ``serve.compressed.compress_params`` produces.

    With a global ``(n, m)`` every eligible linear compresses; with a
    ``PrunePlan`` each path resolves through the plan's rules and only
    paths whose cell has pattern "nm" compress, with *their own* (n, m) —
    mixed dense/compressed residency lowers with per-layer geometry.  An
    expert stack lowers compressed only when every slice resolves to one
    shared (n, m) cell — the same packability contract compress_params
    enforces (it warns/raises on the mismatch; here the stack just stays
    dense in the abstract tree).
    """
    from repro.core.sparsity import (NON_STREAMABLE_KERNELS, NmCompressed,
                                     NmStackedCompressed, nm_storage_shapes)

    if plan is None and (n is None or m is None):
        raise ValueError("abstract_nm_params needs (n, m) or plan=")

    a = abstract_params(model)
    paths = []
    for i in range(model.num_blocks()):
        paths.extend(model.block_linear_paths(a, i))

    from repro.core.schedule import get_path, set_path

    stacks: dict[tuple, dict[int, tuple | None]] = {}
    for path in paths:
        if plan is not None:
            cfg = plan.cfg_for(path)
            nm = cfg is not None and cfg.pattern == "nm"
            pn, pm = (cfg.n, cfg.m) if nm else (None, None)
        else:
            nm, pn, pm = True, n, m
        if isinstance(path[-1], int):     # expert slice — group by stack
            stacks.setdefault(path[:-1], {})[path[-1]] = \
                (pn, pm) if nm else None
            continue
        if not nm:
            continue                      # dense under this plan
        if any(p in NON_STREAMABLE_KERNELS
               for p in path if isinstance(p, str)):
            continue                      # absorbed-decode raw weight —
            #                               compress_params downgrades it
        kernel = get_path(a, path)
        if kernel.ndim != 2:
            continue
        d_in, d_out = kernel.shape
        if d_in % pm:
            continue
        vshape, ishape = nm_storage_shapes(d_out, d_in, pn, pm)
        packed = NmCompressed(
            values=jax.ShapeDtypeStruct(vshape, kernel.dtype),
            indices=jax.ShapeDtypeStruct(ishape, jnp.int8),
            n=pn, m=pm, b=d_in, idx_bits=4,
        )
        a = set_path(a, path[:-1] + ("w",), packed)

    for base, cells in stacks.items():
        kernel = get_path(a, base)
        if kernel.ndim != 3:
            continue
        E, d_in, d_out = kernel.shape
        got = {e: c for e, c in cells.items() if c is not None}
        if set(got) != set(range(E)) or len(set(got.values())) != 1:
            continue                      # unpackable stack — stays dense
        pn, pm = next(iter(got.values()))
        if d_in % pm:
            continue
        vshape, ishape = nm_storage_shapes(d_out, d_in, pn, pm)
        packed = NmStackedCompressed(
            values=jax.ShapeDtypeStruct((E, *vshape), kernel.dtype),
            indices=jax.ShapeDtypeStruct((E, *ishape), jnp.int8),
            n=pn, m=pm, b=d_in, E=E, idx_bits=4,
        )
        a = set_path(a, base, packed)
    return a


def make_decode_step(model, mesh: Mesh, cell,
                     opts: DecodeOptions = DecodeOptions()):
    """→ (jitted serve_step, (params_sds, cache_sds, tokens_sds, pos_sds[, enc]))."""
    cfg = model.cfg
    if opts.kv_dtype:
        cfg = cfg.replace(kv_cache_dtype=opts.kv_dtype)
        model = type(model)(cfg)
    B = cell.global_batch
    max_len = opts.cache_len or cell.seq_len

    if opts.nm:
        a_params = abstract_nm_params(model, *opts.nm)
    else:
        a_params = abstract_params(model)
    a_cache = jax.eval_shape(
        functools.partial(model.init_cache, B, max_len)
    )
    specs = registry.decode_specs(cfg, cell)
    if opts.enc_len and "enc_out" in specs:
        e = specs["enc_out"]
        specs["enc_out"] = jax.ShapeDtypeStruct(
            (e.shape[0], opts.enc_len, e.shape[2]), e.dtype)

    pspec = (D.param_pspecs(a_params, mesh)
             if opts.weight_sharding == "tp"
             else D.fsdp_pspecs(a_params, mesh))
    p_sh = jax.tree.map(lambda s: _ns(mesh, s), pspec,
                        is_leaf=lambda x: isinstance(x, P))
    c_sh = jax.tree.map(lambda s: _ns(mesh, s),
                        D.cache_pspecs(a_cache, mesh, B),
                        is_leaf=lambda x: isinstance(x, P))
    dp = D.data_axes(mesh)
    tok_spec = P(dp) if B % _dp_size(mesh) == 0 else P()
    # per-slot positions ride the same data-parallel layout as the tokens
    pos_sh = _ns(mesh, tok_spec)

    if cfg.family == "encdec":
        def serve_step(params, cache, tokens, pos, enc_out):
            return model.decode_step(params, cache, tokens, pos, enc_out)
        enc_sds = specs["enc_out"]
        if opts.cross_cache:
            enc_sds = jax.eval_shape(model.precompute_cross_kv,
                                     a_params, enc_sds)
            enc_sh = jax.tree.map(
                lambda s: _ns(mesh, s),
                D.cache_pspecs(enc_sds, mesh, B),
                is_leaf=lambda x: isinstance(x, P))
        else:
            enc_sh = _ns(mesh, D.batch_spec(mesh, enc_sds.shape[0], rank=3))
        jitted = jax.jit(
            serve_step,
            in_shardings=(p_sh, c_sh, _ns(mesh, P(*tok_spec, None)),
                          pos_sh, enc_sh),
            out_shardings=None,
            donate_argnums=(1,),
        )
        args = (a_params, a_cache, specs["tokens"], specs["pos"], enc_sds)
    else:
        def serve_step(params, cache, tokens, pos):
            return model.decode_step(params, cache, tokens, pos)
        jitted = jax.jit(
            serve_step,
            in_shardings=(p_sh, c_sh, _ns(mesh, P(*tok_spec, None)),
                          pos_sh),
            out_shardings=None,
            donate_argnums=(1,),
        )
        args = (a_params, a_cache, specs["tokens"], specs["pos"])
    return jitted, args


def make_step(model, mesh: Mesh, cell):
    if cell.kind == "train":
        return make_train_step(model, mesh, cell)
    if cell.kind == "prefill":
        return make_prefill_step(model, mesh, cell)
    return make_decode_step(model, mesh, cell)
