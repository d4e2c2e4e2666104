"""The comparisons that decide ``correct``, against the plain reference.

Serving: for a sample of the requests the window finished, the reference
runs once over each prompt with its served tokens (teacher-forced, fp32,
weights regenerated from the seed), and the number compared is the widest
gap by which a served token's reference logit lies below the reference's
best at that position.  The control reads, at the same positions, the gap
of the token that an fp8 evaluation of the reference puts first.

Pruning: the reference captures the calibration Hessians in fp32 and
prunes every linear with the literal Thanos n:m recurrence.  The program's
result for each linear is held to the n:m pattern exactly, and its
share of mask entries that differ from the reference's, and its weights
in the first block of columns (which no earlier rounding has steered) to
the reference's.  The reconstruction error and the OBS loss are reported
beside them, not compared: the fp8 control moves neither by more than
the program's own run-to-run scatter (PERF.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def _prep(ref, nm):
    """fp32 copy of a block's weights, n:m-masked by the reference's own
    magnitude rule when the cell serves n:m weights: a 2-D kernel whole, a
    stack of experts (E, in, out) one expert at a time."""

    def mask(v):
        return ref.magnitude_nm_mask(v, *nm)

    @jax.jit
    def prep(flat):
        out = {}
        for k, v in flat.items():
            v = v.astype(F32)
            if nm and k in ref.LINEARS:
                v = jnp.where(jax.vmap(mask)(v) if v.ndim == 3 else mask(v),
                              0.0, v)
            out[k] = v
        return out

    return prep


@functools.lru_cache(maxsize=None)
def _serve_fns(ref_name: str, cfg_items: tuple, nm: tuple | None):
    from bench import harness

    ref = harness.reference(ref_name)
    cfg = dict(cfg_items)
    lins = {"ref": ref.matmul, "fp8": ref.fp8_matmul}
    block = {k: jax.jit(functools.partial(ref.block, cfg=cfg, lin=f))
             for k, f in lins.items()}
    head = {k: jax.jit(functools.partial(ref.head, cfg=cfg, lin=f))
            for k, f in lins.items()}
    return ref, _prep(ref, nm), block, head


def served_gaps(cfg: dict, seed: int, requests: list, max_len: int,
                nm: tuple | None, control: bool = False) -> dict:
    """Reference gaps of the served tokens of ``requests`` (each with
    ``prompt`` and ``out``), and with ``control`` the fp8 control's."""
    keys = tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))
    ref, prep, block, head = _serve_fns(cfg["reference"], keys,
                                        tuple(nm) if nm else None)
    kinds = ("ref", "fp8") if control else ("ref",)
    seqs = []
    for r in requests:
        full = np.concatenate([np.asarray(r.prompt, np.int32),
                               np.asarray(r.out[:-1], np.int32)])
        seqs.append(np.pad(full, (0, max_len - len(full))))
    top = prep(W.make(seed, W.TOP, ref.top_leaves(cfg), cfg["dtype"]))
    h = {k: [top["embed/table"][jnp.asarray(s)] for s in seqs] for k in kinds}
    for i in range(cfg["num_layers"]):
        wf = prep(W.make(seed, i, ref.block_leaves(cfg, i), cfg["dtype"]))
        for k in kinds:
            h[k] = [block[k](x, wf) for x in h[k]]
        del wf
    gaps, ctrl = [], []
    for j, r in enumerate(requests):
        s, n = len(r.prompt), len(r.out)
        pos = np.arange(s - 1, s - 1 + n)
        lg = np.asarray(head["ref"](h["ref"][j], top))[pos]
        best = lg.max(-1)
        gaps.append(best - lg[np.arange(n), np.asarray(r.out)])
        if control:
            lq = np.asarray(head["fp8"](h["fp8"][j], top))[pos]
            ctrl.append(best - lg[np.arange(n), lq.argmax(-1)])
    out = {"served_logit_gap": float(np.max(np.concatenate(gaps))),
           "tokens_checked": int(sum(len(g) for g in gaps))}
    if control:
        out["control_logit_gap"] = float(np.max(np.concatenate(ctrl)))
    return out


def sample_requests(done: list, k: int, seed: int) -> list:
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    if not done:
        return []
    order = sorted(done, key=lambda r: (-len(r.out), r.uid))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 3])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [order[0]] + [rest[i] for i in sorted(pick)]


# --------------------------------------------------------------------------
# pruning
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _prune_fns(ref_name: str, cfg_items: tuple):
    from bench import harness

    ref = harness.reference(ref_name)
    cfg = dict(cfg_items)

    def accumulate(hs, x, w, quant):
        cap: dict = {}
        ref.block(x, w, cfg, capture=cap)
        out = {}
        for k, a in cap.items():
            a = ref.fp8(a, -1) if quant else a
            out[k] = hs[k] + jnp.dot(a.T, a, precision=ref.HIGHEST)
        return out

    acc = jax.jit(accumulate, static_argnames=("quant",))
    solve = jax.jit(ref.thanos_nm, static_argnames=("n", "m", "block_size",
                                                    "percdamp"))
    err = jax.jit(ref.reconstruction_error)
    return ref, acc, solve, err


def prune_reference(cfg: dict, seed: int, block: int, tokens: np.ndarray,
                    job: dict, quant: bool = False) -> dict:
    """The reference's prune of ``block``: per linear its dense weights,
    result, mask and loss in the paper layout (c, b), with its fp32
    Hessian.

    Calibration sequences run one at a time through the embedding and the
    blocks up to ``block`` (only block 0 is supported: the cells prune the
    first block), and each of its 2-D linears is pruned under the Hessian
    of its input.  ``quant`` makes it the fp8 control: activations rounded
    per token before the Hessian, weights per output channel before the
    solve."""
    if block != 0:
        raise ValueError("the reference prunes block 0 only")
    keys = tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))
    ref, acc, solve, err = _prune_fns(cfg["reference"], keys)
    top = W.make(seed, W.TOP, ref.top_leaves(cfg), cfg["dtype"])
    leaves = dict(ref.block_leaves(cfg, block))
    wf = jax.tree.map(lambda v: v.astype(F32),
                      W.make(seed, block, leaves.items(), cfg["dtype"]))
    table = top["embed/table"]
    linears = [k for k in ref.LINEARS if k in leaves]
    if any(len(leaves[k]) != 2 for k in linears):
        raise ValueError("the reference prunes 2-D kernels only")
    hs = {ref.LINEAR_INPUT[k]: jnp.zeros((leaves[k][0],) * 2, F32)
          for k in linears}
    for s in tokens:
        hs = acc(hs, table[jnp.asarray(s)].astype(F32), wf, quant=quant)
    hs = {k: 2.0 * v / tokens.size for k, v in hs.items()}
    out = {}
    for name in linears:
        w0 = wf[name].T
        wq = ref.fp8(w0, 1) if quant else w0
        w1, mask, loss = solve(wq, hs[ref.LINEAR_INPUT[name]], n=job["n"],
                               m=job["m"], block_size=job["block_size"],
                               percdamp=job["percdamp"])
        out[name] = {"w0": w0, "w": w1, "mask": mask, "loss": float(loss),
                     "h": hs[ref.LINEAR_INPUT[name]]}
    return {"linears": out, "err": err}


def nm_pattern_errors(w: np.ndarray, mask: np.ndarray, n: int, m: int) -> int:
    """Groups of ``m`` inputs of an output (paper layout (c, b)) whose mask
    prunes other than exactly ``n``, plus pruned weights left non-zero."""
    c, b = mask.shape
    per_group = mask.reshape(c, b // m, m).sum(-1)
    return int(np.sum(per_group != n) + np.sum((w != 0) & mask))


def compare_prune(program: dict, reference: dict, n: int, m: int,
                  block_size: int) -> dict:
    """Per linear: pattern errors of the program's result, its
    reconstruction error over the reference's less one (under the
    reference's Hessian), its OBS loss over the reference's less one, the
    share of mask entries it shares with the reference, and the relative
    error of its weights in the first column block, whose mask and update
    see the weights before any rounding of the solve has touched them."""
    err = reference["err"]
    rows = {}
    for name, r in reference["linears"].items():
        p = program[name]
        h = r["h"]
        wp = jnp.asarray(p["w"], F32)
        e_p = float(err(r["w0"], wp, h))
        first = slice(0, block_size)
        d_first = float(jnp.linalg.norm(wp[:, first] - r["w"][:, first])
                        / jnp.linalg.norm(r["w"][:, first]))
        e_r = float(err(r["w0"], r["w"], h))
        rows[name] = {
            "pattern_errors": nm_pattern_errors(np.asarray(p["w"], np.float32),
                                                np.asarray(p["mask"]) > 0.5,
                                                n, m),
            "err_excess": e_p / e_r - 1.0,
            "loss_excess": p["loss"] / r["loss"] - 1.0,
            "mask_agreement": float(np.mean((np.asarray(p["mask"]) > 0.5)
                                            == np.asarray(r["mask"]))),
            "first_block_err": d_first,
        }
    return rows
