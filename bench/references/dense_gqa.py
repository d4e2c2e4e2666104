"""Plain fp32 reference of a dense GQA decoder and of Thanos n:m pruning.

Imports nothing of the program.  It follows the published descriptions
(Llama/Mistral-style block: RMSNorm, rotary embeddings on half-split
heads, grouped-query causal attention with an optional sliding window,
SwiGLU MLP; untied output head) and the paper's Alg. 8 literally, one
block of columns at a time with the trailing inverse Hessian formed anew
for each block.  Every matrix product runs at ``highest`` precision, so
that on a TPU it is fp32 and not one bf16 pass.

Weights are flat dicts keyed by the leaf names of ``block_leaves`` /
``top_leaves``; kernels are stored (in, out).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------
def block_leaves(cfg: dict, i: int) -> list[tuple[str, tuple[int, ...]]]:
    """(name, shape) of every weight of decoder block ``i``; every block of
    a dense GQA decoder has the same."""
    d, f = cfg["d_model"], cfg["d_ff"]
    q = cfg["num_heads"] * cfg["head_dim"]
    kv = cfg["num_kv_heads"] * cfg["head_dim"]
    return [("ln1/scale", (d,)), ("ln2/scale", (d,)),
            ("attn/wq/w", (d, q)), ("attn/wk/w", (d, kv)),
            ("attn/wv/w", (d, kv)), ("attn/wo/w", (q, d)),
            ("mlp/gate/w", (d, f)), ("mlp/up/w", (d, f)),
            ("mlp/down/w", (f, d))]


def top_leaves(cfg: dict) -> list[tuple[str, tuple[int, ...]]]:
    d, v = cfg["d_model"], cfg["vocab_size"]
    return [("embed/table", (v, d)), ("final_norm/scale", (d,)),
            ("lm_head/w", (d, v))]


LINEARS = ("attn/wq/w", "attn/wk/w", "attn/wv/w", "attn/wo/w",
           "mlp/gate/w", "mlp/up/w", "mlp/down/w")

# which captured input each linear's Hessian is built from
LINEAR_INPUT = {"attn/wq/w": "attn_in", "attn/wk/w": "attn_in",
                "attn/wv/w": "attn_in", "attn/wo/w": "wo_in",
                "mlp/gate/w": "mlp_in", "mlp/up/w": "mlp_in",
                "mlp/down/w": "down_in"}


def magnitude_nm_mask(kernel: jax.Array, n: int, m: int) -> jax.Array:
    """True where an (in, out) kernel is pruned: in every group of ``m``
    consecutive inputs of an output, the ``n`` smallest magnitudes, the
    lower input index first among equal ones.  Outputs are taken 256 or
    fewer at a time, which bounds the (outputs, groups, m) intermediates."""
    b, c = kernel.shape
    rows = math.gcd(c, 256)

    def part(k):                                   # (b, rows)
        mag = jnp.abs(k.astype(F32)).T.reshape(rows, b // m, m)
        j = jnp.arange(m)
        before = (mag[..., None, :] < mag[..., :, None]) | (
            (mag[..., None, :] == mag[..., :, None]) & (j < j[:, None]))
        return (before.sum(-1) < n).reshape(rows, b).T

    parts = jax.lax.map(part, kernel.reshape(b, c // rows, rows)
                        .transpose(1, 0, 2))
    return parts.transpose(1, 0, 2).reshape(b, c)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (T, H, D), pos (T,): rotate the two halves of each head."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = pos[:, None].astype(F32) * freqs                       # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int):
    """Causal GQA over one sequence: q (T, H, D), k/v (T, Hkv, D)."""
    t, h, d = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("thd,uhd->htu", q, k, precision=HIGHEST) / math.sqrt(d)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    ok = j <= i
    if window:
        ok &= j > i - window
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("htu,uhd->thd", p, v, precision=HIGHEST)


def matmul(x, w):
    return jnp.dot(x, w, precision=HIGHEST)


def block(x, w: dict, cfg: dict, lin=matmul, capture: dict | None = None):
    """One decoder block over one sequence x (T, d).  ``capture`` collects
    the input of each linear, by ``LINEAR_INPUT`` name."""
    t = x.shape[0]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    a = rmsnorm(x, w["ln1/scale"], eps)
    q = lin(a, w["attn/wq/w"]).reshape(t, cfg["num_heads"], hd)
    k = lin(a, w["attn/wk/w"]).reshape(t, cfg["num_kv_heads"], hd)
    v = lin(a, w["attn/wv/w"]).reshape(t, cfg["num_kv_heads"], hd)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    o = attention(q, k, v, cfg.get("sliding_window", 0)).reshape(t, -1)
    x = x + lin(o, w["attn/wo/w"])
    b = rmsnorm(x, w["ln2/scale"], eps)
    g = jax.nn.silu(lin(b, w["mlp/gate/w"])) * lin(b, w["mlp/up/w"])
    if capture is not None:
        capture.update(attn_in=a, wo_in=o, mlp_in=b, down_in=g)
    return x + lin(g, w["mlp/down/w"])


def head(x, w: dict, cfg: dict, lin=matmul):
    return lin(rmsnorm(x, w["final_norm/scale"], cfg["rms_norm_eps"]),
               w["lm_head/w"])


# --------------------------------------------------------------------------
# the lower-precision control: fp8 (e4m3) weights and activations
# --------------------------------------------------------------------------
FP8_MAX = 448.0


def fp8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def fp8_matmul(x, w):
    """Activations per token, weights per output channel, both in fp8."""
    return matmul(fp8(x, -1), fp8(w, 0))


# --------------------------------------------------------------------------
# Thanos n:m (paper Alg. 8), literally
# --------------------------------------------------------------------------
def dampen(h, percdamp):
    dead = jnp.diagonal(h) <= 0
    h = h + jnp.diag(jnp.where(dead, 1.0, 0.0))
    return h + percdamp * jnp.mean(jnp.diagonal(h)) * jnp.eye(h.shape[0])


def trailing_inverse(hd, j1):
    """``[H_{j1:, j1:}]⁻¹`` embedded in a (b, b) matrix, zero elsewhere."""
    b = hd.shape[0]
    act = jnp.arange(b) >= j1
    both = act[:, None] & act[None, :]
    ht = jnp.where(both, hd, jnp.eye(b, dtype=F32))
    low = jnp.linalg.cholesky(ht)
    linv = jax.scipy.linalg.solve_triangular(low, jnp.eye(b, dtype=F32),
                                             lower=True)
    return jnp.where(both, jnp.dot(linv.T, linv, precision=HIGHEST), 0.0)


def thanos_nm(w, h, n: int, m: int, block_size: int, percdamp: float):
    """Prune (c, b) ``w`` n:m under the Hessian ``h = 2XXᵀ/N``.

    Per block of ``block_size`` columns: the n smallest |W|·‖X_j‖ of every
    group of m (the lower column first among equal ones), then for every
    row the OBS update of all its trailing columns that zeroes the pruned
    ones (λ̂ R̂ = u, W ← W − λ̂ R), with the loss ½ λ̂·u.
    Returns (weights, mask with True = pruned, loss)."""
    c, b = w.shape
    B = min(block_size, b)
    r = n * B // m
    rc = math.gcd(c, 128)
    diag = jnp.diagonal(h)
    xnorm = jnp.sqrt(jnp.clip(diag, 0.0) * 0.5)
    w = jnp.where((diag <= 0)[None, :], 0.0, w.astype(F32))
    hd = dampen(h, percdamp)

    def one_block(k, carry):
        w, mask, loss = carry
        j1 = k * B
        hinv = trailing_inverse(hd, j1)
        blk = jax.lax.dynamic_slice(w, (0, j1), (c, B))
        xn = jax.lax.dynamic_slice(xnorm, (j1,), (B,))
        metric = (jnp.abs(blk) * xn[None, :]).reshape(c, B // m, m)
        rank = jnp.argsort(jnp.argsort(metric, -1, stable=True), -1,
                           stable=True)
        pruned = (rank < n).reshape(c, B)
        q = jnp.sort(jnp.argsort(~pruned, axis=1, stable=True)[:, :r],
                     axis=1) + j1                                   # (c, r)

        def rows(i, carry):
            w, loss = carry
            wi = jax.lax.dynamic_slice(w, (i * rc, 0), (rc, b))
            qi = jax.lax.dynamic_slice(q, (i * rc, 0), (rc, r))
            big_r = hinv[qi]                                        # (rc,r,b)
            r_hat = jnp.take_along_axis(big_r, qi[:, None, :], axis=2)
            u = jnp.take_along_axis(wi, qi, axis=1)
            lam = jnp.linalg.solve(r_hat, u[..., None])[..., 0]
            wi = wi - jnp.einsum("ir,irb->ib", lam, big_r, precision=HIGHEST)
            wi = wi.at[jnp.arange(rc)[:, None], qi].set(0.0)
            return (jax.lax.dynamic_update_slice(w, wi, (i * rc, 0)),
                    loss + 0.5 * jnp.sum(lam * u))

        w, loss = jax.lax.fori_loop(0, c // rc, rows, (w, loss))
        mask = jax.lax.dynamic_update_slice(mask, pruned, (0, j1))
        return w, mask, loss

    return jax.lax.fori_loop(
        0, b // B, one_block,
        (w, jnp.zeros((c, b), bool), jnp.zeros((), F32)))


def reconstruction_error(w0, w1, h):
    """‖(Ŵ − W)X‖²_F = tr(Δ (H/2) Δᵀ)."""
    d = (w1 - w0).astype(F32)
    return jnp.sum(jnp.dot(d, 0.5 * h, precision=HIGHEST) * d)
