"""Operations and bytes that the work of a cell needs, counted from shapes.

A copy of the arithmetic, kept with the benchmark so that no change to the
program can change it.  Counts are of what the algorithm needs, not of what
an implementation happens to execute:

* a multiply-add is 2 operations;
* an n:m compressed linear needs the multiply-adds of its kept weights,
  ``(m - n) / m`` of the dense ones;
* attention needs ``QKᵀ`` and ``PV`` over the live context of each query
  (causal, clipped to the sliding window); softmax, norms, RoPE and
  activations are left out (a few per cent of a step at these widths);
* embedding lookups need no operations; the output head is dense.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shape:
    """The widths of a dense GQA decoder that the counts need."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    sliding_window: int = 0

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        return cls(**{f.name: cfg.get(f.name, f.default)
                      for f in dataclasses.fields(cls)})

    def linears(self) -> list[tuple[str, int, int]]:
        """(name, c, b) of every linear of one block: c outputs, b inputs."""
        d, q, kv, f = (self.d_model, self.num_heads * self.head_dim,
                       self.num_kv_heads * self.head_dim, self.d_ff)
        return [("wq", q, d), ("wk", kv, d), ("wv", kv, d), ("wo", d, q),
                ("gate", f, d), ("up", f, d), ("down", d, f)]

    def context(self, n_keys: int) -> int:
        """Keys a query attends to when ``n_keys`` precede it (itself
        included)."""
        w = self.sliding_window
        return min(n_keys, w) if w else n_keys


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------
def nm_index_planes(keep: int, idx_bits: int) -> int:
    return (keep + 1) // 2 if idx_bits == 4 else keep


def nm_matmul_cost(B: int, c: int, b: int, n: int, m: int, idx_bits: int = 4,
                   x_bytes: int = 2, w_bytes: int = 2,
                   y_bytes: int = 2) -> tuple[float, float]:
    """(operations, HBM bytes) of ``y = x @ Wᵀ`` for x (B, b) and an n:m
    compressed W (c, b): kept values, in-group indices, x read, y written."""
    keep, g = m - n, b // m
    ops = 2.0 * B * c * g * keep
    weight = keep * c * g * w_bytes + nm_index_planes(keep, idx_bits) * c * g
    return ops, float(weight + B * b * x_bytes + B * c * y_bytes)


def dense_matmul_cost(B: int, c: int, b: int, x_bytes: int = 2,
                      w_bytes: int = 2, y_bytes: int = 2) -> tuple[float, float]:
    return 2.0 * B * c * b, float(c * b * w_bytes + B * b * x_bytes
                                  + B * c * y_bytes)


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")


# --------------------------------------------------------------------------
# model
# --------------------------------------------------------------------------
def linear_ops_per_token(s: Shape, nm: tuple[int, int] | None) -> float:
    """Operations of every block linear and the head for one token."""
    keep = (nm[1] - nm[0]) / nm[1] if nm else 1.0
    per_block = sum(2.0 * c * b for _, c, b in s.linears()) * keep
    return s.num_layers * per_block + 2.0 * s.d_model * s.vocab_size


def attention_ops(s: Shape, n_keys: int) -> float:
    """``QKᵀ`` and ``PV`` of one query over ``n_keys`` keys, all layers."""
    return s.num_layers * 4.0 * s.context(n_keys) * s.num_heads * s.head_dim


def decode_token_ops(s: Shape, position: int,
                     nm: tuple[int, int] | None) -> float:
    """One token at 0-based ``position`` (it attends ``position + 1`` keys)."""
    return linear_ops_per_token(s, nm) + attention_ops(s, position + 1)


def prompt_ops(s: Shape, length: int, nm: tuple[int, int] | None) -> float:
    """A prompt of ``length`` tokens, each attending to those before it."""
    return sum(decode_token_ops(s, p, nm) for p in range(length))


# --------------------------------------------------------------------------
# prune job (paper Alg. 3 over blocks, Alg. 8 per linear)
# --------------------------------------------------------------------------
def block_forward_ops(s: Shape, n_seqs: int, seq_len: int) -> float:
    """One causal forward pass of one block over the calibration set."""
    lin = sum(2.0 * c * b for _, c, b in s.linears()) * n_seqs * seq_len
    # causal QKᵀ and PV: query p attends context(p + 1) keys
    keys = sum(s.context(p + 1) for p in range(seq_len))
    return lin + 4.0 * keys * s.num_heads * s.head_dim * n_seqs


def hessian_ops(s: Shape, tokens: int) -> float:
    """``XᵀX`` of each distinct linear input of a block (wq/wk/wv share one,
    gate/up share one)."""
    widths = (s.d_model, s.num_heads * s.head_dim, s.d_model, s.d_ff)
    return sum(2.0 * tokens * b * b for b in widths)


def thanos_nm_ops(c: int, b: int, block_size: int, n: int, m: int) -> float:
    """Thanos n:m on a (c, b) linear by its block recurrence.

    One inverse of the damped Hessian (``b³`` through its Cholesky factor),
    then for each block of ``B`` columns with ``t`` columns still to prune:
    every row's ``r = nB/m`` multipliers (a Cholesky solve, ``r³/3``), the
    row update over the ``t`` trailing columns (``2rt``), and the trailing
    inverse advanced by a rank-``B`` downdate (``2Bt²``).
    """
    B = min(block_size, b)
    r = n * B // m
    ops = float(b) ** 3
    for j1 in range(0, b, B):
        t = b - j1
        ops += c * (r ** 3 / 3.0 + 2.0 * r * t) + 2.0 * B * t * t
    return ops


def prune_block_ops(s: Shape, n_seqs: int, seq_len: int, block_size: int,
                    n: int, m: int) -> dict[str, float]:
    """One block of the prune job: two forward passes (capture, then
    propagate through the pruned block), the Hessians and the solves."""
    fwd = 2.0 * block_forward_ops(s, n_seqs, seq_len)
    hess = hessian_ops(s, n_seqs * seq_len)
    solve = sum(thanos_nm_ops(c, b, block_size, n, m)
                for _, c, b in s.linears())
    return {"forward": fwd, "hessian": hess, "solve": solve,
            "total": fwd + hess + solve}
