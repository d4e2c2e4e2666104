"""Operations and bytes that the work of a cell needs, counted from shapes.

A copy of the arithmetic, kept with the benchmark so that no change to the
program can change it.  Counts are of what the algorithm needs, not of what
an implementation happens to execute:

* a multiply-add is 2 operations;
* an n:m compressed linear needs the multiply-adds of its kept weights,
  ``(m - n) / m`` of the dense ones; the router and MLA's ``wkv_b`` stay
  dense (the program streams neither through the n:m kernel);
* attention needs ``QKᵀ`` and ``PV`` over the live context of each query
  (causal, clipped to the sliding window); softmax, norms, RoPE and
  activations are left out (a few per cent of a step at these widths);
* MLA attention is counted as it is computed: a prompt in the expanded
  form (keys of ``qk_nope + qk_rope``, values of ``v_head_dim`` per head),
  a decode token in the absorbed form (``W_k`` absorbed into the query,
  scores over ``kv_lora + qk_rope`` per key, values over ``kv_lora``,
  then ``W_v`` un-absorbed), whose two absorb products together cost
  what ``wkv_b`` costs on one token;
* an expert layer holds ``num_experts`` of the router's
  ``num_routed_experts`` (0: all of them), so a token visits
  ``num_experts_per_tok · num_experts / num_routed_experts`` of the held
  experts on average, each of which sees that share of the tokens; the
  router (at its published width) and the shared experts see every token;
* embedding lookups need no operations; the output head is dense.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

DENSE = ("router", "wkv_b")   # linears never streamed n:m


class Linear(NamedTuple):
    """One weight matrix of a block: ``c`` outputs, ``b`` inputs."""

    name: str
    c: int
    b: int
    share: float = 1.0          # of the block's tokens that pass through it
    input: str | None = None    # the activation its Hessian is built from;
                                # None: never pruned


@dataclasses.dataclass(frozen=True)
class Shape:
    """The widths the counts need: a decoder of GQA or MLA attention, with
    dense or held-share expert feed-forward layers."""

    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    sliding_window: int = 0
    # MLA (0: GQA)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # expert layers (0: none)
    num_experts: int = 0          # held on this chip
    num_routed_experts: int = 0   # the router's width; 0: num_experts
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0
    num_dense_layers: int = 0     # leading layers with a dense MLP

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        return cls(**{f.name: cfg.get(f.name, f.default)
                      for f in dataclasses.fields(cls)})

    def is_moe(self, i: int) -> bool:
        return self.num_experts > 0 and i >= self.num_dense_layers

    def blocks(self) -> list[tuple[int, int]]:
        """(first block, number of blocks) of each kind of block: the
        dense ones, then the expert ones."""
        dense = (min(self.num_dense_layers, self.num_layers)
                 if self.num_experts else self.num_layers)
        return [(i, n) for i, n in ((0, dense),
                                    (dense, self.num_layers - dense)) if n]

    def layout(self, i: int = 0) -> list[Linear]:
        """Every linear of block ``i``, a stack of experts one per held
        expert, with the share of tokens it sees and its input."""
        d, H = self.d_model, self.num_heads
        if self.kv_lora_rank:
            dq, dkv = self.q_lora_rank, self.kv_lora_rank
            dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
            attn = [Linear("wq_a", dq, d, input="x"),
                    Linear("wq_b", H * (dn + dr), dq, input="q_latent"),
                    Linear("wkv_a", dkv + dr, d, input="x"),
                    Linear("wkv_b", H * (dn + dv), dkv, input="kv_latent"),
                    Linear("wo", d, H * dv, input="o")]
        else:
            q, kv = H * self.head_dim, self.num_kv_heads * self.head_dim
            attn = [Linear("wq", q, d, input="x"),
                    Linear("wk", kv, d, input="x"),
                    Linear("wv", kv, d, input="x"),
                    Linear("wo", d, q, input="o")]
        if not self.is_moe(i):
            f = self.d_ff
            return attn + [Linear("gate", f, d, input="h"),
                           Linear("up", f, d, input="h"),
                           Linear("down", d, f, input="g")]
        f, held = self.moe_d_ff, self.num_experts
        share = self.num_experts_per_tok / (self.num_routed_experts or held)
        ffn = [Linear("router", self.num_routed_experts or held, d)]
        for name, c, b, x in (("gate", f, d, "h"), ("up", f, d, "h"),
                              ("down", d, f, "g")):
            ffn += [Linear(f"experts/{name}/{e}", c, b, share, f"{x}/{e}")
                    for e in range(held)]
        fs = f * self.num_shared_experts
        if fs:
            ffn += [Linear("shared/gate", fs, d, input="h"),
                    Linear("shared/up", fs, d, input="h"),
                    Linear("shared/down", d, fs, input="shared/g")]
        return attn + ffn

    def linears(self, i: int = 0) -> list[tuple[str, int, int]]:
        """(name, c, b) of every linear of block ``i``: c outputs, b
        inputs."""
        return [(x.name, x.c, x.b) for x in self.layout(i)]

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
def block_linear_ops(s: Shape, i: int, nm: tuple[int, int] | None) -> float:
    """Operations of block ``i``'s linears for one token."""
    keep = (nm[1] - nm[0]) / nm[1] if nm else 1.0
    lay = s.layout(i)
    packed = sum(2.0 * x.c * x.b * x.share for x in lay if x.name not in DENSE)
    dense = sum(2.0 * x.c * x.b * x.share for x in lay if x.name in DENSE)
    return packed * keep + dense


def linear_ops_per_token(s: Shape, nm: tuple[int, int] | None) -> float:
    """Operations of every block linear and the head for one token."""
    return (sum(n * block_linear_ops(s, i, nm) for i, n in s.blocks())
            + 2.0 * s.d_model * s.vocab_size)


def attention_width(s: Shape, absorbed: bool) -> int:
    """Multiply-adds of ``QKᵀ`` and ``PV`` of one head, one query and one
    key: MLA's ``absorbed`` (decode) or expanded (prompt) form."""
    if not s.kv_lora_rank:
        return 2 * s.head_dim
    if absorbed:
        return 2 * s.kv_lora_rank + s.qk_rope_head_dim
    return s.qk_nope_head_dim + s.qk_rope_head_dim + s.v_head_dim


def attention_ops(s: Shape, n_keys: int, absorbed: bool = True) -> float:
    """``QKᵀ`` and ``PV`` of one query over ``n_keys`` keys, all layers."""
    return (s.num_layers * 2.0 * s.context(n_keys) * s.num_heads
            * attention_width(s, absorbed))


def decode_token_ops(s: Shape, position: int,
                     nm: tuple[int, int] | None) -> float:
    """One token at 0-based ``position`` (it attends ``position + 1`` keys)."""
    return linear_ops_per_token(s, nm) + attention_ops(s, position + 1)


def prompt_ops(s: Shape, length: int, nm: tuple[int, int] | None) -> float:
    """A prompt of ``length`` tokens, each attending to those before it."""
    return sum(linear_ops_per_token(s, nm)
               + attention_ops(s, p + 1, absorbed=False)
               for p in range(length))


# --------------------------------------------------------------------------
# prune job (paper Alg. 3 over blocks, Alg. 8 per linear)
# --------------------------------------------------------------------------
def block_forward_ops(s: Shape, n_seqs: int, seq_len: int,
                      i: int = 0) -> float:
    """One causal forward pass of block ``i`` over the calibration set."""
    lin = sum(2.0 * x.c * x.b * x.share
              for x in s.layout(i)) * n_seqs * seq_len
    # causal QKᵀ and PV: query p attends context(p + 1) keys
    keys = sum(s.context(p + 1) for p in range(seq_len))
    return lin + (2.0 * keys * s.num_heads * attention_width(s, False)
                  * n_seqs)


def hessian_ops(s: Shape, tokens: int, i: int = 0) -> float:
    """``XᵀX`` of each distinct input of block ``i``'s pruned linears, over
    the tokens that reach it (wq/wk/wv share one, gate/up share one, an
    expert sees its share of the tokens)."""
    inputs: dict[str, Linear] = {}
    for x in s.layout(i):
        if x.input is not None:
            inputs.setdefault(x.input, x)
    return sum(2.0 * tokens * x.share * x.b * x.b for x in inputs.values())


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
                    n: int, m: int, i: int = 0) -> dict[str, float]:
    """Block ``i`` of the prune job: two forward passes (capture, then
    propagate through the pruned block), the Hessians and the solves (each
    expert of a stack is its own linear; the router is not pruned)."""
    fwd = 2.0 * block_forward_ops(s, n_seqs, seq_len, i)
    hess = hessian_ops(s, n_seqs * seq_len, i)
    solve = sum(thanos_nm_ops(x.c, x.b, block_size, n, m)
                for x in s.layout(i) if x.input is not None)
    return {"forward": fwd, "hessian": hess, "solve": solve,
            "total": fwd + hess + solve}
