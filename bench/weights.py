"""Weights and inputs made from the seed, on the device.

The benchmark makes the weights, not the program: the reference takes
nothing that the program has made, so both are handed the output of the
same generator.  Every leaf is a pure function of (seed, block, name):
one jitted call makes a whole block, and calling it again for the
reference gives the same bits.

Scales: kernels ``N(0, 2/fan_in)``, stored (in, out) or, for a stack of
experts, (E, in, out), so that the fan-in is ``shape[-2]`` either way;
the embedding ``N(0, 1)`` so that RMSNorm's epsilon is negligible against
the mean square; norm scales 1.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

TOP = -1   # block index of the embedding, final norm and head


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words of key material from any whole number."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def std(name: str, shape) -> float:
    """The standard deviation leaf ``name`` of ``shape`` is drawn with."""
    if name.startswith("embed"):
        return 1.0
    return (2.0 / shape[-2 if len(shape) >= 2 else 0]) ** 0.5


def _leaf(key, name: str, shape, dtype):
    if name.endswith("scale"):
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return (jax.random.normal(k, shape, jnp.float32)
            * std(name, shape)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("leaves", "dtype"))
def _make(kd, index, *, leaves, dtype):
    key = jax.random.fold_in(jax.random.wrap_key_data(kd), index + 1)
    return {name: _leaf(key, name, shape, dtype) for name, shape in leaves}


def make(seed: int, index: int, leaves, dtype=jnp.bfloat16) -> dict:
    """Flat {name: array} of block ``index`` (``TOP`` for the rest)."""
    return _make(jnp.asarray(key_data(seed)), jnp.int32(index),
                 leaves=tuple((n, tuple(s)) for n, s in leaves),
                 dtype=jnp.dtype(dtype).name)


def nest(flat: dict) -> dict:
    """{'attn/wq/w': x} → {'attn': {'wq': {'w': x}}}."""
    out: dict = {}
    for name, x in flat.items():
        node = out
        *head, last = name.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = x
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    """Inverse of ``nest`` over nested dicts of arrays."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name + "/"))
        else:
            out[name] = v
    return out


def tokens(seed: int, stream: int, shape, vocab: int,
           zipf_a: float = 0.0) -> np.ndarray:
    """int32 token ids of ``shape``, uniform or Zipf(``zipf_a``) over the
    vocabulary, a pure function of (seed, stream)."""
    rng = np.random.default_rng([int(seed), stream])
    if not zipf_a:
        return rng.integers(0, vocab, size=shape, dtype=np.int32)
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -zipf_a
    return rng.choice(vocab, size=shape, p=p / p.sum()).astype(np.int32)
