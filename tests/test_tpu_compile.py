"""Compile the main-path Pallas kernels, and one block of the Thanos solve,
for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel at h2o-danube-1.8b's
real widths for a v5e chip that is described, not attached, so Mosaic
rejects here what it would reject on the chip (unaligned blocks, in-kernel
lane reshapes, scoped-VMEM overflows).  The topology is described inside
module-scoped fixtures — never at import — so every xdist worker collects
the same tests and only the worker given this file loads the TPU compiler.
This is the only test file that describes the chip.
"""
from __future__ import annotations

import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import solver
from repro.core.sparsity import NmCompressed
from repro.kernels import ops

# (c, b) of every projection: q/o, k/v, gate/up, down
DANUBE_LINEARS = [(2560, 2560), (640, 2560), (6912, 2560), (2560, 6912)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """``ops`` picks interpret mode from the backend, which is the CPU
    here; compile the real kernel instead."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("batch", [4, 128])
@pytest.mark.parametrize("c,b", DANUBE_LINEARS)
def test_nm_kernel_compiles_for_v5e(one_chip, for_the_chip, c, b, batch):
    """2:4, bf16 values, 4-bit indices, tiles from ``choose_tiles`` — the
    serving decode's compressed matmul, padding included."""
    n, m = 2, 4
    keep, g = m - n, b // m

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    packed = NmCompressed(values=sds((keep, c, g), jnp.bfloat16),
                          indices=sds(((keep + 1) // 2, c, g), jnp.int8),
                          n=n, m=m, b=b, idx_bits=4)
    x = sds((batch, b), jnp.bfloat16)
    compiled = _compile(lambda x, p: ops.nm_matmul(x, p, impl="pallas"),
                        x, packed)
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_nm_kernel_keeps_its_name_in_the_compiled_program(one_chip,
                                                          for_the_chip):
    """A profile names each device op after its HLO instruction; the
    benchmark finds the kernel's calls as ``%nm_matmul.<n> = ...``."""
    n, m, c, b = 2, 4, 640, 2560
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    packed = NmCompressed(values=sds((m - n, c, b // m), jnp.bfloat16),
                          indices=sds((1, c, b // m), jnp.int8),
                          n=n, m=m, b=b, idx_bits=4)
    compiled = _compile(lambda x, p: ops.nm_matmul(x, p, impl="pallas"),
                        sds((4, b), jnp.bfloat16), packed)
    assert re.search(r"%nm_matmul(\.\d+)? = \S+ custom-call\(",
                     compiled.as_text())


@pytest.mark.parametrize("b", [2560, 6912])
def test_hessian_kernel_compiles_for_v5e(one_chip, for_the_chip, b):
    x = jax.ShapeDtypeStruct((2048, b), jnp.bfloat16, sharding=one_chip)
    _compile(lambda x: ops.hessian_xtx(x, impl="pallas"), x)


@pytest.mark.parametrize("c,b", [(6912, 2560), (2560, 6912)])
def test_prune_block_selects_without_elementwise_gather(one_chip, c, b):
    """One 2:4 block of the Thanos solve (B 128, r_max 64) at danube's gate
    and down shapes builds each row's (r_max, r_max) system by contraction:
    no gather in the program yields c·64·64 elements, the per-element fetch
    from the (b, b) inverse that once took 83% of the solve on a v5e.  And
    the solve's matvecs stay in f32: no default-precision (bf16-operand)
    convolution yields a (c, 1, r_max) or (c, r_max, 1) result."""
    r_max, B = 64, 128
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(
        lambda h, w, q, v, j1: solver.prune_block(h, w, q, v, j1, B)
    ).lower(sds((b, b), jnp.float32), sds((c, b), jnp.float32),
            sds((c, r_max), jnp.int32), sds((c, r_max), jnp.bool_),
            sds((), jnp.int32)).compile()
    text = compiled.as_text()
    gathers = re.findall(r"= \w+\[([\d,]*)\]\S* gather\(", text)
    sizes = [math.prod(int(d) for d in g.split(",") if d) for g in gathers]
    assert c * r_max * r_max not in sizes
    matvec = re.compile(rf"= f32\[{c},(1,{r_max}|{r_max},1)\]\S* convolution\(")
    assert not [line for line in text.splitlines()
                if matvec.search(line) and "operand_precision" not in line]
