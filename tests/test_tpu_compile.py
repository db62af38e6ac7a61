"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: each test lowers
a kernel at graph500-22 widths (2^22 vertices over b = 16 blocks, so
n_local = 2^18) for a described ``v5e:2x2`` topology and compiles it, which
raises whatever Mosaic would refuse on the chip (unsupported gathers, blocks
not aligned to the (8, 128) tiling, too much VMEM).  Nothing runs; results are
covered by the interpret-mode parity tests in test_kernels.py.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.block_gimv import dense_gimv, dense_gimv_multi
from repro.kernels.ell_spmv import ell_gimv, ell_gimv_multi
from repro.kernels.scatter_combine import (
    packed_scatter_combine_gimv,
    scatter_combine_gimv,
    scatter_combine_gimv_multi,
)

N_LOCAL = (1 << 22) // 16          # graph500-22 over b = 16 blocks
ELL_WIDTH = 32                     # one mid-width degree bucket
Q = 16                             # one serving bucket
SLOTS = 16 * 4096                  # b sets of received exchange slots
DENSE_ROWS, DENSE_COLS = 4096, 2048
HBM_BYTES = 16 * 2**30             # one v5e chip

f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache off so these stay quiet
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the program"
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


ELL = [(ELL_WIDTH, N_LOCAL), i32]              # slot-major [D, R]


@pytest.mark.parametrize("semiring,has_w", [
    ("plus_times", True), ("min_plus", True), ("min_src", False)])
def test_ell_gimv_compiles(one_chip, semiring, has_w):
    if has_w:
        fn = lambda c, w, v: ell_gimv(c, w, v, semiring=semiring)
        shapes = [ELL, [(ELL_WIDTH, N_LOCAL), f32], [(N_LOCAL,), f32]]
    else:
        fn = lambda c, v: ell_gimv(c, None, v, semiring=semiring)
        shapes = [ELL, [(N_LOCAL,), f32]]
    _compile(fn, shapes, one_chip)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_ell_gimv_multi_compiles(one_chip, semiring):
    _compile(lambda c, w, v: ell_gimv_multi(c, w, v, semiring=semiring),
             [ELL, [(ELL_WIDTH, N_LOCAL), f32], [(N_LOCAL, Q), f32]], one_chip)


@pytest.mark.parametrize("multi", [False, True])
def test_ell_gimv_batched_compiles(one_chip, multi):
    """Emulation launches b workers' tables of one bucket together."""
    tbl = (16, ELL_WIDTH, 4096)
    if multi:
        fn = lambda c, w, v: ell_gimv_multi(c, w, v, semiring="plus_times")
        v = [(N_LOCAL, Q), f32]
    else:
        fn = lambda c, w, v: ell_gimv(c, w, v, semiring="plus_times")
        v = [(N_LOCAL,), f32]
    _compile(fn, [[tbl, i32], [tbl, f32], v], one_chip)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_dense_gimv_compiles(one_chip, semiring):
    _compile(lambda m, v: dense_gimv(m, v, semiring=semiring),
             [[(DENSE_ROWS, DENSE_COLS), f32], [(DENSE_COLS,), f32]], one_chip)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_dense_gimv_multi_compiles(one_chip, semiring):
    _compile(lambda m, v: dense_gimv_multi(m, v, semiring=semiring),
             [[(DENSE_ROWS, DENSE_COLS), f32], [(DENSE_COLS, Q), f32]], one_chip)


def test_scatter_combine_gimv_compiles(one_chip):
    _compile(lambda i, v: scatter_combine_gimv(i, v, N_LOCAL + 1, semiring="plus_times"),
             [[(SLOTS,), i32], [(SLOTS,), f32]], one_chip)


@pytest.mark.parametrize("semiring", ["plus_times", "min_plus"])
def test_scatter_combine_gimv_multi_compiles(one_chip, semiring):
    _compile(lambda i, v: scatter_combine_gimv_multi(i, v, N_LOCAL + 1, semiring=semiring),
             [[(SLOTS,), i32], [(SLOTS, Q), f32]], one_chip)


@pytest.mark.parametrize("width,semiring", [(16, "plus_times"), (32, "min_plus")])
def test_packed_scatter_combine_gimv_compiles(one_chip, width, semiring):
    _compile(lambda wd, v: packed_scatter_combine_gimv(
                 wd, v, 16 * (N_LOCAL + 1), set_slots=4096, n_local=N_LOCAL,
                 width=width, semiring=semiring),
             [[(SLOTS * width // 32,), u32], [(SLOTS,), f32]], one_chip)
