"""The main path's Pallas kernels compile for a TPU v5e chip at the widths of
a 2^20-row plan (``grid2d_factor(1024)``, the size ``chip_smoke.py`` runs).

Nothing runs: the TPU compiler is handed a *described* chip, so what Mosaic
would refuse on the device (unaligned blocks, lowering gaps, VMEM or SMEM
overflow) fails here without one. The topology is described inside a fixture,
never at import, so every test worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.solver import _bucketize_levels
from repro.kernels import ops
from repro.kernels.block_spmv import block_gemm, block_gemv
from repro.kernels.block_trsv import block_trsm, block_trsv
from repro.kernels.superstep import superstep_call
from repro.sparse import suite

SIDE = 1024  # grid2d_factor(1024): 2^20 rows, the delaunay_n20 analogue


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def widths():
    """The single-device levelset plan's table sizes and bucket ladders at
    the TPU block size, from the sparsity pattern alone (the dense tile store
    of the real plan would take 1.5 GB of host memory)."""
    B = 128
    a = suite.grid2d_factor(SIDE)
    rows = np.repeat(np.arange(a.n), np.diff(a.row_ptr))
    brow, bcol = rows // B, a.col_idx.astype(np.int64) // B
    nb = -(-a.n // B)
    off = brow != bcol
    key = np.unique(brow[off] * nb + bcol[off])
    trow, tcol = key // nb, key % nb  # sorted by destination row
    lvl = np.zeros(nb, np.int64)
    ptr = np.searchsorted(trow, np.arange(nb + 1))
    for r in range(nb):
        if ptr[r + 1] > ptr[r]:
            lvl[r] = lvl[tcol[ptr[r]:ptr[r + 1]]].max() + 1
    T = int(lvl.max()) + 1
    ws, wu = np.bincount(lvl, minlength=T), np.bincount(lvl[tcol], minlength=T)
    _, _, bws, bwu, _ = _bucketize_levels(ws, wu, np.zeros(T, np.int64))
    return dict(B=B, nb=nb, T=T, S=int(bws.sum()), U=int(bwu.sum()),
                ML=len(key), sw=tuple(sorted({int(w) for w in bws})),
                uw=tuple(sorted({int(w) for w in bwu})))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is there
    return compiled


def test_plan_widths_are_the_2_20_row_plan(widths):
    assert widths["nb"] * widths["B"] == 2 ** 20
    assert widths["T"] > 1000 and widths["ML"] > widths["nb"]
    assert ops.interpret_mode()  # this process is on the CPU; only the compile targets the chip


@pytest.mark.parametrize("op,R", [("solve", 1), ("solve", 8),
                                  ("update", 1), ("update", 8)])
def test_per_op_kernel_compiles(one_chip, widths, op, R):
    """The lax.switch executor's batched solve / tile update at the widest
    level bucket (the TPU's default backend)."""
    B = widths["B"]
    k = max(widths["sw"] if op == "solve" else widths["uw"])
    tiles = jax.ShapeDtypeStruct((k, B, B), jnp.float32, sharding=one_chip)
    rhs = jax.ShapeDtypeStruct((k, B) if R == 1 else (k, B, R), jnp.float32,
                               sharding=one_chip)
    fn = {("solve", 1): block_trsv, ("solve", 8): block_trsm,
          ("update", 1): block_gemv, ("update", 8): block_gemm}[op, R]
    _compile(lambda t, r: fn(t, r, interpret=False), tiles, rhs)


@pytest.mark.parametrize("R", [1, 8])
def test_streamed_megakernel_compiles(one_chip, widths, R):
    """The fused kernel with the schedule-ordered HBM tile store and HBM
    carries, one launch over every level — what kernel="fused" runs at this
    size. Its schedule rides in SMEM, so this also checks that it fits."""
    w = widths
    B, T = w["B"], w["T"]

    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    i32 = jnp.int32
    args = (spec((2,), i32), spec((T, 3), i32), spec((T, 3), i32),
            spec((w["S"],), i32), spec((w["U"],), i32),
            spec((w["ML"] + 1,), i32), spec((w["ML"] + 1,), i32),
            spec((w["S"], B, B)), spec((w["U"], B, B)),
            spec((w["nb"] + 1, R, B)), spec((w["nb"] + 1, R, B)),
            spec((w["nb"] + 1, R, B)), spec((T + 1,), i32))

    def launch(seg, off, wid, sr, ut, trow, tcol, diag, tiles, b, acc, x, stp):
        return superstep_call(seg, off, wid, sr, ut, trow, tcol, diag, tiles,
                              b, acc, x, stp=stp, grid=T, stream=True,
                              solve_widths=w["sw"], upd_widths=w["uw"])

    _compile(launch, *args)
