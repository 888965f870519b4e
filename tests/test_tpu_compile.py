"""The main path's Pallas kernels compile for a TPU v5e chip at the widths of
a 2^20-row plan (``grid2d_factor(1024)``, the size ``chip_smoke.py`` runs).

Nothing runs: the TPU compiler is handed a *described* chip, so what Mosaic
would refuse on the device (unaligned blocks, lowering gaps, VMEM or SMEM
overflow) fails here without one. The topology is described inside a fixture,
never at import, so every test worker collects the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import compat
from repro.core import SolverConfig, build_plan
from repro.core.solver import (
    AXIS, _bucketize_levels, _levelset_device_fn, device_diag,
    host_order_program, rows_in, rows_out,
)
from repro.kernels import ops
from repro.kernels.block_spmv import block_gemm_rows, block_gemv
from repro.kernels.block_trsv import block_trsm_rows, block_trsv
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


@pytest.mark.parametrize("op,R", [("solve", 1), ("update", 1),
                                  ("solve_rows", 8), ("update_rows", 8)])
def test_per_op_kernel_compiles(one_chip, widths, op, R):
    """The lax.switch executor's batched solve / tile update at the widest
    level bucket (the TPU's default backend); ``*_rows`` take the ``(k, R, B)``
    row panels that the levelset executors carry."""
    B = widths["B"]
    k = max(widths["sw"] if op.startswith("solve") else widths["uw"])
    tiles = jax.ShapeDtypeStruct((k, B, B), jnp.float32, sharding=one_chip)
    shape = (k, B) if R == 1 else (k, R, B)
    rhs = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    fn = {("solve", 1): block_trsv, ("update", 1): block_gemv,
          ("solve_rows", 8): block_trsm_rows,
          ("update_rows", 8): block_gemm_rows}[op, R]
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


def _carry_copies(text: str, rows: int) -> list:
    """Shape and layout of each ``copy`` of a whole ``rows``-row f32 array."""
    return re.findall(r"= (f32\[%d,[0-9,]+\]\{[^}]*\}) copy\(" % rows, text)


def test_levelset_panel_keeps_its_carry_layout(topo, monkeypatch):
    """The levelset executor's whole program at R=8 on a 512-block-row plan
    (``grid2d_factor(256)``, B = 128, Pallas kernels): no ``copy`` moves a
    whole ``(nb+1)``-row carry into another layout, and there are no more
    whole-carry copies than the R=1 program has. Column panels ``(nb+1, B, R)``
    were copied between R-on-the-lanes and B-on-the-lanes layouts at every
    level; ``(nb+1, R, B)`` row panels need no such copy."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)  # compile, not interpret
    plan = build_plan(suite.grid2d_factor(256), 1,
                      SolverConfig(block_size=128, kernel_backend="pallas"))
    nb, B = plan.bs.nb, plan.bs.B
    assert nb == 512
    mesh = compat.make_mesh((1,), (AXIS,), devices=topo.devices[:1])
    sharded, repl = NamedSharding(mesh, P(AXIS)), NamedSharding(mesh, P())
    owner = np.ones((1, nb + 1), np.float32)
    host = (plan.solve_rows, plan.solve_slots, plan.upd_tiles, plan.tile_row,
            plan.tile_col, plan.tiles, owner, device_diag(plan), plan.ex_rows)
    args = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharded)
            for x in host[:-1]]
    args.append(jax.ShapeDtypeStruct(host[-1].shape, host[-1].dtype, sharding=repl))
    fn = compat.shard_map(_levelset_device_fn(plan), mesh=mesh,
                          in_specs=(P(AXIS),) * 8 + (P(), P()), out_specs=P())
    copies = {}
    for R in (1, 8):
        shape = (nb + 1, B) if R == 1 else (nb + 1, B, R)
        b = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=repl)
        copies[R] = _carry_copies(_compile(fn, *args, b).as_text(), nb + 1)
    own = f"f32[{nb + 1},8,{B}]{{2,1,0:"  # the row-panel carry, row-major
    assert all(c.startswith(own) for c in copies[8]), copies[8]
    assert len(copies[8]) <= len(copies[1]), copies


def _boundary_layouts(text: str) -> list:
    """The right-hand side's (last) parameter and the result of the entry
    computation, each as ``shape{layout}``."""
    params, result = re.search(r"entry_computation_layout=\{(.*)", text).group(1).split(")->")
    array = r"[a-z0-9]+\[[0-9,]*\]\{[^}]*\}"
    return [re.findall(array, params)[-1], re.findall(array, result)[0]]


@pytest.mark.parametrize("R", [1, 8])
def test_numpy_path_boundary_is_in_host_order(topo, widths, R):
    """The program a numpy solve runs (``host_order_program``) at the 2^20-row
    widths, around a stand-in executor that solves every diagonal block with
    the executors' own kernel on their own carries: its right-hand side and
    its answer are flat and in host order on the device (``{0:T(1024)}``), so
    each transfer is a straight copy. The ``(nb, B, R)`` panel it replaced
    was laid out ``{1,2,0}``, and an ``(n, R)`` one would be ``{0,1}``: both
    transposed on the host at every transfer."""
    B, nb = widths["B"], widths["nb"]
    n = nb * B
    mesh = compat.make_mesh((1,), (AXIS,), devices=topo.devices[:1])
    repl = NamedSharding(mesh, P())

    def executor(diag, b_pad):
        rows = rows_in(b_pad)[:nb]
        solve = block_trsm_rows if R > 1 else block_trsv
        return rows_out(solve(diag, rows, interpret=False), R > 1)

    program = host_order_program(executor, mesh, (P(), P()), nb, B, transpose=True)
    diag = jax.ShapeDtypeStruct((nb, B, B), jnp.float32, sharding=repl)
    b = jax.ShapeDtypeStruct((n * R,), jnp.float32, sharding=repl)
    shape = (n,) if R == 1 else (n, R)
    compiled = program.lower(diag, b, shape=shape).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    flat = f"f32[{n * R}]{{0:T(1024)}}"
    assert _boundary_layouts(text) == [flat, flat]
