"""Fused Pallas superstep megakernel vs the lax.switch compacted executor.

Bit-exactness strategy: XLA does not promise a reduction order across two
separately-compiled programs, so float comparisons between executors are only
meaningful when the arithmetic is *exact* — see the dyadic contract in
``tests/strategies.py`` (the shared home of these generators).
``assert_array_equal`` then really is bit-exactness. Real-valued suites ride
along with the scipy oracle at the usual tolerance.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import strategies
from strategies import EXACT_MATRICES, dyadic_rhs, mesh1 as _mesh1
from repro.api import SpTRSVContext
from repro.core import (
    DistributedSolver, SolverConfig, build_plan, dispatch_stats,
    fused_segments, solve_local,
)
from repro.core.blocking import pad_rhs
from repro.core.solver import _frontier_ladder, level_widths
from repro.kernels import ops
from repro.sparse import suite
from repro.sparse.matrix import reference_solve


@pytest.fixture(scope="module", params=list(EXACT_MATRICES))
def exact_problem(request):
    a = EXACT_MATRICES[request.param]()
    b = dyadic_rhs(a.n)
    x_ref = reference_solve(a, b)
    return a, b, x_ref


def test_dyadic_matrices_are_exact():
    for name, make in EXACT_MATRICES.items():
        a = make()
        assert strategies.exactness_holds(a, dyadic_rhs(a.n)), name


# ---------------------------------------------------------------------------
# fused levelset megakernel vs the lax.switch executor
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_size", [8, 16])
def test_fused_bit_exact_vs_switch(exact_problem, block_size):
    a, b, x_ref = exact_problem
    mesh = _mesh1()
    sw = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=block_size, kernel_backend="pallas")), mesh)
    fu = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=block_size, kernel_backend="fused")), mesh)
    xs, xf = sw.solve(b), fu.solve(b)
    np.testing.assert_array_equal(xs, xf)
    np.testing.assert_allclose(xf, x_ref, rtol=0, atol=0)


def test_fused_multirhs_bit_exact(exact_problem):
    """(n, R) panels through the split trsm/gemm kernel arithmetic."""
    a, b, _ = exact_problem
    rng = np.random.default_rng(2)
    B = np.column_stack([b, rng.integers(-3, 4, (a.n, 2))]).astype(np.float32)
    mesh = _mesh1()
    sw = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=16, kernel_backend="pallas")), mesh)
    fu = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=16, kernel_backend="fused")), mesh)
    Xs, Xf = sw.solve(B), fu.solve(B)
    assert sw.n_solves == fu.n_solves == 1
    np.testing.assert_array_equal(Xs, Xf)


def test_solve_local_fused_bit_exact(exact_problem):
    a, b, _ = exact_problem
    plan_sw = build_plan(a, 1, SolverConfig(block_size=8, kernel_backend="pallas"))
    plan_f = build_plan(a, 1, SolverConfig(block_size=8, kernel_backend="fused"))
    bp = jnp.asarray(pad_rhs(b, plan_sw.bs))
    np.testing.assert_array_equal(
        np.asarray(solve_local(plan_sw, bp)), np.asarray(solve_local(plan_f, bp)))


def test_fused_transpose_solve(exact_problem):
    a, b, _ = exact_problem
    mesh = _mesh1()
    def solve(kb):
        ctx = SpTRSVContext(mesh=mesh, options=SolverConfig(block_size=16, kernel_backend=kb))
        return ctx.solve(ctx.analyse(a), b, transpose=True)

    np.testing.assert_array_equal(solve("pallas"), solve("fused"))


def test_fused_real_values_match_oracle():
    """Real-valued skewed + banded suites: fused agrees with the scipy oracle
    and with the switch executor at float tolerance (XLA fusion may differ by
    ulps across separately-compiled programs)."""
    mats = {
        "skewed": suite.random_levelled(400, 24, 4.0, seed=6),
        "banded": suite.random_levelled(300, 24, 4.0, seed=7, locality=0.8),
    }
    mesh = _mesh1()
    for name, a in mats.items():
        b = np.random.default_rng(3).uniform(-1, 1, a.n)
        x_ref = reference_solve(a, b)
        for sched in ("levelset", "syncfree"):
            cfg = SolverConfig(block_size=16, sched=sched, kernel_backend="fused")
            ctx = SpTRSVContext(mesh=mesh, options=cfg)
            x = ctx.solve(ctx.analyse(a), b)
            np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4,
                                       err_msg=f"{name}/{sched}")


# ---------------------------------------------------------------------------
# streaming HBM tile store (kernel_backend="fused_streamed")
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block_size", [8, 16])
def test_streamed_bit_exact_vs_resident_and_switch(exact_problem, block_size):
    """The streaming store changes data *movement* only: streamed, resident
    fused, and the lax.switch executor agree bit-for-bit on the dyadic
    exact-arithmetic suites."""
    a, b, x_ref = exact_problem
    mesh = _mesh1()
    xs = {}
    for kb in ("pallas", "fused", "fused_streamed"):
        xs[kb] = DistributedSolver(build_plan(
            a, 1, SolverConfig(block_size=block_size, kernel_backend=kb)),
            mesh).solve(b)
    np.testing.assert_array_equal(xs["pallas"], xs["fused"])
    np.testing.assert_array_equal(xs["fused"], xs["fused_streamed"])
    np.testing.assert_allclose(xs["fused_streamed"], x_ref, rtol=0, atol=0)


def test_streamed_multirhs_bit_exact(exact_problem):
    """(n, R) panels stream the same tile slices once per solve, whatever R."""
    a, b, _ = exact_problem
    rng = np.random.default_rng(2)
    B = np.column_stack([b, rng.integers(-3, 4, (a.n, 2))]).astype(np.float32)
    mesh = _mesh1()
    fu = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=16, kernel_backend="fused")), mesh)
    st = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=16, kernel_backend="fused_streamed")), mesh)
    np.testing.assert_array_equal(fu.solve(B), st.solve(B))


def test_streamed_transpose_solve(exact_problem):
    a, b, _ = exact_problem
    mesh = _mesh1()
    def solve(kb):
        ctx = SpTRSVContext(mesh=mesh, options=SolverConfig(block_size=16, kernel_backend=kb))
        return ctx.solve(ctx.analyse(a), b, transpose=True)

    np.testing.assert_array_equal(solve("fused"), solve("fused_streamed"))


def test_streamed_vmem_buffers_sized_by_max_level_slice():
    """Acceptance (trace-time): the streamed kernel's VMEM scratch is two
    double-buffers sized by the *max per-level bucket width* — never by the
    total tile/diag store. Recorded by superstep.LAST_STREAM_ALLOC when the
    streamed launch traces."""
    from repro.kernels import superstep
    from repro.core.solver import level_widths as _lw, streamed_stores

    a = suite.random_levelled(600, 30, 3.0, seed=8)
    b = np.random.default_rng(5).uniform(-1, 1, a.n)
    plan = build_plan(a, 1, SolverConfig(block_size=8,
                                         kernel_backend="fused_streamed"))
    wid = _lw(plan)
    WS, WU = int(wid[:, 0].max()), int(wid[:, 1].max())
    total_tiles = plan.tiles.shape[1]
    assert WU < total_tiles / 4, (WU, total_tiles)  # premise: many levels

    superstep.LAST_STREAM_ALLOC.clear()
    x = DistributedSolver(plan, _mesh1()).solve(b)
    np.testing.assert_allclose(x, reference_solve(a, b), rtol=2e-4, atol=2e-4)
    alloc = superstep.LAST_STREAM_ALLOC
    assert alloc, "streamed launch must record its trace-time scratch shapes"
    B = plan.bs.B
    assert alloc["diag_buf"] == (2, WS, B, B)
    assert alloc["tile_buf"] == (2, WU, B, B)
    # the HBM stores carry the whole schedule; VMEM only the widest slice x2
    diag_s, tiles_s = streamed_stores(plan)
    assert alloc["diag_store"] == diag_s.shape[1:]
    assert alloc["tile_store"] == tiles_s.shape[1:]
    assert 2 * WU < tiles_s.shape[1]


def test_fused_auto_streams_above_vmem_limit(monkeypatch, exact_problem):
    """Plain kernel_backend="fused" upgrades to the streaming store when the
    resident footprint exceeds REPRO_STREAM_VMEM_LIMIT — and still matches
    the switch executor bit-for-bit."""
    from repro.core.solver import fused_streaming, fused_vmem_bytes

    a, b, _ = exact_problem
    cfg = SolverConfig(block_size=16, kernel_backend="fused")
    plan = build_plan(a, 1, cfg)

    monkeypatch.setenv("REPRO_STREAM_VMEM_LIMIT", str(2**40))
    assert not fused_streaming(plan)
    assert not dispatch_stats(plan)["streamed"]

    monkeypatch.setenv("REPRO_STREAM_VMEM_LIMIT", "1")
    assert fused_streaming(plan)
    ds = dispatch_stats(plan)
    assert ds["streamed"] and ds["stream_dma_bytes"] > 0
    # the reported footprint is the streamed one: bounded by the widest level
    # slice, strictly below the resident store it replaced
    assert ds["fused_vmem_bytes"] == fused_vmem_bytes(plan, streamed=True)
    assert ds["fused_vmem_bytes"] < fused_vmem_bytes(plan, streamed=False)

    xs = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=16, kernel_backend="pallas")),
        _mesh1()).solve(b)
    xa = DistributedSolver(plan, _mesh1()).solve(b)
    np.testing.assert_array_equal(xs, xa)


def test_streamed_vmem_footprint_bounded_by_widest_slice(monkeypatch):
    """Acceptance: on a matrix whose total tile store exceeds the resident
    threshold, the streamed footprint is bounded by the widest level slice
    (double-buffered) plus the O(n·B) vectors, not by the tile count."""
    from repro.core.solver import (fused_streaming, fused_vmem_bytes,
                                   level_widths as _lw)

    a = suite.random_levelled(600, 30, 3.0, seed=8)
    plan = build_plan(a, 1, SolverConfig(block_size=8, kernel_backend="fused"))
    resident = fused_vmem_bytes(plan, streamed=False)
    monkeypatch.setenv("REPRO_STREAM_VMEM_LIMIT", str(resident - 1))
    assert fused_streaming(plan)  # total store exceeds the threshold
    streamed = fused_vmem_bytes(plan, streamed=True)
    wid = _lw(plan)
    B = plan.bs.B
    widest_slice = 2 * (int(wid[:, 0].max()) + int(wid[:, 1].max())) * B * B * 4
    vectors = resident - (plan.diag.shape[0] + plan.tiles.shape[1]) * B * B * 4
    assert streamed == widest_slice + vectors
    assert streamed < resident


def test_streamed_refresh_rearms_hbm_stores(exact_problem):
    """Numeric refresh must reach the schedule-ordered HBM stores: after
    DistributedSolver.refresh the streamed executor solves with the NEW
    values, bit-identically to a fresh build on them."""
    from repro.core import refresh_plan
    from repro.sparse.matrix import CSR

    a, b, _ = exact_problem
    a2 = CSR(n=a.n, row_ptr=a.row_ptr, col_idx=a.col_idx, val=a.val * 0.5)
    mesh = _mesh1()
    cfg = SolverConfig(block_size=16, kernel_backend="fused_streamed")
    solver = DistributedSolver(build_plan(a, 1, cfg), mesh)
    solver.solve(b)  # compile on a's values
    solver.refresh(refresh_plan(solver.plan, a2))
    fresh = DistributedSolver(build_plan(a2, 1, cfg), mesh)
    np.testing.assert_array_equal(solver.solve(b), fresh.solve(b))


# ---------------------------------------------------------------------------
# frontier-bucketed syncfree vs the dense scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comm", ["zerocopy", "unified"])
def test_frontier_syncfree_agrees_with_dense(exact_problem, comm):
    a, b, x_ref = exact_problem
    mesh = _mesh1()
    dense = SolverConfig(block_size=16, sched="syncfree", comm=comm)
    front = SolverConfig(block_size=16, sched="syncfree", comm=comm,
                         kernel_backend="fused")
    ctx = SpTRSVContext(mesh=mesh, options=dense)
    xd = ctx.solve(ctx.analyse(a), b)
    ctx = SpTRSVContext(mesh=mesh, options=front)
    xf = ctx.solve(ctx.analyse(a), b)
    np.testing.assert_array_equal(xd, xf)
    np.testing.assert_allclose(xf, x_ref, rtol=0, atol=0)


def test_frontier_syncfree_multirhs(exact_problem):
    a, b, _ = exact_problem
    rng = np.random.default_rng(4)
    B = np.column_stack([b, rng.integers(-3, 4, (a.n, 2))]).astype(np.float32)
    mesh = _mesh1()
    dense = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=16, sched="syncfree")), mesh)
    front = DistributedSolver(build_plan(
        a, 1, SolverConfig(block_size=16, sched="syncfree",
                           kernel_backend="fused")), mesh)
    np.testing.assert_array_equal(dense.solve(B), front.solve(B))


def test_frontier_work_scales_with_bucket_width(monkeypatch):
    """Acceptance: syncfree per-superstep work scales with the frontier
    bucket, not the device's total local rows. Recorded at trace time: the
    dense executor's TRSV batches span all MLR local rows, the frontier
    executor's largest branch stops at the ladder cap derived from the widest
    block level — far below MLR on a chain-skewed matrix."""
    a = suite.random_levelled(600, 30, 3.0, seed=8)
    b = np.random.default_rng(5).uniform(-1, 1, a.n)
    recorded = []
    orig = ops.batched_block_trsv

    def spy(diag, rhs, **kw):
        recorded.append(int(diag.shape[0]))
        return orig(diag, rhs, **kw)

    monkeypatch.setattr(ops, "batched_block_trsv", spy)
    mesh = _mesh1()

    cfg_f = SolverConfig(block_size=8, sched="syncfree", kernel_backend="fused")
    plan = build_plan(a, 1, cfg_f)
    MLR = plan.local_rows.shape[1]
    cap = plan.frontier_caps[0]
    assert cap < MLR / 4, (cap, MLR)  # premise: skewed levels << local rows

    ctx = SpTRSVContext(mesh=mesh, options=cfg_f)
    ctx.solve(ctx.analyse(a), b)
    frontier_widths = set(recorded)
    recorded.clear()
    ctx = SpTRSVContext(mesh=mesh, options=SolverConfig(block_size=8, sched="syncfree"))
    ctx.solve(ctx.analyse(a), b)
    dense_widths = set(recorded)

    ladder = set(_frontier_ladder(min(cap, MLR)))
    assert frontier_widths == ladder
    assert max(frontier_widths) <= max(ladder) < MLR
    assert MLR in dense_widths  # the dense scan really pays all local rows


# ---------------------------------------------------------------------------
# plan-level structure: segments, dispatch counts, ladders
# ---------------------------------------------------------------------------


def test_fused_segments_partition_levels(exact_problem):
    a, _, _ = exact_problem
    for comm, D in (("zerocopy", 1), ("zerocopy", 4), ("unified", 4)):
        plan = build_plan(a, D, SolverConfig(block_size=16, comm=comm))
        segs = fused_segments(plan)
        # segments tile [0, T) exactly, in order
        assert segs[0, 0] == 0 and segs[-1, 1] == plan.n_levels
        np.testing.assert_array_equal(segs[1:, 0], segs[:-1, 1])
        if comm == "unified" and D > 1:
            assert len(segs) == plan.n_levels  # dense psum every superstep
        if D == 1:
            assert len(segs) == 1  # whole solve in one launch
        wid = level_widths(plan)
        if comm == "zerocopy" and D > 1 and plan.n_boundary_rows > 0:
            # every segment break sits exactly before an exchange level
            for lo in segs[1:, 0]:
                assert wid[lo, 2] > 0


def test_dispatch_stats_fused_wins(exact_problem):
    a, _, _ = exact_problem
    for D in (1, 4):
        plan = build_plan(a, D, SolverConfig(block_size=16))
        ds = dispatch_stats(plan)
        assert ds["fused_launches"] == len(fused_segments(plan))
        assert ds["fused_launches"] < ds["switch_dispatches"]


def test_frontier_ladder_properties():
    for cap in (1, 2, 5, 37, 1000, 123456):
        lad = _frontier_ladder(cap)
        assert lad[0] >= 1 and lad[-1] == cap
        assert list(lad) == sorted(set(lad))
        assert len(lad) <= 12
    assert _frontier_ladder(8) == (1, 2, 4, 8)


def test_fused_backend_env_selection(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "fused")
    assert ops.executor_backend(None) == "fused"
    assert ops.op_backend(None) in ("reference", "pallas")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "bogus")
    with pytest.raises(ValueError):
        ops.executor_backend(None)


def test_fused_unified_multidevice_plan_builds():
    """Unified fused executor compiles per-level segments with the split-delta
    carry; structure-only check here (execution is covered on 8 devices in
    test_multidevice)."""
    a = EXACT_MATRICES["skewed"]()
    plan = build_plan(a, 4, SolverConfig(block_size=16, comm="unified",
                                         kernel_backend="fused"))
    segs = fused_segments(plan)
    assert len(segs) == plan.n_levels
    assert dispatch_stats(plan)["exchanges"] == plan.n_levels
