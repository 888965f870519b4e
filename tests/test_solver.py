"""SpTRSV end-to-end vs scipy oracle — all scheduling/comm/partition modes.

Matrix generators live in ``tests/strategies.py`` (shared with the superstep,
malleable, and krylov suites).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from strategies import SOLVER_MATRICES as MATRICES, mesh1 as _mesh1
from repro.api import SpTRSVContext
from repro.core import DistributedSolver, SolverConfig, build_plan, solve_local
from repro.core.blocking import pad_rhs, unpad_x
from repro.obs.metrics import MetricsRegistry
from repro.sparse import suite
from repro.sparse.matrix import reference_solve


@pytest.fixture(scope="module", params=list(MATRICES))
def problem(request):
    a = MATRICES[request.param]()
    b = np.random.default_rng(0).uniform(-1, 1, a.n)
    return a, b, reference_solve(a, b)


@pytest.mark.parametrize("comm", ["zerocopy", "unified"])
@pytest.mark.parametrize("sched", ["levelset", "syncfree"])
def test_all_modes_match_reference(problem, comm, sched):
    a, b, x_ref = problem
    cfg = SolverConfig(block_size=16, comm=comm, sched=sched)
    ctx = SpTRSVContext(mesh=_mesh1(), options=cfg)
    x = ctx.solve(ctx.analyse(a), b)
    np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4)


def test_solve_local_matches_reference(problem):
    a, b, x_ref = problem
    plan = build_plan(a, 1, SolverConfig(block_size=8))
    xb = solve_local(plan, jnp.asarray(pad_rhs(b, plan.bs)))
    np.testing.assert_allclose(unpad_x(np.asarray(xb), plan.bs), x_ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("block_size", [4, 16, 64])
def test_block_size_invariance(block_size):
    a = MATRICES["levelled"]()
    b = np.random.default_rng(1).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(mesh=_mesh1(), options=SolverConfig(block_size=block_size))
    x = ctx.solve(ctx.analyse(a), b)
    np.testing.assert_allclose(x, reference_solve(a, b), rtol=2e-4, atol=2e-4)


def test_solver_reuse_multiple_rhs():
    """Paper runs the solver 100x per matrix: plan/compile once, solve many."""
    a = MATRICES["grid"]()
    plan = build_plan(a, 1, SolverConfig(block_size=16))
    solver = DistributedSolver(plan, _mesh1())
    rng = np.random.default_rng(2)
    for _ in range(3):
        b = rng.uniform(-1, 1, a.n)
        np.testing.assert_allclose(solver.solve(b), reference_solve(a, b),
                                   rtol=2e-4, atol=2e-4)


def test_pallas_backend_end_to_end():
    """Whole solve with the Pallas kernels (interpret mode) instead of XLA ref."""
    a = suite.random_levelled(120, 10, 3.0, seed=5)
    b = np.random.default_rng(3).uniform(-1, 1, a.n)
    cfg = SolverConfig(block_size=16, kernel_backend="pallas")
    ctx = SpTRSVContext(mesh=_mesh1(), options=cfg)
    x = ctx.solve(ctx.analyse(a), b)
    np.testing.assert_allclose(x, reference_solve(a, b), rtol=2e-4, atol=2e-4)


def test_comm_bytes_accounting():
    a = MATRICES["levelled"]()
    zc = build_plan(a, 4, SolverConfig(block_size=16, comm="zerocopy"))
    un = build_plan(a, 4, SolverConfig(block_size=16, comm="unified"))
    assert zc.comm_bytes_per_solve < un.comm_bytes_per_solve


@pytest.mark.parametrize("comm", ["zerocopy", "unified"])
@pytest.mark.parametrize("sched", ["levelset", "syncfree"])
def test_comm_bytes_zero_on_single_device(comm, sched):
    """Single-device plans execute no collectives: the model must say 0 bytes
    (it used to count the sentinel pad slots of the exchange schedules)."""
    a = MATRICES["levelled"]()
    plan = build_plan(a, 1, SolverConfig(block_size=16, comm=comm, sched=sched))
    assert plan.comm_bytes_per_solve == 0


@pytest.mark.parametrize("sched", ["levelset", "syncfree"])
def test_comm_bytes_zero_when_no_boundary(sched):
    """A partition with an empty cut exchanges nothing under zerocopy, even on
    a multi-device plan — and the solver still matches the oracle."""
    a = suite.block_diagonal_parallel(512, 8, 3.0, seed=2)
    cfg = SolverConfig(block_size=16, comm="zerocopy", sched=sched,
                       partition="contiguous")
    plan = build_plan(a, 8, cfg)
    assert plan.n_boundary_rows == 0
    assert plan.comm_bytes_per_solve == 0


def test_comm_bytes_is_executed_exchange_payload():
    """Levelset/zerocopy volume = what the bucketed executor actually psums:
    at least one slot per real boundary row, but strictly below the old dense
    (T, max-width) sentinel-slot accounting."""
    a = MATRICES["levelled"]()
    plan = build_plan(a, 4, SolverConfig(block_size=16, comm="zerocopy"))
    assert plan.n_boundary_rows > 0
    widths = np.array(plan.buckets)[plan.lvl_bucket]
    assert plan.comm_bytes_per_solve == widths[:, 2].sum() * plan.bs.B * 4
    assert plan.comm_bytes_per_solve >= plan.n_boundary_rows * plan.bs.B * 4
    per_level = np.bincount(plan.bs.block_level[plan.part.boundary],
                            minlength=plan.n_levels)
    old_model = plan.n_levels * per_level.max() * plan.bs.B * 4
    assert plan.comm_bytes_per_solve < old_model


def test_compacted_schedules_beat_pad_to_max():
    """The ragged layout's total padded footprint must undercut the old dense
    (T, max-width) layout on a skewed level-size distribution."""
    a = suite.random_levelled(600, 40, 4.0, seed=6)
    plan = build_plan(a, 4, SolverConfig(block_size=16))
    T = plan.n_levels
    assert 1 <= len(plan.buckets) <= 12
    widths = np.array(plan.buckets)[plan.lvl_bucket]  # (T, 3) per-level widths
    for k, flat in ((0, plan.solve_rows), (1, plan.upd_tiles)):
        dense = T * widths[:, k].max()
        assert flat.shape[1] == max(1, widths[:, k].sum()) < dense
    # offsets partition the flats exactly
    np.testing.assert_array_equal(plan.lvl_off[:, 0],
                                  np.concatenate([[0], np.cumsum(widths[:-1, 0])]))
    # every real (non-pad) schedule entry survives compaction
    owned = [np.sort(plan.solve_rows[d][plan.solve_rows[d] >= 0]) for d in range(4)]
    np.testing.assert_array_equal(np.sort(np.concatenate(owned)), np.arange(plan.bs.nb))


def test_comm_bytes_syncfree_counts_counter_traffic():
    """Syncfree/unified psums in-degree counters on top of the accumulators —
    its predicted volume must exceed levelset/unified on the same matrix."""
    a = MATRICES["levelled"]()
    lv = build_plan(a, 4, SolverConfig(block_size=16, comm="unified", sched="levelset"))
    sf = build_plan(a, 4, SolverConfig(block_size=16, comm="unified", sched="syncfree"))
    assert sf.comm_bytes_per_solve > lv.comm_bytes_per_solve
    assert lv.n_supersteps == lv.n_levels


@pytest.mark.parametrize("sched,R", [
    pytest.param("levelset", 3, id="levelset"),
    pytest.param("syncfree", 3, id="syncfree"),
    pytest.param("levelset", 8, id="levelset-R8"),
    pytest.param("dagpart", 3, id="dagpart"),
    pytest.param("dagpart", 8, id="dagpart-R8"),
])
def test_multirhs_panel_matches_columnwise(problem, sched, R):
    """(n, R) panel through one compiled solve == R independent solves. Every
    executor carries the panel as (nb+1, R, B) row panels and counts each
    such call in ``executor.row_panel_solves``; a single vector counts
    none."""
    a, b, x_ref = problem
    rng = np.random.default_rng(7)
    B = np.column_stack([b, rng.uniform(-1, 1, (a.n, R - 1))])
    cfg = SolverConfig(block_size=16, sched=sched)
    reg = MetricsRegistry()
    solver = DistributedSolver(build_plan(a, 1, cfg), _mesh1(), registry=reg)
    np.testing.assert_allclose(solver.solve(b), x_ref, rtol=2e-4, atol=2e-4)
    assert reg.counter("executor.row_panel_solves").value == 0
    X = solver.solve(B)
    assert solver.n_solves == 2
    assert reg.counter("executor.row_panel_solves").value == 1
    np.testing.assert_allclose(X[:, 0], x_ref, rtol=2e-4, atol=2e-4)
    for j in range(1, R):
        np.testing.assert_allclose(X[:, j], reference_solve(a, B[:, j]),
                                   rtol=2e-4, atol=2e-4)
    if sched == "levelset":  # the single-device executor, same row panels
        xb = solve_local(solver.plan, jnp.asarray(pad_rhs(B, solver.plan.bs)))
        np.testing.assert_allclose(unpad_x(np.asarray(xb), solver.plan.bs), X,
                                   rtol=2e-4, atol=2e-4)


def test_transpose_solve_all_matrices(problem):
    a, b, _ = problem
    import scipy.sparse.linalg as spla

    from repro.sparse.matrix import to_scipy

    ctx = SpTRSVContext(mesh=_mesh1(), options=SolverConfig(block_size=16))
    x = ctx.solve(ctx.analyse(a), b, transpose=True)
    x_ref = spla.spsolve_triangular(to_scipy(a).T.tocsr(), b, lower=False)
    np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4)


def test_pallas_backend_multirhs_end_to_end():
    """Whole multi-RHS solve with the Pallas trsm/gemm kernels (interpret)."""
    a = suite.random_levelled(120, 10, 3.0, seed=5)
    rng = np.random.default_rng(4)
    B = rng.uniform(-1, 1, (a.n, 3))
    cfg = SolverConfig(block_size=16, kernel_backend="pallas")
    solver = DistributedSolver(build_plan(a, 1, cfg), _mesh1())
    X = solver.solve(B)
    for j in range(3):
        np.testing.assert_allclose(X[:, j], reference_solve(a, B[:, j]),
                                   rtol=2e-4, atol=2e-4)
