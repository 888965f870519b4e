"""Numeric IC(0)/ILU(0) factorization + transpose-solve correctness."""
import numpy as np
import pytest
import scipy.sparse.linalg as spla

from repro import compat
from repro.api import SpTRSVContext
from repro.core import DistributedSolver, SolverConfig, build_plan
from repro.krylov.precond import (
    ic0,
    ilu0,
    spd_lower_from_triangular,
    symmetric_full_csr,
    upper_as_reversed_lower,
)
from repro.sparse import suite
from repro.sparse.matrix import CSR, csr_transpose, reverse_transpose, to_scipy


def _mesh1():
    return compat.make_mesh((1,), ("x",))


def _spd_lower(side=14, seed=0):
    return spd_lower_from_triangular(suite.grid2d_factor(side, seed=seed))


def _dense_sym(a_lower):
    return to_scipy(symmetric_full_csr(a_lower)).toarray()


# ---------------------------------------------------------------------------
# factorizations
# ---------------------------------------------------------------------------


def test_ic0_equals_cholesky_on_full_pattern():
    """With a dense lower pattern IC(0) has nothing to drop -> exact Cholesky."""
    rng = np.random.default_rng(0)
    n = 24
    m = rng.uniform(-1, 1, (n, n))
    rows, cols = np.tril_indices(n, -1)
    tri = CSR(
        n=n,
        row_ptr=np.concatenate([[0], np.cumsum(np.arange(1, n + 1))]).astype(np.int64),
        col_idx=np.concatenate([np.arange(i + 1) for i in range(n)]).astype(np.int32),
        val=np.concatenate([np.append(m[i, :i], 1.0) for i in range(n)]),
    )
    a = spd_lower_from_triangular(tri)
    L = ic0(a)
    L_exact = np.linalg.cholesky(_dense_sym(a))
    np.testing.assert_allclose(to_scipy(L).toarray(), L_exact, rtol=1e-10, atol=1e-10)


def test_ic0_preserves_pattern_and_residual_on_pattern():
    a = _spd_lower()
    L = ic0(a)
    np.testing.assert_array_equal(L.row_ptr, a.row_ptr)
    np.testing.assert_array_equal(L.col_idx, a.col_idx)
    # defining property of IC(0): (L L^T)_ij = A_ij on the pattern of A
    Ld = to_scipy(L).toarray()
    prod = Ld @ Ld.T
    A = _dense_sym(a)
    rows = np.repeat(np.arange(a.n), np.diff(a.row_ptr))
    np.testing.assert_allclose(prod[rows, a.col_idx], A[rows, a.col_idx],
                               rtol=1e-8, atol=1e-8)


def test_ilu0_exact_lu_on_full_pattern():
    rng = np.random.default_rng(1)
    n = 20
    A = rng.uniform(-1, 1, (n, n)) + n * np.eye(n)
    rp = np.arange(0, n * n + 1, n, dtype=np.int64)
    ci = np.tile(np.arange(n, dtype=np.int32), n)
    lower, upper = ilu0(CSR(n=n, row_ptr=rp, col_idx=ci, val=A.reshape(-1).copy()))
    Ld, Ud = to_scipy(lower).toarray(), to_scipy(upper).toarray()
    np.testing.assert_allclose(Ld @ Ud, A, rtol=1e-9, atol=1e-9)
    assert np.allclose(np.diag(Ld), 1.0)


def test_ilu0_residual_vanishes_on_pattern():
    a_full = symmetric_full_csr(_spd_lower())
    lower, upper = ilu0(a_full)
    resid = to_scipy(lower).toarray() @ to_scipy(upper).toarray() - to_scipy(a_full).toarray()
    rows = np.repeat(np.arange(a_full.n), np.diff(a_full.row_ptr))
    np.testing.assert_allclose(resid[rows, a_full.col_idx], 0.0, atol=1e-8)


def test_spd_lower_is_spd():
    A = _dense_sym(_spd_lower())
    assert np.allclose(A, A.T)
    assert np.linalg.eigvalsh(A).min() > 0


def test_ilu0_zero_pivot_breakdown_regression():
    """A pattern whose elimination produces an exactly-zero pivot: the clamp
    must be written back into U, so U's diagonal stays nonzero and the
    transpose-plan U-solve stays finite (it used to divide by zero)."""
    # A = [[1,1,0],[1,1,1],[0,1,1]]: eliminating row 1 gives U[1,1] = 0, which
    # row 2 then uses as its pivot.
    A = np.array([[1.0, 1.0, 0.0],
                  [1.0, 1.0, 1.0],
                  [0.0, 1.0, 1.0]])
    nz = A != 0
    rp = np.concatenate([[0], np.cumsum(nz.sum(1))]).astype(np.int64)
    ci = np.concatenate([np.nonzero(nz[i])[0] for i in range(3)]).astype(np.int32)
    a = CSR(n=3, row_ptr=rp, col_idx=ci, val=A[nz].astype(np.float64))
    lower, upper = ilu0(a)
    u_diag = upper.val[upper.row_ptr[:-1]]  # upper CSR: diagonal entry first
    assert np.all(u_diag != 0.0), "clamped pivot must be written back"
    assert np.all(np.isfinite(lower.val)) and np.all(np.isfinite(upper.val))
    # the real downstream consumer: U x = y through the transpose-plan solver
    y = np.array([1.0, 2.0, 3.0])
    plan = build_plan(upper_as_reversed_lower(upper), 1,
                      SolverConfig(block_size=4), transpose=True)
    x = DistributedSolver(plan, _mesh1()).solve(y)
    assert np.all(np.isfinite(x))


def test_ilu0_trailing_zero_pivot_clamped():
    """A zero pivot on the LAST row is never used by a later elimination — it
    must still be clamped so U's diagonal is nonzero."""
    A = np.array([[1.0, 1.0],
                  [1.0, 1.0]])
    rp = np.array([0, 2, 4], np.int64)
    ci = np.array([0, 1, 0, 1], np.int32)
    _, upper = ilu0(CSR(n=2, row_ptr=rp, col_idx=ci, val=A.reshape(-1).copy()))
    assert np.all(upper.val[upper.row_ptr[:-1]] != 0.0)


# ---------------------------------------------------------------------------
# transpose / upper-triangular solves through the distributed solver
# ---------------------------------------------------------------------------


def test_reverse_transpose_roundtrip():
    a = suite.random_levelled(200, 16, 3.0, seed=7)
    rt = reverse_transpose(a)
    assert np.all(rt.col_idx <= np.repeat(np.arange(a.n), np.diff(rt.row_ptr)))
    np.testing.assert_allclose(
        to_scipy(reverse_transpose(rt)).toarray(), to_scipy(a).toarray()
    )


@pytest.mark.parametrize("sched", ["levelset", "syncfree"])
def test_transpose_solve_matches_scipy(sched):
    a = suite.grid2d_factor(16, seed=2)
    b = np.random.default_rng(3).uniform(-1, 1, a.n)
    cfg = SolverConfig(block_size=16, sched=sched)
    ctx = SpTRSVContext(mesh=_mesh1(), options=cfg)
    x = ctx.solve(ctx.analyse(a), b, transpose=True)
    x_ref = spla.spsolve_triangular(to_scipy(a).T.tocsr(), b, lower=False)
    np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4)


def test_upper_solve_via_transpose_plan():
    """U x = y for the ILU(0) upper factor, executed as a transposed plan."""
    a_full = symmetric_full_csr(_spd_lower(side=10, seed=4))
    _, upper = ilu0(a_full)
    y = np.random.default_rng(5).uniform(-1, 1, a_full.n)
    plan = build_plan(upper_as_reversed_lower(upper), 1,
                      SolverConfig(block_size=8), transpose=True)
    solver = DistributedSolver(plan, _mesh1())
    x = solver.solve(y)
    x_ref = spla.spsolve_triangular(to_scipy(upper).tocsr(), y, lower=False)
    np.testing.assert_allclose(x, x_ref, rtol=2e-4, atol=2e-4)


def test_csr_transpose_matches_scipy():
    a = suite.random_levelled(150, 12, 3.0, seed=8)
    np.testing.assert_allclose(
        to_scipy(csr_transpose(a)).toarray(), to_scipy(a).toarray().T
    )
