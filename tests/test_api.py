"""Session API: analyse/factorize/solve lifecycle, typed options, auto mode."""
import gc
import weakref

import numpy as np
import pytest

from repro.api import (
    Comm,
    KernelBackend,
    PlanOptions,
    Sched,
    SpTRSVContext,
    as_options,
    pattern_key,
)
from repro import compat
from repro.api import autotune
from repro.api.autotune import candidate_grid, estimate_plan_cost
from repro.core import DistributedSolver, SolverConfig, build_plan, refresh_plan
from repro.krylov import matvec_lower, solve_ic0_pcg, spd_lower_from_triangular
from repro.sparse import suite
from repro.sparse.matrix import CSR, reference_solve

MODES = [("zerocopy", "levelset"), ("zerocopy", "syncfree"),
         ("unified", "levelset"), ("unified", "syncfree")]


def _matrix(seed=0, n=400, levels=16):
    return suite.random_levelled(n, levels, 4.0, seed=seed)


def _revalued(a: CSR, scale=None) -> CSR:
    """Same pattern, different values (diagonal stays nonzero)."""
    if scale is None:
        scale = 1.0 + 0.25 * np.sin(np.arange(a.nnz))
    return CSR(n=a.n, row_ptr=a.row_ptr, col_idx=a.col_idx, val=a.val * scale)


# ---------------------------------------------------------------------------
# eager option validation (satellite: fail at the boundary, name the choices)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("field,value,expect", [
    ("comm", "bogus", "zerocopy"),
    ("sched", "wavefront", "levelset"),
    ("partition", "metis", "taskpool"),
    ("kernel", "cuda", "fused"),
])
def test_plan_options_invalid_choice_raises_eagerly(field, value, expect):
    with pytest.raises(ValueError, match=expect):
        PlanOptions(**{field: value})


@pytest.mark.parametrize("field,value,expect", [
    ("comm", "bogus", "zerocopy"),
    ("sched", "wavefront", "levelset"),
    ("partition", "metis", "taskpool"),
    ("kernel_backend", "cuda", "fused"),
])
def test_solver_config_invalid_choice_raises_eagerly(field, value, expect):
    with pytest.raises(ValueError, match=expect):
        SolverConfig(**{field: value})


def test_partition_cannot_be_auto():
    with pytest.raises(ValueError, match="partition"):
        PlanOptions(partition="auto")


def test_numeric_bounds_validated():
    with pytest.raises(ValueError, match="block_size"):
        PlanOptions(block_size=0)
    with pytest.raises(ValueError, match="rhs_hint"):
        SolverConfig(rhs_hint=0)


def test_options_config_round_trip():
    cfg = SolverConfig(block_size=16, comm="unified", sched="syncfree",
                       partition="malleable", kernel_backend="fused",
                       tasks_per_device=4, rhs_hint=8)
    opts = as_options(cfg)
    assert opts.comm == Comm.UNIFIED and opts.sched == Sched.SYNCFREE
    assert opts.kernel == KernelBackend.FUSED
    assert opts.to_config() == cfg
    # default kernel maps to None (platform default) and back
    assert PlanOptions().to_config().kernel_backend is None
    assert as_options(PlanOptions().to_config()).kernel == KernelBackend.DEFAULT


def test_auto_options_cannot_plan_unresolved():
    with pytest.raises(ValueError, match="auto"):
        PlanOptions.auto().to_config()


# ---------------------------------------------------------------------------
# context lifecycle
# ---------------------------------------------------------------------------


def test_analyse_once_solve_many():
    a = _matrix()
    b = np.random.default_rng(1).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    h = ctx.analyse(a)
    x = ctx.solve(h, b)
    np.testing.assert_allclose(x, reference_solve(a, b), rtol=0, atol=1e-5)
    for _ in range(3):
        ctx.solve(h, b)
    assert ctx.analyse(a) is h  # re-analyse is a cache hit
    st = ctx.stats()
    assert st["analyses"] == 1
    assert st["solves"] == 4
    assert st["solve_cache_hits"] == 3
    assert st["analysis_hits"] == 1
    assert 0 < st["cache_hit_rate"] < 1


def test_transpose_shares_analysis():
    a = _matrix()
    b = np.random.default_rng(2).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    h = ctx.analyse(a)
    xt = ctx.solve(h, b, transpose=True)
    import scipy.sparse.linalg as spla

    from repro.sparse.matrix import to_scipy

    expect = spla.spsolve_triangular(to_scipy(a).T.tocsr(), b, lower=False)
    np.testing.assert_allclose(xt, expect, rtol=0, atol=1e-4)
    assert ctx.stats()["analyses"] == 1  # L^T is an extension, not a re-analysis
    assert ctx.stats()["transpose_extensions"] == 1


def test_solve_accepts_matrix_directly():
    a = _matrix()
    b = np.random.default_rng(3).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    x = ctx.solve(a, b)
    np.testing.assert_allclose(x, reference_solve(a, b), rtol=0, atol=1e-5)


def test_multi_rhs_shape_cache_counts():
    a = _matrix()
    rng = np.random.default_rng(4)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    h = ctx.analyse(a)
    ctx.solve(h, rng.uniform(-1, 1, a.n))
    ctx.solve(h, rng.uniform(-1, 1, (a.n, 4)))  # new shape: miss
    ctx.solve(h, rng.uniform(-1, 1, (a.n, 4)))  # same shape: hit
    st = ctx.stats()
    assert st["solve_cache_misses"] == 2 and st["solve_cache_hits"] == 1


def test_tagged_handles_do_not_alias_values():
    a = _matrix()
    a2 = _revalued(a)
    b = np.random.default_rng(5).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    h1 = ctx.analyse(a)
    h2 = ctx.factorize(a2, tag="factor")
    assert h1 is not h2
    assert h1.symbolic is h2.symbolic  # ONE analysis for the pattern
    assert ctx.stats()["analyses"] == 1
    np.testing.assert_allclose(ctx.solve(h1, b), reference_solve(a, b),
                               rtol=0, atol=1e-5)
    np.testing.assert_allclose(ctx.solve(h2, b), reference_solve(a2, b),
                               rtol=0, atol=1e-5)


def test_analyse_refreshes_stale_values_on_pattern_hit():
    a = _matrix()
    b = np.random.default_rng(6).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    ctx.solve(ctx.analyse(a), b)
    a2 = _revalued(a)
    x = ctx.solve(ctx.analyse(a2), b)  # same pattern, new values
    np.testing.assert_allclose(x, reference_solve(a2, b), rtol=0, atol=1e-5)
    assert ctx.stats()["analyses"] == 1


# ---------------------------------------------------------------------------
# numeric refresh (satellite: bit-identical to a fresh build across modes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comm,sched", MODES)
def test_refresh_bit_identical_to_fresh_build(comm, sched):
    a = _matrix()
    a2 = _revalued(a)
    cfg = SolverConfig(block_size=16, comm=comm, sched=sched)
    refreshed = refresh_plan(build_plan(a, 1, cfg), a2)
    fresh = build_plan(a2, 1, cfg)
    assert np.array_equal(refreshed.diag, fresh.diag)
    assert np.array_equal(refreshed.tiles, fresh.tiles)
    assert np.array_equal(refreshed.solve_rows, fresh.solve_rows)
    # and the solve through the refreshed executor is bit-identical too
    b = np.random.default_rng(7).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=cfg)
    h = ctx.analyse(a)
    ctx.solve(h, b)  # compile on a's values
    ctx.factorize(a2, h)
    assert np.array_equal(ctx.solve(h, b),
                          DistributedSolver(fresh, ctx.mesh).solve(b))


def test_refresh_transpose_plan():
    a = _matrix()
    a2 = _revalued(a)
    cfg = SolverConfig(block_size=16)
    ctx = SpTRSVContext(options=cfg)
    h = ctx.analyse(a)
    ctx.solve(h, np.ones(a.n), transpose=True)  # build + compile transpose
    ctx.factorize(a2, h)
    fresh_t = build_plan(a2, 1, cfg, transpose=True)
    assert np.array_equal(h.tplan.diag, fresh_t.diag)
    assert np.array_equal(h.tplan.tiles, fresh_t.tiles)


def test_factorize_rejects_different_pattern():
    a = _matrix(seed=0)
    other = _matrix(seed=3)
    assert pattern_key(a) != pattern_key(other)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    h = ctx.analyse(a)
    with pytest.raises(ValueError, match="pattern"):
        ctx.factorize(other, h)


def test_refresh_plan_rejects_different_pattern():
    a = _matrix(seed=0)
    plan = build_plan(a, 1, SolverConfig(block_size=16))
    with pytest.raises(ValueError, match="pattern"):
        refresh_plan(plan, _matrix(seed=3))


# ---------------------------------------------------------------------------
# auto mode
# ---------------------------------------------------------------------------


def test_candidate_grid_dimensions():
    # sched axis: levelset + dagpart + syncfree;
    # kernel axis: platform default + fused + fused_streamed
    assert len(candidate_grid(PlanOptions.auto(probe_solves=0), 4)) == 3 * 2 * 3
    assert len(candidate_grid(PlanOptions.auto(probe_solves=0), 1)) == 3 * 1 * 3
    only_kernel = PlanOptions(kernel="auto")
    assert len(candidate_grid(only_kernel, 4)) == 3
    fixed = PlanOptions()
    assert candidate_grid(fixed, 4) == [("levelset", "zerocopy", "default")]


def test_auto_dedups_byte_identical_candidates(monkeypatch):
    """tune() never scores/probes the same compiled program twice: syncfree
    fused_streamed == fused by definition, so only the fused combo survives
    (and on plans past the VMEM limit the levelset pair collapses too)."""
    from repro.api.autotune import tune

    a = _matrix()
    opts = PlanOptions(block_size=16, sched="syncfree", kernel="auto",
                       probe_solves=0)
    _, _, decision, _ = tune(a, opts, compat.make_mesh((1,), ("x",)))
    kernels = {k for (_, _, k) in decision.scores}
    assert "fused_streamed" not in kernels
    assert "fused" in kernels
    # levelset keeps both variants while the resident store fits VMEM...
    opts_lv = PlanOptions(block_size=16, sched="levelset", kernel="auto",
                          probe_solves=0)
    _, _, dec_lv, _ = tune(a, opts_lv, compat.make_mesh((1,), ("x",)))
    assert {"fused", "fused_streamed"} <= {k for (_, _, k) in dec_lv.scores}
    # ...and collapses them once plain fused would auto-stream anyway
    monkeypatch.setenv("REPRO_STREAM_VMEM_LIMIT", "1")
    _, _, dec_small, _ = tune(a, opts_lv, compat.make_mesh((1,), ("x",)))
    assert "fused_streamed" not in {k for (_, _, k) in dec_small.scores}


def test_auto_modelled_selection_records_decision():
    a = _matrix()
    b = np.random.default_rng(8).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions.auto(block_size=16, probe_solves=0))
    h = ctx.analyse(a)
    assert h.auto is not None and h.auto.mode == "modelled"
    sched, comm, kernel = h.auto.chosen
    assert sched in ("levelset", "syncfree") and comm == "zerocopy"
    assert h.auto.scores[h.auto.chosen] == min(h.auto.scores.values())
    assert h.config.sched == sched and h.config.comm == comm
    np.testing.assert_allclose(ctx.solve(h, b), reference_solve(a, b),
                               rtol=0, atol=1e-5)
    ds = ctx.dispatch_stats(h)
    assert ds["auto"]["chosen"] == h.auto.chosen
    assert ctx.stats()["analyses"] == 1  # candidates shared one partition


def test_auto_probed_selection_picks_measured_min():
    a = _matrix(n=200, levels=8)
    opts = PlanOptions(block_size=16, kernel="auto", probe_solves=2)
    ctx = SpTRSVContext(options=opts)
    h = ctx.analyse(a)
    assert h.auto.mode == "probed"
    assert h.auto.probe_us, "probed mode must record measurements"
    assert h.auto.probe_us[h.auto.chosen] == min(h.auto.probe_us.values())
    assert h.auto.probe_overhead_us > 0
    # the probed winner's executor is reused, not recompiled
    assert False in h.solvers
    b = np.random.default_rng(9).uniform(-1, 1, a.n)
    np.testing.assert_allclose(ctx.solve(h, b), reference_solve(a, b),
                               rtol=0, atol=1e-5)


def test_auto_probe_keeps_only_the_returned_solver(monkeypatch):
    """Each probed executor holds its plan's store on the mesh: while probing,
    only the fastest so far outlives its turn, and once tune returns only the
    chosen one is still referenced."""
    made, alive_before = [], []

    def alive():
        gc.collect()
        return [r() for r in made if r() is not None]

    class Recorded(DistributedSolver):
        def __init__(self, *args, **kwargs):
            alive_before.append(len(alive()))
            super().__init__(*args, **kwargs)
            made.append(weakref.ref(self))

    monkeypatch.setattr(autotune, "DistributedSolver", Recorded)
    a = _matrix(n=200, levels=8)
    opts = PlanOptions(sched="auto", comm="zerocopy", kernel="reference",
                       block_size=16, probe_solves=1)
    mesh = compat.make_mesh((1,), ("x",))
    _, _, decision, solver = autotune.tune(a, opts, mesh)
    assert decision.mode == "probed" and len(made) == len(decision.probe_us) > 2
    assert alive_before == [0] + [1] * (len(made) - 1)
    assert alive() == [solver]
    assert solver.plan.config.sched == decision.chosen[0]


def test_estimate_plan_cost_orders_dense_vs_bucketed_syncfree():
    a = _matrix()
    dense = build_plan(a, 1, SolverConfig(block_size=16, sched="syncfree"))
    bucketed = build_plan(a, 1, SolverConfig(block_size=16, sched="syncfree",
                                             kernel_backend="fused"))
    # the frontier-bucketed executor never models worse than the dense scan
    assert estimate_plan_cost(bucketed) <= estimate_plan_cost(dense)


# ---------------------------------------------------------------------------
# krylov as a context client (acceptance: one analysis per pattern)
# ---------------------------------------------------------------------------


def test_solve_ic0_pcg_single_analysis_per_pattern():
    a = spd_lower_from_triangular(suite.grid2d_factor(20, seed=0))
    b = np.random.default_rng(10).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    res = solve_ic0_pcg(a, b, context=ctx, tol=1e-8)
    np.testing.assert_allclose(matvec_lower(a, res.x), b, rtol=0, atol=1e-5)
    st = ctx.stats()
    assert st["analyses"] == 1, st  # SpMV + L + L^T: one partition/analysis
    assert res.info["forward"].n_solves >= res.n_iters > 0
    # a second solve on the same pattern re-analyses nothing
    res2 = solve_ic0_pcg(a, b, context=ctx, tol=1e-8)
    np.testing.assert_allclose(matvec_lower(a, res2.x), b, rtol=0, atol=1e-5)
    assert ctx.stats()["analyses"] == 1
    assert np.array_equal(res.x, res2.x)


def test_ilu0_refresh_rejects_pattern_change():
    from repro.krylov import ILU0Preconditioner, symmetric_full_csr

    a = spd_lower_from_triangular(suite.grid2d_factor(12, seed=2))
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    pre = ILU0Preconditioner(ctx, symmetric_full_csr(a))
    other = spd_lower_from_triangular(suite.grid2d_factor(13, seed=2))
    with pytest.raises(ValueError, match="pattern"):
        pre.refresh(symmetric_full_csr(other))
    # same-pattern refresh stays silent and re-analyses nothing
    n_before = ctx.stats()["analyses"]
    pre.refresh(symmetric_full_csr(_revalued(a, scale=1.3)))
    assert ctx.stats()["analyses"] == n_before


def test_preconditioner_refresh_no_reanalysis():
    a = spd_lower_from_triangular(suite.grid2d_factor(16, seed=1))
    b = np.random.default_rng(11).uniform(-1, 1, a.n)
    ctx = SpTRSVContext(options=PlanOptions(block_size=16))
    res = solve_ic0_pcg(a, b, context=ctx, tol=1e-8)
    pre = res.info["preconditioner"]
    a2 = _revalued(a, scale=1.2)
    pre.refresh(a2)
    assert ctx.stats()["analyses"] == 1
    res2 = solve_ic0_pcg(a2, b, context=ctx, tol=1e-8)
    np.testing.assert_allclose(matvec_lower(a2, res2.x), b, rtol=0, atol=1e-5)
    assert ctx.stats()["analyses"] == 1
