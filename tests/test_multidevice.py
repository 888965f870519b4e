"""Multi-device behaviour (subprocess with 8 forced host CPU devices).

The main test process keeps 1 device (dry-run contract); anything needing a
real multi-device mesh runs here via subprocess.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TESTS = os.path.dirname(os.path.abspath(__file__))


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=1200, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_solver_all_modes_on_8_devices():
    print(run_py("""
        import numpy as np, jax
        from repro.sparse import suite
        from repro.sparse.matrix import reference_solve
        from repro.api import SpTRSVContext
        from repro.core import SolverConfig
        a = suite.random_levelled(600, 24, 4.0, seed=5)
        b = np.random.default_rng(1).uniform(-1, 1, a.n)
        x_ref = reference_solve(a, b)
        from repro import compat
        mesh = compat.make_mesh((8,), ("x",))
        for comm in ["zerocopy", "unified"]:
            for sched in ["levelset", "dagpart", "syncfree"]:
                for part in ["taskpool", "contiguous", "malleable"]:
                    cfg = SolverConfig(block_size=16, comm=comm, sched=sched, partition=part)
                    ctx = SpTRSVContext(mesh=mesh, options=cfg)
                    x = ctx.solve(ctx.analyse(a), b)
                    err = np.abs(x - x_ref).max() / np.abs(x_ref).max()
                    assert err < 1e-5, (comm, sched, part, err)
        print("OK")
    """))


@pytest.mark.slow
def test_fused_backend_bit_exact_all_modes_on_8_devices():
    """Fused superstep megakernel / frontier-bucketed syncfree vs the
    lax.switch / dense executors, all sched x comm modes (including dagpart
    merged supersteps), on a real 8-device mesh. Exact-arithmetic (dyadic)
    values make the bitwise comparison meaningful — see
    tests/test_superstep.py."""
    print(run_py("""
        import numpy as np, jax
        from repro import compat
        from repro.core import DistributedSolver, SolverConfig, build_plan
        from repro.sparse import suite
        from repro.sparse.matrix import CSR, reference_solve

        a0 = suite.random_levelled(400, 8, 4.0, seed=6)
        rows = np.repeat(np.arange(a0.n), np.diff(a0.row_ptr))
        rng = np.random.default_rng(0)
        signs = rng.choice(np.array([-0.5, -0.25, 0.25, 0.5], np.float32),
                           size=a0.val.shape)
        val = np.where(a0.col_idx == rows, 1.0, signs).astype(np.float32)
        a = CSR(n=a0.n, row_ptr=a0.row_ptr, col_idx=a0.col_idx, val=val)
        b = np.random.default_rng(1).integers(-4, 5, a.n).astype(np.float32)
        x_ref = reference_solve(a, b)
        mesh = compat.make_mesh((8,), ("x",))
        for comm in ("zerocopy", "unified"):
            for sched in ("levelset", "dagpart", "syncfree"):
                ref_backend = "pallas" if sched != "syncfree" else None
                sw = DistributedSolver(build_plan(a, 8, SolverConfig(
                    block_size=16, comm=comm, sched=sched,
                    kernel_backend=ref_backend)), mesh)
                fu = DistributedSolver(build_plan(a, 8, SolverConfig(
                    block_size=16, comm=comm, sched=sched,
                    kernel_backend="fused")), mesh)
                xs, xf = sw.solve(b), fu.solve(b)
                assert np.array_equal(xs, xf), (comm, sched)
                assert np.array_equal(xf, x_ref.astype(np.float32)), (comm, sched)
        print("OK")
    """))


@pytest.mark.slow
def test_streamed_store_bit_exact_all_modes_on_8_devices():
    """Streaming HBM tile store vs the resident fused megakernel, all
    sched x comm modes (including dagpart merged supersteps), on a real
    8-device mesh — bit-identical on the dyadic exact-arithmetic structure
    (for sched="syncfree" the streamed backend is defined to behave exactly
    like "fused"; asserting equality there pins that contract too)."""
    print(run_py("""
        import numpy as np, jax
        from repro import compat
        from repro.core import DistributedSolver, SolverConfig, build_plan
        from repro.core.solver import dispatch_stats, fused_streaming
        from repro.sparse import suite
        from repro.sparse.matrix import CSR, reference_solve

        a0 = suite.random_levelled(400, 8, 4.0, seed=6)
        rows = np.repeat(np.arange(a0.n), np.diff(a0.row_ptr))
        rng = np.random.default_rng(0)
        signs = rng.choice(np.array([-0.5, -0.25, 0.25, 0.5], np.float32),
                           size=a0.val.shape)
        val = np.where(a0.col_idx == rows, 1.0, signs).astype(np.float32)
        a = CSR(n=a0.n, row_ptr=a0.row_ptr, col_idx=a0.col_idx, val=val)
        b = np.random.default_rng(1).integers(-4, 5, a.n).astype(np.float32)
        x_ref = reference_solve(a, b)
        mesh = compat.make_mesh((8,), ("x",))
        for comm in ("zerocopy", "unified"):
            for sched in ("levelset", "dagpart", "syncfree"):
                fu = DistributedSolver(build_plan(a, 8, SolverConfig(
                    block_size=16, comm=comm, sched=sched,
                    kernel_backend="fused")), mesh)
                st_plan = build_plan(a, 8, SolverConfig(
                    block_size=16, comm=comm, sched=sched,
                    kernel_backend="fused_streamed"))
                st = DistributedSolver(st_plan, mesh)
                if sched in ("levelset", "dagpart"):
                    ds = dispatch_stats(st_plan)
                    assert fused_streaming(st_plan) and ds["streamed"], (comm, sched)
                    assert ds["stream_dma_bytes"] > 0, (comm, sched)
                if sched == "dagpart":
                    assert ds["supersteps"] <= ds["supersteps_levelset"], comm
                xf, xs = fu.solve(b), st.solve(b)
                assert np.array_equal(xf, xs), (comm, sched)
                assert np.array_equal(xs, x_ref.astype(np.float32)), (comm, sched)
        print("OK")
    """))


@pytest.mark.slow
def test_numeric_refresh_bit_identical_all_modes_on_8_devices():
    """Factorizing new values through the session context must be
    bit-identical to a fresh build_plan on the same pattern — plans AND
    executed solves, across all sched x comm modes, on 8 devices."""
    print(run_py("""
        import numpy as np, jax
        from repro import compat
        from repro.api import SpTRSVContext, PlanOptions
        from repro.core import DistributedSolver, SolverConfig, build_plan
        from repro.sparse import suite
        from repro.sparse.matrix import CSR

        a = suite.random_levelled(600, 24, 4.0, seed=5)
        a2 = CSR(n=a.n, row_ptr=a.row_ptr, col_idx=a.col_idx,
                 val=a.val * (1.0 + 0.25 * np.sin(np.arange(a.nnz))))
        b = np.random.default_rng(1).uniform(-1, 1, a.n)
        mesh = compat.make_mesh((8,), ("x",))
        for comm in ("zerocopy", "unified"):
            for sched in ("levelset", "dagpart", "syncfree"):
                cfg = SolverConfig(block_size=16, comm=comm, sched=sched)
                ctx = SpTRSVContext(mesh=mesh, options=cfg)
                h = ctx.analyse(a)
                ctx.solve(h, b)  # compile on a's values
                ctx.factorize(a2, h)
                fresh = build_plan(a2, 8, cfg)
                refreshed = ctx.plan(h)
                assert np.array_equal(refreshed.diag, fresh.diag), (comm, sched)
                assert np.array_equal(refreshed.tiles, fresh.tiles), (comm, sched)
                x_ctx = ctx.solve(h, b)
                x_fresh = DistributedSolver(fresh, mesh).solve(b)
                assert np.array_equal(x_ctx, x_fresh), (comm, sched)
                assert ctx.stats()["analyses"] == 1, (comm, sched)
        print("OK")
    """))


def test_plan_arguments_resident_on_4_devices():
    """The executor places its plan arguments on the mesh once: a sharded
    argument holds its own (1, ...) slice on each device, a replicated one the
    whole array; a refresh re-places them and the answer follows the new
    values. Levelset and syncfree, zero-copy."""
    print(run_py("""
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.core import DistributedSolver, SolverConfig, build_plan
        from repro.core import refresh_plan
        from repro.core.solver import AXIS
        from repro.obs.metrics import MetricsRegistry
        from repro.sparse import suite
        from repro.sparse.matrix import CSR, reference_solve

        a = suite.random_levelled(400, 12, 4.0, seed=7)
        a2 = CSR(n=a.n, row_ptr=a.row_ptr, col_idx=a.col_idx,
                 val=a.val * (1.0 + 0.25 * np.sin(np.arange(a.nnz))))
        b = np.random.default_rng(2).uniform(-1, 1, a.n)
        mesh = compat.make_mesh((4,), (AXIS,))

        def check(solver, n_sharded):
            host = solver._plan_args(solver.plan)
            specs = [x.sharding.spec for x in solver._args]
            assert specs == [P(AXIS)] * n_sharded + [P()] * (len(host) - n_sharded)
            for x, h in zip(solver._args, host):
                assert x.committed and len(x.addressable_shards) == 4
                for s in x.addressable_shards:
                    lead = 1 if x.sharding.spec == P(AXIS) else h.shape[0]
                    assert s.data.shape == (lead,) + h.shape[1:]
                    assert np.array_equal(np.asarray(s.data), h[s.index])

        def rel_err(x, a):
            x_ref = reference_solve(a, b)
            return np.abs(x - x_ref).max() / np.abs(x_ref).max()

        for sched, n_sharded in (("levelset", 8), ("syncfree", 5)):
            reg = MetricsRegistry()
            cfg = SolverConfig(block_size=16, comm="zerocopy", sched=sched)
            solver = DistributedSolver(build_plan(a, 4, cfg), mesh,
                                       registry=reg)
            check(solver, n_sharded)
            assert rel_err(solver.solve(b), a) < 1e-5, sched
            old = solver._args
            solver.refresh(refresh_plan(solver.plan, a2))
            assert all(x is not y for x, y in zip(solver._args, old))
            check(solver, n_sharded)
            assert reg.counter("executor.plan_uploads").value == 2
            assert rel_err(solver.solve(b), a2) < 1e-5, sched
            assert rel_err(solver.solve(b), a) > 1e-3, sched
        print("OK")
    """, devices=4))


@pytest.mark.parametrize("devices", [4, 8])
def test_diagonal_store_per_device(devices):
    """The levelset executor gives each device the diagonal tiles of the
    block rows it owns, in order, and identity tiles past them (one pad on
    the fullest device). Forward, transpose and R=8 solves are bitwise those
    of the same program fed the whole diagonal on every device (the
    replicated layout), on dyadic values; a refresh re-places the store."""
    print(run_py(f"""
        import sys
        sys.path.insert(0, {TESTS!r})
        import numpy as np
        import strategies as st
        from repro import compat
        from repro.core import DistributedSolver, SolverConfig, build_plan
        from repro.core import refresh_plan
        from repro.core.blocking import pad_rhs
        from repro.core.solver import AXIS, device_diag
        from repro.obs.metrics import MetricsRegistry
        from repro.sparse import suite

        D = {devices}
        a = st.dyadic(suite.random_levelled(400, 6, 4.0, seed=7))
        a2 = st.dyadic(a, seed=9)
        mesh = compat.make_mesh((D,), (AXIS,))
        cfg = SolverConfig(block_size=16, comm="zerocopy", partition="malleable")

        def check_store(solver):
            plan = solver.plan
            nb, B = plan.bs.nb, plan.bs.B
            owned = [np.nonzero(plan.part.owner == d)[0] for d in range(D)]
            MLR = max(r.shape[0] for r in owned)
            store = solver._args[7]
            assert store.shape == (D, MLR + 1, B, B)
            eye = np.eye(B, dtype=np.float32)
            for s in store.addressable_shards:
                d = s.index[0].start
                held = np.asarray(s.data)[0]
                assert held.shape == (MLR + 1, B, B)
                k = owned[d].shape[0]
                assert np.array_equal(held[:k], plan.diag[owned[d]])
                assert (held[k:] == eye).all()
            # each scheduled row reads its own tile from its device's store
            for d in range(D):
                rows = plan.solve_rows[d]
                ok = rows >= 0
                assert np.array_equal(plan.diag[np.where(ok, rows, nb)],
                                      device_diag(plan)[d][plan.solve_slots[d]])

        def replicated(solver, b_blocks):
            plan = solver.plan
            host = list(solver._plan_args(plan))
            host[1] = np.where(plan.solve_rows < 0, plan.bs.nb, plan.solve_rows)
            host[7] = np.stack([plan.diag] * D)
            b_pad = np.concatenate([b_blocks, np.zeros((1,) + b_blocks.shape[1:],
                                                       np.float32)])
            return np.asarray(solver._jitted(*host, b_pad))

        for transpose in (False, True):
            reg = MetricsRegistry()
            solver = DistributedSolver(build_plan(a, D, cfg, transpose=transpose),
                                       mesh, registry=reg)
            assert solver.plan.n_boundary_rows > 0
            check_store(solver)
            for R in (1, 8):
                shape = (a.n,) if R == 1 else (a.n, R)
                b = st.dyadic_rhs(a.n * R, seed=R).reshape(shape)
                bb = pad_rhs(b, solver.plan.bs)
                x = np.asarray(solver.solve_blocks(bb))
                assert np.array_equal(x, replicated(solver, bb)), (transpose, R)
            old = solver._args[7]
            b = st.dyadic_rhs(a.n, seed=3)
            x_old = solver.solve(b)
            solver.refresh(refresh_plan(solver.plan, a2))
            assert solver._args[7] is not old
            check_store(solver)
            assert not np.array_equal(np.asarray(old), np.asarray(solver._args[7]))
            bb = pad_rhs(b, solver.plan.bs)
            assert np.array_equal(np.asarray(solver.solve_blocks(bb)),
                                  replicated(solver, bb))
            assert not np.array_equal(solver.solve(b), x_old)
        print("OK")
    """, devices=devices))


def test_exchange_counters_follow_the_plan():
    """``executor.exchanges``/``executor.exchange_bytes`` add the plan's
    exchanges (``dispatch_stats``) and payload (``comm_bytes_per_solve``, times
    R for a panel) per call, forward and transpose; a syncfree plan exchanges
    once a superstep. On one device both stay 0."""
    print(run_py("""
        import numpy as np
        import jax
        from repro import compat
        from repro.core import DistributedSolver, SolverConfig, build_plan
        from repro.core import dispatch_stats
        from repro.core.solver import AXIS
        from repro.obs.metrics import MetricsRegistry
        from repro.sparse import suite

        a = suite.random_levelled(400, 12, 4.0, seed=7)
        b = np.random.default_rng(2).uniform(-1, 1, a.n)
        B = np.random.default_rng(3).uniform(-1, 1, (a.n, 8))
        for D in (4, 1):
            mesh = compat.make_mesh((D,), (AXIS,), devices=jax.devices()[:D])
            for sched, transpose in (("levelset", False), ("levelset", True),
                                     ("syncfree", False)):
                cfg = SolverConfig(block_size=16, comm="zerocopy",
                                   partition="malleable", sched=sched)
                reg = MetricsRegistry()
                plan = build_plan(a, D, cfg, transpose=transpose)
                solver = DistributedSolver(plan, mesh, registry=reg)
                n_ex = (dispatch_stats(plan)["exchanges"] if sched == "levelset"
                        else plan.n_supersteps * (D > 1))
                ex_bytes = plan.comm_bytes_per_solve
                assert (n_ex > 0 and ex_bytes > 0) == (D > 1), (D, n_ex, ex_bytes)
                for _ in range(3):
                    solver.solve(b)
                solver.solve(B)
                assert reg.counter("executor.solves").value == 4
                assert reg.counter("executor.exchanges").value == 4 * n_ex
                assert reg.counter("executor.exchange_bytes").value == (
                    3 * ex_bytes + 8 * ex_bytes)
        print("OK")
    """, devices=4))


@pytest.mark.parametrize("comm,sched", [("zerocopy", "levelset"),
                                        ("unified", "levelset"),
                                        ("unified", "dagpart"),
                                        ("zerocopy", "syncfree"),
                                        ("unified", "syncfree")])
def test_multirhs_panel_matches_columnwise(comm, sched):
    """On 4 devices, an (n, R) panel through the executors' row-panel carries
    equals R single-vector solves of the same plan (zero-copy pulls, unified
    dense exchanges, dagpart's merged supersteps, syncfree's packed or dense
    exchange of values and counters), for R = 3 and 8."""
    print(run_py(f"""
        import numpy as np
        from repro import compat
        from repro.core import DistributedSolver, SolverConfig, build_plan
        from repro.core.solver import AXIS
        from repro.obs.metrics import MetricsRegistry
        from repro.sparse import suite
        from repro.sparse.matrix import reference_solve

        a = suite.random_levelled(400, 12, 4.0, seed=7)
        mesh = compat.make_mesh((4,), (AXIS,))
        cfg = SolverConfig(block_size=16, comm={comm!r}, sched={sched!r},
                           partition="malleable")
        reg = MetricsRegistry()
        solver = DistributedSolver(build_plan(a, 4, cfg), mesh, registry=reg)
        assert solver.plan.n_boundary_rows > 0
        for R in (3, 8):
            B = np.random.default_rng(R).uniform(-1, 1, (a.n, R))
            X = solver.solve(B)
            assert X.shape == (a.n, R)
            for j in range(R):
                np.testing.assert_allclose(X[:, j], solver.solve(B[:, j]),
                                           rtol=2e-5, atol=2e-5)
                np.testing.assert_allclose(X[:, j], reference_solve(a, B[:, j]),
                                           rtol=2e-4, atol=2e-4)
        assert reg.counter("executor.row_panel_solves").value == 2
        print("OK")
    """, devices=4))


def test_spmv_panel_on_4_devices():
    """The Krylov SpMV on 4 devices: an (n, R) panel, carried as row panels
    on the devices, equals the dense ``A @ V`` of the symmetric matrix, and
    each column equals the single-vector matvec."""
    print(run_py("""
        import numpy as np
        from repro import compat
        from repro.core import SolverConfig, build_plan
        from repro.core.solver import AXIS
        from repro.krylov import spd_lower_from_triangular, symmetric_full_csr
        from repro.krylov.spmv import DistributedSpMV
        from repro.sparse import suite
        from repro.sparse.matrix import to_scipy

        low = spd_lower_from_triangular(suite.grid2d_factor(16, seed=1))
        plan = build_plan(low, 4, SolverConfig(block_size=8, partition="malleable"))
        assert plan.n_boundary_rows > 0
        spmv = DistributedSpMV(plan, compat.make_mesh((4,), (AXIS,)))
        a = to_scipy(symmetric_full_csr(low)).toarray()
        for R in (3, 8):
            V = np.random.default_rng(R).uniform(-1, 1, (low.n, R))
            Y = spmv.matvec(V)
            assert Y.shape == (low.n, R)
            np.testing.assert_allclose(Y, a @ V, rtol=1e-5, atol=1e-5)
            for j in range(R):
                np.testing.assert_allclose(Y[:, j], spmv.matvec(V[:, j]),
                                           rtol=1e-6, atol=1e-6)
        print("OK")
    """, devices=4))


@pytest.mark.slow
def test_lm_train_step_on_4_device_mesh():
    print(run_py("""
        import jax, numpy as np
        from repro.configs import get_reduced
        from repro.data import SyntheticLM
        from repro.models import init_params
        from repro.train.optim import adamw_init
        from repro.train.step import make_train_step
        from repro import compat
        mesh = compat.make_mesh((2, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            cfg = get_reduced("llama3.2-1b")
            params = init_params(cfg, jax.random.PRNGKey(0))
            opt = adamw_init(params)
            data = SyntheticLM(cfg, 4, 32)
            step = make_train_step(cfg, mesh, example_params=params,
                                   example_opt=opt, example_batch=data.batch(0))
            losses = []
            for s in range(3):
                params, opt, m = step(params, opt, data.batch(s), np.int32(s))
                losses.append(float(m["loss"]))
            assert all(np.isfinite(l) for l in losses), losses
        print("OK")
    """, devices=4))


@pytest.mark.slow
def test_serve_decode_on_4_device_mesh():
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_reduced
        from repro.models import init_cache, init_params
        from repro.serve.engine import make_decode_step, make_prefill_step
        from repro import compat
        mesh = compat.make_mesh((2, 2), ("data", "model"))
        with jax.set_mesh(mesh):
            cfg = get_reduced("llama3.2-1b")
            params = init_params(cfg, jax.random.PRNGKey(0))
            B, S = 4, 32
            cache = init_cache(cfg, B, S + 8)  # prefill-into-larger-cache path
            batch = {"tokens": jnp.zeros((B, S), jnp.int32)}
            prefill = make_prefill_step(cfg, mesh, example_params=params,
                                        example_cache=cache, example_batch=batch)
            logits, cache = prefill(params, batch, cache)
            dec_batch = {"tokens": jnp.zeros((B, 1), jnp.int32)}
            decode = make_decode_step(cfg, mesh, example_params=params,
                                      example_cache=cache, example_batch=dec_batch)
            for t in range(3):
                tok, cache = decode(params, dec_batch, cache, jnp.int32(S + t))
            assert tok.shape == (B,)
        print("OK")
    """, devices=4))


@pytest.mark.slow
def test_krylov_pcg_on_4_devices():
    """IC(0)-PCG with distributed SpMV + L/L^T solves on a real 4-device mesh."""
    print(run_py("""
        import numpy as np
        import scipy.sparse.linalg as spla
        from repro import compat
        from repro.core import SolverConfig
        from repro.krylov import solve_ic0_pcg, spd_lower_from_triangular, symmetric_full_csr
        from repro.sparse import suite
        from repro.sparse.matrix import to_scipy
        a = spd_lower_from_triangular(suite.grid2d_factor(16, seed=1))
        b = np.random.default_rng(2).uniform(-1, 1, a.n)
        mesh = compat.make_mesh((4,), ("x",))
        res = solve_ic0_pcg(a, b, mesh=mesh,
                            config=SolverConfig(block_size=8, comm="zerocopy"), tol=1e-8)
        assert res.converged, res.n_iters
        assert res.info["forward"].n_solves == res.n_iters
        x_ref = spla.spsolve(to_scipy(symmetric_full_csr(a)).tocsc(), b)
        err = np.abs(res.x - x_ref).max() / np.abs(x_ref).max()
        assert err < 1e-5, err
        print("OK")
    """, devices=4)
    )
