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
        from repro.core import sptrsv, SolverConfig
        a = suite.random_levelled(600, 24, 4.0, seed=5)
        b = np.random.default_rng(1).uniform(-1, 1, a.n)
        x_ref = reference_solve(a, b)
        from repro import compat
        mesh = compat.make_mesh((8,), ("x",))
        for comm in ["zerocopy", "unified"]:
            for sched in ["levelset", "dagpart", "syncfree"]:
                for part in ["taskpool", "contiguous", "malleable"]:
                    cfg = SolverConfig(block_size=16, comm=comm, sched=sched, partition=part)
                    x = sptrsv(a, b, mesh=mesh, config=cfg)
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
