"""Per-kernel allclose vs the pure-jnp oracles, interpret mode, shape/dtype sweep."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.block_spmv import (
    block_gemm, block_gemm_rows, block_gemv, block_gemv_grouped,
)
from repro.kernels.block_trsv import block_trsm, block_trsm_rows, block_trsv


def _tri(k, B, dtype, seed=0):
    rng = np.random.default_rng(seed)
    L = np.tril(rng.uniform(-1, 1, (k, B, B))).astype(dtype)
    L[:, np.arange(B), np.arange(B)] = 2.0 + rng.uniform(0, 1, (k, B))
    r = rng.uniform(-1, 1, (k, B)).astype(dtype)
    return jnp.asarray(L), jnp.asarray(r)


@pytest.mark.parametrize("B", [8, 16, 32, 64])
@pytest.mark.parametrize("k", [1, 3, 17])
def test_trsv_rowsweep_matches_oracle(B, k):
    L, r = _tri(k, B, np.float32, seed=B * 100 + k)
    out = block_trsv(L, r, algorithm="rowsweep", interpret=True)
    np.testing.assert_allclose(out, ref.block_trsv_ref(L, r), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,panel", [(16, 8), (32, 8), (64, 16)])
def test_trsv_panel_matches_oracle(B, panel):
    L, r = _tri(5, B, np.float32, seed=B)
    out = block_trsv(L, r, algorithm="panel", panel=panel, interpret=True)
    np.testing.assert_allclose(out, ref.block_trsv_ref(L, r), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [np.float32])
@pytest.mark.parametrize("B", [8, 32, 128])
@pytest.mark.parametrize("m", [1, 5, 13])
def test_gemv_matches_oracle(B, m, dtype):
    rng = np.random.default_rng(B + m)
    T = jnp.asarray(rng.uniform(-1, 1, (m, B, B)).astype(dtype))
    x = jnp.asarray(rng.uniform(-1, 1, (m, B)).astype(dtype))
    out = block_gemv(T, x, interpret=True)
    np.testing.assert_allclose(out, ref.block_gemv_ref(T, x), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("group", [2, 4, 8])
def test_gemv_grouped_matches_oracle(group):
    rng = np.random.default_rng(group)
    m, B = 11, 16  # deliberately not a multiple of group (exercises padding)
    T = jnp.asarray(rng.uniform(-1, 1, (m, B, B)).astype(np.float32))
    x = jnp.asarray(rng.uniform(-1, 1, (m, B)).astype(np.float32))
    out = block_gemv_grouped(T, x, group=group, interpret=True)
    np.testing.assert_allclose(out, ref.block_gemv_ref(T, x), rtol=2e-5, atol=2e-5)


def test_trsv_solves_the_system():
    L, r = _tri(4, 32, np.float32)
    x = block_trsv(L, r, interpret=True)
    np.testing.assert_allclose(
        jnp.einsum("kij,kj->ki", L, x), r, rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# multi-RHS panels: one kernel launch serves R systems
# ---------------------------------------------------------------------------


def _panel_cases(cols):
    """``(k,B,R)`` column-panel cases under their old ids, then ``(k,R,B)``
    row-panel cases (the levelset executors' carry layout) at the TPU's
    B = 128 and at 32, for R in 1, 3, 8."""
    return ([pytest.param(*c, "cols", id="-".join(map(str, c))) for c in cols]
            + [pytest.param(B, k, R, "rows", id=f"rows-{B}-{k}-{R}")
               for B in (32, 128) for k, R in ((1, 1), (3, 3), (2, 8))])


@pytest.mark.parametrize("B,k,R,layout",
                         _panel_cases([(8, 1, 2), (16, 3, 4), (32, 5, 8)]))
def test_trsm_matches_oracle(B, k, R, layout):
    L, _ = _tri(k, B, np.float32, seed=B + R)
    r = jnp.asarray(np.random.default_rng(R).uniform(-1, 1, (k, B, R)).astype(np.float32))
    out = block_trsm(L, r, interpret=True)
    np.testing.assert_allclose(out, ref.block_trsv_ref(L, r), rtol=2e-5, atol=2e-5)
    if layout == "rows":
        rows = jnp.swapaxes(r, 1, 2)
        out_rows = block_trsm_rows(L, rows, interpret=True)
        assert out_rows.shape == (k, R, B)
        np.testing.assert_allclose(out_rows, ref.block_trsv_rows_ref(L, rows),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out_rows, jnp.swapaxes(out, 1, 2),
                                   rtol=2e-5, atol=2e-5)


def test_trsm_columns_equal_independent_trsv():
    """Panel solve must be exactly R stacked single-RHS solves."""
    k, B, R = 4, 16, 3
    L, _ = _tri(k, B, np.float32, seed=9)
    r = jnp.asarray(np.random.default_rng(9).uniform(-1, 1, (k, B, R)).astype(np.float32))
    panel = block_trsm(L, r, interpret=True)
    for j in range(R):
        single = block_trsv(L, r[..., j], interpret=True)
        np.testing.assert_allclose(panel[..., j], single, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,m,R,layout",
                         _panel_cases([(8, 1, 2), (16, 7, 4), (32, 4, 5)]))
def test_gemm_matches_oracle(B, m, R, layout):
    rng = np.random.default_rng(B + m + R)
    T = jnp.asarray(rng.uniform(-1, 1, (m, B, B)).astype(np.float32))
    x = jnp.asarray(rng.uniform(-1, 1, (m, B, R)).astype(np.float32))
    out = block_gemm(T, x, interpret=True)
    np.testing.assert_allclose(out, ref.block_gemv_ref(T, x), rtol=2e-5, atol=2e-5)
    if layout == "rows":
        rows = jnp.swapaxes(x, 1, 2)
        out_rows = block_gemm_rows(T, rows, interpret=True)
        assert out_rows.shape == (m, R, B)
        np.testing.assert_allclose(out_rows, ref.block_gemv_rows_ref(T, rows),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out_rows, jnp.swapaxes(out, 1, 2),
                                   rtol=2e-5, atol=2e-5)


def test_ops_dispatch_by_rhs_rank():
    """ops wrappers route (k,B) and (k,B,R) to the right backend kernels."""
    L, r = _tri(3, 16, np.float32, seed=2)
    rp = jnp.asarray(np.random.default_rng(2).uniform(-1, 1, (3, 16, 4)).astype(np.float32))
    for backend in ("reference", "pallas"):
        out1 = ops.batched_block_trsv(L, r, backend=backend)
        out2 = ops.batched_block_trsv(L, rp, backend=backend)
        assert out1.shape == (3, 16) and out2.shape == (3, 16, 4)
        np.testing.assert_allclose(out1, ref.block_trsv_ref(L, r), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(out2, ref.block_trsv_ref(L, rp), rtol=2e-5, atol=2e-5)
        g1 = ops.batched_block_gemv(L, r, backend=backend)
        g2 = ops.batched_block_gemv(L, rp, backend=backend)
        np.testing.assert_allclose(g1, ref.block_gemv_ref(L, r), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(g2, ref.block_gemv_ref(L, rp), rtol=2e-5, atol=2e-5)
        # the (k,R,B) row-panel ops, by the same backend rule
        rows = jnp.swapaxes(rp, 1, 2)
        t3 = ops.batched_block_trsv_rows(L, rows, backend=backend)
        g3 = ops.batched_block_gemv_rows(L, rows, backend=backend)
        assert t3.shape == g3.shape == (3, 4, 16)
        np.testing.assert_allclose(t3, jnp.swapaxes(out2, 1, 2), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(g3, jnp.swapaxes(g2, 1, 2), rtol=2e-5, atol=2e-5)
