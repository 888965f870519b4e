"""Per-kernel allclose vs the pure-jnp oracles, interpret mode, shape/dtype sweep."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.block_spmv import block_gemm_rows, block_gemv
from repro.kernels.block_trsv import block_trsm_rows, block_trsv


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


def test_trsv_solves_the_system():
    L, r = _tri(4, 32, np.float32)
    x = block_trsv(L, r, interpret=True)
    np.testing.assert_allclose(
        jnp.einsum("kij,kj->ki", L, x), r, rtol=1e-4, atol=1e-4
    )


# ---------------------------------------------------------------------------
# multi-RHS panels: one kernel launch serves R systems
# ---------------------------------------------------------------------------


def _panel_cases(shapes):
    """``(k, R, B)`` row-panel cases (the layout every executor carries):
    the given ``(B, k, R)`` under their old ids, then the TPU's B = 128 and
    32, for R in 1, 3, 8."""
    return ([pytest.param(*c, id="-".join(map(str, c))) for c in shapes]
            + [pytest.param(B, k, R, id=f"rows-{B}-{k}-{R}")
               for B in (32, 128) for k, R in ((1, 1), (3, 3), (2, 8))])


def _rows(k, R, B, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(-1, 1, (k, R, B)).astype(np.float32))


@pytest.mark.parametrize("B,k,R", _panel_cases([(8, 1, 2), (16, 3, 4), (32, 5, 8)]))
def test_trsm_matches_oracle(B, k, R):
    L, _ = _tri(k, B, np.float32, seed=B + R)
    rows = _rows(k, R, B, seed=R)
    out = block_trsm_rows(L, rows, interpret=True)
    assert out.shape == (k, R, B)
    np.testing.assert_allclose(out, ref.block_trsv_rows_ref(L, rows),
                               rtol=2e-5, atol=2e-5)


def test_trsm_columns_equal_independent_trsv():
    """A row-panel solve must be exactly R stacked single-RHS solves: row j
    of each panel equals block_trsv on that row."""
    k, B, R = 4, 16, 3
    L, _ = _tri(k, B, np.float32, seed=9)
    rows = _rows(k, R, B, seed=9)
    panel = block_trsm_rows(L, rows, interpret=True)
    for j in range(R):
        single = block_trsv(L, rows[:, j], interpret=True)
        np.testing.assert_allclose(panel[:, j], single, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,m,R", _panel_cases([(8, 1, 2), (16, 7, 4), (32, 4, 5)]))
def test_gemm_matches_oracle(B, m, R):
    rng = np.random.default_rng(B + m + R)
    T = jnp.asarray(rng.uniform(-1, 1, (m, B, B)).astype(np.float32))
    rows = jnp.asarray(rng.uniform(-1, 1, (m, R, B)).astype(np.float32))
    out = block_gemm_rows(T, rows, interpret=True)
    assert out.shape == (m, R, B)
    np.testing.assert_allclose(out, ref.block_gemv_rows_ref(T, rows),
                               rtol=2e-5, atol=2e-5)


def test_ops_dispatch_by_rhs_rank():
    """ops route (k,B) vectors to the vector kernels and (k,R,B) row panels
    to the ``*_rows`` kernels, on both backends; each row of a panel is the
    vector op on that row."""
    L, r = _tri(3, 16, np.float32, seed=2)
    rows = _rows(3, 4, 16, seed=2)
    for backend in ("reference", "pallas"):
        out1 = ops.batched_block_trsv(L, r, backend=backend)
        g1 = ops.batched_block_gemv(L, r, backend=backend)
        assert out1.shape == g1.shape == (3, 16)
        np.testing.assert_allclose(out1, ref.block_trsv_ref(L, r), rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(g1, ref.block_gemv_ref(L, r), rtol=2e-5, atol=2e-5)
        t3 = ops.batched_block_trsv_rows(L, rows, backend=backend)
        g3 = ops.batched_block_gemv_rows(L, rows, backend=backend)
        assert t3.shape == g3.shape == (3, 4, 16)
        for j in range(4):
            np.testing.assert_allclose(
                t3[:, j], ops.batched_block_trsv(L, rows[:, j], backend=backend),
                rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(
                g3[:, j], ops.batched_block_gemv(L, rows[:, j], backend=backend),
                rtol=2e-5, atol=2e-5)


def test_vector_ops_refuse_panels():
    """A rank-3 argument to the vector ops is refused on every backend,
    naming the row-panel op, so a (k, B, R) column panel is never read as
    something else."""
    L, _ = _tri(2, 8, np.float32)
    panel = jnp.ones((2, 8, 3), jnp.float32)
    for backend in ("reference", "pallas"):
        with pytest.raises(ValueError, match="batched_block_trsv_rows"):
            ops.batched_block_trsv(L, panel, backend=backend)
        with pytest.raises(ValueError, match="batched_block_gemv_rows"):
            ops.batched_block_gemv(L, panel, backend=backend)
