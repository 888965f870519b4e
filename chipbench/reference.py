"""Plain references for the benchmark's correctness check.

``solve`` is the float64 triangular solve by scipy on the harness's own CSR
(nothing of the program is imported or reused). ``control_solve`` is the
same substitution computed one precision step below what the program states:
float32 with every product taken at XLA's ``high`` matmul precision (three
bfloat16 passes, the ``bf16_3x`` algorithm), where the program runs its dots
at ``highest``. The control has to fail the comparison that ``solve`` passes.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla


def to_scipy(m) -> sp.csr_matrix:
    return sp.csr_matrix((m.val, m.col_idx, m.row_ptr), shape=(m.n, m.n))


def solve(m, b: np.ndarray, transpose: bool = False, a: sp.csr_matrix | None = None
          ) -> np.ndarray:
    """float64 solve of ``L x = b`` (or ``L^T x = b``); ``b`` is (n,) or (n, R)."""
    a = to_scipy(m) if a is None else a
    b = np.asarray(b, dtype=np.float64)
    if transpose:
        return spla.spsolve_triangular(a.T.tocsr(), b, lower=False)
    return spla.spsolve_triangular(a, b, lower=True)


def rel_err(x: np.ndarray, x_ref: np.ndarray) -> float:
    """max |x - x_ref| / max |x_ref| (inf where x is not finite)."""
    x = np.asarray(x, dtype=np.float64)
    if x.shape != x_ref.shape or not np.isfinite(x).all():
        return float("inf")
    return float(np.abs(x - x_ref).max() / np.abs(x_ref).max())


def bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept as float32."""
    u = np.asarray(a, dtype=np.float32).view(np.uint32).astype(np.uint64)
    r = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return r.astype(np.uint32).view(np.float32)


def high_mul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """a * b as a bf16_3x dot forms it: hi*hi + (hi*lo + lo*hi), in float32."""
    a_hi, b_hi = bf16(a), bf16(b)
    a_lo, b_lo = bf16(a - a_hi), bf16(b - b_hi)
    return a_hi * b_hi + (a_hi * b_lo + a_lo * b_hi)


def control_solve(m, b: np.ndarray, transpose: bool = False) -> np.ndarray:
    """Level-by-level substitution in float32 with ``high``-precision products.

    ``b`` is (n,) or (n, R). Rows of one level are independent, so each level
    is one vectorised step over its rows' off-diagonal entries.
    """
    a = to_scipy(m)
    lvl = m.level
    if transpose:
        a = a.T.tocsr()
        lvl = lvl.max() - lvl
    a = a.tocoo()
    off = a.row != a.col
    rows, cols = a.row[off].astype(np.int64), a.col[off].astype(np.int64)
    vals = a.data[off].astype(np.float32)
    diag = m.val[m.row_ptr[1:] - 1].astype(np.float32)
    b = np.asarray(b, dtype=np.float32)
    x = np.zeros_like(b)
    order = np.argsort(lvl, kind="stable")
    row_ptr = np.searchsorted(lvl[order], np.arange(lvl.max() + 2))
    e_order = np.argsort(lvl[rows], kind="stable")
    rows, cols, vals = rows[e_order], cols[e_order], vals[e_order]
    e_ptr = np.searchsorted(lvl[rows], np.arange(lvl.max() + 2))
    tail = (slice(None),) + (None,) * (b.ndim - 1)
    acc = np.zeros_like(b)
    for t in range(int(lvl.max()) + 1):
        e = slice(e_ptr[t], e_ptr[t + 1])
        if e.stop > e.start:
            prods = high_mul(vals[e][tail], x[cols[e]])
            np.add.at(acc, rows[e], prods)
        r = order[row_ptr[t]:row_ptr[t + 1]]
        x[r] = (b[r] - acc[r]) / diag[r][tail]
    return x
