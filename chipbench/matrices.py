"""The benchmark's own matrix generator (a copy, so the yardstick cannot move).

``grid_lower(nx, ny, seed)`` is the lower factor pattern of a 5-point
Laplacian on an ``nx`` x ``ny`` grid, numbered row by row: row ``i`` has its
north neighbour ``i - nx``, its west neighbour ``i - 1`` and the diagonal.
At ``nx == ny`` it is ``repro.sparse.suite.grid2d_factor(nx)``, and the values
follow ``repro.sparse.matrix.lower_triangular_from_coo``: off-diagonal entries
uniform in (-1, 1), drawn in row-major order from ``default_rng(seed)``, and a
diagonal of ``1 + sum(|row|)``. It is vectorised, so 2^23 rows take seconds.

The pattern is fixed by the configuration; the values come from the seed.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Lower:
    """A lower-triangular CSR matrix with its grid levels (plain numpy)."""

    n: int
    row_ptr: np.ndarray  # (n+1,) int64
    col_idx: np.ndarray  # (nnz,) int32, ascending in each row, diagonal last
    val: np.ndarray  # (nnz,) float64
    level: np.ndarray  # (n,) int64 level of each row in forward substitution

    @property
    def nnz(self) -> int:
        return int(self.row_ptr[-1])


def grid_lower(nx: int, ny: int, seed: int) -> Lower:
    n = nx * ny
    i = np.arange(n, dtype=np.int64)
    has_n = i >= nx
    has_w = (i % nx) != 0
    row_ptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(1 + has_n + has_w, out=row_ptr[1:])
    nnz = int(row_ptr[-1])
    start = row_ptr[:-1]
    col = np.empty(nnz, dtype=np.int32)
    col[start[has_n]] = i[has_n] - nx
    col[start[has_w] + has_n[has_w]] = i[has_w] - 1
    diag_pos = row_ptr[1:] - 1
    col[diag_pos] = i
    off = np.ones(nnz, dtype=bool)
    off[diag_pos] = False
    val = np.zeros(nnz, dtype=np.float64)
    val[off] = np.random.default_rng(seed).uniform(-1.0, 1.0, size=nnz - n)
    # north first, then west: the same summation order as the suite's add.at
    val[diag_pos] = 1.0 + np.add.reduceat(np.abs(val), start)
    level = i // nx + i % nx
    return Lower(n=n, row_ptr=row_ptr, col_idx=col, val=val, level=level)


GENERATORS = {"grid_lower": grid_lower}


def build(spec: dict, seed: int) -> Lower:
    """The matrix a configuration's ``matrix`` entry describes, values from ``seed``."""
    kw = {k: v for k, v in spec.items() if k != "generator"}
    return GENERATORS[spec["generator"]](seed=seed, **kw)
