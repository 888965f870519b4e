"""Bytes the executor hands host to device per (n, R) panel: the program's
``executor.h2d_bytes`` over ``executor.solves``, one solve a panel."""
from chipbench.counters import per_solve


def read(run):
    if run["rhs_columns"] == 1:
        return None
    return per_solve(run, "executor.h2d_bytes")
