"""Bytes the executor hands host to device per single-RHS solve: the program's
``executor.h2d_bytes`` over ``executor.solves`` (host-resident plan arguments
and the staged right-hand side; a device-resident argument counts 0)."""
from chipbench.counters import per_solve


def read(run):
    if run["rhs_columns"] != 1:
        return None
    return per_solve(run, "executor.h2d_bytes")
