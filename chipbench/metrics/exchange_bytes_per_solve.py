"""Bytes one chip puts into the boundary exchanges per solve: the program's
``executor.exchange_bytes`` over ``executor.solves`` (the plan's
``comm_bytes_per_solve`` times the right-hand side's columns, added at every
call; the final gather's psum of the solution is left out)."""
from chipbench.counters import per_solve


def read(run):
    return per_solve(run, "executor.exchange_bytes")
