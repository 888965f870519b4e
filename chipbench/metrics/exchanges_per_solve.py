"""Boundary exchanges between chips per solve: the program's
``executor.exchanges`` over ``executor.solves`` (the plan's static count,
added at every call; the final gather of the solution is not one)."""
from chipbench.counters import per_solve


def read(run):
    return per_solve(run, "executor.exchanges")
