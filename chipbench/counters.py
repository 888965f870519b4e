"""The program's own counters, for the metrics that read them.

The program under test keeps always-on instruments in its process-wide
metrics registry (``repro.obs.metrics``): the executor's host-to-device bytes,
solves and launch times, and JAX's compile time. A reader runs in the process
that ran the cell, after its window, and reads them there. Every executor call
of a run is a step of the cell's mix, the warm-up's included, and the first
call of each shape (the one that compiles) is kept out of the launch times,
so a ratio over the whole run is the window's.

Where the program has no such instrument, the reading is ``None``.
"""
from __future__ import annotations


def program(run: dict) -> dict:
    """The registry's snapshot: ``run["program"]`` where the run carries one
    (the harness's tests), else the live one of the program under test."""
    if "program" in run:
        return run["program"]
    from repro.obs.metrics import get_registry

    return get_registry().snapshot()


def per_solve(run: dict, counter: str) -> float | None:
    """``counter`` over the executor's solves (a panel counts once)."""
    snap = program(run)
    solves = snap.get("executor.solves")
    if counter not in snap or not solves:
        return None
    return snap[counter] / solves
