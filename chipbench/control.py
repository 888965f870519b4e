#!/usr/bin/env python3
"""The control of one cell, read through the harness's own comparison.

    python3 chipbench/control.py --workload <name> --seeds 4001-4003 --seconds 15

For each seed, one whole run of the cell (``run.run_cell``: the same matrix,
pool, set-up, window and sampled comparison with the float64 reference) with
the control in the program's place: the reference computed one precision
step below what the configuration states (``reference.control_solve``,
float32 with ``high`` products where the program runs its dots at
``highest``). It prints each run's numbers compared beside their limits, and
exits 0 only if every run came out ``correct: false``. The smallest
``rel_err`` is the upper reading the limit is set from; the lower reading is
the largest that the cell's own runs print. The benchmark never runs this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import matrices, reference, run  # noqa: E402


@contextlib.contextmanager
def control_in_place(matrix_spec: dict, seed: int):
    """``SpTRSVContext.solve`` answers with the control, on the matrix that
    a run with ``seed`` builds."""
    from repro.api import SpTRSVContext

    m = matrices.build(matrix_spec, seed)
    solve = SpTRSVContext.solve

    def control(self, handle, b, *, transpose=False):
        return reference.control_solve(m, b, transpose)

    SpTRSVContext.solve = control
    try:
        yield
    finally:
        SpTRSVContext.solve = solve


def seed_range(text: str) -> list[int]:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last, inclusive")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    spec = run.cell_spec(run.load_json(ROOT / "BENCHMARK.json"), args.workload)
    upper, every_run_failed = float("inf"), True
    for seed in seed_range(args.seeds):
        t0 = time.perf_counter()
        with control_in_place(spec["config"]["matrix"], seed):
            r = run.run_cell(spec, seed, args.seconds, False, t_start=t0)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "checks": r["checks"],
                          "seconds": time.perf_counter() - t0}), flush=True)
        upper = min(upper, r["checks"]["rel_err"]["value"])
        every_run_failed &= not r["correct"]
    print(json.dumps({"workload": args.workload, "upper": upper,
                      "limit": spec["config"]["limits"]["rel_err"],
                      "every_run_failed": every_run_failed}), flush=True)
    return 0 if every_run_failed else 1


if __name__ == "__main__":
    sys.exit(main())
