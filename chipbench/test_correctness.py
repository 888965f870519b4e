"""The comparison that decides ``correct`` fails what it must.

* The control -- the reference computed one precision step below the
  program's (``high`` products for float32 at ``highest``) -- reads above the
  configuration's ``rel_err`` limit, where the program reads below it.
* A whole run of the harness, with its look for a chip skipped, comes out
  ``correct: false`` with the control in the program's place (as
  ``chipbench/control.py`` reads it at the cells' own sizes on the chip), and
  with the timed path broken underneath for each fault a cell can have: an
  answer altered where it is produced, half of a panel's columns left out,
  and (on four virtual devices) the exchange between chips left out.

All at sizes a CPU test can hold.
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import control, matrices, reference, run  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
SWEEP, PANEL = "grid5pt_1024.sweep", "grid5pt_1024.panel8"
SEED = 2 ** 31 + 11


def small_spec(workload: str, nx: int = 64, ny: int = 48) -> dict:
    spec = run.cell_spec(BENCH, workload)
    spec["config"] = dict(spec["config"], matrix=dict(spec["config"]["matrix"], nx=nx, ny=ny))
    return spec


def run_small(spec: dict, seed: int = SEED) -> dict:
    return run.run_cell(spec, seed, 0.3, False, t_start=time.perf_counter(),
                        require_chip=False)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5])
@pytest.mark.parametrize("workload", [SWEEP, PANEL])
def test_control_fails_where_the_reference_passes(workload, seed):
    spec = small_spec(workload, 96, 64)
    limit = spec["config"]["limits"]["rel_err"]
    traffic = spec["traffic"]
    m = matrices.build(spec["config"]["matrix"], seed)
    m32 = dataclasses.replace(m, val=m.val.astype(np.float32).astype(np.float64))
    R = traffic["rhs_columns"]
    b = np.random.default_rng([seed, 1]).uniform(
        -1, 1, (m.n,) if R == 1 else (m.n, R)).astype(np.float32)
    bref = bctl = b
    for op in traffic["ops"]:
        tr = op == "transpose"
        xref = reference.solve(m, bref, tr)
        xctl = reference.control_solve(m, bctl, tr)
        # rounding the matrix to float32, as the program stores it, stays far below
        x32 = reference.solve(m32, bref, tr)
        assert reference.rel_err(x32, xref) < limit / 10
        assert reference.rel_err(xctl, xref) > 2 * limit
        if traffic.get("chain"):
            bref, bctl = xref, xctl


def test_bf16_rounding():
    x = np.array([1.0, 1 + 2 ** -8, 1 + 2 ** -7 + 2 ** -9, -3.0e-3], np.float32)
    r = reference.bf16(x)
    assert r[0] == 1.0 and r[1] == 1.0  # a tie goes to even
    assert r[2] == np.float32(1 + 2 ** -7)
    assert abs(r[3] - x[3]) <= abs(x[3]) * 2 ** -8
    a, b = np.float32(1 / 3), np.float32(2 / 7)
    assert reference.high_mul(a, b) != a * b
    assert abs(reference.high_mul(a, b) - a * b) < 1e-5 * a * b


@pytest.mark.parametrize("workload", [SWEEP, PANEL])
def test_sound_run_is_correct(workload):
    r = run_small(small_spec(workload))
    assert r["correct"] is True and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert r["checks"]["rel_err"]["value"] < r["checks"]["rel_err"]["limit"]


@pytest.mark.parametrize("workload", [SWEEP, PANEL])
def test_control_in_the_programs_place_is_caught(workload):
    spec = small_spec(workload, 96, 64)
    with control.control_in_place(spec["config"]["matrix"], SEED):
        r = run_small(spec, SEED)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["rel_err"]["value"] > r["checks"]["rel_err"]["limit"]


def test_altered_answer_is_caught(monkeypatch):
    from repro.core import solver

    solve = solver.DistributedSolver.solve

    def altered(self, b):
        x = np.array(solve(self, b))
        i = np.unravel_index(np.abs(x).argmax(), x.shape)
        x[i] *= 1 + 1e-4
        return x

    monkeypatch.setattr(solver.DistributedSolver, "solve", altered)
    r = run_small(small_spec(SWEEP))
    assert r["correct"] is False and r["failed"] > 0


def test_half_the_panel_left_out_is_caught(monkeypatch):
    from repro.core import solver

    solve = solver.DistributedSolver.solve

    def half(self, b):
        x = np.array(solve(self, b))
        x[:, x.shape[1] // 2:] = 0.0
        return x

    monkeypatch.setattr(solver.DistributedSolver, "solve", half)
    r = run_small(small_spec(PANEL))
    assert r["correct"] is False and r["failed"] > 0


FOUR_DEVICES = """
import json, sys, time
sys.path.insert(0, {root!r})
from chipbench import run
from repro.core import solver
if {broken}:
    body = solver._compact_level_body
    solver._compact_level_body = lambda *a, **k: body(*a, **dict(k, ex=None))
config = {{"matrix": {{"generator": "grid_lower", "nx": 128, "ny": 48}},
          "options": {{"partition": "malleable", "comm": "zerocopy"}},
          "limits": {{"rel_err": 1.5e-6}}}}
traffic = {{"ops": ["forward"], "rhs_columns": 1, "pool": 4, "warmup_steps": 1,
           "trace_steps": 1, "check_per_op": 8}}
spec = {{"cell": {{"chips": 4}}, "end_to_end": [], "per_layer": [],
        "config": config, "traffic": traffic}}
r = run.run_cell(spec, 2 ** 31 + 3, 0.3, False, t_start=time.perf_counter(),
                 require_chip=False)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_exchange_left_out_is_caught(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(root=str(HERE.parent), broken=broken)],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not broken), r
