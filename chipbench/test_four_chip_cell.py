"""The four-chip cell ``grid5pt_4096x2048.sweep`` on the CPU: it is declared
with its exchange metrics, their readers read the program's counters and the
trace, and a whole run of the cell's own configuration and mix, at a grid a
CPU test holds, on four virtual devices, is correct, and is caught when the
exchange between chips is left out."""
import json
import os
import pathlib
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chipbench import run  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
CELL = "grid5pt_4096x2048.sweep"
EXCHANGE = ["exchanges_per_solve", "exchange_bytes_per_solve",
            "collective_ms_per_solve"]


def test_cell_is_declared_with_its_exchange_metrics():
    spec = run.cell_spec(BENCH, CELL)
    assert spec["cell"]["chips"] == 4 and spec["cell"]["traffic"] == "sweep"
    assert spec["config"]["options"] == {"partition": "malleable"}
    assert spec["config"]["chips"] == 4 and spec["config"]["reduced"] == []
    assert [m["name"] for m in spec["end_to_end"]] == [
        "solve_ms", "solve_p90_ms", "setup_s"]
    names = [m["name"] for m in spec["per_layer"]]
    assert names == ["plan_s", "launches_per_solve", "device_idle_share.solve",
                     "sptrsv_roofline.solve"] + EXCHANGE
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name in EXCHANGE:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["layer"] == "exchange"
        assert per_layer[name]["moves"] == "solve_ms"


PROGRAM = {"executor.solves": 12, "executor.exchanges": 12 * 2078,
           "executor.exchange_bytes": 12 * 67_124_224}
# on the TPU the exchange's all-reduce is named after the psum, which the
# trace's own ``collective_s`` misses: the reader takes it from the breakdown
TRACE = {"window_s": 8.0, "busy_s": 7.0, "collective_s": 0.0,
         "devices": [{"name": "/device:TPU:0"}],
         "device_ops": [["block_trsv", 4.0], ["copy", 2.0], ["psum", 0.5],
                        ["all-reduce-start", 0.1], ["fusion", 0.05]]}


@pytest.mark.parametrize("name, run_, want", [
    ("exchanges_per_solve", {"program": PROGRAM}, 2078.0),
    ("exchange_bytes_per_solve", {"program": PROGRAM}, 67_124_224.0),
    ("collective_ms_per_solve", {"trace": TRACE, "solves": 8}, 75.0),
])
def test_exchange_readers(name, run_, want):
    read = run.load_reader(name)
    assert read(run_) == pytest.approx(want)
    # a program without the counters, or a run without a trace, reads nothing
    empty = {"program": {}} if "program" in run_ else {"trace": None, "solves": 8}
    assert read(empty) is None


FOUR_DEVICES = """
import json, sys, time
sys.path.insert(0, {root!r})
from chipbench import run
from repro.core import solver
if {broken}:
    body = solver._compact_level_body
    solver._compact_level_body = lambda *a, **k: body(*a, **dict(k, ex=None))
spec = json.loads({spec!r})
r = run.run_cell(spec, 2 ** 31 + 13, 0.3, False, t_start=time.perf_counter(),
                 require_chip=False)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"],
                  "exchanges": run.load_reader("exchanges_per_solve")({{}}),
                  "exchange_bytes": run.load_reader("exchange_bytes_per_solve")({{}})}}))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_four_device_run_of_the_cell(broken):
    """The cell's own configuration, options and sweep mix on a 256 x 64
    grid and four virtual devices, through ``run.run_cell``."""
    spec = run.cell_spec(BENCH, CELL)
    spec["config"] = dict(spec["config"], matrix=dict(
        spec["config"]["matrix"], nx=256, ny=64))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES.format(
            root=str(HERE.parent), broken=broken, spec=json.dumps(spec))],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not broken), r
    # the malleable partition cuts the grid: the levels pull boundary rows
    assert r["exchanges"] > 0 and r["exchange_bytes"] > 0
