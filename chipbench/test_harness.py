"""The benchmark harness on the CPU: cells resolve by name, the yardstick's
pieces (generator, essential bytes, peaks, trace reduction) hold, and the
entry point refuses to measure anywhere but on the chip."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import matrices, roofline, run, xplane  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
SMALL_TRACE = HERE / "testdata" / "small_sweep.xplane.pb"


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_by_name(workload):
    spec = run.cell_spec(BENCH, workload)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert set(spec["traffic"]["ops"]) <= {"forward", "transpose"}
    names = [m["name"] for m in spec["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert spec["per_layer"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(run.load_reader(m["name"]))


def test_config_files_state_their_matrix():
    for c in BENCH["configs"]:
        cfg = json.loads((HERE.parent / c["file"]).read_text())
        mx = cfg["matrix"]
        n = mx["nx"] * mx["ny"]
        assert cfg["n"] == n
        assert cfg["nnz"] == n + mx["ny"] * (mx["nx"] - 1) + (mx["ny"] - 1) * mx["nx"]
        assert cfg["levels"] == mx["nx"] + mx["ny"] - 1
        assert cfg["reduced"] == c["reduced"]


def test_generator_reproduces_the_suite_pattern():
    from repro.sparse import suite

    ref = suite.grid2d_factor(1024, seed=6)
    m = matrices.grid_lower(1024, 1024, seed=6)
    assert (m.n, m.nnz) == (ref.n, ref.nnz) == (2 ** 20, 3_143_680)
    np.testing.assert_array_equal(m.row_ptr, ref.row_ptr)
    np.testing.assert_array_equal(m.col_idx, ref.col_idx)
    np.testing.assert_array_equal(m.val, ref.val)


def test_generator_levels_are_the_substitution_levels():
    m = matrices.grid_lower(5, 3, seed=1)
    rows = np.repeat(np.arange(m.n), np.diff(m.row_ptr))
    off = rows != m.col_idx
    # every dependency sits exactly one level earlier
    np.testing.assert_array_equal(m.level[rows[off]] - m.level[m.col_idx[off]], 1)
    assert m.level.max() == 5 + 3 - 2


def test_essential_bytes_hand_count():
    # 3 x 2 grid: rows 0..5; north links 3, west links 2 + 2; 6 diagonals
    m = matrices.grid_lower(3, 2, seed=0)
    assert m.nnz == 6 + 3 + 4
    # 13 nonzeros x (4 B value + 4 B index) + 6 row pointers x 4 B + b and x
    assert roofline.essential_bytes(m.n, m.nnz, 1) == 13 * 8 + 6 * 4 + 6 * 4 * 2
    assert roofline.essential_bytes(m.n, m.nnz, 8) == 13 * 8 + 6 * 4 + 8 * 6 * 4 * 2
    # the 1024 x 1024 grid at R = 1 and R = 8
    assert roofline.essential_bytes(2 ** 20, 3_143_680, 1) == 37_732_352
    assert roofline.essential_bytes(2 ** 20, 3_143_680, 8) == 96_452_608


def test_peaks_are_keyed_by_device_kind():
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    t = roofline.least_time_s(2 ** 20, 3_143_680, 1, 1, "TPU v5 lite")
    assert 45e-6 < t < 47e-6


def test_trace_reduction_on_a_recorded_chip_trace():
    """A sweep step of a 128 x 64 grid traced on one v5e chip: the sweep
    cell's spec with ``matrix`` set to that grid and ``trace_steps`` to 1,
    run through ``run.run_cell`` with ``trace=True``, and the window's
    ``.xplane.pb`` kept."""
    r = xplane.reduce_profile(xplane.load(str(SMALL_TRACE)))
    assert [d["name"] for d in r["devices"]] == ["/device:TPU:0"]
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0.0
    ops = dict(r["device_ops"])
    assert "block_trsv" in ops and "cond" in ops
    # self times never exceed the busy union, and gaps fill the rest
    assert sum(ops.values()) <= r["busy_s"] * (1 + 1e-9)
    gaps = sum(s for _, s in r["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s"] + 1e-9
    assert r["idle_gaps"][0][1] > 0


def test_union_and_host_segments():
    assert xplane._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    starts, names = xplane._host_segments([(0, 10, "outer"), (2, 4, "inner")])
    at = {t: n for t, n in zip(starts, names)}
    assert at[0] == "outer" and at[2] == "inner" and at[4] == "outer" and at[10] is None
    assert xplane.op_name("%block_trsm.15 = f32[8,128,8] custom-call(...)") == "block_trsm"
    assert xplane.op_name("%all-reduce.3 = f32[4] all-reduce(...)") == "all-reduce"
    assert xplane.op_name("%broadcast_in_dim.183.clone = f32[8] broadcast(...)") \
        == "broadcast_in_dim"


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "grid5pt_1024.sweep",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_off_the_tpu():
    p = _run_py(HERE.parent)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(HERE.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
