"""The program's own counters as the benchmark reads them: the readers of
the new per-layer metrics, and the trace's idle time named by program span
and its host-to-device bytes, checked against the program's counter on a
recorded chip trace."""
import json
import os
import pathlib
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

from chipbench import matrices, run, xplane, xplane_spans  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
BENCH = json.loads((HERE.parent / "BENCHMARK.json").read_text())
SMALL_TRACE = HERE / "testdata" / "small_sweep.xplane.pb"

PROGRAM = {
    "executor.solves": 12,
    "executor.h2d_bytes": 12 * 1_000_000,
    "executor.launch_us": {"count": 10, "sum": 25_000.0, "min": 2_000.0,
                           "max": 3_000.0, "mean": 2_500.0, "last": 2_400.0},
    "jit.compile_s": 7.5,
    "jit.compiles": 9,
}


@pytest.mark.parametrize("name, R, want", [
    ("h2d_bytes_per_solve.solve", 1, 1_000_000.0),
    ("h2d_bytes_per_solve.panel", 8, 1_000_000.0),
    ("host_launch_ms_per_solve", 1, 2.5),
    ("compile_s", 8, 7.5),
])
def test_reader_reads_the_program_counters(name, R, want):
    read = run.load_reader(name)
    assert read({"rhs_columns": R, "program": PROGRAM}) == pytest.approx(want)
    # a program without these instruments (the parent of this metric) reads
    # nothing, and never raises
    assert read({"rhs_columns": R, "program": {}}) is None


def test_new_metrics_are_declared_for_their_cells():
    per_layer = {m["name"]: m for m in BENCH["per_layer"]}
    for name, cells in [
        ("h2d_bytes_per_solve.solve", ["grid5pt_1024.sweep"]),
        ("h2d_bytes_per_solve.panel", ["grid5pt_1024.panel8"]),
        ("host_launch_ms_per_solve", ["grid5pt_1024.sweep"]),
        ("compile_s", ["grid5pt_1024.sweep", "grid5pt_1024.panel8"]),
    ]:
        assert per_layer[name]["workloads"] == cells
        assert per_layer[name]["source"] == "program_counter"
    # the R=1 and the panel reading never both apply to one cell
    assert run.load_reader("h2d_bytes_per_solve.solve")(
        {"rhs_columns": 8, "program": PROGRAM}) is None
    assert run.load_reader("h2d_bytes_per_solve.panel")(
        {"rhs_columns": 1, "program": PROGRAM}) is None


def _ev(name, s, e, **stats):
    return types.SimpleNamespace(name=name, start_ns=s, end_ns=e,
                                 stats=list(stats.items()))


def _pd(host_lines, device_ops):
    line = types.SimpleNamespace
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=xplane.HOST_PLANE, lines=[
            line(name=n, events=evs) for n, evs in host_lines]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            line(name=xplane.OPS_LINE, events=device_ops)]),
    ])


def test_idle_by_span_names_gaps_by_innermost_program_span():
    harness = [_ev(xplane.WINDOW, 0, 100), _ev("sptrsv.solve", 5, 90),
               _ev("sptrsv.fetch", 40, 80), _ev("$array.py:631 _value", 45, 79)]
    runtime = [_ev(xplane_spans.TRANSFER_TO_DEVICE, 10, 12, size=4096),
               _ev(xplane_spans.TRANSFER_TO_DEVICE, 101, 102, size=512),
               _ev(xplane_spans.TRANSFER_TO_DEVICE + "=>IssueEvent", 10, 11)]
    ops = [_ev("%block_trsv.1 = f32[]", 20, 40), _ev("%fusion.2 = f32[]", 80, 85)]
    pd = _pd([("python3", harness), ("pjrt-tpu-tasks/1", runtime)], ops)
    # gaps: 0-20 (mid 10, in solve), 40-80 (mid 60, in fetch: the Python
    # frame inside it is no program span), 85-100 (mid 92.5, after solve)
    got = dict(xplane_spans.idle_by_span(pd))
    assert got == {"sptrsv.solve": 20e-9, "sptrsv.fetch": 40e-9,
                   xplane_spans.OUTSIDE: 15e-9}
    # only the exact runtime event, and only those that start in the window
    assert xplane_spans.h2d_bytes(pd) == 4096


def test_idle_by_span_covers_the_same_gaps_on_a_recorded_chip_trace():
    pd = xplane.load(str(SMALL_TRACE))
    r = xplane.reduce_profile(pd)
    assert len(r["idle_gaps"]) < 10  # not truncated: the totals compare
    by_span = xplane_spans.idle_by_span(pd)
    assert {k for k, _ in by_span} <= {"sptrsv.solve", xplane_spans.OUTSIDE}
    assert sum(v for _, v in by_span) == pytest.approx(
        sum(v for _, v in r["idle_gaps"]), rel=1e-12)
    assert sum(v for _, v in by_span) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


def test_trace_h2d_bytes_match_the_program_counter():
    """The recorded step (one forward and one transpose solve of the 128 x 64
    grid at B = 128) moved what the executor counts for that plan: every
    plan argument and the padded right-hand side, on every solve. The
    runtime pads each small argument to 512 bytes, hence the 0.02%."""
    import numpy as np

    from repro.api import PlanOptions, SpTRSVContext
    from repro.obs.metrics import MetricsRegistry
    from repro.sparse.matrix import CSR

    pd = xplane.load(str(SMALL_TRACE))
    host, thread, w0, w1 = xplane_spans._window(pd, xplane.WINDOW)
    n_solves = sum(ev.name == "sptrsv.solve" and w0 <= ev.start_ns < w1
                   for ev in thread.events)
    assert n_solves == 2
    traced = xplane_spans.h2d_bytes(pd) / n_solves
    assert traced == 8_489_984

    m = matrices.grid_lower(128, 64, seed=1)
    reg = MetricsRegistry()
    ctx = SpTRSVContext(options=PlanOptions(block_size=128), registry=reg)
    h = ctx.analyse(CSR(n=m.n, row_ptr=m.row_ptr, col_idx=m.col_idx, val=m.val))
    b = np.ones(m.n, np.float32)
    ctx.solve(h, ctx.solve(h, b), transpose=True)
    counted = reg.counter("executor.h2d_bytes").value / reg.counter("executor.solves").value
    assert counted == 8_488_196
    assert abs(counted - traced) / traced < 1e-3
