#!/usr/bin/env python3
"""Chip benchmark of the SpTRSV session path: one cell, one run.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of the
checkout. Its configuration (``chipbench/configs/<config>.json``), its
traffic mix (``chipbench/traffic/<traffic>.json``) and every metric it reports
(``chipbench/metrics/<metric>.py``) are found by name, so a new cell, mix or
metric is a new file and never an edit.

A run builds the matrix from the seed, analyses it and builds its plans
(``SpTRSVContext.analyse``), warms every shape the window uses, and then
drives ``SpTRSVContext.solve`` in a closed loop with one caller, numpy in and
numpy out, for ``--seconds``. Everything before the window is set-up. After
the window the device's peak memory is read, the program's state is freed,
and a sample of the window's answers, drawn from the seed, is compared with
the float64 reference (``chipbench/reference.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
traffic mix's ``trace_steps`` steps under the profiler with the program's own
spans on, and prints the per-layer metrics, the device's busy time and a
breakdown. The last line of stdout is one JSON object; the numbers compared
are the last lines of stderr and the last key of that object.

It exits non-zero and prints no result off the TPU, on fewer chips than the
cell asks for, or where Pallas would run interpreted.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chipbench import matrices, reference, roofline, xplane  # noqa: E402


class NoChip(Exception):
    """The machine cannot run this cell as a measurement."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str):
    """The ``read(run) -> float | None`` of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_spec(bench: dict, workload: str) -> dict:
    """The cell's entry, configuration, traffic and metric specs by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"] if workload in m["workloads"]]
    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": e2e, "per_layer": per_layer}


def check_devices(chips: int):
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    if ops.interpret_mode():
        raise NoChip("Pallas kernels would run interpreted")
    return devs


class Sample:
    """A reservoir of at most ``k`` answers per op, drawn from the seed."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = np.random.default_rng([seed, 2])
        self.seen: dict = {}
        self.kept: dict = {}

    def offer(self, op: str, idx: int, x: np.ndarray) -> None:
        c = self.seen.get(op, 0)
        self.seen[op] = c + 1
        kept = self.kept.setdefault(op, [])
        if c < self.k:
            kept.append((idx, x))
        else:
            j = int(self.rng.integers(0, c + 1))
            if j < self.k:
                kept[j] = (idx, x)


class GCWatch:
    """Garbage collections during the window, by generation: printed to
    stderr with the slowest solves, to find the cause of a solve that
    stalls; no metric reads it."""

    def __enter__(self):
        self.runs, self.seconds, self._t = [0, 0, 0], [0.0, 0.0, 0.0], 0.0
        gc.callbacks.append(self._on_gc)
        return self

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.runs[info["generation"]] += 1
            self.seconds[info["generation"]] += time.perf_counter() - self._t

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self._on_gc)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True) -> dict:
    """One run of one cell. ``require_chip=False`` (the harness's tests on
    the CPU) skips the look for a chip and the persistent compile cache."""
    import jax

    from repro import compat
    from repro.api import PlanOptions, SpTRSVContext
    from repro.cache import use_compile_cache
    from repro.core.solver import AXIS, dispatch_stats
    from repro.kernels import ops as kops
    from repro.obs.trace import configure_tracing
    from repro.sparse.matrix import CSR

    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    chips = int(cell["chips"])
    if require_chip:
        devs = check_devices(chips)
        use_compile_cache()
    else:
        devs = jax.devices()

    # -- set-up: matrix, analysis and plans, one warm call per shape --------
    m = matrices.build(config["matrix"], seed)
    a = CSR(n=m.n, row_ptr=m.row_ptr, col_idx=m.col_idx, val=m.val)
    if traffic.get("loop", "closed") != "closed" or int(traffic.get("clients", 1)) != 1:
        raise ValueError("the harness drives a closed loop with one caller")
    R = int(traffic["rhs_columns"])
    op_list = list(traffic["ops"])
    chain = bool(traffic.get("chain", False))
    rng = np.random.default_rng([seed, 1])
    shape = (m.n,) if R == 1 else (m.n, R)
    pool = [rng.uniform(-1.0, 1.0, shape).astype(np.float32)
            for _ in range(int(traffic["pool"]))]
    mesh = (None if chips == 1
            else compat.make_mesh((chips,), (AXIS,), devices=devs[:chips]))
    ctx = SpTRSVContext(mesh=mesh, options=PlanOptions(**config.get("options", {})))
    t0 = time.perf_counter()
    h = ctx.analyse(a)
    for op in dict.fromkeys(op_list):
        ctx.plan(h, transpose=op == "transpose")
    plan_s = time.perf_counter() - t0

    def step(k: int, sample: Sample | None, lat: list | None) -> int:
        """One step of the mix on pool entry ``k``; returns solves done."""
        b = pool[k % len(pool)]
        for op in op_list:
            t = time.perf_counter()
            x = ctx.solve(h, b, transpose=op == "transpose")
            if lat is not None:
                lat.append((time.perf_counter() - t) * 1e3)
            if sample is not None:
                sample.offer(op, k % len(pool), x)
            if chain:
                b = x
        return len(op_list)

    for _ in range(int(traffic.get("warmup_steps", 1))):
        step(0, None, None)
    setup_s = time.perf_counter() - t_start

    counters = {}
    fused = kops.is_fused(h.config.kernel_backend)
    launches = [dispatch_stats(ctx.plan(h, transpose=op == "transpose"))
                ["fused_launches" if fused else "switch_dispatches"] for op in op_list]
    counters["launches_per_solve"] = float(np.mean(launches))

    # -- the window ---------------------------------------------------------
    sample = Sample(int(traffic["check_per_op"]), seed)
    lat: list = []
    solves = k = 0
    summary = host = None
    if trace:
        from jax.profiler import TraceAnnotation

        configure_tracing(None)
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        try:
            jax.profiler.start_trace(tdir)
            with TraceAnnotation(xplane.WINDOW):
                t_w = time.perf_counter()
                for k in range(int(traffic["trace_steps"])):
                    solves += step(k, sample, lat)
                window_s = time.perf_counter() - t_w
            jax.profiler.stop_trace()
            configure_tracing(enabled=False)
            files = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))
            summary = xplane.reduce_profile(xplane.load(str(files[-1])))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        with GCWatch() as watch:
            t_w = time.perf_counter()
            while time.perf_counter() - t_w < seconds:
                solves += step(k, sample, lat)
                k += 1
            window_s = time.perf_counter() - t_w
        med = float(np.median(lat))
        host = dict(gc_runs=watch.runs, gc_s=watch.seconds,
                    median_solve_ms=med, max_solve_ms=max(lat),
                    solves_over_1_5x_median=sum(t > 1.5 * med for t in lat))

    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    dev = devs[0]
    del ctx, h, step
    gc.collect()

    # -- correctness: the sampled answers against the float64 reference -----
    csr = reference.to_scipy(m)
    refs: dict = {}

    def ref(op: str, idx: int) -> np.ndarray:
        key = (op, idx)
        if key not in refs:
            b = pool[idx]
            if chain:
                for prev in op_list[:op_list.index(op)]:
                    b = ref(prev, idx)
            refs[key] = reference.solve(m, b, op == "transpose", a=csr)
        return refs[key]

    worst, failed = 0.0, 0
    limit = float(config["limits"]["rel_err"])
    for op, kept in sample.kept.items():
        for idx, x in kept:
            err = reference.rel_err(x, ref(op, idx))
            worst = max(worst, err)
            failed += err > limit
    checked = sum(len(v) for v in sample.kept.values())
    correct = bool(checked > 0 and failed == 0 and worst <= limit)

    run = {"setup_s": setup_s, "window_s": window_s, "solves": solves,
           "latencies_ms": lat, "rhs_columns": R, "chips": chips,
           "device_kind": dev.device_kind, "n": m.n, "nnz": m.nnz,
           "timers": {"plan_s": plan_s}, "counters": counters, "trace": summary}
    metrics = {}
    for mspec in spec["per_layer"] if trace else spec["end_to_end"]:
        value = load_reader(mspec["name"])(run)
        if value is None and not trace:
            raise RuntimeError(f"end-to-end metric {mspec['name']} has no reading")
        if value is not None:
            metrics[mspec["name"]] = {"value": float(value), "unit": mspec["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": solves, "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    if host is not None:
        result["host"] = host
    result["checks"] = {
        "rel_err": {"value": worst, "limit": limit},
        "answers_checked": {"value": checked, "limit": 1},
        "answers_failed": {"value": failed, "limit": 0},
    }
    return result


def main(argv: list | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = cell_spec(load_json(ROOT / "BENCHMARK.json"), args.workload)
        import repro  # noqa: F401  the system under test sits beside the benchmark
    except (OSError, KeyError, ImportError) as e:
        print(f"[chipbench] cannot set up {args.workload}: {e!r}", file=sys.stderr)
        return 2
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START)
    except NoChip as e:
        print(f"[chipbench] {e}", file=sys.stderr)
        return 3
    if "host" in result:
        print(f"[chipbench] host {json.dumps(result['host'])}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"[chipbench] check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
