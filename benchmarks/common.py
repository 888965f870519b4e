"""Benchmark utilities: timing, device-count subprocesses, CSV convention.

Every bench prints ``name,us_per_call,derived`` lines (one per measurement);
``derived`` carries the paper-figure quantity (speedup, normalized perf, ...).
Every section runs in a subprocess of its own (one process per chip: a parent
that touched JAX would hold the chip its children need). On the CPU the child
gets ``--xla_force_host_platform_device_count``; on a TPU that flag makes no
chips, so a section fails when fewer devices than it asks for exist.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time


def bench_scale() -> float:
    return float(os.environ.get("REPRO_BENCH_SCALE", "0.1"))


def time_call(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time (us) of fn(*args) with block_until_ready."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def run_with_devices(module: str, n_devices: int, extra_env: dict | None = None) -> str:
    """Run ``python -m <module>`` in a child that needs ``n_devices``
    devices (N forced host devices on the CPU); return its stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.join(os.path.dirname(__file__), ".."),
         env.get("PYTHONPATH", "")]
    )
    env.update(extra_env or {})
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.common", module, str(n_devices)],
        env=env, capture_output=True, text=True, timeout=3000,
    )
    if out.returncode != 0:
        sys.stderr.write(out.stderr[-4000:])
        raise RuntimeError(f"{module} failed")
    return out.stdout


def _child(module: str, n_devices: int) -> None:
    """Subprocess entry of :func:`run_with_devices`."""
    import runpy

    import jax

    from repro.cache import use_compile_cache

    use_compile_cache()
    have = len(jax.devices())
    if have < n_devices:
        raise SystemExit(f"{module} needs {n_devices} devices, found {have} "
                         f"{jax.devices()[0].platform} device(s)")
    runpy.run_module(module, run_name="__main__", alter_sys=True)


def emit(name: str, us: float, derived: str = "") -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


if __name__ == "__main__":
    _child(sys.argv[1], int(sys.argv[2]))
