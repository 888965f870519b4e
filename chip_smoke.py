#!/usr/bin/env python3
"""Smoke run of the SpTRSV session path on the TPU.

Builds ``delaunay_n20`` from the Table-I suite at its published size
(n = 2^20 rows, the suite's own seed) and drives the user-facing path —
``SpTRSVContext.analyse`` -> ``solve`` / ``solve(transpose=True)`` through
``DistributedSolver``, and a ``SolveEngine`` — checking every result against
the float64 scipy solve:

    python3 chip_smoke.py              # one chip: phases a-d
    python3 chip_smoke.py --chips 4    # four chips: the boundary exchange only

Phases on one chip:
  a  default options (levelset, zerocopy, the TPU's default backend):
     a forward and a transpose solve
  b  the same with ``kernel="fused"`` (the superstep megakernel, which
     streams its tile store at this size)
  c  one R=8 right-hand-side panel
  d  a SolveEngine serving 8 requests on the pattern

With ``--chips 4`` the matrix is solved on a 4-chip mesh with the malleable
partition, zero-copy exchange and unified exchange each checked.

Each phase prints one line: backend, block size, launches, compile seconds,
first-call seconds (upload of the tile store included), ms per solve and
rel.err = max|x - x_ref| / max|x_ref|. Any phase above
``TOL`` fails the run. It also fails off the TPU, when Pallas runs
interpreted, when a Pallas solve's compiled HLO holds no ``tpu_custom_call``
and when a ticket resolves with an error. The last line of stdout is one
JSON object, ``{"ok": true, "device": {...}}``, printed only on success.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

TOL = 1e-5  # f32 solve vs the f64 reference; a bf16 dot misses it by far
N_ROWS = 2 ** 20
SCALE = 65.6  # suite scale at which delaunay_n20 is 1024 x 1024 = 2^20 rows
REPEATS = 3


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(x: np.ndarray, x_ref: np.ndarray) -> float:
    return float(np.abs(x - x_ref).max() / np.abs(x_ref).max())


def build_matrix():
    from repro.sparse import suite

    entry = {e.name: e for e in suite.table1_suite(SCALE)}["delaunay_n20"]
    a = entry.build()
    check(a.n == N_ROWS, f"delaunay_n20 has n={a.n}, expected {N_ROWS}")
    return a


def reference(a, b: np.ndarray, transpose: bool = False) -> np.ndarray:
    import scipy.sparse.linalg as spla

    from repro.sparse.matrix import to_scipy

    m = to_scipy(a)
    if transpose:
        return spla.spsolve_triangular(m.T.tocsr(), b, lower=False)
    return spla.spsolve_triangular(m.tocsr(), b, lower=True)


def run_phase(name: str, ctx, handle, b: np.ndarray, x_ref: np.ndarray, *,
              transpose: bool = False) -> dict:
    """Compile, make a first call, time REPEATS solves, check the result and
    the compiled program; print the phase line."""
    from repro.kernels import ops

    R = b.shape[1] if b.ndim == 2 else 1
    ex = ctx.executor(handle, transpose=transpose)  # builds the plan
    t0 = time.perf_counter()
    hlo = ex.lower(R).compile().as_text()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ctx.solve(handle, b, transpose=transpose)  # uploads, warms the dispatch
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(REPEATS):
        x = ctx.solve(handle, b, transpose=transpose)  # numpy: device done
    ms = (time.perf_counter() - t0) / REPEATS * 1e3
    err = rel_err(x, x_ref)
    cfg = handle.config
    backend = ops.executor_backend(cfg.kernel_backend)
    ds = ctx.dispatch_stats(handle)
    fused = ops.is_fused(cfg.kernel_backend)
    launches = ds["fused_launches"] if fused else ds["switch_dispatches"]
    line = (f"[smoke] phase={name} backend={backend} B={cfg.block_size} "
            f"devices={ctx.n_devices} R={R} launches={launches} "
            f"streamed={ds['streamed'] if fused else False} "
            f"compile_s={compile_s:.2f} first_call_s={first_s:.2f} "
            f"ms_per_solve={ms:.2f} rel_err={err:.3e}")
    print(line, flush=True)
    check("tpu_custom_call" in hlo,
          f"phase {name}: no tpu_custom_call in the compiled {backend} solve")
    check(np.isfinite(x).all(), f"phase {name}: non-finite solution")
    check(err <= TOL, f"phase {name}: rel.err {err:.3e} > {TOL}")
    return {"phase": name, "rel_err": err, "ms_per_solve": ms}


def one_chip(a, b: np.ndarray, seed: int) -> None:
    from repro.api import PlanOptions, SpTRSVContext
    from repro.kernels import ops
    from repro.service import SolveEngine

    x_ref = reference(a, b)
    xt_ref = reference(a, b, transpose=True)

    ctx = SpTRSVContext()
    h = ctx.analyse(a)
    run_phase("a-forward", ctx, h, b, x_ref)
    run_phase("a-transpose", ctx, h, b, xt_ref, transpose=True)

    fctx = SpTRSVContext(options=PlanOptions(kernel="fused"))
    fh = fctx.analyse(a)
    check(fctx.dispatch_stats(fh)["streamed"],
          "phase b: the fused kernel did not stream at 2^20 rows")
    run_phase("b-forward", fctx, fh, b, x_ref)
    run_phase("b-transpose", fctx, fh, b, xt_ref, transpose=True)
    del fctx, fh

    rng = np.random.default_rng(seed + 1)
    panel = rng.uniform(-1, 1, (a.n, 8))
    run_phase("c-panel", ctx, h, panel, reference(a, panel))
    del ctx, h

    engine = SolveEngine(max_batch=8)
    rhs = [rng.uniform(-1, 1, a.n).astype(np.float32) for _ in range(8)]
    t0 = time.perf_counter()
    tickets = [engine.submit(f"tenant{i % 4}", a, r) for i, r in enumerate(rhs)]
    served = engine.drain()
    wall_s = time.perf_counter() - t0
    errs = []
    for t, r in zip(tickets, rhs):
        try:
            x = t.result(timeout=0)
        except Exception as e:  # the engine turns failures into ticket errors
            raise SmokeFailure(f"phase d: ticket {t.request.id} failed: {e!r}")
        errs.append(rel_err(x, reference(a, r.astype(np.float64))))
    st = engine.stats()
    h = engine.ctx.analyse(a)
    hlo = engine.ctx.executor(h).lower(8).compile().as_text()
    print(f"[smoke] phase=d-engine "
          f"backend={ops.executor_backend(h.config.kernel_backend)} "
          f"B={h.config.block_size} requests={served} batches={st['batches']} "
          f"errors={st.get('errors', 0)} wall_s={wall_s:.2f} "
          f"rel_err={max(errs):.3e}", flush=True)
    check(served == 8 and st.get("errors", 0) == 0, "phase d: not all served")
    check("tpu_custom_call" in hlo, "phase d: no tpu_custom_call in the served solve")
    check(max(errs) <= TOL, f"phase d: rel.err {max(errs):.3e} > {TOL}")


def four_chips(a, b: np.ndarray) -> None:
    from repro import compat
    from repro.api import PlanOptions, SpTRSVContext

    x_ref = reference(a, b)
    mesh = compat.make_mesh((4,), ("x",))
    for comm in ("zerocopy", "unified"):
        ctx = SpTRSVContext(mesh=mesh, options=PlanOptions(
            partition="malleable", comm=comm))
        h = ctx.analyse(a)
        plan = ctx.plan(h)
        print(f"[smoke] comm={comm} boundary_rows={plan.n_boundary_rows} "
              f"comm_bytes_per_solve={plan.comm_bytes_per_solve}", flush=True)
        run_phase(f"4chip-{comm}", ctx, h, b, x_ref)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-chip exchange path")
    ap.add_argument("--seed", type=int, default=0, help="seed of the right-hand sides")
    args = ap.parse_args()
    try:
        from repro.cache import use_compile_cache
    except ImportError as e:
        print(f"[smoke] FAIL: the repro package is not next to this script ({e})",
              file=sys.stderr)
        return 2
    cache = use_compile_cache()

    import jax

    from repro.kernels import ops

    try:
        devs = jax.devices()
        dev = devs[0]
        check(dev.platform == "tpu", f"no TPU: JAX runs on {dev.platform}")
        check(len(devs) >= args.chips,
              f"--chips {args.chips} needs {args.chips} chips, found {len(devs)}")
        check(not ops.interpret_mode(), "Pallas kernels would run interpreted")
        print(f"[smoke] device={dev.device_kind} count={len(devs)} "
              f"jax={jax.__version__} cache={cache}", flush=True)
        t0 = time.perf_counter()
        a = build_matrix()
        b = np.random.default_rng(args.seed).uniform(-1, 1, a.n)
        print(f"[smoke] delaunay_n20 n={a.n} nnz={a.nnz} "
              f"built in {time.perf_counter() - t0:.1f}s", flush=True)
        if args.chips == 4:
            four_chips(a, b)
        else:
            one_chip(a, b, args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
