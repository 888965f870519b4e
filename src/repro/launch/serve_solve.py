"""SpTRSV serving CLI: ``python -m repro.launch.serve_solve [...]``.

Stands up an in-process :class:`repro.service.SolveEngine` and feeds it a
multi-tenant hot/cold request mix: ``--patterns`` distinct synthetic sparsity
patterns, with ``--hot-fraction`` of all requests landing on pattern 0 (the
"hot" preconditioner every iterative solver hammers) and the rest spread over
the cold tail. Reports the serving-axis numbers — solves/sec at the mix,
coalesce width, plan-store hit rate — rather than single-solve latency.

Run it twice against the same ``--plan-store`` directory to see the point of
the subsystem: the first (cold) run pays one symbolic analysis per pattern
and persists the plans; the second (warm) run serves the same mix with
**zero** symbolic analyses, which ``--assert-warm`` turns into a hard exit
code for CI.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from repro import compat
from repro.api import PlanOptions, SpTRSVContext  # noqa: F401  (session API)
from repro.cache import use_compile_cache
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.service import SolveEngine
from repro.sparse import suite
from repro.sparse.matrix import reference_solve


def build_patterns(n_patterns: int, n: int, levels: int, seed: int) -> list:
    """Distinct synthetic lower-triangular patterns, sized down the tail so
    the cold patterns are cheap and the hot one dominates the work."""
    mats = []
    for p in range(n_patterns):
        np_ = max(64, n // (1 + p))
        mats.append(suite.random_levelled(np_, max(4, levels // (1 + p)), 4.0,
                                          seed=seed + p))
    return mats


def request_mix(n_requests: int, n_patterns: int, hot_fraction: float,
                seed: int) -> list[int]:
    """Pattern index per request: ``hot_fraction`` on pattern 0, the rest
    uniform over the cold tail, in a shuffled arrival order."""
    rng = np.random.default_rng(seed)
    n_hot = int(round(n_requests * hot_fraction))
    mix = [0] * n_hot
    if n_patterns > 1:
        mix += [1 + int(rng.integers(n_patterns - 1))
                for _ in range(n_requests - n_hot)]
    else:
        mix += [0] * (n_requests - n_hot)
    rng.shuffle(mix)
    return mix


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--patterns", type=int, default=3,
                    help="distinct sparsity patterns in the mix")
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--hot-fraction", type=float, default=0.7,
                    help="fraction of requests on pattern 0")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--n", type=int, default=512, help="rows of the hot pattern")
    ap.add_argument("--levels", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="coalesced RHS columns per served panel")
    ap.add_argument("--max-wait-ms", type=float, default=0.0,
                    help="admission window before a partial batch dispatches")
    ap.add_argument("--cache-capacity", type=int, default=None,
                    help="LRU bound on the session's compiled-executor cache")
    ap.add_argument("--block-size", type=int, default=None,
                    help="tile size B (default: 128 on the TPU, 32 elsewhere)")
    ap.add_argument("--sched", default="levelset",
                    choices=["levelset", "dagpart", "syncfree", "auto"])
    ap.add_argument("--comm", default="zerocopy",
                    choices=["zerocopy", "unified", "auto"])
    ap.add_argument("--kernel", default="default")
    ap.add_argument("--plan-store", default=None, metavar="DIR",
                    help="persistent plan store (cold run populates it; a "
                         "warm run serves with zero symbolic analyses)")
    ap.add_argument("--assert-warm", action="store_true",
                    help="exit non-zero unless the mix was served with ZERO "
                         "symbolic analyses (requires a populated --plan-store)")
    ap.add_argument("--assert-hit-rate", type=float, default=None,
                    metavar="MIN", help="exit non-zero if the plan-store hit "
                    "rate falls below MIN")
    ap.add_argument("--trace", default=os.environ.get(obs_trace.ENV_TRACE),
                    metavar="PATH.jsonl")
    args = ap.parse_args()
    use_compile_cache()
    if args.trace:
        obs_trace.configure_tracing(args.trace)

    D = len(jax.devices())
    mesh = compat.make_mesh((D,), ("x",))
    opts = PlanOptions(block_size=args.block_size or ops.default_block_size(),
                       sched=args.sched,
                       comm=args.comm, kernel=args.kernel)
    mats = build_patterns(args.patterns, args.n, args.levels, args.seed)
    mix = request_mix(args.requests, args.patterns, args.hot_fraction,
                      args.seed)
    print(f"[serve] D={D} patterns={[m.n for m in mats]} "
          f"requests={args.requests} hot={args.hot_fraction:.0%} "
          f"tenants={args.tenants} max_batch={args.max_batch} "
          f"plan_store={args.plan_store or '-'}")

    engine = SolveEngine(mesh=mesh, options=opts, plan_store=args.plan_store,
                         max_batch=args.max_batch,
                         max_wait_s=args.max_wait_ms / 1e3,
                         cache_capacity=args.cache_capacity)
    rng = np.random.default_rng(args.seed + 1)
    t0 = time.perf_counter()
    tickets = [engine.submit(f"tenant{i % args.tenants}", mats[p],
                             rng.uniform(-1, 1, mats[p].n).astype(np.float32))
               for i, p in enumerate(mix)]
    served = engine.drain()
    wall_s = time.perf_counter() - t0

    # spot-check correctness on a few served tickets against scipy
    for t in tickets[:: max(1, len(tickets) // 8)]:
        x = t.result(timeout=0)
        ref = reference_solve(t.request.matrix, t.request.rhs)
        err = np.abs(x - ref).max() / max(np.abs(ref).max(), 1e-30)
        assert err < 1e-4, f"request {t.request.id}: rel.err {err:.2e}"

    st = engine.stats()
    sess, ps = st["session"], st.get("plan_store", {})
    width = st["coalesced_columns"] / st["batches"] if st["batches"] else 0.0
    lat = sorted(t.latency_s for t in tickets)
    p50, p99 = lat[len(lat) // 2], lat[min(len(lat) - 1, int(len(lat) * .99))]
    print(f"[serve] served {served}/{args.requests} in {wall_s*1e3:.0f}ms: "
          f"{served / wall_s:.0f} req/s via {st['batches']} batches "
          f"({st['solves'] / wall_s:.0f} solves/s, coalesce width {width:.1f}, "
          f"pad {st['pad_columns']} cols)")
    print(f"[serve] latency p50={p50*1e3:.1f}ms p99={p99*1e3:.1f}ms | "
          f"analyses={sess.get('analyses', 0)} "
          f"plan_store_hits={sess.get('plan_store_hits', 0)} "
          f"store hit_rate={ps.get('hit_rate', 0.0):.0%} "
          f"evictions={sess.get('evictions', 0)}")

    tracer = obs_trace.get_tracer()
    if tracer.enabled:
        tracer.write({"type": "metrics",
                      "metrics": engine.registry.snapshot()})
        names = sorted({r["name"] for r in tracer.export()
                        if r.get("type") == "span"})
        print(f"[serve] trace: {len(tracer.export())} records -> "
              f"{tracer.path} (spans: {', '.join(names)})")
        tracer.close()

    if args.assert_warm and sess.get("analyses", 0) != 0:
        print(f"[serve] FAIL: --assert-warm but "
              f"{sess['analyses']} symbolic analyses ran")
        raise SystemExit(2)
    if (args.assert_hit_rate is not None
            and ps.get("hit_rate", 0.0) < args.assert_hit_rate):
        print(f"[serve] FAIL: plan-store hit rate {ps.get('hit_rate', 0.0):.2f} "
              f"< --assert-hit-rate {args.assert_hit_rate}")
        raise SystemExit(2)


if __name__ == "__main__":
    main()
