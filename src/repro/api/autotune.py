"""Backend auto-tuning for the session API.

The right execution mode is matrix-dependent: a chain-skewed factor wants the
fused megakernel's low launch count (and ``dagpart``'s merged supersteps,
which collapse a long run of narrow levels into a handful of grid steps), a
wide shallow DAG wants the syncfree frontier, a heavily cut partition may
prefer unified's dense psum over many packed exchanges. ``PlanOptions`` marks any of ``sched``/``comm``/``kernel``
as ``auto`` and this module resolves them:

1. enumerate the candidate (sched, comm, kernel) combinations — all sharing
   ONE partition, so auto-tuning never re-analyses the pattern;
2. score each candidate plan with the calibrated block-op cost model
   (:mod:`repro.core.costmodel` weights x the plan's bucketized schedule
   widths, plus comm-byte and dispatch-overhead terms);
3. optionally (``probe_solves > 0``) compile each candidate and measure real
   probe solves at the expected RHS width, choosing the measured minimum.

The decision — chosen combination, per-candidate scores/timings, probe
overhead — is recorded as an :class:`AutoDecision` and surfaced through
``SpTRSVContext.dispatch_stats``.
"""
from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np

from repro.core.costmodel import FLOPS_PER_BYTE, calibrate_weights
from repro.core.solver import (
    DistributedSolver,
    Plan,
    dispatch_stats,
    fused_streaming,
    level_widths,
    stream_dma_bytes_per_solve,
)
from repro.kernels import ops
from repro.obs import calibration as _calibration
from repro.obs.trace import get_tracer

# One executor dispatch (gather+kernel launch or collective) costs about this
# many block-op units in the model — the knob that lets launch-bound schedules
# (many tiny levels) prefer the fused path.
DISPATCH_OVERHEAD = 8.0

# Off-TPU the superstep megakernel runs in Pallas interpret mode (pure-Python
# per grid step) — never the fast choice; the model must know what probes
# would measure.
INTERPRET_PENALTY = 100.0

SCHED_CANDIDATES = ("levelset", "dagpart", "syncfree")
COMM_CANDIDATES = ("zerocopy", "unified")


def kernel_candidates() -> tuple:
    """Platform default executor plus the fused megakernel paths (resident
    store and streaming HBM tile store)."""
    return (ops.executor_backend(None), "fused", "fused_streamed")


@dataclasses.dataclass(frozen=True)
class AutoDecision:
    """Record of one auto-tuning pass (kept on the analysis handle)."""

    chosen: tuple  # (sched, comm, kernel)
    mode: str  # "probed" | "modelled"
    scores: dict  # (sched, comm, kernel) -> model score, block-op units
    probe_us: dict  # (sched, comm, kernel) -> measured us/solve ({} unless probed)
    probe_overhead_us: float  # wall time spent probing (compile + measure)
    # (sched, comm, kernel) -> wall time of the candidate's first (compiling)
    # solve, kept OUT of probe_us so the measured ranking never depends on
    # which candidate compiled last ({} unless probed)
    compile_us: dict = dataclasses.field(default_factory=dict)

    def as_derived(self) -> str:
        """Compact ``k=v;...`` form for bench rows / dispatch_stats."""
        sched, comm, kernel = self.chosen
        return (f"sched={sched};comm={comm};kernel={kernel};mode={self.mode};"
                f"probe_overhead_us={self.probe_overhead_us:.0f}")


def plan_work_units(plan: Plan, R: int = 1) -> tuple[float, float, float]:
    """``(su, tu, tf)`` schedule work units for one solve at RHS width R:
    the regressors of the compute term ``w_solve*su + w_tile_mem*tu +
    w_tile_flop*tf``. Shared by :func:`estimate_plan_cost` and the
    calibration feedback recorder so fitted weights mean exactly what the
    scorer multiplies them by."""
    cfg = plan.config
    wid = level_widths(plan) if plan.n_levels else np.zeros((0, 3), np.int64)
    fused = ops.executor_backend(cfg.kernel_backend) in ops.FUSED_BACKENDS
    if cfg.sched != "syncfree" or fused:
        # frontier-bucketed syncfree work is approximated by the same
        # per-level schedule widths the levelset executors dispatch
        n_solve, n_tiles = float(wid[:, 0].sum()), float(wid[:, 1].sum())
    else:
        # dense masked scan: every sweep touches all local rows and tiles
        sweeps = plan.n_supersteps
        n_solve = float(sweeps * plan.local_rows.shape[1])
        n_tiles = float(sweeps * plan.tiles.shape[1])
    return n_solve * R, n_tiles, n_tiles * R


def estimate_plan_cost(plan: Plan, R: int = 1) -> float:
    """Model one solve of ``plan`` in calibrated block-op units.

    Compute term: the bucketized per-level schedule widths (the work the
    executors actually dispatch, not raw row counts) weighted by the
    per-backend TRSV/GEMV weights from :func:`calibrate_weights`. Comm term:
    ``comm_bytes_per_solve`` at the cost model's machine balance, in units of
    one B^2-flop block op. Overhead term: dispatch/launch counts from
    :func:`dispatch_stats` (levelset) or one sweep per superstep (syncfree).
    """
    cfg = plan.config
    B = plan.bs.B
    w_solve, w_tile_mem, w_tile_flop = calibrate_weights(B, backend=cfg.kernel_backend)
    backend = ops.executor_backend(cfg.kernel_backend)
    fused = backend in ops.FUSED_BACKENDS
    su, tu, tf = plan_work_units(plan, R)
    compute = w_solve * su + w_tile_mem * tu + w_tile_flop * tf
    if cfg.sched != "syncfree":
        ds = dispatch_stats(plan)
        launches = (ds["fused_launches"] if fused
                    else ds["switch_dispatches"]) + ds["exchanges"]
    else:
        launches = 2 * plan.n_supersteps  # one solve + one update dispatch per sweep
    comm = plan.comm_bytes_per_solve * FLOPS_PER_BYTE / (B * B)
    # streaming buys bounded VMEM residency with per-level HBM DMA bursts;
    # score those bytes at the machine balance like the collective payload
    # (fused_streaming also covers plain "fused" auto-upgraded past the
    # VMEM limit, so the model prices what would actually execute)
    dma = 0.0
    if fused and fused_streaming(plan, R):
        dma = stream_dma_bytes_per_solve(plan) * FLOPS_PER_BYTE / (B * B)
    cost = compute + comm + dma + DISPATCH_OVERHEAD * launches
    if fused and cfg.sched != "syncfree" and ops.interpret_mode():
        cost *= INTERPRET_PENALTY
    return cost


def candidate_grid(options, n_devices: int | None = None) -> list:
    """All concrete (sched, comm, kernel) combos for ``options``' auto dims.

    On one device comm is vacuous (no collectives execute), so an auto comm
    axis collapses to zerocopy instead of probing the same program twice.
    """
    from repro.api.options import Comm, KernelBackend, Sched

    scheds = SCHED_CANDIDATES if options.sched == Sched.AUTO else (options.sched.value,)
    comms = COMM_CANDIDATES if options.comm == Comm.AUTO else (options.comm.value,)
    if n_devices == 1 and options.comm == Comm.AUTO:
        comms = ("zerocopy",)
    kernels = (kernel_candidates() if options.kernel == KernelBackend.AUTO
               else (options.kernel.value,))
    return list(itertools.product(scheds, comms, kernels))


def tune(a, options, mesh, *, part=None, bs=None, registry=None):
    """Resolve ``options``' auto dimensions for matrix ``a`` on ``mesh``.

    Returns ``(config, plan, decision, solver)`` — the winning concrete
    :class:`SolverConfig`, its plan (built on the shared partition), the
    :class:`AutoDecision`, and, when probing compiled the winner anyway, its
    ready-to-use :class:`DistributedSolver` (else ``None``), whose
    instruments record in ``registry`` (default: the process-wide one).
    """
    from repro.core.blocking import build_blocks, pad_rhs
    from repro.core.partition import make_partition

    D = int(mesh.devices.size)
    if bs is None:
        bs = build_blocks(a, options.block_size)
    if part is None:
        part = make_partition(bs, D, options.partition.value,
                              options.tasks_per_device, cost_R=options.rhs_hint)
    combos = candidate_grid(options, D)
    from repro.core.solver import build_plan

    plans, scores = {}, {}
    with get_tracer().span("sptrsv.autotune", n_candidates=len(combos),
                           probe_solves=options.probe_solves) as tspan:
        for combo in combos:
            sched, comm, kernel = combo
            if kernel == "fused_streamed" and (sched, comm, "fused") in plans:
                # drop combos that resolve to a byte-identical executor as an
                # already-enumerated candidate — same principle as the comm
                # collapse above, never compile/probe the same program twice:
                # syncfree defines fused_streamed == fused, and a levelset plan
                # past the VMEM limit auto-streams plain "fused" anyway
                if sched == "syncfree" or fused_streaming(
                        plans[(sched, comm, "fused")], options.rhs_hint):
                    continue
            cfg = options.to_config(sched=sched, comm=comm, kernel=kernel)
            plans[combo] = build_plan(a, D, cfg, part=part)
            scores[combo] = estimate_plan_cost(plans[combo], R=options.rhs_hint)
        combos = [c for c in combos if c in plans]

        probe_us: dict = {}
        compile_us: dict = {}
        best = None  # the fastest probed executor, the only one kept
        t_probe0 = time.perf_counter()
        if options.probe_solves > 0 and len(combos) > 1:
            import jax
            import jax.numpy as jnp

            rng = np.random.default_rng(0)
            R = options.rhs_hint
            b = rng.uniform(-1, 1, (a.n, R) if R > 1 else a.n).astype(np.float32)
            b_blocks = jnp.asarray(pad_rhs(b, bs))
            store = _calibration.get_store()
            chosen = None
            for combo in combos:
                with get_tracer().span("sptrsv.probe", sched=combo[0],
                                       comm=combo[1], kernel=combo[2]) as sp:
                    solver = DistributedSolver(plans[combo], mesh,
                                               registry=registry)
                    # the first solve pays compilation: record it separately and
                    # follow with an untimed warmup so the measured ranking never
                    # depends on which candidate happened to compile last
                    t_c = time.perf_counter()
                    jax.block_until_ready(solver.solve_blocks(b_blocks))
                    compile_us[combo] = (time.perf_counter() - t_c) * 1e6
                    jax.block_until_ready(solver.solve_blocks(b_blocks))
                    times = []
                    for _ in range(options.probe_solves):
                        t0 = time.perf_counter()
                        jax.block_until_ready(solver.solve_blocks(b_blocks))
                        times.append(time.perf_counter() - t0)
                    times.sort()
                    probe_us[combo] = times[len(times) // 2] * 1e6
                    sp.set(probe_us=probe_us[combo], compile_us=compile_us[combo])
                # feedback loop: the measured solve is a wall-clock sample of the
                # cost model's compute term — persist it for probe-free sessions
                su, tu, tf = plan_work_units(plans[combo], R)
                store.record(
                    backend=ops.executor_backend(combo[2]), B=plans[combo].bs.B,
                    signature=_calibration.probe_signature(plans[combo], R),
                    solve_units=su, tile_units=tu, tile_flop_units=tf, R=R,
                    measured_us=probe_us[combo],
                )
                # every executor holds its plan's store on the mesh: keep only
                # the fastest so far, so at most two are resident at once
                if chosen is None or probe_us[combo] < probe_us[chosen]:
                    chosen, best = combo, solver
                del solver
            mode = "probed"
        else:
            chosen = min(combos, key=lambda c: scores[c])
            mode = "modelled"
        overhead = (time.perf_counter() - t_probe0) * 1e6 if probe_us else 0.0
        decision = AutoDecision(chosen=chosen, mode=mode, scores=scores,
                                probe_us=probe_us, probe_overhead_us=overhead,
                                compile_us=compile_us)
        tspan.set(chosen="/".join(chosen), mode=mode,
                  probe_overhead_us=overhead)
    cfg = options.to_config(sched=chosen[0], comm=chosen[1], kernel=chosen[2])
    return cfg, plans[chosen], decision, best
