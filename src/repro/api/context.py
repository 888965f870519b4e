"""The analyse/factorize/solve session front door.

The paper's pipeline is explicitly staged: symbolic dependency analysis and
partitioning happen ONCE per sparsity pattern, then many numeric solves
amortize it. :class:`SpTRSVContext` is that lifecycle as an object:

* **analyse** — block structure + levels + partition + compacted schedules,
  keyed by a sparsity-*pattern* hash x options. The symbolic analysis is
  shared across every handle on the same pattern (a matrix and its zero-fill
  factor, or ILU's L and reversed-U on a symmetric pattern, partition
  exactly once); distinct numeric contents get distinct *handles* via
  ``tag`` so one factorization can never clobber another's values.
* **factorize** — numeric tile/diagonal refresh into the existing plan
  (:func:`repro.core.solver.refresh_plan`): ILU-style refactorization changes
  values, never structure, so compiled executors are retained and re-armed
  with the new arrays — zero re-partitioning, zero retracing.
* **solve** — cached compiled executors keyed by pattern x options x RHS
  width x transpose. The L and L^T/U sweeps of a preconditioner share one
  analysis: the transpose executor is a lazy extension of the same handle.

Auto mode (:class:`repro.api.options.PlanOptions` with ``sched``/``comm``/
``kernel`` set to ``"auto"``) resolves the execution mode per matrix at
analyse time via :mod:`repro.api.autotune`; the decision is recorded on the
handle and reported by :meth:`SpTRSVContext.dispatch_stats`.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import time

import jax
import numpy as np

from repro import compat
from repro.api import autotune
from repro.api.options import KernelBackend, PlanOptions, as_options
from repro.obs.metrics import MetricsRegistry, get_registry, record_plan_metrics
from repro.obs.trace import get_tracer
from repro.core.blocking import BlockStructure, build_blocks
from repro.core.partition import Partition, make_partition
from repro.core.solver import (
    AXIS,
    DistributedSolver,
    Plan,
    SolverConfig,
    build_plan,
    dispatch_stats,
    refresh_plan,
)
from repro.sparse.matrix import CSR


def pattern_key(a: CSR) -> str:
    """Hash of the exact scalar sparsity pattern (structure only, no values)."""
    h = hashlib.sha1()
    h.update(np.int64(a.n).tobytes())
    h.update(np.ascontiguousarray(a.row_ptr, dtype=np.int64).tobytes())
    h.update(np.ascontiguousarray(a.col_idx, dtype=np.int32).tobytes())
    return h.hexdigest()[:16]


@dataclasses.dataclass
class _Symbolic:
    """The per-pattern analysis every handle on that pattern shares."""

    bs: BlockStructure
    part: Partition
    # auto-tuning is a property of (pattern, options), not of the numeric
    # content: one tuner pass serves every tagged handle on this analysis
    tuned: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class SpTRSVHandle:
    """One numeric factorization on one analysed pattern (opaque to callers).

    References the shared symbolic analysis (block structure, partition) and
    owns the current numeric plans (forward; transpose built lazily so
    L^T/U solves share the analysis), the compiled executors, and the
    auto-tuning decision.
    """

    pattern: str
    tag: str
    options: PlanOptions
    config: SolverConfig  # resolved (post-auto) engine config
    matrix: CSR  # current numeric values on this pattern
    symbolic: _Symbolic
    plan: Plan | None = None  # forward plan (lazy unless auto probing built it)
    tplan: Plan | None = None  # transpose plan (lazy)
    auto: autotune.AutoDecision | None = None
    solvers: dict = dataclasses.field(default_factory=dict)  # transpose -> solver
    shapes: set = dataclasses.field(default_factory=set)  # (transpose, R) compiled
    n_factorize: int = 0
    plan_store_hit: bool = False  # analysis came from the persistent store

    @property
    def part(self) -> Partition:
        return self.symbolic.part

    @property
    def bs(self) -> BlockStructure:
        return self.symbolic.bs


class SpTRSVContext:
    """Analyse-once / factorize-cheaply / solve-many session over one mesh.

    ``options`` set the session default; ``analyse``/``factorize`` accept
    per-call overrides. Counters (:meth:`stats`) audit the amortization:
    ``analyses`` counts real partition/schedule constructions (shared-pattern
    handles do NOT re-count), ``solves`` the executor invocations, and the
    cache hit rate covers re-analyse calls and executor/shape reuse.

    ``plan_store`` (a :class:`repro.service.planstore.PlanStore`, duck-typed)
    makes ``analyse`` consult the persistent store before running a symbolic
    analysis — a warm worker serves without a single partition/schedule
    construction (``plan_store_hits``, not ``analyses``) — and persists every
    freshly built plan. ``cache_capacity`` bounds the handle/executor cache
    LRU-style: the least-recently-used entry (its compiled executors with it)
    is dropped past the capacity, counted under ``session.evictions``.
    """

    def __init__(self, mesh: jax.sharding.Mesh | None = None,
                 options: PlanOptions | SolverConfig | None = None,
                 registry: MetricsRegistry | None = None,
                 plan_store=None, cache_capacity: int | None = None):
        self.mesh = mesh if mesh is not None else compat.make_mesh((1,), (AXIS,))
        self.options = as_options(options)
        self.registry = registry if registry is not None else get_registry()
        self.plan_store = plan_store
        if cache_capacity is not None and cache_capacity < 1:
            raise ValueError("cache_capacity must be >= 1 (or None: unbounded)")
        self.cache_capacity = cache_capacity
        self._entries: collections.OrderedDict[tuple, SpTRSVHandle] = \
            collections.OrderedDict()
        self._symbolic: dict[tuple, _Symbolic] = {}
        self._counters: collections.Counter = collections.Counter()

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    # -- cache bookkeeping ------------------------------------------------

    def _evict(self) -> None:
        # LRU bound on compiled state: handles (and their executors) drop
        # oldest-first; the cheap symbolic cache is deliberately retained so
        # a re-analysed pattern only recompiles, never re-partitions
        while (self.cache_capacity is not None
               and len(self._entries) > self.cache_capacity):
            self._entries.popitem(last=False)
            self._counters["evictions"] += 1
            self.registry.counter("session.evictions").inc()

    def _store_save(self, handle: SpTRSVHandle, plan: Plan) -> None:
        """Persist a freshly built plan; a read-only or full store degrades
        to no persistence, never to a failed solve."""
        if self.plan_store is None:
            return
        try:
            self.plan_store.save(plan, pattern=handle.pattern,
                                 options=handle.options)
        except Exception:
            self._counters["plan_store_save_errors"] += 1
            self.registry.counter("session.plan_store_save_errors").inc()

    # -- analyse ----------------------------------------------------------

    def _symbolic_key(self, pattern: str, opts: PlanOptions) -> tuple:
        # everything the partition construction reads; the kernel backend
        # only matters when it feeds calibrated malleable cost weights
        kernel = (opts.kernel.value
                  if opts.calibrate_cost else None)
        return (pattern, opts.block_size, opts.partition.value,
                opts.tasks_per_device, opts.rhs_hint, opts.calibrate_cost, kernel)

    def _analyse_symbolic(self, a: CSR, pattern: str, opts: PlanOptions) -> _Symbolic:
        key = self._symbolic_key(pattern, opts)
        sym = self._symbolic.get(key)
        if sym is not None:
            # a new handle (new tag / exec options) reusing the expensive
            # symbolic analysis is a cache hit the amortization stats must see
            self._counters["symbolic_hits"] += 1
            self.registry.counter("session.symbolic_hits").inc()
            return sym
        self._counters["analyses"] += 1
        self.registry.counter("session.analyses").inc()
        bs = build_blocks(a, opts.block_size)
        cost_weights = None
        if opts.calibrate_cost and opts.partition.value == "malleable":
            from repro.core.costmodel import calibrate_weights

            backend = (None if opts.kernel in (KernelBackend.AUTO, KernelBackend.DEFAULT)
                       else opts.kernel.value)
            cost_weights = calibrate_weights(opts.block_size, backend=backend)
        part = make_partition(bs, self.n_devices, opts.partition.value,
                              opts.tasks_per_device, cost_weights=cost_weights,
                              cost_R=opts.rhs_hint)
        sym = _Symbolic(bs=bs, part=part)
        self._symbolic[key] = sym
        return sym

    def analyse(self, a: CSR, options: PlanOptions | SolverConfig | None = None,
                *, tag: str = "") -> SpTRSVHandle:
        """Symbolic analysis of ``a``'s sparsity pattern (cached).

        The block structure and partition are computed once per pattern and
        shared; under auto options the backend tuner runs here (candidates
        share the one partition). ``tag`` names the numeric content: handles
        with different tags on the same pattern share the analysis but hold
        independent values (e.g. a matrix and its incomplete factor). The
        returned handle carries ``a``'s values until the next
        :meth:`factorize`.
        """
        opts = as_options(options) if options is not None else self.options
        pat = pattern_key(a)
        key = (pat, opts, tag)
        hit = self._entries.get(key)
        if hit is not None:
            self._counters["analysis_hits"] += 1
            self.registry.counter("session.analysis_hits").inc()
            self._entries.move_to_end(key)
            if hit.matrix is not a and not np.array_equal(hit.matrix.val, a.val):
                # same pattern, new numeric values: the analysis is a cache
                # hit but the values must not go stale — refresh in place
                self.factorize(a, hit)
            return hit
        with get_tracer().span("sptrsv.analyse", pattern=pat, tag=tag,
                               n=int(a.n), n_devices=self.n_devices) as span:
            plan = None
            if (self.plan_store is not None
                    and self._symbolic_key(pat, opts) not in self._symbolic):
                plan = self.plan_store.load(a, self.n_devices, opts)
            stored = plan is not None
            if stored:
                # persistent-store hit: the whole symbolic analysis — and the
                # resolved config, auto dimensions included — arrives
                # pre-built, value-hydrated against ``a``, and verified;
                # no partition/schedule construction runs at all
                sym = _Symbolic(bs=plan.bs, part=plan.part)
                config, decision, solver = plan.config, None, None
                if opts.is_auto:
                    sym.tuned[opts] = (config, None)
                self._symbolic[self._symbolic_key(pat, opts)] = sym
                self._counters["plan_store_hits"] += 1
                self.registry.counter("session.plan_store_hits").inc()
                span.set(plan_store_hit=True, sched=config.sched)
            elif opts.is_auto:
                sym = self._analyse_symbolic(a, pat, opts)
                tuned = sym.tuned.get(opts)
                if tuned is not None:
                    # another handle on this analysis already paid the tuner
                    # cost (candidate plans + probes) — reuse its decision
                    config, decision = tuned
                    plan, solver = None, None
                    self._counters["auto_reuses"] += 1
                else:
                    config, plan, decision, solver = autotune.tune(
                        a, opts, self.mesh, bs=sym.bs, part=sym.part,
                        registry=self.registry)
                    sym.tuned[opts] = (config, decision)
                span.set(sched=config.sched, comm=config.comm,
                         kernel=config.kernel_backend or "default")
            else:
                sym = self._analyse_symbolic(a, pat, opts)
                config = opts.to_config()
                plan, decision, solver = None, None, None
        handle = SpTRSVHandle(pattern=pat, tag=tag, options=opts, config=config,
                              matrix=a, symbolic=sym, plan=plan, auto=decision,
                              plan_store_hit=stored)
        if solver is not None:  # probing already compiled the winner
            handle.solvers[False] = solver
            handle.shapes.add((False, opts.rhs_hint))
        if not stored and plan is not None:
            self._store_save(handle, plan)  # tuner already built the winner
        self._entries[key] = handle
        self._evict()
        return handle

    # -- factorize --------------------------------------------------------

    def factorize(self, a: CSR, handle: SpTRSVHandle | None = None,
                  options: PlanOptions | SolverConfig | None = None,
                  *, tag: str = "") -> SpTRSVHandle:
        """Numeric refresh: install ``a``'s values into an existing analysis.

        ``a`` must share the handle's exact sparsity pattern (checked by
        hash). Existing plans are value-refreshed and live executors re-armed
        without recompiling; with no handle given, the (pattern, options,
        tag) entry is looked up and analysed first if unseen.
        """
        if handle is None:
            opts = as_options(options) if options is not None else self.options
            handle = self._entries.get((pattern_key(a), opts, tag))
            if handle is None:
                handle = self.analyse(a, opts, tag=tag)
                self._counters["factorizes"] += 1
                handle.n_factorize += 1
                return handle
        else:
            # an explicit handle IS the target entry: options/tag that don't
            # match it would be silently ignored — reject the conflict
            if options is not None and as_options(options) != handle.options:
                raise ValueError(
                    "factorize: options conflict with the given handle's — "
                    "pass either a handle or options, not both"
                )
            if tag and tag != handle.tag:
                raise ValueError(
                    f"factorize: tag {tag!r} conflicts with the given "
                    f"handle's tag {handle.tag!r}"
                )
            if pattern_key(a) != handle.pattern:
                raise ValueError(
                    "factorize: sparsity pattern differs from the analysed "
                    "one — numeric refresh requires an identical pattern; "
                    "call analyse() for a new pattern"
                )
        self._counters["factorizes"] += 1
        self.registry.counter("session.factorizes").inc()
        handle.n_factorize += 1
        handle.matrix = a
        with get_tracer().span("sptrsv.factorize", pattern=handle.pattern,
                               tag=handle.tag, n_factorize=handle.n_factorize):
            if handle.plan is not None:
                handle.plan = refresh_plan(handle.plan, a)
                if False in handle.solvers:
                    handle.solvers[False].refresh(handle.plan)
            if handle.tplan is not None:
                handle.tplan = refresh_plan(handle.tplan, a)
                if True in handle.solvers:
                    handle.solvers[True].refresh(handle.tplan)
        return handle

    # -- solve ------------------------------------------------------------

    def solve(self, handle: SpTRSVHandle | CSR, b: np.ndarray, *,
              transpose: bool = False) -> np.ndarray:
        """Solve ``L x = b`` (or ``L^T x = b``) with the cached executor.

        ``b`` is ``(n,)`` or an ``(n, R)`` panel. Executors are cached per
        (pattern, options, tag, transpose); each (..., RHS width) combination
        compiles once and is a cache hit afterwards.
        """
        if isinstance(handle, CSR):
            handle = self.analyse(handle)
        key = (handle.pattern, handle.options, handle.tag)
        if key in self._entries:  # LRU: a served handle is recently used
            self._entries.move_to_end(key)
        solver = self.executor(handle, transpose=transpose)
        b = np.asarray(b)
        R = b.shape[1] if b.ndim == 2 else 1
        shape = (transpose, R)
        if shape in handle.shapes:
            self._counters["solve_cache_hits"] += 1
            self.registry.counter("session.solve_cache_hits").inc()
        else:
            self._counters["solve_cache_misses"] += 1
            self.registry.counter("session.solve_cache_misses").inc()
            handle.shapes.add(shape)
        self._counters["solves"] += 1
        self.registry.counter("session.solves").inc()
        # the span (and the per-solve wall-clock histogram) covers host-side
        # dispatch + device execution of the already-compiled program; the
        # tracer never enters traced computation, so results are bit-identical
        # with tracing on or off
        with get_tracer().span("sptrsv.solve", pattern=handle.pattern,
                               tag=handle.tag, transpose=transpose, R=R):
            t0 = time.perf_counter()
            x = solver.solve(b)
            self.registry.histogram("session.solve_us").observe(
                (time.perf_counter() - t0) * 1e6)
        return x

    def executor(self, handle: SpTRSVHandle, *, transpose: bool = False
                 ) -> DistributedSolver:
        """The compiled :class:`DistributedSolver` for one sweep direction,
        building plan + executor lazily on first use (the transpose executor
        is an extension of the same analysis, not a second one)."""
        solver = handle.solvers.get(transpose)
        if solver is None:
            solver = DistributedSolver(self.plan(handle, transpose=transpose),
                                       self.mesh, registry=self.registry)
            handle.solvers[transpose] = solver
        return solver

    def plan(self, handle: SpTRSVHandle, *, transpose: bool = False) -> Plan:
        """Current numeric plan for the handle (forward plans reuse the
        analysis partition; transpose plans analyse the reversed structure
        once, lazily)."""
        if transpose:
            if handle.tplan is None:
                if self.plan_store is not None:
                    handle.tplan = self.plan_store.load(
                        handle.matrix, self.n_devices, handle.options,
                        transpose=True)
                if handle.tplan is not None:
                    self._counters["plan_store_hits"] += 1
                    self.registry.counter("session.plan_store_hits").inc()
                else:
                    handle.tplan = build_plan(handle.matrix, self.n_devices,
                                              handle.config, transpose=True,
                                              verify=handle.options.verify)
                    self._counters["transpose_extensions"] += 1
                    self._store_save(handle, handle.tplan)
            return handle.tplan
        if handle.plan is None:
            handle.plan = build_plan(handle.matrix, self.n_devices,
                                     handle.config, part=handle.part,
                                     verify=handle.options.verify)
            self._store_save(handle, handle.plan)
        return handle.plan

    # -- introspection ----------------------------------------------------

    def dispatch_stats(self, handle: SpTRSVHandle) -> dict:
        """Core dispatch counts for the handle's forward plan, plus the
        recorded auto-tuning decision when auto mode ran."""
        stats = dict(dispatch_stats(self.plan(handle)))
        stats["plan_store_hit"] = handle.plan_store_hit
        if handle.auto is not None:
            d = handle.auto
            stats["auto"] = {
                "chosen": d.chosen, "mode": d.mode,
                "scores": dict(d.scores), "probe_us": dict(d.probe_us),
                "compile_us": dict(d.compile_us),
                "probe_overhead_us": d.probe_overhead_us,
            }
        return stats

    def stats(self) -> dict:
        """Counter snapshot incl. the cache hit rate over analyse + solve
        (symbolic-analysis reuse across handles counts as hits too)."""
        c = dict(self._counters)
        hits = (c.get("analysis_hits", 0) + c.get("solve_cache_hits", 0)
                + c.get("symbolic_hits", 0) + c.get("plan_store_hits", 0))
        misses = c.get("analyses", 0) + c.get("solve_cache_misses", 0)
        c["cache_hit_rate"] = hits / (hits + misses) if hits + misses else 0.0
        return c

    def metrics_snapshot(self, handle: SpTRSVHandle | None = None) -> dict:
        """One JSON-safe view over the session's registry: runtime counters
        and the solve wall-clock histogram, the derived cache hit rate, and —
        given a handle — that handle's plan-static dispatch/cut gauges plus
        recorded auto probe/compile timings (mirrored into the registry so a
        single sink sees everything)."""
        self.registry.gauge("session.cache_hit_rate").set(
            self.stats()["cache_hit_rate"])
        if handle is not None:
            record_plan_metrics(self.registry, self.plan(handle))
            if handle.auto is not None:
                d = handle.auto
                self.registry.gauge("auto.probe_overhead_us").set(
                    d.probe_overhead_us)
                for combo, us in d.probe_us.items():
                    self.registry.gauge(
                        "auto.probe_us." + "/".join(combo)).set(us)
                for combo, us in d.compile_us.items():
                    self.registry.gauge(
                        "auto.compile_us." + "/".join(combo)).set(us)
        return self.registry.snapshot()
