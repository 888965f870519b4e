"""Unified telemetry layer: spans, metrics registry, calibration feedback.

Covers the ISSUE-6 acceptance points: deterministic span nesting/ordering,
metrics snapshots reconciling field-for-field with ``dispatch_stats`` /
``cut_stats``, bit-identical solves with tracing on vs off across all kernel
backends (no retrace when toggling), and the calibration round-trip — probed
samples persisted, reloaded, and fitted weights applied by a probe-free
``calibrate_weights`` call.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import strategies as st
from repro.api import PlanOptions, SpTRSVContext
from repro.api.autotune import plan_work_units, tune
from repro.core.costmodel import calibrate_weights, hlo_weights
from repro.core.partition import cut_stats
from repro.core.solver import DistributedSolver, build_plan, dispatch_stats
from repro.kernels import ops
from repro.obs import calibration as cal
from repro.obs import metrics as met
from repro.obs import trace as tr
from repro.sparse import suite


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test gets a pristine global tracer/registry/calibration store."""
    tr.configure_tracing(enabled=False)
    met.get_registry().clear()
    cal.set_store(cal.CalibrationStore())
    yield
    tr.configure_tracing(enabled=False)
    met.get_registry().clear()
    cal.set_store(None)


def small_problem(n=120, levels=6, seed=3):
    a = st.dyadic(suite.random_levelled(n, levels, 4.0, seed=seed))
    b = st.dyadic_rhs(a.n, seed=seed + 1)
    return a, b


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering_deterministic(tmp_path):
    path = str(tmp_path / "t.jsonl")
    a, b = small_problem()
    with tr.trace_to(path) as tracer:
        ctx = SpTRSVContext(mesh=st.mesh1())
        h = ctx.analyse(a)
        ctx.solve(h, b)
        recs = tracer.export()
    spans = {r["id"]: r for r in recs if r["type"] == "span"}
    by_name = {}
    for r in spans.values():
        by_name.setdefault(r["name"], []).append(r)
    for name in ("sptrsv.analyse", "sptrsv.partition", "sptrsv.schedule",
                 "sptrsv.solve"):
        assert name in by_name, name
    # ids are the open order: analyse opens before its children. The
    # partition is built inside analyse; the schedule is built lazily at the
    # first solve (plan construction is deferred outside auto mode), so it is
    # a top-level span here.
    analyse = by_name["sptrsv.analyse"][0]
    child = by_name["sptrsv.partition"][0]
    assert child["parent"] == analyse["id"]
    assert child["id"] > analyse["id"]
    assert by_name["sptrsv.schedule"][0]["parent"] is None
    assert by_name["sptrsv.solve"][0]["parent"] is None
    # JSONL sink carries the same records, one valid object per line, in
    # close order (children before parents); ids reconstruct the open order
    lines = [json.loads(line) for line in open(path)]
    line_ids = [r["id"] for r in lines if r["type"] == "span"]
    assert line_ids == [r["id"] for r in recs if r["type"] == "span"]
    assert sorted(line_ids) == list(range(len(line_ids)))


def test_factorize_and_refresh_spans():
    a, b = small_problem()
    a2 = st.dyadic(a, seed=9)  # same pattern, new values
    with tr.trace_to() as tracer:
        ctx = SpTRSVContext(mesh=st.mesh1())
        h = ctx.analyse(a)
        ctx.solve(h, b)
        ctx.factorize(a2, h)
        names = {r["name"] for r in tracer.export()}
    assert "sptrsv.factorize" in names
    assert "sptrsv.refresh" in names  # refresh_plan ran under the factorize


def test_disabled_tracer_is_shared_noop():
    tracer = tr.get_tracer()
    assert tracer is tr.NULL_TRACER and not tracer.enabled
    s1, s2 = tracer.span("a", x=1), tracer.span("b")
    assert s1 is s2  # the shared null span: no allocation per call
    with s1 as s:
        assert s.set(anything=True) is s
    assert tracer.export() == []


def test_trace_to_restores_previous_tracer():
    before = tr.get_tracer()
    with tr.trace_to() as tracer:
        assert tr.get_tracer() is tracer
    assert tr.get_tracer() is before


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_instrument_types_and_snapshot(tmp_path):
    reg = met.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.gauge("g").set(2.5)
    for v in (10.0, 30.0):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["c"] == 3
    assert snap["g"] == 2.5
    assert snap["h"] == {"count": 2, "sum": 40.0, "min": 10.0, "max": 30.0,
                         "mean": 20.0, "last": 30.0}
    with pytest.raises(TypeError):
        reg.gauge("c")
    path = str(tmp_path / "m.jsonl")
    written = reg.dump(path)
    rec = json.loads(open(path).read())
    assert rec["type"] == "metrics" and rec["metrics"] == written == snap


def test_plan_metrics_match_dispatch_and_cut_stats():
    a, _ = small_problem()
    plan = build_plan(a, 2)  # host-built D=2 plan: no devices needed
    reg = met.MetricsRegistry()
    met.record_plan_metrics(reg, plan)
    snap = reg.snapshot()
    ds = dispatch_stats(plan)
    for k, v in ds.items():
        assert snap[f"plan.{k}"] == (int(v) if isinstance(v, bool) else v), k
    cs = cut_stats(plan.bs, plan.part)
    assert snap["plan.boundary_rows"] == cs.boundary_rows
    assert snap["plan.boundary_fraction"] == pytest.approx(cs.boundary_fraction)
    assert snap["plan.level_cost_imbalance"] == pytest.approx(
        cs.level_cost_imbalance)
    assert snap["plan.comm_bytes_per_solve"] == plan.comm_bytes_per_solve
    assert snap["plan.n_boundary_rows"] == plan.n_boundary_rows


def test_context_metrics_snapshot_counters_and_histogram():
    a, b = small_problem()
    ctx = SpTRSVContext(mesh=st.mesh1(), registry=met.MetricsRegistry())
    h = ctx.analyse(a)
    for _ in range(3):
        ctx.solve(h, b)
    snap = ctx.metrics_snapshot(h)
    assert snap["session.analyses"] == 1
    assert snap["session.solves"] == 3
    assert snap["session.solve_cache_misses"] == 1
    assert snap["session.solve_cache_hits"] == 2
    assert snap["session.solve_us"]["count"] == 3
    assert snap["session.solve_us"]["min"] > 0
    assert snap["session.cache_hit_rate"] == ctx.stats()["cache_hit_rate"]
    assert snap["plan.n_levels"] == ctx.plan(h).n_levels


# ---------------------------------------------------------------------------
# tracing on/off: bit-identity and no retrace
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ops.BACKENDS)
def test_solves_bit_identical_tracing_on_vs_off(backend):
    a, b = small_problem()
    assert st.exactness_holds(a, b)
    opts = PlanOptions(kernel=backend, block_size=16)
    tr.configure_tracing(enabled=False)
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts)
    x_off = ctx.solve(ctx.analyse(a), b)
    with tr.trace_to() as tracer:
        ctx2 = SpTRSVContext(mesh=st.mesh1(), options=opts)
        x_on = ctx2.solve(ctx2.analyse(a), b)
        assert {r["name"] for r in tracer.export()} >= {"sptrsv.solve"}
    np.testing.assert_array_equal(np.asarray(x_off), np.asarray(x_on))


def test_toggling_tracing_does_not_retrace():
    a, b = small_problem()
    ctx = SpTRSVContext(mesh=st.mesh1())
    h = ctx.analyse(a)
    ctx.solve(h, b)
    jitted = ctx.executor(h)._jitted
    if not hasattr(jitted, "_cache_size"):
        pytest.skip("jit cache size introspection unavailable")
    size = jitted._cache_size()
    with tr.trace_to():
        ctx.solve(h, b)
    ctx.solve(h, b)
    assert jitted._cache_size() == size  # same trace served all three


# ---------------------------------------------------------------------------
# executor phases, host-to-device bytes and compile accounting
# ---------------------------------------------------------------------------

PHASES = ("sptrsv.stage_in", "sptrsv.launch", "sptrsv.fetch",
          "sptrsv.stage_out")


def host_arg_bytes(solver):
    return sum(x.nbytes for x in jax.tree.leaves(solver._args)
               if isinstance(x, np.ndarray))


@pytest.mark.parametrize("transpose, R", [(False, 1), (True, 1), (False, 8)],
                         ids=["forward", "transpose", "panel8"])
def test_solve_phase_spans_nest_under_solve_in_order(transpose, R):
    a, b = small_problem()
    if R > 1:
        b = np.stack([b * (k + 1) for k in range(R)], axis=1)
    ctx = SpTRSVContext(mesh=st.mesh1())
    h = ctx.analyse(a)
    with tr.trace_to() as tracer:
        ctx.solve(h, b, transpose=transpose)
        spans = [r for r in tracer.export() if r["type"] == "span"]
    solve = next(r for r in spans if r["name"] == "sptrsv.solve")
    phases = sorted((r for r in spans if r["parent"] == solve["id"]),
                    key=lambda r: r["id"])
    assert tuple(r["name"] for r in phases) == PHASES
    for prev, nxt in zip(phases, phases[1:]):  # one after the other in time
        assert nxt["t0_us"] >= prev["t0_us"] + prev["dur_us"] - 1e-3
    assert all(r["dur_us"] <= solve["dur_us"] for r in phases)


def test_h2d_bytes_count_host_arguments_and_padded_rhs_across_refresh():
    a, b = small_problem()
    reg = met.MetricsRegistry()
    ctx = SpTRSVContext(mesh=st.mesh1(), registry=reg)
    h = ctx.analyse(a)
    solver = ctx.executor(h)
    bs = solver.plan.bs
    rhs_bytes = bs.nb * bs.B * 4  # the (nb, B) float32 blocks pad_rhs stages

    def per_call():
        before = reg.counter("executor.h2d_bytes").value
        ctx.solve(h, b)
        return reg.counter("executor.h2d_bytes").value - before

    first = per_call()
    assert first == host_arg_bytes(solver) + rhs_bytes > rhs_bytes
    args_before = solver._args
    ctx.factorize(st.dyadic(a, seed=9), h)  # re-arms the same executor
    assert ctx.executor(h) is solver and solver._args is not args_before
    assert per_call() == host_arg_bytes(solver) + rhs_bytes == first
    assert reg.counter("executor.solves").value == 2


def test_device_resident_argument_drops_out_of_h2d_bytes(monkeypatch):
    a, b = small_problem()
    reg = met.MetricsRegistry()
    solver = DistributedSolver(build_plan(a, 1, PlanOptions().to_config()),
                               st.mesh1(), registry=reg)
    rhs_bytes = solver.plan.bs.nb * solver.plan.bs.B * 4
    x_host = solver.solve(b)
    host_args = reg.counter("executor.h2d_bytes").value - rhs_bytes
    plan_args = DistributedSolver._plan_args
    big = int(np.argmax([np.asarray(x).nbytes for x in solver._args]))

    def with_device_store(self, plan):
        args = list(plan_args(self, plan))
        args[big] = jax.device_put(args[big])
        return tuple(args)

    monkeypatch.setattr(DistributedSolver, "_plan_args", with_device_store)
    solver.refresh(solver.plan)
    moved = solver._args[big].nbytes
    before = reg.counter("executor.h2d_bytes").value
    np.testing.assert_array_equal(solver.solve(b), x_host)
    assert reg.counter("executor.h2d_bytes").value - before \
        == host_args - moved + rhs_bytes


def test_launch_us_leaves_out_the_call_that_compiles():
    a, b = small_problem()
    reg = met.MetricsRegistry()
    ctx = SpTRSVContext(mesh=st.mesh1(), registry=reg)
    h = ctx.analyse(a)
    hist = reg.histogram("executor.launch_us")
    ctx.solve(h, b)
    assert hist.count == 0  # the first call of the shape traced and compiled
    ctx.solve(h, b)
    ctx.solve(h, np.stack([b, b], axis=1))  # a new shape compiles again
    assert hist.count == 1 and hist.total > 0
    ctx.solve(h, np.stack([b, b], axis=1))
    assert hist.count == 2


def test_jit_compile_accounting_counts_compiles_not_calls():
    reg = met.get_registry()
    f = jax.jit(lambda x: jnp.sin(x) * 3.0 + 0.25)
    x, y = (jnp.asarray(np.full((7, 3), v, np.float32)) for v in (1.0, 2.0))
    compiles = reg.counter("jit.compiles").value
    seconds = reg.gauge("jit.compile_s").value
    f(x).block_until_ready()
    assert reg.counter("jit.compiles").value > compiles
    assert reg.gauge("jit.compile_s").value > seconds
    compiles = reg.counter("jit.compiles").value
    seconds = reg.gauge("jit.compile_s").value
    f(y).block_until_ready()  # same shape: the compiled program runs
    assert reg.counter("jit.compiles").value == compiles
    assert reg.gauge("jit.compile_s").value == seconds


# ---------------------------------------------------------------------------
# calibration feedback loop
# ---------------------------------------------------------------------------


def synthetic_samples(w_solve_us=3.0, c_tile=6.0, n=4):
    """Samples generated exactly by us = w_solve*su + c_tile*tu at R=1."""
    rng = np.random.default_rng(0)
    out = []
    for i in range(n):
        su = float(rng.integers(50, 400))
        tu = float(rng.integers(20, 300))
        out.append(dict(signature=f"sig{i}", su=su, tu=tu, tf=tu, R=1,
                        us=w_solve_us * su + c_tile * tu))
    return out


def record_all(store, samples, backend="reference", B=16):
    for s in samples:
        store.record(backend=backend, B=B, signature=s["signature"],
                     solve_units=s["su"], tile_units=s["tu"],
                     tile_flop_units=s["tf"], R=s["R"], measured_us=s["us"])


def test_calibration_fit_recovers_generating_weights():
    store = cal.CalibrationStore()
    record_all(store, synthetic_samples())
    w = store.fitted_weights(16, "reference")
    assert w is not None and w[0] == 1.0
    # uniform R=1 collapses tu/tf into one column: the fitted total tile
    # cost (mem + flop at R=1) must match the generator's ratio c_tile/w_solve
    assert w[1] + w[2] == pytest.approx(6.0 / 3.0, rel=1e-6)
    assert store.fitted_weights(16, "reference") is w  # cached identity


def test_calibration_underdetermined_returns_none():
    store = cal.CalibrationStore()
    assert store.fitted_weights(16, "reference") is None  # no samples
    record_all(store, synthetic_samples(n=1))
    assert store.fitted_weights(16, "reference") is None  # one sample
    # duplicate signature replaces, never stacks
    store2 = cal.CalibrationStore()
    record_all(store2, synthetic_samples(n=3))
    record_all(store2, synthetic_samples(n=3))
    assert store2.n_samples() == 3


def test_calibration_persist_reload_roundtrip(tmp_path):
    path = str(tmp_path / "weights.json")
    store = cal.CalibrationStore(path=path)
    record_all(store, synthetic_samples())  # record() persists each sample
    fresh = cal.CalibrationStore(path=path)  # a later session loads on init
    assert fresh.n_samples() == store.n_samples() == 4
    assert fresh.fitted_weights(16, "reference") == pytest.approx(
        store.fitted_weights(16, "reference"))


def test_probe_free_session_inherits_persisted_weights(tmp_path):
    path = str(tmp_path / "weights.json")
    record_all(cal.CalibrationStore(path=path), synthetic_samples())
    # "new session": a fresh global store pointed at the persisted file,
    # probe_solves=0 — calibrate_weights must prefer the fitted weights
    cal.set_store(cal.CalibrationStore(path=path))
    w = calibrate_weights(16, backend="reference")
    assert w == cal.get_store().fitted_weights(16, "reference")
    assert w[1] + w[2] == pytest.approx(2.0, rel=1e-6)
    assert calibrate_weights(16, backend="reference") is w  # stable identity
    # feedback off, or an empty store, falls back to the HLO estimate
    assert calibrate_weights(16, backend="reference", feedback=False) is \
        hlo_weights(16, "reference")
    cal.set_store(cal.CalibrationStore())
    assert calibrate_weights(16, backend="reference") is \
        hlo_weights(16, "reference")


def record_pair(store, ratio, B=16, n=3):
    """Paired fused / fused_streamed samples where the streamed executor
    costs ``ratio``x the resident one per schedule work unit."""
    for i in range(n):
        su, tu = 100.0 + 10 * i, 50.0 + 5 * i
        units = su + tu
        store.record(backend="fused", B=B, signature=f"f{i}",
                     solve_units=su, tile_units=tu, tile_flop_units=tu,
                     R=1, measured_us=2.0 * units)
        store.record(backend="fused_streamed", B=B, signature=f"s{i}",
                     solve_units=su, tile_units=tu, tile_flop_units=tu,
                     R=1, measured_us=2.0 * ratio * units)


def test_calibrated_stream_limit_scales_default_by_measured_ratio():
    from repro.core.solver import DEFAULT_STREAM_VMEM_LIMIT

    store = cal.CalibrationStore()
    assert cal.calibrated_stream_limit(store) is None  # no samples at all
    record_pair(store, ratio=2.0)  # streaming costs 2x per work unit
    assert cal.calibrated_stream_limit(store) == 2 * DEFAULT_STREAM_VMEM_LIMIT
    # near-free streaming drags the crossover down to the floor clamp,
    # pathological DMA cost saturates at the ceiling
    cheap, costly = cal.CalibrationStore(), cal.CalibrationStore()
    record_pair(cheap, ratio=0.01)
    record_pair(costly, ratio=1000.0)
    assert cal.calibrated_stream_limit(cheap) == cal.STREAM_LIMIT_FLOOR
    assert cal.calibrated_stream_limit(costly) == cal.STREAM_LIMIT_CEIL


def test_calibrated_stream_limit_needs_paired_backends():
    """Fused-only samples measure no crossover: callers must keep the fixed
    default rather than extrapolate from one executor."""
    store = cal.CalibrationStore()
    record_all(store, synthetic_samples(), backend="fused")
    assert cal.calibrated_stream_limit(store) is None


def test_stream_vmem_limit_resolution_order(monkeypatch):
    """env override > calibrated crossover > fixed default."""
    from repro.core.solver import DEFAULT_STREAM_VMEM_LIMIT, stream_vmem_limit

    monkeypatch.delenv("REPRO_STREAM_VMEM_LIMIT", raising=False)
    assert stream_vmem_limit() == DEFAULT_STREAM_VMEM_LIMIT  # pristine store
    record_pair(cal.get_store(), ratio=2.0)
    assert stream_vmem_limit() == 2 * DEFAULT_STREAM_VMEM_LIMIT
    monkeypatch.setenv("REPRO_STREAM_VMEM_LIMIT", "123456")
    assert stream_vmem_limit() == 123456  # env beats the measurement


def test_tune_probes_record_samples_and_compile_us(tmp_path):
    path = str(tmp_path / "weights.json")
    cal.set_store(cal.CalibrationStore(path=path))
    a, _ = small_problem(n=80, levels=5)
    opts = PlanOptions(sched="auto", comm="zerocopy", kernel="reference",
                       block_size=16, probe_solves=1)
    cfg, plan, decision, solver = tune(a, opts, st.mesh1())
    assert decision.mode == "probed"
    assert set(decision.compile_us) == set(decision.probe_us)
    assert all(us > 0 for us in decision.compile_us.values())
    # one sample per probed candidate (levelset/dagpart/syncfree), persisted
    # for the next session
    assert cal.get_store().n_samples() == len(decision.probe_us) == 3
    reloaded = cal.CalibrationStore(path=path)
    assert reloaded.n_samples() == 3
    # recorded work units are exactly what the scorer multiplies weights by
    combo = decision.chosen
    sig = cal.probe_signature(plan, opts.rhs_hint)
    sample = reloaded.samples(ops.executor_backend(combo[2]), 16)[sig]
    su, tu, tf = plan_work_units(plan, opts.rhs_hint)
    assert (sample["su"], sample["tu"], sample["tf"]) == (su, tu, tf)


# ---------------------------------------------------------------------------
# service telemetry (ISSUE 9): registry mirrors engine counters exactly
# ---------------------------------------------------------------------------


def serve_mix(registry=None, tracing=False, tmp_path=None):
    """One deterministic hot/cold mix through an engine; returns (engine,
    ordered results)."""
    from repro.service import SolveEngine

    mats = [st.dyadic(suite.random_levelled(n, 5, 3.0, seed=s))
            for n, s in ((96, 1), (64, 2))]
    kw = dict(mesh=st.mesh1(), options=PlanOptions(block_size=16),
              max_batch=4)
    if registry is not None:
        kw["registry"] = registry
    if tmp_path is not None:
        kw["plan_store"] = str(tmp_path / "plans")
    eng = SolveEngine(**kw)
    tickets = []
    for i in range(8):
        m = mats[0] if i % 3 else mats[1]
        tickets.append(eng.submit(f"t{i % 2}", m,
                                  st.dyadic_rhs(m.n, seed=i)))
    eng.drain()
    return eng, [np.asarray(t.result(0)) for t in tickets]


def test_service_metrics_reconcile_with_engine_counters(tmp_path):
    reg = met.MetricsRegistry()
    eng, _ = serve_mix(registry=reg, tmp_path=tmp_path)
    snap = reg.snapshot()
    stats = eng.stats()
    # every engine counter is mirrored under service.* with the same value
    # (same discipline as record_plan_metrics vs dispatch_stats)
    counters = {k: v for k, v in stats.items()
                if k not in ("queue_depth", "plan_store", "session")}
    assert counters, "engine produced no counters"
    for k, v in counters.items():
        assert snap[f"service.{k}"] == v, k
    assert snap["service.queue_depth"] == stats["queue_depth"] == 0
    # distribution instruments agree with the counted totals
    assert snap["service.coalesce_width"]["count"] == stats["batches"]
    assert snap["service.coalesce_width"]["sum"] == stats["coalesced_columns"]
    assert snap["service.request_us"]["count"] == stats["results"]
    assert snap["service.batch_us"]["count"] == stats["batches"]
    # the plan store mirrors its own counters and the derived hit-rate gauge
    ps = stats["plan_store"]
    for k, v in ps.items():
        if k != "hit_rate":
            assert snap[f"planstore.{k}"] == v, k
    assert snap["service.plan_store_hit_rate"] == pytest.approx(ps["hit_rate"])
    # and the session counters underneath are the ordinary session.* mirror
    for k, v in stats["session"].items():
        if k != "cache_hit_rate":
            assert snap[f"session.{k}"] == v, k


def test_served_results_bit_identical_tracing_on_vs_off():
    a_probe, _ = small_problem()
    assert st.exactness_holds(a_probe, st.dyadic_rhs(a_probe.n))
    tr.configure_tracing(enabled=False)
    _, off = serve_mix(registry=met.MetricsRegistry())
    with tr.trace_to() as tracer:
        _, on = serve_mix(registry=met.MetricsRegistry())
        names = {r["name"] for r in tracer.export() if r["type"] == "span"}
    # the serving lifecycle is spanned...
    assert {"service.batch", "service.request", "sptrsv.analyse",
            "sptrsv.solve"} <= names
    # ...and never enters compiled code: served panels are bit-identical
    assert len(off) == len(on)
    for x_off, x_on in zip(off, on):
        np.testing.assert_array_equal(x_off, x_on)


def test_service_batch_spans_parent_request_spans(tmp_path):
    path = str(tmp_path / "serve.jsonl")
    with tr.trace_to(path):
        serve_mix(registry=met.MetricsRegistry())
    recs = [json.loads(line) for line in open(path)]
    spans = [r for r in recs if r["type"] == "span"]
    batches = [r for r in spans if r["name"] == "service.batch"]
    requests = [r for r in spans if r["name"] == "service.request"]
    assert batches and requests
    # every batch span carries the admission attrs; width <= padded width
    for b in batches:
        assert b["attrs"]["n_requests"] >= 1
        assert b["attrs"]["width"] <= b["attrs"]["padded_width"]
    assert sum(b["attrs"]["n_requests"] for b in batches) == len(requests)
    for r in requests:
        assert r["attrs"]["latency_us"] > 0


def test_dispatch_stats_surfaces_compile_us():
    a, b = small_problem(n=80, levels=5)
    opts = PlanOptions(sched="auto", comm="zerocopy", kernel="reference",
                       block_size=16, probe_solves=1)
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts)
    h = ctx.analyse(a)
    auto = ctx.dispatch_stats(h)["auto"]
    assert set(auto["compile_us"]) == set(auto["probe_us"])
    assert all(us > 0 for us in auto["compile_us"].values())
    snap = ctx.metrics_snapshot(h)
    assert any(k.startswith("auto.compile_us.") for k in snap)
