"""Persistent plan store: cross-session reuse of the symbolic analysis.

Covers the ISSUE-9 plan-persistence points: serialize -> load -> solve is
bit-identical to the fresh-analysis plan across sched x comm x kernel x
transpose (dyadic exactness makes ``assert_array_equal`` real bit-equality),
corrupt or stale entries are rejected by the strict load-time verifier and
fall back to a fresh analysis without crashing, writes are atomic, and a
warm-started worker serves a multi-pattern mix with ZERO symbolic analyses
(the acceptance criterion, asserted via session counters).
"""
import json
import os
import zipfile

import numpy as np
import pytest

import strategies as st
from repro.api import PlanOptions, SpTRSVContext
from repro.obs import metrics as met
from repro.service import PlanStore, options_signature
from repro.sparse import suite
from repro.sparse.matrix import reference_solve


def exact_problem(n=120, levels=6, seed=3):
    a = st.dyadic(suite.random_levelled(n, levels, 4.0, seed=seed))
    b = st.dyadic_rhs(a.n, seed=seed + 1)
    return a, b


def make_store(tmp_path, **kw):
    kw.setdefault("registry", met.MetricsRegistry())
    return PlanStore(str(tmp_path / "plans"), **kw)


def cold_then_warm(tmp_path, opts, a, b, *, transpose=False):
    """Two sessions against one store dir; returns (x_cold, x_warm, warm_ctx)."""
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts,
                        registry=met.MetricsRegistry(),
                        plan_store=make_store(tmp_path))
    h = ctx.analyse(a)
    if transpose:
        # materialize + persist the forward plan too (the typical L / L^T
        # pairing): the warm session's symbolic analysis loads from it
        ctx.plan(h)
    x_cold = np.asarray(ctx.solve(h, b, transpose=transpose))
    store = make_store(tmp_path)
    ctx2 = SpTRSVContext(mesh=st.mesh1(), options=opts,
                         registry=met.MetricsRegistry(), plan_store=store)
    h2 = ctx2.analyse(a)
    x_warm = np.asarray(ctx2.solve(h2, b, transpose=transpose))
    return x_cold, x_warm, ctx2


# ---------------------------------------------------------------------------
# keying
# ---------------------------------------------------------------------------


def test_options_signature_stable_and_sensitive():
    o = PlanOptions(block_size=16, sched="levelset")
    assert options_signature(o, 2) == options_signature(
        PlanOptions(block_size=16, sched="levelset"), 2)
    # every plan-shaping dimension separates entries
    assert options_signature(o, 2) != options_signature(o, 4)
    assert options_signature(o, 2) != options_signature(o, 2, transpose=True)
    assert options_signature(o, 2) != options_signature(
        PlanOptions(block_size=8, sched="levelset"), 2)
    assert options_signature(o, 2) != options_signature(
        PlanOptions(block_size=16, sched="dagpart"), 2)
    # check-only knobs never invalidate a stored plan
    assert options_signature(o, 2) == options_signature(
        PlanOptions(block_size=16, sched="levelset", verify="strict",
                    probe_solves=3), 2)


# ---------------------------------------------------------------------------
# round trip: serialize -> load -> solve bit-identical
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sched,comm,kernel,transpose", [
    ("levelset", "zerocopy", "default", False),
    ("levelset", "unified", "reference", True),
    ("dagpart", "zerocopy", "fused", False),
    ("syncfree", "zerocopy", "default", True),
])
def test_roundtrip_bit_identical(tmp_path, sched, comm, kernel, transpose):
    a, b = exact_problem()
    assert st.exactness_holds(a, b)
    opts = PlanOptions(block_size=16, sched=sched, comm=comm, kernel=kernel)
    x_cold, x_warm, ctx2 = cold_then_warm(tmp_path, opts, a, b,
                                          transpose=transpose)
    np.testing.assert_array_equal(x_cold, x_warm)
    s = ctx2.stats()
    assert s.get("analyses", 0) == 0, "warm session re-ran symbolic analysis"
    assert s["plan_store_hits"] >= 1
    if not transpose:
        np.testing.assert_array_equal(
            x_warm, reference_solve(a, b).astype(np.float32))


def test_roundtrip_covers_transpose_extension(tmp_path):
    """Both sweep directions of one analysis persist and reload: the warm
    L^T solve is a store hit, not a fresh transpose schedule build."""
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts,
                        registry=met.MetricsRegistry(),
                        plan_store=make_store(tmp_path))
    h = ctx.analyse(a)
    xf = np.asarray(ctx.solve(h, b))
    xt = np.asarray(ctx.solve(h, b, transpose=True))
    ctx2 = SpTRSVContext(mesh=st.mesh1(), options=opts,
                         registry=met.MetricsRegistry(),
                         plan_store=make_store(tmp_path))
    h2 = ctx2.analyse(a)
    np.testing.assert_array_equal(np.asarray(ctx2.solve(h2, b)), xf)
    np.testing.assert_array_equal(
        np.asarray(ctx2.solve(h2, b, transpose=True)), xt)
    s = ctx2.stats()
    assert s.get("analyses", 0) == 0
    assert s.get("transpose_extensions", 0) == 0
    assert s["plan_store_hits"] == 2  # forward + transpose both loaded


def test_auto_session_warm_starts_under_auto_key(tmp_path):
    """A cold auto session persists its resolved choice; the warm session
    loads it under the same auto signature — no re-tuning, no analysis."""
    a, b = exact_problem(n=80, levels=5)
    opts = PlanOptions(block_size=16, sched="auto", comm="zerocopy",
                       kernel="reference")
    x_cold, x_warm, ctx2 = cold_then_warm(tmp_path, opts, a, b)
    np.testing.assert_array_equal(x_cold, x_warm)
    s = ctx2.stats()
    assert s.get("analyses", 0) == 0 and s["plan_store_hits"] == 1


def test_values_rehydrate_from_caller_matrix(tmp_path):
    """The store holds no numeric values: a warm load against refreshed
    values solves with THOSE values (same pattern, different answer)."""
    a, b = exact_problem()
    a2 = st.dyadic(a, seed=99)  # same pattern, different values
    opts = PlanOptions(block_size=16)
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts,
                        registry=met.MetricsRegistry(),
                        plan_store=make_store(tmp_path))
    ctx.solve(ctx.analyse(a), b)
    ctx2 = SpTRSVContext(mesh=st.mesh1(), options=opts,
                         registry=met.MetricsRegistry(),
                         plan_store=make_store(tmp_path))
    x2 = np.asarray(ctx2.solve(ctx2.analyse(a2), b))
    assert ctx2.stats().get("analyses", 0) == 0
    np.testing.assert_array_equal(x2, reference_solve(a2, b).astype(np.float32))


# ---------------------------------------------------------------------------
# corruption / staleness: strict verifier rejects, store falls back cleanly
# ---------------------------------------------------------------------------


def populated_store(tmp_path, a, b, opts):
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts,
                        registry=met.MetricsRegistry(),
                        plan_store=make_store(tmp_path))
    ctx.solve(ctx.analyse(a), b)
    paths = [os.path.join(str(tmp_path / "plans"), f)
             for f in sorted(os.listdir(str(tmp_path / "plans")))]
    assert len(paths) == 1 and paths[0].endswith(".plan.npz")
    return paths[0]


def rewrite_npz(path, *, meta_patch=None, array_patch=None):
    """Round-trip the npz with a targeted mutation (a tampering 'attacker')."""
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(str(arrays["meta"][()]))
    if meta_patch:
        meta.update(meta_patch)
    arrays["meta"] = np.array(json.dumps(meta))
    if array_patch:
        for k, fn in array_patch.items():
            arrays[k] = fn(arrays[k])
    with open(path, "wb") as f:
        np.savez(f, **arrays)


def assert_falls_back(tmp_path, a, b, opts, expect_rejected=True):
    """A defective entry must yield a fresh-analysis session that still
    solves correctly — and counts the rejection, not a crash."""
    store = make_store(tmp_path)
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts,
                        registry=met.MetricsRegistry(), plan_store=store)
    h = ctx.analyse(a)
    x = np.asarray(ctx.solve(h, b))
    np.testing.assert_array_equal(x, reference_solve(a, b).astype(np.float32))
    s = ctx.stats()
    assert s["analyses"] == 1 and s.get("plan_store_hits", 0) == 0
    if expect_rejected:
        assert store.stats["rejected"] == 1
    return store


def test_truncated_file_rejected(tmp_path):
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    path = populated_store(tmp_path, a, b, opts)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 3])
    assert_falls_back(tmp_path, a, b, opts)


def test_wrong_version_header_rejected(tmp_path):
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    path = populated_store(tmp_path, a, b, opts)
    rewrite_npz(path, meta_patch={"version": 999})
    assert_falls_back(tmp_path, a, b, opts)
    rewrite_npz(path, meta_patch={"format": "not-a-plan", "version": 1})
    assert_falls_back(tmp_path, a, b, opts)


def test_version_1_entry_falls_back_to_fresh_analysis(tmp_path):
    """An entry in the version-1 layout, whose stored config carries a field
    :class:`SolverConfig` no longer has, is refused at its version header
    before its config is read: the session re-analyses and solves, and
    nothing crashes."""
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    path = populated_store(tmp_path, a, b, opts)
    with np.load(path, allow_pickle=False) as z:
        config = json.loads(str(z["meta"][()]))["config"]
    rewrite_npz(path, meta_patch={"version": 1,
                                  "config": dict(config, retired_field=0)})
    assert_falls_back(tmp_path, a, b, opts)


def test_mutated_schedule_table_rejected_by_strict_verifier(tmp_path):
    """A tampered schedule that still parses must die at ``verify_plan``:
    reversing the compacted solve-row order breaks happens-before."""
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    path = populated_store(tmp_path, a, b, opts)
    rewrite_npz(path,
                array_patch={"solve_rows": lambda v: v[..., ::-1].copy()})
    assert_falls_back(tmp_path, a, b, opts)


def test_zipfile_garbage_rejected(tmp_path):
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    path = populated_store(tmp_path, a, b, opts)
    with zipfile.ZipFile(path, "w") as zf:  # valid zip, not a plan
        zf.writestr("meta", "garbage")
    assert_falls_back(tmp_path, a, b, opts)


def test_atomic_save_leaves_no_temp_files(tmp_path):
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    populated_store(tmp_path, a, b, opts)
    leftovers = [f for f in os.listdir(str(tmp_path / "plans"))
                 if not f.endswith(".plan.npz")]
    assert leftovers == []


def test_unwritable_store_degrades_to_no_persistence(tmp_path, monkeypatch):
    """A store the worker cannot write to (read-only volume, disk full) must
    cost nothing but the saves — the session keeps solving."""
    a, b = exact_problem()
    opts = PlanOptions(block_size=16)
    store = make_store(tmp_path)

    def refuse(*args, **kwargs):
        raise OSError("read-only file system")

    monkeypatch.setattr(store, "save", refuse)
    ctx = SpTRSVContext(mesh=st.mesh1(), options=opts,
                        registry=met.MetricsRegistry(), plan_store=store)
    x = np.asarray(ctx.solve(ctx.analyse(a), b))
    np.testing.assert_array_equal(x, reference_solve(a, b).astype(np.float32))
    assert ctx.stats()["plan_store_save_errors"] == 1
    assert store.stats.get("saves", 0) == 0


# ---------------------------------------------------------------------------
# acceptance: warm worker serves a 3-pattern mix with zero symbolic analyses
# ---------------------------------------------------------------------------


def test_warm_worker_serves_mix_with_zero_analyses(tmp_path):
    patterns = [st.dyadic(suite.random_levelled(n, 6, 3.0, seed=s))
                for n, s in ((120, 1), (90, 2), (70, 3))]
    opts = PlanOptions(block_size=16)
    cold = SpTRSVContext(mesh=st.mesh1(), options=opts,
                         registry=met.MetricsRegistry(),
                         plan_store=make_store(tmp_path))
    for a in patterns:
        cold.solve(cold.analyse(a), st.dyadic_rhs(a.n))
    assert cold.stats()["analyses"] == len(patterns)

    store = make_store(tmp_path)
    assert store.verify == "strict"  # every load below is strict-verified
    warm = SpTRSVContext(mesh=st.mesh1(), options=opts,
                         registry=met.MetricsRegistry(), plan_store=store)
    # hot/cold mix: pattern 0 hammered, the tail touched once each
    for a in (patterns[0], patterns[1], patterns[0], patterns[2], patterns[0]):
        x = np.asarray(warm.solve(warm.analyse(a), st.dyadic_rhs(a.n)))
        np.testing.assert_array_equal(
            x, reference_solve(a, st.dyadic_rhs(a.n)).astype(np.float32))
    s = warm.stats()
    assert s.get("analyses", 0) == 0, "warm worker ran a symbolic analysis"
    assert s["plan_store_hits"] == len(patterns)
    assert store.stats["hits"] == len(patterns)
    assert store.stats.get("rejected", 0) == 0
    assert store.stats["hit_rate"] == 1.0
