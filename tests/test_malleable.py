"""Malleable cost-model partition: invariants, balance acceptance, agreement.

Unlike test_partition.py this module does not need hypothesis, so the
acceptance checks for the malleable strategy always run.
"""
import numpy as np
import pytest

from strategies import mesh1 as _mesh1, random_blocks as _blocks
from repro.api import SpTRSVContext
from repro.core import SolverConfig, build_plan
from repro.core.blocking import build_blocks
from repro.core.partition import block_row_cost, cut_stats, make_partition
from repro.sparse import suite
from repro.sparse.matrix import reference_solve


# ---------------------------------------------------------------------------
# partition invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("D,tpd", [(1, 8), (3, 4), (4, 8), (8, 2)])
def test_malleable_invariants(D, tpd):
    bs = _blocks(seed=5)
    part = make_partition(bs, D, "malleable", tpd)
    # every block row owned exactly once, by a real device
    assert part.owner.shape == (bs.nb,)
    assert part.owner.min() >= 0 and part.owner.max() < D
    # boundary mask matches tile ownership exactly
    remote = part.owner[bs.off_cols] != part.owner[bs.off_rows]
    expect = np.zeros(bs.nb, bool)
    expect[bs.off_rows[remote]] = True
    assert np.array_equal(part.boundary, expect)
    if D == 1:
        assert not part.boundary.any()


def test_malleable_single_device_owns_everything():
    bs = _blocks(seed=6)
    part = make_partition(bs, 1, "malleable", 8)
    assert np.array_equal(part.owner, np.zeros(bs.nb, np.int32))


def test_block_row_cost_counts_column_tiles():
    bs = _blocks(seed=8)
    cost = block_row_cost(bs)
    assert cost.shape == (bs.nb,)
    col_tiles = np.bincount(bs.off_cols, minlength=bs.nb)
    np.testing.assert_allclose(cost, 1.0 + 2.0 * col_tiles)


def test_unknown_strategy_raises():
    bs = _blocks()
    with pytest.raises(ValueError):
        make_partition(bs, 4, "nope")


# ---------------------------------------------------------------------------
# balance acceptance vs the round-robin task pool
# ---------------------------------------------------------------------------

SKEWED = ("chipcool0", "pkustk14", "shipsec1", "dblp-2010")


def test_malleable_beats_taskpool_level_balance_on_skewed_suites():
    """Acceptance: per-level LPT placement never loses to the round-robin deal
    on the paper's skewed (chain-dominated / banded) matrices, and wins
    strictly on at least one of them."""
    deltas = []
    for e in suite.table1_suite(0.05):
        if e.name not in SKEWED:
            continue
        bs = build_blocks(e.build(), 16)
        mal = cut_stats(bs, make_partition(bs, 4, "malleable", 8))
        tp = cut_stats(bs, make_partition(bs, 4, "taskpool", 8))
        assert mal.level_imbalance <= tp.level_imbalance + 1e-9, e.name
        deltas.append(tp.level_imbalance - mal.level_imbalance)
    assert len(deltas) == len(SKEWED)
    assert max(deltas) > 1e-6  # strictly lower somewhere


def test_cut_stats_cost_imbalance_present():
    bs = _blocks(seed=7)
    cs = cut_stats(bs, make_partition(bs, 4, "malleable", 8))
    assert cs.level_cost_imbalance >= 1.0
    assert cs.level_imbalance >= 1.0


# ---------------------------------------------------------------------------
# solution agreement across strategies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("comm", ["zerocopy", "unified"])
@pytest.mark.parametrize("sched", ["levelset", "syncfree"])
def test_strategies_agree_bit_exact_and_match_reference(comm, sched):
    """All three partition strategies produce the same solution (bit-exact on
    one device) and match the scipy oracle, in all four sched x comm modes."""
    a = suite.random_levelled(400, 24, 4.0, seed=3)
    b = np.random.default_rng(0).uniform(-1, 1, a.n)
    x_ref = reference_solve(a, b)
    mesh = _mesh1()
    xs = {}
    for part in ("taskpool", "contiguous", "malleable"):
        cfg = SolverConfig(block_size=16, comm=comm, sched=sched, partition=part)
        ctx = SpTRSVContext(mesh=mesh, options=cfg)
        xs[part] = ctx.solve(ctx.analyse(a), b)
        np.testing.assert_allclose(xs[part], x_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(xs["taskpool"], xs["contiguous"])
    np.testing.assert_array_equal(xs["taskpool"], xs["malleable"])


def test_malleable_partition_reuse_in_plan():
    """A malleable partition built for one pattern is reusable by build_plan
    (the zero-fill-factor sharing path the Krylov front doors rely on)."""
    a = suite.grid2d_factor(16, seed=2)
    cfg = SolverConfig(block_size=16, partition="malleable")
    plan_a = build_plan(a, 1, cfg)
    plan_b = build_plan(a, 1, cfg, part=plan_a.part)
    assert plan_b.part is plan_a.part
    b = np.random.default_rng(1).uniform(-1, 1, a.n)
    from repro.core import DistributedSolver

    x = DistributedSolver(plan_b, _mesh1()).solve(b)
    np.testing.assert_allclose(x, reference_solve(a, b), rtol=2e-4, atol=2e-4)
