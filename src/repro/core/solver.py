"""Distributed SpTRSV — the paper's contribution, TPU-native (DESIGN.md §5).

Execution model
---------------
Block-rows are distributed by a :class:`~repro.core.partition.Partition`
(each device owns block-row *and* block-column ``r`` — the paper's layout
where components x, columns of L and rhs b are co-partitioned). Tiles live on
the owner of their *column*, so an update ``acc[r] += L[r,c] @ x[c]`` is always
computed where ``x[c]`` was produced: the **only** communication is combining
per-device partial accumulators — the paper's read-only model, where each PE
accumulates into its own symmetric-heap array and the owner of a row pulls and
reduces partials right before solving.

Communication modes (paper Fig. 7 scenarios):
* ``unified``  — all-reduce the *full* n-sized accumulator delta every
  superstep (the Unified-Memory analogue: dense, cut-oblivious traffic).
* ``zerocopy`` — exchange only *packed boundary rows*; in ``levelset``
  scheduling each row is exchanged exactly once, lazily, right before its
  level (the NVSHMEM get+warp-reduce analogue: psum of the packed buffer).

Scheduling modes:
* ``levelset`` — host-precomputed block wavefronts (Naumov-style baseline).
* ``dagpart``  — levelset coarsened by the DAG-partition merge pass
  (:func:`repro.core.partition.merge_levels`): consecutive narrow levels fuse
  into one superstep whose in-kernel rowsweep executes intra-step
  dependencies in order — fewer grid steps, fewer exchange segments, smaller
  schedule tables. The micro-level tables stay byte-identical to levelset;
  only ``Plan.step_off`` (and the hoisted exchange slices) differ.
* ``syncfree`` — no level analysis; runtime in-degree counters discover the
  frontier each superstep (the paper's synchronization-free algorithm,
  bulk-synchronous TPU adaptation).

Compacted schedules
-------------------
Levelset schedules are stored *ragged*: one flat array per schedule
(``solve_rows``, ``upd_tiles``, ``ex_rows``) plus per-level offsets
(``lvl_off``). Each level's slice is padded only up to a *bucket width* drawn
from a small ladder (``Plan.buckets``), and the executor compiles one superstep
body per occurring bucket combo, dispatched with ``lax.switch`` — so a level
with 3 rows costs a width-4 superstep instead of the global max width, cutting
the wasted pad flops and pad exchange bytes that a dense ``(T, max)`` layout
burns on skewed level-size distributions.
"""
from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

import warnings

from repro import compat
from repro.core.blocking import BlockStructure, build_blocks, refresh_block_values
from repro.core.partition import (
    STRATEGIES, Partition, make_partition, merge_levels,
)
from repro.kernels import ops
from repro.obs.metrics import MetricsRegistry, get_registry
from repro.obs.trace import get_tracer
from repro.sparse.matrix import CSR, reverse_transpose
from repro.kernels.superstep import (
    from_rows, row_scratch_bytes, superstep_call, to_rows,
)

AXIS = "x"  # device axis name used by the solver

MAX_BUCKETS = 12  # cap on distinct (solve, update, exchange) width combos

COMM_MODES = ("zerocopy", "unified")
SCHED_MODES = ("levelset", "dagpart", "syncfree")
# scheds that execute the compacted levelset tables (dagpart is levelset plus
# a superstep coarsening on top of the same flats)
LEVELSET_SCHEDS = ("levelset", "dagpart")


def _check_choice(name: str, value, valid: tuple) -> None:
    if value not in valid:
        raise ValueError(
            f"invalid {name}: {value!r} (valid choices: {', '.join(valid)})"
        )


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    # B: 128 where Pallas compiles for the TPU, 32 elsewhere
    # (ops.default_block_size says why)
    block_size: int = dataclasses.field(default_factory=ops.default_block_size)
    comm: str = "zerocopy"  # "zerocopy" | "unified"
    sched: str = "levelset"  # "levelset" | "dagpart" | "syncfree"
    partition: str = "taskpool"  # "taskpool" | "contiguous" | "malleable"
    tasks_per_device: int = 8
    # None -> env/platform default; "reference"/"pallas" pick the per-op kernels
    # for the lax.switch executor; "fused" runs the superstep megakernel
    # (levelset) / frontier-bucketed executor (syncfree); "fused_streamed"
    # additionally streams the diag/tile stores from HBM per level (plain
    # "fused" auto-upgrades to streaming above stream_vmem_limit()).
    kernel_backend: str | None = None
    gemv_group: int = 0
    rhs_hint: int = 1  # expected RHS panel width R, feeds the partition cost model
    calibrate_cost: bool = False  # calibrate cost weights via hlo_cost per backend
    # dagpart merge heuristic knobs (ignored by the other scheds):
    # merge_width caps the busiest device's accumulated rows per merged
    # superstep; merge_cost is the narrow-level cost threshold (0 -> the
    # calibrated costmodel.merge_cost_threshold default)
    merge_width: int = 64
    merge_cost: float = 0.0

    def __post_init__(self):
        # Eager validation at the API boundary: a typo'd mode used to surface
        # as an obscure failure deep inside plan construction or tracing.
        _check_choice("comm", self.comm, COMM_MODES)
        _check_choice("sched", self.sched, SCHED_MODES)
        _check_choice("partition", self.partition, STRATEGIES)
        if self.kernel_backend is not None:
            _check_choice("kernel_backend", self.kernel_backend, ops.BACKENDS)
        for name, lo in (("block_size", 1), ("tasks_per_device", 1), ("rhs_hint", 1),
                         ("merge_width", 1)):
            if int(getattr(self, name)) < lo:
                raise ValueError(f"{name} must be >= {lo}, got {getattr(self, name)}")
        if float(self.merge_cost) < 0:
            raise ValueError(f"merge_cost must be >= 0, got {self.merge_cost}")


@dataclasses.dataclass(frozen=True)
class Plan:
    """Host-built execution plan: everything static for a (matrix, partition)."""

    bs: BlockStructure
    part: Partition
    config: SolverConfig
    n_devices: int
    n_levels: int
    # replicated
    diag: np.ndarray  # (nb+1, B, B) identity at pad slot
    owner: np.ndarray  # (nb+1,) int32, -1 at pad
    indeg: np.ndarray  # (nb+1,) int32 tile in-degree per block row
    ex_rows: np.ndarray  # (E,) ragged rows exchanged per level (levelset/zerocopy)
    ex_boundary: np.ndarray  # (n_boundary or 1,) boundary rows (syncfree/zerocopy)
    # ragged levelset schedules: flat arrays + per-level offsets + width buckets
    lvl_off: np.ndarray  # (T, 3) int32 start of level t in (solve, upd, ex) flats
    lvl_bucket: np.ndarray  # (T,) int32 index into `buckets`
    buckets: tuple  # ((ws, wu, we), ...) level widths, small set (<= MAX_BUCKETS)
    # sharded by leading device axis
    solve_rows: np.ndarray  # (D, S) ragged owned rows per level, pad -1 (levelset)
    upd_tiles: np.ndarray  # (D, U) ragged local tile ids per level, pad ML (levelset)
    local_rows: np.ndarray  # (D, MLR) owned rows, pad nb (syncfree)
    tile_row: np.ndarray  # (D, ML+1) dest block-row per local tile, pad nb
    tile_col: np.ndarray  # (D, ML+1) src block-col per local tile, pad nb
    tiles: np.ndarray  # (D, ML+1, B, B) zero tile at pad slot
    transpose: bool = False  # plan solves a^T x = b (built on reverse_transpose(a))
    # max (rows, tiles) any device schedules in one level — the syncfree runtime
    # frontier can never exceed these (bulk-synchronous sweeps converge
    # level-by-level), so they cap the frontier width ladder
    frontier_caps: tuple = (1, 1)
    # dagpart only: (n_steps+1,) level offsets of the merged supersteps —
    # superstep s runs levels [step_off[s], step_off[s+1]) in one grid step.
    # None (levelset/syncfree) means the identity: one superstep per level.
    step_off: np.ndarray | None = None

    @property
    def n_supersteps(self) -> int:
        """Bulk-synchronous supersteps per solve. Levelset executes one
        superstep per block level; syncfree's runtime frontier discovery also
        converges level-by-level (each superstep solves exactly the rows whose
        in-degree count completed, i.e. the next block level); dagpart merges
        consecutive narrow levels, so it reports the merged step count."""
        if self.step_off is not None:
            return max(0, len(self.step_off) - 1)
        return self.n_levels

    @property
    def n_boundary_rows(self) -> int:
        """Block rows that receive updates from a remote device."""
        return int(self.part.boundary.sum())

    @property
    def comm_bytes_per_solve(self) -> int:
        """Predicted collective payload bytes for one solve (one device's
        share) — the payload the executors actually put on the wire. The old
        global pad-to-max sentinel slots are gone (each boundary row is pulled
        once, at its level's *bucket* width, so only the bucket slack rides
        along), and single-device plans — which execute no collectives at
        all — report exactly 0."""
        if self.n_devices == 1:
            return 0
        B = self.bs.B
        itemsize = 4
        if self.config.comm == "unified":
            # an empty cut means every update is device-local: the executors
            # skip the dense psums entirely (hb.exchange.degenerate)
            if self.n_boundary_rows == 0:
                return 0
            # syncfree additionally psums the per-row in-degree counters each
            # superstep (Alg. 2's s.left_sum AND the dependency counters).
            width = B + 1 if self.config.sched == "syncfree" else B
            return (self.bs.nb + 1) * width * itemsize * self.n_supersteps
        if self.config.sched in LEVELSET_SCHEDS:
            # each boundary row is exchanged exactly once, before its level;
            # levels with an empty cut skip the psum entirely (width 0)
            if self.n_boundary_rows == 0:
                return 0
            ex_width = np.asarray(self.buckets, dtype=np.int64)[self.lvl_bucket, 2]
            return int(ex_width.sum()) * B * itemsize
        return self.n_boundary_rows * (B + 1) * itemsize * self.n_supersteps


def _round_up_to(w: np.ndarray, base: int) -> np.ndarray:
    """Round each width up to the next power of ``base`` (0 stays 0)."""
    out = np.ones_like(w)
    while np.any(out < w):
        out = np.where(out < w, out * base, out)
    return np.where(w == 0, 0, out)


def _bucketize_levels(
    ws: np.ndarray, wu: np.ndarray, we: np.ndarray
) -> tuple[tuple, np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Choose the per-level padded widths for the three ragged schedules.

    Widths round up a geometric ladder; the ladder coarsens (base 2 -> 4 -> 16)
    until the number of distinct (ws, wu, we) combos fits MAX_BUCKETS, and in
    the worst case degenerates to the single global-max bucket (the old dense
    layout). Returns (buckets, bucket_id, bws, bwu, bwe).
    """
    T = ws.shape[0]
    if T == 0:
        # empty schedule: an all-zero bucket keeps every executor branch a
        # no-op — a nonzero width would make the (never-executed) branch
        # index the 0-row offset table at trace time
        z = np.zeros(0, dtype=np.int64)
        return ((0, 0, 0),), np.zeros(0, np.int32), z, z, z
    for base in (2, 4, 16, 0):
        if base:
            bws, bwu, bwe = (_round_up_to(w, base) for w in (ws, wu, we))
        else:  # fallback: one global bucket per schedule (pad-to-max)
            bws, bwu, bwe = (
                np.where(w == 0, 0, max(1, int(w.max()))) for w in (ws, wu, we)
            )
        combos = np.unique(np.stack([bws, bwu, bwe], axis=1), axis=0)
        if combos.shape[0] <= MAX_BUCKETS:
            break
    key = {tuple(int(v) for v in c): i for i, c in enumerate(combos)}
    bucket_id = np.array(
        [key[(int(bws[t]), int(bwu[t]), int(bwe[t]))] for t in range(T)], np.int32
    )
    buckets = tuple(tuple(int(v) for v in c) for c in combos)
    return buckets, bucket_id, bws.astype(np.int64), bwu.astype(np.int64), bwe.astype(np.int64)


def _tiles_by_device(bs: BlockStructure, part: Partition, D: int) -> list:
    """Global tile ids resident on each device (tiles live on their column's
    owner) — the one definition of the device tile-store ordering, shared by
    :func:`build_plan` and :func:`refresh_plan` so a refresh scatters values
    into exactly the slots the compiled executors index."""
    tile_dev = part.owner[bs.off_cols]
    return [np.nonzero(tile_dev == d)[0] for d in range(D)]


def build_plan(
    a: CSR, n_devices: int, config: SolverConfig | None = None,
    *, transpose: bool = False, part: Partition | None = None,
    verify: str | None = None,
) -> Plan:
    """``part`` reuses an existing partition computed for the same sparsity
    (e.g. a zero-fill factor shares its matrix's pattern, so one partition
    serves both plans). Not applicable to transpose plans (reversed order).

    ``verify`` opts into the static plan verifier (``repro.verify``) right
    after construction: a level name (``"basic"``/``"contracts"``/
    ``"strict"``) runs :func:`repro.verify.verify_plan` at that level and
    raises :class:`repro.verify.PlanVerificationError` on any finding of
    error grade (or any finding at all for ``"strict"``). ``None`` defers to
    the ``REPRO_VERIFY`` environment variable (``1`` = strict, unset = off).
    """
    config = config if config is not None else SolverConfig()
    with get_tracer().span("sptrsv.schedule", n_devices=n_devices,
                           sched=config.sched, comm=config.comm,
                           transpose=transpose) as span:
        plan = _build_plan(a, n_devices, config, transpose=transpose, part=part)
        span.set(n_levels=plan.n_levels, n_buckets=len(plan.buckets),
                 comm_bytes_per_solve=plan.comm_bytes_per_solve)
    # late import: repro.verify walks plans, so it imports this module
    from repro.verify import env_verify_level, verify_plan

    level = env_verify_level(default=verify) if verify is None else verify
    if level is not None:
        verify_plan(plan, level=level).raise_if_failed()
    return plan


def _build_plan(
    a: CSR, n_devices: int, config: SolverConfig,
    *, transpose: bool = False, part: Partition | None = None,
) -> Plan:
    if transpose:
        # Solve a^T x = b with the forward-substitution machinery: reverse row
        # and column order of a^T, which is lower-triangular again; rhs/solution
        # are flipped at the DistributedSolver boundary.
        assert part is None, "partition reuse is not valid across reversal"
        a = reverse_transpose(a)
    bs = build_blocks(a, config.block_size)
    cost_weights = None
    if config.calibrate_cost and (config.partition == "malleable"
                                  or config.sched == "dagpart"):
        # calibrated weights drive malleable placement and/or the dagpart
        # merge pass's narrow-level threshold
        from repro.core.costmodel import calibrate_weights

        cost_weights = calibrate_weights(
            config.block_size, backend=config.kernel_backend
        )
    if part is None:
        part = make_partition(
            bs, n_devices, config.partition, config.tasks_per_device,
            cost_weights=cost_weights, cost_R=config.rhs_hint,
        )
    else:
        assert part.owner.shape[0] == bs.nb, "partition/block-structure mismatch"
    nb, B, D = bs.nb, bs.B, n_devices
    T = bs.n_block_levels

    diag = np.concatenate([bs.diag, np.eye(B, dtype=np.float32)[None]], axis=0)
    owner = np.concatenate([part.owner, [-1]]).astype(np.int32)
    indeg = np.concatenate([bs.block_indeg, [0]]).astype(np.int32)

    # --- per-device tile stores (tiles live on their column's owner) ---
    tile_dev = part.owner[bs.off_cols]
    per_dev_tiles = _tiles_by_device(bs, part, D)
    ML = max((t.shape[0] for t in per_dev_tiles), default=0)
    tiles = np.zeros((D, ML + 1, B, B), dtype=np.float32)
    tile_row = np.full((D, ML + 1), nb, dtype=np.int32)
    tile_col = np.full((D, ML + 1), nb, dtype=np.int32)
    local_tile_id = np.full(bs.n_tiles, -1, dtype=np.int64)  # global tile -> local slot
    for d, ids in enumerate(per_dev_tiles):
        k = ids.shape[0]
        tiles[d, :k] = bs.off_tiles[ids]
        tile_row[d, :k] = bs.off_rows[ids]
        tile_col[d, :k] = bs.off_cols[ids]
        local_tile_id[ids] = np.arange(k)

    # --- compacted levelset schedules (ragged flats + width buckets) ---
    lvl = bs.block_level
    rows_by = [[np.nonzero((part.owner == d) & (lvl == t))[0] for t in range(T)] for d in range(D)]
    col_lvl = lvl[bs.off_cols]
    tiles_by = [
        [np.nonzero((tile_dev == d) & (col_lvl == t))[0] for t in range(T)] for d in range(D)
    ]
    b_rows = np.nonzero(part.boundary)[0]
    per_level_ex = [b_rows[lvl[b_rows] == t] for t in range(T)]
    # dagpart: coarsen the level range into merged supersteps, then hoist each
    # merge group's exchange rows into the group's FIRST level slice — the
    # boundary psum runs once per group, right before the merged grid step.
    # Legal by construction: merge_levels only groups levels whose remote
    # sources all solved in an earlier superstep.
    step_off = None
    if config.sched == "dagpart":
        step_off = merge_levels(
            bs, part, merge_width=config.merge_width,
            merge_cost=config.merge_cost,
            cost_weights=cost_weights, cost_R=config.rhs_hint,
        )
        ex_by_level = [np.zeros(0, dtype=b_rows.dtype) for _ in range(T)]
        for k in range(len(step_off) - 1):
            g, h = int(step_off[k]), int(step_off[k + 1])
            ex_by_level[g] = (np.concatenate(per_level_ex[g:h])
                              if h - g > 1 else per_level_ex[g])
    else:
        ex_by_level = per_level_ex

    # per-level required widths (max over devices for the sharded schedules)
    ws = np.array([max(rows_by[d][t].shape[0] for d in range(D)) for t in range(T)],
                  dtype=np.int64) if T else np.zeros(0, np.int64)
    wu = np.array([max(tiles_by[d][t].shape[0] for d in range(D)) for t in range(T)],
                  dtype=np.int64) if T else np.zeros(0, np.int64)
    we = np.array([e.shape[0] for e in ex_by_level], dtype=np.int64)
    buckets, lvl_bucket, bws, bwu, bwe = _bucketize_levels(ws, wu, we)

    lvl_off = np.zeros((T, 3), dtype=np.int32)
    if T:
        lvl_off[:, 0] = np.concatenate([[0], np.cumsum(bws)[:-1]])
        lvl_off[:, 1] = np.concatenate([[0], np.cumsum(bwu)[:-1]])
        lvl_off[:, 2] = np.concatenate([[0], np.cumsum(bwe)[:-1]])
    S = max(1, int(bws.sum())) if T else 1
    U = max(1, int(bwu.sum())) if T else 1
    E = max(1, int(bwe.sum())) if T else 1
    solve_rows = np.full((D, S), -1, dtype=np.int32)
    upd_tiles = np.full((D, U), ML, dtype=np.int32)
    ex_rows = np.full((E,), nb, dtype=np.int32)
    for t in range(T):
        for d in range(D):
            r = rows_by[d][t]
            solve_rows[d, lvl_off[t, 0]: lvl_off[t, 0] + r.shape[0]] = r
            ids = tiles_by[d][t]
            upd_tiles[d, lvl_off[t, 1]: lvl_off[t, 1] + ids.shape[0]] = local_tile_id[ids]
        e = ex_by_level[t]
        ex_rows[lvl_off[t, 2]: lvl_off[t, 2] + e.shape[0]] = e
    ex_boundary = b_rows.astype(np.int32) if b_rows.size else np.full((1,), nb, dtype=np.int32)

    # --- syncfree plan ---
    per_dev_rows = [np.nonzero(part.owner == d)[0] for d in range(D)]
    MLR = max((r.shape[0] for r in per_dev_rows), default=1) or 1
    local_rows = np.full((D, MLR), nb, dtype=np.int32)
    for d, r in enumerate(per_dev_rows):
        local_rows[d, : r.shape[0]] = r

    return Plan(
        bs=bs, part=part, config=config, n_devices=D, n_levels=T,
        diag=diag, owner=owner, indeg=indeg, ex_rows=ex_rows,
        ex_boundary=ex_boundary, lvl_off=lvl_off, lvl_bucket=lvl_bucket,
        buckets=buckets, solve_rows=solve_rows, upd_tiles=upd_tiles,
        local_rows=local_rows, tile_row=tile_row, tile_col=tile_col, tiles=tiles,
        transpose=transpose,
        frontier_caps=(max(1, int(ws.max())) if T else 1,
                       max(1, int(wu.max())) if T else 1),
        step_off=step_off,
    )


def refresh_plan(plan: Plan, a: CSR) -> Plan:
    """Numeric refresh: a new :class:`Plan` carrying ``a``'s values on
    ``plan``'s exact pattern, partition, and compacted schedules.

    This is the *factorize* stage of the analyse/factorize/solve lifecycle:
    ILU-style refactorization changes tile values but never the sparsity, so
    everything symbolic (blocking, levels, partition, bucketized schedules,
    the compiled executors' trace) is reused and only ``diag``/``tiles`` are
    rebuilt — bit-identically to what a fresh :func:`build_plan` on the same
    pattern would produce. Transpose plans refresh through the same row/column
    reversal they were built with.
    """
    with get_tracer().span("sptrsv.refresh", transpose=plan.transpose,
                           n_devices=plan.n_devices):
        if plan.transpose:
            a = reverse_transpose(a)
        bs = refresh_block_values(plan.bs, a)
        B, D = bs.B, plan.n_devices
        diag = np.concatenate([bs.diag, np.eye(B, dtype=np.float32)[None]], axis=0)
        tiles = np.zeros_like(plan.tiles)
        for d, ids in enumerate(_tiles_by_device(bs, plan.part, D)):
            tiles[d, : ids.shape[0]] = bs.off_tiles[ids]
        return dataclasses.replace(plan, bs=bs, diag=diag, tiles=tiles)


# ---------------------------------------------------------------------------
# compacted levelset superstep (shared by local/distributed executors)
# ---------------------------------------------------------------------------


def _compact_level_body(
    plan: Plan, sr, ut, trow, tcol, tiles, diag, b_pad, ex, split_delta=False
):
    """Return the compacted superstep body shared by all levelset executors.

    One branch is built per occurring width-bucket combo and dispatched with
    ``lax.switch`` on the level's bucket id; each branch slices its level's
    rows/tiles at the bucket width (static sizes, dynamic offsets), so the
    solve/update/exchange work scales with the level's bucket instead of the
    global max. ``ex is None`` disables the zero-copy boundary pull.

    Carry is ``(acc, x)``, or ``(acc, delta, x)`` with ``split_delta`` — then
    tile updates land in ``delta`` (the unified executor's not-yet-exchanged
    contributions; incompatible with ``ex``) while solves read ``acc + delta``:
    ``acc`` carries the psum-folded remote contributions, ``delta`` makes
    local contributions from earlier levels of the *same* merged superstep
    visible (dagpart runs several levels between dense exchanges). For
    unmerged levelset supersteps ``delta`` is exactly ``+0.0`` at solve time,
    so subtracting it is bit-inert.
    """
    assert not (split_delta and ex is not None)
    cfg = plan.config
    nb = plan.bs.nb
    off = jnp.asarray(plan.lvl_off)
    bucket_id = jnp.asarray(plan.lvl_bucket)

    def make_branch(w_s: int, w_u: int, w_e: int):
        def branch(t, carry):
            if split_delta:
                acc, delta, x = carry
            else:
                acc, x = carry
            # named_scope annotations are metadata-only (always present in the
            # traced program) so profiles line up with the host-side spans and
            # toggling tracing can never retrace a compiled executor
            if ex is not None and w_e > 0:
                with jax.named_scope("sptrsv.exchange"):
                    # lazy exactly-once pull: combine partial accumulators for
                    # the boundary rows of THIS level right before solving them
                    rows = jax.lax.dynamic_slice(ex, (off[t, 2],), (w_e,))
                    acc = acc.at[rows].set(jax.lax.psum(acc[rows], AXIS))
            if w_s > 0:
                with jax.named_scope("sptrsv.level_solve"):
                    rows = jax.lax.dynamic_slice(sr, (off[t, 0],), (w_s,))
                    safe = jnp.where(rows < 0, nb, rows)
                    rhs = b_pad[safe] - acc[safe]
                    if split_delta:
                        rhs = rhs - delta[safe]
                    xs = ops.batched_block_trsv(
                        diag[safe], rhs, backend=cfg.kernel_backend
                    )
                    x = x.at[safe].set(
                        jnp.where(ops.bcast_trailing(rows >= 0, xs), xs, x[safe])
                    )
            if w_u > 0:
                with jax.named_scope("sptrsv.tile_update"):
                    tids = jax.lax.dynamic_slice(ut, (off[t, 1],), (w_u,))
                    prods = ops.batched_block_gemv(
                        tiles[tids], x[tcol[tids]], backend=cfg.kernel_backend,
                        group=cfg.gemv_group,
                    )
                    if split_delta:
                        delta = delta.at[trow[tids]].add(prods)
                    else:
                        acc = acc.at[trow[tids]].add(prods)
            return (acc, delta, x) if split_delta else (acc, x)

        return branch

    branches = [make_branch(*b) for b in plan.buckets]
    if len(branches) == 1:
        return lambda t, carry: branches[0](t, carry)
    return lambda t, carry: jax.lax.switch(bucket_id[t], branches, t, carry)


# ---------------------------------------------------------------------------
# fused superstep megakernel executors (kernel_backend="fused")
# ---------------------------------------------------------------------------


def level_widths(plan: Plan) -> np.ndarray:
    """(T, 3) per-level (solve, update, exchange) bucket widths."""
    return np.asarray(plan.buckets, dtype=np.int64)[plan.lvl_bucket]


def step_offsets(plan: Plan) -> np.ndarray:
    """(n_steps + 1,) level offsets of the plan's supersteps. Identity
    (one level per superstep) for levelset/syncfree; the merge pass's
    coarsening for dagpart."""
    if plan.step_off is not None:
        return np.asarray(plan.step_off, dtype=np.int32)
    return np.arange(plan.n_levels + 1, dtype=np.int32)


def step_widths(plan: Plan) -> np.ndarray:
    """(n_steps, 3) per-superstep (solve, update, exchange) schedule widths —
    each superstep's contiguous flat slice sums its levels' bucket widths.
    Identical to :func:`level_widths` for unmerged plans."""
    wid = level_widths(plan)
    so = step_offsets(plan).astype(np.int64)
    cs = np.zeros((plan.n_levels + 1, 3), dtype=np.int64)
    np.cumsum(wid, axis=0, out=cs[1:])
    return cs[so[1:]] - cs[so[:-1]]


def fused_segments(plan: Plan) -> np.ndarray:
    """(n_seg, 2) ``[lo, hi)`` level ranges, one fused launch each.

    Collectives cannot live inside a Pallas kernel, so the fused executor
    splits the schedule exactly before every level whose boundary rows must be
    combined: zerocopy breaks at levels with a non-empty exchange bucket (for
    dagpart those are exactly the merge-group starts, so segment boundaries
    always align to superstep boundaries), unified (dense psum every
    superstep) degenerates to one segment per *superstep* — per level when
    unmerged, per merge group for dagpart — and single-device / empty-cut
    plans fuse the whole solve into one launch.
    """
    T = plan.n_levels
    if T == 0:
        return np.zeros((0, 2), dtype=np.int32)
    cfg = plan.config
    if cfg.comm == "unified" and plan.n_devices > 1 and plan.n_boundary_rows > 0:
        so = step_offsets(plan)
        return np.stack([so[:-1], so[1:]], axis=1).astype(np.int32)
    wid = level_widths(plan)
    starts = [0]
    if cfg.comm == "zerocopy" and plan.n_devices > 1 and plan.n_boundary_rows > 0:
        starts += [t for t in range(1, T) if wid[t, 2] > 0]
    starts = np.unique(np.asarray(starts, dtype=np.int32))
    his = np.concatenate([starts[1:], [T]]).astype(np.int32)
    return np.stack([starts, his], axis=1)


# ---------------------------------------------------------------------------
# streaming HBM tile store (kernel_backend="fused_streamed", or auto-upgrade)
# ---------------------------------------------------------------------------

# bytes of resident store above which "fused" streams; the resident kernel
# asks for this plus its row scratch and 16 MiB of headroom
# (superstep_call's vmem_limit_bytes), far inside a v5e core's 128 MiB
DEFAULT_STREAM_VMEM_LIMIT = 8 * 2**20


def stream_vmem_limit() -> int:
    """Resident-store VMEM budget (bytes) above which ``kernel_backend="fused"``
    auto-upgrades to the streaming tile store.

    Resolution order: the ``REPRO_STREAM_VMEM_LIMIT`` env override (an int;
    lower it to force streaming), then the per-platform threshold calibrated
    from the auto-tuner's probe-solve measurements
    (:func:`repro.obs.calibration.calibrated_stream_limit` — when the store
    holds paired fused / fused_streamed timings, the crossover moves with the
    measured streaming overhead), then the fixed 8 MiB default."""
    env = os.environ.get("REPRO_STREAM_VMEM_LIMIT")
    if env is not None:
        return int(env)
    from repro.obs.calibration import calibrated_stream_limit

    lim = calibrated_stream_limit()
    return DEFAULT_STREAM_VMEM_LIMIT if lim is None else lim


def stream_widths(plan: Plan) -> tuple[tuple, tuple]:
    """Static DMA ladders: the distinct per-*superstep* (solve, update)
    schedule widths (:func:`step_widths` — equal to the per-level bucket
    widths for unmerged plans; summed over a merge group for dagpart, whose
    grid steps fetch a whole group's slice in one burst). The streamed kernel
    unrolls one predicated async-copy per ladder entry, so DMA start/wait
    always agree on the transfer size and the bytes moved equal the compacted
    schedule footprint (no pad-to-max bursts)."""
    if plan.n_levels == 0:
        return (0,), (0,)
    wid = step_widths(plan)
    return (tuple(sorted({int(w) for w in wid[:, 0]})),
            tuple(sorted({int(w) for w in wid[:, 1]})))


def streamed_stores(plan: Plan) -> tuple[np.ndarray, np.ndarray]:
    """Schedule-ordered ``(diag_sched, tiles_sched)`` stores for streaming.

    ``diag_sched[d, k]`` is the diagonal tile of ``solve_rows[d, k]`` and
    ``tiles_sched[d, k]`` the tile of slot ``upd_tiles[d, k]`` — i.e. the
    stores permuted into compacted-schedule order, so level ``t``'s slice is
    the contiguous run ``[lvl_off[t], lvl_off[t] + width)`` and the kernel's
    per-level DMA is a single contiguous burst. Pad slots materialize the
    identity diagonal / zero tile, keeping the streamed arithmetic
    bit-identical to the resident kernel's pad handling.
    """
    nb = plan.bs.nb
    safe = np.where(plan.solve_rows < 0, nb, plan.solve_rows)  # (D, S)
    diag_sched = np.ascontiguousarray(plan.diag[safe])
    tiles_sched = np.ascontiguousarray(
        np.stack([plan.tiles[d][plan.upd_tiles[d]]
                  for d in range(plan.n_devices)]))
    return diag_sched, tiles_sched


def fused_vmem_bytes(plan: Plan, R: int = 1, *, streamed: bool = False) -> int:
    """VMEM bytes one fused superstep launch allocates (what
    :func:`repro.kernels.superstep.superstep_call` reserves).

    Resident: the whole ``diag`` + per-device ``tiles`` stores sit in VMEM
    (one copy), so the footprint grows with the total tile count. Streamed:
    the stores stay in HBM and only two double-buffers sized by the *widest
    superstep slice* are resident (per level when unmerged, per merge group
    for dagpart). The rhs and the carries stay in HBM in both; only the
    row scratch they pass through is counted.
    """
    B = plan.bs.B
    itemsize = 4
    vecs = row_scratch_bytes(B, R, itemsize)
    if streamed:
        if plan.n_levels:
            wid = step_widths(plan)
            ws, wu = int(wid[:, 0].max()), int(wid[:, 1].max())
        else:
            ws = wu = 0
        store = 2 * (max(1, ws) + max(1, wu)) * B * B * itemsize
    else:
        store = (plan.diag.shape[0] + plan.tiles.shape[1]) * B * B * itemsize
    return store + vecs


def stream_dma_bytes_per_solve(plan: Plan) -> int:
    """HBM→VMEM bytes the streamed megakernel moves per solve (one device):
    every level's diag + tile slice exactly once, at its bucket width."""
    if plan.n_levels == 0:
        return 0
    wid = level_widths(plan)
    return int(wid[:, 0].sum() + wid[:, 1].sum()) * plan.bs.B * plan.bs.B * 4


def fused_streaming(plan: Plan, R: int | None = None) -> bool:
    """Whether ``plan``'s fused levelset executor uses the streaming store:
    explicitly (``kernel_backend="fused_streamed"``) or automatically, when
    the resident store's estimated footprint exceeds
    :func:`stream_vmem_limit` — so ``"auto"`` sessions and large plans pick
    streaming without user action. Syncfree plans never stream (the frontier
    executor has no resident tile store problem)."""
    if plan.config.sched not in LEVELSET_SCHEDS:
        return False
    backend = ops.executor_backend(plan.config.kernel_backend)
    if backend == "fused_streamed":
        return True
    if backend != "fused":
        return False
    R = plan.config.rhs_hint if R is None else R
    return fused_vmem_bytes(plan, R, streamed=False) > stream_vmem_limit()


def dispatch_stats(plan: Plan) -> dict:
    """Predicted per-solve dispatch counts for the two levelset executors.

    The switch path re-dispatches gather+TRSV and GEMV+scatter per level (plus
    the boundary psum); the fused path is one megakernel launch per exchange
    segment. This is the launch-count model behind the fused-vs-switch bench
    columns — measured times ride next to it, the counts are exact.
    ``streamed``/``fused_vmem_bytes``/``stream_dma_bytes`` report the fused
    executor's memory plan: whether the tile store streams from HBM, the
    estimated VMEM footprint of the selected variant, and the per-solve DMA
    traffic the streaming pays for that residency.

    Scheduling columns: ``supersteps`` is the plan's bulk-synchronous step
    count, ``supersteps_levelset`` the unmerged baseline (the block level
    count — identical unless ``sched="dagpart"`` merged something), and
    ``superstep_reduction`` their ratio. ``schedule_table_bytes`` is the
    compacted-schedule footprint: every host-built table the executors
    index (flats, offsets, buckets, stores' index maps, the step table).
    """
    wid = level_widths(plan)
    cfg = plan.config
    has_ex = (cfg.comm == "zerocopy" and plan.n_devices > 1
              and plan.n_boundary_rows > 0)
    unified = (cfg.comm == "unified" and plan.n_devices > 1
               and plan.n_boundary_rows > 0)
    n_ex = (int((wid[:, 2] > 0).sum()) if has_ex
            else (plan.n_supersteps if unified else 0))
    switch = int(2 * (wid[:, 0] > 0).sum() + 2 * (wid[:, 1] > 0).sum()) + n_ex
    n_seg = int(len(fused_segments(plan)))
    streamed = fused_streaming(plan)
    n_steps = plan.n_supersteps
    return {"switch_dispatches": switch, "fused_launches": n_seg,
            "exchanges": n_ex, "streamed": streamed,
            "fused_vmem_bytes": fused_vmem_bytes(
                plan, plan.config.rhs_hint, streamed=streamed),
            "stream_dma_bytes": stream_dma_bytes_per_solve(plan) if streamed else 0,
            "supersteps": n_steps,
            "supersteps_levelset": plan.n_levels,
            "superstep_reduction": (plan.n_levels / n_steps) if n_steps else 1.0,
            "schedule_table_bytes": schedule_table_bytes(plan)}


def schedule_table_bytes(plan: Plan) -> int:
    """Bytes of the host-built schedule tables the executors index — the
    compacted-schedule footprint that rides to the device as jit arguments
    (and, for the streamed kernel, bounds the scalar-prefetch SMEM traffic).
    Merging supersteps shrinks the exchange flat (one group slice instead of
    many per-level slices) and adds only the tiny step table."""
    arrs = [plan.lvl_off, plan.lvl_bucket, plan.solve_rows, plan.upd_tiles,
            plan.ex_rows, plan.ex_boundary, plan.local_rows,
            plan.tile_row, plan.tile_col]
    if plan.step_off is not None:
        arrs.append(plan.step_off)
    return int(sum(np.asarray(x).nbytes for x in arrs))


def _fused_device_args(plan: Plan, d: int = 0):
    """Device-local schedule arrays for a direct (non-shard_map) fused call."""
    return (
        jnp.asarray(plan.lvl_off), jnp.asarray(level_widths(plan)),
        jnp.asarray(plan.solve_rows[d]), jnp.asarray(plan.upd_tiles[d]),
        jnp.asarray(plan.tile_row[d]), jnp.asarray(plan.tile_col[d]),
        jnp.asarray(plan.diag), jnp.asarray(plan.tiles[d]),
    )


def _fused_levelset_device_fn(plan: Plan):
    """Megakernel levelset executor: one Pallas launch per exchange segment.

    Mirrors the ``lax.switch`` executors' arithmetic exactly — the same
    per-level exchange (packed psum at the level's bucket width, or the
    unified dense delta psum) runs *between* launches, and everything between
    two exchanges fuses into a single scalar-prefetched superstep kernel.

    When :func:`fused_streaming` selects the streaming store, the
    ``diag``/``tiles`` arguments are the *schedule-ordered* per-device stores
    from :func:`streamed_stores` (both sharded) and every launch double-buffers
    its levels' slices from HBM instead of holding the stores in VMEM.
    """
    cfg = plan.config
    nb, T, D = plan.bs.nb, plan.n_levels, plan.n_devices
    # both paths gate on a non-empty cut: with every update device-local the
    # psums would only move zeros, so the whole solve fuses into one launch
    unified = cfg.comm == "unified" and D > 1 and plan.n_boundary_rows > 0
    has_ex = cfg.comm == "zerocopy" and D > 1 and plan.n_boundary_rows > 0
    segs = fused_segments(plan)
    n_seg = max(1, len(segs))
    so = step_offsets(plan)
    # the kernel grids over SUPERSTEPS (one level each for unmerged plans, a
    # whole merge group for dagpart); segment boundaries always align to
    # superstep starts, so each segment maps to a contiguous step range
    step_of = (np.repeat(np.arange(len(so) - 1), np.diff(so))
               if T else np.zeros(0, np.int64))
    if len(segs):
        s_lo = step_of[segs[:, 0]]
        seg_len = step_of[segs[:, 1] - 1] + 1 - s_lo
    else:
        s_lo = seg_len = np.zeros(1, np.int64)
    grid = max(1, int(seg_len.max(initial=0)))
    wid = level_widths(plan)
    interp = ops.interpret_mode()
    streamed = fused_streaming(plan)
    sw, uw = stream_widths(plan) if streamed else ((), ())
    seg_tab = np.stack([s_lo, seg_len], axis=1).astype(np.int32)
    if has_ex and len(segs):
        # per-segment exchange width = the first level's exchange bucket
        ex_w = wid[segs[:, 0], 2]
        ex_ladder = sorted({int(w) for w in ex_w})
        ex_sel = np.array([ex_ladder.index(int(w)) for w in ex_w], np.int32)
        ex_off = plan.lvl_off[segs[:, 0], 2].astype(np.int32)

    def fn(sr, ut, trow, tcol, tiles, owner_mask, diag, ex, b_pad):
        sr, ut = sr[0], ut[0]
        trow, tcol, tiles, owner_mask = trow[0], tcol[0], tiles[0], owner_mask[0]
        if streamed:
            diag = diag[0]  # schedule-ordered stores are per-device (sharded)
        off_a = jnp.asarray(plan.lvl_off)
        wid_a = jnp.asarray(wid)
        seg_a = jnp.asarray(seg_tab)
        stp_a = jnp.asarray(so.astype(np.int32))
        multi = b_pad.ndim == 3
        b_pad = to_rows(b_pad)  # the kernel's (nb+1, R, B) row panels
        z = jnp.zeros_like(b_pad)

        if has_ex:
            ex_off_a = jnp.asarray(ex_off)
            ex_sel_a = jnp.asarray(ex_sel)

            def make_branch(w):
                def br(s, acc):
                    if w == 0:
                        return acc
                    rows = jax.lax.dynamic_slice(ex, (ex_off_a[s],), (w,))
                    return acc.at[rows].set(jax.lax.psum(acc[rows], AXIS))

                return br

            ex_branches = [make_branch(w) for w in ex_ladder]

        def body(s, carry):
            if unified:
                acc, delta, x = carry
                with jax.named_scope("sptrsv.exchange"):
                    acc = acc + jax.lax.psum(delta, AXIS)
                    delta = jnp.zeros_like(delta)
                with jax.named_scope("sptrsv.superstep"):
                    return superstep_call(
                        seg_a[s], off_a, wid_a, sr, ut, trow, tcol, diag, tiles,
                        b_pad, acc, x, delta, stp=stp_a, grid=grid,
                        split_delta=True, interpret=interp, stream=streamed,
                        solve_widths=sw, upd_widths=uw,
                    )
            acc, x = carry
            if has_ex:
                with jax.named_scope("sptrsv.exchange"):
                    if len(ex_branches) == 1:
                        acc = ex_branches[0](s, acc)
                    else:
                        acc = jax.lax.switch(ex_sel_a[s], ex_branches, s, acc)
            with jax.named_scope("sptrsv.superstep"):
                return superstep_call(
                    seg_a[s], off_a, wid_a, sr, ut, trow, tcol, diag, tiles,
                    b_pad, acc, x, stp=stp_a, grid=grid, interpret=interp,
                    stream=streamed, solve_widths=sw, upd_widths=uw,
                )

        init = (z, z, z) if unified else (z, z)
        carry = jax.lax.fori_loop(0, n_seg, body, init)
        x = from_rows(carry[-1], multi)
        with jax.named_scope("sptrsv.gather"):
            xg = x * ops.bcast_trailing(owner_mask, x)
            if D > 1:
                xg = jax.lax.psum(xg, AXIS)
        return xg[:nb]

    return fn


# ---------------------------------------------------------------------------
# single-device levelset executor (the "1-GPU" baseline and structural oracle)
# ---------------------------------------------------------------------------


def solve_local(plan: Plan, b_blocks: jax.Array) -> jax.Array:
    """Level-scheduled solve on one device. b_blocks: (nb, B) -> x (nb, B)."""
    nb = plan.bs.nb
    b_pad = jnp.concatenate(
        [b_blocks, jnp.zeros((1,) + b_blocks.shape[1:], b_blocks.dtype)]
    )
    if ops.is_fused(plan.config.kernel_backend):
        # the whole solve is one megakernel launch (no exchanges on 1 device)
        off, wid, sr, ut, trow, tcol, diag, tiles = _fused_device_args(plan, 0)
        streamed = fused_streaming(plan)
        sw, uw = ((), ())
        if streamed:
            diag_s, tiles_s = streamed_stores(plan)
            diag, tiles = jnp.asarray(diag_s[0]), jnp.asarray(tiles_s[0])
            sw, uw = stream_widths(plan)
        b_rows = to_rows(b_pad)
        acc0 = jnp.zeros_like(b_rows)
        seg = jnp.array([0, plan.n_supersteps], jnp.int32)
        stp = jnp.asarray(step_offsets(plan))
        _, x = superstep_call(
            seg, off, wid, sr, ut, trow, tcol, diag, tiles, b_rows, acc0, acc0,
            stp=stp, grid=max(1, plan.n_supersteps),
            interpret=ops.interpret_mode(),
            stream=streamed, solve_widths=sw, upd_widths=uw,
        )
        return from_rows(x, b_pad.ndim == 3)[:nb]
    diag = jnp.asarray(plan.diag)
    sr = jnp.asarray(plan.solve_rows[0])
    ut = jnp.asarray(plan.upd_tiles[0])
    trow = jnp.asarray(plan.tile_row[0])
    tcol = jnp.asarray(plan.tile_col[0])
    tiles = jnp.asarray(plan.tiles[0])
    body = _compact_level_body(plan, sr, ut, trow, tcol, tiles, diag, b_pad, ex=None)
    acc0 = jnp.zeros_like(b_pad)
    _, x = jax.lax.fori_loop(0, plan.n_levels, body, (acc0, acc0))
    return x[:nb]


# ---------------------------------------------------------------------------
# distributed executors (shard_map over AXIS)
# ---------------------------------------------------------------------------


def _levelset_device_fn(plan: Plan):
    cfg = plan.config
    nb, T = plan.bs.nb, plan.n_levels
    # pad-traffic gate: only exchange when a psum can carry real data — the
    # partition actually cut boundary rows AND there is a peer to combine with
    has_ex = (
        cfg.comm == "zerocopy" and plan.n_devices > 1 and plan.n_boundary_rows > 0
    )

    def fn(sr, ut, trow, tcol, tiles, owner_mask, diag, ex, b_pad):
        # leading device dim of sharded operands is 1 inside shard_map
        sr, ut = sr[0], ut[0]
        trow, tcol, tiles, owner_mask = trow[0], tcol[0], tiles[0], owner_mask[0]
        body = _compact_level_body(
            plan, sr, ut, trow, tcol, tiles, diag, b_pad,
            ex=ex if has_ex else None,
        )
        acc0 = jnp.zeros_like(b_pad)
        _, x = jax.lax.fori_loop(0, T, body, (acc0, acc0))
        with jax.named_scope("sptrsv.gather"):
            xg = x * ops.bcast_trailing(owner_mask, x)
            if plan.n_devices > 1:
                xg = jax.lax.psum(xg, AXIS)
        return xg[:nb]

    return fn


def _levelset_unified_device_fn(plan: Plan):
    """Unified-memory analogue: delta accumulators + full-array psum per
    *superstep* — once per level when unmerged, once per merge group for
    dagpart (the levels inside a group see each other's local contributions
    through ``delta``, which solves read alongside ``acc``)."""
    nb = plan.bs.nb
    so = step_offsets(plan)
    n_steps = plan.n_supersteps

    def fn(sr, ut, trow, tcol, tiles, owner_mask, diag, ex, b_pad):
        del ex  # unified ignores the packed exchange schedule
        sr, ut = sr[0], ut[0]
        trow, tcol, tiles, owner_mask = trow[0], tcol[0], tiles[0], owner_mask[0]
        step = _compact_level_body(
            plan, sr, ut, trow, tcol, tiles, diag, b_pad, ex=None, split_delta=True
        )
        stp = jnp.asarray(so.astype(np.int32))

        def body(s, carry):
            acc_red, delta, x = carry
            # dense exchange of everything accumulated since the last
            # superstep — the page-bouncing s.left_sum traffic of Alg. 2.
            with jax.named_scope("sptrsv.exchange"):
                acc_red = acc_red + jax.lax.psum(delta, AXIS)
                delta = jnp.zeros_like(delta)
            return jax.lax.fori_loop(stp[s], stp[s + 1], step,
                                     (acc_red, delta, x))

        z = jnp.zeros_like(b_pad)
        _, _, x = jax.lax.fori_loop(0, n_steps, body, (z, z, z))
        with jax.named_scope("sptrsv.gather"):
            return jax.lax.psum(x * ops.bcast_trailing(owner_mask, x), AXIS)[:nb]

    return fn


def _frontier_ladder(cap: int) -> tuple:
    """Geometric width ladder ``1, b, b², ..., cap`` for the runtime frontier;
    the base coarsens (2 -> 4 -> 16) until the ladder fits MAX_BUCKETS."""
    cap = max(1, int(cap))
    for base in (2, 4, 16):
        lad = sorted({cap} | {base ** k for k in range(64) if base ** k < cap})
        if len(lad) <= MAX_BUCKETS:
            return tuple(int(w) for w in lad)
    return (cap,)


def _syncfree_device_fn(plan: Plan, frontier: bool = False):
    """Runtime-frontier solver: no level analysis, in-degree counters drive it.

    ``frontier=False`` is the paper-faithful dense scan: every sweep solves a
    masked TRSV over *all* local rows and a masked GEMV over *all* local
    tiles. ``frontier=True`` (the ``fused`` backend) compacts the ready set
    each sweep and dispatches one ``lax.switch`` branch at the smallest
    bucket width covering it — the same width-ladder trick as the compacted
    levelset schedules, keyed on the *runtime* frontier size, so per-sweep
    work scales with the frontier, not with the device's whole row set. The
    ladder is capped by ``plan.frontier_caps`` (a bulk-synchronous sweep
    solves exactly one block level, so the frontier never exceeds the widest
    per-device level).
    """
    cfg = plan.config
    nb, B = plan.bs.nb, plan.bs.B
    zerocopy = cfg.comm == "zerocopy"
    multi = plan.n_devices > 1
    # with no boundary rows every tile's contribution is device-local, so any
    # exchange (packed psum of the [nb] sentinel, or unified's dense
    # all-reduce of all-zero deltas) would move no information — skip it and
    # the delta/dcnt split entirely
    has_ex = zerocopy and multi and plan.n_boundary_rows > 0
    needs_ex = multi and plan.n_boundary_rows > 0
    MLR = plan.local_rows.shape[1]
    MLT = plan.tiles.shape[1]  # ML + 1 (pad slot holds the zero tile, dest nb)
    lad_s = _frontier_ladder(min(plan.frontier_caps[0], MLR))
    lad_u = _frontier_ladder(min(plan.frontier_caps[1], MLT))

    def fn(lr, trow, tcol, tiles, owner_mask, diag, indeg, exb, b_pad):
        lr = lr[0]
        trow, tcol, tiles, owner_mask = trow[0], tcol[0], tiles[0], owner_mask[0]
        me = jax.lax.axis_index(AXIS) if multi else 0
        ldiag = diag[lr]
        lb = b_pad[lr]
        lown = owner_mask[lr] > 0  # valid (non-pad) local rows
        dest_mine = owner_mask[trow] > 0  # tile dest owned by this device
        iota_l = jnp.arange(MLR, dtype=jnp.int32)
        iota_t = jnp.arange(MLT, dtype=jnp.int32)
        lad_s_a = jnp.asarray(lad_s, jnp.int32)
        lad_u_a = jnp.asarray(lad_u, jnp.int32)

        def solve_branch(w):
            def br(order, acc_red, x):
                idx = jax.lax.dynamic_slice(order, (0,), (w,))
                valid = idx < MLR
                rows = jnp.where(valid, lr[jnp.where(valid, idx, 0)], nb)
                xs = ops.batched_block_trsv(
                    diag[rows], b_pad[rows] - acc_red[rows],
                    backend=cfg.kernel_backend,
                )
                return x.at[rows].set(
                    jnp.where(ops.bcast_trailing(valid, xs), xs, x[rows])
                )

            return br

        def upd_branch(w):
            def br(torder, x, acc_red, delta, cnt_red, dcnt):
                tid = jax.lax.dynamic_slice(torder, (0,), (w,))
                valid = tid < MLT
                tid = jnp.where(valid, tid, MLT - 1)  # pad: zero tile, dest nb
                rd = trow[tid]
                dmine = dest_mine[tid]
                prods = ops.batched_block_gemv(
                    tiles[tid], x[tcol[tid]], backend=cfg.kernel_backend,
                    group=cfg.gemv_group,
                )
                pm = jnp.where(ops.bcast_trailing(valid, prods), prods, 0.0)
                cm = valid.astype(jnp.int32)
                if needs_ex:
                    dm = ops.bcast_trailing(dmine, pm)
                    acc_red = acc_red.at[rd].add(jnp.where(dm, pm, 0.0))
                    cnt_red = cnt_red.at[rd].add(jnp.where(dmine, cm, 0))
                    delta = delta.at[rd].add(jnp.where(dm, 0.0, pm))
                    dcnt = dcnt.at[rd].add(jnp.where(dmine, 0, cm))
                else:
                    acc_red = acc_red.at[rd].add(pm)
                    cnt_red = cnt_red.at[rd].add(cm)
                return acc_red, delta, cnt_red, dcnt

            return br

        solve_branches = [solve_branch(w) for w in lad_s]
        upd_branches = [upd_branch(w) for w in lad_u]

        def cond(state):
            return jnp.logical_not(state["done"])

        def body(state):
            acc_red, delta, cnt_red, dcnt, solved, x = (
                state["acc_red"], state["delta"], state["cnt_red"],
                state["dcnt"], state["solved"], state["x"],
            )
            # 1. frontier: owned, unsolved, all dependencies counted in
            ready = jnp.logical_and(
                jnp.logical_and(lown, jnp.logical_not(solved[lr])),
                cnt_red[lr] == indeg[lr],
            )
            if frontier:
                # 2. compact the frontier, solve at its bucket width
                with jax.named_scope("sptrsv.level_solve"):
                    order = jnp.sort(jnp.where(ready, iota_l, MLR).astype(jnp.int32))
                    sel = jnp.sum((lad_s_a < jnp.sum(ready)).astype(jnp.int32))
                    if len(solve_branches) == 1:
                        x = solve_branches[0](order, acc_red, x)
                    else:
                        x = jax.lax.switch(sel, solve_branches, order, acc_red, x)
                solved = solved.at[lr].set(jnp.logical_or(solved[lr], ready))
                # 3. compact the tiles sourced at this frontier, update at width
                just = jnp.zeros((nb + 1,), jnp.bool_).at[lr].set(ready)
                tmask = just[tcol]
                torder = jnp.sort(jnp.where(tmask, iota_t, MLT).astype(jnp.int32))
                usel = jnp.sum((lad_u_a < jnp.sum(tmask)).astype(jnp.int32))
                if len(upd_branches) == 1:
                    acc_red, delta, cnt_red, dcnt = upd_branches[0](
                        torder, x, acc_red, delta, cnt_red, dcnt)
                else:
                    acc_red, delta, cnt_red, dcnt = jax.lax.switch(
                        usel, upd_branches, torder, x, acc_red, delta,
                        cnt_red, dcnt)
            else:
                # 2. solve the frontier (masked dense over local rows)
                with jax.named_scope("sptrsv.level_solve"):
                    xs = ops.batched_block_trsv(
                        ldiag, lb - acc_red[lr], backend=cfg.kernel_backend
                    )
                    x = x.at[lr].set(
                        jnp.where(ops.bcast_trailing(ready, xs), xs, x[lr]))
                solved = solved.at[lr].set(jnp.logical_or(solved[lr], ready))
                # 3. updates from tiles whose source column solved THIS superstep
                just = jnp.zeros((nb + 1,), jnp.bool_).at[lr].set(ready)
                tmask = just[tcol]
                prods = ops.batched_block_gemv(
                    tiles, x[tcol], backend=cfg.kernel_backend, group=cfg.gemv_group
                )
                pm = jnp.where(ops.bcast_trailing(tmask, prods), prods, 0.0)
                cm = tmask.astype(jnp.int32)
                if needs_ex:
                    dm = ops.bcast_trailing(dest_mine, pm)
                    acc_red = acc_red.at[trow].add(jnp.where(dm, pm, 0.0))
                    cnt_red = cnt_red.at[trow].add(jnp.where(dest_mine, cm, 0))
                    delta = delta.at[trow].add(jnp.where(dm, 0.0, pm))
                    dcnt = dcnt.at[trow].add(jnp.where(dest_mine, 0, cm))
                else:
                    # single device, or zerocopy with an empty cut: every
                    # tile's destination is local, no exchange needed
                    acc_red = acc_red.at[trow].add(pm)
                    cnt_red = cnt_red.at[trow].add(cm)
            # 4. exchange remote contributions
            if needs_ex:
                with jax.named_scope("sptrsv.exchange"):
                    if has_ex:  # packed boundary rows only
                        red = jax.lax.psum(delta[exb], AXIS)
                        redc = jax.lax.psum(dcnt[exb], AXIS)
                        acc_red = acc_red.at[exb].add(red)
                        cnt_red = cnt_red.at[exb].add(redc)
                        delta = delta.at[exb].set(0.0)
                        dcnt = dcnt.at[exb].set(0)
                    else:  # unified: dense all-reduce of values and counters
                        acc_red = acc_red + jax.lax.psum(delta, AXIS)
                        cnt_red = cnt_red + jax.lax.psum(dcnt, AXIS)
                        delta = jnp.zeros_like(delta)
                        dcnt = jnp.zeros_like(dcnt)
            # 5. global termination check
            remaining = jnp.sum(jnp.logical_and(lown, jnp.logical_not(solved[lr])))
            if multi:
                remaining = jax.lax.psum(remaining, AXIS)
            return dict(
                acc_red=acc_red, delta=delta, cnt_red=cnt_red, dcnt=dcnt,
                solved=solved, x=x, done=remaining == 0,
            )

        zf = jnp.zeros_like(b_pad)
        zi = jnp.zeros((nb + 1,), jnp.int32)
        state = dict(
            acc_red=zf, delta=zf, cnt_red=zi, dcnt=zi,
            solved=jnp.zeros((nb + 1,), jnp.bool_), x=zf,
            done=jnp.asarray(False),
        )
        state = jax.lax.while_loop(cond, body, state)
        with jax.named_scope("sptrsv.gather"):
            xg = state["x"] * ops.bcast_trailing(owner_mask, state["x"])
            if multi:
                xg = jax.lax.psum(xg, AXIS)
        return xg[:nb]

    return fn


class DistributedSolver:
    """Compiled multi-device SpTRSV for one (matrix, partition, mesh).

    One instance is compiled once and invoked many times — the amortized
    regime of preconditioned Krylov loops. ``n_solves`` counts invocations
    (each multi-RHS panel counts once: one compiled solve serves R systems).

    Always-on instruments in ``registry`` (default: the process-wide one):
    ``executor.solves`` counts invocations; ``executor.h2d_bytes`` adds, per
    invocation, the bytes of every host-resident (numpy) argument and of a
    right-hand side staged from numpy — a device-resident argument counts 0;
    ``executor.launch_us`` observes the launch phase (concatenate to the
    return of the compiled call) of calls that run an already-compiled
    program. The first call of each right-hand-side shape traces and
    compiles; its cost is ``jit.compile_s``, not launch time.
    """

    def __init__(self, plan: Plan, mesh: jax.sharding.Mesh,
                 registry: MetricsRegistry | None = None):
        assert mesh.devices.size == plan.n_devices, (mesh.devices.size, plan.n_devices)
        self.plan = plan
        self.mesh = mesh
        self.n_solves = 0
        reg = registry if registry is not None else get_registry()
        self._solves = reg.counter("executor.solves")
        self._h2d_bytes = reg.counter("executor.h2d_bytes")
        self._launch_us = reg.histogram("executor.launch_us")
        self._compiled: set = set()  # (shape, dtype) of right-hand sides seen
        nb = plan.bs.nb
        D = plan.n_devices
        owner_mask = np.zeros((D, nb + 1), np.float32)
        for d in range(D):
            owner_mask[d, :nb] = (plan.part.owner == d).astype(np.float32)
        self._owner_mask = owner_mask

        sharded = P(AXIS)
        repl = P()
        backend = ops.executor_backend(plan.config.kernel_backend)
        self._streamed = fused_streaming(plan)
        if plan.config.sched in LEVELSET_SCHEDS:
            if backend in ops.FUSED_BACKENDS:
                fn = _fused_levelset_device_fn(plan)
            else:
                # unified with an empty cut degrades to the exchange-free
                # executor: the dense per-level psums would only move zeros
                fn = (
                    _levelset_device_fn(plan)
                    if plan.config.comm == "zerocopy" or D == 1
                    or plan.n_boundary_rows == 0
                    else _levelset_unified_device_fn(plan)
                )
            # streaming swaps the replicated diag for the per-device
            # schedule-ordered store, which is sharded like the tiles
            diag_spec = sharded if self._streamed else repl
            in_specs = (sharded,) * 6 + (diag_spec, repl, repl)
        else:
            fn = _syncfree_device_fn(plan, frontier=backend in ops.FUSED_BACKENDS)
            in_specs = (sharded,) * 5 + (repl, repl, repl, repl)
        self._set_args(plan)
        mapped = compat.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
        )
        self._jitted = jax.jit(mapped)

    def _set_args(self, plan: Plan) -> None:
        self._args = self._plan_args(plan)
        # host-resident leaves are uploaded on every call; jax.Arrays are not
        self._args_h2d_bytes = sum(
            x.nbytes for x in jax.tree.leaves(self._args)
            if isinstance(x, np.ndarray))

    def _plan_args(self, plan: Plan) -> tuple:
        if plan.config.sched in LEVELSET_SCHEDS:
            diag, tiles = plan.diag, plan.tiles
            if self._streamed:
                # schedule-ordered HBM stores; recomputed here on every
                # refresh so re-armed values reach the streamed kernel too
                diag, tiles = streamed_stores(plan)
            return (plan.solve_rows, plan.upd_tiles, plan.tile_row,
                    plan.tile_col, tiles, self._owner_mask, diag,
                    plan.ex_rows)
        return (plan.local_rows, plan.tile_row, plan.tile_col,
                plan.tiles, self._owner_mask, plan.diag, plan.indeg,
                plan.ex_boundary)

    def refresh(self, plan: Plan) -> None:
        """Swap in a numerically refreshed plan (:func:`refresh_plan`) without
        recompiling: the executor trace bakes in the *schedules*, while tile
        and diagonal values ride in as jit arguments — same shapes, same
        compiled program, zero retrace."""
        old = self.plan
        # the compiled trace bakes the old schedule in as constants, so a
        # structurally different plan would silently pair new values with the
        # wrong schedule — reject it loudly (never an assert: -O must not
        # disable this)
        if not (plan.config == old.config and plan.n_devices == old.n_devices
                and plan.transpose == old.transpose
                and np.array_equal(plan.solve_rows, old.solve_rows)
                and np.array_equal(plan.lvl_off, old.lvl_off)
                and np.array_equal(step_offsets(plan), step_offsets(old))
                and np.array_equal(plan.local_rows, old.local_rows)
                and np.array_equal(plan.tile_row, old.tile_row)):
            raise ValueError(
                "refresh requires an identical symbolic schedule (same "
                "pattern, config, and device count as the compiled plan)"
            )
        self.plan = plan
        self._set_args(plan)

    def lower(self, R: int = 1) -> "jax.stages.Lowered":
        """The executor lowered for an R-column right-hand side (``R = 1``:
        a single vector) — ``.compile().as_text()`` shows what runs."""
        nb, B = self.plan.bs.nb, self.plan.bs.B
        shape = (nb + 1, B) if R == 1 else (nb + 1, B, R)
        return self._jitted.lower(
            *self._args, jax.ShapeDtypeStruct(shape, jnp.float32))

    def solve_blocks(self, b_blocks: jax.Array) -> jax.Array:
        """b_blocks: (nb, B) or a multi-RHS panel (nb, B, R) -> same shape."""
        self.n_solves += 1
        self._solves.inc()
        rhs_bytes = 0 if isinstance(b_blocks, jax.Array) else b_blocks.nbytes
        self._h2d_bytes.inc(self._args_h2d_bytes + rhs_bytes)
        with get_tracer().span("sptrsv.launch"):
            t0 = time.perf_counter_ns()
            b_pad = jnp.concatenate(
                [b_blocks, jnp.zeros((1,) + b_blocks.shape[1:], b_blocks.dtype)]
            )
            x = self._jitted(*self._args, b_pad)
            launch_ns = time.perf_counter_ns() - t0
        key = (b_pad.shape, b_pad.dtype)
        if key in self._compiled:
            self._launch_us.observe(launch_ns / 1e3)
        else:
            self._compiled.add(key)
        return x

    def solve(self, b: np.ndarray) -> np.ndarray:
        """b: (n,) or (n, R) RHS panel. Transpose plans flip row order at this
        boundary (the plan was built on ``reverse_transpose(a)``)."""
        from repro.core.blocking import pad_rhs, unpad_x

        tracer = get_tracer()
        with tracer.span("sptrsv.stage_in"):
            b = np.asarray(b, np.float32)
            if self.plan.transpose:
                b = b[::-1]
            b_host = pad_rhs(b, self.plan.bs)
            b_blocks = jnp.asarray(b_host)
        self._h2d_bytes.inc(b_host.nbytes)
        xb = self.solve_blocks(b_blocks)
        with tracer.span("sptrsv.fetch"):  # waits for the device
            xb = np.asarray(xb)
        with tracer.span("sptrsv.stage_out"):
            x = unpad_x(xb, self.plan.bs)
            return x[::-1].copy() if self.plan.transpose else x


def sptrsv(
    a: CSR, b: np.ndarray, *, mesh: jax.sharding.Mesh | None = None,
    config: SolverConfig | None = None, transpose: bool = False,
) -> np.ndarray:
    """Deprecated one-shot API: analyse, plan, solve Lx=b (or L^T x=b).

    Kept as a thin shim over :class:`repro.api.SpTRSVContext` — it re-runs the
    full analysis on every call, which is exactly the cost the session API
    amortizes. New code should hold a context and call
    ``ctx.solve(ctx.analyse(a), b)``.
    """
    warnings.warn(
        "repro.core.sptrsv is deprecated: use repro.api.SpTRSVContext "
        "(analyse once, factorize/solve many)", DeprecationWarning, stacklevel=2,
    )
    from repro.api import SpTRSVContext

    ctx = SpTRSVContext(mesh=mesh, options=config)
    return ctx.solve(ctx.analyse(a), b, transpose=transpose)
