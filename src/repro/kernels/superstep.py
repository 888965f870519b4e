"""Fused Pallas superstep megakernel for the compacted levelset schedule.

The paper's zero-copy SpTRSV wins by replacing coarse per-wavefront kernel
launches with fine-grained dependency-aware execution resident on the device.
The ``lax.switch`` compacted executor (``core.solver``) is the XLA analogue of
the *launch-per-superstep* baseline: every level re-dispatches a gather, a
batched TRSV, a batched GEMV and a scatter-add as separate ops, plus one
``switch`` branch per width-bucket combo. This module is the persistent-kernel
analogue: **one** ``pallas_call`` executes a whole run of levels.

Scalar-prefetch layout
----------------------
The ragged compacted schedule rides in as scalar-prefetch operands (SMEM on
TPU, available before the kernel body runs, so schedule reads never touch
HBM). The two level tables are flattened to 1-D so SMEM holds them unpadded:

* ``seg``  ``(2,)``   — ``[first_step, n_active_steps]`` of this launch.
* ``off``  ``(3T,)``  — per-level start offsets into the three flats
  (level ``t``'s entries at ``3t .. 3t+2``).
* ``wid``  ``(3T,)``  — per-level bucket widths ``(w_solve, w_upd, w_ex)``.
* ``stp``  ``(n_steps+1,)`` — level offsets of the supersteps: step ``s``
  covers levels ``[stp[s], stp[s+1])``. Identity (``arange``) for levelset;
  the DAG-partition merge pass's coarsening for ``sched="dagpart"``.
* ``sr``   ``(S,)``   — flat solve rows (device-local), pad ``-1``.
* ``ut``   ``(U,)``   — flat update tile slots (device-local), pad ``ML``.
* ``trow``/``tcol`` ``(ML+1,)`` — per-tile destination row / source column.

Grid = one program per *superstep*; program ``p`` executes the levels of step
``seg[0] + p`` in order (programs beyond ``seg[1]`` are inert padding, which
lets a ``fori_loop`` over variable-length segments reuse one traced launch).
A merged step's levels run back-to-back inside one program — the sequential
rowsweep is exactly what makes intra-step dependencies legal.

Carries in HBM
--------------
The right-hand side and the carries (``acc``, ``x``, and ``delta`` for the
unified split) are *row panels* of shape ``(nb+1, R, B)`` — one ``(R, B)``
row per block row, ``R = 1`` for a single right-hand side — and stay in HBM
(``ANY``). Each solve and each tile update moves the rows it touches through
a small VMEM scratch with synchronous DMAs, so VMEM use does not grow with
``n`` (at 2^20 rows a carry is 4 MiB for one RHS and 32 MiB for eight, more
than VMEM could hold alongside the stores). The ``(R, B)`` orientation keeps
B on the lanes, which is also what the DMA engine requires of a slice. TPU
grid programs run sequentially on a core, so program 0 copies the incoming
carries into the output buffers and every later program reads the partial
sums earlier ones wrote.

Per row a dense forward substitution of the diagonal tile (B masked lane
reductions), then per tile a ``(R,B)@(B,B)^T`` MXU product accumulated into
the destination row of ``acc`` (or ``delta``). Every product is exact on
dyadic values, so the fused kernel agrees bit-for-bit with the
``lax.switch`` executor there — the property ``tests/test_superstep.py`` pins
down in interpret mode — and to float tolerance elsewhere.

Collectives cannot live inside a Pallas kernel, so the boundary exchange
splits the level range into *segments* (``core.solver.fused_segments``): one
launch per run of levels between exchanges. Single-device plans and empty
cuts fuse the entire solve into exactly one launch.

Resident vs streamed stores
---------------------------
In the **resident** variant ``diag``/``tiles`` sit whole in VMEM (one copy,
no pipelining): fine while they fit, but the footprint grows with the
*total* tile count, which caps the matrix sizes the fused hot path can
serve. The **streamed** variant (``stream=True``) is the production-scale
path: ``diag``/``tiles`` arrive *schedule-ordered* (level ``t``'s slice is
contiguous at ``off[t]`` — exactly the compacted flat layout, so a merged
step's slice is contiguous too) and live in ``ANY``/HBM; each grid program
double-buffers its step's slices into two VMEM scratch buffers with async
DMA, prefetching step ``s+1`` while step ``s`` computes. VMEM residency then
scales with the *widest superstep slice* (max summed widths over the step
table), not the total tile store, and the DMA engine sees exactly one
contiguous burst per step per store. The DMA sizes branch over the distinct
step widths (a static ladder of ≤ ``MAX_BUCKETS`` sizes), so the bytes moved
per solve equal the compacted schedule footprint — no pad-to-max traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_trsv import solve_rows

N_PREFETCH = 8  # seg, off, wid, stp, sr, ut, trow, tcol
N_ROW_BUFS = 3  # VMEM row scratch: two operand rows + one result row

HIGHEST = jax.lax.Precision.HIGHEST

# Trace-time record of the most recent streamed launch's VMEM scratch shapes
# (diag_buf/tile_buf) — lets tests assert the streaming contract (buffers
# sized by the max per-level slice, never the total store) without digging
# into lowered HLO.
LAST_STREAM_ALLOC: dict = {}


def stream_scratch_shapes(solve_widths: tuple, upd_widths: tuple, B: int
                          ) -> tuple[tuple, tuple]:
    """The streaming kernel's VMEM scratch allocation rule: double-buffered
    slices sized by the widest entry of each DMA ladder — the distinct
    per-superstep widths — (``(2, W, B, B)`` per store, never the total store
    size). This is the single source shared by
    :func:`superstep_call` and the static plan verifier
    (``repro.verify.contracts``), so the lint checks the allocation the kernel
    actually performs rather than a re-derivation of it."""
    WS = max([w for w in solve_widths if w > 0] or [1])
    WU = max([w for w in upd_widths if w > 0] or [1])
    return (2, WS, B, B), (2, WU, B, B)


def row_scratch_bytes(B: int, R: int = 1, itemsize: int = 4) -> int:
    """VMEM bytes of the kernel's row scratch (``N_ROW_BUFS`` rows of
    ``(R, B)``) — the only VMEM the HBM carries cost."""
    return N_ROW_BUFS * max(1, R) * B * itemsize


def to_rows(v: jax.Array) -> jax.Array:
    """``(N, B)`` vectors or ``(N, B, R)`` panels -> the kernel's ``(N, R, B)``
    row-panel layout (B on the lanes)."""
    return v[:, None, :] if v.ndim == 2 else jnp.swapaxes(v, 1, 2)


def from_rows(v: jax.Array, multi: bool) -> jax.Array:
    """Inverse of :func:`to_rows`."""
    return jnp.swapaxes(v, 1, 2) if multi else v[:, 0, :]


def _superstep_kernel(
    seg_ref, off_ref, wid_ref, stp_ref, sr_ref, ut_ref, trow_ref, tcol_ref,
    diag_ref, tiles_ref, b_ref, *refs, split_delta: bool,
    stream: bool = False, solve_widths: tuple = (), upd_widths: tuple = (),
):
    """Shared kernel body for the resident and streamed variants.

    Resident: ``diag_ref``/``tiles_ref`` are whole VMEM arrays indexed by row
    / tile slot. Streamed: they are *schedule-ordered* HBM (``ANY``) stores —
    slot ``k`` of the solve/update flats corresponds to entry ``k`` — and each
    superstep's contiguous slice is DMA'd into the double-buffered VMEM
    scratch (``dbuf``/``tbuf``) at its exact summed width (one ``pl.when``
    branch per distinct width in the static ladder, so start/wait always
    agree on size).
    """
    n_carry = 3 if split_delta else 2
    carry_in, carry_out = refs[:n_carry], refs[n_carry:2 * n_carry]
    if stream:
        dbuf, tbuf, dsem, tsem, rows = refs[2 * n_carry:]
    else:
        (rows,) = refs[2 * n_carry:]
    if split_delta:
        acc_ref, delta_ref, x_ref = carry_out
    else:
        acc_ref, x_ref = carry_out
        delta_ref = acc_ref  # tile updates land in acc (the zerocopy/local carry)
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _():  # HBM -> HBM: the incoming carries seed the output buffers
        pltpu.sync_copy(carry_in, carry_out)

    def row(k):
        return rows.at[pl.ds(k, 1)]

    def at_row(ref, r):
        return ref.at[pl.ds(r, 1)]

    s = seg_ref[0] + p
    slot = jax.lax.rem(p, 2)

    if stream:

        def _step_copies(q, sl):
            """(predicate, async_copy) pairs moving superstep ``seg[0]+q``'s
            schedule slices into scratch slot ``sl``. A step's levels are
            consecutive in the flats, so one burst per store covers the whole
            merge group; one candidate per distinct per-step summed width,
            predicated on the step's actual total."""
            sq = seg_ref[0] + q
            t0 = stp_ref[sq]
            t1 = stp_ref[sq + 1] - 1  # last level of the step (steps non-empty)
            wsq = off_ref[3 * t1] + wid_ref[3 * t1] - off_ref[3 * t0]
            wuq = off_ref[3 * t1 + 1] + wid_ref[3 * t1 + 1] - off_ref[3 * t0 + 1]
            for w in solve_widths:
                if w > 0:
                    yield wsq == w, pltpu.make_async_copy(
                        diag_ref.at[pl.ds(off_ref[3 * t0], w)],
                        dbuf.at[sl, pl.ds(0, w)], dsem.at[sl])
            for w in upd_widths:
                if w > 0:
                    yield wuq == w, pltpu.make_async_copy(
                        tiles_ref.at[pl.ds(off_ref[3 * t0 + 1], w)],
                        tbuf.at[sl, pl.ds(0, w)], tsem.at[sl])

        @pl.when(jnp.logical_and(p == 0, seg_ref[1] > 0))
        def _():  # warm-up: this launch's first step has no predecessor
            for pred, cp in _step_copies(0, 0):
                pl.when(pred)(cp.start)

        @pl.when(p + 1 < seg_ref[1])
        def _():  # prefetch the next step into the other slot while computing
            for pred, cp in _step_copies(p + 1, jax.lax.rem(p + 1, 2)):
                pl.when(pred)(cp.start)

    @pl.when(p < seg_ref[1])
    def _():
        if stream:  # this step's slices must have landed before compute
            for pred, cp in _step_copies(p, slot):
                pl.when(pred)(cp.wait)

        t_lo = stp_ref[s]
        # streamed scratch holds the whole step slice; level t's entries sit
        # at (off[t] - base) within it
        base_s = off_ref[3 * t_lo]
        base_u = off_ref[3 * t_lo + 1]

        def micro(t, carry):
            # --- solve level t's owned rows (dynamic trip = bucket width) ---
            o_s = off_ref[3 * t]

            def solve_one(i, c):
                r = sr_ref[o_s + i]

                @pl.when(r >= 0)
                def _():
                    l_ref = (dbuf.at[slot, o_s - base_s + i] if stream
                             else diag_ref.at[r])
                    pltpu.sync_copy((at_row(b_ref, r), at_row(acc_ref, r)),
                                    (row(0), row(1)))
                    rhs = rows[0] - rows[1]
                    if split_delta:
                        # earlier levels of this merged step accumulated local
                        # contributions into delta (not yet psum-folded into
                        # acc) — intra-step dependencies read them here. For
                        # an unmerged step delta is exactly +0.0: bit-inert.
                        pltpu.sync_copy(at_row(delta_ref, r), row(1))
                        rhs = rhs - rows[1]
                    rows[2] = solve_rows(l_ref, rhs)
                    pltpu.sync_copy(row(2), at_row(x_ref, r))

                return c

            jax.lax.fori_loop(0, wid_ref[3 * t], solve_one, 0)

            # --- owned-tile updates sourced at level t ---
            o_u = off_ref[3 * t + 1]

            def upd_one(j, c):
                tid = ut_ref[o_u + j]
                t_ref = (tbuf.at[slot, o_u - base_u + j] if stream
                         else tiles_ref.at[tid])
                rd = trow_ref[tid]
                pltpu.sync_copy(
                    (at_row(x_ref, tcol_ref[tid]), at_row(delta_ref, rd)),
                    (row(0), row(1)))
                # the product as rows: X^T L^T, i.e. (R,B) @ (B,B)^T
                prod = jax.lax.dot_general(
                    rows[0], t_ref[...], (((1,), (1,)), ((), ())),
                    precision=HIGHEST, preferred_element_type=jnp.float32)
                rows[2] = rows[1] + prod
                pltpu.sync_copy(row(2), at_row(delta_ref, rd))
                return c

            jax.lax.fori_loop(0, wid_ref[3 * t + 1], upd_one, 0)
            return carry

        # run the step's levels in order inside this one grid program — the
        # sequential rowsweep makes intra-step dependencies legal
        jax.lax.fori_loop(t_lo, stp_ref[s + 1], micro, 0)


@functools.partial(
    jax.jit,
    static_argnames=("grid", "split_delta", "interpret", "stream",
                     "solve_widths", "upd_widths"),
)
def superstep_call(
    seg: jax.Array,  # (2,) int32 [first_step, n_active_steps]
    off: jax.Array,  # (T, 3) int32 level offsets into the flats
    wid: jax.Array,  # (T, 3) int32 level bucket widths
    sr: jax.Array,  # (S,) int32 flat solve rows, pad -1
    ut: jax.Array,  # (U,) int32 flat tile slots, pad ML
    trow: jax.Array,  # (ML+1,) int32
    tcol: jax.Array,  # (ML+1,) int32
    diag: jax.Array,  # (nb+1, B, B) resident; (S, B, B) schedule-ordered streamed
    tiles: jax.Array,  # (ML+1, B, B) resident; (U, B, B) schedule-ordered streamed
    b_pad: jax.Array,  # (nb+1, R, B) row panel (see to_rows)
    acc: jax.Array,
    x: jax.Array,
    delta: jax.Array | None = None,
    stp: jax.Array | None = None,  # (n_steps+1,) int32 superstep level offsets
    *,
    grid: int,
    split_delta: bool = False,
    interpret: bool = False,
    stream: bool = False,
    solve_widths: tuple = (),
    upd_widths: tuple = (),
):
    """One fused launch executing ``grid`` supersteps starting at ``seg[0]``.

    ``b_pad`` and the carries are ``(nb+1, R, B)`` row panels
    (:func:`to_rows`). ``stp`` is the superstep→level offset table; ``None``
    means the identity (one level per superstep — the plain levelset
    schedule). Returns the updated ``(acc, x)`` carry, or ``(acc, delta, x)``
    when ``split_delta`` (the unified executor's not-yet-exchanged
    contributions accumulate in ``delta``; solves read ``acc + delta`` so
    later levels of a merged superstep see the earlier levels' local
    contributions).

    With ``stream=True`` the ``diag``/``tiles`` operands are the
    *schedule-ordered* stores (``core.solver.streamed_stores``): they stay in
    ``ANY``/HBM and each superstep's contiguous slice is double-buffered into
    VMEM scratch sized by the max width in ``solve_widths`` / ``upd_widths``
    (the static ladder of distinct per-step summed widths).
    """
    assert (delta is not None) == split_delta
    if off.shape[0] == 0:
        # empty schedule (0-level plan): every program is inert, but the
        # kernel still traces reads of the level tables — give them one
        # zero row (and a two-entry zero step table) so those
        # (never-executed) reads stay in bounds
        off = jnp.zeros((1, 3), jnp.int32)
        wid = jnp.zeros((1, 3), jnp.int32)
        stp = jnp.zeros((2,), jnp.int32)
    if stp is None:
        stp = jnp.arange(off.shape[0] + 1, dtype=jnp.int32)
    carry_in = (acc, delta, x) if split_delta else (acc, x)
    R, B = b_pad.shape[1], b_pad.shape[2]
    if not interpret and B % 128:
        raise ValueError(
            f"the superstep kernel needs B % 128 == 0 on the TPU (Mosaic "
            f"slices refs in 128-lane tiles), got B={B}")

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    vmem_bytes = row_scratch_bytes(B, R, b_pad.dtype.itemsize)
    if stream:
        # the streaming contract: VMEM scratch scales with the widest level
        # slice (double-buffered), never with the total store size
        dshape, tshape = stream_scratch_shapes(solve_widths, upd_widths, B)
        scratch_shapes = [
            pltpu.VMEM(dshape, diag.dtype),
            pltpu.VMEM(tshape, tiles.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        vmem_bytes += 2 * (dshape[1] + tshape[1]) * B * B * diag.dtype.itemsize
        LAST_STREAM_ALLOC.update(
            diag_buf=dshape, tile_buf=tshape,
            diag_store=tuple(diag.shape), tile_store=tuple(tiles.shape),
        )
        store_spec = hbm
    else:
        scratch_shapes = []
        vmem_bytes += (diag.size + tiles.size) * diag.dtype.itemsize
        store_spec = pl.BlockSpec(memory_space=pltpu.VMEM)  # whole, one copy
    scratch_shapes.append(pltpu.VMEM((N_ROW_BUFS, R, B), b_pad.dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=N_PREFETCH,
        grid=(grid,),
        in_specs=[store_spec, store_spec] + [hbm] * (1 + len(carry_in)),
        out_specs=[hbm] * len(carry_in),
        scratch_shapes=scratch_shapes,
    )
    # The carries are deliberately NOT donated via input_output_aliases:
    # callers init them from one zeroed array that XLA may CSE into a single
    # buffer, and two must-alias outputs sharing one operand buffer would let
    # x_ref writes clobber acc_ref on hardware. Program 0's explicit copy-in
    # already pays the one copy per launch that donation would have saved.
    kernel = functools.partial(
        _superstep_kernel, split_delta=split_delta,
        stream=stream, solve_widths=solve_widths, upd_widths=upd_widths,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in carry_in),
        # room for the stores/scratch above plus Mosaic's own working set
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_bytes + (16 << 20))),
        interpret=interpret,
    )(
        seg.astype(jnp.int32), off.astype(jnp.int32).reshape(-1),
        wid.astype(jnp.int32).reshape(-1), stp.astype(jnp.int32),
        sr.astype(jnp.int32), ut.astype(jnp.int32),
        trow.astype(jnp.int32), tcol.astype(jnp.int32), diag, tiles, b_pad,
        *carry_in,
    )
