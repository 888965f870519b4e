"""Pallas TPU kernel: batched dense lower-triangular block solve (block-TRSV).

TPU mapping of the paper's per-component solve (DESIGN.md §5.3): a wavefront's
diagonal tiles are solved as dense B×B forward substitutions, one grid program
per tile, with the whole tile resident in VMEM.

Two in-kernel algorithms:
* ``row-sweep``  — B scalar steps, each a masked VPU row·x dot (O(B) vector ops).
* ``panel``      — processes P=8 rows per step: a tiny unrolled P×P triangle
  followed by a rank-P MXU update of the remaining rhs. ~P× fewer sequential
  steps; ``rowsweep`` stays the default until a chip run says otherwise.

Two layouts: :func:`block_trsv` solves one ``(k, B)`` vector per tile with
either algorithm; :func:`block_trsm_rows` solves ``(k, R, B)`` row panels, the
right-hand sides as rows with B on the lanes (:func:`solve_rows`, shared with
the superstep megakernel).

Every block's last two dims equal the array's (``(1, B, B)`` tiles,
``(1, 1, B)`` rhs rows, ``(1, R, B)`` panels), which the TPU
compiler accepts for any B. Tile rows are read from the ref at a dynamic sublane
offset (``l_ref[0, pl.ds(i, 1), :]``); Mosaic has no ``dynamic_slice`` on
values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST


def _trsv_rowsweep_kernel(l_ref, r_ref, x_ref):
    # l_ref: (1,B,B)  r_ref/x_ref: (1,1,B)
    B = l_ref.shape[-1]
    r = r_ref[0]  # (1,B)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def body(i, x):
        # partial dot over solved prefix: sum_j<i L[i,j] * x[j]
        li = l_ref[0, pl.ds(i, 1), :]  # (1,B) row i
        s = jnp.sum(jnp.where(col < i, li * x, 0.0), axis=1, keepdims=True)
        lii = jnp.sum(jnp.where(col == i, li, 0.0), axis=1, keepdims=True)
        ri = jnp.sum(jnp.where(col == i, r, 0.0), axis=1, keepdims=True)
        return jnp.where(col == i, (ri - s) / lii, x)

    x_ref[0] = jax.lax.fori_loop(0, B, body, jnp.zeros((1, B), l_ref.dtype))


def _trsv_panel_kernel(l_ref, r_ref, x_ref, *, panel: int):
    # Panel forward substitution: solve P rows with the row sweep, then one
    # MXU-shaped rank-P update of the remaining rhs.
    B = l_ref.shape[-1]
    P = panel
    col = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def outer(p, carry):
        r, x = carry  # both (1,B); r is the running rhs (updated by prior panels)
        base = p * P

        def inner(q, x):
            i = base + q
            li = l_ref[0, pl.ds(i, 1), :]
            in_panel_prefix = jnp.logical_and(col >= base, col < i)
            s = jnp.sum(jnp.where(in_panel_prefix, li * x, 0.0), axis=1,
                        keepdims=True)
            lii = jnp.sum(jnp.where(col == i, li, 0.0), axis=1, keepdims=True)
            ri = jnp.sum(jnp.where(col == i, r, 0.0), axis=1, keepdims=True)
            return jnp.where(col == i, (ri - s) / lii, x)

        x = jax.lax.fori_loop(0, P, inner, x)
        # rank-P update of the trailing rhs: r -= L[:, base:base+P] @ x[base:base+P],
        # i.e. (x masked to the panel) @ L^T in row layout
        in_panel = jnp.logical_and(col >= base, col < base + P)
        upd = jax.lax.dot_general(
            jnp.where(in_panel, x, 0.0), l_ref[0], (((1,), (1,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)  # (1,B)
        r = jnp.where(col >= base + P, r - upd, r)
        return r, x

    _, x = jax.lax.fori_loop(
        0, B // P, outer, (r_ref[0], jnp.zeros((1, B), l_ref.dtype))
    )
    x_ref[0] = x


@functools.partial(jax.jit, static_argnames=("algorithm", "panel", "interpret"))
def block_trsv(
    diag: jax.Array,
    rhs: jax.Array,
    *,
    algorithm: str = "rowsweep",
    panel: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Batched solve of k dense lower-triangular tiles: (k,B,B),(k,B)->(k,B)."""
    k, B, _ = diag.shape
    if algorithm == "rowsweep":
        kernel = _trsv_rowsweep_kernel
    elif algorithm == "panel":
        assert B % panel == 0
        kernel = functools.partial(_trsv_panel_kernel, panel=panel)
    else:
        raise ValueError(algorithm)
    out = pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, B, B), lambda i: (i, 0, 0)),  # one tile in VMEM per program
            pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1, B), diag.dtype),
        interpret=interpret,
    )(diag, rhs.reshape(k, 1, B))
    return out.reshape(k, B)


def solve_rows(l_ref, rhs):
    """Solve ``L X = RHS`` for one ``(B,B)`` lower-triangular tile, with the
    right-hand sides as the rows of ``rhs`` ``(R,B)``: B steps of masked lane
    reductions, row ``i`` of L read from the ref at a dynamic sublane. With B
    on the lanes an ``(8, B)`` panel is one vreg a step, unpadded. Shared by
    :func:`block_trsm_rows` and the superstep megakernel."""
    B = l_ref.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, B), 1)

    def body(i, x):
        li = l_ref[pl.ds(i, 1), :]  # (1,B) row i
        s = jnp.sum(jnp.where(col < i, li * x, 0.0), axis=1, keepdims=True)
        lii = jnp.sum(jnp.where(col == i, li, 0.0), axis=1, keepdims=True)
        ri = jnp.sum(jnp.where(col == i, rhs, 0.0), axis=1, keepdims=True)
        return jnp.where(col == i, (ri - s) / lii, x)

    return jax.lax.fori_loop(0, B, body, jnp.zeros_like(rhs))


def _trsm_rows_kernel(l_ref, r_ref, x_ref):
    # l_ref: (1,B,B)  r_ref/x_ref: (1,R,B) — R right-hand sides as rows
    x_ref[0] = solve_rows(l_ref.at[0], r_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_trsm_rows(diag: jax.Array, rhs: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Batched row-panel solve: (k,B,B) tiles × (k,R,B) panels -> (k,R,B).

    The panels' layout is the levelset executors' ``(nb+1, R, B)`` carry, so
    their per-level gathers feed this kernel with no change of layout."""
    k, B, _ = diag.shape
    R = rhs.shape[1]
    return pl.pallas_call(
        _trsm_rows_kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec((1, B, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, R, B), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, R, B), diag.dtype),
        interpret=interpret,
    )(diag, rhs)
