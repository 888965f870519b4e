"""Pallas TPU kernel: batched block GEMV for off-diagonal tile updates.

The update half of the paper's solve-update phase (Alg. 3 lines 29–35): each
strictly-lower tile L[r,c] contributes ``acc[r] += L[r,c] @ x[c]``. The kernel
computes the per-tile products on the MXU; the scatter-add over destination
rows is a segment-sum outside the kernel (racing scatter across grid programs
is not expressible portably — destinations are combined with a deterministic
jnp segment reduction, mirroring the paper's device-side atomics).

Two layouts, one kernel body: :func:`block_gemv` takes one ``(k, B)`` vector
per tile, :func:`block_gemm_rows` a ``(k, R, B)`` row panel, the right-hand
sides as rows with B on the lanes; both compute ``x @ T^T`` a tile.

Every dot sets ``precision=HIGHEST``: the TPU's default f32 dot is a single
bf16 pass, far outside the solver's tolerance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

HIGHEST = jax.lax.Precision.HIGHEST


def _gemv_kernel(t_ref, x_ref, o_ref):
    # t_ref: (1,B,B), x_ref/o_ref: (1,R,B) — the product as rows: x @ T^T
    o_ref[0] = jax.lax.dot_general(
        x_ref[0], t_ref[0], (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gemv(tiles: jax.Array, xs: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Per-tile products: tiles (m,B,B) @ xs (m,B) -> (m,B)."""
    m, B, _ = tiles.shape
    out = pl.pallas_call(
        _gemv_kernel,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, B, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1, B), tiles.dtype),
        interpret=interpret,
    )(tiles, xs.reshape(m, 1, B))
    return out.reshape(m, B)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gemm_rows(tiles: jax.Array, xs: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """Row-panel tile products: tiles (m,B,B) by xs (m,R,B) -> (m,R,B), each
    panel's rows times the tile's transpose (``x_rows @ T^T``, the
    :func:`block_gemv` product with R rows). The panels' layout is the
    levelset executors' ``(nb+1, R, B)`` carry."""
    m, B, _ = tiles.shape
    R = xs.shape[1]
    return pl.pallas_call(
        _gemv_kernel,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, B, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, R, B), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, R, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, R, B), tiles.dtype),
        interpret=interpret,
    )(tiles, xs)
