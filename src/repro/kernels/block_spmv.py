"""Pallas TPU kernel: batched block GEMV for off-diagonal tile updates.

The update half of the paper's solve-update phase (Alg. 3 lines 29–35): each
strictly-lower tile L[r,c] contributes ``acc[r] += L[r,c] @ x[c]``. The kernel
computes the per-tile products on the MXU; the scatter-add over destination
rows is a segment-sum outside the kernel (racing scatter across grid programs
is not expressible portably — destinations are combined with a deterministic
jnp segment reduction, mirroring the paper's device-side atomics).

``block_gemv_grouped`` processes G tiles per grid program so each MXU call is
a (G*B, B) × (B,) batched matvec — the grouped layout raises MXU utilization
(§Perf hillclimb knob).

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


def _gemv_grouped_kernel(t_ref, x_ref, o_ref):
    # t_ref: (G,B,B), x_ref: (G,B), o_ref: (G,B) — one fused batched matvec
    o_ref[...] = jnp.einsum(
        "gij,gj->gi", t_ref[...], x_ref[...], precision=HIGHEST,
        preferred_element_type=t_ref.dtype,
    )


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


def _gemm_kernel(t_ref, x_ref, o_ref):
    # t_ref: (1,B,B), x_ref: (1,B,R), o_ref: (1,B,R) — per-tile (B,B)@(B,R)
    o_ref[0] = jnp.dot(t_ref[0], x_ref[0], precision=HIGHEST,
                       preferred_element_type=t_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def block_gemm(tiles: jax.Array, xs: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Multi-RHS tile products: tiles (m,B,B) @ xs (m,B,R) -> (m,B,R).

    The RHS panel turns each tile's MXU call from a matvec into a (B,B)@(B,R)
    matmul — the serving-scale batching path (one compiled solve, R systems).
    """
    m, B, _ = tiles.shape
    R = xs.shape[-1]
    return pl.pallas_call(
        _gemm_kernel,
        grid=(m,),
        in_specs=[
            pl.BlockSpec((1, B, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, B, R), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, B, R), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, B, R), tiles.dtype),
        interpret=interpret,
    )(tiles, xs)


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def block_gemv_grouped(
    tiles: jax.Array, xs: jax.Array, *, group: int = 8, interpret: bool = False
) -> jax.Array:
    """Same contract as block_gemv but G tiles per grid program (MXU batching)."""
    m, B, _ = tiles.shape
    pad = (-m) % group
    if pad:
        tiles = jnp.concatenate([tiles, jnp.zeros((pad, B, B), tiles.dtype)])
        xs = jnp.concatenate([xs, jnp.zeros((pad, B), xs.dtype)])
    mg = tiles.shape[0]
    out = pl.pallas_call(
        _gemv_grouped_kernel,
        grid=(mg // group,),
        in_specs=[
            pl.BlockSpec((group, B, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((group, B), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((group, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mg, B), tiles.dtype),
        interpret=interpret,
    )(tiles, xs)
    return out[:m]


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
