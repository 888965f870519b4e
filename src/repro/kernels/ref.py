"""Pure-jnp oracles for the Pallas kernels (independent implementations).

These are the ground truth for tests/*: every Pallas kernel must match its
oracle over a sweep of shapes and dtypes (interpret mode on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# f32 dots on the TPU default to one bf16 pass; the solver needs full f32.
# (``triangular_solve`` already expands with HIGHEST-precision dots.)
HIGHEST = jax.lax.Precision.HIGHEST


def block_trsv_ref(diag: jax.Array, rhs: jax.Array) -> jax.Array:
    """Batched dense lower-triangular solve.

    rhs may be a single vector per tile ``(k, B)`` or a multi-RHS panel
    ``(k, B, R)`` — one solve amortized over R right-hand sides.
    """
    multi = rhs.ndim == 3
    r = rhs if multi else rhs[..., None]
    sol = jax.lax.linalg.triangular_solve(
        diag, r, left_side=True, lower=True, transpose_a=False
    )
    return sol if multi else sol[..., 0]


def block_gemv_ref(tiles: jax.Array, xs: jax.Array) -> jax.Array:
    """Batched tile*vector: tiles (m,B,B), xs (m,B)."""
    return jnp.einsum("mij,mj->mi", tiles, xs, precision=HIGHEST)


def block_trsv_rows_ref(diag: jax.Array, rhs: jax.Array) -> jax.Array:
    """Batched solve of ``(k, R, B)`` row panels: :func:`block_trsv_ref` on
    the swapped axes."""
    return jnp.swapaxes(block_trsv_ref(diag, jnp.swapaxes(rhs, 1, 2)), 1, 2)


def block_gemv_rows_ref(tiles: jax.Array, xs: jax.Array) -> jax.Array:
    """Tile products of ``(m, R, B)`` row panels: ``xs[m] @ tiles[m]^T``."""
    return jnp.einsum("mij,mrj->mri", tiles, xs, precision=HIGHEST)


def fused_level_ref(
    diag: jax.Array,  # (k,B,B) diagonal tiles of the wavefront rows
    rhs: jax.Array,  # (k,B)   b - acc for those rows
    tiles: jax.Array,  # (m,B,B) off-diagonal tiles sourced at this wavefront
    src: jax.Array,  # (m,) index into the wavefront's k solves for each tile's column
) -> tuple[jax.Array, jax.Array]:
    """Solve a wavefront then produce the per-tile updates it triggers."""
    x = block_trsv_ref(diag, rhs)
    prods = block_gemv_ref(tiles, x[src])
    return x, prods
