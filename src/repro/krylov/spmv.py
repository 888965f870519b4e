"""Distributed symmetric SpMV reusing the solver's per-device tile stores.

The Krylov matvec ``y = A v`` runs on exactly the data the SpTRSV plan already
sharded: the plan of A's lower-triangular half owns dense diagonal tiles and
per-device strictly-lower tiles (resident on their column's owner). A device
contributes

* ``D_sym[r] @ v[r]``         for the block rows it owns (symmetrized diagonal
  tiles, counted once via the owner mask),
* ``L[r,c] @ v[c]``           for its resident tiles (scattered to row ``r``),
* ``L[r,c]^T @ v[r]``         the mirrored upper entries (scattered to ``c``),

and one psum combines the partial results — the same read-only communication
model as the solver itself. Multi-RHS panels ``(n, R)`` run as
``(nb+1, R, B)`` row panels on the device, the solver's carry layout, with
the row-panel kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.core.blocking import pad_rhs, unpad_x
from repro.core.solver import AXIS, Plan, rows_in, rows_out
from repro.kernels import ops


def _symmetrize_diag(diag: np.ndarray) -> np.ndarray:
    """(nb+1,B,B) lower-triangular diagonal tiles -> full symmetric tiles."""
    dvals = np.einsum("kii->ki", diag)
    sym = diag + diag.transpose(0, 2, 1)
    k, b, _ = diag.shape
    sym[:, np.arange(b), np.arange(b)] = dvals
    return sym.astype(np.float32)


def _spmv_device_fn(plan: Plan):
    cfg = plan.config
    nb = plan.bs.nb
    multi = plan.n_devices > 1

    def fn(tiles, tiles_t, trow, tcol, owner_mask, sym_diag, v_pad):
        tiles, tiles_t = tiles[0], tiles_t[0]
        trow, tcol, owner_mask = trow[0], tcol[0], owner_mask[0]
        panel = v_pad.ndim == 3
        gemv = ops.batched_block_gemv_rows if panel else ops.batched_block_gemv
        v = rows_in(v_pad)
        y = gemv(sym_diag, v, backend=cfg.kernel_backend)
        y = y * ops.bcast_trailing(owner_mask, y)  # each diag block counted once
        prods = gemv(tiles, v[tcol], backend=cfg.kernel_backend)
        y = y.at[trow].add(prods)  # pad tiles are zero -> pad adds are inert
        mirrored = gemv(tiles_t, v[trow], backend=cfg.kernel_backend)
        y = y.at[tcol].add(mirrored)
        if multi:
            y = jax.lax.psum(y, AXIS)
        return rows_out(y[:nb], panel)

    return fn


class DistributedSpMV:
    """Compiled ``y = A v`` for symmetric A given the plan of its lower half."""

    def __init__(self, plan: Plan, mesh: jax.sharding.Mesh):
        assert not plan.transpose, "SpMV needs the plan of A itself"
        assert mesh.devices.size == plan.n_devices
        self.plan = plan
        self.mesh = mesh
        self.n_matvecs = 0
        nb, D = plan.bs.nb, plan.n_devices
        owner_mask = np.zeros((D, nb + 1), np.float32)
        for d in range(D):
            owner_mask[d, :nb] = (plan.part.owner == d).astype(np.float32)
        self._args = (plan.tiles, plan.tiles.transpose(0, 1, 3, 2).copy(),
                      plan.tile_row, plan.tile_col, owner_mask,
                      _symmetrize_diag(plan.diag))
        sharded, repl = P(AXIS), P()
        mapped = compat.shard_map(
            _spmv_device_fn(plan), mesh=mesh,
            in_specs=(sharded,) * 5 + (repl, repl), out_specs=P(),
        )
        self._jitted = jax.jit(mapped)

    def matvec_blocks(self, v_blocks: jax.Array) -> jax.Array:
        """v_blocks: (nb, B) or (nb, B, R) -> same shape."""
        self.n_matvecs += 1
        v_pad = jnp.concatenate(
            [v_blocks, jnp.zeros((1,) + v_blocks.shape[1:], v_blocks.dtype)]
        )
        return self._jitted(*self._args, v_pad)

    def matvec(self, v: np.ndarray) -> np.ndarray:
        """v: (n,) or (n, R) -> A v, same shape."""
        v_blocks = jnp.asarray(pad_rhs(np.asarray(v, np.float32), self.plan.bs))
        return unpad_x(np.asarray(self.matvec_blocks(v_blocks)), self.plan.bs)
