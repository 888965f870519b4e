"""Calibrated block-op cost weights (ROADMAP "Cost model calibration").

``block_row_cost``'s analytic default says a B×B tile product costs 2× the
diagonal TRSV. This module replaces the guess with a per-backend measurement:
it compiles one representative block TRSV, and a block GEMV on a vector and
on an ``R_PROBE``-row panel, through the actual kernel dispatch
(``kernels.ops``), runs the loop-aware HLO analysis
from :mod:`repro.launch.hlo_cost` over the optimized module, and converts the
result into the weights of the minimal multi-RHS cost model

    cost(row, R) = w_solve·R + Σ_tiles (w_tile_mem + w_tile_flop·R)

``w_tile_mem`` is the R-independent tile-load term (a GEMM panel amortizes the
tile fetch across all R systems), ``w_tile_flop`` the per-RHS MXU slope,
fitted from the measured cost at R=1 and R=R_PROBE. Costs combine dot flops
with the HBM-traffic proxy (dot operand/output bytes) at a fixed machine
balance; weights are normalized to ``w_solve = 1``.

HLO that hides its work from the dot-based analysis — ``triangular_solve``
lowers to a LAPACK custom call on CPU, Pallas interpret bodies reduce with
masked sums — reports 0 flops; every term then falls back to its analytic
count, so calibration degrades gracefully to (a rescaled) analytic model
instead of producing nonsense weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.launch import hlo_cost

R_PROBE = 8  # panel width used to fit the per-RHS slope
FLOPS_PER_BYTE = 4.0  # machine balance: one HBM byte ≈ 4 flop-equivalents
MERGE_NARROW_ROWS = 8  # a "narrow" level carries at most ~this many typical rows


def merge_cost_threshold(weights: tuple = (1.0, 1.0, 1.0), R: int = 1) -> float:
    """Busiest-device cost below which a level counts as *narrow* for the
    DAG-partition merge pass (``sched="dagpart"``).

    A level whose critical device does less work than ``MERGE_NARROW_ROWS``
    typical block rows is launch-overhead-bound: the grid step / exchange
    segment costs more than the level's compute, so merging it into the
    neighbouring superstep wins. "Typical row" = one diagonal TRSV plus two
    tile products, priced by the same (calibrated) weights that drive the
    malleable placement — the heuristic sharpens automatically as the
    wall-clock feedback loop refines the weights.
    """
    w_solve, w_tile_mem, w_tile_flop = weights
    unit = w_solve * R + 2.0 * (w_tile_mem + w_tile_flop * R)
    return MERGE_NARROW_ROWS * max(float(unit), 1e-9)


def _measured(fn, *args) -> tuple[float, float]:
    """(dot flops, dot traffic bytes) of the compiled fn at these shapes."""
    txt = jax.jit(fn).lower(*args).compile().as_text()
    r = hlo_cost.analyze(txt)
    return float(r["flops"]), float(r["dot_bytes"])


def _term(flops: float, bytes_: float, analytic_flops: float,
          analytic_bytes: float) -> float:
    f = flops if flops > 0 else analytic_flops
    by = bytes_ if bytes_ > 0 else analytic_bytes
    return f + FLOPS_PER_BYTE * by


def calibrate_weights(B: int = 32, backend: str | None = None, *,
                      feedback: bool = True) -> tuple:
    """(w_solve, w_tile_mem, w_tile_flop) for B×B tiles on ``backend``,
    normalized to w_solve = 1.

    The wall-clock feedback loop takes precedence: when the calibration
    store (:mod:`repro.obs.calibration`) holds enough measured probe-solve
    samples for this (backend, B) to fit trustworthy weights, those fitted
    weights are returned — a ``probe_solves=0`` session inherits timings a
    prior probed session persisted. Otherwise (or with ``feedback=False``)
    the HLO-derived estimate below is used. Both paths return a stable cached
    tuple per (B, backend) until new samples arrive.
    """
    if feedback:
        from repro.obs.calibration import fitted_weights

        w = fitted_weights(B, backend)
        if w is not None:
            return w
    return hlo_weights(B, backend)


@functools.lru_cache(maxsize=None)
def hlo_weights(B: int = 32, backend: str | None = None) -> tuple:
    """The pure HLO-derived weight estimate (no measured feedback), cached
    per (B, backend)."""
    kb = ops.op_backend(backend)
    diag = jnp.eye(B, dtype=jnp.float32)[None]
    vec = jnp.ones((1, B), jnp.float32)
    panel = jnp.ones((1, R_PROBE, B), jnp.float32)  # a (k, R, B) row panel

    def trsv(d, r):
        return ops.batched_block_trsv(d, r, backend=kb)

    def gemv(t, x):
        return ops.batched_block_gemv(t, x, backend=kb)

    def gemv_rows(t, x):
        return ops.batched_block_gemv_rows(t, x, backend=kb)

    tile_bytes = B * B * 4
    t_f, t_b = _measured(trsv, diag, vec)
    g1_f, g1_b = _measured(gemv, diag, vec)
    gR_f, gR_b = _measured(gemv_rows, diag, panel)
    # analytic fallbacks: TRSV touches the triangle (B² flops), each product
    # moves the full tile plus in/out vectors
    t1 = _term(t_f, t_b, B * B, tile_bytes + 2 * B * 4)
    g1 = _term(g1_f, g1_b, 2 * B * B, tile_bytes + 2 * B * 4)
    gR = _term(gR_f, gR_b, 2 * B * B * R_PROBE, tile_bytes + 2 * B * R_PROBE * 4)
    w_tile_flop = max(0.0, (gR - g1) / (R_PROBE - 1))
    w_tile_mem = max(0.0, g1 - w_tile_flop)
    return (1.0, w_tile_mem / t1, w_tile_flop / t1)
