"""Public jit'd kernel wrappers with backend dispatch.

Backends:
* ``reference`` — pure jnp (XLA) oracles from :mod:`repro.kernels.ref`; the
  default on CPU where Pallas interpret mode would be pure-Python slow.
* ``pallas``    — the TPU kernels; on CPU they run in interpret mode
  (used by tests to validate kernel semantics), on TPU they compile natively.
* ``fused``     — an *executor-level* backend: the levelset executors run the
  whole compacted schedule in one Pallas superstep megakernel
  (:mod:`repro.kernels.superstep`) and syncfree runs frontier-bucketed.
  Individual block ops called under it fall back to the platform default.
* ``fused_streamed`` — the megakernel with the streaming HBM tile store:
  ``diag``/``tiles`` live in ``ANY``/HBM and each level's schedule slice is
  double-buffered into VMEM by async DMA, so VMEM residency scales with the
  widest level slice instead of the total tile count. Plain ``fused`` also
  auto-upgrades to streaming when the resident store would exceed
  ``core.solver.stream_vmem_limit()``. For ``sched="syncfree"`` it behaves
  exactly like ``fused`` (the frontier executor has no resident tile problem).

Each operation has two forms, one per device layout. ``batched_block_trsv``
and ``batched_block_gemv`` take a single right-hand side per tile, ``(k, B)``.
The ``*_rows`` ops take multi-RHS panels as ``(k, R, B)`` row panels, the
right-hand sides as rows with B on the lanes: the layout every executor and
the Krylov SpMV carry, so one compiled solve serves R systems. A ``(k, B, R)``
column panel reaches no kernel; the vector ops refuse rank-3 input.

Select globally with env ``REPRO_KERNEL_BACKEND`` or per-call with ``backend=``.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.block_spmv import block_gemm_rows, block_gemv
from repro.kernels.block_trsv import block_trsm_rows, block_trsv

BACKENDS = ("reference", "pallas", "fused", "fused_streamed")

# executor-level backends that select the megakernel levelset path (and the
# frontier-bucketed syncfree executor)
FUSED_BACKENDS = ("fused", "fused_streamed")


def _default_backend() -> str:
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        return env
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def executor_backend(backend: str | None = None) -> str:
    """Resolve the executor-level backend (``fused`` selects the megakernel
    levelset path / frontier-bucketed syncfree in ``core.solver``)."""
    b = backend or _default_backend()
    if b not in BACKENDS:
        raise ValueError(f"unknown kernel backend: {b!r} (expected {BACKENDS})")
    return b


def is_fused(backend: str | None = None) -> bool:
    """Whether the resolved executor backend is a fused (megakernel) variant."""
    return executor_backend(backend) in FUSED_BACKENDS


def op_backend(backend: str | None = None) -> str:
    """Resolve the per-op backend; the fused variants degrade to the platform
    default (pallas on TPU, reference elsewhere) for the residual batched ops."""
    b = executor_backend(backend)
    if b in FUSED_BACKENDS:
        b = "pallas" if jax.default_backend() == "tpu" else "reference"
    return b


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def default_block_size() -> int:
    """Tile size B when the caller sets none: 128 where Pallas compiles for
    the TPU, 32 elsewhere. Mosaic lays f32 arrays out in 128-lane tiles and
    slices HBM and VMEM refs only at multiples of them, so the superstep
    kernel's per-step DMAs and row reads need ``B % 128 == 0`` (at B=32 it
    does not compile, and at 2^20 rows its schedule would also outgrow the
    1 MiB of SMEM). Interpreted kernels have no such limit, and the smaller
    tile stores fewer dense zeros on scattered patterns."""
    return 32 if _interpret() else 128


def interpret_mode() -> bool:
    """Whether Pallas kernels (incl. the superstep megakernel) run interpreted."""
    return _interpret()


def bcast_trailing(mask: jax.Array, x: jax.Array) -> jax.Array:
    """Reshape ``mask`` with trailing singleton dims so it broadcasts against
    ``x`` — lets solver code stay agnostic to single- vs multi-RHS shapes."""
    return mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim))


def _vectors_only(x: jax.Array, rows_op: str) -> None:
    if x.ndim != 2:
        raise ValueError(
            f"expected (k, B) vectors, got shape {x.shape}: R-column panels "
            f"go to ops.{rows_op} as (k, R, B) row panels")


def batched_block_trsv(diag: jax.Array, rhs: jax.Array, *, backend: str | None = None,
                       algorithm: str = "rowsweep") -> jax.Array:
    """(k,B,B) tiles × (k,B) vectors -> (k,B)."""
    _vectors_only(rhs, "batched_block_trsv_rows")
    if op_backend(backend) == "reference":
        return ref.block_trsv_ref(diag, rhs)
    return block_trsv(diag, rhs, algorithm=algorithm, interpret=_interpret())


def batched_block_gemv(tiles: jax.Array, xs: jax.Array, *,
                       backend: str | None = None) -> jax.Array:
    """(m,B,B) tiles × (m,B) vectors -> (m,B), ``tiles[m] @ xs[m]``."""
    _vectors_only(xs, "batched_block_gemv_rows")
    if op_backend(backend) == "reference":
        return ref.block_gemv_ref(tiles, xs)
    return block_gemv(tiles, xs, interpret=_interpret())


def batched_block_trsv_rows(diag: jax.Array, rhs: jax.Array, *,
                            backend: str | None = None) -> jax.Array:
    """(k,B,B) tiles × (k,R,B) row panels -> (k,R,B)."""
    if op_backend(backend) == "reference":
        return ref.block_trsv_rows_ref(diag, rhs)
    return block_trsm_rows(diag, rhs, interpret=_interpret())


def batched_block_gemv_rows(tiles: jax.Array, xs: jax.Array, *,
                            backend: str | None = None) -> jax.Array:
    """(m,B,B) tiles × (m,R,B) row panels -> (m,R,B), ``xs[m] @ tiles[m]^T``."""
    if op_backend(backend) == "reference":
        return ref.block_gemv_rows_ref(tiles, xs)
    return block_gemm_rows(tiles, xs, interpret=_interpret())
