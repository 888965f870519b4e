"""Unified telemetry: span tracing, metrics registry, calibration feedback.

Three cooperating pieces (ISSUE 6):

* :mod:`repro.obs.trace`       — nested lifecycle spans -> JSONL
  (``REPRO_TRACE=path.jsonl``), aligned with XLA profiles via
  ``jax.named_scope`` annotations baked into the executors.
* :mod:`repro.obs.metrics`     — typed counters/gauges/histograms unifying
  the solver's scattered plan-static and runtime stats behind one
  ``snapshot()``/JSONL sink, and one ``jax.monitoring`` listener, registered
  on import, that feeds ``jit.compiles``/``jit.compile_s``.
* :mod:`repro.obs.calibration` — measured probe timings persisted per
  (backend, bucket-width signature) and fitted back into
  ``core.costmodel.calibrate_weights`` (``REPRO_CALIBRATION=weights.json``).

All of it is zero-cost when disabled: the null tracer is a shared no-op,
registry writes are a few dict operations, and nothing here ever enters
traced computation — solve results are bit-identical with telemetry on or
off, and toggling it cannot retrace a compiled executor.
"""
from repro.obs.calibration import (
    CalibrationStore,
    calibrated_stream_limit,
    fitted_weights,
    get_store,
    probe_signature,
    set_store,
)
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    record_plan_metrics,
)
from repro.obs.trace import (
    NULL_TRACER,
    Tracer,
    configure_tracing,
    get_tracer,
    trace_to,
)

__all__ = [
    "CalibrationStore", "calibrated_stream_limit", "fitted_weights",
    "get_store", "probe_signature",
    "set_store", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_registry", "record_plan_metrics", "NULL_TRACER", "Tracer",
    "configure_tracing", "get_tracer", "trace_to",
]
