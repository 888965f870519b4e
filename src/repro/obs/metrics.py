"""Typed metrics registry (ISSUE 6 tentpole, part b).

One home for the quantities that used to live in scattered one-off probes —
``dispatch_stats`` / ``cut_stats`` / ``comm_bytes_per_solve`` (plan-static)
and cache hit rates / refresh counts / per-solve wall-clock / probe timings
(runtime). Three instrument types:

* :class:`Counter`   — monotically increasing event count (``inc``),
* :class:`Gauge`     — last-written value (``set``),
* :class:`Histogram` — running count/sum/min/max/last of observations
  (``observe``) — enough for wall-clock distributions without binning.

``snapshot()`` returns a plain JSON-serializable dict and ``dump()`` appends
it as one JSONL line (the same sink format the span tracer uses, so a trace
file can interleave spans and metrics snapshots).

:func:`record_plan_metrics` is the bridge from the solver's plan-static
probes into the registry: it mirrors ``dispatch_stats``/``cut_stats`` and the
communication/DMA/VMEM byte counts under ``plan.*`` gauges, so a snapshot of
a known plan agrees field-for-field with the scattered stats it unifies.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time

import jax.monitoring


class Counter:
    __slots__ = ("value",)

    kind = "counter"

    def __init__(self):
        self.value = 0

    def inc(self, v: int = 1) -> None:
        self.value += v

    def snap(self):
        return self.value


class Gauge:
    __slots__ = ("value",)

    kind = "gauge"

    def __init__(self):
        self.value = 0.0

    def set(self, v) -> None:
        self.value = v

    def snap(self):
        return self.value


class Histogram:
    __slots__ = ("count", "total", "vmin", "vmax", "last")

    kind = "histogram"

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.last = 0.0

    def observe(self, v: float) -> None:
        v = float(v)
        self.count += 1
        self.total += v
        self.vmin = min(self.vmin, v)
        self.vmax = max(self.vmax, v)
        self.last = v

    def snap(self) -> dict:
        if self.count == 0:
            return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                    "mean": 0.0, "last": 0.0}
        return {"count": self.count, "sum": self.total, "min": self.vmin,
                "max": self.vmax, "mean": self.total / self.count,
                "last": self.last}


class MetricsRegistry:
    """Named typed instruments, created on first use.

    Re-requesting a name with a different instrument type is a programming
    error and raises — one name, one meaning, for the life of the registry.
    """

    def __init__(self):
        self._metrics: dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls()
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} is a {m.kind}, requested {cls.kind}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def snapshot(self) -> dict:
        """``{name: value}`` for every instrument (histograms as summary
        dicts), JSON-serializable, sorted by name."""
        with self._lock:
            return {name: _jsonable(self._metrics[name].snap())
                    for name in sorted(self._metrics)}

    def dump(self, path: str) -> dict:
        """Append one ``{"type": "metrics", ...}`` JSONL line; returns the
        snapshot it wrote."""
        snap = self.snapshot()
        rec = {"type": "metrics", "t_unix_s": time.time(), "metrics": snap}
        with open(path, "a", buffering=1) as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
        return snap

    def clear(self) -> None:
        with self._lock:
            self._metrics.clear()


def _jsonable(v):
    if isinstance(v, bool):
        return int(v)
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (int, float, str)) or v is None:
        return v
    if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
        return v.item()  # numpy scalar
    return str(v)


def record_plan_metrics(registry: MetricsRegistry, plan, *, prefix: str = "plan"
                        ) -> MetricsRegistry:
    """Mirror a plan's static probes into ``prefix.*`` gauges.

    Covers exactly the quantities the solver already reports — launch /
    dispatch / exchange counts, the fused memory plan (``streamed``,
    ``fused_vmem_bytes``, ``stream_dma_bytes``), the collective payload
    (``comm_bytes_per_solve``), and the partition's cut/balance statistics
    (``boundary_fraction``, ``level_cost_imbalance``, ...) — so the registry
    snapshot is byte-for-byte reconciled with ``dispatch_stats``/``cut_stats``
    in tests.
    """
    from repro.core.partition import cut_stats
    from repro.core.solver import dispatch_stats

    g = registry.gauge
    for k, v in dispatch_stats(plan).items():
        g(f"{prefix}.{k}").set(_jsonable(v))
    g(f"{prefix}.comm_bytes_per_solve").set(plan.comm_bytes_per_solve)
    g(f"{prefix}.n_levels").set(plan.n_levels)
    g(f"{prefix}.n_devices").set(plan.n_devices)
    g(f"{prefix}.n_buckets").set(len(plan.buckets))
    g(f"{prefix}.n_boundary_rows").set(plan.n_boundary_rows)
    for f in dataclasses.fields(cs := cut_stats(plan.bs, plan.part)):
        g(f"{prefix}.{f.name}").set(_jsonable(getattr(cs, f.name)))
    return registry


# -- global registry -------------------------------------------------------

_global = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide default registry (contexts, engines, and benches
    record here unless handed their own)."""
    return _global


# -- compile accounting ----------------------------------------------------

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    # wraps a persistent-cache load as well as a compile
    BACKEND_COMPILE_EVENT,
)
_compile_lock = threading.Lock()


def _on_duration_event(event: str, duration_secs: float, **kwargs) -> None:
    """JAX duration listener, registered once when this module is imported:
    ``jit.compile_s`` sums the seconds of JAX's trace, lower and
    backend-compile (or persistent-cache load) events; ``jit.compiles``
    counts the backend compiles and cache loads. These events fire only when
    JAX compiles, never on the dispatch of a compiled program."""
    if event not in COMPILE_EVENTS:
        return
    with _compile_lock:
        g = _global.gauge("jit.compile_s")
        g.set(g.value + duration_secs)
        if event == BACKEND_COMPILE_EVENT:
            _global.counter("jit.compiles").inc()


jax.monitoring.register_event_duration_secs_listener(_on_duration_event)
