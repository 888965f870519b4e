"""SpTRSV-as-a-service: multi-tenant batched solve engine (ISSUE 9 tentpole).

Three layers over the session API:

* :mod:`repro.service.planstore` — cross-session persistence of the symbolic
  analysis (block structure, partition, compacted schedules, ``step_off``,
  bucket tables) keyed by pattern sha1 x options signature, so short-lived
  workers skip the expensive dependency analysis entirely.
* :mod:`repro.service.queue` — multi-tenant request admission: same-pattern
  RHS vectors coalesce into the multi-RHS ``(n, R)`` panels one compiled
  solve executes, under a max-wait/max-batch window with per-tenant fairness
  and bounded-queue backpressure.
* :mod:`repro.service.engine` — the serve loop driving one
  :class:`repro.api.SpTRSVContext`: plan-store-backed analyse, in-place value
  refresh on hot patterns, ``service.*`` metrics and ``service.request`` /
  ``service.batch`` tracer spans through :mod:`repro.obs`.
"""
from repro.service.engine import SolveEngine
from repro.service.planstore import PlanStore, options_signature
from repro.service.queue import QueueFull, SolveQueue, SolveRequest, Ticket

__all__ = [
    "PlanStore",
    "QueueFull",
    "SolveEngine",
    "SolveQueue",
    "SolveRequest",
    "Ticket",
    "options_signature",
]
