"""``repro.serve``: the production service layer over PUD sessions.

The paper's headline capabilities — MAJX integrity voting (§5),
Multi-RowCopy healing/bulk-erase (§6/§8.2) — matter at production scale
only if many concurrent requests share the simultaneous-many-row
substrate efficiently.  This package is that service subsystem:

* :mod:`repro.serve.queue` — typed ``IntegrityRequest`` / ``HealRequest``
  / ``EraseRequest`` with priorities, deadlines, per-tenant accounting;
* :mod:`repro.serve.admission` — per-tenant row arenas, bounded queues,
  backpressure, load-shedding;
* :mod:`repro.serve.batcher` — continuous batching: same-shape requests
  coalesce into ONE fused Program per tick;
* :mod:`repro.serve.scrub` — replica sets resident on the device and
  their tile-by-tile MAJ scrub;
* :mod:`repro.serve.region` — a region of rows resident on the device
  and its bank-by-bank Multi-RowCopy wipe in place;
* :mod:`repro.serve.slo` — request traces + rolling p50/p99/throughput/
  occupancy/cache-hit SLO snapshots;
* :mod:`repro.serve.service` — :class:`PudService`, the engine tying
  them together over a pool of :class:`~repro.session.DramSession`\\ s.

:mod:`repro.serve.engine` (the LM serving engine whose integrity hooks
are thin clients of :class:`PudService`) is imported separately — it
pulls in the model stack, which service-only consumers don't need.
"""

from repro.serve.admission import (AdmissionController, AdmissionError,
                                   ArenaExhaustedError,
                                   DeadlineExceededError, QueueFullError,
                                   TenantArena)
from repro.serve.batcher import Batcher, BatchOutcome, BatchPlan
from repro.serve.queue import (EraseRequest, EraseResult, HealRequest,
                               HealResult, IntegrityRequest,
                               IntegrityResult, Priority, PudRequest,
                               RegionEraseRequest, RegionEraseResult,
                               RequestQueue, ScrubRequest, ScrubResult,
                               ServeError)
from repro.serve.region import Region
from repro.serve.scrub import ReplicaSet
from repro.serve.service import PudService, ServiceConfig
from repro.serve.slo import RequestTrace, SloMonitor, SloSnapshot, Span

__all__ = [
    "AdmissionController", "AdmissionError", "ArenaExhaustedError",
    "BatchOutcome", "BatchPlan", "Batcher", "DeadlineExceededError",
    "EraseRequest", "EraseResult", "HealRequest", "HealResult",
    "IntegrityRequest", "IntegrityResult", "Priority", "PudRequest",
    "PudService", "QueueFullError", "Region", "RegionEraseRequest",
    "RegionEraseResult", "ReplicaSet", "RequestQueue",
    "RequestTrace", "ScrubRequest", "ScrubResult", "ServeError",
    "ServiceConfig", "SloMonitor", "SloSnapshot", "Span", "TenantArena",
]
