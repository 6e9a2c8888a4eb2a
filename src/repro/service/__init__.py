"""Serving tier: plan cache, shape-bucketed jit reuse, SQL front door.

Guarded aggregate plans are static-dataflow programs — compile once, serve
many.  This package owns everything between "SQL arrives" and "compiled
program runs": query fingerprinting (``fingerprint``), the multi-level
plan cache (``plan_cache``), the persistent cross-process plan store
(``plan_store``), the concurrent micro-batching engine (``engine``), the
async cross-caller batch former (``scheduler``), the persistent
statistics store behind cost-calibrated planning (``stats_store``), and
the tracing + metrics registry every request reports into
(``observability``).
"""

from repro.service.engine import (
    AdmissionError,
    QueryResult,
    QueryService,
    ServeStats,
    ServiceClosedError,
    TenantAdmissionError,
)
from repro.service.fingerprint import (
    CanonicalQuery,
    canonicalize,
    fingerprint,
    prefix_fingerprint,
)
from repro.service.observability import (
    DEFAULT_TENANT,
    Histogram,
    Observability,
    TraceSpan,
)
from repro.service.plan_cache import LRUCache, PlanCache
from repro.service.plan_store import (
    PlanStore,
    enable_executable_cache,
    schema_fingerprint,
    store_fingerprint,
)
from repro.service.scheduler import AsyncScheduler, TenantPolicy
from repro.service.stats_store import StatsStore

__all__ = [
    "AdmissionError",
    "AsyncScheduler",
    "DEFAULT_TENANT",
    "CanonicalQuery",
    "canonicalize",
    "enable_executable_cache",
    "fingerprint",
    "prefix_fingerprint",
    "Histogram",
    "LRUCache",
    "Observability",
    "PlanCache",
    "TraceSpan",
    "PlanStore",
    "QueryResult",
    "QueryService",
    "ServeStats",
    "ServiceClosedError",
    "StatsStore",
    "TenantAdmissionError",
    "TenantPolicy",
    "schema_fingerprint",
    "store_fingerprint",
]
