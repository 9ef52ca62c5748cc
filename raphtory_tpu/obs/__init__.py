"""Observability — metrics + tracing (reference L8, SURVEY §5.1/§5.5).

The reference wires Kamon counters/gauges into every actor and serves
Prometheus on :11600 (``application.conf:208-213``); here the same signal
set is prometheus_client metrics updated by the pipeline/job/compaction
layers, plus spans whose ``TraceAnnotation`` twins land in any JAX
profiler capture (``obs/trace.py``; whoever wants a device trace starts
the profiler with the options it needs, as ``benchmark/run.py`` does)."""

from .trace import (TRACER, TraceContext, Tracer,   # stdlib-only —
                    span)                           # always available
from .ledger import Ledger, REGISTRY, instrument   # stdlib-only (jax lazy)
from .device import RESIDENT, TIMING               # stdlib-only (jax lazy)
from .slo import SERIES, SLO                       # stdlib-only
from .sampler import SAMPLER                       # stdlib-only
from .workload import WORKLOAD                     # stdlib-only
from .budget import BUDGET                         # stdlib-only
from .advisor import ADVISOR                       # stdlib-only
from .freshness import FRESH                       # stdlib-only (numpy lazy)

try:
    # metrics need prometheus_client, which stripped transport-only
    # environments may lack; the span tracer must keep working there
    # (utils/transfer.py relies on this degradation)
    from .metrics import METRICS, Metrics, MetricsServer
except ImportError:   # pragma: no cover — stripped environment
    METRICS = Metrics = MetricsServer = None

__all__ = ["METRICS", "Metrics", "MetricsServer",
           "TRACER", "TraceContext", "Tracer", "span",
           "Ledger", "REGISTRY", "instrument", "SLO", "SERIES",
           "SAMPLER", "WORKLOAD", "BUDGET", "ADVISOR", "RESIDENT",
           "TIMING", "FRESH"]
