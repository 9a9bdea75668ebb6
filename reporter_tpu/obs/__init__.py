"""reporter_tpu.obs — pipeline-wide metrics, tracing, and logging.

``metrics``   dependency-free Counter/Gauge/Histogram registry with
              Prometheus text exposition, JSON snapshots (incl. per-bucket
              exemplars), and cross-process snapshot merging
              (docs/observability.md lists every family)
``trace``     always-on per-request trace context: trace_id + Span stage
              timings, carried via contextvars end to end
``flight``    bounded in-memory flight recorder with tail sampling
              (GET /debug/traces; dumped on SIGTERM/fatal)
``log``       structured one-line-JSON/text event logger; one
              ``configure()`` shared by every entrypoint
``profiler``  on-demand jax.profiler captures (GET /debug/profile),
              single-flight across every capture kind
``attrib``    named-stage device-time attribution: the kernels'
              jax.named_scope labels parsed out of profiler captures
              (GET /debug/attrib, bench.py's ``attrib`` block, the
              reporter_stage_device_seconds gauges) plus the shared
              roofline/row accounting against the device kind's HBM peak
``quantile``  ONE implementation of histogram-quantile math (Prometheus
              semantics) + the shared SLO_BUCKETS_S log-bucket table —
              used by the SLO engine, tools/trace_top.py and
              tools/loadgen.py so every surface computes the same number
``slo``       server-side SLO engine: declarative objectives over
              sliding windows, error-budget burn rates with multi-window
              AND-gated alerting, fed from every terminal request
              outcome (GET /debug/slo, the /statusz burn line, the
              reporter_slo_* families)
``federation``fleet metrics federation: per-replica snapshot pulls with
              stale-labeled retention, the replica-labeled federated
              Prometheus render (router GET /metrics), the client-truth
              reporter_fleet_slo_* family bundle, and the masking-debt
              gauge billing failover-hidden replica burn
              (docs/observability.md "Fleet observability")
"""

from .metrics import (  # noqa: F401
    BATCH_FILL_BUCKETS,
    LATENCY_BUCKETS_S,
    REGISTRY,
    Registry,
    counter,
    gauge,
    histogram,
    merge,
)
from .trace import Span, bind, current_span, current_trace_id, new_trace_id  # noqa: F401

__all__ = [
    "BATCH_FILL_BUCKETS",
    "LATENCY_BUCKETS_S",
    "REGISTRY",
    "Registry",
    "Span",
    "bind",
    "counter",
    "current_span",
    "current_trace_id",
    "gauge",
    "histogram",
    "merge",
    "new_trace_id",
]
