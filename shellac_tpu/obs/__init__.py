"""shellac_tpu.obs — unified metrics, request tracing & introspection.

A dependency-free metrics core (`Counter`, `Gauge`, `Histogram` with
per-bucket trace-id exemplars, `Registry` with labeled series and
Prometheus text exposition), the `RequestTrace` span recorder that
rides each serving request from submit to settlement, and the
distributed-tracing layer (`events.py`): W3C-shaped trace ids with the
x-shellac-trace / x-request-id header contract, plus the
`FlightRecorder` ring of lifecycle events behind the /debug endpoints.
Engines, the HTTP server, and the training loop all deposit into one
process-global registry by default (`get_registry()`), so
`GET /metrics` — or a bench snapshot — sees training throughput and
serving latency through one exposition path.

The fleet layer builds on those: `promtext.py` (the one scrape-side
Prometheus text parser every consumer shares), `fleet.py` (the tier's
federated collector — replica series re-exposed with a `replica`
label, last-known-good through outages, `shellac_fleet_*` merged
aggregates), and `slo.py` (declarative objectives evaluated by
multi-window burn rate, with an ok→warning→page alert state machine
that lands transitions in the flight recorder).

The incident layer makes the evidence durable: `spool.py` (a
rotating, size-capped JSONL spill sink the recorder writes through,
so a SIGKILL'd replica's timelines survive to disk), `incident.py`
(trigger-driven evidence bundles — SLO pages, supervisor rebuilds,
severed/exhausted tier requests, manual POST /debug/incident — each
an atomic on-disk snapshot of the recorder, metrics, in-flight table,
SLO state, and config fingerprint), and `tracereport.py` (the
trace-reading half of /debug/profile: op-level attribution, fusion
counts, and phase alignment from a captured device trace, with a
regression-flagging diff).

See docs/observability.md for the metric catalog, the tracing/header
contract, the recorder event catalog, and §Fleet.
"""

from shellac_tpu.obs.events import (
    REQUEST_ID_HEADER,
    TRACE_HEADER,
    FlightRecorder,
    adopt_trace,
    format_trace_header,
    new_trace_id,
    parse_trace_header,
)
from shellac_tpu.obs.fleet import (
    MERGED_HISTOGRAMS,
    FleetCollector,
)
from shellac_tpu.obs.incident import (
    TRIGGERS,
    IncidentManager,
)
from shellac_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    get_registry,
    linear_buckets,
    log_buckets,
    set_default_registry,
)
from shellac_tpu.obs.promtext import (
    ParsedMetrics,
    cumulative_at,
    histogram_quantile,
    merge_buckets,
    parse_prometheus_text,
)
from shellac_tpu.obs.scenario import (
    ScenarioMetrics,
)
from shellac_tpu.obs.slo import (
    SLOEngine,
    SLOSpec,
    parse_slo_specs,
)
from shellac_tpu.obs.spool import (
    EventSpool,
    read_spool,
    spool_events_for,
    spool_path,
)
from shellac_tpu.obs.trace import (
    LAUNCH_KINDS,
    SPAN_PHASE,
    STEP_COUNTS,
    STEP_PHASES,
    EngineMetrics,
    RequestTrace,
    ServeMetrics,
    StepRecord,
    StepTrace,
    TierMetrics,
)
from shellac_tpu.obs.train import (
    ResilienceMetrics,
    train_interval_histogram,
)

__all__ = [
    "FlightRecorder",
    "TRACE_HEADER",
    "REQUEST_ID_HEADER",
    "new_trace_id",
    "parse_trace_header",
    "format_trace_header",
    "adopt_trace",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "get_registry",
    "set_default_registry",
    "log_buckets",
    "linear_buckets",
    "EngineMetrics",
    "RequestTrace",
    "ServeMetrics",
    "TierMetrics",
    "ResilienceMetrics",
    "train_interval_histogram",
    "STEP_PHASES",
    "SPAN_PHASE",
    "STEP_COUNTS",
    "LAUNCH_KINDS",
    "StepRecord",
    "StepTrace",
    "ParsedMetrics",
    "parse_prometheus_text",
    "histogram_quantile",
    "cumulative_at",
    "merge_buckets",
    "FleetCollector",
    "MERGED_HISTOGRAMS",
    "SLOEngine",
    "SLOSpec",
    "parse_slo_specs",
    "ScenarioMetrics",
    "IncidentManager",
    "TRIGGERS",
    "EventSpool",
    "read_spool",
    "spool_events_for",
    "spool_path",
]
