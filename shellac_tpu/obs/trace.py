"""Request-trace spans and the serving/engine instrument bundles.

A `RequestTrace` rides one request through the serving pipeline —
submit -> queue wait -> prefill -> first token -> per-token decode ->
finish/shed/abort — and deposits the derived latency histograms
(queue-wait, TTFT, time-per-output-token, end-to-end) on settlement.
Every timestamp is host-side `time.monotonic()` captured at an event
the host already observes (queue pop, post-sync token arrival), so
tracing adds no host-device syncs anywhere, let alone inside jitted
code (the SH002 contract).

A `StepTrace` is its sibling for ONE ENGINE: it records every engine
step that did work as a tree of spans (`engine.step` down to the
`cache.*` calls) plus the step's work counts, on
`time.perf_counter_ns()`, mirrors each span as a
`jax.profiler.TraceAnnotation` so a profiler capture shows it beside
the device operations, and derives `shellac_step_phase_seconds` from
the closed spans. It also keeps the engine's own device timeline:
every jitted program a step dispatches is a *launch*, a numbered row
stamped when the program is found finished at a pull the step makes
anyway (`StepTrace.launch` / `land`), which gives each program's
device time without a profiler. Finished records land in a bounded
ring on the registry (`Registry.step_records`), which outlives the
engine.

`ServeMetrics` / `EngineMetrics` bundle the instruments each layer
writes so the metric names and bucket layouts are defined exactly once;
both are cheap to construct repeatedly over the same registry
(registration is idempotent).
"""

from __future__ import annotations

import collections
import time
import weakref
from typing import Any, Dict, List, Optional

from shellac_tpu.obs.metrics import (
    Registry,
    linear_buckets,
    log_buckets,
)

#: Latency buckets shared by the request-span histograms: ~1ms..60s.
LATENCY_BUCKETS = log_buckets(0.001, 60.0, per_decade=4)
#: Per-output-token pace is faster than request latency: ~0.1ms..10s.
TPOT_BUCKETS = log_buckets(0.0001, 10.0, per_decade=4)
#: Batch occupancy is a ratio; eighths resolve typical slot counts.
OCCUPANCY_BUCKETS = linear_buckets(0.125, 0.125, 8)

#: Step-time phases (the `phase` label of shellac_step_phase_seconds).
#: Every engine step's wall time decomposes into exactly these, so
#: sum-over-phases ≈ step wall time and "where does the tick go" is a
#: committed measurement (the disaggregation question's input):
#:   admission        — queue pops, slot prep, finish checks in the
#:                      fill loop (everything admission-side that is
#:                      NOT the prefill programs themselves)
#:   prefill_dispatch — prefill/chunk program dispatches (host-side
#:                      dispatch cost only; with overlapped prefill
#:                      the sync moved to prefill_settle)
#:   prefill_settle   — time blocked in the prefill settle's one
#:                      batched device_get plus its host bookkeeping
#:                      (inline per admission without overlap_prefill;
#:                      one batched pull per step boundary with it)
#:   decode_sync      — time blocked in the decode window's one
#:                      packed device_get
#:   settle           — applying synced window results: detokenize
#:                      appends, finish checks, slot release
#:   host_bookkeeping — the remainder (dispatch bookkeeping, gauge
#:                      updates, scheduler glue)
STEP_PHASES = ("admission", "prefill_dispatch", "prefill_settle",
               "decode_sync", "settle", "host_bookkeeping")

#: Which phase a step span's SELF time (its duration minus its
#: children's) is credited to. A span not listed here (the `cache.*`
#: spans) is credited to its parent's phase, so the six sums are the
#: step's wall time exactly: self times partition the `engine.step`
#: span.
SPAN_PHASE = {
    "engine.step": "host_bookkeeping",
    "engine.settle_prefills": "prefill_settle",
    "engine.wait_prefill": "prefill_settle",
    "engine.dispatch_window": "host_bookkeeping",
    "engine.wait_window": "decode_sync",
    "engine.apply_window": "settle",
    "engine.fill": "admission",
    "engine.admit": "admission",
    "engine.prefill_dispatch": "prefill_dispatch",
}

#: The work counts every step record carries (see docs/observability.md
#: "Step spans"). The first six are taken where the number is known
#: (`prefill_sorted_tokens`: the padded prompt rows whose expert FFN
#: ran over the sorted routed rows, by ops/moe.py::moe_ffn_path; 0 on
#: a model without experts); `compiles`/`compile_s` are what the
#: process-wide compile listener
#: added to the registry since the previous record; the three `eva_*`
#: are the cache backend's (`CacheBackend.window_counts`; 0 but on the
#: 'eva' backend): over every slot-tick of a synced window that
#: produced a token, the exact rows of its own window and the pooled
#: rows of earlier windows that its query attended, and the rows the
#: decode program's read path MOVED for them (attended / read is its
#: read efficiency), from lengths the host has;
#: and, for a model with an indexer (cfg.dsa, the 'paged' backend; 0
#: elsewhere), over the same slot-ticks the rows the indexer scored
#: (the context) and the rows the query then attended (the context or
#: the rows kept, whichever is less); and, for a looped stack (cfg.loop;
#: 0 elsewhere), over the same slot-ticks the stack passes run (`steps`
#: each) and the cached rows read over all passes (`steps` x the
#: context); `slot_uploads`: the host arrays of slot state the step
#: handed to its programs (the block table, the settings matrix, the
#: carried vectors' patch; each only when it changed: 0 on a step that
#: neither admits, settles, releases nor grows a slot, nor follows one
#: that released after its window went out). With `compiles` it says
#: that admission dispatches nothing of its own.
STEP_COUNTS = ("tokens_delivered", "decode_slot_ticks",
               "decode_valid_ticks", "prefill_tokens",
               "prefill_padded_tokens", "prefill_sorted_tokens",
               "compiles", "compile_s",
               "eva_window_rows", "eva_summary_rows", "eva_read_rows",
               "dsa_index_rows", "dsa_selected_rows",
               "loop_passes", "loop_kv_rows", "slot_uploads")

#: What a launch row calls the program it stands for (the `kind` label
#: of shellac_launch_device_seconds and shellac_engine_launches_total):
#: `prefill`, the whole-prompt program (a fresh scratch scattered into
#: a slot); `chunk`, the continuation program (a chunk of a long
#: prompt, or a suffix behind a matched prefix: it writes at an
#: offset); `window`, the decode window (a speculative round is one).
LAUNCH_KINDS = ("prefill", "chunk", "window")

#: Request outcomes (the `outcome` label of shellac_requests_total).
#: ok: completed; shed: deadline expired before prefill; cancelled:
#: client abandoned it; error: bad request; fault: server-side failure
#: (scheduler death, wedge, close) — the supervisor's loud-failure arm.
OUTCOMES = ("ok", "shed", "cancelled", "error", "fault")


class ServeMetrics:
    """The serving-layer instruments over one registry."""

    def __init__(self, registry: Registry):
        self.registry = registry
        h, c, g = registry.histogram, registry.counter, registry.gauge
        self.ttft = h(
            "shellac_ttft_seconds",
            "Time from request submit to its first generated token",
            buckets=LATENCY_BUCKETS,
        )
        self.tpot = h(
            "shellac_tpot_seconds",
            "Mean time per output token after the first, per request",
            buckets=TPOT_BUCKETS,
        )
        self.queue_wait = h(
            "shellac_queue_wait_seconds",
            "Time from request submit to the start of its prefill",
            buckets=LATENCY_BUCKETS,
        )
        self.e2e = h(
            "shellac_e2e_seconds",
            "End-to-end request latency (submit to completion)",
            buckets=LATENCY_BUCKETS,
        )
        # The tenant label is "" for traffic that carried no tenant id
        # (matching Registry.get's empty-string default, so untenanted
        # deployments keep their exact-key lookups and dashboards).
        self.requests = c(
            "shellac_requests_total",
            "Requests settled, by outcome (ok|shed|cancelled|error|"
            "fault) and tenant (empty for untenanted traffic)",
            labels=("outcome", "tenant"),
        )
        self.sheds = c(
            "shellac_requests_shed_total",
            "Requests shed on an expired deadline before prefill",
        )
        self.rejects = c(
            "shellac_admission_rejects_total",
            "Submissions refused at admission, by reason "
            "(overloaded|recovering|draining|throttled) and tenant "
            "(empty for untenanted traffic)",
            labels=("reason", "tenant"),
        )
        self.restarts = c(
            "shellac_supervisor_restarts_total",
            "Engine generations rebuilt by the serving supervisor",
        )
        self.generation = g(
            "shellac_engine_generation",
            "Current engine generation (bumps on supervisor rebuild)",
        )
        self.draining = g(
            "shellac_draining",
            "1 while a graceful drain is in progress (admission "
            "refused, in-flight requests completing), else 0",
        )
        self.uptime = g(
            "shellac_uptime_seconds", "Seconds since the server started"
        )
        self.pending = g(
            "shellac_pending_requests", "Requests currently pending"
        )
        self.constraint_compile = h(
            "shellac_constraint_compile_seconds",
            "Schema/regex -> token-DFA compile latency (paid on "
            "constraint-cache misses only)",
            buckets=LATENCY_BUCKETS,
        )
        self.constraint_cache = c(
            "shellac_constraint_cache_total",
            "Constraint DFA cache lookups, by result (hit|miss)",
            labels=("result",),
        )
        self.cache_backend_info = g(
            "shellac_engine_cache_backend_info",
            "Info gauge: always 1, labeled with the engine's active "
            "KV-cache storage backend (registry name, e.g. dense, "
            "paged-int8) so dashboards can group replicas by storage "
            "policy",
            labels=("backend",),
        )
        self.tool_requests = c(
            "shellac_tool_requests_total",
            "Tool-enabled requests by resolution: call (tool_calls "
            "parsed), text (auto chose free text), truncated (tool "
            "branch cut by the token budget)",
            labels=("outcome",),
        )
        self.role_info = g(
            "shellac_engine_role_info",
            "Info gauge: always 1, labeled with this replica's serving "
            "role (prefill | decode | monolith) — the tier's "
            "disaggregated pair scheduler groups replicas by it",
            labels=("role",),
        )
        self.migrations = c(
            "shellac_migrations_total",
            "KV-migration legs by outcome. Replica-side: export / "
            "export_failed (serialize+push from a prefill replica), "
            "import / import_failed (adoption on a decode replica). "
            "Tier-side: ok (full disaggregated path served), "
            "fallback_* (served monolithically: no_pair | cost | "
            "feature | failed)",
            labels=("outcome",),
        )
        self.kv_transfer_seconds = h(
            "shellac_kv_transfer_seconds",
            "Wall time of one KV-migration push (serialize excluded: "
            "POST /kv/import dispatch to the decode replica's ack)",
            buckets=LATENCY_BUCKETS,
        )
        self.kv_transfer_bytes = h(
            "shellac_kv_transfer_bytes",
            "Serialized size of one KV-migration blob (header + "
            "chunked device-block payload)",
            buckets=log_buckets(1e3, 1e9, per_decade=2),
        )
        self.fabric_seeded = c(
            "shellac_fabric_seeded_blocks_total",
            "Prefix-cache blocks registered from fleet seed pushes "
            "(POST /kv/seed) — KV this replica now serves without "
            "ever having prefilled it",
        )
        self.fabric_seed_rejects = c(
            "shellac_fabric_seed_rejects_total",
            "Seed blobs refused at the door with the registry "
            "untouched, by reason (corrupt|mismatch|exhausted|fault)",
            labels=("reason",),
        )
        self.fabric_parked = c(
            "shellac_fabric_parked_total",
            "Frozen sessions exported to the KV park spool",
        )
        self.fabric_resumed = c(
            "shellac_fabric_resumed_total",
            "Park-spool resume attempts, by outcome (ok: imported and "
            "adopted; missing: unknown park id; torn: blob failed "
            "integrity read-back and was quarantined)",
            labels=("outcome",),
        )
        self.fabric_park_bytes = g(
            "shellac_fabric_park_bytes",
            "Bytes currently resident in this replica's KV park spool "
            "(size-capped; LRU-trimmed on write)",
        )
        # Per-tenant QoS series. Unlike the widened request/reject
        # counters above, these key the RESOLVED tenant ("anonymous"
        # when no id rode the request), so a tenants dashboard always
        # accounts for every token served.
        self.tenant_tokens = c(
            "shellac_tenant_tokens_admitted_total",
            "Tokens admitted past per-tenant quota (prompt + budgeted "
            "max_new, the same cost the token bucket charges), by "
            "resolved tenant",
            labels=("tenant",),
        )
        self.tenant_throttles = c(
            "shellac_tenant_throttles_total",
            "Per-tenant quota rejections (HTTP 429 + Retry-After), by "
            "tenant and exhausted budget (rate|concurrency)",
            labels=("tenant", "reason"),
        )
        self.tenant_preemptions = c(
            "shellac_tenant_preemptions_total",
            "Requests frozen mid-decode and parked so a higher-"
            "priority class could take the slot, by victim tenant",
            labels=("tenant",),
        )
        self.tenant_parked_bytes = g(
            "shellac_tenant_parked_bytes",
            "Bytes of preempted KV currently parked awaiting resume, "
            "by victim tenant (measured blob size, the preemption "
            "cost model's input)",
            labels=("tenant",),
        )
        self.tenant_sheds = c(
            "shellac_tenant_sheds_total",
            "Deadline sheds by resolved tenant (the unlabeled "
            "shellac_requests_shed_total keeps the fleet total)",
            labels=("tenant",),
        )
        self._engine_stats: Dict[str, object] = {}

    def trace(self, trace_id: Optional[str] = None,
              recorder=None, tenant: Optional[str] = None
              ) -> "RequestTrace":
        """A span for one request. `trace_id` links the span to the
        distributed trace (the tier/header id); `recorder` is the
        server's FlightRecorder — when both are set the span's event
        methods also deposit timeline events, and the latency
        histograms retain the id as a per-bucket exemplar. `tenant`
        (None for untenanted traffic) labels the settlement counters."""
        return RequestTrace(self, trace_id=trace_id, recorder=recorder,
                            tenant=tenant)

    def engine_stat(self, key: str):
        """Scrape-time gauge mirroring one engine `stats` counter as
        `shellac_engine_<key>` (keys are code-side identifiers, so the
        name is exposition-safe by construction)."""
        gauge = self._engine_stats.get(key)
        if gauge is None:
            gauge = self.registry.gauge(
                f"shellac_engine_{key}", f"Engine stats counter {key!r}"
            )
            self._engine_stats[key] = gauge
        return gauge


class RequestTrace:
    """Span recorder for ONE request. Event methods are idempotent (the
    first call wins) and `finish`/`shed`/`abort` settle the trace
    exactly once — late duplicate settlement from racing sweeps (close
    vs a final delivery) is ignored, mirroring the server's own
    pop-arbitrated settlement."""

    __slots__ = ("_m", "t_submit", "t_prefill", "t_first", "t_done",
                 "n_tokens", "outcome", "trace_id", "recorder", "tenant")

    def __init__(self, metrics: ServeMetrics,
                 trace_id: Optional[str] = None, recorder=None,
                 tenant: Optional[str] = None):
        self._m = metrics
        # Distributed-trace identity (obs.events.new_trace_id shape) and
        # the flight recorder the span's events feed. Both optional:
        # a bare trace()/RequestTrace() records spans only, exactly the
        # pre-tracing behavior.
        self.trace_id = trace_id
        self.recorder = recorder
        # Tenant id the request carried (None when untenanted): labels
        # the settlement counters and surfaces in /debug/requests.
        self.tenant = tenant
        self.t_submit = time.monotonic()
        self.t_prefill: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self.n_tokens = 0
        self.outcome: Optional[str] = None

    def record(self, event: str, **fields) -> None:
        """Deposit one flight-recorder event under this span's trace
        id. A no-op without a recorder, so engine/server call sites
        need no branching."""
        if self.recorder is not None:
            self.recorder.record(self.trace_id, event, **fields)

    # ---- pipeline events (called by the engine-owning thread) --------

    def prefill_start(self) -> None:
        """Queue wait ends: the scheduler popped this request into a
        slot and is about to prefill it."""
        if self.t_prefill is not None:
            return
        self.t_prefill = time.monotonic()
        wait = self.t_prefill - self.t_submit
        self._m.queue_wait.observe(wait, exemplar=self.trace_id)
        self.record("prefill", src="engine", queue_wait_s=round(wait, 6))

    def first_token(self) -> None:
        """The first generated token exists host-side (prefill sampled
        it): the TTFT point."""
        if self.t_first is not None:
            return
        self.t_first = time.monotonic()
        ttft = self.t_first - self.t_submit
        self._m.ttft.observe(ttft, exemplar=self.trace_id)
        self.record("first-token", src="engine", ttft_s=round(ttft, 6))

    # ---- settlement --------------------------------------------------

    def _settle(self, outcome: str) -> bool:
        if self.outcome is not None:
            return False
        self.outcome = outcome
        self.t_done = time.monotonic()
        self._m.requests.labels(outcome=outcome,
                                tenant=self.tenant or "").inc()
        return True

    def finish(self, n_tokens: int) -> None:
        """Completed normally with `n_tokens` generated tokens."""
        if not self._settle("ok"):
            return
        self.n_tokens = int(n_tokens)
        e2e = self.t_done - self.t_submit
        self._m.e2e.observe(e2e, exemplar=self.trace_id)
        if self.t_first is not None and self.n_tokens > 1:
            self._m.tpot.observe(
                (self.t_done - self.t_first) / (self.n_tokens - 1),
                exemplar=self.trace_id,
            )
        self.record("finish", src="server", n_tokens=self.n_tokens,
                    e2e_s=round(e2e, 6))

    def shed(self) -> None:
        """Deadline expired before prefill; the scheduler dropped it."""
        if self._settle("shed"):
            self._m.sheds.inc()
            if self.tenant:
                self._m.tenant_sheds.labels(tenant=self.tenant).inc()
            self.record("shed", src="server")

    def abort(self, outcome: str = "cancelled") -> None:
        """Any non-ok, non-shed settlement: cancelled | error | fault."""
        if self._settle(outcome):
            self.record(outcome, src="server")


class TierMetrics:
    """The router-tier instruments over one registry.

    Per-replica series are labeled by the replica's base URL so a
    scrape shows exactly where traffic went, what was retried away
    from whom, and who is ejected — the counters the tier chaos tests
    assert against. Written only from router threads (health poller +
    request handlers); replicas keep their own ServeMetrics."""

    def __init__(self, registry: Registry):
        self.registry = registry
        h, c, g = registry.histogram, registry.counter, registry.gauge
        self.routed = c(
            "shellac_tier_routed_total",
            "Request attempts forwarded, by replica and routing reason "
            "(affinity|least_loaded|directory|retry|disagg_prefill|"
            "disagg_decode)",
            labels=("replica", "reason"),
        )
        self.outcomes = c(
            "shellac_tier_requests_total",
            "Tier-level request settlements, by outcome "
            "(ok|failed|rejected|deadline)",
            labels=("outcome",),
        )
        self.retries = c(
            "shellac_tier_retries_total",
            "Retryable attempt failures, by replica the attempt hit "
            "and the failure class (connect|timeout|status_503|"
            "status_429|status_500|stream_pre_byte)",
            labels=("replica", "kind"),
        )
        self.ejections = c(
            "shellac_tier_ejections_total",
            "Circuit-breaker ejections, by replica",
            labels=("replica",),
        )
        self.readmissions = c(
            "shellac_tier_readmissions_total",
            "Half-open probes that readmitted a replica",
            labels=("replica",),
        )
        self.drains = c(
            "shellac_tier_drains_observed_total",
            "Health polls that found a replica newly draining",
            labels=("replica",),
        )
        self.respawns = c(
            "shellac_tier_respawns_total",
            "Dead replicas replaced through the replica factory",
        )
        self.stream_severed = c(
            "shellac_tier_stream_severed_total",
            "Streams lost mid-relay AFTER bytes reached the client "
            "(non-retryable by contract; reported in-band), by replica",
            labels=("replica",),
        )
        self.healthy = g(
            "shellac_tier_replicas_healthy",
            "Replicas currently routable (healthy, not ejected or "
            "draining)",
        )
        self.replica_state = g(
            "shellac_tier_replica_state",
            "Per-replica routability: 1 routable, 0 not (ejected, "
            "draining, or dead)",
            labels=("replica",),
        )
        self.attempt_latency = h(
            "shellac_tier_attempt_seconds",
            "Wall time of one forwarded attempt (connect to full "
            "response, successful or not)",
            buckets=LATENCY_BUCKETS,
        )
        self.e2e = h(
            "shellac_tier_e2e_seconds",
            "End-to-end tier latency (admission to final byte, "
            "retries included)",
            buckets=LATENCY_BUCKETS,
        )
        self.backoff = h(
            "shellac_tier_backoff_seconds",
            "Backoff slept between retry attempts (after jitter and "
            "deadline capping)",
            buckets=LATENCY_BUCKETS,
        )
        # Same family the replicas register (idempotent): tier-side
        # outcomes (ok / fallback_*) and replica-side leg outcomes
        # (export / import / *_failed) share one catalog entry.
        self.migrations = c(
            "shellac_migrations_total",
            "KV-migration legs by outcome. Replica-side: export / "
            "export_failed (serialize+push from a prefill replica), "
            "import / import_failed (adoption on a decode replica). "
            "Tier-side: ok (full disaggregated path served), "
            "fallback_* (served monolithically: no_pair | cost | "
            "feature | failed)",
            labels=("outcome",),
        )
        self.fabric_directory_chains = g(
            "shellac_fabric_directory_chains",
            "Distinct prefix-cache blocks the tier's directory "
            "currently knows across all routable replicas",
        )
        self.fabric_directory_hits = c(
            "shellac_fabric_directory_hits_total",
            "Routing decisions won by directory-measured chain "
            "overlap (the replica was chosen because the directory "
            "says it already holds the prompt's prefix KV)",
        )
        self.fabric_pushes = c(
            "shellac_fabric_pushes_total",
            "Hot-prefix replication pushes planned by the tier, by "
            "outcome (ok|failed|skipped_cost)",
            labels=("outcome",),
        )
        # Tier-side tenant admission shares the replica family name
        # (registration is idempotent) so one catalog entry covers
        # both enforcement points.
        self.tenant_throttles = c(
            "shellac_tenant_throttles_total",
            "Per-tenant quota rejections (HTTP 429 + Retry-After), by "
            "tenant and exhausted budget (rate|concurrency)",
            labels=("tenant", "reason"),
        )
        self.autoscale_actions = c(
            "shellac_autoscale_actions_total",
            "Autoscaler decisions actually executed, by action "
            "(scale_out: replica spawned via the factory; scale_down: "
            "/drain posted to the least-loaded replica)",
            labels=("action",),
        )
        self.autoscale_replicas = g(
            "shellac_autoscale_replicas",
            "Replica count the autoscaler last observed (its min/max "
            "envelope input; present only when autoscaling is on)",
        )


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: Registries whose compile counters the one process-wide
#: jax.monitoring listener feeds (a listener cannot be taken back, so
#: it is registered once and holds the registries weakly).
_compile_sinks: "weakref.WeakSet" = weakref.WeakSet()
_compile_listening = False


def _on_compile(event: str, duration: float, **_: Any) -> None:
    if event != _COMPILE_EVENT:
        return
    for reg in list(_compile_sinks):
        reg.get("shellac_compile_events_total").inc()
        reg.get("shellac_compile_seconds_total").inc(duration)


def _listen_for_compiles(registry: Registry) -> None:
    global _compile_listening
    _compile_sinks.add(registry)
    if not _compile_listening:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _compile_listening = True


class _NullSpan:
    """What `StepTrace.span` hands out while the registry is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def get(self, key):
        return None


_NULL_SPAN = _NullSpan()


class _Span:
    """One open span: a row `[name, start_ns, end_ns, parent, attrs]`
    in the recorder's span list (parent is an index into that list, -1
    for none) and the profiler annotation entered with it."""

    __slots__ = ("_tr", "_row", "_ann")

    def __init__(self, tr: "StepTrace", name: str, attrs: Dict[str, Any]):
        self._tr = tr
        self._row = [name, 0, 0, -1, attrs]
        # Profiler metadata takes numbers and short strings; anything
        # else (a tuple rid) is recorded in the ring only.
        self._ann = tr._annotation(name, **{
            k: v for k, v in attrs.items()
            if isinstance(v, (int, float, str))
        })

    def __enter__(self):
        tr, row = self._tr, self._row
        if tr._stack:
            row[3] = tr._stack[-1]
        tr._stack.append(len(tr._spans))
        tr._spans.append(row)
        self._ann.__enter__()
        row[1] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._row[2] = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._tr._stack.pop()
        return False

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (tokens applied,
        pages reserved); they reach the ring, not the profiler."""
        self._row[4].update(attrs)

    def get(self, key):
        return self._row[4].get(key)


class StepRecord:
    """One engine step that did work: its spans (rows `(name, start_ns,
    end_ns, parent, attrs)` on `time.perf_counter_ns()`, parents before
    children) and its work counts (`STEP_COUNTS`). `root` indexes the
    `engine.step` span; spans before it, and any with parent -1, were
    recorded outside the step's tree (`engine.submit`, a cancel's
    `cache.release_slot`). `launches` are the programs the step
    dispatched, in dispatch order: rows `[seq, kind, program,
    dispatched_ns, busy_from_ns, done_ns, late, attrs]` on the same
    clock (see StepTrace.launch). A row is stamped in place when its
    program lands, which is usually a step later: until then
    `done_ns` is 0."""

    __slots__ = ("step", "root", "spans", "counts", "launches")

    def __init__(self, step: int, root: int, spans: List[list],
                 counts: Dict[str, float], launches: List[list]):
        self.step = step
        self.root = root
        self.spans = spans
        self.counts = counts
        self.launches = launches

    @property
    def start_ns(self) -> int:
        return self.spans[self.root][1]

    @property
    def end_ns(self) -> int:
        return self.spans[self.root][2]

    def phases(self) -> Dict[str, float]:
        """Seconds per STEP_PHASES entry: each span under `engine.step`
        gives its self time to its phase (SPAN_PHASE; a `cache.*` span
        to its parent's). The values sum to the step's wall time."""
        spans = self.spans
        self_ns = [sp[2] - sp[1] for sp in spans]
        for sp in spans:
            if sp[3] >= 0:
                self_ns[sp[3]] -= sp[2] - sp[1]
        phase_of: Dict[int, str] = {}
        out = dict.fromkeys(STEP_PHASES, 0.0)
        for i in range(self.root, len(spans)):
            name, parent = spans[i][0], spans[i][3]
            if i != self.root and parent not in phase_of:
                continue  # outside the step's tree
            ph = SPAN_PHASE.get(name) or phase_of[parent]
            phase_of[i] = ph
            out[ph] += self_ns[i] * 1e-9
        return out

    def blocked_s(self) -> float:
        """Seconds the step spent blocked on a decode window's pull."""
        return sum(sp[2] - sp[1] for sp in self.spans[self.root:]
                   if sp[0] == "engine.wait_window") * 1e-9


class StepTrace:
    """Span and count recorder for one engine's steps.

    `span(name, **attrs)` is a context manager; spans nest by the order
    they are entered (one engine, one thread). `begin_step` opens the
    `engine.step` span; `end_step(did_work)` closes it and either
    commits a StepRecord to the registry's ring, observing the step's
    phases and counts into the histograms and counters, or, for a step
    that did nothing, drops its spans. Spans closed outside a step
    wait for the next record, as do the launch rows of a dropped step.
    `launch` numbers a program just dispatched and queues one of its
    outputs; `land` stamps the queued programs a pull is about to wait
    for. With the registry disabled every method returns after one
    attribute check, nothing is recorded and nothing is queued."""

    def __init__(self, metrics: "EngineMetrics"):
        from jax.profiler import TraceAnnotation
        from jax.tree_util import tree_leaves

        self._m = metrics
        self._reg = metrics.registry
        self._annotation = TraceAnnotation
        self._tree_leaves = tree_leaves
        self._spans: List[list] = []
        self._stack: List[int] = []
        self._counts = dict.fromkeys(STEP_COUNTS, 0)
        self._root: Optional[tuple] = None  # (open engine.step, index)
        self._n_steps = 0
        self._compiles_seen = (metrics.compiles.value,
                               metrics.compile_seconds.value)
        # Launches: the rows of the step being recorded, the (row,
        # handle) pairs not yet landed in dispatch order, and what the
        # last landing left for the next (its done_ns, whether it was
        # late; None: no predecessor this recorder knows of).
        self._launches: List[list] = []
        self._inflight: collections.deque = collections.deque()
        self._n_launches = 0
        self._landed: Optional[tuple] = None

    # ---- spans -------------------------------------------------------

    def span(self, name: str, **attrs):
        if not self._reg.enabled:
            return _NULL_SPAN
        return _Span(self, name, attrs)

    def annotate(self, **attrs) -> None:
        """Set attributes on the innermost open span."""
        if self._reg.enabled and self._stack:
            self._spans[self._stack[-1]][4].update(attrs)

    def count(self, **amounts) -> None:
        if not self._reg.enabled:
            return
        for k, v in amounts.items():
            self._counts[k] += v

    # ---- launches ----------------------------------------------------

    @property
    def next_launch(self) -> int:
        """The number the next launch will get. A dispatch span is
        opened with `launch=` this: an annotation's attributes are
        fixed when it is entered, and so the number is on the
        profiler's host plane as well as in the ring."""
        return self._n_launches + 1

    def launch(self, kind: str, program: str, handle, **attrs) -> None:
        """A jitted engine program was just dispatched (called inside
        its dispatch span, right after the call returned). `kind` is
        one of LAUNCH_KINDS, `program` the name a device trace shows
        for it, `handle` ONE small output of it that the engine keeps
        anyway (the first token, the window's validity flags; never the
        cache) and `attrs` what the host knows of the work (`bucket`,
        `tokens`, `offset`, `slot`, `stalled_rows`; `ticks`, `rows`).

        The launch gets the engine's next sequence number (the one its
        dispatch span was opened with: `next_launch`) and a row `[seq,
        kind, program, dispatched_ns, busy_from_ns, done_ns, late,
        attrs]` in the step's record; `busy_from_ns`, `done_ns` and
        `late` are stamped by `land`."""
        if not self._reg.enabled:
            return
        self._n_launches += 1
        row = [self._n_launches, kind, program, time.perf_counter_ns(),
               0, 0, False, attrs]
        self._launches.append(row)
        self._inflight.append((row, handle))
        self._m.launches.labels(kind=kind).inc()

    def land(self, handle, pull) -> None:
        """The engine is about to pull `pull` (the arrays of its one
        device_get), the results of the launch whose handle this is
        (called inside `engine.wait_prefill` / `engine.wait_window`).
        Their copies to the host are started first, as device_get
        itself starts them before it waits: waiting here first would
        put a transfer's round trip behind the program's end. Then
        every queued launch up to and including that one is landed,
        oldest first: `late` = its handle was ready when the host first
        looked (the program finished at some earlier moment: `done_ns`
        is then an upper bound), wait for the handle, `done_ns` = now,
        `busy_from_ns` = the later of its predecessor's `done_ns` and
        its own `dispatched_ns`. One chip runs the engine's programs in
        dispatch order, so all of these precede the pulled program on
        the device: nothing is waited for that the pull would not have
        waited for. A launch is SOUND when neither it nor its
        predecessor was late; only then is `done_ns - busy_from_ns` its
        device time. A handle not in the queue (recorded under another
        registry, or landed by an earlier pull) lands nothing."""
        q = self._inflight
        if not q or not any(h is handle for _, h in q):
            return
        for leaf in self._tree_leaves(pull):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        m = self._m
        while True:
            row, h = q.popleft()
            late = bool(h.is_ready())
            h.block_until_ready()
            done = time.perf_counter_ns()
            busy_from, sound = row[3], False
            if self._landed is not None:
                prev_done, prev_late = self._landed
                sound = not (late or prev_late)
                if prev_done >= busy_from:
                    busy_from = prev_done
                else:
                    # The device had nothing queued from the moment the
                    # predecessor finished until this dispatch.
                    m.device_drained.inc((busy_from - prev_done) * 1e-9)
            row[4], row[5], row[6] = busy_from, done, late
            self._landed = (done, late)
            if late:
                m.launches_late.inc()
            if sound:
                m.launch_device.labels(kind=row[1]).observe(
                    (done - busy_from) * 1e-9)
            if h is handle:
                return

    def drop_launches(self) -> None:
        """Forget the queued launches (abort_all: their results are
        discarded unseen). Their rows keep `done_ns` 0, and the next
        launch to land has no known predecessor."""
        self._inflight.clear()
        self._landed = None

    # ---- step boundaries ---------------------------------------------

    def begin_step(self, **attrs) -> None:
        self._n_steps += 1
        if not self._reg.enabled:
            return
        root = _Span(self, "engine.step", dict(attrs, step=self._n_steps))
        self._root = (root, len(self._spans))
        root.__enter__()

    def end_step(self, did_work: bool) -> None:
        if self._root is None:
            return
        (root, i), self._root = self._root, None
        root.__exit__(None, None, None)
        if not did_work or not self._reg.enabled:
            del self._spans[i:]
            return
        m = self._m
        seen = (m.compiles.value, m.compile_seconds.value)
        counts, self._counts = self._counts, dict.fromkeys(STEP_COUNTS, 0)
        counts["compiles"] = int(seen[0] - self._compiles_seen[0])
        counts["compile_s"] = seen[1] - self._compiles_seen[1]
        self._compiles_seen = seen
        spans, self._spans = self._spans, []
        launches, self._launches = self._launches, []
        rec = StepRecord(self._n_steps, i, spans, counts, launches)
        self._reg.step_records.append(rec)
        for phase, v in rec.phases().items():
            m.step_phase.labels(phase=phase).observe(v)
        names = {sp[0] for sp in spans[i:]}
        if "engine.wait_window" in names:
            # A step that synced a window: what the device cannot see
            # and overlap exists to hide.
            wall = (rec.end_ns - rec.start_ns) * 1e-9
            m.host_overhead.observe(max(0.0, wall - rec.blocked_s()))
        for k, c in m.step_counters.items():
            if counts[k]:
                c.inc(counts[k])


class EngineMetrics:
    """The engine-layer instruments: batch occupancy, prefill vs decode
    section durations, and cache-utilization gauges. All writes happen
    from the engine-owning thread, once per engine STEP (host code,
    after the step's own host sync) — never per token and never inside
    a jitted program."""

    def __init__(self, registry: Registry):
        self.registry = registry
        h, g = registry.histogram, registry.gauge
        self.launch_device = h(
            "shellac_launch_device_seconds",
            "Device time of one engine program (kind: prefill | chunk "
            "| window), from the engine's own timeline: the program "
            "was found finished at a pull the step makes anyway, less "
            "the moment its predecessor was (or its own dispatch, if "
            "later). Sound launches only: neither it nor its "
            "predecessor had already finished when the host looked",
            labels=("kind",),
            buckets=LATENCY_BUCKETS,
        )
        for kind in LAUNCH_KINDS:
            # Every kind's series exists from the start: an engine that
            # has timed no chunk yet exposes a count of 0, not a gap.
            self.launch_device.labels(kind=kind)
        self.host_overhead = h(
            "shellac_decode_host_overhead_seconds",
            "Per engine step that synced a decode window: step wall "
            "time minus time blocked awaiting window results — the "
            "host-side share of the tick (scheduling, settlement, "
            "prefill dispatch). A replica whose overhead rivals its "
            "window time is host-bound, not device-bound",
            buckets=LATENCY_BUCKETS,
        )
        self.step_phase = h(
            "shellac_step_phase_seconds",
            "Per engine step: wall time attributed to one phase of "
            "the tick (admission | prefill_dispatch | prefill_settle "
            "| decode_sync | settle | host_bookkeeping — see "
            "obs.STEP_PHASES). "
            "Observed once per phase per non-idle step, so the "
            "per-phase _sum series divide the step loop's wall time "
            "exactly and 'prefill stalls decode windows' is a "
            "measurement, not a claim",
            labels=("phase",),
            buckets=TPOT_BUCKETS,
        )
        self.occupancy = h(
            "shellac_batch_occupancy",
            "Active slots / n_slots at each decode window",
            buckets=OCCUPANCY_BUCKETS,
        )
        self.slots_busy = g(
            "shellac_slots_busy", "Slots currently holding a request"
        )
        self.queue_depth = g(
            "shellac_engine_queue_depth",
            "Requests admitted but not yet in a slot",
        )
        self.kv_util = g(
            "shellac_kv_utilization",
            "Live KV tokens / capacity (dense) or pool blocks in use / "
            "pool size (paged)",
        )
        self.prefix_blocks = g(
            "shellac_prefix_cache_blocks",
            "Blocks currently registered in the prefix cache (paged "
            "engines with prefix_cache=True)",
        )
        # The step records' work counts as running totals (StepTrace
        # adds each committed record's counts), keyed as STEP_COUNTS.
        c = registry.counter
        self.step_counters = {
            "tokens_delivered": c(
                "shellac_engine_tokens_delivered_total",
                "Output tokens appended to requests' outputs, credited "
                "in the engine step that handed them out (first tokens "
                "at prefill settle, decode tokens at window settle)",
            ),
            "decode_slot_ticks": c(
                "shellac_engine_decode_slot_ticks_total",
                "Decode rows computed: ticks x n_slots of every decode "
                "window synced",
            ),
            "decode_valid_ticks": c(
                "shellac_engine_decode_valid_ticks_total",
                "Decode rows that produced a token: the per-tick "
                "validity flags of every window synced, summed",
            ),
            "prefill_tokens": c(
                "shellac_engine_prefill_tokens_total",
                "Prompt tokens of the prefill and chunk programs "
                "dispatched",
            ),
            "prefill_padded_tokens": c(
                "shellac_engine_prefill_padded_tokens_total",
                "Bucketed (padded) length of the prefill and chunk "
                "programs dispatched",
            ),
            "prefill_sorted_tokens": c(
                "shellac_engine_prefill_sorted_tokens_total",
                "Padded prompt rows whose expert FFN ran as grouped "
                "GEMMs over the sorted routed rows (0 on a model "
                "without experts, and where the buckets run)",
            ),
            "eva_read_rows": c(
                "shellac_engine_eva_read_rows_total",
                "Ring and pooled rows the decode program's attention "
                "moved for the slot-ticks that produced a token (the "
                "'eva' backend; 0 elsewhere): on the kernel a slot's "
                "valid ring rows in whole blocks and its own pages, on "
                "the XLA form every ring row and the whole pool once a "
                "tick",
            ),
            "loop_passes": c(
                "shellac_engine_loop_passes_total",
                "Passes over the layer stack run by decode slot-ticks "
                "that produced a token (a looped stack, cfg.loop: its "
                "steps for each; 0 on any other model)",
            ),
            "loop_kv_rows": c(
                "shellac_engine_loop_kv_rows_total",
                "Cached rows those slot-ticks read over all their "
                "passes (steps x the query's context; 0 on a model "
                "without a looped stack)",
            ),
            "slot_uploads": c(
                "shellac_engine_slot_uploads_total",
                "Host arrays of slot state handed to the step's programs "
                "as arguments: the block table (a row changed), the "
                "settings matrix (a value changed), the carried "
                "vectors' patch (a slot was armed, frozen or cleared); "
                "0 on a step with no admission, settle, release or "
                "growth in it or just before it",
            ),
        }
        self.launches = c(
            "shellac_engine_launches_total",
            "Engine programs dispatched, by kind (prefill | chunk | "
            "window)",
            labels=("kind",),
        )
        self.launches_late = c(
            "shellac_engine_launches_late_total",
            "Launches whose program had already finished when the host "
            "first looked: the host is behind the device there, and "
            "that launch's time (and its successor's) is an upper "
            "bound, left out of shellac_launch_device_seconds",
        )
        self.device_drained = c(
            "shellac_device_drained_seconds_total",
            "Seconds the device had nothing of the engine's queued: "
            "from the moment a program finished to the dispatch of the "
            "next, where the dispatch came later",
        )
        self.compiles = c(
            "shellac_compile_events_total",
            "Executables built by this process (XLA backend compiles, "
            "persistent-cache loads included): a serve that keeps "
            "counting after warm-up is recompiling under traffic",
        )
        self.compile_seconds = c(
            "shellac_compile_seconds_total",
            "Seconds spent building those executables",
        )
        _listen_for_compiles(registry)
        self.steps = StepTrace(self)
