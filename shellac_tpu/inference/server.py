"""Minimal HTTP serving on top of the continuous-batching engine.

Stdlib-only (`http.server`): one scheduler thread owns the
BatchingEngine and is the ONLY thing touching JAX; request handler
threads just enqueue work and wait on per-request events. POSTs block
until their request completes — the concurrency lives in the slot
batch, not in the HTTP layer.

A SUPERVISOR wraps the scheduler in engine *generations*: when the
step watchdog trips (wedged collective) or the scheduler thread dies,
every in-flight request fails loudly, the wedged thread is abandoned,
and — within the restart budget — a fresh engine is rebuilt from the
retained params/config under a new generation and serving resumes.
Results a stale generation ever produces are discarded. Admission is
bounded (`max_pending`): over-limit submissions are rejected
immediately (HTTP 429 + Retry-After; `ServerUnavailable` for
programmatic callers) instead of queueing without limit, and a
request's client timeout rides its submit tuple as a deadline — the
scheduler sheds requests whose deadline already expired before
spending prefill compute on them.

API:
  POST /generate  {"tokens": [1,2,3] | "text": "...", "max_new": 32,
                   "stop": [[7,8], "..."]?,
                   "temperature"/"top_k"/"top_p"/"min_p": per-request
                   sampling overrides (engine defaults otherwise),
                   "min_tokens": ban EOS until N tokens are emitted,
                   "logit_bias": {token id: additive bias},
                   "logprobs": true? (needs an engine built with
                   logprobs=True / serve --logprobs),
                   "n"/"best_of": parallel sampling — best_of
                   completions are generated concurrently (sharing the
                   slot batch) and the n best by mean logprob return as
                   {"choices": [{"tokens", "text"?, "logprobs"?}, ...]}
                   (best_of > n needs --logprobs; greedy rejects n>1)}
                  -> {"id", "tokens", "text"?, "logprobs"?}
                  With "stream": true the response is newline-delimited
                  JSON written as tokens are generated: zero or more
                  {"tokens": [...]} delta lines, then one
                  {"done": true, "tokens": all, "text"?} line. With stop
                  sequences, the longest stop length is held back from
                  deltas so a token that a later match would truncate is
                  never streamed.
  GET  /health    -> readiness: 200 {"status": "ok", ...} only while
                  serving; 503 with "recovering" (supervisor mid-
                  rebuild), "draining" (graceful drain in progress),
                  or "failed" (fatal, message included). Always
                  carries pending/queue depth, restart count, shed
                  count, and the engine generation.
  POST /drain     -> admin: flip readiness, refuse new admissions
                  (503 + Retry-After), complete in-flight requests.
                  {"resume": true} cancels the drain. Poll /health
                  until "pending" is 0, then stop the replica.
  GET  /stats     -> engine counters (requests/tokens/steps/prefills,
                     slots busy, decode_ticks) plus supervisor state
                     ("fatal", "status", "restarts", "generation",
                     "shed"), uptime_s, and p50/p90/p99 TTFT /
                     queue-wait / e2e latency digests — stays 200 even
                     when fatal, so scrapers keep collecting through an
                     outage.
  GET  /metrics   -> Prometheus text exposition (shellac_ttft_seconds,
                     shellac_tpot_seconds, shellac_queue_wait_seconds,
                     engine occupancy/utilization, supervisor
                     restart/shed/admission counters — the catalog is
                     docs/observability.md). 404 with --no-metrics;
                     otherwise stays 200 through an outage.
"""

from __future__ import annotations

import concurrent.futures
import functools
import itertools
import json
import os
import queue
import random
import threading
import time
import urllib.parse
import urllib.request
from collections import OrderedDict
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference import disagg, fabric
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.cache import PoolExhausted
from shellac_tpu.inference.qos import (
    ANONYMOUS,
    CLASS_NAMES,
    TENANT_HEADER,
    AdmissionController,
    TenantPolicy,
)
from shellac_tpu.obs import (
    REQUEST_ID_HEADER,
    TRACE_HEADER,
    EventSpool,
    FlightRecorder,
    IncidentManager,
    Registry,
    ServeMetrics,
    adopt_trace,
    format_trace_header,
    get_registry,
    new_trace_id,
    spool_path,
)
from shellac_tpu.utils.failure import Heartbeat, RestartBudget

#: Replica roles for disaggregated serving. The role is ADVISORY for
#: the tier's pair scheduler — any role still serves the full API, so
#: monolithic fallback always has somewhere to land — but it is
#: surfaced everywhere (/health, /stats, /metrics, `top`) so routing
#: decisions are inspectable.
ROLES = ("monolith", "prefill", "decode")

#: Sentinel distinguishing "prefill_chunk never tuned" from "tuned to
#: None (whole prompts won the sweep)".
_UNTUNED = object()


def _render_plp(plp):
    """Prompt logprobs for a response: position 0 has no predictor and
    renders as null (the OpenAI convention); one definition so the
    n==1, best_of, and streaming shapes cannot drift."""
    return [None] + plp[1:]


def retry_after(lo: float, hi: float) -> float:
    """A Retry-After value drawn uniformly from [lo, hi]. Every 503/429
    this server emits goes through here: a fixed interval would tell
    every rejected client to come back at the SAME instant, and a
    recovering or draining replica would eat a synchronized thundering
    herd exactly when it is least able to absorb one. The bounds span
    multiple whole seconds because the HTTP header is rendered as
    integer delta-seconds — sub-second jitter would round away."""
    return random.uniform(lo, hi)


class ProfileInProgress(RuntimeError):
    """POST /debug/profile while a capture is already running: the
    profiler is process-global state, so captures are strictly one at
    a time (HTTP 409, not a queue — the second caller retries after
    the first capture's window elapses)."""


class ServerUnavailable(RuntimeError):
    """The server pushed back instead of serving: over the pending cap
    (HTTP 429), mid-recovery, or a request shed on an expired deadline
    (both HTTP 503). A RuntimeError subclass so programmatic callers
    that only know the old fatal contract still fail loudly; the HTTP
    layer maps it to the right status plus a Retry-After header
    instead of a generic 500."""

    def __init__(self, msg: str, *, http_status: int = 503,
                 retry_after: float = 1.0):
        super().__init__(msg)
        self.http_status = http_status
        self.retry_after = retry_after


class _Generation:
    """One scheduler-thread + engine incarnation.

    The supervisor replaces the WHOLE object on recovery: a wedged
    scheduler thread keeps references to its own engine, submit queue,
    and stop event, so it can never consume a successor's work — and
    `dead` / the identity check against the server's current generation
    make any results it produces after un-wedging discardable."""

    __slots__ = ("gen", "engine", "submit_q", "stop", "step_started",
                 "thread", "dead")

    def __init__(self, gen: int, engine):
        self.gen = gen
        self.engine = engine
        self.submit_q: queue.Queue = queue.Queue()
        self.stop = threading.Event()
        # Wall-clock (monotonic) start of the engine step in flight,
        # None between steps; the watchdog reads it cross-thread.
        self.step_started: Optional[float] = None
        self.thread: Optional[threading.Thread] = None
        # Set (under the server lock) the moment the supervisor starts
        # replacing this generation; admission and the watchdog treat a
        # dead generation as already gone.
        self.dead = False


class _ImportAck:
    """Cross-thread ack for one POST /kv/import: the handler thread
    blocks on `event` while the scheduler (the engine-owning thread)
    performs the import."""

    __slots__ = ("event", "slot", "error", "retryable")

    def __init__(self):
        self.event = threading.Event()
        self.slot: Optional[int] = None
        self.error: Optional[str] = None
        self.retryable = False

    def ok(self, slot: int) -> None:
        self.slot = slot
        self.event.set()

    def fail(self, msg: str, retryable: bool) -> None:
        self.error = msg
        self.retryable = retryable
        self.event.set()


class _Pending:
    __slots__ = ("event", "result", "error", "chunks", "emitted", "holdback",
                 "lps", "plp", "tlp", "rid", "deadline", "kind", "trace",
                 "tenant", "on_finish")

    def __init__(self, rid, stream: bool = False, holdback: int = 0,
                 deadline: Optional[float] = None, trace=None,
                 tenant: Optional[str] = None):
        self.rid = rid
        # Tenant id the request carried (None when untenanted):
        # surfaces in /debug/requests and labels the QoS counters.
        self.tenant = tenant
        # Settlement hook, invoked exactly once by finish() — the one
        # choke point every settle path (finish/shed/cancel/fault/
        # sweep) already goes through. Releases the tenant's
        # concurrency lease, so a request that dies on ANY path frees
        # its admission slot.
        self.on_finish: Optional[Callable[[], None]] = None
        # Observability span (obs.RequestTrace): created at admission,
        # handed to the engine for the prefill/first-token marks, and
        # settled wherever the request settles (finish/shed/abort).
        self.trace = trace
        # Absolute monotonic deadline mirroring the client's timeout;
        # the scheduler sheds the request if this expires before its
        # prefill ever runs (None = no deadline).
        self.deadline = deadline
        # How the error in `error` should surface: "error" (bad
        # request, ValueError/400), "fault" (server fault,
        # RuntimeError/500), "shed" (expired deadline under
        # saturation, ServerUnavailable/503 — retryable, unlike 400).
        self.kind = "error"
        self.event = threading.Event()
        self.result = None
        self.error: Optional[str] = None
        # Streaming requests also get a chunk queue: lists of newly
        # generated token ids, then a None sentinel at completion.
        self.chunks: Optional[queue.Queue] = queue.Queue() if stream else None
        self.emitted = 0
        # Tokens withheld from deltas: a stop-sequence match truncates
        # up to max(len(stop)) tokens at the end, so anything closer to
        # the tail than that may still disappear.
        self.holdback = holdback
        # Per-token logprobs of the final result (engines built with
        # logprobs=True deposit them at completion).
        self.lps = None
        self.plp = None  # prompt per-token logprobs (prompt_logprobs)
        self.tlp = None  # per-token top-K alternatives ((ids, lps) pairs)

    def finish(self):
        cb, self.on_finish = self.on_finish, None
        if cb is not None:
            cb()
        if self.chunks is not None:
            self.chunks.put(None)
        self.event.set()


class InferenceServer:
    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        tokenizer=None,
        engine: Optional[BatchingEngine] = None,
        model_name: str = "shellac_tpu",
        step_timeout: Optional[float] = None,
        max_pending: Optional[int] = None,
        restart_budget: int = 0,
        restart_window: float = 300.0,
        engine_factory: Optional[Callable[[], Any]] = None,
        heartbeat_path: Optional[str] = None,
        registry: Optional[Registry] = None,
        metrics: bool = True,
        autotune: bool = False,
        debug: bool = True,
        debug_include_text: bool = False,
        profile_dir: Optional[str] = None,
        recorder: Optional[FlightRecorder] = None,
        role: str = "monolith",
        adopt_ttl: float = 120.0,
        spool_dir: Optional[str] = None,
        spool_max_bytes: int = 8 << 20,
        incident_dir: Optional[str] = None,
        incident_rate: int = 6,
        incident_window: float = 600.0,
        incident_retention: int = 24,
        incident_capture_seconds: float = 0.0,
        park_dir: Optional[str] = None,
        park_max_bytes: int = 256 << 20,
        tenant_config: Optional[Any] = None,
        preempt_after: Optional[float] = None,
        **engine_kw,
    ):
        if role not in ROLES:
            raise ValueError(f"role={role!r}; have {ROLES}")
        #: Disaggregated-serving role (serve --role). Advisory: the
        #: tier pairs prefill/decode replicas by it; the full API
        #: stays served whatever the role, so monolithic fallback and
        #: mixed fleets always work.
        self.role = role
        # Observability: every span/counter lands in `registry` — the
        # process-global default unless the caller isolates one.
        # metrics=False swaps in a disabled registry (all writes no-op,
        # /metrics answers 404) without any call-site branching.
        if registry is None:
            registry = get_registry() if metrics else Registry(enabled=False)
        self._registry = registry
        self._m = ServeMetrics(registry)
        # Introspection: the flight recorder feeds /debug/requests and
        # /debug/request/<trace_id>. debug=False (serve --no-debug)
        # 404s the endpoints AND disables recording; text redaction is
        # separate — events and the in-flight table carry prompt or
        # generated text only with debug_include_text (serve
        # --debug-include-text).
        self._debug = bool(debug)
        self._debug_text = bool(debug_include_text)
        # Durable event spool (serve --spool-dir): the recorder's ring
        # also spills to a rotating on-disk JSONL file, so a SIGKILL'd
        # replica's in-flight timelines survive to disk (recovered via
        # `top --trace <id> --spool <dir>` or read_spool). PR 10
        # redaction applies on the way to disk unless
        # --debug-include-text opted in.
        self._spool = (
            EventSpool(spool_path(spool_dir),
                       max_bytes=spool_max_bytes,
                       include_text=self._debug_text)
            if spool_dir and self._debug else None
        )
        self._recorder = (recorder if recorder is not None
                          else FlightRecorder(registry=registry,
                                              enabled=self._debug,
                                              spool=self._spool))
        # On-demand profiling (POST /debug/profile?seconds=N): writes
        # jax.profiler traces under profile_dir; the non-blocking lock
        # guards the process-global profiler — one capture at a time.
        self._profile_dir = profile_dir
        self._profile_lock = threading.Lock()
        # Incident black box (serve --incident-dir): trigger-driven
        # evidence bundles — supervisor wedge→rebuild / scheduler
        # death / restart-budget exhaustion fire automatically, and
        # POST /debug/incident fires manually. Sections are evaluated
        # AT TRIGGER TIME; a page-style trigger may also arm a bounded
        # jax.profiler capture through the same one-at-a-time profile
        # lock the /debug/profile endpoint uses.
        self._incidents: Optional[IncidentManager] = None
        if incident_dir and self._debug:
            self._incidents = IncidentManager(
                incident_dir,
                source="server",
                registry=registry,
                recorder=self._recorder,
                sections={
                    "flight_recorder": lambda: self._recorder.tail(
                        self._recorder.capacity),
                    "metrics": self._registry.snapshot,
                    "requests": self.debug_requests,
                    "latency": self.latency_summary,
                    "step_phases": self._step_phase_digest,
                    "config": self._config_fingerprint,
                },
                rate=incident_rate,
                rate_window=incident_window,
                retention=incident_retention,
                capture_fn=(self.profile if profile_dir else None),
                capture_seconds=incident_capture_seconds,
                analyze_fn=self._analyze_capture,
            )
        self._t0 = time.monotonic()
        # Validate BEFORE starting the scheduler thread: raising after
        # start() would orphan an engine-owning daemon thread the
        # caller can never close().
        #
        # step_timeout arms the wedge watchdog. A follower process
        # dying mid-collective leaves the primary's step() WEDGED in
        # native code — no exception ever surfaces, so the scheduler-
        # death path alone cannot save pending requests. The watchdog
        # detects the stall from outside and hands the generation to
        # the supervisor. serve --step-timeout wires this; single-host
        # deployments usually leave it off (a long prefill compile
        # would trip a short timeout).
        if step_timeout is not None and step_timeout <= 0:
            raise ValueError("step_timeout must be > 0 seconds")
        if max_pending is not None and max_pending < 1:
            raise ValueError("max_pending must be >= 1")
        if restart_budget < 0:
            raise ValueError("restart_budget must be >= 0")
        if restart_budget > 0 and engine is not None and engine_factory is None:
            raise ValueError(
                "restart_budget > 0 with a prebuilt engine needs an "
                "engine_factory: the server cannot rebuild an engine "
                "it did not construct"
            )
        if engine is None:
            # Engines this server builds share its registry, so engine
            # gauges and request spans expose through one scrape (and a
            # supervisor-rebuilt engine keeps depositing there too).
            engine_kw.setdefault("registry", registry)
            engine = BatchingEngine(cfg, params, **engine_kw)
            if engine_factory is None:
                # Retained cfg/params/engine_kw rebuild an identical
                # engine on recovery; params are shared with the dead
                # engine, which is safe — jax arrays are immutable.
                engine_factory = functools.partial(
                    BatchingEngine, cfg, params, **engine_kw
                )
        # Switches that move a slot's state off this engine are refused
        # here, at construction, by a cache backend that cannot ship
        # its state (the 'eva' backend; base backends refuse nothing).
        backend = getattr(engine, "cache_backend", None)
        if backend is not None:
            if park_dir or preempt_after is not None:
                backend.check_feature("park_resume")
            if role != "monolith":
                backend.check_feature("kv_export")
        self.model_name = model_name
        self.tokenizer = tokenizer
        self._constraint_cache: "OrderedDict[str, Any]" = OrderedDict()
        self._pending: Dict[int, _Pending] = {}
        self._ids = itertools.count()
        # Serializes admission against the supervisor's generation swap
        # and pending sweep: a request either lands in _pending before
        # the sweep (and is failed loudly by it) or sees the post-swap
        # state checks. Never held across an engine step.
        self._lock = threading.Lock()
        self._closed = threading.Event()
        self._fatal: Optional[str] = None
        self._recovering = False
        # Graceful drain: admission refused (503 + Retry-After),
        # readiness flipped, in-flight requests run to completion. A
        # router polling /health bleeds traffic off, and once
        # `pending` reaches zero the replica can exit with zero drops.
        self._draining = False
        self.step_timeout = step_timeout
        self.max_pending = max_pending
        self._engine_factory = engine_factory
        self._budget = (
            RestartBudget(restart_budget, restart_window)
            if restart_budget > 0 and engine_factory is not None else None
        )
        self.restarts = 0   # generations rebuilt by the supervisor
        self.shed = 0       # requests shed on an expired deadline
        # One-way flag letting the per-step shed sweep early-out in
        # O(1) while NO request has ever carried a deadline (the
        # common all-default-timeout deployment). Deliberately never
        # reset — a stale True only costs the scan, a wrong False
        # would stop shedding.
        self._saw_deadline = False
        # KV migration (disaggregated serving). Prefill side: rid ->
        # decode-replica URL for in-flight prefill_only requests (the
        # scheduler exports the frozen slot and a push worker ships
        # it). Decode side: migration id -> (_Pending, import time) —
        # imported requests decode immediately and the adopt request
        # attaches to the pending; unadopted entries expire after
        # adopt_ttl so an abandoned migration cannot pin results
        # forever.
        self._migrate_targets: Dict[int, str] = {}
        self._adoptions: Dict[str, Tuple[_Pending, float]] = {}
        self._adopt_ttl = float(adopt_ttl)
        # KV park spool (serve --park-dir): frozen slots exported to a
        # durable directory so a parked session survives this replica
        # and resumes on any replica that mounts the same spool.
        self._park = (fabric.KVParkStore(park_dir, park_max_bytes)
                      if park_dir else None)
        self._push_pool: Optional[
            concurrent.futures.ThreadPoolExecutor] = None
        # Multi-tenant QoS (serve --tenant-config / --preempt-after).
        # The policy parses BEFORE the scheduler thread starts, so a
        # malformed config is a construction-time ValueError, not a
        # mystery 500 later. Without a config there is no admission
        # controller and no per-tenant gating — tenant ids still ride
        # traces/metrics, but behavior is bit-identical to before.
        self._tenant_policy = (TenantPolicy.parse(tenant_config)
                               if tenant_config is not None else None)
        self._qos_admission = (AdmissionController(self._tenant_policy)
                               if self._tenant_policy is not None
                               else None)
        if preempt_after is not None and preempt_after <= 0:
            raise ValueError("preempt_after must be > 0 seconds")
        self._preempt_after = preempt_after
        # Preempted victims awaiting resume: rid -> (MigrationBlob,
        # tenant, trace_id). Scheduler-thread-only. The blob stays
        # in-memory (resume is same-replica and latency-sensitive); a
        # safety copy goes to the park spool asynchronously when one
        # is configured, so a SIGKILL mid-park still leaves the fleet
        # a resumable artifact.
        self._preempted: "OrderedDict[int, Tuple[Any, Optional[str], str, int]]" = (
            OrderedDict()
        )
        # Parked preempted bytes by resolved tenant (scheduler-thread
        # bookkeeping behind the shellac_tenant_parked_bytes gauge).
        self._parked_tenant_bytes: Dict[str, int] = {}
        # Startup auto-tune (serve --decode-ticks auto, the CLI
        # default): sweep decode_ticks against the live engine BEFORE
        # the scheduler thread exists (the engine is single-owner
        # here), write the winner back, and remember it so supervisor-
        # rebuilt generations inherit the tuned value instead of
        # re-paying the sweep mid-recovery. Library-built servers keep
        # autotune=False: tests and embedders want deterministic, cheap
        # construction.
        self._tuned_ticks: Optional[int] = None
        # prefill_chunk startup sweep (serve --prefill-chunk auto):
        # same discipline — tuned pre-thread, remembered so rebuilt
        # generations inherit it. The sentinel distinguishes "never
        # tuned" from "tuned to None (whole prompts)".
        self._tuned_chunk: Any = _UNTUNED
        if autotune:
            from shellac_tpu.inference.autotune import (
                maybe_autotune,
                maybe_autotune_prefill_chunk,
            )

            res = maybe_autotune(engine)
            if res is not None:
                self._tuned_ticks = res.best
            cres = maybe_autotune_prefill_chunk(engine)
            if cres is not None:
                self._tuned_chunk = cres.best
        # Liveness file beaten from the scheduler loop, so external
        # watchdogs cover inference the same way they cover training.
        # The step watchdog co-beats it while in-process recovery is
        # still possible (and stops once fatal), so an external
        # watchdog doesn't kill the pod mid-wedge-detection or
        # mid-rebuild, defeating the supervisor. Two beaters need the
        # lock: interleaved writes to the shared tmp file would
        # publish a corrupt (= stale-looking) heartbeat.
        self._hb = Heartbeat(heartbeat_path) if heartbeat_path else None
        self._hb_last = 0.0
        self._hb_lock = threading.Lock()
        self._g = self._start_generation(0, engine)
        self._g.thread.start()
        if step_timeout is not None:
            threading.Thread(target=self._watchdog, daemon=True).start()

    # The engine and scheduler thread of the CURRENT generation.
    # Properties (not plain attributes) so every reader — /stats,
    # tests, the OpenAI facade — always sees the live engine, never a
    # wedged predecessor.
    @property
    def engine(self):
        return self._g.engine

    @property
    def _thread(self) -> threading.Thread:
        return self._g.thread

    @property
    def status(self) -> str:
        """Supervisor state: "ok" | "recovering" | "draining" |
        "failed". Failure states win over a drain: a drained-then-
        wedged replica must report the wedge, not a clean drain."""
        if self._fatal is not None:
            return "failed"
        if self._recovering or self._g.dead:
            return "recovering"
        if self._draining:
            return "draining"
        return "ok"

    def health(self) -> Dict[str, Any]:
        """Readiness snapshot served at /health. All reads are plain
        ints/strings — possibly stale, never torn."""
        g = self._g
        info: Dict[str, Any] = {
            "status": self.status,
            "ok": self.status == "ok",
            "role": self.role,
            "pending": len(self._pending),
            "queue_depth": g.submit_q.qsize(),
            "engine_pending": g.engine.pending,
            "generation": g.gen,
            "restarts": self.restarts,
            "restart_budget_used": (self._budget.used
                                    if self._budget is not None else None),
            "shed": self.shed,
            "max_pending": self.max_pending,
            "draining": self._draining,
        }
        if self._fatal is not None:
            info["error"] = self._fatal
        return info

    # ---- graceful drain ---------------------------------------------

    def drain(self) -> Dict[str, Any]:
        """Begin a graceful drain: flip readiness (/health answers 503
        "draining"), refuse new admissions with 503 + Retry-After, and
        let every in-flight request run to completion. Idempotent; the
        returned health snapshot carries `pending`, which a caller (or
        the tier router) polls to zero before stopping the replica —
        that ordering is what makes a planned redeploy drop nothing."""
        with self._lock:
            self._draining = True
            self._m.draining.set(1)
        return self.health()

    def resume_admission(self) -> Dict[str, Any]:
        """Cancel a drain (planned redeploy aborted): readmit traffic.
        A no-op on a fatal server — undraining cannot resurrect it."""
        with self._lock:
            self._draining = False
            self._m.draining.set(0)
        return self.health()

    @property
    def draining(self) -> bool:
        return self._draining

    # ---- observability ----------------------------------------------

    @property
    def metrics_enabled(self) -> bool:
        return self._registry.enabled

    @property
    def uptime_s(self) -> float:
        return time.monotonic() - self._t0

    def metrics_text(self) -> str:
        """Prometheus text exposition of the shared registry, refreshed
        with scrape-time gauges (engine stats counters, supervisor
        state, uptime). Event-driven series (spans, restart/shed/reject
        counters) are already up to date; only mirrors of host ints are
        set here, so an idle server pays nothing between scrapes. Keeps
        answering through an outage, like /stats."""
        m = self._m
        g = self._g
        for k, v in g.engine.stats.items():
            if isinstance(v, (int, float)):
                m.engine_stat(k).set(v)
        m.cache_backend_info.labels(
            backend=str(g.engine.stats.get("cache_backend", "dense"))
        ).set(1)
        m.role_info.labels(role=self.role).set(1)
        m.generation.set(g.gen)
        m.uptime.set(self.uptime_s)
        m.pending.set(len(self._pending))
        return self._registry.render()

    def qos_snapshot(self) -> Dict[str, Any]:
        """Multi-tenant QoS state for /stats and `top`: per-tenant
        admission counters, the weighted-fair queue's per-class
        depths, and parked preemption state. Cheap host reads only —
        possibly stale, never torn, never a device sync."""
        out: Dict[str, Any] = {}
        if self._qos_admission is not None:
            out["tenants"] = self._qos_admission.snapshot()
        q = getattr(self._g.engine, "_queue", None)
        if hasattr(q, "depths"):
            out["queue_depths"] = {
                CLASS_NAMES.get(k, str(k)): v
                for k, v in q.depths().items()
            }
        if self._preempt_after is not None:
            out["preempt_after_s"] = self._preempt_after
            out["parked_victims"] = len(self._preempted)
            out["parked_bytes"] = dict(self._parked_tenant_bytes)
        return out

    def latency_summary(self) -> Dict[str, Any]:
        """p50/p90/p99 digests (seconds) of the request-span histograms
        for /stats — derived from the same series /metrics exposes, so
        the two surfaces cannot disagree."""
        return {
            "ttft_s": self._m.ttft.summary(),
            "e2e_s": self._m.e2e.summary(),
            "queue_wait_s": self._m.queue_wait.summary(),
        }

    # ---- debug introspection (flight recorder + profiler) -----------

    @property
    def debug_enabled(self) -> bool:
        return self._debug

    @property
    def recorder(self) -> FlightRecorder:
        return self._recorder

    def debug_requests(self) -> Dict[str, Any]:
        """The GET /debug/requests snapshot: the in-flight table (slot
        assignments, per-request state), the overlap window depth, the
        cache backend's per-slot residency(), histogram exemplars, and
        the recorder's ring stats. All reads are cross-thread snapshots
        of host state — possibly stale, never torn, never a device
        sync. Prompt/generated text appears only under
        --debug-include-text (redaction by default)."""
        g = self._g
        eng = g.engine
        slots = list(getattr(eng, "_slots", ()) or ())
        prefilling = set(getattr(eng, "_prefilling", ()) or ())
        slot_of = {req.rid: i for i, req in enumerate(slots)
                   if req is not None}
        now = time.monotonic()
        rows = []
        for rid, p in list(self._pending.items()):
            t = p.trace
            slot = slot_of.get(rid)
            row: Dict[str, Any] = {
                "rid": rid,
                "trace_id": getattr(t, "trace_id", None),
                "slot": slot,
                "state": ("parked" if rid in self._preempted
                          else "queued" if slot is None
                          else "prefilling" if slot in prefilling
                          else "decoding"),
                "tenant": p.tenant,
                "stream": p.chunks is not None,
                "age_s": (round(now - t.t_submit, 3)
                          if t is not None else None),
                "deadline_in_s": (round(p.deadline - now, 3)
                                  if p.deadline is not None else None),
            }
            req = slots[slot] if slot is not None else None
            if req is not None and req.rid == rid:
                row["tokens_out"] = len(req.out)
                if self._debug_text:
                    row["prompt_text"] = (
                        self.tokenizer.decode(
                            [int(x) for x in req.tokens[:256]])
                        if self.tokenizer is not None
                        else [int(x) for x in req.tokens[:256]]
                    )
                    row["output_text"] = (
                        self.tokenizer.decode(list(req.out))
                        if self.tokenizer is not None else list(req.out)
                    )
            rows.append(row)
        out: Dict[str, Any] = {
            "in_flight": rows,
            "pending": len(self._pending),
            "overlap_window_depth": len(getattr(eng, "_windows", ())
                                        or ()),
            "generation": g.gen,
            "recorder": self._recorder.stats(),
            "exemplars": {
                "ttft": self._m.ttft.bucket_exemplars(),
                "e2e": self._m.e2e.bucket_exemplars(),
                "queue_wait": self._m.queue_wait.bucket_exemplars(),
                "tpot": self._m.tpot.bucket_exemplars(),
            },
        }
        try:
            out["slots"] = eng.cache_backend.residency()
        except Exception:  # noqa: BLE001 — introspection must not 500
            out["slots"] = None
        if self._spool is not None:
            out["spool"] = self._spool.stats()
        if self._incidents is not None:
            out["last_incident"] = self._incidents.last
        return out

    def debug_request(self, trace_id: str) -> Optional[Dict[str, Any]]:
        """The GET /debug/request/<trace_id> timeline, or None for an
        id the ring no longer (or never) holds. When the ring has
        evicted the id but a spool is configured, the on-disk copy
        answers instead — the same recovery path `top --spool` uses
        on a dead replica, available while the replica still lives."""
        events = self._recorder.events_for(trace_id)
        source = "ring"
        if not events and self._spool is not None:
            events = self._spool.events_for(trace_id)
            source = "spool"
        if not events:
            return None
        return {"trace_id": trace_id, "events": events,
                "source": source}

    def profile(self, seconds: float) -> Dict[str, Any]:
        """POST /debug/profile?seconds=N: capture a jax.profiler device
        trace of the LIVE engine for `seconds`, written under
        --profile-dir. The handler thread sleeps through the window
        (the scheduler keeps serving); the profiler is process-global,
        so captures are strictly one at a time (ProfileInProgress ->
        HTTP 409)."""
        if self._profile_dir is None:
            raise ValueError(
                "profiling needs serve --profile-dir (no capture "
                "directory configured)"
            )
        seconds = float(seconds)
        if not 0 < seconds <= 120:
            raise ValueError(
                f"seconds={seconds:g} out of range (0, 120]"
            )
        if not self._profile_lock.acquire(blocking=False):
            raise ProfileInProgress(
                "a profiler capture is already running; retry after "
                "its window elapses"
            )
        try:
            import jax

            path = os.path.join(
                self._profile_dir,
                f"trace-{int(time.time() * 1000)}",
            )
            jax.profiler.start_trace(path)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            n_files = sum(
                len(files) for _, _, files in os.walk(path)
            )
            self._recorder.record(None, "profile-capture", src="server",
                                  seconds=seconds, trace_dir=path,
                                  files=n_files)
            # capture_id is the path component trace-report resolves:
            # `python -m shellac_tpu trace-report <trace_dir>` works
            # verbatim on the returned value.
            return {"trace_dir": path,
                    "capture_id": os.path.basename(path),
                    "seconds": seconds, "files": n_files}
        finally:
            self._profile_lock.release()

    @staticmethod
    def _analyze_capture(trace_dir: str) -> Dict[str, Any]:
        """trace-report analysis of one capture directory (the
        ?report=1 inline payload and the bundle's trace_report.json)."""
        from shellac_tpu.obs import tracereport

        return tracereport.analyze(trace_dir)

    # ---- incident black box ------------------------------------------

    @property
    def incidents(self) -> Optional[IncidentManager]:
        return self._incidents

    @property
    def spool(self) -> Optional[EventSpool]:
        return self._spool

    def _step_phase_digest(self) -> Dict[str, Any]:
        """Per-phase step-time digest (sum/count/share) from the
        shellac_step_phase_seconds histograms — the bundle's answer to
        'where was the engine tick going when this fired'."""
        phases: Dict[str, Any] = {}
        total = 0.0
        from shellac_tpu.obs import STEP_PHASES

        for phase in STEP_PHASES:
            h = self._registry.get("shellac_step_phase_seconds",
                                   phase=phase)
            if h is None:
                continue
            phases[phase] = {"sum_s": round(h.sum, 6),
                             "count": h.count,
                             "p50_ms": (round(1e3 * (h.percentile(0.5)
                                                     or 0.0), 3))}
            total += h.sum
        for row in phases.values():
            row["share"] = (round(row["sum_s"] / total, 4)
                            if total > 0 else 0.0)
        return phases

    def _config_fingerprint(self) -> Dict[str, Any]:
        """Config + engine/mesh identity: enough to answer 'what
        exactly was running' from the bundle alone."""
        import dataclasses

        g = self._g
        eng = g.engine
        cfg = getattr(eng, "cfg", None)
        try:
            cfg_d = dataclasses.asdict(cfg) if cfg is not None else None
        except TypeError:
            cfg_d = str(cfg)
        mesh = getattr(eng, "mesh", None)
        return {
            "model": self.model_name,
            "role": self.role,
            "generation": g.gen,
            "restarts": self.restarts,
            "status": self.status,
            "uptime_s": round(self.uptime_s, 3),
            "config": cfg_d,
            "engine": {
                "class": type(eng).__name__,
                "n_slots": getattr(eng, "n_slots", None),
                "cache_backend": str(
                    eng.stats.get("cache_backend", "dense")
                    if hasattr(eng, "stats") else None),
                "decode_ticks": getattr(eng, "decode_ticks", None),
                "decode_ticks_source": getattr(
                    eng, "decode_ticks_source", None),
                "overlap_decode": bool(
                    getattr(eng, "overlap_decode", False)),
                "overlap_prefill": bool(
                    getattr(eng, "overlap_prefill", False)),
                "prefill_chunk": getattr(eng, "prefill_chunk", None),
                "prefill_chunk_source": getattr(
                    eng, "prefill_chunk_source", None),
            },
            "mesh": (str(dict(mesh.shape)) if mesh is not None
                     else None),
            "spool": (self._spool.stats()
                      if self._spool is not None else None),
        }

    def trigger_incident(self, trigger: str, *,
                         trace_id: Optional[str] = None,
                         detail: Optional[Dict[str, Any]] = None,
                         capture_seconds: Optional[float] = None,
                         ) -> Optional[str]:
        """Fire one incident trigger (no-op returning None when no
        --incident-dir is configured; None also means rate-limited)."""
        if self._incidents is None:
            return None
        return self._incidents.trigger(
            trigger, trace_id=trace_id, detail=detail,
            capture_seconds=capture_seconds,
        )

    # ---- supervisor --------------------------------------------------

    def _start_generation(self, gen: int, engine) -> _Generation:
        g = _Generation(gen, engine)
        g.thread = threading.Thread(
            target=self._loop, args=(g,), daemon=True,
            name=f"shellac-scheduler-gen{gen}",
        )
        return g

    def _fail_pending_locked(self, msg: str) -> None:
        """Fail every pending request loudly and drain the current
        generation's submit queue (caller holds the lock, so no new
        pending can land mid-sweep). Settlement is arbitrated by the
        atomic dict pop: a scheduler racing this sweep (close() with a
        step still finishing) pops each rid before delivering, so a
        pending this loop can still pop is guaranteed unsettled — a
        just-completed result is never clobbered with an error."""
        while self._pending:
            _, p = self._pending.popitem()
            p.error = msg
            p.kind = "fault"
            if p.trace is not None:
                p.trace.abort("fault")
            p.finish()
        # Every pending just failed; no prefill_only request can reach
        # the export path anymore, so their targets must not outlive
        # them (rids are never reused — a leak would be permanent).
        self._migrate_targets.clear()
        while True:
            try:
                self._g.submit_q.get_nowait()
            except queue.Empty:
                break

    def _recover(self, g: _Generation, msg: str,
                 wedged: bool = False) -> None:
        """Supervisor transition out of a dead/wedged generation:
        fail everything in flight loudly (the unchanged part of the
        contract), then either rebuild a fresh engine under a new
        generation and resume serving, or — restart budget exhausted,
        no factory, in-place factory on a wedge, or server closing —
        stay fatal. Called from the watchdog (wedge) or the dying
        scheduler thread itself (exception); idempotent per generation.

        Memory note: the abandoned thread's frames keep the old
        engine's device allocations (KV cache, executables) alive for
        as long as it stays wedged, so a REBUILD needs headroom for a
        second engine. Size the cache/pool with that in mind, or leave
        restart_budget=0 on memory-tight single-host deployments."""
        # Incident trigger decided under the lock, FIRED after it
        # drops: the bundle write snapshots the recorder/metrics/
        # in-flight state and must not extend the admission-serializing
        # critical section.
        incident: Optional[Tuple[str, Dict[str, Any]]] = None
        with self._lock:
            if g.dead or g is not self._g:
                return  # this generation is already being replaced
            g.dead = True
            g.stop.set()  # a wedged thread that ever returns exits
            self._fail_pending_locked(msg)
            # An IN-PLACE factory (a bound method of the current
            # engine, e.g. MultihostEngine.resync) mutates and reuses
            # the engine the wedged thread is still stepping — two
            # threads would then race one engine and its command
            # broadcasts. Safe after scheduler DEATH (that thread has
            # left the engine); terminal on a WEDGE.
            in_place = (self._engine_factory is not None
                        and getattr(self._engine_factory, "__self__",
                                    None) is g.engine)
            if wedged and in_place:
                # Supervisor state (_fatal/_recovering/restarts/_g) is
                # written under self._lock but read lock-free by the
                # /health and status() snapshot paths — single
                # reference/int swaps, "possibly stale, never torn"
                # (see health()). Annotated rather than locked so the
                # readiness probe never queues behind a recovery.
                self._fatal = (  # shellac: ignore[SH010]
                    f"{msg} [in-place resync cannot recover a wedged "
                    "step: the stuck thread still owns the engine — "
                    "restart the pod]"
                )
                # Terminal AND the pod is about to be restarted by
                # hand: if any fatal deserves a bundle (the in-memory
                # evidence dies with the pod), this one does.
                recover = False
                incident = ("wedge-fatal",
                            {"error": self._fatal,
                             "generation": g.gen,
                             "restarts": self.restarts})
            else:
                recover = (self._budget is not None
                           and not self._closed.is_set()
                           and self._budget.allow())
                if not recover:
                    if (self._budget is not None
                            and not self._closed.is_set()):
                        msg += (f" [restart budget exhausted: "
                                f"{self._budget.max_restarts} "
                                f"restart(s) per "
                                f"{self._budget.window:g}s]")
                        incident = ("restart-budget-exhausted",
                                    {"error": msg,
                                     "generation": g.gen,
                                     "restarts": self.restarts})
                    self._fatal = msg
                else:
                    # Lock-free snapshot readers by design — see the
                    # wedge-fatal arm above.
                    self._recovering = True  # shellac: ignore[SH010]
                    self.restarts += 1  # shellac: ignore[SH010]
                    self._m.restarts.inc()
                    incident = (
                        "wedge-rebuild" if wedged
                        else "scheduler-death",
                        {"error": msg, "generation": g.gen,
                         "restarts": self.restarts},
                    )
        if incident is not None:
            # Evidence FIRST (the recorder still holds the fault's
            # events; the rebuild below may take seconds), then the
            # rebuild. Wedge-class and rebuild triggers arm the
            # auto-capture if one was configured
            # (--incident-capture-seconds) — the device state behind
            # a wedge is exactly what a post-mortem wants, most of
            # all on the terminal wedge-fatal arm where the pod
            # restart is about to destroy it. Only budget exhaustion
            # skips it: there is no engine left worth profiling.
            self.trigger_incident(
                incident[0], detail=incident[1],
                capture_seconds=(
                    0 if incident[0] == "restart-budget-exhausted"
                    else None),
            )
        if not recover:
            return
        # Rebuild OUTSIDE the lock: engine construction allocates
        # device memory and may compile, and /health + admission must
        # stay responsive (reporting "recovering") meanwhile. Keep the
        # liveness heartbeat fresh for the whole rebuild — without a
        # step watchdog (no step_timeout) nothing else beats here, and
        # an external watchdog restarting the pod mid-rebuild would
        # defeat the supervisor.
        stop_beat = threading.Event()
        if self._hb is not None:
            def _rebuild_beater():
                while not stop_beat.wait(0.5):
                    self._beat(g)

            threading.Thread(target=_rebuild_beater, daemon=True).start()
        try:
            engine = self._engine_factory()
            if (self._tuned_ticks is not None
                    and getattr(engine, "decode_ticks_requested", None)
                    == "auto"
                    and getattr(engine, "_decode_ticks_tunable", True)):
                # The rebuilt generation inherits the startup tune; a
                # fresh sweep mid-recovery would stretch the outage.
                engine.set_decode_ticks(self._tuned_ticks)
                engine.decode_ticks_source = "auto-tuned"
            if (self._tuned_chunk is not _UNTUNED
                    and getattr(engine, "prefill_chunk_requested", None)
                    == "auto"
                    and getattr(engine, "_decode_ticks_tunable", True)):
                engine.set_prefill_chunk(self._tuned_chunk)
                engine.prefill_chunk_source = "auto-tuned"
        except Exception as e:  # noqa: BLE001 — any rebuild fault is fatal
            with self._lock:
                self._recovering = False
                self._fatal = (f"{msg}; engine rebuild failed: "
                               f"{type(e).__name__}: {e}")
            return
        finally:
            stop_beat.set()
        with self._lock:
            self._recovering = False
            if self._closed.is_set():
                self._fatal = "server closed during recovery"
                return
            # One reference swap; the engine/_thread properties read it
            # lock-free so every reader sees the live generation
            # without queueing behind recovery.
            self._g = self._start_generation(g.gen + 1, engine)  # shellac: ignore[SH010]
            self._g.thread.start()

    def _watchdog(self) -> None:
        """Detect a wedged engine step (lost follower, hung device) from
        outside the scheduler thread. One watchdog follows the
        supervisor across generations for the server's lifetime; it
        exits when the server closes or goes fatal."""
        poll = min(self.step_timeout / 4, 1.0)
        while not self._closed.wait(poll):
            if self._fatal is not None:
                return
            g = self._g
            # Keep the liveness heartbeat fresh through wedge detection
            # and rebuild: the scheduler loop cannot beat while its
            # step is stuck, and an external watchdog restarting the
            # pod mid-recovery would defeat the supervisor. Beats stop
            # once fatal (above), handing the pod back to the external
            # watchdog exactly when in-process recovery has given up.
            self._beat(g)
            started = g.step_started
            if (g.dead or started is None
                    or time.monotonic() - started <= self.step_timeout):
                continue
            self._recover(
                g,
                f"engine step exceeded step_timeout={self.step_timeout}s "
                "(wedged collective or lost follower)",
                wedged=True,
            )

    # ---- scheduler thread (sole owner of its generation's engine) ---

    def _loop(self, g: _Generation) -> None:
        try:
            self._run(g)
        except BaseException as e:  # noqa: BLE001
            # The scheduler thread is the only consumer; if it dies
            # silently every pending and future request blocks forever.
            # Hand the generation to the supervisor: fail everything
            # loudly, then rebuild within the restart budget.
            self._recover(g, f"scheduler died: {type(e).__name__}: {e}")

    def _beat(self, g: _Generation) -> None:
        """Touch the liveness file at most once a second (from the
        scheduler loop, and from the step watchdog while recovery is
        possible); a full disk must degrade observability, not kill
        serving."""
        if self._hb is None:
            return
        with self._hb_lock:
            now = time.monotonic()
            if now - self._hb_last < 1.0:
                return
            self._hb_last = now
            try:
                self._hb.beat(g.engine.stats.get("engine_steps", 0))
            except OSError:
                pass

    def _shed(self, rid, p: _Pending) -> None:
        """Settle one request as shed (both shed paths share this so
        the accounting and message cannot drift)."""
        if self._pending.pop(rid, None) is None:
            return
        # A shed prefill_only request never reaches the export path:
        # drop its migration target too.
        self._migrate_targets.pop(rid, None)
        # Single-writer: both shed paths run on the scheduler thread,
        # so the bare increment cannot lose updates; /health reads it
        # lock-free ("possibly stale, never torn").
        self.shed += 1  # shellac: ignore[SH010]
        if p.trace is not None:
            p.trace.shed()
        p.error = ("request shed: deadline expired before prefill "
                   "(server saturated past the client timeout)")
        p.kind = "shed"
        p.finish()

    def _shed_expired(self, g: _Generation) -> None:
        """Deadline-aware load shedding: drop engine-QUEUED requests
        whose client deadline already passed — the caller's wait timed
        out, so prefilling the prompt would burn compute on an answer
        nobody is waiting for. Requests already in a slot keep running
        (their compute is sunk; the finish path reclaims the slot)."""
        if not self._saw_deadline:
            return
        now = time.monotonic()
        queued = None
        for rid, p in list(self._pending.items()):
            if p.deadline is None or now <= p.deadline:
                continue
            if queued is None:  # one snapshot per sweep, lazily
                queued = {r.rid for r in g.engine._queue}
            if rid not in queued:
                continue
            g.engine.cancel(rid)
            self._shed(rid, p)

    def _process_item(self, g: _Generation, item) -> None:
        rid, tokens, max_new, stop, samp, deadline = item
        qos = None
        if samp and "_qos" in samp:
            # Tenant identity + scheduling class resolved at admission;
            # popped here so the engine's sampling-kwargs whitelist
            # never sees the marker.
            samp = dict(samp)
            qos = samp.pop("_qos")
        if tokens is None:
            # Cancellation marker: drop queued/in-flight work for an
            # abandoned client request.
            g.engine.cancel(rid)
            self._migrate_targets.pop(rid, None)
            p = self._pending.pop(rid, None)
            if p is not None:
                p.error = "cancelled"
                if p.trace is not None:
                    p.trace.abort("cancelled")
                p.finish()
            return
        if deadline is not None and time.monotonic() > deadline:
            # Expired before it ever reached the engine: shed without
            # spending prefill compute.
            p = self._pending.get(rid)
            if p is not None:
                self._shed(rid, p)
            return
        if samp and "_beam" in samp:
            # Beam request: runs synchronously on the scheduler thread
            # (the engine owner), like a long prefill — the device
            # program IS the whole request, so there is no slot to
            # multiplex.
            self._run_beam(g, rid, tokens, max_new, samp["_beam"])
            return
        if samp and "_kv_import" in samp:
            # KV adoption (decode replica): imported on the scheduler
            # thread — the only thread allowed to touch the engine.
            self._import_item(g, rid, *samp["_kv_import"])
            return
        if samp and "_kv_seed" in samp:
            # Prefix-seed adoption (fabric replication): registers
            # pure cache contents — no pending, no request.
            self._seed_item(g, *samp["_kv_seed"])
            return
        if samp and "_kv_export_chain" in samp:
            # Prefix-chain export (fabric replication, holder side):
            # the handler thread ships the blob; only the device pull
            # runs here.
            self._export_chain_item(g, *samp["_kv_export_chain"])
            return
        extra = {}
        if qos is not None:
            tenant, qcls, qweight = qos
            if tenant is not None:
                extra["tenant"] = tenant
            if qcls is not None:
                extra["qos_class"] = qcls
            if qweight is not None:
                extra["qos_weight"] = qweight
        if samp and "_migrate" in samp:
            # Prefill-only admission (prefill replica): the engine
            # freezes the slot at prefill; _service_frozen exports it
            # and the push worker ships it to the decode target.
            samp = dict(samp)
            self._migrate_targets[rid] = samp.pop("_migrate")
            extra["prefill_only"] = True
        pend = self._pending.get(rid)
        try:
            g.engine.submit(
                rid, tokens, max_new, stop=stop,
                trace=pend.trace if pend is not None else None,
                **extra, **samp,
            )
        except (ValueError, TypeError) as e:
            # TypeError: unknown sampling kwarg from a programmatic
            # caller — a bad request, not a scheduler-killing fault.
            # The pending may already be gone: close()'s sweep can
            # clear _pending while this thread is still draining its
            # last backlog items.
            self._migrate_targets.pop(rid, None)
            p = self._pending.pop(rid, None)
            if p is not None:
                p.error = str(e)
                if p.trace is not None:
                    p.trace.abort("error")
                p.finish()

    def _run_beam(self, g: _Generation, rid, tokens, max_new: int,
                  beam: Dict[str, Any]) -> None:
        """Run one beam-search request on the scheduler thread and
        settle its pending. Engine faults stay request-scoped: a pool-
        exhausted paged beam (RuntimeError) fails THIS request loudly
        instead of killing the scheduler."""
        p = self._pending.get(rid)
        if p is not None and p.trace is not None:
            p.trace.prefill_start()
        try:
            bs = getattr(g.engine, "beam_search", None)
            if bs is None:
                raise ValueError(
                    "beam search is not supported by this engine "
                    "(multi-host serving decodes through slots only)"
                )
            seqs, scores = bs(
                tokens, num_beams=beam["num_beams"],
                max_new_tokens=max_new,
                eos_id=getattr(g.engine, "eos_id", None),
                length_penalty=beam["length_penalty"],
                constraint=beam.get("constraint"),
            )
        except (ValueError, TypeError) as e:
            p = self._pending.pop(rid, None)
            if p is not None:
                p.error = str(e)
                if p.trace is not None:
                    p.trace.abort("error")
                p.finish()
            return
        except Exception as e:  # noqa: BLE001 — request-scoped fault
            p = self._pending.pop(rid, None)
            if p is not None:
                p.error = f"beam search failed: {type(e).__name__}: {e}"
                p.kind = "fault"
                if p.trace is not None:
                    p.trace.abort("fault")
                p.finish()
            return
        p = self._pending.pop(rid, None)
        if p is None:
            return  # cancelled or swept while the search ran
        if p.trace is not None:
            p.trace.first_token()
            p.trace.finish(sum(len(s) for s in seqs))
        p.result = {"beams": seqs, "scores": scores}
        p.finish()

    # ---- KV migration (disaggregated serving) -----------------------

    def _import_item(self, g: _Generation, rid, blob, ack,
                     tid) -> None:
        """Adopt one migrated request into the engine (scheduler
        thread). Failures settle the pending AND the handler's ack —
        PoolExhausted is the retryable class (fresh pair can serve),
        a refused blob (wrong backend/geometry) is a 400. `tid` is
        the migration id import_kv REGISTERED (minted when the blob
        carried none), so failure cleanup always finds the adoption
        entry."""
        pend = self._pending.get(rid)
        try:
            slot = disagg.import_blob(
                g.engine, blob, rid,
                trace=pend.trace if pend is not None else None,
            )
        except PoolExhausted:
            self._fail_import(rid, tid, ack, retryable=True,
                              msg="decode replica has no free slot or "
                                  "pool capacity; retry elsewhere")
            return
        except (ValueError, TypeError) as e:
            self._fail_import(rid, tid, ack, retryable=False, msg=str(e))
            return
        except Exception as e:  # noqa: BLE001 — request-scoped fault
            self._fail_import(
                rid, tid, ack, retryable=True,
                msg=f"kv import failed: {type(e).__name__}: {e}",
            )
            return
        self._m.migrations.labels(outcome="import").inc()
        ack.ok(slot)

    def _fail_import(self, rid, tid, ack, *, retryable: bool,
                     msg: str) -> None:
        self._m.migrations.labels(outcome="import_failed").inc()
        if tid is not None:
            self._adoptions.pop(tid, None)
        p = self._pending.pop(rid, None)
        if p is not None:
            p.error = msg
            if p.trace is not None:
                p.trace.abort("error")
            p.finish()
        ack.fail(msg, retryable)

    def _seed_item(self, g: _Generation, blob, ack, tid) -> None:
        """Adopt one prefix-seed blob (scheduler thread). Unlike
        _import_item there is no pending and no slot — a seed is pure
        cache contents — so failures settle only the handler's ack.
        PoolExhausted is the retryable class; a refused blob (wrong
        kind/backend/geometry) is a 400 with the registry untouched."""
        try:
            n = fabric.seed_chain(g.engine, blob)
        except PoolExhausted:
            self._m.fabric_seed_rejects.labels(reason="exhausted").inc()
            ack.fail(
                "no free-list headroom for the seed (seeding never "
                "evicts to make room); retry after load falls",
                retryable=True,
            )
            return
        except (ValueError, TypeError) as e:
            self._m.fabric_seed_rejects.labels(reason="mismatch").inc()
            ack.fail(str(e), False)
            return
        except Exception as e:  # noqa: BLE001 — request-scoped fault
            self._m.fabric_seed_rejects.labels(reason="fault").inc()
            ack.fail(f"kv seed failed: {type(e).__name__}: {e}", True)
            return
        self._m.fabric_seeded.inc(n)
        if self._recorder is not None:
            self._recorder.record(
                tid, "kv-seed", blocks=n,
                chain=len(blob.header.get("chain") or ()), src="server",
            )
        ack.ok(n)

    def _export_chain_item(self, g: _Generation, tip: bytes, ack,
                           tid) -> None:
        """Export one cached prefix chain (scheduler thread) and hand
        the blob back through the ack; the handler thread owns the
        HTTP leg. An evicted link is a 400 — the tier's directory is
        stale, and re-planning beats retrying a chain that is gone."""
        try:
            blob = fabric.export_chain(g.engine, tip, trace_id=tid)
        except (ValueError, TypeError) as e:
            ack.fail(str(e), False)
            return
        except Exception as e:  # noqa: BLE001 — request-scoped fault
            ack.fail(
                f"chain export failed: {type(e).__name__}: {e}", True,
            )
            return
        ack.ok(blob)

    def _service_frozen(self, g: _Generation) -> None:
        """Prefill-side migration driver, run on the scheduler thread
        after each step: export every newly frozen prefill-only slot
        (one batched device pull each), release the slot immediately
        (the host copy exists), and hand the blob to a push worker —
        the HTTP leg must never block the engine."""
        eng = g.engine
        if not getattr(eng, "frozen_prefills", None):
            return
        for rid in list(eng.frozen_prefills):
            slot = eng.frozen_prefills[rid]
            req = eng._slots[slot]
            target = self._migrate_targets.pop(rid, None)
            p = self._pending.get(rid)
            tid = (p.trace.trace_id
                   if p is not None and p.trace is not None else None)
            try:
                if target is None:
                    raise ValueError(
                        "prefill_only request lost its migrate_to "
                        "target"
                    )
                blob = disagg.export_slot(eng, slot, req, trace_id=tid)
            except Exception as e:  # noqa: BLE001 — request-scoped fault
                eng.release_frozen(rid)
                self._m.migrations.labels(outcome="export_failed").inc()
                pp = self._pending.pop(rid, None)
                if pp is not None:
                    pp.error = (f"kv export failed: "
                                f"{type(e).__name__}: {e}")
                    pp.kind = "fault"
                    if pp.trace is not None:
                        pp.trace.abort("fault")
                    pp.finish()
                continue
            eng.release_frozen(rid)
            eng.stats["kv_exports"] += 1
            if p is not None and p.trace is not None:
                p.trace.record(
                    "kv-export", src="server", rid=rid, slot=slot,
                    tokens=blob.header["length"], target=target,
                    complete=blob.header["complete"],
                )
            if self._push_pool is None:
                self._push_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="shellac-kv-push",
                )
            if target.startswith("park:"):
                # Park leg: the blob goes to the durable spool, not a
                # decode replica — same worker pool, different sink.
                self._push_pool.submit(
                    self._park_blob, rid, blob, target[len("park:"):],
                )
            else:
                self._push_pool.submit(
                    self._push_migration, rid, blob, target,
                    p.deadline if p is not None else None,
                )

    def _push_migration(self, rid, blob, target: str,
                        deadline: Optional[float]) -> None:
        """Push worker: serialize + POST the blob to the decode
        replica's /kv/import, then settle the prefill client's pending
        with the migration ack — or, on any failure, with a retryable
        503 ("kv-push-failed" marker) so the tier re-runs the full
        prefill->migrate path on a fresh pair."""
        p = self._pending.get(rid)
        tid = (p.trace.trace_id
               if p is not None and p.trace is not None else None)
        data = blob.serialize()
        timeout = 30.0
        if deadline is not None:
            timeout = max(1.0, min(timeout,
                                   deadline - time.monotonic()))
        headers = {"Content-Type": "application/octet-stream"}
        if tid is not None:
            headers[TRACE_HEADER] = format_trace_header(tid, 0)
        t0 = time.monotonic()
        try:
            req = urllib.request.Request(
                target.rstrip("/") + "/kv/import", data=data,
                headers=headers,
            )
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                body = json.loads(resp.read() or b"{}")
        except Exception as e:  # noqa: BLE001 — one retryable leg
            self._m.migrations.labels(outcome="export_failed").inc()
            pp = self._pending.pop(rid, None)
            if pp is not None:
                pp.error = (f"kv-push-failed: could not deliver KV to "
                            f"{target}: {type(e).__name__}: {e}")
                pp.kind = "unavailable"
                if pp.trace is not None:
                    pp.trace.abort("fault")
                pp.finish()
            return
        dt = time.monotonic() - t0
        self._m.kv_transfer_seconds.observe(dt, exemplar=tid)
        self._m.kv_transfer_bytes.observe(float(len(data)),
                                          exemplar=tid)
        self._m.migrations.labels(outcome="export").inc()
        pp = self._pending.pop(rid, None)
        if pp is None:
            return  # cancelled or swept while pushing
        n_out = len(blob.header["request"]["out"])
        pp.result = {
            "migrated": True,
            "migration_id": body.get("migration_id") or tid,
            "decode": target.rstrip("/"),
            "complete": bool(blob.header["complete"]),
            "bytes": len(data),
            "transfer_s": round(dt, 6),
            "tokens_out": n_out,
            "prompt_tokens": int(blob.header["length"]),
        }
        if pp.trace is not None:
            pp.trace.finish(n_out)
        pp.finish()

    def _park_blob(self, rid, blob, park_id: str) -> None:
        """Push worker: spool one exported slot durably and settle the
        parking client's pending with the park receipt. A park that
        did not land durably fails loudly — a receipt for a lost blob
        would strand the session."""
        p = self._pending.get(rid)
        tid = (p.trace.trace_id
               if p is not None and p.trace is not None else None)
        data = blob.serialize()
        try:
            self._park.put(park_id, data)
        except OSError as e:
            pp = self._pending.pop(rid, None)
            if pp is not None:
                pp.error = (f"kv park failed: could not spool "
                            f"{park_id!r}: {type(e).__name__}: {e}")
                pp.kind = "fault"
                if pp.trace is not None:
                    pp.trace.abort("fault")
                pp.finish()
            return
        self._m.fabric_parked.inc()
        self._m.fabric_park_bytes.set(
            float(sum(e["bytes"] for e in self._park.list()))
        )
        if self._recorder is not None:
            self._recorder.record(
                tid, "fabric-park", park_id=park_id, bytes=len(data),
                complete=bool(blob.header["complete"]),
            )
        pp = self._pending.pop(rid, None)
        if pp is None:
            return  # cancelled or swept while spooling
        n_out = len(blob.header["request"]["out"])
        pp.result = {
            "parked": True,
            "park_id": park_id,
            "bytes": len(data),
            "complete": bool(blob.header["complete"]),
            "prompt_tokens": int(blob.header["length"]),
            "tokens_out": n_out,
        }
        if pp.trace is not None:
            pp.trace.finish(n_out)
        pp.finish()

    def _sweep_adoptions(self, g: _Generation) -> None:
        """Expire un-adopted migrations (scheduler thread): a decode
        replica must not pin slots or results for a client that never
        arrived (tier died between the migrate and adopt legs)."""
        if not self._adoptions:
            return
        now = time.monotonic()
        for mid, (p, t) in list(self._adoptions.items()):
            if now - t <= self._adopt_ttl:
                continue
            if self._adoptions.pop(mid, None) is None:
                continue
            if not p.event.is_set():
                g.engine.cancel(p.rid)
                pp = self._pending.pop(p.rid, None)
                if pp is not None:
                    pp.error = ("migration never adopted "
                                "(adopt_ttl expired)")
                    if pp.trace is not None:
                        pp.trace.abort("cancelled")
                    pp.finish()

    # ---- preempt-and-park (multi-tenant QoS) ------------------------

    @staticmethod
    def _free_slot_available(engine) -> bool:
        return any(
            r is None and i not in engine._prefilling
            for i, r in enumerate(engine._slots)
        )

    def _maybe_preempt(self, g: _Generation) -> None:
        """Preempt-and-park: when the best-priority waiting request has
        waited past --preempt-after and every slot is busy, freeze the
        cheapest strictly-lower-class victim mid-decode, export its KV,
        and free the slot so the step that follows seats the waiter.
        The victim's client stays attached — _resume_preempted() later
        re-places the KV in a free slot and decoding continues
        token-identical (greedy and seeded sampling both derive their
        keys from position, not a shared stream)."""
        if self._preempt_after is None:
            return
        engine = g.engine
        q = getattr(engine, "_queue", None)
        best = q.best_waiting() if hasattr(q, "best_waiting") else None
        if best is None:
            return
        wcls, head = best
        waited = time.monotonic() - getattr(head, "t_queued", 0.0)
        if waited < self._preempt_after:
            return
        if self._free_slot_available(engine):
            return  # the next step seats the waiter without violence
        victims = [v for v in engine.preemptable() if v[2] > wcls]
        if not victims:
            return
        # Cheapest victim: lowest priority class first, then fewest
        # MEASURED resident KV bytes (bytes_per_token tracks the cache
        # backend, so int8 halves a victim's cost instead of the rule
        # guessing from token counts alone).
        bpt = engine.cache_backend.bytes_per_token()
        vrid, vslot, vcls, vtokens = max(
            victims, key=lambda v: (v[2], -v[3]))
        req = engine._slots[vslot]
        tenant = getattr(req, "tenant", None)
        p = self._pending.get(vrid)
        tid = (p.trace.trace_id
               if p is not None and p.trace is not None else None)
        if tid is None:
            tid = new_trace_id()
        try:
            finished = engine.preempt(vrid)
        except ValueError:
            return  # raced a finish/cancel; nothing to do
        self._deliver_finished(g, finished)
        if vrid not in engine.frozen_decodes:
            return  # the victim finished while the windows drained
        try:
            blob = disagg.export_slot(engine, vslot, req, trace_id=tid)
        except Exception as e:  # noqa: BLE001 — keep the victim alive
            # Export failed: thaw in place. The slot still holds the
            # request, so clearing the freeze resumes decoding exactly
            # where the drain left it — worse fairness beats a lost
            # request.
            engine.frozen_decodes.pop(vrid, None)
            req.frozen = False
            engine._patch_slot(vslot, done=False)
            if p is not None and p.trace is not None:
                p.trace.record("preempt-failed", src="server", rid=vrid,
                               error=f"{type(e).__name__}: {e}")
            return
        engine.release_frozen(vrid)
        name = tenant or ANONYMOUS
        nbytes = int(vtokens) * int(bpt)
        self._preempted[vrid] = (blob, tenant, tid, nbytes)
        self._m.tenant_preemptions.labels(tenant=name).inc()
        self._parked_tenant_bytes[name] = (
            self._parked_tenant_bytes.get(name, 0) + nbytes)
        self._m.tenant_parked_bytes.labels(tenant=name).set(
            float(self._parked_tenant_bytes[name]))
        if p is not None and p.trace is not None:
            p.trace.record(
                "preempt-park", src="server", rid=vrid, slot=vslot,
                victim_class=int(vcls), waiter_class=int(wcls),
                resident_tokens=int(vtokens), bytes=nbytes,
                tenant=name,
            )
        # Durable safety copy, fire-and-forget: a SIGKILL before the
        # resume still leaves the fleet a crc-checked artifact in the
        # shared park spool. The client's pending is NOT settled here
        # — unlike the `park:` migrate leg, preemption keeps the
        # client attached and invisible except as latency.
        if self._park is not None:
            if self._push_pool is None:
                self._push_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=4, thread_name_prefix="shellac-kv-push",
                )
            self._push_pool.submit(
                self._park_safety_copy, blob, "preempt-" + tid)

    def _park_safety_copy(self, blob, park_id: str) -> None:
        """Push worker: best-effort durable copy of a preempted blob.
        The authoritative copy is in-memory and resume does not block
        on the spool, so a failed write costs only the error counter
        the store already keeps."""
        try:
            data = blob.serialize()
            self._park.put(park_id, data)
        except (OSError, ValueError):
            return
        self._m.fabric_parked.inc()
        self._m.fabric_park_bytes.set(
            float(sum(e["bytes"] for e in self._park.list()))
        )

    def _drop_preempted(self, vrid) -> None:
        """Forget one parked victim and settle its parked-bytes
        accounting (resume landed, client vanished, or resume failed
        terminally)."""
        blob, tenant, tid, nbytes = self._preempted.pop(vrid)
        name = tenant or ANONYMOUS
        left = self._parked_tenant_bytes.get(name, 0) - nbytes
        if left > 0:
            self._parked_tenant_bytes[name] = left
        else:
            self._parked_tenant_bytes.pop(name, None)
            left = 0
        self._m.tenant_parked_bytes.labels(tenant=name).set(float(left))

    def _resume_preempted(self, g: _Generation) -> None:
        """Re-place preempted KV into free slots, oldest victim first.
        Runs AFTER the step, so the preempting waiter seats before its
        victim competes for the slot it vacated."""
        if not self._preempted:
            return
        engine = g.engine
        for vrid in list(self._preempted):
            if not self._free_slot_available(engine):
                return
            blob, tenant, tid, _ = self._preempted[vrid]
            p = self._pending.get(vrid)
            if p is None or p.event.is_set():
                # Client gone (cancelled or swept): drop the blob.
                self._drop_preempted(vrid)
                continue
            try:
                slot = disagg.import_blob(engine, blob, vrid, p.trace)
            except PoolExhausted:
                return  # capacity, not corruption: retry next loop
            except Exception as e:  # noqa: BLE001 — request-scoped
                self._drop_preempted(vrid)
                pp = self._pending.pop(vrid, None)
                if pp is not None:
                    pp.error = (f"preempt resume failed: "
                                f"{type(e).__name__}: {e}")
                    pp.kind = "unavailable"
                    if pp.trace is not None:
                        pp.trace.abort("fault")
                    pp.finish()
                continue
            self._drop_preempted(vrid)
            name = tenant or ANONYMOUS
            # The import rebuilds the request with default QoS fields;
            # restore identity so a resumed victim can be preempted
            # (or scheduled) under its own contract again.
            r2 = engine._slots[slot]
            if r2 is not None:
                r2.tenant = tenant
                if self._tenant_policy is not None:
                    spec = self._tenant_policy.spec(name)
                    r2.qos_class = spec.qos_class
                    r2.qos_weight = spec.qos_weight
            if p.trace is not None:
                p.trace.record("preempt-resume", src="server",
                               rid=vrid, slot=slot, tenant=name)

    def _run(self, g: _Generation) -> None:
        engine = g.engine
        # Multi-host engines need a step per loop iteration even when
        # idle: follower processes wait inside the command broadcast,
        # and an un-stepped primary would leave them parked in a device
        # collective until its transport times out.
        idle_steps = bool(getattr(engine, "needs_heartbeat", False))
        while not g.stop.is_set():
            drained = False
            while True:
                try:
                    item = g.submit_q.get_nowait()
                except queue.Empty:
                    break
                drained = True
                self._process_item(g, item)
            self._shed_expired(g)
            self._sweep_adoptions(g)
            self._beat(g)
            if engine.pending or idle_steps:
                # QoS: freeing a victim's slot BEFORE the step lets
                # this very step seat the starved waiter.
                self._maybe_preempt(g)
                g.step_started = time.monotonic()
                try:
                    finished = engine.step() or []
                finally:
                    # Clear the clock even when the step RAISES, so the
                    # watchdog cannot misread a dying scheduler (whose
                    # own _recover is about to run) as a wedge.
                    g.step_started = None
                if g.dead or g is not self._g:
                    # Stale generation: the supervisor replaced this
                    # engine while the step was wedged. Results the old
                    # generation computed are DISCARDED — the pendings
                    # they would resolve were already failed loudly,
                    # and any same-numbered pendings now belong to the
                    # replacement engine.
                    return
                fin = {rid for rid, _ in finished}
                # Stream deltas for requests still in flight. holdback
                # trails the tail by the longest stop length, so a
                # token a later stop match would truncate is never
                # emitted (out only ever shrinks by a matched stop).
                for req in engine._slots:
                    if req is None or req.rid in fin:
                        continue
                    p = self._pending.get(req.rid)
                    if p is None or p.chunks is None:
                        continue
                    upto = max(p.emitted, len(req.out) - p.holdback)
                    if upto > p.emitted:
                        p.chunks.put(list(req.out[p.emitted:upto]))
                        p.emitted = upto
                self._deliver_finished(g, finished)
                # Disaggregated prefill replica: export + ship every
                # slot this step froze (no-op otherwise).
                self._service_frozen(g)
                # Preempted victims re-enter free slots only after the
                # step (the waiter they yielded to seats first).
                self._resume_preempted(g)
                if idle_steps and not drained and not engine.pending:
                    # Idle heartbeat tick: pace the broadcast instead of
                    # spinning the interconnect at full rate.
                    g.stop.wait(0.01)
            elif not drained:
                # Idle: block briefly on the queue instead of spinning.
                # Process in place — re-enqueueing could reorder a
                # submit behind its own cancellation marker.
                try:
                    self._process_item(g, g.submit_q.get(timeout=0.05))
                except queue.Empty:
                    pass

    def _deliver_finished(self, g: _Generation, finished) -> None:
        """Settle every (rid, out) the engine finished this step —
        shared by the step loop and the preemption drain, so the
        logprob-store handoff and pending settlement cannot drift."""
        engine = g.engine
        lp_store = getattr(engine, "finished_logprobs", {})
        plp_store = getattr(engine, "finished_prompt_logprobs", {})
        tl_store = getattr(engine, "finished_top_logprobs", {})
        for rid, out in finished:
            p = self._pending.pop(rid, None)
            if p is not None:
                p.result = out
                if p.trace is not None:
                    p.trace.finish(len(out))
                p.lps = lp_store.pop(rid, None)
                p.plp = plp_store.pop(rid, None)
                p.tlp = tl_store.pop(rid, None)
                if p.chunks is not None and len(out) > p.emitted:
                    p.chunks.put(list(out[p.emitted:]))
                p.finish()
            else:
                lp_store.pop(rid, None)
                plp_store.pop(rid, None)
                tl_store.pop(rid, None)

    # ---- client surface ---------------------------------------------

    def _submit(self, tokens, max_new: int, stop, samp, *, stream: bool,
                deadline: Optional[float] = None,
                trace_ctx: Optional[Tuple[str, int]] = None,
                tenant: Optional[str] = None) -> _Pending:
        # Distributed-trace identity: adopt the (trace_id, attempt) the
        # HTTP layer pulled off x-shellac-trace, minting a fresh id for
        # direct library callers — every admitted request has exactly
        # one id, whoever it came from.
        tid, attempt = (trace_ctx if trace_ctx is not None
                        else (new_trace_id(), 0))
        tenant = str(tenant) if tenant else None
        # The span clock starts at admission, before any copying or
        # queueing, so queue-wait covers everything the client waits
        # through server-side.
        trace = self._m.trace(trace_id=tid, recorder=self._recorder,
                              tenant=tenant)
        # Convert the prompt BEFORE taking the lock: the copy is O(S)
        # and the lock serializes every admission and the supervisor.
        tokens = np.asarray(tokens, np.int32)
        # QoS identity resolved outside the lock too. The priority
        # class/weight come from the tenant policy when one is
        # configured; untenanted servers leave them None and the
        # engine's defaults apply (FIFO-identical scheduling).
        spec = (self._tenant_policy.spec(tenant or ANONYMOUS)
                if self._tenant_policy is not None else None)
        # Admit-event fields built outside the lock too (the optional
        # text decode is O(prompt)); text rides the event only under
        # --debug-include-text.
        admit_fields: Dict[str, Any] = {
            "src": "server", "attempt": attempt,
            "prompt_len": int(tokens.size), "max_new": int(max_new),
            "stream": stream,
        }
        if tenant is not None:
            admit_fields["tenant"] = tenant
        if spec is not None:
            admit_fields["qos_class"] = spec.priority
        if self._debug_text and self.tokenizer is not None:
            admit_fields["prompt_text"] = self.tokenizer.decode(
                [int(t) for t in tokens[:256]]
            )
        with self._lock:
            # Admission control. The lock pairs this with the
            # supervisor's sweep: a request either registers before the
            # sweep (and is failed loudly by it) or sees the post-swap
            # state here — it can never strand in a dead generation's
            # queue unobserved.
            if self._fatal is not None:
                raise RuntimeError(self._fatal)
            if self._closed.is_set():
                raise RuntimeError("server closed")
            g = self._g
            if self._recovering or g.dead:
                self._m.rejects.labels(reason="recovering",
                                       tenant=tenant or "").inc()
                raise ServerUnavailable(
                    "server recovering from an engine fault; retry",
                    http_status=503, retry_after=retry_after(3.0, 8.0),
                )
            if self._draining:
                self._m.rejects.labels(reason="draining",
                                       tenant=tenant or "").inc()
                raise ServerUnavailable(
                    "server draining: not admitting new requests "
                    "(in-flight work is completing); retry elsewhere",
                    http_status=503, retry_after=retry_after(1.0, 4.0),
                )
            if (self.max_pending is not None
                    and len(self._pending) >= self.max_pending):
                self._m.rejects.labels(reason="overloaded",
                                       tenant=tenant or "").inc()
                raise ServerUnavailable(
                    f"server overloaded: {len(self._pending)} requests "
                    f"pending (max_pending={self.max_pending})",
                    http_status=429, retry_after=retry_after(1.0, 3.0),
                )
            released: Optional[Callable[[], None]] = None
            if self._qos_admission is not None:
                # Per-tenant quotas AFTER the global gates: a global
                # overload answer must not charge a tenant's bucket.
                # Cost = prompt + budgeted max_new — the same token
                # count the engine will reserve, so the bucket meters
                # work, not request count.
                name = tenant or ANONYMOUS
                cost = int(tokens.size) + int(max_new)
                ok, why, wait = self._qos_admission.admit(name, cost)
                if not ok:
                    self._m.rejects.labels(reason="throttled",
                                           tenant=tenant or "").inc()
                    self._m.tenant_throttles.labels(
                        tenant=name, reason=why).inc()
                    # Jittered Retry-After on top of the bucket's
                    # deterministic refill estimate: synchronized
                    # over-quota clients must not return in lockstep.
                    raise ServerUnavailable(
                        f"tenant {name!r} over its {why} quota",
                        http_status=429,
                        retry_after=retry_after(
                            max(wait, 0.5), max(wait, 0.5) + 2.0),
                    )
                self._m.tenant_tokens.labels(tenant=name).inc(cost)
                released = functools.partial(
                    self._qos_admission.release, name)
            rid = next(self._ids)
            holdback = max((len(s) for s in stop), default=0) if stop else 0
            if deadline is not None:
                # Monotonic False->True gate; the scheduler reads it
                # lock-free in _shed_expired as a fast-path skip, and
                # a stale False only delays the first shed sweep one
                # loop iteration.
                self._saw_deadline = True  # shellac: ignore[SH010]
            p = _Pending(rid, stream=stream, holdback=holdback,
                         deadline=deadline, trace=trace, tenant=tenant)
            p.on_finish = released
            self._pending[rid] = p
            samp = dict(samp or {})
            if spec is not None or tenant is not None:
                # Rides the submit tuple to the scheduler thread, which
                # pops it into engine.submit(tenant=, qos_class=,
                # qos_weight=) — the weighted-fair queue's inputs.
                samp["_qos"] = (
                    tenant,
                    spec.qos_class if spec is not None else None,
                    spec.qos_weight if spec is not None else None,
                )
            # Recorded BEFORE the scheduler can see the request: the
            # enqueue below hands it to the engine thread, which
            # records queue/prefill next — admit must already hold the
            # timeline's first seq or a fast scheduler reorders it.
            trace.record("admit", rid=rid, pending=len(self._pending),
                         **admit_fields)
            g.submit_q.put(
                (rid, tokens, max_new, stop, samp, deadline)
            )
        return p

    def _raise(self, p: _Pending):
        # Server faults (scheduler death / wedge / close) are HTTP 500,
        # shed deadlines are saturation — retryable 503 + Retry-After,
        # NOT a 400 an OpenAI SDK would treat as permanent — and
        # anything else is a bad request (400): keep the classes
        # distinct. (A non-streaming caller usually races its own
        # identical timeout and sees that instead; the 503 surfaces
        # when the shed outcome reaches a still-waiting client, e.g.
        # a stream whose per-chunk timeout outlives the deadline.)
        if p.kind == "fault":
            raise RuntimeError(p.error)
        if p.kind in ("shed", "unavailable"):
            # "unavailable": a migration leg failed in a way a fresh
            # pair can serve (push failed, pool full) — retryable 503,
            # exactly like a shed, so the tier re-runs the full path.
            raise ServerUnavailable(p.error, http_status=503,
                                    retry_after=retry_after(1.0, 3.0))
        raise ValueError(p.error)

    def _await(self, p: _Pending, deadline: Optional[float]) -> _Pending:
        remaining = (None if deadline is None
                     else max(deadline - time.monotonic(), 0.0))
        if not p.event.wait(remaining):
            raise TimeoutError("request timed out")
        if p.error is not None:
            self._raise(p)
        return p

    def _cancel(self, p: _Pending) -> None:
        """Ask the scheduler to drop an unfinished request (tokens=None
        marker); its engine slot frees instead of generating unread
        tokens."""
        if not p.event.is_set():
            self._g.submit_q.put((p.rid, None, 0, None, None, None))

    @staticmethod
    def _deadline(timeout) -> Optional[float]:
        return None if timeout is None else time.monotonic() + timeout

    def generate(self, tokens, max_new: int, timeout: Optional[float] = None,
                 stop=None, return_logprobs: bool = False,
                 trace_ctx: Optional[Tuple[str, int]] = None,
                 tenant: Optional[str] = None, **samp):
        # The timeout doubles as the request's deadline: it rides the
        # submit tuple so the scheduler can shed the request if it
        # expires before prefill ever runs.
        deadline = self._deadline(timeout)
        p = self._submit(tokens, max_new, stop, samp, stream=False,
                         deadline=deadline, trace_ctx=trace_ctx,
                         tenant=tenant)
        try:
            self._await(p, deadline)
        except TimeoutError:
            # Don't strand the slot generating tokens nobody will read.
            self._cancel(p)
            raise
        if return_logprobs:
            return p.result, p.lps, p.plp, p.tlp
        return p.result

    def generate_stream(self, tokens, max_new: int,
                        timeout: Optional[float] = None, stop=None,
                        return_logprobs: bool = False,
                        trace_ctx: Optional[Tuple[str, int]] = None,
                        tenant: Optional[str] = None, **samp):
        """Yield ("delta", [token ids]) as generation progresses, then
        ("done", full output) — or ("done", (output, logprobs)) with
        return_logprobs=True. `timeout` bounds the wait per chunk (and
        doubles as the admission deadline: a stream that cannot start
        before it elapses is shed instead of prefilled)."""
        p = self._submit(tokens, max_new, stop, samp, stream=True,
                         deadline=self._deadline(timeout),
                         trace_ctx=trace_ctx, tenant=tenant)
        finished = False
        try:
            while True:
                try:
                    chunk = p.chunks.get(timeout=timeout)
                except queue.Empty:
                    raise TimeoutError("request timed out mid-stream")
                if chunk is None:
                    break
                yield ("delta", chunk)
            if p.error is not None:
                self._raise(p)
            finished = True
            yield ("done",
                   (p.result, p.lps, p.plp, p.tlp) if return_logprobs
                   else p.result)
        finally:
            if not finished:
                # Consumer abandoned the stream (client disconnect tears
                # the generator down via GeneratorExit) or it errored:
                # free the slot instead of generating unread tokens.
                self._cancel(p)

    def _parse(self, payload: dict):
        if "tokens" in payload:
            tokens = np.asarray(payload["tokens"], np.int32)
        elif "text" in payload:
            if self.tokenizer is None:
                raise ValueError('"text" needs a server-side tokenizer')
            tokens = self.tokenizer.encode(payload["text"])
        else:
            raise ValueError('need "tokens" or "text"')
        max_new = int(payload.get("max_new", 32))
        stop = payload.get("stop")
        if stop is not None:
            try:
                parsed = []
                for s in stop:
                    if isinstance(s, str):
                        if self.tokenizer is None:
                            raise ValueError(
                                "string stop sequences need a server-side "
                                "tokenizer"
                            )
                        parsed.append(
                            list(map(int, self.tokenizer.encode(s)))
                        )
                    else:
                        parsed.append(list(map(int, s)))
            except (TypeError, ValueError) as e:
                # Malformed payloads must surface as HTTP 400, not a
                # dropped connection.
                raise ValueError(f"bad stop sequences: {e}")
            stop = parsed
        # Per-request sampling overrides (validated by engine.submit;
        # whitelisted so unknown payload keys can't reach **kwargs).
        try:
            samp = {
                k: float(payload[k])
                for k in ("temperature", "top_p", "min_p",
                          "presence_penalty", "frequency_penalty")
                if payload.get(k) is not None
            }
            for key in ("top_k", "min_tokens", "seed"):
                if payload.get(key) is not None:
                    v = float(payload[key])
                    if not v.is_integer():
                        raise ValueError(
                            f"{key} must be an integer, got {v}"
                        )
                    samp[key] = int(v)
            if payload.get("prompt_logprobs"):
                samp["prompt_logprobs"] = True
            if payload.get("logit_bias") is not None:
                lb = payload["logit_bias"]
                if not isinstance(lb, dict):
                    raise ValueError(
                        "logit_bias must be a {token id: bias} object"
                    )
                samp["logit_bias"] = lb  # entries validated by submit
            if payload.get("constraint") is not None:
                samp["constraint"] = self._compile_constraint(
                    payload["constraint"]
                )
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad sampling parameters: {e}")
        return tokens, max_new, stop, samp

    def _compile_constraint(self, spec):
        """Compile a constraint spec ({"regex"|"json_schema"|
        "json_object"}) to a TokenDFA over this server's tokenizer,
        cached per pattern — the compile walks the whole vocab, so a
        repeated schema must not pay it twice."""
        from shellac_tpu.inference.constraints import (
            compile_token_dfa,
            constraint_pattern,
        )

        if self.tokenizer is None:
            raise ValueError(
                "constrained decoding needs a server-side tokenizer "
                "(the grammar compiles against token strings)"
            )
        eos_id = getattr(self.engine, "eos_id", None)
        if eos_id is None:
            raise ValueError(
                "constrained decoding needs the engine's eos_id (serve "
                "--eos-id or a tokenizer that defines one)"
            )
        pattern = constraint_pattern(spec)
        cached = self._constraint_cache.get(pattern)
        if cached is None:
            self._m.constraint_cache.labels(result="miss").inc()
            t0 = time.monotonic()
            cached = compile_token_dfa(
                pattern, self.tokenizer, self.engine.cfg.vocab_size,
                eos_id,
            )
            # Compile latency is the cache-miss cost a novel schema
            # pays at admission (the walk covers the whole vocab);
            # the hit/miss counters say whether production traffic is
            # actually amortizing it.
            self._m.constraint_compile.observe(time.monotonic() - t0)
            self._constraint_cache[pattern] = cached
            # Client-supplied patterns key this cache: bound it (LRU)
            # so sustained novel schemas cannot grow host memory
            # without limit — each table is O(states x vocab) int32.
            while len(self._constraint_cache) > 32:
                self._constraint_cache.pop(
                    next(iter(self._constraint_cache))
                )
        else:
            self._m.constraint_cache.labels(result="hit").inc()
            self._constraint_cache.move_to_end(pattern)
        return cached

    def _check_logprobs(self, payload) -> bool:
        want = bool(payload.get("logprobs"))
        if want and not getattr(self.engine, "logprobs", False):
            raise ValueError(
                "logprobs requested but the server engine was not built "
                "with logprobs=True (serve --logprobs)"
            )
        return want

    def _check_top_logprobs(self, payload, want_lps: bool) -> int:
        """Per-request k of alternatives to RENDER (0 = none). The
        engine records its configured max for every request; k only
        slices."""
        k = payload.get("top_logprobs")
        if k in (None, 0, False):
            return 0
        k = int(k)
        cap = getattr(self.engine, "top_logprobs", 0)
        if k == 1 and cap == 0 and payload.get("top_logprobs_soft"):
            # OpenAI's completions `logprobs: 1` predates alternative
            # recording here; the completions translator marks it soft
            # so servers without --top-logprobs keep its long-standing
            # meaning (chosen token's logprob, no alternatives block).
            # Explicit chat/native `top_logprobs: 1` stays a loud 400
            # below — a misconfigured server must not silently degrade
            # a request that asked for alternatives by name.
            return 0
        if k < 1 or k > cap:
            raise ValueError(
                f"top_logprobs={k}: this server records "
                f"{cap or 'no'} alternatives (serve --top-logprobs N)"
            )
        if not want_lps:
            raise ValueError("top_logprobs needs logprobs=true")
        return k

    @staticmethod
    def _render_tlp(tlp, k):
        """[(ids, lps)] per token -> [[{'id', 'logprob'}] * k]."""
        return [
            [{"id": int(i), "logprob": float(v)}
             for i, v in zip(ids[:k], vals[:k])]
            for ids, vals in tlp
        ]

    # Knobs that do not compose with beam search, with their neutral
    # values: beam decode is deterministic and returns whole ranked
    # sequences, so a non-neutral sampling/streaming knob would be
    # silently ignored — loud 400 instead, the scope-honesty rule the
    # OpenAI facade already follows.
    _BEAM_NEUTRAL = {
        "stream": (None, False), "n": (None, 1), "best_of": (None, 1),
        "logprobs": (None, False), "top_logprobs": (None, 0),
        "min_tokens": (None, 0), "logit_bias": (None,),
        "presence_penalty": (None, 0, 0.0),
        "frequency_penalty": (None, 0, 0.0), "seed": (None,),
        "prompt_logprobs": (None, False), "stop": (None,),
        "temperature": (None, 0, 0.0), "top_p": (None, 1, 1.0),
        "top_k": (None,), "min_p": (None, 0, 0.0),
    }

    def _handle_beam(self, payload: dict,
                     trace_ctx: Optional[Tuple[str, int]] = None,
                     tenant: Optional[str] = None) -> dict:
        """Native beam-search request: `num_beams` (+ optional
        `length_penalty`, `constraint`) returns the ranked beams as
        {"choices": [{"tokens", "beam_score", "text"?}]}."""
        try:
            nb = int(payload["num_beams"])
            lp = float(payload.get("length_penalty", 1.0))
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad num_beams/length_penalty: {e}")
        if nb < 1:
            raise ValueError(f"num_beams must be >= 1, got {nb}")
        cap = max(4 * getattr(self.engine, "n_slots", 8), 16)
        if nb > cap:
            raise ValueError(
                f"num_beams={nb} exceeds this server's cap of {cap}"
            )
        for key, neutral in self._BEAM_NEUTRAL.items():
            if key in payload and payload[key] not in neutral:
                raise ValueError(
                    f"{key}={payload[key]!r} does not compose with "
                    "num_beams (beam search is deterministic and "
                    "unstreamed)"
                )
        tokens, max_new, _, samp = self._parse(payload)
        deadline = self._deadline(payload.get("timeout"))
        p = self._submit(
            tokens, max_new, None,
            {"_beam": {"num_beams": nb, "length_penalty": lp,
                       "constraint": samp.get("constraint")}},
            stream=False, deadline=deadline, trace_ctx=trace_ctx,
            tenant=tenant,
        )
        try:
            self._await(p, deadline)
        except TimeoutError:
            self._cancel(p)
            raise
        choices = []
        for seq, score in zip(p.result["beams"], p.result["scores"]):
            c: Dict[str, Any] = {"tokens": seq,
                                 "beam_score": round(float(score), 6)}
            if self.tokenizer is not None:
                c["text"] = self.tokenizer.decode(seq)
            choices.append(c)
        return {"choices": choices, "num_beams": nb}

    # ---- KV migration client surface (disaggregated serving) --------

    def import_kv(self, body: bytes,
                  trace_ctx: Optional[Tuple[str, int]] = None
                  ) -> Dict[str, Any]:
        """POST /kv/import: adopt a migrated request. Deserializes +
        integrity-checks the blob (400 on refusal), applies the same
        admission gates as _submit, then hands the import to the
        scheduler thread and waits for its ack. The imported request
        starts decoding IMMEDIATELY — the adopt request that follows
        attaches to it, so transfer and decode overlap with the tier's
        second leg instead of serializing behind it."""
        blob = disagg.MigrationBlob.deserialize(bytes(body))
        tid = blob.header.get("trace_id") or (
            trace_ctx[0] if trace_ctx is not None else new_trace_id()
        )
        return self._import_blob(blob, tid)

    def _import_blob(self, blob, tid: str) -> Dict[str, Any]:
        """Admit one already-deserialized migration blob under
        migration id `tid` — the shared tail of POST /kv/import and
        park-resume (which reads its blob from the durable spool
        instead of the wire)."""
        r = blob.header.get("request") or {}
        with self._lock:
            if self._fatal is not None:
                raise RuntimeError(self._fatal)
            if self._closed.is_set():
                raise RuntimeError("server closed")
            g = self._g
            if self._recovering or g.dead:
                self._m.rejects.labels(reason="recovering",
                                       tenant="").inc()
                raise ServerUnavailable(
                    "server recovering from an engine fault; retry",
                    http_status=503, retry_after=retry_after(3.0, 8.0),
                )
            if self._draining:
                self._m.rejects.labels(reason="draining",
                                       tenant="").inc()
                raise ServerUnavailable(
                    "server draining: not admitting migrations; retry "
                    "elsewhere",
                    http_status=503, retry_after=retry_after(1.0, 4.0),
                )
            if (self.max_pending is not None
                    and len(self._pending) >= self.max_pending):
                self._m.rejects.labels(reason="overloaded",
                                       tenant="").inc()
                raise ServerUnavailable(
                    f"server overloaded: {len(self._pending)} requests "
                    f"pending (max_pending={self.max_pending})",
                    http_status=429, retry_after=retry_after(1.0, 3.0),
                )
            stale = self._adoptions.pop(tid, None)
            if stale is not None and not stale[0].event.is_set():
                # A re-run of the same migration (the tier retried
                # after a lost ack): the prior import is now orphaned
                # — cancel it instead of letting it decode to
                # completion unadopted, pinning pool blocks.
                g.submit_q.put((stale[0].rid, None, 0, None, None,
                                None))
            rid = next(self._ids)
            trace = self._m.trace(trace_id=tid, recorder=self._recorder)
            stop = r.get("stop")
            holdback = (max((len(s) for s in stop), default=0)
                        if stop else 0)
            p = _Pending(rid, stream=True, holdback=holdback,
                         trace=trace)
            trace.record("admit", rid=rid, src="server",
                         kind="kv-import",
                         prompt_len=len(r.get("tokens") or ()),
                         pending=len(self._pending) + 1)
            if blob.header.get("complete"):
                # The request finished at its prefill (max_new=1,
                # instant EOS, stop match): settle now — no engine, no
                # pool, nothing to decode.
                trace.prefill_start()
                trace.first_token()
                p.result = list(r.get("out") or ())
                p.lps = r.get("lps") or None
                p.plp = r.get("plp")
                if r.get("tlp") is not None:
                    p.tlp = [(list(ids), list(vals))
                             for ids, vals in r["tlp"]]
                trace.finish(len(p.result))
                p.finish()
                self._adoptions[tid] = (p, time.monotonic())
                self._m.migrations.labels(outcome="import").inc()
                return {"imported": True, "migration_id": tid,
                        "complete": True, "trace_id": tid}
            self._pending[rid] = p
            self._adoptions[tid] = (p, time.monotonic())
            ack = _ImportAck()
            g.submit_q.put((
                rid, np.asarray(r.get("tokens") or [], np.int32),
                int(r.get("max_new") or 1), stop,
                {"_kv_import": (blob, ack, tid)}, None,
            ))
        if not ack.event.wait(timeout=60.0):
            raise ServerUnavailable(
                "kv import not processed in time",
                http_status=503, retry_after=retry_after(1.0, 3.0),
            )
        if ack.error is not None:
            if ack.retryable:
                raise ServerUnavailable(
                    ack.error, http_status=503,
                    retry_after=retry_after(1.0, 3.0),
                )
            raise ValueError(ack.error)
        return {"imported": True, "migration_id": tid,
                "slot": ack.slot, "complete": False, "trace_id": tid}

    # ---- KV fabric surface (directory feed / seed / push / park) ----

    def prefix_manifest(self, since: int = -1) -> Dict[str, Any]:
        """GET /kv/prefixes: the backend's prefix-cache manifest for
        the tier's directory. Read handler-side while the scheduler
        mutates the registry, so a torn iteration (RuntimeError from a
        resized dict) just retries; after a few collisions it reports
        "unchanged" — the directory is a routing hint fed on every
        sweep, not a ledger, so the next poll catches up."""
        for _ in range(3):
            try:
                return self.engine.cache_backend.prefix_manifest(since)
            except RuntimeError:
                continue
        return {"supported": True, "version": since, "unchanged": True}

    def seed_kv(self, body: bytes,
                trace_ctx: Optional[Tuple[str, int]] = None
                ) -> Dict[str, Any]:
        """POST /kv/seed: adopt a prefix-seed blob into the prefix
        registry. Integrity failures (crc, truncation) refuse at
        deserialize with the registry untouched; the seed itself runs
        on the scheduler thread and never evicts live state."""
        try:
            blob = disagg.MigrationBlob.deserialize(bytes(body))
        except ValueError:
            self._m.fabric_seed_rejects.labels(reason="corrupt").inc()
            raise
        tid = blob.header.get("trace_id") or (
            trace_ctx[0] if trace_ctx is not None else new_trace_id()
        )
        with self._lock:
            if self._fatal is not None:
                raise RuntimeError(self._fatal)
            if self._closed.is_set():
                raise RuntimeError("server closed")
            g = self._g
            if self._recovering or g.dead:
                raise ServerUnavailable(
                    "server recovering from an engine fault; retry",
                    http_status=503, retry_after=retry_after(3.0, 8.0),
                )
            if self._draining:
                raise ServerUnavailable(
                    "server draining: not adopting seeds",
                    http_status=503, retry_after=retry_after(1.0, 4.0),
                )
            ack = _ImportAck()
            g.submit_q.put((
                next(self._ids), np.zeros(0, np.int32), 0, None,
                {"_kv_seed": (blob, ack, tid)}, None,
            ))
        if not ack.event.wait(timeout=60.0):
            raise ServerUnavailable(
                "kv seed not processed in time",
                http_status=503, retry_after=retry_after(1.0, 3.0),
            )
        if ack.error is not None:
            if ack.retryable:
                raise ServerUnavailable(
                    ack.error, http_status=503,
                    retry_after=retry_after(1.0, 3.0),
                )
            raise ValueError(ack.error)
        return {"seeded": ack.slot, "trace_id": tid}

    def push_chain(self, payload: dict,
                   trace_ctx: Optional[Tuple[str, int]] = None
                   ) -> Dict[str, Any]:
        """POST /kv/push {"chain": <tip hex>, "target": <url>}: export
        the cached chain ending at `chain` and ship it to `target`'s
        /kv/seed — the leg the tier's replication planner drives
        against a holder replica. The scheduler thread only pays the
        device pull; this handler thread owns serialize + HTTP."""
        tid = (trace_ctx[0] if trace_ctx is not None
               else new_trace_id())
        tip_hex = payload.get("chain")
        target = payload.get("target")
        if not isinstance(tip_hex, str) or not tip_hex:
            raise ValueError(
                'kv push needs "chain": the chain tip hash (hex)'
            )
        if not isinstance(target, str) or "://" not in target:
            raise ValueError(
                'kv push needs "target": the receiving replica base URL'
            )
        try:
            tip = bytes.fromhex(tip_hex)
        except ValueError:
            raise ValueError(f"bad chain hash {tip_hex!r}")
        with self._lock:
            if self._fatal is not None:
                raise RuntimeError(self._fatal)
            if self._closed.is_set():
                raise RuntimeError("server closed")
            g = self._g
            if self._recovering or g.dead:
                raise ServerUnavailable(
                    "server recovering from an engine fault; retry",
                    http_status=503, retry_after=retry_after(3.0, 8.0),
                )
            ack = _ImportAck()
            g.submit_q.put((
                next(self._ids), np.zeros(0, np.int32), 0, None,
                {"_kv_export_chain": (tip, ack, tid)}, None,
            ))
        if not ack.event.wait(timeout=60.0):
            raise ServerUnavailable(
                "chain export not processed in time",
                http_status=503, retry_after=retry_after(1.0, 3.0),
            )
        if ack.error is not None:
            if ack.retryable:
                raise ServerUnavailable(
                    ack.error, http_status=503,
                    retry_after=retry_after(1.0, 3.0),
                )
            raise ValueError(ack.error)
        blob = ack.slot  # the export ack carries the blob
        data = blob.serialize()
        headers = {"Content-Type": "application/octet-stream",
                   TRACE_HEADER: format_trace_header(tid, 0)}
        t0 = time.monotonic()
        try:
            req = urllib.request.Request(
                target.rstrip("/") + "/kv/seed", data=data,
                headers=headers,
            )
            with urllib.request.urlopen(req, timeout=30.0) as resp:
                body = json.loads(resp.read() or b"{}")
        except Exception as e:  # noqa: BLE001 — one retryable leg
            raise ServerUnavailable(
                f"could not deliver seed to {target}: "
                f"{type(e).__name__}: {e}",
                http_status=503, retry_after=retry_after(1.0, 3.0),
            )
        dt = time.monotonic() - t0
        self._m.kv_transfer_seconds.observe(dt, exemplar=tid)
        self._m.kv_transfer_bytes.observe(float(len(data)),
                                          exemplar=tid)
        if self._recorder is not None:
            self._recorder.record(
                tid, "kv-push", chain=tip_hex[:12], target=target,
                bytes=len(data), seeded=body.get("seeded"),
                transfer_s=round(dt, 6),
            )
        return {"pushed": True, "bytes": len(data),
                "seeded": body.get("seeded"),
                "transfer_s": round(dt, 6), "trace_id": tid}

    def _handle_resume(self, payload: dict,
                       trace_ctx: Optional[Tuple[str, int]] = None
                       ) -> dict:
        """Native resume request ({"resume": <park id>}): read the
        parked blob back from the durable spool (crc-verified), import
        it like a migration, and attach exactly like an adopt — so a
        parked session continues on ANY replica that mounts the park
        directory, byte-identical to never having been parked."""
        if self._park is None:
            raise ValueError(
                '"resume" needs serve --park-dir on this replica'
            )
        park_id = str(payload.get("resume"))
        try:
            blob = self._park.get(park_id)
        except KeyError:
            self._m.fabric_resumed.labels(outcome="missing").inc()
            raise ValueError(
                f"unknown park id {park_id!r} (never parked, trimmed "
                "by the size cap, or quarantined)"
            )
        except ValueError as e:
            # Torn/corrupt spool file: quarantined by the store so the
            # next retry does not re-read the same bad sectors. Loud —
            # a server fault, not a bad request.
            self._m.fabric_resumed.labels(outcome="torn").inc()
            raise RuntimeError(
                f"parked blob {park_id!r} failed integrity read-back "
                f"and was quarantined: {e}"
            )
        self._import_blob(blob, park_id)
        self._m.fabric_resumed.labels(outcome="ok").inc()
        if self._recorder is not None:
            self._recorder.record(
                trace_ctx[0] if trace_ctx is not None else None,
                "fabric-resume", park_id=park_id,
                complete=bool(blob.header.get("complete")),
            )
        sub = {k: v for k, v in payload.items() if k != "resume"}
        sub["adopt"] = park_id
        return self._handle_adopt(sub, trace_ctx=trace_ctx)

    def _handle_migrate(self, payload: dict,
                        trace_ctx: Optional[Tuple[str, int]] = None
                        ) -> dict:
        """Native prefill-only request ({"prefill_only": true,
        "migrate_to": <decode URL>}): prefill, freeze, export, push —
        answers with the migration ack once the decode replica holds
        the KV. The tier's disaggregated path drives this as leg 1."""
        target = payload.get("migrate_to")
        if payload.get("park"):
            # Park leg: same prefill/freeze/export path, but the blob
            # lands in the durable spool instead of a decode replica.
            if target is not None:
                raise ValueError(
                    "park and migrate_to are mutually exclusive (a "
                    "parked blob has no decode target yet)"
                )
            if self._park is None:
                raise ValueError(
                    '"park" needs serve --park-dir on this replica'
                )
            target = "park:" + new_trace_id()
        elif not isinstance(target, str) or "://" not in target:
            raise ValueError(
                'prefill_only needs "migrate_to": the decode replica '
                'base URL (or "park": true with serve --park-dir)'
            )
        for key in ("stream", "num_beams", "adopt"):
            if payload.get(key):
                raise ValueError(
                    f"{key} does not compose with prefill_only"
                )
        try:
            n = int(payload.get("n", 1) or 1)
            best_of = int(payload.get("best_of", n) or n)
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad n/best_of: {e}")
        if n != 1 or best_of != 1:
            raise ValueError(
                "n/best_of > 1 do not compose with prefill_only "
                "(fan-out is tier-side)"
            )
        if payload.get("constraint") is not None:
            raise ValueError(
                "constraint does not compose with prefill_only (the "
                "compiled DFA table does not migrate)"
            )
        tokens, max_new, stop, samp = self._parse(payload)
        deadline = self._deadline(payload.get("timeout"))
        p = self._submit(tokens, max_new, stop,
                         {**samp, "_migrate": target}, stream=False,
                         deadline=deadline, trace_ctx=trace_ctx)
        try:
            self._await(p, deadline)
        except TimeoutError:
            self._cancel(p)
            raise
        return dict(p.result)

    def _pop_adoption(self, payload: dict) -> _Pending:
        mid = str(payload.get("adopt"))
        with self._lock:
            ent = self._adoptions.pop(mid, None)
        if ent is None:
            # Retryable by contract: the tier re-runs the full
            # prefill->migrate path on a fresh pair (a 4xx here would
            # read as permanent and fail the client).
            raise ServerUnavailable(
                f"unknown migration id {mid!r} (never imported, "
                "expired, or already adopted); re-run the migration",
                http_status=503, retry_after=retry_after(1.0, 3.0),
            )
        return ent[0]

    def _handle_adopt(self, payload: dict,
                      trace_ctx: Optional[Tuple[str, int]] = None
                      ) -> dict:
        """Native adopt request ({"adopt": <migration id>}): attach to
        an imported request and answer exactly like a local /generate
        would — the disaggregated path's leg 2, byte-identical to
        monolithic serving."""
        want_lps = self._check_logprobs(payload)
        tlk = self._check_top_logprobs(payload, want_lps)
        p = self._pop_adoption(payload)
        deadline = self._deadline(payload.get("timeout"))
        try:
            self._await(p, deadline)
        except TimeoutError:
            self._cancel(p)
            raise
        result = self._format_completion(p.result, p.lps, want_lps,
                                         plp=p.plp, tlp=p.tlp, tlk=tlk)
        result["trace_id"] = (trace_ctx[0] if trace_ctx is not None
                              else p.trace.trace_id)
        return result

    def _adopt_stream(self, payload: dict,
                      trace_ctx: Tuple[str, int]):
        """Streaming adopt: the imported request's chunk queue drains
        as ndjson deltas, then the same final record a local stream
        would end with."""
        want_lps = self._check_logprobs(payload)
        tlk = self._check_top_logprobs(payload, want_lps)
        p = self._pop_adoption(payload)
        timeout = payload.get("timeout")
        tid = trace_ctx[0]
        finished = False
        try:
            while True:
                try:
                    chunk = p.chunks.get(timeout=timeout)
                except queue.Empty:
                    raise TimeoutError("request timed out mid-stream")
                if chunk is None:
                    break
                yield {"tokens": chunk, "trace_id": tid}
            if p.error is not None:
                self._raise(p)
            finished = True
            out = p.result
            final: Dict[str, Any] = {"done": True, "tokens": out,
                                     "trace_id": tid}
            if want_lps:
                final["logprobs"] = p.lps
            if tlk and p.tlp is not None:
                final["top_logprobs"] = self._render_tlp(p.tlp, tlk)
            if p.plp is not None:
                final["prompt_logprobs"] = _render_plp(p.plp)
            if self.tokenizer is not None:
                final["text"] = self.tokenizer.decode(out)
            yield final
        finally:
            if not finished:
                self._cancel(p)

    def _tool_context(self, payload: dict):
        """Validate `tools`/`tool_choice` on a native payload and
        return the ToolContext (None when the request declares no
        tools). The caller compiles ctx.pattern through the DFA cache
        and parses the finished output back into tool_calls. The
        native surface takes the OpenAI tool shapes verbatim — the
        chat facade forwards them here — but does NOT render tools
        into the prompt: a native caller owns its prompt."""
        from shellac_tpu.inference.tools import parse_payload_tools

        ctx = parse_payload_tools(payload)
        if ctx is None:
            return None
        if self.tokenizer is None:
            raise ValueError(
                "tools need a server-side tokenizer (the tool grammar "
                "compiles against token strings)"
            )
        if payload.get("constraint") is not None:
            raise ValueError(
                "tools do not compose with an explicit constraint "
                "(the tool grammar IS the request's constraint)"
            )
        return ctx

    def _tool_constraint(self, samp: dict, tool_ctx) -> None:
        if tool_ctx is not None and tool_ctx.pattern is not None:
            samp["constraint"] = self._compile_constraint(
                {"regex": tool_ctx.pattern}
            )

    def _tool_outcome(self, text: str, calls) -> None:
        # The grammar's free-text branch can never START with '<'
        # (entering the sentinel commits to the tool branch), so any
        # unparsed '<'-prefixed output — including a budget cut inside
        # the sentinel itself — is a truncated call, not free text.
        outcome = ("call" if calls is not None
                   else "truncated" if text.startswith("<")
                   else "text")
        self._m.tool_requests.labels(outcome=outcome).inc()

    def handle(self, payload: dict,
               trace_ctx: Optional[Tuple[str, int]] = None,
               tenant: Optional[str] = None) -> dict:
        # One trace id for the whole request, fan-out included: resolve
        # it here so every sub-submit (and the response echo) agrees.
        if trace_ctx is None:
            trace_ctx = (new_trace_id(), 0)
        tool_ctx = self._tool_context(payload)
        if payload.get("resume") is not None:
            if tool_ctx is not None:
                raise ValueError("tools do not compose with resume")
            return self._handle_resume(payload, trace_ctx=trace_ctx)
        if payload.get("prefill_only"):
            if tool_ctx is not None:
                raise ValueError(
                    "tools do not compose with prefill_only (tool "
                    "grammar state does not migrate)"
                )
            result = self._handle_migrate(payload, trace_ctx=trace_ctx)
            result["trace_id"] = trace_ctx[0]
            return result
        if payload.get("adopt") is not None:
            if tool_ctx is not None:
                raise ValueError("tools do not compose with adopt")
            return self._handle_adopt(payload, trace_ctx=trace_ctx)
        if payload.get("num_beams") is not None:
            if tool_ctx is not None:
                raise ValueError(
                    "tools do not compose with num_beams (a beam is a "
                    "ranked whole sequence, not an assistant turn)"
                )
            result = self._handle_beam(payload, trace_ctx=trace_ctx,
                                       tenant=tenant)
            result["trace_id"] = trace_ctx[0]
            return result
        tokens, max_new, stop, samp = self._parse(payload)
        self._tool_constraint(samp, tool_ctx)
        want_lps = self._check_logprobs(payload)
        tlk = self._check_top_logprobs(payload, want_lps)
        n, best_of = self._parse_n(payload, samp)
        if n == 1 and best_of == 1:
            out, lps, plp, tlp = self.generate(
                tokens, max_new, timeout=payload.get("timeout"), stop=stop,
                return_logprobs=True, trace_ctx=trace_ctx, tenant=tenant,
                **samp,
            )
            result = self._format_completion(
                out, lps, want_lps, plp=plp, tlp=tlp, tlk=tlk,
                tool_ctx=tool_ctx,
            )
            result["trace_id"] = trace_ctx[0]
            return result
        # Parallel sampling: best_of independent completions share the
        # slot batch (and, on a paged+prefix engine, their prompt KV);
        # the n best by mean token logprob come back as "choices". The
        # prompt is identical across the fan-out, so prompt logprobs
        # (echo) are computed ONCE, on the first sub-request only.
        rest_samp = {k: v for k, v in samp.items()
                     if k != "prompt_logprobs"}
        # One overall deadline for the whole fan-out — not a fresh
        # clock per completion — shared with the scheduler so unstarted
        # siblings shed once it passes.
        deadline = self._deadline(payload.get("timeout"))
        pendings = []
        try:
            for i in range(best_of):
                pendings.append(self._submit(
                    tokens, max_new, stop,
                    samp if i == 0 else rest_samp, stream=False,
                    deadline=deadline, trace_ctx=trace_ctx,
                    tenant=tenant,
                ))
        except RuntimeError:
            # Admission cap (or a fault) hit mid-fan-out: release the
            # siblings already submitted before surfacing the refusal.
            for p in pendings:
                self._cancel(p)
            raise
        choices = []
        plp = None
        try:
            for p in pendings:
                self._await(p, deadline)
                choices.append((p.result, p.lps, p.tlp))
                if p.plp is not None:
                    plp = p.plp
        except (TimeoutError, ValueError, RuntimeError):
            # Don't strand the rest: unfinished siblings would keep
            # occupying slots generating tokens nobody will read.
            for p in pendings:
                self._cancel(p)
            raise
        if best_of > n:
            # Rank by mean logprob (length-normalized); engine logprobs
            # are guaranteed on because _parse_n requires the flag. A
            # completion emptied by a stop match ranks last, not first
            # (an empty mean would otherwise score a perfect 0.0).
            def score(c):
                return (sum(c[1]) / len(c[1])) if c[1] else float("-inf")

            choices.sort(key=score, reverse=True)
        result: Dict[str, Any] = {"choices": [
            self._format_completion(out, lps, want_lps, tlp=tlp, tlk=tlk,
                                    tool_ctx=tool_ctx)
            for out, lps, tlp in choices[:n]
        ]}
        if plp is not None:
            result["prompt_logprobs"] = _render_plp(plp)
        result["trace_id"] = trace_ctx[0]
        return result

    def _format_completion(self, out, lps, want_lps,
                           plp=None, tlp=None, tlk=0,
                           tool_ctx=None) -> Dict[str, Any]:
        result: Dict[str, Any] = {"tokens": out}
        if want_lps:
            result["logprobs"] = lps
        if tlk and tlp is not None:
            result["top_logprobs"] = self._render_tlp(tlp, tlk)
        if plp is not None:
            result["prompt_logprobs"] = _render_plp(plp)
        if self.tokenizer is not None:
            result["text"] = self.tokenizer.decode(out)
            if tool_ctx is not None and tool_ctx.pattern is not None:
                from shellac_tpu.inference.tools import parse_tool_calls

                content, calls = parse_tool_calls(
                    result["text"], tool_ctx.mode
                )
                self._tool_outcome(result["text"], calls)
                if calls is not None:
                    result["tool_calls"] = calls
                else:
                    # Free text (auto) or a length-truncated call:
                    # honest content, never a fabricated call.
                    result["content"] = content
        return result

    def _parse_n(self, payload: dict, samp: dict):
        """Validate n (completions returned) and best_of (sampled)."""
        try:
            n = int(payload.get("n", 1))
            best_of = int(payload.get("best_of", n))
        except (TypeError, ValueError) as e:
            raise ValueError(f"bad n/best_of: {e}")
        if n < 1 or best_of < n:
            raise ValueError(f"need best_of >= n >= 1, got n={n}, "
                             f"best_of={best_of}")
        cap = max(4 * getattr(self.engine, "n_slots", 8), 16)
        if best_of > cap:
            raise ValueError(
                f"best_of={best_of} exceeds this server's cap of {cap} "
                "(4x slot count): one request would monopolize the "
                "engine for every other client"
            )
        if best_of == 1:
            return n, best_of
        temp = samp.get("temperature",
                        getattr(self.engine, "_defaults", {}).get(
                            "temperature", 0.0))
        if temp == 0.0:
            raise ValueError(
                "n/best_of > 1 with greedy sampling would return "
                "identical completions; set a temperature"
            )
        if best_of > n and not getattr(self.engine, "logprobs", False):
            raise ValueError(
                "best_of > n ranks completions by logprob; start the "
                "server with logprobs enabled (serve --logprobs)"
            )
        return n, best_of

    def handle_stream(self, payload: dict,
                      trace_ctx: Optional[Tuple[str, int]] = None,
                      tenant: Optional[str] = None):
        """Yield response dicts for a streaming request: delta lines
        {"tokens": [...]}, then {"done": true, "tokens", "text"?,
        "logprobs"?}. Every record carries the request's `trace_id`,
        so a stream that fails after its 200 is attributable from the
        client's capture alone. Logprobs (when requested) arrive on
        the final record only. Parse errors raise before the first
        yield (clean HTTP 400)."""
        if trace_ctx is None:
            trace_ctx = (new_trace_id(), 0)
        if payload.get("num_beams") is not None:
            raise ValueError(
                "num_beams does not compose with streaming (beams are "
                "ranked whole sequences; request them unstreamed)"
            )
        if payload.get("prefill_only"):
            raise ValueError(
                "prefill_only does not compose with streaming (the "
                "migration ack is a single JSON object)"
            )
        if payload.get("adopt") is not None:
            if payload.get("tools"):
                raise ValueError("tools do not compose with adopt")
            yield from self._adopt_stream(payload, trace_ctx)
            return
        tool_ctx = self._tool_context(payload)
        tokens, max_new, stop, samp = self._parse(payload)
        self._tool_constraint(samp, tool_ctx)
        want_lps = self._check_logprobs(payload)
        tlk = self._check_top_logprobs(payload, want_lps)
        n, best_of = self._parse_n(payload, samp)
        if n != 1 or best_of != 1:
            raise ValueError("streaming does not support n/best_of > 1")
        # Tool-enabled streams carry, besides the raw token deltas, a
        # `tool_stream` field with incremental OpenAI-shaped
        # tool_calls deltas / decided free-text content — produced by
        # ONE scanner so SSE, ndjson, and the non-streamed parse
        # cannot disagree. Stop-sequence holdback already guarantees
        # the deltas never overrun the final (trimmed) output.
        scanner = None
        streamed: list = []
        if tool_ctx is not None and tool_ctx.pattern is not None:
            from shellac_tpu.inference.tools import (
                ToolCallStreamParser,
                events_to_stream,
                safe_stream_text,
            )

            scanner = ToolCallStreamParser(tool_ctx.mode)
        stream = self.generate_stream(
            tokens, max_new, timeout=payload.get("timeout"), stop=stop,
            return_logprobs=True, trace_ctx=trace_ctx, tenant=tenant,
            **samp,
        )
        tid = trace_ctx[0]
        for kind, val in stream:
            if kind == "delta":
                rec: Dict[str, Any] = {"tokens": val, "trace_id": tid}
                if scanner is not None:
                    streamed.extend(val)
                    ts = events_to_stream(scanner.feed(safe_stream_text(
                        self.tokenizer.decode(streamed)
                    )))
                    if ts is not None:
                        rec["tool_stream"] = ts
                yield rec
            else:
                out, lps, plp, tlp = val
                final: Dict[str, Any] = {"done": True, "tokens": out,
                                         "trace_id": tid}
                if want_lps:
                    final["logprobs"] = lps
                if tlk and tlp is not None:
                    final["top_logprobs"] = self._render_tlp(tlp, tlk)
                if plp is not None:
                    final["prompt_logprobs"] = _render_plp(plp)
                if self.tokenizer is not None:
                    final["text"] = self.tokenizer.decode(out)
                    if scanner is not None:
                        # The authoritative text (stop-trimmed) settles
                        # the scan: tail events ride the final record,
                        # plus the COMPLETE parsed call list.
                        ts = events_to_stream(scanner.feed(final["text"]))
                        if ts is not None:
                            final["tool_stream"] = ts
                        calls = scanner.result()
                        self._tool_outcome(final["text"], calls)
                        if calls is not None:
                            final["tool_calls"] = calls
                yield final

    def _prompt_lp_capable(self) -> bool:
        eng = self.engine
        if not hasattr(eng, "finished_prompt_logprobs"):
            return False
        # Paged AND speculative engines score prompts now (the spec
        # engine's target prefill runs the same scoring forwards); the
        # one remaining hole is the prefix cache — a cache hit skips
        # exactly the scoring forward passes.
        return (getattr(eng, "_scores_prompts", True)
                and not getattr(eng, "prefix_cache", False))

    # ---- OpenAI-compatible façade -----------------------------------

    def handle_openai(self, payload: dict, chat: bool,
                      trace_ctx: Optional[Tuple[str, int]] = None,
                      tenant: Optional[str] = None) -> dict:
        from shellac_tpu.inference.openai_api import (
            chat_to_native,
            completion_response,
            completion_to_native,
        )

        # trace_ctx passes straight through to handle(), which mints
        # on None — no need to duplicate the fallback here.
        # OpenAI requests may carry the tenant as the `user` field;
        # an explicit x-shellac-tenant header wins.
        if tenant is None and payload.get("user"):
            tenant = str(payload["user"])
        native = (chat_to_native(payload, self.tokenizer) if chat
                  else completion_to_native(payload, self.tokenizer))
        echo = bool(native.pop("echo", False))
        if native.get("prompt_logprobs") and not self._prompt_lp_capable():
            raise ValueError(
                "echo with logprobs is unavailable on this server: the "
                "engine cannot score prompts (a prefix-cached prefill "
                "skips the scoring forwards)"
            )
        tokens = self._parse(native)[0]
        # Hand handle() the ids so the prompt is not tokenized twice.
        native.pop("text", None)
        native["tokens"] = [int(t) for t in tokens]
        prompt_tokens = len(tokens)
        max_new = int(native.get("max_new", 32))
        result = self.handle(native, trace_ctx=trace_ctx,
                             tenant=tenant)
        return completion_response(
            result, model=self.model_name, prompt_tokens=prompt_tokens,
            max_new=max_new, tokenizer=self.tokenizer, chat=chat,
            echo=echo, prompt_ids=[int(t) for t in tokens],
        )

    def handle_openai_stream(self, payload: dict, chat: bool,
                             trace_ctx: Optional[Tuple[str, int]] = None,
                             tenant: Optional[str] = None):
        """Yield OpenAI SSE chunk objects (the HTTP layer frames them
        as `data:` lines and appends [DONE]). Each chunk carries the
        request's `trace_id` alongside the OpenAI fields — unknown
        keys are ignored by SDKs, and a severed stream stays
        attributable from the client's capture."""
        from shellac_tpu.inference.openai_api import (
            StreamTranslator,
            chat_to_native,
            completion_to_native,
        )

        if trace_ctx is None:
            trace_ctx = (new_trace_id(), 0)
        if tenant is None and payload.get("user"):
            tenant = str(payload["user"])
        native = (chat_to_native(payload, self.tokenizer) if chat
                  else completion_to_native(payload, self.tokenizer))
        if native.pop("echo", False):
            raise ValueError(
                "echo does not compose with streaming (the prompt is "
                "known to the client; request it unstreamed)"
            )
        native.pop("prompt_logprobs", None)
        max_new = int(native.get("max_new", 32))
        translator = StreamTranslator(
            model=self.model_name, tokenizer=self.tokenizer, chat=chat,
            # Tool-enabled chat streams translate the server's
            # tool_stream scan, not the raw token text (the one
            # scanner keeps SSE and ndjson surfaces in agreement).
            tool_mode=bool(native.get("tools"))
            and native.get("tool_choice") != "none",
        )
        for record in self.handle_stream(native, trace_ctx=trace_ctx,
                                         tenant=tenant):
            for chunk in translator.feed(record, max_new):
                chunk["trace_id"] = trace_ctx[0]
                yield chunk

    def close(self):
        if self._spool is not None:
            # After the spool closes, late recorder events fall back
            # to append-and-reopen inside EventSpool; closing here
            # just releases the handle on the orderly path.
            self._spool.close()
        if self._push_pool is not None:
            # In-flight pushes settle their pendings or are failed by
            # the sweep below; new pushes cannot start (closed).
            self._push_pool.shutdown(wait=False)
        with self._lock:
            self._closed.set()
            g = self._g
            g.stop.set()
        g.thread.join(timeout=2)
        with self._lock:
            # Whatever is still pending will never finish (the
            # scheduler delivered its last results before exiting, or
            # is wedged): fail the requests loudly NOW instead of
            # leaving blocked generate() callers waiting out their
            # full timeout. Racing a final in-flight delivery is
            # benign — whoever pops the pending first settles it.
            self._fail_pending_locked(
                "server closed before the request completed"
            )
        if getattr(g.engine, "is_primary", False):
            # Multi-host: the followers must be released with a STOP
            # broadcast, and only after the scheduler thread (the
            # broadcast's other participant on this process) has truly
            # exited — two threads must not broadcast at once, and a
            # slow step can easily outlive the 2s fast path above. Only
            # a thread wedged WELL beyond a step (dead transport) may
            # leave shutdown unsent; at that point the followers'
            # collectives are failing on their own.
            deadline = time.monotonic() + 300
            while g.thread.is_alive() and time.monotonic() < deadline:
                g.thread.join(timeout=5)
            if not g.thread.is_alive():
                g.engine.shutdown()


def make_http_server(server: InferenceServer, host: str = "127.0.0.1",
                     port: int = 0) -> ThreadingHTTPServer:
    from shellac_tpu.inference.openai_api import stream_error_payload

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):  # quiet
            pass

        def _send(self, code: int, obj: dict, headers: dict = None):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _send_unavailable(self, e: "ServerUnavailable",
                              openai: bool = False,
                              trace_id: Optional[str] = None):
            err = ({"error": {"message": str(e),
                              "type": "overloaded_error"}}
                   if openai else {"error": str(e)})
            headers = {
                "Retry-After": str(max(1, int(round(e.retry_after)))),
            }
            if trace_id is not None:
                headers[REQUEST_ID_HEADER] = trace_id
            self._send(e.http_status, err, headers=headers)

        def do_GET(self):
            # Error responses carry the trace id too (adopted from the
            # header or minted): a rejected request is exactly the one
            # its sender wants to look up in the recorder.
            rid_hdr = {REQUEST_ID_HEADER:
                       adopt_trace(self.headers.get(TRACE_HEADER))[0]}
            if self.path == "/v1/models":
                self._send(200, {
                    "object": "list",
                    "data": [{
                        "id": server.model_name, "object": "model",
                        "owned_by": "shellac_tpu",
                    }],
                })
            elif self.path == "/health":
                # A real readiness signal: 200 only while serving.
                # Recovering and failed both 503 so load balancers pull
                # the backend; the body says which (and why, when
                # fatal).
                h = server.health()
                self._send(200 if h["ok"] else 503, h,
                           headers=rid_hdr)
            elif self.path == "/stats":
                eng = server.engine
                self._send(200, {
                    **eng.stats,
                    "pending": eng.pending,
                    "slots_busy": sum(r is not None for r in eng._slots),
                    "n_slots": eng.n_slots,
                    "decode_ticks": eng.decode_ticks,
                    # How the window length was chosen ("fixed" |
                    # "auto" pending | "auto-tuned") and whether the
                    # decode loop runs the two-deep overlapped
                    # dispatch pipeline — the tier's load scoring
                    # reads these alongside the host-overhead
                    # histogram at /metrics.
                    "decode_ticks_source": getattr(
                        eng, "decode_ticks_source", "fixed"),
                    "overlap_decode": bool(
                        getattr(eng, "overlap_decode", False)),
                    # The admission-side twins: is prefill dispatch
                    # overlapped, what chunk size is live, and how it
                    # was chosen ("fixed" | "auto" pending |
                    # "auto-tuned") — the stats dict already mirrors
                    # overlap_prefill/prefill_chunk numerically at
                    # /metrics (shellac_engine_*).
                    "overlap_prefill": bool(
                        getattr(eng, "overlap_prefill", False)),
                    "prefill_chunk_source": getattr(
                        eng, "prefill_chunk_source", "fixed"),
                    # Supervisor state: /stats stays 200 through an
                    # outage (scrapers keep collecting); readiness
                    # lives at /health.
                    "role": server.role,
                    "status": server.status,
                    "fatal": server._fatal,
                    "restarts": server.restarts,
                    "generation": server._g.gen,
                    "shed": server.shed,
                    "uptime_s": round(server.uptime_s, 3),
                    # Multi-tenant QoS: per-tenant admission counters,
                    # per-class queue depths, parked preemption state
                    # (empty object when untenanted).
                    "qos": server.qos_snapshot(),
                    # p50/p90/p99 latency digests from the obs
                    # histograms (null until requests have completed).
                    **server.latency_summary(),
                })
            elif self.path == "/metrics":
                if not server.metrics_enabled:
                    self._send(404, {
                        "error": "metrics disabled (serve --no-metrics)",
                    }, headers=rid_hdr)
                    return
                # Prometheus text exposition. Like /stats, this stays
                # 200 through an outage so scrapers keep collecting.
                body = server.metrics_text().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type",
                    "text/plain; version=0.0.4; charset=utf-8",
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif self.path.startswith("/kv/prefixes"):
                # KV-fabric directory feed: what this replica's prefix
                # cache holds (delta-polled — ?since=<version> answers
                # "unchanged" when nothing moved).
                qs = urllib.parse.urlsplit(self.path).query
                try:
                    since = int(urllib.parse.parse_qs(qs).get(
                        "since", ["-1"])[0])
                except ValueError:
                    self._send(400, {"error": "bad since value"},
                               headers=rid_hdr)
                    return
                self._send(200, server.prefix_manifest(since),
                           headers=rid_hdr)
            elif self.path.startswith("/debug/"):
                # Introspection surface: the in-flight table and per-
                # trace timelines. 404 wholesale under --no-debug (the
                # --no-metrics pattern: absent, not forbidden).
                if not server.debug_enabled:
                    self._send(404, {
                        "error": "debug endpoints disabled "
                                 "(serve --no-debug)",
                    }, headers=rid_hdr)
                elif self.path == "/debug/requests":
                    self._send(200, server.debug_requests())
                elif self.path == "/debug/incidents":
                    if server.incidents is None:
                        self._send(400, {
                            "error": "incident bundles need serve "
                                     "--incident-dir",
                        }, headers=rid_hdr)
                    else:
                        self._send(200, {
                            "incidents": server.incidents.list(),
                            "dir": server.incidents.incident_dir,
                            "last": server.incidents.last,
                        })
                elif self.path.startswith("/debug/incident/"):
                    bid = self.path[len("/debug/incident/"):]
                    out = (server.incidents.load(bid)
                           if server.incidents is not None else None)
                    if out is None:
                        self._send(404, {
                            "error": f"no incident bundle {bid!r} "
                                     "(unknown id, evicted by "
                                     "retention, or no --incident-dir)",
                        }, headers=rid_hdr)
                    else:
                        self._send(200, out)
                elif self.path.startswith("/debug/request/"):
                    tid = self.path[len("/debug/request/"):]
                    out = server.debug_request(tid)
                    if out is None:
                        self._send(404, {
                            "error": f"no recorded events for trace id "
                                     f"{tid!r} (finished long ago, "
                                     "evicted from the ring, or never "
                                     "seen)",
                        }, headers=rid_hdr)
                    else:
                        self._send(200, out)
                else:
                    self._send(404, {"error": "not found"},
                               headers=rid_hdr)
            else:
                self._send(404, {"error": "not found"},
                           headers=rid_hdr)

        def _handle_profile(self, rid_hdr: dict):
            """POST /debug/profile?seconds=N — on-demand jax.profiler
            capture on the live engine."""
            if not server.debug_enabled:
                self._send(404, {"error": "debug endpoints disabled "
                                          "(serve --no-debug)"},
                           headers=rid_hdr)
                return
            qs = urllib.parse.urlsplit(self.path).query
            params = urllib.parse.parse_qs(qs)
            try:
                seconds = float(params.get("seconds", ["2"])[0])
                out = server.profile(seconds)
                if params.get("report", ["0"])[0] not in ("0", ""):
                    # ?report=1: inline the trace-report summary of
                    # the capture just taken — one round trip from
                    # "profile it" to "where did the time go".
                    try:
                        out["report"] = server._analyze_capture(
                            out["trace_dir"])
                    except Exception as e:  # noqa: BLE001 — the
                        # capture itself succeeded; report best-effort
                        out["report"] = {
                            "error": f"{type(e).__name__}: {e}"}
                self._send(200, out, headers=rid_hdr)
            except ProfileInProgress as e:
                self._send(409, {"error": str(e)}, headers=rid_hdr)
            except ValueError as e:
                self._send(400, {"error": str(e)}, headers=rid_hdr)
            except RuntimeError as e:
                # A profiler backend fault (another process-global
                # trace active, unwritable dir) is a server error.
                self._send(500, {"error": str(e)}, headers=rid_hdr)

        def _handle_incident(self, tctx: Tuple[str, int],
                             rid_hdr: dict):
            """POST /debug/incident — manual evidence bundle."""
            if not server.debug_enabled:
                self._send(404, {"error": "debug endpoints disabled "
                                          "(serve --no-debug)"},
                           headers=rid_hdr)
                return
            if server.incidents is None:
                self._send(400, {"error": "incident bundles need "
                                          "serve --incident-dir"},
                           headers=rid_hdr)
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("payload must be a JSON object")
                seconds = payload.get("seconds")
                if seconds is not None:
                    seconds = float(seconds)
            except (TypeError, ValueError) as e:
                # TypeError: a list/dict "seconds" — a malformed
                # payload must 400, not drop the connection.
                self._send(400, {"error": f"bad incident payload: {e}"},
                           headers=rid_hdr)
                return
            detail = {"via": "POST /debug/incident"}
            if payload.get("note") is not None:
                detail["note"] = str(payload["note"])[:1024]
            errors_before = server.incidents.write_errors
            bid = server.trigger_incident(
                "manual", trace_id=tctx[0], detail=detail,
                # Explicit opt-in only: a bare manual trigger must
                # not inherit the wedge-path auto-capture default.
                capture_seconds=seconds if seconds is not None else 0,
            )
            if bid is None:
                if server.incidents.write_errors > errors_before:
                    # The bundle write FAILED (full disk, bad
                    # permissions): a server fault, not backpressure
                    # — a 429 would tell the operator to wait for a
                    # disk that will never empty itself.
                    self._send(500, {
                        "error": "incident bundle write failed "
                                 "(check --incident-dir "
                                 "permissions/space)",
                    }, headers=rid_hdr)
                    return
                # The sliding-window limiter dropped it: backpressure,
                # not failure — same contract as admission 429.
                self._send(429, {
                    "error": "incident trigger rate-limited "
                             "(--incident-rate per --incident-window)",
                }, headers={
                    **rid_hdr,
                    "Retry-After": str(max(1, int(round(
                        retry_after(2.0, 6.0))))),
                })
                return
            self._send(200, {
                "incident": bid,
                "manifest": (server.incidents.load(bid) or {}).get(
                    "manifest"),
            }, headers=rid_hdr)

        def _stream(self, payload: dict, tctx: Tuple[str, int],
                    tenant: Optional[str] = None):
            # Newline-delimited JSON, no Content-Length: the connection
            # closes at the end of the stream (HTTP/1.0 semantics of
            # BaseHTTPRequestHandler — no keep-alive to preserve).
            lines = server.handle_stream(payload, trace_ctx=tctx,
                                         tenant=tenant)
            try:
                first = next(lines)  # parse errors surface before 200
            except StopIteration:
                first = None
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header(REQUEST_ID_HEADER, tctx[0])
            self.end_headers()
            rest = (
                itertools.chain([first], lines) if first is not None else lines
            )
            try:
                for obj in rest:
                    self.wfile.write((json.dumps(obj) + "\n").encode())
                    self.wfile.flush()
            except OSError:
                # Client hung up mid-stream (the normal cancel path);
                # nothing to report and nobody left to report it to.
                pass
            except (ValueError, TimeoutError, RuntimeError) as e:
                # Headers are gone; report in-band and close. The
                # record carries type + retryable + the trace id so a
                # fronting router that has not yet forwarded bytes can
                # classify it, and the client's capture alone
                # identifies the request server-side.
                try:
                    self.wfile.write(
                        (json.dumps(stream_error_payload(
                            e, trace_id=tctx[0])) + "\n")
                        .encode()
                    )
                except OSError:
                    pass

        def _stream_sse(self, payload: dict, chat: bool,
                        tctx: Tuple[str, int],
                        tenant: Optional[str] = None):
            # OpenAI Server-Sent Events framing: one `data: <json>` line
            # per chunk, blank-line separated, closed by `data: [DONE]`.
            chunks = server.handle_openai_stream(payload, chat,
                                                 trace_ctx=tctx,
                                                 tenant=tenant)
            try:
                first = next(chunks, None)  # errors surface before 200
            except (ValueError, TimeoutError) as e:
                self._send(400, {"error": {"message": str(e),
                                           "type": "invalid_request_error"}},
                           headers={REQUEST_ID_HEADER: tctx[0]})
                return
            except ServerUnavailable as e:
                self._send_unavailable(e, openai=True, trace_id=tctx[0])
                return
            except RuntimeError as e:
                # Scheduler death is a server fault, not a bad request.
                self._send(500, {"error": {"message": str(e),
                                           "type": "server_error"}},
                           headers={REQUEST_ID_HEADER: tctx[0]})
                return
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Cache-Control", "no-cache")
            self.send_header(REQUEST_ID_HEADER, tctx[0])
            self.end_headers()
            rest = (
                itertools.chain([first], chunks) if first is not None
                else chunks
            )
            try:
                for obj in rest:
                    self.wfile.write(
                        f"data: {json.dumps(obj)}\n\n".encode()
                    )
                    self.wfile.flush()
                self.wfile.write(b"data: [DONE]\n\n")
            except OSError:
                pass  # client hung up: the engine-side cancel fires
            except (ValueError, TimeoutError, RuntimeError) as e:
                try:
                    payload = stream_error_payload(e, trace_id=tctx[0])
                    self.wfile.write(
                        f"data: {json.dumps(payload)}\n\n".encode()
                    )
                except OSError:
                    pass

        def do_POST(self):
            # Trace adoption: the tier (or any front-end) hands the
            # request its distributed trace id + attempt number via
            # x-shellac-trace; direct callers get a freshly minted id.
            # Every response echoes it as x-request-id.
            tctx = adopt_trace(self.headers.get(TRACE_HEADER))
            rid_hdr = {REQUEST_ID_HEADER: tctx[0]}
            # Tenant identity: the explicit header wins everywhere;
            # OpenAI routes additionally fall back to the `user`
            # payload field inside the facade.
            tenant = (self.headers.get(TENANT_HEADER) or "").strip() or None
            if self.path.startswith("/debug/profile"):
                self._handle_profile(rid_hdr)
                return
            if self.path == "/debug/incident":
                # Manual incident trigger: snapshot the evidence NOW.
                # Body (optional): {"note": ..., "seconds": N} — N
                # arms a bounded profiler capture into the bundle.
                self._handle_incident(tctx, rid_hdr)
                return
            if self.path == "/kv/import":
                # Binary KV-migration blob from a prefill replica —
                # handled before the JSON parse below.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    out = server.import_kv(self.rfile.read(n),
                                           trace_ctx=tctx)
                    self._send(200, out, headers=rid_hdr)
                except ValueError as e:
                    self._send(400, {"error": str(e)}, headers=rid_hdr)
                except ServerUnavailable as e:
                    self._send_unavailable(e, trace_id=tctx[0])
                except RuntimeError as e:
                    self._send(500, {"error": str(e)}, headers=rid_hdr)
                return
            if self.path == "/kv/seed":
                # Binary prefix-seed blob (fabric replication) —
                # binary like /kv/import, handled before the JSON
                # parse below.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    out = server.seed_kv(self.rfile.read(n),
                                         trace_ctx=tctx)
                    self._send(200, out, headers=rid_hdr)
                except ValueError as e:
                    self._send(400, {"error": str(e)}, headers=rid_hdr)
                except ServerUnavailable as e:
                    self._send_unavailable(e, trace_id=tctx[0])
                except RuntimeError as e:
                    self._send(500, {"error": str(e)}, headers=rid_hdr)
                return
            if self.path == "/kv/push":
                # Replication order from the tier: export one cached
                # chain and ship it to a peer's /kv/seed.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    if not isinstance(payload, dict):
                        raise ValueError(
                            "kv push payload must be a JSON object"
                        )
                    out = server.push_chain(payload, trace_ctx=tctx)
                    self._send(200, out, headers=rid_hdr)
                except ValueError as e:
                    self._send(400, {"error": str(e)}, headers=rid_hdr)
                except ServerUnavailable as e:
                    self._send_unavailable(e, trace_id=tctx[0])
                except RuntimeError as e:
                    self._send(500, {"error": str(e)}, headers=rid_hdr)
                return
            if self.path == "/drain":
                # Admin surface: begin (or with {"resume": true},
                # cancel) a graceful drain. Returns the health
                # snapshot; callers poll /health until `pending`
                # reaches 0, then stop the replica — zero drops.
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                except ValueError:
                    payload = None
                if not isinstance(payload, dict):
                    self._send(400, {"error": "bad drain payload"},
                               headers=rid_hdr)
                    return
                self._send(200, server.resume_admission()
                           if payload.get("resume") else server.drain(),
                           headers=rid_hdr)
                return
            openai_routes = {
                "/v1/completions": False,
                "/v1/chat/completions": True,
            }
            if self.path not in ("/generate", *openai_routes):
                self._send(404, {"error": "not found"}, headers=rid_hdr)
                return
            try:
                n = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(n) or b"{}")
                if self.path in openai_routes:
                    chat = openai_routes[self.path]
                    if payload.get("stream"):
                        self._stream_sse(payload, chat, tctx,
                                         tenant=tenant)
                    else:
                        self._send(200,
                                   server.handle_openai(
                                       payload, chat, trace_ctx=tctx,
                                       tenant=tenant),
                                   headers=rid_hdr)
                elif payload.get("stream"):
                    self._stream(payload, tctx, tenant=tenant)
                else:
                    self._send(200,
                               server.handle(payload, trace_ctx=tctx,
                                             tenant=tenant),
                               headers=rid_hdr)
            except (ValueError, TimeoutError) as e:
                err = {"error": str(e)}
                if self.path in openai_routes:
                    # OpenAI clients expect the nested error shape.
                    err = {"error": {"message": str(e),
                                     "type": "invalid_request_error"}}
                self._send(400, err, headers=rid_hdr)
            except ServerUnavailable as e:
                # Backpressure, not failure: 429 (over the pending cap)
                # or 503 (recovering), each with Retry-After — before
                # the RuntimeError arm, which would misreport it as an
                # opaque 500.
                self._send_unavailable(e, openai=self.path in openai_routes,
                                       trace_id=tctx[0])
            except RuntimeError as e:
                self._send(500, {"error": str(e)}, headers=rid_hdr)

    return ThreadingHTTPServer((host, port), Handler)


def serve(cfg: ModelConfig, params, *, host="127.0.0.1", port=8000,
          tokenizer=None, **engine_kw):
    """Blocking entry point used by the CLI. The start-up line names
    the device the engine runs on and what it holds once the engine is
    placed; SIGINT stops the server cleanly."""
    from shellac_tpu.utils.metrics import device_info, device_memory

    srv = InferenceServer(cfg, params, tokenizer=tokenizer, **engine_kw)
    httpd = make_http_server(srv, host, port)
    print(json.dumps({
        "serving": f"http://{host}:{httpd.server_address[1]}",
        "device": device_info(),
        "memory": device_memory(),
    }), flush=True)
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        httpd.server_close()
        srv.close()
