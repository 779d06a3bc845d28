"""Serving-tier chaos harness: deterministic fault injection at the
wire and process level.

The training twin (`training/chaos.py`) injects faults into the data
stream and the checkpoint directory; this one injects them between the
router and its replicas, and into the replica processes themselves —
the failure classes a multi-replica tier actually meets:

  - `ChaosProxy`: a byte-level TCP proxy slotted between the router
    and one replica, with switchable modes — `pass_through`, `refuse`
    (connection reset: replica process gone), `unavailable` (canned
    503 + Retry-After: replica recovering), `stall` (accept, read,
    never answer: wedged replica), `cut_stream(n)` (forward the
    response but sever it after n bytes: replica killed mid-stream).
    Because the proxy sits on the wire, what the chaos tests prove is
    the ROUTER's public failure contract — ejection, retry, loud
    mid-stream failure — not anything about replica internals.
  - `ReplicaProc`: a real `python -m shellac_tpu serve` subprocess
    (the CLI path operators run), so a SIGKILL is a true process
    death: sockets reset, no goodbye, exactly what a preempted node
    looks like to the tier.
  - `LoadGenerator`: sustained traffic with per-request deadlines,
    counting outcomes — the background load the acceptance scenarios
    (kill under load, drain under load) assert "zero failures"
    against. Two drive modes: the original CLOSED loop (`concurrency`
    workers back-to-back — throughput-coupled, the server slowing
    down slows the offered load) and an OPEN loop (`schedule=` or
    `rate=` — arrival-driven, the production shape where traffic does
    not care that the server is slow; `run()` plays a deterministic
    (arrival_s, payload) schedule, e.g. from
    `workload.WorkloadModel.payload_schedule()`). Payloads may carry
    reserved client-side keys — `tenant` (sent as the
    x-shellac-tenant header, never in the body), `kind` (a label for
    the tally), `stream` + `cancel_after_deltas` (read the NDJSON
    stream and optionally sever it mid-flight: the client-cancel
    path) — and the tally splits per tenant; with `capture=True`
    every request also leaves a result row (latency, TTFT, outcome,
    trace id) the scenario gate computes SLIs from. `seed=` makes
    closed-loop payload draws deterministic. The shape helpers
    (`zipf_tenant_mix`, `abusive_burst_mix`, `interactive_batch_mix`)
    build multi-tenant payload lists with the traffic skews real
    fleets meet: Zipf tenant popularity, one abusive tenant at N×
    everyone else, and an interactive-vs-batch class split.

Injectors never reach into `TierRouter` or `InferenceServer`
internals; docs/serving_tier.md documents the contract they exercise.
"""

from __future__ import annotations

import json
import os
import queue
import signal
import socket
import socketserver
import subprocess
import sys
import threading
import time
import random
import urllib.error
import urllib.request
from typing import Dict, List, Optional

from shellac_tpu.inference.qos import TENANT_HEADER


class ChaosProxy:
    """TCP proxy with switchable failure modes between a client (the
    tier router) and one upstream replica.

    Mode changes apply to NEW connections; `cut_stream` additionally
    severs the connection that crosses the byte budget mid-flight.
    Thread-safe; `url` is what you hand the router as the replica
    address."""

    PASS = "pass"
    REFUSE = "refuse"
    UNAVAILABLE = "unavailable"
    STALL = "stall"
    CUT = "cut"

    def __init__(self, upstream_host: str, upstream_port: int,
                 host: str = "127.0.0.1"):
        self.upstream = (upstream_host, int(upstream_port))
        self._mode = self.PASS
        self._cut_after = 0
        self._retry_after = 1
        self._lock = threading.Lock()
        self._stall_release = threading.Event()
        proxy = self

        class _Conn(socketserver.BaseRequestHandler):
            def handle(self):
                proxy._handle(self.request)

        class _Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = _Server((host, 0), _Conn)
        self.port = self._server.server_address[1]
        self.url = f"http://{host}:{self.port}"
        threading.Thread(target=self._server.serve_forever,
                         daemon=True).start()

    # ---- mode switches ----------------------------------------------

    def pass_through(self):
        with self._lock:
            self._mode = self.PASS

    def refuse(self):
        """New connections are reset immediately — the wire shape of a
        dead process / closed port."""
        with self._lock:
            self._mode = self.REFUSE

    def unavailable(self, retry_after: int = 1):
        """Answer every request with a canned 503 + Retry-After — the
        wire shape of a replica mid-recovery."""
        with self._lock:
            self._mode = self.UNAVAILABLE
            self._retry_after = retry_after

    def stall(self):
        """Accept and read, never answer — the wire shape of a wedged
        replica. `release_stalls()` unblocks held connections (tests
        must release before teardown so no handler thread leaks)."""
        with self._lock:
            self._mode = self.STALL
            self._stall_release.clear()

    def cut_stream(self, after_bytes: int):
        """Forward the response but sever the connection once
        `after_bytes` response bytes have crossed — a replica killed
        mid-stream, after the client already saw tokens."""
        with self._lock:
            self._mode = self.CUT
            self._cut_after = int(after_bytes)

    def release_stalls(self):
        self._stall_release.set()

    def close(self):
        self._stall_release.set()
        self._server.shutdown()
        self._server.server_close()

    # ---- the wire ----------------------------------------------------

    def _handle(self, client: socket.socket) -> None:
        with self._lock:
            mode = self._mode
            cut_after = self._cut_after
        try:
            if mode == self.REFUSE:
                # RST instead of FIN: a crash, not a polite close.
                client.setsockopt(
                    socket.SOL_SOCKET, socket.SO_LINGER,
                    b"\x01\x00\x00\x00\x00\x00\x00\x00",
                )
                client.close()
                return
            if mode == self.UNAVAILABLE:
                client.settimeout(5.0)
                try:
                    client.recv(65536)  # drain the request politely
                except OSError:
                    pass
                body = json.dumps(
                    {"error": "chaos: replica unavailable"}
                ).encode()
                client.sendall(
                    b"HTTP/1.0 503 Service Unavailable\r\n"
                    b"Content-Type: application/json\r\n"
                    + f"Retry-After: {self._retry_after}\r\n".encode()
                    + f"Content-Length: {len(body)}\r\n\r\n".encode()
                    + body
                )
                client.close()
                return
            if mode == self.STALL:
                client.settimeout(1.0)
                try:
                    client.recv(65536)
                except OSError:
                    pass
                # Hold the connection open, answering nothing, until
                # released or the far side gives up.
                self._stall_release.wait(120)
                client.close()
                return
            # PASS / CUT: full duplex byte pump.
            up = socket.create_connection(self.upstream, timeout=10)
            budget = cut_after if mode == self.CUT else None
            t = threading.Thread(
                target=self._pump, args=(client, up, None), daemon=True
            )
            t.start()
            self._pump(up, client, budget)
            t.join(timeout=10)
        except OSError:
            pass
        finally:
            try:
                client.close()
            except OSError:
                pass

    @staticmethod
    def _pump(src: socket.socket, dst: socket.socket,
              budget: Optional[int]) -> None:
        """Copy src -> dst until EOF; with a byte budget, sever BOTH
        sockets once it is spent (response direction only)."""
        sent = 0
        try:
            while True:
                data = src.recv(4096)
                if not data:
                    break
                if budget is not None and sent + len(data) > budget:
                    dst.sendall(data[: max(0, budget - sent)])
                    raise ConnectionAbortedError("chaos cut")
                dst.sendall(data)
                sent += len(data)
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass


class ReplicaProc:
    """One real replica: `python -m shellac_tpu serve` as a subprocess.

    Binds port 0 and reports the actual address from the CLI's
    `{"serving": ...}` startup line, so parallel replicas never
    collide. `kill()` is SIGKILL — no drain, no goodbye — and
    `drain()` posts the graceful path for contrast.

    A CPU-only harness: replicas start with JAX_PLATFORMS=cpu unless
    the caller's environment says otherwise, so several can run beside
    a parent that holds jax. Nothing measured through it is a device
    number, and on a chip machine its replicas would not see the chip
    (one process per chip) — do not build a benchmark on it."""

    def __init__(self, *, model: str = "tiny",
                 config_path: Optional[str] = None, seed: int = 0,
                 slots: int = 2, max_len: int = 96,
                 extra_args: Optional[List[str]] = None,
                 startup_timeout: float = 120.0):
        # decode_ticks pinned to 1: chaos replicas measure failure
        # semantics, not throughput, and the serve default ("auto")
        # would spend replica startup on a tuning sweep. Overlapped
        # dispatch keeps its serve default, so the chaos scenarios
        # exercise SIGKILL/drain against the overlapped pipeline.
        # extra_args may override either (argparse: last flag wins).
        cmd = [sys.executable, "-m", "shellac_tpu", "serve",
               "--port", "0", "--slots", str(slots),
               "--max-len", str(max_len), "--seed", str(seed),
               "--decode-ticks", "1",
               "--temperature", "0.0", "--tokenizer", "byte"]
        cmd += (["--config", config_path] if config_path
                else ["--model", model])
        cmd += list(extra_args or ())
        env = dict(os.environ)
        env.setdefault("JAX_PLATFORMS", "cpu")
        self.proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            env=env, text=True,
        )
        self.url: Optional[str] = None
        # Read stdout on a side thread: a subprocess that wedges
        # during startup and prints NOTHING must hit startup_timeout,
        # not park this constructor in a blocking readline forever.
        lines: "queue.Queue[str]" = queue.Queue()
        stdout = self.proc.stdout

        def _reader():
            for ln in stdout:
                lines.put(ln)

        threading.Thread(target=_reader, daemon=True).start()
        deadline = time.monotonic() + startup_timeout
        line = ""
        while time.monotonic() < deadline:
            try:
                line = lines.get(timeout=0.5)
            except queue.Empty:
                if self.proc.poll() is not None:
                    raise RuntimeError(
                        f"replica exited rc={self.proc.returncode} "
                        "before serving"
                    )
                continue
            try:
                self.url = json.loads(line)["serving"]
                break
            except (ValueError, KeyError):
                continue
        if self.url is None:
            self.kill()
            raise TimeoutError(
                f"replica never reported serving (last line {line!r})"
            )

    def wait_ready(self, timeout: float = 120.0) -> None:
        """Block until /health answers 200 (first request may compile)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                with urllib.request.urlopen(
                        self.url + "/health", timeout=5) as r:
                    if r.status == 200:
                        return
            except (OSError, urllib.error.URLError):
                pass
            time.sleep(0.2)
        raise TimeoutError(f"replica {self.url} never became ready")

    def drain(self, resume: bool = False) -> dict:
        req = urllib.request.Request(
            self.url + "/drain",
            data=json.dumps({"resume": resume} if resume else {}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read())

    def kill(self) -> None:
        """SIGKILL: the unplanned death. Idempotent."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGKILL)
        self.proc.wait(timeout=30)
        if self.proc.stdout:
            self.proc.stdout.close()

    def terminate(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.kill()
                return
        if self.proc.stdout:
            self.proc.stdout.close()


class LoadGenerator:
    """Background load through the tier, in two drive modes.

    CLOSED (the default, the original behavior): `concurrency`
    threads each issue POSTs back-to-back until stopped — offered
    load couples to server throughput, which is what the chaos
    acceptance scenarios want ("zero failures while a replica was
    killed"). `seed=` makes each worker draw its payload sequence
    from a seeded rng instead of cycling by index, so a multi-shape
    closed run is reproducible.

    OPEN (`schedule=` a sorted [(arrival_s, payload), ...] list, or
    `rate=` + `duration=` for seeded Poisson arrivals over
    `payloads`): a dispatcher fires each request at its arrival
    offset regardless of how the server is doing — the production
    shape an SLO gate must measure under, because a slow server and
    open-loop arrivals is exactly how queues actually build. Arrivals
    never block on in-flight work; past `max_in_flight` the request
    is counted `client_saturated` (the load generator ran out of
    client capacity — loud, never silently re-timed). `run()` plays
    the whole schedule and returns the tally.

    Payloads may carry reserved client-side keys: `tenant` (the
    x-shellac-tenant header), `kind` (tally label only), `stream`
    (read the NDJSON stream; `stream` DOES go to the wire) and
    `cancel_after_deltas` (sever the stream after N delta lines — the
    client-cancel path; tallied `cancelled`). A stream that ends
    without its `{"done": ...}` line is `stream_severed`. With
    `capture=True` each request appends a result row to `.results`:
    arrival/latency/TTFT seconds, outcome, tenant, kind, and the
    trace id from the response's x-request-id header — the raw
    material the scenario gate computes SLIs and violating-trace
    exemplars from."""

    def __init__(self, base_url: str, *, path: str = "/generate",
                 payloads: Optional[List[dict]] = None,
                 concurrency: int = 4, timeout: float = 30.0,
                 schedule: Optional[List] = None,
                 rate: Optional[float] = None,
                 duration: Optional[float] = None,
                 seed: Optional[int] = None,
                 max_in_flight: int = 64,
                 capture: bool = False):
        self.base_url = base_url.rstrip("/")
        self.path = path
        # One payload per worker (cycled): distinct prompts give the
        # workers distinct affinity keys, so load spreads across the
        # tier instead of piling onto one replica's session.
        self.payloads = payloads or [
            {"tokens": [1 + i, 2 + i, 3 + i], "max_new": 4}
            for i in range(max(1, concurrency))
        ]
        self.concurrency = concurrency
        self.timeout = timeout
        self.seed = seed
        self.capture = bool(capture)
        self.max_in_flight = int(max_in_flight)
        if rate is not None and rate <= 0:
            raise ValueError(f"rate must be > 0 (got {rate})")
        if rate is not None and duration is None and schedule is None:
            raise ValueError("open-loop rate= needs duration=")
        if schedule is not None:
            self.schedule = [(float(t), dict(p)) for t, p in schedule]
            self.schedule.sort(key=lambda tp: tp[0])
        elif rate is not None:
            # Seeded Poisson arrivals over the payload list, cycled.
            rng = random.Random(seed if seed is not None else 0)
            self.schedule = []
            t, i = 0.0, 0
            while True:
                t += rng.expovariate(rate)
                if t >= duration:
                    break
                self.schedule.append(
                    (t, dict(self.payloads[i % len(self.payloads)])))
                i += 1
        else:
            self.schedule = None  # closed loop
        self.counts: Dict[str, int] = {}
        # Per-tenant outcome split (only for payloads that carried a
        # `tenant` key): {tenant: {outcome: count}}.
        self.by_tenant: Dict[str, Dict[str, int]] = {}
        self.errors: List[str] = []
        self.results: List[dict] = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._in_flight = threading.Semaphore(self.max_in_flight)

    def _tally(self, key: str, detail: str = "",
               tenant: Optional[str] = None) -> None:
        with self._lock:
            self.counts[key] = self.counts.get(key, 0) + 1
            if tenant is not None:
                per = self.by_tenant.setdefault(tenant, {})
                per[key] = per.get(key, 0) + 1
            if detail and len(self.errors) < 50:
                self.errors.append(detail)

    def _record(self, row: dict) -> None:
        if not self.capture:
            return
        with self._lock:
            self.results.append(row)

    def _one(self, payload: dict, arrival_s: Optional[float] = None
             ) -> None:
        """Issue one request (streaming or not), tally the outcome,
        and capture a result row. `payload` still carries its
        reserved keys; they are stripped here."""
        p = dict(payload)
        tenant = p.pop("tenant", None)
        kind = p.pop("kind", None)
        cancel_after = p.pop("cancel_after_deltas", None)
        stream = bool(p.get("stream"))
        p.setdefault("timeout", self.timeout)
        body = json.dumps(p).encode()
        headers = {"Content-Type": "application/json"}
        if tenant is not None:
            headers[TENANT_HEADER] = tenant
        req = urllib.request.Request(
            self.base_url + self.path, data=body, headers=headers,
        )
        t0 = time.monotonic()
        row = {"arrival_s": arrival_s, "tenant": tenant, "kind": kind,
               "stream": stream, "trace_id": None, "ttft_s": None,
               "latency_s": None, "status": None, "outcome": None}

        def settle(outcome: str, detail: str = "") -> None:
            row["latency_s"] = time.monotonic() - t0
            row["outcome"] = outcome
            self._tally(outcome, detail, tenant=tenant)
            self._record(row)

        try:
            # Read timeout sits above the request deadline so the TIER
            # classifies a blown deadline (504), not the client socket.
            with urllib.request.urlopen(req,
                                        timeout=self.timeout + 15) as r:
                row["status"] = r.status
                row["trace_id"] = r.headers.get("x-request-id")
                if not stream:
                    r.read()
                    settle("ok" if r.status == 200
                           else f"http_{r.status}")
                    return
                # NDJSON stream: each line is a delta until the
                # {"done": ...} record. TTFT = first delta line.
                deltas = 0
                done = False
                for raw in r:
                    if not raw.strip():
                        continue
                    try:
                        obj = json.loads(raw)
                    except ValueError:
                        settle("stream_garbled", raw[:120].decode(
                            errors="replace"))
                        return
                    if obj.get("error"):
                        settle("stream_error", str(obj)[:200])
                        return
                    if obj.get("done"):
                        done = True
                        break
                    deltas += 1
                    if row["ttft_s"] is None:
                        row["ttft_s"] = time.monotonic() - t0
                    if (cancel_after is not None
                            and deltas >= cancel_after):
                        # Client cancel: just stop reading and close
                        # the socket (the `with` does) — the server
                        # sees the hangup and settles `cancelled`.
                        settle("cancelled")
                        return
                settle("ok" if done else "stream_severed")
        except urllib.error.HTTPError as e:
            row["status"] = e.code
            row["trace_id"] = e.headers.get("x-request-id")
            detail = ""
            try:
                detail = e.read().decode(errors="replace")[:200]
            except OSError:
                pass
            settle(f"http_{e.code}", f"{e.code}: {detail}")
        except (OSError, urllib.error.URLError) as e:
            settle("connect_error", repr(e))

    # ---- closed loop -------------------------------------------------

    def _loop(self, idx: int) -> None:
        rng = (random.Random(f"{self.seed}:{idx}")
               if self.seed is not None else None)
        while not self._stop.is_set():
            if rng is not None:
                payload = rng.choice(self.payloads)
            else:
                payload = self.payloads[idx % len(self.payloads)]
            self._one(payload)

    def start(self) -> "LoadGenerator":
        if self.schedule is not None:
            t = threading.Thread(target=self._dispatch, daemon=True)
            t.start()
            self._threads.append(t)
            return self
        for i in range(self.concurrency):
            t = threading.Thread(target=self._loop, args=(i,), daemon=True)
            t.start()
            self._threads.append(t)
        return self

    def stop(self) -> Dict[str, int]:
        """Signal stop, join every worker (each finishes its in-flight
        request), and return the final tally."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=self.timeout + 30)
        with self._lock:
            return dict(self.counts)

    # ---- open loop ---------------------------------------------------

    def _dispatch(self) -> None:
        """Play the schedule: sleep to each arrival offset, fire the
        request on its own thread. Firing never waits on in-flight
        work — that is the open-loop contract."""
        fired: List[threading.Thread] = []
        t0 = time.monotonic()
        for arrival_s, payload in self.schedule:
            if self._stop.is_set():
                break
            delay = t0 + arrival_s - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                break
            if not self._in_flight.acquire(blocking=False):
                self._tally("client_saturated",
                            tenant=payload.get("tenant"))
                self._record({
                    "arrival_s": arrival_s,
                    "tenant": payload.get("tenant"),
                    "kind": payload.get("kind"),
                    "stream": bool(payload.get("stream")),
                    "trace_id": None, "ttft_s": None,
                    "latency_s": None, "status": None,
                    "outcome": "client_saturated",
                })
                continue

            def fire(p=payload, a=arrival_s):
                try:
                    self._one(p, arrival_s=a)
                finally:
                    self._in_flight.release()

            th = threading.Thread(target=fire, daemon=True)
            th.start()
            fired.append(th)
        for th in fired:
            th.join(timeout=self.timeout + 30)

    def run(self) -> Dict[str, int]:
        """Open-loop only: play the whole schedule to completion
        (blocking) and return the tally."""
        if self.schedule is None:
            raise RuntimeError(
                "run() needs an open-loop schedule (schedule= or "
                "rate=+duration=); use start()/stop() for closed loop"
            )
        self.start()
        for t in self._threads:
            t.join()
        with self._lock:
            return dict(self.counts)

    @property
    def total(self) -> int:
        with self._lock:
            return sum(self.counts.values())


# ---- multi-tenant traffic shapes ------------------------------------
# Payload-list builders for LoadGenerator(payloads=...): each entry is
# one worker's steady request, with the reserved `tenant` key naming
# who it bills to. Deterministic (seeded) so a chaos run's tenant mix
# is reproducible run-to-run.

def zipf_tenant_mix(tenants: List[str], concurrency: int,
                    s: float = 1.2, seed: int = 7,
                    max_new: int = 4) -> List[dict]:
    """Zipf tenant popularity: worker i's tenant is drawn with
    P(rank r) ∝ 1/r^s over `tenants` (list order = popularity rank) —
    the heavy-head/long-tail skew real multi-tenant fleets see, where
    one tenant dominates and most barely show up."""
    if not tenants:
        raise ValueError("zipf_tenant_mix needs at least one tenant")
    rng = random.Random(seed)
    weights = [1.0 / (r + 1) ** s for r in range(len(tenants))]
    out = []
    for i in range(max(1, concurrency)):
        t = rng.choices(tenants, weights=weights)[0]
        out.append({"tokens": [1 + i, 2 + i, 3 + i],
                    "max_new": max_new, "tenant": t})
    return out


def abusive_burst_mix(victim: str, abuser: str, concurrency: int,
                      abuse_ratio: int = 10,
                      max_new: int = 4) -> List[dict]:
    """One well-behaved tenant vs one abusive tenant flooding at
    ~abuse_ratio× its worker share — the starvation scenario: the
    assertion is that `victim`'s tally stays clean (zero rejections,
    p99 within SLO) while `abuser` eats 429s."""
    if concurrency < abuse_ratio + 1:
        concurrency = abuse_ratio + 1
    out = []
    for i in range(concurrency):
        t = victim if i % (abuse_ratio + 1) == 0 else abuser
        out.append({"tokens": [1 + i, 2 + i, 3 + i],
                    "max_new": max_new, "tenant": t})
    return out


def interactive_batch_mix(interactive: str, batch: str,
                          concurrency: int,
                          batch_max_new: int = 32) -> List[dict]:
    """Interactive-vs-batch class split: short interactive requests
    interleaved with long-decode batch requests — the mix where
    weighted-fair scheduling and preempt-and-park earn their keep
    (without them, one batch tenant's long decodes monopolize the
    slots and interactive TTFT collapses)."""
    out = []
    for i in range(max(2, concurrency)):
        if i % 2 == 0:
            out.append({"tokens": [1 + i, 2 + i, 3 + i],
                        "max_new": 2, "tenant": interactive})
        else:
            out.append({"tokens": [1 + i, 2 + i, 3 + i, 4 + i,
                                   5 + i, 6 + i],
                        "max_new": batch_max_new, "tenant": batch})
    return out
