"""Open loop through ``InferenceServer.generate_stream``, in-process.

The scheduler thread and the engine are the program's; there is no socket.
Requests are sent on a schedule fixed by the mix (rate, gaps) whatever the
server does, one short-lived client thread per request as the HTTP front
end would have, and every latency is taken from the time the request was
DUE, so that a stall is charged to every request it delays. The measured
requests are those due inside the window; arrivals go on at the same rate
after it closes until the last measured request has finished, so that none
finishes in an emptying system.
"""

from __future__ import annotations

import gc
import threading
import time

from benchmark.harness import program
from benchmark.harness.stats import percentile
from benchmark.harness.traffic import Mix, warmup_requests


class _Client(threading.Thread):
    def __init__(self, server, rid, ids, olen, due, timeout):
        super().__init__(daemon=True)
        self.server, self.rid, self.ids, self.olen = server, rid, ids, olen
        self.due, self.timeout = due, timeout
        self.sent = self.first = self.last = None
        self.deltas = []          # (time, tokens in the delta)
        self.out = None
        self.error = None
        self.queue_wait = None
        self.tid = "00-%032x-%016x-01" % (rid + 1, rid + 1)

    def run(self):
        self.sent = time.perf_counter()
        try:
            for kind, payload in self.server.generate_stream(
                    self.ids, self.olen, timeout=self.timeout,
                    trace_ctx=(self.tid, 0)):
                now = time.perf_counter()
                if kind == "delta":
                    if self.first is None:
                        self.first = now
                    self.last = now
                    self.deltas.append((now, len(payload)))
                else:
                    self.out = list(payload)
        except Exception as e:  # noqa: BLE001 - a failed request is a datum, not a crash
            self.error = f"{type(e).__name__}: {e}"
        try:
            tl = self.server.debug_request(self.tid) or {}
            for ev in tl.get("events", ()):
                if "queue_wait_s" in ev:
                    self.queue_wait = (self.sent - self.due) + float(ev["queue_wait_s"])
                    break
        except Exception:  # noqa: BLE001 - the recorder is optional evidence
            pass


class _Sampler(threading.Thread):
    """Occupancy, resident context and pool use, read from the engine's slot
    table and cache backend at a fixed period (read-only snapshots)."""

    def __init__(self, eng, period=0.05):
        super().__init__(daemon=True)
        self.eng, self.period = eng, period
        self.rows = []
        self.stop = threading.Event()

    def run(self):
        while not self.stop.wait(self.period):
            occ = res = 0
            for req in list(self.eng._slots):
                if req is not None:
                    occ += 1
                    res += len(req.tokens) + len(req.out)
            self.rows.append({"t": time.perf_counter(), "occupied": occ,
                              "resident": res,
                              "kv_util": self.eng.cache_backend.utilization()})


def _in_flight(clients, t):
    """Requests due by ``t`` that had not delivered their last token by ``t``."""
    return sum(1 for c in clients if c.due <= t and (c.out is None or c.last is None or c.last > t))


def run(r):
    cell, traffic = r.cell, r.cell["traffic"]
    s = cell["config"]["serving"]
    eng = program.build_engine(cell, r.cfg, r.params, r.seed, for_server=True)
    server = program.build_server(r.cfg, r.params, eng)
    r.mark("engine")
    mix = Mix(traffic, r.cfg.vocab_size, r.seed)
    rng = mix.rng
    drain = float(traffic["drain_limit_s"])

    try:
        for n, max_new, to_end in warmup_requests(traffic, s, program.max_len(cell)):
            ids = rng.integers(0, r.cfg.vocab_size, size=n)
            if to_end:
                server.generate(ids, max_new, timeout=1200)
            else:
                gen = server.generate_stream(ids, max_new, timeout=1200)
                next(gen)
                gen.close()        # abandons the stream: the server cancels it
        r.mark("warmup")

        sampler = _Sampler(eng)
        sampler.start()
        lead = float(traffic["lead_in_s"])
        sched0 = time.perf_counter()
        t_open = sched0 + lead
        clients, opened, closed = [], False, False
        t0 = t1 = None
        while True:
            due = sched0 + mix.next_due()
            now = time.perf_counter()
            if not opened and due >= t_open:
                # the window opens on the schedule, between two arrivals
                time.sleep(max(0.0, t_open - now))
                t0 = r.open_window()
                opened = True
                now = time.perf_counter()
            if opened and not closed and due >= t0 + r.seconds:
                time.sleep(max(0.0, t0 + r.seconds - now))
                t1 = r.close_window()
                closed = True
            if closed:
                measured = [c for c in clients if t0 <= c.due < t1]
                if all(not c.is_alive() for c in measured):
                    break
                if due > t1 + drain:
                    break
            while True:
                now = time.perf_counter()
                if now >= due:
                    break
                r.poll_trace(now)
                time.sleep(min(0.02, due - now))
            rid, ids, olen = mix.next()
            c = _Client(server, rid, ids, olen, due, timeout=drain)
            c.start()
            clients.append(c)
        r.mark("drain")
        sampler.stop.set()
        sampler.join()
        measured = [c for c in clients if t0 <= c.due < t1]
        for c in measured:
            c.join(timeout=max(0.0, (c.due + drain) - time.perf_counter()))
    finally:
        server.close()

    inf = float("inf")
    good = lambda c: c.error is None and c.out is not None and not c.is_alive()
    ok = [c for c in measured if good(c)]
    failed = len(measured) - len(ok)
    ttft = [(c.first - c.due) * 1e3 if good(c) else inf for c in measured]
    tpot = [((c.last - c.first) / (len(c.out) - 1)) * 1e3
            if good(c) and len(c.out) > 1 else inf for c in measured]
    late = [(c.sent - c.due) * 1e3 for c in measured if c.sent is not None]
    waits = [c.queue_wait * 1e3 for c in ok if c.queue_wait is not None]

    # Work inside the window, over every request that ran in it, measured
    # or not: tokens at their own context, prompts whose first token fell in.
    tf = lambda ctx: r.arch.token_flops(r.hf, ctx)
    delivered, flops, admits = 0, 0.0, []
    for c in clients:
        have = 0
        for t, n in c.deltas:
            have += n
            if t0 <= t < t1:
                delivered += n
                flops += n * tf(len(c.ids) + have - n / 2.0)
        if c.first is not None and t0 <= c.first < t1:
            admits.append((c.first, len(c.ids)))
    res = {
        "end_to_end": {"ttft_p95_ms": percentile(ttft, 95),
                       "tpot_p95_ms": percentile(tpot, 95)},
        "attempted": len(measured), "failed": failed,
        "window_s": t1 - t0, "t0": t0,
        "steps": [dict(row, tokens=0, flops=0.0) for row in sampler.rows
                  if t0 <= row["t"] < t1],
        "admits": admits, "delivered": delivered, "window_flops": flops,
        "finished": [(c.ids, c.out) for c in ok],
        "decode_ticks": int(s["decode_ticks"]), "n_slots": eng.n_slots,
        "queue_wait_ms": waits,
        "extra": {"ttft_p50_ms": percentile(ttft, 50), "tpot_p50_ms": percentile(tpot, 50),
                  "lateness_p95_ms": percentile(late, 95) if late else None,
                  "completed_tok_s": delivered / (t1 - t0),
                  "ttft_within_2s_share": sum(1 for x in ttft if x <= 2000.0) / max(1, len(ttft)),
                  "in_flight_at_open": _in_flight(clients, t0),
                  "in_flight_at_close": _in_flight(clients, t1),
                  "errors": sorted({c.error for c in measured if c.error})[:3]},
    }
    eng.abort_all()
    del eng, server, sampler
    gc.collect()
    return res
