"""Closed loop through the engine, as ``cli.py batch`` drives it.

``engine.submit`` and ``engine.step()`` from one thread; the backlog never
empties (a new request for each one that completes, a few beyond the slots
waiting). Tokens are counted as ``step()`` hands them out: after every step
the driver reads how long each resident request's output has become, so a
request in flight at either edge of the window contributes exactly the
tokens it emitted inside. ``stats["tokens_generated"]`` is never read.
"""

from __future__ import annotations

import gc
import time

from benchmark.harness import program
from benchmark.harness.traffic import Mix, warmup_requests


class Counter:
    """Delivered tokens and the work they took, from the slot table and the
    finished list, after each step."""

    def __init__(self, slots_of, token_flops):
        self.slots_of = slots_of
        self.token_flops = token_flops
        self.seen = {}        # rid -> output tokens already counted
        self.plen = {}        # rid -> prompt tokens
        self.new = []         # rids first seen resident in the last step

    def after_step(self, finished):
        """-> (tokens delivered, slots occupied, resident context tokens,
        FLOPs of the delivered tokens)."""
        n = occupied = resident = 0
        flops = 0.0
        self.new = []
        for req in self.slots_of():
            if req is None:
                continue
            occupied += 1
            plen = len(req.tokens)
            if req.rid not in self.plen:
                self.plen[req.rid] = plen
                self.new.append(req.rid)
            have = len(req.out)
            d = have - self.seen.get(req.rid, 0)
            self.seen[req.rid] = have
            resident += plen + have
            if d:
                n += d
                flops += d * self.token_flops(plen + have - d / 2.0)
        for rid, out in finished:
            d = len(out) - self.seen.pop(rid, 0)
            plen = self.plen.pop(rid, 0)
            if d:
                n += d
                flops += d * self.token_flops(plen + len(out) - d / 2.0)
        return n, occupied, resident, flops


def _drain(eng):
    while eng.pending:
        eng.step()


def run(r):
    cell, traffic = r.cell, r.cell["traffic"]
    s = cell["config"]["serving"]
    eng = program.build_engine(cell, r.cfg, r.params, r.seed, for_server=False)
    r.mark("engine")
    n_slots = eng.n_slots
    mix = Mix(traffic, r.cfg.vocab_size, r.seed)
    rng = mix.rng

    # Warm-up: every prefill bucket the mix's bounds reach, the decode
    # window, the first-token sampler (run together to their end), then
    # every page count of an admission (admitted together, then cancelled).
    warm = warmup_requests(traffic, s, program.max_len(cell))
    for to_end in (True, False):
        batch = [(n, m) for n, m, e in warm if e == to_end]
        for lo in range(0, len(batch), n_slots):
            rids = []
            for n, max_new in batch[lo:lo + n_slots]:
                rids.append(("warm", to_end, len(rids) + lo))
                eng.submit(rids[-1], rng.integers(0, r.cfg.vocab_size, size=n), max_new)
            if not to_end:
                eng.step()
                for rid in rids:
                    eng.cancel(rid)
            _drain(eng)
    r.mark("warmup")

    # Ramp: fill the slots, the first wave's outputs cut to a seeded
    # fraction so that completions are staggered from the start.
    live = {}
    lo, hi = traffic["first_wave_cut"]

    def submit(cut=None):
        rid, ids, olen = mix.next()
        if cut is not None:
            olen = max(2, int(round(olen * cut)))
        live[rid] = ids
        eng.submit(rid, ids, olen)

    # the fractions are the same evenly spaced set for every seed, in a
    # seeded order, so that the ramp takes about the same time
    cuts = lo + (hi - lo) * (rng.permutation(n_slots) + 0.5) / n_slots
    for i in range(n_slots + traffic["backlog_beyond_slots"]):
        submit(cuts[i] if i < n_slots else None)
    counter = Counter(lambda: eng._slots, lambda c: r.arch.token_flops(r.hf, c))
    completed = admitted = 0
    while completed < max(1, n_slots // 2) or admitted < n_slots:
        fin = eng.step()
        counter.after_step(fin)
        admitted += len(counter.new)
        for rid, _ in fin:
            live.pop(rid, None)
            completed += 1
            submit()
    r.mark("ramp")

    # Window: opens and closes on a step boundary; the rate is every token
    # delivered between the two over the seconds between the two.
    t0 = r.open_window()
    steps, finished, admits = [], [], []
    while True:
        fin = eng.step()
        now = time.perf_counter()
        n, occ, resident, flops = counter.after_step(fin)
        steps.append({"t": now, "tokens": n, "occupied": occ, "resident": resident,
                      "flops": flops, "kv_util": eng.cache_backend.utilization()})
        admits.extend((now, len(live[rid])) for rid in counter.new)
        for rid, out in fin:
            finished.append((live.pop(rid), list(out)))
            submit()
        r.poll_trace(now)
        if now - t0 >= r.seconds:
            break
    r.close_window()
    window_s = steps[-1]["t"] - t0
    delivered = sum(st["tokens"] for st in steps)
    in_flight = sum(1 for q in eng._slots if q is not None)
    res = {
        "end_to_end": {"serve_tok_s": delivered / window_s},
        "attempted": len(finished) + in_flight, "failed": 0,
        "window_s": window_s, "t0": t0, "steps": steps, "admits": admits,
        "finished": finished, "delivered": delivered,
        "decode_ticks": int(s["decode_ticks"]), "n_slots": n_slots,
    }
    eng.abort_all()
    del eng, counter
    gc.collect()
    return res
