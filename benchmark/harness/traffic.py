"""The one general traffic generator. A mix is a data file; this reads it.

Lengths are a FIXED stratified multiset: the quantiles of the mix's law at n
evenly spaced probabilities. The seed permutes the order (within strata, so
that every few consecutive requests are a balanced draw), pairs prompts with
outputs and draws the token ids; it never changes the multiset, so every seed
offers the same work in another order. Arrival gaps are made the same way.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _probs(n):
    return [(i + 0.5) / n for i in range(n)]


def lengths(law):
    """The multiset of a length law, ascending, as ints."""
    lo, hi, n = law["min"], law["max"], law["n"]
    if law["law"] == "log_uniform":
        vals = [lo * (hi / lo) ** p for p in _probs(n)]
    elif law["law"] == "log_normal":
        nd = NormalDist(math.log(law["median"]), law["sigma"])
        a, b = nd.cdf(math.log(lo)), nd.cdf(math.log(hi))
        vals = [math.exp(nd.inv_cdf(a + (b - a) * p)) for p in _probs(n)]
    else:
        raise SystemExit(f"unknown length law {law['law']!r}")
    return [int(min(hi, max(lo, round(v)))) for v in vals]


def gaps(law, rate):
    """Inter-arrival gaps (seconds): stratified quantiles of Exp(rate),
    rescaled so that their mean is exactly 1 / rate."""
    if law["law"] != "stratified_exponential":
        raise SystemExit(f"unknown arrival law {law['law']!r}")
    q = np.array([-math.log(1.0 - p) for p in _probs(law["n"])])
    return q / q.mean() / rate


def max_footprint(traffic):
    """Largest prompt + output + 1 the mix can ask for (the engine's rule)."""
    return traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"] + 1


class Mix:
    """Requests of one run: ``next()`` gives (rid, prompt ids, output length)."""

    def __init__(self, traffic, vocab, seed):
        self.prompts = lengths(traffic["prompt_tokens"])
        self.outputs = lengths(traffic["output_tokens"])
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed), 0x5E11AC])
        self._queue = []
        self._n = 0
        arr = traffic.get("arrivals")
        self._gaps = gaps(arr, traffic["rate_rps"]) if arr else None
        self._gap_queue = []
        self._t = 0.0

    def _strata_order(self, values, k=8):
        """One pass through the multiset in an order that keeps every run of
        k consecutive draws balanced: the sorted values are cut into k strata
        and each group of k takes one value from every stratum, shuffled. A
        window of any length then holds nearly the same work, whatever the
        seed; a plain permutation leaves whole seconds of difference between
        two windows that happen to get more of the long prompts."""
        v = sorted(values)
        k = min(k, len(v))
        strata = [list(self.rng.permutation(v[i * len(v) // k:(i + 1) * len(v) // k]))
                  for i in range(k)]
        out = []
        while any(strata):
            group = [s.pop() for s in strata if s]
            out.extend(int(x) for x in self.rng.permutation(group))
        return out

    def _refill(self):
        p, o = self._strata_order(self.prompts), self._strata_order(self.outputs)
        self._queue.extend(zip(p, o))

    def next(self):
        if not self._queue:
            self._refill()
        plen, olen = self._queue.pop(0)
        ids = self.rng.integers(0, self.vocab, size=plen, dtype=np.int32)
        rid = self._n
        self._n += 1
        return rid, ids, olen

    def next_due(self):
        """Seconds from the schedule's start at which the next request is due."""
        if not self._gap_queue:
            self._gap_queue.extend(self.rng.permutation(self._gaps).tolist())
        self._t += self._gap_queue.pop(0)
        return self._t


def warmup_prompt_lengths(traffic, prefill_chunk, lo=16):
    """Prompt lengths that reach every prefill program the mix can reach,
    from the mix's BOUNDS and the engine's bucketing (powers of two from 16),
    never from what one seed happened to draw."""
    def bucket(n):
        b = lo
        while b < n:
            b *= 2
        return b

    pmin, pmax = traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]
    whole_hi = pmax if prefill_chunk is None else min(pmax, prefill_chunk)
    out = []
    b = bucket(pmin)
    while b <= bucket(whole_hi):
        out.append(max(pmin, min(b, whole_hi)))
        b *= 2
    if prefill_chunk is not None and pmax > prefill_chunk:
        b = lo                               # the last chunk's buckets
        while b <= prefill_chunk:
            if prefill_chunk + b <= pmax:
                out.append(prefill_chunk + b)
            b *= 2
    return sorted(set(out))


def warmup_requests(traffic, serving, max_len):
    """(prompt length, max_new, run to its end?) for every program the mix
    can reach: each prefill bucket (run to the end, so the decode window and
    the sampler compile too), and each number of cache pages a request of the
    mix can reserve at admission (the paged backend's table update is one
    small program per page count; these are cancelled after their first
    step, their only purpose being the admission)."""
    ticks, page = int(serving["decode_ticks"]), int(serving["block_size"])
    out = [(n, 2 * ticks + 2, True)
           for n in warmup_prompt_lengths(traffic, serving.get("prefill_chunk"))]
    pmin = traffic["prompt_tokens"]["min"]
    lo = pmin + traffic["output_tokens"]["min"] + 1
    for pages in range(-(-lo // page), -(-max_footprint(traffic) // page) + 1):
        max_new = min(pages * page, max_len) - pmin - 1
        if max_new >= 1:
            out.append((pmin, max_new, False))
    return out
