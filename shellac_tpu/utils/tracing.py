"""Wall-clock step timing for the training loops.

The profiler's host spans are written by one place, the engine's step
recorder (shellac_tpu/obs/trace.py: StepTrace).
"""

from __future__ import annotations

import time
from typing import Optional


class StepTimer:
    """Wall-clock step timing with warmup discard and EMA throughput.

    Synchronization is the caller's job (fetch a scalar from the step
    output before calling tick(); on some platforms block_until_ready
    does not synchronize).
    """

    def __init__(self, tokens_per_step: Optional[int] = None, warmup: int = 2,
                 histogram=None):
        self.tokens_per_step = tokens_per_step
        self.warmup = warmup
        self._count = 0
        self._last: Optional[float] = None
        self._ema: Optional[float] = None
        # Optional obs.Histogram: post-warmup step times are observed
        # into it, so the step-time DISTRIBUTION (not just the EMA)
        # reaches the shared registry / Prometheus exposition.
        self._hist = histogram

    def tick(self) -> Optional[float]:
        """Mark a step boundary; returns the step time (or None in warmup)."""
        now = time.perf_counter()
        if self._last is None:
            self._last = now
            return None
        dt = now - self._last
        self._last = now
        self._count += 1
        if self._count <= self.warmup:
            return None
        self._ema = dt if self._ema is None else 0.9 * self._ema + 0.1 * dt
        if self._hist is not None:
            self._hist.observe(dt)
        return dt

    @property
    def step_time(self) -> Optional[float]:
        return self._ema

    @property
    def tokens_per_sec(self) -> Optional[float]:
        if self._ema is None or not self.tokens_per_step:
            return None
        return self.tokens_per_step / self._ema
