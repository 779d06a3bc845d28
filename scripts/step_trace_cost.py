"""What the engine's step recorder costs per span, on the host it runs on.

    JAX_PLATFORMS=cpu python scripts/step_trace_cost.py

Opens and closes spans the way an engine step does (a root, nested
children with attributes, counts, a commit per step) with the registry
on, then off, and prints microseconds per span for both. No capture is
running, so the profiler annotation is its cheap path."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from shellac_tpu.obs import EngineMetrics, Registry  # noqa: E402

SPANS_PER_STEP = 14


def per_span_us(enabled: bool, steps: int = 20000) -> float:
    tr = EngineMetrics(Registry(enabled=enabled)).steps
    t0 = time.perf_counter()
    for _ in range(steps):
        tr.begin_step(occupied=20, windows=1, prefills=0)
        with tr.span("engine.dispatch_window", ticks=8, rows=20):
            pass
        with tr.span("engine.wait_window"):
            pass
        with tr.span("engine.apply_window") as sp:
            with tr.span("cache.release_slot", slot=3):
                pass
            sp.set(tokens=160, finished=1)
        with tr.span("engine.fill"):
            with tr.span("engine.admit", slot=3) as adm:
                adm.set(rid=17, prompt_tokens=512)
                with tr.span("cache.prepare_slot", slot=3):
                    with tr.span("cache.ensure_blocks", slot=3, pages=4):
                        pass
                with tr.span("engine.prefill_dispatch"):
                    tr.count(prefill_tokens=400, prefill_padded_tokens=512)
                    tr.annotate(bucket=512)
            for _ in range(4):
                with tr.span("cache.release_slot", slot=1):
                    pass
        tr.count(tokens_delivered=160, decode_slot_ticks=160,
                 decode_valid_ticks=150)
        tr.end_step(True)
    return 1e6 * (time.perf_counter() - t0) / (steps * SPANS_PER_STEP)


if __name__ == "__main__":
    print(json.dumps({"spans_per_step": SPANS_PER_STEP,
                      "us_per_span_on": round(per_span_us(True), 3),
                      "us_per_span_off": round(per_span_us(False), 3)}))
