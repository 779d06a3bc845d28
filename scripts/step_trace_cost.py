"""What the engine's step recorder costs per span, on the host it runs on.

    JAX_PLATFORMS=cpu python scripts/step_trace_cost.py

Opens and closes spans the way an engine step does (a root, nested
children with attributes, counts, a commit per step) with the registry
on, then off, and prints microseconds per span for both. No capture is
running, so the profiler annotation is its cheap path. Then the same for a
launch and its landing (a row, a queue entry, `is_ready` and
`block_until_ready` on a small array that IS ready, two counters and a
histogram): what the engine's own device timeline adds to a program it
dispatches, the wait for the device apart (the pull that follows would
have waited as long)."""

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


def per_launch_us(enabled: bool, steps: int = 20000) -> float:
    """One window and two prompt programs a step, landed a step later
    as the engine lands them: all three at the next step's first pull."""
    import jax.numpy as jnp

    tr = EngineMetrics(Registry(enabled=enabled)).steps
    handles = [jnp.zeros((), jnp.int32) + i for i in range(3)]
    for h in handles:
        h.block_until_ready()
    t0 = time.perf_counter()
    for _ in range(steps):
        tr.begin_step(occupied=20, windows=1, prefills=2)
        with tr.span("engine.wait_prefill"):
            tr.land(handles[2], handles)
        with tr.span("engine.dispatch_window", ticks=8, rows=20,
                     launch=tr.next_launch):
            tr.launch("window", "jit__decode_impl", handles[0], ticks=8,
                      rows=20)
        for h in handles[1:]:
            with tr.span("engine.prefill_dispatch", launch=tr.next_launch):
                tr.launch("prefill", "jit__prefill_impl", h, slot=3,
                          bucket=512, tokens=400, offset=0, stalled_rows=19)
        tr.end_step(True)
    spans = 1e6 * (time.perf_counter() - t0) / steps
    # the same spans without the launches, taken off
    tr = EngineMetrics(Registry(enabled=enabled)).steps
    t0 = time.perf_counter()
    for _ in range(steps):
        tr.begin_step(occupied=20, windows=1, prefills=2)
        with tr.span("engine.wait_prefill"):
            pass
        with tr.span("engine.dispatch_window", ticks=8, rows=20):
            pass
        for _ in handles[1:]:
            with tr.span("engine.prefill_dispatch"):
                pass
        tr.end_step(True)
    return (spans - 1e6 * (time.perf_counter() - t0) / steps) / len(handles)


if __name__ == "__main__":
    print(json.dumps({"spans_per_step": SPANS_PER_STEP,
                      "us_per_span_on": round(per_span_us(True), 3),
                      "us_per_span_off": round(per_span_us(False), 3),
                      "us_per_launch_and_landing_on":
                          round(per_launch_us(True), 3),
                      "us_per_launch_and_landing_off":
                          round(per_launch_us(False), 3)}))
