"""Decode/serving benchmark: Pallas flash-decode vs reference, dense vs
paged, on the continuous-batching engine.

Round-2 shipped the flash-decode kernels (ops/decode_attention.py) with
interpret-mode evidence only; this script produces the hardware numbers.
Two measurements per (cache, impl) variant:

  - steady-state: n_slots requests prefilled to ~ctx tokens, then T
    timed decode ticks with every slot live. Reported as decode
    tokens/s (n_slots tokens per tick).
  - churn: 3*n_slots requests with ragged prompt lengths and small
    max_new budgets drained through the engine, so slots turn over and
    prefill/decode interleave the way a real server runs.

Prints one JSON line per variant plus a "summary" line carrying the
Pallas-vs-ref speedups. Run on the TPU host:

    python scripts/bench_decode.py            # shellac-1b, ctx 2048
    python scripts/bench_decode.py --model tiny --ctx 64   # CPU smoke

The reference repo is empty (SURVEY.md §0): the spec being measured is
ops/decode_attention.py's own claim — blocked streaming beats the
whole-buffer XLA path at serving context lengths.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_engine(cfg, params, *, paged, impl, n_slots, max_len,
                 decode_ticks=1, kv_quant=None, rolling=False,
                 registry=None, overlap=False, overlap_prefill=False,
                 max_prefills_per_step=None, spec_draft=None, gamma=3):
    from shellac_tpu.inference.batching import (
        BatchingEngine,
        PagedBatchingEngine,
    )

    if spec_draft is not None:
        # Speculative serving over the backend registry (spec-dense /
        # spec-paged variants, int8 included): the verify round
        # replaces the decode window, so decode_ticks stays pinned.
        from shellac_tpu.inference.cache import (
            engine_class,
            resolve_backend_name,
        )

        name = resolve_backend_name(None, paged=paged, kv_quant=kv_quant,
                                    rolling_window=rolling)
        dcfg, dparams = spec_draft
        extra = ({"block_size": 128 if kv_quant else 64,
                  "pool_tokens": n_slots * max_len}
                 if paged else {})
        return engine_class(name, speculative=True)(
            cfg, params, dcfg, dparams, gamma=gamma, n_slots=n_slots,
            max_len=max_len, temperature=0.0, attn_impl=impl,
            registry=registry, cache_backend=name, **extra,
        )
    if paged:
        # Page size 64: large enough that the paged kernel's per-page
        # DMA is a real tile (64 x 128), small enough that short
        # requests still share the pool at fine grain. Int8 pools need
        # 128-token pages (inference/cache/paged.py).
        return PagedBatchingEngine(
            cfg, params, n_slots=n_slots, max_len=max_len,
            block_size=128 if kv_quant else 64,
            pool_tokens=n_slots * max_len,
            temperature=0.0, attn_impl=impl, decode_ticks=decode_ticks,
            kv_quant=kv_quant, registry=registry, overlap_decode=overlap,
            overlap_prefill=overlap_prefill,
            max_prefills_per_step=max_prefills_per_step,
        )
    return BatchingEngine(
        cfg, params, n_slots=n_slots, max_len=max_len,
        temperature=0.0, attn_impl=impl, decode_ticks=decode_ticks,
        kv_quant=kv_quant, rolling_window=rolling, registry=registry,
        overlap_decode=overlap, overlap_prefill=overlap_prefill,
        max_prefills_per_step=max_prefills_per_step,
    )


def steady_state(cfg, params, *, paged, impl, n_slots, ctx, max_len,
                 ticks, rng, decode_ticks=1, kv_quant=None,
                 rolling=False, registry=None, overlap=False,
                 spec_draft=None, gamma=3):
    """Decode tokens/s with every slot held live at ~ctx context."""
    eng = build_engine(
        cfg, params, paged=paged, impl=impl, n_slots=n_slots,
        max_len=max_len, decode_ticks=decode_ticks, kv_quant=kv_quant,
        rolling=rolling, registry=registry, overlap=overlap,
        spec_draft=spec_draft, gamma=gamma,
    )
    budget = max_len - ctx - 1
    # Spec rounds emit up to gamma+1 tokens per step (and admission
    # reserves gamma+2 slack past the budget).
    per_step = (gamma + 1) if spec_draft is not None else decode_ticks
    need = (2 + ticks) * per_step + (gamma + 2 if spec_draft else 0)
    if budget < need:
        raise SystemExit(
            f"steady_state: per-slot budget {budget} < "
            f"(2+ticks)*decode_ticks = {need}; slots would drain "
            "mid-measurement and inflate tokens/s — lower --ticks/"
            "--decode-ticks or raise headroom"
        )
    for i in range(n_slots):
        prompt = rng.integers(0, cfg.vocab_size, size=ctx, dtype=np.int64)
        eng.submit(i, prompt, max_new=(
            budget if spec_draft is None else budget - gamma - 1
        ))

    def tokens_seen():
        return eng.stats["tokens_generated"] + sum(
            len(r.out) for r in eng._slots if r is not None
        )

    # Prime: prefills all slots + compiles the decode program.
    eng.step()
    eng.step()
    before = tokens_seen()
    t0 = time.perf_counter()
    for _ in range(ticks):
        eng.step()
    # A host read of the newest tokens completes the queued work
    # inside the timed region.
    int(np.asarray(eng._cur)[0])
    dt = time.perf_counter() - t0
    tokens = tokens_seen() - before
    return tokens / dt, dt / ticks


def churn(cfg, params, *, paged, impl, n_slots, ctx, max_len, rng,
          rolling=False, decode_ticks=1, kv_quant=None, registry=None,
          overlap=False, device_latency=0.0, host_latency=0.0,
          n_req=None, gen_budget=None, spec_draft=None, gamma=3):
    """Drain ragged requests (default 3*n_slots); tokens/s generated.

    Each request carries an obs RequestTrace, so the drain leaves
    TTFT / TPOT / queue-wait DISTRIBUTIONS in `registry` for the
    output JSON — a server-shaped workload measured the way the
    server reports it, not just a mean.

    device_latency/host_latency (seconds) arm the simulated-RPC
    harness: the SimulatedHostLatency shim stretches each decode
    window's availability clock by device_latency (a device behind a
    slow host link), and host_latency is slept per drained step
    (stand-in for the serving layer's detokenize/stream/HTTP work
    between windows). With them a CPU box imitates a host-bound decode
    loop — the regime overlapped dispatch exists for. The resulting
    ratios are ratios of injected sleeps, not speed (ROADMAP D1)."""
    from shellac_tpu.obs import ServeMetrics, get_registry

    eng = build_engine(
        cfg, params, paged=paged, impl=impl, n_slots=n_slots,
        max_len=max_len, decode_ticks=decode_ticks, kv_quant=kv_quant,
        rolling=rolling, registry=registry, overlap=overlap,
        spec_draft=spec_draft, gamma=gamma,
    )
    shim = None
    if device_latency > 0:
        from shellac_tpu.inference.autotune import SimulatedHostLatency

        shim = SimulatedHostLatency(eng, device_s=device_latency)
    sm = ServeMetrics(registry if registry is not None else get_registry())
    if n_req is None:
        n_req = 3 * n_slots
    if gen_budget is None:
        gen_budget = min(64, max(4, (max_len - ctx) // 2))
    reqs = []
    for i in range(n_req):
        plen = int(rng.integers(max(8, ctx // 2), ctx + 1))
        prompt = rng.integers(0, cfg.vocab_size, size=plen, dtype=np.int64)
        reqs.append((i, prompt, int(rng.integers(gen_budget // 2, gen_budget + 1))))
    # Warm the prefill buckets + decode program outside the timed
    # region. Prompt lengths span [ctx/2, ctx] — up to two power-of-two
    # pad buckets — and an unwarmed bucket would put its prefill
    # compile INSIDE the measurement (the gate's latency-dominated runs
    # are short enough for one compile to swamp the ratio).
    for wi, wlen in enumerate({max(8, ctx // 2), ctx}):
        eng.submit(("warm", wi), reqs[0][1][:wlen] if wlen <= len(reqs[0][1])
                   else rng.integers(0, cfg.vocab_size, size=wlen,
                                     dtype=np.int64),
                   max_new=2)
    while eng.pending:
        eng.step()
    t0 = time.perf_counter()
    traces = {}
    for rid, prompt, max_new in reqs:
        traces[rid] = sm.trace()
        eng.submit(rid, prompt, max_new, trace=traces[rid])
    results = {}
    while eng.pending:
        for rid, out in eng.step():
            traces[rid].finish(len(out))
            results[rid] = out
        if host_latency > 0:
            time.sleep(host_latency)
    dt = time.perf_counter() - t0
    if shim is not None:
        shim.uninstall()
    total = sum(len(v) for v in results.values())
    assert len(results) == n_req
    return total / dt, total


def mixed_prefill_churn(cfg, params, *, n_slots, ctx, max_len, rng,
                        decode_ticks=1, overlap_prefill=False,
                        device_latency=0.0, prefill_latency=0.0,
                        host_latency=0.0, registry=None, n_long=None,
                        gen_budget=None):
    """Mixed prefill-heavy churn: steady decoders + a stream of
    long-prompt admissions; tokens/s generated over the timed drain.

    The admission-side twin of churn(): a few slots decode steadily
    (long budgets) while a stream of long-prompt, ~2-window-budget
    requests churns through the rest, capped at one prefill per step —
    so nearly every step runs an admission, exactly the regime where a
    synchronous per-prefill settle stalls the decode hot path. The
    SimulatedHostLatency shim stretches BOTH clocks: each decode
    window's results arrive device_latency after dispatch, each
    prefill's prefill_latency after dispatch. Without overlap_prefill
    the admission blocks for the whole prefill round trip inline; with
    it the settle rides the next step boundary and the round trip
    hides behind the window the device was computing anyway — the
    contrast the perf gate's prefill rows assert."""
    from shellac_tpu.obs import ServeMetrics, get_registry

    eng = build_engine(
        cfg, params, paged=False, impl="ref", n_slots=n_slots,
        max_len=max_len, decode_ticks=decode_ticks, registry=registry,
        overlap=True, overlap_prefill=overlap_prefill,
        max_prefills_per_step=1,
    )
    shim = None
    if device_latency > 0 or prefill_latency > 0:
        from shellac_tpu.inference.autotune import SimulatedHostLatency

        shim = SimulatedHostLatency(eng, device_s=device_latency,
                                    prefill_s=prefill_latency)
    sm = ServeMetrics(registry if registry is not None else get_registry())
    if n_long is None:
        n_long = 3 * n_slots
    if gen_budget is None:
        # ~2 windows per long request: the stream stays dense enough
        # that nearly every step runs an admission (the cap is 1), so
        # the off-arm pays the inline prefill round trip per step —
        # the regime the pipeline exists for.
        gen_budget = max(4, 2 * decode_ticks)
    n_steady = max(1, n_slots // 4)
    steady_budget = max(
        8, (n_long // max(1, n_slots - n_steady) + 2) * gen_budget
    )
    reqs = []
    # Steady decoders: short prompts, budgets long enough to live
    # through the whole long-prompt stream.
    for i in range(n_steady):
        prompt = rng.integers(0, cfg.vocab_size, size=8, dtype=np.int64)
        reqs.append((("steady", i), prompt, steady_budget))
    # The prefill-heavy stream: full-ctx prompts, small budgets.
    for i in range(n_long):
        prompt = rng.integers(0, cfg.vocab_size, size=ctx, dtype=np.int64)
        reqs.append((("long", i), prompt, gen_budget))
    # Warm every prefill bucket + the decode program outside the timed
    # region (same rationale as churn()).
    for wi, wlen in enumerate((8, ctx)):
        eng.submit(("warm", wi),
                   rng.integers(0, cfg.vocab_size, size=wlen,
                                dtype=np.int64), max_new=2)
    while eng.pending:
        eng.step()
    t0 = time.perf_counter()
    traces = {}
    for rid, prompt, max_new in reqs:
        traces[rid] = sm.trace()
        eng.submit(rid, prompt, max_new, trace=traces[rid])
    results = {}
    while eng.pending:
        for rid, out in eng.step():
            traces[rid].finish(len(out))
            results[rid] = out
        if host_latency > 0:
            time.sleep(host_latency)
    dt = time.perf_counter() - t0
    if shim is not None:
        shim.uninstall()
    total = sum(len(v) for v in results.values())
    assert len(results) == len(reqs)
    return total / dt, total


def _build_kernel_loop(cfg, *, paged, impl, n_slots, ctx, max_len, iters):
    """Build one jitted scan of `iters` chained decode-attention calls.

    The engine numbers include a per-tick host sync, so they measure
    the host loop as much as the kernel. Chaining the calls inside ONE
    jitted lax.scan (the output feeds the next q, so nothing can be
    CSE'd or overlapped away) measures the op itself.
    Returns (loop_fn, q0, kv_bytes_per_call)."""
    import jax
    import jax.numpy as jnp

    from shellac_tpu.ops.decode_attention import (
        decode_attention,
        paged_decode_attention,
    )

    hkv, dh = cfg.kv_heads, cfg.dim_per_head
    h = cfg.n_heads
    cdt = cfg.compute_dtype
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q0 = jax.random.normal(ks[0], (n_slots, 1, h, dh), cdt)
    # Ragged realistic lengths around ctx.
    lengths = jnp.asarray(
        np.linspace(ctx // 2, ctx, n_slots, dtype=np.int32)
    )
    if paged:
        bs = 64
        max_blocks = max_len // bs
        n_blocks = n_slots * max_blocks + 1
        pool_k = jax.random.normal(ks[1], (n_blocks, hkv, bs, dh), cdt)
        pool_v = jax.random.normal(ks[2], (n_blocks, hkv, bs, dh), cdt)
        tables = jnp.arange(1, n_blocks, dtype=jnp.int32).reshape(
            n_slots, max_blocks
        )

        def one(q):
            return paged_decode_attention(
                q, pool_k, pool_v, tables, lengths, impl=impl
            )
    else:
        ck = jax.random.normal(ks[1], (n_slots, hkv, max_len, dh), cdt)
        cv = jax.random.normal(ks[2], (n_slots, hkv, max_len, dh), cdt)

        def one(q):
            return decode_attention(q, ck, cv, lengths, impl=impl)

    @jax.jit
    def loop(q):
        def body(q, _):
            o = one(q)
            # Data dependence: next q derives from this output.
            return (q0 + 1e-3 * o).astype(cdt), ()

        q, _ = jax.lax.scan(body, q, None, length=iters)
        return q

    live_tokens = int(np.asarray(lengths).sum())
    kv_bytes = 2 * live_tokens * hkv * dh * jnp.dtype(cdt).itemsize
    return loop, q0, kv_bytes


def kernel_microbench_interleaved(cfg, variants, *, n_slots, ctx, max_len,
                                  iters, rounds):
    """Time all variants in interleaved A/B/A/B rounds, min per variant.

    Measuring each variant in its own multi-minute pass lets slow drift
    of the machine masquerade as kernel speed. Interleaving puts every
    variant in every drift regime; the per-variant MIN over rounds is
    robust to latency spikes, and the recorded spread shows whether
    drift occurred.

    Returns {variant: (min_us, gbps_at_min, spread)} where spread =
    max_round_us / min_round_us."""
    import jax.numpy as jnp

    built = {}
    for variant in variants:
        cache_kind, impl = variant.split(":")
        loop, q0, kv_bytes = _build_kernel_loop(
            cfg, paged=cache_kind == "paged", impl=impl,
            n_slots=n_slots, ctx=ctx, max_len=max_len, iters=iters,
        )
        # Warm (compile + first run) outside every timed region.
        float(jnp.sum(loop(q0).astype(jnp.float32)))
        built[variant] = (loop, q0, kv_bytes)

    times = {v: [] for v in variants}
    for _ in range(rounds):
        for variant in variants:
            loop, q0, _ = built[variant]
            t0 = time.perf_counter()
            out = loop(q0)
            # The host read completes the scan inside the timed region.
            float(jnp.sum(out.astype(jnp.float32)))
            times[variant].append(time.perf_counter() - t0)

    results = {}
    for variant in variants:
        best, worst = min(times[variant]), max(times[variant])
        kv_bytes = built[variant][2]
        gbps = kv_bytes / (best / iters) / 1e9
        results[variant] = (best / iters * 1e6, gbps, worst / best)
    return results


def prefix_bench(cfg, params, *, n_slots, ctx, max_len, rng):
    """Shared-system-prompt workload: prefix caching on vs off.

    3*n_slots requests share a ~ctx-token prefix with short distinct
    tails; the interesting number is how much wall time prefix reuse
    removes from the prefill-dominated drain (decode work is identical
    in both runs)."""
    from shellac_tpu.inference.batching import PagedBatchingEngine

    shared = rng.integers(0, cfg.vocab_size, size=ctx, dtype=np.int64)
    reqs = []
    for i in range(3 * n_slots):
        tail = rng.integers(0, cfg.vocab_size, size=16, dtype=np.int64)
        reqs.append((i, np.concatenate([shared, tail]), 8))

    out = {}
    for on in (False, True):
        eng = PagedBatchingEngine(
            cfg, params, n_slots=n_slots, max_len=max_len, block_size=64,
            pool_tokens=2 * n_slots * max_len, temperature=0.0,
            prefix_cache=on,
        )
        # Warm compile caches outside the timed region — twice, so the
        # prefix-hit continuation program (reachable only when a chain
        # is already cached) compiles here, not inside the measurement.
        eng.run([("warm", reqs[0][1], 2)])
        eng.run([("warm2", reqs[0][1], 2)])
        warm_hits = eng.stats.get("prefix_hit_tokens", 0)
        t0 = time.perf_counter()
        results = eng.run(reqs)
        dt = time.perf_counter() - t0
        assert len(results) == len(reqs)
        out[on] = (dt, eng.stats.get("prefix_hit_tokens", 0) - warm_hits)
    return out


def fabric_churn(cfg, params, *, n_slots, ctx, max_len, rng, fabric,
                 prefill_token_s, n_prefix=4, sessions=3, tail=16,
                 gen_budget=8, registry=None):
    """Shared-prefix churn onto a COLD replica: fabric seeding on/off.

    The fleet-fabric scenario the KV directory + hot-prefix push exist
    for: a replica joins (or respawns) mid-load while the fleet is
    serving sessions over a few hot shared prefixes. n_prefix hot
    ~ctx-token prefixes x `sessions` waves of requests with distinct
    short tails drain through a freshly built engine. With fabric on,
    the hot chains are seeded from a warm peer before the drain (the
    bench calls export_chain/seed_chain directly — the same functions
    the /kv/push -> /kv/seed HTTP legs run); with it off the cold
    engine pays one full prefill per hot prefix before its LOCAL
    prefix cache takes over. SimulatedHostLatency(prefill_token_s=..)
    charges each prefill per token it actually computes (prompt minus
    the backend's prefix-cache offset), so the avoided recompute shows
    up in wall clock the way it does on hardware. Greedy outputs must
    be bit-identical on vs off — seeded KV is the same KV.

    Returns {"tokens_s", "drain_s", "hit_tokens", "seeded_blocks",
    "results"}."""
    from shellac_tpu.inference import fabric as fabric_mod
    from shellac_tpu.inference import prefix as prefix_mod
    from shellac_tpu.inference.autotune import SimulatedHostLatency
    from shellac_tpu.inference.batching import PagedBatchingEngine

    bs = 64
    if ctx % bs:
        raise SystemExit(f"fabric_churn: --ctx must be a multiple of "
                         f"the {bs}-token block size")

    def mk():
        return PagedBatchingEngine(
            cfg, params, n_slots=n_slots, max_len=max_len,
            block_size=bs, pool_tokens=2 * n_slots * max_len,
            temperature=0.0, prefix_cache=True, registry=registry,
        )

    # All randomness is drawn up front so the on/off arms (fresh rng,
    # same seed) see byte-identical requests.
    prefixes = [rng.integers(0, cfg.vocab_size, size=ctx, dtype=np.int64)
                for _ in range(n_prefix)]
    waves = []
    for s in range(sessions):
        wave = []
        for p in range(n_prefix):
            t = rng.integers(0, cfg.vocab_size, size=tail, dtype=np.int64)
            wave.append(((p, s), np.concatenate([prefixes[p], t]),
                         gen_budget))
        waves.append(wave)
    warm_prefix = rng.integers(0, cfg.vocab_size, size=ctx, dtype=np.int64)
    warm_tail = rng.integers(0, cfg.vocab_size, size=tail, dtype=np.int64)

    cold = mk()
    # Warm the compile caches outside the timed region with a DISJOINT
    # prefix — twice, so the prefix-hit continuation program (tail-only
    # prefill) compiles here too. Identical treatment on both arms.
    warm_prompt = np.concatenate([warm_prefix, warm_tail])
    cold.run([("warm", warm_prompt, 2)])
    cold.run([("warm2", warm_prompt, 2)])
    warm_hits = cold.stats.get("prefix_hit_tokens", 0)

    if fabric:
        # A warm peer that already served the hot prefixes; ship each
        # chain with the function-level halves of /kv/push -> /kv/seed.
        warm_eng = mk()
        warm_eng.run([(("seed", p), prefixes[p], 2)
                      for p in range(n_prefix)])
        for p in range(n_prefix):
            tip = prefix_mod.chain_hashes(prefixes[p], bs)[-1]
            blob = fabric_mod.export_chain(warm_eng, tip)
            fabric_mod.seed_chain(cold, blob)

    shim = SimulatedHostLatency(cold, prefill_token_s=prefill_token_s)
    results = {}
    t0 = time.perf_counter()
    for wave in waves:
        for rid, prompt, max_new in wave:
            cold.submit(rid, prompt, max_new)
        while cold.pending:
            for rid, out in cold.step():
                results[rid] = out
    dt = time.perf_counter() - t0
    shim.uninstall()
    assert len(results) == n_prefix * sessions
    total = sum(len(v) for v in results.values())
    return {
        "tokens_s": total / dt,
        "drain_s": dt,
        "hit_tokens": int(cold.stats.get("prefix_hit_tokens", 0)
                          - warm_hits),
        "seeded_blocks": int(cold.stats.get("prefix_seeded_blocks", 0)),
        "results": results,
    }


def beam_bench(cfg, params, *, ctx, max_len, rng, num_beams=4,
               steps=32):
    """Dense row-gather beams vs paged CoW beams on ONE long prompt.

    The dense beam gathers EVERY cache row per reorder (O(ctx) copies
    per step at long context); the paged beam copies one partial tail
    block per beam and shares everything sealed — the ratio is the
    CoW payoff. Outputs must agree exactly (compiled parity evidence
    rides the bench)."""
    from shellac_tpu.inference.batching import PagedBatchingEngine
    from shellac_tpu.inference.engine import Engine

    prompt = rng.integers(
        0, cfg.vocab_size, size=ctx, dtype=np.int64
    ).tolist()
    dense = Engine(cfg, params, temperature=0.0, max_len=max_len)
    paged = PagedBatchingEngine(
        cfg, params, n_slots=2, max_len=max_len, block_size=64,
        pool_tokens=4 * max_len, temperature=0.0,
    )
    runs = {
        "dense": lambda: dense.beam_search(
            prompt, num_beams=num_beams, max_new_tokens=steps
        ),
        "paged": lambda: paged.beam_search(
            prompt, num_beams=num_beams, max_new_tokens=steps
        ),
    }
    out = {}
    seqs = {}
    for name, fn in runs.items():
        fn()  # warm the compile cache outside the timed region
        t0 = time.perf_counter()
        s, _ = fn()
        out[name] = time.perf_counter() - t0
        seqs[name] = s
    assert seqs["dense"] == seqs["paged"], "beam parity broke on-device"
    return out


def step_phase_digest(registry):
    """Condensed step-time phase attribution from a run's registry:
    per phase (obs.STEP_PHASES) the total seconds, observation count,
    p50, and share of the attributed step time — the committed
    measurement of where the engine tick goes (docs/observability.md
    §Step-time attribution). Embedded in gate summaries and bench
    rows so BENCH_* files carry the attribution alongside tokens/s."""
    from shellac_tpu.obs import STEP_PHASES

    out = {}
    total = 0.0
    for phase in STEP_PHASES:
        h = registry.get("shellac_step_phase_seconds", phase=phase)
        if h is None or h.count == 0:
            continue
        total += h.sum
        out[phase] = {
            "sum_s": round(h.sum, 4),
            "count": h.count,
            "p50_ms": round((h.percentile(0.5) or 0.0) * 1e3, 3),
        }
    if total > 0:
        for row in out.values():
            row["share"] = round(row["sum_s"] / total, 3)
    return out


def gate(cfg, params, args, backend):
    """CI perf regression gate: the overlapped-decode churn benchmark
    under the simulated dispatch-latency harness, judged against a
    committed baseline.

    The harness (sleep-injected RPC shim; see churn()) makes the run
    latency-dominated, so absolute churn tokens/s is reproducible
    across CI machines to well under the gate's 15% tolerance — model
    compute is a small additive term. Two checks, both machine-
    readable in the emitted summary:

      1. overlapped churn tokens/s >= (1 - tolerance) * baseline —
         perf can no longer silently rot between hardware windows
         (pinning decode_ticks to a pessimal value, breaking the
         auto-tuner, or breaking overlap all fail this);
      2. overlap speedup vs the strict-ordering run of the SAME
         invocation >= the committed floor (1.5x) — the pipeline must
         actually hide the injected host/RPC time;
      3. the mixed prefill-heavy rows: tokens/s vs baseline, prefill
         overlap speedup (on vs off, same invocation) >= its floor
         (1.3x), and the step-phase digest's prefill share
         (prefill_dispatch + prefill_settle) must FALL under overlap —
         the admission-side pipeline must actually hide the injected
         prefill round trip, not just exist.

    --write-gate-baseline re-baselines (run it when the gate workload
    itself changes, and commit the JSON with the change that moved
    it)."""
    from shellac_tpu.inference.autotune import (
        SimulatedHostLatency,
        autotune_decode_ticks,
    )

    device_s = args.device_latency_ms / 1e3
    host_s = args.host_latency_ms / 1e3
    max_len = ((args.ctx + max(64, args.ctx // 4)) + 511) // 512 * 512

    # decode_ticks: auto-tuned against the simulated environment
    # (exactly what serve --decode-ticks auto does against the live
    # mesh), unless pinned via --decode-ticks — the pessimal-pin CI
    # check uses that to prove the gate actually fails.
    if args.decode_ticks == "auto":
        eng = build_engine(
            cfg, params, paged=False, impl="ref", n_slots=args.slots,
            max_len=max_len, decode_ticks="auto", overlap=True,
        )
        shim = SimulatedHostLatency(eng, device_s=device_s)
        # Candidates stop at 4: on a CPU "device" the real model
        # compute scales with K and is paid inline at dispatch, so an
        # unbounded sweep walks into compute-bound windows that the
        # injected latency no longer dominates — the opposite of the
        # host-bound regime the gate simulates. Keeping real compute well
        # under the injected latencies is also what makes the
        # committed baseline transfer across CI machines.
        tune = autotune_decode_ticks(eng, candidates=(1, 2, 4),
                                     probe_windows=2)
        shim.uninstall()
        ticks = tune.best
        tuned = {str(k): round(v, 1) for k, v in tune.measurements.items()}
    else:
        ticks = int(args.decode_ticks)
        tuned = None

    from shellac_tpu.obs import Registry

    rates = {}
    phase_digests = {}
    for overlap in (True, False):
        rng = np.random.default_rng(0)
        reg = Registry()
        tok_s, total = churn(
            cfg, params, paged=False, impl="ref", n_slots=args.slots,
            ctx=args.ctx, max_len=max_len, rng=rng, decode_ticks=ticks,
            overlap=overlap, device_latency=device_s,
            host_latency=host_s, n_req=2 * args.slots, registry=reg,
            # Requests live ~6 windows: the steady-serving regime
            # overlap targets. Sub-2-window budgets make slot turnover
            # (admissions join at window boundaries; a finished slot's
            # stale window is garbage) dominate and under-measure the
            # pipeline — that trade-off is documented in
            # docs/decode_performance.md, not hidden in the gate.
            gen_budget=max(12 * ticks, 32),
        )
        rates[overlap] = tok_s
        phase_digests["overlap" if overlap else "serial"] = (
            step_phase_digest(reg)
        )
    speedup = rates[True] / max(rates[False], 1e-9)

    # Spec-on-paged churn (PR 9's composition): self-draft over the
    # paged pool, host-latency harness only — the window shim hooks
    # the dispatch pipeline the verify round replaces, but the
    # per-step host sleep still dominates tiny-model compute, so the
    # number is sync-count-bound and transfers across CI machines
    # like the others. Guards the new path against silent rot
    # (a crash, a lost multi-token round, or a pathological
    # round-count regression all move it far past tolerance).
    rng = np.random.default_rng(1)
    spec_reg = Registry()
    spec_tok_s, _ = churn(
        cfg, params, paged=True, impl="ref", n_slots=args.slots,
        ctx=args.ctx, max_len=max_len, rng=rng, decode_ticks=1,
        host_latency=host_s, n_req=2 * args.slots, gen_budget=32,
        spec_draft=(cfg, params), gamma=2, registry=spec_reg,
    )
    phase_digests["spec_paged"] = step_phase_digest(spec_reg)

    # Mixed prefill-heavy churn (the admission-side pipeline): long-
    # prompt admissions interleaved with steady decode, with the
    # prefill clock stretched like the window clock. overlap_prefill
    # on vs off in the SAME invocation — the on-arm honors the
    # --overlap-prefill pin so CI can prove the gate fails when the
    # pipeline is disabled (the --decode-ticks 1 self-test's twin).
    prefill_s = args.prefill_latency_ms / 1e3
    mixed = {}
    for opf in (True, False):
        rng = np.random.default_rng(2)
        reg = Registry()
        tok_s, _ = mixed_prefill_churn(
            cfg, params, n_slots=args.slots, ctx=args.ctx,
            max_len=max_len, rng=rng, decode_ticks=ticks,
            overlap_prefill=opf and args.overlap_prefill,
            # A quarter of the decode rows' window latency: the mixed
            # rows measure the ADMISSION-side pipeline, so the on-arm
            # must not simply be window-bound — the contrast is the
            # inline prefill round trip vs the batched settle.
            device_latency=device_s / 4, prefill_latency=prefill_s,
            host_latency=host_s, registry=reg,
            n_long=2 * args.slots,
        )
        mixed[opf] = tok_s
        phase_digests["mixed_prefill" if opf
                      else "mixed_prefill_serial"] = (
            step_phase_digest(reg)
        )
    prefill_speedup = mixed[True] / max(mixed[False], 1e-9)

    # Shared-prefix churn onto a cold replica: fabric seeding on vs
    # off in the SAME invocation. The on-arm honors --no-fabric so CI
    # can prove this gate row fails when seeding is disabled (the
    # --decode-ticks 1 / --no-overlap-prefill self-tests' triplet).
    # Per-token prefill charging makes the avoided recompute a wall-
    # clock quantity a CPU box reproduces; real tiny-model compute is
    # the small additive term, same transferability argument as above.
    fab = {}
    for on in (True, False):
        rng = np.random.default_rng(3)
        fab[on] = fabric_churn(
            cfg, params, n_slots=args.slots, ctx=args.ctx,
            max_len=max_len, rng=rng, fabric=on and args.fabric,
            prefill_token_s=args.fabric_prefill_token_ms / 1e3,
        )
    fabric_speedup = (fab[True]["tokens_s"]
                      / max(fab[False]["tokens_s"], 1e-9))
    fabric_identical = fab[True]["results"] == fab[False]["results"]

    def _prefill_share(digest):
        """prefill_dispatch + prefill_settle share of the attributed
        step time — the admission-side cost the pipeline exists to
        hide (the pre-split metric was prefill_dispatch alone)."""
        return sum(digest.get(p, {}).get("share", 0.0)
                   for p in ("prefill_dispatch", "prefill_settle"))

    summary = {
        "metric": f"decode_gate_{args.model}_{backend}",
        "churn_tokens_s": round(rates[True], 1),
        "serial_tokens_s": round(rates[False], 1),
        "overlap_speedup": round(speedup, 3),
        "spec_paged_tokens_s": round(spec_tok_s, 1),
        "mixed_prefill_tokens_s": round(mixed[True], 1),
        "mixed_prefill_serial_tokens_s": round(mixed[False], 1),
        "prefill_overlap_speedup": round(prefill_speedup, 3),
        "prefill_share_overlap": round(
            _prefill_share(phase_digests["mixed_prefill"]), 3),
        "prefill_share_serial": round(
            _prefill_share(phase_digests["mixed_prefill_serial"]), 3),
        "fabric_tokens_s": round(fab[True]["tokens_s"], 1),
        "fabric_off_tokens_s": round(fab[False]["tokens_s"], 1),
        "fabric_speedup": round(fabric_speedup, 3),
        "fabric_hit_tokens": fab[True]["hit_tokens"],
        "fabric_seeded_blocks": fab[True]["seeded_blocks"],
        "fabric_outputs_identical": fabric_identical,
        "decode_ticks": ticks,
        "autotune": tuned,
        "step_phases": phase_digests,
        "params": {
            "slots": args.slots, "ctx": args.ctx,
            "device_latency_ms": args.device_latency_ms,
            "host_latency_ms": args.host_latency_ms,
            "prefill_latency_ms": args.prefill_latency_ms,
            "fabric_prefill_token_ms": args.fabric_prefill_token_ms,
        },
    }

    if args.write_gate_baseline:
        baseline = {
            "churn_tokens_s": summary["churn_tokens_s"],
            "overlap_speedup_floor": 1.5,
            "spec_paged_tokens_s": summary["spec_paged_tokens_s"],
            "mixed_prefill_tokens_s": summary["mixed_prefill_tokens_s"],
            "prefill_overlap_speedup_floor": 1.3,
            "fabric_tokens_s": summary["fabric_tokens_s"],
            "fabric_speedup_floor": 1.3,
            "tolerance": 0.15,
            "params": summary["params"],
        }
        with open(args.gate_baseline, "w") as f:
            json.dump(baseline, f, indent=1)
            f.write("\n")
        summary["baseline_written"] = args.gate_baseline
        print(json.dumps(summary), flush=True)
        return 0

    try:
        with open(args.gate_baseline) as f:
            baseline = json.load(f)
    except FileNotFoundError:
        print(json.dumps({**summary, "gate": "fail",
                          "error": f"no baseline {args.gate_baseline}; "
                                   "run --write-gate-baseline"}))
        return 1
    if baseline.get("params") != summary["params"]:
        print(json.dumps({**summary, "gate": "fail",
                          "error": "gate params drifted from baseline; "
                                   "re-baseline with "
                                   "--write-gate-baseline"}))
        return 1
    tol = float(baseline.get("tolerance", 0.15))
    floor = float(baseline.get("overlap_speedup_floor", 1.5))
    need = baseline["churn_tokens_s"] * (1.0 - tol)
    failures = []
    if rates[True] < need:
        failures.append(
            f"churn tokens/s {rates[True]:.1f} < {need:.1f} "
            f"(baseline {baseline['churn_tokens_s']} - {tol:.0%})"
        )
    if speedup < floor:
        failures.append(
            f"overlap speedup {speedup:.2f}x < required {floor}x"
        )
    spec_base = baseline.get("spec_paged_tokens_s")
    if spec_base is not None and spec_tok_s < spec_base * (1.0 - tol):
        failures.append(
            f"spec-on-paged churn tokens/s {spec_tok_s:.1f} < "
            f"{spec_base * (1.0 - tol):.1f} "
            f"(baseline {spec_base} - {tol:.0%})"
        )
    mixed_base = baseline.get("mixed_prefill_tokens_s")
    if mixed_base is not None:
        pfloor = float(baseline.get("prefill_overlap_speedup_floor",
                                    1.3))
        if mixed[True] < mixed_base * (1.0 - tol):
            failures.append(
                f"mixed prefill-heavy churn tokens/s "
                f"{mixed[True]:.1f} < {mixed_base * (1.0 - tol):.1f} "
                f"(baseline {mixed_base} - {tol:.0%})"
            )
        if prefill_speedup < pfloor:
            failures.append(
                f"prefill overlap speedup {prefill_speedup:.2f}x < "
                f"required {pfloor}x"
            )
        if (summary["prefill_share_overlap"]
                >= summary["prefill_share_serial"]):
            failures.append(
                "step-phase digest: prefill share did not fall under "
                f"overlap ({summary['prefill_share_overlap']} >= "
                f"{summary['prefill_share_serial']})"
            )
    fab_base = baseline.get("fabric_tokens_s")
    if fab_base is not None:
        ffloor = float(baseline.get("fabric_speedup_floor", 1.3))
        if fab[True]["tokens_s"] < fab_base * (1.0 - tol):
            failures.append(
                f"fabric cold-replica churn tokens/s "
                f"{fab[True]['tokens_s']:.1f} < "
                f"{fab_base * (1.0 - tol):.1f} "
                f"(baseline {fab_base} - {tol:.0%})"
            )
        if fabric_speedup < ffloor:
            failures.append(
                f"fabric seeding speedup {fabric_speedup:.2f}x < "
                f"required {ffloor}x"
            )
        if not fabric_identical:
            failures.append(
                "fabric on/off greedy outputs diverged — seeded KV "
                "changed the math"
            )
        if args.fabric and not fab[True]["seeded_blocks"]:
            failures.append("fabric on-arm seeded 0 blocks")
        if args.fabric and fab[True]["hit_tokens"] <= 0:
            failures.append("fabric on-arm saw no prefix hit tokens")
    summary["gate"] = "fail" if failures else "pass"
    if failures:
        summary["failures"] = failures
    print(json.dumps(summary), flush=True)
    return 1 if failures else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default=None, help="preset (default: auto)")
    ap.add_argument("--ctx", type=int, default=2048)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--ticks", type=int, default=50)
    ap.add_argument("--kernel-iters", type=int, default=50,
                    help="decode-attention calls per timed scan segment")
    ap.add_argument("--kernel-rounds", type=int, default=8,
                    help="interleaved A/B timing rounds per variant "
                         "(result = per-variant min)")
    ap.add_argument("--decode-ticks", default=None,
                    help="engine mode: decode steps per host sync "
                         "(int, default 1; gate mode also accepts "
                         "'auto', its default, to run the startup "
                         "sweep)")
    ap.add_argument("--mode", default="engine",
                    choices=["engine", "kernel", "prefix", "beam",
                             "fabric"])
    ap.add_argument("--overlap", action="store_true",
                    help="engine mode: overlapped window dispatch")
    ap.add_argument("--device-latency-ms", type=float, default=0.0,
                    dest="device_latency_ms",
                    help="simulated per-window device/RPC latency "
                         "(sleep-injected shim; gate default 80)")
    ap.add_argument("--host-latency-ms", type=float, default=0.0,
                    dest="host_latency_ms",
                    help="simulated per-step host work "
                         "(gate default 60)")
    ap.add_argument("--prefill-latency-ms", type=float, default=0.0,
                    dest="prefill_latency_ms",
                    help="simulated per-prefill device/RPC latency "
                         "for the mixed prefill-heavy gate rows "
                         "(gate default 250)")
    ap.add_argument("--overlap-prefill", dest="overlap_prefill",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="gate mode: run the mixed prefill-heavy "
                         "on-arm with the in-flight prefill pipeline "
                         "(--no-overlap-prefill pins it off — the CI "
                         "self-test proving the prefill gate rows can "
                         "fail)")
    ap.add_argument("--fabric", dest="fabric",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="gate/fabric mode: seed the cold replica's "
                         "prefix chains from a warm peer before the "
                         "shared-prefix drain (--no-fabric pins "
                         "seeding off — the CI self-test proving the "
                         "fabric gate row can fail)")
    ap.add_argument("--fabric-prefill-token-ms", type=float,
                    default=0.0, dest="fabric_prefill_token_ms",
                    help="simulated per-COMPUTED-prefill-token cost "
                         "for the fabric rows (gate default 4; prefix "
                         "hits skip their tokens, so avoided recompute "
                         "becomes wall clock)")
    ap.add_argument("--gate", action="store_true",
                    help="CI perf regression gate: overlapped churn "
                         "under the simulated-latency harness vs the "
                         "committed baseline (exit 1 on regression)")
    ap.add_argument("--gate-baseline", default=None,
                    dest="gate_baseline",
                    help="baseline JSON path (default: BENCH_GATE.json "
                         "next to the repo root)")
    ap.add_argument("--write-gate-baseline", action="store_true",
                    dest="write_gate_baseline",
                    help="measure and (over)write the gate baseline "
                         "instead of judging against it")
    ap.add_argument("--variants",
                    default="dense:auto,dense:ref,paged:auto,paged:ref",
                    help="comma list of cache:impl rows; cache in "
                         "{dense, paged, rolling, spec-dense, "
                         "spec-paged} (spec-* = speculative serving "
                         "with a self-draft)")
    ap.add_argument("--kv-quant", choices=["int8"],
                    help="int8 KV cache on the dense engine variants")
    ap.add_argument("--window", type=int, default=None,
                    help="apply a sliding window to the model (enables "
                         "the rolling:* variants — dense-vs-rolling at "
                         "identical math)")
    args = ap.parse_args()

    import jax

    from shellac_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from shellac_tpu import get_model_config
    from shellac_tpu.models import transformer

    backend = jax.default_backend()
    if args.gate:
        # Gate defaults: a fixed, latency-dominated workload so the
        # committed baseline transfers across CI machines.
        if args.model is None:
            args.model = "tiny"
        args.ctx = min(args.ctx, 64)
        args.slots = min(args.slots, 4)
        if args.decode_ticks is None:  # unset -> gate default: sweep.
            # An explicit "--decode-ticks 1" stays pinned (the CI
            # pessimal self-test depends on the distinction).
            args.decode_ticks = "auto"
        # Injected latencies are deliberately LARGE relative to the
        # tiny model's real compute (~30-100 ms per 4-tick window,
        # machine-dependent): the overlapped run's period then pins at
        # the device latency — near-constant tokens/s across CI
        # machines and load spikes — while the serial run pays
        # device + host serially. Real compute only perturbs the
        # serial number, well inside the 15% tolerance.
        if not args.device_latency_ms:
            args.device_latency_ms = 400.0
        if not args.host_latency_ms:
            args.host_latency_ms = 250.0
        if not args.fabric_prefill_token_ms:
            # Per-token so the ratio tracks tokens AVOIDED, not a
            # fixed per-flight cost both arms pay equally. 4 ms/token
            # x 64-token prefix dwarfs real tiny-model prefill
            # compute, same transferability argument as the fixed
            # latencies above.
            args.fabric_prefill_token_ms = 4.0
        if not args.prefill_latency_ms:
            # Large against real tiny-model prefill compute, but at
            # most the hiding capacity of one step boundary (the host
            # sleep + the mixed rows' smaller window clock): the
            # on-arm then hides nearly all of it while the off-arm
            # pays it inline per admission.
            args.prefill_latency_ms = 250.0
        if args.gate_baseline is None:
            args.gate_baseline = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "BENCH_GATE.json",
            )
        cfg = get_model_config(args.model)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        raise SystemExit(gate(cfg, params, args, backend))
    if args.decode_ticks == "auto":
        raise SystemExit("--decode-ticks auto is gate-mode only here; "
                         "pass an int for engine mode")
    args.decode_ticks = int(args.decode_ticks or 1)
    if args.model is None:
        args.model = "shellac-1b" if backend == "tpu" else "tiny"
        if backend != "tpu":
            args.ctx, args.ticks = 64, 5
    cfg = get_model_config(args.model)
    if args.window is not None:
        cfg = cfg.replace(attn_window=args.window).validate()
    # Serving context: ctx prompt + generation headroom, block-aligned.
    max_len = ((args.ctx + max(64, args.ctx // 4)) + 511) // 512 * 512
    cfg = cfg.replace(max_seq_len=max(cfg.max_seq_len, max_len))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))

    if args.mode == "prefix":
        rng = np.random.default_rng(0)
        res = prefix_bench(
            cfg, params, n_slots=args.slots, ctx=args.ctx,
            max_len=max_len, rng=rng,
        )
        (dt_off, _), (dt_on, hits) = res[False], res[True]
        print(json.dumps({
            "metric": f"prefix_cache_drain_{args.model}_ctx{args.ctx}_"
                      f"{backend}",
            "value": round(dt_off / dt_on, 3),
            "unit": "x speedup (shared-prefix drain, off/on)",
            "detail": {
                "drain_s_off": round(dt_off, 3),
                "drain_s_on": round(dt_on, 3),
                "prefix_hit_tokens": int(hits),
            },
        }), flush=True)
        return

    if args.mode == "fabric":
        fab = {}
        for on in (True, False):
            rng = np.random.default_rng(3)
            fab[on] = fabric_churn(
                cfg, params, n_slots=args.slots, ctx=args.ctx,
                max_len=max_len, rng=rng, fabric=on and args.fabric,
                prefill_token_s=args.fabric_prefill_token_ms / 1e3,
            )
        assert fab[True]["results"] == fab[False]["results"], \
            "fabric on/off greedy outputs diverged"
        print(json.dumps({
            "metric": f"fabric_cold_replica_{args.model}_ctx{args.ctx}_"
                      f"{backend}",
            "value": round(fab[True]["tokens_s"]
                           / max(fab[False]["tokens_s"], 1e-9), 3),
            "unit": "x speedup (cold-replica shared-prefix drain, "
                    "seeded/unseeded)",
            "detail": {
                "tokens_s_seeded": round(fab[True]["tokens_s"], 1),
                "tokens_s_cold": round(fab[False]["tokens_s"], 1),
                "seeded_blocks": fab[True]["seeded_blocks"],
                "hit_tokens_seeded": fab[True]["hit_tokens"],
                "hit_tokens_cold": fab[False]["hit_tokens"],
                "prefill_token_ms": args.fabric_prefill_token_ms,
            },
        }), flush=True)
        return

    if args.mode == "beam":
        rng = np.random.default_rng(0)
        nb, st = 4, 32
        res = beam_bench(
            cfg, params, ctx=args.ctx, max_len=max_len, rng=rng,
            num_beams=nb, steps=st,
        )
        print(json.dumps({
            "metric": f"beam_paged_vs_dense_{args.model}_ctx{args.ctx}_"
                      f"{backend}",
            "value": round(res["dense"] / res["paged"], 3),
            "unit": "x speedup (dense-gather beam / CoW paged beam)",
            "detail": {
                "dense_s": round(res["dense"], 3),
                "paged_s": round(res["paged"], 3),
                "num_beams": nb, "steps": st,
            },
        }), flush=True)
        return

    if args.mode == "kernel":
        variants = args.variants.split(",")
        measured = kernel_microbench_interleaved(
            cfg, variants, n_slots=args.slots, ctx=args.ctx,
            max_len=max_len, iters=args.kernel_iters,
            rounds=args.kernel_rounds,
        )
        results = {}
        for variant, (us, gbps, spread) in measured.items():
            cache_kind, impl = variant.split(":")
            row = {
                "metric": f"decode_kernel_{args.model}_ctx{args.ctx}_"
                          f"{cache_kind}_{impl}_{backend}",
                "value": round(us, 1),
                "unit": "us/call (min of interleaved rounds)",
                "detail": {
                    "kv_stream_gbps": round(gbps, 1),
                    "round_spread": round(spread, 3),
                    "rounds": args.kernel_rounds,
                },
            }
            results[variant] = row
            print(json.dumps(row), flush=True)
        summary = {
            "metric": f"decode_kernel_summary_{args.model}_ctx{args.ctx}_{backend}"
        }
        for kind in ("dense", "paged"):
            a, r = results.get(f"{kind}:auto"), results.get(f"{kind}:ref")
            if a and r and a["value"]:
                summary[f"{kind}_speedup"] = round(r["value"] / a["value"], 3)
        print(json.dumps(summary), flush=True)
        return

    results = {}
    for variant in args.variants.split(","):
        cache_kind, impl = variant.split(":")
        # spec-dense / spec-paged: speculative serving (self-draft, so
        # acceptance ~= 1 and the row measures the round machinery,
        # not draft quality) over the named backend.
        spec = cache_kind.startswith("spec-")
        if spec:
            cache_kind = cache_kind[len("spec-"):]
        paged = cache_kind == "paged"
        rolling = cache_kind == "rolling"
        if spec and rolling:
            raise SystemExit("spec composes with dense/paged backends "
                             "only (rolling is excluded)")
        if rolling and cfg.attn_window is None:
            raise SystemExit(
                "rolling:* variants need a windowed model (--window or "
                "a windowed preset)"
            )
        rng = np.random.default_rng(0)
        kvq = args.kv_quant
        # Spec variants: self-draft, pinned decode_ticks=1, no overlap
        # (both excluded compositions).
        spec_kw = dict(
            spec_draft=(cfg, params) if spec else None,
            decode_ticks=1 if spec else args.decode_ticks,
            overlap=False if spec else args.overlap,
        )
        # One fresh registry per variant: the steady-state and churn
        # engines (and the churn request spans) deposit their
        # histograms here, so the output row carries TTFT/TPOT/
        # queue-wait/decode-window DISTRIBUTIONS, not just the means.
        from shellac_tpu.obs import Registry

        reg = Registry()
        tok_s, tick_s = steady_state(
            cfg, params, paged=paged, impl=impl, n_slots=args.slots,
            ctx=args.ctx, max_len=max_len, ticks=args.ticks, rng=rng,
            kv_quant=kvq, rolling=rolling, registry=reg, **spec_kw,
        )
        churn_tok_s, churn_total = churn(
            cfg, params, paged=paged, impl=impl, n_slots=args.slots,
            ctx=args.ctx, max_len=max_len, rng=rng,
            kv_quant=kvq, rolling=rolling, registry=reg,
            device_latency=args.device_latency_ms / 1e3,
            host_latency=args.host_latency_ms / 1e3, **spec_kw,
        )
        row = {
            "metric": f"decode_throughput_{args.model}_ctx{args.ctx}_"
                      f"{'spec_' if spec else ''}{cache_kind}_{impl}"
                      f"{'_kvq' + args.kv_quant if kvq else ''}_{backend}",
            "value": round(tok_s, 1),
            "unit": "tokens/s",
            "detail": {
                "tick_ms": round(tick_s * 1e3, 3),
                "churn_tokens_s": round(churn_tok_s, 1),
                "churn_tokens": churn_total,
                "n_slots": args.slots,
                "decode_ticks": spec_kw["decode_ticks"],
                "overlap_decode": spec_kw["overlap"],
                "metrics": reg.snapshot(),
            },
        }
        results[variant] = row
        print(json.dumps(row), flush=True)

    summary = {"metric": f"decode_summary_{args.model}_ctx{args.ctx}_{backend}"}
    for kind in ("dense", "paged"):
        a, r = results.get(f"{kind}:auto"), results.get(f"{kind}:ref")
        if a and r and r["value"]:
            summary[f"{kind}_speedup"] = round(a["value"] / r["value"], 3)
    roll = results.get("rolling:ref")
    dense_best = results.get("dense:auto") or results.get("dense:ref")
    if roll and dense_best and dense_best["value"]:
        summary["rolling_vs_dense"] = round(
            roll["value"] / dense_best["value"], 3
        )
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
