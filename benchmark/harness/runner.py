"""One run of one cell: set-up, window, memory, reference check, result line."""

from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time

from benchmark.harness import spec


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Run:
    """What a driver is handed. The driver calls ``mark`` as set-up phases
    end, ``open_window`` and ``close_window`` at the window's edges, and
    ``poll_trace`` often in between."""

    TRACE_SECONDS = 4.0

    def __init__(self, cell, seed, seconds, trace, t_start):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.t_start = t_start
        self.marks = [("start", t_start)]
        self.t_open = self.t_close = None
        self.compiles = []            # (time, event, seconds)
        self.trace_dir = None
        self.trace_t0 = self.trace_t1 = None
        self.arch = self.hf = self.cfg = self.params = self.weights = None

    def mark(self, name):
        self.marks.append((name, time.perf_counter()))

    def open_window(self):
        self.t_open = time.perf_counter()
        self.mark("window_open")
        if self.trace:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self.trace_dir = os.path.join(scratch_dir(), "trace")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.trace_t0 = time.perf_counter()
        return self.t_open

    def poll_trace(self, now):
        if self.trace_t0 is not None and self.trace_t1 is None \
                and now - self.trace_t0 >= min(self.TRACE_SECONDS, self.seconds):
            self._stop_trace()

    def _stop_trace(self):
        import jax

        self.trace_t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def close_window(self):
        self.t_close = time.perf_counter()
        if self.trace_t0 is not None and self.trace_t1 is None:
            self._stop_trace()
        return self.t_close

    def setup_phases(self):
        out, prev = {}, self.t_start
        for name, t in self.marks[1:]:
            out[name] = round(t - prev, 3)
            prev = t
        return out

    def compile_seconds_in_window(self):
        return sum(s for t, _, s in self.compiles
                   if self.t_open is not None and self.t_open <= t
                   and (self.t_close is None or t <= self.t_close))


def scratch_dir():
    """Run-time files: under TMPDIR where the driver gives one, else in the
    checkout (both are this side's own)."""
    base = os.environ.get("TMPDIR") or os.path.join(spec.ROOT, ".bench_tmp")
    path = os.path.join(base, "shellac_bench")
    os.makedirs(path, exist_ok=True)
    return path


def place_compile_cache():
    import jax

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(spec.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


def device_block(chips, require_chip):
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if require_chip and (plat == "cpu" or len(devs) < chips):
        log(f"no accelerator for this cell: platform {plat}, {len(devs)} device(s), "
            f"cell needs {chips}")
        raise SystemExit(3)
    return {"platform": plat, "kind": devs[0].device_kind, "count": chips}, devs[:chips]


def memory_peak(devs):
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def build_model(r, seed):
    """Architecture, published keys, the program's config, the weights from
    ``seed`` and the program's view of them, onto the Run."""
    import jax

    from benchmark.harness import program

    config = r.cell["config"]
    r.arch, r.hf = spec.arch(config), spec.hf_keys(config)
    r.cfg = program.model_config(r.hf, r.arch, dtype=config.get("torch_dtype", "bfloat16"))
    r.weights = r.arch.make_weights(r.hf, seed, dtype=r.cfg.compute_dtype)
    r.params = r.arch.to_program(r.weights)
    jax.block_until_ready(r.params)


def run_cell(workload, seed, seconds, trace, t_start=None, *, require_chip=True,
             config_override=None, traffic_override=None, control=False, out=None, bench=None):
    """``require_chip=False``, the overrides and ``control`` exist for the
    tests under benchmark/tests; the command line never sets them."""
    t_start = t_start or time.perf_counter()
    bench = bench or spec.benchmark()
    cell = spec.cell(workload, bench)
    if config_override:
        cell["config"] = _merge(cell["config"], config_override)
    if traffic_override:
        cell["traffic"] = _merge(cell["traffic"], traffic_override)
    seconds = float(seconds if seconds is not None else bench["run_seconds"])
    try:
        import shellac_tpu  # noqa: F401 - the system under test
    except ImportError:
        log("no program to measure: shellac_tpu is not importable from this checkout")
        raise SystemExit(4)

    import jax.monitoring as mon

    cache_dir = place_compile_cache()
    device, devs = device_block(cell["chips"], require_chip)
    r = Run(cell, seed, seconds, trace, t_start)
    mon.register_event_duration_secs_listener(
        lambda ev, dur, **kw: r.compiles.append((time.perf_counter(), ev, dur))
        if "/compile/" in ev or "compilation" in ev else None)
    r.mark("import")

    build_model(r, seed)
    r.mark("weights")

    drv = spec.driver(cell["traffic"]["driver"])
    res = drv.run(r)
    setup_s = r.t_open - t_start
    res["memory_peak_bytes"] = memory_peak(devs)
    r.params = None
    gc.collect()

    comp_s = r.compile_seconds_in_window()
    log(f"setup phases (s): {json.dumps(r.setup_phases())}; compile cache {cache_dir}")
    log(f"window {res['window_s']:.3f}s; compile events inside it: {comp_s:.3f}s")
    for t, ev, d in r.compiles:
        if r.t_open <= t <= (r.t_close or t) and d > 0.001:
            log(f"  compile event in window at +{t - r.t_open:.2f}s: {ev} {d:.3f}s")
    if res.get("extra"):
        log("extra: " + json.dumps(res["extra"]))
    if res.get("delivered"):
        from benchmark.harness.stats import slices

        rows = [(st["t"], st["tokens"]) for st in res["steps"]]
        if any(n for _, n in rows):
            sl = slices(rows, res["t0"], 5.0)[: int(res["window_s"] // 5)]
            log("tokens/s per 5-s slice: " + " ".join(f"{x:.1f}" for x in sl))
    if comp_s > 0.01 * res["window_s"]:
        log(f"FAULT: {comp_s:.2f}s of compilation inside the window (> 1%): the "
            f"compiler's number is not the system's")
        raise SystemExit(5)

    from benchmark.harness import check

    verdict = check.serving(r, res, control=control)
    device["memory_peak_bytes"] = res["memory_peak_bytes"]
    metrics = {}
    units = {m["name"]: m["unit"] for m in cell["end_to_end"] + cell["per_layer"]}
    if not trace:
        vals = dict(res["end_to_end"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
        line = {}
    else:
        from benchmark.harness import trace as tr

        summary = tr.reduce(r.trace_dir, window_s=r.trace_t1 - r.trace_t0,
                            n_devices=cell["chips"])
        if not os.environ.get("BENCH_KEEP_TRACE"):
            shutil.rmtree(r.trace_dir, ignore_errors=True)
        ctx = {"run": r, "res": res, "trace": summary, "device": device,
               "cell": cell, "arch": r.arch, "hf": r.hf}
        for m in cell["per_layer"]:
            v = spec.layer_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        line = {"breakdown": {"device_ops": summary["top_ops"][:10],
                              "idle_gaps": summary["idle_gaps"][:10]}}
    result = {"correct": verdict["correct"], "attempted": res["attempted"],
              "failed": res["failed"], "metrics": metrics, "device": device}
    result.update(line)
    result["check"] = verdict["numbers"]
    for name, v in verdict["numbers"].items():
        log(f"check {name}: {v['value']} limit {v['limit']}")
    text = json.dumps(result)
    if out is not None:
        out.append(result)
    print(text, flush=True)
    return 0


def _merge(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out
