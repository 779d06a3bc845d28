"""Break a train step into fwd / fwd+bwd / full-step timings.

Timings route through the shared obs registry (the same
`shellac_*` exposition path serving and training use) and print as
one JSON document, so a profiling run's numbers land in the same
machine-readable shape as every BENCH_* artifact instead of bare
stdout prose.

`--capture DIR` additionally wraps the full-step timing loop in a
`jax.profiler` trace — the capture is written under DIR and is
consumable VERBATIM by `python -m shellac_tpu trace-report <dir>`
(add `--report` to run the analysis inline).
"""

# shellac: ignore[SH015] — shellac_profile_section_seconds lives in a
# script-local Registry (never the process-global one) and exists only
# inside this script's JSON output; cataloged in docs/observability.md
# §Bench.

import argparse
import json
import time

import jax

from shellac_tpu.obs import Registry, log_buckets


def _fence(out):
    """Fence async dispatch for timing: the device work behind `out`
    is done when this returns."""
    jax.block_until_ready(out)


def timeit(f, *args, n=10):
    """Mean wall seconds per call, compile excluded: one warmup call,
    then n timed calls behind the fence."""
    out = f(*args)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    _fence(out)
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser(
        description="time fwd / fwd+bwd / full train step "
                    "(optionally under a jax.profiler capture)")
    ap.add_argument("--model", default="shellac-1b",
                    help="model preset (see `python -m shellac_tpu "
                         "info`)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=10,
                    help="timed calls per section")
    ap.add_argument("--capture", default=None, metavar="DIR",
                    help="wrap the full-step loop in a jax.profiler "
                         "trace written under DIR (then: python -m "
                         "shellac_tpu trace-report DIR)")
    ap.add_argument("--report", action="store_true",
                    help="with --capture: run trace-report on the "
                         "capture and embed the analysis in the "
                         "output JSON")
    args = ap.parse_args()

    from shellac_tpu import get_model_config
    from shellac_tpu.config import TrainConfig
    from shellac_tpu.models import transformer
    from shellac_tpu.training import init_train_state, make_train_step
    from shellac_tpu.training.losses import cross_entropy
    from shellac_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_model_config(args.model)
    tcfg = TrainConfig(warmup_steps=10, total_steps=1000)
    batch, seq = args.batch, args.seq
    params = jax.jit(transformer.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(0)
    )
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )
    data = {"inputs": tokens, "targets": tokens}

    def loss_fn(params, batch):
        logits = transformer.forward(cfg, params, batch["inputs"])
        loss, _ = cross_entropy(logits, batch["targets"], None, 0.0)
        return loss

    fwd = jax.jit(loss_fn)
    grad = jax.jit(lambda p, b: jax.grad(loss_fn)(p, b))
    step = make_train_step(cfg, tcfg)

    # Every section lands in one registry (the PR 3 path), so the
    # output carries the same series names a /metrics scrape would.
    reg = Registry()
    hist = reg.histogram(
        "shellac_profile_section_seconds",
        "Wall seconds per call of one profiled section",
        labels=("section",),
        buckets=log_buckets(0.0001, 60.0, per_decade=4),
    )

    def record(section, seconds):
        hist.labels(section=section).observe(seconds)
        return round(seconds, 6)

    timings = {}
    timings["fwd_s"] = record("fwd", timeit(fwd, params, data,
                                            n=args.iters))
    timings["fwd_bwd_s"] = record("fwd_bwd", timeit(grad, params, data,
                                                    n=args.iters))
    del params

    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    s2, m = step(state, data)
    _fence(m["loss"])
    if args.capture:
        jax.profiler.start_trace(args.capture)
    t0 = time.perf_counter()
    for _ in range(args.iters):
        s2, m = step(s2, data)
    _fence(m["loss"])
    t_step = (time.perf_counter() - t0) / args.iters
    if args.capture:
        jax.profiler.stop_trace()
    timings["full_step_s"] = record("full_step", t_step)
    timings["optimizer_etc_s"] = round(
        t_step - timings["fwd_bwd_s"], 6)

    out = {
        "model": args.model,
        "batch": batch,
        "seq": seq,
        "iters": args.iters,
        "timings": timings,
        "registry": reg.snapshot(),
    }
    if args.capture:
        out["capture"] = args.capture
        if args.report:
            from shellac_tpu.obs import tracereport

            try:
                out["trace_report"] = tracereport.analyze(args.capture)
            except (OSError, EOFError, ValueError) as e:
                out["trace_report"] = {"error": str(e)}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
