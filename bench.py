"""Training throughput of one preset on the attached TPU, one process.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": ...,
   "device": {"platform", "kind", "count"}, "detail": {...}}

vs_baseline is null: the reference repo is empty (SURVEY.md §0) and
publishes no numbers to compare against, so the value stands alone.

There is no fallback: without a TPU the script exits non-zero and
prints no number. It starts no child process — the chip belongs to one
process at a time.
"""

from __future__ import annotations

# shellac: ignore[SH015] — the shellac_bench_* gauges are bench-local
# headline series (set once per run), deliberately outside the serving
# bundle layer; cataloged in docs/observability.md §Bench.

import argparse
import json
import sys
import time

import jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--packed", action="store_true",
                    help="packed-sequence batch (segment_ids set)")
    ap.add_argument("--quant", choices=["int8", "int8_bwd"], default=None)
    ap.add_argument("--fused-loss", type=int, default=None,
                    dest="fused_loss", metavar="CHUNK",
                    help="vocab-chunked fused cross-entropy")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--preset", default="shellac-1b",
                    help="model preset (e.g. shellac-mla-2b)")
    args = ap.parse_args(argv)

    from shellac_tpu.utils.compile_cache import enable_compile_cache
    from shellac_tpu.utils.metrics import (
        device_info,
        peak_bf16_flops,
        train_flops_per_token,
    )

    enable_compile_cache()
    device = device_info()
    if device["platform"] != "tpu":
        print(f"bench.py measures the TPU and jax found {device}; "
              "no number printed", file=sys.stderr)
        return 2

    from shellac_tpu import get_model_config
    from shellac_tpu.config import TrainConfig
    from shellac_tpu.models.transformer import num_params
    from shellac_tpu.training import init_train_state, make_train_step

    cfg = get_model_config(args.preset)
    # Batch 6 x seq 2048 fills one v5e's 16 GB for shellac-1b with bf16
    # adam mu and the Pallas flash backward; the 2.4B MLA preset fits 4.
    batch = args.batch or (4 if args.preset == "shellac-mla-2b" else 6)
    seq, steps = 2048, 10
    tcfg = TrainConfig(warmup_steps=10, total_steps=1000, quant=args.quant,
                       fused_loss_chunk=args.fused_loss)
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    step = make_train_step(cfg, tcfg)

    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )
    batch_data = {"inputs": tokens, "targets": tokens}
    if args.packed:
        # Four packed documents per row, boundaries off block edges —
        # the pretraining-default shape; exercises the segment-masked
        # flash kernel path.
        import numpy as np

        bounds = [0, seq // 4 + 37, seq // 2 + 11, 3 * seq // 4 + 5, seq]
        seg = np.zeros((batch, seq), np.int32)
        for i in range(4):
            seg[:, bounds[i]:bounds[i + 1]] = i
        batch_data["segment_ids"] = jax.numpy.asarray(seg)

    # Warmup: compile + first step, outside the timed window.
    t0 = time.perf_counter()
    state, metrics = step(state, batch_data)
    jax.block_until_ready(metrics["loss"])
    warmup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_data)
    final_loss = float(jax.block_until_ready(metrics["loss"]))
    dt = time.perf_counter() - t0

    tok_s = batch * seq * steps / dt
    n_params = num_params(state.params)
    flops_per_token = train_flops_per_token(
        n_params, cfg.n_layers, cfg.d_model, seq
    )
    mfu = tok_s * flops_per_token / (
        device["count"] * peak_bf16_flops(device["kind"])
    )

    variant = ("_packed" if args.packed else "") + (
        f"_{args.quant}" if args.quant else ""
    ) + (f"_fused{args.fused_loss}" if args.fused_loss else "")
    detail = {
        "params": n_params,
        "step_time_s": round(dt / steps, 4),
        "warmup_s": round(warmup_s, 2),
        "loss": round(final_loss, 4),
        "mfu": round(mfu, 4),
        "batch": batch,
        "remat_policy": cfg.remat_policy,
        "fused_loss": args.fused_loss,
        "quant": args.quant,
        "packed": bool(args.packed),
    }
    # Deposit the headline into the shared obs registry and snapshot it
    # into the output: one exposition path for bench, train, and serve
    # numbers.
    from shellac_tpu.obs import get_registry

    reg = get_registry()
    reg.gauge("shellac_bench_train_tokens_per_sec",
              "Headline training-bench throughput").set(tok_s)
    reg.gauge("shellac_bench_train_step_seconds",
              "Headline training-bench mean step time").set(dt / steps)
    reg.gauge("shellac_bench_train_mfu",
              "Headline training-bench MFU").set(mfu)
    detail["metrics"] = reg.snapshot()
    print(json.dumps({
        "metric": f"train_throughput_{cfg.d_model}d{cfg.n_layers}L_seq{seq}"
                  f"{variant}_tpu",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        "device": device,
        "detail": detail,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
