"""Perf sweep for the single-chip training bench.

Usage (one configuration per process — OOM kills the process, so the
sweep loop lives outside):

    python scripts/bench_sweep.py batch=6 remat=1
    python scripts/bench_sweep.py batch=6 remat=1 policy=dots
    python scripts/bench_sweep.py batch=6 quant=int8 packed=1

Prints one JSON line per run; OOM exits nonzero. Sweep driver:

    for b in 4 6 8; do for p in none dots; do
      timeout 580 python scripts/bench_sweep.py batch=$b policy=$p
    done; done | tee sweep.jsonl
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def run(batch, remat, steps=10, seq=2048, policy="none", quant=None,
        packed=False, fused=None):
    from shellac_tpu import get_model_config
    from shellac_tpu.config import TrainConfig
    from shellac_tpu.training import init_train_state, make_train_step
    from shellac_tpu.utils.compile_cache import enable_compile_cache
    from shellac_tpu.utils.metrics import (
        device_info,
        peak_bf16_flops,
        train_flops_per_token,
    )

    enable_compile_cache()
    device = device_info()
    # Resolved before any work: an unknown device_kind is an error.
    peak = device["count"] * peak_bf16_flops(device["kind"])

    cfg = get_model_config("shellac-1b").replace(
        remat=bool(remat), remat_policy=policy
    )
    tcfg = TrainConfig(warmup_steps=10, total_steps=1000, quant=quant,
                       fused_loss_chunk=fused)
    state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
    step = make_train_step(cfg, tcfg)
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )
    data = {"inputs": tokens, "targets": tokens}
    if packed:
        bounds = [0, seq // 4 + 37, seq // 2 + 11, 3 * seq // 4 + 5, seq]
        seg = np.zeros((batch, seq), np.int32)
        for i in range(4):
            seg[:, bounds[i]:bounds[i + 1]] = i
        data["segment_ids"] = jnp.asarray(seg)

    state, metrics = step(state, data)
    jax.block_until_ready(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, data)
    loss = float(metrics["loss"])
    dt = (time.perf_counter() - t0) / steps

    from shellac_tpu.models.transformer import num_params

    n = num_params(state.params)
    flops_tok = train_flops_per_token(n, cfg.n_layers, cfg.d_model, seq)
    tok_s = batch * seq / dt
    print(json.dumps({
        "batch": batch, "remat": bool(remat), "policy": policy,
        "quant": quant, "packed": bool(packed), "fused": fused,
        "tok_s": round(tok_s, 1), "step_s": round(dt, 4),
        "mfu": round(tok_s * flops_tok / peak, 4),
        "loss": round(loss, 3),
        "device": device,
    }))


if __name__ == "__main__":
    kw = dict(kv.split("=") for kv in sys.argv[1:])
    run(
        int(kw.get("batch", 2)),
        int(kw.get("remat", 1)),
        int(kw.get("steps", 10)),
        policy=kw.get("policy", "none"),
        quant=kw.get("quant") or None,
        packed=bool(int(kw.get("packed", 0))),
        fused=int(kw["fused"]) if kw.get("fused") else None,
    )
