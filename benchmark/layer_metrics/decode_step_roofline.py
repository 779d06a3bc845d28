"""Share of its roofline that a decode tick reaches: the least time the chip
could take for one tick (the larger of FLOPs over peak and bytes over
bandwidth: every held weight matrix once, the resident KV once) over the
measured device time of a tick. Bandwidth-bound at these batches."""

from benchmark.harness.peaks import peaks


def read(ctx):
    p = ctx["trace"]["programs"].get("decode")
    res, arch, hf, r = ctx["res"], ctx["arch"], ctx["hf"], ctx["run"]
    rows = [s for s in res["steps"] if r.trace_t0 is not None
            and r.trace_t0 <= s["t"] <= r.trace_t1 and s["occupied"]]
    if not p or not p["runs"] or not rows:
        return None
    c, pk = arch.counts(hf), peaks(ctx["device"]["kind"])
    least = 0.0
    for s in rows:
        ctx_mean = s["resident"] / s["occupied"]
        flops = s["occupied"] * arch.token_flops(hf, ctx_mean)
        bytes_ = c["weight_bytes_per_tick"] + c["kv_bytes_per_token"] * s["resident"]
        least += max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    tick_s = p["median_s"] / res["decode_ticks"]
    return 100.0 * (least / len(rows)) / tick_s
