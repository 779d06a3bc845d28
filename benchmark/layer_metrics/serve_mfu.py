"""Whole step's share of the chip's peak: required model FLOPs of every
token processed in the window (each output token at its own context, each
admitted prompt once, from benchmark/arch) over window x chips x peak."""

from benchmark.harness.peaks import peaks


def read(ctx):
    res, arch, hf = ctx["res"], ctx["arch"], ctx["hf"]
    flops = res.get("window_flops")
    if flops is None:
        flops = sum(r["flops"] for r in res["steps"])
    mm = 2 * arch.counts(hf)["matmul_params_per_token"]
    for _, n in res["admits"]:
        flops += mm * n + arch.prefill_attn_flops(hf, n)
    if not flops:
        return None
    peak = peaks(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * flops / (res["window_s"] * peak)
