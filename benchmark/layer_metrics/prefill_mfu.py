"""Model step, serving: share of the chip's peak that the prompt programs
reach: required model FLOPs of the REAL tokens of the window's sound
``prefill`` and ``chunk`` launches (as ``serve_mfu`` reckons a prompt, from
benchmark/arch: 2 x matmul parameters a token, and the attention of rows
``offset .. offset + tokens`` = that of a prompt of ``offset + tokens`` less
that of one of ``offset``) over their device seconds x peak. Nothing comes
from the program but each row's ``tokens``, ``offset`` and times
(benchmark/harness/launches.py). Padding to a bucket, masked tiles and a
discarded unembedding all lower it: it bounds what a prefill change can
claim. ``None`` where the program keeps no launch rows, or ran no prompt."""

from benchmark.harness import launches as ln
from benchmark.harness.peaks import peaks


def read(ctx):
    rows = ln.landed(ctx["res"])
    secs = ln.sound_seconds(rows, ln.PROMPT_KINDS) if rows else 0.0
    if not secs:
        return None
    arch, hf = ctx["arch"], ctx["hf"]
    mm = 2 * arch.counts(hf)["matmul_params_per_token"]
    flops = 0
    for r, sound in rows:
        if sound and r[ln.KIND] in ln.PROMPT_KINDS:
            n, off = r[ln.ATTRS]["tokens"], r[ln.ATTRS]["offset"]
            flops += mm * n + (arch.prefill_attn_flops(hf, off + n)
                               - arch.prefill_attn_flops(hf, off))
    peak = peaks(ctx["device"]["kind"])["bf16_flops"] * ctx["device"]["count"]
    return 100.0 * flops / (secs * peak)
