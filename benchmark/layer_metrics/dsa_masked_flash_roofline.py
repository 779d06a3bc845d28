"""Share of its roofline that the kernel ``dsa_masked_flash`` (a prompt chunk's
attention under its queries' choices) reaches over the traced slice: the least
time the chip could take for the work of the prompt chunks dispatched in the
slice, over the kernel's device time there (``trace["top_ops"]`` by the custom
call's name). The work is what the equations need: each query of a chunk
attends the rows it chose, no more than are kept, whatever the kernel computes
(it multiplies every live key tile and masks), and reads every k/v row up to
the chunk's end once (``chunk_attend_work`` in benchmark/arch). The chunks come
from the program's step records: every ``engine.prefill_dispatch`` span of the
traced window carries the chunk's ``tokens`` and, past a prompt's first chunk,
its ``offset``. ``None`` where the trace holds no such kernel or the records
no such attributes."""

from benchmark.harness import program_spans as ps
from benchmark.harness.peaks import peaks

KERNEL = "dsa_masked_flash"


def read(ctx):
    res, arch, hf, r = ctx["res"], ctx["arch"], ctx["hf"], ctx["run"]
    secs = sum(t for name, t in ctx["trace"].get("top_ops", []) if KERNEL in name)
    recs = ps.records(res)
    work = getattr(arch, "chunk_attend_work", None)
    if not secs or not recs or work is None or r.trace_t0 is None:
        return None
    t0, t1 = int(r.trace_t0 * 1e9), int(r.trace_t1 * 1e9)
    chunks = [(sp[4].get("offset", 0), sp[4]["tokens"])
              for x in recs for sp in x.spans
              if sp[0] == "engine.prefill_dispatch" and "tokens" in sp[4]
              and t0 <= sp[1] <= t1]
    if not chunks:
        return None
    pk = peaks(ctx["device"]["kind"])
    least = 0.0
    for offset, tokens in chunks:
        flops, bytes_ = work(hf, offset, tokens)
        least += max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
