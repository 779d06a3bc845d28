"""Share of its roofline that a decode tick of an EVA model reaches: the
least time the chip could take for one tick over the measured device time of
a tick. The least time is the larger of FLOPs over peak and bytes over
bandwidth, with a tick's bytes = every read weight matrix once + one row's
bytes (k and v, every layer) x the rows the tick's queries attended: the
program's counts ``eva_window_rows`` + ``eva_summary_rows`` over the ticks
(``decode_slot_ticks`` / slots) of the step records in the traced window. It
counts what the equations must read, whatever implements them.
``decode_step_roofline`` would count a whole row for every resident token,
up to sixteen times this model's least bytes. ``None`` where the program
keeps no such counts."""

from benchmark.harness import program_spans as ps
from benchmark.harness.peaks import peaks


def read(ctx):
    p = ctx["trace"]["programs"].get("decode")
    res, arch, hf, r = ctx["res"], ctx["arch"], ctx["hf"], ctx["run"]
    recs = ps.records(res)
    if not p or not p["runs"] or not recs or r.trace_t0 is None:
        return None
    t0, t1 = int(r.trace_t0 * 1e9), int(r.trace_t1 * 1e9)
    recs = [x for x in recs if t0 <= x.end_ns <= t1]
    ticks = ps.total(recs, "decode_slot_ticks") / res["n_slots"] if recs else 0
    rows = ps.total(recs, "eva_window_rows") + ps.total(recs, "eva_summary_rows")
    valid = ps.total(recs, "decode_valid_ticks")
    if not ticks or not rows:
        return None
    c, pk = arch.counts(hf), peaks(ctx["device"]["kind"])
    bytes_ = c["weight_bytes_per_tick"] + c["kv_bytes_per_row"] * rows / ticks
    flops = (2 * c["matmul_params_per_token"] * valid + c["attn_flops_per_key"] * rows) / ticks
    least = max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * least / (p["median_s"] / res["decode_ticks"])
