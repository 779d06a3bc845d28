"""Share of its roofline that a decode tick of a model with a learned
sparse-attention indexer reaches: the least time the chip could take for one
tick over the measured device time of a tick. The least time is the larger of
FLOPs over peak and bytes over bandwidth, with a tick's bytes = the non-expert
weights and the head once + one expert's bytes x layers x E (1 - (1 - k/E)^r),
r the tick's valid rows (uniform routing, the law that touches the most
experts, so the same yardstick whatever the program reads) + one index key's
bytes x layers x the rows the tick's indexers scored + one k/v row's bytes x
layers x the rows its queries then attended: the program's counts
``dsa_index_rows`` and ``dsa_selected_rows`` over the ticks
(``decode_slot_ticks`` / slots) of the step records in the traced window, and
the FLOPs from the same counts. It counts what the equations must read,
whatever implements them; ``decode_step_roofline`` would count every held
expert and a whole row for every resident token, more than twice this model's
least bytes. ``None`` where the program keeps no such counts."""

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
    scored = ps.total(recs, "dsa_index_rows")
    kept = ps.total(recs, "dsa_selected_rows")
    valid = ps.total(recs, "decode_valid_ticks")
    if not ticks or not scored or not kept:
        return None
    c, pk = arch.counts(hf), peaks(ctx["device"]["kind"])
    bytes_ = (c["shared_weight_bytes"] + arch.tick_expert_bytes(hf, valid / ticks)
              + (c["index_bytes_per_row"] * scored + c["kv_bytes_per_row"] * kept) / ticks)
    flops = (2 * c["matmul_params_per_token"] * valid + c["index_flops_per_key"] * scored
             + c["attn_flops_per_key"] * kept) / ticks
    least = max(flops / pk["bf16_flops"], bytes_ / pk["hbm_bytes_per_s"])
    return 100.0 * least / (p["median_s"] / res["decode_ticks"])
