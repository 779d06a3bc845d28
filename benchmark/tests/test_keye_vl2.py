"""The cell ``keye-vl-2.0-30b-a3b-batch-long`` on the tiny CPU rehearsal, and
its two readers. Run by hand with the other tests here:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import peaks, program_spans, spec, trace  # noqa: E402
from benchmark.tests import tiny, tiny_keye_vl2  # noqa: E402

# conftest.py enters the shrinks it knows into tiny.CONFIG and may not be
# edited by the PR that adds a cell: this one enters its own, as every test
# file is imported before any test runs.
tiny.CONFIG.setdefault("keye-vl-2.0-30b-a3b", tiny_keye_vl2.CONFIG)

READERS = ["dsa_decode_step_roofline", "dsa_selected_rows_pct.tok"]
KERNELS = ["dsa_masked_flash_roofline", "dsa_index_scores_roofline"]


@pytest.fixture(scope="module")
def traced():
    """``--trace 1`` on the CPU, as test_evabyte.py rehearses it: the capture
    runs, its reduction (which wants a device plane) is replaced by a summary
    with one decode program of 1 ms a tick, and the CPU is lent a chip's
    peaks."""
    mp = pytest.MonkeyPatch()
    seen = []
    real_driver = spec.driver

    def driver(kind):
        mod = real_driver(kind)
        run = mod.run

        def keep(r):
            seen.append(run(r))
            return seen[-1]

        mod.run = keep
        return mod

    mp.setattr(spec, "driver", driver)
    v5e = peaks.peaks("TPU v5 lite")
    mp.setattr(peaks, "peaks", lambda kind: v5e)
    ticks = tiny_keye_vl2.CONFIG["serving"]["decode_ticks"]
    mp.setattr(trace, "reduce", lambda d, window_s, n_devices=1: {
        "busy_s": 0.0, "window_s": window_s, "idle_gaps": [],
        # the two kernels, as a TPU capture names them, 1 s each
        "top_ops": [["dsa_masked_flash.3", 1.0], ["dsa_index_scores", 1.0]],
        "programs": {"decode": {"runs": 1, "seconds": 1e-3 * ticks,
                                "median_s": 1e-3 * ticks}}})
    out = []
    try:
        assert tiny_keye_vl2.rehearse(2 ** 31 + 31, 2.0, True, out=out) == 0
        yield out[0], seen[-1]
    finally:
        mp.undo()


def test_the_cell_is_correct_and_reports_every_tok_metric(traced):
    line, _ = traced
    assert line["correct"] and line["failed"] == 0
    cell = spec.cell(tiny_keye_vl2.WORKLOAD)
    want = {m["name"] for m in cell["per_layer"]}
    assert set(READERS + KERNELS) <= want and "serve_mfu.tok" in want
    assert not {"decode_step_roofline", "eva_decode_step_roofline"} & want
    # the CPU has no memory_stats(): that reader alone finds nothing here
    assert want - set(line["metrics"]) == {"hbm_peak_gb.tok"}
    assert set(cell["limits"]["limits"]) == {"logit_gap_mean"}


def test_the_two_readers_return_numbers_on_the_rehearsal(traced):
    line, res = traced
    got = {k: v["value"] for k, v in line["metrics"].items()}
    recs = program_spans.records(res)
    scored = program_spans.total(recs, "dsa_index_rows")
    kept = program_spans.total(recs, "dsa_selected_rows")
    assert 0 < kept < scored
    assert got["dsa_selected_rows_pct.tok"] == pytest.approx(100.0 * kept / scored)
    # contexts of 32-190 against 16 rows kept
    assert 8 < got["dsa_selected_rows_pct.tok"] < 50
    assert 0 < got["dsa_decode_step_roofline"]


def test_the_kernel_readers_count_the_chunks_of_the_traced_window(traced):
    """Every prefill_dispatch span carries its chunk's tokens (and offset past
    the first): the least work of those chunks over the kernels' 1 s each."""
    line, res = traced
    got = {k: v["value"] for k, v in line["metrics"].items()}
    cell = spec.cell(tiny_keye_vl2.WORKLOAD)
    cell["config"] = {**cell["config"], **{k: v for k, v in tiny_keye_vl2.CONFIG.items()
                                           if k not in ("serving", "sa_config")},
                      "sa_config": {**cell["config"]["sa_config"],
                                    **tiny_keye_vl2.CONFIG["sa_config"]}}
    arch, hf = spec.arch(cell["config"]), spec.hf_keys(cell["config"])
    chunks = [(sp[4].get("offset", 0), sp[4]["tokens"])
              for r in program_spans.records(res) for sp in r.spans
              if sp[0] == "engine.prefill_dispatch"]
    assert chunks and any(o > 0 for o, _ in chunks)
    assert all(0 < n <= tiny_keye_vl2.CONFIG["serving"]["prefill_chunk"] for _, n in chunks)
    pk = peaks.peaks("TPU v5 lite")
    for name, work in (("dsa_masked_flash_roofline", arch.chunk_attend_work),
                       ("dsa_index_scores_roofline", arch.chunk_index_work)):
        least = sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
                    for f, b in (work(hf, o, n) for o, n in chunks))
        assert got[name] == pytest.approx(100.0 * least, rel=1e-6) and got[name] > 0


def test_the_two_readers_return_none_on_records_without_the_counts(traced, monkeypatch):
    _, res = traced
    recs = program_spans.records(res)
    for r in recs:                      # the parent's records: no such counts
        monkeypatch.setattr(r, "counts", {k: v for k, v in r.counts.items()
                                          if not k.startswith("dsa_")})
    run = type("R", (), {"trace_t0": res["t0"], "trace_t1": res["t0"] + res["window_s"]})()
    cell = spec.cell(tiny_keye_vl2.WORKLOAD)
    ctx = {"res": res, "run": run, "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"decode": {"runs": 1, "median_s": 1e-3}}},
           "arch": spec.arch(cell["config"]), "hf": spec.hf_keys(cell["config"])}
    assert all(spec.layer_reader(n)(ctx) is None for n in READERS + KERNELS)
    # a trace without the kernels (the parent's), or spans without `tokens`
    assert all(spec.layer_reader(n)(ctx) is None for n in KERNELS)
    ctx["trace"]["top_ops"] = [["dsa_masked_flash", 1.0], ["dsa_index_scores", 1.0]]
    assert all(spec.layer_reader(n)(ctx) is not None for n in KERNELS)
    for r in recs:
        monkeypatch.setattr(r, "spans", [
            (*sp[:4], {k: v for k, v in sp[4].items() if k != "tokens"}) for sp in r.spans])
    assert all(spec.layer_reader(n)(ctx) is None for n in KERNELS)
    # ... nor on a program that keeps no ring at all
    from shellac_tpu.obs import get_registry

    monkeypatch.delattr(get_registry(), "step_records")
    assert all(spec.layer_reader(n)(ctx) is None for n in READERS + KERNELS)


def test_counts_follow_the_equations():
    cell = spec.cell(tiny_keye_vl2.WORKLOAD)
    arch, hf = spec.arch(cell["config"]), spec.hf_keys(cell["config"])
    c = arch.counts(hf)
    # ISSUE 31's arithmetic at the published widths, 6 layers
    assert c["layer_matmul_params"] == 18874368 + 2260992 + 262144 + 603979776
    assert c["kv_bytes_per_token"] == 13056 and c["index_bytes_per_row"] == 6 * 128
    assert c["kv_bytes_per_row"] == 6 * 2048
    assert arch.token_flops(hf, 10000) - arch.token_flops(hf, 9999) == c["index_flops_per_key"]
    assert arch.token_flops(hf, 2000) - arch.token_flops(hf, 1999) == (
        c["index_flops_per_key"] + c["attn_flops_per_key"])
    n = 5000
    assert arch.prefill_attn_flops(hf, n) == sum(
        c["index_flops_per_key"] * (t + 1) + c["attn_flops_per_key"] * min(t + 1, 2048)
        for t in range(n))
    # 64 routed rows touch ~51.6 of 128 experts a layer
    assert arch.tick_expert_bytes(hf, 8) / (6 * c["expert_bytes"]) == pytest.approx(51.6, abs=0.1)
