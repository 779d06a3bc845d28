"""The cell ``evabyte-batch-bytes`` on the tiny CPU rehearsal, and its two
readers. Run by hand with the other tests here:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import peaks, program_spans, spec, trace  # noqa: E402
from benchmark.tests import tiny_evabyte  # noqa: E402

READERS = ["eva_decode_step_roofline", "eva_summary_rows_pct.tok"]


@pytest.fixture(scope="module")
def traced():
    """``--trace 1`` on the CPU, as test_program_spans.py rehearses it: the
    capture runs, its reduction (which wants a device plane) is replaced by
    a summary with one decode program of 1 ms a tick, and the CPU is lent a
    chip's peaks."""
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
    ticks = tiny_evabyte.CONFIG["serving"]["decode_ticks"]
    mp.setattr(trace, "reduce", lambda d, window_s, n_devices=1: {
        "busy_s": 0.0, "window_s": window_s, "top_ops": [], "idle_gaps": [],
        "programs": {"decode": {"runs": 1, "seconds": 1e-3 * ticks,
                                "median_s": 1e-3 * ticks}}})
    out = []
    try:
        assert tiny_evabyte.rehearse(2 ** 31 + 27, 2.0, True, out=out) == 0
        yield out[0], seen[-1]
    finally:
        mp.undo()


def test_the_cell_is_correct_and_reports_every_tok_metric(traced):
    line, _ = traced
    assert line["correct"] and line["failed"] == 0
    cell = spec.cell(tiny_evabyte.WORKLOAD)
    want = {m["name"] for m in cell["per_layer"]}
    assert set(READERS) <= want and "decode_step_roofline" not in want
    # the CPU has no memory_stats(): that reader alone finds nothing here
    assert want - set(line["metrics"]) == {"hbm_peak_gb.tok"}


def test_the_two_readers_return_numbers_on_the_rehearsal(traced):
    line, res = traced
    got = {k: v["value"] for k, v in line["metrics"].items()}
    recs = program_spans.records(res)
    pooled = program_spans.total(recs, "eva_summary_rows")
    exact = program_spans.total(recs, "eva_window_rows")
    assert pooled > 0 and exact > 0
    assert got["eva_summary_rows_pct.tok"] == pytest.approx(100.0 * pooled / (pooled + exact))
    # prompts of 40-150 against a window of 32: most attended rows are exact
    assert 5 < got["eva_summary_rows_pct.tok"] < 60
    assert got["eva_decode_step_roofline"] > 0


def test_the_two_readers_return_none_on_records_without_the_counts(traced, monkeypatch):
    _, res = traced
    recs = program_spans.records(res)
    for r in recs:                      # the parent's records: no such counts
        monkeypatch.setattr(r, "counts", {k: v for k, v in r.counts.items()
                                          if not k.startswith("eva_")})
    run = type("R", (), {"trace_t0": res["t0"], "trace_t1": res["t0"] + res["window_s"]})()
    ctx = {"res": res, "run": run, "device": {"kind": "TPU v5 lite"},
           "trace": {"programs": {"decode": {"runs": 1, "median_s": 1e-3}}},
           "arch": spec.arch(spec.cell(tiny_evabyte.WORKLOAD)["config"]),
           "hf": spec.hf_keys(spec.cell(tiny_evabyte.WORKLOAD)["config"])}
    assert all(spec.layer_reader(n)(ctx) is None for n in READERS)
    # ... nor on a program that keeps no ring at all
    from shellac_tpu.obs import get_registry

    monkeypatch.delattr(get_registry(), "step_records")
    assert all(spec.layer_reader(n)(ctx) is None for n in READERS)
