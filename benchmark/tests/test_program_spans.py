"""The readers of the program's own step records, on the tiny CPU rehearsal.
Run by hand with the other tests here:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import peaks, program_spans, runner, spec, trace  # noqa: E402
from benchmark.tests import tiny  # noqa: E402

NEW = ["decode_ticks_useful_pct.tok", "prefill_useful_pct.tok", "step_host_ms.tok",
       "block_table_update_ms.tok"]
CELLS = ["mistral-7b-batch", "deepseek-v2-lite-batch"]


@pytest.fixture
def traced_run(monkeypatch):
    """``--trace 1`` on the CPU: the capture runs, its reduction (which wants
    a device plane) is replaced by an empty summary, the CPU is lent a chip's
    peaks, and the driver's records are kept for the test."""
    seen = []
    real_driver = spec.driver

    def driver(kind):
        mod = real_driver(kind)
        run = mod.run

        def keep(r):
            res = run(r)
            seen.append(res)
            return res

        mod.run = keep
        return mod

    monkeypatch.setattr(spec, "driver", driver)
    v5e = peaks.peaks("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks", lambda kind: v5e)
    monkeypatch.setattr(trace, "reduce", lambda d, window_s, n_devices=1: {
        "busy_s": 0.0, "window_s": window_s, "top_ops": [], "programs": {}, "idle_gaps": []})

    def run(workload):
        out = []
        cfg = spec.cell(workload)["config_name"]
        rc = runner.run_cell(workload, 2 ** 31 + 11, 2.0, True, require_chip=False,
                             config_override=tiny.CONFIG[cfg],
                             traffic_override=tiny.TRAFFIC, out=out)
        assert rc == 0
        return out[0], seen[-1]

    return run


@pytest.mark.parametrize("workload", CELLS)
def test_the_four_readers_report_and_the_records_count_what_the_driver_counts(
        workload, traced_run):
    line, res = traced_run(workload)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got), sorted(got)
    for name in NEW:
        assert got[name] >= 0 and (got[name] <= 100 or not name.endswith("_pct.tok"))
    assert 0 < got["decode_ticks_useful_pct.tok"] and 0 < got["prefill_useful_pct.tok"]
    recs = program_spans.records(res)
    assert program_spans.total(recs, "tokens_delivered") == res["delivered"]
    assert len(recs) == len(res["steps"])


def test_a_ring_that_no_longer_reaches_the_window_s_start_reads_nothing(
        traced_run, monkeypatch):
    _, res = traced_run("mistral-7b-batch")
    from shellac_tpu.obs import get_registry

    reg = get_registry()
    recs = program_spans.records(res)
    monkeypatch.setattr(reg, "step_records",
                        collections.deque(recs[-3:], maxlen=3))
    assert program_spans.records(res) is None
    ctx = {"res": res}
    assert all(spec.layer_reader(n)(ctx) is None for n in NEW)
    # ... and neither does a program that keeps no ring (the parent commit)
    monkeypatch.delattr(reg, "step_records")
    assert all(spec.layer_reader(n)(ctx) is None for n in NEW)
