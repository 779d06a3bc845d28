"""The readers of the engine's own device timeline (the launch rows of the
step records), on the tiny CPU rehearsal and on rows written by hand. Run by
hand with the other tests here:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import types

import pytest

from benchmark.harness import launches as ln
from benchmark.harness import program_spans, spec
from benchmark.tests.test_program_spans import CELLS, traced_run  # noqa: F401

SIX = ["window_device_ms_per_tick.tok", "prefill_device_ms_per_ktok.tok",
       "prefill_device_pct.tok", "prefill_mfu.tok", "device_drained_pct.tok",
       "launch_late_pct.tok"]
MS = 1_000_000


def test_the_six_are_entered_for_every_cell_that_reports_what_they_move():
    bench = spec.benchmark()
    cells = [w["name"] for w in bench["workloads"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in SIX:
        m = by_name[name]
        assert m["workloads"] == cells and m["moves"] == "serve_tok_s"
        assert m["source"] == "program_span"
    assert [m["name"] for m in bench["per_layer"][-6:]] == SIX


@pytest.mark.parametrize("workload", CELLS)
def test_the_six_readers_report_on_the_rehearsal(workload, traced_run):  # noqa: F811
    line, res = traced_run(workload)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(SIX) <= set(got), sorted(got)
    assert got["window_device_ms_per_tick.tok"] > 0
    assert got["prefill_device_ms_per_ktok.tok"] > 0 and got["prefill_mfu.tok"] > 0
    for name in ("prefill_device_pct.tok", "device_drained_pct.tok", "launch_late_pct.tok"):
        assert 0 <= got[name] <= 100
    rows = ln.landed(res)
    # rows are the programs the window's steps dispatched, in order
    seqs = [r[ln.SEQ] for r, _ in rows]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert all(r[ln.BUSY_FROM] <= r[ln.DONE] and r[ln.DISPATCHED] <= r[ln.DONE]
               for r, _ in rows)
    spans = [sp for rec in program_spans.records(res) for sp in rec.spans]
    windows = [sp for sp in spans if sp[0] == "engine.dispatch_window"]
    assert {sp[4]["launch"] for sp in windows} >= {
        r[ln.SEQ] for r, _ in rows if r[ln.KIND] == "window"}
    prompts = [r for r, _ in rows if r[ln.KIND] in ln.PROMPT_KINDS]
    assert prompts and all(r[ln.ATTRS]["tokens"] <= r[ln.ATTRS]["bucket"]
                           and "jit_" in r[ln.PROGRAM] for r in prompts)


def _row(seq, kind, dispatched, busy_from, done, late=False, **attrs):
    return [seq, kind, "jit__x", dispatched * MS, busy_from * MS, done * MS, late, attrs]


def _ctx(monkeypatch, rows, window_ms, trace_t1=None, records=None):
    recs = records if records is not None else [types.SimpleNamespace(launches=rows)]
    monkeypatch.setattr(program_spans, "records", lambda res: recs)
    res = {"t0": 0.0, "window_s": window_ms / 1e3}
    arch = types.SimpleNamespace(
        counts=lambda hf: {"matmul_params_per_token": 1e9},
        prefill_attn_flops=lambda hf, n: 1e3 * n * (n + 1) // 2)
    return {"res": res, "run": types.SimpleNamespace(trace_t1=trace_t1), "arch": arch,
            "hf": {}, "device": {"kind": "TPU v5 lite", "count": 1}}


def _read(name, ctx):
    return spec.layer_reader(name)(ctx)


def test_a_program_without_rows_leaves_all_six_out(monkeypatch):
    ctx = _ctx(monkeypatch, None, 100, records=[types.SimpleNamespace(spans=[], counts={})])
    assert all(_read(n, ctx) is None for n in SIX)
    ctx = _ctx(monkeypatch, None, 100, records=[])
    assert all(_read(n, ctx) is None for n in SIX)
    # rows that never landed, or landed outside the window, read nothing either
    ctx = _ctx(monkeypatch, [_row(1, "window", 1, 0, 0, ticks=2),
                             _row(2, "window", 150, 150, 190, ticks=2)], 100)
    assert all(_read(n, ctx) is None for n in SIX)


def test_only_sound_launches_are_timed(monkeypatch):
    rows = [
        _row(1, "window", 0, 0, 10, ticks=2),                  # no predecessor: unsound
        _row(2, "prefill", 1, 10, 14, tokens=100, offset=0),   # sound, 4 ms
        _row(3, "window", 2, 14, 34, ticks=2),                 # sound, 10 ms a tick
        _row(4, "chunk", 3, 34, 40, late=True, tokens=50, offset=100),   # late: unsound
        _row(5, "window", 4, 40, 90, ticks=2),                 # after a late one: unsound
        _row(6, "chunk", 5, 90, 96, tokens=50, offset=100),    # sound, 6 ms
        _row(8, "window", 6, 96, 99, ticks=2),                 # row 7 is missing: unsound
    ]
    ctx = _ctx(monkeypatch, rows, 100)
    assert [s for _, s in ln.landed(ctx["res"])] == [False, True, True, False, False, True,
                                                     False]
    assert _read("window_device_ms_per_tick.tok", ctx) == pytest.approx(10.0)
    assert _read("prefill_device_ms_per_ktok.tok", ctx) == pytest.approx(10.0 / 0.150)
    assert _read("prefill_device_pct.tok", ctx) == pytest.approx(100 * 10 / 30)
    assert _read("launch_late_pct.tok", ctx) == pytest.approx(100 / 7)
    flops = 2e9 * 150 + 1e3 * (100 * 101 // 2) + 1e3 * (150 * 151 // 2 - 100 * 101 // 2)
    assert _read("prefill_mfu.tok", ctx) == pytest.approx(100 * flops / (0.010 * 197e12))
    # the window is the driver's: a slice of it can be asked for
    assert len(ln.landed(ctx["res"], t0=0.012, t1=0.050)) == 3


def test_drained_time_leaves_out_the_interval_that_holds_the_stop_of_the_trace(monkeypatch):
    rows = [
        _row(1, "window", 0, 0, 10, ticks=2),
        _row(2, "window", 12, 12, 22, ticks=2),       # 2 ms drained before it
        _row(3, "window", 20, 22, 32, ticks=2),       # queued behind: none
        _row(4, "window", 72, 72, 82, ticks=2),       # 40 ms: the profiler stopping
        _row(5, "window", 85, 85, 95, ticks=2),       # 3 ms
    ]
    ctx = _ctx(monkeypatch, rows, 100)
    assert _read("device_drained_pct.tok", ctx) == pytest.approx(100 * 45 / 95)
    ctx = _ctx(monkeypatch, rows, 100, trace_t1=0.040)
    assert _read("device_drained_pct.tok", ctx) == pytest.approx(100 * 5 / 55)
    # the stop can fall inside a launch (the host stalls, the program is found
    # finished late): that launch's stretch is what is left out
    ctx = _ctx(monkeypatch, rows, 100, trace_t1=0.075)
    assert _read("device_drained_pct.tok", ctx) == pytest.approx(100 * 45 / 85)
