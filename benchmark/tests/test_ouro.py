"""The cell ``ouro-2.6b-batch-reason`` on the tiny CPU rehearsal, its reader
and its counts. Run by hand with the other tests here:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import peaks, program_spans, spec, trace  # noqa: E402
from benchmark.tests import tiny, tiny_ouro  # noqa: E402

# conftest.py enters the shrinks it knows into tiny.CONFIG and may not be
# edited by the PR that adds a cell: this one enters its own, as every test
# file is imported before any test runs.
tiny.CONFIG.setdefault("ouro-2.6b", tiny_ouro.CONFIG)

READER = "loop_passes_per_token.tok"


@pytest.fixture(scope="module")
def traced():
    """``--trace 1`` on the CPU, as test_keye_vl2.py rehearses it: the capture
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
    ticks = tiny_ouro.CONFIG["serving"]["decode_ticks"]
    mp.setattr(trace, "reduce", lambda d, window_s, n_devices=1: {
        "busy_s": 0.0, "window_s": window_s, "idle_gaps": [], "top_ops": [],
        "programs": {"decode": {"runs": 1, "seconds": 1e-3 * ticks,
                                "median_s": 1e-3 * ticks}}})
    out = []
    try:
        assert tiny_ouro.rehearse(2 ** 31 + 33, 2.0, True, out=out) == 0
        yield out[0], seen[-1]
    finally:
        mp.undo()


def test_the_cell_is_correct_and_reports_every_tok_metric(traced):
    line, _ = traced
    assert line["correct"] and line["failed"] == 0
    cell = spec.cell(tiny_ouro.WORKLOAD)
    want = {m["name"] for m in cell["per_layer"]}
    assert {READER, "decode_step_roofline", "serve_mfu.tok"} <= want
    assert not {n for n in want if n.startswith(("eva_", "dsa_"))}
    # the CPU has no memory_stats(): that reader alone finds nothing here
    assert want - set(line["metrics"]) == {"hbm_peak_gb.tok"}
    assert set(cell["limits"]["limits"]) == {"logit_gap_max", "logit_gap_mean"}


def test_the_reader_counts_the_passes_of_every_delivered_token(traced):
    line, res = traced
    got = line["metrics"][READER]["value"]
    steps = tiny_ouro.CONFIG["total_ut_steps"]
    recs = program_spans.records(res)
    assert got == pytest.approx(
        program_spans.total(recs, "loop_passes") / res["delivered"])
    # a request's first token comes from its prefill, not from a decode tick
    assert 0.9 * steps < got <= steps
    rows = program_spans.total(recs, "loop_kv_rows")
    ticks = program_spans.total(recs, "decode_valid_ticks")
    # prompts 8-31, outputs 16-48: a tick reads 9-80 rows in each pass
    assert 9 * steps * ticks <= rows <= 80 * steps * ticks


def test_the_reader_returns_none_where_the_program_counts_no_pass(traced, monkeypatch):
    _, res = traced
    read = spec.layer_reader(READER)
    ctx = {"res": res}
    assert read(ctx) is not None
    for r in program_spans.records(res):   # the parent's records: no such count
        monkeypatch.setattr(r, "counts", {k: v for k, v in r.counts.items()
                                          if not k.startswith("loop_")})
    assert read(ctx) is None
    from shellac_tpu.obs import get_registry

    monkeypatch.delattr(get_registry(), "step_records")
    assert read(ctx) is None


def test_counts_follow_the_equations():
    cell = spec.cell(tiny_ouro.WORKLOAD)
    arch, hf = spec.arch(cell["config"]), spec.hf_keys(cell["config"])
    c = arch.counts(hf)
    # ISSUE 33's arithmetic at the published widths, whole
    assert c["layer_params"] == 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert c["params"] == 48 * c["layer_params"] + 2 * 49152 * 2048 + 4097
    assert round(c["params"] * 2 / 1e9, 2) == 5.34
    assert c["kv_bytes_per_token"] == 1572864
    layers = 2 * 48 * c["layer_params"]
    assert round(layers / 1e9, 2) == 4.93
    assert c["weight_bytes_per_tick"] == 4 * layers + 2 * (2048 * 49152 + 4097)
    assert round(c["weight_bytes_per_tick"] / 1e9, 1) == 19.9
    # every pass is counted: a key more costs QK^T and PV in 4 x 48 layers
    assert arch.token_flops(hf, 301) - arch.token_flops(hf, 300) == 4 * 16 * 128 * 48 * 4
    assert arch.token_flops(hf, 0) == 2 * (
        4 * (48 * c["layer_matmul_params"] + 2048) + 2048 * 49152)
    assert arch.prefill_attn_flops(hf, 100) == c["attn_flops_per_key"] * 5050


def test_a_program_without_the_loop_is_refused_at_once(tmp_path):
    """The parent of the PR that brought the loop has no LoopConfig: with
    this file laid over it, ``program_config`` ends the run before any
    weight is made."""
    tree = tmp_path / "checkout"
    (tree / "benchmark" / "arch").mkdir(parents=True)
    (tree / "shellac_tpu").mkdir()
    src = os.path.join(ROOT, "benchmark", "arch", "ouro.py")
    (tree / "benchmark" / "arch" / "ouro.py").write_text(open(src).read())
    (tree / "shellac_tpu" / "config.py").write_text("class ModelConfig:\n    pass\n")
    code = ("import importlib.util as u, sys; sys.path.insert(0, %r); "
            "s = u.spec_from_file_location('a', %r); m = u.module_from_spec(s); "
            "s.loader.exec_module(m); m.program_config({})"
            % (ROOT, str(tree / "benchmark" / "arch" / "ouro.py")))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert p.returncode != 0 and "no looped stack" in p.stderr
