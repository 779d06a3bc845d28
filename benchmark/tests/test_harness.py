"""Tests of the yardstick itself. Run by hand, not part of tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark.harness import spec, stats, trace, traffic  # noqa: E402
from benchmark.harness.drivers import closed_engine  # noqa: E402
from benchmark.tests import tiny  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------- traffic

@pytest.mark.parametrize("name", ["batch", "chat"])
def test_multiset_is_the_same_for_every_seed_and_the_order_differs(name):
    t = spec.load_json(os.path.join(spec.BENCH, "traffic", name + ".json"))
    n = t["prompt_tokens"]["n"]

    def draw(seed):
        m = traffic.Mix(t, 1000, seed)
        reqs = [m.next() for _ in range(n)]
        return [len(ids) for _, ids, _ in reqs], [o for _, _, o in reqs]

    p1, o1 = draw(1)
    p2, o2 = draw(2 ** 31 + 7)
    assert sorted(p1) == sorted(p2) == traffic.lengths(t["prompt_tokens"])
    assert sorted(o1) == sorted(o2) == traffic.lengths(t["output_tokens"])
    assert p1 != p2 and o1 != o2
    assert min(p1) >= t["prompt_tokens"]["min"] and max(p1) <= t["prompt_tokens"]["max"]


def test_batch_medians_are_the_issue_s():
    t = spec.load_json(os.path.join(spec.BENCH, "traffic", "batch.json"))
    p, o = traffic.lengths(t["prompt_tokens"]), traffic.lengths(t["output_tokens"])
    assert 480 <= np.median(p) <= 545 and 145 <= np.median(o) <= 170


def test_arrival_gaps_are_a_fixed_set_with_the_mix_s_rate():
    t = spec.load_json(os.path.join(spec.BENCH, "traffic", "chat.json"))
    a, b = traffic.Mix(t, 10, 1), traffic.Mix(t, 10, 2)
    n = t["arrivals"]["n"]
    da = np.diff([0.0] + [a.next_due() for _ in range(n)])
    db = np.diff([0.0] + [b.next_due() for _ in range(n)])
    assert np.allclose(sorted(da), sorted(db)) and not np.allclose(da, db)
    assert abs(da.sum() - n / t["rate_rps"]) < 1e-9


def test_warmup_reaches_every_bucket_and_every_page_count():
    t = {"prompt_tokens": {"min": 128, "max": 2048}, "output_tokens": {"min": 64, "max": 384}}
    s = {"decode_ticks": 8, "block_size": 256, "prefill_chunk": None}
    reqs = traffic.warmup_requests(t, s, 2560)
    assert [n for n, _, end in reqs if end] == [128, 256, 512, 1024, 2048]
    pages = sorted(-(-(n + m + 1) // 256) for n, m, end in reqs if not end)
    assert pages == list(range(1, 11))
    chunked = traffic.warmup_prompt_lengths(t, 512)
    assert {512 + b for b in (16, 32, 64, 128, 256, 512)} <= set(chunked)


# ------------------------------------------- emitted tokens at window edges

class _Req:
    def __init__(self, rid, plen):
        self.rid, self.tokens, self.out = rid, [0] * plen, []


def test_tokens_are_counted_as_emitted_not_at_completion():
    slots = [_Req("a", 10), _Req("b", 20), None]
    c = closed_engine.Counter(lambda: slots, lambda ctx: 1.0)
    slots[0].out += [1, 2, 3]            # before the window: counted, then dropped
    slots[1].out += [1]
    assert c.after_step([])[0] == 4
    window = 0
    slots[0].out += [4, 5]               # step 1 inside the window
    slots[1].out += [2, 3]
    window += c.after_step([])[0]
    done = slots[0]
    done.out += [6]                      # step 2: "a" finishes with its 6th token
    slots[0] = _Req("c", 5)
    slots[0].out += [9]                  # and "c" is admitted with its first token
    slots[1].out += [4, 5]
    n, occ, resident, _ = c.after_step([("a", done.out)])
    window += n
    assert window == 4 + 4               # not 6 for "a": 3 were emitted before
    assert occ == 2 and resident == (5 + 1) + (20 + 5)
    assert c.new == ["c"]


# ------------------------------------------------------------------ counts

def _hf(name):
    return spec.hf_keys(spec.load_json(os.path.join(spec.BENCH, "configs", name + ".json")))


def test_mistral_counts_match_hand_arithmetic():
    hf = _hf("mistral-7b")
    c = spec.arch({"arch": "mistral"}).counts(hf)
    assert c["layer_matmul_params"] == 218_103_808          # 218.1 M a layer
    assert c["kv_bytes_per_token"] == 16 * 4096             # 4 KiB x 16 layers
    assert abs(c["params"] - (16 * 218.1e6 + 2 * 131.07e6)) < 2e6   # 3.75 B


def test_deepseek_counts_match_hand_arithmetic():
    hf = _hf("deepseek-v2-lite")
    c = spec.arch({"arch": "deepseek_v2"}).counts(hf)
    assert round(c["layer_matmul_params"] / 1e6, 1) == 584.8       # 585 M a layer
    assert round(c["dense_layer_matmul_params"] / 1e6, 1) == 81.0
    assert c["kv_bytes_per_token"] == 576 * 2 * 9                   # 10.1 KiB
    assert abs(c["params"] - 5.18e9) < 0.02e9


# ------------------------------------------------------------------- trace

def test_trace_reduction_on_a_recorded_trace():
    planes = json.load(open(os.path.join(HERE, "fixtures", "trace_cut.json")))
    dev = next(p for p in planes if trace.DEVICE_PLANE.match(p["name"]))
    ops = next(l["events"] for l in dev["lines"] if l["name"] == trace.OPS_LINE)
    span = (max(s + d for _, s, d in ops) - min(s for _, s, d in ops)) / 1e9
    out = trace.reduce_planes(planes, window_s=span)
    assert 0 < out["busy_s"] <= span
    naive = sum(d for _, s, d in ops) / 1e9
    assert abs(sum(v for _, v in out["top_ops"]) - out["busy_s"]) < 0.02 * out["busy_s"] \
        or naive >= out["busy_s"]           # self times add up to the union
    assert out["programs"] and all(v["runs"] > 0 for v in out["programs"].values())
    assert len(out["idle_gaps"]) <= 10 and all(g[1] >= 0 for g in out["idle_gaps"])


def test_busy_is_a_union_and_self_time_excludes_children():
    assert stats.union_seconds([(0, 10), (5, 12), (20, 21)]) == 13
    st = dict(trace.self_times([("while", 0, 100), ("a", 10, 30), ("b", 50, 20)]))
    assert st == {"while": 50, "a": 30, "b": 20}
    k = 10_000                           # ns: realistic gaps, above the 1-us slack
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [("while", 0, 100 * k), ("a", 10 * k, 30 * k),
                                      ("k", 200 * k, 50 * k)]},
        {"name": "XLA Modules", "events": [("jit__decode_impl(1)", 0, 100 * k),
                                          ("jit__prefill_impl(2)", 200 * k, 50 * k)]}]}]
    out = trace.reduce_planes(planes, window_s=250 * k / 1e9)
    assert out["busy_s"] == pytest.approx(150 * k / 1e9)
    assert out["programs"]["decode"]["runs"] == 1 and out["programs"]["prefill"]["runs"] == 1
    # a window cut by the trace's edge does not shorten the tick that is read
    planes[0]["lines"][1]["events"] += [("jit__decode_impl(1)", 300 * k, 100 * k),
                                        ("jit__decode_impl(1)", 400 * k, 100 * k),
                                        ("jit__decode_impl(1)", 500 * k, 7 * k)]
    cut = trace.reduce_planes(planes, window_s=1e-3)["programs"]["decode"]
    assert cut["runs"] == 4 and cut["median_s"] == pytest.approx(100 * k / 1e9)
    assert out["idle_gaps"][0][1] == pytest.approx(100 * k / 1e9)
    assert out["idle_gaps"][0][0] == "host:unattributed|decode_impl(1)>prefill_impl(2)"


# ------------------------------------------------- end to end, tiny, on CPU

def _bench_with_chat():
    """BENCHMARK.json with the open-loop cell's entries merged in."""
    b = spec.benchmark()
    extra = spec.load_json(os.path.join(HERE, "chat_cell.json"))
    for key in ("workloads", "end_to_end", "per_layer"):
        b[key] = b[key] + extra[key]
    return b


CELLS = [w["name"] for w in _bench_with_chat()["workloads"]]
TINY_LIMITS = {"limits": {"logit_gap_max": 1e-3, "logit_gap_mean": 1e-4}}


def _run(workload, seed=5, seconds=2.0, **kw):
    from benchmark.harness import runner

    out = []
    bench = _bench_with_chat()
    cfg = spec.cell(workload, bench)["config_name"]
    kw.setdefault("bench", bench)
    rc = runner.run_cell(workload, seed, seconds, False, require_chip=False,
                         config_override=tiny.CONFIG[cfg], traffic_override=tiny.TRAFFIC,
                         out=out, **kw)
    return rc, out[0]


@pytest.mark.parametrize("workload", CELLS)
def test_each_driver_prints_a_contract_valid_line(workload, capsys):
    rc, res = _run(workload)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line == res
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(res)[-1] == "check"
    cell = spec.cell(workload, _bench_with_chat())
    assert set(res["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    # float32 here: the program picks the reference's token everywhere
    assert res["check"]["logit_gap_max"]["value"] < 1e-3
    assert res["correct"] == bool(cell["limits"].get("limits"))


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_comes_out_as_not_correct(workload, monkeypatch):
    """The reference in the precision below the configuration's (int8 operands
    where the tiny configuration states float32 and the program reads 0) puts
    tokens first that the float32 reference does not: its reading is over any
    limit that the program's float32 reading allows."""
    monkeypatch.setitem(tiny.TRAFFIC, "check_sample", 24)
    rc, res = _run(workload, seconds=4.0, control=True)
    for key in ("logit_gap_max", "logit_gap_mean"):
        program, control = res["check"][key], res["check"]["control_" + key]
        assert program["value"] < 1e-5 < control["value"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_token_altered_where_it_is_produced_fails_the_check(workload, monkeypatch):
    """The fault a serving cell can have: the engine hands out a token other
    than the one it computed. The rest of the run is the ordinary one."""
    from shellac_tpu.inference import batching

    real = batching.BatchingEngine._sync_window

    def altered(self, w):
        per_slot, lps, tl = real(self, w)
        for toks in per_slot:
            if toks:
                toks[0] = (toks[0] + 1) % self.cfg.vocab_size
        return per_slot, lps, tl

    monkeypatch.setattr(batching.BatchingEngine, "_sync_window", altered)
    rc, res = _run(workload)
    assert rc == 0 and res["correct"] is False
    assert res["check"]["logit_gap_max"]["value"] > 0.5


def test_no_accelerator_exits_nonzero_and_prints_nothing(capsys):
    from benchmark.harness import runner

    with pytest.raises(SystemExit) as e:
        runner.run_cell(spec.benchmark()["workloads"][0]["name"], 1, 1.0, False)
    assert e.value.code != 0 and capsys.readouterr().out == ""


# ------------------------------------------------------------ the contract

def test_benchmark_json_keeps_to_the_contract_s_form():
    import re

    b = spec.benchmark()
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= b["run_seconds"] <= 51
    cells = {w["name"]: w for w in b["workloads"]}
    cfgs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"} and name.match(c["name"])
        assert c["file"].startswith("benchmark/") and os.path.exists(os.path.join(spec.ROOT, c["file"]))
        assert 1 <= len(c["why"]) <= 200 and len(c["reduced"]) <= 16
    assert {w["config"] for w in b["workloads"]} == cfgs
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert name.match(m["name"]) and unit.match(m["unit"]) and m["moves"] in e2e
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        for w in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert w in cells and w in moved.get("workloads", cells)
        spec.layer_reader(m["name"])            # a reader exists for it
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for w in cells:
        c = spec.cell(w)
        assert len(c["end_to_end"]) >= 2 and c["per_layer"]
        assert set(c["limits"].get("limits", {})) & {"logit_gap_max", "logit_gap_mean"}


def test_catalog_keys_are_held_unchanged_except_the_reduced_ones():
    """deepseek-v2-lite.json against the catalog row beside the model-configs
    guide, where that guide is installed."""
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(cat):
        pytest.skip("no catalog here")
    row = next(json.loads(l) for l in open(cat) if json.loads(l)["name"] == "DeepSeek-V2-Lite")
    mine = spec.load_json(os.path.join(spec.BENCH, "configs", "deepseek-v2-lite.json"))
    for k, v in row["config"].items():
        if k in mine["reduced"]:
            assert mine["published"][k] == v
        else:
            assert mine[k] == v, k
    assert mine["source"] == row["source_url"]
