"""Incident black box conformance (ISSUE 14).

Unit level (fast, no engines): the incident manager's rate limiting /
retention / atomic bundle write / per-section fault isolation, the
durable event spool's rotation / size cap / redaction / torn-line
recovery, and `trace-report` + `--diff` against the two COMMITTED
fixture captures (including the fixture-regeneration self-test that
keeps them from drifting).

Live level (slow-marked — this file is mid-alphabet and must not eat
the tier-1 wall-clock window; the `incident` CI job runs everything
unfiltered): the full trigger matrix — manual POST /debug/incident,
supervisor scheduler-death and wedge→rebuild, restart-budget
exhaustion, tier severed-stream and exhausted-attempts — plus THE
acceptance scenarios: an SLO page auto-producing a bundle whose
manifest names the violating request's trace id with an embedded
timeline matching /debug/request/<id>, and a SIGKILL'd replica whose
mid-stream request's full timeline is recovered from the on-disk
spool.
"""

import gzip
import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from shellac_tpu.obs import (
    EventSpool,
    FlightRecorder,
    IncidentManager,
    Registry,
    read_spool,
    spool_events_for,
    spool_path,
    tracereport,
)
from shellac_tpu.obs.incident import _SlidingWindow
from shellac_tpu.obs.top import run_top

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
BASE_TRACE = os.path.join(FIXTURES, "decode_base.trace.json.gz")
SCOPED_TRACE = os.path.join(FIXTURES, "decode_scoped.trace.json.gz")
REGRESSED_TRACE = os.path.join(FIXTURES,
                               "decode_regressed.trace.json.gz")


def wait_until(cond, timeout=60.0, interval=0.05, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------
# Incident manager units
# ---------------------------------------------------------------------


class TestIncidentManager:
    def test_sliding_window(self):
        w = _SlidingWindow(2, 10.0)
        assert w.allow(now=0.0) and w.allow(now=1.0)
        assert not w.allow(now=2.0)          # third inside the window
        assert w.allow(now=11.5)             # first aged out

    def test_bundle_write_list_load(self, tmp_path):
        reg = Registry()
        rec = FlightRecorder(registry=reg)
        mgr = IncidentManager(
            str(tmp_path), registry=reg, recorder=rec,
            sections={"metrics": reg.snapshot,
                      "extra": lambda: {"k": 1}},
        )
        bid = mgr.trigger("manual", trace_id="t-1",
                          detail={"note": "x"})
        assert bid and bid.startswith("inc-")
        lst = mgr.list()
        assert [b["id"] for b in lst] == [bid]
        assert lst[0]["trigger"] == "manual"
        full = mgr.load(bid)
        assert full["manifest"]["trace_id"] == "t-1"
        assert full["manifest"]["sections"] == ["extra", "metrics"]
        assert full["extra"] == {"k": 1}
        # The trigger itself landed in the flight recorder, and the
        # counter/histogram series exist.
        evs = [e for e in rec.tail() if e["event"] == "incident"]
        assert evs and evs[-1]["bundle"] == bid
        assert reg.value("shellac_incidents_total",
                         trigger="manual") == 1
        assert mgr.last["id"] == bid

    def test_broken_section_is_isolated(self, tmp_path):
        mgr = IncidentManager(str(tmp_path), sections={
            "good": lambda: [1, 2],
            "bad": lambda: (_ for _ in ()).throw(RuntimeError("boom")),
        })
        full = mgr.load(mgr.trigger("manual"))
        assert full["good"] == [1, 2]
        assert "boom" in full["bad"]["error"]

    def test_rate_limit_drops_and_counts(self, tmp_path):
        reg = Registry()
        mgr = IncidentManager(str(tmp_path), registry=reg,
                              rate=2, rate_window=3600.0)
        assert mgr.trigger("stream-severed")
        assert mgr.trigger("stream-severed")
        assert mgr.trigger("stream-severed") is None
        assert len(mgr.list()) == 2
        assert reg.value("shellac_incidents_dropped_total",
                         trigger="stream-severed") == 1

    def test_retention_evicts_oldest(self, tmp_path):
        mgr = IncidentManager(str(tmp_path), rate=100,
                              rate_window=3600.0, retention=2)
        ids = [mgr.trigger("manual") for _ in range(4)]
        kept = [b["id"] for b in mgr.list()]
        assert kept == ids[-2:]
        assert mgr.load(ids[0]) is None

    def test_tmp_debris_swept_and_no_traversal(self, tmp_path):
        os.makedirs(tmp_path / ".tmp-inc-dead")
        mgr = IncidentManager(str(tmp_path))
        mgr.trigger("manual")
        assert not (tmp_path / ".tmp-inc-dead").exists()
        # Bundle ids never resolve path structure.
        assert mgr.load("../etc") is None
        assert mgr.load("inc-x/../../etc") is None

    def test_retention_spares_concurrent_live_write(self, tmp_path):
        # A tmp dir registered as an IN-FLIGHT write (a concurrent
        # trigger on another thread) must survive the sweep; only
        # orphaned crash debris is swept.
        mgr = IncidentManager(str(tmp_path))
        live = tmp_path / ".tmp-inc-live"
        os.makedirs(live)
        mgr._active_tmp.add(str(live))
        os.makedirs(tmp_path / ".tmp-inc-orphan")
        mgr.trigger("manual")
        assert live.exists()
        assert not (tmp_path / ".tmp-inc-orphan").exists()

    def test_write_failure_counted_not_rate_limited(self, tmp_path):
        reg = Registry()
        mgr = IncidentManager(str(tmp_path), registry=reg, rate=1,
                              rate_window=3600.0)
        # Point the manager at a FILE: every bundle write now fails.
        blocker = tmp_path / "blocker"
        blocker.write_text("x")
        good = mgr.incident_dir
        mgr.incident_dir = str(blocker)
        assert mgr.trigger("manual") is None
        assert mgr.write_errors == 1
        assert reg.value("shellac_incident_write_errors_total",
                         trigger="manual") == 1
        # NOT a rate-limit drop: that counter stays unset.
        assert reg.value("shellac_incidents_dropped_total",
                         trigger="manual") in (None, 0)
        # The failed write REFUNDED its limiter slot (rate=1): once
        # the disk is healthy again the very next trigger succeeds —
        # a full disk must not also burn the rate budget.
        mgr.incident_dir = good
        assert mgr.trigger("manual") is not None

    def test_capture_arm_writes_into_bundle(self, tmp_path):
        done = threading.Event()

        def capture(seconds):
            return {"trace_dir": str(tmp_path / "cap"),
                    "seconds": seconds}

        def analyze(trace_dir):
            done.set()
            return {"device_time_us": 7.0, "dir": trace_dir}

        mgr = IncidentManager(str(tmp_path / "inc"),
                              capture_fn=capture, capture_seconds=0.25,
                              analyze_fn=analyze)
        bid = mgr.trigger("wedge-rebuild")
        full = mgr.load(bid)
        # The fake capture settles instantly, so the background
        # thread may already have flipped armed -> done.
        assert full["manifest"]["capture"]["state"] in ("armed",
                                                        "done")
        wait_until(done.is_set, timeout=10, msg="capture analysis")
        wait_until(
            lambda: "trace_report" in (mgr.load(bid) or {}),
            timeout=10, msg="trace_report lands in bundle")
        full = mgr.load(bid)
        assert full["capture"]["state"] == "done"
        assert full["trace_report"]["device_time_us"] == 7.0
        # The MANIFEST reflects the settled capture too (the incident
        # list summarizes manifests only — "armed" forever would hide
        # a capture that silently died).
        wait_until(lambda: (mgr.load(bid)["manifest"]["capture"]
                            ["state"]) == "done",
                   timeout=10, msg="manifest capture state settles")


# ---------------------------------------------------------------------
# Durable event spool
# ---------------------------------------------------------------------


class TestEventSpool:
    def test_rotation_keeps_footprint_bounded(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        sp = EventSpool(path, max_bytes=4096)
        for i in range(200):
            sp.append({"seq": i, "event": "admit", "pad": "x" * 40})
        assert sp.rotations >= 1
        on_disk = sum(os.path.getsize(p)
                      for p in (path, path + ".1")
                      if os.path.exists(p))
        assert on_disk <= 4096
        evs = read_spool(path)
        # Newest events survive, oldest rotated away, order intact.
        assert evs[-1]["seq"] == 199
        assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)

    def test_redaction_on_disk_by_default(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        EventSpool(path).append(
            {"seq": 1, "event": "admit", "prompt_text": "SECRET",
             "output_text": "SECRET", "text": "SECRET", "rid": 7})
        raw = open(path).read()
        assert "SECRET" not in raw
        assert read_spool(path)[0]["rid"] == 7
        # Opt-in keeps text (the --debug-include-text contract).
        path2 = str(tmp_path / "t.jsonl")
        EventSpool(path2, include_text=True).append(
            {"seq": 1, "event": "admit", "prompt_text": "SECRET"})
        assert "SECRET" in open(path2).read()

    def test_torn_last_line_is_skipped(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        sp = EventSpool(path)
        sp.append({"seq": 1, "event": "admit", "trace": "t-1"})
        sp.append({"seq": 2, "event": "finish", "trace": "t-1"})
        with open(path, "a") as f:
            f.write('{"seq": 3, "event": "adm')  # the kill landed here
        evs = read_spool(path)
        assert [e["seq"] for e in evs] == [1, 2]
        assert [e["event"] for e in spool_events_for(path, "t-1")] == \
            ["admit", "finish"]

    def test_footprint_cap_is_bytes_not_chars(self, tmp_path):
        # Multibyte UTF-8 under include_text must count in BYTES:
        # a char-counted cap would let the footprint run ~3x over.
        path = str(tmp_path / "events.jsonl")
        sp = EventSpool(path, max_bytes=8192, include_text=True)
        for i in range(300):
            sp.append({"seq": i, "event": "admit",
                       "prompt_text": "盔" * 20})
        on_disk = sum(os.path.getsize(p)
                      for p in (path, path + ".1")
                      if os.path.exists(p))
        assert on_disk <= 8192, on_disk

    def test_out_of_order_appends_resort_by_seq(self, tmp_path):
        # The recorder assigns seq under the ring lock but appends to
        # the spool outside it: two racing writers can land in the
        # file out of order, and readers must restore seq order.
        path = str(tmp_path / "events.jsonl")
        sp = EventSpool(path)
        sp.append({"seq": 2, "event": "first-token", "trace": "t"})
        sp.append({"seq": 1, "event": "admit", "trace": "t"})
        assert [e["event"] for e in read_spool(path)] == \
            ["admit", "first-token"]
        assert [e["seq"] for e in spool_events_for(path, "t")] == [1, 2]

    def test_oversized_event_truncated_to_skeleton(self, tmp_path):
        # One record bigger than a whole file's budget could never be
        # bounded by rotation: the payload is dropped honestly, the
        # skeleton (seq/trace/event + truncated marker) survives.
        path = str(tmp_path / "events.jsonl")
        sp = EventSpool(path, max_bytes=4096, include_text=True)
        sp.append({"seq": 1, "event": "admit", "trace": "t",
                   "prompt_text": "x" * 10000})
        on_disk = os.path.getsize(path)
        assert on_disk <= 4096
        evs = read_spool(path)
        assert evs[0]["truncated"] and evs[0]["event"] == "admit"
        assert "prompt_text" not in evs[0]

    def test_restart_reuses_spool_without_seq_interleave(self,
                                                         tmp_path):
        # A respawned replica reuses --spool-dir: its seq restarts at
        # 1, and the reader must order the runs by file appearance,
        # never merge-sort the two seq sequences together.
        path = str(tmp_path / "events.jsonl")
        run1 = EventSpool(path)
        for i in range(1, 4):
            run1.append({"seq": i, "event": f"old-{i}", "trace": "t"})
        run1.close()
        run2 = EventSpool(path)  # the respawn
        for i in range(1, 3):
            run2.append({"seq": i, "event": f"new-{i}", "trace": "t"})
        evs = read_spool(path)
        assert [e["event"] for e in evs] == \
            ["old-1", "old-2", "old-3", "new-1", "new-2"]
        assert all("_run" not in e for e in evs)

    def test_recorder_spills_and_directory_resolution(self, tmp_path):
        sp = EventSpool(spool_path(str(tmp_path)))
        rec = FlightRecorder(capacity=2, spool=sp)
        tid = "00-" + "a" * 32 + "-" + "b" * 16 + "-01"
        for ev in ("admit", "prefill", "first-token", "finish"):
            rec.record(tid, ev)
        # The ring forgot the start; the spool did not.
        assert len(rec.events_for(tid)) == 2
        assert [e["event"] for e in spool_events_for(str(tmp_path),
                                                     tid)] == \
            ["admit", "prefill", "first-token", "finish"]
        # Case-normalization fallback mirrors the ring's.
        assert spool_events_for(str(tmp_path), tid.upper())


# ---------------------------------------------------------------------
# trace-report on the committed fixtures
# ---------------------------------------------------------------------


class TestTraceReport:
    def test_fixtures_are_regenerable(self, tmp_path):
        """The committed captures must be exactly what the generator
        writes — fixture drift would silently change what the diff
        tests prove."""
        spec = importlib.util.spec_from_file_location(
            "make_trace_fixtures",
            os.path.join(FIXTURES, "make_trace_fixtures.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.HERE = str(tmp_path)
        mod.main()
        for name in ("decode_base.trace.json.gz",
                     "decode_regressed.trace.json.gz",
                     "decode_scoped.trace.json.gz"):
            # The payloads, not the files: the gzip header's OS byte
            # differs between Python builds.
            fresh = gzip.decompress((tmp_path / name).read_bytes())
            committed = gzip.decompress(
                open(os.path.join(FIXTURES, name), "rb").read())
            assert fresh == committed, f"{name} drifted from generator"

    def test_analyze_base_capture(self):
        rep = tracereport.analyze(BASE_TRACE)
        assert rep["device_time_us"] == pytest.approx(8200.0)
        assert rep["distinct_ops"] == 4
        # Phase alignment: decode/prefill modules land on their
        # phases, the module-less copy stays unattributed, host-only
        # phases are structurally zero device time.
        ph = rep["phases"]
        assert ph["decode_sync"]["device_us"] == pytest.approx(6400.0)
        assert ph["prefill_dispatch"]["device_us"] == \
            pytest.approx(1600.0)
        assert ph["admission"]["device_us"] == 0.0
        assert rep["unattributed"]["device_us"] == pytest.approx(200.0)
        # Fusion counting uses RAW names: two distinct fusions even
        # though both normalize to one op row.
        assert rep["fusion"]["distinct"] == 2
        assert rep["fusion"]["total_us"] == pytest.approx(5200.0)
        assert rep["top_ops"][0]["name"] == "fusion"
        assert "jit__decode_impl" in rep["modules"]

    def test_by_scope_is_self_time_per_named_scope(self):
        rep = tracereport.analyze(SCOPED_TRACE)
        bs = rep["by_scope"]
        assert "note" not in bs
        got = {k: v["self_us"] for k, v in bs["scopes"].items()}
        # innermost scope wins (attn.qkv/norm -> norm); the while
        # shell keeps only what its children do not cover
        assert got == {"attn.core": 400.0, "attn.qkv": 300.0,
                       "kv.gather": 200.0, "norm": 50.0}
        assert list(got) == ["attn.core", "attn.qkv", "kv.gather", "norm"]
        assert bs["unscoped"]["self_us"] == pytest.approx(250.0)
        assert bs["device_self_us"] == pytest.approx(1200.0)
        assert bs["scopes"]["attn.core"]["share"] == pytest.approx(
            400 / 1200, abs=1e-4)
        assert "by scope" in tracereport.render_report(rep)
        # a capture from before the scopes says so instead of guessing
        old = tracereport.analyze(BASE_TRACE)["by_scope"]
        assert not old["scopes"] and "note" in old
        assert old["unscoped"]["self_us"] == pytest.approx(8200.0)

    def test_self_diff_is_clean(self):
        rep = tracereport.analyze(BASE_TRACE)
        out = tracereport.diff(rep, rep)
        assert out["ok"] and out["regressions"] == []

    def test_diff_flags_injected_regression(self):
        out = tracereport.diff(tracereport.analyze(BASE_TRACE),
                               tracereport.analyze(REGRESSED_TRACE))
        assert not out["ok"]
        kinds = {r["kind"] for r in out["regressions"]}
        assert {"op_regression", "new_op", "device_time_regression",
                "fusion_breakup"} <= kinds
        dot = next(r for r in out["regressions"]
                   if r["kind"] == "op_regression"
                   and r["name"] == "dot")
        assert dot["ratio"] == pytest.approx(4 / 3, rel=1e-3)
        # Reversed direction: the regressed capture as baseline must
        # NOT flag (things got faster, ops disappeared).
        back = tracereport.diff(tracereport.analyze(REGRESSED_TRACE),
                                tracereport.analyze(BASE_TRACE))
        assert all(r["kind"] != "op_regression"
                   or r["name"] != "dot"
                   for r in back["regressions"])

    def test_cli_exit_codes(self):
        from shellac_tpu.cli import main

        assert main(["trace-report", BASE_TRACE]) == 0
        assert main(["trace-report", "--diff", BASE_TRACE,
                     BASE_TRACE]) == 0
        assert main(["trace-report", "--diff", BASE_TRACE,
                     REGRESSED_TRACE]) == 2

    def test_cli_truncated_capture_fails_cleanly(self, tmp_path):
        # A crash mid-capture leaves a TORN gzip — the CLI must exit
        # with a message, not a raw EOFError traceback.
        from shellac_tpu.cli import main

        torn = tmp_path / "torn.trace.json.gz"
        torn.write_bytes(open(BASE_TRACE, "rb").read()[:120])
        with pytest.raises(SystemExit, match="trace-report:"):
            main(["trace-report", str(torn)])

    def test_directory_resolution_and_errors(self, tmp_path):
        # A capture DIRECTORY (the /debug/profile trace_dir shape)
        # resolves to its newest trace file.
        d = tmp_path / "plugins" / "profile" / "run1"
        os.makedirs(d)
        import shutil

        shutil.copy(BASE_TRACE, d / "host.trace.json.gz")
        rep = tracereport.analyze(str(tmp_path))
        assert rep["distinct_ops"] == 4
        with pytest.raises(FileNotFoundError):
            tracereport.analyze(str(tmp_path / "nope"))
        bad = tmp_path / "bad.trace.json.gz"
        bad.write_bytes(gzip.compress(b'{"no": "events"}'))
        with pytest.raises(ValueError):
            tracereport.analyze(str(bad))

    def test_phase_classifier(self):
        assert tracereport.classify_phase("jit__prefill_impl",
                                          "dot") == "prefill_dispatch"
        assert tracereport.classify_phase("jit__decode_impl",
                                          "dot") == "decode_sync"
        assert tracereport.classify_phase(None,
                                          "jit_chunk_step") == \
            "prefill_dispatch"
        assert tracereport.classify_phase(None, "copy") is None


# ---------------------------------------------------------------------
# Bench ledger satellite
# ---------------------------------------------------------------------


class TestBenchLedger:
    def _mod(self):
        spec = importlib.util.spec_from_file_location(
            "bench_ledger",
            os.path.join(os.path.dirname(FIXTURES), "..", "scripts",
                         "bench_ledger.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_committed_ledger_is_current(self):
        mod = self._mod()
        assert mod.main(["--check"]) == 0

    def test_schema_drift_fails_loudly(self):
        mod = self._mod()
        with pytest.raises(mod.SchemaDrift, match="neither"):
            mod._round_rows("BENCH_rXX.json",
                            {"surprise": "shape"})
        with pytest.raises(mod.SchemaDrift, match="share"):
            mod._round_rows("BENCH_rXX.json", {
                "churn_tokens_s": 1.0,
                "step_phases": {"overlap": {"admission": {}}},
            })

    def test_round_shapes_normalize(self):
        mod = self._mod()
        train = mod._round_rows("r", {"metric": "m", "value": 1.5,
                                      "unit": "s",
                                      "detail": {"loss": 2.0}})
        assert train[0]["variant"] == "train"
        assert train[0]["loss"] == 2.0
        assert mod._round_rows("r", None) == []


# ---------------------------------------------------------------------
# Live server: manual trigger, supervisor triggers, spool
# ---------------------------------------------------------------------


def _post(url, payload=b"{}", timeout=120):
    req = urllib.request.Request(
        url, data=payload if isinstance(payload, bytes)
        else json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(url, timeout=30):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture(scope="module")
def tiny_model():
    import jax

    from shellac_tpu import get_model_config
    from shellac_tpu.models import transformer

    cfg = get_model_config("tiny").replace(dtype="float32")
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


@pytest.mark.slow
class TestServerIncidents:
    """Engine-building suites are slow-marked: this file is
    mid-alphabet and must not eat the tier-1 window (the disagg
    precedent); the `incident` CI job runs them unfiltered."""

    def test_manual_trigger_endpoints_and_spool(self, tiny_model,
                                                tmp_path):
        from shellac_tpu.inference.server import (
            InferenceServer,
            make_http_server,
        )

        cfg, params = tiny_model
        idir, sdir = str(tmp_path / "inc"), str(tmp_path / "spool")
        pdir = str(tmp_path / "prof")
        srv = InferenceServer(cfg, params, registry=Registry(),
                              n_slots=2, max_len=64, temperature=0.0,
                              incident_dir=idir, spool_dir=sdir,
                              profile_dir=pdir, incident_rate=2)
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            s, r, _ = _post(url + "/generate",
                            {"tokens": [1, 2, 3], "max_new": 4,
                             "timeout": 120})
            assert s == 200
            tid = r["trace_id"]
            # Manual trigger: bundle exists, sections present, the
            # trace id in the manifest is the caller's.
            s, inc, _ = _post(url + "/debug/incident",
                              {"note": "drill"})
            assert s == 200, inc
            s, full = _get(url + "/debug/incident/" + inc["incident"])
            assert s == 200
            assert full["manifest"]["trigger"] == "manual"
            assert full["manifest"]["detail"]["note"] == "drill"
            for section in ("flight_recorder", "metrics", "requests",
                            "step_phases", "config", "latency"):
                assert section in full, section
            assert full["config"]["engine"]["n_slots"] == 2
            assert full["step_phases"]["decode_sync"]["count"] > 0
            # The completed request's events are in the bundle's
            # recorder dump.
            assert any(e.get("trace") == tid
                       for e in full["flight_recorder"])
            s, lst = _get(url + "/debug/incidents")
            assert s == 200 and lst["last"]["id"] == inc["incident"]
            # Rate limit: rate=2 -> third manual trigger answers 429
            # with Retry-After.
            s2, _, _ = _post(url + "/debug/incident")
            s3, r3, h3 = _post(url + "/debug/incident")
            assert (s2, s3) == (200, 429)
            assert int(h3["Retry-After"]) >= 1
            # /debug/profile: capture id + ?report=1 inline analysis.
            s, prof, _ = _post(url
                               + "/debug/profile?seconds=0.3&report=1")
            assert s == 200
            assert prof["capture_id"] == os.path.basename(
                prof["trace_dir"])
            assert "report" in prof
            # trace-report accepts the returned path verbatim.
            rep = tracereport.analyze(prof["trace_dir"])
            assert "device_time_us" in rep
            # The spool holds the request's full timeline (redacted),
            # and the CLI recovery path renders it.
            evs = spool_events_for(sdir, tid)
            names = [e["event"] for e in evs]
            assert {"admit", "prefill", "first-token",
                    "finish"} <= set(names)
            assert all("prompt_text" not in e for e in evs)
            import io

            buf = io.StringIO()
            assert run_top(None, trace=tid, spool=sdir, out=buf) == 0
            assert "first-token" in buf.getvalue()
        finally:
            httpd.shutdown()
            srv.close()

    def test_unconfigured_endpoints_answer_400(self, tiny_model):
        from shellac_tpu.inference.server import (
            InferenceServer,
            make_http_server,
        )

        cfg, params = tiny_model
        srv = InferenceServer(cfg, params, registry=Registry(),
                              n_slots=2, max_len=64, temperature=0.0)
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            s, body = _get(url + "/debug/incidents")
            assert s == 400 and "--incident-dir" in body["error"]
            s, body, _ = _post(url + "/debug/incident")
            assert s == 400
        finally:
            httpd.shutdown()
            srv.close()


@pytest.mark.slow
class TestSupervisorIncidentTriggers:
    def _dying_factory(self, tiny_model, registry):
        from shellac_tpu.inference.batching import BatchingEngine

        cfg, params = tiny_model

        class _DyingEngine(BatchingEngine):
            def step(self):
                if self.pending:
                    raise RuntimeError("injected scheduler death")
                return super().step()

        def factory():
            return _DyingEngine(cfg, params, n_slots=2, max_len=64,
                                temperature=0.0, registry=registry)

        return factory

    def test_scheduler_death_then_budget_exhaustion(self, tiny_model,
                                                    tmp_path):
        from shellac_tpu.inference.server import InferenceServer

        cfg, params = tiny_model
        reg = Registry()
        factory = self._dying_factory(tiny_model, reg)
        srv = InferenceServer(cfg, params, engine=factory(),
                              registry=reg, restart_budget=1,
                              engine_factory=factory,
                              incident_dir=str(tmp_path))
        try:
            # First death: recovered (budget 1) -> scheduler-death
            # bundle. Second death: budget exhausted -> fatal +
            # restart-budget-exhausted bundle.
            with pytest.raises(RuntimeError):
                srv.generate([1, 2, 3], max_new=2, timeout=60)
            wait_until(lambda: srv.status in ("ok", "failed"),
                       msg="supervisor settles")
            with pytest.raises(RuntimeError):
                srv.generate([1, 2, 3], max_new=2, timeout=60)
            wait_until(lambda: srv._fatal is not None, msg="fatal")
            # The pending fails (and _fatal lands) BEFORE the bundle
            # write on the scheduler thread; wait for the evidence.
            wait_until(lambda: "restart-budget-exhausted" in
                       [b["trigger"] for b in srv.incidents.list()],
                       timeout=15, msg="exhaustion bundle")
            triggers = [b["trigger"] for b in srv.incidents.list()]
            assert triggers.count("scheduler-death") == 1, triggers
            exhausted = next(
                srv.incidents.load(b["id"])
                for b in srv.incidents.list()
                if b["trigger"] == "restart-budget-exhausted")
            assert "restart budget exhausted" in \
                exhausted["manifest"]["detail"]["error"]
            assert reg.value("shellac_incidents_total",
                             trigger="scheduler-death") == 1
        finally:
            srv.close()

    def test_wedge_rebuild_writes_bundle(self, tiny_model, tmp_path):
        from shellac_tpu.inference.batching import BatchingEngine
        from shellac_tpu.inference.server import InferenceServer

        cfg, params = tiny_model
        reg = Registry()

        class _WedgingEngine(BatchingEngine):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self.release = threading.Event()

            def step(self):
                if self.pending:
                    self.release.wait(3600)
                    return []
                return super().step()

        eng = _WedgingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, registry=reg)

        def factory():
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0, registry=reg)

        srv = InferenceServer(cfg, params, engine=eng,
                              registry=reg, step_timeout=1.5,
                              restart_budget=1, engine_factory=factory,
                              incident_dir=str(tmp_path))
        old_thread = srv._thread
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=2, timeout=60)
            wait_until(lambda: srv.status == "ok",
                       msg="rebuild completes")
            triggers = [b["trigger"] for b in srv.incidents.list()]
            assert "wedge-rebuild" in triggers, triggers
            bundle = next(srv.incidents.load(b["id"])
                          for b in srv.incidents.list()
                          if b["trigger"] == "wedge-rebuild")
            assert "step_timeout" in \
                bundle["manifest"]["detail"]["error"]
            # Recovered engine serves again.
            out = srv.generate([1, 2, 3], max_new=2, timeout=120)
            assert len(out) == 2
        finally:
            eng.release.set()
            srv.close()
            old_thread.join(timeout=120)
            assert not old_thread.is_alive(), "wedged thread leaked"

    def test_wedge_with_inplace_factory_writes_fatal_bundle(
            self, tiny_model, tmp_path):
        """The terminal in-place-resync-on-a-wedge arm ('restart the
        pod') must still leave evidence behind — the pod restart is
        exactly when the in-memory recorder dies."""
        from shellac_tpu.inference.batching import BatchingEngine
        from shellac_tpu.inference.server import InferenceServer

        cfg, params = tiny_model

        class _WedgingEngine(BatchingEngine):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self.release = threading.Event()

            def step(self):
                if self.pending:
                    self.release.wait(3600)
                    return []
                return super().step()

        eng = _WedgingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, registry=Registry())
        # A bound method OF the engine = the in-place factory shape
        # (MultihostEngine.resync in production).
        srv = InferenceServer(cfg, params, engine=eng,
                              registry=Registry(), step_timeout=1.5,
                              restart_budget=3,
                              engine_factory=eng.abort_all,
                              incident_dir=str(tmp_path))
        old_thread = srv._thread
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=2, timeout=60)
            wait_until(lambda: srv._fatal is not None, msg="fatal")
            assert "in-place resync" in srv._fatal
            wait_until(lambda: any(
                b["trigger"] == "wedge-fatal"
                for b in srv.incidents.list()),
                timeout=15, msg="wedge-fatal bundle")
            full = next(srv.incidents.load(b["id"])
                        for b in srv.incidents.list()
                        if b["trigger"] == "wedge-fatal")
            assert "restart the pod" in \
                full["manifest"]["detail"]["error"]
        finally:
            eng.release.set()
            srv.close()
            old_thread.join(timeout=120)
            assert not old_thread.is_alive(), "wedged thread leaked"


# ---------------------------------------------------------------------
# Tier triggers with stub replicas (no engines)
# ---------------------------------------------------------------------


class _StubReplica:
    """Minimal HTTP replica: healthy /health, configurable /generate
    behavior ("sever" = stream one delta then FIN without a
    terminator; "fault" = plain 500)."""

    def __init__(self, mode):
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                if self.path == "/health":
                    body = json.dumps({"status": "ok", "ok": True,
                                       "pending": 0,
                                       "role": "monolith"}).encode()
                    self.send_response(200)
                elif self.path == "/metrics":
                    body = b""
                    self.send_response(200)
                else:
                    body = b"{}"
                    self.send_response(404)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                self.rfile.read(n)
                if stub.mode == "fault":
                    body = json.dumps({"error": "injected"}).encode()
                    self.send_response(500)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                # "sever": a 200 ndjson stream that dies after one
                # delta — no done record, no error record.
                self.send_response(200)
                self.send_header("Content-Type",
                                 "application/x-ndjson")
                self.end_headers()
                self.wfile.write(b'{"tokens": [5]}\n')
                self.wfile.flush()

        self.mode = mode
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()


class TestTierIncidentTriggers:
    def _router(self, urls, tmp_path, **kw):
        from shellac_tpu.inference.tier import TierRouter

        return TierRouter(urls, registry=Registry(),
                          health_interval=0.1, backoff_base=0.01,
                          incident_dir=str(tmp_path), **kw)

    def test_severed_stream_triggers_bundle(self, tmp_path):
        from shellac_tpu.inference.tier import make_tier_http_server

        stub = _StubReplica("sever")
        router = self._router([stub.url], tmp_path)
        httpd = make_tier_http_server(router)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            wait_until(lambda: router.replicas[0].routable,
                       msg="stub healthy")
            req = urllib.request.Request(
                base + "/generate",
                data=json.dumps({"tokens": [1], "max_new": 4,
                                 "stream": True,
                                 "timeout": 30}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as r:
                tid = r.headers["x-request-id"]
                body = r.read().decode()
            # The loud in-band error reached the client...
            assert "upstream replica lost mid-stream" in body
            # ... and the black box fired with the same trace id.
            wait_until(lambda: len(router.incidents.list()) >= 1,
                       timeout=15, msg="severed bundle")
            b = router.incidents.list()[-1]
            assert b["trigger"] == "stream-severed"
            assert b["trace_id"] == tid
            full = router.incidents.load(b["id"])
            assert full["manifest"]["detail"]["replica"] == stub.url
            assert stub.url in full["fleet"]
        finally:
            httpd.shutdown()
            router.close()
            stub.close()

    def test_exhausted_attempts_trigger_bundle(self, tmp_path):
        stub = _StubReplica("fault")
        router = self._router([stub.url], tmp_path, max_attempts=2)
        try:
            wait_until(lambda: router.replicas[0].routable,
                       msg="stub healthy")
            status, body, _ = router.forward_json(
                "/generate", {"tokens": [1], "max_new": 2,
                              "timeout": 20})
            assert status == 502
            # Automatic tier triggers fire on a background thread so
            # the client's 502 is not delayed by the evidence fetch.
            wait_until(lambda: router.incidents.list(), timeout=15,
                       msg="exhaustion bundle")
            lst = router.incidents.list()
            assert [b["trigger"] for b in lst] == \
                ["attempts-exhausted"]
            full = router.incidents.load(lst[0]["id"])
            assert full["manifest"]["detail"]["status"] == 502
            # The bundle's recorder dump holds the attempt log for
            # the failed request's trace id.
            tid = lst[0]["trace_id"]
            assert any(e.get("trace") == tid
                       and e.get("event") == "tier-attempt"
                       for e in full["flight_recorder"])
        finally:
            router.close()
            stub.close()


# ---------------------------------------------------------------------
# Acceptance: SLO page -> bundle; SIGKILL -> spool recovery
# ---------------------------------------------------------------------


@pytest.mark.slow
class TestAcceptance:
    def test_slo_page_auto_produces_bundle_with_exemplar(
            self, tiny_model, tmp_path):
        """Under induced latency an SLO page must auto-produce a
        bundle whose manifest carries the violating request's trace
        id and whose embedded timeline matches
        /debug/request/<id>."""
        from shellac_tpu.inference.autotune import SimulatedHostLatency
        from shellac_tpu.inference.server import (
            InferenceServer,
            make_http_server,
        )
        from shellac_tpu.inference.tier import TierRouter

        cfg, params = tiny_model
        srv = InferenceServer(cfg, params, registry=Registry(),
                              n_slots=2, max_len=64, temperature=0.0)
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        # Warm the compile cache so the induced latency, not the
        # compile, dominates the paged requests.
        _post(url + "/generate", {"tokens": [1, 2, 3], "max_new": 2,
                                  "timeout": 300})
        shim = SimulatedHostLatency(srv.engine, device_s=0.4)
        router = TierRouter([url], registry=Registry(),
                            health_interval=0.1,
                            slos=["e2e<250ms@99"],
                            incident_dir=str(tmp_path))
        try:
            wait_until(lambda: router.replicas[0].routable,
                       msg="replica healthy")
            for i in range(4):
                status, _, _ = router.forward_json(
                    "/generate", {"tokens": [2 + i, 3], "max_new": 2,
                                  "timeout": 120})
                assert status == 200
            wait_until(
                lambda: router._slo.state("e2e<250ms@99") == "page",
                timeout=30, msg="burn-rate page")
            wait_until(lambda: any(
                b["trigger"] == "slo-page"
                for b in router.incidents.list()),
                timeout=15, msg="slo-page bundle")
            b = next(x for x in router.incidents.list()
                     if x["trigger"] == "slo-page")
            tid = b["trace_id"]
            assert tid, "page bundle carries no violating trace id"
            full = router.incidents.load(b["id"])
            assert full["manifest"]["detail"]["slo"] == "e2e<250ms@99"
            # Embedded timeline == the live /debug/request/<id>
            # timeline at bundle time (bundle events are a seq-prefix
            # of the live ones).
            bundled = [e for e in full["flight_recorder"]
                       if e.get("trace") == tid]
            assert bundled, "bundle holds no timeline for the exemplar"
            live = router.debug_request(tid)
            assert live is not None
            live_by_seq = {e["seq"]: e["event"]
                           for e in live["events"]}
            for e in bundled:
                assert live_by_seq.get(e["seq"]) == e["event"]
            # SLO section recorded the page.
            row = next(s for s in full["slo"]["slos"]
                       if s["slo"] == "e2e<250ms@99")
            assert row["state"] == "page"
        finally:
            shim.uninstall()
            router.close()
            httpd.shutdown()
            srv.close()

    def test_sigkill_recovers_timeline_from_spool(self, tmp_path):
        """SIGKILL a replica mid-stream; recover that request's full
        timeline from the on-disk spool."""
        from shellac_tpu.inference.chaos import ReplicaProc

        sdir = str(tmp_path / "spool")
        rep = ReplicaProc(extra_args=["--spool-dir", sdir])
        tid = None
        try:
            req = urllib.request.Request(
                rep.url + "/generate",
                data=json.dumps({"tokens": [1, 2, 3], "max_new": 64,
                                 "stream": True,
                                 "timeout": 120}).encode(),
                headers={"Content-Type": "application/json"})
            resp = urllib.request.urlopen(req, timeout=120)
            tid = resp.headers["x-request-id"]
            first = json.loads(resp.readline())
            assert first["tokens"], first
            # Mid-stream, no goodbye.
            rep.kill()
            try:
                resp.read()
            except Exception:  # noqa: BLE001 — the RST is the point
                pass
        finally:
            rep.kill()
        evs = spool_events_for(sdir, tid)
        names = [e["event"] for e in evs]
        # The whole pre-kill lifecycle survived to disk...
        for expected in ("admit", "queue", "prefill", "first-token",
                         "window-dispatch"):
            assert expected in names, (expected, names)
        # ... and never finished (the process died mid-stream).
        assert "finish" not in names
        # `top --trace <id> --spool <dir>` renders the dead replica's
        # timeline.
        import io

        buf = io.StringIO()
        assert run_top(None, trace=tid, spool=sdir, out=buf) == 0
        assert "first-token" in buf.getvalue()
        # Without the spool there is nothing to read — the recovery
        # genuinely came from disk.
        buf2 = io.StringIO()
        assert run_top(None, trace=tid,
                       spool=str(tmp_path / "empty"), out=buf2) == 1
