"""shellac_tpu.obs: metrics core, Prometheus exposition, request-trace
spans, engine instrumentation, and a live-server /metrics scrape."""

import json
import re
import threading
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from shellac_tpu import get_model_config
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.server import InferenceServer, make_http_server
from shellac_tpu.models import transformer
from shellac_tpu.obs import (
    Registry,
    ServeMetrics,
    linear_buckets,
    log_buckets,
)
from shellac_tpu.training.tokenizer import ByteTokenizer
from shellac_tpu.utils.metrics import MetricsLogger


def _tiny():
    return get_model_config("tiny").replace(dtype="float32")


# ---------------------------------------------------------------------
# bucket math + histogram core


class TestBuckets:
    def test_log_buckets_monotonic_and_covering(self):
        b = log_buckets(0.001, 60.0, per_decade=4)
        assert all(x < y for x, y in zip(b, b[1:]))
        assert b[0] <= 0.001 and b[-1] >= 60.0
        # 4 per decade over ~5 decades: enough resolution, bounded size.
        assert 15 <= len(b) <= 30

    def test_log_buckets_validation(self):
        with pytest.raises(ValueError):
            log_buckets(0.0, 1.0)
        with pytest.raises(ValueError):
            log_buckets(1.0, 1.0)
        with pytest.raises(ValueError):
            log_buckets(0.1, 1.0, per_decade=0)

    def test_linear_buckets(self):
        assert linear_buckets(0.25, 0.25, 4) == (0.25, 0.5, 0.75, 1.0)


class TestHistogram:
    def _h(self, buckets=(1.0, 2.0, 4.0)):
        return Registry().histogram("h", "test", buckets=buckets)

    def test_observe_lands_in_correct_bucket(self):
        h = self._h()
        h.observe(0.5)   # le=1
        h.observe(1.0)   # le=1 (upper bounds are inclusive)
        h.observe(1.5)   # le=2
        h.observe(4.0)   # le=4
        h.observe(99.0)  # +Inf overflow
        assert h.counts == [2, 1, 1, 1]
        assert h.count == 5
        assert h.sum == pytest.approx(0.5 + 1.0 + 1.5 + 4.0 + 99.0)

    def test_percentile_interpolates(self):
        h = self._h(buckets=tuple(float(i) for i in range(1, 11)))
        for v in range(1, 11):  # one observation per bucket
            h.observe(v - 0.5)
        # p50 sits at the 5th of 10 observations: inside the (4, 5]
        # bucket's span.
        p50 = h.percentile(0.5)
        assert 4.0 <= p50 <= 5.0
        assert h.percentile(1.0) >= h.percentile(0.5)

    def test_percentile_empty_and_overflow(self):
        h = self._h()
        assert h.percentile(0.5) is None
        h.observe(123.0)  # overflow bucket
        assert h.percentile(0.99) == pytest.approx(123.0)

    def test_summary_digest(self):
        h = self._h()
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        s = h.summary()
        assert s["count"] == 3
        assert s["mean"] == pytest.approx(5.0 / 3)
        assert s["p50"] is not None and s["p99"] is not None

    def test_bad_buckets_rejected(self):
        r = Registry()
        with pytest.raises(ValueError):
            r.histogram("h1", buckets=())
        with pytest.raises(ValueError):
            r.histogram("h2", buckets=(1.0, 1.0))
        with pytest.raises(ValueError):
            r.histogram("h3", buckets=(1.0, float("inf")))


# ---------------------------------------------------------------------
# registry + label handling


class TestRegistry:
    def test_counter_and_gauge(self):
        r = Registry()
        c = r.counter("c", "help")
        c.inc()
        c.inc(2.5)
        assert r.value("c") == pytest.approx(3.5)
        with pytest.raises(ValueError):
            c.inc(-1)
        g = r.gauge("g")
        g.set(4.0)
        g.dec()
        assert r.value("g") == pytest.approx(3.0)

    def test_registration_idempotent(self):
        r = Registry()
        assert r.counter("c") is r.counter("c")
        h = r.histogram("h", buckets=(1.0, 2.0))
        assert r.histogram("h", buckets=(1.0, 2.0)) is h

    def test_kind_and_label_conflicts_raise(self):
        r = Registry()
        r.counter("m")
        with pytest.raises(ValueError):
            r.gauge("m")
        r.counter("lab", labels=("a",))
        with pytest.raises(ValueError):
            r.counter("lab", labels=("b",))
        r.histogram("hb", buckets=(1.0,))
        with pytest.raises(ValueError):
            r.histogram("hb", buckets=(2.0,))

    def test_labeled_series(self):
        r = Registry()
        fam = r.counter("req", labels=("outcome",))
        fam.labels(outcome="ok").inc()
        fam.labels(outcome="ok").inc()
        fam.labels(outcome="shed").inc()
        assert fam.labels(outcome="ok") is fam.labels(outcome="ok")
        assert r.value("req", outcome="ok") == 2
        assert r.value("req", outcome="shed") == 1
        assert r.value("req", outcome="never") is None
        with pytest.raises(ValueError):
            fam.labels(wrong="x")

    def test_disabled_registry_noops(self):
        r = Registry(enabled=False)
        c = r.counter("c")
        h = r.histogram("h")
        c.inc()
        h.observe(1.0)
        assert c.value == 0 and h.count == 0
        r.enable()
        c.inc()
        assert c.value == 1


# ---------------------------------------------------------------------
# Prometheus exposition format

# One sample line: metric name, optional {labels}, a number.
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? "
    r"(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|NaN|[+-]Inf)$"
)


def assert_valid_exposition(text):
    """Every line is a comment or a well-formed sample; histograms have
    cumulative buckets ending at +Inf == _count."""
    assert text.endswith("\n")
    for line in text.splitlines():
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _SAMPLE_RE.match(line), f"bad exposition line: {line!r}"


class TestExposition:
    def test_render_counter_gauge(self):
        r = Registry()
        r.counter("shellac_c", "a counter").inc(2)
        r.gauge("shellac_g").set(1.5)
        text = r.render()
        assert "# HELP shellac_c a counter" in text
        assert "# TYPE shellac_c counter" in text
        assert "shellac_c 2" in text
        assert "shellac_g 1.5" in text
        assert_valid_exposition(text)

    def test_render_labels_escaped(self):
        r = Registry()
        r.counter("c", labels=("x",)).labels(x='we"ird\\').inc()
        text = r.render()
        assert 'c{x="we\\"ird\\\\"} 1' in text
        assert_valid_exposition(text)

    def test_render_histogram_cumulative(self):
        r = Registry()
        h = r.histogram("lat", "latency", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.7, 3.0, 9.0):
            h.observe(v)
        text = r.render()
        assert_valid_exposition(text)
        assert '# TYPE lat histogram' in text
        assert 'lat_bucket{le="1"} 1' in text
        assert 'lat_bucket{le="2"} 3' in text
        assert 'lat_bucket{le="4"} 4' in text
        assert 'lat_bucket{le="+Inf"} 5' in text
        assert "lat_count 5" in text
        assert f"lat_sum {0.5 + 1.5 + 1.7 + 3.0 + 9.0}" in text

    def test_snapshot_roundtrips_to_json(self):
        r = Registry()
        r.counter("c", labels=("o",)).labels(o="ok").inc()
        r.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
        snap = r.snapshot()
        json.dumps(snap)  # must be JSON-able
        assert snap["c"]["type"] == "counter"
        row = snap["h"]["series"][0]
        assert row["count"] == 1 and row["p50"] is not None
        assert row["buckets"]["1"] == 1


# ---------------------------------------------------------------------
# request-trace span lifecycle


class TestRequestTrace:
    def _sm(self):
        return ServeMetrics(Registry())

    def test_full_lifecycle_deposits_histograms(self):
        sm = self._sm()
        t = sm.trace()
        t.prefill_start()
        t.first_token()
        t.finish(8)
        r = sm.registry
        assert r.value("shellac_queue_wait_seconds") == 1  # count
        assert r.value("shellac_ttft_seconds") == 1
        assert r.value("shellac_e2e_seconds") == 1
        assert r.value("shellac_tpot_seconds") == 1
        assert r.value("shellac_requests_total", outcome="ok") == 1

    def test_single_token_has_no_tpot(self):
        sm = self._sm()
        t = sm.trace()
        t.prefill_start()
        t.first_token()
        t.finish(1)
        assert sm.registry.value("shellac_tpot_seconds") == 0

    def test_events_idempotent(self):
        sm = self._sm()
        t = sm.trace()
        t.prefill_start()
        t.prefill_start()
        t.first_token()
        t.first_token()
        t.finish(4)
        assert sm.registry.value("shellac_queue_wait_seconds") == 1
        assert sm.registry.value("shellac_ttft_seconds") == 1

    def test_shed_settles_once(self):
        sm = self._sm()
        t = sm.trace()
        t.shed()
        t.finish(4)  # late duplicate settlement is ignored
        r = sm.registry
        assert r.value("shellac_requests_total", outcome="shed") == 1
        assert r.value("shellac_requests_shed_total") == 1
        assert r.value("shellac_requests_total", outcome="ok") is None
        assert r.value("shellac_e2e_seconds") == 0

    def test_abort_outcomes(self):
        sm = self._sm()
        for outcome in ("cancelled", "error", "fault"):
            t = sm.trace()
            t.abort(outcome)
            assert sm.registry.value(
                "shellac_requests_total", outcome=outcome
            ) == 1


# ---------------------------------------------------------------------
# engine instrumentation (no HTTP in the way)


class TestEngineInstrumentation:
    def test_engine_records_spans_and_gauges(self):
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        reg = Registry()
        sm = ServeMetrics(reg)
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, registry=reg)
        traces = {}
        for i in range(3):
            traces[i] = sm.trace()
            eng.submit(i, [1 + i, 2, 3], 4, trace=traces[i])
        results = {}
        while eng.pending:
            for rid, out in eng.step():
                traces[rid].finish(len(out))
                results[rid] = out
        assert len(results) == 3
        # Spans: every request got a queue-wait, TTFT, e2e, and (4
        # tokens each) a TPOT observation.
        assert reg.value("shellac_queue_wait_seconds") == 3
        assert reg.value("shellac_ttft_seconds") == 3
        assert reg.value("shellac_e2e_seconds") == 3
        assert reg.value("shellac_tpot_seconds") == 3
        # Engine-side sections + occupancy + utilization gauges.
        # (a program found finished, or after one, is counted and not
        # timed: the histogram holds at most what the counter does)
        for kind in ("prefill", "window"):
            n = reg.value("shellac_engine_launches_total", kind=kind)
            assert n >= 1
            assert 0 <= reg.value("shellac_launch_device_seconds",
                                  kind=kind) <= n
        assert reg.value("shellac_batch_occupancy") >= 1
        occ = reg.get("shellac_batch_occupancy")
        assert occ.percentile(1.0) <= 1.0
        assert reg.value("shellac_slots_busy") == 0  # all drained
        assert 0.0 <= reg.value("shellac_kv_utilization") <= 1.0

    def test_cancel_settles_trace(self):
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        reg = Registry()
        sm = ServeMetrics(reg)
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, registry=reg)
        t = sm.trace()
        eng.submit("a", [1, 2], 4, trace=t)
        assert eng.cancel("a")
        assert reg.value(
            "shellac_requests_total", outcome="cancelled"
        ) == 1

    def test_paged_pool_gauges(self):
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        reg = Registry()
        eng = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=64, block_size=16,
            temperature=0.0, prefix_cache=True, registry=reg,
        )
        eng.submit(0, list(range(1, 20)), 4)
        while eng.pending:
            eng.step()
        assert 0.0 <= reg.value("shellac_kv_utilization") <= 1.0
        # Released prompt blocks stay registered in the prefix cache.
        assert reg.value("shellac_prefix_cache_blocks") >= 1


# ---------------------------------------------------------------------
# MetricsLogger: context manager + registry routing


class TestMetricsLogger:
    def test_context_manager_closes_on_exception(self, tmp_path):
        path = tmp_path / "m.jsonl"
        with pytest.raises(RuntimeError):
            with MetricsLogger(str(path), stdout=False) as logger:
                logger.log(1, {"loss": 2.0})
                raise RuntimeError("boom")
        assert logger._file is None  # closed despite the raise
        rows = [json.loads(x) for x in path.read_text().splitlines()]
        assert rows[0]["loss"] == 2.0

    def test_old_call_pattern_still_works(self, tmp_path):
        path = tmp_path / "m.jsonl"
        logger = MetricsLogger(str(path), stdout=False, every=2)
        logger.log(1, {"loss": 1.0})  # skipped (every=2)
        logger.log(2, {"loss": 0.5})
        logger.close()
        logger.close()  # idempotent
        rows = [json.loads(x) for x in path.read_text().splitlines()]
        assert len(rows) == 1 and rows[0]["step"] == 2

    def test_scalars_routed_to_registry(self, tmp_path):
        reg = Registry()
        logger = MetricsLogger(None, stdout=False, registry=reg)
        logger.log(10, {"loss": 1.25, "grad/norm": 3.0, "note": "str"})
        logger.close()
        assert reg.value("shellac_train_loss") == pytest.approx(1.25)
        assert reg.value("shellac_train_grad_norm") == pytest.approx(3.0)
        assert reg.value("shellac_train_step") == 10
        assert reg.value("shellac_train_log_steps_total") == 1
        assert reg.value("shellac_train_note") is None


# ---------------------------------------------------------------------
# live server scrape


@pytest.fixture(scope="module")
def obs_srv():
    cfg = _tiny()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    reg = Registry()
    srv = InferenceServer(
        cfg, params, tokenizer=ByteTokenizer(),
        n_slots=2, max_len=64, temperature=0.0, registry=reg,
    )
    httpd = make_http_server(srv)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield base, srv, reg
    httpd.shutdown()
    srv.close()


def _post(base, payload, timeout=120):
    req = urllib.request.Request(
        f"{base}/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _get(base, path, timeout=60):
    with urllib.request.urlopen(f"{base}{path}", timeout=timeout) as r:
        return r.status, r.headers.get("Content-Type", ""), r.read().decode()


class TestLiveServerScrape:
    def test_metrics_exposes_spans_under_load(self, obs_srv):
        base, srv, reg = obs_srv
        for i in range(3):
            out = _post(base, {"tokens": [1 + i, 2, 3], "max_new": 4})
            assert len(out["tokens"]) == 4
        status, ctype, text = _get(base, "/metrics")
        assert status == 200
        assert ctype.startswith("text/plain")
        assert_valid_exposition(text)
        # The acceptance-criteria series, present with real counts.
        assert 'shellac_ttft_seconds_bucket{le="' in text
        assert "shellac_tpot_seconds_count" in text
        assert "shellac_queue_wait_seconds_count" in text
        assert reg.value("shellac_ttft_seconds") >= 3
        assert reg.value("shellac_queue_wait_seconds") >= 3
        assert reg.value("shellac_tpot_seconds") >= 3
        assert reg.value("shellac_requests_total", outcome="ok") >= 3
        # Supervisor counters are exposed even while zero.
        assert "shellac_supervisor_restarts_total 0" in text
        assert "shellac_requests_shed_total 0" in text
        assert "shellac_engine_generation 0" in text
        # Engine stats mirror in as gauges at scrape time.
        assert re.search(
            r"shellac_engine_requests_completed [1-9]", text
        )
        assert "shellac_uptime_seconds" in text

    def test_stats_carries_uptime_and_percentiles(self, obs_srv):
        base, srv, reg = obs_srv
        _post(base, {"tokens": [5, 6, 7], "max_new": 4})
        status, _, body = _get(base, "/stats")
        assert status == 200
        stats = json.loads(body)
        assert stats["uptime_s"] >= 0
        for key in ("ttft_s", "e2e_s", "queue_wait_s"):
            digest = stats[key]
            assert digest["count"] >= 1
            assert digest["p50"] is not None
            assert digest["p50"] <= digest["p99"]

    def test_trace_rides_streaming(self, obs_srv):
        base, srv, reg = obs_srv
        before = reg.value("shellac_requests_total", outcome="ok") or 0
        req = urllib.request.Request(
            f"{base}/generate",
            data=json.dumps({"tokens": [9, 8], "max_new": 3,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            lines = [json.loads(x) for x in r.read().splitlines()]
        assert lines[-1]["done"] is True
        assert reg.value("shellac_requests_total", outcome="ok") \
            == before + 1

    def test_metrics_404_when_disabled(self):
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        srv = InferenceServer(cfg, params, n_slots=2, max_len=64,
                              temperature=0.0, metrics=False)
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            out = np.asarray(srv.generate([1, 2], max_new=2, timeout=120))
            assert out.size == 2  # serving works, metrics just no-op
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(base + "/metrics", timeout=30)
            assert e.value.code == 404
            # /stats still answers; digests are empty, not broken.
            status, _, body = _get(base, "/stats")
            assert status == 200
            assert json.loads(body)["ttft_s"]["count"] == 0
        finally:
            httpd.shutdown()
            srv.close()
