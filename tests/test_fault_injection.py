"""Fault injection on the serving path.

Failure semantics under test (docs/inference.md, failure section):

  - A WEDGED engine step (a follower process dying mid-collective
    leaves the primary stuck in native code — no exception ever
    surfaces) is detected by the server's step watchdog
    (`step_timeout`): every pending request fails loudly with the
    fatal message. Without a restart budget (the default) new
    submissions are refused with HTTP 500 and the process stays
    responsive — loud failure, never a silent hang.
  - With `restart_budget > 0` the SUPERVISOR recovers in-process:
    the wedged thread is abandoned under its old engine generation, a
    fresh engine is rebuilt from the retained params/config, and
    serving resumes; results a stale generation ever produces are
    discarded; the budget (a sliding-window circuit breaker) turns a
    crash-looping engine fatal instead of rebuilding forever.
  - Admission is bounded (`max_pending` -> HTTP 429 + Retry-After),
    expired-deadline requests shed before prefill, and /health is a
    real readiness signal (ok | recovering | failed).
  - A client disconnecting mid-stream under the MULTIHOST engine
    cancels the generation on every rank (the cancel rides the
    command broadcast), freeing the slot pod-wide.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from shellac_tpu import get_model_config
from shellac_tpu.config import TrainConfig
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.server import (
    InferenceServer,
    ServerUnavailable,
    make_http_server,
)
from shellac_tpu.models import transformer
from shellac_tpu.obs import Registry, set_default_registry
from shellac_tpu.training import chaos
from shellac_tpu.training.checkpoint import TMP_DIR_MARKER, Checkpointer
from shellac_tpu.training.data import token_batches
from shellac_tpu.training.loop import fit

from conftest import run_two_process


def _tiny():
    return get_model_config("tiny").replace(dtype="float32")


class _WedgingEngine(BatchingEngine):
    """Engine whose step() wedges after `good_steps` steps — the
    observable behavior of a primary whose follower died mid-
    collective. The wedge is an Event wait so the test can RELEASE the
    scheduler thread at teardown: a thread left sleeping inside
    step() for the rest of the pytest process has crashed later XLA
    compiles (both full-suite segfaults pointed here)."""

    def __init__(self, *a, good_steps=0, **kw):
        super().__init__(*a, **kw)
        self._good = good_steps
        self.wedged = threading.Event()
        self.release = threading.Event()
        # Optional forged (rid, tokens) the released step reports as
        # finished — the stale-generation discard test plants a result
        # colliding with a live rid of the REBUILT engine.
        self.fake = None

    def step(self):
        if self._good <= 0:
            self.wedged.set()
            self.release.wait(3600)
            return [self.fake] if self.fake is not None else []
        self._good -= 1
        return super().step()


def _teardown(srv, eng, httpd=None, old_threads=()):
    """Release the wedged scheduler thread and JOIN it before the test
    returns — no engine thread may outlive its test. Recovery tests
    pass the ABANDONED generations' threads via old_threads: close()
    only joins the current generation's."""
    if httpd is not None:
        httpd.shutdown()
    eng.release.set()
    srv.close()  # sets the stop flag and joins the scheduler thread
    assert not srv._thread.is_alive(), "scheduler thread leaked"
    for t in old_threads:
        t.join(timeout=120)
        assert not t.is_alive(), "stale scheduler thread leaked"


class TestStepWatchdog:
    def test_wedged_step_fails_pending_loudly(self):
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = _WedgingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, good_steps=0)
        srv = InferenceServer(cfg, params, engine=eng, step_timeout=2.0)
        try:
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=60)
            # Detection must come from the watchdog (well under the
            # pessimistic request timeout), and the server must now
            # refuse new work with the same loud error, not hang.
            assert time.monotonic() - t0 < 30
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([4, 5], max_new=4, timeout=60)
        finally:
            _teardown(srv, eng)

    def test_http_surface_returns_500(self):
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = _WedgingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, good_steps=0)
        srv = InferenceServer(cfg, params, engine=eng, step_timeout=2.0)
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            base = f"http://127.0.0.1:{httpd.server_address[1]}"
            req = urllib.request.Request(
                base + "/generate",
                json.dumps({"tokens": [3, 5, 7], "max_new": 4}).encode(),
                {"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=60)
            assert e.value.code == 500
            assert "step_timeout" in e.value.read().decode()
        finally:
            _teardown(srv, eng, httpd)

    def test_healthy_server_unaffected(self):
        """A generous timeout never fires on a healthy engine — the
        watchdog must not produce false positives mid-service."""
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        srv = InferenceServer(cfg, params, n_slots=2, max_len=64,
                              temperature=0.0, step_timeout=120.0)
        out = srv.generate([1, 2, 3], max_new=6, timeout=120)
        assert len(out) >= 1
        srv.close()

    def test_bad_timeout_rejected(self):
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="step_timeout"):
            InferenceServer(cfg, params, n_slots=2, step_timeout=0.0)


class _GatedEngine(BatchingEngine):
    """Engine whose step() waits for an explicit go-ahead each call —
    a controllable slow engine (never wedged from the watchdog's view
    unless the test wants it: the gate has a deadline)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.gate = threading.Event()

    def step(self):
        self.gate.wait(120)
        return super().step()


def _mk(engine_cls=_WedgingEngine, **kw):
    cfg = _tiny()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = engine_cls(cfg, params, n_slots=2, max_len=64, temperature=0.0,
                     **kw)
    return cfg, params, eng


def _wait_status(srv, want, timeout=60):
    deadline = time.monotonic() + timeout
    while srv.status != want and time.monotonic() < deadline:
        time.sleep(0.05)
    assert srv.status == want, (srv.status, srv._fatal)


class TestSupervisorRecovery:
    def test_wedge_recovers_and_serves_again(self):
        """The acceptance path: wedge -> watchdog fails every in-flight
        request loudly -> supervisor rebuilds a fresh engine under a
        new generation -> a subsequent generate() succeeds, all in one
        server process."""
        cfg, params, eng = _mk(good_steps=0)

        def factory():
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0)

        # step_timeout must clear the rebuilt engine's first-step
        # compile, or the watchdog trips on the recovery itself (the
        # documented sizing rule).
        srv = InferenceServer(cfg, params, engine=eng, step_timeout=10.0,
                              restart_budget=2, engine_factory=factory)
        gen0_thread = srv._thread
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "ok")
            assert srv.restarts == 1
            assert srv._g.gen == 1
            out = srv.generate([4, 5, 6], max_new=4, timeout=120)
            assert len(out) == 4
            h = srv.health()
            assert h["ok"] and h["status"] == "ok" and h["restarts"] == 1
        finally:
            _teardown(srv, eng, old_threads=(gen0_thread,))

    def test_circuit_breaker_exhausts_budget(self):
        """A crash-looping engine (every rebuild wedges again) exhausts
        the restart budget and the server stays fatal: generate raises,
        /health returns 503 with status=failed."""
        cfg, params, eng = _mk(good_steps=0)
        engines = [eng]

        def bad_factory():
            e = _WedgingEngine(cfg, params, n_slots=2, max_len=64,
                               temperature=0.0, good_steps=0)
            engines.append(e)
            return e

        srv = InferenceServer(cfg, params, engine=eng, step_timeout=2.0,
                              restart_budget=1, engine_factory=bad_factory)
        gen0_thread = srv._thread
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            # Poke the rebuilt generation so it steps (and wedges);
            # the second wedge must exhaust the budget of 1.
            deadline = time.monotonic() + 120
            while srv.status != "failed" and time.monotonic() < deadline:
                if srv.status == "ok":
                    try:
                        srv._submit([9], 2, None, {}, stream=False)
                    except RuntimeError:
                        pass
                time.sleep(0.1)
            assert srv.status == "failed"
            assert "restart budget exhausted" in srv._fatal
            with pytest.raises(RuntimeError, match="restart budget"):
                srv.generate([7], max_new=2, timeout=10)
            base = f"http://127.0.0.1:{httpd.server_address[1]}"
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(base + "/health", timeout=30)
            assert e.value.code == 503
            body = json.loads(e.value.read())
            assert body["status"] == "failed" and not body["ok"]
            assert "step_timeout" in body["error"]
            # /stats stays 200 through the outage but names the fault.
            with urllib.request.urlopen(base + "/stats", timeout=30) as r:
                stats = json.loads(r.read())
            assert "step_timeout" in stats["fatal"]
            assert stats["status"] == "failed"
        finally:
            httpd.shutdown()
            for e in engines:
                e.release.set()
            srv.close()
            assert not srv._thread.is_alive()
            gen0_thread.join(timeout=120)
            assert not gen0_thread.is_alive(), "stale scheduler leaked"

    def test_admission_while_recovering_is_503(self):
        """While the supervisor is mid-rebuild, admission refuses with
        a retryable 503 instead of queueing into a dead generation."""
        cfg, params, eng = _mk(good_steps=0)
        factory_gate = threading.Event()
        built = []

        def slow_factory():
            factory_gate.wait(120)
            e = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                               temperature=0.0)
            built.append(e)
            return e

        # step_timeout must clear the rebuilt engine's first-step
        # compile, or the final post-recovery generate() trips the
        # watchdog again and exhausts the budget.
        srv = InferenceServer(cfg, params, engine=eng, step_timeout=10.0,
                              restart_budget=1, engine_factory=slow_factory)
        gen0_thread = srv._thread
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "recovering")
            with pytest.raises(ServerUnavailable) as e:
                srv.generate([4], max_new=2, timeout=10)
            assert e.value.http_status == 503
            factory_gate.set()
            _wait_status(srv, "ok")
            out = srv.generate([4, 5], max_new=3, timeout=120)
            assert len(out) == 3
        finally:
            factory_gate.set()
            _teardown(srv, eng, old_threads=(gen0_thread,))

    def test_stale_generation_results_discarded(self):
        """A wedged thread that eventually un-wedges and returns
        results must NOT resolve the new generation's pendings — even
        when the rids collide by construction."""
        from shellac_tpu.inference.server import _Pending

        cfg, params, eng = _mk(good_steps=0)

        def factory():
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0)

        srv = InferenceServer(cfg, params, engine=eng, step_timeout=10.0,
                              restart_budget=1, engine_factory=factory)
        old_thread = srv._thread
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "ok")
            # Plant a live pending on the NEW generation, then have the
            # OLD thread wake up claiming that very rid finished with a
            # forged output. The generation check must discard it.
            rid = 424242
            p = _Pending(rid)
            srv._pending[rid] = p
            eng.fake = (rid, [999, 999])
            eng.release.set()
            old_thread.join(timeout=30)
            assert not old_thread.is_alive(), "stale scheduler leaked"
            assert not p.event.is_set(), \
                "stale-generation result resolved a live request"
            assert srv._pending.pop(rid, None) is p
            # The new generation still serves normally.
            out = srv.generate([5, 6], max_new=3, timeout=120)
            assert len(out) == 3
        finally:
            _teardown(srv, eng)

    def test_scheduler_death_recovers(self):
        """An exception (not a wedge) in the engine step takes the
        scheduler-death path into the same supervisor: loud failure,
        then rebuild — no watchdog needed."""
        cfg, params, _ = _mk(good_steps=0)

        class _DyingEngine(BatchingEngine):
            def step(self):
                raise OSError("transport reset by peer")

        def factory():
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0)

        srv = InferenceServer(
            cfg, params,
            engine=_DyingEngine(cfg, params, n_slots=2, max_len=64,
                                temperature=0.0),
            restart_budget=1, engine_factory=factory,
        )
        try:
            with pytest.raises(RuntimeError, match="scheduler died"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "ok")
            out = srv.generate([4, 5], max_new=3, timeout=120)
            assert len(out) == 3
        finally:
            srv.close()
            assert not srv._thread.is_alive()


class TestMultihostResyncThroughSupervisor:
    """engine_factory=MultihostEngine.resync (the cmd_serve wiring),
    on a single-process (degenerate) wrapper."""

    def test_scheduler_death_resync_recovers(self):
        from shellac_tpu.inference.multihost import MultihostEngine

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))

        class _DieOnce(BatchingEngine):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                self._die = True

            def step(self):
                if self._die:
                    self._die = False
                    raise OSError("transport reset by peer")
                return super().step()

        mh = MultihostEngine(_DieOnce(cfg, params, n_slots=2, max_len=64,
                                      temperature=0.0))
        srv = InferenceServer(cfg, params, engine=mh, restart_budget=1,
                              engine_factory=mh.resync)
        try:
            with pytest.raises(RuntimeError, match="scheduler died"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "ok")
            # Recovery was an epoch resync of the SAME wrapper, not a
            # rebuild: safe here because the dead scheduler thread has
            # left the engine.
            assert mh.epoch == 1
            assert srv.engine is mh
            out = srv.generate([4, 5], max_new=3, timeout=120)
            assert len(out) == 3
        finally:
            srv.close()
            assert not srv._thread.is_alive()

    def test_wedge_with_inplace_resync_goes_fatal(self):
        """A WEDGED step cannot be recovered by an in-place resync —
        the stuck thread still owns the engine, and two threads must
        not race one command broadcast. The supervisor must refuse and
        go fatal instead of attempting it."""
        from shellac_tpu.inference.multihost import MultihostEngine

        cfg, params, eng = _mk(good_steps=0)
        mh = MultihostEngine(eng)
        srv = InferenceServer(cfg, params, engine=mh, step_timeout=2.0,
                              restart_budget=3, engine_factory=mh.resync)
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "failed")
            assert "in-place resync" in srv._fatal
            assert srv.restarts == 0  # no rebuild was attempted
            assert mh.epoch == 0  # resync never ran against the engine
        finally:
            eng.release.set()
            srv.close()
            assert not srv._thread.is_alive()


class TestAbortAll:
    """BatchingEngine.abort_all — the supervisor-rebuild / multi-host
    epoch-resync cleanup helper. (Exact post-abort output parity vs a
    bare engine is pinned by test_multihost_serving's resync test.)"""

    def test_clears_engine_for_rebuild(self):
        import numpy as np

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = BatchingEngine(cfg, params, n_slots=1, max_len=64)
        eng.submit("in_flight", np.array([1, 2, 3], np.int32), 30)
        eng.submit("queued", np.array([4, 5], np.int32), 30)
        eng.step()  # "in_flight" occupies the only slot
        dropped = eng.abort_all()
        assert sorted(dropped) == ["in_flight", "queued"]
        assert eng.pending == 0
        assert eng.stats["requests_cancelled"] == 2
        results = eng.run([("fresh", np.array([7, 8], np.int32), 4)])
        assert list(results) == ["fresh"] and len(results["fresh"]) == 4

    def test_returns_paged_blocks(self):
        import numpy as np

        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  block_size=16)
        n_free = len(eng._free)
        eng.submit("a", np.array([1, 2, 3], np.int32), 20)
        eng.submit("b", np.array([4, 5], np.int32), 20)
        eng.step()
        assert len(eng._free) < n_free
        eng.abort_all()
        assert len(eng._free) == n_free, "blocks leaked across abort"
        results = eng.run([("fresh", np.array([1, 2, 3], np.int32), 5)])
        assert list(results) == ["fresh"] and len(results["fresh"]) == 5

    def test_abort_all_purges_prefix_cache(self):
        """Paged abort must reset the allocator to its CANONICAL state
        (prefix registries empty, free list in constructor order) —
        the multi-host resync path aborts replicas AFTER they have
        diverged, and surviving per-host prefix registries would make
        a later prompt prefix-hit on one host but miss on another
        (different-shaped programs, wedged collective again)."""
        import numpy as np

        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  block_size=16, prefix_cache=True)
        pristine = list(eng._free)
        prompt = (np.arange(40) % cfg.vocab_size).astype(np.int32)
        eng.run([("a", prompt, 4)])
        assert eng._hash_to_block, "prefix blocks were never registered"
        eng.abort_all()
        assert not eng._hash_to_block and not eng._block_ref
        assert eng._free == pristine, "free list not canonical"
        results = eng.run([("b", prompt, 4)])
        assert len(results["b"]) == 4


class TestOverlapFaults:
    """Overlapped decode dispatch x the failure machinery: a window in
    flight when the supervisor/watchdog/abort path fires must be
    DRAINED (synced and discarded), never attributed to a successor
    request or generation."""

    def test_wedge_recovers_onto_fresh_overlap_engine(self):
        """Wedge -> watchdog -> rebuild, with BOTH generations running
        overlap_decode=True: the rebuilt generation serves correct,
        strict-ordering-identical output."""
        import numpy as np

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = _WedgingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, good_steps=0,
                             overlap_decode=True, decode_ticks=2)

        def factory():
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0, overlap_decode=True,
                                  decode_ticks=2)

        srv = InferenceServer(cfg, params, engine=eng, step_timeout=10.0,
                              restart_budget=2, engine_factory=factory)
        gen0_thread = srv._thread
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "ok")
            out = srv.generate([4, 5, 6], max_new=6, timeout=120)
            ref = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                 temperature=0.0, decode_ticks=2)
            want = ref.run([("r", np.array([4, 5, 6], np.int32), 6)])["r"]
            assert list(out) == list(want)
        finally:
            _teardown(srv, eng, old_threads=(gen0_thread,))

    def test_abort_all_mid_window_no_stale_leak(self):
        """The resync/rebuild cleanup contract under overlap: windows
        in flight at abort_all are synced-and-discarded, and the next
        tenant of every slot produces exactly the strict-ordering
        output (no stale-generation tokens leak)."""
        import numpy as np

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, overlap_decode=True,
                             decode_ticks=3)
        eng.submit("a", np.array([1, 2, 3], np.int32), 20)
        eng.submit("b", np.array([4, 5], np.int32), 20)
        eng.step()
        eng.step()  # a window is in flight beyond the settled one
        assert eng._windows, "pipeline never engaged"
        dropped = eng.abort_all()
        assert sorted(dropped) == ["a", "b"]
        assert not eng._windows
        results = eng.run([("fresh", np.array([7, 8], np.int32), 6)])
        ref = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, decode_ticks=3)
        want = ref.run([("fresh", np.array([7, 8], np.int32), 6)])
        assert {k: list(v) for k, v in results.items()} == {
            k: list(v) for k, v in want.items()}

    def test_streaming_deltas_under_overlap(self):
        """The server's streaming invariant (out only ever grows;
        holdback protects stop truncation) holds when deltas arrive in
        overlapped window batches."""
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        srv = InferenceServer(cfg, params, n_slots=2, max_len=64,
                              temperature=0.0, overlap_decode=True,
                              decode_ticks=2)
        try:
            deltas, final = [], None
            for kind, val in srv.generate_stream([1, 2, 3], max_new=8,
                                                 timeout=120):
                if kind == "delta":
                    deltas.append(list(val))
                else:
                    final = list(val)
            streamed = [t for d in deltas for t in d]
            assert final is not None and len(final) == 8
            assert streamed == final[:len(streamed)]
        finally:
            srv.close()

    def test_deadline_shed_with_overlap_engine(self):
        """Deadline shedding composes with the overlapped engine: a
        request whose deadline expires while the scheduler is parked in
        a gated step is shed before prefill (same contract as
        TestDeadlineShedding, on the overlap pipeline)."""
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = _GatedEngine(cfg, params, n_slots=2, max_len=64,
                           temperature=0.0, overlap_decode=True,
                           decode_ticks=2)
        srv = InferenceServer(cfg, params, engine=eng)
        try:
            results = []
            t = threading.Thread(target=lambda: results.append(
                srv.generate([1, 2, 3], max_new=4, timeout=120)))
            t.start()
            deadline = time.monotonic() + 60
            while not srv._pending and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.2)  # scheduler is now inside the gated step
            with pytest.raises(TimeoutError):
                srv.generate([5, 6], max_new=4, timeout=0.2)
            time.sleep(0.1)
            eng.gate.set()
            t.join(timeout=120)
            assert results and len(results[0]) == 4
            deadline = time.monotonic() + 60
            while srv.shed < 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert srv.shed == 1
            assert eng.stats["prefills"] == 1
        finally:
            eng.gate.set()
            srv.close()

    def test_abort_all_mid_prefill_flight_no_stale_leak(self):
        """Overlapped PREFILL x the failure machinery: prefills in
        flight at abort_all (the supervisor rebuild / resync cleanup)
        are synced-and-discarded like in-flight windows, and the next
        tenant of every slot produces exactly the strict-ordering
        output — no stale first token leaks."""
        import numpy as np

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, overlap_prefill=True,
                             overlap_decode=True, decode_ticks=2)
        eng.submit("a", np.array([1, 2, 3], np.int32), 8)
        eng.submit("b", np.array([4, 5], np.int32), 8)
        eng.step()  # prefills dispatched, NOT settled
        assert eng._pflights, "no prefill in flight"
        dropped = eng.abort_all()
        assert sorted(dropped) == ["a", "b"]
        assert not eng._pflights
        results = eng.run([("fresh", np.array([7, 8], np.int32), 6)])
        ref = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, decode_ticks=2)
        want = ref.run([("fresh", np.array([7, 8], np.int32), 6)])
        assert {k: list(v) for k, v in results.items()} == {
            k: list(v) for k, v in want.items()}

    def test_wedge_recovers_onto_fresh_overlap_prefill_engine(self):
        """Wedge -> watchdog -> rebuild with BOTH generations running
        the full overlap pipeline (decode AND prefill): the rebuilt
        generation serves strict-ordering-identical output."""
        import numpy as np

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = _WedgingEngine(cfg, params, n_slots=2, max_len=64,
                             temperature=0.0, good_steps=0,
                             overlap_decode=True, overlap_prefill=True,
                             decode_ticks=2)

        def factory():
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0, overlap_decode=True,
                                  overlap_prefill=True, decode_ticks=2)

        srv = InferenceServer(cfg, params, engine=eng, step_timeout=10.0,
                              restart_budget=2, engine_factory=factory)
        gen0_thread = srv._thread
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "ok")
            out = srv.generate([4, 5, 6], max_new=6, timeout=120)
            ref = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                 temperature=0.0, decode_ticks=2)
            want = ref.run([("r", np.array([4, 5, 6], np.int32), 6)])["r"]
            assert list(out) == list(want)
        finally:
            _teardown(srv, eng, old_threads=(gen0_thread,))


class TestAdmissionControl:
    def test_over_limit_rejected_429(self):
        cfg, params, eng = _mk(good_steps=0)
        srv = InferenceServer(cfg, params, engine=eng, max_pending=2)
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            for _ in range(2):
                srv._submit([1, 2], 4, None, {}, stream=False)
            with pytest.raises(ServerUnavailable) as e:
                srv._submit([1, 2], 4, None, {}, stream=False)
            assert e.value.http_status == 429
            assert "max_pending=2" in str(e.value)
            base = f"http://127.0.0.1:{httpd.server_address[1]}"
            req = urllib.request.Request(
                base + "/generate",
                json.dumps({"tokens": [1, 2], "max_new": 4}).encode(),
                {"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as he:
                urllib.request.urlopen(req, timeout=30)
            assert he.value.code == 429
            assert he.value.headers.get("Retry-After") is not None
            assert "overloaded" in json.loads(he.value.read())["error"]
            # /health keeps answering (the cap gates generate only) and
            # reports the saturation.
            with urllib.request.urlopen(base + "/health", timeout=30) as r:
                h = json.loads(r.read())
            assert h["pending"] == 2 and h["max_pending"] == 2
        finally:
            httpd.shutdown()
            _teardown(srv, eng)

    def test_bad_max_pending_rejected(self):
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="max_pending"):
            InferenceServer(cfg, params, n_slots=2, max_pending=0)

    def test_prebuilt_engine_needs_factory_for_budget(self):
        cfg, params, eng = _mk(good_steps=10)
        try:
            with pytest.raises(ValueError, match="engine_factory"):
                InferenceServer(cfg, params, engine=eng, restart_budget=1)
        finally:
            eng.release.set()


class TestDeadlineShedding:
    def test_expired_deadline_never_reaches_prefill(self):
        """A request whose client timeout expires while the scheduler
        is busy is shed BEFORE prefill: the engine never sees it."""
        cfg, params, _ = _mk(good_steps=0)
        eng = _GatedEngine(cfg, params, n_slots=2, max_len=64,
                           temperature=0.0)
        srv = InferenceServer(cfg, params, engine=eng)
        try:
            results = []
            t = threading.Thread(target=lambda: results.append(
                srv.generate([1, 2, 3], max_new=4, timeout=120)))
            t.start()
            # Wait for A to be prefill-eligible: the scheduler is now
            # blocked inside step() at the gate.
            deadline = time.monotonic() + 60
            while not srv._pending and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.2)  # let the scheduler enter the gated step
            with pytest.raises(TimeoutError):
                srv.generate([5, 6], max_new=4, timeout=0.2)
            time.sleep(0.1)
            eng.gate.set()
            t.join(timeout=120)
            assert results and len(results[0]) == 4
            # B was shed at the scheduler: exactly one prefill (A's).
            deadline = time.monotonic() + 60
            while srv.shed < 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert srv.shed == 1
            assert eng.stats["prefills"] == 1
        finally:
            eng.gate.set()
            srv.close()
            assert not srv._thread.is_alive()


class TestObservabilityCounters:
    """The obs layer under faults: supervisor restarts and deadline
    sheds must increment their counters (and settle the request spans)
    across an engine rebuild — the /metrics view of PR 2's recovery
    story."""

    def test_restart_counter_increments_across_rebuild(self):
        reg = Registry()
        cfg, params, eng = _mk(good_steps=0, registry=reg)

        def factory():
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0, registry=reg)

        srv = InferenceServer(cfg, params, engine=eng, step_timeout=10.0,
                              restart_budget=2, engine_factory=factory,
                              registry=reg)
        gen0_thread = srv._thread
        httpd = make_http_server(srv)
        threading.Thread(target=httpd.serve_forever, daemon=True).start()
        try:
            with pytest.raises(RuntimeError, match="step_timeout"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "ok")
            # The wedged in-flight request settled as a fault span and
            # the supervisor rebuild incremented the restart counter.
            assert reg.value("shellac_supervisor_restarts_total") == 1
            assert reg.value(
                "shellac_requests_total", outcome="fault"
            ) == 1
            out = srv.generate([4, 5, 6], max_new=4, timeout=120)
            assert len(out) == 4
            assert reg.value("shellac_requests_total", outcome="ok") == 1
            # The REBUILT engine deposits into the same registry, and a
            # scrape shows the new generation + the restart.
            base = f"http://127.0.0.1:{httpd.server_address[1]}"
            with urllib.request.urlopen(base + "/metrics",
                                        timeout=30) as r:
                text = r.read().decode()
            assert "shellac_supervisor_restarts_total 1" in text
            assert "shellac_engine_generation 1" in text
            assert 'shellac_ttft_seconds_bucket{le="' in text
        finally:
            _teardown(srv, eng, httpd=httpd, old_threads=(gen0_thread,))

    def test_shed_counter_increments(self):
        """A deadline-shed request settles its span as shed and bumps
        shellac_requests_shed_total (the scenario of
        TestDeadlineShedding, observed through the registry)."""
        reg = Registry()
        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = _GatedEngine(cfg, params, n_slots=2, max_len=64,
                           temperature=0.0, registry=reg)
        srv = InferenceServer(cfg, params, engine=eng, registry=reg)
        try:
            results = []
            t = threading.Thread(target=lambda: results.append(
                srv.generate([1, 2, 3], max_new=4, timeout=120)))
            t.start()
            deadline = time.monotonic() + 60
            while not srv._pending and time.monotonic() < deadline:
                time.sleep(0.01)
            time.sleep(0.2)  # let the scheduler enter the gated step
            with pytest.raises(TimeoutError):
                srv.generate([5, 6], max_new=4, timeout=0.2)
            time.sleep(0.1)
            eng.gate.set()
            t.join(timeout=120)
            assert results and len(results[0]) == 4
            deadline = time.monotonic() + 60
            while srv.shed < 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert reg.value("shellac_requests_shed_total") == 1
            assert reg.value(
                "shellac_requests_total", outcome="shed"
            ) == 1
            # Only the served request's span reached prefill/TTFT.
            assert reg.value("shellac_ttft_seconds") == 1
        finally:
            eng.gate.set()
            srv.close()
            assert not srv._thread.is_alive()


class TestCloseAndHeartbeat:
    def test_close_fails_pending_loudly(self):
        """close() must fail still-pending requests immediately instead
        of leaving blocked generate() callers waiting out their full
        timeout."""
        cfg, params, _ = _mk(good_steps=0)
        eng = _GatedEngine(cfg, params, n_slots=2, max_len=64,
                           temperature=0.0)
        srv = InferenceServer(cfg, params, engine=eng)
        errors = []

        def hit():
            t0 = time.monotonic()
            try:
                srv.generate([1, 2, 3], max_new=4, timeout=300)
            except RuntimeError as e:
                errors.append((time.monotonic() - t0, str(e)))

        t = threading.Thread(target=hit)
        t.start()
        deadline = time.monotonic() + 60
        while not srv._pending and time.monotonic() < deadline:
            time.sleep(0.01)
        srv.close()
        t.join(timeout=30)
        assert not t.is_alive()
        assert errors, "caller was not failed"
        elapsed, msg = errors[0]
        assert "closed" in msg
        assert elapsed < 60, "caller waited out its timeout"
        # Release the gated step and JOIN the scheduler before the test
        # returns — no engine thread may outlive its test.
        eng.gate.set()
        srv._thread.join(timeout=120)
        assert not srv._thread.is_alive(), "scheduler thread leaked"

    def test_scheduler_beats_heartbeat(self, tmp_path):
        from shellac_tpu.utils.failure import Heartbeat

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        path = str(tmp_path / "serve_hb.json")
        srv = InferenceServer(cfg, params, n_slots=2, max_len=64,
                              temperature=0.0, heartbeat_path=path)
        try:
            deadline = time.monotonic() + 30
            while Heartbeat.is_stale(path, 3600) and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert not Heartbeat.is_stale(path, 3600)
        finally:
            srv.close()

    def test_rebuild_beats_heartbeat_without_watchdog(self, tmp_path):
        """With no step watchdog armed (no step_timeout), the
        supervisor itself must keep the heartbeat fresh through an
        engine rebuild — otherwise an external watchdog restarts the
        pod mid-recovery."""
        from shellac_tpu.utils.failure import heartbeat_age

        cfg = _tiny()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        path = str(tmp_path / "rebuild_hb.json")
        factory_gate = threading.Event()

        def slow_factory():
            factory_gate.wait(60)
            return BatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  temperature=0.0)

        class _DyingEngine(BatchingEngine):
            def step(self):
                raise OSError("transport reset by peer")

        srv = InferenceServer(
            cfg, params,
            engine=_DyingEngine(cfg, params, n_slots=2, max_len=64,
                                temperature=0.0),
            restart_budget=1, engine_factory=slow_factory,
            heartbeat_path=path,
        )
        try:
            with pytest.raises(RuntimeError, match="scheduler died"):
                srv.generate([1, 2, 3], max_new=4, timeout=120)
            _wait_status(srv, "recovering")
            time.sleep(2.0)  # deep in the rebuild window
            deadline = time.monotonic() + 15
            age = None
            while time.monotonic() < deadline:
                age = heartbeat_age(path)
                if age is not None and age < 1.5:
                    break
                time.sleep(0.2)
            assert age is not None and age < 1.5, age
        finally:
            factory_gate.set()
            _wait_status(srv, "ok")
            srv.close()
            assert not srv._thread.is_alive()

    def test_watchdog_cobeats_heartbeat_through_wedge(self, tmp_path):
        """With the step watchdog armed, the heartbeat must stay fresh
        WHILE a step is wedged (the scheduler loop can't beat) — an
        external watchdog restarting the pod before the supervisor's
        own detection window elapses would defeat in-process
        recovery."""
        from shellac_tpu.utils.failure import heartbeat_age

        cfg, params, eng = _mk(good_steps=0)
        path = str(tmp_path / "wedge_hb.json")
        srv = InferenceServer(cfg, params, engine=eng, step_timeout=60.0,
                              heartbeat_path=path)
        try:
            srv._submit([1, 2], 4, None, {}, stream=False)
            assert eng.wedged.wait(60), "engine never wedged"
            time.sleep(2.5)  # several watchdog polls with the step stuck
            # The co-beat cadence is <= ~2s (1s poll x 1s throttle);
            # poll for a fresh beat rather than asserting one instant,
            # so a loaded CI runner can't flake the window.
            deadline = time.monotonic() + 15
            age = None
            while time.monotonic() < deadline:
                age = heartbeat_age(path)
                if age is not None and age < 1.5:
                    break
                time.sleep(0.2)
            assert age is not None and age < 1.5, age
        finally:
            _teardown(srv, eng)


_FOLLOWER_DEATH_WORKER = """
import json, os, threading, time, urllib.request, urllib.error
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.engine import shard_params
from shellac_tpu.inference.multihost import MultihostEngine
from shellac_tpu.inference.server import InferenceServer, make_http_server
from shellac_tpu.models import transformer
from shellac_tpu.parallel.distributed import global_mesh, initialize

assert initialize()
cfg = get_model_config("tiny").replace(dtype="float32")
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
mesh = global_mesh(ParallelConfig(tp=4))
sharded = shard_params(cfg, params, mesh)
eng = MultihostEngine(
    BatchingEngine(cfg, sharded, n_slots=2, max_len=64, mesh=mesh)
)

if eng.is_primary:
    srv = InferenceServer(cfg, sharded, engine=eng, step_timeout=20.0)
    httpd = make_http_server(srv)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    # One healthy request proves the pod serves before the fault.
    req = urllib.request.Request(
        base + "/generate",
        json.dumps({"tokens": [3, 5, 7], "max_new": 4}).encode(),
        {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert len(json.loads(r.read())["tokens"]) >= 1
    # The follower dies now (it exits after its first request). The
    # next request must fail LOUDLY as HTTP 500 — via whichever
    # detection fires first: on this CPU/Gloo transport the dead peer
    # raises promptly in the step ("scheduler died: ... Gloo"), on a
    # real pod a wedged collective never raises and the step watchdog
    # trips ("step_timeout"). Both are the contracted behavior; a
    # hang or a 200 is the bug.
    req2 = urllib.request.Request(
        base + "/generate",
        json.dumps({"tokens": [9, 9], "max_new": 4}).encode(),
        {"Content-Type": "application/json"})
    try:
        urllib.request.urlopen(req2, timeout=120)
        raise AssertionError("request against a dead pod succeeded")
    except urllib.error.HTTPError as e:
        assert e.code == 500, e.code
        body = e.read().decode()
        assert ("step_timeout" in body) or ("scheduler died" in body), body
    print("WORKER_OK", jax.process_index(), flush=True)
    # The scheduler thread is wedged in the dead collective; a normal
    # interpreter exit would join it forever.
    os._exit(0)
else:
    # Serve until the first request completes, then die abruptly
    # mid-pod — the injected fault. The primary's next broadcast
    # wedges with no peer on the other side.
    while eng.step() is not None:
        if eng.stats.get("requests_completed", 0) >= 1:
            os._exit(1)
"""


_DISCONNECT_WORKER = """
import json, socket, threading, time
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.engine import shard_params
from shellac_tpu.inference.multihost import MultihostEngine
from shellac_tpu.inference.server import InferenceServer, make_http_server
from shellac_tpu.models import transformer
from shellac_tpu.parallel.distributed import global_mesh, initialize

assert initialize()
cfg = get_model_config("tiny").replace(dtype="float32")
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
mesh = global_mesh(ParallelConfig(tp=4))
sharded = shard_params(cfg, params, mesh)
eng = MultihostEngine(
    BatchingEngine(cfg, sharded, n_slots=2, max_len=64, mesh=mesh)
)

if eng.is_primary:
    srv = InferenceServer(cfg, sharded, engine=eng)
    httpd = make_http_server(srv)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    # Raw-socket streaming request, disconnected after the first chunk:
    # the generator must cancel the generation pod-wide.
    s = socket.create_connection(("127.0.0.1", port), timeout=60)
    body = json.dumps({"tokens": [3, 5, 7], "max_new": 40,
                       "stream": True}).encode()
    s.sendall(b"POST /generate HTTP/1.1\\r\\nHost: x\\r\\n"
              b"Content-Type: application/json\\r\\n"
              + f"Content-Length: {len(body)}\\r\\n\\r\\n".encode() + body)
    s.recv(1)  # first byte of the response = stream started
    s.close()  # abrupt disconnect mid-stream
    deadline = time.time() + 60
    while (srv.engine.stats.get("requests_cancelled", 0) < 1
           and time.time() < deadline):
        time.sleep(0.2)
    assert srv.engine.stats["requests_cancelled"] == 1, srv.engine.stats
    httpd.shutdown()
    srv.close()  # broadcasts shutdown -> rank 1 exits serve_forever
else:
    eng.serve_forever()
    # The cancel rode the command broadcast: this rank's replica
    # dropped the same request.
    assert eng.stats.get("requests_cancelled", 0) == 1, eng.stats
print("WORKER_OK", jax.process_index(), flush=True)
"""


class TestMultihostFaults:
    def test_follower_death_detected_loudly(self, tmp_path):
        run_two_process(tmp_path, _FOLLOWER_DEATH_WORKER, timeout=420,
                        ok_ranks=(0,))

    def test_client_disconnect_cancels_pod_wide(self, tmp_path):
        run_two_process(tmp_path, _DISCONNECT_WORKER, timeout=420)


# ---------------------------------------------------------------------------
# Train-loop chaos (docs/training.md, "Failure semantics"): the training
# half of the fault story. A run must survive a NaN batch (rollback to
# the last-good checkpoint, deterministic replay), a corrupt latest
# checkpoint (fallback restore + quarantine), and a kill mid-save
# (startup sweep; resume from the newest intact step) — all without a
# human in the loop, all visible through shellac_train_* counters.
# ---------------------------------------------------------------------------


@pytest.fixture
def fresh_train_registry():
    """Swap the process-global obs registry (the fit loop and the
    checkpointer deposit there) so counter assertions see only this
    test's events."""
    reg = Registry()
    old = set_default_registry(reg)
    yield reg
    set_default_registry(old)


class TestTrainChaos:
    def _factory(self, skip=0):
        return token_batches(
            np.tile(np.arange(32, dtype=np.int32), 50),
            batch_size=2, seq_len=16, num_batches=200, skip=skip,
        )

    def _tcfg(self, steps):
        return TrainConfig(warmup_steps=0, learning_rate=3e-3,
                           total_steps=steps)

    @staticmethod
    def _assert_states_equal(a, b):
        assert int(jax.device_get(a.step)) == int(jax.device_get(b.step))
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)
            ),
            (a.params, a.opt_state), (b.params, b.opt_state),
        )

    def test_nan_at_step_k_rolls_back_and_completes_bit_identical(
            self, tmp_path, fresh_train_registry):
        """The acceptance drill: a transient NaN batch at step 5 (last
        checkpoint at 3) rolls the run back and — because the data
        stream is re-derived from the restored step — the final state
        is BIT-identical to an unfaulted run's."""
        cfg = _tiny()
        reg = fresh_train_registry
        baseline = fit(cfg, self._tcfg(8), self._factory(), log_every=1)
        faulted = fit(
            cfg, self._tcfg(8),
            chaos.poison_batches(self._factory(), at_step=5),
            checkpoint_dir=str(tmp_path / "run"), checkpoint_every=3,
            log_every=1, data_factory=self._factory,
        )
        self._assert_states_equal(baseline, faulted)
        assert reg.value("shellac_train_rollbacks_total") == 1
        assert reg.value(
            "shellac_train_anomalies_total",
            kind="nonfinite_loss", action="rollback",
        ) == 1
        assert reg.value("shellac_train_last_good_step") == 8

    def test_corrupt_latest_checkpoint_falls_back_on_resume(
            self, tmp_path, fresh_train_registry):
        """Kill a run at step 6, scramble its newest checkpoint, and
        resume: restore walks back to the newest INTACT step (4), the
        bad one is quarantined (renamed, never re-selected), the data
        stream re-derives from the restored step, and the finished
        state matches an unfaulted straight-through run."""
        cfg = _tiny()
        reg = fresh_train_registry
        ckdir = str(tmp_path / "run")
        baseline = fit(cfg, self._tcfg(8), self._factory(), log_every=1)
        # "Die" at step 6 by exhausting the stream — total_steps stays 8
        # so the LR schedule (cosine to total_steps) matches the
        # baseline's; a shorter total_steps would be a different run.
        died_early = token_batches(
            np.tile(np.arange(32, dtype=np.int32), 50),
            batch_size=2, seq_len=16, num_batches=6,
        )
        fit(cfg, self._tcfg(8), died_early, checkpoint_dir=ckdir,
            checkpoint_every=2, log_every=1, data_factory=self._factory)
        chaos.scramble_step(ckdir, 6)
        # The stale pre-restore skip (6, what the CLI would compute
        # from latest_step) is deliberately wrong; the loop re-derives
        # it from the step actually restored.
        resumed = fit(
            cfg, self._tcfg(8), self._factory(6), checkpoint_dir=ckdir,
            checkpoint_every=2, log_every=1, data_factory=self._factory,
        )
        self._assert_states_equal(baseline, resumed)
        assert os.path.isdir(os.path.join(ckdir, "6.corrupt"))
        assert reg.value("shellac_train_ckpt_quarantined_total") == 1
        assert reg.value("shellac_train_ckpt_fallback_restores_total") == 1
        # The quarantined directory stays on disk for forensics, while
        # the replay re-saved a FRESH step 6 that verifies clean — the
        # run healed its own checkpoint history.
        ck = Checkpointer(ckdir)
        assert ck.verify(6) is None
        assert ck.latest_step() == 8
        ck.close()

    def test_poisoned_corpus_escalates_to_fatal(self, tmp_path,
                                                fresh_train_registry):
        """A fault that REPLAYS (bad shard, not a transient): every
        rebuilt iterator re-poisons step 4, so rollback can never get
        past it — the sentinel's budget (2 recoveries) drains and the
        run dies loudly instead of loop-rolling forever."""
        cfg = _tiny()
        reg = fresh_train_registry

        def poisoned_factory(skip=0):
            return chaos.poison_batches(
                self._factory(skip), at_step=4, start_step=skip,
            )

        with pytest.raises(RuntimeError, match="budget spent"):
            fit(
                cfg, self._tcfg(6), poisoned_factory(),
                checkpoint_dir=str(tmp_path / "run"), checkpoint_every=2,
                log_every=1, data_factory=poisoned_factory,
                max_restores=2,
            )
        assert reg.value("shellac_train_rollbacks_total") == 2
        assert reg.value(
            "shellac_train_anomalies_total",
            kind="nonfinite_loss", action="fatal",
        ) == 1

    def test_sigkill_mid_save_resumes_from_intact_step(self, tmp_path):
        """SIGKILL with an async save in flight: orbax's atomic-rename
        commit means the victim leaves either a committed step or tmp
        debris — never a half-step selectable as latest. The next
        Checkpointer sweeps the debris and restores cleanly."""
        ckdir = str(tmp_path / "run")
        script = f"""
import os, signal
import numpy as np
from shellac_tpu.training.checkpoint import Checkpointer
ck = Checkpointer({ckdir!r})
state = {{"w": np.arange(3_000_000, dtype=np.float32),
          "b": np.ones((64, 64), np.float32)}}
ck.save(1, state, wait=True)
ck.save(2, {{"w": state["w"] + 1, "b": state["b"] + 1}})  # async
os.kill(os.getpid(), signal.SIGKILL)  # dies with the write in flight
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", script], env=env, timeout=300,
            capture_output=True, text=True,
        )
        assert proc.returncode == -signal.SIGKILL, proc.stderr

        # Any debris the kill left behind reads as ABANDONED once it
        # crosses the sweep's TTL (young tmp dirs are left alone — they
        # could be a concurrent process's live save); backdate it so
        # this construction sweeps it.
        for name in os.listdir(ckdir):
            if TMP_DIR_MARKER in name:
                old = time.time() - 2 * 3600
                os.utime(os.path.join(ckdir, name), (old, old))
        ck = Checkpointer(ckdir)
        assert not any(
            TMP_DIR_MARKER in name for name in os.listdir(ckdir)
        )
        latest = ck.latest_step()
        # Step 1 is always intact; step 2 only if the async write
        # committed before the kill. Either way the selected latest
        # verifies and restores to the values saved FOR THAT step.
        assert latest in (1, 2)
        assert ck.verify(latest) is None
        restored = ck.restore(latest)
        np.testing.assert_array_equal(
            np.asarray(restored["w"][:3]),
            np.arange(3, dtype=np.float32) + (latest - 1),
        )
        ck.close()
