"""Overlapped prefill dispatch: the in-flight prefill pipeline must be
invisible to every request's math.

Core contracts under test:
  - overlap_prefill on/off produce TOKEN-IDENTICAL outputs (and
    identical logprob / top-K / prompt-logprob sidecars) across the
    matrix dense/paged/paged-int8 x chunked/unchunked x greedy/seeded,
    with stop sequences, min_tokens, logit_bias in the mix — the
    acceptance criterion of the prefill-overlap PR;
  - a constrained request's DFA state-0 advance happens at SETTLE (the
    first token is a host value only then) and constrained outputs are
    identical on/off;
  - disaggregated prefill_only freezes at settle and the frozen slot
    exports/imports byte-identically to a non-overlapped engine;
  - cancellation / abort with a prefill in flight never leaks a stale
    first token into a successor request;
  - prefill_chunk auto-tuning picks by measurement (scripted-clock
    unit tests), restores engine state, and "auto" construction is
    inert until tuned;
  - the simulated host-latency harness's prefill clock shows the
    overlap win the perf gate's mixed prefill-heavy rows assert in CI.

NOTE tier-1 timing: this file sorts late enough that the 870s window
never reaches it locally; CI runs it explicitly in the perf-gate job
(same treatment as test_overlap_decode.py).
"""

import time

import jax
import numpy as np
import pytest

from shellac_tpu import get_model_config
from shellac_tpu.inference import disagg
from shellac_tpu.inference.autotune import (
    SimulatedHostLatency,
    autotune_prefill_chunk,
    maybe_autotune_prefill_chunk,
)
from shellac_tpu.inference.batching import (
    BatchingEngine,
    PagedBatchingEngine,
)


def _tiny(**kw):
    return get_model_config("tiny").replace(dtype="float32", **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny()
    from shellac_tpu.models import transformer

    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


def _drain(eng):
    out = {}
    while eng.pending:
        for rid, toks in eng.step():
            out[rid] = list(toks)
    return out


def _build(cfg, params, *, backend="dense", overlap_prefill=False,
           **kw):
    if backend.startswith("paged"):
        kw.setdefault("block_size", 16 if backend == "paged" else 128)
        kw.setdefault("pool_tokens", 2048)
        return PagedBatchingEngine(
            cfg, params, cache_backend=backend,
            overlap_prefill=overlap_prefill, **kw,
        )
    return BatchingEngine(cfg, params, cache_backend=backend,
                          overlap_prefill=overlap_prefill, **kw)


def _drain_after_submit(eng, req, **kw):
    eng.submit(*req, **kw)
    return _drain(eng)


class TestOverlapPrefillParity:
    """The on/off token-identity matrix. Each run mixes greedy,
    seeded-sampled, stop-sequence, min_tokens + logit_bias, and
    prompt_logprobs requests in ONE workload, on engines built with
    logprobs + top_logprobs — so every sidecar the settle carries is
    compared, not just the tokens."""

    @pytest.mark.parametrize("chunked", [False, True],
                             ids=["whole", "chunked"])
    @pytest.mark.parametrize("backend", ["dense", "paged", "paged-int8"])
    def test_mixed_workload_token_identical(self, setup, backend,
                                            chunked):
        cfg, params = setup
        rng = np.random.default_rng(0)
        # Probe (strict engine) for an EOS id and a stop sequence that
        # actually occur in greedy output.
        probe = _build(cfg, params, n_slots=1, max_len=96)
        full = probe.run([("p", rng.integers(0, cfg.vocab_size, 6),
                           12)])["p"]
        eos = int(full[len(full) // 2])
        stop = [int(full[3]), int(full[4])]
        prompts = [rng.integers(0, cfg.vocab_size, 4 + 3 * i)
                   for i in range(6)]
        got = []
        for overlap in (False, True):
            kw = dict(n_slots=3, max_len=96, decode_ticks=2,
                      eos_id=eos, logprobs=True, top_logprobs=2,
                      overlap_decode=True)
            if chunked:
                kw.update(prefill_chunk=6, max_prefills_per_step=1)
            eng = _build(cfg, params, backend=backend,
                         overlap_prefill=overlap, **kw)
            eng.submit("greedy", prompts[0], 8)
            eng.submit("seeded", prompts[1], 8, temperature=1.3,
                       top_k=None, seed=1234)
            eng.submit("stopped", prompts[2], 10, stop=[stop])
            eng.submit("banned", prompts[3], 10, min_tokens=5,
                       logit_bias={int(full[1]): -2.0})
            eng.submit("scored", prompts[4], 6, prompt_logprobs=True)
            eng.submit("short", prompts[5], 1)
            out = _drain(eng)
            got.append((
                out,
                {r: eng.finished_logprobs.pop(r) for r in out},
                eng.finished_top_logprobs.pop("greedy"),
                eng.finished_prompt_logprobs.pop("scored"),
            ))
            assert len(out) == 6
        assert got[0] == got[1]
        # The scored prompt's per-token list covers the whole prompt.
        assert len(got[0][3]) == prompts[4].size

    def test_constraint_first_token_advances_at_settle(self, setup):
        """A constrained request's DFA state-0 advance needs the
        SETTLED first token: before the settle the slot's device state
        is still state 0, after it the state matches the host DFA walk
        of the first emitted token — and outputs are identical
        on/off."""
        from shellac_tpu.inference.constraints import compile_token_dfa
        from shellac_tpu.training.tokenizer import ByteTokenizer

        cfg, params = setup
        eos = cfg.vocab_size - 2
        dfa = compile_token_dfa("(cat|dog)", ByteTokenizer(),
                                cfg.vocab_size, eos_id=eos)
        outs = []
        for overlap in (False, True):
            eng = _build(cfg, params, n_slots=2, max_len=64,
                         eos_id=eos, decode_ticks=2,
                         overlap_prefill=overlap, overlap_decode=True)
            eng.submit("c", np.array([1, 2, 3], np.int32), 8,
                       constraint=dfa)
            if overlap:
                eng.step()  # dispatch only: flight in the pipeline
                assert eng._pflights, "prefill never went in flight"
                slot = eng._pflights[0].slot
                # Pre-settle: the slot's DFA state is state 0, armed
                # at admission (host side: the next window selects the
                # pending patch in; the device vector has not moved).
                bit = eng._PATCH_FIELDS.index("cstate")
                assert eng._patch[slot, 0] >> bit & 1
                assert eng._patch[slot, 1 + bit] == 0
                # Settle exactly (white-box: the next step() would
                # also dispatch a window and advance the state past
                # the first token before returning).
                eng._settle_prefills()
                req = next(r for r in eng._slots if r is not None)
                assert req.out, "settle deposited no first token"
                want = max(int(dfa.trans[0, req.out[0]]), 0)
                # The settle re-armed it past the first token.
                assert eng._patch[slot, 0] >> bit & 1
                assert eng._patch[slot, 1 + bit] == want
                assert int(np.asarray(eng._cstate)[slot]) == 0
            outs.append(_drain(eng))
        assert outs[0] == outs[1]
        text = bytes(outs[0]["c"][:3]).decode()
        assert text in ("cat", "dog")

    def test_ttft_recorded_at_settle(self, setup):
        """The span's first-token mark fires at the settle boundary,
        not at dispatch (the settle-point TTFT definition)."""
        from shellac_tpu.obs import Registry, ServeMetrics

        cfg, params = setup
        reg = Registry()
        sm = ServeMetrics(reg)
        eng = _build(cfg, params, n_slots=1, max_len=64,
                     overlap_prefill=True, registry=reg)
        tr = sm.trace()
        eng.submit("t", np.arange(5, dtype=np.int32), 4, trace=tr)
        eng.step()  # dispatch
        h = reg.get("shellac_ttft_seconds")
        assert h is None or h.count == 0
        eng.step()  # settle
        h = reg.get("shellac_ttft_seconds")
        assert h is not None and h.count == 1
        _drain(eng)


class TestOverlapPrefillLifecycle:
    def test_cancel_mid_prefill_flight(self, setup):
        """A request cancelled while its prefill is in flight must not
        leak its first token into the slot's next tenant."""
        cfg, params = setup
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, cfg.vocab_size, 6)
        eng = _build(cfg, params, n_slots=1, max_len=64,
                     overlap_prefill=True, decode_ticks=2)
        eng.submit("c1", prompt, 10)
        eng.step()  # prefill dispatched, not settled
        assert eng._pflights
        assert eng.cancel("c1")
        got = _drain_after_submit(eng, ("c2", prompt[:4], 5))
        want = _build(cfg, params, n_slots=1, max_len=64,
                      decode_ticks=2).run([("c2", prompt[:4], 5)])
        assert got == {k: list(v) for k, v in want.items()}

    def test_abort_all_mid_prefill_flight(self, setup):
        """abort_all with prefills in flight drains them (synced and
        discarded) and the next tenant produces exactly the
        strict-ordering output."""
        cfg, params = setup
        rng = np.random.default_rng(8)
        prompt = rng.integers(0, cfg.vocab_size, 8)
        eng = _build(cfg, params, backend="paged", n_slots=2,
                     max_len=64, overlap_prefill=True, decode_ticks=2)
        free0 = len(eng._free)
        eng.submit("a", prompt, 8)
        eng.submit("b", prompt[:3], 6)
        eng.step()
        assert eng._pflights, "no prefill in flight"
        dropped = eng.abort_all()
        assert sorted(dropped) == ["a", "b"]
        assert not eng._pflights
        assert len(eng._free) == free0  # pool restored
        got = _drain_after_submit(eng, ("fresh", prompt[:5], 4))
        want = _build(cfg, params, backend="paged", n_slots=2,
                      max_len=64, decode_ticks=2).run(
            [("fresh", prompt[:5], 4)])
        assert got == {k: list(v) for k, v in want.items()}

    def test_completed_at_prefill_settles_next_boundary(self, setup):
        """max_new=1 requests complete at settle; the freed slot is
        reused and every output matches strict ordering."""
        cfg, params = setup
        rng = np.random.default_rng(9)
        reqs = [(i, rng.integers(0, cfg.vocab_size, 3 + i), 1)
                for i in range(5)]
        outs = []
        for overlap in (False, True):
            eng = _build(cfg, params, n_slots=2, max_len=64,
                         overlap_prefill=overlap)
            for r in reqs:
                eng.submit(*r)
            outs.append(_drain(eng))
        assert outs[0] == outs[1]
        assert all(len(v) == 1 for v in outs[0].values())

    def test_prefill_only_freezes_at_settle_then_exports(self, setup):
        """Disagg composition: under overlap the freeze appears only
        at the settle boundary, and the exported slot continues
        byte-identically on the importing engine."""
        cfg, params = setup
        prompt = np.arange(1, 9, dtype=np.int32)
        ctrl = _build(cfg, params, backend="paged", n_slots=2,
                      max_len=96)
        expected = ctrl.run([("c", prompt, 6)])["c"]

        a = _build(cfg, params, backend="paged", n_slots=2, max_len=96,
                   overlap_prefill=True)
        a.submit("m", prompt, 6, prefill_only=True)
        a.step()  # dispatch only
        assert not a.frozen_prefills, "froze before the settle"
        while not a.frozen_prefills:
            a.step()
        slot = a.frozen_prefills["m"]
        blob = disagg.MigrationBlob.deserialize(
            disagg.export_slot(a, slot, a._slots[slot]).serialize()
        )
        assert a.release_frozen("m") is not None

        b = _build(cfg, params, backend="paged", n_slots=2, max_len=96,
                   overlap_prefill=True)
        disagg.import_blob(b, blob, rid="m")
        assert _drain(b)["m"] == list(expected)

    def test_prefix_registration_moves_to_settle(self, setup):
        """on_prefill_complete (prefix-cache registration) fires at
        settle: a cancelled in-flight prefill never registers its
        blocks, and a settled one does."""
        cfg, params = setup
        prompt = np.arange(32, dtype=np.int32)
        eng = _build(cfg, params, backend="paged", n_slots=2,
                     max_len=96, overlap_prefill=True,
                     prefix_cache=True)
        eng.submit("x", prompt, 4)
        eng.step()  # dispatch
        assert len(eng._hash_to_block) == 0, "registered pre-settle"
        eng.step()  # settle
        assert len(eng._hash_to_block) > 0
        eng.cancel("x")
        n_reg = len(eng._hash_to_block)
        eng.submit("y", prompt[:16], 4)
        eng.step()
        assert len(eng._hash_to_block) == n_reg  # in flight: no change
        _drain(eng)


class TestPrefillChunkAutotune:
    def test_auto_is_inert_until_tuned(self, setup):
        cfg, params = setup
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             prefill_chunk="auto")
        assert eng.prefill_chunk is None
        assert eng.prefill_chunk_requested == "auto"
        assert eng.prefill_chunk_source == "auto"
        assert eng.stats["prefill_chunk"] == 0

    def test_bad_prefill_chunk_string_rejected(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="auto"):
            BatchingEngine(cfg, params, prefill_chunk="fast")

    def test_scripted_clock_selects_winner(self, setup):
        """Selection is measurement-driven: a scripted clock that
        makes chunk=16 fastest must elect 16 regardless of real wall
        time."""
        cfg, params = setup
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=128,
                             prefill_chunk="auto", seed=3)
        elapsed = {None: 5.0, 8: 3.0, 16: 0.5, 48: 4.0}
        clock = {"t": 0.0, "nticks": 0}

        def timer():
            # Three calls per candidate (t0, t_first, t1): advance the
            # scripted elapsed on the LAST call of each triple.
            clock["nticks"] += 1
            if clock["nticks"] % 3 == 0:
                clock["t"] += elapsed[eng.prefill_chunk]
            return clock["t"]

        res = autotune_prefill_chunk(
            eng, candidates=(None, 8, 16, 48), timer=timer,
        )
        assert res.best == 16
        assert eng.prefill_chunk == 16
        assert eng.prefill_chunk_source == "auto-tuned"
        assert eng.stats["prefill_chunk"] == 16
        assert set(res.measurements) == {None, 8, 16, 48}

    def test_tune_restores_key_and_leaves_engine_idle(self, setup):
        cfg, params = setup
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=128,
                             prefill_chunk="auto", seed=7)
        key0 = np.asarray(eng._key).copy()
        stats0 = dict(eng.stats)
        autotune_prefill_chunk(eng, candidates=(None, 16))
        assert eng.pending == 0
        assert (np.asarray(eng._key) == key0).all()
        for k in ("requests_completed", "tokens_generated", "prefills"):
            assert eng.stats[k] == stats0[k]

    def test_tuned_engine_still_matches_reference(self, setup):
        cfg, params = setup
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, cfg.vocab_size, 40)
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=128,
                             prefill_chunk="auto", seed=3,
                             overlap_prefill=True)
        autotune_prefill_chunk(eng, candidates=(None, 16))
        got = _drain_after_submit(eng, ("r", prompt, 8))
        ref = BatchingEngine(cfg, params, n_slots=2, max_len=128,
                             prefill_chunk=eng.prefill_chunk, seed=3)
        assert got == {"r": list(ref.run([("r", prompt, 8)])["r"])}

    def test_maybe_skips_fixed_and_spec(self, setup):
        cfg, params = setup
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=128,
                             prefill_chunk=8)
        assert maybe_autotune_prefill_chunk(eng) is None
        assert eng.prefill_chunk == 8

    def test_rolling_refuses_chunk_growth(self, setup):
        cfg, params = setup
        wcfg = _tiny(attn_window=32)
        from shellac_tpu.models import transformer

        wparams = transformer.init_params(wcfg, jax.random.PRNGKey(0))
        eng = BatchingEngine(wcfg, wparams, n_slots=2, max_len=64,
                             cache_backend="rolling", prefill_chunk=4)
        with pytest.raises(ValueError, match="chunk slack"):
            eng.set_prefill_chunk(16)
        eng.set_prefill_chunk(2)  # shrinking inside the slack is fine
        assert eng.prefill_chunk == 2


class TestSimulatedPrefillLatency:
    def test_overlap_hides_injected_prefill_latency(self, setup):
        """The gate's mixed-row claim at smoke scale: with an injected
        per-prefill round trip, the in-flight pipeline beats inline
        settles. Thresholds are lenient (the gate's calibrated run
        asserts the real 1.3x floor)."""
        cfg, params = setup
        rng = np.random.default_rng(12)

        def run(overlap):
            eng = _build(cfg, params, n_slots=2, max_len=96,
                         overlap_prefill=overlap, overlap_decode=True,
                         decode_ticks=2, max_prefills_per_step=1)
            eng.run([("w", rng.integers(0, cfg.vocab_size, 8), 2)])
            shim = SimulatedHostLatency(eng, device_s=0.03,
                                        prefill_s=0.05)
            for i in range(6):
                eng.submit(i, rng.integers(0, cfg.vocab_size, 8), 4)
            t0 = time.perf_counter()
            done = {}
            while eng.pending:
                for rid, out in eng.step():
                    done[rid] = out
                time.sleep(0.02)
            dt = time.perf_counter() - t0
            shim.uninstall()
            assert len(done) == 6
            return dt

        serial, overlapped = run(False), run(True)
        assert serial / overlapped > 1.1, (serial, overlapped)

    def test_shim_outputs_identical(self, setup):
        cfg, params = setup
        rng = np.random.default_rng(13)
        prompt = rng.integers(0, cfg.vocab_size, 6)
        eng = _build(cfg, params, n_slots=1, max_len=64,
                     overlap_prefill=True, decode_ticks=2)
        shim = SimulatedHostLatency(eng, device_s=0.01, prefill_s=0.02)
        got = _drain_after_submit(eng, ("x", prompt, 6))
        shim.uninstall()
        ref = _build(cfg, params, n_slots=1, max_len=64, decode_ticks=2)
        assert got == {"x": list(ref.run([("x", prompt, 6)])["x"])}


class TestStatsSurface:
    def test_engine_stats_expose_prefill_config(self, setup):
        cfg, params = setup
        eng = _build(cfg, params, n_slots=1, max_len=64,
                     overlap_prefill=True, prefill_chunk=8)
        assert eng.stats["overlap_prefill"] == 1
        assert eng.stats["prefill_chunk"] == 8
        eng2 = _build(cfg, params, n_slots=1, max_len=64)
        assert eng2.stats["overlap_prefill"] == 0
        assert eng2.stats["prefill_chunk"] == 0

    def test_prefill_settle_phase_observed(self, setup):
        """The step-phase partition carries the new prefill_settle
        phase, and under overlap the settle cost lands there instead
        of in prefill_dispatch."""
        from shellac_tpu.obs import STEP_PHASES, Registry

        assert "prefill_settle" in STEP_PHASES
        cfg, params = setup
        reg = Registry()
        eng = _build(cfg, params, n_slots=2, max_len=64,
                     overlap_prefill=True, registry=reg)
        _drain_after_submit(eng, ("h", np.arange(5, dtype=np.int32), 4))
        h = reg.get("shellac_step_phase_seconds",
                    phase="prefill_settle")
        assert h is not None and h.count > 0 and h.sum > 0

    def test_server_stats_expose_prefill_knobs(self, setup):
        from shellac_tpu.inference.server import InferenceServer

        cfg, params = setup
        srv = InferenceServer(cfg, params, n_slots=2, max_len=64,
                              overlap_prefill=True, prefill_chunk=8,
                              metrics=False)
        try:
            eng = srv.engine
            assert eng.overlap_prefill
            assert eng.prefill_chunk == 8
            assert eng.prefill_chunk_source == "fixed"
        finally:
            srv.close()


class TestSlotReuseWithinOneStep:
    """A slot's settings, bias, counts and DFA state are its tenant's
    alone: with one slot, a tenant with a logit bias, penalties, a
    constraint or min_tokens finishes in a step's window settle and a
    plain tenant takes the slot in the same step's fill, and serves
    exactly what a fresh engine serves it: nothing of the last tenant is
    left in the host matrix, the pending patch or the device vectors."""

    @pytest.mark.parametrize("overlap", [False, True],
                             ids=["strict", "overlap"])
    @pytest.mark.parametrize("backend", ["dense", "paged"])
    @pytest.mark.parametrize("last", ["bias", "penalties", "constraint",
                                      "min_tokens", "sampled"])
    def test_plain_tenant_after(self, setup, last, backend, overlap):
        from shellac_tpu.inference.constraints import compile_token_dfa
        from shellac_tpu.training.tokenizer import ByteTokenizer

        cfg, params = setup
        eos = cfg.vocab_size - 2
        rng = np.random.default_rng(3)
        plain = ("plain", rng.integers(0, cfg.vocab_size, 9), 12)
        kw = dict(backend=backend, n_slots=1, max_len=64, eos_id=eos,
                  decode_ticks=2, logprobs=True,
                  overlap_prefill=overlap, overlap_decode=overlap)
        fresh = _build(cfg, params, **kw)
        want = _drain_after_submit(fresh, plain)
        want_lps = fresh.finished_logprobs.pop("plain")
        greedy = want["plain"]
        special = {
            "bias": dict(logit_bias={int(greedy[0]): -50.0,
                                     int(greedy[1]): -50.0, 7: 9.0}),
            "penalties": dict(presence_penalty=1.5, frequency_penalty=0.7),
            "constraint": dict(constraint=compile_token_dfa(
                "(cat|dog)", ByteTokenizer(), cfg.vocab_size, eos_id=eos)),
            "min_tokens": dict(min_tokens=40,
                               logit_bias={eos: 50.0}),
            "sampled": dict(temperature=1.3, top_k=5, top_p=0.8,
                            min_p=0.05, seed=11),
        }[last]
        eng = _build(cfg, params, **kw)
        eng.submit("last", rng.integers(0, cfg.vocab_size, 6), 5, **special)
        eng.submit(*plain)
        holders, out = [], {}
        while eng.pending:
            out.update({rid: list(t) for rid, t in eng.step()})
            holders.append(getattr(eng._slots[0], "rid", None))
        # Overlapped, the window settles before the fill: the slot
        # changes hands inside one step. In the strict ordering the
        # window is the step's last act, so the release ends one step
        # and the admission opens the next, with no program between.
        handover = holders[holders.index("plain") - 1]
        assert handover == ("last" if overlap else None), holders
        assert out["plain"] == greedy
        assert eng.finished_logprobs.pop("plain") == want_lps
        assert len(out["last"]) >= 1
