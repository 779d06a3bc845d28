"""The pluggable KV-cache backend subsystem (inference/cache).

Three suites:

  1. TestRegistry — the name->backend registry is the ONE resolution
     path: legacy flags map onto it, conflicts are loud, and engine
     classes refuse backends outside their family.
  2. TestBackendParity — the matrix: greedy AND per-request-seeded
     sampled token streams are identical across storage policies
     (dense vs paged within each precision; spec engines included),
     because storage is a schedule, not an algorithm.
  3. TestExclusionMatrix — every remaining spec-engine exclusion has
     (a) a manifest entry in spec_batching.EXCLUSIONS/PINNED, (b) a
     tagged raise in the module, and (c) a dedicated test here; the
     meta-test asserts the three stay in lockstep AND that every
     untagged validation raise in spec_batching.py has a covering
     test, so exclusions can neither rot silently nor be removed
     without their tests noticing.

Distribution note (spec x top-k/top-p): rejection sampling over the
IDENTICALLY filtered draft/target distributions reproduces the
filtered target distribution — the same thing sequential sampling
draws from. test_verify_round_targets_filtered_distribution checks
this empirically (support containment is the sharp part: one emitted
token outside the filtered support fails the test outright).
"""

import ast
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu import ParallelConfig, get_model_config, make_mesh
from shellac_tpu.inference import spec_batching
from shellac_tpu.inference.batching import BatchingEngine, PagedBatchingEngine
from shellac_tpu.inference.cache import (
    BACKENDS,
    DenseBackend,
    backend_flags,
    engine_class,
    make_backend,
    resolve_backend_name,
)
from shellac_tpu.inference.spec_batching import (
    EXCLUSIONS,
    PINNED,
    PagedSpeculativeBatchingEngine,
    SpeculativeBatchingEngine,
)
from shellac_tpu.models import transformer
from shellac_tpu.ops.sampling import filter_logits_batched

ALL_NAMES = ("dense", "dense-int8", "paged", "paged-int8", "rolling",
             "rolling-int8")
# Backends that a model's own attention kind calls for: reachable by
# name only (no legacy flag spells them) and outside the tiny model's
# parity matrices (tests/test_eva.py holds theirs).
MODEL_NAMES = ("eva",)


def _tiny(**kw):
    return get_model_config("tiny").replace(dtype="float32", **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    dcfg = _tiny()
    dparams = transformer.init_params(dcfg, jax.random.PRNGKey(7))
    return cfg, params, dcfg, dparams


# ---------------------------------------------------------------------
# 1. Registry
# ---------------------------------------------------------------------

class TestRegistry:
    def test_registry_and_flags_agree(self):
        assert set(BACKENDS) == set(ALL_NAMES) | set(MODEL_NAMES)
        for name in BACKENDS:
            paged, kvq, rolling = backend_flags(name)
            if name in ALL_NAMES:
                # Legacy flags alone round-trip to the same name.
                assert resolve_backend_name(
                    None, paged=paged, kv_quant=kvq, rolling_window=rolling
                ) == name
            # An explicit name AGREEING with its own flags passes.
            assert resolve_backend_name(
                name, paged=paged, kv_quant=kvq, rolling_window=rolling
            ) == name

    def test_unset_legacy_flags_impose_nothing(self):
        # dense defaults (paged=False etc.) conflict with nothing.
        for name in BACKENDS:
            assert resolve_backend_name(name) == name

    def test_conflicts_are_loud(self):
        with pytest.raises(ValueError, match="conflicts"):
            resolve_backend_name("dense", paged=True)
        with pytest.raises(ValueError, match="conflicts"):
            resolve_backend_name("paged", kv_quant="int8")
        with pytest.raises(ValueError, match="conflicts"):
            resolve_backend_name("paged-int8", rolling_window=True)
        with pytest.raises(ValueError, match="rolling_window"):
            resolve_backend_name(None, paged=True, rolling_window=True)
        with pytest.raises(ValueError, match="unknown"):
            resolve_backend_name("block-pool")

    def test_engine_class_resolution(self):
        assert engine_class("dense") is BatchingEngine
        assert engine_class("rolling-int8") is BatchingEngine
        assert engine_class("paged") is PagedBatchingEngine
        assert engine_class("paged-int8") is PagedBatchingEngine
        assert engine_class("dense", speculative=True) \
            is SpeculativeBatchingEngine
        assert engine_class("paged-int8", speculative=True) \
            is PagedSpeculativeBatchingEngine

    def test_engine_refuses_foreign_backend(self, setup):
        cfg, params = setup[:2]
        with pytest.raises(ValueError, match="engine"):
            BatchingEngine(cfg, params, cache_backend="paged")
        with pytest.raises(ValueError, match="engine"):
            PagedBatchingEngine(cfg, params, cache_backend="dense")

    def test_backend_instance_single_owner(self, setup):
        cfg, params = setup[:2]
        be = DenseBackend(cfg, 2, 64)
        e1 = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                            cache_backend=be)
        assert e1.cache_backend is be
        with pytest.raises(ValueError, match="bound"):
            BatchingEngine(cfg, params, n_slots=2, max_len=64,
                           cache_backend=be)

    def test_backend_instance_conflicts_are_loud(self, setup):
        """Engine kwargs that contradict a constructed backend
        instance refuse instead of being silently dropped — geometry,
        policy flags, and paged pool knobs alike."""
        cfg, params = setup[:2]
        with pytest.raises(ValueError, match="geometry"):
            BatchingEngine(cfg, params, n_slots=4, max_len=64,
                           cache_backend=DenseBackend(cfg, 2, 64))
        with pytest.raises(ValueError, match="rolling_window"):
            BatchingEngine(cfg, params, n_slots=2, max_len=64,
                           cache_backend=DenseBackend(cfg, 2, 64),
                           rolling_window=True)
        paged_be = make_backend("paged", cfg, 2, 64, block_size=16)
        with pytest.raises(ValueError, match="block_size"):
            PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                cache_backend=paged_be, block_size=32)
        with pytest.raises(ValueError, match="pool_tokens"):
            PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                cache_backend=paged_be, pool_tokens=256)
        with pytest.raises(ValueError, match="prefix_cache"):
            PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                cache_backend=paged_be,
                                prefix_cache=True)

    def test_make_backend_rejects_unknown_knobs(self, setup):
        cfg = setup[0]
        # A silently dropped pool size is a capacity incident: dense
        # takes no block_size.
        with pytest.raises(TypeError):
            make_backend("dense", cfg, 2, 64, block_size=16)

    def test_residency_is_json_serializable(self, setup):
        import json

        cfg, params = setup[:2]
        for name in ("dense", "paged", "paged-int8"):
            eng = engine_class(name)(
                cfg, params, n_slots=2, max_len=64, cache_backend=name
            )
            r = eng.cache_backend.residency()
            assert r["backend"] == name
            json.dumps(r)  # the disaggregation seam: must serialize
            assert 0.0 <= eng.cache_backend.utilization() <= 1.0

    def test_engine_stats_name_the_backend(self, setup):
        cfg, params = setup[:2]
        eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  kv_quant="int8")
        assert eng.stats["cache_backend"] == "paged-int8"
        # Legacy compatibility attributes derive from the backend.
        assert eng.kv_quant == "int8"
        assert eng.rolling_window is False


# ---------------------------------------------------------------------
# 2. The parity matrix
# ---------------------------------------------------------------------

def _stream(cfg):
    """The shared request stream: two greedy, two seeded-sampled (the
    sampled rows carry top-k/top-p/min-p so the filtered-identity path
    is exercised, and per-request seeds so outputs are deterministic
    and backend-comparable)."""
    rng = np.random.default_rng(42)
    v = cfg.vocab_size
    return [
        ("g0", rng.integers(0, v, 5), 8, dict(temperature=0.0)),
        ("g1", rng.integers(0, v, 11), 6, dict(temperature=0.0)),
        ("s0", rng.integers(0, v, 7), 8,
         dict(temperature=1.1, top_k=12, top_p=0.9, seed=123)),
        ("s1", rng.integers(0, v, 4), 6,
         dict(temperature=0.8, min_p=0.05, seed=7)),
    ]


def _drive(eng, reqs):
    for rid, toks, max_new, kw in reqs:
        eng.submit(rid, toks, max_new, **kw)
    out = {}
    while eng.pending:
        out.update(eng.step())
    return out


def _seq_engine(setup, name):
    cfg, params = setup[:2]
    return engine_class(name)(cfg, params, n_slots=2, max_len=96,
                              cache_backend=name)


def _spec_engine(setup, name, **kw):
    cfg, params, dcfg, dparams = setup
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("gamma", 3)
    return engine_class(name, speculative=True)(
        cfg, params, dcfg, dparams, cache_backend=name, **kw
    )


@pytest.mark.slow
class TestBackendParity:
    """~160s of engine builds: excluded from the tier-1 window (early-
    alphabet placement would displace ~19% of it) and run in full by
    the dedicated cache-backends CI job, which drops the marker
    filter."""

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_sequential_dense_paged_identity(self, setup, quant):
        """Same precision, different storage policy: token-identical
        for the whole stream — greedy and seeded-sampled rows."""
        cfg = setup[0]
        a = _drive(_seq_engine(setup, "dense-int8" if quant else "dense"),
                   _stream(cfg))
        b = _drive(_seq_engine(setup, "paged-int8" if quant else "paged"),
                   _stream(cfg))
        assert a == b

    @pytest.mark.parametrize("name", ["dense", "dense-int8", "paged",
                                      "paged-int8"])
    def test_spec_greedy_matches_sequential(self, setup, name):
        """The acceptance bar: the spec engine on EVERY supported
        backend emits greedy tokens identical to the sequential engine
        on the same backend (speculation is invisible to the math)."""
        cfg = setup[0]
        greedy = [r for r in _stream(cfg) if r[3]["temperature"] == 0.0]
        want = _drive(_seq_engine(setup, name), greedy)
        spec = _spec_engine(setup, name)
        got = _drive(spec, greedy)
        assert got == want
        assert spec.stats["spec_rounds"] > 0

    @pytest.mark.parametrize("pair", [("dense", "paged"),
                                      ("dense-int8", "paged-int8")])
    def test_spec_seeded_cross_backend_identity(self, setup, pair):
        """Seeded sampled requests through the spec engine are
        deterministic per request and IDENTICAL across cache backends
        (per-row key fan depends only on seed + tokens generated) —
        which also forces acceptance-RATE parity, asserted on the
        round counters."""
        cfg = setup[0]
        a_eng, b_eng = (_spec_engine(setup, n) for n in pair)
        a = _drive(a_eng, _stream(cfg))
        b = _drive(b_eng, _stream(cfg))
        assert a == b
        for k in ("spec_rounds", "spec_proposed", "spec_accepted"):
            assert a_eng.stats[k] == b_eng.stats[k], k

    def test_spec_on_paged_with_prefix_cache(self, setup):
        """Spec decode composes with prefix caching: the second
        same-prefix request hits the cache (target prefills the
        suffix; the draft covers the prompt from 0) and stays greedy
        token-identical."""
        cfg = setup[0]
        rng = np.random.default_rng(3)
        prefix = rng.integers(0, cfg.vocab_size, 32)
        tail = rng.integers(0, cfg.vocab_size, 3)
        p1 = np.concatenate([prefix, tail])
        want = _drive(_seq_engine(setup, "dense"),
                      [("a", prefix, 6, dict(temperature=0.0)),
                       ("b", p1, 6, dict(temperature=0.0))])
        spec = _spec_engine(setup, "paged", n_slots=1, max_len=96,
                            prefix_cache=True, block_size=16)
        got = _drive(spec, [("a", prefix, 6, dict(temperature=0.0))])
        got.update(_drive(spec, [("b", p1, 6, dict(temperature=0.0))]))
        assert got == want
        assert spec.stats["prefix_hit_tokens"] > 0

    def test_spec_topk1_equals_greedy(self, setup):
        """top_k=1 at temperature 1.0 collapses the filtered
        distribution to the argmax token: the sampled spec engine must
        emit exactly the greedy sequence — the exact corner of the
        filtered-identity argument, with zero statistical slack."""
        cfg = setup[0]
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, cfg.vocab_size, 6)
        want = _drive(_seq_engine(setup, "dense"),
                      [("x", prompt, 10, dict(temperature=0.0))])
        got = _drive(
            _spec_engine(setup, "dense"),
            [("x", prompt, 10, dict(temperature=1.0, top_k=1, seed=5))],
        )
        assert got == want

    def test_verify_round_targets_filtered_distribution(self, setup):
        """spec x top-k distribution equivalence vs the sequential
        sampler, empirically: with top_k=2, every emitted token must
        lie in the FILTERED support (sharp — an unfiltered target or
        draft side emits out-of-support tokens almost surely), and
        the conditional frequency of the top token matches the
        filtered softmax within binomial tolerance."""
        cfg, params = setup[:2]
        rng = np.random.default_rng(13)
        prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)

        def filtered(prefix):
            logits = transformer.forward(
                cfg, params, jnp.asarray(np.asarray(prefix, np.int32)[None])
            )[0, -1]
            x = filter_logits_batched(
                logits[None], jnp.ones(1), jnp.full((1,), 2, jnp.int32),
                jnp.ones(1), jnp.zeros(1),
            )[0]
            p = np.asarray(jax.nn.softmax(x))
            sup = np.nonzero(p > 0)[0]
            return {int(t): float(p[t]) for t in sup}

        p0 = filtered(prompt)
        assert len(p0) == 2  # top-2 support (no boundary tie on tiny)
        n = 120
        eng = _spec_engine(setup, "dense", n_slots=4, gamma=2)
        reqs = [(i, prompt, 2, dict(temperature=1.0, top_k=2))
                for i in range(n)]
        results = _drive(eng, reqs)
        pairs = [tuple(results[i]) for i in range(n)]
        # Support containment: position 0 (prefill sample) and
        # position 1 (verify round) both within the filtered support.
        conds = {t0: filtered(np.append(prompt, t0)) for t0 in p0}
        c0 = {t0: 0 for t0 in p0}
        c1 = {t0: {t1: 0 for t1 in conds[t0]} for t0 in p0}
        for t0, t1 in pairs:
            assert t0 in p0, f"t0={t0} outside filtered support {p0}"
            assert t1 in conds[t0], (
                f"t1={t1} outside filtered support {conds[t0]} after "
                f"t0={t0} — the verify round is not sampling the "
                "filtered target distribution"
            )
            c0[t0] += 1
            c1[t0][t1] += 1
        # Frequencies within 4.5 sigma of the filtered probabilities.
        for t0, p in p0.items():
            tol = 4.5 * np.sqrt(p * (1 - p) / n)
            assert abs(c0[t0] / n - p) < tol + 1e-9, (t0, c0, p0)
        for t0 in p0:
            m = c0[t0]
            if m < 25:
                continue  # too few samples for a frequency claim
            for t1, p in conds[t0].items():
                tol = 4.5 * np.sqrt(p * (1 - p) / m)
                assert abs(c1[t0][t1] / m - p) < tol + 1e-9, \
                    (t0, t1, c1, conds[t0])

    @pytest.mark.parametrize("name", ["dense", "paged"])
    def test_spec_min_tokens_logit_bias_prompt_logprobs(self, setup, name):
        """The other three burned-down compositions, pinned so a
        regression cannot ship silently: min_tokens (EOS banned in
        BOTH draft and target until N tokens), logit_bias (identical
        adjustment on both distributions), and prompt_logprobs (the
        target prefill scores the prompt) — token streams AND prompt
        scores must match the sequential engine on the same backend."""
        cfg, params, dcfg, dparams = setup
        prompt = np.asarray([5, 9, 2, 31, 7], np.int32)
        eos = 3
        kwargs = dict(n_slots=1, max_len=96, temperature=0.0, eos_id=eos)
        sub = dict(min_tokens=4, logit_bias={eos: 1e9},
                   prompt_logprobs=True)

        def drive(eng):
            eng.submit("r", prompt, 10, **sub)
            out = {}
            while eng.pending:
                out.update(eng.step())
            return out["r"], eng.finished_prompt_logprobs.pop("r")

        seq_t, seq_p = drive(engine_class(name)(
            cfg, params, cache_backend=name, **kwargs))
        spec_t, spec_p = drive(engine_class(name, speculative=True)(
            cfg, params, dcfg, dparams, gamma=3, cache_backend=name,
            **kwargs))
        # The bias forces EOS the instant the min_tokens ban lifts:
        # 4 ordinary greedy tokens, then EOS — on both engines.
        assert seq_t == spec_t
        assert len(seq_t) == 5 and seq_t[4] == eos
        assert all(t != eos for t in seq_t[:4])
        np.testing.assert_allclose(seq_p, spec_p, rtol=1e-6)

    def test_rolling_backend_unchanged_by_registry(self):
        """The rolling backend rides the same registry: a windowed
        model through cache_backend='rolling' matches the legacy
        rolling_window=True construction token-for-token."""
        cfg = _tiny(attn_window=16)
        params = transformer.init_params(cfg, jax.random.PRNGKey(2))
        rng = np.random.default_rng(4)
        reqs = [("x", rng.integers(0, cfg.vocab_size, 9), 8,
                 dict(temperature=0.0))]
        a = _drive(BatchingEngine(cfg, params, n_slots=1, max_len=96,
                                  cache_backend="rolling"), reqs)
        b = _drive(BatchingEngine(cfg, params, n_slots=1, max_len=96,
                                  rolling_window=True), reqs)
        assert a == b


@pytest.mark.slow
class TestMigrationConformance:
    """The residency()/KV-migration round trip, for EVERY registered
    backend: prefill-only on engine A -> export_slot -> serialize ->
    deserialize -> import_blob onto a FRESH engine B -> B's
    continuation is token-identical to the unmigrated run. This is
    the conformance contract inference/disagg.py (the disaggregated
    serving seam) holds against the registry — a new backend must
    either migrate correctly or be added to disagg's loud refusals."""

    @pytest.mark.parametrize("name", ALL_NAMES)
    def test_round_trip_continuation_identity(self, name):
        from shellac_tpu.inference import disagg

        cfg = (_tiny(attn_window=16) if name.startswith("rolling")
               else _tiny())
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(9)
        reqs = [
            ("g", rng.integers(0, cfg.vocab_size, 11), 6,
             dict(temperature=0.0)),
            ("s", rng.integers(0, cfg.vocab_size, 7), 6,
             dict(temperature=1.1, top_k=12, top_p=0.9, seed=123)),
        ]
        kind = engine_class(name)
        expected = _drive(kind(cfg, params, n_slots=2, max_len=96,
                               cache_backend=name), reqs)

        a = kind(cfg, params, n_slots=2, max_len=96,
                 cache_backend=name)
        for rid, toks, max_new, kw in reqs:
            a.submit(rid, toks, max_new, prefill_only=True, **kw)
        while len(a.frozen_prefills) < len(reqs):
            a.step()
        blobs = {}
        for rid, slot in list(a.frozen_prefills.items()):
            blob = disagg.export_slot(a, slot, a._slots[slot])
            # residency() is the wire manifest: JSON round trip held.
            assert blob.header["residency"]["backend"] == name
            blobs[rid] = disagg.MigrationBlob.deserialize(
                blob.serialize()
            )
            a.release_frozen(rid)
        assert not a.pending  # every frozen slot released cleanly

        b = kind(cfg, params, n_slots=2, max_len=96,
                 cache_backend=name)
        for rid, blob in blobs.items():
            disagg.import_blob(b, blob, rid=rid)
        got = {}
        while b.pending:
            got.update(b.step())
        assert got == expected


# ---------------------------------------------------------------------
# 3. The exclusion matrix, meta-tested
# ---------------------------------------------------------------------

_SPEC_SRC = pathlib.Path(spec_batching.__file__).read_text()

# Untagged validation raises in spec_batching.py: plain input checks,
# not exclusions — each must still have a covering test (named here;
# the meta-test asserts the name exists in this file or in
# tests/test_spec_batching.py). A new raise in spec_batching.py that
# is neither tagged nor listed here fails the meta-test.
VALIDATION_RAISES = {
    "vocab mismatch": "test_vocab_mismatch",
    "gamma must be": "test_gamma_validated",
    "draft model heads": "test_draft_heads_must_divide_tp",
    "speculative slack": "test_slack_budget_enforced",
}


def _raise_messages():
    msgs = []
    for node in ast.walk(ast.parse(_SPEC_SRC)):
        if isinstance(node, ast.Raise) and node.exc is not None:
            parts = [c.value for c in ast.walk(node.exc)
                     if isinstance(c, ast.Constant)
                     and isinstance(c.value, str)]
            msgs.append("".join(parts))
    return msgs


class TestExclusionMatrix:
    # -- the exclusions themselves (one dedicated test per entry) -----

    def test_excluded_rolling_window(self, setup):
        cfg, params, dcfg, dparams = setup
        with pytest.raises(ValueError,
                           match=r"\[excluded: rolling_window\]"):
            SpeculativeBatchingEngine(cfg, params, dcfg, dparams,
                                      rolling_window=True)
        with pytest.raises(ValueError,
                           match=r"\[excluded: rolling_window\]"):
            SpeculativeBatchingEngine(cfg, params, dcfg, dparams,
                                      cache_backend="rolling-int8")

    def test_excluded_overlap_decode(self, setup):
        cfg, params, dcfg, dparams = setup
        with pytest.raises(ValueError,
                           match=r"\[excluded: overlap_decode\]"):
            SpeculativeBatchingEngine(cfg, params, dcfg, dparams,
                                      overlap_decode=True)

    def test_excluded_overlap_prefill(self, setup):
        cfg, params, dcfg, dparams = setup
        with pytest.raises(ValueError,
                           match=r"\[excluded: overlap_prefill\]"):
            SpeculativeBatchingEngine(cfg, params, dcfg, dparams,
                                      overlap_prefill=True)

    def test_excluded_pp_pipeline(self, setup):
        cfg, params, dcfg, dparams = setup
        with pytest.raises(ValueError,
                           match=r"\[excluded: pp_pipeline\]"):
            SpeculativeBatchingEngine(cfg, params, dcfg, dparams,
                                      pp_pipeline=True)

    def test_excluded_constraint(self, setup):
        srv = _spec_engine(setup, "dense")
        with pytest.raises(ValueError, match=r"\[excluded: constraint\]"):
            srv.submit("x", np.array([1], np.int32), 4,
                       constraint=object())

    def test_excluded_penalties(self, setup):
        srv = _spec_engine(setup, "dense")
        with pytest.raises(ValueError, match=r"\[excluded: penalties\]"):
            srv.submit("x", np.array([1], np.int32), 4,
                       presence_penalty=0.5)
        with pytest.raises(ValueError, match=r"\[excluded: penalties\]"):
            srv.submit("x", np.array([1], np.int32), 4,
                       frequency_penalty=0.2)

    def test_pinned_decode_ticks(self, setup):
        cfg, params, dcfg, dparams = setup
        with pytest.raises(ValueError,
                           match=r"\[pinned: decode_ticks\]"):
            SpeculativeBatchingEngine(cfg, params, dcfg, dparams,
                                      decode_ticks=2)
        # "auto" (the serving default) resolves to 1 instead of raising,
        # and the engine opts out of post-construction retuning.
        eng = SpeculativeBatchingEngine(cfg, params, dcfg, dparams,
                                        decode_ticks="auto")
        assert eng.decode_ticks == 1
        assert eng._decode_ticks_tunable is False

    # -- untagged validation raises -----------------------------------

    def test_gamma_validated(self, setup):
        cfg, params, dcfg, dparams = setup
        with pytest.raises(ValueError, match="gamma"):
            SpeculativeBatchingEngine(cfg, params, dcfg, dparams, gamma=0)

    def test_draft_heads_must_divide_tp(self, setup):
        cfg, params = setup[:2]
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices for a tp mesh")
        mesh = make_mesh(ParallelConfig(tp=2), devices=jax.devices()[:2])
        dcfg = _tiny(n_heads=1, n_kv_heads=1)
        with pytest.raises(ValueError, match="draft model heads"):
            SpeculativeBatchingEngine(cfg, params, dcfg, params,
                                      mesh=mesh)

    # -- the meta-test: manifest, raises, and tests in lockstep -------

    def test_matrix_cannot_rot(self):
        msgs = _raise_messages()
        tagged = {}
        for m in msgs:
            for kind, key in re.findall(r"\[(excluded|pinned): (\w+)\]", m):
                tagged.setdefault(kind, set()).add(key)
        # (a) every manifest entry has a tagged raise, and vice versa.
        assert tagged.get("excluded", set()) == set(EXCLUSIONS)
        assert tagged.get("pinned", set()) == set(PINNED)
        # (b) every manifest entry has its dedicated test in this class.
        for key in EXCLUSIONS:
            assert hasattr(TestExclusionMatrix, f"test_excluded_{key}"), \
                f"exclusion {key!r} has no test_excluded_{key}"
        for key in PINNED:
            assert hasattr(TestExclusionMatrix, f"test_pinned_{key}"), \
                f"pinned knob {key!r} has no test_pinned_{key}"
        # (c) every UNTAGGED raise is a known validation raise with a
        # covering test that actually exists.
        here = pathlib.Path(__file__).read_text()
        sibling = (pathlib.Path(__file__).parent
                   / "test_spec_batching.py").read_text()
        for m in msgs:
            if re.search(r"\[(excluded|pinned): \w+\]", m):
                continue
            hits = [s for s in VALIDATION_RAISES if s in m]
            assert hits, (
                f"untagged raise {m!r} in spec_batching.py: tag it "
                "[excluded: <key>] / [pinned: <key>] with a manifest "
                "entry, or register it in VALIDATION_RAISES with a "
                "covering test"
            )
            test_name = VALIDATION_RAISES[hits[0]]
            assert (f"def {test_name}(" in here
                    or f"def {test_name}(" in sibling), \
                f"{test_name} (covering {hits[0]!r}) does not exist"
        # (d) the burn-down is real: the matrix stays at or below the
        # six survivors documented in docs/inference.md (PR 9's five
        # plus overlap_prefill, which joined with the admission
        # pipeline — the same no-sync-to-defer class as
        # overlap_decode).
        assert len(EXCLUSIONS) <= 6


# ---------------------------------------------------------------------
# Observability: the backend is visible at /stats and /metrics
# ---------------------------------------------------------------------

class TestObservability:
    def test_backend_info_gauge_and_stats(self, setup):
        from shellac_tpu.inference.server import InferenceServer
        from shellac_tpu.obs import Registry

        cfg, params = setup[:2]
        reg = Registry()
        eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  registry=reg)
        srv = InferenceServer(cfg, params, engine=eng, registry=reg)
        try:
            assert eng.stats["cache_backend"] == "paged"
            text = srv.metrics_text()
            assert ('shellac_engine_cache_backend_info'
                    '{backend="paged"} 1') in text
        finally:
            srv.close()
