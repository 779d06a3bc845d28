"""EVA attention (EvaByte) on the normal path, against the plain float32
reference in benchmark/arch/evabyte.py: the full forward, prefill then
decode through the cache, the paged engine on the 'eva' backend, the power
of the tolerance, the refusals, and the step records' counts.

Small size on the CPU: 2 layers, hidden 64, 4 heads, W 32, C 4, 3
prediction heads, seeded random weights, float32 at `highest` matmul
precision on both sides.
"""

import importlib.util
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu.inference.cache import engine_class, make_backend
from shellac_tpu.inference.engine import Engine
from shellac_tpu.inference.kvcache import init_cache_for
from shellac_tpu.models import transformer
from shellac_tpu.models.convert import config_from_hf
from shellac_tpu.obs import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, C, HEADS = 32, 4, 3
HF = dict(
    attention_class="eva", chunk_size=C, window_size=W, hidden_size=64,
    intermediate_size=128, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=2, num_pred_heads=HEADS, vocab_size=256,
    rms_norm_eps=1e-5, rope_theta=100000, model_type="evabyte",
    norm_add_unit_offset=True, fp32_skip_add=True,
    max_position_embeddings=512, tie_word_embeddings=False,
)
# Both sides compute in float32 at `highest`; they differ in the ORDER of
# float32 sums only (the program's softmax runs per query block and, in
# decode, over a ring and a pool; the reference's over one concatenated
# row). Logits here reach |4.6|; the widest gap seen over every case below
# is 6e-6. 2e-5 leaves 3 x room and is 50 x under the smallest departure
# the power tests measure.
TOL = 2e-5


@pytest.fixture(scope="module")
def arch():
    spec = importlib.util.spec_from_file_location(
        "bench_arch_evabyte", os.path.join(ROOT, "benchmark", "arch", "evabyte.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model(arch):
    cfg = config_from_hf(types.SimpleNamespace(**HF)).replace(
        dtype="float32", param_dtype="float32", remat=False)
    w = arch.make_weights(HF, 3, dtype=jnp.float32)
    return cfg, w, arch.to_program(w)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n).astype(np.int32)


def _forward(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        return transformer.forward(cfg, params, jnp.asarray(toks)[None])[0]


def test_config_from_hf_maps_evabyte(model):
    cfg = model[0]
    assert cfg.eva.window == W and cfg.eva.chunk == C
    assert cfg.n_pred_heads == HEADS and cfg.fp32_residual
    assert not cfg.tie_embeddings and cfg.rope_theta == 100000.0


# ---- (a) forward, every head ----------------------------------------------

@pytest.mark.parametrize(
    "n", [3, C, W - 1, W, W + 1, 3 * W + 5],
    ids=["in-chunk", "chunk-edge", "W-1", "W", "W+1", "past-3W"])
def test_forward_all_heads_match_reference(arch, model, n):
    cfg, w, params = model
    toks = _tokens(n, seed=n)
    got = _forward(cfg, params, toks)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(n),
                                all_heads=True)
    assert got.shape == (n, HEADS, 256) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - ref))) < TOL


# ---- (b) prefill, then decode through the cache ----------------------------

TOTAL = 3 * W + 6  # decode runs across the roll-overs at 32, 64 and 96


@pytest.fixture(scope="module")
def cached(arch, model):
    cfg, w, params = model
    toks = _tokens(TOTAL, seed=11)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(TOTAL))
    prefill = jax.jit(lambda p, t, c, n: transformer.forward_with_cache(
        cfg, p, t, c, new_tokens_len=n, fresh_cache=True))
    decode = jax.jit(lambda p, t, c: transformer.forward_with_cache(cfg, p, t, c))
    return toks, ref, prefill, decode


@pytest.mark.parametrize(
    "n", [1, C - 1, C, C + 1, W - 1, W, W + 1, W + C, 2 * W - 1, 2 * W, 2 * W + 1])
def test_prefill_then_decode_match_full_forward(model, cached, n):
    """A prompt of n tokens prefilled, then every later token decoded
    through the ring and the pool, against the reference's full forward:
    prefill has to leave what n decode ticks would have left."""
    cfg, _, params = model
    toks, ref, prefill, decode = cached
    pad = 16
    while pad < n:
        pad *= 2
    padded = np.zeros((1, pad), np.int32)
    padded[0, :n] = toks[:n]
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(params, jnp.asarray(padded),
                                init_cache_for(cfg, 1, 128),
                                jnp.asarray([n], jnp.int32))
        worst = float(jnp.max(jnp.abs(logits[0, :n] - ref[:n])))
        for i in range(n, TOTAL):
            lg, cache = decode(params, jnp.asarray(toks[i:i + 1])[None], cache)
            worst = max(worst, float(jnp.max(jnp.abs(lg[0, 0] - ref[i]))))
    assert int(cache.lengths[0]) == TOTAL
    assert worst < TOL


def test_cached_continuation_of_several_rows_refuses(model):
    cfg, _, params = model
    with pytest.raises(NotImplementedError, match="one row at a time"):
        transformer.forward_with_cache(
            cfg, params, jnp.zeros((1, 4), jnp.int32), init_cache_for(cfg, 1, 64))


# ---- (c) the paged engine on the 'eva' backend ------------------------------

REQS = [(5, 40), (33, 70), (64, 30), (31, 50), (70, 60), (17, 90), (96, 20)]


def _engine(model, reg=None, **kw):
    cfg, _, params = model
    kw = dict(dict(n_slots=3, max_len=160, temperature=0.0, decode_ticks=4,
                   overlap_decode=True, overlap_prefill=True, logprobs=True,
                   cache_backend="eva", registry=reg or Registry()), **kw)
    return engine_class("eva")(cfg, params, **kw)


@pytest.fixture(scope="module")
def one_request(model):
    """The one-request path: Engine.generate on each prompt alone."""
    cfg, _, params = model
    single = Engine(cfg, params, temperature=0.0, max_len=160)
    out = {}
    with jax.default_matmul_precision("highest"):
        for rid, (n, m) in enumerate(REQS):
            res = single.generate(jnp.asarray(_tokens(n, seed=100 + rid))[None],
                                  max_new_tokens=m)
            out[rid] = (np.asarray(res.tokens)[0].tolist(),
                        np.asarray(res.logprobs)[0])
    return out


@pytest.mark.parametrize("overlap,ticks", [(True, 4), (False, 1), (True, 7)],
                         ids=["overlap-k4", "strict-k1", "overlap-k7"])
def test_engine_streams_and_logprobs_equal_one_request_path(
        arch, model, one_request, overlap, ticks):
    """Seven requests through three slots: slots sit at different phases
    of their windows, roll over inside decode windows, and are re-used
    after a release. Greedy streams equal the single-request Engine's;
    the emitted tokens' log-probabilities agree to TOL (same float32
    sums, batched differently)."""
    reg = Registry()
    eng = _engine(model, reg, decode_ticks=ticks, overlap_decode=overlap,
                  overlap_prefill=overlap)
    with jax.default_matmul_precision("highest"):
        out = eng.run([(rid, _tokens(n, seed=100 + rid), m)
                       for rid, (n, m) in enumerate(REQS)])
    for rid, (n, m) in enumerate(REQS):
        toks, lps = one_request[rid]
        assert out[rid] == toks[:m], rid
        got = np.asarray(eng.finished_logprobs[rid])
        assert np.max(np.abs(got - lps[:m])) < TOL, rid
    assert eng.cache_backend.utilization() == 0.0
    assert eng.cache_backend.residency()["blocks_free"] == eng._n_blocks - 1


def test_engine_logits_follow_the_reference(arch, model, one_request):
    """The one-request path itself against the reference: every served
    token is the reference's best at its position, by a margin."""
    _, w, _ = model
    rid, (n, m) = 4, REQS[4]
    toks, _ = one_request[rid]
    seq = np.concatenate([_tokens(n, seed=100 + rid), np.asarray(toks[:m - 1])])
    ref = arch.reference_logits(HF, w, jnp.asarray(seq, jnp.int32),
                                jnp.arange(n - 1, n + m - 1))
    assert np.asarray(ref).argmax(axis=-1).tolist() == toks[:m]


def test_accounting_is_not_a_constant_times_tokens(model):
    be = make_backend("eva", model[0], 2, 160, pool_tokens=320)
    assert be.block_size == W and be.n_blocks == 11
    assert be.resident_rows(0) == (0, 0)
    assert be.resident_rows(W) == (W, W // C)          # a full ring
    assert be.resident_rows(W + 1) == (1, W // C)      # rolled over
    assert be.resident_rows(3 * W + 5) == (5, (3 * W + 5) // C)
    row = 2 * 2 * 4 * 16 * 4                           # k, v x L x H x Dh x f32
    assert be.bytes_per_token() == row // C


def test_utilization_follows_the_windows(model):
    """One slot, decoded across a roll-over: the ring's share of the live
    rows falls back when the window closes; the pooled rows only grow."""
    eng = _engine(model, n_slots=1, decode_ticks=1, overlap_decode=False,
                  overlap_prefill=False)
    eng.submit("a", _tokens(W - 4), 12)
    seen = []
    while eng.pending:
        eng.step()
        if eng._slots[0] is not None:
            res = eng.cache_backend.residency()
            seen.append((res["slot_tokens"][0], res["slot_window_rows"][0],
                         res["slot_summary_rows"][0],
                         eng.cache_backend.utilization()))
    for tokens, exact, pooled, util in seen:
        assert (exact, pooled) == ((tokens - 1) % W + 1, tokens // C)
        held = W + (eng._n_blocks - 1) * (W // C)
        assert util == pytest.approx((exact + pooled) / held)
    assert max(e for _, e, _, _ in seen) == W and seen[-1][1] < W


# ---- (d) the tolerance can see the mechanism --------------------------------

def _mutated_gap(arch, model, monkeypatch, name, mutant):
    import shellac_tpu.ops.eva_attention as ops

    cfg, w, params = model
    n = 3 * W + 5
    toks = _tokens(n, seed=n)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(n),
                                all_heads=True)
    monkeypatch.setattr(ops, name, mutant(getattr(ops, name)))
    return float(jnp.max(jnp.abs(_forward(cfg, params, toks) - ref)))


def _zero_pooled_rows(orig):
    def f(*a, **kw):
        return tuple(jnp.zeros_like(x) for x in orig(*a, **kw))
    return f


def _own_window_visible(orig):
    """Every pooled row moved one window earlier: a query then sees the
    chunks of its OWN window (and loses those of the first)."""
    def f(q, k, v, kp, vp, **kw):
        r = kw["window"] // kw["chunk"]
        return orig(q, k, v, jnp.roll(kp, -r, axis=1), jnp.roll(vp, -r, axis=1), **kw)
    return f


@pytest.mark.parametrize("name,mutant", [
    ("eva_pool_sequence", _zero_pooled_rows),
    ("eva_attention", _own_window_visible),
], ids=["pooled-rows-zeroed", "own-window-visible"])
def test_tolerance_fails_a_run_without_the_mechanism(arch, model, monkeypatch,
                                                     name, mutant):
    assert _mutated_gap(arch, model, monkeypatch, name, mutant) > 50 * TOL


# ---- (e) what this state cannot do yet refuses, loudly, at construction ----

def test_refuses_prefix_cache(model):
    with pytest.raises(ValueError, match="does not support prefix_cache yet"):
        _engine(model, prefix_cache=True)


def test_refuses_kv_quant(model):
    with pytest.raises(ValueError, match="does not support kv_quant yet"):
        make_backend("eva", model[0], 2, 64, kv_quant="int8")


def test_refuses_chunked_prefill(model):
    with pytest.raises(ValueError, match="does not support chunked_prefill yet"):
        _engine(model, prefill_chunk=16)
    with pytest.raises(ValueError, match="does not support chunked_prefill yet"):
        _engine(model).set_prefill_chunk(16)


def test_refuses_speculative(model):
    cfg, _, params = model
    with pytest.raises(ValueError, match="does not support speculative yet"):
        engine_class("eva", speculative=True)(
            cfg, params, cfg, params, n_slots=2, max_len=64, cache_backend="eva")


def test_refuses_pp_pipeline(model):
    with pytest.raises(ValueError, match="does not support pp_pipeline yet"):
        _engine(model, pp_pipeline=True)


def test_refuses_beam_search(model):
    with pytest.raises(ValueError, match="does not support beam_search yet"):
        _engine(model).beam_search(_tokens(5), num_beams=2, max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="beam search"):
        Engine(model[0], model[2], max_len=64).beam_search(
            jnp.asarray(_tokens(5)), num_beams=2, max_new_tokens=2)


@pytest.mark.parametrize("kw,feature", [
    (dict(park_dir="spool"), "park_resume"),
    (dict(preempt_after=1.0), "park_resume"),
    (dict(role="prefill"), "kv_export"),
    (dict(role="decode"), "kv_export"),
], ids=["park-dir", "preempt-after", "role-prefill", "role-decode"])
def test_server_refuses_what_moves_a_slot(model, tmp_path, kw, feature):
    from shellac_tpu.inference.server import InferenceServer

    cfg, _, params = model
    if "park_dir" in kw:
        kw = dict(park_dir=str(tmp_path))
    with pytest.raises(ValueError, match=f"does not support {feature} yet"):
        InferenceServer(cfg, params, engine=_engine(model), autotune=False, **kw)


def test_refuses_another_page_size(model):
    with pytest.raises(ValueError, match="page is one window"):
        _engine(model, block_size=16)


def test_model_and_backend_must_match(model):
    from shellac_tpu.inference.batching import BatchingEngine
    from shellac_tpu.models.registry import get_model_config

    cfg, _, params = model
    with pytest.raises(ValueError, match="serves on the 'eva' cache backend"):
        BatchingEngine(cfg, params, n_slots=2, max_len=64)
    tiny = get_model_config("tiny").replace(dtype="float32")
    with pytest.raises(ValueError, match="holds nothing else"):
        make_backend("eva", tiny, 2, 64)
    with pytest.raises(ValueError, match="keeps EVA state"):
        from shellac_tpu.inference.kvcache import init_cache

        transformer.forward_with_cache(
            cfg, params, jnp.zeros((1, 1), jnp.int32), init_cache(cfg, 1, 64))


# ---- (f) the step records' counts -------------------------------------------

def test_step_records_count_the_rows_attended(model):
    reg = Registry()
    eng = _engine(model, reg)
    out = eng.run([(rid, _tokens(n, seed=100 + rid), m)
                   for rid, (n, m) in enumerate(REQS)])
    exact = pooled = 0
    for rid, (n, m) in enumerate(REQS):
        assert len(out[rid]) == m
        # the first token comes from prefill; decode ticks sit at
        # positions n .. n + m - 2
        for p in range(n, n + m - 1):
            exact += p % W + 1
            pooled += (p // W) * (W // C)
    recs = list(reg.step_records)
    assert sum(r.counts["eva_window_rows"] for r in recs) == exact
    assert sum(r.counts["eva_summary_rows"] for r in recs) == pooled
    assert sum(r.counts["decode_valid_ticks"] for r in recs) == sum(
        m - 1 for _, m in REQS)
    names = {sp[0] for r in recs for sp in r.spans}
    assert {"cache.prepare_slot", "cache.ensure_blocks",
            "cache.release_slot"} <= names


def test_the_compiled_programs_carry_the_eva_scopes(model):
    """`trace-report`'s by-scope section splits an EVA tick, and a
    prefill, by these names."""
    import re

    from shellac_tpu.obs import tracereport

    cfg, _, params = model
    cache = init_cache_for(cfg, 2, 128)
    want = {"eva.pool", "eva.summary_write", "eva.attend", "kv.write",
            "attn.qkv", "attn.out", "mlp", "unembed"}
    for toks, fresh in ((jnp.zeros((2, 1), jnp.int32), False),
                        (jnp.zeros((2, 48), jnp.int32), True)):
        text = jax.jit(lambda p, c, t, fresh=fresh: transformer.forward_with_cache(
            cfg, p, t, c, fresh_cache=fresh)).lower(params, cache, toks).as_text(
                debug_info=True)
        found = {tracereport.scope_of({"op_name": n})
                 for n in re.findall(r'loc\("([^"]+)"', text)}
        assert want <= found, sorted(want - found)
        assert found - {None} <= set(tracereport.DEVICE_SCOPES)
