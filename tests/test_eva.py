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


# ---- (e2) the decode kernel against the XLA form -----------------------------
#
# ops/eva_attention.py::eva_decode_kernel, interpreted on the CPU, against
# eva_decode_attention over the same rows. Small shapes with the kernel's
# structure whole: 2 layers of stacks (the kernel reads layer 1), rings of
# 64 rows copied in blocks of 16, 4 heads of 128, pages of 16 pooled rows,
# tables of 4 entries over a pool of 9 pages (page 0 is scratch).

KL, KW, KS, KH, KD, KP, KR, KMB, KBLOCK = 2, 64, 4, 4, 128, 9, 16, 4, 16

# (id, valid ring rows a slot, pages a slot, table rows, ring column a slot)
KERNEL_CASES = [
    # One row and no page: the softmax of one score.
    ("one-row-no-page", (1,), (0,), [[1, 2, 3, 4]], (0,)),
    ("a-blocks-last-row", (KBLOCK,), (1,), [[1, 2, 3, 4]], (1,)),
    ("a-blocks-first-row", (KBLOCK + 1,), (1,), [[1, 2, 3, 4]], (2,)),
    ("the-whole-ring-no-page", (KW,), (0,), [[1, 2, 3, 4]], (3,)),
    ("one-page", (5,), (1,), [[7, 2, 3, 4]], (0,)),
    ("every-page-of-its-row", (5,), (KMB,), [[8, 6, 4, 2]], (0,)),
    # Two slots whose pages alternate through the pool.
    ("tables-interleave", (9, 40), (2, 3), [[1, 3, 5, 7], [2, 4, 6, 8]],
     (1, 0)),
    # Entries past a slot's count name pages it does not own (another
    # slot's, and pages nobody owns, which hold NaN): never read.
    ("stale-entries", (30, 3), (1, 2), [[5, 6, 8, 8], [6, 7, 5, 1]], (2, 3)),
    # Every slot of the stacks, in another order than its columns.
    ("four-slots", (64, 1, 33, 16), (0, 4, 2, 1),
     [[1, 1, 1, 1], [2, 3, 4, 5], [6, 7, 1, 1], [8, 2, 2, 2]], (3, 1, 0, 2)),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_exact,n_pages,tables,cols",
                         [pytest.param(*c[1:], id=c[0]) for c in KERNEL_CASES])
def test_decode_kernel_matches_the_xla_form(dtype, n_exact, n_pages, tables,
                                            cols):
    """The kernel computes eva_decode_attention's equations from the
    WHOLE stacks, a layer's number, the block table and the counts; and
    it reads nothing else: every ring row at or past a slot's count,
    every page no slot owns, every other layer and every column no slot
    names hold NaN on the kernel's side, and no output may see one."""
    from shellac_tpu.ops import eva_attention as ea

    rng = np.random.default_rng(len(n_exact) * 1000 + sum(n_exact))
    b, layer, scale = len(n_exact), 1, KD ** -0.5
    draw = lambda *s: rng.standard_normal(s).astype(np.float32)  # noqa: E731
    q = draw(b, KH, KD)
    ring = [draw(KL, KW, KS, KH, KD) for _ in range(2)]
    pool = [draw(KL, KH, KP, KR, KD) for _ in range(2)]
    tables = np.asarray(tables, np.int32)
    owned = np.zeros((b, KP), bool)
    for i in range(b):
        owned[i, tables[i, :n_pages[i]]] = True
    live_ring = np.zeros((KL, KW, KS), bool)
    for i in range(b):
        live_ring[layer, :n_exact[i], cols[i]] = True
    live_pool = np.zeros((KL, KP), bool)
    live_pool[layer] = owned.any(axis=0)
    cast = lambda a: jnp.asarray(a, dtype)  # noqa: E731
    want = ea.eva_decode_attention(
        cast(q), *(cast(a[layer][:, list(cols)]) for a in ring),
        jnp.asarray(n_exact, jnp.int32), *(cast(a[layer]) for a in pool),
        jnp.asarray(owned), scale=scale)
    poisoned = (
        [np.where(live_ring[..., None, None], a, np.nan) for a in ring]
        + [np.where(live_pool[:, None, :, None, None], a, np.nan)
           for a in pool])
    got = ea.eva_decode_kernel(
        cast(q), cast(poisoned[0]), cast(poisoned[1]), n_exact,
        cast(poisoned[2]), cast(poisoned[3]), tables, n_pages, layer=layer,
        cols=cols, scale=scale, block=KBLOCK, interpret=True)
    assert got.dtype == want.dtype and got.shape == (b, KH, KD)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    # float32: both sides hold float32 scores and sums and differ in the
    # order they add them (the kernel 8 rows at a time with a running
    # max, the XLA form one softmax over the row): 6e-7 at outputs up to
    # |3|; 5e-6 leaves 8 x. bfloat16: the same sums rounded once to
    # bfloat16, and a weight rounded to bfloat16 before its value on
    # both sides, relative to maxima that differ by float32 rounding:
    # one unit of bfloat16 at |2| is 2^-7 = 0.0078; two units.
    tol = 5e-6 if dtype == jnp.float32 else 2 * 2.0 ** -7
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


# The cell's shapes: 24 slots, 32 heads of 128, rings of 2048, 217 pages of
# 128 pooled rows, 8 layers.
_Q, _RING, _POOL = (24, 32, 128), (8, 2048, 24, 32, 128), (8, 32, 217, 128, 128)

# (id, q, ring, pool, dtype, what the refusal says; None: the kernel runs)
PATH_RULE = [
    ("the-cell-bf16", _Q, _RING, _POOL, jnp.bfloat16, None),
    ("the-cell-float32", _Q, _RING, _POOL, jnp.float32, None),
    ("a-short-ring-is-one-block", _Q, (8, 128, 24, 32, 128), _POOL,
     jnp.bfloat16, None),
    ("head-dim-64", (24, 32, 64), (8, 2048, 24, 32, 64),
     (8, 32, 217, 128, 64), jnp.bfloat16, "128-lane"),
    ("8-heads-of-bf16", (24, 8, 128), (8, 2048, 24, 8, 128),
     (8, 8, 217, 128, 128), jnp.bfloat16, "8 heads"),
    ("pages-of-8-rows-of-bf16", _Q, _RING, (8, 32, 217, 8, 128),
     jnp.bfloat16, "8 pooled rows"),
    ("ring-not-whole-blocks", _Q, (8, 2048 + 64, 24, 32, 128), _POOL,
     jnp.bfloat16, "whole blocks"),
    ("float16-rows", _Q, _RING, _POOL, jnp.float16, "float16"),
    ("tiles-over-the-vmem-bound", (24, 128, 128), (8, 2048, 24, 128, 128),
     (8, 128, 217, 128, 128), jnp.bfloat16, "VMEM"),
    ("tiny-eva", (2, 4, 16), (2, 32, 2, 4, 16), (2, 4, 9, 8, 16),
     jnp.float32, "128-lane"),
]


@pytest.mark.parametrize("q,ring,pool,dtype,refusal",
                         [pytest.param(*c[1:], id=c[0]) for c in PATH_RULE])
def test_decode_path_rule(monkeypatch, q, ring, pool, dtype, refusal):
    """One rule says how a decode tick attends, from shapes, dtype,
    `impl` and the platform: under "auto" the kernel on a TPU where its
    constraints hold and the XLA form silently everywhere else; "ref"
    the XLA form; "flash" the kernel or its refusal."""
    from shellac_tpu.ops import eva_attention as ea

    said = ea.eva_kernel_refusal(q, ring, pool, dtype)
    assert (said is None) if refusal is None else (refusal in said), said
    assert ea.eva_decode_path(q, ring, pool, dtype) == "xla"  # the CPU
    assert ea.eva_decode_path(q, ring, pool, dtype, "ref") == "xla"
    if refusal is None:
        assert ea.eva_decode_path(q, ring, pool, dtype, "flash") == "eva_kernel"
    else:
        with pytest.raises(ValueError, match="refuses"):
            ea.eva_decode_path(q, ring, pool, dtype, "flash")
    monkeypatch.setattr(ea, "pallas_supported", lambda: True)
    want = "eva_kernel" if refusal is None else "xla"
    assert ea.eva_decode_path(q, ring, pool, dtype) == want
    assert ea.eva_decode_path(q, ring, pool, dtype, "ref") == "xla"


def _wide_model(arch):
    """The test model at the kernel's widths: 8 heads of 128, float32
    (eight rows a sublane tile), W 32, C 4: pages of 8 pooled rows."""
    hf = dict(HF, hidden_size=1024, num_attention_heads=8,
              num_key_value_heads=8, intermediate_size=256)
    cfg = config_from_hf(types.SimpleNamespace(**hf)).replace(
        dtype="float32", param_dtype="float32", remat=False)
    return cfg, arch.to_program(arch.make_weights(hf, 5, dtype=jnp.float32))


def test_cached_decode_through_the_kernel_matches_the_xla_form(arch):
    """The model's own decode branch, handing the kernel the whole
    stacks, the engine's table, the slots' columns and the windows each
    has completed: prefill two prompts of different lengths, then tick
    across a window's end (a ring that wraps to one row, a page that
    becomes a slot's own), every tick's logits against
    attn_impl="ref"'s."""
    cfg, params = _wide_model(arch)
    assert cfg.dim_per_head == 128
    caches = {}
    for impl in ("flash", "ref"):
        cache = init_cache_for(cfg, 2, 128)
        for slot, n in enumerate((29, 61)):
            view = cache.replace(
                tables=cache.tables[slot:slot + 1],
                lengths=jnp.zeros((1,), jnp.int32),
                slots=jnp.asarray([slot], jnp.int32))
            with jax.default_matmul_precision("highest"):
                _, view = transformer.forward_with_cache(
                    cfg, params, jnp.asarray(_tokens(n, seed=slot))[None],
                    view, fresh_cache=True, attn_impl="ref")
            cache = cache.replace(
                k=view.k, v=view.v, pk=view.pk, pv=view.pv,
                lengths=cache.lengths.at[slot].set(n))
        caches[impl] = cache
    step = jax.jit(transformer.forward_with_cache,
                   static_argnames=("cfg", "attn_impl"))
    for t in range(5):
        toks = jnp.asarray(_tokens(2, seed=50 + t))[:, None]
        out = {}
        for impl in ("flash", "ref"):
            with jax.default_matmul_precision("highest"):
                out[impl], caches[impl] = step(
                    cfg=cfg, params=params, tokens=toks, cache=caches[impl],
                    attn_impl=impl)
        np.testing.assert_allclose(np.asarray(out["flash"]),
                                   np.asarray(out["ref"]), atol=TOL, rtol=0)
    assert caches["flash"].lengths.tolist() == [34, 66]


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
    # On the CPU the tick attends through the XLA form, which reads more
    # than it attends: every row of a slot's ring, the whole pool.
    assert eng.stats["decode_attn"] == "xla"
    assert sum(r.counts["eva_read_rows"] for r in recs) > exact + pooled
    assert sum(r.counts["decode_valid_ticks"] for r in recs) == sum(
        m - 1 for _, m in REQS)
    names = {sp[0] for r in recs for sp in r.spans}
    assert {"cache.prepare_slot", "cache.ensure_blocks",
            "cache.release_slot"} <= names


@pytest.mark.parametrize("path", ["eva_kernel", "xla"])
def test_window_counts_the_rows_the_read_path_moves(path):
    """`eva_read_rows` against a brute count, slot-tick by slot-tick: the
    kernel copies a slot's valid ring rows in whole blocks and the pages
    of its completed windows; the XLA form reads every ring row a
    slot-tick and the whole pool (scratch page and all) once a tick."""
    from shellac_tpu import get_model_config
    from shellac_tpu.config import EvaConfig
    from shellac_tpu.ops.eva_attention import eva_ring_block

    window, chunk, slots = 512, 16, 3
    cfg = get_model_config("tiny-eva").replace(
        eva=EvaConfig(window=window, chunk=chunk), max_seq_len=4096)
    backend = make_backend("eva", cfg, slots, 2048)
    backend._decode_attn = path
    block, per_page = eva_ring_block(window), window // chunk
    assert block == 128 and window % block == 0
    # (prompt tokens, outputs settled, valid ticks of this window): a
    # window's first row, a block's last row then the next block's
    # first, the ring's last row then a wrap to one row with a page more.
    reqs = [(700, 1, 4), (512 + 126, 1, 3), (1022, 3, 2)]
    pairs = [(i, types.SimpleNamespace(tokens=np.zeros(n, np.int32),
                                        out=[0] * m))
             for i, (n, m, _) in enumerate(reqs)]
    n_valid = np.asarray([t for _, _, t in reqs])
    exact = pooled = read = 0
    for n, m, ticks in reqs:
        for t in range(ticks):
            p = n + m - 1 + t
            exact += p % window + 1
            pooled += (p // window) * per_page
            if path == "eva_kernel":
                read += (-(-(p % window + 1) // block) * block
                         + (p // window) * per_page)
            else:
                read += window
    if path == "xla":
        read += max(t for _, _, t in reqs) * backend.n_blocks * per_page
    got = backend.window_counts(pairs, n_valid)
    assert got == {"eva_window_rows": exact, "eva_summary_rows": pooled,
                   "eva_read_rows": read}
    # What the rows attended are of the rows moved (these positions sit
    # at blocks' edges: the worst a block's rounding gets).
    if path == "eva_kernel":
        assert 0.6 < (exact + pooled) / read <= 1.0
    else:
        assert (exact + pooled) / read < 0.6


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
