"""Learned sparse attention (cfg.dsa: Keye-VL-2.0's text model) on the
normal path, against the plain float32 reference in
benchmark/arch/keye_vl2.py: the full forward, prefill (whole and in
chunks) then decode through the paged pools, the paged engine, the sets
themselves, the power of the tolerance, the refusals, the step records'
counts and the conversion of the published config.

Small size on the CPU: 2 layers, hidden 64, 4 heads on 2 KV heads of 16,
2 index heads of 8, 8 rows kept, 8 experts of width 32 with 2 a token,
vocabulary 128, pages of 4, seeded random weights, float32 at `highest`
matmul precision on both sides.
"""

import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu.inference.cache import engine_class, make_backend
from shellac_tpu.inference.engine import Engine
from shellac_tpu.inference.kvcache import init_cache_for, init_paged_cache
from shellac_tpu.models import transformer
from shellac_tpu.models.convert import config_from_hf
from shellac_tpu.obs import Registry
from shellac_tpu.ops import dsa_attention as dsa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEPT, PAGE, VOCAB = 8, 4, 128
HF = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=16, hidden_act="silu",
    hidden_size=64, intermediate_size=96, max_position_embeddings=256,
    max_window_layers=2, mlp_only_layers=[], model_type="KeyeVL2",
    moe_intermediate_size=32, norm_topk_prob=True, num_attention_heads=4,
    num_experts=8, num_experts_per_tok=2, num_hidden_layers=2,
    num_key_value_heads=2, num_local_experts=8, rms_norm_eps=1e-6,
    rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config=dict(indexer_head_dim=8, indexer_num_heads=2,
                   indexer_num_kv_heads=1, kv_chunk_size=512, q_chunk_size=512,
                   topk=KEPT),
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=VOCAB,
)
# Both sides compute in float32 at `highest` and differ in the ORDER of
# float32 sums only (the program attends gathered rows in the order the
# choice returns them, and runs its experts over sorted rows; the
# reference masks a whole row and scans the experts). Logits here reach
# |3.9|; the widest gap seen is 1e-6 over the forward cases and 3e-6 over
# prefill-then-decode. 2e-5 leaves 6 x room and is 70,000 x under the
# smallest departure the power tests measure (1.47: at this size, 8 rows
# kept, the choice decides most of what a query reads).
TOL = 2e-5


@pytest.fixture(scope="module")
def arch():
    spec = importlib.util.spec_from_file_location(
        "bench_arch_keye_vl2", os.path.join(ROOT, "benchmark", "arch", "keye_vl2.py"))
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
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).astype(np.int32)


def _forward(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        return transformer.forward(cfg, params, jnp.asarray(toks)[None])[0]


def _paged(cfg, batch=1, max_len=48):
    """The three pools in pages of 4, every row owning its pages."""
    mb = max_len // PAGE
    tables = 1 + jnp.arange(batch * mb, dtype=jnp.int32).reshape(batch, mb)
    return init_paged_cache(cfg, batch, batch * mb + 1, PAGE, mb, tables=tables)


# ---- (h) the published config -----------------------------------------------

def test_config_from_hf_maps_the_catalog_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        published = json.load(f)
    cfg = config_from_hf(types.SimpleNamespace(**published))
    assert (cfg.dsa.index_heads, cfg.dsa.index_dim, cfg.dsa.topk) == (16, 64, 2048)
    assert cfg.moe.num_experts == 128 and cfg.moe.num_experts_per_token == 8
    assert cfg.moe.norm_topk_prob and cfg.moe.dropless
    assert cfg.moe.num_shared_experts == 0 and cfg.moe.d_ff_expert == 768
    assert cfg.first_k_dense == 0 and cfg.moe_every == 1
    assert cfg.qk_norm and not cfg.attn_bias and not cfg.tie_embeddings
    assert (cfg.n_heads, cfg.kv_heads, cfg.dim_per_head) == (32, 4, 128)
    assert cfg.rope_theta == 1e7 and cfg.attn_window is None
    assert cfg.rope_yarn is None and cfg.rope_linear is None
    assert cfg.vocab_size == 151936 and cfg.d_model == 2048
    # a token's kv heads lie side by side in one cached row
    assert (cfg.cache_kv_heads, cfg.cache_head_dim, cfg.cache_v_head_dim) == (1, 512, 512)


@pytest.mark.parametrize("other", [
    dict(attn_window=16), dict(eva={"window": 32, "chunk": 4}),
    dict(mla="mla"), dict(attn_pattern=("full", "window"), attn_window=16),
], ids=["attn_window", "eva", "mla", "attn_pattern"])
def test_validate_refuses_other_attention_kinds(model, other):
    from shellac_tpu.config import MLAConfig

    if other.get("mla"):
        other = dict(mla=MLAConfig(), n_kv_heads=None)
    with pytest.raises(ValueError, match="dsa chooses the rows|EVA|eva"):
        model[0].replace(**other).validate()


# ---- (a) forward --------------------------------------------------------------

@pytest.mark.parametrize(
    "n", [5, KEPT, KEPT + 1, 4 * KEPT + 5],
    ids=["under", "at", "one-over", "several-times"])
def test_forward_matches_reference(arch, model, n):
    cfg, w, params = model
    toks = _tokens(n, seed=n)
    got = _forward(cfg, params, toks)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(n))
    assert got.shape == (n, VOCAB) and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - ref))) < TOL


# ---- (b) prefill, whole and in chunks, then decode ----------------------------

TOTAL = 4 * KEPT + 7


@pytest.fixture(scope="module")
def cached(arch, model):
    cfg, w, params = model
    toks = _tokens(TOTAL, seed=11)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(TOTAL))
    run = jax.jit(lambda p, t, c, n, fresh: transformer.forward_with_cache(
        cfg, p, t, c, new_tokens_len=n, fresh_cache=fresh), static_argnums=4)
    decode = jax.jit(lambda p, t, c: transformer.forward_with_cache(cfg, p, t, c))
    return toks, ref, run, decode


@pytest.mark.parametrize("chunks", [
    (1,), (3,), (PAGE,), (KEPT - 1,), (KEPT,), (KEPT + 1,), (2 * KEPT + 3,),
    (5, 6), (3, KEPT, 7), (KEPT + 2, 1, 9), (7, 7, 7, 7),
], ids=lambda c: "+".join(map(str, c)))
def test_prefill_then_decode_match_full_forward(model, cached, chunks):
    """A prompt prefilled whole (one chunk) or in chunks that divide
    neither the prompt nor a page, then every later token decoded through
    the three pools, against the reference's full forward: across page
    edges (pages of 4) and across the context at which the choice begins
    (8 rows). A prompt has to leave what its length in decode ticks would
    have left."""
    cfg, _, params = model
    toks, ref, run, decode = cached
    cache, at, worst = _paged(cfg), 0, 0.0
    with jax.default_matmul_precision("highest"):
        for n in chunks:
            padded = np.zeros((1, 16 if n <= 16 else 32), np.int32)
            padded[0, :n] = toks[at:at + n]
            logits, cache = run(params, jnp.asarray(padded), cache,
                                jnp.asarray([n], jnp.int32), at == 0)
            worst = max(worst, float(jnp.max(jnp.abs(logits[0, :n] - ref[at:at + n]))))
            at += n
        for i in range(at, TOTAL):
            lg, cache = decode(params, jnp.asarray(toks[i:i + 1])[None], cache)
            worst = max(worst, float(jnp.max(jnp.abs(lg[0, 0] - ref[i]))))
    assert int(cache.lengths[0]) == TOTAL
    assert worst < TOL


def test_a_state_no_larger_than_the_rows_kept_attends_every_row(model, cached):
    """max_len 8 = the rows kept: nothing to choose, no index read."""
    cfg, _, params = model
    toks, ref, run, decode = cached
    cache = _paged(cfg, max_len=KEPT)
    padded = np.zeros((1, 4), np.int32)
    padded[0, :3] = toks[:3]
    with jax.default_matmul_precision("highest"):
        _, cache = run(params, jnp.asarray(padded), cache, jnp.asarray([3]), True)
        for i in range(3, KEPT):
            lg, cache = decode(params, jnp.asarray(toks[i:i + 1])[None], cache)
            assert float(jnp.max(jnp.abs(lg[0, 0] - ref[i]))) < TOL


# ---- (c) the paged engine ------------------------------------------------------

# prompts under and over the rows kept, outputs that cross them
REQS = [(3, 20), (21, 9), (8, 12), (5, 3), (30, 14), (9, 25), (14, 6)]


def _engine(model, reg=None, **kw):
    cfg, _, params = model
    kw = dict(dict(n_slots=3, max_len=64, temperature=0.0, decode_ticks=4,
                   overlap_decode=True, overlap_prefill=True, logprobs=True,
                   cache_backend="paged", block_size=PAGE,
                   registry=reg or Registry()), **kw)
    return engine_class("paged")(cfg, params, **kw)


@pytest.fixture(scope="module")
def one_request(model):
    """The one-request path: Engine.generate on each prompt alone (its
    cache is the same three pools, every row owning its pages)."""
    cfg, _, params = model
    single = Engine(cfg, params, temperature=0.0, max_len=64)
    out = {}
    with jax.default_matmul_precision("highest"):
        for rid, (n, m) in enumerate(REQS):
            res = single.generate(jnp.asarray(_tokens(n, seed=100 + rid))[None],
                                  max_new_tokens=m)
            out[rid] = (np.asarray(res.tokens)[0].tolist(),
                        np.asarray(res.logprobs)[0])
    return out


@pytest.mark.parametrize("overlap,ticks,chunk", [
    (True, 4, None), (False, 1, None), (True, 7, None), (True, 4, 5),
    (False, 2, 16)], ids=["overlap-k4", "strict-k1", "overlap-k7",
                          "overlap-k4-chunk5", "strict-k2-chunk16"])
def test_engine_streams_and_logprobs_equal_one_request_path(
        model, one_request, overlap, ticks, chunk):
    """Seven requests through three slots: slots under and over the rows
    kept in one batch, slots re-used after a release, prompts whole and
    in chunks. Greedy streams equal the single-request Engine's; the
    emitted tokens' log-probabilities agree to TOL."""
    eng = _engine(model, decode_ticks=ticks, overlap_decode=overlap,
                  overlap_prefill=overlap, prefill_chunk=chunk)
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
    assert eng.stats["decode_attn"] == "chosen_rows"


def test_engine_logits_follow_the_reference(arch, model, one_request):
    _, w, _ = model
    rid, (n, m) = 4, REQS[4]
    toks, _ = one_request[rid]
    seq = np.concatenate([_tokens(n, seed=100 + rid), np.asarray(toks[:m - 1])])
    ref = arch.reference_logits(HF, w, jnp.asarray(seq, jnp.int32),
                                jnp.arange(n - 1, n + m - 1))
    assert np.asarray(ref).argmax(axis=-1).tolist() == toks[:m]


def test_accounting_counts_the_index_rows(model):
    be = make_backend("paged", model[0], 2, 64, block_size=PAGE, pool_tokens=128)
    row = 2 * (2 * 2 * 16 + 8) * 4           # L x (k, v x Hkv x Dh + index key) x f32
    assert be.bytes_per_token() == row
    cache = be.init_cache()
    assert cache.k.shape == (2, 33, 1, PAGE, 32) and cache.v.shape == cache.k.shape
    assert cache.idx.shape == (2, 33, 8, PAGE)
    assert sum(x.size * x.dtype.itemsize for x in (cache.k, cache.v, cache.idx)
               ) == 33 * PAGE * row


# ---- (d) the sets themselves -----------------------------------------------------

def _sets(mask_row):
    return set(np.flatnonzero(np.asarray(mask_row)).tolist())


def test_program_and_reference_choose_the_same_sets(arch, model, cached, monkeypatch):
    """Layer 0's chosen positions at every position of a sequence: from
    the decode path (row numbers), from the cached-chunk path (a mask),
    and from the reference's own projections, scores and top_k."""
    cfg, w, params = model
    toks, _, _, _ = cached
    n_layers = cfg.n_layers
    taken = {"rows": [], "mask": []}
    real_rows, real_mask = dsa.select_rows, dsa.topk_mask

    def rows_spy(scores, allowed, k):
        rows, ok = real_rows(scores, allowed, k)
        jax.debug.callback(lambda r, o: taken["rows"].append((r, o)), rows, ok,
                           ordered=True)
        return rows, ok

    def mask_spy(scores, allowed, k):
        mask = real_mask(scores, allowed, k)
        jax.debug.callback(lambda m: taken["mask"].append(m), mask, ordered=True)
        return mask

    monkeypatch.setattr(dsa, "select_rows", rows_spy)
    monkeypatch.setattr(dsa, "topk_mask", mask_spy)

    # the reference's layer 0, from its own pieces
    with jax.default_matmul_precision("highest"):
        eps, theta = HF["rms_norm_eps"], HF["rope_theta"]
        pos = jnp.arange(TOTAL)
        x = jnp.take(w["embed"], jnp.asarray(toks), axis=0)
        hx = arch._rms(x, w["attn_norm"][0], eps)
        u = arch._rope_half((hx @ w["idx_wq"][0]).reshape(TOTAL, 2, 8), pos, theta)
        c = arch._layer_norm(hx @ w["idx_wk"][0], w["idx_k_norm"][0],
                             w["idx_k_bias"][0], eps)
        c = arch._rope_half(c[:, None, :], pos, theta)[:, 0, :]
        want = arch.chosen(arch.index_scores(u, hx @ w["idx_ww"][0], c), pos, KEPT)
    want = [_sets(r) for r in np.asarray(want)]
    for t, s in enumerate(want):
        assert len(s) == min(t + 1, KEPT)
        if t < KEPT:                      # no more rows than are kept: all
            assert s == set(range(t + 1))

    with jax.default_matmul_precision("highest"):
        # decode, one token at a time from position 1
        cache = _paged(cfg)
        first = np.zeros((1, 4), np.int32)
        first[0, 0] = toks[0]
        _, cache = transformer.forward_with_cache(
            cfg, params, jnp.asarray(first), cache,
            new_tokens_len=jnp.asarray([1]), fresh_cache=True)
        for i in range(1, TOTAL):
            _, cache = transformer.forward_with_cache(
                cfg, params, jnp.asarray(toks[i:i + 1])[None], cache)
        jax.effects_barrier()
        ticks = taken["rows"][::n_layers]
        assert len(ticks) == TOTAL - 1
        for t, (rows, ok) in zip(range(1, TOTAL), ticks):
            got = set(np.asarray(rows)[0][np.asarray(ok)[0]].tolist())
            assert got == want[t], t
        # cached chunks that divide nothing
        cache, at, taken["mask"] = _paged(cfg), 0, []
        for n in (5, 11, 7, 16):
            padded = np.zeros((1, 16), np.int32)
            padded[0, :n] = toks[at:at + n]
            _, cache = transformer.forward_with_cache(
                cfg, params, jnp.asarray(padded), cache,
                new_tokens_len=jnp.asarray([n]), fresh_cache=False)
            jax.effects_barrier()
            mask = np.asarray(taken["mask"][-n_layers])[0]
            for i in range(n):
                assert _sets(mask[i]) == want[at + i], at + i
            at += n


@pytest.mark.parametrize("case", ["ties", "zeros", "few", "random"])
def test_choice_is_exact_with_ties_to_the_lower_position(arch, case):
    """topk_mask (a threshold, no sort), select_rows (top_k) and the
    reference's choice give one set: exactly k rows, ties to the lower
    position, -0.0 a tie with 0.0, every allowed row where there are no
    more than k."""
    rng = np.random.default_rng(5)
    q, s, k = 6, 40, 8
    scores = rng.normal(size=(q, s)).astype(np.float32)
    q_pos = np.asarray([3, 7, 8, 20, 33, 39])
    if case == "ties":
        scores = np.round(scores * 2) / 2            # many equal values
    elif case == "zeros":
        scores = np.where(rng.random((q, s)) < 0.7, 0.0, scores).astype(np.float32)
        scores[:, ::3] *= -1.0                       # -0.0 among the zeros
    elif case == "few":
        q_pos = np.asarray([0, 1, 2, 5, 6, 7])
    allowed = np.arange(s)[None, :] <= q_pos[:, None]
    mask = np.asarray(dsa.topk_mask(jnp.asarray(scores), jnp.asarray(allowed), k))
    rows, ok = dsa.select_rows(jnp.asarray(scores), jnp.asarray(allowed), k)
    ref = np.asarray(arch.chosen(jnp.asarray(scores), jnp.asarray(q_pos), k))
    for i in range(q):
        want = _sets(ref[i])
        assert len(want) == min(q_pos[i] + 1, k)
        assert _sets(mask[i]) == want
        assert set(np.asarray(rows)[i][np.asarray(ok)[i]].tolist()) == want
        # the brute-force definition: sort by (-score, position)
        order = sorted(range(q_pos[i] + 1),
                       key=lambda j: (-float(scores[i, j] + 0.0), j))
        assert want == set(order[:k])


@pytest.mark.parametrize("first_kept,tiles", [
    ("tile0", 3), ("tile1", 3), ("mixed", 3), ("tile0", 4), ("mixed", 4)])
def test_masked_flash_kernel_matches_the_plain_form(first_kept, tiles):
    """The Pallas kernel (interpreted here) under a random mask with the
    causal frontier mid-block, against the reference einsum: with every
    row's first kept key in the first key tile, with whole leading tiles
    masked (the running max still at its floor when the first kept key
    comes), and with some rows that keep nothing at all (they read 0);
    over 3 x 512 keys (steps of BLOCK_K) and 4 x 512 (whole tiles of
    FLASH_BLOCK_K: the kernel steps by those)."""
    rng = np.random.default_rng(2)
    b, sq, sk, h, hkv, d = 1, 2 * dsa.BLOCK_Q, tiles * dsa.BLOCK_K, 4, 2, 128
    assert dsa.flash_block_k(sk) == (dsa.FLASH_BLOCK_K if tiles == 4 else dsa.BLOCK_K)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, hkv, d)), jnp.float32)
    q_pos = 700 + jnp.arange(sq, dtype=jnp.int32)[None]
    mask = (rng.random((b, sq, sk)) < 0.3) & (
        np.arange(sk)[None, None, :] <= np.asarray(q_pos)[:, :, None])
    if first_kept == "tile0":
        mask[:, :, 0] = True
    else:
        mask[:, :, :dsa.BLOCK_K + 37] = False
        mask[:, :, dsa.BLOCK_K + 37] = True
    if first_kept == "mixed":
        mask[:, ::3, :] = False
    some = jnp.asarray(mask.any(-1))
    mask = jnp.asarray(mask, jnp.int8)
    with jax.default_matmul_precision("highest"):
        got = dsa.masked_attention(q, k, v, mask, q_pos, d ** -0.5,
                                   impl="flash", interpret=True)
        ref = dsa.masked_attention_ref(q, k, v, mask != 0, d ** -0.5)
    gap = jnp.abs(got - ref).max(axis=(2, 3))
    assert float(jnp.max(jnp.where(some, gap, 0.0))) < 2e-5
    assert float(jnp.max(jnp.where(some[..., None, None], 0.0, jnp.abs(got)))) == 0.0


def test_index_scores_kernel_matches_the_plain_form():
    """The Pallas kernel (interpreted here) against the einsum, on the
    tiles a query block can reach; and the masks made from either."""
    rng = np.random.default_rng(3)
    b, sq, sk, j, di = 1, 2 * dsa.BLOCK_Q, 3 * dsa.BLOCK_K, 4, 64
    u = jnp.asarray(rng.normal(size=(b, sq, j, di)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(b, sq, j)), jnp.float32)
    c_t = jnp.asarray(rng.normal(size=(b, di, sk)), jnp.float32)
    q_pos = 700 + jnp.arange(sq, dtype=jnp.int32)[None]
    allowed = np.arange(sk)[None, None, :] <= np.asarray(q_pos)[:, :, None]
    with jax.default_matmul_precision("highest"):
        ref = dsa.index_scores(u, w, c_t)
        got = dsa.index_scores_flash(u, w, c_t, dsa._live_blocks(q_pos, sk), True)
        assert float(jnp.max(jnp.abs(jnp.where(allowed, got - ref, 0.0)))) < 2e-5
        k_len = jnp.asarray([1100])
        flash = dsa.choice_mask(u, w, c_t, q_pos, k_len, 64, sk, impl="flash",
                                interpret=True)
        plain = dsa.choice_mask(u, w, c_t, q_pos, k_len, 64, sk, impl="ref")
    assert bool(jnp.all(flash == plain)) and int(plain.sum()) == sq * 64


@pytest.mark.parametrize("cap,topk,unit,want", [
    (33792, 2048, 512, (9216, 17408, 25600, 33792)),  # the cell's: whole pages and key tiles
    (34304, 2048, 512, (8704, 17408, 26112, 34304)),  # 67 pages are no whole tiles: pages
    (48, KEPT, PAGE, (12, 24, 36, 48)),
    (24, KEPT, PAGE, (12, 20, 24)),              # a quarter holds no more than are kept
    (KEPT, KEPT, PAGE, (KEPT,)),                 # nothing to choose: the table
])
def test_live_widths(cap, topk, unit, want):
    assert dsa.live_widths(cap, topk, unit) == want


def test_a_chunk_reads_the_smallest_extent_that_holds_its_rows(model, cached, monkeypatch):
    """A cached chunk scores, ranks and attends the first of
    `live_widths` that holds the rows its slot has after it, whatever
    the table could hold; `cached`'s cases hold its logits to the
    reference at every extent."""
    cfg, _, params = model
    toks, _, _, _ = cached
    seen, real = [], dsa.topk_mask

    def spy(scores, allowed, k):
        mask = real(scores, allowed, k)
        jax.debug.callback(lambda m: seen.append(m.shape[-1]), mask, ordered=True)
        return mask

    monkeypatch.setattr(dsa, "topk_mask", spy)
    cache, at = _paged(cfg), 0            # 48 rows a slot: 12, 24, 36, 48
    for n, width in ((5, 12), (7, 12), (11, 24), (16, 48)):
        padded = np.zeros((1, 16), np.int32)
        padded[0, :n] = toks[at:at + n]
        _, cache = transformer.forward_with_cache(
            cfg, params, jnp.asarray(padded), cache,
            new_tokens_len=jnp.asarray([n]), fresh_cache=False)
        jax.effects_barrier()
        assert seen and set(seen) == {width}, (at, n, seen)
        at += n
        del seen[:]


def test_logits_at_is_that_row_of_the_logits(model, cached):
    """`logits_at` unembeds one row a sequence: the row the whole
    unembedding gives (float32, one matmul either way)."""
    cfg, _, params = model
    toks, _, _, _ = cached
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = toks[:11]
    with jax.default_matmul_precision("highest"):
        whole, _ = transformer.forward_with_cache(
            cfg, params, jnp.asarray(padded), _paged(cfg),
            new_tokens_len=jnp.asarray([11]), fresh_cache=False)
        row, _ = transformer.forward_with_cache(
            cfg, params, jnp.asarray(padded), _paged(cfg),
            new_tokens_len=jnp.asarray([11]), fresh_cache=False,
            logits_at=jnp.asarray([10]))
    assert row.shape == (1, 1, VOCAB)
    assert float(jnp.max(jnp.abs(row[0, 0] - whole[0, 10]))) < 1e-6


# ---- (e) the tolerance can see the mechanism -------------------------------------

def _most_recent(orig):
    def f(u, w, c_t):
        return jnp.broadcast_to(
            jnp.arange(c_t.shape[-1], dtype=jnp.float32), orig(u, w, c_t).shape)
    return f


def _no_relu(orig):
    def f(u, w, c_t):
        s = jnp.einsum("bqjd,bdk->bqjk", u, c_t)
        return jnp.einsum("bqjk,bqj->bqk", s, w)
    return f


@pytest.mark.parametrize("name,mutant,topk", [
    ("topk_mask", lambda orig: lambda scores, allowed, k: allowed, KEPT),
    ("topk_mask", lambda orig: orig, KEPT // 2),
    ("index_scores", _most_recent, KEPT),
    ("index_scores", _no_relu, KEPT),
], ids=["choice-off", "half-the-rows", "most-recent-rows", "no-relu"])
def test_tolerance_fails_a_run_without_the_mechanism(arch, model, monkeypatch,
                                                     name, mutant, topk):
    cfg, w, params = model
    n = 4 * KEPT + 5
    toks = _tokens(n, seed=n)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(n))
    monkeypatch.setattr(dsa, name, mutant(getattr(dsa, name)))
    cfg = cfg.replace(dsa=cfg.dsa.__class__(
        index_heads=cfg.dsa.index_heads, index_dim=cfg.dsa.index_dim, topk=topk))
    assert float(jnp.max(jnp.abs(_forward(cfg, params, toks) - ref))) > 40 * TOL


def test_reference_with_the_choice_off_is_dense_attention(arch, model):
    cfg, w, params = model
    toks = _tokens(30, seed=4)
    dense = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(30), dense=True)
    got = _forward(cfg.replace(dsa=cfg.dsa.__class__(
        index_heads=2, index_dim=8, topk=64)), params, toks)
    assert float(jnp.max(jnp.abs(got - dense))) < TOL


# ---- (f) what the third pool cannot do yet refuses, at construction ---------------

def test_refuses_prefix_cache(model):
    with pytest.raises(ValueError, match="does not support prefix_cache"):
        _engine(model, prefix_cache=True)


def test_refuses_kv_quant(model):
    with pytest.raises(ValueError, match="does not support kv_quant"):
        make_backend("paged-int8", model[0], 2, 64)
    with pytest.raises(ValueError, match="does not support kv_quant"):
        make_backend("paged", model[0], 2, 64, kv_quant="int8")
    with pytest.raises(ValueError, match="rolling / kv_quant do not apply"):
        init_cache_for(model[0], 1, 64, kv_quant="int8")


def test_refuses_speculative(model):
    cfg, _, params = model
    with pytest.raises(ValueError, match="does not support speculative"):
        engine_class("paged", speculative=True)(
            cfg, params, cfg, params, n_slots=2, max_len=64, cache_backend="paged")


def test_refuses_pp_pipeline(model):
    with pytest.raises(ValueError, match="does not support pp_pipeline"):
        _engine(model, pp_pipeline=True)


def test_refuses_a_mesh(model):
    from shellac_tpu.config import ParallelConfig
    from shellac_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(ParallelConfig(tp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="does not support mesh"):
        _engine(model, mesh=mesh)


def test_refuses_beam_search(model):
    with pytest.raises(ValueError, match="does not support beam_search"):
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
    with pytest.raises(ValueError, match=f"does not support {feature}"):
        InferenceServer(cfg, params, engine=_engine(model), autotune=False, **kw)


@pytest.mark.parametrize("backend", ["dense", "rolling", "dense-int8"])
def test_other_backends_hold_no_index_pool(model, backend):
    with pytest.raises(ValueError, match="serves on the 'paged' cache backend"):
        make_backend(backend, model[0], 2, 64)


def test_a_cache_without_the_third_pool_refuses(model):
    from shellac_tpu.inference.kvcache import init_cache

    cfg, _, params = model
    with pytest.raises(ValueError, match="holds the third pool"):
        transformer.forward_with_cache(
            cfg, params, jnp.zeros((1, 1), jnp.int32), init_cache(cfg, 1, 64))
    with pytest.raises(NotImplementedError, match="packed segments"):
        transformer.forward(cfg, params, jnp.zeros((1, 8), jnp.int32),
                            segment_ids=jnp.zeros((1, 8), jnp.int32))


def test_three_unequal_position_axes_refuse(model):
    cfg, _, params = model
    toks = jnp.asarray(_tokens(6))[None]
    text = np.broadcast_to(np.arange(6, dtype=np.int32), (3, 1, 6))
    with jax.default_matmul_precision("highest"):
        same = transformer.forward(cfg, params, toks, positions=text)
        assert float(jnp.max(jnp.abs(same - transformer.forward(cfg, params, toks)))) == 0.0
    image = text.copy()
    image[1, 0, 2:] += 3                    # a height axis of its own
    with pytest.raises(NotImplementedError, match="three unequal M-RoPE position axes"):
        transformer.forward(cfg, params, toks, positions=image)


# ---- (g) the step records' counts ---------------------------------------------------

def test_step_records_count_the_rows_scored_and_kept(model):
    reg = Registry()
    eng = _engine(model, reg)
    out = eng.run([(rid, _tokens(n, seed=100 + rid), m)
                   for rid, (n, m) in enumerate(REQS)])
    scored = kept = 0
    for rid, (n, m) in enumerate(REQS):
        assert len(out[rid]) == m
        # the first token comes from prefill; decode queries sit at
        # positions n .. n + m - 2 and score every row up to themselves
        for p in range(n, n + m - 1):
            scored += p + 1
            kept += min(p + 1, KEPT)
    recs = list(reg.step_records)
    assert sum(r.counts["dsa_index_rows"] for r in recs) == scored
    assert sum(r.counts["dsa_selected_rows"] for r in recs) == kept
    assert sum(r.counts["decode_valid_ticks"] for r in recs) == sum(
        m - 1 for _, m in REQS)
    assert all(r.counts["eva_window_rows"] == 0 for r in recs)


def test_the_compiled_programs_carry_the_dsa_scopes(model):
    """`trace-report`'s by-scope section splits a tick, and a chunk, by
    these names."""
    import re

    from shellac_tpu.obs import tracereport

    cfg, _, params = model
    cache = _paged(cfg, batch=2)
    want = {"dsa.index_proj", "dsa.index_write", "dsa.score", "dsa.select",
            "dsa.attend", "kv.write", "attn.qkv", "attn.out", "unembed"}
    for toks in (jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 12), jnp.int32)):
        text = jax.jit(lambda p, c, t: transformer.forward_with_cache(
            cfg, p, t, c)).lower(params, cache, toks).as_text(debug_info=True)
        found = {tracereport.scope_of({"op_name": n})
                 for n in re.findall(r'loc\("([^"]+)"', text)}
        assert want <= found, sorted(want - found)
        assert found - {None} <= set(tracereport.DEVICE_SCOPES)
