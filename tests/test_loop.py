"""A looped stack (Ouro) on the normal path, against the plain float32
reference in benchmark/arch/ouro.py: the full forward, prefill then decode
through the paged pool and through the single-request Engine, the exit
rule, which pass reads which rows, the between-pass norm, the power of the
tolerance, the accounting, the conversion, the refusals and the step
records' counts.

Small size on the CPU: hidden 64, 4 heads = 4 KV heads x 16, FFN 96,
3 layers run 3 times (9 cached layers), vocabulary 256, seeded random
weights, float32 at `highest` matmul precision on both sides.
"""

import importlib.util
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu.config import LOOP_EXCLUDES, LoopConfig, MLAConfig, MoEConfig
from shellac_tpu.inference.batching import BatchingEngine, PagedBatchingEngine
from shellac_tpu.inference.cache import engine_class, make_backend
from shellac_tpu.inference.cache.base import LOOP_UNSUPPORTED
from shellac_tpu.inference.engine import Engine
from shellac_tpu.inference.kvcache import (
    init_cache_for,
    init_paged_slot_cache,
    kv_field_names,
)
from shellac_tpu.models import transformer
from shellac_tpu.models.convert import (
    config_from_hf,
    params_from_state_dict,
    to_state_dict,
)
from shellac_tpu.obs import Registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, T, V = 3, 3, 256
HF = dict(
    model_type="ouro", hidden_size=64, intermediate_size=96, head_dim=16,
    num_attention_heads=4, num_key_value_heads=4, num_hidden_layers=L,
    total_ut_steps=T, early_exit_threshold=1, vocab_size=V,
    rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
    max_position_embeddings=512, tie_word_embeddings=False,
    layer_types=["full_attention"] * L, sliding_window=None,
    use_sliding_window=False, max_window_layers=L, hidden_act="silu",
)
# Both sides compute in float32 at `highest`; they differ in the ORDER of
# float32 sums only (the program's attention runs per query block and, in
# decode, over pages; its norms as x * rsqrt * (1 + (g - 1))). Nine layer
# passes and three norms between them; logits here reach |4.1|. The widest
# gap seen over every case below is 3.4e-6. 2e-5 leaves 5 x room and is far
# under the smallest departure the power tests measure (bfloat16 operands
# move these logits by 0.08, int8 operands by 0.20, a skipped norm between
# passes by 0.80, a skipped pass by 1.8, skipped output norms by 2.8).
TOL = 2e-5


@pytest.fixture(scope="module")
def arch():
    spec = importlib.util.spec_from_file_location(
        "bench_arch_ouro", os.path.join(ROOT, "benchmark", "arch", "ouro.py"))
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
    return np.random.default_rng(seed).integers(0, V, size=n).astype(np.int32)


def _forward(cfg, params, toks, **kw):
    with jax.default_matmul_precision("highest"):
        out = transformer.forward(cfg, params, jnp.asarray(toks)[None], **kw)
    return jax.tree.map(lambda a: a[0] if jnp.ndim(a) else a, out)


def _gap(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a) - jnp.asarray(b))))


def test_config_from_hf_maps_ouro(model):
    cfg = model[0]
    assert cfg.loop == LoopConfig(steps=T, exit_threshold=1.0)
    assert cfg.post_norms and not cfg.tie_embeddings and cfg.attn_window is None
    assert (cfg.n_layers, cfg.cache_layers) == (L, T * L)
    assert (cfg.kv_heads, cfg.dim_per_head, cfg.rope_theta) == (4, 16, 1e6)
    bad = dict(HF, layer_types=["full_attention", "sliding_attention",
                                "full_attention"])
    with pytest.raises(NotImplementedError, match="sliding_attention"):
        config_from_hf(types.SimpleNamespace(**bad))


def test_the_gate_is_a_parameter(model):
    cfg = model[0]
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    axes = transformer.logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    assert params["loop_gate"]["w"].shape == (64,)
    assert params["loop_gate"]["b"].shape == ()
    plain = transformer.init_params(cfg.replace(loop=None), jax.random.PRNGKey(0))
    assert transformer.num_params(params) == transformer.num_params(plain) + 65


# ---- (a) forward -----------------------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 33, 70])
def test_forward_matches_reference(arch, model, n):
    cfg, w, params = model
    toks = _tokens(n, seed=n)
    got = _forward(cfg, params, toks)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(n))
    assert got.shape == (n, V) and got.dtype == jnp.float32
    assert _gap(got, ref) < TOL


# ---- (b) prefill, then decode through the paged pool ------------------------

TOTAL = 41


@pytest.fixture(scope="module")
def cached(arch, model):
    cfg, w, params = model
    toks = _tokens(TOTAL, seed=11)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(TOTAL))
    prefill = jax.jit(lambda p, t, c, n: transformer.forward_with_cache(
        cfg, p, t, c, new_tokens_len=n, fresh_cache=True))
    decode = jax.jit(lambda p, t, c: transformer.forward_with_cache(cfg, p, t, c))
    return toks, ref, prefill, decode


def _prefill_then_decode(model, cached, cache, n):
    _, _, params = model
    toks, ref, prefill, decode = cached
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = toks[:n]
    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(params, jnp.asarray(padded), cache,
                                jnp.asarray([n], jnp.int32))
        worst = _gap(logits[0, :n], ref[:n])
        for i in range(n, TOTAL):
            lg, cache = decode(params, jnp.asarray(toks[i:i + 1])[None], cache)
            worst = max(worst, _gap(lg[0, 0], ref[i]))
    assert int(cache.lengths[0]) == TOTAL
    return worst


@pytest.mark.parametrize("n", [1, 7, 8, 9, 30])
def test_paged_prefill_then_decode_match_full_forward(model, cached, n):
    """A prompt of n tokens written through the block table into the
    (9 x n_blocks) pool, then every later token decoded through it, token
    by token, against the reference's full forward pass: LOGITS, every
    position. Pages of 8 rows: decode crosses page edges in every pass."""
    cfg = model[0]
    cache = init_paged_slot_cache(cfg, 1, 64)
    page = 8
    mb = 64 // page
    cache = cache.replace(
        k=jnp.zeros((T * L, mb + 1, 4, page, 16), jnp.float32),
        v=jnp.zeros((T * L, mb + 1, 4, page, 16), jnp.float32),
        tables=1 + jnp.arange(mb, dtype=jnp.int32)[None],
    )
    assert _prefill_then_decode(model, cached, cache, n) < TOL


REQS = [(5, 12), (17, 9), (8, 20), (30, 6), (3, 15)]


def _ref_stream(arch, w, prompt, out):
    """Reference logits at the positions that predicted `out`."""
    seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
    n = len(prompt)
    return np.asarray(arch.reference_logits(
        HF, w, jnp.asarray(seq), jnp.arange(n - 1, len(seq))))


@pytest.mark.parametrize("kw", [
    dict(),
    dict(prefix_cache=True),
    dict(prefill_chunk=8),
    dict(overlap_decode=False, overlap_prefill=False, decode_ticks=1),
], ids=["plain", "prefix-cache", "chunked-prefill", "strict-k1"])
def test_paged_engine_follows_the_reference(arch, model, kw):
    """Five requests through two slots of the paged engine (slots re-used,
    pages freed and taken again): every served token is the reference's
    best at its position, and its log-probability is the reference's
    log-softmax there to TOL: logits, not tokens alone."""
    cfg, w, params = model
    kw = dict(dict(n_slots=2, max_len=64, block_size=8, temperature=0.0,
                   decode_ticks=3, logprobs=True, pool_tokens=256,
                   registry=Registry()), **kw)
    eng = PagedBatchingEngine(cfg, params, **kw)
    prompts = {rid: _tokens(n, seed=50 + rid) for rid, (n, _) in enumerate(REQS)}
    if kw.get("prefix_cache"):
        # two prompts share their first two pages
        prompts[3][:16] = prompts[1][:16]
    with jax.default_matmul_precision("highest"):
        out = eng.run([(rid, prompts[rid], m) for rid, (_, m) in enumerate(REQS)])
    for rid, (_, m) in enumerate(REQS):
        ref = _ref_stream(arch, w, prompts[rid], out[rid])
        assert len(out[rid]) == m
        assert ref.argmax(axis=-1).tolist() == out[rid], rid
        lps = np.asarray(jax.nn.log_softmax(ref, axis=-1))[np.arange(m), out[rid]]
        assert np.max(np.abs(np.asarray(eng.finished_logprobs[rid]) - lps)) < TOL
    if kw.get("prefix_cache"):
        assert eng.stats["prefix_hit_tokens"] == 16
    assert eng.cache_backend.utilization() == (
        eng.cache_backend.residency()["prefix_cached_blocks"]
        / (eng._n_blocks - 1))


def test_int8_pool_serves_a_looped_stack(arch, model):
    """kv_quant='int8' pools hold a row a pass too: the stream follows the
    bf16-free reference but for int8 rounding of cached rows (greedy tokens
    whose reference gap is under 0.05, far above TOL and far under a
    missing pass)."""
    cfg, w, params = model
    eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=128, block_size=128,
                              temperature=0.0, decode_ticks=3, kv_quant="int8",
                              registry=Registry())
    assert eng._cache.k.shape[0] == T * L and eng._cache.ks.shape[0] == T * L
    prompt = _tokens(9, seed=5)
    with jax.default_matmul_precision("highest"):
        out = eng.run([("a", prompt, 10)])["a"]
    ref = _ref_stream(arch, w, prompt, out)
    gap = ref.max(axis=-1) - ref[np.arange(10), out]
    assert gap.max() < 0.05


# ---- (c) the single-request Engine, and the dense slot engine ---------------

def test_single_request_engine_follows_the_reference(arch, model):
    cfg, w, params = model
    single = Engine(cfg, params, temperature=0.0, max_len=64)
    prompt = _tokens(13, seed=7)
    with jax.default_matmul_precision("highest"):
        res = single.generate(jnp.asarray(prompt)[None], max_new_tokens=14)
    out = np.asarray(res.tokens)[0].tolist()
    ref = _ref_stream(arch, w, prompt, out)
    assert ref.argmax(axis=-1).tolist() == out
    lps = np.asarray(jax.nn.log_softmax(ref, axis=-1))[np.arange(14), out]
    assert np.max(np.abs(np.asarray(res.logprobs)[0] - lps)) < TOL


def test_slot_cache_prefill_then_decode_match_full_forward(model, cached):
    """The dense slot cache, (9, B, Hkv, max_len, Dh), rides the passes as
    xs / ys: the single-request Engine's cache and the paged prefill's
    scratch."""
    cache = init_cache_for(model[0], 1, 64)
    assert cache.k.shape == (T * L, 1, 4, 64, 16)
    assert _prefill_then_decode(model, cached, cache, 9) < TOL


def test_dense_engine_equals_paged_engine(model):
    cfg, _, params = model
    reqs = [(rid, _tokens(n, seed=50 + rid), m) for rid, (n, m) in enumerate(REQS)]
    kw = dict(n_slots=2, max_len=64, temperature=0.0, decode_ticks=3)
    with jax.default_matmul_precision("highest"):
        dense = BatchingEngine(cfg, params, **kw).run(reqs)
        paged = PagedBatchingEngine(cfg, params, block_size=8, **kw).run(reqs)
    assert dense == paged


# ---- (d) the exit rule ------------------------------------------------------

@pytest.mark.parametrize("thr", [0.3, 0.6, 1.0])
def test_exit_rule_matches_reference(arch, model, thr):
    cfg, w, params = model
    cfg = cfg.replace(loop=LoopConfig(T, thr))
    hf = dict(HF, early_exit_threshold=thr)
    n = 48
    toks = _tokens(n, seed=21)
    got, aux = _forward(cfg, params, toks, return_aux=True)
    _, lams, step = arch.reference_passes(hf, w, jnp.asarray(toks))
    ref = arch.reference_logits(hf, w, jnp.asarray(toks), jnp.arange(n))
    assert np.array_equal(np.asarray(aux["loop_exit_step"]), np.asarray(step))
    assert _gap(got, ref) < TOL
    if thr == 1.0:
        assert np.all(np.asarray(step) == T - 1)
    else:
        # the rule decides something: tokens leave at more than one step
        assert len(set(np.asarray(step).tolist())) > 1
        # and the cached path chooses alike
        cache = init_cache_for(cfg, 1, 64)
        with jax.default_matmul_precision("highest"):
            lg, _ = transformer.forward_with_cache(
                cfg, params, jnp.asarray(toks)[None], cache, fresh_cache=True)
        assert _gap(lg[0], ref) < TOL


def test_exit_steps_by_hand(arch):
    lams = [jnp.asarray([0.5, 0.1, 0.9]), jnp.asarray([0.5, 0.1, 0.9])]
    lams.append(jnp.asarray([0.0, 0.0, 0.0]))   # the last takes what is left
    # p: [.5, .25, .25], [.1, .09, .81], [.9, .09, .01]
    assert arch.exit_steps(lams, 0.6).tolist() == [1, 2, 0]
    assert arch.exit_steps(lams, 0.05).tolist() == [0, 0, 0]
    assert arch.exit_steps(lams, 1.0).tolist() == [2, 2, 2]


# ---- (e) pass t reads pass t's rows ----------------------------------------

@pytest.mark.parametrize("t,l", [(0, 1), (1, 0), (2, 2)])
def test_a_pass_reads_its_own_rows(model, cached, t, l):
    """Overwrite cached layer t * L + l, the rows that pass t of layer l
    wrote for the prompt, and decode one token: that layer's attention in
    pass t reads them and no other does. The new token's own rows show it:
    every cached layer up to and including t * L + l gets the rows it got
    from the clean cache, bit for bit (layer l's k and v are projected
    before its attention), and every later one differs."""
    cfg, _, params = model
    toks, _, prefill, decode = cached
    n = 12
    padded = np.zeros((1, 32), np.int32)
    padded[0, :n] = toks[:n]
    _, cache = prefill(params, jnp.asarray(padded), init_cache_for(cfg, 1, 64),
                       jnp.asarray([n], jnp.int32))
    _, clean = decode(params, jnp.asarray(toks[n:n + 1])[None], cache)
    at = t * L + l
    noise = jax.random.normal(jax.random.PRNGKey(at), cache.k[at, :, :, :n].shape)
    _, dirty = decode(
        params, jnp.asarray(toks[n:n + 1])[None],
        cache.replace(k=cache.k.at[at, :, :, :n].set(noise)))
    same = [bool(jnp.array_equal(clean.k[i, 0, :, n], dirty.k[i, 0, :, n])
                 and jnp.array_equal(clean.v[i, 0, :, n], dirty.v[i, 0, :, n]))
            for i in range(T * L)]
    assert same == [i <= at for i in range(T * L)]


# ---- (f) the tolerance can see the mechanism --------------------------------

def test_the_normed_state_enters_the_next_pass(arch, model):
    cfg, w, params = model
    toks = _tokens(33, seed=33)
    got = _forward(cfg, params, toks)
    skipped = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(33),
                                    norm_between=False)
    assert _gap(got, skipped) > 1000 * TOL


def _without_post_norms(cfg, params):
    layers = {k: v for k, v in params["layers"].items()
              if not k.startswith("post_")}
    return cfg.replace(post_norms=False), {**params, "layers": layers}


@pytest.mark.parametrize("departure", [
    "int8-operands", "a-pass-skipped", "post-norms-skipped"])
def test_tolerance_fails_a_run_that_leaves_something_out(arch, model, departure):
    """Each departure the acceptance names moves the logits by at least a
    thousand tolerances: operands rounded below bfloat16 (the benchmark's
    int8 control, 0.20; bfloat16 operands themselves move them by 0.08), a
    pass left out, the sandwich norms left out."""
    from benchmark.harness.check import int8_rows

    cfg, w, params = model
    toks = _tokens(33, seed=33)
    ref = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(33))
    if departure == "int8-operands":
        got = arch.reference_logits(HF, w, jnp.asarray(toks), jnp.arange(33),
                                    quant=int8_rows)
    elif departure == "a-pass-skipped":
        got = _forward(cfg.replace(loop=LoopConfig(T - 1, 1.0)), params, toks)
    else:
        got = _forward(*_without_post_norms(cfg, params), toks)
    assert _gap(got, ref) > 1000 * TOL


# ---- (g) the accounting counts steps x n_layers layers ----------------------

def test_accounting_counts_every_pass(model):
    cfg, _, params = model
    row = T * L * 2 * 4 * 16 * 4          # passes x layers x (k, v) x Hkv x Dh x f32
    for name in ("paged", "dense"):
        assert make_backend(name, cfg, 2, 64).bytes_per_token() == row
    assert make_backend("paged-int8", cfg, 2, 128).bytes_per_token() == (
        T * L * 4 * (2 * 16 + 8))
    plain = make_backend("paged", cfg.replace(loop=None), 2, 64)
    assert plain.bytes_per_token() * T == row
    eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                              temperature=0.0, decode_ticks=1, pool_tokens=128,
                              overlap_decode=False, overlap_prefill=False)
    assert eng.stats["kv_bytes_per_token"] == row
    eng.submit("a", _tokens(11), 8)
    eng.step()
    be = eng.cache_backend
    res = be.residency()
    assert (res["cached_layers"], res["loop_steps"], res["row_bytes"]) == (
        T * L, T, row)
    # The slot holds pages of 8 rows for its prompt and its budget, each
    # page every pass's rows; utilization counts pages of the pool.
    pages = res["slot_blocks"][0]
    assert pages == -(-(11 + 8 + 1) // 8)
    pool_bytes = sum(getattr(eng._cache, f).nbytes for f in kv_field_names())
    page_bytes = pool_bytes // be.n_blocks
    assert page_bytes == 8 * row
    assert be.utilization() * (be.n_blocks - 1) * page_bytes == pytest.approx(
        pages * 8 * row)
    eng.abort_all()
    dense = BatchingEngine(cfg, params, n_slots=2, max_len=64).cache_backend
    assert dense.residency()["cached_layers"] == T * L
    assert dense.residency()["row_bytes"] == row


def _gauge_cfgs():
    from shellac_tpu import get_model_config
    from shellac_tpu.config import DSAConfig, ModelConfig

    tiny = get_model_config("tiny")
    loop = ModelConfig(
        vocab_size=V, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=96, tie_embeddings=False, post_norms=True,
        loop=LoopConfig(3, 1.0))
    return {
        "mistral-shaped": tiny,
        "mla-shaped": get_model_config("tiny-mla"),
        "indexer-shaped": tiny.replace(dsa=DSAConfig(2, 8, 16)),
        "loop-shaped": loop,
    }


@pytest.mark.parametrize("shape", ["mistral-shaped", "mla-shaped",
                                   "indexer-shaped", "loop-shaped"])
def test_the_gauge_is_the_allocated_pool_over_its_tokens(shape):
    """shellac_engine_kv_bytes_per_token (CacheBackend.bytes_per_token) and
    the pool's shape both read cfg.cache_layers: the bytes allocated for
    the pool's fields over the tokens the pool holds IS the gauge."""
    cfg = _gauge_cfgs()[shape].replace(dtype="float32").validate()
    be = make_backend("paged", cfg, 2, 64, block_size=8, pool_tokens=128)
    cache = jax.eval_shape(be.init_cache)
    fields = kv_field_names() + (("idx",) if cfg.dsa is not None else ())
    held = sum(np.prod(getattr(cache, f).shape) * 4 for f in fields)
    assert held == be.bytes_per_token() * be.n_blocks * be.block_size
    assert getattr(cache, "k").shape[0] == cfg.cache_layers


# ---- (h) one pass is the plain model ----------------------------------------

def test_one_step_equals_no_loop_bit_for_bit(model):
    cfg, _, params = model
    one = cfg.replace(loop=LoopConfig(1, 1.0))
    plain = cfg.replace(loop=None)
    pparams = {k: v for k, v in params.items() if k != "loop_gate"}
    toks = _tokens(20, seed=2)
    assert jnp.array_equal(_forward(one, params, toks),
                           _forward(plain, pparams, toks))
    padded = jnp.asarray(toks)[None]
    n = jnp.asarray([20], jnp.int32)
    a, ca = transformer.forward_with_cache(
        one, params, padded, init_cache_for(one, 1, 32), new_tokens_len=n,
        fresh_cache=True)
    b, cb = transformer.forward_with_cache(
        plain, pparams, padded, init_cache_for(plain, 1, 32), new_tokens_len=n,
        fresh_cache=True)
    assert jnp.array_equal(a, b) and jnp.array_equal(ca.k, cb.k)
    a, _ = transformer.forward_with_cache(one, params, padded[:, :1], ca)
    b, _ = transformer.forward_with_cache(plain, pparams, padded[:, :1], cb)
    assert jnp.array_equal(a, b)


# ---- (i) conversion ---------------------------------------------------------

def test_convert_round_trip_of_the_ouro_names(model):
    cfg, w, params = model
    sd = to_state_dict(cfg, params)
    for name in ("input_layernorm", "input_layernorm_2",
                 "post_attention_layernorm", "post_attention_layernorm_2"):
        assert f"model.layers.{L - 1}.{name}.weight" in sd
    assert sd["model.early_exit_gate.weight"].shape == (1, 64)
    assert sd["model.early_exit_gate.bias"].shape == (1,)
    # published gains, not the program's offsets
    np.testing.assert_allclose(
        sd["model.layers.1.input_layernorm_2.weight"],
        np.asarray(w["post_attn_norm"][1]), rtol=1e-6)
    back = params_from_state_dict(sd, cfg, norm_offset=-1.0)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_batch_cli_serves_a_converted_config(model, tmp_path):
    """`cli.py batch` from the config.json that `convert` writes for a
    `model_type: ouro` checkpoint (dataclasses.asdict of the converted
    ModelConfig, the loop a nested object), on the paged backend, with no
    switch of its own: the normal path."""
    import dataclasses
    import json

    from shellac_tpu.cli import main

    cfg = model[0]
    (tmp_path / "config.json").write_text(json.dumps(dataclasses.asdict(cfg)))
    (tmp_path / "in.jsonl").write_text(json.dumps(
        {"prompt": [5, 9, 2, 11], "max_tokens": 6}))
    rc = main(["batch", "--config", str(tmp_path / "config.json"),
               "--input", str(tmp_path / "in.jsonl"),
               "--output", str(tmp_path / "out.jsonl"),
               "--cache-backend", "paged", "--slots", "2", "--max-len", "64"])
    assert rc == 0
    got = json.loads((tmp_path / "out.jsonl").read_text())
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    ref = Engine(cfg, params, temperature=0.0, max_len=64).generate(
        np.asarray([[5, 9, 2, 11]], np.int32), max_new_tokens=6).tokens[0]
    assert got["tokens"] == list(np.asarray(ref))


# ---- (j) what a looped stack cannot carry yet refuses, with its reason -----

def _paged(model, **kw):
    cfg, _, params = model
    return PagedBatchingEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                               **kw)


def _refuse_rolling(model):
    make_backend("rolling", model[0], 2, 64)


def _refuse_rolling_single(model):
    Engine(model[0], model[2], max_len=64, rolling_window=True)


def _refuse_speculative(model):
    cfg, _, params = model
    engine_class("paged", speculative=True)(
        cfg, params, cfg, params, n_slots=2, max_len=64, cache_backend="paged")


def _refuse_speculative_single(model):
    from shellac_tpu.inference.speculative import SpeculativeEngine

    cfg, _, params = model
    SpeculativeEngine(cfg, params, cfg, params)


def _refuse_pp_pipeline(model):
    cfg, _, params = model
    BatchingEngine(cfg, params, n_slots=2, max_len=64, pp_pipeline=True)


def _mesh():
    from shellac_tpu import ParallelConfig, make_mesh

    return make_mesh(ParallelConfig(tp=2), devices=jax.devices()[:2])


def _refuse_mesh(model):
    _paged(model, mesh=_mesh())


def _refuse_mesh_single(model):
    Engine(model[0], model[2], max_len=64, mesh=_mesh())


def _server(model, **kw):
    from shellac_tpu.inference.server import InferenceServer

    cfg, _, params = model
    InferenceServer(cfg, params, engine=_paged(model), autotune=False, **kw)


def _refuse_beam(model):
    _paged(model).beam_search(_tokens(5), num_beams=2, max_new_tokens=2)


def _refuse_beam_single(model):
    Engine(model[0], model[2], max_len=64).beam_search(
        jnp.asarray(_tokens(5)), num_beams=2, max_new_tokens=2)


REFUSALS = [
    ("rolling", _refuse_rolling), ("rolling", _refuse_rolling_single),
    ("speculative", _refuse_speculative),
    ("speculative", _refuse_speculative_single),
    ("pp_pipeline", _refuse_pp_pipeline),
    ("mesh", _refuse_mesh), ("mesh", _refuse_mesh_single),
    ("park_resume", lambda m: _server(m, preempt_after=1.0)),
    ("kv_export", lambda m: _server(m, role="prefill")),
    ("kv_export", lambda m: _server(m, role="decode")),
    ("beam_search", _refuse_beam), ("beam_search", _refuse_beam_single),
]


def test_the_refusal_table_is_covered():
    assert {f for f, _ in REFUSALS} == set(LOOP_UNSUPPORTED)


@pytest.mark.parametrize(
    "feature,build", REFUSALS,
    ids=[f"{f}-{b.__name__.strip('_<>')}-{i}" for i, (f, b) in enumerate(REFUSALS)])
def test_refuses_with_its_reason(model, feature, build):
    with pytest.raises(ValueError) as e:
        build(model)
    assert f"does not support {feature} yet" in str(e.value)
    assert LOOP_UNSUPPORTED[feature] in str(e.value)


def test_refuses_park_dir(model, tmp_path):
    with pytest.raises(ValueError, match="does not support park_resume yet"):
        _server(model, park_dir=str(tmp_path))


@pytest.mark.parametrize("name,extra", [
    ("eva", dict(eva=dict(window=32, chunk=4), post_norms=False)),
    ("moe", dict(moe=MoEConfig(num_experts=4))),
    ("mla / dsa", dict(mla=MLAConfig(), n_kv_heads=None)),
    ("attn_window / attn_pattern", dict(attn_window=16)),
    ("n_pred_heads > 1", dict(n_pred_heads=2)),
    ("causal=False", dict(causal=False)),
])
def test_validate_refuses_what_does_not_combine(model, name, extra):
    reason = {n: why for n, _, why in LOOP_EXCLUDES}[name]
    with pytest.raises(ValueError) as e:
        model[0].replace(**extra).validate()
    assert f"does not combine with {name}" in str(e.value)
    assert reason in str(e.value)
    assert len(LOOP_EXCLUDES) == 6


def test_validate_refuses_bad_loops(model):
    with pytest.raises(ValueError, match="steps=0"):
        model[0].replace(loop=LoopConfig(0, 1.0)).validate()
    with pytest.raises(ValueError, match="exit_threshold"):
        model[0].replace(loop=LoopConfig(2, 0.0)).validate()


def test_forward_refuses_a_pipeline(model):
    from shellac_tpu import ParallelConfig, make_mesh

    cfg, _, params = model
    mesh = make_mesh(ParallelConfig(pp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="pp over a looped stack"):
        transformer.forward(cfg, params, jnp.zeros((2, 8), jnp.int32), mesh=mesh)


# ---- (k) the step records' counts, the counters, the scopes ----------------

def test_step_records_count_passes_and_rows(model):
    cfg, _, params = model
    reg = Registry()
    eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64, block_size=8,
                              temperature=0.0, decode_ticks=3, registry=reg)
    out = eng.run([(rid, _tokens(n, seed=50 + rid), m)
                   for rid, (n, m) in enumerate(REQS)])
    ticks = rows = 0
    for rid, (n, m) in enumerate(REQS):
        assert len(out[rid]) == m
        # the first token comes from prefill; decode ticks sit at
        # positions n .. n + m - 2 and read position + 1 rows a pass
        ticks += m - 1
        rows += sum(p + 1 for p in range(n, n + m - 1))
    recs = list(reg.step_records)
    assert sum(r.counts["decode_valid_ticks"] for r in recs) == ticks
    assert sum(r.counts["loop_passes"] for r in recs) == T * ticks
    assert sum(r.counts["loop_kv_rows"] for r in recs) == T * rows
    text = reg.render()
    assert f"shellac_engine_loop_passes_total {T * ticks}\n" in text
    assert f"shellac_engine_loop_kv_rows_total {T * rows}\n" in text


def test_a_model_without_a_loop_counts_none(model):
    cfg, _, params = model
    reg = Registry()
    plain = cfg.replace(loop=None)
    pparams = {k: v for k, v in params.items() if k != "loop_gate"}
    eng = PagedBatchingEngine(plain, pparams, n_slots=2, max_len=64,
                              block_size=8, temperature=0.0, registry=reg)
    eng.run([("a", _tokens(5), 6)])
    assert sum(r.counts["loop_passes"] for r in reg.step_records) == 0
    assert sum(r.counts["loop_kv_rows"] for r in reg.step_records) == 0


def test_the_compiled_programs_carry_the_loop_scopes(model):
    """`trace-report`'s by-scope section tells the between-pass norm, the
    gate and the exit choice apart from the layers' norms."""
    from shellac_tpu.obs import tracereport

    cfg, _, params = model
    cache = init_cache_for(cfg, 2, 64)
    want = {"loop.norm", "loop.gate", "loop.exit", "norm", "kv.write",
            "attn.qkv", "attn.out", "mlp", "unembed"}
    for toks, fresh in ((jnp.zeros((2, 1), jnp.int32), False),
                        (jnp.zeros((2, 16), jnp.int32), True)):
        text = jax.jit(lambda p, c, t, fresh=fresh: transformer.forward_with_cache(
            cfg, p, t, c, fresh_cache=fresh)).lower(params, cache, toks).as_text(
                debug_info=True)
        found = {tracereport.scope_of({"op_name": n})
                 for n in re.findall(r'loc\("([^"]+)"', text)}
        assert want <= found, sorted(want - found)
        assert found - {None} <= set(tracereport.DEVICE_SCOPES)


def test_one_body_for_the_passes(model):
    """The passes are one scanned body: the lowered decode step holds the
    attention of one layer once, not steps x n_layers times."""
    cfg, _, params = model
    cache = init_cache_for(cfg, 1, 64)
    text = jax.jit(lambda p, c, t: transformer.forward_with_cache(
        cfg, p, t, c)).lower(params, cache, jnp.zeros((1, 1), jnp.int32)).as_text()
    assert text.count("stablehlo.while") >= 2
    assert text.count("stablehlo.logistic") <= 2   # the gate and silu, once each


# ---- the cell's decode path -------------------------------------------------

def test_the_cells_shapes_read_through_the_block_table(monkeypatch):
    """ouro-2.6b-batch-reason: 8 slots, 16 q heads = 16 KV heads x 128,
    128-row pages, a (192 x 41)-block pool in bfloat16. The dispatcher's
    own rule sends that through the paged kernel (one q head a kv head),
    not a gathered view of 192 cached layers a tick."""
    import shellac_tpu.ops.decode_attention as da

    shapes = ((8, 1, 16, 128), (41, 16, 128, 128), jnp.bfloat16)
    assert da.paged_kernel_under_auto(*shapes)
    # The tile's bound allows two pages a grid step at 16 kv heads, and
    # two does not divide a table of five: the one-page kernel.
    pool = jnp.zeros((3, 16, 128, 128), jnp.bfloat16)
    assert da._paged_group(jnp.zeros((8, 5), jnp.int32), pool, pool) == 1
    # Off the TPU "auto" never takes a kernel; where compiled Pallas is
    # live it takes this one.
    monkeypatch.setattr(da, "pallas_supported", lambda: True)
    assert da.paged_decode_path(*shapes, "auto") == "paged_kernel"
