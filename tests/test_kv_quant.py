"""Int8 KV cache: quantization, engine parity, kernel parity, guards."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu import ParallelConfig, get_model_config, make_mesh
from shellac_tpu.inference.batching import BatchingEngine, PagedBatchingEngine
from shellac_tpu.inference.engine import Engine, shard_params
from shellac_tpu.inference.kvcache import (
    init_cache,
    init_quant_cache,
    quantize_kv,
)
from shellac_tpu.models import transformer


def _tiny():
    return get_model_config("tiny").replace(dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = _tiny()
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


class TestQuantization:
    def test_roundtrip_error_bound(self, rng):
        x = jnp.asarray(rng.normal(size=(2, 5, 4, 64)), jnp.float32)
        q, s = quantize_kv(x)
        assert q.dtype == jnp.int8 and s.shape == (2, 5, 4)
        back = q.astype(jnp.float32) * s[..., None]
        # Symmetric int8: error <= scale/2 per element.
        assert float(jnp.max(jnp.abs(back - x) / s[..., None])) <= 0.5 + 1e-6

    def test_zero_rows_stable(self):
        q, s = quantize_kv(jnp.zeros((1, 2, 3, 8)))
        assert float(jnp.abs(q).max()) == 0
        assert float(s.min()) == 1.0  # no div-by-zero scale


class TestForwardParity:
    def test_cached_forward_tracks_bf16(self, model):
        """Prefill + decode with the int8 cache stays close to exact."""
        cfg, params = model
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size
        )
        nl = jnp.array([12, 12], jnp.int32)

        def run(cache):
            logits, cache = transformer.forward_with_cache(
                cfg, params, toks, cache, fresh_cache=True, new_tokens_len=nl
            )
            cur = jnp.argmax(logits[:, -1], -1)
            outs = [cur]
            for _ in range(6):
                logits, cache = transformer.forward_with_cache(
                    cfg, params, cur[:, None], cache
                )
                cur = jnp.argmax(logits[:, 0], -1)
                outs.append(cur)
            return jnp.stack(outs, 1), logits

        t_ref, l_ref = run(init_cache(cfg, 2, 64))
        t_q, l_q = run(init_quant_cache(cfg, 2, 64))
        np.testing.assert_array_equal(np.asarray(t_q), np.asarray(t_ref))
        assert float(jnp.max(jnp.abs(l_q - l_ref))) < 0.05

    def test_kernel_parity_with_scales(self, rng):
        """Interpret-mode quant kernel == dequantized reference."""
        from shellac_tpu.ops.decode_attention import (
            _decode_ref,
            decode_attention,
        )

        B, L, H, HKV, D = 2, 256, 8, 4, 128
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
        kf = jax.random.normal(ks[1], (B, L, HKV, D), jnp.float32)
        vf = jax.random.normal(ks[2], (B, L, HKV, D), jnp.float32)
        kq, ksc = quantize_kv(kf)
        vq, vsc = quantize_kv(vf)
        # head-major (B, Hkv, L, D) cache + (B, Hkv, L) scales
        ck, cv = kq.transpose(0, 2, 1, 3), vq.transpose(0, 2, 1, 3)
        kscale, vscale = ksc.transpose(0, 2, 1), vsc.transpose(0, 2, 1)
        index = jnp.array([19, L - 1], jnp.int32)
        for window in (None, 40):
            out = decode_attention(
                q, ck, cv, index, window=window, impl="flash",
                interpret=True, k_scale=kscale, v_scale=vscale,
            )
            ref = _decode_ref(
                q, ck, cv, index, window, D ** -0.5,
                k_scale=kscale, v_scale=vscale,
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
            )

    def test_flash_rejects_quant_dh64(self):
        from shellac_tpu.ops.decode_attention import decode_attention

        q = jnp.zeros((1, 1, 4, 64))
        ck = jnp.zeros((1, 4, 128, 64), jnp.int8)
        sc = jnp.ones((1, 4, 128))
        with pytest.raises(ValueError, match="unsupported"):
            decode_attention(
                q, ck, ck, jnp.zeros((1,), jnp.int32), impl="flash",
                k_scale=sc, v_scale=sc,
            )


class TestEngines:
    def test_batching_matches_single_request(self, model):
        """Both engines quantize at the same write points, so greedy
        outputs are bit-identical between them (the serving parity
        invariant, kept under kv_quant)."""
        cfg, params = model
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (3, 7, 5, 9)]
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             kv_quant="int8")
        got = eng.run([(i, p, 8) for i, p in enumerate(prompts)])

        single = Engine(cfg, params, temperature=0.0, max_len=64,
                        kv_quant="int8")
        for i, p in enumerate(prompts):
            res = single.generate(
                jnp.asarray([p], jnp.int32), max_new_tokens=8
            )
            assert got[i] == np.asarray(res.tokens)[0].tolist(), i

    def test_chunked_prefill_parity(self, model):
        cfg, params = model
        rng = np.random.default_rng(6)
        prompts = [rng.integers(1, cfg.vocab_size, size=40).tolist(),
                   rng.integers(1, cfg.vocab_size, size=23).tolist()]
        want = BatchingEngine(
            cfg, params, n_slots=2, max_len=96, kv_quant="int8"
        ).run([(i, p, 6) for i, p in enumerate(prompts)])
        got = BatchingEngine(
            cfg, params, n_slots=2, max_len=96, kv_quant="int8",
            prefill_chunk=16,
        ).run([(i, p, 6) for i, p in enumerate(prompts)])
        assert got == want

    def test_sharded_quant_engine(self, model):
        cfg, params = model
        mesh = make_mesh(ParallelConfig(dp=2, tp=4))
        sharded = shard_params(cfg, params, mesh)
        want = BatchingEngine(
            cfg, params, n_slots=2, max_len=64, kv_quant="int8"
        ).run([(0, [3, 5, 7], 6)])
        got = BatchingEngine(
            cfg, sharded, n_slots=2, max_len=64, kv_quant="int8", mesh=mesh
        ).run([(0, [3, 5, 7], 6)])
        assert got == want

    def test_guards(self, model):
        cfg, params = model
        # Int8 paged pools exist now; the remaining guard is the page
        # alignment (the kernel's scale DMA needs 128-token pages —
        # cache/paged.py), an actionable config error. An unset
        # block_size auto-resolves to the aligned default, so the
        # guard only fires on an EXPLICIT misaligned page size.
        for bad in (16, 32, 64):
            with pytest.raises(ValueError, match="block_size % 128"):
                PagedBatchingEngine(cfg, params, kv_quant="int8",
                                    block_size=bad)
        assert PagedBatchingEngine(
            cfg, params, kv_quant="int8"
        ).block_size == 128
        # spec x int8 is no longer excluded (the verify round reads
        # the same write-then-read int8 bits sequential decode does);
        # composition is pinned in test_spec_batching.py and the
        # cross-backend parity matrix in test_cache_backends.py.
        with pytest.raises(ValueError, match="kv_quant"):
            BatchingEngine(cfg, params, kv_quant="fp4")


class TestTwoStackInt8:
    """Int8 KV over the two-stack layer layouts (DeepSeek's
    first_k_dense and moe_every interleaving) — previously guarded
    out; now the quant scan mirrors the bf16 stack split."""

    @pytest.mark.parametrize("preset", ["tiny-deepseek",
                                        "tiny-moe-interleaved"])
    def test_batching_matches_single_request(self, preset):
        cfg = get_model_config(preset).replace(dtype="float32")
        if cfg.moe is not None and not cfg.moe.dropless:
            # Parity asserts need dropless MoE: routed capacity depends
            # on the padded token count, which differs between the
            # batching engine's buckets and the single-request pad.
            import dataclasses

            cfg = cfg.replace(
                moe=dataclasses.replace(cfg.moe, dropless=True)
            )
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (3, 9, 5)]
        got = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             kv_quant="int8").run(
            [(i, p, 6) for i, p in enumerate(prompts)]
        )
        single = Engine(cfg, params, temperature=0.0, max_len=64,
                        kv_quant="int8")
        for i, p in enumerate(prompts):
            res = single.generate(jnp.asarray([p], jnp.int32),
                                  max_new_tokens=6)
            assert got[i] == np.asarray(res.tokens)[0].tolist(), (preset, i)

    def test_deepseek_tracks_bf16(self):
        """Int8 rounding stays small on the DeepSeek latent + two-stack
        path: greedy tokens match bf16 on a short horizon."""
        cfg = get_model_config("tiny-deepseek").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jnp.asarray([[7, 23, 5, 11]], jnp.int32)
        exact = Engine(cfg, params, temperature=0.0,
                       max_len=64).generate(prompt, max_new_tokens=6)
        quant = Engine(cfg, params, temperature=0.0, max_len=64,
                       kv_quant="int8").generate(prompt, max_new_tokens=6)
        assert (np.asarray(exact.tokens) == np.asarray(quant.tokens)).all()


class TestPagedInt8:
    def test_paged_matches_single_request(self, model):
        """The serving parity invariant under the int8 pool: greedy
        outputs bit-identical to the single-request engine with the
        SAME cache quantization (both quantize at write, both
        dequantize the read path)."""
        cfg, params = model
        rng = np.random.default_rng(7)
        # Prompts end inside the first, second and third 128-token
        # page, so decode crosses page boundaries.
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (3, 125, 5, 253)]
        eng = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=384, block_size=128,
            kv_quant="int8",
        )
        got = eng.run([(i, p, 8) for i, p in enumerate(prompts)])
        single = Engine(cfg, params, temperature=0.0, max_len=384,
                        kv_quant="int8")
        for i, p in enumerate(prompts):
            res = single.generate(
                jnp.asarray([p], jnp.int32), max_new_tokens=8
            )
            assert got[i] == np.asarray(res.tokens)[0].tolist(), i

    def test_prefix_cache_composes(self, model):
        """Prefix-cached int8 pool: bit-identical outputs with real
        block reuse (scales ride with their blocks)."""
        cfg, params = model
        rng = np.random.default_rng(8)
        shared = rng.integers(1, cfg.vocab_size, size=256).tolist()
        reqs = [(i, shared + rng.integers(1, cfg.vocab_size, size=5).tolist(), 6)
                for i in range(4)]
        plain = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=384, block_size=128,
            kv_quant="int8",
        ).run(reqs)
        cached_eng = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=384, block_size=128,
            kv_quant="int8", prefix_cache=True,
        )
        cached = cached_eng.run(reqs)
        assert cached == plain
        assert cached_eng.stats["prefix_hit_tokens"] > 0

    def test_grouped_kernel_parity_interpret(self, rng):
        """Interpret-mode int8 grouped-gather kernel == gathered
        dequantized reference."""
        from shellac_tpu.inference.kvcache import (
            paged_gather_layer,
            paged_gather_scales,
        )
        from shellac_tpu.ops.decode_attention import (
            _decode_ref,
            paged_decode_attention,
        )

        B, H, HKV, D, bs, mb = 2, 8, 4, 128, 128, 8
        n_blocks = B * mb + 1
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
        kf = jax.random.normal(ks[1], (n_blocks, bs, HKV, D), jnp.float32)
        vf = jax.random.normal(ks[2], (n_blocks, bs, HKV, D), jnp.float32)
        kq, ksc = quantize_kv(kf)
        vq, vsc = quantize_kv(vf)
        pool_k = kq.transpose(0, 2, 1, 3)  # (nb, HKV, bs, D) int8
        pool_v = vq.transpose(0, 2, 1, 3)
        pks = ksc.transpose(0, 2, 1)  # (nb, HKV, bs)
        pvs = vsc.transpose(0, 2, 1)
        perm = np.random.default_rng(0).permutation(n_blocks - 1) + 1
        tables = jnp.asarray(perm.reshape(B, mb), jnp.int32)
        index = jnp.array([173, mb * bs - 1], jnp.int32)
        for window in (None, 200):
            out = paged_decode_attention(
                q, pool_k, pool_v, tables, index, window=window,
                impl="flash", interpret=True, k_scale=pks, v_scale=pvs,
            )
            k_all, v_all = paged_gather_layer(pool_k, pool_v, tables)
            ref = _decode_ref(
                q, k_all, v_all, index, window, D ** -0.5,
                k_scale=paged_gather_scales(pks, tables),
                v_scale=paged_gather_scales(pvs, tables),
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
            )

    def test_chunked_prefill_parity(self, model):
        cfg, params = model
        rng = np.random.default_rng(10)
        prompts = [rng.integers(1, cfg.vocab_size, size=140).tolist(),
                   rng.integers(1, cfg.vocab_size, size=23).tolist()]
        want = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=256, block_size=128,
            kv_quant="int8",
        ).run([(i, p, 6) for i, p in enumerate(prompts)])
        got = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=256, block_size=128,
            kv_quant="int8", prefill_chunk=16,
        ).run([(i, p, 6) for i, p in enumerate(prompts)])
        assert got == want
