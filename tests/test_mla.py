"""Multi-head latent attention (DeepSeek-style): training, serving,
sharding. Exact numerics vs HF are covered in test_hf_convert.py; here
the native stack is exercised end to end."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu import ParallelConfig, get_model_config, make_mesh
from shellac_tpu.config import TrainConfig
from shellac_tpu.inference.batching import (
    BatchingEngine,
    PagedBatchingEngine,
)
from shellac_tpu.inference.engine import Engine, shard_params
from shellac_tpu.models import transformer


def _cfg():
    return get_model_config("tiny-mla").replace(dtype="float32")


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


class TestTraining:
    def test_loss_decreases(self):
        from shellac_tpu.training import init_train_state, make_train_step

        cfg = _cfg()
        tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=5,
                           total_steps=100)
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        step = make_train_step(cfg, tcfg)
        toks = jnp.asarray(
            np.tile(np.array([5, 9, 13, 2]), 16)[None].repeat(4, 0),
            jnp.int32,
        )
        batch = {"inputs": toks, "targets": toks}
        first = last = None
        for _ in range(60):
            state, m = step(state, batch)
            if first is None:
                first = float(m["loss"])
            last = float(m["loss"])
        assert last < 0.1 * first, (first, last)

    def test_ring_attention_parity(self, mesh8, model):
        """MLA long-context training: the expanded attention dispatches
        through ring attention on sp meshes and matches unsharded."""
        cfg, params = model
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0,
                                  cfg.vocab_size)
        want = transformer.forward(cfg, params, toks)
        sharded = shard_params(cfg, params, mesh8)
        got = jax.jit(
            lambda p, t: transformer.forward(
                cfg, p, t, mesh=mesh8, attn_impl="ring"
            )
        )(sharded, toks)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4
        )

    def test_packed_segments(self, model):
        """Packed pretraining rows: each document computes as if alone
        (block-diagonal attention + per-segment rope restart)."""
        cfg, params = model
        a = jax.random.randint(jax.random.PRNGKey(5), (1, 12), 1,
                               cfg.vocab_size)
        bq = jax.random.randint(jax.random.PRNGKey(6), (1, 20), 1,
                                cfg.vocab_size)
        packed = jnp.concatenate([a, bq], axis=1)
        seg = jnp.concatenate(
            [jnp.zeros((1, 12), jnp.int32), jnp.ones((1, 20), jnp.int32)],
            axis=1,
        )
        out = transformer.forward(cfg, params, packed, segment_ids=seg)
        ref_a = transformer.forward(cfg, params, a)
        ref_b = transformer.forward(cfg, params, bq)
        np.testing.assert_allclose(
            np.asarray(out[:, :12]), np.asarray(ref_a), atol=2e-5,
            rtol=2e-5,
        )
        np.testing.assert_allclose(
            np.asarray(out[:, 12:]), np.asarray(ref_b), atol=2e-5,
            rtol=2e-5,
        )

    def test_trains_on_fsdp_mesh(self, mesh_fsdp8, model):
        from shellac_tpu.training import (
            batch_shardings,
            init_train_state,
            make_train_step,
        )

        cfg = _cfg()
        tcfg = TrainConfig(warmup_steps=1, total_steps=4)
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                 mesh=mesh_fsdp8)
        step = make_train_step(cfg, tcfg, mesh=mesh_fsdp8)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                  cfg.vocab_size)
        bs = batch_shardings(mesh_fsdp8)
        batch = {"inputs": jax.device_put(toks, bs),
                 "targets": jax.device_put(toks, bs)}
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))


class TestServing:
    def test_batching_bit_matches_engine(self, model):
        """The serving invariant holds under MLA: continuous batching
        through the latent cache == single-request engine."""
        cfg, params = model
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (3, 7, 5, 9)]
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64)
        got = eng.run([(i, p, 8) for i, p in enumerate(prompts)])
        single = Engine(cfg, params, temperature=0.0, max_len=64)
        for i, p in enumerate(prompts):
            res = single.generate(jnp.asarray([p], jnp.int32),
                                  max_new_tokens=8)
            assert got[i] == np.asarray(res.tokens)[0].tolist(), i

    def test_latent_cache_shape(self, model):
        """The decode cache really is the latent: one row per token,
        kv_lora_rank + qk_rope_head_dim wide, zero-width v."""
        from shellac_tpu.inference.kvcache import init_cache

        cfg, _ = model
        cache = init_cache(cfg, 2, 32)
        assert cache.k.shape == (cfg.n_layers, 2, 1, 32, 40)  # 32 + 8
        assert cache.v.shape == (cfg.n_layers, 2, 1, 32, 0)

    def test_chunked_prefill_parity(self, model):
        cfg, params = model
        rng = np.random.default_rng(12)
        prompts = [rng.integers(1, cfg.vocab_size, size=40).tolist()]
        want = BatchingEngine(cfg, params, n_slots=1, max_len=96).run(
            [(0, prompts[0], 6)]
        )
        got = BatchingEngine(cfg, params, n_slots=1, max_len=96,
                             prefill_chunk=16).run([(0, prompts[0], 6)])
        assert got == want

    def test_sharded_tp_bit_matches(self, model):
        cfg, params = model
        mesh = make_mesh(ParallelConfig(dp=2, tp=4))
        want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
            [(0, [3, 5, 7], 6), (1, [2, 9], 6)]
        )
        sharded = shard_params(cfg, params, mesh)
        got = BatchingEngine(cfg, sharded, n_slots=2, max_len=64,
                             mesh=mesh).run(
            [(0, [3, 5, 7], 6), (1, [2, 9], 6)]
        )
        assert got == want

    def test_speculative_bit_matches(self, model):
        """Speculative batching over MLA latent caches (self-draft):
        rollback-by-lengths works on the latent rows too."""
        from shellac_tpu.inference.spec_batching import (
            SpeculativeBatchingEngine,
        )

        cfg, params = model
        rng = np.random.default_rng(17)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (3, 6, 4)]
        want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
            [(i, p, 8) for i, p in enumerate(prompts)]
        )
        eng = SpeculativeBatchingEngine(cfg, params, cfg, params, gamma=3,
                                        n_slots=2, max_len=64)
        got = eng.run([(i, p, 8) for i, p in enumerate(prompts)])
        assert got == want
        assert eng.stats["spec_accepted"] == eng.stats["spec_proposed"]

    def test_deepseek_layout_trains_and_serves(self):
        """tiny-deepseek (MLA + first-k-dense + MoE + shared expert):
        the native stack trains on a mesh and the serving parity
        invariant holds through the latent cache."""
        from shellac_tpu import ParallelConfig, make_mesh
        from shellac_tpu.training import (
            batch_shardings,
            init_train_state,
            make_train_step,
        )

        cfg = get_model_config("tiny-deepseek").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))

        # Training on an fsdp mesh (experts shard over fsdp).
        mesh = make_mesh(ParallelConfig(fsdp=4, tp=2))
        tcfg = TrainConfig(warmup_steps=1, total_steps=4)
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                 mesh=mesh)
        step = make_train_step(cfg, tcfg, mesh=mesh)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                  cfg.vocab_size)
        bs = batch_shardings(mesh)
        batch = {"inputs": jax.device_put(toks, bs),
                 "targets": jax.device_put(toks, bs)}
        state, met = step(state, batch)
        assert np.isfinite(float(met["loss"]))

        # Serving: batching == single-request, greedy.
        rng = np.random.default_rng(13)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (3, 6)]
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64)
        got = eng.run([(i, p, 6) for i, p in enumerate(prompts)])
        single = Engine(cfg, params, temperature=0.0, max_len=64)
        for i, p in enumerate(prompts):
            res = single.generate(jnp.asarray([p], jnp.int32),
                                  max_new_tokens=6)
            assert got[i] == np.asarray(res.tokens)[0].tolist(), i

    def test_paged_bit_matches(self, model):
        """Paged serving over latent-row pools == the dense engine,
        greedy, with prefix caching reusing latent blocks."""
        cfg, params = model
        rng = np.random.default_rng(19)
        common = rng.integers(1, cfg.vocab_size, size=16).tolist()
        prompts = [common + rng.integers(1, cfg.vocab_size, size=4).tolist()
                   for _ in range(4)]
        want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
            [(i, p, 6) for i, p in enumerate(prompts)]
        )
        eng = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=64, block_size=16,
            prefix_cache=True,
        )
        got = eng.run([(i, p, 6) for i, p in enumerate(prompts)])
        assert got == want
        assert eng.stats["prefix_hit_tokens"] > 0

    def test_int8_latent_cache(self, model):
        """kv_quant='int8' quantizes the latent rows (one scale per
        row); batching stays bit-identical to the single-request
        engine, and greedy typically matches the bf16 cache."""
        cfg, params = model
        rng = np.random.default_rng(23)
        prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
                   for n in (3, 7, 5)]
        eng = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                             kv_quant="int8")
        got = eng.run([(i, p, 8) for i, p in enumerate(prompts)])
        single = Engine(cfg, params, temperature=0.0, max_len=64,
                        kv_quant="int8")
        for i, p in enumerate(prompts):
            res = single.generate(jnp.asarray([p], jnp.int32),
                                  max_new_tokens=8)
            assert got[i] == np.asarray(res.tokens)[0].tolist(), i
        from shellac_tpu.inference.kvcache import init_cache_for

        cache = init_cache_for(cfg, 2, 32, "int8")
        assert cache.k.dtype == jnp.int8
        assert cache.k.shape == (cfg.n_layers, 2, 1, 32, 40)
        assert cache.v.shape == (cfg.n_layers, 2, 1, 32, 0)


def _wide_cfg():
    """tiny-mla with a latent row wider than one lane tile (136 + 8 =
    144 lanes): the paged pool holds it at 256 (kvcache.held_width),
    as DeepSeek-V2-Lite's 576 at 640."""
    from shellac_tpu.config import MLAConfig

    return _cfg().replace(mla=MLAConfig(
        kv_lora_rank=136, q_lora_rank=24, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)).validate()


@pytest.fixture(scope="class")
def wide(request):
    """One dense-slot run and ONE paged engine shape for the class:
    (cfg, params, prompts, the dense engine's greedy tokens, a builder
    of the paged engine)."""
    cfg = _wide_cfg()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(29)
    common = rng.integers(1, cfg.vocab_size, size=16).tolist()
    prompts = [common + rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (4, 9, 2, 6)]
    want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
        [(i, p, 6) for i, p in enumerate(prompts)])

    def paged(**kw):
        return PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                   block_size=16, **kw)

    return cfg, params, prompts, want, paged


class TestHeldWidth:
    """The bf16/fp32 paged pool holds an MLA latent row at whole lane
    tiles, its pad lanes zeros (PagedKVCache's docstring)."""

    def test_padded_pool_matches_dense_slots(self, wide):
        """Greedy tokens through the padded pool (whole-prompt prefill,
        decode through the table, churn over two slots) equal the
        dense-slot engine's; the pad lanes of every page are still
        zeros afterwards, the scratch page's too."""
        cfg, _, prompts, want, paged = wide
        eng = paged()
        assert cfg.cache_head_dim == 144
        assert eng._cache.k.shape[2:] == (1, 16, 256)
        assert eng._cache.v.shape[-1] == 0
        got = eng.run([(i, p, 6) for i, p in enumerate(prompts)])
        assert got == want
        assert not np.asarray(eng._cache.k[..., 144:]).any()
        assert np.asarray(eng._cache.k[..., :144]).any()

    def test_prefix_cache_and_chunks_over_the_padded_pool(self, wide):
        """Shared prefix pages and a prompt prefilled in chunks (rows
        of s > 1 read through the table against the held row)."""
        _, _, prompts, want, paged = wide
        eng = paged(prefix_cache=True, prefill_chunk=8)
        got = eng.run([(i, p, 6) for i, p in enumerate(prompts)])
        assert got == want
        assert eng.stats["prefix_hit_tokens"] > 0
        assert not np.asarray(eng._cache.k[..., 144:]).any()

    def test_bytes_per_token_is_what_the_pool_holds(self, wide):
        cfg, params, _, _, paged = wide
        eng = paged()
        pool = eng._cache.k
        rows = pool.shape[1] * pool.shape[3]  # pages x rows a page
        held = (pool.size + eng._cache.v.size) * pool.dtype.itemsize
        assert eng.cache_backend.bytes_per_token() * rows == held
        assert eng.stats["kv_bytes_per_token"] == cfg.n_layers * 256 * 4
        # The int8 pool keeps the row's own width (and its gather).
        q8 = PagedBatchingEngine(cfg, params, n_slots=2, max_len=128,
                                 block_size=128, kv_quant="int8")
        assert q8._cache.k.shape[-1] == 144
        assert q8.cache_backend.bytes_per_token() == cfg.n_layers * (144 + 8)
        # Dense slots hold the row as the model writes it.
        dense = BatchingEngine(cfg, params, n_slots=2, max_len=64)
        assert dense._cache.k.shape[-1] == 144
        assert dense.cache_backend.bytes_per_token() \
            == cfg.n_layers * 144 * 4

    def test_latent_slot_export_import_round_trip(self, wide):
        """disagg ships a paged slot's pages as they are held: the
        blob's rows are 256 wide, pad lanes included, and a fresh
        engine that imports them continues token for token."""
        from shellac_tpu.inference import disagg

        _, _, prompts, want, paged = wide
        a = paged()
        for i, p in enumerate(prompts[:2]):
            a.submit(i, np.asarray(p, np.int32), 6, prefill_only=True)
        while len(a.frozen_prefills) < 2:
            a.step()
        blobs = {}
        for rid, slot in list(a.frozen_prefills.items()):
            blob = disagg.export_slot(a, slot, a._slots[slot])
            assert blob.arrays["k"].shape[-1] == 256
            assert not blob.arrays["k"][..., 144:].any()
            assert blob.header["model"]["head_dim"] == 144
            blobs[rid] = disagg.MigrationBlob.deserialize(blob.serialize())
            a.release_frozen(rid)
        b = paged()
        for rid, blob in blobs.items():
            disagg.import_blob(b, blob, rid=rid)
        got = {}
        while b.pending:
            got.update(b.step())
        assert got == {i: want[i] for i in (0, 1)}


class TestLoRA:
    def test_mla_lora_trains_and_merges(self, model):
        """LoRA on MLA: the generic default resolves to the latent
        projections (wkv_b_* folded as their real matrices), adapters
        start as the identity, and a short run moves the loss."""
        from shellac_tpu.training.lora import (
            LoRAConfig,
            init_lora,
            init_lora_state,
            make_lora_train_step,
            merge_lora,
        )

        cfg, params = model
        lcfg = LoRAConfig(rank=4).validate(cfg)
        assert "wkv_b_k" in lcfg.targets and "wq_a" in lcfg.targets
        # q_lora_rank=None models resolve to the plain wq instead.
        cfg_noq = cfg.replace(
            mla=cfg.mla.__class__(**{
                **cfg.mla.__dict__, "q_lora_rank": None,
            })
        ).validate()
        lcfg_noq = LoRAConfig(rank=4).validate(cfg_noq)
        assert "wq" in lcfg_noq.targets
        assert "wq_a" not in lcfg_noq.targets
        import pytest as _pt
        with _pt.raises(ValueError, match="unknown LoRA targets"):
            LoRAConfig(rank=4, targets=("wq_a",)).validate(cfg_noq)

        lora = init_lora(cfg, lcfg, jax.random.PRNGKey(1))
        assert lora["layers"]["wkv_b_k"]["a"].shape == (2, 32, 4)
        assert lora["layers"]["wkv_b_k"]["b"].shape == (2, 4, 4, 16)
        # B = 0 -> merge is the identity.
        merged = merge_lora(params, lora, lcfg)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                  cfg.vocab_size)
        np.testing.assert_allclose(
            np.asarray(transformer.forward(cfg, merged, toks)),
            np.asarray(transformer.forward(cfg, params, toks)),
            atol=1e-6,
        )

        tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2,
                           total_steps=30)
        state = init_lora_state(cfg, tcfg, lcfg, jax.random.PRNGKey(3))
        step = make_lora_train_step(cfg, tcfg, lcfg)
        batch = {"inputs": toks, "targets": toks}
        state, m0 = step(state, params, batch)
        for _ in range(15):
            state, m = step(state, params, batch)
        assert float(m["loss"]) < float(m0["loss"])

    def test_first_k_dense_lora(self):
        """LoRA over the two-stack first-k layout: per-stack adapters
        (dense MLP in the prefix, experts in the MoE suffix), identity
        at B=0, and a step that moves the loss."""
        from shellac_tpu.training.lora import (
            LoRAConfig,
            init_lora,
            init_lora_state,
            make_lora_train_step,
            merge_lora,
        )

        cfg = get_model_config("tiny-deepseek").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        lcfg = LoRAConfig(
            rank=4, targets=("wkv_a", "wo", "w_gate", "w_up", "w_down"),
        ).validate(cfg)
        lora = init_lora(cfg, lcfg, jax.random.PRNGKey(1))
        # Dense prefix: plain MLP adapters; MoE suffix: per-expert.
        assert lora["layers"]["dense"]["w_gate"]["a"].shape[:2] == (1, 64)
        assert lora["layers"]["moe"]["w_gate"]["a"].shape[:2] == (2, 4)
        toks = jax.random.randint(jax.random.PRNGKey(2), (2, 16), 0,
                                  cfg.vocab_size)
        merged = merge_lora(params, lora, lcfg)
        np.testing.assert_allclose(
            np.asarray(transformer.forward(cfg, merged, toks)),
            np.asarray(transformer.forward(cfg, params, toks)),
            atol=1e-6,
        )
        tcfg = TrainConfig(learning_rate=3e-3, warmup_steps=2,
                           total_steps=20)
        state = init_lora_state(cfg, tcfg, lcfg, jax.random.PRNGKey(3))
        step = make_lora_train_step(cfg, tcfg, lcfg)
        batch = {"inputs": toks, "targets": toks}
        state, m0 = step(state, params, batch)
        for _ in range(10):
            state, m = step(state, params, batch)
        assert float(m["loss"]) < float(m0["loss"])
