"""MoE routing and model tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu import MoEConfig, ParallelConfig, get_model_config, make_mesh
from shellac_tpu.config import TrainConfig
from shellac_tpu.models import transformer
from shellac_tpu.ops import moe
from shellac_tpu.ops.moe import expert_capacity, moe_ffn, route
from shellac_tpu.training import batch_shardings, init_train_state, make_train_step


class TestRouting:
    def test_slots_unique_and_capped(self):
        cfg = MoEConfig(num_experts=4, num_experts_per_token=2, capacity_factor=1.0)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 16)), jnp.float32)
        w = jnp.asarray(np.random.default_rng(1).normal(size=(16, 4)), jnp.float32)
        slot, weight, aux, metrics = route(x, w, cfg)
        c = expert_capacity(cfg, 32)
        s = np.asarray(slot).reshape(-1)
        valid = s[s < 4 * c]
        # No two assignments share a capacity slot.
        assert len(valid) == len(set(valid.tolist()))
        # Combine weights are normalized over kept experts.
        np.testing.assert_allclose(np.asarray(weight).sum(-1), 1.0, rtol=1e-5)

    def test_capacity_drops_overflow(self):
        # Router forced to send everything to expert 0 -> all but C dropped.
        cfg = MoEConfig(num_experts=4, num_experts_per_token=1, capacity_factor=1.0)
        x = jnp.ones((16, 8), jnp.float32)
        w = jnp.zeros((8, 4), jnp.float32).at[:, 0].set(10.0)
        slot, _, _, metrics = route(x, w, cfg)
        c = expert_capacity(cfg, 16)  # = 4
        kept = int((np.asarray(slot) < 4 * c).sum())
        assert kept == c
        assert float(metrics["moe_dropped_frac"]) == pytest.approx(1 - c / 16)

    def test_uniform_router_balance_loss_is_one(self):
        # With a uniform router, balance loss == num_experts * E[f*p] == 1.
        cfg = MoEConfig(num_experts=8, num_experts_per_token=2)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(64, 16)), jnp.float32)
        w = jnp.zeros((16, 8), jnp.float32)
        _, _, _, metrics = route(x, w, cfg)
        assert float(metrics["moe_balance_loss"]) == pytest.approx(1.0, rel=1e-3)


class TestGroupedDropless:
    def _weights(self, rng, d=16, f=32, e=4):
        r = np.random.default_rng(rng)
        mk = lambda *s: jnp.asarray(  # noqa: E731
            r.normal(size=s, scale=0.3), jnp.float32
        )
        return (mk(d, e), mk(e, d, f), mk(e, d, f), mk(e, f, d))

    def test_matches_bucket_path_when_nothing_drops(self):
        """Parity at capacity_factor -> inf: with capacity covering
        every assignment, the bucket path drops nothing and the
        grouped path must produce the same outputs and the same aux
        (the gate scoring is one shared definition)."""
        from shellac_tpu.ops.moe import moe_ffn_grouped

        cfg = MoEConfig(num_experts=4, num_experts_per_token=2,
                        capacity_factor=64.0)
        wr, wg, wu, wd = self._weights(3)
        x = jnp.asarray(
            np.random.default_rng(0).normal(size=(2, 24, 16)),
            jnp.float32,
        )
        want, aux_w, m_w = moe_ffn(x, wr, wg, wu, wd, cfg)
        got, aux_g, m_g = moe_ffn_grouped(x, wr, wg, wu, wd, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert float(aux_g) == pytest.approx(float(aux_w), rel=1e-6)
        assert float(m_w["moe_dropped_frac"]) == 0.0
        assert float(m_g["moe_dropped_frac"]) == 0.0

    def test_nothing_drops_under_pathological_routing(self):
        """Every token routed to ONE expert — the bucket path at
        capacity_factor=1 drops most assignments; the grouped path
        drops none, by construction."""
        from shellac_tpu.ops.moe import moe_ffn_grouped

        cfg = MoEConfig(num_experts=4, num_experts_per_token=1,
                        capacity_factor=1.0)
        _, wg, wu, wd = self._weights(5)
        wr = jnp.zeros((16, 4), jnp.float32).at[:, 0].set(10.0)
        x = jnp.asarray(
            np.random.default_rng(1).normal(size=(1, 16, 16)),
            jnp.float32,
        )
        _, _, m_bucket = moe_ffn(x, wr, wg, wu, wd, cfg)
        got, _, m_g = moe_ffn_grouped(x, wr, wg, wu, wd, cfg)
        assert float(m_bucket["moe_dropped_frac"]) >= 0.5
        assert float(m_g["moe_dropped_frac"]) == 0.0
        # And the grouped output equals an exact per-token reference.
        ref, _, _ = moe_ffn(x, wr, wg, wu, wd, cfg, drop_tokens=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_training_step_dropped_frac_zero(self, mesh8):
        """A sharded train step on the ep mesh with grouped_dropless:
        moe_dropped_frac == 0 BY CONSTRUCTION, loss finite, gradients
        flow (loss changes over steps)."""
        import dataclasses

        from shellac_tpu.parallel.mesh import factor_devices

        base = get_model_config("tiny-moe")
        cfg = base.replace(
            d_model=128, n_heads=4, vocab_size=512, remat=True,
            moe=dataclasses.replace(base.moe, grouped_dropless=True,
                                    capacity_factor=1.0),
        )
        mesh = make_mesh(factor_devices(8, moe=True))
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=1,
                           total_steps=10)
        key = jax.random.PRNGKey(0)
        state = init_train_state(cfg, tcfg, key, mesh=mesh)
        step = make_train_step(cfg, tcfg, mesh=mesh)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size
        )
        bs = batch_shardings(mesh)
        batch = {"inputs": jax.device_put(tokens, bs),
                 "targets": jax.device_put(tokens, bs)}
        losses = []
        for _ in range(3):
            state, metrics = step(state, batch)
            assert float(metrics["moe_dropped_frac"]) == 0.0
            losses.append(float(metrics["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] != losses[0]


def _mesh_of(kind):
    """None, one device, or eight: tp alone, and the expert axis cut."""
    if kind is None:
        return None
    if kind == "one":
        return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("tp",))
    return make_mesh({"tp": ParallelConfig(tp=8),
                      "ep": ParallelConfig(ep=2, fsdp=2, tp=2)}[kind])


class TestPathRule:
    """ops/moe.py::moe_ffn_path: which form a call of the expert FFN
    takes, from the config's two flags, whether the call continues a
    cache, the mesh, and whether the weights are plain."""

    FLAGS = {
        "neither": {},
        "dropless": {"dropless": True},
        "grouped": {"grouped_dropless": True},
        "both": {"dropless": True, "grouped_dropless": True},
    }

    @pytest.mark.parametrize("mesh_kind", [None, "one", "tp", "ep"])
    @pytest.mark.parametrize("cached", [False, True])
    @pytest.mark.parametrize("flags", list(FLAGS))
    def test_table(self, flags, cached, mesh_kind):
        cfg = MoEConfig(num_experts=8, num_experts_per_token=2,
                        **self.FLAGS[flags])
        got = moe.moe_ffn_path(cfg, cached=cached, mesh=_mesh_of(mesh_kind))
        if cfg.grouped_dropless and not cached:
            want = "sorted"  # the training option, on any mesh
        elif not (cached or cfg.dropless):
            want = "capacity"
        else:  # must not drop: sorted on one device, as before on a mesh
            want = "sorted" if mesh_kind in (None, "one") else "buckets"
        assert got == want

    @pytest.mark.parametrize("cached", [False, True])
    def test_quantized_experts_keep_the_buckets(self, cached):
        cfg = MoEConfig(num_experts=8, num_experts_per_token=2,
                        dropless=True)
        assert moe.moe_ffn_path(cfg, cached=cached, plain=False) == "buckets"

    def test_plain_means_the_compute_dtype(self):
        from shellac_tpu.ops.quant import quantize

        w = {n: jnp.zeros((2, 4, 8, 8), jnp.bfloat16)
             for n in moe.EXPERT_STACKS}
        assert moe.experts_plain(w, jnp.bfloat16)
        assert not moe.experts_plain(w, jnp.float32)
        assert not moe.experts_plain(
            dict(w, w_up=quantize(w["w_up"])), jnp.bfloat16)
        rows = {n: moe.StackRow(v, jnp.int32(1)) for n, v in w.items()}
        assert moe.experts_plain(rows, jnp.bfloat16)

    def test_the_model_asks_the_rule(self, monkeypatch):
        """_block_mlp decides nothing on its own: every MoE layer of a
        cached forward asks moe_ffn_path, with what the rule needs."""
        from shellac_tpu.inference import init_cache

        cfg = get_model_config("tiny-moe").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        asked = []
        rule = moe.moe_ffn_path

        def spy(c, **kw):
            asked.append(kw)
            return rule(c, **kw)

        monkeypatch.setattr(moe, "moe_ffn_path", spy)
        tokens = jnp.zeros((1, 4), jnp.int32)
        cache = init_cache(cfg, 1, 8)
        _, cache = transformer.forward_with_cache(
            cfg, params, tokens, cache, fresh_cache=True)
        transformer.forward_with_cache(cfg, params, tokens[:, :1], cache)
        assert {(k["cached"], k["plain"], k["mesh"]) for k in asked} == {
            (False, True, None), (True, True, None)}


class TestSortedMatchesBuckets:
    """The sorted form against the worst-case buckets it replaces
    (`moe_ffn(drop_tokens=False)`, the form a mesh keeps): the same
    exact top-k computation."""

    E, D, F = 4, 16, 32

    def _weights(self, seed, bias):
        r = np.random.default_rng(seed)
        mk = lambda *s: jnp.asarray(  # noqa: E731
            r.normal(size=s, scale=0.3), jnp.float32
        )
        e, d, f = self.E, self.D, self.F
        w = (mk(d, e), mk(e, d, f), mk(e, d, f), mk(e, f, d))
        b = dict(b_gate=mk(e, f), b_up=mk(e, f), b_down=mk(e, d))
        return w, (b if bias else {})

    @pytest.mark.parametrize("bias", [False, True], ids=["plain", "biased"])
    @pytest.mark.parametrize("shape,one_expert", [
        ((4, 1), False),   # a decode tick: one row a slot
        ((1, 48), False),  # a prompt
        ((1, 16), True),   # every token on one expert
    ], ids=["decode", "prefill", "one_expert"])
    def test_parity(self, shape, one_expert, bias):
        cfg = MoEConfig(num_experts=self.E,
                        num_experts_per_token=1 if one_expert else 2,
                        capacity_factor=1.0, dropless=True,
                        expert_bias=bias)
        (wr, wg, wu, wd), b = self._weights(7, bias)
        if one_expert:
            wr = jnp.zeros((self.D, self.E), jnp.float32).at[:, 0].set(10.0)
        x = jnp.asarray(
            np.random.default_rng(1).normal(size=(*shape, self.D)),
            jnp.float32,
        )
        want, aux_w, m_w = moe_ffn(x, wr, wg, wu, wd, cfg,
                                   drop_tokens=False, **b)
        got, aux_g, m_g = moe.moe_ffn_grouped(x, wr, wg, wu, wd, cfg, **b)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        assert float(aux_g) == pytest.approx(float(aux_w), rel=1e-6)
        assert float(m_w["moe_dropped_frac"]) == 0.0
        assert float(m_g["moe_dropped_frac"]) == 0.0

    @pytest.mark.parametrize("rows", [6, 200])
    def test_kernel_reads_its_layer_in_the_whole_stack(self, rows):
        """The Pallas kernel (interpret mode here) over a StackRow:
        layer `row`'s experts inside the (L, E, in, out) stack, rows
        padded to whole tiles, equal to ragged_dot over the slice."""
        import functools

        import jax.experimental.pallas.ops.tpu.megablox as megablox

        r = np.random.default_rng(3)
        n_layers, e, d, f = 3, self.E, 32, 128
        stack = jnp.asarray(r.normal(size=(n_layers, e, d, f)), jnp.float32)
        flat_e = jnp.asarray(r.integers(0, e, size=rows), jnp.int32)
        lhs = jnp.asarray(r.normal(size=(rows, d)), jnp.float32)
        order = jnp.argsort(flat_e, stable=True)
        lhs = lhs[order]
        sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
        gmm = functools.partial(megablox.gmm, interpret=True)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(megablox, "gmm", gmm)
            for row in range(n_layers):
                got = moe._gmm_in_stack(
                    lhs, moe.StackRow(stack, jnp.int32(row)), sizes)
                want = jax.lax.ragged_dot(lhs, stack[row], sizes)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)

    def test_tiling_fits_the_tile_budget(self):
        for k, n, size in [(2048, 1408, 2), (1408, 2048, 2), (4096, 14336, 2),
                           (64, 96, 4), (2048, 1408, 4)]:
            tm, tk, tn = moe._gmm_tiling(k, n, size)
            assert tm == 128
            assert tk == k or tk % 128 == 0
            assert tn == n or tn % 128 == 0
            assert tk * tn * size <= max(moe._GMM_TILE_BYTES, tk * 128 * size)
        # The cell's widths, as timed (PERF.md, PR 30).
        assert moe._gmm_tiling(2048, 1408, 2) == (128, 2048, 768)
        assert moe._gmm_tiling(1408, 2048, 2) == (128, 1408, 1024)


class TestMoEFFN:
    def test_identity_experts_equal_dense(self):
        """With all experts identical and capacity ample, MoE == dense SwiGLU."""
        rng = np.random.default_rng(0)
        d, f, e = 16, 32, 4
        x = jnp.asarray(rng.normal(size=(2, 8, d)), jnp.float32)
        wg1 = jnp.asarray(rng.normal(size=(d, f)) * 0.1, jnp.float32)
        wu1 = jnp.asarray(rng.normal(size=(d, f)) * 0.1, jnp.float32)
        wd1 = jnp.asarray(rng.normal(size=(f, d)) * 0.1, jnp.float32)
        cfg = MoEConfig(num_experts=e, num_experts_per_token=2, capacity_factor=8.0)
        out, aux, _ = moe_ffn(
            x,
            jnp.zeros((d, e), jnp.float32),
            jnp.broadcast_to(wg1, (e, d, f)),
            jnp.broadcast_to(wu1, (e, d, f)),
            jnp.broadcast_to(wd1, (e, f, d)),
            cfg,
        )
        want = (jax.nn.silu(x @ wg1) * (x @ wu1)) @ wd1
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=1e-4, atol=1e-5
        )


class TestMoEModel:
    def _cfg(self):
        return get_model_config("tiny-moe").replace(dtype="float32")

    def test_forward_and_aux(self):
        cfg = self._cfg()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        logits, aux = transformer.forward(cfg, params, tokens, return_aux=True)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert float(aux["aux"]) > 0
        assert float(aux["balance_loss"]) > 0

    def test_training_decreases_loss(self):
        cfg = self._cfg()
        tcfg = TrainConfig(warmup_steps=0, learning_rate=3e-3)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        step = make_train_step(cfg, tcfg)
        batch = {"inputs": tokens, "targets": tokens}
        state, m0 = step(state, batch)
        for _ in range(9):
            state, m = step(state, batch)
        assert float(m["loss"]) < float(m0["loss"]) - 0.5
        assert "moe_aux_loss" in m

    def test_sharded_matches_unsharded(self):
        cfg = self._cfg()
        tcfg = TrainConfig(warmup_steps=0, learning_rate=1e-3)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
        batch = {"inputs": tokens, "targets": tokens}

        state_u = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        step_u = make_train_step(cfg, tcfg)
        state_u, mu = step_u(state_u, batch)

        mesh = make_mesh(ParallelConfig(fsdp=4, tp=2))
        state_s = init_train_state(cfg, tcfg, jax.random.PRNGKey(0), mesh=mesh)
        # Experts shard over (ep, fsdp); with ep=1 that is fsdp sharding.
        assert state_s.params["layers"]["w_gate"].sharding.spec[1] == (
            "ep", "fsdp",
        )
        step_s = make_train_step(cfg, tcfg, mesh=mesh)
        bs = batch_shardings(mesh)
        batch_s = jax.tree.map(lambda x: jax.device_put(x, bs), batch)
        state_s, ms = step_s(state_s, batch_s)
        np.testing.assert_allclose(
            float(mu["loss"]), float(ms["loss"]), rtol=1e-4
        )

    @pytest.mark.parametrize("dropless", [False, True],
                             ids=["capacity", "dropless"])
    def test_cached_decode_matches_full(self, dropless):
        from shellac_tpu.inference import init_cache

        # Capacity must be ample: C scales with dispatch size T, so a
        # token dropped at prefill-T but kept at decode-T (or vice versa)
        # would legitimately change outputs. cf=8 => no drops either way.
        # A dropless model runs the sorted form in all three: the full
        # forward, the prefill and each decode step (capacity 0.5
        # would drop in the buckets).
        cfg = self._cfg().replace(
            moe=MoEConfig(num_experts=4, num_experts_per_token=2,
                          capacity_factor=0.5 if dropless else 8.0,
                          dropless=dropless)
        )
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
        full = transformer.forward(cfg, params, tokens)
        cache = init_cache(cfg, 1, 16)
        _, cache = transformer.forward_with_cache(
            cfg, params, tokens[:, :4], cache, fresh_cache=dropless
        )
        outs = []
        for i in range(4, 8):
            logits, cache = transformer.forward_with_cache(
                cfg, params, tokens[:, i : i + 1], cache
            )
            outs.append(logits[:, 0])
        got = jnp.stack(outs, axis=1)
        # NOTE: routing capacity differs between prefill (T=8) and
        # decode (T=1) only when tokens are dropped; with the default
        # capacity_factor and tiny T, capacity is ample so results match.
        np.testing.assert_allclose(
            np.asarray(full[:, 4:]), np.asarray(got), rtol=1e-4, atol=1e-4
        )

    @pytest.mark.parametrize("preset", ["tiny-deepseek", "tiny-gptoss"])
    def test_cached_forward_through_the_kernel(self, preset, monkeypatch):
        """Where the sorted form runs as the compiled kernel the layer
        walk hands the expert stacks whole; prefill and decode through
        it (interpret mode here, steered in the test) equal the same
        through ragged_dot over the scan's slices: a dense prefix with
        shared experts, and an attention pattern with expert biases."""
        import dataclasses
        import functools

        import jax.experimental.pallas.ops.tpu.megablox as megablox

        from shellac_tpu.inference import init_cache

        cfg = get_model_config(preset).replace(dtype="float32")
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, dropless=True))
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, 7), 0, cfg.vocab_size)

        def run():
            cache = init_cache(cfg, 2, 16)
            first, cache = transformer.forward_with_cache(
                cfg, params, tokens[:, :6], cache, fresh_cache=True)
            last, _ = transformer.forward_with_cache(
                cfg, params, tokens[:, 6:], cache)
            return np.asarray(first), np.asarray(last)

        want = run()
        calls = []
        kernel = moe._gmm_in_stack
        monkeypatch.setattr(moe, "sorted_kernel_runs", lambda mesh=None: True)
        monkeypatch.setattr(
            megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
        monkeypatch.setattr(
            moe, "_gmm_in_stack",
            lambda *a: calls.append(a[1].stack.shape) or kernel(*a))
        got = run()
        n_moe = cfg.n_layers - cfg.first_k_dense
        # Three matmuls a MoE layer body, one body a layer kind traced.
        assert calls and len(calls) % 3 == 0
        assert all(shape[0] == n_moe for shape in calls)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
