"""Expert-parallel (ep) mesh axis on the virtual 8-device CPU mesh.

The ep design is pure GSPMD sharding (docs/parallelism.md): expert
weights and the dispatched capacity buckets shard E over (ep, fsdp);
XLA inserts the token all-to-all at the dispatch/combine resharding
boundaries, and the expert FFN einsums stay local to each ep group.
Parity with the unsharded path is therefore the whole correctness
story — these tests pin it for the plain MoE, the interleaved
dense/MoE stack, and the DeepSeek shape (shared experts + MLA +
first-k-dense).
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from shellac_tpu import ParallelConfig, get_model_config, make_mesh
from shellac_tpu.config import TrainConfig
from shellac_tpu.parallel.sharding import logical_to_spec
from shellac_tpu.training import (
    batch_shardings,
    init_train_state,
    make_train_step,
)


@pytest.fixture(scope="module")
def mesh_ep8():
    # dp=2 x ep=2 x tp=2: tokens shard over dp, experts over ep, expert
    # FFN width over tp — the three-way composition a real MoE run uses.
    return make_mesh(ParallelConfig(dp=2, ep=2, tp=2))


class TestEpRules:
    def test_expert_param_spec(self):
        assert logical_to_spec(("experts", "embed", "mlp")) == P(
            ("ep", "fsdp"), None, "tp"
        )

    def test_stacked_expert_param_spec(self):
        # Layer-stacked expert weights: layers->pp, experts->(ep,fsdp).
        assert logical_to_spec(("layers", "experts", "embed", "mlp")) == P(
            "pp", ("ep", "fsdp"), None, "tp"
        )


def _losses(cfg, tcfg, batch, mesh, steps=3):
    key = jax.random.PRNGKey(0)
    state = init_train_state(cfg, tcfg, key, mesh=mesh)
    step = make_train_step(cfg, tcfg, mesh=mesh)
    if mesh is not None:
        bs = batch_shardings(mesh)
        batch = jax.tree.map(lambda x: jax.device_put(x, bs), batch)
    out = []
    for _ in range(steps):
        state, m = step(state, batch)
        out.append(float(m["loss"]))
    return out


class TestEpTraining:
    def _batch(self, cfg, b=4, s=32):
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size
        )
        return {"inputs": tokens, "targets": tokens}

    def test_ep_step_matches_unsharded(self, mesh_ep8):
        cfg = get_model_config("tiny-moe").replace(dtype="float32")
        tcfg = TrainConfig(warmup_steps=0, total_steps=100,
                           learning_rate=1e-3)
        batch = self._batch(cfg)
        ref = _losses(cfg, tcfg, batch, None)
        ep = _losses(cfg, tcfg, batch, mesh_ep8)
        np.testing.assert_allclose(ref, ep, rtol=1e-4)

    def test_ep_deepseek_shared_experts(self, mesh_ep8):
        # MLA + first-k-dense + shared expert + narrow routed experts:
        # the DeepSeek composition ep has to cover.
        cfg = get_model_config("tiny-deepseek").replace(dtype="float32")
        tcfg = TrainConfig(warmup_steps=0, total_steps=100,
                           learning_rate=1e-3)
        batch = self._batch(cfg)
        ref = _losses(cfg, tcfg, batch, None)
        ep = _losses(cfg, tcfg, batch, mesh_ep8)
        np.testing.assert_allclose(ref, ep, rtol=1e-4)

    def test_ep_interleaved_stack(self, mesh_ep8):
        cfg = get_model_config("tiny-moe-interleaved").replace(
            dtype="float32"
        )
        tcfg = TrainConfig(warmup_steps=0, total_steps=100,
                           learning_rate=1e-3)
        batch = self._batch(cfg)
        ref = _losses(cfg, tcfg, batch, None)
        ep = _losses(cfg, tcfg, batch, mesh_ep8)
        np.testing.assert_allclose(ref, ep, rtol=1e-4)

    def test_ep_fsdp_composition(self):
        # ep=2 x fsdp=2: E shards over both (ZeRO over the ep groups).
        mesh = make_mesh(ParallelConfig(fsdp=2, ep=2, tp=2))
        cfg = get_model_config("tiny-moe").replace(dtype="float32")
        tcfg = TrainConfig(warmup_steps=0, total_steps=100,
                           learning_rate=1e-3)
        batch = self._batch(cfg)
        ref = _losses(cfg, tcfg, batch, None)
        ep = _losses(cfg, tcfg, batch, mesh)
        np.testing.assert_allclose(ref, ep, rtol=1e-4)

    def test_ep_serving_bit_parity(self, mesh_ep8):
        """MoE decode on an ep mesh: greedy bit-identical to the
        unsharded engine (decode runs dropless, so expert sharding must
        not change which experts compute or what they return)."""
        from shellac_tpu.inference.batching import BatchingEngine
        from shellac_tpu.inference.engine import shard_params
        from shellac_tpu.models import transformer

        cfg = get_model_config("tiny-moe").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        reqs = [(i, [3 + i, 9, 2, 31], 6) for i in range(3)]
        want = BatchingEngine(cfg, params, n_slots=2, max_len=64,
                              temperature=0.0).run(reqs)
        sharded = shard_params(cfg, params, mesh_ep8)
        got = BatchingEngine(cfg, sharded, n_slots=2, max_len=64,
                             temperature=0.0, mesh=mesh_ep8).run(reqs)
        assert got == want

    def test_indivisible_experts_raise(self):
        mesh = make_mesh(ParallelConfig(ep=8))
        cfg = get_model_config("tiny-moe")  # 4 experts, 8 ep shards
        tcfg = TrainConfig()
        # Either guard may fire first: jax refuses the param sharding at
        # init ("divisible by 8"), or moe_ffn's explicit check ("divide
        # evenly") on paths that build no sharded params.
        with pytest.raises(ValueError, match="divis|divide"):
            state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0),
                                     mesh=mesh)
            step = make_train_step(cfg, tcfg, mesh=mesh)
            bs = batch_shardings(mesh)
            batch = jax.tree.map(
                lambda x: jax.device_put(x, bs), self._batch(cfg, b=8)
            )
            step(state, batch)
