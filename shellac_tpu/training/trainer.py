"""Training step construction.

`make_train_step` returns a jitted step with donated state; under a mesh
the state/batch shardings are attached so XLA partitions the whole step
(forward, backward, optimizer) and inserts collectives over ICI.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding

from shellac_tpu.config import ModelConfig, TrainConfig
from shellac_tpu.models import transformer
from shellac_tpu.training.losses import cross_entropy
from shellac_tpu.training.optimizer import make_optimizer
from shellac_tpu.training.train_state import TrainState, state_shardings
from shellac_tpu.parallel.sharding import DEFAULT_RULES, logical_to_spec


def batch_shardings(mesh: Mesh, rules=DEFAULT_RULES):
    """Sharding for {"inputs","targets","mask"}: batch over dp/fsdp, seq over sp."""
    spec = logical_to_spec(("batch", "seq"), rules)
    return NamedSharding(mesh, spec)


def init_train_state(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    key: jax.Array,
    mesh: Optional[Mesh] = None,
) -> TrainState:
    optimizer = make_optimizer(train_cfg)

    def init_fn(key):
        params = transformer.init_params(model_cfg, key)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=optimizer.init(params),
            ema_params=(
                jax.tree.map(lambda p: p, params)
                if train_cfg.ema_decay is not None else None
            ),
        )

    if mesh is None:
        return jax.jit(init_fn)(key)
    abstract = jax.eval_shape(init_fn, key)
    shardings = state_shardings(mesh, abstract, transformer.logical_axes(model_cfg))
    return jax.jit(init_fn, out_shardings=shardings)(key)


def make_train_step(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    mesh: Optional[Mesh] = None,
    attn_impl: str = "auto",
    jit: bool = True,
    pipeline_microbatches: Optional[int] = None,
):
    """Build `train_step(state, batch) -> (state, metrics)`.

    batch: {"inputs": (B,S) i32, "targets": (B,S) i32, "mask": (B,S) f32?}.
    With grad_accum > 1 the leading batch dim is split into microbatches
    scanned sequentially, accumulating grads in fp32.
    """
    if train_cfg.quant is not None:
        # Opt into quantized compute for this train step only; the
        # model config itself (and any checkpoint metadata derived from
        # it) stays unquantized.
        model_cfg = model_cfg.replace(quant_training=train_cfg.quant).validate()
    if model_cfg.n_pred_heads > 1:
        raise NotImplementedError(
            f"n_pred_heads={model_cfg.n_pred_heads}: forward() returns "
            "every prediction head's logits and there is no loss over "
            "the heads yet (ROADMAP, Queue 2)"
        )
    optimizer = make_optimizer(train_cfg)
    accum = train_cfg.grad_accum

    fused_chunk = train_cfg.fused_loss_chunk
    if fused_chunk is not None and (
        model_cfg.logit_softcap is not None
        or model_cfg.vocab_size % fused_chunk
    ):
        # Softcap changes the logit function itself; indivisible vocabs
        # have no even chunking. Both fall back to the unfused path.
        fused_chunk = None

    def loss_fn(params, batch):
        if fused_chunk is not None:
            from shellac_tpu.training.losses import fused_cross_entropy

            hidden, aux = transformer.forward(
                model_cfg, params, batch["inputs"], mesh=mesh,
                attn_impl=attn_impl, segment_ids=batch.get("segment_ids"),
                pipeline_microbatches=pipeline_microbatches,
                return_aux=True, return_hidden=True,
            )
            w_out = transformer.output_weights(
                model_cfg, params, model_cfg.compute_dtype
            )
            loss, metrics = fused_cross_entropy(
                hidden, w_out, batch["targets"], batch.get("mask"),
                train_cfg.z_loss_weight, vocab_chunk=fused_chunk,
            )
            if model_cfg.moe is not None:
                metrics["moe_aux_loss"] = aux["aux"]
                metrics["moe_balance_loss"] = aux["balance_loss"]
                metrics["moe_router_z_loss"] = aux["router_z_loss"]
                metrics["moe_dropped_frac"] = aux["dropped_frac"]
                loss = loss + aux["aux"]
            return loss, metrics
        logits, aux = transformer.forward(
            model_cfg, params, batch["inputs"], mesh=mesh, attn_impl=attn_impl,
            segment_ids=batch.get("segment_ids"),
            pipeline_microbatches=pipeline_microbatches, return_aux=True,
        )
        loss, metrics = cross_entropy(
            logits, batch["targets"], batch.get("mask"), train_cfg.z_loss_weight
        )
        if model_cfg.moe is not None:
            metrics["moe_aux_loss"] = aux["aux"]
            metrics["moe_balance_loss"] = aux["balance_loss"]
            metrics["moe_router_z_loss"] = aux["router_z_loss"]
            metrics["moe_dropped_frac"] = aux["dropped_frac"]
            loss = loss + aux["aux"]
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if accum <= 1:
            (_, metrics), grads = grad_fn(params, batch)
            return grads, metrics

        def micro(grads_acc, mb):
            (_, metrics), grads = grad_fn(params, mb)
            grads_acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), grads_acc, grads
            )
            return grads_acc, metrics

        mbs = jax.tree.map(
            lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]), batch
        )
        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        grads, metrics_stack = jax.lax.scan(micro, zero_grads, mbs)
        grads = jax.tree.map(lambda g: g / accum, grads)
        metrics = jax.tree.map(lambda m: jnp.mean(m, axis=0), metrics_stack)
        metrics["tokens"] = metrics["tokens"] * accum
        return grads, metrics

    def train_step(state: TrainState, batch):
        from shellac_tpu.utils.failure import all_finite, guard_update

        grads, metrics = compute_grads(state.params, batch)
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        new_params = optax.apply_updates(state.params, updates)
        new_ema = state.ema_params
        if train_cfg.ema_decay is not None:
            d = train_cfg.ema_decay
            new_ema = jax.tree.map(
                lambda e, p: (e * d + p.astype(e.dtype) * (1.0 - d)).astype(
                    e.dtype
                ),
                state.ema_params, new_params,
            )
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        if train_cfg.skip_nonfinite_updates:
            # Guard on the loss too: an overflowed loss with grads that
            # still came out finite (clipping, a masked-out NaN term)
            # means the update direction is untrustworthy — skip it the
            # same way, so the host-side sentinel sees the anomaly in
            # `update_skipped` while the state stays clean.
            ok = all_finite(grads) & jnp.isfinite(metrics["loss"])
            new_params = guard_update(state.params, new_params, ok)
            new_opt_state = guard_update(state.opt_state, new_opt_state, ok)
            if new_ema is not None:
                new_ema = guard_update(state.ema_params, new_ema, ok)
            metrics["update_skipped"] = 1.0 - ok.astype(jnp.float32)
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt_state,
            ema_params=new_ema,
        )
        return new_state, metrics

    if not jit:
        return train_step

    if mesh is None:
        return jax.jit(train_step, donate_argnums=(0,))

    # Attach explicit shardings so the compiled step is fully partitioned.
    def jit_with_shardings(state, batch):
        abstract_state = jax.eval_shape(lambda s: s, state)
        param_axes = transformer.logical_axes(model_cfg)
        st_sh = state_shardings(mesh, abstract_state, param_axes)
        b_sh = batch_shardings(mesh)
        batch_in = jax.tree.map(lambda _: b_sh, batch)
        return jax.jit(
            train_step,
            in_shardings=(st_sh, batch_in),
            out_shardings=(st_sh, None),
            donate_argnums=(0,),
        )

    return _LazyShardedStep(jit_with_shardings)


class _LazyShardedStep:
    """Defers jit-with-shardings until the first call, when the concrete
    state/batch structure (which depends on the optax chain) is known.
    Generic over the step arity (also reused by the LoRA step)."""

    def __init__(self, build):
        self._build = build
        self._jitted = None

    def __call__(self, *args):
        if self._jitted is None:
            self._jitted = self._build(*args)
        return self._jitted(*args)

    def lower(self, *args):
        return self._build(*args).lower(*args)
