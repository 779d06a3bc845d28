"""Decoder-only transformer (LLaMA-style), functional and TPU-first.

Design choices, all driven by how XLA compiles for TPU:
  - Parameters are a plain pytree of arrays with a parallel pytree of
    *logical axis names* (see parallel/sharding.py). No module framework:
    pjit sees exactly the arrays and shardings we declare.
  - Layers are **stacked** along a leading axis and the forward pass is a
    `lax.scan` over them: one compiled block body regardless of depth
    (fast compiles), and the same stacked layout pipeline parallelism
    wants.
  - Each block is wrapped in `jax.checkpoint` when cfg.remat is set:
    activations are recomputed in backward, trading MXU FLOPs (cheap) for
    HBM (the scarce resource).
  - Compute in bf16, master params and softmax/norm accumulation in fp32.

The reference repo for this project is empty (SURVEY.md §0), so there is
no upstream architecture to cite; this is the standard pre-norm rotary
GQA decoder.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from shellac_tpu.config import ModelConfig
from shellac_tpu.ops.activations import geglu, softcap, swiglu
from shellac_tpu.ops.attention import attention
from shellac_tpu.ops.norms import rms_norm
from shellac_tpu.ops.qtrain import quant_dot
from shellac_tpu.ops.quant import materialize
from shellac_tpu.ops.rope import apply_rope, rope_angles
from shellac_tpu.parallel.sharding import constrain

Params = Dict[str, Any]


def grouped_moe(cfg: ModelConfig) -> bool:
    """True for interleaved dense/MoE stacks (moe_every > 1).

    Layout: layers are grouped into n_layers // moe_every super-blocks
    of (moe_every - 1) dense layers followed by one MoE layer (the
    DeepSeek/Mixtral-hybrid pattern, dense-first). Params hold two
    uniform stacks — {"dense": (ng, every-1, ...), "moe": (ng, ...)} —
    so the forward stays a scan over groups with a scan over the dense
    sub-stack inside: still one compiled block body per kind.
    """
    return cfg.moe is not None and cfg.moe_every > 1


def is_grouped_layers(layers) -> bool:
    """Structural twin of grouped_moe for code holding a params/axes
    layer tree but no config (merge helpers, axes mirrors)."""
    return set(layers.keys()) == {"dense", "moe"}


def first_k_layout(cfg: ModelConfig) -> bool:
    """True for the DeepSeek layout: a dense prefix then all-MoE.

    Params hold the same {"dense", "moe"} two-stack tree as the
    interleaved layout (so quantization/LoRA/sharding reuse applies
    unchanged), but the stacks are (first_k_dense, ...) and
    (n_layers - first_k_dense, ...) flat layer axes scanned back to
    back, not per-group sub-stacks.
    """
    return cfg.moe is not None and cfg.first_k_dense > 0


def _add_aux(a, b):
    return jax.tree.map(lambda u, v: u + v, a, b)


def map_layer_stacks(layers, fn):
    """Apply `fn(stack, name)` to each per-layer stack of a layers tree.

    The single place that knows a layers tree is either one flat stack
    (name=None) or the {"dense", "moe"} sub-stacks of an interleaved
    layout — consumers (quantization, LoRA, sharding) use this instead
    of re-implementing the grouped branch.
    """
    if is_grouped_layers(layers):
        return {k: fn(layers[k], k) for k in ("dense", "moe")}
    return fn(layers, None)


def _expert_stack(layers):
    """The stack of a layers tree that holds the MoE layers."""
    return layers["moe"] if is_grouped_layers(layers) else layers


def expert_ffn_path(cfg: ModelConfig, layers, *, cached: bool,
                    mesh=None) -> Optional[str]:
    """ops/moe.py::moe_ffn_path as this model's MoE layers will ask it
    (`layers` is params["layers"]); None for a model without experts.
    For callers that need to know the form before the call is traced:
    the cached forward (does the kernel want the stacks whole?) and the
    engine's `prefill_sorted_tokens` count."""
    if cfg.moe is None:
        return None
    from shellac_tpu.ops.moe import experts_plain, moe_ffn_path

    return moe_ffn_path(
        cfg.moe, cached=cached, mesh=mesh,
        plain=experts_plain(_expert_stack(layers), cfg.compute_dtype),
    )


def n_routers(cfg: ModelConfig) -> int:
    """Layers that hold a router: what MoE diagnostics average over
    (every layer of a flat stack, a dense model's zeros included)."""
    if grouped_moe(cfg):
        return cfg.n_layers // cfg.moe_every
    if first_k_layout(cfg):
        return cfg.n_layers - cfg.first_k_dense
    return cfg.n_layers


def scan_layers(cfg: ModelConfig, layers, carry, step, xs=(), first=0,
                experts_whole=False):
    """Walk a layers tree in layer order; returns (carry, ys).

    The single place that knows how a layer stack is laid out and
    scanned. Training, cached decode over every kind of cache and both
    pipelines supply `step(carry, lp, li, xs_l, moe_layer, attn_kind)
    -> (carry, ys_l)` and nothing else:

      - `layers` is params["layers"] or a pipeline stage's slice of it,
        `first` the index of its first layer; `li` = first, first + 1,
        ... is TRACED (it rides the scan), for state held in the carry
        that a layer addresses by its own index (a paged pool's blocks,
        EVA's rings and pages).
      - `moe_layer` (bool) and `attn_kind` (None, or the layer's entry
        of cfg.attn_pattern) are Python values: they choose which block
        body is compiled, so each kind costs one body whatever the
        depth.
      - `xs` is a pytree of per-layer stacks (L, ...) riding with the
        layers (a slot cache's leaves) and `ys` the pytree of whatever
        the step returns for each, restacked (L, ...). The walker
        splits and restacks them as it splits the parameters. With
        xs=() only the carry crosses the loops: a paged pool and EVA
        state are carries, so that each layer writes its rows in place
        and no stack is sliced out or put back (PERF.md, PR 26, 27).
        On a patterned stack `xs` may be {"window": ..., "full": ...},
        each kind's stacks holding that kind's layers only, in layer
        order (the mixed ring/dense caches); `ys` come back the same.

      - `experts_whole` keeps the MoE layers' expert weights
        (ops/moe.py::EXPERT_STACKS) out of the scans: a MoE layer's
        `lp` then holds each as a `StackRow(stack, row)`, the whole
        stack and the layer's row in it, for a kernel that reads the
        layer's experts where they lie (a kernel operand sliced out by
        the scan is a copy of them). `forward_with_cache` asks where
        ops/moe.py::sorted_kernel_runs.

    The four layouts (ModelConfig.validate keeps them exclusive):
    interleaved = a scan over (dense^(every-1), moe) groups holding a
    scan over the group's dense layers and one MoE step; dense prefix =
    two scans back to back; pattern = a scan over whole periods with
    the kinds unrolled inside (a window is a static kernel argument),
    the flat (L, ...) stacks viewed (L/period, period, ...) so params
    and checkpoints keep one layers axis; flat = one scan.
    """
    tmap = jax.tree.map

    if experts_whole:
        from shellac_tpu.ops.moe import EXPERT_STACKS, StackRow

        # A MoE layer's row in its stack, from its index: the stack
        # starts after the dense prefix, or holds every moe_every-th
        # layer (the group's last).
        stack = _expert_stack(layers)
        experts = {n: stack[n] for n in EXPERT_STACKS}
        stack = {n: v for n, v in stack.items() if n not in experts}
        layers = ({**layers, "moe": stack} if is_grouped_layers(layers)
                  else stack)
        every = cfg.moe_every if grouped_moe(cfg) else 1
        skip = cfg.first_k_dense if first_k_layout(cfg) else every - 1
        inner = step

        def step(carry, lp, li, xs_l, moe_layer, attn_kind):
            if moe_layer:
                row = (li - first - skip) // every
                lp = {**lp, **{n: StackRow(w, row)
                               for n, w in experts.items()}}
            return inner(carry, lp, li, xs_l, moe_layer, attn_kind)

    def n_of(stack):
        return jax.tree.leaves(stack)[0].shape[0]

    def merged(ys):  # (groups, n, ...) -> (groups * n, ...)
        return tmap(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), ys
        )

    def indices(n, start, stride=1):
        return start + stride * jnp.arange(n, dtype=jnp.int32)

    def scan_stack(carry, stack, xs, start, moe_layer):
        """Layers start, start + 1, ... of one homogeneous stack."""
        def body(c, inp):
            return step(c, *inp, moe_layer, None)

        return jax.lax.scan(
            body, carry, (stack, indices(n_of(stack), start), xs)
        )

    if grouped_moe(cfg):
        every, ng = cfg.moe_every, n_of(layers["moe"])

        def group_body(c, inp):
            glp, start, gx = inp
            c, yd = scan_stack(
                c, glp["dense"], tmap(lambda a: a[: every - 1], gx), start,
                False,
            )
            c, ym = step(
                c, glp["moe"], start + every - 1,
                tmap(lambda a: a[every - 1], gx), True, None,
            )
            return c, tmap(
                lambda d, m: jnp.concatenate([d, m[None]], axis=0), yd, ym
            )

        carry, ys = jax.lax.scan(
            group_body, carry,
            (layers, indices(ng, first, every),
             tmap(lambda a: a.reshape(ng, every, *a.shape[1:]), xs)),
        )
        return carry, merged(ys)
    if first_k_layout(cfg):
        kk = n_of(layers["dense"])
        carry, yd = scan_stack(
            carry, layers["dense"], tmap(lambda a: a[:kk], xs), first, False
        )
        carry, ym = scan_stack(
            carry, layers["moe"], tmap(lambda a: a[kk:], xs), first + kk, True
        )
        return carry, tmap(
            lambda d, m: jnp.concatenate([d, m], axis=0), yd, ym
        )
    moe_layer = cfg.moe is not None
    if cfg.attn_pattern is None:
        return scan_stack(carry, layers, xs, first, moe_layer)
    pattern = cfg.attn_pattern
    ng = n_of(layers) // len(pattern)
    # Where layer i of a period finds its xs: row i of every stack, or,
    # with xs given per kind, its kind's next row.
    by_kind = isinstance(xs, dict) and set(xs) == set(pattern)
    rows = [(k, pattern[:i].count(k)) if by_kind else (None, i)
            for i, k in enumerate(pattern)]
    held = xs if by_kind else {None: xs}

    def periods(a):
        return a.reshape(ng, a.shape[0] // ng, *a.shape[1:])

    def period_body(c, inp):
        gl, start, gx = inp
        outs = {k: [] for k in held}
        for i, (kind, (of, row)) in enumerate(zip(pattern, rows)):
            c, y = step(
                c, tmap(lambda a, i=i: a[i], gl), start + i,
                tmap(lambda a, row=row: a[row], gx[of]), moe_layer, kind,
            )
            outs[of].append(y)
        return c, {
            k: tmap(lambda *a: jnp.stack(a, axis=0), *v)
            for k, v in outs.items()
        }

    carry, ys = jax.lax.scan(
        period_body, carry,
        (tmap(periods, layers), indices(ng, first, len(pattern)),
         tmap(periods, held)),
    )
    ys = merged(ys)
    return carry, ys if by_kind else ys[None]


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Initialize a parameter pytree (master copy, cfg.param_dtype)."""
    cfg.validate()
    if grouped_moe(cfg) and cfg.n_layers % cfg.moe_every != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide into groups of "
            f"moe_every={cfg.moe_every}"
        )
    pdt = cfg.params_dtype
    d, h, hkv, dh, f = (
        cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.dim_per_head, cfg.ff_dim,
    )
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in, scale=1.0):
        std = scale * fan_in ** -0.5
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(pdt)

    def layer(key, moe_layer):
        ks = jax.random.split(key, 8)
        # Residual-output projections scaled down GPT-2 style so the
        # residual stream variance stays O(1) at depth.
        out_scale = (2 * cfg.n_layers) ** -0.5
        if cfg.mla is not None:
            m = cfg.mla
            kq = jax.random.split(ks[0], 2)
            kkv = jax.random.split(ks[1], 3)
            p = {
                "attn_norm": jnp.zeros((d,), pdt),
                "wkv_a": dense(kkv[0], (d, m.cache_dim), d),
                "kv_a_norm": jnp.zeros((m.kv_lora_rank,), pdt),
                "wkv_b_k": dense(
                    kkv[1], (m.kv_lora_rank, h, m.qk_nope_head_dim),
                    m.kv_lora_rank,
                ),
                "wkv_b_v": dense(
                    kkv[2], (m.kv_lora_rank, h, m.v_head_dim),
                    m.kv_lora_rank,
                ),
                "wo": dense(ks[3], (h * m.v_head_dim, d), h * m.v_head_dim,
                            out_scale),
                "mlp_norm": jnp.zeros((d,), pdt),
            }
            if m.q_lora_rank is None:
                p["wq"] = dense(kq[0], (d, h * m.qk_head_dim), d)
            else:
                p.update({
                    "wq_a": dense(kq[0], (d, m.q_lora_rank), d),
                    "q_a_norm": jnp.zeros((m.q_lora_rank,), pdt),
                    "wq_b": dense(kq[1], (m.q_lora_rank, h * m.qk_head_dim),
                                  m.q_lora_rank),
                })
        else:
            p = {
                "attn_norm": jnp.zeros((d,), pdt),
                "wq": dense(ks[0], (d, h * dh), d),
                "wk": dense(ks[1], (d, hkv * dh), d),
                "wv": dense(ks[2], (d, hkv * dh), d),
                "wo": dense(ks[3], (h * dh, d), h * dh, out_scale),
                "mlp_norm": jnp.zeros((d,), pdt),
            }
            if cfg.qk_norm:
                p.update({
                    "q_norm": jnp.zeros((dh,), pdt),
                    "k_norm": jnp.zeros((dh,), pdt),
                })
            if cfg.eva is not None:
                # The pooling's two learned vectors a head. Their
                # published initial law is not known; N(0, 1) and
                # N(0, 0.5^2) spread the pooling weights and offset the
                # pooled keys enough to matter from the first step.
                kp = jax.random.split(ks[7], 2)
                p.update({
                    "eva_phi": dense(kp[0], (h, dh), 1),
                    "eva_mu": dense(kp[1], (h, dh), 1, 0.5),
                })
            if cfg.dsa is not None:
                # The indexer: index queries, one index key a token
                # (LayerNorm'd: gain in the 1 + w form the RMS norms
                # use, and a bias), and the per-head weight.
                a = cfg.dsa
                kd = jax.random.split(ks[7], 3)
                p.update({
                    "dsa_wq": dense(kd[0], (d, a.index_heads * a.index_dim), d),
                    "dsa_wk": dense(kd[1], (d, a.index_dim), d),
                    "dsa_k_norm": jnp.zeros((a.index_dim,), pdt),
                    "dsa_k_bias": jnp.zeros((a.index_dim,), pdt),
                    "dsa_ww": dense(kd[2], (d, a.index_heads), d),
                })
        if cfg.attn_bias:
            p.update({
                "bq": jnp.zeros((h * dh,), pdt),
                "bk": jnp.zeros((hkv * dh,), pdt),
                "bv": jnp.zeros((hkv * dh,), pdt),
            })
        if cfg.post_norms:
            p.update({
                "post_attn_norm": jnp.zeros((d,), pdt),
                "post_mlp_norm": jnp.zeros((d,), pdt),
            })
        if cfg.attn_sink:
            p["sinks"] = jnp.zeros((h,), pdt)
        if cfg.attn_out_bias:
            p["bo"] = jnp.zeros((d,), pdt)
        if not moe_layer:
            p.update({
                "w_gate": dense(ks[4], (d, f), d),
                "w_up": dense(ks[5], (d, f), d),
                "w_down": dense(ks[6], (f, d), f, out_scale),
            })
        else:
            e = cfg.moe.num_experts
            fe = cfg.moe.d_ff_expert or f
            p.update({
                "w_router": dense(ks[7], (d, e), d),
                "w_gate": dense(ks[4], (e, d, fe), d),
                "w_up": dense(ks[5], (e, d, fe), d),
                "w_down": dense(ks[6], (e, fe, d), fe, out_scale),
            })
            if cfg.moe.scoring in ("sigmoid", "softmax_topk"):
                p["b_router"] = jnp.zeros((e,), pdt)
            if cfg.moe.expert_bias:
                p.update({
                    "b_gate": jnp.zeros((e, fe), pdt),
                    "b_up": jnp.zeros((e, fe), pdt),
                    "b_down": jnp.zeros((e, d), pdt),
                })
            if cfg.moe.num_shared_experts > 0:
                sf = cfg.moe.num_shared_experts * fe
                ks2 = jax.random.split(ks[7], 4)
                p.update({
                    "w_gate_shared": dense(ks2[1], (d, sf), d),
                    "w_up_shared": dense(ks2[2], (d, sf), d),
                    "w_down_shared": dense(ks2[3], (sf, d), sf, out_scale),
                })
        return p

    if grouped_moe(cfg):
        every = cfg.moe_every
        ng = cfg.n_layers // every
        keys = jax.random.split(k_layers, cfg.n_layers).reshape(
            ng, every, -1
        )
        layers = {
            "dense": jax.vmap(jax.vmap(lambda k: layer(k, False)))(
                keys[:, : every - 1]
            ),
            "moe": jax.vmap(lambda k: layer(k, True))(keys[:, every - 1]),
        }
    elif first_k_layout(cfg):
        kk = cfg.first_k_dense
        keys = jax.random.split(k_layers, cfg.n_layers)
        layers = {
            "dense": jax.vmap(lambda k: layer(k, False))(keys[:kk]),
            "moe": jax.vmap(lambda k: layer(k, True))(keys[kk:]),
        }
    else:
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        layers = jax.vmap(lambda k: layer(k, cfg.moe is not None))(layer_keys)
    params: Params = {
        "embed": (jax.random.normal(k_embed, (cfg.vocab_size, d), jnp.float32)
                  * 0.02).astype(pdt),
        "layers": layers,
        "final_norm": jnp.zeros((d,), pdt),
    }
    if not cfg.tie_embeddings:
        # Head m of n_pred_heads holds columns [m V, (m + 1) V).
        params["lm_head"] = dense(
            k_head, (d, cfg.n_pred_heads * cfg.vocab_size), d
        )
    if cfg.loop is not None:
        # The exit gate of a looped stack: one linear map with a bias
        # on each pass's normed state (loop_passes).
        params["loop_gate"] = {
            "w": dense(jax.random.fold_in(k_head, 1), (d,), d),
            "b": jnp.zeros((), pdt),
        }
    return params


def _layer_axes(cfg: ModelConfig, moe_layer: bool, lead=("layers",)) -> dict:
    """Axes for one layer stack; `lead` is the stacking prefix."""
    if not moe_layer:
        mlp_axes = {
            "w_gate": (*lead, "embed", "mlp"),
            "w_up": (*lead, "embed", "mlp"),
            "w_down": (*lead, "mlp", "embed"),
        }
    else:
        mlp_axes = {
            "w_router": (*lead, "embed", None),
            "w_gate": (*lead, "experts", "embed", "mlp"),
            "w_up": (*lead, "experts", "embed", "mlp"),
            "w_down": (*lead, "experts", "mlp", "embed"),
        }
        if cfg.moe.scoring in ("sigmoid", "softmax_topk"):
            mlp_axes["b_router"] = (*lead, None)
        if cfg.moe.expert_bias:
            mlp_axes.update({
                "b_gate": (*lead, "experts", "mlp"),
                "b_up": (*lead, "experts", "mlp"),
                "b_down": (*lead, "experts", "embed"),
            })
        if cfg.moe.num_shared_experts > 0:
            mlp_axes.update({
                "w_gate_shared": (*lead, "embed", "mlp"),
                "w_up_shared": (*lead, "embed", "mlp"),
                "w_down_shared": (*lead, "mlp", "embed"),
            })
    bias_axes = {}
    if cfg.attn_bias:
        bias_axes = {
            "bq": (*lead, "heads"),
            "bk": (*lead, "kv_heads"),
            "bv": (*lead, "kv_heads"),
        }
    if cfg.mla is not None:
        attn_axes = {
            # The latent projections are rank-bottlenecked, not
            # head-structured; only the per-head expansions and the
            # output projection shard over tp.
            "wkv_a": (*lead, "embed", None),
            "kv_a_norm": (*lead, None),
            "wkv_b_k": (*lead, None, "heads", None),
            "wkv_b_v": (*lead, None, "heads", None),
            "wo": (*lead, "heads", "embed"),
        }
        if cfg.mla.q_lora_rank is None:
            attn_axes["wq"] = (*lead, "embed", "heads")
        else:
            attn_axes.update({
                "wq_a": (*lead, "embed", None),
                "q_a_norm": (*lead, None),
                "wq_b": (*lead, None, "heads"),
            })
    else:
        attn_axes = {
            "wq": (*lead, "embed", "heads"),
            "wk": (*lead, "embed", "kv_heads"),
            "wv": (*lead, "embed", "kv_heads"),
            "wo": (*lead, "heads", "embed"),
        }
        if cfg.qk_norm:
            attn_axes.update({
                "q_norm": (*lead, None),
                "k_norm": (*lead, None),
            })
        if cfg.eva is not None:
            attn_axes.update({
                "eva_phi": (*lead, "heads", None),
                "eva_mu": (*lead, "heads", None),
            })
        if cfg.dsa is not None:
            attn_axes.update({
                "dsa_wq": (*lead, "embed", None),
                "dsa_wk": (*lead, "embed", None),
                "dsa_k_norm": (*lead, None),
                "dsa_k_bias": (*lead, None),
                "dsa_ww": (*lead, "embed", None),
            })
    post_axes = {}
    if cfg.post_norms:
        post_axes = {
            "post_attn_norm": (*lead, None),
            "post_mlp_norm": (*lead, None),
        }
    if cfg.attn_sink:
        post_axes["sinks"] = (*lead, "heads")
    if cfg.attn_out_bias:
        post_axes["bo"] = (*lead, None)
    return {
        "attn_norm": (*lead, None),
        **attn_axes,
        "mlp_norm": (*lead, None),
        **bias_axes,
        **post_axes,
        **mlp_axes,
    }


def logical_axes(cfg: ModelConfig) -> Params:
    """Pytree of logical axis names matching init_params' structure."""
    if grouped_moe(cfg):
        layers = {
            # Group axis maps like "layers" (pp shards it); the dense
            # sub-layer axis inside a group is unsharded.
            "dense": _layer_axes(cfg, False, lead=("layers", None)),
            "moe": _layer_axes(cfg, True),
        }
    elif first_k_layout(cfg):
        layers = {
            "dense": _layer_axes(cfg, False),
            "moe": _layer_axes(cfg, True),
        }
    else:
        layers = _layer_axes(cfg, cfg.moe is not None)
    la: Params = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": (None,),
    }
    if not cfg.tie_embeddings:
        la["lm_head"] = ("embed", "vocab")
    if cfg.loop is not None:
        la["loop_gate"] = {"w": (None,), "b": ()}
    return la


def _gated_act(cfg: ModelConfig):
    if cfg.activation == "swiglu":
        return swiglu
    if cfg.activation == "geglu":
        return geglu
    raise ValueError(
        f"unknown activation {cfg.activation!r}; have swiglu, geglu"
    )


@jax.named_scope("embed")
def _embed_tokens(cfg: ModelConfig, params: Params, tokens, cdt, mesh=None):
    from shellac_tpu.parallel.mesh import AXIS_TENSOR

    if mesh is not None and mesh.shape.get(AXIS_TENSOR, 1) > 1:
        # The table's vocab axis is tp-sharded. A plain gather makes the
        # SPMD partitioner replicate the whole table every step
        # ("involuntary full rematerialization" warning); a one-hot
        # contraction keeps it sharded — the one-hot is built locally on
        # each shard, the contraction rides the MXU, and XLA inserts a
        # single psum over tp. Exact: one row of 1.0 per token, so the
        # bf16 sum adds zeros.
        one_hot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=cdt)
        x = jnp.einsum(
            "bsv,vd->bsd", one_hot, params["embed"].astype(cdt)
        )
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(cdt)
    if cfg.embed_scale:
        # Gemma convention; the scale is computed in the compute dtype
        # (HF casts the normalizer to the embedding dtype too).
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdt)
    if cfg.fp32_residual:
        # The stream between blocks stays float32: every block adds its
        # compute-dtype branch outputs into it (jnp promotes the sum),
        # and every norm reads it and hands the compute dtype on.
        x = x.astype(jnp.float32)
    return x


def _remat_policy(name: str):
    """Map ModelConfig.remat_policy to a jax.checkpoint saveable policy."""
    if name == "none":
        return None  # recompute everything (max memory savings)
    policies = {
        "dots": jax.checkpoint_policies.checkpoint_dots,
        "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    }
    if name not in policies:
        raise ValueError(
            f"unknown remat_policy {name!r}; have none, {sorted(policies)}"
        )
    return policies[name]


def _zero_aux():
    """Zero-valued MoE aux dict; the single source of its tree structure
    (the pipeline's aux accumulation requires every producer to match)."""
    zero = jnp.zeros((), jnp.float32)
    return {"aux": zero, "balance_loss": zero, "router_z_loss": zero,
            "dropped_frac": zero}


def _block(
    cfg: ModelConfig, mesh, attn_impl: str, x, lp, cos, sin, cache=None,
    fresh_cache: bool = False, segments=None, page_tables=None,
    moe_layer=None, kv_scales=None, attn_kind=None, rolled=False,
    new_len=None,
):
    """One pre-norm transformer block. x: (B, S, D) in compute dtype.

    With `cache=(cache_k, cache_v, index, q_positions)` the block runs in
    decode mode: new k/v are written at `index` and attention reads the
    whole cache; returns (x, (new_cache_k, new_cache_v)). Without cache
    it returns (x, None).

    fresh_cache=True asserts every sequence starts at index 0 (prefill
    into an empty cache): attention then runs causally over the new
    chunk itself — O(S^2/2) and flash-eligible — instead of scanning the
    whole max_len buffer, while k/v still land in the cache.

    attn_kind overrides cfg.attn_window per layer for patterned stacks
    (cfg.attn_pattern): "full" drops the window, "window"/None keep it.
    """
    window = None if attn_kind == "full" else cfg.attn_window
    cdt = cfg.compute_dtype
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.kv_heads, cfg.dim_per_head

    def pdot(xin, w):
        # Dense projection: bf16 matmul, or an int8 MXU dot when the
        # training step opted in (cfg.quant_training, ops/qtrain.py).
        return quant_dot(xin, materialize(w, cdt), cfg.quant_training)

    # --- attention ---
    hx = rms_norm(x, lp["attn_norm"], cfg.norm_eps, mesh=mesh).astype(cdt)
    if cfg.mla is not None:
        o, new_cache = _mla_attention(
            cfg, mesh, attn_impl, hx, lp, cos, sin, cache,
            fresh_cache, segments, pdot, page_tables=page_tables,
            kv_scales=kv_scales,
        )
        with jax.named_scope("attn.out"):
            o = pdot(o, lp["wo"])
            if cfg.post_norms:
                o = rms_norm(o, lp["post_attn_norm"], cfg.norm_eps, mesh=mesh).astype(cdt)
            x = x + constrain(o, mesh, ("batch", "seq", None))
        return _block_mlp(cfg, mesh, x, lp, pdot, cache, fresh_cache,
                          moe_layer, new_cache)
    if cfg.eva is not None:
        o, new_cache = _eva_attention(
            cfg, attn_impl, hx, lp, cos, sin, cache, fresh_cache, pdot,
            eva_tables=page_tables, new_len=new_len,
        )
        with jax.named_scope("attn.out"):
            x = x + constrain(pdot(o, lp["wo"]), mesh, ("batch", "seq", None))
        return _block_mlp(cfg, mesh, x, lp, pdot, cache, fresh_cache,
                          moe_layer, new_cache)
    dsa_rope = None
    if cfg.dsa is not None:
        # The tables carry the index queries' and keys' beside the
        # heads' (dsa_rope_tables).
        half = dh // 2
        dsa_rope = (cos[..., half:], sin[..., half:])
        cos, sin = cos[..., :half], sin[..., :half]
    with jax.named_scope("attn.qkv"):
        q = pdot(hx, lp["wq"])
        k = pdot(hx, lp["wk"])
        v = pdot(hx, lp["wv"])
        if cfg.attn_bias:
            q = q + lp["bq"].astype(cdt)
            k = k + lp["bk"].astype(cdt)
            v = v + lp["bv"].astype(cdt)
        q = q.reshape(b, s, h, dh)
        k = k.reshape(b, s, hkv, dh)
        v = v.reshape(b, s, hkv, dh)
        if cfg.qk_norm:
            # Qwen3-style per-head-dim RMSNorm on q/k, applied before
            # rope.
            q = rms_norm(q, lp["q_norm"], cfg.norm_eps, mesh=mesh).astype(cdt)
            k = rms_norm(k, lp["k_norm"], cfg.norm_eps, mesh=mesh).astype(cdt)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    sinks = lp["sinks"] if cfg.attn_sink else None
    new_cache = None
    if cfg.dsa is not None:
        o, new_cache = _dsa_attention(
            cfg, mesh, attn_impl, hx, lp, q, k, v, dsa_rope, cache,
            fresh_cache, segments, pdot, page_tables=page_tables,
            new_len=new_len,
        )
    elif cache is None:
        o = _training_attention(cfg, mesh, attn_impl, q, k, v, segments,
                                window=window, sinks=sinks)
    elif page_tables is not None:
        from shellac_tpu.inference.kvcache import (
            paged_update_layer,
            quant_paged_update_layer,
        )

        # pool: (N, Hkv, bs, D) — the stacked pool viewed flat, read and
        # written through tables offset to this layer's blocks.
        pool_k, pool_v, index, q_positions = cache
        with jax.named_scope("kv.write"):
            if kv_scales is not None:
                # Int8 pool: quantize at write (K post-rope, the
                # QuantKVCache contract); scale pools scatter through
                # the same block tables.
                ks_l, vs_l = kv_scales
                pool_k, pool_v, ks_l, vs_l = quant_paged_update_layer(
                    pool_k, pool_v, ks_l, vs_l, k, v, index, page_tables
                )
                new_cache = (pool_k, pool_v, ks_l, vs_l)
            else:
                ks_l = vs_l = None
                pool_k, pool_v = paged_update_layer(
                    pool_k, pool_v, k, v, index, page_tables
                )
                new_cache = (pool_k, pool_v)
        if fresh_cache:
            o = attention(
                q, k, v, causal=True, window=window, impl=attn_impl, mesh=mesh,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks,
            )
        else:
            from shellac_tpu.ops.decode_attention import (
                paged_decode_attention,
            )

            o = paged_decode_attention(
                q, pool_k, pool_v, page_tables, index,
                window=window, impl=attn_impl, mesh=mesh,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks, k_scale=ks_l, v_scale=vs_l,
            )
    elif rolled:
        from shellac_tpu.inference.kvcache import (
            quant_roll_update_layer,
            roll_update_layer,
        )
        from shellac_tpu.ops.decode_attention import (
            rolled_decode_attention,
        )

        cache_k, cache_v, index, q_positions = cache  # ring buffers
        with jax.named_scope("kv.write"):
            if kv_scales is not None:
                # Int8 ring: quantize at write (K post-rope, the
                # QuantKVCache contract); reads dequantize the
                # window-sized ring.
                ks_l, vs_l = kv_scales
                cache_k, cache_v, ks_l, vs_l = quant_roll_update_layer(
                    cache_k, cache_v, ks_l, vs_l, k, v, index,
                    valid_len=new_len,
                )
                new_cache = (cache_k, cache_v, ks_l, vs_l)
            else:
                cache_k, cache_v = roll_update_layer(
                    cache_k, cache_v, k, v, index, valid_len=new_len
                )
                new_cache = (cache_k, cache_v)
        if fresh_cache:
            # Whole-prompt prefill attends the incoming chunk itself
            # (exact values — identical to the dense path); the ring
            # only matters for later reads.
            o = attention(
                q, k, v, causal=True, window=window, impl=attn_impl, mesh=mesh,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks,
            )
        else:
            rk, rv = cache_k, cache_v
            if kv_scales is not None:
                # Dequantize IN fp32 and stay there: a cast to the
                # compute dtype would add a rounding the dense int8
                # path never pays (its kernel folds the fp32 scale
                # after the integer dot).
                rk = rk.astype(jnp.float32) * ks_l[..., None]
                rv = rv.astype(jnp.float32) * vs_l[..., None]
            vl = s if new_len is None else new_len
            o = rolled_decode_attention(
                q, rk, rv, index, index + vl, window=window,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks,
            )
    elif kv_scales is not None:
        from shellac_tpu.inference.kvcache import quant_update_layer
        from shellac_tpu.ops.decode_attention import decode_attention

        cache_k, cache_v, index, q_positions = cache  # int8 cache layer
        ks_l, vs_l = kv_scales
        with jax.named_scope("kv.write"):
            cache_k, cache_v, ks_l, vs_l = quant_update_layer(
                cache_k, cache_v, ks_l, vs_l, k, v, index
            )
        new_cache = (cache_k, cache_v, ks_l, vs_l)
        if fresh_cache:
            # Prefill computes on the exact (unquantized) chunk; only
            # later reads see the int8 rounding.
            o = attention(
                q, k, v, causal=True, window=window, impl=attn_impl, mesh=mesh,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks,
            )
        else:
            o = decode_attention(
                q, cache_k, cache_v, index,
                window=window, impl=attn_impl, mesh=mesh,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks, k_scale=ks_l, v_scale=vs_l,
            )
    else:
        from shellac_tpu.inference.kvcache import update_layer

        cache_k, cache_v, index, q_positions = cache  # index: (B,)
        with jax.named_scope("kv.write"):
            cache_k, cache_v = update_layer(cache_k, cache_v, k, v, index)
        new_cache = (cache_k, cache_v)
        if fresh_cache:
            # Empty-cache prefill: attend within the new chunk only.
            # Every row's positions start at 0, so plain causal masking
            # already excludes the right-pad tail of shorter prompts.
            o = attention(
                q, k, v, causal=True, window=window, impl=attn_impl, mesh=mesh,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks,
            )
        else:
            from shellac_tpu.ops.decode_attention import decode_attention

            o = decode_attention(
                q, cache_k, cache_v, index,
                window=window, impl=attn_impl, mesh=mesh,
                scale=cfg.attn_scale, softcap=cfg.attn_softcap,
                sinks=sinks,
            )
    with jax.named_scope("attn.out"):
        o = pdot(o.reshape(b, s, h * dh), lp["wo"])
        if cfg.attn_out_bias:
            o = o + lp["bo"].astype(cdt)
        if cfg.post_norms:
            # Gemma-2 sandwich norm: the branch OUTPUT is normed before
            # the residual add (HF post_attention_layernorm placement).
            o = rms_norm(o, lp["post_attn_norm"], cfg.norm_eps, mesh=mesh).astype(cdt)
        x = x + constrain(o, mesh, ("batch", "seq", None))
    return _block_mlp(cfg, mesh, x, lp, pdot, cache, fresh_cache,
                      moe_layer, new_cache)


def _block_mlp(cfg, mesh, x, lp, pdot, cache, fresh_cache, moe_layer,
               new_cache):
    """The MLP half of a block (shared by the MHA/GQA and MLA paths)."""
    cdt = cfg.compute_dtype
    hx = rms_norm(x, lp["mlp_norm"], cfg.norm_eps, mesh=mesh).astype(cdt)
    moe_out = _zero_aux()
    # moe_layer overrides the config for interleaved stacks (grouped_moe):
    # dense sub-layers of a MoE model run the plain gated MLP.
    use_moe = cfg.moe is not None if moe_layer is None else moe_layer
    if use_moe:
        from shellac_tpu.ops.moe import (
            experts_plain,
            moe_ffn,
            moe_ffn_grouped,
            moe_ffn_path,
        )

        # Cached continuation (decode s=1, speculative verify windows,
        # prefix-cached suffix prefill) must never capacity-drop: a
        # dropped token's FFN output would silently become zero, and
        # decode-path exactness is the serving contract. Which form
        # the call then takes (and which a fresh call takes) is
        # ops/moe.py's rule.
        path = moe_ffn_path(
            cfg.moe, cached=cache is not None and not fresh_cache,
            mesh=mesh, plain=experts_plain(lp, cdt),
        )
        # Strict lookups for biased gates: a missing bias must be a
        # loud KeyError, not a silent zero (it changes which experts
        # are selected / what they compute).
        bias_kw = dict(
            b_router=(lp["b_router"]
                      if cfg.moe.scoring in ("sigmoid", "softmax_topk")
                      else None),
            b_gate=lp["b_gate"] if cfg.moe.expert_bias else None,
            b_up=lp["b_up"] if cfg.moe.expert_bias else None,
            b_down=lp["b_down"] if cfg.moe.expert_bias else None,
        )
        if path == "sorted":
            down, aux, metrics = moe_ffn_grouped(
                hx, lp["w_router"], lp["w_gate"], lp["w_up"],
                lp["w_down"], cfg.moe, mesh=mesh, **bias_kw,
            )
        else:
            down, aux, metrics = moe_ffn(
                hx, lp["w_router"], lp["w_gate"], lp["w_up"],
                lp["w_down"], cfg.moe,
                drop_tokens=path == "capacity",
                mesh=mesh, **bias_kw,
            )
        if cfg.moe.num_shared_experts > 0:
            with jax.named_scope("moe.shared"):
                sg = hx @ materialize(lp["w_gate_shared"], cdt)
                su = hx @ materialize(lp["w_up_shared"], cdt)
                down = down + _gated_act(cfg)(sg, su) @ materialize(
                    lp["w_down_shared"], cdt
                )
        moe_out = {
            "aux": aux,
            "balance_loss": metrics["moe_balance_loss"],
            "router_z_loss": metrics["moe_router_z_loss"],
            "dropped_frac": metrics["moe_dropped_frac"],
        }
    else:
        with jax.named_scope("mlp"):
            gate = pdot(hx, lp["w_gate"])
            up = pdot(hx, lp["w_up"])
            gate = constrain(gate, mesh, ("batch", "seq", "mlp"))
            up = constrain(up, mesh, ("batch", "seq", "mlp"))
            down = pdot(_gated_act(cfg)(gate, up), lp["w_down"])
    if cfg.post_norms:
        down = rms_norm(down, lp["post_mlp_norm"], cfg.norm_eps, mesh=mesh).astype(cdt)
    # The residual add belongs to whichever FFN ran.
    with jax.named_scope("moe.combine" if use_moe else "mlp"):
        x = x + constrain(down, mesh, ("batch", "seq", None))
    return x, new_cache, moe_out


def _training_attention(cfg, mesh, attn_impl, q, k, v, segments,
                        window="cfg", sinks=None):
    """Full-sequence attention with sequence-parallel dispatch.

    q (B, S, H, D); k/v (B, S, Hkv, D'). Shared by the standard GQA
    path and MLA's expanded form (there Hkv == H and v is padded to
    q's width, so the default d**-0.5 scale is already the MLA scale).
    `window` overrides cfg.attn_window for patterned stacks (the "cfg"
    sentinel keeps MLA's call sites untouched).
    """
    if window == "cfg":
        window = cfg.attn_window
    h, hkv = q.shape[2], k.shape[2]
    q = constrain(q, mesh, ("batch", "seq", "heads", None))
    k = constrain(k, mesh, ("batch", "seq", "kv_heads", None))
    v = constrain(v, mesh, ("batch", "seq", "kv_heads", None))
    from shellac_tpu.parallel.mesh import AXIS_SEQ

    sp_active = mesh is not None and mesh.shape.get(AXIS_SEQ, 1) > 1
    if attn_impl in ("ring", "ulysses") and not sp_active:
        raise ValueError(
            f"attn_impl={attn_impl!r} requires a mesh with sp > 1; got "
            f"mesh={'None' if mesh is None else dict(mesh.shape)}"
        )
    from shellac_tpu.parallel.ulysses import ulysses_supported

    ulysses_ok = sp_active and ulysses_supported(h, hkv, mesh)
    if attn_impl == "ulysses" and not ulysses_ok:
        raise ValueError(
            f"attn_impl='ulysses' needs per-device head counts divisible "
            f"by sp: n_heads={h}, n_kv_heads={hkv}, "
            f"mesh={dict(mesh.shape)}"
        )
    # 'auto' on an sp mesh: ring for plain causal (O(S/sp) kv
    # memory), ulysses for windowed attention when head counts
    # permit (full local sequence -> the flash kernel's window
    # block-skipping applies); ring handles windows too (banded
    # mask on global positions), so it is the windowed fallback
    # when ulysses can't split the heads.
    use_ulysses = attn_impl == "ulysses" or (
        attn_impl == "auto" and sp_active and window is not None
        and ulysses_ok
    )
    use_ring = attn_impl == "ring" or (
        attn_impl == "auto" and sp_active and not use_ulysses
    )
    if use_ring:
        # Sequence is sharded over sp: ring attention keeps kv local
        # (O(S/sp) memory) and rotates chunks over ICI instead of
        # letting GSPMD all-gather the whole sequence. Packed
        # segment ids rotate with their kv chunks.
        from shellac_tpu.parallel.ring_attention import ring_attention

        return ring_attention(
            q, k, v, mesh, causal=cfg.causal, segments=segments,
            window=window, scale=cfg.attn_scale, softcap=cfg.attn_softcap,
            sinks=sinks,
        )
    if use_ulysses:
        from shellac_tpu.parallel.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, mesh, causal=cfg.causal, window=window,
            scale=cfg.attn_scale, softcap=cfg.attn_softcap,
            sinks=sinks, segments=segments,
        )
    return attention(
        q, k, v, causal=cfg.causal, window=window,
        scale=cfg.attn_scale, softcap=cfg.attn_softcap, sinks=sinks,
        q_segments=segments, kv_segments=segments, impl=attn_impl, mesh=mesh,
    )


def _mla_attention(
    cfg: ModelConfig, mesh, attn_impl, hx, lp, cos, sin, cache,
    fresh_cache, segments, pdot, page_tables=None, kv_scales=None,
):
    """Multi-head latent attention (DeepSeek-style). Returns
    (o (B, S, H*v_head_dim), new_cache-or-None).

    Numerics follow HF DeepseekV2Attention exactly (interleaved rope on
    the qk_rope slice, shared single-head roped key, softmax scale
    qk_head_dim**-0.5). The cached path is the TPU-first part: the
    cache holds ONE row per token — concat(normed latent, roped k_pe),
    `kv_lora_rank + qk_rope_head_dim` wide, no head axis — and decode
    uses matrix absorption: scores contract the latent against
    per-head-projected queries (q_nope @ W_bk), and values re-expand
    AFTER the weighted sum (attn @ latent, then W_bv). That is exact
    algebra, not an approximation, and shrinks the cache ~n_heads-fold
    vs materializing K/V (HF's cache stores the expanded tensors).
    """
    from shellac_tpu.ops.rope import apply_rope_interleaved

    m = cfg.mla
    cdt = cfg.compute_dtype
    b, s, _ = hx.shape
    h = cfg.n_heads
    scale = m.qk_head_dim ** -0.5

    with jax.named_scope("attn.qkv"):
        if m.q_lora_rank is None:
            q = pdot(hx, lp["wq"])
        else:
            qa = rms_norm(
                pdot(hx, lp["wq_a"]), lp["q_a_norm"], cfg.norm_eps,
                mesh=mesh,
            ).astype(cdt)
            q = pdot(qa, lp["wq_b"])
        q = q.reshape(b, s, h, m.qk_head_dim)
        q = constrain(q, mesh, ("batch", "seq", "heads", None))
        q_nope = q[..., : m.qk_nope_head_dim]
        ckv = pdot(hx, lp["wkv_a"])  # (b, s, kv_rank + rope)
        c = rms_norm(
            ckv[..., : m.kv_lora_rank], lp["kv_a_norm"], cfg.norm_eps,
            mesh=mesh,
        ).astype(cdt)
    q_pe = apply_rope_interleaved(q[..., m.qk_nope_head_dim:], cos, sin)
    k_pe = apply_rope_interleaved(
        ckv[..., None, m.kv_lora_rank:], cos, sin
    )  # (b, s, 1, rope)

    w_bk = materialize(lp["wkv_b_k"], cdt)  # (kv_rank, h, nope)
    w_bv = materialize(lp["wkv_b_v"], cdt)  # (kv_rank, h, v_dim)

    def expanded_attention():
        """Full-K/V form (training and fresh prefill): expand the
        latent per head, pad v up to the qk width so the flash kernel
        applies, slice the pad back off. Dispatches through the shared
        sequence-parallel selection (ring/ulysses on sp meshes), where
        the default q-width scale IS the MLA scale."""
        with jax.named_scope("attn.qkv"):
            k_nope = jnp.einsum("bsr,rhn->bshn", c, w_bk)
            v = jnp.einsum("bsr,rhv->bshv", c, w_bv)
            k = jnp.concatenate(
                [k_nope,
                 jnp.broadcast_to(k_pe, (b, s, h, m.qk_rope_head_dim))],
                axis=-1,
            )
            qf = jnp.concatenate([q_nope, q_pe], axis=-1)
            pad = m.qk_head_dim - m.v_head_dim
            vp = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
        o = _training_attention(cfg, mesh, attn_impl, qf, k, vp, segments)
        return o[..., : m.v_head_dim]

    if cache is None:
        o = expanded_attention()
        return o.reshape(b, s, h * m.v_head_dim), None

    def absorbed_q():
        """Per-head queries projected into latent space + the roped
        slice: MQA rows against the latent cache."""
        with jax.named_scope("mla.absorb"):
            q_eff = jnp.einsum("bshn,rhn->bshr", q_nope, w_bk)
            return jnp.concatenate([q_eff, q_pe], axis=-1)

    def expand_values(o_lat):
        """The other half of the absorption: values re-expand per head
        AFTER the weighted sum over the latent rows."""
        with jax.named_scope("mla.absorb"):
            return jnp.einsum("bshr,rhv->bshv", o_lat, w_bv)

    with jax.named_scope("mla.latent_write"):
        latent = jnp.concatenate([c[:, :, None, :], k_pe], axis=-1)  # (b,s,1,·)
        v_stub = jnp.zeros((b, s, 1, 0), cdt)

    if page_tables is not None:
        from shellac_tpu.inference.kvcache import (
            paged_update_layer,
            quant_paged_update_layer,
        )
        from shellac_tpu.ops.decode_attention import paged_decode_attention

        pool_k, pool_v, index, _ = cache
        with jax.named_scope("mla.latent_write"):
            if kv_scales is not None:
                # Int8 latent pool: one scale per latent row, serving
                # both attention roles like the dense int8 latent
                # cache. (It keeps the row's own width, which does not
                # fill the lanes, where the bf16 pool pads it
                # (kvcache.held_width): reads take the gather + dequant
                # reference path — correct, with the paged-fallback
                # warning naming the constraint.)
                ks_l, vs_l = kv_scales
                pool_k, pool_v, ks_l, vs_l = quant_paged_update_layer(
                    pool_k, pool_v, ks_l, vs_l, latent, v_stub, index,
                    page_tables,
                )
                new_cache = (pool_k, pool_v, ks_l, vs_l)
            else:
                ks_l = None
                pool_k, pool_v = paged_update_layer(
                    pool_k, pool_v, latent, v_stub, index, page_tables
                )
                new_cache = (pool_k, pool_v)
        if fresh_cache:
            o = expanded_attention()
        else:
            # Same k-as-v trick as the dense path: the latent pool
            # serves both roles, values are its first kv_rank lanes
            # (handed in as ONE array, the kernel copies a page once).
            # The bf16 pool holds the row at whole lane tiles, 576 at
            # 640 (kvcache.held_width), its pad lanes zeros: the writer
            # above and this read zero-extend the row and the query.
            o_lat = paged_decode_attention(
                absorbed_q(), pool_k, pool_k, page_tables, index,
                scale=scale, impl=attn_impl, mesh=mesh,
                k_scale=ks_l, v_scale=ks_l,
            )[..., : m.kv_lora_rank]
            o = expand_values(o_lat)
        return o.reshape(b, s, h * m.v_head_dim), new_cache

    from shellac_tpu.ops.decode_attention import decode_attention

    cache_k, cache_v, index, _ = cache
    if kv_scales is not None:
        # Int8 latent cache: one scale per latent row; the k array (and
        # its scale) serves both attention roles, like the bf16 path.
        from shellac_tpu.inference.kvcache import quant_update_layer

        ks_l, vs_l = kv_scales
        with jax.named_scope("mla.latent_write"):
            cache_k, cache_v, ks_l, vs_l = quant_update_layer(
                cache_k, cache_v, ks_l, vs_l, latent, v_stub, index
            )
        new_cache = (cache_k, cache_v, ks_l, vs_l)
        if fresh_cache:
            o = expanded_attention()
        else:
            o_lat = decode_attention(
                absorbed_q(), cache_k, cache_k, index, scale=scale,
                impl=attn_impl, mesh=mesh, k_scale=ks_l, v_scale=ks_l,
            )[..., : m.kv_lora_rank]
            o = expand_values(o_lat)
        return o.reshape(b, s, h * m.v_head_dim), new_cache

    from shellac_tpu.inference.kvcache import update_layer

    with jax.named_scope("mla.latent_write"):
        cache_k, cache_v = update_layer(cache_k, cache_v, latent, v_stub,
                                        index)
    new_cache = (cache_k, cache_v)
    if fresh_cache:
        o = expanded_attention()
    else:
        # Absorbed decode: MQA over the latent rows. The same cache
        # array serves as k AND v (values are its first kv_rank lanes
        # after the weighted sum), so no second copy is ever stored.
        o_lat = decode_attention(
            absorbed_q(), cache_k, cache_k, index, scale=scale,
            impl=attn_impl, mesh=mesh,
        )[..., : m.kv_lora_rank]
        o = expand_values(o_lat)
    return o.reshape(b, s, h * m.v_head_dim), new_cache


def _eva_attention(cfg, attn_impl, hx, lp, cos, sin, cache, fresh_cache,
                   pdot, eva_tables=None, new_len=None):
    """EVA attention (cfg.eva; ops/eva_attention.py has the equations).
    hx: (B, S, D) normed input. Returns (o (B, S, H * Dh), new_cache).

    Without a cache, and for a fresh prefill, the sequence starts at
    position 0 and attends within itself: the pooled rows of all its
    chunks are made once (`eva.pool`) and every query block reads its
    own window's exact rows beside them (`eva.attend`).

    With `cache=((ring_k, ring_v), (pool_k, pool_v), index, _)`, the
    whole layer stacks (layout.EvaKVCache), and `eva_tables` saying
    which layer, rings and pages are this call's:

    * fresh prefill leaves what the same number of decode ticks would
      have: the last, partial window's exact rows in the ring (the whole
      ring is written; rows past the prompt are masked by position until
      decode overwrites them) and the pooled rows of every chunk through
      the slot's table (`eva.summary_write`; rows of chunks the prompt
      did not complete are rewritten when they do complete);
    * a decode tick writes its one exact row at position % W, pools the
      chunk that row belongs to from the ring, and writes that pooled
      row through the table if the row completed the chunk, then
      attends its ring rows 0 .. p % W
      and the pages of its completed windows in one softmax;
    * a block of exactly W rows that starts at a window's first position
      (a served prompt's prefill, one window at a time) writes its rows
      over the ring and its chunks' pooled rows to the window's page,
      then attends its own rows, causal, and the pooled rows of the pages
      of the windows before it (`eva_window_attention`). It reads no
      ring row: the window before it has closed, and is pooled.
    """
    from jax.experimental.layout import Layout, with_layout_constraint

    from shellac_tpu.inference.kvcache import eva_ring_write, paged_write
    from shellac_tpu.ops.eva_attention import (
        eva_attention,
        eva_decode_attention,
        eva_decode_kernel,
        eva_decode_path,
        eva_pool,
        eva_pool_sequence,
        eva_pooled_widths,
        eva_window_attention,
    )

    e = cfg.eva
    b, s, _ = hx.shape
    h, dh = cfg.n_heads, cfg.dim_per_head
    scale = dh ** -0.5
    with jax.named_scope("attn.qkv"):
        q = pdot(hx, lp["wq"]).reshape(b, s, h, dh)
        k = pdot(hx, lp["wk"]).reshape(b, s, h, dh)
        v = pdot(hx, lp["wv"]).reshape(b, s, h, dh)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    phi, mu = lp["eva_phi"], lp["eva_mu"]

    if cache is None or fresh_cache:
        with jax.named_scope("eva.pool"):
            kp, vp = eva_pool_sequence(k, v, phi, mu, e.chunk, scale)
        with jax.named_scope("eva.attend"):
            o = eva_attention(q, k, v, kp, vp, window=e.window,
                              chunk=e.chunk, scale=scale, impl=attn_impl)
        if cache is None:
            return o.reshape(b, s, h * dh), None
        ring, pool, _, _ = cache
        layer = eva_tables["layer"]
        n = jnp.full((b,), s, jnp.int32) if new_len is None else new_len
        # The ring: the window the prompt ends in, from its start.
        padded = -(-s // e.window) * e.window
        pad = ((0, 0), (0, padded - s), (0, 0), (0, 0))
        start = (n // e.window) * e.window

        def last_window(a):
            return jax.vmap(
                lambda a_b, st: jax.lax.dynamic_slice_in_dim(
                    a_b, st, e.window, axis=0)
            )(jnp.pad(a, pad), start)

        zero = jnp.zeros((b,), jnp.int32)
        with jax.named_scope("kv.write"):
            ring = eva_ring_write(ring, (last_window(k), last_window(v)),
                                  layer, eva_tables["slots"], zero)
        with jax.named_scope("eva.summary_write"):
            pool = paged_write(
                pool,
                (kp.astype(pool[0].dtype).transpose(0, 2, 1, 3),
                 vp.astype(pool[1].dtype).transpose(0, 2, 1, 3)),
                zero, eva_tables["tables"], layer=layer,
            )
        return o.reshape(b, s, h * dh), (ring, pool)

    ring, pool, index, _ = cache
    layer = eva_tables["layer"]
    if s != 1:  # one whole window at its first position (forward_with_cache)
        zero = jnp.zeros((b,), jnp.int32)
        with jax.named_scope("eva.pool"):
            kp, vp = eva_pool_sequence(k, v, phi, mu, e.chunk, scale)
        with jax.named_scope("kv.write"):
            ring = eva_ring_write(ring, (k, v), layer, eva_tables["slots"],
                                  zero)
        with jax.named_scope("eva.summary_write"):
            pool = paged_write(
                pool,
                (kp.astype(pool[0].dtype).transpose(0, 2, 1, 3),
                 vp.astype(pool[1].dtype).transpose(0, 2, 1, 3)),
                index // e.chunk, eva_tables["tables"], layer=layer,
            )
        with jax.named_scope("eva.attend"):
            # The pooled rows of the table's first n pages, in table
            # order: a window's index is at most the table's last entry,
            # so the pages before it are among all but that one (and
            # the scratch page where a width holds more). Row c is chunk
            # c; the block sees it iff its window has closed. One branch
            # a width: the smallest that holds the pages of the windows
            # before the block (the stacks stay out of it).
            r = e.window // e.chunk
            pages = eva_tables["tables"][:, :-1]
            widths = eva_pooled_widths(pages.shape[1], r, e.window)
            pages = jnp.pad(pages, ((0, 0), (0, widths[-1] - pages.shape[1])))

            def earlier(a):  # (L, H, N, R, D) -> (B, pages x R, H, D)
                return a[layer, :, pages].transpose(0, 1, 3, 2, 4).reshape(
                    b, -1, h, dh)

            def over(n):
                def attend(q, k, v, kp, vp):
                    if n == 0:
                        return attention(q, k, v, causal=True, scale=scale,
                                         impl=attn_impl)
                    seen = (jnp.arange(n * r, dtype=jnp.int32)[None, :]
                            < (index // e.chunk)[:, None])
                    return eva_window_attention(
                        q, k, v, kp[:, :n * r], vp[:, :n * r], seen,
                        scale=scale, impl=attn_impl,
                    )
                return attend

            which = jnp.searchsorted(jnp.asarray(widths, jnp.int32),
                                     jnp.max(index // e.window))
            o = jax.lax.switch(which, [over(n) for n in widths], q, k, v,
                               earlier(pool[0]), earlier(pool[1]))
        return o.reshape(b, s, h * dh), (ring, pool)

    at = index % e.window  # the new row's place in the ring
    with jax.named_scope("kv.write"):
        ring = eva_ring_write(ring, (k, v), layer, eva_tables["slots"], at)

    # This layer's rings and pages, read where they lie: the layout
    # constraint keeps the slice in the stack's own layout (left alone,
    # the TPU's compiler may suit a layout to a reader and copy the
    # stack to get it: PERF.md, PR 27).
    def of_layer(a):
        return with_layout_constraint(
            jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
            Layout(major_to_minor=tuple(range(a.ndim - 1))),
        )

    with jax.named_scope("eva.pool"):
        # The chunk's rows, (B, C, H, D): a gather of B x C slabs out of
        # the stack itself. (A vmapped slice of the layer's rings moved
        # their slot axis first, and a gather from the layer's slice
        # made the slice real: either way every ring of the layer was
        # copied, 2.4 ms a layer on the chip, half the tick.)
        rows = ((at // e.chunk) * e.chunk)[:, None] + jnp.arange(
            e.chunk, dtype=jnp.int32)
        cols = eva_tables["slots"][:, None]
        kp, vp = eva_pool(ring[0][layer, rows, cols],
                          ring[1][layer, rows, cols], phi, mu, scale)
    with jax.named_scope("eva.summary_write"):
        pool = paged_write(
            pool, (kp[:, :, None, :], vp[:, :, None, :]), index // e.chunk,
            eva_tables["tables"], layer=layer,
            only=(index % e.chunk) == e.chunk - 1,
        )
    with jax.named_scope("eva.attend"):
        if eva_decode_path(q[:, 0].shape, ring[0].shape, pool[0].shape,
                           ring[0].dtype, attn_impl) == "eva_kernel":
            # The kernel takes the whole stacks and the layer's number,
            # and copies a slot's valid rows and own pages itself.
            o = eva_decode_kernel(
                q[:, 0], *ring, at + 1, *pool, eva_tables["tables"],
                eva_tables["done"], layer=layer, cols=eva_tables["slots"],
                scale=scale,
            )
        else:
            o = eva_decode_attention(
                q[:, 0], of_layer(ring[0]), of_layer(ring[1]), at + 1,
                of_layer(pool[0]), of_layer(pool[1]), eva_tables["owned"],
                scale=scale,
            )
    return o.reshape(b, 1, h * dh), (ring, pool)


def dsa_rope_tables(cfg: ModelConfig, positions, cos, sin):
    """Rope tables of a model with an indexer: the attention heads'
    (dim_per_head wide) with the index queries' and keys' (index_dim
    wide, the same theta and scaling) appended on the last axis.
    _dsa_attention splits them again; riding as one pair they cross the
    layer walks, remat and the pipeline's extras like any other."""
    icos, isin = rope_angles(positions, cfg.dsa.index_dim, cfg.rope_theta,
                             yarn=cfg.rope_yarn, llama3=cfg.rope_llama3,
                             linear=cfg.rope_linear)
    return (jnp.concatenate([cos, icos], axis=-1),
            jnp.concatenate([sin, isin], axis=-1))


def _dsa_attention(cfg, mesh, attn_impl, hx, lp, q, k, v, rope, cache,
                   fresh_cache, segments, pdot, page_tables=None,
                   new_len=None):
    """Attention of a model with an indexer (cfg.dsa;
    ops/dsa_attention.py has the equations). hx: (B, S, D) normed
    input; q (B, S, H, Dh), k, v (B, S, Hkv, Dh), roped; rope: the
    (cos, sin) of the index queries and keys. Returns (o (B, S, H, Dh),
    new_cache).

    Without a cache the run attends within itself under each query's
    choice. With `cache=(pool_k, pool_v, pool_i, index, _)`, the paged
    pools viewed flat (forward_with_cache) and `page_tables` offset to
    this layer's blocks, the run's k, v and index-key rows are written
    through the tables first (`kv.write`, `dsa.index_write`), then:

    * a fresh prompt attends within itself, as without a cache;
    * a cached chunk of several rows reads the slot's rows back densely
      (its own among them) and attends under its queries' choices among
      them, over the smallest extent of the table that holds them
      (`dsa.live_widths`: one branch of a switch each);
    * a decode tick scores its one query against the slot's index rows
      through the table (`dsa.score`), takes the choice as row numbers
      (`dsa.select`), gathers those k and v rows by (page, offset) and
      attends them (`dsa.attend`).

    A state that cannot hold more rows than are kept takes the same
    paths with every row chosen and no index read."""
    from jax.experimental.layout import Layout, with_layout_constraint

    from shellac_tpu.ops import dsa_attention as dsa
    from shellac_tpu.ops.norms import layer_norm_ref

    a = cfg.dsa
    cdt = cfg.compute_dtype
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    scale = cfg.attn_scale if cfg.attn_scale is not None else dh ** -0.5
    if segments is not None:
        raise NotImplementedError(
            "the indexer chooses among the earlier positions of ONE "
            "sequence a row: packed segments are not defined for it"
        )
    icos, isin = rope
    with jax.named_scope("dsa.index_proj"):
        u = pdot(hx, lp["dsa_wq"]).reshape(b, s, a.index_heads, a.index_dim)
        u = apply_rope(u, icos, isin)
        c = layer_norm_ref(
            pdot(hx, lp["dsa_wk"]),
            1.0 + lp["dsa_k_norm"].astype(jnp.float32), lp["dsa_k_bias"],
            cfg.norm_eps,
        ).astype(cdt)
        c = apply_rope(c[:, :, None, :], icos, isin)[:, :, 0, :]
        w = jnp.einsum("bsd,dj->bsj", hx, materialize(lp["dsa_ww"], cdt),
                       preferred_element_type=jnp.float32)
    steps = jnp.arange(s, dtype=jnp.int32)

    def within(n):
        """The run attends within itself; n (B,) of its rows exist."""
        if s <= a.topk:
            return attention(q, k, v, causal=True, impl=attn_impl,
                             mesh=mesh, scale=scale)
        at = jnp.broadcast_to(steps, (b, s))
        # The kernels have no backward pass: a run without a cache
        # (scoring, training) takes the plain forms.
        impl = attn_impl if cache is not None else "ref"
        mask = dsa.choice_mask(u, w, c.transpose(0, 2, 1), at, n, a.topk, s,
                               impl=impl)
        with jax.named_scope("dsa.attend"):
            return dsa.masked_attention(q, k, v, mask, at, scale, impl=impl)

    if cache is None:
        return within(jnp.full((b,), s, jnp.int32)), None
    if page_tables is None:
        raise ValueError(
            "a model with an indexer (cfg.dsa) keeps its rows in the "
            "paged pools (k, v and the index keys under one block "
            "table): serve it on the 'paged' cache backend"
        )
    from shellac_tpu.inference.kvcache import fit_row, paged_write

    # pool_k, pool_v: (N, 1, bs, W), a token's kv heads side by side in
    # one row (cfg.cache_head_dim, zero-extended to the width the pool
    # holds rows at: kvcache.held_width): a tick gathers whole rows.
    pool_k, pool_v, pool_i, index, _ = cache
    with jax.named_scope("kv.write"):
        pool_k, pool_v = paged_write(
            (pool_k, pool_v),
            tuple(fit_row(x.astype(p.dtype).reshape(b, 1, s, hkv * dh),
                          p.shape[-1])
                  for x, p in ((k, pool_k), (v, pool_v))),
            index, page_tables,
        )
    with jax.named_scope("dsa.index_write"):
        # (N, Di, bs): a page holds its index keys key-axis innermost,
        # the form the scores' matmul reads; pinned as held on the way
        # out as well as in (paged_write's docstring has why).
        (pool_i,) = paged_write(
            (pool_i,), (c.astype(pool_i.dtype).transpose(0, 2, 1),),
            index, page_tables,
        )
        pool_i = with_layout_constraint(
            pool_i, Layout(major_to_minor=(0, 1, 2)))
    new_cache = (pool_k, pool_v, pool_i)
    n_new = jnp.full((b,), s, jnp.int32) if new_len is None else new_len
    if fresh_cache:
        return within(n_new), new_cache

    mb = page_tables.shape[1]
    bs = pool_k.shape[2]
    cap = mb * bs

    def index_keys():
        """(B, Di, cap): the slot's index keys through its table."""
        x = jnp.take(pool_i, page_tables.reshape(-1), axis=0)
        x = x.reshape(b, mb, a.index_dim, bs).transpose(0, 2, 1, 3)
        return x.reshape(b, a.index_dim, cap)

    if s > 1:
        with jax.named_scope("kv.gather"):
            # Whole pages through the table: (B, cap, Hkv, Dh), the
            # run's own rows among them.
            k_all, v_all = (
                jnp.take(p, page_tables.reshape(-1), axis=0).reshape(
                    b, cap, -1)[..., :hkv * dh].reshape(b, cap, hkv, dh)
                for p in (pool_k, pool_v))
            c_t = index_keys() if cap > a.topk else None
        at = index[:, None] + steps[None, :]
        k_len = index + n_new

        def over(wd):
            """The run against the slot's first `wd` rows."""
            def run():
                mask = dsa.choice_mask(
                    u, w, None if c_t is None else c_t[..., :wd], at, k_len,
                    a.topk, wd, impl=attn_impl)
                with jax.named_scope("dsa.attend"):
                    return dsa.masked_attention(
                        q, k_all[:, :wd], v_all[:, :wd], mask, at, scale,
                        impl=attn_impl)
            return run

        # One program holds the attention at a few extents of the table
        # and a chunk takes the smallest that holds its rows: the
        # program does not depend on the chunk's offset, its cost does.
        widths = dsa.live_widths(cap, a.topk, bs)
        which = sum((jnp.max(k_len) > wd).astype(jnp.int32)
                    for wd in widths[:-1])
        return jax.lax.switch(which, [over(wd) for wd in widths]), new_cache

    allowed = jnp.arange(cap, dtype=jnp.int32)[None, :] <= index[:, None]
    if cap <= a.topk:
        # A state that cannot hold more rows than are kept: every row.
        rows = jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), (b, cap))
        ok = allowed
    else:
        with jax.named_scope("dsa.score"):
            scores = dsa.index_scores(u, w, index_keys())[:, 0]
        with jax.named_scope("dsa.select"):
            rows, ok = dsa.select_rows(scores, allowed, a.topk)
    with jax.named_scope("dsa.attend"):
        block = jnp.take_along_axis(page_tables, rows // bs, axis=1)
        k_rows, v_rows = (
            dsa.gather_rows(p, block, rows % bs)[..., :hkv * dh].reshape(
                b, -1, hkv, dh)
            for p in (pool_k, pool_v))
        o = dsa.attend_rows(q[:, 0], k_rows, v_rows, ok, scale)
    return o[:, None], new_cache


def loop_passes(cfg: ModelConfig, params: Params, x, one_pass, state,
                xs=None, mesh=None):
    """Run a looped stack (cfg.loop): the layer walk `steps` times over
    the same parameters, ONE compiled body for the passes.

    `one_pass(x, state, first, xs_t) -> (x, state, ys_t)` walks the
    layers once with `first` = t * n_layers (traced) as the index of
    its first cached layer: pass t of layer l reads and writes cached
    layer t * n_layers + l and no other. `state` crosses the passes as
    a carry (a paged pool, written in place; MoE aux sums), `xs` is a
    pytree of (steps, ...) stacks of which a pass takes its own (a slot
    cache's leaves viewed (steps, n_layers, ...)), `ys` what the passes
    return, stacked.

    Between passes the stream is normed with the final norm, and the
    NORMED state is what enters the next pass and what the gate reads:
    lam_t = sigmoid(w . h_{t+1} + b). A token's exit step is the first
    t at which p_0 + ... + p_t >= exit_threshold, with p_t = lam_t
    prod_{j<t} (1 - lam_j) and the last pass taking the rest, else the
    last. Every pass runs for every token whatever its exit step (a
    later token's pass t attends every earlier token's pass-t rows).

    Returns (h, state, ys, exit_step): h (B, S, D) each token's normed
    state at its exit step, ready for the output projection with no
    second norm; exit_step (B, S) int32."""
    lp, cdt = cfg.loop, cfg.compute_dtype
    last = lp.steps - 1
    gate_w = params["loop_gate"]["w"].astype(cdt)
    gate_b = params["loop_gate"]["b"].astype(jnp.float32)
    b, s, _ = x.shape

    def body(c, inp):
        x, state, left, cum, exit_step, chosen = c
        t, xs_t = inp
        y, state, ys_t = one_pass(x, state, t * cfg.n_layers, xs_t)
        h = rms_norm(y, params["final_norm"], cfg.norm_eps, mesh=mesh,
                     scope="loop.norm").astype(cdt)
        with jax.named_scope("loop.gate"):
            lam = jax.nn.sigmoid(jnp.einsum(
                "bsd,d->bs", h, gate_w,
                preferred_element_type=jnp.float32,
            ) + gate_b)
        with jax.named_scope("loop.exit"):
            # `left` = prod_{j<t} (1 - lam_j): what no earlier pass took.
            cum = cum + jnp.where(t == last, left, lam * left)
            now = (exit_step < 0) & (
                (cum >= lp.exit_threshold) | (t == last)
            )
            chosen = jnp.where(now[..., None], h, chosen)
            exit_step = jnp.where(now, t, exit_step)
            left = left * (1.0 - lam)
        return (h.astype(x.dtype), state, left, cum, exit_step,
                chosen), ys_t

    init = (
        x, state, jnp.ones((b, s), jnp.float32),
        jnp.zeros((b, s), jnp.float32), jnp.full((b, s), -1, jnp.int32),
        jnp.zeros(x.shape, cdt),
    )
    (_, state, _, _, exit_step, chosen), ys = jax.lax.scan(
        body, init, (jnp.arange(lp.steps, dtype=jnp.int32), xs)
    )
    return chosen, state, ys, exit_step


def segment_positions(segment_ids: jax.Array) -> jax.Array:
    """Per-segment position ids: restart at 0 on every segment change.

    segment_ids: (B, S) int32, non-decreasing along S within a row.
    """
    b, s = segment_ids.shape
    ar = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    changed = jnp.concatenate(
        [jnp.ones((b, 1), bool), segment_ids[:, 1:] != segment_ids[:, :-1]],
        axis=1,
    )
    start = jax.lax.cummax(jnp.where(changed, ar, 0), axis=1)
    return ar - start


def forward(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # (B, S) int32
    *,
    positions: Optional[jax.Array] = None,  # (B, S) int32
    segment_ids: Optional[jax.Array] = None,  # (B, S) int32 — packed docs
    mesh=None,
    attn_impl: str = "auto",
    pipeline_microbatches: Optional[int] = None,
    return_aux: bool = False,
    return_hidden: bool = False,
) -> jax.Array:
    """Full forward pass; returns fp32 logits (B, S, V), or
    (B, S, n_pred_heads, V) for a model with several prediction heads
    (head m scores the token m + 1 ahead).

    With return_hidden=True, skips the LM head and returns the
    post-final-norm hidden states (B, S, D) in compute dtype instead of
    logits — the seam the fused (vocab-chunked) loss uses so the full
    logits tensor never materializes.

    With a mesh whose pp axis > 1, the layer stack runs as a GPipe
    pipeline with `pipeline_microbatches` microbatches (default pp).
    With segment_ids, rows hold multiple packed documents: attention is
    block-diagonal over segments and RoPE positions restart per segment,
    so each document computes exactly as if it were alone in the row.
    With return_aux=True, returns (logits, aux) where aux is a dict:
    "aux" (summed MoE auxiliary loss, 0 for dense) plus per-layer-mean
    router diagnostics (balance_loss, router_z_loss, dropped_frac).
    """
    cdt = cfg.compute_dtype
    b, s = tokens.shape
    if cfg.eva is not None and (positions is not None
                                or segment_ids is not None):
        raise NotImplementedError(
            "EVA attention windows and chunks count from position 0 of "
            "one sequence per row: explicit positions and packed "
            "segments are not defined for it"
        )
    if positions is not None and jnp.ndim(positions) == 3:
        # M-RoPE ids (3, B, S): token input only (three equal axes).
        from shellac_tpu.ops.rope import mrope_token_positions

        positions = mrope_token_positions(positions)
    pos = positions
    if pos is None:
        if segment_ids is not None:
            pos = segment_positions(segment_ids)
        else:
            pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    cos, sin = rope_angles(pos, cfg.rope_dim, cfg.rope_theta,
                           yarn=cfg.rope_yarn, llama3=cfg.rope_llama3,
                           linear=cfg.rope_linear)
    if cfg.rope_local_theta is not None:
        # Gemma-3 dual rope: "window" layers use their own unscaled
        # frequency base; rope scaling applies to global layers only.
        cos_l, sin_l = rope_angles(pos, cfg.rope_dim, cfg.rope_local_theta)
    else:
        cos_l = sin_l = None
    if cfg.dsa is not None:
        cos, sin = dsa_rope_tables(cfg, pos, cos, sin)

    x = _embed_tokens(cfg, params, tokens, cdt, mesh=mesh)
    x = constrain(x, mesh, ("batch", "seq", None))

    if segment_ids is not None and mesh is not None:
        # Replicate the segment row over sp ONCE, outside the layer
        # scan: both sp attention paths want non-seq-sharded views of it
        # (ulysses needs the full row on every rank; ring slices its
        # chunk inside shard_map), and without this constraint GSPMD
        # would place the sp all-gather at the shard_map boundary inside
        # the scan body — one collective per layer for layer-invariant
        # int32 ids.
        segment_ids = constrain(segment_ids, mesh, ("batch", None))

    def run_stack(layers, x, cos, sin, cos_l, sin_l, seg):
        """Walk a layers tree: the whole stack, or one pipeline stage's
        with the rope tables and segment ids of the microbatch it holds.
        Returns (x, aux summed over the layers walked)."""
        def step(carry, lp, li, xs_l, moe_layer, attn_kind):
            def blk(x, lp, cos, sin, seg):
                return _block(
                    cfg, mesh, attn_impl, x, lp, cos, sin, segments=seg,
                    moe_layer=moe_layer, attn_kind=attn_kind,
                )

            if cfg.remat:
                blk = jax.checkpoint(
                    blk, policy=_remat_policy(cfg.remat_policy)
                )
            x, acc = carry
            # Gemma-3 dual rope: "window" layers take the local tables.
            local = cos_l is not None and attn_kind == "window"
            x, _, moe_out = blk(
                x, lp, cos_l if local else cos, sin_l if local else sin, seg
            )
            return (x, _add_aux(acc, moe_out)), None

        return scan_layers(cfg, layers, (x, _zero_aux()), step)[0]

    from shellac_tpu.parallel.mesh import AXIS_PIPE

    pp = mesh.shape.get(AXIS_PIPE, 1) if mesh is not None else 1
    if pp > 1 and cfg.eva is not None:
        raise NotImplementedError("pp over EVA attention is not wired yet")
    if pp > 1 and cfg.loop is not None:
        raise NotImplementedError(
            "pp over a looped stack is not wired yet: a stage would "
            "hold its layers for every pass, and the stream would cross "
            "the stages once a pass"
        )
    n_micro = 1
    exit_step = None
    if cfg.loop is not None:
        def one_pass(x, acc, first, _):
            x, aux = run_stack(
                params["layers"], x, cos, sin, cos_l, sin_l, segment_ids
            )
            return x, _add_aux(acc, aux), None

        x, aux_sum, _, exit_step = loop_passes(
            cfg, params, x, one_pass, _zero_aux(), mesh=mesh
        )
    elif pp > 1:
        from shellac_tpu.parallel.pipeline import pipeline_apply

        if first_k_layout(cfg):
            raise NotImplementedError(
                "pp over a first_k_dense layout is not wired yet (the "
                "two stacks are unequal; stage balancing needs its own "
                "schedule) — use pp=1 or the moe_every layout"
            )
        if grouped_moe(cfg):
            # Interleaved stacks pipeline at GROUP granularity: each
            # stage holds whole (dense^(every-1), moe) super-blocks, so
            # stage compute stays uniform and the group axis shards
            # over pp exactly like the layer axis does for flat stacks.
            ng = cfg.n_layers // cfg.moe_every
            if ng % pp:
                raise ValueError(
                    f"n_layers/moe_every = {ng} groups not divisible "
                    f"by pp={pp}"
                )
            per_stage = ng // pp
        else:
            if cfg.n_layers % pp:
                raise ValueError(
                    f"n_layers={cfg.n_layers} not divisible by pp={pp}"
                )
            per_stage = cfg.n_layers // pp
            if cfg.attn_pattern is not None and \
                    per_stage % len(cfg.attn_pattern):
                raise ValueError(
                    f"pp={pp} stages hold {per_stage} layers each, not a "
                    f"whole number of attn_pattern periods "
                    f"(len {len(cfg.attn_pattern)})"
                )
        stage_params = jax.tree.map(
            lambda p: p.reshape(pp, per_stage, *p.shape[1:]),
            params["layers"],
        )

        # Microbatches see a slice of the batch, so per-row RoPE tables
        # and segment ids ride WITH each microbatch through the stage
        # shift register, as extras.
        if positions is not None or segment_ids is not None:
            extras = {"cos": cos, "sin": sin}
            if cos_l is not None:
                extras.update({"cos_l": cos_l, "sin_l": sin_l})
            extras_axes = {k: ("batch", "seq", None) for k in extras}
            if segment_ids is not None:
                # Keep the sp replication set up above: sharding seg
                # over "seq" here would reintroduce the per-layer sp
                # all-gather inside every pipeline tick.
                extras["seg"] = segment_ids
                extras_axes["seg"] = ("batch", None)

            def stage_fn(sp_lp, x, ex):
                return run_stack(
                    sp_lp, x, ex["cos"], ex["sin"], ex.get("cos_l"),
                    ex.get("sin_l"), ex.get("seg"),
                )
        else:
            extras = extras_axes = None
            # Uniform positions: a (1, S, half) table broadcasts over
            # every microbatch — cheaper than shifting per-row tables.
            cos, sin = cos[:1], sin[:1]
            if cos_l is not None:
                cos_l, sin_l = cos_l[:1], sin_l[:1]

            def stage_fn(sp_lp, x):
                return run_stack(sp_lp, x, cos, sin, cos_l, sin_l, None)

        n_micro = pipeline_microbatches or pp
        x, aux_sum = pipeline_apply(
            stage_fn, stage_params, x,
            n_stages=pp, n_micro=n_micro, mesh=mesh, aux_init=_zero_aux(),
            extras=extras, extras_axes=extras_axes,
        )
    else:
        x, aux_sum = run_stack(
            params["layers"], x, cos, sin, cos_l, sin_l, segment_ids
        )
    # aux_sum holds every (layer, microbatch) contribution once. The aux
    # loss sums over layers and averages over microbatches (each micro's
    # balance loss is computed on its own token population — the
    # standard grad-accum estimator); diagnostics additionally average
    # over the layers that hold a router.
    inv_m = 1.0 / n_micro
    inv_lm = inv_m / n_routers(cfg)
    aux = {
        "aux": aux_sum["aux"] * inv_m,
        "balance_loss": aux_sum["balance_loss"] * inv_lm,
        "router_z_loss": aux_sum["router_z_loss"] * inv_lm,
        "dropped_frac": aux_sum["dropped_frac"] * inv_lm,
    }
    # A looped stack's state is each token's exit step's, already under
    # the final norm (loop_passes); aux then says which step that was.
    normed = cfg.loop is not None
    if normed:
        aux["loop_exit_step"] = exit_step

    if return_hidden:
        if not normed:
            x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                         mesh=mesh).astype(cdt)
        x = constrain(x, mesh, ("batch", "seq", None))
        if return_aux:
            return x, aux
        return x
    logits = unembed(cfg, params, x, mesh=mesh, all_heads=True,
                     normed=normed)
    if cfg.n_pred_heads == 1:
        logits = constrain(logits, mesh, ("batch", "seq", "vocab"))
    if return_aux:
        return logits, aux
    return logits


def output_weights(cfg: ModelConfig, params: Params, cdt) -> jax.Array:
    """The LM-head matrix (D, n_pred_heads * V) in compute dtype (tied
    or untied)."""
    if cfg.tie_embeddings:
        return params["embed"].astype(cdt).T
    return params["lm_head"].astype(cdt)


@jax.named_scope("unembed")
def unembed(cfg: ModelConfig, params: Params, x: jax.Array,
            mesh=None, all_heads: bool = False,
            normed: bool = False) -> jax.Array:
    """Final RMSNorm + output projection (+ logit softcap): the model
    tail shared by forward, forward_with_cache, and the pipelined
    decode's per-group exit (inference/pp_pipeline.py), so a head
    change cannot drift between them. x: (B, S, D) pre-final-norm
    hidden; returns fp32 (B, S, V) logits. Callers own any mesh
    constraint on the result.

    A model with n_pred_heads > 1 unembeds head 0, the next token,
    which is all that cached generation reads; `all_heads` (forward:
    scoring, training) returns (B, S, n_pred_heads, V). `normed`: x is
    already under the final norm (a looped stack's exit state), and
    takes no second one."""
    cdt = cfg.compute_dtype
    if not normed:
        x = rms_norm(x, params["final_norm"], cfg.norm_eps,
                     mesh=mesh).astype(cdt)
    w = output_weights(cfg, params, cdt)
    if cfg.n_pred_heads > 1 and not all_heads:
        w = w[:, :cfg.vocab_size]
    logits = jnp.einsum(
        "bsd,dv->bsv", x, w, preferred_element_type=jnp.float32,
    )
    if cfg.logit_softcap is not None:
        logits = softcap(logits, cfg.logit_softcap)
    if cfg.n_pred_heads > 1 and all_heads:
        logits = logits.reshape(*logits.shape[:2], cfg.n_pred_heads,
                                cfg.vocab_size)
    return logits


def forward_with_cache(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,  # (B, S) int32 — new tokens only
    cache,  # KVCache
    *,
    new_tokens_len: Optional[jax.Array] = None,  # (B,) — valid count in `tokens`
    mesh=None,
    fresh_cache: bool = False,
    attn_impl: str = "auto",
    logits_at: Optional[jax.Array] = None,  # (B,) — the one row to unembed
):
    """Incremental forward: consumes `tokens` starting at cache.lengths.

    Returns (logits (B, S, V) fp32, updated KVCache). Used for both
    prefill (S = padded prompt length, empty cache, new_tokens_len =
    actual prompt lengths) and decode (S = 1). Writes land at each
    sequence's own length, so ragged batches decode with continuous
    positions and pads never pollute later steps.

    logits_at (B,) unembeds that row of each sequence alone and returns
    logits (B, 1, V): a prompt chunk that only samples from its last
    position has no use for S rows over the vocabulary (at S = 4096 and
    151,936 words they are 2.5 GB of float32 and a tenth of the chunk).

    fresh_cache=True (prefill into an all-empty cache) attends within
    the incoming chunk instead of over the max_len buffer — quadratic
    not rectangular, and flash-eligible via attn_impl="auto".
    """
    from shellac_tpu.inference.kvcache import (
        EvaKVCache,
        PagedKVCache,
        PatternedKVCache,
        QuantKVCache,
        QuantPagedKVCache,
        QuantPatternedKVCache,
        QuantRollingKVCache,
        RollingKVCache,
        kv_field_names,
    )

    if not cfg.causal:
        raise ValueError(
            "KV-cache generation requires a causal model (cfg.causal=True)"
        )
    eva = isinstance(cache, EvaKVCache)
    if eva != (cfg.eva is not None):
        raise ValueError(
            "an EVA model (cfg.eva) keeps EVA state and nothing else "
            "does: serve it on the 'eva' cache backend (inference/cache), "
            f"not with a {type(cache).__name__}"
        )
    paged = isinstance(cache, (PagedKVCache, QuantPagedKVCache))
    quant = isinstance(
        cache, (QuantKVCache, QuantPagedKVCache, QuantRollingKVCache,
                QuantPatternedKVCache)
    )
    rolled = isinstance(cache, (RollingKVCache, QuantRollingKVCache))
    mixed = isinstance(cache, (PatternedKVCache, QuantPatternedKVCache))
    if (rolled or mixed) and cfg.attn_window is None:
        raise ValueError("rolling cache on a model without attn_window")
    if mixed and cfg.attn_pattern is None:
        raise ValueError("patterned cache on a model without attn_pattern")
    cdt = cfg.compute_dtype
    b, s = tokens.shape
    index = cache.lengths  # (B,)
    if eva and not fresh_cache and s != 1:
        # A traced index is the caller's to align (the 'eva' backend's
        # prefill loop); a concrete one is checked here.
        w = cfg.eva.window
        if s != w or (not isinstance(index, jax.core.Tracer)
                      and bool(jnp.any(index % w))):
            raise NotImplementedError(
                "EVA state continues one row at a time, or one whole "
                f"window of {w} rows at a window's first position: a "
                f"cached chunk of {s} rows (chunked prefill, a speculative "
                "verify window) would need the pooled rows of chunks that "
                "complete inside it"
            )
    positions = index[:, None] + jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32), (b, s)
    )
    cos, sin = rope_angles(positions, cfg.rope_dim, cfg.rope_theta,
                           yarn=cfg.rope_yarn, llama3=cfg.rope_llama3,
                           linear=cfg.rope_linear)
    if cfg.rope_local_theta is not None:
        cos_l, sin_l = rope_angles(
            positions, cfg.rope_dim, cfg.rope_local_theta
        )
    else:
        cos_l = sin_l = None
    if cfg.dsa is not None:
        cos, sin = dsa_rope_tables(cfg, positions, cos, sin)

    x = _embed_tokens(cfg, params, tokens, cdt, mesh=mesh)
    x = constrain(x, mesh, ("batch", "seq", None))

    def block(x, lp, state, moe_layer, attn_kind, **kw):
        """One layer over `state`, the (k, v) of its kind of cache."""
        local = cos_l is not None and attn_kind == "window"
        return _block(
            cfg, mesh, attn_impl, x, lp,
            cos_l if local else cos, sin_l if local else sin,
            cache=(*state, index, positions), fresh_cache=fresh_cache,
            moe_layer=moe_layer, attn_kind=attn_kind,
            new_len=new_tokens_len, **kw,
        )

    # Cache leaves, values only (bf16) or values + scale stacks (int8):
    # one step a kind of state serves both, handing the scales to the
    # block when they are there.
    if mixed:
        w_names = ("kw", "vw", "kws", "vws") if quant else ("kw", "vw")
        f_names = ("kf", "vf", "kfs", "vfs") if quant else ("kf", "vf")
        names = w_names + f_names
    elif eva:
        names = ("k", "v", "pk", "pv")
    else:
        names = kv_field_names("int8" if quant else None)
        if cfg.dsa is not None:
            if not isinstance(cache, PagedKVCache) or cache.idx is None:
                raise ValueError(
                    "a model with an indexer (cfg.dsa) keeps one index "
                    "key a token beside its k and v rows: it serves on a "
                    "PagedKVCache that holds the third pool (the 'paged' "
                    f"cache backend), not on a {type(cache).__name__}"
                )
            names = names + ("idx",)
    cleaves = tuple(getattr(cache, n) for n in names)

    from shellac_tpu.ops.moe import experts_plain, sorted_kernel_runs

    layers = params["layers"]
    walk = functools.partial(
        scan_layers, cfg, layers,
        experts_whole=(
            expert_ffn_path(cfg, layers, cached=not fresh_cache, mesh=mesh)
            == "sorted"
            and sorted_kernel_runs(mesh)
            and experts_plain(_expert_stack(layers), cdt)
        ),
    )

    if eva:
        # Both kinds of EVA state ride the layer loop whole, as carries,
        # as the paged pool does below; a layer writes and reads its own
        # slice of each stack. Which pages a row attends is the same in
        # every layer: those of its completed windows.
        n_blocks = cache.pk.shape[2]
        done = index // cfg.eva.window  # completed windows a row
        owned = jnp.any(
            (cache.tables[:, :, None]
             == jnp.arange(n_blocks, dtype=jnp.int32))
            & (jnp.arange(cache.max_blocks, dtype=jnp.int32)[None, :, None]
               < done[:, None, None]),
            axis=1,
        )

        def step(carry, lp, li, xs_l, moe_layer, attn_kind):
            x, ring, pool = carry
            x, (ring, pool), _ = block(
                x, lp, (ring, pool), moe_layer, attn_kind,
                page_tables={"layer": li, "slots": cache.slots,
                             "tables": cache.tables, "owned": owned,
                             "done": done},
            )
            return (x, ring, pool), None

        def one_pass(x, state, first, _):
            (x, ring, pool), _ = walk((x, *state), step, first=first)
            return x, (ring, pool), None

        state, xs = (cleaves[:2], cleaves[2:]), None

        def finish(state, _):
            return state[0] + state[1]
    elif paged:
        # A paged pool rides the layer loops as a CARRY, never as xs/ys:
        # the stacked (L, n_blocks, ...) pools are viewed as
        # (L * n_blocks, ...) (a bitcast) and each block writes its rows
        # in place and reads through the tables offset to its layer's
        # blocks. No layer's pool is sliced out or restacked, and the
        # donated buffers come back where they came in
        # (tests/test_paged_inplace.py holds the compiled program to it).
        # A looped stack's pool holds n_layers entries a pass
        # (cfg.cache_layers) and rides every pass as the same carry:
        # pass t's layers are first = t * n_layers onward.
        n_blocks = cache.k.shape[1]
        # k and v, and with an indexer the index keys: the rest are an
        # int8 pool's scales.
        n_state = 3 if cfg.dsa is not None else 2

        def step(carry, lp, li, xs_l, moe_layer, attn_kind):
            x, pools = carry
            x, pools, _ = block(
                x, lp, pools[:n_state], moe_layer, attn_kind,
                kv_scales=pools[n_state:] or None,
                page_tables=cache.tables + li * n_blocks,
            )
            return (x, pools), None

        def one_pass(x, pools, first, _):
            (x, pools), _ = walk((x, pools), step, first=first)
            return x, pools, None

        state, xs = tuple(
            a.reshape(a.shape[0] * n_blocks, *a.shape[2:]) for a in cleaves
        ), None

        def finish(pools, _):
            return tuple(p.reshape(a.shape) for p, a in zip(pools, cleaves))
    else:
        # Slot caches ride as xs/ys, each layer its own rows. The mixed
        # ring/dense caches hold one set of stacks a kind: "window"
        # layers take ring rows (rolled update + rolled read), "full"
        # layers dense rows (the decode kernel's path).
        def step(x, lp, li, vals, moe_layer, attn_kind):
            x, new, _ = block(
                x, lp, vals[:2], moe_layer, attn_kind,
                kv_scales=vals[2:] or None,
                rolled=rolled or (mixed and attn_kind == "window"),
            )
            return x, new

        def one_pass(x, state, first, xs):
            x, news = walk(x, step, xs=xs, first=first)
            return x, state, news

        state, xs = None, cleaves
        if mixed:
            half = len(cleaves) // 2
            xs = {"window": cleaves[:half], "full": cleaves[half:]}

        def finish(_, news):
            return news["window"] + news["full"] if mixed else news

    if cfg.loop is None:
        x, state, ys = one_pass(x, state, 0, xs)
    else:
        # One compiled body for the passes. A slot cache's stacks hold
        # a pass's layers together, (steps * n_layers, ...) viewed
        # (steps, n_layers, ...), and ride the passes as xs / ys.
        steps = cfg.loop.steps
        x, state, ys, _ = loop_passes(
            cfg, params, x, one_pass, state, mesh=mesh,
            xs=jax.tree.map(
                lambda a: a.reshape(steps, a.shape[0] // steps,
                                    *a.shape[1:]), xs),
        )
        ys = jax.tree.map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), ys
        )
    news = finish(state, ys)

    if logits_at is not None:
        x = jnp.take_along_axis(
            x, logits_at.astype(jnp.int32)[:, None, None], axis=1)
    logits = unembed(cfg, params, x, mesh=mesh, normed=cfg.loop is not None)
    if new_tokens_len is None:
        new_lengths = index + s
    else:
        new_lengths = index + new_tokens_len.astype(jnp.int32)
    new_cache = cache.replace(**dict(zip(names, news)), lengths=new_lengths)
    return logits, new_cache


def num_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
