"""Mixture-of-experts routing and expert FFN.

TPU-first choices:
  - Static shapes everywhere: per-expert capacity buckets (tokens over
    capacity are dropped, standard Switch/GShard semantics), so the
    whole layer jits with no data-dependent shapes.
  - Scatter/gather dispatch (`.at[slot].add`, `take`): O(T·D) HBM
    traffic, instead of the classic one-hot dispatch einsum whose
    T·E·C·D MXU cost dwarfs the expert matmuls at long sequence.
  - Expert FFNs run as one batched einsum over the expert axis, sharded
    over the mesh's (ep, fsdp) axes; GSPMD inserts the collectives.
  - Expert parallelism is pure sharding: the dispatched capacity
    buckets (E, C, D) are constrained to shard E over the ep axis, so
    the scatter that builds them reshards token-sharded activations to
    expert-sharded buckets — that resharding IS the all-to-all, chosen
    by XLA (an explicit shard_map ppermute would hand-schedule what
    GSPMD already lays out). The expert FFN einsums are then local to
    each ep group, and the combine gather reshards back.
  - Router math in fp32, with load-balance and router-z auxiliary losses.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from shellac_tpu.config import MoEConfig
from shellac_tpu.ops.quant import materialize
from shellac_tpu.parallel.sharding import constrain


def expert_capacity(cfg: MoEConfig, num_tokens: int) -> int:
    cap = int(cfg.capacity_factor * num_tokens * cfg.num_experts_per_token
              / cfg.num_experts)
    return max(cap, 1)


def _group_mask(choice: jax.Array, cfg: MoEConfig, group_rank) -> jax.Array:
    """Zero out experts outside the top `topk_group` groups.

    `group_rank` ranks each group from its members' scores — max for
    softmax (V2), top-2 sum for sigmoid (V3) — matching each HF gate.
    """
    t, e = choice.shape
    g = cfg.n_group
    group_scores = group_rank(choice.reshape(t, g, e // g))
    _, gidx = jax.lax.top_k(group_scores, cfg.topk_group)
    gmask = jnp.zeros((t, g), choice.dtype).at[
        jnp.arange(t)[:, None], gidx
    ].set(1.0)
    return choice * jnp.repeat(gmask, e // g, axis=1)


def _route_scores(
    x: jax.Array,  # (T, D) — flattened tokens
    w_router: jax.Array,  # (D, E)
    cfg: MoEConfig,
    b_router: jax.Array | None = None,  # (E,) sigmoid selection bias
) -> Tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Gate scoring shared by the capacity-bucket and grouped paths:
    returns (expert_idx (T, k) int32, weight (T, k) fp32, aux_loss
    scalar, metrics dict WITHOUT a drop fraction — dropping is the
    capacity path's business)."""
    e, k = cfg.num_experts, cfg.num_experts_per_token

    logits = jnp.einsum(
        "td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32)
    )
    if cfg.scoring == "softmax_topk" and b_router is not None:
        # GPT-OSS router bias is part of the logits proper (selection
        # AND weights AND the aux losses see it).
        logits = logits + b_router.astype(jnp.float32)[None]
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E) — also feeds aux
    if cfg.scoring == "softmax_topk":
        # GPT-OSS gate: top-k over RAW logits, softmax over just the
        # kept values (not a renormalized slice of the full softmax —
        # the dropped logits never enter the denominator).
        top_vals, expert_idx = jax.lax.top_k(logits, k)
        weight = jax.nn.softmax(top_vals, axis=-1)
    elif cfg.scoring == "sigmoid":
        # DeepSeek-V3 gate: sigmoid scores; an additive per-expert bias
        # steers SELECTION only (load balancing knob trained outside
        # the gradient), combine weights come from the raw scores.
        scores = jax.nn.sigmoid(logits)
        choice = scores + (b_router.astype(jnp.float32)[None]
                           if b_router is not None else 0.0)
        if cfg.n_group > 1:
            choice = _group_mask(
                choice, cfg,
                lambda gsc: jnp.sum(jax.lax.top_k(gsc, 2)[0], axis=-1),
            )
        _, expert_idx = jax.lax.top_k(choice, k)
        weight = jnp.take_along_axis(scores, expert_idx, axis=-1)
        if cfg.norm_topk_prob:
            weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    else:
        probs_sel = probs
        if cfg.n_group > 1:
            # V2's group rank is the max member probability.
            probs_sel = _group_mask(
                probs, cfg, lambda gsc: jnp.max(gsc, axis=-1)
            )
        weight, expert_idx = jax.lax.top_k(probs_sel, k)  # (T, k)
        if cfg.norm_topk_prob:
            # Renormalize the kept probabilities to sum to 1.
            weight = weight / jnp.maximum(
                jnp.sum(weight, -1, keepdims=True), 1e-9
            )
    weight = weight * cfg.routed_scaling_factor

    # Load-balance loss (Switch §2.2 form): E * sum_e f_e * p_e where
    # f_e = fraction of tokens whose top-1 is e, p_e = mean router prob.
    top1 = jax.nn.one_hot(expert_idx[:, 0], e, dtype=jnp.float32)
    f = jnp.mean(top1, axis=0)
    p = jnp.mean(probs, axis=0)
    balance_loss = e * jnp.sum(f * p)
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = (cfg.router_aux_loss_weight * balance_loss
           + cfg.router_z_loss_weight * z_loss)
    metrics = {
        "moe_balance_loss": balance_loss,
        "moe_router_z_loss": z_loss,
    }
    return expert_idx, weight, aux, metrics


def route(
    x: jax.Array,  # (T, D) — flattened tokens
    w_router: jax.Array,  # (D, E)
    cfg: MoEConfig,
    capacity: int | None = None,
    b_router: jax.Array | None = None,  # (E,) sigmoid selection bias
) -> Tuple[jax.Array, jax.Array, jax.Array, dict]:
    """Top-k routing with capacity buckets.

    Returns (slot (T, k) int32 — flat index into E*C, or E*C when
    dropped/overflow; weight (T, k) fp32 combine weights; aux_loss
    scalar; metrics dict).
    """
    t, _ = x.shape
    e = cfg.num_experts
    c = expert_capacity(cfg, t) if capacity is None else capacity
    expert_idx, weight, aux, metrics = _route_scores(
        x, w_router, cfg, b_router
    )

    # Position of each assignment within its expert, in token order:
    # cumsum over the one-hot assignment matrix (T*k, E).
    flat_expert = expert_idx.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)  # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1  # position per expert
    pos_in_expert = jnp.take_along_axis(pos, flat_expert[:, None], axis=1)[:, 0]
    ok = pos_in_expert < c
    slot = jnp.where(ok, flat_expert * c + pos_in_expert, e * c)  # overflow -> E*C
    k = expert_idx.shape[1]
    slot = slot.reshape(t, k).astype(jnp.int32)

    dropped = jnp.mean(1.0 - ok.reshape(t, k).astype(jnp.float32))
    metrics = dict(metrics, moe_dropped_frac=dropped)
    return slot, weight, aux, metrics


def _check_expert_shards(e: int, mesh) -> None:
    if mesh is None:
        return
    from shellac_tpu.parallel.mesh import AXIS_EXPERT, AXIS_FSDP

    shards = mesh.shape.get(AXIS_EXPERT, 1) * mesh.shape.get(AXIS_FSDP, 1)
    if e % shards:
        raise ValueError(
            f"num_experts={e} must divide evenly over the expert "
            f"shards (ep*fsdp={shards}); uneven splits silently "
            "pad and waste MXU time"
        )


def _expert_act(gate: jax.Array, up: jax.Array, cfg: MoEConfig):
    """Pre-activation clamp + gated activation, shared by the bucket
    and grouped paths so their math cannot drift (the grouped-vs-
    bucket parity test depends on it)."""
    if cfg.gate_limit is not None:
        # GPT-OSS clamps pre-activation: gate one-sided to limit, up
        # symmetric.
        lim = cfg.gate_limit
        gate = jnp.clip(gate, None, lim)
        up = jnp.clip(up, -lim, lim)
    if cfg.expert_act == "gptoss":
        # glu = gate * sigmoid(1.702 * gate); output (up + 1) * glu.
        return (up + 1.0) * (gate * jax.nn.sigmoid(1.702 * gate))
    return jax.nn.silu(gate) * up


def moe_ffn(
    x: jax.Array,  # (B, S, D) compute dtype
    w_router: jax.Array,  # (D, E)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    cfg: MoEConfig,
    *,
    drop_tokens: bool = True,
    mesh=None,
    b_router: jax.Array | None = None,
    b_gate: jax.Array | None = None,  # (E, F)
    b_up: jax.Array | None = None,  # (E, F)
    b_down: jax.Array | None = None,  # (E, D)
) -> Tuple[jax.Array, jax.Array, dict]:
    """Returns (out (B, S, D), aux_loss scalar, metrics).

    drop_tokens=False sizes capacity at T (worst case: every token's
    top-1 on one expert) so nothing ever drops — required for decode,
    where a capacity drop would silently zero a token's FFN output and
    make generation diverge from prefill. Only safe for small T.
    """
    b, s, d = x.shape
    e = cfg.num_experts
    t = b * s
    c = expert_capacity(cfg, t) if drop_tokens else t
    cdt = x.dtype
    _check_expert_shards(e, mesh)

    x2 = x.reshape(t, d)
    with jax.named_scope("moe.route"):
        slot, weight, aux, metrics = route(
            x2, w_router, cfg, capacity=c, b_router=b_router
        )
    k = slot.shape[1]

    with jax.named_scope("moe.sort"):
        # Scatter tokens into capacity buckets; one extra slot absorbs
        # drops.
        buckets = jnp.zeros((e * c + 1, d), cdt)
        flat_slot = slot.reshape(-1)  # (T*k,)
        x_rep = jnp.repeat(x2, k, axis=0)  # (T*k, D) — one per assignment
        buckets = buckets.at[flat_slot].add(x_rep, mode="drop")
        # Dispatch boundary: constrain the buckets to expert sharding.
        # The scatter's input is token-sharded (batch over dp/fsdp, seq
        # over sp); forcing its output onto the ep axis here is what
        # makes XLA emit the token all-to-all instead of replicating
        # the buckets.
        dispatched = constrain(
            buckets[: e * c].reshape(e, c, d), mesh,
            ("experts", None, None)
        )

    with jax.named_scope("moe.gemm"):
        # Expert FFNs: batched over the expert axis (sharded over
        # 'fsdp').
        gate = jnp.einsum("ecd,edf->ecf", dispatched,
                          materialize(w_gate, cdt),
                          preferred_element_type=jnp.float32).astype(cdt)
        up = jnp.einsum("ecd,edf->ecf", dispatched, materialize(w_up, cdt),
                        preferred_element_type=jnp.float32).astype(cdt)
        if b_gate is not None:
            gate = gate + b_gate.astype(cdt)[:, None, :]
        if b_up is not None:
            up = up + b_up.astype(cdt)[:, None, :]
        act = _expert_act(gate, up, cfg)
        act = constrain(act, mesh, ("experts", None, "mlp"))
        out_e = jnp.einsum("ecf,efd->ecd", act, materialize(w_down, cdt),
                           preferred_element_type=jnp.float32).astype(cdt)
        out_e = constrain(out_e, mesh, ("experts", None, None))
        if b_down is not None:
            # The per-expert output bias applies to every ROUTED token's
            # expert output (dropped tokens still get zeros downstream).
            out_e = out_e + b_down.astype(cdt)[:, None, :]

    with jax.named_scope("moe.combine"):
        # Gather back and combine with router weights (dropped -> zeros
        # row).
        out_flat = jnp.concatenate([out_e.reshape(e * c, d),
                                    jnp.zeros((1, d), cdt)], axis=0)
        gathered = jnp.take(out_flat, flat_slot, axis=0).reshape(t, k, d)
        combined = jnp.sum(gathered * weight[..., None].astype(cdt), axis=1)
    return combined.reshape(b, s, d), aux, metrics


def moe_ffn_grouped(
    x: jax.Array,  # (B, S, D) compute dtype
    w_router: jax.Array,  # (D, E)
    w_gate: jax.Array,  # (E, D, F)
    w_up: jax.Array,  # (E, D, F)
    w_down: jax.Array,  # (E, F, D)
    cfg: MoEConfig,
    *,
    mesh=None,
    b_router: jax.Array | None = None,
    b_gate: jax.Array | None = None,  # (E, F)
    b_up: jax.Array | None = None,  # (E, F)
    b_down: jax.Array | None = None,  # (E, D)
) -> Tuple[jax.Array, jax.Array, dict]:
    """DROPLESS MoE via grouped (sorted-segment) expert matmuls.

    Token assignments sort by expert id; each expert's contiguous
    segment feeds `jax.lax.ragged_dot` (XLA's grouped matmul, which
    Mosaic lowers to MXU-tiled per-group GEMMs on TPU). No capacity
    buckets exist, so nothing can drop: `moe_dropped_frac == 0` by
    construction — the loss-sensitive fine-tuning option the
    capacity-bucket path can't provide. Memory is O(T*k*F), the same
    as a dense MLP over the assignments, so it is training-viable at
    large T, unlike the capacity-at-T dropless buckets
    (MoEConfig.dropless), which exist for decode's tiny T.

    Sharding note: ragged group sizes are data-dependent, so the
    expert axis cannot shard the way the capacity buckets do — under
    an ep mesh GSPMD gathers the expert weights to each data shard.
    Correct everywhere (the ep dryrun runs it), but for ep-sharded
    THROUGHPUT training prefer the capacity path; grouped is for
    exactness-sensitive runs.
    """
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.num_experts_per_token
    t = b * s
    cdt = x.dtype
    _check_expert_shards(e, mesh)

    x2 = x.reshape(t, d)
    expert_idx, weight, aux, metrics = _route_scores(
        x2, w_router, cfg, b_router
    )
    flat_e = expert_idx.reshape(-1)  # (T*k,)
    order = jnp.argsort(flat_e, stable=True)
    x_sorted = jnp.take(x2, order // k, axis=0)  # (T*k, D) by expert
    seg_e = jnp.take(flat_e, order)  # sorted expert id per row
    group_sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)

    def gdot(lhs, rhs):
        return jax.lax.ragged_dot(
            lhs, materialize(rhs, cdt), group_sizes,
            preferred_element_type=jnp.float32,
        ).astype(cdt)

    gate = gdot(x_sorted, w_gate)
    up = gdot(x_sorted, w_up)
    if b_gate is not None:
        gate = gate + jnp.take(b_gate, seg_e, axis=0).astype(cdt)
    if b_up is not None:
        up = up + jnp.take(b_up, seg_e, axis=0).astype(cdt)
    act = _expert_act(gate, up, cfg)
    down = gdot(act, w_down)  # (T*k, D)
    if b_down is not None:
        down = down + jnp.take(b_down, seg_e, axis=0).astype(cdt)

    # Unsort and combine with router weights.
    inv = jnp.argsort(order)
    out_assign = jnp.take(down, inv, axis=0).reshape(t, k, d)
    combined = jnp.sum(out_assign * weight[..., None].astype(cdt), axis=1)
    metrics = dict(metrics, moe_dropped_frac=jnp.zeros((), jnp.float32))
    return combined.reshape(b, s, d), aux, metrics
