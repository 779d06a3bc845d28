"""Mixture-of-experts routing and expert FFN.

TPU-first choices:
  - Static shapes everywhere, in both forms of the expert FFN.
    Capacity buckets (tokens over capacity are dropped, standard
    Switch/GShard semantics) for training, sized at the worst case
    where a call on a mesh must not drop; the T*k routed rows sorted
    by expert and multiplied as grouped GEMMs wherever a call must not
    drop (MoEConfig.dropless, every cached continuation) and one
    device runs the program. `moe_ffn_path` is the one place that
    chooses.
  - Scatter/gather dispatch (`.at[slot].add`, `take`): O(T·D) HBM
    traffic, instead of the classic one-hot dispatch einsum whose
    T·E·C·D MXU cost dwarfs the expert matmuls at long sequence.
  - Bucketed expert FFNs run as one batched einsum over the expert
    axis, sharded over the mesh's (ep, fsdp) axes; GSPMD inserts the
    collectives.
  - Expert parallelism is pure sharding: the dispatched capacity
    buckets (E, C, D) are constrained to shard E over the ep axis, so
    the scatter that builds them reshards token-sharded activations to
    expert-sharded buckets — that resharding IS the all-to-all, chosen
    by XLA (an explicit shard_map ppermute would hand-schedule what
    GSPMD already lays out). The expert FFN einsums are then local to
    each ep group, and the combine gather reshards back.
  - The sorted form's kernel reads a layer's experts where they lie in
    the (L, E, ...) stack (`StackRow`): inside a layer loop a kernel
    operand sliced out of the stack is a copy of the layer's expert
    weights, three times the bytes the layer has to read.
  - Router math in fp32, with load-balance and router-z auxiliary losses.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from shellac_tpu.config import MoEConfig
from shellac_tpu.ops.dispatch import on_mesh, pallas_supported
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


class StackRow(NamedTuple):
    """Layer `row` of a per-layer weight stack, not sliced out.

    Inside a layer loop on the TPU a kernel's operand cannot be a view
    into the stack: `stack[row]` in front of the grouped-matmul kernel
    is a copy of the layer's expert weights (written, then read again:
    three times the bytes the layer has to read). The kernel instead
    takes the whole (L, E, ...) stack as L*E groups of which this
    layer's alone are non-empty, and reads those where they lie. The
    one layer walk (`transformer.scan_layers(experts_whole=True)`)
    hands the expert weights over in this form.
    """

    stack: jax.Array  # (L, E, in, out), compute dtype
    row: jax.Array  # () int32, traced: it rides the layer scan


#: The expert weight stacks a StackRow can stand for.
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def experts_plain(layer, compute_dtype) -> bool:
    """Can the grouped kernel read this layer's (or stack's) expert
    weights as they lie, in the compute dtype? A quantized or wider
    master copy is rewritten in front of any matmul, and the bucket
    einsums fuse that into their operand read."""
    return all(
        getattr(layer[n], "stack", layer[n]).dtype == compute_dtype
        for n in EXPERT_STACKS
    )


def moe_ffn_path(cfg: MoEConfig, *, cached: bool = False, mesh=None,
                 plain: bool = True) -> str:
    """The form one call of the expert FFN takes: the rule, for the
    model that makes the call and for whoever wants to know what the
    model will do (the engine's `prefill_sorted_tokens` count).

      "capacity"  per-expert capacity buckets; overflow is dropped.
                  Fresh calls (training, whole-prompt prefill) of a
                  config that asks for nothing else.
      "sorted"    the T*k routed rows sorted by expert, grouped GEMMs
                  (`moe_ffn_grouped`). Where the call must not drop
                  (cfg.dropless; `cached`: a decode tick, a chunk or
                  suffix of a prompt, a verify window), the program
                  runs on one device and the weights are `plain`; and
                  wherever cfg.grouped_dropless asks for it in place
                  of capacity buckets.
      "buckets"   buckets sized at the worst case, T rows an expert:
                  exact as well, E*T rows of work for T*k routed. What
                  a must-not-drop call keeps on a mesh (ragged groups
                  cannot shard over ep, GSPMD cannot partition the
                  kernel, and XLA's own grouped matmul ran a decode
                  tick's rows 4x slower than the buckets: PERF.md, PR
                  30) and over quantized experts.

    There is no threshold on T: on a v5e at DeepSeek-V2-Lite's widths
    the sorted form ties the buckets at 64 rows (1.68 ms a layer both)
    and is 6x faster at 2,048 (PERF.md, PR 30).
    """
    if cfg.grouped_dropless and not cached:
        return "sorted"
    if not (cached or cfg.dropless):
        return "capacity"
    return "buckets" if on_mesh(mesh) or not plain else "sorted"


def sorted_kernel_runs(mesh=None) -> bool:
    """Does "sorted" run as the compiled kernel here (Pallas compiles,
    and GSPMD, which cannot partition a Mosaic kernel, is not
    partitioning the program)? The layer walk then hands the expert
    stacks over whole (`StackRow`)."""
    return pallas_supported() and not on_mesh(mesh)


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
    """The bucket forms. Returns (out (B, S, D), aux_loss scalar,
    metrics).

    drop_tokens=False sizes capacity at T (worst case: every token's
    top-1 on one expert) so nothing ever drops: `moe_ffn_path`'s
    "buckets", the exact form a mesh keeps. It builds and multiplies
    E*T rows for T*k routed ones; one device runs `moe_ffn_grouped`.
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

    Token assignments sort by expert id and each expert's contiguous
    segment is one group of a grouped matmul. No capacity buckets
    exist, so nothing can drop: `moe_dropped_frac == 0` by
    construction, and the work and the memory are those of a dense MLP
    over the T*k assignments, at a decode tick's rows and at a
    training batch's.

    Two kernels, one computation (bf16 operands, float32 accumulation):
    expert weights given as `StackRow`s go through the megablox Pallas
    kernel, the stack whole (`sorted_kernel_runs` says where: compiled
    Pallas, one device); sliced (E, in, out) weights go through
    `jax.lax.ragged_dot`, which differentiates, partitions and runs on
    the CPU.

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
    with jax.named_scope("moe.route"):
        expert_idx, weight, aux, metrics = _route_scores(
            x2, w_router, cfg, b_router
        )
    with jax.named_scope("moe.sort"):
        flat_e = expert_idx.reshape(-1)  # (T*k,)
        order = jnp.argsort(flat_e, stable=True)
        x_sorted = jnp.take(x2, order // k, axis=0)  # (T*k, D) by expert
        seg_e = jnp.take(flat_e, order)  # sorted expert id per row
        group_sizes = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)

    def gdot(lhs, w):
        if isinstance(w, StackRow):
            return _gmm_in_stack(lhs, w, group_sizes)
        return jax.lax.ragged_dot(
            lhs, materialize(w, cdt), group_sizes,
            preferred_element_type=jnp.float32,
        ).astype(cdt)

    with jax.named_scope("moe.gemm"):
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

    with jax.named_scope("moe.combine"):
        # Unsort and combine with router weights. (Places from a
        # one-hot cumsum in place of this second sort, and `order` by
        # a permutation scatter, were timed on the chip and bought
        # nothing: PERF.md, PR 30.)
        inv = jnp.argsort(order)
        out_assign = jnp.take(down, inv, axis=0).reshape(t, k, d)
        combined = jnp.sum(
            out_assign * weight[..., None].astype(cdt), axis=1
        )
    metrics = dict(metrics, moe_dropped_frac=jnp.zeros((), jnp.float32))
    return combined.reshape(b, s, d), aux, metrics


#: Rows of one grouped-matmul tile: the bf16 MXU pass, and the most an
#: expert's segment can waste (a decode tick holds ~6 rows an expert).
_GMM_ROWS = 128
#: Bytes of one weight tile. The kernel streams each visited expert's
#: matrix through VMEM in tiles of (in, out) = (tk, tn), double
#: buffered; few large tiles keep its grid steps from costing more
#: than their DMAs. 3 MiB is the tiling that tied the bucket einsums
#: at 64 rows and ran 2,048 rows in 3.7 ms a layer (PERF.md, PR 30:
#: 128-wide tiles took 11-29 ms, 5.5-MiB ones lost 0.1 ms at 64 rows).
_GMM_TILE_BYTES = 3 << 20


def _gmm_tiling(k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) for a grouped matmul of (m, k) rows by (k, n)
    experts: the whole contraction where it fits 2,048, and as many
    128-lane output columns as `_GMM_TILE_BYTES` holds."""
    tk = k if k <= 2048 else 1024
    tn = max(128, _GMM_TILE_BYTES // (tk * itemsize) // 128 * 128)
    return _GMM_ROWS, tk, min(tn, n)


def _gmm_in_stack(lhs: jax.Array, w: StackRow,
                  group_sizes: jax.Array) -> jax.Array:
    """lhs (M, in), rows sorted by expert, times layer `w.row`'s
    experts inside the whole stack: (M, out) in lhs's dtype, float32
    accumulation. The stack (L, E, in, out) is L*E groups (a bitcast)
    and the group sizes are zero outside this layer's E: the kernel
    visits non-empty groups only, so it reads this layer's routed
    experts and nothing is sliced out. Rows are padded to whole tiles;
    the kernel leaves rows past the last group unwritten."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    n_layers, e = w.stack.shape[:2]
    rhs = w.stack.reshape(n_layers * e, *w.stack.shape[2:])
    in_stack = jax.lax.dynamic_update_slice(
        jnp.zeros((n_layers * e,), jnp.int32), group_sizes, (w.row * e,)
    )
    m = lhs.shape[0]
    pad = -m % _GMM_ROWS
    out = gmm(
        jnp.pad(lhs, ((0, pad), (0, 0))), rhs, in_stack,
        preferred_element_type=lhs.dtype,
        tiling=_gmm_tiling(rhs.shape[1], rhs.shape[2], lhs.dtype.itemsize),
    )
    return out[:m]
