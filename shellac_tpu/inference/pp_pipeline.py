"""Token-level pipelined decode for pp-mesh serving.

Plain pp serving (GSPMD layer sharding, one batched tick in flight)
leaves pp-1 stages idle at every instant: decode is strictly
sequential through the stages, so pp buys KV/weight capacity while
wasting the chips it adds. This module removes the idle time the same
way the training pipeline does (parallel/pipeline.py) — not with
per-stage programs, but with ONE scanned GSPMD program over a stage
register:

  - the n_slots slot batch splits into pp contiguous GROUPS of
    G = n_slots/pp slots;
  - a register holds per-stage activations (pp, G, 1, D), sharded over
    the `pp` mesh axis like the (pp, L/pp, ...) reshaped layer stack
    and KV cache;
  - each MICROTICK, `jax.vmap` over the stage axis applies every
    stage's layer block to the group it currently holds — pp different
    groups advance one stage each, concurrently, on their own devices;
  - the register then rolls one stage (XLA: collective-permute over
    ICI): the group leaving stage pp-1 is sampled, and the group whose
    token was just sampled re-enters at stage 0 next microtick.

Steady-state stage utilization is 100%: at microtick t, stage s works
on group (t - s) mod pp. A decode window of K tokens per slot costs
pp*K + (pp-1) microticks (the pp-1 tail is the drain ramp), against
pp*K stage-sequential units for the unpipelined tick — and each
microtick runs all stages in parallel, so wall-clock per window
approaches (K + 1) stage-times instead of pp*K.

Scope: dense bf16, int8, and rolling-ring caches over uniform layer
stacks, plus patterned stacks (Gemma-2/3, GPT-OSS) over the dense
caches — each stage holds whole pattern periods and the kinds unroll
inside the stage scan with dual rope. Excluded: first_k_dense /
moe_every layouts, paged pools, and the mixed PatternedKVCache
(patterned + rolling). int8 scale stacks ride the same stage split;
ring wrap stays bit-exact because stale one-ahead writes alias only
positions outside every window. Each slot's math is row-for-row
identical to the unpipelined engine, so greedy output is bit-exact
(tests/test_pp_pipeline.py).

The reference repo for this project is empty (SURVEY.md §0); there is
no upstream pipelined-decoding implementation to cite. The schedule is
the classic round-robin token-level pipelining idea (public
literature: PipeDream-style weight-stationary decode), rebuilt for the
GSPMD/`lax.scan` compilation model.
"""

from __future__ import annotations

from typing import List, Optional

import jax

from shellac_tpu.config import ModelConfig
from shellac_tpu.models.transformer import (
    _block,
    _embed_tokens,
    rope_angles,
    scan_layers,
    unembed,
)
from shellac_tpu.parallel.sharding import constrain

# Logical axes for the stage-reshaped buffers: leading axis is the
# stage ("layers" -> pp in the shared rule table); the slot batch is
# replicated in serving (the scheduler owns it).
_REG_AXES = ("layers", None, None, None)


def pp_schedule(pp: int, ticks: int) -> List[dict]:
    """The static microtick schedule, for tests and docs.

    Returns one dict per microtick t of a K=`ticks` decode window:
      enter: group entering stage 0 (None once entries stop),
      exit:  group leaving stage pp-1 (None during warmup),
      stages: {stage: group} for every stage holding a LIVE token.

    Live means the token both entered at a real entry microtick and
    will exit within the window (drain-tail entries never exit; their
    cache writes land at each slot's next position and are overwritten
    by that token's real pass in the following window).
    """
    total = pp * ticks + pp - 1
    out = []
    for t in range(total):
        stages = {}
        for s in range(pp):
            entered_at = t - s
            if 0 <= entered_at < pp * ticks:
                stages[s] = entered_at % pp
        out.append({
            "enter": t % pp if t < pp * ticks else None,
            "exit": (t - (pp - 1)) % pp if t >= pp - 1 else None,
            "stages": stages,
        })
    return out


def stage_split(tree, pp: int):
    """Reshape every (L, ...) leaf to (pp, L/pp, ...)."""
    return jax.tree.map(
        lambda a: a.reshape(pp, a.shape[0] // pp, *a.shape[1:]), tree
    )


def stage_merge(tree):
    """Inverse of stage_split: (pp, Lp, ...) -> (L, ...)."""
    return jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]), tree
    )


def embed_group(cfg: ModelConfig, params, tokens, mesh):
    """Embed one group's next tokens: (G,) int32 -> (G, 1, D)."""
    return _embed_tokens(
        cfg, params, tokens[:, None], cfg.compute_dtype, mesh=mesh
    )


def head_logits(cfg: ModelConfig, params, y):
    """Final norm + unembedding on one group's exit activations.

    y: (G, 1, D) -> (G, V) fp32. Defers to the SHARED model tail
    (transformer.unembed) so per-row logits are bit-identical to the
    unpipelined tick by construction.
    """
    return unembed(cfg, params, y)[:, 0]


def stage_apply(
    cfg: ModelConfig,
    mesh,
    attn_impl: str,
    stage_params,  # pytree, leaves (pp, Lp, ...)
    cache_st,  # tuple of stage-split cache stacks, batch at axis 2:
               # (k, v) bf16 — (pp, Lp, B, Hkv, len, Dh) — or
               # (k, v, ks, vs) int8, scale stacks (pp, Lp, B, Hkv, len)
    stage_x,  # (pp, G, 1, D)
    stage_pos,  # (pp, G) int32 — this token's write position
    stage_gstart,  # (pp,) int32 — first slot of the group each stage holds
    rolled: bool = False,
):
    """One pipelined microtick: every stage runs its layer block on the
    group it holds. Returns (outputs (pp, G, 1, D), cache_st). With
    int8 stacks the per-layer scales thread into _block exactly as the
    unpipelined quant scan does, so quantize-at-write stays per-row
    identical. rolled=True threads ring-buffer semantics (position p
    writes slot p mod ring); the drain-tail and warmup stale writes
    land one position AHEAD of the final lengths, whose ring slot
    aliases a position already outside every attention window (ring
    >= window + slack), so the dense self-healing argument holds on
    the ring too."""
    G = stage_x.shape[1]

    def one_stage(sp, blocks, x, pos, gstart):
        slices = tuple(
            jax.lax.dynamic_slice_in_dim(b, gstart, G, axis=1)
            for b in blocks
        )
        positions = pos[:, None]
        cos, sin = rope_angles(
            positions, cfg.rope_dim, cfg.rope_theta,
            yarn=cfg.rope_yarn, llama3=cfg.rope_llama3,
            linear=cfg.rope_linear,
        )
        if cfg.rope_local_theta is not None:
            # Dual rope (Gemma-3): window layers use the local theta.
            cos_l, sin_l = rope_angles(
                positions, cfg.rope_dim, cfg.rope_local_theta
            )
        else:
            cos_l = sin_l = None

        def step(xx, lp, li, vals, moe_layer, kind):
            local = cos_l is not None and kind == "window"
            xx, nc, _ = _block(
                cfg, mesh, attn_impl, xx, lp,
                cos_l if local else cos, sin_l if local else sin,
                cache=(vals[0], vals[1], pos, positions),
                kv_scales=vals[2:] or None, moe_layer=moe_layer,
                attn_kind=kind, rolled=rolled,
            )
            return xx, nc

        # The shared walk over this stage's layers, its cache slices
        # riding as xs. A patterned stage starts at pattern phase 0
        # (validate_pp_pipeline enforces Lp % period == 0), so the
        # period walk applies to the chunk as it does to the full stack.
        x, news = scan_layers(cfg, sp, x, step, xs=slices)
        blocks = tuple(
            jax.lax.dynamic_update_slice_in_dim(b, n, gstart, axis=1)
            for b, n in zip(blocks, news)
        )
        return x, blocks

    return jax.vmap(one_stage)(
        stage_params, cache_st, stage_x, stage_pos, stage_gstart
    )


def constrain_register(x, mesh):
    return constrain(x, mesh, _REG_AXES)


def validate_pp_pipeline(cfg: ModelConfig, mesh, n_slots: int,
                         kv_quant: Optional[str], rolling: bool,
                         swaps_cache: bool) -> int:
    """Checks the pp_pipeline=True configuration; returns pp."""
    from shellac_tpu.models.transformer import first_k_layout, grouped_moe

    if mesh is None or dict(mesh.shape).get("pp", 1) < 2:
        raise ValueError(
            "pp_pipeline needs a mesh with pp >= 2 (token-level "
            "pipelining staggers slot groups across pipeline stages)"
        )
    pp = dict(mesh.shape)["pp"]
    if swaps_cache:
        raise ValueError(
            "pp_pipeline is a dense-cache feature; the paged engine's "
            "block pools do not reshape into per-stage registers yet"
        )
    if first_k_layout(cfg) or grouped_moe(cfg):
        raise ValueError(
            "pp_pipeline needs a uniformly-stacked layer tree (no "
            "first_k_dense or moe_every layouts)"
        )
    if cfg.attn_pattern is not None and rolling:
        raise ValueError(
            "pp_pipeline on patterned models needs the DENSE cache: "
            "rolling_window would use the mixed ring/dense "
            "PatternedKVCache, whose per-kind stacks do not stage-"
            "split uniformly"
        )
    if n_slots % pp:
        raise ValueError(
            f"pp_pipeline needs n_slots divisible by pp: {n_slots} % "
            f"{pp} != 0 (slots split into pp staggered groups)"
        )
    if cfg.n_layers % pp:
        raise ValueError(
            f"pp_pipeline needs n_layers divisible by pp: "
            f"{cfg.n_layers} % {pp} != 0"
        )
    if cfg.attn_pattern is not None:
        period = len(cfg.attn_pattern)
        if (cfg.n_layers // pp) % period:
            raise ValueError(
                f"pp_pipeline on a patterned model needs each stage's "
                f"layer chunk to hold whole pattern periods: "
                f"(n_layers/pp)={cfg.n_layers // pp} % "
                f"period={period} != 0"
            )
    return pp
