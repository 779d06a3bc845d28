"""Ulysses-style (all-to-all) sequence parallelism over the 'sp' mesh axis.

The complement to ring attention (parallel/ring_attention.py): instead of
rotating kv chunks around a ring, two `all_to_all` collectives reshard
the activations so attention itself is embarrassingly parallel.

Each sp rank enters holding a contiguous sequence chunk of q/k/v
(B, S/n, H, D). The first all-to-all trades the sequence sharding for a
head sharding: every rank ends up with the FULL sequence for H/n heads.
Local attention then needs no communication at all — so it supports
sliding windows and arbitrary masks, and it can use the Pallas flash
kernel as-is (both things the ring cannot do without extra machinery).
A second all-to-all restores the sequence sharding for the residual
stream.

Cost model: 2 all-to-alls moving O(B·S·H·D / n) per device over ICI,
independent of sequence length per hop, vs the ring's n ppermutes of kv.
Ulysses wins when H is large relative to n and masks are irregular; ring
wins on kv memory (O(S/n) holds throughout) and when H/n would round
badly. Both are exposed; `auto` in the model picks ring for plain causal
and ulysses for windowed attention on an sp mesh.

GQA: kv heads are split over sp like q heads when divisible; otherwise
kv is broadcast to full multi-head (a memory cost, never a correctness
change). Backward is jax autodiff through the collectives (all_to_all is
its own transpose up to permutation).

No reference citation is possible: the reference mount is empty
(SURVEY.md §0). The design follows the public DeepSpeed-Ulysses idea,
re-expressed as shard_map + lax.all_to_all so GSPMD sees static shapes.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from shellac_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQ, AXIS_TENSOR


def ulysses_supported(
    n_heads: int, n_kv_heads: int, mesh: Mesh, *, axis_name: str = AXIS_SEQ
) -> bool:
    """Can ulysses run for these head counts on this mesh?

    Heads are already sharded over tp before the sp all-to-all, so the
    per-device head count (H / tp) must split evenly over sp.
    """
    n = mesh.shape.get(axis_name, 1)
    tp = mesh.shape.get(AXIS_TENSOR, 1)
    if n_heads % tp or n_kv_heads % tp:
        return False
    return (n_heads // tp) % n == 0


def _ulysses_local(
    q, k, v, seg, sinks, *, axis_name: str, causal: bool,
    window: Optional[int], scale: float, impl: str, has_segments: bool,
    softcap=None, has_sinks=False,
):
    """Runs on one device inside shard_map.

    q: (B, S_loc, H_loc, D); k, v: (B, S_loc, Hkv_loc, D) — local
    shapes. seg: (B, S) packed document ids, FULL row (replicated over
    sp by the in_spec; dummy when has_segments=False).
    """
    from shellac_tpu.ops.attention import attention

    n = jax.lax.axis_size(axis_name)
    b, s_loc, h_loc, dh = q.shape
    hkv_loc = k.shape[2]
    if h_loc % n:
        raise ValueError(
            f"ulysses: local head count {h_loc} not divisible by sp={n}"
        )
    if hkv_loc % n:
        # Repeat kv heads to the smallest count that splits evenly over
        # sp: lcm(hkv_loc, n). It divides h_loc (hkv_loc and n both do),
        # so GQA grouping downstream stays valid, and it beats
        # broadcasting to the full q head count on kv memory/bandwidth.
        import math

        hkv_new = math.lcm(hkv_loc, n)
        rep = hkv_new // hkv_loc
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    # seq-sharded -> head-sharded: (B, S_loc, H_loc, D) -> (B, S, H_loc/n, D)
    a2a = functools.partial(
        jax.lax.all_to_all, axis_name=axis_name,
        split_axis=2, concat_axis=1, tiled=True,
    )
    qh, kh, vh = a2a(q), a2a(k), a2a(v)

    seg_full = None
    if has_segments:
        # After the a2a every rank holds the FULL sequence for its
        # heads, so the block-diagonal mask needs the full segment row.
        # The in_spec replicates seg over sp (it arrives as the full
        # (B, S) row), so no per-layer collective runs here: the ids are
        # constant across layers and one resharding outside the layer
        # scan covers every block.
        seg_full = seg  # (B, S)

    sinks_h = None
    if has_sinks:
        # After the a2a this rank computes heads
        # [my * h_loc/n, (my+1) * h_loc/n) of the LOCAL (tp-sharded)
        # head axis; slice the matching sink logits.
        my = jax.lax.axis_index(axis_name)
        per = h_loc // n
        sinks_h = jax.lax.dynamic_slice_in_dim(sinks, my * per, per)
    o = attention(
        qh, kh, vh, causal=causal, window=window, scale=scale, impl=impl,
        softcap=softcap, sinks=sinks_h,
        q_segments=seg_full, kv_segments=seg_full,
    )

    # head-sharded -> seq-sharded
    return jax.lax.all_to_all(
        o, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    sinks: Optional[jax.Array] = None,
    segments: Optional[jax.Array] = None,  # (B, S) packed document ids
    axis_name: str = AXIS_SEQ,
    impl: str = "auto",
) -> jax.Array:
    """All-to-all sequence-parallel attention. q (B,S,H,D); k,v (B,S,Hkv,D).

    S is globally sharded over `axis_name`; batch over dp/fsdp; heads over
    tp. Returns (B,S,H,D) with the same sharding as q. `impl` is forwarded
    to the local attention dispatch ("auto" uses the flash kernel on TPU).
    With `segments`, attention is block-diagonal over packed documents.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q_spec = P((AXIS_DATA, AXIS_FSDP), axis_name, AXIS_TENSOR, None)
    kv_spec = P((AXIS_DATA, AXIS_FSDP), axis_name, AXIS_TENSOR, None)
    # seg replicated over sp: every rank needs the full row after the
    # head a2a anyway, and ids are layer-invariant, so resharding once
    # outside beats an all_gather inside every layer's body.
    seg_spec = P((AXIS_DATA, AXIS_FSDP), None)
    sink_spec = P(AXIS_TENSOR)
    has_segments = segments is not None
    if not has_segments:
        segments = jnp.zeros(q.shape[:2], jnp.int32)
    has_sinks = sinks is not None
    if not has_sinks:
        sinks = jnp.zeros((q.shape[2],), jnp.float32)
    fn = shard_map(
        functools.partial(
            _ulysses_local, axis_name=axis_name, causal=causal,
            window=window, scale=float(scale), impl=impl,
            has_segments=has_segments,
            softcap=None if softcap is None else float(softcap),
            has_sinks=has_sinks,
        ),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, seg_spec, sink_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v, segments, sinks)
