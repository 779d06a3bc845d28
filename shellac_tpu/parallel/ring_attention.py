"""Ring attention: sequence-parallel attention over the 'sp' mesh axis.

Each sp rank holds a contiguous sequence chunk of q/k/v. The kv chunks
rotate around the ring (`ppermute`) while each rank folds every visiting
chunk into a running online-softmax state — the same math as the flash
kernel, lifted one level up: blocks are whole per-device chunks and the
"grid" is the ring. KV memory per device stays O(S / sp), so context
length scales linearly with the sp axis, and the permutes ride ICI
neighbor links.

Causality is enforced at two granularities: whole visiting chunks from
the future are masked out, and the diagonal (own) chunk gets the usual
triangular mask. Sliding windows add a global-position band mask per
visiting chunk (chunks wholly outside the window contribute nothing via
the mask; the rotation itself stays uniform, which is what lax.scan
wants). Backward is jax autodiff through the scan; wrap the caller in
jax.checkpoint (the model's remat does) to keep residuals per layer
instead of per ring step.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from shellac_tpu.parallel.mesh import AXIS_DATA, AXIS_FSDP, AXIS_SEQ, AXIS_TENSOR

NEG_INF = -2.0e38


def _block_stats(q, k, v, scale, mask, softcap=None):
    """Unnormalized block attention: returns (acc, m, l).

    q (B,Sq,Hkv,G,D); k,v (B,Sk,Hkv,D); mask (Sq,Sk) or (B,Sq,Sk) or
    None, True=attend. acc (B,Sq,Hkv,G,D) fp32; m,l (B,Sq,Hkv,G,1) fp32.
    """
    s = jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        mask = mask[:, None, None]  # (B,1,1,Sq,Sk)
        s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # (B,Hkv,G,Sq,1)
    # Guard all-masked blocks: exp(NEG_INF - NEG_INF) would be exp(0)=1.
    m_safe = jnp.maximum(m, -1e37)
    p = jnp.exp(s - m_safe)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum(
        "bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    # -> (B,Sq,Hkv,G,·)
    perm = (0, 3, 1, 2, 4)
    return acc.transpose(perm), m_safe.transpose(perm), l.transpose(perm)


def _ring_local(
    q, k, v, seg, sinks, *, axis_name: str, causal: bool, scale: float,
    has_segments: bool, window=None, softcap=None, has_sinks=False,
):
    """Runs on one device inside shard_map. q (B,S_loc,H,D); k,v
    (B,S_loc,Hkv,D); seg (B,S_loc) int32 (packed document ids; a dummy
    when has_segments=False — shard_map needs a uniform signature)."""
    b, s_loc, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    my = jax.lax.axis_index(axis_name)
    n = jax.lax.axis_size(axis_name)

    # Keep q in its input dtype: preferred_element_type on the einsums
    # already gives fp32 accumulation, and bf16 inputs run the MXU at
    # full rate with half the live-range footprint.
    qg = q.reshape(b, s_loc, hkv, g, d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    tri = jnp.tril(jnp.ones((s_loc, s_loc), bool)) if causal else None

    def step(carry, i):
        acc, m, l, kv = carry
        k_cur, v_cur, seg_cur = kv
        src = (my - i) % n  # which chunk of the sequence we hold now
        if causal:
            # src < my: fully visible. src == my: triangular. src > my: hidden.
            block_mask = jnp.where(
                src < my,
                jnp.ones((s_loc, s_loc), bool),
                jnp.where(src == my, tri, jnp.zeros((s_loc, s_loc), bool)),
            )
        else:
            block_mask = None
        if window is not None:
            # Global positions: rank r's rows sit at r*s_loc + i.
            qpos = my * s_loc + jnp.arange(s_loc)
            kpos = src * s_loc + jnp.arange(s_loc)
            wmask = qpos[:, None] - kpos[None, :] < window  # (Sq, Sk)
            block_mask = wmask if block_mask is None else block_mask & wmask
        if has_segments:
            # Packed documents: attend only within the same segment. The
            # segment ids rotate with their kv chunk, so the pairing is
            # always (my q chunk) x (visiting kv chunk) — global-order
            # causality plus segment equality is exactly within-document
            # causal attention for contiguous packing.
            seg_mask = seg[:, :, None] == seg_cur[:, None, :]  # (B,Sq,Sk)
            block_mask = (
                seg_mask if block_mask is None
                else block_mask[None] & seg_mask
            )
        acc_c, m_c, l_c = _block_stats(qg, k_cur, v_cur, scale,
                                       block_mask, softcap)
        m_new = jnp.maximum(m, m_c)
        a1 = jnp.exp(m - m_new)
        a2 = jnp.exp(m_c - m_new)
        acc = acc * a1 + acc_c * a2
        l = l * a1 + l_c * a2
        # Rotate kv to the next rank; the last iteration's rotate returns
        # chunks home (kept for a uniform loop; XLA overlaps it).
        kv = jax.lax.ppermute((k_cur, v_cur, seg_cur), axis_name, perm)
        return (acc, m_new, l, kv), None

    acc0 = jnp.zeros((b, s_loc, hkv, g, d), jnp.float32)
    m0 = jnp.full((b, s_loc, hkv, g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s_loc, hkv, g, 1), jnp.float32)
    (acc, m, l, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, (k, v, seg)), jnp.arange(n)
    )
    if has_sinks:
        # Sink denominator: per-head exp(sink) joins l. sinks is
        # (H_loc,) ordered (kv_head, group) like qg.
        from shellac_tpu.ops.flash_attention import sink_rebase

        sk = sinks.astype(jnp.float32).reshape(1, 1, hkv, g, 1)
        r, l, _ = sink_rebase(m, l, sk)
        acc = acc * r
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).reshape(b, s_loc, h, d)
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    segments: Optional[jax.Array] = None,  # (B, S) packed document ids
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    sinks: Optional[jax.Array] = None,
    axis_name: str = AXIS_SEQ,
) -> jax.Array:
    """Sequence-parallel attention. q (B,S,H,D); k,v (B,S,Hkv,D).

    S is globally sharded over `axis_name`; batch over dp/fsdp; heads
    over tp. Returns (B,S,H,D) with the same sharding as q. With
    `segments`, attention is block-diagonal over packed documents (the
    ids rotate around the ring with their kv chunk). With `window`,
    attention is banded on global positions (qpos - kpos < window).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q_spec = P((AXIS_DATA, AXIS_FSDP), axis_name, AXIS_TENSOR, None)
    kv_spec = P((AXIS_DATA, AXIS_FSDP), axis_name, AXIS_TENSOR, None)
    seg_spec = P((AXIS_DATA, AXIS_FSDP), axis_name)
    # Sink logits shard with the heads (tp axis).
    sink_spec = P(AXIS_TENSOR)
    has_segments = segments is not None
    if not has_segments:
        segments = jnp.zeros(q.shape[:2], jnp.int32)
    has_sinks = sinks is not None
    if not has_sinks:
        sinks = jnp.zeros((q.shape[2],), jnp.float32)
    fn = shard_map(
        functools.partial(
            _ring_local, axis_name=axis_name, causal=causal,
            scale=float(scale), has_segments=has_segments, window=window,
            softcap=None if softcap is None else float(softcap),
            has_sinks=has_sinks,
        ),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, seg_spec, sink_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v, segments, sinks)
