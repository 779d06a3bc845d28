"""Attention ops.

`attention(...)` is the single entry point used by every model. It
dispatches between:
  - `attention_ref`: einsum + fp32 softmax. XLA already maps this onto the
    MXU and fuses the mask/softmax; it is the correctness reference and
    the CPU path.
  - `flash_attention` (ops/flash_attention.py): blocked Pallas TPU kernel
    with online softmax, used on TPU for long sequences.

Layout convention everywhere: q (B, Sq, H, D); k, v (B, Sk, Hkv, D) with
grouped-query attention when Hkv < H. Softmax/logits are always fp32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from shellac_tpu.ops.dispatch import on_mesh, per_shard

NEG_INF = -2.0e38


def _build_mask(
    q_positions: jax.Array,  # (B, Sq) int32
    kv_positions: jax.Array,  # (B, Sk) int32
    causal: bool,
    window: Optional[int],
    kv_mask: Optional[jax.Array],  # (B, Sk) bool — valid kv slots
    q_segments: Optional[jax.Array] = None,  # (B, Sq) int32
    kv_segments: Optional[jax.Array] = None,  # (B, Sk) int32
) -> Optional[jax.Array]:
    """Boolean (B, 1, Sq, Sk) mask; True = attend."""
    parts = []
    qp = q_positions[:, :, None]  # (B, Sq, 1)
    kp = kv_positions[:, None, :]  # (B, 1, Sk)
    if causal:
        parts.append(kp <= qp)
    if window is not None:
        parts.append(qp - kp < window)
    if kv_mask is not None:
        parts.append(kv_mask[:, None, :])
    if q_segments is not None:
        # Packed sequences: attend only within the same document.
        parts.append(q_segments[:, :, None] == kv_segments[:, None, :])
    if not parts:
        return None
    mask = parts[0]
    for p in parts[1:]:
        mask = jnp.logical_and(mask, p)
    return mask[:, None, :, :]  # add heads axis


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    sinks: Optional[jax.Array] = None,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    q_segments: Optional[jax.Array] = None,
    kv_segments: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference scaled-dot-product attention with GQA.

    softcap: Gemma-2-style logit soft-capping — scaled scores pass
    through cap*tanh(s/cap) BEFORE masking (masked slots stay NEG_INF,
    matching the HF eager path which caps, then adds the mask).

    sinks: (H,) per-head learned sink logits (GPT-OSS): each row's
    softmax denominator gains exp(sink_h) — a virtual column attending
    a zero value — so real attention mass can drain somewhere. Exactly
    HF's concat-softmax-drop formulation.
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    if h % hkv != 0:
        raise ValueError(f"n_heads={h} not divisible by n_kv_heads={hkv}")
    g = h // hkv
    if scale is None:
        scale = d ** -0.5
    if q_positions is None:
        # Assume q is the tail of the kv sequence (prefill: sq == sk).
        q_positions = jnp.broadcast_to(jnp.arange(sk - sq, sk, dtype=jnp.int32), (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32), (b, sk))

    qg = q.reshape(b, sq, hkv, g, d)
    # (B, Hkv, G, Sq, Sk) logits in fp32.
    logits = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    )
    logits = logits * scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    mask = _build_mask(
        q_positions, kv_positions, causal, window, kv_mask,
        q_segments, kv_segments,
    )
    if mask is not None:
        logits = jnp.where(mask[:, :, None, :, :], logits, NEG_INF)
    if sinks is not None:
        sink_col = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(1, hkv, g, 1, 1),
            (b, hkv, g, sq, 1),
        )
        probs = jax.nn.softmax(
            jnp.concatenate([logits, sink_col], axis=-1), axis=-1
        )[..., :-1]
    else:
        probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, sq, h, d).astype(q.dtype)


@jax.named_scope("attn.core")
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    sinks: Optional[jax.Array] = None,
    q_positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    kv_mask: Optional[jax.Array] = None,
    q_segments: Optional[jax.Array] = None,
    kv_segments: Optional[jax.Array] = None,
    impl: str = "auto",
    mesh=None,
) -> jax.Array:
    """Dispatching attention. impl: "auto" | "flash" | "ref".

    `mesh`: the mesh the caller is partitioned over, if any; the flash
    kernel then runs per shard of batch and heads (see _flash)."""
    if impl not in ("auto", "flash", "ref"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl == "ref":
        return attention_ref(
            q, k, v, causal=causal, window=window, scale=scale,
            softcap=softcap, sinks=sinks,
            q_positions=q_positions, kv_positions=kv_positions, kv_mask=kv_mask,
            q_segments=q_segments, kv_segments=kv_segments,
        )
    from shellac_tpu.ops.flash_attention import flash_attention, flash_supported

    if impl == "flash":
        if q_positions is not None or kv_positions is not None \
                or kv_mask is not None:
            raise ValueError(
                "impl='flash' does not support q_positions/kv_positions/"
                "kv_mask; use impl='auto' or 'ref'"
            )
        if (q_segments is None) != (kv_segments is None) or (
            q_segments is not None and q_segments is not kv_segments
        ):
            raise ValueError(
                "impl='flash' needs q_segments and kv_segments to be the "
                "same packed-segment array"
            )
        out = _flash(q, k, v, q_segments, sinks, mesh, causal=causal,
                     scale=scale, window=window, softcap=softcap)
        if out is None:
            raise ValueError(
                f"impl='flash': q={q.shape} k={k.shape} do not divide "
                f"over mesh {dict(mesh.shape)}"
            )
        return out
    if impl == "auto" and flash_supported(
        q, k, v, window=window, q_positions=q_positions,
        kv_positions=kv_positions, kv_mask=kv_mask, causal=causal,
        q_segments=q_segments, kv_segments=kv_segments,
    ):
        out = _flash(q, k, v, q_segments, sinks, mesh, causal=causal,
                     scale=scale, window=window, softcap=softcap)
        if out is not None:
            return out
    return attention_ref(
        q, k, v, causal=causal, window=window, scale=scale,
        softcap=softcap, sinks=sinks,
        q_positions=q_positions, kv_positions=kv_positions, kv_mask=kv_mask,
        q_segments=q_segments, kv_segments=kv_segments,
    )


def _flash(q, k, v, segments, sinks, mesh, **kw):
    """The flash kernel, per shard when the caller is partitioned over
    a mesh: batch over the data axes, q and kv heads over the tensor
    axis (each shard keeps whole GQA groups), the sequence whole —
    sequence parallelism is ring/ulysses' job, upstream of here.
    Returns None when the shapes do not divide over the mesh."""
    from shellac_tpu.ops.flash_attention import flash_attention

    if not on_mesh(mesh):
        return flash_attention(q, k, v, segments=segments, sinks=sinks, **kw)
    q_axes = ("batch", None, "heads", None)
    kv_axes = ("batch", None, "kv_heads", None)
    return per_shard(
        functools.partial(flash_attention, **kw), mesh,
        {"q": (q, q_axes), "k": (k, kv_axes), "v": (v, kv_axes),
         "segments": (segments, ("batch", None)),
         "sinks": (sinks, ("heads",))},
        q_axes,
    )
