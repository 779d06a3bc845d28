"""Rotary position embeddings (non-interleaved / NeoX half-rotation form).

Frequencies are computed in fp32 regardless of compute dtype: bf16 loses
precision at long positions, which shows up as attention drift past ~8k
tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.named_scope("attn.rope")
def rope_angles(positions: jax.Array, head_dim: int, theta: float = 10000.0,
                yarn=None, llama3=None, linear=None):
    """cos/sin tables for given absolute positions.

    positions: int32 array, any shape (typically (B, S) or (S,)).
    Returns (cos, sin) with shape positions.shape + (head_dim // 2,), fp32.
    With a YarnConfig the inverse frequencies blend interpolation and
    extrapolation per the NTK-by-parts recipe and the tables carry the
    attention (mscale) factor; with a Llama3RopeConfig the frequencies
    scale by wavelength band — both numerics match HF exactly. `linear`
    is classic position interpolation (HF "linear": every inverse
    frequency divides by the factor; Gemma-3 global layers).
    """
    half = head_dim // 2
    scale = 1.0
    if yarn is not None:
        freq, scale = _yarn_inv_freq(head_dim, theta, yarn)
    else:
        freq = 1.0 / (
            theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
        )
        if llama3 is not None:
            freq = _llama3_inv_freq(freq, llama3)
        if linear is not None:
            freq = freq / linear
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def _llama3_inv_freq(inv_freq: jax.Array, l3):
    """Llama-3.1 banded frequency scaling (HF _compute_llama3_parameters).

    Long wavelengths divide by `factor`, short ones stay, the middle
    band interpolates by a smooth factor in old-context rotations.
    """
    import math

    old = l3.original_max_position_embeddings
    low_wl = old / l3.low_freq_factor
    high_wl = old / l3.high_freq_factor
    wavelen = 2 * math.pi / inv_freq
    scaled = jnp.where(wavelen > low_wl, inv_freq / l3.factor, inv_freq)
    smooth = (old / wavelen - l3.low_freq_factor) / (
        l3.high_freq_factor - l3.low_freq_factor
    )
    smoothed = (1 - smooth) * scaled / l3.factor + smooth * scaled
    medium = (~(wavelen < high_wl)) & (~(wavelen > low_wl))
    return jnp.where(medium, smoothed, scaled)


def _yarn_inv_freq(dim: int, base: float, yarn):
    """Yarn inverse frequencies + attention factor (static, numpy).

    Mirrors transformers' _compute_yarn_parameters step for step so
    converted long-context checkpoints (e.g. DeepSeek) reproduce HF
    logits exactly.
    """
    import math

    import numpy as np

    factor = yarn.factor
    attention_factor = yarn.attention_factor

    def get_mscale(scale, mscale=1.0):
        if scale <= 1:
            return 1.0
        return 0.1 * mscale * math.log(scale) + 1.0

    if attention_factor is None:
        if yarn.mscale and yarn.mscale_all_dim:
            attention_factor = float(
                get_mscale(factor, yarn.mscale)
                / get_mscale(factor, yarn.mscale_all_dim)
            )
        else:
            attention_factor = get_mscale(factor)

    def correction_dim(num_rot):
        return (dim * math.log(
            yarn.original_max_position_embeddings / (num_rot * 2 * math.pi)
        )) / (2 * math.log(base))

    low = correction_dim(yarn.beta_fast)
    high = correction_dim(yarn.beta_slow)
    if yarn.truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001

    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extrap = 1.0 / pos_freqs
    interp = 1.0 / (factor * pos_freqs)
    ramp = np.clip(
        (np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1
    )
    extrap_factor = 1.0 - ramp
    inv_freq = interp * (1 - extrap_factor) + extrap * extrap_factor
    return jnp.asarray(inv_freq, jnp.float32), float(attention_factor)


@jax.named_scope("attn.rope")
def apply_rope_interleaved(
    x: jax.Array, cos: jax.Array, sin: jax.Array
) -> jax.Array:
    """Rotate ADJACENT pairs of the head dim (complex/GPT-J form).

    DeepSeek's MLA rope treats (x[2i], x[2i+1]) as one complex number
    (torch.view_as_complex), unlike the half-rotation above; converted
    checkpoints only reproduce with matching pairing. Shapes as
    apply_rope: x (..., S, H, D), cos/sin broadcastable to
    (..., S, 1, D/2).
    """
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos[..., None, :]
    s = sin[..., None, :]
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    # Re-interleave: (..., D/2, 2) -> (..., D)
    out = jnp.stack([out1, out2], axis=-1).reshape(*x.shape)
    return out.astype(x.dtype)


@jax.named_scope("attn.rope")
def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate the head dimension of x.

    x: (..., S, H, D). cos/sin: broadcastable to (..., S, 1, D/2) — e.g.
    shape (B, S, D/2) or (S, D/2).
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # Insert the heads axis for broadcasting.
    c = cos[..., None, :]
    s = sin[..., None, :]
    x1f = x1.astype(jnp.float32)
    x2f = x2.astype(jnp.float32)
    out1 = x1f * c - x2f * s
    out2 = x2f * c + x1f * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


def mrope_token_positions(position_ids) -> jax.Array:
    """(B, S) positions from M-RoPE position ids (3, B, S), for token
    input. Keye-VL-2.0 ropes with three position axes (temporal, height,
    width); for text tokens all three equal the token's index and the
    rope is the ordinary one, which is what this program computes.
    Anything else (image or video tokens) refuses: the check reads the
    values, so the ids must be concrete, not traced."""
    import numpy as np

    if isinstance(position_ids, jax.core.Tracer):
        raise NotImplementedError(
            "M-RoPE position ids are checked on the host (three equal "
            "axes, or refused): pass them concrete, not traced"
        )
    p = np.asarray(position_ids)
    if p.ndim != 3 or p.shape[0] != 3:
        raise ValueError(
            f"M-RoPE position ids are (3, B, S), got {p.shape}"
        )
    if not (np.array_equal(p[0], p[1]) and np.array_equal(p[0], p[2])):
        raise NotImplementedError(
            "three unequal M-RoPE position axes (image or video tokens): "
            "only token-id input is served, where the three axes are equal "
            "and M-RoPE is the ordinary rope; the vision tower is not part "
            "of this program"
        )
    return jnp.asarray(p[0], jnp.int32)
