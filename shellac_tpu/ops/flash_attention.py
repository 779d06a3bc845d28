"""Blocked (flash) attention as a Pallas TPU kernel.

Forward: classic online-softmax tiling. Grid is (batch*heads, q_blocks,
kv_blocks); the kv axis is innermost, so fp32 accumulators live in VMEM
scratch across kv steps. Causal upper-triangle blocks are skipped
entirely (no compute), which halves the work for causal prefill. GQA is
handled in the index map: the kv block for q-head h is head h // group,
so kv tiles are never replicated in HBM.

Sliding windows and packed segments are first-class (they are the
pretraining default, not an exotic): a window additionally skips kv
blocks entirely below the window's reach — compute AND the DMA, via the
same index-map clamping trick as the causal skip — so windowed training
cost scales with O(S*W) not O(S^2). Packed segment ids ride along as
(1, 1, block) int32 tiles and contribute a block-diagonal mask; a tile
whose every entry is masked is handled exactly (the online softmax
update is gated so the accumulator passes through unchanged).

Backward: blocked Pallas kernels as well. The forward additionally
writes the logsumexp rows; backward recomputes tile probabilities from
(q, k, lse) — never materializing the S×S matrix — in two passes:
one over kv blocks producing dk/dv (GQA group summed in-kernel), one
over q blocks producing dq. Causal/window dead blocks are skipped in
both. Segment ids need no gradient (they are an integer mask).

The compiled kernel wants head_dim a multiple of 64 (blocks span the
full head_dim, which Mosaic accepts; dh=64 pays ~2x lane padding but
still beats the O(S^2) reference) and block-divisible sequence lengths;
`flash_supported` gates dispatch and everything else falls back to the
reference implementation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from shellac_tpu.ops.dispatch import pallas_supported

# Tuned on v5e at (B=4, S=2048, H=16, Hkv=8, D=128): 512/1024 beats
# 256/256 by ~30% forward and ~2x on the backward pass.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
NEG_INF = -2.0e38


def sink_rebase(m, l, sink):
    """Fold a sink logit into an online-softmax (m, l) pair.

    Returns (r, l2, m2): rescale the accumulator by r, divide by l2,
    and m2 + log(l2) is the sink-inclusive logsumexp. Shared by the
    flash/decode/ring finalizers so the rebase math cannot drift.
    l2 >= exp(sink - m2) > 0, so fully-masked rows need no zero guard.
    """
    m2 = jnp.maximum(m, sink)
    r = jnp.exp(m - m2)
    return r, l * r + jnp.exp(sink - m2), m2


def _fit_block(seq: int, block: int) -> int:
    """Largest divisor of `seq` that is <= `block` and a multiple of 8
    (TPU sublane tiling); 0 if none exists."""
    b = min(block, seq)
    while b >= 8:
        if seq % b == 0 and b % 8 == 0:
            return b
        b -= 8
    return 0


def flash_supported(
    q, k, v, *, causal, window=None, q_positions=None, kv_positions=None,
    kv_mask=None, q_segments=None, kv_segments=None,
    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
) -> bool:
    """Can the compiled Pallas kernel handle this call?"""
    if not pallas_supported():
        return False
    if q_positions is not None or kv_positions is not None:
        return False
    if kv_mask is not None:
        return False
    if (q_segments is None) != (kv_segments is None):
        return False
    if q_segments is not None and q_segments is not kv_segments:
        # The kernel masks with ONE packed-segment row per batch entry
        # (training packing always has q and kv sharing it); distinct
        # q/kv segment arrays fall back to the reference path.
        return False
    if window is not None and window < 1:
        return False
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    if sq != sk:
        return False
    if not causal and window is not None:
        # One-sided windows without causality are ambiguous; only the
        # reference path defines them.
        return False
    if d % 64 != 0:
        # Head dims below a 128-lane tile are zero-padded up to one at
        # the flash_attention entry (Mosaic rejects block selects on
        # unaligned lane dims); d % 64 bounds that lane waste at ~2x.
        return False
    if _fit_block(sq, block_q) == 0 or _fit_block(sk, block_k) == 0:
        return False
    if h % hkv != 0:
        return False
    return True


def _scores(
    q_blk, k_blk, q_start, k_start, scale, causal, window=None,
    q_seg=None, k_seg=None, softcap=None,
):
    """Scaled (block_q, block_k) fp32 logits with all masks applied.

    q_seg/k_seg: (block_q,), (block_k,) int32 packed document ids, or
    None for unpacked. softcap: Gemma-2 logit capping — the scaled
    scores pass through cap*tanh(s/cap) BEFORE the masks, so masked
    slots keep the NEG_INF sentinel the online softmax gates on.
    """
    q = q_blk.astype(jnp.float32) * scale
    k = k_blk.astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    shape = s.shape
    if causal or window is not None:
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        if causal:
            s = jnp.where(cols <= rows, s, NEG_INF)
        if window is not None:
            # Valid iff qpos - kpos < window.
            s = jnp.where(rows - cols < window, s, NEG_INF)
    if q_seg is not None:
        s = jnp.where(q_seg[:, None] == k_seg[None, :], s, NEG_INF)
    return s


def _tile_p_ds(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    q_start, k_start, scale, causal, window, q_seg, k_seg, softcap=None,
):
    """Recompute a probability tile and its score gradient from saved lse.

    Shared by both backward kernels so the masking/lse handling cannot
    drift between dq and dk/dv. Returns (p, ds), both (block_q, block_k)
    fp32; ds carries the softmax scale factor (and, with softcap, the
    tanh derivative 1 - (s_cap/cap)^2 of the capping).
    """
    s = _scores(
        q_ref[0], k_ref[0], q_start, k_start, scale, causal, window,
        q_seg, k_seg, softcap,
    )
    # Masked entries carry s = NEG_INF (finite): exp(s - lse) underflows
    # to 0 for any real lse, but a fully-masked row would hit
    # exp(NEG_INF - NEG_INF) = 1, so gate on s itself.
    p = jnp.where(
        s > 0.5 * NEG_INF, jnp.exp(s - lse_ref[0, 0, :][:, None]), 0.0
    )
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - delta_ref[0, 0, :][:, None]) * scale
    if softcap is not None:
        # s holds the CAPPED score where live, so tanh(raw/cap) = s/cap
        # and d(cap)/d(raw) = 1 - (s/cap)^2. Masked slots hold NEG_INF;
        # (NEG_INF/cap)^2 overflows fp32 to inf and 0*inf = NaN, so gate
        # the factor on the same sentinel as p (ds is 0 there anyway).
        ds = ds * jnp.where(
            s > 0.5 * NEG_INF, 1.0 - jnp.square(s / softcap), 0.0
        )
    return p, ds


def _first_live_ki(q_start, window, block_k):
    """First kv block any row of this q block can attend (window only)."""
    return jnp.maximum(q_start - window + 1, 0) // block_k


def _make_clamp_ki(causal, window, block_q, block_k):
    """kv-block DMA clamp shared by the forward and dq index maps.

    Clamps dead kv blocks (above the causal diagonal, or wholly below
    the window's reach) onto the live range: the Mosaic pipeline only
    issues a DMA when the block index changes, so skipped blocks cost
    no HBM bandwidth.
    """

    def clamp_ki(qi, ki):
        if causal:
            last = (qi * block_q + block_q - 1) // block_k
            if window is not None:
                ki = jnp.clip(
                    ki, _first_live_ki(qi * block_q, window, block_k), last
                )
            else:
                ki = jnp.minimum(ki, last)
        return ki

    return clamp_ki


def _unpack_refs(refs, has_segments, n_out_scratch, has_sinks=False):
    """Split a kernel's positional refs into
    (main_inputs, segs, sinks, rest)."""
    n_extra = (2 if has_segments else 0) + (1 if has_sinks else 0)
    ins = refs[: len(refs) - n_out_scratch - n_extra]
    extra = refs[len(refs) - n_out_scratch - n_extra:
                 len(refs) - n_out_scratch]
    rest = refs[len(refs) - n_out_scratch:]
    segs = (extra[0], extra[1]) if has_segments else (None, None)
    sinks = extra[-1] if has_sinks else None
    return ins, segs, sinks, rest


def _flash_kernel(
    *refs, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, num_kv: int, has_segments: bool,
    softcap: Optional[float], has_sinks: bool,
):
    (q_ref, k_ref, v_ref), (qs_ref, ks_ref), sinks_ref, (
        o_ref, lse_ref, acc_ref, m_ref, l_ref,
    ) = _unpack_refs(refs, has_segments, 5, has_sinks)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    q_start = qi * block_q
    k_start = ki * block_k

    if causal:
        # Last kv block this q block attends to (where the output write
        # happens); later blocks are skipped entirely.
        last_ki = jnp.minimum(num_kv - 1, (q_start + block_q - 1) // block_k)
        live = k_start <= q_start + block_q - 1
    else:
        last_ki = num_kv - 1
        live = True
    if window is not None:
        # Blocks wholly below the window's reach are skipped too.
        live &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _compute():
        v = v_ref[0]
        q_seg = qs_ref[0, 0, :] if has_segments else None
        k_seg = ks_ref[0, 0, :] if has_segments else None
        s = _scores(
            q_ref[0], k_ref[0], q_start, k_start, scale, causal, window,
            q_seg, k_seg, softcap,
        )
        m_prev = m_ref[:, :1]  # (block_q, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # A fully-masked tile leaves m_new at NEG_INF; exp(s - m_new)
        # would then be exp(0) = 1 for every masked entry. Gate on s so
        # the tile contributes nothing (alpha = exp(m_prev - m_new) = 1
        # keeps the accumulator intact).
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == last_ki)
    def _finalize():
        l = l_ref[:, :1]
        m = m_ref[:, :1]
        if has_sinks:
            # GPT-OSS attention sink: the softmax denominator gains
            # exp(sink_h) — a virtual column over a zero value. The
            # saved lse then INCLUDES the sink, which is exactly what
            # makes the backward kernels correct unchanged (p =
            # exp(s - lse) are the true probabilities, delta =
            # sum(dO*O) still sums only real columns because the
            # sink's value is 0).
            r, l2, m2 = sink_rebase(m, l, sinks_ref[0, 0, 0])
            o_ref[0] = (acc_ref[...] * r / l2).astype(o_ref.dtype)
            lse_ref[0, 0, :] = (m2 + jnp.log(l2))[:, 0]
        else:
            # Guard fully-masked rows (can't happen for causal, cheap
            # anyway).
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
            lse_ref[0, 0, :] = (m + jnp.log(l))[:, 0]


def _flash_forward(
    q, k, v, seg, causal, scale, window, block_q, block_k, interpret,
    softcap=None, sinks=None,
):
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    block_q = _fit_block(sq, block_q) or min(block_q, sq)
    block_k = _fit_block(sk, block_k) or min(block_k, sk)
    num_q = sq // block_q
    num_kv = sk // block_k
    has_segments = seg is not None

    # (B, S, H, D) -> (B*H, S, D)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)

    clamp_ki = _make_clamp_ki(causal, window, block_q, block_k)

    def kv_index(bh, qi, ki):
        kv_bh = (bh // h) * hkv + (bh % h) // g
        return kv_bh, clamp_ki(qi, ki), 0

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    inputs = [qf, kf, vf]
    if has_segments:
        segr = seg.astype(jnp.int32).reshape(b, 1, sq)
        in_specs += [
            pl.BlockSpec(
                (1, 1, block_q), lambda bh, qi, ki: (bh // h, 0, qi)
            ),
            pl.BlockSpec(
                (1, 1, block_k),
                lambda bh, qi, ki: (bh // h, 0, clamp_ki(qi, ki)),
            ),
        ]
        inputs += [segr, segr]
    has_sinks = sinks is not None
    if has_sinks:
        # One scalar per q-head, tiled across a lane row (Mosaic wants
        # a 128-lane trailing dim). The unit middle dim makes the
        # block's last two dims equal the array's: a (1, 128) block of
        # an (h, 128) array is refused (sublane blocks come in 8s).
        sinks_arr = jnp.tile(
            sinks.astype(jnp.float32)[:, None, None], (1, 1, 128)
        )
        in_specs += [
            pl.BlockSpec((1, 1, 128), lambda bh, qi, ki: (bh % h, 0, 0)),
        ]
        inputs += [sinks_arr]

    out, lse = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            window=window,
            block_q=block_q,
            block_k=block_k,
            num_kv=num_kv,
            has_segments=has_segments,
            softcap=softcap,
            has_sinks=has_sinks,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(qf.shape, q.dtype),
            # (B*H, 1, S): the unit middle dim keeps the block's trailing
            # two dims TPU-tileable ((1, block_q) alone is not).
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ],
        grid=(b * h, num_q, num_kv),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*inputs)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3), lse[:, 0, :]



def _flash_bwd_dkdv_kernel(
    *refs, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, num_q: int, inner: int, has_segments: bool,
    softcap: Optional[float],
):
    """Grid (B*Hkv, kv_blocks, G*q_blocks): one (dk, dv) tile per kv block,
    accumulated over every q block of every q-head in the GQA group."""
    (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref), (qs_ref, ks_ref), _, (
        dk_ref, dv_ref, dk_acc, dv_acc,
    ) = _unpack_refs(refs, has_segments, 4)
    ki = pl.program_id(1)
    j = pl.program_id(2)
    qi = j % num_q

    k_start = ki * block_k
    q_start = qi * block_q
    live = (not causal) or (q_start + block_q - 1 >= k_start)
    if window is not None:
        # q rows beyond k_start + block_k - 1 + window - 1 can't reach
        # this kv block.
        live &= q_start <= k_start + block_k + window - 2

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(live)
    def _compute():
        q_seg = qs_ref[0, 0, :] if has_segments else None
        k_seg = ks_ref[0, 0, :] if has_segments else None
        p, ds = _tile_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, scale, causal, window, q_seg, k_seg, softcap,
        )
        do = do_ref[0]
        # dv += p^T do
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dk += ds^T q_raw  (ds carries the softmax scale)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0],
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(j == inner - 1)
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    *refs, scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, num_kv: int, has_segments: bool,
    softcap: Optional[float],
):
    """Grid (B*H, q_blocks, kv_blocks): one dq tile per q block."""
    (q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref), (qs_ref, ks_ref), _, (
        dq_ref, dq_acc,
    ) = _unpack_refs(refs, has_segments, 2)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q_start = qi * block_q
    k_start = ki * block_k

    if causal:
        last_ki = jnp.minimum(num_kv - 1, (q_start + block_q - 1) // block_k)
        live = k_start <= q_start + block_q - 1
    else:
        last_ki = num_kv - 1
        live = True
    if window is not None:
        live &= k_start + block_k - 1 >= q_start - window + 1

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(live)
    def _compute():
        q_seg = qs_ref[0, 0, :] if has_segments else None
        k_seg = ks_ref[0, 0, :] if has_segments else None
        _, ds = _tile_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, scale, causal, window, q_seg, k_seg, softcap,
        )
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    @pl.when(ki == last_ki)
    def _write():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_backward(
    q, k, v, seg, o, lse, g_out, causal, scale, window, block_q, block_k,
    interpret, softcap=None, sinks=None,
):
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    block_q = _fit_block(sq, block_q) or min(block_q, sq)
    block_k = _fit_block(sk, block_k) or min(block_k, sk)
    num_q = sq // block_q
    num_kv = sk // block_k
    has_segments = seg is not None

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, sk, d)
    dof = g_out.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    # delta_i = sum_d dO_id * O_id, per (head, row) — fp32. Shaped with a
    # unit middle dim (like lse) so blocks stay TPU-tileable.
    delta = jnp.einsum(
        "bshd,bshd->bhs", g_out.astype(jnp.float32), o.astype(jnp.float32)
    ).reshape(b * h, 1, sq)
    lse = lse.reshape(b * h, 1, sq)
    segr = (
        seg.astype(jnp.int32).reshape(b, 1, sq) if has_segments else None
    )

    # --- pass 1: dk, dv (GQA group summed in-kernel) ---
    inner = g * num_q

    def clamp_qi(ki, qi):
        if causal:
            # Clamp dead pre-diagonal q blocks to the first live one so
            # the pipeline issues no DMA for skipped blocks.
            qi = jnp.maximum(qi, (ki * block_k) // block_q)
        if window is not None:
            last_qi = jnp.minimum(
                (ki * block_k + block_k + window - 2) // block_q, num_q - 1
            )
            qi = jnp.minimum(qi, last_qi)
        return qi

    def q_row(bkv, ki, j):
        # q-head row for this (kv head, group member) pair.
        return (bkv // hkv) * h + (bkv % hkv) * g + j // num_q

    def q_index(bkv, ki, j):
        return q_row(bkv, ki, j), clamp_qi(ki, j % num_q), 0

    def row_index(bkv, ki, j):
        return q_row(bkv, ki, j), 0, clamp_qi(ki, j % num_q)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_index),
        pl.BlockSpec((1, block_q, d), q_index),
        pl.BlockSpec((1, 1, block_q), row_index),
        pl.BlockSpec((1, 1, block_q), row_index),
        pl.BlockSpec((1, block_k, d), lambda bkv, ki, j: (bkv, ki, 0)),
        pl.BlockSpec((1, block_k, d), lambda bkv, ki, j: (bkv, ki, 0)),
    ]
    inputs = [qf, dof, lse, delta, kf, vf]
    if has_segments:
        in_specs += [
            pl.BlockSpec(
                (1, 1, block_q),
                lambda bkv, ki, j: (bkv // hkv, 0, clamp_qi(ki, j % num_q)),
            ),
            pl.BlockSpec(
                (1, 1, block_k), lambda bkv, ki, j: (bkv // hkv, 0, ki)
            ),
        ]
        inputs += [segr, segr]

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkdv_kernel, scale=scale, causal=causal,
            window=window, block_q=block_q, block_k=block_k, num_q=num_q,
            inner=inner, has_segments=has_segments, softcap=softcap,
        ),
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, k.dtype),
            jax.ShapeDtypeStruct(vf.shape, v.dtype),
        ],
        grid=(b * hkv, num_kv, inner),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bkv, ki, j: (bkv, ki, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, ki, j: (bkv, ki, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(*inputs)

    # --- pass 2: dq ---
    clamp_ki = _make_clamp_ki(causal, window, block_q, block_k)

    def kv_index(bh, qi, ki):
        kv_bh = (bh // h) * hkv + (bh % h) // g
        return kv_bh, clamp_ki(qi, ki), 0

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        pl.BlockSpec((1, block_k, d), kv_index),
        pl.BlockSpec((1, block_k, d), kv_index),
    ]
    inputs = [qf, dof, lse, delta, kf, vf]
    if has_segments:
        in_specs += [
            pl.BlockSpec(
                (1, 1, block_q), lambda bh, qi, ki: (bh // h, 0, qi)
            ),
            pl.BlockSpec(
                (1, 1, block_k),
                lambda bh, qi, ki: (bh // h, 0, clamp_ki(qi, ki)),
            ),
        ]
        inputs += [segr, segr]

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_kv=num_kv,
            has_segments=has_segments, softcap=softcap,
        ),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid=(b * h, num_q, num_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(*inputs)

    unflat = lambda x, hh: x.reshape(b, hh, -1, d).transpose(0, 2, 1, 3)
    d_sinks = None
    if sinks is not None:
        # The sink column's value is zero, so its only gradient path is
        # the softmax denominator: dL/dsink_h = -sum_{b,rows}
        # p_sink * delta_row, with p_sink = exp(sink - lse) (lse already
        # includes the sink) and delta = sum(dO * O).
        lse_r = lse.reshape(b, h, sq)
        delta_r = delta.reshape(b, h, sq)
        d_sinks = -jnp.sum(
            jnp.exp(sinks.astype(jnp.float32)[None, :, None] - lse_r)
            * delta_r,
            axis=(0, 2),
        ).astype(sinks.dtype)
    return unflat(dq, h), unflat(dk, hkv), unflat(dv, hkv), d_sinks


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, seg, sinks, causal, scale, window, block_q, block_k,
           interpret, softcap):
    out, _ = _flash_forward(
        q, k, v, seg, causal, scale, window, block_q, block_k, interpret,
        softcap, sinks,
    )
    return out


def _flash_fwd(q, k, v, seg, sinks, causal, scale, window, block_q, block_k,
               interpret, softcap):
    out, lse = _flash_forward(
        q, k, v, seg, causal, scale, window, block_q, block_k, interpret,
        softcap, sinks,
    )
    return out, (q, k, v, seg, sinks, out, lse)


def _flash_bwd(causal, scale, window, block_q, block_k, interpret, softcap,
               res, g_out):
    q, k, v, seg, sinks, o, lse = res
    dq, dk, dv, d_sinks = _flash_backward(
        q, k, v, seg, o, lse, g_out, causal, scale, window, block_q, block_k,
        interpret, softcap, sinks,
    )
    return dq, dk, dv, None, d_sinks


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q, k, v, *, causal: bool = True, scale: Optional[float] = None,
    window: Optional[int] = None, segments: Optional[jax.Array] = None,
    softcap: Optional[float] = None, sinks: Optional[jax.Array] = None,
    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
):
    """Flash attention. q (B,S,H,D); k,v (B,S,Hkv,D).

    `window`: sliding-window size (qpos - kpos < window). `segments`:
    (B, S) int32 packed document ids shared by q and kv; attention is
    block-diagonal over them. `softcap`: Gemma-2-style tanh capping of
    the scaled scores (fwd and both bwd passes chain the derivative).
    """
    d = q.shape[-1]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = not pallas_supported()
    pad = (-d) % 128
    if pad:
        # Mosaic rejects memref slices (every `ref[0]` block select in
        # the kernels) on refs whose lane dim is not 128-aligned, so
        # dh=64-class models zero-pad the head dim up to a tile. Zero
        # k/v lanes leave the logits and the real output lanes exact;
        # the padded output lanes are sliced off (and autodiff of
        # pad/slice keeps the gradients exact too). ~2x lane waste,
        # still far ahead of the O(S^2) reference path.
        widths = [(0, 0)] * 3 + [(0, pad)]
        q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
    out = _flash(
        q, k, v, segments, sinks, causal, float(scale), window, block_q,
        block_k, interpret, None if softcap is None else float(softcap),
    )
    return out[..., :d] if pad else out
