"""Flash-decode attention over KV caches as Pallas TPU kernels.

The decode hot loop reads a head-major (B, Hkv, max_len, D) cache (or a
paged block pool) with a tiny q (B, s, H, D). The XLA ref path computes
logits over the whole max_len buffer every tick; these kernels instead
stream the cache in blocks with online softmax and — the actual win —
*skip the blocks beyond each sequence's own length entirely*:
per-sequence lengths are scalar-prefetched into SMEM and both the DMA
index map and the compute are clamped to the live range. A slot at
position 130 of a 4096-token buffer touches one or two KV blocks, not
4096 rows.

Two decode-specific grid decisions (history of the measurements:
PERF.md):
  - Head-major cache layout is load-bearing: Mosaic requires a block's
    trailing two dims to be tileable, so the per-head kv stream must be
    a contiguous (seq_block, head_dim) tile — the kvcache module stores
    caches this way precisely so these kernels never relayout them.
  - The grid iterates (batch, kv_blocks) with ALL kv heads processed
    per step (a static in-kernel loop), not (batch, head, kv_blocks):
    decode tiles are tiny (G*s rows), so a per-head grid drowns in
    per-step DMA/pipeline overhead — the first cut of this kernel ran
    2x SLOWER than the XLA ref exactly this way. Batching heads per
    step makes each DMA hkv times larger and cuts grid steps hkv-fold.

Two entry points:
  - `decode_attention`: dense cache (B, Hkv, L, D). GQA q rows are
    flattened to (H*s, D), kv-head-major, so each head's group shares
    one kv tile and kv is never replicated in HBM.
  - `paged_decode_attention`: block-pool cache (n_blocks, Hkv, bs, D)
    with per-slot tables. Same kernel body; the kv DMA indirects
    through the scalar-prefetched block table, so the dense (B,
    view, H, D) gather the ref path materializes never exists.

Both positions contracts follow forward_with_cache: q row si of batch b
sits at position lengths[b] + si, kv slot p is valid iff p <= that
(causal), optionally windowed. Rows whose scores are all masked in a
block self-correct in the online softmax once a valid block arrives
(alpha underflows to 0), and every real row attends at least its own
token.

The reference repo is empty (SURVEY.md §0); the blocked-decode idea is
the public flash-decoding / PagedAttention pattern, reimplemented for
the TPU memory hierarchy.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from shellac_tpu.ops.attention import attention_ref
from shellac_tpu.ops.dispatch import on_mesh, pallas_supported, per_shard
from shellac_tpu.ops.flash_attention import _fit_block, sink_rebase

DEFAULT_BLOCK_K = 512
NEG_INF = -2.0e38


class PagedFallbackWarning(UserWarning):
    """Paged decode silently fell back to the dense-gather path."""


class QuantFallbackWarning(UserWarning):
    """Int8-cache decode fell back to the full-dequant reference path."""


# ---------------------------------------------------------------------------
# shared kernel body
# ---------------------------------------------------------------------------


def _decode_tile(
    idx, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, s, hkv, block_k, window, k_start, ki, last_ki, first_ki,
    ks_ref=None, vs_ref=None, softcap=None, sink_ref=None,
):
    """One online-softmax step over every kv head of one sequence.

    idx: scalar — this sequence's pre-write length (q row si sits at
    position idx + si). q_ref/o_ref: (hkv*G*s, D) rows, kv-head-major.
    k_ref/v_ref: (hkv, block_k, D) kv tile whose first row is global
    position k_start. acc/m/l scratch span all rows; the per-head work
    is a static python loop — tiny decode matmuls cannot amortize a
    per-head grid dimension (see module docstring).

    ks_ref/vs_ref: (hkv, block_k) per-token dequant scales for int8
    caches. The scale folds in AFTER the integer-valued dot (exact:
    sum_d q*k_int*s == s * sum_d q*k_int) and, for v, onto p before the
    pv dot; the int8 stream itself is the bandwidth win.
    """
    live = (ki >= first_ki) & (k_start <= idx + s - 1)
    rows = q_ref.shape[0]
    rph = rows // hkv  # G*s rows per kv head

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _compute():
        r = jax.lax.broadcasted_iota(jnp.int32, (rph, block_k), 0)
        qpos = idx + r % s  # row r is (g, si=r%s) → position idx + si
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rph, block_k), 1
        )
        mask = kpos <= qpos
        if window is not None:
            mask &= qpos - kpos < window

        for kh in range(hkv):
            sl = pl.dslice(kh * rph, rph)
            # Operands in q's dtype (an int8 k is exact in it), float32
            # accumulation, the scale on the float32 logits: the
            # reference's precision, and one MXU pass for bf16.
            q = q_ref[sl, :]
            logits = jax.lax.dot_general(
                q, k_ref[kh].astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (rph, block_k)
            if ks_ref is not None:
                logits = logits * ks_ref[kh][None, :]
            if softcap is not None:
                # Gemma-2 capping, after dequant (the dequantized value
                # IS the real scaled logit), before masking.
                logits = softcap * jnp.tanh(logits / softcap)
            logits = jnp.where(mask, logits, NEG_INF)

            m_prev = m_ref[sl, :1]
            l_prev = l_ref[sl, :1]
            m_cur = jnp.max(logits, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[sl, :] = jnp.broadcast_to(
                alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
                (rph, l_ref.shape[1]),
            )
            v = v_ref[kh]
            if vs_ref is not None:
                p = p * vs_ref[kh][None, :]
                v = v.astype(jnp.float32)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_ref[sl, :] = acc_ref[sl, :] * alpha + pv
            m_ref[sl, :] = jnp.broadcast_to(m_new, (rph, m_ref.shape[1]))

    @pl.when(ki == last_ki)
    def _finalize():
        l = l_ref[:, :1]
        m = m_ref[:, :1]
        if sink_ref is not None:
            # GPT-OSS sink: the denominator gains exp(sink_row) (a
            # virtual zero-valued column).
            r, l2, _ = sink_rebase(m, l, sink_ref[...][:, :1])
            o_ref[...] = (acc_ref[...] * r / l2).astype(o_ref.dtype)
        else:
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_tile_values(
    idx, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, s, hkv, block_k, window, k_start, ki, last_ki, first_ki,
    softcap=None, sink_ref=None,
):
    """_decode_tile for head dims whose lane count is not 128-aligned.

    Mosaic rejects ANY memref_slice on a ref whose last dim is not a
    multiple of the 128-lane tiling ("Slice shape along dimension 2
    must be aligned to tiling (128), but is 64" — found compiling the
    dh=64 parity case; interpret mode does not catch it). So this
    variant takes the RAW (1, ...) refs, reads each one whole (full
    loads of padded refs are legal), slices VALUES per kv head, and
    stores whole refs back. Same math as _decode_tile to the last op.
    """
    live = (ki >= first_ki) & (k_start <= idx + s - 1)
    rows = q_ref.shape[1]
    rph = rows // hkv

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _compute():
        r = jax.lax.broadcasted_iota(jnp.int32, (rph, block_k), 0)
        qpos = idx + r % s
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (rph, block_k), 1
        )
        mask = kpos <= qpos
        if window is not None:
            mask &= qpos - kpos < window

        qall = q_ref[...][0]  # (rows, d)
        kall = k_ref[...][0]  # (hkv, block_k, d)
        vall = v_ref[...][0]
        acc_all = acc_ref[...]
        m_all = m_ref[...]
        l_all = l_ref[...]
        lanes = m_all.shape[1]
        accs, ms, ls = [], [], []
        for kh in range(hkv):
            lo, hi = kh * rph, (kh + 1) * rph
            q = jax.lax.slice_in_dim(qall, lo, hi, axis=0)
            k = jax.lax.slice_in_dim(kall, kh, kh + 1, axis=0)[0]
            logits = jax.lax.dot_general(
                q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if softcap is not None:
                logits = softcap * jnp.tanh(logits / softcap)
            logits = jnp.where(mask, logits, NEG_INF)

            m_prev = jax.lax.slice(m_all, (lo, 0), (hi, 1))
            l_prev = jax.lax.slice(l_all, (lo, 0), (hi, 1))
            m_cur = jnp.max(logits, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            v = jax.lax.slice_in_dim(vall, kh, kh + 1, axis=0)[0]
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc_prev = jax.lax.slice_in_dim(acc_all, lo, hi, axis=0)
            accs.append(acc_prev * alpha + pv)
            ms.append(jnp.broadcast_to(m_new, (rph, lanes)))
            ls.append(jnp.broadcast_to(l_new, (rph, lanes)))
        acc_ref[...] = jnp.concatenate(accs, axis=0)
        m_ref[...] = jnp.concatenate(ms, axis=0)
        l_ref[...] = jnp.concatenate(ls, axis=0)

    @pl.when(ki == last_ki)
    def _finalize():
        l = jax.lax.slice(l_ref[...], (0, 0), (rows, 1))
        if sink_ref is not None:
            m = jax.lax.slice(m_ref[...], (0, 0), (rows, 1))
            sink = jax.lax.slice(sink_ref[...], (0, 0), (rows, 1))
            r, l2, _ = sink_rebase(m, l, sink)
            o_ref[...] = ((acc_ref[...] * r / l2).astype(o_ref.dtype))[None]
        else:
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[...] = ((acc_ref[...] / l).astype(o_ref.dtype))[None]


def _decode_tile_any(
    idx, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, **kw
):
    """Dispatch on head-dim lane alignment (see _decode_tile_values)."""
    if q_ref.shape[-1] % 128 == 0:
        _decode_tile(
            idx, q_ref.at[0], k_ref.at[0], v_ref.at[0], o_ref.at[0],
            acc_ref, m_ref, l_ref, **kw,
        )
    else:
        _decode_tile_values(
            idx, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, **kw
        )


# Logical axes of q and of the result on a mesh: slots over the data
# axes, heads over the tensor axis.
_Q_AXES = ("batch", None, "heads", None)


def _kv_axis(cache):
    """Logical axis of a cache's kv-head dim (dim 1) on a mesh: kv
    heads shard with the q heads, so every shard keeps whole GQA
    groups; a single shared kv head (MQA, the MLA latent) is
    replicated instead."""
    return "kv_heads" if cache.shape[1] > 1 else None


def _mesh_fallback(impl, loses, warning, q, cache, mesh):
    """The kernel could not be cut over the mesh (per_shard refused):
    a forced kernel is an error; otherwise the reference path runs, and
    says what it `loses` against the kernel (None: nothing worth a
    warning)."""
    what = (f"q={tuple(q.shape)} cache={tuple(cache.shape)} do not divide "
            f"over mesh {dict(mesh.shape)}")
    if impl == "flash":
        raise ValueError(f"impl='flash': {what}")
    if loses:
        warnings.warn(
            f"decode kernel unavailable: {what} — the reference fallback "
            f"{loses}", warning, stacklevel=3,
        )


_DEQUANT_EVERY_TICK = "dequantizes the cache every tick"


def _live_range(idx, s, block_k, window, num_kv):
    """(first_ki, last_ki) of kv blocks any q row can attend."""
    last_ki = jnp.minimum((idx + s - 1) // block_k, num_kv - 1)
    if window is None:
        first_ki = jnp.int32(0)
    else:
        first_ki = jnp.maximum(idx - window + 1, 0) // block_k
    return first_ki, last_ki


def _split_sink_rest(rest, has_sinks):
    """Split a kernel's trailing refs into (sink_ref, remaining): the
    optional sink operand sits between the inputs and the outputs."""
    if has_sinks:
        return rest[0], rest[1:]
    return None, rest


def _row_sinks(sinks, s):
    """Per-ROW sink tile for the decode kernels: rows are kv-head-major
    q heads x s (matching _flatten_q), tiled to a 128-lane block."""
    return jnp.tile(
        jnp.repeat(sinks.astype(jnp.float32), s)[:, None], (1, 128)
    )


def _flatten_q(q, hkv):
    """(B, s, H, D) -> (B, H*s, D), rows kv-head-major (GQA groups are
    contiguous because q head h belongs to kv head h // G)."""
    b, s, h, d = q.shape
    return q.transpose(0, 2, 1, 3).reshape(b, h * s, d)


def _unflatten_o(o, b, s, h, d):
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# dense cache
# ---------------------------------------------------------------------------


def _dense_kernel(
    idx_ref, q_ref, k_ref, v_ref, *rest,
    scale, s, hkv, block_k, window, num_kv, softcap=None, has_sinks=False,
):
    sink_ref, (o_ref, acc_ref, m_ref, l_ref) = _split_sink_rest(
        rest, has_sinks
    )
    b = pl.program_id(0)
    ki = pl.program_id(1)
    idx = idx_ref[b]
    first_ki, last_ki = _live_range(idx, s, block_k, window, num_kv)
    _decode_tile_any(
        idx, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
        scale=scale, s=s, hkv=hkv, block_k=block_k, window=window,
        k_start=ki * block_k, ki=ki, last_ki=last_ki, first_ki=first_ki,
        softcap=softcap, sink_ref=sink_ref,
    )


def _dense_kernel_quant(
    idx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, *rest,
    scale, s, hkv, block_k, window, num_kv, softcap=None, has_sinks=False,
):
    """Dense kernel over an int8 cache with per-token dequant scales
    (d % 128 == 0 only; the dispatch gate guarantees it)."""
    sink_ref, (o_ref, acc_ref, m_ref, l_ref) = _split_sink_rest(
        rest, has_sinks
    )
    b = pl.program_id(0)
    ki = pl.program_id(1)
    idx = idx_ref[b]
    first_ki, last_ki = _live_range(idx, s, block_k, window, num_kv)
    _decode_tile(
        idx, q_ref.at[0], k_ref.at[0], v_ref.at[0], o_ref.at[0],
        acc_ref, m_ref, l_ref,
        scale=scale, s=s, hkv=hkv, block_k=block_k, window=window,
        k_start=ki * block_k, ki=ki, last_ki=last_ki, first_ki=first_ki,
        ks_ref=ks_ref.at[0], vs_ref=vs_ref.at[0], softcap=softcap,
        sink_ref=sink_ref,
    )


def _dense_flash(q, cache_k, cache_v, index, scale, window, block_k,
                 interpret, k_scale=None, v_scale=None, softcap=None,
                 sinks=None):
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    _, hkv, max_len, _ = cache_k.shape
    rows = h * s
    num_kv = max_len // block_k
    quant = k_scale is not None

    qf = _flatten_q(q, hkv)

    def kv_map(bi, ki, idx_ref):
        first_ki, last_ki = _live_range(
            idx_ref[bi], s, block_k, window, num_kv
        )
        # Clamp dead blocks onto the live range: Mosaic only issues a
        # DMA when the block index changes, so skipped blocks cost no
        # HBM bandwidth.
        return bi, 0, jnp.clip(ki, first_ki, last_ki), 0

    def scale_map(bi, ki, idx_ref):
        first_ki, last_ki = _live_range(
            idx_ref[bi], s, block_k, window, num_kv
        )
        return bi, 0, jnp.clip(ki, first_ki, last_ki)

    in_specs = [
        pl.BlockSpec((1, rows, d), lambda bi, ki, idx_ref: (bi, 0, 0)),
        pl.BlockSpec((1, hkv, block_k, d), kv_map),
        pl.BlockSpec((1, hkv, block_k, d), kv_map),
    ]
    operands = [qf, cache_k, cache_v]
    if quant:
        in_specs += [
            pl.BlockSpec((1, hkv, block_k), scale_map),
            pl.BlockSpec((1, hkv, block_k), scale_map),
        ]
        operands += [k_scale, v_scale]
    has_sinks = sinks is not None
    if has_sinks:
        in_specs += [
            pl.BlockSpec((rows, 128), lambda bi, ki, idx_ref: (0, 0)),
        ]
        operands += [_row_sinks(sinks, s)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, num_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, rows, d), lambda bi, ki, idx_ref: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _dense_kernel_quant if quant else _dense_kernel,
            scale=scale, s=s, hkv=hkv, block_k=block_k,
            window=window, num_kv=num_kv, softcap=softcap,
            has_sinks=has_sinks,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        interpret=interpret,
        name="decode_dense",
    )(index.astype(jnp.int32), *operands)
    return _unflatten_o(out, b, s, h, d)


def _pick_block_k(max_len: int, hkv: int, block_k: int) -> int:
    """Largest workable kv block: divides max_len, and the (hkv,
    block_k, d) k+v tiles stay within a double-buffered VMEM budget."""
    # ~4 MiB for k+v at bf16 with double buffering: hkv*block_k <= 8192.
    cap = max(8, 8192 // max(hkv, 1))
    return _fit_block(max_len, min(block_k, cap))


def decode_supported(
    q, cache_k, *, block_k: Optional[int] = None, quant: bool = False
) -> bool:
    """Can the compiled dense decode kernel handle these shapes?"""
    b, s, h, d = q.shape
    hkv, max_len, dk = cache_k.shape[1], cache_k.shape[2], cache_k.shape[3]
    if d % 64 != 0 or dk != d:
        return False
    if quant and d % 128 != 0:
        # The int8-cache kernel reuses the ref-slicing fast tile, which
        # needs full-lane head dims; dh=64 int8 takes the ref path.
        return False
    if h % hkv != 0:
        return False
    if h * s > 1024:  # VMEM accumulator budget
        return False
    return _pick_block_k(max_len, hkv, block_k or DEFAULT_BLOCK_K) != 0


@jax.named_scope("attn.core")
def decode_attention(
    q, cache_k, cache_v, index, *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    sinks=None,
    impl: str = "auto",
    block_k: int = DEFAULT_BLOCK_K,
    interpret: Optional[bool] = None,
    k_scale=None, v_scale=None,
    mesh=None,
):
    """Attention of q (B, s, H, D) against a dense cache (B, Hkv, L, D).

    index: (B,) int32 — per-sequence pre-write length; q row si sits at
    position index + si and attends kv positions <= its own (optionally
    windowed). Dispatches to the Pallas kernel when supported, else the
    masked reference path (bit-identical semantics).

    k_scale/v_scale: (B, Hkv, L) per-token dequant scales for an int8
    cache (see kvcache.QuantKVCache); both or neither.

    `mesh`: the mesh the caller is partitioned over, if any; the kernel
    then runs per shard of slots and heads (_kv_axis).
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale come together")
    quant = k_scale is not None
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = not pallas_supported()
    shapes_ok = decode_supported(q, cache_k, block_k=block_k, quant=quant)
    if impl == "flash":
        if not shapes_ok:
            raise ValueError(
                f"impl='flash' unsupported for q={q.shape} "
                f"cache={cache_k.shape} quant={quant}"
            )
        use_kernel = True
    else:
        # 'auto' only takes the kernel when compiled Pallas is live —
        # interpret mode exists for tests, not as a dispatch target.
        use_kernel = impl == "auto" and pallas_supported() and shapes_ok
        if (impl == "auto" and pallas_supported() and not shapes_ok
                and quant):
            # An int8 cache whose shape disqualifies the kernel takes a
            # ref path that dequantizes the WHOLE buffer every tick —
            # more HBM traffic than the bf16 cache the operator was
            # trying to halve. Same say-it-once policy as the paged
            # fallback warning.
            warnings.warn(
                "decode_attention: int8-cache Pallas kernel unavailable "
                f"for q={tuple(q.shape)} cache={tuple(cache_k.shape)} — "
                "the reference fallback dequantizes the full cache every "
                "tick (the kv_quant bandwidth win inverts). Kernel "
                "needs head_dim % 128 == 0 for int8 caches.",
                QuantFallbackWarning,
                stacklevel=2,
            )
    if use_kernel:
        def kernel(q, cache_k, cache_v, index, k_scale, v_scale, sinks):
            bk = _pick_block_k(cache_k.shape[2], cache_k.shape[1], block_k)
            return _dense_flash(
                q, cache_k, cache_v, index, float(scale), window, bk,
                interpret, k_scale=k_scale, v_scale=v_scale,
                softcap=None if softcap is None else float(softcap),
                sinks=sinks,
            )

        if not on_mesh(mesh):
            return kernel(q, cache_k, cache_v, index, k_scale, v_scale, sinks)
        kv = _kv_axis(cache_k)
        out = per_shard(kernel, mesh, {
            "q": (q, _Q_AXES),
            "cache_k": (cache_k, ("batch", kv, None, None)),
            "cache_v": (cache_v, ("batch", kv, None, None)),
            "index": (index, ("batch",)),
            "k_scale": (k_scale, ("batch", kv, None)),
            "v_scale": (v_scale, ("batch", kv, None)),
            "sinks": (sinks, ("heads",)),
        }, _Q_AXES)
        if out is not None:
            return out
        _mesh_fallback(impl, _DEQUANT_EVERY_TICK if quant else None,
                       QuantFallbackWarning, q, cache_k, mesh)
    return _decode_ref(
        q, cache_k, cache_v, index, window, scale, softcap=softcap,
        sinks=sinks, k_scale=k_scale, v_scale=v_scale,
    )


def _decode_ref(q, cache_k, cache_v, index, window, scale, softcap=None,
                sinks=None, k_scale=None, v_scale=None):
    if k_scale is not None:
        # Dequantize the int8 cache at read; XLA fuses the multiply
        # into the attention contraction's operand read.
        cache_k = cache_k.astype(jnp.float32) * k_scale[..., None]
        cache_v = cache_v.astype(jnp.float32) * v_scale[..., None]
        cache_k = cache_k.astype(q.dtype)
        cache_v = cache_v.astype(q.dtype)
    # cache: (B, Hkv, L, D) head-major -> (B, L, Hkv, D) for the ref.
    cache_k = cache_k.transpose(0, 2, 1, 3)
    cache_v = cache_v.transpose(0, 2, 1, 3)
    b, s = q.shape[:2]
    max_len = cache_k.shape[1]
    cdt = q.dtype
    q_positions = index[:, None] + jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32), (b, s)
    )
    kv_positions = jnp.broadcast_to(
        jnp.arange(max_len, dtype=jnp.int32), (b, max_len)
    )
    kv_mask = kv_positions < (index[:, None] + s)
    return attention_ref(
        q, cache_k.astype(cdt), cache_v.astype(cdt),
        causal=True, window=window, scale=scale, softcap=softcap,
        sinks=sinks,
        q_positions=q_positions, kv_positions=kv_positions, kv_mask=kv_mask,
    )


# ---------------------------------------------------------------------------
# paged cache
# ---------------------------------------------------------------------------


def _paged_group_kernel(
    len_ref, tab_ref, q_ref, k_hbm, *rest,
    scale, s, hkv, bs, group, window, num_kv, softcap=None,
    has_sinks=False, quant=False, shared=False,
):
    """Grouped paged decode: `group` pages gathered per grid step.

    The pool stays in HBM (memory_space=ANY) and the kernel gathers a
    step's pages itself, with parallel async copies into one contiguous
    VMEM tile, so per-step overhead amortizes `group`-fold and the dot
    runs group*bs wide. Skipping is page-granular: dead groups issue no
    DMAs at all, and a live boundary group only fetches its live pages.
    The v rows (and scales) of its dead page slots are ZEROED in VMEM
    instead: unfetched scratch is uninitialized, and a stray Inf/NaN bit
    pattern would poison the accumulator through the masked-out p=0
    rows as 0*Inf (k needs none: its logits are replaced, not scaled).

    The tile is double-buffered across grid steps: a live step starts
    the copies of the NEXT live step (this slot's next group, else the
    next slot's first) into the other half before it waits for its own,
    so the dots of one step hide the DMA of the next. Grid steps run in
    order on one core; `step_ref` counts the live ones, its parity is
    the half in use.
    """
    from jax.experimental.pallas import tpu as pltpu

    if not shared:
        v_hbm, rest = rest[0], rest[1:]
    if quant:
        # Int8 pools travel with fp32 scale pools, gathered page-for-
        # page into their own VMEM tiles (sem rows 2/3).
        ks_hbm, vs_hbm = rest[0], rest[1]
        rest = rest[2:]
    sink_ref, rest = _split_sink_rest(rest, has_sinks)
    ks_buf = vs_buf = None
    if quant:
        (o_ref, acc_ref, m_ref, l_ref, k_buf, v_buf, ks_buf, vs_buf,
         sems, step_ref) = rest
    elif shared:
        # One pool serves as k and as v (the MLA latent): v_hbm is the
        # k operand again and a page is copied once, into the one tile.
        o_ref, acc_ref, m_ref, l_ref, k_buf, sems, step_ref = rest
        v_buf = k_buf
    else:
        o_ref, acc_ref, m_ref, l_ref, k_buf, v_buf, sems, step_ref = rest
    b = pl.program_id(0)
    gi = pl.program_id(1)
    n_slots = pl.num_programs(0)
    block_k = group * bs
    num_groups = num_kv // group

    def live_groups(slot):
        return _live_range(len_ref[slot], s, block_k, window, num_groups)

    def pages(slot, g_idx, half, wait):
        """Start (or wait for) the copies of grid step (slot, g_idx)
        into tile `half`, page by page (page granularity, not group
        granularity); starting also zeroes its dead page slots."""
        first_pg, last_pg = _live_range(len_ref[slot], s, bs, window, num_kv)
        for g in range(group):
            pg = g_idx * group + g
            dst = pl.dslice(g * bs, bs)
            pg_live = (pg >= first_pg) & (pg <= last_pg)

            @pl.when(pg_live)
            def _copy(g=g, pg=pg, dst=dst):
                page = tab_ref[slot, pg]
                copies = [pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[half, :, dst, :],
                    sems.at[half, 0, g])]
                if not shared:
                    copies.append(pltpu.make_async_copy(
                        v_hbm.at[page], v_buf.at[half, :, dst, :],
                        sems.at[half, 1, g]))
                if quant:
                    copies += [
                        pltpu.make_async_copy(
                            ks_hbm.at[page], ks_buf.at[half, :, dst],
                            sems.at[half, 2, g]),
                        pltpu.make_async_copy(
                            vs_hbm.at[page], vs_buf.at[half, :, dst],
                            sems.at[half, 3, g]),
                    ]
                for c in copies:
                    c.wait() if wait else c.start()

            if not wait:
                @pl.when(~pg_live)
                def _zero(dst=dst):
                    v_buf[half, :, dst, :] = jnp.zeros(
                        (hkv, bs, v_buf.shape[-1]), v_buf.dtype)
                    if quant:
                        vs_buf[half, :, dst] = jnp.zeros(
                            (hkv, bs), vs_buf.dtype)

    @pl.when((b == 0) & (gi == 0))
    def _prime():
        step_ref[0] = 0
        pages(0, live_groups(0)[0], 0, wait=False)

    idx = len_ref[b]
    first_gi, last_gi = live_groups(b)
    live = (gi >= first_gi) & (gi <= last_gi)
    half = step_ref[0] % 2

    @pl.when(live)
    def _stream():
        more = gi < last_gi  # this slot has another live group
        nxt = jnp.minimum(b + 1, n_slots - 1)

        @pl.when(more | (b + 1 < n_slots))
        def _prefetch():
            pages(jnp.where(more, b, nxt),
                  jnp.where(more, gi + 1, live_groups(nxt)[0]),
                  1 - half, wait=False)

        pages(b, gi, half, wait=True)
        step_ref[0] = step_ref[0] + 1

    _decode_tile(
        idx, q_ref.at[0], k_buf.at[half], v_buf.at[half], o_ref.at[0],
        acc_ref, m_ref, l_ref,
        scale=scale, s=s, hkv=hkv, block_k=block_k, window=window,
        k_start=gi * block_k, ki=gi, last_ki=last_gi, first_ki=first_gi,
        ks_ref=None if ks_buf is None else ks_buf.at[half],
        vs_ref=None if vs_buf is None else vs_buf.at[half],
        softcap=softcap, sink_ref=sink_ref,
    )


def _paged_group_flash(
    q, pool_k, pool_v, tables, index, scale, window, group, interpret,
    softcap=None, sinks=None, k_scale=None, v_scale=None,
):
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    hkv, bs = pool_k.shape[1], pool_k.shape[2]
    rows = h * s
    num_kv = tables.shape[1]
    num_groups = num_kv // group
    block_k = group * bs
    quant = k_scale is not None
    # pool_v None: the k rows serve as v too (the MLA latent), and a
    # page is copied once.
    shared = pool_v is None
    dv = d if shared else pool_v.shape[-1]

    qf = _flatten_q(q, hkv)

    in_specs = [
        pl.BlockSpec((1, rows, d), lambda bi, gi, lr, tr: (bi, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),  # k pool stays in HBM
    ]
    operands = [qf, pool_k]
    if not shared:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # v pool too
        operands.append(pool_v)
    if quant:
        in_specs += [
            pl.BlockSpec(memory_space=pl.ANY),  # scale pools too
            pl.BlockSpec(memory_space=pl.ANY),
        ]
        operands += [k_scale, v_scale]
    has_sinks = sinks is not None
    if has_sinks:
        in_specs += [
            pl.BlockSpec((rows, 128), lambda bi, gi, lr, tr: (0, 0)),
        ]
        operands += [_row_sinks(sinks, s)]
    scratch = [
        pltpu.VMEM((rows, dv), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
        pltpu.VMEM((rows, 128), jnp.float32),
    ]
    # Two halves of each gathered tile, one in use, one in flight; the
    # count of live steps whose parity says which.
    scratch += [pltpu.VMEM((2, hkv, block_k, d), pool_k.dtype)]
    if not shared:
        scratch += [pltpu.VMEM((2, hkv, block_k, dv), pool_v.dtype)]
    if quant:
        scratch += [pltpu.VMEM((2, hkv, block_k), jnp.float32),
                    pltpu.VMEM((2, hkv, block_k), jnp.float32)]
    scratch += [pltpu.SemaphoreType.DMA((2, 4 if quant else 2, group)),
                pltpu.SMEM((1,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, num_groups),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, rows, dv), lambda bi, gi, lr, tr: (bi, 0, 0)
        ),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_group_kernel, scale=scale, s=s, hkv=hkv, bs=bs,
            group=group, window=window, num_kv=num_kv, softcap=softcap,
            has_sinks=has_sinks, quant=quant, shared=shared,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, dv), q.dtype),
        interpret=interpret,
        name="decode_paged_group",
    )(index.astype(jnp.int32), tables.astype(jnp.int32), *operands)
    return _unflatten_o(out, b, s, h, dv)


# The most k and v elements a grid step of the grouped kernel gathers
# into VMEM (two such tiles are held, one in use, one in flight: 8 MiB
# of fp32).
PAGED_TILE_ELEMS = 1 << 20


def _paged_group(tables, pool_k, pool_v) -> int:
    """Pages per grid step: aim for a ~512-row kv tile, divide the
    table, and keep a step's k and v tiles within PAGED_TILE_ELEMS
    (pool_v None: v is read out of the k tile and takes no room). On a
    v5e, at 8 kv heads x 128 and 256-row pages 1024 rows took the same
    time as 512; over the 640-lane latent at the batch mix's contexts
    (mean 785 rows) 512 and 640 rows tied, 256 took 34 % longer, 1280
    rows 4 % and 2560 rows 37 %: rows past a slot's length in a tile are
    multiplied all the same (PERF.md, PR 28 and PR 32).
    Returns 1 (one-page kernel) when grouping cannot work: the gather
    lands each page at sublane offset g*bs of the VMEM tile, so bs
    must be a multiple of the dtype's sublane tile (fp32 8, bf16 16,
    int8 32) or Mosaic rejects the slice."""
    num_kv = tables.shape[1]
    hkv, bs, dk = pool_k.shape[1:]
    sublane = 8 * max(1, 4 // jnp.dtype(pool_k.dtype).itemsize)
    if bs % sublane:
        return 1
    page = hkv * bs * (dk + (0 if pool_v is None else pool_v.shape[3]))
    g = min(max(512 // bs, 1), max(PAGED_TILE_ELEMS // page, 1), num_kv)
    while g > 1 and num_kv % g:
        g -= 1
    return g


def _paged_kernel(
    len_ref, tab_ref, q_ref, k_ref, v_ref, *rest,
    scale, s, hkv, block_k, window, num_kv, softcap=None, has_sinks=False,
):
    sink_ref, (o_ref, acc_ref, m_ref, l_ref) = _split_sink_rest(
        rest, has_sinks
    )
    b = pl.program_id(0)
    ki = pl.program_id(1)
    idx = len_ref[b]
    first_ki, last_ki = _live_range(idx, s, block_k, window, num_kv)
    _decode_tile_any(
        idx, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
        scale=scale, s=s, hkv=hkv, block_k=block_k, window=window,
        k_start=ki * block_k, ki=ki, last_ki=last_ki, first_ki=first_ki,
        softcap=softcap, sink_ref=sink_ref,
    )


def _paged_flash(q, pool_k, pool_v, tables, index, scale, window, interpret,
                 softcap=None, sinks=None):
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    hkv = pool_k.shape[1]
    bs = pool_k.shape[2]
    rows = h * s
    num_kv = tables.shape[1]  # logical blocks per slot

    qf = _flatten_q(q, hkv)

    def kv_map(bi, ki, len_ref, tab_ref):
        first_ki, last_ki = _live_range(len_ref[bi], s, bs, window, num_kv)
        ki = jnp.clip(ki, first_ki, last_ki)
        # Indirect through the block table: logical block ki of slot bi
        # lives at pool block tables[bi, ki]. Unallocated entries point
        # at scratch block 0 and are never live.
        return tab_ref[bi, ki], 0, 0, 0

    in_specs = [
        pl.BlockSpec((1, rows, d), lambda bi, ki, lr, tr: (bi, 0, 0)),
        pl.BlockSpec((1, hkv, bs, d), kv_map),
        pl.BlockSpec((1, hkv, bs, d), kv_map),
    ]
    operands = [qf, pool_k, pool_v]
    has_sinks = sinks is not None
    if has_sinks:
        in_specs += [
            pl.BlockSpec((rows, 128), lambda bi, ki, lr, tr: (0, 0)),
        ]
        operands += [_row_sinks(sinks, s)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, num_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, rows, d), lambda bi, ki, lr, tr: (bi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((rows, d), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _paged_kernel, scale=scale, s=s, hkv=hkv, block_k=bs,
            window=window, num_kv=num_kv, softcap=softcap,
            has_sinks=has_sinks,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        interpret=interpret,
        name="decode_paged",
    )(index.astype(jnp.int32), tables.astype(jnp.int32), *operands)
    return _unflatten_o(out, b, s, h, d)


def paged_decode_supported(q, pool_k, *, quant: bool = False) -> bool:
    """Can a compiled paged kernel handle these arrays?"""
    return _paged_shapes_supported(q.shape, pool_k.shape, quant)


def _paged_shapes_supported(q_shape, pool_shape, quant: bool) -> bool:
    b, s, h, d = q_shape
    hkv, bs, dk = pool_shape[1:]
    if d % 64 != 0 or dk != d:
        return False
    if quant and (d % 128 != 0 or bs % 128 != 0):
        # Int8 runs through the grouped-gather kernel only: its tile
        # body is the ref-slicing fast path (full-lane head dims), and
        # each page's scales are DMA'd out of an (n_blocks, hkv, bs)
        # fp32 pool — Mosaic refuses to slice an HBM ref whose lane dim
        # is below the 128-lane tiling (libtpu 0.0.34: "Slice shape
        # along dimension 2 must be aligned to tiling (128)"), and the
        # (hkv, group*bs) VMEM destination needs the same alignment.
        return False
    if h % hkv != 0 or bs % 8 != 0:
        return False
    if hkv * bs > 8192:
        # Same double-buffered VMEM budget the dense path enforces via
        # _pick_block_k; the paged tile is fixed by the pool's page
        # size, so oversized pages must fall back rather than fail to
        # compile.
        return False
    return h * s <= 1024


# The shortest page, in rows, a bf16/fp32 pool reads through the kernel
# under "auto": the shortest a benchmark cell (and the int8 pool) runs.
# On a v5e the kernel also won a tick's micro-timing at 64 and 16 rows
# (docs/decode_performance.md); those wait for an end-to-end reading.
PAGED_KERNEL_MIN_PAGE = 128


def paged_kernel_under_auto(q_shape, pool_shape, pool_dtype) -> bool:
    """The rule of impl="auto" on a backend with compiled Pallas: does a
    pool of this shape and dtype read through the block table in the
    kernel (True), or through the gathered dense view (False)? A pure
    function of (q.shape, pool.shape, pool.dtype): the dispatcher asks
    it, and so does whoever wants to know what the dispatcher will do.

    An int8 pool takes the kernel wherever the kernel can run (the
    gather dequantizes every gathered page every tick). A bf16/fp32
    pool takes it where it wins: the head dimension fills the lanes
    (the grouped kernel's precondition) and a page is long enough for
    a grid step to amortize (PAGED_KERNEL_MIN_PAGE).
    """
    if jnp.dtype(pool_dtype) == jnp.int8:
        return _paged_shapes_supported(q_shape, pool_shape, quant=True)
    return (_paged_shapes_supported(q_shape, pool_shape, quant=False)
            and pool_shape[3] % 128 == 0
            and pool_shape[2] >= PAGED_KERNEL_MIN_PAGE)


def paged_decode_path(q_shape, pool_shape, pool_dtype,
                      impl: str = "auto") -> str:
    """What paged_decode_attention does with these shapes on this
    backend: "paged_kernel" or "gather"."""
    if impl == "flash":
        return "paged_kernel"
    kernel = (impl == "auto" and pallas_supported()
              and paged_kernel_under_auto(q_shape, pool_shape, pool_dtype))
    return "paged_kernel" if kernel else "gather"


def paged_decode_attention(
    q, pool_k, pool_v, tables, index, *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    sinks=None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
    k_scale=None, v_scale=None,
    mesh=None,
):
    """Attention of q (B, s, H, D) against a paged pool via block tables.

    pool_k/v: (n_blocks, Hkv, bs, D); tables: (B, max_blocks) int32;
    index: (B,) pre-write lengths. The kernel walks each slot's table —
    the dense per-slot view is never materialized. impl="auto" takes it
    where paged_kernel_under_auto says it wins and compiled Pallas is
    live, and the gather + masked reference attention elsewhere.

    k_scale/v_scale: (n_blocks, Hkv, bs) fp32 per-token dequant scale
    pools for an int8 pool (see kvcache.QuantPagedKVCache); both or
    neither. The grouped kernel gathers scale pages alongside value
    pages and folds them in after the integer dots (same exact algebra
    as the dense int8 kernel).

    A pool may hold its rows wider than q's (kvcache.held_width: whole
    lane tiles, the pad lanes zeros): q is then zero-extended to the
    pool's width, which changes no logit, `scale` defaults from q's own
    width, and the result comes back as wide as q (its pad lanes, zeros
    under a zero-padded v, dropped).

    `mesh`: the mesh the caller is partitioned over, if any; the kernel
    then runs per shard of slots and heads, every shard over its own
    heads of the whole pool (_kv_axis).
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale come together")
    quant = k_scale is not None
    from shellac_tpu.inference.kvcache import (
        fit_row,
        paged_gather_layer,
        paged_gather_scales,
    )

    width = q.shape[-1]
    if scale is None:
        scale = width ** -0.5
    q = fit_row(q, max(width, pool_k.shape[-1]))

    def narrowed(o):
        return o if o.shape[-1] <= width else o[..., :width]

    if interpret is None:
        interpret = not pallas_supported()
    shapes_ok = paged_decode_supported(q, pool_k, quant=quant)
    if impl == "flash" and not shapes_ok:
        raise ValueError(
            f"impl='flash' unsupported for q={q.shape} "
            f"pool={pool_k.shape} quant={quant}"
        )
    # impl="auto" reads through the table where paged_kernel_under_auto
    # says the kernel wins, and every pool it sends to the gather goes
    # there by decision, silently — but for an int8 pool whose shape
    # disqualifies the kernel: that gather dequantizes every page every
    # tick, which inverts what kv_quant was asked for.
    use_kernel = paged_decode_path(
        q.shape, pool_k.shape, pool_k.dtype, impl) == "paged_kernel"
    if (impl == "auto" and pallas_supported() and not shapes_ok
            and quant):
        # Say so once per shape (warnings' default "once per
        # message+location" dedup), with the actionable constraint
        # named.
        b, s, h, d = q.shape
        hkv, bs, dk = pool_k.shape[1], pool_k.shape[2], pool_k.shape[3]
        warnings.warn(
            "paged_decode_attention: Pallas kernel unavailable for "
            f"q={tuple(q.shape)} int8 pool={tuple(pool_k.shape)} — "
            "falling back to a dense gather + reference attention "
            "(paging's memory win is lost). "
            "Kernel needs: head_dim % 64 == 0 "
            f"(got {d}), pool head_dim == q head_dim (got {dk} vs {d}), "
            f"page block size % 8 == 0 (got {bs}), "
            f"n_heads % kv_heads == 0 (got {h}/{hkv}), "
            f"H*s <= 1024 (got {h * s}), and for int8 pools "
            "head_dim % 128 == 0 with block size % 128 == 0.",
            PagedFallbackWarning,
            stacklevel=2,
        )
    if use_kernel:
        # One array handed in as k and as v (the MLA latent pool) goes
        # on as ONE operand, pool_v None, across a mesh's shards too: the
        # grouped kernel then copies a page once and reads v out of the
        # k tile. (An int8 pool's tiles travel with their scales, apart.)
        kernel_v = None if pool_v is pool_k and not quant else pool_v

        def kernel(q, pool_k, pool_v, tables, index, k_scale, v_scale, sinks):
            # Grouped gather kernel when the head dim keeps full-lane
            # tiles (its tile body is the ref-slicing fast path) and
            # grouping actually amortizes anything; one-page kernel
            # otherwise. Int8 pools always take the grouped kernel (the
            # support gate guarantees its constraints): the one-page
            # kernel's BlockSpec body has no scale plumbing.
            group = (_paged_group(tables, pool_k, pool_v)
                     if q.shape[-1] % 128 == 0 else 1)
            sc = None if softcap is None else float(softcap)
            if group > 1 or quant:
                return _paged_group_flash(
                    q, pool_k, pool_v, tables, index, float(scale), window,
                    max(group, 1), interpret, softcap=sc, sinks=sinks,
                    k_scale=k_scale, v_scale=v_scale,
                )
            return _paged_flash(
                q, pool_k, pool_k if pool_v is None else pool_v, tables,
                index, float(scale), window, interpret, softcap=sc,
                sinks=sinks,
            )

        with jax.named_scope("attn.core"):
            if not on_mesh(mesh):
                return narrowed(kernel(q, pool_k, kernel_v, tables, index,
                                       k_scale, v_scale, sinks))
            kv = _kv_axis(pool_k)
            out = per_shard(kernel, mesh, {
                "q": (q, _Q_AXES),
                "pool_k": (pool_k, (None, kv, None, None)),
                "pool_v": (kernel_v, (None, kv, None, None)),
                "tables": (tables, ("batch", None)),
                "index": (index, ("batch",)),
                "k_scale": (k_scale, (None, kv, None)),
                "v_scale": (v_scale, (None, kv, None)),
                "sinks": (sinks, ("heads",)),
            }, _Q_AXES)
        if out is not None:
            return narrowed(out)
        _mesh_fallback(
            impl, _DEQUANT_EVERY_TICK if quant
            else "gathers every slot's dense view every tick",
            PagedFallbackWarning, q, pool_k, mesh)
    # The XLA path: materialize each slot's dense view through its
    # table, then the masked reference attention over it.
    with jax.named_scope("kv.gather"):
        k_all, v_all = paged_gather_layer(pool_k, pool_v, tables)
        ks_all = vs_all = None
        if quant:
            ks_all = paged_gather_scales(k_scale, tables)
            vs_all = paged_gather_scales(v_scale, tables)
    with jax.named_scope("attn.core"):
        return narrowed(_decode_ref(
            q, k_all, v_all, index, window, scale, softcap=softcap,
            sinks=sinks, k_scale=ks_all, v_scale=vs_all))


@jax.named_scope("attn.core")
def rolled_decode_attention(
    q, cache_k, cache_v, start, lengths_after, *,
    window: int,
    scale: Optional[float] = None,
    softcap: Optional[float] = None,
    sinks=None,
):
    """Attention of q (B, s, H, D) against a RING buffer
    (B, Hkv, ring, D) whose newest position is lengths_after - 1 (the
    chunk was already written). q row j sits at position start + j —
    padded chunks put their REAL rows first, so the start anchors the
    q positions (rows past lengths_after - start are padding whose
    outputs the caller discards).

    Per-slot positions are reconstructed from the ring arithmetic and
    fed to the reference attention — the ring is window-sized, so the
    Pallas decode kernels' dead-block skipping has nothing to win here
    and the masked reference over O(window) keys IS the fast path.
    """
    from shellac_tpu.inference.kvcache import rolled_kv_positions

    b, s = q.shape[:2]
    ring = cache_k.shape[2]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    kv_pos, kv_mask = rolled_kv_positions(lengths_after, ring)
    q_pos = start.astype(jnp.int32)[:, None] + jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32), (b, s)
    )

    def ref_dtype(x):
        # fp32 rings (int8 dequant) keep their precision — the fp32
        # logit einsum upcasts the other operand anyway; only widen
        # narrower inputs to the q dtype.
        return x if x.dtype == jnp.float32 else x.astype(q.dtype)

    return attention_ref(
        q, ref_dtype(cache_k.transpose(0, 2, 1, 3)),
        ref_dtype(cache_v.transpose(0, 2, 1, 3)),
        causal=True, window=window, scale=scale, softcap=softcap,
        sinks=sinks,
        q_positions=q_pos, kv_positions=kv_pos, kv_mask=kv_mask,
    )
