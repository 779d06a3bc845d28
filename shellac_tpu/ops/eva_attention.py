"""EVA attention (EvaByte; Zheng et al., arXiv:2302.04542): pooling, and
one softmax over two key sets with different validity rules.

Per head, with W the window, C the chunk, s the score scale, q and k
already rotated, phi and mu the head's two learned vectors:

  pooled row of chunk c (positions cC .. cC + C - 1):
      a_j = softmax over the chunk's j of (s k_j . phi)
      K~_c = sum_j a_j k_j + mu          V~_c = sum_j a_j v_j
  query i, in window w = i // W, attends in ONE softmax
      the exact rows   { j : wW <= j <= i }          (its own window)
      the pooled rows  { c : (c + 1) C <= wW }       (every chunk of
                                                      every earlier window)

Three functions: `eva_pool` (chunks -> pooled rows), `eva_attention` (a
whole sequence from position 0: training, scoring, prefill; through
`ops.attention`, so the flash kernel where there is one) and
`eva_decode_attention` (one new row a slot against a ring of exact rows
and a pool of pooled rows; plain jnp: scores and the softmax in
float32, the value sums accumulated in float32, rows kept in the dtype
they arrive in). benchmark/arch/evabyte.py is the independent float32
reference the tests hold these to.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def eva_pool(k, v, phi, mu, scale: float):
    """Pooled rows of whole chunks.

    k, v: (..., C, H, D), one chunk per leading index; phi, mu: (H, D).
    Returns (K~, V~), (..., H, D) each, in k's and v's dtypes.
    """
    kf = k.astype(jnp.float32)
    a = jax.nn.softmax(
        jnp.einsum("...chd,hd->...ch", kf, phi.astype(jnp.float32)) * scale,
        axis=-2,
    )
    kp = jnp.einsum("...ch,...chd->...hd", a, kf) + mu.astype(jnp.float32)
    vp = jnp.einsum("...ch,...chd->...hd", a, v.astype(jnp.float32))
    return kp.astype(k.dtype), vp.astype(v.dtype)


def eva_pool_sequence(k, v, phi, mu, chunk: int, scale: float):
    """Pooled rows of every chunk of a sequence from position 0.

    k, v: (B, S, H, D). Returns (B, ceil(S / chunk), H, D) each; a
    trailing partial chunk is pooled over zero padding, and no query
    ever attends it (it completes, and is pooled again, later).
    """
    b, s, h, d = k.shape
    n = -(-s // chunk)
    pad = ((0, 0), (0, n * chunk - s), (0, 0), (0, 0))
    return eva_pool(
        jnp.pad(k, pad).reshape(b, n, chunk, h, d),
        jnp.pad(v, pad).reshape(b, n, chunk, h, d),
        phi, mu, scale,
    )


def eva_attention(q, k, v, kp, vp, *, window: int, chunk: int,
                  scale: float, impl: str = "auto"):
    """EVA attention of whole sequences that start at position 0.

    q, k, v: (B, S, H, D); kp, vp: (B, N, H, D) with N >= S // chunk,
    the pooled rows of `eva_pool_sequence`. Returns (B, S, H, D) in q's
    dtype.

    Each window becomes one row of a batch: its keys are the pooled
    rows of the whole sequence followed by the window's own exact rows,
    and `ops.attention` runs over that, causal, with a segment row that
    hides the pooled rows of the window itself and of later ones. A
    window's queries stand after every pooled row, so causality shows
    them all the pooled rows their segment admits and the exact rows up
    to themselves: one softmax over both kinds, in the flash kernel
    where the dispatcher has one (the pooled rows' own query positions
    are padding, computed and dropped). A sequence inside one window
    is plain causal attention.
    """
    from shellac_tpu.ops.attention import attention

    b, s, h, d = q.shape
    if -(-s // chunk) * chunk <= window:
        return attention(q, k, v, causal=True, scale=scale, impl=impl)
    n_w = -(-s // window)
    r = window // chunk
    n_p = n_w * r

    def windows(a):  # (B, S, H, D) -> (B, n_w, W, H, D)
        a = jnp.pad(a, ((0, 0), (0, n_w * window - s), (0, 0), (0, 0)))
        return a.reshape(b, n_w, window, h, d)

    def pooled(a):  # (B, N, H, D) -> (B, n_w, n_p, H, D)
        a = jnp.pad(a[:, :n_p], ((0, 0), (0, max(0, n_p - a.shape[1])),
                                 (0, 0), (0, 0)))
        return jnp.broadcast_to(a[:, None], (b, n_w, n_p, h, d))

    def rows(front, back):
        return jnp.concatenate([front, back], axis=2).reshape(
            b * n_w, n_p + window, h, d)

    # Segment 1: what a window's queries may see. Pooled row c belongs
    # to window c // r; window w sees it iff c // r < w.
    seen = (jnp.arange(n_p, dtype=jnp.int32)[None, :] // r
            < jnp.arange(n_w, dtype=jnp.int32)[:, None])
    seg = jnp.concatenate(
        [seen.astype(jnp.int32), jnp.ones((n_w, window), jnp.int32)], axis=1)
    seg = jnp.broadcast_to(seg[None], (b, n_w, n_p + window)).reshape(
        b * n_w, n_p + window)
    out = attention(
        rows(jnp.zeros((b, n_w, n_p, h, d), q.dtype), windows(q)),
        rows(pooled(kp), windows(k)), rows(pooled(vp), windows(v)),
        causal=True, scale=scale, q_segments=seg, kv_segments=seg, impl=impl,
    )
    return out[:, n_p:].reshape(b, n_w * window, h, d)[:, :s]


def eva_decode_attention(q, ring_k, ring_v, n_exact, pool_k, pool_v, owned,
                         *, scale: float):
    """One new row a slot against its ring and the pool.

    q: (B, H, D). ring_k, ring_v: (W, B, H, D), the slots' windows
    position-outermost, slot b's row j valid iff j < n_exact[b].
    pool_k, pool_v: (H, P, R, D), pages of R pooled rows,
    head-outermost; owned: (B, P) bool, slot b attends every row of
    page p (a page is one completed window's rows). Returns (B, H, D)
    in q's dtype.

    Every slot is scored against every page and the pages a slot does
    not own are masked: the pool is read once, where it lies, as one
    matmul with the heads as its batch, with no per-slot gather of
    pages. That costs B / (pages a slot owns) more FLOPs than a gather
    would and far fewer bytes; decode attention is bound by bytes up to
    a few hundred slots.
    """
    se = jnp.einsum("bhd,wbhd->bhw", q, ring_k,
                    preferred_element_type=jnp.float32) * scale
    w = ring_k.shape[0]
    se = jnp.where(
        jnp.arange(w, dtype=jnp.int32)[None, None, :] < n_exact[:, None, None],
        se, -jnp.inf,
    )
    sp = jnp.einsum("bhd,hprd->bhpr", q, pool_k,
                    preferred_element_type=jnp.float32) * scale
    sp = jnp.where(owned[:, None, :, None], sp, -jnp.inf)
    # One softmax over both score sets.
    m = jnp.maximum(se.max(axis=-1), sp.max(axis=(-2, -1)))
    pe = jnp.exp(se - m[..., None])
    pp = jnp.exp(sp - m[..., None, None])
    den = pe.sum(axis=-1) + pp.sum(axis=(-2, -1))
    o = (jnp.einsum("bhw,wbhd->bhd", pe.astype(ring_v.dtype), ring_v,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhpr,hprd->bhd", pp.astype(pool_v.dtype), pool_v,
                      preferred_element_type=jnp.float32))
    return (o / den[..., None]).astype(q.dtype)
