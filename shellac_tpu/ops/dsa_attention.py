"""Learned sparse attention (cfg.dsa): an indexer picks the rows a query
attends.

Per layer, beside q / k / v, a token t has `J = index_heads` index
queries u_{t,j} and a per-head weight w_{t,j}, and leaves ONE index key
c_t (`index_dim` wide) in the cache next to its k and v rows. A query's
score of an earlier position s is

    I[t, s] = sum_j w[t, j] * relu(u[t, j] . c[s])            (float32)

and the query attends S_t = every s <= t while t + 1 <= topk, else the
topk positions of largest I[t, s], ties to the lower position (what
jax.lax.top_k does). One set a query a layer, shared by all heads; the
softmax runs over S_t alone. This is the DeepSeek-Sparse-Attention
indexer (one index key a token, ReLU'd per-head dot products weighted by
a learned weight of the query token, a token-level top-k) over GQA.

Three call shapes share the pieces here:

* a run of queries against keys it can hold densely (`forward`, a whole
  prompt, a cached chunk against the slot's gathered rows):
  `choice_mask` scores in query blocks (on the TPU one Pallas kernel,
  `index_scores_flash`, that keeps a tile's per-head products in VMEM),
  turns each row of scores into a mask by finding the topk-th largest score as a THRESHOLD (a bitwise
  bisection: 32 counting passes, no sort; ties cut by position), and
  runs softmax attention under the mask: `masked_attention`, a Pallas
  flash kernel on the TPU (kv blocks past the causal frontier skipped),
  the plain reference elsewhere;
* a decode tick, one query a slot: `select_rows` takes the choice as
  row numbers (jax.lax.top_k over the slot's scores), the caller gathers
  those k / v rows by (page, offset), `attend_rows` is the softmax over
  them.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from shellac_tpu.ops.dispatch import pallas_supported

NEG_INF = -2.0e38
#: Where the masked flash kernel's running max starts: under any score,
#: over NEG_INF by enough that exp(NEG_INF - MAX_FLOOR) is 0.
MAX_FLOOR = -1.0e30
#: Query rows a block of index scores covers: (block, J, Sk) float32
#: products are the largest temporary of a long prompt's chunk.
SCORE_BLOCK = 256
BLOCK_Q = 256
BLOCK_K = 512
#: Key rows a step of the masked flash kernel takes where the keys are
#: whole tiles of them (else BLOCK_K): on the v5e a 4096-query chunk at
#: offset 13,312 took 8.9 ms with 1024 and 13.8 with 512 (17.2 and 27.5
#: at offset 29,696).
FLASH_BLOCK_K = 1024


def index_scores(u, w, c_t):
    """I (B, Sq, Sk) float32. u: (B, Sq, J, Di) index queries; w: (B, Sq,
    J) their weights; c_t: (B, Di, Sk) index keys, key axis innermost (as
    the cache holds them)."""
    s = jnp.einsum("bqjd,bdk->bqjk", u, c_t,
                   preferred_element_type=jnp.float32)
    # The sum over index heads in float32 proper: as an einsum the TPU
    # would round both factors to bfloat16 first.
    return jnp.sum(jax.nn.relu(s) * w.astype(jnp.float32)[..., None], axis=2)


def _kernel_wanted(impl: str, interpret: Optional[bool]):
    """(take the Pallas kernel?, interpret it?) for a dispatcher's
    `impl` ("auto" | "flash" | "ref")."""
    if interpret is None:
        interpret = not pallas_supported()
    return impl == "flash" or (impl == "auto" and pallas_supported()), interpret


def _rankable(scores, allowed):
    """The scores as the choice ranks them: float32, -0.0 counted as 0.0
    (a float comparison's order; a sort's total order would put it
    below), disallowed rows at -inf."""
    s = jnp.where(scores == 0, 0.0, scores).astype(jnp.float32)
    return jnp.where(allowed, s, -jnp.inf)


def _sortable(scores, allowed):
    """int32 keys ordered as the ranked scores are (disallowed rows
    lowest but one)."""
    s = _rankable(scores, allowed)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def topk_mask(scores, allowed, k: int):
    """True at the k allowed positions of the last axis with the largest
    score, ties to the lower position; at every allowed position where
    there are no more than k. scores (..., S) float32, allowed (..., S)
    bool.

    The k-th largest key T is built bit by bit from the top (for each
    bit: does setting it still leave k keys at or above?), 32 counting
    passes over the row; rows above T are in, rows equal to T fill what
    is left in position order. No sort: a sort of every query's row is
    what a long prompt cannot afford."""
    size = scores.shape[-1]
    if size <= k:
        return allowed
    key = _sortable(scores, allowed)
    lowest = jnp.int32(-2 ** 31)

    def count_ge(t):
        return jnp.sum((key >= t).astype(jnp.int32), axis=-1, keepdims=True)

    t = jnp.where(count_ge(jnp.int32(0)) >= k, jnp.int32(0), lowest)
    t = jnp.broadcast_to(t, (*scores.shape[:-1], 1))

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(count_ge(cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 31, bit, t)
    above = key > t
    tied = key == t
    room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
    n_tied = jnp.sum(tied.astype(jnp.int32), axis=-1, keepdims=True)

    def cut(tied):
        return tied & (jnp.cumsum(tied.astype(jnp.int32), axis=-1) <= room)

    # Only a row with more rows tied at T than it has room for needs the
    # running count; exact float ties are rare.
    tied = jax.lax.cond(jnp.any(n_tied > room), cut, lambda x: x, tied)
    return (above | tied) & allowed


def select_rows(scores, allowed, k: int):
    """The decode tick's choice as row numbers: (rows (B, K) int32, ok
    (B, K) bool), K = min(k, S); `ok` is False where a slot has fewer
    than K allowed rows. Ties to the lower position."""
    s = _rankable(scores, allowed)
    vals, rows = jax.lax.top_k(s, min(k, s.shape[-1]))
    return rows.astype(jnp.int32), vals > -jnp.inf


def attend_rows(q, k_rows, v_rows, ok, scale: float):
    """Softmax attention of one query a slot over its chosen rows.
    q: (B, H, D); k_rows, v_rows: (B, K, Hkv, D); ok: (B, K) bool.
    Returns (B, H, D) in q's dtype."""
    b, h, d = q.shape
    hkv = k_rows.shape[2]
    qg = q.reshape(b, hkv, h // hkv, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k_rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bkhd->bhgd", p.astype(v_rows.dtype), v_rows,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, h, d).astype(q.dtype)


def gather_rows(pool, block, offset):
    """A slot's chosen rows out of a paged pool, by (page, offset).
    pool: (N, 1, bs, W), a token's whole row innermost; block, offset:
    (B, K) int32. Returns (B, K, W): one slice a chosen row."""
    n, _, bs, width = pool.shape
    return jnp.take(pool.reshape(n * bs, width), block * bs + offset, axis=0)


def masked_attention_ref(q, k, v, mask, scale: float):
    """Softmax attention under an explicit mask, the plain way.
    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D); mask: (B, Sq, Sk) bool."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, sq, hkv, h // hkv, d)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# The Pallas kernel: flash attention under an explicit (query, key) mask
# ---------------------------------------------------------------------------


def _masked_flash_kernel(live_ref, q_ref, k_ref, v_ref, mask_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale, group, block_q,
                         num_q, num_kv, hkv):
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_live = live_ref[(bh // hkv) * num_q + qi]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, MAX_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki < n_live)
    def _compute():
        kb, vb = k_ref[0], v_ref[0]
        keep = mask_ref[0].astype(jnp.float32) > 0.0  # (block_q, block_k)
        # The q heads of this kv head are the rows of ONE matmul against
        # the key tile (it is pushed to the MXU once for all of them) and
        # share the mask tile.
        s = jax.lax.dot_general(
            q_ref[0, 0], kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(keep[None], s.reshape(group, block_q, -1),
                      NEG_INF).reshape(group * block_q, -1)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # The running max starts at MAX_FLOOR, far above NEG_INF: a
        # masked entry's exp underflows to exactly 0 even in a row that
        # has kept nothing yet, with no gate on s.
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def masked_flash_supported(q, k, mask) -> bool:
    """Shapes the compiled kernel takes: whole blocks, full-lane heads."""
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    return (d % 128 == 0 and h % hkv == 0 and sq % BLOCK_Q == 0
            and sk % BLOCK_K == 0 and mask.dtype == jnp.int8)


def flash_block_k(sk: int) -> int:
    """Key rows a step of the masked flash kernel takes over `sk` keys."""
    return FLASH_BLOCK_K if sk % FLASH_BLOCK_K == 0 else BLOCK_K


def masked_flash(q, k, v, mask, live, scale: float, interpret: bool):
    """q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D), read where they lie (a
    head is a block of lanes of a token's row); mask: (B, Sq, Sk) int8
    (nonzero = attend); live: (B, Sq // BLOCK_Q) int32, the kv blocks of
    flash_block_k(Sk) rows each query block visits (blocks at or past it
    hold no kept key: neither computed nor fetched)."""
    from jax.experimental.pallas import tpu as pltpu

    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq, bk = BLOCK_Q, flash_block_k(sk)
    nq, nk = sq // bq, sk // bk
    # (B, Sq, H, D) -> (B * Hkv, nq, G * bq, D): a kv head's q heads
    # stacked as the rows of one block.
    qf = q.reshape(b, nq, bq, hkv, g, d).transpose(0, 3, 1, 4, 2, 5)
    qf = qf.reshape(b * hkv, nq, g * bq, d)
    kf = k.reshape(b, sk, hkv * d)
    vf = v.reshape(b, sk, hkv * d)

    def clamp(bh, qi, ki, live_ref):
        return jnp.minimum(
            ki, jnp.maximum(live_ref[(bh // hkv) * nq + qi] - 1, 0))

    def kv_map(bh, qi, ki, live_ref):
        return bh // hkv, clamp(bh, qi, ki, live_ref), bh % hkv

    def mask_map(bh, qi, ki, live_ref):
        return bh // hkv, qi, clamp(bh, qi, ki, live_ref)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g * bq, d),
                         lambda bh, qi, ki, live_ref: (bh, qi, 0, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bq, bk), mask_map),
        ],
        out_specs=pl.BlockSpec((1, 1, g * bq, d),
                               lambda bh, qi, ki, live_ref: (bh, qi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g * bq, d), jnp.float32),
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _masked_flash_kernel, scale=scale, group=g, block_q=bq,
            num_q=nq, num_kv=nk, hkv=hkv,
        ),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="dsa_masked_flash",
    )(live.reshape(-1).astype(jnp.int32), qf, kf, vf, mask)
    out = out.reshape(b, hkv, nq, g, bq, d).transpose(0, 2, 4, 1, 3, 5)
    return out.reshape(b, sq, h, d)


def masked_attention(q, k, v, mask, q_pos, scale: float,
                     impl: str = "auto", interpret: Optional[bool] = None):
    """Softmax attention of q (B, Sq, H, D) over k, v (B, Sk, Hkv, D)
    under `mask` (B, Sq, Sk) int8. `q_pos` (B, Sq) int32, the queries' positions, bounds the keys a query block can
    reach (the mask already holds the causal cut; this only lets the
    kernel skip the blocks past it)."""
    use_kernel, interpret = _kernel_wanted(impl, interpret)
    if use_kernel and masked_flash_supported(q, k, mask):
        sk = k.shape[1]
        return masked_flash(q, k, v, mask,
                            _live_blocks(q_pos, sk, flash_block_k(sk)),
                            float(scale), interpret)
    return masked_attention_ref(q, k, v, mask != 0, scale)


def _index_scores_kernel(live_ref, u_ref, w_ref, c_ref, o_ref, *, heads,
                         num_q):
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki < live_ref[b * num_q + qi])
    def _compute():
        c = c_ref[0]  # (Di, block_k)
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        # One index head after another against the same key tile: the
        # products of a head never leave VMEM.
        for j in range(heads):
            s = jax.lax.dot_general(
                u_ref[0, j], c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            acc = acc + jnp.maximum(s, 0.0) * w_ref[0, j]
        o_ref[0] = acc


def index_scores_flash(u, w, c_t, live, interpret: bool):
    """index_scores as a Pallas kernel over (query block, key block)
    tiles: u (B, Sq, J, Di), w (B, Sq, J), c_t (B, Di, Sk) -> (B, Sq, Sk)
    float32. `live` (B, Sq // BLOCK_Q) int32: the key blocks a query
    block can reach; the tiles past them are neither computed nor
    fetched, and hold whatever the buffer held (every reader masks them
    by position)."""
    from jax.experimental.pallas import tpu as pltpu

    b, sq, heads, di = u.shape
    sk = c_t.shape[-1]
    bq, bk = BLOCK_Q, BLOCK_K
    nq, nk = sq // bq, sk // bk
    uf = u.transpose(0, 2, 1, 3)  # (B, J, Sq, Di)
    wf = w.astype(jnp.float32).transpose(0, 2, 1)[..., None]  # (B, J, Sq, 1)

    def clamp(bi, qi, ki, live_ref):
        return jnp.minimum(ki, jnp.maximum(live_ref[bi * nq + qi] - 1, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nq, nk),
        in_specs=[
            pl.BlockSpec((1, heads, bq, di),
                         lambda bi, qi, ki, live_ref: (bi, 0, qi, 0)),
            pl.BlockSpec((1, heads, bq, 1),
                         lambda bi, qi, ki, live_ref: (bi, 0, qi, 0)),
            pl.BlockSpec((1, di, bk),
                         lambda bi, qi, ki, live_ref:
                         (bi, 0, clamp(bi, qi, ki, live_ref))),
        ],
        out_specs=pl.BlockSpec((1, bq, bk),
                               lambda bi, qi, ki, live_ref: (bi, qi, ki)),
    )
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, heads=heads, num_q=nq),
        out_shape=jax.ShapeDtypeStruct((b, sq, sk), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="dsa_index_scores",
    )(live.reshape(-1).astype(jnp.int32), uf, wf, c_t)


def live_widths(cap: int, topk: int, unit: int):
    """The key extents a cached run's attention is compiled for, ascending,
    `cap` last: the quarters of the slot's rows in whole `unit`s (pages;
    whole FLASH_BLOCK_K tiles too where the table is), those that hold
    more rows than are kept. A chunk early in a long table scores, ranks
    and attends the smallest extent that holds its rows, not the table:
    the threshold search is 32 passes over every column it is given, live
    or not."""
    step = math.lcm(unit, FLASH_BLOCK_K)
    if cap % step:
        step = unit
    out = []
    for quarters in (1, 2, 3, 4):
        w = min(cap, -(-cap * quarters // (4 * step)) * step)
        if w > topk and w not in out:
            out.append(w)
    return tuple(out) or (cap,)


def _live_blocks(q_pos, sk: int, block_k: int = BLOCK_K):
    """(B, Sq // BLOCK_Q) int32: the key blocks of `block_k` each query
    block can reach (its last query's position bounds them)."""
    b, sq = q_pos.shape
    reach = jnp.max(q_pos.reshape(b, sq // BLOCK_Q, BLOCK_Q), axis=-1)
    return jnp.minimum(reach // block_k + 1, sk // block_k)


def choice_mask(u, w, c_t, q_pos, k_len, topk: int, sk: int,
                impl: str = "auto", interpret: Optional[bool] = None):
    """The choice of every query of a run as a mask, (B, Sq, Sk) int8:
    1 where query (b, i) attends key position s. u: (B, Sq, J, Di); w:
    (B, Sq, J); c_t: (B, Di, Sk); q_pos: (B, Sq) the queries' positions;
    k_len: (B,) the keys that exist (positions below it); sk: the key
    positions held. Scored and chosen in blocks of SCORE_BLOCK queries;
    with no more keys held than are kept there is nothing to score
    (c_t may be None). On the TPU (or impl="flash"), and where the run
    is whole blocks, the scores come from the kernel in one call."""
    b, sq = q_pos.shape
    k_pos = jnp.arange(sk, dtype=jnp.int32)

    def allowed_of(qp):
        return ((k_pos[None, None, :] <= qp[:, :, None])
                & (k_pos[None, None, :] < k_len[:, None, None]))

    if sk <= topk:
        return allowed_of(q_pos).astype(jnp.int8)

    blk = SCORE_BLOCK if sq % SCORE_BLOCK == 0 else sq
    n = sq // blk

    def split(a):  # (B, Sq, ...) -> (n, B, blk, ...)
        return jnp.moveaxis(a.reshape(b, n, blk, *a.shape[2:]), 1, 0)

    def choose(scores, qp):
        with jax.named_scope("dsa.select"):
            return topk_mask(scores, allowed_of(qp), topk).astype(jnp.int8)

    use_kernel, interpret = _kernel_wanted(impl, interpret)
    if use_kernel and sq % BLOCK_Q == 0 and sk % BLOCK_K == 0:
        with jax.named_scope("dsa.score"):
            scores = index_scores_flash(u, w, c_t, _live_blocks(q_pos, sk),
                                        interpret)
        out = jax.lax.map(lambda a: choose(*a), (split(scores), split(q_pos)))
    else:
        def block(args):
            u_b, w_b, qp = args
            with jax.named_scope("dsa.score"):
                scores = index_scores(u_b, w_b, c_t)
            return choose(scores, qp)

        out = jax.lax.map(block, (split(u), split(w), split(q_pos)))
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, sk)
