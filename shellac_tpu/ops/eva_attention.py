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

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from shellac_tpu.ops.dispatch import pallas_supported


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


# ---------------------------------------------------------------------------
# the decode kernel
# ---------------------------------------------------------------------------

# Ring rows one copy moves (a slot's valid rows are rounded up to whole
# blocks) and the most rows one step of the arithmetic takes. On a v5e
# at the evabyte cell's shapes (docs/decode_performance.md, "The EVA
# rule") a tick's attention took 6.20 ms at blocks of 128 against 6.69 /
# 6.47 / 7.39 at 64 / 256 / 512 (fewer rows copied past a slot's count,
# against more copies started), and 8.39 in steps of 8 rows.
EVA_RING_BLOCK = 128
_CHUNK = 64


def _eva_decode_kernel(
    nex_ref, npg_ref, tab_ref, col_ref, layer_ref,
    q_ref, rk_hbm, rv_hbm, pk_hbm, pv_hbm, o_ref,
    rk_buf, rv_buf, pk_buf, pv_buf, acc_ref, m_ref, l_ref, sems, cnt_ref,
    *, scale, bw,
):
    """One slot a grid step: its live ring blocks, then its own pages.

    Every operand but q stays in HBM (memory space ANY) as the WHOLE
    layer stack; the kernel copies what the slot attends into VMEM
    itself: ring rows [j bw, (j + 1) bw) of the slot's column for every
    j below ceil(n_exact / bw), and pages tables[b, :n_pages[b]]. A dead
    block or table entry starts no copy. Each kind of tile has two
    halves, one in use, one in flight: a step starts the copies of the
    NEXT tile (this slot's next block, else its first page, else the
    next slot's first block) before it waits for its own. `cnt_ref`
    counts the ring blocks and the pages consumed so far; their parity
    is the half in use. Grid steps run in order on one core.

    At group size 1 (every head its own k and v) there is no matmul row
    to batch: a score is a multiply and a lane reduction a (row, head),
    on the VPU, in float32; (max, sum, accumulator) are float32.
    """
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    h = q_ref.shape[1]
    step = min(_CHUNK, bw)

    def ring_blocks(slot):
        return (nex_ref[slot] + bw - 1) // bw

    def ring_copies(slot, j, half):
        src = (layer, pl.ds(j * bw, bw), col_ref[slot])
        return [
            pltpu.make_async_copy(rk_hbm.at[src], rk_buf.at[half],
                                  sems.at[0, half, 0]),
            pltpu.make_async_copy(rv_hbm.at[src], rv_buf.at[half],
                                  sems.at[0, half, 1]),
        ]

    def page_copies(slot, j, half):
        page = tab_ref[slot, j]
        return [
            pltpu.make_async_copy(pk_hbm.at[layer, :, page], pk_buf.at[half],
                                  sems.at[1, half, 0]),
            pltpu.make_async_copy(pv_hbm.at[layer, :, page], pv_buf.at[half],
                                  sems.at[1, half, 1]),
        ]

    def start(copies):
        for c in copies:
            c.start()

    def wait(copies):
        for c in copies:
            c.wait()

    @pl.when(b == 0)
    def _prime():
        cnt_ref[0] = 0
        cnt_ref[1] = 0
        start(ring_copies(0, 0, 0))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)

    n_exact = nex_ref[b]
    n_blocks = ring_blocks(b)
    n_pages = npg_ref[b]
    qf = q_ref[0].astype(jnp.float32) * scale  # (H, D)

    def start_next_slot(ring_half):
        @pl.when(b + 1 < n_slots)
        def _():
            start(ring_copies(b + 1, 0, ring_half))

    def attend(s, v, vf, at, keepdims):
        """One online-softmax step over the leading axis of scores `s`
        and values `v` (`vf`: v in float32), into the statistics at
        `at` of (max, sum, accumulator)."""
        m_prev = m_ref[at]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=keepdims))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[at] = alpha * l_ref[at] + jnp.sum(p, axis=0, keepdims=keepdims)
        # p in the rows' dtype, as the reference's value einsum has it.
        pv = p.astype(v.dtype).astype(jnp.float32) * vf
        acc_ref[at] = (acc_ref[at] * alpha
                       + jnp.sum(pv, axis=0, keepdims=keepdims))
        m_ref[at] = m_new

    def attend_rows(k, v, valid):
        """k, v: (c, H, D) ring rows, heads on sublanes; valid: None or
        a (c, 1, 1) mask of the rows that exist."""
        s = jnp.sum(k.astype(jnp.float32) * qf, axis=-1, keepdims=True)
        vf = v.astype(jnp.float32)
        if valid is not None:
            s = jnp.where(valid, s, -jnp.inf)
            # A row past the slot's count holds whatever was there: its
            # weight is 0, and 0 * NaN would still poison the sum.
            vf = jnp.where(valid, vf, 0.0)
        attend(s, v, vf, ..., False)  # (c, H, 1) against (H, 1)

    def ring_block(j, carry):
        half = cnt_ref[0] % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            start(ring_copies(b, j + 1, 1 - half))

        @pl.when((j + 1 == n_blocks) & (n_pages > 0))
        def _():
            start(page_copies(b, 0, cnt_ref[1] % 2))

        @pl.when((j + 1 == n_blocks) & (n_pages == 0))
        def _():
            start_next_slot(1 - half)

        wait(ring_copies(b, j, half))
        live = jnp.minimum(n_exact - j * bw, bw)

        def rows(i):
            return pl.ds(pl.multiple_of(i * step, step), step)

        def whole(i, carry):
            attend_rows(rk_buf[half, rows(i)], rv_buf[half, rows(i)], None)
            return carry

        jax.lax.fori_loop(0, live // step, whole, 0)

        @pl.when(live % step > 0)
        def _tail():
            at = jax.lax.broadcasted_iota(jnp.int32, (step, 1, 1), 0)
            attend_rows(rk_buf[half, rows(live // step)],
                        rv_buf[half, rows(live // step)],
                        at < live % step)

        cnt_ref[0] = cnt_ref[0] + 1
        return carry

    jax.lax.fori_loop(0, n_blocks, ring_block, 0)

    def page(j, carry):
        half = cnt_ref[1] % 2

        @pl.when(j + 1 < n_pages)
        def _():
            start(page_copies(b, j + 1, 1 - half))

        @pl.when(j + 1 == n_pages)
        def _():
            start_next_slot(cnt_ref[0] % 2)

        wait(page_copies(b, j, half))

        # Heads are a static loop: a head's statistics are one sublane
        # row of (max, sum, accumulator), and Mosaic takes no dynamic
        # index on a sublane.
        for hi in range(h):
            row = slice(hi, hi + 1)
            k = pk_buf[half, hi].astype(jnp.float32)  # (R, D)
            v = pv_buf[half, hi]
            s = jnp.sum(k * qf[row], axis=-1, keepdims=True)  # (R, 1)
            attend(s, v, v.astype(jnp.float32), row, True)
        cnt_ref[1] = cnt_ref[1] + 1
        return carry

    jax.lax.fori_loop(0, n_pages, page, 0)
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def eva_ring_block(window: int) -> int:
    """Ring rows one copy of the kernel moves."""
    return min(EVA_RING_BLOCK, window)


# The most VMEM the kernel's tiles may ask for (two halves each of a k
# and a v ring block and page, and as much again for the arithmetic):
# under the v5e's 128 MiB with room for the compiler's own.
EVA_VMEM_BOUND = 64 << 20


def _vmem_bytes(block, h, r, d, dtype) -> int:
    tiles = 2 * 2 * (block + r) * h * d * jnp.dtype(dtype).itemsize
    return 2 * tiles + (4 << 20)


def eva_kernel_refusal(q_shape, ring_shape, pool_shape, dtype):
    """Why the kernel cannot take these shapes (None: it can).

    q: (B, H, D); ring: (L, W, S, H, D); pool: (L, H, P, R, D). The
    kernel's own constraints: whole lane tiles a head, whole sublane
    tiles of heads (a ring row is an (H, D) tile) and of pooled rows (a
    page a head is an (R, D) tile), whole blocks a ring, rows of 2 or 4
    bytes, the tiles under EVA_VMEM_BOUND.
    """
    _, h, d = q_shape
    w, r = ring_shape[1], pool_shape[3]
    dt = jnp.dtype(dtype)
    if dt not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return f"rows of {dt.name}: the kernel reads bfloat16 or float32"
    sublanes = 8 * (4 // dt.itemsize)
    if d % 128:
        return f"head dimension {d} is not whole 128-lane tiles"
    if h % sublanes:
        return f"{h} heads are not whole {sublanes}-row tiles of {dt.name}"
    if r % sublanes:
        return (f"a page of {r} pooled rows is not whole {sublanes}-row "
                f"tiles of {dt.name}")
    block = eva_ring_block(w)
    if w % block or block % min(_CHUNK, block):
        return (f"a ring of {w} rows is not whole blocks of {block} rows "
                f"in steps of {min(_CHUNK, block)}")
    if _vmem_bytes(block, h, r, d, dt) > EVA_VMEM_BOUND:
        return (f"tiles of {block} ring rows and {r} pooled rows x {h} "
                f"heads x {d} exceed the VMEM bound")
    return None


def eva_decode_path(q_shape, ring_shape, pool_shape, dtype,
                    impl: str = "auto") -> str:
    """How a decode tick of an EVA model attends, on this backend:
    "eva_kernel" (the Pallas kernel: live rows only) or "xla"
    (`eva_decode_attention`: every ring row and every page, masked). A
    pure function of shapes, dtype, `impl` and the platform: the model
    asks it, and so does whoever wants to know what the model will do.

    "auto": the kernel on a TPU where its constraints hold, the XLA
    form silently everywhere else; "ref": the XLA form; "flash": the
    kernel (interpreted off a TPU), or its refusal raised.
    """
    if impl == "ref":
        return "xla"
    refusal = eva_kernel_refusal(q_shape, ring_shape, pool_shape, dtype)
    if impl == "flash":
        if refusal is not None:
            raise ValueError(
                f"attn_impl='flash': the EVA decode kernel refuses: {refusal}")
        return "eva_kernel"
    return "eva_kernel" if pallas_supported() and refusal is None else "xla"


def eva_decode_kernel(q, ring_k, ring_v, n_exact, pool_k, pool_v, tables,
                      n_pages, *, layer, cols, scale: float,
                      block: int | None = None, interpret: bool | None = None):
    """`eva_decode_attention`'s equations, moving only live rows.

    q: (B, H, D). ring_k, ring_v: the WHOLE ring stacks (L, W, S, H, D);
    pool_k, pool_v: the WHOLE pool stacks (L, H, P, R, D); `layer` a
    scalar: the kernel reads the stacks where they lie (an operand
    sliced out of a scanned stack is a copy). Slot b's ring is column
    cols[b], its valid rows j < n_exact[b] (at least 1), its pages
    tables[b, :n_pages[b]]; entries past that count are never read.
    Interpreted off a TPU unless `interpret` says. Returns (B, H, D) in
    q's dtype.
    """
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = not pallas_supported()
    b, h, d = q.shape
    r = pool_k.shape[3]
    block = eva_ring_block(ring_k.shape[1]) if block is None else block
    any_ = pl.BlockSpec(memory_space=pl.ANY)
    row = pl.BlockSpec((1, h, d), lambda bi, *_: (bi, 0, 0))
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_eva_decode_kernel, scale=scale, bw=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b,),
            in_specs=[row, any_, any_, any_, any_],
            out_specs=row,
            scratch_shapes=[
                pltpu.VMEM((2, block, h, d), ring_k.dtype),
                pltpu.VMEM((2, block, h, d), ring_v.dtype),
                pltpu.VMEM((2, h, r, d), pool_k.dtype),
                pltpu.VMEM((2, h, r, d), pool_v.dtype),
                pltpu.VMEM((h, d), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2, 2)),
                pltpu.SMEM((2,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_vmem_bytes(block, h, r, d, ring_k.dtype),
        ),
        interpret=interpret,
        name="eva_decode",
    )(i32(n_exact), i32(n_pages), i32(tables), i32(cols),
      i32(layer).reshape(1), q, ring_k, ring_v, pool_k, pool_v)
