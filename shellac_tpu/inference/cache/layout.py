"""KV cache for autoregressive decoding.

Layout: stacked over layers and HEAD-MAJOR, (L, B, Hkv, max_len, Dh).
Stacking over layers matches the stacked-layer parameter layout so the
decode forward remains a single `lax.scan`. Head-major (head before
sequence) is a hard requirement of the compiled Pallas decode kernels:
Mosaic block shapes must keep the last two dims tileable, so the kv
stream a kernel DMAs has to be a contiguous (seq_block, head_dim) tile
per head — with seq-major layout the head axis lands second-to-last
with block size 1, which the TPU lowering rejects (and a relayout copy
of a multi-GiB cache every tick is exactly what the kernel exists to
avoid). The cache lives in compute dtype (bf16): it is read-only
bandwidth, and attention logits accumulate in fp32 regardless.

Ragged batches are handled with per-sequence `lengths`: prompts are
right-padded and written from offset 0; `lengths` records how many slots
are real. Decode writes each sequence's next token at its own length
(vmapped dynamic_update_slice), overwriting stale pad slots, so position
ids stay continuous per sequence and pads are never attended.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from shellac_tpu.config import ModelConfig


@flax.struct.dataclass
class KVCache:
    k: Any  # (L, B, Hkv, max_len, Dh)
    v: Any  # (L, B, Hkv, max_len, Dh)
    lengths: Any  # (B,) int32 — valid positions per sequence

    @property
    def max_len(self) -> int:
        return self.k.shape[3]


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> KVCache:
    head = (cfg.cache_layers, batch, cfg.cache_kv_heads, max_len)
    return KVCache(
        k=jnp.zeros((*head, cfg.cache_head_dim), cfg.compute_dtype),
        # MLA: v is a zero-width placeholder — values re-expand from the
        # latent the k cache already stores (transformer._block).
        v=jnp.zeros((*head, cfg.cache_v_head_dim), cfg.compute_dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def cache_logical_axes(cfg: Optional[ModelConfig] = None):
    """Logical axes for sharding the cache over a mesh.

    Under MLA the cache is one shared latent row per token (head axis
    of size 1) — it replicates over tp instead of sharding; the
    per-head work stays tp-sharded through the q/o projections. Pass
    the cfg to get that right; None keeps the standard kv_heads axes.
    """
    heads = "kv_heads" if cfg is None or cfg.mla is None else None
    return KVCache(
        k=("layers", "batch", heads, None, None),
        v=("layers", "batch", heads, None, None),
        lengths=("batch",),
    )


# ---------------------------------------------------------------------------
# Int8-quantized cache (serving memory/bandwidth: half of bf16)
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class QuantKVCache:
    """KV cache stored int8 with one fp32 scale per written token/head.

    Same head-major layout and write-at-own-length contract as KVCache;
    k/v hold symmetric int8 (scale = amax/127 over the head_dim axis,
    computed at write time — K is quantized AFTER RoPE so dequantized
    reads reproduce the rotated values directly). Decode is HBM-bound
    on cache reads, so int8 halves both the resident footprint (double
    the servable slots*context) and the stream the attention pays per
    tick; the logits dot runs fp32 with the per-token scale folded in
    after (exact algebra: sum_d q*k_int*s == s * sum_d q*k_int).
    """

    k: Any  # (L, B, Hkv, max_len, Dh) int8
    v: Any  # (L, B, Hkv, max_len, Dh) int8
    ks: Any  # (L, B, Hkv, max_len) fp32 — k dequant scale per token
    vs: Any  # (L, B, Hkv, max_len) fp32
    lengths: Any  # (B,) int32

    @property
    def max_len(self) -> int:
        return self.k.shape[3]


def init_quant_cache(cfg: ModelConfig, batch: int, max_len: int) -> QuantKVCache:
    head = (cfg.cache_layers, batch, cfg.cache_kv_heads, max_len)
    return QuantKVCache(
        k=jnp.zeros((*head, cfg.cache_head_dim), jnp.int8),
        v=jnp.zeros((*head, cfg.cache_v_head_dim), jnp.int8),
        ks=jnp.zeros(head, jnp.float32),
        vs=jnp.zeros(head, jnp.float32),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def quant_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    heads = "kv_heads" if cfg is None or cfg.mla is None else None
    return QuantKVCache(
        k=("layers", "batch", heads, None, None),
        v=("layers", "batch", heads, None, None),
        ks=("layers", "batch", heads, None),
        vs=("layers", "batch", heads, None),
        lengths=("batch",),
    )


def kv_field_names(kv_quant=None):
    """The value/scale field names shared by the dense and paged cache
    kinds — the ONE definition the engines' field-tuple plumbing
    (pipelined stage splits, paged beam CoW, prefill scatters) keys
    on, so a new cache field cannot silently miss a path."""
    return ("k", "v", "ks", "vs") if kv_quant == "int8" else ("k", "v")


def init_cache_for(cfg: ModelConfig, batch: int, max_len: int,
                   kv_quant=None, rolling: bool = False,
                   chunk_slack: int = 1):
    """The engines' cache constructor: dense bf16, int8, or a rolling
    ring buffer (sliding-window models) by flags; an EVA model's slot
    cache is its ring-plus-pool state with every slot's pages its own."""
    if cfg.eva is not None:
        if rolling or kv_quant is not None:
            raise ValueError(
                "EVA models keep their own state (a ring of exact rows "
                "and a pool of pooled rows); rolling / kv_quant do not "
                "apply"
            )
        return init_eva_slot_cache(cfg, batch, max_len)
    if cfg.dsa is not None:
        if rolling or kv_quant is not None:
            raise ValueError(
                "a model with an indexer (cfg.dsa) keeps bf16 paged pools "
                "with one index key a token; rolling / kv_quant do not "
                "apply"
            )
        return init_paged_slot_cache(cfg, batch, max_len)
    if rolling:
        if kv_quant is not None and kv_quant != "int8":
            raise ValueError(f"kv_quant={kv_quant!r}; have None, 'int8'")
        patterned = (cfg.attn_pattern is not None
                     and "full" in cfg.attn_pattern)
        if kv_quant == "int8":
            if patterned:
                return init_quant_patterned_cache(
                    cfg, batch, max_len, chunk_slack=chunk_slack
                )
            return init_quant_rolling_cache(cfg, batch, max_len,
                                            chunk_slack=chunk_slack)
        if patterned:
            return init_patterned_cache(cfg, batch, max_len,
                                        chunk_slack=chunk_slack)
        return init_rolling_cache(cfg, batch, max_len,
                                  chunk_slack=chunk_slack)
    if kv_quant == "int8":
        return init_quant_cache(cfg, batch, max_len)
    if kv_quant is not None:
        raise ValueError(f"kv_quant={kv_quant!r}; have None, 'int8'")
    return init_cache(cfg, batch, max_len)


def cache_logical_axes_for(cfg: ModelConfig, kv_quant=None,
                           rolling: bool = False):
    """Logical axes matching what init_cache_for builds for the same
    flags — the single place the cache-kind dispatch lives, so jit
    out_shardings can never desync from the cache pytree."""
    if cfg.eva is not None:
        return eva_cache_logical_axes(cfg)
    if cfg.dsa is not None:
        return paged_cache_logical_axes(cfg)
    if rolling:
        patterned = (cfg.attn_pattern is not None
                     and "full" in cfg.attn_pattern)
        if kv_quant == "int8":
            if patterned:
                return quant_patterned_cache_logical_axes(cfg)
            return quant_rolling_cache_logical_axes(cfg)
        if patterned:
            return patterned_cache_logical_axes(cfg)
        return rolling_cache_logical_axes(cfg)
    if kv_quant == "int8":
        return quant_cache_logical_axes(cfg)
    return cache_logical_axes(cfg)


def quantize_kv(x: jax.Array):
    """(B, S, Hkv, Dh) -> int8 values + (B, S, Hkv) fp32 scales.

    Zero-width inputs (MLA's v placeholder) quantize to a zero-width
    int8 array with unit scales — an empty-axis amax would be -inf.
    """
    if x.shape[-1] == 0:
        return (jnp.zeros(x.shape, jnp.int8),
                jnp.ones(x.shape[:-1], jnp.float32))
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(
        jnp.round(xf / scale[..., None]), -127.0, 127.0
    ).astype(jnp.int8)
    return q, scale


def quant_update_layer(
    cache_k, cache_v, cache_ks, cache_vs,  # one layer's (B, Hkv, len[, Dh])
    k_new, v_new,  # (B, S, Hkv, Dh) unquantized
    index,  # (B,) int32
):
    """Quantize S new positions and write them at per-sequence offsets."""
    kq, ks = quantize_kv(k_new)
    vq, vs = quantize_kv(v_new)
    ck, cv = update_layer(cache_k, cache_v, kq, vq, index)

    def upd(c, n, i):
        return jax.lax.dynamic_update_slice(c, n, (0, i))

    cks = jax.vmap(upd)(cache_ks, ks.transpose(0, 2, 1), index)
    cvs = jax.vmap(upd)(cache_vs, vs.transpose(0, 2, 1), index)
    return ck, cv, cks, cvs


def paged_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    """Logical axes for sharding a paged cache over a mesh.

    The KV pools shard over kv_heads (tensor parallelism), same as the
    dense cache (replicated under MLA — one shared latent row); the
    block axis is scheduler-addressed (host-side free list picks
    arbitrary block ids) so it stays unsharded, and the tables/lengths
    are tiny scheduler metadata, replicated.
    """
    # (Replicated under MLA, one shared latent row, and with an indexer,
    # whose one row a token holds every kv head.)
    heads = ("kv_heads" if cfg is None
             or (cfg.mla is None and cfg.dsa is None) else None)
    return PagedKVCache(
        k=("layers", None, heads, None, None),
        v=("layers", None, heads, None, None),
        tables=(None, None),
        lengths=(None,),
        idx=(("layers", None, None, None)
             if cfg is not None and cfg.dsa is not None else None),
    )


def update_layer(
    cache_k: jax.Array,  # (B, Hkv, max_len, Dh) — one layer's cache
    cache_v: jax.Array,
    k_new: jax.Array,  # (B, S, Hkv, Dh)
    v_new: jax.Array,
    index: jax.Array,  # (B,) int32 — per-sequence write offset
):
    """Write S new positions at per-sequence offsets; returns (k, v)."""
    k_new = k_new.astype(cache_k.dtype).transpose(0, 2, 1, 3)  # (B,Hkv,S,Dh)
    v_new = v_new.astype(cache_v.dtype).transpose(0, 2, 1, 3)

    def upd(c, n, i):
        return jax.lax.dynamic_update_slice(c, n, (0, i, 0))

    ck = jax.vmap(upd)(cache_k, k_new, index)
    cv = jax.vmap(upd)(cache_v, v_new, index)
    return ck, cv


def scatter_slot(cache, mini, slot):
    """Write a batch-1 mini-cache into `slot` of a slot cache.

    Works for KVCache and QuantKVCache alike (the serving engines use
    it so their prefill programs stay cache-type-agnostic).
    """

    def upd(c, n):
        return jax.lax.dynamic_update_slice_in_dim(c, n, slot, axis=1)

    if isinstance(cache, QuantPatternedKVCache):
        fields = {n: upd(getattr(cache, n), getattr(mini, n))
                  for n in ("kw", "vw", "kws", "vws",
                            "kf", "vf", "kfs", "vfs")}
    elif isinstance(cache, PatternedKVCache):
        fields = {n: upd(getattr(cache, n), getattr(mini, n))
                  for n in ("kw", "vw", "kf", "vf")}
    else:
        fields = {"k": upd(cache.k, mini.k), "v": upd(cache.v, mini.v)}
        if isinstance(cache, (QuantKVCache, QuantRollingKVCache)):
            fields.update(ks=upd(cache.ks, mini.ks),
                          vs=upd(cache.vs, mini.vs))
    fields["lengths"] = jax.lax.dynamic_update_slice(
        cache.lengths, mini.lengths, (slot,))
    return cache.replace(**fields)


def slot_view(cache, slot, lengths):
    """Batch-1 view of one slot's rows, with `lengths` (1,) overriding
    the stored per-slot lengths (chunked-prefill continuations resume
    from an explicit offset)."""

    def sl(c):
        return jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1)

    if isinstance(cache, QuantPatternedKVCache):
        fields = {n: sl(getattr(cache, n))
                  for n in ("kw", "vw", "kws", "vws",
                            "kf", "vf", "kfs", "vfs")}
    elif isinstance(cache, PatternedKVCache):
        fields = {n: sl(getattr(cache, n))
                  for n in ("kw", "vw", "kf", "vf")}
    else:
        fields = {"k": sl(cache.k), "v": sl(cache.v)}
        if isinstance(cache, (QuantKVCache, QuantRollingKVCache)):
            fields.update(ks=sl(cache.ks), vs=sl(cache.vs))
    fields["lengths"] = lengths.astype(jnp.int32)
    return cache.replace(**fields)


# ---------------------------------------------------------------------------
# Paged cache (block pool + per-sequence block tables)
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class PagedKVCache:
    """Block-pool KV cache: slots map to pool blocks via tables.

    A dense slot cache reserves max_len for every slot; the pool is
    sized to the *total* tokens actually resident, so many short
    requests and a few long ones share memory. Block allocation is a
    host-side free list (see PagedBatchingEngine); the device side only
    ever sees the tables.

    k, v: (L, n_blocks, Hkv, block_size, W) — head-major inside each
        block, same Pallas tiling requirement as the dense cache. W is
        held_width(Dh): a row wider than one 128-lane tile is held at a
        whole number of tiles (the MLA latent's 576 lanes at 640; 128,
        256, 512 as they are). The device keeps such a pool
        row-innermost, the form paged_write pins and a Mosaic operand
        takes where it lies; at 576 it kept the page's token axis
        innermost and copied the WHOLE pool in front of either (PERF.md,
        PR 32). The pad lanes hold zeros and stay zeros: the pool starts
        as zeros, the scratch block included, and every writer
        zero-extends its rows (fit_row), so a reader that zero-extends
        its queries gets the logits of the narrow row and zeros in the
        output's pad lanes. The int8 pool (QuantPagedKVCache) keeps its
        rows as wide as the model's.
    tables: (n_slots, max_blocks) int32 — pool block id per logical
        block; unallocated entries MUST point at block 0 (reserved as
        scratch: it is never handed to a slot, so stray writes and reads
        through unallocated table entries land there harmlessly).
    lengths: (n_slots,) int32 — valid tokens per slot.
    idx: None, or for a model with an indexer (cfg.dsa) a third pool
        under the SAME tables, (L, n_blocks, index_dim, block_size): one
        index key a token, a page's keys held key-axis innermost. That
        is the form the scores' matmul reads (index queries against a
        page's (index_dim, block_size) tile), it keeps the lane axis a
        whole number of 128s at any index width (a 64-wide row innermost
        would not: PERF.md fault 1), and to paged_write it is a pool
        with index_dim heads and no row width, the form an int8 pool's
        scales already have. Written in the same tick and prompt writes
        as k and v, freed with the same pages.

    How the pool travels through a model step (forward_with_cache): as
    a CARRY of the layer loops, viewed as (L * n_blocks, Hkv, bs, Dh) —
    a bitcast — with the tables offset by layer * n_blocks, so a
    (layer, page) pair is one block index. New rows are written in
    place by paged_write, slot by slot (why: its docstring); reads go
    through the offset tables. No layer's pool is sliced out of the
    stack or restacked into it, and the buffer a program was handed
    (and donated) is the buffer it returns.
    """

    k: Any
    v: Any
    tables: Any
    lengths: Any
    idx: Any = None

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_blocks(self) -> int:
        return self.tables.shape[1]


#: Lanes of one tile of the device's layout.
LANES = 128


def held_width(width: int) -> int:
    """The width a bf16/fp32 paged pool holds rows of `width` at: wider
    than one lane tile, the next whole number of tiles (PagedKVCache's
    docstring has why); a row inside one tile stays as it is."""
    return width if width <= LANES else -(-width // LANES) * LANES


def fit_row(x: jax.Array, width: int) -> jax.Array:
    """`x` with its last axis zero-extended to `width` lanes (a pool's
    held width); unchanged where it already has them."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def init_paged_cache(
    cfg: ModelConfig,
    n_slots: int,
    n_blocks: int,
    block_size: int,
    max_blocks_per_slot: int,
    tables=None,
) -> PagedKVCache:
    head = (cfg.cache_layers, n_blocks, cfg.cache_kv_heads, block_size)
    if tables is None:
        tables = jnp.zeros((n_slots, max_blocks_per_slot), jnp.int32)
    return PagedKVCache(
        k=jnp.zeros((*head, held_width(cfg.cache_head_dim)),
                    cfg.compute_dtype),
        # MLA: zero-width v pool (values re-expand from the latent the
        # k pool stores), same convention as the dense cache.
        v=jnp.zeros((*head, held_width(cfg.cache_v_head_dim)),
                    cfg.compute_dtype),
        tables=tables,
        lengths=jnp.zeros((n_slots,), jnp.int32),
        idx=(jnp.zeros((cfg.cache_layers, n_blocks, cfg.dsa.index_dim,
                        block_size), cfg.compute_dtype)
             if cfg.dsa is not None else None),
    )


#: Rows a page of the single-request cache of a model with an indexer.
SLOT_PAGE = 128


def init_paged_slot_cache(cfg: ModelConfig, batch: int,
                          max_len: int) -> PagedKVCache:
    """The paged pools with nothing to allocate: every row owns the
    pages its max_len needs (the single-request Engine's cache of a
    model with an indexer, whose state is the three pools)."""
    page = min(SLOT_PAGE, max_len)
    mb = -(-max_len // page)
    tables = 1 + jnp.arange(batch * mb, dtype=jnp.int32).reshape(batch, mb)
    return init_paged_cache(cfg, batch, batch * mb + 1, page, mb,
                            tables=tables)


def paged_write(pools, news, index, tables, layer=None, only=None):
    """Write S new positions a slot through the block tables, in place.

    pools: (N, Hkv, bs[, D]) each — one layer's pool, or the stacked
    (L, n_blocks, ...) pool viewed as (L * n_blocks, ...) with `tables`
    offset by layer * n_blocks; news: (B, Hkv, S[, D]) each, head-major
    like a page, in the pool's dtype. Positions index[b] + i map to
    pool coords (tables[b, p // bs], :, p % bs). Slots must have blocks
    allocated for every written position (the scheduler guarantees it);
    writes through unallocated entries land in a scratch block, and so
    does a position past the table's end. Returns the pools.

    A loop over (slot, piece) updates, each a dynamic_update_slice that
    keeps the head axis whole: decode's one row a slot goes in as that
    row; a run of S > 1 rows goes in page by page, each piece merging
    the run's rows into the page it read (a run starting mid-page
    touches at most (S + bs - 2) // bs + 1 pages; a piece past the
    run's end rewrites its page unchanged). The layout constraint pins
    the pool to the layout it is held in. Left to itself the TPU
    compiler suits the pool to its writer: a scatter at (block,
    offset), a bare row update, or rows arriving token-major each made
    it move the WHOLE pool head-innermost on entry and back on exit,
    every call, with a second pool of temporaries (PERF.md, PR 26;
    tests/test_aot_compile.py asks that compiler).

    With `layer` the pools are a layer STACK held head-outermost,
    (L, Hkv, N, bs[, D]) (EvaKVCache's pooled rows, which every slot
    reads with the heads as the batch of one matmul), `tables` index one
    layer's blocks, and the same pieces land in that layer. There a
    single row goes in as the 16-row group it lies in (a whole tile of
    the device's layout), read and merged like a page piece: a bare
    one-row update of this stack made the TPU's compiler hold the whole
    stack head-innermost through the program, constraint or none, with
    a copy in and out and one of each layer's slice for every reader
    (PERF.md, PR 27).

    `only` (B,) bool names the slots that write at all; the loop then
    visits those alone (a chunk's pooled row is due from one slot in
    sixteen a tick, and a visit that merges into a tile group costs
    ~50 us on the chip)."""
    stacked = layer is not None
    bs = pools[0].shape[3 if stacked else 2]
    b, s = news[0].shape[0], news[0].shape[2]
    last = tables.shape[1] - 1
    # A piece is a whole page, or the row itself where a slot writes one
    # (the row's tile group in a head-outermost stack).
    h = bs if s > 1 else (math.gcd(bs, 16) if stacked else 1)
    n_pieces = (s + h - 2) // h + 1

    if only is not None:
        order = jnp.argsort(~only, stable=True)  # the writers first
        b = jnp.sum(only.astype(jnp.int32))

    def piece(i, pools):
        slot, j = i // n_pieces, i % n_pieces
        if only is not None:
            slot = order[slot]
        start = index[slot]
        first = (start // h + j) * h  # the piece's first position
        page = first // bs
        block = jnp.where(
            page <= last, tables[slot, jnp.minimum(page, last)], 0
        )
        # The run's row held at each offset of this piece.
        row = first + jnp.arange(h, dtype=jnp.int32) - start
        held = (row >= 0) & (row < s)
        out = []
        for pool, run in zip(pools, news):
            if stacked:
                at = (layer, 0, block, first % bs) + (0,) * (pool.ndim - 4)
                size = (1, pool.shape[1], 1, h, *pool.shape[4:])
            else:
                at = (block, 0, first % bs) + (0,) * (pool.ndim - 3)
                size = (1, pool.shape[1], h, *pool.shape[3:])
            old = jax.lax.dynamic_slice(pool, at, size)
            new = jnp.take(
                jax.lax.dynamic_index_in_dim(run, slot, 0, keepdims=True),
                jnp.clip(row, 0, s - 1), axis=2,
            )  # (1, Hkv, h[, D])
            mask = held.reshape(1, 1, h, *(1,) * (new.ndim - 3))
            if stacked:
                new, mask = new[:, :, None], mask[:, :, None]
            out.append(jax.lax.dynamic_update_slice(
                pool, jnp.where(mask, new, old), at
            ))
        return tuple(out)

    def held(p):
        return with_layout_constraint(
            p, Layout(major_to_minor=tuple(range(p.ndim)))
        )

    out = jax.lax.fori_loop(
        0, b * n_pieces, piece, tuple(held(p) for p in pools)
    )
    # A stack is pinned where it leaves the loop too: with the entry
    # alone pinned the TPU's compiler still carried one of two stacks
    # written side by side in a layout of its own.
    return tuple(held(p) for p in out) if stacked else out


def paged_write_prompt(pools, minis, table_row):
    """Write a prefilled prompt into one slot's pages, every layer at
    once: a batch-1 dense mini cache's S rows go through `table_row`
    (max_blocks,) into the stacked pools, by paged_write with the
    LAYERS as its slots — layer l's table is the row offset by
    l * n_blocks, its run the mini's layer l from position 0.

    pools: (L, n_blocks, Hkv, bs[, D]) each; minis: the mini cache's
    matching fields, (L, 1, Hkv, S[, D]), rows zero-extended to the
    width the pool holds them at (held_width). Returns the pools."""
    n_layers, n_blocks = pools[0].shape[:2]
    layers = jnp.arange(n_layers, dtype=jnp.int32)

    def run(mini, pool):
        rows = mini[:, 0].astype(pool.dtype)
        # (An int8 pool's scales have no row width.)
        return fit_row(rows, pool.shape[-1]) if pool.ndim == 5 else rows

    out = paged_write(
        [p.reshape(n_layers * n_blocks, *p.shape[2:]) for p in pools],
        [run(m, p) for m, p in zip(minis, pools)],
        jnp.zeros_like(layers), table_row[None, :] + n_blocks * layers[:, None],
    )
    return tuple(o.reshape(p.shape) for o, p in zip(out, pools))


def paged_update_layer(
    pool_k: jax.Array,  # (N, Hkv, bs, Dh) — see paged_write
    pool_v: jax.Array,
    k_new: jax.Array,  # (B, S, Hkv, Dh)
    v_new: jax.Array,
    index: jax.Array,  # (B,) — per-slot write offsets (token positions)
    tables: jax.Array,  # (B, max_blocks) int32
):
    """Write S new positions through the block tables, the rows
    zero-extended to the width the pool holds them at (held_width);
    returns pools."""
    return paged_write(
        (pool_k, pool_v),
        tuple(fit_row(new.astype(pool.dtype), pool.shape[-1])
              .transpose(0, 2, 1, 3)
              for new, pool in ((k_new, pool_k), (v_new, pool_v))),
        index, tables,
    )


def paged_gather_layer(
    pool_k: jax.Array,  # (n_blocks, Hkv, bs, Dh)
    pool_v: jax.Array,
    tables: jax.Array,  # (B, max_blocks)
):
    """Materialize each slot's logical KV view, head-major:
    (B, Hkv, max_blocks*bs, D) — the same layout as a dense cache layer,
    so the decode fallback consumes it directly."""
    b, mb = tables.shape
    hkv, bs, dh = pool_k.shape[1:]

    def gather(pool):
        x = jnp.take(pool, tables.reshape(-1), axis=0)  # (B*mb, Hkv, bs, Dh)
        x = x.reshape(b, mb, hkv, bs, dh).transpose(0, 2, 1, 3, 4)
        return x.reshape(b, hkv, mb * bs, dh)

    return gather(pool_k), gather(pool_v)


# ---------------------------------------------------------------------------
# Int8-quantized paged cache (pool memory/bandwidth: half of bf16)
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class QuantPagedKVCache:
    """Paged block pool stored int8 with per-token/head dequant scales.

    Same block-table indirection, scratch-block-0 convention, and
    host-side allocator contract as PagedKVCache; same write-time
    symmetric quantization contract as QuantKVCache (K quantized after
    RoPE). Scale pools mirror the value pools block-for-block — one
    allocator run covers both, so the free list and prefix-cache
    refcounts need no changes.

    k, v: (L, n_blocks, Hkv, block_size, Dh) int8 — rows as wide as the
        model's, never padded to whole lane tiles as a bf16 pool's are
        (held_width): an int8 MLA latent pool keeps its 576-wide row and
        reads through the gather (no cell runs it; PERF.md section 7).
    ks, vs: (L, n_blocks, Hkv, block_size) fp32
    tables: (n_slots, max_blocks) int32
    lengths: (n_slots,) int32
    """

    k: Any
    v: Any
    ks: Any
    vs: Any
    tables: Any
    lengths: Any

    @property
    def block_size(self) -> int:
        return self.k.shape[3]

    @property
    def max_blocks(self) -> int:
        return self.tables.shape[1]


def init_quant_paged_cache(
    cfg: ModelConfig,
    n_slots: int,
    n_blocks: int,
    block_size: int,
    max_blocks_per_slot: int,
) -> QuantPagedKVCache:
    head = (cfg.cache_layers, n_blocks, cfg.cache_kv_heads, block_size)
    return QuantPagedKVCache(
        k=jnp.zeros((*head, cfg.cache_head_dim), jnp.int8),
        v=jnp.zeros((*head, cfg.cache_v_head_dim), jnp.int8),
        ks=jnp.zeros(head, jnp.float32),
        vs=jnp.zeros(head, jnp.float32),
        tables=jnp.zeros((n_slots, max_blocks_per_slot), jnp.int32),
        lengths=jnp.zeros((n_slots,), jnp.int32),
    )


def quant_paged_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    heads = "kv_heads" if cfg is None or cfg.mla is None else None
    return QuantPagedKVCache(
        k=("layers", None, heads, None, None),
        v=("layers", None, heads, None, None),
        ks=("layers", None, heads, None),
        vs=("layers", None, heads, None),
        tables=(None, None),
        lengths=(None,),
    )


def quant_paged_update_layer(
    pool_k, pool_v, pool_ks, pool_vs,  # int8 pools + scales (paged_write)
    k_new, v_new,  # (B, S, Hkv, Dh) unquantized
    index,  # (B,) int32 — per-slot write offsets (token positions)
    tables,  # (B, max_blocks) int32
):
    """Quantize S new positions, write values and scales through the
    block tables in one pass over the same coordinates."""
    kq, ks = quantize_kv(k_new)
    vq, vs = quantize_kv(v_new)
    return paged_write(
        (pool_k, pool_v, pool_ks, pool_vs),
        (kq.transpose(0, 2, 1, 3), vq.transpose(0, 2, 1, 3),
         ks.transpose(0, 2, 1), vs.transpose(0, 2, 1)),
        index, tables,
    )


def paged_gather_scales(
    pool_s: jax.Array,  # (n_blocks, Hkv, bs)
    tables: jax.Array,  # (B, max_blocks)
):
    """Materialize each slot's logical scale view: (B, Hkv, max_blocks*bs)
    — the dense QuantKVCache scale layout, so the dequant fallback
    consumes it directly."""
    b, mb = tables.shape
    hkv, bs = pool_s.shape[1:]
    x = jnp.take(pool_s, tables.reshape(-1), axis=0)  # (B*mb, Hkv, bs)
    x = x.reshape(b, mb, hkv, bs).transpose(0, 2, 1, 3)
    return x.reshape(b, hkv, mb * bs)


# ---------------------------------------------------------------------------
# EVA state: a ring of exact rows and a paged pool of pooled rows
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class EvaKVCache:
    """The decode state of EVA attention (cfg.eva; ops/eva_attention.py):
    two kinds of state for one slot.

    k, v: (L, W, n_slots, H, Dh) — a ring of the W = cfg.eva.window
        exact rows of the slot's CURRENT window, position p at ring row
        p % W; rows 0 .. (lengths - 1) % W hold the window so far, the
        rest are the window before and are never read (a window attends
        none of its predecessor's exact rows). Position-outermost: a
        slot's new row is one contiguous (H, Dh) slab, and a tick reads
        a layer's rings as one matrix-vector product a slot and head,
        which the TPU's compiler runs on the vector unit and wants
        row-outermost (held any other way it copied every layer's rings
        to that layout, every tick: PERF.md, PR 27).
    pk, pv: (L, H, n_blocks, R, Dh) — a paged pool of pooled rows, one
        row a chunk of cfg.eva.chunk positions, R = W // chunk rows a
        page, so a page is one window and chunk c of a slot lives at
        (tables[slot, c // R], c % R). A chunk's row is written in the
        tick that completes the chunk; a window's page is read only once
        the window is complete. Block 0 is scratch, as in PagedKVCache.
        Head-outermost: every slot is scored against every page in one
        matmul whose batch is the heads (ops/eva_attention.py), and the
        pool is read where it lies.
    tables: (n_slots, max_blocks) int32 — pool block per window.
    lengths: (n_slots,) int32 — positions seen.
    slots: (n_slots,) int32 — which ring each batch row is; arange for
        the engine's cache, [slot] for the batch-1 view a prefill writes
        through.

    Both kinds ride forward_with_cache's layer loop whole, as carries,
    as the paged pool does: a layer writes its rows in place
    (eva_ring_write; paged_write with `layer`) and reads its own slice of
    the stack, and nothing is sliced out and restacked.
    """

    k: Any
    v: Any
    pk: Any
    pv: Any
    tables: Any
    lengths: Any
    slots: Any

    @property
    def block_size(self) -> int:
        """Positions a page stands for (one window)."""
        return self.k.shape[1]

    @property
    def max_blocks(self) -> int:
        return self.tables.shape[1]


def init_eva_cache(cfg: ModelConfig, n_slots: int, n_blocks: int,
                   max_blocks_per_slot: int, tables=None) -> EvaKVCache:
    e = cfg.eva
    ring = (cfg.cache_layers, e.window, n_slots, cfg.n_heads, cfg.dim_per_head)
    pool = (cfg.cache_layers, cfg.n_heads, n_blocks, e.window // e.chunk,
            cfg.dim_per_head)
    dt = cfg.compute_dtype
    if tables is None:
        tables = jnp.zeros((n_slots, max_blocks_per_slot), jnp.int32)
    return EvaKVCache(
        k=jnp.zeros(ring, dt), v=jnp.zeros(ring, dt),
        pk=jnp.zeros(pool, dt), pv=jnp.zeros(pool, dt),
        tables=tables,
        lengths=jnp.zeros((n_slots,), jnp.int32),
        slots=jnp.arange(n_slots, dtype=jnp.int32),
    )


def init_eva_slot_cache(cfg: ModelConfig, batch: int,
                        max_len: int) -> EvaKVCache:
    """The same state with nothing to allocate: every row owns the
    pages its max_len needs (the single-request Engine's cache)."""
    mb = -(-max_len // cfg.eva.window)
    tables = 1 + jnp.arange(batch * mb, dtype=jnp.int32).reshape(batch, mb)
    return init_eva_cache(cfg, batch, batch * mb + 1, mb, tables=tables)


def eva_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    return EvaKVCache(
        k=("layers", None, None, "kv_heads", None),
        v=("layers", None, None, "kv_heads", None),
        pk=("layers", "kv_heads", None, None, None),
        pv=("layers", "kv_heads", None, None, None),
        tables=(None, None), lengths=(None,), slots=(None,),
    )


def eva_ring_write(rings, rows, layer, slots, at):
    """Write each slot's new exact rows into its ring, in place.

    rings: (L, W, n_slots, H, D) each, the layer stack; rows: (B, S, H,
    D) each, S = 1 (a decode tick's row) or W (a prefilled window);
    rows[b] land at ring rows at[b] .. at[b] + S - 1 of ring slots[b] in
    `layer`. A loop of one dynamic_update_slice a slot, under the
    layout constraint that pins the stack as it is held (paged_write's
    docstring has why)."""
    def one(b, rings):
        return tuple(
            jax.lax.dynamic_update_slice(
                ring,
                jax.lax.dynamic_index_in_dim(new, b, 0, keepdims=False)[None, :, None]
                .astype(ring.dtype),
                (layer, at[b], slots[b], 0, 0),
            ) for ring, new in zip(rings, rows)
        )

    held_as = tuple(
        with_layout_constraint(
            r, Layout(major_to_minor=tuple(range(r.ndim)))
        ) for r in rings
    )
    return jax.lax.fori_loop(0, rows[0].shape[0], one, held_as)


# ---------------------------------------------------------------------------
# Rolling (ring-buffer) cache for sliding-window attention
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class RollingKVCache:
    """Ring-buffer KV cache: storage scales with the WINDOW, not the
    context.

    A sliding-window layer only ever attends the last `window`
    positions, so position p lives at ring slot p % ring and old
    positions are overwritten in place. `lengths` still counts TOTAL
    positions seen (the position arithmetic is identical to the dense
    cache); only the storage wraps. ring must be >= window + the
    largest cache-READING write chunk (decode writes 1; chunked-prefill
    continuations write up to prefill_chunk) — the extra slack keeps a
    chunk's EARLIEST query row's window intact while the chunk's own
    writes land. Fresh prefill attends the incoming chunk directly
    (never the buffer), so whole-prompt prefill needs no slack.

    Same head-major (L, B, Hkv, ring, Dh) layout as KVCache. Reads go
    through the reference attention with reconstructed per-slot
    positions — the ring is window-sized, so the Pallas decode kernel's
    dead-block skipping (its reason to exist on a max_len buffer) has
    nothing left to skip.
    """

    k: Any  # (L, B, Hkv, ring, Dh)
    v: Any  # (L, B, Hkv, ring, Dh)
    lengths: Any  # (B,) int32 — TOTAL positions seen

    @property
    def ring(self) -> int:
        return self.k.shape[3]


def rolling_ring(cfg: ModelConfig, max_len: int, chunk_slack: int) -> int:
    """Ring size for a config: window + slack, sublane-rounded, capped
    at max_len (a ring bigger than the context is just a dense cache)."""
    if cfg.attn_window is None:
        raise ValueError("rolling cache needs cfg.attn_window")
    ring = cfg.attn_window + max(int(chunk_slack), 1)
    ring = ((ring + 7) // 8) * 8
    return min(ring, max_len)


def init_rolling_cache(
    cfg: ModelConfig, batch: int, max_len: int, chunk_slack: int = 1,
) -> RollingKVCache:
    if cfg.mla is not None:
        raise ValueError("MLA models have no sliding window to roll")
    if cfg.attn_window is None:
        raise ValueError(
            "rolling cache needs a sliding-window model (attn_window)"
        )
    if cfg.attn_pattern is not None and "full" in cfg.attn_pattern:
        raise NotImplementedError(
            "patterned local/global stacks roll via the MIXED cache — "
            "use init_patterned_cache (init_cache_for routes there "
            "automatically); this constructor builds the uniform ring"
        )
    ring = rolling_ring(cfg, max_len, chunk_slack)
    head = (cfg.cache_layers, batch, cfg.cache_kv_heads, ring)
    return RollingKVCache(
        k=jnp.zeros((*head, cfg.cache_head_dim), cfg.compute_dtype),
        v=jnp.zeros((*head, cfg.cache_head_dim), cfg.compute_dtype),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def rolling_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    return RollingKVCache(
        k=("layers", "batch", "kv_heads", None, None),
        v=("layers", "batch", "kv_heads", None, None),
        lengths=("batch",),
    )


def roll_update_layer(
    cache_k: jax.Array,  # (B, Hkv, ring, Dh) — one layer's ring
    cache_v: jax.Array,
    k_new: jax.Array,  # (B, S, Hkv, Dh)
    v_new: jax.Array,
    index: jax.Array,  # (B,) int32 — first new position (total count)
    valid_len=None,  # (B,) int32 — REAL rows in the chunk (None = S)
):
    """Write the chunk's REAL positions into the ring at
    (index + i) % ring.

    valid_len masks right-padding: the dense cache can write pad rows
    harmlessly (reads mask by lengths), but a ring write WRAPS — a pad
    row landing at (index + i) % ring would clobber an in-window
    position, so pad rows must never touch the buffer.

    S == 1 (decode) is a plain per-row scatter. For larger chunks the
    write is LAST-WINS per slot, computed by gather-select (a naive
    scatter with duplicate ring indices has unspecified order): ring
    slot j's newest VALID chunk element is c_j = (cm - (cm - j) % ring)
    - index with cm the final real position; slots no valid element
    maps to keep their current rows.
    """
    ring = cache_k.shape[2]
    b, s = k_new.shape[:2]
    kn = k_new.astype(cache_k.dtype).transpose(0, 2, 1, 3)  # (B,Hkv,S,Dh)
    vn = v_new.astype(cache_v.dtype).transpose(0, 2, 1, 3)
    if s == 1 and valid_len is None:
        slot = (index % ring).astype(jnp.int32)
        barange = jnp.arange(b)
        ck = cache_k.at[barange, :, slot].set(kn[:, :, 0])
        cv = cache_v.at[barange, :, slot].set(vn[:, :, 0])
        return ck, cv
    vl = (jnp.full((b,), s, jnp.int32) if valid_len is None
          else jnp.minimum(valid_len.astype(jnp.int32), s))
    cm = index + vl - 1  # (B,) — final REAL position
    j = jnp.arange(ring, dtype=jnp.int32)[None, :]  # (1, ring)
    p = cm[:, None] - ((cm[:, None] - j) % ring)  # newest position per slot
    c = p - index[:, None]  # chunk element index
    valid = (c >= 0) & (c < vl[:, None])
    c_clamped = jnp.clip(c, 0, s - 1)
    take = jnp.take_along_axis(
        kn, c_clamped[:, None, :, None], axis=2
    )  # (B, Hkv, ring, Dh)
    ck = jnp.where(valid[:, None, :, None], take, cache_k)
    take_v = jnp.take_along_axis(vn, c_clamped[:, None, :, None], axis=2)
    cv = jnp.where(valid[:, None, :, None], take_v, cache_v)
    return ck, cv


def rolled_kv_positions(lengths: jax.Array, ring: int):
    """(kv_positions (B, ring) int32, kv_mask (B, ring) bool) for a ring
    whose newest written position is lengths - 1 (post-write)."""
    cm = lengths.astype(jnp.int32)[:, None] - 1  # (B, 1)
    j = jnp.arange(ring, dtype=jnp.int32)[None, :]
    p = cm - ((cm - j) % ring)
    return p, p >= 0


# ---------------------------------------------------------------------------
# Patterned cache: ring buffers for window layers, dense for full layers
# ---------------------------------------------------------------------------


@flax.struct.dataclass
class PatternedKVCache:
    """Mixed cache for attn_pattern models: the "window" layers roll in
    ring buffers while the "full" layers keep the dense max_len stack —
    so a Gemma-2/GPT-OSS-style half-local stack cuts its cache memory
    roughly in half at long context (and far more as max_len grows).

    Layer i of kind "window" is row (number of window layers before i)
    of the kw/vw stacks; "full" layers index kf/vf the same way. The
    stacking order inside each kind follows layer order, so the
    pattern-period reshape in forward_with_cache stays a pure
    view + static in-group indexing.
    """

    kw: Any  # (Lw, B, Hkv, ring, Dh)
    vw: Any
    kf: Any  # (Lf, B, Hkv, max_len, Dh)
    vf: Any
    lengths: Any  # (B,) int32 — TOTAL positions (shared by both kinds)

    @property
    def ring(self) -> int:
        return self.kw.shape[3]

    @property
    def dense_len(self) -> int:
        return self.kf.shape[3]


def pattern_kind_counts(cfg: ModelConfig):
    """(n_window, n_full) per pattern period."""
    pat = cfg.attn_pattern
    nw = sum(1 for k in pat if k == "window")
    return nw, len(pat) - nw


def init_patterned_cache(
    cfg: ModelConfig, batch: int, max_len: int, chunk_slack: int = 1,
) -> PatternedKVCache:
    if cfg.attn_pattern is None or "window" not in cfg.attn_pattern:
        raise ValueError(
            "patterned cache needs an attn_pattern with 'window' layers"
        )
    if "full" not in cfg.attn_pattern:
        raise ValueError(
            "uniformly-windowed patterns use the plain rolling cache"
        )
    ring = rolling_ring(cfg, max_len, chunk_slack)
    nw, nf = pattern_kind_counts(cfg)
    groups = cfg.n_layers // len(cfg.attn_pattern)
    cdt = cfg.compute_dtype
    dh = cfg.cache_head_dim
    hkv = cfg.cache_kv_heads
    return PatternedKVCache(
        kw=jnp.zeros((groups * nw, batch, hkv, ring, dh), cdt),
        vw=jnp.zeros((groups * nw, batch, hkv, ring, dh), cdt),
        kf=jnp.zeros((groups * nf, batch, hkv, max_len, dh), cdt),
        vf=jnp.zeros((groups * nf, batch, hkv, max_len, dh), cdt),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def patterned_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    ax = ("layers", "batch", "kv_heads", None, None)
    return PatternedKVCache(
        kw=ax, vw=ax, kf=ax, vf=ax, lengths=("batch",),
    )


@flax.struct.dataclass
class QuantRollingKVCache:
    """Int8 ring buffer: the rolling cache's window-sized storage AND
    the int8 cache's halved bytes/bandwidth, composed. Same write-time
    symmetric quantization contract as QuantKVCache (K quantized after
    RoPE); same ring position arithmetic as RollingKVCache. Reads
    dequantize the ring (it is window-sized — the dequant is O(window),
    not O(context)) and run the masked reference attention.
    """

    k: Any  # (L, B, Hkv, ring, Dh) int8
    v: Any  # (L, B, Hkv, ring, Dh) int8
    ks: Any  # (L, B, Hkv, ring) fp32
    vs: Any  # (L, B, Hkv, ring) fp32
    lengths: Any  # (B,) int32 — TOTAL positions seen

    @property
    def ring(self) -> int:
        return self.k.shape[3]


def init_quant_rolling_cache(
    cfg: ModelConfig, batch: int, max_len: int, chunk_slack: int = 1,
) -> QuantRollingKVCache:
    if cfg.attn_window is None:
        raise ValueError(
            "rolling cache needs a sliding-window model (attn_window)"
        )
    if cfg.attn_pattern is not None and "full" in cfg.attn_pattern:
        raise ValueError(
            "patterned local/global stacks roll int8 via the quant "
            "MIXED cache — use init_quant_patterned_cache "
            "(init_cache_for routes there automatically); this "
            "constructor builds the uniform int8 ring"
        )
    ring = rolling_ring(cfg, max_len, chunk_slack)
    head = (cfg.cache_layers, batch, cfg.cache_kv_heads, ring)
    return QuantRollingKVCache(
        k=jnp.zeros((*head, cfg.cache_head_dim), jnp.int8),
        v=jnp.zeros((*head, cfg.cache_head_dim), jnp.int8),
        ks=jnp.zeros(head, jnp.float32),
        vs=jnp.zeros(head, jnp.float32),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def quant_rolling_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    return QuantRollingKVCache(
        k=("layers", "batch", "kv_heads", None, None),
        v=("layers", "batch", "kv_heads", None, None),
        ks=("layers", "batch", "kv_heads", None),
        vs=("layers", "batch", "kv_heads", None),
        lengths=("batch",),
    )


@flax.struct.dataclass
class QuantPatternedKVCache:
    """Int8 mixed cache: the patterned cache's window-sized rings for
    "window" layers and dense max_len stacks for "full" layers, all
    stored int8 with per-token/head scales. Same layer->row mapping as
    PatternedKVCache, same write-time quantization contract as
    QuantKVCache (K post-rope). Window layers ring-write values AND
    scales (quant_roll_update_layer); full layers take the dense int8
    decode path (scales carried by the kernel or dequant reference).
    """

    kw: Any  # (Lw, B, Hkv, ring, Dh) int8
    vw: Any
    kws: Any  # (Lw, B, Hkv, ring) fp32
    vws: Any
    kf: Any  # (Lf, B, Hkv, max_len, Dh) int8
    vf: Any
    kfs: Any  # (Lf, B, Hkv, max_len) fp32
    vfs: Any
    lengths: Any  # (B,) int32 — TOTAL positions (shared by both kinds)

    @property
    def ring(self) -> int:
        return self.kw.shape[3]

    @property
    def dense_len(self) -> int:
        return self.kf.shape[3]


def init_quant_patterned_cache(
    cfg: ModelConfig, batch: int, max_len: int, chunk_slack: int = 1,
) -> QuantPatternedKVCache:
    if cfg.attn_pattern is None or "window" not in cfg.attn_pattern:
        raise ValueError(
            "patterned cache needs an attn_pattern with 'window' layers"
        )
    if "full" not in cfg.attn_pattern:
        raise ValueError(
            "uniformly-windowed patterns use the plain rolling cache"
        )
    ring = rolling_ring(cfg, max_len, chunk_slack)
    nw, nf = pattern_kind_counts(cfg)
    groups = cfg.n_layers // len(cfg.attn_pattern)
    dh = cfg.cache_head_dim
    hkv = cfg.cache_kv_heads
    return QuantPatternedKVCache(
        kw=jnp.zeros((groups * nw, batch, hkv, ring, dh), jnp.int8),
        vw=jnp.zeros((groups * nw, batch, hkv, ring, dh), jnp.int8),
        kws=jnp.zeros((groups * nw, batch, hkv, ring), jnp.float32),
        vws=jnp.zeros((groups * nw, batch, hkv, ring), jnp.float32),
        kf=jnp.zeros((groups * nf, batch, hkv, max_len, dh), jnp.int8),
        vf=jnp.zeros((groups * nf, batch, hkv, max_len, dh), jnp.int8),
        kfs=jnp.zeros((groups * nf, batch, hkv, max_len), jnp.float32),
        vfs=jnp.zeros((groups * nf, batch, hkv, max_len), jnp.float32),
        lengths=jnp.zeros((batch,), jnp.int32),
    )


def quant_patterned_cache_logical_axes(cfg: Optional[ModelConfig] = None):
    val = ("layers", "batch", "kv_heads", None, None)
    sc = ("layers", "batch", "kv_heads", None)
    return QuantPatternedKVCache(
        kw=val, vw=val, kws=sc, vws=sc,
        kf=val, vf=val, kfs=sc, vfs=sc, lengths=("batch",),
    )


def quant_roll_update_layer(
    cache_k, cache_v, cache_ks, cache_vs,  # one layer's ring (+ scales)
    k_new, v_new,  # (B, S, Hkv, Dh) unquantized
    index,  # (B,) int32
    valid_len=None,
):
    """Quantize the chunk, then ring-write values AND scales with the
    same last-wins/pad-mask semantics as roll_update_layer."""
    kq, ks = quantize_kv(k_new)
    vq, vs = quantize_kv(v_new)
    ck, cv = roll_update_layer(cache_k, cache_v, kq, vq, index,
                               valid_len=valid_len)
    # Scales are (B, S, Hkv) -> ring scatter on a 3D buffer: reuse the
    # 4D path with a width-1 head dim (the k and v slots of
    # roll_update_layer are independent, so one call does both rings).
    cks, cvs = roll_update_layer(
        cache_ks[..., None], cache_vs[..., None],
        ks[..., None], vs[..., None], index, valid_len=valid_len,
    )
    return ck, cv, cks[..., 0], cvs[..., 0]
