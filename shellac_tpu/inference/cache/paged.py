"""Paged block-pool backend: slots borrow fixed-size blocks as they
grow and return them on completion, so resident KV memory tracks the
tokens actually alive instead of n_slots x max_len worst case.

All ALLOCATOR state lives here — free list, per-slot block lists, the
prefix-cache hash registry and refcounts — and so does the BLOCK TABLE:
a host (n_slots, max_blocks) int32 array with one writer of a row
(`_write_row`). The device never sees a table op of its own: the table,
when a row changed since the last program, rides the next engine
program as an argument (`slot_tables()`) and becomes that program's
`cache.tables`, so admission and release dispatch nothing. Block 0 is
reserved scratch: unallocated table entries point at it, so stray
writes/reads through them land harmlessly and are masked downstream.

prefix_cache=True adds automatic prefix caching (the public
PagedAttention/vLLM idea): full prompt blocks are content-hashed with a
position-dependent chain, kept pooled after release (refcounted,
LRU-evicted only when the free list runs dry), and new prompts attach
the longest matching chain read-only — prefill then computes only the
unmatched suffix.

QuantPagedBackend stores the pool int8 with per-token fp32 scale pools
that mirror the value pools block-for-block, so ONE allocator run
covers both and nothing here changes.
"""

from __future__ import annotations

import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference import prefix as prefix_mod
from shellac_tpu.inference.cache.base import CacheBackend, PoolExhausted
from shellac_tpu.inference.cache.layout import (
    held_width,
    init_cache_for,
    init_paged_cache,
    init_quant_paged_cache,
    kv_field_names,
    paged_cache_logical_axes,
    paged_write_prompt,
    quant_paged_cache_logical_axes,
)


#: Page geometry of int8 pools. The grouped-gather decode kernel DMAs
#: each page's fp32 scales out of an (n_blocks, Hkv, block_size) pool in
#: HBM, and Mosaic refuses any slice of an HBM ref whose lane (last) dim
#: is narrower than the 128-lane tiling — so a page holds at least 128
#: tokens. The chipless compile gate (tests/test_aot_compile.py)
#: compiles the kernel at every size named here.
#: feature -> why the third pool of a model with an indexer (cfg.dsa:
#: one index key a token under the same tables as k and v) cannot carry
#: it yet (ROADMAP Queue 2). Refused where the switch is turned on: the
#: constructor, bind(), check_feature().
INDEX_POOL_UNSUPPORTED = {
    "kv_quant": (
        "the index keys have no int8 form, and a tick gathers its chosen "
        "rows out of bf16 pools"
    ),
    "prefix_cache": (
        "a shared page holds its index rows, but registration, eviction "
        "and seeding name k and v alone: nothing shows yet that an "
        "attached page's index rows are the attaching prompt's"
    ),
    "speculative": (
        "a verify window scores several queries at once and rolls back, "
        "and a rejected token's index row would stay behind"
    ),
    "pp_pipeline": "the per-stage registers hold k and v rows, no index keys",
    "mesh": "the index pool and the choice are not sharded yet",
    "park_resume": (
        "parking a slot would have to ship its index rows with its k and "
        "v rows"
    ),
    "kv_export": (
        "disaggregated export ships the k and v fields; the index rows "
        "would be left behind"
    ),
    "beam_search": (
        "beams copy k and v pages on write; the index pages would not "
        "follow"
    ),
}

INT8_BLOCK_ALIGN = 128
INT8_BLOCK_SIZES_RECOMMENDED = (INT8_BLOCK_ALIGN, 2 * INT8_BLOCK_ALIGN)
INT8_BLOCK_SIZE_DEFAULT = INT8_BLOCK_ALIGN


class PagedBackend(CacheBackend):
    name = "paged"
    is_paged = True

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int, *,
                 kv_quant: Optional[str] = None, block_size: int = 16,
                 pool_tokens: Optional[int] = None,
                 prefix_cache: bool = False, chunk_slack: int = 1):
        super().__init__(cfg, n_slots, max_len, kv_quant=kv_quant,
                         chunk_slack=chunk_slack)
        if cfg.dsa is not None:
            if kv_quant is not None:
                self.refuse_index_pool("kv_quant")
            if prefix_cache:
                self.refuse_index_pool("prefix_cache")
        if kv_quant == "int8":
            if block_size % INT8_BLOCK_ALIGN:
                # An engine knob, so an error beats a per-tick fallback
                # warning.
                raise ValueError(
                    f"kv_quant='int8' paged pools need block_size % "
                    f"{INT8_BLOCK_ALIGN} == 0 (got {block_size}); use "
                    + " or ".join(map(str, INT8_BLOCK_SIZES_RECOMMENDED))
                )
            self.name = "paged-int8"
        self.block_size = block_size
        self.prefix_cache = prefix_cache
        self.max_blocks_per_slot = -(-max_len // block_size)
        if pool_tokens is None:
            # Half the dense footprint, but never fewer pages than
            # slots: with coarse pages (int8 pools: 128 tokens) and a
            # short max_len the halving would leave slots that can
            # never be admitted while another holds the only page.
            pool_tokens = max(n_slots * max_len // 2, n_slots * block_size)
        self.n_blocks = max(
            -(-pool_tokens // block_size), self.max_blocks_per_slot
        ) + 1
        self._free: List[int] = list(range(self.n_blocks - 1, 0, -1))
        self._slot_blocks: List[List[int]] = [[] for _ in range(n_slots)]
        # The block table, host side: row i is _slot_blocks[i] padded
        # with scratch block 0. _write_row is its only writer; the
        # device copy (None: a row changed since it was uploaded) is
        # what slot_tables() hands the next engine program.
        self._tables = np.zeros((n_slots, self.max_blocks_per_slot),
                                np.int32)
        self._tables_dev: Optional[jax.Array] = None
        # Prefix cache state (all host-side; empty when disabled):
        # hash -> block id, insertion/touch-ordered so the front is
        # LRU; _block_ref counts slots currently attached to a cached
        # block (membership also marks "cached": release keeps these
        # pooled instead of freeing them); ref == 0 means evictable.
        self._hash_to_block: "OrderedDict[bytes, int]" = OrderedDict()
        self._block_ref: Dict[int, int] = {}
        self._slot_prefix_len: List[int] = [0] * n_slots
        # Registrations deferred until the slot's prefill completes
        # (the blocks hold garbage until then):
        # slot -> [(idx, hash, parent_hash)].
        self._pending_reg: Dict[int, List] = {}
        # Fabric/directory state (host-side, prefix_cache only): chain
        # links child -> parent (b"" roots a chain), per-hash chain
        # depth and last-touch stamps, per-hash attach hit counters
        # keyed by the LAST MATCHED hash of each attach (for the
        # shared-system-prompt shape that is exactly the hot shared
        # prefix's tip), and a monotonic version the /kv/prefixes
        # delta-poll compares against.
        self._hash_parent: Dict[bytes, bytes] = {}
        self._hash_depth: Dict[bytes, int] = {}
        self._hash_touch: Dict[bytes, float] = {}
        self._prefix_hits: Dict[bytes, int] = {}
        self._prefix_version = 0

    @staticmethod
    def refuse_index_pool(feature: str) -> None:
        raise ValueError(
            f"a model with an indexer (cfg.dsa) does not support {feature} "
            f"on the 'paged' cache backend yet: "
            f"{INDEX_POOL_UNSUPPORTED[feature]}"
        )

    def check_feature(self, feature: str) -> None:
        if self.cfg.dsa is not None and feature in INDEX_POOL_UNSUPPORTED:
            self.refuse_index_pool(feature)
        super().check_feature(feature)

    def bind(self, engine) -> None:
        if self.cfg.dsa is not None:
            from shellac_tpu.inference.spec_batching import _SpecDecodeMixin

            if isinstance(engine, _SpecDecodeMixin):
                self.refuse_index_pool("speculative")
            if engine.mesh is not None:
                self.refuse_index_pool("mesh")
        super().bind(engine)

    # ---- device cache construction ----------------------------------

    def init_cache(self):
        init_pool = (init_quant_paged_cache if self.kv_quant == "int8"
                     else init_paged_cache)
        return init_pool(self.cfg, self.n_slots, self.n_blocks,
                         self.block_size, self.max_blocks_per_slot)

    def init_mini(self, length: int):
        # Prefill computes into a DENSE mini of the pool's kind, then
        # the engine's prefill program scatters it through the slot's
        # block table.
        return init_cache_for(self.cfg, 1, length, self.kv_quant)

    def logical_axes(self):
        if self.kv_quant == "int8":
            return quant_paged_cache_logical_axes(self.cfg)
        return paged_cache_logical_axes(self.cfg)

    @staticmethod
    def default_block_size(cfg: ModelConfig) -> int:
        """Tokens a page when the engine is given no block_size."""
        return 16

    def prefill_into(self, cache, slot, tokens, prompt_len, forward, *,
                     all_rows: bool):
        """Inside the engine's prefill program: run `forward(scratch,
        tokens, new_len, fresh_cache=, logits_at=) -> (logits, scratch)`
        for one prompt, `tokens` (1, pad) holding `prompt_len` (1,) real
        ones, and leave its rows in `slot`'s pages. Returns (logits,
        cache): logits (1, pad, V) of every row if `all_rows`, else (1,
        1, V), the row of the prompt's last token.

        Here: a dense mini cache of the pool's kind (bf16, or int8 +
        scales: the quant mini already quantized at write, K post-rope,
        and its scales go through the same pages as its values), then
        paged_write_prompt through the slot's table row."""
        def rows(logits):
            return logits if all_rows else jnp.take_along_axis(
                logits, (prompt_len - 1)[:, None, None].astype(jnp.int32),
                axis=1)

        if self.cfg.dsa is not None:
            # Three pools and no dense mini of their kind: the prompt
            # goes straight through a batch-1 view of the slot's table
            # row, as a cached chunk does.
            view = cache.replace(
                tables=jax.lax.dynamic_slice_in_dim(cache.tables, slot, 1, 0),
                lengths=jnp.zeros((1,), jnp.int32),
            )
            logits, view = forward(view, tokens, prompt_len)
            cache = cache.replace(
                k=view.k, v=view.v, idx=view.idx,
                lengths=jax.lax.dynamic_update_slice(
                    cache.lengths, view.lengths, (slot,)
                ),
            )
            return rows(logits), cache
        logits, mini = forward(self.init_mini(tokens.shape[1]), tokens,
                               prompt_len)
        table_row = jax.lax.dynamic_slice_in_dim(cache.tables, slot, 1, 0)[0]
        names = kv_field_names(self.kv_quant)
        fields = dict(zip(names, paged_write_prompt(
            [getattr(cache, n) for n in names],
            [getattr(mini, n) for n in names], table_row,
        )))
        fields["lengths"] = jax.lax.dynamic_update_slice(
            cache.lengths, mini.lengths, (slot,)
        )
        return rows(logits), cache.replace(**fields)

    def decode_attn(self) -> str:
        """How the engine's decode program reads this pool: through the
        block table in the kernel ("paged_kernel"), or through a
        gathered dense view of every slot ("gather"). The dispatcher's
        own rule, asked with the shapes built here."""
        from shellac_tpu.ops.decode_attention import paged_decode_path

        cfg = self.cfg
        if cfg.dsa is not None:
            # Neither: the tick gathers the rows its indexer chose.
            return "chosen_rows"
        width = self.row_width(cfg.cache_head_dim)
        return paged_decode_path(
            (self.n_slots, 1, cfg.n_heads, width),
            (self.n_blocks, cfg.cache_kv_heads, self.block_size, width),
            jnp.int8 if self.kv_quant == "int8" else cfg.compute_dtype,
            self.engine.attn_impl,
        )

    def row_width(self, width: int) -> int:
        """Lanes this pool holds a row of `width` at: a bf16/fp32 pool
        whole lane tiles (layout.held_width), the int8 pool the row's
        own."""
        return width if self.kv_quant == "int8" else held_width(width)

    # ---- allocator ---------------------------------------------------

    def initial_stats(self) -> Dict[str, int]:
        # "decode_attn" is non-numeric, like "cache_backend": the
        # /metrics mirror skips it, /stats shows it.
        stats = {"decode_attn": self.decode_attn()}
        if self.prefix_cache:
            stats.update({
                "prefix_hit_tokens": 0,
                "prefix_query_tokens": 0,
                "prefix_evictions": 0,
                "prefix_seeded_blocks": 0,
            })
        return stats

    def evictable(self) -> int:
        return sum(1 for r in self._block_ref.values() if r == 0)

    def alloc_block(self) -> int:
        """Pop a free block, evicting the LRU unreferenced cached block
        when the free list is dry. Caller checks capacity first."""
        if self._free:
            return self._free.pop()
        for h, blk in self._hash_to_block.items():  # front = LRU
            if self._block_ref[blk] == 0:
                del self._hash_to_block[h]
                del self._block_ref[blk]
                self._prune_hash(h)
                self.engine.stats["prefix_evictions"] += 1
                return blk
        raise RuntimeError("alloc_block called with no capacity")

    def _prune_hash(self, h: bytes) -> None:
        """Drop fabric sidecar state for an evicted hash. The parent
        LINK of surviving children is left in place on purpose: a
        child whose ancestor was evicted is unreachable through
        _match_prefix (the walk starts at the root), and chain_blocks
        refuses it loudly — pruning links would instead silently
        re-root a mid-chain block at the wrong position."""
        self._hash_parent.pop(h, None)
        self._hash_depth.pop(h, None)
        self._hash_touch.pop(h, None)
        self._prefix_hits.pop(h, None)
        self._prefix_version += 1

    def _write_row(self, slot: int) -> None:
        """THE writer of a table row: host row `slot` := the slot's
        block list, scratch block 0 behind it. Nothing is dispatched;
        the device sees the row when the next engine program takes
        slot_tables(). A released slot's row therefore reads zeros on
        the device from the first program dispatched after its release,
        whichever it is (a prefill, a chunk, the window) — before any
        later window can write through it and before a new tenant's
        prefill writes its pages — which is the order the eager
        zeroing used to give by dispatch order."""
        blocks = self._slot_blocks[slot]
        row = self._tables[slot]
        row[:len(blocks)] = blocks
        row[len(blocks):] = 0
        self._tables_dev = None

    def slot_tables(self):
        """The block table as the next engine program's argument (the
        program makes it its cache.tables on entry). Uploaded here, one
        host-to-device copy of the whole table, only if a row changed
        since the last program took it; otherwise the device copy that
        program already saw."""
        if self._tables_dev is None:
            # A copy: the CPU backend may alias a host buffer, and the
            # host table goes on changing under programs in flight.
            self._tables_dev = jnp.asarray(self._tables.copy())
            self.engine.obs.steps.count(slot_uploads=1)
        return self._tables_dev

    def ensure_blocks(self, slot: int, total_tokens: int) -> bool:
        """Grow slot's block list (and its host table row) to cover
        total_tokens; False if the pool is empty. Host work only: the
        grown row rides the next engine program (slot_tables)."""
        eng = self.engine
        need = -(-total_tokens // self.block_size)
        have = len(self._slot_blocks[slot])
        if need <= have:
            return True
        if need - have > len(self._free) + self.evictable():
            return False
        # Opened only when the table grows: the backstop calls of
        # pre_window find nothing to do and record nothing.
        with eng.obs.steps.span("cache.ensure_blocks", slot=slot,
                                pages=need - have):
            self._slot_blocks[slot].extend(
                self.alloc_block() for _ in range(need - have)
            )
            self._write_row(slot)
        return True

    # ---- prefix cache ------------------------------------------------

    def chain_hashes(self, tokens: np.ndarray) -> List[bytes]:
        """Position-dependent content hashes of the full token blocks
        (see shellac_tpu.inference.prefix.chain_hashes — shared with
        the tier's directory matcher so routing and cache contents key
        identically by construction)."""
        return prefix_mod.chain_hashes(tokens, self.block_size)

    def _match_prefix(self, tokens: np.ndarray) -> Tuple[List[bytes], int]:
        """Longest cached block chain covering a strict prompt prefix
        (shared by slot admission and beam search)."""
        hashes = self.chain_hashes(tokens)
        # Cap: at least one prompt token must be computed (its logits
        # seed sampling; full-match reuse would leave none).
        cap = (tokens.size - 1) // self.block_size
        m = 0
        for h in hashes[:cap]:
            if h not in self._hash_to_block:
                break
            m += 1
        return hashes, m

    def attach_prefix(self, tokens: np.ndarray):
        """Match + attach the longest cached chain READ-ONLY: bumps
        refcounts and touches LRU order. Returns (hashes, matched
        block ids). Callers own the hit-rate stats (count them only
        once the attach is certain) and roll back a failed attach via
        detach_prefix — shared by slot admission and beam search so
        the attach protocol cannot drift between them."""
        hashes, m = self._match_prefix(tokens)
        matched = [self._hash_to_block[h] for h in hashes[:m]]
        now = time.time()
        for h, blk in zip(hashes[:m], matched):
            self._block_ref[blk] += 1
            self._hash_to_block.move_to_end(h)  # LRU touch
            self._hash_touch[h] = now
        if m:
            # Hit counters key on the last matched hash: under the
            # shared-system-prompt shape that is the tip of the shared
            # prefix, which is exactly the chain replication ships.
            tip = hashes[m - 1]
            self._prefix_hits[tip] = self._prefix_hits.get(tip, 0) + 1
            self._prefix_version += 1
        return hashes, matched

    def detach_prefix(self, matched) -> None:
        for blk in matched:
            self._block_ref[blk] -= 1

    # ---- slot lifecycle ---------------------------------------------

    def prepare_slot(self, slot: int, req, footprint: int) -> None:
        """Reserve the FULL footprint (prompt + generation budget +
        engine slack) at admission: growth mid-decode could exhaust
        the pool and there is no good victim to evict at that point.
        With the prefix cache, the longest cached chain is attached
        first (rolled back if the rest does not fit). Host work only:
        the slot's row reaches the device as an argument of its own
        prefill program."""
        eng = self.engine
        if not self.prefix_cache:
            if not self.ensure_blocks(slot, footprint):
                raise PoolExhausted()
            eng.obs.steps.annotate(pages=len(self._slot_blocks[slot]))
            return

        hashes, matched = self.attach_prefix(req.tokens)
        m = len(matched)
        if matched:
            self._slot_blocks[slot] = list(matched)
            self._write_row(slot)
        if not self.ensure_blocks(slot, footprint):
            # Roll back the attach (blocks stay cached) and requeue.
            self.detach_prefix(matched)
            self._slot_blocks[slot] = []
            self._write_row(slot)
            raise PoolExhausted()
        # The slot's own full prompt blocks become matchable only once
        # prefill has actually written them — with chunked prefill that
        # is several steps away, and registering early would let a
        # concurrent same-prefix admission attend over unwritten KV.
        # Stash the registrations; on_prefill_complete flushes them.
        self._pending_reg[slot] = [
            (j, hashes[j], hashes[j - 1] if j else b"")
            for j in range(m, req.tokens.size // self.block_size)
        ]
        self._slot_prefix_len[slot] = m * self.block_size
        eng.obs.steps.annotate(pages=len(self._slot_blocks[slot]),
                               prefix_pages=m)
        eng.stats["prefix_hit_tokens"] += m * self.block_size
        eng.stats["prefix_query_tokens"] += req.tokens.size

    def on_prefill_complete(self, slot: int) -> None:
        # The prompt blocks now hold real KV: make them matchable.
        registered = False
        now = time.time()
        for j, h, parent in self._pending_reg.pop(slot, ()):
            if h in self._hash_to_block:
                continue  # identical chain cached by an earlier finisher
            blk = self._slot_blocks[slot][j]
            self._hash_to_block[h] = blk
            self._block_ref[blk] = 1
            self._hash_parent[h] = parent
            self._hash_depth[h] = j + 1
            self._hash_touch[h] = now
            registered = True
        if registered:
            self._prefix_version += 1

    def release_slot(self, slot: int) -> None:
        """The request left `slot`: its pages go back (free list, or
        stay cached at refcount 0) and its host row is zeroed. Host
        work only; see _write_row for when the device reads the zeros."""
        self._pending_reg.pop(slot, None)
        if self.prefix_cache:
            for blk in self._slot_blocks[slot]:
                if blk in self._block_ref:
                    # Stays cached, evictable at refcount 0.
                    self._block_ref[blk] -= 1
                else:
                    self._free.append(blk)
        else:
            self._free.extend(reversed(self._slot_blocks[slot]))
        self._slot_blocks[slot] = []
        self._slot_prefix_len[slot] = 0
        self._write_row(slot)

    def pre_window(self, active_rows, advance, span: int) -> None:
        # Backstop only — admission already reserved the full
        # footprint. Lengths are tracked on host (prompt + generated so
        # far, projected past any un-synced in-flight window via
        # `advance`): no device sync in the serving hot loop. A window
        # can write up to `span` positions before the host intervenes;
        # anything past the request's own footprint lands in scratch
        # block 0 (post-finish overshoot), so the reservation is capped
        # at the footprint.
        eng = self.engine
        for i, active in enumerate(active_rows):
            if not active:
                continue
            req = eng._slots[i]
            length = (req.tokens.size + len(req.out)
                      + (advance.get(i, 0) if advance else 0))
            need = min(
                length + span,
                eng._slot_footprint(req),
            )
            if not self.ensure_blocks(i, need):
                raise RuntimeError(
                    "paged KV pool exhausted mid-decode; size "
                    "pool_tokens for n_slots concurrent worst-case "
                    "lengths"
                )

    def prefill_offset(self, slot: int) -> int:
        return self._slot_prefix_len[slot] if self.prefix_cache else 0

    def reset(self) -> None:
        """abort_all: reset the allocator to its canonical pristine
        state — prefix-cache registries purged and the free list
        rebuilt in constructor order. Keeping cached prefix blocks
        (the normal release behavior) would be a correctness bug on
        the multi-host resync path: replicas abort AFTER diverging, so
        their registries/free lists differ, and a later prompt would
        prefix-hit on one host but miss on another — different-shaped
        programs, wedged collective all over again."""
        self._hash_to_block.clear()
        self._block_ref.clear()
        self._pending_reg.clear()
        self._hash_parent.clear()
        self._hash_depth.clear()
        self._hash_touch.clear()
        self._prefix_hits.clear()
        self._prefix_version += 1
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._slot_blocks = [[] for _ in range(self.n_slots)]
        self._slot_prefix_len = [0] * self.n_slots
        for slot in range(self.n_slots):
            self._write_row(slot)

    # ---- fabric: directory manifest + chain export/seed -------------

    def prefix_manifest(self, since: int = -1, *, max_blocks: int = 512,
                        max_hot: int = 32) -> Dict[str, Any]:
        """Directory feed for GET /kv/prefixes: the registered block
        hashes (most-recent-first, capped at max_blocks so the payload
        stays bounded) plus the hottest matched hashes with
        depth/hits/age for replication planning. `since` is the
        version a prior poll returned; when nothing changed the reply
        collapses to {"unchanged": true}, keeping the health-sweep
        cadence cheap on an idle fleet. The manifest is possibly stale
        the instant it is serialized — every consumer treats entries
        as hints (a stale hit costs one prefix miss, never an
        error)."""
        if not self.prefix_cache:
            return {"supported": False}
        if since == self._prefix_version:
            return {"supported": True, "version": self._prefix_version,
                    "unchanged": True}
        now = time.time()
        blocks = [
            h.hex()
            for h in list(reversed(self._hash_to_block))[:max_blocks]
        ]
        hot = sorted(self._prefix_hits.items(), key=lambda kv: kv[1],
                     reverse=True)[:max_hot]
        return {
            "supported": True,
            "version": self._prefix_version,
            "block_size": self.block_size,
            "blocks": blocks,
            "blocks_total": len(self._hash_to_block),
            "hot": [
                {"h": h.hex(), "hits": n,
                 "depth": self._hash_depth.get(h, 0),
                 "age_s": round(now - self._hash_touch.get(h, now), 3)}
                for h, n in hot if h in self._hash_to_block
            ],
        }

    def chain_blocks(self, tip: bytes) -> Tuple[List[bytes], List[int]]:
        """Root-first (hashes, pool block ids) of the chain ending at
        `tip`. ValueError when the tip or any ancestor is no longer
        registered — a chain with an evicted link cannot be exported
        (the matcher walks from the root, so a torn chain would never
        be hit; shipping one would seed unreachable blocks)."""
        chain: List[bytes] = []
        h = tip
        while h != b"":
            if h not in self._hash_to_block:
                raise ValueError(
                    f"prefix chain broken at {h.hex()[:12]}…: link "
                    "evicted from the registry"
                )
            chain.append(h)
            h = self._hash_parent.get(h, b"")
        chain.reverse()
        return chain, [self._hash_to_block[h] for h in chain]

    def seed_blocks(self, n: int) -> List[int]:
        """Phase 1 of seeding KV pushed by a peer: allocate n pool
        blocks from the FREE LIST only — seeding is speculative, so
        it never evicts cached blocks, and a full slot's worth of
        headroom stays free so a seed can never starve the next
        admission. Raises PoolExhausted (the retryable class) when the
        pool is too tight."""
        if n > len(self._free) - self.max_blocks_per_slot:
            raise PoolExhausted()
        return [self._free.pop() for _ in range(n)]

    def abort_seed(self, blocks: List[int]) -> None:
        """Return phase-1 blocks to the free list with the registry
        untouched (the device write never happened)."""
        self._free.extend(reversed(blocks))

    def commit_seed(self, entries: List[Tuple[bytes, bytes, int]]) -> None:
        """Phase 2: the device arrays are written — register
        (hash, parent_hash, block) rows at refcount 0, i.e.
        LRU-evictable and never pinned: a seed the local workload
        never hits simply ages out of the pool."""
        now = time.time()
        for h, parent, blk in entries:
            self._hash_to_block[h] = blk
            self._block_ref[blk] = 0
            self._hash_parent[h] = parent
            self._hash_depth[h] = (
                self._hash_depth.get(parent, 0) + 1 if parent else 1
            )
            self._hash_touch[h] = now
        if entries:
            self._prefix_version += 1
            self.engine.stats["prefix_seeded_blocks"] += len(entries)

    # ---- accounting --------------------------------------------------

    def utilization(self) -> float:
        # Pool utilization replaces the dense token-count estimate:
        # blocks out of the free list / pool size (block 0 is scratch).
        pool = self.n_blocks - 1
        return (pool - len(self._free)) / pool

    def residency(self) -> Dict[str, Any]:
        out = {
            "backend": self.name,
            "slot_tokens": self._slot_tokens(),
            "slot_blocks": [len(b) for b in self._slot_blocks],
            "block_size": self.block_size,
            "blocks_total": self.n_blocks - 1,  # minus scratch
            "blocks_free": len(self._free),
            "prefix_cached_blocks": len(self._hash_to_block),
        }
        if self.cfg.dsa is not None:
            # A page holds three pools' rows; utilization() counts
            # pages, so it counts all three.
            item = jnp.dtype(self.cfg.compute_dtype).itemsize
            out.update(
                row_bytes=self.bytes_per_token(),
                index_row_bytes=(self.cfg.cache_layers
                                 * self.cfg.dsa.index_dim * item),
                rows_kept=self.cfg.dsa.topk,
            )
        if self.cfg.loop is not None:
            # A page holds a row a layer a pass; utilization() counts
            # pages, so it counts every pass's.
            out.update(
                row_bytes=self.bytes_per_token(),
                cached_layers=self.cfg.cache_layers,
                loop_steps=self.cfg.loop.steps,
            )
        return out

    def window_counts(self, pairs, n_valid) -> Dict[str, int]:
        """With an indexer: over every (slot, tick) of a synced window
        that produced a token, the rows the indexer scored (the query's
        context: it sits at position prompt + outputs settled - 1 + tick
        and scores every row up to itself) and the rows it then attended
        (no more than are kept). Host arithmetic on lengths."""
        if self.cfg.dsa is None:
            return super().window_counts(pairs, n_valid)
        scored = kept = 0
        for slot, req in pairs:
            first = req.tokens.size + len(req.out)
            ctx = first + np.arange(int(n_valid[slot]))
            scored += int(ctx.sum())
            kept += int(np.minimum(ctx, self.cfg.dsa.topk).sum())
        return {"dsa_index_rows": scored, "dsa_selected_rows": kept}


class QuantPagedBackend(PagedBackend):
    """Int8 paged pool: PagedBackend's allocator over int8 value pools
    + fp32 scale pools (layout.QuantPagedKVCache). Pure storage swap —
    scale pools mirror the value pools block-for-block, so the free
    list, prefix refcounts, and tables need no changes."""

    name = "paged-int8"

    @staticmethod
    def default_block_size(cfg: ModelConfig) -> int:
        # The grouped-gather kernel's scale DMA needs 128-token pages.
        return INT8_BLOCK_SIZE_DEFAULT

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int, *,
                 kv_quant: Optional[str] = "int8",
                 block_size: int = INT8_BLOCK_SIZE_DEFAULT,
                 pool_tokens: Optional[int] = None,
                 prefix_cache: bool = False, chunk_slack: int = 1):
        if kv_quant != "int8":
            raise ValueError(
                f"QuantPagedBackend is the int8 pool; kv_quant="
                f"{kv_quant!r} wants PagedBackend"
            )
        super().__init__(
            cfg, n_slots, max_len, kv_quant="int8",
            block_size=block_size, pool_tokens=pool_tokens,
            prefix_cache=prefix_cache, chunk_slack=chunk_slack,
        )
