"""EVA backend: two kinds of state for one slot in one manager.

An EVA model (cfg.eva; ops/eva_attention.py) attends the exact rows of
its current window and one pooled row per chunk of every earlier
window. Per slot that is (layout.EvaKVCache):

  * a RING of `window` exact (k, v) rows, position p at row p % window,
    overwritten window after window — the shape the rolling backend
    has, owned by the slot for as long as it holds the slot;
  * PAGES of pooled rows in a shared pool, one page per window
    (`window // chunk` rows), borrowed at admission and returned at
    release — the shape the paged backend has, and its allocator, free
    list, tables and scratch block 0 unchanged (this class inherits
    them).

`block_size` is the positions a page stands for, which is the window,
so `block_size`, `pool_tokens`, `footprint` and `ensure_blocks` keep
their meaning in tokens and whole footprints are reserved at admission
exactly as for the paged pool. What a token costs is no longer a
constant: its exact row is paid until its window closes, a sixteenth of
a row after (`bytes_per_token`, `resident_rows`).

What this state cannot do yet is refused in ONE place, `refuse()`, with
the reason; the constructor, `bind()` and the callers that own the
remaining switches (the engine's pp_pipeline and prefill-chunk setters,
the server's park directory and role) all go through it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference.cache.layout import (
    eva_cache_logical_axes,
    init_eva_cache,
)
from shellac_tpu.inference.cache.paged import PagedBackend

#: feature -> why the EVA state cannot carry it yet (ROADMAP Queue 2).
UNSUPPORTED = {
    "prefix_cache": (
        "a shared prefix would need a snapshot of the ring at the "
        "prefix's end, not only its pages"
    ),
    "kv_quant": "the ring and the pooled rows have no int8 form",
    "chunked_prefill": (
        "a cached chunk of several rows would need the pooled rows of "
        "chunks that complete inside it; prompts prefill whole"
    ),
    "park_resume": "parking a slot would have to ship its ring and its pages",
    "kv_export": (
        "disaggregated export ships rows by token position; a slot here "
        "is a ring and pages of pooled rows"
    ),
    "speculative": (
        "a verify window writes several rows and rolls back; a ring row "
        "overwritten by a rejected token cannot be restored"
    ),
    "pp_pipeline": "the per-stage registers hold dense rows only",
    "beam_search": (
        "beams share pages copy-on-write, and a ring has no pages to "
        "share"
    ),
    "mesh": "the ring and the pool are not sharded yet",
}


class EvaBackend(PagedBackend):
    name = "eva"
    holds_eva = True

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int, *,
                 kv_quant: Optional[str] = None,
                 block_size: Optional[int] = None,
                 pool_tokens: Optional[int] = None,
                 prefix_cache: bool = False, chunk_slack: int = 1):
        window = cfg.eva.window if cfg.eva is not None else None
        if window is not None and block_size not in (None, window):
            raise ValueError(
                f"the 'eva' backend's page is one window: block_size must "
                f"be {window} (cfg.eva.window), got {block_size}"
            )
        if kv_quant is not None:
            self.refuse("kv_quant")
        if prefix_cache:
            self.refuse("prefix_cache")
        if chunk_slack != 1:
            self.refuse("chunked_prefill")
        # (A model with no cfg.eva is refused by the base constructor.)
        super().__init__(cfg, n_slots, max_len, block_size=window or 1,
                         pool_tokens=pool_tokens, chunk_slack=1)

    @staticmethod
    def default_block_size(cfg: ModelConfig) -> int:
        return cfg.eva.window

    @staticmethod
    def refuse(feature: str) -> None:
        raise ValueError(
            f"the 'eva' cache backend does not support {feature} yet: "
            f"{UNSUPPORTED[feature]}"
        )

    def check_feature(self, feature: str) -> None:
        if feature in UNSUPPORTED:
            self.refuse(feature)
        super().check_feature(feature)

    def bind(self, engine) -> None:
        from shellac_tpu.inference.spec_batching import _SpecDecodeMixin

        if isinstance(engine, _SpecDecodeMixin):
            self.refuse("speculative")
        if engine.mesh is not None:
            self.refuse("mesh")
        super().bind(engine)

    def decode_attn(self) -> str:
        """How the engine's decode program attends: through the kernel
        that moves a slot's valid ring rows and own pages only
        ("eva_kernel"), or through the XLA form that reads every ring
        row and every page and masks ("xla"). The model's own rule,
        asked with the shapes built here."""
        from shellac_tpu.ops.eva_attention import eva_decode_path

        cfg, e = self.cfg, self.cfg.eva
        h, d, layers = cfg.n_heads, cfg.dim_per_head, cfg.cache_layers
        return eva_decode_path(
            (self.n_slots, h, d),
            (layers, e.window, self.n_slots, h, d),
            (layers, h, self.n_blocks, e.window // e.chunk, d),
            cfg.compute_dtype, self.engine.attn_impl,
        )

    def initial_stats(self) -> Dict[str, int]:
        # Non-numeric, as on the paged backend: /metrics skips it.
        self._decode_attn = self.decode_attn()
        return {"decode_attn": self._decode_attn}

    # ---- device cache construction ----------------------------------

    def init_cache(self):
        return init_eva_cache(self.cfg, self.n_slots, self.n_blocks,
                              self.max_blocks_per_slot)

    def init_mini(self, length: int):
        raise NotImplementedError(
            "the 'eva' backend prefills through a view of the slot "
            "(prefill_into), not into a scratch cache"
        )

    def logical_axes(self):
        return eva_cache_logical_axes(self.cfg)

    def prefill_rows(self, tokens: int):
        """One buffer for every prompt, `max_len` in whole windows (one
        prefill program, whatever the length); the program runs the
        prompt's windows alone."""
        w = self.cfg.eva.window
        return -(-self.max_len // w) * w, -(-tokens // w) * w

    def prefill_into(self, cache, slot, tokens, prompt_len, forward, *,
                     all_rows: bool):
        """Prefill one prompt straight into `slot`, a window at a time:
        a loop over the prompt's windows alone, ceil(prompt_len / W)
        trips whatever the buffer's length, runs `forward` on W rows of
        `tokens` at a time, on a batch-1 view that shares the rings and
        the pool and names the slot's ring row and table row. Each window
        attends its own rows and the pooled rows its predecessors left
        in the slot's pages, writes its rows over the ring and its pooled
        rows to its page; the last window's rows stay in the ring, where
        decode will read them. Logits as `PagedBackend.prefill_into`'s:
        every row's (rows of windows not run are zeros, and no score
        reads them), or the last token's, unembedded from its window
        alone."""
        w = self.cfg.eva.window
        n = prompt_len.astype(jnp.int32)
        view = cache.replace(
            tables=jax.lax.dynamic_slice_in_dim(cache.tables, slot, 1, 0),
            lengths=jnp.zeros((1,), jnp.int32),
            slots=jnp.reshape(slot, (1,)).astype(jnp.int32),
        )

        def window(i, carry):
            view, logits = carry
            start = i * w
            real = jnp.minimum(n - start, w)
            lg, view = forward(
                view, jax.lax.dynamic_slice_in_dim(tokens, start, w, axis=1),
                real, fresh_cache=False, logits_at=None if all_rows else real - 1,
            )
            if all_rows:
                lg = jax.lax.dynamic_update_slice_in_dim(logits, lg, start,
                                                         axis=1)
            return view, lg

        logits = jnp.zeros(
            (1, tokens.shape[1] if all_rows else 1, self.cfg.vocab_size),
            jnp.float32)
        view, logits = jax.lax.fori_loop(
            0, (n[0] + w - 1) // w, window, (view, logits))
        return logits, cache.replace(
            k=view.k, v=view.v, pk=view.pk, pv=view.pv,
            lengths=jax.lax.dynamic_update_slice(
                cache.lengths, view.lengths, (slot,)
            ),
        )

    # ---- accounting --------------------------------------------------

    def _row_bytes(self) -> int:
        """One exact row, or one pooled row: k and v, every layer."""
        cfg = self.cfg
        return (2 * cfg.cache_layers * cfg.n_heads * cfg.dim_per_head
                * jnp.dtype(cfg.compute_dtype).itemsize)

    def resident_rows(self, tokens: int):
        """(exact rows, pooled rows) live for a slot holding `tokens`
        positions: its current window's rows, and a pooled row for every
        chunk completed so far (the current window's are written as
        they complete, though not read until the window does)."""
        e = self.cfg.eva
        if tokens <= 0:
            return 0, 0
        return (tokens - 1) % e.window + 1, tokens // e.chunk

    def bytes_per_token(self) -> int:
        """What a position costs once its window has closed: its share
        of a pooled row. (Inside its window it costs a whole exact row;
        `residency()` has the live split.) The tier's transfer-cost
        estimate reads this as bytes per context token, and long
        contexts are nearly all pooled."""
        return -(-self._row_bytes() // self.cfg.eva.chunk)

    def utilization(self) -> float:
        """Bytes live over bytes held: the rings' rows in use plus the
        pooled rows written, over every ring and every page of the pool
        (block 0 is scratch). Not a constant times the tokens: a slot's
        exact rows fall back to 1 each time a window closes."""
        e = self.cfg.eva
        rows = [self.resident_rows(t) for t in self._slot_tokens()]
        live = sum(x + p for x, p in rows)
        held = (self.n_slots * e.window
                + (self.n_blocks - 1) * (e.window // e.chunk))
        return live / held

    def residency(self) -> Dict[str, Any]:
        rows = [self.resident_rows(t) for t in self._slot_tokens()]
        out = super().residency()
        out.update(
            window=self.cfg.eva.window, chunk=self.cfg.eva.chunk,
            slot_window_rows=[x for x, _ in rows],
            slot_summary_rows=[p for _, p in rows],
            row_bytes=self._row_bytes(),
        )
        return out

    def window_counts(self, pairs, n_valid) -> Dict[str, int]:
        """Work of one synced decode window, from lengths the host
        already has: over every (slot, tick) that produced a token, the
        exact rows and the pooled rows its query attended, and the rows
        the program's read path MOVED for them (`eva_read_rows`;
        ops/eva_attention.py: the kernel a slot's valid rows in whole
        blocks and its own pages; the XLA form every ring row a
        slot-tick and the whole pool once a tick). A request with n
        outputs settled has its prompt plus n - 1 positions written, so
        the window's tick t sits at position prompt + n - 1 + t."""
        from shellac_tpu.ops.eva_attention import eva_ring_block

        e = self.cfg.eva
        per_page = e.window // e.chunk
        kernel = self._decode_attn == "eva_kernel"
        block = eva_ring_block(e.window)
        exact = pooled = read = ticks = 0
        for slot, req in pairs:
            first = req.tokens.size + len(req.out) - 1
            p = first + np.arange(int(n_valid[slot]))
            n_exact = p % e.window + 1
            exact += int(n_exact.sum())
            pooled += int((p // e.window).sum()) * per_page
            ticks = max(ticks, p.size)
            read += (int((-(-n_exact // block) * block).sum()) if kernel
                     else p.size * e.window)
        read += pooled if kernel else ticks * self.n_blocks * per_page
        return {"eva_window_rows": exact, "eva_summary_rows": pooled,
                "eva_read_rows": read}
