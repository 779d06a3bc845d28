"""CacheBackend: the storage-policy interface the serving engines hold.

The separation is TVM's algorithm-vs-schedule split applied to KV
storage: decode ALGORITHMS (the dense window, the speculative verify
round, beam search, chunked prefill) are written once against this
interface, while the STORAGE POLICY — dense slot rows, paged block
pool, int8 quantization, rolling ring — is a pluggable backend behind
it. An engine never branches on cache shape; it asks its backend.

A backend owns two things:

  1. the DEVICE cache construction contract: `init_cache()` builds the
     engine's cache pytree, `init_mini(length)` the batch-1 prefill
     scratch of the matching kind, and `logical_axes()` the sharding
     axes tree — the single place jit `out_shardings` derive from, so
     sharding can never desync from what the backend built;
  2. the HOST-side slot residency policy: `prepare_slot` /
     `release_slot` / `pre_window` / `reset` hooks (the paged block
     allocator and prefix-cache registries live entirely here),
     `utilization()` for the capacity gauge, and `residency()` — a
     JSON-serializable report of what each slot holds, the piece the
     disaggregated prefill/decode split will ship between hosts.

Backends are bound to exactly one engine (`bind`); the engine keeps
rebinding `engine._cache` from its jitted programs' donated outputs —
one owner for the device tree, one for the host policy. The backend
never writes that tree: what its policy changes about a slot (the
paged pool's block-table rows) it keeps on the host and hands the
engine's next program as an argument (`slot_tables`), so admission and
release dispatch nothing of their own.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from shellac_tpu.config import ModelConfig


class PoolExhausted(Exception):
    """Raised by `prepare_slot` when the backend cannot admit the
    request right now (paged: pool has too few free/evictable blocks).
    The engine requeues the request and retries after a release."""


#: feature -> why a looped stack (cfg.loop: cfg.cache_layers cached
#: layers, n_layers a pass, every cache kind's leading axis) cannot
#: carry it yet (ROADMAP Queue 2). Refused where the switch is turned
#: on: a backend's constructor, bind(), check_feature(), and the
#: single-request Engine's constructor. The 'dense' and 'paged'
#: backends, int8 pools and slot caches, the prefix cache and chunked
#: prefill carry a looped stack (tests/test_loop.py).
LOOP_UNSUPPORTED = {
    "rolling": (
        "a ring holds a window's rows of one layer; no published looped "
        "model has a window, and a ring a pass is not tested"
    ),
    "speculative": (
        "a verify window rolls rejected tokens back, and every pass's "
        "rows of each would have to go"
    ),
    "pp_pipeline": (
        "a stage's registers hold its layers' rows for one pass; the "
        "stream would cross the stages once a pass"
    ),
    "mesh": (
        "the gate and the cached layers of a pass are not sharded yet"
    ),
    "park_resume": (
        "a parked slot's blob names n_layers layers; shipping a row a "
        "pass is not tested"
    ),
    "kv_export": (
        "the disaggregated blob's agreement block names n_layers layers; "
        "a decode replica would read a pass's rows as the model's"
    ),
    "beam_search": (
        "beams copy pages on write and reorder slot rows; neither is "
        "tested over a row a pass"
    ),
}


def refuse_loop(feature: str) -> None:
    raise ValueError(
        f"a looped stack (cfg.loop) does not support {feature} yet: "
        f"{LOOP_UNSUPPORTED[feature]}"
    )


class CacheBackend:
    """Base storage policy: one slot row per request, nothing to
    allocate. Subclasses override the hooks that their policy needs;
    every default below is the dense no-op."""

    #: registry name ("dense", "paged-int8", ...) — exposed at /stats
    #: and as the shellac_engine_cache_backend_info gauge label.
    name: str = "dense"
    #: True for block-pool backends (drives the pp-pipeline gate and
    #: the engines' historical `_swaps_cache` contract).
    is_paged: bool = False
    #: True for ring-buffer backends (the engines' rolling_window
    #: compatibility attribute derives from this).
    is_rolling: bool = False
    #: True for the backend that holds EVA attention state (cfg.eva):
    #: such a model serves on it and on nothing else, and it serves
    #: nothing else.
    holds_eva: bool = False

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int, *,
                 kv_quant: Optional[str] = None, chunk_slack: int = 1):
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant={kv_quant!r}; have None, 'int8'")
        if (cfg.eva is not None) != self.holds_eva:
            raise ValueError(
                "a model with EVA attention (cfg.eva) keeps a ring of exact "
                "rows and a pool of pooled rows, and serves on the 'eva' "
                f"cache backend and no other; the {self.name!r} backend "
                + ("holds no such state" if cfg.eva is not None
                   else "holds nothing else (this model has no cfg.eva)")
            )
        if cfg.dsa is not None and not self.is_paged:
            raise ValueError(
                "a model with an indexer (cfg.dsa) keeps one index key a "
                "token in a third pool under the block table, and serves "
                f"on the 'paged' cache backend; the {self.name!r} backend "
                "holds no such pool"
            )
        if cfg.loop is not None and self.is_rolling:
            refuse_loop("rolling")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.kv_quant = kv_quant
        self.chunk_slack = chunk_slack
        self.engine: Any = None

    # ---- engine binding ---------------------------------------------

    def bind(self, engine) -> None:
        """Attach to the owning engine. One backend, one engine: the
        slot hooks read engine state (slots, stats, the live cache
        pytree) and a shared backend would alias allocator state."""
        if self.engine is not None and self.engine is not engine:
            raise ValueError(
                f"{self.name} backend is already bound to an engine; "
                "construct one backend per engine"
            )
        if self.cfg.loop is not None:
            from shellac_tpu.inference.spec_batching import _SpecDecodeMixin

            if isinstance(engine, _SpecDecodeMixin):
                refuse_loop("speculative")
            if engine.mesh is not None:
                refuse_loop("mesh")
        self.engine = engine

    # ---- device cache construction ----------------------------------

    def init_cache(self):
        raise NotImplementedError

    def init_mini(self, length: int):
        """Batch-1 prefill scratch of the kind the engine's prefill
        program scatters into this backend's cache."""
        raise NotImplementedError

    def logical_axes(self):
        """Sharding axes tree matching init_cache()'s pytree."""
        raise NotImplementedError

    # ---- slot lifecycle (host-side policy) --------------------------

    def prepare_slot(self, slot: int, req, footprint: int) -> None:
        """Reserve residency for `req` before its prefill. `footprint`
        is the request's worst-case token residency (prompt + budget +
        engine slack). May raise PoolExhausted; the engine requeues."""

    def on_prefill_complete(self, slot: int) -> None:
        """The slot's prompt KV is now real (prefill finished) —
        paged prefix caching registers the prompt blocks here."""

    def release_slot(self, slot: int) -> None:
        """The request left `slot` (finish/cancel/abort)."""

    def pre_window(self, active_rows, advance: Optional[Dict[int, int]],
                   span: int) -> None:
        """About to run one decode window writing up to `span` tokens
        per active slot; `advance` maps slot -> tokens an un-synced
        in-flight window will still append (overlapped dispatch)."""

    def prefill_offset(self, slot: int) -> int:
        """Tokens already resident when prefill starts (paged prefix
        caching returns the matched prefix length)."""
        return 0

    def slot_tables(self):
        """Per-slot storage indirection the next engine program must
        see (the paged pool's block table: a host array whose changed
        rows ride the program as an argument and become its
        cache.tables), or None where a slot's storage is its own row."""
        return None

    def reset(self) -> None:
        """abort_all: restore the allocator to its canonical pristine
        state (multi-host resync depends on every replica converging
        to identical post-abort state)."""

    def initial_stats(self) -> Dict[str, int]:
        """Backend-owned counters merged into engine.stats at
        construction (paged prefix caching adds its hit counters)."""
        return {}

    def check_feature(self, feature: str) -> None:
        """Raise ValueError if this storage cannot carry `feature`
        ("pp_pipeline", "chunked_prefill", "park_resume", "kv_export",
        ...). Called where a switch that needs the feature is turned
        on, so the refusal comes at construction and not mid-request.
        The base policy refuses what a looped stack cannot carry
        (LOOP_UNSUPPORTED) and nothing else: each feature's own gate
        (validate_pp_pipeline, disagg._check_exportable) still holds."""
        if self.cfg.loop is not None and feature in LOOP_UNSUPPORTED:
            refuse_loop(feature)

    def window_counts(self, pairs, n_valid) -> Dict[str, int]:
        """Backend-owned work counts of one synced decode window
        (`pairs`: the (slot, request) rows it ran; `n_valid[slot]`: the
        ticks that produced a token), added to the step record. Host
        arithmetic on lengths already known; never a device read.

        The base policy counts a looped stack's passes (cfg.loop; no
        counts otherwise): over every slot-tick that produced a token,
        the stack passes it ran (`steps` each) and the cached rows it
        read over all of them (`steps` x its context: a query at
        position p reads p + 1 rows of its own pass in every pass)."""
        if self.cfg.loop is None:
            return {}
        steps = self.cfg.loop.steps
        ticks = rows = 0
        for slot, req in pairs:
            n = int(n_valid[slot])
            first = req.tokens.size + len(req.out)  # the first tick's context
            ticks += n
            rows += n * first + n * (n - 1) // 2
        return {"loop_passes": steps * ticks, "loop_kv_rows": steps * rows}

    def prefix_manifest(self, since: int = -1, **_: Any) -> Dict[str, Any]:
        """Directory feed for GET /kv/prefixes. Backends without a
        prefix-cache registry answer {"supported": false} — an honest
        refusal the tier's directory treats as "never route here for
        cache contents", never an error."""
        return {"supported": False}

    # ---- accounting --------------------------------------------------

    def utilization(self) -> float:
        """Live residency / capacity, in [0, 1] (the kv_utilization
        gauge the serving tier's load scoring reads)."""
        raise NotImplementedError

    def residency(self) -> Dict[str, Any]:
        """JSON-serializable per-slot residency: what each slot holds
        and the pool-level headroom. The engine adds request identity;
        this is the storage view only."""
        raise NotImplementedError

    def row_width(self, width: int) -> int:
        """Lanes this storage holds a row of `width` at: the row's own,
        unless the policy pads it (the paged pool: whole lane tiles)."""
        return width

    def bytes_per_token(self) -> int:
        """Resident KV bytes one token costs under this storage policy
        (per-slot view; paged block rounding ignored). Exposed as the
        shellac_engine_kv_bytes_per_token gauge — the tier's
        KV-migration transfer-cost estimate reads it, so the cost
        model tracks the backend (int8 halves it) instead of guessing
        from the model name."""
        import jax.numpy as jnp

        cfg = self.cfg
        width = (self.row_width(cfg.cache_head_dim)
                 + self.row_width(cfg.cache_v_head_dim))
        # One row a cached layer: a looped stack holds one a layer a
        # pass (cfg.cache_layers, which the pools' shapes read too).
        if self.kv_quant == "int8":
            # int8 values + one fp32 scale per token/head for k and v.
            return cfg.cache_layers * cfg.cache_kv_heads * (width + 2 * 4)
        itemsize = jnp.dtype(cfg.compute_dtype).itemsize
        # With an indexer, one index key a token a layer beside k and v.
        index = cfg.dsa.index_dim if cfg.dsa is not None else 0
        return (cfg.cache_layers * (cfg.cache_kv_heads * width + index)
                * itemsize)

    # ---- shared helpers ---------------------------------------------

    def _slot_tokens(self) -> List[int]:
        """Host-known live tokens per slot (prompt + generated)."""
        eng = self.engine
        return [
            (r.tokens.size + len(r.out)) if r is not None else 0
            for r in eng._slots
        ]
