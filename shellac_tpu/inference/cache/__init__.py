"""The KV-cache subsystem: storage layouts + pluggable backends.

`layout` holds the cache pytrees and their update/gather free functions
(the former inference/kvcache.py, still importable there); `base`
defines the CacheBackend interface the engines hold; `dense` / `paged`
/ `rolling` / `eva` implement the storage policies. This registry is the ONE
name->backend mapping every consumer resolves through — the engines,
the CLI's --cache-backend flag (and its deprecated legacy aliases
--paged / --kv-quant / --rolling-window), and the tests — so a new
backend registers once and is reachable everywhere.
"""

from __future__ import annotations

from typing import Optional

from shellac_tpu.inference.cache.base import CacheBackend, PoolExhausted
from shellac_tpu.inference.cache.dense import DenseBackend
from shellac_tpu.inference.cache.eva import EvaBackend
from shellac_tpu.inference.cache.paged import PagedBackend, QuantPagedBackend
from shellac_tpu.inference.cache.rolling import RollingBackend

__all__ = [
    "BACKENDS",
    "CacheBackend",
    "DenseBackend",
    "EvaBackend",
    "PagedBackend",
    "PoolExhausted",
    "QuantPagedBackend",
    "RollingBackend",
    "backend_flags",
    "engine_class",
    "make_backend",
    "resolve_backend_name",
]

# name -> (backend class, pinned ctor kwargs). The int8 variants pin
# kv_quant so one registry name fully determines the storage.
BACKENDS = {
    "dense": (DenseBackend, {}),
    "dense-int8": (DenseBackend, {"kv_quant": "int8"}),
    "paged": (PagedBackend, {}),
    "paged-int8": (QuantPagedBackend, {}),
    "rolling": (RollingBackend, {}),
    "rolling-int8": (RollingBackend, {"kv_quant": "int8"}),
    "eva": (EvaBackend, {}),
}

# What the legacy engine/CLI flags would have been for each name —
# engines keep exposing .kv_quant / .rolling_window for compatibility.
_FLAGS = {
    "dense": (False, None, False),
    "dense-int8": (False, "int8", False),
    "paged": (True, None, False),
    "paged-int8": (True, "int8", False),
    "rolling": (False, None, True),
    "rolling-int8": (False, "int8", True),
    # Paged: its pooled rows live in a block pool behind tables, and the
    # paged engine (block_size, pool_tokens) drives it.
    "eva": (True, None, False),
}


def backend_flags(name: str):
    """(is_paged, kv_quant, rolling_window) for a registry name."""
    if name not in _FLAGS:
        raise ValueError(
            f"unknown cache backend {name!r}; have {sorted(BACKENDS)}"
        )
    return _FLAGS[name]


def resolve_backend_name(
    explicit: Optional[str] = None, *,
    paged: bool = False,
    kv_quant: Optional[str] = None,
    rolling_window: bool = False,
) -> str:
    """Canonical backend name from an explicit --cache-backend choice
    and/or the deprecated legacy flags. Legacy flags alone map onto
    the registry; combined with an explicit name they must AGREE —
    a conflict is a config error, not a silent precedence rule."""
    if kv_quant not in (None, "int8"):
        raise ValueError(f"kv_quant={kv_quant!r}; have None, 'int8'")
    if paged and rolling_window:
        raise ValueError(
            "rolling_window is a slot-cache feature; the paged pool "
            "sizes memory via its block pool instead"
        )
    kind = "paged" if paged else ("rolling" if rolling_window else "dense")
    legacy = kind + ("-int8" if kv_quant == "int8" else "")
    if explicit is None:
        return legacy
    if explicit not in BACKENDS:
        raise ValueError(
            f"unknown cache backend {explicit!r}; have {sorted(BACKENDS)}"
        )
    # Each explicitly-set legacy flag must AGREE with the explicit
    # name (unset flags — the dense no-op defaults — impose nothing).
    exp_paged, exp_quant, exp_rolling = _FLAGS[explicit]
    if ((paged and not exp_paged)
            or (rolling_window and not exp_rolling)
            or (kv_quant is not None and kv_quant != exp_quant)):
        raise ValueError(
            f"cache backend {explicit!r} conflicts with legacy flags "
            f"(paged={paged}, kv_quant={kv_quant!r}, "
            f"rolling_window={rolling_window}); drop the legacy flags "
            "— they are deprecated aliases"
        )
    return explicit


def make_backend(name: str, cfg, n_slots: int, max_len: int,
                 **opts) -> CacheBackend:
    """Instantiate a registered backend. `opts` are the policy knobs
    (block_size, pool_tokens, prefix_cache, chunk_slack); knobs a
    backend does not take are rejected by its constructor — loudly,
    because a silently dropped pool size is a capacity incident."""
    if name not in BACKENDS:
        raise ValueError(
            f"unknown cache backend {name!r}; have {sorted(BACKENDS)}"
        )
    cls, pinned = BACKENDS[name]
    return cls(cfg, n_slots, max_len, **{**pinned, **opts})


def engine_class(name: str, speculative: bool = False):
    """The engine class serving a backend name (lazy imports: the
    engines import this package for their backends)."""
    paged, _, _ = backend_flags(name)
    if speculative:
        from shellac_tpu.inference.spec_batching import (
            PagedSpeculativeBatchingEngine,
            SpeculativeBatchingEngine,
        )

        return (PagedSpeculativeBatchingEngine if paged
                else SpeculativeBatchingEngine)
    from shellac_tpu.inference.batching import (
        BatchingEngine,
        PagedBatchingEngine,
    )

    return PagedBatchingEngine if paged else BatchingEngine
