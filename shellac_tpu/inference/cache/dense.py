"""Dense slot backend: one (max_len) cache row per slot, bf16 or int8.

The simplest storage policy — every slot reserves its full row, so
there is nothing to allocate or free; capacity accounting is token
counting. kv_quant="int8" swaps the row storage for int8 values +
per-token fp32 scales (half the resident bytes and half the HBM
stream per decode tick) with no policy change.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference.cache.base import CacheBackend
from shellac_tpu.inference.cache.layout import (
    cache_logical_axes_for,
    init_cache_for,
)


class DenseBackend(CacheBackend):
    name = "dense"

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int, *,
                 kv_quant: Optional[str] = None, chunk_slack: int = 1):
        super().__init__(cfg, n_slots, max_len, kv_quant=kv_quant,
                         chunk_slack=chunk_slack)
        if kv_quant == "int8":
            self.name = "dense-int8"

    def init_cache(self):
        return init_cache_for(self.cfg, self.n_slots, self.max_len,
                              self.kv_quant)

    def init_mini(self, length: int):
        return init_cache_for(self.cfg, 1, length, self.kv_quant)

    def logical_axes(self):
        return cache_logical_axes_for(self.cfg, self.kv_quant)

    def utilization(self) -> float:
        return sum(self._slot_tokens()) / (self.n_slots * self.max_len)

    def residency(self) -> Dict[str, Any]:
        return {
            "backend": self.name,
            "slot_tokens": self._slot_tokens(),
            "capacity_tokens": self.n_slots * self.max_len,
            "row_bytes": self.bytes_per_token(),
            "cached_layers": self.cfg.cache_layers,
        }
