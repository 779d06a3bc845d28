"""Compatibility shim: the cache layouts moved into the
`shellac_tpu.inference.cache` subsystem (cache/layout.py holds the
pytrees and update/gather free functions; cache/{dense,paged,rolling}.py
hold the CacheBackend storage policies the engines plug in).

Every public name keeps resolving from here so existing imports —
engines, kernels, tests, external callers — stay valid.
"""

from shellac_tpu.inference.cache.layout import *  # noqa: F401,F403
from shellac_tpu.inference.cache.layout import (  # noqa: F401
    cache_logical_axes,
    cache_logical_axes_for,
    eva_cache_logical_axes,
    eva_ring_write,
    init_cache,
    init_cache_for,
    init_eva_cache,
    init_eva_slot_cache,
    init_paged_cache,
    init_quant_cache,
    init_quant_paged_cache,
    kv_field_names,
    paged_cache_logical_axes,
    paged_gather_layer,
    paged_gather_scales,
    paged_update_layer,
    paged_write,
    paged_write_prompt,
    quant_cache_logical_axes,
    quant_paged_cache_logical_axes,
    quant_paged_update_layer,
    quant_roll_update_layer,
    quant_update_layer,
    quantize_kv,
    roll_update_layer,
    rolled_kv_positions,
    scatter_slot,
    slot_view,
    update_layer,
)
