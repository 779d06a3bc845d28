"""The decode program keeps a paged KV pool in place.

The stacked pool rides the layer loops of `forward_with_cache` as a
carry: rows are written at (layer's block, offset) by row updates and
read through the offset tables, so the compiled decode window holds no
second pool (the old `ys` restack), no copy of the pool into another
layout (the old scatter's), and no layer's pool sliced out (the old
`xs`). This test reads that off the compiled program, on the CPU, for
every stack layout that can hold a paged pool.
"""

import jax
import numpy as np
import pytest

from shellac_tpu.inference.batching import PagedBatchingEngine
from shellac_tpu.inference.kvcache import kv_field_names
from shellac_tpu.models import transformer

BLOCK = 128  # int8 pools need 128-token pages; bf16 takes the same
MAX_LEN = 4 * BLOCK
N_SLOTS = 4


def _compiled_decode(cfg, kv_quant, program="_decode_impl"):
    """The engine's own decode program (or, by name, its whole-prompt
    prefill), compiled for the arguments its first call passes, and the
    cache those arguments held."""
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = PagedBatchingEngine(
        cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN, block_size=BLOCK,
        decode_ticks=2, kv_quant=kv_quant,
        # A pool four times the slots' views: the views an int8 pool
        # dequantizes to float32 must not pass for a second pool either.
        pool_tokens=4 * N_SLOTS * MAX_LEN,
    )
    seen = {}
    jit_program = eng._jit_cache_program

    def spy(fn, n_tail, **kw):
        jitted = jit_program(fn, n_tail, **kw)
        if getattr(fn, "__name__", "") != program:
            return jitted

        def run(*args, **kwargs):
            if not seen:
                seen["cache"] = jax.tree.map(
                    lambda a: (a.shape, a.dtype), args[1]
                )
                seen["compiled"] = jitted.lower(*args, **kwargs).compile()
            return jitted(*args, **kwargs)

        return run

    eng._jit_cache_program = spy
    rng = np.random.default_rng(0)
    eng.run([("a", rng.integers(0, cfg.vocab_size, 5), 4)])
    return seen["compiled"], seen["cache"]


@pytest.mark.parametrize("kv_quant", [None, "int8"], ids=["bf16", "int8"])
def test_decode_program_keeps_the_pool_in_place(paged_stack_cfg, kv_quant,
                                                pool_sized_ops):
    # Eight layers: one layer's gathered view (what the reference
    # attention reads, a temporary by design) is an eighth of the pool,
    # and a second pool cannot hide behind it.
    # The looped layout walks them twice: sixteen cached layers ride
    # both passes as one carry, and a copy of the pool between passes
    # would show as a pool-sized temporary.
    cfg = paged_stack_cfg
    assert cfg.cache_kv_heads >= 2 and cfg.n_layers == 8
    assert cfg.cache_layers == (16 if cfg.loop else 8)
    compiled, cache = _compiled_decode(cfg, kv_quant)
    pools = [getattr(cache, n) for n in kv_field_names(kv_quant)]
    pool_bytes = sum(
        int(np.prod(shape)) * np.dtype(dtype).itemsize
        for shape, dtype in pools
    )
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 2, (temp, pool_bytes)
    moved = pool_sized_ops(compiled.as_text(), [s for s, _ in pools])
    assert not moved, "\n".join(moved)


@pytest.mark.parametrize("layout", ["plain", "looped"])
def test_prefill_program_keeps_the_pool_in_place(layout, pool_sized_ops):
    """The whole-prompt prefill computes into a dense scratch of the
    prompt's bucket (every cached layer's: a looped stack's holds a row
    a pass) and writes it through the slot's table: its temporaries are
    that scratch, never a pool, and the pool it was handed is the pool
    it returns."""
    from conftest import PAGED_STACK_LAYOUTS

    from shellac_tpu import get_model_config

    preset, extra = PAGED_STACK_LAYOUTS[layout]
    cfg = get_model_config(preset).replace(
        dtype="float32", n_layers=8, **extra)
    compiled, cache = _compiled_decode(cfg, None, "_prefill_impl")
    pools = [getattr(cache, n) for n in kv_field_names(None)]
    assert pools[0][0][0] == cfg.cache_layers
    pool_bytes = sum(
        int(np.prod(shape)) * np.dtype(dtype).itemsize
        for shape, dtype in pools
    )
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 8, (temp, pool_bytes)
    moved = pool_sized_ops(compiled.as_text(), [s for s, _ in pools])
    assert not moved, "\n".join(moved)
