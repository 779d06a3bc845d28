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


def _compiled_decode(cfg, kv_quant):
    """The engine's own decode program, compiled for the arguments its
    first window passes, and the cache those arguments held."""
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
        if getattr(fn, "__name__", "") != "_decode_impl":
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
    cfg = paged_stack_cfg
    assert cfg.cache_kv_heads >= 2 and cfg.n_layers == 8
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
