"""Paged KV cache: block-pool correctness and memory behavior.

Core invariant (same as dense continuous batching): paging must be
invisible to the math — greedy output equals the single-request Engine
for every request, through block allocation, slot churn, and reuse.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu import get_model_config
from shellac_tpu.inference.batching import PagedBatchingEngine
from shellac_tpu.inference.engine import Engine
from shellac_tpu.inference.kvcache import (
    init_cache,
    init_cache_for,
    init_paged_cache,
    init_quant_paged_cache,
    kv_field_names,
    paged_gather_layer,
    paged_gather_scales,
    paged_update_layer,
)
from shellac_tpu.models import transformer


def _tiny(**kw):
    return get_model_config("tiny").replace(dtype="float32", **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = _tiny()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _ref(cfg, params, tokens, max_new):
    eng = Engine(cfg, params, temperature=0.0)
    out = eng.generate(
        jnp.asarray(np.asarray(tokens, np.int32)[None]), max_new_tokens=max_new
    )
    return np.asarray(out.tokens)[0].tolist()


class TestPagedOps:
    def test_update_then_gather_roundtrip(self, rng):
        pool_k = jnp.zeros((5, 2, 4, 8))  # (nb, H=2, bs=4, D=8)
        pool_v = jnp.zeros((5, 2, 4, 8))
        tables = jnp.asarray([[1, 3], [2, 4]], jnp.int32)  # 2 slots
        k_new = jnp.asarray(rng.normal(size=(2, 3, 2, 8)), jnp.float32)
        v_new = jnp.asarray(rng.normal(size=(2, 3, 2, 8)), jnp.float32)
        index = jnp.asarray([2, 0], jnp.int32)  # slot0 writes pos 2..4
        pk, pv = paged_update_layer(pool_k, pool_v, k_new, v_new, index, tables)
        k_all, _ = paged_gather_layer(pk, pv, tables)  # (B, H, mb*bs, D)
        k_all = jnp.transpose(k_all, (0, 2, 1, 3))  # token-major for asserts
        # Slot 0 positions 2,3 -> block 1 offsets 2,3; pos 4 -> block 3 off 0.
        np.testing.assert_allclose(np.asarray(k_all[0, 2:5]), np.asarray(k_new[0]))
        # Slot 1 positions 0..2 -> block 2.
        np.testing.assert_allclose(np.asarray(k_all[1, 0:3]), np.asarray(k_new[1]))

    def test_paged_forward_matches_dense(self, setup):
        """Same tokens through dense and paged caches -> same logits."""
        cfg, params = setup
        toks = jax.random.randint(jax.random.PRNGKey(3), (2, 7), 0,
                                  cfg.vocab_size)
        dense = init_cache(cfg, 2, 32)
        paged = init_paged_cache(cfg, 2, n_blocks=17, block_size=4,
                                 max_blocks_per_slot=8)
        # Allocate disjoint nonzero blocks for both slots up front.
        tables = jnp.asarray(
            [[1, 2, 3, 4, 0, 0, 0, 0], [5, 6, 7, 8, 0, 0, 0, 0]], jnp.int32
        )
        paged = paged.replace(tables=tables)

        ld, dense = transformer.forward_with_cache(cfg, params, toks, dense)
        lp, paged = transformer.forward_with_cache(cfg, params, toks, paged)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ld), atol=1e-5)
        # And one decode step each.
        nxt = jnp.argmax(ld[:, -1], -1).astype(jnp.int32)[:, None]
        ld2, _ = transformer.forward_with_cache(cfg, params, nxt, dense)
        lp2, _ = transformer.forward_with_cache(cfg, params, nxt, paged)
        np.testing.assert_allclose(np.asarray(lp2), np.asarray(ld2), atol=1e-5)

    @pytest.mark.parametrize("kv_quant", [None, "int8"],
                             ids=["bf16", "int8"])
    def test_paged_pool_matches_dense_rows(self, paged_stack_cfg, kv_quant):
        """Every stack layout x pool kind: runs of s > 1 rows that cross
        a page, then s = 1 ticks with one slot frozen, give the dense
        slot cache's logits, and the pool holds the dense cache's rows
        bit for bit at the same positions. A slot whose table is
        unallocated writes into scratch block 0 and nowhere else."""
        cfg = paged_stack_cfg
        bs, mb, b = 4, 6, 3
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        dense = init_cache_for(cfg, b, mb * bs, kv_quant=kv_quant)
        init = init_quant_paged_cache if kv_quant else init_paged_cache
        owned = np.arange(1, 2 * mb + 1).reshape(2, mb)
        paged = init(cfg, b, n_blocks=2 * mb + 3, block_size=bs,
                     max_blocks_per_slot=mb)
        # Slots 0 and 1 own their pages; slot 2's table is unallocated.
        paged = paged.replace(tables=jnp.asarray(
            np.concatenate([owned, np.zeros((1, mb), int)]), jnp.int32
        ))
        fields = kv_field_names(kv_quant)

        def both(toks, dense, paged):
            ld, dense = transformer.forward_with_cache(
                cfg, params, toks, dense)
            lp, paged = transformer.forward_with_cache(
                cfg, params, toks, paged)
            np.testing.assert_allclose(
                np.asarray(lp[:2]), np.asarray(ld[:2]), atol=1e-5)
            return ld, dense, paged

        def slot_rows(cache, slot, n):
            """The slot's first n rows of every field, every layer."""
            out = []
            for name in fields:
                pool = getattr(cache, name)
                if pool.ndim == 5:
                    views = [paged_gather_layer(p, p, cache.tables)[0]
                             for p in pool]
                else:
                    views = [paged_gather_scales(p, cache.tables)
                             for p in pool]
                out.append(np.stack([np.asarray(v[slot, :, :n])
                                     for v in views]))
            return out

        # 7 rows from 0 cross pages 0|1; 3 more from 7 cross pages 1|2.
        toks = jax.random.randint(jax.random.PRNGKey(3), (b, 10), 0,
                                  cfg.vocab_size)
        _, dense, paged = both(toks[:, :7], dense, paged)
        ld, dense, paged = both(toks[:, 7:], dense, paged)
        frozen = slot_rows(paged, 1, 10)
        for _ in range(3):
            # Slot 1 is inactive, as the engine freezes it: its length
            # stays, so each tick's stray row lands past its valid rows.
            nxt = jnp.argmax(ld[:, -1], -1).astype(jnp.int32)[:, None]
            ld, dense, paged = both(nxt, dense, paged)
            hold = paged.lengths.at[1].set(10)
            dense = dense.replace(lengths=hold)
            paged = paged.replace(lengths=hold)
        for got, want in zip(slot_rows(paged, 1, 10), frozen):
            np.testing.assert_array_equal(got, want)
        for slot, n in ((0, 13), (1, 10)):
            for name, got in zip(fields, slot_rows(paged, slot, n)):
                want = np.asarray(getattr(dense, name))[:, slot, :, :n]
                np.testing.assert_array_equal(got, want, err_msg=name)
        # Slot 2 wrote 13 rows through unallocated entries: all of them
        # in scratch block 0, none in a block no slot owns.
        k = np.asarray(paged.k)
        assert np.any(k[:, 0] != 0)
        assert not np.any(k[:, 2 * mb + 1:])


class TestPagedEngine:
    def test_matches_engine(self, setup):
        cfg, params = setup
        rng = np.random.default_rng(0)
        reqs = [
            ("a", rng.integers(0, cfg.vocab_size, 5), 7),
            ("b", rng.integers(0, cfg.vocab_size, 19), 4),
            ("c", rng.integers(0, cfg.vocab_size, 2), 9),
        ]
        srv = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  block_size=8)
        results = srv.run(reqs)
        for rid, toks, max_new in reqs:
            assert results[rid] == _ref(cfg, params, toks, max_new), rid

    def test_blocks_recycled(self, setup):
        cfg, params = setup
        rng = np.random.default_rng(1)
        srv = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=64, block_size=8,
            pool_tokens=96,  # 12 usable blocks < 2 slots * 8 blocks dense
        )
        free0 = len(srv._free)
        reqs = [(i, rng.integers(0, cfg.vocab_size, 20), 6)
                for i in range(6)]
        results = srv.run(reqs)
        assert len(results) == 6
        for rid, toks, max_new in reqs:
            assert results[rid] == _ref(cfg, params, toks, max_new), rid
        assert len(srv._free) == free0  # everything returned to the pool

    def test_admission_blocks_until_blocks_free(self, setup):
        """Pool smaller than two concurrent requests: they serialize."""
        cfg, params = setup
        srv = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=64, block_size=8,
            pool_tokens=40,  # 5+1 blocks: one 33-token request at a time
        )
        rng = np.random.default_rng(2)
        reqs = [(i, rng.integers(0, cfg.vocab_size, 33), 4) for i in range(3)]
        results = srv.run(reqs)
        assert len(results) == 3
        for rid, toks, max_new in reqs:
            assert results[rid] == _ref(cfg, params, toks, max_new), rid

    def test_non_power_of_two_max_len(self, setup):
        """Prompt whose pad bucket exceeds max_len must not corrupt KV.

        Regression: pad=64 > max_len=48 used to clamp pad positions onto
        the slot's last real block, overwriting prompt K/V.
        """
        cfg, params = setup
        rng = np.random.default_rng(3)
        toks = rng.integers(0, cfg.vocab_size, 44)
        srv = PagedBatchingEngine(cfg, params, n_slots=1, max_len=48,
                                  block_size=8)
        results = srv.run([("x", toks, 3)])
        assert results["x"] == _ref(cfg, params, toks, 3)

    def test_full_footprint_reserved_at_admission(self, setup):
        """Concurrent requests that would exhaust the pool mid-decode
        must serialize at admission instead of crashing the engine."""
        cfg, params = setup
        rng = np.random.default_rng(4)
        srv = PagedBatchingEngine(
            cfg, params, n_slots=2, max_len=64, block_size=8,
            pool_tokens=64,  # 8 usable blocks; each request needs 6
        )
        reqs = [(i, rng.integers(0, cfg.vocab_size, 20), 20)
                for i in range(2)]
        results = srv.run(reqs)
        for rid, toks, max_new in reqs:
            assert results[rid] == _ref(cfg, params, toks, max_new), rid

    def test_memory_is_actually_smaller(self, setup):
        cfg, params = setup
        dense_tokens = 8 * 512
        srv = PagedBatchingEngine(cfg, params, n_slots=8, max_len=512,
                                  block_size=16)
        pool_positions = srv._cache.k.shape[1] * srv._cache.k.shape[3]
        assert pool_positions < dense_tokens * 0.6


# ---- the block table lives on the host -------------------------------------

def _watch_tables(eng):
    """From here on, every engine program `eng` dispatches is checked as
    it returns: the device table it leaves equals the host table (the
    truth) as it stood at the dispatch, host rows are the slots' block
    lists, and no page sits in two rows unless the prefix cache shares
    it. So whichever program comes first after a row changed carries
    the change, and a program that writes a new tenant's pages runs
    with their last tenant's row already zero. Returns the list the
    checked programs' names are appended to."""
    be = eng.cache_backend
    seen = []
    jit_program = eng._jit_cache_program

    def check(name, cache):
        host = be._tables.copy()
        for slot, blocks in enumerate(be._slot_blocks):
            assert host[slot, :len(blocks)].tolist() == blocks
            assert not host[slot, len(blocks):].any()
        ids, counts = np.unique(host[host > 0], return_counts=True)
        for blk in ids[counts > 1]:
            assert int(blk) in be._block_ref, f"page {blk} in two rows"
        np.testing.assert_array_equal(np.asarray(cache.tables), host)
        seen.append(name)

    def spy(fn, n_tail, **kw):
        jitted = jit_program(fn, n_tail, **kw)
        name = getattr(fn, "__name__", None) or fn.func.__name__

        def run(*args, **kwargs):
            out = jitted(*args, **kwargs)
            check(name, out[0])
            return out

        return run

    eng._jit_cache_program = spy
    return seen


def _paged(cfg, params, **kw):
    kw = dict(dict(n_slots=2, max_len=64, block_size=8, decode_ticks=2,
                   overlap_decode=True, overlap_prefill=True), **kw)
    return PagedBatchingEngine(cfg, params, **kw)


def _drain(eng):
    out = {}
    while eng.pending:
        out.update({rid: list(t) for rid, t in eng.step()})
    return out


def _prompt(cfg, seed, n):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, n)


def _event_admission(cfg, params):
    eng = _paged(cfg, params)
    seen = _watch_tables(eng)
    eng.submit("a", _prompt(cfg, 0, 11), 9)
    eng.step()
    assert seen == ["_prefill_impl"]
    assert np.asarray(eng._cache.tables)[0, :3].all()  # 21 tokens: 3 pages
    return eng, seen, {"a": (_prompt(cfg, 0, 11), 9)}


def _event_release(cfg, params):
    eng, seen, want = _event_admission(cfg, params)
    out = _drain(eng)
    pages = set(range(1, eng.cache_backend.n_blocks)) - set(eng._free)
    assert not pages and not eng.cache_backend._tables.any()
    # the release followed the step's last program: the device row goes
    # to zero with the next one, here a new tenant's prefill, which
    # takes the very pages "a" gave back
    assert np.asarray(eng._cache.tables).any()
    eng.submit("b", _prompt(cfg, 1, 20), 5)
    eng.step()
    assert seen[-1] == "_prefill_impl"
    want["b"] = (_prompt(cfg, 1, 20), 5)
    return eng, seen, want, out


def _event_prefix_attach(cfg, params):
    eng = _paged(cfg, params, prefix_cache=True)
    seen = _watch_tables(eng)
    shared = _prompt(cfg, 2, 24)
    first = np.concatenate([shared, _prompt(cfg, 3, 3)])
    second = np.concatenate([shared, _prompt(cfg, 4, 5)])
    eng.submit("a", first, 4)
    out = _drain(eng)
    cached = [eng._hash_to_block[h]
              for h in eng.cache_backend.chain_hashes(shared)]
    eng.submit("b", second, 4)
    eng.step()
    assert eng.cache_backend._tables[0, :3].tolist() == cached
    assert seen[-1] == "_prefix_prefill_impl"
    return eng, seen, {"a": (first, 4), "b": (second, 4)}, out


def _event_pool_exhausted_rollback(cfg, params):
    # 9 usable pages. "a" leaves three cached; "c" then holds five of
    # the six free, so "b", which matches the cached three and needs
    # two more, attaches, fails to grow, and is rolled back and put
    # back in the queue until "c" is done.
    eng = _paged(cfg, params, prefix_cache=True, pool_tokens=72)
    seen = _watch_tables(eng)
    shared = _prompt(cfg, 5, 24)
    first = np.concatenate([shared, _prompt(cfg, 6, 2)])
    second = np.concatenate([shared, _prompt(cfg, 7, 6)])
    other = _prompt(cfg, 8, 30)
    eng.submit("a", first, 3)
    out = _drain(eng)
    eng.submit("c", other, 9)
    eng.submit("b", second, 9)
    eng.step()
    be = eng.cache_backend
    assert [len(b) for b in be._slot_blocks] == [5, 0]
    assert not be._tables[1].any() and eng._slots[1] is None
    assert all(r == 0 for r in be._block_ref.values())  # detached again
    return (eng, seen,
            {"a": (first, 3), "b": (second, 9), "c": (other, 9)}, out)


def _event_cancel_in_flight(cfg, params):
    eng, seen, want = _event_admission(cfg, params)
    eng.submit("b", _prompt(cfg, 9, 7), 12)
    eng.step()
    eng.step()
    assert eng._windows, "no window in flight to cancel under"
    slot = next(i for i, r in enumerate(eng._slots) if r.rid == "a")
    row = np.asarray(eng._cache.tables)[slot].copy()
    assert eng.cancel("a")
    assert not eng.cache_backend._tables[slot].any()
    # no program since: the device still holds the row, which the window
    # in flight may write through; the next program zeroes it
    np.testing.assert_array_equal(np.asarray(eng._cache.tables)[slot], row)
    eng.step()
    assert not np.asarray(eng._cache.tables)[slot].any()
    return eng, seen, {"b": (_prompt(cfg, 9, 7), 12)}


def _event_preempt(cfg, params):
    eng, seen, want = _event_admission(cfg, params)
    eng.step()
    eng.step()
    finished = dict(eng.preempt("a"))
    assert not finished and eng._slots[0].frozen
    bit = eng._PATCH_FIELDS.index("done")  # armed for the next window
    assert eng._patch[0, 0] >> bit & 1 and eng._patch[0, 1 + bit] == 1
    held = eng.cache_backend._tables[0].copy()
    assert held.any()  # frozen in place: the row stays until the release
    eng.submit("b", _prompt(cfg, 10, 9), 6)
    eng.step()
    np.testing.assert_array_equal(np.asarray(eng._cache.tables)[0], held)
    assert eng.release_frozen("a") is not None
    assert not eng.cache_backend._tables[0].any()
    eng.step()
    assert not np.asarray(eng._cache.tables)[0].any()
    return eng, seen, {"b": (_prompt(cfg, 10, 9), 6)}


def _event_abort_all(cfg, params):
    eng, seen, _ = _event_admission(cfg, params)
    eng.submit("b", _prompt(cfg, 11, 30), 6)
    eng.step()
    assert sorted(eng.abort_all()) == ["a", "b"]
    assert not eng.cache_backend._tables.any()
    assert np.asarray(eng._cache.tables).any()  # until the next program
    eng.submit("c", _prompt(cfg, 12, 13), 7)
    eng.step()
    assert not np.asarray(eng._cache.tables)[1].any()
    return eng, seen, {"c": (_prompt(cfg, 12, 13), 7)}


def _event_disagg_import(cfg, params):
    from shellac_tpu.inference import disagg

    src = _paged(cfg, params)
    src.submit("m", _prompt(cfg, 13, 19), 8, prefill_only=True)
    while not src.frozen_prefills:
        src.step()
    slot = src.frozen_prefills["m"]
    blob = disagg.export_slot(src, slot, src._slots[slot])
    assert src.release_frozen("m") is not None
    eng = _paged(cfg, params)
    seen = _watch_tables(eng)
    eng.submit("other", _prompt(cfg, 14, 5), 5)
    eng.step()
    got = disagg.import_blob(eng, blob, rid="m")
    assert eng.cache_backend._tables[got].any()
    assert not np.asarray(eng._cache.tables)[got].any()
    eng.step()  # the window: the imported row and its armed vectors
    assert np.asarray(eng._cache.tables)[got].any()
    return eng, seen, {"m": (_prompt(cfg, 13, 19), 8),
                       "other": (_prompt(cfg, 14, 5), 5)}


@pytest.mark.parametrize("event", [
    "admission", "prefix_attach", "pool_exhausted_rollback", "release",
    "cancel_in_flight", "preempt", "abort_all", "disagg_import",
])
def test_the_device_table_follows_the_host_table(setup, event):
    """The block table is a host array with one writer of a row; the
    device copy is written only by engine programs, from the argument
    they are handed. After each kind of change the next program, be it
    a prefill, a continuation or the window, leaves the device table
    equal to the host's (checked at every program by _watch_tables), a
    released slot's row is zero there before its pages are written for
    another, and every request still reads as the one-request engine's."""
    cfg, params = setup
    eng, seen, want, *rest = globals()[f"_event_{event}"](cfg, params)
    out = dict(rest[0]) if rest else {}
    out.update(_drain(eng))
    for rid, (toks, max_new) in want.items():
        assert out[rid] == _ref(cfg, params, toks, max_new), rid
    assert "_decode_impl" in seen and len(seen) > 2
    # drained: every row is released on the host (the device's last rows
    # wait for a next program that an idle engine never dispatches)
    assert not eng.cache_backend._tables.any()
