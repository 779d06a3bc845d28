"""Beam search on the single-request Engine.

The correctness bar is an exact reference: a host-side beam loop over
the full (uncached) forward must produce the same sequences and scores
as the device implementation (cached forward + flat top-k + cache-row
reordering inside a lax.scan).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu import get_model_config
from shellac_tpu.inference.engine import Engine
from shellac_tpu.models import transformer


def _cfg(**kw):
    return get_model_config("tiny").replace(dtype="float32", **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


def _ref_beam(cfg, params, prompt, k, steps, eos_id=None,
              length_penalty=1.0):
    """Host beam search over the full forward (no cache): the oracle."""
    beams = [(list(map(int, prompt)), 0.0, False)]  # (tokens, score, done)
    neg = -1e30
    for _ in range(steps):
        cand = []
        for toks, score, done in beams:
            if done:
                cand.append((toks, score, True, None))
                continue
            logits = transformer.forward(
                cfg, params, jnp.asarray([toks], jnp.int32)
            )[0, -1]
            lp = np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32)))
            for t in np.argsort(-lp)[: 2 * k]:
                cand.append((toks, score + float(lp[t]), False, int(t)))
        cand.sort(key=lambda c: c[1], reverse=True)
        new = []
        for toks, score, done, t in cand[:k] if len(beams) > 1 else cand:
            if len(new) == k:
                break
            if done:
                new.append((toks, score, True))
            else:
                nt = toks + [t]
                new.append((nt, score,
                            eos_id is not None and t == eos_id))
        beams = new
        if all(d for _, _, d in beams):
            break
    out = []
    plen = len(prompt)
    for toks, score, _ in beams:
        gen = toks[plen:]
        out.append((gen, score / (len(gen) ** length_penalty)))
    out.sort(key=lambda c: c[1], reverse=True)
    return out


class TestBeamSearch:
    def test_matches_reference(self, model):
        cfg, params = model
        eng = Engine(cfg, params, temperature=0.0, max_len=64)
        prompt = [7, 23, 5]
        k, steps = 3, 5
        got_seqs, got_scores = eng.beam_search(
            prompt, num_beams=k, max_new_tokens=steps, length_penalty=1.0
        )
        ref = _ref_beam(cfg, params, prompt, k, steps)
        # The TOP beam must match exactly (lower beams can differ by
        # tie-breaks between equal-score candidates).
        assert got_seqs[0] == ref[0][0], (got_seqs[0], ref[0][0])
        np.testing.assert_allclose(got_scores[0], ref[0][1], rtol=1e-4)
        # Scores must be sorted best-first.
        assert got_scores == sorted(got_scores, reverse=True)

    def test_beam1_equals_greedy(self, model):
        cfg, params = model
        eng = Engine(cfg, params, temperature=0.0, max_len=64)
        prompt = jnp.asarray([[3, 9, 17]], jnp.int32)
        greedy = np.asarray(
            eng.generate(prompt, max_new_tokens=6).tokens
        )[0].tolist()
        seqs, _ = eng.beam_search([3, 9, 17], num_beams=1,
                                  max_new_tokens=6)
        assert seqs[0] == greedy

    def test_eos_finishes_and_freezes(self, model):
        """Declare the model's own top first token to be EOS: that beam
        finishes at length 1, and with raw-sum scoring
        (length_penalty=0) no longer sequence can beat it — every
        continuation only ADDS negative log-probs to a start that was
        already <= the best single step."""
        cfg, params = model
        eng = Engine(cfg, params, temperature=0.0, max_len=64)
        prompt = [1, 2]
        greedy = np.asarray(
            eng.generate(jnp.asarray([prompt], jnp.int32),
                         max_new_tokens=1).tokens
        )[0, 0]
        eos = int(greedy)
        seqs, scores = eng.beam_search(
            prompt, num_beams=3, max_new_tokens=8, eos_id=eos,
            length_penalty=0.0,
        )
        assert seqs[0] == [eos]
        # The frozen beam's score is exactly the single-step logprob —
        # it must not have accumulated anything while frozen.
        logits = transformer.forward(
            cfg, params, jnp.asarray([prompt], jnp.int32)
        )[0, -1]
        lp0 = float(jax.nn.log_softmax(logits.astype(jnp.float32))[eos])
        np.testing.assert_allclose(scores[0], lp0, rtol=1e-4)

    def test_length_penalty_changes_ranking(self, model):
        cfg, params = model
        eng = Engine(cfg, params, temperature=0.0, max_len=64)
        raw_seqs, raw = eng.beam_search([4, 8], num_beams=4,
                                        max_new_tokens=6,
                                        length_penalty=0.0)
        mean_seqs, mean = eng.beam_search([4, 8], num_beams=4,
                                          max_new_tokens=6,
                                          length_penalty=1.0)
        # Same candidate set; alpha=1 divides by length (all beams run
        # the full budget without EOS, so scores scale by 1/6).
        np.testing.assert_allclose(
            sorted(np.asarray(raw) / 6.0), sorted(mean), rtol=1e-5
        )

    def test_paged_matches_dense_bit_exact(self, model):
        """CoW paged beams vs the dense-cache beam: identical
        sequences AND scores — the block-table gather + partial-tail
        copy is invisible to the math."""
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg, params = model
        dense = Engine(cfg, params, temperature=0.0, max_len=64)
        paged = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                    block_size=4, temperature=0.0)
        for prompt, k, steps, eos, alpha in (
            ([7, 23, 5], 3, 5, None, 1.0),        # partial prompt tail
            ([7, 23, 5, 9], 4, 9, None, 0.0),     # block-aligned prompt
            ([1, 2], 3, 12, None, 1.0),           # multi-crossing run
            ([4, 8, 15, 16, 23], 2, 1, None, 1.0),  # no decode writes
        ):
            want = dense.beam_search(prompt, num_beams=k,
                                     max_new_tokens=steps, eos_id=eos,
                                     length_penalty=alpha)
            got = paged.beam_search(prompt, num_beams=k,
                                    max_new_tokens=steps, eos_id=eos,
                                    length_penalty=alpha)
            assert got[0] == want[0], (prompt, k, steps)
            np.testing.assert_allclose(got[1], want[1], rtol=1e-5)

    def test_paged_eos_freeze_matches_dense(self, model):
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg, params = model
        dense = Engine(cfg, params, temperature=0.0, max_len=64)
        paged = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                    block_size=4, temperature=0.0)
        prompt = [1, 2]
        greedy = np.asarray(
            dense.generate(jnp.asarray([prompt], jnp.int32),
                           max_new_tokens=1).tokens
        )[0, 0]
        eos = int(greedy)
        want = dense.beam_search(prompt, num_beams=3, max_new_tokens=8,
                                 eos_id=eos, length_penalty=0.0)
        got = paged.beam_search(prompt, num_beams=3, max_new_tokens=8,
                                eos_id=eos, length_penalty=0.0)
        assert got[0] == want[0]
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5)

    def test_paged_beam_churn_through_allocator(self, model):
        """Beam searches interleaved with live paged requests: the
        borrowed blocks come from (and return to) the same pool the
        slots use, block accounting balances, and neither the beams
        nor the requests' greedy outputs move."""
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg, params = model
        dense = Engine(cfg, params, temperature=0.0, max_len=64)
        eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  block_size=4, temperature=0.0,
                                  prefix_cache=True)
        rng = np.random.default_rng(11)
        reqs = [(i, rng.integers(1, cfg.vocab_size, size=5 + i).tolist(), 6)
                for i in range(4)]
        ref_engine = PagedBatchingEngine(cfg, params, n_slots=2,
                                         max_len=64, block_size=4,
                                         temperature=0.0)
        want_reqs = ref_engine.run(reqs)
        want_beam = dense.beam_search([7, 23, 5], num_beams=3,
                                      max_new_tokens=5)

        for rid, toks, n in reqs:
            eng.submit(rid, toks, n)
        got_reqs = {}
        beams = []
        free_before = len(eng._free) + eng._evictable()
        while eng.pending:
            for rid, out in eng.step():
                got_reqs[rid] = out
            # A beam search between engine steps — mid-churn.
            beams.append(eng.beam_search([7, 23, 5], num_beams=3,
                                         max_new_tokens=5))
        assert got_reqs == want_reqs
        for got_beam in beams:
            assert got_beam[0] == want_beam[0]
            np.testing.assert_allclose(got_beam[1], want_beam[1],
                                       rtol=1e-5)
        # Everything borrowed came back (slots freed theirs on finish).
        assert len(eng._free) + eng._evictable() == free_before

    def test_paged_beam_reuses_prefix_cache(self, model):
        """A beam prompt sharing a cached prefix attaches the cached
        blocks read-only and computes only the suffix — beams stay
        bit-identical to the dense beam, prefix_hit_tokens counts the
        reuse, and the cached blocks' refcounts are restored."""
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg, params = model
        dense = Engine(cfg, params, temperature=0.0, max_len=64)
        eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  block_size=4, pool_tokens=1024,
                                  temperature=0.0, prefix_cache=True)
        rng = np.random.default_rng(21)
        prefix = rng.integers(1, cfg.vocab_size, size=12).tolist()
        # Seed the cache: one request whose prompt IS the prefix
        # (plus a tail so full blocks register).
        eng.run([("seed", prefix + [5, 7], 4)])
        assert eng._hash_to_block, "prefix blocks should be registered"
        refs_before = dict(eng._block_ref)

        prompt = prefix + [9, 11, 13]
        hits0 = eng.stats["prefix_hit_tokens"]
        want = dense.beam_search(prompt, num_beams=3, max_new_tokens=6)
        got = eng.beam_search(prompt, num_beams=3, max_new_tokens=6)
        assert got[0] == want[0]
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
        assert eng.stats["prefix_hit_tokens"] - hits0 >= 12 // 4 * 4
        assert eng._block_ref == refs_before  # attach fully released

    def test_paged_beam_prompt_fills_whole_table(self, model):
        """Prompt long enough that its pad bucket exceeds max_len AND
        its blocks fill the whole table row: unclamped pad writes
        would gather-clamp onto the last real block and corrupt
        just-written prompt KV (the pad cap guards this)."""
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg, params = model
        dense = Engine(cfg, params, temperature=0.0, max_len=96)
        paged = PagedBatchingEngine(cfg, params, n_slots=2, max_len=96,
                                    block_size=4, pool_tokens=2048,
                                    temperature=0.0)
        rng = np.random.default_rng(31)
        prompt = rng.integers(1, cfg.vocab_size, size=93).tolist()
        want = dense.beam_search(prompt, num_beams=2, max_new_tokens=2)
        got = paged.beam_search(prompt, num_beams=2, max_new_tokens=2)
        assert got[0] == want[0]
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5)

    def test_paged_beam_pool_exhaustion_is_loud(self, model):
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg, params = model
        eng = PagedBatchingEngine(cfg, params, n_slots=2, max_len=64,
                                  block_size=4, pool_tokens=32,
                                  temperature=0.0)
        with pytest.raises(RuntimeError, match="pool exhausted"):
            eng.beam_search(list(range(1, 9)), num_beams=8,
                            max_new_tokens=32)

    def test_paged_mla_matches_dense_mla_beam(self):
        """MLA latent-row pools compose: the CoW copy moves latent
        blocks like any value block (v pool is zero-width), so paged
        MLA beams equal the dense MLA beam exactly."""
        from shellac_tpu.inference.batching import PagedBatchingEngine

        mcfg = get_model_config("tiny-mla").replace(dtype="float32")
        params = transformer.init_params(mcfg, jax.random.PRNGKey(0))
        dense = Engine(mcfg, params, temperature=0.0, max_len=64)
        paged = PagedBatchingEngine(mcfg, params, n_slots=2, max_len=64,
                                    block_size=4, pool_tokens=1024,
                                    temperature=0.0)
        for prompt, k, steps in (([3, 5, 7], 3, 9), ([1, 2], 2, 12)):
            want = dense.beam_search(prompt, num_beams=k,
                                     max_new_tokens=steps)
            got = paged.beam_search(prompt, num_beams=k,
                                    max_new_tokens=steps)
            assert got[0] == want[0], (prompt, k, steps)
            np.testing.assert_allclose(got[1], want[1], rtol=1e-5)

    def test_paged_int8_matches_dense_int8_beam(self, model):
        """int8 pools compose: the CoW copy moves the scale pools in
        lockstep with the value pools, so paged int8 beams equal the
        dense int8-cache beam exactly."""
        from shellac_tpu.inference.batching import PagedBatchingEngine

        cfg, params = model
        dense = Engine(cfg, params, temperature=0.0, max_len=192,
                       kv_quant="int8")
        paged = PagedBatchingEngine(cfg, params, n_slots=2, max_len=192,
                                    block_size=128, kv_quant="int8",
                                    pool_tokens=2048, temperature=0.0)
        for prompt, k, steps in (
            ([7, 23, 5], 3, 6),        # within one block
            (list(range(1, 121)), 2, 14),  # crosses a block boundary
        ):
            want = dense.beam_search(prompt, num_beams=k,
                                     max_new_tokens=steps)
            got = paged.beam_search(prompt, num_beams=k,
                                    max_new_tokens=steps)
            assert got[0] == want[0], (prompt, k, steps)
            np.testing.assert_allclose(got[1], want[1], rtol=1e-5)

    def test_int8_cache_composes(self, model):
        """Beam search over the int8 cache: correct shape/ordering and
        a top score within the int8 rounding envelope of bf16 (near-tie
        beams may legitimately swap — cache rounding shifts scores by
        ~1e-2 on this model, so sequence equality is NOT the contract)."""
        cfg, params = model
        a, sa = Engine(cfg, params, temperature=0.0,
                       max_len=64).beam_search([6, 6, 2], num_beams=3,
                                               max_new_tokens=5)
        b, sb = Engine(cfg, params, temperature=0.0, max_len=64,
                       kv_quant="int8").beam_search([6, 6, 2],
                                                    num_beams=3,
                                                    max_new_tokens=5)
        assert len(b) == 3 and sb == sorted(sb, reverse=True)
        np.testing.assert_allclose(sa[0], sb[0], atol=0.05)

    def test_guards(self, model):
        cfg, params = model
        eng = Engine(cfg, params, max_len=32)
        with pytest.raises(ValueError, match="num_beams"):
            eng.beam_search([1], num_beams=0, max_new_tokens=4)
        with pytest.raises(ValueError, match="max_len"):
            eng.beam_search(list(range(30)), num_beams=2,
                            max_new_tokens=8)
