"""Speculative decoding inside the continuous-batching engine.

The single-request SpeculativeEngine (speculative.py) amortizes the
target model's HBM read over gamma draft proposals; this module brings
the same trick to the serving engines: every engine step runs ONE
verification round over all slots — the draft proposes gamma tokens per
slot, the target scores the gamma+1 window in one forward, and each
slot independently accepts a prefix by rejection sampling (exact-match
accept for greedy slots). A round emits 1..gamma+1 tokens per slot per
host sync, against the base engine's decode_ticks=1 emitting exactly 1.

The speculative behavior is a MIXIN written against the cache-backend
interface (inference/cache), so it composes with storage policies
instead of being welded to the dense engine:

  - `SpeculativeBatchingEngine` — dense/int8 slot caches;
  - `PagedSpeculativeBatchingEngine` — the paged block pool (bf16 or
    int8), including prefix caching and pool admission control.

The TARGET cache is whatever the host engine's backend built; the
verify round's writes and in-window attention reads go through the
same `forward_with_cache` storage dispatch as sequential decode. The
DRAFT always keeps a dense per-slot cache (its own DenseBackend): the
draft model is small, so its cache is not worth paging, and a dense
row rolls back by clamping `lengths` exactly like the single-request
engine.

Sampling composition: per-request temperature, top-k/top-p/min-p,
min_tokens, logit_bias, and per-request seeds all compose. The rule
for every distribution-shaping knob is the same — apply the IDENTICAL
adjustment/truncation to the draft and target distributions before
the acceptance test (ops/sampling.filter_logits_batched is the single
truncation definition, shared with the sequential sampler), and
rejection sampling then reproduces the ADJUSTED target distribution,
which is exactly what sequential decoding samples from.

int8 KV composes too, on both dense and paged pools: the verify
forward WRITES each position's K/V (quantizing at write) before its
in-window attention READS them back through the cache, so the verify
round scores every draft against the same int8-rounded bits
sequential decode re-reads — the acceptance identity holds bit-for-bit
on the shared reference path (greedy parity is pinned by tests).

Remaining exclusions live in EXCLUSIONS below — every raise carries an
`[excluded: <key>]` (or `[pinned: <key>]`) tag that the exclusion-
matrix meta-test (tests/test_cache_backends.py) cross-checks against
this manifest AND against a dedicated test per entry, so an exclusion
can neither rot silently nor be removed without its test noticing.

The reference repo for this project is empty (SURVEY.md §0); there is
no upstream speculative serving engine to cite.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference.batching import (
    BatchingEngine,
    PagedBatchingEngine,
    _bucket,
    named_program,
    program_name,
)
from shellac_tpu.inference.cache import CacheBackend, DenseBackend
from shellac_tpu.models import transformer
from shellac_tpu.ops.sampling import NEG_INF, filter_logits_batched
from shellac_tpu.parallel.sharding import make_shardings

# The spec-engine exclusion matrix: feature -> why it stays excluded.
# Every entry has (a) a tagged raise in this module and (b) a test in
# tests/test_cache_backends.py::TestExclusionMatrix — the meta-test
# asserts the three stay in lockstep. Burned down in PR 9 from nine
# (rolling, decode_ticks, overlap, int8, pp, constraint, seed,
# prompt_logprobs, all sampling extras) to five; overlap_prefill
# joined when the admission pipeline shipped (same class of survivor
# as overlap_decode — the round accounting leaves no sync to defer).
EXCLUSIONS: Dict[str, str] = {
    "rolling_window": (
        "the verify round re-reads positions a ring may have already "
        "evicted mid-round (a rejected draft's rollback needs the "
        "overwritten rows back)"
    ),
    "overlap_decode": (
        "the host must see each round's per-slot acceptance counts "
        "before it can account the next round, so there is no sync to "
        "defer behind a second in-flight window"
    ),
    "overlap_prefill": (
        "admission fills the draft AND target caches in lockstep "
        "(the draft prefill dispatches from inside _run_prefill), and "
        "the next verify round is accounted against both — there is "
        "no settle to defer without staging the draft cursor through "
        "the flight too"
    ),
    "pp_pipeline": (
        "the verify round replaces the decode scan the pp stage "
        "register pipelines; staging a gamma+1 window through the "
        "register would serialize the stages it exists to overlap"
    ),
    "constraint": (
        "the draft proposes unconstrained tokens, so the verify round "
        "would reject almost everything — a constrained request on a "
        "draft server is a config error, not a slow path; constraining "
        "the draft's proposals through the DFA is the lift that would "
        "remove this"
    ),
    "penalties": (
        "presence/frequency penalties depend on running per-token "
        "counts that change WITH each accepted token inside the round; "
        "supporting them needs per-position count snapshots threaded "
        "through the draft scan and target scoring (deferred — the "
        "identity itself permits it)"
    ),
}

# Knobs pinned by construction rather than excluded compositions.
PINNED: Dict[str, str] = {
    "decode_ticks": (
        "a verify round already emits up to gamma+1 tokens per host "
        "sync; multi-tick windows are the dense engine's answer to the "
        "same problem, so the knob stays 1 ('auto' resolves to 1 and "
        "the startup auto-tuner skips spec engines)"
    ),
}


class _SpecDecodeMixin:
    """Draft-propose / target-verify decode over any cache backend.

    Mixed in FRONT of a BatchingEngine subclass: slot mechanics
    (admission, stop sequences, streaming, per-request sampling
    state) come from the host engine; this mixin replaces prefill
    (adds the draft cache alongside) and `_decode_tokens` (the verify
    round), and widens the admission footprint by gamma+1 (a round
    writes cur + gamma positions before rolling back)."""

    _decode_ticks_tunable = False  # rounds, not tick windows

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        draft_cfg: ModelConfig,
        draft_params: Any,
        *,
        gamma: int = 4,
        **kw,
    ):
        cb = kw.get("cache_backend")
        rolling = bool(kw.get("rolling_window")) or (
            isinstance(cb, str) and cb.startswith("rolling")
        ) or (isinstance(cb, CacheBackend) and cb.is_rolling)
        if rolling:
            raise ValueError(
                "speculative batching does not support rolling caches "
                "[excluded: rolling_window]: the verify round re-reads "
                "positions a ring may have already evicted mid-round"
            )
        if cfg.vocab_size != draft_cfg.vocab_size:
            raise ValueError(
                f"target/draft vocab mismatch: {cfg.vocab_size} vs "
                f"{draft_cfg.vocab_size}"
            )
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        # decode_ticks is pinned: a verify round already emits up to
        # gamma+1 tokens per sync. "auto" (the serving default) is
        # accepted and resolves to 1 — _decode_ticks_tunable=False
        # makes the startup auto-tuner skip this engine.
        if kw.get("decode_ticks", 1) not in (1, "auto"):
            raise ValueError(
                "speculative batching emits up to gamma+1 tokens per "
                "step already; decode_ticks must stay 1 "
                "[pinned: decode_ticks]"
            )
        kw["decode_ticks"] = 1
        if kw.get("overlap_decode"):
            raise ValueError(
                "overlap_decode is not wired for the speculative engine "
                "[excluded: overlap_decode]: the host must see each "
                "round's per-slot acceptance counts before it can "
                "account the next round, so there is no sync to defer; "
                "use a non-draft engine for overlapped decode"
            )
        if kw.get("overlap_prefill"):
            raise ValueError(
                "overlap_prefill is not wired for the speculative "
                "engine [excluded: overlap_prefill]: admission fills "
                "the draft and target caches in lockstep and the next "
                "verify round is accounted against both, so there is "
                "no settle to defer; use a non-draft engine for "
                "overlapped prefill"
            )
        if kw.get("pp_pipeline"):
            raise ValueError(
                "pp_pipeline is not wired for the speculative engine "
                "[excluded: pp_pipeline] (its verify round replaces "
                "the decode scan the stage register pipelines; use a "
                "non-draft engine on pp meshes)"
            )
        if kw.get("mesh") is not None:
            tp = kw["mesh"].shape.get("tp", 1)
            if draft_cfg.kv_heads % tp or draft_cfg.n_heads % tp:
                # Fails later anyway, but deep inside device_put with a
                # PartitionSpec message that never names the draft; the
                # draft being smaller than the target makes this the
                # common misconfiguration.
                raise ValueError(
                    f"draft model heads (n_heads={draft_cfg.n_heads}, "
                    f"kv_heads={draft_cfg.kv_heads}) must divide tp={tp} "
                    "— pick a draft with more heads or a smaller tp"
                )
        # The verify round writes cur + gamma positions past the live
        # length before rolling back; admission must keep that span
        # resident (paged: reserved blocks) for every request.
        self.gamma = gamma
        self._footprint_slack = gamma + 1
        super().__init__(cfg, params, **kw)
        self.draft_cfg = draft_cfg
        self.draft_params = draft_params
        # The draft cache is ALWAYS dense, whatever the target backend:
        # the draft model is small (its cache is not worth paging) and
        # a dense row rolls back by clamping lengths. Built through a
        # backend so its construction/sharding contract matches the
        # target's.
        self._draft_backend = DenseBackend(draft_cfg, self.n_slots,
                                           self.max_len)
        self._dcache = self._draft_backend.init_cache()
        self._dcache_sh = None
        if self.mesh is not None:
            # The draft pins its OWN sharding tree (the target's may be
            # a paged pool with a different pytree); draft params must
            # arrive pre-sharded, same contract as the target's.
            self._dcache_sh = make_shardings(
                self.mesh, self._draft_backend.logical_axes()
            )
            self._dcache = jax.device_put(self._dcache, self._dcache_sh)
        self._draft_prefill_jit = {}
        self._draft_chunk_jit = {}
        # Draft-side chunked-prefill cursor: slot -> tokens of the
        # prompt already in the draft cache. Tracked separately from
        # the target's because a prefix-cache hit starts the TARGET at
        # the matched offset while the draft owns no prefix blocks and
        # must cover the prompt from 0.
        self._draft_chunk_off: Dict[int, int] = {}
        # Reentrancy flag: the paged prefix path runs the target's
        # suffix through _chunk_prefill from inside _run_prefill, which
        # then draft-prefills the WHOLE prompt itself — the wrapper
        # must not also append a bogus draft chunk at the suffix
        # offset.
        self._spec_skip_draft = False
        self._spec_round = None  # built lazily (static sampling flags)
        self.stats.update({
            "spec_rounds": 0,
            "spec_proposed": 0,
            "spec_accepted": 0,
        })

    def _window_write_span(self) -> int:
        # One verify round writes cur + gamma positions per slot.
        return self.gamma + 1

    # ---- admission ---------------------------------------------------

    def submit(self, rid, tokens, max_new: int, stop=None, *,
               temperature=None, top_k=None, top_p=None, min_p=None,
               min_tokens=None, logit_bias=None,
               presence_penalty=None, frequency_penalty=None,
               prompt_logprobs=False, seed=None, constraint=None,
               trace=None) -> None:
        if constraint is not None:
            raise ValueError(
                f"request {rid!r}: structured decoding is not wired "
                "for the speculative engine [excluded: constraint] "
                "(the draft proposes unconstrained tokens, so the "
                "verify round would reject almost everything); use a "
                "non-draft engine"
            )
        if (presence_penalty is not None and presence_penalty != 0.0) or \
                (frequency_penalty is not None and frequency_penalty != 0.0):
            raise ValueError(
                f"request {rid!r}: presence/frequency penalties are "
                "not wired for the speculative engine "
                "[excluded: penalties] (the per-token counts change "
                "with each accepted token inside the round); use a "
                "non-draft engine"
            )
        size = np.asarray(tokens, np.int32).reshape(-1).size
        # A slot finishing mid-round keeps writing the round's window at
        # its frozen tail; reserve gamma+1 slack past the usual budget
        # so those writes stay off other valid positions.
        need = size + max_new + self.gamma + 2
        if need > self.max_len:
            raise ValueError(
                f"request {rid!r}: prompt {size} + max_new {max_new} + "
                f"speculative slack (gamma+2) exceeds max_len {self.max_len}"
            )
        super().submit(rid, tokens, max_new, stop=stop,
                       temperature=temperature, top_k=top_k,
                       top_p=top_p, min_p=min_p, min_tokens=min_tokens,
                       logit_bias=logit_bias,
                       prompt_logprobs=prompt_logprobs, seed=seed,
                       trace=trace)

    # ---- prefill (target via the host engine, plus the draft cache) --

    def _run_prefill(self, slot: int, req):
        # The paged prefix path prefills the target's unmatched SUFFIX
        # via _chunk_prefill; the flag stops the wrapper below from
        # appending a draft chunk at the suffix offset — the draft owns
        # no prefix and prefills the whole prompt right after.
        self._spec_skip_draft = True
        try:
            first_and_lp = super()._run_prefill(slot, req)
        finally:
            self._spec_skip_draft = False
        s = req.tokens.size
        pad = min(_bucket(s), self.max_len)
        if pad not in self._draft_prefill_jit:
            kw = ({"out_shardings": self._dcache_sh}
                  if self._dcache_sh is not None else {})
            # Donate the draft cache (arg 1): the call below rebinds
            # self._dcache from the result, so the slot scatter may
            # write in place instead of copying the whole draft cache.
            self._draft_prefill_jit[pad] = jax.jit(
                self._draft_prefill_impl, donate_argnums=(1,), **kw
            )
        padded = np.zeros((1, pad), np.int32)
        padded[0, :s] = req.tokens
        self._dcache = self._draft_prefill_jit[pad](
            self.draft_params, self._dcache, jnp.asarray(padded),
            np.array([s], np.int32), slot,
        )
        return first_and_lp

    def _draft_prefill_impl(self, dparams, dcache, tokens, prompt_len, slot):
        from shellac_tpu.inference.kvcache import scatter_slot

        mini = self._draft_backend.init_mini(self.max_len)
        _, mini = transformer.forward_with_cache(
            self.draft_cfg, dparams, tokens, mini, new_tokens_len=prompt_len,
            fresh_cache=True, attn_impl=self.attn_impl, mesh=self.mesh,
        )
        return scatter_slot(dcache, mini, slot)

    # ---- chunked prefill (draft cache chunks alongside the target) ---

    def _chunk_prefill(self, pad, fresh, tokens, chunk_len, offset, slot,
                       samp, boundary_next=None, want_plp=False):
        """The target chunk program runs via the host engine; the
        draft's cache row is then brought to the SAME coverage, so by
        the final chunk both caches hold the full prompt — identical
        state to the whole-prompt path, which is why chunked spec
        serving stays bit-exact (tests/test_spec_batching.py chunked
        cases). The draft covers the prompt from ITS OWN cursor
        (always 0-origin): a prefix-cache hit starts the target at the
        matched offset, but the draft owns no prefix blocks."""
        out = super()._chunk_prefill(
            pad, fresh, tokens, chunk_len, offset, slot, samp,
            boundary_next=boundary_next, want_plp=want_plp,
        )
        if self._spec_skip_draft:
            return out
        req = self._slots[slot]
        # Host ints: these arrays were built from host values on the
        # admission path (no device compute pending behind them).
        t_end = int(np.asarray(offset)[0]) + int(np.asarray(chunk_len)[0])
        dstart = self._draft_chunk_off.get(slot, 0)
        dchunk = req.tokens[dstart:t_end]
        ds = dchunk.size
        if ds > 0:
            dpad = min(_bucket(ds), self.max_len - dstart)
            dfresh = dstart == 0
            jkey = (dpad, dfresh)
            if jkey not in self._draft_chunk_jit:
                jit_kw = ({"out_shardings": self._dcache_sh}
                          if self._dcache_sh is not None else {})
                import functools

                # Same donation contract as the draft prefill:
                # self._dcache is rebound from the result right below.
                self._draft_chunk_jit[jkey] = jax.jit(
                    named_program(functools.partial(
                        self._draft_chunk_impl, fresh=dfresh)),
                    donate_argnums=(1,), **jit_kw,
                )
            self._dcache = self._draft_chunk_jit[jkey](
                self.draft_params, self._dcache,
                jnp.asarray(np.pad(dchunk, (0, dpad - ds))[None]),
                np.array([ds], np.int32),
                np.array([dstart], np.int32), slot,
            )
        if t_end >= req.tokens.size:
            self._draft_chunk_off.pop(slot, None)
        else:
            self._draft_chunk_off[slot] = t_end
        return out

    def _draft_chunk_impl(self, dparams, dcache, tokens, chunk_len,
                          offset, slot, *, fresh):
        from shellac_tpu.inference.kvcache import scatter_slot, slot_view

        view = slot_view(dcache, slot, offset)
        _, view = transformer.forward_with_cache(
            self.draft_cfg, dparams, tokens, view,
            new_tokens_len=chunk_len, fresh_cache=fresh,
            attn_impl=self.attn_impl if fresh else "ref", mesh=self.mesh,
        )
        return scatter_slot(dcache, view, slot)

    def _release_slot(self, slot: int) -> None:
        super()._release_slot(slot)
        self._draft_chunk_off.pop(slot, None)

    # ---- one verification round over all slots ----------------------

    def _spec_round_impl(self, params, dparams, tcache, dcache, key, carry,
                         win, samp, tables, bias, use_bias: bool = False,
                         use_seed: bool = False):
        """The verify round's program. Its slot-state arguments are the
        decode window's (BatchingEngine._decode_impl): the engine's key
        (split here), the carried vectors (`carry`: cur and min_rem are
        this round's; the budget, done flag and DFA state pass through
        with the patch applied), the call's host array `win` (active,
        gen0, pending patch), the host settings matrix `samp`, the
        target backend's slot tables, the bias matrix. Returns (tcache,
        dcache, key, carry, emitted (B, g+1), counts (B,), lps (B, g+1)
        — zeros unless self.logprobs, top-K value/id sidecars).

        counts[b] tokens of emitted[b] are real (0 for inactive rows).
        Per-row temperature: greedy rows use the exact-match degenerate
        form; sampled rows use standard rejection sampling over the
        ADJUSTED + FILTERED draft/target distributions — logit_bias and
        the min_tokens EOS ban adjust both sides identically, then
        filter_logits_batched truncates both sides identically (the
        same definition sample_batched uses), so the round reproduces
        exactly the distribution the sequential sampler draws from.
        Inactive rows compute garbage that is frozen (lengths, cur)
        and dropped (counts=0).

        use_seed: rows with seed >= 0 draw every round decision (draft
        proposals, acceptance uniforms, residual, bonus) from
        fold_in(PRNGKey(seed), tokens-generated-so-far) — deterministic
        per request and identical across cache backends, independent of
        co-tenants and the engine's shared stream. (It is NOT the
        sequential engine's seeded stream: a verify round draws a
        different number of variates than a token-by-token sampler.)
        """
        g = self.gamma
        tcache, key_out, key = self._enter(tcache, tables, key)
        cur, min_rem0, rem, done, cstate = self._apply_patch(carry, win)
        active, gen0 = win[:, 0] != 0, win[:, 1]
        b = cur.shape[0]
        st = self._unpack_slot_samp(samp)
        temp, topk, topp, minp = (st["temperature"], st["top_k"],
                                  st["top_p"], st["min_p"])
        seed_vec = st["seed"]
        key, kd, kacc, kres, kbonus = jax.random.split(key, 5)
        greedy = temp <= 0.0
        t = jnp.where(greedy, 1.0, temp)[:, None]
        lt0, ld0 = tcache.lengths, dcache.lengths

        def adjust(logits, pos):
            """logit_bias + the min_tokens EOS ban at round-emission
            position `pos` — the same pre-sampler adjustment the base
            engine's _row_decode_step applies, applied to BOTH sides
            so the acceptance identity targets the adjusted
            distribution."""
            x = logits.astype(jnp.float32)
            if use_bias:
                x = x + bias
            if self.eos_id is not None:
                ban = (min_rem0 - pos) > 0
                col = jnp.where(ban, NEG_INF, x[:, self.eos_id])
                x = x.at[:, self.eos_id].set(col)
            return x

        if use_seed:
            # Per-row deterministic key fan: g draft draws + acceptance
            # uniforms + residual + bonus, all derived from (seed,
            # tokens generated before this round).
            def row_keys(s, g0):
                base = jax.random.fold_in(
                    jax.random.PRNGKey(jnp.maximum(s, 0)), g0
                )
                return jax.random.split(base, g + 3)

            rkeys = jax.vmap(row_keys)(seed_vec, gen0)  # (B, g+3, 2)
            seeded = seed_vec >= 0

        def pick_cat(shared_key, per_key_idx, x):
            """Categorical draw: shared-stream rows from `shared_key`,
            seeded rows from their own per-row key."""
            drawn = jax.random.categorical(shared_key, x, axis=-1)
            if use_seed:
                per = jax.vmap(jax.random.categorical)(
                    rkeys[:, per_key_idx], x
                )
                drawn = jnp.where(seeded, per, drawn)
            return drawn

        def dstep(carry, inp):
            k_i, i = inp
            dc, tok = carry
            logits, dc = transformer.forward_with_cache(
                self.draft_cfg, dparams, tok[:, None], dc,
                attn_impl=self.attn_impl, mesh=self.mesh,
            )
            adj = adjust(logits[:, 0], i)
            xq = filter_logits_batched(adj, temp, topk, topp, minp)
            q = jax.nn.softmax(xq, axis=-1)
            nxt = jnp.where(
                greedy,
                jnp.argmax(adj, axis=-1),
                pick_cat(k_i, i, xq),
            ).astype(jnp.int32)
            return (dc, nxt), (nxt, q)

        (dcache, _), (drafts, qs) = jax.lax.scan(
            dstep, (dcache, cur),
            (jax.random.split(kd, g), jnp.arange(g, dtype=jnp.int32)),
        )
        # Backfill the last proposal's kv so the all-accepted case
        # leaves the draft cache complete for the next round.
        _, dcache = transformer.forward_with_cache(
            self.draft_cfg, dparams, drafts[-1][:, None], dcache,
            attn_impl=self.attn_impl, mesh=self.mesh,
        )
        drafts = drafts.T  # (B, g)
        qs = jnp.moveaxis(qs, 0, 1)  # (B, g, V)

        # Target scores [cur, d_0..d_{g-1}] in one forward.
        tin = jnp.concatenate([cur[:, None], drafts], axis=1)  # (B, g+1)
        tlogits, tcache = transformer.forward_with_cache(
            self.cfg, params, tin, tcache, attn_impl=self.attn_impl,
            mesh=self.mesh,
        )
        # Adjusted target logits per emission position, then the SAME
        # truncation as the draft side (rows repeat per position so
        # the per-row filter params line up after the flatten).
        pos = jnp.arange(g + 1, dtype=jnp.int32)
        adj_t = tlogits.astype(jnp.float32)
        if use_bias:
            adj_t = adj_t + bias[:, None, :]
        if self.eos_id is not None:
            ban = (min_rem0[:, None] - pos[None, :]) > 0  # (B, g+1)
            col = jnp.where(ban, NEG_INF, adj_t[:, :, self.eos_id])
            adj_t = adj_t.at[:, :, self.eos_id].set(col)
        rep = lambda v: jnp.repeat(v, g + 1, axis=0)  # noqa: E731
        xp = filter_logits_batched(
            adj_t.reshape(b * (g + 1), -1),
            rep(temp), rep(topk), rep(topp), rep(minp),
        ).reshape(b, g + 1, -1)
        ps = jax.nn.softmax(xp, axis=-1)  # (B, g+1, V) filtered target

        p_d = jnp.take_along_axis(
            ps[:, :g], drafts[..., None], axis=-1
        )[..., 0]
        q_d = jnp.take_along_axis(qs, drafts[..., None], axis=-1)[..., 0]
        u = jax.random.uniform(kacc, (b, g))
        if use_seed:
            u_per = jax.vmap(
                lambda rk: jax.random.uniform(rk, (g,))
            )(rkeys[:, g])
            u = jnp.where(seeded[:, None], u_per, u)
        accept = jnp.where(
            greedy[:, None],
            drafts == jnp.argmax(adj_t[:, :g], axis=-1),
            u * q_d < p_d,
        )
        n = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

        # Token after the accepted prefix: residual resample on
        # rejection, bonus sample from the g+1'th target dist otherwise
        # (argmax degenerate forms for greedy rows, on the ADJUSTED
        # unfiltered logits — matching the base engine's greedy path).
        idx = jnp.minimum(n, g - 1)
        p_n = jnp.take_along_axis(ps, idx[:, None, None], axis=1)[:, 0]
        q_n = jnp.take_along_axis(qs, idx[:, None, None], axis=1)[:, 0]
        adj_n = jnp.take_along_axis(
            adj_t, idx[:, None, None], axis=1
        )[:, 0]
        res = jnp.maximum(p_n - q_n, 0.0)
        res_mass = jnp.sum(res, axis=-1, keepdims=True)
        res = jnp.where(res_mass > 1e-9, res, p_n)
        r = jnp.where(
            greedy,
            jnp.argmax(adj_n, axis=-1),
            pick_cat(kres, g + 1, jnp.log(res + 1e-30)),
        ).astype(jnp.int32)
        bonus = jnp.where(
            greedy,
            jnp.argmax(adj_t[:, g], axis=-1),
            pick_cat(kbonus, g + 2, jnp.log(ps[:, g] + 1e-30)),
        ).astype(jnp.int32)
        extra = jnp.where(n < g, r, bonus)

        cols = jnp.arange(g + 1, dtype=jnp.int32)[None, :]
        padded = jnp.concatenate([drafts, extra[:, None]], axis=1)
        emitted = jnp.where(cols == n[:, None], extra[:, None], padded)

        # Roll back: valid history = old length + 1 (cur) + n accepted;
        # inactive rows freeze entirely.
        tcache = tcache.replace(
            lengths=jnp.where(active, lt0 + 1 + n, lt0)
        )
        dcache = dcache.replace(
            lengths=jnp.where(active, ld0 + 1 + n, ld0)
        )
        cur = jnp.where(active, extra, cur)
        counts = jnp.where(active, n + 1, 0)
        # The min_tokens countdown consumed one unit per emitted token.
        min_rem = jnp.where(
            active, jnp.maximum(min_rem0 - counts, 0), min_rem0
        )
        k_tl = self.top_logprobs
        if self.logprobs:
            # Raw-logit log_softmax of each emitted token (cols past
            # counts are garbage the host drops) — Engine convention.
            lsm = jax.nn.log_softmax(tlogits.astype(jnp.float32), axis=-1)
            lps = jnp.take_along_axis(
                lsm, emitted[..., None], axis=-1
            )[..., 0]
            if k_tl:
                # Alternatives per emitted position ride the same
                # verify scoring pass; the host slices by counts like
                # the tokens themselves.
                tlv, tli = jax.lax.top_k(lsm, k_tl)
                tli = tli.astype(jnp.int32)
            else:
                tlv = jnp.zeros((*emitted.shape, 0), jnp.float32)
                tli = jnp.zeros((*emitted.shape, 0), jnp.int32)
        else:
            lps = jnp.zeros(emitted.shape, jnp.float32)
            tlv = jnp.zeros((*emitted.shape, 0), jnp.float32)
            tli = jnp.zeros((*emitted.shape, 0), jnp.int32)
        return (tcache, dcache, key_out, (cur, min_rem, rem, done, cstate),
                emitted, counts, lps, tlv, tli)

    def _decode_tokens(self, active_rows):
        steps = self.obs.steps
        n_rows = sum(active_rows)
        with steps.span("engine.dispatch_window", ticks=self.gamma + 1,
                        rows=n_rows, launch=steps.next_launch):
            # Backend backstop for the round's write span (paged: grow
            # tables to cover cur + gamma positions; admission already
            # reserved the full slack footprint, so this is the same
            # no-op-in-steady-state check the dense window performs).
            self._pre_decode(active_rows)
            use_bias = self._sbias is not None and any(
                bb is not None for bb in self._slot_bias
            )
            use_seed = any(
                r is not None and r.seed is not None for r in self._slots
            )
            gen0 = [len(r.out) if r is not None else 0 for r in self._slots]
            if self._spec_round is None:
                round_kw = (
                    {"out_shardings": ((self._cache_sh, self._dcache_sh)
                                       + (None,) * 7)}
                    if self._cache_sh is not None else {}
                )
                self._spec_round = jax.jit(
                    self._spec_round_impl,
                    static_argnames=("use_bias", "use_seed"), **round_kw,
                )
            (self._cache, self._dcache, self._key, self._carry, emitted,
             counts, lps, tlv, tli) = self._spec_round(
                self.params, self.draft_params, self._cache, self._dcache,
                self._key, self._carry,
                self._window_arg(active_rows, gen0), self._samp_arg(),
                self.cache_backend.slot_tables(),
                self._sbias if self._sbias is not None
                else self._zero_bias_row,
                use_bias=use_bias, use_seed=use_seed,
            )
            # The whole round (draft steps and the verify pass) is one
            # program, so one launch.
            steps.launch("window", program_name(self._spec_round), counts,
                         ticks=self.gamma + 1, rows=n_rows)
        # The one host sync. The base engine's window instruments live
        # in _sync_window, which this override replaces: report the
        # verify round as the decode window it is.
        with steps.span("engine.wait_window"):
            arrays = (emitted, counts, lps, tlv, tli)
            steps.land(counts, arrays)
            em, cnt, host_lps, host_tlv, host_tli = jax.device_get(arrays)  # shellac: ignore[SH002] — the verify round's ONE packed sync (acceptance counts must reach the host before the next round)
        # A round computes gamma + 1 positions a slot and keeps
        # `counts` of them.
        steps.count(decode_slot_ticks=(self.gamma + 1) * self.n_slots,
                    decode_valid_ticks=int(cnt.sum()))
        self.stats["spec_rounds"] += 1
        self.stats["spec_proposed"] += int((cnt > 0).sum()) * self.gamma
        self.stats["spec_accepted"] += int(np.maximum(cnt - 1, 0).sum())
        per_slot = [em[i, :cnt[i]].tolist() for i in range(self.n_slots)]
        if not self.logprobs:
            return per_slot, None, None
        per_lps = [host_lps[i, :cnt[i]].tolist()
                   for i in range(self.n_slots)]
        if not self.top_logprobs:
            return per_slot, per_lps, None
        per_tl = [
            [(host_tli[i, j].tolist(), host_tlv[i, j].tolist())
             for j in range(cnt[i])]
            for i in range(self.n_slots)
        ]
        return per_slot, per_lps, per_tl


class SpeculativeBatchingEngine(_SpecDecodeMixin, BatchingEngine):
    """Speculative continuous batching on the dense-family backends
    ("dense", "dense-int8")."""


class PagedSpeculativeBatchingEngine(_SpecDecodeMixin, PagedBatchingEngine):
    """Speculative continuous batching over the paged block pool
    ("paged", "paged-int8"), prefix caching included: the verify
    round's writes and in-window reads go through the block tables via
    the same forward dispatch sequential paged decode uses, and
    rejected proposals roll back by clamping slot lengths (stale block
    tails self-heal exactly like dense rows). The draft keeps its own
    dense cache — see the module docstring."""
