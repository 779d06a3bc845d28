"""Autoregressive generation engine.

Prefill and decode are two jitted programs over the same cached forward:
prefill consumes the whole (padded) prompt in one MXU-friendly pass;
decode runs a `lax.scan` of single-token steps, keeping the loop on
device — no host round-trip per token.

With a `mesh`, the engine runs sharded (tensor-parallel weights, KV
cache sharded over kv_heads, batch over dp/fsdp): pass params already
placed with `shard_params`, and prefill pins the cache's shardings so
the decode scan stays partitioned instead of letting GSPMD re-derive a
layout per step.
"""

from __future__ import annotations

import functools
from typing import Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference.cache.base import refuse_loop
from shellac_tpu.inference.kvcache import init_cache_for
from shellac_tpu.models import transformer
from shellac_tpu.ops.sampling import sample
from shellac_tpu.parallel.sharding import make_shardings, shard_pytree


@flax.struct.dataclass
class GenerationResult:
    tokens: jax.Array  # (B, max_new_tokens) int32
    logprobs: jax.Array  # (B, max_new_tokens) fp32 — logprob of each sampled token


def shard_params(cfg: ModelConfig, params, mesh):
    """Place inference params onto a mesh by their logical axes.

    Handles both plain and int8-quantized (QTensor) parameter trees.
    """
    from shellac_tpu.ops.quant import QTensor, quantize_logical_axes

    axes = transformer.logical_axes(cfg)
    layers = params["layers"]
    stacks = (list(layers.values())
              if transformer.is_grouped_layers(layers) else [layers])
    q_targets = tuple(sorted({
        k for st in stacks for k, v in st.items() if isinstance(v, QTensor)
    }))
    if q_targets:
        axes = quantize_logical_axes(axes, q_targets)
    return shard_pytree(params, mesh, axes)


class Engine:
    """Holds jitted prefill/decode for one (config, shapes) pair."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_len: Optional[int] = None,
        temperature: float = 1.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        repetition_penalty: float = 1.0,
        mesh=None,
        kv_quant: Optional[str] = None,
        rolling_window: bool = False,
    ):
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant={kv_quant!r}; have None, 'int8'")
        if cfg.loop is not None:
            if mesh is not None:
                refuse_loop("mesh")
            if rolling_window:
                refuse_loop("rolling")
        if rolling_window and cfg.attn_window is None:
            raise ValueError(
                "rolling_window needs a sliding-window model (attn_window)"
            )
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.kv_quant = kv_quant
        self.rolling_window = rolling_window
        self.max_len = max_len or cfg.max_seq_len
        self.repetition_penalty = repetition_penalty
        self._sampler = functools.partial(
            sample, temperature=temperature, top_k=top_k, top_p=top_p,
            min_p=min_p,
        )
        if mesh is None:
            # Nothing donatable: prefill allocates its cache internally
            # and params must stay live for decode/beam afterwards.
            self._prefill = jax.jit(self._prefill_impl)  # shellac: ignore[SH001]
        else:
            # Pin the cache layout at the prefill boundary; decode then
            # inherits it from its (committed) cache argument.
            from shellac_tpu.inference.kvcache import (
                cache_logical_axes_for,
            )

            axes = cache_logical_axes_for(
                cfg, kv_quant, rolling=rolling_window
            )
            cache_sh = make_shardings(mesh, axes)
            # Nothing donatable here either (see the unsharded branch).
            self._prefill = jax.jit(  # shellac: ignore[SH001]
                self._prefill_impl, out_shardings=(None, cache_sh, None)
            )
        # No donation: the scanned decode returns only tokens/logprobs
        # (the final cache is a discarded scan carry), so there is no
        # output to alias the cache into — donating would just emit
        # XLA's "donated buffers were not usable" warning every compile
        # while invalidating the caller's array for nothing.
        self._decode = jax.jit(  # shellac: ignore[SH001]
            self._decode_impl, static_argnums=(3,)
        )
        self._beam = jax.jit(self._beam_impl, static_argnums=(3, 4, 5))

    def _prefill_impl(self, params, tokens, prompt_len):
        """tokens: (B, S_pad) right-padded; prompt_len: (B,) real lengths."""
        b, s = tokens.shape
        cache = init_cache_for(self.cfg, b, self.max_len, self.kv_quant,
                               rolling=self.rolling_window)
        logits, cache = transformer.forward_with_cache(
            self.cfg, params, tokens, cache, new_tokens_len=prompt_len,
            mesh=self.mesh, fresh_cache=True, attn_impl="auto",
        )
        # Logits at the last *real* prompt position seed the first sample.
        last = jnp.take_along_axis(
            logits, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        # Token-presence mask over the valid prompt (repetition penalty).
        valid = (
            jnp.arange(s, dtype=jnp.int32)[None, :] < prompt_len[:, None]
        )
        seen = jnp.zeros((b, self.cfg.vocab_size), bool)
        seen = seen.at[jnp.arange(b)[:, None], tokens].max(valid)
        return last, cache, seen

    def _decode_impl(self, params, first_token_logits, cache, steps, key, seen):
        from shellac_tpu.ops.sampling import repetition_penalty

        rp = self.repetition_penalty
        b = first_token_logits.shape[0]
        rows = jnp.arange(b)

        def step(carry, _):
            cache, tok, key, seen = carry
            logits, cache = transformer.forward_with_cache(
                self.cfg, params, tok[:, None], cache, mesh=self.mesh
            )
            logits = repetition_penalty(logits[:, 0], seen, rp)
            key, sub = jax.random.split(key)
            nxt = self._sampler(sub, logits)
            seen = seen.at[rows, nxt].set(True)
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits, axis=-1), nxt[:, None], axis=-1
            )[:, 0]
            return (cache, nxt, key, seen), (nxt, lp)

        key, sub = jax.random.split(key)
        first_token_logits = repetition_penalty(first_token_logits, seen, rp)
        first = self._sampler(sub, first_token_logits)
        seen = seen.at[rows, first].set(True)
        first_lp = jnp.take_along_axis(
            jax.nn.log_softmax(first_token_logits, axis=-1), first[:, None], axis=-1
        )[:, 0]
        # The first token comes from prefill logits; the scan samples the
        # remaining steps-1 (no discarded trailing forward pass).
        _, (toks, lps) = jax.lax.scan(
            step, (cache, first, key, seen), None, length=steps - 1
        )
        tokens = jnp.concatenate([first[None], toks], axis=0)
        logprobs = jnp.concatenate([first_lp[None], lps], axis=0)
        return GenerationResult(
            tokens=jnp.moveaxis(tokens, 0, 1), logprobs=jnp.moveaxis(logprobs, 0, 1)
        )

    def generate(
        self,
        prompt_tokens: jax.Array,  # (B, S) int32, right-padded
        prompt_len: Optional[jax.Array] = None,  # (B,) int32
        *,
        max_new_tokens: int = 32,
        key: Optional[jax.Array] = None,
    ) -> GenerationResult:
        if key is None:
            key = jax.random.PRNGKey(0)
        b, s = prompt_tokens.shape
        if prompt_len is None:
            prompt_len = jnp.full((b,), s, jnp.int32)
        first_logits, cache, seen = self._prefill(
            self.params, prompt_tokens, prompt_len
        )
        return self._decode(
            self.params, first_logits, cache, max_new_tokens, key, seen
        )

    # ---- beam search -------------------------------------------------

    @staticmethod
    def _reorder_cache(cache, idx):
        """Gather cache rows by beam index. Every cache field is
        stacked (L, B, ...) except the per-sequence lengths (B,) — so
        the gather axis is a field-name rule, valid for the dense,
        int8, and rolling cache types alike."""
        fields = {
            name: jnp.take(getattr(cache, name), idx, axis=1)
            for name in cache.__dataclass_fields__
            if name != "lengths"
        }
        return cache.replace(lengths=cache.lengths[idx], **fields)

    def _beam_impl(self, params, first_logits, cache, steps, eos_id,
                   length_penalty, ctrans=None):
        """Device-side beam loop: one forward per step for all beams,
        flat top-k over (K, V) candidates, cache rows gathered by the
        winning beams (the standard public algorithm, built on the same
        scanned cached forward as sampling). The expansion/bookkeeping
        math lives in the shared beam_* helpers below so the paged
        engine's CoW beam cannot drift from this one. `ctrans` (a
        TokenDFA table) constrains the search: each beam's logprobs
        are masked through its own DFA row before scoring and the
        per-beam state rides the reorder with the beam."""
        k, _ = first_logits.shape
        scores, beam0, tok0, cstate0 = beam_first_expand(
            first_logits[0], k, ctrans, eos_id
        )
        cache = self._reorder_cache(cache, beam0)
        finished0 = (tok0 == eos_id) if eos_id is not None else (
            jnp.zeros((k,), bool)
        )
        out0 = jnp.zeros((k, steps), jnp.int32).at[:, 0].set(tok0)
        lens0 = jnp.ones((k,), jnp.int32)

        def step(carry, _):
            cache, cur, scores, finished, out, lens, cstate, i = carry
            logits, cache = transformer.forward_with_cache(
                self.cfg, params, cur[:, None], cache, mesh=self.mesh
            )
            (scores, beam, tok, out, lens, finished, was_done,
             cstate) = beam_expand(
                logits[:, 0], scores, finished, out, lens, i, eos_id,
                ctrans, cstate,
            )
            cache = self._reorder_cache(cache, beam)
            # A frozen beam must not grow its cache: re-feeding EOS
            # writes a row, but lengths were already advanced by the
            # forward — roll them back for finished beams.
            cache = cache.replace(
                lengths=jnp.where(
                    was_done, cache.lengths - 1, cache.lengths
                )
            )
            return (cache, tok, scores, finished, out, lens, cstate,
                    i + 1), None

        carry = (cache, tok0, scores, finished0, out0, lens0, cstate0,
                 jnp.int32(1))
        (cache, _, scores, finished, out, lens, _, _), _ = jax.lax.scan(
            step, carry, None, length=steps - 1
        )
        return beam_rank(scores, out, lens, length_penalty)

    def beam_search(
        self,
        prompt_tokens,  # (S,) or (1, S) int32
        *,
        num_beams: int = 4,
        max_new_tokens: int = 32,
        eos_id: Optional[int] = None,
        length_penalty: float = 1.0,
        constraint=None,
    ):
        """Deterministic beam decode of ONE prompt.

        Returns (sequences, scores): sequences is a list of up to
        num_beams token lists (EOS included when hit, best first),
        scores their length-penalized log-probabilities. With a
        compiled `constraint` (constraints.TokenDFA), every beam's
        candidates are masked through its own DFA state before scoring
        — each returned sequence satisfies the grammar — and beams
        forced onto masked candidates (fewer legal continuations than
        beams) are pruned from the result, so fewer than num_beams
        sequences may return. The dense/int8/rolling caches gather
        rows directly; for block pools use
        PagedBatchingEngine.beam_search, which reorders via
        copy-on-write block tables and returns bit-identical beams.
        """
        if self.cfg.eva is not None:
            raise NotImplementedError(
                "beam search reorders dense cache rows by beam; EVA state "
                "(a ring and pooled pages a row) has no such reorder yet"
            )
        if self.cfg.dsa is not None:
            raise NotImplementedError(
                "beam search reorders dense cache rows by beam; a model "
                "with an indexer keeps paged pools (k, v and index keys) "
                "that have no such reorder yet"
            )
        if self.cfg.loop is not None:
            refuse_loop("beam_search")
        if num_beams < 1:
            raise ValueError("num_beams must be >= 1")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        ctrans, eos_id = check_beam_constraint(
            constraint, eos_id, self.cfg.vocab_size
        )
        tokens = jnp.asarray(prompt_tokens, jnp.int32).reshape(1, -1)
        s = tokens.shape[1]
        if s + max_new_tokens + 1 > self.max_len:
            raise ValueError(
                f"prompt {s} + max_new {max_new_tokens} exceeds "
                f"max_len {self.max_len}"
            )
        # Prefill ONCE (B=1): every beam starts from the same prompt,
        # so the K-way cache is a broadcast of one row, not K prefills.
        first_logits, cache, _ = self._prefill(
            self.params, tokens, jnp.full((1,), s, jnp.int32)
        )
        first_logits = jnp.tile(first_logits, (num_beams, 1))
        cache = self._reorder_cache(
            cache, jnp.zeros((num_beams,), jnp.int32)
        )
        out, norm, lens = self._beam(
            self.params, first_logits, cache, int(max_new_tokens),
            eos_id, float(length_penalty), ctrans,
        )
        out, norm, lens = jax.device_get((out, norm, lens))
        return beam_filter_invalid(out, norm, lens)


#: Junk-beam score: a beam forced onto a constraint-masked candidate
#: (fewer legal continuations than beams) carries this; the host-side
#: BEAM_INVALID filter drops it from the returned set. A numpy scalar:
#: a jnp one is a device array, and making it at import initialises the
#: backend before a multi-host worker can call initialize().
BEAM_NEG = np.float32(-1e30)
BEAM_INVALID = -1e20  # host-side validity threshold on final scores


def _beam_mask(lp, row, eos_id):
    """Mask a (K, V) logprob block by each beam's DFA row ((K, V+1);
    -1 = disallowed, last column = EOS legality). Disallowed entries
    drop to BEAM_NEG so a flat top-k can only pick them when fewer
    than K legal candidates exist — those beams rank (and are pruned)
    as invalid."""
    allowed = row[:, :-1] >= 0
    if eos_id is not None:
        allowed = allowed.at[:, eos_id].set(row[:, -1] >= 0)
    return jnp.where(allowed, lp, BEAM_NEG)


def _beam_advance_state(row, cstate, tok, keep, eos_id):
    """Advance each beam's DFA state past its selected token (`row` is
    the pre-selection (K, V+1) table rows, already gathered by beam).
    `keep` marks beams whose state must not move (frozen EOS
    self-loops). Clipped at 0 so an invalid (masked-candidate) beam
    stays traversable — its BEAM_NEG score already prunes it."""
    col = tok
    if eos_id is not None:
        col = jnp.where(tok == eos_id, row.shape[1] - 1, tok)
    nxt = jnp.take_along_axis(row, col[:, None], axis=1)[:, 0]
    return jnp.where(keep, cstate, jnp.maximum(nxt, 0))


def beam_first_expand(last_logits, k, ctrans=None, eos_id=None):
    """First beam expansion from ONE distribution (every beam holds the
    same prefill): masking all but beam 0 keeps the flat top-k from
    picking duplicate (beam, token) pairs. last_logits: (V,). With a
    constraint table `ctrans`, the DFA's start row masks the
    distribution and the returned per-beam states advance past each
    selected token. Returns (scores, beam0, tok0, cstate0), each
    (k,)."""
    lp0 = jax.nn.log_softmax(last_logits.astype(jnp.float32))
    v = lp0.shape[0]
    if ctrans is not None:
        lp0 = _beam_mask(lp0[None], ctrans[:1], eos_id)[0]
    scores0 = jnp.where(jnp.arange(k) == 0, 0.0, BEAM_NEG)
    cand = (scores0[:, None] + lp0[None, :]).reshape(-1)
    scores, flat = jax.lax.top_k(cand, k)
    tok0 = (flat % v).astype(jnp.int32)
    cstate0 = jnp.zeros((k,), jnp.int32)
    if ctrans is not None:
        row = jnp.broadcast_to(ctrans[0][None], (k, ctrans.shape[1]))
        cstate0 = _beam_advance_state(
            row, cstate0, tok0, jnp.zeros((k,), bool), eos_id
        )
    return scores, flat // v, tok0, cstate0


def beam_expand(logits, scores, finished, out, lens, i, eos_id,
                ctrans=None, cstate=None):
    """One beam-search expansion: frozen-EOS self-loop, flat top-k over
    (K, V) candidates, and the out/lens/finished bookkeeping — SHARED
    by the dense loop (Engine._beam_impl) and the paged CoW loop
    (PagedBatchingEngine._beam_paged_impl) so their beams cannot
    drift. With (ctrans, cstate) each live beam's logprobs are masked
    by its own DFA row BEFORE scoring and the returned cstate advanced
    with the beam reorder (frozen beams keep the EOS self-loop
    regardless — they terminated in an accepting state). Returns
    (scores, beam, tok, out, lens, finished, was_done, cstate); the
    caller owns the cache reorder and length rollback."""
    k = scores.shape[0]
    v = logits.shape[-1]
    lp = jax.nn.log_softmax(logits.astype(jnp.float32))
    row = None
    if ctrans is not None:
        row = ctrans[cstate]  # (K, V+1)
        lp = _beam_mask(lp, row, eos_id)
    if eos_id is not None:
        # Finished beams persist unchanged: their only legal
        # continuation is a zero-cost EOS self-loop (this wins over
        # the constraint mask — the beam already terminated legally).
        frozen = jnp.full((v,), BEAM_NEG).at[eos_id].set(0.0)
        lp = jnp.where(finished[:, None], frozen[None], lp)
    cand = (scores[:, None] + lp).reshape(-1)
    scores, flat = jax.lax.top_k(cand, k)
    beam = flat // v
    tok = (flat % v).astype(jnp.int32)
    out = out[beam].at[:, i].set(tok)
    was_done = finished[beam]
    lens = jnp.where(was_done, lens[beam], lens[beam] + 1)
    if eos_id is not None:
        finished = was_done | (tok == eos_id)
    else:
        finished = was_done
    if ctrans is not None:
        cstate = _beam_advance_state(
            row[beam], cstate[beam], tok, was_done, eos_id
        )
    elif cstate is not None:
        cstate = cstate[beam]
    return scores, beam, tok, out, lens, finished, was_done, cstate


def beam_rank(scores, out, lens, length_penalty):
    """Length-penalized final ranking (HF/GNMT convention: divide by
    len^alpha; alpha=0 is raw sum-logprob, alpha=1 is mean)."""
    norm = scores / jnp.power(lens.astype(jnp.float32),
                              jnp.float32(length_penalty))
    order = jnp.argsort(-norm)
    return out[order], norm[order], lens[order]


def check_beam_constraint(constraint, eos_id, vocab_size):
    """Validate a beam-search constraint and resolve the EOS id the
    search must use. Returns (ctrans device array or None, eos_id) —
    the same submit-time contract the batching engine enforces:
    termination (EOS finishing a beam) and the DFA's EOS column must
    agree, or the mask would silently diverge from the search."""
    if constraint is None:
        return None, eos_id
    from shellac_tpu.inference.constraints import TokenDFA

    if not isinstance(constraint, TokenDFA):
        raise ValueError(
            "beam constraint must be a compiled constraints.TokenDFA "
            "(the server compiles specs; library users call "
            "compile_token_dfa)"
        )
    if constraint.trans.shape[1] != vocab_size + 1:
        raise ValueError(
            f"beam constraint table covers "
            f"{constraint.trans.shape[1] - 1} tokens, model vocab is "
            f"{vocab_size}"
        )
    if eos_id is None:
        eos_id = constraint.eos_id
    elif eos_id != constraint.eos_id:
        raise ValueError(
            f"beam constraint eos_id {constraint.eos_id} must equal "
            f"the requested eos_id {eos_id} (termination and EOS "
            "masking must agree)"
        )
    if not 0 <= eos_id < vocab_size:
        # jnp .at[] clips an out-of-range index instead of raising, so
        # an EOS the model cannot emit would silently corrupt another
        # token's mask AND leave every beam unable to terminate-accept.
        raise ValueError(
            f"constraint eos_id {eos_id} is outside the model vocab "
            f"({vocab_size}); the model cannot emit it"
        )
    return jnp.asarray(constraint.trans), eos_id


def beam_filter_invalid(out, norm, lens):
    """Host-side post-pass shared by the dense and paged searches:
    drop beams whose score shows they were forced onto a masked
    candidate (a constrained search with fewer legal continuations
    than beams). The best beam always survives — the compiled DFA has
    no dead states, so a legal path exists whenever the grammar is
    non-empty."""
    seqs, scores = [], []
    for row, n, s in zip(out, lens, norm):
        if float(s) <= BEAM_INVALID:
            continue
        seqs.append(row[:n].tolist())
        scores.append(float(s))
    return seqs, scores


def truncate_at_stop(tokens, stop, prompt_outputs=None):
    """Host-side stop-sequence post-processing for Engine outputs.

    The Engine's decode loop runs entirely on device (a lax.scan with a
    fixed budget), so stop sequences are applied after the fact: each
    row of `tokens` (B, max_new) is cut at the FIRST occurrence of any
    stop sequence, excluding the match. Returns a list of per-row
    python lists (ragged). The continuous-batching engine implements
    the same contract with true early exit (its submit(..., stop=...));
    this helper keeps the single-request API consistent.
    """
    rows = np.asarray(tokens)
    seqs = [list(map(int, s)) for s in stop]
    if any(len(s) == 0 for s in seqs):
        raise ValueError("empty stop sequence")
    out = []
    for row in rows:
        row = row.tolist()
        cut = len(row)
        for s in seqs:
            n = len(s)
            for i in range(0, len(row) - n + 1):
                if row[i:i + n] == s:
                    cut = min(cut, i)
                    break
        out.append(row[:cut])
    return out
