"""Speculative decoding: draft-model proposal + target verification.

Decode is HBM-bound, so the target model's per-token cost is dominated by
re-reading its weights. Speculative decoding (Leviathan et al., 2022)
amortizes that read: a small draft model proposes `gamma` tokens
autoregressively, then the target scores all gamma+1 positions in ONE
forward pass (an MXU-friendly batched matmul instead of gamma small
ones) and accepts a prefix via rejection sampling. The emitted
distribution is mathematically identical to sampling the target alone.

TPU-first structure — everything is static-shape and stays on device:
  - the round loop is a `lax.while_loop`; each round emits between 1 and
    gamma+1 tokens per sequence (batch entries advance unevenly, tracked
    by per-sequence write offsets into a slack-padded output buffer);
  - rejected tokens are "rolled back" by clamping the KV cache's
    per-sequence `lengths` — stale entries are overwritten on the next
    write at that offset (see kvcache.py), no copies;
  - after its gamma sampled steps the draft runs one backfill step on
    its last proposal so that, when every token is accepted, the draft
    cache already holds the full history for the next round.

Temperature 0 uses the exact-match degenerate form (accept iff the draft
token equals the target argmax), which makes greedy speculative output
EXACTLY equal to greedy target-only decoding — the main correctness test.

The reference repo for this project is empty (SURVEY.md §0); there is no
upstream speculative decoder to cite.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference.cache.base import refuse_loop
from shellac_tpu.inference.kvcache import KVCache, init_cache
from shellac_tpu.models import transformer
from shellac_tpu.ops.sampling import sample


@flax.struct.dataclass
class SpecResult:
    tokens: jax.Array  # (B, max_new_tokens) int32 — target-distributed
    rounds: jax.Array  # () int32 — verification rounds run
    accept_rate: jax.Array  # () fp32 — accepted draft tokens / proposed


def _probs(logits: jax.Array, temperature: float) -> jax.Array:
    return jax.nn.softmax(logits.astype(jnp.float32) / temperature, axis=-1)


class SpeculativeEngine:
    """Paired target/draft engine. Models must share the vocabulary."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        draft_cfg: ModelConfig,
        draft_params: Any,
        *,
        gamma: int = 4,
        temperature: float = 1.0,
        max_len: Optional[int] = None,
    ):
        if cfg.vocab_size != draft_cfg.vocab_size:
            raise ValueError(
                f"target/draft vocab mismatch: {cfg.vocab_size} vs "
                f"{draft_cfg.vocab_size}"
            )
        if gamma < 1:
            raise ValueError(f"gamma must be >= 1, got {gamma}")
        if cfg.loop is not None or draft_cfg.loop is not None:
            refuse_loop("speculative")
        self.cfg = cfg
        self.draft_cfg = draft_cfg
        self.params = params
        self.draft_params = draft_params
        self.gamma = gamma
        self.temperature = float(temperature)
        self.max_len = max_len or min(cfg.max_seq_len, draft_cfg.max_seq_len)
        self._gen = jax.jit(self._generate_impl, static_argnums=(4,))

    # ---- one verification round -------------------------------------

    def _draft_propose(self, draft_params, dcache, cur, key):
        """gamma sampled draft steps + one cache-backfill step.

        Returns (dcache, drafts (B, gamma) int32, q (B, gamma, V) fp32).
        """
        g = self.gamma

        def step(carry, k):
            dc, tok = carry
            logits, dc = transformer.forward_with_cache(
                self.draft_cfg, draft_params, tok[:, None], dc
            )
            logits = logits[:, 0]
            nxt = sample(k, logits, temperature=self.temperature)
            q = _probs(logits, self.temperature or 1.0)
            return (dc, nxt), (nxt, q)

        (dcache, _), (drafts, qs) = jax.lax.scan(
            step, (dcache, cur), jax.random.split(key, g)
        )
        # Backfill: write the last proposal's kv so the all-accepted case
        # leaves the draft cache complete for the next round.
        _, dcache = transformer.forward_with_cache(
            self.draft_cfg, draft_params, drafts[-1][:, None], dcache
        )
        return dcache, drafts.T, jnp.moveaxis(qs, 0, 1)  # (B,g), (B,g,V)

    def _round(self, params, draft_params, carry):
        (tcache, dcache, cur, out, out_len, key, n_acc, n_prop, rounds,
         max_new) = carry
        g = self.gamma
        b = cur.shape[0]
        key, kd, kacc, kres, kbonus = jax.random.split(key, 5)

        lt0 = tcache.lengths  # target history length before this round
        ld0 = dcache.lengths

        dcache, drafts, qs = self._draft_propose(draft_params, dcache, cur, kd)

        # Target scores [cur, d_0..d_{g-1}] in one forward: logits[:, i]
        # is the target distribution for position i's successor.
        tin = jnp.concatenate([cur[:, None], drafts], axis=1)  # (B, g+1)
        tlogits, tcache = transformer.forward_with_cache(
            self.cfg, params, tin, tcache
        )
        ps = _probs(tlogits, self.temperature or 1.0)  # (B, g+1, V)

        p_d = jnp.take_along_axis(ps[:, :g], drafts[..., None], axis=-1)[..., 0]
        q_d = jnp.take_along_axis(qs, drafts[..., None], axis=-1)[..., 0]
        if self.temperature == 0.0:
            accept = drafts == jnp.argmax(ps[:, :g], axis=-1)
        else:
            u = jax.random.uniform(kacc, (b, g))
            accept = u * q_d < p_d
        # Length of the accepted prefix: 0..g per sequence.
        n = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1), axis=1)

        # Token emitted after the accepted prefix: residual resample on
        # rejection, bonus sample from the g+1'th target dist otherwise.
        idx = jnp.minimum(n, g - 1)
        p_n = jnp.take_along_axis(ps, idx[:, None, None], axis=1)[:, 0]  # (B,V)
        q_n = jnp.take_along_axis(qs, idx[:, None, None], axis=1)[:, 0]
        if self.temperature == 0.0:
            # Degenerate (one-hot) form: a rejected position emits the
            # target's own argmax, not the continuous-residual argmax.
            r = jnp.argmax(p_n, axis=-1).astype(jnp.int32)
            bonus = jnp.argmax(ps[:, g], axis=-1).astype(jnp.int32)
        else:
            res = jnp.maximum(p_n - q_n, 0.0)
            res_mass = jnp.sum(res, axis=-1, keepdims=True)
            # p == q pointwise means rejection has probability 0; the
            # guard only protects against fp rounding making a zero row.
            res = jnp.where(res_mass > 1e-9, res, p_n)
            r = jax.random.categorical(kres, jnp.log(res + 1e-30)).astype(
                jnp.int32
            )
            bonus = jax.random.categorical(
                kbonus, jnp.log(ps[:, g] + 1e-30)
            ).astype(jnp.int32)
        extra = jnp.where(n < g, r, bonus)

        # Emitted chunk (B, g+1): accepted drafts then `extra` at col n;
        # columns past n are garbage that later rounds overwrite.
        cols = jnp.arange(g + 1, dtype=jnp.int32)[None, :]
        padded = jnp.concatenate([drafts, extra[:, None]], axis=1)
        emitted = jnp.where(cols == n[:, None], extra[:, None], padded)

        done = out_len >= max_new
        # Roll back: valid history = old length + 1 (cur) + n accepted;
        # finished sequences freeze entirely.
        new_tlen = jnp.where(done, lt0, lt0 + 1 + n)
        new_dlen = jnp.where(done, ld0, ld0 + 1 + n)
        tcache = KVCache(k=tcache.k, v=tcache.v, lengths=new_tlen)
        dcache = KVCache(k=dcache.k, v=dcache.v, lengths=new_dlen)
        cur = jnp.where(done, cur, extra)

        offset = jnp.minimum(out_len, max_new)  # done rows write to slack
        out = jax.vmap(
            lambda row, chunk, i: jax.lax.dynamic_update_slice(row, chunk, (i,))
        )(out, emitted, offset)
        out_len = jnp.where(done, out_len, out_len + n + 1)
        live = (~done).astype(jnp.int32)
        n_acc = n_acc + jnp.sum(n * live)
        n_prop = n_prop + jnp.sum(live) * g
        return (tcache, dcache, cur, out, out_len, key, n_acc, n_prop,
                rounds + 1, max_new)

    # ---- generation --------------------------------------------------

    def _generate_impl(self, params, draft_params, tokens, prompt_len,
                       max_new, key):
        b, s = tokens.shape
        g = self.gamma
        tcache = init_cache(self.cfg, b, self.max_len)
        dcache = init_cache(self.draft_cfg, b, self.max_len)
        tlogits, tcache = transformer.forward_with_cache(
            self.cfg, params, tokens, tcache, new_tokens_len=prompt_len,
            fresh_cache=True, attn_impl="auto",
        )
        _, dcache = transformer.forward_with_cache(
            self.draft_cfg, draft_params, tokens, dcache,
            new_tokens_len=prompt_len, fresh_cache=True, attn_impl="auto",
        )
        last = jnp.take_along_axis(
            tlogits, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        key, k0 = jax.random.split(key)
        cur = sample(k0, last, temperature=self.temperature)

        out = jnp.zeros((b, max_new + g + 1), jnp.int32)
        # The token sampled from prefill is the first output.
        out = out.at[:, 0].set(cur)
        out_len = jnp.ones((b,), jnp.int32)
        zero = jnp.zeros((), jnp.int32)
        carry = (tcache, dcache, cur, out, out_len, key, zero, zero, zero,
                 jnp.asarray(max_new, jnp.int32))

        def cond(c):
            return jnp.any(c[4] < c[9])

        carry = jax.lax.while_loop(
            cond, functools.partial(self._round, params, draft_params), carry
        )
        (_, _, _, out, _, _, n_acc, n_prop, rounds, _) = carry
        rate = n_acc.astype(jnp.float32) / jnp.maximum(
            n_prop.astype(jnp.float32), 1.0
        )
        return SpecResult(
            tokens=out[:, :max_new], rounds=rounds, accept_rate=rate
        )

    def generate(
        self,
        prompt_tokens: jax.Array,  # (B, S) int32, right-padded
        prompt_len: Optional[jax.Array] = None,
        *,
        max_new_tokens: int = 32,
        key: Optional[jax.Array] = None,
    ) -> SpecResult:
        if key is None:
            key = jax.random.PRNGKey(0)
        b, s = prompt_tokens.shape
        if prompt_len is None:
            prompt_len = jnp.full((b,), s, jnp.int32)
        # Worst case: a finished row freezes its cache length at up to
        # s + max_new + gamma - 1 and later rounds still write gamma+1
        # entries there, so reserve s + max_new + 2*gamma slots (+2 slack)
        # to keep those writes off the valid prefix.
        need = s + max_new_tokens + 2 * self.gamma + 2
        if need > self.max_len:
            raise ValueError(
                f"prompt ({s}) + max_new_tokens ({max_new_tokens}) + "
                f"gamma slack needs cache length {need} > max_len "
                f"{self.max_len}"
            )
        return self._gen(
            self.params, self.draft_params, prompt_tokens, prompt_len,
            max_new_tokens, key,
        )
