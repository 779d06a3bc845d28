"""Token sampling: temperature, top-k, nucleus (top-p), greedy.

All filtering happens in fp32 logit space with jnp.where masks — no
data-dependent shapes, so the whole sampler jits into the decode loop.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def top_k_mask(logits: jax.Array, k: int) -> jax.Array:
    """Mask all but the k largest logits per row.

    k >= vocab degrades to a no-op rather than indexing out of bounds;
    k < 1 is rejected (k is user-supplied via the CLI/engine).
    """
    if k < 1:
        raise ValueError(f"top_k must be >= 1, got {k}")
    k = min(k, logits.shape[-1])
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, NEG_INF, logits)


def top_p_mask(logits: jax.Array, p: float) -> jax.Array:
    """Nucleus filtering: keep the smallest set with cumulative prob >= p."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep entries whose *previous* cumulative mass is < p (always keeps top-1).
    keep_sorted = (cum - probs) < p
    # Threshold logit = smallest kept logit.
    kth = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
    )
    return jnp.where(logits < kth, NEG_INF, logits)


def min_p_mask(logits: jax.Array, p: float) -> jax.Array:
    """Keep tokens whose prob >= p * max prob (scale-adaptive cutoff)."""
    probs = jax.nn.softmax(logits, axis=-1)
    cutoff = p * jnp.max(probs, axis=-1, keepdims=True)
    return jnp.where(probs < cutoff, NEG_INF, logits)


def repetition_penalty(
    logits: jax.Array,  # (..., V)
    seen: jax.Array,  # (..., V) bool — tokens already in the context
    penalty: float,
) -> jax.Array:
    """HF-convention penalty: seen tokens' logits /p if >0 else *p."""
    if penalty == 1.0:
        return logits
    penalized = jnp.where(logits > 0, logits / penalty, logits * penalty)
    return jnp.where(seen, penalized, logits)


@jax.named_scope("sample")
def sample(
    key: jax.Array,
    logits: jax.Array,  # (..., V)
    *,
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    min_p: Optional[float] = None,
) -> jax.Array:
    """Sample token ids. temperature == 0 means greedy."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        logits = top_k_mask(logits, top_k)
    if top_p is not None:
        logits = top_p_mask(logits, top_p)
    if min_p is not None:
        logits = min_p_mask(logits, min_p)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def filter_logits_batched(
    logits: jax.Array,  # (B, V)
    temperature: jax.Array,  # (B,) fp32; <= 0 rows temper at 1.0
    top_k: jax.Array,  # (B,) int32; >= V disables
    top_p: jax.Array,  # (B,) fp32; 1.0 disables
    min_p: jax.Array,  # (B,) fp32; 0.0 disables
) -> jax.Array:
    """The tempered, top-k/top-p/min-p-masked fp32 logits the batched
    sampler draws from — THE truncation definition, factored out so
    speculative decoding can apply the IDENTICAL filter to both the
    draft and target distributions (rejection sampling then provably
    reproduces the FILTERED target distribution, which is exactly what
    sequential sampling draws from — the spec x top-k/top-p identity).

    Greedy rows (temperature <= 0) are tempered at 1.0 and otherwise
    filtered like any row; callers argmax those rows on their own
    unfiltered logits, matching `sample_batched`.
    """
    logits = logits.astype(jnp.float32)
    v = logits.shape[-1]
    t = jnp.where(temperature <= 0.0, 1.0, temperature)[:, None]
    x = logits / t
    # top-k: per-row kth-largest threshold (ties at the boundary are
    # kept, matching top_k_mask).
    k = jnp.clip(top_k, 1, v)
    asc = jnp.sort(x, axis=-1)
    kth = jnp.take_along_axis(asc, (v - k)[:, None], axis=-1)
    x = jnp.where(x < kth, NEG_INF, x)
    # top-p on the top-k-filtered rows (same order as the scalar path);
    # re-sort so boundary ties behave exactly like top_p_mask.
    desc = jnp.sort(x, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p[:, None]
    kth_p = jnp.min(
        jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
    )
    x = jnp.where(x < kth_p, NEG_INF, x)
    # min-p relative to each row's current max.
    probs_x = jax.nn.softmax(x, axis=-1)
    cutoff = min_p[:, None] * jnp.max(probs_x, axis=-1, keepdims=True)
    x = jnp.where(probs_x < cutoff, NEG_INF, x)
    return x


@jax.named_scope("sample")
def sample_batched(
    key: jax.Array,
    logits: jax.Array,  # (B, V)
    temperature: jax.Array,  # (B,) fp32; 0 = greedy
    top_k: jax.Array,  # (B,) int32; >= V disables
    top_p: jax.Array,  # (B,) fp32; 1.0 disables
    min_p: jax.Array,  # (B,) fp32; 0.0 disables
    seed: Optional[jax.Array] = None,  # (B,) int32; -1 = unseeded
    gen_idx: Optional[jax.Array] = None,  # (B,) int32 — tokens generated
) -> jax.Array:
    """`sample` with PER-ROW parameters, for serving engines that mix
    requests with different sampling settings in one device batch.

    Same filter semantics as the scalar path (verified token-exact in
    tests when all rows share one setting): disabled values are the
    no-op sentinels above rather than None, so the whole thing stays
    one jittable program with fixed shapes.

    seed/gen_idx: per-request DETERMINISTIC sampling — a seeded row
    draws from fold_in(PRNGKey(seed), gen_idx), so its tokens depend
    only on (seed, logits, position in its own generation), never on
    slot placement, co-tenant requests, or the engine's key state.
    Rows with seed < 0 keep the shared stream.
    """
    logits = logits.astype(jnp.float32)
    greedy = temperature <= 0.0
    x = filter_logits_batched(logits, temperature, top_k, top_p, min_p)
    sampled = jax.random.categorical(key, x, axis=-1)
    if seed is not None:
        def row_draw(s, g, row):
            k = jax.random.fold_in(
                jax.random.PRNGKey(jnp.maximum(s, 0)), g
            )
            return jax.random.categorical(k, row)

        per_row = jax.vmap(row_draw)(seed, gen_idx, x)
        sampled = jnp.where(seed >= 0, per_row, sampled)
    return jnp.where(
        greedy, jnp.argmax(logits, axis=-1), sampled
    ).astype(jnp.int32)
