"""Keye-VL-2.0's text model (GQA with a learned sparse-attention indexer,
128 routed experts) for the benchmark.

Same parts as the other architecture files, all independent of the program
under test: ``make_weights`` (from a seed, on the device, as served),
``reference_logits`` (the plain forward pass in float32 at ``highest`` matmul
precision, layer by layer, no cache, no kernels, no batching, in query blocks
so that 33,408 positions fit beside the weights), ``counts`` / ``token_flops``
/ ``prefill_attn_flops`` (parameters, FLOPs and bytes from shapes alone) and
``to_program`` (the one place that knows the program's names).

The layer. Input x_t; h_t = RMSNorm(x_t). Rope: ``rope_theta``, the half-split
convention, position t.

  main projections   q_{t,a} = rope(RMSNorm_dh(W_q^a h_t)),  a = 1..H
                     k_{t,b} = rope(RMSNorm_dh(W_k^b h_t)),  v_{t,b} = W_v^b h_t,
                     b = 1..Hkv; head a reads kv head (a - 1) // (H / Hkv)
  indexer            u_{t,j} = rope_Di(W_I^j h_t),  j = 1..J      (index queries)
                     c_t     = rope_Di(LayerNorm_Di(W_C h_t))     (ONE index key)
                     w_t     = W_w h_t  in R^J
                     I[t, s] = sum_j w_{t,j} relu(u_{t,j} . c_s),  s <= t
  choice             S_t = { s <= t } while t + 1 <= topk; after that the topk
                     positions of largest I[t, s], ties to the lower s
                     (jax.lax.top_k). One set a query a layer, all heads.
  attention          o_{t,a} = sum_{s in S_t} softmax_{S_t}(dh^-0.5 q_{t,a} . k_s) v_s
                     x_t += W_o [o_{t,1} .. o_{t,H}]
  experts            h' = RMSNorm(x_t); p = softmax(W_r h') over E (float32);
                     T = the k largest; x_t += sum_{e in T} (p_e / sum_T p)
                     W_down^e(silu(W_gate^e h') * W_up^e h'). No shared expert,
                     no dense layer (``intermediate_size`` belongs to no layer).
  logits             RMSNorm(x) W_head, untied.

This is the catalog's ``config`` with the indexer of DeepSeek-Sparse-Attention
as published (DeepSeek-V3.2-Exp: ReLU'd per-head dot products of index queries
with one index key a token, weighted by a learned per-head weight of the query
token, then a token-level top-k). The released form's constants J^-0.5 and
Di^-0.5 are positive powers of two here: kept or dropped, the choice is the same
bit for bit; they are dropped. What the config does not settle (configuration
file, ``assumed``), each a departure or a reading of this file's own:

1. ``index_queries``: the index queries are projected from the normed hidden
   state; the published indexer takes them from MLA's query latent, which a GQA
   model has not.
2. ``index_key_norm_rope``: LayerNorm (gain and bias, eps ``rms_norm_eps``) on
   the index key, and rope over all Di index dimensions on both sides.
3. ``chunk_sizes``: ``q_chunk_size`` / ``kv_chunk_size`` 512 are read as the tile
   sizes of the released kernels and enter no equation: the choice is per query
   and per token.
4. ``qk_norm``: per-head q/k RMSNorm before rope, as in the Qwen3-MoE block
   whose keys the text model carries.
5. ``index_precision``: the released indexer's fp8 and Hadamard step is an
   implementation of the same scores; index keys are cached in bfloat16.
6. ``weights``: the law of the seeded weights: as the other architectures'
   but for the embedding, N(0, 1) where they draw N(0, 0.02^2). With random
   values an attention output is an average of 2048 unrelated rows, so one row
   swapped at a near tie of the choice moves it by percents; under a stream of
   0.02 the first routers read that and flip experts on bfloat16 rounding of
   the index scores alone (PERF.md, PR 31: the program then read as far from
   this reference as its int8 control). At unit scale, a trained stream's, the
   swaps fall under the rounding every cell carries.

The vision tower is no part of this file: the served path takes token ids, for
which the three axes of M-RoPE are equal and the rope is the ordinary one.

``dense=True`` (one reading in PERF.md, nothing else) switches the choice off:
every query attends every earlier position.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from benchmark.harness import weights

KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
        "num_experts", "num_experts_per_tok", "moe_intermediate_size",
        "norm_topk_prob")

#: Rows a query block of the reference holds scores for at once.
QBLOCK = 128
#: Positions are cut to a multiple of this past the last one asked for
#: (causal: later rows change nothing), so a short request costs its own
#: length and a few lengths share a compiled layer.
TRIM = 16384


def dims(hf):
    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
        raise ValueError("this reference writes every layer routed")
    sa = hf["sa_config"]
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise ValueError("this reference writes one index key a token")
    return dict(V=hf["vocab_size"], d=hf["hidden_size"], L=hf["num_hidden_layers"],
                h=hf["num_attention_heads"], hkv=hf["num_key_value_heads"],
                dh=hf["head_dim"], E=hf["num_experts"], k=hf["num_experts_per_tok"],
                fe=hf["moe_intermediate_size"], J=sa["indexer_num_heads"],
                Di=sa["indexer_head_dim"], topk=sa["topk"])


# ---------------------------------------------------------------- weights

def weight_shapes(hf):
    m = dims(hf)
    L, d, h, hkv, dh, E, fe, V, J, Di = (m[x] for x in (
        "L", "d", "h", "hkv", "dh", "E", "fe", "V", "J", "Di"))
    out = (2 * L) ** -0.5
    return {
        "embed": ((V, d), 1.0),
        "attn_norm": ((L, d), None),
        "wq": ((L, d, h * dh), d ** -0.5),
        "wk": ((L, d, hkv * dh), d ** -0.5),
        "wv": ((L, d, hkv * dh), d ** -0.5),
        "q_norm": ((L, dh), None),
        "k_norm": ((L, dh), None),
        "wo": ((L, h * dh, d), out * (h * dh) ** -0.5),
        "idx_wq": ((L, d, J * Di), d ** -0.5),
        "idx_wk": ((L, d, Di), d ** -0.5),
        "idx_k_norm": ((L, Di), None),
        "idx_k_bias": ((L, Di), 0.1),
        "idx_ww": ((L, d, J), d ** -0.5),
        "mlp_norm": ((L, d), None),
        "w_router": ((L, d, E), d ** -0.5),
        "w_gate": ((L, E, d, fe), d ** -0.5),
        "w_up": ((L, E, d, fe), d ** -0.5),
        "w_down": ((L, E, fe, d), out * fe ** -0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, V), d ** -0.5),
    }


def make_weights(hf, seed, dtype=jnp.bfloat16, shardings=None):
    """All weights (``x @ W`` orientation, stacked over layers) from ``seed``."""
    return weights.make(weight_shapes(hf), seed, dtype, shardings)


_NORMS = ("attn_norm", "q_norm", "k_norm", "mlp_norm")
_RENAMED = {"idx_wq": "dsa_wq", "idx_wk": "dsa_wk", "idx_ww": "dsa_ww",
            "idx_k_bias": "dsa_k_bias"}


def to_program(w):
    """The program's parameter tree. Its norms multiply by ``1 + scale``; the
    weights here hold the whole multiplier."""
    off = lambda g: (g.astype(jnp.float32) - 1.0).astype(g.dtype)
    layer = {}
    for name, a in w.items():
        if name in ("embed", "final_norm", "lm_head"):
            continue
        if name == "idx_k_norm":
            layer["dsa_k_norm"] = off(a)
        else:
            layer[_RENAMED.get(name, name)] = off(a) if name in _NORMS else a
    return {"embed": w["embed"], "layers": layer,
            "final_norm": off(w["final_norm"]), "lm_head": w["lm_head"]}


def program_config(hf):
    """The published keys, for the program's own
    ``models.convert.config_from_hf`` (``model_type: KeyeVL2``).

    A program without the mechanism must not serve another model under this
    name: a converter that does not know ``KeyeVL2`` reads these keys as a
    Mixtral-shaped model (``num_local_experts``) with dense attention, and runs.
    So the checkout is asked, by its source text and without importing it,
    whether its configuration has an indexer at all; where not, the run ends
    here, at once and with a non-zero exit code."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        with open(os.path.join(root, "shellac_tpu", "config.py")) as f:
            has = "class DSAConfig" in f.read()
    except OSError:
        has = False
    if not has:
        raise SystemExit(
            "keye_vl2: the program in this checkout has no learned sparse-attention "
            "indexer (no DSAConfig in shellac_tpu/config.py): it cannot run this "
            "configuration")
    return {"hf_config": dict(hf)}


# -------------------------------------------------------------- reference

def _rms(x, g, eps):
    v = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * g


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _rope_half(x, pos, theta):
    """x: (S, H, D). Rotate (x[:D/2], x[D/2:]) pairs."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _q(x, quant):
    return x if quant is None else quant(x)


def index_scores(u, w, c, quant=None):
    """I (Q, S) float32: u (Q, J, Di), w (Q, J), c (S, Di)."""
    s = jnp.einsum("qjd,sd->qjs", _q(u, quant), _q(c, quant))
    return jnp.einsum("qjs,qj->qs", _q(jax.nn.relu(s), quant), _q(w, quant))


def chosen(scores, q_pos, topk):
    """(Q, S) bool: the set S_t of each query. scores (Q, S); q_pos (Q,)."""
    n = scores.shape[1]
    causal = jnp.arange(n)[None, :] <= q_pos[:, None]
    if n <= topk:
        return causal
    # -0.0 counts as 0.0 (a float comparison's order; a sort's total order
    # would put it below).
    s = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(s, topk)
    keep = jnp.zeros(s.shape, bool).at[jnp.arange(s.shape[0])[:, None], idx].set(True)
    return keep & causal


def _attention(x, lw, hf, quant, dense):
    m = dims(hf)
    h, hkv, dh, J, Di = m["h"], m["hkv"], m["dh"], m["J"], m["Di"]
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    f32 = lambda a: _q(a.astype(jnp.float32), quant)
    g32 = lambda a: a.astype(jnp.float32)
    s = x.shape[0]
    pos = jnp.arange(s)
    hx = _q(_rms(x, g32(lw["attn_norm"]), eps), quant)
    q = _rms((hx @ f32(lw["wq"])).reshape(s, h, dh), g32(lw["q_norm"]), eps)
    k = _rms((hx @ f32(lw["wk"])).reshape(s, hkv, dh), g32(lw["k_norm"]), eps)
    v = (hx @ f32(lw["wv"])).reshape(s, hkv, dh)
    q, k = _rope_half(q, pos, theta), _rope_half(k, pos, theta)
    u = _rope_half((hx @ f32(lw["idx_wq"])).reshape(s, J, Di), pos, theta)
    c = _layer_norm(hx @ f32(lw["idx_wk"]), g32(lw["idx_k_norm"]),
                    g32(lw["idx_k_bias"]), eps)
    c = _rope_half(c[:, None, :], pos, theta)[:, 0, :]
    w = hx @ f32(lw["idx_ww"])
    qg = _q(q, quant).reshape(s, hkv, h // hkv, dh)
    kq, vq = _q(k, quant), _q(v, quant)
    scale = dh ** -0.5
    qb = min(QBLOCK, s)
    while s % qb:
        qb -= 1

    def block(lo):
        qpos = lo + jnp.arange(qb)
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, qb, axis=0)
        if dense:
            keep = jnp.arange(s)[None, :] <= qpos[:, None]
        else:
            keep = chosen(index_scores(sl(u), sl(w), c, quant), qpos, m["topk"])
        sc = jnp.einsum("qhgd,khd->hgqk", sl(qg), kq) * scale
        sc = jnp.where(keep[None, None], sc, -jnp.inf)
        p = _q(jax.nn.softmax(sc, axis=-1), quant)
        return jnp.einsum("hgqk,khd->qhgd", p, vq).reshape(qb, h * dh)

    o = jax.lax.map(block, jnp.arange(0, s, qb)).reshape(s, h * dh)
    return x + _q(o, quant) @ f32(lw["wo"])


@functools.partial(jax.jit, static_argnames=("hf_t", "quant", "dense"))
def _layer(x, lw, hf_t, quant=None, dense=False):
    hf = _unfreeze(hf_t)
    m = dims(hf)
    f32 = lambda a: _q(a.astype(jnp.float32), quant)
    x = _attention(x, lw, hf, quant, dense)
    hx = _q(_rms(x, lw["mlp_norm"].astype(jnp.float32), hf["rms_norm_eps"]), quant)
    # The router is float32 in the published model whatever the rest runs in.
    probs = jax.nn.softmax(hx @ lw["w_router"].astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(probs, m["k"])
    if hf.get("norm_topk_prob"):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)

    def one(acc, ew):
        wg, wu, wd, g = ew
        act = jax.nn.silu(hx @ f32(wg)) * (hx @ f32(wu))
        return acc + g[:, None] * (_q(act, quant) @ f32(wd)), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x), (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T))
    return x + routed


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, g, lm_head, eps, quant=None):
    hx = _q(_rms(x, g.astype(jnp.float32), eps), quant)
    return hx @ _q(lm_head.astype(jnp.float32), quant)


def _freeze(v):
    return tuple(sorted((k, _freeze(x)) for k, x in v.items())) if isinstance(v, dict) else v


def _unfreeze(t):
    return {k: (dict(v) if isinstance(v, tuple) else v) for k, v in t}


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo", "idx_wq",
              "idx_wk", "idx_k_norm", "idx_k_bias", "idx_ww", "mlp_norm",
              "w_router", "w_gate", "w_up", "w_down")


def reference_logits(hf, w, tokens, positions, quant=None, dense=False):
    """float32 logits (len(positions), V) of one sequence ``tokens`` (S,) at
    ``positions``. ``quant`` (a function on float32 arrays) rounds every matmul
    operand, the indexer's included: the control's lower precision."""
    hf_t = _freeze({k: hf[k] for k in hf if k in KEYS or k == "sa_config"})
    need = int(jnp.max(positions)) + 1
    tokens = tokens[: min(tokens.shape[0], -(-need // TRIM) * TRIM)]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        for l in range(hf["num_hidden_layers"]):
            x = _layer(x, {k: w[k][l] for k in LAYER_KEYS}, hf_t, quant, dense)
        return _head(x[positions], w["final_norm"], w["lm_head"],
                     hf["rms_norm_eps"], quant)


# ------------------------------------------------------------------ counts

def counts(hf):
    """Parameters, and FLOPs/bytes as functions of shapes. No measurement."""
    m = dims(hf)
    L, d, h, hkv, dh, E, k, fe, V, J, Di = (m[x] for x in (
        "L", "d", "h", "hkv", "dh", "E", "k", "fe", "V", "J", "Di"))
    attn = 2 * d * h * dh + 2 * d * hkv * dh
    index = d * J * Di + d * Di + d * J
    expert = 3 * d * fe
    shared = attn + index + d * E          # what every token's layer reads
    layer = shared + E * expert
    active = L * (shared + k * expert) + d * V
    norms = 2 * d + 2 * dh + 2 * Di
    return {
        "layer_params": layer + norms,
        "layer_matmul_params": layer,
        "params": L * (layer + norms) + 2 * V * d + d,
        "matmul_params_per_token": active,
        "experts": E, "experts_per_token": k,
        "expert_bytes": 2 * expert,                        # bf16, one expert
        "shared_weight_bytes": 2 * (L * shared + d * V),   # bf16: non-expert + head
        "weight_bytes_per_tick": 2 * (L * layer + d * V),  # every held matrix once
        "rows_kept": m["topk"],
        # a query attending one row (QK^T and PV), and scoring one row
        "attn_flops_per_key": 4 * h * dh * L,
        "index_flops_per_key": 2 * J * Di * L,
        # one cached row: k and v of every kv head; and its index key (bf16)
        "kv_bytes_per_row": 2 * hkv * dh * 2 * L,
        "index_bytes_per_row": Di * 2 * L,
        "kv_bytes_per_token": (2 * hkv * dh + Di) * 2 * L,
    }


def tick_expert_bytes(hf, rows):
    """Expert weights a decode tick of ``rows`` routed tokens must read, all
    layers: one expert's bytes x E (1 - (1 - k/E)^rows), uniform routing (the
    law that touches the most experts, so the same yardstick whatever the
    program reads)."""
    c = counts(hf)
    e, k = c["experts"], c["experts_per_token"]
    touched = e * (1.0 - (1.0 - k / e) ** rows)
    return c["expert_bytes"] * hf["num_hidden_layers"] * touched


def chunk_index_work(hf, offset, tokens):
    """(FLOPs, bytes) the index scores of a prompt chunk need, all layers:
    the chunk's ``tokens`` queries at positions ``offset`` .. each score every
    row up to themselves; every index key up to the chunk's end is read once,
    the chunk's index queries once. (The scores themselves stay on the chip
    in a fused form: not counted.)"""
    c = counts(hf)
    m = dims(hf)
    offset, tokens = int(offset), int(tokens)
    end = offset + tokens
    scored = end * (end + 1) // 2 - offset * (offset + 1) // 2
    bytes_ = (c["index_bytes_per_row"] * end
              + 2 * m["J"] * m["Di"] * m["L"] * tokens)
    return c["index_flops_per_key"] * scored, bytes_


def chunk_attend_work(hf, offset, tokens):
    """(FLOPs, bytes) the attention of a prompt chunk needs, all layers: each
    query attends min(position + 1, topk) rows; every k/v row up to the
    chunk's end is read once (late queries choose among all of them), the
    chunk's q once and its output written once."""
    c = counts(hf)
    m = dims(hf)
    offset, tokens, k = int(offset), int(tokens), c["rows_kept"]
    end = offset + tokens

    def upto(n):  # sum over t < n of min(t + 1, k)
        f = min(n, k)
        return f * (f + 1) // 2 + (n - f) * k

    bytes_ = (c["kv_bytes_per_row"] * end
              + 2 * 2 * m["h"] * m["dh"] * m["L"] * tokens)
    return c["attn_flops_per_key"] * (upto(end) - upto(offset)), bytes_


def token_flops(hf, context):
    """Required forward FLOPs for one token at context ``context``: its active
    matmul parameters, ``context`` rows scored and min(context, topk) attended."""
    c = counts(hf)
    context = int(context)
    return (2 * c["matmul_params_per_token"] + c["index_flops_per_key"] * context
            + c["attn_flops_per_key"] * min(context, c["rows_kept"]))


def prefill_attn_flops(hf, n):
    """Required indexer and attention FLOPs of a fresh prompt of ``n`` tokens:
    token t scores t + 1 rows and attends min(t + 1, topk)."""
    c = counts(hf)
    n, k = int(n), c["rows_kept"]
    scored = n * (n + 1) // 2
    m = min(n, k)
    attended = m * (m + 1) // 2 + (n - m) * k
    return c["index_flops_per_key"] * scored + c["attn_flops_per_key"] * attended
