"""EvaByte (byte-level, EVA attention, multi-byte prediction heads) for the
benchmark.

Four things, all independent of the program under test:

* ``make_weights``: the model's weights from a seed, on the device, in one
  jitted call, in the type they are served in (phi and mu included).
* ``reference_logits``: the plain forward pass in float32 at ``highest``
  matmul precision, layer by layer, no cache, no kernels, no batching, in
  query blocks so that 18,048 positions fit beside the weights.
* ``counts``, ``token_flops``, ``prefill_attn_flops``: parameters, FLOPs and
  bytes from shapes alone.
* ``to_program``: the one place that knows the program's names for the
  weights; it renames and offsets, and copies nothing large.

The layer, per head, with W = ``window_size``, C = ``chunk_size``,
s = head_dim^-0.5, q, k rotated by rope (``rope_theta``, the whole head, the
half-split convention) before anything else, phi and mu the head's two
learned vectors:

  pooled row of chunk c (positions cC .. cC + C - 1):
      a_j = softmax over the chunk's j of (s k_j . phi)
      K~_c = sum_j a_j k_j + mu          V~_c = sum_j a_j v_j
  query i, in window w = i // W:
      exact set   E_i = { j : wW <= j <= i }
      pooled set  R_i = { c : (c + 1) C <= wW }
      o_i = softmax over E_i and R_i TOGETHER of (s q_i . k_j | s q_i . K~_c)
            applied to (v_j | V~_c)
  block: x float32; x += W_o o(RMSNorm(x)); x += W_down(silu(W_gate h) * W_up h),
         h = RMSNorm(x); logits float32 = RMSNorm(x) W_head, (num_pred_heads, V):
         head 0 is the next byte, head m the byte m + 1 ahead.

This is EVA (Zheng et al., arXiv:2302.04542: the local set exact, each
remote chunk one control-variate row with a pooled key and a self-normalised
value) in the deterministic form of the released EvaByte model. Departures
from, and gaps in, the published description:

* The catalog's ``config.json`` gives W, C, the heads and every width. It
  does not give the pooling's form: the softmax-of-(k . phi) pooling with an
  additive mu on the pooled key is this file's reading of the released
  code's ``adaptive_phi`` / ``adaptive_mu_k`` (configuration file,
  ``assumed.pooling``).
* The released model draws random-feature noise in training; inference, and
  this file, use the deterministic form above (no noise term).
* The initial law of phi and mu is not published: N(0, 1) and N(0, 0.5^2)
  here (``assumed.phi_mu``), wide enough that the pooling weights and the
  pooled keys' offset move the logits by far more than the comparison's
  tolerance.
* ``norm_add_unit_offset``: the published RMSNorm multiplies by 1 + g. The
  weights here hold the whole multiplier G = 1 + g (drawn 1 + 0.1 N(0,1));
  ``to_program`` hands the program g = G - 1.
* ``mixedp_attn`` / ``fp32_ln`` say in which precision the released code
  runs parts of the layer; the reference is float32 throughout.
* The released model's pooled rows take no rope of their own beyond what
  their member keys carry; same here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.harness import weights

KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "window_size", "chunk_size", "num_pred_heads")

PHI_STD, MU_STD = 1.0, 0.5


def dims(hf):
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    if hf["num_key_value_heads"] != h:
        raise SystemExit("evabyte: num_key_value_heads must equal num_attention_heads")
    return dict(V=hf["vocab_size"], d=d, f=hf["intermediate_size"],
                L=hf["num_hidden_layers"], h=h, dh=d // h,
                W=hf["window_size"], C=hf["chunk_size"], P=hf["num_pred_heads"])


# ---------------------------------------------------------------- weights

def weight_shapes(hf):
    m = dims(hf)
    L, d, f, h, dh, V, P = m["L"], m["d"], m["f"], m["h"], m["dh"], m["V"], m["P"]
    out = (2 * L) ** -0.5
    # name: (shape, std or None for a norm gain)
    return {
        "embed": ((V, d), 0.02),
        "attn_norm": ((L, d), None),
        "wq": ((L, d, h * dh), d ** -0.5),
        "wk": ((L, d, h * dh), d ** -0.5),
        "wv": ((L, d, h * dh), d ** -0.5),
        "wo": ((L, h * dh, d), out * (h * dh) ** -0.5),
        "phi": ((L, h, dh), PHI_STD),
        "mu": ((L, h, dh), MU_STD),
        "mlp_norm": ((L, d), None),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), out * f ** -0.5),
        "final_norm": ((d,), None),
        # head m's columns are [m V, (m + 1) V)
        "lm_head": ((d, P * V), d ** -0.5),
    }


def make_weights(hf, seed, dtype=jnp.bfloat16, shardings=None):
    """All weights (``x @ W`` orientation, stacked over layers) from ``seed``."""
    return weights.make(weight_shapes(hf), seed, dtype, shardings)


def to_program(w):
    """The program's parameter tree (``shellac_tpu.models.transformer``).

    Its RMSNorm multiplies by ``1 + scale``, which is the published form
    (``norm_add_unit_offset``); the weights here hold the whole multiplier.
    """
    off = lambda g: (g.astype(jnp.float32) - 1.0).astype(g.dtype)
    layer = {k: w[k] for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
    layer["attn_norm"] = off(w["attn_norm"])
    layer["mlp_norm"] = off(w["mlp_norm"])
    layer["eva_phi"], layer["eva_mu"] = w["phi"], w["mu"]
    return {"embed": w["embed"], "layers": layer,
            "final_norm": off(w["final_norm"]), "lm_head": w["lm_head"]}


def program_config(hf):
    """The published keys, for ``shellac_tpu.models.convert.config_from_hf``
    (``model_type: evabyte``)."""
    return {"hf_config": dict(hf)}


# -------------------------------------------------------------- reference

def _rms(x, g, eps):
    v = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * g


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


def _pool(k, v, phi, mu, scale, chunk, quant):
    """Pooled (K~, V~), (S // C, H, D) each, of every whole chunk of k, v (S, H, D)."""
    n = k.shape[0] // chunk
    kc = k[: n * chunk].reshape(n, chunk, *k.shape[1:])
    vc = v[: n * chunk].reshape(n, chunk, *v.shape[1:])
    a = jax.nn.softmax(
        jnp.einsum("cjhd,hd->cjh", _q(kc, quant), _q(phi, quant)) * scale, axis=1)
    a = _q(a, quant)
    return (jnp.einsum("cjh,cjhd->chd", a, _q(kc, quant)) + mu,
            jnp.einsum("cjh,cjhd->chd", a, _q(vc, quant)))


def _attend(q, k, v, kp, vp, scale, window, chunk, quant, qblock=512):
    """EVA attention of one sequence: q, k, v (S, H, D), pooled rows kp, vp
    (S // C, H, D). Query blocks that never straddle a window."""
    s = q.shape[0]
    qblock = min(qblock, window)
    while window % qblock:
        qblock -= 1
    cpos = (jnp.arange(kp.shape[0]) + 1) * chunk       # first position past chunk c
    q, k, v, kp, vp = (_q(a, quant) for a in (q, k, v, kp, vp))
    outs = []
    for lo in range(0, s, qblock):
        qb = q[lo:lo + qblock]
        start = (lo // window) * window                # the block's window
        hi = min(start + window, s)
        qpos = lo + jnp.arange(qb.shape[0])
        kpos = start + jnp.arange(hi - start)
        se = jnp.einsum("qhd,khd->hqk", qb, k[start:hi]) * scale
        se = jnp.where((kpos[None, :] <= qpos[:, None])[None], se, -jnp.inf)
        sp = jnp.einsum("qhd,chd->hqc", qb, kp) * scale
        sp = jnp.where((cpos <= start)[None, None, :], sp, -jnp.inf)
        p = _q(jax.nn.softmax(jnp.concatenate([se, sp], axis=-1), axis=-1), quant)
        ne = se.shape[-1]
        outs.append(jnp.einsum("hqk,khd->qhd", p[..., :ne], v[start:hi])
                    + jnp.einsum("hqc,chd->qhd", p[..., ne:], vp))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("hf_t", "quant"))
def _layer(x, lw, hf_t, quant=None):
    hf = dict(hf_t)
    m = dims(hf)
    h, dh, eps = m["h"], m["dh"], hf["rms_norm_eps"]
    f32 = lambda a: _q(a.astype(jnp.float32), quant)
    s = x.shape[0]
    pos = jnp.arange(s)
    scale = dh ** -0.5
    hx = _q(_rms(x, lw["attn_norm"].astype(jnp.float32), eps), quant)
    q = (hx @ f32(lw["wq"])).reshape(s, h, dh)
    k = (hx @ f32(lw["wk"])).reshape(s, h, dh)
    v = (hx @ f32(lw["wv"])).reshape(s, h, dh)
    q, k = _rope_half(q, pos, hf["rope_theta"]), _rope_half(k, pos, hf["rope_theta"])
    kp, vp = _pool(k, v, lw["phi"].astype(jnp.float32), lw["mu"].astype(jnp.float32),
                   scale, m["C"], quant)
    o = _attend(q, k, v, kp, vp, scale, m["W"], m["C"], quant)
    x = x + _q(o.reshape(s, h * dh), quant) @ f32(lw["wo"])
    hx = _q(_rms(x, lw["mlp_norm"].astype(jnp.float32), eps), quant)
    act = jax.nn.silu(hx @ f32(lw["w_gate"])) * (hx @ f32(lw["w_up"]))
    return x + _q(act, quant) @ f32(lw["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, g, lm_head, eps, quant=None):
    hx = _q(_rms(x, g.astype(jnp.float32), eps), quant)
    return hx @ _q(lm_head.astype(jnp.float32), quant)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "phi", "mu", "mlp_norm",
              "w_gate", "w_up", "w_down")


def reference_logits(hf, w, tokens, positions, quant=None, all_heads=False):
    """float32 logits of one sequence ``tokens`` (S,) at ``positions``:
    (len(positions), V) of head 0, the next byte, which is all that greedy
    serving reads; with ``all_heads`` (len(positions), num_pred_heads, V).

    ``quant`` (a function on float32 arrays) rounds every matmul operand; it
    is how the control computes the same pass in a lower precision.
    """
    hf_t = tuple(sorted((k, hf[k]) for k in hf if k in KEYS))
    v, p = hf["vocab_size"], hf["num_pred_heads"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        for l in range(hf["num_hidden_layers"]):
            x = _layer(x, {k: w[k][l] for k in LAYER_KEYS}, hf_t, quant)
        head = w["lm_head"] if all_heads else w["lm_head"][:, :v]
        logits = _head(x[positions], w["final_norm"], head, hf["rms_norm_eps"], quant)
    return logits.reshape(-1, p, v) if all_heads else logits


# ------------------------------------------------------------------ counts

def counts(hf):
    """Parameters, and FLOPs/bytes as functions of shapes. No measurement."""
    m = dims(hf)
    L, d, f, h, dh, V, P = m["L"], m["d"], m["f"], m["h"], m["dh"], m["V"], m["P"]
    layer = 4 * d * h * dh + 3 * d * f
    matmul = L * layer + d * V              # served: head 0 only; embedding is a gather
    return {
        "layer_params": layer + 2 * d + 2 * h * dh,
        "layer_matmul_params": layer,
        "params": L * (layer + 2 * d + 2 * h * dh) + V * d + d + d * P * V,
        "matmul_params_per_token": matmul,
        # a token attending one row (exact or pooled): QK^T and PV
        "attn_flops_per_key": 4 * h * dh * L,
        # one pooled row: C scores against phi, C-term weighted sums of k and v
        "pool_flops_per_chunk": 6 * h * dh * m["C"] * L,
        # one exact row or one pooled row: k and v, bf16
        "kv_bytes_per_row": 2 * h * dh * 2 * L,
        "weight_bytes_per_tick": 2 * (L * layer + d * V),   # bf16, every read matrix once
    }


def attended_rows(hf, context):
    """(exact rows, pooled rows) that a token at position ``context`` attends."""
    w, c = hf["window_size"], hf["chunk_size"]
    context = int(context)
    return context % w + 1, (context // w) * (w // c)


def token_flops(hf, context):
    """Required forward FLOPs for one token at context ``context``: the
    matmuls, its exact and pooled rows, and the pooling's own FLOPs spread
    over the chunk's tokens."""
    c = counts(hf)
    exact, pooled = attended_rows(hf, context)
    return (2 * c["matmul_params_per_token"] + c["attn_flops_per_key"] * (exact + pooled)
            + c["pool_flops_per_chunk"] / hf["chunk_size"])


def prefill_attn_flops(hf, n):
    """Required attention FLOPs of a fresh prompt of ``n`` tokens: every
    token's exact and pooled rows, and one pooling per whole chunk."""
    c = counts(hf)
    w, ch = hf["window_size"], hf["chunk_size"]
    full, rest = divmod(int(n), w)
    exact = full * w * (w + 1) // 2 + rest * (rest + 1) // 2
    pooled = (w // ch) * (w * full * (full - 1) // 2 + rest * full)
    return c["attn_flops_per_key"] * (exact + pooled) + c["pool_flops_per_chunk"] * (n // ch)
