"""Mistral (dense, grouped-query attention, sliding window) for the benchmark.

Three things, all independent of the program under test:

* ``make_weights``: the model's weights from a seed, on the device, in one
  jitted call, in the type they are served in.
* ``reference_logits``: the plain forward pass in float32 at ``highest``
  matmul precision, layer by layer, no cache, no kernels, no batching. It
  follows the published architecture (``modeling_mistral.py`` of the
  ``transformers`` release that introduced Mistral-7B-v0.1): pre-norm
  RMSNorm with weight ``g``, rotary embedding in the half-rotation form
  over the whole head, grouped-query attention, causal mask limited to
  ``sliding_window`` keys, SwiGLU MLP, untied output head.
* ``counts``: parameters, FLOPs and bytes from shapes alone.

``to_program`` is the one place that knows the program's names for the
weights; it renames and offsets, and copies nothing large.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.harness import weights

KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "sliding_window")


def dims(hf):
    d, h, hkv = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    return dict(V=hf["vocab_size"], d=d, f=hf["intermediate_size"],
                L=hf["num_hidden_layers"], h=h, hkv=hkv,
                dh=hf.get("head_dim") or d // h)


# ---------------------------------------------------------------- weights

def weight_shapes(hf):
    m = dims(hf)
    L, d, f, h, hkv, dh, V = m["L"], m["d"], m["f"], m["h"], m["hkv"], m["dh"], m["V"]
    out = (2 * L) ** -0.5
    # name: (shape, std or None for a norm gain)
    return {
        "embed": ((V, d), 0.02),
        "attn_norm": ((L, d), None),
        "wq": ((L, d, h * dh), d ** -0.5),
        "wk": ((L, d, hkv * dh), d ** -0.5),
        "wv": ((L, d, hkv * dh), d ** -0.5),
        "wo": ((L, h * dh, d), out * (h * dh) ** -0.5),
        "mlp_norm": ((L, d), None),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), out * f ** -0.5),
        "final_norm": ((d,), None),
        "lm_head": ((d, V), d ** -0.5),
    }


def make_weights(hf, seed, dtype=jnp.bfloat16, shardings=None):
    """All weights (``x @ W`` orientation, stacked over layers) from ``seed``."""
    return weights.make(weight_shapes(hf), seed, dtype, shardings)


def to_program(w):
    """The program's parameter tree (``shellac_tpu.models.transformer``).

    Its RMSNorm multiplies by ``1 + scale``; the published one by ``g``.
    """
    off = lambda g: (g.astype(jnp.float32) - 1.0).astype(g.dtype)
    layer = {k: w[k] for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")}
    layer["attn_norm"] = off(w["attn_norm"])
    layer["mlp_norm"] = off(w["mlp_norm"])
    return {"embed": w["embed"], "layers": layer,
            "final_norm": off(w["final_norm"]), "lm_head": w["lm_head"]}


def program_config(hf):
    """Keys of ``shellac_tpu.config.ModelConfig`` for this configuration."""
    m = dims(hf)
    return dict(vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"],
                n_kv_heads=m["hkv"], head_dim=m["dh"], d_ff=m["f"],
                rope_theta=float(hf["rope_theta"]), norm_eps=hf["rms_norm_eps"],
                tie_embeddings=False, attn_window=hf.get("sliding_window"))


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


def _attend(q, k, v, scale, window, qblock=512):
    """Causal softmax attention, (S, H, D) each, query blocks to bound memory."""
    s = q.shape[0]
    kpos = jnp.arange(s)
    outs = []
    for lo in range(0, s, qblock):
        qb = q[lo:lo + qblock]
        qpos = lo + jnp.arange(qb.shape[0])
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        ok = kpos[None, :] <= qpos[:, None]
        if window is not None:
            ok &= kpos[None, :] > qpos[:, None] - window
        sc = jnp.where(ok[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def _q(x, quant):
    return x if quant is None else quant(x)


@functools.partial(jax.jit, static_argnames=("hf_t", "quant"))
def _layer(x, lw, hf_t, quant=None):
    hf = dict(hf_t)
    m = dims(hf)
    h, hkv, dh, eps = m["h"], m["hkv"], m["dh"], hf["rms_norm_eps"]
    f32 = lambda a: _q(a.astype(jnp.float32), quant)
    s = x.shape[0]
    pos = jnp.arange(s)
    hx = _q(_rms(x, lw["attn_norm"].astype(jnp.float32), eps), quant)
    q = (hx @ f32(lw["wq"])).reshape(s, h, dh)
    k = (hx @ f32(lw["wk"])).reshape(s, hkv, dh)
    v = (hx @ f32(lw["wv"])).reshape(s, hkv, dh)
    q, k = _rope_half(q, pos, hf["rope_theta"]), _rope_half(k, pos, hf["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    o = _attend(_q(q, quant), _q(k, quant), _q(v, quant), dh ** -0.5,
                hf.get("sliding_window"))
    x = x + _q(o.reshape(s, h * dh), quant) @ f32(lw["wo"])
    hx = _q(_rms(x, lw["mlp_norm"].astype(jnp.float32), eps), quant)
    act = jax.nn.silu(hx @ f32(lw["w_gate"])) * (hx @ f32(lw["w_up"]))
    return x + _q(act, quant) @ f32(lw["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, g, lm_head, eps, quant=None):
    hx = _q(_rms(x, g.astype(jnp.float32), eps), quant)
    return hx @ _q(lm_head.astype(jnp.float32), quant)


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up",
              "w_down")


def reference_logits(hf, w, tokens, positions, quant=None):
    """float32 logits (len(positions), V) of one sequence ``tokens`` (S,).

    ``quant`` (a function on float32 arrays) rounds every matmul operand; it
    is how the control computes the same pass in a lower precision.
    """
    hf_t = tuple(sorted((k, hf[k]) for k in hf if k in KEYS or k == "head_dim"))
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        for l in range(hf["num_hidden_layers"]):
            x = _layer(x, {k: w[k][l] for k in LAYER_KEYS}, hf_t, quant)
        return _head(x[positions], w["final_norm"], w["lm_head"],
                     hf["rms_norm_eps"], quant)


# ------------------------------------------------------------------ counts

def counts(hf):
    """Parameters, and FLOPs/bytes as functions of shapes. No measurement."""
    m = dims(hf)
    L, d, f, h, hkv, dh, V = m["L"], m["d"], m["f"], m["h"], m["hkv"], m["dh"], m["V"]
    layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    matmul = L * layer + d * V              # the embedding table is a gather
    return {
        "layer_params": layer + 2 * d,
        "layer_matmul_params": layer,
        "params": matmul + V * d + (2 * L + 1) * d,
        "matmul_params_per_token": matmul,
        # a token attending c keys: QK^T and PV, 2 FLOPs a multiply-add
        "attn_flops_per_key": 4 * h * dh * L,
        "kv_bytes_per_token": 2 * hkv * dh * 2 * L,
        "weight_bytes_per_tick": 2 * matmul,   # bf16, every held matrix once
    }


def token_flops(hf, context):
    """Required forward FLOPs for one token that attends ``context`` keys."""
    c = counts(hf)
    w = hf.get("sliding_window")
    keys = context if w is None else min(context, w)
    return 2 * c["matmul_params_per_token"] + c["attn_flops_per_key"] * keys


def prefill_attn_flops(hf, n):
    """Required causal attention FLOPs of a fresh prompt of ``n`` tokens."""
    c = counts(hf)
    return c["attn_flops_per_key"] * n * (n + 1) // 2
