"""DeepSeek-V2 (multi-head latent attention, routed + shared experts).

Same three parts as ``mistral.py``: weights from a seed, the plain float32
reference, and counts from shapes. The reference follows the published
architecture (DeepSeek-V2 paper, sections 2.1 and 2.2, and the released
``modeling_deepseek.py``):

* attention: queries ``h W_q`` (no query low-rank here: ``q_lora_rank`` is
  null), split per head into a no-position part (128) and a rotary part
  (64); ``h W_kv_a`` gives the latent (512, RMS-normed) and one shared
  rotary key (64); keys and values re-expand from the latent per head;
  rotary pairs are ADJACENT elements (complex form); softmax scale
  ``(128 + 64) ** -0.5``.
* rotary frequencies: YaRN (factor 40 over 4096 original positions),
  whose cos/sin factor is mscale(40, 0.707) / mscale(40, 0.707) = 1.
* layer 0: dense SwiGLU (10944). Later layers: softmax router over 64
  experts, the 6 largest probabilities kept UN-normalised
  (``norm_topk_prob`` false, ``routed_scaling_factor`` 1) as weights of
  their experts' SwiGLU (1408), plus two shared experts as one SwiGLU of
  width 2816 applied to every token.

Departure, noted: the released code multiplies the softmax scale by
mscale(40, 0.707) ** 2 = 1.59 when YaRN is on; the ``transformers`` port
of DeepSeek-V2 does not, and neither does the program (which was checked
against that port). The reference follows the port so that it describes
the same function as the program; with random weights the factor changes
no cost. ``PERF.md`` lists it under Open questions.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.harness import weights
import numpy as np

KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "rms_norm_eps", "rope_theta", "kv_lora_rank",
        "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
        "moe_intermediate_size", "first_k_dense_replace", "routed_scaling_factor",
        "norm_topk_prob", "max_position_embeddings")


def dims(hf):
    if hf.get("q_lora_rank") is not None or hf.get("moe_layer_freq", 1) != 1 \
            or hf["first_k_dense_replace"] < 1:
        raise ValueError("this reference writes DeepSeek-V2 without query low-rank, "
                         "with leading dense layers and every later layer routed")
    L, k = hf["num_hidden_layers"], hf["first_k_dense_replace"]
    return dict(V=hf["vocab_size"], d=hf["hidden_size"], f=hf["intermediate_size"],
                L=L, Ld=k, Lm=L - k, h=hf["num_attention_heads"],
                r=hf["kv_lora_rank"], nope=hf["qk_nope_head_dim"],
                rope=hf["qk_rope_head_dim"], vd=hf["v_head_dim"],
                E=hf["n_routed_experts"], k=hf["num_experts_per_tok"],
                fe=hf["moe_intermediate_size"],
                fs=hf["n_shared_experts"] * hf["moe_intermediate_size"])


# ---------------------------------------------------------------- weights

def _attn_shapes(m, n, out):
    d, h, r = m["d"], m["h"], m["r"]
    return {
        "attn_norm": ((n, d), None),
        "wq": ((n, d, h * (m["nope"] + m["rope"])), d ** -0.5),
        "wkv_a": ((n, d, r + m["rope"]), d ** -0.5),
        "kv_a_norm": ((n, r), None),
        "wkv_b_k": ((n, r, h, m["nope"]), r ** -0.5),
        "wkv_b_v": ((n, r, h, m["vd"]), r ** -0.5),
        "wo": ((n, h * m["vd"], d), out * (h * m["vd"]) ** -0.5),
        "mlp_norm": ((n, d), None),
    }


def weight_shapes(hf):
    m = dims(hf)
    d, f, E, fe, fs, V = m["d"], m["f"], m["E"], m["fe"], m["fs"], m["V"]
    out = (2 * m["L"]) ** -0.5
    dense = _attn_shapes(m, m["Ld"], out)
    dense.update({"w_gate": ((m["Ld"], d, f), d ** -0.5),
                  "w_up": ((m["Ld"], d, f), d ** -0.5),
                  "w_down": ((m["Ld"], f, d), out * f ** -0.5)})
    moe = _attn_shapes(m, m["Lm"], out)
    moe.update({"w_router": ((m["Lm"], d, E), d ** -0.5),
                "w_gate": ((m["Lm"], E, d, fe), d ** -0.5),
                "w_up": ((m["Lm"], E, d, fe), d ** -0.5),
                "w_down": ((m["Lm"], E, fe, d), out * fe ** -0.5),
                "w_gate_shared": ((m["Lm"], d, fs), d ** -0.5),
                "w_up_shared": ((m["Lm"], d, fs), d ** -0.5),
                "w_down_shared": ((m["Lm"], fs, d), out * fs ** -0.5)})
    shapes = {"embed": ((V, d), 0.02), "final_norm": ((d,), None),
              "lm_head": ((d, V), d ** -0.5)}
    shapes.update({"dense." + k: v for k, v in dense.items()})
    shapes.update({"moe." + k: v for k, v in moe.items()})
    return shapes


def make_weights(hf, seed, dtype=jnp.bfloat16, shardings=None):
    """All weights (``x @ W`` orientation, stacked over layers) from ``seed``."""
    return weights.make(weight_shapes(hf), seed, dtype, shardings)


_NORMS = ("attn_norm", "kv_a_norm", "mlp_norm")


def to_program(w):
    off = lambda g: (g.astype(jnp.float32) - 1.0).astype(g.dtype)
    stacks = {"dense": {}, "moe": {}}
    for name, a in w.items():
        if "." in name:
            stack, key = name.split(".", 1)
            stacks[stack][key] = off(a) if key in _NORMS else a
    return {"embed": w["embed"], "layers": stacks,
            "final_norm": off(w["final_norm"]), "lm_head": w["lm_head"]}


def program_config(hf):
    """An object shaped like the published config, for the program's own
    ``models.convert.config_from_hf`` (the path a real checkpoint takes)."""
    return {"hf_config": {k: v for k, v in hf.items()}}


# -------------------------------------------------------------- reference

def yarn_inv_freq(dim, base, rs):
    """YaRN inverse frequencies and the cos/sin factor (Peng et al. 2023,
    as DeepSeek-V2 uses it)."""
    factor, orig = rs["factor"], rs["original_max_position_embeddings"]

    def mscale(s, m):
        return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0

    if rs.get("mscale") and rs.get("mscale_all_dim"):
        att = mscale(factor, rs["mscale"]) / mscale(factor, rs["mscale_all_dim"])
    else:
        att = mscale(factor, 1.0)

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs.get("beta_fast", 32))), 0)
    high = min(math.ceil(corr(rs.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    pos = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    inv = (1.0 / (factor * pos)) * ramp + (1.0 / pos) * (1.0 - ramp)
    return jnp.asarray(inv, jnp.float32), float(att)


def _rms(x, g, eps):
    v = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(v + eps) * g


def _rope_pairs(x, cos, sin):
    """x: (S, H, D); rotate adjacent pairs (x[2i], x[2i+1])."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(x.shape)


def _q(x, quant):
    return x if quant is None else quant(x)


def _attention(x, lw, hf, quant):
    m = dims(hf)
    h, r, nope, rope, vd = m["h"], m["r"], m["nope"], m["rope"], m["vd"]
    eps = hf["rms_norm_eps"]
    f32 = lambda a: _q(a.astype(jnp.float32), quant)
    s = x.shape[0]
    rs = hf.get("rope_scaling")
    if rs:
        inv, att = yarn_inv_freq(rope, hf["rope_theta"], dict(rs))
    else:
        inv, att = 1.0 / (hf["rope_theta"] ** (jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)), 1.0
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang) * att, jnp.sin(ang) * att

    hx = _q(_rms(x, lw["attn_norm"].astype(jnp.float32), eps), quant)
    q = (hx @ f32(lw["wq"])).reshape(s, h, nope + rope)
    q_nope, q_pe = q[..., :nope], _rope_pairs(q[..., nope:], cos, sin)
    ckv = hx @ f32(lw["wkv_a"])
    c = _q(_rms(ckv[:, :r], lw["kv_a_norm"].astype(jnp.float32), eps), quant)
    k_pe = _rope_pairs(ckv[:, None, r:], cos, sin)                 # (S, 1, rope)
    k_nope = jnp.einsum("sr,rhn->shn", c, f32(lw["wkv_b_k"]))
    v = jnp.einsum("sr,rhv->shv", c, f32(lw["wkv_b_v"]))
    qf = _q(jnp.concatenate([q_nope, q_pe], axis=-1), quant)
    kf = _q(jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (s, h, rope))], axis=-1), quant)
    v = _q(v, quant)
    scale = (nope + rope) ** -0.5
    kpos = jnp.arange(s)
    outs = []
    for lo in range(0, s, 512):
        qb = qf[lo:lo + 512]
        qpos = lo + jnp.arange(qb.shape[0])
        sc = jnp.einsum("qhd,khd->hqk", qb, kf) * scale
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khv->qhv", jax.nn.softmax(sc, axis=-1), v))
    o = jnp.concatenate(outs, axis=0).reshape(s, h * vd)
    return x + _q(o, quant) @ f32(lw["wo"])


def _swiglu(hx, wg, wu, wd, quant):
    act = jax.nn.silu(hx @ wg) * (hx @ wu)
    return _q(act, quant) @ wd


@functools.partial(jax.jit, static_argnames=("hf_t", "quant"))
def _dense_layer(x, lw, hf_t, quant=None):
    hf = _unfreeze(hf_t)
    f32 = lambda a: _q(a.astype(jnp.float32), quant)
    x = _attention(x, lw, hf, quant)
    hx = _q(_rms(x, lw["mlp_norm"].astype(jnp.float32), hf["rms_norm_eps"]), quant)
    return x + _swiglu(hx, f32(lw["w_gate"]), f32(lw["w_up"]), f32(lw["w_down"]), quant)


@functools.partial(jax.jit, static_argnames=("hf_t", "quant"))
def _moe_layer(x, lw, hf_t, quant=None):
    hf = _unfreeze(hf_t)
    m = dims(hf)
    f32 = lambda a: _q(a.astype(jnp.float32), quant)
    x = _attention(x, lw, hf, quant)
    hx = _q(_rms(x, lw["mlp_norm"].astype(jnp.float32), hf["rms_norm_eps"]), quant)
    # The router is float32 in the published model whatever the rest runs in.
    probs = jax.nn.softmax(hx @ lw["w_router"].astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(probs, m["k"])
    if hf.get("norm_topk_prob"):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * hf.get("routed_scaling_factor", 1.0)
    gate = jnp.zeros_like(probs).at[jnp.arange(x.shape[0])[:, None], idx].set(top)

    def one(acc, ew):
        wg, wu, wd, g = ew
        return acc + g[:, None] * _swiglu(hx, f32(wg), f32(wu), f32(wd), quant), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T))
    shared = _swiglu(hx, f32(lw["w_gate_shared"]), f32(lw["w_up_shared"]),
                     f32(lw["w_down_shared"]), quant)
    return x + routed + shared


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, g, lm_head, eps, quant=None):
    hx = _q(_rms(x, g.astype(jnp.float32), eps), quant)
    return hx @ _q(lm_head.astype(jnp.float32), quant)


def _freeze(v):
    return tuple(sorted((k, _freeze(x)) for k, x in v.items())) if isinstance(v, dict) else v


def _unfreeze(t):
    return {k: (dict(v) if isinstance(v, tuple) else v) for k, v in t}


def reference_logits(hf, w, tokens, positions, quant=None):
    hf_t = _freeze({k: hf[k] for k in hf if k in KEYS or k in ("rope_scaling", "moe_layer_freq")})
    m = dims(hf)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        for stack, n, fn in (("dense", m["Ld"], _dense_layer), ("moe", m["Lm"], _moe_layer)):
            keys = [k.split(".", 1)[1] for k in w if k.startswith(stack + ".")]
            for l in range(n):
                x = fn(x, {k: w[stack + "." + k][l] for k in keys}, hf_t, quant)
        return _head(x[positions], w["final_norm"], w["lm_head"],
                     hf["rms_norm_eps"], quant)


# ------------------------------------------------------------------ counts

def counts(hf):
    m = dims(hf)
    d, h, r, V = m["d"], m["h"], m["r"], m["V"]
    attn = d * h * (m["nope"] + m["rope"]) + d * (r + m["rope"]) \
        + r * h * m["nope"] + r * h * m["vd"] + h * m["vd"] * d
    expert = 3 * d * m["fe"]
    dense_layer = attn + 3 * d * m["f"]
    moe_layer = attn + d * m["E"] + m["E"] * expert + 3 * d * m["fs"]
    moe_active = attn + d * m["E"] + m["k"] * expert + 3 * d * m["fs"]
    held = m["Ld"] * dense_layer + m["Lm"] * moe_layer + d * V
    active = m["Ld"] * dense_layer + m["Lm"] * moe_active + d * V
    return {
        "layer_params": moe_layer + 2 * d + r,
        "layer_matmul_params": moe_layer,
        "dense_layer_matmul_params": dense_layer,
        "params": held + V * d + (2 * m["L"] + 1) * d + m["L"] * r,
        "matmul_params_per_token": active,
        # expanded form: scores over nope+rope, values over v_head_dim
        "attn_flops_per_key": 2 * h * (m["nope"] + m["rope"] + m["vd"]) * m["L"],
        "kv_bytes_per_token": (r + m["rope"]) * 2 * m["L"],
        "weight_bytes_per_tick": 2 * held,
    }


def token_flops(hf, context):
    c = counts(hf)
    return 2 * c["matmul_params_per_token"] + c["attn_flops_per_key"] * context


def prefill_attn_flops(hf, n):
    return counts(hf)["attn_flops_per_key"] * n * (n + 1) // 2
