"""Ouro (a looped language model: one dense stack run several times a token
over shared weights) for the benchmark.

Same parts as the other architecture files, all independent of the program
under test: ``make_weights`` (from a seed, on the device, as served),
``reference_logits`` (the plain forward pass in float32 at ``highest`` matmul
precision, layer by layer and pass by pass, no cache, no kernels, no
batching), ``counts`` / ``token_flops`` / ``prefill_attn_flops`` (parameters,
FLOPs and bytes from shapes alone) and ``to_program`` (the one place that knows
the program's names).

The model ("Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741, and the released ``modeling_ouro.py``). T =
``total_ut_steps``, L = ``num_hidden_layers``; RMS(x; g) = x / sqrt(mean(x^2)
+ eps) * g.

    h_0 = E[token]
    for t in 0 .. T-1:                          # the SAME L layers' weights
        x = h_t
        for l in 0 .. L-1:
            n = RMS(x; g1_l)
            q, k, v = n Wq_l, n Wk_l, n Wv_l    # rope (half-rotation, whole head) on q, k
            a = softmax(q k^T / sqrt(dh), causal) v ; a = a Wo_l
            x = x + RMS(a; g2_l)                # the branch OUTPUT is normed
            n = RMS(x; g3_l) ; m = (silu(n Wgate_l) * (n Wup_l)) Wdown_l
            x = x + RMS(m; g4_l)
        h_{t+1} = RMS(x; g_final)               # the NORMED state enters pass t + 1
        lam_t   = sigmoid(w_gate . h_{t+1} + b_gate)
    p_t = lam_t prod_{j<t} (1 - lam_j) for t < T-1 ; p_{T-1} = prod_{j<T-1} (1 - lam_j)
    s = the first t with p_0 + ... + p_t >= early_exit_threshold, else T-1
    logits = h_{s+1} W_head                     # h is already normed

Pass t of a token attends pass t's keys and values of the earlier tokens (in
the released code cached layer t * L + l); with no cache that is simply each
pass attending within itself. Every pass runs for every token whatever s is.

Departures from the released code, each a reading of this file's own
(configuration file, ``assumed``):

1. ``norm_places``: the four norms of a layer sit as above (released names
   ``input_layernorm``, ``input_layernorm_2``, ``post_attention_layernorm``,
   ``post_attention_layernorm_2``).
2. ``no_bias_no_qk_norm``: no projection has a bias and q, k take no per-head
   norm (the config has no such key).
3. ``gate``: one linear map with a bias on each pass's normed state.
4. ``exit_rule``: as above; the released generation code also offers a fixed
   exit step, which is not part of the model's equations and is not written.
5. ``weights``: seeded, not released (see ``weight_shapes``: an embedding of
   unit RMS, and the residual branches' scale, (2 L T)^-0.5, in the output
   norms' gains).

``norm_between=False`` (one test's control, nothing else) feeds pass t + 1 the
un-normed stream.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from benchmark.harness import weights

KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "head_dim", "rms_norm_eps",
        "rope_theta", "total_ut_steps", "early_exit_threshold")


def dims(hf):
    if set(hf.get("layer_types") or ()) - {"full_attention"} or \
            (hf.get("sliding_window") and hf.get("use_sliding_window")):
        raise ValueError("this reference writes every layer with full attention")
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return dict(V=hf["vocab_size"], d=d, f=hf["intermediate_size"],
                L=hf["num_hidden_layers"], h=h,
                hkv=hf.get("num_key_value_heads") or h,
                dh=hf.get("head_dim") or d // h, T=int(hf["total_ut_steps"]),
                thr=float(hf.get("early_exit_threshold", 1.0)))


# ---------------------------------------------------------------- weights

def weight_shapes(hf):
    """The other dense configuration's law (mistral.py: matrices N(0,
    1/fan_in), norm gains 1 + 0.1 N(0, 1), residual branches scaled down by the
    depth), written for a looped sandwich-normed stack, which moves two things.

    The embedding is N(0, 1), as keye_vl2.py's: every later pass enters the
    layers at unit RMS (the final norm's output), so the first must too. At
    0.02 the first branch, a tenth in size, is already five times the token's
    own row, and what tells one token from another is from then on a small
    part of every state.

    The residual scale counts every branch the stream crosses, 2 a layer, L
    layers, T passes: (2 L T)^-0.5. Under sandwich norms it cannot live in wo
    and w_down, whose output is normed: it lives in the two OUTPUT norms'
    gains, (2 L T)^-0.5 (1 + 0.1 N(0, 1)) (``make_weights``). A pass then adds
    T^-0.5 of the state's size to it and four passes as much as the state.

    Why (PERF.md, PR 33; TPU v5 lite): a random model's attention is diffuse,
    so an attention branch is close to the same vector at every position, and
    its output norm brings that vector to full size whatever its length was.
    With gains of 1 (the first law tried) or (2 L)^-0.5 over a 0.02 embedding
    (the second) the passes iterate towards ONE state: most requests then serve
    one token for ever at a gap of exactly 0 (the check sees nothing), and a
    prompt near the edge of that state's basin lands elsewhere in bfloat16
    than in float32, a whole request as far from the reference as the int8
    control (one sampled request in about forty; the driver's seed 256552127
    read logit_gap_max 3.98). Under this law a request still serves few
    distinct tokens (2 to 89 in 114 to 338), but every request reads alike:
    over 1,088 prompts on four seeds the bfloat16 state at a prompt's last
    position lies 2 % (median) to 8.7 % (most) from the reference's, where the
    second law read 2 % to 43.6 % on 320.

    The gate: weight N(0, 1/d) (a unit-RMS state gives a logit of about
    N(0, 1), so lam spreads over (0.1, 0.9) and the exit rule has something to
    decide), bias N(0, 1)."""
    m = dims(hf)
    L, d, f, h, hkv, dh, V = m["L"], m["d"], m["f"], m["h"], m["hkv"], m["dh"], m["V"]
    return {
        "embed": ((V, d), 1.0),
        "attn_norm": ((L, d), None),
        "wq": ((L, d, h * dh), d ** -0.5),
        "wk": ((L, d, hkv * dh), d ** -0.5),
        "wv": ((L, d, hkv * dh), d ** -0.5),
        "wo": ((L, h * dh, d), (h * dh) ** -0.5),
        "post_attn_norm": ((L, d), None),
        "mlp_norm": ((L, d), None),
        "w_gate": ((L, d, f), d ** -0.5),
        "w_up": ((L, d, f), d ** -0.5),
        "w_down": ((L, f, d), f ** -0.5),
        "post_mlp_norm": ((L, d), None),
        "final_norm": ((d,), None),
        "lm_head": ((d, V), d ** -0.5),
        "gate_w": ((d,), d ** -0.5),
        "gate_b": ((1,), 1.0),
    }


def make_weights(hf, seed, dtype=jnp.bfloat16, shardings=None):
    """All weights (``x @ W`` orientation, stacked over layers) from ``seed``.

    The two output norms' gains are scaled by (2 L T)^-0.5 (``weight_shapes``)
    and then moved to the nearest gain g for which g - 1 is a value of
    ``dtype``: the program holds a norm's gain as that offset, and at g = 0.05
    a bfloat16 offset rounded on its own would misstate the gain by 4 %."""
    w = dict(weights.make(weight_shapes(hf), seed, dtype, shardings))
    out = (2 * hf["num_hidden_layers"] * int(hf["total_ut_steps"])) ** -0.5

    @jax.jit
    def branch_gain(g):
        off = (g.astype(jnp.float32) * out - 1.0).astype(dtype)
        return (1.0 + off.astype(jnp.float32)).astype(dtype)

    for k in ("post_attn_norm", "post_mlp_norm"):
        w[k] = branch_gain(w[k])
    return w


NORMS = ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_KEYS = NORMS + MATRICES


def to_program(w):
    """The program's parameter tree (``shellac_tpu.models.transformer`` with
    ``post_norms`` and ``loop``). Its RMSNorm multiplies by ``1 + scale``; the
    published one by ``g``."""
    off = lambda g: (g.astype(jnp.float32) - 1.0).astype(g.dtype)
    layer = {k: w[k] for k in MATRICES}
    layer.update({k: off(w[k]) for k in NORMS})
    return {"embed": w["embed"], "layers": layer,
            "final_norm": off(w["final_norm"]), "lm_head": w["lm_head"],
            "loop_gate": {"w": w["gate_w"], "b": w["gate_b"].reshape(())}}


def program_config(hf):
    """The published keys, for the program's own
    ``models.convert.config_from_hf`` (``model_type: ouro``).

    A program without the loop must not serve another model under this name: a
    converter that does not know ``ouro`` reads these keys as a Llama-shaped
    model and runs its 48 layers once. So the checkout is asked, by its source
    text and without importing it, whether its configuration has a looped
    stack at all; where not, the run ends here, at once and with a non-zero
    exit code."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        with open(os.path.join(root, "shellac_tpu", "config.py")) as f:
            has = "class LoopConfig" in f.read()
    except OSError:
        has = False
    if not has:
        raise SystemExit(
            "ouro: the program in this checkout has no looped stack (no LoopConfig "
            "in shellac_tpu/config.py): it cannot run this configuration")
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


def _attend(q, k, v, scale, qblock=512):
    """Causal softmax attention, (S, H, D) each, query blocks to bound memory."""
    s = q.shape[0]
    kpos = jnp.arange(s)
    outs = []
    for lo in range(0, s, qblock):
        qb = q[lo:lo + qblock]
        qpos = lo + jnp.arange(qb.shape[0])
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        sc = jnp.where((kpos[None, :] <= qpos[:, None])[None], sc, -jnp.inf)
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
    gain = lambda k: lw[k].astype(jnp.float32)
    s = x.shape[0]
    pos = jnp.arange(s)
    hx = _q(_rms(x, gain("attn_norm"), eps), quant)
    q = (hx @ f32(lw["wq"])).reshape(s, h, dh)
    k = (hx @ f32(lw["wk"])).reshape(s, hkv, dh)
    v = (hx @ f32(lw["wv"])).reshape(s, hkv, dh)
    q, k = _rope_half(q, pos, hf["rope_theta"]), _rope_half(k, pos, hf["rope_theta"])
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    o = _attend(_q(q, quant), _q(k, quant), _q(v, quant), dh ** -0.5)
    a = _q(o.reshape(s, h * dh), quant) @ f32(lw["wo"])
    x = x + _rms(a, gain("post_attn_norm"), eps)
    hx = _q(_rms(x, gain("mlp_norm"), eps), quant)
    act = jax.nn.silu(hx @ f32(lw["w_gate"])) * (hx @ f32(lw["w_up"]))
    mo = _q(act, quant) @ f32(lw["w_down"])
    return x + _rms(mo, gain("post_mlp_norm"), eps)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _between(x, g, gate_w, gate_b, eps, quant=None):
    """After a pass: the normed state and the gate's lam (S,)."""
    h = _rms(x, g.astype(jnp.float32), eps)
    z = _q(h, quant) @ _q(gate_w.astype(jnp.float32)[None, :], quant)[0]
    return h, jax.nn.sigmoid(z + gate_b.astype(jnp.float32)[0])


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(h, lm_head, quant=None):
    return _q(h, quant) @ _q(lm_head.astype(jnp.float32), quant)


def exit_steps(lams, threshold):
    """The exit step of each token, (S,) int32, from the passes' lam, each
    (S,): the first t at which p_0 + ... + p_t >= threshold, the last pass
    taking what the earlier ones left, else the last."""
    left = jnp.ones_like(lams[0])
    cum = jnp.zeros_like(lams[0])
    step = jnp.full(lams[0].shape, len(lams) - 1, jnp.int32)
    done = jnp.zeros(lams[0].shape, bool)
    for t, lam in enumerate(lams):
        cum = cum + (left if t == len(lams) - 1 else lam * left)
        now = ~done & (cum >= threshold)
        step = jnp.where(now, t, step)
        done = done | now
        left = left * (1.0 - lam)
    return step


def reference_passes(hf, w, tokens, quant=None, norm_between=True):
    """Every pass of one sequence ``tokens`` (S,): the normed states h_1 ..
    h_T, each (S, d) float32, the gate's lam_0 .. lam_{T-1}, each (S,), and
    the exit step of each token."""
    m = dims(hf)
    hf_t = tuple(sorted((k, hf[k]) for k in hf if k in KEYS))
    eps = hf["rms_norm_eps"]
    states, lams = [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
        for _ in range(m["T"]):
            for l in range(m["L"]):
                x = _layer(x, {k: w[k][l] for k in LAYER_KEYS}, hf_t, quant)
            h, lam = _between(x, w["final_norm"], w["gate_w"], w["gate_b"], eps, quant)
            states.append(h)
            lams.append(lam)
            x = h if norm_between else x
    return states, lams, exit_steps(lams, m["thr"])


def reference_logits(hf, w, tokens, positions, quant=None, norm_between=True):
    """float32 logits (len(positions), V) of one sequence ``tokens`` (S,):
    each token's state at its exit step through the head.

    ``quant`` (a function on float32 arrays) rounds every matmul operand; it
    is how the control computes the same pass in a lower precision.
    """
    states, _, step = reference_passes(hf, w, tokens, quant, norm_between)
    with jax.default_matmul_precision("highest"):
        h = jnp.take_along_axis(jnp.stack(states), step[None, :, None], axis=0)[0]
        return _head(h[positions], w["lm_head"], quant)


# ------------------------------------------------------------------ counts

def counts(hf):
    """Parameters, and FLOPs/bytes as functions of shapes. No measurement.

    A token takes T passes, so its matmul work and its cached rows are T
    times one pass's. ``weight_bytes_per_tick`` counts the layers' weights T
    times: 48 layers are 4.93 GB in bfloat16, which cannot stay on the chip
    between passes (its fast memory holds a few tens of MB), so every pass of
    a tick must read them from device memory again; the head, the final norm
    and the gate are read once."""
    m = dims(hf)
    L, d, f, h, hkv, dh, V, T = (m[x] for x in ("L", "d", "f", "h", "hkv", "dh", "V", "T"))
    layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    per_pass = L * layer + d                 # the gate reads every pass's state
    matmul = T * per_pass + d * V            # the embedding table is a gather
    return {
        "layer_params": layer + 4 * d,
        "layer_matmul_params": layer,
        "params": L * (layer + 4 * d) + 2 * V * d + d + d + 1,
        "matmul_params_per_token": matmul,
        # a token attending c keys in each of T passes: QK^T and PV
        "attn_flops_per_key": 4 * h * dh * L * T,
        "kv_bytes_per_token": 2 * hkv * dh * 2 * L * T,
        "weight_bytes_per_tick": 2 * (T * L * (layer + 4 * d) + d * V + 2 * d + 1),
    }


def token_flops(hf, context):
    """Required forward FLOPs for one token that attends ``context`` keys in
    every pass."""
    c = counts(hf)
    return 2 * c["matmul_params_per_token"] + c["attn_flops_per_key"] * context


def prefill_attn_flops(hf, n):
    """Required causal attention FLOPs of a fresh prompt of ``n`` tokens."""
    c = counts(hf)
    return c["attn_flops_per_key"] * n * (n + 1) // 2
