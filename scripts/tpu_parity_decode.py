"""TPU-compiled parity gate for the Pallas kernels.

The pytest suite pins the CPU platform and runs every Pallas kernel in
interpret mode; a bug that only manifests under compiled Mosaic
layout/DMA semantics (index-map clamping, scalar prefetch, VMEM
accumulator tiling) would pass CI and ship. This script runs the SAME
parity assertions with interpret=False on the real chip:

  - dense decode: GQA, sliding window, ragged lengths (incl. 0 and
    max_len-s), s=1 and s=4
  - paged decode: shuffled block table, window, ragged lengths; the
    MHA kernel with NaN in every row no slot may read
  - training flash attention: forward + backward grads vs reference

Exits 0 and prints one JSON line {"ok": true, ...} on success; any
mismatch raises; without a TPU it exits 2 and checks nothing. Driven by
chip_smoke.py's kernels phase (in-process, through run_all).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _normal(key, shape, dtype=jnp.float32):
    """Standard-normal test data made on the host from a jax key: on
    the chip jax.random.normal compiles a program per shape (seconds
    each), which is set-up, not what this gate checks."""
    seed = np.asarray(jax.random.key_data(key)).ravel().tolist()
    return jnp.asarray(
        np.random.default_rng(seed).standard_normal(shape, np.float32), dtype
    )


def _jitted(fn):
    """fn as ONE compiled program per call: array arguments are traced,
    everything else (window sizes, impl names, flags) is closed over.
    Called eagerly, every jnp op inside a dispatcher or a reference is
    its own compile, and on the chip those add up to minutes."""
    def call(*args, **kw):
        def is_array(v):
            return isinstance(v, (jax.Array, np.ndarray))

        where = [is_array(a) for a in args]
        traced_kw = {k: v for k, v in kw.items() if is_array(v)}
        static_kw = {k: v for k, v in kw.items() if k not in traced_kw}

        def program(arrays, arrays_kw):
            it = iter(arrays)
            full = [next(it) if w else a for w, a in zip(where, args)]
            return fn(*full, **arrays_kw, **static_kw)

        return jax.jit(program)(
            [a for w, a in zip(where, args) if w], traced_kw
        )

    return call


def _host(x):
    """fp32 on the host (no device-side convert to compile)."""
    return np.asarray(x).astype(np.float32)


def check(name, got, want, atol, checks, rtol=None):
    np.testing.assert_allclose(
        _host(got), _host(want), atol=atol,
        rtol=rtol if rtol is not None else atol, err_msg=name,
    )
    checks.append(name)


def check_grads(label, got, want, checks, atol=3e-2):
    """dq/dk/dv against the reference's, each scaled by the reference's
    largest entry so one tolerance fits all three."""
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        a, b = _host(a), _host(b)
        scale = max(1.0, float(np.abs(b).max()))
        check(f"{label} {name}", a / scale, b / scale, atol, checks)


def dense_decode_cases(checks):
    from shellac_tpu.ops.decode_attention import _decode_ref, decode_attention

    B, L, H, HKV, D = 4, 1024, 16, 8, 128
    for s, window in [(1, None), (1, 200), (4, None), (4, 200)]:
        ks = jax.random.split(jax.random.PRNGKey(s * 13 + (window or 1)), 3)
        q = _normal(ks[0], (B, s, H, D), jnp.bfloat16)
        ck = _normal(ks[1], (B, HKV, L, D), jnp.bfloat16)
        cv = _normal(ks[2], (B, HKV, L, D), jnp.bfloat16)
        index = jnp.array([0, 37, 519, L - s], jnp.int32)
        out = _jitted(decode_attention)(
            q, ck, cv, index, window=window, impl="flash", interpret=False
        )
        ref = _jitted(_decode_ref)(q, ck, cv, index, window, D ** -0.5)
        check(
            f"dense s={s} window={window}",
            out, ref,
            atol=2e-2, checks=checks,
        )


def paged_decode_cases(checks):
    from shellac_tpu.ops.decode_attention import (
        _decode_ref,
        paged_decode_attention,
    )

    B, H, D = 4, 16, 128
    # bs=64 runs the grouped gather with 2 groups; bs=16 is the serving
    # default page size (group=32, the shape the one-page kernel lost
    # to the XLA ref on — BENCH_DECODE.json); bs=256 is the page "auto"
    # sends to the kernel (2 pages a step, 2 groups: the double buffer
    # crosses groups and slots). The last two are ouro-2.6b-batch-
    # reason's pool: MHA 16/16 over FIVE 128-row pages a slot, one query
    # row a head at s=1 (the MHA kernel; paged_mha_cases has more), and
    # at s=3, which two pages a step do not divide, the one-page kernel.
    for s, window, bs, L, HKV in [
        (1, None, 64, 1024, 8), (1, 200, 64, 1024, 8), (2, None, 64, 1024, 8),
        (1, None, 16, 1024, 8), (1, 200, 16, 1024, 8),
        (1, None, 256, 1024, 8), (3, 600, 256, 1024, 8),
        (1, None, 128, 640, 16), (3, None, 128, 640, 16),
    ]:
        max_blocks = L // bs
        n_blocks = B * max_blocks + 1
        ks = jax.random.split(jax.random.PRNGKey(s * 11 + (window or 1)), 3)
        q = _normal(ks[0], (B, s, H, D), jnp.bfloat16)
        dense_k = _normal(ks[1], (B, L, HKV, D), jnp.bfloat16)
        dense_v = _normal(ks[2], (B, L, HKV, D), jnp.bfloat16)
        index = jnp.array([0, 37, 519, L - s], jnp.int32)

        rng = np.random.default_rng(s)
        ids = rng.permutation(np.arange(1, n_blocks))
        tables = ids.reshape(B, max_blocks)
        pool_k = np.zeros((n_blocks, HKV, bs, D), np.float32)
        pool_v = np.zeros((n_blocks, HKV, bs, D), np.float32)
        # Host-side fixture construction, not a decode hot loop: the
        # transfers here build the test pools once per case.
        dk = np.asarray(dense_k, np.float32).transpose(0, 2, 1, 3)  # shellac: ignore[SH002]
        dv = np.asarray(dense_v, np.float32).transpose(0, 2, 1, 3)  # shellac: ignore[SH002]
        for b in range(B):
            for j in range(max_blocks):
                pool_k[tables[b, j]] = dk[b, :, j * bs:(j + 1) * bs]
                pool_v[tables[b, j]] = dv[b, :, j * bs:(j + 1) * bs]

        out = _jitted(paged_decode_attention)(
            q, jnp.asarray(pool_k, jnp.bfloat16),
            jnp.asarray(pool_v, jnp.bfloat16),
            jnp.asarray(tables, jnp.int32), index,
            window=window, impl="flash", interpret=False,
        )
        ref = _jitted(_decode_ref)(
            q, dense_k.transpose(0, 2, 1, 3), dense_v.transpose(0, 2, 1, 3),
            index, window, D ** -0.5,
        )
        check(
            f"paged s={s} window={window} bs={bs} L={L} hkv={HKV} "
            "shuffled-table",
            out, ref,
            atol=2e-2, checks=checks,
        )


def quant_cache_cases(checks):
    """int8 KV cache decode kernel (per-token dequant scales) compiled."""
    from shellac_tpu.inference.kvcache import quantize_kv
    from shellac_tpu.ops.decode_attention import _decode_ref, decode_attention

    B, L, H, HKV, D = 4, 1024, 16, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _normal(ks[0], (B, 1, H, D), jnp.bfloat16)
    kf = _normal(ks[1], (B, L, HKV, D), jnp.float32)
    vf = _normal(ks[2], (B, L, HKV, D), jnp.float32)
    kq, ksc = _jitted(quantize_kv)(kf)
    vq, vsc = _jitted(quantize_kv)(vf)
    ck, cv = kq.transpose(0, 2, 1, 3), vq.transpose(0, 2, 1, 3)
    kscale, vscale = ksc.transpose(0, 2, 1), vsc.transpose(0, 2, 1)
    index = jnp.array([0, 37, 519, L - 1], jnp.int32)
    for window in (None, 200):
        out = _jitted(decode_attention)(
            q, ck, cv, index, window=window, impl="flash", interpret=False,
            k_scale=kscale, v_scale=vscale,
        )
        ref = _jitted(_decode_ref)(
            q, ck, cv, index, window, D ** -0.5,
            k_scale=kscale, v_scale=vscale,
        )
        check(
            f"dense int8-kv window={window}",
            out, ref,
            atol=2e-2, checks=checks,
        )


def quant_paged_cases(checks):
    """int8 paged pool: grouped-gather kernel with scale pages, compiled."""
    from shellac_tpu.inference.cache.paged import (
        INT8_BLOCK_SIZE_DEFAULT,
        INT8_BLOCK_SIZES_RECOMMENDED,
    )
    from shellac_tpu.inference.kvcache import (
        paged_gather_layer,
        paged_gather_scales,
        quantize_kv,
    )
    from shellac_tpu.ops.decode_attention import (
        _decode_ref,
        paged_decode_attention,
    )

    H, HKV, D = 16, 8, 128
    dflt = INT8_BLOCK_SIZE_DEFAULT
    cases = [(4, 1, None, dflt, 1024), (4, 1, 200, dflt, 1024),
             (4, 2, None, dflt, 1024)]
    # Every other page size the engine's error message recommends, and
    # the default at the serve shape (8 slots x ctx 2048).
    cases += [(4, 1, None, bs, 1024)
              for bs in INT8_BLOCK_SIZES_RECOMMENDED if bs != dflt]
    cases += [(8, 1, None, dflt, 2048)]
    for B, s, window, bs, L in cases:
        mb = L // bs
        n_blocks = B * mb + 1
        ks = jax.random.split(jax.random.PRNGKey(s * 7 + (window or 1)), 3)
        q = _normal(ks[0], (B, s, H, D), jnp.bfloat16)
        kf = _normal(ks[1], (n_blocks, bs, HKV, D), jnp.float32)
        vf = _normal(ks[2], (n_blocks, bs, HKV, D), jnp.float32)
        kq, ksc = _jitted(quantize_kv)(kf)
        vq, vsc = _jitted(quantize_kv)(vf)
        pool_k = kq.transpose(0, 2, 1, 3)
        pool_v = vq.transpose(0, 2, 1, 3)
        pks = ksc.transpose(0, 2, 1)
        pvs = vsc.transpose(0, 2, 1)
        rng = np.random.default_rng(s)
        tables = jnp.asarray(
            (rng.permutation(n_blocks - 1) + 1).reshape(B, mb), jnp.int32
        )
        index = jnp.asarray(([0, 37, 519, L - s] * 2)[:B], jnp.int32)
        out = _jitted(paged_decode_attention)(
            q, pool_k, pool_v, tables, index, window=window,
            impl="flash", interpret=False, k_scale=pks, v_scale=pvs,
        )
        k_all, v_all = _jitted(paged_gather_layer)(pool_k, pool_v, tables)
        ref = _jitted(_decode_ref)(
            q, k_all, v_all, index, window, D ** -0.5,
            k_scale=_jitted(paged_gather_scales)(pks, tables),
            v_scale=_jitted(paged_gather_scales)(pvs, tables),
        )
        check(
            f"paged int8 B={B} L={L} s={s} window={window} bs={bs} "
            "shuffled-table",
            out, ref,
            atol=2e-2, checks=checks,
        )


def flash_train_cases(checks):
    from shellac_tpu.ops.attention import attention_ref
    from shellac_tpu.ops.flash_attention import flash_attention

    B, S, H, HKV, D = 2, 2048, 8, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = _normal(ks[0], (B, S, H, D), jnp.bfloat16)
    k = _normal(ks[1], (B, S, HKV, D), jnp.bfloat16)
    v = _normal(ks[2], (B, S, HKV, D), jnp.bfloat16)
    # Ragged packed documents, boundaries off block edges.
    seg = jnp.asarray(
        np.concatenate([
            np.repeat([0, 1, 2], [700, 900, 448])[None],
            np.repeat([0, 1], [1500, 548])[None],
        ]), jnp.int32,
    )

    for label, window, segments, causal in [
        ("causal GQA", None, None, True),
        ("window=600", 600, None, True),
        ("packed", None, seg, True),
        ("window=600 packed", 600, seg, True),
        ("noncausal", None, None, False),
    ]:
        def loss_flash(q, k, v):
            return jnp.sum(
                flash_attention(
                    q, k, v, causal=causal, window=window,
                    segments=segments, interpret=False,
                ) ** 2
            )

        def loss_ref(q, k, v):
            return jnp.sum(
                attention_ref(
                    q, k, v, causal=causal, window=window,
                    q_segments=segments, kv_segments=segments,
                ) ** 2
            )

        out = _jitted(flash_attention)(
            q, k, v, causal=causal, window=window, segments=segments,
            interpret=False,
        )
        ref = _jitted(attention_ref)(
            q, k, v, causal=causal, window=window,
            q_segments=segments, kv_segments=segments,
        )
        check(
            f"flash fwd {label}",
            out, ref,
            atol=2e-2, checks=checks,
        )
        gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
        check_grads(f"flash bwd {label}", gf, gr, checks)


def head_dim_64_cases(checks):
    """dh=64 (Qwen2-0.5B class) through both kernel families compiled."""
    from shellac_tpu.ops.attention import attention_ref
    from shellac_tpu.ops.decode_attention import _decode_ref, decode_attention
    from shellac_tpu.ops.flash_attention import flash_attention

    B, L, H, HKV, D = 2, 512, 8, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _normal(ks[0], (B, 1, H, D), jnp.bfloat16)
    ck = _normal(ks[1], (B, HKV, L, D), jnp.bfloat16)
    cv = _normal(ks[2], (B, HKV, L, D), jnp.bfloat16)
    index = jnp.array([33, L - 1], jnp.int32)
    out = _jitted(decode_attention)(q, ck, cv, index, impl="flash", interpret=False)
    ref = _jitted(_decode_ref)(q, ck, cv, index, None, D ** -0.5)
    check(
        "dense dh=64",
        out, ref,
        atol=2e-2, checks=checks,
    )

    S = 1024
    qf = _normal(ks[0], (B, S, H, D), jnp.bfloat16)
    kf = _normal(ks[1], (B, S, HKV, D), jnp.bfloat16)
    vf = _normal(ks[2], (B, S, HKV, D), jnp.bfloat16)
    out = _jitted(flash_attention)(qf, kf, vf, causal=True, interpret=False)
    ref = _jitted(attention_ref)(qf, kf, vf, causal=True)
    check(
        "flash fwd dh=64",
        out, ref,
        atol=2e-2, checks=checks,
    )
    gf = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=False) ** 2
        ),
        argnums=(0, 1, 2),
    ))(qf, kf, vf)
    gr = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(attention_ref(q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2),
    ))(qf, kf, vf)
    check_grads("flash bwd dh=64", gf, gr, checks)


def mla_shape_cases(checks):
    """The kernel shapes MLA routes through, compiled: decode over the
    576-wide latent (d % 128 == 64 -> whole-ref-load tile) as MQA, and
    flash fwd/bwd at qk width 192 (entry pads to 256)."""
    from shellac_tpu.ops.attention import attention_ref
    from shellac_tpu.ops.decode_attention import _decode_ref, decode_attention
    from shellac_tpu.ops.flash_attention import flash_attention

    B, L, H, D = 2, 1024, 16, 576  # latent width kv_rank 512 + rope 64
    ks = jax.random.split(jax.random.PRNGKey(13), 2)
    q = _normal(ks[0], (B, 1, H, D), jnp.bfloat16)
    lat = _normal(ks[1], (B, 1, L, D), jnp.bfloat16)
    index = jnp.array([43, L - 1], jnp.int32)
    out = _jitted(decode_attention)(q, lat, lat, index, impl="flash",
                           scale=192 ** -0.5, interpret=False)
    ref = _jitted(_decode_ref)(q, lat, lat, index, None, 192 ** -0.5)
    check("mla latent decode d=576", out, ref, atol=2e-2, checks=checks)

    # The same latent as a paged pool (deepseek-v2-lite-batch's read):
    # rows held 640 wide with zeros in the pad lanes, 20 pages of 128,
    # ONE array as k and as v (a page is copied once, v read out of the
    # k tile), 4 pages a grid step, so the double buffer crosses groups
    # and slots; pages no slot owns hold NaN.
    from shellac_tpu.inference.kvcache import held_width
    from shellac_tpu.ops.decode_attention import paged_decode_attention

    B, bs, mb = 4, 128, 20
    L, W = bs * mb, held_width(D)
    for s in (1, 3):
        ks = jax.random.split(jax.random.PRNGKey(15 + s), 2)
        q = _normal(ks[0], (B, s, H, D), jnp.bfloat16)
        lat = np.zeros((B, 1, L, W), np.float32)
        lat[..., :D] = _host(_normal(ks[1], (B, 1, L, D), jnp.bfloat16))
        index = np.array([0, 37, 1300, L - s], np.int32)
        tables = (np.random.default_rng(s).permutation(B * mb) + 1).reshape(
            B, mb)
        pool = np.full((B * mb + 1, 1, bs, W), np.nan, np.float32)
        pool[0] = 0.0
        for b in range(B):
            for j in range(-(-(index[b] + s) // bs)):
                pool[tables[b, j]] = lat[b, :, j * bs:(j + 1) * bs]
        out = _jitted(lambda q, p, t, i: paged_decode_attention(
            q, p, p, t, i, impl="flash", scale=192 ** -0.5, interpret=False,
        ))(q, jnp.asarray(pool, jnp.bfloat16), jnp.asarray(tables, jnp.int32),
           jnp.asarray(index))
        ref = _jitted(_decode_ref)(
            jnp.pad(q, ((0, 0),) * 3 + ((0, W - D),)),
            jnp.asarray(lat, jnp.bfloat16), jnp.asarray(lat, jnp.bfloat16),
            jnp.asarray(index), None, 192 ** -0.5)
        check(f"mla latent paged d=576 held 640 k-as-v s={s}",
              _host(out)[..., :512], _host(ref)[..., :512], atol=2e-2,
              checks=checks)

    B, S, HKV, DQ = 2, 1024, 8, 192
    ks = jax.random.split(jax.random.PRNGKey(14), 3)
    qf = _normal(ks[0], (B, S, HKV, DQ), jnp.bfloat16)
    kf = _normal(ks[1], (B, S, HKV, DQ), jnp.bfloat16)
    vf = _normal(ks[2], (B, S, HKV, DQ), jnp.bfloat16)
    out = _jitted(flash_attention)(qf, kf, vf, causal=True, interpret=False)
    ref = _jitted(attention_ref)(qf, kf, vf, causal=True)
    check("mla flash fwd d=192", out, ref, atol=2e-2, checks=checks)
    gf = jax.jit(jax.grad(lambda a, b, c: jnp.sum(flash_attention(
        a, b, c, causal=True, interpret=False) ** 2), (0, 1, 2)))(qf, kf, vf)
    gr = jax.jit(jax.grad(lambda a, b, c: jnp.sum(attention_ref(
        a, b, c, causal=True) ** 2), (0, 1, 2)))(qf, kf, vf)
    check_grads("mla flash bwd d=192", gf, gr, checks)




def sink_cases(checks):
    """GPT-OSS attention sinks, compiled: the (H,128)/(rows,128) sink
    operand tiles must satisfy Mosaic's layout rules, and the finalize
    rebase must hold on the real softmax/exp units."""
    from shellac_tpu.ops.attention import attention_ref
    from shellac_tpu.ops.decode_attention import _decode_ref, decode_attention
    from shellac_tpu.ops.flash_attention import flash_attention

    B, L, H, HKV, D = 4, 1024, 16, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(99), 4)
    sinks = _normal(ks[3], (H,), jnp.float32) * 2.0
    q = _normal(ks[0], (B, 1, H, D), jnp.bfloat16)
    ck = _normal(ks[1], (B, HKV, L, D), jnp.bfloat16)
    cv = _normal(ks[2], (B, HKV, L, D), jnp.bfloat16)
    index = jnp.array([0, 37, 519, L - 1], jnp.int32)
    for window in (None, 200):
        out = _jitted(decode_attention)(
            q, ck, cv, index, window=window, sinks=sinks, impl="flash",
            interpret=False,
        )
        ref = _jitted(_decode_ref)(q, ck, cv, index, window, D ** -0.5, sinks=sinks)
        check(
            f"dense sinks window={window}",
            out, ref,
            atol=2e-2, checks=checks,
        )

    S = 512
    qf = _normal(ks[0], (2, S, H, D), jnp.bfloat16)
    kf = _normal(ks[1], (2, S, HKV, D), jnp.bfloat16)
    vf = _normal(ks[2], (2, S, HKV, D), jnp.bfloat16)
    out = _jitted(flash_attention)(qf, kf, vf, causal=True, sinks=sinks,
                          interpret=False)
    ref = _jitted(attention_ref)(qf, kf, vf, causal=True, sinks=sinks)
    check("flash fwd sinks", out, ref, atol=2e-2, checks=checks)

    def loss_flash(q, k, v, s):
        return (flash_attention(
            q, k, v, causal=True, sinks=s, interpret=False
        ).astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v, s):
        return (attention_ref(
            q, k, v, causal=True, sinks=s
        ).astype(jnp.float32) ** 2).sum()

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2, 3)))(
        qf, kf, vf, sinks)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2, 3)))(
        qf, kf, vf, sinks)
    for name, a, b in zip(("dq", "dk", "dv", "dsink"), gf, gr):
        check(f"flash bwd sinks {name}", a, b, atol=1.5e-1, checks=checks)


def paged_mha_cases(checks):
    """The MHA paged kernel (`decode_paged_mha`), compiled: ouro-2.6b-
    batch-reason's read, 16 q heads = 16 kv heads x 128 over five
    128-row pages a slot, one query row a head. Every row no slot may
    read holds NaN (past a slot's last row, before its window, and the
    pages no slot owns): the kernel copies a last page only up to the
    row block that holds the slot's last row, and its copies run ahead
    across slots, which interpret mode cannot fault."""
    from shellac_tpu.ops.decode_attention import (
        _decode_ref,
        paged_decode_attention,
        paged_kernel_name,
    )

    B, H, D, bs, mb = 8, 16, 128, 128, 5
    L = bs * mb
    index = np.array([0, 31, 32, 127, 128, 300, 511, L - 1], np.int32)
    rng = np.random.default_rng(42)
    tables = (rng.permutation(B * mb) + 1).reshape(B, mb).astype(np.int32)
    dense = [rng.standard_normal((B, H, L, D), np.float32) for _ in range(2)]
    sinks = rng.standard_normal((H,), np.float32)

    def exact(*a, **k):  # float32 rows multiply as float32 on this side too
        with jax.default_matmul_precision("highest"):
            return _decode_ref(*a, **k)

    assert paged_kernel_name((B, 1, H, D), (1, H, bs, D), jnp.bfloat16, mb,
                             D) == "decode_paged_mha"
    for dtype, atol in ((jnp.bfloat16, 2e-2), (jnp.float32, 1e-4)):
        for window, extra in ((None, {}), (200, {}),
                              (None, {"softcap": 30.0, "sinks": sinks})):
            pools = []
            for a in dense:
                pool = np.full((B * mb + 1, H, bs, D), np.nan, np.float32)
                pool[0] = 0.0
                for b in range(B):
                    pos = np.arange(L)
                    keep = pos <= index[b]
                    if window is not None:
                        keep &= index[b] - pos < window
                    rows = np.where(keep[None, :, None], a[b], np.nan)
                    for j in range(mb):
                        pool[tables[b, j]] = rows[:, j * bs:(j + 1) * bs]
                pools.append(jnp.asarray(pool, dtype))
            kw = {k: (jnp.asarray(v) if k == "sinks" else v)
                  for k, v in extra.items()}
            q = _normal(jax.random.PRNGKey(7), (B, 1, H, D), dtype)
            out = _jitted(paged_decode_attention)(
                q, pools[0], pools[1], jnp.asarray(tables),
                jnp.asarray(index), window=window, impl="flash",
                interpret=False, **kw)
            ref = _jitted(exact)(
                q, jnp.asarray(dense[0], dtype), jnp.asarray(dense[1], dtype),
                jnp.asarray(index), window, D ** -0.5, **kw)
            assert np.isfinite(_host(out)).all()
            check(f"paged mha {jnp.dtype(dtype).name} window={window} "
                  f"{'+'.join(extra) or 'plain'} NaN past the rows",
                  out, ref, atol=atol, checks=checks)


def eva_decode_cases(checks):
    """ops/eva_attention.py::eva_decode_kernel, compiled, against the
    XLA form: tests/test_eva.py's cases at the evabyte cell's sizes (W
    2048 in blocks of 128, 32 heads x 128, pages of 128 pooled rows,
    two layers of stacks, layer 1 read). Every ring row at or past a
    slot's count, every page no slot owns and all of layer 0 hold NaN
    on the kernel's side: the copies are the kernel's own, across grid
    steps, which interpret mode cannot fault."""
    from shellac_tpu.ops.eva_attention import (
        eva_decode_attention,
        eva_decode_kernel,
        eva_ring_block,
    )

    L, W, S, H, D, P, R = 2, 2048, 4, 32, 128, 9, 128
    blk = eva_ring_block(W)
    cases = [
        ("one row, no page", (1,), (0,), [[1, 2, 3, 4]], (0,)),
        ("a block's last row", (blk,), (1,), [[1, 2, 3, 4]], (1,)),
        ("a block's first row", (blk + 1,), (1,), [[1, 2, 3, 4]], (2,)),
        ("the whole ring, no page", (W,), (0,), [[1, 2, 3, 4]], (3,)),
        ("every page of its row", (37,), (4,), [[8, 6, 4, 2]], (0,)),
        ("tables interleave", (70, 1500), (2, 3),
         [[1, 3, 5, 7], [2, 4, 6, 8]], (1, 0)),
        ("stale entries", (900, 3), (1, 2), [[5, 6, 8, 8], [6, 7, 5, 1]],
         (2, 3)),
        ("four slots", (W, 1, 1025, blk), (0, 4, 2, 1),
         [[1, 1, 1, 1], [2, 3, 4, 5], [6, 7, 1, 1], [8, 2, 2, 2]],
         (3, 1, 0, 2)),
    ]
    rng = np.random.default_rng(36)
    ring = [rng.standard_normal((L, W, S, H, D), np.float32) for _ in range(2)]
    pool = [rng.standard_normal((L, H, P, R, D), np.float32) for _ in range(2)]
    layer = 1

    def inputs(dtype, n_exact, n_pages, tables, cols):
        """(kernel's arguments, the XLA form's, the slots' columns): the
        kernel's stacks hold NaN wherever no slot may read."""
        b = len(n_exact)
        q = jnp.asarray(rng.standard_normal((b, H, D), np.float32), dtype)
        tables = np.array(tables, np.int32)
        n_exact, n_pages = np.array(n_exact, np.int32), np.array(n_pages, np.int32)
        owned = np.zeros((b, P), bool)
        live = np.zeros((L, W, S), bool)
        for i in range(b):
            owned[i, tables[i, :n_pages[i]]] = True
            live[layer, :n_exact[i], cols[i]] = True
        held = np.zeros((L, P), bool)
        held[layer] = owned.any(axis=0)
        bad = ([np.where(live[..., None, None], a, np.nan) for a in ring]
               + [np.where(held[:, None, :, None, None], a, np.nan)
                  for a in pool])
        rk, rv, pk, pv = (jnp.asarray(a, dtype) for a in bad)
        clean = ([jnp.asarray(a[layer][:, list(cols)], dtype) for a in ring]
                 + [jnp.asarray(a[layer], dtype) for a in pool])
        return ((q, rk, rv, n_exact, pk, pv, tables, n_pages),
                (q, clean[0], clean[1], n_exact, clean[2], clean[3], owned),
                np.array(cols, np.int32))

    kernel = _jitted(eva_decode_kernel)

    def exact(*a, **k):  # float32 rows multiply as float32 on this side too
        with jax.default_matmul_precision("highest"):
            return eva_decode_attention(*a, **k)

    ref = _jitted(exact)
    for dtype, atol in ((jnp.bfloat16, 2e-2), (jnp.float32, 1e-4)):
        for label, n_exact, n_pages, tables, cols in cases:
            ours, theirs, cols = inputs(dtype, n_exact, n_pages, tables, cols)
            out = kernel(*ours, layer=np.int32(layer), cols=cols,
                         scale=D ** -0.5, interpret=False)
            want = ref(*theirs, scale=D ** -0.5)
            assert np.isfinite(_host(out)).all(), label
            check(f"eva decode {jnp.dtype(dtype).name} {label}", out, want,
                  atol=atol, checks=checks)


def run_all():
    """Every compiled parity check; returns the list of check names.
    Raises on the first mismatch. The caller has made sure a TPU is
    the default backend."""
    checks = []
    dense_decode_cases(checks)
    paged_decode_cases(checks)
    quant_cache_cases(checks)
    quant_paged_cases(checks)
    flash_train_cases(checks)
    head_dim_64_cases(checks)
    mla_shape_cases(checks)
    sink_cases(checks)
    paged_mha_cases(checks)
    eva_decode_cases(checks)
    return checks


def main():
    from shellac_tpu.utils.compile_cache import enable_compile_cache
    from shellac_tpu.utils.metrics import device_info

    enable_compile_cache()
    device = device_info()
    if device["platform"] != "tpu":
        print(json.dumps({"ok": False, "device": device,
                          "error": "need a tpu"}))
        sys.exit(2)
    checks = run_all()
    print(json.dumps({"ok": True, "device": device, "checks": checks}))


if __name__ == "__main__":
    main()
