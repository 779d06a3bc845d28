"""Chipless compile gate: the main path's Pallas kernels at shellac-1b
widths, compiled by the installed TPU compiler for a *described* (not
attached) v5e:2x2 device with interpret=False.

Interpret-mode parity cannot see what Mosaic refuses (unaligned memref
slices, VMEM overflows); a chip run can, but costs chip time. This
compile costs none, so it guards every PR. Nothing runs here — results
and timings come only from `chip_smoke.py` on the chip.

The file name sorts early on purpose: tier-1's time window has never
reached the late alphabet.
"""

import json
import math
import os
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# shellac-1b (models/registry.py): 16 q / 8 kv heads, head_dim 128,
# d_model 2048, seq 2048; train batch 6, serve 8 slots x ctx 2048.
H, HKV, D, DMODEL = 16, 8, 128, 2048
TRAIN_B, SEQ = 6, 2048
SERVE_B, CTX = 8, 2048
BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e:2x2; persistent compile cache off
    around the module (a chipless compile writes entries no process can
    read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One chip of it."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _flash(grad, *, window=None, segments=False, **kw):
    from shellac_tpu.ops.flash_attention import flash_attention

    shapes = [((TRAIN_B, SEQ, H, D), BF16), ((TRAIN_B, SEQ, HKV, D), BF16),
              ((TRAIN_B, SEQ, HKV, D), BF16)]
    if segments:
        shapes.append(((TRAIN_B, SEQ), I32))

    def fwd(q, k, v, seg=None):
        return flash_attention(q, k, v, causal=True, window=window,
                               segments=seg, interpret=False, **kw)

    if not grad:
        return fwd, shapes

    def loss(q, k, v, seg=None):
        return jnp.sum(fwd(q, k, v, seg).astype(F32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2)), shapes


def _dense_decode(*, quant=False, d=D, hkv=HKV, **kw):
    from shellac_tpu.ops.decode_attention import decode_attention

    cdt = I8 if quant else BF16
    shapes = [((SERVE_B, 1, H, d), BF16), ((SERVE_B, hkv, CTX, d), cdt),
              ((SERVE_B, hkv, CTX, d), cdt), ((SERVE_B,), I32)]
    if quant:
        shapes += [((SERVE_B, hkv, CTX), F32)] * 2

    def fn(q, ck, cv, index, ks=None, vs=None):
        return decode_attention(q, ck, cv, index, impl="flash",
                                interpret=False, k_scale=ks, v_scale=vs,
                                **kw)

    return fn, shapes


def _paged_decode(bs, *, quant=False):
    from shellac_tpu.ops.decode_attention import paged_decode_attention

    mb = CTX // bs
    n_blocks = SERVE_B * mb + 1
    cdt = I8 if quant else BF16
    shapes = [((SERVE_B, 1, H, D), BF16), ((n_blocks, HKV, bs, D), cdt),
              ((n_blocks, HKV, bs, D), cdt), ((SERVE_B, mb), I32),
              ((SERVE_B,), I32)]
    if quant:
        shapes += [((n_blocks, HKV, bs), F32)] * 2

    def fn(q, pk, pv, tables, index, ks=None, vs=None):
        return paged_decode_attention(q, pk, pv, tables, index, impl="flash",
                                      interpret=False, k_scale=ks,
                                      v_scale=vs)

    return fn, shapes


def _paged_latent_decode():
    """deepseek-v2-lite-batch's read: 16 q heads on the one shared
    latent row, held at 640 lanes (kvcache.held_width(576)), 128-row
    pages, the pool serving as k and as v; q arrives 576 wide."""
    from shellac_tpu.inference.kvcache import held_width
    from shellac_tpu.ops.decode_attention import paged_decode_attention

    slots, pages, d = 64, 20, 576
    pool = ((slots * pages + 1, 1, 128, held_width(d)), BF16)
    shapes = [((slots, 1, H, d), BF16), pool, ((slots, pages), I32),
              ((slots,), I32)]

    def fn(q, pk, tables, index):
        return paged_decode_attention(q, pk, pk, tables, index, impl="flash",
                                      interpret=False, scale=192 ** -0.5)

    return fn, shapes


def _eva_decode():
    """evabyte-batch-bytes' tick: 24 slots of 32 heads x 128 against the
    WHOLE stacks of 8 layers: rings of 2048 rows, 217 pages of 128
    pooled rows, tables of 9 entries; the layer a scalar."""
    from shellac_tpu.ops.eva_attention import eva_decode_kernel

    layers, w, slots, heads, pages, rows, mb = 8, 2048, 24, 32, 217, 128, 9
    ring = ((layers, w, slots, heads, D), BF16)
    pool = ((layers, heads, pages, rows, D), BF16)
    shapes = [((slots, heads, D), BF16), ring, ring, ((slots,), I32), pool,
              pool, ((slots, mb), I32), ((slots,), I32), ((), I32)]

    def fn(q, rk, rv, n_exact, pk, pv, tables, n_pages, layer):
        return eva_decode_kernel(
            q, rk, rv, n_exact, pk, pv, tables, n_pages, layer=layer,
            cols=jnp.arange(slots, dtype=I32), scale=D ** -0.5,
            interpret=False)

    return fn, shapes


def _rmsnorm(grad):
    from shellac_tpu.ops.norms import rms_norm_pallas

    shapes = [((TRAIN_B * SEQ, DMODEL), BF16), ((DMODEL,), F32)]

    def fwd(x, scale):
        return rms_norm_pallas(x, scale, 1e-5, False)

    if not grad:
        return fwd, shapes
    return jax.grad(lambda x, s: jnp.sum(fwd(x, s).astype(F32) ** 2),
                    argnums=(0, 1)), shapes


def _dsa_chunk(which):
    """The two kernels of a prompt chunk under a learned indexer, at
    keye-vl-2.0-30b-a3b-batch-long's shapes: 4096 queries of 32 heads on
    4 kv heads of 128 (16 index heads of 64) against the 33,792 rows a
    slot can hold."""
    from shellac_tpu.ops import dsa_attention as dsa

    sq, sk, h, hkv, j, di = 4096, 33792, 32, 4, 16, 64
    live = ((1, sq // dsa.BLOCK_Q), I32)
    if which == "scores":
        return (lambda u, w, c, n: dsa.index_scores_flash(u, w, c, n, False),
                [((1, sq, j, di), BF16), ((1, sq, j), F32),
                 ((1, di, sk), BF16), live])
    return (lambda q, k, v, m, n: dsa.masked_flash(q, k, v, m, n, D ** -0.5,
                                                   False),
            [((1, sq, h, D), BF16), ((1, sk, hkv, D), BF16),
             ((1, sk, hkv, D), BF16), ((1, sq, sk), I8), live])


def _int8_page_sizes():
    """The engine's default int8 page size plus every size its error
    message recommends — read from the engine, not repeated here."""
    from shellac_tpu.inference.cache.paged import (
        INT8_BLOCK_SIZE_DEFAULT,
        INT8_BLOCK_SIZES_RECOMMENDED,
    )

    return sorted({INT8_BLOCK_SIZE_DEFAULT, *INT8_BLOCK_SIZES_RECOMMENDED})


# (id, builder, expects a Mosaic kernel in the compiled text)
CASES = [
    ("flash-fwd", lambda: _flash(False), True),
    ("flash-fwd-bwd", lambda: _flash(True), True),
    ("flash-bwd-window-segments",
     lambda: _flash(True, window=1024, segments=True), True),
    ("flash-fwd-bwd-sinks-softcap",
     lambda: _flash(True, sinks=jnp.zeros((H,), F32), softcap=30.0), True),
    ("decode-dense-bf16", lambda: _dense_decode(), True),
    ("decode-dense-int8", lambda: _dense_decode(quant=True), True),
    ("decode-dense-window-sinks-softcap",
     lambda: _dense_decode(window=1024, softcap=30.0,
                           sinks=jnp.zeros((H,), F32)), True),
    ("decode-mla-latent-d576",
     lambda: _dense_decode(d=576, hkv=1, scale=192 ** -0.5), True),
    ("paged-bf16-page16", lambda: _paged_decode(16), True),
    ("paged-bf16-page64", lambda: _paged_decode(64), True),
    # The pool "auto" sends to the kernel (mistral-7b-batch's pages).
    ("paged-bf16-page256", lambda: _paged_decode(256), True),
    # And the latent pool it sends there (deepseek-v2-lite-batch's).
    ("paged-latent-page128", _paged_latent_decode, True),
    *[(f"paged-int8-page{bs}",
       lambda bs=bs: _paged_decode(bs, quant=True), True)
      for bs in _int8_page_sizes()],
    ("dsa-index-scores-chunk4096", lambda: _dsa_chunk("scores"), True),
    ("dsa-masked-flash-chunk4096", lambda: _dsa_chunk("attend"), True),
    # evabyte-batch-bytes' decode attention, whole stacks in HBM.
    ("eva-decode-w2048-page128", _eva_decode, True),
    ("rmsnorm-fwd", lambda: _rmsnorm(False), True),
    # The backward is plain XLA (vjp of the reference); it only has to
    # compile.
    ("rmsnorm-bwd", lambda: _rmsnorm(True), False),
]


@pytest.mark.parametrize("build,has_kernel",
                         [pytest.param(b, k, id=i) for i, b, k in CASES])
def test_kernel_compiles_for_v5e(chip, build, has_kernel):
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    if has_kernel:
        assert "tpu_custom_call" in text


# ---------------------------------------------------------------------------
# kernels inside a program partitioned over a mesh
# ---------------------------------------------------------------------------
#
# GSPMD refuses to partition a Mosaic kernel, so on a mesh the
# dispatchers run them per shard (ops/dispatch.per_shard). The CPU's
# virtual devices never take the kernel branch under "auto", so only
# these two tests see that path: a compile for the described 2x2 (the
# dispatchers steered to their TPU branch), and interpret-mode numerics
# on the virtual CPU mesh.

Q_AXES = ("batch", None, "heads", None)
KV_AXES = ("batch", None, "kv_heads", None)


def _mesh_rmsnorm(mesh):
    from shellac_tpu.ops.norms import rms_norm

    return (lambda x, s: rms_norm(x, s, mesh=mesh),
            [((4, SEQ, DMODEL), BF16, ("batch", "seq", None)),
             ((DMODEL,), F32, (None,))])


def _mesh_flash_grad(mesh):
    from shellac_tpu.ops.attention import attention

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v, mesh=mesh).astype(F32) ** 2)

    return (jax.grad(loss, argnums=(0, 1, 2)),
            [((4, SEQ, H, D), BF16, Q_AXES), ((4, SEQ, HKV, D), BF16, KV_AXES),
             ((4, SEQ, HKV, D), BF16, KV_AXES)])


def _mesh_dense_decode(mesh):
    from shellac_tpu.ops.decode_attention import decode_attention

    cache = ((SERVE_B, HKV, CTX, D), BF16, ("batch", "kv_heads", None, None))
    return (lambda q, ck, cv, i: decode_attention(q, ck, cv, i, mesh=mesh),
            [((SERVE_B, 1, H, D), BF16, Q_AXES), cache, cache,
             ((SERVE_B,), I32, ("batch",))])


def _mesh_paged_int8(mesh):
    from shellac_tpu.inference.cache.paged import INT8_BLOCK_SIZE_DEFAULT
    from shellac_tpu.ops.decode_attention import paged_decode_attention

    bs = INT8_BLOCK_SIZE_DEFAULT
    mb = CTX // bs
    nb = SERVE_B * mb + 1
    pool = ((nb, HKV, bs, D), I8, (None, "kv_heads", None, None))
    scales = ((nb, HKV, bs), F32, (None, "kv_heads", None))

    def fn(q, pk, pv, tables, index, ks, vs):
        return paged_decode_attention(q, pk, pv, tables, index, k_scale=ks,
                                      v_scale=vs, mesh=mesh)

    return fn, [((SERVE_B, 1, H, D), BF16, Q_AXES), pool, pool,
                ((SERVE_B, mb), I32, (None, None)),
                ((SERVE_B,), I32, (None,)), scales, scales]


def _gqa_stack():
    from shellac_tpu import get_model_config

    return get_model_config("tiny").replace(
        d_model=512, n_heads=H, n_kv_heads=HKV, head_dim=D, d_ff=1024,
        n_layers=8, dtype="bfloat16", param_dtype="bfloat16",
    ).validate()


def _mla_stack(d_model=512, **kw):
    """DeepSeek-V2-Lite's attention widths (16 heads, latent 512 + rope
    64, no q compression) over a narrow FFN."""
    from shellac_tpu import get_model_config
    from shellac_tpu.config import MLAConfig

    return get_model_config("tiny-mla").replace(
        d_model=d_model, n_heads=H, d_ff=1024, n_layers=8, dtype="bfloat16",
        param_dtype="bfloat16",
        mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128), **kw,
    ).validate()


@pytest.mark.parametrize("stack,page,kernel", [
    pytest.param(_gqa_stack, 256, True, id="page256-kernel"),
    pytest.param(_gqa_stack, 64, False, id="page64-gather"),
    pytest.param(_mla_stack, 128, True, id="latent-page128-kernel"),
])
def test_paged_decode_window_keeps_the_pool_in_place_on_v5e(
        chip, pool_sized_ops, monkeypatch, stack, page, kernel):
    """The TPU compiler's verdict on what tests/test_paged_inplace.py
    reads off the CPU's: a window of decode ticks over a bf16 paged pool
    at serving widths (8 kv heads x 128) holds no pool-sized temporary
    and moves no pool. The two compilers differ: a one-row update at
    (block, offset) is in place on the CPU, while this one then holds
    the pool head-innermost inside the window and copies it in and out
    (PERF.md, PR 26).

    The dispatchers are steered to their TPU branch, as on the chip, so
    256-row pages read through the block table in the Mosaic kernel and
    the window that must hold is the one WITH THE KERNEL IN IT: a pool
    whose layout the kernel's operand does not share would be copied in
    front of every layer's call. Shorter pages take the gather, whose
    window must hold too. So must the window over an MLA model's latent
    pool, held at 640 lanes so that writer and kernel share its form
    (at 576 the compiler copied the whole pool twice a window)."""
    from shellac_tpu.ops import decode_attention as da

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from shellac_tpu.inference.kvcache import init_paged_cache
    from shellac_tpu.models import transformer

    cfg = stack()
    slots, pages = 8, 1024 // page

    def window(params, cache, cur):
        def tick(carry, _):
            cache, cur = carry
            logits, cache = transformer.forward_with_cache(
                cfg, params, cur[:, None], cache
            )
            return (cache, jnp.argmax(logits[:, 0], -1).astype(I32)), None

        return jax.lax.scan(tick, (cache, cur), None, length=2)[0]

    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree,
    )
    params = shaped(jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
    ))
    cache = shaped(jax.eval_shape(
        lambda: init_paged_cache(cfg, slots, slots * pages + 1, page, pages)
    ))
    cur = jax.ShapeDtypeStruct((slots,), I32, sharding=chip)
    assert da.paged_decode_path(
        (slots, 1, H, cache.k.shape[-1]), cache.k.shape[1:], BF16
    ) == ("paged_kernel" if kernel else "gather")
    compiled = jax.jit(window, donate_argnums=(1,)).lower(
        params, cache, cur
    ).compile()
    pool_bytes = (cache.k.size + cache.v.size) * cache.k.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 2, (temp, pool_bytes)
    text = compiled.as_text()
    assert ("decode_paged_group" in text) is kernel
    moved = pool_sized_ops(text, [cache.k.shape])
    assert not moved, "\n".join(moved)


@pytest.mark.parametrize("rows", [2048, 64], ids=["prompt2048", "tick64"])
def test_dropless_experts_multiply_the_routed_rows_on_v5e(
        chip, monkeypatch, rows):
    """A dropless MoE stack at DeepSeek-V2-Lite's expert widths (64
    experts, top-6, 2048 -> 1408), through the cached forward's own
    path, at a 2,048-token prompt and at a decode tick of 64 rows: the
    expert FFN is the grouped-matmul kernel over the sorted routed
    rows. The program builds no (E*T, D) bucket (two of 537 MB at 2,048
    rows, before PR 30) and no copy of a layer's expert weights: the
    kernel reads them in the whole stack, where the scan's slice in
    front of a kernel call is a 369-MB rewrite of each matrix every
    layer (PERF.md, PR 30)."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from shellac_tpu import MoEConfig, get_model_config
    from shellac_tpu.inference.kvcache import init_cache
    from shellac_tpu.models import transformer

    e, k, d, f = 64, 6, 2048, 1408
    cfg = get_model_config("tiny-moe").replace(
        d_model=d, n_heads=H, n_kv_heads=HKV, head_dim=D, d_ff=f,
        n_layers=2, max_seq_len=2048, dtype="bfloat16",
        param_dtype="bfloat16",
        moe=MoEConfig(num_experts=e, num_experts_per_token=k,
                      d_ff_expert=f, norm_topk_prob=False, dropless=True),
    ).validate()
    prompt = rows > 64
    batch, seq = (1, rows) if prompt else (rows, 1)

    def run(params, cache, tokens):
        return transformer.forward_with_cache(
            cfg, params, tokens, cache, fresh_cache=prompt)

    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree,
    )
    params = shaped(jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))))
    # (A slot cache rides the layer loop as xs/ys, a copy: kept short
    # at the tick so that the expert weights are what could show.)
    cache = shaped(jax.eval_shape(
        lambda: init_cache(cfg, batch, 2048 if prompt else 128)))
    tokens = jax.ShapeDtypeStruct((batch, seq), I32, sharding=chip)
    compiled = jax.jit(run, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    text = compiled.as_text()
    assert len(re.findall(
        r'custom_call_target="tpu_custom_call".*moe\.gemm/jit\(gmm\)', text
    )) == 3
    bucket = e * rows * d * 2
    weights = e * d * f * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < weights / 2, (temp, weights)  # no matrix rewritten
    if prompt:  # under a quarter of the gate/up and down buckets
        assert temp < 2 * bucket / 4, (temp, bucket)
    # No bucket (E*T rows flat, or (E, T, ...)), and no layer's experts
    # (E, in, out) out of the (2, E, in, out) stacks.
    made = re.findall(
        rf"= \w+\[(?:{e * rows}|{e * rows + 1}|{e},{rows}|{e},{d},{f}"
        rf"|{e},{f},{d}),[\d,]*\]\S* (?!parameter|bitcast|get-tuple)\w",
        text,
    )
    assert not made, made[:5]


@pytest.mark.parametrize("heads,hkv,d,in_place", [
    pytest.param(16, 1, 576, False, id="latent-d576-copied"),
    pytest.param(16, 1, 640, True, id="latent-d640-in-place"),
    pytest.param(H, HKV, D, True, id="gqa-d128-in-place"),
])
def test_latent_pool_is_relaid_for_the_kernel_on_v5e(chip, heads, hkv, d,
                                                     in_place):
    """Why a bf16 pool's row is held at whole lane tiles
    (kvcache.held_width): the device holds a (n_blocks, 1, 128, 576)
    latent pool in a tiling Mosaic's operand does not share, so the
    compiler copies the WHOLE pool in front of the kernel's call
    (temporaries of a pool's size, which the guard above refuses),
    where the same rows held 640 wide, like a 128-wide pool, are read
    in place in the layer loop. If the 576 case ever fails the copy is
    gone and the pad can go (PERF.md, PR 32)."""
    from shellac_tpu.inference.kvcache import held_width
    from shellac_tpu.ops.decode_attention import (
        paged_decode_attention,
        paged_kernel_under_auto,
    )

    layers, pages = 4, 16
    n_blocks = SERVE_B * pages + 1
    q = ((layers, SERVE_B, 1, heads, d), BF16)
    pool = ((layers * n_blocks, hkv, 128, d), BF16)
    shapes = [q, pool, pool, ((SERVE_B, pages), I32), ((SERVE_B,), I32)]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
    kw = {"scale": 192 ** -0.5} if hkv == 1 else {}

    def tick(qs, pk, pv, tables, index):  # the layer loop's shape
        def layer(i, acc):
            return acc + paged_decode_attention(
                qs[i], pk, pv, tables + i * n_blocks, index,
                impl="flash", interpret=False, **kw).astype(F32)

        return jax.lax.fori_loop(0, layers, layer, jnp.zeros(q[0][1:], F32))

    compiled = jax.jit(tick).lower(*args).compile()
    pool_bytes = 2
    for n in pool[0]:
        pool_bytes *= n
    assert paged_kernel_under_auto(q[0][1:], pool[0], BF16) is in_place
    assert (held_width(d) == d) is in_place
    temp = compiled.memory_analysis().temp_size_in_bytes
    if in_place:
        assert temp < pool_bytes / 8, (temp, pool_bytes)
    else:
        assert temp >= pool_bytes, (temp, pool_bytes)


def _engine_program(eng, program, prompt):
    """The engine's decode window ("decode") or whole-prompt prefill of
    `prompt` padded tokens, jitted as the engine jits it, with the
    arguments its own call passes (the slot state as the engine's
    accessors hand it to a program) and the static flags of a greedy
    run."""
    key = jax.random.PRNGKey(0)
    row = eng._zero_bias_row
    tables = eng.cache_backend.slot_tables()
    if program == "decode":
        fn = eng._jit_cache_program(
            eng._decode_impl, 8, static_argnames=("greedy_only",))
        args = (key, eng._carry,
                eng._window_arg([True] * eng.n_slots, [0] * eng.n_slots),
                eng._samp_arg(), tables, row, row, eng._dummy_ctrans)
        return fn, args, {"greedy_only": True}
    fn = eng._jit_cache_program(
        eng._prefill_impl, 6, static_argnames=("want_plp",))
    args = (jnp.zeros((1, prompt), I32), jnp.asarray([prompt], I32),
            jnp.int32(0), key, (jnp.zeros((6,), I32), row, row), tables)
    return fn, args, {"want_plp": False}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_mla_engine_programs_keep_the_latent_pool_in_place_on_v5e(
        chip, pool_sized_ops, monkeypatch, program):
    """The engine's REAL programs over a latent pool at
    DeepSeek-V2-Lite's attention widths and deepseek-v2-lite-batch's
    pages: the decode window reads the pool through the block table in
    the kernel (no gathered view of every slot, no `kv.gather` scope)
    and the whole-prompt prefill writes its rows through the table, and
    neither holds a pool-sized temporary nor moves the pool: at a
    576-wide row each copied the whole pool in and out (two copies a
    window, four a prompt; PERF.md, PR 32)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from shellac_tpu.inference.batching import PagedBatchingEngine
    from shellac_tpu.models import transformer

    cfg = _mla_stack(d_model=1024, vocab_size=1024, max_seq_len=2560)
    slots, prompt = 32, 1024
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree,
    )
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    eng = PagedBatchingEngine(
        cfg, params, n_slots=slots, max_len=2560, block_size=128,
        pool_tokens=slots * 2560, decode_ticks=2,
    )
    assert eng.stats["decode_attn"] == "paged_kernel"
    cache = eng._cache
    assert cache.k.shape == (8, slots * 20 + 1, 1, 128, 640)
    fn, args, kw = _engine_program(eng, program, prompt)
    compiled = fn.lower(shaped(params), shaped(cache), *shaped(args),
                        **kw).compile()
    text = compiled.as_text()
    pool_bytes = cache.k.size * cache.k.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 8, (temp, pool_bytes)
    assert ("decode_paged_group" in text) is (program == "decode")
    assert "kv.gather" not in text
    moved = pool_sized_ops(text, [cache.k.shape])
    assert not moved, "\n".join(moved)


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_looped_engine_programs_fit_one_chip_on_v5e(
        chip, pool_sized_ops, monkeypatch, program):
    """The engine's REAL programs for Ouro-2.6B at its published widths
    (benchmark/configs/ouro-2.6b.json: 48 layers run four times, 192
    cached layers) and the cell's serving numbers: 8 slots of five
    128-row pages, a (192, 41, 16, 128, 128) k and v pool of 8.05 GB
    beside 5.34 GB of weights. The decode window and the largest prompt
    bucket each hold their arguments and temporaries under the chip's
    15.75 GiB, read a tick's pages through the block table in the
    kernel, and hold no pool-sized temporary: the pool rides all four
    passes as one donated carry (one copy of k alone is 4 GB and would
    not fit)."""
    import types

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from shellac_tpu.inference.batching import PagedBatchingEngine
    from shellac_tpu.models import transformer
    from shellac_tpu.models.convert import config_from_hf

    with open(os.path.join(REPO, "benchmark", "configs", "ouro-2.6b.json")) as f:
        hf = json.load(f)
    serving = hf["serving"]
    cfg = config_from_hf(types.SimpleNamespace(**hf)).replace(
        dtype="bfloat16", param_dtype="bfloat16").validate()
    assert (cfg.n_layers, cfg.cache_layers, cfg.loop.steps) == (48, 192, 4)
    slots, page, max_len = serving["n_slots"], serving["block_size"], 640
    assert (slots, page) == (8, 128)
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree,
    )
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert round(weights / 1e9, 2) == 5.34
    # The engine is built over one slot's pages (0.3 GB of real zeros
    # here); its programs take the cache as an argument, and are
    # compiled for the cell's whole pool, described.
    eng = PagedBatchingEngine(
        cfg, params, n_slots=slots, max_len=max_len, block_size=page,
        pool_tokens=max_len, decode_ticks=int(serving["decode_ticks"]),
    )
    assert eng.stats["decode_attn"] == "paged_kernel"
    assert eng.stats["kv_bytes_per_token"] == 1572864
    pool = (192, slots * (max_len // page) + 1, 16, page, 128)
    cache = eng._cache.replace(
        k=jax.ShapeDtypeStruct(pool, BF16), v=jax.ShapeDtypeStruct(pool, BF16))
    pool_bytes = 2 * 2 * math.prod(pool)  # k and v, bfloat16
    assert round(pool_bytes / 1e9, 2) == 8.25  # 8.05 GB + the scratch page
    # (256: the largest bucket of prompts of 64-255 tokens)
    fn, args, kw = _engine_program(eng, program, 256)
    compiled = fn.lower(shaped(params), shaped(cache), *shaped(args),
                        **kw).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert held < 15.75 * 2 ** 30, held
    # One copy of k or v is 4.1 GB: the temporaries stay under half that
    # (1.2 GB of them are wq, wk, wv re-laid each call; PERF.md, PR 33).
    assert ma.temp_size_in_bytes < pool_bytes / 4, ma.temp_size_in_bytes
    assert ma.alias_size_in_bytes >= pool_bytes  # donated, returned in place
    assert ("decode_paged" in text) is (program == "decode")
    assert "kv.gather" not in text
    moved = pool_sized_ops(text, [pool])
    assert not moved, "\n".join(moved)


def test_eva_engine_decode_window_moves_live_rows_only_on_v5e(
        chip, pool_sized_ops, monkeypatch):
    """The engine's REAL decode window for EvaByte at its published
    widths (benchmark/configs/evabyte.json: 8 of its layers) and the
    cell's serving numbers: 24 slots, rings of 24 x 256 MiB, 217 pages
    of 16 MiB, described and never allocated. The tick attends through
    the kernel, which takes the WHOLE ring and pool stacks where the
    layer walk carries them: the program holds no temporary, copy or
    slice of a layer's rings or pages and none of a stack (a layer of
    rings copied in front of the call was half the tick before PR 27
    found it in a trace), its arguments and temporaries fit the chip,
    and its temporaries are under those of the XLA form, which holds
    the float32 scores of every slot against every page."""
    import re
    import types

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from shellac_tpu.inference.cache import eva as eva_backend
    from shellac_tpu.inference.cache import engine_class
    from shellac_tpu.models import transformer
    from shellac_tpu.models.convert import config_from_hf

    with open(os.path.join(REPO, "benchmark", "configs", "evabyte.json")) as f:
        hf = json.load(f)
    serving = hf["serving"]
    cfg = config_from_hf(types.SimpleNamespace(**hf)).replace(
        dtype="bfloat16", param_dtype="bfloat16").validate()
    slots, window, max_len = serving["n_slots"], cfg.eva.window, 9 * 2048
    assert (slots, window, serving["block_size"]) == (24, 2048, 2048)
    described = eva_backend.init_eva_cache

    def describe(*a, **k):  # 9.4 GiB of zeros otherwise
        return jax.eval_shape(lambda: described(*a, **k))

    monkeypatch.setattr(eva_backend, "init_eva_cache", describe)
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree,
    )
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    ring = (8, window, slots, 32, 128)
    pool = (8, 32, slots * 9 + 1, window // cfg.eva.chunk, 128)
    stack_bytes = 2 * 2 * (math.prod(ring) + math.prod(pool))
    assert round(stack_bytes / 2 ** 30, 2) == 9.39
    temps = {}
    for impl, path in (("auto", "eva_kernel"), ("ref", "xla")):
        eng = engine_class("eva")(
            cfg, params, n_slots=slots, max_len=max_len, block_size=window,
            pool_tokens=slots * max_len, cache_backend="eva", attn_impl=impl,
            decode_ticks=int(serving["decode_ticks"]),
        )
        assert eng.stats["decode_attn"] == path
        assert (eng._cache.k.shape, eng._cache.pk.shape) == (ring, pool)
        fn, args, kw = _engine_program(eng, "decode", None)
        compiled = fn.lower(shaped(params), shaped(eng._cache),
                            *shaped(args), **kw).compile()
        ma = compiled.memory_analysis()
        temps[path] = ma.temp_size_in_bytes
        if path == "xla":
            continue
        text = compiled.as_text()
        assert re.search(r'custom_call_target="tpu_custom_call".*eva_decode',
                         text)
        held = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
        assert held < 15.75 * 2 ** 30, held
        assert ma.alias_size_in_bytes >= stack_bytes  # donated, in place
        # What a fusion computes inside itself is never held (the
        # summary writer reads its one chunk through a re-laid view of
        # the pool, as on the XLA form): the instructions that hold
        # their results are those outside the fused computations.
        text = re.sub(r"(?ms)^%fused_computation\S* \(.*?^}\n", "", text)
        moved = pool_sized_ops(text, [ring, pool])
        # A materialised slice is a fusion, not a copy: nothing may
        # have the shape of one layer's rings or pages at all.
        layer = "|".join(",".join(map(str, s[1:])) for s in (ring, pool))
        moved += [ln.strip()[:160] for ln in text.splitlines() if re.search(
            rf"= \(?\w+\[(?:1,)?(?:{layer})\]\S* (?!bitcast|parameter"
            rf"|get-tuple-element)\w", ln)]
        assert not moved, "\n".join(moved)
    assert temps["eva_kernel"] < temps["xla"], temps


def test_eva_engine_prefill_holds_one_window_on_v5e(chip, pool_sized_ops,
                                                    monkeypatch):
    """The engine's REAL prefill program for EvaByte at the cell's
    shapes (8 layers at the published widths, 24 slots, 217 pages, the
    one buffer of 18,432 bytes every prompt is padded to), the cache
    described: a loop over the prompt's windows, whose temporaries are
    one window's (the whole-sequence form held 1.67 GiB for a prompt of
    16,384), whose arguments still alias the donated rings and pool, and
    which moves no ring or pool stack."""
    import re
    import types

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    from shellac_tpu.inference.cache import eva as eva_backend
    from shellac_tpu.inference.cache import engine_class
    from shellac_tpu.models import transformer
    from shellac_tpu.models.convert import config_from_hf

    with open(os.path.join(REPO, "benchmark", "configs", "evabyte.json")) as f:
        hf = json.load(f)
    serving = hf["serving"]
    cfg = config_from_hf(types.SimpleNamespace(**hf)).replace(
        dtype="bfloat16", param_dtype="bfloat16").validate()
    slots, window, max_len = serving["n_slots"], cfg.eva.window, 9 * 2048
    described = eva_backend.init_eva_cache
    monkeypatch.setattr(eva_backend, "init_eva_cache", lambda *a, **k:
                        jax.eval_shape(lambda: described(*a, **k)))
    shaped = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        tree,
    )
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    eng = engine_class("eva")(
        cfg, params, n_slots=slots, max_len=max_len, block_size=window,
        pool_tokens=slots * max_len, cache_backend="eva",
        decode_ticks=int(serving["decode_ticks"]),
    )
    pad, run = eng.cache_backend.prefill_rows(16384)
    assert (pad, run) == (max_len, 16384)
    ring, pool = eng._cache.k.shape, eng._cache.pk.shape
    stack_bytes = 2 * 2 * (math.prod(ring) + math.prod(pool))
    fn, args, kw = _engine_program(eng, "prefill", pad)
    compiled = fn.lower(shaped(params), shaped(eng._cache), *shaped(args),
                        **kw).compile()
    ma = compiled.memory_analysis()
    held = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert held < 15.75 * 2 ** 30, held
    assert ma.temp_size_in_bytes < 1.67 * 2 ** 30 / 2, ma.temp_size_in_bytes
    assert ma.alias_size_in_bytes >= stack_bytes  # donated, in place
    text = re.sub(r"(?ms)^%fused_computation\S* \(.*?^}\n", "",
                  compiled.as_text())
    moved = pool_sized_ops(text, [ring, pool])
    assert not moved, "\n".join(moved)


@pytest.mark.parametrize("build", [
    pytest.param(_mesh_rmsnorm, id="rmsnorm"),
    pytest.param(_mesh_flash_grad, id="flash-fwd-bwd"),
    pytest.param(_mesh_dense_decode, id="decode-dense"),
    pytest.param(_mesh_paged_int8, id="paged-int8-default-page"),
])
def test_kernel_compiles_per_shard_on_2x2(topo, monkeypatch, build):
    from jax.sharding import NamedSharding

    from shellac_tpu import ParallelConfig, make_mesh
    from shellac_tpu.parallel.sharding import logical_to_spec

    # The dispatchers ask the default backend whether compiled Pallas
    # is live; here the target is the described chip, not the host.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = make_mesh(ParallelConfig(fsdp=2, tp=2), devices=topo.devices)
    fn, shapes = build(mesh)
    args = [jax.ShapeDtypeStruct(
        s, dt, sharding=NamedSharding(mesh, logical_to_spec(axes)))
        for s, dt, axes in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _close(got, want, tol):
    import numpy as np

    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


def test_per_shard_kernels_match_reference(mesh8):
    """Interpret-mode kernels under shard_map on the dp2/sp2/tp2 CPU
    mesh agree with the unsharded references: the cut along batch and
    heads keeps whole GQA groups and every operand's rows together."""
    import numpy as np

    from shellac_tpu.inference.kvcache import (
        paged_gather_layer,
        paged_gather_scales,
        quantize_kv,
    )
    from shellac_tpu.ops.attention import attention, attention_ref
    from shellac_tpu.ops.decode_attention import (
        _decode_ref,
        decode_attention,
        paged_decode_attention,
    )
    from shellac_tpu.ops.norms import rms_norm, rms_norm_ref

    B, S, Hq, Hk, Dh, L, bs = 4, 256, 4, 2, 128, 256, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (B, S, 256), F32)
    w = jax.random.normal(ks[1], (256,), F32)
    _close(jax.jit(lambda a, b: rms_norm(a, b, impl="pallas", mesh=mesh8))(
        x, w), rms_norm_ref(x, w), 1e-5)

    q = jax.random.normal(ks[2], (B, S, Hq, Dh), F32)
    k = jax.random.normal(ks[3], (B, S, Hk, Dh), F32)
    v = jax.random.normal(ks[4], (B, S, Hk, Dh), F32)
    seg = jnp.asarray(np.repeat([[0, 1, 2, 3]], B, 0).repeat(S // 4, 1), I32)
    sinks = jax.random.normal(ks[5], (Hq,), F32)
    got = jax.jit(lambda *a: attention(
        *a[:3], q_segments=a[3], kv_segments=a[3], sinks=a[4],
        impl="flash", mesh=mesh8))(q, k, v, seg, sinks)
    _close(got, attention_ref(q, k, v, q_segments=seg, kv_segments=seg,
                              sinks=sinks), 2e-5)

    # Decode against an int8 dense cache, then the same tokens paged.
    q1 = jax.random.normal(ks[6], (B, 1, Hq, Dh), F32)
    kq, ksc = quantize_kv(k)
    vq, vsc = quantize_kv(v)
    ck, cv = kq.transpose(0, 2, 1, 3), vq.transpose(0, 2, 1, 3)
    cks, cvs = ksc.transpose(0, 2, 1), vsc.transpose(0, 2, 1)
    index = jnp.asarray([0, 37, 130, L - 1], I32)
    got = jax.jit(lambda *a: decode_attention(
        *a[:4], k_scale=a[4], v_scale=a[5], impl="flash", mesh=mesh8))(
        q1, ck, cv, index, cks, cvs)
    _close(got, _decode_ref(q1, ck, cv, index, None, Dh ** -0.5,
                            k_scale=cks, v_scale=cvs), 2e-5)

    mb = L // bs
    order = np.random.default_rng(0).permutation(B * mb) + 1
    tables = jnp.asarray(order.reshape(B, mb), I32)

    def pool(c):  # (B, Hk, L, ...) rows scattered to their pages
        pages = c.reshape(B, Hk, mb, bs, *c.shape[3:])
        pages = jnp.moveaxis(pages, 2, 1).reshape(B * mb, Hk, bs,
                                                  *c.shape[3:])
        out = jnp.zeros((B * mb + 1, *pages.shape[1:]), c.dtype)
        return out.at[tables.reshape(-1)].set(pages)

    pk, pv, pks, pvs = pool(ck), pool(cv), pool(cks), pool(cvs)
    got = jax.jit(lambda *a: paged_decode_attention(
        *a[:5], k_scale=a[5], v_scale=a[6], impl="flash", mesh=mesh8))(
        q1, pk, pv, tables, index, pks, pvs)
    k_all, v_all = paged_gather_layer(pk, pv, tables)
    _close(got, _decode_ref(
        q1, k_all, v_all, index, None, Dh ** -0.5,
        k_scale=paged_gather_scales(pks, tables),
        v_scale=paged_gather_scales(pvs, tables)), 2e-5)

    # One shared row a token as k AND v (the MLA latent, replicated over
    # the tensor axis), held wider than q: one operand a shard.
    rows = jnp.pad(k[:, :, 0], ((0, 0), (0, 0), (0, Dh)))  # (B, L, 2 Dh)
    lat = jnp.zeros((B * mb + 1, 1, bs, 2 * Dh), F32).at[
        tables.reshape(-1)].set(rows.reshape(B * mb, 1, bs, 2 * Dh))
    got = jax.jit(lambda *a: paged_decode_attention(
        a[0], a[1], a[1], *a[2:], impl="flash", mesh=mesh8))(
        q1, lat, tables, index)
    k_all, _ = paged_gather_layer(lat, lat, tables)
    _close(got, _decode_ref(jnp.pad(q1, ((0, 0),) * 3 + ((0, Dh),)), k_all,
                            k_all, index, None, Dh ** -0.5)[..., :Dh], 2e-5)


# ---------------------------------------------------------------------------
# compile-cache helper and the smoke's no-chip contract
# ---------------------------------------------------------------------------


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> the helper sets nothing in code
    (jax reads the variable itself)."""
    from shellac_tpu.utils import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    """Unset -> one fixed directory inside the checkout, identical
    across calls (the path is part of the cache key)."""
    from shellac_tpu.utils import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert seen["jax_compilation_cache_dir"] == first


def test_chip_smoke_fails_without_a_chip():
    """On the CPU the smoke exits non-zero, says ok:false, runs no
    phase and never prints ok:true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert "phase" not in r.stdout
