"""Parity tests for the Pallas decode-attention kernels (interpret mode).

The kernels are graded against the masked reference path that the
engines used before: identical semantics (causal vs per-row positions
derived from cache lengths, optional sliding window, garbage beyond the
valid length ignored) across GQA, ragged lengths, s=1 and small-s
decode. Paged variants walk a shuffled block table. Caches are
head-major: dense (B, Hkv, L, D), pools (nb, Hkv, bs, D) — see
kvcache.py. Compiled-mode parity runs on the chip via
scripts/tpu_parity_decode.py (chip_smoke.py's kernels phase); the
chipless compile of the same kernels is tests/test_aot_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu.ops.decode_attention import (
    _decode_ref,
    decode_attention,
    paged_decode_attention,
)

B, L, H, HKV, D = 3, 128, 8, 4, 128


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize("s", [1, 4])
@pytest.mark.parametrize("window", [None, 20])
def test_dense_decode_matches_ref(s, window):
    ks = jax.random.split(jax.random.PRNGKey(s * 7 + (window or 0)), 3)
    q = _rand(ks[0], (B, s, H, D))
    ck = _rand(ks[1], (B, HKV, L, D))
    cv = _rand(ks[2], (B, HKV, L, D))
    index = jnp.array([0, 37, L - s], jnp.int32)  # empty, mid, full

    ref = _decode_ref(q, ck, cv, index, window, D ** -0.5)
    out = decode_attention(
        q, ck, cv, index, window=window, impl="flash", block_k=64,
        interpret=True,
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_dense_decode_mha_no_gqa():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _rand(ks[0], (2, 1, 4, D))
    ck = _rand(ks[1], (2, 4, L, D))
    cv = _rand(ks[2], (2, 4, L, D))
    index = jnp.array([5, 99], jnp.int32)
    ref = _decode_ref(q, ck, cv, index, None, D ** -0.5)
    out = decode_attention(
        q, ck, cv, index, impl="flash", block_k=64, interpret=True
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_dense_decode_ignores_garbage_tail():
    """Slots beyond index+s must not leak into the output."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = _rand(ks[0], (1, 1, H, D))
    ck = _rand(ks[1], (1, HKV, L, D))
    cv = _rand(ks[2], (1, HKV, L, D))
    index = jnp.array([10], jnp.int32)
    out1 = decode_attention(
        q, ck, cv, index, impl="flash", block_k=64, interpret=True
    )
    poison = jnp.full_like(ck[:, :, 11:], 1e4)
    ck2 = ck.at[:, :, 11:].set(poison)
    cv2 = cv.at[:, :, 11:].set(poison)
    out2 = decode_attention(
        q, ck2, cv2, index, impl="flash", block_k=64, interpret=True
    )
    np.testing.assert_allclose(out1, out2, atol=1e-6)


@pytest.mark.parametrize("s", [1, 3])
@pytest.mark.parametrize("window", [None, 20])
def test_paged_decode_matches_dense(s, window):
    """Paged kernel through a shuffled table == dense ref on the same kv."""
    bs = 16
    n_blocks = (L // bs) * B + 1  # + scratch block 0
    max_blocks = L // bs
    ks = jax.random.split(jax.random.PRNGKey(s * 5 + (window or 0)), 3)
    q = _rand(ks[0], (B, s, H, D))
    dense_k = _rand(ks[1], (B, L, HKV, D))
    dense_v = _rand(ks[2], (B, L, HKV, D))
    index = jnp.array([0, 37, L - s], jnp.int32)

    # Scatter the dense cache into a shuffled pool.
    rng = np.random.default_rng(0)
    ids = rng.permutation(np.arange(1, n_blocks))
    tables = ids.reshape(B, max_blocks)
    pool_k = np.zeros((n_blocks, HKV, bs, D), np.float32)
    pool_v = np.zeros((n_blocks, HKV, bs, D), np.float32)
    dkn = np.asarray(dense_k).transpose(0, 2, 1, 3)  # (B, HKV, L, D)
    dvn = np.asarray(dense_v).transpose(0, 2, 1, 3)
    for b in range(B):
        for j in range(max_blocks):
            pool_k[tables[b, j]] = dkn[b, :, j * bs:(j + 1) * bs]
            pool_v[tables[b, j]] = dvn[b, :, j * bs:(j + 1) * bs]

    ref = _decode_ref(
        q, dense_k.transpose(0, 2, 1, 3), dense_v.transpose(0, 2, 1, 3),
        index, window, D ** -0.5,
    )
    out = paged_decode_attention(
        q, jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(tables),
        index, window=window, impl="flash", interpret=True,
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def _scatter_pool(dense_k, dense_v, bs, shuffle_seed=0):
    """Scatter head-major dense caches (B, HKV, L, D) into a shuffled
    pool + tables (block 0 reserved scratch)."""
    b, hkv, l, d = dense_k.shape
    max_blocks = l // bs
    n_blocks = b * max_blocks + 1
    rng = np.random.default_rng(shuffle_seed)
    ids = rng.permutation(np.arange(1, n_blocks))
    tables = ids.reshape(b, max_blocks)
    pool_k = np.zeros((n_blocks, hkv, bs, d), np.float32)
    pool_v = np.zeros((n_blocks, hkv, bs, d), np.float32)
    dkn, dvn = np.asarray(dense_k), np.asarray(dense_v)
    for bi in range(b):
        for j in range(max_blocks):
            pool_k[tables[bi, j]] = dkn[bi, :, j * bs:(j + 1) * bs]
            pool_v[tables[bi, j]] = dvn[bi, :, j * bs:(j + 1) * bs]
    return (jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables, jnp.int32))


@pytest.mark.parametrize("window", [None, 100])
def test_paged_grouped_multi_group(window):
    """Grouped gather across num_groups > 1: the cross-group online-
    softmax carry, per-page liveness (zeroed dead pages), and windowed
    first-page skipping must all match the dense ref. The small-table
    tests only ever hit num_groups == 1."""
    from shellac_tpu.ops.decode_attention import _paged_group

    big_l, bs = 2048, 16
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    q = _rand(ks[0], (2, 1, H, D))
    dense_k = _rand(ks[1], (2, HKV, big_l, D))
    dense_v = _rand(ks[2], (2, HKV, big_l, D))
    # One short slot (first group boundary) and one near the end.
    index = jnp.array([7, big_l - 1], jnp.int32)
    pool_k, pool_v, tables = _scatter_pool(dense_k, dense_v, bs)
    assert tables.shape[1] // _paged_group(tables, pool_k, pool_v) > 1

    ref = _decode_ref(q, dense_k, dense_v, index, window, D ** -0.5)
    out = paged_decode_attention(
        q, pool_k, pool_v, tables, index, window=window, impl="flash",
        interpret=True,
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_paged_one_page_kernel_pinned():
    """The one-page kernel stays correct for 128-aligned head dims
    (it is the fallback when grouping cannot divide the table)."""
    from shellac_tpu.ops.decode_attention import _paged_flash

    bs = 16
    ks = jax.random.split(jax.random.PRNGKey(33), 3)
    q = _rand(ks[0], (2, 1, H, D))
    dense_k = _rand(ks[1], (2, HKV, L, D))
    dense_v = _rand(ks[2], (2, HKV, L, D))
    index = jnp.array([5, L - 1], jnp.int32)
    pool_k, pool_v, tables = _scatter_pool(dense_k, dense_v, bs)
    ref = _decode_ref(q, dense_k, dense_v, index, None, D ** -0.5)
    out = _paged_flash(
        q, pool_k, pool_v, tables, index, D ** -0.5, None, True
    )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_paged_group_respects_sublane_tiling():
    """bs=8 bf16 pools must take the one-page kernel (a grouped gather
    would land pages at sublane offset 8 of a 16-tiled bf16 VMEM tile,
    which Mosaic rejects compiled)."""
    from shellac_tpu.ops.decode_attention import _paged_group

    tables = jnp.zeros((2, 64), jnp.int32)

    def group(bs, dtype):
        pool = jnp.zeros((9, 4, bs, 128), dtype)
        return _paged_group(tables, pool, jnp.zeros_like(pool))

    assert group(8, jnp.bfloat16) == 1
    assert group(16, jnp.bfloat16) > 1
    assert group(8, jnp.float32) > 1
    assert group(16, jnp.int8) == 1


@pytest.mark.parametrize("hkv,bs,d,pages,shared,want", [
    pytest.param(8, 256, 128, 10, False, 2, id="mistral-7b-batch"),
    pytest.param(1, 128, 640, 20, True, 4, id="deepseek-v2-lite-batch"),
    pytest.param(8, 16, 128, 128, False, 32, id="shellac-1b-page16"),
    pytest.param(8, 128, 128, 16, False, 4, id="int8-default-page"),
    # The tile's bound, counted in elements whatever the row's width: a
    # 2048-lane row, and k and v of 1024 lanes each, fit 2 pages.
    pytest.param(1, 256, 2048, 8, True, 2, id="wide-row-bound-by-the-tile"),
    pytest.param(2, 128, 1024, 8, False, 2, id="k-and-v-apart-count-twice"),
    pytest.param(2, 128, 1024, 8, True, 4, id="v-in-the-k-tile-counts-once"),
])
def test_paged_group_is_sized_from_the_tile(hkv, bs, d, pages, shared, want):
    """Pages a grid step, from the shapes alone: about 512 rows, no more
    than keep a step's k and v tiles within PAGED_TILE_ELEMS (v's not
    counted where it is read out of the k tile), a count that divides
    the table. At the pools the chip timed these are the values it
    preferred (PERF.md, PR 28 and PR 32)."""
    from shellac_tpu.ops.decode_attention import _paged_group

    pool_k = jnp.zeros((3, hkv, bs, d), jnp.bfloat16)
    tables = jnp.zeros((2, pages), jnp.int32)
    assert _paged_group(tables, pool_k, None if shared else pool_k) == want


def test_auto_falls_back_to_ref_off_tpu():
    """impl='auto' off-TPU must take the ref path bit-for-bit."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (B, 1, H, D))
    ck = _rand(ks[1], (B, HKV, L, D))
    cv = _rand(ks[2], (B, HKV, L, D))
    index = jnp.array([4, 9, 50], jnp.int32)
    auto = decode_attention(q, ck, cv, index, impl="auto")
    ref = _decode_ref(q, ck, cv, index, None, D ** -0.5)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(ref))


def test_flash_rejects_bad_head_dim():
    # dh must be a multiple of 64 (dh=64 itself IS supported).
    q = jnp.zeros((1, 1, 4, 96))
    ck = jnp.zeros((1, 4, 64, 96))
    with pytest.raises(ValueError, match="unsupported"):
        decode_attention(q, ck, ck, jnp.zeros((1,), jnp.int32), impl="flash")


@pytest.mark.parametrize("paged", [False, True])
def test_head_dim_64_matches_ref(paged):
    """dh=64 models (Qwen2-0.5B class) run the kernels natively."""
    d64 = 64
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q = _rand(ks[0], (2, 1, H, d64))
    if not paged:
        ck = _rand(ks[1], (2, HKV, L, d64))
        cv = _rand(ks[2], (2, HKV, L, d64))
        index = jnp.array([9, 77], jnp.int32)
        ref = _decode_ref(q, ck, cv, index, None, d64 ** -0.5)
        out = decode_attention(
            q, ck, cv, index, impl="flash", block_k=64, interpret=True
        )
    else:
        bs = 16
        max_blocks = L // bs
        n_blocks = 2 * max_blocks + 1
        dense_k = _rand(ks[1], (2, HKV, L, d64))
        dense_v = _rand(ks[2], (2, HKV, L, d64))
        index = jnp.array([9, 77], jnp.int32)
        tables = np.arange(1, n_blocks).reshape(2, max_blocks)
        pool_k = np.zeros((n_blocks, HKV, bs, d64), np.float32)
        pool_v = np.zeros((n_blocks, HKV, bs, d64), np.float32)
        for b in range(2):
            for j in range(max_blocks):
                pool_k[tables[b, j]] = np.asarray(dense_k)[b, :, j*bs:(j+1)*bs]
                pool_v[tables[b, j]] = np.asarray(dense_v)[b, :, j*bs:(j+1)*bs]
        ref = _decode_ref(q, dense_k, dense_v, index, None, d64 ** -0.5)
        out = paged_decode_attention(
            q, jnp.asarray(pool_k), jnp.asarray(pool_v),
            jnp.asarray(tables, jnp.int32), index, impl="flash",
            interpret=True,
        )
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize(
    "bs,d", [(12, 128), (32, 96)],
    ids=["bad_page_size", "bad_head_dim"],
)
def test_paged_quant_fallback_warns_on_tpu_like_backend(monkeypatch, bs, d):
    """Int8 pools still WANT the kernel under auto (the ref fallback
    dequantizes gathered pages every tick), so silently losing it to a
    disqualifying shape must surface a PagedFallbackWarning."""
    import shellac_tpu.ops.decode_attention as da

    monkeypatch.setattr(da, "pallas_supported", lambda: True)
    n_blocks, max_blocks = 5, 4
    q = jnp.zeros((1, 1, 4, d))
    pool = jnp.zeros((n_blocks, 4, bs, d), jnp.int8)
    scale = jnp.ones((n_blocks, 4, bs), jnp.float32)
    tables = jnp.arange(1, 1 + max_blocks, dtype=jnp.int32)[None, :]
    index = jnp.zeros((1,), jnp.int32)
    with pytest.warns(da.PagedFallbackWarning, match="falling"):
        da.paged_decode_attention(
            q, pool, pool, tables, index, interpret=True,
            k_scale=scale, v_scale=scale,
        )


# (id, q heads, kv heads, head dim, page rows, pool dtype, kernel?)
AUTO_RULE = [
    ("bf16-page16-d128", 4, 4, 128, 16, jnp.bfloat16, False),
    ("bf16-page64-d128", 4, 4, 128, 64, jnp.bfloat16, False),
    ("bf16-page256-hkv8-d128", 32, 8, 128, 256, jnp.bfloat16, True),
    ("bf16-page128-d128", 4, 4, 128, 128, jnp.bfloat16, True),
    ("mla-hkv1-d576-page128", 16, 1, 576, 128, jnp.bfloat16, False),
    # ... which is why the latent pool is held 640 wide (held_width).
    ("mla-hkv1-d640-page128", 16, 1, 640, 128, jnp.bfloat16, True),
    ("mla-int8-hkv1-d576-page128", 16, 1, 576, 128, jnp.int8, False),
    ("bf16-page256-d64", 4, 4, 64, 256, jnp.bfloat16, False),
    ("int8-page128-d128", 4, 4, 128, 128, jnp.int8, True),
    ("int8-page256-d128", 4, 4, 128, 256, jnp.int8, True),
]


@pytest.mark.parametrize(
    "h,hkv,d,bs,dtype,kernel",
    [pytest.param(*c[1:], id=c[0]) for c in AUTO_RULE],
)
def test_paged_auto_rule(monkeypatch, h, hkv, d, bs, dtype, kernel):
    """impl="auto" on a Pallas-capable backend: a pool reads through
    the block table in the kernel where the rule says the kernel wins
    (int8 wherever it runs; bf16 with full-lane heads and pages long
    enough for a grid step to amortize), and through the gather
    everywhere else BY DECISION: no warning, but for an int8 pool the
    kernel cannot run on. The rule is a pure
    function of shapes and dtype, and the dispatcher does what it
    says."""
    import warnings as _w

    import shellac_tpu.ops.decode_attention as da

    q = jnp.zeros((1, 1, h, d), jnp.bfloat16)
    pool = jnp.zeros((3, hkv, bs, d), dtype)
    scale = (jnp.ones((3, hkv, bs), jnp.float32)
             if dtype == jnp.int8 else None)
    assert da.paged_kernel_under_auto(q.shape, pool.shape, pool.dtype) \
        is kernel
    # Off the TPU "auto" never takes a kernel, whatever the rule says.
    assert da.paged_decode_path(q.shape, pool.shape, pool.dtype) == "gather"
    monkeypatch.setattr(da, "pallas_supported", lambda: True)
    want = "paged_kernel" if kernel else "gather"
    assert da.paged_decode_path(q.shape, pool.shape, pool.dtype) == want
    assert da.paged_decode_path(q.shape, pool.shape, pool.dtype,
                                "ref") == "gather"

    took = []
    for name in ("_paged_group_flash", "_paged_flash"):
        real = getattr(da, name)
        monkeypatch.setattr(
            da, name,
            lambda *a, _real=real, _name=name, **k: (
                took.append(_name), _real(*a, **k))[1],
        )
    tables = jnp.asarray([[1, 2]], jnp.int32)
    index = jnp.asarray([bs + 3], jnp.int32)
    with _w.catch_warnings(record=True) as said:
        _w.simplefilter("always", da.PagedFallbackWarning)
        out = da.paged_decode_attention(
            q, pool, pool, tables, index, interpret=True,
            k_scale=scale, v_scale=scale,
        )
    # Only an int8 pool the kernel cannot take is worth a word: its
    # gather dequantizes every page every tick (the int8 latent pool).
    assert bool(said) is (dtype == jnp.int8 and not kernel), said
    assert out.shape == q.shape
    assert bool(took) is kernel, took


@pytest.mark.parametrize("backend,head_dim,page,want", [
    ("paged", 128, 256, "paged_kernel"),
    ("paged", 128, 16, "gather"),
    ("paged", 16, 256, "gather"),
    ("paged-int8", 128, 128, "paged_kernel"),
    # An MLA model's latent row, 512 + 64 lanes: held at 640 in the
    # bf16 pool, which the kernel reads; at its own 576 in the int8
    # pool, which the gather reads.
    ("paged", 576, 128, "paged_kernel"),
    ("paged-int8", 576, 128, "gather"),
    # An EVA model's ring and pool (its page is its window, 32 rows):
    # the kernel that moves live rows only where a head fills the lanes,
    # the XLA form over every row elsewhere.
    ("eva", 128, 32, "eva_kernel"),
    ("eva", 16, 32, "xla"),
])
def test_engine_records_its_decode_read_path(monkeypatch, backend, head_dim,
                                             page, want):
    """engine.stats["decode_attn"] is the dispatcher's own verdict for
    the decode program's shapes, recorded once at construction beside
    "cache_backend" (non-numeric: /stats shows it, the /metrics mirror
    skips it). Off the TPU every pool reads through the gather, and an
    EVA model's state through the XLA form."""
    import shellac_tpu.ops.decode_attention as da
    import shellac_tpu.ops.eva_attention as ea
    from shellac_tpu import get_model_config
    from shellac_tpu.config import MLAConfig
    from shellac_tpu.inference.batching import PagedBatchingEngine
    from shellac_tpu.models import transformer

    if backend == "eva":
        # 8 heads of float32: whole sublane tiles, as the kernel asks.
        cfg = get_model_config("tiny-eva").replace(
            n_heads=8, head_dim=head_dim, n_layers=1, dtype="float32",
            param_dtype="float32").validate()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))

        def build_eva():
            return PagedBatchingEngine(cfg, params, n_slots=2, max_len=256,
                                       cache_backend="eva")

        assert build_eva().stats["decode_attn"] == "xla"
        monkeypatch.setattr(ea, "pallas_supported", lambda: True)
        eng = build_eva()
        assert eng.stats["decode_attn"] == want
        assert eng.stats["cache_backend"] == "eva"
        c = eng._cache
        assert ea.eva_decode_path((2, 8, head_dim), c.k.shape, c.pk.shape,
                                  c.k.dtype) == want
        return
    if head_dim == 576:
        cfg = get_model_config("tiny-mla").replace(
            n_layers=1, max_seq_len=512,
            mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                          qk_nope_head_dim=16, qk_rope_head_dim=64,
                          v_head_dim=16)).validate()
    else:
        cfg = get_model_config("tiny-gqa").replace(
            head_dim=head_dim, n_layers=1, max_seq_len=512).validate()
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))

    def build():
        return PagedBatchingEngine(cfg, params, n_slots=2, max_len=512,
                                   block_size=page, cache_backend=backend)

    assert build().stats["decode_attn"] == "gather"
    monkeypatch.setattr(da, "pallas_supported", lambda: True)
    eng = build()
    assert eng.stats["decode_attn"] == want
    assert eng.stats["cache_backend"] == backend
    pool = eng._cache.k.shape[1:]
    assert pool[-1] == (640 if (head_dim, backend) == (576, "paged")
                        else head_dim)
    assert da.paged_decode_path(
        (2, 1, cfg.n_heads, pool[-1]), pool, eng._cache.k.dtype
    ) == want


# (kv heads, q heads a kv head, row, held row, page rows, pages a slot,
#  pages a grid step, k served as v)
CELLS = {
    # mistral-7b-batch's pool.
    "gqa": (8, 4, D, D, 256, 4, 2, False),
    # deepseek-v2-lite-batch's: the MLA latent, 576 lanes held at 640,
    # one pool as k and as v (values are its first 512 lanes).
    "latent": (1, 16, 576, 640, 128, 20, 4, True),
    # ouro-2.6b-batch-reason's: MHA 16/16 (one q head a kv head) over
    # FIVE 128-row pages a slot. The tile's bound allows two pages a
    # step, two does not divide five, so this is the one-page kernel
    # (`decode_paged`); on the chip two pages a step over a table
    # extended by a dead entry took the same time (PERF.md, PR 33).
    "mha-odd-table": (16, 1, D, D, 128, 5, 1, False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("s", [1, 3])
@pytest.mark.parametrize("cell", list(CELLS))
def test_paged_kernel_at_serving_shape_matches_ref(cell, s, dtype):
    """The grouped kernel at a benchmark cell's pool (8 kv heads x 128,
    256-row pages, 2 pages a grid step, 4 query heads a kv head; and
    the latent pool, one 640-lane row a token under 16 heads, 4
    128-row pages a step, the k tile read as v): lengths that end
    mid-page, on a page's last row, on a page's first row, at one row
    and at the view's end, and a slot whose table row was never
    allocated (every entry the scratch page 0). Every live row of
    every slot is attended, no dead one: the pool's unowned pages hold
    NaN, which any read past a slot's length would carry into its
    output. The latent's q arrives 576 wide against rows whose pad
    lanes hold zeros, as the model's does."""
    from shellac_tpu.ops.decode_attention import _paged_group

    hkv, per_kv, d, held, page, pages, group, k_as_v = CELLS[cell]
    view = page * pages
    index = jnp.asarray(
        [page + 44, page - s, page, 0, view - s, 0], jnp.int32)
    n = index.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(40 + s), 3)
    q = _rand(ks[0], (n, s, per_kv * hkv, d)).astype(dtype)
    dense_k = _rand(ks[1], (n, hkv, view, d)).astype(dtype)
    dense_k = jnp.pad(dense_k, ((0, 0),) * 3 + ((0, held - d),))
    dense_v = (dense_k if k_as_v
               else _rand(ks[2], (n, hkv, view, d)).astype(dtype))
    pool_k, pool_v, tables = _scatter_pool(dense_k, dense_v, page)
    assert _paged_group(tables, pool_k, None if k_as_v else pool_v) == group
    # Pages wholly past a slot's length: NaN in the pool (never to be
    # read), zero in the reference's dense view (masked there). Rows
    # past the length inside a live page keep their finite garbage in
    # both, as a served pool's do.
    live_pages = (np.arange(pages)[None, :] * page
                  < np.asarray(index)[:, None] + s)  # (n, pages)
    keep = jnp.asarray(np.repeat(live_pages, page, axis=1))
    dead = np.ones(pool_k.shape[0], bool)
    dead[0] = False
    dead[np.asarray(tables)[live_pages]] = False
    # The last slot was never allocated: its table row names page 0,
    # which holds finite scratch (the allocator's convention: zeros in
    # a held row's pad lanes there too).
    tables = tables.at[n - 1].set(0)

    def served(pool, dense):
        pool = jnp.where(jnp.asarray(dead)[:, None, None, None], jnp.nan,
                         pool).astype(dtype)
        dense = jnp.where(keep[:, None, :, None], dense, 0)
        return (pool.at[0, ..., :d].set(1.0),
                dense.at[n - 1, ..., :d].set(1.0))

    pool_k, dense_k = served(pool_k, dense_k)
    pool_v, dense_v = (pool_k, dense_k) if k_as_v else served(pool_v, dense_v)

    scale = (192 if k_as_v else d) ** -0.5
    ref = _decode_ref(jnp.pad(q, ((0, 0),) * 3 + ((0, held - d),)),
                      dense_k, dense_v, index, None, scale)
    out = paged_decode_attention(
        q, pool_k, pool_v, tables, index, impl="flash", interpret=True,
        scale=scale,
    )
    assert out.shape == q.shape
    # What the model reads of the latent's output: its first 512 lanes.
    lanes = 512 if k_as_v else d
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert np.isfinite(np.asarray(out, np.float32)).all()
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[..., :lanes],
        np.asarray(ref, np.float32)[..., :lanes], atol=tol, rtol=tol,
    )


def test_paged_supported_shapes_do_not_warn():
    import warnings as _w

    import shellac_tpu.ops.decode_attention as da

    q = jnp.zeros((1, 1, 4, 128))
    pool = jnp.zeros((5, 4, 16, 128))
    tables = jnp.arange(1, 5, dtype=jnp.int32)[None, :]
    index = jnp.zeros((1,), jnp.int32)
    with _w.catch_warnings():
        _w.simplefilter("error", da.PagedFallbackWarning)
        # Off-TPU: pallas_supported() is False, so no warning and the
        # ref path runs.
        da.paged_decode_attention(q, pool, pool, tables, index)
