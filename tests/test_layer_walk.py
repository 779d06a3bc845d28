"""The one walk over a layers tree (transformer.scan_layers): every
layout visits its layers once each, in order, with the layout's
moe_layer / attn_kind for each index, and hands per-layer stacks through
as they came. No model is compiled: the layers trees here hold one leaf,
each layer's own index, so the step can tell whose parameters it got."""

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import PAGED_STACK_LAYOUTS

from shellac_tpu import get_model_config
from shellac_tpu.models.transformer import (
    first_k_layout,
    grouped_moe,
    n_routers,
    scan_layers,
)

L = 8
KINDS = {None: 0, "window": 1, "full": 2}


def _cfg(layout):
    preset, extra = PAGED_STACK_LAYOUTS[layout]
    return get_model_config(preset).replace(n_layers=L, **extra).validate()


def _layers(cfg, first):
    """A layers tree in cfg's layout whose only leaf is the layer index."""
    ids = first + jnp.arange(L, dtype=jnp.int32)
    if grouped_moe(cfg):
        groups = ids.reshape(L // cfg.moe_every, cfg.moe_every)
        return {"dense": {"id": groups[:, :-1]}, "moe": {"id": groups[:, -1]}}
    if first_k_layout(cfg):
        kk = cfg.first_k_dense
        return {"dense": {"id": ids[:kk]}, "moe": {"id": ids[kk:]}}
    return {"id": ids}


def _expected(cfg, i):
    """(moe_layer, attn_kind) of layer i, from the config's own fields."""
    if grouped_moe(cfg):
        return (i + 1) % cfg.moe_every == 0, None
    if first_k_layout(cfg):
        return i >= cfg.first_k_dense, None
    kind = (cfg.attn_pattern[i % len(cfg.attn_pattern)]
            if cfg.attn_pattern is not None else None)
    return cfg.moe is not None, kind


CASES = [
    pytest.param(layout, xs, first, id=f"{layout}-xs_{xs}-first{first}")
    for layout in PAGED_STACK_LAYOUTS
    for xs in ("none", "two") + (("per_kind",) if layout == "attn_pattern"
                                 else ())
    for first in (0, L)
]


@pytest.mark.parametrize("layout,xs_kind,first", CASES)
def test_walk_visits_each_layer_once_in_order(layout, xs_kind, first):
    cfg = _cfg(layout)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(L, 3)), jnp.float32)
    b = jnp.asarray(rng.integers(0, 99, size=(L, 2, 2)), jnp.int32)
    if xs_kind == "none":
        xs = ()
    elif xs_kind == "two":
        xs = (a, b)
    else:
        # One set of stacks a kind, each holding its kind's layers only.
        of = {k: np.array([i for i in range(L)
                           if _expected(cfg, i)[1] == k])
              for k in set(cfg.attn_pattern)}
        xs = {k: (a[rows], b[rows]) for k, rows in of.items()}

    def step(c, lp, li, xs_l, moe_layer, attn_kind):
        seen = {
            "li": li, "id": lp["id"], "moe": jnp.asarray(moe_layer),
            "kind": jnp.asarray(KINDS[attn_kind], jnp.int32),
        }
        return c * jnp.uint32(31) + li.astype(jnp.uint32), (xs_l, seen)

    c, out = scan_layers(
        cfg, _layers(cfg, first), jnp.uint32(7), step, xs=xs, first=first
    )

    order = list(range(first, first + L))
    want = 7
    for i in order:
        want = (want * 31 + i) % 2 ** 32
    assert int(c) == want  # once each, in order
    if xs_kind == "per_kind":
        # What the step returned comes back a kind too, each kind's
        # records its own layers', in layer order.
        assert set(out) == set(xs)
        ys = {k: v[0] for k, v in out.items()}
        rows = np.argsort(np.concatenate([of[k] for k in sorted(of)]))
        seen = {f: np.concatenate([np.asarray(out[k][1][f])
                                   for k in sorted(of)])[rows]
                for f in ("li", "id", "moe", "kind")}
    else:
        ys, seen = out
    assert list(seen["li"]) == order
    assert list(seen["id"]) == order  # each index with its own parameters
    moe, kind = zip(*(_expected(cfg, i - first) for i in order))
    assert list(seen["moe"]) == list(moe)
    assert list(seen["kind"]) == [KINDS[k] for k in kind]
    # ys come back stacked as xs went in.
    if xs_kind == "none":
        assert ys == ()
    elif xs_kind == "two":
        np.testing.assert_array_equal(ys[0], a)
        np.testing.assert_array_equal(ys[1], b)
    else:
        for k in xs:
            np.testing.assert_array_equal(ys[k][0], xs[k][0])
            np.testing.assert_array_equal(ys[k][1], xs[k][1])


@pytest.mark.parametrize("layout,routers", [
    ("plain", L), ("first_k_dense", L - 2), ("grouped_moe", None),
    ("attn_pattern", L),
])
def test_n_routers(layout, routers):
    cfg = _cfg(layout)
    if routers is None:
        routers = L // cfg.moe_every
    assert n_routers(cfg) == routers


@pytest.mark.parametrize("first", [0, L])
@pytest.mark.parametrize("layout,preset,extra", [
    ("flat", "tiny-moe", {}),
    ("first_k_dense", *PAGED_STACK_LAYOUTS["first_k_dense"]),
    ("grouped_moe", *PAGED_STACK_LAYOUTS["grouped_moe"]),
    ("attn_pattern", "tiny-gptoss", {}),
])
def test_experts_whole_hands_each_moe_layer_its_row(layout, preset, extra,
                                                    first):
    """With experts_whole the expert weights stay out of the scans: a
    MoE layer gets each as StackRow(the whole stack, its own row in
    it), every other leaf sliced as before; dense layers get none."""
    from shellac_tpu.ops.moe import EXPERT_STACKS, StackRow

    cfg = get_model_config(preset).replace(n_layers=L, **extra).validate()
    layers = _layers(cfg, first)
    moe_stack = layers["moe"] if "moe" in layers else layers
    for j, n in enumerate(EXPERT_STACKS):
        moe_stack[n] = 1000 * (j + 1) + moe_stack["id"]

    def step(c, lp, li, xs_l, moe_layer, attn_kind):
        got = jnp.full((len(EXPERT_STACKS),), -1, jnp.int32)
        if moe_layer:
            assert all(isinstance(lp[n], StackRow) for n in EXPERT_STACKS)
            assert all(lp[n].stack.shape == moe_stack[n].shape
                       for n in EXPERT_STACKS)
            got = jnp.stack([lp[n].stack[lp[n].row] for n in EXPERT_STACKS])
        else:
            assert not set(EXPERT_STACKS) & set(lp)
        return c, (li, lp["id"], got)

    _, (li, ids, got) = scan_layers(
        cfg, layers, jnp.uint32(0), step, first=first, experts_whole=True
    )
    order = list(range(first, first + L))
    assert list(li) == order and list(ids) == order
    for i, row in zip(order, np.asarray(got)):
        want = ([1000 * (j + 1) + i for j in range(len(EXPERT_STACKS))]
                if _expected(cfg, i - first)[0] else [-1] * len(EXPERT_STACKS))
        assert list(row) == want
