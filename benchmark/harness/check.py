"""The comparison that decides ``correct``.

Served model: once the window has closed and the program's state is freed,
a sample of the requests the window finished, drawn from the seed and with
the longest in it, is run once through the plain float32 reference (prompt
and served tokens together, teacher-forced). For every served token the gap
is the reference's best logit at that position minus the reference's logit
of the token that was served: 0 where the program chose the reference's
token, small where rounding in the served precision flipped a near tie,
large where the served path computed something else. The number compared is
the widest gap and the mean gap over the sample; their limits are the
cell's, in benchmark/cells/<cell>.json (a number with no limit there is
printed, not compared).
Valid for greedy tokens only, which is what the mixes send.
"""

from __future__ import annotations

import numpy as np


def int8_rows(x):
    """The control's arithmetic: symmetric int8 with one scale per row of the
    last axis, applied to every matmul operand of the reference."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def sample(finished, seed, k):
    """k finished requests: the longest and k-1 drawn from the seed."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0]) + len(finished[i][1])))
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rest = order[1:]
    pick = [order[0]] + [rest[i] for i in rng.permutation(len(rest))[: max(0, k - 1)]]
    return [finished[i] for i in pick]


def gaps(arch, hf, weights, prompt, out, pad_to, quant=None):
    """Reference logits, float32 (len(out), V), at the positions that
    predicted ``out``."""
    import jax.numpy as jnp

    n, m = len(prompt), len(out)
    toks = np.zeros((pad_to,), np.int32)
    toks[:n] = prompt
    toks[n:n + m - 1] = out[:-1]
    pos = np.arange(n - 1, n + m - 1)
    logits = arch.reference_logits(hf, weights, jnp.asarray(toks), jnp.asarray(pos),
                                   quant=quant)
    return np.asarray(logits, np.float32)


def _stats(g):
    g = np.concatenate(g)
    return {"max": float(g.max()), "mean": float(g.mean()),
            "not_best": int((g > 0).sum()), "n": int(g.size)}


def serving(r, res, control=False):
    lim = r.cell["limits"].get("limits", {})
    k = int(r.cell["traffic"].get("check_sample", 4))
    picked = sample(res["finished"], r.seed, k)
    numbers = {"requests_failed": {"value": res["failed"], "limit": 0}}
    if not picked:
        numbers["requests_compared"] = {"value": 0, "limit": ">=1"}
        return {"correct": False, "numbers": numbers}
    from benchmark.harness.traffic import max_footprint

    pad_to = -(-max_footprint(r.cell["traffic"]) // 128) * 128
    served, low = [], []
    for prompt, out in picked:
        out = list(out)
        logits = gaps(r.arch, r.hf, r.weights, prompt, out, pad_to)
        best, rows = logits.max(axis=-1), np.arange(len(out))
        served.append(best - logits[rows, np.asarray(out)])
        if control:
            # the same pass in the precision below the configuration's: the
            # gap of the token that the lower precision puts first
            first = gaps(r.arch, r.hf, r.weights, prompt, out, pad_to,
                         quant=int8_rows).argmax(axis=-1)
            low.append(best - logits[rows, first])
    st, st_low = _stats(served), _stats(low) if control else None
    numbers["requests_compared"] = {"value": len(picked), "limit": ">=1"}
    numbers["tokens_compared"] = {"value": st["n"], "limit": ">=1"}
    numbers["tokens_not_reference_best"] = {"value": st["not_best"], "limit": "reported"}
    ok = res["failed"] == 0 and st["n"] > 0
    for key in ("max", "mean"):
        name = "logit_gap_" + key
        if control:
            numbers["control_" + name] = {"value": st_low[key], "limit": lim.get(name)}
        limit = lim.get(name)
        numbers[name] = {"value": st[key], "limit": limit if limit is not None else "reported"}
        ok = ok and (limit is None or st[key] <= limit)
    ok = ok and any(lim.get("logit_gap_" + k2) is not None for k2 in ("max", "mean"))
    if control:
        numbers["control_tokens_not_reference_best"] = {
            "value": st_low["not_best"], "limit": "reported"}
    return {"correct": bool(ok), "numbers": numbers}
