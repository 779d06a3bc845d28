"""The engine's step recorder (obs.StepTrace): span trees, work counts,
the phase histograms derived from them, the ring, and the mirror onto the
profiler's clock. CPU, tiny model."""

import collections
import glob
import itertools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shellac_tpu.inference.batching import _bucket
from shellac_tpu.inference.cache import engine_class
from shellac_tpu.models import transformer
from shellac_tpu.models.registry import get_model_config
from shellac_tpu.obs import (
    LAUNCH_KINDS,
    SPAN_PHASE,
    STEP_COUNTS,
    STEP_PHASES,
    EngineMetrics,
    Registry,
    ServeMetrics,
)
from shellac_tpu.obs import tracereport
from shellac_tpu.obs.metrics import STEP_RING_CAPACITY

COMBOS = list(itertools.product((False, True), (False, True),
                                ("dense", "paged")))
IDS = [f"od{int(od)}-op{int(op)}-{b}" for od, op, b in COMBOS]
N_SLOTS, TICKS = 3, 2


@pytest.fixture(scope="module")
def model():
    cfg = get_model_config("tiny").replace(dtype="float32",
                                           param_dtype="float32")
    return cfg, transformer.init_params(cfg, jax.random.PRNGKey(0))


def _engine(model, backend, registry, **kw):
    cfg, params = model
    if backend == "paged":
        kw.setdefault("block_size", 16)
    return engine_class(backend)(
        cfg, params, n_slots=N_SLOTS, max_len=96, decode_ticks=TICKS,
        registry=registry, cache_backend=backend, **kw)


def _requests(cfg, n=7, seed=0):
    rng = np.random.default_rng(seed)
    return [(i, rng.integers(0, cfg.vocab_size,
                             size=int(rng.integers(3, 40))),
             int(rng.integers(2, 12))) for i in range(n)]


def _drive(eng, reqs, stop_after=None):
    outs = {}
    for rid, toks, max_new in reqs:
        eng.submit(rid, toks, max_new)
    n = 0
    while eng.pending and (stop_after is None or n < stop_after):
        outs.update(dict(eng.step()))
        n += 1
    return outs


@pytest.fixture(scope="module")
def runs(model):
    """One drained run per combination, made on first use."""
    done = {}

    def get(combo):
        if combo not in done:
            od, op, backend = combo
            reg = Registry()
            eng = _engine(model, backend, reg, overlap_decode=od,
                          overlap_prefill=op)
            reqs = _requests(model[0])
            done[combo] = (reg, reqs, _drive(eng, reqs))
        return done[combo]

    return get


@pytest.mark.parametrize("combo", COMBOS, ids=IDS)
def test_span_tree_is_well_formed(runs, combo):
    reg, _, _ = runs(combo)
    recs = list(reg.step_records)
    assert recs
    for rec in recs:
        assert rec.spans[rec.root][0] == "engine.step"
        for i, (name, start, end, parent, attrs) in enumerate(rec.spans):
            assert end >= start and len(name) <= 24
            if parent >= 0:
                # children lie inside their parent, which comes first
                assert parent < i
                assert rec.spans[parent][1] <= start
                assert end <= rec.spans[parent][2]
        under_step = [i for i in range(rec.root, len(rec.spans))
                      if i == rec.root or rec.spans[i][3] >= rec.root]
        for i in under_step:
            kids = sorted((sp[1], sp[2]) for sp in rec.spans if sp[3] == i)
            for (_, e0), (s1, _) in zip(kids, kids[1:]):
                assert e0 <= s1        # siblings do not overlap
        # engine.step = sum of children + self: the self times, by
        # phase, partition the step span exactly
        wall = (rec.end_ns - rec.start_ns) * 1e-9
        phases = rec.phases()
        assert set(phases) == set(STEP_PHASES)
        assert all(v >= 0 for v in phases.values())
        assert sum(phases.values()) == pytest.approx(wall, abs=1e-9)
        names = {sp[0] for sp in rec.spans}
        assert all(n in SPAN_PHASE or n.startswith("cache.")
                   or n == "engine.submit" for n in names)


@pytest.mark.parametrize("combo", COMBOS, ids=IDS)
def test_phase_histograms_still_add_up_to_the_steps_wall_time(runs, combo):
    reg, _, _ = runs(combo)
    recs = list(reg.step_records)
    wall = sum(r.end_ns - r.start_ns for r in recs) * 1e-9
    hists = [reg.get("shellac_step_phase_seconds", phase=p)
             for p in STEP_PHASES]
    assert all(h.count == len(recs) for h in hists)
    assert sum(h.sum for h in hists) == pytest.approx(wall, rel=1e-9)
    sync = reg.get("shellac_step_phase_seconds", phase="decode_sync").sum
    waited = sum(r.blocked_s() for r in recs)
    assert sync == pytest.approx(waited, rel=1e-9) and sync > 0
    # the host-overhead histogram: wall minus blocked, synced steps only
    synced = [r for r in recs if r.blocked_s() > 0]
    ho = reg.get("shellac_decode_host_overhead_seconds")
    assert ho.count == len(synced)
    assert ho.sum == pytest.approx(
        sum((r.end_ns - r.start_ns) * 1e-9 - r.blocked_s()
            for r in synced), rel=1e-6)


@pytest.mark.parametrize("combo", COMBOS, ids=IDS)
def test_counts_are_what_the_engine_did(runs, combo):
    reg, reqs, outs = runs(combo)
    recs = list(reg.step_records)
    tot = {k: sum(r.counts[k] for r in recs) for k in STEP_COUNTS}
    assert tot["tokens_delivered"] == sum(len(o) for o in outs.values())
    assert len(reqs) == len(outs)
    assert 0 < tot["decode_valid_ticks"] <= tot["decode_slot_ticks"]
    assert tot["decode_slot_ticks"] % (TICKS * N_SLOTS) == 0
    assert tot["prefill_tokens"] == sum(t.size for _, t, _ in reqs)
    assert tot["prefill_padded_tokens"] == sum(
        _bucket(t.size) for _, t, _ in reqs)
    assert tot["prefill_sorted_tokens"] == 0  # a model without experts
    assert tot["compiles"] > 0 and tot["compile_s"] > 0
    for k in STEP_COUNTS[:6]:
        assert reg.value(f"shellac_engine_{k}_total") == tot[k]
    assert reg.value("shellac_compile_events_total") >= tot["compiles"]
    # every admission names its request and its slot; the padded
    # length is its prefill's bucket
    admits = [sp for r in recs for sp in r.spans if sp[0] == "engine.admit"]
    assert sorted(sp[4]["rid"] for sp in admits) == [r[0] for r in reqs]
    by_rid = {rid: t.size for rid, t, _ in reqs}
    for sp in admits:
        assert sp[4]["prompt_tokens"] == by_rid[sp[4]["rid"]]
        assert sp[4]["padded_tokens"] == _bucket(by_rid[sp[4]["rid"]])
    assert len([sp for r in recs for sp in r.spans
                if sp[0] == "engine.submit"]) == len(reqs)


@pytest.mark.parametrize("dropless,chunk", [
    (True, None), (True, 16), (False, None), (False, 16),
], ids=["dropless-whole", "dropless-chunked", "capacity-whole",
        "capacity-chunked"])
def test_prefill_sorted_tokens_follow_the_models_rule(dropless, chunk):
    """The padded prompt rows whose expert FFN ran over the sorted
    routed rows: all of them on a dropless MoE model (one device, plain
    weights); with capacity buckets only the rows behind resident
    tokens (a chunk past the first), which must not drop. The engine
    asks the model's rule; the total is on /metrics."""
    import dataclasses

    cfg = get_model_config("tiny-moe").replace(dtype="float32",
                                               param_dtype="float32")
    cfg = cfg.replace(moe=dataclasses.replace(cfg.moe, dropless=dropless))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    reg = Registry()
    eng = engine_class("paged")(
        cfg, params, n_slots=2, max_len=96, decode_ticks=TICKS,
        registry=reg, cache_backend="paged", block_size=16,
        prefill_chunk=chunk)
    reqs = _requests(cfg, n=3, seed=3)
    _drive(eng, reqs)
    recs = list(reg.step_records)
    tot = {k: sum(r.counts[k] for r in recs) for k in STEP_COUNTS}
    assert tot["prefill_tokens"] == sum(t.size for _, t, _ in reqs)
    programs = [sp[4]["bucket"] for r in recs for sp in r.spans
                if sp[0] == "engine.prefill_dispatch"]
    assert tot["prefill_padded_tokens"] == sum(programs)
    if dropless:
        want = tot["prefill_padded_tokens"]
    else:  # every chunk but a prompt's first continues a cache
        first = sum(min(_bucket(min(t.size, chunk)), 96)
                    for _, t, _ in reqs) if chunk else sum(programs)
        want = tot["prefill_padded_tokens"] - first
        assert (want > 0) is bool(chunk)
    assert tot["prefill_sorted_tokens"] == want
    assert (f"shellac_engine_prefill_sorted_tokens_total {want}\n"
            in reg.render())


def test_tokens_of_resident_requests_are_counted_when_handed_out(model):
    """Cut a run short: the records hold the tokens of finished AND of
    still-resident requests (stats["tokens_generated"] only the
    former)."""
    reg = Registry()
    eng = _engine(model, "paged", reg, overlap_decode=True,
                  overlap_prefill=True)
    outs = _drive(eng, _requests(model[0], n=6, seed=3), stop_after=4)
    resident = [r for r in eng._slots if r is not None]
    assert resident
    delivered = sum(r.counts["tokens_delivered"] for r in reg.step_records)
    assert delivered == (sum(len(o) for o in outs.values())
                         + sum(len(r.out) for r in resident))
    assert delivered > eng.stats["tokens_generated"]
    eng.abort_all()


def test_the_ring_is_bounded_and_a_disabled_registry_records_nothing(model):
    reg = Registry()
    assert reg.step_records.maxlen == STEP_RING_CAPACITY
    reg.step_records = collections.deque(maxlen=4)
    _drive(_engine(model, "dense", reg), _requests(model[0]))
    assert len(reg.step_records) == 4
    steps = [r.step for r in reg.step_records]
    assert steps == sorted(steps) and steps[0] > 1

    off = Registry(enabled=False)
    eng = _engine(model, "dense", off)
    assert len(_drive(eng, _requests(model[0]))) == 7
    assert not off.step_records and not eng.obs.steps._spans
    assert off.value("shellac_engine_tokens_delivered_total") == 0.0


def test_an_idle_step_leaves_no_record_and_a_cancel_s_spans_wait(model):
    reg = Registry()
    eng = _engine(model, "paged", reg)
    eng.step()
    assert not reg.step_records
    cfg = model[0]
    eng.submit("a", np.arange(5) % cfg.vocab_size, 8)
    eng.step()
    assert eng.cancel("a")          # releases the slot outside a step
    eng.submit("b", np.arange(7) % cfg.vocab_size, 2)
    while eng.pending:
        eng.step()
    loose = [sp for r in reg.step_records for sp in r.spans[:r.root]]
    assert {"engine.submit", "cache.release_slot"} <= {sp[0] for sp in loose}


def test_a_step_that_raises_closes_its_root_span(model, monkeypatch):
    reg = Registry()
    eng = _engine(model, "dense", reg)
    eng.submit("a", np.arange(5) % model[0].vocab_size, 4)

    def boom(*a, **k):
        raise RuntimeError("prefill failed")

    with monkeypatch.context() as mp:
        mp.setattr(eng, "_prepare_slot", boom)
        with pytest.raises(RuntimeError):
            eng.step()
    steps = eng.obs.steps
    assert not steps._stack and steps._root is None
    assert not reg.step_records
    eng.submit("b", np.arange(7) % model[0].vocab_size, 2)
    while eng.pending:
        eng.step()
    # the next records are whole trees again
    assert all(r.spans[r.root][0] == "engine.step" and r.spans[r.root][3] == -1
               for r in reg.step_records)
    assert len(reg.step_records) > 0


def test_an_admission_joins_the_request_s_own_trace(model):
    reg = Registry()
    eng = _engine(model, "dense", reg)
    span = ServeMetrics(reg).trace(trace_id="0af7651916cd43dd8448eb211c80319c")
    eng.submit("r", np.arange(9) % model[0].vocab_size, 3, trace=span)
    while eng.pending:
        eng.step()
    admit = next(sp for r in reg.step_records for sp in r.spans
                 if sp[0] == "engine.admit")
    assert admit[4]["trace_id"] == span.trace_id and admit[4]["rid"] == "r"


def test_the_speculative_round_opens_the_same_window_spans(model):
    from shellac_tpu.inference.spec_batching import SpeculativeBatchingEngine

    cfg, params = model
    reg = Registry()
    eng = SpeculativeBatchingEngine(cfg, params, cfg, params, gamma=2,
                                    n_slots=2, max_len=64, registry=reg)
    outs = _drive(eng, _requests(cfg, n=3, seed=1))
    recs = list(reg.step_records)
    names = collections.Counter(sp[0] for r in recs for sp in r.spans)
    assert names["engine.wait_window"] == names["engine.dispatch_window"] > 0
    assert sum(r.counts["tokens_delivered"] for r in recs) == \
        sum(len(o) for o in outs.values())
    slot_ticks = sum(r.counts["decode_slot_ticks"] for r in recs)
    assert slot_ticks == names["engine.wait_window"] * 3 * 2
    assert 0 < sum(r.counts["decode_valid_ticks"] for r in recs) <= slot_ticks


def test_span_names_reach_the_profiler_s_host_plane(model, tmp_path):
    """Under a capture with the benchmark harness's options (no Python
    tracer) the spans are on /host:CPU with their attributes."""
    reg = Registry()
    eng = _engine(model, "paged", reg, overlap_decode=True,
                  overlap_prefill=True)
    _drive(eng, _requests(model[0], n=3, seed=5))      # compile first
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _drive(eng, [(f"p{r}", t, m) for r, t, m in _requests(model[0], n=4,
                                                              seed=6)])
    finally:
        jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                   recursive=True)
    host = next(p for p in jax.profiler.ProfileData.from_file(pb[0]).planes
                if p.name == "/host:CPU")
    seen = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name.startswith(("engine.", "cache.")):
                seen.setdefault(ev.name, dict(ev.stats))
    assert {"engine.step", "engine.fill", "engine.admit",
            "engine.prefill_dispatch", "engine.settle_prefills",
            "engine.wait_prefill", "engine.dispatch_window",
            "engine.wait_window", "engine.apply_window", "engine.submit",
            "cache.prepare_slot", "cache.ensure_blocks",
            "cache.release_slot"} <= set(seen)
    assert "slot" in seen["engine.admit"] and "ticks" in \
        seen["engine.dispatch_window"]
    # a dispatch span carries the number of the launch it makes
    assert int(seen["engine.dispatch_window"]["launch"]) >= 1
    assert int(seen["engine.prefill_dispatch"]["launch"]) >= 1


@pytest.mark.parametrize("preset,expect", [
    ("tiny", {"embed", "norm", "attn.qkv", "attn.rope", "kv.write",
              "kv.gather", "attn.core", "attn.out", "mlp", "unembed"}),
    ("tiny-deepseek", {"mla.absorb", "mla.latent_write", "moe.route",
                       "moe.sort", "moe.gemm", "moe.combine", "moe.shared",
                       "kv.gather", "attn.core", "mlp"}),
])
def test_the_compiled_decode_step_carries_the_named_scopes(preset, expect):
    from shellac_tpu.inference.kvcache import init_paged_cache

    cfg = get_model_config(preset).replace(dtype="float32",
                                           param_dtype="float32")
    params = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, 2, 9, 16, 4))

    def tick(params, cache, tokens):
        logits, cache = transformer.forward_with_cache(
            cfg, params, tokens, cache, attn_impl="ref")
        return jnp.argmax(logits[:, -1], axis=-1), cache

    text = jax.jit(tick).lower(
        params, cache, jax.ShapeDtypeStruct((2, 1), jnp.int32)
    ).as_text(debug_info=True)
    found = {tracereport.scope_of({"op_name": n})
             for n in re.findall(r'loc\("([^"]+)"', text)}
    assert expect <= found, sorted(expect - found)
    assert found - {None} <= set(tracereport.DEVICE_SCOPES)
    assert tracereport.scope_of(
        {"op_name": "jit(tick)/while/body/attn.qkv/norm/mul"}) == "norm"


def test_the_sampler_s_scope():
    from shellac_tpu.ops.sampling import sample_batched

    z, o = jnp.zeros((2,)), jnp.ones((2,))
    text = jax.jit(sample_batched).lower(
        jax.random.PRNGKey(0), jnp.zeros((2, 8)), o,
        jnp.full((2,), 8, jnp.int32), o, z).as_text(debug_info=True)
    assert "/sample/" in text and "sample" in tracereport.DEVICE_SCOPES


# ---- slot state reaches the device as arguments (slot_uploads) -------------

FLAVOURS = ("dense", "paged", "eva", "spec-paged")
_EVA_HF = dict(
    attention_class="eva", chunk_size=4, window_size=32, hidden_size=64,
    intermediate_size=128, num_attention_heads=4, num_key_value_heads=4,
    num_hidden_layers=2, num_pred_heads=3, vocab_size=256,
    rms_norm_eps=1e-5, rope_theta=100000, model_type="evabyte",
    norm_add_unit_offset=True, fp32_skip_add=True,
    max_position_embeddings=512, tie_word_embeddings=False,
)


def _flavour(model, flavour, reg, **kw):
    """-> (engine, tokens a page or None, pages a slot). Two slots, so
    that requests churn, over a pool that holds both at their largest
    (no admission is put back); every overlap on, as the benchmark's
    cells."""
    cfg, params = model
    kw = dict(dict(n_slots=2, decode_ticks=TICKS, registry=reg,
                   overlap_decode=True, overlap_prefill=True), **kw)
    if flavour == "dense":
        return engine_class("dense")(cfg, params, max_len=96, **kw), None, 6
    if flavour == "paged":
        return engine_class("paged")(
            cfg, params, max_len=96, block_size=16, pool_tokens=192,
            cache_backend="paged", **kw), 16, 6
    if flavour == "eva":
        import types

        from shellac_tpu.models.convert import config_from_hf

        ecfg = config_from_hf(types.SimpleNamespace(**_EVA_HF)).replace(
            dtype="float32", param_dtype="float32", remat=False)
        eparams = transformer.init_params(ecfg, jax.random.PRNGKey(0))
        return engine_class("eva")(
            ecfg, eparams, max_len=160, pool_tokens=320,
            cache_backend="eva", **kw), 32, 5
    # The speculative engine refuses both overlaps (its round has no
    # sync to defer): it runs the strict ordering.
    kw.update(overlap_decode=False, overlap_prefill=False, decode_ticks=1)
    return engine_class("paged", speculative=True)(
        cfg, params, cfg, params, gamma=2, max_len=96, block_size=16,
        pool_tokens=192, cache_backend="paged", **kw), 16, 6


def _quiet(rec):
    """A step that neither admitted, settled, released nor grew a slot."""
    return not {sp[0] for sp in rec.spans} & {
        "engine.admit", "engine.settle_prefills", "cache.release_slot",
        "cache.ensure_blocks"}


@pytest.mark.parametrize("flavour", FLAVOURS)
def test_admission_compiles_and_dispatches_nothing_of_its_own(model, flavour):
    """Past the first admission and the first window a step compiles
    NOTHING, whatever page count a request reserves (1 to the most a
    slot can hold): a slot's table row, settings and carried vectors
    are arguments of the prefill and the window, not programs of their
    own (one scatter shape a page count, before). And a step hands its
    programs a host array of slot state only around an admission, a
    settle, a release or growth: a release made after the step's window
    went out rides the next step's first program, so the step after
    counts too."""
    reg = Registry()
    eng, page, pages = _flavour(model, flavour, reg)
    vocab, prompt = eng.cfg.vocab_size, 5
    slack = 1 + eng._footprint_slack
    rng = np.random.default_rng(0)

    def request(rid, n_pages):
        # a footprint of exactly n_pages pages (16-token pages where
        # the backend has none)
        max_new = n_pages * (page or 16) - prompt - slack
        return rid, rng.integers(0, vocab, size=prompt), max_new

    _drive(eng, [request("warm", 1)])
    warm = len(reg.step_records)
    outs = _drive(eng, [request(p, p) for p in range(1, pages + 1)]
                  + [request(("again", p), p) for p in (pages, 1, 2)])
    assert len(outs) == pages + 3
    recs = list(reg.step_records)[warm:]
    assert len(recs) > pages
    assert [r.counts["compiles"] for r in recs] == [0] * len(recs)
    if page is not None:
        grown = {sp[4]["pages"] for r in recs for sp in r.spans
                 if sp[0] == "cache.ensure_blocks"}
        assert grown == set(range(1, pages + 1))
    quiet = [r.counts["slot_uploads"] for prev, r in zip(recs, recs[1:])
             if _quiet(prev) and _quiet(r)]
    assert quiet and not any(quiet)
    busy = sum(r.counts["slot_uploads"] for r in recs)
    # an admission arms its slot (the patch: one upload for all a window
    # finds pending) and, on a paged pool, its row rides its prefill and
    # its release's the next program; greedy requests of equal settings
    # never touch the settings matrix
    admits = sum(sp[0] == "engine.admit" for r in recs for sp in r.spans)
    assert admits == pages + 3
    assert 0 < busy <= admits * (3 if page is not None else 1)
    if page is not None:
        assert busy > admits
    assert reg.value("shellac_engine_slot_uploads_total") == sum(
        r.counts["slot_uploads"] for r in reg.step_records)


def test_the_settings_matrix_goes_up_only_when_a_value_changed(model):
    """Requests of the engine's own settings upload no settings; one
    with another temperature uploads the matrix once, and so does the
    plain request that takes its slot back."""
    reg = Registry()
    eng, _, _ = _flavour(model, "dense", reg, n_slots=1)
    rng = np.random.default_rng(1)
    prompt = lambda: rng.integers(0, eng.cfg.vocab_size, size=5)  # noqa: E731
    seen = []
    for kw in ({}, {}, {"temperature": 0.7, "seed": 3}, {}, {}):
        eng._samp_dev is None and eng._samp_arg()  # the first upload
        before = reg.value("shellac_engine_slot_uploads_total")
        changed = []
        real = eng._samp_arg

        def spy():
            changed.append(eng._samp_dev is None)
            return real()

        eng._samp_arg = spy
        eng.submit(len(seen), prompt(), 4, **kw)
        while eng.pending:
            eng.step()
        eng._samp_arg = real
        seen.append(sum(changed))
        assert reg.value("shellac_engine_slot_uploads_total") > before
    assert seen == [0, 0, 1, 1, 0]


@pytest.mark.parametrize("flavour", ["dense", "paged"])
def test_the_only_programs_a_step_builds_are_the_engine_s(model, flavour,
                                                          caplog):
    """Admission, release and the window's dispatch run no eager device
    op: once the engine is built, the only executables its steps ever
    build are its own prefill, chunk and window programs (an
    `x.at[i].set(v)`, a `random.split`, a `jnp.asarray` of a Python
    scalar each build, and then dispatch every time, a tiny program of
    their own), and each has a name of its own: a chunk program jitted
    through functools.partial is not `<unknown>`."""
    eng, _, _ = _flavour(model, flavour, Registry(), prefill_chunk=8)
    rng = np.random.default_rng(2)
    with jax.log_compiles():
        _drive(eng, [(i, rng.integers(0, eng.cfg.vocab_size, size=n), 5)
                     for i, n in enumerate((5, 21, 9))])
    built = set(re.findall(r"Compiling jit\((.*?)\)", caplog.text))
    chunk = {"dense": "_chunk_prefill_impl",
             "paged": "_prefix_prefill_impl"}[flavour]
    assert {"_decode_impl", chunk} <= built
    assert built <= {"_decode_impl", "_prefill_impl", chunk}, built


# ---- the engine's own device timeline: launch rows ------------------
# a row: [seq, kind, program, dispatched_ns, busy_from_ns, done_ns,
#         late, attrs]
DISPATCH_SPANS = ("engine.prefill_dispatch", "engine.dispatch_window")


def _rows(reg):
    return [row for r in reg.step_records for row in r.launches]


@pytest.mark.parametrize("combo", COMBOS, ids=IDS)
def test_every_program_dispatched_is_a_numbered_row(runs, combo):
    """Rows are in dispatch order, numbered from 1 without a gap, one
    for each dispatch span, which carries the row's number and whose
    attributes the row repeats."""
    reg, reqs, _ = runs(combo)
    rows = _rows(reg)
    assert [row[0] for row in rows] == list(range(1, len(rows) + 1))
    assert all(a[3] <= b[3] for a, b in zip(rows, rows[1:]))
    spans = [sp for r in reg.step_records for sp in r.spans
             if sp[0] in DISPATCH_SPANS]
    assert [sp[4]["launch"] for sp in spans] == [row[0] for row in rows]
    for sp, (seq, kind, program, disp, busy, done, late, attrs) in zip(spans,
                                                                        rows):
        assert kind in LAUNCH_KINDS and program.startswith("jit__")
        assert sp[1] <= disp <= sp[2]          # stamped inside its span
        if kind == "window":
            assert sp[0] == "engine.dispatch_window"
            assert attrs == {"ticks": TICKS, "rows": sp[4]["rows"]}
            assert "decode" in program
        else:
            assert "prefill" in program
            assert attrs["bucket"] == sp[4]["bucket"] >= attrs["tokens"] \
                == sp[4]["tokens"]
            assert attrs["offset"] == 0 and 0 <= attrs["slot"] < N_SLOTS
            assert 0 <= attrs["stalled_rows"] < N_SLOTS
    # every prompt went through one whole-prompt program
    assert sum(row[1] == "prefill" for row in rows) == len(reqs)
    assert reg.value("shellac_engine_launches_total",
                     kind="prefill") == len(reqs)
    assert reg.value("shellac_engine_launches_total", kind="window") == \
        sum(row[1] == "window" for row in rows)


@pytest.mark.parametrize("combo", COMBOS, ids=IDS)
def test_a_launch_has_landed_by_the_time_its_result_is_applied(runs, combo):
    """Landing happens inside the pulls the step makes anyway, oldest
    first; what is never pulled (the last window of a drained run,
    dispatched ahead and discarded) never lands."""
    reg, _, _ = runs(combo)
    rows = _rows(reg)
    waits = [sp for r in reg.step_records for sp in r.spans
             if sp[0] in ("engine.wait_window", "engine.wait_prefill")]
    landed = [row for row in rows if row[5]]
    assert all(row[1] == "window" for row in rows if not row[5])
    assert len(rows) - len(landed) <= (1 if combo[0] else 0)
    for seq, kind, _, disp, busy, done, late, _ in landed:
        assert disp <= busy <= done
        assert any(w[1] <= done <= w[2] for w in waits)
    # in dispatch order: each starts where its predecessor ended, or
    # at its own dispatch if that came later
    for a, b in zip(landed, landed[1:]):
        assert a[5] <= b[5] and b[4] == max(a[5], b[3])
    # a window's tokens are applied after it landed
    applies = [sp for r in reg.step_records for sp in r.spans
               if sp[0] == "engine.apply_window"]
    windows = [row for row in landed if row[1] == "window"]
    assert len(applies) == len(windows)
    assert all(row[5] <= sp[1] for row, sp in zip(windows, applies))
    timed = sum(reg.value("shellac_launch_device_seconds", kind=k)
                for k in LAUNCH_KINDS)
    assert timed <= len(landed) - 1
    assert reg.value("shellac_engine_launches_late_total") == \
        sum(row[6] for row in rows)


class _Handle:
    """What the recorder asks of a program's output."""

    def __init__(self, ready):
        self.ready, self.waited = ready, 0

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.waited += 1


def test_a_launch_found_ready_is_late_and_its_successor_is_not_timed():
    reg = Registry()
    steps = EngineMetrics(reg).steps
    a, b, c, d, e = (_Handle(r) for r in (False, True, False, False, False))
    steps.begin_step()
    with steps.span("engine.dispatch_window", launch=steps.next_launch) as sp:
        steps.launch("window", "jit__decode_impl", a, ticks=2, rows=1)
    assert sp.get("launch") == 1 and steps.next_launch == 2
    for h in (b, c):
        steps.launch("chunk", "jit__chunk_prefill_impl", h, tokens=8)
    steps.launch("window", "jit__decode_impl", d, ticks=2, rows=1)
    steps.launch("window", "jit__decode_impl", e, ticks=2, rows=1)
    steps.land(_Handle(False), ())      # not a queued one: nothing lands
    assert not any(h.waited for h in (a, b, c, d, e))
    steps.land(d, (d,))                 # everything up to d, not e
    assert [h.waited for h in (a, b, c, d, e)] == [1, 1, 1, 1, 0]
    steps.end_step(True)
    rows = reg.step_records[-1].launches
    assert [row[0] for row in rows] == [1, 2, 3, 4, 5]
    assert [row[6] for row in rows] == [False, True, False, False, False]
    assert all(row[3] <= row[4] <= row[5] for row in rows[:4])
    assert rows[4][4] == rows[4][5] == 0        # e: not landed yet
    # a has no predecessor, b was found ready, c follows b: only d is
    # timed
    assert reg.value("shellac_launch_device_seconds", kind="window") == 1
    assert reg.value("shellac_launch_device_seconds", kind="chunk") == 0
    assert reg.value("shellac_engine_launches_late_total") == 1
    assert reg.value("shellac_engine_launches_total", kind="window") == 3
    steps.land(e, (e,))                 # stamped in the ring, in place
    assert rows[4][5] >= rows[4][4] == rows[3][5]
    assert reg.value("shellac_launch_device_seconds", kind="window") == 2
    # the rows of a step that is dropped ride in the next record, and
    # dropping the queue leaves the next launch without a predecessor
    steps.begin_step()
    steps.launch("window", "jit__decode_impl", _Handle(False), ticks=2, rows=1)
    steps.end_step(False)
    f = _Handle(False)
    steps.drop_launches()
    steps.begin_step()
    steps.launch("window", "jit__decode_impl", f, ticks=2, rows=1)
    steps.land(f, (f,))
    steps.end_step(True)
    assert [row[0] for row in reg.step_records[-1].launches] == [6, 7]
    assert reg.step_records[-1].launches[0][5] == 0
    assert reg.value("shellac_launch_device_seconds", kind="window") == 2


def test_drained_time_is_what_lies_between_a_landing_and_a_later_dispatch(
        model):
    """While a window is always in flight the device is never drained
    (the next is dispatched before the last is found finished); across
    an idle engine it is, for as long as the host stayed away."""
    import time

    cfg = model[0]
    reg = Registry()
    eng = _engine(model, "dense", reg, overlap_decode=True,
                  overlap_prefill=True)
    eng.submit("a", np.arange(9) % cfg.vocab_size, 30)
    for _ in range(3):
        eng.step()
    before = reg.value("shellac_device_drained_seconds_total")
    n0 = len(_rows(reg))
    for _ in range(6):
        eng.step()
    rows = _rows(reg)[n0:]
    assert len(rows) == 6 and all(row[1] == "window" for row in rows)
    assert reg.value("shellac_device_drained_seconds_total") == before
    while eng.pending:
        eng.step()
    eng.step()                          # nothing to do
    drained = reg.value("shellac_device_drained_seconds_total")
    time.sleep(0.05)
    eng.submit("b", np.arange(7) % cfg.vocab_size, 3)
    while eng.pending:
        eng.step()
    assert reg.value("shellac_device_drained_seconds_total") >= \
        drained + 0.05


@pytest.mark.parametrize("backend,program", [
    ("dense", "jit__chunk_prefill_impl"),
    ("paged", "jit__prefix_prefill_impl"),
])
def test_a_chunked_prompt_is_one_row_a_chunk(model, backend, program):
    cfg = model[0]
    reg = Registry()
    eng = _engine(model, backend, reg, prefill_chunk=8, overlap_decode=True,
                  overlap_prefill=True)
    eng.submit("short", np.arange(5) % cfg.vocab_size, 12)
    eng.step()
    eng.step()
    eng.submit("long", np.arange(21) % cfg.vocab_size, 3)
    while eng.pending:
        eng.step()
    chunks = [row for row in _rows(reg) if row[1] == "chunk"]
    assert [(row[7]["offset"], row[7]["tokens"], row[7]["bucket"])
            for row in chunks] == [(0, 8, 16), (8, 8, 16), (16, 5, 16)]
    assert {row[2] for row in chunks} == {program}
    assert {row[7]["slot"] for row in chunks} == {1}
    # the short request was decoding and sat the chunks out
    assert all(row[7]["stalled_rows"] == 1 for row in chunks)
    assert all(row[5] for row in chunks)        # all landed at the settle
    spans = [sp for r in reg.step_records for sp in r.spans
             if sp[0] == "engine.prefill_dispatch" and "offset" in sp[4]]
    assert [sp[4]["launch"] for sp in spans] == [row[0] for row in chunks]


def test_the_speculative_round_is_one_row(model):
    from shellac_tpu.inference.spec_batching import SpeculativeBatchingEngine

    cfg, params = model
    reg = Registry()
    eng = SpeculativeBatchingEngine(cfg, params, cfg, params, gamma=2,
                                    n_slots=2, max_len=64, registry=reg)
    _drive(eng, _requests(cfg, n=3, seed=1))
    rows = _rows(reg)
    rounds = [row for row in rows if row[1] == "window"]
    n_waits = sum(sp[0] == "engine.wait_window"
                  for r in reg.step_records for sp in r.spans)
    assert len(rounds) == n_waits > 0
    assert all(row[2] == "jit__spec_round_impl" and row[7]["ticks"] == 3
               and row[5] for row in rounds)
    assert sum(row[1] == "prefill" for row in rows) == 3


def test_a_disabled_registry_keeps_no_queue_and_never_waits(model,
                                                           monkeypatch):
    """With the recorder off the engine makes no device call of the
    recorder's: no `is_ready`, no `block_until_ready`, nothing queued."""
    calls = collections.Counter()
    array = type(jnp.zeros(()))
    for name in ("is_ready", "block_until_ready"):
        real = getattr(array, name)

        def counting(self, _real=real, _name=name):
            calls[_name] += 1
            return _real(self)

        monkeypatch.setattr(array, name, counting)
    off = Registry(enabled=False)
    eng = _engine(model, "paged", off, overlap_decode=True,
                  overlap_prefill=True, prefill_chunk=16)
    assert len(_drive(eng, _requests(model[0]))) == 7
    assert not calls and not eng.obs.steps._inflight
    assert not eng.obs.steps._launches and eng.obs.steps.next_launch == 1
    on = Registry()
    eng = _engine(model, "paged", on, overlap_decode=True,
                  overlap_prefill=True, prefill_chunk=16)
    _drive(eng, _requests(model[0]))
    landed = sum(1 for row in _rows(on) if row[5])
    assert calls["block_until_ready"] == calls["is_ready"] == landed > 0


def test_abort_all_forgets_the_queued_launches(model):
    cfg = model[0]
    reg = Registry()
    eng = _engine(model, "dense", reg, overlap_decode=True,
                  overlap_prefill=True)
    eng.submit("a", np.arange(9) % cfg.vocab_size, 30)
    for _ in range(3):
        eng.step()
    assert eng.obs.steps._inflight
    eng.abort_all()
    assert not eng.obs.steps._inflight
    unlanded = [row[0] for row in _rows(reg) if not row[5]]
    assert unlanded
    eng.submit("b", np.arange(7) % cfg.vocab_size, 4)
    while eng.pending:
        eng.step()
    rows = _rows(reg)
    # what was dropped stays unstamped; what came after landed
    assert [row[0] for row in rows if not row[5]][:len(unlanded)] == unlanded
    assert any(row[5] for row in rows if row[0] > unlanded[-1])


@pytest.mark.parametrize("combo", [c for c in COMBOS if c[0] == c[1]],
                         ids=[i for c, i in zip(COMBOS, IDS) if c[0] == c[1]])
def test_streams_are_the_same_with_the_recorder_off(model, runs, combo):
    _, reqs, outs = runs(combo)
    od, op, backend = combo
    eng = _engine(model, backend, Registry(enabled=False), overlap_decode=od,
                  overlap_prefill=op)
    assert _drive(eng, reqs) == outs
