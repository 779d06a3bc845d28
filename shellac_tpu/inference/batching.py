"""Continuous batching: many requests share one fixed device batch.

A serving engine cannot wait for a whole batch to finish: requests
arrive at different times with different prompt and output lengths. This
engine keeps a **fixed-shape** slot batch on device — (n_slots,
max_len) KV cache — and multiplexes requests onto it:

  - a free slot is filled by prefilling one request's prompt into a
    single-sequence mini-cache and scattering it into the slot (two
    jitted programs; prompt lengths bucket to powers of two to bound
    recompiles);
  - every tick runs ONE jitted decode step over all slots; inactive
    slots compute garbage that is masked on host and their cache
    lengths are frozen, so shapes never change;
  - a request leaves its slot on EOS or at its max_new budget, and the
    slot is immediately refillable — no head-of-line blocking.

This is the TPU analogue of GPU continuous batching: instead of paging,
the cache is a dense per-slot ring the scheduler rolls back by writing
`lengths` (kvcache.py's write-at-own-length contract makes stale slots
self-healing). The per-tick host sync is one (n_slots,) int32 fetch.

Greedy output for any request is exactly what the single-request Engine
produces — the scheduling is invisible to the math (tested).

The reference repo for this project is empty (SURVEY.md §0); there is no
upstream serving engine to cite.
"""

from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from shellac_tpu.config import ModelConfig
from shellac_tpu.inference.cache import PoolExhausted
from shellac_tpu.inference.kvcache import (
    PagedKVCache,
    QuantPagedKVCache,
    kv_field_names,
    paged_write_prompt,
    scatter_slot,
    slot_view,
)
from shellac_tpu.inference.qos import WeightedFairQueue
from shellac_tpu.models import transformer
from shellac_tpu.obs import EngineMetrics, get_registry
from shellac_tpu.ops.sampling import NEG_INF, sample_batched
from shellac_tpu.parallel.sharding import make_shardings


@dataclass
class _Request:
    rid: Any
    tokens: np.ndarray  # (S,) int32 prompt
    max_new: int
    stop: Optional[List[List[int]]] = None  # token-id stop sequences
    # Per-request sampling settings, resolved to concrete values at
    # submit time (top_k is always >= 1; vocab size = disabled).
    temperature: float = 0.0
    top_k: int = 1
    top_p: float = 1.0
    min_p: float = 0.0
    # EOS is banned from sampling until this many tokens are emitted
    # (0 = off; stop sequences still end generation regardless).
    min_tokens: int = 0
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    prompt_logprobs: bool = False
    plp: Optional[List[float]] = None
    # Per emitted token, the engine's top-K alternatives as
    # ([ids], [logprobs]) pairs (engines built with top_logprobs > 0).
    tlp: Optional[List] = None
    seed: Optional[int] = None
    # Disaggregated serving: run the prompt, sample the first token,
    # then FREEZE the slot instead of decoding — the KV-migration
    # exporter (inference/disagg.py) ships the slot to a decode
    # replica and releases it. Frozen slots never join decode windows.
    prefill_only: bool = False
    # Additive per-token logit biases applied before sampling (OpenAI
    # semantics); logprobs still report the raw distribution.
    logit_bias: Optional[Dict[int, float]] = None
    # Structured decoding: a compiled constraints.TokenDFA whose
    # transition table masks the logits each step (None = free).
    constraint: Optional[Any] = None
    # Observability span (obs.RequestTrace) riding the request through
    # the pipeline; the engine marks prefill-start and first-token on
    # it. None when the caller doesn't trace (offline batch runs).
    trace: Optional[Any] = None
    # Generated tokens so far. INVARIANT (the server's streaming path
    # reads this between engine steps): `out` only ever grows, except
    # that a stop-sequence match removes exactly the matched suffix
    # (<= the longest stop length) once, at completion. Streaming holds
    # back that many tokens so an emitted token can never be retracted.
    out: List[int] = field(default_factory=list)
    # Logprob of each emitted token under the raw (unfiltered,
    # untempered) model distribution — same convention as the
    # single-request Engine. Populated only when the engine was built
    # with logprobs=True; kept in lockstep with `out`.
    lps: List[float] = field(default_factory=list)
    # Multi-tenant QoS: owning tenant id (None = untagged), priority
    # class (inference/qos.py PRIORITY_CLASSES; lower = better) and
    # DRR weight steering the weighted-fair pending queue, and the
    # monotonic enqueue time the preemption driver reads wait ages
    # from.
    tenant: Optional[str] = None
    qos_class: int = 1
    qos_weight: float = 4.0
    t_queued: float = 0.0
    # Preempt-and-park: True while this mid-decode request is frozen
    # in its slot awaiting export (frozen_decodes). Frozen slots never
    # join decode windows and never settle through _finish_check —
    # they leave through export_slot -> release_frozen, exactly like
    # prefill_only freezes.
    frozen: bool = False

    def hit_stop(self) -> Optional[int]:
        """Length of the matched stop suffix of `out`, or None."""
        for seq in self.stop or ():
            n = len(seq)
            if n and len(self.out) >= n and self.out[-n:] == seq:
                return n
        return None


def named_program(fn):
    """`fn` with a `__name__` for jax.jit to name the program after:
    its own, or, for a functools.partial (which has none, and would
    compile as `jit__unknown`), that of the method it wraps."""
    if not hasattr(fn, "__name__"):
        fn.__name__ = fn.func.__name__
    return fn


def program_name(jitted) -> str:
    """The name a device trace shows for a jitted program."""
    return "jit_" + jitted.__name__


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


class _PrefillFlight:
    """Host-side record of ONE dispatched (not yet settled) prefill.

    `arrays` are the prefill program's device outputs — (first token,
    its raw logprob, the top-K alternatives or None, and the
    prompt-logprob payload or None) — held as futures: nothing is
    synced at dispatch. `req` captures which request owned the slot AT
    DISPATCH so settlement can discard results for slots whose request
    was cancelled/replaced while the prefill was in flight (identity
    check, the same arbitration _DecodeWindow settlement uses). The
    prompt-logprob payload is either the whole-prompt (pad,) score
    array or the chunked path's list of (in-chunk scores, size,
    boundary score) pieces — both ride the ONE batched settle pull."""

    __slots__ = ("slot", "req", "arrays")

    def __init__(self, slot, req, arrays):
        self.slot = slot
        self.req = req
        self.arrays = arrays  # (first, lp, tl, plp) futures


class _DecodeWindow:
    """Host-side record of ONE dispatched (not yet synced) decode
    window.

    `arrays` are the window's device outputs (dispatched async — jax
    returns futures immediately); `pairs` captures which request owned
    each active slot AT DISPATCH, so settlement can discard results for
    slots whose request was cancelled/replaced while the window was in
    flight (identity check, the same arbitration the supervisor uses
    for stale generations). `ticks` is the window's decode_ticks at
    dispatch (the auto-tuner may retune between windows)."""

    __slots__ = ("pairs", "ticks", "arrays")

    def __init__(self, pairs, ticks, arrays):
        self.pairs = pairs      # [(slot, _Request)] active at dispatch
        self.ticks = ticks
        self.arrays = arrays    # (toks, lps, tlvs, tlis, acts) futures


class BatchingEngine:
    """Fixed-slot continuous batching over one model.

    Storage policy is delegated to a cache backend
    (inference/cache): the engine holds the decode ALGORITHM — slot
    scheduling, the jitted window programs, sampling state — and asks
    `self.cache_backend` for construction, sharding axes, slot
    residency, and capacity accounting. `cache_backend` accepts a
    registry name ("dense", "dense-int8", "rolling", "rolling-int8";
    the paged subclass takes "paged"/"paged-int8") or a constructed
    CacheBackend; the legacy kv_quant / rolling_window kwargs remain
    as aliases that resolve through the same registry.
    """

    # Backend families this engine class can drive (the paged subclass
    # overrides — its jitted programs scatter through block tables).
    _backend_family = ("dense", "dense-int8", "rolling", "rolling-int8")
    # Can this engine score prompts (prompt_logprobs)? Subclasses whose
    # prefill skips scoring forwards (speculative drafts) set False.
    _scores_prompts = True
    # Extra per-slot residency past prompt + max_new + 1 the engine's
    # window may write (the speculative mixin sets gamma + 1: a verify
    # round writes g+1 positions before rolling back).
    _footprint_slack = 0
    # Can decode_ticks be retuned post-construction? The speculative
    # engine pins it to 1 (a verify round already emits up to gamma+1
    # tokens per sync) and sets this False so the auto-tuner skips it.
    _decode_ticks_tunable = True
    # Columns of the host matrix `_samp` (per-slot settings the device
    # never writes): the first _SAMP_FLOATS are float32 bitcast to
    # int32, the rest int32. `_unpack_slot_samp` is the one decoder.
    _SAMP_COLS = ("temperature", "top_p", "min_p", "presence", "frequency",
                  "top_k", "seed", "coff")
    _SAMP_FLOATS = 5
    # Fields of `_patch`, in the order the window carries them:
    # (_cur, _smin, _srem, _sdone, _cstate).
    _PATCH_FIELDS = ("cur", "min_rem", "rem", "done", "cstate")

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int = 8,
        max_len: Optional[int] = None,
        temperature: float = 0.0,
        top_k: Optional[int] = None,
        top_p: Optional[float] = None,
        min_p: Optional[float] = None,
        eos_id: Optional[int] = None,
        seed: int = 0,
        attn_impl: str = "auto",
        decode_ticks="auto",
        overlap_decode: bool = False,
        overlap_prefill: bool = False,
        max_prefills_per_step: Optional[int] = None,
        prefill_chunk: Optional[int] = None,
        logprobs: bool = False,
        top_logprobs: int = 0,
        mesh=None,
        kv_quant: Optional[str] = None,
        rolling_window: bool = False,
        pp_pipeline: bool = False,
        cache_backend=None,
        registry=None,
    ):
        top_logprobs = int(top_logprobs or 0)
        if top_logprobs < 0 or top_logprobs > 32:
            raise ValueError(
                f"top_logprobs={top_logprobs}: must be in [0, 32]"
            )
        if top_logprobs and not logprobs:
            raise ValueError(
                "top_logprobs needs logprobs=True (the alternatives "
                "ride the same scoring pass)"
            )
        # decode_ticks: K decode steps per host sync, or "auto" — the
        # serving entry points run inference.autotune against the live
        # mesh at startup and write the winner back; until tuned,
        # "auto" behaves exactly like 1 (bit-identical), so library
        # construction stays cheap and deterministic.
        self.decode_ticks_requested = decode_ticks
        if decode_ticks == "auto":
            decode_ticks = 1
        elif isinstance(decode_ticks, str):
            raise ValueError(
                f"decode_ticks={decode_ticks!r}: need an int >= 1 or "
                "'auto'"
            )
        if decode_ticks < 1:
            raise ValueError(f"decode_ticks must be >= 1, got {decode_ticks}")
        if max_prefills_per_step is not None and max_prefills_per_step < 1:
            raise ValueError("max_prefills_per_step must be >= 1")
        # prefill_chunk: chunk size, None (whole prompts), or "auto" —
        # the serving entry points sweep candidates on the live engine
        # (inference.autotune.autotune_prefill_chunk) and write the
        # winner back; until tuned, "auto" behaves exactly like None.
        self.prefill_chunk_requested = prefill_chunk
        if prefill_chunk == "auto":
            prefill_chunk = None
        elif isinstance(prefill_chunk, str):
            raise ValueError(
                f"prefill_chunk={prefill_chunk!r}: need an int >= 1, "
                "None, or 'auto'"
            )
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len or cfg.max_seq_len
        self.eos_id = eos_id
        self.attn_impl = attn_impl
        # With a mesh the engine runs sharded, same contract as the
        # single-request Engine: params already placed (shard_params),
        # KV cache sharded over kv_heads, slot batch replicated (the
        # scheduler owns it). Shardings are pinned at the jit
        # boundaries so GSPMD keeps one layout across every program.
        self.mesh = mesh
        # Storage policy: resolve the cache backend (registry name,
        # constructed instance, or the legacy kv_quant/rolling_window
        # aliases — one resolution path, shared with the CLI).
        from shellac_tpu.inference.cache import (
            CacheBackend,
            make_backend,
            resolve_backend_name,
        )

        # Chunked-prefill continuations READ the ring before their own
        # rows age out; the ring carries that chunk as slack.
        self._chunk_slack = prefill_chunk or 1
        wants_paged = any(n.startswith("paged")
                          for n in self._backend_family)
        if isinstance(cache_backend, CacheBackend):
            # A constructed instance carries its own policy + geometry;
            # engine kwargs that contradict it must refuse as loudly as
            # the name path does — silently dropped knobs are exactly
            # the capacity incidents the registry exists to prevent.
            if kv_quant is not None and kv_quant != cache_backend.kv_quant:
                raise ValueError(
                    f"kv_quant={kv_quant!r} conflicts with the "
                    f"{cache_backend.name!r} backend instance"
                )
            if rolling_window and not cache_backend.is_rolling:
                raise ValueError(
                    f"rolling_window={rolling_window!r} conflicts with "
                    f"the {cache_backend.name!r} backend instance"
                )
            if (cache_backend.n_slots != n_slots
                    or cache_backend.max_len != self.max_len):
                raise ValueError(
                    f"{cache_backend.name!r} backend instance geometry "
                    f"(n_slots={cache_backend.n_slots}, "
                    f"max_len={cache_backend.max_len}) does not match "
                    f"the engine (n_slots={n_slots}, "
                    f"max_len={self.max_len})"
                )
            backend = cache_backend
        else:
            name = resolve_backend_name(
                cache_backend, kv_quant=kv_quant,
                rolling_window=rolling_window,
            )
            if name not in self._backend_family:
                raise ValueError(
                    f"{type(self).__name__} drives cache backends "
                    f"{self._backend_family}; {name!r} needs a "
                    "different engine class — resolve it through "
                    "inference.cache.engine_class"
                )
            backend = make_backend(
                name, cfg, n_slots, self.max_len,
                chunk_slack=self._chunk_slack,
            )
        if backend.is_paged != wants_paged:
            raise ValueError(
                f"{type(self).__name__} cannot drive the "
                f"{backend.name!r} backend (paged={backend.is_paged})"
            )
        backend.bind(self)
        self.cache_backend = backend
        # Legacy attributes, derived from the backend — the jitted
        # programs and external callers keep reading them.
        self.kv_quant = backend.kv_quant
        self.rolling_window = backend.is_rolling
        # Token-level pipelined decode on pp meshes: slots split into
        # pp staggered groups so every pipeline stage computes a
        # different group each microtick instead of idling pp-1 of the
        # time (inference/pp_pipeline.py). Bit-exact per slot; greedy
        # parity is tested against the unpipelined engine.
        self.pp_pipeline = bool(pp_pipeline)
        self._pp = 0
        if self.pp_pipeline:
            from shellac_tpu.inference.pp_pipeline import (
                validate_pp_pipeline,
            )

            backend.check_feature("pp_pipeline")

            self._pp = validate_pp_pipeline(
                cfg, mesh, n_slots, self.kv_quant, self.rolling_window,
                self.cache_backend.is_paged,
            )
        self.decode_ticks = decode_ticks
        # Overlapped dispatch: with overlap_decode=True, step() keeps a
        # two-deep window pipeline — the NEXT decode window is
        # dispatched (async) before the previous one's host sync is
        # paid, so the device computes window k+1 while the host
        # settles window k's requests and runs admissions. Requests
        # admitted during a step join at the NEXT window boundary, and
        # per-request outputs stay token-identical to the strict
        # ordering (greedy and per-request-seeded sampling; the shared
        # unseeded stream draws in a different order, like any
        # scheduling change). False = strict ordering, bit-identical to
        # the pre-overlap engine.
        self.overlap_decode = bool(overlap_decode)
        # Dispatched-but-unsynced decode windows, oldest first. Depth
        # is bounded at 2 by step()'s structure (pre-dispatch exactly
        # one window before settling exactly one).
        self._windows: deque[_DecodeWindow] = deque()
        # Overlapped prefill dispatch: with overlap_prefill=True, an
        # admission dispatches its prefill program and returns — the
        # slot is marked prefill-pending (excluded from decode windows
        # until settled), the host immediately admits the next request
        # or dispatches the next decode window, and every in-flight
        # prefill settles in ONE batched device_get at the next step
        # boundary (first tokens, logprobs, top-K, and the opt-in
        # prompt-logprob payload all ride the same pull; TTFT is
        # recorded at settle). False = each prefill settles inside its
        # own admission, bit-identical to the pre-overlap engine.
        self.overlap_prefill = bool(overlap_prefill)
        # Dispatched-but-unsettled prefills, oldest first.
        self._pflights: List[_PrefillFlight] = []
        # Test/bench seam, the prefill-side twin of _window_hooks:
        # None, or an object with on_prefill_dispatch(flight) /
        # before_prefill_sync(flights).
        self._prefill_hooks = None
        # Test/bench seam (inference.autotune.SimulatedHostLatency):
        # None, or an object with on_dispatch(window) / before_sync
        # (window) — a sleep-injecting RPC shim that lets CPU CI
        # imitate a host-bound decode loop.
        self._window_hooks = None
        # Cap prefills per engine step: a burst of queued prompts would
        # otherwise run n_slots sequential prefill programs before the
        # next decode tick, stalling every active request's output for
        # the whole burst. None = no cap (drain-oriented batch use);
        # servers should set 1-2 to bound decode latency jitter.
        self.max_prefills_per_step = max_prefills_per_step
        # Chunked prefill: prompts longer than this many tokens prefill
        # incrementally, one chunk program per step (each chunk counts
        # against max_prefills_per_step), so ONE long prompt can no
        # longer stall every active request for its whole prefill the
        # way the admission cap alone cannot prevent. None = whole
        # prompts in one program (the drain-oriented default).
        self.prefill_chunk = prefill_chunk
        self._prefilling: Dict[int, int] = {}  # slot -> tokens written
        self._chunk_jit: Dict[Any, Any] = {}  # keyed (pad, fresh)
        # logprobs=True: every emitted token's logprob (raw-logit
        # log_softmax, the Engine convention) is tracked; finished
        # requests deposit theirs here, keyed by rid, for the server
        # (or any caller) to pop.
        self.logprobs = logprobs
        # K alternatives recorded per generated token (0 = off). The
        # engine computes its max for every request; per-request k is
        # the renderer's slice.
        self.top_logprobs = top_logprobs
        self.finished_top_logprobs: Dict[Any, List] = {}
        self.finished_logprobs: Dict[Any, List[float]] = {}
        # prompt_logprobs=True requests deposit the prompt's per-token
        # logprobs here on completion (keyed by rid), like
        # finished_logprobs.
        self.finished_prompt_logprobs: Dict[Any, List[float]] = {}
        # ---- per-slot state -------------------------------------------
        # Where it lives: the HOST. Nothing below is ever written by an
        # eager device op on the step's path: a change reaches the
        # device as an argument of a program the step dispatches anyway
        # (the prefill, the chunk, the decode window), so admission and
        # release put nothing between two programs and the chip does
        # not drain around them. Three kinds:
        #
        #  * settings the device never writes (temperature, top_p,
        #    min_p, the penalty coefficients, top_k, the seed, the
        #    constraint row offset): ONE host int32 matrix `_samp`
        #    (floats bitcast, columns _SAMP_COLS), uploaded when a
        #    value changed (`_samp_dev` is None) and otherwise handed
        #    to the window as the device copy it already saw;
        #  * vectors the window carries (`_cur`, `_smin`, `_srem`,
        #    `_sdone`, `_cstate`): device arrays rebound from each
        #    window's outputs. A new tenant's values (and a freeze, a
        #    thaw, a cleared DFA state) are written into the host
        #    `_patch` by `_patch_slot`, ride the next window's one
        #    host array and are selected in at its entry;
        #  * the (n_slots, vocab) matrices (`_sbias`, `_scounts`): only
        #    allocated, and only written, for a request that has a
        #    logit bias or penalties.
        self._sbias: Optional[jax.Array] = None
        self._zero_bias_row = jnp.zeros((1, cfg.vocab_size), jnp.float32)
        self._slot_bias: List[Optional[Dict[int, float]]] = [None] * n_slots
        # Remaining min_tokens (EOS ban countdown, decremented on
        # device inside the decode scan).
        self._smin = jnp.zeros((n_slots,), jnp.int32)
        # Device-side stop/budget decisions: per-slot remaining max_new
        # budget and a sticky done flag, threaded through the decode
        # window so a slot that samples EOS (or exhausts its budget)
        # mid-window FREEZES on device — no overshoot compute, no
        # cache-length drift — and the window reports per-tick validity
        # flags so the host slices instead of scanning. Stop SEQUENCES
        # stay a host decision (arbitrary token lists); a stop-matched
        # slot decodes to the end of its window like before, and the
        # host discards the tail.
        self._srem = jnp.zeros((n_slots,), jnp.int32)
        self._sdone = jnp.zeros((n_slots,), bool)
        # OpenAI-style repetition penalties over GENERATED tokens:
        # per-slot token-count matrix (lazily allocated, like the bias
        # matrix); the presence/frequency coefficients are columns of
        # `_samp`. Counts update on device inside the decode scan.
        self._scounts: Optional[jax.Array] = None
        self._slot_pen: List[bool] = [False] * n_slots
        # Structured decoding: active constrained slots' TokenDFA
        # tables stacked into one device table (rows bucketed so the
        # decode trace is reused across request churn), a per-slot row
        # offset (-1 = unconstrained; a column of `_samp`), and
        # per-slot DFA state that advances on device inside the decode
        # scan.
        self._slot_dfa: List[Optional[Any]] = [None] * n_slots
        self._ctrans: Optional[jax.Array] = None
        self._con_dirty = False
        self._cstate = jnp.zeros((n_slots,), jnp.int32)
        # Shared dummy table for unconstrained decode steps (the hot
        # path): allocated once, like _zero_bias_row.
        self._dummy_ctrans = jnp.full(
            (1, cfg.vocab_size + 1), -1, jnp.int32
        )
        # Engine-level sampling defaults; submit() can override any of
        # them per request. Each slot's effective settings are a row of
        # `_samp`, so one decode tick serves greedy and sampled
        # requests side by side. Per-request deterministic sampling:
        # seed (-1 = unseeded, use the shared stream) + the slot's
        # generated-token count at the start of each decode window
        # (host-known: len(req.out)).
        self._defaults = {
            "temperature": float(temperature),
            # top_k resolves once, here: None (disabled) = full vocab.
            "top_k": int(top_k) if top_k is not None else cfg.vocab_size,
            "top_p": float(top_p) if top_p is not None else 1.0,
            "min_p": float(min_p) if min_p is not None else 0.0,
        }
        self._validate_sampling(self._defaults, "engine defaults")
        self._samp = np.zeros((n_slots, len(self._SAMP_COLS)), np.int32)
        self._samp_dev: Optional[jax.Array] = None
        for slot in range(n_slots):
            self._write_samp(slot, seed=-1, coff=-1, **self._defaults)
        # The carried vectors' pending writes: column 0 a bit a field
        # of _PATCH_FIELDS, then the fields' values.
        self._patch = np.zeros((n_slots, 1 + len(self._PATCH_FIELDS)),
                               np.int32)
        # The construction seed is retained (not just consumed into the
        # key) so the multi-host epoch resync can re-key deterministically
        # per (seed, epoch) instead of collapsing every job onto the
        # same post-recovery stream.
        self.seed = int(seed)
        self._key = jax.random.PRNGKey(seed)

        # The backend builds the device cache (dense rows, int8 rows +
        # scales, a rolling ring, or the paged block pool — the engine
        # never branches on the kind). On a mesh it is born in its
        # shardings: built on the default device and placed afterwards,
        # the whole cache — and a second copy while device_put slices
        # it — sits on the first chip (measured on four v5e chips: a
        # 1.9 GB transient on chip 0 for a 1.07 GB cache).
        if mesh is None:
            self._cache = self.cache_backend.init_cache()
        else:
            self._cache = jax.jit(
                self.cache_backend.init_cache,
                out_shardings=make_shardings(
                    mesh, self.cache_backend.logical_axes()
                ),
            )()
        self._cur = jnp.zeros((n_slots,), jnp.int32)  # next input token
        # The pending queue is a weighted-fair queue over priority
        # classes (deficit round robin on token costs). With a single
        # class in play — every engine that never tags qos_class —
        # it is FIFO-identical to the deque it replaced.
        self._queue: WeightedFairQueue = WeightedFairQueue()
        self._slots: List[Optional[_Request]] = [None] * n_slots
        # Prefill-only requests whose prompt KV is resident and frozen,
        # awaiting export (rid -> slot). The serving scheduler drains
        # this after each step: export_slot -> release_frozen.
        self.frozen_prefills: Dict[Any, int] = {}
        # Preempted mid-decode requests frozen in place awaiting
        # export (rid -> slot). A SEPARATE table from frozen_prefills:
        # the scheduler's export policies differ (prefill_only slots
        # settle their client with a migration/park receipt; preempted
        # slots keep their client attached across park -> resume).
        self.frozen_decodes: Dict[Any, int] = {}
        self._prefill_jit: Dict[int, Any] = {}  # bucketed by padded S
        # Lazily built single-request Engine sharing these params:
        # the dense beam_search() entry point (the paged subclass
        # searches its own block pool instead).
        self._beam_delegate = None
        # The decode jit is built lazily (first _decode_tokens): with a
        # mesh its out_shardings pin the cache layout, and the paged
        # subclass swaps in its own cache (different pytree) after this
        # constructor runs. Two decode variants (one trace each):
        # greedy_only skips the batched sampler's full-vocab sorts when
        # every active request is greedy — the common serving default.
        self._decode = None
        # The backend built the final cache pytree above, so shardings
        # pin once, here, for every backend kind (the paged subclass no
        # longer swaps a transient dense cache).
        self._mesh_setup()
        # Serving observability (read by the HTTP /stats endpoint).
        # Written only by the engine-owning thread; plain ints so
        # cross-thread reads are merely possibly-stale, never torn.
        self.stats: Dict[str, int] = {
            "requests_completed": 0,
            "tokens_generated": 0,
            "engine_steps": 0,
            "prefills": 0,
            "prefill_chunks": 0,
            "requests_cancelled": 0,
            # Mirrored as shellac_engine_* gauges at /metrics scrape
            # time: the live decode_ticks (the auto-tuner rewrites it)
            # and the window pipeline depth (2 = overlapped dispatch,
            # 1 = strict ordering) so the tier's load scoring can see
            # how each replica runs its hot loop.
            "decode_ticks": decode_ticks,
            "overlap_depth": 2 if self.overlap_decode else 1,
            # Admission-side pipeline knobs, mirrored like the decode
            # ones: is prefill dispatch overlapped, and what chunk size
            # is live (0 = whole prompts; the auto-tuner rewrites it).
            "overlap_prefill": 1 if self.overlap_prefill else 0,
            "prefill_chunk": prefill_chunk or 0,
            # The active storage policy (registry name). Non-numeric,
            # so the /metrics stat mirror skips it; the server exposes
            # it as the shellac_engine_cache_backend_info gauge label.
            "cache_backend": self.cache_backend.name,
            # Disaggregated serving: migration legs served by this
            # engine, plus the backend's resident bytes per KV token —
            # the tier's transfer-cost estimate reads the mirrored
            # shellac_engine_kv_bytes_per_token gauge.
            "kv_exports": 0,
            "kv_imports": 0,
            "kv_bytes_per_token": self.cache_backend.bytes_per_token(),
            # Multi-tenant QoS: mid-decode freezes ordered by the
            # serving scheduler's preempt-and-park driver.
            "preemptions": 0,
        }
        self.stats.update(self.cache_backend.initial_stats())
        # How decode_ticks was chosen: "fixed" (explicit int) or
        # "auto" (pending tune; autotune rewrites it to "auto-tuned").
        self.decode_ticks_source = (
            "auto" if self.decode_ticks_requested == "auto" else "fixed"
        )
        # How prefill_chunk was chosen, mirroring decode_ticks_source:
        # "fixed" (explicit int or None) or "auto" (pending tune;
        # autotune_prefill_chunk rewrites it to "auto-tuned").
        self.prefill_chunk_source = (
            "auto" if self.prefill_chunk_requested == "auto" else "fixed"
        )
        # Richer observability (histograms + gauges) over the shared
        # registry — the Prometheus-facing counterpart of `stats`.
        # Everything it records is host-side and per engine STEP, never
        # per token and never inside a jitted program. `obs.steps` is
        # the step span recorder (obs.StepTrace): always reached
        # through `self.obs`, which the auto-tuner swaps for a disabled
        # bundle while it probes.
        self.obs = EngineMetrics(
            registry if registry is not None else get_registry()
        )

    # ---- sharding ----------------------------------------------------

    def _mesh_setup(self) -> None:
        """Pin the cache's shardings on the mesh, whatever its backend
        kind. Called once self._cache holds its final pytree (end of
        the constructor). Re-called, it just recomputes the sharding
        tree and invalidates the lazily-built decode jit.
        """
        if self.mesh is None:
            self._cache_sh = None
            return
        # The backend that built the cache provides its axes — the
        # sharding tree can never desync from the pytree.
        self._cache_sh = make_shardings(
            self.mesh, self.cache_backend.logical_axes()
        )
        self._cache = jax.device_put(self._cache, self._cache_sh)
        self._decode = None

    def _jit_cache_program(self, fn, n_tail: int, **jit_kw):
        """jit a program returning (cache, <n_tail others>), pinning the
        cache's shardings on the mesh (no-op unsharded) and donating
        the cache argument: every program threads cache-in -> cache-out
        (arg index 1, after params) and the caller rebinds self._cache
        from the result immediately. Donation lets the result alias the
        argument; whether the program also leaves the buffer alone in
        between is the program's doing. The paged programs do:
        forward_with_cache carries the pool through its layer loops,
        every writer goes through kvcache.paged_write, and
        tests/test_paged_inplace.py (with test_aot_compile.py, for the
        TPU's compiler) holds the compiled decode window to no
        pool-sized temporary, copy, transpose or per-layer slice.

        The program is named after the method it runs, a
        functools.partial of one included (jax calls that `_unknown`):
        `jit_<name>` is what a device trace shows and what the
        program's launch rows say (program_name)."""
        if self._cache_sh is not None:
            jit_kw["out_shardings"] = (self._cache_sh,) + (None,) * n_tail
        return jax.jit(named_program(fn), donate_argnums=(1,), **jit_kw)

    def _launch_prompt(self, kind: str, program, first, **attrs) -> None:
        """A whole-prompt (`prefill`) or continuation (`chunk`) program
        was just dispatched; `first` is the token it samples, the
        output its flight keeps. `stalled_rows` is taken here: the
        slots that hold a decoding request and sit out while the
        program runs."""
        if self.obs.registry.enabled:
            self.obs.steps.launch(
                kind, program_name(program), first,
                stalled_rows=sum(self._active_rows()), **attrs)

    # ---- slot state: host writers, program-side readers -------------

    def _write_samp(self, slot: int, **values) -> None:
        """Set columns (named as _SAMP_COLS) of `slot`'s row of the
        host settings matrix. The device copy is dropped only if a
        value changed, so a stream of requests with equal settings
        uploads nothing."""
        row = self._samp[slot]
        for name, v in values.items():
            col = self._SAMP_COLS.index(name)
            bits = (np.float32(v).view(np.int32)
                    if col < self._SAMP_FLOATS else np.int32(v))
            if row[col] != bits:
                row[col] = bits
                self._samp_dev = None

    def _samp_arg(self):
        """The settings matrix as the window's argument: uploaded (one
        host-to-device copy) only if a value changed since the last
        window took it."""
        if self._samp_dev is None:
            # A copy: the CPU backend may alias a host buffer.
            self._samp_dev = jnp.asarray(self._samp.copy())
            self.obs.steps.count(slot_uploads=1)
        return self._samp_dev

    def _unpack_slot_samp(self, samp):
        """Decode the (n_slots, len(_SAMP_COLS)) settings matrix inside
        a program: a dict of (n_slots,) vectors keyed as _SAMP_COLS,
        the float columns bit-equal to the float32 the host wrote."""
        nf = self._SAMP_FLOATS
        fl = jax.lax.bitcast_convert_type(samp[:, :nf], jnp.float32)
        cols = [fl[:, i] for i in range(nf)]
        cols += [samp[:, i] for i in range(nf, len(self._SAMP_COLS))]
        return dict(zip(self._SAMP_COLS, cols))

    def _patch_slot(self, slot: int, **fields) -> None:
        """THE writer of a slot's carried vectors (`_cur`, `_smin`,
        `_srem`, `_sdone`, `_cstate`; named as _PATCH_FIELDS): record
        the values on the host; the next window selects them in at its
        entry. Nothing is dispatched. Every caller that arms, freezes,
        thaws or clears a slot goes through here: the prefill settle,
        release, preemption, a disaggregated import, the server's
        thaw."""
        row = self._patch[slot]
        for name, v in fields.items():
            bit = self._PATCH_FIELDS.index(name)
            row[0] |= 1 << bit
            row[1 + bit] = int(v)

    @property
    def _carry(self):
        """The vectors one window hands the next, in _PATCH_FIELDS
        order: device arrays, rebound from each window's outputs."""
        return (self._cur, self._smin, self._srem, self._sdone,
                self._cstate)

    @_carry.setter
    def _carry(self, carry) -> None:
        (self._cur, self._smin, self._srem, self._sdone,
         self._cstate) = carry

    def _window_arg(self, active_rows, gen0):
        """The window's ONE per-call host array, (n_slots, 2 +
        _patch's columns) int32: is the slot active, its generated-
        token count at the window's start, and the pending patch,
        which is cleared here (the window about to be dispatched
        applies it)."""
        win = np.empty((self.n_slots, 2 + self._patch.shape[1]), np.int32)
        win[:, 0] = active_rows
        win[:, 1] = gen0
        win[:, 2:] = self._patch
        if self._patch[:, 0].any():
            self.obs.steps.count(slot_uploads=1)
            self._patch[:] = 0
        return jnp.asarray(win)

    @staticmethod
    def _apply_patch(carry, win):
        """Inside a window program: the carried vectors, in
        _PATCH_FIELDS order, with the patched fields of `win` (see
        _window_arg) selected in."""
        mask = win[:, 2]
        return tuple(
            jnp.where((mask >> bit) & 1 == 1,
                      win[:, 3 + bit].astype(vec.dtype), vec)
            for bit, vec in enumerate(carry)
        )

    @staticmethod
    def _enter(cache, tables, key):
        """First thing inside every engine program. -> (the cache with
        the backend's per-slot indirection as the program received it
        (`CacheBackend.slot_tables()`: the paged block table, whose
        rows the host owns; None from a backend without one), the half
        of the engine's PRNG key the program hands back, the half it
        draws from): the halves an eager `key, sub = split(key)` gave,
        so every stream is what it was."""
        carried, sub = jax.random.split(key)
        if tables is not None:
            cache = cache.replace(tables=tables)
        return cache, carried, sub

    # ---- jitted programs --------------------------------------------

    def _fresh_mini(self, length: int):
        """Batch-1 cache of the engine's cache kind (prefill scratch),
        built by the backend so it always matches the slot cache."""
        return self.cache_backend.init_mini(length)

    @staticmethod
    def _plp_within(logits, tokens):
        """Each token's logprob given its IN-ROW predecessor: position
        t scores from logits row t-1; position 0 (no predictor in this
        row) reports 0.0. The single definition the whole-prompt AND
        chunked paths share, so their scoring cannot drift."""
        lps = jax.nn.log_softmax(logits[0, :-1].astype(jnp.float32))
        tok_lp = jnp.take_along_axis(
            lps, tokens[0, 1:][:, None], axis=-1
        )[:, 0]
        return jnp.zeros((tokens.shape[1],), jnp.float32).at[1:].set(tok_lp)

    def _prefill_impl(self, params, cache, tokens, prompt_len, slot, key,
                      samp, tables, want_plp: bool = False):
        """Prefill one request and scatter it into `slot` of `cache`.
        Like every engine program it starts with _enter: it takes the
        engine's PRNG key (and hands the carried half back) and the
        backend's slot tables.

        want_plp additionally returns the PROMPT's per-token logprobs
        (token t given tokens[:t]; position 0 has no predictor and
        reports 0.0 — the server renders it as null)."""
        cache, key_out, key = self._enter(cache, tables, key)
        mini = self._fresh_mini(self.max_len)
        logits, mini = transformer.forward_with_cache(
            self.cfg, params, tokens, mini, new_tokens_len=prompt_len,
            fresh_cache=True, attn_impl=self.attn_impl, mesh=self.mesh,
        )
        last = jnp.take_along_axis(
            logits, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1
        )[0, 0]
        first, first_lp = self._sample_first(key, last, samp)
        plp = (self._plp_within(logits, tokens) if want_plp
               else jnp.zeros((tokens.shape[1],), jnp.float32))
        tlv, tli = self._first_tl(last)
        return (scatter_slot(cache, mini, slot), key_out, first, first_lp,
                plp, tlv, tli)

    def _decode_impl(self, params, cache, key, carry, win, samp, tables,
                     bias, counts, ctrans, greedy_only: bool = False,
                     use_bias: bool = False, use_pen: bool = False,
                     use_seed: bool = False, use_con: bool = False):
        """THE decode-window program: everything a window needs to know
        about the slots arrives as its arguments, so the step
        dispatches nothing around it.

        `key`: the engine's PRNG key, split by _enter (the carried half
        is returned). `carry`: the vectors a window hands the next (cur,
        min_rem, rem, done, cstate). `win`: the call's one host array
        (_window_arg: active, gen0, and the pending patch, selected
        into `carry` first). `samp`: the host settings matrix
        (_samp_arg). `tables`: the backend's slot tables or None.
        `bias` / `counts` / `ctrans`: the (n_slots, vocab) matrices and
        the stacked DFA table, or their shared dummies.

        Returns (cache, key, carry, counts, tokens (K, n_slots),
        logprobs, top-K values, top-K ids, acts (K, n_slots) validity
        flags); the scan itself is _decode_scan (or, on a pp mesh,
        _decode_impl_pp)."""
        cache, key, sub = self._enter(cache, tables, key)
        cur, min_rem, rem, done, cstate = self._apply_patch(carry, win)
        s = self._unpack_slot_samp(samp)
        scan = self._decode_impl_pp if self.pp_pipeline else self._decode_scan
        (cache, toks, lps, min_rem, counts, cstate, tlvs, tlis, rem, done,
         acts) = scan(
            params, cache, cur, win[:, 0] != 0, sub,
            (s["temperature"], s["top_k"], s["top_p"], s["min_p"], bias,
             min_rem, s["presence"], s["frequency"], counts, s["seed"],
             win[:, 1], ctrans, s["coff"], cstate, rem, done),
            greedy_only=greedy_only, use_bias=use_bias, use_pen=use_pen,
            use_seed=use_seed, use_con=use_con,
        )
        return (cache, key, (toks[-1], min_rem, rem, done, cstate), counts,
                toks, lps, tlvs, tlis, acts)

    def _decode_scan(self, params, cache, cur, active, key, samp,
                     greedy_only: bool = False, use_bias: bool = False,
                     use_pen: bool = False, use_seed: bool = False,
                     use_con: bool = False):
        """decode_ticks decode steps over every slot, ONE host sync.

        Per-tick host reads dominate serving latency when the device is
        remote (each tick would pay a full RPC round trip); scanning K
        ticks on device amortizes that K-fold. The per-slot stop
        decisions the host used to make by scanning the window live
        HERE now: a slot whose sampled token is EOS, or whose max_new
        budget runs out, sets its sticky `done` flag and freezes
        (lengths, sampling state, token stream) for the rest of the
        window — the host receives per-tick validity flags and slices,
        instead of re-deriving EOS/budget cuts from the raw token
        matrix. Inactive slots stay frozen throughout. Returns (cache,
        tokens (K, n_slots), logprobs (K, n_slots) — zeros unless
        self.logprobs, min_rem, counts, cstate, top-K values/ids, rem,
        done, acts (K, n_slots) validity flags).

        use_con: constrained slots mask logits through their DFA row
        and advance their state per sampled token — two gathers per
        tick, no host sync, so structured decoding rides the same
        multi-tick scan.
        """

        bias = samp[4] if use_bias else None
        min_rem0 = samp[5]
        pres, freq, counts0 = samp[6], samp[7], samp[8]
        seed_vec, gen0 = samp[9], samp[10]
        ctrans, coff, cstate0 = samp[11], samp[12], samp[13]
        rem0, done0 = samp[14], samp[15]

        def tick(carry, key_i):
            key, i = key_i
            cache, cur, min_rem, counts, cstate, rem, done = carry
            # A slot finished earlier in THIS window freezes exactly
            # like an inactive one.
            act = active & ~done
            old_lengths = cache.lengths
            logits, cache = transformer.forward_with_cache(
                self.cfg, params, cur[:, None], cache,
                attn_impl=self.attn_impl, mesh=self.mesh,
            )
            lengths = jnp.where(act, cache.lengths, old_lengths)
            cache = cache.replace(lengths=lengths)
            nxt, min_rem, new_cstate, lp, tlv, tli = (
                self._row_decode_step(
                    key, logits[:, 0], cur, act, min_rem, bias,
                    (pres, freq, counts) if use_pen else None,
                    (coff, cstate, ctrans) if use_con else None,
                    samp[:4], seed_vec if use_seed else None, gen0 + i,
                    greedy_only, use_pen, use_con, use_seed,
                )
            )
            if use_con:
                cstate = new_cstate
            if use_pen:
                counts = counts.at[
                    jnp.arange(counts.shape[0]), nxt
                ].add(act.astype(jnp.float32))
            # Device-side stop decision: this emitted token ends the
            # request when it is EOS (min_tokens already banned EOS
            # from sampling while its countdown runs) or when it is the
            # last of the max_new budget. rem <= 1 rather than == 1 so
            # a slot that somehow enters with rem 0 freezes instead of
            # wrapping.
            fin = act & (rem <= 1)
            if self.eos_id is not None:
                fin = fin | (act & (nxt == self.eos_id))
            rem = jnp.where(act, jnp.maximum(rem - 1, 0), rem)
            done = done | fin
            return ((cache, nxt, min_rem, counts, cstate, rem, done),
                    (nxt, lp, tlv, tli, act))

        keys = jax.random.split(key, self.decode_ticks)
        ticks_i = jnp.arange(self.decode_ticks, dtype=jnp.int32)
        ((cache, _, min_rem, counts, cstate, rem, done),
         (toks, lps, tlvs, tlis, acts)) = jax.lax.scan(
            tick, (cache, cur, min_rem0, counts0, cstate0, rem0, done0),
            (keys, ticks_i),
        )
        return (cache, toks, lps, min_rem, counts, cstate, tlvs, tlis,
                rem, done, acts)

    @jax.named_scope("sample")
    def _row_decode_step(self, key, logits, cur_r, active_r, min_rem_r,
                         bias_r, pen_r, con_r, samp_r, seed_r, gen_idx_r,
                         greedy_only, use_pen, use_con, use_seed):
        """The per-row exit math of ONE decode tick, shared by the
        unpipelined scan (_decode_impl, rows = all slots) and the
        pipelined scan (_decode_impl_pp, rows = the exiting group) so
        the two paths cannot drift: logit adjust (bias + min_tokens),
        OpenAI penalties, DFA constraint masking + state advance,
        sampling, and logprob extraction are defined once, here.

        logits: (R, V) raw fp32 rows. pen_r = (pres, freq, counts)
        rows or None; con_r = (coff, cstate, ctrans) or None. Returns
        (nxt, min_rem_new, cstate_new or None, lp, tlv, tli); callers
        own the counts scatter (their layouts differ) and any validity
        masking beyond active_r (the pipelined path folds its warmup
        mask into it)."""
        adj = self._adjust_logits(logits, bias_r, min_rem_r)
        if use_pen:
            # OpenAI semantics over generated tokens: presence
            # subtracts once per seen token, frequency per count.
            pres_r, freq_r, counts_r = pen_r
            adj = adj - (pres_r[:, None] * (counts_r > 0.0)
                         + freq_r[:, None] * counts_r)
        row = None
        if use_con:
            coff_r, cstate_r, ctrans = con_r
            con = coff_r >= 0
            row = ctrans[jnp.clip(coff_r, 0, None) + cstate_r]
            allowed = row[:, :-1] >= 0  # (R, V)
            if self.eos_id is not None:
                # EOS legality comes from the dedicated last column
                # (allowed exactly in accepting states).
                allowed = allowed.at[:, self.eos_id].set(
                    row[:, -1] >= 0
                )
            # Constraint wins over any user bias: disallowed stays
            # -inf regardless of logit_bias.
            adj = jnp.where(con[:, None] & ~allowed, NEG_INF, adj)
        if greedy_only:
            nxt = jnp.argmax(adj, axis=-1).astype(jnp.int32)
        elif use_seed:
            nxt = sample_batched(
                key, adj, *samp_r, seed=seed_r, gen_idx=gen_idx_r,
            )
        else:
            nxt = sample_batched(key, adj, *samp_r)
        nxt = jnp.where(active_r, nxt, cur_r)
        min_rem_new = jnp.where(
            active_r, jnp.maximum(min_rem_r - 1, 0), min_rem_r
        )
        cstate_new = None
        if use_con:
            col = nxt
            if self.eos_id is not None:
                col = jnp.where(
                    nxt == self.eos_id, row.shape[1] - 1, nxt
                )
            new_st = jnp.take_along_axis(
                row, col[:, None], axis=1
            )[:, 0]
            cstate_new = jnp.where(
                con & active_r, jnp.maximum(new_st, 0), cstate_r
            )
        k_tl = self.top_logprobs
        n_rows = nxt.shape[0]
        if self.logprobs:
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32))
            lp = jnp.take_along_axis(lsm, nxt[:, None], axis=-1)[:, 0]
            if k_tl:
                tlv, tli = jax.lax.top_k(lsm, k_tl)
                tli = tli.astype(jnp.int32)
            else:
                tlv = jnp.zeros((n_rows, 0), jnp.float32)
                tli = jnp.zeros((n_rows, 0), jnp.int32)
        else:
            lp = jnp.zeros((n_rows,), jnp.float32)
            tlv = jnp.zeros((n_rows, 0), jnp.float32)
            tli = jnp.zeros((n_rows, 0), jnp.int32)
        return nxt, min_rem_new, cstate_new, lp, tlv, tli

    def _decode_impl_pp(self, params, cache, cur, active, key, samp,
                        greedy_only: bool = False, use_bias: bool = False,
                        use_pen: bool = False, use_seed: bool = False,
                        use_con: bool = False):
        """Token-level pipelined decode window — same contract as
        _decode_impl (decode_ticks tokens per slot, one host sync),
        restructured so pp stages never idle.

        Slots split into pp contiguous groups of G = n_slots/pp. A
        stage register (pp, G, 1, D) rolls through pp*K + (pp-1)
        microticks: each microtick vmaps every stage's layer block
        over the group it holds (pp groups advance concurrently on
        their own devices), the group leaving the last stage is
        sampled, and it re-enters stage 0 next microtick with its
        fresh token. The pp-1 tail microticks drain the register so
        no pipeline state crosses the call boundary — slot churn
        (prefills, releases) between windows needs no special casing.
        Drain-tail entries never exit; their cache writes land at each
        slot's NEXT position and are overwritten by that token's real
        pass in the following window (same self-healing argument as
        the engine's finished-slot overshoot).

        Per-row math is identical to _decode_impl (same block, norm,
        unembed, adjust, sample formulas on the same values), so
        greedy output is bit-exact vs the unpipelined engine.

        Device-side stop decisions are NOT wired here: freezing a
        group mid-register would leave drain-tail bookkeeping per
        stage for a path whose win is stage utilization, not host
        syncs. rem/done pass through untouched, the validity flags
        report every active exit, and the host keeps its historical
        EOS/budget scan for pipelined engines — outputs are identical
        either way (the flags only dropped tokens the host discarded).
        """
        from shellac_tpu.inference import pp_pipeline as ppl

        pp = self._pp
        n_slots = self.n_slots
        G = n_slots // pp
        K = self.decode_ticks
        total = pp * K + pp - 1
        cdt = self.cfg.compute_dtype
        d_model = self.cfg.d_model
        vocab = self.cfg.vocab_size

        bias = samp[4] if use_bias else None
        min_rem0 = samp[5]
        pres, freq, counts0 = samp[6], samp[7], samp[8]
        seed_vec, gen0 = samp[9], samp[10]
        ctrans, coff, cstate0 = samp[11], samp[12], samp[13]
        rem0, done0 = samp[14], samp[15]

        cache_fields = kv_field_names(self.kv_quant)
        cache_st = tuple(
            ppl.stage_split(getattr(cache, f), pp) for f in cache_fields
        )
        sp = ppl.stage_split(params["layers"], pp)

        def rows(vec, gstart):
            return jax.lax.dynamic_slice_in_dim(vec, gstart, G, axis=0)

        def put_rows(vec, val, gstart):
            return jax.lax.dynamic_update_slice_in_dim(
                vec, val, gstart, axis=0
            )

        def microtick(carry, inp):
            key_t, t = inp
            (cache_st, lengths, cur, min_rem, counts, cstate,
             stage_x, stage_pos, stage_gstart) = carry

            # Entry: the group t mod pp embeds its latest token into
            # stage 0. During the drain tail these entries are dead
            # (they never exit; see docstring).
            gstart_in = (t % pp) * G
            cur_in = rows(cur, gstart_in)
            len_in = rows(lengths, gstart_in)
            x_in = ppl.embed_group(self.cfg, params, cur_in, self.mesh)
            stage_x = jnp.roll(stage_x, 1, axis=0).at[0].set(x_in)
            stage_pos = jnp.roll(stage_pos, 1, axis=0).at[0].set(len_in)
            stage_gstart = (
                jnp.roll(stage_gstart, 1, axis=0).at[0].set(gstart_in)
            )
            stage_x = ppl.constrain_register(stage_x, self.mesh)

            outs, cache_st = ppl.stage_apply(
                self.cfg, self.mesh, self.attn_impl, sp,
                cache_st, stage_x, stage_pos, stage_gstart,
                rolled=self.rolling_window,
            )
            outs = ppl.constrain_register(outs, self.mesh)
            stage_x = outs

            # Exit: the group leaving stage pp-1 gets sampled. Before
            # warmup completes (t < pp-1) the exit rows are garbage —
            # every state update is masked off and the emitted tokens
            # are dropped on the host side.
            exit_valid = t >= (pp - 1)
            gstart_out = stage_gstart[pp - 1]
            pos_out = stage_pos[pp - 1]
            logits_g = ppl.head_logits(self.cfg, params, outs[pp - 1])

            # Warmup exits (t < pp-1) are garbage: fold the validity
            # mask into the active rows so _row_decode_step's own
            # masking freezes every state update, and the emitted
            # tokens (cur echoes) are dropped on the host side.
            active_eff = rows(active, gstart_out) & exit_valid
            cur_out = rows(cur, gstart_out)
            len_out = rows(lengths, gstart_out)
            bias_g = (
                jax.lax.dynamic_slice(bias, (gstart_out, 0), (G, vocab))
                if use_bias else None
            )
            pen_r = None
            if use_pen:
                pen_r = (
                    rows(pres, gstart_out), rows(freq, gstart_out),
                    jax.lax.dynamic_slice(
                        counts, (gstart_out, 0), (G, vocab)
                    ),
                )
            con_r = None
            if use_con:
                con_r = (rows(coff, gstart_out),
                         rows(cstate, gstart_out), ctrans)
            # This exit is the group's ((t - (pp-1)) // pp)-th token of
            # the window — the per-slot gen counter seeded sampling
            # uses, so seeded streams match the unpipelined engine.
            k_idx = jnp.maximum(t - (pp - 1), 0) // pp
            nxt, min_rem_g, cstate_g, lp, tlv, tli = (
                self._row_decode_step(
                    key_t, logits_g, cur_out, active_eff,
                    rows(min_rem, gstart_out), bias_g, pen_r, con_r,
                    (rows(samp[0], gstart_out),
                     rows(samp[1], gstart_out),
                     rows(samp[2], gstart_out),
                     rows(samp[3], gstart_out)),
                    rows(seed_vec, gstart_out) if use_seed else None,
                    rows(gen0, gstart_out) + k_idx,
                    greedy_only, use_pen, use_con, use_seed,
                )
            )
            lengths = put_rows(
                lengths, jnp.where(active_eff, pos_out + 1, len_out),
                gstart_out,
            )
            cur = put_rows(cur, nxt, gstart_out)
            min_rem = put_rows(min_rem, min_rem_g, gstart_out)
            if use_con:
                cstate = put_rows(cstate, cstate_g, gstart_out)
            if use_pen:
                counts = counts.at[
                    gstart_out + jnp.arange(G), nxt
                ].add(active_eff.astype(jnp.float32))
            new_carry = (cache_st, lengths, cur, min_rem, counts,
                         cstate, stage_x, stage_pos, stage_gstart)
            return new_carry, (nxt, lp, tlv, tli, active_eff)

        stage_x0 = ppl.constrain_register(
            jnp.zeros((pp, G, 1, d_model), cdt), self.mesh
        )
        # Warmup stages hold garbage (gstart 0); pin their write
        # position to group 0's CURRENT lengths so the garbage K/V
        # lands exactly where group 0's real token writes correct
        # values before any read — never at position 0, which would
        # corrupt live prefix rows.
        stage_pos0 = jnp.broadcast_to(
            cache.lengths[:G][None, :], (pp, G)
        )
        stage_gstart0 = jnp.zeros((pp,), jnp.int32)
        keys = jax.random.split(key, total)
        ts = jnp.arange(total, dtype=jnp.int32)
        carry0 = (cache_st, cache.lengths, cur, min_rem0, counts0,
                  cstate0, stage_x0, stage_pos0, stage_gstart0)
        ((cache_st, lengths, _, min_rem, counts, cstate, _, _, _),
         (nxts, lps, tlvs, tlis, acts)) = jax.lax.scan(
            microtick, carry0, (keys, ts)
        )
        cache = cache.replace(
            lengths=lengths,
            **{f: ppl.stage_merge(c)
               for f, c in zip(cache_fields, cache_st)},
        )
        # Exits come out round-robin: microtick pp-1+m emits group
        # m mod pp's (m//pp)-th token. Groups are contiguous ascending
        # slot ranges, so reshaping the valid tail gives (K, n_slots)
        # in slot order — the same shape _decode_impl returns.
        toks = nxts[pp - 1:].reshape(K, n_slots)
        lps_out = lps[pp - 1:].reshape(K, n_slots)
        k_tl = self.top_logprobs
        tlvs_out = tlvs[pp - 1:].reshape(K, n_slots, k_tl)
        tlis_out = tlis[pp - 1:].reshape(K, n_slots, k_tl)
        acts_out = acts[pp - 1:].reshape(K, n_slots)
        return (cache, toks, lps_out, min_rem, counts, cstate,
                tlvs_out, tlis_out, rem0, done0, acts_out)

    # ---- scheduling --------------------------------------------------

    @staticmethod
    def _validate_sampling(d: Dict[str, Any], label) -> None:
        if d["temperature"] < 0:
            raise ValueError(f"{label}: temperature must be >= 0")
        if d["top_k"] < 1:
            raise ValueError(f"{label}: top_k must be >= 1 (or None)")
        if not 0 < d["top_p"] <= 1:
            raise ValueError(f"{label}: top_p must be in (0, 1]")
        if not 0 <= d["min_p"] < 1:
            raise ValueError(f"{label}: min_p must be in [0, 1)")

    def _adjust_logits(self, logits, bias, min_rem):
        """Apply per-row logit biases and the min_tokens EOS ban to a
        (B, V) fp32 logit block; sampling consumes the result while
        logprobs keep reporting the raw distribution."""
        x = logits.astype(jnp.float32)
        if bias is not None:
            x = x + bias
        if self.eos_id is not None:
            col = jnp.where(min_rem > 0, NEG_INF, x[:, self.eos_id])
            x = x.at[:, self.eos_id].set(col)
        return x

    def _first_tl(self, last):
        """Top-K alternatives of a prefill's first sampled position
        ((1, K) values, (1, K) ids) — zero-width when disabled, so
        every prefill program keeps one output arity per engine."""
        k = self.top_logprobs
        if not k:
            return (jnp.zeros((1, 0), jnp.float32),
                    jnp.zeros((1, 0), jnp.int32))
        lsm = jax.nn.log_softmax(last.astype(jnp.float32))[None]
        vals, ids = jax.lax.top_k(lsm, k)
        return vals, ids.astype(jnp.int32)

    @staticmethod
    def _unpack_samp(samp):
        """Unpack a _slot_samp tuple: (temperature, top_k, top_p,
        min_p, bias row, min_tokens, seed, constraint mask), each a
        (1,)/(1, V) array. The scalars ride ONE packed int32 device
        buffer (floats bitcast); this is the single place the layout
        is decoded, shared by every prefill program."""
        packed, bias, cmask = samp
        fl = jax.lax.bitcast_convert_type(packed[:3], jnp.float32)
        return (fl[0][None], packed[3][None], fl[1][None], fl[2][None],
                bias, packed[4][None], packed[5][None], cmask)

    @jax.named_scope("sample")
    def _sample_first(self, key, last, samp):
        """Sample a prefill's first output token from the adjusted
        (biased, EOS-banned, constraint-masked) logits; the logprob
        stays on the raw ones. A seeded request's first token is draw
        gen_idx=0 of its own deterministic stream."""
        temp, topk, topp, minp, bias, min_rem, seed, cmask = (
            self._unpack_samp(samp)
        )
        adjusted = self._adjust_logits(last[None], bias, min_rem)
        # Constraint mask LAST: a grammar-disallowed token must stay
        # disallowed no matter what the user's logit_bias says.
        adjusted = adjusted + cmask
        first = sample_batched(
            key, adjusted, temp, topk, topp, minp,
            seed=seed, gen_idx=jnp.zeros((1,), jnp.int32),
        )[0]
        lp = jax.nn.log_softmax(last.astype(jnp.float32))[first]
        return first, lp

    def submit(self, rid, tokens, max_new: int, stop=None, *,
               temperature=None, top_k=None, top_p=None,
               min_p=None, min_tokens=None, logit_bias=None,
               presence_penalty=None, frequency_penalty=None,
               prompt_logprobs=False, seed=None,
               constraint=None, trace=None,
               prefill_only: bool = False,
               tenant=None, qos_class=None, qos_weight=None) -> None:
        """Queue a request. `stop`: optional list of token-id sequences;
        generation ends when the output ends with any of them, and the
        matched sequence is removed from the returned tokens.
        temperature/top_k/top_p/min_p override the engine defaults for
        this request only — requests with different sampling settings
        share one device batch."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if tokens.size == 0:
            raise ValueError(f"request {rid!r}: empty prompt")
        if max_new < 1:
            # The engine always emits the prefill-sampled token, so
            # max_new=0 would still return one token; reject it.
            raise ValueError(f"request {rid!r}: max_new must be >= 1")
        if tokens.size + max_new + 1 > self.max_len:
            raise ValueError(
                f"request {rid!r}: prompt {tokens.size} + max_new {max_new} "
                f"exceeds max_len {self.max_len}"
            )
        if stop is not None:
            stop = [list(map(int, s)) for s in stop]
            if any(len(s) == 0 for s in stop):
                raise ValueError(f"request {rid!r}: empty stop sequence")
        d = self._defaults
        samp = {
            "temperature": float(
                temperature if temperature is not None else d["temperature"]
            ),
            "top_k": int(top_k) if top_k is not None else d["top_k"],
            "top_p": float(top_p) if top_p is not None else d["top_p"],
            "min_p": float(min_p) if min_p is not None else d["min_p"],
        }
        self._validate_sampling(samp, f"request {rid!r}")
        min_tokens = int(min_tokens) if min_tokens is not None else 0
        if min_tokens < 0:
            raise ValueError(f"request {rid!r}: min_tokens must be >= 0")
        if min_tokens > 0 and self.eos_id is None:
            raise ValueError(
                f"request {rid!r}: min_tokens needs the engine's eos_id "
                "(there is no EOS to suppress otherwise)"
            )
        if logit_bias is not None:
            try:
                logit_bias = {int(k): float(v)
                              for k, v in dict(logit_bias).items()}
            except (TypeError, ValueError) as e:
                raise ValueError(f"request {rid!r}: bad logit_bias: {e}")
            oob = [k for k in logit_bias if not 0 <= k < self.cfg.vocab_size]
            if oob:
                raise ValueError(
                    f"request {rid!r}: logit_bias token ids {oob} outside "
                    f"vocab [0, {self.cfg.vocab_size})"
                )
        if prompt_logprobs and getattr(self, "prefix_cache", False):
            raise ValueError(
                f"request {rid!r}: prompt_logprobs does not compose "
                "with the prefix cache (a cache hit skips exactly the "
                "forward passes that would score the prefix); use a "
                "non-prefix-cached engine for scoring"
            )
        pres = float(presence_penalty) if presence_penalty is not None \
            else 0.0
        freq = float(frequency_penalty) if frequency_penalty is not None \
            else 0.0
        for nm, v in (("presence_penalty", pres),
                      ("frequency_penalty", freq)):
            if not np.isfinite(v):
                raise ValueError(f"request {rid!r}: {nm} must be finite")
        if seed is not None:
            seed = int(seed)
            if seed < 0:
                raise ValueError(
                    f"request {rid!r}: seed must be >= 0 (negative is "
                    "the unseeded sentinel)"
                )
            # OpenAI clients send 63-bit seeds; the device vector is
            # int32. Fold deterministically instead of overflowing in
            # the scheduler thread.
            seed &= 0x7FFFFFFF
        if constraint is not None:
            from shellac_tpu.inference.constraints import TokenDFA

            if not isinstance(constraint, TokenDFA):
                raise ValueError(
                    f"request {rid!r}: constraint must be a compiled "
                    "constraints.TokenDFA (the server compiles specs; "
                    "library users call compile_token_dfa)"
                )
            if constraint.trans.shape[1] != self.cfg.vocab_size + 1:
                raise ValueError(
                    f"request {rid!r}: constraint table covers "
                    f"{constraint.trans.shape[1] - 1} tokens, model "
                    f"vocab is {self.cfg.vocab_size}"
                )
            if self.eos_id is None or constraint.eos_id != self.eos_id:
                raise ValueError(
                    f"request {rid!r}: constraint eos_id "
                    f"{constraint.eos_id} must equal the engine's "
                    f"eos_id {self.eos_id} (termination and EOS "
                    "masking must agree)"
                )
            if min_tokens > 0:
                raise ValueError(
                    f"request {rid!r}: min_tokens does not compose "
                    "with constraint (the EOS ban can contradict a "
                    "state where only EOS is legal)"
                )
        if prefill_only and constraint is not None:
            # A compiled TokenDFA is device-table state the wire
            # format cannot ship; constrained requests serve
            # monolithically (the tier's feature fallback).
            raise ValueError(
                f"request {rid!r}: prefill_only does not compose with "
                "constraint (the DFA table does not migrate)"
            )
        if qos_class is not None:
            qos_class = int(qos_class)
            if qos_class < 0:
                raise ValueError(
                    f"request {rid!r}: qos_class must be >= 0"
                )
        if qos_weight is not None:
            qos_weight = float(qos_weight)
            if qos_weight <= 0:
                raise ValueError(
                    f"request {rid!r}: qos_weight must be > 0"
                )
        # The request is valid: from here it is in the engine. (Outside
        # a step, so the span has no parent; it rides in the next
        # step's record.)
        with self.obs.steps.span("engine.submit", rid=rid):
            self._queue.append(_Request(
                rid, tokens, max_new, stop=stop, min_tokens=min_tokens,
                logit_bias=logit_bias, presence_penalty=pres,
                frequency_penalty=freq,
                prompt_logprobs=bool(prompt_logprobs), seed=seed,
                constraint=constraint, trace=trace,
                prefill_only=bool(prefill_only),
                tenant=tenant if tenant is None else str(tenant),
                qos_class=qos_class if qos_class is not None else 1,
                qos_weight=qos_weight if qos_weight is not None else 4.0,
                t_queued=time.monotonic(), **samp,
            ))
            if trace is not None:
                # Flight-recorder timeline: the request entered the
                # engine's admission queue (queue-wait ends at the
                # span's prefill_start). No-op without a recorder on
                # the trace.
                trace.record("queue", src="engine", rid=rid,
                             queue_depth=len(self._queue))

    def _slot_footprint(self, req: _Request) -> int:
        """Worst-case token residency of `req`: prompt + budget + 1,
        plus the engine's window slack (speculative rounds overshoot
        by gamma+1 before rolling back). The backend reserves this at
        admission and caps mid-decode growth at it."""
        return req.tokens.size + req.max_new + 1 + self._footprint_slack

    def _window_write_span(self) -> int:
        """Positions one decode window may write per slot — what the
        backend must keep resident ahead of the live length. The
        speculative mixin overrides (a verify round writes gamma+1)."""
        return self.decode_ticks

    def _prepare_slot(self, slot: int, req: _Request) -> None:
        """Reserve storage for `req` before its prefill (backend hook;
        paged allocates/attaches blocks). May raise PoolExhausted —
        _fill_slots requeues the request and retries after a release."""
        with self.obs.steps.span("cache.prepare_slot", slot=slot):
            self.cache_backend.prepare_slot(slot, req,
                                            self._slot_footprint(req))

    def _release_slot(self, slot: int) -> None:
        """A request left `slot`: release its storage (backend hook;
        paged frees blocks and zeroes the slot's HOST table row) and
        clear the slot's SAMPLING state, which is the engine's own.
        Host writes only, for a request without a bias or penalties:
        the zeroed row, the cleared coefficients and the cleared DFA
        state ride the next program. Clearing the logit bias drops the
        engine back to the cheap no-bias decode variant — zeroing the
        row too, or a later unbiased request on this slot would
        silently inherit the stale biases."""
        with self.obs.steps.span("cache.release_slot", slot=slot):
            self.cache_backend.release_slot(slot)
        if self._slot_bias[slot] is not None:
            self._sbias = self._sbias.at[slot].set(0.0)
            self._slot_bias[slot] = None
        if self._slot_pen[slot]:
            # Clear the coefficient AND the counts, or the next request
            # on this slot would inherit a stale repetition history.
            self._write_samp(slot, presence=0.0, frequency=0.0)
            self._scounts = self._scounts.at[slot].set(0.0)
            self._slot_pen[slot] = False
        if self._slot_dfa[slot] is not None:
            self._slot_dfa[slot] = None
            self._patch_slot(slot, cstate=0)
            self._con_dirty = True

    def _bias_row(self, req: _Request) -> np.ndarray:
        row = np.zeros((self.cfg.vocab_size,), np.float32)
        for k, v in (req.logit_bias or {}).items():
            row[k] = v
        return row

    def _slot_samp(self, slot: int, req: _Request):
        """This request's sampling settings for the prefill jits:
        (packed scalars, logit bias row, first-token constraint mask).

        The six scalars (temperature, top_p, min_p bitcast to int32;
        top_k, remaining min_tokens, seed) are packed into ONE (6,)
        int32 host buffer so admission pays a single host->device
        upload instead of six round trips through the dispatch path —
        _unpack_samp is the matching device-side decoder. The bias row
        is a device slice of the matrix _set_slot_sampling already
        wrote (shared zero row when unbiased). The constraint mask is
        the DFA's state-0 row as an additive -inf mask — the prefill's
        sampled token must obey the grammar too; later tokens mask
        inside the decode scan."""
        packed = np.empty((6,), np.int32)
        packed[:3] = np.asarray(
            [req.temperature, req.top_p, req.min_p], np.float32
        ).view(np.int32)
        packed[3] = req.top_k
        packed[4] = req.min_tokens
        packed[5] = req.seed if req.seed is not None else -1
        bias = (self._sbias[slot][None] if req.logit_bias
                else self._zero_bias_row)
        if req.constraint is not None:
            row = req.constraint.trans[0]
            mask = np.where(row[:-1] >= 0, 0.0, NEG_INF).astype(np.float32)
            mask[req.constraint.eos_id] = 0.0 if row[-1] >= 0 else NEG_INF
            cmask = jnp.asarray(mask)[None]
        else:
            cmask = self._zero_bias_row
        return (jnp.asarray(packed), bias, cmask)

    def _set_slot_sampling(self, slot: int, req: _Request) -> None:
        """Write the request's settings into the slot's row of the
        host settings matrix the decode window samples with (the
        window uploads the matrix if a value changed: requests with
        equal settings upload nothing). Only a logit bias or penalties
        touch the device here, in the (n_slots, vocab) matrices such a
        request needs."""
        self._write_samp(
            slot, temperature=req.temperature, top_k=req.top_k,
            top_p=req.top_p, min_p=req.min_p,
            seed=req.seed if req.seed is not None else -1,
            presence=req.presence_penalty, frequency=req.frequency_penalty,
        )
        new_bias = req.logit_bias or None
        if new_bias != self._slot_bias[slot]:
            # O(n_slots x vocab) device copy — only when this slot's
            # bias actually changes (never on the bias-free path).
            if self._sbias is None:
                self._sbias = jnp.zeros(
                    (self.n_slots, self.cfg.vocab_size), jnp.float32
                )
            self._sbias = self._sbias.at[slot].set(
                jnp.asarray(self._bias_row(req))
            )
            self._slot_bias[slot] = new_bias
        penalized = (req.presence_penalty != 0.0
                     or req.frequency_penalty != 0.0)
        if penalized or self._slot_pen[slot]:
            if self._scounts is None:
                self._scounts = jnp.zeros(
                    (self.n_slots, self.cfg.vocab_size), jnp.float32
                )
            self._scounts = self._scounts.at[slot].set(0.0)
        self._slot_pen[slot] = penalized
        if req.constraint is not None or self._slot_dfa[slot] is not None:
            self._slot_dfa[slot] = req.constraint
            self._patch_slot(slot, cstate=0)
            # Lazy: admissions and releases in one engine step coalesce
            # into a single restack right before the next decode.
            self._con_dirty = True

    def _rebuild_constraints(self) -> None:
        """Restack active constrained slots' DFA tables into one device
        table with per-slot row offsets (a column of the host settings
        matrix). Rows are bucketed to powers of two so the decode
        program's trace survives request churn."""
        self._con_dirty = False
        tables, off = [], 0
        for slot, dfa in enumerate(self._slot_dfa):
            if dfa is None:
                self._write_samp(slot, coff=-1)
                continue
            self._write_samp(slot, coff=off)
            tables.append(dfa.trans)
            off += dfa.trans.shape[0]
        if not tables:
            self._ctrans = None
            return
        rows = _bucket(off)
        stacked = np.concatenate(tables, axis=0)
        if rows > off:
            # Pad rows are unreachable (offsets only point at real
            # rows); -1 everywhere keeps them inert if that ever
            # changes.
            pad = np.full((rows - off, stacked.shape[1]), -1, np.int32)
            stacked = np.concatenate([stacked, pad], axis=0)
        self._ctrans = jnp.asarray(stacked)

    def _run_prefill(self, slot: int, req: _Request):
        """Run the (bucketed, jitted) prefill for `req`; returns
        (first sampled token, its raw logprob, top-K alternatives or
        None, prompt-logprob scores or None) — all DEVICE values, so
        dispatch pays no host sync; _settle_prefills (inline without
        overlap, batched at the next step boundary with it) pulls
        everything in one device_get."""
        s = req.tokens.size
        # The backend pads (a bucket capped at max_len; EVA state one
        # buffer of whole windows) and says what the program runs.
        pad, run = self.cache_backend.prefill_rows(s)
        key = (pad, req.prompt_logprobs)
        if key not in self._prefill_jit:
            self._prefill_jit[key] = self._jit_cache_program(
                self._prefill_impl, 6, static_argnames=("want_plp",)
            )
        padded = np.zeros((1, pad), np.int32)
        padded[0, :s] = req.tokens
        self._count_prefill(s, run, True)
        program = self._prefill_jit[key]
        self._cache, self._key, first, lp, plp, tlv, tli = program(
            self.params, self._cache, jnp.asarray(padded),
            np.array([s], np.int32), slot, self._key,
            self._slot_samp(slot, req),
            self.cache_backend.slot_tables(),
            want_plp=req.prompt_logprobs,
        )
        self._launch_prompt("prefill", program, first, slot=slot,
                            bucket=run, tokens=int(s), offset=0)
        # Prompt scoring no longer pays its own per-admission pull: the
        # device array rides the flight and lands in the ONE batched
        # settle device_get alongside the first token (SH002 history:
        # this line used to be a dedicated device_get).
        return (first, lp, ((tlv, tli) if self.top_logprobs else None),
                plp if req.prompt_logprobs else None)

    def _count_prefill(self, tokens: int, padded: int,
                       fresh: bool) -> None:
        """A prefill or chunk program of `tokens` real prompt tokens
        that runs `padded` rows is about to be dispatched (called
        inside its engine.prefill_dispatch span), into an empty cache
        (`fresh`: a whole prompt, a first chunk) or behind resident
        tokens. The model's own rule says whether its expert FFN runs
        over the sorted routed rows."""
        steps = self.obs.steps
        path = transformer.expert_ffn_path(
            self.cfg, self.params["layers"], cached=not fresh,
            mesh=self.mesh,
        )
        steps.count(
            prefill_tokens=tokens, prefill_padded_tokens=padded,
            prefill_sorted_tokens=padded if path == "sorted" else 0,
        )
        steps.annotate(bucket=padded, tokens=tokens)

    def _prefill_start_offset(self, slot: int) -> int:
        """Tokens already resident when prefill starts (the paged
        backend reports its matched prefix length)."""
        return self.cache_backend.prefill_offset(slot)

    def _fill_slots(self, budget: Optional[int] = None):
        done = 0
        steps = self.obs.steps
        for i in range(self.n_slots):
            if self._slots[i] is not None or not self._queue:
                continue
            if budget is not None and done >= budget:
                break
            done += 1
            # One admission, queue pop to prefill dispatched. The rid
            # (and the request's trace id, when it has one) joins the
            # step spans a request rode to its own RequestTrace.
            with steps.span("engine.admit", slot=i) as adm:
                req = self._queue.popleft()
                adm.set(rid=req.rid, prompt_tokens=int(req.tokens.size))
                if req.trace is not None and req.trace.trace_id:
                    adm.set(trace_id=req.trace.trace_id)
                try:
                    self._prepare_slot(i, req)
                except PoolExhausted:
                    # Backend capacity exhausted: put the request back
                    # and let it wait; retry after a slot frees its
                    # storage.
                    self._queue.appendleft(req)
                    adm.set(requeued=True)
                    break
                if req.trace is not None:
                    # Queue wait ends here (after _prepare_slot: a
                    # paged pool miss requeues the request, so its wait
                    # goes on).
                    req.trace.prefill_start()
                self._set_slot_sampling(i, req)
                off = self._prefill_start_offset(i)
                if (self.prefill_chunk is not None
                        and req.tokens.size - off > self.prefill_chunk):
                    # Long prompt: admit now, prefill incrementally in
                    # step() (the slot stays out of decode until done).
                    self._slots[i] = req
                    self._prefilling[i] = off
                    continue
                # The prefill program dispatch, split out of the
                # surrounding admission bookkeeping (the settle sync
                # has its own span — immediately below without
                # overlap, at the next step boundary with it).
                with steps.span("engine.prefill_dispatch",
                                launch=steps.next_launch) as pf:
                    arrays = self._run_prefill(i, req)
                    self._dispatch_prefill(i, req, arrays)
                adm.set(padded_tokens=pf.get("bucket"))
            if not self.overlap_prefill:
                self._settle_prefills()

    def _dispatch_prefill(self, slot: int, req: _Request,
                          arrays) -> None:
        """A prefill (or final chunk) was just dispatched for `req`:
        record it as an in-flight _PrefillFlight. No host sync — the
        device outputs stay futures until _settle_prefills. The slot is
        occupied from here (pending accounting, admission exclusion)
        but prefill-pending: _active_rows keeps it out of decode
        windows until the settle writes its host bookkeeping."""
        self._slots[slot] = req
        self.stats["prefills"] += 1
        fl = _PrefillFlight(slot, req, arrays)
        self._pflights.append(fl)
        if self._prefill_hooks is not None:
            self._prefill_hooks.on_prefill_dispatch(fl)
        if self.overlap_prefill and req.trace is not None:
            # Flight-recorder timeline: dispatch half of the prefill
            # pipeline (settle lands as the span's first_token). Only
            # recorded under overlap — without it dispatch and settle
            # are one event, the span's existing prefill section.
            req.trace.record("prefill-dispatch", src="engine",
                             rid=req.rid, slot=slot,
                             depth=len(self._pflights))

    def _pending_prefill_slots(self):
        """Slots whose prefill is dispatched but not yet settled (and
        whose request still owns the slot) — excluded from decode
        windows until the settle writes their host bookkeeping."""
        return {fl.slot for fl in self._pflights
                if self._slots[fl.slot] is fl.req}

    def _settle_prefills(self) -> bool:
        """Settle EVERY in-flight prefill in ONE batched device_get:
        first tokens, logprobs, top-K alternatives, and the opt-in
        prompt-logprob payloads all ride the same pull. TTFT
        (trace.first_token) is recorded here — the settle point.
        Results for slots whose request was cancelled or replaced while
        the prefill was in flight are discarded (identity check, like
        stale decode windows). False if nothing was in flight."""
        if not self._pflights:
            return False
        flights, self._pflights = self._pflights, []
        steps = self.obs.steps
        with steps.span("engine.settle_prefills", prefills=len(flights)):
            with steps.span("engine.wait_prefill"):
                if self._prefill_hooks is not None:
                    self._prefill_hooks.before_prefill_sync(flights)
                arrays = [fl.arrays for fl in flights]
                # The newest flight's first token: everything
                # dispatched before it lands with it.
                steps.land(arrays[-1][0], arrays)
                host = jax.device_get(arrays)  # shellac: ignore[SH002] — THE prefill settle: one batched pull for every in-flight prefill's first token / logprob / top-K / prompt scores (the per-admission pulls this replaces each paid their own round trip); the first tokens MUST reach the host here — settle is the TTFT point and the finish check needs them
            for fl, (first, lp, tl, plp) in zip(flights, host):
                if self._slots[fl.slot] is not fl.req:
                    continue
                self._finish_prefill_host(fl.slot, fl.req, first, lp, tl,
                                          plp)
        return True

    @staticmethod
    def _stitch_plp(plp_host, s: int) -> List[float]:
        """Normalize a settled prompt-logprob payload to the flat
        per-token list the server renders: either the whole-prompt
        score array (sliced to the real prompt length) or the chunked
        path's (in-chunk scores, size, boundary score) pieces stitched
        across chunk boundaries. Position 0 has no predictor and
        reports 0.0 (rendered as null)."""
        if not isinstance(plp_host, list):
            return [float(x) for x in np.asarray(plp_host)[:s]]
        flat = [0.0]
        for plp_w, sz, blp in plp_host:
            flat.extend(float(x) for x in np.asarray(plp_w)[1:sz])
            if blp is not None:
                flat.append(float(blp))
        return flat

    def _finish_prefill_host(self, slot: int, req: _Request, first,
                             lp=None, tl=None, plp=None) -> None:
        """Host half of prefill completion: all arguments are settled
        HOST values (pulled by _settle_prefills' one batched sync).
        The slot's prompt KV is now certainly resident, so paged
        prefix caching registers the prompt blocks as matchable here —
        at settle, never at dispatch (an in-flight program's blocks
        must not be matchable, and a cancelled flight's never are).

        The new tenant's carried vectors (next input token, budget,
        done flag, EOS-ban countdown, DFA state) are ARMED here by one
        _patch_slot call: host writes that the next decode window, the
        first the slot is active in, selects in at its entry. Why the
        patch and not the prefill program: the values are final only at
        the settle (the DFA advance and the repetition count need the
        first token on the host, a cancelled flight must arm nothing,
        prefill_only freezes), the same writer serves an imported slot,
        a preemption and a thaw, and a window in between runs the slot
        inactive, so the values are bit-equal to eager writes made
        here."""
        self.cache_backend.on_prefill_complete(slot)
        first_tok = int(first)
        self._slots[slot] = req
        # The prefill-sampled token is the first of max_new, so the
        # decode window may emit max_new - 1 more before the budget
        # freeze; done clears in case the slot's previous tenant froze
        # it — unless this is a disaggregated freeze: then the
        # device-side done flag plus host-side exclusion keep the slot
        # out of every decode window, the KV-migration exporter ships
        # it and release_frozen() reclaims the slot. The prefill-sampled
        # token also consumed one unit of the EOS ban.
        arm = dict(cur=first_tok, rem=req.max_new - 1,
                   done=req.prefill_only,
                   min_rem=max(req.min_tokens - 1, 0))
        if req.constraint is not None:
            # Advance the DFA past the prefill-sampled token (host-side:
            # the token is already a host int here). Decode-time tokens
            # advance on device inside the scan.
            trans = req.constraint.trans
            col = (trans.shape[1] - 1 if first_tok == req.constraint.eos_id
                   else first_tok)
            arm["cstate"] = max(int(trans[0, col]), 0)
        self._patch_slot(slot, **arm)
        if self._slot_pen[slot]:
            # The prefill-sampled token is generated output: it joins
            # the slot's repetition counts.
            self._scounts = self._scounts.at[slot, first_tok].add(1.0)
        req.out.append(first_tok)
        self.obs.steps.count(tokens_delivered=1)
        if req.trace is not None:
            # The batched settle pull already synced: the first token
            # is a host value, so this is the request's TTFT point —
            # under overlap_prefill, the settle boundary, not the
            # dispatch (docs/decode_performance.md "Prefill overlap").
            req.trace.first_token()
        if self.logprobs and lp is not None:
            req.lps.append(float(lp))
        if self.top_logprobs and tl is not None:
            tlv, tli = tl  # host arrays — pulled with `first` above
            req.tlp = [(np.asarray(tli)[0].tolist(),
                        np.asarray(tlv)[0].tolist())]
        if req.prompt_logprobs and plp is not None:
            req.plp = self._stitch_plp(plp, req.tokens.size)
        if req.prefill_only:
            self.frozen_prefills[req.rid] = slot
            if req.trace is not None:
                req.trace.record("prefill-frozen", src="engine",
                                 rid=req.rid, slot=slot,
                                 prompt_len=int(req.tokens.size))

    # ---- chunked prefill --------------------------------------------

    def _advance_prefills(self, budget: Optional[int]) -> int:
        """Run up to `budget` prefill-chunk programs (all of them when
        budget is None); returns the number launched. Lowest slot
        first, drained depth-first — chunk N+1 reuses chunk N's cache
        row while it is hot."""
        used = 0
        steps = self.obs.steps
        while self._prefilling and (budget is None or used < budget):
            slot = min(self._prefilling)
            used += 1
            self.stats["prefill_chunks"] += 1
            req = self._slots[slot]
            off = self._prefilling[slot]
            chunk = req.tokens[off:off + self.prefill_chunk]
            s = chunk.size
            pad = min(_bucket(s), self.max_len - off)
            final = off + s >= req.tokens.size
            # One chunk program's dispatch (a final chunk's inline
            # settle has its own span, after this one).
            with steps.span("engine.prefill_dispatch", slot=slot,
                            offset=int(off), launch=steps.next_launch):
                # (numpy, like every small host argument below: jnp
                # would dispatch a program to convert a Python scalar)
                boundary = np.int32(0 if final else req.tokens[off + s])
                self._count_prefill(s, pad, off == 0)
                first, lp, plp_w, blp, tlv, tli = self._chunk_prefill(
                    pad, off == 0, jnp.asarray(
                        np.pad(chunk, (0, pad - s))[None]
                    ),
                    np.array([s], np.int32),
                    np.array([off], np.int32),
                    slot, self._slot_samp(slot, req),
                    boundary_next=boundary,
                    want_plp=req.prompt_logprobs,
                )
                if req.prompt_logprobs:
                    # Collect DEVICE arrays; the one blocking transfer
                    # happens at the final chunk, so scoring does not
                    # serialize the chunk pipeline with per-chunk syncs.
                    if req.plp is None:
                        req.plp = []
                    req.plp.append((plp_w, s, None if final else blp))
                if final:
                    del self._prefilling[slot]
                    # The final chunk's stitching sync no longer happens
                    # here: the collected plp pieces (device arrays)
                    # ride the flight and settle in the ONE batched pull
                    # with the first token — _stitch_plp flattens them
                    # host-side at settle.
                    pieces = req.plp
                    req.plp = None
                    self._dispatch_prefill(
                        slot, req,
                        (first, lp,
                         ((tlv, tli) if self.top_logprobs else None),
                         pieces),
                    )
                else:
                    self._prefilling[slot] = off + s
            if final and not self.overlap_prefill:
                self._settle_prefills()
        return used

    def _chunk_prefill(self, pad, fresh, tokens, chunk_len, offset, slot,
                       samp, boundary_next=None, want_plp=False):
        """Dispatch one (bucketed, jitted) chunk-continuation program;
        rebinds the cache and the PRNG key from its outputs and returns
        the rest (first token, its logprob, in-chunk prompt scores,
        boundary score, top-K values and ids: device values)."""
        jkey = (pad, fresh, want_plp)
        if jkey not in self._chunk_jit:
            self._chunk_jit[jkey] = self._jit_cache_program(
                functools.partial(self._chunk_prefill_impl, fresh=fresh,
                                  want_plp=want_plp), 7
            )
        return self._run_chunk_program(
            self._chunk_jit[jkey], tokens, chunk_len, offset, slot, samp,
            boundary_next,
        )

    def _run_chunk_program(self, program, tokens, chunk_len, offset, slot,
                           samp, boundary_next):
        """One chunk program's call, the dense and the paged engine's:
        the engine's key and the backend's slot tables go in as
        arguments, the cache and the key come back."""
        if boundary_next is None:
            boundary_next = np.int32(0)
        self._cache, self._key, *rest = program(
            self.params, self._cache, tokens, chunk_len, offset, slot,
            self._key, samp, boundary_next,
            self.cache_backend.slot_tables(),
        )
        self._launch_prompt("chunk", program, rest[0], slot=slot,
                            bucket=tokens.shape[1],
                            tokens=int(chunk_len[0]), offset=int(offset[0]))
        return rest

    def _chunk_prefill_impl(self, params, cache, tokens, chunk_len, offset,
                            slot, key, samp, boundary_next, tables, *,
                            fresh: bool, want_plp: bool = False):
        """Write one prompt chunk at `offset` into `slot`'s cache row.

        A batch-1 view of the row continues from `offset` tokens
        (fresh_cache only for the first chunk — later chunks attend to
        the buffered prefix via the masked decode path). The sampled
        token is only meaningful for the final chunk; earlier chunks
        compute and discard it (cheaper than a second program variant).

        want_plp additionally returns (a) each chunk token's logprob
        given its IN-CHUNK predecessor (rows 1..s-1; row 0's predictor
        lives in the previous chunk) and (b) the boundary logprob of
        `boundary_next` — the NEXT chunk's first token — from this
        chunk's final position, so the host can stitch the full prompt
        scoring across chunks.
        """
        cache, key_out, key = self._enter(cache, tables, key)
        view = slot_view(cache, slot, offset)
        logits, view = transformer.forward_with_cache(
            self.cfg, params, tokens, view, new_tokens_len=chunk_len,
            fresh_cache=fresh,
            attn_impl=self.attn_impl if fresh else "ref", mesh=self.mesh,
            # Only prompt scoring reads a chunk's other rows.
            logits_at=None if want_plp else chunk_len - 1,
        )
        last = jnp.take_along_axis(
            logits, (chunk_len - 1)[:, None, None].astype(jnp.int32), axis=1
        )[0, 0] if want_plp else logits[0, 0]
        first, first_lp = self._sample_first(key, last, samp)
        plp_within = jnp.zeros((tokens.shape[1],), jnp.float32)
        boundary_lp = jnp.zeros((), jnp.float32)
        if want_plp:
            plp_within = self._plp_within(logits, tokens)
            boundary_lp = jax.nn.log_softmax(
                last.astype(jnp.float32)
            )[boundary_next]
        tlv, tli = self._first_tl(last)
        return (scatter_slot(cache, view, slot), key_out, first, first_lp,
                plp_within, boundary_lp, tlv, tli)

    def _finish_check(self, finished):
        for i, req in enumerate(self._slots):
            if req is None or not req.out or req.prefill_only \
                    or req.frozen:
                # Slots mid-chunked-prefill have no output yet; frozen
                # prefill-only slots settle through the export path
                # (even when the prefill token alone completes them —
                # the blob carries the completion); preempted frozen
                # decodes leave through export_slot -> release_frozen.
                continue
            last = req.out[-1]
            nstop = req.hit_stop()
            if nstop is not None:
                req.out = req.out[:-nstop]
                req.lps = req.lps[:len(req.out)]
                if req.tlp is not None:
                    req.tlp = req.tlp[:len(req.out)]
            if nstop is not None or (
                self.eos_id is not None and last == self.eos_id
            ) or len(req.out) >= req.max_new:
                finished.append((req.rid, req.out))
                if self.logprobs:
                    self.finished_logprobs[req.rid] = req.lps[:len(req.out)]
                if self.top_logprobs and req.tlp is not None:
                    self.finished_top_logprobs[req.rid] = (
                        req.tlp[:len(req.out)]
                    )
                if req.plp is not None:
                    self.finished_prompt_logprobs[req.rid] = req.plp
                self.stats["requests_completed"] += 1
                self.stats["tokens_generated"] += len(req.out)
                self._slots[i] = None
                self._release_slot(i)

    def step(self) -> List[Tuple[Any, List[int]]]:
        """Fill free slots, run one decode window (decode_ticks ticks);
        returns finished requests. One host sync per call regardless of
        decode_ticks.

        overlap_decode=True turns this into a two-deep pipeline: the
        next window is dispatched against the CURRENT slot view before
        the previous window's sync is paid, so the device computes
        window k+1 while the host settles window k (detokenize, finish
        checks, slot release) and runs admissions. Consequences, all
        tested: requests admitted in a step join at the NEXT window
        boundary; a slot whose request finished in the un-synced window
        decodes one more (frozen-by-done or discarded) window; settle
        discards results for slots whose request was cancelled or
        replaced in flight (identity check). Strict ordering
        (overlap_decode=False) is bit-identical to the pre-overlap
        engine.

        overlap_prefill=True pipelines the ADMISSION side the same
        way: prefills dispatched in earlier steps settle first — one
        batched pull for all of them, at the step boundary — and the
        settled slots join this step's window; admissions later in
        the step dispatch their prefill and leave it in flight.
        overlap_prefill=False settles each prefill inline at its
        admission, bit-identical to the pre-pipeline engine."""
        finished: List[Tuple[Any, List[int]]] = []
        self.stats["engine_steps"] += 1
        steps = self.obs.steps
        steps.begin_step(
            occupied=sum(r is not None for r in self._slots),
            windows=len(self._windows), prefills=len(self._pflights),
        )
        did_work = False
        try:
            synced = False
            settled_prefills = False
            if self._pflights:
                # Step boundary: every prefill dispatched in earlier steps
                # settles NOW, in one batched pull, BEFORE the next decode
                # window is dispatched — settled slots join this step's
                # window instead of waiting another boundary. A request
                # satisfied by its prefill alone (max_new=1, instant EOS,
                # stop completed by the first token) must be noticed here,
                # before admissions, or its slot stays occupied a step.
                settled_prefills = self._settle_prefills()
                if settled_prefills:
                    self._finish_check(finished)
            if self.overlap_decode and self._windows:
                # Keep the device busy across the sync: dispatch the next
                # window on the current (stale w.r.t. the un-synced window)
                # slot view, THEN pay the previous window's sync. Slots
                # whose request finished in the un-synced window carry a
                # device-side done flag, so their extra window freezes.
                rows = self._active_rows()
                if any(rows):
                    self.obs.occupancy.observe(sum(rows) / self.n_slots)
                    self._dispatch_window(rows)
                synced = self._settle_window(finished) or synced
            prefills0 = self.stats["prefills"] + self.stats["prefill_chunks"]
            # Fill/check until stable: a request satisfied by its prefill
            # alone (max_new=1, instant EOS, or a stop sequence completed by
            # the prefill token) frees its slot for the next queued request,
            # which may itself finish at prefill — every admitted request
            # must pass a finish check BEFORE the decode window, or its
            # one-shot finish condition is missed forever. The prefill
            # budget is shared across the loop's iterations (per step).
            # The span's self time is the admission phase: queue pops, slot
            # prep and finish checks, without the prefill dispatches and
            # inline settles it ran.
            with steps.span("engine.fill"):
                remaining = self.max_prefills_per_step
                # In-flight chunked prefills advance FIRST: they are older
                # than anything still queued, and giving admissions
                # priority would let a sustained stream of short prompts
                # starve an admitted long prompt's chunks out of the
                # per-step budget forever.
                if self._prefilling:
                    used = self._advance_prefills(remaining)
                    if remaining is not None:
                        remaining -= used
                    # A request satisfied by its final chunk alone
                    # (max_new=1, instant EOS) must be noticed before
                    # admission/decode.
                    self._finish_check(finished)
                while True:
                    before = self.stats["prefills"]
                    self._fill_slots(remaining)
                    if remaining is not None:
                        remaining -= self.stats["prefills"] - before
                    n_done = len(finished)
                    self._finish_check(finished)
                    if len(finished) == n_done or (
                        remaining is not None and remaining <= 0
                    ):
                        break
                if self._prefilling and (remaining is None or remaining > 0):
                    # Chunked prompts admitted THIS step start their first
                    # chunk immediately instead of idling a full decode
                    # window.
                    self._advance_prefills(remaining)
                    self._finish_check(finished)
            active_rows = self._active_rows()
            if any(active_rows) and not self._windows:
                self.obs.occupancy.observe(sum(active_rows) / self.n_slots)
                if self.overlap_decode:
                    # Pipeline warm-up (or re-fill after an idle/abort
                    # gap): dispatch and leave in flight; the next step
                    # settles it.
                    self._dispatch_window(active_rows)
                else:
                    # Strict ordering: dispatch and sync within the step.
                    pairs = [(i, self._slots[i])
                             for i in range(self.n_slots) if active_rows[i]]
                    per_slot, per_lps, per_tl = (
                        self._decode_tokens(active_rows)
                    )
                    self._apply_window(pairs, per_slot, per_lps, per_tl,
                                       finished)
                    synced = True
            self._observe_cache_gauges()
            # A record (and the phase, host-overhead and prefill-section
            # observations derived from its spans) only for steps that did
            # work — synced a window, ran or settled a prefill, or finished
            # a request: a server's idle polling steps would otherwise
            # drown the distributions in zeros.
            did_work = (
                synced or settled_prefills or bool(finished)
                or self.stats["prefills"] + self.stats["prefill_chunks"]
                > prefills0
            )
        finally:
            # A step that raises still closes its root span (and the
            # profiler annotation under it) and records nothing.
            steps.end_step(did_work)
        return finished

    # ---- decode-window dispatch / settle ----------------------------

    def _active_rows(self) -> List[bool]:
        """Slots a decode window should advance right now (occupied,
        not mid-chunked-prefill, not awaiting an overlapped prefill
        settle, not frozen awaiting migration)."""
        pending = (self._pending_prefill_slots() if self._pflights
                   else ())
        return [
            r is not None and i not in self._prefilling
            and i not in pending and not r.prefill_only
            and not r.frozen
            for i, r in enumerate(self._slots)
        ]

    def _inflight_advance(self) -> Dict[int, int]:
        """Tokens the un-synced window(s) will have appended to each
        still-current request by the time they settle: a continuing
        request always accepts the full window (anything less means it
        finished, and then the projection is discarded with the slot),
        so the host can project len(out) forward WITHOUT syncing —
        the fact that makes overlapped gen0/length bookkeeping exact."""
        adv: Dict[int, int] = {}
        for w in self._windows:
            for slot, req in w.pairs:
                if self._slots[slot] is req:
                    adv[slot] = adv.get(slot, 0) + w.ticks
        return adv

    def _dispatch_window(self, active_rows) -> _DecodeWindow:
        """Dispatch ONE jitted decode window asynchronously and record
        it in the flight queue. No host sync happens here — jax returns
        the outputs as futures — and no other device call either: the
        window's program is the one dispatch. What changed about the
        slots since the last window goes in as its arguments (the
        active rows, gen0 and the pending patch as one host array; the
        settings matrix and the block table when a value or a row
        changed), the PRNG key is split inside it, and the vectors the
        window carries are rebound from its outputs, so admissions and
        releases that run before the sync compose in dispatch order."""
        steps = self.obs.steps
        n_rows = sum(active_rows)
        with steps.span("engine.dispatch_window", ticks=self.decode_ticks,
                        rows=n_rows, launch=steps.next_launch):
            if self._decode is None:
                self._decode = self._jit_cache_program(
                    self._decode_impl, 8,
                    static_argnames=("greedy_only", "use_bias", "use_pen",
                                     "use_seed", "use_con"),
                )
            adv = self._inflight_advance()
            self._pre_decode(active_rows, adv)
            greedy_only = all(
                r is None or r.temperature == 0.0 for r in self._slots
            )
            use_pen = any(self._slot_pen)
            if self._con_dirty:
                self._rebuild_constraints()
            use_con = self._ctrans is not None
            # Generated-token counts at the window's start: host-known
            # len(out), projected past any window still in flight.
            gen0 = [len(r.out) + adv.get(i, 0) if r is not None else 0
                    for i, r in enumerate(self._slots)]
            (self._cache, self._key, self._carry, counts, toks, lps, tlvs,
             tlis, acts) = self._decode(
                self.params, self._cache, self._key, self._carry,
                self._window_arg(active_rows, gen0), self._samp_arg(),
                self.cache_backend.slot_tables(),
                self._sbias if self._sbias is not None
                else self._zero_bias_row,
                self._scounts if use_pen else self._zero_bias_row,
                # Unconstrained steps pass the shared dummy table so the
                # arg tree keeps its structure without holding a real
                # table alive.
                self._ctrans if use_con else self._dummy_ctrans,
                greedy_only=greedy_only,
                use_bias=self._sbias is not None and any(
                    b is not None for b in self._slot_bias
                ),
                use_pen=use_pen,
                use_seed=any(
                    r is not None and r.seed is not None for r in self._slots
                ),
                use_con=use_con,
            )
            if use_pen:
                self._scounts = counts
            steps.launch("window", program_name(self._decode), acts,
                         ticks=self.decode_ticks, rows=n_rows)
            w = _DecodeWindow(
                pairs=[(i, self._slots[i])
                       for i in range(self.n_slots) if active_rows[i]],
                ticks=self.decode_ticks,
                arrays=(toks, lps, tlvs, tlis, acts),
            )
            self._windows.append(w)
            for slot, req in w.pairs:
                if req.trace is not None:
                    # Dispatch half of the overlap pipeline: recorded per
                    # request so a timeline shows every window the request
                    # rode, with the in-flight depth at dispatch.
                    req.trace.record("window-dispatch", src="engine",
                                     rid=req.rid, slot=slot, ticks=w.ticks,
                                     depth=len(self._windows))
            if self._window_hooks is not None:
                self._window_hooks.on_dispatch(w)
            return w

    def _sync_window(self, w: _DecodeWindow):
        """THE host sync: pull a dispatched window's packed results
        (tokens, validity flags, logprob sidecars — one transfer) and
        slice each slot's valid prefix. Returns (tokens, logprobs,
        top-K alternatives) keyed by slot."""
        steps = self.obs.steps
        with steps.span("engine.wait_window"):
            if self._window_hooks is not None:
                self._window_hooks.before_sync(w)
            steps.land(w.arrays[4], w.arrays)
            host_toks, host_lps, host_tlv, host_tli, host_acts = (
                jax.device_get(w.arrays)  # shellac: ignore[SH002] — the decode window's ONE packed sync; everything the host needs arrives in this single transfer
            )
        # Device-side stop decisions arrive as per-tick validity flags;
        # valid ticks are a prefix (done is sticky), so each slot's
        # token list is a slice, not a scan.
        n_valid = host_acts.sum(axis=0)
        steps.count(decode_slot_ticks=w.ticks * self.n_slots,
                    decode_valid_ticks=int(n_valid.sum()),
                    **self.cache_backend.window_counts(w.pairs, n_valid))
        per_slot = [host_toks[:n_valid[i], i].tolist()
                    for i in range(self.n_slots)]
        if not self.logprobs:
            return per_slot, None, None
        per_lps = [host_lps[:n_valid[i], i].tolist()
                   for i in range(self.n_slots)]
        if not self.top_logprobs:
            return per_slot, per_lps, None
        # (ticks, n_slots, K) -> per slot, per valid tick: (ids, lps).
        per_tl = [
            [(host_tli[j, i].tolist(), host_tlv[j, i].tolist())
             for j in range(n_valid[i])]
            for i in range(self.n_slots)
        ]
        return per_slot, per_lps, per_tl

    def _apply_window(self, pairs, per_slot, per_lps, per_tl,
                      finished) -> None:
        """Settle a synced window on the host: append its tokens, then
        the finish checks and slot releases they trigger."""
        with self.obs.steps.span("engine.apply_window") as sp:
            n_done = len(finished)
            n = self._apply_pairs(pairs, per_slot, per_lps, per_tl)
            self._finish_check(finished)
            sp.set(tokens=n, finished=len(finished) - n_done)

    def _apply_pairs(self, pairs, per_slot, per_lps, per_tl) -> int:
        """Append a window's valid tokens to the requests that owned
        the slots at dispatch; returns how many were appended. The
        identity check discards results for slots cancelled or
        re-admitted while the window was in flight (overlap), and the
        per-token break re-checks the host-only finish conditions
        (stop sequences; EOS/budget are pre-cut device-side but
        re-checked as the single source of truth)."""
        n_applied = 0
        for slot, req in pairs:
            if self._slots[slot] is not req or slot in self._prefilling:
                # Cancelled or replaced while the window was in flight:
                # results discarded, and deliberately NO settle event —
                # a cancelled request's timeline ends at its
                # cancellation, never with a stale-slot settle.
                continue
            if req.trace is not None:
                req.trace.record("window-settle", src="engine",
                                 rid=req.rid, slot=slot,
                                 n_tokens=len(per_slot[slot]))
            for j, tok in enumerate(per_slot[slot]):
                req.out.append(int(tok))
                n_applied += 1
                if per_lps is not None:
                    req.lps.append(float(per_lps[slot][j]))
                if per_tl is not None:
                    if req.tlp is None:
                        req.tlp = []
                    req.tlp.append(per_tl[slot][j])
                last = req.out[-1]
                if (self.eos_id is not None and last == self.eos_id) or (
                    len(req.out) >= req.max_new
                ) or req.hit_stop() is not None:
                    # Later window tokens are post-EOS/budget/stop
                    # overshoot; the device froze (EOS/budget) or kept
                    # decoding (stop sequence), and the request never
                    # sees them either way.
                    break
        self.obs.steps.count(tokens_delivered=n_applied)
        return n_applied

    def _settle_window(self, finished) -> bool:
        """Sync and settle the OLDEST in-flight window; False if none
        was in flight."""
        if not self._windows:
            return False
        w = self._windows.popleft()
        per_slot, per_lps, per_tl = self._sync_window(w)
        self._apply_window(w.pairs, per_slot, per_lps, per_tl, finished)
        return True

    def _observe_cache_gauges(self) -> None:
        """Per-step utilization gauges. Host-known values only (slot
        list, host-tracked lengths) — no device reads."""
        obs = self.obs
        if not obs.registry.enabled:
            return
        obs.slots_busy.set(sum(r is not None for r in self._slots))
        obs.queue_depth.set(len(self._queue))
        obs.kv_util.set(self._kv_utilization())

    def _kv_utilization(self) -> float:
        """Live residency / capacity, by the backend's own accounting
        (dense: token counting; paged: pool blocks in use)."""
        return self.cache_backend.utilization()

    def _decode_tokens(self, active_rows):
        """Advance every active slot; returns (tokens_per_slot,
        logprobs_per_slot or None, top-K per slot or None), already cut
        to each slot's valid count, in one host sync. The strict-
        ordering path (dispatch + immediate sync); overridden wholesale
        by the speculative engine."""
        w = self._dispatch_window(active_rows)
        self._windows.pop()  # settled inline, not via the flight queue
        return self._sync_window(w)

    def _pre_decode(self, active_rows, advance=None) -> None:
        """Backend hook before each decode window (paged: grow block
        tables to cover the window's write span). `advance` maps slot
        -> tokens an un-synced in-flight window will still append
        (overlapped dispatch), so length projections stay exact
        without a host sync."""
        self.cache_backend.pre_window(active_rows, advance,
                                      self._window_write_span())

    def release_frozen(self, rid) -> Optional[_Request]:
        """Release a frozen slot (prefill-only OR preempted decode)
        after its export (caller must be the engine-owning thread —
        the same thread that froze it). Returns the request, or None
        for an unknown rid. Device rows need no repair: stale rows are
        self-healing, exactly as on cancel."""
        slot = self.frozen_prefills.pop(rid, None)
        if slot is None:
            slot = self.frozen_decodes.pop(rid, None)
        if slot is None:
            return None
        req = self._slots[slot]
        self._slots[slot] = None
        self._release_slot(slot)
        return req

    def preemptable(self) -> List[Tuple[Any, int, int, int]]:
        """(rid, slot, qos_class, resident_tokens) for every slot a
        preemption could evict right now: occupied, actively decoding
        (not frozen, not prefill-only, not mid-prefill), and carrying
        only state the migration wire format can ship (no compiled
        constraint). resident_tokens is the slot's physical KV
        residency — multiply by the backend's bytes_per_token() for
        the park-bytes cost the victim rule ranks on."""
        pending = (self._pending_prefill_slots() if self._pflights
                   else ())
        out = []
        for i, req in enumerate(self._slots):
            if (req is None or req.prefill_only or req.frozen
                    or i in self._prefilling or i in pending
                    or req.constraint is not None or not req.out):
                continue
            resident = int(req.tokens.size) + max(len(req.out) - 1, 0)
            out.append((req.rid, i, int(req.qos_class), resident))
        return out

    def preempt(self, rid) -> List[Tuple[Any, List[int]]]:
        """Freeze an actively-decoding request in place so the caller
        can export -> park -> release its slot (caller must be the
        engine-owning thread). Mirrors the prefill_only freeze: the
        device row gets its sticky done flag, the host excludes the
        slot from decode windows and _finish_check, and the rid lands
        in frozen_decodes.

        In-flight pipelines (overlapped prefills and decode windows)
        are settled FIRST so the host's `out` and the device KV agree
        at the freeze point — anything that finished while draining is
        returned exactly as step() results, for normal delivery. If
        the target itself finished during the drain, nothing freezes
        and the finished list carries its settlement."""
        finished: List[Tuple[Any, List[int]]] = []
        slot = next((i for i, r in enumerate(self._slots)
                     if r is not None and r.rid == rid), None)
        if slot is None:
            raise ValueError(f"preempt: rid {rid!r} holds no slot")
        req = self._slots[slot]
        if req.prefill_only or req.frozen:
            raise ValueError(f"preempt: rid {rid!r} is already frozen")
        if slot in self._prefilling or (
            self._pflights and slot in self._pending_prefill_slots()
        ):
            raise ValueError(f"preempt: rid {rid!r} is mid-prefill")
        if self._pflights:
            self._settle_prefills()
            self._finish_check(finished)
        while self._windows:
            self._settle_window(finished)
        if self._slots[slot] is not req:
            return finished
        self._patch_slot(slot, done=True)
        req.frozen = True
        self.frozen_decodes[rid] = slot
        self.stats["preemptions"] += 1
        if req.trace is not None:
            req.trace.record("preempt", src="engine", rid=rid,
                             slot=slot, n_out=len(req.out),
                             qos_class=int(req.qos_class))
        return finished

    def cancel(self, rid) -> bool:
        """Drop a queued or in-flight request (caller must be the
        engine-owning thread). Frees its slot immediately; device
        state needs no repair (stale cache rows are self-healing)."""
        for i, req in enumerate(self._slots):
            if req is not None and req.rid == rid:
                self._slots[i] = None
                self._prefilling.pop(i, None)
                self.frozen_prefills.pop(rid, None)
                self.frozen_decodes.pop(rid, None)
                self._release_slot(i)
                self.finished_logprobs.pop(rid, None)
                self.finished_prompt_logprobs.pop(rid, None)
                self.finished_top_logprobs.pop(rid, None)
                self.stats["requests_cancelled"] += 1
                if req.trace is not None:
                    req.trace.abort("cancelled")
                return True
        for req in list(self._queue):
            if req.rid == rid:
                self._queue.remove(req)
                self.stats["requests_cancelled"] += 1
                if req.trace is not None:
                    req.trace.abort("cancelled")
                return True
        return False

    def abort_all(self) -> List[Any]:
        """Drop EVERY queued and in-flight request (caller must be the
        engine-owning thread); returns the dropped rids. The supervisor
        rebuild / multi-host epoch-resync helper: slots release cleanly
        (paged pools get their blocks back), per-slot sampling state
        clears through _release_slot, and stale finished_* deposits are
        swept so a rebuilt server cannot hand a new request an old
        generation's logprobs. Device cache rows need no repair — stale
        rows are self-healing (lengths roll back at the next admit)."""
        # Drain the in-flight decode window(s) and prefill flight(s)
        # first (overlapped dispatch): block until the device finishes
        # and DISCARD the results, so a rebuilt/resynced engine can
        # never mis-attribute a stale window's tokens (or a stale
        # prefill's first token) to a new generation's requests, and
        # the device is quiescent when the caller reuses it. The
        # prefill hooks are deliberately NOT consulted — this is
        # failure-path cleanup, not a measured settle.
        while self._windows:
            jax.device_get(self._windows.popleft().arrays)
        while self._pflights:
            jax.device_get(self._pflights.pop().arrays)
        self.obs.steps.drop_launches()
        dropped = []
        for req in self._queue:
            dropped.append(req.rid)
            if req.trace is not None:
                req.trace.abort("cancelled")
        self._queue.clear()
        for i, req in enumerate(self._slots):
            if req is None:
                continue
            dropped.append(req.rid)
            if req.trace is not None:
                req.trace.abort("cancelled")
            self._slots[i] = None
            self._release_slot(i)
        self._prefilling.clear()
        self._patch[:] = 0
        self.frozen_prefills.clear()
        self.frozen_decodes.clear()
        self.finished_logprobs.clear()
        self.finished_prompt_logprobs.clear()
        self.finished_top_logprobs.clear()
        # Backend allocator to canonical pristine state (paged purges
        # prefix registries and rebuilds the free list in constructor
        # order — required for multi-host resync convergence).
        self.cache_backend.reset()
        self.stats["requests_cancelled"] += len(dropped)
        return dropped

    def set_decode_ticks(self, k: int) -> None:
        """Rewrite decode_ticks between windows — the auto-tuner's
        write-back. Invalidates the lazily built decode program (the
        window length is baked into its trace); windows already in
        flight keep the tick count they were dispatched with."""
        k = int(k)
        if k < 1:
            raise ValueError(f"decode_ticks must be >= 1, got {k}")
        if k != self.decode_ticks:
            self.decode_ticks = k
            self._decode = None
        self.stats["decode_ticks"] = k

    def set_prefill_chunk(self, chunk: Optional[int]) -> None:
        """Rewrite prefill_chunk between steps — the prefill
        auto-tuner's write-back (None = whole prompts). The chunk jits
        are keyed by pad bucket, so nothing invalidates; rolling
        backends refuse (their ring slack was sized to the
        construction-time chunk and cannot grow post-hoc)."""
        if chunk is not None:
            chunk = int(chunk)
            if chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {chunk}"
                )
        if chunk is not None:
            self.cache_backend.check_feature("chunked_prefill")
        if self.cache_backend.is_rolling and (
            chunk or 1
        ) > self.cache_backend.chunk_slack:
            raise ValueError(
                f"prefill_chunk={chunk} exceeds the rolling ring's "
                f"construction-time chunk slack "
                f"({self.cache_backend.chunk_slack}); pass "
                "prefill_chunk at construction instead"
            )
        self.prefill_chunk = chunk
        self.stats["prefill_chunk"] = chunk or 0

    @property
    def pending(self) -> int:
        return len(self._queue) + sum(r is not None for r in self._slots)

    def run(self, requests=None) -> Dict[Any, List[int]]:
        """Drain: submit (rid, tokens, max_new) triples, step to empty."""
        for r in requests or ():
            self.submit(*r)
        results: Dict[Any, List[int]] = {}
        while self.pending:
            for rid, out in self.step():
                results[rid] = out
        return results

    # ---- beam search (dense caches) ----------------------------------

    def beam_search(self, prompt_tokens, *, num_beams: int = 4,
                    max_new_tokens: int = 32, eos_id=None,
                    length_penalty: float = 1.0, constraint=None):
        """Deterministic beam decode of ONE prompt on this engine's
        params — the HTTP-facing entry point (server `num_beams`).

        Dense/int8/rolling caches delegate to a lazily built
        single-request Engine SHARING the params (jax arrays are
        immutable, so no copy; the delegate allocates its own
        (num_beams, max_len) cache per call and frees it on return —
        the slot batch is untouched). The paged subclass overrides
        this with its copy-on-write block-table search. Caller must be
        the engine-owning thread, like step()/submit(). `constraint`
        (a compiled constraints.TokenDFA) masks every beam through the
        grammar; invalid beams are pruned."""
        if eos_id is None:
            eos_id = self.eos_id
        if self._beam_delegate is None:
            from shellac_tpu.inference.engine import Engine

            self._beam_delegate = Engine(
                self.cfg, self.params, max_len=self.max_len,
                mesh=self.mesh, kv_quant=self.kv_quant,
                rolling_window=self.rolling_window,
            )
        return self._beam_delegate.beam_search(
            prompt_tokens, num_beams=num_beams,
            max_new_tokens=max_new_tokens, eos_id=eos_id,
            length_penalty=length_penalty, constraint=constraint,
        )


class PagedBatchingEngine(BatchingEngine):
    """Continuous batching over a shared block pool (paged KV cache).

    Dense slots reserve n_slots*max_len tokens of KV whether used or
    not; here slots borrow fixed-size blocks from one pool as they grow
    and return them on completion, so resident KV memory tracks the
    tokens actually alive. `pool_tokens` (default: half the dense
    footprint) is the capacity knob; admission blocks — requests wait in
    queue — when the pool can't cover a prompt.

    Block 0 is reserved scratch: unallocated table entries point at it,
    so out-of-range reads/writes land there and are masked downstream.

    prefix_cache=True adds automatic prefix caching (the public
    PagedAttention/vLLM idea, re-built for this pool): full prompt
    blocks are content-hashed with a position-dependent chain, kept in
    the pool after release (refcounted, LRU-evicted only when the free
    list runs dry), and new prompts attach the longest matching block
    chain read-only — prefill then computes only the unmatched suffix,
    attending over the cached prefix KV through the block table. Shared
    blocks are never rewritten: a slot's writes start at its first
    owned block (the match is capped so at least one prompt token is
    computed, which also yields the last-token logits sampling needs).
    """

    _backend_family = ("paged", "paged-int8", "eva")

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        n_slots: int = 8,
        max_len: Optional[int] = None,
        block_size: Optional[int] = None,
        pool_tokens: Optional[int] = None,
        prefix_cache: bool = False,
        cache_backend=None,
        kv_quant: Optional[str] = None,
        **kw,
    ):
        from shellac_tpu.inference.cache import (
            BACKENDS,
            CacheBackend,
            make_backend,
            resolve_backend_name,
        )

        if not isinstance(cache_backend, CacheBackend):
            name = (resolve_backend_name(None, paged=True,
                                         kv_quant=kv_quant)
                    if cache_backend is None else
                    resolve_backend_name(cache_backend,
                                         kv_quant=kv_quant))
            if name not in self._backend_family:
                raise ValueError(
                    f"{type(self).__name__} drives cache backends "
                    f"{self._backend_family}; {name!r} needs a "
                    "different engine class — resolve it through "
                    "inference.cache.engine_class"
                )
            if block_size is None:
                # The backend's own: bf16 pools the finer 16, int8
                # pools 128 (the grouped-gather kernel's scale DMA), an
                # EVA pool its window.
                block_size = BACKENDS[name][0].default_block_size(cfg)
            chunk = kw.get("prefill_chunk")
            cache_backend = make_backend(
                name, cfg, n_slots, max_len or cfg.max_seq_len,
                block_size=block_size, pool_tokens=pool_tokens,
                prefix_cache=prefix_cache,
                # "auto" resolves to whole prompts until tuned — slack
                # like the untuned case (paged slack is advisory).
                chunk_slack=chunk if isinstance(chunk, int) else 1,
            )
        else:
            # A constructed pool carries its own geometry; engine
            # kwargs that would have shaped a registry-built pool are
            # refused instead of silently dropped (a dropped pool size
            # is a capacity incident).
            if block_size is not None \
                    and block_size != cache_backend.block_size:
                raise ValueError(
                    f"block_size={block_size} conflicts with the "
                    f"{cache_backend.name!r} backend instance "
                    f"(block_size={cache_backend.block_size})"
                )
            if pool_tokens is not None:
                raise ValueError(
                    "pool_tokens cannot reshape a constructed backend "
                    "instance; pass pool_tokens to the backend "
                    "constructor instead"
                )
            if prefix_cache and not cache_backend.prefix_cache:
                raise ValueError(
                    f"prefix_cache=True conflicts with the "
                    f"{cache_backend.name!r} backend instance "
                    "(constructed without prefix_cache)"
                )
        super().__init__(cfg, params, n_slots=n_slots, max_len=max_len,
                         cache_backend=cache_backend, **kw)
        self.block_size = self.cache_backend.block_size
        self.prefix_cache = self.cache_backend.prefix_cache
        self._n_blocks = self.cache_backend.n_blocks
        # Keyed (pad_bucket, want_plp), like the dense _chunk_jit.
        self._prefix_prefill_jit: Dict[Any, Any] = {}
        # Beam-search programs, keyed (s_pad, beams, steps, eos,
        # length_penalty, n_gen) — see beam_search below.
        self._beam_jit: Dict[Any, Any] = {}

    # ---- allocator views --------------------------------------------
    # The PagedBackend owns the allocator state; these forward the
    # historical engine surface for the CoW beam search below, tests,
    # and external callers.

    @property
    def _free(self):
        return self.cache_backend._free

    @property
    def _slot_blocks(self):
        return self.cache_backend._slot_blocks

    @property
    def _hash_to_block(self):
        return self.cache_backend._hash_to_block

    @property
    def _block_ref(self):
        return self.cache_backend._block_ref

    def _evictable(self) -> int:
        return self.cache_backend.evictable()

    def _alloc_block(self) -> int:
        return self.cache_backend.alloc_block()

    def _ensure_blocks(self, slot: int, total_tokens: int) -> bool:
        return self.cache_backend.ensure_blocks(slot, total_tokens)

    def _attach_prefix(self, tokens):
        return self.cache_backend.attach_prefix(tokens)

    def _detach_prefix(self, matched) -> None:
        self.cache_backend.detach_prefix(matched)

    def _observe_cache_gauges(self) -> None:
        super()._observe_cache_gauges()
        if self.prefix_cache and self.obs.registry.enabled:
            self.obs.prefix_blocks.set(len(self._hash_to_block))

    # ---- jitted programs --------------------------------------------
    def _chunk_prefill(self, pad, fresh, tokens, chunk_len, offset, slot,
                       samp, boundary_next=None, want_plp=False):
        """Paged chunks reuse the continuation program (a chunk is a
        'suffix' past `offset` resident tokens; offset 0 included).
        Prompt logprobs ride the same stitching contract as the dense
        chunked path: per-chunk in-row scores plus the boundary score
        of the next chunk's first token."""
        jkey = (pad, want_plp)
        if jkey not in self._prefix_prefill_jit:
            self._prefix_prefill_jit[jkey] = self._jit_cache_program(
                functools.partial(
                    self._prefix_prefill_impl, want_plp=want_plp
                ), 7,
            )
        return self._run_chunk_program(
            self._prefix_prefill_jit[jkey], tokens, chunk_len, offset,
            slot, samp, boundary_next,
        )

    def _run_prefill(self, slot: int, req):
        """Prefix-cached prefill: compute only the unmatched suffix;
        returns (first sampled token, its raw logprob)."""
        p = self._prefill_start_offset(slot)
        if p == 0:
            return super()._run_prefill(slot, req)
        suffix = req.tokens[p:]
        s = suffix.size  # >= 1 by the match cap
        # Cap the pad at the table space REMAINING past the prefix:
        # writes start at offset p, and padded positions beyond the
        # table would gather-clamp onto the slot's last real block,
        # corrupting just-written suffix KV (s <= max_len - p always,
        # so the cap never cuts real tokens).
        pad = min(_bucket(s), self.max_len - p)
        padded = np.zeros((1, pad), np.int32)
        padded[0, :s] = suffix
        self._count_prefill(s, pad, False)
        # One dispatch path: the chunk-continuation program IS the
        # suffix prefill (a suffix is a chunk past `p` resident tokens).
        first, lp, _, _, tlv, tli = self._chunk_prefill(
            pad, False, jnp.asarray(padded),
            np.array([s], np.int32), np.array([p], np.int32),
            slot, self._slot_samp(slot, req),
        )
        # No plp payload: submit() refuses prompt_logprobs on
        # prefix-cached engines (the hit skips the scoring passes).
        return (first, lp, ((tlv, tli) if self.top_logprobs else None),
                None)

    def _prefix_prefill_impl(
        self, params, cache, tokens, suffix_len, prefix_len, slot, key,
        samp, boundary_next, tables, *, want_plp: bool = False,
    ):
        """Continue from `prefix_len` cached tokens: a batch-1 view of
        the slot's table row over the shared pool, forwarded with
        fresh_cache=False so the suffix attends to the cached prefix KV
        (and itself) through the table. Suffix K/V writes land in the
        slot's own blocks — shared prefix blocks are upstream of every
        written position, so they stay read-only.

        want_plp returns the same (in-chunk scores, boundary score)
        pair as the dense chunked program, so the base class's
        cross-chunk stitching applies unchanged.

        attn_impl is pinned to "ref": the chunked continuation attends
        over the gathered block view once per request; the flash decode
        kernel targets s<=8 steady-state decode and would only fall
        back (warning) on a prefill-sized s.
        """
        cache, key_out, key = self._enter(cache, tables, key)
        row = jax.lax.dynamic_slice_in_dim(cache.tables, slot, 1, 0)
        if self.kv_quant == "int8":
            view = QuantPagedKVCache(
                k=cache.k, v=cache.v, ks=cache.ks, vs=cache.vs,
                tables=row, lengths=prefix_len.astype(jnp.int32),
            )
        else:
            view = PagedKVCache(
                k=cache.k, v=cache.v, tables=row,
                lengths=prefix_len.astype(jnp.int32), idx=cache.idx,
            )
        logits, view = transformer.forward_with_cache(
            self.cfg, params, tokens, view, new_tokens_len=suffix_len,
            fresh_cache=False, mesh=self.mesh,
            # With an indexer a chunk attends under its queries' choices
            # (ops/dsa_attention.py), a kernel of its own on the TPU.
            attn_impl="ref" if self.cfg.dsa is None else self.attn_impl,
            # Only prompt scoring reads a chunk's other rows.
            logits_at=None if want_plp else suffix_len - 1,
        )
        last = jnp.take_along_axis(
            logits, (suffix_len - 1)[:, None, None].astype(jnp.int32), axis=1
        )[0, 0] if want_plp else logits[0, 0]
        first, first_lp = self._sample_first(key, last, samp)
        plp_within = jnp.zeros((tokens.shape[1],), jnp.float32)
        boundary_lp = jnp.zeros((), jnp.float32)
        if want_plp:
            plp_within = self._plp_within(logits, tokens)
            boundary_lp = jax.nn.log_softmax(
                last.astype(jnp.float32)
            )[boundary_next]
        fields = dict(
            k=view.k, v=view.v,
            lengths=jax.lax.dynamic_update_slice(
                cache.lengths, view.lengths, (slot,)
            ),
        )
        if self.kv_quant == "int8":
            fields.update(ks=view.ks, vs=view.vs)
        if self.cfg.dsa is not None:
            fields.update(idx=view.idx)
        cache = cache.replace(**fields)
        tlv, tli = self._first_tl(last)
        return (cache, key_out, first, first_lp, plp_within, boundary_lp,
                tlv, tli)

    def _prefill_impl(self, params, cache, tokens, prompt_len, slot, key,
                      samp, tables, want_plp: bool = False):
        """Prefill one prompt and leave its state in the slot's pages,
        the backend's way (`prefill_into`: a dense mini cache of the
        pool's kind scattered through the slot's table, or, for EVA
        state, straight through a view of the slot, a window at a
        time). `tables` is the host's block table as this program's
        argument: a new tenant's row (and every row released since the
        last program) reaches the device HERE. want_plp scores the
        prompt from the prefill's own logits — identical math to the
        dense engine's whole-prompt scoring."""
        cache, key_out, key = self._enter(cache, tables, key)

        def forward(scratch, toks, n, fresh_cache=True, logits_at=None):
            return transformer.forward_with_cache(
                self.cfg, params, toks, scratch,
                new_tokens_len=n, fresh_cache=fresh_cache,
                attn_impl=self.attn_impl, mesh=self.mesh,
                logits_at=logits_at,
            )

        logits, cache = self.cache_backend.prefill_into(
            cache, slot, tokens, prompt_len, forward, all_rows=want_plp
        )
        last = (jnp.take_along_axis(
            logits, (prompt_len - 1)[:, None, None].astype(jnp.int32), axis=1
        ) if want_plp else logits)[0, 0]
        first, first_lp = self._sample_first(key, last, samp)
        plp = (self._plp_within(logits, tokens) if want_plp
               else jnp.zeros((tokens.shape[1],), jnp.float32))
        tlv, tli = self._first_tl(last)
        return cache, key_out, first, first_lp, plp, tlv, tli


    # ---- beam search over the pool (copy-on-write tables) ------------

    def beam_search(self, prompt_tokens, *, num_beams: int = 4,
                    max_new_tokens: int = 32, eos_id=None,
                    length_penalty: float = 1.0, constraint=None):
        """Deterministic beam decode of ONE prompt over the block pool.

        Returns (sequences, scores) — the same contract as
        Engine.beam_search, and bit-identical beams to the dense-cache
        implementation (tests/test_beam_search.py paged cases). A
        compiled `constraint` (constraints.TokenDFA) masks each beam
        through its own DFA state exactly like the dense search — the
        shared beam_expand helper owns the math for both.

        Copy-on-write mechanics (the public vLLM CoW idea, expressed
        functionally so the whole search stays one jitted scan):

          - the prompt prefills ONCE into ceil(s/bs) borrowed blocks
            that every beam's table shares READ-ONLY — prompt blocks
            are never written after prefill, so sharing them is free;
            with prefix_cache=True, a cached block chain covering a
            prompt prefix attaches read-only instead (refcounted for
            the search) and only the unmatched suffix is computed;
          - each beam owns one statically-assigned pool block per
            generated logical block (beams advance in lockstep, so
            block boundaries are crossed together and the assignment
            never collides);
          - on beam reorder the adopting beam copies the winning
            beam's PARTIAL tail block into its own block (one
            block-sized copy per beam per step) and repoints its
            table; SEALED full blocks stay shared through the
            gathered tables — never copied.

        Borrowed blocks come from the engine's allocator (evicting LRU
        prefix-cache blocks when the free list is dry) and return on
        completion, so beam searches and live requests share the pool;
        engine slots' tables/lengths are untouched. int8 pools
        compose (the CoW copy moves the scale pools in lockstep with
        the value pools — same block ids), and so do MLA latent-row
        pools (the latent block copies like any value block; the v
        pool is zero-width): both are bit-identical to their
        dense-cache beams.
        """
        from shellac_tpu.inference.engine import check_beam_constraint

        self.cache_backend.check_feature("beam_search")
        k_beams = int(num_beams)
        steps = int(max_new_tokens)
        if k_beams < 1:
            raise ValueError("num_beams must be >= 1")
        if steps < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if eos_id is None and constraint is not None:
            eos_id = self.eos_id
        ctrans, eos_id = check_beam_constraint(
            constraint, eos_id, self.cfg.vocab_size
        )
        toks = np.asarray(prompt_tokens, np.int32).reshape(-1)
        s = int(toks.size)
        bs = self.block_size
        if s + steps + 1 > self.max_len:
            raise ValueError(
                f"prompt {s} + max_new {steps} exceeds max_len "
                f"{self.max_len}"
            )
        lb0 = s // bs
        # Owned generated blocks must cover every CoW target: writes
        # land at positions s .. s+steps-2, and the post-reorder CoW
        # additionally targets the NEXT write position, up to
        # s+steps-1.
        n_gen = 0 if steps == 1 else ((s + steps - 1) // bs - lb0 + 1)
        # Prefix caching composes: a cached block chain covering a
        # strict prompt prefix attaches READ-ONLY (refcounted for the
        # search's duration, exactly like a slot attach) and only the
        # unmatched suffix is computed. The match cap leaves >= 1
        # suffix token so the last-token logits exist, which also
        # keeps the beams' CoW tail block a borrowed one.
        matched: List[int] = []
        if self.prefix_cache:
            _, matched = self._attach_prefix(toks)
        m_tokens = len(matched) * bs
        prompt_n = -(-s // bs) - len(matched)
        need = prompt_n + k_beams * n_gen
        if need > len(self._free) + self._evictable():
            self._detach_prefix(matched)
            raise RuntimeError(
                f"paged pool exhausted: beam search needs {need} "
                f"blocks ({prompt_n} suffix-prompt past "
                f"{len(matched)} cached prefix blocks + "
                f"{k_beams}x{n_gen} owned tails); free "
                f"{len(self._free)} + evictable {self._evictable()}"
            )
        if self.prefix_cache:
            # Counted only once the attach is certain, matching the
            # slot path's hit-rate accounting under pool pressure.
            self.stats["prefix_hit_tokens"] += m_tokens
            self.stats["prefix_query_tokens"] += s
        borrowed = [self._alloc_block() for _ in range(need)]
        try:
            prompt_ids = matched + borrowed[:prompt_n]
            gen_ids = np.asarray(
                borrowed[prompt_n:], np.int32
            ).reshape(n_gen, k_beams)
            mb = self._cache.max_blocks
            row = np.zeros((mb,), np.int32)
            row[:len(prompt_ids)] = prompt_ids
            tables0 = np.tile(row, (k_beams, 1))
            # Only the suffix past the matched prefix is computed. The
            # pad caps at the table space past the prefix: unclamped
            # pads would gather-clamp onto the row's LAST entry and,
            # when the prompt fills the whole table, cycle garbage
            # into real just-written positions (same hazard
            # _run_prefill's cap guards).
            s_suf = s - m_tokens
            s_pad = min(_bucket(s_suf), self.max_len - m_tokens)
            tokens_pad = np.zeros((1, s_pad), np.int32)
            tokens_pad[0, :s_suf] = toks[m_tokens:]
            jit_key = (s_pad, k_beams, steps, eos_id,
                       float(length_penalty), n_gen, m_tokens > 0,
                       ctrans is not None)
            pool_fields = kv_field_names(self.kv_quant)
            fn = self._beam_jit.get(jit_key)
            if fn is None:
                impl = functools.partial(
                    self._beam_paged_impl, steps=steps, eos_id=eos_id,
                    length_penalty=float(length_penalty),
                    has_prefix=m_tokens > 0,
                )
                jit_kw = {}
                if self._cache_sh is not None:
                    jit_kw["out_shardings"] = (
                        tuple(getattr(self._cache_sh, f)
                              for f in pool_fields),
                        None, None, None,
                    )
                fn = jax.jit(named_program(impl), **jit_kw)
                self._beam_jit[jit_key] = fn
            pools, out, norm, lens = fn(
                self.params,
                tuple(getattr(self._cache, f) for f in pool_fields),
                jnp.asarray(tokens_pad),
                jnp.full((1,), s, jnp.int32),
                jnp.full((1,), s_suf, jnp.int32),
                jnp.full((1,), m_tokens, jnp.int32),
                jnp.asarray(tables0), jnp.asarray(gen_ids),
                jnp.int32(lb0), ctrans,
            )
            self._cache = self._cache.replace(
                **dict(zip(pool_fields, pools))
            )
            out, norm, lens = jax.device_get((out, norm, lens))
        finally:
            self._free.extend(borrowed)
            self._detach_prefix(matched)
        from shellac_tpu.inference.engine import beam_filter_invalid

        return beam_filter_invalid(out, norm, lens)

    def _beam_paged_impl(self, params, pools, tokens, prompt_len,
                         suffix_len, prefix_len, tables0, gen_ids, lb0,
                         ctrans=None, *, steps, eos_id, length_penalty,
                         has_prefix=False):
        """Device side of beam_search: prefill once through the shared
        prompt table row, then the dense beam loop with table-gather
        reordering + CoW tail copies instead of cache-row gathers.

        `pools` is (k, v) for bf16 pools or (k, v, ks, vs) for int8
        pools — every array has the block axis at dim 1, so the CoW
        copy and prefill scatter treat them uniformly and the scale
        pools stay in lockstep with the values by construction.

        has_prefix: a cached block chain covers the first prefix_len
        prompt tokens read-only; `tokens` holds only the suffix, which
        forwards as a continuation through the table view (the same
        idiom as _prefix_prefill_impl) and attends to the cached
        prefix KV."""
        cfg = self.cfg
        quant = len(pools) == 4
        k_beams, _ = tables0.shape
        bs = pools[0].shape[3]
        ak = jnp.arange(k_beams)
        mini_fields = kv_field_names(self.kv_quant)

        def make_cache(pools, tables, lengths):
            if quant:
                return QuantPagedKVCache(
                    k=pools[0], v=pools[1], ks=pools[2], vs=pools[3],
                    tables=tables, lengths=lengths,
                )
            return PagedKVCache(k=pools[0], v=pools[1], tables=tables,
                                lengths=lengths)

        s_pad = tokens.shape[1]
        if has_prefix:
            # Suffix continuation through the pool view: writes land
            # in the borrowed prompt blocks past the cached prefix,
            # which stays read-only upstream of every written position.
            view = make_cache(pools, tables0[:1],
                              prefix_len.astype(jnp.int32))
            logits, view = transformer.forward_with_cache(
                cfg, params, tokens, view, new_tokens_len=suffix_len,
                fresh_cache=False, attn_impl="ref", mesh=self.mesh,
            )
            pools = tuple(getattr(view, f) for f in mini_fields)
            last = jnp.take_along_axis(
                logits,
                (suffix_len - 1)[:, None, None].astype(jnp.int32),
                axis=1,
            )[0, 0]
        else:
            # Whole-prompt prefill: mini of the pool's kind once,
            # scattered through the shared prompt blocks (same math as
            # the engine's paged prefill). Pad positions write garbage
            # at tail offsets >= s%bs — overwritten by the beams' own
            # tokens before any read reaches them.
            mini = self._fresh_mini(s_pad)
            logits, mini = transformer.forward_with_cache(
                cfg, params, tokens, mini, new_tokens_len=prompt_len,
                fresh_cache=True, attn_impl=self.attn_impl,
                mesh=self.mesh,
            )
            last = jnp.take_along_axis(
                logits,
                (prompt_len - 1)[:, None, None].astype(jnp.int32),
                axis=1,
            )[0, 0]
            pools = paged_write_prompt(
                pools, [getattr(mini, f) for f in mini_fields], tables0[0]
            )

        from shellac_tpu.inference.engine import (
            beam_expand,
            beam_first_expand,
            beam_rank,
        )

        scores, beam0, tok0, cstate0 = beam_first_expand(
            last, k_beams, ctrans, eos_id
        )
        tables = tables0[beam0]  # rows identical; kept for symmetry
        finished0 = ((tok0 == eos_id) if eos_id is not None
                     else jnp.zeros((k_beams,), bool))
        out0 = jnp.zeros((k_beams, steps), jnp.int32).at[:, 0].set(tok0)
        lens0 = jnp.ones((k_beams,), jnp.int32)
        lengths0 = jnp.broadcast_to(
            prompt_len.astype(jnp.int32), (k_beams,)
        )

        if steps == 1:
            out, norm, lens = beam_rank(scores, out0, lens0,
                                        length_penalty)
            return pools, out, norm, lens

        def scratch_frozen(tables, finished):
            # A frozen beam's cache is dead weight: its logits are
            # replaced by the frozen EOS distribution and no live beam
            # can ever adopt it (finished persists through adoption).
            # Point its WHOLE table at scratch block 0 so its EOS
            # refeed writes land there instead of in a real block —
            # a frozen beam is parked at an old position, and writing
            # through a sealed (shared) block would corrupt live
            # lineages that still read it.
            return jnp.where(finished[:, None], 0, tables)

        def cow(pools, tables, lengths, live):
            # Own the tail block each LIVE beam is about to write: copy
            # the (possibly shared) partial tail into the beam's
            # statically assigned block and repoint its table entry.
            # Live beams advance in lockstep, so `lb` is uniform across
            # them and the (crossing, slot) assignment never reuses a
            # block a sealed table still references; frozen beams are
            # excluded (their lb is stale) and no-op via scratch.
            lb = lengths // bs
            j = jnp.clip(lb - lb0, 0, gen_ids.shape[0] - 1)
            owned = jnp.where(live, gen_ids[j, ak], 0)
            src = jnp.where(live, tables[ak, lb], 0)
            pools = tuple(p.at[:, owned].set(p[:, src]) for p in pools)
            tables = tables.at[ak, lb].set(
                jnp.where(live, owned, tables[ak, lb])
            )
            return pools, tables

        tables = scratch_frozen(tables, finished0)
        pools, tables = cow(pools, tables, lengths0, ~finished0)

        # Named beam_step (not `step`): the module-local lint evidence
        # for scan bodies keys on the NAME, and calling this `step`
        # would mark the host-side engine step() as traced too.
        def beam_step(carry, _):
            (pools, tables, cur, scores, finished, out, lens,
             lengths, cstate, i) = carry
            cache = make_cache(pools, tables, lengths)
            logits, cache = transformer.forward_with_cache(
                cfg, params, cur[:, None], cache,
                attn_impl=self.attn_impl, mesh=self.mesh,
            )
            pools = tuple(getattr(cache, f) for f in mini_fields)
            lengths = cache.lengths
            (scores, beam, tok, out, lens, finished, was_done,
             cstate) = beam_expand(
                logits[:, 0], scores, finished, out, lens, i, eos_id,
                ctrans, cstate,
            )
            tables = tables[beam]
            lengths = lengths[beam]
            # A frozen beam must not grow its cache: the forward wrote
            # its EOS refeed — roll the length back (same as dense).
            lengths = jnp.where(was_done, lengths - 1, lengths)
            tables = scratch_frozen(tables, finished)
            pools, tables = cow(pools, tables, lengths, ~finished)
            return (pools, tables, tok, scores, finished, out, lens,
                    lengths, cstate, i + 1), None

        carry = (pools, tables, tok0, scores, finished0, out0, lens0,
                 lengths0, cstate0, jnp.int32(1))
        (pools, _, _, scores, _, out, lens, _, _, _), _ = jax.lax.scan(
            beam_step, carry, None, length=steps - 1
        )
        out, norm, lens = beam_rank(scores, out, lens, length_penalty)
        return pools, out, norm, lens


# Backward-compatible alias: the exception moved to the cache
# subsystem with the allocator that raises it.
_PoolExhausted = PoolExhausted
