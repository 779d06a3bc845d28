"""Disaggregated prefill/decode serving: the KV-migration subsystem.

Prefill is compute-bound and bursty; decode is memory-bandwidth-bound
and steady. Co-locating them makes every replica bad at both (the
shellac_step_phase_seconds{phase="prefill_dispatch"} share is the
committed measurement of the interference). This module is the seam
that splits them: a PREFILL replica runs the prompt, freezes the slot
(the engine's device-side done flag — PR 7's freeze mechanism), and
ships the slot's KV state to a DECODE replica, which re-registers the
blocks with its own allocator and streams tokens as if it had
prefilled locally.

The migration contract is built on `CacheBackend.residency()` being
JSON-serializable per-slot state and on the paged backend owning ALL
allocator state host-side: "migrate a request" is exactly "transfer
its blocks and re-register them" (`ensure_blocks` grows the importer's
table; the device only ever sees tables, so block ids are free to
differ across replicas).

Wire format (version 1, `MigrationBlob.serialize`):

    magic "SHLKV1\\0" | u32 header length | JSON header | raw payload

The header carries the backend registry name, per-array dtype/shape,
the backend's `residency()` manifest, the full request state (prompt,
sampling settings, the prefill-sampled token(s), logprob sidecars),
the engine agreement block (eos_id, logprobs, top_logprobs), the model
geometry fingerprint, and the trace id (PR 10) — so one id walks the
prefill replica's recorder, the transfer, and the decode replica's
recorder. The device payload is CHUNKED: each array is split into
`chunk_bytes` chunks, each with its own crc32, so a truncated or
corrupted transfer is refused loudly at deserialize instead of
decoding garbage KV. Chunk size is a knob on purpose: the transfer
path is characterized (bytes histogram + seconds histogram), not
guessed — the CUDA-aware-MPI discipline from PAPERS.md.

Token identity across the migration (tested in tests/test_disagg.py
and the test_cache_backends.py conformance suite): greedy requests
are bit-identical because the decode math reads the same KV values at
the same positions; seeded requests are identical because sampling
derives from the REQUEST's (seed, gen_idx) stream, not the engine's
shared key. Unseeded sampled requests draw from the destination
engine's stream — the same caveat as any scheduling change.

Out of scope (loud refusals, never silent): cross-backend migration
(the wire format names the backend and the importer must match),
constrained requests (a compiled TokenDFA does not serialize),
speculative engines (the draft cache is unshipped state), and
patterned local/global rolling caches.
"""

from __future__ import annotations

import json
import zlib
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from shellac_tpu.inference.cache import PoolExhausted
from shellac_tpu.inference.kvcache import kv_field_names

MAGIC = b"SHLKV1\x00"
VERSION = 1
#: Default transfer chunk size. Each chunk carries its own crc32 in the
#: header, so integrity granularity (and any future streaming overlap
#: of transfer with compute) is tunable without a format bump.
DEFAULT_CHUNK_BYTES = 1 << 20

#: Backends the migration path supports — exactly the registry.
SUPPORTED_BACKENDS = ("dense", "dense-int8", "paged", "paged-int8",
                      "rolling", "rolling-int8")


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, including the ml_dtypes extensions jax caches
    use (bfloat16)."""
    try:
        return np.dtype(name)
    except TypeError:
        if name == "bfloat16":
            return np.dtype(jnp.bfloat16)
        raise ValueError(f"unknown array dtype {name!r} in KV blob")


def model_fingerprint(engine) -> Dict[str, Any]:
    """The geometry both sides must agree on for imported KV to mean
    the same thing to the importer's decode programs. `dtype` is the
    cache compute dtype: without it a bf16->f32 pair would silently
    CAST the KV at import (jnp .set casts) instead of refusing — the
    one mismatch the array shapes cannot catch."""
    cfg = engine.cfg
    return {
        "n_layers": int(cfg.n_layers),
        "kv_heads": int(cfg.cache_kv_heads),
        "head_dim": int(cfg.cache_head_dim),
        "v_head_dim": int(cfg.cache_v_head_dim),
        "vocab_size": int(cfg.vocab_size),
        "dtype": str(jnp.dtype(cfg.compute_dtype).name),
    }


def _engine_agreement(engine) -> Dict[str, Any]:
    """Engine-level settings that change the decode MATH or the render
    surface: a mismatch would silently break token identity (eos) or
    drop sidecars a client asked for (logprobs)."""
    return {
        "eos_id": engine.eos_id,
        "logprobs": bool(engine.logprobs),
        "top_logprobs": int(engine.top_logprobs),
    }


class MigrationBlob:
    """One migratable request: JSON header + named device arrays."""

    __slots__ = ("header", "arrays")

    def __init__(self, header: Dict[str, Any],
                 arrays: Dict[str, np.ndarray]):
        self.header = header
        self.arrays = arrays

    # ---- wire format -------------------------------------------------

    def serialize(self, chunk_bytes: int = DEFAULT_CHUNK_BYTES) -> bytes:
        """MAGIC | u32 header-len | header JSON | concatenated array
        bytes. The header's `arrays` manifest records, per array:
        name, dtype, shape, and the per-chunk crc32 list (chunks of
        `chunk_bytes`, last one short)."""
        if chunk_bytes < 1:
            raise ValueError("chunk_bytes must be >= 1")
        manifest: List[Dict[str, Any]] = []
        payloads: List[bytes] = []
        for name, arr in self.arrays.items():
            arr = np.ascontiguousarray(arr)
            raw = arr.tobytes()
            crcs = [
                zlib.crc32(raw[i:i + chunk_bytes])
                for i in range(0, max(len(raw), 1), chunk_bytes)
            ]
            manifest.append({
                "name": name,
                "dtype": str(arr.dtype),
                "shape": list(arr.shape),
                "nbytes": len(raw),
                "chunk_bytes": chunk_bytes,
                "crcs": crcs,
            })
            payloads.append(raw)
        header = dict(self.header)
        header["version"] = VERSION
        header["arrays"] = manifest
        hj = json.dumps(header).encode()
        return b"".join([MAGIC, len(hj).to_bytes(4, "big"), hj] + payloads)

    @classmethod
    def deserialize(cls, data: bytes) -> "MigrationBlob":
        """Parse + integrity-check a serialized blob. Every failure is
        a ValueError naming what broke — corrupt KV must be refused at
        the door, never decoded into a pool."""
        if len(data) < len(MAGIC) + 4 or data[:len(MAGIC)] != MAGIC:
            raise ValueError("not a KV migration blob (bad magic)")
        off = len(MAGIC)
        hlen = int.from_bytes(data[off:off + 4], "big")
        off += 4
        if off + hlen > len(data):
            raise ValueError("KV blob truncated inside the header")
        try:
            header = json.loads(data[off:off + hlen])
        except ValueError as e:
            raise ValueError(f"KV blob header is not valid JSON: {e}")
        off += hlen
        if header.get("version") != VERSION:
            raise ValueError(
                f"KV blob version {header.get('version')!r}; this "
                f"build speaks version {VERSION}"
            )
        arrays: Dict[str, np.ndarray] = {}
        for ent in header.get("arrays", ()):
            n = int(ent["nbytes"])
            raw = data[off:off + n]
            if len(raw) != n:
                raise ValueError(
                    f"KV blob truncated inside array {ent['name']!r} "
                    f"(want {n} bytes, have {len(raw)})"
                )
            cb = int(ent["chunk_bytes"])
            crcs = ent["crcs"]
            for j in range(len(crcs)):
                chunk = raw[j * cb:(j + 1) * cb]
                if zlib.crc32(chunk) != crcs[j]:
                    raise ValueError(
                        f"KV blob chunk {j} of array {ent['name']!r} "
                        "failed its crc32 (corrupt transfer)"
                    )
            arrays[ent["name"]] = np.frombuffer(
                raw, dtype=_np_dtype(ent["dtype"])
            ).reshape(ent["shape"])
            off += n
        if off != len(data):
            raise ValueError(
                f"KV blob carries {len(data) - off} trailing bytes "
                "past its manifest"
            )
        return cls(header, arrays)


# ---------------------------------------------------------------------
# Export (prefill replica, engine-owning thread)
# ---------------------------------------------------------------------


def _check_exportable(engine) -> None:
    from shellac_tpu.inference.spec_batching import _SpecDecodeMixin

    if isinstance(engine, _SpecDecodeMixin):
        # The draft model's cache is unshipped state: an exported slot
        # would adopt with a desynced draft, and an imported one would
        # verify against a draft that never saw the prompt. Refused on
        # BOTH sides (this check guards export and import alike).
        raise ValueError(
            "KV migration does not support speculative engines (the "
            "draft model's cache does not migrate); serve draft-model "
            "replicas monolithically"
        )
    name = engine.cache_backend.name
    if name not in SUPPORTED_BACKENDS:
        raise ValueError(
            f"KV migration does not support the {name!r} backend"
        )
    kind = type(engine._cache).__name__
    if "Patterned" in kind:
        raise ValueError(
            "KV migration does not support patterned local/global "
            "rolling caches (mixed ring/dense rows per layer); use a "
            "uniform-window or dense backend, or serve monolithically"
        )


def _request_state(req, eos_id):
    """(state dict, complete?) — the request's JSON-serializable half:
    everything the importer needs to rebuild an identical _Request and
    slot sampling state."""
    out = list(req.out)
    lps = list(req.lps)
    tlp = req.tlp
    nstop = req.hit_stop()
    if nstop is not None:
        out = out[:-nstop]
        lps = lps[:len(out)]
        if tlp is not None:
            tlp = tlp[:len(out)]
    complete = (
        nstop is not None
        or (eos_id is not None and out and out[-1] == eos_id)
        or len(out) >= req.max_new
    )
    state: Dict[str, Any] = {
        "tokens": [int(t) for t in req.tokens],
        "max_new": int(req.max_new),
        "stop": req.stop,
        "temperature": req.temperature,
        "top_k": req.top_k,
        "top_p": req.top_p,
        "min_p": req.min_p,
        "min_tokens": req.min_tokens,
        "presence_penalty": req.presence_penalty,
        "frequency_penalty": req.frequency_penalty,
        "seed": req.seed,
        "logit_bias": ({str(k): v for k, v in req.logit_bias.items()}
                       if req.logit_bias else None),
        "prompt_logprobs": bool(req.prompt_logprobs),
        "out": [int(t) for t in out],
        "lps": [float(x) for x in lps],
        "tlp": ([[list(ids), [float(v) for v in vals]]
                 for ids, vals in tlp] if tlp is not None else None),
        # By export time the prefill is complete, so plp (when the
        # request scored its prompt) is the stitched flat float list.
        "plp": (None if req.plp is None
                else [float(x) for x in req.plp]),
    }
    return state, complete


def export_slot(engine, slot: int, req,
                trace_id: Optional[str] = None) -> MigrationBlob:
    """Serialize the frozen prefill-only request in `slot` (caller must
    be the engine-owning thread). The slot is NOT released here — the
    caller releases after the host copies below exist (device_get),
    so a failed export leaves a slot the caller can still clean up.

    A request already complete at its prefill (max_new=1, instant EOS,
    or a stop match on the first token) exports with `complete: true`
    and NO device payload — the importer settles it without touching
    its pool."""
    _check_exportable(engine)
    backend = engine.cache_backend
    state, complete = _request_state(req, engine.eos_id)
    # Physical KV residency: after n_out emitted tokens the slot holds
    # the prompt plus (n_out - 1) generated positions — the latest
    # token lives in _cur and writes its KV on the NEXT decode tick.
    # At the prefill_only freeze (n_out == 1) this is exactly the old
    # prompt-length export; a mid-decode preemption export ships the
    # decoded positions too.
    length = int(req.tokens.size) + max(len(req.out) - 1, 0)
    header: Dict[str, Any] = {
        "backend": backend.name,
        "kv_quant": engine.kv_quant,
        "model": model_fingerprint(engine),
        "engine": _engine_agreement(engine),
        "length": length,
        "complete": complete,
        "request": state,
        "residency": backend.residency(),
        "trace_id": trace_id,
    }
    if complete:
        return MigrationBlob(header, {})
    fields = kv_field_names(engine.kv_quant)
    cache = engine._cache
    if backend.is_paged:
        bs = backend.block_size
        nb_used = -(-length // bs)
        blocks = backend._slot_blocks[slot][:nb_used]
        if len(blocks) < nb_used:
            raise ValueError(
                f"slot {slot} holds {len(blocks)} blocks but its "
                f"{length} resident tokens need {nb_used} — allocator "
                "state desynced from the request"
            )
        header["block_size"] = bs
        header["n_blocks"] = nb_used
        idx = jnp.asarray(blocks, jnp.int32)
        pulls = {f: getattr(cache, f)[:, idx] for f in fields}
    elif backend.is_rolling:
        # The ring is window-sized and positions wrap: ship the WHOLE
        # ring row verbatim (content-at-ring-slot is the state).
        header["ring"] = int(cache.ring)
        pulls = {f: getattr(cache, f)[:, slot] for f in fields}
    else:
        pulls = {f: getattr(cache, f)[:, slot, :, :length]
                 for f in fields}
    # ONE blocking pull for the whole slot: the export is the admission
    # path's tail, never the decode hot loop.
    host = jax.device_get(pulls)  # shellac: ignore[SH002] — the migration export's single batched pull; the KV must reach the host to go on the wire
    return MigrationBlob(header, {f: np.asarray(a)
                                  for f, a in host.items()})


# ---------------------------------------------------------------------
# Import (decode replica, engine-owning thread)
# ---------------------------------------------------------------------


def _validate_import(engine, header: Dict[str, Any]) -> None:
    _check_exportable(engine)
    backend = engine.cache_backend
    if header.get("backend") != backend.name:
        raise ValueError(
            f"KV blob is for backend {header.get('backend')!r}; this "
            f"engine runs {backend.name!r} (cross-backend migration "
            "is refused — the storage layouts differ)"
        )
    fp = model_fingerprint(engine)
    if header.get("model") != fp:
        raise ValueError(
            f"KV blob model geometry {header.get('model')} does not "
            f"match this engine's {fp}"
        )
    agree = _engine_agreement(engine)
    if header.get("engine") != agree:
        raise ValueError(
            f"KV blob engine contract {header.get('engine')} does not "
            f"match this engine's {agree} (eos/logprobs settings must "
            "agree across a disaggregated pair)"
        )
    if backend.is_paged and header.get("block_size") != backend.block_size:
        raise ValueError(
            f"KV blob pages are {header.get('block_size')} tokens; "
            f"this pool uses {backend.block_size} (block_size must "
            "match across a disaggregated pair)"
        )


def import_blob(engine, blob: MigrationBlob, rid: Any,
                trace: Optional[Any] = None) -> int:
    """Adopt one INCOMPLETE migrated request into a free slot (caller
    must be the engine-owning thread; complete blobs settle without an
    engine — see the server's import path). Returns the slot.

    Raises PoolExhausted when no slot (or no pool capacity) is free —
    the retryable class; ValueError for a blob this engine must refuse
    (wrong backend/geometry/contract)."""
    header = blob.header
    _validate_import(engine, header)
    if header.get("complete"):
        raise ValueError("complete blobs carry no KV to import")
    backend = engine.cache_backend
    r = header["request"]

    slot = next(
        (i for i, occ in enumerate(engine._slots)
         if occ is None and i not in engine._prefilling),
        None,
    )
    if slot is None:
        raise PoolExhausted()

    # Rebuild the request through submit() so every validation (budget
    # vs max_len, sampling ranges, seed folding, logit_bias bounds)
    # applies to imported state exactly as it would to a local
    # admission — then pop it straight off the queue into the slot.
    engine.submit(
        rid, np.asarray(r["tokens"], np.int32), int(r["max_new"]),
        stop=r.get("stop"),
        temperature=r.get("temperature"), top_k=r.get("top_k"),
        top_p=r.get("top_p"), min_p=r.get("min_p"),
        min_tokens=r.get("min_tokens"),
        logit_bias=({int(k): float(v)
                     for k, v in r["logit_bias"].items()}
                    if r.get("logit_bias") else None),
        presence_penalty=r.get("presence_penalty"),
        frequency_penalty=r.get("frequency_penalty"),
        prompt_logprobs=bool(r.get("prompt_logprobs")),
        seed=r.get("seed"), trace=trace,
    )
    req = engine._queue.pop()
    req.out = [int(t) for t in r["out"]]
    req.lps = [float(x) for x in r.get("lps") or ()]
    if r.get("tlp") is not None:
        req.tlp = [(list(ids), list(vals)) for ids, vals in r["tlp"]]
    if r.get("plp") is not None:
        req.plp = r["plp"]
    if not req.out:
        raise ValueError("KV blob carries no generated tokens")
    length = int(header["length"])

    try:
        return _place_slot(engine, backend, blob, header, req, rid,
                           slot, length, trace)
    except Exception:
        # A failure past block reservation (malformed manifest, a
        # shape-mismatched array) must not leak pool blocks or
        # half-written slot sampling state: release exactly like a
        # cancel — the slot was never occupied, so there is nothing
        # else to unwind.
        engine._slots[slot] = None
        engine._release_slot(slot)
        raise


def _place_slot(engine, backend, blob, header, req, rid, slot,
                length, trace) -> int:
    """Device writes + the _finish_prefill host-bookkeeping mirror for
    one validated import (import_blob's guarded tail)."""
    # ---- device writes ----------------------------------------------
    fields = kv_field_names(engine.kv_quant)
    cache = engine._cache
    if backend.is_paged:
        if not backend.ensure_blocks(slot, engine._slot_footprint(req)):
            raise PoolExhausted()
        nb = int(header["n_blocks"])
        blocks = backend._slot_blocks[slot][:nb]
        idx = jnp.asarray(blocks, jnp.int32)
        new = {
            f: getattr(cache, f).at[:, idx].set(
                jnp.asarray(blob.arrays[f])
            )
            for f in fields
        }
    elif backend.is_rolling:
        if int(header.get("ring", -1)) != int(cache.ring):
            raise ValueError(
                f"KV blob ring size {header.get('ring')} does not "
                f"match this engine's ring {int(cache.ring)}"
            )
        new = {
            f: getattr(cache, f).at[:, slot].set(
                jnp.asarray(blob.arrays[f])
            )
            for f in fields
        }
    else:
        new = {
            f: getattr(cache, f).at[:, slot, :, :length].set(
                jnp.asarray(blob.arrays[f])
            )
            for f in fields
        }
    new["lengths"] = cache.lengths.at[slot].set(length)
    engine._cache = cache.replace(**new)

    # ---- host bookkeeping (the _finish_prefill mirror) --------------
    # The slot's carried vectors go through the engine's one writer
    # (host values the next decode window selects in); its table row
    # went through the backend's (ensure_blocks above) and rides the
    # same window.
    n_out = len(req.out)
    engine._patch_slot(
        slot, cur=int(req.out[-1]), rem=max(req.max_new - n_out, 0),
        done=False, min_rem=max(req.min_tokens - n_out, 0),
    )
    engine._set_slot_sampling(slot, req)
    if req.constraint is not None:  # unreachable: submit refuses above
        raise ValueError("constrained requests do not migrate")
    if engine._slot_pen[slot]:
        for t in req.out:
            engine._scounts = engine._scounts.at[slot, int(t)].add(1.0)
    engine._slots[slot] = req
    engine.stats["kv_imports"] += 1
    if trace is not None:
        # Decode-side span marks: queue wait ends at adoption, and the
        # first token already exists (it crossed on the wire) — the
        # importer's TTFT is honest about that.
        trace.prefill_start()
        trace.first_token()
        trace.record("kv-import", src="engine", rid=rid, slot=slot,
                     backend=backend.name, tokens=length,
                     n_out=n_out)
    return slot
