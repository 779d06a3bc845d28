"""Device-trace analysis: `python -m shellac_tpu trace-report`.

`POST /debug/profile` (PR 10) captures a `jax.profiler` trace of the
live engine, but nothing in the repo could READ one — fusion and
step-time questions were still answered by guessing. This module
parses the profiler's Chrome-trace event stream (the
`*.trace.json.gz` every capture contains, host and TPU alike) into:

  op-level time attribution — every complete ('X') event on a device
    process's "XLA Ops" line (a `process_name` containing "/device:";
    its other lines repeat the same time as whole programs and
    asynchronous copies), or — the CPU backend's shape — any event
    whose args carry an `hlo_op`/`hlo_module`, aggregated per op name:
    count, SELF time (the op line nests: a `while` holds its body's
    operations), share.

  phase alignment — each device op is classified against the
    `shellac_step_phase_seconds` phases by the HLO module / op name
    it belongs to (its `hlo_module` argument, or on a TPU capture the
    program on the "XLA Modules" line that was running when it
    started; the engine's jitted programs have recognizable
    names: prefill/chunk programs -> `prefill_dispatch`, decode
    window/beam programs -> `decode_sync`). `admission`,
    `prefill_settle`, `settle`, and `host_bookkeeping` are host-side
    phases with no device ops of their own (the prefill COMPUTE the
    settle waits on is attributed to `prefill_dispatch`, where its
    programs run); their device share is structurally zero and the
    live histogram stays the authority for them — the report says
    where the DEVICE half of each phase goes, which is exactly the
    half the histogram cannot see.

  fusion counts — events and distinct ops named `fusion*` (XLA's
    fused computations): how much of the device time runs fused, and
    how many distinct fusions the compiler emitted. A layout change
    that breaks a fusion apart shows up here as more distinct ops and
    less fused time — the regression class "Operator Fusion in XLA"
    (PAPERS.md) describes.

  by scope — device SELF time per named scope of the model
    (`jax.named_scope` in models/transformer.py and ops/: `attn.qkv`,
    `kv.gather`, `moe.gemm`, ... — DEVICE_SCOPES below), the rest
    under `unscoped`. An operation's scope is the innermost catalogued
    name in its `op_name` path; a fusion is charged to the scope of
    its root instruction. Self time, because the device's op line
    nests (a `while` holds the layer scan's operations). The
    `op_name` rides in the operation's arguments in the
    `.trace.json.gz` (under `tf_op` on a TPU capture); a capture whose
    operations carry none gets a note instead of a guess.

`diff(before, after)` compares two reports and FLAGS regressions —
per-op slowdowns past a threshold, expensive new ops, total device
time growth, fusion breakup — so two committed captures answer "did
this change regress the step" mechanically (the trace-reading half
ROADMAP item 3's TPU re-measure campaign needs). The CLI exits
non-zero when the diff flags anything, so the comparison gates.

Dependency-free (stdlib only): reading a capture must work on any
box, not just an accelerator host.
"""

from __future__ import annotations

import bisect
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from shellac_tpu.obs.trace import STEP_PHASES

#: Module/op name -> step phase (first match wins; matched against
#: the HLO module name first, then the op/event name). The catalog
#: mirrors the engine's jitted-program names in
#: inference/batching.py: `_prefill_impl` and the chunked-prefill
#: programs carry "prefill"/"chunk", the decode window programs carry
#: "decode", beam search carries "beam".
PHASE_RULES: Tuple[Tuple[str, str], ...] = (
    (r"prefill|chunk", "prefill_dispatch"),
    # NOT "window": XLA's reduce-window pooling ops would
    # false-positive into the decode phase.
    (r"decode|beam", "decode_sync"),
)
_PHASE_RES = tuple((re.compile(p, re.I), phase) for p, phase in PHASE_RULES)

#: The named scopes the model wraps its device operations in. Kept
#: here as data so that reading a capture imports no model code;
#: tests/test_step_trace.py checks the list against what the compiled
#: programs carry.
DEVICE_SCOPES = (
    "embed", "norm", "attn.qkv", "attn.rope", "kv.write", "kv.gather",
    "attn.core", "attn.out", "mla.absorb", "mla.latent_write", "mlp",
    "moe.route", "moe.sort", "moe.gemm", "moe.combine", "moe.shared",
    "eva.pool", "eva.summary_write", "eva.attend",
    "dsa.index_proj", "dsa.index_write", "dsa.score", "dsa.select",
    "dsa.attend",
    "loop.norm", "loop.gate", "loop.exit",
    "unembed", "sample",
)
_SCOPE_RE = re.compile(
    r"(?:^|/)(" + "|".join(
        re.escape(n) for n in sorted(DEVICE_SCOPES, key=len, reverse=True)
    ) + r")(?=/|$)"
)

#: XLA fusion op names: `fusion`, `fusion.123`, `%fusion.4`, plus the
#:  kind-tagged `loop_fusion`/`input_fusion` variants.
_FUSION_RE = re.compile(r"^%?(?:[a-z]+_)?fusion(?:[._]\d+)?$", re.I)

#: Op-name normalization: strip the leading '%' and any SSA suffix so
#: `%add.12` and `add.7` aggregate as one op family.
_OP_NORM_RE = re.compile(r"^%?(.*?)(?:\.\d+)?$")


def _norm_op(name: str) -> str:
    m = _OP_NORM_RE.match(name)
    return m.group(1) if m and m.group(1) else name


def classify_phase(module: Optional[str], name: str) -> Optional[str]:
    """Phase for one device op, or None (unattributed) when neither
    the module nor the op name matches the catalog."""
    for rx, phase in _PHASE_RES:
        if module and rx.search(module):
            return phase
        if rx.search(name):
            return phase
    return None


# ---- loading ---------------------------------------------------------


def find_trace_file(path: str) -> str:
    """Resolve a capture argument to one trace file. Accepts the
    `.trace.json.gz` (or plain .json) file itself, or a capture
    directory — the `trace_dir` a /debug/profile response names —
    searched recursively for the newest `*.trace.json(.gz)`."""
    if os.path.isfile(path):
        return path
    if os.path.isdir(path):
        hits: List[str] = []
        for root, _, files in os.walk(path):
            for f in files:
                if f.endswith((".trace.json.gz", ".trace.json")):
                    hits.append(os.path.join(root, f))
        if not hits:
            raise FileNotFoundError(
                f"no *.trace.json(.gz) under {path!r} — is this a "
                "jax.profiler capture directory?"
            )
        return max(hits, key=os.path.getmtime)
    raise FileNotFoundError(f"no such capture: {path!r}")


def load_trace(path: str) -> Dict[str, Any]:
    """The parsed Chrome-trace JSON object of one capture."""
    f = find_trace_file(path)
    opener = gzip.open if f.endswith(".gz") else open
    with opener(f, "rb") as fh:
        data = json.loads(fh.read().decode("utf-8", errors="replace"))
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise ValueError(
            f"{f!r} is not a Chrome-trace capture (no traceEvents)"
        )
    data["_trace_file"] = f
    return data


# ---- by scope --------------------------------------------------------


def scope_of(args: Dict[str, Any]) -> Optional[str]:
    """The innermost catalogued scope in any string argument of one
    op event (its `op_name` path, under whichever key the backend
    writes it), or None."""
    for v in args.values():
        if isinstance(v, str) and "/" in v:
            found = _SCOPE_RE.findall(v)
            if found:
                return found[-1]
    return None


def _self_us(events: List[Dict[str, Any]]) -> List[float]:
    """Self time of each event (same order as given): its duration
    minus what the events nested inside it on its own thread cover."""
    out = [float(e.get("dur") or 0.0) for e in events]
    by_thread: Dict[Any, List[int]] = {}
    for i, e in enumerate(events):
        by_thread.setdefault((e.get("pid"), e.get("tid")), []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (float(events[i].get("ts") or 0.0), -out[i]))
        stack: List[Tuple[int, float]] = []  # (event, end)
        for i in idx:
            ts = float(events[i].get("ts") or 0.0)
            dur = float(events[i].get("dur") or 0.0)
            while stack and stack[-1][1] <= ts:
                stack.pop()
            if stack:
                out[stack[-1][0]] -= min(dur, stack[-1][1] - ts)
            stack.append((i, ts + dur))
    return out


def by_scope(op_events: List[Dict[str, Any]],
             selfs: List[float]) -> Dict[str, Any]:
    """Device self time per named scope, from the operations'
    `op_name` in their trace-event arguments."""
    rows: Dict[str, Dict[str, float]] = {}
    loose: Dict[str, float] = {}
    total = 0.0
    for e, us in zip(op_events, selfs):
        args = e.get("args") if isinstance(e.get("args"), dict) else {}
        scope = scope_of(args)
        if scope is None:
            op = str(e["name"]).lstrip("%")
            loose[op] = loose.get(op, 0.0) + us
        row = rows.setdefault(scope or "unscoped",
                              {"self_us": 0.0, "ops": 0})
        row["self_us"] += us
        row["ops"] += 1
        total += us
    for row in rows.values():
        row["share"] = round(row["self_us"] / total, 4) if total else 0.0
        row["self_us"] = round(row["self_us"], 3)
    unscoped = rows.pop("unscoped", {"self_us": 0.0, "ops": 0, "share": 0.0})
    # What XLA put around the model's own operations, largest first.
    unscoped["top"] = [
        [op, round(us, 3)] for op, us in
        sorted(loose.items(), key=lambda kv: -kv[1])[:8]
    ]
    table = {
        "device_self_us": round(total, 3),
        "scopes": dict(sorted(rows.items(),
                              key=lambda kv: -kv[1]["self_us"])),
        "unscoped": unscoped,
    }
    if not rows:
        table["note"] = ("no operation in this capture carries a scoped "
                         "op_name (a program built before the scopes, or a "
                         "backend that writes none)")
    return table


# ---- analysis --------------------------------------------------------


def _process_names(events: Iterable[Dict[str, Any]]) -> Dict[Any, str]:
    out: Dict[Any, str] = {}
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "process_name"
                and isinstance(e.get("args"), dict)):
            out[e.get("pid")] = str(e["args"].get("name", ""))
    return out


def _thread_names(events: Iterable[Dict[str, Any]]) -> Dict[Any, str]:
    out: Dict[Any, str] = {}
    for e in events:
        if (e.get("ph") == "M" and e.get("name") == "thread_name"
                and isinstance(e.get("args"), dict)):
            out[(e.get("pid"), e.get("tid"))] = str(
                e["args"].get("name", ""))
    return out


#: The device process's threads: a TPU capture has one line of
#: operations beside lines that repeat the same time (whole programs,
#: asynchronous copies, overlays). Only the first is summed.
_OPS_THREAD, _MODULES_THREAD = "XLA Ops", "XLA Modules"


def _is_op_event(e: Dict[str, Any], device_pids, threads=None) -> bool:
    if e.get("ph") != "X" or not e.get("name"):
        return False
    if e.get("pid") in device_pids:
        name = (threads or {}).get((e.get("pid"), e.get("tid")))
        return name is None or name == _OPS_THREAD
    args = e.get("args")
    # CPU-backend captures put the op stream on the host process but
    # tag each op event with its HLO identity.
    return isinstance(args, dict) and (
        "hlo_op" in args or "hlo_module" in args
    )


def analyze(path: str, *, top: int = 20) -> Dict[str, Any]:
    """One capture -> the trace-report dict (the `--json` payload, the
    bundle's trace_report.json, and diff()'s input)."""
    data = load_trace(path)
    events = data.get("traceEvents") or []
    procs = _process_names(events)
    device_pids = {pid for pid, name in procs.items()
                   if "/device:" in name}
    threads = _thread_names(events)
    # A TPU capture names the program on its own line, not on each
    # operation: an operation belongs to the program running at its
    # start.
    programs: Dict[Any, List[Tuple[float, float, str]]] = {}
    for e in events:
        if (e.get("ph") == "X" and e.get("pid") in device_pids
                and threads.get((e.get("pid"), e.get("tid")))
                == _MODULES_THREAD):
            ts = float(e.get("ts") or 0.0)
            programs.setdefault(e.get("pid"), []).append(
                (ts, ts + float(e.get("dur") or 0.0), str(e["name"])))
    starts = {pid: [p[0] for p in sorted(rows)]
              for pid, rows in programs.items()}
    for rows in programs.values():
        rows.sort()

    def program_at(pid, ts) -> Optional[str]:
        rows = programs.get(pid)
        if not rows:
            return None
        i = bisect.bisect_right(starts[pid], ts) - 1
        if i >= 0 and ts < rows[i][1]:
            return re.sub(r"\(\d+\)$", "", rows[i][2])
        return None

    ops: Dict[str, Dict[str, Any]] = {}
    modules: Dict[str, float] = {}
    # Phase attribution over the device ops (host-only phases report
    # zero device time by construction — see module docstring).
    # Accumulated PER EVENT: the same op name may run under a prefill
    # module in one event and a decode module in the next, and
    # distinct fusions (fusion.1, fusion.2) normalize to one op row
    # but must count as distinct fusions.
    phases: Dict[str, Dict[str, float]] = {
        p: {"device_us": 0.0, "ops": 0} for p in STEP_PHASES
    }
    unattributed: Dict[str, float] = {"device_us": 0.0, "ops": 0}
    fus_raw: set = set()
    fus_events = 0
    fus_us = 0.0
    total_us = 0.0
    n_events = 0
    op_events = [e for e in events
                 if _is_op_event(e, device_pids, threads)]
    # Self time throughout: the device's op line nests (a `while`
    # holds its body's operations), and a sum of durations would count
    # the body twice.
    selfs = _self_us(op_events)
    for e, dur in zip(op_events, selfs):
        args = e.get("args") if isinstance(e.get("args"), dict) else {}
        raw = str(e["name"])
        module = str(args["hlo_module"]) if args.get("hlo_module") \
            else program_at(e.get("pid"), float(e.get("ts") or 0.0))
        op = _norm_op(str(args.get("hlo_op") or raw))
        n_events += 1
        total_us += dur
        if module:
            modules[module] = modules.get(module, 0.0) + dur
        ph = classify_phase(module, op)
        tgt = phases[ph] if ph else unattributed
        tgt["device_us"] += dur
        tgt["ops"] += 1
        if _FUSION_RE.match(raw) or _FUSION_RE.match(op):
            fus_raw.add(raw)
            fus_events += 1
            fus_us += dur
        row = ops.get(op)
        if row is None:
            row = ops[op] = {"name": op, "count": 0, "total_us": 0.0,
                             "phase": ph}
        row["count"] += 1
        row["total_us"] += dur
    fus_distinct = len(fus_raw)
    for p in phases.values():
        p["share"] = round(p["device_us"] / total_us, 4) if total_us else 0.0
        p["device_us"] = round(p["device_us"], 3)
    unattributed["share"] = (round(unattributed["device_us"] / total_us, 4)
                             if total_us else 0.0)
    unattributed["device_us"] = round(unattributed["device_us"], 3)
    ranked = sorted(ops.values(), key=lambda r: -r["total_us"])
    top_ops = [
        {
            "name": r["name"], "count": r["count"],
            "total_us": round(r["total_us"], 3),
            "avg_us": round(r["total_us"] / r["count"], 3),
            "share": (round(r["total_us"] / total_us, 4)
                      if total_us else 0.0),
            "phase": r["phase"],
        }
        for r in ranked[: max(0, int(top))]
    ]
    return {
        "capture": data.get("_trace_file"),
        "op_events": n_events,
        "distinct_ops": len(ops),
        "device_time_us": round(total_us, 3),
        "top_ops": top_ops,
        # The full per-op table rides along for diff(): same row shape
        # as top_ops, unranked callers can rank themselves.
        "ops": {r["name"]: {"count": r["count"],
                            "total_us": round(r["total_us"], 3),
                            "phase": r["phase"]}
                for r in ranked},
        "modules": {k: round(v, 3) for k, v in sorted(
            modules.items(), key=lambda kv: -kv[1])},
        "fusion": {
            "distinct": fus_distinct,
            "events": int(fus_events),
            "total_us": round(fus_us, 3),
            "share": round(fus_us / total_us, 4) if total_us else 0.0,
        },
        "phases": phases,
        "unattributed": unattributed,
        "by_scope": by_scope(op_events, selfs),
    }


# ---- diff ------------------------------------------------------------


def diff(before: Dict[str, Any], after: Dict[str, Any], *,
         threshold: float = 0.15, min_us: float = 50.0,
         phase_shift_points: float = 0.15) -> Dict[str, Any]:
    """Compare two reports; flag regressions in `after` relative to
    `before`. A regression is flagged when it is BOTH relatively
    (`threshold`, default +15%) and absolutely (`min_us`) significant
    — a 3µs op doubling is noise, not a finding. `phase_shift_points`
    is a separate, ABSOLUTE knob (share points a phase's device share
    may grow): shares live on a 0..1 scale, so reusing the relative
    `threshold` would silently retune this check whenever the op
    knob moved. Identical captures produce zero flags by
    construction."""
    regressions: List[Dict[str, Any]] = []
    b_ops = before.get("ops") or {}
    a_ops = after.get("ops") or {}
    for name, a in a_ops.items():
        b = b_ops.get(name)
        if b is None:
            if a["total_us"] >= min_us:
                regressions.append({
                    "kind": "new_op", "name": name,
                    "after_us": a["total_us"],
                    "note": "op absent from the baseline capture",
                })
            continue
        if (a["total_us"] > b["total_us"] * (1.0 + threshold)
                and a["total_us"] - b["total_us"] >= min_us):
            regressions.append({
                "kind": "op_regression", "name": name,
                "before_us": b["total_us"], "after_us": a["total_us"],
                "ratio": round(a["total_us"] / max(b["total_us"], 1e-9),
                               3),
            })
    b_tot = float(before.get("device_time_us") or 0.0)
    a_tot = float(after.get("device_time_us") or 0.0)
    if a_tot > b_tot * (1.0 + threshold) and a_tot - b_tot >= min_us:
        regressions.append({
            "kind": "device_time_regression",
            "before_us": b_tot, "after_us": a_tot,
            "ratio": round(a_tot / max(b_tot, 1e-9), 3),
        })
    b_fus = before.get("fusion") or {}
    a_fus = after.get("fusion") or {}
    # Fusion breakup: the same workload executing MORE distinct ops
    # while the fused share of device time fell — the compiler split
    # work fusions used to cover.
    if (int(after.get("distinct_ops") or 0)
            > int(before.get("distinct_ops") or 0) * (1.0 + threshold)
            and float(a_fus.get("share") or 0.0)
            < float(b_fus.get("share") or 0.0)):
        regressions.append({
            "kind": "fusion_breakup",
            "before_distinct_ops": before.get("distinct_ops"),
            "after_distinct_ops": after.get("distinct_ops"),
            "before_fused_share": b_fus.get("share"),
            "after_fused_share": a_fus.get("share"),
        })
    # Phase shift: a phase's device share growing past the absolute
    # share-point knob — e.g. prefill programs eating into the decode
    # window's device time.
    for phase in STEP_PHASES:
        b_share = float(((before.get("phases") or {}).get(phase)
                         or {}).get("share") or 0.0)
        a_share = float(((after.get("phases") or {}).get(phase)
                         or {}).get("share") or 0.0)
        if a_share - b_share > phase_shift_points:
            regressions.append({
                "kind": "phase_shift", "phase": phase,
                "before_share": b_share, "after_share": a_share,
            })
    return {
        "ok": not regressions,
        "threshold": threshold,
        "min_us": min_us,
        "phase_shift_points": phase_shift_points,
        "before": before.get("capture"),
        "after": after.get("capture"),
        "regressions": regressions,
    }


# ---- rendering -------------------------------------------------------


def render_report(report: Dict[str, Any]) -> str:
    """Human text for the CLI (the --json flag prints the dict)."""
    out: List[str] = []
    out.append(f"capture: {report.get('capture')}")
    out.append(
        f"device time: {report.get('device_time_us', 0) / 1e3:.3f} ms "
        f"over {report.get('op_events')} op events "
        f"({report.get('distinct_ops')} distinct ops)"
    )
    fus = report.get("fusion") or {}
    out.append(
        f"fusion: {fus.get('distinct', 0)} distinct / "
        f"{fus.get('events', 0)} events / "
        f"{100 * (fus.get('share') or 0):.1f}% of device time"
    )
    out.append("")
    out.append("phase alignment (device half of shellac_step_phase_seconds)")
    for phase in STEP_PHASES:
        p = (report.get("phases") or {}).get(phase) or {}
        out.append(
            f"  {phase:<18} {p.get('device_us', 0) / 1e3:10.3f} ms"
            f"  {100 * (p.get('share') or 0):5.1f}%"
            f"  ({p.get('ops', 0)} ops)"
        )
    un = report.get("unattributed") or {}
    out.append(
        f"  {'(unattributed)':<18} {un.get('device_us', 0) / 1e3:10.3f} ms"
        f"  {100 * (un.get('share') or 0):5.1f}%"
        f"  ({un.get('ops', 0)} ops)"
    )
    bs = report.get("by_scope") or {}
    out.append("")
    out.append(
        "by scope (device self time, "
        f"{bs.get('device_self_us', 0) / 1e3:.3f} ms)"
    )
    if bs.get("note"):
        out.append(f"  {bs['note']}")
    rows = list((bs.get("scopes") or {}).items())
    rows.append(("(unscoped)", bs.get("unscoped") or {}))
    for name, r in rows:
        out.append(
            f"  {name:<18} {r.get('self_us', 0) / 1e3:10.3f} ms"
            f"  {100 * (r.get('share') or 0):5.1f}%"
            f"  ({r.get('ops', 0)} ops)"
        )
    for op, us in (bs.get("unscoped") or {}).get("top") or []:
        out.append(f"      unscoped: {op[:40]:<40} {us / 1e3:10.3f} ms")
    out.append("")
    out.append(f"{'top ops':<28}{'count':>7}{'total ms':>11}"
               f"{'share':>8}  phase")
    for r in report.get("top_ops") or []:
        out.append(
            f"{r['name'][:27]:<28}{r['count']:>7}"
            f"{r['total_us'] / 1e3:>11.3f}"
            f"{100 * r['share']:>7.1f}%  {r['phase'] or '-'}"
        )
    return "\n".join(out) + "\n"


def render_diff(result: Dict[str, Any]) -> str:
    out = [
        f"before: {result.get('before')}",
        f"after:  {result.get('after')}",
    ]
    regs = result.get("regressions") or []
    if not regs:
        out.append("no regressions flagged "
                   f"(threshold {100 * result['threshold']:.0f}%, "
                   f"min {result['min_us']:g}us)")
        return "\n".join(out) + "\n"
    out.append(f"{len(regs)} regression(s) flagged:")
    for r in regs:
        kind = r.get("kind")
        if kind == "op_regression":
            out.append(
                f"  op {r['name']}: {r['before_us'] / 1e3:.3f} -> "
                f"{r['after_us'] / 1e3:.3f} ms ({r['ratio']:.2f}x)"
            )
        elif kind == "new_op":
            out.append(
                f"  new op {r['name']}: {r['after_us'] / 1e3:.3f} ms "
                "(absent from baseline)"
            )
        elif kind == "device_time_regression":
            out.append(
                f"  device time: {r['before_us'] / 1e3:.3f} -> "
                f"{r['after_us'] / 1e3:.3f} ms ({r['ratio']:.2f}x)"
            )
        elif kind == "fusion_breakup":
            out.append(
                f"  fusion breakup: {r['before_distinct_ops']} -> "
                f"{r['after_distinct_ops']} distinct ops, fused share "
                f"{r['before_fused_share']} -> {r['after_fused_share']}"
            )
        elif kind == "phase_shift":
            out.append(
                f"  phase {r['phase']}: device share "
                f"{r['before_share']} -> {r['after_share']}"
            )
        else:
            out.append(f"  {r}")
    return "\n".join(out) + "\n"
