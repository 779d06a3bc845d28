"""Regenerate the committed trace-report fixture captures.

Two small, deterministic Chrome-trace captures shaped like a TPU
`jax.profiler` dump (a `/device:TPU:0` process with an "XLA Ops"
thread, op events carrying `hlo_module` args, a host process with
python-function events):

  decode_base.trace.json.gz       the healthy baseline: the decode
      window's time runs mostly inside one big fusion, prefill is a
      small share, a little unattributed copy traffic.
  decode_regressed.trace.json.gz  the same workload with an INJECTED
      regression: the decode fusion broken apart into add/multiply/
      reduce (more distinct ops, less fused time), the dot 40%
      slower, and a new convert op — the three regression classes
      `trace-report --diff` exists to flag.

  decode_scoped.trace.json.gz     one decode window whose operations
      carry scoped `op_name`s, nested inside a `while` as the device's
      op line nests them: what `trace-report`'s by-scope section reads
      (self time per named scope; the `while` shell's own time and a
      module-less copy stay unscoped).

Run `python tests/fixtures/make_trace_fixtures.py` to rewrite the
files (gzip mtime pinned to 0; the test suite compares the
decompressed payloads, since the gzip header's OS byte differs between
Python builds); the suite asserts the diff flags the regressed capture
and passes the base against itself.
"""

import gzip
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

_DEVICE_PID = 1
_HOST_PID = 9


def _meta():
    return [
        {"ph": "M", "pid": _DEVICE_PID, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": _DEVICE_PID, "tid": 1,
         "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": _HOST_PID, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": _HOST_PID, "tid": 1, "name": "thread_name",
         "args": {"name": "python3"}},
    ]


def _ops(rows):
    """rows: (name, module, count, dur_us) -> laid-out X events."""
    events = []
    ts = 1000.0
    for name, module, count, dur in rows:
        for _ in range(count):
            ev = {"ph": "X", "pid": _DEVICE_PID, "tid": 1,
                  "ts": round(ts, 1), "dur": float(dur), "name": name}
            if module:
                ev["args"] = {"hlo_module": module}
            events.append(ev)
            ts += dur + 1.0
    return events


def _host_events():
    return [
        {"ph": "X", "pid": _HOST_PID, "tid": 1, "ts": 900.0,
         "dur": 50000.0, "name": "$batching.py:1596 step"},
        {"ph": "X", "pid": _HOST_PID, "tid": 1, "ts": 950.0,
         "dur": 400.0, "name": "$batching.py:1269 _fill_slots"},
    ]


BASE_OPS = [
    # The decode window: one dominant fusion + matmul + cache write.
    ("%fusion.1", "jit__decode_impl", 40, 100.0),
    ("%dot.3", "jit__decode_impl", 40, 50.0),
    ("%dynamic-update-slice.4", "jit__decode_impl", 40, 10.0),
    # Prefill programs: their own fusion + matmul.
    ("%fusion.2", "jit__prefill_impl", 4, 300.0),
    ("%dot.5", "jit__prefill_impl", 4, 100.0),
    # Unattributed device traffic (no module tag).
    ("%copy.6", None, 10, 20.0),
]

REGRESSED_OPS = [
    # INJECTED: the decode fusion broke apart (three distinct ops,
    # slower in aggregate than the fusion they replace)...
    ("%add.7", "jit__decode_impl", 40, 60.0),
    ("%multiply.8", "jit__decode_impl", 40, 50.0),
    ("%reduce.9", "jit__decode_impl", 40, 40.0),
    # ... the dot regressed 40% ...
    ("%dot.3", "jit__decode_impl", 40, 70.0),
    ("%dynamic-update-slice.4", "jit__decode_impl", 40, 10.0),
    ("%fusion.2", "jit__prefill_impl", 4, 300.0),
    ("%dot.5", "jit__prefill_impl", 4, 100.0),
    ("%copy.6", None, 10, 20.0),
    # ... and a new op appeared.
    ("%convert.11", "jit__decode_impl", 5, 30.0),
]


_SCAN = "jit(_decode_impl)/jit(main)/while/body/closed_call/"

# (name, ts, dur, op_name): a while shell of 1000 us holding three
# scoped fusions (900 us: its own 100 us stay unscoped), then a copy
# with no op_name, then a norm inside attn.qkv (the innermost wins).
SCOPED_OPS = [
    ("%while.1", 1000.0, 1000.0, "jit(_decode_impl)/jit(main)/while"),
    ("%fusion.10", 1000.0, 300.0, _SCAN + "attn.qkv/dot_general"),
    ("%fusion.11", 1300.0, 200.0, _SCAN + "kv.gather/gather"),
    ("%fusion.12", 1500.0, 400.0, _SCAN + "attn.core/reduce_max"),
    ("%copy.1", 2100.0, 150.0, None),
    ("%fusion.13", 2300.0, 50.0, _SCAN + "attn.qkv/norm/mul"),
]


def _scoped_ops():
    events = []
    for name, ts, dur, op_name in SCOPED_OPS:
        ev = {"ph": "X", "pid": _DEVICE_PID, "tid": 1, "ts": ts,
              "dur": dur, "name": name}
        if op_name:
            ev["args"] = {"hlo_module": "jit__decode_impl",
                          "op_name": op_name}
        events.append(ev)
    return events


def _write(name, rows):
    doc = {
        "displayTimeUnit": "ns",
        "metadata": {"highres-ticks": True},
        "traceEvents": _meta() + _host_events()
        + (rows if isinstance(rows[0], dict) else _ops(rows)),
    }
    data = json.dumps(doc, sort_keys=True).encode()
    path = os.path.join(HERE, name)
    # mtime=0 keeps the gzip byte-stable across regenerations.
    with open(path, "wb") as f:
        f.write(gzip.compress(data, mtime=0))
    print(f"wrote {path} ({os.path.getsize(path)} bytes)")


def main():
    _write("decode_base.trace.json.gz", BASE_OPS)
    _write("decode_regressed.trace.json.gz", REGRESSED_OPS)
    _write("decode_scoped.trace.json.gz", _scoped_ops())


if __name__ == "__main__":
    main()
