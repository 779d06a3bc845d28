#!/usr/bin/env python3
"""Proof that shellac_tpu starts on the chip: shellac-1b, full width,
through the entry points a user calls.

    python chip_smoke.py            # one TPU v5e chip
    python chip_smoke.py --chips4   # one host with four (builder-run)

One chip, phases run one after another, each in its own process:

  kernels  compiled Pallas parity (scripts/tpu_parity_decode.py) and a
           compile of shellac-1b's train step and the engine's prefill
           and decode programs, checked for tpu_custom_call
  train    python -m shellac_tpu train --model shellac-1b, batch 6 x
           seq 2048, a few steps on the CLI's synthetic corpus
  serve    python -m shellac_tpu serve --model shellac-1b with its
           defaults, then --cache-backend paged-int8, then
           --cache-backend paged --prefix-cache; a few concurrent
           greedy requests each

--chips4 runs only the paths that exist across chips and what they are
compared with: train --mesh fsdp=4 against a one-device run, and serve
--mesh tp=4 against the unsharded server.

The last line of stdout is one JSON object,
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
with the device as the children's jax reported it. Any failed phase, or
any process that saw a platform other than tpu, makes it "ok": false
and a non-zero exit. Without a TPU nothing runs.

A chip belongs to one process at a time, so this parent never imports
jax (nor shellac_tpu, which does): every phase is a child that ends
before the next starts and reports the device it saw.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import subprocess
import sys
import threading
import time
import traceback
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chiprun_out", "chip_smoke")
# The contract allows 1200 s, compilation included; leave a margin for
# interpreter exits and the final line.
BUDGET_S = 1140.0
MODEL = "shellac-1b"
SERVE_MAX_NEW = 16
# stderr of a serve child must hold neither: they mean a decode kernel
# was silently replaced by the reference path.
FALLBACK_WARNINGS = ("PagedFallbackWarning", "QuantFallbackWarning")
MESH_FALLBACK = "make_mesh: create_device_mesh refused"
# --chips4 tolerances, fixed before the first chip run. bf16 compute
# with a different reduction order per layout: losses (~10 falling to
# ~6) within 1 %, prompt logprobs within 0.15 nat anywhere and 0.03 on
# average.
LOSS_RTOL = 0.01
LOGPROB_MAX_ABS = 0.15
LOGPROB_MEAN_ABS = 0.03


class PhaseFailed(Exception):
    pass


def say(**fields):
    """One JSON line of evidence on stdout (never the final line)."""
    print(json.dumps(fields), flush=True)


class Run:
    """Shared state of one smoke run: the clock, the child environment,
    the devices children reported."""

    def __init__(self, args):
        self.t0 = time.monotonic()
        self.rehearse = args.rehearse
        self.seed = args.seed
        self.model = "tiny" if args.rehearse else MODEL
        self.devices = []  # every device report a child made
        self.cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                          or os.path.join(HERE, ".jax_cache"))
        self.env = dict(
            os.environ, PYTHONPATH=HERE, PYTHONUNBUFFERED="1",
            # compile and persistent-cache-hit lines on the children's
            # stderr, counted per phase
            JAX_LOG_COMPILES="1",
            # cache every program, not only the slow ones: a phase
            # makes hundreds of sub-second compiles
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
        )
        os.makedirs(OUT, exist_ok=True)

    def remaining(self) -> float:
        return BUDGET_S - (time.monotonic() - self.t0)

    def cache_entries(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.cache_dir)
                       if not n.endswith("-atime"))
        except OSError:
            return 0

    def saw(self, device, who):
        if not isinstance(device, dict) or "platform" not in device:
            raise PhaseFailed(f"{who} reported no device: {device!r}")
        self.devices.append(device)
        if device["platform"] != "tpu" and not self.rehearse:
            raise PhaseFailed(f"{who} ran on {device}, not on a tpu")

    def child(self, name, cmd, *, cap_s):
        """Run one child to its end; returns (stdout lines, stderr
        text). Its stderr is kept under chiprun_out/ for the builder."""
        timeout = min(cap_s, self.remaining())
        if timeout <= 5:
            raise PhaseFailed(f"{name}: out of time before it started")
        err_path = os.path.join(OUT, f"{name}.stderr")
        with open(err_path, "w") as err:
            try:
                r = subprocess.run(cmd, cwd=HERE, env=self.env, text=True,
                                   stdout=subprocess.PIPE, stderr=err,
                                   timeout=timeout)
            except subprocess.TimeoutExpired:
                raise PhaseFailed(f"{name}: no end after {timeout:.0f} s")
        with open(err_path) as f:
            stderr = f.read()
        if r.returncode != 0:
            raise PhaseFailed(
                f"{name}: exit code {r.returncode}; stderr ends:\n"
                + stderr[-3000:]
            )
        return r.stdout.strip().splitlines(), stderr


def compile_counts(stderr: str) -> dict:
    """What JAX_LOG_COMPILES=1 left on a child's stderr."""
    return {
        "compiles": stderr.count("Finished XLA compilation of"),
        "cache_hits": stderr.count("Persistent compilation cache hit"),
    }


def last_json(lines, key, who):
    for line in reversed(lines):
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and key in doc:
            return doc
    raise PhaseFailed(f"{who}: no JSON line with {key!r} on stdout")


# ---------------------------------------------------------------------------
# phases (parent side)
# ---------------------------------------------------------------------------


def phase_probe(run: Run):
    lines, _ = run.child(
        "probe", [sys.executable, __file__, "--child", "probe"], cap_s=120
    )
    return last_json(lines, "platform", "probe")


def phase_kernels(run: Run):
    cmd = [sys.executable, __file__, "--child", "kernels",
           "--seed", str(run.seed)]
    if run.rehearse:
        cmd.append("--rehearse")
    lines, stderr = run.child("kernels", cmd, cap_s=600)
    for line in lines[:-1]:
        print(line, flush=True)
    doc = last_json(lines, "kernels_ok", "kernels")
    run.saw(doc.get("device"), "kernels")
    if doc.get("cache_dir") != run.cache_dir:
        raise PhaseFailed(
            f"kernels child cached in {doc.get('cache_dir')!r}, "
            f"expected {run.cache_dir!r}"
        )
    if not doc["kernels_ok"]:
        raise PhaseFailed(f"kernels: {doc}")
    return {**doc, **compile_counts(stderr)}


def read_train_log(path):
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if "loss" in r]


def run_train(run: Run, name, *, batch, seq, steps, mesh=None):
    """One `train` child; returns its per-step losses, step times,
    final line and compile counts."""
    log_path = os.path.join(OUT, f"{name}.jsonl")
    if os.path.exists(log_path):
        os.remove(log_path)
    cmd = [sys.executable, "-m", "shellac_tpu", "train",
           "--model", run.model, "--batch", str(batch), "--seq", str(seq),
           "--steps", str(steps), "--seed", str(run.seed),
           "--log-every", "1", "--log-path", log_path,
           # A schedule on which a handful of steps visibly learn.
           "--warmup-steps", "2", "--learning-rate", "3e-4"]
    if mesh:
        cmd += ["--mesh", mesh]
    t0 = time.monotonic()
    lines, stderr = run.child(name, cmd, cap_s=700)
    wall = time.monotonic() - t0
    final = last_json(lines, "final_step", name)
    run.saw(final.get("device"), name)
    rows = read_train_log(log_path)
    losses = [r["loss"] for r in rows]
    times = [b["time"] - a["time"] for a, b in zip(rows, rows[1:])]
    if final["final_step"] != steps or len(losses) != steps:
        raise PhaseFailed(f"{name}: {final} with {len(losses)} logged steps")
    if not all(isinstance(x, float) and x == x and abs(x) != float("inf")
               for x in losses):
        raise PhaseFailed(f"{name}: non-finite loss in {losses}")
    return {"losses": losses, "step_s": times, "wall_s": round(wall, 1),
            "final": final, "mesh_fallback": MESH_FALLBACK in stderr,
            **compile_counts(stderr)}


def phase_train(run: Run):
    batch, seq, steps = (2, 64, 6) if run.rehearse else (6, 2048, 6)
    r = run_train(run, "train", batch=batch, seq=seq, steps=steps)
    losses, step_s = r["losses"], r["step_s"]
    if not losses[-1] < losses[0]:
        raise PhaseFailed(f"train: loss did not fall: {losses}")
    # step_s[i] is the gap between the logs of steps i+1 and i+2; the
    # compile sits before the first log. A second compile would show
    # as one gap many times the others.
    if not run.rehearse and max(step_s) > 3.0 * min(step_s):
        raise PhaseFailed(
            f"train: uneven steps after the first (a recompile?): {step_s}"
        )
    steady = sorted(step_s)[len(step_s) // 2]
    memory = r["final"].get("memory")
    return {
        "batch": batch, "seq": seq, "losses": [round(x, 4) for x in losses],
        "step_s_after_first": [round(x, 3) for x in step_s],
        "startup_compile_first_step_s": round(
            r["wall_s"] - sum(step_s), 1),
        "median_step_s": round(steady, 3),
        "peak_bytes_in_use": [m["peak_bytes_in_use"] for m in memory],
        "wall_s": r["wall_s"], "compiles": r["compiles"],
        "cache_hits": r["cache_hits"],
    }


class Server:
    """One `serve` child: started, waited for, queried, stopped."""

    def __init__(self, run: Run, name, extra):
        self.run, self.name = run, name
        self.err_path = os.path.join(OUT, f"{name}.stderr")
        cmd = [sys.executable, "-m", "shellac_tpu", "serve",
               "--model", run.model, "--port", "0",
               "--seed", str(run.seed)] + list(extra)
        self.cmd = cmd
        self.t0 = time.monotonic()
        self._err = open(self.err_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=HERE, env=run.env, text=True,
            stdout=subprocess.PIPE, stderr=self._err,
        )
        self.url = None
        self.startup_s = None
        self.memory = None

    def wait_ready(self, cap_s):
        """Block until the {"serving": ...} line; stdout is read on a
        side thread so a child that prints nothing hits the cap."""
        box = {}

        def read():
            for line in self.proc.stdout:
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
                if isinstance(doc, dict) and "serving" in doc:
                    box["doc"] = doc
                    return

        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(timeout=min(cap_s, self.run.remaining()))
        doc = box.get("doc")
        if doc is None:
            raise PhaseFailed(
                f"{self.name}: no serving line "
                f"(exit code {self.proc.poll()}); stderr ends:\n"
                + self.stderr()[-3000:]
            )
        self.startup_s = time.monotonic() - self.t0
        self.url = doc["serving"]
        self.memory = doc.get("memory")
        self.run.saw(doc.get("device"), self.name)

    def stderr(self):
        if not self._err.closed:
            self._err.flush()
        with open(self.err_path) as f:
            return f.read()

    def post(self, path, payload, timeout=300):
        req = urllib.request.Request(
            self.url + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read().decode()

    def get(self, path):
        with urllib.request.urlopen(self.url + path, timeout=60) as r:
            return json.loads(r.read().decode())

    def stop(self):
        """SIGINT is the clean stop of a blocking serve: exit code 0."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                pass
        self.kill()
        return self.proc.returncode

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30)
        self._err.close()


def make_prompts(run: Run, vocab):
    """Mixed prompt lengths, two of them >= 1k tokens so a real prefill
    bucket compiles; three buckets in all (16, 64, 2048), because every
    bucket is a compile of its own. Made from --seed."""
    rng = random.Random(run.seed)
    lens = (5, 40, 24, 30) if run.rehearse else (12, 1100, 1500, 40)
    return [[rng.randrange(1, vocab) for _ in range(n)] for n in lens]


def drive_requests(srv: Server, prompts, *, logprobs=False):
    """The same few concurrent greedy requests for every server: three
    /generate (one streamed) and one /v1/chat/completions. Returns
    {name: tokens or info}; raises on any non-200 or short answer."""
    out, errors = {}, []
    extra = {"logprobs": True, "prompt_logprobs": True} if logprobs else {}

    def generate(i, stream=False):
        body = {"tokens": prompts[i], "max_new": SERVE_MAX_NEW,
                "temperature": 0.0, **extra}
        if stream:
            body["stream"] = True
        status, text = srv.post("/generate", body)
        if status != 200:
            raise PhaseFailed(f"/generate #{i}: HTTP {status}")
        if stream:
            records = [json.loads(l) for l in text.splitlines() if l.strip()]
            if not records or not records[-1].get("done"):
                raise PhaseFailed(f"stream #{i}: no done record")
            doc = records[-1]
            doc["stream_records"] = len(records)
        else:
            doc = json.loads(text)
        if len(doc.get("tokens", ())) != SERVE_MAX_NEW:
            raise PhaseFailed(
                f"/generate #{i}: {len(doc.get('tokens', ()))} tokens, "
                f"wanted {SERVE_MAX_NEW}"
            )
        out[f"generate{i}" + ("_stream" if stream else "")] = doc

    def chat():
        status, text = srv.post("/v1/chat/completions", {
            "messages": [{"role": "user",
                          "content": "Say something about varnish."}],
            "max_tokens": SERVE_MAX_NEW, "temperature": 0,
        })
        doc = json.loads(text)
        if status != 200 or not doc.get("choices"):
            raise PhaseFailed(f"chat: HTTP {status} {text[:200]}")
        n = doc.get("usage", {}).get("completion_tokens")
        if n != SERVE_MAX_NEW:
            raise PhaseFailed(f"chat: completion_tokens={n}")
        out["chat"] = doc

    def guard(fn, *a, **k):
        try:
            fn(*a, **k)
        except Exception as e:  # noqa: BLE001 — collected, re-raised below
            errors.append(f"{fn.__name__}{a}: {type(e).__name__}: {e}")

    jobs = [(generate, (0,), {}), (generate, (1,), {}),
            (generate, (2,), {"stream": True}), (generate, (3,), {}),
            (chat, (), {})]
    threads = [threading.Thread(target=guard, args=(fn, *a), kwargs=k)
               for fn, a, k in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    if errors or any(t.is_alive() for t in threads):
        raise PhaseFailed(f"{srv.name}: requests failed: {errors}")
    return out


def run_server(run: Run, name, extra, *, logprobs=False, again=None):
    """Start one server, drive the requests, read /stats, stop it
    cleanly. `again` repeats one prompt (prefix-cache hit)."""
    entries0 = run.cache_entries()
    srv = Server(run, name, extra)
    try:
        srv.wait_ready(cap_s=700)
        vocab = 256 if run.rehearse else 32768
        prompts = make_prompts(run, vocab)
        t0 = time.monotonic()
        answers = drive_requests(srv, prompts, logprobs=logprobs)
        if again is not None:
            status, _ = srv.post("/generate", {
                "tokens": prompts[again], "max_new": 4, "temperature": 0.0,
            })
            if status != 200:
                raise PhaseFailed(f"{name}: repeat request HTTP {status}")
        requests_s = time.monotonic() - t0
        stats = srv.get("/stats")
        rc = srv.stop()
    finally:
        srv.kill()
    stderr = srv.stderr()
    for w in FALLBACK_WARNINGS:
        if w in stderr:
            raise PhaseFailed(f"{name}: {w} on stderr — a decode kernel "
                              "fell back to the reference path")
    if rc != 0:
        raise PhaseFailed(f"{name}: exit code {rc} after SIGINT; stderr "
                          "ends:\n" + stderr[-2000:])
    want = 5 + (again is not None)
    if stats.get("requests_completed", 0) < want:
        raise PhaseFailed(f"{name}: /stats {stats}")
    if stats.get("tokens_generated", 0) < 5 * SERVE_MAX_NEW:
        raise PhaseFailed(f"{name}: /stats tokens_generated "
                          f"{stats.get('tokens_generated')}")
    if not stats.get("engine_steps") or not stats.get("prefills"):
        raise PhaseFailed(f"{name}: /stats {stats}")
    report = {
        "server": name, "args": extra,
        "startup_s": round(srv.startup_s, 1),
        "requests_s": round(requests_s, 1),
        "cache_backend": stats.get("cache_backend"),
        "decode_ticks": stats.get("decode_ticks"),
        "decode_ticks_source": stats.get("decode_ticks_source"),
        "overlap_decode": stats.get("overlap_decode"),
        "overlap_prefill": stats.get("overlap_prefill"),
        "requests_completed": stats.get("requests_completed"),
        "tokens_generated": stats.get("tokens_generated"),
        "engine_steps": stats.get("engine_steps"),
        "prefills": stats.get("prefills"),
        "prefix_hit_tokens": stats.get("prefix_hit_tokens"),
        "stream_records": answers["generate2_stream"]["stream_records"],
        "bytes_in_use": [m["bytes_in_use"] for m in srv.memory],
        "peak_bytes_in_use": [m["peak_bytes_in_use"] for m in srv.memory],
        "cache_entries_added": run.cache_entries() - entries0,
        "mesh_fallback": MESH_FALLBACK in stderr,
        **compile_counts(stderr),
    }
    return report, answers


def phase_serve(run: Run):
    reports = []
    # 1. Every default, the start-up decode_ticks sweep included.
    rep, _ = run_server(run, "serve-dense", [])
    if rep["decode_ticks_source"] != "auto-tuned":
        raise PhaseFailed(f"serve-dense: decode_ticks not tuned: {rep}")
    say(phase="serve", **rep)
    reports.append(rep["server"])
    # 2./3. The paged backends take the window length the sweep above
    # chose: one sweep per run keeps three cold start-ups inside the
    # time limit.
    ticks = ["--decode-ticks", str(rep["decode_ticks"])]
    rep, _ = run_server(run, "serve-paged-int8",
                        ["--cache-backend", "paged-int8"] + ticks)
    say(phase="serve", **rep)
    reports.append(rep["server"])
    if run.remaining() < 240:
        say(phase="serve", skipped="serve-paged-prefix",
            why=f"{run.remaining():.0f} s left")
        return reports
    rep, _ = run_server(
        run, "serve-paged-prefix",
        ["--cache-backend", "paged", "--prefix-cache"] + ticks, again=1,
    )
    if not rep["prefix_hit_tokens"]:
        raise PhaseFailed(f"serve-paged-prefix: no prefix hit: {rep}")
    say(phase="serve", **rep)
    reports.append(rep["server"])
    return reports


# ---------------------------------------------------------------------------
# the path across four chips
# ---------------------------------------------------------------------------


def balanced(per_device, who):
    """Sharded state within ~10 % across devices, none left empty."""
    if len(per_device) != 4 or not all(per_device):
        raise PhaseFailed(f"{who}: per-device bytes {per_device}")
    if max(per_device) > 1.10 * min(per_device):
        raise PhaseFailed(f"{who}: uneven per-device bytes {per_device}")


def phase_train4(run: Run):
    batch, seq, steps = (4, 64, 4) if run.rehearse else (4, 2048, 5)
    one = run_train(run, "train-1dev", batch=batch, seq=seq, steps=steps)
    four = run_train(run, "train-fsdp4", batch=batch, seq=seq, steps=steps,
                     mesh="fsdp=4")
    diffs = [abs(a - b) / abs(a)
             for a, b in zip(one["losses"], four["losses"])]
    report = {
        "losses_1dev": [round(x, 4) for x in one["losses"]],
        "losses_fsdp4": [round(x, 4) for x in four["losses"]],
        "max_rel_diff": round(max(diffs), 5), "rtol": LOSS_RTOL,
        "mesh_branch": ("plain reshape (fallback)" if four["mesh_fallback"]
                        else "create_device_mesh"),
        "peak_bytes_1dev": [m["peak_bytes_in_use"]
                            for m in one["final"]["memory"]],
        "peak_bytes_fsdp4": [m["peak_bytes_in_use"]
                             for m in four["final"]["memory"]],
        "bytes_in_use_fsdp4": [m["bytes_in_use"]
                               for m in four["final"]["memory"]],
        "wall_s": [one["wall_s"], four["wall_s"]],
    }
    say(phase="train4", **report)
    if max(diffs) > LOSS_RTOL:
        raise PhaseFailed(f"train4: losses disagree: {report}")
    if not run.rehearse:
        balanced(report["bytes_in_use_fsdp4"], "train fsdp=4 bytes in use")
        balanced(report["peak_bytes_fsdp4"], "train fsdp=4 peak bytes")
    return {"max_rel_diff": report["max_rel_diff"]}


def phase_serve4(run: Run):
    # The window length is pinned on both sides: the sweep is proven on
    # one chip, and here each second costs four.
    common = ["--logprobs", "--decode-ticks", "4"]
    rep1, ans1 = run_server(run, "serve-1dev", common, logprobs=True)
    say(phase="serve4", **rep1)
    rep4, ans4 = run_server(run, "serve-tp4", common + ["--mesh", "tp=4"],
                            logprobs=True)
    say(phase="serve4", **rep4)
    worst, total, count, prefixes = 0.0, 0.0, 0, {}
    for key in ("generate0", "generate1", "generate2_stream", "generate3"):
        a, b = ans1[key], ans4[key]
        pa, pb = a.get("prompt_logprobs"), b.get("prompt_logprobs")
        if not pa or not pb or len(pa) != len(pb):
            raise PhaseFailed(f"serve4: {key} has no prompt_logprobs")
        for x, y in zip(pa[1:], pb[1:]):
            d = abs(x - y)
            worst, total, count = max(worst, d), total + d, count + 1
        # First generated token: same logprob where the token agrees.
        if a["tokens"][0] == b["tokens"][0]:
            d = abs(a["logprobs"][0] - b["logprobs"][0])
            worst, total, count = max(worst, d), total + d, count + 1
        n = 0
        while (n < SERVE_MAX_NEW and a["tokens"][n] == b["tokens"][n]):
            n += 1
        prefixes[key] = n
    report = {
        "prompt_logprobs_compared": count,
        "max_abs_diff": round(worst, 5),
        "mean_abs_diff": round(total / max(count, 1), 6),
        "tolerance": {"max_abs": LOGPROB_MAX_ABS,
                      "mean_abs": LOGPROB_MEAN_ABS},
        "agreeing_token_prefix_of_%d" % SERVE_MAX_NEW: prefixes,
        "mesh_branch": ("plain reshape (fallback)" if rep4["mesh_fallback"]
                        else "create_device_mesh"),
        "bytes_in_use_1dev": rep1["bytes_in_use"],
        "bytes_in_use_tp4": rep4["bytes_in_use"],
        "peak_bytes_tp4": rep4["peak_bytes_in_use"],
    }
    say(phase="serve4", **report)
    if worst > LOGPROB_MAX_ABS or total / max(count, 1) > LOGPROB_MEAN_ABS:
        raise PhaseFailed(f"serve4: logprobs disagree: {report}")
    if not run.rehearse:
        balanced(report["bytes_in_use_tp4"], "serve tp=4 bytes in use")
        # No whole-model transient on the first chip: its peak stays
        # with the others'.
        balanced(report["peak_bytes_tp4"], "serve tp=4 peak bytes")
    return {"max_abs_diff": report["max_abs_diff"],
            "mean_abs_diff": report["mean_abs_diff"]}


# ---------------------------------------------------------------------------
# children (the only code here that imports jax)
# ---------------------------------------------------------------------------


def child_probe():
    import jax

    d = jax.devices()
    print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                      "count": len(d)}), flush=True)
    return 0


def kernels_in(text: str) -> dict:
    """Pallas kernels in a compiled program's text, by the names the
    kernels give their pallas_call."""
    import re

    names = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="[^"]*?'
        r'([A-Za-z_0-9]+)/pallas_call', text)
    out = {}
    for n in names:
        out[n] = out.get(n, 0) + 1
    out["tpu_custom_call"] = text.count('custom_call_target="tpu_custom_call"')
    return out


def child_kernels(args):
    """Compiled parity, then the programs of train and serve compiled
    through the constructors the CLI uses, in this one process."""
    import gc

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(HERE, "scripts"))
    from shellac_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shellac_tpu import get_model_config
    from shellac_tpu.config import TrainConfig
    from shellac_tpu.models import transformer
    from shellac_tpu.training import init_train_state, make_train_step
    from shellac_tpu.utils.metrics import device_info, device_memory

    device = device_info()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.rehearse:
        say(kernels_ok=False, device=device, cache_dir=cache_dir,
            error="no tpu")
        return 3
    model = "tiny" if args.rehearse else MODEL
    batch, seq = (2, 64) if args.rehearse else (6, 2048)
    n_slots, long_prompt = (2, 40) if args.rehearse else (8, 1100)

    t0 = time.monotonic()
    checks = []
    if on_tpu:
        import tpu_parity_decode

        checks = tpu_parity_decode.run_all()
    say(phase="kernels", parity_checks=len(checks),
        parity_s=round(time.monotonic() - t0, 1))

    def require_kernels(program, found, wanted):
        say(phase="kernels", program=program, kernels=found)
        missing = [k for k in wanted if not found.get(k)]
        if on_tpu and (missing or not found["tpu_custom_call"]):
            raise PhaseFailed(f"{program}: compiled without {missing}")

    # -- the train step, as cmd_train builds it -------------------------
    cfg = get_model_config(model)
    tcfg = TrainConfig(total_steps=6, warmup_steps=2, learning_rate=3e-4,
                       seed=args.seed)
    t0 = time.monotonic()
    state = jax.eval_shape(
        lambda: init_train_state(cfg, tcfg, jax.random.PRNGKey(args.seed))
    )
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    compiled = make_train_step(cfg, tcfg).lower(
        state, {"inputs": tokens, "targets": tokens}
    ).compile()
    mem = compiled.memory_analysis()
    require_kernels(
        "train_step", kernels_in(compiled.as_text()),
        ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq", "rms_norm"),
    )
    say(phase="kernels", program="train_step",
        compile_s=round(time.monotonic() - t0, 1),
        argument_bytes=mem.argument_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        output_bytes=mem.output_size_in_bytes)
    del compiled

    # -- the engine's prefill and decode programs ----------------------
    # Every program the engine builds goes through _jit_cache_program;
    # wrapping it here shows the compiled text of exactly what serves.
    from shellac_tpu.inference.cache import engine_class

    def build(backend, wanted_decode):
        # One init program, not one per parameter shape.
        params = jax.jit(lambda k: transformer.init_params(cfg, k))(
            jax.random.PRNGKey(args.seed)
        )
        eng = engine_class(backend)(
            cfg, params, n_slots=n_slots, max_len=cfg.max_seq_len,
            temperature=0.0, decode_ticks=2, cache_backend=backend,
        )
        seen = {}  # program -> the kernels its compiled text holds
        make = eng._jit_cache_program

        def recording(fn, n_tail, **kw):
            jitted = make(fn, n_tail, **kw)
            name = f"{fn.__name__}#{len(seen)}"
            seen[name] = None

            def call(*a, **k):
                if seen[name] is None:
                    seen[name] = kernels_in(
                        jitted.lower(*a, **k).compile().as_text()
                    )
                return jitted(*a, **k)

            return call

        eng._jit_cache_program = recording
        rng = np.random.default_rng(args.seed)
        eng.submit("long", rng.integers(1, cfg.vocab_size, long_prompt), 6)
        eng.submit("short", rng.integers(1, cfg.vocab_size, 9), 6)
        t0 = time.monotonic()
        out = dict(eng.run())
        if sorted(len(v) for v in out.values()) != [6, 6]:
            raise PhaseFailed(f"{backend}: engine answered {out}")
        for name, found in seen.items():
            wanted = (("flash_fwd", "rms_norm") if "prefill" in name
                      else wanted_decode + ("rms_norm",))
            require_kernels(f"engine[{backend}].{name}", found, wanted)
        say(phase="kernels", engine=backend,
            run_s=round(time.monotonic() - t0, 1),
            bytes_in_use=[m["bytes_in_use"] for m in device_memory()])

    for backend, wanted in (("dense", ("decode_dense",)),
                            ("paged-int8", ("decode_paged_group",))):
        build(backend, wanted)
        gc.collect()  # the engine and its params, before the next one

    say(kernels_ok=True, device=device, cache_dir=cache_dir,
        parity_checks=len(checks),
        peak_bytes_in_use=[m["peak_bytes_in_use"] for m in device_memory()])
    return 0


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips4", action="store_true",
                    help="run only the four-chip paths (train --mesh "
                         "fsdp=4, serve --mesh tp=4) and their one-device "
                         "twins; needs a host with four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, data and prompts")
    # Builder's rehearsal on the CPU at tiny size: never prints ok:true.
    ap.add_argument("--rehearse", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--child", choices=["probe", "kernels"],
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.child == "probe":
        return child_probe()
    if args.child == "kernels":
        try:
            return child_kernels(args)
        except PhaseFailed as e:
            say(kernels_ok=False, error=str(e))
            return 1

    device = None
    ok = False
    try:
        if not os.path.isdir(os.path.join(HERE, "shellac_tpu")):
            raise PhaseFailed(f"no shellac_tpu package beside {__file__}")
        run = Run(args)
        if args.rehearse and args.chips4:
            run.env["XLA_FLAGS"] = (
                run.env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4"
            ).strip()
        device = phase_probe(run)
        if device["platform"] != "tpu" and not run.rehearse:
            raise PhaseFailed(f"jax found no tpu: {device}")
        run.saw(device, "probe")
        need = 4 if args.chips4 else 1
        if device["count"] < need:
            raise PhaseFailed(f"need {need} chips, jax found {device}")
        say(device=device, cache_dir=run.cache_dir,
            cache_entries_before=run.cache_entries(),
            cache_dir_from_env="JAX_COMPILATION_CACHE_DIR" in os.environ)
        phases = ((("train4", phase_train4), ("serve4", phase_serve4))
                  if args.chips4 else
                  (("kernels", phase_kernels), ("train", phase_train),
                   ("serve", phase_serve)))
        failed = []
        for name, fn in phases:
            # A failed phase fails the run but not the phases after it:
            # one call then shows every fault there is.
            t0 = time.monotonic()
            before = run.cache_entries()
            try:
                result = fn(run)
            except PhaseFailed as e:
                failed.append(name)
                say(phase=name, passed=False, failed=str(e),
                    wall_s=round(time.monotonic() - t0, 1))
                continue
            say(phase=name, passed=True,
                wall_s=round(time.monotonic() - t0, 1),
                cache_entries=[before, run.cache_entries()],
                result=result)
        say(total_s=round(time.monotonic() - run.t0, 1),
            cache_entries_after=run.cache_entries())
        if failed:
            raise PhaseFailed(f"phases failed: {failed}")
        if any(d != device for d in run.devices):
            raise PhaseFailed(f"children disagree on the device: "
                              f"{run.devices}")
        ok = not args.rehearse
        if args.rehearse:
            say(rehearsal="all phases passed on " + device["platform"])
    except PhaseFailed as e:
        say(failed=str(e))
    except Exception:  # noqa: BLE001 — a fault of the smoke itself fails it
        say(failed=traceback.format_exc())
    final = {"ok": ok, "device": device}
    if args.rehearse:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
