"""Reads BENCHMARK.json and the data files it names. Nothing here is specific
to a cell: a later PR adds entries and files, never an edit."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name, bench=None):
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    bench = bench or benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(wl)}")
    w = wl[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits_path = os.path.join(BENCH, "cells", name + ".json")
    limits = load_json(limits_path) if os.path.exists(limits_path) else {}

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if mine(m) and m["moves"] in reported]
    return {"name": name, "workload": w, "config_name": w["config"], "config": config,
            "traffic_name": w["traffic"], "traffic": traffic, "limits": limits,
            "end_to_end": e2e, "per_layer": per_layer, "chips": w["chips"]}


def arch(config):
    return load_module(os.path.join(BENCH, "arch", config["arch"] + ".py"),
                       "bench_arch_" + config["arch"])


def driver(kind):
    return load_module(os.path.join(BENCH, "harness", "drivers", kind + ".py"),
                       "bench_driver_" + kind)


def layer_reader(metric_name):
    """``decode_step_ms.tok`` is read by ``layer_metrics/decode_step_ms.py``:
    the part after the first dot only says which end-to-end metric it moves."""
    base = metric_name.split(".", 1)[0]
    path = os.path.join(BENCH, "layer_metrics", base + ".py")
    return load_module(path, "bench_layer_" + base).read


def hf_keys(config):
    """The published keys of a configuration file (what is not ours)."""
    ours = {"arch", "source", "deployment", "published", "reduced", "serving",
            "training", "assumed"}
    return {k: v for k, v in config.items() if k not in ours}
