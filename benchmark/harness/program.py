"""The one file that imports the system under test.

It builds the program's model configuration from the published keys, hands
the benchmark's weights to the program under the program's names, and
constructs the engine and the server exactly as ``cli.py batch`` and
``cli.py serve`` do, with every ``"auto"`` option pinned from the
configuration file and no sweep.
"""

from __future__ import annotations

import types


def model_config(hf, arch, dtype="bfloat16"):
    """The program's ModelConfig from the published keys ``hf``."""
    from shellac_tpu.config import ModelConfig

    pc = arch.program_config(hf)
    if "hf_config" in pc:
        from shellac_tpu.models.convert import config_from_hf

        cfg = config_from_hf(types.SimpleNamespace(**pc["hf_config"]))
    else:
        cfg = ModelConfig(**pc)
    return cfg.replace(dtype=dtype, param_dtype=dtype).validate()


def max_len(cell):
    from benchmark.harness.traffic import max_footprint

    page = cell["config"]["serving"]["block_size"]
    return -(-max_footprint(cell["traffic"]) // page) * page


def build_engine(cell, cfg, params, seed, *, for_server):
    from shellac_tpu.inference.cache import engine_class

    s = cell["config"]["serving"]
    ml = max_len(cell)
    kw = dict(
        n_slots=s["n_slots"], max_len=ml, temperature=s.get("temperature", 0.0),
        eos_id=None, decode_ticks=int(s["decode_ticks"]),
        prefill_chunk=s.get("prefill_chunk"),
        overlap_decode=s["overlap_decode"], overlap_prefill=s["overlap_prefill"],
        attn_impl=s.get("attn_impl", "auto"), seed=int(seed) % (2 ** 31 - 1),
        cache_backend=s["cache_backend"], block_size=s["block_size"],
        pool_tokens=s["n_slots"] * ml,
    )
    if for_server and s.get("max_prefills_per_step") is not None:
        kw["max_prefills_per_step"] = s["max_prefills_per_step"]
    return engine_class(s["cache_backend"])(cfg, params, **kw)


def build_server(cfg, params, engine):
    from shellac_tpu.inference.server import InferenceServer

    return InferenceServer(cfg, params, engine=engine, autotune=False)
