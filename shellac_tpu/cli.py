"""Command-line interface: `python -m shellac_tpu <command>`.

Commands:
  train     train a preset (or JSON-configured) model on token shards or
            synthetic data, with checkpoints/resume and metrics logging
  eval      token-weighted NLL / perplexity of a checkpoint over shards
  generate  autoregressive sampling from a checkpoint (or random init),
            optionally speculative with a smaller draft preset
  info      show presets, a config's derived dims, and parameter counts
  top       live fleet dashboard over a serving tier URL (per-replica
            load, SLO burn rates, step-phase attribution; --once for
            scripts, --trace <id> for one request's timeline)
  lint      JAX/TPU-aware static analysis of the source tree (the SH
            rule set; see docs/static_analysis.md)

Token ids go in and out as comma-separated integers; plug a tokenizer in
front as needed. Everything here is a thin shell over the library — each
command body is the same code a user would write in a script.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import numpy as np


def _decode_ticks_arg(v: str):
    """--decode-ticks parser: an int >= 1, or 'auto' (startup sweep)."""
    if v == "auto":
        return v
    try:
        return int(v)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--decode-ticks wants an integer or 'auto', got {v!r}"
        )


def _prefill_chunk_arg(v: str):
    """--prefill-chunk parser: an int >= 1, or 'auto' (startup sweep
    of chunk candidates on the live engine — the TTFT-vs-TPOT
    fairness knob, measured instead of guessed)."""
    if v == "auto":
        return v
    try:
        return int(v)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--prefill-chunk wants an integer or 'auto', got {v!r}"
        )


def _model_config(args):
    from shellac_tpu.config import ModelConfig
    from shellac_tpu.models.registry import PRESETS

    if getattr(args, "config", None):
        with open(args.config) as f:
            raw = json.load(f)
        base = PRESETS[raw.pop("preset")] if "preset" in raw else ModelConfig()
        return base.replace(**raw).validate()
    return PRESETS[args.model].validate()


def _parallel_config(spec: str):
    from shellac_tpu.config import ParallelConfig

    if not spec:
        return None
    kw = {}
    for part in spec.split(","):
        k, v = part.split("=")
        kw[k.strip()] = int(v)
    return ParallelConfig(**kw)


def _mesh_from(args):
    pcfg = _parallel_config(getattr(args, "mesh", "") or "")
    if pcfg is None:
        return None
    from shellac_tpu.parallel.mesh import make_mesh

    return make_mesh(pcfg)


def _data_iter(args, cfg, batch_size, seq_len, num_batches=None, skip=0):
    from shellac_tpu.training.data import shard_batches, token_batches

    if args.data:
        return shard_batches(
            args.data, batch_size=batch_size, seq_len=seq_len,
            seed=args.seed, num_batches=num_batches, skip=skip,
        )
    # Synthetic corpus: a noisy periodic token stream, so the loss has
    # structure to fall on (unlike uniform random tokens).
    rng = np.random.default_rng(args.seed)
    n = max(seq_len * 64, 1 << 16)
    base = np.arange(n, dtype=np.int32) % min(97, cfg.vocab_size)
    noise = rng.integers(0, cfg.vocab_size, size=n)
    corpus = np.where(rng.random(n) < 0.1, noise, base).astype(np.int32)
    return token_batches(
        corpus, batch_size=batch_size, seq_len=seq_len, seed=args.seed,
        num_batches=num_batches, skip=skip,
    )


def _load_native(native_dir):
    """(cfg, params) from a directory written by `convert`."""
    import os

    import orbax.checkpoint as ocp

    from shellac_tpu.config import (
        Llama3RopeConfig,
        MLAConfig,
        ModelConfig,
        MoEConfig,
        YarnConfig,
    )

    with open(os.path.join(native_dir, "config.json")) as f:
        cfg_d = json.load(f)
    # Rehydrate every nested config dataclass (dataclasses.asdict wrote
    # them as plain dicts).
    nested = {
        "moe": MoEConfig, "mla": MLAConfig,
        "rope_yarn": YarnConfig, "rope_llama3": Llama3RopeConfig,
    }
    kw = {}
    for name, cls in nested.items():
        d = cfg_d.pop(name, None)
        kw[name] = cls(**d) if d else None
    cfg = ModelConfig(**cfg_d, **kw)
    params = ocp.StandardCheckpointer().restore(
        os.path.join(os.path.abspath(native_dir), "params")
    )
    return cfg.validate(), params


def _restore_params(args, cfg, train_cfg=None, mesh=None):
    """Params from --ckpt-dir (latest step), or a fresh random init.

    With `mesh`, the params are created (or restored) directly in the
    mesh's shardings, as the trainer does: a whole model built on the
    default device and sharded afterwards leaves a model-sized
    transient on one chip, and cannot work at all once the model is
    larger than that chip.

    With --ema (eval/generate on a checkpoint trained with
    TrainConfig.ema_decay), returns the averaged weights instead."""
    import jax

    from shellac_tpu.models import transformer

    use_ema = bool(getattr(args, "ema", False))
    if getattr(args, "ckpt_dir", None):
        from shellac_tpu.config import TrainConfig
        from shellac_tpu.training.checkpoint import Checkpointer
        from shellac_tpu.training.trainer import init_train_state

        tcfg = train_cfg or TrainConfig(
            # Any non-None decay makes the abstract state carry
            # ema_params so the restore's structure matches a
            # checkpoint that has them.
            ema_decay=0.999 if use_ema else None,
        )
        ckpt = Checkpointer(args.ckpt_dir)
        abstract = jax.eval_shape(
            lambda: init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        )
        state = ckpt.restore(
            abstract_state=abstract, mesh=mesh,
            model_cfg=cfg if mesh is not None else None,
        )
        if use_ema:
            if state.ema_params is None:
                raise SystemExit(
                    "--ema: checkpoint has no EMA parameters (train with "
                    "TrainConfig.ema_decay)"
                )
            return state.ema_params
        return state.params
    key = jax.random.PRNGKey(args.seed)
    if mesh is None:
        return transformer.init_params(cfg, key)
    from shellac_tpu.parallel.sharding import make_shardings

    return jax.jit(
        lambda k: transformer.init_params(cfg, k),
        out_shardings=make_shardings(mesh, transformer.logical_axes(cfg)),
    )(key)


def _resume_skip(args) -> int:
    """Batches already consumed by a checkpointed run: resume continues
    the data stream where it left off rather than replaying (and
    re-training on) the earliest batches. A cheap directory scan — the
    real Checkpointer (sweeps, manager threads) is built once, inside
    the loop, which also re-derives this skip via data_factory if the
    restore lands on an older intact step."""
    if not getattr(args, "ckpt_dir", None):
        return 0
    from shellac_tpu.training.checkpoint import latest_step_on_disk

    latest = latest_step_on_disk(args.ckpt_dir)
    return int(latest) if latest is not None else 0


def _train_config(args):
    from shellac_tpu.config import TrainConfig

    kw = {}
    for field in ("learning_rate", "warmup_steps", "weight_decay",
                  "grad_accum", "seed", "optimizer", "quant",
                  "ema_decay"):
        v = getattr(args, field, None)
        if v is not None:
            kw[field] = v
    kw["total_steps"] = args.steps
    return TrainConfig(**kw)


def cmd_train(args):
    from shellac_tpu.training.loop import fit

    cfg = _model_config(args)
    tcfg = _train_config(args)

    from shellac_tpu.parallel.distributed import initialize

    multihost = initialize()
    if multihost:
        import jax

        from shellac_tpu.parallel.distributed import global_mesh

        if not args.mesh:
            raise SystemExit(
                "multi-host train needs an explicit --mesh multiplying "
                "out to the GLOBAL device count (e.g. fsdp=32)"
            )
        if args.lora_rank is not None:
            raise SystemExit("--lora-rank training is single-host")
        pcfg = _parallel_config(args.mesh)
        mesh = global_mesh(pcfg)
        nbatch = pcfg.dp * pcfg.fsdp
        nproc = jax.process_count()
        if nbatch > 1:
            # The batch axes span processes: --batch is the GLOBAL batch
            # size; each process loads its share from a distinct stream.
            # The shards must align with process boundaries, or two
            # processes would contribute DIFFERENT rows to the same
            # shard region (undefined data, or a rejected local shape).
            if nbatch % nproc:
                raise SystemExit(
                    f"dp*fsdp={nbatch} must be a multiple of the "
                    f"{nproc} processes (batch shards must align with "
                    "process boundaries); use dp/fsdp >= processes or "
                    "a tp/pp-only mesh"
                )
            if args.batch % nproc:
                raise SystemExit(
                    f"--batch {args.batch} must divide evenly over "
                    f"{nproc} processes"
                )
            args.batch //= nproc
            args.seed = args.seed + jax.process_index()
        # else (tp/pp-only mesh): the batch is replicated across
        # processes — every process must feed IDENTICAL data, so the
        # seed stays shared.
    else:
        mesh = _mesh_from(args)
    if args.lora_rank is not None:
        data = _data_iter(args, cfg, args.batch, args.seq,
                          skip=_resume_skip(args))
        rc = _train_lora(args, cfg, tcfg, mesh, data)
        _dump_metrics(args)
        return rc

    def data_factory(step):
        # fit builds the stream from this exactly once, at the step the
        # run actually starts from (resume restore included), and
        # sentinel rollbacks re-derive it from the restored step: the
        # deterministic skip path replays exactly the batches the
        # rolled-back steps consumed, so a recovered run finishes
        # identical to an unfaulted one.
        return _data_iter(args, cfg, args.batch, args.seq, skip=step)

    state = fit(
        cfg, tcfg, None,
        mesh=mesh,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
        log_path=args.log_path,
        log_every=args.log_every,
        heartbeat_path=args.heartbeat_file,
        anomaly_action=args.anomaly_action,
        max_restores=args.max_restores,
        data_factory=data_factory,
    )
    _dump_metrics(args)
    import jax

    from shellac_tpu.utils.metrics import device_info, device_memory

    print(json.dumps({"final_step": int(jax.device_get(state.step)),
                      "device": device_info(),
                      "memory": device_memory()}))
    return 0


def _dump_metrics(args):
    """train --metrics-file: write the shared registry's snapshot (the
    shellac_train_* gauges and the step-interval histogram the loop
    deposited) as JSON, so a run's final throughput picture lands next
    to its JSONL log in one scrape-equivalent file."""
    path = getattr(args, "metrics_file", None)
    if not path:
        return
    from shellac_tpu.obs import get_registry

    with open(path, "w") as f:
        json.dump(get_registry().snapshot(), f, indent=2)
        f.write("\n")


def _train_lora(args, cfg, tcfg, mesh, data):
    """train --lora-rank: adapter-only fine-tuning over a frozen base.

    Base weights come from --base-ckpt (a regular train checkpoint) or
    a seeded random init; --ckpt-dir holds ONLY the (tiny) adapter
    state plus a lora_config.json that eval/generate --lora-dir read
    back, so the adapter checkpoint is self-describing.
    """
    import os

    import jax

    from shellac_tpu.training.loop import fit_lora
    from shellac_tpu.training.lora import LoRAConfig

    for knob in ("grad_accum", "quant", "ema_decay"):
        if getattr(args, knob, None):
            raise SystemExit(
                f"--lora-rank does not support --{knob.replace('_', '-')} "
                "(the adapter train step has no accumulation/quant/EMA)"
            )
    lcfg = LoRAConfig(
        rank=args.lora_rank,
        alpha=args.lora_alpha,
        targets=tuple(t.strip() for t in args.lora_targets.split(",")),
    ).validate(cfg)
    base_params = _restore_base_params(args, cfg, mesh)
    if args.ckpt_dir:
        os.makedirs(args.ckpt_dir, exist_ok=True)
        meta = {
            "rank": lcfg.rank,
            "alpha": lcfg.alpha,
            "targets": list(lcfg.targets),
            "optimizer": tcfg.optimizer,
            "mu_dtype": tcfg.mu_dtype,
        }
        meta_path = os.path.join(args.ckpt_dir, "lora_config.json")
        if os.path.exists(meta_path):
            # Resuming: the flags must match the checkpoint — silently
            # rewriting the metadata would brick a valid adapter dir
            # the moment the restore failed on structure mismatch.
            with open(meta_path) as f:
                saved = json.load(f)
            if saved != meta:
                raise SystemExit(
                    f"--ckpt-dir {args.ckpt_dir} holds adapters trained "
                    f"with {saved}; current flags give {meta}. Match the "
                    "original --lora-* / --optimizer flags or use a "
                    "fresh --ckpt-dir."
                )
        else:
            with open(meta_path, "w") as f:
                json.dump(meta, f)
    state = fit_lora(
        cfg, tcfg, lcfg, base_params, data,
        mesh=mesh,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
        log_path=args.log_path,
        log_every=args.log_every,
    )
    print(json.dumps({
        "final_step": int(jax.device_get(state.step)),
        "lora_rank": lcfg.rank,
        "adapter_params": int(sum(
            x.size for x in jax.tree.leaves(state.lora)
        )),
    }))
    return 0


def _restore_base_params(args, cfg, mesh):
    """Frozen base weights for adapter training: sharded restore when a
    mesh is given (materializing a large base unsharded would OOM), a
    seeded random init otherwise."""
    import jax

    from shellac_tpu.models import transformer

    if not args.base_ckpt:
        params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
        if mesh is not None:
            from shellac_tpu.parallel.sharding import shard_pytree

            params = shard_pytree(
                params, mesh, transformer.logical_axes(cfg)
            )
        return params
    if mesh is None:
        return _restore_params(
            argparse.Namespace(ckpt_dir=args.base_ckpt, ema=False,
                               seed=args.seed), cfg,
        )
    from shellac_tpu.config import TrainConfig
    from shellac_tpu.training.checkpoint import Checkpointer
    from shellac_tpu.training.trainer import init_train_state

    abstract = jax.eval_shape(
        lambda: init_train_state(cfg, TrainConfig(), jax.random.PRNGKey(0))
    )
    state = Checkpointer(args.base_ckpt).restore(
        abstract_state=abstract, mesh=mesh, model_cfg=cfg
    )
    return state.params


def _apply_lora(args, cfg, params):
    """Merge adapters from --lora-dir (written by train --lora-rank)
    into base params; no-op without the flag."""
    if not getattr(args, "lora_dir", None):
        return params
    import os

    import jax

    from shellac_tpu.config import TrainConfig
    from shellac_tpu.training.checkpoint import Checkpointer
    from shellac_tpu.training.lora import (
        LoRAConfig,
        init_lora_state,
        merge_lora,
    )

    with open(os.path.join(args.lora_dir, "lora_config.json")) as f:
        d = json.load(f)
    lcfg = LoRAConfig(rank=d["rank"], alpha=d["alpha"],
                      targets=tuple(d["targets"]))
    # Only optimizer/mu_dtype shape the state structure for restore.
    tcfg = TrainConfig(optimizer=d["optimizer"], mu_dtype=d["mu_dtype"])
    abstract = jax.eval_shape(
        lambda: init_lora_state(cfg, tcfg, lcfg, jax.random.PRNGKey(0))
    )
    state = Checkpointer(args.lora_dir).restore(abstract_state=abstract)
    # Adapters trained on a mesh restore with their saved sharding;
    # the eager merge below must not mix committed placements with the
    # host-restored base, so pull the (tiny) adapters to host first.
    return merge_lora(params, jax.device_get(state.lora), lcfg)


def cmd_dpo(args):
    """Preference fine-tuning (DPO) from a JSONL of pairs.

    The policy starts from --base-ckpt (or random); the frozen
    reference defaults to a copy of the starting policy. Data rows:
    {"prompt": ..., "chosen": ..., "rejected": ...} with token-id
    lists, or strings when --tokenizer is given.
    """
    from shellac_tpu.training.dpo import (
        DPOConfig,
        fit_dpo,
        preference_batches,
    )

    cfg = _model_config(args)
    tcfg = _train_config(args)
    dcfg = DPOConfig(
        beta=args.beta,
        loss_type=args.loss_type,
        label_smoothing=args.label_smoothing,
        reference_free=args.reference_free,
    ).validate()
    mesh = _mesh_from(args)
    tokenizer = None
    if args.tokenizer:
        from shellac_tpu.training.tokenizer import ByteTokenizer

        tokenizer = ByteTokenizer()
    data = preference_batches(
        args.data, args.batch, args.max_len,
        tokenizer=tokenizer, seed=args.seed, skip=_resume_skip(args),
    )
    init_params = _restore_base_params(args, cfg, mesh)
    state = fit_dpo(
        cfg, tcfg, dcfg, data,
        init_params=init_params,
        mesh=mesh,
        checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
        log_path=args.log_path,
        log_every=args.log_every,
    )
    import jax

    print(json.dumps({"final_step": int(jax.device_get(state.step))}))
    return 0


def cmd_distill(args):
    """Distill a frozen teacher checkpoint into a (usually smaller)
    student. The teacher is any checkpoint this framework can run; only
    the vocabularies must match."""
    import jax

    from shellac_tpu.training.distill import (
        DistillConfig,
        fit_distill,
    )

    cfg = _model_config(args)
    tcfg = _train_config(args)
    dcfg = DistillConfig(
        temperature=args.kd_temperature, alpha=args.alpha, kind=args.kind,
    ).validate()
    mesh = _mesh_from(args)
    if args.teacher_model:
        from shellac_tpu.models.registry import get_model_config

        teacher_cfg = get_model_config(args.teacher_model)
    else:
        teacher_cfg = cfg
    teacher_params = _restore_base_params(
        argparse.Namespace(base_ckpt=args.teacher_ckpt, seed=args.seed),
        teacher_cfg, mesh,
    )
    data = _data_iter(args, cfg, args.batch, args.seq,
                      skip=_resume_skip(args))
    state = fit_distill(
        cfg, tcfg, dcfg, teacher_params, data,
        teacher_cfg=teacher_cfg, mesh=mesh,
        checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every,
        log_path=args.log_path, log_every=args.log_every,
    )
    print(json.dumps({"final_step": int(jax.device_get(state.step))}))
    return 0


def cmd_eval(args):
    from shellac_tpu.training.evaluate import evaluate

    cfg = _model_config(args)
    params = _apply_lora(args, cfg, _restore_params(args, cfg))
    data = _data_iter(args, cfg, args.batch, args.seq,
                      num_batches=args.batches)
    out = evaluate(cfg, params, data, max_batches=args.batches)
    print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                      for k, v in out.items()}))
    return 0


def cmd_tokenize(args):
    from shellac_tpu.training.data import write_token_shard
    from shellac_tpu.training.tokenizer import BPETokenizer, get_tokenizer

    if args.train_bpe is not None:
        if not args.tokenizer.endswith(".json"):
            raise SystemExit(
                "--train-bpe writes a .json tokenizer file; point "
                "--tokenizer at the output path (e.g. tok.json)"
            )
        tok = BPETokenizer.train(
            args.input, vocab_size=args.train_bpe, out_path=args.tokenizer
        )
    else:
        tok = get_tokenizer(args.tokenizer)
    docs = []
    for path in args.input:
        with open(path, encoding="utf-8") as f:
            docs.append(f.read())
    tokens = tok.encode_documents(docs)
    write_token_shard(args.output, tokens)
    print(json.dumps({
        "output": args.output,
        "tokens": int(tokens.size),
        "vocab_size": tok.vocab_size,
    }))
    return 0


def cmd_generate(args):
    import jax.numpy as jnp

    if getattr(args, "native_dir", None):
        cfg, params = _load_native(args.native_dir)
    else:
        cfg = _model_config(args)
        params = _restore_params(args, cfg)
    params = _apply_lora(args, cfg, params)
    tok = None
    if args.text is not None:
        from shellac_tpu.training.tokenizer import get_tokenizer

        tok = get_tokenizer(args.tokenizer)
        ids = tok.encode(args.text, bos=False)
        prompt = ids[None, :].astype(np.int32)
    else:
        if args.prompt is None:
            raise SystemExit("need --prompt or --text")
        prompt = np.array([[int(t) for t in args.prompt.split(",")]], np.int32)
    if prompt.size == 0:
        raise SystemExit("empty prompt")

    stop_seqs = []
    if args.stop:
        for part in args.stop.split(";"):
            if not part:
                continue
            try:
                seq = [int(t) for t in part.split(",")]
            except ValueError:
                raise SystemExit(
                    f'--stop: bad token-id sequence {part!r} '
                    '(expected e.g. "13,10;0")'
                )
            if not seq:
                raise SystemExit("--stop: empty stop sequence")
            stop_seqs.append(seq)
    if args.stop_text:
        if tok is None:
            from shellac_tpu.training.tokenizer import get_tokenizer

            tok = get_tokenizer(args.tokenizer)
        for s in args.stop_text:
            seq = list(map(int, tok.encode(s, bos=False)))
            if not seq:
                raise SystemExit(
                    f"--stop-text: {s!r} encodes to zero tokens"
                )
            stop_seqs.append(seq)

    def apply_stop(ids):
        if not stop_seqs:
            return ids
        from shellac_tpu.inference.engine import truncate_at_stop

        return np.asarray(truncate_at_stop(ids[None], stop_seqs)[0], np.int64)

    if args.draft_model:
        if args.kv_quant:
            raise SystemExit("--kv-quant does not compose with "
                             "--draft-model")
        if args.num_beams and args.num_beams > 1:
            raise SystemExit("--num-beams does not compose with "
                             "--draft-model (beam search is "
                             "deterministic; speculative decoding "
                             "samples)")
        from shellac_tpu.inference.speculative import SpeculativeEngine
        from shellac_tpu.models.registry import PRESETS

        dcfg = PRESETS[args.draft_model]
        import jax

        from shellac_tpu.models import transformer

        dparams = transformer.init_params(dcfg, jax.random.PRNGKey(args.seed))
        eng = SpeculativeEngine(
            cfg, params, dcfg, dparams,
            gamma=args.gamma, temperature=args.temperature,
        )
        out = eng.generate(jnp.asarray(prompt), max_new_tokens=args.max_new)
        ids = apply_stop(np.asarray(out.tokens)[0])
        result = {
            "tokens": ids.tolist(),
            "accept_rate": round(float(out.accept_rate), 4),
            "rounds": int(out.rounds),
        }
        if tok is not None:
            result["text"] = tok.decode(ids)
        print(json.dumps(result))
        return 0

    from shellac_tpu.inference.engine import Engine

    if args.quantize:
        from shellac_tpu.ops.quant import quantize_params

        params = quantize_params(cfg, params)
    eng = Engine(
        cfg, params,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        kv_quant=args.kv_quant,
    )
    constraint = None
    if getattr(args, "json_schema", None):
        # Schema-constrained beams: compile through the same
        # schema->regex->token-DFA path the server uses, so the CLI
        # surface and HTTP surface cannot drift (docs/
        # structured_output.md).
        if not args.num_beams or args.num_beams < 1:
            raise SystemExit("--json-schema needs --num-beams >= 1 "
                             "(constrained beam search)")
        if args.eos_id is None:
            raise SystemExit("--json-schema needs --eos-id (the DFA's "
                             "EOS column and beam termination must "
                             "agree)")
        if args.stop_text:
            # The HTTP surface refuses stop with num_beams for the
            # same reason: truncating a schema-constrained beam can
            # leave schema-INVALID output, contradicting the flag's
            # promise.
            raise SystemExit("--stop-text does not compose with "
                             "--json-schema (truncation could break "
                             "the schema)")
        raw = args.json_schema
        if raw.startswith("@"):
            with open(raw[1:]) as f:
                raw = f.read()
        try:
            schema = json.loads(raw)
        except ValueError as e:
            raise SystemExit(f"--json-schema is not valid JSON: {e}")
        if tok is None:
            from shellac_tpu.training.tokenizer import get_tokenizer

            tok = get_tokenizer(args.tokenizer)
        from shellac_tpu.inference.constraints import (
            compile_token_dfa,
            constraint_pattern,
        )

        try:
            constraint = compile_token_dfa(
                constraint_pattern({"json_schema": schema}), tok,
                cfg.vocab_size, args.eos_id,
            )
        except ValueError as e:
            raise SystemExit(f"--json-schema: {e}")
    if args.num_beams and (args.num_beams > 1 or constraint is not None):
        try:
            seqs, scores = eng.beam_search(
                jnp.asarray(prompt)[0], num_beams=args.num_beams,
                max_new_tokens=args.max_new, eos_id=args.eos_id,
                length_penalty=args.length_penalty,
                constraint=constraint,
            )
        except ValueError as e:
            raise SystemExit(f"beam search: {e}")
        if not seqs:
            raise SystemExit("constrained beam search returned no "
                             "valid beams (max-new too small for the "
                             "schema?)")
        ids = np.asarray(apply_stop(np.asarray(seqs[0], np.int64)))
        result = {
            "tokens": ids.tolist(),
            "beam_scores": [round(s, 4) for s in scores],
        }
        if tok is not None:
            result["text"] = tok.decode(ids)
        print(json.dumps(result))
        return 0
    out = eng.generate(jnp.asarray(prompt), max_new_tokens=args.max_new)
    ids = apply_stop(np.asarray(out.tokens)[0])
    result = {"tokens": ids.tolist()}
    if tok is not None:
        result["text"] = tok.decode(ids)
    print(json.dumps(result))
    return 0


def cmd_batch(args):
    """Offline batch generation: JSONL prompts in, JSONL completions
    out, through the continuous-batching engine (slots stay saturated
    across requests — the high-throughput path, no HTTP in the way)."""
    from shellac_tpu.inference.cache import engine_class, resolve_backend_name
    from shellac_tpu.training.tokenizer import get_tokenizer

    try:
        backend_name = resolve_backend_name(
            args.cache_backend, kv_quant=args.kv_quant,
            rolling_window=args.rolling_window,
        )
    except ValueError as e:
        raise SystemExit(str(e))
    cfg = _model_config(args)
    mesh = _mesh_from(args)
    params = _apply_lora(args, cfg, _restore_params(args, cfg, mesh=mesh))
    if mesh is not None:
        from shellac_tpu.inference.engine import shard_params

        # Already in the mesh's shardings unless adapters were merged
        # in; then this re-places the merged leaves.
        params = shard_params(cfg, params, mesh)
    tok = get_tokenizer(args.tokenizer)
    eng = engine_class(backend_name)(
        cfg, params, n_slots=args.slots,
        max_len=args.max_len or cfg.max_seq_len,
        temperature=args.temperature, eos_id=args.eos_id,
        decode_ticks=args.decode_ticks,
        overlap_decode=args.overlap_decode,
        overlap_prefill=args.overlap_prefill,
        mesh=mesh, seed=args.seed,
        cache_backend=backend_name,
        logprobs=args.logprobs,
    )
    if args.decode_ticks == "auto":
        from shellac_tpu.inference.autotune import maybe_autotune

        maybe_autotune(eng, log=lambda m: print(m, file=sys.stderr))

    rows = []
    with open(args.input) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    if not rows:
        raise SystemExit(f"no prompts in {args.input}")

    per_req = ("max_tokens", "temperature", "top_k", "top_p", "min_p",
               "seed", "presence_penalty", "frequency_penalty")
    for i, row in enumerate(rows):
        prompt = row.get("prompt")
        if isinstance(prompt, str):
            ids = tok.encode(prompt)
        elif isinstance(prompt, list):
            ids = np.asarray(prompt, np.int32)
        else:
            raise SystemExit(f"row {i}: prompt must be text or id list")
        kw = {k: row[k] for k in per_req if row.get(k) is not None}
        max_new = int(kw.pop("max_tokens", args.max_new))
        stop = row.get("stop")
        if stop is not None:
            if isinstance(stop, str):
                # OpenAI scalar form: ONE sequence, not per-character.
                stop = [stop]
            try:
                stop = [list(map(int, tok.encode(s)))
                        if isinstance(s, str) else list(map(int, s))
                        for s in stop]
            except TypeError:
                raise SystemExit(
                    f"row {i}: stop must be a string or a list of "
                    "strings / token-id lists"
                )
        try:
            eng.submit(i, ids, max_new, stop=stop, **kw)
        except ValueError as e:
            # One malformed row must fail the job BEFORE any compute,
            # with the row named — not a traceback after checkpoint
            # load and half a batch of generation.
            raise SystemExit(f"row {i}: {e}")

    results = dict(eng.run())

    with open(args.output, "w") as f:
        for i in range(len(rows)):
            out = results[i]
            rec = {"index": i, "tokens": out, "text": tok.decode(out)}
            if args.logprobs:
                lps = eng.finished_logprobs.pop(i, None)
                if lps is not None:
                    rec["logprobs"] = lps
            f.write(json.dumps(rec) + "\n")
    print(json.dumps({
        "output": args.output,
        "requests": len(rows),
        "tokens_generated": int(eng.stats["tokens_generated"]),
        "engine_steps": int(eng.stats["engine_steps"]),
    }))
    return 0


def cmd_serve(args):
    from shellac_tpu.inference.server import serve
    from shellac_tpu.training.tokenizer import get_tokenizer

    if not args.metrics:
        # One switch for the whole process: engines, the server, and
        # the request spans all deposit into the global registry, so
        # disabling it here no-ops every write and /metrics answers
        # 404. Metrics stay ON by default — the cost when nothing
        # scrapes is a few host-side adds per engine STEP.
        from shellac_tpu.obs import get_registry

        get_registry().disable()
    # One resolution path for storage policy: the explicit
    # --cache-backend name and the deprecated legacy aliases (--paged,
    # --kv-quant, --rolling-window) all land on the same backend
    # registry the engines use.
    from shellac_tpu.inference.cache import (
        backend_flags,
        resolve_backend_name,
    )

    try:
        backend_name = resolve_backend_name(
            args.cache_backend, paged=args.paged, kv_quant=args.kv_quant,
            rolling_window=args.rolling_window,
        )
    except ValueError as e:
        raise SystemExit(str(e))
    paged, kvq, rolling = backend_flags(backend_name)
    if args.prefix_cache and not paged:
        raise SystemExit("--prefix-cache requires a paged cache backend "
                         "(--cache-backend paged|paged-int8)")
    if args.draft_model and rolling:
        raise SystemExit(
            "--draft-model (speculative) does not compose with rolling "
            "backends: the verify round re-reads positions a ring may "
            "have already evicted mid-round"
        )
    if args.draft_model and args.decode_ticks not in (1, "auto"):
        raise SystemExit("--draft-model already emits up to gamma+1 tokens "
                         "per step; --decode-ticks must stay 1")
    if args.overlap_decode is None:
        # Default: overlap on — except speculative serving, where the
        # verify round's acceptance counts gate the next round, so a
        # draft-model serve silently keeps strict ordering instead of
        # refusing a previously working invocation.
        args.overlap_decode = not args.draft_model
    elif args.draft_model and args.overlap_decode:
        raise SystemExit(
            "--overlap-decode does not compose with --draft-model (the "
            "verify round's acceptance counts gate the next round); use "
            "--no-overlap-decode"
        )
    if args.overlap_prefill is None:
        # Same default policy as --overlap-decode: on, silently off
        # for speculative serving (draft + target caches fill in
        # lockstep at admission — nothing to defer).
        args.overlap_prefill = not args.draft_model
    elif args.draft_model and args.overlap_prefill:
        raise SystemExit(
            "--overlap-prefill does not compose with --draft-model "
            "(admission fills the draft and target caches in "
            "lockstep); use --no-overlap-prefill"
        )
    if args.draft_model and args.prefill_chunk == "auto":
        raise SystemExit(
            "--prefill-chunk auto does not tune speculative engines "
            "(they pin their own prefill discipline); pass an "
            "explicit chunk size"
        )
    if args.pp_pipeline and (paged or args.draft_model):
        raise SystemExit(
            "--pp-pipeline composes with the slot caches (dense, "
            "dense-int8, rolling backends) only — no paged backends or "
            "--draft-model"
        )
    if args.pp_pipeline and not args.mesh:
        raise SystemExit("--pp-pipeline needs --mesh with pp>=2")

    from shellac_tpu.parallel.distributed import initialize

    multihost = initialize()  # joins the cluster iff the env asks
    if multihost and not args.mesh:
        raise SystemExit(
            "multi-host serve needs an explicit --mesh (e.g. tp=8) "
            "multiplying out to the GLOBAL device count"
        )
    cfg = _model_config(args)
    mesh = None
    if args.mesh:
        from shellac_tpu.inference.engine import shard_params
        from shellac_tpu.parallel.distributed import global_mesh

        pcfg = _parallel_config(args.mesh)
        if pcfg.sp > 1:
            raise SystemExit(
                "serve --mesh supports tp/pp (and single-host dp/fsdp); "
                "the sequence axis is training-side"
            )
        if multihost and pcfg.pp > 1:
            raise SystemExit(
                "multi-host serve shards with tp only; pp stages would "
                "span hosts and put per-stage cache rows off-host"
            )
        if args.pp_pipeline and pcfg.pp < 2:
            raise SystemExit(
                "--pp-pipeline needs a pp axis in --mesh (e.g. "
                "pp=2,tp=2); got " + args.mesh
            )
        if multihost and (pcfg.dp > 1 or pcfg.fsdp > 1):
            # dp/fsdp shard the KV cache's slot axis; across hosts that
            # puts decode outputs on non-addressable devices and breaks
            # the engine's replicated-host-state contract.
            raise SystemExit(
                "multi-host serve shards with tp only (e.g. --mesh "
                "tp=8); dp/fsdp would split the slot batch across hosts"
            )
        mesh = global_mesh(pcfg)
    # With a mesh the params are born in its shardings (no whole-model
    # transient on the first chip).
    params = _apply_lora(args, cfg, _restore_params(args, cfg, mesh=mesh))
    if args.quantize:
        from shellac_tpu.ops.quant import quantize_params

        params = quantize_params(cfg, params)
    if mesh is not None:
        # Re-places only what adapters or quantization rebuilt.
        params = shard_params(cfg, params, mesh)
    # Engine construction is wrapped in a zero-arg closure wherever an
    # engine is built here: the serving supervisor's auto-recovery
    # (serve --restart-budget) rebuilds a fresh engine from it after a
    # wedge, so the factory must capture everything construction needs.
    engine = None
    engine_factory = None
    from shellac_tpu.inference.cache import engine_class

    # Paged policy knobs travel with the backend name wherever a paged
    # engine (speculative or not) is constructed below.
    paged_extra = {}
    if paged:
        # block_size=None lets the engine resolve the backend's own
        # default (128 for int8 pools, 16 for bf16) — ONE source of
        # truth for page geometry.
        paged_extra = {
            "prefix_cache": args.prefix_cache,
            "block_size": args.block_size,
        }
    if args.draft_model:
        import jax

        from shellac_tpu.models import transformer
        from shellac_tpu.models.registry import PRESETS

        kind = engine_class(backend_name, speculative=True)
        dcfg = PRESETS[args.draft_model]
        dparams = transformer.init_params(dcfg, jax.random.PRNGKey(args.seed))
        if mesh is not None:
            dparams = shard_params(dcfg, dparams, mesh)

        def engine_factory():
            return kind(
                cfg, params, dcfg, dparams, gamma=args.gamma,
                n_slots=args.slots, max_len=args.max_len or cfg.max_seq_len,
                temperature=args.temperature, eos_id=args.eos_id,
                seed=args.seed, logprobs=args.logprobs,
                top_logprobs=args.top_logprobs,
                max_prefills_per_step=args.max_prefills_per_step,
                prefill_chunk=args.prefill_chunk,
                mesh=mesh,
                cache_backend=backend_name,
                **paged_extra,
            )

        engine = engine_factory()
    if engine is None and (paged or mesh is not None):
        kind = engine_class(backend_name)
        extra = dict(paged_extra)
        if not paged:
            extra["pp_pipeline"] = args.pp_pipeline

        def engine_factory():
            return kind(
                cfg, params, n_slots=args.slots,
                max_len=args.max_len or cfg.max_seq_len,
                temperature=args.temperature, eos_id=args.eos_id,
                decode_ticks=args.decode_ticks,
                overlap_decode=args.overlap_decode,
                overlap_prefill=args.overlap_prefill,
                max_prefills_per_step=args.max_prefills_per_step,
                prefill_chunk=args.prefill_chunk,
                logprobs=args.logprobs,
                top_logprobs=args.top_logprobs,
                mesh=mesh,
                cache_backend=backend_name,
                **extra,
            )

        engine = engine_factory()
    if multihost:
        from shellac_tpu.inference.multihost import MultihostEngine

        engine = MultihostEngine(engine)
        # Recovery on a pod is an epoch resync, not a rebuild: the
        # wrapper drops local work and broadcasts an epoch bump so
        # followers resynchronize (scheduler-death faults only; a
        # truly wedged native collective goes fatal immediately — the
        # stuck thread still owns the engine — see docs/inference.md).
        engine_factory = engine.resync
        if not engine.is_primary:
            # Followers never open a port: they mirror the primary's
            # command stream until it broadcasts shutdown. The fault
            # budget mirrors the primary's restart budget — 0 keeps
            # the loud crash-on-exception contract on both sides.
            engine.serve_forever(fault_budget=args.restart_budget,
                                 fault_window=args.restart_window)
            return 0
    serve(
        cfg, params,
        host=args.host, port=args.port,
        tokenizer=get_tokenizer(args.tokenizer),
        model_name=(args.model or "shellac_tpu"),
        engine=engine,
        engine_factory=engine_factory,
        n_slots=args.slots, max_len=args.max_len,
        temperature=args.temperature, eos_id=args.eos_id,
        decode_ticks=args.decode_ticks,
        overlap_decode=args.overlap_decode,
        overlap_prefill=args.overlap_prefill,
        autotune=True,
        max_prefills_per_step=args.max_prefills_per_step,
        prefill_chunk=args.prefill_chunk,
        logprobs=args.logprobs,
        top_logprobs=args.top_logprobs,
        cache_backend=backend_name,
        step_timeout=args.step_timeout,
        max_pending=args.max_pending,
        restart_budget=args.restart_budget,
        restart_window=args.restart_window,
        heartbeat_path=args.heartbeat_file,
        debug=args.debug,
        debug_include_text=args.debug_include_text,
        profile_dir=args.profile_dir,
        role=args.role,
        spool_dir=args.spool_dir,
        spool_max_bytes=args.spool_max_bytes,
        incident_dir=args.incident_dir,
        incident_rate=args.incident_rate,
        incident_window=args.incident_window,
        incident_retention=args.incident_retention,
        incident_capture_seconds=args.incident_capture_seconds,
        park_dir=args.park_dir,
        park_max_bytes=args.park_max_bytes,
        tenant_config=_load_tenant_config(args.tenant_config),
        preempt_after=args.preempt_after,
    )
    return 0


def _load_tenant_config(value):
    """--tenant-config accepts inline JSON ('{...}') or a file path.
    Returned as raw text either way — TenantPolicy.parse owns the
    actual validation, so a typo dies at startup with its real
    error, not a CLI-side guess at one."""
    if value is None:
        return None
    if value.lstrip().startswith("{"):
        return value
    with open(value) as f:
        return f.read()


def _load_slos(args):
    """Collect SLO specs from repeated --slo flags and/or --slo-file
    (a JSON list of spec strings, or {"slos": [...]}), parsed eagerly
    so a typo dies at startup, not at the first alert."""
    from shellac_tpu.obs import parse_slo_specs

    specs = list(args.slo or [])
    if args.slo_file:
        try:
            with open(args.slo_file) as f:
                data = json.load(f)
        except OSError as e:
            raise SystemExit(f"--slo-file {args.slo_file}: {e}")
        except ValueError as e:
            raise SystemExit(
                f"--slo-file {args.slo_file}: not valid JSON ({e}); "
                'expected a list of spec strings or {"slos": [...]}'
            )
        if isinstance(data, dict):
            data = data.get("slos", [])
        if not isinstance(data, list):
            raise SystemExit(
                f"--slo-file {args.slo_file}: expected a JSON list of "
                'spec strings or {"slos": [...]}'
            )
        specs.extend(str(s) for s in data)
    try:
        return parse_slo_specs(specs)
    except ValueError as e:
        raise SystemExit(f"--slo: {e}")


def cmd_serve_tier(args):
    from shellac_tpu.inference.tier import TierRouter, serve_tier

    if not args.metrics:
        from shellac_tpu.obs import get_registry

        get_registry().disable()
    autoscale = None
    if args.autoscale:
        from shellac_tpu.inference.autoscale import AutoscalePolicy

        autoscale = AutoscalePolicy(
            min_replicas=args.autoscale_min,
            max_replicas=args.autoscale_max,
            cooldown_s=args.autoscale_cooldown,
            idle_after_s=args.autoscale_idle_after,
        )
    router = TierRouter(
        args.replica,
        health_interval=args.health_interval,
        health_timeout=args.health_timeout,
        breaker_failures=args.breaker_failures,
        breaker_window=args.breaker_window,
        breaker_cooldown=args.breaker_cooldown,
        max_attempts=args.max_attempts,
        backoff_base=args.backoff_base,
        backoff_cap=args.backoff_cap,
        default_timeout=args.default_timeout,
        affinity_tolerance=args.affinity_tolerance,
        debug=args.debug,
        federate=args.federate,
        stale_after=args.stale_after,
        slos=_load_slos(args),
        disagg=args.disagg,
        kv_bandwidth=args.kv_bandwidth,
        disagg_min_prompt=args.disagg_min_prompt,
        fabric=args.fabric,
        fabric_hot_hits=args.fabric_hot_hits,
        fabric_max_push=args.fabric_max_push,
        spool_dir=args.spool_dir,
        spool_max_bytes=args.spool_max_bytes,
        incident_dir=args.incident_dir,
        incident_rate=args.incident_rate,
        incident_window=args.incident_window,
        incident_retention=args.incident_retention,
        tenant_config=_load_tenant_config(args.tenant_config),
        autoscale=autoscale,
    )
    serve_tier(router, host=args.host, port=args.port)
    return 0


def cmd_top(args):
    # Deliberately jax-free: `top` is an operator tool that must start
    # instantly on any box with Python, not just an accelerator host.
    from shellac_tpu.obs.top import run_top

    if args.tier is None and not (args.trace and args.spool):
        raise SystemExit(
            "top needs --tier (live dashboard) or --trace with "
            "--spool (recover a dead replica's timeline from disk)"
        )
    return run_top(args.tier, once=args.once, interval=args.interval,
                   trace=args.trace, timeout=args.timeout,
                   spool=args.spool)


def cmd_trace_report(args):
    # jax-free like `top`: reading a capture must work anywhere.
    from shellac_tpu.obs import tracereport

    try:
        if args.diff:
            a, b = args.diff
            result = tracereport.diff(
                tracereport.analyze(a, top=args.top),
                tracereport.analyze(b, top=args.top),
                threshold=args.threshold, min_us=args.min_us,
                phase_shift_points=args.phase_shift_points,
            )
            print(json.dumps(result, indent=1) if args.json
                  else tracereport.render_diff(result), end="")
            # Non-zero on flagged regressions so the diff gates (the
            # ROADMAP item 3 re-measure campaign's comparison step).
            return 0 if result["ok"] else 2
        if not args.capture:
            raise SystemExit(
                "trace-report needs a capture path (or --diff A B)"
            )
        report = tracereport.analyze(args.capture, top=args.top)
        print(json.dumps(report, indent=1) if args.json
              else tracereport.render_report(report), end="")
        return 0
    except (OSError, EOFError, ValueError) as e:
        # OSError covers missing files AND gzip.BadGzipFile; EOFError
        # is a TRUNCATED gzip — exactly what a crash mid-capture
        # leaves behind, so it must fail cleanly, not traceback.
        raise SystemExit(f"trace-report: {e}")


def cmd_scenarios(args):
    """Scenario-matrix SLO gate: workload-model traffic x chaos x
    per-scenario SLO assertions, verdicts folded into
    SCENARIO_LEDGER.json (docs/scenarios.md)."""
    from shellac_tpu.inference import scenarios

    return scenarios.cli_run(args)


def cmd_convert(args):
    """HF checkpoint directory -> native orbax params + config JSON."""
    import dataclasses as dc
    import os

    import orbax.checkpoint as ocp

    from shellac_tpu.models.convert import from_hf

    cfg, params = from_hf(args.hf_dir)
    out = os.path.abspath(args.out)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(out, "params"), params, force=True)
    ckptr.wait_until_finished()
    cfg_dict = dc.asdict(cfg)
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(cfg_dict, f, indent=2)
    n = sum(int(np.prod(x.shape)) for x in
            __import__("jax").tree.leaves(params))
    print(json.dumps({"out": out, "params": n,
                      "model_type": "moe" if cfg.moe else "dense"}))
    return 0


def cmd_info(args):
    import jax

    from shellac_tpu.models import transformer
    from shellac_tpu.models.registry import PRESETS

    if args.model or args.config:
        cfg = _model_config(args)
        shapes = jax.eval_shape(
            lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))
        )
        n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        print(json.dumps({
            "config": dataclasses.asdict(cfg),
            "params": n,
            "ff_dim": cfg.ff_dim,
            "head_dim": cfg.dim_per_head,
            "kv_heads": cfg.kv_heads,
        }, indent=2))
    else:
        print(json.dumps(sorted(PRESETS), indent=2))
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="shellac_tpu", description=__doc__)
    sub = p.add_subparsers(dest="command", required=True)

    def common(sp):
        sp.add_argument("--model", default="tiny",
                        help="preset name (see `info`)")
        sp.add_argument("--config", help="JSON file of ModelConfig overrides "
                        '(may include {"preset": name})')
        sp.add_argument("--seed", type=int, default=0)

    t = sub.add_parser("train", help="train a model")
    common(t)
    t.add_argument("--steps", type=int, default=100)
    t.add_argument("--batch", type=int, default=8)
    t.add_argument("--seq", type=int, default=128)
    t.add_argument("--data", nargs="*", default=None,
                   help="token shard files (default: synthetic stream)")
    t.add_argument("--mesh", default="",
                   help="mesh axes, e.g. dp=2,fsdp=2,tp=2")
    t.add_argument("--ckpt-dir")
    t.add_argument("--ckpt-every", type=int, default=500)
    t.add_argument("--log-path")
    t.add_argument("--log-every", type=int, default=10)
    t.add_argument("--metrics-file", default=None, dest="metrics_file",
                   help="write the shared metrics-registry snapshot "
                        "(shellac_train_* gauges, step-interval "
                        "histogram) as JSON when training finishes")
    t.add_argument("--heartbeat-file", default=None, dest="heartbeat_file",
                   help="liveness file the training loop touches at "
                        "1 Hz at step boundaries (forced beats bracket "
                        "anomaly rollback/restore), for external "
                        "watchdogs — matches serve --heartbeat-file")
    t.add_argument("--anomaly-action", default="rollback",
                   dest="anomaly_action",
                   choices=["warn", "skip", "rollback", "fatal"],
                   help="what the anomaly sentinel does about a "
                        "non-finite/spiking loss: rollback (default) "
                        "restores the last-good checkpoint and replays "
                        "the data stream; see docs/training.md "
                        "failure semantics")
    t.add_argument("--max-restores", type=int, default=2,
                   dest="max_restores",
                   help="skip/rollback recoveries allowed per hour "
                        "before the sentinel escalates to fatal "
                        "(0 = first anomaly is fatal)")
    t.add_argument("--learning-rate", type=float, dest="learning_rate")
    t.add_argument("--warmup-steps", type=int, dest="warmup_steps")
    t.add_argument("--weight-decay", type=float, dest="weight_decay")
    t.add_argument("--grad-accum", type=int, dest="grad_accum")
    t.add_argument("--optimizer", choices=["adamw", "lion", "adafactor", "muon"])
    t.add_argument("--quant", choices=["int8", "int8_bwd"], default=None,
                   help="quantized training compute (int8 MXU dots; "
                        "int8_bwd quantizes the backward matmuls too)")
    t.add_argument("--ema-decay", type=float, default=None, dest="ema_decay",
                   help="keep an EMA of the weights (e.g. 0.999)")
    t.add_argument("--lora-rank", type=int, default=None, dest="lora_rank",
                   help="LoRA fine-tuning: adapter rank (enables adapter-"
                        "only training; --ckpt-dir then stores adapters)")
    t.add_argument("--lora-alpha", type=float, default=16.0,
                   dest="lora_alpha")
    t.add_argument("--lora-targets", default="wq,wk,wv,wo",
                   dest="lora_targets",
                   help="comma list of wq,wk,wv,wo,w_gate,w_up,w_down")
    t.add_argument("--base-ckpt", default=None, dest="base_ckpt",
                   help="frozen base weights for --lora-rank (a regular "
                        "train checkpoint dir; default: random init)")
    t.set_defaults(fn=cmd_train)

    d = sub.add_parser("dpo", help="preference fine-tuning (DPO)")
    common(d)
    d.add_argument("--data", required=True,
                   help='JSONL of {"prompt","chosen","rejected"} pairs '
                        "(token-id lists, or text with --tokenizer)")
    d.add_argument("--tokenizer", action="store_true",
                   help="rows hold text; encode with the byte tokenizer")
    d.add_argument("--steps", type=int, default=100)
    d.add_argument("--batch", type=int, default=8)
    d.add_argument("--max-len", type=int, default=128, dest="max_len")
    d.add_argument("--beta", type=float, default=0.1)
    d.add_argument("--loss-type", default="sigmoid", dest="loss_type",
                   choices=["sigmoid", "ipo", "hinge"])
    d.add_argument("--label-smoothing", type=float, default=0.0,
                   dest="label_smoothing")
    d.add_argument("--reference-free", action="store_true",
                   dest="reference_free")
    d.add_argument("--mesh", default="",
                   help="mesh axes, e.g. dp=2,fsdp=2,tp=2")
    d.add_argument("--base-ckpt", default=None, dest="base_ckpt",
                   help="starting policy weights (a train checkpoint "
                        "dir; also the frozen reference)")
    d.add_argument("--ckpt-dir")
    d.add_argument("--ckpt-every", type=int, default=500)
    d.add_argument("--log-path")
    d.add_argument("--log-every", type=int, default=10)
    d.add_argument("--learning-rate", type=float, dest="learning_rate")
    d.add_argument("--warmup-steps", type=int, dest="warmup_steps")
    d.add_argument("--weight-decay", type=float, dest="weight_decay")
    d.add_argument("--optimizer",
                   choices=["adamw", "lion", "adafactor", "muon"])
    d.set_defaults(fn=cmd_dpo)

    kd = sub.add_parser("distill",
                        help="distill a teacher checkpoint into a student")
    common(kd)
    kd.add_argument("--teacher-model", default=None, dest="teacher_model",
                    help="teacher preset (default: same config as the "
                         "student)")
    kd.add_argument("--teacher-ckpt", default=None, dest="teacher_ckpt",
                    help="teacher train checkpoint dir (default: seeded "
                         "random weights — useful only for smoke tests)")
    kd.add_argument("--kd-temperature", type=float, default=2.0,
                    dest="kd_temperature")
    kd.add_argument("--alpha", type=float, default=0.5,
                    help="KD weight; (1-alpha) goes to hard-target CE")
    kd.add_argument("--kind", choices=["forward", "reverse"],
                    default="forward")
    kd.add_argument("--steps", type=int, default=100)
    kd.add_argument("--batch", type=int, default=8)
    kd.add_argument("--seq", type=int, default=128)
    kd.add_argument("--data", nargs="*", default=None,
                    help="token shard files (default: synthetic stream)")
    kd.add_argument("--mesh", default="")
    kd.add_argument("--ckpt-dir")
    kd.add_argument("--ckpt-every", type=int, default=500)
    kd.add_argument("--log-path")
    kd.add_argument("--log-every", type=int, default=10)
    kd.add_argument("--learning-rate", type=float, dest="learning_rate")
    kd.add_argument("--warmup-steps", type=int, dest="warmup_steps")
    kd.add_argument("--weight-decay", type=float, dest="weight_decay")
    kd.add_argument("--optimizer",
                    choices=["adamw", "lion", "adafactor", "muon"])
    kd.set_defaults(fn=cmd_distill)

    e = sub.add_parser("eval", help="perplexity of a checkpoint")
    common(e)
    e.add_argument("--ema", action="store_true",
                   help="evaluate the EMA-averaged weights")
    e.add_argument("--batch", type=int, default=8)
    e.add_argument("--seq", type=int, default=128)
    e.add_argument("--batches", type=int, default=16)
    e.add_argument("--data", nargs="*", default=None)
    e.add_argument("--ckpt-dir")
    e.add_argument("--lora-dir", default=None, dest="lora_dir",
                   help="merge adapters from a train --lora-rank dir")
    e.set_defaults(fn=cmd_eval)

    g = sub.add_parser("generate", help="sample tokens")
    common(g)
    g.add_argument("--prompt",
                   help="comma-separated token ids, e.g. 1,5,42")
    g.add_argument("--text", help="text prompt (encoded with --tokenizer)")
    g.add_argument("--tokenizer", default="byte",
                   help='"byte" or a local HF tokenizer dir')
    g.add_argument("--max-new", type=int, default=32)
    g.add_argument("--temperature", type=float, default=1.0)
    g.add_argument("--top-k", type=int, default=None)
    g.add_argument("--top-p", type=float, default=None)
    g.add_argument("--num-beams", type=int, default=None, dest="num_beams",
                   help="beam search with N beams (deterministic; "
                        "ignores temperature/top-k/top-p)")
    g.add_argument("--length-penalty", type=float, default=1.0,
                   dest="length_penalty",
                   help="beam ranking divides scores by len^alpha "
                        "(0 = raw sum, 1 = mean logprob)")
    g.add_argument("--eos-id", type=int, default=None, dest="eos_id",
                   help="EOS token id for beam finishing")
    g.add_argument("--json-schema", default=None, dest="json_schema",
                   help="JSON schema (inline, or @file) compiled to a "
                        "token-DFA constraint for beam search: every "
                        "returned beam satisfies the schema. Needs "
                        "--num-beams and --eos-id")
    g.add_argument("--ckpt-dir")
    g.add_argument("--native-dir", dest="native_dir",
                   help="directory written by `convert`")
    g.add_argument("--quantize", action="store_true",
                   help="int8 weight-only quantization")
    g.add_argument("--kv-quant", choices=["int8"], default=None,
                   dest="kv_quant",
                   help="int8 KV cache (not with --draft-model)")
    g.add_argument("--ema", action="store_true",
                   help="generate with the EMA-averaged weights")
    g.add_argument("--stop", default=None,
                   help='token-id stop sequences, e.g. "13,10;0"')
    g.add_argument("--stop-text", default=None, nargs="*",
                   help="string stop sequences (encoded with --tokenizer)")
    g.add_argument("--draft-model", default=None,
                   help="draft preset for speculative decoding")
    g.add_argument("--gamma", type=int, default=4)
    g.add_argument("--lora-dir", default=None, dest="lora_dir",
                   help="merge adapters from a train --lora-rank dir")
    g.set_defaults(fn=cmd_generate)

    b = sub.add_parser("batch",
                       help="offline batch generation (JSONL in/out)")
    common(b)
    b.add_argument("--input", required=True,
                   help='JSONL rows: {"prompt": text-or-ids, '
                        '"max_tokens"?, "temperature"?, "seed"?, '
                        '"stop"?, ...}')
    b.add_argument("--output", required=True, help="JSONL results path")
    b.add_argument("--max-new", type=int, default=64,
                   help="default max tokens when a row has none")
    b.add_argument("--slots", type=int, default=8)
    b.add_argument("--max-len", type=int, default=None, dest="max_len")
    b.add_argument("--temperature", type=float, default=0.0)
    b.add_argument("--eos-id", type=int, default=None, dest="eos_id")
    b.add_argument("--decode-ticks", type=_decode_ticks_arg, default=4,
                   dest="decode_ticks",
                   help="decode steps per host sync, or 'auto' to "
                        "sweep before the drain")
    b.add_argument("--overlap-decode", dest="overlap_decode",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="overlapped window dispatch during the drain")
    b.add_argument("--overlap-prefill", dest="overlap_prefill",
                   action=argparse.BooleanOptionalAction, default=True,
                   help="in-flight prefill pipeline during the drain "
                        "(admissions dispatch without syncing; one "
                        "batched settle per step boundary)")
    b.add_argument("--mesh", default="", help="e.g. tp=4")
    b.add_argument("--cache-backend", default=None, dest="cache_backend",
                   choices=["dense", "dense-int8", "paged", "paged-int8",
                            "rolling", "rolling-int8", "eva"],
                   help="KV-cache storage policy (the registry the "
                        "engines resolve through; see docs/inference.md "
                        "capability table)")
    b.add_argument("--kv-quant", choices=["int8"], default=None,
                   dest="kv_quant",
                   help="deprecated alias for --cache-backend "
                        "dense-int8 (composes with --rolling-window)")
    b.add_argument("--rolling-window", action="store_true",
                   dest="rolling_window",
                   help="deprecated alias for --cache-backend rolling")
    b.add_argument("--logprobs", action="store_true")
    b.add_argument("--tokenizer", default="byte")
    b.add_argument("--ckpt-dir")
    b.add_argument("--lora-dir", default=None, dest="lora_dir")
    b.set_defaults(fn=cmd_batch)

    s = sub.add_parser("serve", help="HTTP server with continuous batching")
    common(s)
    s.add_argument("--host", default="127.0.0.1")
    s.add_argument("--port", type=int, default=8000)
    s.add_argument("--slots", type=int, default=8)
    s.add_argument("--max-len", type=int, default=None, dest="max_len")
    s.add_argument("--temperature", type=float, default=0.0)
    s.add_argument("--eos-id", type=int, default=None, dest="eos_id")
    s.add_argument("--role", choices=["monolith", "prefill", "decode"],
                   default="monolith",
                   help="disaggregated-serving role, reflected in "
                        "/health, /stats, /metrics "
                        "(shellac_engine_role_info) and `top`: the "
                        "tier pairs prefill replicas (run the prompt, "
                        "export KV) with decode replicas (import KV, "
                        "stream tokens). Advisory — every role still "
                        "serves the full API, so monolithic fallback "
                        "always has a target (docs/serving_tier.md)")
    s.add_argument("--cache-backend", default=None, dest="cache_backend",
                   choices=["dense", "dense-int8", "paged", "paged-int8",
                            "rolling", "rolling-int8", "eva"],
                   help="KV-cache storage policy, resolved through the "
                        "same backend registry the engines use (the "
                        "legacy --paged/--kv-quant/--rolling-window "
                        "flags are deprecated aliases onto these names; "
                        "see docs/inference.md for the engine x backend "
                        "capability table)")
    s.add_argument("--paged", action="store_true",
                   help="deprecated alias for --cache-backend paged "
                        "(paged-int8 with --kv-quant)")
    s.add_argument("--mesh", default="",
                   help="serve sharded, e.g. tp=4 (multi-host: multiply "
                        "out to the global device count and set the "
                        "JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/"
                        "JAX_PROCESS_ID env on every process)")
    s.add_argument("--rolling-window", action="store_true",
                   dest="rolling_window",
                   help="deprecated alias for --cache-backend rolling: "
                        "ring-buffer KV cache for sliding-window models "
                        "(cache memory scales with the window, not "
                        "max-len)")
    s.add_argument("--kv-quant", choices=["int8"], default=None,
                   dest="kv_quant",
                   help="deprecated alias selecting the -int8 backend "
                        "variant: half the cache memory and HBM stream "
                        "per decode tick (dense, rolling on uniform "
                        "windows, and paged pools)")
    s.add_argument("--block-size", type=int, default=None, dest="block_size",
                   help="paged pool page size (default 16; int8 pools "
                        "need a multiple of 128 and default to 128)")
    s.add_argument("--prefix-cache", action="store_true", dest="prefix_cache",
                   help="reuse cached KV blocks across prompts sharing a "
                        "prefix (requires a paged backend)")
    s.add_argument("--decode-ticks", type=_decode_ticks_arg,
                   default="auto", dest="decode_ticks",
                   help="decode steps per host sync (throughput vs "
                        "per-token latency): an int, or 'auto' (the "
                        "default) to sweep candidates against the live "
                        "mesh at startup and keep the fastest")
    s.add_argument("--overlap-decode", dest="overlap_decode",
                   action=argparse.BooleanOptionalAction, default=None,
                   help="two-deep decode pipeline: dispatch window k+1 "
                        "while the host settles window k (greedy and "
                        "seeded outputs are token-identical either "
                        "way; --no-overlap-decode restores strict "
                        "ordering; default on, off for --draft-model)")
    s.add_argument("--overlap-prefill", dest="overlap_prefill",
                   action=argparse.BooleanOptionalAction, default=None,
                   help="in-flight prefill pipeline: admissions "
                        "dispatch their prefill and return, and every "
                        "in-flight prefill settles in one batched "
                        "pull at the next step boundary, so a prompt "
                        "burst no longer stalls the decode hot path "
                        "(TTFT is recorded at settle; greedy and "
                        "seeded outputs are token-identical either "
                        "way; default on, off for --draft-model)")
    s.add_argument("--pp-pipeline", action="store_true",
                   dest="pp_pipeline",
                   help="token-level pipelined decode on a pp mesh: "
                        "slot groups stagger across stages so no stage "
                        "idles (slot caches: bf16/int8/rolling; "
                        "n_slots divisible by pp)")
    s.add_argument("--step-timeout", type=float, default=None,
                   dest="step_timeout",
                   help="fail in-flight requests loudly if one engine "
                        "step exceeds this many seconds (wedged "
                        "collective / lost follower detection; with "
                        "--restart-budget the supervisor then rebuilds "
                        "the engine and resumes). Size it above the "
                        "worst compile, including late retraces — see "
                        "docs/inference.md failure semantics")
    s.add_argument("--restart-budget", type=int, default=0,
                   dest="restart_budget",
                   help="auto-recovery: after a wedged step or dead "
                        "scheduler, fail in-flight requests loudly and "
                        "rebuild a fresh engine, up to N times per "
                        "--restart-window before staying fatal "
                        "(0 = fail terminally, the old contract)")
    s.add_argument("--restart-window", type=float, default=300.0,
                   dest="restart_window",
                   help="sliding window (seconds) for --restart-budget; "
                        "a crash-looping engine exhausts the budget "
                        "inside it and the server goes fatal")
    s.add_argument("--max-pending", type=int, default=None,
                   dest="max_pending",
                   help="admission control: reject new requests with "
                        "HTTP 429 + Retry-After once this many are "
                        "pending, instead of queueing unboundedly")
    s.add_argument("--metrics", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="Prometheus metrics + request tracing via "
                        "GET /metrics (on by default; --no-metrics "
                        "no-ops every instrument and the endpoint "
                        "answers 404)")
    s.add_argument("--debug", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="flight-recorder debug endpoints: GET "
                        "/debug/requests (in-flight table, slot "
                        "residency, histogram exemplars), GET "
                        "/debug/request/<trace-id> (event timeline), "
                        "POST /debug/profile (on-demand capture). "
                        "--no-debug answers 404 and disables event "
                        "recording (mirrors --no-metrics)")
    s.add_argument("--debug-include-text", action="store_true",
                   dest="debug_include_text",
                   help="include prompt/generated text in /debug "
                        "responses and recorder events (REDACTED by "
                        "default: debug surfaces must not leak "
                        "transcripts)")
    s.add_argument("--profile-dir", default=None, dest="profile_dir",
                   help="directory for POST /debug/profile?seconds=N "
                        "jax.profiler captures of the live engine "
                        "(unset = the endpoint answers 400; responses "
                        "carry a capture_id/trace_dir that `python -m "
                        "shellac_tpu trace-report` accepts verbatim, "
                        "and ?report=1 inlines the analysis)")
    s.add_argument("--spool-dir", default=None, dest="spool_dir",
                   help="durable event spool: every flight-recorder "
                        "event also appends to a rotating size-capped "
                        "JSONL file here, so a SIGKILL'd replica's "
                        "in-flight timelines survive to disk (recover "
                        "with `top --trace <id> --spool <dir>`; "
                        "redaction rules apply on disk too)")
    s.add_argument("--spool-max-bytes", type=int, default=8 << 20,
                   dest="spool_max_bytes",
                   help="on-disk footprint cap for the event spool "
                        "(active + one rotated file; default 8 MiB)")
    s.add_argument("--park-dir", default=None, dest="park_dir",
                   help="KV park spool: {\"prefill_only\": true, "
                        "\"park\": true} requests export their frozen "
                        "slot as a crc-checked SHLKV1 blob here "
                        "(atomic writes, size-capped LRU), and any "
                        "replica that mounts the same directory can "
                        "{\"resume\": <park_id>} the session — so a "
                        "parked session survives this replica's death "
                        "(unset = park/resume answer 400)")
    s.add_argument("--park-max-bytes", type=int, default=256 << 20,
                   dest="park_max_bytes",
                   help="on-disk footprint cap for the park spool "
                        "(oldest-parked blobs trimmed first; default "
                        "256 MiB)")
    s.add_argument("--tenant-config", default=None, dest="tenant_config",
                   metavar="JSON_OR_PATH",
                   help="per-tenant QoS policy (inline JSON or a file "
                        'path): {"tenants": {name: {rate, burst, '
                        "max_concurrency, priority, weight}}} with an "
                        'optional "default" entry for unlisted '
                        "tenants. Enables per-tenant token-bucket + "
                        "concurrency admission (429 + Retry-After "
                        "over quota) and weighted-fair slot "
                        "scheduling by priority class "
                        "(docs/serving_tier.md#multi-tenancy)")
    s.add_argument("--preempt-after", type=float, default=None,
                   dest="preempt_after",
                   help="seconds a higher-priority request may wait "
                        "with no free slot before the cheapest lower-"
                        "class decode is preempted: frozen mid-"
                        "window, its KV parked, auto-resumed when a "
                        "slot frees — token-identical to an "
                        "unpreempted run, invisible to the victim's "
                        "client except latency (unset = never "
                        "preempt)")
    s.add_argument("--incident-dir", default=None, dest="incident_dir",
                   help="incident black box: supervisor wedge/rebuild, "
                        "restart-budget exhaustion, and POST "
                        "/debug/incident each write an atomic evidence "
                        "bundle here (recorder dump, metrics snapshot, "
                        "in-flight table, step-phase digest, config "
                        "fingerprint; docs/observability.md#incidents)")
    s.add_argument("--incident-rate", type=int, default=6,
                   dest="incident_rate",
                   help="at most this many bundles per "
                        "--incident-window seconds (sliding window; "
                        "dropped triggers are counted, not silent)")
    s.add_argument("--incident-window", type=float, default=600.0,
                   dest="incident_window",
                   help="sliding window (seconds) for --incident-rate")
    s.add_argument("--incident-retention", type=int, default=24,
                   dest="incident_retention",
                   help="bundles kept on disk; oldest deleted beyond "
                        "this")
    s.add_argument("--incident-capture-seconds", type=float, default=0.0,
                   dest="incident_capture_seconds",
                   help="arm an automatic bounded jax.profiler capture "
                        "(through the same one-at-a-time profile lock "
                        "as /debug/profile) on wedge/rebuild incident "
                        "triggers; needs --profile-dir (0 = off)")
    s.add_argument("--heartbeat-file", default=None, dest="heartbeat_file",
                   help="liveness file the serving scheduler touches "
                        "every second, for external watchdogs "
                        "(utils.failure.Heartbeat.is_stale)")
    s.add_argument("--max-prefills-per-step", type=int, default=1,
                   dest="max_prefills_per_step",
                   help="cap prefills per engine step so prompt bursts "
                        "don't stall active decodes")
    s.add_argument("--draft-model", default=None,
                   help="draft preset: serve with speculative decoding "
                        "(dense and paged backends, int8 included; "
                        "not rolling)")
    s.add_argument("--gamma", type=int, default=4,
                   help="draft tokens proposed per verification round")
    s.add_argument("--logprobs", action="store_true",
                   help="track per-token logprobs so requests may ask "
                        "for them")
    s.add_argument("--top-logprobs", type=int, default=0,
                   dest="top_logprobs",
                   help="record N alternative tokens per generated "
                        "token (payload top_logprobs slices down; "
                        "needs --logprobs)")
    s.add_argument("--prefill-chunk", type=_prefill_chunk_arg,
                   default=None, dest="prefill_chunk",
                   help="prefill prompts longer than this incrementally "
                        "(one chunk per step) so a long prompt cannot "
                        "stall active decodes; 'auto' sweeps chunk "
                        "candidates on the live engine at startup and "
                        "keeps the fastest mixed-workload setting (the "
                        "TTFT-vs-TPOT fairness knob, measured)")
    s.add_argument("--ckpt-dir")
    s.add_argument("--lora-dir", default=None, dest="lora_dir",
                   help="merge adapters from a train --lora-rank dir")
    s.add_argument("--quantize", action="store_true")
    s.add_argument("--tokenizer", default="byte")
    s.set_defaults(fn=cmd_serve)

    st = sub.add_parser(
        "serve-tier",
        help="failure-aware router over N serve replicas: health-"
             "checked membership with per-replica circuit breakers, "
             "prefix/session-affinity + load-weighted routing, retry "
             "with backoff+jitter, graceful-drain observation "
             "(docs/serving_tier.md)",
    )
    st.add_argument("--replica", action="append", required=True,
                    metavar="URL",
                    help="replica base URL (repeat per replica), e.g. "
                         "--replica http://10.0.0.1:8000")
    st.add_argument("--host", default="127.0.0.1")
    st.add_argument("--port", type=int, default=8100)
    st.add_argument("--health-interval", type=float, default=0.5,
                    dest="health_interval",
                    help="seconds between /health sweeps of the "
                         "replica set")
    st.add_argument("--health-timeout", type=float, default=2.0,
                    dest="health_timeout",
                    help="per-replica health/metrics request timeout")
    st.add_argument("--breaker-failures", type=int, default=3,
                    dest="breaker_failures",
                    help="failures inside --breaker-window that eject "
                         "a replica from routing")
    st.add_argument("--breaker-window", type=float, default=30.0,
                    dest="breaker_window",
                    help="sliding window (seconds) for the per-replica "
                         "circuit breaker")
    st.add_argument("--breaker-cooldown", type=float, default=5.0,
                    dest="breaker_cooldown",
                    help="seconds an ejected replica waits before one "
                         "half-open health probe may readmit it")
    st.add_argument("--max-attempts", type=int, default=4,
                    dest="max_attempts",
                    help="total attempts per request (first + retries "
                         "on other replicas)")
    st.add_argument("--backoff-base", type=float, default=0.05,
                    dest="backoff_base",
                    help="base of the capped exponential retry backoff "
                         "(full jitter; never outlives the request "
                         "deadline)")
    st.add_argument("--backoff-cap", type=float, default=2.0,
                    dest="backoff_cap",
                    help="ceiling (seconds) of one retry backoff draw")
    st.add_argument("--default-timeout", type=float, default=60.0,
                    dest="default_timeout",
                    help="request deadline when the payload carries no "
                         "timeout; retries stop at the deadline")
    st.add_argument("--affinity-tolerance", type=float, default=4.0,
                    dest="affinity_tolerance",
                    help="load-score gap (roughly queued requests) an "
                         "affinity hit may cost before spilling to the "
                         "least-loaded replica")
    st.add_argument("--metrics", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="Prometheus shellac_tier_* series at /metrics")
    st.add_argument("--debug", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="tier flight-recorder endpoints: GET "
                         "/debug/requests (attempt log tail, e2e "
                         "exemplars) and /debug/request/<trace-id>; "
                         "--no-debug answers 404 and stops recording")
    st.add_argument("--federate", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="re-expose every replica /metrics series on "
                         "the tier's /metrics with a replica label "
                         "(last-known-good through outages, staleness-"
                         "stamped) plus shellac_fleet_* aggregates")
    st.add_argument("--stale-after", type=float, default=5.0,
                    dest="stale_after",
                    help="seconds without a successful replica scrape "
                         "before its federated series are flagged "
                         "stale (they keep serving last-known-good)")
    st.add_argument("--slo", action="append", metavar="SPEC",
                    help="declarative SLO evaluated by multi-window "
                         "burn rate, e.g. 'ttft_p99<500ms@99.9' or "
                         "'availability@99.9' (repeatable; "
                         "docs/observability.md#fleet)")
    st.add_argument("--slo-file", default=None, dest="slo_file",
                    help="JSON file with SLO specs: a list of spec "
                         'strings, or {"slos": [...]}')
    st.add_argument("--disagg", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="disaggregated prefill/decode routing: pair "
                         "a prefill-role replica (runs the prompt, "
                         "exports KV) with a decode-role replica "
                         "(imports KV, streams tokens) per request, "
                         "falling back to monolithic serving when no "
                         "pair exists, the request uses a feature "
                         "that does not migrate, or the estimated "
                         "transfer cost exceeds the measured prefill "
                         "interference. Inert on a fleet without "
                         "role-labeled replicas (serve --role)")
    st.add_argument("--kv-bandwidth", type=float, default=1e9,
                    dest="kv_bandwidth",
                    help="assumed replica-to-replica transfer "
                         "bandwidth in bytes/s for the migration "
                         "cost estimate (est prompt tokens x the "
                         "replica-reported kv_bytes_per_token / this)")
    st.add_argument("--disagg-min-prompt", type=int, default=64,
                    dest="disagg_min_prompt",
                    help="prompts estimated shorter than this many "
                         "tokens always serve monolithically (their "
                         "prefill is cheaper than any migration)")
    st.add_argument("--fabric", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="fleet-wide KV fabric: poll each replica's "
                         "GET /kv/prefixes into a prefix directory, "
                         "route by directory-measured chain overlap "
                         "(a measured hit replaces the discounted "
                         "affinity guess), and proactively replicate "
                         "hot prefix chains to replicas that lack "
                         "them (docs/serving_tier.md#kv-fabric)")
    st.add_argument("--fabric-hot-hits", type=int, default=4,
                    dest="fabric_hot_hits",
                    help="fleet-wide hit count above which a prefix "
                         "chain is hot enough to replicate")
    st.add_argument("--fabric-max-push", type=int, default=2,
                    dest="fabric_max_push",
                    help="replication pushes ordered per health sweep "
                         "(0 keeps the directory routing but never "
                         "pushes)")
    st.add_argument("--spool-dir", default=None, dest="spool_dir",
                    help="durable event spool for the tier's attempt "
                         "log (rotating size-capped JSONL; the "
                         "replica-side serve --spool-dir twin)")
    st.add_argument("--spool-max-bytes", type=int, default=8 << 20,
                    dest="spool_max_bytes",
                    help="on-disk footprint cap for the event spool")
    st.add_argument("--incident-dir", default=None, dest="incident_dir",
                    help="incident black box: SLO page transitions, "
                         "severed streams, exhausted retries, failed "
                         "migrations, and POST /debug/incident each "
                         "write an atomic evidence bundle here — "
                         "including a federated fetch of every "
                         "replica's in-flight table and incident list "
                         "(docs/observability.md#incidents)")
    st.add_argument("--incident-rate", type=int, default=6,
                    dest="incident_rate",
                    help="at most this many bundles per "
                         "--incident-window seconds")
    st.add_argument("--incident-window", type=float, default=600.0,
                    dest="incident_window",
                    help="sliding window (seconds) for --incident-rate")
    st.add_argument("--incident-retention", type=int, default=24,
                    dest="incident_retention",
                    help="bundles kept on disk; oldest deleted beyond "
                         "this")
    st.add_argument("--tenant-config", default=None,
                    dest="tenant_config", metavar="JSON_OR_PATH",
                    help="per-tenant QoS policy enforced at the tier "
                         "edge (same JSON language as serve "
                         "--tenant-config): over-quota tenants get "
                         "429 + Retry-After before their traffic "
                         "reaches any replica, and the tenant id "
                         "rides every forwarded attempt as the "
                         "x-shellac-tenant header")
    st.add_argument("--autoscale",
                    action=argparse.BooleanOptionalAction,
                    default=False,
                    help="SLO-actuated autoscaler: a fast-burn SLO "
                         "page scales out through the replica "
                         "factory; sustained fleet idle drains the "
                         "least-loaded replica — within the "
                         "min/max envelope, one action per cooldown, "
                         "every decision a recorder event + incident "
                         "trigger. Scale-out needs a replica factory "
                         "(programmatic embedders); without one the "
                         "attempt is counted as failed. Default off: "
                         "--no-autoscale tiers are bit-identical to "
                         "pre-autoscaler builds")
    st.add_argument("--autoscale-min", type=int, default=1,
                    dest="autoscale_min",
                    help="replica floor: idle never drains below this")
    st.add_argument("--autoscale-max", type=int, default=4,
                    dest="autoscale_max",
                    help="replica ceiling: pages at the ceiling "
                         "refuse (and keep paging) rather than grow")
    st.add_argument("--autoscale-cooldown", type=float, default=60.0,
                    dest="autoscale_cooldown",
                    help="seconds after ANY action (or failed "
                         "attempt) before the next; absorbs the "
                         "previous action's effect before re-judging")
    st.add_argument("--autoscale-idle-after", type=float,
                    default=300.0, dest="autoscale_idle_after",
                    help="continuous seconds of near-zero per-replica "
                         "load before a scale-down drain")
    st.set_defaults(fn=cmd_serve_tier)

    tp = sub.add_parser(
        "top",
        help="live fleet dashboard over a tier URL: per-replica "
             "routability/pending/KV/p99, SLO burn rates, step-phase "
             "attribution, recent recorder events (--once for a "
             "single snapshot; --trace <id> for one request's "
             "timeline)",
    )
    tp.add_argument("--tier", default=None,
                    help="tier base URL, e.g. http://127.0.0.1:8100 "
                         "(optional with --trace --spool: a dead "
                         "replica's timeline reads from disk alone)")
    tp.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (CI/scripts)")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="refresh interval in seconds")
    tp.add_argument("--timeout", type=float, default=5.0,
                    help="per-endpoint fetch timeout")
    tp.add_argument("--trace", default=None, metavar="TRACE_ID",
                    help="print this trace id's recorded timeline "
                         "instead of the dashboard")
    tp.add_argument("--spool", default=None, metavar="PATH",
                    help="event-spool file or directory (the replica's "
                         "serve --spool-dir): with --trace, recover "
                         "the timeline from disk when the tier lookup "
                         "404s or the replica is dead (no --tier "
                         "needed)")
    tp.set_defaults(fn=cmd_top)

    tr = sub.add_parser(
        "trace-report",
        help="analyze a jax.profiler capture (the *.trace.json.gz a "
             "POST /debug/profile or scripts/profile_step.py "
             "--capture writes): op-level time attribution aligned "
             "with the shellac_step_phase_seconds phases, top-N ops, "
             "fusion counts; --diff A B flags regressions between "
             "two captures and exits non-zero on any "
             "(docs/observability.md#trace-analysis)",
    )
    tr.add_argument("capture", nargs="?", default=None,
                    help="capture directory (a /debug/profile "
                         "trace_dir) or a *.trace.json(.gz) file")
    tr.add_argument("--diff", nargs=2, metavar=("BEFORE", "AFTER"),
                    default=None,
                    help="compare two captures; exit 2 if AFTER "
                         "regressed vs BEFORE")
    tr.add_argument("--top", type=int, default=20,
                    help="ops listed in the report (default 20)")
    tr.add_argument("--threshold", type=float, default=0.15,
                    help="relative regression threshold for --diff "
                         "(default 0.15 = +15%%)")
    tr.add_argument("--min-us", type=float, default=50.0,
                    dest="min_us",
                    help="absolute floor (microseconds) below which "
                         "--diff ignores a change as noise")
    tr.add_argument("--phase-shift-points", type=float, default=0.15,
                    dest="phase_shift_points",
                    help="ABSOLUTE device-share points a phase may "
                         "grow before --diff flags a phase_shift "
                         "(separate from --threshold: shares live on "
                         "a 0..1 scale)")
    tr.add_argument("--json", action="store_true",
                    help="print the report/diff as JSON")
    tr.set_defaults(fn=cmd_trace_report)

    sc = sub.add_parser(
        "scenarios",
        help="scenario-matrix SLO gate: run workload-model traffic "
             "against a replica, assert per-scenario SLOs, fold "
             "verdicts into SCENARIO_LEDGER.json",
    )
    sc.add_argument("--list", action="store_true",
                    help="print the scenario catalog and exit")
    sc.add_argument("--gate", action="store_true",
                    help="run the fast gate subset and compare the "
                         "stable verdict rows against --ledger "
                         "(exit 1 SLO failure, 2 schema drift, "
                         "3 stale ledger)")
    sc.add_argument("--check", action="store_true",
                    help="no traffic: schema-check the committed "
                         "ledger and diff its statically-recomputable "
                         "fields (exit 2 drift, 3 stale)")
    sc.add_argument("--update-ledger", action="store_true",
                    dest="update_ledger",
                    help="run the gate set and rewrite --ledger")
    sc.add_argument("--ledger", default="SCENARIO_LEDGER.json",
                    help="committed baseline path "
                         "(default SCENARIO_LEDGER.json)")
    sc.add_argument("--target", default=None,
                    help="base URL of a live replica/tier to drive; "
                         "default self-hosts tiny in-process replicas")
    sc.add_argument("--scenario", action="append", default=None,
                    help="run only this scenario (repeatable)")
    sc.add_argument("--all", action="store_true",
                    help="include gate=False scenarios (subprocess "
                         "chaos) in the default selection")
    sc.add_argument("--seed", type=int, default=None,
                    help="override every workload seed (changes "
                         "fingerprints: not valid with "
                         "--update-ledger)")
    sc.add_argument("--duration-scale", type=float, default=1.0,
                    dest="duration_scale",
                    help="scale workload durations (burst offsets "
                         "and ramps scale with them)")
    sc.add_argument("--timeout", type=float, default=30.0,
                    help="per-request deadline handed to the server")
    sc.add_argument("--incident-dir", default=None,
                    dest="incident_dir",
                    help="incident bundle directory for self-hosted "
                         "replicas (an SLO breach fires "
                         "POST /debug/incident)")
    sc.add_argument("--induce-violation", action="store_true",
                    dest="induce_violation",
                    help="self-test: swap every assertion for an "
                         "impossible SLO so the gate MUST fail "
                         "(proves a green gate means something)")
    sc.add_argument("--out", default=None,
                    help="write full (non-stable) verdict rows to "
                         "this JSON file")
    sc.set_defaults(fn=cmd_scenarios)

    k = sub.add_parser("tokenize", help="encode text files into a token shard")
    k.add_argument("--input", nargs="+", required=True, help="text files")
    k.add_argument("--output", required=True, help="shard path to write")
    k.add_argument("--tokenizer", default="byte",
                   help='"byte", a trained BPE .json, or a local HF '
                        "tokenizer dir")
    k.add_argument("--train-bpe", type=int, default=None, dest="train_bpe",
                   metavar="VOCAB_SIZE",
                   help="train a byte-level BPE on the inputs first, "
                        "saving it to the --tokenizer path")
    k.set_defaults(fn=cmd_tokenize)

    c = sub.add_parser("convert",
                       help="HF checkpoint dir -> native params + config")
    c.add_argument("--hf-dir", required=True, dest="hf_dir")
    c.add_argument("--out", required=True)
    c.set_defaults(fn=cmd_convert)

    i = sub.add_parser("info", help="presets and config details")
    i.add_argument("--model")
    i.add_argument("--config")
    i.set_defaults(fn=cmd_info)

    # `lint` is dispatched before argparse (see main) so the analysis
    # CLI's option surface is forwarded verbatim and can never drift;
    # this stub only makes it show up in `--help`.
    sub.add_parser(
        "lint", add_help=False,
        help="JAX/TPU-aware static analysis (SH rule set; options: "
             "python -m shellac_tpu.analysis --help)",
    )
    return p


# Commands that never compile (stdlib-only router and dashboards, pure
# text tools): they stay importable without jax, so main() does not
# place the compile cache for them.
_HOST_ONLY_COMMANDS = frozenset(
    {"serve-tier", "top", "trace-report", "tokenize"}
)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv[:1] == ["lint"]:
        # One lint engine, two spellings: hand the rest of the command
        # line to the analysis CLI untouched.
        from shellac_tpu.analysis.cli import main as lint_main

        return lint_main(argv[1:])
    args = build_parser().parse_args(argv)
    if args.command not in _HOST_ONLY_COMMANDS:
        from shellac_tpu.utils.compile_cache import enable_compile_cache

        enable_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
