"""Multi-host training: 2 real processes, global batch assembly, parity.

The workers bring up jax.distributed on the CPU backend (2 processes x
2 devices), build a global fsdp=4 mesh, assemble global batches from
per-process local slices (distribute_batches), and train tiny for a few
steps. The test process independently trains the same model
single-process on the CONCATENATED batches (process 0's rows then
process 1's) and checks the multi-host losses match it — the global
batch semantics, not just "it ran".
"""

import re

import jax
import numpy as np

from shellac_tpu import ParallelConfig, get_model_config, make_mesh
from shellac_tpu.config import TrainConfig
from shellac_tpu.training import init_train_state, make_train_step

STEPS = 4
LOCAL_BATCH = 2
SEQ = 32


def _local_batches(proc: int, vocab: int):
    """Process `proc`'s deterministic local stream."""
    yield from _local_batches_n(proc, vocab, STEPS)


def _local_batches_n(proc: int, vocab: int, n: int):
    rng = np.random.default_rng(100 + proc)
    for _ in range(n):
        w = rng.integers(0, vocab, size=(LOCAL_BATCH, SEQ + 1), dtype=np.int32)
        yield {"inputs": w[:, :-1], "targets": w[:, 1:]}


_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.config import TrainConfig
from shellac_tpu.parallel.distributed import global_mesh, initialize
from shellac_tpu.training import init_train_state, make_train_step
from shellac_tpu.training.data import distribute_batches

assert initialize()
proc = jax.process_index()

STEPS, LOCAL_BATCH, SEQ = {steps}, {local_batch}, {seq}
cfg = get_model_config("tiny").replace(dtype="float32")
tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=STEPS)
mesh = global_mesh(ParallelConfig(fsdp=4))


def local_batches():
    rng = np.random.default_rng(100 + proc)
    for _ in range(STEPS):
        w = rng.integers(0, cfg.vocab_size, size=(LOCAL_BATCH, SEQ + 1),
                         dtype=np.int32)
        yield {{"inputs": w[:, :-1], "targets": w[:, 1:]}}


state = init_train_state(cfg, tcfg, jax.random.PRNGKey(tcfg.seed), mesh=mesh)
step = make_train_step(cfg, tcfg, mesh=mesh)
loss = None
for batch in distribute_batches(local_batches(), mesh):
    state, m = step(state, batch)
    loss = float(jax.device_get(m["loss"]))
print("FINAL_LOSS", proc, loss, flush=True)
print("WORKER_OK", proc, flush=True)
"""


_FIT_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.config import TrainConfig
from shellac_tpu.parallel.distributed import global_mesh, initialize
from shellac_tpu.training.loop import fit

assert initialize()
proc = jax.process_index()
cfg = get_model_config("tiny").replace(dtype="float32")
mesh = global_mesh(ParallelConfig(fsdp=4))


def local_batches(n):
    rng = np.random.default_rng(100 + proc)
    for _ in range(n):
        w = rng.integers(0, cfg.vocab_size, size=(2, 33), dtype=np.int32)
        yield {{"inputs": w[:, :-1], "targets": w[:, 1:]}}


# First run: 4 steps, checkpoint every 2 (collective orbax saves).
tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=4)
state = fit(cfg, tcfg, local_batches(8), mesh=mesh,
            checkpoint_dir={ckpt!r}, checkpoint_every=2,
            log_path=({log!r} if proc == 0 else None))
assert int(jax.device_get(state.step)) == 4

# Resume: total_steps=6 restores step 4 and trains 2 more.
tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=6)
state = fit(cfg, tcfg, local_batches(8), mesh=mesh,
            checkpoint_dir={ckpt!r}, checkpoint_every=2)
assert int(jax.device_get(state.step)) == 6, int(jax.device_get(state.step))
print("WORKER_OK", proc, flush=True)
"""


_ELASTIC_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.config import TrainConfig
from shellac_tpu.parallel.distributed import global_mesh, initialize
from shellac_tpu.training.loop import fit

assert initialize()
proc = jax.process_index()
cfg = get_model_config("tiny").replace(dtype="float32")
mesh = global_mesh(ParallelConfig(fsdp=4))


def local_batches():
    rng = np.random.default_rng(100 + proc)
    for _ in range({steps}):
        w = rng.integers(0, cfg.vocab_size, size=({local_batch}, {seq} + 1),
                         dtype=np.int32)
        yield {{"inputs": w[:, :-1], "targets": w[:, 1:]}}


# total_steps=6 — the SAME schedule as the continuation runs (the LR
# at each step depends on total_steps, so a shorter horizon here would
# checkpoint a genuinely different trajectory). The 4-batch stream
# stops the loop at step 4 via StopIteration; fit force-saves there.
tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=6)
state = fit(cfg, tcfg, local_batches(), mesh=mesh,
            checkpoint_dir={ckpt!r}, checkpoint_every=100)
assert int(jax.device_get(state.step)) == 4
print("WORKER_OK", proc, flush=True)
"""


from conftest import run_two_process as _run_pair


class TestMultihostTraining:
    def test_fit_checkpoint_resume(self, tmp_path):
        """fit() across 2 processes: collective orbax saves, proc-0-only
        metrics file, and a resumed run continuing from the restore."""
        ckpt = tmp_path / "ckpt"
        log = tmp_path / "metrics.jsonl"
        _run_pair(tmp_path, _FIT_WORKER.format(
            ckpt=str(ckpt), log=str(log)
        ))
        assert log.exists() and log.read_text().strip()

    def test_two_process_training_matches_single(self, tmp_path):
        outs = _run_pair(tmp_path, _WORKER.format(
            steps=STEPS, local_batch=LOCAL_BATCH, seq=SEQ
        ))
        losses = []
        for r, out in enumerate(outs):
            m = re.search(rf"FINAL_LOSS {r} ([0-9.]+)", out)
            assert m, out
            losses.append(float(m.group(1)))
        # Both processes observed the same replicated loss.
        assert losses[0] == losses[1], losses

        # Single-process reference over the concatenated global batches.
        cfg = get_model_config("tiny").replace(dtype="float32")
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2,
                           total_steps=STEPS)
        mesh = make_mesh(ParallelConfig(fsdp=4), devices=jax.devices()[:4])
        state = init_train_state(
            cfg, tcfg, jax.random.PRNGKey(tcfg.seed), mesh=mesh
        )
        step = make_train_step(cfg, tcfg, mesh=mesh)
        streams = [_local_batches(p, cfg.vocab_size) for p in range(2)]
        ref_loss = None
        for b0, b1 in zip(*streams):
            batch = {k: np.concatenate([b0[k], b1[k]]) for k in b0}
            state, m = step(state, batch)
            ref_loss = float(jax.device_get(m["loss"]))
        assert abs(losses[0] - ref_loss) < 1e-4, (losses[0], ref_loss)

    def test_elastic_rescale_resume(self, tmp_path):
        """Elastic recovery: a checkpoint written by a 2-process fsdp=4
        job restores onto a SINGLE-process fsdp=2 mesh (different
        process count AND topology — orbax reshards onto the target
        shardings) and continues with losses EQUAL to an uninterrupted
        single-process run over the same global batch stream. This is
        the down-scale-after-losing-a-host story, loss-exact."""
        ckpt = tmp_path / "ckpt"
        steps_total = 6
        # The worker's stream carries only the first 4 batches: fit
        # stops on StopIteration at step 4 and force-saves there.
        _run_pair(tmp_path, _ELASTIC_WORKER.format(
            steps=4, local_batch=LOCAL_BATCH, seq=SEQ,
            ckpt=str(ckpt),
        ))

        cfg = get_model_config("tiny").replace(dtype="float32")
        streams = [list(_local_batches_n(p, cfg.vocab_size, steps_total))
                   for p in range(2)]
        global_batches = [
            {k: np.concatenate([b0[k], b1[k]]) for k in b0}
            for b0, b1 in zip(*streams)
        ]

        # Uninterrupted single-process run over all 6 batches — the
        # trajectory anchor (loose: phase A ran fsdp=4 across 2 procs,
        # so cross-mesh reduction-order float noise is already in the
        # handoff state).
        tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2,
                           total_steps=steps_total)
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(tcfg.seed))
        step = make_train_step(cfg, tcfg)
        full = []
        for batch in global_batches:
            state, m = step(state, batch)
            full.append(float(jax.device_get(m["loss"])))

        # Two continuations from the SAME checkpoint: unsharded, and
        # re-scaled onto an fsdp=2 mesh. They start from bit-identical
        # state, so they must agree tightly — THE elastic-resume
        # equivalence (restore-onto-new-topology changes nothing).
        import json as _json

        from shellac_tpu.training.loop import fit

        def continue_from_ckpt(mesh, tag):
            # Private copy: fit writes a final save, which would bleed
            # a later step into the next continuation's restore.
            import shutil

            my_ckpt = tmp_path / f"ckpt_{tag}"
            shutil.copytree(ckpt, my_ckpt)
            log = tmp_path / f"resumed_{tag}.jsonl"
            final = fit(cfg, tcfg, iter(global_batches[4:]), mesh=mesh,
                        checkpoint_dir=str(my_ckpt), checkpoint_every=100,
                        log_path=str(log), log_every=1)
            assert int(jax.device_get(final.step)) == steps_total
            rows = [_json.loads(x) for x in log.read_text().splitlines()]
            return {r["step"]: r["loss"] for r in rows if "loss" in r}

        mesh2 = make_mesh(ParallelConfig(fsdp=2),
                          devices=jax.devices()[:2])
        flat = continue_from_ckpt(None, "flat")
        rescaled = continue_from_ckpt(mesh2, "fsdp2")
        for s in (5, 6):
            assert abs(rescaled[s] - flat[s]) < 2e-4, (s, rescaled, flat)
            # Loose anchor against the uninterrupted trajectory.
            assert abs(rescaled[s] - full[s - 1]) < 5e-3, (
                s, rescaled, full
            )
