"""Multi-host serving: lockstep engine replication across 2 processes.

The worker script runs REAL cross-process collectives on the CPU
backend (same harness as tests/test_distributed.py): both ranks build a
tp=4 global mesh spanning 2 processes x 2 devices, shard the same tiny
model onto it, and drive a MultihostEngine — rank 0 submits, rank 1
sits in serve_forever(). Rank 0 asserts the multi-host outputs are
bit-identical to a local single-process unsharded engine.
"""

import jax
import pytest

from shellac_tpu import get_model_config
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.multihost import MultihostEngine
from shellac_tpu.models import transformer

_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.engine import shard_params
from shellac_tpu.inference.multihost import MultihostEngine
from shellac_tpu.models import transformer
from shellac_tpu.parallel.distributed import global_mesh, initialize

assert initialize(), "initialize() did not join the cluster"
assert jax.process_count() == 2

cfg = get_model_config("tiny").replace(dtype="float32")
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
mesh = global_mesh(ParallelConfig(tp=4))
sharded = shard_params(cfg, params, mesh)
eng = MultihostEngine(
    BatchingEngine(cfg, sharded, n_slots=2, max_len=64, mesh=mesh)
)

rng = np.random.default_rng(7)
prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
           for n in (3, 7, 5, 6)]

if eng.is_primary:
    got = eng.run([(i, p, 8) for i, p in enumerate(prompts)])
    # Reference: plain single-process engine over the same local params.
    want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
        [(i, p, 8) for i, p in enumerate(prompts)]
    )
    assert got == want, (got, want)
else:
    eng.serve_forever()
    # The follower's replica saw the same requests and produced the
    # same tokens — its counters prove it did the work, not just idled.
    assert eng.stats["requests_completed"] == len(prompts)
    assert eng.stats["tokens_generated"] == 8 * len(prompts)
print("WORKER_OK", jax.process_index(), flush=True)
"""


_HTTP_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import json, urllib.request
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.engine import shard_params
from shellac_tpu.inference.multihost import MultihostEngine
from shellac_tpu.inference.server import InferenceServer, make_http_server
from shellac_tpu.models import transformer
from shellac_tpu.parallel.distributed import global_mesh, initialize

assert initialize()
cfg = get_model_config("tiny").replace(dtype="float32")
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
mesh = global_mesh(ParallelConfig(tp=4))
sharded = shard_params(cfg, params, mesh)
eng = MultihostEngine(
    BatchingEngine(cfg, sharded, n_slots=2, max_len=64, mesh=mesh)
)

if eng.is_primary:
    srv = InferenceServer(cfg, sharded, engine=eng)
    httpd = make_http_server(srv)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    import threading
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    req = urllib.request.Request(
        base + "/generate",
        data=json.dumps({"tokens": [3, 5, 7], "max_new": 6}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        got = json.loads(r.read())["tokens"]
    want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
        [(0, [3, 5, 7], 6)]
    )[0]
    assert got == want, (got, want)
    httpd.shutdown()
    srv.close()  # broadcasts shutdown -> rank 1 exits serve_forever
else:
    eng.serve_forever()
    assert eng.stats["requests_completed"] == 1
print("WORKER_OK", jax.process_index(), flush=True)
"""


_SPEC_WORKER = """import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
import numpy as np
from shellac_tpu import ParallelConfig, get_model_config
from shellac_tpu.inference.batching import BatchingEngine
from shellac_tpu.inference.engine import shard_params
from shellac_tpu.inference.multihost import MultihostEngine
from shellac_tpu.inference.spec_batching import SpeculativeBatchingEngine
from shellac_tpu.models import transformer
from shellac_tpu.parallel.distributed import global_mesh, initialize

assert initialize()
cfg = get_model_config("tiny").replace(dtype="float32")
params = transformer.init_params(cfg, jax.random.PRNGKey(0))
mesh = global_mesh(ParallelConfig(tp=4))
sharded = shard_params(cfg, params, mesh)
eng = MultihostEngine(SpeculativeBatchingEngine(
    cfg, sharded, cfg, sharded, gamma=3, n_slots=2, max_len=64, mesh=mesh,
))
rng = np.random.default_rng(29)
prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist() for n in (3, 6, 4)]
if eng.is_primary:
    got = eng.run([(i, p, 8) for i, p in enumerate(prompts)])
    want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
        [(i, p, 8) for i, p in enumerate(prompts)])
    assert got == want, (got, want)
else:
    eng.serve_forever()
    assert eng.stats["requests_completed"] == len(prompts)
print("WORKER_OK", jax.process_index(), flush=True)
"""


class TestMultihostServing:
    def _run_pair(self, tmp_path, source):
        from conftest import run_two_process

        run_two_process(tmp_path, source)

    def test_two_process_http_serving(self, tmp_path):
        """Full HTTP path on rank 0, follower mirroring on rank 1."""
        self._run_pair(tmp_path, _HTTP_WORKER)

    def test_two_process_lockstep_serving(self, tmp_path):
        """Engine-level drive: rank 0 run()s, rank 1 mirrors."""
        self._run_pair(tmp_path, _WORKER)

    def test_two_process_speculative_serving(self, tmp_path):
        """Speculative batching under the lockstep wrapper: the
        draft/verify rounds are deterministic given the command stream,
        so the replicas stay bit-identical too."""
        self._run_pair(tmp_path, _SPEC_WORKER)


class TestSingleProcessDegenerate:
    """The wrapper is a clean pass-through on single-process jobs."""

    def test_run_matches_bare_engine(self):
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        prompts = [[3, 5, 7], [11, 2]]
        want = BatchingEngine(cfg, params, n_slots=2, max_len=64).run(
            [(i, p, 6) for i, p in enumerate(prompts)]
        )
        eng = MultihostEngine(
            BatchingEngine(cfg, params, n_slots=2, max_len=64)
        )
        assert eng.is_primary
        got = eng.run([(i, p, 6) for i, p in enumerate(prompts)])
        assert got == want
        assert eng.step() is None  # shut down

    def test_follower_surface_guard(self):
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = MultihostEngine(
            BatchingEngine(cfg, params, n_slots=2, max_len=64)
        )
        eng.is_primary = False  # simulate a follower
        with pytest.raises(RuntimeError, match="primary-only"):
            eng.submit("r", [1, 2], 4)

    def test_cancel_flows_through(self):
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = MultihostEngine(
            BatchingEngine(cfg, params, n_slots=1, max_len=64)
        )
        eng.submit("a", [1, 2, 3], 8)
        eng.submit("b", [4, 5], 8)  # queued behind a
        assert eng.cancel("b") is True
        assert eng.cancel("nope") is False
        out = {}
        while eng.pending:
            for rid, toks in eng.step():
                out[rid] = toks
        assert set(out) == {"a"}

    def test_resync_bumps_epoch_and_drops_work(self):
        """The supervisor's recovery hook: resync() aborts local work,
        bumps the epoch, and the engine serves fresh requests after —
        the epoch command rides the next step's command stream."""
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = MultihostEngine(
            BatchingEngine(cfg, params, n_slots=1, max_len=64)
        )
        eng.submit("in_flight", [1, 2, 3], 8)
        eng.submit("queued", [4, 5], 8)
        eng.step()  # "in_flight" takes the slot
        assert eng.resync() is eng
        assert eng.epoch == 1
        assert eng.pending == 0
        out = {}
        eng.submit("fresh", [1, 2, 3], 4)
        while eng.pending:
            for rid, toks in eng.step():
                out[rid] = toks
        assert set(out) == {"fresh"}
        want = BatchingEngine(cfg, params, n_slots=1, max_len=64).run(
            [("fresh", [1, 2, 3], 4)]
        )
        assert out == want

    def test_resync_rekeys_prng_from_seed_and_epoch(self):
        """Post-recovery sampling must stay seed-dependent: the epoch
        re-key folds the CONSTRUCTION seed, so two jobs with different
        seeds do not collapse onto one stream after a resync."""
        import numpy as np

        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = BatchingEngine(cfg, params, n_slots=1, max_len=64, seed=5)
        mh = MultihostEngine(eng)
        mh.resync()
        want = jax.random.fold_in(jax.random.PRNGKey(5), 1)
        assert (np.asarray(eng._key) == np.asarray(want)).all()
        mh.resync()
        want2 = jax.random.fold_in(jax.random.PRNGKey(5), 2)
        assert (np.asarray(eng._key) == np.asarray(want2)).all()

    def test_follower_step_faults_tolerated_within_budget(self):
        """A replicated step exception must not kill the follower loop
        outright — it drops local work and keeps participating so the
        primary's epoch bump can resynchronize it; a crash loop
        exhausts the budget and re-raises loudly."""
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))

        aborts = []

        class _AlwaysDies(BatchingEngine):
            def step(self):
                raise OSError("transport reset by peer")

            def abort_all(self):
                aborts.append(1)
                return super().abort_all()

        mh = MultihostEngine(_AlwaysDies(cfg, params, n_slots=1,
                                         max_len=64))
        with pytest.raises(OSError, match="transport reset"):
            mh.serve_forever(fault_budget=2)
        assert len(aborts) == 2  # two tolerated faults, third re-raised
        # Default budget 0: the loud legacy contract, first fault
        # re-raises untouched.
        aborts.clear()
        mh2 = MultihostEngine(_AlwaysDies(cfg, params, n_slots=1,
                                          max_len=64))
        with pytest.raises(OSError, match="transport reset"):
            mh2.serve_forever()
        assert aborts == []

    def test_resync_after_shutdown_refused(self):
        cfg = get_model_config("tiny").replace(dtype="float32")
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        eng = MultihostEngine(
            BatchingEngine(cfg, params, n_slots=1, max_len=64)
        )
        eng.shutdown()
        with pytest.raises(RuntimeError, match="shutdown"):
            eng.resync()
