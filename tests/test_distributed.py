"""Distributed bootstrap helpers (single-process behaviors only)."""

import pytest

from shellac_tpu import ParallelConfig
from shellac_tpu.parallel.distributed import env_config, global_mesh, initialize


class TestEnvConfig:
    def test_empty_env(self, monkeypatch):
        for var in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                    "JAX_NUM_PROCESSES", "NUM_PROCESSES", "WORLD_SIZE",
                    "JAX_PROCESS_ID", "PROCESS_ID", "RANK"):
            monkeypatch.delenv(var, raising=False)
        assert env_config() is None
        assert initialize() is False

    def test_full_env(self, monkeypatch):
        monkeypatch.setenv("COORDINATOR_ADDRESS", "10.0.0.1:1234")
        monkeypatch.setenv("WORLD_SIZE", "4")
        monkeypatch.setenv("RANK", "2")
        cfg = env_config()
        assert cfg == {
            "coordinator_address": "10.0.0.1:1234",
            "num_processes": 4,
            "process_id": 2,
        }

    def test_jax_prefixed_wins(self, monkeypatch):
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "a:1")
        monkeypatch.setenv("COORDINATOR_ADDRESS", "b:2")
        monkeypatch.setenv("WORLD_SIZE", "2")
        monkeypatch.setenv("RANK", "0")
        assert env_config()["coordinator_address"] == "a:1"

    def test_partial_env_raises(self, monkeypatch):
        for var in ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                    "JAX_NUM_PROCESSES", "NUM_PROCESSES", "WORLD_SIZE",
                    "JAX_PROCESS_ID", "PROCESS_ID", "RANK"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("WORLD_SIZE", "4")
        with pytest.raises(ValueError, match="partial distributed"):
            env_config()

    def test_single_process_noop(self, monkeypatch):
        monkeypatch.setenv("COORDINATOR_ADDRESS", "x:1")
        monkeypatch.setenv("WORLD_SIZE", "1")
        monkeypatch.setenv("RANK", "0")
        assert initialize() is False  # nothing to rendezvous


class TestGlobalMesh:
    def test_device_count_mismatch(self):
        with pytest.raises(ValueError, match="wants 16 devices"):
            global_mesh(ParallelConfig(dp=16))

    def test_builds_over_all_devices(self):
        mesh = global_mesh(ParallelConfig(fsdp=8))
        assert mesh.devices.size == 8


_WORKER = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
from shellac_tpu.config import ParallelConfig
from shellac_tpu.parallel.distributed import initialize, global_mesh
assert initialize(), "initialize() did not join the cluster"
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
assert jax.process_count() == 2, jax.process_count()
assert len(jax.devices()) == 4, jax.devices()
mesh = global_mesh(ParallelConfig(dp=4))
sh = NamedSharding(mesh, P(("dp",)))
data = np.arange(4, dtype=np.float32)
arr = jax.make_array_from_callback((4,), sh, lambda idx: data[idx])
total = jax.jit(lambda x: x.sum(), out_shardings=NamedSharding(mesh, P()))(arr)
assert float(total) == 6.0, float(total)
print("WORKER_OK", jax.process_index(), flush=True)
"""


class TestTwoProcessRendezvous:
    """Actual 2-process jax.distributed bring-up over the CPU backend.

    Each worker forces the CPU platform with 2 virtual devices, joins
    through our env-driven initialize(), builds the *global* 4-device
    mesh, and jit-reduces a dp-sharded array — a real cross-process
    collective (Gloo), not env parsing.
    """

    def test_rendezvous_and_allreduce(self, tmp_path):
        import socket
        import subprocess
        import sys

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        script = tmp_path / "worker.py"
        script.write_text(_WORKER)
        env_base = {
            **__import__("os").environ,
            "PYTHONPATH": str(__import__("pathlib").Path(__file__).parents[1]),
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
        }
        procs = [
            subprocess.Popen(
                [sys.executable, str(script)],
                env={**env_base, "JAX_PROCESS_ID": str(r)},
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for r in range(2)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=180)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for r, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"rank {r} failed:\n{out}"
            assert f"WORKER_OK {r}" in out, out
