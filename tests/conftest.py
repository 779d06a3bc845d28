"""Test configuration: an 8-device virtual CPU mesh, persistent compile
cache off.

The tests run on the CPU (tier-1 sets JAX_PLATFORMS=cpu; it is set here
too so a bare `pytest` and the subprocesses tests spawn agree). The
chip is exercised by chip_smoke.py, never by pytest.

The persistent compile cache stays off under pytest — in this process
and, through the environment, in every CLI subprocess a test starts —
so tier-1 neither writes into the checkout nor trips on the unreadable
entries a chipless TPU compile (tests/test_aot_compile.py) would leave.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)

import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

_last_module = [None]


def pytest_runtest_setup(item):
    """Clear XLA's compiled-executable caches at module boundaries.

    A full serial run accumulates ~600 modules' worth of CPU
    executables in one process and eventually crashes inside an XLA
    compile (round-4 root cause analysis; every crash site passes in
    isolation). Dropping the caches when the suite moves to a new test
    module bounds the accumulation; within-module compile reuse — the
    kind that matters for runtime — is preserved.
    """
    mod = getattr(item, "module", None)
    name = getattr(mod, "__name__", None)
    if _last_module[0] is not None and name != _last_module[0]:
        jax.clear_caches()
    _last_module[0] = name


@pytest.fixture(autouse=True)
def no_thread_leaks():
    """Fail any test that leaks a non-daemon thread (SH014's runtime
    twin): a scheduler/poller/push-worker thread that outlives its test
    would hang the interpreter at exit and, in a full serial run, bleed
    state into every later test. Daemon threads are exempt — they are
    the explicitly fire-and-forget class — as are threads that predate
    the test (pytest plugins, jax's internals)."""
    before = set(threading.enumerate())
    yield
    leaked = [
        t for t in threading.enumerate()
        if t not in before and not t.daemon and t.is_alive()
    ]
    if not leaked:
        return
    # Close paths signal first and join second; give a shutting-down
    # thread one grace period before calling it a leak.
    deadline = 5.0 / max(1, len(leaked))
    for t in leaked:
        t.join(timeout=deadline)
    leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        "test leaked non-daemon thread(s): "
        + ", ".join(sorted(t.name for t in leaked))
        + " — join them on the owning object's close() path"
    )


@pytest.fixture(scope="session")
def mesh8():
    from shellac_tpu import ParallelConfig, make_mesh

    return make_mesh(ParallelConfig(dp=2, fsdp=1, sp=2, tp=2))


@pytest.fixture(scope="session")
def mesh_fsdp8():
    from shellac_tpu import ParallelConfig, make_mesh

    return make_mesh(ParallelConfig(fsdp=8))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


#: The stack layouts of forward_with_cache that can hold a paged
#: pool, each as (preset, overrides) with kv_heads >= 2. "looped" is
#: the plain walk run twice over the same layers, each pass with its
#: own cached layers (cfg.loop).
PAGED_STACK_LAYOUTS = {
    "plain": ("tiny", {}),
    "first_k_dense": ("tiny-moe", {"first_k_dense": 2}),
    "grouped_moe": ("tiny-moe-interleaved", {}),
    "attn_pattern": ("tiny-gemma2", {}),
    "looped": ("tiny", {"post_norms": True,
                        "loop": {"steps": 2, "exit_threshold": 1.0}}),
}


def _pool_sized_ops(hlo, pools):
    """Instructions of the kinds that move a pool (copy, transpose,
    concatenate, dynamic-slice) in optimized HLO text, whose result has
    the shape of a whole pool — stacked (L, n_blocks, ...) or viewed
    flat (L * n_blocks, ...) — or of one layer's."""
    import re

    shapes = set()
    for shape in pools:
        shapes |= {shape, shape[1:], (shape[0] * shape[1], *shape[2:])}
    found = []
    for line in hlo.splitlines():
        kind = re.search(
            r" (copy|transpose|concatenate|dynamic-slice)\(", line
        )
        dims = re.search(r"= \(?\w+\[([\d,]+)\]", line)
        if kind and dims and tuple(
            int(d) for d in dims.group(1).split(",")
        ) in shapes:
            found.append(line.strip()[:160])
    return found


@pytest.fixture
def pool_sized_ops():
    return _pool_sized_ops


@pytest.fixture(params=list(PAGED_STACK_LAYOUTS))
def paged_stack_cfg(request):
    """A float32 eight-layer config of each paged stack layout."""
    from shellac_tpu import get_model_config

    preset, extra = PAGED_STACK_LAYOUTS[request.param]
    return get_model_config(preset).replace(
        dtype="float32", n_layers=8, **extra
    )


def run_two_process(tmp_path, source, timeout=300, ok_ranks=(0, 1)):
    """Launch `source` as 2 rendezvousing jax.distributed processes.

    Shared by the multi-host serving/training tests. Asserts ranks in
    `ok_ranks` exit 0 and printed "WORKER_OK <rank>"; returns their
    outputs. Fault-injection tests pass ok_ranks=(0,) when rank 1 is
    MEANT to die mid-run.
    """
    import os
    import pathlib
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(source)
    env_base = {
        **os.environ,
        "PYTHONPATH": str(pathlib.Path(__file__).parents[1]),
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
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for r, (p, out) in enumerate(zip(procs, outs)):
        if r not in ok_ranks:
            continue
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
        assert f"WORKER_OK {r}" in out, out
    return outs
