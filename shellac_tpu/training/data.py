"""Host-side data pipeline.

The framework consumes batches of {"inputs", "targets"} int32 arrays.
Sources:
  - `token_batches`: random contiguous windows from one in-memory token
    array (tests, small corpora).
  - `shard_batches`: streaming reader over binary token shards written
    by `write_token_shard` — the pure-Python counterpart of the native
    (C++) loader in shellac_tpu/runtime, which it transparently uses
    when the compiled library is available.

Every iterator yields numpy on host; `device_prefetch` moves batches to
device (with the right sharding) one step ahead of consumption so the
TPU never waits on the host.
"""

from __future__ import annotations

import queue
import struct
import sys
import threading
from typing import Iterator, Optional, Sequence

import jax
import numpy as np

_MAGIC = b"STSH"  # shellac tpu shard
_HEADER = struct.Struct("<4sIQ")  # magic, version, num_tokens


def write_token_shard(path: str, tokens: np.ndarray) -> None:
    """Write int32 tokens as a binary shard (header + raw little-endian)."""
    tokens = np.ascontiguousarray(tokens, dtype=np.int32)
    with open(path, "wb") as f:
        f.write(_HEADER.pack(_MAGIC, 1, tokens.size))
        f.write(tokens.tobytes())


def read_token_shard(path: str) -> np.ndarray:
    with open(path, "rb") as f:
        magic, version, n = _HEADER.unpack(f.read(_HEADER.size))
        if magic != _MAGIC:
            raise ValueError(f"{path}: not a token shard (bad magic {magic!r})")
        if version != 1:
            raise ValueError(f"{path}: unsupported shard version {version}")
        data = np.frombuffer(f.read(n * 4), dtype=np.int32)
        if data.size != n:
            raise ValueError(f"{path}: truncated shard ({data.size} != {n})")
        return data


def token_batches(
    tokens: np.ndarray,
    *,
    batch_size: int,
    seq_len: int,
    seed: int = 0,
    num_batches: Optional[int] = None,
    skip: int = 0,
) -> Iterator[dict]:
    """Random contiguous windows: inputs = w[:-1], targets = w[1:].

    `skip` fast-forwards the sampler past that many batches without
    materializing them, so a resumed run (checkpoint at step N ->
    skip=N) continues the SAME deterministic stream instead of
    replaying batches it already trained on.
    """
    tokens = np.asarray(tokens, dtype=np.int32)
    if tokens.size < seq_len + 1:
        raise ValueError(f"corpus of {tokens.size} tokens < seq_len+1")
    rng = np.random.default_rng(seed)
    for _ in range(skip):
        rng.integers(0, tokens.size - seq_len, size=batch_size)
    produced = 0
    while num_batches is None or produced < num_batches:
        # Valid starts are [0, size - seq_len - 1] inclusive: the window
        # takes seq_len + 1 tokens. integers() has an exclusive high.
        starts = rng.integers(0, tokens.size - seq_len, size=batch_size)
        window = np.stack([tokens[s : s + seq_len + 1] for s in starts])
        yield {"inputs": window[:, :-1], "targets": window[:, 1:]}
        produced += 1


def distribute_batches(it: Iterator[dict], mesh) -> Iterator[dict]:
    """Per-process local batches -> global jax.Arrays on a multi-host
    mesh.

    On a pod, jit with non-addressable batch shardings cannot consume
    host numpy; every process instead contributes its LOCAL slice of
    the global batch and the runtime assembles the global array
    (jax.make_array_from_process_local_data). The iterator on each
    process must therefore yield that process's share: distinct streams
    (seed offset by process_index) when the mesh's batch axes span
    processes, or IDENTICAL streams when the batch is replicated across
    processes (tp-only meshes) — the CLI picks the seed accordingly.

    Single-process meshes pass batches through untouched (jit places
    host numpy directly).
    """
    if jax.process_count() == 1:
        yield from it
        return
    from shellac_tpu.parallel.sharding import logical_to_spec
    from jax.sharding import NamedSharding

    nbatch = mesh.shape.get("dp", 1) * mesh.shape.get("fsdp", 1)
    nproc = jax.process_count()
    if nbatch > 1 and nbatch % nproc:
        raise ValueError(
            f"batch axes (dp*fsdp={nbatch}) must be a multiple of the "
            f"{nproc} processes: with shards spanning process "
            "boundaries, two processes would contribute different rows "
            "to the same shard region"
        )
    sh = NamedSharding(mesh, logical_to_spec(("batch", "seq")))
    for batch in it:
        yield {
            k: jax.make_array_from_process_local_data(sh, np.asarray(v))
            for k, v in batch.items()
        }


def _note_reader(kind: str, why: str = "") -> None:
    """Say on stderr which shard reader a stream got (once per stream)."""
    print(f"shard reader: {kind}" + (f" ({why})" if why else ""),
          file=sys.stderr, flush=True)


def shard_batches(
    paths: Sequence[str],
    *,
    batch_size: int,
    seq_len: int,
    seed: int = 0,
    num_batches: Optional[int] = None,
    use_native: bool = True,
    skip: int = 0,
) -> Iterator[dict]:
    """Batches drawn from a set of token shards (round-robin by epoch).

    Uses the native C++ loader when it builds (mmap + prefetch
    threads); on a machine without a toolchain it falls back to the
    pure-Python reader; either way the stream says on stderr which
    reader it got. `skip` resumes
    the stream past already-trained batches (see token_batches); the
    native reader's prefetch threads make its order non-reproducible
    across run shapes, so there skipping discards real batches — cheap
    (host memcpy), and it preserves the don't-retrain-the-head
    property.
    """
    if use_native:
        try:
            from shellac_tpu.runtime.loader import NativeShardReader

            reader = NativeShardReader(paths, seed=seed)
            _note_reader("native")
            it = reader.batches(
                batch_size=batch_size, seq_len=seq_len,
                num_batches=num_batches + skip
                if num_batches is not None else None,
            )
            for _ in range(skip):
                next(it, None)
            yield from it
            return
        except (ImportError, OSError) as e:
            _note_reader("python", f"native loader unavailable: {e}")
    else:
        _note_reader("python")
    corpus = np.concatenate([read_token_shard(p) for p in paths])
    yield from token_batches(
        corpus, batch_size=batch_size, seq_len=seq_len, seed=seed,
        num_batches=num_batches, skip=skip,
    )


def pack_documents(
    docs,
    *,
    seq_len: int,
    pad_id: int = 0,
) -> Iterator[dict]:
    """Greedy first-fit packing of documents into fixed-length rows.

    Yields one row at a time: {"inputs", "targets" (seq_len,),
    "segment_ids" (seq_len,) int32 — 0 marks padding, and "mask"
    (seq_len,) fp32 — 1 only where the target stays inside the same
    document}. Feed through `batch_rows` to group into batches. Combined
    with forward(segment_ids=...), each packed document trains exactly
    as if it were alone in the row (block-diagonal attention, restarted
    positions) — no cross-document leakage, no padding waste beyond the
    final row tail.

    Documents longer than seq_len + 1 are truncated.
    """
    row_tok: list = []
    row_seg: list = []
    seg = 1

    def emit():
        t = np.full((seq_len + 1,), pad_id, np.int32)
        g = np.zeros((seq_len + 1,), np.int32)
        t[: len(row_tok)] = row_tok
        g[: len(row_seg)] = row_seg
        same = (g[1:] == g[:-1]) & (g[:-1] > 0)
        return {
            "inputs": t[:-1],
            "targets": t[1:],
            "segment_ids": g[:-1],
            "mask": same.astype(np.float32),
        }

    for doc in docs:
        d = np.asarray(doc, np.int32).reshape(-1)[: seq_len + 1]
        if d.size < 2:
            continue
        if row_tok and len(row_tok) + d.size > seq_len + 1:
            yield emit()
            row_tok, row_seg = [], []
        row_tok.extend(d.tolist())
        row_seg.extend([seg] * d.size)
        seg += 1
    if row_tok:
        yield emit()


def batch_rows(rows: Iterator[dict], batch_size: int) -> Iterator[dict]:
    """Group per-row dicts into stacked batches (drops a partial tail)."""
    buf: list = []
    for r in rows:
        buf.append(r)
        if len(buf) == batch_size:
            yield {
                k: np.stack([x[k] for x in buf]) for k in buf[0]
            }
            buf = []


def device_prefetch(
    it: Iterator[dict],
    *,
    sharding=None,
    depth: int = 2,
) -> Iterator[dict]:
    """Move batches to device ahead of consumption (double buffering).

    A small background thread keeps `depth` device-resident batches
    queued so the host-to-HBM copy overlaps the previous step's compute.
    """
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = object()
    closed = threading.Event()

    def put(batch):
        if sharding is not None:
            return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)
        return jax.tree.map(jax.device_put, batch)

    def send(item) -> bool:
        """Enqueue unless the consumer abandoned the generator — a
        worker parked forever in q.put() outlives its test/run and
        leaks a thread into the rest of the process."""
        while not closed.is_set():
            try:
                q.put(item, timeout=0.5)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for batch in it:
                if not send(put(batch)):
                    return
        except BaseException as e:  # re-raised in the consumer
            send(e)
        else:
            send(stop)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is stop:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        # Runs on normal exhaustion AND on generator close/GC: release
        # a worker mid-put and let it exit.
        closed.set()
        try:
            q.get_nowait()
        except queue.Empty:
            pass
