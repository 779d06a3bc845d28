"""Scalar metrics logging (JSONL file + stdout) and perf accounting.

`MetricsLogger` sits on top of the shellac_tpu.obs core: every scalar
it logs is also routed into the shared registry as a
`shellac_train_<name>` gauge (latest value), so train throughput/MFU
and serving latency share one Prometheus exposition path. The JSONL
file remains the durable per-step record; the registry is the live
scrape surface.
"""

from __future__ import annotations

import json
import re
import sys
import time
from typing import IO, Optional

import jax
import numpy as np

from shellac_tpu.obs import get_registry

# Published bf16 peak FLOP/s per chip, keyed by jax's `device_kind` —
# the one table MFU is computed against. Source: Google Cloud
# documentation, "TPU v5e" (197 TFLOP/s bf16 per chip).
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
}


def peak_bf16_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of this kind. A device that is not
    in the table is an error, not a default: an MFU against the wrong
    peak is worse than none."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published bf16 peak for device_kind={device_kind!r}; "
            f"add it (with its source) to PEAK_BF16_FLOPS "
            f"(known: {sorted(PEAK_BF16_FLOPS)})"
        ) from None


def device_info() -> dict:
    """The device a result was produced on, as jax reports it — every
    printed result carries this so no number travels without it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_memory() -> list:
    """Per local device, bytes in use now and at peak as the backend
    reports them (None where it reports nothing, as the CPU does)."""
    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": stats.get("bytes_in_use"),
                    "peak_bytes_in_use": stats.get("peak_bytes_in_use")})
    return out


def train_flops_per_token(n_params: int, n_layers: int, d_model: int, seq: int) -> int:
    """Rough model FLOPs per trained token: 6*params (fwd+bwd matmuls)
    plus the causal-attention term."""
    return 6 * n_params + 12 * n_layers * d_model * seq


def _to_python(tree):
    return jax.tree.map(
        lambda x: float(np.asarray(x)) if hasattr(x, "dtype") else x, tree
    )


def _metric_name(key: str) -> str:
    """A logged dict key as a Prometheus-safe metric name suffix."""
    return re.sub(r"[^a-zA-Z0-9_]", "_", key)


class MetricsLogger:
    """JSONL + stdout scalar logger, usable as a context manager so the
    file is closed (and flushed) even when the training loop raises:

        with MetricsLogger(path) as logger:
            logger.log(step, metrics)

    The legacy call pattern (construct, log, close) keeps working.
    """

    def __init__(
        self,
        path: Optional[str] = None,
        *,
        stdout: bool = True,
        every: int = 1,
        registry=None,
        prefix: str = "shellac_train_",
    ):
        self._file: Optional[IO] = open(path, "a") if path else None
        self._stdout = stdout
        self._every = max(every, 1)
        self._registry = registry if registry is not None else get_registry()
        self._prefix = prefix
        self._gauges: dict = {}
        self._steps = self._registry.counter(
            f"{prefix}log_steps_total",
            "Training steps that reached the metrics logger",
        )

    def _route(self, record: dict) -> None:
        """Mirror the record's scalars into the shared registry as
        latest-value gauges (one exposition path with serving)."""
        if not self._registry.enabled:
            return
        self._steps.inc()
        for k, v in record.items():
            if k == "time" or not isinstance(v, (int, float)):
                continue
            gauge = self._gauges.get(k)
            if gauge is None:
                gauge = self._registry.gauge(
                    f"{self._prefix}{_metric_name(k)}",
                    f"Latest logged training scalar {k!r}",
                )
                self._gauges[k] = gauge
            gauge.set(float(v))

    def log(self, step: int, metrics: dict) -> None:
        if step % self._every:
            return
        record = {"step": int(step), "time": time.time(), **_to_python(metrics)}
        self._route(record)
        line = json.dumps(record)
        if self._file:
            self._file.write(line + "\n")
            self._file.flush()
        if self._stdout:
            shown = " ".join(
                f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
                for k, v in record.items()
                if k != "time"
            )
            print(shown, file=sys.stderr)

    def close(self) -> None:
        if self._file:
            self._file.flush()
            self._file.close()
            self._file = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
