"""Backend dispatch helpers for ops with both Pallas and XLA paths."""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
from jax import shard_map
from jax.sharding import Mesh

from shellac_tpu.parallel.mesh import AXIS_PIPE
from shellac_tpu.parallel.sharding import logical_to_spec


def default_backend() -> str:
    # Deliberately NOT cached: jax.default_backend() is already memoized
    # inside jax.
    return jax.default_backend()


def pallas_supported() -> bool:
    """True when compiled (non-interpret) Pallas TPU kernels can run."""
    return default_backend() == "tpu"


def on_mesh(mesh: Optional[Mesh]) -> bool:
    """True when `mesh` spans more than one device, i.e. the caller is
    being partitioned by GSPMD."""
    return mesh is not None and mesh.size > 1


def per_shard(kernel, mesh: Mesh, operands: Dict[str, Tuple], out_axes):
    """Run a Pallas kernel once per shard of `mesh`.

    GSPMD cannot partition a Mosaic kernel (lowering fails with "Mosaic
    kernels cannot be automatically partitioned"), so inside a program
    partitioned over a mesh a kernel runs under shard_map: every operand
    is cut along its logical axes (the same rule table that shards the
    model) and the kernel sees its local block. The kernels here are
    independent across batch rows and attention heads, so no collective
    is needed inside.

    operands: {keyword: (array or None, logical axes)} — the kernel is
    called as kernel(**arrays); a None array stays None. out_axes: the
    logical axes of its one result.

    Returns None when a sharded dim does not divide over its mesh axes
    or the mesh pipelines layers (stages run their own shard_map) — the
    caller then takes its reference path, which GSPMD partitions itself.
    """
    if mesh.shape.get(AXIS_PIPE, 1) > 1:
        return None
    names = [n for n, (x, _) in operands.items() if x is not None]
    arrays = [operands[n][0] for n in names]
    specs = [logical_to_spec(operands[n][1]) for n in names]
    for x, spec in zip(arrays, specs):
        for dim, axes in zip(x.shape, spec):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            if dim % math.prod(mesh.shape[a] for a in axes):
                return None
    absent = {n: None for n in operands if n not in names}
    return shard_map(
        lambda *xs: kernel(**absent, **dict(zip(names, xs))),
        mesh=mesh, in_specs=tuple(specs),
        out_specs=logical_to_spec(out_axes), check_vma=False,
    )(*arrays)
