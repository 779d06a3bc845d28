"""RMSNorm / LayerNorm.

Two implementations with one dispatch point:
  - `rms_norm_ref`: pure jnp, fp32 accumulation — XLA fuses this well and
    it is the autodiff reference.
  - `rms_norm_pallas`: a Pallas TPU kernel (rows blocked into VMEM) with a
    custom VJP whose backward recomputes through the reference (RMSNorm is
    cheap to recompute; this keeps the kernel forward-only and simple).

`rms_norm(..., impl="auto")` picks pallas on TPU when the trailing dim is
lane-aligned (multiple of 128), else the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from shellac_tpu.ops.dispatch import on_mesh, pallas_supported, per_shard


def rms_norm_ref(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    """RMSNorm with fp32 accumulation, output in x.dtype."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def layer_norm_ref(
    x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5
) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

_BLOCK_ROWS = 256


def _rms_kernel(x_ref, scale_ref, out_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    out_ref[:] = (y * (1.0 + scale_ref[:].astype(jnp.float32))).astype(out_ref.dtype)


def _rms_forward(x, scale, eps, interpret):
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    # _BLOCK_ROWS rows of a 2-byte type; half as many of float32 (a
    # float32 residual stream), so that a block in, a block out and
    # their double buffers stay inside the kernel's 16 MiB of VMEM at a
    # width of 4096 (256 float32 rows were refused on the chip by 12 KiB).
    block = min(_BLOCK_ROWS * 2 // max(2, x.dtype.itemsize), rows)
    # Pad rows to a multiple of the block so the grid divides evenly.
    pad = (-rows) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    grid = (x2.shape[0] // block,)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block, d), lambda i: (i, 0)),
        interpret=interpret,
        name="rms_norm",
    )(x2, scale)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm_pallas(x, scale, eps: float = 1e-5, interpret: bool = False):
    return _rms_forward(x, scale, eps, interpret)


def _rms_fwd(x, scale, eps, interpret):
    return _rms_forward(x, scale, eps, interpret), (x, scale)


def _rms_bwd(eps, interpret, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x_, s_: rms_norm_ref(x_, s_, eps), x, scale)
    return vjp(g)


rms_norm_pallas.defvjp(_rms_fwd, _rms_bwd)


def rms_norm(x, scale, eps: float = 1e-5, impl: str = "auto", mesh=None,
             scope: str = "norm"):
    """Dispatching RMSNorm. impl: "auto" | "pallas" | "ref".

    `mesh`: the mesh the caller is partitioned over, if any. x is then
    (batch, seq[, heads], d) in the model's logical axes and the kernel
    runs per shard (ops/dispatch.per_shard); rows are independent.
    `scope` names the device scope its operations are charged to (a
    caller that wants its norm told apart in a trace gives its own)."""
    with jax.named_scope(scope):
        return _rms_norm(x, scale, eps, impl, mesh)


def _rms_norm(x, scale, eps, impl, mesh):
    if impl == "ref":
        return rms_norm_ref(x, scale, eps)
    if impl != "pallas" and not (
        pallas_supported() and x.shape[-1] % 128 == 0
    ):
        return rms_norm_ref(x, scale, eps)
    interpret = not pallas_supported()
    if not on_mesh(mesh):
        return rms_norm_pallas(x, scale, eps, interpret)
    x_axes = ("batch", "seq", "heads")[: x.ndim - 1] + (None,)
    out = per_shard(
        lambda x, scale: rms_norm_pallas(x, scale, eps, interpret), mesh,
        {"x": (x, x_axes), "scale": (scale, (None,))}, x_axes,
    )
    return rms_norm_ref(x, scale, eps) if out is None else out
