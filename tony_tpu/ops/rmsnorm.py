"""Fused RMSNorm: pallas TPU kernel + jnp reference.

RMSNorm is HBM-bandwidth bound; the kernel fuses the mean-square reduction,
rsqrt, and scale into one VMEM pass (the guide's elementwise+reduction
pattern). Statistics are computed in f32 regardless of input dtype. The
custom_vjp keeps the backward in plain jnp — XLA fuses it with the
surrounding matmul epilogues anyway; the forward fusion is where the
bandwidth win is.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.ops.vma import batch_axes_dividing, mosaic_region, say_once

# f32 bytes of one (rows, D) block of the kernel: 128 rows at D = 2048
BLOCK_BYTES = 1 << 20


def _rms_reference(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_pallas(x: jax.Array, weight: jax.Array, eps: float,
                interpret: bool = False) -> jax.Array:
    from jax.experimental import pallas as pl

    orig_shape = x.shape
    d = orig_shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    # TPU tiling: the second-to-minor block dim must be 8-divisible or
    # equal the array dim. Few rows (decode slots, short prompts) → one
    # block equal to the array dim; otherwise fixed blocks of at most
    # BLOCK_BYTES in f32 (the kernel's working precision), with rows
    # padded up to a multiple (rows are independent, so padding is sliced
    # off harmlessly). 256 x 4096 f32 blocks, double-buffered in and out
    # beside the f32 temporaries, were refused by the v5e compiler for
    # more scoped VMEM than a kernel may use.
    block_rows = max(8, BLOCK_BYTES // (4 * d) // 8 * 8)
    if rows <= block_rows:
        block_rows, padded = rows, rows
    else:
        padded = rows + ((-rows) % block_rows)
        if padded != rows:
            x2 = jnp.pad(x2, ((0, padded - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(padded // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, d), x.dtype,
                                       vma=jax.typeof(x).vma),
        interpret=interpret,
        name="tony_rmsnorm",
    )(x2, weight)
    return out[:rows].reshape(orig_shape)


def _row_shard_spec(shape) -> jax.P:
    """How the ambient mesh may split (..., D) activations with every row
    whole on one device: the leading (batch) dim over the batch axes that
    divide it, a middle (sequence) dim over sp. Any such split is local
    for a row-wise op; D stays whole."""
    mesh = jax.sharding.get_abstract_mesh()
    spec = [None] * len(shape)
    if len(shape) >= 2:
        spec[0] = batch_axes_dividing(shape[0]) or None
    if len(shape) >= 3 and mesh.shape.get("sp", 1) > 1 \
            and shape[1] % mesh.shape["sp"] == 0:
        spec[1] = "sp"
    return jax.P(*spec)


def _rms_forward(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    # the branch is picked at LOWERING time, like flash attention's
    # (ops/attention.py): never by enumerating jax.devices() while
    # tracing, which made every CPU-mesh test and every AOT compile for a
    # described TPU take the jnp reference in silence
    def local(xs, ws):
        return lax.platform_dependent(
            xs, ws, tpu=functools.partial(_rms_pallas, eps=eps),
            default=functools.partial(_rms_reference, eps=eps))

    region = mosaic_region()
    if region == "local":
        return local(x, weight)
    if region == "partial":
        # inside a pipeline stage: no Mosaic call can lower there
        # (ops/vma.py mosaic_region); plain jnp partitions fine
        say_once("rms_norm inside a partial-manual region: the Pallas "
                 "kernel cannot lower here, using the jnp reference")
        return _rms_reference(x, weight, eps)
    # top level of a mesh: XLA cannot partition a Mosaic call, so the
    # kernel runs on local row shards under a shard_map over every axis
    rows = _row_shard_spec(x.shape)
    return jax.shard_map(
        local, in_specs=(rows, jax.P()), out_specs=rows,
        axis_names=set(jax.sharding.get_abstract_mesh().axis_names),
    )(x, weight)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6) -> jax.Array:
    """y = x * rsqrt(mean(x^2) + eps) * weight, over the last dim."""
    return _rms_forward(x, weight, eps)


def _rms_fwd(x, weight, eps):
    return rms_norm(x, weight, eps), (x, weight)


def _rms_bwd(eps, residuals, g):
    x, weight = residuals
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = weight.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = xf * rstd
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    gw = gf * wf
    # d/dx of x * rsqrt(mean(x^2)+eps): gw*rstd - xhat * mean(gw*xhat) * rstd
    dx = rstd * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    return dx.astype(x.dtype), dw.astype(weight.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)
