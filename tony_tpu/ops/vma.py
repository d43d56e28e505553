"""Varying-manual-axes (vma) helpers for check_vma=True shard_map bodies.

Under a partial-manual `jax.shard_map` (e.g. the pp pipeline), scan
carries, fresh zeros, and pallas out_shapes must carry explicit vma
annotations or tracing fails with carry/type mismatches. This module is
the single implementation of the idempotent `lax.pcast(..., to="varying")`
promotions, shared by the pipeline schedule, the flash-attention kernels
and ring attention, and of `mosaic_region`, which tells a Pallas TPU
kernel how it may lower under the ambient mesh.

Lives under ops/ (a leaf package) on purpose: parallel/__init__ imports
ulysses which imports ops.attention, so an ops -> parallel import edge
would be a cycle whose failure depends on import order.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
from jax import lax

LOG = logging.getLogger(__name__)


@functools.cache
def say_once(msg: str) -> None:
    """One log line per distinct message per process: a kernel's give-way
    to its jnp path is decided at trace time, and a retrace must not
    repeat it."""
    LOG.warning(msg)


def varying_over(x: jax.Array, axis_name: str) -> jax.Array:
    """Mark `x` varying over one manual axis; idempotent."""
    if axis_name in jax.typeof(x).vma:
        return x
    return lax.pcast(x, (axis_name,), to="varying")


def match_vma(x: jax.Array, ref) -> jax.Array:
    """Give `x` the varying axes of `ref` (scan carries must match their
    outputs; a fresh zeros init is unvarying)."""
    want = jax.typeof(ref).vma - jax.typeof(x).vma
    return lax.pcast(x, tuple(want), to="varying") if want else x


def varying_full(x: jax.Array) -> jax.Array:
    """Mark `x` varying over EVERY manual axis of the ambient context —
    the right promotion for fresh constants (zeros inits, streams,
    replicated weights) entering a multi-axis manual region; the vjp of
    the inserted pcast is the psum that correctly reduces their
    cotangents."""
    want = (frozenset(jax.sharding.get_abstract_mesh().manual_axes)
            - jax.typeof(x).vma)
    return lax.pcast(x, tuple(sorted(want)), to="varying") if want else x


def batch_axes_dividing(dim: int) -> tuple:
    """The largest subset of the batch mesh axes (dp, fsdp) — those the
    ambient context holds Auto — whose product divides `dim`; () when none
    does. Not all-or-nothing: a small eval/decode batch on a big fsdp mesh
    should still shard over whatever divides (fsdp preferred — it's the
    bigger axis in every plan) instead of silently all-gathering the batch
    to every chip. Shared by the shard_maps that run the flash-attention
    and RMSNorm kernels on local shards."""
    mesh = jax.sharding.get_abstract_mesh()
    present = tuple(a for a in ("dp", "fsdp")
                    if mesh.shape.get(a, 1) > 1 and a not in mesh.manual_axes)
    options = [present] + [(a,) for a in reversed(present)]
    return next(
        (o for o in options
         if o and dim % math.prod(mesh.shape[a] for a in o) == 0), ())


def mosaic_region() -> str:
    """How a Mosaic (Pallas TPU) kernel may lower under the ambient mesh.
    XLA's Auto partitioner cannot split a Mosaic custom call, and jax's
    tpu_custom_call lowering wants the manual context to cover EVERY mesh
    axis, size-1 axes included. Three regimes:

    - "local": no mesh, one device, or a region already manual over all
      axes — the kernel lowers as a purely local call;
    - "wrap": top level of a mesh — the caller wraps the kernel in a
      shard_map over every mesh axis with purely local shards;
    - "partial": inside a region manual over SOME axes (a pipeline stage
      manual over pp / pp+sp), whose remaining Auto axes cannot legally
      host a nested manual computation — no kernel can run there."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names or mesh.size == 1:
        return "local"
    if not mesh.manual_axes:
        return "wrap"
    if set(mesh.manual_axes) == set(mesh.axis_names):
        return "local"
    return "partial"
