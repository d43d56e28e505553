"""Sharded train step.

One jitted function does forward, backward, and optimizer update; under an
ambient mesh (jax.set_mesh) XLA inserts the data-parallel gradient
reduce-scatters and FSDP all-gathers from the shardings alone — no explicit
collectives, per the scaling-book recipe. Buffers are donated so params and
optimizer state update in place in HBM.

`grad_accum > 1` adds microbatch gradient accumulation: the global batch's
leading dim is split into `grad_accum` slices, a `lax.scan` accumulates
gradients (f32 by default — one accumulator tree, no per-micro activation
growth since each microbatch's backward completes inside its scan step),
and ONE optimizer update applies the mean. This is the standard big-model
lever when the per-step batch doesn't fit HBM but pipeline parallelism
isn't warranted. The microbatch axis is scanned, not vmapped, precisely so
peak activation memory stays that of a single microbatch.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from jax import lax


class AnnotatedStep:
    """Wraps a step callable so every invocation runs under
    `jax.profiler.StepTraceAnnotation` with an auto-incrementing
    `step_num` — XProf then attributes host stalls (input waits, sync
    points) to the exact train step they delayed. The counter is plain
    host state: a resuming trainer re-seats it (`step_num = resume_step`)
    so trace step numbers line up with training steps across retries."""

    def __init__(self, fn: Callable, name: str = "train_step",
                 step_num: int = 0):
        self._fn = fn
        self._name = name
        self.step_num = step_num

    def __call__(self, *args, **kwargs):
        with jax.profiler.StepTraceAnnotation(self._name,
                                              step_num=self.step_num):
            out = self._fn(*args, **kwargs)
        self.step_num += 1
        return out


def make_train_step(loss_fn: Callable[..., jax.Array],
                    optimizer: optax.GradientTransformation,
                    jit: bool = True,
                    grad_accum: int = 1,
                    accum_dtype: Any = jnp.float32,
                    emit_accum_dtype: bool = False,
                    annotate: bool = False) -> Callable:
    """loss_fn(params, batch) -> scalar. Returns
    train_step(params, opt_state, batch) -> (params, opt_state, loss).

    With grad_accum=N, every array in `batch` must have a leading dim
    divisible by N; the returned loss is the mean over microbatches.
    The accumulated mean gradient is cast back to the param dtype by
    default (optax type promotion would otherwise upcast the params on
    apply); pass emit_accum_dtype=True when the optimizer keeps its own
    higher-precision state (train/precision.py with_f32_master) so the
    f32-accumulated mean is not quantized at the interface.

    annotate=True wraps the returned callable in AnnotatedStep so each
    dispatch carries an XProf StepTraceAnnotation (hot-loop overlap
    tracing, docs/HOTLOOP.md)."""

    if grad_accum <= 1:
        def loss_and_grads(params, batch):
            return jax.value_and_grad(loss_fn)(params, batch)
    else:
        from tony_tpu.parallel.sharding import constrain

        def _batch_shards() -> int:
            """Devices the batch dim is sharded over under the ambient
            mesh (dp*fsdp), 1 when unmeshed."""
            mesh = jax.sharding.get_abstract_mesh()
            if not mesh.axis_names:
                return 1
            shape = dict(mesh.shape)
            return shape.get("dp", 1) * shape.get("fsdp", 1)

        def split(leaf):
            b = leaf.shape[0]
            if b % grad_accum != 0:
                raise ValueError(
                    f"batch dim {b} not divisible by grad_accum="
                    f"{grad_accum}")
            mb = b // grad_accum
            shards = _batch_shards()
            if mb % shards != 0:
                raise ValueError(
                    f"microbatch dim {mb} (= batch {b} / grad_accum "
                    f"{grad_accum}) must divide by the dp*fsdp shard "
                    f"count {shards}, or devices idle every scan step")
            # STRIDED split (microbatch i = rows i, i+accum, ...), not a
            # contiguous one: each device's contiguous batch shard then
            # contributes equally to every microbatch, so the constraint
            # below reshards nothing. Composition is irrelevant to the
            # averaged gradient.
            leaf = leaf.reshape((mb, grad_accum) + leaf.shape[1:])
            leaf = jnp.moveaxis(leaf, 1, 0)
            # scan (micro) axis replicated, batch stays on (dp, fsdp)
            return constrain(leaf, (None, "batch")
                             + (None,) * (leaf.ndim - 2))

        def loss_and_grads(params, batch):
            micro = jax.tree.map(split, batch)

            def body(carry, mb):
                loss_sum, grad_acc = carry
                loss, grads = jax.value_and_grad(loss_fn)(params, mb)
                grad_acc = jax.tree.map(
                    lambda a, g: a + g.astype(accum_dtype), grad_acc, grads)
                return (loss_sum + loss.astype(jnp.float32), grad_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            (loss_sum, grad_sum), _ = lax.scan(
                body, (jnp.zeros((), jnp.float32), zeros), micro)
            grads = jax.tree.map(
                lambda g, p: (g / grad_accum if emit_accum_dtype
                              else (g / grad_accum).astype(p.dtype)),
                grad_sum, params)
            return loss_sum / grad_accum, grads

    def train_step(params: Any, opt_state: Any, batch: Any):
        loss, grads = loss_and_grads(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    if jit:
        train_step = jax.jit(train_step, donate_argnums=(0, 1))
    if annotate:
        train_step = AnnotatedStep(train_step)
    return train_step


def make_eval_step(loss_fn: Callable[..., jax.Array],
                   jit: bool = True) -> Callable:
    def eval_step(params: Any, batch: Any) -> jax.Array:
        return loss_fn(params, batch)

    return jax.jit(eval_step) if jit else eval_step
