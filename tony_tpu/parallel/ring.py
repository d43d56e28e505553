"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

Long-context design (first-class per the build goals; absent from the
reference, SURVEY.md §5): the sequence dim is sharded over `sp`, each device
holds its local Q/K/V chunk, and K/V chunks rotate around the ring via
`lax.ppermute` — ICI neighbor traffic only, overlapping the blockwise
attention compute.

The per-chunk attention is the stack's flash kernel (ops/attention.py), so
the ring composes with pallas instead of materializing the O(S_local^2)
score matrix per step:

- forward: each ring step runs flash on (local Q, visiting K/V chunk) and
  merges the normalized partial (out_c, lse_c) into the running result by
  logsumexp weights — O(S_local * D) merge state, exact online softmax.
- backward (custom VJP, the flash-ring decomposition): the ring is just a
  distributed K-block loop, so the standard flash backward per chunk with
  the GLOBAL lse and delta = rowsum(dO * O) is exact. dQ accumulates
  locally; each visiting chunk's dK/dV partial rotates around the ring
  WITH its chunk, arriving home after n steps with every device's
  contribution summed.

Causality is decided per chunk pair: a K/V chunk entirely in this Q chunk's
future is skipped (lax.switch — no kernel launch, ~half the FLOPs at long
context), the diagonal chunk runs the causal kernel, past chunks run the
dense kernel.

Use inside shard_map, or via `ring_attention_sharded` which wraps the
shard_map with the canonical activation specs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import tony_tpu.ops.attention as _attn
from tony_tpu.ops.attention import (
    NEG_INF, _backward_dispatch, _forward, merge_partials,
)
from tony_tpu.ops.vma import match_vma


def _blocks(s: int) -> tuple[int, int]:
    """Largest standard block sizes that divide the local chunk (the flash
    entry clamps block > s down to s, so s itself always works). Reads the
    defaults off the module at call time so block-size sweeps that mutate
    them (tools/aot_rank.py) reach the ring path too."""
    bq, bk = _attn.DEFAULT_BLOCK_Q, _attn.DEFAULT_BLOCK_K
    for b in (bq, 256, 128):
        if s % b == 0:
            return min(b, bq), min(b, bk)
    return s, s


def _chunk_forward(q, k_cur, v_cur, mode, sm_scale):
    """One visiting chunk's flash forward. mode: 0 = dense (past chunk),
    1 = causal (diagonal), 2 = skip (future chunk, no kernel launch)."""
    bq, bk = _blocks(q.shape[2])

    def dense(q, k, v):
        return _forward(q, k, v, False, sm_scale, bq, bk, None)

    def diag(q, k, v):
        return _forward(q, k, v, True, sm_scale, bq, bk, None)

    def skip(q, k, v):
        b, h, s, d = q.shape
        return (match_vma(jnp.zeros((b, h, s, d), q.dtype), q),
                match_vma(jnp.full((b, h, s), NEG_INF, jnp.float32), q))

    return lax.switch(mode, (dense, diag, skip), q, k_cur, v_cur)


def _chunk_backward(q, k_cur, v_cur, out, lse, g, mode, sm_scale):
    """One visiting chunk's flash backward against the GLOBAL out/lse/delta
    (exact partial-softmax gradients; platform-dispatched like the fwd)."""
    bq, bk = _blocks(q.shape[2])

    def bwd(causal):
        def run(q, k, v, out, g):
            return _backward_dispatch(q, k, v, out, lse, g, causal,
                                      sm_scale, bq, bk, None)
        return run

    def skip(q, k, v, out, g):
        return (match_vma(jnp.zeros_like(q), q),
                match_vma(jnp.zeros_like(k), k),
                match_vma(jnp.zeros_like(v), v))

    return lax.switch(mode, (bwd(False), bwd(True), skip),
                      q, k_cur, v_cur, out, g)


def _rotate(x, axis_name: str, n: int):
    return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def _chunk_mode(src_idx, my_idx, causal: bool):
    """0 dense / 1 diagonal-causal / 2 skip, per global chunk position."""
    if not causal:
        return jnp.int32(0)
    return jnp.where(src_idx == my_idx, 1,
                     jnp.where(src_idx < my_idx, 0, 2)).astype(jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_core(q, k, v, axis_name, causal, sm_scale):
    out, _ = _ring_fwd_loop(q, k, v, axis_name, causal, sm_scale)
    return out


def _ring_fwd_loop(q, k, v, axis_name, causal, sm_scale):
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, s_local, d = q.shape

    def step(t, carry):
        out_acc, lse_acc, k_cur, v_cur = carry
        src_idx = (my_idx - t) % n           # who produced the chunk we hold
        mode = _chunk_mode(src_idx, my_idx, causal)
        out_c, lse_c = _chunk_forward(q, k_cur, v_cur, mode, sm_scale)
        # exact online merge of normalized partials (shared rule:
        # ops/attention.py merge_partials)
        out_acc, lse_new = merge_partials(out_acc, lse_acc, out_c, lse_c)
        # rotate K/V to the next neighbor; the last rotation is wasted but
        # keeps the loop body uniform (and XLA overlaps it with compute)
        return (out_acc, lse_new, _rotate(k_cur, axis_name, n),
                _rotate(v_cur, axis_name, n))

    init = (match_vma(jnp.zeros((b, h, s_local, d), jnp.float32), q),
            match_vma(jnp.full((b, h, s_local), NEG_INF, jnp.float32), q),
            k, v)
    out, lse, _, _ = lax.fori_loop(0, n, step, init)
    return out.astype(q.dtype), lse


def _ring_fwd_rule(q, k, v, axis_name, causal, sm_scale):
    out, lse = _ring_fwd_loop(q, k, v, axis_name, causal, sm_scale)
    return out, (q, k, v, out, lse)


def _ring_bwd_rule(axis_name, causal, sm_scale, residuals, g):
    q, k, v, out, lse = residuals
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)

    def step(t, carry):
        dq_acc, dk_acc, dv_acc, k_cur, v_cur = carry
        src_idx = (my_idx - t) % n
        mode = _chunk_mode(src_idx, my_idx, causal)
        dq_c, dk_c, dv_c = _chunk_backward(q, k_cur, v_cur, out, lse, g,
                                           mode, sm_scale)
        dq_acc = dq_acc + dq_c.astype(jnp.float32)
        # the visiting chunk's dK/dV partial travels WITH the chunk: after
        # n rotations both are home, the partial fully accumulated
        dk_acc = dk_acc + dk_c.astype(jnp.float32)
        dv_acc = dv_acc + dv_c.astype(jnp.float32)
        return (dq_acc, _rotate(dk_acc, axis_name, n),
                _rotate(dv_acc, axis_name, n),
                _rotate(k_cur, axis_name, n), _rotate(v_cur, axis_name, n))

    init = (match_vma(jnp.zeros(q.shape, jnp.float32), q),
            match_vma(jnp.zeros(k.shape, jnp.float32), k),
            match_vma(jnp.zeros(v.shape, jnp.float32), v),
            k, v)
    dq, dk, dv, _, _ = lax.fori_loop(0, n, step, init)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_core.defvjp(_ring_fwd_rule, _ring_bwd_rule)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp", causal: bool = False,
                   sm_scale: Optional[float] = None) -> jax.Array:
    """Call inside shard_map. q,k,v: local shards (B, H, S_local, D); the
    global sequence is the concatenation over `axis_name` in ring order."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    return _ring_core(q, k, v, axis_name, causal, sm_scale)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, causal: bool = False,
                           sm_scale: Optional[float] = None) -> jax.Array:
    """Standalone wrapper: manual over sp only (batch/heads dims stay Auto
    and keep whatever dp/fsdp/tp sharding the arrays carry)."""
    spec = P(None, None, "sp")
    f = jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name="sp",
                                          causal=causal, sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={"sp"})
    return f(q, k, v)
