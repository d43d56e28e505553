"""Fused chunked softmax cross-entropy for large-vocab LM heads.

The unfused path materializes logits (B, S, V) in f32 — 2.1 GB at
llama3_1b_proxy bench shapes (B4 x S4096 x V32k) — plus the same again for
dlogits in the backward, and keeps softmax statistics as autodiff residuals.
On a 16 GB v5e that HBM is the binding constraint on batch size.

This op never materializes more than one sequence-chunk of logits at a time:

- forward: `lax.scan` over S-chunks; each chunk computes its logits tile on
  the MXU (bf16 operands, f32 accumulation), reduces it to logsumexp + the
  gold logit, and frees it. Residuals are just (x, w, targets) — O(B*S*D).
- backward: custom VJP re-runs the chunk matmul (the flash-attention trade:
  ~2*B*S*D*V extra FLOPs, <2% of a training step at 1B scale, for ~4 GB of
  freed HBM), forms `softmax - onehot` per chunk, and accumulates
  dx per-chunk and dw in an f32 scan carry.

The one-hot subtraction is written as an iota-compare-select so XLA fuses it
into the dlogits elementwise graph instead of materializing a (B, C, V)
one-hot.

Reference parity: the reference is an orchestrator with no tensor math
(SURVEY.md §2.3); this belongs to the TPU compute plane that replaces the
reference's delegated-to-TensorFlow data path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.parallel.sharding import constrain


def _chunk_logits(x_c: jax.Array, w: jax.Array) -> jax.Array:
    """(B, C, D) @ (D, V) -> (B, C, V) f32-accumulated logits tile."""
    return jnp.einsum("bcd,dv->bcv", x_c, w,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_xent_sum(x, w, targets, mask_start, chunk):
    """Sum over valid tokens of (logsumexp - gold logit).

    x: (B, S, D) hidden states (S divisible by `chunk`); w: (D, V);
    targets: (B, S) int32. Tokens at flat sequence index >= mask_start are
    padding and contribute zero.
    """
    loss, _ = _fwd(x, w, targets, mask_start, chunk)
    return loss


def _scan_chunks(x, targets, chunk):
    """(B, S, ...) -> leading-axis chunk stacks for lax.scan."""
    b, s, d = x.shape
    nc = s // chunk
    xs = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)     # (nc,B,C,D)
    ts = targets.reshape(b, nc, chunk).transpose(1, 0, 2)     # (nc,B,C)
    return xs, ts


def _valid_mask(chunk_idx, chunk, shape_bc, mask_start):
    """f32 mask of in-bounds tokens for one chunk; (B, C)."""
    pos = chunk_idx * chunk + lax.broadcasted_iota(jnp.int32, shape_bc, 1)
    return (pos < mask_start).astype(jnp.float32)


def _fwd(x, w, targets, mask_start, chunk):
    xs, ts = _scan_chunks(x, targets, chunk)

    def body(acc, inp):
        ci, x_c, t_c = inp
        logits = _chunk_logits(x_c, w)
        logz = jax.nn.logsumexp(logits, axis=-1)              # (B, C)
        gold = jnp.take_along_axis(logits, t_c[..., None],
                                   axis=-1)[..., 0]
        m = _valid_mask(ci, chunk, logz.shape, mask_start)
        return acc + jnp.sum((logz - gold) * m), None

    n = xs.shape[0]
    loss, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                       (jnp.arange(n), xs, ts))
    return loss, (x, w, targets)


def _bwd(mask_start, chunk, residuals, g):
    x, w, targets = residuals
    xs, ts = _scan_chunks(x, targets, chunk)

    def body(dw, inp):
        ci, x_c, t_c = inp
        logits = _chunk_logits(x_c, w)
        logz = jax.nn.logsumexp(logits, axis=-1)
        p = jnp.exp(logits - logz[..., None])                 # (B, C, V)
        coef = g * _valid_mask(ci, chunk, logz.shape, mask_start)
        # onehot as iota==target: XLA fuses the compare+select into the
        # elementwise dlogits graph — no (B, C, V) onehot in HBM
        vocab_iota = lax.broadcasted_iota(jnp.int32, p.shape, 2)
        onehot = (vocab_iota == t_c[..., None]).astype(jnp.float32)
        dlog = (p - onehot) * coef[..., None]                 # (B, C, V)
        dx_c = jnp.einsum("bcv,dv->bcd", dlog, w,
                          preferred_element_type=jnp.float32)
        dw = dw + jnp.einsum("bcd,bcv->dv", x_c, dlog,
                             preferred_element_type=jnp.float32)
        return dw, dx_c.astype(x.dtype)

    n = xs.shape[0]
    dw, dx_chunks = lax.scan(
        body, jnp.zeros(w.shape, jnp.float32), (jnp.arange(n), xs, ts))
    b, s, d = x.shape
    dx = dx_chunks.transpose(1, 0, 2, 3).reshape(b, s, d)
    dx = constrain(dx, ("batch", "seq", None))
    dw = constrain(dw, ("embed", "vocab"))
    # float0 zero (not bare None) for the integer targets primal: None is
    # accepted by jax>=0.9 but older versions require the typed zero —
    # keep the op version-portable
    dt = jax.custom_derivatives.zero_from_primal(targets)
    return dx, dw.astype(w.dtype), dt


_fused_xent_sum.defvjp(lambda x, w, t, ms, c: _fwd(x, w, t, ms, c), _bwd)


def fused_cross_entropy(x: jax.Array, w: jax.Array, targets: jax.Array,
                        chunk: int = 1024) -> jax.Array:
    """Mean next-token CE of an LM head, without materializing full logits.

    x: (B, S, D) final hidden states; w: (D, V) head weights;
    targets: (B, S) int. Equivalent to
    `cross_entropy(einsum('bsd,dv->bsv', x, w), targets)` up to f32
    accumulation order, at O(B*chunk*V) peak logits memory.
    """
    b, s, _ = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    n_valid = b * s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
    total = _fused_xent_sum(x, w, targets, s, chunk)
    return total / n_valid
