"""Flash attention: pallas TPU forward kernel + blockwise backward.

Design (pallas_guide.md patterns):
- grid = (batch*heads, q_blocks); each program streams K/V blocks through
  VMEM with an online-softmax accumulator held in registers — O(S) memory
  instead of the O(S^2) score matrix.
- blocks are MXU-shaped (128 x head_dim) and matmuls accumulate in f32 via
  `preferred_element_type` so bf16 inputs keep f32 softmax statistics.
- causal masking skips fully-masked K blocks: the K-loop upper bound is
  derived from the Q block index, so the kernel does ~half the FLOPs of the
  dense version at long context.
- backward on TPU: two pallas kernels (dQ over K blocks; dK/dV over Q
  blocks) with flash-style recompute from the saved lse — causal skipping
  bounds each loop at/after the diagonal. CPU path: the same math as a
  blockwise lax.scan (O(S*Bk) memory), also the parity oracle for the
  kernels in interpret mode.

Dispatch: TPU -> compiled pallas; other platforms -> the same blockwise math
in pure jnp (CPU tests, virtual-device meshes). `reference_attention` is the
trusted O(S^2) parity oracle.
"""

from __future__ import annotations

import functools
import math
import os
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# vma plumbing for check_vma=True shard_map contexts (the pp pipeline):
# pallas out_shapes and scan inits need explicit varying annotations
from tony_tpu.ops.vma import (
    batch_axes_dividing, match_vma as _like_vma, mosaic_region, say_once,
)

# bigger q blocks amortize the K/V stream and feed the MXU full tiles;
# 2048 blows compile. Which tile is fastest on the chip is not measured
# (PERF.md; the benchmark runs this one).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
# inside the Pallas kernels a sequence no longer than one block is padded
# to a multiple of this (prompts of 3, 37, 129 tokens at serving admission)
SHORT_SEQ_ALIGN = 128

NEG_INF = -1e30


def _sds(shape, dtype, like):
    """pallas out_shape carrying `like`'s varying manual axes."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        sm_scale: Optional[float] = None) -> jax.Array:
    """O(S^2) oracle. q: (B, H, S, D); k/v: (B, Hkv, S, D) with H % Hkv == 0
    (GQA groups broadcast here)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    k, v = _gqa_broadcast(q, k, v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qlen, klen = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool), klen - qlen)
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# pallas forward kernel
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      block_k: int, seq_len: int, kv_len: int,
                      causal: bool, sm_scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    q = q_ref[0].astype(jnp.float32) * sm_scale          # (Bq, D)
    q_offset = qi * block_q

    num_kb = pl.cdiv(seq_len, block_k)
    if causal:
        # K blocks strictly above the diagonal contribute nothing
        num_kb_live = lax.div(q_offset + block_q + block_k - 1, block_k)
        num_kb_live = jnp.minimum(num_kb_live, num_kb)
    else:
        num_kb_live = num_kb

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)  # (Bq,Bk)
        cols = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            rows = q_offset + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_len < seq_len:       # padded K columns contribute nothing
            s = jnp.where(cols < kv_len, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)                   # (Bq,1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                       # (Bq,Bk)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p, v_blk,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    head_dim = q_ref.shape[2]
    init = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, head_dim), jnp.float32))
    m, l, acc = lax.fori_loop(0, num_kb_live, body, init)
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # lse block is (1, 1, Bq): TPU tiling needs the second-to-minor block
    # dim equal to the array dim, hence the singleton middle axis.
    lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _kv_row_map(h: int, hk: int):
    """Grid-row -> K/V-row index map for GQA: program i walks (batch-major)
    the b*h q-heads; its K/V live at row (batch * hk + group). The same map
    serves the equal-heads case (h == hk -> identity), so one kernel covers
    MHA and GQA without streaming repeated K/V bytes from HBM."""
    # guard here so BOTH pallas directions fail loud: on compiled TPU an
    # out-of-range index-map block clamps instead of raising
    assert h % hk == 0, (h, hk)
    rep = h // hk

    def row(i):
        return (i // h) * hk + (i % h) // rep

    return row


def _kernel_shard_axes(batch_dim: int, nh: int, nkv: int):
    """Mesh axes the flash kernels must be manually mapped over on a
    multi-chip mesh: batch over (dp, fsdp), heads over tp. A Mosaic
    custom call CANNOT be split by XLA's Auto partitioner ("Mosaic
    kernels cannot be automatically partitioned" — surfaced by the v5p
    AOT compile, tools/aot_8b.py), so the kernel runs inside a shard_map
    over exactly these axes with purely local shards; attention is
    embarrassingly parallel across batch and heads, so no collectives
    are introduced. Axes already Manual in the ambient context (sp/pp in
    the ring or pipeline paths) and axes that don't divide the operand
    dims are excluded."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return (), ()
    manual = mesh.manual_axes
    batch_axes = batch_axes_dividing(batch_dim)
    tp = mesh.shape.get("tp", 1)
    tp_axes = ("tp",) if (tp > 1 and "tp" not in manual
                          and nh % tp == 0 and nkv % tp == 0) else ()
    return batch_axes, tp_axes


def _shard_kernel_call(fn, args, n_in: int, n_out: int):
    """Run `fn(*args)` so the Mosaic kernel never needs Auto partitioning
    (ops/vma.py mosaic_region has the three regimes). At the top level of
    a mesh the WHOLE dispatch (pallas + blockwise branches) runs in a
    shard_map over EVERY mesh axis — batch dims ride (dp, fsdp), heads
    ride tp, all other axes are unmentioned in the specs (operands
    replicated over them, exactly the Auto semantics). This sits inside
    the custom_vjp rules, so AD never differentiates through the
    shard_map. Inside a PARTIAL manual region the blockwise branch is
    forced — plain jnp that the Auto partitioner splits fine. Correct
    everywhere; a perf (not correctness) cost limited to multi-chip
    pipeline stages, and said once in the log."""
    region = mosaic_region()
    if region == "local":
        return fn(*args)
    mesh = jax.sharding.get_abstract_mesh()
    if region == "partial":
        say_once(
            "flash attention inside a partial-manual region (manual "
            f"{sorted(mesh.manual_axes)} of {list(mesh.axis_names)}): the "
            "Pallas kernels cannot lower here, using the blockwise jnp "
            "path")
        return fn(*args, force="blockwise")
    q, k = args[0], args[1]
    batch_axes, tp_axes = _kernel_shard_axes(q.shape[0], q.shape[1],
                                             k.shape[1])
    spec = jax.P(batch_axes if batch_axes else None,
                 "tp" if tp_axes else None)
    f = jax.shard_map(
        fn, in_specs=(spec,) * n_in,
        out_specs=tuple(spec for _ in range(n_out)),
        axis_names=set(mesh.axis_names))
    return f(*args)


def _tile_pad(s: int, block_q: int, block_k: int) -> int:
    """Rows to add so that a sequence ONE block covers is whole tiles.
    Mosaic wants (8, 128) tiles: a 3- or 37-row block is refused ("cannot
    statically prove that index in dimension 1 is a multiple of 8"), which
    interpret mode and the blockwise path never see. Only the kernels pad
    — the jnp path keeps the raw length — and the padded K columns are
    masked by kv_len, the padded Q rows sliced off by the callers below.
    Longer sequences arrive block-divisible from flash_attention."""
    return (-s) % SHORT_SEQ_ALIGN if s <= min(block_q, block_k) else 0


def _pad_seq(x, pad: int):
    widths = [(0, 0)] * x.ndim
    widths[2] = (0, pad)
    return jnp.pad(x, widths)


def _pallas_forward(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                    kv_len=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401

    b, h, s, d = q.shape
    pad = _tile_pad(s, block_q, block_k)
    if pad:
        out, lse = _pallas_forward(
            _pad_seq(q, pad), _pad_seq(k, pad), _pad_seq(v, pad), causal,
            sm_scale, block_q, block_k, interpret,
            kv_len=s if kv_len is None else kv_len)
        return out[:, :, :s], lse[:, :, :s]
    hk = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    assert h % hk == 0, (h, hk)
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(b * hk, s, d)
    vf = v.reshape(b * hk, s, d)
    kv_row = _kv_row_map(h, hk)
    grid = (bh, s // block_q)
    kernel = functools.partial(
        _flash_fwd_kernel, block_k=block_k, seq_len=s,
        kv_len=kv_len if kv_len is not None else s, causal=causal,
        sm_scale=sm_scale)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (kv_row(i), 0, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (kv_row(i), 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            _sds((bh, s, d), q.dtype, q),
            _sds((bh, 1, s), jnp.float32, q),
        ],
        interpret=interpret,
        name="tony_flash_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


# ---------------------------------------------------------------------------
# blockwise jnp path (CPU fallback fwd + the shared bwd)
# ---------------------------------------------------------------------------

def _gqa_broadcast(q, k, v):
    """Repeat K/V heads up to Q's head count (non-pallas paths; the pallas
    kernels read the narrow K/V directly via the grid index map)."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return k, v


def _gqa_reduce(dk, dv, hk: int):
    """Sum per-q-head K/V grads over each GQA group -> (B, Hkv, S, D)."""
    b, h, s, d = dk.shape
    if h == hk:
        return dk, dv
    rep = h // hk
    return (dk.reshape(b, hk, rep, s, d).sum(axis=2),
            dv.reshape(b, hk, rep, s, d).sum(axis=2))


def _blockwise_forward(q, k, v, causal, sm_scale, block_k, kv_len=None):
    """Same online-softmax math as the kernel, expressed as a lax.scan over
    K blocks — O(S*Bk) memory."""
    k, v = _gqa_broadcast(q, k, v)
    b, h, s, d = q.shape
    block_k = min(block_k, s)
    assert s % block_k == 0
    nkb = s // block_k
    qf = q.astype(jnp.float32) * sm_scale
    kb = k.reshape(b, h, nkb, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nkb, block_k, d).transpose(2, 0, 1, 3, 4)
    rows = lax.broadcasted_iota(jnp.int32, (s, block_k), 0)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        kb_i, (k_blk, v_blk) = inp
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32),
                           preferred_element_type=jnp.float32)
        cols = kb_i * block_k + lax.broadcasted_iota(
            jnp.int32, (s, block_k), 1)
        if causal:
            s_blk = jnp.where((rows >= cols)[None, None], s_blk, NEG_INF)
        if kv_len is not None and kv_len < s:
            s_blk = jnp.where((cols < kv_len)[None, None], s_blk, NEG_INF)
        m_cur = jnp.max(s_blk, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s_blk - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (_like_vma(jnp.full((b, h, s, 1), NEG_INF, jnp.float32), q),
            _like_vma(jnp.zeros((b, h, s, 1), jnp.float32), q),
            _like_vma(jnp.zeros((b, h, s, d), jnp.float32), q))
    (m, l, acc), _ = lax.scan(body, init, (jnp.arange(nkb), (kb, vb)))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l).astype(q.dtype)
    lse = (m + jnp.log(l))[..., 0]
    return out, lse


def _blockwise_backward(q, k, v, out, lse, g, causal, sm_scale, block_k,
                        kv_len=None):
    """Flash backward: recompute P per K block from saved lse
    (dS = P * (dP - D), D = rowsum(dO * O))."""
    hk = k.shape[1]
    k, v = _gqa_broadcast(q, k, v)
    b, h, s, d = q.shape
    block_k = min(block_k, s)
    nkb = s // block_k
    qf = q.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)        # (B,H,S)
    kb = k.reshape(b, h, nkb, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nkb, block_k, d).transpose(2, 0, 1, 3, 4)
    rows = lax.broadcasted_iota(jnp.int32, (s, block_k), 0)

    def body(dq, inp):
        kb_i, (k_blk, v_blk) = inp
        k_f = k_blk.astype(jnp.float32)
        v_f = v_blk.astype(jnp.float32)
        s_blk = jnp.einsum("bhqd,bhkd->bhqk", qf, k_f) * sm_scale
        cols = kb_i * block_k + lax.broadcasted_iota(
            jnp.int32, (s, block_k), 1)
        if causal:
            s_blk = jnp.where((rows >= cols)[None, None], s_blk, NEG_INF)
        if kv_len is not None and kv_len < s:
            s_blk = jnp.where((cols < kv_len)[None, None], s_blk, NEG_INF)
        p = jnp.exp(s_blk - lse[..., None])                       # (B,H,S,Bk)
        dv_blk = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v_f)
        ds = p * (dp - delta[..., None]) * sm_scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, k_f)
        dk_blk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq, (dk_blk, dv_blk)

    dq0 = _like_vma(jnp.zeros((b, h, s, d), jnp.float32), q)
    dq, (dk_blocks, dv_blocks) = lax.scan(
        body, dq0, (jnp.arange(nkb), (kb, vb)))
    dk = dk_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)
    dv = dv_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, s, d)
    dk, dv = _gqa_reduce(dk, dv, hk)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# pallas backward kernels (flash-style recompute; dQ and dKV separately so
# each accumulator lives in registers with a clean parallel grid)
# ---------------------------------------------------------------------------

def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, seq_len: int, kv_len: int,
                         causal: bool, sm_scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    block_q = q_ref.shape[1]
    q_offset = qi * block_q
    q = q_ref[0].astype(jnp.float32)                      # (Bq, D)
    g = g_ref[0].astype(jnp.float32)                      # (Bq, D)
    lse = lse_ref[0, 0][:, None]                          # (Bq, 1)
    delta = delta_ref[0, 0][:, None]                      # (Bq, 1)

    num_kb = pl.cdiv(seq_len, block_k)
    if causal:
        num_kb_live = jnp.minimum(
            lax.div(q_offset + block_q + block_k - 1, block_k), num_kb)
    else:
        num_kb_live = num_kb

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32) * sm_scale
        cols = kb * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            rows = q_offset + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_len < seq_len:
            s = jnp.where(cols < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse)                              # (Bq, Bk)
        dp = jnp.dot(g, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        return dq + jnp.dot(ds, k_blk, preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, num_kb_live, body,
                       jnp.zeros((block_q, q.shape[1]), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, seq_len: int,
                          kv_len: int, causal: bool, sm_scale: float):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    block_k = k_ref.shape[1]
    k_offset = ki * block_k
    k_blk = k_ref[0].astype(jnp.float32)                  # (Bk, D)
    v_blk = v_ref[0].astype(jnp.float32)                  # (Bk, D)

    num_qb = pl.cdiv(seq_len, block_q)
    if causal:
        # Q blocks strictly before this K block contribute nothing
        qb_start = lax.div(k_offset, block_q)
    else:
        qb_start = 0

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        g = g_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, pl.ds(qb * block_q, block_q)][:, None]
        s = jnp.dot(q, k_blk.T,
                    preferred_element_type=jnp.float32) * sm_scale
        cols = k_offset + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if causal:
            rows = qb * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(rows >= cols, s, NEG_INF)
        if kv_len < seq_len:
            s = jnp.where(cols < kv_len, s, NEG_INF)
        p = jnp.exp(s - lse)                              # (Bq, Bk)
        dv = dv + jnp.dot(p.T, g, preferred_element_type=jnp.float32)
        dp = jnp.dot(g, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    d = k_blk.shape[1]
    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, d), jnp.float32))
    dk, dv = lax.fori_loop(qb_start, num_qb, body, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _pallas_backward(q, k, v, out, lse, g, causal, sm_scale, block_q,
                     block_k, kv_len, interpret=False):
    from jax.experimental import pallas as pl

    b, h, s, d = q.shape
    pad = _tile_pad(s, block_q, block_k)
    if pad:
        # padded rows carry g = 0 (and out = 0, lse = 0), so they add
        # nothing to dK/dV, and their dQ rows are sliced off
        dq, dk, dv = _pallas_backward(
            *(_pad_seq(x, pad) for x in (q, k, v, out, lse, g)), causal,
            sm_scale, block_q, block_k, s if kv_len is None else kv_len,
            interpret=interpret)
        return dq[:, :, :s], dk[:, :, :s], dv[:, :, :s]
    hk = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    bh = b * h
    qf = q.reshape(bh, s, d)
    kf = k.reshape(b * hk, s, d)
    vf = v.reshape(b * hk, s, d)
    kv_row = _kv_row_map(h, hk)
    gf = g.reshape(bh, s, d)
    lse_f = lse.reshape(bh, 1, s)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, s)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k, seq_len=s,
                          kv_len=kv_len if kv_len is not None else s,
                          causal=causal, sm_scale=sm_scale),
        grid=(bh, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (kv_row(i), 0, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (kv_row(i), 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=_sds((bh, s, d), q.dtype, q),
        interpret=interpret,
        name="tony_flash_bwd_dq",
    )(qf, kf, vf, gf, lse_f, delta)

    # dK/dV per q-head (clean parallel grid, K/V streamed once per program
    # via the same row map), group-reduced to the narrow GQA layout after
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q, seq_len=s,
                          kv_len=kv_len if kv_len is not None else s,
                          causal=causal, sm_scale=sm_scale),
        grid=(bh, s // block_k),
        in_specs=[
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (kv_row(i), j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (kv_row(i), j, 0)),
            pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            _sds((bh, s, d), k.dtype, k),
            _sds((bh, s, d), v.dtype, k),
        ],
        interpret=interpret,
        name="tony_flash_bwd_dkv",
    )(qf, kf, vf, gf, lse_f, delta)

    dk, dv = _gqa_reduce(dk.reshape(b, h, s, d), dv.reshape(b, h, s, d), hk)
    return dq.reshape(b, h, s, d), dk, dv


# ---------------------------------------------------------------------------
# core op with custom VJP (always sees block-divisible shapes + kv_len mask)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, sm_scale, block_q, block_k, kv_len):
    out, _ = _forward(q, k, v, causal, sm_scale, block_q, block_k, kv_len)
    return out


# Platform dispatch happens at LOWERING time via lax.platform_dependent —
# never by enumerating jax.devices() at trace time (that forces backend
# initialisation as a trace side effect and breaks AOT lowering for a
# platform other than the default one).
# TONY_FLASH_FORCE={pallas,blockwise} pins a branch and
# TONY_FLASH_INTERPRET=1 runs the REAL kernels interpreted on the CPU
# through every dispatch layer (segmentation, ring, GQA). Both are test
# and debugging switches: a worker shows which branch it compiled by the
# kernel names in its lowered step (KERNEL_NAMES, kernel_counts), and
# chip_smoke.py refuses to run with either set.
_FORCE = os.environ.get("TONY_FLASH_FORCE", "")
_INTERPRET = os.environ.get("TONY_FLASH_INTERPRET", "") == "1"

# pallas_call names as they appear in a lowered or compiled program
KERNEL_NAMES = ("tony_flash_fwd", "tony_flash_bwd_dq", "tony_flash_bwd_dkv",
                "tony_rmsnorm")


def kernel_counts(program_text: str) -> dict[str, int]:
    """Pallas TPU kernel call sites in a lowered
    (`jit(f).lower(...).as_text()`) or compiled (`.compile().as_text()`)
    program, by kernel name, plus the total under "tpu_custom_call". All
    zero on a program that took the jnp branches — which is how a worker
    shows what it is about to run."""
    counts = {name: len(re.findall(
        rf'kernel_name = "{name}"|%{name}[.\d]* = [^=]*? custom-call\(',
        program_text)) for name in KERNEL_NAMES}
    counts["tpu_custom_call"] = len(re.findall(
        r'@tpu_custom_call|custom_call_target="tpu_custom_call"',
        program_text))
    return counts


# Largest LOCAL sequence whose whole K/V rows the pallas kernels may
# stage in VMEM: each grid program holds full (s, d) K and V tiles, and
# at s = 32768, d = 128 that is 2 x 8 MB (x2 double-buffered) against the
# 16 MB scoped-vmem budget — the v5p AOT compile of a 128k-context
# fsdp=4 x sp=4 mesh failed exactly there. Longer local sequences are
# split into <=LONG_SEQ_CHUNK segments and every (q_i, k_j) pair runs
# the standard kernel (dense below the diagonal, causal on it, skipped
# above), merged by the exact normalized-partial lse rule — the ring's
# per-chunk math (parallel/ring.py) applied locally.
LONG_SEQ_CHUNK = int(os.environ.get("TONY_FLASH_MAX_CHUNK", 8192))
_MAX_SEGMENTS = 16   # past this, the O(n^2) unrolled pairs bloat the
                     # program; the blockwise path handles it instead


def _segments(s: int) -> int:
    """Segment count for a local sequence, 0 = no segmentation."""
    if s <= LONG_SEQ_CHUNK or s % LONG_SEQ_CHUNK != 0:
        return 0
    n = s // LONG_SEQ_CHUNK
    return n if n <= _MAX_SEGMENTS else 0


def _say_unsegmentable(s: int) -> None:
    say_once(
        f"flash attention at local sequence {s}: longer than "
        f"{LONG_SEQ_CHUNK} and not {LONG_SEQ_CHUNK} x (2..{_MAX_SEGMENTS}),"
        " so the Pallas kernels are not used, using the blockwise jnp path")


def _seg_kv_len(kv_len, j: int, seg: int):
    """The j-th K segment's live-column count (None = full)."""
    return seg if kv_len is None else min(max(kv_len - j * seg, 0), seg)


def merge_partials(out_acc, lse_acc, o_c, l_c):
    """Exact online merge of normalized attention partials: new weights
    from the joint logsumexp; a skipped/empty partial (lse = -inf) is a
    strict no-op. Shared by the ring (parallel/ring.py) and the local
    long-sequence segmentation so the numerically delicate rule lives
    once."""
    lse_new = jnp.logaddexp(lse_acc, l_c)
    out_new = (out_acc * jnp.exp(lse_acc - lse_new)[..., None]
               + o_c.astype(jnp.float32)
               * jnp.exp(l_c - lse_new)[..., None])
    return out_new, lse_new


def _segmented_forward(one, q, k, v, causal, kv_len, eff):
    """(out, lse) over VMEM-sized K/V segments; `one` runs the standard
    kernel for a single (q_i, k_j) pair."""
    b, h, s, d = q.shape
    seg = LONG_SEQ_CHUNK
    n = s // seg
    outs, lses = [], []
    for i in range(n):
        qi = q[:, :, i * seg:(i + 1) * seg]
        out_acc = jnp.zeros((b, h, seg, d), jnp.float32)
        lse_acc = jnp.full((b, h, seg), NEG_INF, jnp.float32)
        for j in range(i + 1 if causal else n):
            kvl = _seg_kv_len(kv_len, j, seg)
            if kvl == 0:
                continue
            kj = k[:, :, j * seg:(j + 1) * seg]
            vj = v[:, :, j * seg:(j + 1) * seg]
            o_c, l_c = one(qi, kj, vj, causal and j == i,
                           kvl if kvl < seg else None, eff)
            out_acc, lse_acc = merge_partials(out_acc, lse_acc, o_c, l_c)
        outs.append(out_acc.astype(q.dtype))
        lses.append(lse_acc)
    return jnp.concatenate(outs, axis=2), jnp.concatenate(lses, axis=2)


def _forward(q, k, v, causal, sm_scale, block_q, block_k, kv_len):
    def one(qs, ks, vs, causal_, kv_len_, eff):
        pallas_fwd = functools.partial(
            _pallas_forward, causal=causal_, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=_INTERPRET,
            kv_len=kv_len_)
        blockwise_fwd = functools.partial(
            _blockwise_forward, causal=causal_, sm_scale=sm_scale,
            block_k=block_k, kv_len=kv_len_)
        if eff == "pallas":
            return pallas_fwd(qs, ks, vs)
        if eff == "blockwise":
            return blockwise_fwd(qs, ks, vs)
        return lax.platform_dependent(qs, ks, vs, tpu=pallas_fwd,
                                      default=blockwise_fwd)

    def dispatch(qs, ks, vs, force=""):
        eff = force or _FORCE
        s = qs.shape[2]
        # segmentation exists purely for the pallas kernels' VMEM
        # budget; the blockwise branch streams any length in one call
        if eff != "blockwise" and _segments(s):
            return _segmented_forward(one, qs, ks, vs, causal, kv_len,
                                      eff)
        if s > LONG_SEQ_CHUNK and eff != "pallas":
            # unsegmentable long sequence (non-multiple or too many
            # segments): the pallas kernels would blow scoped VMEM
            # staging full K/V rows — the blockwise path is the one
            # that scales
            _say_unsegmentable(s)
            eff = "blockwise"
        return one(qs, ks, vs, causal, kv_len, eff)

    return _shard_kernel_call(dispatch, (q, k, v), 3, 2)


# the custom VJP's residuals, by the names `_fwd_rule` gives them
FLASH_RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_out",
                        "flash_lse")


def _fwd_rule(q, k, v, causal, sm_scale, block_q, block_k, kv_len):
    out, lse = _forward(q, k, v, causal, sm_scale, block_q, block_k, kv_len)
    # all five residuals are named, so a `save_only_these_names(*
    # FLASH_RESIDUAL_NAMES)` remat policy keeps exactly what the backward
    # kernels read: the replay then re-runs neither the fwd kernel nor
    # what made q, k and v (three projections, RoPE on two, three head
    # transposes). Outside a jax.checkpoint a name is the identity, and a
    # serving program never reaches this rule (it runs under
    # differentiation alone). Bytes and what they buy: PERF.md §3, "the
    # replay's rule"
    from jax.ad_checkpoint import checkpoint_name
    q, k, v, out, lse = (checkpoint_name(x, name) for x, name in zip(
        (q, k, v, out, lse), FLASH_RESIDUAL_NAMES))
    return out, (q, k, v, out, lse)


def _backward_dispatch(q, k, v, out, lse, g, causal, sm_scale, block_q,
                       block_k, kv_len):
    """The platform/TONY_FLASH_FORCE dispatch for the flash backward —
    shared by the custom-VJP rule here and the ring (parallel/ring.py)
    per-chunk backward, so a forced branch pins BOTH directions."""
    def one(qs, ks, vs, outs, lses, gs, causal_, kv_len_, eff):
        pallas_bwd = lambda *a: _pallas_backward(    # noqa: E731
            *a, causal_, sm_scale, block_q, block_k, kv_len_,
            interpret=_INTERPRET)
        blockwise_bwd = lambda *a: _blockwise_backward(    # noqa: E731
            *a, causal_, sm_scale, block_k, kv_len=kv_len_)
        args = (qs, ks, vs, outs, lses, gs)
        if eff == "pallas":
            return pallas_bwd(*args)
        if eff == "blockwise":
            return blockwise_bwd(*args)
        return lax.platform_dependent(*args, tpu=pallas_bwd,
                                      default=blockwise_bwd)

    def dispatch(qs, ks, vs, outs, lses, gs, force=""):
        eff = force or _FORCE
        n = 0 if eff == "blockwise" else _segments(qs.shape[2])
        if not n:
            if qs.shape[2] > LONG_SEQ_CHUNK and eff != "pallas":
                _say_unsegmentable(qs.shape[2])   # see the forward dispatch
                eff = "blockwise"
            return one(qs, ks, vs, outs, lses, gs, causal, kv_len, eff)
        # segmented backward: every (q_i, k_j) pair's standard flash
        # backward against q_i's GLOBAL out/lse/g is exact (the ring's
        # per-chunk decomposition); dq accumulates per q segment, dK/dV
        # per k segment
        seg = LONG_SEQ_CHUNK
        dq_segs = []
        dk_acc = jnp.zeros(ks.shape, jnp.float32)
        dv_acc = jnp.zeros(vs.shape, jnp.float32)
        for i in range(n):
            sl_i = slice(i * seg, (i + 1) * seg)
            dq_i = jnp.zeros(qs[:, :, sl_i].shape, jnp.float32)
            for j in range(i + 1 if causal else n):
                kvl = _seg_kv_len(kv_len, j, seg)
                if kvl == 0:
                    continue
                sl_j = slice(j * seg, (j + 1) * seg)
                dq_c, dk_c, dv_c = one(
                    qs[:, :, sl_i], ks[:, :, sl_j], vs[:, :, sl_j],
                    outs[:, :, sl_i], lses[:, :, sl_i], gs[:, :, sl_i],
                    causal and j == i, kvl if kvl < seg else None, eff)
                dq_i = dq_i + dq_c.astype(jnp.float32)
                dk_acc = dk_acc.at[:, :, sl_j].add(
                    dk_c.astype(jnp.float32))
                dv_acc = dv_acc.at[:, :, sl_j].add(
                    dv_c.astype(jnp.float32))
            dq_segs.append(dq_i.astype(qs.dtype))
        return (jnp.concatenate(dq_segs, axis=2),
                dk_acc.astype(ks.dtype), dv_acc.astype(vs.dtype))

    return _shard_kernel_call(dispatch, (q, k, v, out, lse, g), 6, 3)


def _bwd_rule(causal, sm_scale, block_q, block_k, kv_len, residuals, g):
    q, k, v, out, lse = residuals
    return _backward_dispatch(q, k, v, out, lse, g, causal, sm_scale,
                              block_q, block_k, kv_len)


_flash_core.defvjp(_fwd_rule, _bwd_rule)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """Memory-efficient attention. q: (B, H, S, D); k/v: (B, Hkv, S, D)
    with H % Hkv == 0 — GQA is native: the pallas kernels stream the narrow
    K/V via the grid index map (no repeated K/V bytes in HBM), and dK/dV
    come back in the narrow layout. Sequence lengths that don't divide the
    block size are zero-padded; padded K columns are masked out inside the
    kernels and padded Q rows sliced off (gradients flow through pad/slice,
    so training works at any length)."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    # resolved at call time (not def time) so tuning harnesses can sweep
    # the module-level defaults without threading args through every model
    if block_q is None:
        block_q = DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = DEFAULT_BLOCK_K
    s = q.shape[2]
    if s <= min(block_q, block_k):
        # kernels clamp both block sizes down to s (and round a
        # one-block sequence up to whole tiles themselves: _tile_pad)
        pad = 0
    else:
        # padded length must divide by BOTH block sizes after the kernels'
        # min(block, s) clamps; a multiple of lcm(bq, bk) >= max(bq, bk)
        # satisfies every case (each original block then divides it)
        pad = (-s) % math.lcm(block_q, block_k)
    if pad == 0:
        return _flash_core(q, k, v, causal, sm_scale, block_q, block_k, s)
    widths = ((0, 0), (0, 0), (0, pad), (0, 0))
    out = _flash_core(jnp.pad(q, widths), jnp.pad(k, widths),
                      jnp.pad(v, widths), causal, sm_scale, block_q,
                      block_k, s)
    return out[:, :, :s]
