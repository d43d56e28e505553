"""Decode-side attention over the serving cache, reading only the rows a
slot attends to.

A window of W new positions a slot (W = 1 for a decode step, gamma + 1 for
the speculative verify) attends to the cached rows below the slot's attend
length and to the window's own rows, which are not in the cache yet. The
cache is a (L, B, Hkv, S, hd) buffer of which a decode step at 5 busy slots
of 32 needs a twentieth: the einsums this replaces read all S rows of all B
slots of every layer and cast them to float32 (5.6 of 17.0 ms a step in
`chat-steady`: PERF.md, PR 35).

The kernel `tony_decode_read` takes the WHOLE cache leaf in HBM — nothing
is sliced out of it, so no slab copy can come back (PERF.md, PR 27) — with
the layer index and the per-slot lengths scalar-prefetched. One program a
slot: rows [0, ceil(len / chunk) * chunk) of all Hkv heads are brought to
VMEM in the cache's own type, a chunk of rows at a time (one strided copy
of Hkv runs for K and one for V, double-buffered), under an online softmax
in float32. A slot of length 0 costs a grid step and moves nothing. An
int8 cache's row scales come with each chunk and are applied to the scores
(K) and to the probabilities (V): q . (s_i k_i) = s_i (q . k_i), so the
int8 rows are multiplied as they are stored, exactly.

Dispatch is by platform at lowering time, as in ops/attention.py: the
kernel on a TPU, the same arithmetic in plain jnp elsewhere (also the
tests' reference). TONY_FLASH_INTERPRET=1 runs the kernel interpreted on
the CPU. A budget that no chunk of whole tiles divides takes the jnp body
on every platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.ops.attention import _INTERPRET, NEG_INF

# rows of all Hkv heads one copy brings in, at most: 8 heads x 256 rows of
# 128 bf16 are 0.5 MB for K and as much for V, twice for the two buffers. A
# slot's read is rounded up to it (the engine's `cache_rows_read_total`)
READ_CHUNK_ROWS = 256


def read_chunk_rows(budget: int, dtype) -> int:
    """Rows a chunk of `tony_decode_read` holds for a cache of `budget`
    rows stored as `dtype`: the largest power of two up to READ_CHUNK_ROWS
    that divides the budget and is whole tiles of the stored type (16 x 128
    for bf16, 32 x 128 for int8). 0 when there is none: the jnp body then
    reads the whole budget."""
    tile = 32 // jnp.dtype(dtype).itemsize
    chunk = READ_CHUNK_ROWS
    while chunk >= tile:
        if budget % chunk == 0:
            return chunk
        chunk //= 2
    return 0


def _attend_jnp(layer, lens, q, k_new, v_new, k_cache, v_cache, *scales,
                window: int, sm_scale: float | None = None, out_dtype=None):
    """The plain body: every row of the layer is read and the rows at or
    past lens[b] are masked. One softmax over the cached and the window's
    own scores: every position attends to exactly its rows 0..position."""
    def of_layer(a):
        return lax.dynamic_index_in_dim(a, layer[0], 0, keepdims=False)

    kc = of_layer(k_cache).astype(jnp.float32)
    vc = of_layer(v_cache).astype(jnp.float32)
    if scales:
        kc = kc * of_layer(scales[0])[..., None]
        vc = vc * of_layer(scales[1])[..., None]
    b, g, r, d = q.shape
    s, w = kc.shape[2], window
    qg = q.reshape(b, g, r // w, w, d).astype(jnp.float32) * (
        d ** -0.5 if sm_scale is None else sm_scale)
    old = jnp.einsum("bgrwd,bgsd->bgrws", qg, kc)       # (B,G,rep,W,S)
    col = lax.broadcasted_iota(jnp.int32, old.shape, 4)
    old = jnp.where(col < lens[:, None, None, None, None], old, NEG_INF)
    new = jnp.einsum("bgrwd,bgud->bgrwu", qg,
                     k_new.astype(jnp.float32))         # (B,G,rep,W,W)
    causal = (lax.broadcasted_iota(jnp.int32, new.shape, 4)
              <= lax.broadcasted_iota(jnp.int32, new.shape, 3))
    new = jnp.where(causal, new, NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([old, new], axis=-1), axis=-1)
    out = (jnp.einsum("bgrws,bgsd->bgrwd", probs[..., :s], vc)
           + jnp.einsum("bgrwu,bgud->bgrwd", probs[..., s:],
                        v_new.astype(jnp.float32)))     # (B,G,rep,W,hd)
    return out.reshape(b, g, r, d).astype(out_dtype or q.dtype)


def _decode_read_kernel(layer_ref, len_ref, q_ref, kn_ref, vn_ref, *refs,
                        chunk: int, window: int, sm_scale: float,
                        quant: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n_leaves = 4 if quant else 2
    hbm, o_ref = refs[:n_leaves], refs[n_leaves]
    bufs, sems = refs[n_leaves + 1:-1], refs[-1]
    b = pl.program_id(0)
    layer = layer_ref[0]
    n = len_ref[b]
    n_chunks = (n + chunk - 1) // chunk

    def copies(c, slot):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        return [pltpu.make_async_copy(
                    src.at[(layer, b, slice(None), rows)
                           + (slice(None),) * (len(src.shape) - 4)],
                    dst.at[slot], sems.at[i, slot])
                for i, (src, dst) in enumerate(zip(hbm, bufs))]

    @pl.when(n_chunks > 0)
    def _():
        for cp in copies(0, 0):
            cp.start()

    # the window's own rows, from registers: they are not in the cache
    # yet. Row r of a head group is query head r // W at window position
    # r % W, which sees the window's rows 0..r % W
    q = q_ref[0]                                          # (G, R, d)
    qf = q.astype(jnp.float32)
    at = lax.rem(lax.broadcasted_iota(jnp.int32, (1, q.shape[1], 1), 1),
                 window)
    m = l = acc = None
    for u in range(window):
        kn = kn_ref[0, :, u:u + 1, :].astype(jnp.float32)   # (G, 1, d)
        vn = vn_ref[0, :, u:u + 1, :].astype(jnp.float32)
        s = jnp.sum(qf * kn, axis=-1, keepdims=True) * sm_scale  # (G,R,1)
        if u == 0:
            m, l, acc = s, jnp.ones_like(s), jnp.broadcast_to(vn, qf.shape)
            continue
        seen = at >= u
        m_new = jnp.maximum(m, jnp.where(seen, s, NEG_INF))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        m, l, acc = m_new, alpha * l + p, alpha * acc + p * vn

    def body(c, carry):
        m_prev, l_prev, acc = carry
        slot = lax.rem(c, 2)

        @pl.when(c + 1 < n_chunks)
        def _():
            for cp in copies(c + 1, 1 - slot):
                cp.start()

        for cp in copies(c, slot):
            cp.wait()
        kb, vb = bufs[0][slot], bufs[1][slot]             # (G, chunk, d)
        s = lax.dot_general(q, kb.astype(q.dtype),
                            (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * sm_scale
        if quant:
            s = s * bufs[2][slot][:, None, :]             # (G, R, chunk)
        cols = c * chunk + lax.broadcasted_iota(jnp.int32, (1, 1, chunk), 2)
        ok = cols < n
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if quant:
            p = p * bufs[3][slot][:, None, :]
        pv = lax.dot_general(p.astype(q.dtype), vb.astype(q.dtype),
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
        return m_new, l_new, acc * alpha + pv

    _, l, acc = lax.fori_loop(0, n_chunks, body, (m, l, acc))
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _attend_pallas(layer, lens, q, k_new, v_new, k_cache, v_cache, *scales,
                   window: int, chunk: int, sm_scale: float | None = None,
                   out_dtype=None, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, r, d = q.shape
    leaves = (k_cache, v_cache) + scales
    kernel = functools.partial(_decode_read_kernel, chunk=chunk,
                               window=window,
                               sm_scale=d ** -0.5 if sm_scale is None
                               else sm_scale,
                               quant=bool(scales))

    def per_slot(rows):
        return pl.BlockSpec((1, g, rows, d), lambda i, *_: (i, 0, 0, 0))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[per_slot(r), per_slot(window), per_slot(window)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(leaves),
            out_specs=per_slot(r),
            scratch_shapes=[
                pltpu.VMEM((2, g, chunk) + leaf.shape[4:], leaf.dtype)
                for leaf in leaves
            ] + [pltpu.SemaphoreType.DMA((len(leaves), 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, r, d), out_dtype or q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="tony_decode_read",
    )(layer, lens, q, k_new, v_new, *leaves)


def cache_attention(layer: jax.Array, lens: jax.Array, q: jax.Array,
                    k_new: jax.Array, v_new: jax.Array,
                    cache: dict[str, jax.Array],
                    sm_scale: float | None = None,
                    out_dtype=None) -> jax.Array:
    """Attention of W new positions a slot against the cache AND
    themselves.

    q (B, H, W, hd) for the window at batch row b's next W positions;
    `cache` holds the WHOLE K/V leaves (L, B, Hkv, S, hd), of which only
    `layer` (a (1,) int32) is read, and of it only rows below lens[b]
    (int32, at most S: the caller's to see to, once, outside its layer
    loop) — whatever lies at or past them is stale and never reaches the
    result; a row with lens[b] = 0 reads nothing. An int8 cache (leaves
    `k_scale`, `v_scale` (L, B, Hkv, S, 1)) is told by its tree. k_new,
    v_new (B, Hkv, W, hd) are the window's own rows as a read back from
    the cache would give them, attended from registers under the
    within-window causal mask. GQA by head group: K/V are never repeated.
    The scores are scaled by `sm_scale` (absent: hd ** -0.5; a caller that
    lays several narrow heads side by side in one row gives its own).
    Returns (B, H, W, hd) in `out_dtype` (absent: q's)."""
    b, nh, w, hd = q.shape
    g = k_new.shape[1]
    budget = cache["k"].shape[3]
    scales = tuple(cache[name][..., 0] for name in ("k_scale", "v_scale")
                   if name in cache)
    args = (layer, lens, q.reshape(b, g, nh // g * w, hd), k_new, v_new,
            cache["k"], cache["v"]) + scales
    chunk = read_chunk_rows(budget, cache["k"].dtype)
    plain = functools.partial(_attend_jnp, window=w, sm_scale=sm_scale,
                              out_dtype=out_dtype)
    kernel = functools.partial(_attend_pallas, window=w, chunk=chunk,
                               sm_scale=sm_scale, out_dtype=out_dtype)
    if not chunk:
        out = plain(*args)
    elif _INTERPRET:
        out = kernel(*args, interpret=True)
    else:
        out = lax.platform_dependent(*args, tpu=kernel, default=plain)
    return out.reshape(b, nh, w, hd)
