"""Block-sparse attention with a learned block selection (InfLLM-V2, the
`minicpm4` mixer of models/sala.py).

A query does not attend to its whole context but to `topk` blocks of
`block_size` tokens. Which blocks is decided in two stages:

- **Stage 1, selection** (`select_blocks`): the keys are mean-pooled into
  *compressed keys* (`compress_keys`: windows of `kernel_size` tokens every
  `kernel_stride`); every query head takes a softmax over the compressed
  keys whose window is complete inside its context, the heads of one
  key/value group add their distributions, and a block's score is the
  largest of the compressed keys that overlap it (a max-pool of 5 every 4,
  padded by 1, because a block is 4 strides and a window 2). The first
  `init_blocks` blocks and those covering the last `window_size` tokens are
  always taken; the `topk` best *including those* are attended.
- **Stage 2, attention**: causal softmax over exactly the tokens of the
  selected blocks.

Prefill (`sparse_prefill_attention`, one prompt) runs in chunks of
queries, so the score tensors of stage 1 stay small. Its stage 2 is the
Pallas kernel `tony_sparse_attn`: a flash kernel (one head and 512
queries a program, the whole K/V of the group resident in VMEM) whose
mask is each query's own selection, handed in as a byte for every key
tile of 8 blocks. It walks every key tile up to the causal edge: the
queries of a block select different blocks, and with weights that are
not trained their union is nearly all of them (a first version that
walked the union of 64 queries' blocks through scalar-prefetched indices
ran 4 x slower for it: PERF.md, PR 30).

Decode (`select_decode` + `sparse_decode_attention`) selects over the
slot's cached compressed keys and reads the selected blocks of the K/V
cache in place. Stage 1 is one kernel a sparse layer, `tony_sparse_select`:
a program a riding slot takes that slot's compressed keys out of the whole
`ck` leaf, scores its blocks with `block_scores`' arithmetic (bfloat16
operands, float32 products and softmax, the pool, the forced blocks) and
takes the `topk` best by rank instead of sorting them — the k-th largest
score by a search over its bits, ties to the lower block as `lax.top_k`
breaks them, the ascending ids by a count over a running sum; a slot that
does not ride costs a grid step, its count 0. (As XLA operations the same
stage was two sorts and some 25 launches a layer: `lax.top_k` lowers to a
full sort of the 576 block scores; PERF.md, PR 44.) Stage 2 is the kernel
`tony_sparse_read`, which takes the whole cache in HBM and copies only the
selected blocks to VMEM, so no gathered copy of the cache exists. The
admission keeps `select_blocks` (`lax.top_k` over a chunk of queries).

Dispatch is by platform at lowering time, as in ops/attention.py: the
Pallas kernels on a TPU, the same arithmetic in plain jnp elsewhere.
TONY_FLASH_INTERPRET=1 runs the kernels interpreted on the CPU.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.ops.attention import _INTERPRET, NEG_INF
from tony_tpu.ops.lightning import compact_riders, riding_mask

FORCED = -NEG_INF       # score of a block that is always taken

# queries a prefill chunk selects for at once: the (heads, chunk,
# compressed keys) float32 scores of stage 1 are 268 MB at 1024 queries of
# a 32k prompt
PREFILL_CHUNK = 1024
# queries one program of tony_sparse_attn serves, against key tiles of 8
# blocks (512 x 512 at blocks of 64: the flash kernel's best shape)
BLOCK_Q = 512
# blocks tony_sparse_read multiplies at once (1024 keys of 64-token blocks)
READ_CHUNK_BLOCKS = 16


@dataclass(frozen=True)
class SparseSpec:
    kernel_size: int = 32
    kernel_stride: int = 16
    init_blocks: int = 1
    block_size: int = 64
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192

    def __post_init__(self):
        st = self.kernel_stride
        if self.kernel_size != 2 * st or self.block_size != 4 * st:
            raise ValueError(
                "the block pooling (5 every 4, padded by 1) needs "
                "kernel_size = 2 x kernel_stride and block_size = 4 x "
                f"kernel_stride; got {self}")
        if self.window_size % self.block_size or \
                self.dense_len % self.block_size:
            raise ValueError(f"window_size and dense_len must be whole "
                             f"blocks; got {self}")
        forced = self.init_blocks + self.window_size // self.block_size + 1
        if forced > self.topk or self.dense_len < self.topk * self.block_size:
            raise ValueError(f"topk must hold the forced blocks and fit "
                             f"inside dense_len; got {self}")

    @property
    def max_read_blocks(self) -> int:
        """Blocks a decode step may read for one slot: `topk` past
        `dense_len`, the whole context up to it."""
        return max(self.topk, self.dense_len // self.block_size)

    def read_blocks(self, context: int) -> tuple[int, int]:
        """(blocks attended, blocks of context) for a query whose context
        (itself included) is `context` tokens."""
        total = -(-context // self.block_size)
        if context <= self.dense_len:
            return total, total
        return min(total, self.topk), total


# ---------------------------------------------------------------------------
# stage 1: compressed keys, block scores, top-k
# ---------------------------------------------------------------------------

def compress_keys(k: jax.Array, spec: SparseSpec) -> jax.Array:
    """k (..., n, d) -> (..., n // stride - 1, d): the mean of every
    complete window of `kernel_size` rows at stride `kernel_stride`,
    summed in float32 and stored in k's dtype."""
    n, st = k.shape[-2], spec.kernel_stride
    nh = n // st
    if nh < 2:
        return jnp.zeros(k.shape[:-2] + (0, k.shape[-1]), k.dtype)
    half = k[..., :nh * st, :].astype(jnp.float32).reshape(
        k.shape[:-2] + (nh, st, k.shape[-1])).sum(axis=-2)
    return ((half[..., :-1, :] + half[..., 1:, :])
            / spec.kernel_size).astype(k.dtype)


def block_scores(q: jax.Array, ck: jax.Array, qpos: jax.Array,
                 spec: SparseSpec, new=None) -> jax.Array:
    """Scores of every block for every query of one sequence.

    q (G, R, T, d): T queries at positions qpos (T,), R heads a group;
    ck (G, NC, d) compressed keys, NC a multiple of 4 (entries past the
    last complete window are never read: validity is by position).
    `new` = (j, flag, row (G, d)): a compressed key completed by the query
    itself, standing at index j where `flag` is set (decode).
    Returns (G, T, NC // 4) float32: FORCED for the blocks always taken,
    NEG_INF for those past the query, else the pooled score."""
    g, r, t, d = q.shape
    nc = ck.shape[1]
    st, scale = spec.kernel_stride, d ** -0.5
    s = jnp.einsum("grtd,gjd->grtj", q, ck,
                   preferred_element_type=jnp.float32) * scale
    j = jnp.arange(nc, dtype=jnp.int32)
    if new is not None:
        j_new, flag, row = new
        s_new = jnp.einsum("grtd,gd->grt", q, row,
                           preferred_element_type=jnp.float32) * scale
        s = jnp.where((j == j_new) & flag, s_new[..., None], s)
    valid = j[None, :] * st + spec.kernel_size <= qpos[:, None] + 1
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    group = jnp.sum(p, axis=1)                              # (G, T, NC)
    pooled = lax.reduce_window(group, -jnp.inf, lax.max, (1, 1, 5),
                               (1, 1, 4), ((0, 0), (0, 0), (1, 0)))
    b = jnp.arange(nc // 4, dtype=jnp.int32)
    last = qpos // spec.block_size
    first_window = jnp.maximum(qpos - spec.window_size + 1, 0) \
        // spec.block_size
    forced = (b[None, :] < spec.init_blocks) \
        | (b[None, :] >= first_window[:, None])
    score = jnp.where(forced, FORCED, pooled)
    return jnp.where(b[None, :] <= last[:, None], score, NEG_INF)


def select_blocks(q, ck, qpos, spec: SparseSpec, new=None) -> jax.Array:
    """(G, T, topk) int32: the selected blocks of every query, best first;
    -1 where the context has fewer than `topk` blocks."""
    score = block_scores(q, ck, qpos, spec, new)
    k = min(spec.topk, score.shape[-1])
    vals, idx = lax.top_k(score, k)
    idx = jnp.where(vals > NEG_INF / 2, idx, -1).astype(jnp.int32)
    if k < spec.topk:
        idx = jnp.pad(idx, ((0, 0), (0, 0), (0, spec.topk - k)),
                      constant_values=-1)
    return idx


def _pad_ck(ck: jax.Array, n_blocks: int) -> jax.Array:
    """Compressed keys padded to 4 a block (the padding is never valid)."""
    return jnp.pad(ck, ((0, 0), (0, 4 * n_blocks - ck.shape[1]), (0, 0)))


# ---------------------------------------------------------------------------
# stage 2, prefill: the selected blocks of every query of one prompt
# ---------------------------------------------------------------------------

def _prefill_attend_jnp(qc, k, v, sel, q0, spec: SparseSpec):
    """qc (G, R, T, d) queries at positions q0.., k/v (G, n, d), sel
    (G, T, topk): causal softmax over the tokens of each query's blocks,
    by a dense mask (small sizes: the CPU path)."""
    g, r, t, d = qc.shape
    n = k.shape[1]
    s = jnp.einsum("grtd,gnd->grtn", qc, k,
                   preferred_element_type=jnp.float32) * d ** -0.5
    key_block = jnp.arange(n, dtype=jnp.int32) // spec.block_size
    member = jnp.any(sel[..., None] == key_block, axis=-2)   # (G, T, n)
    rows = q0 + jnp.arange(t, dtype=jnp.int32)
    ok = member & (jnp.arange(n)[None, :] <= rows[:, None])[None]
    s = jnp.where(ok[:, None], s, NEG_INF)
    p = jnp.where(ok[:, None], jnp.exp(s - jnp.max(s, -1, keepdims=True)),
                  0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    return jnp.einsum("grtn,gnd->grtd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(qc.dtype)


def _member_bytes(sel, n_blocks: int):
    """sel (G, T, topk) -> (G, T, n_blocks / 8) float32: per query and
    key tile of 8 blocks, the byte whose bit b says that the query
    selected the tile's block b (a float, so the kernel can pick a
    tile's column out with a one-hot sum)."""
    g, t, _ = sel.shape
    idx = jnp.where(sel < 0, n_blocks, sel)
    gi = lax.broadcasted_iota(jnp.int32, idx.shape, 0)
    ti = lax.broadcasted_iota(jnp.int32, idx.shape, 1)
    hit = jnp.zeros((g, t, n_blocks), jnp.int32).at[gi, ti, idx].max(
        1, mode="drop")
    bits = hit.reshape(g, t, n_blocks // 8, 8) << jnp.arange(8)
    return jnp.sum(bits, axis=-1).astype(jnp.float32)


def _sparse_attn_kernel(q0_ref, q_ref, k_ref, v_ref, m_ref, o_ref, *,
                        block: int, sm_scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    q = q_ref[0, 0]                                     # (bq, d)
    bq, d = q.shape
    members = m_ref[0]                                  # (bq, key tiles)
    n_tiles = members.shape[1]
    tile = 8 * block
    rows = q0_ref[0] + qi * bq + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    tile_of = lax.broadcasted_iota(jnp.int32, members.shape, 1)
    col = lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    # key tiles past the block's last query contribute nothing
    live = jnp.minimum(
        lax.div(q0_ref[0] + (qi + 1) * bq + tile - 1, tile), n_tiles)

    def body(kb, carry):
        m_prev, l_prev, acc = carry
        start = pl.multiple_of(kb * tile, tile)
        kt = k_ref[0, pl.ds(start, tile), :]
        vt = v_ref[0, pl.ds(start, tile), :]
        s = lax.dot_general(q, kt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        byte = jnp.sum(jnp.where(tile_of == kb, members, 0.0), axis=1,
                       keepdims=True).astype(jnp.int32)     # (bq, 1)
        chosen = (jnp.right_shift(byte, col // block) & 1) > 0
        ok = chosen & (start + col <= rows)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(vt.dtype), vt,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((bq, 1), NEG_INF, jnp.float32),
            jnp.zeros((bq, 1), jnp.float32),
            jnp.zeros((bq, d), jnp.float32))
    _, l, acc = lax.fori_loop(0, live, body, init)
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _prefill_attend_pallas(qc, k, v, sel, q0, spec: SparseSpec,
                           interpret: bool = False):
    """The same as `_prefill_attend_jnp` through tony_sparse_attn: a flash
    kernel (one head and BLOCK_Q queries a program, the group's K/V
    resident in VMEM, key tiles of 8 blocks up to the causal edge) whose
    mask is each query's own selection, a byte a key tile. k/v are whole
    key tiles long; T a multiple of the query block."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, r, t, d = qc.shape
    n = k.shape[1]
    bq = min(BLOCK_Q, t)
    members = _member_bytes(sel, n // spec.block_size)
    kernel = functools.partial(_sparse_attn_kernel, block=spec.block_size,
                               sm_scale=d ** -0.5)
    kv_bytes = 2 * 2 * n * d * k.dtype.itemsize      # K and V, two buffers
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(g, r, t // bq),
            in_specs=[
                pl.BlockSpec((1, 1, bq, d), lambda a, b, c, *_: (a, b, c, 0)),
                pl.BlockSpec((1, n, d), lambda a, b, c, *_: (a, 0, 0)),
                pl.BlockSpec((1, n, d), lambda a, b, c, *_: (a, 0, 0)),
                pl.BlockSpec((1, bq, members.shape[2]),
                             lambda a, b, c, *_: (a, c, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, d),
                                   lambda a, b, c, *_: (a, b, c, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((g, r, t, d), qc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=kv_bytes + (24 << 20)),
        interpret=interpret,
        name="tony_sparse_attn",
    )(jnp.reshape(q0, (1,)).astype(jnp.int32), qc, k, v, members)


def sparse_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                             spec: SparseSpec,
                             chunk: int = PREFILL_CHUNK) -> jax.Array:
    """Block-sparse causal attention of one prompt longer than
    `dense_len`. q (n, H * d), a head's d columns side by side as the
    projection leaves them; k, v (G, n, d), H a multiple of G. Every query
    selects its blocks (stage 1) and attends to them (stage 2), a chunk of
    queries at a time. Returns (n, H * d)."""
    g, n, d = k.shape
    h = q.shape[1] // d
    r = h // g
    block = spec.block_size
    nb = -(-n // block)
    bq = min(BLOCK_Q, -(-n // 8) * 8)
    chunk = min(chunk, -(-n // bq) * bq)
    chunk -= chunk % bq
    n_chunks = -(-n // chunk)
    ck = _pad_ck(compress_keys(k, spec), nb)
    rows = -(-nb // 8) * 8 * block          # whole key tiles of 8 blocks
    kp = jnp.pad(k, ((0, 0), (0, rows - n), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, rows - n), (0, 0)))
    qp = jnp.pad(q, ((0, n_chunks * chunk - n), (0, 0)))

    def attend(qc, sel, q0):
        if _INTERPRET:
            return _prefill_attend_pallas(qc, kp, vp, sel, q0, spec, True)
        return lax.platform_dependent(
            qc, kp, vp, sel, q0,
            tpu=functools.partial(_prefill_attend_pallas, spec=spec),
            default=functools.partial(_prefill_attend_jnp, spec=spec))

    def one(c):
        q0 = c * chunk
        # only a chunk of queries is ever transposed to (G, R, chunk, d)
        qc = lax.dynamic_slice_in_dim(qp, q0, chunk, axis=0).reshape(
            chunk, g, r, d).transpose(1, 2, 0, 3)
        qpos = q0 + jnp.arange(chunk, dtype=jnp.int32)
        with jax.named_scope("tony_sparse_prefill_select"):
            sel = select_blocks(qc, ck, qpos, spec)
        return attend(qc, sel, q0).transpose(2, 0, 1, 3).reshape(
            chunk, h * d)

    out = lax.map(one, jnp.arange(n_chunks, dtype=jnp.int32))
    return out.reshape(n_chunks * chunk, h * d)[:n]


# ---------------------------------------------------------------------------
# decode: select over the cached compressed keys, read the blocks in place
# ---------------------------------------------------------------------------

def _select_decode_jnp(layer, slots, count, lens, j, flag, q, row, ck, *,
                       spec: SparseSpec):
    """The selection in plain jnp (what runs off the TPU, and the kernel's
    reference): `select_blocks` a slot, which sorts twice (`lax.top_k`,
    then the ids ascending)."""
    sm = spec.max_read_blocks
    ck = lax.dynamic_index_in_dim(ck, layer[0], 0, keepdims=False)

    def one(qb, ckb, pos, j, flag, row):
        return select_blocks(qb[:, :, None, :], ckb, pos[None], spec,
                             (j, flag > 0, row))[:, 0]      # (G, topk)

    sel = jax.vmap(one)(q, ck, lens, j, flag, row)          # (B, G, topk)
    big = jnp.int32(2 ** 30)
    picked = jnp.sort(jnp.where(sel < 0, big, sel), axis=-1)
    picked = jnp.pad(picked, ((0, 0), (0, 0), (0, sm - spec.topk)),
                     constant_values=2 ** 30)
    n_picked = jnp.sum(sel >= 0, axis=-1).astype(jnp.int32)
    dense = lens + 1 <= spec.dense_len                      # (B,)
    every = jnp.arange(sm, dtype=jnp.int32)
    n_every = (lens + spec.block_size - 1) // spec.block_size
    ids = jnp.where(dense[:, None, None], every[None, None, :], picked)
    counts = jnp.where(dense[:, None], n_every[:, None], n_picked)
    counts = jnp.where(riding_mask(slots, count)[:, None], counts, 0)
    ids = jnp.where(every[None, None, :] < counts[..., None], ids, 0)
    return ids.astype(jnp.int32), counts.astype(jnp.int32)


def _select_kernel(layer_ref, slots_ref, count_ref, lens_ref, j_ref,
                   flag_ref, q_ref, row_ref, ck_ref, ids_ref, cnt_ref, ckf,
                   score_ref, *, spec: SparseSpec, nb: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    groups, _, d = q_ref.shape[1:]
    nc = ck_ref.shape[3]
    nbp = score_ref.shape[1]        # the blocks, padded to whole lane tiles
    sm = ids_ref.shape[-1]
    st, ks, block = spec.kernel_stride, spec.kernel_size, spec.block_size
    k = min(spec.topk, nb)
    f32 = jnp.float32

    @pl.when(i == 0)
    def _():        # a slot no program visits: count 0, ids 0
        ids_ref[...] = jnp.zeros_like(ids_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)
        if nc < 4 * nbp:    # rows past the keys: never valid, not NaN
            ckf[pl.ds(nc, 4 * nbp - nc), :] = jnp.zeros(
                (4 * nbp - nc, d), f32)

    @pl.when(i < count_ref[0])
    def _():
        slot = slots_ref[i]
        pos = lens_ref[slot]
        lane = lax.broadcasted_iota(jnp.int32, (1, nbp), 1)

        def cumsum(x):      # along lanes, inclusive: log2(nbp) shifted adds
            shift = 1
            while shift < nbp:
                x = x + jnp.where(lane >= shift, pltpu.roll(x, shift, 1), 0.0)
                shift *= 2
            return x

        # `block_scores` at T = 1, a group at a time. The four compressed
        # keys of a block are four reads of every fourth row (of a float32
        # copy: a strided read wants 32-bit rows), so a block is a lane
        # from the matmul on and the pool is a max of planes.
        last = pos // block
        first_window = jnp.maximum(pos - spec.window_size + 1, 0) // block
        for g in range(groups):
            ckf[pl.ds(0, nc), :] = ck_ref[0, 0, g].astype(f32)

            @pl.when(flag_ref[slot] > 0)
            def _():    # the key the token itself completes
                ckf[pl.ds(j_ref[slot], 1), :] = \
                    row_ref[0, g:g + 1, :].astype(f32)

            q = q_ref[0, g]                                     # (R, d)
            planes, valid = [], []
            for r in range(4):
                keys = ckf[pl.ds(r, nbp, stride=4), :].astype(q.dtype)
                s = lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                                    preferred_element_type=f32) * d ** -0.5
                ok = (4 * lane + r) * st + ks <= pos + 1
                planes.append(jnp.where(ok, s, NEG_INF))
                valid.append(ok)
            m = functools.reduce(jnp.maximum, (
                jnp.max(s, axis=1, keepdims=True) for s in planes))
            planes = [jnp.where(ok, jnp.exp(s - m), 0.0)
                      for s, ok in zip(planes, valid)]
            den = jnp.maximum(sum(jnp.sum(p, axis=1, keepdims=True)
                                  for p in planes), 1e-30)
            g0, g1, g2, g3 = (jnp.sum(p / den, axis=0, keepdims=True)
                              for p in planes)                  # (1, nbp)
            before = jnp.where(lane >= 1, pltpu.roll(g3, 1, 1), -jnp.inf)
            pooled = functools.reduce(jnp.maximum, (before, g0, g1, g2, g3))
            forced = (lane < spec.init_blocks) | (lane >= first_window)
            score = jnp.where(forced, FORCED, pooled)
            score_ref[g:g + 1, :] = jnp.where(lane <= last, score, NEG_INF)

        # the k best, ties to the lower index (`lax.top_k`'s rule), with
        # no sort: the k-th largest score by a search over the bits of its
        # order-preserving integer, every group at once
        score = score_ref[...]                                  # (G, nbp)
        bits = pltpu.bitcast(score, jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)

        def at_least(t):
            return jnp.sum((key >= t).astype(f32), axis=1, keepdims=True)

        t = jnp.where(at_least(jnp.int32(0)) >= k, jnp.int32(0),
                      jnp.int32(-2 ** 31))
        for bit in range(30, -1, -1):
            higher = t + jnp.int32(1 << bit)
            t = jnp.where(at_least(higher) >= k, higher, t)
        above, tie = key > t, key == t
        ties = tie.astype(f32)
        room = k - jnp.sum(above.astype(f32), axis=1, keepdims=True)
        taken = (above | (tie & (cumsum(ties) - ties < room))) \
            & (score > NEG_INF / 2)
        taken = taken.astype(f32)
        n_picked = jnp.sum(taken, axis=1, keepdims=True)        # (G, 1)
        seen = cumsum(taken)                                    # (G, nbp)

        # ascending ids: the n-th taken block is the number of blocks by
        # which at most n were seen; stood up as a column, laid down as a
        # row by a sum against the identity
        nth = lax.broadcasted_iota(jnp.int32, (spec.topk, 1), 0).astype(f32)
        eye = lax.broadcasted_iota(jnp.int32, (spec.topk, sm), 0) \
            == lax.broadcasted_iota(jnp.int32, (spec.topk, sm), 1)
        every = lax.broadcasted_iota(jnp.int32, (1, sm), 1)
        dense = pos + 1 <= spec.dense_len
        n_every = (pos + block - 1) // block
        for g in range(groups):
            column = jnp.sum((seen[g:g + 1, :] <= nth).astype(f32), axis=1,
                             keepdims=True)                     # (topk, 1)
            picked = jnp.sum(jnp.where(eye, column, 0.0), axis=0,
                             keepdims=True).astype(jnp.int32)   # (1, sm)
            n = jnp.where(dense, n_every,
                          n_picked[g:g + 1, :].astype(jnp.int32))  # (1, 1)
            ids = jnp.where(dense, every, picked)
            ids_ref[slot, g:g + 1, :] = jnp.where(every < n, ids, 0)
            cnt_ref[pl.ds(slot, 1), g:g + 1] = n


def _select_decode_pallas(layer, slots, count, lens, j, flag, q, row, ck, *,
                          spec: SparseSpec, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, r, d = q.shape
    nc = ck.shape[3]
    nb = nc // 4
    nbp = -(-nb // 128) * 128
    sm = spec.max_read_blocks

    return pl.pallas_call(
        functools.partial(_select_kernel, spec=spec, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            # one program a slot that rides (`compact_riders`); the
            # programs past the last rider name its blocks again and are
            # empty grid steps
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, g, r, d),
                             lambda i, _, slots, *__: (slots[i], 0, 0, 0)),
                pl.BlockSpec((1, g, d),
                             lambda i, _, slots, *__: (slots[i], 0, 0)),
                # the rider's compressed keys of this layer, out of the
                # whole leaf
                pl.BlockSpec((1, 1, g, nc, d),
                             lambda i, layer, slots, *_:
                             (layer[0], slots[i], 0, 0, 0)),
            ],
            # every slot's ids and counts stay in VMEM for the whole call
            out_specs=[pl.BlockSpec((b, g, sm), lambda i, *_: (0, 0, 0)),
                       pl.BlockSpec((b, g), lambda i, *_: (0, 0))],
            scratch_shapes=[pltpu.VMEM((4 * nbp, d), jnp.float32),
                            pltpu.VMEM((g, nbp), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, g, sm), jnp.int32),
                   jax.ShapeDtypeStruct((b, g), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="tony_sparse_select",
    )(layer, slots, count, lens, j, flag, q, row, ck)


def select_decode(layer: jax.Array, q: jax.Array, ck: jax.Array,
                  lens: jax.Array, spec: SparseSpec, new,
                  riders=None) -> tuple[jax.Array, jax.Array]:
    """The blocks each slot's new token reads. q (B, G, R, d) at position
    lens[b] (the rows the cache holds); ck (L, B, G, NC, d) the WHOLE leaf
    of compressed keys, of which only `layer` (a (1,) int32) is read;
    `new` = (j (B,), flag (B,), row (B, G, d)) as in `block_scores`;
    `riders` = `compact_riders(mask)` of ops/lightning.py (absent: every
    slot rides). Returns ids (B, G, max_read_blocks) ascending and counts
    (B, G): a context of at most `dense_len` tokens (the new one included)
    reads all its blocks, a longer one its selected `topk`; a slot that
    does not ride reads none (count 0, ids 0).

    On a TPU one call is the kernel `tony_sparse_select`: a program a
    rider scores its blocks as `block_scores` does and takes the `topk`
    best by rank, with `lax.top_k`'s ties, sorting nothing. Elsewhere the
    same selection through `select_blocks` (two sorts)."""
    if riders is None:
        riders = compact_riders(jnp.ones(q.shape[:1], bool))
    j, flag, row = new
    args = (layer, *riders, lens.astype(jnp.int32), j.astype(jnp.int32),
            flag.astype(jnp.int32), q, row, ck)
    if _INTERPRET:
        return _select_decode_pallas(*args, spec=spec, interpret=True)
    return lax.platform_dependent(
        *args, tpu=functools.partial(_select_decode_pallas, spec=spec),
        default=functools.partial(_select_decode_jnp, spec=spec))


def _valid_rows(ids, counts, lens, block: int):
    """Rows of the gathered blocks that hold context: all of every block
    but the last, which the slot's length may cut (ids ascend, and the
    block holding position lens[b] is always among them)."""
    last = jnp.take_along_axis(ids, jnp.maximum(counts - 1, 0)[..., None],
                               axis=-1)[..., 0]
    tail = jnp.clip(lens[:, None] - last * block, 0, block)
    return jnp.where(counts > 0, (counts - 1) * block + tail, 0)


def _decode_attend_jnp(layer, ids, counts, lens, q, k_new, v_new, k_cache,
                       v_cache, block: int):
    b, g, r, d = q.shape
    sm = ids.shape[-1]
    kc = lax.dynamic_index_in_dim(k_cache, layer[0], 0, keepdims=False)
    vc = lax.dynamic_index_in_dim(v_cache, layer[0], 0, keepdims=False)
    shape = (b, g, kc.shape[2] // block, block, d)
    take = ids[..., None, None]
    kb = jnp.take_along_axis(kc.reshape(shape), take, axis=2).reshape(
        b, g, sm * block, d)
    vb = jnp.take_along_axis(vc.reshape(shape), take, axis=2).reshape(
        b, g, sm * block, d)
    scale = d ** -0.5
    s = jnp.einsum("bgrd,bgnd->bgrn", q, kb,
                   preferred_element_type=jnp.float32) * scale
    nvalid = _valid_rows(ids, counts, lens, block)
    ok = (jnp.arange(sm * block)[None, None, :] < nvalid[..., None])[
        :, :, None, :]
    s = jnp.where(ok, s, NEG_INF)
    s_new = jnp.einsum("bgrd,bgd->bgr", q, k_new,
                       preferred_element_type=jnp.float32)[..., None] * scale
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), s_new)
    p = jnp.where(ok, jnp.exp(s - m), 0.0)
    p_new = jnp.exp(s_new - m)
    den = jnp.sum(p, axis=-1, keepdims=True) + p_new
    out = jnp.einsum("bgrn,bgnd->bgrd", p.astype(vb.dtype), vb,
                     preferred_element_type=jnp.float32) \
        + p_new * v_new[:, :, None, :].astype(jnp.float32)
    return (out / den).astype(q.dtype)


def _sparse_read_kernel(layer_ref, ids_ref, cnt_ref, nvalid_ref, q_ref,
                        kn_ref, vn_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                        sems, *, block: int, chunk: int, sm_scale: float):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    count = cnt_ref[b, g]
    nvalid = nvalid_ref[b, g]

    @pl.when((b == 0) & (g == 0))
    def _():        # rows past a slot's count are multiplied by 0: no NaN
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(s):
        start = pl.multiple_of(ids_ref[b, g, s] * block, block)
        dst = pl.ds(pl.multiple_of(s * block, block), block)
        return (pltpu.make_async_copy(
                    k_hbm.at[layer, b, g, pl.ds(start, block), :],
                    kbuf.at[dst, :], sems.at[0]),
                pltpu.make_async_copy(
                    v_hbm.at[layer, b, g, pl.ds(start, block), :],
                    vbuf.at[dst, :], sems.at[1]))

    def start(s, _):
        for c in copies(s):
            c.start()
        return 0

    def wait(s, _):
        for c in copies(s):
            c.wait()
        return 0

    lax.fori_loop(0, count, start, 0)
    lax.fori_loop(0, count, wait, 0)

    q = q_ref[0, 0]                                       # (R, d)
    kn = kn_ref[0, 0]                                     # (1, d)
    vn = vn_ref[0, 0].astype(jnp.float32)
    s_new = jnp.sum(q.astype(jnp.float32) * kn.astype(jnp.float32),
                    axis=-1, keepdims=True) * sm_scale    # (R, 1)
    rows = chunk * block

    def body(c, carry):
        m_prev, l_prev, acc = carry
        at = pl.ds(pl.multiple_of(c * rows, rows), rows)
        kb, vb = kbuf[at, :], vbuf[at, :]
        s = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        cols = c * rows + lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        ok = cols < nvalid
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(vb.dtype), vb,
                                    preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    # the new token attends to itself from registers: its row is not in
    # the cache yet
    init = (s_new, jnp.ones_like(s_new),
            jnp.broadcast_to(vn, (q.shape[0], q.shape[1])))
    _, l, acc = lax.fori_loop(0, (count + chunk - 1) // chunk, body, init)
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)


def _decode_attend_pallas(layer, ids, counts, lens, q, k_new, v_new,
                          k_cache, v_cache, block: int,
                          interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, g, r, d = q.shape
    sm = ids.shape[-1]
    chunk = min(READ_CHUNK_BLOCKS, sm)
    assert sm % chunk == 0, (sm, chunk)
    nvalid = _valid_rows(ids, counts, lens, block).astype(jnp.int32)
    kernel = functools.partial(_sparse_read_kernel, block=block,
                               chunk=chunk, sm_scale=d ** -0.5)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, g),
            in_specs=[
                pl.BlockSpec((1, 1, r, d), lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, d), lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, d), lambda i, j, *_: (i, j, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, r, d),
                                   lambda i, j, *_: (i, j, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((sm * block, d), k_cache.dtype),
                pltpu.VMEM((sm * block, d), v_cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, r, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="tony_sparse_read",
    )(layer, ids, counts, nvalid, q, k_new[:, :, None, :],
      v_new[:, :, None, :], k_cache, v_cache)


def sparse_decode_attention(layer: jax.Array, ids: jax.Array,
                            counts: jax.Array, lens: jax.Array,
                            q: jax.Array, k_new: jax.Array,
                            v_new: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array,
                            spec: SparseSpec) -> jax.Array:
    """One new token a slot against the blocks `select_decode` chose.

    q (B, G, R, d); k_new, v_new (B, G, d) the token's own row, attended
    from registers; k_cache, v_cache (L, B, G, S, d) the WHOLE cache of
    the sparse layers, of which only `layer` (a (1,) int32) is read, and
    of it only rows below lens[b] of the blocks ids[b, g, :counts[b, g]].
    Returns (B, G, R, d)."""
    args = (layer, ids, counts, lens, q, k_new, v_new, k_cache, v_cache)
    if _INTERPRET:
        return _decode_attend_pallas(*args, block=spec.block_size,
                                     interpret=True)
    return lax.platform_dependent(
        *args,
        tpu=functools.partial(_decode_attend_pallas, block=spec.block_size),
        default=functools.partial(_decode_attend_jnp,
                                  block=spec.block_size))
