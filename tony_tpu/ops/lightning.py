"""Lightning attention: linear attention with a per-head decay.

Per head, with `lam = exp(-slope)`:

    S_t = lam * S_{t-1} + k_t^T v_t          (S is d x d, float32)
    o_t = q_t S_t

There is no K/V cache: a sequence is its state. Two forms of the same
recurrence:

- `lightning_chunk` (prefill, one prompt): inside a chunk of C tokens the
  quadratic form with the decay mask `lam^(i-j)`, between chunks the
  state. With i, j the positions inside a chunk and S the state before it:
      o_i   = lam^(i+1) q_i S + sum_{j<=i} lam^(i-j) (q_i . k_j) v_j
      S_end = lam^C S + sum_j lam^(C-1-j) k_j^T v_j
  Every exponent is >= 0, so nothing overflows whatever the decay. The
  Pallas kernel `tony_lightning_chunk` walks the chunks of one head in
  order with the state in VMEM.
- `lightning_step` (decode, one token a slot): the recurrence itself. The
  kernel `tony_lightning_step` updates one layer's slice of the whole
  state array in place (aliased in and out), so a decode step carries the
  array through its layer loop without ever copying it; of that slice it
  reads and rewrites the slabs of the slots that ride, and no other: a
  slot no stream holds costs an empty grid step (2 MB each way a layer
  at 32 heads of 128, of a replica with 2-5 riders of 16 slots: PERF.md,
  PR 38).

Dispatch is by platform at lowering time, as in ops/attention.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.ops.attention import _INTERPRET

CHUNK = 256         # tokens a chunk: two MXU tiles of quadratic work


# ---------------------------------------------------------------------------
# prefill: the chunked form
# ---------------------------------------------------------------------------

def _chunk_math(q, k, v, state, slope, valid: jax.Array, chunk: int):
    """One chunk of one head. q, k, v (C, d); state (d, d) float32; slope
    a scalar; `valid` how many of the chunk's rows are the prompt's (the
    last chunk is padded). Returns (o (C, d) float32, new state)."""
    i = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    j = lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    diff = i - j
    decay = jnp.where(diff >= 0,
                      jnp.exp(-slope * jnp.maximum(diff, 0).astype(
                          jnp.float32)), 0.0)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * decay
    o = jnp.dot(s.astype(v.dtype), v, preferred_element_type=jnp.float32)
    o = o + jnp.dot(q, state.astype(q.dtype),
                    preferred_element_type=jnp.float32) \
        * jnp.exp(-slope * (i + 1).astype(jnp.float32))
    k_decay = jnp.where(i < valid, jnp.exp(
        -slope * jnp.maximum(valid - 1 - i, 0).astype(jnp.float32)), 0.0)
    kw = (k.astype(jnp.float32) * k_decay).astype(k.dtype)
    new = jnp.exp(-slope * valid.astype(jnp.float32)) * state \
        + lax.dot_general(kw, v, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return o, new


def _chunk_jnp(slopes, q, k, v, *, n: int, chunk: int):
    h = slopes.shape[0]
    npad, d = q.shape[0], q.shape[1] // h
    nc = npad // chunk

    def step(state, xs):
        c, qc, kc, vc = xs
        valid = jnp.minimum(chunk, n - c * chunk)
        o, state = jax.vmap(
            lambda a, b_, c_, s, sl: _chunk_math(a, b_, c_, s, sl, valid,
                                                 chunk))(
            qc, kc, vc, state, slopes)
        return state, o.astype(q.dtype)

    def split(x):           # (npad, H*d) -> (chunks, H, chunk, d)
        return x.reshape(nc, chunk, h, d).transpose(0, 2, 1, 3)

    state, o = lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                        (jnp.arange(nc), split(q), split(k), split(v)))
    return o.transpose(0, 2, 1, 3).reshape(npad, h * d), state


def _chunk_kernel(slope_ref, q_ref, k_ref, v_ref, o_ref, s_out_ref, s_scr,
                  *, n: int, chunk: int):
    from jax.experimental import pallas as pl

    h, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    valid = jnp.minimum(chunk, n - c * chunk)
    o, new = _chunk_math(q_ref[...], k_ref[...], v_ref[...], s_scr[...],
                         slope_ref[h], valid, chunk)
    o_ref[...] = o.astype(o_ref.dtype)
    s_scr[...] = new

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out_ref[0] = new


def _chunk_pallas(slopes, q, k, v, *, n: int, chunk: int,
                  interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h = slopes.shape[0]
    npad, d = q.shape[0], q.shape[1] // h
    # head a's rows of chunk b: a (chunk, d) window of the (rows, H*d)
    # array the projections leave, so nothing is ever transposed
    rows = pl.BlockSpec((chunk, d), lambda a, b, *_: (b, a))
    return pl.pallas_call(
        functools.partial(_chunk_kernel, n=n, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h, npad // chunk),
            in_specs=[rows, rows, rows],
            out_specs=[rows, pl.BlockSpec((1, d, d),
                                          lambda a, b, *_: (a, 0, 0))],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((npad, h * d), q.dtype),
                   jax.ShapeDtypeStruct((h, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="tony_lightning_chunk",
    )(slopes, q, k, v)


def lightning_chunk(q: jax.Array, k: jax.Array, v: jax.Array,
                    slopes: jax.Array, chunk: int = CHUNK
                    ) -> tuple[jax.Array, jax.Array]:
    """Lightning attention over one prompt. q (already scaled), k, v
    (n, H * d), a head's d columns side by side as the projections leave
    them; slopes (H,) float32. Returns (o (n, H * d), the state after the
    last token (H, d, d) float32)."""
    n = q.shape[0]
    chunk = min(chunk, -(-n // 8) * 8)
    pad = (-n) % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, pad), (0, 0))) for x in (q, k, v))
    slopes = slopes.astype(jnp.float32)
    if _INTERPRET:
        o, state = _chunk_pallas(slopes, q, k, v, n=n, chunk=chunk,
                                 interpret=True)
    else:
        o, state = lax.platform_dependent(
            slopes, q, k, v,
            tpu=functools.partial(_chunk_pallas, n=n, chunk=chunk),
            default=functools.partial(_chunk_jnp, n=n, chunk=chunk))
    return o[:n], state


# ---------------------------------------------------------------------------
# decode: one step of the recurrence, the state updated in place
# ---------------------------------------------------------------------------

def compact_riders(riding: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(slots (B,) int32, count (1,) int32) of a riding mask (B,): the
    slots that ride in order, then the last of them again and again (slot
    B - 1 where none rides), so that a program of `tony_lightning_step`
    past the last rider names the block the one before it named and moves
    nothing. Made once a decode step, outside its layer loop."""
    b = riding.shape[0]
    seen = jnp.cumsum(riding.astype(jnp.int32))
    count = seen[-1]
    i = jnp.arange(b, dtype=jnp.int32)
    # the (i + 1)-th rider is the slot before which as many slots have
    # seen at most i riders
    slots = jnp.sum(seen[None, :] <= i[:, None], axis=1, dtype=jnp.int32)
    last = jnp.take(slots, jnp.maximum(count - 1, 0))
    slots = jnp.minimum(jnp.where(i < count, slots, last), b - 1)
    return slots, jnp.reshape(count, (1,))


def riding_mask(slots: jax.Array, count: jax.Array) -> jax.Array:
    """The mask (B,) that `compact_riders` compacted into (slots, count)."""
    return jnp.zeros(slots.shape, bool).at[slots].set(True) & (count[0] > 0)


def _step_jnp(layer, slots, count, decay, q, k, v, state, *, scale: float):
    riding = riding_mask(slots, count)
    old = lax.dynamic_index_in_dim(state, layer[0], 0, keepdims=False)
    s = decay[None, :, None, None] * old.astype(jnp.float32) \
        + k[..., :, None] * v[..., None, :]
    o = jnp.einsum("bhd,bhde->bhe", q * scale, s,
                   precision=lax.Precision.HIGHEST)
    s = jnp.where(riding[:, None, None, None], s.astype(state.dtype), old)
    return (jnp.where(riding[:, None, None], o, 0.0),
            lax.dynamic_update_index_in_dim(state, s, layer[0], 0))


def _step_kernel(layer_ref, slots_ref, count_ref, decay_ref, q_ref, k_ref,
                 v_ref, s_ref, o_ref, s_out_ref, *, scale: float):
    from jax.experimental import pallas as pl

    i, count = pl.program_id(0), count_ref[0]
    heads, d = q_ref.shape[1:]

    @pl.when(i == 0)
    def _():        # a slot that does not ride: a finite row, no state read
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((i == 0) & (count == 0))
    def _():        # no rider at all: the one block named goes back as it came
        s_out_ref[...] = s_ref[...]

    @pl.when(i < count)
    def _():
        # q and k arrive as rows (the layout their projections leave them
        # in) and are stood up as columns by products with the identity:
        # exact in float32, a value being the sum of three bfloat16 terms
        eye = (lax.broadcasted_iota(jnp.int32, (d, d), 0)
               == lax.broadcasted_iota(jnp.int32, (d, d), 1)).astype(
                   jnp.bfloat16)

        def columns(x):                             # (heads, d) -> (d, heads)
            out = jnp.zeros((d, x.shape[0]), jnp.float32)
            for _ in range(3):
                term = x.astype(jnp.bfloat16)
                out = out + lax.dot_general(
                    eye, term, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                x = x - term.astype(jnp.float32)
            return out

        slot = slots_ref[i]
        q_cols = columns(q_ref[0]) * scale
        k_cols = columns(k_ref[0])
        for h in range(heads):
            s = decay_ref[h] * s_ref[0, 0, h].astype(jnp.float32) \
                + k_cols[:, h:h + 1] * v_ref[0, h:h + 1, :]
            s_out_ref[0, 0, h] = s.astype(s_out_ref.dtype)
            o_ref[slot, h:h + 1, :] = jnp.sum(q_cols[:, h:h + 1] * s, axis=0,
                                              keepdims=True)


def _step_pallas(layer, slots, count, decay, q, k, v, state, *,
                 scale: float, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    # one program a slot that rides, all its heads (2 MB of float32 state
    # at 32 heads of 128, in and out, each double-buffered); the programs
    # past the last rider are empty grid steps
    row = pl.BlockSpec((1, h, d), lambda i, _, slots, *__: (slots[i], 0, 0))
    slab = pl.BlockSpec(
        (1, 1, h, d, d),
        lambda i, layer, slots, *_: (layer[0], slots[i], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_step_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b,),
            in_specs=[row, row, row, slab],
            # every slot's output row stays in VMEM for the whole call:
            # the rows of slots no program visits are written too (zeros)
            out_specs=[pl.BlockSpec((b, h, d), lambda i, *_: (0, 0, 0)),
                       slab],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, h, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 7 (after the four prefetched scalars and q, k, v) is the
        # state: the same buffer comes out, and a slab no program names is
        # neither read nor written
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="tony_lightning_step",
    )(layer, slots, count, decay, q, k, v, state)


def lightning_step(layer: jax.Array, decay: jax.Array, q: jax.Array,
                   k: jax.Array, v: jax.Array, state: jax.Array,
                   scale: float, riders=None) -> tuple[jax.Array, jax.Array]:
    """One token a slot through one lightning layer. `state`
    (L, B, H, d, d), float32 or kept rounded to bfloat16, is the WHOLE
    state array of the lightning layers, of which slice `layer` (a (1,)
    int32) is read and rewritten, and of it only the slabs of the slots
    that ride: `riders` = `compact_riders(mask)` (absent: every slot
    rides). A slot that does not ride moves no state (its slabs stay
    bit-equal) and its output row is zeros.
    decay (H,) = exp(-slope); q, k, v (B, H, d); q is multiplied by `scale`.
    Returns (o (B, H, d) float32, the state array)."""
    if riders is None:
        riders = compact_riders(jnp.ones(q.shape[:1], bool))
    args = (layer, *riders, decay.astype(jnp.float32),
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), state)
    if _INTERPRET:
        return _step_pallas(*args, scale=scale, interpret=True)
    return lax.platform_dependent(
        *args, tpu=functools.partial(_step_pallas, scale=scale),
        default=functools.partial(_step_jnp, scale=scale))
