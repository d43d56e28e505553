"""Grouped matmul of an expert layer: rows sorted by expert, an expert's
weights read once, and only if it has rows.

The rows of all experts lie in one (rows, K) array, group by group, each
group starting at a multiple of `tile_rows` (models/moe.py `group_rows`
lays them out and pads a group's last tile with zero rows). A tile of rows
therefore belongs to one expert, named by the scalar-prefetched
`tile_expert`; `tiles_used` says how many tiles hold rows at all. The
kernel `tony_expert_matmul` runs one grid over (column tile, row tile),
row tiles innermost:

- the weight block of a step is `(layer, tile_expert[row tile], :, column
  tile)` of the WHOLE stacked leaf (layers, experts, K, N), which stays in
  HBM where it lies: nothing is sliced out of it for the call, so a layer
  loop that closes over the stack copies no expert's weights. Consecutive
  row tiles of one expert name the same block and it is not fetched
  again; an expert without rows owns no tile, so it costs neither a DMA
  nor a grid step;
- a row tile past `tiles_used` names the blocks of the last used one
  (nothing moves) and computes nothing: a decode step that few slots ride
  pays grid steps for the layout's slack, not bytes.

With two weight leaves the call is the gated pair of a SwiGLU,
`silu(x W1) * (x W3)`, from one read of the rows. Rows that come in
float32 are multiplied as two bfloat16 halves stacked in one left operand
(`split_rows`): the weights pass through the MXU once, and the product
carries 16 bits of the rows' mantissa. One kernel serves an
admission (hundreds of rows an expert, `tile_rows` up to 256) and a decode
step (0-4 rows an expert, `tile_rows` 16).

Dispatch is by platform at lowering time, as in ops/attention.py: the
kernel on a TPU, the same arithmetic in plain jnp elsewhere (a loop over
the tiles, each times its expert's block: the tests' reference).
TONY_FLASH_INTERPRET=1 runs the kernel interpreted on the CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.ops.attention import _INTERPRET

# columns of the result one step computes: a (2048, 512) bf16 weight block
# is 2 MB, two leaves and two buffers of it 8 MB of VMEM
TILE_COLS = 512
MIN_TILE_ROWS, MAX_TILE_ROWS = 16, 256
VMEM_LIMIT_BYTES = 48 * 2 ** 20


def tile_rows_for(rows: int, n_experts: int) -> int:
    """Rows a tile holds for `rows` routed rows over `n_experts`: the mean
    group rounded up to a power of two, at least a bf16 tile's 16 rows and
    at most 256 (a (256, 2048) block of rows is 1 MB)."""
    mean = max(1, -(-rows // n_experts))
    tile = 1 << (mean - 1).bit_length()
    return min(max(tile, MIN_TILE_ROWS), MAX_TILE_ROWS)


def padded_rows(rows: int, n_experts: int, tile_rows: int) -> int:
    """Rows of the grouped layout, whatever the routing: every group may
    waste all but one row of its last tile."""
    worst = rows + n_experts * (tile_rows - 1)
    return -(-worst // tile_rows) * tile_rows


def _col_tile(n: int) -> int:
    return TILE_COLS if n % TILE_COLS == 0 else n


def split_rows(x, dtype):
    """Rows wider than the weights' type as the halves the MXU takes: x
    (rows, K) float32 -> (2 rows, K) in `dtype`, the rows cut to their
    upper 16 bits on top of what the cut left, so that ONE pass of a
    weight block through the MXU multiplies both and their sum carries 16
    bits of the rows' mantissa (bfloat16 alone carries 8: the 0.1 % that
    moves a router's score past its neighbour's). The upper half is cut
    with a bit mask, not rounded by a convert: XLA's TPU compiler reads
    `x - float32(bfloat16(x))` as 0 (it may keep excess precision), and
    the lower half it then multiplied was 0 on the chip while every CPU
    test agreed with float32 (PERF.md, PR 37). Rows already in `dtype`
    come back as they are."""
    if x.dtype == dtype:
        return x
    if (x.dtype, jnp.dtype(dtype)) != (jnp.float32, jnp.bfloat16):
        raise ValueError(f"rows of {x.dtype} over weights of {dtype}")
    hi = lax.bitcast_convert_type(
        lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000),
        x.dtype)
    return jnp.concatenate([hi.astype(dtype), (x - hi).astype(dtype)],
                           axis=0)


def split_dot(x, lhs, w, transposed: bool = False):
    """`lhs` = split_rows(x, w.dtype) times w (K, N) — or (N, K) if
    `transposed` — accumulated in float32 and the two halves added:
    (rows, N) float32."""
    out = lax.dot_general(lhs, w, (((1,), (1 if transposed else 0,)),
                                   ((), ())),
                          preferred_element_type=jnp.float32)
    if lhs.shape[0] == x.shape[0]:
        return out
    return out[:x.shape[0]] + out[x.shape[0]:]


def _tile_product(x, weights):
    """One tile's rows times its expert's block(s), in float32: the plain
    product, or the gated pair of a SwiGLU."""
    lhs = split_rows(x, weights[0].dtype)
    out = split_dot(x, lhs, weights[0])
    if len(weights) == 2:
        out = jax.nn.silu(out) * split_dot(x, lhs, weights[1])
    return out


def _expert_matmul_kernel(layer_ref, expert_ref, used_ref, x_ref, *refs):
    from jax.experimental import pallas as pl

    w_refs, o_ref = refs[:-1], refs[-1]

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        out = _tile_product(x_ref[...], [w[...] for w in w_refs])
        o_ref[...] = out.astype(o_ref.dtype)


def _matmul_pallas(layer, tile_expert, tiles_used, x, *weights,
                   tile_rows: int, out_dtype, interpret: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, k = x.shape
    n = weights[0].shape[-1]
    cols = _col_tile(n)

    def row_tile(i, used):
        # a tile past the used ones names the last used one's blocks
        return jnp.minimum(i, jnp.maximum(used[0] - 1, 0))

    def x_map(j, i, layer, expert, used):
        return row_tile(i, used), 0

    def w_map(j, i, layer, expert, used):
        return layer[0], expert[row_tile(i, used)], 0, j

    def o_map(j, i, layer, expert, used):
        return row_tile(i, used), j

    return pl.pallas_call(
        _expert_matmul_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // cols, rows // tile_rows),
            in_specs=[pl.BlockSpec((tile_rows, k), x_map)]
            + [pl.BlockSpec((None, None, k, cols), w_map)] * len(weights),
            out_specs=pl.BlockSpec((tile_rows, cols), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="tony_expert_matmul",
    )(layer, tile_expert, tiles_used, x, *weights)


def _matmul_jnp(layer, tile_expert, tiles_used, x, *weights,
                tile_rows: int, out_dtype):
    """The plain body: tile by tile, the tile's rows times its expert's
    block. Rows of tiles past `tiles_used` come out zero."""
    rows, k = x.shape

    def one(i):
        e = tile_expert[jnp.minimum(i, jnp.maximum(tiles_used[0] - 1, 0))]
        w = [lax.dynamic_index_in_dim(
            lax.dynamic_index_in_dim(leaf, layer[0], 0, False), e, 0, False)
            for leaf in weights]
        got = _tile_product(
            lax.dynamic_slice_in_dim(x, i * tile_rows, tile_rows), w)
        return jnp.where(i < tiles_used[0], got, 0.0).astype(out_dtype)

    out = lax.map(one, jnp.arange(rows // tile_rows, dtype=jnp.int32))
    return out.reshape(rows, -1)


def expert_matmul(layer: jax.Array, tile_expert: jax.Array,
                  tiles_used: jax.Array, x: jax.Array, *weights: jax.Array,
                  tile_rows: int, out_dtype=None) -> jax.Array:
    """x (rows, K), grouped by expert in tiles of `tile_rows` rows, times
    each tile's expert's (K, N) block of `weights[0]` (layers, experts, K,
    N), of which only `layer` (a (1,) int32) is read; with a second leaf,
    `silu(x W1) * (x W3)`. `tile_expert` (rows / tile_rows,) int32 names a
    tile's expert, `tiles_used` (1,) int32 how many tiles hold rows: the
    result's rows past them are undefined (the caller gathers none).
    Rows wider than the weights (float32 over bfloat16) are multiplied as
    two halves (`split_rows`). Accumulates in float32; returns (rows, N)
    in `out_dtype` (x's)."""
    if not 1 <= len(weights) <= 2:
        raise ValueError("one weight leaf, or the gated pair of a SwiGLU")
    if x.shape[0] % tile_rows:
        raise ValueError(f"{x.shape[0]} rows are not whole tiles of "
                         f"{tile_rows}")
    kw = dict(tile_rows=tile_rows, out_dtype=out_dtype or x.dtype)
    args = (layer, tile_expert, tiles_used, x) + weights
    plain = functools.partial(_matmul_jnp, **kw)
    kernel = functools.partial(_matmul_pallas, **kw)
    if _INTERPRET:
        return kernel(*args, interpret=True)
    return lax.platform_dependent(*args, tpu=kernel, default=plain)
