"""A hybrid of sparse-attention and linear-attention layers (the
MiniCPM-SALA architecture), for the serving plane.

Two kinds of layer, named by the config's `mixer_types`, in periods of one
`minicpm4` layer followed by R `lightning-attn` layers:

- `minicpm4`: GQA softmax attention without positions (q and k RMS-normed
  per head), dense up to `dense_len` tokens of context and block-sparse
  past it (ops/sparse_attention.py: each query attends to `topk` selected
  blocks of the cache), then an output gate `o * sigmoid(W_g x)`.
- `lightning-attn`: linear attention with a per-head decay
  (ops/lightning.py): q and k RMS-normed per head and rotated, a d x d
  float32 state a head instead of a K/V cache, the output RMS-normed per
  head and gated.

Both are wrapped the muP way: `h = x + c * Mixer(RMSNorm(x))`,
`x' = h + c * MLP(RMSNorm(h))` with `c = scale_depth / sqrt(depth_layers)`;
the embedding is scaled by `scale_emb` and the logits divided by
`dim / dim_model_base`. docs/SERVING.md writes the equations out. A served
token's residual stream is float32 whatever the weights' type (`STREAM`).

The layers are stacked by kind (`params["sparse"]` over periods,
`params["lightning"]` over periods x R) and scanned period by period, so a
compiled step holds each kind's block once whatever the depth.

**The cache is by layer kind** (`empty_cache`): K/V rows and compressed
keys for the sparse layers, a state for the lightning layers. Every leaf
has the slot on axis 1, which is all the engine needs to know to admit
into a slot. `prefill` gives the leaves one prompt writes, `decode_step`
advances every slot that rides by one token: the sparse layers read the
cache in place and hand their new rows out to be written after the layer
loop (models/generate.py `write_cache_rows`), the lightning layers update
the riders' slabs of their slice of the state in place.

There is no training path: the model is served, not trained.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tony_tpu.models.llama import swiglu_mlp
from tony_tpu.ops.attention import flash_attention
from tony_tpu.ops.lightning import (
    compact_riders, lightning_chunk, lightning_step,
)
from tony_tpu.ops.rmsnorm import rms_norm
from tony_tpu.ops.rope import rope_frequencies
from tony_tpu.ops.sparse_attention import (
    SparseSpec, compress_keys, select_decode, sparse_decode_attention,
    sparse_prefill_attention,
)

Params = dict[str, Any]
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# rows of a prompt the MLP (and the norms around it) take at once: the two
# (rows, ffn) intermediates are 134 MB each at 4096 x 16384 in bf16
MLP_ROWS = 4096
# a served token's residual stream is float32 whatever the weights' type.
# Every branch enters the stream scaled by c = scale_depth / sqrt(depth)
# ~ 0.25 under an embedding scaled by 12, so a branch is ~2 % of the stream
# and bfloat16's rounding of the stream, which nothing damps, is 5 % of a
# branch: the largest error of a logit by far (the head divides by 16, two
# candidate tokens lie 0.001 apart). A decode step carries (slots, dim) in
# float32; a prompt's rows stay in the weights' type (they feed only the
# cache, where a row's error is one of thousands averaged) save the last,
# whose logits choose the first served token and which is carried in
# float32 beside them
STREAM = jnp.float32


@dataclass(frozen=True)
class SalaConfig:
    vocab_size: int = 73_448
    dim: int = 4096
    n_layers: int = 32
    mixer_types: tuple = ((SPARSE,) + (LIGHTNING,) * 3) * 8
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    ffn_dim: int = 16_384
    max_seq: int = 524_288
    # the depth `scale_depth` is divided by the root of: the published
    # model's, also where fewer layers are run
    depth_layers: int = 32
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    norm_eps: float = 1e-6
    rope_theta: float = 10_000.0        # the lightning layers' positions
    lightning_heads: int = 32
    sparse: SparseSpec = SparseSpec()
    dtype: Any = jnp.bfloat16
    # what a lightning layer's state is kept in between two tokens (the
    # recurrence itself is float32): bfloat16 halves the 0.8 GB a decode
    # step of 16 slots reads and writes, and rounds the state every token
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        period = self.period
        if self.n_layers % period or tuple(self.mixer_types) != \
                ((SPARSE,) + (LIGHTNING,) * (period - 1)) \
                * (self.n_layers // period):
            raise ValueError(
                f"mixer_types must be n_layers / period repeats of one "
                f"{SPARSE!r} then {LIGHTNING!r} layers; got "
                f"{self.mixer_types} for {self.n_layers} layers")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def period(self) -> int:
        """Layers from one sparse layer to the next."""
        types = tuple(self.mixer_types)
        later = [i for i, t in enumerate(types) if t == SPARSE and i > 0]
        return later[0] if later else max(len(types), 1)

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def has_recurrent_state(self) -> bool:
        """A slot's cache is not a function of its rows alone: a prefix's
        state cannot be shared page by page, nor its rows migrated."""
        return True

    @property
    def moves_state_by_riding(self) -> bool:
        """A decode step reads and rewrites the lightning state of the
        slots that ride and of no other (the engine's
        `state_slots_moved_total`)."""
        return True

    @property
    def depth_scale(self) -> float:
        return self.scale_depth / self.depth_layers ** 0.5

    def sparse_read_blocks(self, context: int) -> tuple[int, int]:
        """(blocks a sparse layer attends, blocks of context) for a token
        whose context, itself included, is `context` tokens: the same two
        up to `dense_len`."""
        return self.sparse.read_blocks(context)

    def dense_context(self, context: int) -> bool:
        """Whether a sparse layer attends to all of such a context."""
        return context <= self.sparse.dense_len

    @property
    def cache_module(self) -> str:
        """The module that implements this config's `empty_cache`,
        `prefill` and `decode_step` (models/generate.py `kind_module`)."""
        return __name__


PRESETS = {
    # test size: two periods, blocks of 8 tokens, dense up to 64
    "sala_tiny": SalaConfig(
        vocab_size=256, dim=64, n_layers=8,
        mixer_types=((SPARSE,) + (LIGHTNING,) * 3) * 2, n_heads=4,
        n_kv_heads=2, head_dim=16, ffn_dim=128, max_seq=512,
        depth_layers=8, dim_model_base=16, lightning_heads=4,
        sparse=SparseSpec(kernel_size=4, kernel_stride=2, init_blocks=1,
                          block_size=8, window_size=16, topk=6,
                          dense_len=64),
        dtype=jnp.float32),
}


def is_sala_preset(name: str) -> bool:
    return name in PRESETS


def get_sala_config(name: str, **overrides) -> SalaConfig:
    return replace(PRESETS[name], **overrides)


get_config = get_sala_config


def init(config: SalaConfig, key: jax.Array) -> Params:
    """What `python -m tony_tpu.serve` draws a preset's weights with:
    `sala_init`, looked up when called."""
    return sala_init(config, key)


def lightning_slopes(config: SalaConfig) -> np.ndarray:
    """(periods, R, heads) float32: the decay slope of every lightning
    layer's heads, `2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)` with l
    the layer's index among all L layers as run (Lightning Attention's
    slopes and per-layer factor)."""
    h = config.lightning_heads
    base = 2.0 ** (-8.0 * (np.arange(h) + 1) / h)
    layers = np.arange(config.n_layers).reshape(config.n_periods,
                                                config.period)[:, 1:]
    factor = 1.0 - layers / max(config.n_layers - 1, 1) + 1e-5
    return (factor[..., None] * base).astype(np.float32)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def sala_init(config: SalaConfig, key: jax.Array) -> Params:
    """Scaled-normal init (embedding N(0, 1), every matrix N(0, 1/fan_in),
    norms 1); a kind's layers stacked on leading axes."""
    d, f, hd = config.dim, config.ffn_dim, config.head_dim
    nh, nkv, lh = config.n_heads, config.n_kv_heads, config.lightning_heads
    p, r = config.n_periods, config.period - 1
    k_embed, k_out, k_sparse, k_light = jax.random.split(key, 4)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            config.dtype)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    si, sf = d ** -0.5, f ** -0.5
    ks, kl = jax.random.split(k_sparse, 8), jax.random.split(k_light, 8)
    return {
        "embed": normal(k_embed, (config.vocab_size, d), 1.0),
        "sparse": {
            "wq": normal(ks[0], (p, d, nh * hd), si),
            "wk": normal(ks[1], (p, d, nkv * hd), si),
            "wv": normal(ks[2], (p, d, nkv * hd), si),
            "wo": normal(ks[3], (p, nh * hd, d), (nh * hd) ** -0.5),
            "w_og": normal(ks[4], (p, d, nh * hd), si),
            "w_gate": normal(ks[5], (p, d, f), si),
            "w_up": normal(ks[6], (p, d, f), si),
            "w_down": normal(ks[7], (p, f, d), sf),
            "q_norm": ones(p, hd), "k_norm": ones(p, hd),
            "attn_norm": ones(p, d), "mlp_norm": ones(p, d),
        },
        "lightning": {
            "wq": normal(kl[0], (p, r, d, lh * hd), si),
            "wk": normal(kl[1], (p, r, d, lh * hd), si),
            "wv": normal(kl[2], (p, r, d, lh * hd), si),
            "wo": normal(kl[3], (p, r, lh * hd, d), (lh * hd) ** -0.5),
            "w_og": normal(kl[4], (p, r, d, lh * hd), si),
            "w_gate": normal(kl[5], (p, r, d, f), si),
            "w_up": normal(kl[6], (p, r, d, f), si),
            "w_down": normal(kl[7], (p, r, f, d), sf),
            "q_norm": ones(p, r, hd), "k_norm": ones(p, r, hd),
            "o_norm": ones(p, r, hd),
            "attn_norm": ones(p, r, d), "mlp_norm": ones(p, r, d),
        },
        "final_norm": ones(d),
        "output": normal(k_out, (d, config.vocab_size), si),
    }


def empty_cache(config: SalaConfig, n_slots: int, token_budget: int
                ) -> dict[str, jax.Array]:
    """The serving cache by layer kind, slots on axis 1 of every leaf:
    `k`, `v` (periods, slots, kv heads, budget, hd), the compressed keys
    `ck` (.., budget / kernel_stride, hd) and the ring `tail` (..,
    kernel_size, hd) of the last K rows, from which a decode step makes
    the compressed key it completes, of the sparse layers; `state`
    (periods x R, slots, heads, hd, hd) in `state_dtype` (float32) of the
    lightning layers."""
    sp = config.sparse
    if token_budget % sp.block_size:
        raise ValueError(f"token_budget {token_budget} must be whole "
                         f"blocks of {sp.block_size} tokens")
    p, r = config.n_periods, config.period - 1
    rows = (p, n_slots, config.n_kv_heads, token_budget, config.head_dim)
    hd = config.head_dim
    return {
        "k": jnp.zeros(rows, config.dtype),
        "v": jnp.zeros(rows, config.dtype),
        "ck": jnp.zeros(rows[:3] + (token_budget // sp.kernel_stride, hd),
                        config.dtype),
        "tail": jnp.zeros(rows[:3] + (sp.kernel_size, hd), config.dtype),
        "state": jnp.zeros((p * r, n_slots, config.lightning_heads, hd, hd),
                           config.state_dtype),
    }


# ---------------------------------------------------------------------------
# pieces shared by prefill and decode
# ---------------------------------------------------------------------------

def _head_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last (head) dimension, statistics in float32."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


def _norm(x, weight, config: SalaConfig):
    """RMSNorm of the stream's rows, in the type the matmuls take."""
    return rms_norm(x, weight, config.norm_eps).astype(config.dtype)


def _finish_layer(x, mixed, layer: Params, config: SalaConfig):
    """Residual of the mixer, then the MLP half. x (rows, dim) the stream
    in its own type, mixed (rows, dim) the mixer's output."""
    c = config.depth_scale
    h = x + (c * mixed.astype(STREAM)).astype(x.dtype)
    m = swiglu_mlp(_norm(h, layer["mlp_norm"], config)[None], layer)[0]
    return h + (c * m.astype(STREAM)).astype(x.dtype)


def _by_rows(fn, *arrays):
    """fn over the rows of (rows, .) arrays, MLP_ROWS at a time: what a
    prompt's float32 and ffn-wide intermediates are held for."""
    n = arrays[0].shape[0]
    if n <= MLP_ROWS:
        return fn(*arrays)
    pad = (-n) % MLP_ROWS
    blocks = tuple(jnp.pad(a, ((0, pad), (0, 0))).reshape(
        -1, MLP_ROWS, a.shape[1]) for a in arrays)
    out = lax.map(lambda b: fn(*b), blocks)
    return out.reshape(-1, out.shape[-1])[:n]


def _gated_finish(x, last, attn, layer: Params, config: SalaConfig,
                  norm=None):
    """The rest of a layer after its mixer's core, row block by row block:
    (the per-head RMSNorm `norm` of the lightning output,) the output gate
    on the normed input, the output projection, the residual and the MLP
    half. x (rows, dim) the layer's input and `last` (1, dim) its last row
    in float32 (see STREAM); attn (rows, heads * hd). Returns both, the
    last row of x being `last` rounded."""
    def rows(x, a):
        if norm is not None:
            a = _head_norm(a.reshape(a.shape[0], -1, config.head_dim), norm,
                           config.norm_eps).reshape(a.shape)
        h = _norm(x, layer["attn_norm"], config)
        a = a * jax.nn.sigmoid(h @ layer["w_og"])
        return _finish_layer(x, a @ layer["wo"], layer, config)

    last = rows(last, attn[-1:])
    x = _by_rows(rows, x, attn)
    return lax.dynamic_update_slice_in_dim(
        x, last.astype(x.dtype), x.shape[0] - 1, 0), last


def _logits(x, params: Params, config: SalaConfig) -> jax.Array:
    x = _norm(x, params["final_norm"], config)
    logits = jnp.einsum("bd,dv->bv", x, params["output"],
                        preferred_element_type=jnp.float32)
    return logits / (config.dim / config.dim_model_base)


def _rope_tables(config: SalaConfig, seq: int):
    return rope_frequencies(config.head_dim, seq, config.rope_theta)


# ---------------------------------------------------------------------------
# prefill: one prompt
# ---------------------------------------------------------------------------

def _project(h, w, n_heads: int, norm, config: SalaConfig, rope=None,
             scale: float = 1.0):
    """(rows, dim) x (dim, heads * hd) -> (rows, heads * hd): a head's
    columns stay side by side, which is how the kernels take them, so a
    prompt's heads are never transposed. Per head, RMS-normed by the weight
    `norm` and rotated by `rope` = (cos, sin) if given, then scaled; that
    part runs MLP_ROWS rows at a time, so its float32 is a row block's."""
    y = h @ w
    if norm is None:
        return y
    hd = config.head_dim

    def finish(rows, *tables):
        x = _head_norm(rows.reshape(rows.shape[0], n_heads, hd), norm,
                       config.norm_eps).astype(jnp.float32)
        if tables:
            x1, x2 = jnp.split(x, 2, axis=-1)
            c, s = (t[:, None, :] for t in tables)
            x = jnp.concatenate((x1 * c - x2 * s, x1 * s + x2 * c), axis=-1)
        return (x * scale).astype(rows.dtype).reshape(rows.shape)

    return _by_rows(finish, y, *(rope or ()))


def _heads_first(x, n_heads: int):
    """(rows, heads * hd) -> (heads, rows, hd)."""
    return x.reshape(x.shape[0], n_heads, -1).transpose(1, 0, 2)


def _sparse_prefill(x, last, layer: Params, config: SalaConfig):
    n = x.shape[0]
    nh, nkv = config.n_heads, config.n_kv_heads
    h = _norm(x, layer["attn_norm"], config)
    q = _project(h, layer["wq"], nh, layer["q_norm"], config)
    k = _heads_first(_project(h, layer["wk"], nkv, layer["k_norm"], config),
                     nkv)
    v = _heads_first(_project(h, layer["wv"], nkv, None, config), nkv)
    if n <= config.sparse.dense_len:
        attn = flash_attention(_heads_first(q, nh)[None], k[None], v[None],
                               True)[0]
        attn = attn.transpose(1, 0, 2).reshape(n, -1)
    else:
        attn = sparse_prefill_attention(q, k, v, config.sparse)
    return _gated_finish(x, last, attn, layer, config), (k, v)


def _lightning_prefill(x, last, layer: Params, slopes, cos, sin,
                       config: SalaConfig):
    lh, hd = config.lightning_heads, config.head_dim
    h = _norm(x, layer["attn_norm"], config)
    q = _project(h, layer["wq"], lh, layer["q_norm"], config, (cos, sin),
                 hd ** -0.5)
    k = _project(h, layer["wk"], lh, layer["k_norm"], config, (cos, sin))
    v = _project(h, layer["wv"], lh, None, config)
    o, state = lightning_chunk(q, k, v, slopes)
    return _gated_finish(x, last, o, layer, config, layer["o_norm"]), state


def prefill(params: Params, tokens: jax.Array, config: SalaConfig,
            cache_len: int) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One prompt (tokens (1, P)) through the model: the last position's
    logits (1, V) and what its admission writes into a slot — every leaf
    of `empty_cache` with one slot (K/V rows and compressed keys padded to
    `cache_len`, the state after the last token)."""
    if tokens.shape[0] != 1:
        raise ValueError("a recurrent-state model prefills one prompt at "
                         f"a time; got a batch of {tokens.shape[0]}")
    n = tokens.shape[1]
    sp = config.sparse
    cos, sin = _rope_tables(config, n)
    x = jnp.take(params["embed"], tokens[0], axis=0).astype(STREAM) \
        * config.scale_emb
    x, last = x.astype(config.dtype), x[-1:]

    # a period's lightning layers are indexed out of the whole stack by
    # the inner loop itself (see decode_step)
    r = config.period - 1
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                        params["lightning"])
    slopes = jnp.asarray(lightning_slopes(config)).reshape(
        -1, config.lightning_heads)

    def period(stream, xs):
        sparse, p = xs
        stream, (k, v) = _sparse_prefill(*stream, sparse, config)

        def one(stream, i):
            index = p * r + i
            layer = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, index, 0, False), flat)
            return _lightning_prefill(
                *stream, layer,
                lax.dynamic_index_in_dim(slopes, index, 0, False), cos, sin,
                config)

        stream, states = lax.scan(one, stream, jnp.arange(r, dtype=jnp.int32))
        return stream, (k, v, compress_keys(k, sp), k[:, ring], states)

    # the ring of the last kernel_size K rows: entry j holds the last
    # position p < n with p % kernel_size == j (rows before the prompt's
    # start are read by no complete window)
    j = np.arange(sp.kernel_size)
    ring = np.maximum(n - 1 - (n - 1 - j) % sp.kernel_size, 0)
    (_, last), (ks, vs, cks, tails, states) = lax.scan(
        period, (x, last), (params["sparse"],
                            jnp.arange(config.n_periods, dtype=jnp.int32)))
    logits = _logits(last, params, config)

    def padded(a, length):          # (periods, G, rows, hd), slot axis in
        return jnp.pad(a, ((0, 0), (0, 0), (0, length - a.shape[2]),
                           (0, 0)))[:, None]

    states = states.reshape((-1, 1) + states.shape[2:])
    return logits, {"k": padded(ks, cache_len), "v": padded(vs, cache_len),
                    "ck": padded(cks, cache_len // sp.kernel_stride),
                    "tail": tails[:, None], "state": states}


# ---------------------------------------------------------------------------
# decode: one token a slot
# ---------------------------------------------------------------------------

def _completed_window(pos, sp: SparseSpec):
    """Which compressed key the token at `pos` completes, if it does, and
    where its K row stands in the ring of the last kernel_size rows:
    (index j (B,), flag (B,), own (B, kernel_size) the ring entry
    pos % kernel_size). The same for every sparse layer of a step."""
    ks, st = sp.kernel_size, sp.kernel_stride
    flag = (pos + 1 >= ks) & ((pos + 1 - ks) % st == 0)
    own = jnp.arange(ks, dtype=jnp.int32)[None, :] == (pos % ks)[:, None]
    return jnp.maximum(pos + 1 - ks, 0) // st, flag, own


def _window_mean(tail, k_new, own):
    """The compressed key (B, G, hd) of the window the new token ends.
    `tail` (B, G, kernel_size, hd) is the slot's ring of its last
    kernel_size K rows (position t at t % kernel_size): the window's mean
    is the ring's, with the new token's row where it will be written
    (`own`)."""
    rows = jnp.where(own[:, None, :, None], k_new[:, :, None, :], tail)
    mean = jnp.sum(rows.astype(jnp.float32), axis=2) / tail.shape[2]
    return mean.astype(k_new.dtype)


def _sparse_decode(x, layer: Params, p, cache, pos, window, riders,
                   config: SalaConfig):
    b = x.shape[0]
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    sp = config.sparse
    h = _norm(x, layer["attn_norm"], config)
    def heads(w, n):       # the barrier: see _lightning_decode
        return lax.optimization_barrier(h @ w).reshape(b, n, hd)

    q = _head_norm(heads(layer["wq"], nh), layer["q_norm"], config.norm_eps)
    k = _head_norm(heads(layer["wk"], nkv), layer["k_norm"],
                   config.norm_eps).astype(cache["k"].dtype)
    v = heads(layer["wv"], nkv).astype(cache["v"].dtype)
    qg = q.reshape(b, nkv, nh // nkv, hd)
    layer_index = jnp.reshape(p, (1,))
    with jax.named_scope("tony_sparse_select"):
        tail = lax.dynamic_index_in_dim(cache["tail"], p, 0, keepdims=False)
        j, flag, own = window
        new = (j, flag, _window_mean(tail, k, own))
        # no blocks are selected for a slot that does not ride (count 0):
        # its token attends to its own row alone
        ids, counts = select_decode(layer_index, qg, cache["ck"], pos, sp,
                                    new, riders)
    attn = sparse_decode_attention(layer_index, ids, counts, pos, qg, k, v,
                                   cache["k"], cache["v"], sp)
    attn = attn.reshape(b, nh * hd) * jax.nn.sigmoid(h @ layer["w_og"])
    x = _finish_layer(x, attn @ layer["wo"], layer, config)
    return x, {"k": k[:, :, None, :], "v": v[:, :, None, :],
               "ck": new[2][:, :, None, :]}


def _lightning_decode(x, state, layer: Params, decay, index, cos, sin, pos,
                      riders, config: SalaConfig):
    b = x.shape[0]
    lh, hd = config.lightning_heads, config.head_dim
    h = _norm(x, layer["attn_norm"], config)

    def heads(w):
        # the barrier keeps the split into heads out of the matmul: folded
        # into it, the compiler wants the weight transposed and copies the
        # whole stack of every layer's, 1.2 GB a step
        return lax.optimization_barrier(h @ w).reshape(b, lh, hd)

    def rotate(x):              # (B, H, hd) at each slot's own position
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        c, s = cos[pos][:, None, :], sin[pos][:, None, :]
        return jnp.concatenate((x1 * c - x2 * s, x1 * s + x2 * c), axis=-1)

    q = rotate(_head_norm(heads(layer["wq"]), layer["q_norm"],
                          config.norm_eps))
    k = rotate(_head_norm(heads(layer["wk"]), layer["k_norm"],
                          config.norm_eps).astype(config.dtype))
    v = heads(layer["wv"])
    # k is stored nowhere: it is rounded as the prefill's is, so that a
    # token's state is the same whichever path made it
    o, state = lightning_step(jnp.reshape(index, (1,)), decay,
                              q.astype(config.dtype), k.astype(config.dtype),
                              v, state, hd ** -0.5, riders)
    o = _head_norm(o, layer["o_norm"], config.norm_eps).astype(config.dtype)
    o = o.reshape(b, lh * hd) * jax.nn.sigmoid(h @ layer["w_og"])
    return _finish_layer(x, o @ layer["wo"], layer, config), state


def decode_step(params: Params, config: SalaConfig,
                cache: dict[str, jax.Array], token: jax.Array,
                pos: jax.Array, attend=None
                ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One token a slot. token (B,) int32 at positions pos (B,) (the rows
    each slot's cache holds); `attend` (B,) is the engine's riding mask: 0
    for a slot that does not ride (absent: every slot rides). Such a slot
    moves no lightning state (its slabs stay bit-equal) and reads no block
    of the sparse layers' cache; it still writes its token's K/V, `ck` and
    `tail` rows where it is parked, and its logits are thrown away. What
    a riding slot attends to is these layers' own rule, not `attend`'s
    length. Returns (logits (B, V), the cache with the token's K/V rows,
    any compressed key it completed and the riders' advanced states
    written)."""
    from tony_tpu.models.generate import write_cache_rows

    sp = config.sparse
    r = config.period - 1
    budget = cache["k"].shape[3]
    cos, sin = _rope_tables(config, budget)
    x = jnp.take(params["embed"], token, axis=0).astype(STREAM) \
        * config.scale_emb
    riding = jnp.ones(token.shape, bool) if attend is None else attend > 0
    riders = compact_riders(riding)     # once a step, not once a layer
    window = _completed_window(pos, sp)
    decays = jnp.exp(-jnp.asarray(lightning_slopes(config)))

    # a period's lightning layers are indexed out of the whole stack by
    # the inner loop itself: handed down as the outer scan's slice they
    # would be copied, a period's 1.7 GB of them a step
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                        params["lightning"])
    decays = decays.reshape((-1,) + decays.shape[2:])

    def period(carry, xs):
        x, state = carry
        sparse, p = xs
        x, rows = _sparse_decode(x, sparse, p, cache, pos, window, riders,
                                 config)

        def one(carry, i):
            x, state = carry
            index = p * r + i
            layer = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, index, 0, False), flat)
            x, state = _lightning_decode(
                x, state, layer,
                lax.dynamic_index_in_dim(decays, index, 0, False), index,
                cos, sin, pos, riders, config)
            return (x, state), None

        (x, state), _ = lax.scan(one, (x, state),
                                 jnp.arange(r, dtype=jnp.int32))
        return (x, state), rows

    (x, state), rows = lax.scan(
        period, (x, cache["state"]),
        (params["sparse"], jnp.arange(config.n_periods, dtype=jnp.int32)))
    # the new rows of all sparse layers, written once and in place; a
    # token that completes no window writes its compressed key to the last
    # entry, which no position ever reads
    j, done, _ = window
    at = jnp.where(done, j, cache["ck"].shape[3] - 1)
    written = write_cache_rows(
        {name: cache[name] for name in ("k", "v", "ck", "tail")},
        {**rows, "tail": rows["k"]},
        {"k": pos, "v": pos, "ck": at, "tail": pos % sp.kernel_size})
    return _logits(x, params, config), {**written, "state": state}
