"""A hybrid of gated short-convolution and GQA attention layers with dense
and sparse-expert MLPs (the LFM2-MoE architecture, HF `lfm2_moe`), for the
serving plane.

Every layer is `h = x + Op(RMSNorm(x)); x' = h + FFN(RMSNorm(h))`. The
operator is named by the config's `layer_types`:

- `conv`: `[B, C, z] = W_in u`; `y = C * conv1d(B * z)`, a causal
  depthwise convolution of `conv_kernel` taps without bias (tap j
  multiplies the gated input at t - (K - 1) + j); `out = W_out y`.
- `full_attention`: GQA softmax attention, q and k RMS-normed per head and
  rotated (half-split RoPE), causal: flash attention for a prompt,
  `ops/cache_attention.py` for a decode step.

The first `n_dense_layers` layers have a dense SwiGLU MLP, the others an
expert MLP: `top_k` of `n_experts` by sigmoid score in float32
(models/moe.py `grouped_expert_mlp`: no token is dropped; one grouped
matmul, `tony_expert_matmul`, reads an expert's weights only if it has
rows). The embedding is tied to the output head.

The layers run are the dense prefix (all `conv`) and then whole periods of
one `full_attention` layer followed by R `conv` layers; an order that is
not that is refused. Layers are stacked by kind and scanned (the dense
prefix, then period by period), so a compiled step holds each kind's body
once whatever the depth; the expert stacks are closed over whole and read
by layer index, never sliced.

**The cache is by layer kind** (`empty_cache`): K/V rows `k`, `v` for the
attention layers only, and `conv`, the last `conv_kernel` gated inputs
`B * z` of every conv layer. Every leaf has the slot on axis 1.
`decode_step` takes the engine's riding mask (`attend`: 0 = the slot does
not ride): such a slot reads no cache row and its row reaches no expert.
`STEP_COUNTS` names what `decode_step_counted` counts on the device.

A token's residual stream is float32 whatever the weights' type
(`STREAM`), the conv state with it, and the stream's rows meet a bfloat16
weight as two bfloat16 halves in one matmul (`_matmul`): the router
scores the stream's own normed rows, and a score moved by bfloat16's
rounding picks another expert, whose whole output then differs.

There is no training path: the model is served, not trained.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.moe import RouterSpec, grouped_expert_mlp
from tony_tpu.ops.attention import flash_attention
from tony_tpu.ops.cache_attention import cache_attention
from tony_tpu.ops.expert_matmul import split_dot, split_rows
from tony_tpu.ops.rmsnorm import rms_norm
from tony_tpu.ops.rope import apply_rope, rope_frequencies

Params = dict[str, Any]
CONV, ATTENTION = "conv", "full_attention"
STREAM = jnp.float32
# this model's K/V rows take the int8 form of models/generate.py
INT8_CACHE = True


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65_536
    dim: int = 2048
    n_layers: int = 40
    layer_types: tuple = (CONV, CONV) + (ATTENTION, CONV, CONV, CONV) * 9 \
        + (ATTENTION, CONV)
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    ffn_dim: int = 11_776           # the dense layers' MLP
    expert_dim: int = 1536
    n_experts: int = 64
    top_k: int = 4
    norm_topk: bool = True
    routed_scale: float = 1.0
    conv_kernel: int = 3
    max_seq: int = 128_000
    norm_eps: float = 1e-5
    rope_theta: float = 1_000_000.0
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        types, nd = tuple(self.layer_types), self.n_dense_layers
        period = (ATTENTION,) + (CONV,) * (self.period - 1)
        rest = len(types) - nd
        if len(types) != self.n_layers or types[:nd] != (CONV,) * nd \
                or rest % len(period) or types[nd:] != period * (
                    rest // len(period)):
            raise ValueError(
                f"layer_types must be {nd} dense {CONV!r} layers and then "
                f"whole periods of one {ATTENTION!r} and its {CONV!r} "
                f"layers; got {types} for {self.n_layers} layers")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def period(self) -> int:
        """Layers from one attention layer to the next."""
        at = [i for i, t in enumerate(self.layer_types) if t == ATTENTION]
        if len(at) > 1:
            return at[1] - at[0]
        return max(len(self.layer_types) - self.n_dense_layers, 1)

    @property
    def n_periods(self) -> int:
        return (self.n_layers - self.n_dense_layers) // self.period

    @property
    def n_conv_layers(self) -> int:
        return self.n_dense_layers + self.n_periods * (self.period - 1)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kv_pack(self) -> int:
        """K/V heads laid side by side in one cache row: a row of heads of
        64 would be padded to the chip's 128 lanes, twice the bytes kept
        and read, so two heads share a row of 128."""
        return math.gcd(max(128 // self.head_dim, 1), self.n_kv_heads)

    @property
    def router(self) -> RouterSpec:
        return RouterSpec(self.n_experts, self.top_k, self.norm_topk,
                          self.routed_scale)

    @property
    def has_recurrent_state(self) -> bool:
        """A slot's cache is not a function of its K/V rows alone."""
        return True

    @property
    def reads_cache_by_attend(self) -> bool:
        """The attention layers read K/V rows through
        ops/cache_attention.py, by the engine's attend lengths."""
        return True

    @property
    def cache_module(self) -> str:
        """The module that implements this config's `empty_cache`,
        `prefill` and `decode_step` (models/generate.py
        `kind_module`)."""
        return __name__


PRESETS = {
    # test size: two dense layers and two periods of [attention, conv x2]
    "lfm2_tiny": Lfm2Config(
        vocab_size=256, dim=64, n_layers=8,
        layer_types=(CONV, CONV) + (ATTENTION, CONV, CONV) * 2,
        n_heads=4, n_kv_heads=2, head_dim=16, ffn_dim=128, expert_dim=32,
        n_experts=8, top_k=2, max_seq=512, dtype=jnp.float32),
}


def get_config(name: str, **overrides) -> Lfm2Config:
    return replace(PRESETS[name], **overrides)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def lfm2_init(config: Lfm2Config, key: jax.Array) -> Params:
    """Scaled-normal init (every matrix N(0, 1/fan_in), a conv's taps
    N(0, 1/taps), norms 1, the routing bias 0); a kind's layers stacked
    on leading axes. The head is the embedding, so the embedding is drawn
    as a head is, N(0, 1/dim): at N(0, 1) a token's own logit (its row's
    dot with itself, the stream's residual path) stands 40 deviations
    above all others and every stream repeats its last token whatever the
    layers do."""
    c = config
    d, hd, nh, nkv = c.dim, c.head_dim, c.n_heads, c.n_kv_heads
    nd, p, r, lm = (c.n_dense_layers, c.n_periods, c.period - 1,
                    c.n_expert_layers)
    k_embed, k_dense, k_attn, k_conv, k_moe = jax.random.split(key, 5)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            c.dtype)

    def ones(*shape):
        return jnp.ones(shape, jnp.float32)

    def conv_op(keys, *lead):
        return {"w_in": normal(keys[0], lead + (d, 3 * d), d ** -0.5),
                "w_conv": normal(keys[1], lead + (c.conv_kernel, d),
                                 c.conv_kernel ** -0.5),
                "w_out": normal(keys[2], lead + (d, d), d ** -0.5),
                "op_norm": ones(*lead, d), "ffn_norm": ones(*lead, d)}

    kd, ka = jax.random.split(k_dense, 6), jax.random.split(k_attn, 4)
    kc, km = jax.random.split(k_conv, 3), jax.random.split(k_moe, 4)
    return {
        "embed": normal(k_embed, (c.vocab_size, d), d ** -0.5),
        "dense": {
            **conv_op(kd[:3], nd),
            "w1": normal(kd[3], (nd, d, c.ffn_dim), d ** -0.5),
            "w3": normal(kd[4], (nd, d, c.ffn_dim), d ** -0.5),
            "w2": normal(kd[5], (nd, c.ffn_dim, d), c.ffn_dim ** -0.5),
        },
        "attn": {
            "wq": normal(ka[0], (p, d, nh * hd), d ** -0.5),
            "wk": normal(ka[1], (p, d, nkv * hd), d ** -0.5),
            "wv": normal(ka[2], (p, d, nkv * hd), d ** -0.5),
            "wo": normal(ka[3], (p, nh * hd, d), (nh * hd) ** -0.5),
            "q_norm": ones(p, hd), "k_norm": ones(p, hd),
            "op_norm": ones(p, d), "ffn_norm": ones(p, d),
        },
        "conv": conv_op(kc, p, r),
        "moe": {
            "router": normal(km[0], (lm, d, c.n_experts), d ** -0.5),
            "expert_bias": jnp.zeros((lm, c.n_experts), jnp.float32),
            "w1": normal(km[1], (lm, c.n_experts, d, c.expert_dim),
                         d ** -0.5),
            "w3": normal(km[2], (lm, c.n_experts, d, c.expert_dim),
                         d ** -0.5),
            "w2": normal(km[3], (lm, c.n_experts, c.expert_dim, d),
                         c.expert_dim ** -0.5),
        },
        "final_norm": ones(d),
    }


def init(config: Lfm2Config, key: jax.Array) -> Params:
    """What `python -m tony_tpu.serve` draws a preset's weights with."""
    return lfm2_init(config, key)


def empty_cache(config: Lfm2Config, n_slots: int, token_budget: int,
                quant_cache: bool = False) -> dict[str, jax.Array]:
    """The serving cache by layer kind, slots on axis 1 of every leaf:
    `k`, `v` (attention layers, slots, kv heads / kv_pack, budget, kv_pack
    x hd: `kv_pack` heads side by side in a row) — int8 rows with
    `k_scale`, `v_scale` if `quant_cache` — and `conv` (conv layers,
    slots, conv_kernel, dim) float32, each conv layer's last gated inputs,
    oldest first: they are taps of the stream, kept as the stream is."""
    from tony_tpu.models.generate import kv_leaves

    pack = config.kv_pack
    kv = kv_leaves((config.n_periods, n_slots, config.n_kv_heads // pack,
                    token_budget, pack * config.head_dim), config.dtype,
                   quant_cache)
    return {**kv, "conv": jnp.zeros(
        (config.n_conv_layers, n_slots, config.conv_kernel, config.dim),
        STREAM)}


# ---------------------------------------------------------------------------
# pieces shared by prefill and decode
# ---------------------------------------------------------------------------

def _norm(x, weight, config: Lfm2Config):
    """RMSNorm of the stream's rows, in the stream's type."""
    return rms_norm(x, weight, config.norm_eps)


def _head_norm(x, weight, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * weight


def _matmul(x, w, transposed: bool = False):
    """The stream's float32 rows x (..., K) times a weight (K, N) — or
    (N, K) if `transposed` — accumulated in float32. Rows wider than the
    weights go as two halves in ONE left operand (ops/expert_matmul.py
    `split_rows`): the weight is read once and the product keeps 16 bits
    of the rows' mantissa. With bfloat16's 8 a fifth of the served tokens
    chose another expert set than a float32 computation somewhere in
    their 8 expert layers, or followed such a token through the short
    convolutions (PERF.md, PR 37)."""
    rows = x.reshape(-1, x.shape[-1])
    out = split_dot(rows, split_rows(rows, w.dtype), w, transposed)
    return out.reshape(x.shape[:-1] + out.shape[-1:])


def _dense_ffn(x, layer: Params, config: Lfm2Config):
    u = _norm(x, layer["ffn_norm"], config)
    h = jax.nn.silu(_matmul(u, layer["w1"])) * _matmul(u, layer["w3"])
    return x + _matmul(h, layer["w2"])


def _expert_ffn(x, ffn_norm, index, experts: Params, config: Lfm2Config,
                valid=None):
    """x (..., dim) -> (x + the expert MLP of expert layer `index`, the
    rows each expert got (E,))."""
    u = _norm(x, ffn_norm, config).reshape(-1, config.dim)
    out, counts = grouped_expert_mlp(u, index, experts, config.router,
                                     valid)
    return x + out.reshape(x.shape), counts


def _pack_rows(x, pack: int):
    """K or V rows (B, Hkv, S, hd) -> (B, Hkv / pack, S, pack * hd): `pack`
    consecutive heads side by side in a row."""
    b, h, s, d = x.shape
    x = x.reshape(b, h // pack, pack, s, d)
    if s > 1:
        x = x.transpose(0, 1, 3, 2, 4)
    return x.reshape(b, h // pack, s, pack * d)


def _lane_of(config: Lfm2Config):
    """(H,) which of a packed row's `kv_pack` heads a query head attends."""
    rep = config.n_heads // config.n_kv_heads
    return (jnp.arange(config.n_heads) // rep) % config.kv_pack


def _spread_queries(q, config: Lfm2Config):
    """q (B, H, W, hd) -> (B, H, W, pack * hd): a head's values in the
    lanes of its own K/V head, zeros in the others', so that a dot with a
    packed row is the head's own score."""
    b, h, w, d = q.shape
    own = jax.nn.one_hot(_lane_of(config), config.kv_pack, dtype=q.dtype)
    return (q[:, :, :, None, :] * own[None, :, None, :, None]).reshape(
        b, h, w, config.kv_pack * d)


def _own_lanes(o, config: Lfm2Config):
    """The attention of packed rows (B, H, W, pack * hd) -> each head's
    own (B, H, W, hd)."""
    b, h, w, _ = o.shape
    o = o.reshape(b, h, w, config.kv_pack, -1)
    return jnp.take_along_axis(
        o, _lane_of(config)[None, :, None, None, None], axis=3)[:, :, :, 0]


def _conv_gates(u, layer: Params):
    """The three gates of a conv operator's input rows: (B * z, C)."""
    b, c, z = jnp.split(_matmul(u, layer["w_in"]), 3, axis=-1)
    return b * z, c


def _logits(x, params: Params, config: Lfm2Config) -> jax.Array:
    return _matmul(_norm(x, params["final_norm"], config), params["embed"],
                   transposed=True)


def _hit(counts) -> jax.Array:
    """(experts with at least one row, rows) summed over the layers of
    `counts` (..., E): what a step reports of its expert layers."""
    return jnp.stack([jnp.sum(counts > 0), jnp.sum(counts)]).astype(
        jnp.int32)


def _at(tree, index):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, index, 0, False), tree)


def _flat_conv(params: Params) -> Params:
    """The periods' conv layers as one stack, indexed by the inner loop
    itself: handed down as the outer scan's slice they would be copied
    (models/sala.py)."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                        params["conv"])


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _conv_prefill(x, layer: Params, config: Lfm2Config):
    """x (B, S, dim) -> (x + the conv operator, its state: the last
    conv_kernel gated inputs (B, K, dim), zeros before the prompt)."""
    k = config.conv_kernel
    s = x.shape[1]
    with jax.named_scope("tony_short_conv"):
        gated, c = _conv_gates(_norm(x, layer["op_norm"], config), layer)
        padded = jnp.pad(gated, ((0, 0), (k - 1, 0), (0, 0)))
        taps = layer["w_conv"].astype(jnp.float32)
        y = c * sum(taps[j] * padded[:, j:j + s] for j in range(k))
        state = jnp.pad(gated, ((0, 0), (k, 0), (0, 0)))[:, -k:]
    return x + _matmul(y, layer["w_out"]), state


def _attention_prefill(x, layer: Params, cos, sin, config: Lfm2Config):
    b, s, _ = x.shape
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    u = _norm(x, layer["op_norm"], config)

    def heads(w, n, norm=None):
        y = _matmul(u, w).reshape(b, s, n, hd).transpose(0, 2, 1, 3)
        if norm is not None:
            y = apply_rope(_head_norm(y, norm, config.norm_eps), cos, sin)
        return y

    # float32 through the flash kernel (its blocks are multiplied in
    # float32 whatever comes in): the prompt's rows feed every later
    # token's attention, and the output joins the float32 stream
    q = heads(layer["wq"], nh, layer["q_norm"])
    k = heads(layer["wk"], nkv, layer["k_norm"])
    v = heads(layer["wv"], nkv)
    attn = flash_attention(q, k, v, True)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, nh * hd)
    return x + _matmul(attn, layer["wo"]), (
        _pack_rows(k.astype(config.dtype), config.kv_pack),
        _pack_rows(v.astype(config.dtype), config.kv_pack))


def prefill(params: Params, tokens: jax.Array, config: Lfm2Config,
            cache_len: int, quant_cache: bool = False
            ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Prompts (B, P) through the model: the last position's logits
    (B, V) and the cache they leave, every leaf of `empty_cache` with B
    slots (K/V rows padded to `cache_len`, each conv layer's state after
    the last token)."""
    from tony_tpu.models.quant import quantize_rows

    b, n = tokens.shape
    r = config.period - 1
    cos, sin = rope_frequencies(config.head_dim, n, config.rope_theta)
    x = jnp.take(params["embed"], tokens, axis=0).astype(STREAM)

    def dense(x, layer):
        x, state = _conv_prefill(x, layer, config)
        return _dense_ffn(x, layer, config), state

    x, dense_states = lax.scan(dense, x, params["dense"])
    flat = _flat_conv(params)

    def period(x, xs):
        layer, p = xs
        x, kv = _attention_prefill(x, layer, cos, sin, config)
        x, _ = _expert_ffn(x, layer["ffn_norm"], p * (r + 1),
                           params["moe"], config)

        def one(x, i):
            conv = _at(flat, p * r + i)
            x, state = _conv_prefill(x, conv, config)
            x, _ = _expert_ffn(x, conv["ffn_norm"], p * (r + 1) + 1 + i,
                               params["moe"], config)
            return x, state

        x, states = lax.scan(one, x, jnp.arange(r, dtype=jnp.int32))
        return x, (kv, states)

    x, ((ks, vs), states) = lax.scan(
        period, x, (params["attn"],
                    jnp.arange(config.n_periods, dtype=jnp.int32)))
    logits = _logits(x[:, -1], params, config)

    widths = ((0, 0), (0, 0), (0, 0), (0, cache_len - n), (0, 0))
    if quant_cache:
        (ks, k_scale), (vs, v_scale) = quantize_rows(ks), quantize_rows(vs)
        kv = {"k": ks, "v": vs, "k_scale": k_scale, "v_scale": v_scale}
    else:
        kv = {"k": ks, "v": vs}
    states = states.reshape((-1,) + states.shape[2:])
    conv = jnp.concatenate([dense_states, states])
    return logits, {**{name: jnp.pad(a, widths) for name, a in kv.items()},
                    "conv": conv}


# ---------------------------------------------------------------------------
# decode: one token a slot
# ---------------------------------------------------------------------------

def _conv_decode(x, state, layer: Params, config: Lfm2Config):
    """x (B, dim), state (B, K, dim) the layer's last gated inputs ->
    (x + the conv operator, the new gated input (B, dim))."""
    k = config.conv_kernel
    with jax.named_scope("tony_short_conv"):
        gated, c = _conv_gates(_norm(x, layer["op_norm"], config), layer)
        taps = layer["w_conv"].astype(jnp.float32)
        y = c * (taps[k - 1] * gated
                 + sum(taps[j] * state[:, j + 1] for j in range(k - 1)))
    return x + _matmul(y, layer["w_out"]), gated


def _attention_decode(x, layer: Params, index, cache, cos, sin, pos, attend,
                      config: Lfm2Config):
    from tony_tpu.models.generate import new_cache_rows

    b = x.shape[0]
    nh, nkv, hd = config.n_heads, config.n_kv_heads, config.head_dim
    u = _norm(x, layer["op_norm"], config)

    def heads(w, n, norm=None):
        y = _matmul(u, w).reshape(b, n, 1, hd)
        if norm is not None:
            y = apply_rope(_head_norm(y, norm, config.norm_eps), cos, sin,
                           pos[:, None])
        return y.astype(config.dtype)

    q = heads(layer["wq"], nh, layer["q_norm"])
    k = heads(layer["wk"], nkv, layer["k_norm"])
    v = heads(layer["wv"], nkv)
    k, v = lax.optimization_barrier(
        (_pack_rows(k, config.kv_pack), _pack_rows(v, config.kv_pack)))
    rows, k_new, v_new = new_cache_rows(k, v, cache["k"].dtype,
                                        "k_scale" in cache)
    attn = cache_attention(jnp.reshape(index, (1,)), attend,
                           _spread_queries(q, config), k_new, v_new, cache,
                           sm_scale=hd ** -0.5, out_dtype=STREAM)
    attn = _own_lanes(attn, config).reshape(b, nh * hd)
    return x + _matmul(attn, layer["wo"]), rows


# what `decode_step_counted` counts on the device, entry by entry of its
# third result (the serving engine adds each to `<name>_total`)
STEP_COUNTS = ("moe_experts_hit", "moe_rows")


def decode_step(params: Params, config: Lfm2Config,
                cache: dict[str, jax.Array], token: jax.Array,
                pos: jax.Array, attend: Optional[jax.Array] = None
                ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """`decode_step_counted` without its counts."""
    return decode_step_counted(params, config, cache, token, pos,
                               attend)[:2]


def decode_step_counted(params: Params, config: Lfm2Config,
                        cache: dict[str, jax.Array], token: jax.Array,
                        pos: jax.Array, attend: Optional[jax.Array] = None
                        ) -> tuple[jax.Array, dict[str, jax.Array],
                                   jax.Array]:
    """One token a slot. token (B,) int32 at positions pos (B,); `attend`
    (B,) the cached rows each slot attends to, 0 for a slot that does not
    ride: it reads no row and its row reaches no expert (absent: every
    slot rides at its position). Returns (logits (B, V), the cache with
    the token's K/V rows and every conv layer's state advanced, counts
    (2,) int32 as `STEP_COUNTS` names them: experts that got at least one
    row summed over the expert layers, and the rows they got)."""
    from tony_tpu.models.generate import write_cache_rows

    r = config.period - 1
    nd = config.n_dense_layers
    kv = {name: a for name, a in cache.items() if name != "conv"}
    budget = kv["k"].shape[3]
    cos, sin = rope_frequencies(config.head_dim, budget, config.rope_theta)
    valid = None if attend is None else attend > 0
    attend = jnp.minimum(pos if attend is None else attend,
                         budget).astype(jnp.int32)
    x = jnp.take(params["embed"], token, axis=0).astype(STREAM)
    states = cache["conv"]

    def dense(x, xs):
        layer, i = xs
        x, new = _conv_decode(x, lax.dynamic_index_in_dim(
            states, i, 0, False), layer, config)
        return _dense_ffn(x, layer, config), new

    x, dense_new = lax.scan(dense, x, (params["dense"],
                                       jnp.arange(nd, dtype=jnp.int32)))
    flat = _flat_conv(params)

    def period(x, xs):
        layer, p = xs
        x, rows = _attention_decode(x, layer, p, kv, cos, sin, pos, attend,
                                    config)
        x, hit = _expert_ffn(x, layer["ffn_norm"], p * (r + 1),
                             params["moe"], config, valid)

        def one(x, i):
            conv = _at(flat, p * r + i)
            x, new = _conv_decode(x, lax.dynamic_index_in_dim(
                states, nd + p * r + i, 0, False), conv, config)
            x, hit = _expert_ffn(x, conv["ffn_norm"], p * (r + 1) + 1 + i,
                                 params["moe"], config, valid)
            return x, (new, hit)

        x, (new, hits) = lax.scan(one, x, jnp.arange(r, dtype=jnp.int32))
        return x, (rows, new, jnp.concatenate([hit[None], hits]))

    x, (rows, new, hits) = lax.scan(
        period, x, (params["attn"],
                    jnp.arange(config.n_periods, dtype=jnp.int32)))
    written = write_cache_rows(kv, rows, pos)
    new = jnp.concatenate([dense_new, new.reshape((-1,) + new.shape[2:])])
    conv = jnp.concatenate([states[:, :, 1:], new[:, :, None]], axis=2)
    return (_logits(x, params, config), {**written, "conv": conv},
            _hit(hits))
