"""Autoregressive generation for the Llama family: KV-cache decode.

TPU-first inference path (greenfield — the reference is an orchestrator
with no model code, SURVEY.md §2.3):

- **Static shapes end to end**: the cache is allocated once at
  (L, B, Hkv, prompt+max_new, hd); the decode loop is a `lax.scan` over a
  fixed token budget with a length mask — no dynamic shapes, one compile.
- **Prefill via the training forward pieces**: full causal flash attention
  over the prompt (narrow GQA K/V), capturing each layer's K/V as scan
  outputs.
- **Decode step**: one token per step (`decode_step`, the W=1 case of
  `window_logits`). The layer loop only READS the cache, and of it only
  the rows each batch row attends to (`ops/cache_attention.py`: the
  kernel `tony_decode_read` on a TPU) plus the new row straight from
  registers, grouped by GQA head group (no K/V repeat materialization).
  The new rows of all layers are written once, after the loop, in place
  (`write_cache_rows`) — no layer's slab is ever sliced out, copied or
  stacked back.
- **Sampling**: greedy (temperature 0) or temperature + optional top-k
  via `jax.random.categorical`; an emitted `eos_id` latches and pads the
  remainder with `eos_id`.

Oracle parity: `tests/test_generate.py` pins greedy decode against
re-running the full training forward on the growing sequence.
"""

from __future__ import annotations

import importlib
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.llama import (
    LlamaConfig, Params, embed_lookup, qkv_proj, rope_tables, swiglu_mlp,
)
from tony_tpu.models.quant import (
    dequantize_layer, dequantize_rows, maybe_dequantize, quantize_rows,
)
from tony_tpu.ops.attention import NEG_INF, flash_attention
from tony_tpu.ops.cache_attention import cache_attention
from tony_tpu.ops.rmsnorm import rms_norm
from tony_tpu.ops.rope import apply_rope


def _mlp(h: jax.Array, layer: Params, config: LlamaConfig) -> jax.Array:
    """Dense SwiGLU or MoE expert MLP, dispatched on the config type —
    ONE decode/serve stack for both families. The MoE aux loss is a
    training concern and is dropped here.

    MoE capacity note: each call routes over ITS OWN tokens, so a
    decode step's expert queues start empty while a full training
    forward fills them across the whole sequence. With
    capacity_factor >= n_experts / top_k nothing overflows in either
    case and incremental decode is exactly the training forward
    (pinned by tests/test_moe_generate.py); below that, training may
    drop tokens that decode serves — standard Switch semantics.
    `generate()` warns at trace time when a below-no-drop-capacity
    config reaches the decode path (`_warn_moe_below_capacity`);
    speculative_generate raises, because there the divergence breaks
    its lossless-identity contract outright."""
    if getattr(config, "n_experts", 0):
        from tony_tpu.models.moe import moe_mlp
        out, _aux = moe_mlp(h, layer, config)
        return out
    return swiglu_mlp(h, layer)


def _warn_moe_below_capacity(config: LlamaConfig, who: str = "decode"
                             ) -> None:
    """Warn when an MoE config below no-drop capacity reaches the decode
    path. Decode routes 1 token per call while the training forward
    routes the whole sequence, so below capacity_factor >= n_experts /
    top_k the two paths overflow DIFFERENT expert queues and decode
    silently serves tokens training dropped (ADVICE r5). Mirrors the
    ValueError in speculative_generate, softened to a warning here
    because plain sampling has no exactness contract to break."""
    if not getattr(config, "n_experts", 0) \
            or not hasattr(config, "capacity_factor"):
        return      # no experts, or experts routed without a capacity
    from tony_tpu.models.moe import no_drop_capacity_floor
    floor = no_drop_capacity_floor(config)
    if config.capacity_factor < floor:
        import warnings
        warnings.warn(
            f"MoE config reaches the {who} path below no-drop capacity "
            f"(capacity_factor {config.capacity_factor} < n_experts/"
            f"top_k = {floor}): decode routes tokens the training "
            f"forward dropped — raise capacity_factor to >= {floor} "
            f"for train/serve parity", stacklevel=3)


def cache_by_kind(config) -> bool:
    """True for a model whose layers are of several kinds and whose cache
    therefore has other leaves than K/V rows: `prefill`, `decode_step` and
    `empty_cache` hand such a config to the module it names
    (`kind_module`). A Python test at trace time: a LlamaConfig's programs
    hold no trace of it."""
    return getattr(config, "has_recurrent_state", False)


def kind_module(config, quant_cache: bool = False):
    """The module a config whose cache is by layer kind names under
    `cache_module` — it implements `empty_cache(config, slots, budget)`,
    `prefill(params, tokens, config, cache_len)` and `decode_step(params,
    config, cache, token, pos, attend)`, and takes `quant_cache=True` where
    it declares `INT8_CACHE` — or None for a config whose cache is K/V
    rows alone. Trace-time Python only."""
    if not cache_by_kind(config):
        return None
    module = importlib.import_module(config.cache_module)
    if quant_cache and not getattr(module, "INT8_CACHE", False):
        raise ValueError(
            "quant_cache: this model's cache is by layer kind "
            "(compressed keys, recurrent state); it has no int8 form")
    return module


def kv_leaves(shape: tuple, dtype, quant_cache: bool
              ) -> dict[str, jax.Array]:
    """Zero K/V rows of `shape` (L, B, Hkv, S, hd): int8 rows with one
    float32 scale a row if `quant_cache`, else `dtype`."""
    if quant_cache:
        scale = shape[:-1] + (1,)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(scale, jnp.float32),
                "v_scale": jnp.zeros(scale, jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def empty_cache(config, n_slots: int, token_budget: int,
                quant_cache: bool = False) -> dict[str, jax.Array]:
    """The zero serving cache of `n_slots` slots of `token_budget` tokens,
    in exactly the tree `prefill` writes (int8 layout included, which
    `window_logits` detects by structure). Every leaf has the slot on
    axis 1: that is all an admission needs to know to write one."""
    module = kind_module(config, quant_cache)
    if module is not None:
        return module.empty_cache(config, n_slots, token_budget,
                                  **({"quant_cache": True} if quant_cache
                                     else {}))
    return kv_leaves((config.n_layers, n_slots, config.n_kv_heads,
                      token_budget, config.head_dim), config.dtype,
                     quant_cache)


def new_cache_rows(k, v, dtype, quant: bool):
    """What the cache stores for new K/V rows (B, Hkv, W, hd), quantized
    iff `quant` (an int8 cache) and cast to the cache's `dtype` otherwise.
    Returns (rows, k_eff, v_eff): `rows` holds one entry per key of the
    cache dict, k_eff/v_eff are the attention-ready views of those rows —
    exactly what a read back from the cache would give.

    ONE place for the int8/bf16 row format, shared by every decode-side
    caller through `window_logits` — a scheme change applied to one and
    not the other would silently break the greedy-lossless identity."""
    if not quant:
        k, v = k.astype(dtype), v.astype(dtype)
        return {"k": k, "v": v}, k, v
    qk, k_s = quantize_rows(k)
    qv, v_s = quantize_rows(v)
    return ({"k": qk, "v": qv, "k_scale": k_s, "v_scale": v_s},
            dequantize_rows(qk, k_s), dequantize_rows(qv, v_s))


def write_cache_rows(cache, rows, offsets):
    """Write every layer's new rows {name: (L, B, Hkv, W, d)} into the
    cache {name: (L, B, Hkv, S, d)} at PER-ROW offsets (B,): batch row b's
    W rows land at positions offsets[b]..offsets[b]+W-1 of all L layers.

    One `dynamic_update_slice` per batch row into the whole buffer, in a
    loop that carries it — with the cache donated (or carried by an outer
    loop) each is an in-place write of L x Hkv x W rows, and nothing
    cache-sized is sliced, copied or stacked. A scatter would do it in one
    op, but forces a cache layout that brings slab copies back. `offsets`
    may also be {name: (B,)}: leaves that advance at different paces (a
    cache by layer kind) are still written by the one loop."""
    per_leaf = offsets if isinstance(offsets, dict) else dict.fromkeys(
        cache, offsets)

    def write_row(b, cache):
        return {name: lax.dynamic_update_slice(
                    arr, lax.dynamic_slice_in_dim(rows[name], b, 1, axis=1),
                    (0, b, 0, per_leaf[name][b], 0))
                for name, arr in cache.items()}

    n_rows = next(iter(per_leaf.values())).shape[0]
    return lax.fori_loop(0, n_rows, write_row, cache)


def prefill(params: Params, tokens: jax.Array, config: LlamaConfig,
            cache_len: int, quant_cache: bool = False
            ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Run the prompt through the model, returning last-position logits
    and the KV cache (prompt K/V written, remainder zeros).

    tokens: (B, P) int32; cache_len >= P. quant_cache=True stores the
    cache as per-row int8 + scales (models/quant.py) — at long contexts
    decode bandwidth is cache-read-bound, so halving cache bytes is the
    long-context serving lever the way weight int8 is the short-context
    one."""
    module = kind_module(config, quant_cache)
    if module is not None:
        return module.prefill(params, tokens, config, cache_len,
                              **({"quant_cache": True} if quant_cache
                                 else {}))
    b, p = tokens.shape
    nkv, hd = config.n_kv_heads, config.head_dim
    cos, sin = rope_tables(config, cache_len)
    x = embed_lookup(params["embed"], tokens, config)

    def body(x, layer):
        # int8-quantized layers (models/quant.py) dequantize HERE, inside
        # the scan body, so XLA fuses the int8 read into each matmul
        layer = dequantize_layer(layer)
        h = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q, k, v = qkv_proj(h, layer, config, split_on_result=True)
        q = apply_rope(q, cos[:p], sin[:p])
        k = apply_rope(k, cos[:p], sin[:p])
        attn = flash_attention(q, k, v, True)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, p, -1)
        x = x + jnp.einsum("bsh,hd->bsd", attn, layer["wo"])
        h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
        x = x + _mlp(h, layer, config)
        return x, (k, v)

    x, (ks, vs) = lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x[:, -1],
                        maybe_dequantize(params["output"]),
                        preferred_element_type=jnp.float32)

    pad = cache_len - p
    widths = ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0))
    if quant_cache:
        qk, k_scale = quantize_rows(ks)
        qv, v_scale = quantize_rows(vs)
        cache = {"k": jnp.pad(qk, widths), "v": jnp.pad(qv, widths),
                 "k_scale": jnp.pad(k_scale, widths),
                 "v_scale": jnp.pad(v_scale, widths)}
    else:
        cache = {"k": jnp.pad(ks, widths), "v": jnp.pad(vs, widths)}
    return logits, cache


def window_logits(params: Params, config: LlamaConfig,
                  cache: dict[str, jax.Array], tokens: jax.Array,
                  lens: jax.Array, attend: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Forward a (B, W) token window against per-row cache lengths — THE
    decode-side forward: `decode_step` is its W=1 case, the speculative
    verify its W=gamma+1 case, so the two cannot drift apart.

    Row b's window occupies positions lens[b]..lens[b]+W-1. Returns
    (logits (B, W, V), new cache) with the window's K/V written there.
    The caller owns lens bookkeeping: only advance past positions whose
    tokens were actually accepted — anything beyond stays invisible to
    the mask and is overwritten by later windows. An int8 cache
    (prefill's quant_cache=True) is detected by tree structure — a static
    property under jit, so both layouts share this function.

    `attend` (B,), where given, is how many cached rows each batch row
    attends to, in place of lens: 0 for a row whose result the caller
    throws away (the serving engine's slots that do not ride), which then
    reads nothing of the cache. Where its window is written is still
    lens[b].

    The layer loop only READS the cache, which it closes over whole: each
    layer attends to its own rows below `attend` (`cache_attention`: only
    those leave HBM) plus the window's own rows from registers, and hands
    the new rows out as `ys`. They are written once, after the loop, in
    place (`write_cache_rows`). Passing updated slabs back through the
    scan instead costs a slice, a copy and a write-back of every layer's
    whole slab per token."""
    quant = "k_scale" in cache
    b, w = tokens.shape
    cache_len = cache["k"].shape[3]
    cos, sin = rope_tables(config, cache_len)
    positions = lens[:, None] + jnp.arange(w, dtype=lens.dtype)[None, :]
    attend = jnp.minimum(lens if attend is None else attend,
                         cache_len).astype(jnp.int32)
    x = embed_lookup(params["embed"], tokens, config)   # (B, W, D)

    def body(x, layer_and_index):
        layer, index = layer_and_index
        # int8-quantized layers dequantize HERE, inside the scan body
        layer = dequantize_layer(layer)
        h = rms_norm(x, layer["attn_norm"], config.norm_eps)
        q, k, v = qkv_proj(h, layer, config, split_on_result=True)
        q = apply_rope(q, cos, sin, positions)
        k = apply_rope(k, cos, sin, positions)
        rows, k_new, v_new = new_cache_rows(k, v, cache["k"].dtype, quant)
        attn = cache_attention(index[None], attend, q, k_new, v_new, cache)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, w, -1)
        x = x + jnp.einsum("bsh,hd->bsd", attn, layer["wo"])
        h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
        x = x + _mlp(h, layer, config)
        return x, rows

    x, rows = lax.scan(body, x, (params["layers"],
                                 jnp.arange(config.n_layers,
                                            dtype=jnp.int32)))
    cache = write_cache_rows(cache, rows, lens)
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    logits = jnp.einsum("bwd,dv->bwv", x,
                        maybe_dequantize(params["output"]),
                        preferred_element_type=jnp.float32)
    return logits, cache


def decode_step_counted(params: Params, config: LlamaConfig,
                        cache: dict[str, jax.Array], token: jax.Array,
                        pos: jax.Array, attend: Optional[jax.Array] = None):
    """`decode_step`, and what the model counted on the device while it
    ran: an int32 vector named entry by entry by the module's
    `STEP_COUNTS` (experts that got rows, say), or None for a
    model that counts nothing — no result of the compiled step."""
    pos = jnp.broadcast_to(pos, token.shape)
    module = kind_module(config)
    if module is not None:
        counted = getattr(module, "decode_step_counted", None)
        if counted is not None:
            return counted(params, config, cache, token, pos, attend)
        return (*module.decode_step(params, config, cache, token, pos,
                                    attend), None)
    logits, cache = window_logits(params, config, cache, token[:, None],
                                  pos, attend)
    return logits[:, 0], cache, None


def decode_step(params: Params, config: LlamaConfig,
                cache: dict[str, jax.Array], token: jax.Array,
                pos: jax.Array, attend: Optional[jax.Array] = None
                ) -> tuple[jax.Array, dict[str, jax.Array]]:
    """One decode step. token: (B,) int32; pos: scalar int32 (the position
    the token occupies) or (B,) int32 per-row positions — the latter is
    the continuous-batching shape (serve/engine.py), where every batch
    row is an independent request slot at its own sequence position.
    `attend` as in `window_logits`; a model whose cache is by layer kind
    is handed it too, as its riding mask (0: the slot does not ride).
    Returns (logits (B, V), updated cache)."""
    return decode_step_counted(params, config, cache, token, pos,
                               attend)[:2]


def _sample(logits: jax.Array, temperature: float, top_k: int,
            key: jax.Array, top_p: float = 1.0) -> jax.Array:
    """(B, V) -> (B,) next tokens."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        # clamp: top_k past the vocab is "no truncation", not an opaque
        # XLA shape error inside jit
        top_k = min(top_k, logits.shape[-1])
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]      # (B, 1)
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p < 1.0:
        # nucleus: keep the smallest set of tokens whose probability
        # mass reaches top_p. Floored so the most-probable token ALWAYS
        # survives — at top_p=0 an all-False keep would mask every
        # token to the same NEG_INF and categorical would then sample
        # uniformly over the whole vocab (pure noise)
        top_p = max(top_p, 1e-9)
        srt = jnp.sort(logits, axis=-1)[:, ::-1]           # descending
        probs = jax.nn.softmax(srt, axis=-1)
        keep = jnp.cumsum(probs, axis=-1) - probs < top_p  # (B, V)
        threshold = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                            keepdims=True)                 # (B, 1)
        logits = jnp.where(logits >= threshold, logits, NEG_INF)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


@partial(jax.jit, static_argnames=("config", "max_new_tokens",
                                   "temperature", "top_k", "top_p",
                                   "eos_id", "quant_cache"))
def generate(params: Params, config: LlamaConfig, prompt: jax.Array,
             max_new_tokens: int, temperature: float = 0.0,
             top_k: int = 0, top_p: float = 1.0,
             eos_id: Optional[int] = None,
             key: Optional[jax.Array] = None,
             quant_cache: bool = False) -> jax.Array:
    """prompt: (B, P) int32 -> (B, max_new_tokens) generated tokens.

    Greedy when temperature == 0 (key unused); once a row emits eos_id it
    keeps emitting eos_id. One compile per (shape, config, budget).
    quant_cache=True keeps the KV cache in per-row int8 (long-context
    bandwidth lever; composes freely with int8 weight-only params).
    An MoE config below no-drop capacity triggers a trace-time warning
    (once per compile) — see _warn_moe_below_capacity."""
    _warn_moe_below_capacity(config)
    if key is None:
        key = jax.random.PRNGKey(0)
    b, p = prompt.shape
    cache_len = p + max_new_tokens
    if cache_len > config.max_seq:
        raise ValueError(f"prompt {p} + max_new {max_new_tokens} exceeds "
                         f"max_seq {config.max_seq}")
    logits, cache = prefill(params, prompt, config, cache_len,
                            quant_cache=quant_cache)

    keys = jax.random.split(key, max_new_tokens)
    tok0 = _sample(logits, temperature, top_k, keys[0], top_p)
    done0 = (tok0 == eos_id) if eos_id is not None else jnp.zeros((b,),
                                                                  bool)

    def step(carry, step_key):
        cache, tok, pos, done = carry
        # decode the PREVIOUS token, sample the next — the final sampled
        # token therefore never pays a trailing decode_step
        logits, cache = decode_step(params, config, cache, tok, pos)
        nxt = _sample(logits, temperature, top_k, step_key, top_p)
        if eos_id is not None:
            nxt = jnp.where(done, eos_id, nxt)
            done = done | (nxt == eos_id)
        return (cache, nxt, pos + 1, done), nxt

    if max_new_tokens == 1:
        return tok0[:, None]
    (_, _, _, _), rest = lax.scan(
        step, (cache, tok0, jnp.int32(p), done0), keys[1:])
    return jnp.concatenate([tok0[:, None], rest.T], axis=1)   # (B, N)


def generate_text(params: Params, config: LlamaConfig, prompt: Any,
                  tokenizer: Any, max_new_tokens: int = 64,
                  **kwargs) -> list[str]:
    """Convenience wrapper for tokenizer objects with encode/decode
    (e.g. a transformers tokenizer); prompt: str or list[str].

    There is no padding/attention mask in the decode path, so ragged
    prompts are grouped by length and each group generated as its own
    batch — padding a shorter prompt would feed pad embeddings into
    attention and shift its RoPE positions."""
    if isinstance(prompt, str):
        prompt = [prompt]
    ids = [tuple(tokenizer.encode(t)) for t in prompt]
    out: dict[int, list[int]] = {}
    by_len: dict[int, list[int]] = {}
    for i, seq in enumerate(ids):
        by_len.setdefault(len(seq), []).append(i)
    for length, idxs in by_len.items():
        batch = jnp.asarray([list(ids[i]) for i in idxs], jnp.int32)
        toks = generate(params, config, batch, max_new_tokens, **kwargs)
        for i, row in zip(idxs, jax.device_get(toks)):
            out[i] = list(row)
    return [tokenizer.decode(out[i]) for i in range(len(ids))]
