"""Mixture-of-Experts Llama variant with expert parallelism.

No reference equivalent (the reference is an orchestrator; SURVEY.md §2.3
lists expert parallelism as absent) — this is the TPU-first extension that
makes the mesh's `ep` axis real. Design:

- **Sparse slot-indexed dispatch (default)**: each (token, k-th choice)
  pair maps to a static expert-queue slot `expert_id * capacity + pos`;
  tokens reach their expert through ONE gather of (E*C, D) rows and
  return through k gathers + a weighted sum. Cost is O(T*k*D) data
  movement — the dense one-hot dispatch/combine einsums it replaces were
  2*T*(E*C)*D = O(k*T^2*D) MXU FLOPs, which at mixtral_proxy scale
  (T=16k, D=2048, k=2) EXCEEDS the expert matmul FLOPs themselves
  (VERDICT r2 item 4). Every shape stays static, so XLA still compiles
  one program. The two modes' step times on the chip are not measured
  (no cell of the benchmark runs an MoE; PERF.md section 7).
- **Dense dispatch (dispatch_mode="dense")**: the GShard/Switch one-hot
  einsum formulation, kept as a fallback because its all-to-all insertion
  under an `ep`-sharded mesh is driven purely by shardings (no gather
  sharding edge cases); bit-identical routing semantics to sparse.
- **Capacity factor**: each expert processes a fixed `capacity` of tokens
  per batch; overflow tokens are dropped (standard Switch behavior),
  keeping every tensor static.
- **Sharding**: expert weight dim maps to the `ep` mesh axis (sharding
  rule "expert" → "ep"); token batch stays on (dp, fsdp).
- **Aux load-balancing loss** (Switch-style): sum_e(fraction_tokens_e *
  fraction_router_prob_e) * (E / k) — normalized so perfectly balanced
  top-k routing scores ~1.0; returned alongside the output.

The MoE block replaces the dense SwiGLU MLP in the Llama block; attention,
RoPE, norms are shared with models/llama.py.

**Two paths, two callers.** Everything above (`MoEConfig`, `moe_mlp`: a
padded queue an expert, overflow dropped) is the TRAINING path: the
trainer's `moe_loss`, and `generate()` / the engine for a `MoEConfig`
preset, which route through `models/generate._mlp`. The SERVING path of a
model whose experts are published with no capacity (models/lfm2.py) is
the last section, `grouped_expert_mlp`: sigmoid scores and top-k in
float32 (`route_topk`), every chosen (token, expert) pair laid out group by
group (`group_rows`) and multiplied by ONE grouped matmul
(ops/expert_matmul.py `tony_expert_matmul`) that reads an expert's weights
only if it has rows. No token is dropped at any imbalance; a row the
caller marks as not riding (the serving engine's parked slots) reaches no
expert. It has no training path and no aux loss.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.llama import (
    LlamaConfig, attention_sublayer, llama_init, llama_param_axes,
)
from tony_tpu.ops.expert_matmul import (
    expert_matmul, padded_rows, tile_rows_for,
)
from tony_tpu.ops.rmsnorm import rms_norm
from tony_tpu.parallel.sharding import constrain

Params = dict[str, Any]


@dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    # "sparse": slot-indexed gather dispatch, O(T*k*D) movement;
    # "dense": one-hot einsum dispatch, O(k*T^2*D) FLOPs (fallback)
    dispatch_mode: str = "sparse"

    def __post_init__(self):
        super().__post_init__()
        if self.dispatch_mode not in ("sparse", "dense"):
            raise ValueError(
                f"dispatch_mode must be 'sparse' or 'dense', got "
                f"{self.dispatch_mode!r}")

    def num_params(self) -> int:
        """Total parameters: the dense count with the single SwiGLU MLP
        swapped for `n_experts` expert banks + the router."""
        d, f, L, E = self.dim, self.ffn_dim, self.n_layers, self.n_experts
        dense = super().num_params()
        # super() counted ONE 3*d*f MLP per layer; experts add E of them
        return dense + L * ((E - 1) * 3 * d * f + d * E)

    def active_params(self) -> int:
        """Parameters a token actually touches: attention + norms +
        embeddings as dense, but only `top_k` of the `n_experts` MLP
        banks (+ the router). THE number MFU must be derived from —
        using total params would flatter a sparse model by counting
        FLOPs it never executes."""
        d, f, L = self.dim, self.ffn_dim, self.n_layers
        dense = super().num_params()
        # swap the one dense MLP per layer for top_k expert MLPs + router
        return dense + L * ((self.top_k - 1) * 3 * d * f
                            + d * self.n_experts)

    def matmul_params(self) -> int:
        """ACTIVE weights a token is multiplied by: the dense count with
        the one MLP per layer swapped for `top_k` expert MLPs + the
        router."""
        d, f, L = self.dim, self.ffn_dim, self.n_layers
        return super().matmul_params() + L * (
            (self.top_k - 1) * 3 * d * f + d * self.n_experts)


PRESETS = {
    "moe_tiny": MoEConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, ffn_dim=128, max_seq=128,
                          dtype=jnp.float32, remat=False, n_experts=4,
                          top_k=2),
    "mixtral_proxy": MoEConfig(vocab_size=32_000, dim=2048, n_layers=16,
                               n_heads=16, n_kv_heads=8, ffn_dim=4096,
                               max_seq=4096, n_experts=8, top_k=2,
                               xent_chunk=1024),
}


def get_moe_config(name: str, **overrides) -> MoEConfig:
    return replace(PRESETS[name], **overrides)


def is_moe_preset(name: str) -> bool:
    """Family resolver for entrypoints that accept any preset name —
    membership in THIS registry, not name sniffing, so a future preset
    with an unconventional name routes correctly everywhere."""
    return name in PRESETS


def no_drop_capacity_floor(config) -> float:
    """Smallest capacity_factor at which NO routing can overflow an
    expert queue: with capacity = capacity_factor * T * top_k / E, even
    all T*top_k assignments landing on one expert fit once
    capacity_factor >= n_experts / top_k. Below this floor, overflow
    depends on how many tokens a call routes at once — decode routes 1
    per call while training routes the whole sequence, so the two paths
    drop DIFFERENT tokens. The single source of truth behind generate's
    decode warning and speculative_generate's hard error."""
    return config.n_experts / config.top_k


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def moe_init(config: MoEConfig, key: jax.Array) -> Params:
    """Llama params with the dense MLP swapped for router + expert banks."""
    k_base, k_router, k_experts = jax.random.split(key, 3)
    params = llama_init(config, k_base)
    d, f, L, E = config.dim, config.ffn_dim, config.n_layers, config.n_experts

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(
            config.dtype)

    ks = jax.random.split(k_experts, 3)
    layers = dict(params["layers"])
    for dense_key in ("w_gate", "w_up", "w_down"):
        del layers[dense_key]
    layers["router"] = normal(k_router, (L, d, E), d ** -0.5)
    layers["we_gate"] = normal(ks[0], (L, E, d, f), d ** -0.5)
    layers["we_up"] = normal(ks[1], (L, E, d, f), d ** -0.5)
    layers["we_down"] = normal(ks[2], (L, E, f, d), f ** -0.5)
    params["layers"] = layers
    return params


def moe_param_axes(config: MoEConfig) -> Params:
    axes = llama_param_axes(config)
    layers = dict(axes["layers"])
    for dense_key in ("w_gate", "w_up", "w_down"):
        del layers[dense_key]
    layers["router"] = ("layers", "embed", None)
    layers["we_gate"] = ("layers", "expert", "embed", "mlp")
    layers["we_up"] = ("layers", "expert", "embed", "mlp")
    layers["we_down"] = ("layers", "expert", "mlp", "embed")
    axes["layers"] = layers
    return axes


# ---------------------------------------------------------------------------
# MoE layer (dense dispatch)
# ---------------------------------------------------------------------------

def _expert_bank(expert_in: jax.Array, layer: Params) -> jax.Array:
    """(E, C, D) -> (E, C, D) through each expert's SwiGLU."""
    expert_in = constrain(expert_in, ("expert", None, None))
    gate = jnp.einsum("ecd,edf->ecf", expert_in, layer["we_gate"])
    up = jnp.einsum("ecd,edf->ecf", expert_in, layer["we_up"])
    act = jax.nn.silu(gate) * up
    expert_out = jnp.einsum("ecf,efd->ecd", act, layer["we_down"])
    return constrain(expert_out, ("expert", None, None))


def moe_mlp(x: jax.Array, layer: Params, config: MoEConfig
            ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss). Top-k routing with capacity; the
    dispatch itself is sparse (slot-indexed gathers) or dense (one-hot
    einsums) per config.dispatch_mode — identical routing semantics."""
    b, s, d = x.shape
    E, k = config.n_experts, config.top_k
    n_tokens = b * s
    capacity = max(1, int(config.capacity_factor * n_tokens * k / E))

    xt = x.reshape(n_tokens, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        layer["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                       # (T, E)

    # top-k expert choice per token, one expert at a time so every
    # intermediate stays static-shaped; per-k indices retained for the
    # sparse path's slot arithmetic
    gates = jnp.zeros_like(probs)
    masked = probs
    topk_idx = []
    for _ in range(k):
        idx = jnp.argmax(masked, axis=-1)                         # (T,)
        topk_idx.append(idx)
        onehot = jax.nn.one_hot(idx, E, dtype=probs.dtype)
        gates = gates + onehot * probs
        masked = masked * (1.0 - onehot)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)             # renorm

    # capacity assignment: position of each token within its expert queue
    chosen = gates > 0.0                                          # (T, E)
    position = jnp.cumsum(chosen, axis=0) - 1                     # (T, E)
    keep = chosen & (position < capacity)

    if config.dispatch_mode == "dense":
        out = _dense_dispatch(xt, layer, gates, keep, position, capacity,
                              x.dtype)
    else:
        out = _sparse_dispatch(xt, layer, gates, keep, position, capacity,
                               topk_idx, x.dtype)

    # Switch-style load-balance aux loss
    frac_tokens = jnp.mean(chosen.astype(jnp.float32), axis=0)    # (E,)
    frac_probs = jnp.mean(probs, axis=0)                          # (E,)
    aux = jnp.sum(frac_tokens * frac_probs) * (E / k)

    return out.reshape(b, s, d).astype(x.dtype), aux


def _dense_dispatch(xt, layer, gates, keep, position, capacity, dtype):
    """GShard-style one-hot dispatch/combine einsums. O(T*E*C*D) MXU
    FLOPs — quadratic in tokens since E*C ~ k*T; the fallback path."""
    slot = jnp.where(keep, position, 0)
    dispatch = (keep[..., None]
                * jax.nn.one_hot(slot, capacity, dtype=dtype))    # (T,E,C)
    combine = dispatch * gates[..., None].astype(dtype)           # (T,E,C)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)
    expert_out = _expert_bank(expert_in, layer)
    return jnp.einsum("tec,ecd->td", combine, expert_out)


def _sparse_dispatch(xt, layer, gates, keep, position, capacity,
                     topk_idx, dtype):
    """Slot-indexed dispatch: (token, choice) -> static queue slot
    `expert * C + pos`; ONE scatter builds slot->token, ONE gather feeds
    the expert bank, k gathers combine. O(T*k*D) data movement, no
    dispatch matmul (VERDICT r2 item 4's 1.3x-of-ideal bar)."""
    n_tokens, d = xt.shape
    E = gates.shape[-1]
    n_slots = E * capacity
    token_ids = jnp.arange(n_tokens, dtype=jnp.int32)
    sentinel = n_slots                    # dropped/overflow writes land here

    slot_token = jnp.zeros((n_slots + 1,), jnp.int32)
    slot_valid = jnp.zeros((n_slots + 1,), dtype)
    slots_k = []
    for idx in topk_idx:                  # static python loop over k
        pos_k = jnp.take_along_axis(position, idx[:, None], axis=1)[:, 0]
        keep_k = jnp.take_along_axis(keep, idx[:, None], axis=1)[:, 0]
        slot_k = jnp.where(keep_k, idx * capacity + pos_k, sentinel)
        slots_k.append(slot_k)
        # distinct k never share a live slot (queue positions are unique
        # per expert), so the scatters cannot collide except at sentinel
        slot_token = slot_token.at[slot_k].set(token_ids, mode="drop")
        slot_valid = slot_valid.at[slot_k].set(1, mode="drop")

    expert_in = (jnp.take(xt, slot_token[:n_slots], axis=0)
                 * slot_valid[:n_slots, None])                    # (E*C, D)
    expert_out = _expert_bank(expert_in.reshape(E, capacity, d), layer)

    # combine: each token gathers its k expert rows, weighted by its gate
    flat_out = jnp.concatenate(
        [expert_out.reshape(n_slots, d),
         jnp.zeros((1, d), expert_out.dtype)])    # sentinel row = zeros
    out = jnp.zeros((n_tokens, d), dtype)
    for idx, slot_k in zip(topk_idx, slots_k):
        gate_k = jnp.take_along_axis(gates, idx[:, None], axis=1)
        out = out + gate_k.astype(dtype) * jnp.take(flat_out, slot_k,
                                                    axis=0).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# forward/loss (Llama block with MoE MLP)
# ---------------------------------------------------------------------------

def _block(config: MoEConfig, cos, sin, x, layer: Params):
    h = rms_norm(x, layer["attn_norm"], config.norm_eps)
    x = x + attention_sublayer(h, layer, config, cos, sin)
    x = constrain(x, ("batch", "seq", None))
    h = rms_norm(x, layer["mlp_norm"], config.norm_eps)
    moe_out, aux = moe_mlp(h, layer, config)
    return constrain(x + moe_out, ("batch", "seq", None)), aux


def moe_hidden(params: Params, tokens: jax.Array, config: MoEConfig
               ) -> tuple[jax.Array, jax.Array]:
    """-> (final-normed hidden (B,S,D), total aux loss)."""
    from tony_tpu.models.llama import embed_lookup, rope_tables

    s = tokens.shape[1]
    cos, sin = rope_tables(config, s)
    x = embed_lookup(params["embed"], tokens, config)

    block = partial(_block, config, cos, sin)
    if config.remat:
        block = jax.checkpoint(block, policy=config.checkpoint_policy())

    x, aux_losses = lax.scan(lambda x, layer: block(x, layer), x,
                             params["layers"])
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return x, jnp.sum(aux_losses)


def moe_forward(params: Params, tokens: jax.Array, config: MoEConfig
                ) -> tuple[jax.Array, jax.Array]:
    """-> (logits (B,S,V) f32, total aux loss). bf16 operands with f32
    accumulation on the head matmul, same as the dense model."""
    x, aux = moe_hidden(params, tokens, config)
    logits = jnp.einsum("bsd,dv->bsv", x, params["output"],
                        preferred_element_type=jnp.float32)
    return constrain(logits, ("batch", "seq", "vocab")), aux


def moe_loss(params: Params, batch: dict[str, jax.Array],
             config: MoEConfig) -> jax.Array:
    from tony_tpu.models.llama import _head_loss, unpack_lm_batch

    inputs, targets = unpack_lm_batch(batch)
    x, aux = moe_hidden(params, inputs, config)
    return (_head_loss(x, params, targets, config)
            + config.aux_loss_weight * aux)


# ---------------------------------------------------------------------------
# serving: routing without dropped tokens, one grouped matmul
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RouterSpec:
    """How a layer chooses experts: `top_k` of `n_experts` by sigmoid
    score plus a per-expert bias; the weights are the chosen scores
    themselves (without the bias), divided by their sum + `norm_eps` if
    `norm_topk`, times `scale`."""
    n_experts: int
    top_k: int
    norm_topk: bool = True
    scale: float = 1.0
    norm_eps: float = 1e-6


def route_topk(u: jax.Array, router: jax.Array, bias: jax.Array,
               spec: RouterSpec) -> tuple[jax.Array, jax.Array]:
    """u (T, D) -> (chosen experts (T, k) int32, their weights (T, k)
    float32). The scores are computed in float32 whatever the weights'
    type (`highest`: one bf16 pass would move a score by 1e-2, more than
    the gap between a token's 4th and 5th expert one time in ten); ties go
    to the lower expert index."""
    z = jnp.dot(u.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(z)
    _, chosen = lax.top_k(scores + bias.astype(jnp.float32), spec.top_k)
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if spec.norm_topk:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + spec.norm_eps)
    return chosen.astype(jnp.int32), weights * spec.scale


def group_rows(chosen: jax.Array, valid: jax.Array | None, n_experts: int,
               tile_rows: int) -> dict[str, jax.Array]:
    """Lay the (token, choice) pairs out group by group, each expert's
    group starting at a multiple of `tile_rows` (ops/expert_matmul.py).
    chosen (T, k); valid (T,) bool or None: a token that is not valid
    reaches no expert. Returns `dest` (T, k) the row of each pair (the
    layout's length for a pair that was dropped), `row_token` (rows,) the
    token whose input a row holds (T for a padding row), `tile_expert`
    (rows / tile_rows,), `tiles_used` (1,) and `counts` (E,) the rows each
    expert got. No sort: a pair's rank in its group is a running count."""
    t, k = chosen.shape
    rows = padded_rows(t * k, n_experts, tile_rows)
    flat = chosen.reshape(-1)
    onehot = flat[:, None] == jnp.arange(n_experts, dtype=jnp.int32)[None]
    if valid is not None:
        onehot &= jnp.repeat(valid, k)[:, None]
    running = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    counts = running[-1]
    rank = jnp.take_along_axis(running, flat[:, None], axis=1)[:, 0] - 1
    ends = jnp.cumsum(-(-counts // tile_rows) * tile_rows)
    starts = ends - -(-counts // tile_rows) * tile_rows
    kept = jnp.any(onehot, axis=1)
    dest = jnp.where(kept, starts[flat] + rank, rows)
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.full((rows,), t, jnp.int32).at[dest].set(
        token, mode="drop")
    first_row = jnp.arange(rows // tile_rows, dtype=jnp.int32) * tile_rows
    tile_expert = jnp.minimum(
        jnp.sum(ends[None, :] <= first_row[:, None], axis=1),
        n_experts - 1).astype(jnp.int32)
    return {"dest": dest.reshape(t, k), "row_token": row_token,
            "tile_expert": tile_expert,
            "tiles_used": (ends[-1:] // tile_rows).astype(jnp.int32),
            "counts": counts}


def grouped_expert_mlp(u: jax.Array, layer: jax.Array, experts: Params,
                       spec: RouterSpec, valid: jax.Array | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """The expert MLP of one layer over rows u (T, D): out (T, D) float32
    = sum over a token's k experts of w_e * W2_e(silu(W1_e u) * W3_e u),
    and the rows each expert got (E,) int32. `experts` holds the WHOLE
    stacks `router` (layers, D, E), `expert_bias` (layers, E), `w1`, `w3`
    (layers, E, D, F), `w2` (layers, E, F, D), of which `layer` (scalar
    int32) is read. Rows in float32 are scored as they are and multiplied
    as two halves of the weights' type (ops/expert_matmul.py
    `split_rows`); the gated product between the two matmuls stays
    float32. `valid` (T,) marks the rows that ride: the others reach no
    expert and come out zero."""
    t, d = u.shape
    tile = tile_rows_for(t * spec.top_k, spec.n_experts)
    layer1 = jnp.reshape(layer, (1,)).astype(jnp.int32)
    with jax.named_scope("tony_moe_route"):
        router = lax.dynamic_index_in_dim(experts["router"], layer, 0, False)
        bias = lax.dynamic_index_in_dim(experts["expert_bias"], layer, 0,
                                        False)
        chosen, weights = route_topk(u, router, bias, spec)
        groups = group_rows(chosen, valid, spec.n_experts, tile)
        x = jnp.take(jnp.concatenate([u, jnp.zeros((1, d), u.dtype)]),
                     groups["row_token"], axis=0)
    tiles = (layer1, groups["tile_expert"], groups["tiles_used"])
    h = expert_matmul(*tiles, x, experts["w1"], experts["w3"],
                      tile_rows=tile, out_dtype=jnp.float32)
    y = expert_matmul(*tiles, h, experts["w2"], tile_rows=tile,
                      out_dtype=jnp.float32)
    with jax.named_scope("tony_moe_route"):
        dest = groups["dest"]
        got = jnp.take(y, jnp.minimum(dest, y.shape[0] - 1), axis=0)
        # a dropped pair's row is not its own (and a tile nobody used was
        # never written): masked, not multiplied by zero
        out = jnp.sum(jnp.where((dest < y.shape[0])[..., None],
                                weights[..., None] * got, 0.0), axis=1)
    return out, groups["counts"]
