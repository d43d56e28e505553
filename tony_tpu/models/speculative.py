"""Speculative decoding: draft-model propose, target-model verify.

Single-sequence decode runs one matmul-starved token at a time; a small
draft model proposes `gamma` tokens cheaply and the target verifies ALL
of them in ONE windowed forward (W = gamma+1 positions through the MXU
instead of 1). Greedy-only and LOSSLESS: the emitted stream is exactly
`generate(params, ...)`'s greedy output — the draft only changes how
fast tokens appear, never which tokens. That identity is the test
oracle (tests/test_speculative.py, CPU) and is re-asserted on the real
backend by the multichip dryrun's decode-spec leg (__graft_entry__.py):
the (gamma+1)-wide verify-window matmuls could in principle accumulate
in a different order than single-token decode steps and flip argmax on
near-ties, so exactness is pinned per-backend, not assumed.

TPU-first mechanics (greenfield — the reference is an orchestrator with
no inference code, SURVEY §2.3):
- static shapes end to end: every round drafts exactly `gamma` tokens
  and verifies a fixed (gamma+1)-token window inside `lax.while_loop`;
  per-row acceptance divergence is handled with per-row cache lengths,
  not dynamic shapes.
- caches may hold garbage BEYOND each row's length: the attention mask
  (`col < len`, the window's own rows come from registers) makes stale
  rows invisible and later rounds simply overwrite them — no rollback
  pass.
- the windowed forward is `models/generate.window_logits`, the SAME
  function vanilla decode runs at W=1 (`decode_step`): one attention,
  one row format, one in-place per-row cache write — the two paths
  cannot drift apart. RoPE uses `apply_rope`'s per-batch positions.
- the draft chain deliberately consumes ALL gamma drafted tokens (one
  step more than strictly needed to produce them): that keeps the draft
  cache exactly ONE token behind the target stream in every case, so
  rounds stay uniform with no data-dependent resync window.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from tony_tpu.models.generate import prefill, window_logits
from tony_tpu.models.llama import LlamaConfig, Params


@partial(jax.jit, static_argnames=("config", "draft_config",
                                   "max_new_tokens", "gamma",
                                   "quant_cache", "eos_id"))
def speculative_generate(params: Params, draft_params: Params,
                         config: LlamaConfig, draft_config: LlamaConfig,
                         prompt: jax.Array, max_new_tokens: int,
                         gamma: int = 4, quant_cache: bool = False,
                         eos_id: int | None = None) -> jax.Array:
    """prompt: (B, P) int32 -> (B, max_new_tokens), greedily identical
    to `generate(params, config, prompt, max_new_tokens,
    quant_cache=quant_cache)` — with an int8 cache both paths quantize
    the SAME K/V rows at the same positions, so the identity holds
    exactly, not approximately. The models must share a vocabulary.
    gamma = drafted tokens per round."""
    if config.vocab_size != draft_config.vocab_size:
        raise ValueError("target and draft must share a vocabulary: "
                         f"{config.vocab_size} vs "
                         f"{draft_config.vocab_size}")
    for cfg, who in ((config, "target"), (draft_config, "draft")):
        if not getattr(cfg, "n_experts", 0):
            continue
        from tony_tpu.models.moe import no_drop_capacity_floor
        floor = no_drop_capacity_floor(cfg)
        if cfg.capacity_factor < floor:
            # below no-drop capacity, expert-queue overflow depends on
            # how many tokens each call routes — the verify window
            # routes gamma+1 at once while vanilla decode routes 1, so
            # the two paths drop DIFFERENT tokens and the lossless
            # identity silently breaks
            raise ValueError(
                f"speculative decoding needs the {who} MoE config at "
                f"no-drop capacity (capacity_factor >= n_experts/top_k "
                f"= {floor}); got {cfg.capacity_factor}")
    b, p = prompt.shape
    n = max_new_tokens
    # slack: a round may write gamma+1 rows beyond a row's frozen length
    cache_len = p + n + gamma + 2
    if cache_len > config.max_seq or cache_len > draft_config.max_seq:
        raise ValueError(f"prompt {p} + max_new {n} + gamma {gamma} "
                         f"slack exceeds max_seq")

    t_logits, t_cache = prefill(params, prompt, config, cache_len,
                                quant_cache=quant_cache)
    _, d_cache = prefill(draft_params, prompt, draft_config, cache_len,
                         quant_cache=quant_cache)

    tok0 = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)   # (B,)
    out0 = jnp.zeros((b, n), jnp.int32).at[:, 0].set(tok0)

    # per-row state; `last` = the newest emitted token, which NEITHER
    # model has consumed yet. Invariant at every round boundary:
    # t_len = tokens the target consumed (= p + emitted - 1) and the
    # draft cache holds exactly the same tokens (d_len == t_len).
    state = {
        "t_cache": t_cache, "d_cache": d_cache,
        "len": jnp.full((b,), p, jnp.int32),
        "last": tok0,
        "out": out0,
        "emitted": jnp.ones((b,), jnp.int32),
    }

    def not_done(s):
        return jnp.any(s["emitted"] < n)

    def round_(s):
        live = s["emitted"] < n   # (B,) — frozen rows stop advancing

        # --- draft chain: consume [last, d1..d_{gamma-1}] to produce
        # d1..dgamma, then one extra step consumes dgamma so the draft
        # cache ends exactly one token behind the target stream for ANY
        # acceptance count (stale rows are masked + overwritten later)
        def draft_step(carry, _):
            d_cache, d_len, tok = carry
            lg, d_cache = window_logits(draft_params, draft_config,
                                        d_cache, tok[:, None], d_len)
            nxt = lg[:, 0].argmax(-1).astype(jnp.int32)
            return (d_cache, d_len + jnp.where(live, 1, 0), nxt), nxt

        # gamma+1 steps: consume [last, d1..dgamma] so the draft cache
        # covers every token the target can accept this round; the
        # (gamma+1)-th proposal is produced but never used
        (d_cache, _, _), drafts = lax.scan(
            draft_step, (s["d_cache"], s["len"], s["last"]), None,
            length=gamma + 1)
        drafts = drafts.T[:, :gamma]                    # (B, gamma)

        # --- target: one windowed forward over [last, d1..dgamma]
        window = jnp.concatenate([s["last"][:, None], drafts], axis=1)
        t_logits, t_cache = window_logits(
            params, config, s["t_cache"], window, s["len"])
        greedy = t_logits.argmax(-1).astype(jnp.int32)  # (B, gamma+1)

        # accept the longest draft prefix that matched target-greedy
        match = (drafts == greedy[:, :gamma])
        accepted = jnp.argmin(
            jnp.concatenate([match, jnp.zeros((b, 1), bool)], axis=1),
            axis=1).astype(jnp.int32)                   # (B,) in [0, g]

        # emit accepted+1 target-greedy tokens (bounded by remaining).
        # Gather-select per output slot — NOT a scatter: clipped scatter
        # indices would collide and a masked keep-original duplicate
        # could overwrite the real token (unspecified duplicate order)
        emit = jnp.where(live,
                         jnp.minimum(accepted + 1, n - s["emitted"]), 0)
        off = jnp.arange(n)[None, :] - s["emitted"][:, None]   # (B, n)
        sel = (off >= 0) & (off < emit[:, None])
        gathered = jnp.take_along_axis(greedy,
                                       jnp.clip(off, 0, gamma), axis=1)
        out = jnp.where(sel, gathered, s["out"])

        # the target consumed [last, d1..d_accepted] = accepted+1
        # tokens; the new `last` is its correction/bonus greedy[accepted].
        # adv is clipped exactly like emit so a finishing row's len stays
        # <= p+n-1 and frozen-row window writes can never outrun the
        # cache_len slack (gamma+2) — without the clip a final-round
        # full acceptance would overshoot and rely on XLA's update-slice
        # clamping
        adv = emit
        last = jnp.take_along_axis(greedy, accepted[:, None],
                                   axis=1)[:, 0]
        return {
            "t_cache": t_cache, "d_cache": d_cache,
            "len": s["len"] + adv,
            "last": jnp.where(live, last, s["last"]),
            "out": out,
            "emitted": s["emitted"] + emit,
        }

    state = lax.while_loop(not_done, round_, state)
    out = state["out"]
    if eos_id is not None:
        # vanilla generate LATCHES eos: every token after the first
        # emitted eos_id is forced to eos_id regardless of the model.
        # The loop above keeps emitting target-greedy continuations, so
        # reproducing the latch is pure post-processing — the prefix
        # before the first eos is target-greedy in both paths
        hit = out == eos_id
        first = jnp.argmax(hit, axis=1)
        after = jnp.arange(n)[None, :] > first[:, None]
        out = jnp.where(after & hit.any(axis=1)[:, None], eos_id, out)
    return out
